"""Where one cell's step time goes, by the program's scopes and spans.

    python3 bench/split.py --workload <cell> --seed <n> [--seconds 30]
        [--out split.json]

Runs the cell as `bench/run.py` does (the harness's set-up, an untraced
window of `--seconds`, then the traced window of `trace_periods` refresh
periods) and keeps what the result line of `bench/run.py --trace 1` cannot
hold yet: the compiled step's `op_name` map, read through
`engine.executable(...)`, and the program's `engine.*`/`loop.*` host spans
(bench.scopes). Prints one JSON object:

* `device`: fwd/bwd/opt/rotate/refresh device ms and the unscoped share
  (`bench.scopes.device_split`), and the top ops of each category;
* `host`: ms per step in `engine.args` + `engine.lookup`, in
  `engine.launch`, and `engine.compile` spans per step
  (`bench.scopes.host_split`);
* `readers`: the cell's accepted per-layer metrics on the same trace;
* `untraced`: the same harness numbers from the untraced window;
* `clock`: whether each step program execution starts after the start of
  the `engine.launch` span that launched it.

Exits 3, printing nothing, when JAX finds no TPU.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

TOP = 8


def top_ops(chips, steps, names, n=TOP):
    """Per category, the ops with the most innermost device time, in ms per
    step, averaged over chips."""
    from bench import scopes

    acc = defaultdict(lambda: defaultdict(float))
    for chip in chips:
        for m in scopes.step_executions(chip, len(steps)) or []:
            for label, s in scopes.innermost_seconds(chip.ops, m.start, m.end).items():
                inst = scopes.instruction(label)
                op = names.get(inst, "")
                acc[scopes.category(op)][f"{inst} {op}"] += s
    scale = 1e3 / (len(steps) * max(len(chips), 1))
    return {cat: [[k, v * scale] for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:n]]
            for cat, ops in acc.items()}


def launch_leads(spans, chip, n):
    """Per step, ns from the start of the i-th `engine.launch` span to the
    start of the i-th step program execution (negative: the device started
    first, which shared clocks rule out)."""
    from bench import scopes

    launches = [o for o in spans if o.name == "engine.launch"]
    runs = scopes.step_executions(chip, n) or []
    return [m.start - s.start for s, m in zip(launches[-n:], runs)]


def main() -> int:
    from bench import counts, harness, scopes, spec
    from bench import trace as tr

    ap = argparse.ArgumentParser(prog="bench/split.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    harness.configure()
    harness.use_cache()
    try:
        devices = harness.chips(cell.chips)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    p = harness.prepare(cell, args.seed)
    setup_s = time.perf_counter() - T_START
    o, tf = cell.options, cell.traffic
    period = int(o["rotation_freq"])
    tokens = int(tf["batch"]) * int(tf["seq_len"])
    flops = counts.model_flops_per_token(cell.config, int(tf["seq_len"])) * tokens
    peaks = counts.chip_peaks(devices[0].device_kind)

    p.timed.dispatch_s.clear()
    _, elapsed, n = harness.measure(p, args.seconds, period)
    untraced = {"tokens_per_s": n * tokens / elapsed, "step_wall_ms": 1e3 * elapsed / n,
                "step_dispatch_ms": 1e3 * sum(p.timed.dispatch_s) / len(p.timed.dispatch_s),
                "steps": n}

    p.timed.dispatch_s.clear()
    log_dir = tempfile.mkdtemp(prefix="bench_split_")
    try:
        t0 = time.perf_counter()
        _, steps = harness.traced(p, int(o["trace_periods"]), period, log_dir)
        traced_wall = time.perf_counter() - t0
        path = tr.find_xplane(log_dir)
        reduced = tr.load(path)
        spans = scopes.program_spans(path)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    text = p.engine.executable(p.state, p.pool[0], p.step).as_text()
    names = scopes.op_names(text)

    ctx = harness.Context(reduced, steps, list(p.timed.dispatch_s), cell, peaks, flops)
    readers = {m["name"]: spec.metric_reader(m["name"])(ctx) for m in cell.per_layer}
    leads = [x for c in reduced.chips for x in launch_leads(spans, c, len(steps))]
    result = {
        "workload": cell.name, "seed": args.seed, "setup_s": setup_s,
        "device": {**(scopes.device_split(reduced.chips, steps, names, period) or {}),
                   "top_ops": top_ops(reduced.chips, steps, names)},
        "host": scopes.host_split(spans, reduced.window, len(steps)),
        "readers": readers,
        "traced": {"step_wall_ms": 1e3 * traced_wall / len(steps), "steps": len(steps)},
        "untraced": untraced,
        "clock": {"steps": len(steps),
                  "launch_spans": sum(s.name == "engine.launch" for s in spans),
                  "min_lead_us": min(leads) * 1e-3 if leads else None,
                  "all_after_launch": bool(leads) and min(leads) >= 0},
        "hlo_instructions_named": len(names),
    }
    line = json.dumps(result)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
