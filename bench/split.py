"""Where one cell's step time goes, by the program's scopes and spans.

    python3 bench/split.py --workload <cell> --seed <n> [--seconds 30]
        [--out split.json]

Runs the cell as `bench/run.py` does (the harness's set-up, an untraced
window of `--seconds`, then the traced window of `trace_periods` refresh
periods, read into the harness's Context) and prints, by hand, more than a
result line holds. One JSON object:

* `device`: `bench.scopes.device_split`, and the top ops of each category;
* `scopes`: for each of the program's scopes, `bench.scopes.seconds_under`
  over all, plain and refresh steps;
* `host`: `bench.scopes.host_split`;
* `readers`: the cell's per-layer metrics on the same trace;
* `untraced`: the same harness numbers from the untraced window;
* `clock`: whether each step program execution starts after the start of
  the `engine.launch` span that launched it.

Exits 3, printing nothing, when JAX finds no TPU.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

TOP = 8


def top_ops(ctx, n=TOP):
    """Per category, the ops with the most innermost device time, in ms per
    step, averaged over chips."""
    from bench import scopes

    acc = defaultdict(lambda: defaultdict(float))
    for rows in ctx.step_seconds or []:
        for secs in rows:
            for label, s in secs.items():
                inst = scopes.instruction(label)
                op = ctx.op_names.get(inst, "")
                acc[scopes.category(op)][f"{inst} {op}"] += s
    scale = 1e3 / (len(ctx.steps) * max(len(ctx.trace.chips), 1))
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
    _, ctx = harness.traced_context(p, cell, peaks, flops)
    steps, spans = ctx.steps, ctx.program_spans
    leads = [x for c in ctx.trace.chips for x in launch_leads(spans, c, len(steps))]
    result = {
        "workload": cell.name, "seed": args.seed, "setup_s": setup_s,
        "device": {**(ctx.device_split or {}), "top_ops": top_ops(ctx)},
        "scopes": {s: {w: scopes.seconds_under(ctx, s, w) for w in scopes.WHICH}
                   for s in scopes.SCOPES},
        "host": ctx.host_split,
        "readers": {m["name"]: spec.metric_reader(m["name"])(ctx) for m in cell.per_layer},
        "untraced": untraced,
        "clock": {"steps": len(steps),
                  "launch_spans": sum(s.name == "engine.launch" for s in spans),
                  "min_lead_us": min(leads) * 1e-3 if leads else None,
                  "all_after_launch": bool(leads) and min(leads) >= 0},
        "hlo_instructions_named": len(ctx.op_names),
    }
    line = json.dumps(result)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
