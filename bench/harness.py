"""One run of one cell: set-up, a measured (or traced) window, the check.

Set-up builds the engine once, makes the weights and the traffic pool on the
device from the seed, and drives the engine through its first
`CHECK_STEPS` steps with the window's own call (`repro.engine.loop.run_loop`)
and feed. Those steps warm up every program the window runs, a basis
refresh among them (step 0). They also give the program's readings for the
check. The window then drives the same engine and state for a whole number
of refresh periods, until at least `--seconds` have passed. Its clock stops
when the last step's loss is on the host. After the window, once the peak
memory has been read and the program's state is freed, the plain reference
redoes the first steps from the same seed and batches.
"""
from __future__ import annotations

import gc
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

from bench import spec

CHECK_STEPS = 3
CACHE_DIR = spec.ROOT / ".bench_cache" / "jax"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
RETRIEVE_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
HIT_EVENT = "/jax/compilation_cache/cache_hits"
MISS_EVENT = "/jax/compilation_cache/cache_misses"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def use_cache(cache_dir: Path = CACHE_DIR) -> None:
    """JAX's persistent cache at a fixed path inside the checkout, whatever
    JAX_COMPILATION_CACHE_DIR says: only the checkout outlasts a run."""
    import jax

    cache_dir.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def configure() -> None:
    """Every cell runs float32 matmuls at `highest`, the precision its
    configuration states: at the TPU's default they take one bfloat16 pass.
    The program's precision policy does not set it (PERF.md, Open questions),
    so the benchmark does, once, for all cells."""
    import jax

    jax.config.update("jax_default_matmul_precision", "highest")


def chips(n: int) -> List[Any]:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices only")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


class CompileMonitor:
    """Counts compiles, traces and cache traffic; set `in_window` around the
    window. A compile in the window fails the run. A trace there (a jit
    specialised anew, its compile served from memory) is reported."""

    def __init__(self):
        import jax

        self.in_window = False
        self.compile_s = self.retrieve_s = 0.0
        self.compiles = self.hits = self.misses = 0
        self.window_compiles = self.window_traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_: Any) -> None:
        if event == COMPILE_EVENT:
            self.compile_s += secs
            self.compiles += 1
            self.window_compiles += self.in_window
        elif event == TRACE_EVENT:
            self.window_traces += self.in_window
        elif event == RETRIEVE_EVENT:
            self.retrieve_s += secs

    def _event(self, event: str, **_: Any) -> None:
        if event == HIT_EVENT:
            self.hits += 1
        elif event == MISS_EVENT:
            self.misses += 1


class TimedEngine:
    """The engine as the loop sees it, with the harness's `dispatch` span
    and host clock around each `step` call."""

    def __init__(self, engine):
        self._engine = engine
        self.dispatch_s: List[float] = []

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def step(self, state, batch, t):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("dispatch"):
            out = self._engine.step(state, batch, t)
        self.dispatch_s.append(time.perf_counter() - t0)
        return out


def seed_key(seed: int):
    """A key from any whole number: the low 31 bits seed it, the rest are
    folded in, so seeds past 2**31 stay distinct."""
    import jax

    if seed < 0:
        raise ValueError("--seed must be a whole number >= 0")
    key = jax.random.PRNGKey(seed % 2**31)
    return jax.random.fold_in(key, seed // 2**31)


@dataclass
class Prepared:
    """The engine after set-up, with the program's readings of the first
    steps. `step` is the next step index."""

    engine: Any
    timed: TimedEngine
    state: Any
    feed: Any
    pool: List[Dict[str, Any]]
    model_key: Any
    program: Dict[str, Any]
    step: int
    split: Dict[str, float] = field(default_factory=dict)


def prepare(cell: spec.Cell, seed: int, wrap_step=None) -> Prepared:
    """Set-up: build, pool, and the first CHECK_STEPS steps through the
    window's own call. `wrap_step` plants a fault (tests only)."""
    import jax

    from bench import traffic
    from repro.engine import LoopConfig, run_loop

    key = seed_key(seed)
    model_key, data_key = jax.random.fold_in(key, 0), jax.random.fold_in(key, 1)
    build = spec.builder(cell)
    split = {}
    t = time.perf_counter()
    engine, state = build.build(cell, model_key)
    jax.block_until_ready(state.params)
    split["init_s"] = time.perf_counter() - t
    t = time.perf_counter()
    pool = spec.generator(cell.traffic).make_pool(data_key, cell.traffic,
                                                  cell.config["vocab_size"])
    jax.block_until_ready(pool)
    split["pool_s"] = time.perf_counter() - t
    t = time.perf_counter()
    timed = TimedEngine(engine)
    if wrap_step is not None:
        timed._engine = wrap_step(engine)
    feed = traffic.Feed(pool, span=jax.profiler.TraceAnnotation)
    state, l0 = run_loop(timed, feed, LoopConfig(steps=1), state=state)
    grad = build.first_gradient_norms(engine, state)
    state, l12 = run_loop(timed, feed, LoopConfig(steps=CHECK_STEPS), state=state,
                          start_step=1)
    change = build.change_norms(engine, state, model_key)
    split["warmup_s"] = time.perf_counter() - t
    program = {"loss": l0 + l12, "grad": grad, "change": change}
    return Prepared(engine, timed, state, feed, pool, model_key, program,
                    CHECK_STEPS, split)


def _periods(p: Prepared, n: int, period: int) -> List[float]:
    from repro.engine import LoopConfig, run_loop

    end = p.step + n * period
    p.state, losses = run_loop(p.timed, p.feed, LoopConfig(steps=end), state=p.state,
                               start_step=p.step)
    p.step = end
    return losses


def measure(p: Prepared, seconds: float, period: int):
    """Whole refresh periods until at least `seconds` have passed."""
    first = p.step
    t0 = time.perf_counter()
    losses = _periods(p, 1, period)
    elapsed = time.perf_counter() - t0
    while elapsed < seconds:
        per_period = elapsed * period / (p.step - first)
        losses += _periods(p, max(1, math.ceil((seconds - elapsed) / per_period)), period)
        elapsed = time.perf_counter() - t0
    return losses, elapsed, p.step - first


def traced(p: Prepared, periods: int, period: int, log_dir: str):
    import jax

    first = p.step
    jax.profiler.start_trace(log_dir)
    try:
        with jax.profiler.TraceAnnotation("window"):
            with jax.profiler.TraceAnnotation("loop"):
                losses = _periods(p, periods, period)
    finally:
        jax.profiler.stop_trace()
    return losses, list(range(first, p.step))


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


@dataclass
class Context:
    """What a per-layer metric's `read(ctx)` gets."""

    trace: Any  # bench.trace.Reduced
    steps: List[int]  # the step indices in the traced window
    dispatch_s: List[float]  # host seconds of each engine.step call there
    cell: spec.Cell
    peaks: Dict[str, Any]
    flops_per_step: float  # model FLOPs of one step (bench.counts)


def per_layer(cell: spec.Cell, ctx: Context) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in cell.per_layer:
        v = spec.metric_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run(argv: List[str], t_start: float, require_chip: bool = True,
        bench_dir: Path = spec.BENCH_DIR, benchmark: Optional[Path] = None,
        wrap_step=None) -> Dict[str, Any]:
    """One run; returns the result line's object. Raises NoChip first."""
    import argparse

    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload, bench_dir, benchmark)

    import jax

    from bench import check, counts
    from bench import trace as tr

    configure()
    if require_chip:
        use_cache()
        devices = chips(cell.chips)
    else:
        devices = jax.devices()[: cell.chips]
    monitor = CompileMonitor()
    p = prepare(cell, args.seed, wrap_step)
    setup_s = time.perf_counter() - t_start
    split = {"compile_s": monitor.compile_s, "cache_load_s": monitor.retrieve_s, **p.split}
    print(json.dumps({"setup": split, "compiles": monitor.compiles,
                      "cache_hits": monitor.hits, "cache_misses": monitor.misses,
                      "from_cache": monitor.misses == 0 and monitor.hits > 0}),
          file=sys.stderr, flush=True)

    o, tf = cell.options, cell.traffic
    period = int(o["rotation_freq"])
    tokens_per_step = int(tf["batch"]) * int(tf["seq_len"])
    flops_per_step = counts.model_flops_per_token(cell.config, int(tf["seq_len"])) * tokens_per_step
    kind = devices[0].device_kind
    device: Dict[str, Any] = {"platform": devices[0].platform, "kind": kind,
                              "count": len(devices)}
    result: Dict[str, Any] = {}
    monitor.in_window = True
    p.timed.dispatch_s.clear()
    if args.trace:
        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            losses, steps = traced(p, int(o["trace_periods"]), period, log_dir)
            monitor.in_window = False
            reduced = tr.load(tr.find_xplane(log_dir))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        busy = [tr.total(tr.busy(c)) * 1e-9 for c in reduced.chips]
        device["busy_s"] = sum(busy) / max(len(busy), 1)
        device["window_s"] = reduced.window_s
        ctx = Context(reduced, steps, list(p.timed.dispatch_s), cell,
                      counts.chip_peaks(kind) if require_chip else {}, flops_per_step)
        metrics = per_layer(cell, ctx)
        result["breakdown"] = tr.breakdown(reduced)
        attempted = len(steps)
    else:
        losses, elapsed, n = measure(p, args.seconds, period)
        monitor.in_window = False
        tps = n * tokens_per_step / elapsed
        peak = memory_peak(devices)
        values = {"tokens_per_s": tps, "setup_s": setup_s, "hbm_peak_gb": peak / 1e9}
        if require_chip:
            values["mfu"] = (100.0 * tps * flops_per_step / tokens_per_step
                             / (len(devices) * counts.chip_peaks(kind)["bf16_flops_per_s"]))
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {k: {"value": v, "unit": units[k]} for k, v in values.items() if k in units}
        attempted = n
    device["memory_peak_bytes"] = memory_peak(devices)
    failed = sum(1 for x in losses if not math.isfinite(x))
    print(json.dumps({"window_compiles": monitor.window_compiles,
                      "window_traces": monitor.window_traces}), file=sys.stderr, flush=True)

    batches = [p.pool[i] for i in range(CHECK_STEPS)]
    program, model_key = p.program, p.model_key
    del p
    gc.collect()
    ref = spec.reference_module(cell.config).readings(
        model_key, cell.config, cell.options, batches, mode="f32", steps=CHECK_STEPS)
    values = check.readings(program, ref)
    values["window_compiles"] = float(monitor.window_compiles)
    correct, checks = check.judge(values, {**o["limits"], "window_compiles": 0.0})
    correct = correct and failed == 0
    for name, c in checks.items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    result.update({"correct": correct, "attempted": attempted, "failed": failed,
                   "metrics": metrics, "device": device, "checks": checks})
    order = ["correct", "attempted", "failed", "metrics", "device", "breakdown", "checks"]
    return {k: result[k] for k in order if k in result}
