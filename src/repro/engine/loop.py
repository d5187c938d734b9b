"""The single training loop shared by every driver.

Previously the history / stale-params bookkeeping, checkpoint-resume,
incremental-JSON metrics and logging lived in near-identical copies inside
`launch/train.py`, `pipeline/simulate.py`, the benchmarks and the examples.
They now live here once, driving any `PipelineEngine` backend.

    engine = SimEngine(cfg, opt, ...)            # or SpmdEngine(...)
    state, losses = run_loop(engine, data_iter, LoopConfig(steps=300))

Checkpoint layout is unchanged from the pre-engine driver ((params,
opt_state) + step in the manifest), so old checkpoints resume under the loop.

Host I/O stays off the step critical path (DESIGN.md §11):

* the per-step loss is NOT fetched to host every iteration — device scalars
  accumulate in a pending list and are converted in one batch at the
  log/metrics cadence, so the loop never forces a device sync per step
  (this alone helps both backends, donation or not);
* checkpoints are snapshotted to host synchronously (`engine.checkpoint_job`
  — cheap device->host copies that must precede the next donated step) but
  WRITTEN on a single background writer thread, as are metrics files.
  One serial writer per process keeps the multi-controller barrier sequence
  inside checkpoint jobs globally ordered. ``async_io=False`` runs every
  job inline (bit-identical output either way — the writer is drained
  before `run_loop` returns, and any writer exception re-raises on the
  loop thread).

Under `jax.profiler`, each iteration is a `StepTraceAnnotation("train",
step_num=t)` (TensorBoard's step-time view reads it) and each checkpoint
snapshot a ``loop.ckpt`` span.
"""
from __future__ import annotations

import json
import os
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax

from repro.engine.base import EngineState, PipelineEngine


@dataclass
class LoopConfig:
    steps: int
    log_every: int = 0
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 0
    out_path: Optional[str] = None
    # constant metadata merged into the JSON metrics file (arch, optimizer...)
    out_meta: Dict[str, Any] = field(default_factory=dict)
    # run checkpoint/metrics writes on the background writer thread; flip
    # off to force every write inline on the loop thread (same bytes on
    # disk — the async/sync parity test pins this)
    async_io: bool = True


class _AsyncWriter:
    """One serial background writer: jobs run strictly in submission order.

    Serial ordering is load-bearing twice over: metrics flushes must never
    overtake the checkpoint they were batched with (a metrics file lagging
    its checkpoint forfeits the pre-resume series at merge time), and in
    multi-controller runs the checkpoint jobs contain `barrier` calls whose
    names must hit the rendezvous in the same order on every process.

    A job exception is captured and re-raised on the loop thread at the
    next submit/close — a failed checkpoint must fail the run, not
    disappear into a daemon thread.
    """

    def __init__(self) -> None:
        self._q: "queue.Queue[Optional[Callable[[], None]]]" = queue.Queue()
        self._exc: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._drain, name="repro-io-writer", daemon=True
        )
        self._thread.start()

    def _drain(self) -> None:
        while True:
            job = self._q.get()
            if job is None:
                return
            if self._exc is not None:
                continue  # poisoned: drop remaining jobs, surface the error
            try:
                job()
            except BaseException as e:  # noqa: BLE001 — re-raised on loop thread
                self._exc = e

    def _check(self) -> None:
        if self._exc is not None:
            exc, self._exc = self._exc, None
            raise exc

    def submit(self, job: Callable[[], None]) -> None:
        self._check()
        self._q.put(job)

    def close(self) -> None:
        """Drain everything, stop the thread, re-raise any job failure."""
        self._q.put(None)
        self._thread.join()
        self._check()


def resume_if_present(
    engine: PipelineEngine,
    state: EngineState,
    ckpt_dir: Optional[str],
    data_iter: Optional[Iterator[Dict]] = None,
) -> Tuple[EngineState, int]:
    """Replace `state` with the latest checkpoint under `ckpt_dir`, if any.

    Pass the run's `data_iter` to fast-forward it past the `start_step`
    batches the interrupted run already consumed — without this a resumed
    run replays batches 0..start_step and diverges from the uninterrupted
    fixed-seed curve it is supposed to continue.
    """
    if not ckpt_dir or not os.path.exists(os.path.join(ckpt_dir, "manifest.json")):
        return state, 0
    from repro.checkpoint import load_checkpoint

    tree, step, _ = load_checkpoint(ckpt_dir)
    if data_iter is not None:
        for _ in range(step):
            next(data_iter)
    return engine.load_state(tree), step


def _read_metrics_prefix(cfg: LoopConfig, start_step: int) -> Tuple[List[float], int]:
    """Losses for absolute steps [prev_start, start_step) from an existing
    metrics file, so a resumed run keeps the full absolute-step series.

    Returns (prefix, prefix_start); falls back to an empty prefix anchored at
    `start_step` when there is no usable file (then `steps_done` still counts
    absolute steps but the series only covers the post-resume segment).
    """
    if not (cfg.out_path and start_step and os.path.exists(cfg.out_path)):
        return [], start_step
    try:
        with open(cfg.out_path) as f:
            prev = json.load(f)
        prev_losses = list(prev.get("losses", []))
        prev_start = int(prev.get("start_step", 0))
    except (ValueError, OSError, TypeError):
        return [], start_step
    need = start_step - prev_start
    if need < 0 or len(prev_losses) < need:
        return [], start_step  # gap: the old file doesn't reach start_step
    return prev_losses[:need], prev_start


def _write_metrics(
    cfg: LoopConfig, losses: List[float], steps_done: int, start_step: int
) -> None:
    os.makedirs(os.path.dirname(cfg.out_path) or ".", exist_ok=True)
    # atomic replace: a process killed mid-write (pod loss) must never leave
    # a truncated JSON — a later resume reads this file to keep the full
    # absolute-step series
    tmp = cfg.out_path + ".tmp"
    with open(tmp, "w") as f:  # incremental: survives interruption
        # losses[i] is the loss at absolute step start_step + i; on resume the
        # caller merges the pre-resume series so this covers the whole run
        json.dump({**cfg.out_meta, "steps_done": steps_done,
                   "start_step": start_step, "losses": losses}, f)
    os.replace(tmp, cfg.out_path)


def run_loop(
    engine: PipelineEngine,
    data_iter: Iterator[Dict],
    cfg: LoopConfig,
    state: Optional[EngineState] = None,
    start_step: int = 0,
    key: Any = None,
) -> Tuple[EngineState, List[float]]:
    """Run `cfg.steps` engine steps (from `start_step` when resuming).

    Multi-controller runs drive this loop on EVERY process in lock-step:
    all processes step the engine and save checkpoints (each flushes its
    own shard files), but stdout logging and the metrics JSON are
    process-0-only — non-main processes must never race on the metrics
    file the main process owns. The background writer exists on every
    process (checkpoint jobs carry barriers), metrics jobs only on main.
    """
    from repro.launch.distributed import is_main

    main = is_main()
    if state is None:
        state = engine.init_state(key=key)
    prefix, prefix_start = (
        _read_metrics_prefix(cfg, start_step) if main else ([], start_step)
    )
    losses: List[float] = []  # host floats, filled at flush cadence
    pending: List[Any] = []  # device scalars not yet fetched

    def flush_losses() -> None:
        # one deferred host sync for the whole pending window — float()
        # blocks on the newest value, by which time the rest are ready
        losses.extend(float(x) for x in pending)
        pending.clear()

    writer = _AsyncWriter() if cfg.async_io else None

    def submit(job: Callable[[], None]) -> None:
        if writer is None:
            job()
        else:
            writer.submit(job)

    try:
        t0 = time.time()
        for t in range(start_step, cfg.steps):
            with jax.profiler.StepTraceAnnotation("train", step_num=t):
                batch = next(data_iter)
                state, loss, metrics = engine.step(state, batch, t)
                pending.append(loss)
                if main and cfg.log_every and t % cfg.log_every == 0:
                    flush_losses()
                    extra = (
                        f"  ce {float(metrics['ce']):.4f}" if "ce" in metrics else ""
                    )
                    print(f"step {t:5d}  loss {losses[-1]:.4f}{extra}"
                          f"  ({time.time() - t0:.1f}s)")
                wrote_ckpt = (
                    cfg.ckpt_dir and cfg.ckpt_every and (t + 1) % cfg.ckpt_every == 0
                )
                if wrote_ckpt:
                    # the engine owns the on-disk format (SpmdEngine writes one
                    # arrays file per stage shard instead of gathering to host,
                    # and in multi-process runs each process writes only its own
                    # shards); the snapshot half runs here, the write half on
                    # the writer thread
                    with jax.profiler.TraceAnnotation("loop.ckpt"):
                        job = engine.checkpoint_job(cfg.ckpt_dir, state, step=t + 1)
                    submit(job)
                # metrics are flushed at every checkpoint too, so the metrics
                # file never lags a checkpoint a later resume will restart from
                # (a lagging file would forfeit its pre-resume series at merge
                # time; the serial writer preserves ckpt-then-metrics order)
                if main and cfg.out_path and (
                    wrote_ckpt or (t + 1) % max(cfg.log_every, 1) == 0
                ):
                    flush_losses()
                    snapshot = prefix + losses
                    done = t + 1
                    submit(lambda s=snapshot, d=done: _write_metrics(
                        cfg, s, d, prefix_start
                    ))
        flush_losses()
        if cfg.ckpt_dir:
            with jax.profiler.TraceAnnotation("loop.ckpt"):
                job = engine.checkpoint_job(cfg.ckpt_dir, state, step=cfg.steps)
            submit(job)
        if main and cfg.out_path:
            final = prefix + losses
            submit(lambda: _write_metrics(cfg, final, cfg.steps, prefix_start))
    finally:
        if writer is not None:
            writer.close()
    return state, losses
