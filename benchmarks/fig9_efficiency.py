"""Fig. 9: computational efficiency of basis rotation.

(a) wall-clock per step vs baselines (us_per_call column);
(b) basis-update frequency sweep (performance degrades only mildly);
(c) stage-aware vs uniform vs reversed allocation under the same budget;
(d) SPMD schedule comparison — fill-drain vs 1F1B step time on the real
    shard_map runtime (a subprocess on XLA:CPU with forced host devices)."""
from __future__ import annotations

import json
import os
import subprocess
import sys

sys.path.insert(0, "src")

from benchmarks.common import cpu_child_env, tail, train_curve

SPMD_TIMING_SCRIPT = r"""
import sys
sys.path.insert(0, "src")
from repro.launch.devices import ensure_host_devices
ensure_host_devices(%(stages)d)
import json, time
import jax
from repro.configs.base import ModelConfig, AttentionConfig, BlockSpec, OptimizerConfig
from repro.data import batches
from repro.engine import LoopConfig, SpmdEngine, run_loop
from repro.launch.mesh import auto_axes

cfg = ModelConfig(num_layers=%(stages)d, d_model=32, d_ff=64, vocab_size=64,
                  max_seq_len=64,
                  attention=AttentionConfig(num_heads=2, num_kv_heads=2, head_dim=16),
                  pattern=(BlockSpec("attn", "dense"),), scan_layers=False)
K, M, steps = %(stages)d, %(microbatches)d, %(steps)d
mesh = jax.make_mesh((K, 1), ("stage", "data"), axis_types=auto_axes(2))
rows = []
for sched in %(schedules)s:
    ocfg = OptimizerConfig(name="adam", learning_rate=1e-3, total_steps=steps,
                           schedule="constant")
    engine = SpmdEngine(cfg, ocfg, num_stages=K, num_microbatches=M, mesh=mesh,
                        schedule=sched)
    state = engine.init_state(key=jax.random.PRNGKey(0))
    data = batches(cfg, M * 2, 16, seed=0)
    state, _ = run_loop(engine, data, LoopConfig(steps=1), state=state)  # compile
    t0 = time.perf_counter()
    state, losses = run_loop(engine, data, LoopConfig(steps=steps), state=state,
                             start_step=1)
    dt = time.perf_counter() - t0
    rows.append({"schedule": sched, "us_per_step": 1e6 * dt / (steps - 1),
                 "final": losses[-1]})
print(json.dumps(rows))
"""


def spmd_schedule_rows(quick: bool = True, schedules=("fill_drain", "1f1b")):
    """Time the shard_map runtime under each schedule (fig9d)."""
    stages, microbatches = (4, 8) if quick else (8, 16)
    steps = 6 if quick else 20
    script = SPMD_TIMING_SCRIPT % {
        "stages": stages, "microbatches": microbatches, "steps": steps,
        "schedules": repr(tuple(schedules)),
    }
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=cpu_child_env(), timeout=1800,
    )
    if out.returncode != 0:
        raise RuntimeError(f"spmd timing subprocess failed: {out.stderr[-2000:]}")
    rows = []
    for r in json.loads(out.stdout.strip().splitlines()[-1]):
        rows.append({
            "name": f"fig9d/spmd_{r['schedule']}",
            "us_per_call": r["us_per_step"],
            "derived": (f"K={stages};M={microbatches};"
                        f"final={r['final']:.3f};platform=cpu"),
        })
    return rows


def run(quick: bool = True):
    steps = 150 if quick else 400
    rows = []
    # (a) GPU-hours proxy: us/step at P=8
    for m in ("adam", "nesterov", "basis_rotation"):
        out = train_curve(m, stages=8, steps=steps)
        rows.append({"name": f"fig9a/{m}", "us_per_call": out["us_per_step"],
                     "derived": f"final={tail(out['losses']):.3f}"})
    # (b) frequency sweep
    for freq in (2, 10, 50):
        out = train_curve("basis_rotation", stages=8, steps=steps, rotation_freq=freq)
        rows.append({"name": f"fig9b/freq{freq}", "us_per_call": out["us_per_step"],
                     "derived": f"final={tail(out['losses']):.3f}"})
    # (c) stage-aware allocation (+ reversed ablation, Fig. 17)
    uni = train_curve("basis_rotation", stages=8, steps=steps, rotation_freq=10)
    sa = train_curve("basis_rotation", stages=8, steps=steps, rotation_freq=10,
                     stage_aware=True)
    rev = train_curve("basis_rotation", stages=8, steps=steps, rotation_freq=10,
                      stage_aware=True, stage_aware_reversed=True)
    rows.append({"name": "fig9c/uniform", "us_per_call": uni["us_per_step"],
                 "derived": f"final={tail(uni['losses']):.3f}"})
    rows.append({"name": "fig9c/stage_aware", "us_per_call": sa["us_per_step"],
                 "derived": f"final={tail(sa['losses']):.3f}"})
    rows.append({"name": "fig9c/reversed", "us_per_call": rev["us_per_step"],
                 "derived": f"final={tail(rev['losses']):.3f}"})
    # (d) SPMD runtime: step-time of fill-drain vs 1F1B on forced host devices
    rows.extend(spmd_schedule_rows(quick=quick))
    return rows


if __name__ == "__main__":
    import argparse

    from benchmarks.common import emit

    ap = argparse.ArgumentParser()
    ap.add_argument("--spmd-smoke", action="store_true",
                    help="only the 1F1B schedule point at tiny shapes (CI)")
    args = ap.parse_args()
    if args.spmd_smoke:
        emit(spmd_schedule_rows(quick=True, schedules=("1f1b",)))
    else:
        emit(run())
