"""Fig. 17 (Appendix I): stage-aware basis-refresh allocation vs uniform vs
the reversed ablation, at the same total refresh budget — runnable on either
backend.

``--backend sim`` (default) runs the virtual-stage simulation; ``--backend
spmd`` runs the same three allocations on the shard_map pipeline runtime
(a subprocess on XLA:CPU with forced host devices), where the per-stage
periods live inside one stacked ``(K, per, m, n)`` leaf via the vectorized
refresh mask.

The sim sweep is 2-D: each allocation runs at every data delay in
``DATA_DELAYS`` (0 = pipeline staleness only; D > 0 composes the uniform
staleness of a D-step deferred cross-replica reduction onto every leaf, the
async data axis). The stage-aware allocation renormalises its refresh
budget over the TOTAL per-leaf delay tau + D, so the sweep shows whether
its advantage over uniform survives when the data axis goes asynchronous.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

sys.path.insert(0, "src")

from benchmarks.common import cpu_child_env, tail, train_curve

ALLOCATIONS = (
    ("uniform", {}),
    ("stage_aware", {"stage_aware": True}),
    ("reversed", {"stage_aware": True, "stage_aware_reversed": True}),
)

SPMD_SCRIPT = r"""
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
K, M, steps = %(stages)d, %(stages)d, %(steps)d
mesh = jax.make_mesh((K, 1), ("stage", "data"), axis_types=auto_axes(2))
rows = []
for label, kw in %(allocs)s:
    ocfg = OptimizerConfig(name="basis_rotation", learning_rate=3e-3,
                           total_steps=steps, rotation_freq=5,
                           schedule="constant", **kw)
    engine = SpmdEngine(cfg, ocfg, num_stages=K, num_microbatches=M, mesh=mesh)
    state = engine.init_state(key=jax.random.PRNGKey(0))
    data = batches(cfg, M * 2, 16, seed=0)
    state, first = run_loop(engine, data, LoopConfig(steps=1), state=state)  # compile
    t0 = time.perf_counter()
    state, losses = run_loop(engine, data, LoopConfig(steps=steps), state=state,
                             start_step=1)
    dt = time.perf_counter() - t0
    losses = first + losses
    rows.append({"label": label, "us_per_step": 1e6 * dt / (steps - 1),
                 "final": sum(losses[-5:]) / 5})
print(json.dumps(rows))
"""


def spmd_rows(quick: bool = True):
    stages = 4 if quick else 8
    steps = 10 if quick else 120
    script = SPMD_SCRIPT % {
        "stages": stages, "steps": steps, "allocs": repr(ALLOCATIONS),
    }
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=cpu_child_env(), timeout=1800,
    )
    if out.returncode != 0:
        raise RuntimeError(f"fig17 spmd subprocess failed: {out.stderr[-2000:]}")
    rows = []
    for r in json.loads(out.stdout.strip().splitlines()[-1]):
        rows.append({
            "name": f"fig17/spmd_{r['label']}",
            "us_per_call": r["us_per_step"],
            "derived": f"K={stages};final={r['final']:.3f};platform=cpu",
        })
    return rows


# second sweep axis: data-axis staleness of the deferred reduction
DATA_DELAYS = (0, 1, 2)


def sim_rows(quick: bool = True, smoke: bool = False):
    stages, steps = (4, 20) if smoke else (8, 120 if quick else 400)
    delays = DATA_DELAYS[:2] if smoke else DATA_DELAYS
    rows = []
    for data_delay in delays:
        for label, kw in ALLOCATIONS:
            out = train_curve("basis_rotation", stages=stages, steps=steps,
                              rotation_freq=10, data_delay=data_delay, **kw)
            # D=0 keeps the original row names so the committed BENCH
            # baselines and any trend tooling keep matching
            suffix = f"_dd{data_delay}" if data_delay else ""
            rows.append({"name": f"fig17/sim_{label}{suffix}",
                         "us_per_call": out["us_per_step"],
                         "derived": (f"data_delay={data_delay};"
                                     f"final={tail(out['losses']):.3f}")})
    return rows


def run(quick: bool = True):
    return sim_rows(quick=quick)


if __name__ == "__main__":
    import argparse

    from benchmarks.common import emit

    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="sim", choices=["sim", "spmd"])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes / few steps (CI)")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    if args.backend == "spmd":
        emit(spmd_rows(quick=args.smoke or not args.full))
    else:
        emit(sim_rows(quick=not args.full, smoke=args.smoke))
