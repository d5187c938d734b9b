"""Shared benchmark helpers: tiny-model training runs with exact delay
simulation, timing, and iterations-to-target-loss measurement.

All benchmarks run REDUCED-scale versions of the paper's experiments on CPU
with fixed seeds; each module maps 1:1 to a paper table/figure and returns
rows of (name, us_per_call, derived-metric).
"""
from __future__ import annotations

import sys
import time
from typing import Dict, List, Optional, Sequence

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.configs.base import (
    AttentionConfig,
    BlockSpec,
    MoEConfig,
    ModelConfig,
    OptimizerConfig,
)
from repro.data import batches
from repro.engine import LoopConfig, SimEngine, run_loop
from repro.models import init_model
from repro.optim.factory import build_optimizer

BENCH_MODEL = ModelConfig(
    name="bench_lm",
    num_layers=8,
    d_model=64,
    d_ff=256,
    vocab_size=128,
    max_seq_len=64,
    attention=AttentionConfig(num_heads=4, num_kv_heads=4, head_dim=16),
    pattern=(BlockSpec("attn", "dense"),),
    norm="layernorm",
    mlp_act="gelu",
    learnable_pos_emb=True,
    scan_layers=False,
)

BENCH_MOE = BENCH_MODEL.replace(
    name="bench_moe",
    num_layers=4,
    moe=MoEConfig(num_experts=4, top_k=2, d_ff_expert=128),
    pattern=(BlockSpec("attn", "moe"),),
)


def train_curve(
    name: str,
    stages: int,
    steps: int,
    cfg: ModelConfig = BENCH_MODEL,
    lr: float = 3e-3,
    seed: int = 0,
    batch: int = 8,
    seq: int = 32,
    data_delay: int = 0,
    **okw,
) -> Dict:
    """Run one simulated-async training; returns losses + per-step wall time.

    ``data_delay`` adds the uniform data-axis staleness of a deferred
    cross-replica reduction on top of each leaf's pipeline delay (the sim
    analogue of the SPMD engine's ``data_async`` FIFO)."""
    ocfg = OptimizerConfig(name=name, learning_rate=lr, total_steps=steps,
                           rotation_freq=okw.pop("rotation_freq", 5), **okw)
    params = init_model(jax.random.PRNGKey(seed), cfg)
    opt = build_optimizer(ocfg, params, cfg, num_stages=stages,
                          data_delay=data_delay)
    engine = SimEngine(cfg, opt)
    state = engine.init_state(params=params)
    t0 = time.perf_counter()
    _, losses = run_loop(engine, batches(cfg, batch, seq, seed=seed),
                         LoopConfig(steps=steps), state=state)
    dt = time.perf_counter() - t0
    return {"losses": losses, "us_per_step": 1e6 * dt / steps}


SPMD_CURVES_SCRIPT = r"""
import sys
sys.path.insert(0, "src")
from repro.launch.devices import ensure_host_devices
ensure_host_devices(%(devices)d)
import json, time
import jax
from repro.configs.base import ModelConfig, AttentionConfig, BlockSpec, OptimizerConfig
from repro.data import batches
from repro.engine import LoopConfig, SpmdEngine, run_loop
from repro.launch.topology import Topology
from repro.models import init_model

runs = %(runs)s
out = []
for r in runs:
    cfg = ModelConfig(
        name="bench_lm", num_layers=r["num_layers"], d_model=64, d_ff=256,
        vocab_size=128, max_seq_len=64,
        attention=AttentionConfig(num_heads=4, num_kv_heads=4, head_dim=16),
        pattern=(BlockSpec("attn", "dense"),), norm="layernorm", mlp_act="gelu",
        learnable_pos_emb=True, scan_layers=False)
    K = r["stages"]
    ocfg = OptimizerConfig(name=r["name"], learning_rate=r["lr"],
                           total_steps=r["steps"], rotation_freq=r["rotation_freq"],
                           **r["okw"])
    engine = SpmdEngine(cfg, ocfg, num_stages=K, num_microbatches=K,
                        topology=Topology(stages=K, data=r["data_par"]),
                        schedule=r["schedule"], use_kernels=r["use_kernels"],
                        precision=r["precision"],
                        data_async=r["data_async"], data_delay=r["data_delay"])
    params = init_model(jax.random.PRNGKey(r["seed"]), cfg)
    state = engine.init_state(params=params)
    data = batches(cfg, r["batch"], r["seq"], seed=r["seed"])
    t0 = time.perf_counter()
    state, losses = run_loop(engine, data, LoopConfig(steps=r["steps"]), state=state)
    dt = time.perf_counter() - t0
    out.append({"losses": losses, "us_per_step": 1e6 * dt / r["steps"]})
print(json.dumps(out))
"""


def cpu_child_env() -> Dict[str, str]:
    """Environment for a benchmark child process that forces host devices.

    The parent has imported JAX and may hold the accelerator; a child that
    needs it too would fail or hang. Such children therefore run on XLA:CPU
    explicitly, and the rows they produce say ``platform=cpu``.
    """
    import os

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def spmd_train_curves(runs: List[Dict]) -> List[Dict]:
    """Run `train_curve`-style async trainings on the SPMD backend.

    Each run dict: {name, stages, steps, num_layers, lr, seed, batch, seq,
    rotation_freq, okw, data_par, data_async, data_delay}. All runs execute
    in ONE subprocess with ``max(stages * data_par)`` forced host devices
    (smaller topologies use a device prefix), so the engine-driven fig5/fig6
    sweeps cross-validate the sim convergence claims on the real shard_map
    runtime without a process per point. ``data_par > 1`` shards the batch
    over replicas; ``data_async``/``data_delay`` route the cross-replica
    gradient reduction through the engine's deferred FIFO. Staleness
    matches the sim path: the per-stage delay FIFO on the stage-stacked
    layout == the simulator's per-leaf FIFO. The subprocess runs on XLA:CPU
    (`cpu_child_env`); every result carries ``"platform": "cpu"``.
    """
    import json
    import os
    import subprocess

    defaults = {"num_layers": 8, "lr": 3e-3, "seed": 0, "batch": 8, "seq": 32,
                "rotation_freq": 5, "okw": {}, "schedule": "fill_drain",
                "use_kernels": False, "precision": "f32", "data_par": 1,
                "data_async": False, "data_delay": 0}
    runs = [{**defaults, **r} for r in runs]
    script = SPMD_CURVES_SCRIPT % {
        "devices": max(r["stages"] * r["data_par"] for r in runs),
        "runs": repr(runs),
    }
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=cpu_child_env(), timeout=3600,
    )
    if out.returncode != 0:
        raise RuntimeError(f"spmd curve subprocess failed: {out.stderr[-2000:]}")
    return [{**r, "platform": "cpu"}
            for r in json.loads(out.stdout.strip().splitlines()[-1])]


def iters_to_loss(losses: Sequence[float], target: float) -> Optional[int]:
    run_min = float("inf")
    for i, l in enumerate(losses):
        run_min = min(run_min, l)
        if run_min <= target:
            return i + 1
    return None


def slowdown(losses_delayed, losses_ref, target: float) -> float:
    a = iters_to_loss(losses_delayed, target)
    b = iters_to_loss(losses_ref, target)
    if b is None or b == 0:
        return float("nan")
    if a is None:
        return float("inf")  # never reached the target: the paper's "diverged"
    return a / b


def tail(losses: Sequence[float], k: int = 10) -> float:
    return sum(losses[-k:]) / min(k, len(losses))


def emit(rows: List[Dict]) -> None:
    for r in rows:
        print(f"{r['name']},{r.get('us_per_call', 0):.1f},{r.get('derived', '')}")
