"""End-to-end system behaviour: simulated async-pipeline training converges,
basis rotation beats the vanilla async baseline under large delay, and the
shard_map pipeline runtime matches the single-device reference (subprocess —
it needs a multi-device fake topology that must not leak into this process).
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import (
    AttentionConfig,
    BlockSpec,
    ModelConfig,
    OptimizerConfig,
)
from repro.data import batches
from repro.models import init_model
from repro.optim.factory import build_optimizer
from repro.pipeline.simulate import run_sim_training

CFG = ModelConfig(
    num_layers=8, d_model=64, d_ff=256, vocab_size=128, max_seq_len=64,
    attention=AttentionConfig(num_heads=4, num_kv_heads=4, head_dim=16),
    pattern=(BlockSpec("attn", "dense"),), scan_layers=False,
    learnable_pos_emb=True, norm="layernorm", mlp_act="gelu",
)
STEPS = 120


def _run(name, stages, steps=STEPS, **okw):
    ocfg = OptimizerConfig(name=name, learning_rate=3e-3, total_steps=steps,
                           rotation_freq=5, **okw)
    params = init_model(jax.random.PRNGKey(0), CFG)
    opt = build_optimizer(ocfg, params, CFG, num_stages=stages)
    _, _, losses = run_sim_training(
        CFG, opt, batches(CFG, 8, 32, seed=0), steps=steps, params=params
    )
    return losses


def _avg_tail(losses, k=10):
    return sum(losses[-k:]) / k


def test_training_converges_no_delay():
    losses = _run("adam", stages=1)
    assert _avg_tail(losses) < losses[0] - 1.0


def test_delay_hurts_vanilla_adam():
    """Reproduces the paper's core observation (Fig. 2a): more stages =>
    slower convergence for PipeDream-style async Adam."""
    l1 = _run("adam", stages=1)
    l8 = _run("adam", stages=8)
    assert _avg_tail(l8) > _avg_tail(l1) - 1e-3


def test_basis_rotation_beats_vanilla_under_delay():
    """The paper's core claim (Fig. 5): under large delay, basis rotation
    converges faster than vanilla async Adam."""
    base = _run("adam", stages=8)
    rot = _run("basis_rotation", stages=8)
    assert _avg_tail(rot) < _avg_tail(base) + 0.05
    # and is no worse than 25% behind the zero-delay reference
    ref = _run("adam", stages=1)
    assert _avg_tail(rot) < _avg_tail(ref) * 1.25 + 0.5


def test_all_methods_stable_under_delay():
    for name in ["pipedream_lr", "nesterov", "delay_compensation"]:
        losses = _run(name, stages=4, steps=60)
        assert all(jnp.isfinite(jnp.asarray(losses))), name


SPMD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp, json
from repro.configs.base import ModelConfig, AttentionConfig, BlockSpec
from repro.launch.mesh import auto_axes
from repro.models import init_model
from repro.models.model import loss_fn
from repro.pipeline.spmd import stack_stage_params, make_pipeline_grad, make_pipeline_loss

cfg = ModelConfig(num_layers=4, d_model=32, d_ff=64, vocab_size=64, max_seq_len=64,
                  attention=AttentionConfig(num_heads=2, num_kv_heads=2, head_dim=16),
                  pattern=(BlockSpec("attn","dense"),), scan_layers=False)
params = init_model(jax.random.PRNGKey(0), cfg)
K, M = 4, 4
stacked, shared = stack_stage_params(params, cfg, K)
mesh = jax.make_mesh((K, 2), ("stage", "data"), axis_types=auto_axes(2))
toks = jax.random.randint(jax.random.PRNGKey(1), (M, 4, 16), 0, 64)
labels = jax.random.randint(jax.random.PRNGKey(2), (M, 4, 16), 0, 64)
batch = {"tokens": toks, "labels": labels}
grad_fn = make_pipeline_grad(cfg, mesh, K, M)
with jax.set_mesh(mesh):
    loss, (gs, gsh) = jax.jit(grad_fn)(stacked, shared, batch)
flat = {"tokens": toks.reshape(-1, 16), "labels": labels.reshape(-1, 16)}
(ref_loss, _), ref_g = jax.value_and_grad(loss_fn, has_aux=True)(params, cfg, flat)
re_stacked, _ = stack_stage_params({**{k: v for k, v in ref_g.items()}}, cfg, K)
d_blocks = max(jax.tree.leaves(jax.tree.map(
    lambda a, b: float(jnp.max(jnp.abs(a - b))), gs, re_stacked)))
d_loss = abs(float(loss) - float(ref_loss))

def n_eqns(jaxpr):
    total = len(jaxpr.eqns)
    for eq in jaxpr.eqns:
        for v in eq.params.values():
            if hasattr(v, "jaxpr"):
                total += n_eqns(v.jaxpr)
            elif hasattr(v, "eqns"):
                total += n_eqns(v)
    return total

# scanned schedule: trace size must not grow with microbatch count
sizes = []
for m in (4, 16):
    lf = make_pipeline_loss(cfg, mesh, K, m)
    b = {"tokens": jnp.zeros((m, 4, 16), jnp.int32),
         "labels": jnp.zeros((m, 4, 16), jnp.int32)}
    sizes.append(n_eqns(jax.make_jaxpr(lf)(stacked, shared, b).jaxpr))
print(json.dumps({"d_loss": d_loss, "d_blocks": d_blocks,
                  "eqns_m4": sizes[0], "eqns_m16": sizes[1]}))
"""


def test_spmd_pipeline_matches_reference():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", SPMD_SCRIPT],
        capture_output=True, text=True, cwd=os.path.dirname(os.path.dirname(__file__)),
        env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["d_loss"] < 1e-4
    assert res["d_blocks"] < 1e-4
    # jaxpr size constant in num_microbatches (lax.scan schedule, no unroll)
    assert res["eqns_m16"] == res["eqns_m4"]


def test_dryrun_smoke_subprocess():
    """One real (arch x shape) dry-run end-to-end through the CLI."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "qwen1_5_0_5b", "--shape", "decode_32k"],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(__file__)), env=env, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    row = json.loads(out.stdout.strip().splitlines()[-1])
    assert row["status"] == "ok"
    assert row["flops"] > 0 and row["bottleneck"] in ("compute", "memory", "collective")


PIPE_TRAIN_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp, json
from repro.configs.base import ModelConfig, AttentionConfig, BlockSpec, OptimizerConfig
from repro.data import batches
from repro.engine import SpmdEngine, LoopConfig, run_loop
from repro.launch.mesh import auto_axes

cfg = ModelConfig(num_layers=4, d_model=32, d_ff=64, vocab_size=64, max_seq_len=64,
                  attention=AttentionConfig(num_heads=2, num_kv_heads=2, head_dim=16),
                  pattern=(BlockSpec("attn","dense"),), scan_layers=False)
K, M = 4, 4
mesh = jax.make_mesh((K, 2), ("stage", "data"), axis_types=auto_axes(2))
ocfg = OptimizerConfig(name="basis_rotation", learning_rate=3e-3, total_steps=25,
                       rotation_freq=5, schedule="constant")
engine = SpmdEngine(cfg, ocfg, num_stages=K, num_microbatches=M, mesh=mesh)
state = engine.init_state(key=jax.random.PRNGKey(0))
state, losses = run_loop(engine, batches(cfg, M * 4, 16, seed=0),
                         LoopConfig(steps=25), state=state)
print(json.dumps({"first": losses[0], "last": sum(losses[-5:]) / 5}))
"""


def test_spmd_pipeline_async_training_converges():
    """End-to-end: the SpmdEngine — shard_map pipeline grads + per-stage
    delayed basis-rotation updates under the shared loop — reduces the loss."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", PIPE_TRAIN_SCRIPT],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(__file__)), env=env, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["last"] < res["first"] - 0.3, res
