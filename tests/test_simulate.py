"""Simulation-trainer mechanics: weight prediction, no-stash gradients,
sim-vs-bare-optimizer delay equivalence."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AttentionConfig, BlockSpec, ModelConfig, OptimizerConfig
from repro.data import batches
from repro.models import init_model
from repro.optim import adam, constant_schedule
from repro.optim.base import make_schedule
from repro.optim.factory import build_optimizer
from repro.pipeline.delay import delayed_optimizer
from repro.pipeline.partition import delay_tree, leaf_delays
from repro.pipeline.simulate import (
    make_two_version_loss,
    predict_weights,
    run_sim_training,
)

CFG = ModelConfig(
    num_layers=4, d_model=32, d_ff=64, vocab_size=64, max_seq_len=64,
    attention=AttentionConfig(num_heads=2, num_kv_heads=2, head_dim=16),
    pattern=(BlockSpec("attn", "dense"),), scan_layers=False,
)


def test_delay_zero_equals_no_wrapper():
    params = init_model(jax.random.PRNGKey(0), CFG)
    sched = constant_schedule(1e-3)
    bare = adam(sched)
    wrapped = delayed_optimizer(adam(sched), [0] * len(jax.tree.leaves(params)))
    g = jax.tree.map(jnp.ones_like, params)
    sb, sw = bare.init(params), wrapped.init(params)
    ub, _ = bare.update(g, sb, params, jnp.int32(0))
    uw, _ = wrapped.update(g, sw, params, jnp.int32(0))
    d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), ub, uw)
    assert max(jax.tree.leaves(d)) == 0.0


def test_predict_weights_moves_against_momentum():
    params = {"w": jnp.ones((4,))}
    state = {"m": {"w": jnp.ones((4,))}, "v": {"w": jnp.ones((4,))}}
    pred = predict_weights(params, state, {"w": 2}, lr=0.1)
    np.testing.assert_allclose(np.asarray(pred["w"]), 1.0 - 0.1 * 2 * 1.0, rtol=1e-5)
    # zero delay leaves weights untouched
    pred0 = predict_weights(params, state, {"w": 0}, lr=0.1)
    np.testing.assert_allclose(np.asarray(pred0["w"]), 1.0)


def test_predict_weights_rotated_state_coherent():
    """PipeMare prediction under basis rotation: the state's m holds the
    first moment in the eigenbasis (U^T M V), the ratio with the
    rotated-space v is formed there and the step rotated back — the old
    elementwise original/rotated mix is a regression."""
    n = 8
    U = jnp.asarray(np.eye(n, dtype=np.float32)[::-1].copy())  # reversal perm
    V = jnp.eye(n, dtype=jnp.float32)
    m = jnp.arange(n * n, dtype=jnp.float32).reshape(n, n) / 10.0
    v = (jnp.arange(n * n, dtype=jnp.float32).reshape(n, n) % 7) + 1.0
    p = jnp.ones((n, n), jnp.float32)
    state = {"leaves": [{"m": U.T @ m @ V, "v": v, "U": U, "V": V}]}
    pred = predict_weights({"w": p}, state, {"w": 3}, lr=0.01)
    m_rot = U.T @ m @ V
    want = p - 0.01 * 3 * (U @ (m_rot / (jnp.sqrt(v) + 1e-8)) @ V.T)
    np.testing.assert_allclose(np.asarray(pred["w"]), np.asarray(want), rtol=1e-5)
    # the basis-mixing formula gives a different (incoherent) answer here
    mixed = p - 0.01 * 3 * m / (jnp.sqrt(v) + 1e-8)
    assert float(jnp.max(jnp.abs(pred["w"] - mixed))) > 1e-3
    # identity bases reduce to the plain Adam-style extrapolation
    eye_state = {"leaves": [{"m": m, "v": v, "U": jnp.eye(n), "V": jnp.eye(n)}]}
    pred_id = predict_weights({"w": p}, eye_state, {"w": 3}, lr=0.01)
    np.testing.assert_allclose(np.asarray(pred_id["w"]), np.asarray(mixed), rtol=1e-5)
    # non-rotated leaves (no U/V) keep the plain formula
    plain_state = {"leaves": [{"m": m, "v": v}]}
    pred_pl = predict_weights({"w": p}, plain_state, {"w": 3}, lr=0.01)
    np.testing.assert_allclose(np.asarray(pred_pl["w"]), np.asarray(mixed), rtol=1e-5)


def test_two_version_loss_gradients():
    """Same versions => identical to the plain gradient; different versions
    => a deliberately 'incorrect' gradient (no-stash pathology)."""
    from repro.models.model import loss_fn

    params = init_model(jax.random.PRNGKey(0), CFG)
    batch = next(batches(CFG, 2, 16, seed=0))
    batch = {"tokens": batch["tokens"], "labels": batch["labels"]}
    loss2w = make_two_version_loss(CFG)
    g_same = jax.grad(loss2w)(params, params, batch)
    (_, _), g_ref = jax.value_and_grad(loss_fn, has_aux=True)(params, CFG, batch)
    d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), g_same, g_ref)
    assert max(jax.tree.leaves(d)) < 1e-5

    older = jax.tree.map(lambda x: x * 0.9, params)
    g_mix = jax.grad(loss2w)(params, older, batch)
    d2 = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), g_mix, g_ref)
    assert max(jax.tree.leaves(d2)) > 1e-4  # versions differ -> gradient differs


def test_run_sim_training_smoke_paths():
    params = init_model(jax.random.PRNGKey(0), CFG)
    ocfg = OptimizerConfig(name="adam", learning_rate=1e-3, total_steps=10)
    dt = delay_tree(params, CFG, 4)
    sched = make_schedule("cosine", 1e-3, 10, 0.1)
    for kw in (
        {},
        {"weight_prediction": True, "delays_tree": dt, "schedule": sched},
        {"no_stash": True},
    ):
        opt = build_optimizer(ocfg, params, CFG, num_stages=4)
        _, _, losses = run_sim_training(
            CFG, opt, batches(CFG, 4, 16, seed=0), steps=10, params=params, **kw
        )
        assert len(losses) == 10 and all(np.isfinite(losses))
