"""Core rotation machinery: power iteration, rotations, the optimizer's
algebraic invariants, stage-aware frequencies."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    basis_rotation_adam,
    build_layout,
    power_qr,
    rotate,
    rotated_fraction,
    unrotate,
)
from repro.core.rotation import batched_eye, gram_left, gram_right, refresh_basis
from repro.core.stage_aware import (
    NEVER,
    StageContext,
    budget,
    freqs_for_delays,
    stage_aware_freq,
)
from repro.optim import adam, constant_schedule
from repro.optim.base import bias_correction


def test_power_qr_orthonormal_and_converges():
    key = jax.random.PRNGKey(0)
    Q0 = jnp.linalg.qr(jax.random.normal(key, (16, 16)))[0]
    A = Q0 @ jnp.diag(jnp.linspace(10, 0.1, 16)) @ Q0.T  # PSD, known eigvecs
    U = jnp.eye(16)
    for _ in range(60):
        U = power_qr(A, U)
    assert np.allclose(U.T @ U, np.eye(16), atol=1e-5)
    # subspace alignment: |<u_i, q_i>| -> 1
    overlap = jnp.abs(jnp.sum(U * Q0, axis=0))
    assert float(jnp.min(overlap)) > 0.99


def test_power_qr_batched():
    key = jax.random.PRNGKey(1)
    A = jax.random.normal(key, (3, 8, 8))
    A = jnp.einsum("bij,bkj->bik", A, A)  # PSD batch
    U = batched_eye(8, (3,))
    U = power_qr(A, U)
    eye_err = jnp.einsum("bji,bjk->bik", U, U) - jnp.eye(8)
    assert float(jnp.max(jnp.abs(eye_err))) < 1e-5


def test_rotate_unrotate_inverse():
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (12, 20))
    U = jnp.linalg.qr(jax.random.normal(key, (12, 12)))[0]
    V = jnp.linalg.qr(jax.random.normal(jax.random.PRNGKey(3), (20, 20)))[0]
    np.testing.assert_allclose(
        np.asarray(unrotate(rotate(x, U, V), U, V)), np.asarray(x), atol=1e-5
    )
    # Frobenius norm preserved (orthogonality)
    np.testing.assert_allclose(
        float(jnp.linalg.norm(rotate(x, U, V))), float(jnp.linalg.norm(x)), rtol=1e-5
    )


def test_identity_rotation_is_adam():
    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (16, 24)), "scale": jnp.ones((24,))}
    grads = {"w": jax.random.normal(jax.random.PRNGKey(1), (16, 24)),
             "scale": jnp.ones((24,)) * 0.1}
    sched = constant_schedule(1e-2)
    br, ad = basis_rotation_adam(sched, freq=0), adam(sched)
    s1, s2 = br.init(params), ad.init(params)
    for t in range(4):
        u1, s1 = br.update(grads, s1, params, jnp.int32(t))
        u2, s2 = ad.update(grads, s2, params, jnp.int32(t))
        d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), u1, u2)
        assert max(jax.tree.leaves(d)) < 1e-6


def test_rotation_equivariance():
    """Basis rotation with the TRUE eigenbasis of a rotated quadratic matches
    plain Adam on the axis-aligned version of the same problem."""
    key = jax.random.PRNGKey(0)
    d = 8
    Q = jnp.linalg.qr(jax.random.normal(key, (d, d)))[0]
    diag = jnp.linspace(10.0, 0.5, d)

    # aligned problem: f(w) = 1/2 w^T D w ; rotated: g(x) = f(Q^T x)
    w = jax.random.normal(jax.random.PRNGKey(1), (1, d))  # as a 1 x d matrix
    x = w @ Q.T  # rotated iterate

    sched = constant_schedule(0.1)
    ad = adam(sched, beta1=0.0)
    br = basis_rotation_adam(sched, beta1=0.0, freq=0, min_dim=1)
    sa = ad.init({"w": w})
    sb = br.init({"x": x})
    # manually install the true eigenbasis (right rotation of the 1 x d
    # iterate: x_tilde = x V with V = Q maps to the aligned coordinates)
    sb["leaves"][0]["V"] = Q

    for t in range(20):
        gw = w * diag  # grad of aligned quadratic
        gx = (x @ Q) * diag @ Q.T  # grad of rotated quadratic
        uw, sa = ad.update({"w": gw}, sa, {"w": w}, jnp.int32(t))
        ux, sb = br.update({"x": gx}, sb, {"x": x}, jnp.int32(t))
        w = w + uw["w"]
        x = x + ux["x"]
        # the rotated trajectory tracks the aligned one exactly
        np.testing.assert_allclose(np.asarray(x @ Q), np.asarray(w), atol=1e-4)


def test_layout_exclusions_and_sides():
    params = {
        "embed": {"embedding": jnp.zeros((64, 16))},
        "lm_head": jnp.zeros((16, 64)),
        "blocks": [{"norm1": {"scale": jnp.zeros((16,))},
                    "mixer": {"w_q": jnp.zeros((16, 32)), "b_q": jnp.zeros((32,))},
                    "mlp": {"w_down": jnp.zeros((32, 16))}}],
    }
    lay = {p.path: p for p in build_layout(params, "unilateral")}
    assert not lay["embed/embedding"].rotate
    assert not lay["lm_head"].rotate
    assert not lay["blocks/0/norm1/scale"].rotate
    assert not lay["blocks/0/mixer/b_q"].rotate
    wq = lay["blocks/0/mixer/w_q"]
    assert wq.rotate and wq.left and not wq.right  # smaller dim = rows
    wd = lay["blocks/0/mlp/w_down"]
    assert wd.rotate and not wd.left and wd.right
    bi = {p.path: p for p in build_layout(params, "bilateral")}
    assert bi["blocks/0/mixer/w_q"].left and bi["blocks/0/mixer/w_q"].right
    frac = rotated_fraction(params, build_layout(params, "bilateral"))
    assert 0.0 < frac < 1.0


def test_refresh_sources_state():
    g = jax.random.normal(jax.random.PRNGKey(0), (8, 12))
    m = 0.5 * g
    U, V = batched_eye(8, ()), batched_eye(12, ())
    L, R = jnp.zeros((8, 8)), jnp.zeros((12, 12))
    U2, V2, L2, R2 = refresh_basis(g, m, U, V, L, R, "2nd", 0.9)
    assert float(jnp.max(jnp.abs(L2 - 0.1 * gram_left(g)))) < 1e-5
    assert float(jnp.max(jnp.abs(R2 - 0.1 * gram_right(g)))) < 1e-5
    U1, V1, L1, R1 = refresh_basis(g, m, U, V, None, None, "1st", 0.9)
    assert L1 is None and R1 is None  # no Fisher state for S=1st


def test_stage_aware_rule():
    P, f0 = 8, 10
    freqs = [stage_aware_freq(tau, P, f0) for tau in range(P)]
    # most-delayed stages refresh most often
    assert freqs[P - 1] < f0
    # least-delayed stages never refresh
    assert freqs[0] == NEVER and freqs[1] == NEVER
    # monotone: more delay => more frequent (smaller period), among finite
    finite = [f for f in freqs if f < NEVER]
    assert finite == sorted(finite, reverse=True)
    # budget-normalised allocation never exceeds the uniform budget
    norm = freqs_for_delays(list(range(P)), P, f0)
    assert budget(norm, 1000) <= budget([f0] * P, 1000) + 1e-6


def test_stage_aware_reversed_allocation():
    delays = [3, 2, 1, 0]
    fwd = freqs_for_delays(delays, 4, 10)
    rev = freqs_for_delays(delays, 4, 10, reversed_allocation=True)
    assert fwd == list(reversed(rev))


def test_per_stage_refresh_mask_selective():
    """A stacked (K, m, n) leaf with per-stage periods [1, NEVER] refreshes
    exactly stage 0's basis every step; stage 1's basis stays identity."""
    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (2, 16, 16))}
    opt = basis_rotation_adam(constant_schedule(1e-2), freq=[(1, NEVER)])
    s = opt.init(params)
    eye = jnp.eye(16)
    for t in range(3):
        g = {"w": jax.random.normal(jax.random.PRNGKey(10 + t), (2, 16, 16))}
        _, s = opt.update(g, s, params, jnp.int32(t))
        U, V = s["leaves"][0]["U"], s["leaves"][0]["V"]
        assert float(jnp.max(jnp.abs(U[0] - eye))) > 1e-3, f"step {t}"
        np.testing.assert_array_equal(np.asarray(U[1]), np.asarray(eye))
        np.testing.assert_array_equal(np.asarray(V[1]), np.asarray(eye))
        # the non-refreshing stage's Fisher EMA must not advance either
        np.testing.assert_array_equal(
            np.asarray(s["leaves"][0]["L"][1]), np.zeros((16, 16), np.float32)
        )


def test_per_stage_uniform_freqs_match_scalar_path():
    """The vectorized per-stage mask with one period on every stage must
    reproduce the scalar lax.cond path (the sim backend's entry) exactly."""
    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (2, 16, 24))}
    sched = constant_schedule(1e-2)
    opt_scalar = basis_rotation_adam(sched, freq=3)
    opt_tuple = basis_rotation_adam(sched, freq=[(3, 3)])
    s1, s2 = opt_scalar.init(params), opt_tuple.init(params)
    for t in range(7):
        g = {"w": jax.random.normal(jax.random.PRNGKey(20 + t), (2, 16, 24))}
        u1, s1 = opt_scalar.update(g, s1, params, jnp.int32(t))
        u2, s2 = opt_tuple.update(g, s2, params, jnp.int32(t))
        np.testing.assert_array_equal(np.asarray(u1["w"]), np.asarray(u2["w"]))


def test_never_freq_never_refreshes():
    """Periods >= NEVER mean literally never — including step 0 — so the
    'never refresh' stages of the stage-aware allocation keep identity bases
    on both the scalar and the vectorized path."""
    key = jax.random.PRNGKey(3)
    params = {"w": jax.random.normal(key, (16, 16))}
    opt = basis_rotation_adam(constant_schedule(1e-2), freq=NEVER)
    s = opt.init(params)
    g = {"w": jax.random.normal(jax.random.PRNGKey(4), (16, 16))}
    _, s = opt.update(g, s, params, jnp.int32(0))
    np.testing.assert_array_equal(
        np.asarray(s["leaves"][0]["U"]), np.asarray(jnp.eye(16))
    )


def test_stage_context_freqs_match_sim_multiset():
    """The stacked layout's per-stage periods equal the per-layer sim
    layout's: budget renormalisation over the expanded canonical multiset
    assigns the same period to the same delay on both layouts."""
    K, per = 4, 3
    # sim layout: per-layer scalar leaves (K*per block leaves + 2 shared)
    sim_delays = tuple(
        K - 1 - (l // per) for l in range(K * per)
    ) + (K - 1, 0)
    ctx_sim = StageContext(K, sim_delays, (1,) * len(sim_delays))
    # stacked layout: one (K, per, ...) leaf + the same 2 shared leaves
    stage_delays = tuple(K - 1 - k for k in range(K))
    ctx_stacked = StageContext(K, (stage_delays, K - 1, 0), (per, 1, 1))
    for base in (2, 5, 10):
        fs = ctx_sim.refresh_freqs(base)
        fstk = ctx_stacked.refresh_freqs(base)
        lut_sim = dict(zip(sim_delays, fs))
        lut_stk = dict(zip(stage_delays, fstk[0]))
        for tau in stage_delays:
            assert lut_sim[tau] == lut_stk[tau], (base, tau)
        assert fstk[1] == lut_sim[K - 1] and fstk[2] == lut_sim[0]


def test_stage_context_delay_specs_and_scales():
    ctx = StageContext(3, ((2, 1, 0), 2, 0), (2, 1, 1))
    assert ctx.delay_specs() == ["stage", 2, 0]
    params = (jnp.zeros((3, 2, 4, 4)), {"e": jnp.zeros((4,)), "h": jnp.zeros((4,))})
    scales = ctx.delay_scales(params)
    assert scales[0].shape == (3, 1, 1, 1)
    np.testing.assert_array_equal(
        np.asarray(scales[0]).reshape(-1), np.asarray([2.0, 1.0, 0.0])
    )
    assert scales[1]["e"] == 2 and scales[1]["h"] == 0


# ---------------------------------------------------------------------------
# the first moment kept in the eigenbasis
# ---------------------------------------------------------------------------

STAGED = (10, 4, NEVER)  # step 4: stage 1 alone; step 10: stage 0 alone


def _reference_run(gs, shape, freq, source, geometry, lr=1e-2, b1=0.9,
                   b2=0.999, eps=1e-8):
    """The paper's Algorithm 1 as written: M in the original space, the
    gradient and M rotated and the step unrotated every step (three
    two-sided products). Yields (update, M, (U, V)) per step."""
    (plan,) = build_layout({"w": jnp.zeros(shape)}, geometry)
    batch, (m, n) = shape[:-2], shape[-2:]
    U = batched_eye(m, batch) if plan.left else None
    V = batched_eye(n, batch) if plan.right else None
    L = jnp.zeros(batch + (m, m)) if plan.left and source == "2nd" else None
    R = jnp.zeros(batch + (n, n)) if plan.right and source == "2nd" else None
    M, v = jnp.zeros(shape), jnp.zeros(shape)
    for t, g in enumerate(gs):
        M = b1 * M + (1 - b1) * g
        due = np.array([0 < f < NEVER and t % f == 0 for f in
                        (freq if isinstance(freq, tuple) else (freq,))])
        if due.any():
            new = refresh_basis(g, M, U, V, L, R, source, b2)
            keep = due.reshape((-1,) + (1,) * len(shape[1:]))
            U, V, L, R = [None if x is None else jnp.where(keep, x, old)
                          for x, old in zip(new, (U, V, L, R))]
        bc1, bc2 = bias_correction(b1, jnp.int32(t)), bias_correction(b2, jnp.int32(t))
        g_rot, m_rot = rotate(g, U, V), rotate(M, U, V)
        v = b2 * v + (1 - b2) * jnp.square(g_rot)
        step = (m_rot / bc1) / (jnp.sqrt(v / bc2) + eps)
        yield -lr * unrotate(step, U, V), M, (U, V)


def _gradients(shape, steps, seed=0):
    """Random gradients around a fixed well-conditioned matrix, so that the
    power-QR refresh is well posed and two exact implementations agree to
    rounding."""
    m, n = shape[-2:]
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    Q1 = jnp.linalg.qr(jax.random.normal(k1, (m, m)))[0]
    Q2 = jnp.linalg.qr(jax.random.normal(k2, (n, n)))[0]
    r = min(m, n)
    C = Q1[:, :r] @ jnp.diag(jnp.linspace(2.0, 1.0, r)) @ Q2[:, :r].T
    noise = jax.random.normal(k3, (steps,) + shape)
    return [C + 0.3 * noise[t] for t in range(steps)]


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


@pytest.mark.parametrize(
    "shape,freq,geometry,source,kernels",
    [
        ((16, 16), 10, "bilateral", "2nd", False),
        ((16, 24), 10, "unilateral", "2nd", False),
        ((16, 16), 10, "bilateral", "1st", False),
        ((24, 16), 10, "unilateral", "1st", False),
        ((3, 16, 16), STAGED, "bilateral", "2nd", False),
        ((3, 16, 24), STAGED, "unilateral", "1st", False),
        ((16, 16), 10, "bilateral", "2nd", True),
    ],
    ids=["scalar-bi-2nd", "scalar-uni-2nd", "scalar-bi-1st", "scalar-uni-1st",
         "stage-bi-2nd", "stage-uni-1st", "kernels-bi-2nd"],
)
def test_eigenbasis_moment_matches_original_space(shape, freq, geometry, source,
                                                  kernels):
    """Keeping M~ = U^T M V as the state, re-expressed at each refresh,
    gives the original-space algorithm's updates over 25 steps (three
    refreshes), and unrotating the stored m gives back its M.

    Step 0 is Adam's sign step: each rotated entry moves by lr m~/|g~|
    whatever its size, so an entry of g~ at rounding level takes either
    sign under either order of the same products. Its update is left out;
    its moment is compared like every other step's."""
    steps = 25
    gs = _gradients(shape, steps)
    opt = basis_rotation_adam(
        constant_schedule(1e-2), source=source, geometry=geometry,
        freq=[freq] if isinstance(freq, tuple) else freq, use_kernels=kernels,
    )
    params = {"w": jnp.zeros(shape)}
    s = opt.init(params)
    update = jax.jit(lambda g, s, t: opt.update({"w": g}, s, params, t))
    ref = _reference_run(gs, shape, freq, source, geometry)
    for t, (g, (want, M, (U, V))) in enumerate(zip(gs, ref)):
        u, s = update(g, s, jnp.int32(t))
        st = s["leaves"][0]
        assert t == 0 or _rel(u["w"], want) < 1e-5, t
        assert _rel(unrotate(st["m"], st.get("U"), st.get("V")), M) < 1e-5, t
    B = U if U is not None else V  # the run did refresh: off identity
    assert float(jnp.max(jnp.abs(B - jnp.eye(B.shape[-1])))) > 1e-3


def _dot_generals(jx):
    """(outside any cond, inside a cond under the `rebase` scope) counts."""
    from repro.analysis.jaxpr import iter_eqns

    outside = rebase = 0
    for eq, ctx in iter_eqns(jx):
        if eq.primitive.name != "dot_general":
            continue
        if "cond" not in ctx:
            outside += 1
        elif "rebase" in str(eq.source_info.name_stack):
            rebase += 1
    return outside, rebase


def test_plain_step_runs_two_products_and_refresh_rebases():
    """One bilateral leaf: four dot_generals outside the refresh cond (rotate
    the gradient, unrotate the step: two two-sided products, where keeping
    M in the original space took three), and four in the refresh branch's
    `rebase` scope (unrotate from the old basis, rotate into the new)."""
    params = {"w": jnp.zeros((16, 24))}
    opt = basis_rotation_adam(constant_schedule(1e-2), freq=10)
    s = opt.init(params)
    g = {"w": jnp.ones((16, 24))}
    jx = jax.make_jaxpr(lambda g, s, t: opt.update(g, s, params, t))(
        g, s, jnp.int32(0))
    assert _dot_generals(jx) == (4, 4)
    # unilateral: one side each way
    uni = basis_rotation_adam(constant_schedule(1e-2), freq=10, geometry="unilateral")
    su = uni.init(params)
    jx = jax.make_jaxpr(lambda g, s, t: uni.update(g, s, params, t))(
        g, su, jnp.int32(0))
    assert _dot_generals(jx) == (2, 2)


def test_stored_moment_keeps_the_per_layer_norm():
    """Each (stage, layer) matrix of the stored m has the Frobenius norm of
    the original-space M: what a per-layer gradient-norm reading of
    m / (1 - b1) relies on."""
    shape = (2, 3, 16, 24)
    gs = _gradients(shape, 12, seed=1)
    opt = basis_rotation_adam(constant_schedule(1e-2), freq=5)
    params = {"w": jnp.zeros(shape)}
    s = opt.init(params)
    update = jax.jit(lambda g, s, t: opt.update({"w": g}, s, params, t))
    norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x), axis=(-2, -1)))
    M = jnp.zeros(shape)
    for t, g in enumerate(gs):
        _, s = update(g, s, jnp.int32(t))
        M = 0.9 * M + 0.1 * g
        np.testing.assert_allclose(np.asarray(norm(s["leaves"][0]["m"])),
                                   np.asarray(norm(M)), rtol=1e-5)
    assert float(jnp.max(jnp.abs(s["leaves"][0]["m"] - M))) > 1e-2  # rotated
