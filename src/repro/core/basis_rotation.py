"""Adam with Basis Rotation (paper Algorithm 1) — the core contribution.

Per rotatable weight matrix W (m x n):
    G_t  <- grad                                  (original space)
    M_t  <- b1 M_{t-1} + (1-b1) G_t               (original space, Appendix G)
    if t % freq == 0:  U,V <- Eigenbasis-Estimation(G_t, M_t, U, V)
    G~ <- U^T G V ; M~ <- U^T M V                 (rotate at use time)
    V~_t <- b2 V~_{t-1} + (1-b2) G~^2             (rotated second moment)
    W <- W - lr * U ( M~ / sqrt(V~ + eps) ) V^T

Non-rotatable leaves (embeddings, norms, biases, 1-D params) fall back to
plain Adam — exactly the paper's setup.

State is a flat list of per-leaf dicts (ordered like
``jax.tree_util.tree_flatten(params)``), which keeps the whole thing a plain
pytree: shardable under pjit, delayable under the FIFO wrapper, and
checkpointable with no special cases.

``freq``: the refresh-period spec. A scalar int applies one period to every
leaf; a per-leaf sequence (stage-aware allocation, `repro.core.stage_aware`)
gives each leaf its own entry, where an entry is either

  * an int — uniform period for the whole leaf (the sim layout's per-layer
    leaves), refreshed via a single ``lax.cond``; or
  * a tuple of K ints — per-stage periods over the leaf's LEADING stage axis
    (the SPMD stage-stacked ``(K, per, m, n)`` layout), refreshed through a
    vectorized per-stage mask: ``refresh_basis`` runs batched over the stage
    axis and ``jnp.where`` keeps stage k's old basis unless
    ``step % freq[k] == 0``.

A period <= 0 or >= ``stage_aware.NEVER`` means "never refresh" (the basis
stays at identity unless warm-started).
"""
from __future__ import annotations

from typing import List, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.layout import LeafPlan, build_layout
from repro.core.rotation import (
    batched_eye,
    refresh_basis,
    rotate,
    unrotate,
)
from repro.core.stage_aware import NEVER
from repro.optim.base import Optimizer, Schedule, bias_correction

FreqSpec = Union[int, Tuple[int, ...]]


def _init_leaf(p: jnp.ndarray, plan: LeafPlan, source: str) -> dict:
    st = {
        "m": jnp.zeros(p.shape, jnp.float32),
        "v": jnp.zeros(p.shape, jnp.float32),
    }
    if not plan.rotate:
        return st
    batch = p.shape[:-2]
    m, n = p.shape[-2], p.shape[-1]
    if plan.left:
        st["U"] = batched_eye(m, batch)
        if source == "2nd":
            st["L"] = jnp.zeros(batch + (m, m), jnp.float32)
    if plan.right:
        st["V"] = batched_eye(n, batch)
        if source == "2nd":
            st["R"] = jnp.zeros(batch + (n, n), jnp.float32)
    return st


def _refresh_ops(g, m, ops, freq: FreqSpec, step, source: str, beta2: float):
    """Apply the (possibly per-stage) refresh schedule to (U, V, L, R).

    Scalar periods keep the single ``lax.cond`` on ``step % f == 0`` — the
    sim backend's bit-for-bit path. Tuple periods vectorize: the refresh runs
    batched over the leaf's leading stage axis and a per-stage mask selects,
    per stage, the refreshed or the previous basis (and Fisher EMA — a
    non-refreshing stage must not advance L/R either, matching the cond).
    """

    def do_refresh(o):
        # the scope sits inside the branch: ops of the cond itself (operand
        # copies, the identity branch of a plain step) stay outside it
        with jax.named_scope("refresh"):
            Uo, Vo, Lo, Ro = o
            return refresh_basis(g, m, Uo, Vo, Lo, Ro, source, beta2)

    if isinstance(freq, tuple):
        K = len(freq)
        assert g.shape[0] == K, (
            f"per-stage freqs {freq} need a leading stage axis of {K}, "
            f"got leaf shape {g.shape}"
        )
        if not any(0 < f < NEVER for f in freq):
            return ops
        farr = jnp.asarray(freq, jnp.int32)
        live = jnp.asarray([0 < f < NEVER for f in freq])
        mask = live & (step % jnp.maximum(farr, 1) == 0)  # (K,)

        def masked_refresh(o):
            new = do_refresh(o)

            def sel(n, old):
                if old is None:
                    return None
                return jnp.where(mask.reshape((K,) + (1,) * (old.ndim - 1)), n, old)

            with jax.named_scope("refresh"):
                return tuple(sel(n, old) for n, old in zip(new, o))

        return jax.lax.cond(jnp.any(mask), masked_refresh, lambda o: o, ops)

    if 0 < freq < NEVER:
        return jax.lax.cond(step % freq == 0, do_refresh, lambda o: o, ops)
    return ops


def basis_rotation_adam(
    schedule: Schedule,
    beta1: float = 0.9,
    beta2: float = 0.999,
    eps: float = 1e-8,
    source: str = "2nd",
    geometry: str = "bilateral",
    freq: Union[int, Sequence[FreqSpec]] = 10,
    weight_decay: float = 0.0,
    min_dim: int = 8,
    use_kernels: bool = False,
) -> Optimizer:
    assert source in ("1st", "2nd") and geometry in ("unilateral", "bilateral")

    if use_kernels:
        from repro.kernels import ops as kops
    else:
        kops = None

    def init(params):
        layout = build_layout(params, geometry, min_dim)
        flat, _ = jax.tree_util.tree_flatten_with_path(params)
        return {"leaves": [_init_leaf(x, pl, source) for (_, x), pl in zip(flat, layout)]}

    def update(grads, state, params, step, aux=None):
        layout = build_layout(params, geometry, min_dim)
        if isinstance(freq, int):
            freqs: List[FreqSpec] = [freq] * len(layout)
        else:
            freqs = [tuple(f) if isinstance(f, (tuple, list)) else int(f)
                     for f in freq]
            assert len(freqs) == len(layout), "freq list must match leaf count"
        lr = schedule(step)
        bc1, bc2 = bias_correction(beta1, step), bias_correction(beta2, step)

        gflat, gdef = jax.tree_util.tree_flatten(grads)
        new_leaves = []
        updates = []
        for g, st, plan, f in zip(gflat, state["leaves"], layout, freqs):
            g = g.astype(jnp.float32)
            m = beta1 * st["m"] + (1 - beta1) * g
            nst = dict(st)
            nst["m"] = m

            if plan.rotate:
                U, V, L, R = _refresh_ops(
                    g, m,
                    (st.get("U"), st.get("V"), st.get("L"), st.get("R")),
                    f, step, source, beta2,
                )
                if kops is not None:
                    with jax.named_scope("rotate"):
                        g_rot = kops.two_sided_rotate(g, U, V, transpose=True)
                        m_rot = kops.two_sided_rotate(m, U, V, transpose=True)
                    step_rot, v = kops.adam_scale(
                        g_rot, m_rot, st["v"], beta2, eps, bc1, bc2
                    )
                    with jax.named_scope("rotate"):
                        back = kops.two_sided_rotate(step_rot, U, V, transpose=False)
                else:
                    with jax.named_scope("rotate"):
                        g_rot = rotate(g, U, V)
                        m_rot = rotate(m, U, V)
                    v = beta2 * st["v"] + (1 - beta2) * jnp.square(g_rot)
                    step_rot = (m_rot / bc1) / (jnp.sqrt(v / bc2) + eps)
                    with jax.named_scope("rotate"):
                        back = unrotate(step_rot, U, V)
                upd = -lr * back
                nst["v"] = v
                if U is not None:
                    nst["U"] = U
                if V is not None:
                    nst["V"] = V
                if L is not None:
                    nst["L"] = L
                if R is not None:
                    nst["R"] = R
            else:
                v = beta2 * st["v"] + (1 - beta2) * jnp.square(g)
                upd = -lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps)
                nst["v"] = v

            updates.append(upd)
            new_leaves.append(nst)

        if weight_decay:
            # decoupled weight decay on matrices only (norms/biases exempt)
            pflat, _ = jax.tree_util.tree_flatten(params)
            updates = [
                u - lr * weight_decay * p.astype(jnp.float32) if p.ndim >= 2 else u
                for u, p in zip(updates, pflat)
            ]
        return jax.tree_util.tree_unflatten(gdef, updates), {"leaves": new_leaves}

    return Optimizer(init, update)
