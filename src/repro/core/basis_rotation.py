"""Adam with Basis Rotation (paper Algorithm 1) — the core contribution.

Per rotatable weight matrix W (m x n):
    G_t  <- grad                                  (original space)
    if t % freq == 0:  U,V <- Eigenbasis-Estimation(G_t, M_t, U, V)
    G~ <- U^T G V                                 (rotated gradient)
    M~_t <- b1 M~_{t-1} + (1-b1) G~               (first moment, eigenbasis)
    V~_t <- b2 V~_{t-1} + (1-b2) G~^2             (rotated second moment)
    W <- W - lr * U ( M~ / sqrt(V~ + eps) ) V^T

The paper states M_t in the original space (Appendix G) and rotates it at
use time. Between refreshes U and V are fixed and the EMA is linear, so
U^T M_t V = b1 (U^T M_{t-1} V) + (1-b1) U^T G_t V: the state's ``"m"``
holds M~ = U^T M V (the missing side of a unilateral leaf is the identity),
and a plain step runs two two-sided products (rotate G, unrotate the step)
instead of three. A refresh re-expresses M~ in the new basis,
rotate(unrotate(M~, U, V), U', V') under the ``rebase`` scope inside
``refresh``: exact, since U and V are square and orthogonal (U U^T = I).
Source "1st" forms the original-space M_t it needs inside the refresh
branch. Each matrix of ``"m"`` has the Frobenius norm of the original-space
M. A checkpoint written while ``"m"`` was original-space is not compatible.

Non-rotatable leaves (embeddings, norms, biases, 1-D params) fall back to
plain Adam — exactly the paper's setup — with ``"m"`` in the original space.

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
    axis and ``jnp.where`` keeps stage k's old basis (and its M~) unless
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


def _refresh_ops(g, ops, freq: FreqSpec, step, source: str, beta1: float,
                 beta2: float):
    """Apply the (possibly per-stage) refresh schedule to (U, V, L, R, M~).

    M~ is the previous step's first moment in the basis (U, V); a refresh
    returns it re-expressed in the new basis. Scalar periods keep the single
    ``lax.cond`` on ``step % f == 0`` — the sim backend's bit-for-bit path.
    Tuple periods vectorize: the refresh runs batched over the leaf's leading
    stage axis and a per-stage mask selects, per stage, the refreshed or the
    previous basis, Fisher EMA and M~ (a non-refreshing stage must not
    advance L/R either, matching the cond).
    """

    def do_refresh(o):
        # the scope sits inside the branch: ops of the cond itself (operand
        # copies, the identity branch of a plain step) stay outside it
        with jax.named_scope("refresh"):
            Uo, Vo, Lo, Ro, mo = o
            with jax.named_scope("rebase"):
                m_prev = unrotate(mo, Uo, Vo)
            m = beta1 * m_prev + (1 - beta1) * g if source == "1st" else None
            U, V, L, R = refresh_basis(g, m, Uo, Vo, Lo, Ro, source, beta2)
            with jax.named_scope("rebase"):
                return U, V, L, R, rotate(m_prev, U, V)

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
            nst = dict(st)

            if plan.rotate:
                U, V, L, R, m_prev = _refresh_ops(
                    g,
                    (st.get("U"), st.get("V"), st.get("L"), st.get("R"), st["m"]),
                    f, step, source, beta1, beta2,
                )
                with jax.named_scope("rotate"):
                    g_rot = (rotate(g, U, V) if kops is None else
                             kops.two_sided_rotate(g, U, V, transpose=True))
                m = beta1 * m_prev + (1 - beta1) * g_rot
                if kops is not None:
                    step_rot, v = kops.adam_scale(
                        g_rot, m, st["v"], beta2, eps, bc1, bc2
                    )
                    with jax.named_scope("rotate"):
                        back = kops.two_sided_rotate(step_rot, U, V, transpose=False)
                else:
                    v = beta2 * st["v"] + (1 - beta2) * jnp.square(g_rot)
                    step_rot = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
                    with jax.named_scope("rotate"):
                        back = unrotate(step_rot, U, V)
                upd = -lr * back
                if U is not None:
                    nst["U"] = U
                if V is not None:
                    nst["V"] = V
                if L is not None:
                    nst["L"] = L
                if R is not None:
                    nst["R"] = R
            else:
                m = beta1 * st["m"] + (1 - beta1) * g
                v = beta2 * st["v"] + (1 - beta2) * jnp.square(g)
                upd = -lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps)
            nst["m"] = m
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
