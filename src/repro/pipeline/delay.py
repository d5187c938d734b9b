"""Deterministic gradient-staleness wrapper (weight-stashing semantics).

In PipeDream with weight stashing, the gradient applied at step t on stage k
was computed — forward AND backward — from the weights that stage held at
step t - tau_k. A per-leaf FIFO of gradients of depth tau_k reproduces this
exactly: push the fresh gradient, pop and apply the one from tau_k steps ago.
During the first tau_k steps the queue yields zeros — the pipeline warm-up,
where no update has arrived yet.

This is the deterministic, single-program equivalent of the paper's
virtual-stage simulation (Appendix D.2) and what the convergence benchmarks
run on CPU. In the distributed runtime the same wrapper runs sharded: each
stage's queue lives on that stage's devices, which is precisely weight
stashing's memory footprint (linear in pipeline depth — paper Section 4.3).

``store_params=True`` additionally queues parameter snapshots so
delay-compensation (Zheng et al. 2017) can access w_{t-tau}.
"""
from __future__ import annotations

from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.optim.base import Optimizer


def _push_pop(queue: jnp.ndarray, fresh: jnp.ndarray):
    """queue: (tau, ...). Returns (oldest, new_queue)."""
    with jax.named_scope("fifo"):
        oldest = queue[0]
        new_q = jnp.concatenate([queue[1:], fresh[None].astype(queue.dtype)], axis=0)
        return oldest, new_q


def delayed_optimizer(
    inner: Optimizer,
    delays: Sequence[int],
    store_params: bool = False,
) -> Optimizer:
    """Wrap ``inner`` so each leaf's gradient is applied tau leaf-steps late.

    ``delays``: per-leaf ints ordered like ``jax.tree_util.tree_flatten``.
    """
    delays = [int(d) for d in delays]

    def init(params):
        flat, _ = jax.tree_util.tree_flatten(params)
        assert len(flat) == len(delays), "delay list must match leaf count"
        gq = [
            jnp.zeros((d,) + p.shape, jnp.float32) if d > 0 else None
            for p, d in zip(flat, delays)
        ]
        state = {"inner": inner.init(params), "grad_q": gq}
        if store_params:
            state["param_q"] = [
                jnp.stack([p.astype(jnp.float32)] * d) if d > 0 else None
                for p, d in zip(flat, delays)
            ]
        return state

    def update(grads, state, params, step, aux=None):
        gflat, gdef = jax.tree_util.tree_flatten(grads)
        delayed, new_gq = [], []
        for g, q, d in zip(gflat, state["grad_q"], delays):
            if d == 0:
                delayed.append(g)
                new_gq.append(None)
            else:
                old, nq = _push_pop(q, g)
                delayed.append(old)
                new_gq.append(nq)
        delayed_tree = jax.tree_util.tree_unflatten(gdef, delayed)

        inner_aux = dict(aux or {})
        new_state = {"grad_q": new_gq}
        if store_params:
            pflat, _ = jax.tree_util.tree_flatten(params)
            stale, new_pq = [], []
            for p, q, d in zip(pflat, state["param_q"], delays):
                if d == 0:
                    stale.append(p)
                    new_pq.append(None)
                else:
                    old, nq = _push_pop(q, p)
                    stale.append(old)
                    new_pq.append(nq)
            inner_aux["stale_params"] = jax.tree_util.tree_unflatten(gdef, stale)
            new_state["param_q"] = new_pq

        try:
            updates, inner_state = inner.update(
                delayed_tree, state["inner"], params, step, aux=inner_aux or None
            )
        except TypeError:
            updates, inner_state = inner.update(delayed_tree, state["inner"], params, step)
        new_state["inner"] = inner_state
        return updates, new_state

    return Optimizer(init, update)


def stage_delayed_optimizer(
    inner: Optimizer,
    specs: Sequence,
    num_stages: int,
    store_params: bool = False,
    extra_param_delay: int = 0,
) -> Optimizer:
    """Delay wrapper for the SPMD stage-stacked parameter layout.

    ``specs`` is per-leaf (ordered like ``tree_flatten``): either an int delay
    (shared/replicated leaves — identical to ``delayed_optimizer``) or the
    string ``"stage"`` for leaves whose LEADING axis is the pipeline stage
    (``StageContext.delay_specs`` produces exactly this list).

    For a ``"stage"`` leaf of shape (K, ...), a FIFO of depth K-1 holds the
    last K-1 full gradients; stage k pops the one from tau_k = K-1-k steps
    ago, which after the push/pop algebra is exactly the DIAGONAL read
    ``queue[k][k]`` (the last stage uses the fresh gradient). Sharded over the
    `stage` mesh axis, each device materialises only its own (K-1, 1, ...)
    queue slice — weight stashing's linear-in-depth footprint (paper §4.3).

    During warm-up (t < tau_k) stage k receives zeros, matching the per-leaf
    FIFO semantics of the simulator.

    ``store_params=True`` additionally queues parameter snapshots with the
    same diagonal read, so stage k sees its own w_{t-tau_k} — the stale
    weight version delay compensation linearises around
    (``aux={"stale_params": ...}``). Param queues warm-start with the current
    parameters (during warm-up the "stale" weights ARE the initial weights),
    mirroring ``delayed_optimizer``.

    ``extra_param_delay=D`` deepens only the PARAM queues by D slots, so the
    stale snapshot stage k reads is w_{t-(tau_k+D)} — the total staleness
    when the engine additionally applies a D-step-old deferred data-axis
    reduction to every gradient (async data mode). The grad queues stay at
    pipeline depth: the data-axis delay on gradients is imposed upstream by
    the engine's reduction FIFO, not here.
    """
    K = int(num_stages)
    E = int(extra_param_delay)
    specs = list(specs)

    def _q_shape(p, s):
        if s == "stage":
            return jnp.zeros((K - 1,) + p.shape, jnp.float32) if K > 1 else None
        return jnp.zeros((int(s),) + p.shape, jnp.float32) if int(s) > 0 else None

    def _p_queue(p, s):
        depth = ((K - 1) if s == "stage" else int(s)) + E
        if depth <= 0:
            return None
        return jnp.broadcast_to(p.astype(jnp.float32), (depth,) + p.shape)

    def _pop_push(q, fresh, s):
        """(stale, new_queue) under spec ``s``; fresh is the step's value."""
        if s == "stage":
            # pop: stage k reads the entry pushed K-1-k steps ago (row k),
            # restricted to its own stage slice -> queue diagonal; one
            # gather keeps the traced step O(1) in K
            with jax.named_scope("fifo"):
                idx = jnp.arange(K - 1)
                diag = q[idx, idx]
                stale = jnp.concatenate([diag, fresh[K - 1 :].astype(q.dtype)], axis=0)
                new_q = jnp.concatenate([q[1:], fresh[None].astype(q.dtype)], axis=0)
                return stale, new_q
        return _push_pop(q, fresh)

    def _pop_push_param(q, fresh, s):
        """Param-queue pop with the extra data-axis depth E.

        Queue depth is base+E and q[r] holds the value pushed depth-r steps
        ago, so stage k's w_{t-(tau_k+E)} sits at row k — the SAME diagonal
        read, now defined for every stage (E >= 1 means even the last stage
        reads a queued snapshot instead of the fresh value)."""
        if E == 0:
            return _pop_push(q, fresh, s)
        if s == "stage":
            with jax.named_scope("fifo"):
                idx = jnp.arange(K)
                stale = q[idx, idx]
                new_q = jnp.concatenate([q[1:], fresh[None].astype(q.dtype)], axis=0)
                return stale, new_q
        return _push_pop(q, fresh)

    def init(params):
        flat, _ = jax.tree_util.tree_flatten(params)
        assert len(flat) == len(specs), "delay-spec list must match leaf count"
        state = {
            "inner": inner.init(params),
            "grad_q": [_q_shape(p, s) for p, s in zip(flat, specs)],
        }
        if store_params:
            state["param_q"] = [_p_queue(p, s) for p, s in zip(flat, specs)]
        return state

    def update(grads, state, params, step, aux=None):
        gflat, gdef = jax.tree_util.tree_flatten(grads)
        assert len(gflat) == len(specs), "delay-spec list must match leaf count"
        delayed, new_gq = [], []
        for g, q, s in zip(gflat, state["grad_q"], specs):
            if q is None:
                delayed.append(g)
                new_gq.append(None)
            else:
                old, nq = _pop_push(q, g, s)
                delayed.append(old)
                new_gq.append(nq)
        delayed_tree = jax.tree_util.tree_unflatten(gdef, delayed)

        inner_aux = dict(aux or {})
        new_state = {"grad_q": new_gq}
        if store_params:
            pflat, _ = jax.tree_util.tree_flatten(params)
            stale, new_pq = [], []
            for p, q, s in zip(pflat, state["param_q"], specs):
                if q is None:
                    stale.append(p)
                    new_pq.append(None)
                else:
                    old, nq = _pop_push_param(q, p, s)
                    stale.append(old)
                    new_pq.append(nq)
            inner_aux["stale_params"] = jax.tree_util.tree_unflatten(gdef, stale)
            new_state["param_q"] = new_pq

        try:
            updates, inner_state = inner.update(
                delayed_tree, state["inner"], params, step, aux=inner_aux or None
            )
        except TypeError:
            updates, inner_state = inner.update(delayed_tree, state["inner"], params, step)
        new_state["inner"] = inner_state
        return updates, new_state

    return Optimizer(init, update)


def max_delay(delays: Sequence[int]) -> int:
    return max([int(d) for d in delays] or [0])
