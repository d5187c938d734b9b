"""Single-program simulation of asynchronous pipeline-parallel training.

This is the deterministic equivalent of the paper's virtual-stage setup
(Appendix D.2): the delay pattern of a K-stage PipeDream pipeline is imposed
exactly — per-stage gradient delay tau_k = K-1-k via the FIFO wrapper — while
compute runs as one jitted program. Convergence behaviour (the paper's
experimental subject) depends only on the delay pattern, so this reproduces
Figures 2, 5-10 faithfully on CPU at reduced scale.

Modes:
  * weight stashing (default): gradient FIFO == stashed-weight semantics.
  * weight prediction (PipeMare, Yang et al. 2021): the forward pass runs on
    weights extrapolated tau steps ahead using the optimizer's momentum.
  * no stashing (Gaunt et al. 2017): forward activations and backward
    linearisation use *different* weight versions per stage — the gradient is
    not the gradient of any single point. Implemented with a per-block
    custom_vjp taking two parameter versions.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.model import loss_fn
from repro.optim.base import Optimizer, apply_updates, clip_by_global_norm


# ---------------------------------------------------------------------------
# PipeMare-style weight prediction
# ---------------------------------------------------------------------------


def _find_moments(opt_state: Any) -> Optional[Dict]:
    """Locate Adam-style (m, v) in possibly-wrapped optimizer state."""
    if isinstance(opt_state, dict):
        if "m" in opt_state and "v" in opt_state:
            return {"m": opt_state["m"], "v": opt_state["v"]}
        if "inner" in opt_state:
            return _find_moments(opt_state["inner"])
        if "leaves" in opt_state:
            return {"leaves": opt_state["leaves"]}
    return None


def predict_weights(params, opt_state, delays_tree, lr, eps: float = 1e-8):
    """w_hat = w - lr * tau * m / (sqrt(v) + eps): extrapolate tau steps ahead.

    For basis-rotation state both moments of a rotated leaf live in the
    eigenbasis (`m` holds U^T M V), so the Adam-style ratio is formed there
    and the step rotated back — mirroring the optimizer's own update
    direction. (Dividing original-space M by rotated-space v elementwise
    mixes bases and produces an incoherent prediction.)
    """
    mo = _find_moments(opt_state)
    if mo is None:
        return params
    if "leaves" in mo:  # basis-rotation state: flat leaf list
        from repro.core.rotation import unrotate

        flat, treedef = jax.tree_util.tree_flatten(params)
        dflat, _ = jax.tree_util.tree_flatten(delays_tree)
        new = []
        for p, st, d in zip(flat, mo["leaves"], dflat):
            if d <= 0:
                new.append(p)
                continue
            U, V = st.get("U"), st.get("V")
            step = st["m"] / (jnp.sqrt(st["v"]) + eps)
            if U is not None or V is not None:
                step = unrotate(step, U, V)
            new.append((p - lr * d * step).astype(p.dtype))
        return jax.tree_util.tree_unflatten(treedef, new)
    return jax.tree.map(
        lambda p, m, v, d: (p - lr * d * m / (jnp.sqrt(v) + eps)).astype(p.dtype),
        params,
        mo["m"],
        mo["v"],
        delays_tree,
    )


# ---------------------------------------------------------------------------
# No-stash two-version gradients
# ---------------------------------------------------------------------------


def make_two_version_loss(cfg: ModelConfig) -> Callable:
    """loss(params_fwd, params_bwd, batch): activations from params_fwd,
    backward linearisation at params_bwd. Differentiate w.r.t. arg 1."""
    from repro.models.model import _embed, _logits, _run_blocks_train, cross_entropy
    from repro.models.layers import apply_norm
    from repro.models.transformer import block_train

    assert not cfg.scan_layers, "no-stash simulation requires scan_layers=False"

    @jax.custom_vjp
    def block2w(pf, pb, x, l):
        y, _ = block_train(pf, x, cfg, cfg.pattern[l % len(cfg.pattern)])
        return y

    def block2w_fwd(pf, pb, x, l):
        y, _ = block_train(pf, x, cfg, cfg.pattern[l % len(cfg.pattern)])
        return y, (pf, pb, x, l)

    def block2w_bwd(res, ct):
        pf, pb, x, l = res
        # linearise at the *backward-time* weights (version mismatch)
        _, vjp = jax.vjp(
            lambda p, xx: block_train(p, xx, cfg, cfg.pattern[l % len(cfg.pattern)])[0],
            pb,
            x,
        )
        dpb, dx = vjp(ct)
        dpf = jax.tree.map(jnp.zeros_like, pf)
        return dpf, dpb, dx, None

    block2w.defvjp(block2w_fwd, block2w_bwd)

    def loss2w(params_bwd, params_fwd, batch):
        with jax.named_scope("embed"):
            x = _embed(params_bwd, cfg, batch["tokens"])
            if cfg.learnable_pos_emb:
                x = x + params_bwd["pos_emb"][: x.shape[1]].astype(x.dtype)
        with jax.named_scope("blocks"):
            for l in range(cfg.num_layers):
                x = block2w(params_fwd["blocks"][l], params_bwd["blocks"][l], x, l)
        with jax.named_scope("head"):
            x = apply_norm(params_bwd["final_norm"], x)
            logits = _logits(params_bwd, cfg, x)
            return cross_entropy(logits, batch["labels"])

    return loss2w


# ---------------------------------------------------------------------------
# Train-step factory + driver
# ---------------------------------------------------------------------------


def make_sim_train_step(
    cfg: ModelConfig,
    optimizer: Optimizer,
    grad_clip: float = 1.0,
    weight_prediction: bool = False,
    delays_tree=None,
    schedule=None,
    no_stash: bool = False,
):
    loss2w = make_two_version_loss(cfg) if no_stash else None

    def train_step(params, opt_state, fwd_hist, batch, step):
        fwd_params = params
        if weight_prediction and delays_tree is not None and schedule is not None:
            fwd_params = predict_weights(params, opt_state, delays_tree, schedule(step))

        if no_stash:
            # forward runs on stage-stale snapshots, backward on current
            # params — the version-mismatch pathology of stash-less PipeDream
            loss, grads = jax.value_and_grad(loss2w)(params, fwd_hist, batch)
            metrics = {"ce": loss, "aux": jnp.zeros((), jnp.float32)}
        else:
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                fwd_params, cfg, batch
            )
        if grad_clip:
            with jax.named_scope("clip"):
                grads = clip_by_global_norm(grads, grad_clip)
        with jax.named_scope("opt"):
            updates, opt_state = optimizer.update(grads, opt_state, params, step)
            params = apply_updates(params, updates)
        return params, opt_state, loss, metrics

    # NOTE: no buffer donation — the simulator is CPU-scale and callers often
    # reuse the same initial params across optimizer comparisons.
    return jax.jit(train_step)


def stale_forward_params(history, params, delays_tree):
    """Per-leaf stale parameter tree: the leaf on the stage with delay tau
    comes from the snapshot tau steps ago (its forward-time version)."""
    if delays_tree is None or not history:
        return params
    pflat, treedef = jax.tree_util.tree_flatten(params)
    dflat = jax.tree_util.tree_leaves(delays_tree)
    hists = [jax.tree_util.tree_leaves(h) for h in history]  # oldest..newest
    out = []
    for i, (p, d) in enumerate(zip(pflat, dflat)):
        # history[-1] == current params (appended after the step), so the
        # version from d steps ago lives at history[-1-d]
        age = min(int(d), len(hists) - 1)
        out.append(hists[-1 - age][i] if age > 0 else p)
    return jax.tree_util.tree_unflatten(treedef, out)


def run_sim_training(
    cfg: ModelConfig,
    optimizer: Optimizer,
    data_iter,
    steps: int,
    grad_clip: float = 1.0,
    key=None,
    params=None,
    weight_prediction: bool = False,
    delays_tree=None,
    schedule=None,
    no_stash: bool = False,
    log_every: int = 0,
) -> Tuple[Any, Any, List[float]]:
    """Run `steps` simulated-async steps; returns (params, opt_state, losses).

    Thin wrapper over the unified engine loop (`repro.engine`): builds a
    `SimEngine` and drives it with `run_loop` — the step sequence (and hence
    the fixed-seed loss curve) is unchanged from the pre-engine driver.
    """
    from repro.engine.loop import LoopConfig, run_loop
    from repro.engine.sim import SimEngine

    engine = SimEngine(
        cfg, optimizer, grad_clip, weight_prediction, delays_tree, schedule, no_stash
    )
    state = engine.init_state(params=params, key=key)
    state, losses = run_loop(
        engine, data_iter, LoopConfig(steps=steps, log_every=log_every), state=state
    )
    return state.params, state.opt_state, losses
