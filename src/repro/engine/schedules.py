"""Pipeline tick schedules for the SPMD runtime (DESIGN.md §3).

One schedule = one way to order forward/backward work over the `stage` mesh
axis inside a single jitted program. Both schedules share the same contract:

    grad_fn = make_schedule_grad(cfg, mesh, K, M, schedule=...)
    loss, (g_stacked, g_shared) = grad_fn(stage_params, shared, batch)

and both keep the tick body inside `jax.lax.scan`, so trace/jaxpr size is
O(1) in the microbatch count M and the stage count K.

* ``fill_drain`` (GPipe-shaped): M + K - 1 forward ticks collect every
  microbatch's output into an (M, mb, S, d) buffer; reverse-mode autodiff
  through the scanned ppermute schedule generates the backward pipeline.
  Live activation memory is **O(M)** per stage (the staged embeddings and the
  collect buffer, plus the scan residuals autodiff stashes per tick).

* ``1f1b`` (one-forward-one-backward): every tick runs at most one forward
  and one backward microbatch per stage, with activations ppermuted forward
  and activation-gradients ppermuted backward in the same tick body. The
  backward is explicit — per-tick `jax.vjp` against a stashed stage *input*
  (recompute-style, so no residuals accumulate across the scan) — and
  parameter gradients are accumulated in the carry. The stash is a circular
  buffer of 2K - 1 slots: stage k's input for microbatch m is consumed by its
  own backward exactly 2(K-1-k) ticks later, so **O(K)** live activations per
  stage, independent of M. This is the memory property production 1F1B exists
  for; the gradient itself is identical (fp32 tolerance) to fill-drain's.

1F1B tick timetable (t = 0 .. M + 2K - 3):
  forward   F(k, m) at t = k + m
  backward  B(k, m) at t = 2(K-1) - k + m
so the last stage's backward of microbatch m consumes its own fresh forward
output (same tick), and B(k, m) receives the activation gradient B(k+1, m)
sent one tick earlier. Stage warm-up/drain ticks are masked out with
`jnp.where`; `jax.vjp` is linear in the cotangent, so a zero-masked incoming
gradient yields exactly zero parameter/input gradients for idle ticks.

Staleness composes the same for both schedules: the scanned loss is
synchronous, and `stage_delayed_optimizer` imposes the per-stage delay on the
resulting gradient (DESIGN.md §3, staleness semantics).

``data_axis`` is whatever `Topology.schedule_data_axis` hands over: the bare
``"data"`` axis on single-pod meshes or the ``("pod", "data")`` tuple on
pod-replicated ones — every loss/gradient `pmean` spans the full tuple, so
multi-pod runs are combined data + pipeline parallelism, not replicated
training.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import apply_norm
from repro.models.model import _embed, _logits, cast_params, cross_entropy
from repro.models.transformer import block_train

SCHEDULES = ("fill_drain", "1f1b")

# Structural invariants each schedule promises, consumed by the static
# analyzer (`repro.analysis`, DESIGN.md §8). A new schedule MUST declare its
# row here — the matrix runner refuses to audit undeclared schedules:
#   const_float_bytes_in_M  largest live float buffer is O(1) in the
#                           microbatch count (1F1B's O(K) stash property;
#                           fill-drain's buffers legitimately grow with M,
#                           which the analyzer checks as non-vacuous growth)
#   vocab_dot_gated         the O(vocab) LM-head matmul inside the scanned
#                           tick body must sit under a lax.cond (and exist);
#                           schedules computing logits outside the scan set
#                           False — the analyzer still requires zero
#                           ungated vocab dots inside the scan either way
#   stash_bound             the 2K-1 input-stash bound applies
SCHEDULE_INVARIANTS = {
    "fill_drain": {
        "const_float_bytes_in_M": False,
        "vocab_dot_gated": False,
        "stash_bound": False,
    },
    "1f1b": {
        "const_float_bytes_in_M": True,
        "vocab_dot_gated": True,
        "stash_bound": True,
    },
}


def _stage_apply_fn(cfg: ModelConfig):
    """stage_f(wk_raw, x): cast the stage's stacked layers and scan them.

    The cast lives inside so `jax.vjp(stage_f, wk_raw, x)` yields gradients
    with respect to the raw fp32 master weights, exactly like autodiff
    through fill-drain's single outer cast.
    """
    spec = cfg.pattern[0]

    def stage_f(wk_raw, x):
        wk = cast_params(wk_raw, cfg.compute_dtype)

        def body(h, w):
            h, _ = block_train(w, h, cfg, spec)
            return h, None

        x, _ = jax.lax.scan(body, x, wk)
        return x

    return stage_f


def _embed_fn(cfg: ModelConfig):
    def embed_f(shared_raw, tokens_m):
        sh = cast_params(shared_raw, cfg.compute_dtype)
        emb = _embed(sh, cfg, tokens_m)  # (mb, S, d)
        if cfg.learnable_pos_emb:
            emb = emb + sh["pos_emb"][: tokens_m.shape[-1]].astype(emb.dtype)
        return emb

    return embed_f


def _head_fn(cfg: ModelConfig):
    def head_f(shared_raw, h, labels_m):
        sh = cast_params(shared_raw, cfg.compute_dtype)
        x = apply_norm(sh["final_norm"], h)
        logits = _logits(sh, cfg, x)  # (mb, S, V)
        return cross_entropy(logits, labels_m)

    return head_f


# ---------------------------------------------------------------------------
# fill-drain: scanned forward schedule, backward via autodiff
# ---------------------------------------------------------------------------


def make_fill_drain_loss(
    cfg: ModelConfig,
    mesh: Mesh,
    num_stages: int,
    num_microbatches: int,
    stage_axis: str = "stage",
    data_axis: str = "data",
):
    """Returns loss_fn(stage_params, shared_params, batch) -> scalar.

    batch: tokens/labels of shape (M, mb, S) sharded over data on dim 1.
    """
    M = num_microbatches
    stage_f = _stage_apply_fn(cfg)

    def per_device(stage_params, shared, tokens, labels):
        # stage_params leaves arrive as (1, per, ...) local slices
        wk_raw = jax.tree.map(lambda x: x[0], stage_params)
        shared_c = cast_params(shared, cfg.compute_dtype)
        k = jax.lax.axis_index(stage_axis)
        K = num_stages
        mb, S = tokens.shape[1], tokens.shape[2]

        emb = _embed(shared_c, cfg, tokens)  # (M, mb, S, d)
        if cfg.learnable_pos_emb:
            emb = emb + shared_c["pos_emb"][:S].astype(emb.dtype)

        d = emb.shape[-1]
        zeros = jnp.zeros((mb, S, d), emb.dtype)
        out_buf = jnp.zeros((M, mb, S, d), emb.dtype)
        fwd_perm = [(i, i + 1) for i in range(K - 1)]

        # Fill-drain schedule as a scan over ticks: stage 0 injects microbatch
        # t while t < M, the last stage collects microbatch t - (K-1) once it
        # exists. The tick body is traced ONCE — trace/jaxpr size is constant
        # in M and K (the Python-unrolled predecessor was O(M + K)).
        def tick(carry, t):
            recv, out = carry
            inject = jax.lax.dynamic_index_in_dim(
                emb, jnp.minimum(t, M - 1), axis=0, keepdims=False
            )
            inject = jnp.where(t < M, inject, zeros)
            inp = jnp.where(k == 0, inject, recv)
            h = stage_f(wk_raw, inp)
            mb_idx = t - (K - 1)
            collect = (mb_idx >= 0) & (k == K - 1)
            idx = jnp.clip(mb_idx, 0, M - 1)
            cur = jax.lax.dynamic_index_in_dim(out, idx, axis=0, keepdims=False)
            out = jax.lax.dynamic_update_index_in_dim(
                out, jnp.where(collect, h, cur), idx, axis=0
            )
            recv = jax.lax.ppermute(h, stage_axis, fwd_perm)
            return (recv, out), None

        ticks = jnp.arange(M + K - 1)
        (_, out_buf), _ = jax.lax.scan(tick, (zeros, out_buf), ticks)

        x = apply_norm(shared_c["final_norm"], out_buf)
        logits = _logits(shared_c, cfg, x)  # (M, mb, S, V)
        ce = cross_entropy(logits, labels)
        is_last = (k == K - 1).astype(jnp.float32)
        # only the last stage's loss is real; psum over stages, mean over the
        # data axes (a tuple covers the multi-pod (pod, data) case)
        loss = jax.lax.psum(ce * is_last, stage_axis)
        loss = jax.lax.pmean(loss, data_axis)
        return loss

    ln = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(
            P(stage_axis),  # stage params stacked on stage axis
            P(),  # shared params replicated
            P(None, data_axis, None),  # tokens (M, mb, S)
            P(None, data_axis, None),
        ),  # data_axis may be a tuple of mesh axes (multi-pod)
        out_specs=P(),
        check_vma=False,
    )

    def loss_fn(stage_params, shared, batch):
        return ln(stage_params, shared, batch["tokens"], batch["labels"])

    return loss_fn


def make_fill_drain_local_grad(
    cfg: ModelConfig,
    mesh: Mesh,
    num_stages: int,
    num_microbatches: int,
    stage_axis: str = "stage",
    data_axis: str = "data",
):
    """Fill-drain gradient WITHOUT the data-axis reduction (async data mode).

    Returns ``grad_fn(stage_params, shared, batch) ->
    (loss_r, (gs_r, gsh_r))`` where the leading axis of every output is the
    data replica: loss_r ``(R,)``, gs_r ``(R, K, per, ...)``, gsh_r
    ``(R, ...)`` for R = product of the data axes. No collective over the
    data axes appears anywhere in the program — the deferred cross-replica
    mean runs in a separate reduce program off the step critical path.

    The synchronous fill-drain path differentiates OUTSIDE shard_map, where
    the transpose of the replicated-parameter broadcast IS the data-axis
    psum; here `jax.value_and_grad` runs INSIDE the per-device body (of the
    per-device masked loss — the stage psums happen after differentiation),
    so autodiff transposes only the ppermute chain and each replica keeps
    its own local gradient.
    """
    M = num_microbatches
    stage_f = _stage_apply_fn(cfg)

    def per_device(stage_params, shared, tokens, labels):
        k = jax.lax.axis_index(stage_axis)
        K = num_stages
        mb, S = tokens.shape[1], tokens.shape[2]

        def local_loss(stage_params, shared):
            # identical tick schedule to make_fill_drain_loss, minus the
            # final pmean over the data axes
            wk_raw = jax.tree.map(lambda x: x[0], stage_params)
            shared_c = cast_params(shared, cfg.compute_dtype)

            emb = _embed(shared_c, cfg, tokens)  # (M, mb, S, d)
            if cfg.learnable_pos_emb:
                emb = emb + shared_c["pos_emb"][:S].astype(emb.dtype)

            d = emb.shape[-1]
            zeros = jnp.zeros((mb, S, d), emb.dtype)
            out_buf = jnp.zeros((M, mb, S, d), emb.dtype)
            fwd_perm = [(i, i + 1) for i in range(K - 1)]

            def tick(carry, t):
                recv, out = carry
                inject = jax.lax.dynamic_index_in_dim(
                    emb, jnp.minimum(t, M - 1), axis=0, keepdims=False
                )
                inject = jnp.where(t < M, inject, zeros)
                inp = jnp.where(k == 0, inject, recv)
                h = stage_f(wk_raw, inp)
                mb_idx = t - (K - 1)
                collect = (mb_idx >= 0) & (k == K - 1)
                idx = jnp.clip(mb_idx, 0, M - 1)
                cur = jax.lax.dynamic_index_in_dim(
                    out, idx, axis=0, keepdims=False
                )
                out = jax.lax.dynamic_update_index_in_dim(
                    out, jnp.where(collect, h, cur), idx, axis=0
                )
                recv = jax.lax.ppermute(h, stage_axis, fwd_perm)
                return (recv, out), None

            ticks = jnp.arange(M + K - 1)
            (_, out_buf), _ = jax.lax.scan(tick, (zeros, out_buf), ticks)

            x = apply_norm(shared_c["final_norm"], out_buf)
            logits = _logits(shared_c, cfg, x)  # (M, mb, S, V)
            ce = cross_entropy(logits, labels)
            is_last = (k == K - 1).astype(jnp.float32)
            # per-device masked loss, NOT stage-psum'd: psum transposes to
            # psum, so differentiating through an in-body stage psum would
            # seed the cotangent K times (once per stage) and scale every
            # gradient by K. The masked scalar seeds only the last stage's
            # ce; transposed ppermutes carry its cotangent back through the
            # pipeline, exactly like the outer-autodiff sync path.
            return ce * is_last

        loss, (gs, gsh) = jax.value_and_grad(local_loss, argnums=(0, 1))(
            stage_params, shared
        )
        # replicate the loss value across stages AFTER differentiation (the
        # transpose never sees these psums); still no data-axis collective.
        # Shared grads are also summed over stages: each stage holds only its
        # own contribution (embed on stage 0, norm/head on the last stage)
        # and the P(data_axis) out_spec requires stage-replicated values —
        # same tail as the 1f1b unreduced path.
        loss = jax.lax.psum(loss, stage_axis)
        gsh = jax.tree.map(lambda a: jax.lax.psum(a, stage_axis), gsh)
        # add the replica axis: per-device shapes (1,), (1, 1, per, ...),
        # (1, ...) assemble to (R,), (R, K, per, ...), (R, ...) globally
        return (
            loss[None],
            jax.tree.map(lambda a: a[None], gs),
            jax.tree.map(lambda a: a[None], gsh),
        )

    gf = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(
            P(stage_axis),
            P(),
            P(None, data_axis, None),
            P(None, data_axis, None),
        ),
        out_specs=(
            P(data_axis),
            P(data_axis, stage_axis),
            P(data_axis),
        ),
        check_vma=False,
    )

    def grad_fn(stage_params, shared, batch):
        loss_r, gs_r, gsh_r = gf(
            stage_params, shared, batch["tokens"], batch["labels"]
        )
        return loss_r, (gs_r, gsh_r)

    return grad_fn


# ---------------------------------------------------------------------------
# 1F1B: explicit forward/backward ticks, O(K) activation stash
# ---------------------------------------------------------------------------


def stash_slots(num_stages: int) -> int:
    """Circular-buffer depth of the 1F1B input stash.

    Stage k re-reads its forward input 2(K-1-k) ticks later; the worst case
    (stage 0) is 2(K-1), so 2K - 1 slots suffice for every stage and a slot
    is only overwritten after its consumer has read it. The static analyzer
    enforces this as the ``stash_bound`` check: no activation-shaped buffer
    in the traced step may exceed this depth.
    """
    return 2 * num_stages - 1


_stash_slots = stash_slots  # pre-analysis-layer private name


def make_1f1b_grad(
    cfg: ModelConfig,
    mesh: Mesh,
    num_stages: int,
    num_microbatches: int,
    stage_axis: str = "stage",
    data_axis: str = "data",
    reduce_data: bool = True,
):
    """Returns grad_fn(stage_params, shared, batch) -> (loss, (gs, gsh)).

    Explicit-backward 1F1B: no reverse-mode pass over the tick scan, so XLA
    never materialises an O(M) residual/output buffer — the only per-stage
    activation state is the (2K-1, mb, S, d) input stash in the carry.

    ``reduce_data=False`` (async data mode) skips the three data-axis pmeans
    and returns per-replica outputs with a leading replica axis — loss
    ``(R,)``, gs ``(R, K, per, ...)``, gsh ``(R, ...)`` — leaving NO
    collective over the data axes in the program; the deferred cross-replica
    mean runs in a separate reduce program off the step critical path.
    """
    M = num_microbatches
    K = num_stages
    Q = stash_slots(K)
    stage_f = _stage_apply_fn(cfg)
    embed_f = _embed_fn(cfg)
    head_f = _head_fn(cfg)

    def per_device(stage_params, shared, tokens, labels):
        wk_raw = jax.tree.map(lambda x: x[0], stage_params)
        k = jax.lax.axis_index(stage_axis)
        mb, S = tokens.shape[1], tokens.shape[2]
        d = cfg.d_model
        cdt = cfg.compute_dtype
        zeros = jnp.zeros((mb, S, d), cdt)
        fwd_perm = [(i, i + 1) for i in range(K - 1)]
        bwd_perm = [(i + 1, i) for i in range(K - 1)]

        g_stage0 = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), wk_raw)
        g_shared0 = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), shared)
        stash0 = jnp.zeros((Q, mb, S, d), cdt)

        def tick(carry, t):
            fwd_recv, bwd_recv, stash, g_stage, g_shared, loss_acc = carry

            # -- forward: stage k runs microbatch m_f = t - k ----------------
            m_f = t - k
            do_f = (m_f >= 0) & (m_f < M)
            idx_f = jnp.clip(m_f, 0, M - 1)
            tok_f = jax.lax.dynamic_index_in_dim(tokens, idx_f, 0, keepdims=False)
            x_in = jnp.where(k == 0, embed_f(shared, tok_f), fwd_recv)
            x_in = jnp.where(do_f, x_in, zeros)  # idle ticks stash zeros
            h = stage_f(wk_raw, x_in)
            stash = jax.lax.dynamic_update_index_in_dim(stash, x_in, t % Q, 0)

            # -- backward: stage k runs microbatch m_b = t - (2(K-1) - k) ----
            m_b = t - (2 * (K - 1) - k)
            do_b = (m_b >= 0) & (m_b < M)
            idx_b = jnp.clip(m_b, 0, M - 1)
            lbl_b = jax.lax.dynamic_index_in_dim(labels, idx_b, 0, keepdims=False)
            tok_b = jax.lax.dynamic_index_in_dim(tokens, idx_b, 0, keepdims=False)

            # the last stage seeds its backward from this tick's fresh output
            # (m_b == m_f there); every microbatch contributes ce_m / M, which
            # equals fill-drain's joint mean when microbatches are full. The
            # O(vocab) head matmul + its vjp run under a lax.cond so only the
            # last stage pays for them (shard_map stages the body per device,
            # so the cond lowers to a real branch, not a masked select); the
            # other stages' head contributions were zero-masked anyway.
            def head_grads(_):
                ce, head_vjp = jax.vjp(
                    lambda sh, hh: head_f(sh, hh, lbl_b), shared, h
                )
                dsh_head, dh = head_vjp(jnp.float32(1.0 / M))
                return ce, dsh_head, dh

            def head_zeros(_):
                return (
                    jnp.float32(0.0),
                    jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), shared),
                    jnp.zeros(h.shape, h.dtype),
                )

            ce, dsh_head, dh = jax.lax.cond(
                k == K - 1, head_grads, head_zeros, 0
            )
            dy = jnp.where(k == K - 1, dh.astype(cdt), bwd_recv)
            dy = jnp.where(do_b, dy, zeros)

            # recompute-backward at the stashed input: vjp is linear in dy,
            # so masked (zero) ticks contribute exactly zero grads
            x_saved = jax.lax.dynamic_index_in_dim(
                stash, (t - 2 * (K - 1 - k)) % Q, 0, keepdims=False
            )
            _, stage_vjp = jax.vjp(stage_f, wk_raw, x_saved)
            dwk, dx = stage_vjp(dy)
            # stage 0's input grad is the embedding grad (dx is zero-masked)
            (dsh_emb,) = jax.vjp(lambda sh: embed_f(sh, tok_b), shared)[1](dx)

            head_on = (do_b & (k == K - 1)).astype(jnp.float32)
            emb_on = (k == 0).astype(jnp.float32)
            g_stage = jax.tree.map(lambda a, b: a + b, g_stage, dwk)
            g_shared = jax.tree.map(
                lambda a, hh, ee: a + head_on * hh + emb_on * ee,
                g_shared, dsh_head, dsh_emb,
            )
            loss_acc = loss_acc + head_on * ce / M

            fwd_recv = jax.lax.ppermute(h, stage_axis, fwd_perm)
            bwd_recv = jax.lax.ppermute(dx, stage_axis, bwd_perm)
            return (fwd_recv, bwd_recv, stash, g_stage, g_shared, loss_acc), None

        ticks = jnp.arange(M + 2 * (K - 1))
        carry0 = (zeros, zeros, stash0, g_stage0, g_shared0, jnp.float32(0.0))
        (_, _, _, g_stage, g_shared, loss_acc), _ = jax.lax.scan(
            tick, carry0, ticks
        )

        # loss lives on the last stage; grads follow fill-drain's reduction
        # semantics: mean over data replicas, shared grads summed over stages
        if reduce_data:
            loss = jax.lax.pmean(jax.lax.psum(loss_acc, stage_axis), data_axis)
            g_stage = jax.lax.pmean(g_stage, data_axis)
            g_shared = jax.lax.pmean(
                jax.lax.psum(g_shared, stage_axis), data_axis
            )
            g_stage = jax.tree.map(lambda a: a[None], g_stage)  # (1, per, ...)
            return loss, g_stage, g_shared
        # async data mode: stage collectives only, plus a leading replica
        # axis so each replica's local gradient survives to the output
        loss = jax.lax.psum(loss_acc, stage_axis)
        g_shared = jax.lax.psum(g_shared, stage_axis)
        g_stage = jax.tree.map(lambda a: a[None, None], g_stage)
        g_shared = jax.tree.map(lambda a: a[None], g_shared)
        return loss[None], g_stage, g_shared

    out_specs = (
        (P(), P(stage_axis), P()) if reduce_data
        else (P(data_axis), P(data_axis, stage_axis), P(data_axis))
    )
    gf = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(
            P(stage_axis),
            P(),
            P(None, data_axis, None),
            P(None, data_axis, None),
        ),
        out_specs=out_specs,
        check_vma=False,
    )

    def grad_fn(stage_params, shared, batch):
        loss, gs, gsh = gf(stage_params, shared, batch["tokens"], batch["labels"])
        return loss, (gs, gsh)

    return grad_fn


# ---------------------------------------------------------------------------
# Dispatch + memory model
# ---------------------------------------------------------------------------


def make_schedule_grad(
    cfg: ModelConfig,
    mesh: Mesh,
    num_stages: int,
    num_microbatches: int,
    schedule: str = "fill_drain",
    reduce_data: bool = True,
    **kw,
):
    """grad_fn(stage_params, shared, batch) -> (loss, (g_stacked, g_shared)).

    ``reduce_data=False`` returns the UNREDUCED per-replica gradient instead
    — ``(loss_r, (gs_r, gsh_r))`` with a leading data-replica axis and no
    collective over the data axes anywhere in the program (async data mode;
    the deferred cross-replica mean is a separate program).
    """
    if schedule == "fill_drain":
        if not reduce_data:
            return make_fill_drain_local_grad(
                cfg, mesh, num_stages, num_microbatches, **kw
            )
        loss_fn = make_fill_drain_loss(cfg, mesh, num_stages, num_microbatches, **kw)

        def grad_fn(stage_params, shared, batch):
            return jax.value_and_grad(loss_fn, argnums=(0, 1))(
                stage_params, shared, batch
            )

        return grad_fn
    if schedule == "1f1b":
        return make_1f1b_grad(
            cfg, mesh, num_stages, num_microbatches,
            reduce_data=reduce_data, **kw,
        )
    raise ValueError(f"unknown pipeline schedule {schedule!r}; one of {SCHEDULES}")


def schedule_activation_bytes(
    cfg: ModelConfig,
    num_stages: int,
    num_microbatches: int,
    microbatch_size: int,
    seq_len: int,
    schedule: str = "fill_drain",
) -> int:
    """Per-device live activation-buffer bytes held across schedule ticks.

    Counts the (mb, S, d)-shaped buffers a stage keeps alive between ticks —
    the quantity 1F1B bounds at O(K) while fill-drain grows it O(M):

    * fill_drain: staged embeddings (M) + output collect buffer (M) + the
      ppermute recv slot -> (2M + 1) activations.
    * 1f1b: input stash (2K - 1) + forward recv + backward recv
      -> (2K + 1) activations.
    """
    act = (
        microbatch_size * seq_len * cfg.d_model
        * jnp.dtype(cfg.compute_dtype).itemsize
    )
    if schedule == "fill_drain":
        return (2 * num_microbatches + 1) * act
    if schedule == "1f1b":
        return (stash_slots(num_stages) + 2) * act
    raise ValueError(f"unknown pipeline schedule {schedule!r}; one of {SCHEDULES}")
