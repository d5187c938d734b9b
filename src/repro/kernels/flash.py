"""Flash-attention Pallas kernels (online softmax), causal + sliding window.

The attention score matrix is never materialised in HBM: the forward kernel
streams K/V blocks against each Q block, carrying the running row-max m,
normaliser l and output accumulator in VMEM scratch — the TPU-fused version
of the chunked-attention schedule used by the pure-JAX model path
(`repro.models.attention`). BlockSpecs are 128-aligned for the MXU.

The public `flash_attention` is differentiable end to end via
`jax.custom_vjp`: the forward additionally emits the per-row logsumexp
L = m + log l, and the backward is a recompute-style pair of Pallas kernels
(FlashAttention-2 style) that rebuild p = exp(s·scale − L) tile by tile:

* dQ kernel: grid (BH, S/bq, S/bk), K innermost, (bq, dh) f32 accumulator;
  dS = p ⊙ (dO·Vᵀ − D) with D = rowsum(dO ⊙ O), dQ = scale · dS·K.
* dK/dV kernel: grid (BH, S/bk, S/bq), Q innermost, (bk, dh) accumulators;
  dV = pᵀ·dO, dK = scale · dSᵀ·Q.

Row statistics never hit HBM unnormalised: only O and L are saved, so the
residual cost is O(S·dh + S) per head — what the 1F1B input stash budget
assumes (DESIGN.md §9).

TPU tiling: the last two dims of every block must be multiples of (8, 128)
or span the whole array. Per-row statistics therefore cross the kernel
boundary lane-broadcast as (BH, Sp, 128) blocks of (1, bq, 128), the layout
of the TPU flash attention in `jax.experimental.pallas.ops`. Only lane 0 is
kept as the residual L (BH, Sp); the backward broadcasts L and D back to
128 lanes just before its kernels.

Masking: fully-masked tiles are guarded (p forced to 0) so they contribute
nothing to l/acc; fully-masked *rows* produce exactly-zero output and an
L sentinel of NEG_INF. Sequence lengths that do not divide the block sizes
are zero-padded up front and the kernels mask `cols < seq_len`; the padding
is applied with differentiable jnp ops outside the custom_vjp, so cotangents
for padded rows arrive as zeros and contribute nothing to dK/dV.

Layout: inputs are (BH, S, dh) with batch*heads flattened into the leading
grid dimension; grid = (BH, S/bq, S/bk) with the K dimension innermost.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30
_EPS = 1e-30
LANES = 128  # lane width of a TPU vreg: row statistics are broadcast to it


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def _scratch(shapes):
    try:
        from jax.experimental.pallas import tpu as pltpu

        return [pltpu.VMEM(s, jnp.float32) for s in shapes]
    except Exception:  # pragma: no cover
        return [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]


def _tile_mask(iq, ik, bq: int, bk: int, *, causal: bool, window, seq_len: int):
    """Validity mask for the (iq, ik) tile; padded key columns are invalid."""
    rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = cols < seq_len
    if causal:
        mask &= cols <= rows
    if window is not None:
        mask &= cols > rows - window
    return mask


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, L_ref, m_ref, l_ref, acc_ref,
    *, scale: float, bq: int, bk: int, k_steps: int, causal: bool, window,
    seq_len: int,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # (bq, dh)
    k = k_ref[0].astype(jnp.float32)  # (bk, dh)
    v = v_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (bq, bk)

    mask = _tile_mask(iq, ik, bq, bk, causal=causal, window=window,
                      seq_len=seq_len)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]  # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    # guard: in a fully-masked tile m_new can stay ~NEG_INF, making
    # exp(s - m_new) = exp(0) = 1 for every masked entry — without the mask
    # here those 1s pollute l/acc (mean-of-V garbage for fully-masked rows)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ik == k_steps - 1)
    def _done():
        l = l_ref[...]
        o = jnp.where(l > 0.0, acc_ref[...] / jnp.maximum(l, _EPS), 0.0)
        o_ref[0] = o.astype(o_ref.dtype)
        lse = jnp.where(
            l > 0.0, m_ref[...] + jnp.log(jnp.maximum(l, _EPS)), NEG_INF
        )
        L_ref[0] = jnp.broadcast_to(lse, L_ref.shape[1:])


def _flash_forward(cfg, qf, kf, vf):
    """Padded-layout forward: (BH, Sp, dh)³ -> (O (BH,Sp,dh), L (BH,Sp))."""
    causal, window, bq, bk, seq_len, interpret = cfg
    BH, Sp, dh = qf.shape
    scale = 1.0 / math.sqrt(dh)
    k_steps = Sp // bk
    o, L = pl.pallas_call(
        functools.partial(
            _flash_fwd_kernel, scale=scale, bq=bq, bk=bk, k_steps=k_steps,
            causal=causal, window=window, seq_len=seq_len,
        ),
        grid=(BH, Sp // bq, k_steps),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sp, dh), qf.dtype),
            jax.ShapeDtypeStruct((BH, Sp, LANES), jnp.float32),
        ],
        scratch_shapes=_scratch([(bq, 1), (bq, 1), (bq, dh)]),
        interpret=interpret,
        name="flash_fwd",
    )(qf, kf, vf)
    return o, L[..., 0]


# ---------------------------------------------------------------------------
# Backward (recompute from saved O and logsumexp L)
# ---------------------------------------------------------------------------


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, L_ref, D_ref, dq_ref, acc_ref,
    *, scale: float, bq: int, bk: int, k_steps: int, causal: bool, window,
    seq_len: int,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    L = L_ref[0][:, :1]  # (bq, 1) f32, lane 0 of the broadcast block
    D = D_ref[0][:, :1]

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    mask = _tile_mask(iq, ik, bq, bk, causal=causal, window=window,
                      seq_len=seq_len)
    # fully-masked rows carry L = NEG_INF; exp overflows there but the mask
    # zeroes every such entry before it can propagate
    p = jnp.where(mask, jnp.exp(s - L), 0.0)
    dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
    ds = p * (dp - D)
    acc_ref[...] += jnp.dot(ds, k, preferred_element_type=jnp.float32) * scale

    @pl.when(ik == k_steps - 1)
    def _done():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, L_ref, D_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, scale: float, bq: int, bk: int, q_steps: int, causal: bool, window,
    seq_len: int,
):
    ik = pl.program_id(1)
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    L = L_ref[0][:, :1]
    D = D_ref[0][:, :1]

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    mask = _tile_mask(iq, ik, bq, bk, causal=causal, window=window,
                      seq_len=seq_len)
    p = jnp.where(mask, jnp.exp(s - L), 0.0)  # (bq, bk)
    dv_acc[...] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
    dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
    ds = p * (dp - D)
    dk_acc[...] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32) * scale

    @pl.when(iq == q_steps - 1)
    def _done():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_backward(cfg, qf, kf, vf, o, L, do):
    """Padded-layout backward -> (dQ, dK, dV), each (BH, Sp, dh)."""
    causal, window, bq, bk, seq_len, interpret = cfg
    BH, Sp, dh = qf.shape
    scale = 1.0 / math.sqrt(dh)
    q_steps, k_steps = Sp // bq, Sp // bk
    # D_i = rowsum(dO ⊙ O) — cheap elementwise reduce, plain XLA
    D = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    lanes = lambda r: jnp.broadcast_to(r[..., None], (BH, Sp, LANES))
    L, D = lanes(L), lanes(D)

    q_spec = pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0))
    row_spec = pl.BlockSpec((1, bq, LANES), lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, scale=scale, bq=bq, bk=bk, k_steps=k_steps,
            causal=causal, window=window, seq_len=seq_len,
        ),
        grid=(BH, q_steps, k_steps),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((BH, Sp, dh), qf.dtype),
        scratch_shapes=_scratch([(bq, dh)]),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qf, kf, vf, do, L, D)

    # transposed grid: K blocks outer, Q innermost, accumulate over queries
    q_spec_t = pl.BlockSpec((1, bq, dh), lambda b, j, i: (b, i, 0))
    kv_spec_t = pl.BlockSpec((1, bk, dh), lambda b, j, i: (b, j, 0))
    row_spec_t = pl.BlockSpec((1, bq, LANES), lambda b, j, i: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, scale=scale, bq=bq, bk=bk, q_steps=q_steps,
            causal=causal, window=window, seq_len=seq_len,
        ),
        grid=(BH, k_steps, q_steps),
        in_specs=[q_spec_t, kv_spec_t, kv_spec_t, q_spec_t, row_spec_t,
                  row_spec_t],
        out_specs=[kv_spec_t, kv_spec_t],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sp, dh), kf.dtype),
            jax.ShapeDtypeStruct((BH, Sp, dh), vf.dtype),
        ],
        scratch_shapes=_scratch([(bk, dh), (bk, dh)]),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qf, kf, vf, do, L, D)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(cfg, qf, kf, vf):
    o, _ = _flash_forward(cfg, qf, kf, vf)
    return o


def _flash_fwd_rule(cfg, qf, kf, vf):
    o, L = _flash_forward(cfg, qf, kf, vf)
    return o, (qf, kf, vf, o, L)


def _flash_bwd_rule(cfg, res, do):
    qf, kf, vf, o, L = res
    return _flash_backward(cfg, qf, kf, vf, o, L, do)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ---------------------------------------------------------------------------
# Public entry point
# ---------------------------------------------------------------------------


def _plan(
    S: int,
    block_q=None,
    block_k=None,
    *,
    dh: int = 0,
    dtype_name: str = "float32",
    interpret: bool = True,
):
    """Resolve block sizes and the padded sequence length.

    Explicit caller blocks always win. When a block is None, the tuned-plan
    cache (`repro.tune.kernel_plan`, keyed by (kernel, shape, dtype,
    platform)) is consulted at trace time; on a cache miss the default is
    one full-operand tile in interpret mode (one grid step — the
    interpreter pays per grid step, so fewer steps dominate on CPU) and the
    128-aligned MXU tile compiled.
    """
    cap = max(8, _next_pow2(S))
    if block_q is None or block_k is None:
        plan = None
        if dh:
            from repro.tune import kernel_plan

            plan = kernel_plan("flash", (S, dh), dtype_name)
        default = cap if interpret else 128
        if block_q is None:
            block_q = int(plan["block_q"]) if plan else default
        if block_k is None:
            block_k = int(plan["block_k"]) if plan else default
    assert block_q & (block_q - 1) == 0 and block_k & (block_k - 1) == 0, \
        "block sizes must be powers of two"
    bq, bk = min(block_q, cap), min(block_k, cap)
    return bq, bk, _round_up(S, max(bq, bk))


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window=None,
    block_q=None,
    block_k=None,
    interpret: bool = True,
) -> jnp.ndarray:
    """q,k,v: (B, H, S, dh) -> (B, H, S, dh) in q.dtype; differentiable.

    S need not divide the block sizes: inputs are zero-padded to the block
    grid and the pad is sliced back off (padded key columns are masked
    inside the kernels, so numerics are unaffected). ``block_q``/``block_k``
    default to the autotuned plan for this (S, dh, dtype, platform) — see
    `_plan`.
    """
    B, H, S, dh = q.shape
    bq, bk, Sp = _plan(
        S, block_q, block_k, dh=dh, dtype_name=str(q.dtype),
        interpret=interpret,
    )
    BH = B * H
    qf = q.reshape(BH, S, dh)
    kf = k.reshape(BH, S, dh)
    vf = v.reshape(BH, S, dh)
    if Sp != S:
        pad = ((0, 0), (0, Sp - S), (0, 0))
        qf, kf, vf = jnp.pad(qf, pad), jnp.pad(kf, pad), jnp.pad(vf, pad)
    cfg = (causal, window, bq, bk, S, interpret)
    o = _flash(cfg, qf, kf, vf)
    return o[:, :S].reshape(B, H, S, dh)
