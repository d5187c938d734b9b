"""Fused Adam-moment/step Pallas kernel.

The elementwise half of the basis-rotation update — second-moment EMA,
bias correction, rsqrt and step — reads/writes each of (g~, v, m~) exactly
once when fused, instead of one HBM round-trip per op. On TPU this is a
VPU-bound elementwise kernel tiled over (block_r, block_c) VMEM blocks.

Computes (in fp32):
    v'   = b2 * v + (1 - b2) * g~^2
    step = (m~ / bc1) / (sqrt(v' / bc2) + eps)
returning (step, v'). Scalars (b2, eps, bc1, bc2) arrive via a (1, 4) SMEM
operand so the kernel is reusable across training steps without recompiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pltpu = None


def _adam_kernel(scalars_ref, g_ref, m_ref, v_ref, step_ref, v_out_ref):
    b2 = scalars_ref[0, 0]
    eps = scalars_ref[0, 1]
    bc1 = scalars_ref[0, 2]
    bc2 = scalars_ref[0, 3]
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    v_new = b2 * v + (1.0 - b2) * g * g
    # denominator matches the reference exactly: sqrt(v/bc2) + eps (an rsqrt
    # would fold eps inside the root and diverge from ref.py near v ~ 0)
    step = (m / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    step_ref[...] = step.astype(step_ref.dtype)
    v_out_ref[...] = v_new.astype(v_out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_r", "block_c", "interpret")
)
def fused_adam_scale(
    g: jnp.ndarray,
    m: jnp.ndarray,
    v: jnp.ndarray,
    beta2: jnp.ndarray,
    eps: jnp.ndarray,
    bc1: jnp.ndarray,
    bc2: jnp.ndarray,
    *,
    block_r: int = 256,
    block_c: int = 256,
    interpret: bool = True,
):
    """Returns (step_dir, v_new) for 2-D inputs (leading dims: vmap)."""
    R, C = g.shape
    br, bc = min(block_r, R), min(block_c, C)
    pr, pc = (-R) % br, (-C) % bc
    if pr or pc:
        pad = lambda x: jnp.pad(x, ((0, pr), (0, pc)))
        g, m, v = pad(g), pad(m), pad(v)
    Rp, Cp = g.shape
    scalars = jnp.stack(
        [jnp.asarray(beta2, jnp.float32), jnp.asarray(eps, jnp.float32),
         jnp.asarray(bc1, jnp.float32), jnp.asarray(bc2, jnp.float32)]
    )[None, :]

    scalar_spec = pl.BlockSpec((1, 4), lambda i, j: (0, 0))
    if pltpu is not None and not interpret:
        scalar_spec = pl.BlockSpec((1, 4), lambda i, j: (0, 0), memory_space=pltpu.SMEM)

    step, v_new = pl.pallas_call(
        _adam_kernel,
        grid=(Rp // br, Cp // bc),
        in_specs=[
            scalar_spec,
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Rp, Cp), jnp.float32),
            jax.ShapeDtypeStruct((Rp, Cp), jnp.float32),
        ],
        interpret=interpret,
        name="adam_step",
    )(scalars, g, m, v)
    return step[:R, :C], v_new[:R, :C]
