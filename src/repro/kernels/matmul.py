"""Tiled matmul Pallas kernel — the basis-rotation hot spot.

Every basis-rotation step performs two two-sided rotations per weight matrix
(U^T G V and U X V^T), i.e. four (m x m)(m x n)-class matmuls. On TPU these
are MXU work; the kernel tiles all three dims with 128-aligned BlockSpecs so
each (block_m x block_k) x (block_k x block_n) product fits VMEM, accumulates
in an fp32 VMEM scratch across the k grid dimension, and writes the output
tile once on the last k step.

Grid: (m / bm, n / bn, k / bk) with k innermost ("arbitrary" semantics — the
accumulator carries across k steps; m/n are parallel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...].astype(jnp.float32),
        b_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pad_to(x: jnp.ndarray, mult0: int, mult1: int) -> jnp.ndarray:
    p0 = (-x.shape[0]) % mult0
    p1 = (-x.shape[1]) % mult1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret", "out_dtype"),
)
def matmul(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = True,
    out_dtype=jnp.float32,
) -> jnp.ndarray:
    """C = A @ B via the tiled Pallas kernel. a: (M,K), b: (K,N).

    Inputs are zero-padded up to tile multiples and the result sliced back,
    so arbitrary shapes are accepted; MXU-aligned shapes take the fast path.
    """
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, f"contraction mismatch {a.shape} x {b.shape}"
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    a_p = _pad_to(a, bm, bk)
    b_p = _pad_to(b, bk, bn)
    Mp, Kp = a_p.shape
    Np = b_p.shape[1]
    k_steps = Kp // bk

    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]

    out = pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=k_steps),
        grid=(Mp // bm, Np // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        name="matmul",
    )(a_p, b_p)
    return out[:M, :N]
