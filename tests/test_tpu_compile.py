"""Compile the Pallas kernels for a described TPU v5e, at real widths.

Nothing runs: each test lowers a kernel with ``interpret=False`` against a
`v5e:2x2` topology description and compiles it with the TPU compiler, which
enforces what interpret mode does not (block tiling, VMEM limits). The
compiled text must hold a ``tpu_custom_call``: the kernel was lowered to
Mosaic, not interpreted. Shapes are paper_95m's (d_model 384, d_ff 1536,
6 heads of 64, seq 512).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and pytest-xdist workers all
import this file. Keep every such compile in this one file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.adam_step import fused_adam_scale
from repro.kernels.flash import flash_attention
from repro.kernels.matmul import matmul


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep such compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


FLASH = dict(causal=True, block_q=128, block_k=128, interpret=False)
B, H, S, DH = 1, 6, 512, 64


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_forward_compiles(one_chip, no_persistent_cache, dtype):
    qkv = [((B, H, S, DH), dtype)] * 3
    text = _compiled_text(
        functools.partial(flash_attention, **FLASH), one_chip, *qkv
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_backward_compiles(one_chip, no_persistent_cache, dtype):
    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, **FLASH).astype(jnp.float32))

    qkv = [((B, H, S, DH), dtype)] * 3
    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), one_chip, *qkv)
    # forward (for the residuals), dQ and dK/dV: three Mosaic kernels
    assert text.count('custom_call_target="tpu_custom_call"') >= 3


@pytest.mark.parametrize("m,k,n", [(384, 384, 1536), (1536, 1536, 384)])
def test_matmul_compiles(one_chip, no_persistent_cache, m, k, n):
    mm = functools.partial(
        matmul, block_m=128, block_n=128, block_k=128, interpret=False
    )
    text = _compiled_text(
        mm, one_chip, ((m, k), jnp.float32), ((k, n), jnp.float32)
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape", [(384, 1536), (1, 384)])
def test_fused_adam_scale_compiles(one_chip, no_persistent_cache, shape):
    def step(g, m, v):
        return fused_adam_scale(
            g, m, v, 0.999, 1e-8, 0.9, 0.99, block_r=256, block_c=256,
            interpret=False,
        )

    text = _compiled_text(step, one_chip, *[(shape, jnp.float32)] * 3)
    assert "tpu_custom_call" in text
