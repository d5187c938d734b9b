"""The plain reference's own arithmetic."""
import jax
import jax.numpy as jnp
import numpy as np

from bench import spec


def test_power_qr_is_blind_to_the_scale_of_its_input():
    """The basis refresh divides A Q by its largest entry before the QR: a
    Fisher factor at 1e-13, as a small leaf's is, gives the Q that the same
    factor at 1 does, orthogonal."""
    ref = spec.reference_module({"reference": "nanogpt"})
    g = jax.random.normal(jax.random.PRNGKey(0), (48, 96), jnp.float32)
    A = g @ g.T
    eye = jnp.eye(48, dtype=jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    Q1 = ref._power_qr(A, eye, hi)
    Q2 = ref._power_qr(A * 1e-13, eye, hi)
    np.testing.assert_allclose(Q2, Q1, atol=1e-5)
    np.testing.assert_allclose(Q2.T @ Q2, np.eye(48), atol=1e-5)
    assert np.all(np.isfinite(ref._power_qr(jnp.zeros((8, 8)), jnp.eye(8), hi)))
