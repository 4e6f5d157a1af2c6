"""Fused-norm custom_vjp (model/normalization.py) regression tests.

The fused core computes variance as E[x^2] - mu^2 (one shared read of x);
unlike the subtractive form this can cancel to a small negative value when
|mu| >> std — the clamp keeps rsqrt finite.  The backward is hand-written;
pin it against autodiff of the composed expression.
"""
import jax
import jax.numpy as jnp
import numpy as np

from backend import make_params  # noqa: F401  (sets up the CPU mesh env)
from homebrewnlp_tpu.model.normalization import _norm_core


def _composed(x, scale, shift, axes, eps):
    mu = jnp.mean(x, axes, keepdims=True)
    c = x - mu
    inv = jax.lax.rsqrt(jnp.mean(c * c, axes, keepdims=True) + eps)
    return c * inv * scale + shift


def large_mean_no_nan_test():
    """|mu| >> std must not produce NaN (catastrophic cancellation in
    E[x^2] - mu^2 goes slightly negative; the clamp catches it)."""
    x = jnp.full((4, 64), 300.0, jnp.float32) + jnp.linspace(0, 1e-3, 64)
    one = jnp.ones((1, 1), jnp.float32)
    y = _norm_core(x, one, one, (1,), 1e-5, False, False)
    assert bool(jnp.isfinite(y).all())
    g = jax.grad(lambda a: _norm_core(a, one, one, (1,), 1e-5, False,
                                      False).sum())(x)
    assert bool(jnp.isfinite(g).all())


def fused_matches_autodiff_test():
    """Forward and all three gradients match autodiff of the composed
    expression, for group (last-axis) and full-feature reductions."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 16, 2, 8)) * 2 + 0.5, jnp.float32)
    scale = jnp.asarray(rng.standard_normal((1, 1, 2, 8)) + 1, jnp.float32)
    shift = jnp.asarray(rng.standard_normal((1, 1, 2, 8)), jnp.float32)
    for axes in ((3,), (2, 3)):
        y1 = _composed(x, scale, shift, axes, 1e-5)
        y2 = _norm_core(x, scale, shift, axes, 1e-5, True, True)
        np.testing.assert_allclose(y2, y1, atol=5e-6)
        g1 = jax.grad(lambda *a: _composed(*a, axes, 1e-5).sum(),
                      argnums=(0, 1, 2))(x, scale, shift)
        g2 = jax.grad(lambda *a: _norm_core(*a, axes, 1e-5, True, True).sum(),
                      argnums=(0, 1, 2))(x, scale, shift)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(b, a, atol=1e-4, rtol=1e-5)
