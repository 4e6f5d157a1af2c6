"""Normalization layer (reference: /root/reference/src/model/normalization.py).

Mean-subtract + RMS rescale with optional learned scale/shift.  The 'group'
flag keeps the head dim out of the normalized axes, giving per-head groupnorm
over features_per_head only (normalization.py:22-34).  The 'rms' flag leaves
the mean where it is: ``x * rsqrt(mean(x^2) + eps)``, RMSNorm.

The computation runs through a fused ``jax.custom_vjp`` core: statistics are
computed in one f32 pass (E[x] and E[x^2] share the read), the output in a
second, and the hand-written backward re-derives x_hat from (x, mu, inv)
instead of saving the centered intermediate.  The composed mtf-style
expression (separate mean-subtract -> rms -> einsum scale -> shift) compiled
to ~4 HBM round-trips per call fwd and more in backward; with 4 norms per
depth-unit at d4096 this was ~23% of the flagship step (round-2 trace:
reduce fusions 243 ms of a 716 ms step).  Same math, fewer passes.
"""
from __future__ import annotations

import functools
import typing

import jax
import jax.numpy as jnp

from ..config import BlockArgs
from ..core.dims import SHAPE, shape_sub
from ..core.tensor import NamedTensor, _align, nt
from .backend import normal_var
from .utils import linear_shapes


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _norm_core(x, scale, shift, axes: typing.Tuple[int, ...], eps: float,
               has_scale: bool, has_shift: bool, center: bool = True):
    y, _, _ = _norm_fwd_impl(x, scale, shift, axes, eps, has_scale, has_shift,
                             center)
    return y


def _norm_fwd_impl(x, scale, shift, axes, eps, has_scale, has_shift,
                   center=True):
    xf = x.astype(jnp.float32)
    if center:
        mu = jnp.mean(xf, axis=axes, keepdims=True)
        # E[x^2] - mu^2 == E[(x-mu)^2]: both reductions share one read of
        # x.  Unlike the subtractive form this can cancel to a small
        # NEGATIVE value when |mu| >> std, and rsqrt(negative) is NaN —
        # clamp at 0
        var = jnp.mean(jnp.square(xf), axis=axes, keepdims=True) \
            - jnp.square(mu)
    else:
        # RMSNorm: the mean of squares itself, nothing subtracted
        mu = jnp.zeros((1,) * x.ndim, jnp.float32)
        var = jnp.mean(jnp.square(xf), axis=axes, keepdims=True)
    inv = jax.lax.rsqrt(jnp.maximum(var, 0.0) + eps)
    y = (xf - mu) * inv
    if has_scale:
        y = y * scale.astype(jnp.float32)
    if has_shift:
        y = y + shift.astype(jnp.float32)
    return y.astype(x.dtype), mu, inv


def _norm_fwd(x, scale, shift, axes, eps, has_scale, has_shift, center=True):
    y, mu, inv = _norm_fwd_impl(x, scale, shift, axes, eps, has_scale,
                                has_shift, center)
    return y, (x, scale, shift, mu, inv)


def _norm_bwd(axes, eps, has_scale, has_shift, center, res, dy):
    x, scale, shift, mu, inv = res
    xf = x.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    xhat = (xf - mu) * inv
    g = dyf * scale.astype(jnp.float32) if has_scale else dyf
    m2 = jnp.mean(g * xhat, axis=axes, keepdims=True)
    # without centering the mean's own gradient path (m1) does not exist
    m1 = jnp.mean(g, axis=axes, keepdims=True) if center else 0.0
    dx = ((g - m1 - xhat * m2) * inv).astype(x.dtype)
    # param cotangents reduce over the axes the (broadcast-shaped) params
    # have size 1; zeros for the unused placeholder operands
    if has_scale:
        bcast = tuple(i for i in range(x.ndim) if scale.shape[i] == 1)
        dscale = jnp.sum(dyf * xhat, axis=bcast, keepdims=True).astype(scale.dtype)
    else:
        dscale = jnp.zeros_like(scale)
    if has_shift:
        bcast = tuple(i for i in range(x.ndim) if shift.shape[i] == 1)
        dshift = jnp.sum(dyf, axis=bcast, keepdims=True).astype(shift.dtype)
    else:
        dshift = jnp.zeros_like(shift)
    return dx, dscale, dshift


_norm_core.defvjp(_norm_fwd, _norm_bwd)


def norm(args: BlockArgs, feature_shape: typing.Optional[SHAPE] = None) -> NamedTensor:
    params = args.params
    block_input = args.tensor
    if feature_shape is None:
        feature_shape = linear_shapes(args).old
    feature_shape = list(feature_shape)
    reduced = feature_shape if "group" not in args.name_extras else \
        shape_sub(feature_shape, params.head_dim)
    normalized_shape = shape_sub(block_input.dims, reduced)

    x = block_input.data
    axes = tuple(i for i, d in enumerate(block_input.dims)
                 if d not in normalized_shape)
    has_scale = "scale" in args.name_extras
    has_shift = "shift" in args.name_extras
    one = jnp.ones((1,) * x.ndim, x.dtype)
    scale = _align(normal_var(args, feature_shape, mean=1), block_input.dims) \
        if has_scale else one
    shift = _align(normal_var(args, feature_shape, mean=0), block_input.dims) \
        if has_shift else one
    out = _norm_core(x, scale, shift, axes, params.norm_epsilon, has_scale,
                     has_shift, "rms" not in args.name_extras)
    return nt(out, block_input.dims)
