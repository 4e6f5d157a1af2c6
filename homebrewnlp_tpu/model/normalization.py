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


def _norm_bwd_xla(axes, eps, has_scale, has_shift, res, dy, center=True):
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


# ---- one-pass pallas backward --------------------------------------------
#
# The XLA backward above performs two reductions along the FEATURE axes
# (m1, m2 — row reductions) and two along the BATCH axes (dscale, dshift —
# column reductions) over the same (x, dy) tensors.  XLA cannot multi-output
# -fuse reductions over different dimension sets, so the step trace shows
# separate HBM passes for each family — the "reduce fusions at 22%"
# weight-gradient cost named in docs/PERFORMANCE.md.  This kernel streams
# row blocks once on a PARALLEL grid: per-row statistics and dx in
# registers, per-block dscale/dshift PARTIAL sums written to a [nb, H, F]
# output and reduced outside the kernel.

def _norm_bwd_kernel(x_ref, dy_ref, scale_ref, dx_ref, dsc_ref, dsh_ref, *,
                     eps: float, has_scale: bool, has_shift: bool):
    xf = x_ref[...].astype(jnp.float32)          # [block_r, H, F]
    dyf = dy_ref[...].astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True) - mu * mu
    inv = jax.lax.rsqrt(jnp.maximum(var, 0.0) + eps)
    xhat = (xf - mu) * inv
    g = dyf * scale_ref[...][None].astype(jnp.float32) if has_scale else dyf
    m1 = jnp.mean(g, axis=-1, keepdims=True)
    m2 = jnp.mean(g * xhat, axis=-1, keepdims=True)
    dx_ref[...] = ((g - m1 - xhat * m2) * inv).astype(dx_ref.dtype)
    # per-block PARTIAL column sums (summed outside) keep the grid fully
    # parallel.  NOTE: both this form and the earlier sequential
    # accumulating grid measured the SAME 26.5k -> 20.1k tok/s regression on
    # the flagship step — the cost is the kernel's fusion boundary, not the
    # grid semantics (docs/PERFORMANCE.md round 3)
    dsc_ref[...] = (jnp.sum(dyf * xhat, axis=0) if has_scale
                    else jnp.zeros_like(dsc_ref))
    dsh_ref[...] = (jnp.sum(dyf, axis=0) if has_shift
                    else jnp.zeros_like(dsh_ref))


def _norm_bwd_pallas(axes, eps, has_scale, has_shift, res, dy,
                     interpret: bool = False):
    """One-pass fused backward.  Returns None when the layout doesn't fit the
    kernel (caller falls back to the XLA path): needs trailing contiguous
    reduce axes, lane-aligned features, and a row count divisible into
    blocks.  Statistics are recomputed from x in VMEM (cheaper than reading
    saved mu/inv from HBM)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    x, scale, shift, mu, inv = res
    nd = x.ndim
    if axes != tuple(range(nd - len(axes), nd)):
        return None  # reduce axes must be the trailing block
    param = scale if has_scale else shift
    lead = 0
    while lead < nd and param.shape[lead] == 1:
        lead += 1
    if lead > nd - len(axes):
        lead = nd - len(axes)
    if (param.shape[lead:] != x.shape[lead:]
            or (has_scale and has_shift and scale.shape != shift.shape)):
        return None  # params must cover exactly the trailing dims
    import math
    rows = math.prod(x.shape[:lead])
    h = math.prod(x.shape[lead:nd - len(axes)])
    f = math.prod(x.shape[nd - len(axes):])
    if f % 128 or rows < 2:
        return None
    block_r = 1
    # ~2MB per f32 working array (x, dy, dx live simultaneously in VMEM)
    for cand in (256, 128, 64, 32, 16, 8, 4, 2):
        if rows % cand == 0 and cand * h * f * 4 <= 2 * 2 ** 20:
            block_r = cand
            break
    else:
        return None

    x3 = x.reshape(rows, h, f)
    dy3 = dy.reshape(rows, h, f)
    scale2 = (scale if has_scale else shift).reshape(h, f)
    nb = rows // block_r
    kernel = functools.partial(_norm_bwd_kernel, eps=eps,
                               has_scale=has_scale, has_shift=has_shift)
    dx3, dsc, dsh = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((block_r, h, f), lambda i: (i, 0, 0)),
                  pl.BlockSpec((block_r, h, f), lambda i: (i, 0, 0)),
                  pl.BlockSpec((h, f), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((block_r, h, f), lambda i: (i, 0, 0)),
                   pl.BlockSpec((None, h, f), lambda i: (i, 0, 0)),
                   pl.BlockSpec((None, h, f), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, h, f), x.dtype),
                   jax.ShapeDtypeStruct((nb, h, f), jnp.float32),
                   jax.ShapeDtypeStruct((nb, h, f), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x3, dy3, scale2)
    dx = dx3.reshape(x.shape)
    dscale = dsc.sum(0).reshape(scale.shape).astype(scale.dtype) if has_scale \
        else jnp.zeros_like(scale)
    dshift = dsh.sum(0).reshape(shift.shape).astype(shift.dtype) if has_shift \
        else jnp.zeros_like(shift)
    return dx, dscale, dshift


# The kernel is OFF by default: measured on the flagship 32big_mixer step it
# REGRESSES 26.5k -> 20.1k tokens/sec (identical with sequential-accumulating
# and fully-parallel grids).  The pallas call is an opaque fusion boundary:
# XLA was already folding the norm-backward elementwise work into the
# adjacent matmul/reduce fusions, and forcing x and dy through a standalone
# kernel materialises ~0.5GB of bf16 operands per call that previously never
# hit HBM as standalone tensors — costing more than the saved reduction
# passes.  Kept (tested, numerics-pinned) for layouts where the fusion
# context differs; enable with HBNLP_NORM_BWD_PALLAS=1.
def _norm_bwd(axes, eps, has_scale, has_shift, center, res, dy):
    import os
    if (center and (has_scale or has_shift)
            and jax.default_backend() == "tpu"
            and os.environ.get("HBNLP_NORM_BWD_PALLAS") == "1"):
        out = _norm_bwd_pallas(axes, eps, has_scale, has_shift, res, dy)
        if out is not None:
            return out
    return _norm_bwd_xla(axes, eps, has_scale, has_shift, res, dy, center)


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
    out = _norm_core(x, scale, shift, axes, 1e-5, has_scale, has_shift,
                     "rms" not in args.name_extras)
    return nt(out, block_input.dims)
