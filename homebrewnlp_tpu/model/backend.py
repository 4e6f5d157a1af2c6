"""Variable creation + linear projections.

Mirrors /root/reference/src/model/backend.py semantics on the jax substrate:

- ``OrthogonalInit``: QR-orthogonal init with the reference's exact quirks —
  fan_in comes ONLY from explicitly passed fan_in_dims (the reference
  replaces ``None`` with ``[]`` before its get_fan_in fallback can run,
  backend.py:19-29, so un-hinted orthogonal vars get fan_in=1, i.e. a
  unit-norm vector: this shapes the output-embedding scale and therefore the
  loss trajectory — reproduced faithfully), transpose when fan_out > fan_in,
  sign-fix by diag(R), and 1/sqrt(depth) scaling when scale_by_depth & is_last.
- ``get_var``: cross-layer weight sharing when the ``shared`` flag is present
  (backend.py:50-94): the variable resolves to the depth-0 block's parameter,
  so all depth repetitions of a block-config position share weights.
- ``linear``/``linear_to_features``/``linear_from_features``: einsum with an
  orthogonal var over old+new dims (backend.py:108-118).
"""
from __future__ import annotations

import typing

import numpy as np

from ..config import BlockArgs, ModelParameter
from ..core import scope
from ..core.dims import Dim, SHAPE, deduplicate, shape_size
from ..core.tensor import NamedTensor, einsum


class OrthogonalInit:
    def __init__(self, params: ModelParameter, shape: SHAPE, is_last: bool,
                 fan_in_dims: typing.Optional[SHAPE] = None):
        if fan_in_dims is None:
            fan_in_dims = []
        self.sizes = [d.size for d in shape]
        # contracted-dim names, recorded per parameter at init: serving
        # quantization (core/quant.py) scales per-channel over every
        # NON-contracted axis, which needs to know which axes the consuming
        # einsum sums over
        self.fan_in_names = tuple(d.name for d in fan_in_dims)
        fan_in = int(np.prod([d.size for d in fan_in_dims])) if fan_in_dims else 1
        fan_out = int(np.prod(self.sizes)) // fan_in
        self.transpose = fan_out > fan_in
        self.qr_shape = (fan_out, fan_in) if self.transpose else (fan_in, fan_out)
        self.scale = (params.depth ** -0.5) if (params.scale_by_depth and is_last) else 1.0

    def __call__(self, rng: np.random.Generator, sizes) -> np.ndarray:
        a = rng.standard_normal(self.qr_shape, dtype=np.float32)
        q, r = np.linalg.qr(a)
        q = q * np.sign(np.diagonal(r))
        if self.transpose:
            q = q.T
        return np.reshape(q, self.sizes) * self.scale


class NormalInit:
    def __init__(self, stddev: float = 0.02, mean: float = 0.):
        self.stddev = stddev
        self.mean = mean

    def __call__(self, rng: np.random.Generator, sizes) -> np.ndarray:
        return (rng.standard_normal(sizes, dtype=np.float32) * self.stddev
                + self.mean)


class ConstantInit:
    def __init__(self, value: float = 0.):
        self.value = value

    def __call__(self, rng, sizes) -> np.ndarray:
        return np.full(sizes, self.value, dtype=np.float32)


class UniformInit:
    """``transform(U[low, high))``, elementwise."""

    def __init__(self, low: float, high: float, transform=None):
        self.low, self.high, self.transform = low, high, transform

    def __call__(self, rng: np.random.Generator, sizes) -> np.ndarray:
        value = rng.uniform(self.low, self.high, sizes).astype(np.float32)
        return value if self.transform is None else self.transform(value)


def get_var(args: BlockArgs, shape: SHAPE, initializer) -> NamedTensor:
    """Create/fetch a parameter; resolve to the depth-0 name when shared."""
    params = args.params
    ctx = scope.current()
    shape = list(shape)

    if "shared" not in args.name_extras:
        return scope.get_param("var", shape, initializer,
                               params.slice_dtype, params.calculation_dtype)

    # Shared across depth: canonicalise the body-block scope segment to depth 0
    # (reference keys its cache on block-part index + fn call order,
    # backend.py:53-94 — hierarchical naming gives us the same identity).
    name = ctx.full_name("var")
    canonical = scope.depth0_name(name)
    if ctx.mode == "init" and canonical not in ctx.params:
        scope.new_param(ctx, canonical, shape, initializer, params.slice_dtype)
    return scope.param_tensor(ctx, canonical, shape, params.calculation_dtype)


def orthogonal_var(args: BlockArgs, shape: SHAPE,
                   fan_in_dims: typing.Optional[SHAPE] = None) -> NamedTensor:
    shape = deduplicate(shape)
    return scope.scoped("orthogonal_var", get_var, args, shape,
                        OrthogonalInit(args.params, shape, args.is_last, fan_in_dims))


def normal_var(args: BlockArgs, shape: SHAPE, stddev: float = 0.02,
               mean: float = 0.) -> NamedTensor:
    shape = deduplicate(shape)
    return scope.scoped("normal_var", get_var, args, shape, NormalInit(stddev, mean))


def linear(args: BlockArgs, old: SHAPE, new: SHAPE, contract=einsum
           ) -> NamedTensor:
    """einsum(x, W[old+new]) -> x.shape - old + new (backend.py:108-110).
    ``contract`` stands in for the einsum (same inputs, same output dims):
    the replay-stashed in-projection of model/basic.py."""
    old = list(old)
    new = list(new)
    var = orthogonal_var(args, old + new, old)
    out_shape = deduplicate([d for d in args.tensor.dims if d not in old] + new)
    return contract([args.tensor, var], out_shape)


def linear_to_features(args: BlockArgs,
                       old: typing.Optional[SHAPE] = None) -> NamedTensor:
    return linear(args, old, args.params.feature_dims)


def linear_from_features(args: BlockArgs,
                         new: typing.Optional[SHAPE] = None) -> NamedTensor:
    return linear(args, args.params.feature_dims, new)
