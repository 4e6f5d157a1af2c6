"""Activation registry (reference: /root/reference/src/model/activation.py).

The reference hand-writes forward AND backward slicewise kernels for
mish/silu/lecun_tanh/softsign because mtf can't differentiate through
``cwise``; under jax every one of these is a plain jnp expression with native
AD, and XLA fuses them into the surrounding matmuls.
"""
from __future__ import annotations

import numpy as np

from ..config import BlockArgs
from ..core import scope
from ..core.tensor import (NamedTensor, multiply, sigmoid as _sigmoid,
                           softplus, tanh as _tanh, unary)
import jax
import jax.numpy as jnp


def _gelu(args: BlockArgs) -> NamedTensor:
    """tanh-approx gelu — the reference's formula (activation.py:158-161),
    as ONE fused scalar expression.

    The historical spelling built the cubic and the final product through
    ``einsum([x, x, x, const])`` with NamedTensor scalar constants; on the
    profiled flagship step each constant materialised as a full
    activation-shaped broadcast instruction with multiple fusion users
    (~4% of step time pure broadcast traffic — docs/PERFORMANCE.md 'Round
    11').  The single jnp expression keeps every constant scalar inside
    one fusion.  Same formula and dtype; product association differs by
    <= 1 bf16 ulp (step-loss parity to 4 decimals verified in the round-11
    A/B; tests/basic_pointwise_test.py pins the closed form)."""
    x = args.tensor

    def f(v):
        c = np.float32(0.044715).astype(v.dtype)
        s = np.float32(np.sqrt(2 / np.pi)).astype(v.dtype)
        inner = v * v * v * c + v * s
        return v * (jnp.tanh(inner) + np.float32(1).astype(v.dtype)) \
            * np.float32(0.5).astype(v.dtype)
    return unary(f, x)


def _relu(args):
    return unary(jax.nn.relu, args.tensor)


def _relu2(args):
    # relu(x)^2 (Primer's squared ReLU; Nemotron's ungated experts)
    return unary(lambda x: jnp.square(jax.nn.relu(x)), args.tensor)


def _sigmoid_fn(args):
    return _sigmoid(args.tensor)


def _tanh_fn(args):
    return _tanh(args.tensor)


def _lecun_tanh(args):
    # tanh(x) + 0.1 * x (activation.py:93-94)
    return unary(lambda x: jnp.tanh(x) + x * 0.1, args.tensor)


def _silu(args):
    return unary(lambda x: x * jax.nn.sigmoid(x), args.tensor)


def _mish(args):
    return multiply(_tanh(softplus(args.tensor)), args.tensor)


def _softsign(args):
    # x / (1 + |x|) (activation.py:126-127)
    return unary(lambda x: x / (1. + jnp.abs(x)), args.tensor)


def _exp(args):
    return unary(jnp.exp, args.tensor)


ACTIVATIONS = {'relu': _relu,
               'relu2': _relu2,
               'sigmoid': _sigmoid_fn,
               'tanh': _tanh_fn,
               'gelu': _gelu,
               'lecun_tanh': _lecun_tanh,
               'silu': _silu,
               'mish': _mish,
               'mtf_mish': _mish,
               'softsign': _softsign,
               'exp': _exp,
               }


def activate(args: BlockArgs) -> NamedTensor:
    """First recognised activation flag wins; identity otherwise
    (activation.py:200-211)."""
    for fn_name in args:
        if fn_name not in ACTIVATIONS:
            continue
        return scope.scoped(fn_name, ACTIVATIONS[fn_name], args)
    return args.tensor
