"""Layers shared by the plain references (float32, no kernels, no scan).

Written from the layer equations of HomebrewNLP's text model (the flagship
``32big_mixer`` config and its dot-product-attention variant), not from the
program's code paths: named axes become explicit einsums, the reversible
residual stream is a Python loop, parameters are read by the names the
program gives them (that is the only thing taken from the program — the
seeded weights have to be the same ones).  Every matmul runs under
``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul is
otherwise done in bfloat16 passes.

Axes: ``b`` batch, ``s``/``t`` query/key position, ``h`` heads, ``k``
features per head, ``i`` bottleneck ("intermediate"), ``j`` widened
per-head features (``group_linear_factor * k``), ``v`` vocabulary.

Departures from a textbook transformer, all the published model's own:
the norm is per head with a learned scale and shift; gelu's cubic term is
not multiplied by sqrt(2/pi); attention scores are scaled by
``sequence_length ** -0.5``; the output projection is one unit-norm vector
reshaped, so logits at initialisation are small.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

EPS = 1e-5
ROOT = "gpt0"


def highest(fn):
    """Run ``fn`` with float32 matmuls at full precision."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return wrapped


def param(variables, name):
    return jnp.asarray(variables[f"{ROOT}/{name}/var0"], jnp.float32)


def block_params(variables, depth_idx: int, cfg_idx: int, names):
    """``{short: array}`` for one block; ``names`` maps short names to the
    path below the block's scope."""
    scope = f"body0/block{depth_idx}_{cfg_idx}_0"
    return {short: param(variables, f"{scope}/{path}")
            for short, path in names.items()}


def group_norm(x, scale, shift):
    """Per-head normalisation over the last axis, learned scale and shift."""
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS) * scale + shift


def gelu(x):
    inner = x * x * x * 0.044715 + x * math.sqrt(2 / math.pi)
    return x * (jnp.tanh(inner) + 1.0) * 0.5


GROUP_LINEAR = {
    "scale": "norm_0/normal_var0", "shift": "norm_0/normal_var1",
    "w_in": "bottleneck_group_linear_0/orthogonal_var0",
    "w_mid": "bottleneck_group_linear_0/orthogonal_var1",
    "scale_mid": "bottleneck_group_linear_0/normal_var0",
    "shift_mid": "bottleneck_group_linear_0/normal_var1",
    "w_out": "bottleneck_group_linear_0/orthogonal_var2",
}


@jax.jit
@highest
def group_linear_block(p, x):
    """norm -> bottleneck (all heads -> i) -> relu -> widened per-head
    features -> relu -> norm -> per-head projection back to k."""
    y = group_norm(x, p["scale"], p["shift"])
    a = jax.nn.relu(jnp.einsum("bshk,hki->bsi", y, p["w_in"]))
    m = jax.nn.relu(jnp.einsum("bsi,ihj->bshj", a, p["w_mid"]))
    m = group_norm(m, p["scale_mid"], p["shift_mid"])
    return jnp.einsum("bshj,hjk->bshk", m, p["w_out"])


@jax.jit
@highest
def embed(variables, tokens):
    """Factorised token embedding: vocab -> narrow table -> all features."""
    table = param(variables, "input0/gather0/embed0/normal_var0")
    w = param(variables, "input0/orthogonal_var0")[0]          # [i, h, k]
    return jnp.einsum("bsi,ihk->bshk", table[tokens], w)


@jax.jit
@highest
def logits_of(variables, x):
    w = param(variables, "output0/embed0/orthogonal_var0")[:, :, 0, :]
    return jnp.einsum("bshk,hkv->bsv", x, w)


def reversible_stack(src, blocks):
    """The reversible residual stream: both halves start as the embedding,
    every block reads one half and is added to the other, and the output
    is their sum.  ``blocks`` yields callables in execution order."""
    x1 = x2 = src
    for block in blocks:
        x1, x2 = x2, x1 + block(x2)
    return x1 + x2


@jax.jit
def loss_of(logits, targets, z_loss: float):
    """Mean softmax cross-entropy plus ``z_loss`` times the mean squared
    log-partition."""
    log_z = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(log_z - picked) + z_loss * jnp.mean(jnp.square(log_z))
