"""Plain reference of granite-4.0-h-micro (``granitemoehybrid``): a pre-norm
residual stream of Mamba-2 layers and a few NoPE grouped-query attention
layers, each followed by a dense SwiGLU MLP, RMSNorm, three multipliers and a
head tied to the embedding.

Written from HF ``modeling_granitemoehybrid.py``'s layer equations (the Mamba
layer's ``torch_forward``, ``GraniteMoeHybridAttention`` with
``position_embedding_type`` ``nope``, ``GraniteMoeHybridMLP``,
``GraniteMoeHybridRMSNormGated``) in float32 ``jax.numpy`` under ``highest``
matmul precision.  The state-space recurrence is run AS the recurrence: a
``lax.fori_loop`` over the positions updating the state, no chunks, no
cumulative sums; the conv is four shifted multiplies; attention is explicit
einsums with K and V repeated over their groups, one block of queries at a
time against all keys (a ``[32, s, s]`` float32 score tensor is 34 GB at
16k); an explicit Python loop over the layers; the logits leave in blocks of
positions as a HOST array (``[16384, 100352]`` float32 is 6.6 GB).
Parameters are read by the names the program gives them — the seeded weights
have to be the same ones — and nothing else is taken from it.

With ``E`` the embedding, ``h = 12 E[tokens]`` (``embedding_multiplier``),
per layer

    h = h + 0.22 mixer(rms(h) * w1)          residual_multiplier
    h = h + 0.22 mlp(rms(h) * w2)

``logits = (rms(h) * wf) E^T / 8`` (``logits_scaling``; the head is tied),
``rms(x) = x / sqrt(mean(x^2) + 1e-5)``.

Mamba-2 mixer on ``u [b, s, 2048]``, ``d_inner`` 4096 = 64 heads x 64, state
128, one group:

    z, xBC, dt = split(u W_in)               4096, 4352, 64 columns, no bias
    xBC = silu(bias + sum_k w_k xBC[t-3+k])  causal depthwise conv, width 4
    x, B, C = split(xBC)                     4096, 128, 128
    dt = softplus(dt + dt_bias);  A = -exp(A_log)           per head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T              S: [64, 128]
    y_t = S_t C_t + D x_t
    y = rms(y * silu(z)) * w_norm            gate first, norm over all 4096
    out = y W_out

Attention on ``a = rms(h) * w1``: ``q = a Wq`` (32 heads x 64), ``k = a Wk``,
``v = a Wv`` (8 heads x 64), no positions, K/V head ``j`` serves query heads
``4j .. 4j + 3``, ``causal softmax(0.015625 q k^T) v Wo``
(``attention_multiplier``, not ``1 / sqrt(64)``).

MLP: ``down(silu(gate(m)) * up(m))``; HF fuses gate and up into one
``input_linear`` and chunks it: first half gate, second half up.

Departures from HF, each the program's too:
- the loss is the mean cross-entropy over all positions of the batch
  (``common.loss_of``); HF shifts labels itself and ignores an index.
- the gate ``silu(z)`` and the product ``y * silu(z)`` are float32 here; HF
  computes them in the activations' dtype.
- HF's ``time_step_limit`` (0, inf) and its ``time_step_min`` clamp change
  nothing and are left out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import common

EPS = 1e-5
MAMBA = {
    "w1": "norm_0/normal_var0",
    "w_in": "mamba_0/normal_var0", "conv_w": "mamba_0/uniform_var0",
    "conv_b": "mamba_0/uniform_var1", "dt_bias": "mamba_0/uniform_var2",
    "a_log": "mamba_0/uniform_var3", "d": "mamba_0/constant_var0",
    "w_norm": "mamba_0/constant_var1", "w_out": "mamba_0/normal_var1",
}
ATTENTION = {
    "w1": "norm_0/normal_var0",
    "w_key": "attention_0/normal_var0", "w_query": "attention_0/normal_var1",
    "w_value": "attention_0/normal_var2", "w_out": "attention_0/normal_var3",
}
MLP = {
    "w2": "norm_0/normal_var0",
    "w_gate": "mlp_0/normal_var0", "w_up": "mlp_0/normal_var1",
    "w_down": "mlp_0/normal_var2",
}
#: queries a block of the attention reference, positions a block of logits
QUERY_BLOCK = 512
LOGIT_BLOCK = 2048


def rms(x, scale):
    """RMSNorm over ALL trailing axes that ``scale`` has."""
    axes = tuple(range(x.ndim - scale.ndim, x.ndim))
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=axes,
                                      keepdims=True) + EPS) * scale


def _conv(x, weight, bias):
    """``y[t] = bias + sum_k weight[k] x[t - 3 + k]``, zeros before the
    sequence: four shifted multiplies."""
    s = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (weight.shape[0] - 1, 0), (0, 0)))
    return bias + sum(padded[:, k:k + s] * weight[k]
                      for k in range(weight.shape[0]))


def recurrence(x, dt, a, b_mat, c_mat):
    """``y_t = S_t C_t`` with ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``,
    position by position: ``x [b, s, h, p]``, ``dt [b, s, h]``, ``a [h]``,
    ``b_mat`` / ``c_mat`` ``[b, s, n]`` -> ``[b, s, h, p]``."""
    bsz, s, h, p = x.shape
    n = b_mat.shape[-1]

    def step(t, carry):
        state, ys = carry
        keep = jnp.exp(dt[:, t] * a)                               # [b, h]
        state = state * keep[..., None, None] \
            + (dt[:, t, :, None] * x[:, t])[..., None] \
            * b_mat[:, t, None, None, :]
        y = jnp.einsum("bhpn,bn->bhp", state, c_mat[:, t])
        return state, ys.at[:, t].set(y)

    _, ys = jax.lax.fori_loop(
        0, s, step, (jnp.zeros((bsz, h, p, n), jnp.float32),
                     jnp.zeros((bsz, s, h, p), jnp.float32)))
    return ys


@functools.partial(jax.jit, static_argnums=(2, 3))
@common.highest
def mamba_block(p, h, heads: int, state: int):
    u = rms(h, p["w1"])
    proj = jnp.einsum("bsgf,gfo->bso", u, p["w_in"])
    d_inner = p["w_out"].shape[0]
    z, xbc, dt = jnp.split(proj, [d_inner, proj.shape[-1] - heads], axis=-1)
    xbc = jax.nn.silu(_conv(xbc, p["conv_w"], p["conv_b"]))
    x, b_mat, c_mat = jnp.split(xbc, [d_inner, d_inner + state], axis=-1)
    x = x.reshape(x.shape[:2] + (heads, d_inner // heads))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(x, dt, -jnp.exp(p["a_log"]), b_mat, c_mat) \
        + p["d"][:, None] * x
    y = rms(y.reshape(z.shape) * jax.nn.silu(z), p["w_norm"])
    return jnp.einsum("bsi,ihd->bshd", y, p["w_out"])


@jax.jit
@common.highest
def _qkv(p, h):
    a = rms(h, p["w1"])
    return (jnp.einsum("bsgf,gfhd->bshd", a, p["w_query"]),
            jnp.einsum("bsgf,gfhd->bshd", a, p["w_key"]),
            jnp.einsum("bsgf,gfhd->bshd", a, p["w_value"]))


@functools.partial(jax.jit, static_argnums=(4,))
@common.highest
def _attend(q_block, k, v, first, scale: float):
    """One block of queries, positions ``first ..``, against all keys."""
    group = q_block.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    score = jnp.einsum("bshd,bthd->bhst", q_block, k) * scale
    causal = (first + jnp.arange(q_block.shape[1]))[:, None] \
        >= jnp.arange(k.shape[1])[None, :]
    weight = jax.nn.softmax(jnp.where(causal, score, -jnp.inf), axis=-1)
    return jnp.einsum("bhst,bthd->bshd", weight, v)


@jax.jit
@common.highest
def _project_out(o, w_out):
    return jnp.einsum("bsgf,gfhd->bshd", o, w_out)


def attention_block(p, h, scale: float):
    q, k, v = _qkv(p, h)
    s = q.shape[1]
    block = min(s, QUERY_BLOCK)
    o = jnp.concatenate([_attend(q[:, i:i + block], k, v, i, scale)
                         for i in range(0, s, block)], axis=1)
    return _project_out(o, p["w_out"])


@jax.jit
@common.highest
def mlp_block(p, h):
    m = rms(h, p["w2"])
    gate = jnp.einsum("bsgf,gfi->bsi", m, p["w_gate"])
    up = jnp.einsum("bsgf,gfi->bsi", m, p["w_up"])
    return jnp.einsum("bsi,ihd->bshd", jax.nn.silu(gate) * up, p["w_down"])


@jax.jit
@common.highest
def _logits(h, scale, table, divisor):
    return jnp.einsum("bshd,vhd->bsv", rms(h, scale), table) / divisor


def _mixers(config):
    """The kind of each layer's mixer, in order, read off ``block_config``:
    every even block is a mixer's, every odd one its MLP's."""
    kinds = [block["layer"][-1].split("-")[0]
             for block in config["block_config"]]
    assert all(k == "mlp" for k in kinds[1::2]), kinds
    return kinds[0::2]


def forward(variables, tokens, config, stream_dtype=None):
    """Logits ``[b, s, vocab]`` (float32, a host array) for ``tokens [b,
    s]``.  ``stream_dtype``: round the residual stream to it after the
    embedding and after every block — not the model, but what a lower
    activation precision than the configuration's does to it; the tests and
    PERF.md show that float8 misses the bound that bfloat16 meets."""
    def stream(h):
        return h if stream_dtype is None \
            else h.astype(stream_dtype).astype(jnp.float32)

    table = common.param(variables, "input0/gather0/embed0/normal_var0")
    h = stream(table[tokens] * config["embedding_multiplier"])
    scale = config["attention_scale"] or config["features_per_head"] ** -0.5
    mult = config["residual_multiplier"]
    for d in range(config["depth"]):
        for i, kind in enumerate(_mixers(config)):
            if kind == "mamba":
                h = stream(h + mult * mamba_block(
                    common.block_params(variables, d, 2 * i, MAMBA), h,
                    int(config["mamba_heads"]), int(config["mamba_state"])))
            else:
                h = stream(h + mult * attention_block(
                    common.block_params(variables, d, 2 * i, ATTENTION), h,
                    float(scale)))
            h = stream(h + mult * mlp_block(
                common.block_params(variables, d, 2 * i + 1, MLP), h))
    final = common.param(variables, "output0/lang_out0_0/norm_0/normal_var0")
    return np.concatenate([
        np.asarray(_logits(h[:, i:i + LOGIT_BLOCK], final, table,
                           float(config["logits_scaling"])))
        for i in range(0, h.shape[1], LOGIT_BLOCK)], axis=1)
