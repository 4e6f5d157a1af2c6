"""Plain reference of Olmo-Hybrid-7B (``olmo_hybrid``): a POST-norm residual
stream in which three gated delta-rule linear-attention layers and one NoPE
QK-norm full-attention layer alternate, each followed by a dense SwiGLU MLP;
RMSNorm, untied embedding and head.

Written from the layer equations of HF ``modeling_olmo_hybrid`` as ISSUE 32
states them (OLMo 2 / OLMo 3 block order, flash-linear-attention's
``GatedDeltaNet``) in float32 ``jax.numpy`` under ``highest`` matmul
precision.  The delta rule is run AS the recurrence: a ``lax.fori_loop`` over
the positions updating a ``[d_v, d_k]`` state a head — no chunks, no
cumulative sums, no triangular solve; the conv is four shifted multiplies;
attention is explicit einsums, one block of queries at a time against all keys
(a ``[30, s, s]`` float32 score tensor is 32 GB at 16k); an explicit Python
loop over the layers; the logits leave in blocks of positions as a HOST array.
Parameters are read by the names the program gives them — the seeded weights
have to be the same ones — and nothing else is taken from it.

With ``E`` the embedding, ``h = E[tokens]``, per layer

    h = h + rms(mixer(h)) * w1               the norm AFTER the sublayer,
    h = h + rms(mlp(h)) * w2                 before the residual add

``logits = (rms(h) * wf) W_head``, ``rms(x) = x / sqrt(mean(x^2) + 1e-6)``
(``rms_norm_eps``).

Gated delta-rule mixer on ``x [b, s, 3840]``, 30 heads, ``d_k`` 96, ``d_v``
192:

    q | k | v = x W_qkv;  z = x W_gate;  b | a = x W_ba
                                             2880, 2880, 5760; 5760; 30, 30
    q, k, v = silu(sum_j w_j (q|k|v)[t-3+j]) causal depthwise conv, 4 taps,
                                             no bias
    q~ = q rsqrt(|q|^2 + 1e-6) / sqrt(96),  k~ = k rsqrt(|k|^2 + 1e-6)
    beta = 2 sigmoid(b)                      linear_allow_neg_eigval
    g = -exp(A_log) softplus(a + dt_bias)
    S <- exp(g_t) S;  u = beta_t (v_t - S k~_t);  S <- S + u k~_t^T
    o_t = S q~_t
    y = rms_192(o) * w_norm * silu(z)        per head, the norm before the gate
    out = y W_out

Attention: ``q = x Wq``, ``k = x Wk``, ``v = x Wv`` (30 heads x 128, 30 K/V
heads), RMSNorm with a learned scale over ALL 3840 features of ``q`` and of
``k`` before the head split, no positions (``rope_theta`` null), ``causal
softmax(q k^T / sqrt(128)) v Wo``.

MLP: ``down(silu(gate(m)) * up(m))``, 3840 -> 11008 -> 3840.

Departures from HF, each the program's too:
- the six input projections of the mixer are the columns of three matrices
  (q | k | v, the gate, b | a: independent normal(0.02) columns either way);
  likewise the three convs are one depthwise conv over the concatenated
  channels.
- the loss is the mean cross-entropy over all positions of the batch
  (``common.loss_of``); HF shifts labels itself and ignores an index.
- the gate ``silu(z)`` and its product with the normed output are float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import common

L2_EPS = 1e-6
DELTA = {
    "w_qkv": "gated_delta_0/normal_var0", "w_gate": "gated_delta_0/normal_var1",
    "w_ba": "gated_delta_0/normal_var2",
    "conv_w": "gated_delta_0/uniform_var0",
    "dt_bias": "gated_delta_0/uniform_var1",
    "a_log": "gated_delta_0/uniform_var2",
    "w_norm": "gated_delta_0/constant_var0",
    "w_out": "gated_delta_0/normal_var3", "w1": "norm_0/normal_var0",
}
ATTENTION = {
    "w_key": "attention_0/normal_var0", "w_query": "attention_0/normal_var1",
    "w_value": "attention_0/normal_var2",
    "scale_query": "attention_0/normal_var3",
    "scale_key": "attention_0/normal_var4",
    "w_out": "attention_0/normal_var5", "w1": "norm_0/normal_var0",
}
MLP = {
    "w_gate": "mlp_0/normal_var0", "w_up": "mlp_0/normal_var1",
    "w_down": "mlp_0/normal_var2", "w2": "norm_0/normal_var0",
}
#: queries a block of the attention reference, positions a block of logits
QUERY_BLOCK = 512
LOGIT_BLOCK = 2048


def rms(x, scale, eps):
    """RMSNorm over ALL trailing axes that ``scale`` has."""
    axes = tuple(range(x.ndim - scale.ndim, x.ndim))
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=axes,
                                      keepdims=True) + eps) * scale


def _conv(x, weight):
    """``y[t] = sum_k weight[k] x[t - (K - 1) + k]``, zeros before the
    sequence: K shifted multiplies."""
    s = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (weight.shape[0] - 1, 0), (0, 0)))
    return sum(padded[:, k:k + s] * weight[k]
               for k in range(weight.shape[0]))


def recurrence(q, k, v, beta, g):
    """The gated delta rule position by position: ``q`` / ``k [b, s, h,
    d_k]`` (normalised), ``v [b, s, h, d_v]``, ``beta`` / ``g [b, s, h]`` ->
    ``o [b, s, h, d_v]``."""
    bsz, s, h, dk = q.shape
    dv = v.shape[-1]

    def step(t, carry):
        state, out = carry                               # [b, h, d_v, d_k]
        state = state * jnp.exp(g[:, t])[..., None, None]
        write = beta[:, t, :, None] * (
            v[:, t] - jnp.einsum("bhvk,bhk->bhv", state, k[:, t]))
        state = state + write[..., :, None] * k[:, t][..., None, :]
        return state, out.at[:, t].set(
            jnp.einsum("bhvk,bhk->bhv", state, q[:, t]))

    _, out = jax.lax.fori_loop(
        0, s, step, (jnp.zeros((bsz, h, dv, dk), jnp.float32),
                     jnp.zeros((bsz, s, h, dv), jnp.float32)))
    return out


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + L2_EPS)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
@common.highest
def delta_block(p, h, heads: int, dk: int, dv: int, neg_eigval: bool,
                eps: float):
    qkv, z, ba = (jnp.einsum("bsgf,gfo->bso", h, p[w])
                  for w in ("w_qkv", "w_gate", "w_ba"))
    d_key = heads * dk
    b_raw, a_raw = jnp.split(ba, 2, axis=-1)
    q, k, v = jnp.split(jax.nn.silu(_conv(qkv, p["conv_w"])),
                        [d_key, 2 * d_key], axis=-1)
    lead = h.shape[:2]
    q = _unit(q.reshape(lead + (heads, dk))) * dk ** -0.5
    k = _unit(k.reshape(lead + (heads, dk)))
    beta = jax.nn.sigmoid(b_raw) * (2.0 if neg_eigval else 1.0)
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(a_raw + p["dt_bias"])
    o = recurrence(q, k, v.reshape(lead + (heads, dv)), beta, g)
    y = rms(o, p["w_norm"], eps).reshape(z.shape) * jax.nn.silu(z)
    return rms(jnp.einsum("bsi,ihd->bshd", y, p["w_out"]), p["w1"], eps)


@functools.partial(jax.jit, static_argnums=(2,))
@common.highest
def _qkv(p, h, eps: float):
    q = rms(jnp.einsum("bsgf,gfhd->bshd", h, p["w_query"]),
            p["scale_query"], eps)
    k = rms(jnp.einsum("bsgf,gfhd->bshd", h, p["w_key"]),
            p["scale_key"], eps)
    return q, k, jnp.einsum("bsgf,gfhd->bshd", h, p["w_value"])


@jax.jit
@common.highest
def _attend(q_block, k, v, first):
    """One block of queries, positions ``first ..``, against all keys."""
    score = jnp.einsum("bshd,bthd->bhst", q_block, k) \
        * q_block.shape[-1] ** -0.5
    causal = (first + jnp.arange(q_block.shape[1]))[:, None] \
        >= jnp.arange(k.shape[1])[None, :]
    weight = jax.nn.softmax(jnp.where(causal, score, -jnp.inf), axis=-1)
    return jnp.einsum("bhst,bthd->bshd", weight, v)


@functools.partial(jax.jit, static_argnums=(3,))
@common.highest
def _project_out(o, w_out, w1, eps: float):
    return rms(jnp.einsum("bsgf,gfhd->bshd", o, w_out), w1, eps)


def attention_block(p, h, eps: float):
    q, k, v = _qkv(p, h, eps)
    s = q.shape[1]
    block = min(s, QUERY_BLOCK)
    o = jnp.concatenate([_attend(q[:, i:i + block], k, v, i)
                         for i in range(0, s, block)], axis=1)
    return _project_out(o, p["w_out"], p["w1"], eps)


@functools.partial(jax.jit, static_argnums=(2,))
@common.highest
def mlp_block(p, h, eps: float):
    gate = jnp.einsum("bsgf,gfi->bsi", h, p["w_gate"])
    up = jnp.einsum("bsgf,gfi->bsi", h, p["w_up"])
    return rms(jnp.einsum("bsi,ihd->bshd", jax.nn.silu(gate) * up,
                          p["w_down"]), p["w2"], eps)


@functools.partial(jax.jit, static_argnums=(3,))
@common.highest
def _logits(h, scale, w_head, eps: float):
    return jnp.einsum("bshd,hdv->bsv", rms(h, scale, eps), w_head)


def mixers(config):
    """The kind of each layer's mixer in one depth unit, read off
    ``block_config``: every even block is a mixer's, every odd one its
    MLP's; the sublayer comes first in its block, its norm after it."""
    kinds = [block["layer"][0].split("-")[0]
             for block in config["block_config"]]
    assert all(k == "mlp" for k in kinds[1::2]), kinds
    assert all(block["layer"][1:] == ["norm-rms-scale"]
               for block in config["block_config"]), config["block_config"]
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

    eps = float(config["norm_epsilon"])
    h = stream(common.param(variables,
                            "input0/gather0/embed0/normal_var0")[tokens])
    for d in range(config["depth"]):
        for i, kind in enumerate(mixers(config)):
            if kind == "gated_delta":
                h = stream(h + delta_block(
                    common.block_params(variables, d, 2 * i, DELTA), h,
                    int(config["delta_heads"]),
                    int(config["delta_key_features"]),
                    int(config["delta_value_features"]),
                    bool(config["delta_allow_neg_eigval"]), eps))
            else:
                h = stream(h + attention_block(
                    common.block_params(variables, d, 2 * i, ATTENTION), h,
                    eps))
            h = stream(h + mlp_block(
                common.block_params(variables, d, 2 * i + 1, MLP), h, eps))
    final = common.param(variables, "output0/lang_out0_0/norm_0/normal_var0")
    head = common.param(variables, "output0/embed0/normal_var0")[:, :, 0, :]
    return np.concatenate([
        np.asarray(_logits(h[:, i:i + LOGIT_BLOCK], final, head, eps))
        for i in range(0, h.shape[1], LOGIT_BLOCK)], axis=1)
