"""Plain reference of Kimi-Linear-48B-A3B (``kimi_linear``) as ONE RANK of an
expert-parallel group holds it: a pre-norm residual stream of Kimi Delta
Attention layers and, one in four, a latent attention layer without
positions; a leading dense SwiGLU MLP, then a 256-way top-8 sigmoid router
with a selection bias over the experts held here beside one shared expert;
RMSNorm (eps 1e-5), an untied head.

Written from the published ``config.json``, the Kimi Linear report
(arXiv:2510.26692), flash-linear-attention's ``KimiDeltaAttention`` and the
layer equations of ISSUE 58 in float32 ``jax.numpy`` under ``highest`` matmul
precision.  No kernel and nothing of ``homebrewnlp_tpu``: the delta rule is
run AS the recurrence, position by position, with the state a ``[d_k, d_v]``
matrix a head; attention is explicit einsums one block of queries against
all keys; the top-k is a stable ranking; every held expert runs on every
token and is weighted by the token's weight for it (zero where not chosen).
Parameters are read by the names the program gives them — the seeded weights
have to be the same ones.

With ``h [b, s, 2304]`` the stream, every block ``h <- h + f(rms(h) w)``:

KDA on ``u``, 32 heads of ``d_k = d_v = 128``:

    q | k | v = u W_qkv;  q, k, v = silu(conv4(.))      no bias anywhere
    q~ = q rsqrt(|q|^2 + 1e-6) d_k^-1/2,  k~ = k rsqrt(|k|^2 + 1e-6)
    g = -exp(A_log)[h] softplus(u W_f1 W_f2 + dt_bias)    [s, 32, 128], <= 0
    beta = sigmoid(u W_b)                                  [s, 32]
    S_t = (I - beta_t k~_t k~_t^T) diag(exp(g_t)) S_{t-1} + beta_t k~_t v_t^T
    o_t = S_t^T q~_t
    y = rms(o) w_norm * sigmoid(u W_g1 W_g2);  out = y W_o

Latent attention (MLA) without positions, 32 heads, no query latent:

    q = u W_q                          a head's q = [q_n (128) | q_s (64)]
    c | k_s = u W_kvd                  c [s, 512]; k_s [s, 64], ONE for all heads
    k_n | v = rms(c) w_c W_kvu         [s, 32, 128 + 128]
    k = [k_n | k_s];  o = causal softmax(192^-1/2 q k^T) v;  out = o W_o

Experts on ``x = rms(h) w``:

    s = sigmoid(x W_r)                 float32, 256 scores
    T = top-8(s + b)                   b chooses only
    w_e = 2.446 s_e / (sum_T s + 1e-20)
    out = shared(x) + sum_{e in T, e HELD HERE} w_e expert_e(x)
    shared, expert_e: W_d (silu(W_g x) * W_u x), width 1,024

Layer 1's MLP is the same SwiGLU at 9,216.  ``bias_update``: ``b_e <- b_e +
rate sign(mean(c) - c_e)`` with ``c`` the step's pair counts of all experts
(DeepSeek-V3, arXiv:2412.19437 section 2.1.2).  ``train_loss`` adds
``moe_balance_loss x experts x sum_e f_e mean_t(s_e / sum s)`` a sparse layer
(``f``: the pair shares, constant).

Departures from the published description, each the program's too (and in
``benchmark/configs/kimi_linear_48b_a3b.json`` under ``assumed`` /
``deployment``): one rank's share — ``experts_held`` experts from
``experts_first``, a slice of both tables; the absent experts add nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import common

QUERY_BLOCK = 512
LOGIT_BLOCK = 2048
L2_EPS = 1e-6
NORM = "norm_0/normal_var0"
KDA = {
    "w_qkv": "kda_0/normal_var0", "w_f1": "kda_0/normal_var1",
    "w_f2": "kda_0/normal_var2", "w_g1": "kda_0/normal_var3",
    "w_g2": "kda_0/normal_var4", "w_b": "kda_0/normal_var5",
    "conv_w": "kda_0/uniform_var0", "dt_bias": "kda_0/uniform_var1",
    "a_log": "kda_0/uniform_var2", "w_norm": "kda_0/constant_var0",
    "w_out": "kda_0/normal_var6",
}
ATTENTION = {
    "w_query": "attention_0/normal_var0", "w_down": "attention_0/normal_var1",
    "w_latent_norm": "attention_0/normal_var2",
    "w_up": "attention_0/normal_var3", "w_out": "attention_0/normal_var4",
}
DENSE = {"w_gate": "mlp_0/normal_var0", "w_up": "mlp_0/normal_var1",
         "w_down": "mlp_0/normal_var2"}
SPARSE = {
    "w_router": "moe_0/normal_var0", "bias": "moe_0/selection_bias0",
    "w_gate": "moe_0/normal_var1", "w_up": "moe_0/normal_var2",
    "w_down": "moe_0/normal_var3", "s_gate": "moe_0/normal_var4",
    "s_up": "moe_0/normal_var5", "s_down": "moe_0/normal_var6",
}
KINDS = {"kda": KDA, "attention": ATTENTION, "mlp": DENSE, "moe": SPARSE}


def rms(x, scale, eps: float):
    """RMSNorm over ALL trailing axes that ``scale`` has."""
    axes = tuple(range(x.ndim - scale.ndim, x.ndim))
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=axes,
                                      keepdims=True) + eps) * scale


def sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def silu(x):
    return x * sigmoid(x)


def softplus(x):
    return jnp.logaddexp(x, 0.0)


def top_k_mask(values, k: int):
    """Booleans ``[.., n]``: the ``k`` largest of the last axis, the lower
    index first among equals — a stable ranking, no top-k primitive."""
    order = jnp.argsort(-values, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return rank < k


# ---- Kimi Delta Attention ----------------------------------------------------

def _conv(x, weight):
    """``y[t] = sum_k weight[k] x[t - (K - 1) + k]``, zeros before the
    sequence: K shifted multiplies."""
    s = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (weight.shape[0] - 1, 0), (0, 0)))
    return sum(padded[:, k:k + s] * weight[k]
               for k in range(weight.shape[0]))


def recurrence(q, k, v, beta, g):
    """The delta rule with a decay a channel, position by position: ``q`` /
    ``k [b, s, h, d_k]`` (normalised), ``v [b, s, h, d_v]``, ``beta [b, s,
    h]``, ``g [b, s, h, d_k]`` -> ``o [b, s, h, d_v]``."""
    bsz, s, h, dk = q.shape
    dv = v.shape[-1]

    def step(t, carry):
        state, out = carry                               # [b, h, d_k, d_v]
        state = state * jnp.exp(g[:, t])[..., None]
        write = beta[:, t, :, None] * (
            v[:, t] - jnp.einsum("bhkv,bhk->bhv", state, k[:, t]))
        state = state + k[:, t][..., :, None] * write[..., None, :]
        return state, out.at[:, t].set(
            jnp.einsum("bhkv,bhk->bhv", state, q[:, t]))

    _, out = jax.lax.fori_loop(
        0, s, step, (jnp.zeros((bsz, h, dk, dv), jnp.float32),
                     jnp.zeros((bsz, s, h, dv), jnp.float32)))
    return out


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + L2_EPS)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
@common.highest
def kda_block(p, h, heads: int, dk: int, dv: int, eps: float):
    u = rms(h, p["w_norm_in"], eps)
    qkv = jnp.einsum("bsgf,gfo->bso", u, p["w_qkv"])
    d_key = heads * dk
    q, k, v = jnp.split(silu(_conv(qkv, p["conv_w"])), [d_key, 2 * d_key],
                        axis=-1)
    lead = h.shape[:2]
    q = _unit(q.reshape(lead + (heads, dk))) * dk ** -0.5
    k = _unit(k.reshape(lead + (heads, dk)))
    raw = jnp.einsum("bsr,ro->bso",
                     jnp.einsum("bsgf,gfr->bsr", u, p["w_f1"]), p["w_f2"])
    g = -jnp.exp(p["a_log"])[:, None] * softplus(
        (raw + p["dt_bias"]).reshape(lead + (heads, dk)))
    beta = sigmoid(jnp.einsum("bsgf,gfh->bsh", u, p["w_b"]))
    o = recurrence(q, k, v.reshape(lead + (heads, dv)), beta, g)
    gate = sigmoid(jnp.einsum(
        "bsr,ro->bso", jnp.einsum("bsgf,gfr->bsr", u, p["w_g1"]), p["w_g2"]))
    y = rms(o, p["w_norm"], eps).reshape(gate.shape) * gate
    return jnp.einsum("bsi,igf->bsgf", y, p["w_out"])


# ---- latent attention, no positions -------------------------------------------

@functools.partial(jax.jit, static_argnums=(2,))
@common.highest
def _latent_qkv(p, h, eps: float):
    u = rms(h, p["w_norm_in"], eps)
    q = jnp.einsum("bsgf,gfhd->bshd", u, p["w_query"])
    down = jnp.einsum("bsgf,gfc->bsc", u, p["w_down"])
    latent = p["w_latent_norm"].shape[0]
    up = jnp.einsum("bsc,chd->bshd",
                    rms(down[..., :latent], p["w_latent_norm"], eps),
                    p["w_up"])
    width = up.shape[-1] // 2
    shared = jnp.broadcast_to(down[:, :, None, latent:],
                              up.shape[:3] + (down.shape[-1] - latent,))
    return q, jnp.concatenate([up[..., :width], shared], axis=-1), \
        up[..., width:]


@jax.jit
@common.highest
def _attend(q_block, k, v, first):
    """One block of queries, positions ``first ..``, against all keys; the
    value's width need not be the key's."""
    score = jnp.einsum("bshd,bthd->bhst", q_block, k) \
        * q_block.shape[-1] ** -0.5
    causal = (first + jnp.arange(q_block.shape[1]))[:, None] \
        >= jnp.arange(k.shape[1])[None, :]
    weight = jax.nn.softmax(jnp.where(causal, score, -jnp.inf), axis=-1)
    return jnp.einsum("bhst,bthd->bshd", weight, v)


@jax.jit
@common.highest
def _project_out(o, w_out):
    return jnp.einsum("bshd,hdgf->bsgf", o, w_out)


def attention_block(p, h, eps: float):
    q, k, v = _latent_qkv(p, h, eps)
    s = q.shape[1]
    block = min(s, QUERY_BLOCK)
    o = jnp.concatenate([_attend(q[:, i:i + block], k, v, i)
                         for i in range(0, s, block)], axis=1)
    return _project_out(o, p["w_out"])


# ---- the MLP and the experts ----------------------------------------------------

def _swiglu(x, w_gate, w_up, w_down):
    return jnp.einsum(
        "bsi,igf->bsgf", silu(jnp.einsum("bsgf,gfi->bsi", x, w_gate))
        * jnp.einsum("bsgf,gfi->bsi", x, w_up), w_down)


@functools.partial(jax.jit, static_argnums=(2,))
@common.highest
def dense_block(p, h, eps: float):
    return _swiglu(rms(h, p["w_norm_in"], eps), p["w_gate"], p["w_up"],
                   p["w_down"])


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
@common.highest
def route(p, h, top_k: int, norm_topk: bool, scale: float, eps: float,
          balance: float):
    """``(x, weights [b, s, experts], pair counts [experts], the balance
    term)``: each token's weight for every routed expert, zero where the
    router did not choose it."""
    x = rms(h, p["w_norm_in"], eps)
    scores = sigmoid(jnp.einsum("bsgf,gfe->bse", x, p["w_router"]))
    chosen = top_k_mask(scores + p["bias"], top_k)
    picked = jnp.where(chosen, scores, 0.0)
    if norm_topk:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    counts = jnp.sum(chosen.astype(jnp.float32), axis=(0, 1))
    share = jax.lax.stop_gradient(counts / jnp.sum(counts))
    term = balance * scores.shape[-1] * jnp.sum(share * jnp.mean(
        scores / jnp.sum(scores, axis=-1, keepdims=True), axis=(0, 1)))
    return x, scale * picked, counts, term


@jax.jit
@common.highest
def one_expert(x, w_gate, w_up, w_down, weight):
    """One expert on EVERY token, times the token's weight for it."""
    return _swiglu(x, w_gate, w_up, w_down) * weight[..., None, None]


swiglu = jax.jit(common.highest(_swiglu))


def sparse_block(p, h, config):
    """``(the shared expert, counted once, plus this rank's routed part; the
    pair counts of all experts; the balance term)``."""
    x, weights, counts, term = route(
        p, h, int(config["moe_top_k"]), bool(config["moe_norm_topk"]),
        float(config["moe_route_scale"]), float(config["norm_epsilon"]),
        float(config.get("moe_balance_loss", 0.0)))
    first = int(config.get("experts_first", 0))
    out = swiglu(x, p["s_gate"], p["s_up"], p["s_down"])
    for j in range(int(config.get("experts_held") or config["experts"])):
        out = out + one_expert(x, p["w_gate"][j], p["w_up"][j],
                               p["w_down"][j], weights[..., first + j])
    return out, counts, term


def bias_update(bias, counts, rate: float = 1e-3):
    """The selection bias after a step whose pair counts of all experts
    were ``counts``."""
    counts = jnp.asarray(counts, jnp.float32)
    return jnp.asarray(bias, jnp.float32) \
        + rate * jnp.sign(jnp.mean(counts) - counts)


# ---- the model ----------------------------------------------------------------

def layers_of(variables, config):
    """``(kind, parameters)`` of every layer in execution order: ``depth``
    times the blocks of ``block_config``, each ``[norm-rms-scale, layer]``."""
    for d in range(int(config["depth"])):
        for i, block in enumerate(config["block_config"]):
            norm, layer = block["layer"]
            assert norm == "norm-rms-scale" and block["skip"], block
            kind = layer.split("-")[0]
            yield kind, common.block_params(
                variables, d, i, {**KINDS[kind], "w_norm_in": NORM})


def hidden(variables, tokens, config, stream_dtype=None, counts=None,
           terms=None):
    """The residual stream after the last block, ``[b, s, heads, width]``;
    ``counts`` / ``terms``: lists that take each sparse layer's pair counts
    and balance term."""
    eps = float(config["norm_epsilon"])

    def stream(x):
        # the control of benchmark/precision_control.py: the stream rounded
        # to a lower precision after every block
        return x if stream_dtype is None \
            else x.astype(stream_dtype).astype(jnp.float32)

    h = stream(common.param(variables,
                            "input0/gather0/embed0/normal_var0")[tokens])
    for kind, p in layers_of(variables, config):
        if kind == "kda":
            h = h + kda_block(p, h, int(config["kda_heads"]),
                              int(config["kda_key_features"]),
                              int(config["kda_value_features"]), eps)
        elif kind == "attention":
            h = h + attention_block(p, h, eps)
        elif kind == "mlp":
            h = h + dense_block(p, h, eps)
        else:
            out, layer_counts, term = sparse_block(p, h, config)
            h = h + out
            if counts is not None:
                counts.append(layer_counts)
            if terms is not None:
                terms.append(term)
        h = stream(h)
    return h


@functools.partial(jax.jit, static_argnums=(3,))
@common.highest
def _logits(h, scale, w_head, eps: float):
    return jnp.einsum("bsgf,gfv->bsv", rms(h, scale, eps), w_head)


def _head(variables):
    return (common.param(variables, "output0/lang_out0_0/norm_0/normal_var0"),
            common.param(variables, "output0/embed0/normal_var0")[:, :, 0, :])


def forward(variables, tokens, config, stream_dtype=None):
    """Logits ``[b, s, vocab]`` (float32) for ``tokens [b, s]``, made in
    blocks of ``LOGIT_BLOCK`` positions and handed over as a host array, so
    that they fit beside the train state.  ``stream_dtype``: the control's
    lower-precision residual stream."""
    h = hidden(variables, tokens, config, stream_dtype)
    scale, w_head = _head(variables)
    eps = float(config["norm_epsilon"])
    return np.concatenate([
        np.asarray(_logits(h[:, i:i + LOGIT_BLOCK], scale, w_head, eps))
        for i in range(0, h.shape[1], LOGIT_BLOCK)], axis=1)


def pair_counts(variables, tokens, config):
    """The pair counts ``[experts]`` of every sparse layer, in order."""
    counts: list = []
    hidden(variables, tokens, config, counts=counts)
    return counts


def train_loss(variables, tokens, targets, config):
    """Cross-entropy (+ the configuration's output z-loss) plus the sparse
    layers' balance terms: the scalar whose gradient the program's step
    applies (the selection bias has none).  Differentiable."""
    terms: list = []
    h = hidden(variables, tokens, config, terms=terms)
    scale, w_head = _head(variables)
    logits = _logits(h, scale, w_head, float(config["norm_epsilon"]))
    return common.loss_of(logits, targets, config["z_loss"]) + sum(terms)
