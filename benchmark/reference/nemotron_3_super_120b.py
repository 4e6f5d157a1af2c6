"""Plain reference of NVIDIA-Nemotron-3-Super-120B-A12B (``nemotron_h``): a
pre-norm residual stream of Mamba-2 layers with GROUPED ``B`` / ``C``, a few
NoPE grouped-query attention layers and LatentMoE layers, RMSNorm (eps 1e-5),
an untied head.

Written from the published ``config.json``, the catalog's description
("LatentMoE: experts in 1024-d latent; routed scaling 5") and the layer
equations of HF ``modeling_nemotron_h.py`` (the Mamba-2 mixer's
``torch_forward`` with ``n_groups``, ``NemotronHAttention`` — no positional
embedding —, ``NemotronHTopkRouter``: sigmoid scores, a selection bias
``e_score_correction_bias`` that chooses only, the chosen scores
renormalised and scaled) and Megatron-LM's ``moe_latent_size`` /
``fc1_latent_proj`` / ``fc2_latent_proj`` for where the latent's projections
stand, in float32 ``jax.numpy`` under ``highest`` matmul precision.  No
kernel and nothing of ``homebrewnlp_tpu``: the state-space recurrence is run
AS the recurrence, position by position; the sigmoid, the top-k (a stable
ranking), relu squared and the grouped norm are written out here; attention
is explicit einsums one block of queries at a time; every expert runs on
every token and is weighted by the token's weight for it (zero where not
chosen).  Parameters are read by the names the program gives them — the
seeded weights have to be the same ones, so ``rescale_prenorm_residual``
(the matrices that write into the stream start at 0.02 / sqrt(2 x 88)) is
the initialisation's and nothing here reads it.

With ``h [b, s, 4096]`` the stream, every block ``h <- h + f(rms(h) w)``:

Mamba-2 on ``u``, ``d_inner = heads x 64``, ``g`` groups, state 128:

    z, xBC, dt = split(u W_in)            d_inner, d_inner + 2 g n, heads
    xBC = silu(bias + sum_k w_k xBC[t-3+k])
    x, B, C = split(xBC)                  B, C: [s, g, n]
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A_j) S_{t-1} + dt_t x_t B_{t,G(j)}^T     G(j) = j // (heads / g)
    y_t = S_t C_{t,G(j)} + D_j x_t
    y = y silu(z), then RMSNorm over EACH group's d_inner / g columns, times
    a [d_inner] scale;  out = y W_out

Attention: ``q = a Wq``, ``k = a Wk``, ``v = a Wv`` at head 128, no positions,
K/V head ``j`` serves its group of query heads, ``causal softmax(128^-0.5 q
k^T) v Wo``.

LatentMoE on ``x = rms(h) w``:

    s = sigmoid(x W_r)                    float32, W_r: 4096 x 512
    T = top-22(s + b)                     b chooses only
    w_e = 5 s_e / (sum_{T} s + 1e-20)
    l = x W_down                          4096 x 1024
    r = sum_{e in T, e held} w_e relu(l U_e)^2 V_e
    out = r W_up + relu(x U_s)^2 V_s

``bias_update``: ``b_e <- b_e + rate sign(mean(c) - c_e)`` with ``c`` the
step's pair counts of all experts (DeepSeek-V3, arXiv:2412.19437 section
2.1.2).  ``train_loss`` adds ``moe_balance_loss x experts x sum_e f_e
mean_t(s_e / sum s)`` a sparse layer (``f``: the pair shares, constant).

Departures from the published description, each the program's too:
- one rank's share: ``experts_held`` experts from ``experts_first``, half the
  Mamba-2 and attention heads, a slice of both tables — what the
  configuration's ``deployment`` says; the absent parts add nothing.
- no multi-token-prediction module (``num_nextn_predict_layers`` 1 -> 0).
- the loss is the mean cross-entropy over all positions (``common.loss_of``).
- the router's weights stay float32 where HF rounds them to the activations'
  dtype; the gate ``silu(z)`` and its product are float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import common

QUERY_BLOCK = 512
LOGIT_BLOCK = 2048
NORM = "norm_0/normal_var0"
MAMBA = {
    "w_in": "mamba_0/normal_var0", "conv_w": "mamba_0/uniform_var0",
    "conv_b": "mamba_0/uniform_var1", "dt_bias": "mamba_0/uniform_var2",
    "a_log": "mamba_0/uniform_var3", "d": "mamba_0/constant_var0",
    "w_norm": "mamba_0/constant_var1", "w_out": "mamba_0/normal_var1",
}
ATTENTION = {
    "w_key": "attention_0/normal_var0", "w_query": "attention_0/normal_var1",
    "w_value": "attention_0/normal_var2", "w_out": "attention_0/normal_var3",
}
SPARSE = {
    "w_router": "moe_0/normal_var0", "bias": "moe_0/selection_bias0",
    "w_latent_down": "moe_0/normal_var1", "w_up": "moe_0/normal_var2",
    "w_down": "moe_0/normal_var3", "w_latent_up": "moe_0/normal_var4",
    "s_up": "moe_0/normal_var5", "s_down": "moe_0/normal_var6",
}
KINDS = {"mamba": MAMBA, "attention": ATTENTION, "moe": SPARSE}


def rms(x, scale, eps: float):
    """RMSNorm over ALL trailing axes that ``scale`` has."""
    axes = tuple(range(x.ndim - scale.ndim, x.ndim))
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=axes,
                                      keepdims=True) + eps) * scale


def sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def silu(x):
    return x * sigmoid(x)


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def top_k_mask(values, k: int):
    """Booleans ``[.., n]``: the ``k`` largest of the last axis, the lower
    index first among equals — a stable ranking, no top-k primitive."""
    order = jnp.argsort(-values, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return rank < k


# ---- Mamba-2 with groups ------------------------------------------------------

def _conv(x, weight, bias):
    """``y[t] = bias + sum_k weight[k] x[t - (K - 1) + k]``, zeros before
    the sequence."""
    s = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (weight.shape[0] - 1, 0), (0, 0)))
    return bias + sum(padded[:, k:k + s] * weight[k]
                      for k in range(weight.shape[0]))


def recurrence(x, dt, a, b_mat, c_mat):
    """``y_t = S_t C_t`` with ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``,
    position by position: ``x [b, s, h, p]``, ``dt [b, s, h]``, ``a [h]``,
    ``b_mat`` / ``c_mat`` ``[b, s, g, n]``, head ``j`` with group ``j // (h /
    g)`` -> ``[b, s, h, p]``."""
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2:]

    def step(t, carry):
        state, ys = carry
        b_t = jnp.repeat(b_mat[:, t], h // g, axis=1)            # [b, h, n]
        c_t = jnp.repeat(c_mat[:, t], h // g, axis=1)
        state = state * jnp.exp(dt[:, t] * a)[..., None, None] \
            + (dt[:, t, :, None] * x[:, t])[..., None] * b_t[:, :, None, :]
        return state, ys.at[:, t].set(jnp.einsum("bhpn,bhn->bhp", state, c_t))

    _, ys = jax.lax.fori_loop(
        0, s, step, (jnp.zeros((bsz, h, p, n), jnp.float32),
                     jnp.zeros((bsz, s, h, p), jnp.float32)))
    return ys


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
@common.highest
def mamba_block(p, h, heads: int, state: int, groups: int, eps: float):
    u = rms(h, p["w_norm_in"], eps)
    proj = jnp.einsum("bsgf,gfo->bso", u, p["w_in"])
    d_inner = p["w_out"].shape[0]
    z, xbc, dt = jnp.split(proj, [d_inner, proj.shape[-1] - heads], axis=-1)
    xbc = silu(_conv(xbc, p["conv_w"], p["conv_b"]))
    x, b_mat, c_mat = jnp.split(
        xbc, [d_inner, d_inner + groups * state], axis=-1)
    x = x.reshape(x.shape[:2] + (heads, d_inner // heads))
    b_mat, c_mat = (m.reshape(m.shape[:2] + (groups, state))
                    for m in (b_mat, c_mat))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(x, dt, -jnp.exp(p["a_log"]), b_mat, c_mat) \
        + p["d"][:, None] * x
    y = (y.reshape(z.shape) * silu(z)).reshape(z.shape[:2] + (groups, -1))
    # each group's columns normalised apart
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + 1e-5)
    return jnp.einsum("bsi,ihd->bshd", y.reshape(z.shape) * p["w_norm"],
                      p["w_out"])


# ---- attention, no positions --------------------------------------------------

@functools.partial(jax.jit, static_argnums=(2,))
@common.highest
def _qkv(p, h, eps: float):
    a = rms(h, p["w_norm_in"], eps)
    return (jnp.einsum("bsgf,gfhd->bshd", a, p["w_query"]),
            jnp.einsum("bsgf,gfhd->bshd", a, p["w_key"]),
            jnp.einsum("bsgf,gfhd->bshd", a, p["w_value"]))


@jax.jit
@common.highest
def _attend(q_block, k, v, first):
    """One block of queries, positions ``first ..``, against all keys."""
    group = q_block.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    score = jnp.einsum("bshd,bthd->bhst", q_block, k) \
        * q_block.shape[-1] ** -0.5
    causal = (first + jnp.arange(q_block.shape[1]))[:, None] \
        >= jnp.arange(k.shape[1])[None, :]
    weight = jax.nn.softmax(jnp.where(causal, score, -jnp.inf), axis=-1)
    return jnp.einsum("bhst,bthd->bshd", weight, v)


@jax.jit
@common.highest
def _project_out(o, w_out):
    return jnp.einsum("bsgf,gfhd->bshd", o, w_out)


def attention_block(p, h, eps: float):
    q, k, v = _qkv(p, h, eps)
    s = q.shape[1]
    block = min(s, QUERY_BLOCK)
    o = jnp.concatenate([_attend(q[:, i:i + block], k, v, i)
                         for i in range(0, s, block)], axis=1)
    return _project_out(o, p["w_out"])


# ---- LatentMoE ----------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
@common.highest
def route(p, h, top_k: int, scale: float, eps: float, balance: float):
    """``(x, weights [b, s, experts], pair counts [experts], the balance
    term)``: each token's weight for every routed expert, zero where the
    router did not choose it."""
    x = rms(h, p["w_norm_in"], eps)
    scores = sigmoid(jnp.einsum("bsgf,gfe->bse", x, p["w_router"]))
    chosen = top_k_mask(scores + p["bias"], top_k)
    picked = jnp.where(chosen, scores, 0.0)
    weights = scale * picked / (jnp.sum(picked, axis=-1, keepdims=True)
                                + 1e-20)
    counts = jnp.sum(chosen.astype(jnp.float32), axis=(0, 1))
    share = jax.lax.stop_gradient(counts / jnp.sum(counts))
    term = balance * scores.shape[-1] * jnp.sum(share * jnp.mean(
        scores / jnp.sum(scores, axis=-1, keepdims=True), axis=(0, 1)))
    return x, weights, counts, term


@jax.jit
@common.highest
def latent_down(x, w):
    return jnp.einsum("bsgf,gfl->bsl", x, w)


@jax.jit
@common.highest
def one_expert(latent, w_up, w_down, weight):
    """One expert on EVERY token's latent, times the token's weight for
    it."""
    return jnp.einsum("bsi,il->bsl", relu2(jnp.einsum(
        "bsl,li->bsi", latent, w_up)), w_down) * weight[..., None]


@jax.jit
@common.highest
def latent_up_and_shared(routed, x, w_latent_up, s_up, s_down):
    return jnp.einsum("bsl,lgf->bsgf", routed, w_latent_up) + jnp.einsum(
        "bsw,wgf->bsgf", relu2(jnp.einsum("bsgf,gfw->bsw", x, s_up)), s_down)


def sparse_block(p, h, config):
    """``(this rank's routed part through the latent plus the shared expert,
    counted once; the pair counts of all experts; the balance term)``."""
    x, weights, counts, term = route(
        p, h, int(config["moe_top_k"]), float(config["moe_route_scale"]),
        float(config["norm_epsilon"]),
        float(config.get("moe_balance_loss", 0.0)))
    latent = latent_down(x, p["w_latent_down"])
    first = int(config.get("experts_first", 0))
    routed = jnp.zeros_like(latent)
    for j in range(int(config.get("experts_held") or config["experts"])):
        routed = routed + one_expert(latent, p["w_up"][j], p["w_down"][j],
                                     weights[..., first + j])
    return latent_up_and_shared(routed, x, p["w_latent_up"], p["s_up"],
                                p["s_down"]), counts, term


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
            assert norm == "norm-rms-scale", block
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
        if kind == "mamba":
            h = h + mamba_block(p, h, int(config["mamba_heads"]),
                                int(config["mamba_state"]),
                                int(config.get("mamba_groups", 1)), eps)
        elif kind == "attention":
            h = h + attention_block(p, h, eps)
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
    blocks of ``LOGIT_BLOCK`` positions and handed over as a host array.
    ``stream_dtype``: the control's lower-precision residual stream."""
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
