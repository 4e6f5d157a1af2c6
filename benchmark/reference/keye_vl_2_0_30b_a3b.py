"""Plain reference of Keye-VL-2.0-30B-A3B's LANGUAGE MODEL as ONE RANK of an
expert-parallel group holds it: a pre-norm residual stream of grouped-query
attention over the keys a learned token-level indexer keeps (DeepSeek Sparse
Attention's lightning indexer at Keye's ``sa_config`` sizes), each followed by
a 128-way softmax top-8 router over the experts held here; RMSNorm, untied
head.  No vision tower: text only, where M-RoPE's three position streams are
equal.

Written from the published ``config.json`` (``model_type: KeyeVL2``) and the
layer equations of ISSUE 62 in float32 ``jax.numpy`` under ``highest`` matmul
precision: explicit einsums, a Python loop over layers and over blocks of 512
queries against ALL keys with every mask written out, the index scores of all
16 index heads of a block at once, the selection by a STABLE SORT of a row's
scores (no bisection, no bits), a Python loop over the held experts with a
mask, no kernel, no scan.  Parameters are read by the names the program gives
them — the seeded weights have to be the same ones — and nothing else is
taken from the program.  The router, the experts, ``rms`` and the head are
the ones of ``laguna_s_2_1.py`` (the same softmax top-k layer with a held
share).

On ``x [s, 2048]``, every layer alike, 32 query heads and 4 K/V heads of 128:

    h = rms(x) * w1
    q, k, v = h Wq [s, 32, 128], h Wk [s, 4, 128], h Wv [s, 4, 128]
    q, k <- rms_128(q) * wq, rms_128(k) * wk           a head's own features
    q, k <- M-RoPE(theta 1e7, sections [16, 24, 24] over three position
            streams, all equal to 0 .. s - 1 on text)  = rotate-half RoPE
    hd = stop_gradient(h)
    qI = rope(hd WqI) [s, 16, 64];  kI = rope(layer_norm_64(hd WkI)) [s, 64]
    w  = (hd Ww) / sqrt(16) [s, 16]
    I[t, u] = sum_j w[t, j] relu(qI[t, j] . kI[u]) / sqrt(64),      u <= t
    S_t = the min(t + 1, 2048) largest I[t, .] among u <= t, ties to lower u
    o[t, a] = sum_{u in S_t} softmax_{u in S_t}(q[t, a] . k[u, a // 8] /
              sqrt(128)) v[u, a // 8];      y = x + o Wo
    pbar[t, u] = mean over the 32 heads of that softmax, detached
    L_I = mean_t sum_{u in S_t} pbar (log pbar - log softmax_{S_t}(I[t, .]))
    m = rms(y) * w2;  p = softmax(m Wr) (float32, 128 logits);  top 8 of p,
    renormalised to sum to one
    out = y + sum_{e in top 8, e HELD HERE} p_e expert_e(m)     SwiGLU of 768

and ``logits = (rms(x) * wf) Whead`` over this rank's rows of the vocabulary.
``rms(x) = x / sqrt(mean(x^2) + 1e-6)``; ``layer_norm`` subtracts the mean,
divides by ``sqrt(var + 1e-6)`` and has a learned scale and shift.  What the
experts held elsewhere would have added is left out (``experts_first``,
``experts_held``; 0 held = all, the uncut layer).  ``train_loss`` is the
cross-entropy plus the layers' router terms plus the SUM of the layers'
``L_I`` (weight 1): the language-model loss reaches no indexer parameter (the
indexer reads ``stop_gradient``, the choice is discrete) and ``L_I`` nothing
but the indexer (``pbar`` is detached).

Assumed, where ``config.json`` has no key: see
``benchmark/configs/keye_vl_2_0_30b_a3b.json`` ``assumed``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import common
from .laguna_s_2_1 import (_logits, default_inv_freq, rms, rope, route,
                           routed_part)

QUERY_BLOCK = 512
LOGIT_BLOCK = 2048
MROPE_SECTIONS = (16, 24, 24)
ATTENTION = {
    "w_key": "attention_0/normal_var0", "w_query": "attention_0/normal_var1",
    "w_value": "attention_0/normal_var2", "q_scale": "attention_0/normal_var3",
    "k_scale": "attention_0/normal_var4",
    "w_index_query": "attention_0/normal_var5",
    "w_index_key": "attention_0/normal_var6",
    "index_scale": "attention_0/normal_var7",
    "index_shift": "attention_0/normal_var8",
    "w_index_weight": "attention_0/normal_var9",
    "w_out": "attention_0/normal_var10",
}
SPARSE = {"w_router": "moe_0/normal_var0", "w_gate": "moe_0/normal_var1",
          "w_up": "moe_0/normal_var2", "w_down": "moe_0/normal_var3"}
NORM = "norm_0/normal_var0"


def mrope(x, positions, theta: float, sections=None):
    """Qwen2-VL's multimodal rotary embedding on ``x [b, s, h, d]``:
    ``positions [3, s]`` (temporal, height, width), frequency ``i`` of the
    ``d / 2`` takes its angle from the stream its section belongs to
    (``sections`` frequencies each, repeated over both halves as HF's
    ``apply_multimodal_rotary_pos_emb`` splits ``cos`` into ``sections * 2``
    chunks and takes chunk ``i`` from stream ``i % 3``); rotate-half.
    ``sections`` None: the published ones, at another head width than 128 (a
    test's) in the same proportion."""
    d = x.shape[-1]
    if sections is None:
        sections = tuple(n * d // 128 for n in MROPE_SECTIONS)
    assert 2 * sum(sections) == d, (sections, d)
    inv_freq = jnp.asarray(default_inv_freq(theta, d), jnp.float32)
    angles = positions.astype(jnp.float32)[:, :, None] * inv_freq[None, None]
    stream = np.repeat(np.arange(len(sections)), sections)     # [d / 2]
    angle = angles[stream, :, np.arange(d // 2)].T             # [s, d / 2]
    emb = jnp.concatenate([angle, angle], axis=-1)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(emb) + rotated * jnp.sin(emb)


def layer_norm(x, scale, shift, eps: float):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + shift


def layer_spec(layer: str) -> dict:
    name, *flags = layer.split("-")
    assert name == "attention" and "indexed" in flags \
        and "qk_norm_head" in flags and "rope" in flags, layer
    number = {f.rstrip("0123456789"): int(f[len(f.rstrip("0123456789")):])
              for f in flags if f[-1].isdigit()}
    return {"heads": number["q_heads"], "kv_heads": number["kv_heads"]}


def layers_of(variables, config):
    """``(kind, parameters, spec)`` of every block in execution order:
    ``depth`` times the period (``block_config``), a block ``[norm,
    sublayer]``."""
    for d in range(config["depth"]):
        for i, block in enumerate(config["block_config"]):
            norm, sub = block["layer"]
            assert norm == "norm-rms-scale" and block["skip"], block
            attention = sub.split("-")[0] == "attention"
            assert attention or sub == "moe-silu", sub
            p = {k: common.param(variables, f"body0/block{d}_{i}_0/{path}")
                 for k, path in {**(ATTENTION if attention else SPARSE),
                                 "w_norm": NORM}.items()}
            yield ("attention" if attention else "sparse"), p, \
                (layer_spec(sub) if attention else None)


# ---- the attention layer -------------------------------------------------------

def _project(p, h, heads, kv_heads, theta, index_heads, eps):
    """``(q, k, v, qI, kI, w)`` of the block's input ``h [b, s, g, f]``."""
    a = rms(h, p["w_norm"], eps)
    q = rms(jnp.einsum("bsgf,gfhd->bshd", a, p["w_query"]), p["q_scale"], eps)
    k = rms(jnp.einsum("bsgf,gfhd->bshd", a, p["w_key"]), p["k_scale"], eps)
    v = jnp.einsum("bsgf,gfhd->bshd", a, p["w_value"])
    positions = jnp.broadcast_to(jnp.arange(h.shape[1])[None], (3, h.shape[1]))
    q, k = mrope(q, positions, theta), mrope(k, positions, theta)
    hd = jax.lax.stop_gradient(a)
    inv_freq = default_inv_freq(theta, p["w_index_query"].shape[-1])
    q_index = rope(jnp.einsum("bsgf,gfjd->bsjd", hd, p["w_index_query"]),
                   inv_freq, 1.0)
    k_index = rope(layer_norm(
        jnp.einsum("bsgf,gfd->bsd", hd, p["w_index_key"]), p["index_scale"],
        p["index_shift"], eps)[:, :, None], inv_freq, 1.0)[:, :, 0]
    weight = jnp.einsum("bsgf,gfj->bsj", hd, p["w_index_weight"]) \
        / jnp.sqrt(jnp.float32(index_heads))
    return q, k, v, q_index, k_index, weight


def _attend(q, k, v, q_index, k_index, weight, start, topk):
    """One block of queries (positions ``start ..``) against all keys:
    ``(o [b, n, heads, d], the block's sum over queries of the index loss,
    keep [b, n, s])``."""
    b, n, heads, d = q.shape
    s, kv_heads = k.shape[1], k.shape[2]
    pos = start + jnp.arange(n)
    visible = jnp.arange(s)[None, :] <= pos[:, None]
    index = jnp.einsum("bnj,bnjs->bns", weight, jax.nn.relu(jnp.einsum(
        "bnjd,bsd->bnjs", q_index, k_index))) \
        / jnp.sqrt(jnp.float32(q_index.shape[-1]))
    index = jnp.where(visible, index, -jnp.inf)
    # a row's keys by falling score, equal scores by rising position (a
    # STABLE sort); the row keeps what comes at or before its k-th entry, k =
    # min(t + 1, topk): a higher score, or the same score no later
    order = jnp.argsort(-index, axis=-1, stable=True)
    last = jnp.take_along_axis(
        order, (jnp.minimum(pos + 1, topk) - 1)[None, :, None], axis=-1)
    level = jnp.take_along_axis(index, last, axis=-1)
    keep = visible & ((index > level) | (
        (index == level) & (jnp.arange(s)[None, None, :] <= last)))
    qg = q.reshape(b, n, kv_heads, heads // kv_heads, d)
    score = jnp.einsum("bnkgd,bskd->bkgns", qg, k) / jnp.sqrt(jnp.float32(d))
    prob = jax.nn.softmax(jnp.where(keep[:, None, None], score, -jnp.inf),
                          axis=-1)
    o = jnp.einsum("bkgns,bskd->bnkgd", prob, v).reshape(b, n, heads, d)
    pbar = jax.lax.stop_gradient(jnp.mean(prob, axis=(1, 2)))
    log_index = jax.nn.log_softmax(jnp.where(keep, index, -jnp.inf), axis=-1)
    loss = jnp.sum(jnp.where(pbar > 0, pbar * (
        jnp.log(jnp.where(pbar > 0, pbar, 1.0))
        - jnp.where(keep, log_index, 0.0)), 0.0))
    return o, loss, keep


project = jax.jit(common.highest(_project), static_argnums=(2, 3, 4, 5, 6))
attend = jax.jit(common.highest(_attend), static_argnums=7)


@jax.jit
@common.highest
def _out(o, w_out):
    return jnp.einsum("bshd,hdgf->bsgf", o, w_out)


def attention_block(p, h, spec, config, kept=None):
    """``(what the layer adds to the stream, L_I)``; ``kept``: a list that
    takes the layer's choice, ``[b, s, s / 8]`` uint8 on the host (bit ``u %
    8`` of byte ``u // 8``, numpy's ``packbits`` little-endian)."""
    q, k, v, q_index, k_index, weight = project(
        p, h, spec["heads"], spec["kv_heads"], float(config["rope_theta"]),
        int(config["index_heads"]), float(config["norm_epsilon"]))
    s = h.shape[1]
    out, loss, rows = [], 0.0, []
    for start in range(0, s, QUERY_BLOCK):
        cut = slice(start, start + QUERY_BLOCK)
        o, part, keep = attend(q[:, cut], k, v, q_index[:, cut], k_index,
                               weight[:, cut], start,
                               int(config["index_topk"]))
        out.append(o)
        loss = loss + part
        if kept is not None:
            rows.append(np.packbits(np.asarray(keep), axis=-1,
                                    bitorder="little"))
    if kept is not None:
        kept.append(np.concatenate(rows, axis=1))
    return _out(jnp.concatenate(out, axis=1), p["w_out"]), \
        loss / (h.shape[0] * s)


def sparse_block(p, h, config):
    """``(this rank's routed part, the layer's router losses)``."""
    m, weights, losses = route(
        p, h, int(config["moe_top_k"]), bool(config["moe_norm_topk"]),
        float(config["moe_route_scale"]), float(config["norm_epsilon"]),
        float(config.get("moe_balance_loss", 0.0)),
        float(config.get("moe_router_z_loss", 0.0)))
    held = int(config.get("experts_held") or config["experts"])
    return routed_part(p, m, weights, int(config.get("experts_first", 0)),
                       held), losses


def hidden(variables, tokens, config, stream_dtype=None, losses=None,
           kept=None):
    """The residual stream after the last block, ``[b, s, heads, width]``;
    ``losses``: a dict that takes ``"router"`` and ``"index"``, a list of the
    layers' terms each; ``kept``: a list that takes every attention layer's
    choice (``attention_block``)."""
    def stream(x):
        # the control of benchmark/precision_control.py: the stream rounded
        # to a lower precision after every block
        return x if stream_dtype is None \
            else x.astype(stream_dtype).astype(jnp.float32)

    h = stream(common.param(variables,
                            "input0/gather0/embed0/normal_var0")[tokens])
    for kind, p, spec in layers_of(variables, config):
        if kind == "attention":
            out, loss = attention_block(p, h, spec, config, kept)
        else:
            out, loss = sparse_block(p, h, config)
        h = stream(h + out)
        if losses is not None:
            losses.setdefault("index" if kind == "attention" else "router",
                              []).append(loss)
    return h


def _head(variables):
    return (common.param(variables, "output0/lang_out0_0/norm_0/normal_var0"),
            common.param(variables, "output0/embed0/normal_var0")[:, :, 0, :])


def forward(variables, tokens, config, stream_dtype=None, losses=None,
            kept=None):
    """Logits ``[b, s, vocab]`` (float32) for ``tokens [b, s]``, made in
    blocks of ``LOGIT_BLOCK`` positions and handed over as a host array.
    ``stream_dtype``: the control's lower-precision residual stream;
    ``losses`` / ``kept``: as ``hidden``."""
    h = hidden(variables, tokens, config, stream_dtype, losses, kept)
    scale, w_head = _head(variables)
    eps = float(config["norm_epsilon"])
    return np.concatenate([
        np.asarray(_logits(h[:, i:i + LOGIT_BLOCK], scale, w_head, eps))
        for i in range(0, h.shape[1], LOGIT_BLOCK)], axis=1)


def losses_of(variables, tokens, targets, config):
    """``(cross-entropy, the sum of the router terms, the layers' L_I as a
    list)``, differentiable."""
    losses: dict = {}
    h = hidden(variables, tokens, config, losses=losses)
    logits = _logits(h, *_head(variables), float(config["norm_epsilon"]))
    return common.loss_of(logits, targets, config["z_loss"]), \
        sum(losses["router"]), losses["index"]


def train_loss(variables, tokens, targets, config):
    """Cross-entropy plus the sparse layers' router terms plus the attention
    layers' index losses (weight 1): the scalar whose gradient the program's
    step applies."""
    lm, router, index = losses_of(variables, tokens, targets, config)
    return lm + router + sum(index)
