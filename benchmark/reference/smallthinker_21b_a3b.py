"""Plain reference of SmallThinker-21BA3B-Instruct as ONE RANK of an
expert-parallel group holds it: a pre-norm residual stream whose every layer
ROUTES BEFORE IT ATTENDS — the sparse block's router reads the ATTENTION
block's normed input — with ReGLU experts, grouped-query attention of 28 / 4
heads that is global WITHOUT positions in layer 0 of every four and a rotary
window of 4,096 in the other three, RMSNorm, untied tables.

Written from the published ``config.json`` (``model_type: smallthinker``) and
the equations of ISSUE 72 (the paper is arXiv:2507.20984, section 2) in float32
``jax.numpy`` under ``highest`` matmul precision: explicit einsums, a Python
loop over layers, over blocks of 512 queries against all keys with the mask
written out from its definition, and over the held experts each on EVERY
token; no kernel, no scan.  Parameters are read by the names the program
gives them — the seeded weights have to be the same ones — and nothing else
is taken from the program; ``rms``, ``rope`` (HF's rotate-half) and the
head's ``_logits`` are ``laguna_s_2_1.py``'s.

Layer ``l``, ``g(l) = (l mod 4 == 0)``, eps 1e-6, no bias anywhere:

    u        = rms(h) w_1                          the attention block's input
    r        = u W_r                               [64] float32: THE ROUTER
    q, k, v  = u W_q [28, 128], u W_k [4, 128], u W_v [4, 128]
    q, k     = rope(q), rope(k)                    only where not g(l): theta
                                                   1.5e6, all 128 features
    o        = softmax(q k^T / sqrt(128) + M_l) v  query head j reads K/V head
                                                   j // 7
               M_l: key t visible to query i iff t <= i, and where not g(l)
               also i - t < 4096
    h'       = h + o W_o
    x        = rms(h') w_2
    p        = softmax(r); T = the 6 largest; w_e = p_e / sum_T p
    h''      = h' + sum_{e in T, HELD HERE} w_e W_down,e (relu(x W_gate,e)
                                                          * (x W_up,e))
    logits   = rms(h_last) w W_head

What the experts held elsewhere would have added is left out
(``experts_first``, ``experts_held``; 0 held = all 64, the uncut layer).
``train_loss`` adds each layer's balance term ``moe_balance_loss x 64 x sum_e
f_e P_e / 6`` (``f``: the chosen pairs' shares, constant; ``P``: the mean
probabilities): the scalar the step differentiates.

Assumed, where ``config.json`` has no key: see
``benchmark/configs/smallthinker_21b_a3b.json`` ``assumed``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import common
from .laguna_s_2_1 import _logits, default_inv_freq, rms, rope

QUERY_BLOCK = 512
LOGIT_BLOCK = 2048
NORM = "norm_0/normal_var0"
ATTENTION = {
    "w_router": "route_early_0/normal_var0",
    "w_key": "attention_0/normal_var0", "w_query": "attention_0/normal_var1",
    "w_value": "attention_0/normal_var2", "w_out": "attention_0/normal_var3",
}
SPARSE = {"w_gate": "moe_0/normal_var0", "w_up": "moe_0/normal_var1",
          "w_down": "moe_0/normal_var2"}


def layer_spec(layer: str, config: dict) -> dict:
    """What one attention layer string of the configuration says: the head
    counts, the window (None = global) and the rotary frequencies (None =
    no positions)."""
    name, *flags = layer.split("-")
    assert name == "attention" and ("nope" in flags) != ("rope" in flags), \
        layer
    number = {f.rstrip("0123456789"): int(f[len(f.rstrip("0123456789")):])
              for f in flags if f[-1].isdigit()}
    inv_freq = None if "nope" in flags else tuple(
        float(f) for f in default_inv_freq(
            float(number.get("theta", config["rope_theta"])),
            config["features_per_head"]))
    return {"heads": number["q_heads"], "kv_heads": number["kv_heads"],
            "window": number.get("window"), "inv_freq": inv_freq}


def layers_of(variables, config):
    """``(kind, parameters, spec)`` of every block in execution order:
    ``depth`` times the period (``block_config``), an attention block
    ``[norm, route_early, attention]`` before every sparse block ``[norm,
    moe-relu-routed_early]``."""
    for d in range(config["depth"]):
        for i, block in enumerate(config["block_config"]):
            layer = block["layer"]
            assert layer[0] == "norm-rms-scale" and block["skip"], block
            attention = len(layer) == 3
            assert layer[1:] == ["moe-relu-routed_early"] or (
                attention and layer[1] == "route_early"), block
            p = {k: common.param(variables, f"body0/block{d}_{i}_0/{path}")
                 for k, path in {**(ATTENTION if attention else SPARSE),
                                 "w_norm": NORM}.items()}
            yield ("attention" if attention else "sparse"), p, \
                (layer_spec(layer[2], config) if attention else None)


def _project(p, h, inv_freq, eps):
    """``(r, q, k, v)`` of the block's input ``h [b, s, g, f]``: the router's
    logits and the attention's operands, all from the SAME normed input."""
    u = rms(h, p["w_norm"], eps)
    r = jnp.einsum("bsgf,gfe->bse", u, p["w_router"])
    q = jnp.einsum("bsgf,gfhd->bshd", u, p["w_query"])
    k = jnp.einsum("bsgf,gfhd->bshd", u, p["w_key"])
    v = jnp.einsum("bsgf,gfhd->bshd", u, p["w_value"])
    if inv_freq is not None:
        q, k = rope(q, inv_freq, 1.0), rope(k, inv_freq, 1.0)
    return r, q, k, v


def visible(rows, keys: int, window):
    """Rows ``rows`` of ``M_l`` against ``keys`` keys, from the definition."""
    i, t = np.asarray(rows)[:, None], np.arange(keys)[None, :]
    seen = t <= i
    if window is not None:
        seen &= i - t < window
    return seen


def _attend(q, k, v, mask):
    """One block of queries ``q [b, n, heads, d]`` against all keys under
    ``mask [n, s]``: query head ``j`` reads K/V head ``j // group``."""
    b, n, heads, d = q.shape
    kv_heads = k.shape[2]
    qg = q.reshape(b, n, kv_heads, heads // kv_heads, d)
    score = jnp.einsum("bnkgd,bskd->bkgns", qg, k) / jnp.sqrt(jnp.float32(d))
    prob = jax.nn.softmax(jnp.where(mask[None, None, None], score, -jnp.inf),
                          axis=-1)
    return jnp.einsum("bkgns,bskd->bnkgd", prob, v).reshape(b, n, heads, d)


def _out(o, w_out):
    return jnp.einsum("bshd,hdgf->bsgf", o, w_out)


def _route(r, top_k: int, norm_topk: bool, balance: float, z: float):
    """``(weights [b, s, experts], the layer's router terms)`` of the logits
    ``r``: each token's weight for every routed expert, zero where the router
    did not choose it."""
    probs = jax.nn.softmax(r, axis=-1)
    chosen = probs >= jax.lax.top_k(probs, top_k)[0][..., -1:]
    weights = jnp.where(chosen, probs, 0.0)
    if norm_topk:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    share = jax.lax.stop_gradient(
        jnp.mean(chosen.astype(jnp.float32), axis=(0, 1)))   # sums to top_k
    return weights, balance * probs.shape[-1] * jnp.sum(
        share * jnp.mean(probs, axis=(0, 1))) / top_k + z * jnp.mean(
        jnp.square(jax.scipy.special.logsumexp(r, axis=-1)))


def _one_expert(x, w_gate, w_up, w_down, weight):
    """One ReGLU expert on EVERY token, times the token's weight for it."""
    gate = jnp.einsum("bsgf,gfi->bsi", x, w_gate)
    up = jnp.einsum("bsgf,gfi->bsi", x, w_up)
    return jnp.einsum("bsi,igf->bsgf", jax.nn.relu(gate) * up, w_down) \
        * weight[..., None, None]


project = jax.jit(common.highest(_project), static_argnums=(2, 3))
attend = jax.jit(common.highest(_attend))
out_proj = jax.jit(common.highest(_out))
route = jax.jit(common.highest(_route), static_argnums=(1, 2, 3, 4))
normed = jax.jit(common.highest(rms), static_argnums=2)
one_expert = jax.jit(common.highest(_one_expert))


def attention_block(p, h, spec, eps):
    """``(what the layer adds to the stream h [b, s, g, f], the router's
    logits [b, s, experts])``."""
    r, q, k, v = project(p, h, spec["inv_freq"], eps)
    s = h.shape[1]
    out = []
    for start in range(0, s, QUERY_BLOCK):
        rows = np.arange(start, min(start + QUERY_BLOCK, s))
        out.append(attend(q[:, rows[0]:rows[-1] + 1], k, v,
                          jnp.asarray(visible(rows, s, spec["window"]))))
    return out_proj(jnp.concatenate(out, axis=1), p["w_out"]), r


def sparse_block(p, h, r, config):
    """``(this rank's routed part for the stream h under the logits r that
    the attention block before it made, the layer's router terms)``."""
    weights, losses = route(
        r, int(config["moe_top_k"]), bool(config["moe_norm_topk"]),
        float(config.get("moe_balance_loss", 0.0)),
        float(config.get("moe_router_z_loss", 0.0)))
    x = normed(h, p["w_norm"], float(config["norm_epsilon"]))
    first = int(config.get("experts_first", 0))
    out = jnp.zeros_like(x)
    for j in range(int(config.get("experts_held") or config["experts"])):
        # the layer's matrices j are expert first + j's
        out = out + one_expert(x, p["w_gate"][j], p["w_up"][j],
                               p["w_down"][j], weights[..., first + j])
    return out, losses


def hidden(variables, tokens, config, stream_dtype=None, router_losses=None):
    """The residual stream after the last block, ``[b, s, heads, width]``;
    ``router_losses``: a list that takes each sparse layer's router terms."""
    eps = float(config["norm_epsilon"])

    def stream(x):
        # the control of benchmark/precision_control.py: the stream rounded
        # to a lower precision after every block
        return x if stream_dtype is None \
            else x.astype(stream_dtype).astype(jnp.float32)

    h = stream(common.param(variables,
                            "input0/gather0/embed0/normal_var0")[tokens])
    r = None
    for kind, p, spec in layers_of(variables, config):
        if kind == "attention":
            out, r = attention_block(p, h, spec, eps)
        else:
            out, losses = sparse_block(p, h, r, config)
            r = None
            if router_losses is not None:
                router_losses.append(losses)
        h = stream(h + out)
    return h


def _head(variables):
    return common.param(variables, "output0/lang_out0_0/norm_0/normal_var0"), \
        common.param(variables, "output0/embed0/normal_var0")[:, :, 0, :]


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


def train_loss(variables, tokens, targets, config):
    """Next-token cross-entropy (+ the configuration's output z-loss) plus
    the sparse layers' router terms: the scalar whose gradient the program's
    step applies.  Differentiable: the logits stay on the device."""
    router: list = []
    h = hidden(variables, tokens, config, router_losses=router)
    logits = _logits(h, *_head(variables), float(config["norm_epsilon"]))
    return common.loss_of(logits, targets, config["z_loss"]) + sum(router)
