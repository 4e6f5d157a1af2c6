"""Plain reference of Laguna-S-2.1 as ONE RANK of an expert-parallel group
holds it: a pre-norm residual stream of gated grouped-query attention —
global layers with partly rotated, YaRN-scaled heads, window layers with
more heads of plain rotary — a leading dense SwiGLU MLP, then a 256-way
top-10 router over the experts held here beside a shared expert, RMSNorm,
untied head.

Written from the published ``config.json`` (``model_type: laguna``) and the
layer equations of ISSUE 36 in float32 ``jax.numpy`` under ``highest``
matmul precision: explicit einsums, a Python loop over layers, a Python loop
over the held experts with a mask (every held expert computes every token
and the mask keeps the chosen ones), attention over blocks of 512 queries
against all keys with the mask written out, no kernel, no sort, no scan.
Parameters are read by the names the program gives them — the seeded
weights have to be the same ones — and the layers' kinds, head counts and
window by the configuration's layer strings; nothing else is taken from the
program.

On ``h [b, s, 3072]``, layer ``l`` with ``H`` query heads (48 where
``layer_types[l]`` is ``full_attention``, 72 where ``sliding_attention``),
8 K/V heads of width 128:

    a = rms(h) * w1;  q = a Wq [s, H, 128];  k = a Wk, v = a Wv [s, 8, 128]
    full:     rope on the FIRST 64 features of each head (rotate-half inside
              them), theta 500,000, YaRN frequencies (HF
              ``_compute_yarn_parameters``: factor 128 from 8,192 positions,
              beta_fast 32, beta_slow 1, truncated), cos and sin times
              attention_factor 1.4852030263919618
    sliding:  rope on all 128 features, theta 10,000
    query head j reads K/V head j // (H / 8)
    key t is visible to query i iff t <= i (full), 0 <= i - t < 512 (sliding)
    o = softmax(q k^T / sqrt(128)) v;  o = o * sigmoid(a Wg)[..., None]
    h = h + o Wo
    layer 0:     m = rms(h) * w2;  h = h + Wd (silu(Wg m) * Wu m)   (12,288)
    layers >= 1: m = rms(h) * w2;  p = softmax(m Wr)  (float32, 256 logits)
                 top-10 of p;  w_e = 2.5 * p_e / sum_{top-10} p
                 h = h + shared(m) + sum_{e in top-10, e HELD HERE} w_e
                 expert_e(m);  shared, expert_e: SwiGLU of width 1,024

and ``logits = (rms(h) * wf) Whead`` over this rank's rows of the
vocabulary.  ``rms(x) = x / sqrt(mean(x^2) + 1e-6)``.  What the experts held
elsewhere would have added is left out (``experts_first``, ``experts_held``
of the configuration; 0 held = all of them, the uncut layer), and that
partial result is what goes on to the next layer.

Assumed, where ``config.json`` has no key (each also in
``benchmark/configs/laguna_s_2_1.json``): pre-norm block order; no QK-norm;
softmax scoring over all 256 logits (the lineage of ``norm_topk_prob``,
``decoder_sparse_step``, ``mlp_only_layers``,
``shared_expert_intermediate_size``); no router bias, no soft cap
(``moe_router_logit_softcapping`` 0); the shared expert ungated, weight 1;
the gate ``Wg`` from the block's normed input, no bias, a sigmoid a head
(``gating: per-head``; Qiu et al., arXiv:2505.06708); HF's
``sliding_window`` convention (the query's own position counts among the
512); a load-balancing term ``E * sum_e f_e P_e / 10`` per sparse layer over
ALL 256 experts (1.0 when balanced, f a constant) times the configuration's
``moe_balance_loss``, and the router z-loss ``mean(logsumexp(m Wr)^2)`` times
``moe_router_z_loss``: the layers' terms ADDED, reaching the gradients only
(``train_loss``); the reported loss is the cross-entropy alone.

Departure from HF, the program's too: HF rounds the router's weights to the
activations' dtype before it weights the experts' outputs; here they stay
float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import common

QUERY_BLOCK = 512
LOGIT_BLOCK = 2048
ATTENTION = {
    "w_key": "attention_0/normal_var0", "w_query": "attention_0/normal_var1",
    "w_value": "attention_0/normal_var2", "w_gate": "attention_0/normal_var3",
    "w_out": "attention_0/normal_var4",
}
DENSE = {"w_gate": "mlp_0/normal_var0", "w_up": "mlp_0/normal_var1",
         "w_down": "mlp_0/normal_var2"}
SPARSE = {
    "w_router": "moe_0/normal_var0", "w_gate": "moe_0/normal_var1",
    "w_up": "moe_0/normal_var2", "w_down": "moe_0/normal_var3",
    "s_gate": "moe_0/normal_var4", "s_up": "moe_0/normal_var5",
    "s_down": "moe_0/normal_var6",
}
NORM = "norm_0/normal_var0"


def rms(x, scale, eps: float):
    """RMSNorm over ALL trailing axes that ``scale`` has."""
    axes = tuple(range(x.ndim - scale.ndim, x.ndim))
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=axes,
                                      keepdims=True) + eps) * scale


# ---- rotary positions: HF's modeling_rope_utils, transcribed ----------------

def default_inv_freq(theta: float, dim: int):
    return 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)


def yarn_inv_freq(theta: float, dim: int, factor: float, original: int,
                  beta_fast: float, beta_slow: float):
    """``_compute_yarn_parameters`` (``truncate`` true) for ``dim`` rotated
    features: the inverse frequencies, float64."""
    def find_correction_dim(num_rotations):
        return (dim * math.log(original / (num_rotations * 2 * math.pi))) \
            / (2 * math.log(theta))

    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001          # prevent singularity
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    extrapolation = default_inv_freq(theta, dim)
    interpolation = extrapolation / factor
    extrapolation_factor = 1 - ramp
    return interpolation * (1 - extrapolation_factor) \
        + extrapolation * extrapolation_factor


def rope(x, inv_freq, factor: float):
    """HF's ``apply_rotary_pos_emb`` on ``x [b, s, h, d]``: the first ``2 *
    len(inv_freq)`` features of each head turn (rotate-half inside them),
    the rest pass; cos and sin times ``factor``."""
    s, dim = x.shape[1], 2 * len(inv_freq)
    freqs = jnp.arange(s, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    x_rot, x_pass = x[..., :dim], x[..., dim:]
    rotated = jnp.concatenate([-x_rot[..., dim // 2:], x_rot[..., :dim // 2]],
                              axis=-1)
    return jnp.concatenate([x_rot * (jnp.cos(emb) * factor)
                            + rotated * (jnp.sin(emb) * factor), x_pass],
                           axis=-1)


# ---- the configuration's layer strings ---------------------------------------

def layer_spec(layer: str, config: dict) -> dict:
    """What one attention layer string of the configuration says: the head
    counts, the window (None = global), and the rotary frequencies with
    their cos / sin factor."""
    name, *flags = layer.split("-")
    assert name == "attention", layer
    number = {f.rstrip("0123456789"): int(f[len(f.rstrip("0123456789")):])
              for f in flags if f[-1].isdigit()}
    width = config["features_per_head"] * number.get("rotary_pct", 100) // 100
    theta = float(number.get("theta", config["rope_theta"]))
    if "yarn" in flags:
        inv_freq = yarn_inv_freq(
            theta, width, config["rope_yarn_factor"],
            config["rope_yarn_original_positions"],
            config["rope_yarn_beta_fast"], config["rope_yarn_beta_slow"])
        factor = float(config["rope_yarn_attention_factor"])
    else:
        inv_freq, factor = default_inv_freq(theta, width), 1.0
    return {"heads": number["q_heads"], "kv_heads": number["kv_heads"],
            "window": number.get("window"),
            "inv_freq": tuple(float(f) for f in inv_freq), "factor": factor}


def _is(layer: str, name: str) -> bool:
    return layer.split("-")[0] == name


def layers_of(variables, config):
    """``(kind, parameters, spec)`` of every block in execution order: the
    leading blocks (``input_block_config``), then ``depth`` times the
    period (``block_config``).  A block is ``[norm, sublayer]``."""
    def blocks(cfgs, scope_of):
        for i, block in enumerate(cfgs):
            norm, sub = block["layer"]
            assert norm == "norm-rms-scale" and block["skip"], block
            names = ATTENTION if _is(sub, "attention") else \
                DENSE if _is(sub, "mlp") else SPARSE
            p = {k: common.param(variables, f"{scope_of(i)}/{path}")
                 for k, path in {**names, "w_norm": NORM}.items()}
            if _is(sub, "attention"):
                yield "attention", p, layer_spec(sub, config)
            elif _is(sub, "mlp"):
                yield "dense", p, None
            else:
                assert sub == "moe-silu-shared_expert", sub
                yield "sparse", p, None

    yield from blocks(config.get("input_block_config", []),
                      lambda i: f"input0/lang_inp{i}_0")
    for d in range(config["depth"]):
        yield from blocks(config["block_config"],
                          lambda i, d=d: f"body0/block{d}_{i}_0")


# ---- the layers ----------------------------------------------------------------

def _attention_block(p, h, heads, kv_heads, window, inv_freq, factor, eps):
    a = rms(h, p["w_norm"], eps)
    q = jnp.einsum("bsgf,gfhd->bshd", a, p["w_query"])
    k = jnp.einsum("bsgf,gfhd->bshd", a, p["w_key"])
    v = jnp.einsum("bsgf,gfhd->bshd", a, p["w_value"])
    gate = jax.nn.sigmoid(jnp.einsum("bsgf,gfh->bsh", a, p["w_gate"]))
    q, k = rope(q, inv_freq, factor), rope(k, inv_freq, factor)
    s, d = q.shape[1], q.shape[3]
    group = heads // kv_heads
    # query head j reads K/V head j // group
    q = q.reshape(q.shape[0], s, kv_heads, group, d)
    out = []
    for start in range(0, s, QUERY_BLOCK):
        qb = q[:, start:start + QUERY_BLOCK]
        score = jnp.einsum("bsngd,btnd->bngst", qb, k) / jnp.sqrt(
            jnp.float32(d))
        i = start + jnp.arange(qb.shape[1])[:, None]
        t = jnp.arange(s)[None, :]
        seen = t <= i
        if window is not None:
            seen &= i - t < window
        weight = jax.nn.softmax(jnp.where(seen, score, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bngst,btnd->bsngd", weight, v))
    o = jnp.concatenate(out, axis=1).reshape(q.shape[0], s, heads, d)
    return jnp.einsum("bshd,hdgf->bsgf", o * gate[..., None], p["w_out"])


def _swiglu(m, w_gate, w_up, w_down):
    gate = jnp.einsum("bsgf,gfi->bsi", m, w_gate)
    up = jnp.einsum("bsgf,gfi->bsi", m, w_up)
    return jnp.einsum("bsi,igf->bsgf", jax.nn.silu(gate) * up, w_down)


def _dense_block(p, h, eps):
    return _swiglu(rms(h, p["w_norm"], eps), p["w_gate"], p["w_up"],
                   p["w_down"])


def _route(p, h, top_k: int, norm_topk: bool, scale: float, eps,
           balance: float = 0.0, z: float = 0.0):
    """``(m, weights [b, s, experts], router losses)``: each token's weight
    for every routed expert, zero where the router did not choose it, and
    this layer's balance and z terms with their coefficients."""
    m = rms(h, p["w_norm"], eps)
    logits = jnp.einsum("bsgf,gfe->bse", m, p["w_router"])
    probs = jax.nn.softmax(logits, axis=-1)
    chosen = probs >= jax.lax.top_k(probs, top_k)[0][..., -1:]
    weights = jnp.where(chosen, probs, 0.0)
    if norm_topk:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    share = jax.lax.stop_gradient(
        jnp.mean(chosen.astype(jnp.float32), axis=(0, 1)))   # sums to top_k
    losses = balance * probs.shape[-1] * jnp.sum(
        share * jnp.mean(probs, axis=(0, 1))) / top_k + z * jnp.mean(
        jnp.square(jax.scipy.special.logsumexp(logits, axis=-1)))
    return m, weights * scale, losses


def _one_expert(m, w_gate, w_up, w_down, weight):
    """One expert on EVERY token, times the token's weight for it."""
    return _swiglu(m, w_gate, w_up, w_down) * weight[..., None, None]


attention_block = jax.jit(common.highest(_attention_block),
                          static_argnums=(2, 3, 4, 5, 6, 7))
dense_block = jax.jit(common.highest(_dense_block), static_argnums=2)
route = jax.jit(common.highest(_route), static_argnums=(2, 3, 4, 5, 6, 7))
one_expert = jax.jit(common.highest(_one_expert))
swiglu = jax.jit(common.highest(_swiglu))


def routed_part(p, m, weights, first: int, held: int):
    """What experts ``first .. first + held - 1`` add, their weights
    ``p["w_gate"][j]`` being expert ``first + j``'s: a Python loop, one small
    program run once an expert."""
    out = jnp.zeros_like(m)
    for j in range(held):
        out = out + one_expert(m, p["w_gate"][j], p["w_up"][j],
                               p["w_down"][j], weights[..., first + j])
    return out


def sparse_block(p, h, config):
    """``(the shared expert, counted once, plus this rank's routed part;
    the layer's router losses)``."""
    m, weights, losses = route(
        p, h, int(config["moe_top_k"]), bool(config["moe_norm_topk"]),
        float(config["moe_route_scale"]), float(config["norm_epsilon"]),
        float(config.get("moe_balance_loss", 0.0)),
        float(config.get("moe_router_z_loss", 0.0)))
    held = int(config.get("experts_held") or config["experts"])
    return swiglu(m, p["s_gate"], p["s_up"], p["s_down"]) + routed_part(
        p, m, weights, int(config.get("experts_first", 0)), held), losses


@jax.jit
@common.highest
def _logits(h, scale, w_head, eps):
    return jnp.einsum("bsgf,gfv->bsv", rms(h, scale, eps), w_head)


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
    for kind, p, spec in layers_of(variables, config):
        if kind == "attention":
            h = h + attention_block(p, h, spec["heads"], spec["kv_heads"],
                                    spec["window"], spec["inv_freq"],
                                    spec["factor"], eps)
        elif kind == "dense":
            h = h + dense_block(p, h, eps)
        else:
            out, losses = sparse_block(p, h, config)
            h = h + out
            if router_losses is not None:
                router_losses.append(losses)
        h = stream(h)
    return h


def forward(variables, tokens, config, stream_dtype=None):
    """Logits ``[b, s, vocab]`` (float32) for ``tokens [b, s]``, made in
    blocks of ``LOGIT_BLOCK`` positions and handed over as a host array, so
    that they fit beside the train state.  ``stream_dtype``: the control's
    lower-precision residual stream."""
    h = hidden(variables, tokens, config, stream_dtype)
    scale = common.param(variables, "output0/lang_out0_0/norm_0/normal_var0")
    w_head = common.param(variables, "output0/embed0/normal_var0")[:, :, 0, :]
    eps = float(config["norm_epsilon"])
    return np.concatenate([
        np.asarray(_logits(h[:, i:i + LOGIT_BLOCK], scale, w_head, eps))
        for i in range(0, h.shape[1], LOGIT_BLOCK)], axis=1)


def train_loss(variables, tokens, targets, config):
    """Cross-entropy (+ the configuration's output z-loss) plus the sparse
    layers' router terms: the scalar whose gradient the program's step
    applies.  Differentiable: the logits stay on the device."""
    router: list = []
    h = hidden(variables, tokens, config, router_losses=router)
    logits = _logits(
        h, common.param(variables, "output0/lang_out0_0/norm_0/normal_var0"),
        common.param(variables, "output0/embed0/normal_var0")[:, :, 0, :],
        float(config["norm_epsilon"]))
    return common.loss_of(logits, targets, config["z_loss"]) + sum(router)
