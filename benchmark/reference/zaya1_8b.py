"""Plain reference of ZAYA1-8B as ONE RANK of an expert-parallel pair holds
it: a residual stream with a scaled merge, compressed convolutional
attention (CCA) in a latent half as wide as the stream, then a top-1 router
— an MLP fed by the previous layer's router state — over the experts held
here; RMSNorm, a tied head.

Written from the published ``config.json`` (``model_type: zaya``), Zyphra's
CCA paper (arXiv:2510.04476) and the ZAYA1 report (arXiv:2511.17127) as the
layer equations of ISSUE 39 set them down, in float32 ``jax.numpy`` under
``highest`` matmul precision: explicit einsums, a Python loop over layers, a
Python loop over the held experts with a mask (every held expert computes
every token and the mask keeps the chosen ones), the convolutions as shifted
copies of the sequence, attention over blocks of 512 queries against all
keys with the mask written out; no kernel, no sort, no scan, no cache.
Parameters are read by the names the program gives them — the seeded weights
have to be the same ones — and the head counts, the rotated share and theta
by the configuration's layer string; nothing else is taken from the program.

On ``h [b, s, 2048]``, ``H = 8`` query heads, ``G = 2`` K/V heads, ``d =
128``; ``rms(x) = x / sqrt(mean(x^2) + 1e-5) * w``:

    merge:  h <- (h * a_r + b_r) + (f(rms(h)) * a_o + b_o)       both sublayers
    CCA (x = rms(h), x[-1] = 0):
        q~ = x Wq [s, 8, 128];  k~ = x Wk [s, 2, 128]
        m_q[j] = (q~[j] + k~[j // 4]) / 2;  m_k[g] = mean_j m_q[j], j in g
        c = [q~ ; k~] [s, 1280]
        c1[t] = w0[0] * c[t-1] + w0[1] * c[t] + b0                depthwise
        c2[t] = W1[0] c1[t-1] + W1[1] c1[t] + b1                  10 blocks of
                                                                  128 x 128 a tap
        q = c2[:1024] + m_q;  k = c2[1024:] + m_k
        q = q / |q| sqrt(128);  k = k / |k| sqrt(128) tau[g]
        rope on the first 64 features of a head, theta 5,000,000
        v = [x Wv1 ; x[t-1] Wv2] [s, 2, 128]
        o = softmax(q k^T / sqrt(128), causal) v;  f = o Wo
    router and experts of layer l (x = rms(h)):
        r_l = x Wd + bd + g_l * r_{l-1}                           r_{-1} = 0
        u = rms(r_l) * w_r;  z = W3 gelu(W2 gelu(W1 u + b1) + b2)  [s, 16]
        p = softmax(z);  e = argmax p
        f = p[e] * down_e(silu(x gate_e) * (x up_e))   if e is HELD HERE, else 0

and ``logits = rms(h) E^T`` over this rank's rows of the tied table ``E``.
What the experts held elsewhere would have added is left out
(``experts_first``, ``experts_held`` of the configuration; 0 held = all of
them, the uncut layer), and that partial result is what goes on.

Assumed, where ``config.json`` has no key (each also in
``benchmark/configs/zaya1_8b.json``): pre-norm sublayers and a final norm;
the merge's form; biases on both convolutions and none on the projections,
no activation between the convolutions, q and k packed into one
convolution; ``tau`` a key head on the normalised key; ``|x|`` is
``sqrt(sum x^2 + 1e-12)``; the value shift's split by K/V head; the router
MLP's three layers with GELU in its erf form, biases on the first two, the
RMSNorm before it, ``g_l`` in every layer; no skip expert and no balancing
bias; a load-balancing term ``E * sum_e f_e P_e`` per sparse layer over ALL
16 experts (1.0 when balanced, f a constant) times the configuration's
``moe_balance_loss`` and the router z-loss times ``moe_router_z_loss``, the
layers' terms ADDED and reaching the gradients only (``train_loss``); the
reported loss is the cross-entropy alone.

Departure from the equations as written: none.  (The program shifts the
PROJECTED previous-token values, ``(x Wv2)[t-1]``; here ``x[t-1]`` is shifted
and then projected, as written.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import common

QUERY_BLOCK = 512
LOGIT_BLOCK = 2048
NORM_EPS = 1e-12
CCA = {
    "w_query": "cca_0/normal_var0", "w_key": "cca_0/normal_var1",
    "w_value_now": "cca_0/normal_var2", "w_value_prev": "cca_0/normal_var3",
    "w_conv0": "cca_0/normal_var4", "b_conv0": "cca_0/constant_var0",
    "w_conv1": "cca_0/normal_var5", "b_conv1": "cca_0/constant_var1",
    "tau": "cca_0/constant_var2", "w_out": "cca_0/normal_var6",
}
SPARSE = {
    "w_gate": "moe_0/normal_var0", "w_up": "moe_0/normal_var1",
    "w_down": "moe_0/normal_var2",
    "r_down": "moe_0/normal_var3", "r_down_bias": "moe_0/constant_var0",
    "r_gain": "moe_0/constant_var1", "r_norm": "moe_0/constant_var2",
    "r_w1": "moe_0/normal_var4", "r_b1": "moe_0/constant_var3",
    "r_w2": "moe_0/normal_var5", "r_b2": "moe_0/constant_var4",
    "r_w3": "moe_0/normal_var6",
}
SHARED = {
    "w_norm": "norm_0/normal_var0",
    "a_res": "merge_0/constant_var0", "b_res": "merge_0/constant_var1",
    "a_out": "merge_0/constant_var2", "b_out": "merge_0/constant_var3",
}


def rms(x, scale, eps: float):
    """RMSNorm over ALL trailing axes that ``scale`` has."""
    axes = tuple(range(x.ndim - scale.ndim, x.ndim))
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=axes,
                                      keepdims=True) + eps) * scale


def previous(x):
    """``y[t] = x[t - 1]`` along axis 1, ``y[0] = 0``."""
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def taps(x, count: int):
    """``[x[t - count + 1], .., x[t - 1], x[t]]``: what each of a causal
    convolution's ``count`` taps reads, tap 0 furthest back."""
    out = [x]
    for _ in range(count - 1):
        out.insert(0, previous(out[0]))
    return out


def rope(x, theta: float, width: int):
    """HF's ``apply_rotary_pos_emb`` on ``x [b, s, h, d]``: the first
    ``width`` features of each head turn (rotate-half inside them), the rest
    pass."""
    inv_freq = 1.0 / theta ** (np.arange(0, width, 2, dtype=np.float64)
                               / width)
    freqs = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    x_rot, x_pass = x[..., :width], x[..., width:]
    rotated = jnp.concatenate([-x_rot[..., width // 2:],
                               x_rot[..., :width // 2]], axis=-1)
    return jnp.concatenate([x_rot * jnp.cos(emb) + rotated * jnp.sin(emb),
                            x_pass], axis=-1)


def unit(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                        + NORM_EPS)


# ---- the configuration's layer strings ---------------------------------------

def cca_spec(layer: str, config: dict) -> tuple:
    """``(query heads, K/V heads, rotated features, theta)`` of the layer
    string ``cca-q_heads<n>-kv_heads<m>-rotary_pct<p>-theta<t>``."""
    name, *flags = layer.split("-")
    assert name == "cca", layer
    number = {f.rstrip("0123456789"): int(f[len(f.rstrip("0123456789")):])
              for f in flags}
    return (number["q_heads"], number["kv_heads"],
            config["features_per_head"] * number.get("rotary_pct", 100)
            // 100, float(number.get("theta", config["rope_theta"])))


def layers_of(variables, config):
    """``(kind, parameters, spec)`` of every sublayer in execution order:
    ``depth`` times the period (``block_config``), each block ``[norm,
    sublayer]`` with the scaled merge."""
    for d in range(config["depth"]):
        for i, block in enumerate(config["block_config"]):
            norm, sub = block["layer"]
            assert norm == "norm-rms-scale" and block["skip"] \
                and block["merge"] == "scaled", block
            kind = sub.split("-")[0]
            names = {**(CCA if kind == "cca" else SPARSE), **SHARED}
            p = {k: common.param(variables,
                                 f"body0/block{d}_{i}_0/{path}")
                 for k, path in names.items()}
            if kind == "cca":
                yield "cca", p, cca_spec(sub, config)
            else:
                assert sub == "moe-silu-router_mlp", sub
                yield "sparse", p, None


# ---- the layers ----------------------------------------------------------------

def _merge(p, h, out):
    return (h * p["a_res"] + p["b_res"]) + (out * p["a_out"] + p["b_out"])


def _cca_block(p, h, heads, kv_heads, width, theta, eps):
    x = rms(h, p["w_norm"], eps)
    b, s = x.shape[:2]
    d = p["w_query"].shape[-1]
    group = heads // kv_heads
    q_lat = jnp.einsum("bsgf,gfhd->bshd", x, p["w_query"])
    k_lat = jnp.einsum("bsgf,gfhd->bshd", x, p["w_key"])
    # the q-k mean of the un-convolved latents: query head j with K/V head
    # j // group; a key head takes the mean over its group
    mean_q = (q_lat.reshape(b, s, kv_heads, group, d)
              + k_lat[:, :, :, None, :]) / 2
    mean_k = jnp.mean(mean_q, axis=3)
    mean_q = mean_q.reshape(b, s, heads, d)
    # two causal convolutions over the packed latent, written as shifted
    # copies: tap 0 reads the token furthest back
    c = jnp.concatenate([q_lat, k_lat], axis=2).reshape(b, s, -1)
    c1 = sum(w * x_t for w, x_t in zip(
        p["w_conv0"], taps(c, p["w_conv0"].shape[0]))) + p["b_conv0"]
    c1 = c1.reshape(b, s, heads + kv_heads, d)
    c2 = sum(jnp.einsum("bsgi,gio->bsgo", x_t, w) for w, x_t in zip(
        p["w_conv1"], taps(c1, p["w_conv1"].shape[0]))) + p["b_conv1"]
    q = c2[:, :, :heads] + mean_q
    k = c2[:, :, heads:] + mean_k
    root = jnp.sqrt(jnp.float32(d))
    q = unit(q) * root
    k = unit(k) * root * p["tau"][:, None]
    q, k = rope(q, theta, width), rope(k, theta, width)
    # the value shift: the first half of the K/V heads from this token, the
    # second from the one before it
    v = jnp.concatenate([
        jnp.einsum("bsgf,gfhd->bshd", x, p["w_value_now"]),
        jnp.einsum("bsgf,gfhd->bshd", previous(x), p["w_value_prev"])],
        axis=2)
    q = q.reshape(b, s, kv_heads, group, d)
    out = []
    for start in range(0, s, QUERY_BLOCK):
        qb = q[:, start:start + QUERY_BLOCK]
        score = jnp.einsum("bsngd,btnd->bngst", qb, k) / root
        i = start + jnp.arange(qb.shape[1])[:, None]
        seen = jnp.arange(s)[None, :] <= i
        weight = jax.nn.softmax(jnp.where(seen, score, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bngst,btnd->bsngd", weight, v))
    o = jnp.concatenate(out, axis=1).reshape(b, s, heads, d)
    return _merge(p, h, jnp.einsum("bshd,hdgf->bsgf", o, p["w_out"]))


def _route(p, h, state, eps, balance: float = 0.0, z: float = 0.0):
    """``(x, this layer's router state, weights [b, s, experts], router
    losses)``: each token's probability for the ONE expert it chose, zero
    for every other."""
    x = rms(h, p["w_norm"], eps)
    state = jnp.einsum("bsgf,gfw->bsw", x, p["r_down"]) + p["r_down_bias"] \
        + p["r_gain"] * state
    u = rms(state, p["r_norm"], eps)
    u = jax.nn.gelu(u @ p["r_w1"] + p["r_b1"], approximate=False)
    u = jax.nn.gelu(u @ p["r_w2"] + p["r_b2"], approximate=False)
    logits = u @ p["r_w3"]
    probs = jax.nn.softmax(logits, axis=-1)
    chosen = jax.nn.one_hot(jnp.argmax(probs, axis=-1), probs.shape[-1],
                            dtype=jnp.float32)
    share = jax.lax.stop_gradient(jnp.mean(chosen, axis=(0, 1)))
    losses = balance * probs.shape[-1] * jnp.sum(
        share * jnp.mean(probs, axis=(0, 1))) + z * jnp.mean(
        jnp.square(jax.scipy.special.logsumexp(logits, axis=-1)))
    return x, state, probs * chosen, losses


def _one_expert(x, w_gate, w_up, w_down, weight):
    """One expert on EVERY token, times the token's weight for it."""
    gate = jnp.einsum("bsgf,gfi->bsi", x, w_gate)
    up = jnp.einsum("bsgf,gfi->bsi", x, w_up)
    return jnp.einsum("bsi,igf->bsgf", jax.nn.silu(gate) * up, w_down) \
        * weight[..., None, None]


cca_block = jax.jit(common.highest(_cca_block),
                    static_argnums=(2, 3, 4, 5, 6))
route = jax.jit(common.highest(_route), static_argnums=(3, 4, 5))
one_expert = jax.jit(common.highest(_one_expert))
merge = jax.jit(_merge)


def routed_part(p, x, weights, first: int, held: int):
    """What experts ``first .. first + held - 1`` add, their weights
    ``p["w_gate"][j]`` being expert ``first + j``'s: a Python loop, one small
    program run once an expert."""
    out = jnp.zeros_like(x)
    for j in range(held):
        out = out + one_expert(x, p["w_gate"][j], p["w_up"][j],
                               p["w_down"][j], weights[..., first + j])
    return out


def sparse_block(p, h, state, config):
    """``(the stream after the merge, this layer's router state, the layer's
    router losses)``."""
    x, state, weights, losses = route(
        p, h, state, float(config["norm_epsilon"]),
        float(config.get("moe_balance_loss", 0.0)),
        float(config.get("moe_router_z_loss", 0.0)))
    held = int(config.get("experts_held") or config["experts"])
    out = routed_part(p, x, weights, int(config.get("experts_first", 0)),
                      held)
    return merge(p, h, out), state, losses


@jax.jit
@common.highest
def _logits(h, scale, table, eps):
    return jnp.einsum("bsgf,vgf->bsv", rms(h, scale, eps), table)


def hidden(variables, tokens, config, stream_dtype=None, router_losses=None):
    """The residual stream after the last block, ``[b, s, heads, width]``;
    ``router_losses``: a list that takes each sparse layer's router terms."""
    eps = float(config["norm_epsilon"])
    assert int(config["moe_top_k"]) == 1 and config["tie_word_embeddings"]

    def stream(x):
        # the control of benchmark/precision_control.py: the stream rounded
        # to a lower precision after every block
        return x if stream_dtype is None \
            else x.astype(stream_dtype).astype(jnp.float32)

    table = common.param(variables, "input0/gather0/embed0/normal_var0")
    h = stream(table[tokens])
    # the first layer of the cut takes r_{-1} = 0, as layer 0 of the whole
    # model does
    state = jnp.zeros(tokens.shape + (int(config["moe_router_width"]),),
                      jnp.float32)
    for kind, p, spec in layers_of(variables, config):
        if kind == "cca":
            h = cca_block(p, h, *spec, eps)
        else:
            h, state, losses = sparse_block(p, h, state, config)
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
    table = common.param(variables, "input0/gather0/embed0/normal_var0")
    eps = float(config["norm_epsilon"])
    return np.concatenate([
        np.asarray(_logits(h[:, i:i + LOGIT_BLOCK], scale, table, eps))
        for i in range(0, h.shape[1], LOGIT_BLOCK)], axis=1)


def train_loss(variables, tokens, targets, config):
    """Cross-entropy (+ the configuration's output z-loss) plus the sparse
    layers' router terms: the scalar whose gradient the program's step
    applies.  Differentiable: the logits stay on the device."""
    router: list = []
    h = hidden(variables, tokens, config, router_losses=router)
    logits = _logits(
        h, common.param(variables, "output0/lang_out0_0/norm_0/normal_var0"),
        common.param(variables, "input0/gather0/embed0/normal_var0"),
        float(config["norm_epsilon"]))
    return common.loss_of(logits, targets, config["z_loss"]) + sum(router)
