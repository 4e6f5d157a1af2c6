"""Plain reference of OLMoE-1B-7B: pre-norm residual stream of RoPE +
QK-norm attention and 64 x top-8 SwiGLU experts, RMSNorm, untied head.

Written from HF ``modeling_olmoe.py``'s layer equations in float32
``jax.numpy`` under ``highest`` matmul precision: explicit einsums, a Python
loop over layers, a Python loop over experts with a mask (every expert
computes every token and the mask keeps the chosen ones), no kernel, no sort,
no scan.  Parameters are read by the names the program gives them — the
seeded weights have to be the same ones — and nothing else is taken from it.

Per layer, on ``h [b, s, 2048]``:

    a = rms(h) * w1;  q, k, v = a Wq, a Wk, a Wv          (no bias)
    q = rms(q) * wq;  k = rms(k) * wk     over all 2048 columns, THEN the
                                          split into 16 heads x 128
    q, k = rope(q), rope(k)               theta 10,000, rotate-half
    h = h + causal softmax(q k^T / sqrt(128)) v Wo
    m = rms(h) * w2;  p = softmax(m Wr)   float32, over all 64 experts
    h = h + sum_{e in top-8(p)} p_e Wdown_e (silu(Wgate_e m) * Wup_e m)

and ``logits = (rms(h) * wf) Whead``.  ``rms(x) = x / sqrt(mean(x^2) +
1e-5)``.  The 8 largest ``p`` are used as they are (``norm_topk_prob``
false), no token is dropped.

Departures from HF, each the program's too:
- HF adds the load-balancing loss ``E * sum_e f_e P_e`` over all layers'
  tokens at once (f summing to 8) times ``router_aux_loss_coef``; here, as in
  the OLMoE paper's training code, it is ``E * sum_e f_e P_e / 8`` per layer
  (1.0 when balanced), f a constant, and the layers' terms are ADDED; the
  router z-loss ``mean(logsumexp(m Wr)^2)`` likewise per layer.  Their
  coefficients are the configuration's ``moe_balance_loss`` and
  ``moe_router_z_loss``; HF has no router z-loss.
- the reported loss is the cross-entropy alone (``loss_of``); the router
  terms reach the gradients only (``train_loss`` is the function whose
  gradient the program's step applies).
- HF rounds the router's probabilities to the activations' dtype before it
  weights the experts' outputs; here they stay float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common

EPS = 1e-5
ATTENTION = {
    "w1": "norm_0/normal_var0",
    "w_key": "attention_0/normal_var0", "w_query": "attention_0/normal_var1",
    "w_value": "attention_0/normal_var2",
    "scale_query": "attention_0/normal_var3",
    "scale_key": "attention_0/normal_var4",
    "w_out": "attention_0/normal_var5",
}
EXPERTS = {
    "w2": "norm_0/normal_var0",
    "w_router": "moe_0/normal_var0", "w_gate": "moe_0/normal_var1",
    "w_up": "moe_0/normal_var2", "w_down": "moe_0/normal_var3",
}


def rms(x, scale):
    """RMSNorm over ALL trailing axes that ``scale`` has."""
    axes = tuple(range(x.ndim - scale.ndim, x.ndim))
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=axes,
                                      keepdims=True) + EPS) * scale


def rope(x, theta: float):
    """HF's rotate-half rotary embedding on ``x [b, s, h, d]``."""
    s, d = x.shape[1], x.shape[3]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    freqs = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(emb) + rotated * jnp.sin(emb)


def _attention_block(p, h, theta: float):
    a = rms(h, p["w1"])
    q = rms(jnp.einsum("bsgf,gfhd->bshd", a, p["w_query"]), p["scale_query"])
    k = rms(jnp.einsum("bsgf,gfhd->bshd", a, p["w_key"]), p["scale_key"])
    v = jnp.einsum("bsgf,gfhd->bshd", a, p["w_value"])
    q, k = rope(q, theta), rope(k, theta)
    s, d = q.shape[1], q.shape[3]
    score = jnp.einsum("bshd,bthd->bhst", q, k) / jnp.sqrt(jnp.float32(d))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    weight = jax.nn.softmax(jnp.where(causal, score, -jnp.inf), axis=-1)
    o = jnp.einsum("bhst,bthd->bshd", weight, v)
    return jnp.einsum("bsgf,gfhd->bshd", o, p["w_out"])


def _router(p, h, top_k: int):
    """``(m, router logits, probabilities, mask of the top_k)``."""
    m = rms(h, p["w2"])
    logits = jnp.einsum("bsgf,gfe->bse", m, p["w_router"])
    probs = jax.nn.softmax(logits, axis=-1)
    kth = jax.lax.top_k(probs, top_k)[0][..., -1:]
    return m, logits, probs, probs >= kth


def _one_expert(m, w_gate, w_up, w_down, weight):
    """One expert on EVERY token, times the token's weight for it (zero
    where the router did not choose it)."""
    gate = jnp.einsum("bsgf,gfi->bsi", m, w_gate)
    up = jnp.einsum("bsgf,gfi->bsi", m, w_up)
    y = jnp.einsum("bsi,ihd->bshd", jax.nn.silu(gate) * up, w_down)
    return y * weight[..., None, None]


attention_block = jax.jit(common.highest(_attention_block), static_argnums=2)
router = jax.jit(common.highest(_router), static_argnums=2)
one_expert = jax.jit(common.highest(_one_expert))


def expert_block(p, h, top_k: int):
    """A Python loop over the experts: one small program, run once an
    expert, so that the reference compiles in seconds at 64 experts."""
    m, _, probs, chosen = router(p, h, top_k)
    out = jnp.zeros_like(h)
    for e in range(probs.shape[-1]):
        out = out + one_expert(m, p["w_gate"][e], p["w_up"][e],
                               p["w_down"][e],
                               jnp.where(chosen[..., e], probs[..., e], 0.0))
    return out


@jax.jit
@common.highest
def _logits(h, scale, w_head):
    return jnp.einsum("bshd,hdv->bsv", rms(h, scale), w_head)


def _blocks(variables, config):
    for d in range(config["depth"]):
        yield (common.block_params(variables, d, 0, ATTENTION),
               common.block_params(variables, d, 1, EXPERTS))


def forward(variables, tokens, config):
    """Logits ``[b, s, vocab]`` (float32) for ``tokens [b, s]``."""
    h = common.param(variables,
                     "input0/gather0/embed0/normal_var0")[tokens]
    for at, ex in _blocks(variables, config):
        h = h + attention_block(at, h, float(config["rope_theta"]))
        h = h + expert_block(ex, h, int(config["moe_top_k"]))
    return _logits(
        h, common.param(variables, "output0/lang_out0_0/norm_0/normal_var0"),
        common.param(variables, "output0/embed0/normal_var0")[:, :, 0, :])


def router_losses(variables, tokens, config):
    """The two router terms, summed over the layers, with their
    coefficients: what the program's step adds to the cross-entropy's
    gradient (and never reports)."""
    k, total = int(config["moe_top_k"]), 0.0
    h = common.param(variables,
                     "input0/gather0/embed0/normal_var0")[tokens]
    for at, ex in _blocks(variables, config):
        h = h + attention_block(at, h, float(config["rope_theta"]))
        _, logits, probs, chosen = router(ex, h, k)
        n_exp = probs.shape[-1]
        share = jax.lax.stop_gradient(
            jnp.mean(chosen.astype(jnp.float32), axis=(0, 1)))  # sums to k
        balance = n_exp * jnp.sum(share * jnp.mean(probs, axis=(0, 1))) / k
        z = jnp.mean(jnp.square(
            jax.scipy.special.logsumexp(logits, axis=-1)))
        total = total + config["moe_balance_loss"] * balance \
            + config["moe_router_z_loss"] * z
        h = h + expert_block(ex, h, k)
    return total


def train_loss(variables, tokens, targets, config):
    """Cross-entropy (+ the configuration's output z-loss) plus the router
    terms: the scalar whose gradient the program's step applies."""
    return common.loss_of(forward(variables, tokens, config), targets,
                          config["z_loss"]) \
        + router_losses(variables, tokens, config)
