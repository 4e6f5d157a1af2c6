"""Plain reference of Ouro-2.6B, a looped language model: ONE stack of
sandwich-norm layers (RoPE attention, SwiGLU) applied ``loop_steps`` times to
the stream with the same weights, the final norm and the untied head after
every pass, and an exit gate a token that spreads the loss over the passes.

Written from the equations of ISSUE 49 (the published ``config.json`` names the
loop and its count, ``total_ut_steps`` 4; the layer, the loop, the gate and the
loss are HF ``modeling_ouro.py``'s and the paper's, "Scaling Latent Reasoning
via Looped Language Models", arXiv:2510.25741) in float32 ``jax.numpy`` under
``highest`` matmul precision: explicit einsums, a Python loop over the passes
and one over the layers, no kernel, no scan, no checkpoint.  Parameters are
read by the names the program gives them — the seeded weights have to be the
same ones — and nothing else is taken from it.

A layer, on ``h [b, s, 2048]`` (four RMS norms, each with a learned scale):

    a = n1(h);  q, k, v = a Wq, a Wk, a Wv        (no bias; 16 heads x 128)
    q, k = rope(q), rope(k)                       theta 1,000,000, rotate-half
    h = h + n2(causal softmax(q k^T / sqrt(128)) v Wo)
    h = h + n4(Wd (silu(Wg n3(h)) * Wu n3(h)))

The loop: ``h_0 = E[ids]``; ``h_t = norm_f(stack(h_(t-1)))`` for ``t = 1 ..
T`` — the same layers, the same positions, the final norm at the end of every
pass and its OUTPUT the next pass's input; ``logits_t = h_t W_head``.

The gate (one linear with a bias, shared by the passes): ``lambda_t =
sigmoid(w_g . h_t + b_g)``; ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for
``t < T``, ``p_T = prod_{j<T} (1 - lambda_j)``.  The loss is the mean over the
tokens of ``sum_t p_t CE_t - beta H(p)``, ``H(p) = - sum_t p_t log p_t``,
``beta = loop_exit_entropy``.  ``rms(x) = x / sqrt(mean(x^2) + 1e-6)``.

Departures, each the program's too: the gate is stage one's only (stage two
trains the gate alone on what a pass improves; exit by
``early_exit_threshold`` and the passes' KV caches are serving's); the gate
of the LAST pass is never read (``p_T`` is what the earlier ones left).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import common

ATTENTION = {
    "n1": "norm_0/normal_var0",
    "w_key": "attention_0/normal_var0", "w_query": "attention_0/normal_var1",
    "w_value": "attention_0/normal_var2", "w_out": "attention_0/normal_var3",
    "n2": "norm_1/normal_var0",
}
MLP = {
    "n3": "norm_0/normal_var0",
    "w_gate": "mlp_0/normal_var0", "w_up": "mlp_0/normal_var1",
    "w_down": "mlp_0/normal_var2",
    "n4": "norm_1/normal_var0",
}
FINAL_NORM = "output0/lang_out0_0/norm_0/normal_var0"
HEAD = "output0/embed0/normal_var0"
TABLE = "input0/gather0/embed0/normal_var0"
GATE_WEIGHT = "loss0/exit_gate0/normal_var0"
GATE_BIAS = "loss0/exit_gate0/constant_var0"


def rms(x, scale, eps: float):
    """RMSNorm over ALL trailing axes that ``scale`` has."""
    axes = tuple(range(x.ndim - scale.ndim, x.ndim))
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=axes,
                                      keepdims=True) + eps) * scale


def rope(x, theta: float):
    """HF's rotate-half rotary embedding on ``x [b, s, h, d]``, all ``d``
    features turned."""
    s, d = x.shape[1], x.shape[3]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    freqs = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(emb) + rotated * jnp.sin(emb)


def _attention_block(p, h, theta: float, eps: float):
    a = rms(h, p["n1"], eps)
    q = rope(jnp.einsum("bsgf,gfhd->bshd", a, p["w_query"]), theta)
    k = rope(jnp.einsum("bsgf,gfhd->bshd", a, p["w_key"]), theta)
    v = jnp.einsum("bsgf,gfhd->bshd", a, p["w_value"])
    s, d = q.shape[1], q.shape[3]
    score = jnp.einsum("bshd,bthd->bhst", q, k) / jnp.sqrt(jnp.float32(d))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    weight = jax.nn.softmax(jnp.where(causal, score, -jnp.inf), axis=-1)
    o = jnp.einsum("bhst,bthd->bshd", weight, v)
    return rms(jnp.einsum("bsgf,gfhd->bshd", o, p["w_out"]), p["n2"], eps)


def _mlp_block(p, h, eps: float):
    m = rms(h, p["n3"], eps)
    gate = jnp.einsum("bsgf,gfi->bsi", m, p["w_gate"])
    up = jnp.einsum("bsgf,gfi->bsi", m, p["w_up"])
    return rms(jnp.einsum("bsi,ihd->bshd", jax.nn.silu(gate) * up,
                          p["w_down"]), p["n4"], eps)


attention_block = jax.jit(common.highest(_attention_block),
                          static_argnums=(2, 3))
mlp_block = jax.jit(common.highest(_mlp_block), static_argnums=2)
final_norm = jax.jit(rms, static_argnums=2)


@jax.jit
@common.highest
def logits_of(h, w_head):
    return jnp.einsum("bshd,hdv->bsv", h, w_head)


def passes(variables, tokens, config, stream_dtype=None,
           pass_variables=None):
    """``[h_1 .. h_T]``, each ``[b, s, heads, features]``: what the head and
    the gate read after every pass.  ``stream_dtype``: round the stream to it
    after the embedding, after every block and after every pass's final norm
    — not the model, but what a lower activation precision than the
    configuration's does to it.  ``pass_variables``: a dict of parameters a
    pass, where a test gives every pass a copy of the weights of its own
    (the default: ``variables`` at every pass, which is the model)."""
    def stream(h):
        return h if stream_dtype is None \
            else h.astype(stream_dtype).astype(jnp.float32)

    if f"{common.ROOT}/{GATE_WEIGHT}/var0" not in variables:
        # a program that does not know ``loop_steps`` (a parent of PR 49)
        # takes the key with a warning and trains an un-looped model
        raise KeyError(f"the program made no {GATE_WEIGHT}: it runs no "
                       "looped model, and this reference is of one")
    theta, eps = float(config["rope_theta"]), float(config["norm_epsilon"])
    h = stream(common.param(variables, TABLE)[tokens])
    out = []
    for step in range(int(config["loop_steps"])):
        own = variables if pass_variables is None else pass_variables[step]
        for d in range(int(config["depth"])):
            h = stream(h + attention_block(
                common.block_params(own, d, 0, ATTENTION), h, theta, eps))
            h = stream(h + mlp_block(
                common.block_params(own, d, 1, MLP), h, eps))
        h = stream(final_norm(h, common.param(own, FINAL_NORM), eps))
        out.append(h)
    return out


def exit_distribution(variables, hidden):
    """``p [T, b, s]`` from the passes' outputs: the gate reads ``h_1 ..
    h_(T-1)``."""
    w = common.param(variables, GATE_WEIGHT)
    b = common.param(variables, GATE_BIAS)
    lam = [jax.nn.sigmoid(jnp.sum(h * w, axis=(-2, -1)) + b)
           for h in hidden[:-1]]
    p, left = [], jnp.ones_like(lam[0])
    for gate in lam:
        p.append(gate * left)
        left = left * (1.0 - gate)
    return jnp.stack(p + [left])


def token_losses(logits, targets):
    """Cross-entropy a token, ``[b, s]``."""
    log_z = jax.scipy.special.logsumexp(logits, axis=-1)
    return log_z - jnp.take_along_axis(logits, targets[..., None],
                                       axis=-1)[..., 0]


def outputs(variables, tokens, targets, config, stream_dtype=None,
            pass_variables=None, keep_logits: bool = True):
    """Everything a step reads: ``{"logits": [T, b, s, vocab] (None without
    keep_logits), "token_loss": CE_t [T, b, s], "p": [T, b, s], "entropy":
    H(p) [b, s], "loss": the scalar}``."""
    hidden = passes(variables, tokens, config, stream_dtype, pass_variables)
    w_head = common.param(variables, HEAD)[:, :, 0, :]
    logits = [logits_of(h, w_head) for h in hidden] if keep_logits else None
    cross = jnp.stack([token_losses(
        logits[t] if keep_logits else logits_of(h, w_head), targets)
        for t, h in enumerate(hidden)])
    p = exit_distribution(variables, hidden)
    entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(p), 0.0), axis=0)
    loss = jnp.mean(jnp.sum(p * cross, axis=0)) \
        - float(config["loop_exit_entropy"]) * jnp.mean(entropy)
    return {"logits": None if logits is None else jnp.stack(logits),
            "token_loss": cross, "p": p, "entropy": entropy, "loss": loss}


def forward(variables, tokens, config, stream_dtype=None):
    """The LAST pass's logits ``[b, s, vocab]`` (float32, a host array) for
    ``tokens [b, s]``, a sequence at a time so that the float32 scores and
    logits of one fit beside a train state."""
    w_head = common.param(variables, HEAD)[:, :, 0, :]
    return np.concatenate([np.asarray(logits_of(
        passes(variables, tokens[i:i + 1], config, stream_dtype)[-1], w_head))
        for i in range(len(tokens))])


def train_loss(variables, tokens, targets, config, pass_variables=None):
    """The scalar whose gradient the program's step applies: the gated sum of
    the passes' cross-entropies less ``loop_exit_entropy`` times the mean
    entropy of the exit distribution."""
    return outputs(variables, tokens, targets, config,
                   pass_variables=pass_variables, keep_logits=False)["loss"]
