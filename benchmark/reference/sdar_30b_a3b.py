"""Plain reference of SDAR-30B-A3B-Chat under BLOCK-DIFFUSION TRAINING as ONE
RANK of an expert-parallel group holds it: a Qwen3-MoE (pre-norm residual
stream, grouped-query attention with a head norm and rotary, a 128-way
softmax top-8 router over the experts held here, RMSNorm, untied head) run
ONCE over ``[noised sequence | clean sequence]`` under the block-diffusion
mask, read at the noised half.

Written from the published ``config.json`` (``model_type: sdar_moe``; the
keys are ``Qwen3MoeConfig``'s) and the equations of ISSUE 67 — block
diffusion language models' objective (BD3-LM, arXiv:2503.09573 section 3 and
its appendix on the training mask) as SDAR uses it (arXiv:2510.06303) — in
float32 ``jax.numpy`` under ``highest`` matmul precision: explicit einsums, a
Python loop over layers and over blocks of 512 queries against ALL ``2 L``
keys with the mask written out from its definition, no kernel, no scan.
Parameters are read by the names the program gives them — the seeded weights
have to be the same ones — and nothing else is taken from the program.  The
router, the experts, ``rms``, ``rope`` and the head are the ones of
``laguna_s_2_1.py`` / ``keye_vl_2_0_30b_a3b.py`` (the same softmax top-k
layer with a held share).

On a clean sequence ``x_0 .. x_(L-1)`` in blocks of ``B``, ``b(i) = i // B``:

    t_b   = t_min + (1 - t_min) u_b,  u ~ uniform(fold_in(key, 0), [batch, L / B])
    m_i   = uniform(fold_in(key, 1), [batch, L])_i < t_b(i)
    z_i   = MASK if m_i else x_i;     w_i = m_i / t_b(i)
    h     = E[z_0 .. z_(L-1) | x_0 .. x_(L-1)]       2 L positions
    pos   = (0 .. L-1 | 0 .. L-1)                    rotary by index mod L
    M[i, j], N = the noised half, C = the clean half:
            i in N, j in N:  b(i) == b(j)            the own block, both ways
            i in N, j in C:  b(j) <  b(i)            clean EARLIER blocks
            i in C, j in C:  b(j) <= b(i)            block-causal
            i in C, j in N:  never
    every layer:  a = rms(h) w1;  q, k, v = a Wq [32, 128], a Wk [4, 128],
                  a Wv [4, 128];  q, k <- rope(rms_128(q) wq), rope(rms_128(k)
                  wk);  h <- h + softmax(q k^T / sqrt(128) + M) v Wo
                  m = rms(h) w2;  p = softmax(m Wr) (128 logits); the 8
                  largest renormalised;  h <- h + sum_{e chosen, HELD HERE}
                  p_e expert_e(m)                    SwiGLU of 768
    logits_i = (rms(h_i) wf) Whead,  i in N          L positions
    loss  = (1 / (batch L)) sum_i w_i CE(logits_i, x_i)     the SAME
                                                     position's clean token

``rms(x) = x / sqrt(mean(x^2) + 1e-6)``.  What the experts held elsewhere
would have added is left out (``experts_first``, ``experts_held``; 0 held =
all, the uncut layer).  ``forward`` is the noised half's logits under the
noise of ``PRNGKey(0)``: what ``Model.apply`` returns without a key.
``train_loss`` adds the layers' router terms: the scalar the step
differentiates.

Assumed, where ``config.json`` has no key: see
``benchmark/configs/sdar_30b_a3b.json`` ``assumed``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import common
from .keye_vl_2_0_30b_a3b import NORM, SPARSE, _head, sparse_block
from .laguna_s_2_1 import _logits, default_inv_freq, rms, rope

QUERY_BLOCK = 512
LOGIT_BLOCK = 2048
ATTENTION = {
    "w_key": "attention_0/normal_var0", "w_query": "attention_0/normal_var1",
    "w_value": "attention_0/normal_var2", "q_scale": "attention_0/normal_var3",
    "k_scale": "attention_0/normal_var4", "w_out": "attention_0/normal_var5",
}


def mask_token(config) -> int:
    given = int(config.get("diffusion_mask_id", -1))
    return int(config["vocab_size"]) - 1 if given < 0 else given


def noise(key, tokens, config):
    """``(noised, weights)`` of ``tokens [batch, L]``: the docstring's draws."""
    block, t_min = int(config["diffusion_block"]), \
        float(config.get("diffusion_t_min", 1e-3))
    batch, length = tokens.shape
    t = t_min + (1.0 - t_min) * jax.random.uniform(
        jax.random.fold_in(key, 0), (batch, length // block), jnp.float32)
    t = jnp.repeat(t, block, axis=1)
    m = jax.random.uniform(jax.random.fold_in(key, 1), (batch, length),
                           jnp.float32) < t
    return jnp.where(m, mask_token(config), jnp.asarray(tokens)), m / t


def mask_rows(rows, length: int, block: int):
    """Rows ``rows`` (positions of the ``2 length`` stream) of ``M``, against
    all ``2 length`` keys, from the definition."""
    keys = np.arange(2 * length)
    q_clean, k_clean = (rows >= length)[:, None], (keys >= length)[None, :]
    q_blk, k_blk = ((rows % length) // block)[:, None], \
        ((keys % length) // block)[None, :]
    return (~q_clean & ~k_clean & (q_blk == k_blk)) \
        | (~q_clean & k_clean & (k_blk < q_blk)) \
        | (q_clean & k_clean & (k_blk <= q_blk))


def layer_spec(layer: str, config) -> dict:
    name, *flags = layer.split("-")
    assert name == "attention" and "block_diffusion" in flags \
        and "qk_norm_head" in flags and "rope" in flags, layer
    number = {f.rstrip("0123456789"): int(f[len(f.rstrip("0123456789")):])
              for f in flags if f[-1].isdigit()}
    return {"heads": number["q_heads"], "kv_heads": number["kv_heads"],
            "theta": float(number.get("theta", config["rope_theta"]))}


def layers_of(variables, config):
    """``(kind, parameters, spec)`` of every block in execution order."""
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
                (layer_spec(sub, config) if attention else None)


def _project(p, h, theta, eps):
    """``(q, k, v)`` of the block's input ``h [b, 2 L, g, f]``, each half
    turned by its own index."""
    a = rms(h, p["w_norm"], eps)
    q = rms(jnp.einsum("bsgf,gfhd->bshd", a, p["w_query"]), p["q_scale"], eps)
    k = rms(jnp.einsum("bsgf,gfhd->bshd", a, p["w_key"]), p["k_scale"], eps)
    v = jnp.einsum("bsgf,gfhd->bshd", a, p["w_value"])
    inv_freq = default_inv_freq(theta, q.shape[-1])
    length = h.shape[1] // 2

    def turn(x):
        return jnp.concatenate([rope(x[:, :length], inv_freq, 1.0),
                                rope(x[:, length:], inv_freq, 1.0)], axis=1)

    return turn(q), turn(k), v


def _attend(q, k, v, mask):
    """One block of queries against all keys under ``mask [n, 2 L]``."""
    b, n, heads, d = q.shape
    kv_heads = k.shape[2]
    qg = q.reshape(b, n, kv_heads, heads // kv_heads, d)
    score = jnp.einsum("bnkgd,bskd->bkgns", qg, k) / jnp.sqrt(jnp.float32(d))
    prob = jax.nn.softmax(jnp.where(mask[None, None, None], score, -jnp.inf),
                          axis=-1)
    return jnp.einsum("bkgns,bskd->bnkgd", prob, v).reshape(b, n, heads, d)


project = jax.jit(common.highest(_project), static_argnums=(2, 3))
attend = jax.jit(common.highest(_attend))


@jax.jit
@common.highest
def _out(o, w_out):
    return jnp.einsum("bshd,hdgf->bsgf", o, w_out)


def attention_block(p, h, spec, config):
    """What the layer adds to the stream ``h [b, 2 L, g, f]``."""
    q, k, v = project(p, h, spec["theta"], float(config["norm_epsilon"]))
    length, block = h.shape[1] // 2, int(config["diffusion_block"])
    out = []
    for start in range(0, 2 * length, QUERY_BLOCK):
        rows = np.arange(start, min(start + QUERY_BLOCK, 2 * length))
        out.append(attend(q[:, rows[0]:rows[-1] + 1], k, v,
                          jnp.asarray(mask_rows(rows, length, block))))
    return _out(jnp.concatenate(out, axis=1), p["w_out"])


def hidden(variables, tokens, config, key, stream_dtype=None, losses=None):
    """``(the residual stream after the last block [b, 2 L, heads, width],
    the weights [b, L])`` under the noise of ``key``; ``losses``: a list that
    takes the sparse layers' router terms."""
    def stream(x):
        # the control of benchmark/precision_control.py: the stream rounded
        # to a lower precision after every block
        return x if stream_dtype is None \
            else x.astype(stream_dtype).astype(jnp.float32)

    noised, weights = noise(key, jnp.asarray(tokens), config)
    both = jnp.concatenate([noised, jnp.asarray(tokens)], axis=1)
    h = stream(common.param(variables,
                            "input0/gather0/embed0/normal_var0")[both])
    for kind, p, spec in layers_of(variables, config):
        if kind == "attention":
            out = attention_block(p, h, spec, config)
        else:
            out, router = sparse_block(p, h, config)
            if losses is not None:
                losses.append(router)
        h = stream(h + out)
    return h, weights


def forward(variables, tokens, config, stream_dtype=None, key=None):
    """The noised half's logits ``[b, L, vocab]`` (float32) for ``tokens [b,
    L]`` under the noise of ``key`` (None: ``PRNGKey(0)``, ``Model.apply``'s
    without a key), made in blocks of ``LOGIT_BLOCK`` positions and handed
    over as a host array.  ``stream_dtype``: the control's lower-precision
    residual stream."""
    key = jax.random.PRNGKey(0) if key is None else key
    h, _ = hidden(variables, tokens, config, key, stream_dtype)
    scale, w_head = _head(variables)
    eps, length = float(config["norm_epsilon"]), h.shape[1] // 2
    return np.concatenate([
        np.asarray(_logits(h[:, i:min(i + LOGIT_BLOCK, length)], scale,
                           w_head, eps))
        for i in range(0, length, LOGIT_BLOCK)], axis=1)


def _weighted(logits, targets, weights, z_loss):
    log_z = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    token = log_z - picked + z_loss * jnp.square(log_z)
    return jnp.sum(weights * token) / targets.size


def loss(variables, tokens, config, key, losses=None):
    """``(1 / (batch L)) sum_i w_i CE(logits_i, x_i)`` under the noise of
    ``key``, differentiable; the head in blocks of ``LOGIT_BLOCK``."""
    h, weights = hidden(variables, tokens, config, key, losses=losses)
    scale, w_head = _head(variables)
    eps, length = float(config["norm_epsilon"]), h.shape[1] // 2
    tokens = jnp.asarray(tokens)
    total = 0.0
    for i in range(0, length, LOGIT_BLOCK):
        cut = slice(i, min(i + LOGIT_BLOCK, length))
        total = total + _weighted(
            _logits(h[:, cut], scale, w_head, eps), tokens[:, cut],
            weights[:, cut], float(config["z_loss"])) \
            * tokens[:, cut].size / tokens.size
    return total


def train_loss(variables, tokens, targets, config, key=None):
    """The diffusion loss plus the sparse layers' router terms: the scalar
    whose gradient the program's step applies.  ``targets`` (the batch's
    ``token_y``) is not read: the target is the same position's clean
    token."""
    del targets
    losses: list = []
    key = jax.random.PRNGKey(0) if key is None else key
    return loss(variables, tokens, config, key, losses) + sum(losses)
