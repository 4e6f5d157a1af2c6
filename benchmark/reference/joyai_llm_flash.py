"""Plain reference of JoyAI-LLM-Flash (``joyai_llm_flash``; the published
keys are DeepSeek-V3's) as ONE RANK of an expert-parallel group holds it: a
pre-norm residual stream of latent-attention layers with a query latent and a
rotary key part shared by the heads; layer 0 a dense SwiGLU MLP, every later
layer a 256-way top-8 sigmoid router with a selection bias over the experts
held here beside one shared expert; RMSNorm (eps 1e-6), an untied head; and
one multi-token-prediction module that shares the embedding and the head.

Written from the published ``config.json``, the DeepSeek-V3 report
(arXiv:2412.19437 sections 2.1.1, 2.1.2, 2.2) and the layer equations of
ISSUE 65 in float32 ``jax.numpy`` under ``highest`` matmul precision.  No
kernel, no scan and nothing of ``homebrewnlp_tpu``: attention is explicit
einsums one block of queries against all keys, rotary is HF's rotate-half
(``laguna_s_2_1.rope``).  The sparse layer, the dense MLP, the block of
queries and the logits are the Kimi-Linear reference's (the same layers:
``kimi_linear_48b_a3b.sparse_block`` runs every held expert on every token,
weighted by the token's weight for it, the top-k a stable ranking).
Parameters are read by the names the program gives them — the seeded weights
have to be the same ones.

With ``h [b, s, 2048]`` the stream, every block ``h <- h + f(rms(h) w)``:

Latent attention on ``u``, 32 heads, ``d`` 128, ``r`` 64:

    c_q = rms(u W_qa) w_q              [s, 1536]
    q = c_q W_qb                       a head's q = [q_n (128) | q_r (64)]
    c | k_r = u W_kva                  c [s, 512]; k_r [s, 64], ONE for all heads
    k_n | v = rms(c) w_c W_kvb         [s, 32, 128 + 128]
    q_r, k_r = rotary(q_r), rotary(k_r)     theta 32,000,000, pairs (i, i + 32),
                                            position = index
    k = [k_n | k_r];  o = causal softmax(192^-1/2 q k^T) v;  out = o W_o

Layer 0's MLP is SwiGLU at 7,168; the sparse layer is Kimi-Linear's at 768
and x 2.5 (its docstring has the equations and ``bias_update``).

The module, on the main stack's output after its final norm ``h``, the
embedding ``E`` and the head ``W_head`` (both the main model's):

    x_i = [rms(E[t_(i+1)]) w_e | rms(h_i) w_h] W_eh       4096 x 2048
    g = one more block pair (latent attention, sparse layer), own weights
    p_i = rms(g_i) w_o W_head                             predicts t_(i+2)
    L_mtp = mean over i = 0 .. T - 2 of CE(p_i, t_(i+2))

``train_loss`` is ``L_main + mtp_loss_weight x L_mtp`` plus every sparse
layer's balance term (the module's too): the scalar whose gradient the
program's step applies (the selection bias has none).

Departures from the published description, each the program's too (and in
``benchmark/configs/joyai_llm_flash.json`` under ``assumed`` /
``deployment``): one rank's share — ``experts_held`` experts from
``experts_first``, a slice of both tables; the absent experts add nothing;
the rotary pairing; the module's joined order and its input after the final
norm.
"""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np

from . import common
from .kimi_linear_48b_a3b import (DENSE, LOGIT_BLOCK, NORM, QUERY_BLOCK,
                                  SPARSE, _attend, _logits, _project_out,
                                  dense_block, rms, sparse_block)
from .laguna_s_2_1 import default_inv_freq, rope

ATTENTION = {
    "w_q_down": "attention_0/normal_var0", "w_q_norm": "attention_0/normal_var1",
    "w_q_up": "attention_0/normal_var2", "w_down": "attention_0/normal_var3",
    "w_latent_norm": "attention_0/normal_var4",
    "w_up": "attention_0/normal_var5", "w_out": "attention_0/normal_var6",
}
KINDS = {"attention": ATTENTION, "mlp": DENSE, "moe": SPARSE}
MODULE = {"w_embedding_norm": "mtp0/norm_0/normal_var0",
          "w_stream_norm": "mtp0/norm_1/normal_var0",
          "w_join": "mtp0/normal_var0",
          "w_output_norm": "mtp0/output0/norm_0/normal_var0"}
EMBEDDING = "input0/gather0/embed0/normal_var0"


def layer_spec(layer: str) -> dict:
    """The numbers in a latent attention layer's string."""
    name, *flags = layer.split("-")
    assert name == "attention" and "rope" in flags, layer
    spec = {m.group(1): int(m.group(2)) for m in (
        re.fullmatch(r"([a-z_]+)(\d+)", flag) for flag in flags) if m}
    assert set(spec) == {"theta", "q_heads", "kv_heads", "kv_latent",
                         "shared_key", "q_latent"}, layer
    return spec


# ---- latent attention ---------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(2, 3, 4))
@common.highest
def _latent_qkv(p, h, theta: float, rotary: int, eps: float):
    u = rms(h, p["w_norm_in"], eps)
    c_q = rms(jnp.einsum("bsgf,gfc->bsc", u, p["w_q_down"]), p["w_q_norm"],
              eps)
    q = jnp.einsum("bsc,chd->bshd", c_q, p["w_q_up"])
    down = jnp.einsum("bsgf,gfc->bsc", u, p["w_down"])
    latent = p["w_latent_norm"].shape[0]
    up = jnp.einsum("bsc,chd->bshd",
                    rms(down[..., :latent], p["w_latent_norm"], eps),
                    p["w_up"])
    width = up.shape[-1] // 2
    inv_freq = default_inv_freq(theta, rotary)
    plain = q.shape[-1] - rotary
    q = jnp.concatenate([q[..., :plain], rope(q[..., plain:], inv_freq, 1.0)],
                        axis=-1)
    shared = rope(down[:, :, None, latent:], inv_freq, 1.0)
    k = jnp.concatenate([up[..., :width], jnp.broadcast_to(
        shared, up.shape[:3] + (rotary,))], axis=-1)
    return q, k, up[..., width:]


def attention_block(p, h, spec: dict, eps: float):
    q, k, v = _latent_qkv(p, h, float(spec["theta"]), spec["shared_key"], eps)
    s = q.shape[1]
    block = min(s, QUERY_BLOCK)
    o = jnp.concatenate([_attend(q[:, i:i + block], k, v, i)
                         for i in range(0, s, block)], axis=1)
    return _project_out(o, p["w_out"])


# ---- the model ----------------------------------------------------------------

def _blocks(variables, cfgs, scope_of):
    """``(kind, parameters, the attention's numbers)`` of blocks ``[norm,
    layer]`` whose scopes ``scope_of(index)`` names."""
    for i, block in enumerate(cfgs):
        norm, layer = block["layer"]
        assert norm == "norm-rms-scale" and block["skip"], block
        kind = layer.split("-")[0]
        yield kind, {k: common.param(variables, f"{scope_of(i)}/{path}")
                     for k, path in {**KINDS[kind],
                                     "w_norm_in": NORM}.items()}, \
            layer_spec(layer) if kind == "attention" else None


def layers_of(variables, config):
    """The main model's blocks in execution order: the leading ones
    (``input_block_config``: layer 0), then ``depth`` times ``block_config``."""
    yield from _blocks(variables, config["input_block_config"],
                       lambda i: f"input0/lang_inp{i}_0")
    for d in range(int(config["depth"])):
        yield from _blocks(variables, config["block_config"],
                           lambda i, d=d: f"body0/block{d}_{i}_0")


def _run(blocks, h, config, stream_dtype=None, counts=None, terms=None):
    """``h`` through ``blocks``; ``counts`` / ``terms`` take each sparse
    layer's pair counts and balance term."""
    eps = float(config["norm_epsilon"])

    def stream(x):
        # the control of benchmark/precision_control.py: the stream rounded
        # to a lower precision after every block
        return x if stream_dtype is None \
            else x.astype(stream_dtype).astype(jnp.float32)

    h = stream(h)
    for kind, p, spec in blocks:
        if kind == "attention":
            h = h + attention_block(p, h, spec, eps)
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


def hidden(variables, tokens, config, stream_dtype=None, counts=None,
           terms=None):
    """The residual stream after the last block of the main model, ``[b, s,
    heads, width]``."""
    return _run(layers_of(variables, config),
                common.param(variables, EMBEDDING)[tokens], config,
                stream_dtype, counts, terms)


def _head(variables):
    return (common.param(variables, "output0/lang_out0_0/norm_0/normal_var0"),
            common.param(variables, "output0/embed0/normal_var0")[:, :, 0, :])


def _in_blocks(h, scale, w_head, eps: float):
    """The head on ``h`` in blocks of ``LOGIT_BLOCK`` positions, a host
    array: the logits fit beside the train state."""
    return np.concatenate([
        np.asarray(_logits(h[:, i:i + LOGIT_BLOCK], scale, w_head, eps))
        for i in range(0, h.shape[1], LOGIT_BLOCK)], axis=1)


def forward(variables, tokens, config, stream_dtype=None):
    """The main model's logits ``[b, s, vocab]`` (float32) for ``tokens [b,
    s]``.  ``stream_dtype``: the control's lower-precision residual stream."""
    h = hidden(variables, tokens, config, stream_dtype)
    scale, w_head = _head(variables)
    return _in_blocks(h, scale, w_head, float(config["norm_epsilon"]))


@functools.partial(jax.jit, static_argnums=(4,))
@common.highest
def _join(p, table_rows, h, scale, eps: float):
    """``[rms(E[next]) w_e | rms(rms(h) w_final) w_h] W_eh``."""
    pair = jnp.stack([rms(table_rows, p["w_embedding_norm"], eps),
                      rms(rms(h, scale, eps), p["w_stream_norm"], eps)],
                     axis=2)
    return jnp.einsum("bspgf,pgfhk->bshk", pair, p["w_join"])


def streams(variables, tokens, next_tokens, config, stream_dtype=None,
            counts=None, terms=None):
    """``(the main stream after its last block, the module's before its own
    last norm, that norm's scale)``: the module reads the main stack's
    output after the final norm, joined to ``E[next_tokens]``, and runs its
    own blocks."""
    assert int(config["mtp_depth"]) == 1, "one module, as published"
    h = hidden(variables, tokens, config, stream_dtype, counts, terms)
    p = {k: common.param(variables, path) for k, path in MODULE.items()}
    joined = _join(p, common.param(variables, EMBEDDING)[next_tokens], h,
                   _head(variables)[0], float(config["norm_epsilon"]))
    blocks = _blocks(variables, config["mtp_block_config"],
                     lambda i: f"mtp0/body0/block0_{i}_0")
    return h, _run(blocks, joined, config, stream_dtype, counts, terms), \
        p["w_output_norm"]


def mtp_forward(variables, tokens, next_tokens, config, stream_dtype=None):
    """The module's logits ``[b, s, vocab]`` (float32): position ``i`` reads
    ``tokens[.. i]`` and ``next_tokens[.. i]`` (the token one on) and
    predicts the token two on."""
    _, g, scale = streams(variables, tokens, next_tokens, config,
                          stream_dtype)
    return _in_blocks(g, scale, _head(variables)[1],
                      float(config["norm_epsilon"]))


def pair_counts(variables, tokens, next_tokens, config):
    """The pair counts ``[experts]`` of every sparse layer, in order: the
    main model's, then the module's."""
    counts: list = []
    streams(variables, tokens, next_tokens, config, counts=counts)
    return counts


def mtp_loss_of(logits, targets, z_loss: float):
    """``L_mtp``: position ``i`` of the module's ``logits`` is held to
    ``targets[i + 1]`` (``targets`` = the batch's ``token_y``), over the
    positions that have one."""
    return common.loss_of(logits[:, :-1], targets[:, 1:], z_loss)


def train_loss(variables, tokens, targets, config):
    """``L_main + mtp_loss_weight x L_mtp`` (+ the configuration's output
    z-loss in each) plus the sparse layers' balance terms.  Differentiable."""
    terms: list = []
    eps = float(config["norm_epsilon"])
    scale, w_head = _head(variables)
    h, g, module_scale = streams(variables, tokens, targets, config,
                                 terms=terms)
    main = _logits(h, scale, w_head, eps)
    module = _logits(g, module_scale, w_head, eps)
    return common.loss_of(main, targets, config["z_loss"]) \
        + float(config["mtp_loss_weight"]) * mtp_loss_of(
            module, targets, config["z_loss"]) + sum(terms)
