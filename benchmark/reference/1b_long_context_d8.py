"""Plain reference of ``1b_long_context``: group-linear block + causal
dot-product attention, scores computed one block of queries at a time.

The attention block projects the normed input to the bottleneck, applies a
relu, and takes key, query and value as three projections of that
bottleneck back to all heads.  Scores are scaled by ``sequence ** -0.5``
(the published model's choice, not ``k ** -0.5``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common

ATTENTION = {
    "scale": "norm_0/normal_var0", "shift": "norm_0/normal_var1",
    "w_in": "attention_0/orthogonal_var0",
    "w_key": "attention_0/orthogonal_var1",
    "w_query": "attention_0/orthogonal_var2",
    "w_value": "attention_0/orthogonal_var3",
}
QUERY_BLOCK = 512


def _causal_attention(q, k, v):
    s = q.shape[1]
    outs = []
    for start in range(0, s, QUERY_BLOCK):
        stop = min(start + QUERY_BLOCK, s)
        score = jnp.einsum("bshk,bthk->bhst", q[:, start:stop], k[:, :stop])
        rows = jnp.arange(start, stop)[:, None]
        score = jnp.where(rows >= jnp.arange(stop)[None, :], score, -jnp.inf)
        outs.append(jnp.einsum("bhst,bthk->bshk",
                               jax.nn.softmax(score, axis=-1), v[:, :stop]))
    return jnp.concatenate(outs, axis=1)


def _attention_block(p, x, sequence_length):
    y = common.group_norm(x, p["scale"], p["shift"])
    a = jax.nn.relu(jnp.einsum("bshk,hki->bsi", y, p["w_in"]))
    key = jnp.einsum("bsi,ihk->bshk", a, p["w_key"])
    query = jnp.einsum("bsi,ihk->bshk", a, p["w_query"]) \
        * sequence_length ** -0.5
    value = jnp.einsum("bsi,ihk->bshk", a, p["w_value"])
    return _causal_attention(query, key, value)


attention_block = jax.jit(common.highest(_attention_block), static_argnums=2)


def forward(variables, tokens, config):
    """Logits ``[b, s, vocab]`` (float32) for ``tokens [b, s]``."""
    seq = config["sequence_length"]
    blocks = []
    for d in range(config["depth"]):
        gl = common.block_params(variables, d, 0, common.GROUP_LINEAR)
        at = common.block_params(variables, d, 1, ATTENTION)
        blocks.append(lambda x, p=gl: common.group_linear_block(p, x))
        blocks.append(lambda x, p=at: attention_block(p, x, seq))
    out = common.reversible_stack(common.embed(variables, tokens), blocks)
    return common.logits_of(variables, out)
