"""Plain reference of ``32big_mixer``: group-linear block + learned-map mixer.

One depth = two blocks on the reversible stream.  The mixer block replaces
attention by two learned causal maps ``B[h, s, t]`` (one per mixing site,
shared by every depth): ``out[b, s, h, k] = sum_{t <= s} B[h, s, t] *
v[b, t, h, k]``, with the block's input as the value.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common

MIXER = {
    "scale0": "norm_0/normal_var0", "shift0": "norm_0/normal_var1",
    "scale1": "norm_1/normal_var0", "shift1": "norm_1/normal_var1",
}
#: the two maps live under depth 0 and are shared by all depths
MAPS = {"map0": "attention_0/embed0/normal_var0",
        "map1": "attention_1/embed0/normal_var0"}


def _mix(bias, v):
    s = v.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bias.dtype))
    return jnp.einsum("hst,bthk->bshk", bias[:, :s, :s] * causal, v)


@jax.jit
@common.highest
def mixer_block(p, maps, x):
    y = common.group_norm(x, p["scale0"], p["shift0"])
    y = _mix(maps["map0"], y)
    y = common.group_norm(y, p["scale1"], p["shift1"])
    return _mix(maps["map1"], common.gelu(y))


def forward(variables, tokens, config):
    """Logits ``[b, s, vocab]`` (float32) for ``tokens [b, s]``; ``s`` may
    be shorter than the configured sequence (the maps are cut to it)."""
    maps = common.block_params(variables, 0, 1, MAPS)
    blocks = []
    for d in range(config["depth"]):
        gl = common.block_params(variables, d, 0, common.GROUP_LINEAR)
        mx = common.block_params(variables, d, 1, MIXER)
        blocks.append(lambda x, p=gl: common.group_linear_block(p, x))
        blocks.append(lambda x, p=mx: mixer_block(p, maps, x))
    out = common.reversible_stack(common.embed(variables, tokens), blocks)
    return common.logits_of(variables, out)
