"""Required operations of the Ouro-2.6B configuration (a looped model),
computed from shapes.

From the layer equations in ``benchmark/reference/ouro_2_6b.py``.  A layer
APPLICATION is the four attention projections ``d x d``, causal scores and
weighted values over the keys a query meets (the lower triangle: ``(s + 1) /
2`` on average) and the MLP's three ``d x i`` matmuls; a pass applies every
layer once and the head (``d x vocab``) once; a step makes ``loop_steps``
passes over the same weights.  "Required" is what the mathematics needs —
every pass counts, since each is part of the model's forward, and nothing a
checkpoint region replays does.  Norms, the rotation, the gate (a ``d``-long
dot a token a pass) and the softmax are not matmuls.
"""
from __future__ import annotations

from . import costs


def layer_flops_per_token(config: dict, executed: bool = False) -> float:
    """Matmul FLOPs of ONE application of one layer to one token.
    ``executed``: what a dense masked form runs instead (the whole square of
    scores)."""
    d = config["heads"] * config["features_per_head"]
    i = int(d * config["intermediate_feed_forward_multiplier"])
    keys = costs._mixing_keys(config["sequence_length"],
                              "square" if executed else "causal")
    return 2 * 4 * d * d + 2 * 2 * d * keys + 2 * 3 * d * i


def head_flops_per_token(config: dict) -> float:
    """One pass's head matmul for one token."""
    return 2 * config["heads"] * config["features_per_head"] \
        * config["vocab_size"]


def forward_flops_per_token(config: dict, executed: bool = False) -> float:
    """Matmul FLOPs of one token's forward: ``loop_steps`` passes of
    ``depth`` layers and a head each."""
    return config["loop_steps"] * (
        config["depth"] * layer_flops_per_token(config, executed)
        + head_flops_per_token(config))


def train_flops_per_token(config: dict) -> float:
    """Forward plus backward (twice the forward's matmuls); a replayed
    forward is not credited."""
    return 3 * forward_flops_per_token(config)
