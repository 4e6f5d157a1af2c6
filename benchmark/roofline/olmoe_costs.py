"""Required operations of the OLMoE configuration, computed from shapes.

From the layer equations in ``benchmark/reference/olmoe_1b_7b.py``: per
layer four ``d x d`` projections, causal scores and weighted values, the
router, and three ``d x i`` matmuls in each of the ``k`` experts a token is
routed to; then the head.  "Required" is what the mathematics needs: the
lower triangle of the scores, ``k`` experts a token, nothing recomputed.
Norms, rotary embedding, softmax and the gate are not matmuls.
"""
from __future__ import annotations

from . import costs


def forward_flops_per_token(config: dict, executed: bool = False) -> float:
    """Matmul FLOPs of one token's forward pass through its ACTIVE
    parameters.  ``executed``: what the plain reference runs instead — the
    whole square of scores and EVERY expert on every token — used only to
    check this enumeration against a jaxpr count of that reference."""
    d = config["heads"] * config["features_per_head"]
    i = int(d * config["intermediate_feed_forward_multiplier"])
    keys = costs._mixing_keys(config["sequence_length"],
                              "square" if executed else "causal")
    experts = config["experts"] if executed else config["moe_top_k"]
    attention = 4 * 2 * d * d + 2 * 2 * d * keys
    routed = 2 * d * config["experts"] + experts * 3 * 2 * d * i
    return config["depth"] * (attention + routed) \
        + 2 * d * config["vocab_size"]


def train_flops_per_token(config: dict) -> float:
    """Forward + backward = 3 x forward; recomputation is not credited."""
    return 3.0 * forward_flops_per_token(config)
