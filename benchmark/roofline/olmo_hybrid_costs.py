"""Required operations and bytes of the Olmo-Hybrid-7B configuration, computed
from shapes.

From the layer equations in ``benchmark/reference/olmo_hybrid_7b.py``.  Per
gated delta-rule layer: the in-projection ``d x (2 H d_k + 2 H d_v + 2 H)``,
the rule, the out-projection ``H d_v x d``; per attention layer: four ``d x
d`` projections, causal scores and weighted values; after every mixer a gated
MLP of three ``d x i`` matmuls; then the head over the vocabulary held here.
"Required" is what the mathematics needs in the form the configuration
states — the chunked rule at ``delta_chunk`` positions a chunk, lower
triangles inside a chunk, the triangular system by substitution, the lower
triangle of the attention scores — and nothing recomputed.  Norms, the conv's
four multiplies, the L2 normalisation, softplus, the gate and the decays are
not matmuls.
"""
from __future__ import annotations

from . import costs


def _mixers(config: dict):
    """The kind of each layer's mixer in one depth unit: ``block_config``
    alternates a mixer's block and its MLP's, the sublayer first."""
    return [block["layer"][0].split("-")[0]
            for block in config["block_config"][0::2]]


def _chunk(config: dict) -> int:
    return min(config["delta_chunk"], config["sequence_length"])


def rule_flops_per_token(config: dict, executed: bool = False) -> float:
    """The chunked rule's matmuls for one token of one layer, all heads.
    Inside the chunk, over the keys a position meets — ``(chunk - 1) / 2``
    before it for ``K K^T``, ``(chunk + 1) / 2`` up to it for ``Q K^T``, ``T
    K``, ``T V`` and the weighted ``V'`` required, the whole ``chunk``
    executed by dense masked matmuls —; the unit lower triangular inverse,
    ``chunk^3 / 3`` a chunk by substitution required, twelve dense ``chunk^3``
    matmuls (``2 log2(chunk)``) executed by the doubling blocks; and three
    products with the ``d_v x d_k`` state: ``W S^T``, ``Q S^T`` and the
    state's own update."""
    h, dk, dv = (config["delta_heads"], config["delta_key_features"],
                 config["delta_value_features"])
    c = _chunk(config)
    before, upto = (c, c) if executed else ((c - 1) / 2, (c + 1) / 2)
    levels = max(0, c - 1).bit_length()
    solve = 2 * levels * 2 * c * c if executed else c * c / 3
    return h * (2 * dk * before + 2 * dk * upto       # K K^T, Q K^T
                + solve
                + 2 * dk * upto + 2 * 2 * dv * upto   # T K; T V, (QK) V'
                + 3 * 2 * dk * dv)


def forward_flops_per_token(config: dict, executed: bool = False) -> float:
    """Matmul FLOPs of one token's forward pass.  ``executed``: what the
    dense masked forms run instead (whole chunks, the doubling solve, the
    whole square of attention scores)."""
    d = config["heads"] * config["features_per_head"]
    i = int(d * config["intermediate_feed_forward_multiplier"])
    h, dk, dv = (config["delta_heads"], config["delta_key_features"],
                 config["delta_value_features"])
    keys = costs._mixing_keys(config["sequence_length"],
                              "square" if executed else "causal")
    delta = 2 * d * (2 * h * dk + 2 * h * dv + 2 * h) \
        + rule_flops_per_token(config, executed) + 2 * h * dv * d
    attention = 4 * 2 * d * d + 2 * 2 * d * keys
    mixers = sum(delta if kind == "gated_delta" else attention
                 for kind in _mixers(config))
    return config["depth"] * (mixers + len(_mixers(config)) * 3 * 2 * d * i) \
        + 2 * d * config["vocab_size"]


def train_flops_per_token(config: dict) -> float:
    """Forward + backward = 3 x forward; recomputation is not credited."""
    return 3.0 * forward_flops_per_token(config)


def delta_layers(config: dict) -> int:
    return config["depth"] * sum(k == "gated_delta" for k in _mixers(config))


def rule_cost(config: dict, width: int = 2):
    """``(flops, bytes)`` ONE layer's rule needs in one train step on one
    chip, forward and backward.  Operations: the forward's matmuls and twice
    that for their gradients.  Bytes, ``width`` an element and 4 for ``beta``
    and ``g``: the forward reads ``q``, ``k``, ``v``, ``beta``, ``g`` and
    writes ``o``; the backward reads those five and ``do`` and writes ``dq``,
    ``dk``, ``dv``, ``dbeta``, ``dg``.  No decay matrix, no solved transform,
    no chunk state and nothing recomputed is credited: a fused kernel keeps
    them on the chip."""
    tokens = config["train_batch_size"] * config["sequence_length"]
    h, dk, dv = (config["delta_heads"], config["delta_key_features"],
                 config["delta_value_features"])
    flops = 3 * rule_flops_per_token(config) * tokens
    forward = (2 * h * dk + 2 * h * dv) * width + 2 * h * 4
    backward = (4 * h * dk + 3 * h * dv) * width + 4 * h * 4
    return flops, (forward + backward) * tokens
