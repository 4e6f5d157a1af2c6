"""Required operations and bytes of the granite-4.0-h-micro configuration,
computed from shapes.

From the layer equations in ``benchmark/reference/granite_4_0_h_micro.py``.
Per Mamba-2 layer: the in-projection ``d x (2 d_inner + 2 n + heads)``, the
scan, the out-projection ``d_inner x d``; per attention layer: the query and
output projections ``d x d``, key and value ``d x d / query_group``, causal
scores and weighted values; after every mixer a gated MLP of three ``d x i``
matmuls; then the tied head.  "Required" is what the mathematics needs in
the form the configuration states — the chunked scan at ``mamba_chunk``
positions a chunk, the lower triangle inside a chunk, the lower triangle of
the attention scores — and nothing recomputed.  Norms, the conv's four
multiplies, softplus, the gate and the decays are not matmuls.
"""
from __future__ import annotations

from . import costs


def _mixers(config: dict):
    """The kind of each layer's mixer in one depth unit: ``block_config``
    alternates a mixer's block and its MLP's."""
    return [block["layer"][-1].split("-")[0]
            for block in config["block_config"][0::2]]


def _inner(config: dict) -> int:
    return config["mamba_heads"] * config["mamba_head_features"]


def _chunk(config: dict) -> int:
    return min(config["mamba_chunk"], config["sequence_length"])


def scan_flops_per_token(config: dict, executed: bool = False) -> float:
    """The chunked scan's matmuls for one token of one layer: inside the
    chunk ``C B^T`` (``n`` deep) and its product with ``x`` (all ``d_inner``
    columns) over the keys of the chunk a query meets — ``(chunk + 1) / 2``
    required, the whole ``chunk`` executed by a dense masked matmul —, the
    chunk's state ``x B^T`` and the entering state's part ``S C``, ``d_inner
    x n`` each."""
    di, n = _inner(config), config["mamba_state"]
    keys = _chunk(config) if executed else (_chunk(config) + 1) / 2
    return 2 * n * keys + 2 * di * keys + 2 * 2 * di * n


def forward_flops_per_token(config: dict, executed: bool = False) -> float:
    """Matmul FLOPs of one token's forward pass.  ``executed``: what a
    dense masked form runs instead (whole chunks, the whole square of
    attention scores)."""
    d = config["heads"] * config["features_per_head"]
    i = int(d * config["intermediate_feed_forward_multiplier"])
    di, n = _inner(config), config["mamba_state"]
    keys = costs._mixing_keys(config["sequence_length"],
                              "square" if executed else "causal")
    mamba = 2 * d * (2 * di + 2 * n + config["mamba_heads"]) \
        + scan_flops_per_token(config, executed) + 2 * di * d
    attention = 2 * 2 * d * d + 2 * 2 * d * d // config["query_group"] \
        + 2 * 2 * d * keys
    mixers = sum(mamba if kind == "mamba" else attention
                 for kind in _mixers(config))
    return config["depth"] * (mixers + len(_mixers(config)) * 3 * 2 * d * i) \
        + 2 * d * config["vocab_size"]


def train_flops_per_token(config: dict) -> float:
    """Forward + backward = 3 x forward; recomputation is not credited."""
    return 3.0 * forward_flops_per_token(config)


def mamba_layers(config: dict) -> int:
    return config["depth"] * sum(k == "mamba" for k in _mixers(config))


def scan_cost(config: dict, width: int = 2):
    """``(flops, bytes)`` ONE layer's scan needs in one train step on one
    chip, forward and backward.  Operations: the forward's matmuls and twice
    that for their gradients.  Bytes, ``width`` an element and 4 for ``dt``:
    the forward reads ``x``, ``B``, ``C``, ``dt`` and writes ``y``; the
    backward reads those four and ``dy`` and writes ``dx``, ``dB``, ``dC``,
    ``ddt``; the per-head ``A`` and ``D`` are nothing beside them.  No
    decay matrix, no chunk state and nothing recomputed is credited: a fused
    kernel keeps them on the chip."""
    tokens = config["train_batch_size"] * config["sequence_length"]
    di, n, h = _inner(config), config["mamba_state"], config["mamba_heads"]
    flops = 3 * scan_flops_per_token(config) * tokens
    forward = (2 * di + 2 * n) * width + h * 4
    backward = (3 * di + 4 * n) * width + 2 * h * 4
    return flops, (forward + backward) * tokens
