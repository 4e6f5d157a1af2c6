"""Required operations and bytes of the SDAR-30B-A3B configuration under
block-diffusion training as one expert-parallel rank holds it, computed from
shapes.

From the layer equations in ``benchmark/reference/sdar_30b_a3b.py``.  A step
trains ``L = sequence_length`` tokens a sequence and runs the body once over
``2 L`` positions (the noised sequence beside the clean one), so a TRAINED
token costs: every layer's projections (query and output ``d x H k``, key and
value ``d x G k``) TWICE, the attention's scores and weighted values over the
LIVE pairs of the block-diffusion mask (``L (L + B)`` a head and sequence: two
block-causal triangles and the noised blocks' own pairs; ``L + B`` a trained
token), the router over ALL routed experts and the routed experts HELD HERE
(of a token's ``top_k`` choices the share ``held / experts`` lands on this
rank when the router is balanced) TWICE, and the head over this rank's rows
of the vocabulary ONCE (the noised half alone).  "Required" is what the
mathematics needs, whatever kernel does it, and nothing recomputed or masked;
norms, rotary positions and the softmax are not matmuls.  The last layer's
clean-half queries feed nothing; they are counted like the others (the
program runs them: one call a layer over both halves).
"""
from __future__ import annotations

import re
import typing


def attention_layers(config: dict) -> typing.List[dict]:
    """``{"q_heads", "kv_heads"}`` of each attention layer of the step, in
    execution order."""
    out = []
    for block in config["block_config"]:
        for layer in block["layer"]:
            name, *flags = layer.split("-")
            if name != "attention":
                continue
            if "block_diffusion" not in flags:
                raise KeyError(f"no cost function for layer {layer!r}")
            counts = {m.group(1): int(m.group(2)) for m in (
                re.fullmatch(r"(q_heads|kv_heads)(\d+)", f) for f in flags)
                if m}
            out.append({"q_heads": counts.get("q_heads", config["heads"]),
                        "kv_heads": counts.get("kv_heads", config["heads"])})
    return out * config["depth"]


def sparse_layers(config: dict) -> int:
    return config["depth"] * sum(
        layer.split("-")[0] == "moe" for block in config["block_config"]
        for layer in block["layer"])


def live_pairs(config: dict) -> int:
    """(query, key) pairs the mask lets through, a head and sequence:
    clean to clean ``L (L + B) / 2``, noised to clean ``L (L - B) / 2``,
    noised to its own block ``L B``."""
    s, block = config["sequence_length"], config["diffusion_block"]
    return s * s + s * block


def executed_pairs(config: dict) -> int:
    """What the plain reference scores: the whole ``[2 L, 2 L]`` square."""
    return 4 * config["sequence_length"] ** 2


def layer_flops_per_token(layer: dict, config: dict, executed: bool = False
                          ) -> typing.Dict[str, float]:
    """``{projections, attention}`` of one attention layer, a TRAINED
    token's forward (both halves of the stream)."""
    d = config["heads"] * config["features_per_head"]
    k, s = config["features_per_head"], config["sequence_length"]
    hq, hk = layer["q_heads"], layer["kv_heads"]
    pairs = executed_pairs(config) if executed else live_pairs(config)
    return {"projections": 2 * 2 * d * k * (2 * hq + 2 * hk),
            "attention": 2 * 2 * hq * k * pairs / s}


def sparse_flops_per_token(config: dict, executed: bool = False) -> float:
    """The router over all experts and the held experts' share of a token's
    choices, one sparse layer, a TRAINED token's forward (both halves).
    ``executed``: every held expert on every position, as the plain
    reference runs it."""
    d = config["heads"] * config["features_per_head"]
    held = config.get("experts_held") or config["experts"]
    routed = held if executed \
        else config["moe_top_k"] * held / config["experts"]
    return 2 * (2 * d * config["experts"]
                + routed * 3 * 2 * d * config["expert_width"])


def head_flops_per_token(config: dict) -> float:
    d = config["heads"] * config["features_per_head"]
    return 2.0 * d * config["vocab_size"]


def forward_flops_per_token(config: dict, executed: bool = False) -> float:
    return head_flops_per_token(config) \
        + sum(sum(layer_flops_per_token(layer, config, executed).values())
              for layer in attention_layers(config)) \
        + sparse_layers(config) * sparse_flops_per_token(config, executed)


def train_flops_per_token(config: dict) -> float:
    """Forward + backward = 3 x forward; recomputation is not credited."""
    return 3.0 * forward_flops_per_token(config)


# ---- the kernels: (flops, bytes) ---------------------------------------------

#: kernel (without ``_blockdiff`` and the trailing number) -> (matmuls a live
#: pair, activations of b * 2 L * H * k read or written, those of b * L * H *
#: k: the clean half's keys and values and their gradients)
BLOCKDIFF = {"flash_fwd": (2, 2, 2), "flash_bwd_fused": (5, 4, 4),
             "flash_bwd_dq": (3, 3, 2), "flash_bwd_dkv": (4, 2, 4)}


def flash_cost(kind: str, layer: dict, config: dict, width: int = 2
               ) -> typing.Tuple[float, float]:
    """``(flops, bytes)`` of one call of the kernel the trace names ``kind``
    (``flash_*_blockdiff``: one call a layer over both halves), costed at the
    LIVE pairs of the WHOLE mask at key = value width — the own blocks' pairs
    too, whatever runs them — and each of its tensors once (K and V after
    the repeat over their group: a query head each, as the causal kernels'),
    plus the float32 row statistics."""
    if not kind.endswith("_blockdiff"):
        raise KeyError(f"kernel {kind!r}: only block-diffusion calls are "
                       "costed")
    base = kind[:-len("_blockdiff")]
    if base not in BLOCKDIFF:
        raise KeyError(f"no cost function for kernel {kind!r}")
    matmuls, doubled, single = BLOCKDIFF[base]
    b, s, k = config["train_batch_size"], config["sequence_length"], \
        config["features_per_head"]
    hq = layer["q_heads"]
    return (matmuls * 2 * b * hq * k * live_pairs(config),
            (2 * doubled + single) * b * s * hq * k * width
            + 2 * b * hq * 2 * s * 4)
