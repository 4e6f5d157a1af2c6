"""Required operations and bytes of the Keye-VL-2.0 configuration (its
language model) as one expert-parallel rank holds it, computed from shapes.

From the layer equations in ``benchmark/reference/keye_vl_2_0_30b_a3b.py``.
Every layer: the attention's projections (query and output ``d x H k``, key
and value ``d x G k``), the indexer's three projections (``d x J e``, ``d x
e``, ``d x J``) and its scores over EVERY visible (query, key) pair (``J e`` a
pair: the indexer has to look at a pair to drop it), the attention's scores
and weighted values over the KEPT pairs, the index loss's second ``q k^T``
over the kept pairs (``pbar`` needs every head's probabilities again; the
forward alone: ``pbar`` is detached), the router over ALL routed experts and
the routed experts HELD HERE (of a token's ``top_k`` choices the share ``held
/ experts`` lands on this rank when the router is balanced); then the head
over this rank's rows of the vocabulary.  "Required" is what the mathematics
needs, whatever kernel does it, and nothing recomputed or masked.  Norms,
rotary positions, softmax, the ReLU and the weighted sum over index heads,
the counting passes of the top-k and the loss's logarithms are not matmuls.

How many keys a query keeps does not depend on the weights: ``min(t + 1,
index_topk)``.  So the kept pairs are a closed form of the length and
``index_topk`` (``kept_pairs``).
"""
from __future__ import annotations

import re
import typing


def attention_layers(config: dict) -> typing.List[dict]:
    """``{"q_heads", "kv_heads"}`` of each indexed attention layer of the
    step, in execution order."""
    out = []
    for block in config["block_config"]:
        for layer in block["layer"]:
            name, *flags = layer.split("-")
            if name != "attention":
                continue
            if "indexed" not in flags:
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


# ---- the selection: closed forms ---------------------------------------------

def kept_keys(t: int, config: dict) -> int:
    return min(t + 1, config["index_topk"])


def kept_pairs(config: dict) -> int:
    """(query, key) pairs every head attends, one sequence."""
    s, k = config["sequence_length"], config["index_topk"]
    full = min(s, k)
    return full * (full + 1) // 2 + (s - full) * k


def visible_pairs(config: dict) -> int:
    s = config["sequence_length"]
    return s * (s + 1) // 2


def kept_key_share(config: dict) -> float:
    """Kept keys over visible keys, the mean over the queries: what the
    program's ``hbnlp_sparse_kept_key_share`` reads."""
    s = config["sequence_length"]
    return sum(kept_keys(t, config) / (t + 1) for t in range(s)) / s


def choosing_query_share(config: dict) -> float:
    s = config["sequence_length"]
    return max(0, s - config["index_topk"]) / s


# ---- the model ---------------------------------------------------------------

def layer_flops_per_token(layer: dict, config: dict) -> typing.Dict[str, float]:
    """``{projections, attention, index_projections, index_scores,
    index_loss}`` of one attention layer, a token's forward."""
    d = config["heads"] * config["features_per_head"]
    k, s = config["features_per_head"], config["sequence_length"]
    hq, hk = layer["q_heads"], layer["kv_heads"]
    j, e = config["index_heads"], config["index_features"]
    kept = kept_pairs(config) / s
    return {"projections": 2 * d * k * (2 * hq + 2 * hk),
            "attention": 2 * 2 * hq * k * kept,
            "index_projections": 2 * d * (j * e + e + j),
            "index_scores": 2 * j * e * visible_pairs(config) / s,
            "index_loss": 2 * hq * k * kept}


def sparse_flops_per_token(config: dict) -> float:
    """The router over all experts and the held experts' share of a token's
    choices, one sparse layer, a token's forward."""
    d = config["heads"] * config["features_per_head"]
    held = config.get("experts_held") or config["experts"]
    routed = config["moe_top_k"] * held / config["experts"]
    return 2 * d * config["experts"] + routed * 3 * 2 * d * config["expert_width"]


def forward_flops_per_token(config: dict) -> float:
    d = config["heads"] * config["features_per_head"]
    return 2.0 * d * config["vocab_size"] \
        + sum(sum(layer_flops_per_token(layer, config).values())
              for layer in attention_layers(config)) \
        + sparse_layers(config) * sparse_flops_per_token(config)


def train_flops_per_token(config: dict) -> float:
    """Forward + backward = 3 x forward, but for the index loss's second ``q
    k^T``, which has no backward (``pbar`` is detached): counted once.  The
    indexer's scores DO have one (the index loss trains it).  Recomputation
    is not credited: the scores the select pass and the loss pass both make
    count once."""
    once = sum(layer_flops_per_token(layer, config)["index_loss"]
               for layer in attention_layers(config))
    return 3.0 * forward_flops_per_token(config) - 2.0 * once


# ---- the kernels: (flops, bytes) ---------------------------------------------

#: as roofline/sala_costs.py SELECT: kernel (without ``_select`` and the
#: trailing number) -> (matmuls a kept pair, activations of b*s*H*k read or
#: written, those of b*s*G*k)
SELECT = {"flash_fwd": (2, 2, 2), "flash_bwd_dq": (3, 3, 2),
          "flash_bwd_dkv": (4, 2, 4)}


def select_cost(kind: str, layer: dict, config: dict, width: int = 2
                ) -> typing.Tuple[float, float]:
    """``(flops, bytes)`` of one call of the key-at-a-time selected kernel
    the trace names ``kind``: its matmuls over the KEPT pairs only — the
    pairs a tile holds beside them are the kernel's own affair — and each of
    its tensors once, K and V a K/V head each, plus the float32 row
    statistics and the choice's bits once (a bit a pair of the square)."""
    if not kind.endswith("_select"):
        raise KeyError(f"kernel {kind!r}: only selected calls are costed")
    base = kind[:-len("_select")]
    if base not in SELECT:
        raise KeyError(f"no cost function for kernel {kind!r}")
    matmuls, wide, narrow = SELECT[base]
    b, s, k = config["train_batch_size"], config["sequence_length"], \
        config["features_per_head"]
    hq, hk = layer["q_heads"], layer["kv_heads"]
    return (matmuls * 2 * b * hq * k * kept_pairs(config),
            (wide * hq + narrow * hk) * b * s * k * width
            + 2 * b * hq * s * 4 + b * s * s // 8)
