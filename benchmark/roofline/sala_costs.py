"""Required operations and bytes of the MiniCPM-SALA configuration, computed
from shapes.

From the layer equations in ``benchmark/reference/minicpm_sala.py``, for the
share of each layer this tensor-parallel rank holds.  Per lightning layer:
four projections ``d x (H d_h)``, the rule, the out-projection; per sparse
layer: the query, gate and out projections over the held query heads and key
and value over the held K/V heads, the indexer's scores over the pooled keys
a query may see, and scores and weighted values over the KEPT keys; after
every mixer the whole gated MLP of three ``d x i`` matmuls; then the head
over the vocabulary held here.  "Required" is what the mathematics needs in
the form the configuration states — the chunked rule at ``lightning_chunk``
positions a chunk with the lower triangle inside a chunk, the kept (query,
key) pairs of the selection — whatever kernel does it, and nothing
recomputed.  Norms, rotary positions, gates, decays, the pooling's sums, the
block maxima and the top-k are not matmuls.

How many blocks a query keeps does not depend on the weights: a query that
may see at most ``sparse_topk`` blocks keeps them all; any later one keeps
exactly ``sparse_topk`` — its own (forced) up to the query itself, the others
whole.  So the kept pairs are a closed form of the length and the sparse
sizes (``kept_pairs``; ``benchmark/tests/sala_costs_test.py`` counts them on
a selection made of random scores).
"""
from __future__ import annotations

import re
import typing


def mixers(config: dict) -> typing.List[dict]:
    """``{"kind": "lightning" | "sparse", ...}`` of each layer of the step,
    in execution order; a sparse layer with its held head counts."""
    out = []
    for block in config["block_config"][0::2]:
        name, *flags = block["layer"][1].split("-")
        if name == "lightning":
            out.append({"kind": "lightning"})
            continue
        if name != "attention" or "sparse" not in flags:
            raise KeyError(f"no cost function for layer {block['layer'][1]!r}")
        counts = {m.group(1): int(m.group(2)) for m in (
            re.fullmatch(r"(q_heads|kv_heads)(\d+)", f) for f in flags) if m}
        out.append({"kind": "sparse",
                    "q_heads": counts.get("q_heads", config["heads"]),
                    "kv_heads": counts.get("kv_heads", config["heads"])})
    return out * config["depth"]


def count(config: dict, kind: str) -> int:
    return sum(m["kind"] == kind for m in mixers(config))


def sizes(config: dict) -> dict:
    return {k: config[f"sparse_{k}"] for k in (
        "kernel_size", "kernel_stride", "block_size", "topk", "init_blocks",
        "window", "dense_length")}


# ---- the selection: closed forms ---------------------------------------------

def selects(config: dict) -> bool:
    return config["sequence_length"] > config["sparse_dense_length"]


def kept_keys(t: int, config: dict) -> int:
    """Keys query ``t`` attends in one K/V group."""
    z = sizes(config)
    if not selects(config):
        return t + 1
    forced = z["init_blocks"] + max(1, z["window"] // z["block_size"])
    if forced > z["topk"]:
        raise ValueError("more forced blocks than sparse_topk keeps")
    if t // z["block_size"] + 1 <= z["topk"]:
        return t + 1
    return (z["topk"] - 1) * z["block_size"] + t % z["block_size"] + 1


def kept_pairs(config: dict) -> int:
    """(query, key) pairs one K/V group's heads each attend, one sequence."""
    return sum(kept_keys(t, config) for t in range(config["sequence_length"]))


def kept_key_share(config: dict) -> float:
    """Kept keys over visible keys, the mean over the queries: what the
    program's ``hbnlp_sparse_kept_key_share`` reads."""
    s = config["sequence_length"]
    return sum(kept_keys(t, config) / (t + 1) for t in range(s)) / s


def choosing_query_share(config: dict) -> float:
    s, z = config["sequence_length"], sizes(config)
    if not selects(config):
        return 0.0
    return sum(t // z["block_size"] + 1 > z["topk"] for t in range(s)) / s


def visible_pooled(config: dict) -> int:
    """(query, pooled key) pairs the indexer scores, one sequence: window
    ``j`` is visible when ``stride j + kernel <= t + 1``."""
    z = sizes(config)
    return sum(max(0, (t + 1 - z["kernel_size"]) // z["kernel_stride"] + 1)
               for t in range(config["sequence_length"]))


# ---- the model ---------------------------------------------------------------

def lightning_held(config: dict) -> int:
    return config["lightning_heads_held"] or config["lightning_heads"]


def rule_flops_per_token(config: dict, executed: bool = False) -> float:
    """The chunked rule's matmuls for one token of one layer, the held
    heads: ``q k^T`` and the weighted values over the ``(chunk + 1) / 2``
    keys a position meets in its chunk (the whole ``chunk`` executed by the
    dense masked form), the chunk's state ``k^T v`` and the entering state's
    part ``q S``."""
    h, d = lightning_held(config), config["lightning_head_features"]
    c = min(config["lightning_chunk"], config["sequence_length"])
    keys = c if executed else (c + 1) / 2
    return h * (2 * 2 * d * keys + 2 * 2 * d * d)


def sparse_flops_per_token(layer: dict, config: dict) -> typing.Dict[str, float]:
    """``{projections, indexer, attention}`` of one sparse layer, a token."""
    d = config["heads"] * config["features_per_head"]
    k, s = config["features_per_head"], config["sequence_length"]
    hq, hk = layer["q_heads"], layer["kv_heads"]
    out = {"projections": 2 * d * k * (3 * hq + 2 * hk), "indexer": 0.0,
           "attention": 2 * 2 * hq * k * kept_pairs(config) / s}
    if selects(config):
        out["indexer"] = 2 * hq * k * visible_pooled(config) / s
    return out


def forward_flops_per_token(config: dict) -> float:
    d = config["heads"] * config["features_per_head"]
    i = int(d * config["intermediate_feed_forward_multiplier"])
    total = 2.0 * d * config["vocab_size"]
    for layer in mixers(config):
        if layer["kind"] == "lightning":
            inner = lightning_held(config) * config["lightning_head_features"]
            total += 5 * 2 * d * inner + rule_flops_per_token(config)
        else:
            total += sum(sparse_flops_per_token(layer, config).values())
        total += 3 * 2 * d * i
    return total


def train_flops_per_token(config: dict) -> float:
    """Forward + backward = 3 x forward; recomputation is not credited (nor
    is a backward of the indexer: the selection carries no gradient, so its
    scores are counted once)."""
    indexer = sum(sparse_flops_per_token(layer, config)["indexer"]
                  for layer in mixers(config) if layer["kind"] == "sparse")
    return 3.0 * forward_flops_per_token(config) - 2.0 * indexer


# ---- the kernels: (flops, bytes) ---------------------------------------------

def rule_cost(config: dict, width: int = 2) -> typing.Tuple[float, float]:
    """``(flops, bytes)`` ONE lightning layer's rule needs in one train step,
    forward and backward.  Bytes, ``width`` an element: the forward reads
    ``q``, ``k``, ``v`` and writes ``o``; the backward reads those three and
    ``do`` and writes ``dq``, ``dk``, ``dv``.  No decay matrix, no chunk
    state and nothing recomputed is credited: a fused kernel keeps them on
    the chip."""
    tokens = config["train_batch_size"] * config["sequence_length"]
    inner = lightning_held(config) * config["lightning_head_features"]
    return 3 * rule_flops_per_token(config) * tokens, \
        11 * inner * width * tokens


#: kernel (without ``_select`` and the trailing number) -> (matmuls a kept
#: pair, activations of b*s*H*k read or written, those of b*s*G*k), as
#: roofline/zaya_costs.py FLASH: forward q, out | k, v; dq q, do, dq | k, v;
#: dk/dv q, do | k, v, dk, dv
SELECT = {"flash_fwd": (2, 2, 2), "flash_bwd_dq": (3, 3, 2),
          "flash_bwd_dkv": (4, 2, 4)}


def select_cost(kind: str, layer: dict, config: dict, width: int = 2
                ) -> typing.Tuple[float, float]:
    """``(flops, bytes)`` of one call of the selected kernel the trace names
    ``kind``: its matmuls over the KEPT pairs only — the pairs a tile holds
    beside them are the kernel's own affair — and each of its tensors once,
    K and V a K/V head each, plus the float32 row statistics.  The rows'
    choice (a bit a block) is not counted."""
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
            + 2 * b * hq * s * 4)

