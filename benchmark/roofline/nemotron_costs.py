"""Required operations and bytes of the Nemotron-3-Super configuration as
one rank of a tensor-parallel pair x an expert-parallel group holds it,
computed from shapes.

From the layer equations in ``benchmark/reference/nemotron_3_super_120b.py``.
A Mamba-2 layer with ``H`` heads of ``p`` (``d_inner = H p``), ``g`` groups of
``B`` / ``C`` and state ``n`` on a stream ``d`` wide: the in-projection ``d x
(2 d_inner + 2 g n + H)``, the scan, the out-projection ``d_inner x d``.  An
attention layer with ``Q`` query heads over ``G`` K/V heads of width ``k``:
query and output ``d x Q k``, key and value ``d x G k``, scores and weighted
values over the lower triangle.  A LatentMoE layer: the router over ALL
routed experts ``d x E``, the latent's projections ``d x L`` and ``L x d``,
the shared expert's two matmuls ``d x W`` and ``W x d``, and the routed
experts HELD HERE, two matmuls ``L x I`` and ``I x L`` each: of a token's
``top_k`` choices the share ``held / experts`` lands on this rank when the
router is balanced.  Then the head over this rank's rows of the vocabulary.
"Required" is what the mathematics needs in the form the configuration
states — the chunked scan at ``mamba_chunk`` positions a chunk with ``C B^T``
made once a GROUP, the lower triangles — and nothing masked or recomputed.
Norms, the conv's four multiplies, softplus, sigmoids, relu squared and the
decays are not matmuls.

The layers are read from the configuration's layer strings, as the reference
reads them.
"""
from __future__ import annotations

import typing

from . import costs


def _number(flags, name: str) -> int:
    return next(int(f[len(name):]) for f in flags
                if f.startswith(name) and f[len(name):].isdigit())


def layers(config: dict) -> typing.List[dict]:
    """Every sublayer in execution order: ``{"kind": "mamba"}``, ``{"kind":
    "attention", "heads", "kv_heads"}`` or ``{"kind": "sparse"}``."""
    out = []
    for block in list(config["block_config"]) * config["depth"]:
        for layer in block["layer"]:
            name, *flags = layer.split("-")
            if name == "attention":
                out.append({"kind": "attention",
                            "heads": _number(flags, "q_heads"),
                            "kv_heads": _number(flags, "kv_heads")})
            elif name == "mamba":
                out.append({"kind": "mamba"})
            elif name == "moe":
                out.append({"kind": "sparse"})
    return out


def count(config: dict, kind: str) -> int:
    return sum(layer["kind"] == kind for layer in layers(config))


def _stream(config: dict) -> int:
    return config["heads"] * config["features_per_head"]


def _inner(config: dict) -> int:
    return config["mamba_heads"] * config["mamba_head_features"]


def _chunk(config: dict) -> int:
    return min(config["mamba_chunk"], config["sequence_length"])


def scan_flops_per_token(config: dict, executed: bool = False) -> float:
    """The chunked scan's matmuls for one token of one layer: inside the
    chunk ``C B^T`` (``n`` deep, once a GROUP) and its product with ``x``
    (all ``d_inner`` columns) over the keys of the chunk a query meets —
    ``(chunk + 1) / 2`` required, the whole ``chunk`` executed by a dense
    masked matmul —, the chunk's state ``x B^T`` and the entering state's
    part ``S C``, ``d_inner x n`` each."""
    di, n, g = _inner(config), config["mamba_state"], config["mamba_groups"]
    keys = _chunk(config) if executed else (_chunk(config) + 1) / 2
    return g * 2 * n * keys + 2 * di * keys + 2 * 2 * di * n


def mamba_flops_per_token(config: dict, executed: bool = False) -> float:
    d, di = _stream(config), _inner(config)
    return 2 * d * (2 * di + 2 * config["mamba_groups"]
                    * config["mamba_state"] + config["mamba_heads"]) \
        + scan_flops_per_token(config, executed) + 2 * di * d


def attention_flops_per_token(config: dict, layer: dict,
                              executed: bool = False) -> float:
    d, k, s = _stream(config), config["features_per_head"], \
        config["sequence_length"]
    keys = float(s) if executed else costs.causal_pairs(s) / s
    return 2 * 2 * d * layer["heads"] * k + 2 * 2 * d * layer["kv_heads"] * k \
        + 2 * 2 * layer["heads"] * k * keys


def sparse_parts_per_token(config: dict, executed: bool = False
                           ) -> typing.Dict[str, float]:
    """The LatentMoE layer's matmul FLOPs a token, by part.  ``executed``:
    every held expert on every token, as the plain reference runs them."""
    d, latent = _stream(config), config["moe_latent_width"]
    held = config.get("experts_held") or config["experts"]
    routed = held if executed \
        else config["moe_top_k"] * held / config["experts"]
    return {"router": 2 * d * config["experts"],
            "latent": 2 * 2 * d * latent,
            "shared": 2 * 2 * d * config["shared_expert_width"],
            "held": routed * 2 * 2 * latent * config["expert_width"]}


def forward_flops_per_token(config: dict, executed: bool = False) -> float:
    """Matmul FLOPs of one token's forward pass on this rank."""
    total = 2 * _stream(config) * config["vocab_size"]
    for layer in layers(config):
        if layer["kind"] == "mamba":
            total += mamba_flops_per_token(config, executed)
        elif layer["kind"] == "attention":
            total += attention_flops_per_token(config, layer, executed)
        else:
            total += sum(sparse_parts_per_token(config, executed).values())
    return total


def train_flops_per_token(config: dict) -> float:
    """Forward + backward = 3 x forward; recomputation is not credited."""
    return 3.0 * forward_flops_per_token(config)


def scan_cost(config: dict, width: int = 2) -> typing.Tuple[float, float]:
    """``(flops, bytes)`` ONE layer's scan needs in one train step on one
    chip, forward and backward.  Operations: the forward's matmuls and twice
    that for their gradients.  Bytes, ``width`` an element and 4 for ``dt``:
    the forward reads ``x``, ``B``, ``C`` (``g n`` columns each, ONCE a group
    — not once a head), ``dt`` and writes ``y``; the backward reads those
    four and ``dy`` and writes ``dx``, ``dB``, ``dC``, ``ddt``.  No decay
    matrix, no chunk state and nothing recomputed is credited: the kernels
    keep them on the chip, and the replay's second forward is the memory
    strategy's."""
    tokens = config["train_batch_size"] * config["sequence_length"]
    di, h = _inner(config), config["mamba_heads"]
    gn = config["mamba_groups"] * config["mamba_state"]
    forward = (2 * di + 2 * gn) * width + h * 4
    backward = (3 * di + 4 * gn) * width + 2 * h * 4
    return 3 * scan_flops_per_token(config) * tokens, \
        (forward + backward) * tokens


#: flash kernel (without ``_causal`` and the trailing number) -> (matmuls a
#: pair, activations read or written at the QUERY heads, at the K/V heads):
#: forward q, o | k, v; the fused backward q, o, do, dq | k, v, dk, dv; the
#: split pair q, do, dq | k, v and q, do | k, v, dk, dv
FLASH = {"flash_fwd": (2, 2, 2), "flash_bwd_fused": (5, 4, 4),
         "flash_bwd_dq": (3, 3, 2), "flash_bwd_dkv": (4, 2, 4)}


def flash_cost(kind: str, config: dict, width: int = 2
               ) -> typing.Tuple[float, float]:
    """``(flops, bytes)`` of one call of the causal flash kernel the trace
    names ``kind`` at the attention LAYER's own head counts (``q_heads`` /
    ``kv_heads`` of its layer string, not the stream's ``heads``) over the
    lower triangle.  Operations: the kernel's matmuls, ``2 k`` a pair and
    query head.  Bytes, ``width`` an element: the query-side activations at
    ``b s Q k``, key, value and their gradients at ``b s G k`` — once a K/V
    head, what the mathematics needs, although the program hands the kernel
    K and V repeated over the group — plus the float32 row statistics."""
    if not kind.endswith("_causal"):
        raise KeyError(f"kernel {kind!r}: only causal calls are costed")
    base = kind[:-len("_causal")]
    if base not in FLASH:
        raise KeyError(f"no cost function for kernel {kind!r}")
    found = {(layer["heads"], layer["kv_heads"]) for layer in layers(config)
             if layer["kind"] == "attention"}
    if len(found) != 1:
        raise KeyError(f"attention layers of {len(found)} shapes: "
                       f"{sorted(found)}")
    (q_heads, kv_heads), = found
    matmuls, at_q, at_kv = FLASH[base]
    b, s, k = config["train_batch_size"], config["sequence_length"], \
        config["features_per_head"]
    return (matmuls * 2 * b * q_heads * k * costs.causal_pairs(s),
            (at_q * q_heads + at_kv * kv_heads) * b * s * k * width
            + 2 * b * q_heads * s * 4)


def held_gemm_cost(config: dict, pairs: float, width: int = 2
                   ) -> typing.Tuple[float, float]:
    """``(flops, bytes)`` one LatentMoE layer's grouped matmuls over the
    HELD experts need in one train step, ``pairs`` (token, choice) pairs
    routed to them.  TWO matmuls a pair (up: ``L -> I``; down: ``I -> L``; no
    gate), each forward and twice more for its two gradients: ``2 x 3 x 2
    pairs L I``.  Bytes, ``width`` an element, a pass: the pairs' rows at
    both widths and the held experts' weights once."""
    latent, i = config["moe_latent_width"], config["expert_width"]
    held = config.get("experts_held") or config["experts"]
    one = (pairs * latent + held * latent * i + pairs * i) * width
    return 2 * 3 * 2 * pairs * latent * i, 2 * 3 * one
