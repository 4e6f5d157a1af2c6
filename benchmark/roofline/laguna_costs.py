"""Required operations and bytes of the Laguna configuration as one
expert-parallel rank holds it, computed from shapes.

From the layer equations in ``benchmark/reference/laguna_s_2_1.py``.  An
attention layer with ``H`` query heads over ``G`` K/V heads of width ``k`` on
a stream ``d`` wide: the query and output projections ``d x H k``, key and
value ``d x G k``, the gate ``d x H``, and scores and weighted values over
the keys a query sees — the lower triangle in a global layer, the band
``0 <= i - t < window`` in a window layer.  Layer 0 has the dense MLP; every
other layer the router over ALL routed experts, the shared expert, and the
routed experts HELD HERE: of a token's ``top_k`` choices the share ``held /
experts`` lands on this rank when the router is balanced.  Then the head
over this rank's rows of the vocabulary.  "Required" is what the
mathematics needs: nothing masked, nothing recomputed.  Norms, rotary
embedding, softmax and the gates' sigmoids are not matmuls.

The layers are read from the configuration's layer strings, as the reference
reads them.
"""
from __future__ import annotations

import typing

from . import costs


def band_pairs(s: int, window: int) -> int:
    """Query-key pairs of one sequence under a window: query ``i`` sees
    ``min(i + 1, window)`` keys."""
    w = min(window, s)
    return s * w - w * (w - 1) // 2


def _number(flags, name: str, default=None):
    for f in flags:
        if f.startswith(name) and f[len(name):].isdigit():
            return int(f[len(name):])
    return default


def layers(config: dict) -> typing.List[dict]:
    """Every sublayer in execution order: ``{"kind": "attention", "heads",
    "kv_heads", "window"}``, ``{"kind": "dense"}`` or ``{"kind":
    "sparse"}``."""
    blocks = list(config.get("input_block_config", [])) \
        + list(config["block_config"]) * config["depth"]
    out = []
    for block in blocks:
        for layer in block["layer"]:
            name, *flags = layer.split("-")
            if name == "attention":
                out.append({"kind": "attention",
                            "heads": _number(flags, "q_heads"),
                            "kv_heads": _number(flags, "kv_heads"),
                            "window": _number(flags, "window")})
            elif name == "mlp":
                out.append({"kind": "dense"})
            elif name == "moe":
                out.append({"kind": "sparse"})
    return out


def _widths(config: dict):
    d = config["heads"] * config["features_per_head"]
    return d, config["features_per_head"], \
        int(d * config["intermediate_feed_forward_multiplier"]), \
        config["expert_width"]


def forward_flops_per_token(config: dict, executed: bool = False) -> float:
    """Matmul FLOPs of one token's forward pass on this rank.
    ``executed``: what the plain reference runs instead — the whole square
    of scores in every attention layer and EVERY held expert on every token
    — used only to check this enumeration against a jaxpr count of that
    reference."""
    d, k, dense, width = _widths(config)
    s = config["sequence_length"]
    held = config.get("experts_held") or config["experts"]
    total = 2 * d * config["vocab_size"]
    for layer in layers(config):
        if layer["kind"] == "attention":
            h, g = layer["heads"], layer["kv_heads"]
            if executed:
                keys = float(s)
            elif layer["window"] is None:
                keys = costs.causal_pairs(s) / s
            else:
                keys = band_pairs(s, layer["window"]) / s
            total += 2 * 2 * d * h * k + 2 * 2 * d * g * k + 2 * d * h \
                + 2 * 2 * h * k * keys
        elif layer["kind"] == "dense":
            total += 3 * 2 * d * dense
        else:
            routed = held if executed \
                else config["moe_top_k"] * held / config["experts"]
            total += 2 * d * config["experts"] + (1 + routed) * 3 * 2 * d * width
    return total


def train_flops_per_token(config: dict) -> float:
    """Forward + backward = 3 x forward; recomputation is not credited."""
    return 3.0 * forward_flops_per_token(config)


# ---- the flash kernels: (flops, bytes) of ONE call ---------------------------

#: kernel (without ``_causal`` / ``_window`` and the trailing number) ->
#: (matmuls a pair, activations of b*s*h*k read or written): roofline/costs.py
FLASH = {"flash_fwd": (2, 4), "flash_bwd_fused": (5, 8),
         "flash_bwd_dq": (3, 5), "flash_bwd_dkv": (4, 6)}


def flash_heads(config: dict, windowed: bool) -> typing.Tuple[int, typing.Optional[int]]:
    """``(query heads, window)`` of the attention layers that call the
    windowed (or the causal) kernels; they have to agree among themselves,
    or one kernel name would stand for two costs."""
    found = {(layer["heads"], layer["window"]) for layer in layers(config)
             if layer["kind"] == "attention"
             and (layer["window"] is not None) == windowed}
    if len(found) != 1:
        raise KeyError(f"{'windowed' if windowed else 'causal'} attention "
                       f"layers of {len(found)} shapes: {sorted(found)}")
    return next(iter(found))


def flash_cost(kind: str, config: dict, width: int = 2
               ) -> typing.Tuple[float, float]:
    """``(flops, bytes)`` of one call of the kernel the trace names
    ``kind``: a ``_window`` call is a window layer's (its head count, the
    band's pairs), a ``_causal`` call a global layer's (its head count, the
    triangle's).  Bytes: each of the call's activations once at ``b * s * H
    * k`` — K and V as the kernel reads them, repeated to the query heads —
    plus the float32 row statistics."""
    for suffix, windowed in (("_window", True), ("_causal", False)):
        if kind.endswith(suffix):
            base = kind[:-len(suffix)]
            break
    else:
        raise KeyError(f"kernel {kind!r}: neither causal nor windowed")
    if base not in FLASH:
        raise KeyError(f"no cost function for kernel {kind!r}")
    matmuls, tensors = FLASH[base]
    heads, window = flash_heads(config, windowed)
    b, s, k = config["train_batch_size"], config["sequence_length"], \
        config["features_per_head"]
    # a window at least as long as the sequence runs the causal kernels
    pairs = band_pairs(s, window) if windowed else costs.causal_pairs(s)
    return (matmuls * 2 * b * heads * k * pairs,
            tensors * b * s * heads * k * width + 2 * b * heads * s * 4)


# ---- the held experts' grouped matmuls ---------------------------------------

def held_gemm_cost(config: dict, pairs: float, width: int = 2
                   ) -> typing.Tuple[float, float]:
    """``(flops, bytes)`` one sparse layer's grouped matmuls over the HELD
    experts need in one train step, ``pairs`` (token, choice) pairs routed
    to them.  Three matmuls (gate, up: ``d -> i``; down: ``i -> d``), each
    forward and twice more for its two gradients: ``3 x 3 x 2 pairs d i``.
    Bytes, ``width`` an element, a pass: the pairs' rows at both widths and
    the held experts' weights once."""
    d, _, _, i = _widths(config)
    held = config.get("experts_held") or config["experts"]
    one = (pairs * d + held * d * i + pairs * i) * width
    return 3 * 3 * 2 * pairs * d * i, 3 * 3 * one
