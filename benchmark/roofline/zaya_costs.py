"""Required operations and bytes of the ZAYA1 configuration as one
expert-parallel rank holds it, computed from shapes.

From the layer equations in ``benchmark/reference/zaya1_8b.py``.  A CCA
layer with ``H`` query heads over ``G`` K/V heads of width ``k`` on a stream
``d`` wide: the query projection ``d x H k``, the key's ``d x G k``, the two
value halves ``2 x d x (G / 2) k``, the output's ``H k x d``; the grouped
convolution, ``K1`` taps of ``H + G`` blocks ``k x k``; scores and weighted
values over the lower triangle.  The depthwise convolution, the q-k mean, the
normalisation, the rotary embedding and the value shift are no matmuls.  A
sparse layer: the router's down-projection ``d x w`` and its MLP ``w x w``,
``w x w``, ``w x E``, and the ONE expert a token chose where this rank holds
it: the share ``held / experts`` of the tokens when the router is balanced,
or the share the program counted.  Then the head over this rank's rows of
the tied table.  "Required" is what the mathematics needs: nothing masked,
nothing recomputed.

The layers are read from the configuration's layer strings, as the reference
reads them.
"""
from __future__ import annotations

import typing

from . import costs


def _number(flags, name: str, default=None):
    for f in flags:
        if f.startswith(name) and f[len(name):].isdigit():
            return int(f[len(name):])
    return default


def layers(config: dict) -> typing.List[dict]:
    """Every sublayer in execution order: ``{"kind": "cca", "heads",
    "kv_heads"}`` or ``{"kind": "sparse"}``."""
    out = []
    for block in list(config["block_config"]) * config["depth"]:
        for layer in block["layer"]:
            name, *flags = layer.split("-")
            if name == "cca":
                out.append({"kind": "cca",
                            "heads": _number(flags, "q_heads"),
                            "kv_heads": _number(flags, "kv_heads")})
            elif name == "moe":
                out.append({"kind": "sparse"})
    return out


def count(config: dict, kind: str) -> int:
    return sum(layer["kind"] == kind for layer in layers(config))


def _widths(config: dict):
    """``(stream, head, expert, router)`` widths."""
    return (config["heads"] * config["features_per_head"],
            config["features_per_head"], config["expert_width"],
            config["moe_router_width"])


def cca_heads(config: dict) -> typing.Tuple[int, int]:
    """``(query heads, K/V heads)`` of the CCA layers; they have to agree
    among themselves, or one kernel name would stand for two costs."""
    found = {(layer["heads"], layer["kv_heads"]) for layer in layers(config)
             if layer["kind"] == "cca"}
    if len(found) != 1:
        raise KeyError(f"cca layers of {len(found)} shapes: {sorted(found)}")
    return next(iter(found))


def held_share(config: dict) -> float:
    """The share of the (token, choice) pairs that lands on this rank when
    the router is balanced."""
    held = config.get("experts_held") or config["experts"]
    return held / config["experts"]


def cca_flops_per_token(config: dict, executed: bool = False
                        ) -> typing.Dict[str, float]:
    """One CCA layer's forward matmul FLOPs a token, by part."""
    d, k, _, _ = _widths(config)
    h, g = cca_heads(config)
    s = config["sequence_length"]
    keys = float(s) if executed else costs.causal_pairs(s) / s
    return {"projections": 2 * d * (h + g) * k + 2 * d * g * k
            + 2 * h * k * d,
            "conv": 2 * config["cca_time1"] * (h + g) * k * k,
            "scores": 2 * 2 * h * k * keys}


def forward_flops_per_token(config: dict, executed: bool = False,
                            share: typing.Optional[float] = None) -> float:
    """Matmul FLOPs of one token's forward pass on this rank.  ``share``:
    the share of the pairs on the held experts (None = a balanced router's).
    ``executed``: what the plain reference runs instead — the whole square
    of scores and EVERY held expert on every token — used only to check this
    enumeration against a jaxpr count of that reference."""
    d, _, width, w = _widths(config)
    held = config.get("experts_held") or config["experts"]
    if executed:
        routed = float(held)
    else:
        routed = config["moe_top_k"] * (held_share(config) if share is None
                                        else share)
    total = 2.0 * d * config["vocab_size"]
    for layer in layers(config):
        if layer["kind"] == "cca":
            total += sum(cca_flops_per_token(config, executed).values())
        else:
            total += 2 * d * w + 2 * 2 * w * w + 2 * w * config["experts"] \
                + routed * 3 * 2 * d * width
    return total


def train_flops_per_token(config: dict,
                          share: typing.Optional[float] = None) -> float:
    """Forward + backward = 3 x forward; recomputation is not credited."""
    return 3.0 * forward_flops_per_token(config, share=share)


# ---- the flash kernels: (flops, bytes) of ONE call ---------------------------

#: kernel (without ``_causal`` and the trailing number) -> (matmuls a pair,
#: activations of b*s*h*k read or written): roofline/costs.py
FLASH = {"flash_fwd": (2, 4), "flash_bwd_fused": (5, 8),
         "flash_bwd_dq": (3, 5), "flash_bwd_dkv": (4, 6)}


def flash_cost(kind: str, config: dict, width: int = 2
               ) -> typing.Tuple[float, float]:
    """``(flops, bytes)`` of one call of the kernel the trace names ``kind``
    at the CCA layers' QUERY head count (not the stream's ``heads``) over
    the lower triangle.  Bytes: each of the call's activations once at ``b *
    s * H * k`` — K and V as the kernel reads them, repeated to the query
    heads — plus the float32 row statistics."""
    if not kind.endswith("_causal"):
        raise KeyError(f"kernel {kind!r}: only causal calls are costed")
    base = kind[:-len("_causal")]
    if base not in FLASH:
        raise KeyError(f"no cost function for kernel {kind!r}")
    matmuls, tensors = FLASH[base]
    heads, _ = cca_heads(config)
    b, s, k = config["train_batch_size"], config["sequence_length"], \
        config["features_per_head"]
    return (matmuls * 2 * b * heads * k * costs.causal_pairs(s),
            tensors * b * s * heads * k * width + 2 * b * heads * s * 4)


# ---- what CCA adds around the kernel -----------------------------------------

def cca_mix_cost(config: dict, width: int = 2) -> typing.Tuple[float, float]:
    """``(flops, bytes)`` one CCA layer's mixing — the q-k mean, the two
    convolutions, the normalisation, the rotary embedding and the value
    shift: scopes ``body/cca/qk_mean|conv|qk_norm|rope|value_shift`` —
    requires in one train step, whatever runs it (XLA's fusions or one
    kernel).  Operations: the grouped convolution's matmuls, forward and
    twice more for its two gradients.  Bytes, ``width`` an element, with ``P
    = H + G`` packed heads: the forward reads the latents (``P``) and the two
    value halves (``G``) and writes q, k (``P``) and v (``G``); the backward
    reads the cotangents of q, k, v (``P + G``) and the latents again (``P``)
    and writes the latents' and the value halves' cotangents (``P + G``): ``5
    P + 4 G`` head-rows a token.  The taps, biases and temperatures are a
    few hundred kilobytes and not counted; a replayed forward is not
    credited."""
    _, k, _, _ = _widths(config)
    h, g = cca_heads(config)
    tokens = config["train_batch_size"] * config["sequence_length"]
    flops = 3 * cca_flops_per_token(config)["conv"] * tokens
    return flops, (5 * (h + g) + 4 * g) * k * width * tokens


# ---- the held experts' grouped matmuls ---------------------------------------

def held_gemm_cost(config: dict, pairs: float, width: int = 2
                   ) -> typing.Tuple[float, float]:
    """``(flops, bytes)`` one sparse layer's grouped matmuls over the HELD
    experts need in one train step, ``pairs`` (token, choice) pairs routed
    to them.  Three matmuls (gate, up: ``d -> i``; down: ``i -> d``), each
    forward and twice more for its two gradients: ``3 x 3 x 2 pairs d i``.
    Bytes, ``width`` an element, a pass: the pairs' rows at both widths and
    the held experts' weights once."""
    d, _, i, _ = _widths(config)
    held = config.get("experts_held") or config["experts"]
    one = (pairs * d + held * d * i + pairs * i) * width
    return 3 * 3 * 2 * pairs * d * i, 3 * 3 * one
