"""Required operations and bytes of the SmallThinker-21BA3B configuration as
one expert-parallel rank holds it, computed from shapes.

From the layer equations in ``benchmark/reference/smallthinker_21b_a3b.py``.
A layer on a stream ``d`` wide: the attention's projections (query and output
``d x H k``, key and value ``d x G k``; no gate), its scores and weighted
values over the LIVE pairs — the lower triangle in a global layer, the band
``0 <= i - t < window`` in a window layer —, the EARLY router ``d x experts``
(one matmul a layer, in the attention block: the sparse block runs none), and
the routed experts HELD HERE, three matrices ``d x i`` each: of a token's
``top_k`` choices the share ``held / experts`` lands on this rank when the
router is balanced.  Then the head over this rank's rows of the vocabulary.
"Required" is what the mathematics needs: nothing masked, nothing recomputed.
Norms, rotary positions, the softmaxes and ReLU are not matmuls.

The layers are read from the configuration's layer strings, as the reference
reads them (``laguna_costs.layers``: the same ``q_heads<n>-kv_heads<m>`` and
``window<w>`` flags); the flash calls' costs are ``laguna_costs.flash_cost``'s
at this configuration's head count and window.
"""
from __future__ import annotations

import typing

from . import costs, laguna_costs
from .laguna_costs import band_pairs


def attention_layers(config: dict) -> typing.List[dict]:
    """``{"heads", "kv_heads", "window"}`` of each attention layer of the
    step, in execution order."""
    return [layer for layer in laguna_costs.layers(config)
            if layer["kind"] == "attention"]


def early_routers(config: dict) -> int:
    return config["depth"] * sum(
        layer == "route_early" for block in config["block_config"]
        for layer in block["layer"])


def sparse_layers(config: dict) -> int:
    return sum(layer["kind"] == "sparse"
               for layer in laguna_costs.layers(config))


def live_pairs(config: dict, window: typing.Optional[int]) -> int:
    """(query, key) pairs one head of one sequence scores: query ``i`` sees
    keys ``max(0, i - window + 1) .. i`` (``window`` None: ``0 .. i``)."""
    s = config["sequence_length"]
    return costs.causal_pairs(s) if window is None else band_pairs(s, window)


def _d(config: dict) -> int:
    return config["heads"] * config["features_per_head"]


def attention_flops_per_token(layer: dict, config: dict,
                              executed: bool = False
                              ) -> typing.Dict[str, float]:
    """``{projections, pairs}`` of one attention layer, a token's forward.
    ``executed``: the whole square of scores, as the plain reference runs
    it."""
    d, k, s = _d(config), config["features_per_head"], \
        config["sequence_length"]
    h, g = layer["heads"], layer["kv_heads"]
    keys = float(s) if executed else live_pairs(config, layer["window"]) / s
    return {"projections": 2 * 2 * d * k * (h + g),
            "pairs": 2 * 2 * h * k * keys}


def router_flops_per_token(config: dict) -> float:
    return 2.0 * _d(config) * config["experts"]


def experts_flops_per_token(config: dict, executed: bool = False) -> float:
    """The held experts' share of a token's choices, one sparse layer.
    ``executed``: every held expert on every token, as the plain reference
    runs it."""
    held = config.get("experts_held") or config["experts"]
    routed = held if executed \
        else config["moe_top_k"] * held / config["experts"]
    return routed * 3 * 2 * _d(config) * config["expert_width"]


def head_flops_per_token(config: dict) -> float:
    return 2.0 * _d(config) * config["vocab_size"]


def forward_parts_per_token(config: dict, executed: bool = False
                            ) -> typing.Dict[str, float]:
    """A token's forward by part: the windowed layers' pairs, the global
    layers', all projections, routers, held experts, the head."""
    layers = attention_layers(config)
    parts = [attention_flops_per_token(layer, config, executed)
             for layer in layers]
    return {
        "window_pairs": sum(p["pairs"] for p, layer in zip(parts, layers)
                            if layer["window"] is not None),
        "global_pairs": sum(p["pairs"] for p, layer in zip(parts, layers)
                            if layer["window"] is None),
        "projections": sum(p["projections"] for p in parts),
        "routers": early_routers(config) * router_flops_per_token(config),
        "experts": sparse_layers(config)
        * experts_flops_per_token(config, executed),
        "head": head_flops_per_token(config)}


def forward_flops_per_token(config: dict, executed: bool = False) -> float:
    return sum(forward_parts_per_token(config, executed).values())


def train_flops_per_token(config: dict) -> float:
    """Forward + backward = 3 x forward; recomputation is not credited."""
    return 3.0 * forward_flops_per_token(config)


def flash_cost(kind: str, config: dict) -> typing.Tuple[float, float]:
    """``(flops, bytes)`` of one call of the kernel the trace names ``kind``:
    a ``flash_*_window`` call over the band's live pairs, a
    ``flash_*_causal`` call over the triangle's, both at the layers' query
    head count (K and V as the kernels read them, repeated over their
    group)."""
    return laguna_costs.flash_cost(kind, config)
