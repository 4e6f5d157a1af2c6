"""Required operations of the JoyAI-LLM-Flash configuration as one rank of an
expert-parallel group holds it, computed from shapes.

From the layer equations in ``benchmark/reference/joyai_llm_flash.py``.  The
dense MLP, the sparse layer's parts and the flash kernels' calls are
``roofline/kimi_costs.py``'s (the same layers: three ``d x i`` matmuls; the
router over ALL routed experts, the shared expert, the routed experts HELD
HERE at ``top_k x held / experts``; a causal triangle at key ``d_k + r`` /
value ``d_k`` — ``kimi_costs.flash_cost`` reads this configuration's layer
strings as it stands, and ``tests/joyai_costs_test.py`` holds it to the
numbers here).  What is this configuration's own: a latent attention layer
whose query comes through a latent of ``c_q`` — ``d x c_q`` down and ``c_q x
Q (d_k + r)`` up in the place of one ``d x Q (d_k + r)`` — in EVERY layer (the
leading block, ``depth`` times the body's, the module's); the head over this
rank's rows of the vocabulary TWICE (the main pass and the module's); and the
module's join, ``2 d x d``.  "Required" is what the mathematics needs in the
form the configuration states and nothing masked or recomputed; rotary, norms
and sigmoids are not matmuls.
"""
from __future__ import annotations

import typing

from . import kimi_costs


def _block_layers(config: dict, block: dict) -> typing.List[dict]:
    """One block's sublayers as ``kimi_costs.layers`` names them, a latent
    layer with its ``q_latent`` (0: none) on top."""
    found = kimi_costs.layers({**config, "block_config": [block], "depth": 1})
    for layer in found:
        if layer["kind"] == "latent":
            flags = next(name for name in block["layer"]
                         if name.startswith("attention-")).split("-")[1:]
            layer["q_latent"] = kimi_costs._number(flags, "q_latent", 0)
    return found


def layers(config: dict) -> typing.List[dict]:
    """Every sublayer of the step in execution order: the leading blocks
    once, the body ``depth`` times, the module's blocks ``mtp_depth`` times."""
    return [layer for blocks, times in (
        (config.get("input_block_config", []), 1),
        (config["block_config"], config["depth"]),
        (config.get("mtp_block_config", []), config.get("mtp_depth", 0)))
        for block in list(blocks) * times
        for layer in _block_layers(config, block)]


def count(config: dict, kind: str) -> int:
    return sum(layer["kind"] == kind for layer in layers(config))


def latent_flops_per_token(config: dict, layer: dict,
                           executed: bool = False) -> float:
    """``kimi_costs.latent_flops_per_token`` with the query through its
    latent: ``d x c_q`` and ``c_q x Q (d_k + r)`` for ``d x Q (d_k + r)``."""
    d = config["heads"] * config["features_per_head"]
    wide = layer["heads"] * (config["features_per_head"] + layer["shared"])
    total = kimi_costs.latent_flops_per_token(config, layer, executed)
    if layer["q_latent"]:
        total += 2 * layer["q_latent"] * (d + wide) - 2 * d * wide
    return total


def join_flops_per_token(config: dict) -> float:
    """The module's join, ``[embedding | stream] W_eh``: ``2 d x d`` a pass."""
    d = config["heads"] * config["features_per_head"]
    return config.get("mtp_depth", 0) * 2 * 2 * d * d


def head_flops_per_token(config: dict) -> float:
    """The head over this rank's rows, the main pass and every pass of the
    module."""
    d = config["heads"] * config["features_per_head"]
    return (1 + config.get("mtp_depth", 0)) * 2 * d * config["vocab_size"]


def forward_flops_per_token(config: dict, executed: bool = False) -> float:
    """Matmul FLOPs of one token's forward pass on this rank, the module's
    included.  ``executed``: as the plain reference runs it (the whole square
    of scores, every held expert on every token)."""
    total = head_flops_per_token(config) + join_flops_per_token(config)
    for layer in layers(config):
        if layer["kind"] == "latent":
            total += latent_flops_per_token(config, layer, executed)
        elif layer["kind"] == "dense":
            total += kimi_costs.dense_flops_per_token(config)
        elif layer["kind"] == "sparse":
            total += sum(kimi_costs.sparse_parts_per_token(
                config, executed).values())
        else:
            raise KeyError(f"no cost for a layer of kind {layer['kind']!r}")
    return total


def train_flops_per_token(config: dict) -> float:
    """Forward + backward = 3 x forward; recomputation is not credited."""
    return 3.0 * forward_flops_per_token(config)
