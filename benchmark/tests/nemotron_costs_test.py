"""``roofline/nemotron_costs.py`` against hand sums at the published widths
and a jaxpr count of the plain reference at a toy size."""
import importlib
import json
import os

import numpy as np
import pytest

from benchmark.roofline import flops, nemotron_costs

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "train_nemotron_3_super_tp2_ep64_s16k"


def _doc():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "nemotron_3_super_120b.json")) as f:
        return json.load(f)


def required_flops_at_the_published_widths_test():
    """d 4096, 16,384 positions, 16,384 columns.  A Mamba-2 layer (64 heads
    x 64 = 4,096 inner, 4 groups, state 128, chunk 128): in 2 x 4096 x (8192
    + 1024 + 64) = 76,021,760, out 2 x 4096 x 4096 = 33,554,432, the scan 4
    x 2 x 128 x 64.5 (C B^T once a group) + 2 x 4096 x 64.5 + 2 x 2 x 4096 x
    128 = 2,691,584.  The attention layer (16 / 1 heads of 128): q and o 2 x
    2 x 4096 x 2048 = 33,554,432, k and v 2 x 2 x 4096 x 128 = 2,097,152,
    the triangle 2 x 2 x 2048 x 8192.5 = 67,112,960.  A LatentMoE layer:
    router 2 x 4096 x 512 = 4,194,304, latent 2 x 2 x 4096 x 1024 =
    16,777,216, shared 2 x 2 x 4096 x 5376 = 88,080,384, one expert 2 x 2 x
    1024 x 2688 = 11,010,048 at 22 x 8 / 512 = 0.34375.  Head 2 x 4096 x
    16,384 = 134,217,728."""
    config = _doc()["config"]
    scan = 4 * 2 * 128 * 64.5 + 2 * 4096 * 64.5 + 2 * 2 * 4096 * 128
    assert nemotron_costs.scan_flops_per_token(config) == scan == 2_691_584
    mamba = 76_021_760 + 33_554_432 + scan
    attention = 33_554_432 + 2_097_152 + 67_112_960
    parts = nemotron_costs.sparse_parts_per_token(config)
    assert parts == {"router": 4_194_304, "latent": 16_777_216,
                     "shared": 88_080_384, "held": 0.34375 * 11_010_048}
    want = 5 * mamba + attention + 5 * sum(parts.values()) + 134_217_728
    assert nemotron_costs.mamba_flops_per_token(config) == mamba
    assert nemotron_costs.forward_flops_per_token(config) \
        == pytest.approx(want)
    assert 1.36e9 < want < 1.37e9
    assert nemotron_costs.train_flops_per_token(config) \
        == pytest.approx(3 * want)
    kinds = [layer["kind"] for layer in nemotron_costs.layers(config)]
    assert kinds == ["attention"] + ["sparse", "mamba"] * 5
    assert nemotron_costs.layers(config)[0] == {
        "kind": "attention", "heads": 16, "kv_heads": 1}
    assert (nemotron_costs.count(config, "mamba"),
            nemotron_costs.count(config, "sparse")) == (5, 5)


def scan_and_gemm_costs_at_the_cells_shape_test():
    """The scan a layer a step: 3 x 2,691,584 x 16,384 operations; bytes a
    token: forward x and y 2 x 4096 x 2 + B and C 2 x 512 x 2 (ONCE a group:
    4 x 128 columns each, not 64 heads') + dt 64 x 4, backward 3 x 4096 x 2
    + 4 x 512 x 2 + 2 x 64 x 4.  The held experts at 5,632 pairs: TWO
    matmuls, 2 x 3 x 2 x 5632 x 1024 x 2688, each pass the rows at both
    widths and 8 experts' weights."""
    config = _doc()["config"]
    assert nemotron_costs.scan_cost(config) == (
        3 * 2_691_584 * 16384,
        (2 * 4096 * 2 + 2 * 512 * 2 + 64 * 4
         + 3 * 4096 * 2 + 4 * 512 * 2 + 2 * 64 * 4) * 16384)
    assert nemotron_costs.held_gemm_cost(config, 5632) == (
        2 * 3 * 2 * 5632 * 1024 * 2688,
        2 * 3 * (5632 * 1024 + 8 * 1024 * 2688 + 5632 * 2688) * 2)
    # not Laguna's three matmuls at the stream's width
    from benchmark.roofline import laguna_costs
    assert laguna_costs.held_gemm_cost(config, 5632)[0] \
        == 6 * nemotron_costs.held_gemm_cost(config, 5632)[0]


def flash_costs_at_the_layers_own_heads_test():
    """16 query heads over 1 K/V head of 128 on 16,384 positions, the
    triangle 16,384 x 16,385 / 2 = 134,225,920 pairs: a matmul is 2 x 16 x
    128 a pair; the forward runs 2 and moves q, o at 16 heads and k, v at 1,
    the fused backward 5 with q, o, do, dq and k, v, dk, dv; the row
    statistics 2 x 16 x 16,384 float32.  Not the stream's 32 heads."""
    config = _doc()["config"]
    pairs = 16384 * 16385 // 2
    stats = 2 * 16 * 16384 * 4
    assert nemotron_costs.flash_cost("flash_fwd_causal", config) == (
        2 * 2 * 16 * 128 * pairs,
        (2 * 16 + 2 * 1) * 16384 * 128 * 2 + stats)
    assert nemotron_costs.flash_cost("flash_bwd_fused_causal", config) == (
        5 * 2 * 16 * 128 * pairs,
        (4 * 16 + 4 * 1) * 16384 * 128 * 2 + stats)
    assert nemotron_costs.flash_cost("flash_bwd_dq_causal", config)[0] \
        + nemotron_costs.flash_cost("flash_bwd_dkv_causal", config)[0] \
        == 7 * 2 * 16 * 128 * pairs
    # the triangle of the forward is the attention layer's required scores
    assert nemotron_costs.flash_cost("flash_fwd_causal", config)[0] \
        == 67_112_960 * 16384
    from benchmark.roofline import costs
    assert costs.KERNELS["flash_fwd"](1, 16384, config["heads"], 128)[0] \
        == 2 * nemotron_costs.flash_cost("flash_fwd_causal", config)[0]
    with pytest.raises(KeyError, match="only causal"):
        nemotron_costs.flash_cost("flash_fwd_window", config)
    with pytest.raises(KeyError, match="no cost function"):
        nemotron_costs.flash_cost("flash_other_causal", config)


def the_enumeration_matches_the_reference_jaxpr_test():
    """What the plain reference EXECUTES counted from its jaxpr at the
    rehearsal's toy shape: the whole square of scores, every held expert on
    every token, and — where the enumeration counts the chunked scan — the
    recurrence's one ``S C`` product a position (``2 d_inner n``; its state
    update is no matmul)."""
    with open(os.path.join(REPO, "benchmark", "workloads",
                           f"{CELL}.json")) as f:
        toy = json.load(f)["rehearsal"]["config"]
    config = {**_doc()["config"], **toy, "sequence_length": 32,
              "train_batch_size": 1, "vocab_size": 96}
    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.model import Model
    model = Model(ModelParameter(config))
    tokens = np.zeros((1, 32, 1), np.int32)
    variables = model.init({"token_x": tokens, "token_y": tokens}, seed=1)
    ref = importlib.import_module("benchmark.reference.nemotron_3_super_120b")
    counted = flops.forward_flops(
        lambda v, t: ref.train_loss(v, t, t, config), variables,
        tokens[..., 0])
    inner = config["mamba_heads"] * config["mamba_head_features"]
    chunked = nemotron_costs.scan_flops_per_token(config, executed=True)
    recurrence = 2 * inner * config["mamba_state"]
    assert counted == 32 * (
        nemotron_costs.forward_flops_per_token(config, executed=True)
        - nemotron_costs.count(config, "mamba") * (chunked - recurrence))
