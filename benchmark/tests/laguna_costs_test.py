"""``roofline/laguna_costs.py`` against a hand count at the published
widths, a jaxpr count of the plain reference at a toy size, and brute-force
pair counts."""
import importlib
import json
import os

import numpy as np
import pytest

from benchmark.roofline import costs, flops, laguna_costs

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _doc():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "laguna_s_2_1.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("s,window", [(16, 4), (16, 16), (16, 40), (64, 1),
                                      (8192, 512)])
def band_and_triangle_pairs_by_brute_force_test(s, window):
    i, t = np.arange(s)[:, None], np.arange(s)[None, :]
    assert laguna_costs.band_pairs(s, window) == \
        int(((t <= i) & (i - t < window)).sum())
    assert costs.causal_pairs(s) == int((t <= i).sum())
    assert laguna_costs.band_pairs(s, s) == costs.causal_pairs(s)


def required_flops_at_the_published_widths_test():
    """d 3072, k 128, 8 K/V heads, 8,192 positions, 12,544 columns.  A
    window layer (72 heads): q and o 2 x 2 x 3072 x 9216 = 113,246,208, k
    and v 2 x 2 x 3072 x 1024 = 12,582,912, gate 2 x 3072 x 72 = 442,368,
    the band 2 x 2 x 9216 x (8192 x 512 - 512 x 511 / 2) / 8192.  A global
    layer (48): 75,497,472 + 12,582,912 + 294,912 and the triangle 2 x 2 x
    6144 x 4096.5.  Dense MLP 3 x 2 x 3072 x 12288 = 226,492,416; router
    2 x 3072 x 256 = 1,572,864; one expert 3 x 2 x 3072 x 1024 =
    18,874,368: the shared one whole, the routed ones 10 x 8 / 256.  Head
    2 x 3072 x 12,544 = 77,070,336."""
    config = _doc()["config"]
    band = 8192 * 512 - 512 * 511 // 2
    window = 113_246_208 + 12_582_912 + 442_368 + 4 * 9216 * band / 8192
    full = 75_497_472 + 12_582_912 + 294_912 + 4 * 6144 * 4096.5
    sparse = 1_572_864 + 18_874_368 * (1 + 10 * 8 / 256)
    want = 2 * full + 3 * window + 226_492_416 + 4 * sparse + 77_070_336
    assert laguna_costs.forward_flops_per_token(config) == pytest.approx(want)
    assert 1.21e9 < want < 1.23e9
    assert laguna_costs.train_flops_per_token(config) == pytest.approx(3 * want)
    kinds = [layer["kind"] for layer in laguna_costs.layers(config)]
    assert kinds == ["attention", "dense"] + ["attention", "sparse"] * 4
    assert laguna_costs.flash_heads(config, True) == (72, 512)
    assert laguna_costs.flash_heads(config, False) == (48, None)


def the_enumeration_matches_the_reference_jaxpr_test():
    """What the plain reference EXECUTES (the whole square of scores in
    every layer, every held expert on every token) counted from its
    jaxpr, at the rehearsal's toy shape."""
    with open(os.path.join(REPO, "benchmark", "workloads",
                           "train_laguna_s_2_1_ep32_s8k.json")) as f:
        toy = json.load(f)["rehearsal"]["config"]
    config = {**_doc()["config"], **toy, "sequence_length": 32,
              "train_batch_size": 1, "vocab_size": 96}
    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.model import Model
    model = Model(ModelParameter(config))
    tokens = np.zeros((1, 32, 1), np.int32)
    variables = model.init({"token_x": tokens, "token_y": tokens}, seed=1)
    ref = importlib.import_module("benchmark.reference.laguna_s_2_1")
    counted = flops.forward_flops(
        lambda v, t: ref.train_loss(v, t, t, config), variables,
        tokens[..., 0])
    assert counted == 32 * laguna_costs.forward_flops_per_token(
        config, executed=True)


def kernel_and_gemm_costs_at_the_cells_shape_test():
    """A windowed forward call: 2 matmuls x 2 x 2 x 72 x 128 x the band's
    pairs, 4 activations of 2 x 8192 x 72 x 128 x 2 bytes and the row
    statistics; a causal fused backward: 5 matmuls over the triangle at 48
    heads, 8 activations.  The held experts at 5,120 pairs: 9 x 2 x 5120 x
    3072 x 1024, each pass the rows at both widths and 8 experts' weights."""
    config = _doc()["config"]
    band, tri = 8192 * 512 - 512 * 511 // 2, 8192 * 8193 // 2
    assert laguna_costs.flash_cost("flash_fwd_window", config) == (
        2 * 2 * 2 * 72 * 128 * band,
        4 * 2 * 8192 * 72 * 128 * 2 + 2 * 2 * 72 * 8192 * 4)
    assert laguna_costs.flash_cost("flash_bwd_fused_causal", config) == (
        5 * 2 * 2 * 48 * 128 * tri,
        8 * 2 * 8192 * 48 * 128 * 2 + 2 * 2 * 48 * 8192 * 4)
    with pytest.raises(KeyError):
        laguna_costs.flash_cost("flash_fwd", config)
    got = laguna_costs.held_gemm_cost(config, 5120)
    assert got == (9 * 2 * 5120 * 3072 * 1024,
                   9 * (5120 * 3072 + 8 * 3072 * 1024 + 5120 * 1024) * 2)
