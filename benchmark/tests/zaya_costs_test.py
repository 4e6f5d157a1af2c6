"""``roofline/zaya_costs.py`` against a hand count at the published widths
(ISSUE 39's numbers, from the config's keys) and a jaxpr count of the plain
reference at a toy size."""
import importlib
import json
import os

import numpy as np
import pytest

from benchmark.roofline import flops, zaya_costs

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _doc():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "zaya1_8b.json")) as f:
        return json.load(f)


def the_programs_widths_are_the_published_keys_test():
    doc = _doc()
    config = doc["config"]
    assert config["heads"] * config["features_per_head"] == doc["hidden_size"]
    assert zaya_costs.cca_heads(config) == (doc["num_attention_heads"],
                                            doc["num_key_value_heads"])
    assert zaya_costs._widths(config) == (
        doc["hidden_size"], doc["head_dim"], doc["moe_intermediate_size"],
        doc["router_hidden_size"])
    assert (config["cca_time0"], config["cca_time1"]) == (doc["cca_time0"],
                                                          doc["cca_time1"])
    assert config["moe_top_k"] == doc["num_experts_per_tok"] == 1
    assert config["experts_held"] == doc["num_experts"] == 8
    assert config["experts"] == doc["published"]["num_experts"] == 16
    kinds = [layer["kind"] for layer in zaya_costs.layers(config)]
    assert kinds == ["cca", "sparse"] * doc["num_hidden_layers"]
    assert zaya_costs.count(config, "cca") == zaya_costs.count(
        config, "sparse") == 8


def required_flops_at_the_published_widths_test():
    """d 2048, 8 query / 2 K/V heads of 128, 16,384 positions, 32,784 rows.
    CCA: Wq 2048x1024, Wk 2048x256, Wv1 and Wv2 2 x 2048x128, Wo 1024x2048 =
    5,242,880 weights = 10,485,760 FLOPs; the grouped conv 2 taps x 10 blocks
    x 128 x 128 x 2 = 655,360; scores and weighted values 2 x 2 x 1024 x
    8,192.5 = 33,556,480.  Router: 2 x (2048x256 + 2 x 256x256 + 256x16) =
    1,318,912.  One expert 3 x 2 x 2048 x 2048 = 25,165,824, at half the
    tokens.  Head 2 x 2048 x 32,784 = 134,283,264."""
    config = _doc()["config"]
    parts = zaya_costs.cca_flops_per_token(config)
    assert parts == {"projections": 10_485_760, "conv": 655_360,
                     "scores": pytest.approx(33_556_480)}
    cca = 10_485_760 + 655_360 + 33_556_480
    sparse = 1_318_912 + 25_165_824 / 2
    want = 8 * (cca + sparse) + 134_283_264
    assert zaya_costs.forward_flops_per_token(config) == pytest.approx(want)
    assert 602e6 < want < 604e6
    assert 0.58 < 8 * cca / want < 0.60           # CCA 59% of the step
    assert 0.75 < cca / (cca + sparse) < 0.77     # and 76% of a layer
    assert 0.21 < 134_283_264 / want < 0.23       # the head 22%
    assert zaya_costs.train_flops_per_token(config) == pytest.approx(3 * want)
    # the experts at the share really held
    assert zaya_costs.forward_flops_per_token(config, share=0.25) == \
        pytest.approx(want - 8 * 25_165_824 / 4)
    assert zaya_costs.held_share(config) == 0.5


def the_enumeration_matches_the_reference_jaxpr_test():
    """What the plain reference EXECUTES (the whole square of scores, every
    held expert on every token) counted from its jaxpr, at the rehearsal's
    toy shape."""
    with open(os.path.join(REPO, "benchmark", "workloads",
                           "train_zaya1_8b_ep2_s16k.json")) as f:
        toy = json.load(f)["rehearsal"]["config"]
    config = {**_doc()["config"], **toy, "sequence_length": 32,
              "train_batch_size": 1, "vocab_size": 80}
    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.model import Model
    model = Model(ModelParameter(config))
    tokens = np.zeros((1, 32, 1), np.int32)
    variables = model.init({"token_x": tokens, "token_y": tokens}, seed=1)
    ref = importlib.import_module("benchmark.reference.zaya1_8b")
    counted = flops.forward_flops(
        lambda v, t: ref.train_loss(v, t, t, config), variables,
        tokens[..., 0])
    assert counted == 32 * zaya_costs.forward_flops_per_token(
        config, executed=True)


def kernel_mix_and_gemm_costs_at_the_cells_shape_test():
    """A causal forward call at CCA's 8 QUERY heads (the stream has 16): 2
    matmuls x 2 x 8 x 128 x the triangle's pairs, 4 activations of 16,384 x
    8 x 128 x 2 bytes and the row statistics; the fused backward 5 matmuls
    and 8 activations.  The mixing: 3 x 655,360 x 16,384 FLOPs and (5 x 10 +
    4 x 2) x 128 x 2 bytes a token.  The held experts at 8,192 pairs: 9 x 2
    x 8192 x 2048 x 2048, each pass the rows at both widths and 8 experts'
    weights."""
    config = _doc()["config"]
    tri = 16384 * 16385 // 2
    assert zaya_costs.flash_cost("flash_fwd_causal", config) == (
        2 * 2 * 8 * 128 * tri, 4 * 16384 * 8 * 128 * 2 + 2 * 8 * 16384 * 4)
    assert zaya_costs.flash_cost("flash_bwd_fused_causal", config) == (
        5 * 2 * 8 * 128 * tri, 8 * 16384 * 8 * 128 * 2 + 2 * 8 * 16384 * 4)
    for kind in ("flash_fwd", "flash_fwd_window", "flash_other_causal"):
        with pytest.raises(KeyError):
            zaya_costs.flash_cost(kind, config)
    assert zaya_costs.cca_mix_cost(config) == (
        3 * 655_360 * 16384, 58 * 128 * 2 * 16384)
    assert zaya_costs.held_gemm_cost(config, 8192) == (
        9 * 2 * 8192 * 2048 * 2048,
        9 * (8192 * 2048 + 8 * 2048 * 2048 + 8192 * 2048) * 2)
