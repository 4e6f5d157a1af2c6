"""``roofline/kimi_costs.py`` against hand sums at the published widths and a
jaxpr count of the plain reference at a toy size."""
import importlib
import json
import os

import numpy as np
import pytest

from benchmark.roofline import flops, kimi_costs

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "train_kimi_linear_ep32_s16k"


def _doc():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "kimi_linear_48b_a3b.json")) as f:
        return json.load(f)


def required_flops_at_the_published_widths_test():
    """d 2304, 16,384 positions, 20,480 columns.  A KDA layer (32 heads of
    128 / 128, low rank 128, chunk 64): q, k, v 2 x 2304 x 12,288 =
    56,623,104, the decay pair and the gate pair 2 x (2304 x 128 + 128 x
    4096) = 1,638,400 each, beta 2 x 2304 x 32 = 147,456, out 2 x 4096 x
    2304 = 18,874,368; the rule a head 2 x 128 x 31.5 (K K^T) + 2 x 128 x
    32.5 (Q K^T) + 64^2 / 3 (the solve) + 2 x 128 x 32.5 (T K) + 2 x 2 x 128
    x 32.5 (T V, A' V') + 3 x 2 x 128 x 128 (the state) = 141,013.33, x 32.
    The latent layer (32 heads, key 192, value 128, latent 512): q 2 x 2304
    x 6144 = 28,311,552, down 2 x 2304 x 576 = 2,654,208, up 2 x 512 x 8192
    = 8,388,608, out 2 x 4096 x 2304 = 18,874,368, the triangle 32 x (2 x
    192 + 2 x 128) x 8192.5 = 167,782,400.  The dense MLP 3 x 2 x 2304 x
    9216 = 127,401,984.  A sparse layer: router 2 x 2304 x 256 = 1,179,648,
    shared 3 x 2 x 2304 x 1024 = 14,155,776, one expert the same at 8 x 8 /
    256 = 0.25.  Head 2 x 2304 x 20,480 = 94,371,840."""
    config = _doc()["config"]
    rule = 32 * (2 * 128 * 31.5 + 2 * 128 * 32.5 + 64 * 64 / 3
                 + 2 * 128 * 32.5 + 2 * 2 * 128 * 32.5 + 3 * 2 * 128 * 128)
    assert kimi_costs.rule_flops_per_token(config) == pytest.approx(rule)
    assert rule == pytest.approx(4_512_426.67)
    kda = 56_623_104 + 2 * 1_638_400 + 147_456 + 18_874_368 + rule
    assert kimi_costs.kda_flops_per_token(config) == pytest.approx(kda)
    latent = 28_311_552 + 2_654_208 + 8_388_608 + 18_874_368 + 167_782_400
    layer, = [x for x in kimi_costs.layers(config) if x["kind"] == "latent"]
    assert layer == {"kind": "latent", "heads": 32, "latent": 512,
                     "shared": 64}
    assert kimi_costs.latent_flops_per_token(config, layer) == latent
    assert kimi_costs.dense_flops_per_token(config) == 127_401_984
    parts = kimi_costs.sparse_parts_per_token(config)
    assert parts == {"router": 1_179_648, "shared": 14_155_776,
                     "held": 0.25 * 14_155_776}
    want = 4 * kda + latent + 127_401_984 + 4 * sum(parts.values()) \
        + 94_371_840
    assert kimi_costs.forward_flops_per_token(config) == pytest.approx(want)
    assert 8.5e8 < want < 8.6e8
    assert kimi_costs.train_flops_per_token(config) == pytest.approx(3 * want)
    assert [x["kind"] for x in kimi_costs.layers(config)] == [
        "kda", "dense", "kda", "sparse", "kda", "sparse", "latent", "sparse",
        "kda", "sparse"]
    assert (kimi_costs.count(config, "kda"), kimi_costs.count(
        config, "sparse"), kimi_costs.count(config, "latent")) == (4, 4, 1)
    # the whole model: 20 KDA, 7 latent, 1 dense and 26 sparse layers
    with open(os.path.join(REPO, "configs", "kimi_linear_48b_a3b.json")) as f:
        whole = json.load(f)
    assert [kimi_costs.count(whole, kind) for kind in
            ("kda", "latent", "dense", "sparse")] == [20, 7, 1, 26]


def rule_cost_at_the_cells_shape_test():
    """The rule a layer a step: 3 x the forward's operations x 16,384;
    bytes a token: forward q, k 2 x 4096 x 2 + v, o 2 x 4096 x 2 + beta 32 x
    4 + g 4096 x 4 (a float32 A CHANNEL), backward q, k, dq, dk 4 x 4096 x 2
    + v, do, dv 3 x 4096 x 2 + beta, dbeta, g, dg 2 x (32 + 4096) x 4."""
    config = _doc()["config"]
    ops, bytes_ = kimi_costs.rule_cost(config)
    assert ops == pytest.approx(3 * 4_512_426.67 * 16384, rel=1e-6)
    assert bytes_ == (4 * 4096 * 2 + (32 + 4096) * 4
                      + 7 * 4096 * 2 + 2 * (32 + 4096) * 4) * 16384
    assert bytes_ == 139_648 * 16384


def flash_costs_at_the_two_widths_test():
    """32 heads, key 192, value 128 on 16,384 positions, the triangle
    16,384 x 16,385 / 2 = 134,225,920 pairs.  The forward: one matmul at
    the key's width and one at the value's, q and k at 192, v and o at 128;
    the fused backward 3 + 2 over q, k, dq, dk | v, do, dv; the dq kernel 2
    + 1 over q, k, dq | v, do; the dk / dv kernel 2 + 2 over q, k, dk | v,
    do, dv; the row statistics 2 x 32 x 16,384 float32."""
    config = _doc()["config"]
    pairs = 16384 * 16385 // 2
    stats = 2 * 32 * 16384 * 4

    def at(key, value):
        return (key * 192 + value * 128) * 32 * 16384 * 2 + stats

    assert kimi_costs.flash_cost("flash_fwd_causal", config) == (
        (2 * 192 + 2 * 128) * 32 * pairs, at(2, 2))
    assert kimi_costs.flash_cost("flash_bwd_fused_causal", config) == (
        (3 * 2 * 192 + 2 * 2 * 128) * 32 * pairs, at(4, 3))
    assert kimi_costs.flash_cost("flash_bwd_dq_causal", config) == (
        (2 * 2 * 192 + 2 * 128) * 32 * pairs, at(3, 2))
    assert kimi_costs.flash_cost("flash_bwd_dkv_causal", config) == (
        (2 * 2 * 192 + 2 * 2 * 128) * 32 * pairs, at(3, 3))
    # the forward's triangle is the latent layer's required scores; the
    # generic cost at one width of 192 would credit the value's matmul at
    # 192 too
    assert kimi_costs.flash_cost("flash_fwd_causal", config)[0] \
        == 167_782_400 * 16384
    from benchmark.roofline import costs
    assert costs.KERNELS["flash_fwd"](1, 16384, 32, 192)[0] \
        > kimi_costs.flash_cost("flash_fwd_causal", config)[0]
    with pytest.raises(KeyError, match="only causal"):
        kimi_costs.flash_cost("flash_fwd_window", config)
    with pytest.raises(KeyError, match="no cost function"):
        kimi_costs.flash_cost("flash_other_causal", config)


def the_enumeration_matches_the_reference_jaxpr_test():
    """What the plain reference EXECUTES counted from its jaxpr at the
    rehearsal's toy shape: the whole square of scores, every held expert on
    every token, and the recurrence's two products with the state a
    position where the enumeration counts the chunked rule."""
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
    ref = importlib.import_module("benchmark.reference.kimi_linear_48b_a3b")
    counted = flops.forward_flops(
        lambda v, t: ref.train_loss(v, t, t, config), variables,
        tokens[..., 0])
    assert counted == 32 * kimi_costs.forward_flops_per_token(
        config, executed=True)


def the_chunk_counted_is_the_chunk_run_test():
    from homebrewnlp_tpu.model import kda
    assert kimi_costs.KDA_CHUNK == kda.CHUNK
