"""``roofline/keye_costs.py`` against a hand count at the published widths
(from the config's keys; ISSUE 62's count beside it), the closed forms of the
kept pairs against a brute count, and the file's parameter count against the
shapes the program builds."""
import json
import os

import numpy as np
import pytest

from benchmark.roofline import keye_costs

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _doc():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "keye_vl_2_0_30b_a3b.json")) as f:
        return json.load(f)


def the_programs_widths_are_the_published_keys_test():
    doc = _doc()
    config, sa = doc["config"], doc["sa_config"]
    assert config["heads"] * config["features_per_head"] \
        == doc["hidden_size"] == 2048
    assert config["features_per_head"] == doc["head_dim"] == 128
    layers = keye_costs.attention_layers(config)
    assert layers == [{"q_heads": doc["num_attention_heads"],
                       "kv_heads": doc["num_key_value_heads"]}] \
        * doc["num_hidden_layers"]
    assert (config["index_heads"], config["index_features"],
            config["index_topk"]) == (sa["indexer_num_heads"],
                                      sa["indexer_head_dim"], sa["topk"]) \
        == (16, 64, 2048)
    assert config["expert_width"] == doc["moe_intermediate_size"] == 768
    assert (config["experts"], config["moe_top_k"], config["experts_held"]) \
        == (doc["num_local_experts"], doc["num_experts_per_tok"],
            doc["num_experts"]) == (128, 8, 16)
    assert config["moe_norm_topk"] is doc["norm_topk_prob"] is True
    assert config["rope_theta"] == doc["rope_theta"] == 10_000_000
    assert config["norm_epsilon"] == doc["rms_norm_eps"]
    assert config["vocab_size"] == doc["vocab_size"] == 151936 // 8
    assert keye_costs.sparse_layers(config) == config["depth"] \
        == doc["num_hidden_layers"]
    # every published number the cut did not touch stands as published
    for key, value in doc["published"].items():
        assert doc[key] != value and key in doc["reduced"], key


def parameter_count_is_the_issues_test():
    """A layer 96,899,456 (ISSUE 62), the tables 77,791,232 + the final
    norm; the file states the sum beside the program's own count."""
    doc = _doc()
    layer = doc["parameters"]["a_layer"]
    assert sum(layer.values()) == 96_899_456
    assert layer["indexer"] == 2048 * 1024 + 2048 * 64 + 128 + 2048 * 16
    counted = doc["num_hidden_layers"] * 96_899_456 \
        + doc["parameters"]["tables_and_final_norm"]
    assert doc["parameters"]["counted"] == counted \
        == doc["parameters"]["program"]
    if doc["num_hidden_layers"] == 6:
        assert counted == doc["parameters"]["issue_62"] == 659_190_016


def required_flops_at_the_published_widths_test():
    """d 2048, 16,384 positions, 18,992 rows.  Projections 2 x 2048 x 128 x
    (2 x 32 + 2 x 4) = 37,748,736.  A query keeps min(t + 1, 2048) keys:
    1,920.0625 in the mean, 8,192.5 visible.  Attention 4 x 32 x 128 x
    1,920.0625; the indexer's projections 2 x 2048 x (1024 + 64 + 16) =
    4,521,984 and its scores 2 x 16 x 64 x 8,192.5; the loss's second QK 2 x
    32 x 128 x 1,920.0625; the router 2 x 2048 x 128 and one expert's worth
    (8 x 16 / 128) of 3 x 2 x 2048 x 768; the head 2 x 2048 x 18,992."""
    config = _doc()["config"]
    s = config["sequence_length"]
    assert keye_costs.kept_pairs(config) / s == 1920.0625
    assert keye_costs.visible_pairs(config) / s == 8192.5
    parts = keye_costs.layer_flops_per_token(
        keye_costs.attention_layers(config)[0], config)
    assert parts == {"projections": 37_748_736,
                     "attention": 4 * 32 * 128 * 1920.0625,
                     "index_projections": 4_521_984,
                     "index_scores": 2 * 16 * 64 * 8192.5,
                     "index_loss": 2 * 32 * 128 * 1920.0625}
    sparse = keye_costs.sparse_flops_per_token(config)
    assert sparse == 2 * 2048 * 128 + 3 * 2 * 2048 * 768 == 9_961_472
    layer = sum(parts.values()) + sparse
    # ISSUE 62's ~38 + ~31 + ~21 + ~16 + ~10 M a layer
    assert 115e6 < layer < 117e6
    want = config["depth"] * layer + 2 * 2048 * 18_992
    assert keye_costs.forward_flops_per_token(config) == pytest.approx(want)
    # the new mechanism (indexer, kept pairs, the loss's QK) is over half
    new = parts["attention"] + parts["index_projections"] \
        + parts["index_scores"] + parts["index_loss"]
    assert 0.58 < new / layer < 0.60
    assert keye_costs.train_flops_per_token(config) == pytest.approx(
        3 * want - 2 * config["depth"] * parts["index_loss"])


def closed_forms_are_a_brute_count_test():
    """On a selection made of random scores at toy sizes: the kept pairs, the
    mean kept share of a query's visible keys and the share of queries that
    left a key out."""
    config = {"sequence_length": 96, "index_topk": 20}
    rng = np.random.default_rng(0)
    score = rng.normal(size=(96, 96))
    keep = np.zeros((96, 96), bool)
    for t in range(96):
        order = np.argsort(-score[t, :t + 1], kind="stable")
        keep[t, order[:20]] = True
    assert keep.sum() == keye_costs.kept_pairs(config)
    assert [keye_costs.kept_keys(t, config) for t in (0, 19, 20, 95)] \
        == [1, 20, 20, 20]
    share = np.mean(keep.sum(-1) / np.arange(1, 97))
    assert keye_costs.kept_key_share(config) == pytest.approx(share)
    assert keye_costs.choosing_query_share(config) \
        == np.mean(keep.sum(-1) < np.arange(1, 97)) == 76 / 96
    # the cell: 38.5% of a query's visible keys, 23.4% of all visible pairs
    cell = _doc()["config"]
    assert 0.384 < keye_costs.kept_key_share(cell) < 0.386
    assert 0.234 < keye_costs.kept_pairs(cell) \
        / keye_costs.visible_pairs(cell) < 0.235
    assert keye_costs.choosing_query_share(cell) == 0.875


def select_kernels_cost_the_kept_pairs_test():
    """A call's matmuls over the kept pairs only (2, 3, 4 a pair: forward,
    dq, dk/dv), q-sized tensors at 32 heads, K/V-sized at 4, the float32 row
    statistics and the choice's bits once."""
    config = _doc()["config"]
    layer = keye_costs.attention_layers(config)[0]
    pairs = keye_costs.kept_pairs(config)
    rows = 16_384 * 128 * 2
    stats, bits = 2 * 32 * 16_384 * 4, 16_384 * 16_384 // 8
    for kind, matmuls, wide, narrow in (("flash_fwd_select", 2, 2, 2),
                                        ("flash_bwd_dq_select", 3, 3, 2),
                                        ("flash_bwd_dkv_select", 4, 2, 4)):
        flops, bytes_ = keye_costs.select_cost(kind, layer, config)
        assert flops == matmuls * 2 * 32 * 128 * pairs
        assert bytes_ == (wide * 32 + narrow * 4) * rows + stats + bits
    with pytest.raises(KeyError, match="only selected calls"):
        keye_costs.select_cost("flash_fwd_causal", layer, config)
    with pytest.raises(KeyError, match="no cost function"):
        keye_costs.attention_layers(
            {**config, "block_config": [{"layer": ["attention-rope"]}]})
