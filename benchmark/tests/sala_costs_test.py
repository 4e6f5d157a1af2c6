"""``roofline/sala_costs.py`` against a hand count at the published widths
(from the config's keys; ISSUE 46's count beside it) and the closed form of
the kept pairs against a brute count on a selection made of random scores."""
import json
import os

import numpy as np
import pytest

from benchmark.roofline import sala_costs

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _doc():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "minicpm_sala.json")) as f:
        return json.load(f)


def the_programs_widths_are_the_published_keys_test():
    doc = _doc()
    config = doc["config"]
    assert config["heads"] * config["features_per_head"] == doc["hidden_size"]
    assert config["features_per_head"] == doc["head_dim"]
    assert config["lightning_head_features"] == doc["lightning_head_dim"]
    mixers = sala_costs.mixers(config)
    assert mixers == [{"kind": "sparse", "q_heads": doc["num_attention_heads"],
                       "kv_heads": doc["num_key_value_heads"]}] \
        + [{"kind": "lightning"}] * 3
    assert [{"sparse": "minicpm4", "lightning": "lightning-attn"}[m["kind"]]
            for m in mixers] == doc["mixer_types"][:doc["num_hidden_layers"]]
    assert sala_costs.lightning_held(config) == doc["lightning_nh"] == 16
    assert (sala_costs.count(config, "sparse"),
            sala_costs.count(config, "lightning")) == (1, 3)


def required_flops_at_the_published_widths_test():
    """d 4096, i 16,384, 16,384 positions, 9,181 rows.  An MLP 3 x 2 x 4096 x
    16384 = 402,653,184.  Lightning: five 4096 x 2048 projections =
    83,886,080 (2 x the held 41.9 M parameters; the issue's 151 M counted
    more columns than the share holds) + the rule 16 heads x (2 x 2 x 128 x
    128.5 + 2 x 2 x 128 x 128) = 2,101,248.  Sparse: q, gate, out 3 x 2 x
    4096 x 2048 and k, v 2 x 2 x 4096 x 128 = 52,428,800; the indexer 2 x 16
    x 128 x 510.6 visible windows; attention 4 x 16 x 128 x 3,560.5 kept keys
    (a dense layer: 8,192.5).  Head 2 x 4096 x 9,181."""
    config = _doc()["config"]
    mlp, head = 402_653_184, 75_210_752
    assert sala_costs.rule_flops_per_token(config) == 2_101_248
    parts = sala_costs.sparse_flops_per_token(
        sala_costs.mixers(config)[0], config)
    assert parts["projections"] == 52_428_800
    assert parts["indexer"] == pytest.approx(2 * 16 * 128 * 510.5634765625)
    assert parts["attention"] == pytest.approx(4 * 16 * 128 * 3560.5)
    want = 4 * mlp + 3 * (83_886_080 + 2_101_248) + sum(parts.values()) + head
    assert sala_costs.forward_flops_per_token(config) == pytest.approx(want)
    assert 2.02e9 < want < 2.04e9
    assert 0.79 < 4 * mlp / want < 0.80            # the MLP, twice its share
    assert sala_costs.train_flops_per_token(config) == pytest.approx(
        3 * want - 2 * parts["indexer"])
    # a dense layer at this length would spend 67 M on scores and values
    dense = {**config, "sparse_dense_length": 16384}
    assert sala_costs.sparse_flops_per_token(
        sala_costs.mixers(dense)[0], dense) == {
            "projections": 52_428_800, "indexer": 0.0,
            "attention": pytest.approx(4 * 16 * 128 * 8192.5)}


def _brute(config, seed: int):
    """A selection as the program makes it, of RANDOM scores: the forced
    blocks +inf, blocks past the query's own -inf, the top-k by rank."""
    from homebrewnlp_tpu.model import sparse
    s, block = config["sequence_length"], config["sparse_block_size"]
    blocks = s // block
    rng = np.random.default_rng(seed)
    score = rng.normal(size=(s, blocks)).astype(np.float32)
    idx, own = np.arange(blocks)[None, :], (np.arange(s) // block)[:, None]
    forced = (idx < config["sparse_init_blocks"]) | (
        idx > own - max(1, config["sparse_window"] // block))
    score = np.where(forced, np.inf, score)
    score = np.where(idx <= own, score, -np.inf)
    return np.asarray(sparse.top_blocks(score, config["sparse_topk"]))


@pytest.mark.parametrize("sizes", [
    {}, {"sparse_topk": 9, "sparse_window": 64, "sparse_init_blocks": 2},
    {"sparse_block_size": 8, "sparse_kernel_stride": 8, "sparse_window": 8,
     "sparse_topk": 3},
    {"sparse_dense_length": 1024}], ids=["toy", "more", "small", "dense"])
def kept_pairs_are_a_closed_form_test(sizes):
    """How many blocks a query keeps does not depend on the scores: the
    closed form is the brute count of the pairs, the kept share the
    program's own gauge."""
    from homebrewnlp_tpu.model import sparse
    config = {**_doc()["config"], "sequence_length": 512,
              "sparse_kernel_size": 16, "sparse_kernel_stride": 8,
              "sparse_block_size": 16, "sparse_topk": 6,
              "sparse_init_blocks": 1, "sparse_window": 32,
              "sparse_dense_length": 128, **sizes}
    s, block = 512, config["sparse_block_size"]
    if not sala_costs.selects(config):
        assert sala_costs.kept_pairs(config) == s * (s + 1) // 2
        assert sala_costs.kept_key_share(config) == 1.0
        assert sala_costs.choosing_query_share(config) == 0.0
        return
    for seed in (0, 1):
        keep = _brute(config, seed)
        pairs = np.repeat(keep, block, axis=-1) \
            & (np.arange(s)[:, None] >= np.arange(s)[None, :])
        assert int(pairs.sum()) == sala_costs.kept_pairs(config)
        share, chose = sparse.kept_shares(keep[None, None], block)
        assert float(share) == pytest.approx(
            sala_costs.kept_key_share(config), rel=1e-6)
        assert float(chose) == pytest.approx(
            sala_costs.choosing_query_share(config))
    visible = sum(
        sum(config["sparse_kernel_stride"] * j + config["sparse_kernel_size"]
            <= t + 1 for j in range(
                (s - config["sparse_kernel_size"])
                // config["sparse_kernel_stride"] + 1)) for t in range(s))
    assert sala_costs.visible_pooled(config) == visible


def the_cells_shares_test():
    """At 16,384 keys of 256 blocks: queries past 4,096 choose (75%), a query
    keeps at most 63 x 64 + 64 keys, 59.39% of the visible ones in the
    mean and 43.46% of the triangle's pairs."""
    config = _doc()["config"]
    assert sala_costs.choosing_query_share(config) == 0.75
    assert sala_costs.kept_keys(4095, config) == 4096
    assert sala_costs.kept_keys(4096, config) == 63 * 64 + 1
    assert sala_costs.kept_keys(16383, config) == 4096
    assert sala_costs.kept_key_share(config) == pytest.approx(0.59388177)
    assert sala_costs.kept_pairs(config) / (16384 * 16385 / 2) \
        == pytest.approx(0.43460482)
    with pytest.raises(ValueError, match="forced"):
        sala_costs.kept_keys(9000, {**config, "sparse_topk": 32})


def kernel_and_rule_costs_at_the_cells_shape_test():
    """A selected forward call: 2 matmuls x 2 x 16 heads x 128 x the kept
    pairs; q and out at 16 heads, k and v at the ONE K/V head, the row
    statistics.  dq 3 matmuls, dk/dv 4.  The rule: 3 x 2,101,248 x 16,384
    FLOPs and 11 tensors of 2,048 x 2 bytes a token."""
    config = _doc()["config"]
    layer = sala_costs.mixers(config)[0]
    kept = sala_costs.kept_pairs(config)
    rows = 16384 * 128 * 2
    stats = 2 * 16 * 16384 * 4
    assert sala_costs.select_cost("flash_fwd_select", layer, config) == (
        2 * 2 * 16 * 128 * kept, (2 * 16 + 2 * 1) * rows + stats)
    assert sala_costs.select_cost("flash_bwd_dq_select", layer, config) == (
        3 * 2 * 16 * 128 * kept, (3 * 16 + 2 * 1) * rows + stats)
    assert sala_costs.select_cost("flash_bwd_dkv_select", layer, config) == (
        4 * 2 * 16 * 128 * kept, (2 * 16 + 4 * 1) * rows + stats)
    for kind in ("flash_fwd_causal", "flash_bwd_fused_select", "flash_fwd"):
        with pytest.raises(KeyError):
            sala_costs.select_cost(kind, layer, config)
    assert sala_costs.rule_cost(config) == (
        3 * 2_101_248 * 16384, 11 * 2048 * 2 * 16384)
    with pytest.raises(KeyError, match="no cost function for layer"):
        sala_costs.mixers({**config, "block_config": [
            {"layer": ["norm-rms-scale", "mamba"]}] * 2})
