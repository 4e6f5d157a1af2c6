"""``roofline/sdar_costs.py`` against hand sums at the published widths and a
jaxpr count of the plain reference at a toy size."""
import importlib
import json
import os

import numpy as np

from benchmark.roofline import flops, sdar_costs

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "train_sdar_30b_a3b_ep8_s8k"


def _doc():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "sdar_30b_a3b.json")) as f:
        return json.load(f)


def required_flops_at_the_published_widths_test():
    """d 2048, 8,192 trained tokens a sequence (a stream of 16,384), block 4,
    18,992 columns.  A layer, a TRAINED token: the projections over both
    halves 2 x 2 x 2048 x 128 x (2 x 32 + 2 x 4) = 75,497,472; the live
    pairs (8,192 + 4) x 32 heads x (2 x 128 + 2 x 128) = 134,283,264; the
    sparse layer over both halves 2 x (router 2 x 2048 x 128 = 524,288 + one
    expert's 3 x 2 x 2048 x 768 = 9,437,184 at 8 x 16 / 128 = 1) =
    19,922,944.  The head 2 x 2048 x 18,992 = 77,791,232, once."""
    config = _doc()["config"]
    layers = sdar_costs.attention_layers(config)
    assert layers == [{"q_heads": 32, "kv_heads": 4}] * 7
    assert sdar_costs.sparse_layers(config) == 7
    assert sdar_costs.live_pairs(config) == 8192 * 8196
    assert sdar_costs.layer_flops_per_token(layers[0], config) \
        == {"projections": 75_497_472, "attention": 134_283_264}
    assert sdar_costs.sparse_flops_per_token(config) == 19_922_944
    assert sdar_costs.head_flops_per_token(config) == 77_791_232
    want = 7 * (75_497_472 + 134_283_264 + 19_922_944) + 77_791_232
    assert sdar_costs.forward_flops_per_token(config) == want \
        == 1_685_716_992
    assert sdar_costs.train_flops_per_token(config) == 3 * want
    # the whole model: 48 layers of each kind, all 128 experts held: still 8
    # a token
    with open(os.path.join(REPO, "configs", "sdar_30b_a3b.json")) as f:
        whole = json.load(f)
    assert len(sdar_costs.attention_layers(whole)) \
        == sdar_costs.sparse_layers(whole) == 48
    assert sdar_costs.sparse_flops_per_token(whole) \
        == 2 * (524_288 + 8 * 9_437_184)


def the_kernels_are_costed_at_the_whole_masks_live_pairs_test():
    """One call a layer over both halves: the forward's two matmuls a live
    pair, the fused backward's five, at 32 heads x 128; q and out (and their
    gradients) over 16,384 positions, K and V over the clean half's 8,192."""
    config = _doc()["config"]
    layer = sdar_costs.attention_layers(config)[0]
    pairs = 8192 * 8196
    flops_fwd, bytes_fwd = sdar_costs.flash_cost(
        "flash_fwd_blockdiff", layer, config)
    assert flops_fwd == 2 * 2 * 32 * 128 * pairs == 134_283_264 * 8192
    unit = 8192 * 32 * 128 * 2
    assert bytes_fwd == (2 * 2 + 2) * unit + 2 * 32 * 16384 * 4
    flops_bwd, bytes_bwd = sdar_costs.flash_cost(
        "flash_bwd_fused_blockdiff", layer, config)
    assert flops_bwd == 5 * 2 * 32 * 128 * pairs
    assert bytes_bwd == (2 * 4 + 4) * unit + 2 * 32 * 16384 * 4
    for kind in ("flash_bwd_dq_blockdiff", "flash_bwd_dkv_blockdiff"):
        assert sdar_costs.flash_cost(kind, layer, config)[0] > flops_fwd
    for kind in ("flash_fwd_causal", "flash_fwd_select", "other_blockdiff"):
        try:
            sdar_costs.flash_cost(kind, layer, config)
        except KeyError:
            continue
        raise AssertionError(kind)


def the_enumeration_matches_the_reference_jaxpr_test():
    """What the plain reference EXECUTES counted from its jaxpr at a toy
    shape — the whole ``[2 L, 2 L]`` square of scores, every held expert on
    every position of the doubled stream, the head over the noised half — is
    the enumeration's, a trained token."""
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
    ref = importlib.import_module("benchmark.reference.sdar_30b_a3b")
    counted = flops.forward_flops(
        lambda v, t: ref.train_loss(v, t, t, config), variables,
        tokens[..., 0])
    assert counted == 32 * sdar_costs.forward_flops_per_token(
        config, executed=True)
