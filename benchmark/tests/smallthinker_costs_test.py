"""``roofline/smallthinker_costs.py`` against hand sums at the published
widths and a jaxpr count of the plain reference at a toy size."""
import importlib
import json
import os

import numpy as np

from benchmark.roofline import flops, smallthinker_costs

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "train_smallthinker_21b_ep8_s16k"


def _doc():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "smallthinker_21b_a3b.json")) as f:
        return json.load(f)


def the_live_pairs_are_the_masks_own_count_test():
    """A query sees ``min(i + 1, window)`` keys: summed by hand at a toy
    size, and the cell's two numbers (ISSUE 72: a windowed head scores 43.7%
    of a global one's pairs)."""
    for s, window in ((64, 16), (64, 1), (64, 64), (64, 100), (16384, 4096)):
        i, t = np.arange(s)[:, None], np.arange(s)[None, :]
        assert smallthinker_costs.live_pairs(
            {"sequence_length": s}, window) \
            == int(np.sum((t <= i) & (i - t < window)))
    config = _doc()["config"]
    assert smallthinker_costs.live_pairs(config, 4096) == 58_722_304
    assert smallthinker_costs.live_pairs(config, None) == 134_225_920
    assert smallthinker_costs.live_pairs(config, 16384) \
        == smallthinker_costs.live_pairs(config, None)


def required_flops_at_the_published_widths_test():
    """d 2,560, 28 / 4 heads of 128, 16,384 tokens, 18,992 columns.  A layer,
    a token: projections 2 x 2 x 2560 x 128 x (28 + 4) = 41,943,040; the live
    pairs 2 x 2 x 28 x 128 x pairs / 16,384 (a window layer 51,382,016, a
    global one 117,447,680); the router 2 x 2560 x 64 = 327,680; the held
    experts 6 x 8 / 64 of one expert's 3 x 2 x 2560 x 768 = 11,796,480."""
    config = _doc()["config"]
    layers = smallthinker_costs.attention_layers(config)
    assert [layer["window"] for layer in layers] \
        == [None, 4096, 4096, 4096] * 2
    assert all((layer["heads"], layer["kv_heads"]) == (28, 4)
               for layer in layers)
    assert smallthinker_costs.early_routers(config) \
        == smallthinker_costs.sparse_layers(config) == 8
    assert smallthinker_costs.attention_flops_per_token(layers[1], config) \
        == {"projections": 41_943_040, "pairs": 14336 * 58_722_304 / 16384}
    assert smallthinker_costs.attention_flops_per_token(layers[0], config) \
        == {"projections": 41_943_040, "pairs": 14336 * 134_225_920 / 16384}
    assert smallthinker_costs.router_flops_per_token(config) == 327_680
    assert smallthinker_costs.experts_flops_per_token(config) \
        == 0.75 * 11_796_480
    assert smallthinker_costs.head_flops_per_token(config) == 97_239_040
    parts = smallthinker_costs.forward_parts_per_token(config)
    assert parts == {
        "window_pairs": 6 * 14336 * 58_722_304 / 16384,
        "global_pairs": 2 * 14336 * 134_225_920 / 16384,
        "projections": 8 * 41_943_040, "routers": 8 * 327_680,
        "experts": 8 * 8_847_360, "head": 97_239_040}
    total = smallthinker_costs.forward_flops_per_token(config)
    assert total == sum(parts.values()) == 1_049_371_136
    assert smallthinker_costs.train_flops_per_token(config) == 3 * total
    # ISSUE 72's shares: the scores 52%, projections 32%, head 9%, experts 7%
    share = {k: round(100 * v / total) for k, v in parts.items()}
    assert share["window_pairs"] + share["global_pairs"] == 51 \
        and share["projections"] == 32 and share["head"] == 9 \
        and share["experts"] == 7
    # the whole model: 52 layers, all 64 experts held: still 6 a token
    with open(os.path.join(REPO, "configs", "smallthinker_21b_a3b.json")) as f:
        whole = json.load(f)
    assert len(smallthinker_costs.attention_layers(whole)) \
        == smallthinker_costs.sparse_layers(whole) == 52
    assert smallthinker_costs.experts_flops_per_token(whole) \
        == 6 * 11_796_480


def the_kernels_are_costed_by_their_own_kind_test():
    """A windowed call over the band's pairs, a causal one over the
    triangle's, both at 28 heads x 128: the forward's two matmuls a live
    pair, the fused backward's five; every tensor once."""
    config = _doc()["config"]
    unit = 16384 * 28 * 128 * 2
    stats = 2 * 28 * 16384 * 4
    for kind, pairs in (("window", 58_722_304), ("causal", 134_225_920)):
        flops_fwd, bytes_fwd = smallthinker_costs.flash_cost(
            f"flash_fwd_{kind}", config)
        assert flops_fwd == 2 * 2 * 28 * 128 * pairs
        assert bytes_fwd == 4 * unit + stats
        flops_bwd, bytes_bwd = smallthinker_costs.flash_cost(
            f"flash_bwd_fused_{kind}", config)
        assert flops_bwd == 5 * 2 * 28 * 128 * pairs
        assert bytes_bwd == 8 * unit + stats
    for kind in ("flash_fwd_select", "flash_fwd_blockdiff", "other_window"):
        try:
            smallthinker_costs.flash_cost(kind, config)
        except KeyError:
            continue
        raise AssertionError(kind)


def the_enumeration_matches_the_reference_jaxpr_test():
    """What the plain reference EXECUTES counted from its jaxpr at a toy
    shape — the whole square of scores in every layer, every held expert on
    every token, ONE router matmul a layer — is the enumeration's."""
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
    ref = importlib.import_module("benchmark.reference.smallthinker_21b_a3b")
    counted = flops.forward_flops(
        lambda v, t: ref.train_loss(v, t, t, config), variables,
        tokens[..., 0])
    assert counted == 32 * smallthinker_costs.forward_flops_per_token(
        config, executed=True)
