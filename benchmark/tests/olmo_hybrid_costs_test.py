"""``roofline/olmo_hybrid_costs.py`` against a hand count at the cell's shape
and against a jaxpr count of the PROGRAM's forward (the plain reference runs
the recurrence position by position, so only the program executes the chunked
rule's matmuls)."""
import json
import os

import numpy as np

from benchmark.roofline import flops, olmo_hybrid_costs

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "olmo_hybrid_7b.json")) as f:
        return json.load(f)["config"]


def required_flops_at_the_published_widths_test():
    """d 3840, 30 heads x (96, 192), chunk 64, i 11,008, 16,384 positions,
    12,544 columns.  A gated delta-rule layer: in-projection 2 x 3840 x
    17,340 = 133,171,200; the rule 30 x (K K^T 2 x 96 x 31.5 + Q K^T, T K 2 x
    2 x 96 x 32.5 + the solve 64^2 / 3 + T V, (QK) V' 2 x 2 x 192 x 32.5 +
    three state products 3 x 2 x 96 x 192) = 30 x 155,445.33 = 4,663,360;
    out-projection 2 x 5760 x 3840 = 44,236,800.  The attention layer: four
    projections 117,964,800, scores and weighted values 2 x 2 x 3840 x
    8192.5 = 125,836,800.  An MLP 3 x 2 x 3840 x 11,008 = 253,624,320.  Head
    2 x 3840 x 12,544 = 96,337,920."""
    config = _config()
    assert olmo_hybrid_costs.rule_flops_per_token(config) == 4_663_360
    delta = 133_171_200 + 4_663_360 + 44_236_800
    attention = 117_964_800 + 125_836_800
    assert olmo_hybrid_costs.forward_flops_per_token(config) == \
        3 * delta + attention + 4 * 253_624_320 + 96_337_920 \
        == 1_900_850_880
    assert olmo_hybrid_costs.train_flops_per_token(config) \
        == 3 * 1_900_850_880
    assert olmo_hybrid_costs.delta_layers(config) == 3


def rule_cost_at_the_cells_shape_test():
    """16,384 tokens a layer a step: 3 x the forward's matmuls; forward q, k
    (2,880 each), v, o (5,760 each) at 2 bytes and beta, g (30 each) at 4 =
    34,800 bytes a token, backward q, k, dq, dk, v, do, dv at 2 and beta, g,
    dbeta, dg at 4 = 58,080."""
    got_flops, got_bytes = olmo_hybrid_costs.rule_cost(_config())
    assert got_flops == 3 * 4_663_360 * 16384 == 229_213_470_720
    assert got_bytes == (34_800 + 58_080) * 16384 == 1_521_745_920


def the_enumeration_matches_the_programs_jaxpr_test():
    """What the program EXECUTES in a forward on the CPU (whole chunks, the
    doubling solve's 2 log2(chunk) dense matmuls, the whole square of
    scores), from its jaxpr at a toy shape.  ``Model.apply`` makes the head
    matmul twice, for the logits and inside the fused head loss."""
    config = dict(_config(), depth=1, heads=4, features_per_head=8,
                  sequence_length=64, train_batch_size=1, delta_heads=3,
                  delta_key_features=8, delta_value_features=16,
                  delta_chunk=16, vocab_size=4224,
                  model_path="/tmp/olmo_hybrid_costs", dataset_configs=[])
    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.model import Model
    model = Model(ModelParameter(config))
    tokens = np.zeros((1, 64, 1), np.int32)
    batch = {"token_x": tokens, "token_y": tokens}
    variables = model.init(batch, seed=1)
    counted = flops.forward_flops(
        lambda v, b: model.apply(v, b).token_out.data, variables, batch)
    head = 2 * 32 * 4224      # above 4,096 rows the embedding is a gather
    assert counted == 64 * (olmo_hybrid_costs.forward_flops_per_token(
        config, executed=True) + head)
