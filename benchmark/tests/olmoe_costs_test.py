"""``roofline/olmoe_costs.py`` against a jaxpr count of the plain reference,
and the expert matmuls' cost against a hand count at the cell's shape."""
import importlib
import json
import os

import jax
import numpy as np

from benchmark.roofline import flops, olmoe_costs

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "olmoe_1b_7b.json")) as f:
        return json.load(f)["config"]


def required_flops_at_the_published_widths_test():
    """Depth 2, d 2048, i 1024, 8 of 64 experts, 4,096 positions, 50,304
    columns.  Per layer: projections 4 x 2 x 2048^2 = 33,554,432; scores
    and weighted values 2 x 2 x 2048 x 2048.5 = 16,781,312; router
    2 x 2048 x 64 = 262,144; experts 8 x 3 x 2 x 2048 x 1024 = 100,663,296.
    Head 2 x 2048 x 50,304 = 206,045,184."""
    config = _config()
    layer = 33_554_432 + 16_781_312 + 262_144 + 100_663_296
    assert olmoe_costs.forward_flops_per_token(config) == \
        2 * layer + 206_045_184 == 508_567_552
    assert olmoe_costs.train_flops_per_token(config) == 3 * 508_567_552


def the_enumeration_matches_the_reference_jaxpr_test():
    """What the plain reference EXECUTES (the whole square of scores, every
    expert on every token) counted from its jaxpr, at a toy shape."""
    config = dict(_config(), depth=2, heads=2, features_per_head=16,
                  sequence_length=32, train_batch_size=1, experts=4,
                  moe_top_k=2, vocab_size=96)
    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.model import Model
    model = Model(ModelParameter(config))
    tokens = np.zeros((1, 32, 1), np.int32)
    variables = model.init({"token_x": tokens, "token_y": tokens}, seed=1)
    ref = importlib.import_module("benchmark.reference.olmoe_1b_7b")
    counted = flops.forward_flops(
        lambda v, t: ref.forward(v, t, config), variables, tokens[..., 0])
    assert counted == 32 * olmoe_costs.forward_flops_per_token(
        config, executed=True)


def expert_gemm_cost_at_the_cells_shape_test():
    """m = 2 x 4,096 x 8 = 65,536 rows: 3 matmuls x 3 passes x 2 x 65,536 x
    2048 x 1024 operations a layer a step; each pass moves the rows at both
    widths and all 64 experts' weights once, 2 bytes an element."""
    metric = importlib.import_module(
        "benchmark.metrics.moe_expert_gemm_roofline")
    got_flops, got_bytes = metric.expert_gemm_cost(_config())
    assert got_flops == 9 * 2 * 65_536 * 2048 * 1024 == 2_473_901_162_496
    one = (65_536 * 2048 + 64 * 2048 * 1024 + 65_536 * 1024) * 2
    assert got_bytes == 9 * one == 6_039_797_760
