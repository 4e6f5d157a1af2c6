"""``roofline/granite_costs.py`` against a hand count at the cell's shape and
against a jaxpr count of the PROGRAM's forward (the plain reference runs the
recurrence position by position, so only the program executes the chunked
scan's matmuls)."""
import json
import os

import numpy as np

from benchmark.roofline import flops, granite_costs

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "granite_4_0_h_micro.json")) as f:
        return json.load(f)["config"]


def required_flops_at_the_published_widths_test():
    """d 2048, d_inner 4096, n 128, 64 heads, chunk 256, i 8192, 8,192
    positions, 100,352 columns.  A Mamba layer: in-projection 2 x 2048 x 8512
    = 34,865,152; the scan 2 x 128 x 128.5 + 2 x 4096 x 128.5 + 4 x 4096 x
    128 = 3,182,720; out-projection 16,777,216.  The attention layer: q and o
    16,777,216, k and v (8 of 32 heads) 4,194,304, scores and weighted
    values 2 x 2 x 2048 x 4096.5 = 33,558,528.  An MLP 3 x 2 x 2048 x 8192 =
    100,663,296.  Head 2 x 2048 x 100,352 = 411,041,792."""
    config = _config()
    assert granite_costs.scan_flops_per_token(config) == 3_182_720
    mamba = 34_865_152 + 3_182_720 + 16_777_216
    attention = 16_777_216 + 4_194_304 + 33_558_528
    assert granite_costs.forward_flops_per_token(config) == \
        9 * mamba + attention + 10 * 100_663_296 + 411_041_792 \
        == 1_965_630_592
    assert granite_costs.train_flops_per_token(config) == 3 * 1_965_630_592
    assert granite_costs.mamba_layers(config) == 9


def scan_cost_at_the_cells_shape_test():
    """8,192 tokens a layer a step: 3 x the forward's matmuls; forward x, y
    (4096 each), B, C (128 each) at 2 bytes and dt (64) at 4 = 17,152 bytes a
    token, backward x, dy, dx, B, C, dB, dC at 2 and dt, ddt at 4 = 26,112."""
    got_flops, got_bytes = granite_costs.scan_cost(_config())
    assert got_flops == 3 * 3_182_720 * 8192 == 78_218_526_720
    assert got_bytes == (17_152 + 26_112) * 8192 == 354_418_688


def the_enumeration_matches_the_programs_jaxpr_test():
    """What the program EXECUTES in a forward on the CPU (whole chunks, the
    whole square of scores; K and V repeated changes no count), from its
    jaxpr at a toy shape.  ``Model.apply`` makes the head matmul twice, for
    the logits and inside the fused head loss."""
    config = dict(_config(), depth=1, heads=4, features_per_head=8,
                  sequence_length=64, train_batch_size=1, mamba_heads=4,
                  mamba_head_features=8, mamba_state=16, mamba_chunk=16,
                  vocab_size=4224, model_path="/tmp/granite_costs",
                  dataset_configs=[])
    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.model import Model
    model = Model(ModelParameter(config))
    tokens = np.zeros((1, 64, 1), np.int32)
    batch = {"token_x": tokens, "token_y": tokens}
    variables = model.init(batch, seed=1)
    counted = flops.forward_flops(
        lambda v, b: model.apply(v, b).token_out.data, variables, batch)
    head = 2 * 32 * 4224      # above 4,096 rows the embedding is a gather
    assert counted == 64 * (granite_costs.forward_flops_per_token(
        config, executed=True) + head)
