"""``roofline/ouro_costs.py`` against a hand count at the published widths
and a jaxpr count of the plain reference, and the four readers on a run the
parent's program would give them."""
import importlib
import json
import os
import types

import numpy as np

from benchmark.roofline import flops, ouro_costs

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "ouro_2_6b.json")) as f:
        return json.load(f)["config"]


def required_flops_at_the_published_widths_test():
    """d 2048, i 5632, 4,096 positions, 49,152 columns, 12 layers, 4 passes.
    A layer application: projections 4 x 2 x 2048^2 = 33,554,432; the MLP
    3 x 2 x 2048 x 5632 = 69,206,016; scores and weighted values 2 x 2 x
    2048 x 2048.5 = 16,781,312.  A head pass 2 x 2048 x 49,152 =
    201,326,592."""
    config = _config()
    layer = 33_554_432 + 69_206_016 + 16_781_312
    assert ouro_costs.layer_flops_per_token(config) == layer == 119_541_760
    assert ouro_costs.head_flops_per_token(config) == 201_326_592
    assert ouro_costs.forward_flops_per_token(config) == \
        4 * (12 * layer + 201_326_592) == 6_543_310_848
    assert ouro_costs.train_flops_per_token(config) == 3 * 6_543_310_848


def the_enumeration_matches_the_reference_jaxpr_test():
    """What the plain reference EXECUTES (the whole square of scores, every
    pass's head) counted from its jaxpr, at a toy shape."""
    config = dict(_config(), depth=2, heads=2, features_per_head=16,
                  sequence_length=32, train_batch_size=1, vocab_size=96,
                  intermediate_feed_forward_multiplier=2.75)
    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.model import Model
    model = Model(ModelParameter(config))
    tokens = np.zeros((1, 32, 1), np.int32)
    variables = model.init({"token_x": tokens, "token_y": tokens}, seed=1)
    ref = importlib.import_module("benchmark.reference.ouro_2_6b")
    counted = flops.forward_flops(
        lambda v, t: ref.outputs(v, t, t, config)["loss"], variables,
        tokens[..., 0])
    assert counted == 32 * ouro_costs.forward_flops_per_token(
        config, executed=True)


def the_readers_return_nothing_on_an_unlooped_run_test():
    """A program that makes no pass region, no gate scope and no gauge (a
    parent of PR 49) gives the four readers nothing to read: None, and no
    exception."""
    run = types.SimpleNamespace(
        config={"heads": 16, "features_per_head": 128},
        result=types.SimpleNamespace(
            end_to_end={"train_tokens_per_sec_chip": 1.0},
            device={"kind": "TPU v5 lite"}, trace_path=None),
        trace=None, notes=[], cell=None)
    for name in ("ouro_mfu_required", "scope_loop_time_share",
                 "scope_exit_gate_time_share", "loop_exit_entropy"):
        metric = importlib.import_module("benchmark.metrics." + name)
        assert metric.read(run) is None, name
