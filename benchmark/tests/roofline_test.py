"""Operations and bytes of the kernels and of the model against hand
counts at the two configurations' shapes, and the table of peaks."""
import importlib
import json
import os

import numpy as np
import pytest

from benchmark.roofline import costs, flops

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _config(name):
    with open(os.path.join(REPO, "benchmark", "configs", f"{name}.json")) as f:
        return json.load(f)["config"]


def peaks_are_the_published_v5e_figures_test():
    peak = costs.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["int8_ops_per_s"] == 393e12
    assert peak["hbm_bytes_per_s"] == 819e9
    with open(os.path.join(REPO, "benchmark", "roofline", "peaks.json")) as f:
        assert "cloud.google.com/tpu/docs/v5e" in json.load(f)["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def an_unknown_device_kind_is_an_error_test(kind):
    with pytest.raises(costs.UnknownDeviceKind):
        costs.peaks(kind)


# flagship, one chip: 32 sequences x 512 positions x 8 heads x 512 features.
# One causal map: 512 * 513 / 2 = 131,328 query-key pairs a head.
#   flops = 2 * 32 * 8 * 512 * 131,328            = 34,426,847,232
#   one activation  = 32 * 512 * 8 * 512 * 2 B    = 134,217,728 B
#   the map's triangle = 8 * 131,328 * 2 B        = 2,101,248 B
@pytest.mark.parametrize("kind", ["map_mixer_fwd_causal",
                                  "map_mixer_bwd_dval_causal",
                                  "map_mixer_bwd_dbias_causal"])
def map_mixer_at_the_flagship_shape_test(kind):
    assert costs.causal_pairs(512) == 131_328
    assert costs.kernel_cost(kind, 32, 512, 8, 512) == \
        (34_426_847_232, 2 * 134_217_728 + 2_101_248)


def map_mixer_on_the_four_chip_mesh_test():
    # {data: 2, model: 2} at batch 256: 128 sequences x 4 heads a chip —
    # twice the one-chip cell's flops and activations, half its map
    assert costs.kernel_cost("map_mixer_fwd_causal", 128, 512, 4, 512) == \
        (2 * 34_426_847_232, 2 * 268_435_456 + 1_050_624)


# long context, one chip: 1 sequence x 16,384 positions x 16 heads x 512.
# 16,384 * 16,385 / 2 = 134,225,920 pairs a head; a matmul costs
# 2 * 512 = 1,024 flops a pair a head: 16 * 134,225,920 * 1,024
#   = 2,199,157,473,280 flops a matmul
#   one activation = 16,384 * 16 * 512 * 2 B = 268,435,456 B
#   row statistics = 2 * 16 * 16,384 * 4 B   = 2,097,152 B
@pytest.mark.parametrize("kind,matmuls,tensors", [
    ("flash_fwd_causal", 2, 4), ("flash_bwd_dq_causal", 3, 5),
    ("flash_bwd_dkv_causal", 4, 6), ("flash_bwd_fused_causal", 5, 8)])
def flash_attention_at_the_long_context_shape_test(kind, matmuls, tensors):
    assert costs.causal_pairs(16_384) == 134_225_920
    assert costs.kernel_cost(kind, 1, 16_384, 16, 512) == \
        (matmuls * 2_199_157_473_280, tensors * 268_435_456 + 2_097_152)


@pytest.mark.parametrize("kind", ["flash_fwd", "some_other_kernel_causal"])
def a_kernel_without_a_cost_function_is_an_error_test(kind):
    with pytest.raises(KeyError):
        costs.kernel_cost(kind, 1, 128, 1, 128)


def which_peak_bounds_each_kernel_test():
    """The map mixer at sequence 512 is memory-bound by the table: 34.4
    GFLOP over 270.5 MB is 127 flops a byte, under the chip's ridge of
    197e12 / 819e9 = 240.5; flash attention at 16k is compute-bound."""
    peak = costs.peaks("TPU v5 lite")
    seconds, bound = costs.least_seconds(
        *costs.kernel_cost("map_mixer_fwd_causal", 32, 512, 8, 512), peak)
    assert bound == "memory"
    assert seconds == pytest.approx(270_536_704 / 819e9)
    seconds, bound = costs.least_seconds(
        *costs.kernel_cost("flash_fwd_causal", 1, 16_384, 16, 512), peak)
    assert bound == "compute"
    assert seconds == pytest.approx(2 * 2_199_157_473_280 / 197e12)
    # 34.4 GFLOP at 197 TFLOP/s
    assert costs.least_seconds(34_426_847_232, 1, peak)[0] == \
        pytest.approx(174.755e-6, rel=1e-4)
    assert costs.least_seconds(1, 819e9, peak) == (1.0, "memory")


# required forward FLOPs of one token, by hand
#   flagship: d 4096, bottleneck 512, widened 1024 a head, narrow table 64
#     in 2*64*4096 = 524,288; out 2*4096*256 = 2,097,152
#     group linear 2*4096*512 + 2*512*8192 + 2*8192*512 = 20,971,520
#     two causal maps 2 * 2*4096*256.5 = 4,202,496
#     32 * (20,971,520 + 4,202,496) + 2,621,440 = 808,189,952
#   long context at depth 8: d 8192, 16 heads
#     group linear 2*8192*512 + 2*512*16384 + 2*16384*512 = 41,943,040
#     attention 2*8192*512 + 6*512*8192 + 4*8192*8192.5 = 302,006,272
#     in 2*64*8192 = 1,048,576; out 2*8192*256 = 4,194,304
#     8 * 343,949,312 + 5,242,880 = 2,756,837,376
@pytest.mark.parametrize("name,forward", [
    ("32big_mixer", 808_189_952), ("1b_long_context_d8", 2_756_837_376)])
def required_model_flops_by_hand_test(name, forward):
    config = _config(name)
    assert costs.forward_flops_per_token(config) == forward
    assert costs.train_flops_per_token(config) == 3 * forward


@pytest.mark.parametrize("name", ["32big_mixer", "1b_long_context_d8"])
def the_enumeration_agrees_with_a_jaxpr_count_of_the_reference_test(name):
    """At a toy size the plain reference executes the full square (a dense
    masked matmul; one query block); counting its dots gives exactly the
    enumeration with ``mixing="square"`` — so the enumeration misses no
    matmul, and ``causal`` differs from it in the mixing term only."""
    config = dict(_config(name), depth=2, heads=4, features_per_head=32,
                  sequence_length=128)
    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.model import Model
    tokens = np.zeros((1, 128, 1), np.int32)
    variables = Model(ModelParameter(dict(config, train_batch_size=1))).init(
        {"token_x": tokens, "token_y": tokens})
    ref = importlib.import_module(f"benchmark.reference.{name}")
    counted = flops.forward_flops(
        lambda v: ref.forward(v, tokens[..., 0], config), variables)
    assert counted == 128 * costs.forward_flops_per_token(config, "square")
    assert costs.forward_flops_per_token(config) < \
        costs.forward_flops_per_token(config, "square")
