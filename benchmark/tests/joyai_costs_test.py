"""``roofline/joyai_costs.py`` against hand sums at the published widths, a
jaxpr count of the plain reference at a toy size, and
``kimi_costs.flash_cost`` read through this configuration's layer strings."""
import importlib
import json
import os

import numpy as np

from benchmark.roofline import flops, joyai_costs, kimi_costs

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "train_joyai_llm_flash_ep16_s16k"


def _doc(name: str = "joyai_llm_flash"):
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def required_flops_at_the_published_widths_test():
    """d 2048, 16,384 positions, 16,160 columns.  A latent attention layer
    (32 heads, key 192, value 128, K/V latent 512, query latent 1,536): the
    query down 2 x 2048 x 1536 = 6,291,456 and up 2 x 1536 x 6144 =
    18,874,368, K/V down 2 x 2048 x 576 = 2,359,296, up 2 x 512 x 8192 =
    8,388,608, out 2 x 4096 x 2048 = 16,777,216, the triangle 32 x (2 x 192 +
    2 x 128) x 8192.5 = 167,782,400.  The dense MLP 3 x 2 x 2048 x 7168 =
    88,080,384.  A sparse layer: router 2 x 2048 x 256 = 1,048,576, shared 3
    x 2 x 2048 x 768 = 9,437,184, one expert the same at 8 x 16 / 256 = 0.5.
    The head 2 x 2048 x 16,160 = 66,191,360, TWICE; the module's join 2 x
    4096 x 2048 = 16,777,216.  Seven latent layers (layer 0, five, the
    module's), six sparse, one dense."""
    config = _doc()["config"]
    latent = 6_291_456 + 18_874_368 + 2_359_296 + 8_388_608 + 16_777_216 \
        + 167_782_400
    found = joyai_costs.layers(config)
    assert [x["kind"] for x in found] == ["latent", "dense"] \
        + ["latent", "sparse"] * 6
    assert found[0] == {"kind": "latent", "heads": 32, "latent": 512,
                        "shared": 64, "q_latent": 1536}
    assert joyai_costs.latent_flops_per_token(config, found[0]) == latent \
        == 220_473_344
    assert kimi_costs.dense_flops_per_token(config) == 88_080_384
    parts = kimi_costs.sparse_parts_per_token(config)
    assert parts == {"router": 1_048_576, "shared": 9_437_184,
                     "held": 0.5 * 9_437_184}
    assert joyai_costs.head_flops_per_token(config) == 2 * 66_191_360
    assert joyai_costs.join_flops_per_token(config) == 16_777_216
    want = 7 * latent + 88_080_384 + 6 * sum(parts.values()) \
        + 2 * 66_191_360 + 16_777_216
    assert joyai_costs.forward_flops_per_token(config) == want \
        == 1_871_779_840
    assert joyai_costs.train_flops_per_token(config) == 3 * want
    assert [joyai_costs.count(config, kind) for kind in
            ("latent", "sparse", "dense")] == [7, 6, 1]
    # the whole model: 41 latent layers (40 + the module's), 40 sparse, 1 dense
    with open(os.path.join(REPO, "configs", "joyai_llm_flash.json")) as f:
        whole = json.load(f)
    assert [joyai_costs.count(whole, kind) for kind in
            ("latent", "sparse", "dense")] == [41, 40, 1]
    # without a query latent the layer is Kimi-Linear's
    plain = dict(found[0], q_latent=0)
    assert joyai_costs.latent_flops_per_token(config, plain) \
        == kimi_costs.latent_flops_per_token(config, plain)


def the_flash_calls_are_kimi_linears_test():
    """``kimi_costs.flash_cost`` reads this configuration's layer strings
    (``rope``, ``theta<t>`` and ``q_latent<cq>`` beside the numbers it knows)
    and its body block, and gives Kimi-Linear's numbers: the calls are the
    same ``[32, 16384, 192 / 128]``."""
    config, kimi = _doc()["config"], _doc("kimi_linear_48b_a3b")["config"]
    for kind in ("flash_fwd_causal", "flash_bwd_fused_causal",
                 "flash_bwd_dq_causal", "flash_bwd_dkv_causal"):
        assert kimi_costs.flash_cost(kind, config) \
            == kimi_costs.flash_cost(kind, kimi)
    pairs = 16384 * 16385 // 2
    assert kimi_costs.flash_cost("flash_fwd_causal", config)[0] \
        == (2 * 192 + 2 * 128) * 32 * pairs == 167_782_400 * 16384


def the_enumeration_matches_the_reference_jaxpr_test():
    """What the plain reference EXECUTES counted from its jaxpr at the
    rehearsal's toy shape — the whole square of scores, every held expert on
    every token, both head passes, the join — is the enumeration's."""
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
    ref = importlib.import_module("benchmark.reference.joyai_llm_flash")
    counted = flops.forward_flops(
        lambda v, t: ref.train_loss(v, t, t, config), variables,
        tokens[..., 0])
    assert counted == 32 * joyai_costs.forward_flops_per_token(
        config, executed=True)
