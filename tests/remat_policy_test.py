"""Measured remat policy (``remat_policy``, model/remat.py).

Every policy executes the SAME primal recurrence: losses match exactly and
updated parameters agree to reconstruction ulps (the tolerance class the
stash tests established).  ``auto`` resolution is pinned: explicit values
pass through, the legacy ``stash_attention_outputs`` boolean maps onto
stash/recompute, the long-context stash rule still fires, and short-context
default resolves to recompute (the round-11 A/B measured the save modes
SLOWER on the memory-bound rig — auto must not silently adopt them).
"""
import contextlib
import functools
import typing

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from backend import make_params
from homebrewnlp_tpu.model import Model
from homebrewnlp_tpu.model.remat import (STASH_KINDS, remat_report,
                                         resolve_remat)
from homebrewnlp_tpu.train import Trainer

#: no kind engaged
_IDLE = {kind: (0, 0) for kind in STASH_KINDS}

_CFG = dict(sequence_length=32, features_per_head=16, heads=2, depth=2,
            train_batch_size=4, vocab_size=64,
            optimizer="momentum:0.9:1:1-learning_rate", learning_rate=0.01)


def _step(policy, strategy, scan):
    params = make_params(memory_reduction_strategy=strategy,
                         scan_layers=scan, remat_policy=policy, **_CFG)
    model = Model(params)
    trainer = Trainer(params, model)
    rng = np.random.default_rng(0)
    x = rng.integers(0, params.vocab_size,
                     (params.train_batch_size, params.sequence_length, 1))
    batch = {"token_x": jnp.asarray(x),
             "token_y": jnp.asarray((x + 1) % params.vocab_size)}
    state = trainer.init_state(batch)
    state, metrics = trainer.step(state, batch, jax.random.PRNGKey(0))
    return state, metrics


@pytest.mark.parametrize("strategy", ["revnet", "momentum"])
@pytest.mark.parametrize("scan", [True, False])
@pytest.mark.parametrize("policy", ["save", "save_dots"])
def save_policy_parity_test(strategy, scan, policy):
    """save/save_dots vs the recompute default: identical loss (same
    primal), same updated params to reconstruction ulps — scanned and
    unrolled, both invertible strategies."""
    s0, m0 = _step("recompute", strategy, scan)
    s1, m1 = _step(policy, strategy, scan)
    np.testing.assert_allclose(float(m0["loss"]), float(m1["loss"]),
                               rtol=1e-6)
    for n in s0.variables:
        np.testing.assert_allclose(np.asarray(s0.variables[n], np.float32),
                                   np.asarray(s1.variables[n], np.float32),
                                   rtol=2e-4, atol=1e-5, err_msg=n)


def resolve_remat_mapping_test():
    def p(**kw):
        return make_params(**{**_CFG, **kw})

    # explicit values pass straight through
    for v in ("recompute", "stash", "save", "save_dots"):
        assert resolve_remat(p(remat_policy=v)) == v
    # legacy boolean maps onto the policy when remat_policy stays auto
    assert resolve_remat(p(stash_attention_outputs=True)) == "stash"
    assert resolve_remat(p(stash_attention_outputs=False)) == "recompute"
    # explicit policy WINS over the legacy boolean
    assert resolve_remat(p(remat_policy="save",
                           stash_attention_outputs=False)) == "save"
    # the long-context auto-stash rule survives the policy layer (the
    # measured 16k recipe), short context resolves to recompute
    assert resolve_remat(p(sequence_length=16384)) == "stash"
    assert resolve_remat(p(sequence_length=512)) == "recompute"
    assert resolve_remat(p(sequence_length=16384 + 64)) == "recompute"
    # a stash too big for 15% of HBM falls back (32k x batch 64 at the
    # 16k-recipe width: ~70GB of stash vs a 16GB planning figure —
    # stash_test pins the same boundary through resolve_stash)
    assert resolve_remat(p(sequence_length=32768, train_batch_size=64,
                           features_per_head=128, heads=8,
                           depth=16)) == "recompute"


def remat_report_fields_test():
    rep = remat_report(make_params(**_CFG))
    for key in ("stash_bytes_per_device", "save_residual_bytes_per_device",
                "hbm_bytes", "recompute_block_s", "save_block_s"):
        assert rep[key] > 0, key


def auto_is_recompute_at_flagship_shapes_test():
    """The flagship (CPU-shrunk) bench shapes resolve to recompute — the
    round-11 A/B measured recompute 204 / save 280 / save_dots 249 ms/step
    there, and auto must track the measurement, not a hunch."""
    params = make_params(sequence_length=64, features_per_head=64, heads=8,
                         depth=4, train_batch_size=8,
                         memory_reduction_strategy="revnet")
    assert resolve_remat(params) == "recompute"


# ---- the bottleneck kind (PR 27): the in-projection's all-reduced output
# rides the strategy residuals where its contraction crosses 'model' -------

_TP_CFG = dict(sequence_length=32, features_per_head=16, heads=4, depth=2,
               train_batch_size=8, vocab_size=64, calculation_dtype="float32",
               optimizer="momentum:0.9:1:1-learning_rate", learning_rate=0.01,
               mesh_shape_override={"data": 2, "model": 2}, tpu_size=4)


def _tp_trainer(policy, strategy="revnet", scan=True, **kw):
    """Toy flagship widths (the mixer blocks of tests/backend.py) on a
    {data: 2, model: 2} mesh over four of the eight virtual devices."""
    from homebrewnlp_tpu.core import sharding as shardlib
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    params = make_params(**{**_TP_CFG, "memory_reduction_strategy": strategy,
                            "scan_layers": scan, "remat_policy": policy, **kw})
    mesh = shardlib.build_mesh(params, jax.devices()[:4])
    assert dict(mesh.shape) == {"data": 2, "model": 2}
    trainer = Trainer(params, Model(params), mesh=mesh)
    x = np.random.default_rng(0).integers(
        0, params.vocab_size,
        (params.train_batch_size, params.sequence_length, 1))
    batch = {"token_x": jnp.asarray(x),
             "token_y": jnp.asarray((x + 1) % params.vocab_size)}
    return params, mesh, trainer, trainer.init_state(batch), batch


@pytest.mark.parametrize("policy,expected", [("auto", 2), ("stash", 2),
                                             ("recompute", 3)])
def bottleneck_stash_allreduce_count_test(policy, expected):
    """The compiled step holds the in-projection's all-reduce (a partial
    [b_local, s, intermediate] sum over the model-sharded heads) in the
    forward scan body, for the backward's cotangent, and — only without the
    stash — once more in the replay."""
    import re
    params, _, trainer, state, batch = _tp_trainer(policy)
    hlo = trainer.lowered(state, batch).compile().as_text()
    shape = (params.train_batch_size // 2, params.sequence_dim.size,
             params.intermediate[-1].size)
    found = re.findall(r"= f32\[(\d+),(\d+),(\d+)\]\S* all-reduce(?:-start)?\(",
                       hlo)
    assert sum(tuple(map(int, f)) == shape for f in found) == expected, found


@pytest.mark.parametrize("strategy", ["revnet", "momentum"])
@pytest.mark.parametrize("scan", [True, False])
def bottleneck_stash_parity_test(strategy, scan):
    """Same primal recurrence: the loss is bit-identical to "recompute";
    the replay starts the block's tail from the forward's exact
    intermediate instead of one rebuilt from the reconstructed stream, so
    updated parameters agree to reconstruction ulps."""
    results = []
    for policy in ("recompute", "auto"):
        _, _, trainer, state, batch = _tp_trainer(policy, strategy, scan)
        results.append(trainer.step(state, batch, jax.random.PRNGKey(0)))
    (s0, m0), (s1, m1) = results
    assert float(m0["loss"]) == float(m1["loss"])
    for n in s0.variables:
        np.testing.assert_allclose(np.asarray(s0.variables[n], np.float32),
                                   np.asarray(s1.variables[n], np.float32),
                                   rtol=2e-4, atol=1e-5, err_msg=n)


def _flagship(**kw):
    """The four-chip cell's shapes (benchmark/workloads/
    train_32big_mixer_dp2tp2.json), for the resolver alone: nothing is
    built."""
    return make_params(**{**dict(
        sequence_length=512, features_per_head=512, heads=8, depth=32,
        train_batch_size=256, calculation_dtype="bfloat16",
        memory_reduction_strategy="revnet", tpu_size=4,
        mesh_shape_override={"data": 2, "model": 2}), **kw})


_LONG_BLOCKS = [{"layer": ["norm-shift-scale-features-group",
                           "attention-dot_product-context-in:relu"]}]
_MIXER_LONG_BLOCKS = [
    {"layer": ["norm-shift-scale-features-group",
               "bottleneck_group_linear-in:relu-mid:relu-mid:norm-mid:shift"
               "-mid:scale-mid:features"]}] + _LONG_BLOCKS


@pytest.mark.parametrize("case", ["no_mesh", "model_axis_1", "engaged",
                                  "over_budget", "legacy_false",
                                  "checkpoint_strategy",
                                  "attention_unmoved"])
def bottleneck_stash_resolver_test(case):
    from homebrewnlp_tpu.core import sharding as shardlib
    from homebrewnlp_tpu.model.remat import stash_kinds, stash_plan
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    devices = jax.devices()[:4]
    if case == "no_mesh":
        p = _flagship()
        assert stash_kinds(p) == frozenset()
        assert resolve_remat(p) == "recompute"
        assert stash_plan(p)["bottleneck"] == (0, 0)
    elif case == "model_axis_1":
        p = _flagship(mesh_shape_override={"data": 4})
        mesh = shardlib.build_mesh(p, devices)
        assert mesh.shape.get("model", 1) == 1
        assert stash_kinds(p, mesh) == frozenset()
    elif case == "engaged":
        p = _flagship()
        mesh = shardlib.build_mesh(p, devices)
        assert stash_kinds(p, mesh) == {"bottleneck"}
        assert resolve_remat(p, mesh) == "stash"
        # 128 sequences a chip x 512 x 512 x bfloat16 x 32 layers
        assert stash_plan(p, mesh) == {
            **_IDLE, "bottleneck": (32, 128 * 512 * 512 * 2 * 32)}
    elif case == "over_budget":
        # the published deployment's share, 256 sequences a chip: 4.3 GB
        # against 15% of the planning figure
        p = _flagship(train_batch_size=512)
        mesh = shardlib.build_mesh(p, devices)
        rep = remat_report(p, mesh)
        assert rep["bottleneck_stash_bytes_per_device"] \
            > rep["stash_budget_bytes"]
        assert stash_kinds(p, mesh) == frozenset()
        assert resolve_remat(p, mesh) == "recompute"
    elif case == "legacy_false":
        p = _flagship(stash_attention_outputs=False)
        assert stash_kinds(p, shardlib.build_mesh(p, devices)) == frozenset()
    elif case == "checkpoint_strategy":
        p = _flagship(memory_reduction_strategy="checkpoint")
        plan = stash_plan(p, shardlib.build_mesh(p, devices))
        assert plan == _IDLE
    else:
        # a long-context configuration's attention decision is the same
        # with and without a bottleneck in the block, on one device and on
        # the mesh, for the rule and for the legacy boolean — and the
        # bottleneck only gets what attention leaves of the budget
        for extra in ({}, {"stash_attention_outputs": True},
                      {"train_batch_size": 64, "sequence_length": 32768}):
            decisions = []
            for blocks in (_LONG_BLOCKS, _MIXER_LONG_BLOCKS):
                p = _flagship(**{**dict(
                    sequence_length=16384, features_per_head=128, depth=16,
                    train_batch_size=2, use_flash_attention=True,
                    block_config=blocks), **extra})
                mesh = shardlib.build_mesh(p, devices)
                decisions.append(("attention" in stash_kinds(p),
                                  "attention" in stash_kinds(p, mesh)))
            assert decisions[0] == decisions[1], (extra, decisions)
        p = _flagship(sequence_length=16384, train_batch_size=4, depth=16,
                      use_flash_attention=True,
                      block_config=_MIXER_LONG_BLOCKS)
        mesh = shardlib.build_mesh(p, devices)
        rep = remat_report(p, mesh)
        assert rep["bottleneck_stash_bytes_per_device"] \
            <= rep["stash_budget_bytes"] \
            < rep["bottleneck_stash_bytes_per_device"] \
            + rep["stash_bytes_per_device"]
        assert stash_kinds(p, mesh) == {"attention"}


@pytest.mark.parametrize("engaged", [True, False])
def remat_stash_gauges_test(engaged):
    """``hbnlp_remat_stash_bytes{kind}`` / ``hbnlp_remat_stash_layers{kind}``
    read what the shapes give once the step is built, 0 when not engaged."""
    from homebrewnlp_tpu import telemetry
    params, _, trainer, state, _ = _tp_trainer(
        "auto" if engaged else "recompute")
    trainer._build_step(state=state)
    snap = telemetry.registry().snapshot()
    got = {name: snap[name]["series"] for name in
           ("hbnlp_remat_stash_bytes", "hbnlp_remat_stash_layers")}
    item = (params.train_batch_size // 2) * params.sequence_dim.size \
        * params.intermediate[-1].size * 4
    assert got["hbnlp_remat_stash_bytes"] == {
        ("attention",): 0,
        ("bottleneck",): item * params.depth if engaged else 0,
        ("experts",): 0, ("recurrent",): 0, ("dense",): 0}
    assert got["hbnlp_remat_stash_layers"] == {
        ("attention",): 0, ("bottleneck",): params.depth if engaged else 0,
        ("experts",): 0, ("recurrent",): 0, ("dense",): 0}
    assert trainer.publish_stash_plan().startswith("remat stash: attention 0")


@pytest.mark.parametrize("strategy", ["revnet", "momentum"])
def one_device_step_is_untouched_test(strategy):
    """No mesh, no model axis crossed: "auto" stashes nothing and adds no
    residual — the lowered step is "recompute"'s, text for text."""
    texts = []
    for policy in ("recompute", "auto"):
        params = make_params(memory_reduction_strategy=strategy,
                             remat_policy=policy, **_CFG)
        trainer = Trainer(params, Model(params))
        x = np.zeros((params.train_batch_size, params.sequence_length, 1),
                     np.int32)
        batch = {"token_x": jnp.asarray(x), "token_y": jnp.asarray(x)}
        texts.append(trainer.lowered(trainer.init_state(batch),
                                     batch).as_text())
    assert texts[0] == texts[1]


# ---- the experts kind (PR 29): layer moe's three grouped-matmul outputs
# and its routing triple are saved by the checkpoint strategy's
# jax.checkpoint where their bytes fit ---------------------------------------

def _cell_params(cell: str, **kw):
    """A benchmark cell's configuration as the cell runs it, for the
    resolver alone: nothing is built."""
    from benchmark.lib.cell import load_cell
    from homebrewnlp_tpu.config import ModelParameter
    return ModelParameter({**load_cell(cell).model_config(),
                           "model_path": "/tmp/remat_policy_test", **kw})


#: 65,536 pairs x (2 x 1,024 + 2,048) x bfloat16, and order + inverse + the
#: 64 sizes + (PR 39) the router's choice [8,192, 8] in int32, a layer
_OLMOE_LAYER = 65536 * (2 * 1024 + 2048) * 2 + (3 * 65536 + 64) * 4


@pytest.mark.parametrize("case", [
    "engaged", "depth_16", "recompute", "stash", "legacy_false",
    "no_moe_layer", "revnet", "none", "macro_batching"])
def experts_stash_resolver_test(case):
    from homebrewnlp_tpu.model.remat import stash_kinds, stash_plan
    idle = _IDLE
    if case == "engaged":
        p = _cell_params("train_olmoe_1b_7b_s4k")
        rep = remat_report(p)
        assert rep["experts_stash_bytes_per_device"] == 2 * _OLMOE_LAYER \
            == 1073741824 + 1573376 <= rep["stash_budget_bytes"]
        assert "experts" in stash_kinds(p)
        # the attention kind is decided before it (PR 61; after it since PR
        # 40): both fit, so neither order moves the other
        assert stash_plan(p) == {**idle, "experts": (2, 2 * _OLMOE_LAYER),
                                 "attention": (2, 68157440)}
    elif case == "depth_16":
        # the published depth: 8.6 GB, all layers or none
        p = _cell_params("train_olmoe_1b_7b_s4k", depth=16)
        rep = remat_report(p)
        assert rep["experts_stash_bytes_per_device"] == 16 * _OLMOE_LAYER \
            > rep["stash_budget_bytes"]
        # a kind that passes the budget takes none of it and moves no other
        # kind's decision (PR 61): the sixteen layers' (out, lse) ride
        assert stash_kinds(p) == {"attention"}
        assert stash_plan(p) == {**idle, "attention": (16, 8 * 68157440)}
    elif case == "recompute":
        p = _cell_params("train_olmoe_1b_7b_s4k", remat_policy="recompute")
        assert stash_kinds(p) == frozenset() and stash_plan(p) == idle
    elif case == "stash":
        # explicit: on whatever the bytes
        p = _cell_params("train_olmoe_1b_7b_s4k", remat_policy="stash",
                         depth=16)
        assert stash_plan(p) == {**idle, "experts": (16, 16 * _OLMOE_LAYER),
                                 "attention": (16, 8 * 68157440)}
    elif case == "legacy_false":
        p = _cell_params("train_olmoe_1b_7b_s4k",
                         stash_attention_outputs=False)
        assert stash_plan(p) == idle
    elif case == "no_moe_layer":
        p = _cell_params("train_32big_mixer_b32",
                         memory_reduction_strategy="checkpoint")
        assert remat_report(p)["experts_stash_layers"] == 0
        assert "experts" not in stash_kinds(p) and stash_plan(p) == idle
        p = _cell_params("train_32big_mixer_b32", remat_policy="stash",
                         memory_reduction_strategy="checkpoint")
        assert stash_plan(p) == idle
    elif case in ("revnet", "none"):
        p = _cell_params("train_olmoe_1b_7b_s4k",
                         memory_reduction_strategy=case)
        assert "experts" not in stash_kinds(p)
        assert stash_plan(p)["experts"] == (0, 0)
        p = _cell_params("train_olmoe_1b_7b_s4k", remat_policy="stash",
                         memory_reduction_strategy=case)
        assert stash_plan(p)["experts"] == (0, 0)
    else:
        # three micro-batches hold three sets of outputs: over the budget
        p = _cell_params("train_olmoe_1b_7b_s4k", macro_batching=3)
        assert remat_report(p)["experts_stash_bytes_per_device"] \
            == 6 * _OLMOE_LAYER
        # ... and take none of it (PR 61): three sets of (out, lse) ride
        assert stash_plan(p) == {**idle, "attention": (2, 3 * 68157440)}


_MOE_NAMES = ("moe_gate", "moe_up", "moe_down", "moe_order", "moe_inverse",
              "moe_sizes", "moe_experts")
_FLASH_NAMES = ("flash_out", "flash_lse")


#: a v5e's memory: the table's 15.75 GiB (``utils/flops.py HBM_BYTES``, what
#: a described chip reads and ISSUE 52 counted with; the budget is 15% of it,
#: 2,536,715,059 bytes) and what a live chip reports as its limit
#: (``memory_stats()['bytes_limit']``: 2 MiB less; my chip runs, PR 50)
_CHIP_LIMITS = (16911433728, 16909336064)
_MLP_NAMES = ("mlp_gate", "mlp_up")


@pytest.fixture(params=_CHIP_LIMITS, ids=["table", "reported"])
def chip_limit(request, monkeypatch):
    """The rules read the chip's own limit, as a run on the chip does (the
    CPU's table row is 16 GiB); the limit."""
    from homebrewnlp_tpu.utils import flops
    monkeypatch.setattr(flops, "hbm_capacity",
                        lambda device=None: (request.param, "memory_stats"))
    return request.param


@pytest.mark.parametrize("cell,kinds,policy,plan,names,dense", [
    ("train_32big_mixer_b32", set(), "recompute", {}, (), (0, 0, ())),
    ("train_32big_mixer_dp2tp2", {"bottleneck"}, "stash",
     {"bottleneck": (32, 2147483648)}, (), (0, 0, ())),
    ("train_1b_long_context_s16k", {"attention"}, "stash",
     {"attention": (8, 2155872256)}, (), (0, 0, ())),
    # 2 x (out [2, 4096, 16, 128] bfloat16 + lse [32, 4096] float32)
    ("train_olmoe_1b_7b_s4k", {"attention", "experts"}, "stash",
     {"experts": (2, 1075315200), "attention": (2, 68157440)},
     _MOE_NAMES + _FLASH_NAMES, (0, 0, ())),
    # out [1, 8192, 32, 64] + lse [32, 8192]; PR 52: 2 x [1, 8192, 8192]
    # bfloat16 an execution, six of ten inside 2.537 GB - 34.6 MB - 20 block
    # inputs of [1, 8192, 2048] = 1.831 GB, from the last block backwards;
    # PR 71, the one row that moved: nine in-projection outputs [1, 8192,
    # 8512] are decided before them and leave 0.576 GB, two of ten
    ("train_granite_4_0_h_micro_long", {"attention", "recurrent", "dense"},
     "stash", {"attention": (1, 34603008), "recurrent": (9, 1255145472)},
     ("mamba_in_proj",) + _FLASH_NAMES, (2, 536870912, (17, 19))),
    # out [1, 16384, 30, 128] + lse [30, 16384]; PR 52: 2 x [1, 16384, 11008]
    # an execution, one of four inside 2.537 GB - 127.8 MB - 566.2 MB - 8
    # block inputs of [1, 16384, 3840] = 0.836 GB: the step's last block
    ("train_olmo_hybrid_7b_long", {"attention", "recurrent", "dense"},
     "stash", {"recurrent": (3, 566231040), "attention": (1, 127795200)},
     ("gated_delta_out",) + _FLASH_NAMES, (1, 721420288, (7,))),
    # the experts kind has 5,375,525,008 bytes to save, passes the budget and
    # takes none of it: since PR 61 the global layer's (out [2, 8192, 48, 128]
    # + lse [96, 8192]) rides on its own bytes (the three window-512 layers
    # are under 2,048 keys); its one ``mlp`` is an input block, outside every
    # region
    ("train_laguna_s_2_1_ep32_s8k", {"attention"}, "stash",
     {"attention": (1, 204472320)}, _FLASH_NAMES, (0, 0, ())),
    # layer cca's OWN 8 query heads in a 16-head stream: 8 x (out [1, 16384,
    # 8, 128] + lse [8, 16384])
    ("train_zaya1_8b_ep2_s16k", {"attention", "experts"}, "stash",
     {"experts": (8, 1612185888), "attention": (8, 272629760)},
     _MOE_NAMES + _FLASH_NAMES, (0, 0, ())),
    # PR 52: 2 x [1, 16384, 16384] an execution, one of four inside 2.537 GB
    # - 72.4 MB - 8 block inputs of [1, 16384, 4096] = 1.391 GB
    ("train_minicpm_sala_tp2_long", {"attention", "dense"}, "stash",
     {"attention": (1, 72351744)}, _FLASH_NAMES + ("sparse_keep",),
     (1, 1073741824, (7,))),
    # 96 regions x [2, 4096, 2048] block inputs = 3.2 GB beside the 1.6 GB of
    # (out, lse): nothing is left (PR 52)
    ("train_ouro_2_6b_loop4_s4k", {"attention"}, "stash",
     {"attention": (48, 1635778560)}, _FLASH_NAMES, (0, 0, ()))])
def experts_kind_moves_no_other_cell_test(cell, kinds, policy, plan, names,
                                          dense, chip_limit):
    """What the three cells without a ``moe`` layer resolved to before the
    experts kind existed, and (PR 33) what the five cells without a layer
    that offers its output resolved to before the recurrent kind existed
    (read off the parent commit), kind for kind and byte for byte; the
    ``jax.checkpoint`` policy is the named object itself where the parent's
    was, and saves layer ``moe``'s names alone where the parent's did.
    Since PR 40 all eight cells: the attention kind rides the four
    ``checkpoint`` cells whose earlier kinds fitted — each layer's own query
    heads, ISSUE 40's table to the byte — after the kinds the parent
    resolved, which it moved in none; Laguna's and the three revnet cells'
    plan and names are the parent's (until PR 61: Laguna's global layer rides
    now, the one row that moved).  Since PR 52 all ten cells, at the
    chip's own limit: the ``dense`` kind, decided last, admits ``dense`` =
    (executions, bytes, the regions that save layer ``mlp``'s two names) —
    ISSUE 52's table to the byte — and moves no other kind's numbers; every
    other region's policy names what the parent's named.  Since PR 71
    granite's row holds layer ``mamba``'s in-projection outputs."""
    from benchmark.lib.cell import load_cell
    from homebrewnlp_tpu.core import sharding as shardlib
    from homebrewnlp_tpu.model.blocks import (_checkpoint_policy, _name_chan,
                                              _named_policy,
                                              _region_policies)
    from homebrewnlp_tpu.model.remat import (dense_executions, region_names,
                                             saved_attention_keys,
                                             stash_kinds, stash_names,
                                             stash_plan)
    p = _cell_params(cell)
    mesh = None
    if load_cell(cell).chips > 1:
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 virtual devices")
        mesh = shardlib.build_mesh(p, jax.devices()[:4])
    assert stash_kinds(p, mesh) == kinds
    assert resolve_remat(p, mesh) == policy
    assert stash_plan(p, mesh) == {**_IDLE, **plan, "dense": dense[:2]}
    assert dense_executions(p, mesh) == dense[0]
    assert stash_names(p, mesh) == names
    assert (_checkpoint_policy(p, mesh)
            is jax.checkpoint_policies.nothing_saveable) == (not names)
    # region by region: the parent's names, and the two new ones only where
    # an admitted execution is
    regions = len(p.block_config) * p.depth * p.loop_steps
    saved = [names + (_MLP_NAMES if region in dense[2] else ())
             for region in range(regions)]
    assert region_names(p, mesh) == saved
    assert all(policy is _named_policy(p.gradient_checkpointing_policy, held)
               for policy, held in zip(_region_policies(p, mesh), saved))
    # the blocks' "name" channel exactly where the two names are saved
    rides = "flash_out" in names
    assert saved_attention_keys(p, mesh) == (2048 if rides else None)
    assert (_name_chan(p, mesh) is not None) == rides


@pytest.mark.parametrize("cell,layers", [
    ("train_32big_mixer_b32", 0), ("train_32big_mixer_dp2tp2", 0),
    ("train_1b_long_context_s16k", 0), ("train_olmoe_1b_7b_s4k", 0),
    ("train_granite_4_0_h_micro_long", 9)])
def conv_kernel_moves_no_other_cell_test(cell, layers):
    """The Pallas conv pair is chosen inside layer ``mamba`` alone: on a TPU
    the granite cell's nine layers take it, the four other cells have no
    such layer to trace anything new (their lowered steps hashed equal to
    the parent's, ``PERF.md`` section 6, PR 31); off the TPU nobody does."""
    from homebrewnlp_tpu.model.declare import layers as _layers
    from homebrewnlp_tpu.model.recurrent import conv_kernel_layers
    p = _cell_params(cell)
    assert conv_kernel_layers(p, "tpu") == layers
    assert conv_kernel_layers(p) == 0
    assert any(name == "mamba" for name, *_ in _layers(p)) is bool(layers)


@pytest.mark.parametrize("cell,layers", [
    ("train_32big_mixer_b32", None), ("train_32big_mixer_dp2tp2", None),
    ("train_1b_long_context_s16k", None), ("train_olmoe_1b_7b_s4k", None),
    ("train_granite_4_0_h_micro_long", None),
    ("train_laguna_s_2_1_ep32_s8k", None),
    ("train_olmo_hybrid_7b_long", 3)])
def solve_kernel_moves_no_other_cell_test(cell, layers):
    """The Pallas pair for the triangular solve is chosen inside layer
    ``gated_delta`` alone, by what it DECLARES (``Recurrent.solve``): on a
    TPU the Olmo-Hybrid cell's three layers take it, the six other cells
    have no layer that declares a solve (``mamba`` declares none) and trace
    nothing new (their train steps' jaxprs hash equal to the parent's,
    ``PERF.md`` section 6, PR 37); off the TPU nobody does."""
    from homebrewnlp_tpu.model.declare import layers as _layers
    from homebrewnlp_tpu.model.recurrent import solve_kernel_layers
    p = _cell_params(cell)
    assert solve_kernel_layers(p, "tpu") == layers
    assert solve_kernel_layers(p) == (None if layers is None else 0)
    assert any(name == "gated_delta" for name, *_ in _layers(p)) \
        is (layers is not None)


def experts_stash_line_and_policy_test():
    """The start-up line names the kind, and ``_checkpoint_policy`` saves
    layer moe's names exactly where the plan says the kind rides: today's
    object (the named policy itself) everywhere else."""
    from homebrewnlp_tpu.model.blocks import _checkpoint_policy
    from homebrewnlp_tpu.model.remat import stash_line, stash_plan
    p = _cell_params("train_olmoe_1b_7b_s4k")
    assert stash_line(stash_plan(p)) == (
        "remat stash: attention 2 layers, 68157440 bytes a device; "
        "bottleneck 0 layers, 0 bytes a device; experts 2 layers, "
        f"{2 * _OLMOE_LAYER} bytes a device; recurrent 0 layers, 0 bytes a "
        "device; dense 0 layers, 0 bytes a device")
    nothing = jax.checkpoint_policies.nothing_saveable
    assert _checkpoint_policy(p) is not nothing
    # (at the published depth the experts pass the budget; since PR 61 the
    # sixteen flash pairs ride there on their own bytes: flash off, nothing)
    for kw in ({"remat_policy": "recompute"},
               {"depth": 16, "use_flash_attention": False}):
        assert _checkpoint_policy(
            _cell_params("train_olmoe_1b_7b_s4k", **kw)) is nothing
    assert _checkpoint_policy(_cell_params(
        "train_olmoe_1b_7b_s4k", depth=16, use_flash_attention=False,
        gradient_checkpointing_policy="dots_saveable")) \
        is jax.checkpoint_policies.dots_saveable


# ---- the recurrent kind (PR 33): the output a recurrent mixer offers
# because it re-materialises its own interior (layer gated_delta's rule) is
# saved by the checkpoint strategy's jax.checkpoint where the whole depth's
# declared bytes fit what the experts kind leaves ------------------------------

#: the rule's output [1, 16384, 30, 192] in bfloat16, a layer
_OLMO_LAYER = 16384 * 30 * 192 * 2
_OLMO = "train_olmo_hybrid_7b_long"
#: its one flash layer's out [1, 16384, 30, 128] in bfloat16 and lse [30,
#: 16384] in float32 (PR 40)
_OLMO_FLASH = 16384 * 30 * (128 * 2 + 4)
#: one MLP's gate and up [1, 16384, 11008] in bfloat16 (PR 52); granite's
#: [1, 8192, 8192]
_OLMO_MLP = 2 * 16384 * 11008 * 2
_GRANITE_MLP = 2 * 8192 * 8192 * 2
#: layer mamba's in-projection output (PR 71), z | xBC | dt = 2 d_inner + 2
#: groups x state + heads columns in bfloat16: granite's [1, 8192, 8512], the
#: Nemotron cell's [1, 16384, 9280] (one tensor-parallel rank's 64 heads and
#: 4 groups)
_GRANITE_PROJ = 8192 * (2 * 4096 + 2 * 128 + 64) * 2
_NEMOTRON_PROJ = 16384 * (2 * 4096 + 2 * 4 * 128 + 64) * 2
_NEMOTRON = "train_nemotron_3_super_tp2_ep64_s16k"
_MAMBA_NAMES = ("mamba_in_proj",)


_GRANITE = "train_granite_4_0_h_micro_long"


def _with_moe(top_k: int):
    """The Olmo-Hybrid cell with its first MLP made a ``moe`` layer of 64
    experts as wide as the MLP: 16,384 x top-k pairs x (2 x 11,008 + 3,840)
    x bfloat16 and the routing triple, for the resolver alone."""
    blocks = _cell_params(_OLMO).block_config
    blocks = [{"layer": list(b.layer), "skip": b.skip} for b in blocks]
    at = next(i for i, b in enumerate(blocks) if b["layer"][0] == "mlp-silu")
    blocks[at]["layer"][0] = "moe"
    p = _cell_params(_OLMO, block_config=blocks, experts=64, moe_top_k=top_k)
    pairs = 16384 * top_k
    return p, pairs * (2 * 11008 + 3840) * 2 + (3 * pairs + 64) * 4


@pytest.mark.parametrize("case", [
    "engaged", "over_budget", "recompute", "stash", "legacy_false", "revnet",
    "none", "pipe_mesh", "macro_batching", "experts_leave_room",
    "experts_leave_none", "experts_decline",
    "mamba_offers_its_in_projection"])
def recurrent_stash_resolver_test(case):
    from homebrewnlp_tpu.model.blocks import _checkpoint_policy
    from homebrewnlp_tpu.model.declare import offers
    from homebrewnlp_tpu.model.recurrent import \
        recurrent_layers as _recurrent_layers
    from homebrewnlp_tpu.model.remat import (region_names, stash_kinds,
                                             stash_names, stash_plan)
    idle = _IDLE
    nothing = jax.checkpoint_policies.nothing_saveable
    if case == "engaged":
        p = _cell_params(_OLMO)
        rep = remat_report(p)
        assert (rep["recurrent_stash_layers"],
                rep["recurrent_stash_bytes_per_device"]) \
            == (3, 3 * _OLMO_LAYER) == (3, 566231040)
        assert 3 * _OLMO_LAYER <= rep["stash_budget_bytes"]
        # (PR 52: the dense kind, decided after all three, takes the last
        # MLP's gate and up from what they and the block inputs leave)
        assert stash_kinds(p) == {"attention", "recurrent", "dense"}
        assert stash_plan(p) == {**idle, "recurrent": (3, 566231040),
                                 "attention": (1, _OLMO_FLASH),
                                 "dense": (1, _OLMO_MLP)}
        assert stash_names(p) == ("gated_delta_out",) + _FLASH_NAMES
        assert _checkpoint_policy(p) is not nothing
    elif case == "over_budget":
        # the published depth, eight periods, offers 4.5 GB: an execution at
        # a time from the step's end (PR 61; all layers or none before it,
        # so none) — the eight that fit what the eight flash pairs leave
        p = _cell_params(_OLMO, depth=8)
        rep = remat_report(p)
        assert rep["recurrent_stash_bytes_per_device"] == 24 * _OLMO_LAYER \
            > rep["stash_budget_bytes"]
        left = rep["stash_budget_bytes"] - 8 * _OLMO_FLASH
        assert 8 * _OLMO_LAYER <= left < 9 * _OLMO_LAYER
        assert stash_plan(p) == {**idle, "attention": (8, 8 * _OLMO_FLASH),
                                 "recurrent": (8, 8 * _OLMO_LAYER)}
        # the last eight: the last two periods' three and two of the sixth's
        held = [r for r, names in enumerate(region_names(p))
                if "gated_delta_out" in names]
        assert held == list(range(42, 64))
        assert _checkpoint_policy(p) is not nothing
    elif case == "recompute":
        p = _cell_params(_OLMO, remat_policy="recompute")
        assert stash_kinds(p) == frozenset() and stash_plan(p) == idle
        assert _checkpoint_policy(p) is nothing
    elif case == "stash":
        # explicit: on whatever the bytes
        p = _cell_params(_OLMO, remat_policy="stash", depth=8)
        assert stash_plan(p) == {**idle, "recurrent": (24, 24 * _OLMO_LAYER),
                                 "attention": (8, 8 * _OLMO_FLASH),
                                 "dense": (32, 32 * _OLMO_MLP)}
    elif case == "legacy_false":
        p = _cell_params(_OLMO, stash_attention_outputs=False)
        assert stash_plan(p) == idle and _checkpoint_policy(p) is nothing
    elif case in ("revnet", "none"):
        for kw in ({}, {"remat_policy": "stash"}):
            p = _cell_params(_OLMO, memory_reduction_strategy=case, **kw)
            assert kw or "recurrent" not in stash_kinds(p)
            assert stash_plan(p)["recurrent"] == (0, 0)
            assert stash_names(p) == ()
    elif case == "pipe_mesh":
        from homebrewnlp_tpu.core.sharding import PIPE_AXIS

        class Piped:
            devices = None
            shape = {PIPE_AXIS: 2}

        for kw in ({}, {"remat_policy": "stash"}):
            p = _cell_params(_OLMO, **kw)
            assert stash_plan(p, Piped()) == idle
            assert _checkpoint_policy(p, Piped()) is nothing
    elif case == "macro_batching":
        # five micro-batches hold five sets of outputs: 2.83 GB offered, and
        # (PR 61) the last two layers' 1.89 GB admitted beside the flash pair
        p = _cell_params(_OLMO, macro_batching=5)
        rep = remat_report(p)
        assert rep["recurrent_stash_bytes_per_device"] == 15 * _OLMO_LAYER \
            > rep["stash_budget_bytes"]
        assert stash_plan(p) == {**idle, "attention": (1, 5 * _OLMO_FLASH),
                                 "recurrent": (2, 10 * _OLMO_LAYER)}
    elif case == "experts_leave_room":
        # decided AFTER experts, from what it and attention took of the 15%
        p, experts = _with_moe(2)
        budget = remat_report(p)["stash_budget_bytes"]
        assert experts + 3 * _OLMO_LAYER + _OLMO_FLASH <= budget
        assert stash_plan(p) == {**idle, "experts": (1, experts),
                                 "recurrent": (3, 3 * _OLMO_LAYER),
                                 "attention": (1, _OLMO_FLASH)}
        assert stash_names(p) == _MOE_NAMES + ("gated_delta_out",) \
            + _FLASH_NAMES
    elif case == "experts_leave_none":
        p, experts = _with_moe(3)
        budget = remat_report(p)["stash_budget_bytes"]
        assert experts <= budget and 3 * _OLMO_LAYER <= budget \
            < experts + 3 * _OLMO_LAYER
        # the attention kind is taken FIRST (PR 61; last since PR 40, when
        # the experts took the budget and the two others rode nowhere): the
        # experts, all layers or none, no longer fit what it leaves and take
        # nothing; the rule's outputs and the last MLP are judged on their own
        assert experts + _OLMO_FLASH > budget
        assert stash_kinds(p) == {"attention", "recurrent", "dense"}
        assert stash_plan(p) == {**idle, "attention": (1, _OLMO_FLASH),
                                 "recurrent": (3, 3 * _OLMO_LAYER),
                                 "dense": (1, _OLMO_MLP)}
        assert stash_names(p) == ("gated_delta_out",) + _FLASH_NAMES
    elif case == "experts_decline":
        # experts over the budget take none of it and (PR 61) move no other
        # kind's decision: the cell's own plan, its first MLP a ``moe`` layer
        p, experts = _with_moe(8)
        assert experts > remat_report(p)["stash_budget_bytes"]
        assert stash_plan(p) == {**idle, "attention": (1, _OLMO_FLASH),
                                 "recurrent": (3, 3 * _OLMO_LAYER),
                                 "dense": (1, _OLMO_MLP)}
        assert stash_names(p) == ("gated_delta_out",) + _FLASH_NAMES
    else:
        # PR 71: layer mamba has no inner jax.checkpoint, so its OUTPUT saved
        # would skip none of the replay; what it offers is its in-projection's
        # output (the layer's largest matmul, which the replay then does not
        # run), one name and no interior.  Granite's nine layers ride whole
        # and, decided before the dense kind, leave it two of the ten MLPs
        # where it had six (all ten where forced)
        for kw in ({}, {"remat_policy": "stash"}):
            p = _cell_params(_GRANITE, **kw)
            assert len(_recurrent_layers(p)) == 9
            assert [(o.names, o.nbytes, o.interior_names)
                    for o in offers(p, "recurrent")] \
                == [(_MAMBA_NAMES, _GRANITE_PROJ, ())] * 9
            rep = remat_report(p)
            assert (rep["recurrent_stash_layers"],
                    rep["recurrent_stash_bytes_per_device"]) \
                == (9, 9 * _GRANITE_PROJ) == (9, 1255145472)
            assert stash_plan(p) == {
                **idle, "attention": (1, 34603008),
                "recurrent": (9, 1255145472),
                "dense": (10 if kw else 2, (10 if kw else 2) * _GRANITE_MLP)}
            assert stash_names(p) == _MAMBA_NAMES + _FLASH_NAMES
            # the first region holds an admitted layer: the name from there on
            assert all("mamba_in_proj" in names for names in region_names(p))
            assert _checkpoint_policy(p) is not nothing
        # the Nemotron cell: its five layers beside the attention and the
        # experts kind as they were, in the 2.33 GB those leave; the name
        # from the first mamba region (2 of 0-10) on
        p = _cell_params(_NEMOTRON)
        assert [o.nbytes for o in offers(p, "recurrent")] \
            == [_NEMOTRON_PROJ] * 5 == [304087040] * 5
        assert stash_plan(p) == {**idle, "attention": (1, 68157440),
                                 "experts": (5, 180224180),
                                 "recurrent": (5, 1520435200)}
        assert stash_names(p)[-3:] == _MAMBA_NAMES + _FLASH_NAMES
        assert _holding(p, "mamba_in_proj") == list(range(2, 11))
        # three micro-batches hold three sets: from the step's end, the last
        # two layers' (regions 8 and 10)
        p = _cell_params(_NEMOTRON, macro_batching=3)
        assert stash_plan(p)["recurrent"] == (2, 6 * _NEMOTRON_PROJ)
        assert _holding(p, "mamba_in_proj") == [8, 9, 10]
        for kw in ({"remat_policy": "recompute"},
                   {"memory_reduction_strategy": "none"}):
            assert stash_plan(_cell_params(_NEMOTRON, **kw)) == idle


def recurrent_stash_line_test():
    """The start-up line names the kind last, before the chunk states."""
    from homebrewnlp_tpu.model.remat import stash_line, stash_plan
    assert stash_line(stash_plan(_cell_params(_OLMO))) == (
        "remat stash: attention 1 layers, 127795200 bytes a device; "
        "bottleneck 0 layers, 0 bytes a device; experts 0 layers, 0 bytes a "
        "device; recurrent 3 layers, 566231040 bytes a device; dense 1 "
        "layers, 721420288 bytes a device")


# ---- the attention kind under checkpoint (PR 40): every flash layer's (out,
# lse) rides the block's jax.checkpoint as named values where a query sees at
# least 2,048 keys; since PR 61 decided FIRST and on its own bytes (PR 40: last,
# and not at all where an earlier kind declined for size) -----------------------

_LAGUNA = "train_laguna_s_2_1_ep32_s8k"
_ZAYA = "train_zaya1_8b_ep2_s16k"


def _relayered(cell: str, change, **kw):
    """``cell`` with ``change(layer name) -> layer name`` over its period."""
    blocks = [{"layer": [change(layer) for layer in b.layer], "skip": b.skip}
              for b in _cell_params(cell).block_config]
    return _cell_params(cell, block_config=blocks, **kw)


@pytest.mark.parametrize("case", [
    "own_heads", "window_under_2048_keys", "window_of_2048_keys",
    "sequence_under_2048", "sequence_off_the_tile", "flash_off", "a_mesh",
    "macro_batching", "earlier_kinds_exhaust_the_budget",
    "earlier_kind_declined_for_size", "depth_over_budget", "recompute",
    "stash", "legacy_true", "legacy_false", "revnet", "momentum", "none",
    "pipe_mesh"])
def attention_saved_resolver_test(case):
    from homebrewnlp_tpu.model.blocks import _checkpoint_policy, _name_chan
    from homebrewnlp_tpu.model.remat import (saved_attention_keys,
                                             stash_kinds, stash_names,
                                             stash_plan)
    idle = _IDLE
    nothing = jax.checkpoint_policies.nothing_saveable

    def declines(p, mesh=None):
        # (an explicit "stash" names every kind, whatever can ride)
        assert p.remat_policy == "stash" \
            or "attention" not in stash_kinds(p, mesh)
        assert stash_plan(p, mesh)["attention"] == (0, 0)
        assert not set(_FLASH_NAMES) & set(stash_names(p, mesh))
        assert saved_attention_keys(p, mesh) is None
        assert _name_chan(p, mesh) is None

    if case == "own_heads":
        # sized from each layer's OWN query heads, not the stream's: cca's 8
        # of 16, and 72 / 48 where the stream has 24
        rep = remat_report(_cell_params(_ZAYA))
        assert rep["saved_attention_bytes_per_device"] == 272629760 \
            == rep["stash_bytes_per_device"] // 2
        p = _cell_params(_LAGUNA, experts_held=0, experts=8, moe_top_k=1)
        rep = remat_report(p)
        # its one global layer of the period: out [2, 8192, 48, 128] +
        # lse [96, 8192]; the window-512 layers do not count
        assert (rep["saved_attention_layers"],
                rep["saved_attention_bytes_per_device"]) == (1, 204472320)
        assert rep["stash_bytes_per_device"] == 102236160
    elif case == "window_under_2048_keys":
        # a window of 512 keys on 8,192 positions: granite's one layer
        p = _relayered(_GRANITE, lambda l: l + "-window512"
                       if l.startswith("attention") else l)
        assert remat_report(p)["saved_attention_layers"] == 0
        declines(p)
        # (PR 71: the nine in-projection outputs ride; the 34.6 MB the layer
        # does not hold are no room for a third MLP's gate and up)
        assert stash_plan(p) == {**idle, "recurrent": (9, 9 * _GRANITE_PROJ),
                                 "dense": (2, 2 * _GRANITE_MLP)}
        assert stash_names(p) == _MAMBA_NAMES
    elif case == "window_of_2048_keys":
        p = _relayered(_GRANITE, lambda l: l + "-window2048"
                       if l.startswith("attention") else l)
        assert stash_plan(p) == {**idle, "attention": (1, 34603008),
                                 "recurrent": (9, 9 * _GRANITE_PROJ),
                                 "dense": (2, 2 * _GRANITE_MLP)}
        assert saved_attention_keys(p) == 2048
    elif case == "sequence_under_2048":
        declines(_cell_params(_GRANITE, sequence_length=1024))
    elif case == "sequence_off_the_tile":
        # 8,256 = 64.5 tiles of 128: the flash route does not engage
        declines(_cell_params(_GRANITE, sequence_length=8256))
    elif case == "flash_off":
        for kw in ({}, {"remat_policy": "stash"}):
            declines(_cell_params(_GRANITE, use_flash_attention=False, **kw))
    elif case == "a_mesh":
        # _flash's shard_map branch keeps the plain kernel
        from homebrewnlp_tpu.core import sharding as shardlib
        p = _cell_params(_GRANITE)
        declines(p, shardlib.build_mesh(p, jax.devices()[:1]))
    elif case == "macro_batching":
        # three micro-batches hold three sets: the experts kind passes the
        # budget and takes none of it; the attention kind is judged on its own
        p = _cell_params("train_olmoe_1b_7b_s4k", macro_batching=3)
        assert stash_plan(p) == {**idle, "attention": (2, 3 * 68157440)}
        assert saved_attention_keys(p) == 2048
        # three times the bytes (PR 71: of the in-projection outputs too —
        # the last five layers' fit what the pair leaves)
        p = _cell_params(_GRANITE, macro_batching=3)
        assert stash_plan(p) == {**idle, "attention": (1, 3 * 34603008),
                                 "recurrent": (5, 3 * 5 * _GRANITE_PROJ)}
    elif case == "earlier_kinds_exhaust_the_budget":
        # experts at top-3 alone would leave 34.6 MB, less than the layer's
        # 127.8 MB: taken first, the one kind whose forward grows with the
        # square of the sequence is never squeezed out by a cheaper one
        p, experts = _with_moe(3)
        assert 0 < remat_report(p)["stash_budget_bytes"] - experts \
            < _OLMO_FLASH
        assert stash_plan(p)["attention"] == (1, _OLMO_FLASH)
        assert stash_plan(p)["experts"] == (0, 0)
        assert saved_attention_keys(p) == 2048
    elif case == "earlier_kind_declined_for_size":
        # Laguna: the experts kind has 5.4 GB to save and declined; the
        # global layer's 204 MB fit the budget twelve times over and (PR 61)
        # ride: each kind is judged on its own bytes
        p = _cell_params(_LAGUNA)
        rep = remat_report(p)
        assert rep["experts_stash_bytes_per_device"] == 5375525008 \
            > rep["stash_budget_bytes"] \
            > rep["saved_attention_bytes_per_device"] == 204472320
        assert stash_kinds(p) == {"attention"}
        assert stash_plan(p) == {**idle, "attention": (1, 204472320)}
        assert stash_names(p) == _FLASH_NAMES
        assert saved_attention_keys(p) == 2048
        assert _checkpoint_policy(p) is not nothing
        # ... beside the experts where their bytes fit too (8 experts at
        # top-1 in a buffer of their own size)
        p = _cell_params(_LAGUNA, experts_held=0, experts=8, moe_top_k=1)
        assert stash_kinds(p) == {"experts", "attention"}
        assert stash_plan(p)["attention"] == (1, 204472320)
    elif case == "depth_over_budget":
        # all qualifying layers or none: 80 periods of granite's one layer
        p = _cell_params(_GRANITE, depth=80)
        assert remat_report(p)["saved_attention_bytes_per_device"] \
            == 80 * 34603008 > remat_report(p)["stash_budget_bytes"]
        declines(p)
    elif case == "recompute":
        declines(_cell_params(_ZAYA, remat_policy="recompute"))
    elif case == "stash":
        # explicit: every engaged flash layer, whatever the keys and bytes
        p = _cell_params(_LAGUNA, remat_policy="stash")
        # 3 x (out [2, 8192, 72, 128] + lse [144, 8192]) and the global one
        assert stash_plan(p)["attention"] == (4, 3 * 306708480 + 204472320)
        assert saved_attention_keys(p) == 0
        assert _name_chan(p, None)["min_keys"] == 0
    elif case == "legacy_true":
        p = _cell_params(_GRANITE, stash_attention_outputs=True,
                         sequence_length=1024)
        assert stash_plan(p) == {**idle, "attention": (1, 34603008 // 8),
                                 "recurrent": (9, 9 * _GRANITE_PROJ // 8),
                                 "dense": (10, 10 * _GRANITE_MLP // 8)}
        assert saved_attention_keys(p) == 0
    elif case == "legacy_false":
        declines(_cell_params(_ZAYA, stash_attention_outputs=False))
    elif case in ("revnet", "momentum", "none"):
        # nothing is carried through the policy: the channel's kinds keep
        # their historical rule, and "none" has no replay
        for kw in ({}, {"remat_policy": "stash"}):
            p = _cell_params(_OLMO, memory_reduction_strategy=case, **kw)
            assert stash_names(p) == () and _checkpoint_policy(p) is nothing
            assert saved_attention_keys(p) is None
            assert _name_chan(p, None) is None
            assert "attention" in stash_kinds(p)    # the sequence rule's
    else:
        from homebrewnlp_tpu.core.sharding import PIPE_AXIS

        class Piped:
            devices = None
            shape = {PIPE_AXIS: 2}

        for kw in ({}, {"remat_policy": "stash"}):
            p = _cell_params(_GRANITE, **kw)
            assert stash_plan(p, Piped()) == idle
            assert _name_chan(p, Piped()) is None
            assert _checkpoint_policy(p, Piped()) is nothing


# ---- the dense kind (PR 52): layer mlp's gate and up outputs ride the
# jax.checkpoint of the regions that hold an admitted execution — admitted one
# execution at a time from the step's LAST backwards, into what the earlier
# kinds AND the block inputs ``checkpoint`` itself keeps leave of the 15% -----

_SALA = "train_minicpm_sala_tp2_long"
_OURO = "train_ouro_2_6b_loop4_s4k"
_TOY = {"depth": 2, "heads": 4, "features_per_head": 16,
        "sequence_length": 64, "train_batch_size": 2, "vocab_size": 384,
        "tpu_size": 1, "use_checkpointing": False, "slice_dtype": "float32",
        "calculation_dtype": "float32", "model_path": "/tmp/remat_policy_test"}
#: the toy's gate and up [2, 64, 176] float32 an execution, and a block input
#: [2, 64, 4 x 16]
_TOY_MLP = 2 * 2 * 64 * 176 * 4
_TOY_INPUT = 2 * 64 * 64 * 4


@functools.lru_cache(maxsize=None)
def _dense_toy(loops: int, policy: str, limit: int = 0, scan: bool = False):
    """Ouro's period (an attention block, then an MLP block, each between two
    norms) at toy widths, two periods deep, ``loops`` passes: ``(params, the
    gradient's jaxpr, (loss, gradients))`` under ``policy``; ``limit``: the
    bytes the chip reports, where the rule is to admit a part."""
    import json
    import os
    from unittest import mock
    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.utils import flops
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "configs", "ouro_2_6b.json")) as f:
        config = {**json.load(f), **_TOY, "loop_steps": loops,
                  "remat_policy": policy, "scan_layers": scan}
    params = ModelParameter(config)
    model = Model(params)
    tokens = np.random.default_rng(0).integers(0, 256, (2, 64, 1)).astype(
        np.int32)
    batch = {"token_x": tokens, "token_y": np.roll(tokens, -1, axis=1)}
    variables = {k: jnp.asarray(v)
                 for k, v in model.init(batch, seed=11).items()}
    fn = jax.value_and_grad(lambda v: model.apply(v, batch).total_loss.data)
    with mock.patch.object(flops, "hbm_capacity",
                           lambda device=None: (limit, "test")) \
            if limit else contextlib.nullcontext():
        from homebrewnlp_tpu.model.remat import stash_plan
        return (params, stash_plan(params)["dense"],
                jax.make_jaxpr(fn)(variables).jaxpr, jax.jit(fn)(variables))


def _limit_admitting(executions: int, loops: int) -> int:
    """The chip limit whose 15% holds the toy's block inputs (one a region)
    and ``executions`` and a half of its MLPs' gate and up."""
    from homebrewnlp_tpu.model.remat import STASH_HBM_FRACTION
    regions = 2 * _TOY["depth"] * loops
    return int((regions * _TOY_INPUT + executions * _TOY_MLP + _TOY_MLP // 2)
               / STASH_HBM_FRACTION)


def _replayed_dense_dots(jaxpr) -> typing.List[int]:
    """The matmuls of the gate / up shapes — ``[2, 64, 4, 16] x [4, 16, 176]
    -> [2, 64, 176]`` — in the BACKWARD of every ``jax.checkpoint`` region of
    a gradient's jaxpr (where the region's replay is), in execution order of
    the regions: the backward holds them last region first."""
    counts = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name != "remat2":
            continue
        counts.append(sum(
            e.primitive.name == "dot_general"
            and tuple(e.invars[1].aval.shape) == (4, 16, 176)
            and tuple(e.outvars[0].aval.shape) == (2, 64, 176)
            for e in eqn.params["jaxpr"].eqns))
    return counts[::-1]


@pytest.mark.parametrize("loops,admit,scan", [
    (1, 0, False), (1, 1, False), (1, "all", False), (2, 0, False),
    (2, 1, False), (2, 3, False), (2, "all", False), (1, "all", True),
    (1, 1, True)])
def dense_admission_changes_no_value_test(loops, admit, scan):
    """A toy ``checkpoint`` model with none, some and all of its ``mlp``
    executions admitted — unrolled, looped (the admission runs on over the
    passes: three of four reach into the first pass) and scanned (all or
    none) — against ``"recompute"``: the loss bit for bit, the gradients to
    the file's tolerance; the replay of an admitted block runs no matmul of
    the gate / up shapes, that of a block not admitted both; admitted are the
    LAST executions of the step."""
    _, _, base_jaxpr, (want_loss, want) = _dense_toy(loops, "recompute",
                                                     scan=scan)
    executions = _TOY["depth"] * loops
    if admit == "all":
        # the toy's bytes fit the CPU's 16 GiB many times over
        params, plan, jaxpr, (loss, grads) = _dense_toy(loops, "auto",
                                                        scan=scan)
        admitted = executions
    else:
        params, plan, jaxpr, (loss, grads) = _dense_toy(
            loops, "auto", _limit_admitting(admit, loops), scan)
        # a scanned body traces one block for all its iterations: all its
        # executions or none, and one of two does not fit
        admitted = 0 if scan else admit
    assert plan == (admitted, admitted * _TOY_MLP)
    assert float(loss) == float(want_loss) and np.isfinite(float(loss))
    assert set(grads) == set(want)
    for name in want:
        np.testing.assert_allclose(np.asarray(grads[name], np.float32),
                                   np.asarray(want[name], np.float32),
                                   rtol=2e-4, atol=1e-5, err_msg=name)
    if scan:
        # one region a block of the period, inside the scan's backward
        return
    # the regions alternate: an attention block, an MLP block
    regions = 2 * executions
    assert _replayed_dense_dots(base_jaxpr) == [0, 2] * executions
    assert _replayed_dense_dots(jaxpr) == [
        0 if region % 2 == 0 or region >= regions - 2 * admitted else 2
        for region in range(regions)]


@pytest.mark.parametrize("case", [
    "from_the_end", "block_inputs_count", "earlier_kind_declined_for_size",
    "earlier_kinds_leave_less", "stash", "recompute", "legacy_false",
    "revnet", "none", "pipe_mesh", "scan_layers", "macro_batching",
    "input_block_offers_nothing", "two_layers_a_block"])
def dense_stash_resolver_test(case, chip_limit):
    from homebrewnlp_tpu.model.blocks import (_checkpoint_policy,
                                              _region_policies)
    from homebrewnlp_tpu.model.declare import offers
    from homebrewnlp_tpu.model.remat import (dense_executions, region_names,
                                             stash_kinds, stash_plan)
    budget = int(0.15 * chip_limit)

    def saving(p, mesh=None):
        """The regions whose policy saves the two names."""
        names, policies = region_names(p, mesh), _region_policies(p, mesh)
        assert len(names) == len(policies) \
            == len(p.block_config) * p.depth * p.loop_steps
        found = [r for r, held in enumerate(names)
                 if set(_MLP_NAMES) & set(held)]
        assert all((policy is not _checkpoint_policy(p, mesh)) == (r in found)
                   for r, policy in enumerate(policies))
        return found

    def declines(p, mesh=None, kinds=True):
        # (an explicit "stash" names every kind, whatever can ride)
        assert p.remat_policy == "stash" or not kinds \
            or "dense" not in stash_kinds(p, mesh)
        assert stash_plan(p, mesh)["dense"] == (0, 0)
        assert dense_executions(p, mesh) == 0 and saving(p, mesh) == []

    if case == "from_the_end":
        # SALA's four MLPs at 1,073,741,824 bytes each: as the chip grows the
        # rule takes block 7, then 5, then 3, then 1 — never an earlier one
        # before a later
        p = _cell_params(_SALA)
        assert [o.nbytes for o in offers(p, "dense")] == [_SALA_MLP] * 4
        held = 72351744 + 8 * 16384 * 4096 * 2
        from homebrewnlp_tpu.utils import flops
        for executions, blocks in enumerate(([], [7], [5, 7], [3, 5, 7],
                                             [1, 3, 5, 7])):
            limit = int((held + executions * _SALA_MLP + 1000) / 0.15) + 7
            with pytest.MonkeyPatch.context() as patch:
                patch.setattr(flops, "hbm_capacity",
                              lambda device=None, v=limit: (v, "test"))
                assert stash_plan(p)["dense"] == (executions,
                                                  executions * _SALA_MLP)
                assert saving(p) == blocks
                assert stash_plan(p)["attention"] == (1, 72351744)
    elif case == "block_inputs_count":
        # Ouro: 2.537 GB - 1.636 GB of (out, lse) would hold four executions
        # of 184.5 MB by the kinds alone; the 96 block inputs [2, 4096, 2048]
        # the strategy itself keeps are 3.2 GB: nothing is left
        p = _cell_params(_OURO)
        one = offers(p, "dense")[0].nbytes
        assert one == 2 * 2 * 4096 * 5632 * 2
        assert (budget - 1635778560) // one == 4
        assert 96 * 2 * 4096 * 2048 * 2 == 3221225472 > budget
        declines(p)
        # granite: 20 inputs of [1, 8192, 2048] take 0.671 GB of the 1.247 GB
        # the attention kind and (PR 71) the nine in-projection outputs
        # leave: two executions where four would fit
        p = _cell_params(_GRANITE)
        left = budget - 34603008 - 9 * _GRANITE_PROJ
        assert left // _GRANITE_MLP == 4
        assert (left - 20 * 8192 * 2048 * 2) // _GRANITE_MLP == 2
        assert stash_plan(p)["dense"] == (2, 2 * _GRANITE_MLP)
        assert saving(p) == [17, 19]
    elif case == "earlier_kind_declined_for_size":
        # the experts kind over the budget takes none of it and (PR 61)
        # moves no other kind's decision: the cell's last MLP, as in the cell
        p, experts = _with_moe(8)
        assert experts > budget
        assert stash_plan(p)["recurrent"] == (3, 3 * _OLMO_LAYER)
        assert stash_plan(p)["dense"] == (1, _OLMO_MLP)
        assert saving(p) == [7]
        # Laguna: its one ``mlp`` is an input block, which no region holds
        p = _cell_params(_LAGUNA)
        assert offers(p, "dense") == []
        declines(p)
    elif case == "earlier_kinds_leave_less":
        # decided LAST: experts at top-2 (the parent's plan, unmoved), the
        # rule's outputs and the flash pair leave 0.15 GB, less than the
        # eight block inputs [1, 16384, 3840] alone
        p, experts = _with_moe(2)
        left = budget - experts - 3 * _OLMO_LAYER - _OLMO_FLASH
        assert 0 < left < 8 * 16384 * 3840 * 2
        assert stash_plan(p) == {**_IDLE, "experts": (1, experts),
                                 "recurrent": (3, 3 * _OLMO_LAYER),
                                 "attention": (1, _OLMO_FLASH)}
        declines(p)
    elif case == "stash":
        # explicit: every execution, whatever the bytes — 12 x 4 passes
        p = _cell_params(_OURO, remat_policy="stash")
        one = offers(p, "dense")[0].nbytes
        assert stash_plan(p)["dense"] == (48, 48 * one)
        assert saving(p) == list(range(1, 96, 2))
    elif case in ("recompute", "legacy_false"):
        declines(_cell_params(_GRANITE, **(
            {"remat_policy": "recompute"} if case == "recompute"
            else {"stash_attention_outputs": False})))
    elif case in ("revnet", "none"):
        # no jax.checkpoint to ride ("none" has no replay)
        for kw in ({}, {"remat_policy": "stash"}):
            declines(_cell_params(_OLMO, memory_reduction_strategy=case,
                                  **kw))
    elif case == "pipe_mesh":
        from homebrewnlp_tpu.core.sharding import PIPE_AXIS

        class Piped:
            devices = None
            shape = {PIPE_AXIS: 2}

        for kw in ({}, {"remat_policy": "stash"}):
            # (the kinds are the mesh-blind rules'; the plan is the step's)
            declines(_cell_params(_GRANITE, **kw), Piped(), kinds=False)
    elif case == "scan_layers":
        # a scanned body traces ONE block for all its iterations: all the
        # step's executions or none.  Two periods of granite at 4,096
        # positions are 20 MLPs, 40 block inputs and (PR 71) 18 in-projection
        # outputs of half the cell's bytes each
        half = {"depth": 2, "sequence_length": 4096}
        p = _cell_params(_GRANITE, **half)
        assert stash_plan(p)["recurrent"] == (18, 9 * _GRANITE_PROJ)
        assert stash_plan(p)["dense"] == (4, 2 * _GRANITE_MLP)
        p = _cell_params(_GRANITE, scan_layers=True, **half)
        assert stash_plan(p)["recurrent"] == (18, 9 * _GRANITE_PROJ)
        declines(p)
        # (the recurrent kind likewise: 17 of the 18 at the cell's sequence,
        # none of them scanned)
        assert stash_plan(_cell_params(_GRANITE, depth=2))["recurrent"] \
            == (17, 17 * _GRANITE_PROJ)
        assert stash_plan(_cell_params(_GRANITE, depth=2, scan_layers=True)
                          )["recurrent"] == (0, 0)
        p = _cell_params(_GRANITE, depth=2, scan_layers=True,
                         sequence_length=1024)
        assert stash_plan(p)["dense"] == (20, 20 * _GRANITE_MLP // 8)
        assert saving(p) == list(range(1, 40, 2))
    elif case == "macro_batching":
        # two micro-batches hold two sets of everything: at half the cell's
        # sequence, what the cell holds
        p = _cell_params(_GRANITE, macro_batching=2, sequence_length=4096)
        assert (budget - 34603008 - 9 * _GRANITE_PROJ
                - 20 * 8192 * 2048 * 2) // _GRANITE_MLP == 2
        assert stash_plan(p)["dense"] == (2, 2 * _GRANITE_MLP)
        assert saving(p) == [17, 19]
        # at the cell's own (PR 71) eight of the nine in-projection outputs
        # fit, and leave the dense kind less than the block inputs
        p = _cell_params(_GRANITE, macro_batching=2)
        assert stash_plan(p)["recurrent"] == (8, 2 * 8 * _GRANITE_PROJ)
        assert stash_plan(p)["dense"] == (0, 0)
    elif case == "input_block_offers_nothing":
        # the input and output blocks run outside any region: an ``mlp``
        # there is neither offered nor counted
        blocks = [{"layer": list(b.layer), "skip": b.skip}
                  for b in _cell_params(_GRANITE).block_config]
        p = _cell_params(_GRANITE, input_block_config=blocks[1:2])
        assert stash_plan(p)["dense"] == (2, 2 * _GRANITE_MLP)
    else:
        # a block's layers share their names: its executions go together.
        # Granite's last two MLPs in one block: 19 regions, and what the
        # nine in-projection outputs leave (PR 71), 0.609 GB, holds the pair
        # (0.537 GB) ...
        blocks = [{"layer": list(b.layer), "skip": b.skip}
                  for b in _cell_params(_GRANITE).block_config]
        blocks[17]["layer"] += blocks[19]["layer"]
        p = _cell_params(_GRANITE, block_config=blocks[:19])
        assert stash_plan(p)["dense"] == (2, 2 * _GRANITE_MLP)
        assert saving(p) == [17]
        # ... and not the last four in one block of 15 (1.074 GB for the
        # 1.023 GB seven in-projection outputs leave), of which three would
        # fit one at a time
        blocks[13]["layer"] += blocks[15]["layer"] + blocks[17]["layer"]
        p = _cell_params(_GRANITE, block_config=blocks[:15])
        assert stash_plan(p)["recurrent"] == (7, 7 * _GRANITE_PROJ)
        left = budget - 34603008 - 7 * _GRANITE_PROJ - 15 * 8192 * 2048 * 2
        assert 3 * _GRANITE_MLP <= left < 4 * _GRANITE_MLP
        declines(p)


_SALA_MLP = 2 * 16384 * 16384 * 2


# ---- one rule for the ``checkpoint`` strategy's kinds (PR 61): each judged on
# its own bytes in the order attention, experts, recurrent, dense; the
# recurrent kind, like the dense one, an execution at a time from the step's
# end — an offer's first part, then the interior of those that ride ------------

_KIMI = "train_kimi_linear_ep32_s16k"
#: the Kimi-Linear cell's latent attention: out [1, 16384, 32, 128] bfloat16 +
#: lse [32, 16384] float32
_KIMI_FLASH = 16384 * 32 * (128 * 2 + 4)
#: one ``kda`` layer's offer: the rule's output o [1, 16384, 32, 128] bfloat16
#: and, where its rule is the Pallas pairs, the interior: q~, k~ the same,
#: gamma the same in float32, A and the inverse [1, 256, 32, 64, 64] float32,
#: A' the same in bfloat16, the entering states [1, 256, 32, 128, 128] bfloat16
_KIMI_OUT = 16384 * 32 * 128 * 2
_KIMI_INSIDE = 2 * _KIMI_OUT + 2 * _KIMI_OUT \
    + 256 * 32 * 64 * 64 * (4 + 4 + 2) + 256 * 32 * 128 * 128 * 2
#: its four sparse layers' row buffers and routing
_KIMI_EXPERTS = 4569694352
_KDA_INSIDE = ("kda_strict", "kda_mixed", "kda_gamma", "kda_q_unit",
               "kda_k_unit", "kda_solved", "kda_states")


@pytest.fixture
def kda_kernels(monkeypatch):
    """Layer ``kda`` reads its predicate as a TPU process does: its offer has
    the interior of its Pallas pairs."""
    from homebrewnlp_tpu.model import kda
    from homebrewnlp_tpu.parallel.kda_rule import kda_kernel_applies
    monkeypatch.setattr(kda, "kda_kernel_applies", functools.partial(
        kda_kernel_applies, backend="tpu"))


def _at_limit(monkeypatch, limit: int):
    from homebrewnlp_tpu.utils import flops
    monkeypatch.setattr(flops, "hbm_capacity",
                        lambda device=None: (limit, "memory_stats"))


@pytest.mark.parametrize("cell,plan", [
    ("train_32big_mixer_b32", {}),
    ("train_32big_mixer_dp2tp2", {"bottleneck": (32, 2147483648)}),
    ("train_1b_long_context_s16k", {"attention": (8, 2155872256)}),
    ("train_olmoe_1b_7b_s4k", {"attention": (2, 68157440),
                               "experts": (2, 1075315200)}),
    # PR 71: the nine in-projection outputs, and two MLPs for six
    ("train_granite_4_0_h_micro_long", {"attention": (1, 34603008),
                                        "recurrent": (9, 1255145472),
                                        "dense": (2, 536870912)}),
    ("train_olmo_hybrid_7b_long", {"attention": (1, 127795200),
                                   "recurrent": (3, 566231040),
                                   "dense": (1, 721420288)}),
    # the parent: nothing (the experts' 5.38 GB declined, and all with them)
    ("train_laguna_s_2_1_ep32_s8k", {"attention": (1, 204472320)}),
    ("train_zaya1_8b_ep2_s16k", {"attention": (8, 272629760),
                                 "experts": (8, 1612185888)}),
    ("train_minicpm_sala_tp2_long", {"attention": (1, 72351744),
                                     "dense": (1, 1073741824)}),
    ("train_ouro_2_6b_loop4_s4k", {"attention": (48, 1635778560)}),
    # PR 71: the five in-projection outputs [1, 16384, 9280] bfloat16
    ("train_nemotron_3_super_tp2_ep64_s16k", {"attention": (1, 68157440),
                                              "experts": (5, 180224180),
                                              "recurrent": (5, 1520435200)}),
    # the parent: recurrent (4, 536870912), the four outputs, and nothing
    # else (the experts' 4.57 GB declined); now the flash pair and, beside
    # the four outputs, the LAST layer's interior of 1,140,850,688 bytes
    (_KIMI, {"attention": (1, 136314880), "recurrent": (4, 1677721600)})])
def every_cells_plan_test(cell, plan, monkeypatch, kda_kernels):
    """``stash_plan`` of all twelve cells' configurations at the table's
    16,911,433,728 bytes a chip: ten as the parent of PR 61 read them (ISSUE
    61's table), Laguna's global flash pair and the Kimi-Linear cell's pair
    and last ``kda`` interior as the one rule admits them; since PR 71 the
    two cells with a ``mamba`` layer with its in-projection outputs (ISSUE
    71's table)."""
    from benchmark.lib.cell import load_cell
    from homebrewnlp_tpu.core import sharding as shardlib
    from homebrewnlp_tpu.model.remat import stash_plan
    _at_limit(monkeypatch, _CHIP_LIMITS[0])
    p = _cell_params(cell)
    mesh = None
    if load_cell(cell).chips > 1:
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 virtual devices")
        mesh = shardlib.build_mesh(p, jax.devices()[:4])
    assert stash_plan(p, mesh) == {**_IDLE, **plan}


def _holding(p, name: str) -> typing.List[int]:
    """The regions whose policy saves ``name``."""
    from homebrewnlp_tpu.model.blocks import (_named_policy,
                                              _region_policies)
    from homebrewnlp_tpu.model.remat import region_names
    names, policies = region_names(p), _region_policies(p)
    assert len(names) == len(policies) \
        == len(p.block_config) * p.depth * p.loop_steps
    assert all(policy is _named_policy(p.gradient_checkpointing_policy, held)
               for policy, held in zip(policies, names))
    return [r for r, held in enumerate(names) if name in held]


@pytest.mark.parametrize("case", [
    "no_output", "two_outputs", "no_interior", "one_interior",
    "two_interiors", "all", "off_the_tpu", "dense_takes_what_is_left",
    "scan_layers", "stash", "recompute"])
def recurrent_admission_test(case, monkeypatch, kda_kernels):
    """The recurrent kind an execution at a time from the step's LAST
    backwards (the Kimi-Linear cell's ``kda`` blocks are regions 0, 2, 4 and
    8 of ten): the offers' first part — none, some and all of the rule's
    outputs as the chip grows, never an earlier one before a later — then the
    interior of the executions that ride, likewise; no part of a part; all or
    none under ``scan_layers``; every execution under ``"stash"``."""
    from homebrewnlp_tpu.model import kda
    from homebrewnlp_tpu.model.declare import offers
    from homebrewnlp_tpu.model.remat import (STASH_HBM_FRACTION, region_names,
                                             stash_kinds, stash_plan)

    def limit_for(nbytes: int) -> int:
        return int((nbytes + 1000) / STASH_HBM_FRACTION) + 7

    def rides(outputs: int, interiors: int, first: int, inside: int):
        assert stash_plan(p)["recurrent"] == (
            outputs, outputs * _KIMI_OUT + interiors * _KIMI_INSIDE)
        assert _holding(p, "kda_out") == list(range(first, 10))
        for name in _KDA_INSIDE:
            assert _holding(p, name) == list(range(inside, 10))

    p = _cell_params(_KIMI)
    if case not in ("off_the_tpu", "scan_layers"):
        offered = offers(p, "recurrent")
        assert [(o.names, o.nbytes, o.interior_names, o.interior_nbytes)
                for o in offered] == [(("kda_out",), _KIMI_OUT, _KDA_INSIDE,
                                       _KIMI_INSIDE)] * 4
        assert _KIMI_OUT + _KIMI_INSIDE == 1275068416
        assert ("kda_out",) + _KDA_INSIDE == kda.SAVED_NAMES
    if case == "no_output":
        # an output less a byte beside the flash pair: no part of a part
        _at_limit(monkeypatch, limit_for(_KIMI_FLASH + _KIMI_OUT - 2000))
        assert stash_kinds(p) == {"attention"}
        assert stash_plan(p) == {**_IDLE, "attention": (1, _KIMI_FLASH)}
        rides(0, 0, 10, 10)
        assert _holding(p, "flash_out") == list(range(10))
    elif case == "two_outputs":
        _at_limit(monkeypatch, limit_for(_KIMI_FLASH + 2 * _KIMI_OUT))
        rides(2, 0, 4, 10)
    elif case == "no_interior":
        # the four outputs and an interior less a byte
        _at_limit(monkeypatch, limit_for(_KIMI_FLASH + 4 * _KIMI_OUT
                                         + _KIMI_INSIDE - 2000))
        rides(4, 0, 0, 10)
        assert stash_kinds(p) == {"attention", "recurrent"}
    elif case == "one_interior":
        # the chip as it is: from the last ``kda`` block on (a block without
        # the layer names nothing by them)
        _at_limit(monkeypatch, _CHIP_LIMITS[0])
        rides(4, 1, 0, 8)
        assert stash_plan(p) == {**_IDLE, "attention": (1, _KIMI_FLASH),
                                 "recurrent": (4, 1677721600)}
        assert region_names(p)[7] == ("kda_out",) + _FLASH_NAMES
        assert region_names(p)[9] == ("kda_out",) + _KDA_INSIDE \
            + _FLASH_NAMES
    elif case == "two_interiors":
        _at_limit(monkeypatch, limit_for(_KIMI_FLASH + 4 * _KIMI_OUT
                                         + 2 * _KIMI_INSIDE))
        rides(4, 2, 0, 4)
    elif case == "all":
        # (a chip that holds all four holds the experts' row buffers, decided
        # before them, too)
        _at_limit(monkeypatch, limit_for(
            _KIMI_FLASH + _KIMI_EXPERTS + 4 * (_KIMI_OUT + _KIMI_INSIDE)))
        rides(4, 4, 0, 0)
        assert stash_plan(p)["experts"] == (4, _KIMI_EXPERTS)
        assert region_names(p)[0] == _MOE_NAMES + ("kda_out",) \
            + _KDA_INSIDE + _FLASH_NAMES
    elif case == "off_the_tpu":
        # the XLA form's offer has no interior: the four outputs, and the
        # MLP's gate and up [1, 16384, 9216] x 2 after the ten block inputs
        # [1, 16384, 2304]
        monkeypatch.undo()
        _at_limit(monkeypatch, _CHIP_LIMITS[0])
        assert [(o.names, o.nbytes, o.interior_names, o.interior_nbytes)
                for o in offers(p, "recurrent")] \
            == [(("kda_out",), _KIMI_OUT, (), 0)] * 4
        assert stash_plan(p) == {**_IDLE, "attention": (1, _KIMI_FLASH),
                                 "recurrent": (4, 4 * _KIMI_OUT),
                                 "dense": (1, 2 * 16384 * 9216 * 2)}
        assert _holding(p, "kda_out") == list(range(10))
        assert _holding(p, "mlp_gate") == [1]
    elif case == "dense_takes_what_is_left":
        # decided after it, from what it took: while an interior more fits,
        # the ten block inputs and the MLP's gate and up (1.36 GB) do not; a
        # chip whose 15% holds every earlier kind whole, the inputs and the
        # MLP admits it
        inputs = 10 * 16384 * 2304 * 2
        mlp = 2 * 16384 * 9216 * 2
        assert _KIMI_INSIDE < inputs + mlp
        for short, dense in ((2000, (0, 0)), (0, (1, mlp))):
            _at_limit(monkeypatch, limit_for(
                _KIMI_FLASH + _KIMI_EXPERTS + 4 * (_KIMI_OUT + _KIMI_INSIDE)
                + inputs + mlp - short))
            rides(4, 4, 0, 0)
            assert stash_plan(p)["dense"] == dense
        assert _holding(p, "mlp_gate") == [1]
    elif case == "scan_layers":
        # a scanned body traces ONE block for all its iterations: Olmo-Hybrid
        # at its published depth offers 24 outputs, 4.5 GB — unrolled the
        # last eight ride, scanned none; at an eighth of the sequence all
        p = _cell_params(_OLMO, depth=8)
        assert stash_plan(p)["recurrent"] == (8, 8 * _OLMO_LAYER)
        p = _cell_params(_OLMO, depth=8, scan_layers=True)
        assert stash_plan(p)["recurrent"] == (0, 0)
        assert _holding(p, "gated_delta_out") == []
        p = _cell_params(_OLMO, depth=8, scan_layers=True,
                         sequence_length=2048)
        assert stash_plan(p)["recurrent"] == (24, 24 * _OLMO_LAYER // 8)
        assert _holding(p, "gated_delta_out") == list(range(64))
    elif case == "stash":
        # explicit: every execution and its interior, whatever the bytes
        _at_limit(monkeypatch, limit_for(0))
        p = _cell_params(_KIMI, remat_policy="stash")
        rides(4, 4, 0, 0)
    else:
        p = _cell_params(_KIMI, remat_policy="recompute")
        assert stash_plan(p) == _IDLE and region_names(p) == [()] * 10
