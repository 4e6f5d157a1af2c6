"""The declaration seam (PR 42): a layer declares what it reports, offers and
runs (``model/declare.py``); the trainer and the memory rule read
declarations.  What every cell and configuration file prints and publishes at
start-up is written down a file under ``tests/pins/startup/``, how each
statistic folds here: nothing below builds a model or compiles a step."""
import json
import os
import types

import jax
import jax.numpy as jnp
import pytest

from homebrewnlp_tpu import telemetry
from homebrewnlp_tpu.config import ModelParameter
from homebrewnlp_tpu.core import sharding as shardlib
from homebrewnlp_tpu.model import declare
from homebrewnlp_tpu.train import _LAYER_STATS, Trainer, _info_metrics

import harness
from remat_policy_test import _cell_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


#: the facts that read 0 where no layer has the mechanism; the others have no
#: series there
_ALWAYS = ("hbnlp_ssd_state_bytes", "hbnlp_mamba_conv_kernel_layers",
           "hbnlp_delta_solve_kernel_layers", "hbnlp_delta_rule_kernel_layers",
           "hbnlp_ssd_scan_kernel_layers", "hbnlp_flash_band_layers",
           "hbnlp_flash_backward_one_pass_layers")
_SPARSE = ("hbnlp_moe_held_rows_bound", "hbnlp_router_carry_bytes",
           "hbnlp_flash_scored_over_live_pairs",
           "hbnlp_index_loss_kernel_layers",
           "hbnlp_index_loss_walked_over_visible_pairs",
           "hbnlp_denoise_stream_positions")


@pytest.fixture
def fresh_registry():
    prev = telemetry.set_registry(telemetry.Registry())
    yield telemetry.registry()
    telemetry.set_registry(prev)


def _startup(params, mesh=None):
    """``{"line", "series": {metric: {labels: value}}}`` of a trainer's
    start-up, as the files under ``tests/pins/startup/`` hold it."""
    line = Trainer(params, None, mesh).publish_stash_plan()
    return {"line": line, "series": {
        name: {",".join(labels): value
               for labels, value in entry["series"].items()}
        for name, entry in telemetry.snapshot().items()}}


@pytest.mark.parametrize("cell", harness.train_cells())
def cell_startup_is_the_parents_test(cell, monkeypatch, fresh_registry):
    """Each cell's ``remat stash:`` line, to the byte, and every start-up
    series with its value, as a TPU process reads them: what the pin of the
    cell's configuration file holds for a TPU, or for this cell where its
    overrides move them."""
    from benchmark.lib.cell import load_cell
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    params = _cell_params(cell)
    mesh = None
    if load_cell(cell).chips > 1:
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 virtual devices")
        mesh = shardlib.build_mesh(params, jax.devices()[:4])
    pin = harness.startup_pin(harness.cell_config_file(cell))
    assert _startup(params, mesh) == pin.get("cells", {}).get(cell,
                                                              pin["tpu"])


@pytest.mark.parametrize("path", harness.config_files())
def every_configuration_file_starts_as_on_the_parent_test(path, monkeypatch,
                                                          fresh_registry):
    """A file under ``configs/`` or ``benchmark/configs/``: the line and the
    series of its own pin, on a TPU and on the CPU."""
    with open(os.path.join(REPO, path)) as f:
        config = json.load(f)
    config = config.get("config", config)
    pin = harness.startup_pin(path)
    for backend in ("tpu", "cpu"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        telemetry.set_registry(telemetry.Registry())
        assert _startup(ModelParameter(
            {**config, "model_path": "/tmp/declare_test"})) == pin[backend], \
            backend


# ---- statistics ------------------------------------------------------------

#: what three moe layers, two top-1 routers, two cca, two mamba and two
#: gated_delta layers appended, merged as ``Model.apply`` merges them
_LAYER_STATS_IN = {
    "moe_load_max_over_mean": [1.5, 2.5, 1.25],
    "moe_routed_pairs": [1024.0, 1024.0, 2048.0],
    "moe_held_pairs": [256.0, 512.0, 256.0],
    "moe_held_row_tiles": [1.0, 1.0, 1.0],
    "moe_held_bound_tiles": [2.0, 2.0, 8.0],
    "moe_top1_weight_mean": [0.3, 0.2],
    "cca_logit_scale": [3.0, 7.0],
    "ssd_log_decay_min": [-3.0, -9.0],
    "delta_transform_abs_max": [2.0, 11.0],
    "kda_log_decay_min": [-40.0, -144.0],
    "sparse_kept_key_share": [0.75, 0.5],
    "sparse_choosing_query_share": [0.5, 0.75],
    "index_loss": [0.25, 0.75],
    "index_score_abs_max": [3.0, 5.0],
    "lightning_state_abs_max": [4.0, 9.0]}


def _info(layer_stats):
    zero = types.SimpleNamespace(data=jnp.float32(0))
    return types.SimpleNamespace(
        layer_stats=layer_stats and {k: jnp.asarray(v, jnp.float32)
                                     for k, v in layer_stats.items()},
        total_loss=zero, token_loss=None, video_loss=None, accuracy=None)


@pytest.mark.parametrize("name,kind,metric,value", [
    ("moe_load_max_over_mean", "gauge", "hbnlp_moe_load_max_over_mean", 2.5),
    ("moe_routed_pairs", "counter", "hbnlp_moe_routed_pairs_total", 4096.0),
    ("moe_held_pairs", "counter", "hbnlp_moe_held_pairs_total", 1024.0),
    ("moe_held_pair_share", "gauge", "hbnlp_moe_held_pair_share", 0.25),
    ("moe_held_pair_share_max", "gauge", "hbnlp_moe_held_pair_share_max", 0.5),
    ("moe_held_row_tiles", "counter", "hbnlp_moe_held_row_tiles_total", 3.0),
    ("moe_held_tile_share", "gauge", "hbnlp_moe_held_tile_share", 0.25),
    ("moe_top1_weight_mean", "gauge", "hbnlp_moe_top1_weight_mean",
     0.20000000298023224),
    ("cca_logit_scale_max", "gauge", "hbnlp_cca_logit_scale_max", 7.0),
    ("ssd_log_decay_min", "gauge", "hbnlp_ssd_log_decay_min", -9.0),
    ("delta_transform_abs_max", "gauge", "hbnlp_delta_transform_abs_max",
     11.0),
    # PR 58: layer kda's own statistic (it shares the one above)
    ("kda_log_decay_min", "gauge", "hbnlp_kda_log_decay_min", -144.0),
    ("sparse_kept_key_share", "gauge", "hbnlp_sparse_kept_key_share", 0.5),
    ("sparse_choosing_query_share", "gauge",
     "hbnlp_sparse_choosing_query_share", 0.75),
    ("lightning_state_abs_max", "gauge", "hbnlp_lightning_state_abs_max", 9.0),
    # PR 62: attention flag indexed's own two (the MEAN over the layers, and
    # the largest)
    ("index_loss", "gauge", "hbnlp_index_loss", 0.5),
    ("index_score_abs_max", "gauge", "hbnlp_index_score_abs_max", 5.0)])
def declared_statistic_folds_as_on_the_parent_test(name, kind, metric, value):
    """One declared statistic: the parent's fold over the layers, its kind,
    its metric name and (by digest) its help text."""
    assert float(_info_metrics(_info(_LAYER_STATS_IN))[name]) == value
    stat = _LAYER_STATS[name]
    assert (stat.kind, stat.metric) == (kind, metric)
    harness.pinned("help/" + name, stat.help)


def statistics_are_all_declared_test():
    """The trainer's table is the declarations': nineteen statistics of
    layers (PR 54: the selection bias's two; PR 58: layer ``kda``'s log-decay,
    and its transform under ``gated_delta``'s name; PR 62: attention flag
    ``indexed``'s index loss and largest kept score), (PR 49) three of a
    looped model's loss and (PR 65) two of a multi-token-prediction
    module's, (PR 72) the live share of a relu-gated sparse layer's gate
    values, and a step whose layers report nothing (or only some) has only
    those."""
    assert len(_LAYER_STATS) == 28 == len(declare.stats())
    assert {name for name in _LAYER_STATS if name.startswith("denoise_")} == {
        "denoise_masked_share", "denoise_weight_mean", "denoise_loss"}
    assert {name for name in _LAYER_STATS if name.startswith("mtp_")} == {
        "mtp_loss", "mtp_loss_over_main"}
    assert {"moe_bias_abs_max", "moe_all_load_max_over_mean"} \
        <= set(_LAYER_STATS)
    assert {name for name in _LAYER_STATS if name.startswith("loop_")} == {
        "loop_pass_loss", "loop_exit_share", "loop_exit_entropy"}
    base = {"loss", "token_loss", "video_loss", "accuracy"}
    assert set(_info_metrics(_info(None))) == base
    some = {"ssd_log_decay_min": [-1.0]}
    assert set(_info_metrics(_info(some))) == base | {"ssd_log_decay_min"}
    # a layer that holds no share reports no held pairs: no share either
    routed = {k: _LAYER_STATS_IN[k]
              for k in ("moe_load_max_over_mean", "moe_routed_pairs")}
    assert set(_info_metrics(_info(routed))) == base | set(routed)


def publish_layer_stats_reads_the_declarations_test(fresh_registry):
    """``Trainer._publish_layer_stats``: gauges set, counters added, by the
    declared names, from steps the device has finished."""
    params = _cell_params("train_32big_mixer_b32")
    trainer = Trainer(params, None)
    metrics = _info_metrics(_info(_LAYER_STATS_IN))
    trainer._publish_layer_stats(metrics)
    trainer._publish_layer_stats(metrics)
    snap = telemetry.snapshot()
    assert snap["hbnlp_moe_routed_pairs_total"]["series"][()] == 2 * 4096.0
    assert snap["hbnlp_moe_held_pair_share"]["series"][()] == 0.25
    assert snap["hbnlp_cca_logit_scale_max"]["series"][()] == 7.0
    assert not trainer._pending_layer_stats


def flash_scored_over_live_gauge_test(monkeypatch):
    """PR 55: at the Ouro shapes (4,096 positions: forward 1,024 x 2,048
    tiles, backward 1,024 x 1,024) the kernels score 1.25 / 1.125 of the live
    pairs — scored whole, the six forward and ten backward live cells were
    1.5 / 1.25 of them — and the gauge has no series where no call reaches
    the tiled causal kernels: the CPU, the map mixer, the selected kernels."""
    from homebrewnlp_tpu.model import spatial
    from homebrewnlp_tpu.parallel import flash_attention as fa
    ouro = _cell_params("train_ouro_2_6b_loop4_s4k")
    assert spatial.flash_scored_over_live(ouro, "tpu") == {"fwd": 1.25,
                                                           "bwd": 1.125}
    live = fa.live_pairs(4096)
    assert (6 * 1024 * 2048 / live, 10 * 1024 * 1024 / live) == (1.5, 1.25)
    assert spatial.flash_scored_over_live(ouro, "cpu") is None
    assert spatial.flash_scored_over_live(ouro) is None
    for cell in ("train_32big_mixer_b32", "train_minicpm_sala_tp2_long"):
        assert spatial.flash_scored_over_live(_cell_params(cell),
                                              "tpu") is None
    # a windowed layer's backward counts, its band forward does not
    assert spatial.flash_scored_over_live(
        _cell_params("train_laguna_s_2_1_ep32_s8k"), "tpu") == {
        "fwd": 1.125, "bwd": 6094848 / 4059392}


# ---- the registry of declarations -----------------------------------------

def facts_are_declared_once_in_line_order_test():
    facts = declare.facts()
    assert [fact.metric for fact in facts] == [
        "hbnlp_ssd_state_bytes", "hbnlp_mamba_conv_kernel_layers",
        "hbnlp_delta_solve_kernel_layers", "hbnlp_delta_rule_kernel_layers",
        "hbnlp_ssd_scan_kernel_layers", "hbnlp_moe_held_rows_bound",
        "hbnlp_router_carry_bytes", "hbnlp_flash_band_layers",
        "hbnlp_flash_scored_over_live_pairs",
        "hbnlp_index_loss_kernel_layers",
        "hbnlp_index_loss_walked_over_visible_pairs",
        "hbnlp_flash_backward_one_pass_layers",
        "hbnlp_denoise_stream_positions"]
    assert [fact.metric for fact in facts if fact.zero] == list(_ALWAYS)
    assert [fact.metric for fact in facts if not fact.zero] == list(_SPARSE)
    assert len({fact.place for fact in facts}) == len(facts)


@pytest.mark.parametrize("layer,kind,names", [
    ("moe", "experts", ("moe_gate", "moe_up", "moe_down", "moe_order",
                        "moe_inverse", "moe_sizes", "moe_experts")),
    # PR 54: a plain expert has no gate to save; a latent layer offers its
    # combined sum a token in the place of the pairs' rows
    ("moe-relu2-plain", "experts",
     ("moe_up", "moe_down", "moe_order", "moe_inverse", "moe_sizes",
      "moe_experts")),
    ("moe-relu2-plain-latent-sigmoid_bias-shared_expert", "experts",
     ("moe_order", "moe_inverse", "moe_sizes", "moe_experts",
      "moe_latent_sum")),
    ("gated_delta", "recurrent", ("gated_delta_out",)),
    # PR 71: its in-projection's output (nothing until then)
    ("mamba", "recurrent", ("mamba_in_proj",)),
    ("attention-nope", "attention", ("flash_out", "flash_lse")),
    ("cca-q_heads8-kv_heads2", "attention", ("flash_out", "flash_lse")),
    ("bottleneck_group_linear-in:relu", "bottleneck", ()),
    ("mlp-silu", "dense", ("mlp_gate", "mlp_up")),
    ("lightning", None, None), ("norm-shift-scale", None, None),
    ("attention-biased_attention_map-absolute-input_as_value", None, None),
    ("bottleneck_group_linear-in:mixture_of_experts", None, None)])
def layer_offers_its_kind_test(layer, kind, names):
    """What a layer offers a memory strategy, by the layer's own
    declaration: the kind, the names it tags."""
    from homebrewnlp_tpu.model.frontend import LAYER_FUNCTIONS
    params = _cell_params("train_olmo_hybrid_7b_long")
    name, *extras = layer.split("-")
    spec = getattr(LAYER_FUNCTIONS[name], "declares", declare.Layer())
    offer = spec.offer(params, set(extras)) if spec.offer else None
    if kind is None:
        assert offer is None
    else:
        assert (offer.kind, offer.names) == (kind, names)
        assert offer.nbytes > 0 and offer.count >= 1
    if kind == "dense":
        # gate and up, [1, 16384, 11008] in bfloat16 each
        assert (offer.nbytes, offer.count) == (2 * 16384 * 11008 * 2, 2)
