"""The declaration seam (PR 42): a layer declares what it reports, offers and
runs (``model/declare.py``); the trainer and the memory rule read
declarations.  What the eight cells print and publish at start-up, and how
each statistic folds, are WRITTEN DOWN HERE FROM THE PARENT (commit 357d03a,
before the refactor): nothing below builds a model or compiles a step."""
import glob
import hashlib
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

from remat_policy_test import _cell_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _kinds(attention=(0, 0), bottleneck=(0, 0), experts=(0, 0),
           recurrent=(0, 0), dense=(0, 0), unit="layers"):
    plan = {"attention": attention, "bottleneck": bottleneck,
            "experts": experts, "recurrent": recurrent, "dense": dense}
    return ("remat stash: " + "; ".join(
        f"{kind} {layers} {unit}, {nbytes} bytes a device"
        for kind, (layers, nbytes) in plan.items()), plan)


#: cell -> (the kinds' part of the line and the plan, the rest of the line,
#: the start-up gauges beside the stash's) on a TPU, from the parent; PR 52
#: added the fifth kind to every line and the two gauges, ``dense``: 0 / 0
#: but in the three cells named below; PR 55 added, at the end of the line of
#: every cell that runs the tiled causal flash kernels, what they score over
#: what their calls have to (``_scored``): before it an edge cell was scored
#: whole and the Ouro shapes read 1.5 / 1.25
def _scored(fwd, bwd):
    return (f"; flash scored over live pairs fwd {fwd:.6g} bwd {bwd:.6g}",
            {"hbnlp_flash_scored_over_live_pairs": {("fwd",): fwd,
                                                    ("bwd",): bwd}})


def _one_pass(scored, layers):
    """PR 68 added, after ``scored``'s part of the line, how many attention
    layers run their flash backward as the one-pass kernel
    (``hbnlp_flash_backward_one_pass_layers``; the long-context cell's
    eight, at head width 512, were on the split pair, 0, until PR 73 held a
    head's dq resident in place of its dk and dv)."""
    return (scored[0] + f"; flash backward one pass {layers} layers",
            {**scored[1], "hbnlp_flash_backward_one_pass_layers": layers})


#: 4,096 positions (forward 1,024 x 2,048 tiles, backward 1,024 x 1,024),
#: 8,192 and 16,384
_S4K, _S8K, _S16K = (_scored(1.25, 1.125), _scored(1.125, 1.0625),
                     _scored(1.0625, 1.03125))
#: Laguna: the global layers' forward; the windowed layers' backward at 512 x
#: 512 tiles under a window of 512 (three quadrants of either cell of a band:
#: 93 x 65,536 x 6 / 4 pairs over 4,059,392), their forward the band kernel
_LAGUNA = _scored(1.125, 6094848 / 4059392)
#: the block-diffusion mask at 8,192 trained tokens, block 4: the far part's
#: causal tiles over both halves and the own blocks' 2 x 8,192 x 4 pairs over
#: 8,192 x 8,196 live pairs (forward 1,024 x 2,048 tiles, backward 1,024 x
#: 1,024)
_SDAR = _scored((2 * 37748736 + 65536) / 67141632,
                (2 * 35651584 + 65536) / 67141632)
#: SmallThinker: the global layers' forward at 16,384; the windowed layers'
#: backward at 512 x 512 tiles under a window of 4,096 (62,390,272 pairs
#: scored over ``flash_attention.live_pairs``' 58,714,112), their forward the
#: band kernel
_SMALLTHINKER = _scored(1.0625, 62390272 / 58714112)
_CELLS = {
    "train_32big_mixer_b32": (_kinds(), "", {}),
    "train_32big_mixer_dp2tp2": (
        _kinds(bottleneck=(32, 2147483648)), "", {}),
    "train_1b_long_context_s16k": (
        # PR 73: the eight backwards are the one pass with dq resident, at k
        # tiles of 512 (head width 512: ``one_pass_tiles``), so a diagonal
        # cell pair scores 10 of its 16 sub-squares of 256
        _kinds(attention=(8, 2155872256)),
        *_one_pass(_scored(1.0625, 1.015625), 8)),
    "train_olmoe_1b_7b_s4k": (
        _kinds(attention=(2, 68157440), experts=(2, 1075315200)),
        *_one_pass(_S4K, 2)),
    "train_granite_4_0_h_micro_long": (
        # PR 52: six of the ten MLPs' gate and up [1, 8192, 8192] bfloat16;
        # PR 71: the nine mamba layers' in-projection outputs [1, 8192, 8512]
        # bfloat16, decided before them, leave two
        _kinds(attention=(1, 34603008), recurrent=(9, 1255145472),
               dense=(2, 536870912)),
        "; ssd chunk states 67108864 bytes a device; conv kernel 9 layers; "
        "scan kernel 9 layers" + _one_pass(_S8K, 1)[0],          # PR 48
        {"hbnlp_ssd_state_bytes": 67108864,
         "hbnlp_mamba_conv_kernel_layers": 9,
         "hbnlp_ssd_scan_kernel_layers": 9, **_one_pass(_S8K, 1)[1]}),
    "train_olmo_hybrid_7b_long": (
        # PR 52: the last MLP's gate and up [1, 16384, 11008]
        _kinds(attention=(1, 127795200), recurrent=(3, 566231040),
               dense=(1, 721420288)),
        # PR 50: the rule is the Pallas pair, which keeps the entering
        # states of all 30 heads (until then one group's ten: 94371840)
        "; ssd chunk states 283115520 bytes a device; conv kernel 3 layers; "
        "solve kernel 3 layers; rule kernel 3 layers"
        + _one_pass(_S16K, 1)[0],
        {"hbnlp_ssd_state_bytes": 283115520,
         "hbnlp_mamba_conv_kernel_layers": 3,
         "hbnlp_delta_solve_kernel_layers": 3,
         "hbnlp_delta_rule_kernel_layers": 3, **_one_pass(_S16K, 1)[1]}),
    # PR 61: the global layer's (out [2, 8192, 48, 128], lse [96, 8192]) rides
    # on its own bytes (until then nothing: the experts' decline kept it out)
    "train_laguna_s_2_1_ep32_s8k": (
        _kinds(attention=(1, 204472320)),
        "; moe held rows bound 131072; flash band 3 layers"
        + _one_pass(_LAGUNA, 5)[0],
        {"hbnlp_moe_held_rows_bound": 131072, "hbnlp_flash_band_layers": 3,
         **_one_pass(_LAGUNA, 5)[1]}),
    "train_zaya1_8b_ep2_s16k": (
        _kinds(attention=(8, 272629760), experts=(8, 1612185888)),
        "; moe held rows bound 16384; router carry 117440512 bytes"
        + _one_pass(_S16K, 8)[0],
        {"hbnlp_moe_held_rows_bound": 16384,
         "hbnlp_router_carry_bytes": 117440512, **_one_pass(_S16K, 8)[1]}),
    # PR 46: the sparse layer's (out, lse) and its choice (a bool a query and
    # a block); three lightning layers' chunk states, and no conv of theirs
    "train_minicpm_sala_tp2_long": (
        # PR 52: the last MLP's gate and up [1, 16384, 16384]
        _kinds(attention=(1, 72351744), dense=(1, 1073741824)),
        "; ssd chunk states 67108864 bytes a device",
        {"hbnlp_ssd_state_bytes": 67108864}),
    # PR 49: a looped model counts a layer each time the step runs it, and
    # says so: 12 layers x 4 passes of (out [2, 4096, 16, 128] bfloat16, lse
    # [2, 16, 4096] float32).  The nine lines above stand as they were
    "train_ouro_2_6b_loop4_s4k": (
        _kinds(attention=(48, 1635778560), unit="executions"),
        *_one_pass(_S4K, 12)),
    # PR 54: five grouped Mamba-2 layers' chunk states ([1, 128, 64, 64, 128]
    # float32 a layer) and the static row buffer of five LatentMoE layers,
    # whose offer is the combined sum a token ([16384, 1024] bfloat16) with
    # the routing triple and the choice, 36,044,836 bytes a layer (the pairs'
    # rows, 131,072 x (2,688 + 1,024) a layer, would pass the budget); the
    # one attention layer's (out, lse) rides after it.  The ten lines above
    # stand.  PR 71: the five mamba layers' in-projection outputs [1, 16384,
    # 9280] bfloat16 ride in what those two kinds leave
    "train_nemotron_3_super_tp2_ep64_s16k": (
        _kinds(attention=(1, 68157440), experts=(5, 180224180),
               recurrent=(5, 1520435200)),
        "; ssd chunk states 268435456 bytes a device; conv kernel 5 layers; "
        "scan kernel 5 layers; moe held rows bound 131072"
        + _one_pass(_S16K, 1)[0],
        {"hbnlp_ssd_state_bytes": 268435456,
         "hbnlp_mamba_conv_kernel_layers": 5,
         "hbnlp_ssd_scan_kernel_layers": 5,
         "hbnlp_moe_held_rows_bound": 131072, **_one_pass(_S16K, 1)[1]}),
    # PR 58: four KDA layers' rule outputs ([1, 16384, 32, 128] bfloat16 a
    # layer) ride as the recurrent kind and one group's chunk states (8 of 32
    # heads: [256, 8, 128, 128] bfloat16) are what is alive for the backward;
    # the four sparse layers' row buffers (131,072 x (2 x 1,024 + 2,304) a
    # layer) pass the budget, so the latent attention's (out, lse) does not
    # ride either; the conv and the solve are the Pallas pairs.  PR 59: so is
    # the rule (parallel/kda_rule.py, by the layer's own predicate): ``; rule
    # kernel 4 layers``, and the chunk states alive for the backward are all
    # 32 heads' ([256, 32, 128, 128] bfloat16) where they were one group's
    # 67,108,864.  The eleven lines above stand.  PR 61: each kind on its own
    # bytes, so the latent attention's (out, lse) rides, and the recurrent
    # kind an execution at a time, the pairs' interior (1,140,850,688 bytes a
    # layer on a TPU) a second part of the offer: beside the four outputs
    # (536,870,912 bytes, as before) the LAST layer's interior
    "train_kimi_linear_ep32_s16k": (
        _kinds(attention=(1, 136314880), recurrent=(4, 1677721600)),
        "; ssd chunk states 268435456 bytes a device; conv kernel 4 layers; "
        "solve kernel 4 layers; rule kernel 4 layers; "
        "moe held rows bound 131072" + _one_pass(_S16K, 1)[0],
        {"hbnlp_ssd_state_bytes": 268435456,
         "hbnlp_mamba_conv_kernel_layers": 4,
         "hbnlp_delta_solve_kernel_layers": 4,
         "hbnlp_delta_rule_kernel_layers": 4,
         "hbnlp_moe_held_rows_bound": 131072, **_one_pass(_S16K, 1)[1]}),
    # PR 67: block-diffusion training: seven layers' (out [1, 16384, 32, 128]
    # bfloat16, lse [32, 16384] float32) of the mask's far part over BOTH
    # halves of the doubled stream, the row buffer of a layer that is handed
    # 16,384 rows, the pairs the blockdiff kernels and the own blocks score
    # over the mask's 8,192 x 8,196 live ones, and the stream's positions.
    # The twelve lines above stand
    "train_sdar_30b_a3b_ep8_s8k": (
        _kinds(attention=(7, 954204160)),
        "; moe held rows bound 131072" + _one_pass(_SDAR, 7)[0]
        + "; denoise stream 16384 positions",
        {"hbnlp_moe_held_rows_bound": 131072, **_one_pass(_SDAR, 7)[1],
         "hbnlp_denoise_stream_positions": 16384}),
    # PR 72: pre-attention routing: eight layers' (out [1, 16384, 28, 128]
    # bfloat16, lse [28, 16384] float32), the row buffer at six slots a token,
    # the CARRIED logits (eight float32 [1, 16384, 64]: the gauge counts both
    # kinds of side value), six band forwards; the worst pass over live pairs
    # is the global layers' forward and the window-4,096 layers' backward at
    # 512 x 512 tiles.  The thirteen lines above stand
    "train_smallthinker_21b_ep8_s16k": (
        _kinds(attention=(8, 954204160)),
        "; moe held rows bound 98304; router carry 33554432 bytes; "
        "flash band 6 layers" + _one_pass(_SMALLTHINKER, 8)[0],
        {"hbnlp_moe_held_rows_bound": 98304,
         "hbnlp_router_carry_bytes": 33554432, "hbnlp_flash_band_layers": 6,
         **_one_pass(_SMALLTHINKER, 8)[1]}),
}
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
    """``(the line, {metric: {labels: value}})`` of a trainer's start-up."""
    line = Trainer(params, None, mesh).publish_stash_plan()
    snap = telemetry.snapshot()
    return line, {name: dict(entry["series"]) for name, entry in snap.items()}


@pytest.mark.parametrize("cell", sorted(_CELLS))
def cell_startup_is_the_parents_test(cell, monkeypatch, fresh_registry):
    """Each cell's ``remat stash:`` line, to the byte, and every start-up
    series with its value, as a TPU process reads them."""
    from benchmark.lib.cell import load_cell
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    params = _cell_params(cell)
    mesh = None
    if load_cell(cell).chips > 1:
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 virtual devices")
        mesh = shardlib.build_mesh(params, jax.devices()[:4])
    (head, plan), tail, gauges = _CELLS[cell]
    line, series = _startup(params, mesh)
    assert line == head + tail
    assert series.pop("hbnlp_remat_stash_layers") == {
        (kind,): layers for kind, (layers, _) in plan.items()}
    assert series.pop("hbnlp_remat_stash_bytes") == {
        (kind,): nbytes for kind, (_, nbytes) in plan.items()}
    want = {name: {(): 0} for name in _ALWAYS}
    want.update({name: value if isinstance(value, dict) else {(): value}
                 for name, value in gauges.items()})
    assert series == want


def _config_files():
    return sorted(glob.glob(os.path.join(REPO, "configs", "*.json"))
                  + glob.glob(os.path.join(REPO, "benchmark", "configs",
                                           "*.json")))


#: sha1 of the line + the start-up series of every configuration file as it
#: stands, TPU then CPU, from the parent (PR 46 added the two MiniCPM-SALA
#: files; without them the digest is PR 42's
#: 14424d9e0db6655b911c55f5cf6f76f5b6505273; PR 48 added the series
#: ``hbnlp_ssd_scan_kernel_layers`` to every file and ``; scan kernel N
#: layers`` to the lines of the two granite files: before it
#: 0120375caf4cdf6e013cbb72aedfbae4ab651566; PR 49 added the two Ouro files,
#: whose lines count executions: without them the digest is PR 48's
#: 1ae9e7a6b258ab7ceafda5cfe9cd9ec99860c294, every other line as it was; PR 50
#: added the series ``hbnlp_delta_rule_kernel_layers`` to every file and ``;
#: rule kernel N layers`` to the lines of the two Olmo-Hybrid files, whose
#: chunk states on a TPU are all 30 heads' where they were one group's: before
#: it cab4c9c0a1c8793f2e07b4e41810430d7e27b83b; PR 52 added the fifth kind,
#: ``dense``, to every file's line and two gauges — 0 layers and 0 bytes in
#: every file but the three cells' ``benchmark/configs/`` files of granite (6,
#: 1610612736), MiniCPM-SALA (1, 1073741824) and Olmo-Hybrid (1, 721420288):
#: before it 5221896c3d024205a9d2196fd56b63c8840405ae; PR 54 added the two
#: Nemotron-3-Super files: without them the digest is PR 52's
#: 822c534a7685509a587d4690f8021ccd1e6099de, every other line as it was; PR 55
#: added ``; flash scored over live pairs fwd F bwd B`` and the series
#: ``hbnlp_flash_scored_over_live_pairs{pass}`` to the TPU side of every file
#: whose step calls the tiled causal flash kernels, and nothing to the others
#: or to any CPU side: before it 8b03ac467bb6c441d9ab0b271ec8f57775930b9f;
#: PR 58 added the two Kimi-Linear files: without them the digest is PR 55's
#: cf28920b09793a3aa8ba5412654c871541bed16a, every other line as it was;
#: PR 59: layer ``kda`` declares its rule, so the two Kimi-Linear files' lines
#: gain ``; rule kernel N layers`` (4 and 20 on a TPU, 0 on the CPU) and
#: their chunk states on a TPU are all 32 heads' where they were one
#: group's; the other 27 files' lines and series as they were: before it
#: a5ae0c5439eacddd8671609ed08f3e82c06cec80; PR 61: the two cells' files
#: alone — ``benchmark/configs/laguna_s_2_1.json`` reads ``attention 1 layers,
#: 204472320`` on both sides, ``benchmark/configs/kimi_linear_48b_a3b.json``
#: ``attention 1 layers, 136314880`` with ``recurrent 4 layers, 1677721600``
#: on a TPU and, on the CPU, the four outputs as before and ``dense 1 layers,
#: 603979776``; the other 27 files as they were: before it
#: 6fc52f37f21988967d830151219f98f18fbe5817; PR 62 added the two Keye-VL-2.0
#: files — the cell's reads ``attention 7 layers, 1695547420`` on both sides:
#: a layer's ``(out, lse)``, its choice as bits and the index loss's
#: gradients —: without them the digest is PR 61's
#: 9ecdcf46a27f8dec5a5319d33aeb28b42ab64363, every other line as it was;
#: PR 64: the two Keye-VL-2.0 files alone gain ``; index loss kernel N
#: layers; index loss walked over visible pairs X`` and the two series
#: ``hbnlp_index_loss_kernel_layers`` / ``..._walked_over_visible_pairs`` — 7
#: (the cell's file) and 48 layers at 1.03119 on a TPU, 0 at 1.24992 on the
#: CPU —, the other 29 files as they were: before it
#: 71a59d30fb05fedc6feb56ee36b3684de7f15891; PR 65 added the two
#: JoyAI-LLM-Flash files — the cell's reads ``attention 6 layers, 817889280``
#: on both sides (five body layers' and the multi-token-prediction module's
#: ``(out, lse)``; layer 0 runs outside any region) with ``moe held rows bound
#: 131072`` —: without them the digest is PR 64's
#: fc574cb9973bd6b1ce062da7b715e870e530095f, every other line as it was;
#: PR 67 added the two SDAR-30B-A3B files — the cell's reads ``attention 7
#: layers, 954204160`` on both sides with ``moe held rows bound 131072`` and,
#: new, ``; denoise stream 16384 positions`` (the series
#: ``hbnlp_denoise_stream_positions``, which no other file has), on a TPU
#: ``flash scored over live pairs fwd 1.12543 bwd 1.06296`` —: without them
#: the digest is PR 65's 9e0fcbd609c6d4328deeeeb2142f9de7f2bb983e, every
#: other line as it was; PR 68 added the series
#: ``hbnlp_flash_backward_one_pass_layers`` to every file (0 where no call
#: reaches the kernels) and ``; flash backward one pass N layers`` to the TPU
#: side of the 24 files whose step calls the causal, windowed or
#: block-diffusion flash kernels, and nothing to any CPU side — the cells'
#: files read JoyAI 7, Kimi-Linear 1, ZAYA1 8, Ouro 12 (layers, not
#: executions), SDAR 7, Laguna 5, OLMoE 2, granite, Olmo-Hybrid and Nemotron
#: 1, the long-context file 0 (its eight on the split pair) —: before it
#: afab9a58e0a13082ca6848573b5819db77f6b067; PR 71: layer ``mamba`` offers its
#: in-projection's output, so the two cells' files alone moved, on both sides
#: — ``benchmark/configs/granite_4_0_h_micro.json`` reads ``recurrent 9
#: layers, 1255145472`` and ``dense 2 layers, 536870912`` (6, 1610612736),
#: ``benchmark/configs/nemotron_3_super_120b.json`` ``recurrent 5 layers,
#: 1520435200`` —, the other 33 files as they were: before it
#: 8af285da012d194c1f1f2761c2da8d6dc449c633; PR 72 added the two SmallThinker
#: files — the cell's reads ``attention 8 layers, 954204160`` on both sides
#: with ``moe held rows bound 98304; router carry 33554432 bytes`` (the
#: carried logits: until now the gauge counted ``router_mlp`` states alone)
#: and, on a TPU, ``flash band 6 layers; flash scored over live pairs fwd
#: 1.0625 bwd 1.06261; flash backward one pass 8 layers`` —: without them the
#: digest is PR 71's abf9c04a11d6e8f83fd126478a6dd66bb2935c3c, every other
#: line as it was; PR 73: the one pass with a head's dq resident where its dk
#: and dv do not fit, so the TPU side of four files moved —
#: ``benchmark/configs/1b_long_context_d8.json`` reads ``flash backward one
#: pass 8 layers`` (0) and ``bwd 1.01562`` (1.03125: k tiles of 512 at head
#: width 512), and three published files leave the split pair,
#: ``configs/1b_long_context_draft_247m.json`` (32,768 positions at head
#: width 256) 26 layers, ``configs/olmo_hybrid_7b.json`` 8 and
#: ``configs/ouro_2_6b.json`` 48 (65,536 at 128; 0 each) — and no CPU side:
#: before it
#: 9e1f0d21625332039d01c4ad2166a4b66a736b4a)
_FILE_DIGEST = "66f33ddb4841058f7caace34ee98f2cd4fcb17b9"


def every_configuration_file_starts_as_on_the_parent_test(monkeypatch):
    """Every file under ``configs/`` and ``benchmark/configs/``: the same
    line and the same series on a TPU and on the CPU (one digest; the cells'
    own test above says which line moved)."""
    real = jax.default_backend
    seen = []
    for path in _config_files():
        with open(path) as f:
            config = json.load(f)
        config = config.get("config", config)
        for backend in ("tpu", "cpu"):
            monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
            prev = telemetry.set_registry(telemetry.Registry())
            try:
                line, series = _startup(ModelParameter(
                    {**config, "model_path": "/tmp/declare_test"}))
            finally:
                telemetry.set_registry(prev)
            seen.append([os.path.relpath(path, REPO), backend, line, sorted(
                (name, sorted((list(k), v) for k, v in values.items()))
                for name, values in series.items())])
    monkeypatch.setattr(jax, "default_backend", real)
    digest = hashlib.sha1(json.dumps(seen).encode()).hexdigest()
    assert digest == _FILE_DIGEST, seen


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


@pytest.mark.parametrize("name,kind,metric,text,value", [
    ("moe_load_max_over_mean", "gauge", "hbnlp_moe_load_max_over_mean",
     "88106c01a6f0", 2.5),
    ("moe_routed_pairs", "counter", "hbnlp_moe_routed_pairs_total",
     "4f9956787e42", 4096.0),
    ("moe_held_pairs", "counter", "hbnlp_moe_held_pairs_total",
     "1894dc887eb0", 1024.0),
    ("moe_held_pair_share", "gauge", "hbnlp_moe_held_pair_share",
     "180250f2524a", 0.25),
    ("moe_held_pair_share_max", "gauge", "hbnlp_moe_held_pair_share_max",
     "4eb530aac659", 0.5),
    ("moe_held_row_tiles", "counter", "hbnlp_moe_held_row_tiles_total",
     "30245da96d7a", 3.0),
    ("moe_held_tile_share", "gauge", "hbnlp_moe_held_tile_share",
     "f1450e20cc47", 0.25),
    ("moe_top1_weight_mean", "gauge", "hbnlp_moe_top1_weight_mean",
     "8f9d99ebcdda", 0.20000000298023224),
    ("cca_logit_scale_max", "gauge", "hbnlp_cca_logit_scale_max",
     "926f7565e35d", 7.0),
    ("ssd_log_decay_min", "gauge", "hbnlp_ssd_log_decay_min",
     "c1d31e0cfa01", -9.0),
    ("delta_transform_abs_max", "gauge", "hbnlp_delta_transform_abs_max",
     "2dce3a99aa4b", 11.0),
    # PR 58: layer kda's own statistic (it shares the one above)
    ("kda_log_decay_min", "gauge", "hbnlp_kda_log_decay_min",
     "e0a2d3a5188e", -144.0),
    ("sparse_kept_key_share", "gauge", "hbnlp_sparse_kept_key_share",
     "b2ef4e49f658", 0.5),
    ("sparse_choosing_query_share", "gauge",
     "hbnlp_sparse_choosing_query_share", "62dca63d7d24", 0.75),
    ("lightning_state_abs_max", "gauge", "hbnlp_lightning_state_abs_max",
     "49c68f690d74", 9.0),
    # PR 62: attention flag indexed's own two (the MEAN over the layers, and
    # the largest)
    ("index_loss", "gauge", "hbnlp_index_loss", "f8211bece1a4", 0.5),
    ("index_score_abs_max", "gauge", "hbnlp_index_score_abs_max",
     "db2b64e379d3", 5.0)])
def declared_statistic_folds_as_on_the_parent_test(name, kind, metric, text,
                                                   value):
    """One declared statistic: the parent's fold over the layers, its kind,
    its metric name and (by digest) its help text."""
    assert float(_info_metrics(_info(_LAYER_STATS_IN))[name]) == value
    stat = _LAYER_STATS[name]
    assert (stat.kind, stat.metric) == (kind, metric)
    assert hashlib.sha1(stat.help.encode()).hexdigest()[:12] == text


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
