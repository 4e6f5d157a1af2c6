"""Laguna-S-2.1's layers through the normal path (ISSUE 36): the program
against the plain reference ``benchmark/reference/laguna_s_2_1.py`` in
logits, loss and gradients at toy widths; the SHARE test (all expert-parallel
ranks' routed parts plus the shared expert once = the uncut layer); the
window below, at and above the sequence; YaRN's frequencies and the partial
rotation against a transcription of HF's; the held-expert dispatch at its
extremes; the new flags' and keys' refusals; scopes and gauges."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from harness import REPO
from homebrewnlp_tpu.analysis.cost_ledger import scope_key
from homebrewnlp_tpu.config import ModelParameter
from homebrewnlp_tpu.model import Model, moe as moe_mod, remat, spatial
from homebrewnlp_tpu.model.spatial import (_standard_flags, rotary,
                                           yarn_inv_freq)

FULL = "attention-yarn-q_heads4-kv_heads2-gate-rotary_pct50-theta500000"
SLIDE = "attention-rope-q_heads6-kv_heads2-gate-window32"
MOE = "moe-silu-shared_expert"


def _block(*layers):
    return {"skip": True, "layer": list(layers)}


# head counts 4 and 6 over 2 K/V heads on a stream of 2 x 16, 16 routed
# experts of which 4 are held, 4 a token, a window of 32 on 128 positions
TINY = {"depth": 1, "heads": 2, "features_per_head": 16,
        "sequence_length": 128, "train_batch_size": 2, "vocab_size": 384,
        "experts": 16, "experts_held": 4, "moe_top_k": 4, "expert_width": 24,
        "rope_yarn_original_positions": 64, "tpu_size": 1,
        "use_checkpointing": False,
        "input_block_config": [_block("norm-rms-scale", FULL),
                               _block("norm-rms-scale", "mlp-silu")],
        "block_config": [_block("norm-rms-scale", SLIDE),
                         _block("norm-rms-scale", MOE)] * 3
        + [_block("norm-rms-scale", FULL), _block("norm-rms-scale", MOE)],
        "output_block_config": [{"layer": ["norm-rms-scale"]}]}


def _reference():
    return harness.reference("laguna_s_2_1")


def _config(dtype: str = "float32", **extra) -> dict:
    return harness.config_of("laguna_s_2_1", TINY, dtype, **extra)


def _build(dtype: str = "float32", **extra):
    return harness.build(_config(dtype, **extra))


# ---- the program against the reference ---------------------------------------

@pytest.mark.parametrize("dtype,tolerance,extra", [
    # float32 against float32: only the order of sums differs, so this pins
    # the EQUATIONS: a missing gate, plain top-k weights, a window off by
    # one, rotate-half over the wrong width are off by orders of magnitude
    ("float32", 2e-5, {}),
    # the sequence BELOW the window (32 of it on 16 positions: every layer
    # sees the whole triangle), AT it, and above (the default: 128)
    ("float32", 2e-5, {"sequence_length": 16}),
    ("float32", 2e-5, {"sequence_length": 32}),
    # every expert held (the uncut layer, OLMoE's dispatch) and a share
    # that is not the first
    ("float32", 2e-5, {"experts_held": 0}),
    ("float32", 2e-5, {"experts_held": 4, "experts_first": 8}),
    # fewer held than a token's choices: two slots a token
    ("float32", 2e-5, {"experts_held": 2, "experts_first": 3}),
    # a buffer an eighth full or less when balanced (4 of 64 held, as the
    # cell's 8 of 256): dispatch, the activation and combine walk the real
    # rows' tiles (moe.walks_real_rows); the cases above move the buffer
    ("float32", 2e-5, {"experts": 64}),
    ("bfloat16", 2 ** -4, {"experts": 64, "experts_first": 30}),
    # two periods: the second one's layers have weights of their own (the
    # shared expert's flag is not the DSL's cross-layer ``shared``)
    ("float32", 2e-5, {"depth": 2}),
    # the configuration's bfloat16, at the cells' bound
    ("bfloat16", 2 ** -4, {})],
    ids=["float32", "below_window", "at_window", "all_held", "third_share",
         "two_slots", "walked", "walked_bfloat16", "two_periods", "bfloat16"])
def program_matches_reference_test(dtype, tolerance, extra):
    config, _, model, batch, variables = _build(dtype, **extra)
    assert sum("moe_0/normal_var4" in name for name in variables) \
        == 4 * config["depth"]
    got = harness.assert_program_matches_reference(
        _reference(), (config, _, model, batch, variables), dtype, tolerance)


@pytest.mark.parametrize("extra", [{}, {"experts": 64}],
                         ids=["whole_buffer", "walked"])
def loss_and_gradients_match_reference_test(extra):
    config, params, model, batch, variables = _build(**extra)
    assert moe_mod.walks_real_rows(
        config["experts"], config["experts_held"], config["moe_top_k"]) \
        == bool(extra)
    ref = _reference()
    tokens, targets = batch["token_x"][..., 0], batch["token_y"][..., 0]
    got = jax.jit(jax.grad(lambda v: model.apply(v, batch).total_loss.data))(
        variables)
    _, want = harness.reference_loss_and_grads(ref, variables, tokens,
                                               targets, config)
    harness.assert_grads_match(got, want, 2e-4)


def reference_at_the_next_precision_below_fails_test():
    """``harness.assert_float8_stream_misses``."""
    harness.assert_float8_stream_misses(_reference(), _build("bfloat16"))


# ---- the share test ------------------------------------------------------------

def _moe_layer(params, weights, x):
    """Layer ``moe-silu-shared_expert`` of ``params`` on ``x [b, s, heads,
    features]`` with the given weights (the reference's short names)."""
    return harness.layer_on(params, moe_mod.moe, _reference().SPARSE, weights,
                            x, ["silu", "shared_expert"])[0]


def the_shares_add_up_to_the_uncut_layer_test():
    """Four expert-parallel ranks of four experts each: their routed parts,
    with the shared expert that every rank computes alike counted once, add
    up to what the uncut reference gives for the whole layer — and so do
    the reference's own shares."""
    ref = _reference()
    rng = np.random.default_rng(2)
    heads, width, n_exp, inter = 2, 16, 16, 24

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32) * 0.3)

    whole = {"w_router": normal(heads, width, n_exp),
             "w_gate": normal(n_exp, heads, width, inter),
             "w_up": normal(n_exp, heads, width, inter),
             "w_down": normal(n_exp, inter, heads, width),
             "s_gate": normal(heads, width, inter),
             "s_up": normal(heads, width, inter),
             "s_down": normal(inter, heads, width)}
    m = normal(2, 128, heads, width)
    weights = ref.route({**whole, "w_norm": jnp.ones((heads, width))}, m, 4,
                        True, 2.5, 1e-6)[1]
    # the uncut layer on the already-normed input: rms's scale is one and
    # its input has unit mean square only roughly, so route() above and the
    # layer below must see the SAME m: give the layer the normed m
    m = ref.rms(m, jnp.ones((heads, width)), 1e-6)
    shared = ref.swiglu(m, whole["s_gate"], whole["s_up"], whole["s_down"])
    uncut = shared + ref.routed_part(whole, m, weights, 0, n_exp)

    parts, ref_parts = [], []
    for rank in range(4):
        first = 4 * rank
        params = ModelParameter(_config(experts_held=4, experts_first=first))
        share = dict(whole, **{k: whole[k][first:first + 4]
                               for k in ("w_gate", "w_up", "w_down")})
        parts.append(_moe_layer(params, share, m) - shared)
        ref_parts.append(ref.routed_part(share, m, weights, first, 4))
        np.testing.assert_allclose(np.asarray(parts[-1]),
                                   np.asarray(ref_parts[-1]), atol=2e-5)
        assert float(jnp.max(jnp.abs(parts[-1]))) > 1e-3
    np.testing.assert_allclose(np.asarray(shared + sum(parts)),
                               np.asarray(uncut), atol=5e-5)
    np.testing.assert_allclose(np.asarray(shared + sum(ref_parts)),
                               np.asarray(uncut), atol=5e-5)
    # and the program's own uncut layer
    np.testing.assert_allclose(
        np.asarray(_moe_layer(ModelParameter(_config(experts_held=0)), whole,
                              m)), np.asarray(uncut), atol=5e-5)


# ---- the held-expert dispatch ----------------------------------------------------

@pytest.mark.parametrize("first,held", [(0, 4), (5, 3), (14, 2), (0, 16)])
def held_slots_keep_every_held_choice_test(first, held):
    rng = np.random.default_rng(held)
    t, k, n = 64, 4, 16
    logits = jnp.asarray(rng.normal(size=(t, n)).astype(np.float32))
    weights, experts = moe_mod.route(logits, k, True, 2.5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.5, rtol=1e-5)
    w, local, real = (np.asarray(a) for a in moe_mod.held_slots(
        weights, experts, first, held))
    assert w.shape == (t, min(k, held))
    for row in range(t):
        want = {int(e) - first: float(p) for e, p in zip(
            np.asarray(experts)[row], np.asarray(weights)[row])
            if first <= e < first + held}
        got = {int(e): float(p) for e, p, r in zip(local[row], w[row],
                                                   real[row]) if r}
        assert got == pytest.approx(want)
        assert np.all(local[row][~real[row]] == held)
        assert np.all(w[row][~real[row]] == 0)
    assert moe_mod.held_rows_bound(t, k, held) == t * min(k, held)


@pytest.mark.parametrize("experts", [16, 64], ids=["whole_buffer", "walked"])
def every_held_pair_is_computed_when_all_land_here_test(experts):
    """The router sends EVERY token's every choice to the held experts: the
    static buffer is full to its last row and nothing is dropped; with none
    landing here the routed part is exactly zero (and finite, whatever the
    unwritten rows hold) and so are the held experts' gradients — where the
    layer moves the whole buffer and where it walks the real rows."""
    ref = _reference()
    config, params, model, batch, variables = _build(experts_held=4,
                                                     experts=experts)
    tokens = batch["token_x"][..., 0]
    # one program each for both biases
    run = jax.jit(lambda v: model.apply(v, batch, layer_stats=True))
    grad = jax.jit(jax.grad(lambda v: harness.loss_of(model)(v, batch)))
    for bias in (+40.0, -40.0):
        skewed = dict(variables)
        for name in variables:
            if name.endswith("moe_0/normal_var0/var0"):
                w = np.array(variables[name])
                w[..., :4] += bias / w.shape[0] / w.shape[1] * np.sign(
                    np.asarray(variables[name.replace(
                        "moe_0/normal_var0", "norm_0/normal_var0")]))[..., None]
                skewed[name] = jnp.asarray(w)
        # a positive input to the router is not guaranteed: read the share
        info = run(skewed)
        held = np.asarray(info.layer_stats["moe_held_pairs"])
        routed = np.asarray(info.layer_stats["moe_routed_pairs"])
        assert routed.tolist() == [2 * 128 * 4] * 4
        got = np.asarray(info.token_out.data.astype(jnp.float32))[:, :, 0, :]
        want = np.asarray(ref.forward(skewed, tokens, config))
        assert harness.error(got, want) < 2e-5, (bias, held / routed)
        assert all(np.all(np.isfinite(np.asarray(g)))
                   for g in grad(skewed).values())


def _choices(rng, tokens, n_exp, top_k, first, held, n_held):
    """``[tokens, top_k]`` distinct experts a token of which exactly
    ``n_held`` pairs in all lie in ``first .. first + held - 1``, spread
    over the tokens at random (at most ``min(top_k, held)`` a token)."""
    slots = min(top_k, held)
    counts = np.zeros(tokens, np.int64)
    for spot in rng.permutation(tokens * slots)[:n_held]:
        counts[spot // slots] += 1
    inside = np.arange(first, first + held)
    outside = np.setdiff1d(np.arange(n_exp), inside)
    rows = [rng.permutation(np.concatenate([
        rng.permutation(inside)[:c], rng.permutation(outside)[:top_k - c]]))
        for c in counts]
    return jnp.asarray(np.stack(rows).astype(np.int32))


def _held_part(x, weights, experts, mats, first, held, dense: bool):
    """The routed part of a layer that holds a share, through the shipped
    tiled passes or — ``dense`` — the parent's forms: every slot's row
    gathered, activated and summed, the unreal ones selected away, plain
    ``jax.numpy`` under autodiff."""
    w, local, real = moe_mod.held_slots(weights, experts, first, held)
    slots = w.shape[-1]

    def gated(gate, up):
        return jax.nn.silu(gate) * up

    if dense:
        order, inverse, sizes = moe_mod.sort_pairs(local, held + 1)
        rows, n_real = x[order // slots], None
    else:
        order, sizes = moe_mod.sort_held(local, held)
        n_real = jnp.sum(sizes[:held])
        rows = moe_mod._twice_held(
            moe_mod._dispatch_held(x, order, n_real, slots), n_real)
    sizes = sizes[:held]
    gate = moe_mod.grouped_dot(rows if dense else rows[0], mats[0], sizes)
    up = moe_mod.grouped_dot(rows if dense else rows[1], mats[1], sizes)
    hidden = gated(gate, up) if dense \
        else moe_mod._gated_held(gated, (gate, up), n_real)
    out = moe_mod.grouped_dot(hidden, mats[2], sizes)
    if not dense:
        return moe_mod._combine_held(out, w, order, n_real, slots)
    pairs = out[inverse].reshape(-1, slots, out.shape[-1]).astype(
        jnp.float32) * w[..., None]
    return jnp.sum(jnp.where(real[..., None], pairs, 0.0), axis=1).astype(
        out.dtype)


@pytest.mark.parametrize("dtype,tolerance", [
    # the same products at the same rounding points: what differs is the
    # order of a token's float32 sum over its slots
    ("float32", 1e-5), ("bfloat16", 2 ** -7)])
@pytest.mark.parametrize("fill", ["none", "one", "tile", "tile+1", "bound"])
@pytest.mark.parametrize("n_exp,top_k,first,held", [
    (16, 4, 4, 4),      # Laguna's class: 4 of 16 held, four slots a token
    (16, 4, 14, 2),     # two held, two slots
    (16, 1, 0, 8)])     # ZAYA1's: top-1, half held, one slot
def the_tiled_passes_are_the_dense_ones_test(n_exp, top_k, first, held, fill,
                                             dtype, tolerance, monkeypatch):
    """Dispatch, the gated activation and combine over ``ceil(real rows /
    tile)`` tiles against the parent's dense forms: the value and the
    gradients of the input, the weights and the three expert matrices, at
    the fills where a loop's trip count or its last tile's mask could be
    off by one.  The buffer starts as NaN here (``lax.empty`` hands out
    zeros on the CPU, anything on a TPU): whatever reads a row past the
    real ones fails."""
    tokens, features, width = 1536 // min(top_k, held), 16, 24
    bound = moe_mod.held_rows_bound(tokens, top_k, held)
    tile = moe_mod._row_tile(bound)
    assert (bound, tile) == (1536, 512)
    n_held = {"none": 0, "one": 1, "tile": tile, "tile+1": tile + 1,
              "bound": bound}[fill]
    rng = np.random.default_rng(n_held + held)
    experts = _choices(rng, tokens, n_exp, top_k, first, held, n_held)

    def normal(*shape, scale=1.0):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32) * scale
                           ).astype(dtype)

    x = normal(tokens, features)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, (tokens, top_k)).astype(
        np.float32))
    mats = (normal(held, features, width, scale=0.3),
            normal(held, features, width, scale=0.3),
            normal(held, width, features, scale=0.3))
    probe = normal(tokens, features)

    def run(dense):
        def loss(x, weights, mats):
            out = _held_part(x, weights, experts, mats, first, held, dense)
            return jnp.sum(out.astype(jnp.float32)
                           * probe.astype(jnp.float32)), out
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))(
            x, weights, mats)

    (_, want), want_grads = run(dense=True)
    monkeypatch.setattr(jax.lax, "empty", lambda shape, dtype: jnp.full(
        shape, jnp.nan, dtype))
    (_, got), grads = run(dense=False)
    sizes = moe_mod.sort_held(moe_mod.held_slots(
        weights, experts, first, held)[1], held)[1]
    assert int(jnp.sum(sizes[:held])) == n_held == bound - int(sizes[held])
    for name, a, b in zip(
            ("out", "x", "weights", "gate", "up", "down"),
            (got, grads[0], grads[1], *grads[2]),
            (want, want_grads[0], want_grads[1], *want_grads[2])):
        a, b = (np.asarray(v.astype(jnp.float32)) for v in (a, b))
        assert np.all(np.isfinite(a)), name
        scale = max(float(np.max(np.abs(b))), 1e-6)
        assert float(np.max(np.abs(a - b))) <= tolerance * scale, name
        assert (n_held == 0) == (not np.any(b)), name


def routing_without_the_new_keys_is_olmoes_test():
    """``route`` without renormalisation or scale traces to the softmax and
    the top-k alone, as before ISSUE 36."""
    logits = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    plain = str(jax.make_jaxpr(lambda x: moe_mod.route(x, 4))(logits))
    assert plain == str(jax.make_jaxpr(
        lambda x: moe_mod.route(x, 4, False, 1.0))(logits))
    assert "div" in str(jax.make_jaxpr(
        lambda x: moe_mod.route(x, 4, True, 1.0))(logits)).split("top_k")[1]
    assert "div" not in plain.split("top_k")[1]
    assert "mul" not in plain.split("top_k")[1]


def the_step_reports_the_held_share_test():
    """At seeded initialisation the held experts get about ``held /
    experts`` of the pairs; the trainer's metrics carry the counter and both
    gauges, and the start-up line the static buffer's rows."""
    config, params, model, batch, variables = _build()
    info = harness.apply_with_stats(model, variables, batch)
    held = np.asarray(info.layer_stats["moe_held_pairs"])
    routed = np.asarray(info.layer_stats["moe_routed_pairs"])
    assert routed.tolist() == [1024.0] * 4
    assert np.all((held > 0.15 * routed) & (held < 0.35 * routed))
    assert np.all(np.asarray(
        info.layer_stats["moe_load_max_over_mean"]) >= 1.0)
    from homebrewnlp_tpu.train import _LAYER_STATS, _info_metrics
    metrics = _info_metrics(info)
    assert float(metrics["moe_held_pairs"]) == held.sum()
    assert float(metrics["moe_held_pair_share"]) == pytest.approx(
        held.sum() / routed.sum())
    assert float(metrics["moe_held_pair_share_max"]) == pytest.approx(
        (held / routed).max())
    assert {"moe_held_pairs", "moe_held_pair_share",
            "moe_held_pair_share_max"} <= set(_LAYER_STATS)
    # a buffer this full (a quarter) is moved whole: no tiles to report
    assert "moe_held_row_tiles" not in info.layer_stats
    assert "moe_held_tile_share" not in metrics
    assert moe_mod.moe_held_rows(params) == 2 * 128 * 4
    assert moe_mod.moe_held_rows(ModelParameter(_config(experts_held=0))) == 0
    from homebrewnlp_tpu import telemetry
    from homebrewnlp_tpu.train import Trainer
    line = Trainer(params, model).publish_stash_plan()
    assert line.startswith("remat stash:")
    assert line.endswith("moe held rows bound 1024; flash band 0 layers")
    assert telemetry.snapshot()["hbnlp_moe_held_rows_bound"]["series"][()] \
        == 1024


def the_step_reports_the_tiles_it_walks_test():
    """``moe_held_row_tiles`` / ``hbnlp_moe_held_tile_share`` (PR 47): where
    the layer walks the real rows (4 of 64 experts held), ``ceil(held pairs
    / tile)`` tiles a layer — the trip count of every pass — over the
    bound's tiles."""
    from homebrewnlp_tpu.train import _LAYER_STATS, _info_metrics
    config, params, model, batch, variables = _build(experts=64)
    info = harness.apply_with_stats(model, variables, batch)
    held = np.asarray(info.layer_stats["moe_held_pairs"])
    assert np.all((held > 0) & (held < 0.15 * 1024))
    tile = moe_mod._row_tile(moe_mod.moe_held_rows(params))
    assert tile == 512
    visited = np.ceil(held / tile)
    assert np.asarray(info.layer_stats["moe_held_row_tiles"]).tolist() \
        == visited.tolist() == [1.0] * 4
    metrics = _info_metrics(info)
    assert float(metrics["moe_held_row_tiles"]) == 4
    assert float(metrics["moe_held_tile_share"]) == 4 / (4 * 2)
    assert (_LAYER_STATS["moe_held_row_tiles"].metric,
            _LAYER_STATS["moe_held_tile_share"].metric) == (
        "hbnlp_moe_held_row_tiles_total", "hbnlp_moe_held_tile_share")


@pytest.mark.parametrize("config,walks", [
    ("laguna_s_2_1", True),     # 8 / 256 x 10 / 8 = 3.9% of the buffer
    ("zaya1_8b", False),        # 8 / 16 x 1 / 1 = 50%
    ("olmoe_1b_7b", False)])    # every expert held: no buffer of slots
def the_form_follows_the_fill_test(config, walks):
    """The held path's form is decided by the fill the configuration fixes
    (at most an eighth of the static buffer when balanced), per cell."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           config + ".json")) as f:
        cell = json.load(f)["config"]
    assert moe_mod.walks_real_rows(
        cell["experts"], cell.get("experts_held", 0),
        min(cell["moe_top_k"], cell["experts"])) == walks
    # the edge: an eighth walks, a quarter does not
    assert moe_mod.walks_real_rows(16, 1, 2)
    assert not moe_mod.walks_real_rows(16, 2, 4)
    assert not moe_mod.walks_real_rows(16, 4, 4)


def the_band_gauge_counts_the_window_layers_test():
    """``hbnlp_flash_band_layers`` (PR 41): the layers whose windowed flash
    forward is the band kernel, by ``parallel/flash_attention.band_applies``
    — the cell's three window-512 layers on a TPU (the full model's 36: the
    body's three an eleventh of the depth, three more trailing), none on the
    CPU, none where no layer declares a window, none where the kernels are
    not reached."""
    from homebrewnlp_tpu import telemetry
    from homebrewnlp_tpu.train import Trainer
    with open(os.path.join(REPO, "benchmark", "configs",
                           "laguna_s_2_1.json")) as f:
        cell = json.load(f)["config"]
    params = ModelParameter(cell)
    assert spatial.flash_band_layers(params, "tpu") == 3
    assert spatial.flash_band_layers(params) == 0
    with open(os.path.join(REPO, "configs", "laguna_s_2_1.json")) as f:
        full = ModelParameter(json.load(f))
    assert spatial.flash_band_layers(full, "tpu") == 3 * full.depth + 3 == 36
    tiny = ModelParameter(_config("bfloat16"))
    assert spatial.flash_band_layers(tiny, "tpu") == 3
    assert spatial.flash_band_layers(tiny) == 0
    assert spatial.flash_band_layers(
        ModelParameter(_config(use_flash_attention=False)), "tpu") == 0
    assert spatial.flash_band_layers(
        ModelParameter(_config(sequence_length=96)), "tpu") == 0
    # a window as long as the sequence is the causal call; no window, no line
    assert spatial.flash_band_layers(
        ModelParameter(_config(sequence_length=32)), "tpu") is None
    with open(os.path.join(REPO, "configs", "olmoe_1b_7b.json")) as f:
        assert spatial.flash_band_layers(ModelParameter(json.load(f)),
                                       "tpu") is None
    model = Model(tiny)
    line = Trainer(tiny, model).publish_stash_plan()
    assert line.endswith("; flash band 0 layers")
    assert telemetry.snapshot()["hbnlp_flash_band_layers"]["series"][()] == 0


def the_experts_stash_counts_the_bounds_rows_test():
    """model/remat.py's ``experts`` kind: the saved outputs of a layer that
    holds a share are its whole static buffer, and (PR 39) the router's
    choice, ``[tokens, moe_top_k]`` int32, beside the routing triple."""
    params = ModelParameter(_config("bfloat16"))
    layers, nbytes = remat._offered_stash(params, "experts", 1)
    rows = 2 * 128 * 4
    assert layers == 4
    assert nbytes == 4 * (rows * (2 * 24 + 32) * 2
                          + (2 * rows + 5 + 2 * 128 * 4) * 4)


# ---- rotary positions ------------------------------------------------------------

@pytest.mark.parametrize("theta,width,factor,original,fast,slow", [
    (500000.0, 64, 128.0, 8192, 32.0, 1.0),       # the published global layers
    (500000.0, 8, 128.0, 64, 32.0, 1.0),          # the toy size
    (10000.0, 128, 4.0, 4096, 32.0, 1.0),
    (10000.0, 32, 1.0, 2048, 16.0, 2.0)])
def yarn_frequencies_are_hfs_test(theta, width, factor, original, fast, slow):
    """Against the transcription of HF's ``_compute_yarn_parameters`` in the
    reference, and its landmarks: the fastest frequencies stay, the slowest
    are divided by the factor."""
    got = yarn_inv_freq(theta, width, factor, original, fast, slow)
    want = _reference().yarn_inv_freq(theta, width, factor, original, fast,
                                      slow)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    plain = theta ** (-np.arange(0, width, 2) / width)
    assert got[0] == pytest.approx(plain[0])
    assert got[-1] == pytest.approx(plain[-1] / factor, rel=1e-6)
    assert np.all(np.diff(got) < 0)


@pytest.mark.parametrize("width", [None, 8, 16])
def partial_rotation_is_hfs_test(width):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 24, 3, 16)).astype(np.float32))
    inv_freq = yarn_inv_freq(500000.0, width or 16, 128.0, 8, 32.0, 1.0)
    got = rotary(x, 500000.0, width, inv_freq, 1.4852030263919618)
    want = _reference().rope(x, inv_freq, 1.4852030263919618)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    if width == 8:
        np.testing.assert_array_equal(np.asarray(got)[..., 8:],
                                      np.asarray(x)[..., 8:])
    # the plain call is what it was
    np.testing.assert_allclose(
        np.asarray(rotary(x, 10000.0)),
        np.asarray(_reference().rope(
            x, _reference().default_inv_freq(10000.0, 16), 1.0)), atol=2e-6)


# ---- refusals ----------------------------------------------------------------------

@pytest.mark.parametrize("flags,match", [
    (["rope", "sliding512"], "sliding512"),
    (["rope", "window"], "window"),
    (["rope", "yarn"], "exactly one"),
    (["gate"], "exactly one"),
    (["nope", "theta10000"], "nope"),
    (["rope", "q_heads8"], "come together"),
    (["rope", "q_heads6", "kv_heads4"], "must divide")])
def unknown_attention_flags_refuse_by_name_test(flags, match):
    with pytest.raises(ValueError, match=match):
        _standard_flags(flags)


def known_attention_flags_parse_test():
    assert _standard_flags(FULL.split("-")[1:]) == {
        "yarn": True, "q_heads": 4, "kv_heads": 2, "gate": True,
        "rotary_pct": 50, "theta": 500000}
    assert _standard_flags(["rope", "qk_norm"]) == {"rope": True,
                                                    "qk_norm": True}


@pytest.mark.parametrize("extra,match", [
    ({"experts_held": 17}, "exceeds experts"),
    ({"experts_held": 4, "experts_first": 13}, "exceeds experts"),
    ({"experts_held": 0, "experts_first": 2}, "without experts_held"),
    ({"experts_held": -1}, "whole number"),
    ({"expert_width": 1.5}, "whole number"),
    ({"moe_route_scale": 0}, "moe_route_scale"),
    ({"rope_yarn_factor": 0.5}, "rope_yarn"),
    ({"rope_yarn_beta_fast": 1, "rope_yarn_beta_slow": 1}, "rope_yarn")])
def bad_keys_refuse_by_name_test(extra, match):
    with pytest.raises(ValueError, match=match):
        ModelParameter(_config(**extra))


@pytest.mark.parametrize("layer,match", [
    ("moe-silu-shared_expert-capacity2", "capacity2"),
    (FULL.replace("rotary_pct50", "rotary_pct30"), "rotary_pct30")])
def unknown_layer_flags_refuse_at_init_test(layer, match):
    config = _config()
    config["block_config"] = [_block("norm-rms-scale", layer)]
    model = Model(ModelParameter(config))
    tokens = np.zeros((2, 128, 1), np.int32)
    with pytest.raises(ValueError, match=match):
        model.init({"token_x": tokens, "token_y": tokens}, seed=1)


# ---- the repo's config, scopes ---------------------------------------------------

def the_repos_config_is_the_published_model_test():
    """``configs/laguna_s_2_1.json`` against the catalog's published keys
    that the benchmark's file repeats."""
    with open(os.path.join(REPO, "configs", "laguna_s_2_1.json")) as f:
        config = json.load(f)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "laguna_s_2_1.json")) as f:
        doc = json.load(f)
    params = ModelParameter(config)
    assert not params.unknown_config_keys
    assert params.heads * params.features_per_head == doc["hidden_size"]
    assert params.features_per_head == doc["head_dim"]
    assert params.intermediate[0].size == doc["intermediate_size"]
    assert params.expert_intermediate[0].size == doc["moe_intermediate_size"] \
        == doc["shared_expert_intermediate_size"]
    assert params.expert_dim.size == doc["published"]["num_experts"]
    assert params.moe_top_k == doc["num_experts_per_tok"]
    assert params.moe_route_scale == doc["moe_routed_scaling_factor"]
    assert params.moe_norm_topk == doc["norm_topk_prob"]
    assert params.vocab_size == doc["published"]["vocab_size"]
    layers = [b["layer"][1] for cfgs, times in (
        (config["input_block_config"], 1), (config["block_config"],
                                            config["depth"]),
        (config["output_block_config"][:-1], 1))
        for _ in range(times) for b in cfgs]
    attention = [l for l in layers if l.startswith("attention")]
    assert len(attention) == doc["published"]["num_hidden_layers"]
    for layer, kind, heads in zip(attention, doc["layer_types"],
                                  doc["num_attention_heads_per_layer"]):
        flags = _standard_flags(layer.split("-")[1:])
        assert flags["q_heads"] == heads
        assert flags["kv_heads"] == doc["num_key_value_heads"]
        assert flags.get("window") == (doc["sliding_window"]
                                       if kind == "sliding_attention"
                                       else None)
        assert "gate" in flags
        assert ("yarn" in flags) == (kind == "full_attention")
    rope = doc["rope_parameters"]["full_attention"]
    assert (params.rope_yarn_factor, params.rope_yarn_original_positions,
            params.rope_yarn_beta_fast, params.rope_yarn_beta_slow,
            params.rope_yarn_attention_factor) == (
        rope["factor"], rope["original_max_position_embeddings"],
        rope["beta_fast"], rope["beta_slow"], rope["attention_factor"])
    mlps = [l.split("-")[0] for l in layers if not l.startswith("attention")]
    assert mlps == ["mlp" if k == "dense" else "moe"
                    for k in doc["mlp_layer_types"]]
    assert doc["config"]["experts_held"] == doc["num_experts"] == 8


@pytest.mark.parametrize("path,scope_name", [
    ("jit(step_fn)/jvp(gpt0)/body0/checkpoint/block0_1_0/moe_0/shared/dot_general",
     "body/moe/shared"),
    ("jit(step_fn)/transpose(jvp(gpt0))/body0/block0_0_0/attention_0/gate/logistic",
     "body/attention/gate"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_0_0/attention_0/rope/mul",
     "body/attention"),
    # a leading block is a body layer that runs once
    ("jit(step_fn)/jvp(gpt0)/input0/lang_inp0_0/attention_0/flash_attention/x",
     "body/attention"),
    # the band forward (PR 41), in a block's forward and in its replay
    ("jit(step_fn)/jvp(gpt0)/body0/checkpoint/block0_0_0/attention_0/"
     "flash_attention/flash_fwd_window", "body/attention"),
    ("jit(step_fn)/transpose(jvp(gpt0))/body0/checkpoint/rematted_computation/"
     "block0_2_0/attention_0/flash_attention/flash_fwd_window",
     "body/attention"),
    # the held path's loops (PR 47), as a lowered step names them: forward,
    # the block's replay and the backward's own
    ("jit(step_fn)/jvp(gpt0)/body0/checkpoint/block0_1_0/moe_0/dispatch/"
     "while/body/gather", "body/moe/dispatch"),
    ("jit(step_fn)/transpose(jvp(gpt0))/body0/jvp(gpt0)/body0/checkpoint/"
     "rematted_computation/block0_1_0/moe_0/dispatch/while/body/"
     "dynamic_update_slice", "body/moe/dispatch"),
    ("jit(step_fn)/transpose(jvp(gpt0))/body0/jvp(gpt0)/body0/checkpoint/"
     "block0_1_0/moe_0/dispatch/while/body/scatter-add", "body/moe/dispatch"),
    ("jit(step_fn)/jvp(gpt0)/body0/checkpoint/block0_3_0/moe_0/experts/"
     "while/body/exp", "body/moe/experts"),
    ("jit(step_fn)/transpose(jvp(gpt0))/body0/jvp(gpt0)/body0/checkpoint/"
     "rematted_computation/block0_3_0/moe_0/experts/while/cond/lt",
     "body/moe/experts"),
    ("jit(step_fn)/transpose(jvp(gpt0))/body0/jvp(gpt0)/body0/checkpoint/"
     "block0_3_0/moe_0/experts/while/body/transpose(jvp())/mul",
     "body/moe/experts"),
    ("jit(step_fn)/jvp(gpt0)/body0/checkpoint/block0_1_0/moe_0/combine/"
     "while/body/scatter-add", "body/moe/combine"),
    ("jit(step_fn)/transpose(jvp(gpt0))/body0/jvp(gpt0)/body0/checkpoint/"
     "block0_1_0/moe_0/combine/while/body/scatter", "body/moe/combine"),
    ("jit(step_fn)/jvp(gpt0)/input0/lang_inp1_0/mlp_0/dot_general",
     "body/mlp"),
    ("jit(step_fn)/jvp(gpt0)/input0/gather0/embed0/gather", "input/embed"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_1_0/mamba_0/gate_norm/mul",
     "body/mamba/gate_norm")])
def the_new_scopes_fold_test(path, scope_name):
    assert scope_key(path) == scope_name


def dense_kind_leaves_the_cells_step_alone_test(monkeypatch):
    """PR 52: the cell's one ``mlp`` (the published dense layer 0) is an
    INPUT block — outside every ``jax.checkpoint`` region, so it offers
    nothing, whatever the budget.  PR 61: the experts kind passes the budget
    and takes none of it, so the global layer's flash pair rides on its own
    204,472,320 bytes — every region's policy names the pair and nothing of
    layer ``mlp`` (until then every region's was the named policy itself:
    the experts' decline kept every later kind out)."""
    from homebrewnlp_tpu.model.blocks import (_named_policy,
                                              _region_policies)
    from homebrewnlp_tpu.model.declare import step_offers
    from homebrewnlp_tpu.utils import flops
    from remat_policy_test import _cell_params
    monkeypatch.setattr(flops, "hbm_capacity",
                        lambda device=None: (16911433728, "memory_stats"))
    params = _cell_params("train_laguna_s_2_1_ep32_s8k")
    assert any("mlp" in layer for block in params.input_block_config
               for layer in block.layer)
    assert remat.offers(params, "dense") == []
    assert len(list(step_offers(params, "dense"))) == 1
    assert remat.stash_plan(params)["dense"] == (0, 0)
    assert remat.stash_kinds(params) == {"attention"}
    assert remat.stash_plan(params)["attention"] == (1, 204472320)
    assert remat.stash_plan(params)["experts"] == (0, 0)
    regions = len(params.block_config) * params.depth
    pair = ("flash_out", "flash_lse")
    assert remat.region_names(params) == [pair] * regions
    policies = _region_policies(params)
    assert len(policies) == regions
    assert all(policy is _named_policy("nothing_saveable", pair)
               for policy in policies)
    # the three window-512 layers' queries see fewer than 2,048 keys: the
    # blocks' channel names the global layer's call alone
    assert remat.saved_attention_keys(params) == 2048


# ---- compiled for a described v5e ---------------------------------------------

def held_row_buffers_are_allocated_where_they_are_filled_test(v5e,
                                                              monkeypatch):
    """One ``moe`` layer of the Laguna cell (8 of 256 experts held, eight
    slots a token) at 1 x 8,192 tokens, compiled for a v5e as a TPU process
    traces it (ISSUE 47): the held path's loops are ``while`` ops in the
    layer's three scopes; their row buffers come from ``moe_held_rows_alloc``
    calls — a custom call with an operand, scheduled where it is filled — and
    not from operand-less ``AllocateBuffer``s, which XLA schedules at the
    step's start (every layer's buffers alive at once: the cell's step then
    needs 19 GB); no buffer of the bound's rows is copied."""
    import re
    params, hlo = harness.cell_layer_hlo(
        v5e, monkeypatch, "train_laguna_s_2_1_ep32_s8k", 1,
        input_block_config=[], train_batch_size=1)
    assert params.block_config[0].layer[-1].startswith("moe")
    assert params.sequence_length == 8192
    rows = moe_mod.moe_held_rows(params)
    assert rows == 8192 * 8
    buffer = rf"bf16\[{rows},(?:3072|1024)\]"
    assert not re.search(rf"= {buffer}\S* custom-call\(\)", hlo)
    assert not re.search(rf"= {buffer}\S* copy\(", hlo)
    allocs = re.findall(rf'= {buffer}[^\n]*?custom_call_target='
                        r'"tpu_custom_call"[^\n]*?op_name="([^"]+'
                        r'moe_held_rows_alloc[^"]*)"', hlo)
    # dispatch forward and replay; the activation forward, replay and its
    # backward's two; combine's backward
    assert sorted(scope_key(op) for op in allocs) \
        == ["body/moe/combine"] + ["body/moe/dispatch"] * 2 \
        + ["body/moe/experts"] * 4
    loops = re.findall(r'= [^\n]*? while\([^\n]*?op_name="([^"]+moe_0[^"]+)"',
                       hlo)
    held = [op for op in loops if "searchsorted" not in op]
    assert {scope_key(op) for op in held} == {
        "body/moe/dispatch", "body/moe/experts", "body/moe/combine"}
    # dispatch 3 (forward, replay, backward), the fan-out's backward 1, the
    # activation 3, combine 2 (its forward is not replayed)
    assert len(held) == 9
