"""Keye-VL-2.0's language model through the normal path (ISSUE 62): the
program against the plain reference ``benchmark/reference/
keye_vl_2_0_30b_a3b.py`` in logits, loss, index loss and every parameter's
gradient at toy widths; the exact top-k against a stable sort, with planted
ties and rows shorter than ``index_topk``; which loss reaches which leaf; the
key-at-a-time select kernels in interpret mode against the dense masked form,
forward and backward; the dense case; the SHARE test (the eight expert shares
add up to the uncut layer); M-RoPE on equal streams; what the parent traced
still traces; refusals, scopes, gauges and the offer."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from homebrewnlp_tpu.analysis.cost_ledger import scope_key
from homebrewnlp_tpu.config import ModelParameter
from homebrewnlp_tpu.model import Model, indexer, remat, spatial
from homebrewnlp_tpu.parallel import flash_attention as fa

CELL = "train_keye_vl_2_0_ep8_s16k"
INDEXED = "attention-rope-qk_norm_head-q_heads8-kv_heads2-indexed"


def _blocks(layer: str = INDEXED):
    return [{"skip": True, "layer": ["norm-rms-scale", layer]},
            {"skip": True, "layer": ["norm-rms-scale", "moe-silu"]}]


# 8 query heads over 2 K/V heads of 16 on a stream of 4 x 16, 4 index heads
# of 8, 32 keys kept of a sequence of 128: three quarters of the queries
# choose (the cell: seven eighths); 16 experts of which a token takes 4
TINY = {"depth": 2, "heads": 4, "features_per_head": 16,
        "sequence_length": 128, "train_batch_size": 2, "vocab_size": 272,
        "experts": 16, "experts_held": 0, "moe_top_k": 4, "expert_width": 24,
        "index_heads": 4, "index_features": 8, "index_topk": 32,
        "tpu_size": 1, "use_checkpointing": False,
        "block_config": _blocks()}
#: the indexer's own leaves of an attention layer (benchmark/reference's
#: names): query, key, the LayerNorm's scale and shift, the weights
INDEX_LEAF = re.compile(r"attention_0/normal_var[5-9]/")


def _reference():
    return harness.reference("keye_vl_2_0_30b_a3b")


def _lively(variables, seed: int = 3):
    """The seeded weights with the norms' scales moved off 1 and the
    indexer's projections scaled up: at normal(0.02) every index score is a
    near-tie."""
    rng = np.random.default_rng(seed)
    out = {}
    for name, value in variables.items():
        value = np.asarray(value)
        if re.search(r"attention_0/normal_var[3478]/", name):
            value = value + rng.normal(size=value.shape).astype(
                np.float32) * 0.2
        elif re.search(r"attention_0/normal_var[0159]/", name):
            value = value * 10.0
        out[name] = jnp.asarray(value)
    return out


def _build(dtype: str = "float32", **extra):
    return harness.build(harness.config_of(
        "keye_vl_2_0_30b_a3b", TINY, dtype, **extra), lively=_lively)


# ---- the program against the reference ---------------------------------------

@pytest.mark.parametrize("dtype,tolerance,extra", [
    # float32 against float32: only the order of sums differs, so this pins
    # the EQUATIONS (a scale on the wrong side, a key too many, rotary on the
    # wrong features are off by orders of magnitude)
    ("float32", 2e-5, {}),
    # a rank's share as the cell holds it, and another rank's
    ("float32", 2e-5, {"experts_held": 2}),
    ("float32", 2e-5, {"experts_held": 2, "experts_first": 6}),
    # at or under index_topk keys nothing is selected
    ("float32", 2e-5, {"sequence_length": 32}),
    # the configuration's bfloat16 at the other cells' bound, where nothing
    # is chosen: at toy sizes a key weighs 1 / 32 (the cell: 1 / 2,048) and
    # rounding the operands moves the choice itself, which float32 pins above
    # and the cell measures (PERF.md section 6, PR 62)
    ("bfloat16", 2 ** -4, {"sequence_length": 32})],
    ids=["float32", "first_share", "fourth_share", "dense", "bfloat16"])
def program_matches_reference_test(dtype, tolerance, extra):
    built = _build(dtype, **extra)
    got = harness.assert_program_matches_reference(_reference(), built,
                                                   dtype, tolerance)
    assert got.shape == (2, built[0]["sequence_length"], 272)


@pytest.fixture(scope="module")
def trained():
    """One toy model, its gradients with and without the index loss's
    weight, and the reference's losses and gradients: one compile each for
    every test below."""
    config, params, model, batch, variables = _build()
    tokens, targets = batch["token_x"][..., 0], batch["token_y"][..., 0]
    loss, grads = harness.loss_and_grads(model, variables, batch)
    saved = indexer.LOSS_WEIGHT
    indexer.LOSS_WEIGHT = 0.0
    try:
        _, lm_grads = harness.loss_and_grads(model, variables, batch)
    finally:
        indexer.LOSS_WEIGHT = saved
    ref = _reference()
    v = {k: jnp.asarray(a) for k, a in variables.items()}
    want_loss, want = harness.reference_loss_and_grads(ref, variables, tokens,
                                                       targets, config)
    lm, router, index = ref.losses_of(v, tokens, targets, config)
    index_grads = jax.grad(lambda v: sum(ref.losses_of(
        v, tokens, targets, config)[2]))(v)
    stats = harness.apply_with_stats(model, variables, batch).layer_stats
    return dict(config=config, loss=loss, grads=grads, lm_grads=lm_grads,
                want_loss=want_loss, want=want, lm=lm, router=router,
                index=index, index_grads=index_grads, stats=stats)


def losses_and_gradients_match_reference_test(trained):
    """The reported loss is the language-model loss alone, the index loss the
    step statistic a layer, and every parameter's gradient is ``jax.grad`` of
    the reference's ``train_loss`` = LM + router terms + the SUM of the
    layers' index losses; none is dead."""
    assert float(trained["loss"]) == pytest.approx(float(trained["lm"]),
                                                   abs=2e-6)
    assert float(trained["want_loss"]) == pytest.approx(float(
        trained["lm"] + trained["router"] + sum(trained["index"])), abs=1e-6)
    np.testing.assert_allclose(trained["stats"]["index_loss"],
                               np.asarray(trained["index"]), rtol=2e-5)
    assert min(float(x) for x in trained["index"]) > 1e-3
    harness.assert_grads_match(trained["grads"], trained["want"], 2e-4,
                               alive=True)


def each_loss_reaches_its_own_leaves_test(trained):
    """The language-model loss gives the indexer's leaves a zero gradient —
    exactly: it reads ``stop_gradient`` and the choice is discrete — and the
    index loss gives every OTHER leaf zero: with its weight at 0 only the
    indexer's gradients move."""
    for name, full in trained["grads"].items():
        lm = np.asarray(trained["lm_grads"][name])
        alone = np.asarray(trained["index_grads"][name])
        if INDEX_LEAF.search(name):
            assert not lm.any(), name
            assert alone.any(), name
            assert harness.error(np.asarray(full), alone) < 2e-4, name
        else:
            assert not alone.any(), name
            assert np.array_equal(np.asarray(full), lm), name


# ---- the selection -------------------------------------------------------------

def _stable_top(score, first: int, topk: int):
    """The ``min(t + 1, topk)`` largest of a row among ``u <= t`` by a
    stable sort: ties to the lower position."""
    b, n, s = score.shape
    keep = np.zeros((b, n, s), bool)
    for i in range(b):
        for r in range(n):
            t = first + r
            order = np.argsort(-score[i, r, :t + 1], kind="stable")
            keep[i, r, order[:min(t + 1, topk)]] = True
    return keep


@pytest.mark.parametrize("first,topk", [(0, 8), (64, 8), (0, 200), (32, 1)],
                         ids=["from_zero", "later_chunk", "all_short", "one"])
def top_keys_is_the_stable_sort_test(first, topk):
    """Random scores with PLANTED ties (a few values only, zeros of both
    signs, infinities) on rows of which some are shorter than ``topk``."""
    rng = np.random.default_rng(first + topk)
    score = rng.normal(size=(2, 64, 128)).astype(np.float32)
    score[0] = rng.integers(-2, 3, (64, 128)).astype(np.float32)
    score[1, ::3] = np.round(score[1, ::3] * 2) / 2
    score[0, 5, :40] = -0.0
    score[1, 7, 10:20] = np.inf
    score[1, 9, 3:30] = -np.inf
    want = _stable_top(score + 0.0, first, topk)
    got = np.asarray(jax.jit(indexer.top_keys, static_argnums=(1, 2))(
        jnp.asarray(score) + 0.0, first, topk))
    assert np.array_equal(got, want)
    kept = got.sum(-1)
    assert np.array_equal(kept, np.broadcast_to(np.minimum(
        first + np.arange(64) + 1, topk), kept.shape))


def choice_is_the_references_and_packs_to_bits_test():
    """``select_keys`` on the layer's own operands keeps the reference's keys
    (its stable sort of the same float32 scores), as bits that unpack to it."""
    rng = np.random.default_rng(11)
    q_index = jnp.asarray(rng.normal(size=(2, 128, 4, 8)), jnp.float32)
    k_index = jnp.asarray(rng.normal(size=(2, 128, 8)), jnp.float32)
    weight = jnp.asarray(rng.normal(size=(2, 128, 4)), jnp.float32)
    words = jax.jit(indexer.select_keys, static_argnums=3)(
        q_index, k_index, weight, 32)
    assert words.shape == (2, 1, 4, 128) and words.dtype == jnp.int32
    score = np.asarray(indexer.scores(q_index, k_index, weight))
    want = _stable_top(score, 0, 32)
    assert np.array_equal(np.asarray(fa.unpack_keep(words))[:, 0], want)
    assert np.array_equal(np.asarray(fa.pack_keep(jnp.asarray(want))),
                          np.asarray(words)[:, 0])
    share, chose = indexer.kept_shares(words)
    from benchmark.roofline import keye_costs
    config = {"sequence_length": 128, "index_topk": 32}
    assert float(share) == pytest.approx(keye_costs.kept_key_share(config),
                                         rel=1e-6)
    assert float(chose) == keye_costs.choosing_query_share(config) == 0.75


@pytest.mark.parametrize("chunk", [32, 64, 128], ids=["two_chunk_bands",
                                                       "four_bands",
                                                       "one_band"])
def chunks_and_bands_change_nothing_test(monkeypatch, chunk):
    """The passes in chunks of queries and bands of chunks — a band against
    the keys up to its last query — give the one-chunk pass's choice, loss
    and gradients."""
    rng = np.random.default_rng(13)
    q_index = jnp.asarray(rng.normal(size=(2, 256, 4, 8)), jnp.float32)
    k_index = jnp.asarray(rng.normal(size=(2, 256, 8)), jnp.float32)
    weight = jnp.asarray(rng.normal(size=(2, 256, 4)), jnp.float32)
    q, k, _ = _qkv(14, 2, 256, 4, 16, 2)

    def run():
        keep = indexer.select_keys(q_index, k_index, weight, 32)
        return keep, indexer.index_loss(q_index, k_index, weight, q, k, None,
                                        keep, 0.25), indexer.index_loss(
            q_index, k_index, weight, q, k, None, None, 0.25)

    want_keep, want, want_dense = jax.jit(run)()
    monkeypatch.setattr(indexer, "QUERY_CHUNK", chunk)
    assert len(indexer._bands(256, chunk)) == (4 if chunk < 128 else 1)
    keep, got, got_dense = jax.jit(run)()
    assert np.array_equal(np.asarray(keep), np.asarray(want_keep))
    names = ("loss", "top", "dq", "dk", "dw")
    harness.assert_close_each(got, want, 1e-5, names, {"loss": 0, "top": 0})
    harness.assert_close_each(got_dense, want_dense, 1e-5, names,
                              {"loss": 0, "top": 0})


def at_index_topk_keys_the_layer_is_the_plain_attention_test():
    """At or under ``index_topk`` keys the output equals the flag-free causal
    attention's on the same weights (the indexer's leaves aside), and past it
    the selection changes the result."""
    plain = INDEXED.replace("-indexed", "")
    for length, same in ((32, True), (128, False)):
        config, _, model, batch, variables = _build(sequence_length=length)
        other = Model(ModelParameter({**config,
                                      "block_config": _blocks(plain)}))
        other.init(batch, seed=13)
        moved = {re.sub(r"normal_var10/", "normal_var5/", name): value
                 for name, value in variables.items()
                 if not INDEX_LEAF.search(name)}
        err = harness.error(
            harness.logits_and_loss(other, moved, batch)[0],
            harness.logits_and_loss(model, variables, batch)[0])
        assert (err < 1e-6) if same else (err > 1e-3)


# ---- the key-at-a-time select kernels, interpreted --------------------------------

@pytest.fixture
def small_tiles(monkeypatch):
    """The block form's tile at 128 (the key-at-a-time form's at 256), so
    that tiles of a sequence of 512 die."""
    monkeypatch.setattr(fa, "_SELECT_TILE", 128)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _qkv(seed, b, s, h, d, g):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(b, s, n, d)), jnp.float32)
                 for n in (h, g, g))


def _choice(kind: str):
    """``(the packed words, the pairs a row keeps AND may see)``."""
    rng = np.random.default_rng(2)
    if kind in ("scattered", "above"):
        keep = rng.random((1, 1, 512, 512)) < 0.1
        # the last q tile keeps nothing of the first two k tiles: dead tiles
        keep[:, :, 384:, :256] = False
    elif kind == "late":
        # query 400 keeps its own key and no other while its neighbours keep
        # key 0: its q tile's first cells run with nothing kept in its row
        keep = np.zeros((1, 1, 512, 512), bool)
        keep[..., 0] = True
        keep[:, :, 400] = False
    else:
        # a row keeps key 0 and its own: every tile between is dead for every
        # row of a q tile
        keep = np.zeros((1, 1, 512, 512), bool)
        keep[..., 0] = True
    keep |= np.eye(512, dtype=bool)
    seen = keep & np.tril(np.ones((512, 512), bool))
    # "above": the words hold bits past the diagonal, inside the cells it
    # crosses (the kernels mask those by position) and in whole tiles above
    # it (the tables kill those)
    return fa.pack_keep(jnp.asarray(keep if kind == "above" else seen)), seen


def _live_steps(fetch, nq: int, nk: int, k_outer: bool = False):
    """``[nq, nk]`` bool: the cells whose table entry is their own step."""
    shape, own = ((nk, nq), np.arange(nq)) if k_outer \
        else ((nq, nk), np.arange(nk))
    live = np.asarray(fetch).reshape(shape) == own[None, :]
    return live.T if k_outer else live


@pytest.mark.parametrize("kind,tiles", [
    ("scattered", None), ("local", None), ("late", None), ("above", None),
    ("scattered", (128, 256)), ("above", (128, 256)),
    ("local", (128, 128)), ("late", (128, 128))])
def key_select_kernels_match_the_dense_form_test(small_tiles, monkeypatch,
                                                 kind, tiles):
    """Forward, ``lse``, dq, dk and dv of the ``flash_*_select`` kernels at
    ``block`` 1 (8 query heads over 2 K/V heads, ONE choice for all) against
    the dense masked XLA form, at the form's own tile (256 x 256 under
    ``small_tiles``), a rectangular and a smaller one, and the tables skip
    the dead tiles: a k tile no row kept, a
    tile above the diagonal whatever its bits say.  ``late``: a row that has
    kept nothing when its q tile's first cells run (the finite first maximum:
    ``p`` 0 there, ``out`` and ``lse`` finite); ``above``: bits past the
    diagonal, masked by position."""
    if tiles is None:
        tiles = fa.select_tile(512, 1)
        assert tiles == (256, 256)
    else:
        monkeypatch.setattr(fa, "select_tile", lambda s, block: tiles)
    q, k, v = _qkv(4, 1, 512, 8, 32, 2)
    words, seen = _choice(kind)
    scale = 32 ** -0.5
    weights = jnp.asarray(np.random.default_rng(5).normal(
        size=(1, 512, 8, 32)), jnp.float32)
    got = harness.with_input_grads(
        lambda *t: fa.flash_select(*t, words, scale, 1, True), (q, k, v),
        weights)
    want = harness.with_input_grads(
        lambda *t: fa._xla_select(*t, words, scale, 1), (q, k, v), weights)
    assert all(bool(jnp.isfinite(x).all()) for x in got)
    harness.assert_close_each(got, want, 2e-5, ("out", "dq", "dk", "dv"))
    _, lse = fa._select_fwd_impl(q, k, v, words, scale, 1, True)
    _, want_lse = fa._xla_select_with_lse(q, k, v, words, scale, 1)
    assert bool(jnp.isfinite(lse).all())
    assert harness.error(lse, want_lse) < 1e-5
    tq, tk = tiles
    nq, nk = 512 // tq, 512 // tk
    _, fetch_k, fetch_q = fa._select_tables(words, tq, tk, 1)
    live = seen.reshape(nq, tq, nk, tk).any(axis=(1, 3))
    assert np.array_equal(_live_steps(fetch_k, nq, nk), live)
    assert np.array_equal(_live_steps(fetch_q, nq, nk, k_outer=True), live)
    # whatever the words' bits say, no live cell lies above the diagonal: a
    # cell it does not cross holds no pair a row may not see
    above = np.arange(nk)[None, :] * tk > np.arange(nq)[:, None] * tq + tq - 1
    if tiles == (128, 128):
        assert int(live.sum()) < int((~above).sum()) == 10
    bits = np.asarray(fa.unpack_keep(words)).reshape(nq, tq, nk, tk).any(
        axis=(1, 3))
    assert (bits & above).any() == (kind == "above")
    assert not (_live_steps(fetch_k, nq, nk) & above).any()


@pytest.mark.parametrize("s,block,tiles", [
    (16384, 1, (1024, 1024)), (1024, 1, (1024, 1024)), (512, 1, (512, 512)),
    (768, 1, (256, 256)), (16384, 64, (512, 512)), (16384, 16, (512, 512)),
    (256, 16, (256, 256))])
def select_tile_by_form_test(s, block, tiles):
    """The key-at-a-time form takes twice the block form's tile where the
    sequence holds one."""
    assert fa.select_tile(s, block) == tiles


def dispatch_returns_the_dense_forms_value_and_gradients_test():
    """``key_select_attention`` off the TPU: the dense form's output, its
    ``lse`` without a gradient, and the dense form's gradients."""
    q, k, v = _qkv(6, 1, 128, 4, 16, 2)
    words, _ = _choice("scattered")
    words = words[:, :, :4, :128]
    scale = 0.25
    weights = jnp.ones((1, 128, 4, 16), jnp.float32)
    got = harness.with_input_grads(
        lambda *t: fa.key_select_attention(*t, words, scale)[0], (q, k, v),
        weights)
    want = harness.with_input_grads(
        lambda *t: fa._xla_select(*t, words, scale, 1), (q, k, v), weights)
    harness.assert_close_each(got, want, 1e-6, ("out", "dq", "dk", "dv"))


# ---- the shares, the positions -------------------------------------------------

def eight_expert_shares_add_up_to_the_uncut_layer_test():
    """The reference's sparse block with 2 of 16 experts held, from each of
    the eight first experts, adds up to the uncut layer (0 held = all)."""
    config, _, _, batch, variables = _build()
    ref = _reference()
    _, p, _ = [x for x in ref.layers_of(variables, config)
               if x[0] == "sparse"][0]
    h = jnp.asarray(np.random.default_rng(7).normal(size=(2, 128, 4, 16)),
                    jnp.float32)
    whole, _ = ref.sparse_block(p, h, config)
    parts = sum(ref.sparse_block(
        {**p, **{w: p[w][first:first + 2] for w in ("w_gate", "w_up",
                                                    "w_down")}},
        h, {**config, "experts_held": 2, "experts_first": first})[0]
        for first in range(0, 16, 2))
    assert float(jnp.max(jnp.abs(whole))) > 1e-4
    assert harness.error(parts, whole) < 1e-5


def mrope_on_equal_streams_is_rope_test():
    """M-RoPE's three position streams are equal on text: then it is the
    reference's ``rope`` and the program's ``rotary``; on unequal streams it
    is not."""
    ref = _reference()
    x = jnp.asarray(np.random.default_rng(8).normal(size=(2, 64, 3, 128)),
                    jnp.float32)
    equal = jnp.broadcast_to(jnp.arange(64)[None], (3, 64))
    got = ref.mrope(x, equal, 1e7)
    assert harness.error(got, ref.rope(x, ref.default_inv_freq(1e7, 128),
                                       1.0)) < 1e-6
    assert harness.error(got, spatial.rotary(x, 1e7)) < 1e-6
    assert harness.error(ref.mrope(x, equal * jnp.asarray([[1], [2], [3]]),
                                   1e7), got) > 1e-2
    assert ref.MROPE_SECTIONS == (16, 24, 24)


# ---- what the parent traced still traces ---------------------------------------

def block_selected_call_traces_as_on_the_parent_test():
    """SALA's form of the select kernels — blocks of keys, a choice a K/V
    group: the whole call and its two backward kernels (body, grid, index
    maps) trace to the pinned jaxprs."""
    q = jnp.zeros((1, 512, 4, 32), jnp.float32)
    k = v = jnp.zeros((1, 512, 2, 32), jnp.float32)
    keep = jnp.zeros((1, 2, 512, 32), bool)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda q, k, v: jnp.sum(fa.flash_select(q, k, v, keep, 0.25, 16,
                                                True)),
        argnums=(0, 1, 2)))(q, k, v)
    harness.pinned("kernel/select", str(jaxpr))
    calls = {eqn.params["name"]: str(eqn)
             for eqn in harness.pallas_calls(jaxpr.jaxpr)}
    assert sorted(calls) == ["flash_bwd_dkv_select", "flash_bwd_dq_select",
                             "flash_fwd_select"]
    harness.pinned("kernel/select_dq", calls["flash_bwd_dq_select"])
    harness.pinned("kernel/select_dkv", calls["flash_bwd_dkv_select"])


def a_layer_of_the_cell_holds_the_issues_parameters_test():
    """The shapes the program builds for the cell at ONE layer: ISSUE 62's
    96,899,456 a layer beside the two table slices and the final norm — the
    count ``benchmark/configs/keye_vl_2_0_30b_a3b.json`` states."""
    from benchmark.lib.cell import load_cell
    cell = load_cell(CELL)
    stated = cell.config_doc["parameters"]
    model = Model(ModelParameter({**cell.model_config(), "depth": 1,
                                  "model_path": "/tmp/keye_test"}))
    batch = {k: np.zeros((1, 16384, 1), np.int32)
             for k in ("token_x", "token_y")}
    shapes = jax.eval_shape(lambda b: model.init(b, seed=1), batch)
    count = sum(int(np.prod(v.shape)) for v in shapes.values())
    assert count == sum(stated["a_layer"].values()) \
        + stated["tables_and_final_norm"] == 96_899_456 + 77_793_280
    assert stated["program"] == stated["counted"] \
        == cell.config_doc["num_hidden_layers"] * 96_899_456 + 77_793_280


# ---- refusals, scopes, the offer -------------------------------------------------

@pytest.mark.parametrize("flags,message", [
    ("rope-indexed-sparse", "sparse or indexed, not both"),
    ("rope-indexed-window8", "indexed or window, not both"),
    ("nope-q_heads4-kv_heads4-kv_latent8-indexed", "does not build indexed")])
def flags_that_do_not_go_with_indexed_refuse_by_name_test(flags, message):
    with pytest.raises(ValueError, match=message):
        spatial._standard_flags(flags.split("-"))


def a_mesh_and_a_tile_without_words_refuse_by_name_test():
    import types
    ctx = types.SimpleNamespace(mesh=types.SimpleNamespace(size=2))
    for flag in ("indexed", "sparse"):
        with pytest.raises(NotImplementedError,
                           match=f"attention flag {flag} on a mesh"):
            spatial._one_device(ctx, flag)
    with pytest.raises(ValueError, match="the forms are blocks of keys"):
        fa.select_tile(512, 48)
    assert fa.select_tile(16384, 1) == (1024, 1024)
    with pytest.raises(ValueError, match="whole words of 32"):
        indexer.selects(32, 48)
    with pytest.raises(ValueError, match="index_features 7"):
        ModelParameter({**harness.config_of("keye_vl_2_0_30b_a3b", TINY),
                        "index_features": 7})


def compiled_for_a_v5e_the_layer_runs_the_key_kernels_once_test(
        v5e, monkeypatch):
    """One layer of the cell at its published widths and 16,384 tokens,
    compiled for a v5e as a TPU process traces it: Mosaic takes the
    key-at-a-time kernels (a cell's window of the bits, shifted out), the
    attention kind rides (``(out, lse)``, the bits, the index loss's
    gradients), so ONE forward kernel runs — none in the replay — beside one
    dq and one dk/dv call, all named ``flash_*_select`` under ``attend``; and
    the compiled program's ops name ``index``, ``select``, ``attend`` and
    ``index_loss`` under ``sparse_attention``, each a scope of the cost
    ledger's.  The index loss is ``index_loss_pass``, once, under scope
    ``index_loss`` (ISSUE 64)."""
    params, hlo = harness.cell_layer_hlo(v5e, monkeypatch, CELL, 0, depth=1)
    plan = remat.stash_plan(params)
    assert plan["attention"][0] == 1
    offer = spatial._offer(params, set(params.block_config[0].layer[1]
                                       .split("-")[1:]))
    assert set(offer.names) == set(fa.SAVED_NAMES + (fa.SELECT_NAME,)
                                   + indexer.INDEX_LOSS_NAMES)
    # (out bf16 + lse) + a bit a pair + the loss pass's float32 gradients
    assert offer.nbytes == 32 * 16384 * (128 * 2 + 4) + 16384 * 16384 // 8 \
        + 4 * (1 + 16384 * (16 * 65 + 64))
    calls = harness.kernel_calls(hlo)
    assert sorted(name for name, _ in calls) == [
        "flash_bwd_dkv_select", "flash_bwd_dq_select", "flash_fwd_select",
        "index_loss_pass"]
    for name, op_name in calls:
        assert scope_key(op_name) == "body/attention/sparse_attention/" + (
            "index_loss" if name == "index_loss_pass" else "attend")
    found = {scope_key(name) for name in re.findall(r'op_name="([^"]*)"', hlo)}
    for step in ("index", "select", "attend", "index_loss"):
        assert f"body/attention/sparse_attention/{step}" in found, step
    # ISSUE 64: the index loss is ONE kernel a layer — its results are named
    # and kept, so the replay runs none — and no ``[512, keys]`` float32
    # plane of the XLA form is left under its scope
    assert spatial.index_loss_kernel_layers(params, "tpu") == 1
    planes = [line for line in hlo.splitlines()
              if "sparse_attention/index_loss" in line
              and re.search(r"= f32\[(1,)?512,(4096|8192|12288|16384)\]", line)]
    assert not planes, planes[:3]
