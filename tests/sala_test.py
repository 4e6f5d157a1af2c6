"""MiniCPM-SALA's layers through the normal path (ISSUE 46): the program
against the plain reference ``benchmark/reference/minicpm_sala.py`` in logits,
loss and every parameter's gradient at toy widths; the chunked lightning rule
against the serial recurrence and against ``ssd`` where they coincide; the
indexer's selection against brute force; the selected flash kernels in
interpret mode against a masked softmax, forward and backward; the SHARE test
(the two tensor-parallel ranks' mixer outputs add up to the uncut layer); a
replay that would choose otherwise still reads the saved choice; refusals,
scopes and gauges."""
import json
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from harness import REPO
from homebrewnlp_tpu.analysis.cost_ledger import scope_key
from homebrewnlp_tpu.config import ModelParameter
from homebrewnlp_tpu.model import Model, remat, sparse
from homebrewnlp_tpu.model import lightning as lightning_mod
from homebrewnlp_tpu.model import spatial
from homebrewnlp_tpu.model.mamba import ssd
from homebrewnlp_tpu.parallel import flash_attention as fa

SPARSE = "attention-nope-qk_norm_head-gate_features-sparse"


def _block(layer):
    return {"skip": True, "layer": ["norm-rms-scale", layer]}


def _blocks(q_heads: int = 4, kv_heads: int = 2, order=("s", "l")):
    layers = {"s": f"{SPARSE}-q_heads{q_heads}-kv_heads{kv_heads}",
              "l": "lightning"}
    out = []
    for kind in order:
        out += [_block(layers[kind]), _block("mlp-silu")]
    return out


# 4 query heads over 2 K/V heads of 16 on a stream of 4 x 16, 4 lightning
# heads of 16; a sequence of 16 blocks of 8 keys of which a query keeps 4
# (the first and the two that end at its own forced), past a dense length of
# 32: three quarters of the queries choose, as in the cell; a vocabulary that
# is no multiple of 128, as the cell's 9,181 is none
TINY = {"depth": 1, "heads": 4, "features_per_head": 16,
        "sequence_length": 128, "train_batch_size": 2, "vocab_size": 272,
        "lightning_heads": 4, "lightning_heads_held": 0,
        "lightning_head_features": 16, "lightning_chunk": 16,
        "lightning_norm_groups": 2,
        "sparse_kernel_size": 8, "sparse_kernel_stride": 4,
        "sparse_block_size": 8, "sparse_topk": 4, "sparse_init_blocks": 1,
        "sparse_window": 16, "sparse_dense_length": 32,
        "tpu_size": 1, "use_checkpointing": False,
        "block_config": _blocks()}


def _reference():
    return harness.reference("minicpm_sala")


def _config(dtype: str = "float32", **extra) -> dict:
    return harness.config_of("minicpm_sala", TINY, dtype, **extra)


def _batch(config, seed: int = 5):
    return harness.token_batch(config["train_batch_size"],
                               config["sequence_length"], seed)


def _lively(variables, seed: int = 3):
    """The seeded weights with the norms' scales moved off 1, so that a wrong
    use of any of them shows, and the sparse layers' query and key
    projections scaled up: at normal(0.02) the indexer's probabilities are
    all but uniform and every choice a near-tie."""
    rng = np.random.default_rng(seed)
    out = {}
    for name, value in variables.items():
        value = np.asarray(value)
        if "constant_var" in name or re.search(
                r"attention_0/normal_var[45]/", name):
            value = value + rng.normal(size=value.shape).astype(
                np.float32) * 0.2
        elif re.search(r"attention_0/normal_var[01]/", name):
            value = value * 10.0
        out[name] = jnp.asarray(value)
    return out


def _build(dtype: str = "float32", **extra):
    return harness.build(_config(dtype, **extra), lively=_lively)


# ---- the program against the reference ---------------------------------------

@pytest.mark.parametrize("dtype,tolerance,extra", [
    # float32 against float32: only the order of sums differs, so this pins
    # the EQUATIONS: a decay by the held index instead of the layer's, the
    # scale on the wrong side of the output norm, a pooled window that looks
    # ahead, a block too few are off by orders of magnitude
    ("float32", 2e-5, {}),
    # this rank's share as the cell holds it, and the other rank's
    ("float32", 2e-5, {"lightning_heads_held": 2,
                       "block_config": _blocks(2, 1)}),
    ("float32", 2e-5, {"lightning_heads_held": 2, "lightning_heads_first": 2,
                       "block_config": _blocks(2, 1)}),
    # the cell's period: one sparse layer, then three lightning layers
    ("float32", 2e-5, {"block_config": _blocks(order="slll")}),
    ("float32", 2e-5, {"block_config": _blocks(order="ls"), "depth": 2}),
    # at or under the dense length nothing is selected
    ("float32", 2e-5, {"sequence_length": 32}),
    # more pooled windows a block, a longer pooling window, no leading block
    ("float32", 2e-5, {"sparse_kernel_size": 12, "sparse_kernel_stride": 2,
                       "sparse_init_blocks": 0, "sparse_topk": 5}),
    # a sequence of one chunk, shorter than lightning_chunk
    ("float32", 2e-5, {"lightning_chunk": 256}),
    # the configuration's bfloat16, at the other cells' bound
    ("bfloat16", 2 ** -4, {})],
    ids=["float32", "first_share", "second_share", "the_period", "two_deep",
         "dense", "other_sizes", "one_chunk", "bfloat16"])
def program_matches_reference_test(dtype, tolerance, extra):
    config, _, model, batch, variables = _build(dtype, **extra)
    got = harness.assert_program_matches_reference(
        _reference(), (config, _, model, batch, variables), dtype, tolerance)
    assert got.shape == (2, config["sequence_length"], 272)


def loss_and_gradients_match_reference_test():
    """Every parameter's gradient against ``jax.grad`` of the reference's
    ``train_loss``, and none is dead."""
    config, params, model, batch, variables = _build(
        block_config=_blocks(order="sll"))
    ref = _reference()
    tokens, targets = batch["token_x"][..., 0], batch["token_y"][..., 0]
    got = jax.jit(jax.grad(lambda v: model.apply(v, batch).total_loss.data))(
        variables)
    _, want = harness.reference_loss_and_grads(ref, variables, tokens,
                                               targets, config)
    harness.assert_grads_match(got, want, 2e-4, alive=True)


def reference_at_the_next_precision_below_fails_test():
    """``harness.assert_float8_stream_misses``."""
    # at this toy depth of two layers both lie under the cells' 2^-4: what
    # separates them is a bound between the two readings, as the cell's is
    harness.assert_float8_stream_misses(_reference(), _build("bfloat16"), 0.02)


def nothing_looks_ahead_test():
    """Perturb token ``t``: no logit before ``t`` moves — the pooled windows
    a query scores end at or before it — and those from ``t`` on do."""
    config, _, model, batch, variables = _build()
    base, _ = harness.logits_and_loss(model, variables, batch)
    t = 77
    tokens = batch["token_x"].copy()
    tokens[:, t] = (tokens[:, t] + 1) % 256
    moved, _ = harness.logits_and_loss(model, variables,
                                {**batch, "token_x": tokens})
    assert np.array_equal(base[:, :t], moved[:, :t])
    assert np.all(np.max(np.abs(base[:, t:] - moved[:, t:]), axis=-1) > 0)


# ---- the lightning rule --------------------------------------------------------

def _qkv(seed, b, s, h, d, g=None):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(b, s, n, d)), jnp.float32)
                 for n in (h, g or h, g or h))


def _serial(q, k, v, rates):
    q, k, v = (np.asarray(t, np.float64) for t in (q, k, v))
    lam = np.exp(-rates.astype(np.float64))
    state = np.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]))
    out = np.zeros(v.shape)
    for t in range(q.shape[1]):
        state = state * lam[None, :, None, None] \
            + np.einsum("bhd,bhe->bhde", k[:, t], v[:, t])
        out[:, t] = np.einsum("bhd,bhde->bhe", q[:, t], state)
    return out


@pytest.mark.parametrize("chunk", [8, 16, 64])
def chunked_rule_is_the_serial_recurrence_test(chunk):
    q, k, v = _qkv(0, 2, 64, 4, 8)
    rates = lightning_mod.decay_rates(4)
    got, state_max = lightning_mod.lightning_rule(q, k, v, rates, chunk)
    want = _serial(q, k, v, rates)
    assert harness.error(np.asarray(got), want) < 1e-6
    assert float(state_max) > 0 or chunk == 64


def rule_is_ssd_at_one_shared_key_test():
    """Where the two coincide — every head the same key and query, ``dt`` 1,
    ``A`` the decay's rate — the rule is Mamba-2's scan."""
    q, k, v = _qkv(1, 2, 64, 4, 8)
    rates = lightning_mod.decay_rates(4)
    shared = (jnp.broadcast_to(t[:, :, :1], t.shape) for t in (q, k))
    got, _ = lightning_mod.lightning_rule(*shared, v, rates, 16)
    want, _ = ssd(v, jnp.ones(v.shape[:3], jnp.float32), -jnp.asarray(rates),
                  k[:, :, 0], q[:, :, 0], 16)
    assert harness.error(np.asarray(got), np.asarray(want)) < 1e-6


def a_heads_decay_follows_its_index_in_the_whole_layer_test():
    whole = lightning_mod.decay_rates(32)
    np.testing.assert_allclose(whole, 2.0 ** (-8 * np.arange(1, 33) / 32))
    assert np.array_equal(lightning_mod.decay_rates(32, 16, 16), whole[16:])
    assert np.array_equal(lightning_mod.decay_rates(32, 0, 16), whole[:16])
    np.testing.assert_allclose(_reference().decays(
        {"lightning_heads": 32, "lightning_heads_held": 16,
         "lightning_heads_first": 16}), np.exp(-whole[16:]), rtol=1e-6)


# ---- the selection -------------------------------------------------------------

SIZES = sparse.Sizes(8, 4, 8, 4, 1, 16, 32)


def _reference_sizes(sizes: sparse.Sizes) -> dict:
    return dict(zip(("kernel", "stride", "block", "topk", "init_blocks",
                     "window", "dense_length"), sizes))


@pytest.mark.parametrize("sizes", [
    SIZES, sparse.Sizes(16, 4, 8, 6, 2, 8, 32),
    sparse.Sizes(8, 8, 16, 3, 0, 16, 32)], ids=["cell_like", "wide", "coarse"])
def selection_is_the_brute_force_one_test(sizes):
    q, k, _ = _qkv(2, 2, 128, 4, 16, 2)
    got = np.asarray(sparse.select_blocks(q * 3, k * 3, sizes, 0.25))
    want = np.asarray(_reference().selection(q * 3, k * 3,
                                             _reference_sizes(sizes)))
    assert got.shape == want.shape == (2, 2, 128, 128 // sizes.block)
    assert np.array_equal(got, want)
    pos = np.arange(128)
    own = pos // sizes.block
    kept = got.sum(-1)
    # as many as it may see, sparse_topk at most; never one past its own
    assert np.array_equal(kept, np.broadcast_to(
        np.minimum(own + 1, sizes.topk), kept.shape))
    idx = np.arange(got.shape[-1])
    assert not np.any(got & (idx[None, :] > own[:, None]))
    # the forced blocks: the leading ones and those that end at its own
    local = (idx[None, :] <= own[:, None]) & (
        idx[None, :] > own[:, None] - max(1, sizes.window // sizes.block))
    forced = local | ((idx[None, :] < sizes.init_blocks)
                      & (idx[None, :] <= own[:, None]))
    assert np.all(got | ~forced)
    # and the choice depends on the scores: two K/V groups differ
    assert not np.array_equal(got[:, 0], got[:, 1])


def ties_go_to_the_lower_block_test():
    score = jnp.asarray([[1.0, 3.0, 3.0, -jnp.inf, 3.0, 0.5, jnp.inf]])
    assert np.asarray(sparse.top_blocks(score, 3)).tolist() \
        == [[False, True, True, False, False, False, True]]
    assert np.asarray(sparse.top_blocks(score, 7)).tolist() \
        == [[True, True, True, False, True, True, True]]


def kept_shares_are_the_closed_form_test():
    """What the gauges read is a function of the length and the sizes
    alone."""
    from benchmark.roofline import sala_costs
    config = _config()
    q, k, _ = _qkv(3, 1, 128, 4, 16, 2)
    keep = sparse.select_blocks(q, k, SIZES, 0.25)
    share, chose = sparse.kept_shares(keep, SIZES.block)
    assert float(share) == pytest.approx(sala_costs.kept_key_share(config),
                                         rel=1e-6)
    assert float(chose) == sala_costs.choosing_query_share(config) == 0.75


def at_the_dense_length_the_layer_is_the_plain_attention_test():
    """At or under ``sparse_dense_length`` keys every block is kept: the
    layer is ``attention-nope-qk_norm_head-gate_features`` on the same
    weights, bit for bit."""
    config, _, model, batch, variables = _build(sequence_length=32)
    plain = [dict(b, layer=[name.replace("-sparse", "") for name in b["layer"]])
             for b in config["block_config"]]
    other = Model(ModelParameter({**config, "block_config": plain}))
    other.init(batch, seed=13)
    assert np.array_equal(harness.logits_and_loss(model, variables, batch)[0],
                          harness.logits_and_loss(other, variables, batch)[0])
    # and past it the selection changes the result
    config, _, model, batch, variables = _build()
    plain = [dict(b, layer=[name.replace("-sparse", "") for name in b["layer"]])
             for b in config["block_config"]]
    other = Model(ModelParameter({**config, "block_config": plain}))
    other.init(batch, seed=13)
    assert harness.error(harness.logits_and_loss(model, variables, batch)[0],
                  harness.logits_and_loss(other, variables, batch)[0]) > 1e-3


# ---- the selected kernels, interpreted -------------------------------------------

@pytest.fixture
def small_tiles(monkeypatch):
    """Four tiles of 128 keys a sequence of 512, so that tiles die."""
    monkeypatch.setattr(fa, "_SELECT_TILE", 128)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _choice(kind: str, q, k):
    sizes = sparse.Sizes(8, 4, 16, 6, 1, 32, 64)
    if kind == "indexer":
        return sparse.select_blocks(q, k, sizes, q.shape[-1] ** -0.5)
    # a row keeps block 0 and its own: the tiles between are dead for every
    # row of a q tile
    idx = np.arange(32)[None, :]
    own = (np.arange(512) // 16)[:, None]
    keep = (idx == 0) | (idx == own)
    if kind == "late":
        # query 400 keeps its own block and no other while its neighbours
        # keep block 0: its q tile's first cell runs with nothing kept in
        # its row (the finite first maximum)
        keep[400, 0] = False
    if kind == "above":
        # blocks past a row's own: masked by position where the diagonal
        # crosses a cell, in tiles the tables kill above it
        keep = keep | (idx > own)
    return jnp.asarray(np.broadcast_to(keep, (1, 2, 512, 32)))


@pytest.mark.parametrize("kind,tiles", [
    ("indexer", None), ("local", None), ("late", None), ("above", None),
    ("indexer", (128, 256)), ("above", (128, 256))])
def selected_kernels_match_a_masked_softmax_test(small_tiles, monkeypatch,
                                                 kind, tiles):
    if tiles is None:
        assert fa.select_tile(512, 16) == (128, 128)
    else:
        monkeypatch.setattr(fa, "select_tile", lambda s, block: tiles)
    q, k, v = _qkv(4, 1, 512, 4, 32, 2)
    keep = _choice(kind, q, k)
    scale = 32 ** -0.5
    out, lse = fa._select_fwd_impl(q, k, v, keep, scale, 16, True)
    want, want_lse = jax.jit(lambda *t: fa._xla_select_with_lse(
        *t, keep, scale, 16))(q, k, v)
    assert bool(jnp.isfinite(out).all() & jnp.isfinite(lse).all())
    assert harness.error(np.asarray(out), np.asarray(want)) < 1e-5
    assert float(jnp.max(jnp.abs(lse - want_lse))) < 1e-5
    # against the softmax written out: exactly the kept keys <= t
    mask = np.repeat(np.asarray(keep), 16, axis=-1) \
        & (np.arange(512)[:, None] >= np.arange(512)[None, :])
    score = np.einsum("bqgrd,bkgd->bgrqk", np.asarray(q, np.float64).reshape(
        1, 512, 2, 2, 32), np.asarray(k, np.float64)) * scale
    score = np.where(mask[:, :, None], score, -np.inf)
    weight = np.exp(score - score.max(-1, keepdims=True))
    weight /= weight.sum(-1, keepdims=True)
    plain = np.einsum("bgrqk,bkgd->bqgrd", weight,
                      np.asarray(v, np.float64)).reshape(1, 512, 4, 32)
    assert harness.error(np.asarray(out), plain) < 1e-5
    cot = jnp.asarray(np.random.default_rng(9).normal(size=out.shape),
                      jnp.float32)
    got = jax.grad(lambda *t: jnp.sum(fa.flash_select(
        *t, keep, scale, 16, True) * cot), (0, 1, 2))(q, k, v)
    wanted = jax.jit(jax.grad(lambda *t: jnp.sum(fa._xla_select(
        *t, keep, scale, 16) * cot), (0, 1, 2)))(q, k, v)
    for g, w in zip(got, wanted):
        assert harness.error(np.asarray(g), np.asarray(w)) < 1e-5


def tiles_no_row_kept_are_not_visited_test(small_tiles):
    """The table a selected grid reads: a step holds the k tile some row of
    the q tile kept, else repeats the last such one (no fetch, and the cell
    does nothing); the k-outer grid's likewise."""
    q, k, _ = _qkv(4, 1, 512, 4, 32, 2)
    rows, fetch_k, fetch_q = fa._select_tables(_choice("local", q, k), 128,
                                               128, 16)
    assert rows.shape == (2, 512, 128) and rows.dtype == jnp.bfloat16
    assert np.asarray(fetch_k).reshape(2, 4, 4)[0].tolist() == [
        [0, 0, 0, 0], [0, 1, 1, 1], [0, 0, 2, 2], [0, 0, 0, 3]]
    assert np.asarray(fetch_q).reshape(2, 4, 4)[1].tolist() == [
        [0, 1, 2, 3], [1, 1, 1, 1], [2, 2, 2, 2], [3, 3, 3, 3]]
    with pytest.raises(ValueError, match="whole power-of-two number"):
        fa.select_tile(512, 48)


def precomputed_form_runs_no_forward_test(small_tiles):
    """Handed ``(out, lse)`` the call returns them and its backward is the
    selected pass: the gradients of ``flash_select``."""
    q, k, v = _qkv(5, 1, 256, 2, 32, 1)
    keep = sparse.select_blocks(q, k, sparse.Sizes(8, 4, 16, 4, 1, 32, 64),
                                32 ** -0.5)
    scale = 32 ** -0.5
    out, lse = fa._select_fwd_impl(q, k, v, keep, scale, 16, True)
    text = str(jax.make_jaxpr(lambda *t: fa.flash_select_precomputed(
        *t, keep, out, lse, scale, 16, True))(q, k, v))
    assert "pallas_call" not in text
    got = jax.grad(lambda *t: jnp.sum(fa.flash_select_precomputed(
        *t, keep, out, lse, scale, 16, True) ** 2), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *t: jnp.sum(fa.flash_select(
        *t, keep, scale, 16, True) ** 2), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))


# ---- the share ---------------------------------------------------------------------

def _mixer_output(config, variables, h, kind: str):
    """The reference's mixer on the stream ``h``, without the residual."""
    from benchmark.reference import common
    ref = _reference()
    eps = float(config["norm_epsilon"])
    if kind == "lightning":
        return ref.lightning_block(
            common.block_params(variables, 0, 2, ref.LIGHTNING), h,
            jnp.asarray(ref.decays(config)), float(config["rope_theta"]), eps,
            ref.norm_group(config))
    return ref.sparse_block(common.block_params(variables, 0, 0, ref.SPARSE),
                            h, ref.sparse_sizes(config), eps)


def _share_of(variables, rank: int):
    """Rank ``rank``'s columns of the uncut layers' weights: K/V head
    ``rank`` with its 2 query heads, lightning heads ``2 rank ..``."""
    out = {}
    for name, value in variables.items():
        if re.search(r"attention_0/normal_var[012367]/", name):
            n = value.shape[-2] // 2 if "var6" not in name \
                else value.shape[0] // 2
            value = value[..., rank * n:(rank + 1) * n, :] \
                if "var6" not in name else value[rank * n:(rank + 1) * n]
        elif re.search(r"lightning_0/normal_var[0123]/", name):
            value = value[..., rank * 32:(rank + 1) * 32]
        elif "lightning_0/normal_var4/" in name \
                or "lightning_0/constant_var2/" in name:
            value = value[rank * 32:(rank + 1) * 32]
        out[name] = value
    return out


@pytest.mark.parametrize("kind", ["sparse", "lightning"])
def the_shares_add_up_to_the_uncut_layer_test(kind):
    """The two ranks' mixer outputs — each the held heads' part of ``W_o``'s
    sum, by program and reference alike — add up to what the uncut reference
    gives for the whole layer: the sum is the all-reduce nothing stands in
    for."""
    config, params, model, batch, variables = _build()
    rng = np.random.default_rng(11)
    h = jnp.asarray(rng.normal(size=(2, 128, 4, 16)), jnp.float32)
    whole = np.asarray(_mixer_output(config, variables, h, kind))
    parts = []
    for rank in (0, 1):
        cut = {**config, "lightning_heads_held": 2,
               "lightning_heads_first": 2 * rank,
               "block_config": _blocks(2, 1)}
        share = _share_of(variables, rank)
        parts.append(np.asarray(_mixer_output(cut, share, h, kind)))
        # and the program at this share is the reference at this share
        cut_model = Model(ModelParameter(cut))
        cut_model.init(batch, seed=13)
        got, _ = harness.logits_and_loss(cut_model, share, batch)
        want = _reference().forward(share, batch["token_x"][..., 0], cut)
        assert harness.error(got, want) < 2e-5
    assert harness.error(parts[0] + parts[1], whole) < 1e-5
    assert harness.error(parts[0], whole) > 0.1


# ---- the saved choice ----------------------------------------------------------------

def the_replay_reads_the_saved_choice_test(monkeypatch):
    """Under a ``jax.checkpoint`` that saves the layer's names the indexer
    runs ONCE: handed scores that change from one evaluation to the next (a
    host callback that counts), the replay still attends the forward's
    blocks.  Without the names it runs again, and would choose otherwise."""
    calls = []

    def noise(shape):
        calls.append(1)
        return np.random.default_rng(len(calls)).normal(
            size=shape).astype(np.float32)

    real = sparse.block_scores

    def noisy(q, pooled_keys, first, sizes, scale, blocks):
        score = real(q, pooled_keys, first, sizes, scale, blocks)
        return jnp.where(jnp.isfinite(score), jax.pure_callback(
            lambda: noise(score.shape),
            jax.ShapeDtypeStruct(score.shape, jnp.float32)), score)

    monkeypatch.setattr(sparse, "block_scores", noisy)
    params = ModelParameter(_config())
    q, k, v = _qkv(6, 1, 128, 4, 16, 2)
    chan = {"mode": "name", "kinds": frozenset({"attention"}), "min_keys": 0}

    def run(names):
        ctx = types.SimpleNamespace(layer_stats=None, mesh=None,
                                    replay_stash=chan)
        inner = jax.checkpoint(
            lambda *t: spatial.sparse_heads(ctx, params, *t, 0.25),
            policy=jax.checkpoint_policies.save_only_these_names(*names))
        del calls[:]
        grads = jax.jit(jax.grad(lambda *t: jnp.sum(inner(*t) ** 2),
                                 (0, 1, 2)))(q, k, v)
        jax.block_until_ready(grads)
        return len(calls)

    assert run(fa.SAVED_NAMES + (fa.SELECT_NAME,)) == 1
    assert run(()) == 2


def the_choice_rides_the_attention_kind_test():
    """The sparse layer offers its choice with ``(out, lse)``: where the
    attention kind rides the checkpoint, ``sparse_keep`` is among the saved
    names, and the gradients are those of ``remat_policy: "recompute"``."""
    results = {}
    for policy in ("stash", "recompute"):
        config, params, model, batch, variables = _build(remat_policy=policy)
        assert (fa.SELECT_NAME in remat.stash_names(params)) \
            == (policy == "stash")
        results[policy] = jax.jit(jax.value_and_grad(
            lambda v: model.apply(v, batch).total_loss.data))(variables)
    assert float(results["stash"][0]) == float(results["recompute"][0])
    for name, want in results["recompute"][1].items():
        scale = float(jnp.max(jnp.abs(want)))
        assert float(jnp.max(jnp.abs(results["stash"][1][name] - want))) \
            <= 1e-5 * scale, name
    offer = spatial._offer(params, set(_blocks()[0]["layer"][1].split("-")[1:]))
    assert offer.names == fa.SAVED_NAMES + (fa.SELECT_NAME,)
    # out in float32 and lse for 4 heads, a bool a query, a block, a K/V head
    assert offer.nbytes == 4 * 2 * 128 * (16 * 4 + 4) + 2 * 2 * 128 * 16
    dense = ModelParameter(_config(sequence_length=32))
    assert spatial._offer(dense, set(_blocks()[0]["layer"][1].split("-")[1:])
                          ).names == fa.SAVED_NAMES


# ---- refusals ------------------------------------------------------------------------

def decode_forms_are_later_issues_test():
    _, params, model, batch, variables = _build()
    with pytest.raises(NotImplementedError, match="decode"):
        model.apply_decode(variables, batch["token_x"][:, :1],
                           jnp.int32(0), {})
    _, params, model, batch, variables = _build(
        block_config=_blocks(order="l"))
    with pytest.raises(NotImplementedError,
                       match="layer lightning has no incremental decode"):
        model.apply_decode(variables, batch["token_x"][:, :1],
                           jnp.int32(0), {})


@pytest.mark.parametrize("layer,match", [
    ("lightning", "layer lightning on a mesh"),
    (f"{SPARSE}-q_heads4-kv_heads2", "on a mesh")])
def a_mesh_refuses_by_name_test(layer, match):
    from homebrewnlp_tpu.core import sharding as shardlib
    config, _, model, batch, variables = _build(block_config=[_block(layer)])
    mesh = shardlib.build_mesh(ModelParameter({**config, "tpu_size": 2}),
                               jax.devices()[:2])
    with pytest.raises(NotImplementedError, match=match):
        model.apply(variables, batch, mesh=mesh)


@pytest.mark.parametrize("extra,match", [
    ({"memory_reduction_strategy": "revnet"}, "residual_multiplier"),
    ({"sequence_length": 120}, "multiple of lightning's chunk"),
    ({"sequence_length": 100, "lightning_chunk": 100,
      "sparse_dense_length": 16}, "whole blocks")])
def modes_the_layers_do_not_run_refuse_by_name_test(extra, match):
    with pytest.raises((ValueError, NotImplementedError), match=match):
        config = _config(**extra)
        model = Model(ModelParameter(config))
        model.init(_batch(config), seed=1)


@pytest.mark.parametrize("flags,match", [
    ("attention-nope-sparse-window8", "sparse or window"),
    ("attention-nope-qk_norm-qk_norm_head", "qk_norm or qk_norm_head"),
    ("attention-nope-gate-gate_features", "gate or gate_features"),
    ("attention-nope-sparse_topk4", "does not know flag 'sparse_topk4'")])
def bad_attention_flags_refuse_by_name_test(flags, match):
    with pytest.raises(ValueError, match=match):
        spatial._standard_flags(flags.split("-")[1:])


@pytest.mark.parametrize("extra,match", [
    ({"lightning_heads": 0}, "lightning_heads"),
    ({"lightning_chunk": 1.5}, "lightning_chunk"),
    ({"lightning_heads_held": -1}, "lightning_heads_held"),
    ({"lightning_heads_held": 3, "lightning_heads_first": 2},
     "exceeds lightning_heads"),
    ({"lightning_heads_first": 2}, "without lightning_heads_held"),
    ({"lightning_norm_groups": 3}, "lightning_norm_groups 3 divides"),
    ({"lightning_norm_groups": 1, "lightning_heads_held": 2},
     "a rank holds whole groups"),
    ({"sparse_topk": 0}, "sparse_topk"),
    ({"sparse_kernel_size": 6}, "sparse_kernel_stride divides"),
    ({"sparse_window": 12}, "sparse_block_size divides")])
def bad_keys_refuse_by_name_test(extra, match):
    with pytest.raises(ValueError, match=match):
        ModelParameter(_config(**extra))


# ---- the configuration ------------------------------------------------------------------

def the_repos_config_is_the_published_model_test():
    """``configs/minicpm_sala.json`` is the whole model as published, and the
    cell's configuration differs from it by exactly what it lists."""
    with open(os.path.join(REPO, "configs", "minicpm_sala.json")) as f:
        repo = json.load(f)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "minicpm_sala.json")) as f:
        doc = json.load(f)
    kinds = [b["layer"][1].split("-")[0] for b in repo["block_config"][0::2]]
    assert [{"attention": "minicpm4", "lightning": "lightning-attn"}[k]
            for k in kinds] == doc["mixer_types"]
    assert [i for i, k in enumerate(kinds) if k == "attention"] \
        == [0, 9, 16, 17, 22, 29, 30, 31]
    assert all(b["layer"] == ["norm-rms-scale", "mlp-silu"]
               for b in repo["block_config"][1::2])
    assert repo["heads"] * repo["features_per_head"] == doc["hidden_size"] \
        == 4096
    assert repo["heads"] * repo["features_per_head"] \
        * repo["intermediate_feed_forward_multiplier"] \
        == doc["intermediate_size"] == 16384
    assert repo["embedding_multiplier"] == doc["scale_emb"] == 12
    assert repo["residual_multiplier"] == pytest.approx(
        doc["scale_depth"] / doc["published"]["num_hidden_layers"] ** 0.5)
    assert repo["logits_scaling"] \
        == doc["hidden_size"] / doc["dim_model_base"] == 16
    assert repo["norm_epsilon"] == doc["rms_norm_eps"]
    assert repo["vocab_size"] == doc["published"]["vocab_size"] == 73448
    assert (repo["lightning_heads"], repo["lightning_head_features"]) \
        == (doc["published"]["lightning_nh"], doc["lightning_head_dim"])
    cell = doc["config"]
    changed = {k for k in cell if k in repo and cell[k] != repo[k]}
    assert changed == {"block_config", "lightning_heads_held", "vocab_size",
                       "sequence_length", "train_batch_size", "tpu_size",
                       "use_checkpointing"}
    assert cell["block_config"][0]["layer"][1] \
        == f"{SPARSE}-q_heads16-kv_heads1"
    assert [b["layer"][1].split("-")[0] for b in cell["block_config"][0::2]] \
        == ["attention", "lightning", "lightning", "lightning"]
    assert doc["num_hidden_layers"] == 4 == cell["depth"] * 4
    assert (doc["num_attention_heads"], doc["num_key_value_heads"],
            doc["lightning_nh"], doc["lightning_nkv"]) == (16, 1, 16, 16)
    assert cell["lightning_heads_held"] == 16
    assert cell["vocab_size"] == doc["vocab_size"] == 9181 == 73448 // 8
    assert cell["sequence_length"] == doc["max_position_embeddings"] == 16384
    assert set(doc["reduced"]) >= {
        "num_hidden_layers", "depth", "num_attention_heads",
        "num_key_value_heads", "lightning_nh", "lightning_nkv", "vocab_size",
        "max_position_embeddings", "sequence_length", "train_batch_size",
        "tpu_size"}
    for key in ("kernel_size", "kernel_stride", "block_size", "topk",
                "init_blocks", "window_size", "dense_len", "decay",
                "qk_norm", "output_norm"):
        assert key in doc["assumed"], key
    params = ModelParameter({**cell, "model_path": "/tmp/sala"})
    assert not params.unknown_config_keys


# ---- scopes, gauges, the trainer ------------------------------------------------------

@pytest.mark.parametrize("path,scope_name", [
    ("jit(step_fn)/jvp(gpt0)/body0/checkpoint/block0_2_0/lightning_0/in_proj/"
     "dot_general", "body/lightning/in_proj"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_2_0/lightning_0/qk_norm/rsqrt",
     "body/lightning/qk_norm"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_2_0/lightning_0/rope/mul",
     "body/lightning/rope"),
    ("jit(step_fn)/transpose(jvp(gpt0))/body0/block0_4_0/lightning_0/rule/"
     "inter_chunk/while", "body/lightning/rule"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_2_0/lightning_0/rule/intra_chunk/"
     "dot_general", "body/lightning/rule"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_2_0/lightning_0/gate_norm/mul",
     "body/lightning/gate_norm"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_2_0/lightning_0/out_proj/"
     "dot_general", "body/lightning/out_proj"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_0_0/attention_0/sparse_attention/"
     "compress/reduce_sum", "body/attention/sparse_attention/compress"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_0_0/attention_0/sparse_attention/"
     "while/body/index/dot_general", "body/attention/sparse_attention/index"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_0_0/attention_0/sparse_attention/"
     "while/body/select/reduce_sum",
     "body/attention/sparse_attention/select"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_0_0/attention_0/sparse_attention/"
     "attend/flash_attention/flash_fwd_select",
     "body/attention/sparse_attention/attend"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_0_0/attention_0/sparse_attention/"
     "checkpoint_name", "body/attention/sparse_attention"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_0_0/attention_0/gate/dot_general",
     "body/attention/gate"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_0_0/attention_0/dot_general",
     "body/attention")])
def the_new_scopes_fold_test(path, scope_name):
    assert scope_key(path) == scope_name


def traced_ops_carry_the_steps_test():
    """Every step of the rule and of the selection is a named scope of the
    compiled program's ops, which is what the trace reads."""
    _, _, model, batch, variables = _build()
    names = harness.traced_op_names(model, variables, batch)
    for step in ("intra_chunk", "chunk_states", "inter_chunk", "state_out"):
        inside = [n for n in names
                  if re.search(rf"lightning_0/rule/(.*/)?{step}/", n)]
        assert inside, step
        assert {scope_key(n) for n in inside} == {"body/lightning/rule"}
    for step in ("compress", "index", "select", "attend"):
        inside = [n for n in names if re.search(
            rf"attention_0/sparse_attention/(.*/)?{step}/", n)]
        assert inside, step
        assert {scope_key(n) for n in inside} \
            == {f"body/attention/sparse_attention/{step}"}, step


def the_step_reports_the_selection_and_the_state_test():
    """The trainer's metrics carry the kept-key share, the share of the
    queries that chose and the largest carried state; the start-up line
    counts the lightning layers' chunk states and no conv of theirs."""
    from benchmark.roofline import sala_costs
    config, params, model, batch, variables = _build(
        block_config=_blocks(order="sll"))
    info = harness.apply_with_stats(model, variables, batch)
    share = np.asarray(info.layer_stats["sparse_kept_key_share"])
    assert share.shape == (1,)
    assert float(share[0]) == pytest.approx(
        sala_costs.kept_key_share(config), rel=1e-6)
    assert np.asarray(info.layer_stats["sparse_choosing_query_share"]
                      ).tolist() == [0.75]
    assert np.asarray(info.layer_stats["lightning_state_abs_max"]).shape \
        == (2,)
    from homebrewnlp_tpu.train import _LAYER_STATS, _info_metrics
    metrics = _info_metrics(info)
    assert float(metrics["sparse_kept_key_share"]) == pytest.approx(
        float(share[0]))
    assert {"sparse_kept_key_share", "sparse_choosing_query_share",
            "lightning_state_abs_max"} <= set(_LAYER_STATS)
    from homebrewnlp_tpu.model import recurrent
    # [batch 2, 128 / 16 chunks, 4 heads, 16, 16] float32, one layer's
    assert recurrent.ssd_state_bytes(params) == 2 * 8 * 4 * 16 * 16 * 4
    assert recurrent.conv_kernel_layers(params) == 0
    from homebrewnlp_tpu.train import Trainer
    line = Trainer(params, model).publish_stash_plan()
    assert "ssd chunk states 65536 bytes a device" in line
    # at a dense length the gauges read a dense layer
    dense = _build(sequence_length=32)
    info = dense[2].apply(dense[4], dense[3], layer_stats=True)
    assert np.asarray(info.layer_stats["sparse_kept_key_share"]).tolist() \
        == [1.0]


def the_trainer_steps_test():
    """``Trainer.step`` on the toy configuration: the loss falls and the
    step's metrics hold the gauges' sources."""
    config, params, model, batch, _ = _build(
        telemetry_enabled=True, learning_rate=0.01, learning_rate_config={})
    from homebrewnlp_tpu.train import Trainer
    trainer = Trainer(params, model)
    state = trainer.init_state(batch)
    losses = []
    for _ in range(8):
        state, metrics = trainer.step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.05
    assert {"sparse_kept_key_share", "sparse_choosing_query_share",
            "lightning_state_abs_max"} <= set(metrics)


# ---- compiled for a described v5e ---------------------------------------------

def selected_kernels_keep_their_scope_and_names_test(v5e, monkeypatch):
    """MiniCPM-SALA's sparse layer at its published widths and the cell's
    16,384 tokens, compiled for a v5e as a TPU process traces it (PR 46):
    Mosaic accepts the three selected kernels, each runs ONCE — the forward
    outside the block's replay, which reads the saved ``(out, lse)`` and the
    saved choice — all are named ``flash_*_select`` (the
    ``sala_sparse_flash_*`` readers) and fold into
    ``body/attention/sparse_attention/attend``; no causal kernel runs, and
    the indexer's ops carry their steps.  Under ``"recompute"`` the replay
    runs the forward kernel again."""
    kinds = {}
    for policy in ("auto", "recompute"):
        params, hlo = harness.cell_layer_hlo(
            v5e, monkeypatch, "train_minicpm_sala_tp2_long", 0, policy)
        assert "sparse" in params.block_config[0].layer[1]
        assert (remat.stash_plan(params)["attention"][0] == 1) \
            == (policy == "auto")
        calls = harness.kernel_calls(hlo)
        for name, op_name in calls:
            assert re.match(r"flash_.*_select", name), name
            assert scope_key(op_name) \
                == "body/attention/sparse_attention/attend", op_name
        kinds[policy] = sorted(name for name, _ in calls)
        names = set(re.findall(r'op_name="([^"]*)"', hlo))
        for step in ("compress", "index", "select"):
            assert any(scope_key(n)
                       == f"body/attention/sparse_attention/{step}"
                       for n in names), step
    assert kinds["auto"] == ["flash_bwd_dkv_select", "flash_bwd_dq_select",
                             "flash_fwd_select"]
    assert kinds["recompute"] == kinds["auto"] + ["flash_fwd_select"]
