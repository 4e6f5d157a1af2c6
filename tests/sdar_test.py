"""SDAR-30B-A3B-Chat under block-diffusion training through the normal path
(ISSUE 67): the program against the plain reference ``benchmark/reference/
sdar_30b_a3b.py`` in the noised half's logits (three keys), the loss and
every parameter's gradient at toy widths; the mask against its definition at
block lengths 1, 4 and L, the first block's rows; the ``flash_*_blockdiff``
kernels in interpret mode against the dense masked form, forward, fused
backward, dq and dk/dv; the noise against the reference's and its
expectation; the SHARE test on the doubled stream; refusals; what the parent
traced still traces; scopes, gauges, the offer and the cell's parameters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from homebrewnlp_tpu.analysis.cost_ledger import scope_key
from homebrewnlp_tpu.config import ModelParameter
from homebrewnlp_tpu.model import Model, denoise, remat, spatial
from homebrewnlp_tpu.parallel import flash_attention as fa

CELL = "train_sdar_30b_a3b_ep8_s8k"
MASKED = "attention-rope-qk_norm_head-q_heads8-kv_heads2-block_diffusion"


def _blocks(layer: str = MASKED):
    return [{"skip": True, "layer": ["norm-rms-scale", layer]},
            {"skip": True, "layer": ["norm-rms-scale", "moe-silu"]}]


# 8 query heads over 2 K/V heads of 16 on a stream of 4 x 16; 64 trained
# tokens a sequence in blocks of 4 (a stream of 128); 16 experts of which a
# token takes 4; the mask token the last row of the vocabulary
TINY = {"depth": 2, "heads": 4, "features_per_head": 16,
        "sequence_length": 64, "train_batch_size": 2, "vocab_size": 272,
        "experts": 16, "experts_held": 0, "moe_top_k": 4, "expert_width": 24,
        "tpu_size": 1, "use_checkpointing": False, "diffusion_mask_id": -1,
        "block_config": _blocks()}


def _reference():
    return harness.reference("sdar_30b_a3b")


def _lively(variables, seed: int = 3):
    """The seeded weights with the norms' scales moved off 1."""
    rng = np.random.default_rng(seed)
    return {name: jnp.asarray(np.asarray(value) + (
        rng.normal(size=np.shape(value)).astype(np.float32) * 0.2
        if "norm_0/" in name or "attention_0/normal_var3" in name
        or "attention_0/normal_var4" in name else 0.0))
        for name, value in variables.items()}


def _build(dtype: str = "float32", **extra):
    return harness.build(harness.config_of("sdar_30b_a3b", TINY, dtype,
                                           **extra), lively=_lively)


@pytest.fixture(scope="module")
def built():
    return _build()


def _logits(model, variables, batch, key):
    out = jax.jit(lambda v, b: model.apply(v, b, rng=key).token_out.data)(
        variables, batch)
    return np.asarray(out.astype(jnp.float32))[:, :, 0, :]


# ---- the program against the reference ---------------------------------------

@pytest.mark.parametrize("key", [None, 3, 11], ids=["key0_of_apply", "key3",
                                                    "key11"])
def noised_half_logits_match_reference_test(built, key):
    """float32 against float32: only the order of sums differs, so 2e-5 pins
    the EQUATIONS — the noise, the join, rotary by index mod L, the mask, the
    split (a key from the wrong half, a position off by L, a target's own
    block missing are off by orders of magnitude).  Without a key
    ``Model.apply`` is under ``PRNGKey(0)``'s noise: what the harness's
    ``logits_agree`` compares."""
    config, _, model, batch, variables = built
    key = None if key is None else jax.random.PRNGKey(key)
    got = _logits(model, variables, batch, key)
    want = _reference().forward(variables, batch["token_x"][..., 0], config,
                                key=key)
    assert got.shape == want.shape == (2, 64, 272)
    assert harness.error(got, want) < 2e-5


@pytest.mark.parametrize("extra", [{"experts_held": 4, "experts_first": 8}],
                         ids=["a_rank's_share"])
def a_share_of_the_experts_matches_reference_test(extra):
    built = _build(**extra)
    config, _, model, batch, variables = built
    want = _reference().forward(variables, batch["token_x"][..., 0], config)
    assert harness.error(_logits(model, variables, batch, None), want) < 2e-5


def bfloat16_holds_the_cells_bound_and_float8_misses_it_test():
    """The program in bfloat16 against the float32 reference: activations,
    stream and logits carry 8 bits of mantissa (measured here 1-2% of the
    largest logit); the reference with a float8 stream misses 2^-4."""
    harness.assert_float8_stream_misses(_reference(), _build("bfloat16"))


def loss_and_every_gradient_match_reference_test(built):
    """The step's loss under a key of its own — the masked positions alone,
    each at 1 / its rate, against the SAME position's clean token — and
    ``jax.grad`` of it for every parameter against ``jax.grad`` of the
    reference's ``train_loss`` (which adds the router's terms: the program's
    reach the gradients only).  float32 both: 1e-4 of each gradient's
    largest entry is summation order (measured 3e-7 .. 1.3e-6)."""
    config, _, model, batch, variables = built
    ref, key = _reference(), jax.random.PRNGKey(7)
    tokens = batch["token_x"][..., 0]
    v = {k: jnp.asarray(a) for k, a in variables.items()}
    loss, grads = jax.jit(jax.value_and_grad(
        lambda v: model.apply(v, batch, rng=key).total_loss.data))(v)
    want = float(ref.loss(variables, tokens, config, key))
    assert abs(float(loss) - want) < 1e-5 * want
    want_grads = jax.jit(jax.grad(
        lambda v: ref.train_loss(v, tokens, None, config, key)))(v)
    harness.assert_grads_match(grads, want_grads, 1e-4, alive=True)


def token_y_is_not_read_test(built):
    _, _, model, batch, variables = built
    other = {**batch, "token_y": np.zeros_like(batch["token_y"])}
    assert float(harness.loss_of(model)(variables, batch)) \
        == float(harness.loss_of(model)(variables, other))


# ---- the noise ---------------------------------------------------------------

def noise_is_the_references_and_meets_its_expectation_test():
    """``denoise.noise`` draws what the reference draws from the same key,
    and over keys: half the positions masked (rates U[t_min, 1]), the
    weights' mean 1 (E[m / t] = 1), a block's positions at ONE rate, the
    noised token the mask token exactly where the weight is positive."""
    config = {"diffusion_block": 4, "diffusion_t_min": 1e-3,
              "diffusion_mask_id": -1, "vocab_size": 272}
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, 256, (4, 2048)), jnp.int32)
    shares, means = [], []
    for seed in range(8):
        key = jax.random.PRNGKey(seed)
        noised, weights = denoise.noise(key, tokens, 4, 1e-3, 271)
        want_noised, want_weights = _reference().noise(key, tokens, config)
        assert np.array_equal(noised, want_noised)
        assert np.array_equal(weights, want_weights)
        masked = np.asarray(weights) > 0
        assert np.array_equal(np.asarray(noised) == 271, masked)
        assert np.array_equal(np.asarray(noised)[~masked],
                              np.asarray(tokens)[~masked])
        rates = np.where(masked, 1.0 / np.where(masked, weights, 1.0), np.nan)
        blocks = rates.reshape(4, 512, 4)
        assert np.all((np.nanmax(blocks, -1) == np.nanmin(blocks, -1))
                      | np.isnan(np.nanmax(blocks, -1)))
        assert np.nanmin(rates) >= 1e-3 and np.nanmax(rates) <= 1.0
        shares.append(masked.mean())
        means.append(float(np.mean(weights)))
    # 8 x 2,048 blocks: the share's sd is 0.003, the weight mean's 0.02
    assert abs(np.mean(shares) - 0.5) < 0.01
    assert abs(np.mean(means) - 1.0) < 0.08


def the_step_reports_its_noise_test(built):
    _, _, model, batch, variables = built
    stats = harness.apply_with_stats(model, variables, batch).layer_stats
    folded = {k: float(v) for k, v in
              __import__("homebrewnlp_tpu.model.declare", fromlist=["x"]
                         ).fold_stats(stats).items() if "denoise" in k}
    assert 0.3 < folded["denoise_masked_share"] < 0.7
    assert 0.3 < folded["denoise_weight_mean"] < 3.0
    assert folded["denoise_loss"] == pytest.approx(
        float(harness.loss_of(model)(variables, batch)), rel=1e-6)


# ---- the mask ----------------------------------------------------------------

def _dense(q2, k2, v2, scale, mask):
    scores = jnp.einsum("bqhd,bkhd->bhqk", q2 * scale, k2)
    prob = jax.nn.softmax(jnp.where(mask[None, None], scores, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", prob, v2)


def _folded(q2, k2, v2):
    b, s2, h, d = q2.shape
    fold = lambda t: t.reshape(b * 2, s2 // 2, h, d)   # noqa: E731

    def clean(t):
        pair = t.reshape(b, 2, s2 // 2, h, d)
        return jnp.broadcast_to(pair[:, 1:], pair.shape).reshape(
            b * 2, s2 // 2, h, d)
    return fold(q2), fold(k2), fold(v2), clean(k2), clean(v2)


def _stream(seed, b, length, h, d):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.normal(size=(b, 2 * length, h, d)), jnp.float32)
            for _ in range(4)]


def the_mask_is_its_definition_test():
    """``block_diffusion_mask`` written out pair by pair, the reference's
    rows the same, at blocks of 1, 4 and L; the live pairs' count."""
    ref = _reference()
    for length, block in ((8, 1), (8, 4), (8, 8), (16, 4)):
        mask = fa.block_diffusion_mask(length, block)
        for i in range(2 * length):
            for j in range(2 * length):
                bi, bj = (i % length) // block, (j % length) // block
                if i < length and j < length:
                    want = bi == bj
                elif i < length:
                    want = bj < bi
                elif j < length:
                    want = False
                else:
                    want = bj <= bi
                assert mask[i, j] == want, (length, block, i, j)
        assert np.array_equal(mask, ref.mask_rows(np.arange(2 * length),
                                                  length, block))
        assert mask.sum() == fa.block_diffusion_live_pairs(length, block)


@pytest.mark.parametrize("length,block", [(16, 1), (16, 4), (16, 16)],
                         ids=["block_1", "block_4", "one_block_of_L"])
def two_parts_merged_are_the_dense_mask_test(length, block):
    """``block_diffusion_attention`` (XLA's form here: the far part over the
    clean keys of earlier blocks, the own block, merged by log-sum-exp)
    against ONE softmax under the dense ``[2 L, 2 L]`` mask, value and the
    three gradients.  Block L is one bidirectional block a half (no far key
    at all); block 1 a causal model whose query sees the clean keys before it
    and itself."""
    q2, k2, v2, w = _stream(length + block, 1, length, 2, 8)
    mask = jnp.asarray(fa.block_diffusion_mask(length, block))

    def program(q2, k2, v2):
        return fa.block_diffusion_attention(
            *_folded(q2, k2, v2), block, 0.3).reshape(q2.shape)

    got = harness.with_input_grads(program, (q2, k2, v2), w)
    want = harness.with_input_grads(
        lambda q, k, v: _dense(q, k, v, 0.3, mask), (q2, k2, v2), w)
    harness.assert_close_each(got, want, 1e-5, ("out", "dq", "dk", "dv"))
    if block == 1:
        # the noised half at block 1: causal over the clean keys before the
        # query, plus the query's own (noised) key
        causal = np.tril(np.ones((length, length), bool), -1)
        own = np.eye(length, dtype=bool)
        assert np.array_equal(np.asarray(mask)[:length],
                              np.concatenate([own, causal], axis=1))


def the_first_blocks_rows_see_their_own_block_only_test():
    """The first noised block has no clean key: its rows' output is the
    softmax over their own four noised keys, whatever the clean half holds —
    in the merged form (whose far part reads an ``lse`` of -1e30 there)."""
    q2, k2, v2, _ = _stream(5, 1, 16, 2, 8)
    out = fa.block_diffusion_attention(*_folded(q2, k2, v2), 4, 0.3).reshape(
        q2.shape)
    own = jax.nn.softmax(jnp.einsum("qhd,khd->hqk", q2[0, :4] * 0.3,
                                    k2[0, :4]), -1)
    want = jnp.einsum("hqk,khd->qhd", own, v2[0, :4])
    assert harness.error(out[0, :4], want) < 1e-6
    moved = fa.block_diffusion_attention(
        *_folded(q2, k2.at[:, 16:].add(3.0), v2.at[:, 16:].add(3.0)), 4,
        0.3).reshape(q2.shape)
    assert np.array_equal(np.asarray(moved[0, :4]), np.asarray(out[0, :4]))
    assert np.all(np.isfinite(np.asarray(out)))


@pytest.mark.parametrize("tiles,block,form", [
    ((64, 128), 4, "dkv_resident"), ((64, 64), 4, "split"),
    ((128, 128), 8, "dkv_resident"), ((64, 128), 1, "split"),
    ((64, 64), 4, "dq_resident")],
    ids=["fwd_tile_twice_the_q_fused", "square_tiles_dq_and_dkv",
         "block_8_fused", "block_1_dq_and_dkv", "square_tiles_dq_resident"])
def blockdiff_kernels_match_the_dense_mask_test(monkeypatch, tiles, block,
                                                form):
    """The ``flash_*_blockdiff`` kernels, interpreted, at 256 positions a half
    in several tiles — the forward with its carried state, the backward as
    the fused pass (either side resident) and as the dq / dk-dv pair, under
    both cotangents (the
    merge reads ``lse``) — merged with the own blocks: against ONE softmax
    under the dense mask, value and the three gradients.  1e-5: float32,
    summation order."""
    monkeypatch.setattr(fa, "backward_form", lambda *a: form)
    length, (bq, bk) = 256, tiles
    q2, k2, v2, w = _stream(7 + block, 1, length, 2, 16)
    mask = jnp.asarray(fa.block_diffusion_mask(length, block))
    names = []

    def program(q2, k2, v2):
        q, k, v, kc, vc = _folded(q2, k2, v2)
        out_s, lse_s = fa._flash_fwd_impl(
            *(jax.lax.stop_gradient(t) for t in (q, kc, vc)), 0.25, True, bq,
            bk, True, step=block)
        far, far_lse = fa.flash_stepped_precomputed(
            q, kc, vc, out_s, lse_s, 0.25, block, bq, bq, True)
        # the far part alone is the dense stepped form
        dense, dense_lse = fa._xla_stepped_with_lse(q, kc, vc, 0.25, block)
        names.append((far, far_lse, dense, dense_lse))
        own, own_lse = fa._own_block(q, k, v, 0.25, block)
        far_lse = far_lse.reshape(2, 2, length).transpose(0, 2, 1)
        top = jnp.maximum(far_lse, own_lse)
        wf, wo = jnp.exp(far_lse - top), jnp.exp(own_lse - top)
        return (far * (wf / (wf + wo))[..., None]
                + own * (wo / (wf + wo))[..., None]).reshape(q2.shape)

    got = harness.with_input_grads(program, (q2, k2, v2), w)
    want = harness.with_input_grads(
        lambda q, k, v: _dense(q, k, v, 0.25, mask), (q2, k2, v2), w)
    harness.assert_close_each(got, want, 1e-5, ("out", "dq", "dk", "dv"))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(program(q, k, v)), argnums=(0, 1, 2)))(
            q2, k2, v2)
    calls = sorted({eqn.params["name"]
                    for eqn in harness.pallas_calls(jaxpr.jaxpr)})
    assert calls == (["flash_bwd_fused_blockdiff", "flash_fwd_blockdiff"]
                     if form != "split" else ["flash_bwd_dkv_blockdiff",
                                    "flash_bwd_dq_blockdiff",
                                    "flash_fwd_blockdiff"])


def the_far_part_alone_is_the_stepped_dense_form_test():
    """The forward kernel's ``(out, lse)`` against ``_xla_stepped_with_lse``
    on the rows that see a key (the first block's rows hold a finite value
    under an ``lse`` at -1e30 in both)."""
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 256, 2, 16)), jnp.float32)
               for _ in range(3))
    out, lse = fa._flash_fwd_impl(q, k, v, 0.25, True, 64, 128, True, step=4)
    want, want_lse = fa._xla_stepped_with_lse(q, k, v, 0.25, 4)
    assert harness.error(out[:, 4:], want[:, 4:]) < 1e-5
    assert harness.error(lse[:, 4:], want_lse[:, 4:]) < 1e-5
    assert np.all(np.asarray(lse[:, :4]) < -1e29)
    assert np.all(np.isfinite(np.asarray(out)))


def scored_over_live_at_the_cells_shape_test():
    """The cell: 8,192 trained tokens a sequence at head width 128 in
    bfloat16, block 4.  Live pairs L (L + B); the kernels score the causal
    tiles of BOTH halves against the clean keys and XLA the own blocks:
    forward 1.1254, backward 1.0630 of the live pairs — under the issue's
    1.3, and never the ``[2 L, 2 L]`` square (4.0)."""
    assert fa.block_diffusion_live_pairs(8192, 4) == 8192 * 8196
    shares = fa.block_diffusion_scored_over_live(8192, 128, 4, 2)
    causal = fa.scored_over_live(8192, 128, None, 2)
    assert shares["fwd"] == pytest.approx(1.12543, abs=1e-4)
    assert shares["bwd"] == pytest.approx(1.06296, abs=1e-4)
    assert shares["fwd"] < causal["fwd"] + 1e-3 < 1.3
    assert fa.stepped_applies(8192, 128, 4, 2)
    assert not fa.stepped_applies(8192, 128, 3, 2)
    assert not fa.stepped_applies(8192 + 64, 128, 4, 2)
    config = harness.config_of("sdar_30b_a3b", {
        "depth": 1, "vocab_size": 512, "train_batch_size": 1, "tpu_size": 1,
        "experts_held": 16, "diffusion_mask_id": -1}, "bfloat16")
    params = ModelParameter({**config, "model_path": "/tmp/sdar_test"})
    assert spatial.flash_scored_over_live(params, "tpu") == shares
    assert spatial.flash_scored_over_live(params, "cpu") is None
    assert spatial.flash_band_layers(params, "tpu") is None


# ---- the shares --------------------------------------------------------------

def eight_expert_shares_add_up_on_the_doubled_stream_test(built):
    """The guide's share test where the sparse layer is handed ``2 L`` rows:
    the reference's sparse block with 2 of 16 experts held, from each of the
    eight first experts, adds up to the uncut layer on a stream of 128
    positions; and the PROGRAM's eight ranks' logits differ (each leaves the
    others' experts out) while the uncut model's lie within the ranks' span."""
    config, _, _, batch, variables = built
    ref = _reference()
    _, p, _ = [x for x in ref.layers_of(variables, config)
               if x[0] == "sparse"][0]
    h = jnp.asarray(np.random.default_rng(7).normal(size=(2, 128, 4, 16)),
                    jnp.float32)
    from benchmark.reference.keye_vl_2_0_30b_a3b import sparse_block
    whole, _ = sparse_block(p, h, config)
    parts = sum(sparse_block(
        {**p, **{w: p[w][first:first + 2] for w in ("w_gate", "w_up",
                                                    "w_down")}},
        h, {**config, "experts_held": 2, "experts_first": first})[0]
        for first in range(0, 16, 2))
    assert float(jnp.max(jnp.abs(whole))) > 1e-4
    assert harness.error(parts, whole) < 1e-5


# ---- refusals ------------------------------------------------------------------

@pytest.mark.parametrize("flags,message", [
    ("rope-block_diffusion-sparse", "does not build sparse"),
    ("rope-block_diffusion-indexed", "does not build indexed"),
    ("rope-block_diffusion-window8", "does not build window"),
    ("rope-block_diffusion-kv_latent8-shared_key4-q_heads4-kv_heads4",
     "does not build kv_latent")])
def flags_that_do_not_go_with_the_mask_refuse_by_name_test(flags, message):
    with pytest.raises(ValueError, match=message):
        spatial._standard_flags(flags.split("-"))


@pytest.mark.parametrize("extra,message", [
    ({"loop_steps": 2}, "loop_steps 2"),
    ({"mtp_depth": 1, "mtp_block_config": _blocks()}, "mtp_depth 1"),
    ({"memory_reduction_strategy": "revnet"}, "revnet"),
    ({"scan_layers": True}, "scan_layers"),
    ({"sequence_parallel": 2, "tpu_size": 2}, "sequence-sharded"),
    ({"sequence_length": 66}, "no whole blocks of 4"),
    ({"diffusion_t_min": 0.0}, "diffusion_t_min"),
    ({"diffusion_mask_id": 272}, "diffusion_mask_id"),
    ({"diffusion_block": -1}, "diffusion_block")],
    ids=["looped", "mtp", "revnet", "scan_layers", "sequence_parallel",
         "ragged_blocks", "t_min", "mask_id", "negative_block"])
def the_configuration_refuses_by_name_test(extra, message):
    with pytest.raises(ValueError, match=message):
        ModelParameter({**harness.config_of("sdar_30b_a3b", TINY),
                        "model_path": "/tmp/sdar_test", **extra})


def a_pipeline_mesh_refuses_by_name_test():
    with pytest.raises(ValueError, match="refuses a pipeline mesh"):
        ModelParameter({**harness.config_of("sdar_30b_a3b", TINY),
                        "model_path": "/tmp/sdar_test", "depth": 2,
                        "tpu_size": 2, "pipeline_stages": 2,
                        "mesh_shape_override": {"pipe": 2}})


def the_flag_needs_the_doubled_stream_and_serving_refuses_test(built):
    config, _, model, batch, variables = built
    with pytest.raises(ValueError, match="doubled stream"):
        harness.build({**config, "diffusion_block": 0})
    token = jnp.zeros((2, 1, 1), jnp.int32)
    with pytest.raises(NotImplementedError, match="block-diffusion model"):
        model.apply_decode(variables, token, 0, {})
    with pytest.raises(NotImplementedError, match="block-diffusion model"):
        model.apply_prefill(variables, batch["token_x"], 4)


# ---- what the parent traced still traces ---------------------------------------

@pytest.mark.parametrize("window", [None, 64], ids=["causal", "window"])
def causal_flash_calls_trace_as_on_the_parent_test(window):
    """The causal and the windowed call, forward and backward, through the
    kernels the mask's parameter was threaded through: every Pallas call's
    equation is the pinned one, letter for letter."""
    q = jnp.zeros((1, 256, 2, 32), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(fa.flash_attention(
            q, k, v, 0.25, True, 64, 128, True, 64, 64, window)),
        argnums=(0, 1, 2)))(q, q, q)
    calls = {eqn.params["name"]: str(eqn)
             for eqn in harness.pallas_calls(jaxpr.jaxpr)}
    kind = "causal" if window is None else "window"
    assert sorted(calls) == ["flash_bwd_fused_" + kind, "flash_fwd_" + kind]
    harness.pinned(f"kernel/flash_fwd_{kind}", calls["flash_fwd_" + kind])
    harness.pinned(f"kernel/flash_bwd_fused_{kind}",
                   calls["flash_bwd_fused_" + kind])


# ---- scopes, the offer, the cell's parameters -----------------------------------

def scopes_fold_where_the_readers_look_test(built):
    assert scope_key("gpt0/input0/denoise/noise/mul") == "denoise/noise"
    assert scope_key("jvp(gpt0)/input0/denoise/join/concatenate") \
        == "denoise/join"
    assert scope_key("transpose(jvp(gpt0))/output0/denoise/split/slice") \
        == "denoise/split"
    for part in ("halves", "own_block", "lse_merge"):
        assert scope_key(f"gpt0/body0/block0_0_0/attention_0/{part}/x") \
            == f"body/attention/{part}"
    assert scope_key("gpt0/body0/block0_0_0/attention_0/flash_attention/x") \
        == "body/attention"
    _, _, model, batch, variables = built
    keys = {scope_key(name) for name in harness.traced_op_names(
        model, variables, batch, compiled=False)}
    assert {"denoise/noise", "denoise/join", "denoise/split",
            "body/attention/halves", "body/attention/own_block",
            "body/attention/lse_merge", "body/moe/experts",
            "head_loss"} <= keys


def the_offer_is_the_far_parts_pair_over_both_halves_test():
    config = harness.config_of("sdar_30b_a3b", {
        "depth": 2, "vocab_size": 512, "train_batch_size": 1, "tpu_size": 1,
        "experts_held": 16, "diffusion_mask_id": -1}, "bfloat16")
    params = ModelParameter({**config, "model_path": "/tmp/sdar_test"})
    assert params.sequence_dim.size == 16384 == params.stream_length
    assert params.token_sequence_dim.size == 8192
    offer = spatial._offer(params, set(
        config["block_config"][0]["layer"][1].split("-")[1:]))
    assert (offer.kind, offer.names, offer.keys, offer.block) \
        == ("attention", fa.SAVED_NAMES, 8192, 4)
    # out [1, 16384, 32, 128] in bfloat16 and lse [32, 16384] float32
    assert offer.nbytes == 32 * 16384 * (128 * 2 + 4)
    assert remat.stash_plan(params)["attention"] == (2, 2 * offer.nbytes)


def a_layer_of_the_cell_holds_the_issues_parameters_test():
    """The shapes the program builds for the cell at ONE layer: ISSUE 67's
    94,638,336 a layer beside the two table slices and the final norm — the
    count ``benchmark/configs/sdar_30b_a3b.json`` states."""
    from benchmark.lib.cell import load_cell
    cell = load_cell(CELL)
    stated = cell.config_doc["parameters"]
    model = Model(ModelParameter({**cell.model_config(), "depth": 1,
                                  "model_path": "/tmp/sdar_test"}))
    batch = {k: np.zeros((1, 8192, 1), np.int32)
             for k in ("token_x", "token_y")}
    shapes = jax.eval_shape(lambda b: model.init(b, seed=1), batch)
    count = sum(int(np.prod(v.shape)) for v in shapes.values())
    assert count == sum(stated["a_layer"].values()) \
        + stated["tables_and_final_norm"] == 94_638_336 + 77_793_280
    assert stated["program"] == stated["counted"] == stated["issue_67"] \
        == cell.config_doc["num_hidden_layers"] * 94_638_336 + 77_793_280 \
        == 740_261_632
