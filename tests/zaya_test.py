"""ZAYA1-8B's layers through the normal path (ISSUE 39): the program against
the plain reference ``benchmark/reference/zaya1_8b.py`` in logits, loss and
every parameter kind's gradient at toy widths; the convolutions and the value
shift are causal; the router state's cotangent crosses the checkpoint
boundary; the SHARE test (the two ranks' expert parts, with attention, router
and merge counted once, add up to the uncut layer); top-1 with every token's
expert absent; the refusals of the modes that carry no side value; scopes and
gauges."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from harness import REPO
from homebrewnlp_tpu.analysis.cost_ledger import scope_key
from homebrewnlp_tpu.config import BlockArgs, BlockConfig, ModelParameter
from homebrewnlp_tpu.core import scope
from homebrewnlp_tpu.core.tensor import nt
from homebrewnlp_tpu.model import Model, cca as cca_mod, moe as moe_mod, remat
from homebrewnlp_tpu.model.spatial import numbered_flags

CCA = "cca-q_heads4-kv_heads2-rotary_pct50-theta5000000"
MOE = "moe-silu-router_mlp"


def _block(*layers):
    return {"skip": True, "merge": "scaled", "layer": list(layers)}


# 4 query heads over 2 K/V heads of 16 on a stream of 4 x 16 (the latent is
# as wide as the stream here: the toy keeps the head counts' ratio, not the
# compression), 8 routed experts of which the first 4 are held, one a token;
# a vocabulary that is no multiple of 128, as the cell's 32,784 is none
TINY = {"depth": 3, "heads": 4, "features_per_head": 16,
        "sequence_length": 64, "train_batch_size": 2, "vocab_size": 272,
        "experts": 8, "experts_held": 4, "expert_width": 24,
        "moe_router_width": 16, "tpu_size": 1, "use_checkpointing": False,
        "block_config": [_block("norm-rms-scale", CCA),
                         _block("norm-rms-scale", MOE)]}


def _reference():
    return harness.reference("zaya1_8b")


def _config(dtype: str = "float32", **extra) -> dict:
    return harness.config_of("zaya1_8b", TINY, dtype, **extra)


def _batch(config, seed: int = 5):
    return harness.token_batch(config["train_batch_size"],
                               config["sequence_length"], seed)


def _lively(variables, seed: int = 3):
    """The seeded weights with the vectors that start at a constant (the
    merge's four, the conv biases, tau, the router's biases, gain and norm)
    moved off it, so that a wrong use of any of them shows, and the router's
    matrices scaled up: at normal(0.02) its logits are ~0.01 apart and the
    biases alone would choose one expert for every token."""
    rng = np.random.default_rng(seed)
    out = {}
    for name, value in variables.items():
        value = np.asarray(value)
        if "constant_var" in name:
            value = value + rng.normal(size=value.shape).astype(
                np.float32) * 0.2
        elif any(f"moe_0/normal_var{i}/" in name for i in (3, 4, 5, 6)):
            value = value * 25.0
        out[name] = jnp.asarray(value)
    return out


def _build(dtype: str = "float32", **extra):
    return harness.build(_config(dtype, **extra), lively=_lively)


# ---- the program against the reference ---------------------------------------

@pytest.mark.parametrize("dtype,tolerance,extra", [
    # float32 against float32: only the order of sums differs, so this pins
    # the EQUATIONS: a conv tap on the wrong side, the mean of the convolved
    # latents, tau on the query, the previous token's values on the wrong
    # K/V head, a renormalised top-1 are off by orders of magnitude
    ("float32", 2e-5, {}),
    # every expert held (the uncut layer) and the OTHER rank's share
    ("float32", 2e-5, {"experts_held": 0}),
    ("float32", 2e-5, {"experts_held": 4, "experts_first": 4}),
    # one layer: no router state arrives; five: it crosses four attention
    # blocks
    ("float32", 2e-5, {"depth": 1}),
    ("float32", 2e-5, {"depth": 5}),
    # more taps than the published two, and a conv of one tap (no shift)
    ("float32", 2e-5, {"cca_time0": 3, "cca_time1": 4}),
    ("float32", 2e-5, {"cca_time0": 1, "cca_time1": 1}),
    # the configuration's bfloat16, at the cells' bound
    ("bfloat16", 2 ** -4, {})],
    ids=["float32", "all_held", "second_share", "one_layer", "five_layers",
         "more_taps", "one_tap", "bfloat16"])
def program_matches_reference_test(dtype, tolerance, extra):
    config, _, model, batch, variables = _build(dtype, **extra)
    got = harness.assert_program_matches_reference(
        _reference(), (config, _, model, batch, variables), dtype, tolerance)
    assert got.shape == (2, 64, 272)


#: one parameter of every kind the issue names, by its path below a block
KINDS = {"conv taps (depthwise)": "block1_0_0/cca_0/normal_var4",
         "conv bias (depthwise)": "block1_0_0/cca_0/constant_var0",
         "conv taps (grouped)": "block1_0_0/cca_0/normal_var5",
         "conv bias (grouped)": "block1_0_0/cca_0/constant_var1",
         "tau": "block1_0_0/cca_0/constant_var2",
         "Wv2": "block1_0_0/cca_0/normal_var3",
         "g_l": "block1_1_0/moe_0/constant_var1",
         "router down": "block0_1_0/moe_0/normal_var3",
         "router norm": "block1_1_0/moe_0/constant_var2",
         "router W1": "block1_1_0/moe_0/normal_var4",
         "router b2": "block1_1_0/moe_0/constant_var4",
         "router W3": "block1_1_0/moe_0/normal_var6",
         "merge a_r": "block1_0_0/merge_0/constant_var0",
         "merge b_r": "block1_1_0/merge_0/constant_var1",
         "merge a_o": "block1_1_0/merge_0/constant_var2",
         "merge b_o": "block1_0_0/merge_0/constant_var3"}


def loss_and_gradients_match_reference_test():
    """Every parameter's gradient against ``jax.grad`` of the reference's
    ``train_loss`` (cross-entropy plus the balance term the step injects),
    and none of the kinds the issue names is dead."""
    config, params, model, batch, variables = _build()
    assert params.train and params.moe_balance_loss
    ref = _reference()
    tokens, targets = batch["token_x"][..., 0], batch["token_y"][..., 0]
    got = jax.jit(jax.grad(lambda v: model.apply(v, batch).total_loss.data))(
        variables)
    _, want = harness.reference_loss_and_grads(ref, variables, tokens,
                                               targets, config)
    harness.assert_grads_match(got, want, 2e-4)
    for kind, path in KINDS.items():
        name = f"gpt0/body0/{path}/var0"
        assert float(jnp.max(jnp.abs(got[name]))) > 0, kind
    # the first layer's gain multiplies r_{-1} = 0
    assert float(jnp.max(jnp.abs(
        got["gpt0/body0/block0_1_0/moe_0/constant_var1/var0"]))) == 0


def reference_at_the_next_precision_below_fails_test():
    """``harness.assert_float8_stream_misses``."""
    harness.assert_float8_stream_misses(_reference(), _build("bfloat16"))


# ---- causality -------------------------------------------------------------------

@pytest.mark.parametrize("extra", [{}, {"cca_time0": 3, "cca_time1": 4}],
                         ids=["two_taps", "more_taps"])
def nothing_looks_ahead_test(extra):
    """Perturb token ``t``: no logit at a position before ``t`` moves (the
    two convolutions, the value shift and the q-k mean are causal), and the
    logits from ``t`` on do."""
    config, _, model, batch, variables = _build(**extra)
    base, _ = harness.logits_and_loss(model, variables, batch)
    t = 37
    other = {k: np.array(v) for k, v in batch.items()}
    other["token_x"][:, t] = (other["token_x"][:, t] + 1) % 256
    moved, _ = harness.logits_and_loss(model, variables, other)
    np.testing.assert_array_equal(moved[:, :t], base[:, :t])
    assert np.max(np.abs(moved[:, t] - base[:, t])) > 1e-4
    assert np.max(np.abs(moved[:, t + 1] - base[:, t + 1])) > 1e-5


def the_shift_and_the_grouped_conv_are_causal_test():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 9, 3, 4)).astype(np.float32))
    np.testing.assert_array_equal(np.asarray(cca_mod.shift_tokens(x, 2))[:, 2:],
                                  np.asarray(x)[:, :-2])
    assert not np.any(np.asarray(cca_mod.shift_tokens(x, 2))[:, :2])
    assert cca_mod.shift_tokens(x, 0) is x
    w = jnp.asarray(rng.normal(size=(3, 3, 4, 4)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(3, 4)).astype(np.float32))
    got = np.asarray(cca_mod.causal_grouped_conv(x, w, b))
    want = np.zeros_like(got) + np.asarray(b)
    for t in range(9):
        for i in range(3):
            src = t - 2 + i
            if src >= 0:
                want[:, t] += np.einsum("bgi,gio->bgo", np.asarray(x)[:, src],
                                        np.asarray(w)[i])
    np.testing.assert_allclose(got, want, atol=1e-5)


def unit_heads_bound_the_logit_test():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(2, 8, 4, 16)).astype(np.float32) * 7)
    k = jnp.asarray(rng.normal(size=(2, 8, 2, 16)).astype(np.float32) * 0.01)
    tau = jnp.asarray([1.5, -0.5])
    qn = cca_mod.unit_heads(q, 4.0)
    kn = cca_mod.unit_heads(k, 4.0 * tau[:, None])
    np.testing.assert_allclose(np.linalg.norm(np.asarray(qn), axis=-1), 4.0,
                               rtol=1e-5)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(kn), axis=-1),
        np.broadcast_to(4.0 * np.abs(np.asarray(tau)), (2, 8, 2)), rtol=1e-5)
    logits = np.einsum("bshd,btgd->bhgst", np.asarray(qn), np.asarray(kn)) / 4
    assert np.max(np.abs(logits)) <= 4.0 * 1.5 * (1 + 1e-5)


# ---- the carried side value ------------------------------------------------------

def the_router_state_crosses_the_checkpoint_boundary_test():
    """The gradient under ``checkpoint`` (every block a ``jax.checkpoint``
    region with the router state an operand in and out) equals the one under
    ``none``, and layer ``l``'s router state reaches layer ``l - 1``'s
    router: with the later layers' gains at zero the first router's
    gradient is another."""
    grads = {}
    for strategy in ("checkpoint", "none"):
        config, _, model, batch, variables = _build(
            memory_reduction_strategy=strategy)
        grads[strategy] = jax.jit(jax.grad(
            lambda v: model.apply(v, batch).total_loss.data))(variables)
    for name in grads["none"]:
        np.testing.assert_allclose(
            np.asarray(grads["checkpoint"][name]),
            np.asarray(grads["none"][name]), rtol=1e-5,
            atol=1e-6 * float(jnp.max(jnp.abs(grads["none"][name]))) + 1e-12)
    cut = {k: (jnp.zeros_like(v) if k.endswith("moe_0/constant_var1/var0")
               else v) for k, v in variables.items()}
    alone = jax.jit(jax.grad(
        lambda v: model.apply(v, batch).total_loss.data))(cut)
    first = "gpt0/body0/block0_1_0/moe_0/normal_var3/var0"
    full = grads["checkpoint"][first]
    assert float(jnp.max(jnp.abs(full - alone[first]))) \
        > 1e-3 * float(jnp.max(jnp.abs(full)))


def the_router_state_is_an_operand_of_every_region_test():
    """In the gradient's jaxpr every checkpoint region after the first
    sparse block takes and returns the float32 ``[tokens, width]`` state."""
    config, _, model, batch, variables = _build()
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda v: model.apply(v, batch).total_loss.data))(variables)
    regions = [e for e in jaxpr.jaxpr.eqns if e.primitive.name
               in ("checkpoint", "remat", "remat2")]
    assert len(regions) >= 2 * config["depth"]
    state = (2 * 64, 16)
    carrying = [e for e in regions
                if any(getattr(v.aval, "shape", None) == state
                       for v in e.invars)]
    assert len(carrying) >= 2 * config["depth"] - 2


@pytest.mark.parametrize("extra,match", [
    ({"scan_layers": True}, "carried side value"),
    ({"memory_reduction_strategy": "revnet"}, "revnet"),
    ({"memory_reduction_strategy": "momentum"}, "revnet / momentum")])
def modes_without_a_side_value_refuse_by_name_test(extra, match):
    config = _config(**extra)
    model = Model(ModelParameter(config))
    batch = _batch(config)
    with pytest.raises(NotImplementedError, match=match):
        variables = model.init(batch, seed=1)
        jax.grad(lambda v: model.apply(v, batch).total_loss.data)(variables)


def decode_and_the_stats_probe_refuse_by_name_test():
    params = ModelParameter(_config())
    x = nt(jnp.zeros((2, 64, 4, 16)), [params.batch_dim, params.sequence_dim]
           + list(params.feature_dims))
    ctx = scope.Context("init", params={})
    assert ctx.side is None
    with scope.context(ctx), pytest.raises(NotImplementedError,
                                           match="carried side value"):
        scope.scoped("moe_", moe_mod.moe, BlockArgs(params, x,
                                                    ["silu", "router_mlp"]))
    ctx = scope.Context("init", params={}, decode=object())
    with scope.context(ctx), pytest.raises(NotImplementedError,
                                           match="decode / prefill"):
        scope.scoped("cca_", cca_mod.cca, BlockArgs(
            params, x, CCA.split("-")[1:]))


# ---- the share test ----------------------------------------------------------------

def _moe_layer(params, weights, x, state=None):
    """Layer ``moe-silu-router_mlp`` of ``params`` on the normed ``x [b, s,
    heads, features]`` with the given weights (the reference's short names):
    ``(output, the router state it leaves)``."""
    out, ctx = harness.layer_on(
        params, moe_mod.moe, _reference().SPARSE, weights, x,
        ["silu", "router_mlp"],
        {} if state is None else {moe_mod.ROUTER_STATE: state})
    return out, ctx.side[moe_mod.ROUTER_STATE]


def the_shares_add_up_to_the_uncut_layer_test():
    """The two expert-parallel ranks (experts 0-3 and 4-7 here, 0-7 and 8-15
    in the cell): their expert parts add up to what the uncut reference
    gives for the whole layer — the router, whole on both ranks, chooses
    alike and is counted once (one state leaves, whichever rank) — and so do
    the reference's own shares."""
    ref = _reference()
    rng = np.random.default_rng(2)
    heads, width, n_exp, inter, rw = 4, 16, 8, 24, 16

    def normal(*shape, scale=0.3):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32) * scale)

    whole = {"w_gate": normal(n_exp, heads, width, inter),
             "w_up": normal(n_exp, heads, width, inter),
             "w_down": normal(n_exp, inter, heads, width),
             "r_down": normal(heads, width, rw), "r_down_bias": normal(rw),
             "r_gain": 1 + normal(rw), "r_norm": 1 + normal(rw),
             "r_w1": normal(rw, rw, scale=1.0), "r_b1": normal(rw),
             "r_w2": normal(rw, rw, scale=1.0), "r_b2": normal(rw),
             "r_w3": normal(rw, n_exp, scale=2.0)}
    ones = jnp.ones((heads, width))
    x = ref.rms(normal(2, 64, heads, width, scale=1.0), ones, 1e-5)
    before = normal(2, 64, rw)
    # rms of an already normed x with a scale of one is x again (to 1e-5)
    _, state, weights, _ = ref.route({**whole, "w_norm": ones}, x, before,
                                     1e-5)
    assert np.all(np.sum(np.asarray(weights) > 0, axis=-1) == 1)
    uncut = ref.routed_part(whole, x, weights, 0, n_exp)

    parts, ref_parts = [], []
    for rank in range(2):
        first = 4 * rank
        params = ModelParameter(_config(experts_held=4, experts_first=first))
        share = dict(whole, **{k: whole[k][first:first + 4]
                               for k in ("w_gate", "w_up", "w_down")})
        part, left = _moe_layer(params, share, x, before)
        parts.append(part)
        ref_parts.append(ref.routed_part(share, x, weights, first, 4))
        np.testing.assert_allclose(np.asarray(part),
                                   np.asarray(ref_parts[-1]), rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(left).reshape(2, 64, rw),
                                   np.asarray(state), rtol=2e-5, atol=2e-5)
        assert float(jnp.max(jnp.abs(part))) > 1e-3
    # a token's one expert lives on exactly one rank: the other adds zero
    assert not np.any((np.abs(np.asarray(parts[0])).sum((-1, -2)) > 0)
                      & (np.abs(np.asarray(parts[1])).sum((-1, -2)) > 0))
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(uncut),
                               rtol=2e-5, atol=5e-5)
    np.testing.assert_allclose(np.asarray(sum(ref_parts)), np.asarray(uncut),
                               rtol=2e-5, atol=5e-5)
    # and the program's own uncut layer
    np.testing.assert_allclose(
        np.asarray(_moe_layer(ModelParameter(_config(experts_held=0)), whole,
                              x, before)[0]), np.asarray(uncut), rtol=2e-5,
        atol=5e-5)


def every_tokens_expert_absent_gives_zero_test():
    """Top-1 with EVERY token's expert on the other rank: the routed part is
    exactly zero, the model still agrees with the reference, and every
    gradient is finite (the held experts' are zero)."""
    ref = _reference()
    config, params, model, batch, variables = _build()
    skewed = dict(variables)
    for name in variables:
        if name.endswith("moe_0/normal_var6/var0"):      # W3 [width, experts]
            w = np.array(variables[name])
            w[:, :4] = 0.0
            w[:, 4:] = 0.0
            skewed[name] = jnp.asarray(w)
        if name.endswith("moe_0/constant_var4/var0"):    # b2: gelu(b2) > 0
            skewed[name] = jnp.full_like(variables[name], 3.0)
    for name in variables:
        if name.endswith("moe_0/normal_var6/var0"):
            w = np.array(skewed[name])
            w[:, 5] = 1.0          # gelu(...) is positive: expert 5 wins
            skewed[name] = jnp.asarray(w)
        if name.endswith("moe_0/normal_var5/var0"):      # W2 = 0: u = gelu(b2)
            skewed[name] = jnp.zeros_like(variables[name])
    info = jax.jit(lambda v: model.apply(v, batch, layer_stats=True))(skewed)
    assert np.asarray(info.layer_stats["moe_held_pairs"]).tolist() == [0.0] * 3
    assert np.asarray(info.layer_stats["moe_routed_pairs"]).tolist() \
        == [128.0] * 3
    got = np.asarray(info.token_out.data.astype(jnp.float32))[:, :, 0, :]
    want = np.asarray(ref.forward(skewed, batch["token_x"][..., 0], config))
    assert harness.error(got, want) < 2e-5
    params_f = ModelParameter(_config())
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(2, 64, 4, 16)).astype(np.float32))
    weights = {short: skewed[f"gpt0/body0/block0_1_0/{path}/var0"]
               for short, path in ref.SPARSE.items()}
    assert not np.any(np.asarray(_moe_layer(params_f, weights, x)[0]))
    _, grads = harness.loss_and_grads(model, skewed, batch)
    assert all(np.all(np.isfinite(np.asarray(g))) for g in grads.values())
    assert not np.any(np.asarray(
        grads["gpt0/body0/block1_1_0/moe_0/normal_var0/var0"]))


def rows_no_kernel_wrote_reach_no_gradient_test():
    """The rows past the held groups poisoned with NaN, in the grouped
    matmuls' outputs and in their lhs cotangents (what a kernel that never
    writes them may leave there): the loss and every gradient stay finite,
    because every read of them is selected away."""
    orig = moe_mod.grouped_dot

    @jax.custom_vjp
    def poison_out(x, live):
        return jnp.where(live[:, None], x, jnp.nan)
    poison_out.defvjp(
        lambda x, live: (jnp.where(live[:, None], x, jnp.nan), live),
        lambda live, g: (g, None))

    @jax.custom_vjp
    def poison_in(x, live):
        return x
    poison_in.defvjp(
        lambda x, live: (x, live),
        lambda live, g: (jnp.where(live[:, None], g, jnp.nan), None))

    def poisoned(lhs, rhs, sizes):
        live = jnp.arange(lhs.shape[0]) < jnp.sum(sizes)
        return poison_out(orig(poison_in(lhs, live), rhs, sizes), live)

    config, _, model, batch, variables = _build()
    moe_mod.grouped_dot = poisoned
    try:
        loss, grads = harness.loss_and_grads(model, variables, batch)
    finally:
        moe_mod.grouped_dot = orig
    assert np.isfinite(float(loss))
    assert all(np.all(np.isfinite(np.asarray(g))) for g in grads.values())


def the_replay_holds_the_forwards_choice_test():
    """Where the ``experts`` kind rides the checkpoint (model/remat.py), the
    router's CHOICE is saved with the sort it made (``moe_experts``), and the
    gradients are those of ``remat_policy: "recompute"`` bit for bit.  (A
    replay that chose again could choose otherwise at a near-tie and read,
    by the saved ``inverse``, a row no kernel wrote: NaN on the chip, PR
    39.)"""
    results = {}
    for policy in ("stash", "recompute"):
        config, params, model, batch, variables = _build(remat_policy=policy)
        assert ("moe_experts" in remat.stash_names(params)) \
            == (policy == "stash")
        results[policy] = jax.jit(jax.value_and_grad(
            lambda v: model.apply(v, batch).total_loss.data))(variables)
    assert float(results["stash"][0]) == float(results["recompute"][0])
    for name, want in results["recompute"][1].items():
        assert np.array_equal(np.asarray(results["stash"][1][name]),
                              np.asarray(want)), name


# ---- the scaled merge --------------------------------------------------------------

def the_merge_starts_as_the_plain_residual_test():
    """At initialisation (a = 1, b = 0) the scaled merge is ``x + f(x)``: the
    model with ``merge`` left out, on the same weights, gives the same
    logits; the vectors exist, four a block part."""
    config = _config()
    params = ModelParameter(config)
    model = Model(params)
    batch = _batch(config)
    variables = model.init(batch, seed=13)
    merges = [n for n in variables if "/merge_0/" in n]
    assert len(merges) == 4 * 2 * config["depth"]
    assert all(variables[n].shape == (4, 16) for n in merges)
    plain = dict(config, block_config=[
        {"skip": True, "layer": b["layer"]} for b in config["block_config"]])
    plain_model = Model(ModelParameter(plain))
    plain_vars = plain_model.init(batch, seed=13)
    assert set(plain_vars) == set(variables) - set(merges)
    got, _ = harness.logits_and_loss(model, variables, batch)
    want, _ = harness.logits_and_loss(plain_model,
                               {k: variables[k] for k in plain_vars}, batch)
    np.testing.assert_allclose(got, want, atol=1e-6)


@pytest.mark.parametrize("block,match", [
    ({"skip": True, "merge": "gated", "layer": ["norm-rms-scale"]}, "gated"),
    ({"skip": False, "merge": "scaled", "layer": ["norm-rms-scale"]},
     "without skip")])
def bad_merges_refuse_by_name_test(block, match):
    with pytest.raises(ValueError, match=match):
        BlockConfig(block, "checkpoint")


# ---- refusals ------------------------------------------------------------------------

@pytest.mark.parametrize("flags,match", [
    (["q_heads4", "kv_heads2", "window32"], "window32"),
    (["q_heads4", "kv_heads2", "rope"], "rope"),
    (["q_heads4"], "come together"),
    (["q_heads6", "kv_heads4"], "must divide")])
def unknown_cca_flags_refuse_by_name_test(flags, match):
    with pytest.raises(ValueError, match=match):
        numbered_flags(flags, (), cca_mod._NUMBERED, "layer cca")


@pytest.mark.parametrize("layer,match", [
    ("cca-rotary_pct50", "head counts"),
    ("cca-q_heads3-kv_heads3", "two halves"),
    (CCA.replace("rotary_pct50", "rotary_pct30"), "rotary_pct30"),
    ("moe-silu-router_mlp-capacity2", "capacity2")])
def bad_layers_refuse_at_init_test(layer, match):
    config = _config()
    config["block_config"] = [_block("norm-rms-scale", layer)]
    model = Model(ModelParameter(config))
    with pytest.raises(ValueError, match=match):
        model.init(_batch(config), seed=1)


def the_matrices_that_write_into_the_stream_start_smaller_test():
    """``residual_out_stddev``: CCA's output projection and the experts'
    down-projection at that standard deviation, every other matrix at 0.02;
    0 = 0.02 for them too (the whole-model file runs Megatron's 0.02 /
    sqrt(2 x 40))."""
    config = _config(residual_out_stddev=0.002, depth=1)
    batch = _batch(config)
    small = Model(ModelParameter(config)).init(batch, seed=3)
    plain = Model(ModelParameter(dict(config, residual_out_stddev=0.0))).init(
        batch, seed=3)
    out = {"gpt0/body0/block0_0_0/cca_0/normal_var6/var0",
           "gpt0/body0/block0_1_0/moe_0/normal_var2/var0"}
    for name in small:
        if name in out:
            assert np.std(small[name]) == pytest.approx(0.002, rel=0.1)
            np.testing.assert_allclose(np.asarray(small[name]) * 10,
                                       np.asarray(plain[name]), rtol=1e-5)
        else:
            np.testing.assert_array_equal(np.asarray(small[name]),
                                          np.asarray(plain[name]))
    with open(os.path.join(REPO, "configs", "zaya1_8b.json")) as f:
        assert json.load(f)["residual_out_stddev"] == pytest.approx(
            0.02 / 80 ** 0.5)


@pytest.mark.parametrize("extra,match", [
    ({"residual_out_stddev": -1}, "residual_out_stddev"),
    ({"cca_time0": 0}, "cca_time0"),
    ({"cca_time1": 1.5}, "cca_time1"),
    ({"moe_router_width": 0}, "moe_router_width")])
def bad_keys_refuse_by_name_test(extra, match):
    with pytest.raises(ValueError, match=match):
        ModelParameter(_config(**extra))


# ---- the repo's config, scopes, gauges -------------------------------------------

def the_repos_config_is_the_published_model_test():
    """``configs/zaya1_8b.json`` against the catalog's published keys that
    the benchmark's file repeats."""
    with open(os.path.join(REPO, "configs", "zaya1_8b.json")) as f:
        config = json.load(f)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "zaya1_8b.json")) as f:
        doc = json.load(f)
    params = ModelParameter(config)
    assert not params.unknown_config_keys
    assert params.heads * params.features_per_head == doc["hidden_size"]
    assert params.features_per_head == doc["head_dim"]
    assert params.expert_intermediate[0].size == doc["moe_intermediate_size"]
    assert params.expert_dim.size == doc["published"]["num_experts"] == 16
    assert params.moe_top_k == doc["num_experts_per_tok"] == 1
    assert params.moe_router_width == doc["router_hidden_size"]
    assert (params.cca_time0, params.cca_time1) == (doc["cca_time0"],
                                                    doc["cca_time1"])
    assert params.norm_epsilon == doc["rms_norm_eps"]
    assert params.vocab_size == doc["published"]["vocab_size"]
    assert params.depth == doc["published"]["num_hidden_layers"] \
        == len(doc["layer_types"])
    assert params.sequence_length == doc["published"][
        "max_position_embeddings"]
    assert params.tie_word_embeddings == doc["tie_word_embeddings"]
    attention, sparse = (b["layer"][1] for b in config["block_config"])
    flags = numbered_flags(attention.split("-")[1:], (), cca_mod._NUMBERED,
                           "layer cca")
    rope = doc["rope_parameters"]["hybrid"]
    assert flags == {"q_heads": doc["num_attention_heads"],
                     "kv_heads": doc["num_key_value_heads"],
                     "rotary_pct": int(100 * rope["partial_rotary_factor"]),
                     "theta": rope["rope_theta"]}
    assert sparse == MOE
    assert all(b["merge"] == "scaled" for b in config["block_config"])
    cell = doc["config"]
    assert cell["block_config"] == config["block_config"]
    assert cell["experts"] == 16 and cell["experts_held"] \
        == doc["num_experts"] == 8
    assert cell["vocab_size"] == doc["vocab_size"] == 32784
    assert cell["depth"] == doc["num_hidden_layers"]


@pytest.mark.parametrize("path,scope_name", [
    ("jit(step_fn)/jvp(gpt0)/body0/checkpoint/block0_0_0/cca_0/in_proj/dot_general",
     "body/cca/in_proj"),
    ("jit(step_fn)/transpose(jvp(gpt0))/body0/block0_0_0/cca_0/qk_mean/add",
     "body/cca/qk_mean"),
    ("jit(step_fn)/jvp(gpt0)/body0/block3_0_0/cca_0/conv/dot_general",
     "body/cca/conv"),
    ("jit(step_fn)/jvp(gpt0)/body0/block3_0_0/cca_0/qk_norm/rsqrt",
     "body/cca/qk_norm"),
    ("jit(step_fn)/jvp(gpt0)/body0/block3_0_0/cca_0/rope/mul",
     "body/cca/rope"),
    ("jit(step_fn)/jvp(gpt0)/body0/block3_0_0/cca_0/value_shift/pad",
     "body/cca/value_shift"),
    ("jit(step_fn)/jvp(gpt0)/body0/block3_0_0/cca_0/out_proj/dot_general",
     "body/cca/out_proj"),
    ("jit(step_fn)/jvp(gpt0)/body0/block3_0_0/cca_0/flash_attention/x",
     "body/cca"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_1_0/moe_0/router/down/dot_general",
     "body/moe/router/down"),
    ("jit(step_fn)/transpose(jvp(gpt0))/body0/block0_1_0/moe_0/router/carry/mul",
     "body/moe/router/carry"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_1_0/moe_0/router/mlp/erf",
     "body/moe/router/mlp"),
    # the router's softmax and top-k, and the one-matrix router, stay here
    ("jit(step_fn)/jvp(gpt0)/body0/block0_1_0/moe_0/router/top_k",
     "body/moe/router"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_1_0/moe_0/experts/gmm",
     "body/moe/experts"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_1_0/merge_0/merge/add",
     "body/merge"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_1_0/mamba_0/conv/mul",
     "body/mamba/conv")])
def the_new_scopes_fold_test(path, scope_name):
    assert scope_key(path) == scope_name


def the_step_reports_the_router_and_the_logit_bound_test():
    """The trainer's metrics carry the chosen expert's mean probability in
    the layer where it is smallest and the largest ``sqrt(d) |tau|``; the
    start-up line ends with the carried router states' bytes."""
    config, params, model, batch, variables = _build()
    info = harness.apply_with_stats(model, variables, batch)
    top1 = np.asarray(info.layer_stats["moe_top1_weight_mean"])
    assert top1.shape == (3,) and np.all(top1 >= 1 / 8) and np.all(top1 <= 1)
    scales = np.asarray(info.layer_stats["cca_logit_scale"])
    taus = [np.abs(np.asarray(variables[
        f"gpt0/body0/block{d}_0_0/cca_0/constant_var2/var0"])).max()
        for d in range(3)]
    np.testing.assert_allclose(scales, 4.0 * np.asarray(taus), rtol=1e-6)
    from homebrewnlp_tpu.train import _LAYER_STATS, _info_metrics
    metrics = _info_metrics(info)
    assert float(metrics["moe_top1_weight_mean"]) == pytest.approx(top1.min())
    assert float(metrics["cca_logit_scale_max"]) == pytest.approx(
        scales.max())
    assert float(metrics["moe_held_pair_share"]) == pytest.approx(
        float(np.sum(info.layer_stats["moe_held_pairs"])) / (3 * 128))
    assert {"moe_top1_weight_mean", "cca_logit_scale_max"} <= set(_LAYER_STATS)
    # one float32 [2, 64, 16] state for every carrying layer but the last
    assert moe_mod.router_carry_bytes(params) == 2 * 2 * 64 * 16 * 4
    assert moe_mod.router_carry_bytes(ModelParameter(_config(depth=1))) == 0
    from homebrewnlp_tpu import telemetry
    from homebrewnlp_tpu.train import Trainer
    line = Trainer(params, model).publish_stash_plan()
    assert line.startswith("remat stash:")
    assert line.endswith("moe held rows bound 128; router carry 16384 bytes")
    assert telemetry.snapshot()["hbnlp_router_carry_bytes"]["series"][()] \
        == 16384


def the_trainer_steps_test():
    """``Trainer.step`` on the toy configuration: the loss falls and the
    step's metrics hold the gauges' sources."""
    config, params, model, batch, _ = _build(
        telemetry_enabled=True, learning_rate=0.01,
        learning_rate_config={})
    from homebrewnlp_tpu.train import Trainer
    trainer = Trainer(params, model)
    state = trainer.init_state(batch)
    losses = []
    for _ in range(8):
        state, metrics = trainer.step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.05
    assert {"moe_top1_weight_mean", "cca_logit_scale_max",
            "moe_held_pair_share"} <= set(metrics)


# ---- compiled for a described v5e ---------------------------------------------

def saved_flash_outputs_keep_their_scope_test(v5e, monkeypatch):
    """The cell's ``cca`` layer (``harness.py
    saved_flash_outputs_keep_their_scope``)."""
    harness.saved_flash_outputs_keep_their_scope(
        v5e, monkeypatch, "train_zaya1_8b_ep2_s16k",
        "cca-q_heads8-kv_heads2-rotary_pct50-theta5000000", "body/cca")
