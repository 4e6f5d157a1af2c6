"""Kimi-Linear's layers through the normal path (ISSUE 58): the program
against the plain reference ``benchmark/reference/kimi_linear_48b_a3b.py`` in
logits, loss and EVERY gradient at toy widths; the chunked KDA rule against
the recurrence run position by position (chunks 16-64, odd head counts,
per-step log-decays down to -20, where ``exp(-gamma)`` overflows float32),
and against ``gated_delta``'s rule where the decay is flat over a head's
channels; that no ``exp`` of a positive log-decay difference is formed; the
32 expert shares (the shared expert counted once) add up to the uncut layer;
refusals, scopes, statistics, the repo's configuration."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from harness import REPO
from homebrewnlp_tpu import telemetry
from homebrewnlp_tpu.analysis.cost_ledger import scope_key
from homebrewnlp_tpu.config import BlockArgs, ModelParameter
from homebrewnlp_tpu.core import scope
from homebrewnlp_tpu.core.tensor import nt
from homebrewnlp_tpu.model import (Model, gated_delta as delta_mod,
                                   kda as kda_mod, moe as moe_mod)
from homebrewnlp_tpu.optim import own_rule
from homebrewnlp_tpu.train import Trainer

MOE = "moe-sigmoid_bias-shared_expert"
MLA = "attention-nope-q_heads4-kv_heads4-kv_latent24-shared_key8"


def _block(layer):
    return {"skip": True, "layer": ["norm-rms-scale", layer]}


# a stream of 2 x 16; 3 KDA heads of key 16 / value 8 (so a low rank of 8) in
# one chunk of 64; 4 latent-attention heads at key 16 + 8 shared / value 16
# from a latent of 24; 16 routed experts of 24, 4 held, 4 a token, a shared
# expert of 40; the cell's order of layers: KDA + dense, KDA + MoE, MLA + MoE
TINY = {"depth": 1, "heads": 2, "features_per_head": 16,
        "sequence_length": 64, "train_batch_size": 2, "vocab_size": 384,
        "kda_heads": 3, "kda_key_features": 16, "kda_value_features": 8,
        "experts": 16, "experts_held": 4, "moe_top_k": 4, "expert_width": 24,
        "shared_expert_width": 40, "tpu_size": 1, "use_checkpointing": False,
        "block_config": [_block(layer) for layer in
                         ("kda", "mlp-silu", "kda", MOE, MLA, MOE)]}


def _reference():
    return harness.reference("kimi_linear_48b_a3b")


def _config(dtype: str = "float32", **extra) -> dict:
    return harness.config_of("kimi_linear_48b_a3b", TINY, dtype, **extra)


def _lively(variables, bias: float = 0.05):
    """At normal(0.02) a sigmoid router's scores are all but 0.5 and the
    decay's low-rank pair says nothing: the router's matrix and the pair's
    second matrix scaled up make the choice and the decay depend on the
    token and the channel, and a selection bias that is not zero makes the
    choice differ from the scores' own."""
    rng = np.random.default_rng(1)
    out = {}
    for name, value in variables.items():
        if name.endswith("moe_0/normal_var0/var0") \
                or name.endswith("kda_0/normal_var2/var0"):
            value = value * 30
        elif moe_mod.SELECTION_BIAS in name:
            value = (rng.normal(size=value.shape) * bias).astype(np.float32)
        out[name] = value
    return out


@pytest.fixture
def chunk(monkeypatch):
    """Sets the positions a chunk of layer ``kda`` (a module constant)."""
    return lambda positions: monkeypatch.setattr(kda_mod, "CHUNK", positions)


def _build(dtype: str = "float32", **extra):
    return harness.build(_config(dtype, **extra), lively=_lively)


def _biases(variables):
    return sorted(k for k in variables if own_rule(k))


# ---- the program against the reference ---------------------------------------

@pytest.mark.parametrize("dtype,tolerance,extra", [
    # float32 against float32 pins the EQUATIONS: silu for sigmoid in the
    # gate, the gate before the norm, beta at 2 sigmoid, a decay a head, a
    # key part a head where one is shared, softmax for sigmoid scores are
    # off by orders of magnitude
    ("float32", 2e-5, {}),
    ("float32", 2e-5, {"experts_held": 0}),
    ("float32", 2e-5, {"experts_held": 4, "experts_first": 8}),
    # two chunks a sequence, four, and two periods
    ("float32", 2e-5, {"chunk": 32}),
    ("float32", 2e-5, {"chunk": 16, "depth": 2}),
    ("bfloat16", 2 ** -4, {})],
    ids=["float32", "all_held", "third_share", "two_chunks",
         "four_chunks_two_periods", "bfloat16"])
def program_matches_reference_test(dtype, tolerance, extra, chunk):
    extra = dict(extra)
    if "chunk" in extra:
        chunk(extra.pop("chunk"))
    config, _, model, batch, variables = _build(dtype, **extra)
    got = harness.assert_program_matches_reference(
        _reference(), (config, _, model, batch, variables), dtype, tolerance)


@pytest.mark.parametrize("extra", [
    {}, {"memory_reduction_strategy": "none"}, {"remat_policy": "stash"}],
    ids=["checkpoint", "no_replay", "saved"])
def loss_and_every_gradient_match_reference_test(extra):
    config, params, model, batch, variables = _build(**extra)
    ref = _reference()
    tokens, targets = batch["token_x"][..., 0], batch["token_y"][..., 0]
    trainer = Trainer(params, model)
    got, _ = jax.jit(lambda v, b: trainer._grads(v, b, None))(variables, batch)
    _, want = harness.reference_loss_and_grads(ref, variables, tokens,
                                               targets, config)
    counts = ref.pair_counts(variables, tokens, config)
    assert set(got) == set(want) and len(_biases(got)) == len(counts) == 2
    for name in got:
        if own_rule(name):
            continue
        assert float(jnp.max(jnp.abs(want[name]))) > 0, name
        assert harness.error(got[name], want[name]) < 2e-4, name
    # the selection bias has no gradient: the program hands the optimizer
    # the step's pair counts in its place
    for name, layer_counts in zip(_biases(got), counts):
        assert float(jnp.max(jnp.abs(want[name]))) == 0.0
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(layer_counts))


def reference_at_the_next_precision_below_fails_test():
    """``harness.assert_float8_stream_misses``."""
    harness.assert_float8_stream_misses(_reference(), _build("bfloat16"))


# ---- the chunked rule ------------------------------------------------------------

def _rule_inputs(seed, s=128, h=3, dk=16, dv=8, low=-1.0, batch=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k = (jax.random.normal(key, (batch, s, h, dk)) for key in ks[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (batch, s, h, dv))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (batch, s, h)))
    g = low * jax.random.uniform(ks[4], (batch, s, h, dk))
    return q, k, v, beta, g


def _with_gradients(fn, inputs):
    weights = jax.random.normal(jax.random.PRNGKey(9), inputs[2].shape)
    return harness.with_input_grads(fn, inputs, weights)


@pytest.mark.parametrize("chunk,heads,low", [
    (16, 3, -1.0), (32, 5, -1.0), (64, 3, -1.0), (64, 1, -0.01),
    # a step's log-decay down to -20: -1,280 a chunk of 64, where
    # exp(-gamma) overflows float32 after five positions
    (16, 3, -20.0), (64, 3, -20.0)],
    ids=["c16", "c32_h5", "c64", "c64_slow", "c16_steep", "c64_steep"])
def chunked_rule_is_the_recurrence_test(chunk, heads, low):
    inputs = _rule_inputs(chunk + heads, s=2 * chunk, h=heads, low=low)
    got = _with_gradients(
        lambda *a: kda_mod.grouped_rule(*a, chunk)[0], inputs)
    want = _with_gradients(_reference().recurrence, inputs)
    for mine, theirs in zip(got, want):
        assert mine.shape == theirs.shape
        assert bool(jnp.all(jnp.isfinite(mine)))
        assert harness.error(mine, theirs) < 1e-4
    _, transform_max, log_decay_min = jax.jit(
        lambda *a: kda_mod.grouped_rule(*a, chunk))(*inputs)
    assert float(transform_max) >= float(jnp.max(inputs[3])) - 1e-6
    gamma = jnp.cumsum(inputs[4].reshape(1, -1, chunk, heads, 16), axis=2)
    assert float(log_decay_min) == pytest.approx(float(jnp.min(gamma)),
                                                 rel=1e-6)
    if low == -20.0:
        assert float(log_decay_min) < -88 * 2        # exp(-gamma) = inf


def groups_of_heads_are_the_rule_over_all_test(monkeypatch):
    inputs = _rule_inputs(3, s=64, h=6)
    whole = jax.jit(lambda *a: kda_mod.kda_rule(*a, 32))(*inputs)
    monkeypatch.setattr(kda_mod, "GROUP_BYTES", 2 * 64 * kda_mod._SUB * 16 * 4)
    assert kda_mod._group_heads(1, 64, 6, 16) == 2
    grouped = jax.jit(lambda *a: kda_mod.grouped_rule(*a, 32))(*inputs)
    for got, want in zip(grouped, whole):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-7)


def what_the_rule_keeps_in_float32_is_felt_in_bfloat16_test(monkeypatch):
    """``KEPT``, which scripts/kimi_rule_control.py sets for the benchmark's
    control: at bfloat16 the cumulative log-decays, the solve's input and the
    carried state are rounded and the rule moves away from the recurrence by
    orders of magnitude; inputs already rounded are not what moves it."""
    inputs = _rule_inputs(5, s=256, low=-0.3)
    want = _reference().recurrence(*inputs)
    kept = harness.error(
        jax.jit(lambda *a: kda_mod.kda_rule(*a, 64)[0])(*inputs), want)
    monkeypatch.setattr(kda_mod, "KEPT", jnp.bfloat16)
    rounded = harness.error(
        jax.jit(lambda *a: kda_mod.kda_rule(*a, 64)[0])(*inputs), want)
    assert kept < 1e-5 and rounded > 100 * kept


def the_low_rank_pairs_are_a_heads_width_test():
    """The decay's and the gate's pair have the value's width a head
    between them (the source fixes it at ``head_dim``): no key sets it."""
    _, _, _, _, variables = _build(block_config=[_block("kda")])
    shapes = {name.split("kda_0/")[1]: value.shape
              for name, value in variables.items() if "kda_0/" in name}
    for first, second, columns in (("normal_var1", "normal_var2", 3 * 16),
                                   ("normal_var3", "normal_var4", 3 * 8)):
        assert shapes[first + "/var0"] == (2, 16, 8)
        assert shapes[second + "/var0"] == (8, columns)


def a_flat_decay_is_gated_deltas_rule_test():
    """With ``g`` equal over a head's channels the recurrence is
    ``gated_delta``'s (its state transposed): outputs and gradients."""
    q, k, v, beta, g = _rule_inputs(7, s=64, dv=24)
    flat = g[..., 0]

    def ours(q, k, v, beta, flat):
        return kda_mod.grouped_rule(
            q, k, v, beta, jnp.broadcast_to(flat[..., None], g.shape), 32)[0]

    got = _with_gradients(ours, (q, k, v, beta, flat))
    want = _with_gradients(
        lambda *a: delta_mod.grouped_rule(*a, 32)[0], (q, k, v, beta, flat))
    for mine, theirs in zip(got, want):
        assert harness.error(mine, theirs) < 2e-5


def no_exp_of_a_positive_decay_difference_is_formed_test():
    """Every ``exp`` in the rule's jaxpr, forward and backward, is given
    values <= 0 (or -inf): run on log-decays of -20 a step with every
    ``exp``'s operand recorded."""
    inputs = _rule_inputs(11, s=64, h=1, low=-20.0)
    closed = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(kda_mod.kda_rule(*a, 32)[0]),
        argnums=(0, 1, 2, 3, 4)))(*inputs)
    _, seen = harness.exp_operands(closed, inputs)
    assert len(seen) >= 8
    assert max(seen) <= 0.0


def _layer_on(*args, **kwargs):
    return harness.layer_on(*args, **kwargs)[0]


# ---- the share test --------------------------------------------------------------


def the_32_expert_shares_add_up_to_the_uncut_layer_test():
    """Thirty-two expert-parallel ranks of two experts each: their routed
    parts, with what every rank computes alike (the shared expert) counted
    once, add up to what the uncut reference gives for the whole layer."""
    ref = _reference()
    rng = np.random.default_rng(2)
    heads, width, n_exp, inter, shared, ranks = 2, 16, 64, 24, 40, 32

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32) * 0.3)

    whole = {"w_router": normal(heads, width, n_exp), "bias": normal(n_exp),
             "w_gate": normal(n_exp, heads, width, inter),
             "w_up": normal(n_exp, heads, width, inter),
             "w_down": normal(n_exp, inter, heads, width),
             "s_gate": normal(heads, width, shared),
             "s_up": normal(heads, width, shared),
             "s_down": normal(shared, heads, width),
             "w_norm_in": jnp.ones((heads, width))}
    h = normal(2, 64, heads, width)
    x = ref.rms(h, whole["w_norm_in"], 1e-5)
    config = _config(experts=n_exp, experts_held=0, moe_top_k=8)
    uncut, _, _ = ref.sparse_block(whole, h, config)
    shared_part = ref.swiglu(x, whole["s_gate"], whole["s_up"],
                             whole["s_down"])
    flags = MOE.split("-")[1:]
    held = n_exp // ranks
    total = np.asarray(shared_part)
    for rank in range(ranks):
        first = held * rank
        cut = _config(experts=n_exp, experts_held=held, experts_first=first,
                      moe_top_k=8)
        share = dict(whole, **{k: whole[k][first:first + held]
                               for k in ("w_gate", "w_up", "w_down")})
        want, _, _ = ref.sparse_block(share, h, cut)
        if rank in (0, 13, 31):
            got = _layer_on(ModelParameter(cut), moe_mod.moe, ref.SPARSE, share,
                         x, flags)
            assert harness.error(got, want) < 2e-5
        total = total + np.asarray(want - shared_part)
    assert harness.error(total, uncut) < 2e-5
    assert harness.error(_layer_on(ModelParameter(config), moe_mod.moe, ref.SPARSE,
                         whole, x, flags), uncut) < 2e-5


# ---- refusals, scopes, statistics ----------------------------------------------

@pytest.mark.parametrize("layer,error,match", [
    (MLA + "-rms", ValueError, "does not know flag"),
    (MLA.replace("nope", "yarn"), ValueError, "does not build yarn"),
    (MLA + "-window32", ValueError, "does not build window"),
    (MLA + "-qk_norm", ValueError, "does not build qk_norm"),
    (MLA + "-gate", ValueError, "does not build gate"),
    (MLA.replace("kv_heads4", "kv_heads2"), ValueError, "kv_heads = q_heads"),
    ("attention-nope-shared_key8", ValueError, "comes with kv_latent"),
    ("kda-anything", None, None)],
    ids=["unknown", "yarn", "window", "qk_norm", "gate", "grouped",
         "shared_alone", "kda_flag"])
def what_is_not_built_refuses_by_name_test(layer, error, match):
    config = _config(block_config=[_block(layer)])
    tokens = np.zeros((2, 64, 1), np.int32)
    batch = {"token_x": tokens, "token_y": tokens}
    if error is None:
        # layer kda reads no flag: the configuration builds
        Model(ModelParameter(config)).init(batch, seed=1)
        return
    with pytest.raises(error, match=match):
        Model(ModelParameter(config)).init(batch, seed=1)


@pytest.mark.parametrize("layer,match", [("kda", "layer kda"),
                                         (MLA, "latent attention")])
def a_mesh_and_decode_refuse_by_name_test(layer, match):
    params = ModelParameter(_config(block_config=[_block(layer)]))
    x = nt(jnp.zeros((2, 64, 2, 16)), [params.batch_dim, params.sequence_dim]
           + list(params.feature_dims))
    from homebrewnlp_tpu.model.frontend import LAYER_FUNCTIONS
    name, *flags = layer.split("-")

    class Mesh:
        size = 2
        shape = {}

    for ctx, wanted in ((scope.Context("init", mesh=Mesh()), match),
                        (scope.Context("init", decode=object()), "decode")):
        with scope.context(ctx), pytest.raises(NotImplementedError,
                                               match=wanted):
            scope.scoped(name + "_", LAYER_FUNCTIONS[name],
                         BlockArgs(params, x, flags))


@pytest.mark.parametrize("key", ["kda_heads", "kda_key_features",
                                 "kda_value_features", "kda_conv_size"])
def bad_keys_refuse_by_name_test(key):
    with pytest.raises(ValueError, match=key):
        ModelParameter(_config(**{key: 0}))


def a_sequence_of_no_whole_chunks_refuses_test(chunk):
    chunk(48)
    config = _config()
    tokens = np.zeros((2, 64, 1), np.int32)
    with pytest.raises(ValueError, match="multiple of kda's chunk"):
        Model(ModelParameter(config)).init(
            {"token_x": tokens, "token_y": tokens}, seed=1)


@pytest.mark.parametrize("path,scope_name", [
    ("jit(step_fn)/jvp(gpt0)/body0/block0_0_0/kda_0/in_proj/dot_general",
     "body/kda/in_proj"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_0_0/kda_0/conv/mul",
     "body/kda/conv"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_0_0/kda_0/decay/softplus",
     "body/kda/decay"),
    ("jit(step_fn)/transpose(jvp(gpt0))/body0/block0_2_0/kda_0/rule/"
     "checkpoint/solve/dot_general", "body/kda/rule"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_2_0/kda_0/rule/decay/cumsum",
     "body/kda/rule"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_2_0/kda_0/gate_norm/mul",
     "body/kda/gate_norm"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_2_0/kda_0/out_proj/dot_general",
     "body/kda/out_proj"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_4_0/attention_0/q_proj/dot_general",
     "body/attention/q_proj"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_4_0/attention_0/kv_down/"
     "dot_general", "body/attention/kv_down"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_4_0/attention_0/kv_norm/mul",
     "body/attention/kv_norm"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_4_0/attention_0/kv_up/dot_general",
     "body/attention/kv_up"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_4_0/attention_0/attend/"
     "flash_attention/flash_fwd_causal", "body/attention"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_4_0/attention_0/out_proj/"
     "dot_general", "body/attention/out_proj"),
    # a sparse layer's own ``attend`` stays the sparse one
    ("jit(step_fn)/jvp(gpt0)/body0/block0_4_0/attention_0/sparse_attention/"
     "attend/flash_fwd_select", "body/attention/sparse_attention/attend")])
def the_new_scopes_fold_test(path, scope_name):
    assert scope_key(path) == scope_name


def traced_ops_carry_the_new_scopes_test():
    _, _, model, batch, variables = _build()
    found = {scope_key(name) for name in harness.traced_op_names(
        model, variables, batch, compiled=False)}
    assert {"body/kda/in_proj", "body/kda/conv", "body/kda/decay",
            "body/kda/rule", "body/kda/gate_norm", "body/kda/out_proj",
            "body/attention/q_proj", "body/attention/kv_down",
            "body/attention/kv_norm", "body/attention/kv_up",
            "body/attention", "body/attention/out_proj",
            "body/moe/shared", "body/moe/router", "body/mlp"} <= found
    # the rule's own steps, inside the group's checkpoint
    steps = {str(eqn.source_info.name_stack).split("/")[0]
             for eqn in jax.make_jaxpr(lambda *a: kda_mod.kda_rule(*a, 32))(
                 *_rule_inputs(1)).jaxpr.eqns}
    assert {"decay", "solve", "intra_chunk", "inter_chunk",
            "state_out"} <= steps


def the_step_reports_the_decay_and_the_transform_test():
    prev = telemetry.set_registry(telemetry.Registry())
    try:
        _, params, model, batch, _ = _build(telemetry_enabled=True)
        trainer = Trainer(params, model)
        state = trainer.init_state(batch, seed=13)
        for _ in range(2):
            state, metrics = trainer.step(state, batch)
        jax.block_until_ready(metrics["loss"])
        trainer.step(state, batch)
        assert float(metrics["kda_log_decay_min"]) < 0
        assert 0 < float(metrics["delta_transform_abs_max"]) < 4
        snap = telemetry.snapshot()
        assert snap["hbnlp_kda_log_decay_min"]["series"][()] < 0
        assert snap["hbnlp_delta_transform_abs_max"]["series"][()] > 0
    finally:
        telemetry.set_registry(prev)


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "pairs"])
def the_layers_offer_their_outputs_test(kernels, monkeypatch):
    """Layer ``kda`` offers the rule's output and — PR 61, where the rule is
    the Pallas pairs, by the layer's own predicate on its shapes (a toy of
    kernel widths: the file's declines on any backend) — what the pairs'
    forwards hand their backwards, each in the precision it is kept in."""
    from homebrewnlp_tpu.parallel import kda_rule as kr
    if kernels:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    wide = ModelParameter(_config(
        "bfloat16", sequence_length=128, kda_key_features=32,
        kda_value_features=16))
    offer = kda_mod.kda.declares.offer(wide, set())
    assert (offer.kind, offer.names, offer.nbytes) \
        == ("recurrent", ("kda_out",), 2 * 128 * 3 * 16 * 2)
    if kernels:
        assert ("kda_out",) + offer.interior_names == (
            "kda_out", *kr.SCORES_NAMES, "kda_solved", kr.STATES_NAME) \
            == kda_mod.SAVED_NAMES
        # gamma in float32 and q~, k~ [2, 128, 3, 32]; A and the inverse in
        # float32, A' [2, 2, 3, 64, 64]; the states [2, 2, 3, 16, 32]
        assert offer.interior_nbytes == 2 * 128 * 3 * 32 * (4 + 2 + 2) \
            + 2 * 2 * 3 * 64 * 64 * (4 + 4 + 2) + 2 * 2 * 3 * 16 * 32 * 2
    else:
        assert (offer.interior_names, offer.interior_nbytes) == ((), 0)
    params = ModelParameter(_config())
    offer = kda_mod.kda.declares.offer(params, set())
    assert (offer.kind, offer.names) == ("recurrent", ("kda_out",))
    assert offer.nbytes == 2 * 64 * 3 * 8 * 4
    from homebrewnlp_tpu.model.spatial import attention
    latent = attention.declares.offer(params, set(MLA.split("-")[1:]))
    # out at the VALUE's width (16, not 16 + 8) and lse a query head
    assert latent.kind == "attention"
    assert latent.nbytes == 4 * 2 * 64 * (16 * 4 + 4)
    spec = kda_mod.kda.declares.recurrent
    assert spec.conv(params) == (3 * (16 + 16 + 8), 4, 0)
    assert spec.solve(params, None) == (64, 2 * 1 * 3)
    assert spec.state_bytes(params) == 2 * 1 * 3 * 16 * 8 * 4
    # the rule's shapes and its own predicate (PR 59): the toy's value width
    # of 8 and its half lane tile of positions decline on any backend
    assert spec.rule(params) == (64, 3, 16, 8, 64)
    assert not spec.rule_applies(*spec.rule(params), "tpu")


# ---- the configurations ----------------------------------------------------------

def the_repos_config_is_the_published_model_test():
    with open(os.path.join(REPO, "configs", "kimi_linear_48b_a3b.json")) as f:
        whole = json.load(f)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "kimi_linear_48b_a3b.json")) as f:
        doc = json.load(f)
    linear = doc["linear_attn_config"]
    mixers = [block["layer"][-1] for block in whole["block_config"][0::2]]
    others = [block["layer"][-1] for block in whole["block_config"][1::2]]
    assert len(mixers) == 27 == doc["published"]["num_hidden_layers"]
    assert [i + 1 for i, m in enumerate(mixers) if m == "kda"] \
        == linear["kda_layers"]
    assert [i + 1 for i, m in enumerate(mixers) if m != "kda"] \
        == linear["full_attn_layers"]
    full = "attention-nope-q_heads32-kv_heads32-kv_latent512-shared_key64"
    assert {m for m in mixers if m != "kda"} == {full}
    assert others[:doc["first_k_dense_replace"]] == ["mlp-silu"]
    assert set(others[1:]) == {MOE}
    params = ModelParameter({**whole, "model_path": "/tmp/kimi"})
    assert not params.unknown_config_keys
    assert (params.features, params.kda_heads, params.kda_key_features,
            params.kda_value_features, params.kda_conv_size) \
        == (doc["hidden_size"], linear["num_heads"], linear["head_dim"],
            linear["head_dim"], linear["short_conv_kernel_size"])
    assert (params.key_dim.size, params.key_dim.size + 64, 512) \
        == (doc["v_head_dim"], doc["qk_nope_head_dim"]
            + doc["qk_rope_head_dim"], doc["kv_lora_rank"])
    assert (params.experts, params.moe_top_k, params.expert_width,
            params.shared_expert_width, params.moe_route_scale,
            params.vocab_size, params.norm_epsilon) \
        == (256, 8, 1024, 1024, 2.446, 163840, 1e-5)
    assert params.intermediate_feed_forward_multiplier * params.features \
        == doc["intermediate_size"] == 9216
    # the cell's cut: published layers 1-5 at every width, this rank's share
    cut = doc["config"]
    assert cut["block_config"] == whole["block_config"][:10]
    same = ("features_per_head", "heads", "kda_heads", "kda_key_features",
            "kda_value_features", "kda_conv_size", "experts", "moe_top_k",
            "expert_width", "shared_expert_width", "moe_route_scale",
            "moe_norm_topk", "intermediate_feed_forward_multiplier",
            "norm_epsilon")
    assert {k: cut[k] for k in same} == {k: whole[k] for k in same}
    assert (cut["experts_held"], cut["vocab_size"], cut["sequence_length"]) \
        == (8, 20480, 16384)
    assert sorted(doc["reduced"]) == sorted(
        set(doc["published"]) | {"experts_held", "sequence_length",
                                 "train_batch_size", "tpu_size"})
    for key, value in doc["published"].items():
        assert doc["reduced"][key]["from"] == value


# ---- compiled for a described v5e ---------------------------------------------

@pytest.mark.parametrize("policy,forwards", [("auto", 1), ("recompute", 2)])
def kda_block_compiled_for_a_v5e_test(v5e, monkeypatch, policy, forwards):
    """One ``kda`` block of the cell at its published widths and 8,192 tokens,
    loss and gradients compiled for a v5e as a TPU process traces them.  PR
    61: where the block's interior is admitted (one block: its 0.64 GB fit)
    the ``jax.checkpoint`` holds what the three pairs' forwards hand their
    backwards and the compiled step runs each forward ONCE — the replay none;
    under ``"recompute"`` twice.  Mosaic accepts every call; the rule's ops
    still fold into ``body/kda/rule``, which ``kimi_kda_rule_roofline`` and
    ``scope_kda_time_share`` read."""
    from homebrewnlp_tpu.model import remat
    params, hlo = harness.cell_layer_hlo(
        v5e, monkeypatch, "train_kimi_linear_ep32_s16k", "kda", policy,
        sequence_length=8192)
    assert params.block_config[0].layer[-1] == "kda"
    offer = kda_mod.kda.declares.offer(params, set())
    assert offer.names + offer.interior_names == kda_mod.SAVED_NAMES
    assert remat.stash_plan(params)["recurrent"] == (
        (1, offer.nbytes + offer.interior_nbytes) if policy == "auto"
        else (0, 0))
    calls = harness.kernel_calls(hlo)
    assert sorted(name for name, _ in calls) == sorted(
        ["kda_scores_bwd", "delta_solve_bwd", "kda_rule_bwd",
         "mamba_conv_bwd"] + ["mamba_conv_fwd"] * 2
        + ["kda_scores_fwd", "delta_solve_fwd", "kda_rule_fwd"] * forwards)
    for name, op_name in calls:
        assert scope_key(op_name) == ("body/kda/conv" if name.startswith(
            "mamba_conv") else "body/kda/rule"), (name, op_name)
