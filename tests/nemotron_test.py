"""Nemotron-3-Super's layers through the normal path (ISSUE 54): the program
against the plain reference ``benchmark/reference/nemotron_3_super_120b.py``
in logits, loss and EVERY gradient at toy widths, under both forms of the
held-row path and every memory strategy that runs it; the selection bias
after three steps against the reference's rule on the program's own counts;
the grouped scan (Pallas pair interpreted, XLA form, position by position) at
1 / 2 / 4 groups; ``mamba_groups`` 1 as the parent's graph; the SHARE tests
(all expert-parallel ranks' routed parts with what every rank computes alike
counted once, and both tensor-parallel halves of a Mamba-2 and an attention
layer, add up to the uncut layer); refusals, scopes, statistics, the repo's
configuration."""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from harness import REPO
from homebrewnlp_tpu import telemetry
from homebrewnlp_tpu.analysis.cost_ledger import scope_key
from homebrewnlp_tpu.config import BlockArgs, ModelParameter
from homebrewnlp_tpu.core.tensor import nt
from homebrewnlp_tpu.model import Model, mamba as mamba_mod, moe as moe_mod
from homebrewnlp_tpu.model.activation import ACTIVATIONS
from homebrewnlp_tpu.optim import Optimizer, own_rule, selection_bias_rule
from homebrewnlp_tpu.parallel import ssd_scan as sk
from homebrewnlp_tpu.train import Trainer

MOE = "moe-relu2-plain-latent-sigmoid_bias-shared_expert"
ATTENTION = "attention-nope-q_heads4-kv_heads1"


def _block(layer):
    return {"skip": True, "layer": ["norm-rms-scale", layer]}


# a stream of 2 x 16; 4 query heads over 1 K/V head; 8 Mamba-2 heads of 16 in
# 4 groups, state 16, two chunks of 32; 16 routed experts of 24 in a latent
# of 32, 4 held, 4 a token, a shared expert of 40
TINY = {"depth": 1, "heads": 2, "features_per_head": 16,
        "sequence_length": 64, "train_batch_size": 2, "vocab_size": 384,
        "mamba_heads": 8, "mamba_head_features": 16, "mamba_state": 16,
        "mamba_chunk": 32, "mamba_groups": 4,
        "experts": 16, "experts_held": 4, "moe_top_k": 4, "expert_width": 24,
        "moe_latent_width": 32, "shared_expert_width": 40,
        "tpu_size": 1, "use_checkpointing": False,
        "block_config": [_block(layer) for layer in
                         (ATTENTION, MOE, "mamba", MOE, "mamba")],
        "output_block_config": [{"layer": ["norm-rms-scale"]}]}


def _reference():
    return harness.reference("nemotron_3_super_120b")


def _config(dtype: str = "float32", **extra) -> dict:
    return harness.config_of("nemotron_3_super_120b", TINY, dtype, **extra)


def _lively(variables, bias: float = 0.05):
    """At normal(0.02) a sigmoid router's scores are all but 0.5: the
    router's matrix scaled up makes the choice depend on the token, and a
    selection bias that is not zero makes it differ from the scores' own."""
    rng = np.random.default_rng(1)
    out = {}
    for name, value in variables.items():
        if name.endswith("moe_0/normal_var0/var0"):
            value = value * 30
        elif moe_mod.SELECTION_BIAS in name:
            value = (rng.normal(size=value.shape) * bias).astype(np.float32)
        out[name] = value
    return out


def _build(dtype: str = "float32", **extra):
    return harness.build(_config(dtype, **extra), lively=_lively)


def _biases(variables):
    return sorted(k for k in variables if own_rule(k))


# ---- the program against the reference ---------------------------------------

@pytest.mark.parametrize("dtype,tolerance,extra", [
    # float32 against float32 pins the EQUATIONS: a gate left in, softmax
    # for sigmoid, the bias inside the weights, a norm over all groups at
    # once, head j reading the wrong group are off by orders of magnitude
    ("float32", 2e-5, {}),
    # the held-row path's other form (4 of 64 held: the walk) — flag plain
    # under both — every expert held, and a share that is not the first
    ("float32", 2e-5, {"experts": 64}),
    ("float32", 2e-5, {"experts_held": 0}),
    ("float32", 2e-5, {"experts_held": 4, "experts_first": 8}),
    # fewer held than a token's choices; one group of B / C; two periods
    ("float32", 2e-5, {"experts_held": 2, "experts_first": 3}),
    ("float32", 2e-5, {"mamba_groups": 1}),
    ("float32", 2e-5, {"mamba_groups": 2, "depth": 2}),
    # the configuration's bfloat16, at the cells' usual bound
    ("bfloat16", 2 ** -4, {}),
    ("bfloat16", 2 ** -4, {"experts": 64, "experts_first": 30})],
    ids=["float32", "walked", "all_held", "third_share", "two_slots",
         "one_group", "two_groups_two_periods", "bfloat16",
         "walked_bfloat16"])
def program_matches_reference_test(dtype, tolerance, extra):
    config, _, model, batch, variables = _build(dtype, **extra)
    assert moe_mod.walks_real_rows(
        config["experts"], config["experts_held"] or config["experts"],
        config["moe_top_k"]) == (config["experts"] == 64)
    got = harness.assert_program_matches_reference(
        _reference(), (config, _, model, batch, variables), dtype, tolerance)


@pytest.mark.parametrize("extra", [
    {}, {"experts": 64},
    # what a replay must route as its forward did: no replay at all, the
    # block's jax.checkpoint with nothing of the layer saved (the cell's
    # form), and with the layer's names saved (the experts kind riding:
    # the choice, the sort and the matmuls' outputs come from the forward)
    {"memory_reduction_strategy": "none"},
    {"remat_policy": "stash"}, {"experts": 64, "remat_policy": "stash"}],
    ids=["whole_buffer", "walked", "no_replay", "saved", "walked_saved"])
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
        assert harness.error(got[name], want[name]) < 2e-4, name
    # the selection bias has no gradient: the reference's is zero, and the
    # program hands the optimizer the step's pair counts in its place
    for name, layer_counts in zip(_biases(got), counts):
        assert float(jnp.max(jnp.abs(want[name]))) == 0.0
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(layer_counts))
        assert float(jnp.sum(got[name])) == 2 * 64 * config["moe_top_k"]


def reference_at_the_next_precision_below_fails_test():
    """``harness.assert_float8_stream_misses``."""
    harness.assert_float8_stream_misses(_reference(), _build("bfloat16"))


# ---- the selection bias --------------------------------------------------------

def the_bias_after_three_steps_is_the_references_rule_test():
    """Three steps of the trainer: every layer's bias equals the
    reference's rule applied, step by step, to the counts the program
    itself reported (the bias's cotangent); it has moved, by the rate, and
    has neither slots nor a part in the clip's norm."""
    config, params, model, batch, variables = _build(moe_bias_rate=0.01)
    ref = _reference()
    trainer = Trainer(params, model)
    state = trainer.init_state(batch, seed=13)
    state = state._replace(variables={
        k: jnp.asarray(v) for k, v in _lively(
            {k: np.asarray(v) for k, v in state.variables.items()},
            bias=0.0).items()})
    names = _biases(state.variables)
    assert all(state.opt_state[name] == {} for name in names)
    counts_of = jax.jit(lambda v, b: {
        k: g for k, g in trainer._grads(v, b, jax.random.PRNGKey(0))[0]
        .items() if own_rule(k)})
    want = {name: np.zeros(16, np.float32) for name in names}
    for _ in range(3):
        counts = counts_of(state.variables, batch)
        for name in names:
            want[name] = np.asarray(ref.bias_update(
                want[name], counts[name], 0.01))
        state, metrics = trainer.step(state, batch)
        # the counts (~30 a expert) would swamp the clip's norm
        assert float(metrics["global_grad_norm"]) < 10
    for name in names:
        got = np.asarray(state.variables[name])
        np.testing.assert_allclose(got, want[name], atol=1e-7)
        assert 0.01 <= np.max(np.abs(got)) <= 0.03 + 1e-6
    assert float(metrics["loss"]) < 6.0


def the_rule_pulls_the_load_towards_the_mean_test():
    bias = jnp.zeros((2, 4))
    counts = jnp.asarray([[9.0, 1.0, 5.0, 5.0], [0.0, 0.0, 0.0, 8.0]])
    got = np.asarray(selection_bias_rule(bias, counts, 0.5))
    np.testing.assert_array_equal(got, [[-0.5, 0.5, 0.0, 0.0],
                                        [0.5, 0.5, 0.5, -0.5]])
    np.testing.assert_array_equal(
        got[0], np.asarray(_reference().bias_update(bias[0], counts[0], 0.5)))
    params = ModelParameter(_config())
    slots = Optimizer(params, {}).init(
        {"a/selection_bias0/var0": jnp.zeros(4), "a/normal_var0/var0":
         jnp.zeros(4)})
    assert slots["a/selection_bias0/var0"] == {}
    assert set(slots["a/normal_var0/var0"]) == {"adam/exp_avg_p1",
                                               "adam/exp_avg_p2"}


def sigmoid_route_weighs_the_scores_not_the_biased_ones_test():
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(16,)) * 0.5, jnp.float32)
    weights, experts, counts = moe_mod.route_sigmoid(logits, bias, 4, True,
                                                     5.0)
    ref = _reference()
    scores = np.asarray(ref.sigmoid(logits))
    chosen = np.asarray(ref.top_k_mask(scores + np.asarray(bias), 4))
    for t in range(64):
        assert set(np.asarray(experts)[t]) == set(np.nonzero(chosen[t])[0])
        picked = scores[t][np.asarray(experts)[t]]
        np.testing.assert_allclose(np.asarray(weights)[t],
                                   5.0 * picked / picked.sum(), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(counts), chosen.sum(0))
    # without the bias other experts are chosen: it does choose
    assert not np.array_equal(
        np.asarray(ref.top_k_mask(jnp.asarray(scores), 4)), chosen)


def relu2_is_the_squared_relu_test():
    from homebrewnlp_tpu.core.dims import Dim
    x = nt(jnp.asarray([-2.0, -0.5, 0.0, 0.5, 3.0]), [Dim("_five", 5)])
    params = ModelParameter(_config())
    got = ACTIVATIONS["relu2"](BlockArgs(params, x, ["relu2"])).data
    np.testing.assert_array_equal(np.asarray(got), [0, 0, 0, 0.25, 9.0])


# ---- the grouped scan ------------------------------------------------------------

def _scan_inputs(s, heads, groups, p=8, n=16, batch=2, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(batch, s, heads, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.5, 1.5, (batch, s, heads)), jnp.float32)
    a = jnp.asarray(-0.1 * rng.uniform(0.5, 1.5, (heads,)), jnp.float32)
    b_mat, c_mat = (jnp.asarray(rng.normal(size=(batch, s, groups, n)),
                                jnp.float32) for _ in range(2))
    weights = jnp.asarray(rng.normal(size=x.shape), jnp.float32)
    return (x, dt, a, b_mat, c_mat), weights


@pytest.mark.parametrize("groups,heads_a_block", [(1, 2), (1, None), (2, 2),
                                                  (4, 2), (4, 1)])
def grouped_scan_is_the_recurrence_test(groups, heads_a_block):
    """The Pallas pair (interpreted), the XLA form and the recurrence run
    position by position agree in ``y`` and all five gradients: head ``j``
    reads group ``j // (heads / groups)``, ``dB`` / ``dC`` sum over a
    group's heads only."""
    inputs, weights = _scan_inputs(64, 8, groups)
    chunk = 16

    def kernel(x, dt, a, b_mat, c_mat):
        return sk.ssd_scan(x, dt, sk.log_decay(dt, a, chunk), b_mat, c_mat,
                           chunk, heads_a_block, True)

    forms = {"kernel": kernel,
             "xla": lambda *args: mamba_mod.ssd_xla(*args, chunk)[0],
             "sequential": _reference().recurrence}
    out = {}
    for name, fn in forms.items():
        out[name] = harness.with_input_grads(fn, inputs, weights)
    for name in ("kernel", "xla"):
        for got, want in zip(out[name], out["sequential"]):
            assert got.shape == want.shape
            assert harness.error(got, want) < 2e-5, name
    # the groups are not one another's: with one group's B doubled only
    # its own heads move
    x, dt, a, b_mat, c_mat = inputs
    if groups > 1:
        moved = kernel(x, dt, a, b_mat.at[:, :, 0].multiply(2.0), c_mat)
        per = 8 // groups
        assert harness.error(moved[:, :, per:], out["kernel"][0][:, :, per:]) == 0.0
        assert harness.error(moved[:, :, :per], out["kernel"][0][:, :, :per]) > 0.1


def three_dimensional_b_and_c_are_one_group_test():
    inputs, _ = _scan_inputs(32, 4, 1)
    x, dt, a, b_mat, c_mat = inputs
    flat = mamba_mod.ssd_xla(x, dt, a, b_mat[:, :, 0], c_mat[:, :, 0], 16)
    grouped = mamba_mod.ssd_xla(x, dt, a, b_mat, c_mat, 16)
    np.testing.assert_allclose(np.asarray(flat[0]), np.asarray(grouped[0]),
                               rtol=1e-6, atol=1e-6)
    assert float(flat[1]) == float(grouped[1])


@pytest.mark.parametrize("heads,p,groups,block", [
    (64, 64, 1, 8), (64, 64, 4, 8), (128, 64, 8, 8), (8, 16, 4, 2),
    (24, 64, 1, 8), (24, 64, 3, 8)])
def a_head_block_lies_inside_one_group_test(heads, p, groups, block):
    assert sk.head_block(heads, p, groups) == block
    assert (heads // groups) % block == 0


def the_predicate_reads_the_groups_test():
    shape = (16384, 128, 64, 64, 128)
    assert sk.ssd_kernel_applies(*shape, "tpu")
    assert sk.ssd_kernel_applies(*shape, "tpu", 4)
    assert not sk.ssd_kernel_applies(*shape, "cpu", 4)
    # a group of 4 heads gives no block of whole sublane tiles; 5 groups do
    # not divide the heads
    assert not sk.ssd_kernel_applies(*shape, "tpu", 16)
    assert not sk.ssd_kernel_applies(*shape, "tpu", 5)
    params = ModelParameter(_config(mamba_heads=64, mamba_head_features=64,
                                    mamba_state=128, mamba_chunk=128,
                                    sequence_length=256))
    from homebrewnlp_tpu.model import recurrent
    assert recurrent.scan_kernel_layers(params, "tpu") == 2
    assert recurrent.conv_kernel_layers(params, "tpu") == 2
    assert recurrent.scan_kernel_layers(params, "cpu") == 0
    assert mamba_mod._conv(params) == (64 * 64 + 2 * 4 * 128, 4, 64 * 64)


def the_layer_runs_the_grouped_kernels_test(monkeypatch):
    """Layer ``mamba`` with its scan steered to the interpreted Pallas pair
    is the layer with XLA's form: the same logits and loss."""
    config, _, model, batch, variables = _build()
    want = harness.logits_and_loss(model, variables, batch)
    harness.steer_mamba_scan(monkeypatch)
    got = harness.logits_and_loss(model, variables, batch)
    assert harness.error(got[0], want[0]) < 2e-5 and abs(got[1] - want[1]) < 1e-5


def one_group_lowers_to_the_parents_graph_test():
    """The StableHLO of loss and gradients of ONE ``mamba`` block at toy
    widths on the CPU is what a tree without ``mamba_groups`` lowered (PR
    54's parent), byte for byte — under ``remat_policy: "recompute"``, where
    no policy saves the name layer ``mamba`` gives its in-projection's output
    and the name is free."""
    config = _config(block_config=[_block("mamba")], mamba_groups=1,
                     remat_policy="recompute")
    params = ModelParameter(config)
    model = Model(params)
    tokens = np.zeros((2, 64, 1), np.int32)
    batch = {"token_x": tokens, "token_y": tokens}
    variables = model.init(batch, seed=1)
    text = jax.jit(jax.value_and_grad(
        lambda v, b: model.apply(v, b).total_loss.data)).lower(
        variables, batch).as_text()
    harness.pinned("layer/mamba/one_group_stablehlo", text)
    # and B / C reach the scan as the parent's [b, s, n]
    seen = []
    real = mamba_mod.ssd_xla

    def spy(x, dt, a, b_mat, c_mat, chunk):
        seen.append(b_mat.shape)
        return real(x, dt, a, b_mat, c_mat, chunk)
    mamba_mod.ssd_xla, before = spy, mamba_mod.ssd_xla
    try:
        jax.eval_shape(lambda v, b: model.apply(v, b).total_loss.data,
                       variables, batch)
    finally:
        mamba_mod.ssd_xla = before
    assert seen == [(2, 64, 16)]


def _layer_on(*args, **kwargs):
    return harness.layer_on(*args, **kwargs)[0]


# ---- the share tests -----------------------------------------------------------


def the_expert_shares_add_up_to_the_uncut_layer_test():
    """Four expert-parallel ranks of four experts each: their routed parts
    through the latent, with what every rank computes alike (the shared
    expert) counted once, add up to what the uncut reference gives for the
    whole layer."""
    ref = _reference()
    rng = np.random.default_rng(2)
    heads, width, n_exp, inter, latent, shared = 2, 16, 16, 24, 32, 40

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32) * 0.3)

    whole = {"w_router": normal(heads, width, n_exp), "bias": normal(n_exp),
             "w_latent_down": normal(heads, width, latent),
             "w_up": normal(n_exp, latent, inter),
             "w_down": normal(n_exp, inter, latent),
             "w_latent_up": normal(latent, heads, width),
             "s_up": normal(heads, width, shared),
             "s_down": normal(shared, heads, width),
             "w_norm_in": jnp.ones((heads, width))}
    # the reference's block norms its input; the program's layer is given
    # the normed one
    h = normal(2, 64, heads, width)
    x = ref.rms(h, whole["w_norm_in"], 1e-5)
    config = _config(experts_held=0)
    uncut, _, _ = ref.sparse_block(whole, h, config)
    shared_part = ref.latent_up_and_shared(
        jnp.zeros((2, 64, latent)), x, whole["w_latent_up"], whole["s_up"],
        whole["s_down"])
    flags = MOE.split("-")[1:]
    parts = []
    for rank in range(4):
        first = 4 * rank
        cut = _config(experts_held=4, experts_first=first)
        share = dict(whole, **{k: whole[k][first:first + 4]
                               for k in ("w_up", "w_down")})
        got = _layer_on(ModelParameter(cut), moe_mod.moe, ref.SPARSE, share, x,
                     flags)
        want, _, _ = ref.sparse_block(share, h, cut)
        assert harness.error(got, want) < 2e-5
        parts.append(np.asarray(got - shared_part))
        assert np.max(np.abs(parts[-1])) > 1e-3
    assert harness.error(np.asarray(shared_part) + sum(parts), uncut) < 2e-5
    assert harness.error(_layer_on(ModelParameter(config), moe_mod.moe, ref.SPARSE,
                         whole, x, flags), uncut) < 2e-5


def _mamba_half(whole, rank, heads, p, groups, n):
    """Tensor-parallel rank ``rank`` of 2 of a Mamba-2 layer's weights: its
    half of the heads with THEIR groups — columns of ``W_in`` and the conv,
    the per-head vectors, the norm's scale, rows of ``W_out``."""
    di, gn = heads * p, groups * n
    h2, d2, g2 = heads // 2, di // 2, gn // 2
    cols = np.concatenate([
        np.arange(d2) + rank * d2,                      # z
        di + np.arange(d2) + rank * d2,                 # x
        2 * di + np.arange(g2) + rank * g2,             # B
        2 * di + gn + np.arange(g2) + rank * g2,        # C
        2 * di + 2 * gn + np.arange(h2) + rank * h2])   # dt
    conv = cols[d2:d2 + d2 + 2 * g2] - di
    head = slice(rank * h2, (rank + 1) * h2)
    inner = slice(rank * d2, (rank + 1) * d2)
    return {"w_norm_in": whole["w_norm_in"], "w_in": whole["w_in"][..., cols],
            "conv_w": whole["conv_w"][:, conv], "conv_b": whole["conv_b"][conv],
            "dt_bias": whole["dt_bias"][head], "a_log": whole["a_log"][head],
            "d": whole["d"][head], "w_norm": whole["w_norm"][inner],
            "w_out": whole["w_out"][inner]}


def both_tensor_parallel_halves_add_up_to_the_uncut_layers_test():
    """A Mamba-2 layer of 8 heads in 4 groups and an attention layer of 4
    query over 2 K/V heads, each cut by heads into the pair's two halves
    (heads with THEIR groups / K/V head): the halves' outputs — partial sums
    of the out-projection, by program and reference alike — add up to the
    uncut reference's layer.  The sum is the all-reduce nothing stands in
    for."""
    ref = _reference()
    rng = np.random.default_rng(4)
    heads, p, groups, n, f = 8, 16, 4, 16, (2, 16)
    di, conv = heads * p, heads * p + 2 * groups * n

    def normal(*shape, scale=0.3):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32) * scale)

    h = normal(2, 64, *f, scale=1.0)
    whole = {"w_norm_in": jnp.ones(f), "w_in": normal(*f, di + conv + heads),
             "conv_w": normal(4, conv), "conv_b": normal(conv),
             "dt_bias": normal(heads), "a_log": jnp.log(jnp.asarray(
                 rng.uniform(1, 4, heads), jnp.float32)),
             "d": normal(heads), "w_norm": 1 + normal(di),
             "w_out": normal(di, *f)}
    uncut = ref.mamba_block(whole, h, heads, n, groups, 1e-5)
    x = ref.rms(h, whole["w_norm_in"], 1e-5)
    cut = ModelParameter(_config(mamba_heads=4, mamba_groups=2))
    halves = []
    for rank in (0, 1):
        share = _mamba_half(whole, rank, heads, p, groups, n)
        want = ref.mamba_block(share, h, heads // 2, n, groups // 2, 1e-5)
        got = _layer_on(cut, mamba_mod.mamba, ref.MAMBA, share, x)
        assert harness.error(got, want) < 2e-5
        halves.append(np.asarray(got))
    assert harness.error(halves[0] + halves[1], uncut) < 2e-5
    assert harness.error(halves[0], uncut) > 0.1

    from homebrewnlp_tpu.model import spatial
    whole = {"w_norm_in": jnp.ones(f), "w_key": normal(*f, 2, 16),
             "w_query": normal(*f, 4, 16), "w_value": normal(*f, 2, 16),
             "w_out": normal(4, 16, *f)}
    uncut = ref.attention_block(whole, h, 1e-5)
    halves = []
    for rank in (0, 1):
        share = {"w_norm_in": whole["w_norm_in"],
                 "w_key": whole["w_key"][:, :, rank:rank + 1],
                 "w_value": whole["w_value"][:, :, rank:rank + 1],
                 "w_query": whole["w_query"][:, :, 2 * rank:2 * rank + 2],
                 "w_out": whole["w_out"][2 * rank:2 * rank + 2]}
        want = ref.attention_block(share, h, 1e-5)
        got = _layer_on(ModelParameter(_config()), spatial.attention,
                     ref.ATTENTION, share, x,
                     ["nope", "q_heads2", "kv_heads1"])
        assert harness.error(got, want) < 2e-5
        halves.append(np.asarray(got))
    assert harness.error(halves[0] + halves[1], uncut) < 2e-5
    assert harness.error(halves[0], uncut) > 0.1


# ---- refusals, scopes, statistics ----------------------------------------------

@pytest.mark.parametrize("extra,error,match", [
    ({"block_config": [_block("moe-relu2-plain-latent-sigmoid_bias-rms")]},
     ValueError, "does not know flag"),
    ({"block_config": [_block("moe-relu2-sigmoid_bias-router_mlp")]},
     NotImplementedError, "router_mlp"),
    ({"scan_layers": True}, NotImplementedError, "scan_layers"),
    ({"moe_latent_width": 0}, ValueError, "moe_latent_width"),
    ({"moe_router_z_loss": 0.1}, ValueError, "z_loss")])
def what_cannot_run_refuses_by_name_test(extra, error, match):
    config = _config(**extra)
    params = ModelParameter(config)
    tokens = np.zeros((2, 64, 1), np.int32)
    with pytest.raises(error, match=match):
        Model(params).init({"token_x": tokens, "token_y": tokens}, seed=1)


@pytest.mark.parametrize("extra,match", [
    ({"mamba_groups": 3}, "mamba_groups"), ({"mamba_groups": 0},
                                            "mamba_groups"),
    ({"moe_bias_rate": -1.0}, "moe_bias_rate"),
    ({"moe_latent_width": -1}, "moe_latent_width"),
    ({"shared_expert_width": 1.5}, "shared_expert_width")])
def bad_keys_refuse_by_name_test(extra, match):
    with pytest.raises(ValueError, match=match):
        ModelParameter(_config(**extra))


@pytest.mark.parametrize("path,scope_name", [
    ("jit(step_fn)/jvp(gpt0)/body0/block0_1_0/moe_0/latent_down/dot_general",
     "body/moe/latent_down"),
    ("jit(step_fn)/transpose(jvp(gpt0))/body0/block0_1_0/moe_0/latent_up/"
     "dot_general", "body/moe/latent_up"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_1_0/moe_0/experts/gmm",
     "body/moe/experts"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_2_0/mamba_0/ssd/ssd_scan_fwd",
     "body/mamba/ssd")])
def the_new_scopes_fold_test(path, scope_name):
    assert scope_key(path) == scope_name


def traced_ops_carry_the_latent_scopes_test():
    config, _, model, batch, variables = _build()
    found = {scope_key(name) for name in harness.traced_op_names(
        model, variables, batch, compiled=False)}
    assert {"body/moe/latent_down", "body/moe/latent_up", "body/moe/shared",
            "body/moe/router", "body/moe/experts", "body/mamba/ssd",
            "body/mamba/gate_norm"} <= found


def the_step_reports_the_bias_and_the_load_of_all_experts_test():
    prev = telemetry.set_registry(telemetry.Registry())
    try:
        config, params, model, batch, _ = _build(telemetry_enabled=True)
        trainer = Trainer(params, model)
        state = trainer.init_state(batch, seed=13)
        for _ in range(3):
            state, metrics = trainer.step(state, batch)
        jax.block_until_ready(metrics["loss"])
        trainer.step(state, batch)
        assert float(metrics["moe_bias_abs_max"]) == pytest.approx(2e-3)
        assert float(metrics["moe_all_load_max_over_mean"]) >= 1.0
        assert float(metrics["moe_routed_pairs"]) == 2 * 2 * 64 * 4
        snap = telemetry.snapshot()
        assert snap["hbnlp_moe_bias_abs_max"]["series"][()] > 0
        assert snap["hbnlp_moe_all_load_max_over_mean"]["series"][()] >= 1.0
        assert snap["hbnlp_moe_held_rows_bound"]["series"][()] == 2 * 64 * 4
    finally:
        telemetry.set_registry(prev)


def a_plain_layer_offers_two_matmuls_and_a_latent_one_its_sum_test():
    params = ModelParameter(_config())
    pairs, choices = 2 * 64 * 4, 2 * 64 * 4
    plain = moe_mod.moe.declares.offer(params, {"relu2", "plain"})
    assert plain.kind == "experts"
    assert plain.names == moe_mod.SAVED_NAMES[1:]
    assert plain.nbytes == pairs * (24 + 32) * 4 \
        + (2 * pairs + 5 + choices) * 4
    # the latent layer: the combined sum a TOKEN, no pair's row
    offer = moe_mod.moe.declares.offer(params, set(MOE.split("-")[1:]))
    assert offer.kind == "experts"
    assert offer.names == ("moe_order", "moe_inverse", "moe_sizes",
                           "moe_experts", "moe_latent_sum")
    assert offer.nbytes == 2 * 64 * 32 * 4 + (2 * pairs + 5 + choices) * 4
    gated = moe_mod.moe.declares.offer(params, {"silu"})
    assert gated.names == moe_mod.SAVED_NAMES
    assert gated.nbytes == pairs * (2 * 24 + 32) * 4 \
        + (2 * pairs + 5 + choices) * 4


def the_matrices_that_write_into_the_stream_start_smaller_test():
    """``rescale_prenorm_residual``: Mamba-2's out-projection, the
    attention's output projection, the experts' down-projection, the latent's
    up-projection and the shared expert's down-projection start at
    ``residual_out_stddev`` (the files: Megatron's 0.02 / sqrt(2 x 88));
    every other leaf, and at 0 these five too, as at normal(0.02)."""
    config = _config(residual_out_stddev=0.002)
    tokens = np.zeros((2, 64, 1), np.int32)
    batch = {"token_x": tokens, "token_y": tokens}
    small = Model(ModelParameter(config)).init(batch, seed=3)
    plain = Model(ModelParameter(dict(config, residual_out_stddev=0.0))).init(
        batch, seed=3)
    out = [name for name in small if re.search(
        r"mamba_0/normal_var1/|attention_0/normal_var3/"
        r"|moe_0/normal_var[346]/", name)]
    assert len(out) == 2 + 1 + 3 * 2
    for name in small:
        if name in out:
            assert np.std(small[name]) == pytest.approx(0.002, rel=0.15)
            np.testing.assert_allclose(np.asarray(small[name]) * 10,
                                       np.asarray(plain[name]), rtol=1e-5)
        else:
            np.testing.assert_array_equal(np.asarray(small[name]),
                                          np.asarray(plain[name]))
    for path in ("configs", os.path.join("benchmark", "configs")):
        with open(os.path.join(REPO, path,
                               "nemotron_3_super_120b.json")) as f:
            doc = json.load(f)
        assert doc.get("config", doc)["residual_out_stddev"] \
            == pytest.approx(0.02 / (2 * 88) ** 0.5)


# ---- the configurations ----------------------------------------------------------

def the_repos_config_is_the_published_model_test():
    with open(os.path.join(REPO, "configs",
                           "nemotron_3_super_120b.json")) as f:
        whole = json.load(f)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "nemotron_3_super_120b.json")) as f:
        doc = json.load(f)
    pattern = doc["hybrid_override_pattern"]
    layers = [block["layer"][-1].split("-")[0]
              for block in whole["block_config"]]
    assert len(layers) == 88 == doc["published"]["num_hidden_layers"]
    assert "".join({"mamba": "M", "moe": "E", "attention": "*"}[k]
                   for k in layers) == pattern
    assert (layers.count("attention"), layers.count("moe"),
            layers.count("mamba")) == (8, 40, 40)
    params = ModelParameter({**whole, "model_path": "/tmp/nemotron"})
    assert not params.unknown_config_keys
    assert (params.features, params.mamba_heads * params.mamba_head_features,
            params.mamba_groups, params.mamba_state, params.mamba_chunk,
            params.mamba_conv_size) == (4096, 8192, 8, 128, 128, 4)
    assert (params.experts, params.moe_top_k, params.expert_width,
            params.moe_latent_width, params.shared_expert_width,
            params.moe_route_scale, params.vocab_size) \
        == (512, 22, 2688, 1024, 5376, 5.0, 131072)
    # the cell's cut: one whole period at every width, this rank's share
    cut = doc["config"]
    assert "".join({"mamba": "M", "moe": "E", "attention": "*"}[
        block["layer"][-1].split("-")[0]] for block in cut["block_config"]) \
        == pattern[25:36] == "*EMEMEMEMEM"
    same = ("features_per_head", "heads", "mamba_head_features",
            "mamba_state", "mamba_chunk", "mamba_conv_size", "experts",
            "moe_top_k", "expert_width", "moe_latent_width",
            "shared_expert_width", "moe_route_scale", "norm_epsilon")
    assert {k: cut[k] for k in same} == {k: whole[k] for k in same}
    assert (cut["mamba_heads"], cut["mamba_groups"], cut["experts_held"],
            cut["vocab_size"]) == (64, 4, 8, 16384)
    assert cut["mamba_heads"] // cut["mamba_groups"] \
        == whole["mamba_heads"] // whole["mamba_groups"] == 16
    assert sorted(doc["reduced"]) == sorted(
        set(doc["published"]) | {"depth", "experts_held", "sequence_length",
                                 "train_batch_size", "tpu_size"})
    for key, value in doc["published"].items():
        assert doc[key] == doc["reduced"][key]["to"] != value \
            == doc["reduced"][key]["from"]


# ---- compiled for a described v5e ---------------------------------------------

@pytest.mark.parametrize("groups", [1, 4])
def scan_pair_compiles_at_a_chunk_of_one_lane_tile_test(v5e, groups):
    """PR 54: Mosaic accepts the scan pair at Nemotron-3's shapes — 64 heads
    of 64, a state of 128, a chunk of ONE lane tile (the last lane's decay is
    a masked lane sum there, ``_head``), and ``B`` / ``C`` in 4 groups whose
    two head blocks each share one ``scores`` tile — as at one group."""
    b, s, h, p, n, chunk = 1, 1024, 64, 64, 128, 128
    assert sk.ssd_kernel_applies(s, chunk, h, p, n, "tpu", groups)
    assert sk.head_block(h, p, groups) == 8
    cols = (b, s, groups, n) if groups > 1 else (b, s, n)
    avals = [jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
             for shape, dtype in (((b, s, h, p), jnp.bfloat16),
                                  ((b, s, h), jnp.float32),
                                  ((b, s, h), jnp.float32),
                                  (cols, jnp.bfloat16), (cols, jnp.bfloat16))]
    hlo = jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(sk.ssd_scan(*a, chunk)), argnums=(0, 1, 2, 3, 4))
    ).lower(*avals).compile().as_text()
    assert "ssd_scan_fwd" in hlo and "ssd_scan_bwd" in hlo
