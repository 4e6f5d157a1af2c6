"""Olmo-Hybrid-7B's layers through the normal path (ISSUE 32): the chunked
gated delta rule against the recurrence run position by position, the
blocked triangular solve, post-norm blocks and NoPE QK-norm attention at a
head count that is no power of two, the program against the plain reference
``benchmark/reference/olmo_hybrid_7b.py``, the eight-block period, and the
new scopes and gauges."""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from harness import REPO
from homebrewnlp_tpu.analysis.cost_ledger import scope_key
from homebrewnlp_tpu.config import ModelParameter
from homebrewnlp_tpu.model import Model, gated_delta as delta_mod
from homebrewnlp_tpu.model import mamba as mamba_mod
from homebrewnlp_tpu.model import recurrent, remat

# three heads: no power of two, as the published thirty
TINY = {"depth": 1, "heads": 3, "features_per_head": 16,
        "sequence_length": 64, "train_batch_size": 2, "vocab_size": 384,
        "delta_heads": 3, "delta_key_features": 8,
        "delta_value_features": 16, "delta_chunk": 16, "tpu_size": 1,
        "use_checkpointing": False}


def _reference():
    return harness.reference("olmo_hybrid_7b")


def _config(dtype: str = "float32", **extra) -> dict:
    return harness.config_of("olmo_hybrid_7b", TINY, dtype, **extra)


def _build(dtype: str = "float32", **extra):
    return harness.build(_config(dtype, **extra))


def _logits(model, variables, batch):
    return harness.logits_and_loss(model, variables, batch)[0]


# ---- the chunked rule --------------------------------------------------------

def _rule_inputs(s: int, decay: float, seed: int = 0):
    """Unit keys that share a direction (neighbouring tokens' keys are alike
    in a trained model: the triangular system is then far from the identity),
    ``beta`` over all of (0, 2), log-decays of about ``-decay`` a position:
    at 6 a product of 16 exponentials is e^-96, below float32's smallest
    normal number."""
    rng = np.random.default_rng(seed)
    b, h, dk, dv = 2, 3, 4, 5
    shared = rng.normal(size=(b, 1, h, dk))

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.normal(size=(b, s, h, dk)) + shared) * dk ** -0.5
    k = unit(rng.normal(size=(b, s, h, dk)) + 2 * shared)
    v = rng.normal(size=(b, s, h, dv))
    beta = rng.uniform(0.0, 2.0, size=(b, s, h))
    beta[:, ::5] = 2.0
    g = -decay * rng.uniform(0.5, 1.5, size=(b, s, h))
    return tuple(jnp.asarray(t, jnp.float32) for t in (q, k, v, beta, g))


@pytest.mark.parametrize("decay", [0.05, 6.0])
@pytest.mark.parametrize("chunk,s", [(4, 4), (8, 32), (12, 12), (16, 16),
                                     (16, 64), (64, 128)])
def chunked_rule_is_the_recurrence_test(chunk, s, decay):
    """One chunk (a power of two and not) and several, ``beta`` up to 2, with
    decays so strong that any product (or quotient) of exponentials along a
    chunk under- (or over-)flows: the rule forms ``exp`` of differences
    only."""
    inputs = _rule_inputs(s, decay)
    got = jax.jit(lambda *a: delta_mod.delta_rule(*a, chunk)[0])(*inputs)
    want = jax.jit(_reference().recurrence)(*inputs)
    assert got.dtype == jnp.float32
    assert harness.error(np.asarray(got), np.asarray(want)) < 2e-5


@pytest.mark.parametrize("decay", [0.05, 6.0])
@pytest.mark.parametrize("chunk,s", [(12, 12), (8, 32), (64, 128)])
def chunked_rules_gradients_are_the_recurrences_test(chunk, s, decay):
    """All five gradients through the solve and the serial carry against
    autodiff of the position-by-position loop."""
    inputs = _rule_inputs(s, decay)
    weights = jnp.asarray(np.random.default_rng(1).normal(
        size=inputs[2].shape).astype(np.float32))
    recurrence = _reference().recurrence

    def chunked(*args):
        return jnp.sum(delta_mod.delta_rule(*args, chunk)[0] * weights)

    def stepped(*args):
        return jnp.sum(recurrence(*args) * weights)

    grads = jax.jit(jax.grad(chunked, argnums=range(5)))(*inputs)
    wants = jax.jit(jax.grad(stepped, argnums=range(5)))(*inputs)
    for name, a, r in zip("q k v beta g".split(), grads, wants):
        assert np.all(np.isfinite(a)), name
        assert harness.error(np.asarray(a), np.asarray(r)) < 1e-4, name


@pytest.mark.optimised
@pytest.mark.parametrize("budget,groups", [(48 << 20, 1), (2 * 32 * 8 * 4, 3),
                                           (0, 3)])
def grouped_rule_is_the_rule_test(monkeypatch, budget, groups):
    """Heads are independent: the rule over groups of heads, one after
    another and each rematerialised in the backward, gives the values, the
    gauge and the five gradients of the rule over all heads at once — for
    one group (every toy size), for a budget that one head's matrix fits
    and two heads' do not, and for none at all (a head a group)."""
    monkeypatch.setattr(delta_mod, "GROUP_BYTES", budget)
    inputs = _rule_inputs(32, 0.05)
    assert 3 // delta_mod._group_heads(2, 32, 3, 8) == groups
    weights = jnp.asarray(np.random.default_rng(1).normal(
        size=inputs[2].shape).astype(np.float32))

    def loss(rule):
        def fn(*args):
            o, biggest = rule(*args, 8)
            return jnp.sum(o * weights), (o, biggest)
        return jax.jit(jax.value_and_grad(fn, argnums=range(5), has_aux=True))

    (_, (got, got_max)), grads = loss(delta_mod.grouped_rule)(*inputs)
    (_, (want, want_max)), wants = loss(delta_mod.delta_rule)(*inputs)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert float(got_max) == float(want_max)
    for a, r in zip(grads, wants):
        np.testing.assert_allclose(a, r, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("size", [1, 2, 5, 16, 64])
def blocked_solve_is_the_inverse_test(size):
    """``(I + N)^-1`` of a strictly lower triangular ``N`` whose entries are
    near 1.6 — keys that nearly coincide, ``beta`` near 2: the Neumann
    series' terms reach 1e12 at 64 rows before they cancel, blocked
    substitution stays at float32's rounding."""
    rng = np.random.default_rng(size)
    strict = np.tril(rng.uniform(1.2, 2.0, size=(3, size, size)), -1)
    got = np.asarray(delta_mod._inverse_unit_lower(
        jnp.asarray(strict, jnp.float32)))
    want = np.linalg.inv(np.eye(size) + strict)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= 2e-5 * max(1.0, np.abs(want).max())
    assert not np.triu(got, 1).any()


def transform_gauge_is_the_solved_transforms_size_test():
    """With ``beta = 1`` and orthogonal keys ``T`` is the identity; with one
    repeated key and ``beta = 2`` (a reflection a position) its entries
    below the diagonal alternate between -4 and 4."""
    eye = jnp.eye(4, dtype=jnp.float32)[None, :, None, :]       # [1, 4, 1, 4]
    v = jnp.ones((1, 4, 1, 2), jnp.float32)
    zero = jnp.zeros((1, 4, 1), jnp.float32)
    _, size = delta_mod.delta_rule(eye, eye, v, zero + 1, zero, 4)
    assert float(size) == 1.0
    same = jnp.broadcast_to(eye[:, :1], eye.shape)
    _, size = delta_mod.delta_rule(same, same, v, zero + 2, zero, 4)
    assert float(size) == 4.0


# ---- the program against the plain reference ---------------------------------

_PERIOD = None      # the published three linear layers and one full
_ONE = {kind: [{"skip": True, "layer": [kind, "norm-rms-scale"]},
               {"skip": True, "layer": ["mlp-silu", "norm-rms-scale"]}]
        for kind in ("gated_delta", "attention-nope-qk_norm")}


@pytest.mark.parametrize("blocks", [_PERIOD, _ONE["gated_delta"],
                                    _ONE["attention-nope-qk_norm"]],
                         ids=["period", "gated_delta", "attention"])
def float32_program_is_the_reference_test(blocks):
    """float32 program against float32 reference: only summation order and
    the chunked form differ, so this pins the EQUATIONS — the post-norm
    block order (a pre-norm block is off by the logits' own size), the
    QK-norm over all of three heads' features, the L2 norms, ``beta``'s
    factor 2, the decay, the gate after the norm — layer by layer and for
    the whole period."""
    extra = {} if blocks is None else {"block_config": blocks}
    config, _, model, batch, variables = _build("float32", **extra)
    want = _reference().forward(variables, batch["token_x"][..., 0], config)
    logits, loss = harness.logits_and_loss(model, variables, batch)
    assert harness.error(logits, want) < 2e-5
    from benchmark.reference import common
    want_loss = float(common.loss_of(want, batch["token_y"][..., 0], 0.0))
    assert abs(want_loss - loss) <= 2.0 ** -18 * want_loss


def block_order_is_post_norm_test():
    """``h + norm(f(h))``: with every sublayer's norm scale at zero the
    stream is the embedding itself, whatever the sublayers compute; a
    pre-norm block ``h + f(norm(h))`` would not be."""
    config, _, model, batch, variables = _build("float32")
    silent = {k: (np.zeros_like(v) if "/body0/" in k and "/norm_0/" in k
                  else v) for k, v in variables.items()}
    got = _logits(model, silent, batch)
    bare, _, bare_model, _, _ = _build("float32", block_config=[])
    want = _logits(bare_model, {k: v for k, v in variables.items()
                                if "/body0/" not in k}, batch)
    np.testing.assert_array_equal(got, want)


def bfloat16_program_meets_a_bound_float8_misses_test():
    """The configuration's own bfloat16 (activations, the residual stream,
    the rule's matmul operands; float32 decays, solve and state) against the
    float32 reference.  Post-norm blocks put every sublayer's rounding into
    the stream at full size (the stream IS the sum of unit-size normed
    outputs; a pre-norm stream is led by the embedding), so the error is
    several times a pre-norm model's: measured here 0.12-0.23 over three
    seeds at depth 1 (the float32 reference with its residual stream alone
    rounded to bfloat16: 0.06-0.09).  The reference with its stream rounded
    to float8 (e4m3) is off by 0.85-1.0: the bound 0.4 separates the
    precisions."""
    config, _, model, batch, variables = _build("bfloat16")
    tokens = batch["token_x"][..., 0]
    ref = _reference()
    want = ref.forward(variables, tokens, config)
    assert harness.error(_logits(model, variables, batch), want) < 0.4
    assert harness.error(ref.forward(variables, tokens, config,
                              stream_dtype=jnp.bfloat16), want) < 0.4
    assert harness.error(ref.forward(variables, tokens, config,
                              stream_dtype=jnp.float8_e4m3fn), want) > 0.6


def rounded_weights_alone_miss_the_benchmarks_toy_bound_test():
    """Why ``conftest.py`` expects ``benchmark/tests/reference_test.py``'s
    bfloat16 case to fail for this configuration: at that test's size
    (hidden 64, two periods, the published mixer widths) the float32
    reference itself, every operation in float32 at ``highest``, is off by
    more than the test's 2^-4 as soon as its weights are rounded to bfloat16
    as the program's are where it uses them (0.127-0.130 over seeds; the
    embedding table alone 0.066-0.075), and so is any program."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "olmo_hybrid_7b.json")) as f:
        config = dict(json.load(f)["config"], depth=2, heads=4,
                      features_per_head=16, sequence_length=128,
                      train_batch_size=2, model_path="/tmp/olmo_toy_bound")
    model = Model(ModelParameter(config))
    tokens = np.random.default_rng(0).integers(
        0, 256, (2, 128, 1)).astype(np.int32)
    variables = model.init({"token_x": tokens, "token_y": tokens}, seed=7)
    rounded = {k: np.asarray(jnp.asarray(v).astype(jnp.bfloat16)
                             .astype(jnp.float32)) if np.ndim(v) >= 2 else v
               for k, v in variables.items()}
    (name,) = [k for k in variables
               if "input0/gather0/embed0/normal_var0" in k]
    ref = _reference()
    want = ref.forward(variables, tokens[..., 0], config)
    assert harness.error(ref.forward(rounded, tokens[..., 0], config), want) \
        > 1.5 * 2 ** -4
    assert harness.error(ref.forward({**variables, name: rounded[name]},
                              tokens[..., 0], config), want) > 2 ** -4


def precision_control_goes_through_the_drivers_comparison_test(capsys):
    """``benchmark/precision_control.py`` at the cell's toy size: the
    program's numbers are the train driver's own (``_reference_check``, the
    cell's ``logit_tolerance``), the lower-precision references go through
    the same comparison, and the float8 stream comes out as not correct."""
    from benchmark import precision_control
    assert precision_control.main([
        "--workload", "train_olmo_hybrid_7b_long", "--seed", "3000000019",
        "--rehearse-cpu"]) == precision_control.EXIT_REHEARSAL
    lines = capsys.readouterr().out.strip().splitlines()
    out = json.loads(lines[-1])
    with open(os.path.join(REPO, "benchmark", "workloads",
                           "train_olmo_hybrid_7b_long.json")) as f:
        tolerance = json.load(f)["correct"]["logit_tolerance"]
    assert out["logit_tolerance"] == tolerance
    said = [float(re.search(r"= ([0-9.]+) \(tolerance", line).group(1))
            for line in lines if line.startswith("reference: ")]
    for key, logged in zip(("program", "bfloat16_stream",
                            "float8_e4m3fn_stream"), said, strict=True):
        assert abs(out[key]["logit_error"] - logged) < 1e-6
        assert out[key]["logits_agree"] == (out[key]["logit_error"]
                                            <= tolerance)
    assert out["bfloat16_stream"]["logit_error"] \
        < out["float8_e4m3fn_stream"]["logit_error"]
    assert not out["float8_e4m3fn_stream"]["logits_agree"]


def a_short_sequence_is_one_chunk_test():
    """A sequence below ``delta_chunk`` runs as one chunk of its own length
    (48: no power of two), and equals the reference."""
    config, _, model, batch, variables = _build(
        "float32", sequence_length=48, delta_chunk=64)
    want = _reference().forward(variables, batch["token_x"][..., 0], config)
    assert harness.error(_logits(model, variables, batch), want) < 2e-5


# ---- the configuration -------------------------------------------------------

def _layer_counts(params):
    d = params.heads * params.features_per_head
    h, dk, dv = (params.delta_heads, params.delta_key_features,
                 params.delta_value_features)
    conv = 2 * h * dk + h * dv
    mlp = 3 * d * params.intermediate[0].size + d          # and its norm
    mixer = d * (conv + h * dv + 2 * h) + params.delta_conv_size * conv \
        + 2 * h + dv + h * dv * d + d
    return mixer + mlp, 4 * d * d + 2 * d + d + mlp


def published_configuration_counts_test():
    """The repository's full-depth configuration, every width as published:
    215.6 M a linear-attention layer, 185.8 M the full-attention layer, 7.43 B
    in all; 928.9 M at the benchmark's cut (one period, an eighth of the two
    tables)."""
    with open(os.path.join(REPO, "configs", "olmo_hybrid_7b.json")) as f:
        config = json.load(f)
    params = ModelParameter(dict(config, model_path="/tmp/olmo_counts"))
    assert not params.unknown_config_keys
    d = params.heads * params.features_per_head
    assert (d, params.intermediate[0].size, params.vocab_size, params.depth,
            params.sequence_length, params.norm_epsilon) \
        == (3840, 11008, 100352, 8, 65536, 1e-6)
    assert (params.delta_heads, params.delta_key_features,
            params.delta_value_features, params.delta_conv_size,
            params.delta_chunk, params.delta_allow_neg_eigval) \
        == (30, 96, 192, 4, 64, True)
    delta, attention = _layer_counts(params)
    assert (delta, attention) == (215_570_172, 185_809_920)
    period = 3 * delta + attention
    assert 8 * period + 2 * d * 100352 + d == 7_430_870_688
    assert period + 2 * d * 12544 + d == 928_862_196
    with open(os.path.join(REPO, "benchmark", "configs",
                           "olmo_hybrid_7b.json")) as f:
        cut = json.load(f)
    assert {**config, **cut["overrides"], "norm_epsilon": 1e-6} \
        == {**config, **cut["config"]}, "the cut changes only its overrides"
    assert cut["layer_types"][:4] == ["linear_attention"] * 3 \
        + ["full_attention"]


def period_is_eight_blocks_test():
    """Three linear layers and one full-attention layer, an MLP after each;
    the toy model's parameters are the counts' formula at its sizes."""
    _, params, model, _, variables = _build("float32", depth=2)
    assert len(model.plan) == 16
    mixers = [next(n.split("/")[3] for n in variables
                   if f"/block{d}_{c}_0/" in n and "/norm_" not in n)
              for d in range(2) for c in range(0, 8, 2)]
    assert mixers == (["gated_delta_0"] * 3 + ["attention_0"]) * 2
    assert all(any(f"/block{d}_{c}_0/mlp_0/" in n for n in variables)
               for d in range(2) for c in range(1, 8, 2))
    delta, attention = _layer_counts(params)
    d = params.heads * params.features_per_head
    assert sum(int(np.prod(v.shape)) for v in variables.values()) \
        == 2 * (3 * delta + attention) + 2 * d * params.vocab_size + d
    # untied: two tables
    assert "gpt0/output0/embed0/normal_var0/var0" in variables


@pytest.mark.parametrize("bad,message", [
    ({"delta_heads": 0}, "delta_heads"),
    ({"delta_key_features": 1.5}, "delta_key_features"),
    ({"delta_chunk": -64}, "delta_chunk"),
    ({"delta_conv_size": 129}, "delta_conv_size"),
    ({"norm_epsilon": 0}, "norm_epsilon")])
def configuration_is_validated_test(bad, message):
    with pytest.raises(ValueError, match=message):
        ModelParameter(dict(_config(), **bad))


def sequence_is_whole_chunks_test():
    with pytest.raises(ValueError, match="multiple of gated_delta's chunk"):
        _build("float32", sequence_length=40)


def decode_forms_are_later_issues_test():
    _, params, model, batch, variables = _build("float32")
    with pytest.raises(NotImplementedError, match="decode"):
        model.apply_decode(variables, batch["token_x"][:, :1],
                           jnp.int32(0), {})


def shared_pieces_are_shared_test():
    """The conv, the per-channel parameters and their initialisers: one
    function each, which both recurrent layers import."""
    for name in ("causal_depthwise_conv", "_small_var",
                 "_inverse_softplus_of_exp", "_norm_core", "_matmul"):
        assert getattr(delta_mod, name) is getattr(mamba_mod, name), name
    assert delta_mod.causal_depthwise_conv is recurrent.causal_depthwise_conv
    assert delta_mod.causal_conv_silu is mamba_mod.causal_conv_silu


# ---- scopes, gauges, the memory strategy -------------------------------------

@pytest.mark.parametrize("path,scope", [
    ("gpt0/body0/block0_0_0/gated_delta_0/delta_rule/solve/dot_general",
     "body/gated_delta/delta_rule"),
    ("transpose(jvp(gpt0))/body0/block0_2_0/"
     "gated_delta_0/delta_rule/inter_chunk/while",
     "body/gated_delta/delta_rule"),
    ("jvp(gpt0)/body0/block0_4_0/gated_delta_0/in_proj/dot_general",
     "body/gated_delta/in_proj"),
    ("gpt0/body0/block0_0_0/gated_delta_0/conv/mul", "body/gated_delta/conv"),
    ("gpt0/body0/block0_0_0/gated_delta_0/gate_norm/mul",
     "body/gated_delta/gate_norm"),
    ("gpt0/body0/block0_0_0/gated_delta_0/out_proj/dot_general",
     "body/gated_delta/out_proj"),
    ("gpt0/body0/block0_0_0/gated_delta_0/normal_var0/convert",
     "body/gated_delta"),
    ("gpt0/body0/block0_0_0/norm_0/mul", "body/norm"),
    ("gpt0/body0/block0_6_0/attention_0/dot_general", "body/attention")])
def new_layer_folds_into_its_scopes_test(path, scope):
    assert scope_key(path) == scope


def traced_ops_carry_the_rules_steps_test():
    """Every step of the rule is a named scope of the compiled program's
    ops, inside ``delta_rule`` — through ``grouped_rule``'s ``lax.map`` and
    ``jax.checkpoint``, whose bodies are lowered as functions of their own:
    the compiled ops carry the whole path, which is what the trace reads."""
    _, _, model, batch, variables = _build("float32")
    names = harness.traced_op_names(model, variables, batch)
    for step in ("decay", "solve", "intra_chunk", "inter_chunk",
                 "state_out"):
        inside = [n for n in names if re.search(
            rf"gated_delta_0/delta_rule/.*/{step}/", n)]
        assert inside, step
        assert {scope_key(n) for n in inside} \
            == {"body/gated_delta/delta_rule"}, step
    for part in ("in_proj", "conv", "gate_norm", "out_proj"):
        assert any(f"gated_delta_0/{part}/" in n for n in names), part


def remat_rules_count_the_new_layer_test(monkeypatch):
    """``checkpoint`` with no ``moe`` layer: the experts kind rides nothing
    (and under ``"recompute"`` nothing does); the chunk states' gauge counts
    one layer's (the largest of the declared), every layer's under ``none``;
    the conv gauge counts the three bias-free convs where the kernel takes
    them, the solve gauge (PR 37) the three triangular solves where theirs
    does — through what the layer declares, the predicate it calls."""
    from homebrewnlp_tpu import telemetry
    from homebrewnlp_tpu.model.blocks import _checkpoint_policy
    from homebrewnlp_tpu.train import Trainer
    _, params, model, _, _ = _build("float32")
    assert params.memory_reduction_strategy == "checkpoint"
    assert remat.stash_plan(params)["experts"] == (0, 0)
    assert "experts" not in remat.stash_kinds(params)
    # the rule's output of three layers, [2, 64, 3, 16] float32 each (PR 33)
    assert remat.stash_plan(params)["recurrent"] == (3, 3 * 24576)
    _, params, model, _, _ = _build("float32", remat_policy="recompute")
    assert _checkpoint_policy(params) \
        is jax.checkpoint_policies.nothing_saveable
    # [2, 64 / 16, 3, 16, 8] in the calculation dtype, here float32
    assert recurrent.ssd_state_bytes(params) == 2 * 4 * 3 * 16 * 8 * 4 == 12288
    assert recurrent.conv_kernel_layers(params, "tpu") == 0     # 96 channels
    line = Trainer(params, model).publish_stash_plan()
    assert line.endswith("experts 0 layers, 0 bytes a device; recurrent 0 "
                         "layers, 0 bytes a device; dense 0 layers, 0 bytes "
                         "a device; ssd chunk states 12288 "
                         "bytes a device; conv kernel 0 layers; solve kernel "
                         "0 layers; rule kernel 0 layers")
    snap = telemetry.registry().snapshot()
    assert snap["hbnlp_ssd_state_bytes"]["series"][()] == 12288
    assert snap["hbnlp_delta_solve_kernel_layers"]["series"][()] == 0
    # 2 x 4 chunks x 3 heads = 24 systems of 16 x 16 a call: no whole tile
    assert delta_mod.gated_delta.declares.recurrent.solve(params) == (16, 24)
    assert recurrent.solve_kernel_layers(params, "tpu") == 0
    _, none, _, _, _ = _build("float32", memory_reduction_strategy="none")
    assert recurrent.ssd_state_bytes(none) == 3 * 12288
    # 3 x (2 x 32 + 64) = 384 channels from channel 0 of proj on
    _, wide, _, _, _ = _build("float32", delta_key_features=32,
                              delta_value_features=64, sequence_length=256)
    assert delta_mod.gated_delta.declares.recurrent.conv(wide) == (384, 4, 0)
    assert recurrent.conv_kernel_layers(wide, "tpu") == 3
    assert recurrent.conv_kernel_layers(wide) == 0
    # 2 x 16 chunks x 4 heads = 128 systems a call: one tile of the kernel
    _, tiled, _, _, _ = _build("float32", sequence_length=256, delta_heads=4)
    assert delta_mod.gated_delta.declares.recurrent.solve(tiled) == (16, 128)
    assert recurrent.solve_kernel_layers(tiled, "tpu") == 3
    assert recurrent.solve_kernel_layers(tiled) == 0            # the CPU
    odd = _build("float32", sequence_length=192, delta_heads=4,
                 delta_chunk=48)[1]
    assert delta_mod.gated_delta.declares.recurrent.solve(odd) == (48, 32)
    assert recurrent.solve_kernel_layers(odd, "tpu") == 0       # no tile takes 48
    with open(os.path.join(REPO, "benchmark", "configs",
                           "olmo_hybrid_7b.json")) as f:
        cell = ModelParameter(dict(json.load(f)["config"],
                                   model_path="/tmp/olmo_cell"))
    # 256 chunks' entering states of one group of 10 heads in bfloat16
    assert recurrent.ssd_state_bytes(cell) == 256 * 10 * 192 * 96 * 2 \
        == 94_371_840
    assert recurrent.conv_kernel_layers(cell, "tpu") == 3
    # 256 chunks x the 10 heads of a group, chunk 64; 3 layers x depth 1
    assert delta_mod.gated_delta.declares.recurrent.solve(cell) == (64, 2560)
    assert recurrent.solve_kernel_layers(cell, "tpu") == 3 * cell.depth == 3
    assert recurrent.solve_kernel_layers(cell) == 0
    # PR 50: where the rule is the Pallas pair every head's systems are one
    # call's (256 x 30) and every head's entering states are alive at once
    assert delta_mod.gated_delta.declares.recurrent.solve(cell, "tpu") \
        == (64, 7680)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert recurrent.ssd_state_bytes(cell) == 256 * 30 * 192 * 96 * 2 \
        == 283_115_520


def step_reports_the_transform_watch_test():
    """Five steps of the trainer: the loss is finite and falls, a later call
    publishes an earlier step's ``hbnlp_delta_transform_abs_max``."""
    from homebrewnlp_tpu import telemetry
    from homebrewnlp_tpu.train import Trainer
    _, params, model, batch, _ = _build(
        "float32", telemetry_enabled=True, sequence_length=32,
        learning_rate=0.01,
        learning_rate_config={"linear_warmup": {"final_step": 1}})
    batch = {k: v[:, :32] for k, v in batch.items()}
    trainer = Trainer(params, model)
    state = trainer.init_state(batch)
    losses = []
    for _ in range(5):
        state, metrics = trainer.step(state, batch)
        jax.block_until_ready(metrics["loss"])
        losses.append(float(metrics["loss"]))
        assert 0.5 < float(metrics["delta_transform_abs_max"]) < 10
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    snap = telemetry.registry().snapshot()
    assert 0.5 < snap["hbnlp_delta_transform_abs_max"]["series"][()] < 10


# ---- the recurrent kind (PR 33): the rule's output rides the block's
# jax.checkpoint, so the replay runs no forward of the rule ---------------------

def _forward_carries(jaxpr, found=None, path=""):
    """The forward ``inter_chunk`` scans of a jaxpr, through every nested
    one (``checkpoint``, ``lax.map``, the scan over the depth): one a forward
    of the rule over a group of heads."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        here = f"{path}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "scan" and "inter_chunk" in here \
                and not eqn.params["reverse"]:
            found.append(here)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _forward_carries(sub, found, here)
    return found


@pytest.mark.optimised
@pytest.mark.parametrize("groups", [1, 3])
@pytest.mark.parametrize("scan_layers", [False, True],
                         ids=["unrolled", "scan_layers"])
def saved_rule_output_changes_no_bit_test(monkeypatch, scan_layers, groups):
    """Two periods — of ONE linear layer and the full one here, not the
    published three and one: a step's compile is its blocks' count, and the
    layers' counts below follow the toy (PR 60) — under ``checkpoint`` with
    the rule's output saved by each
    block's ``jax.checkpoint`` (``"auto"``: the bytes fit): the loss and every
    gradient are bit for bit those of ``remat_policy: "recompute"``, the
    gradient's jaxpr runs the rule forward twice a ``gated_delta`` layer
    instead of three times (the step and the group's own re-materialisation;
    the block's replay no longer), and the gauges read the declared bytes —
    for the rule over all heads at once and a head a group."""
    from homebrewnlp_tpu import telemetry
    from homebrewnlp_tpu.model.blocks import _checkpoint_policy
    from homebrewnlp_tpu.train import Trainer
    if groups == 3:
        # [2, 64] tokens x chunk 16 x float32: one head's matrix
        monkeypatch.setattr(delta_mod, "GROUP_BYTES", 2 * 64 * 16 * 4)
    assert 3 // delta_mod._group_heads(2, 64, 3, 16) == groups
    depth, linear = 2, 1
    results = {}
    for policy in ("recompute", "auto"):
        _, params, model, batch, variables = _build(
            "float32", depth=depth, scan_layers=scan_layers,
            remat_policy=policy, block_config=_ONE["gated_delta"]
            + _ONE["attention-nope-qk_norm"])
        fn = jax.value_and_grad(
            lambda v: model.apply(v, batch).total_loss.data)
        # the depth's scan traces its linear layers once
        layers = linear if scan_layers else linear * depth
        forwards = len(_forward_carries(jax.make_jaxpr(fn)(variables).jaxpr))
        results[policy] = (params, model, forwards / layers,
                           jax.jit(fn)(variables))
    params, model, forwards, (loss, grads) = results["auto"]
    _, _, before, (want_loss, want) = results["recompute"]
    assert (before, forwards) == (3, 2)
    assert float(loss) == float(want_loss) and np.isfinite(float(loss))
    assert set(grads) == set(want)
    for name in want:
        np.testing.assert_array_equal(np.asarray(grads[name]),
                                      np.asarray(want[name]), err_msg=name)
    # [2, 64, 3, 16] float32 a layer
    saved = 2 * 64 * 3 * 16 * 4 * linear * depth
    assert delta_mod.gated_delta.declares.offer(params, set()).names \
        == ("gated_delta_out",)
    assert remat.stash_plan(params)["recurrent"] == (linear * depth, saved)
    assert remat.stash_names(params) == ("gated_delta_out",)
    assert _checkpoint_policy(params) \
        is not jax.checkpoint_policies.nothing_saveable
    line = Trainer(params, model).publish_stash_plan()
    assert f"recurrent {linear * depth} layers, {saved} bytes a device" \
        in line
    snap = telemetry.registry().snapshot()
    assert snap["hbnlp_remat_stash_bytes"]["series"][("recurrent",)] == saved
    assert snap["hbnlp_remat_stash_layers"]["series"][("recurrent",)] \
        == linear * depth
    off = results["recompute"][0]
    assert remat.stash_plan(off)["recurrent"] == (0, 0)
    assert remat.stash_names(off) == ()
    assert _checkpoint_policy(off) is jax.checkpoint_policies.nothing_saveable


def dense_kind_rides_the_last_region_alone_test(monkeypatch):
    """PR 52: of the cell's four MLPs the rule admits ONE, the step's last
    block's — 0.836 GB are left of the 15% after the flash pair, the rule's
    three outputs and the eight block inputs, and gate + up of [1, 16384,
    11008] are 0.721 GB — so only the last region's policy holds the two new
    names; the seven before it get the parent's policy, the same object."""
    from homebrewnlp_tpu.model.blocks import (_checkpoint_policy,
                                              _region_policies)
    from homebrewnlp_tpu.utils import flops
    from remat_policy_test import _cell_params
    monkeypatch.setattr(flops, "hbm_capacity",
                        lambda device=None: (16911433728, "memory_stats"))
    params = _cell_params("train_olmo_hybrid_7b_long")
    assert len(params.block_config) * params.depth == 8
    left = int(0.15 * 16911433728) - 127795200 - 566231040 \
        - 8 * 16384 * 3840 * 2
    assert 721420288 <= left < 2 * 721420288
    assert remat.stash_plan(params)["dense"] == (1, 721420288)
    assert remat.dense_executions(params) == 1
    parents = ("gated_delta_out", "flash_out", "flash_lse")
    assert remat.region_names(params) == [parents] * 7 \
        + [parents + ("mlp_gate", "mlp_up")]
    policies = _region_policies(params)
    assert [policy is _checkpoint_policy(params) for policy in policies] \
        == [True] * 7 + [False]


def the_scalar_decay_layers_jaxpr_is_the_parents_test():
    """PR 58 added layer ``kda`` (a decay a channel) BESIDE this one and
    widened the flash kernels to two widths: the toy model's gradient —
    ``gated_delta``'s scalar-decay rule, the attention layer, the MLPs —
    traces to the pinned jaxpr, source positions and object addresses
    stripped."""
    _, _, model, batch, variables = _build()
    text = str(jax.make_jaxpr(jax.grad(
        lambda v, b: model.apply(v, b).total_loss.data))(variables, batch))
    harness.pinned("step/olmo_hybrid_toy/grad",
                   re.sub(r" at \S+:\d+", "", text))


# ---- compiled for a described v5e ---------------------------------------------

def delta_layers_kernels_keep_their_scopes_test(v5e, monkeypatch):
    """One ``gated_delta`` layer at Olmo-Hybrid's published widths, 1 x 8,192
    tokens (half the cell's), compiled for a v5e as a TPU process traces it:
    the bias-free conv over 11,520 channels is the same Pallas pair in its
    three forms, every one folds into ``body/gated_delta/conv``; the rule's
    triangular solve (PR 37) is the pair of ``parallel/delta_solve.py`` and
    what is around it (PR 50) the two pairs of ``parallel/delta_rule.py``
    (``delta_strict_*`` makes the solve's input, ``delta_rule_*`` runs the
    rule): ONE ``delta_rule_bwd``, ``delta_solve_bwd`` and
    ``delta_strict_bwd`` a layer and as many of each ``_fwd`` as the memory
    plan gives — two (the step's forward and the block's replay) where the
    ``recurrent`` kind saves the rule's output alone, so that the replay
    makes ``T`` and the entering states again; one if they ride with it —
    the solve on
    all 3,840 systems of the layer at once, operands ``[systems * 64, 64]``
    (a bitcast of XLA's ``[.., 64, 64]``), the rule on the sequence-minor
    layout the conv's kernels write: no transposing copy of a large operand
    beside any.  Mosaic accepts all six, their ops carry
    ``gated_delta_0/delta_rule/`` (the solve's ``../solve/``) and fold into
    ``body/gated_delta/delta_rule``, which ``delta_rule_time_share`` and
    ``delta_rule_roofline`` read, and none bears a name another metric's
    reader takes."""
    params, hlo = harness.cell_layer_hlo(
        v5e, monkeypatch, "train_olmo_hybrid_7b_long", 0,
        sequence_length=8192)
    assert params.block_config[0].layer[0] == "gated_delta"
    assert recurrent.conv_kernel_layers(params) == 1
    assert recurrent.solve_kernel_layers(params) == 1
    assert recurrent.rule_kernel_layers(params) == 1
    # what the plan saves of the rule: its output alone -> the replay runs
    # both forward kernels again
    assert remat.stash_plan(params)["recurrent"][0] == 1
    saved = delta_mod.gated_delta.declares.offer(params, set()).names
    forwards = 2 if saved == ("gated_delta_out",) else 1
    calls = re.findall(r'%([\w.-]+) = ([^\n]*?)custom_call_target='
                       r'"tpu_custom_call"[^\n]*?op_name="([^"]+)"', hlo)
    assert sorted(re.sub(r"\.\d+$", "", name) for name, _, _ in calls) \
        == sorted(["delta_rule_bwd", "delta_solve_bwd", "delta_strict_bwd",
                   "mamba_conv_bwd"]
                  + ["delta_rule_fwd", "delta_solve_fwd", "delta_strict_fwd"]
                  * forwards + ["mamba_conv_fwd"] * 2)

    def operand_bytes(name):
        shape = re.search(rf"%{re.escape(name)} = (\w+)\[([\d,]*)\]", hlo)
        return np.dtype(shape.group(1).replace("bf16", "float16")
                        .replace("f32", "float32")).itemsize * int(np.prod(
            [int(d) for d in shape.group(2).split(",") if d]))

    forms = {"delta_solve": [], "delta_strict": [], "delta_rule": []}
    for name, line, op_name in calls:
        assert not re.match(r"flash_|map_mixer_", name)
        if name.startswith("mamba_conv"):
            assert scope_key(op_name) == "body/gated_delta/conv", op_name
            continue
        assert scope_key(op_name) == "body/gated_delta/delta_rule", op_name
        assert "gated_delta_0/delta_rule/" in op_name, op_name
        # nothing laid out again but the float32 [1, 30, 8192] rows of gamma
        # (``copy-done`` is XLA's move between memory spaces, one layout)
        for copied in re.findall(r"%((?:copy|transpose)(?:\.\d+)?)(?![\w.-])",
                                 line.split("custom-call(")[1]):
            assert operand_bytes(copied) <= 30 * 8192 * 4, (name, copied)
        if name.startswith(("delta_solve", "delta_strict")):
            assert re.search(r"gated_delta_0/delta_rule/.*solve/", op_name), \
                op_name
        if name.startswith("delta_solve"):
            # 128 chunks x 30 heads x 64 rows
            assert line.startswith("f32[245760,64]{1,0"), line
        elif name.startswith("delta_strict_fwd"):
            assert line.startswith("f32[1,128,30,64,64]{4,3,2,1,0"), line
        elif name.startswith("delta_rule_fwd"):
            # o^T and the entering states of every chunk and head
            assert line.startswith("(bf16[1,5760,8192]{2,1,0") \
                and "bf16[1,128,30,192,96]{4,3,2,1,0" in line, line
        forms[name[:name.index("_", 6)]].append((
            name.split(".")[0].endswith("bwd"),
            "rematted_computation" in op_name,
            "/transpose(jvp(" in op_name))
    # the step's forward; the block's replay and the backward, both inside
    # the transposed program
    for kernel, seen in forms.items():
        assert sorted(seen) == sorted(
            [(False, False, False), (True, False, True)]
            + [(False, True, True)] * (forwards - 1)), (kernel, seen)


def saved_flash_outputs_keep_their_scope_test(v5e, monkeypatch):
    """The cell's full-attention layer (``harness.py
    saved_flash_outputs_keep_their_scope``)."""
    harness.saved_flash_outputs_keep_their_scope(
        v5e, monkeypatch, "train_olmo_hybrid_7b_long",
        "attention-nope-qk_norm", "body/attention")
