"""A looped model through the normal path (ISSUE 49, Ouro-2.6B): the program
against the plain reference ``benchmark/reference/ouro_2_6b.py`` in every
pass's logits, the exit distribution, the loss and the gradients at tiny
widths; one set of parameters re-entered by every pass; the weighted walk of
the head loss; what the memory rule, the trace's scopes and the step's gauges
read of the passes; and what refuses a looped model by name."""
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
from homebrewnlp_tpu.config import ModelParameter
from homebrewnlp_tpu.model import Model, loop as loop_mod, loss as loss_mod
from homebrewnlp_tpu.model.remat import stash_line, stash_plan

TINY = {"depth": 2, "heads": 4, "features_per_head": 16,
        "sequence_length": 64, "train_batch_size": 2, "vocab_size": 384,
        "tpu_size": 1, "use_checkpointing": False, "slice_dtype": "float32",
        "model_path": "/tmp/ouro_test"}
GATE = "gpt0/loss0/exit_gate0/normal_var0/var0"
GATE_BIAS = "gpt0/loss0/exit_gate0/constant_var0/var0"


def _reference():
    return harness.reference("ouro_2_6b")


def _config(dtype="float32", **extra):
    return harness.config_of("ouro_2_6b", TINY, dtype, **extra)


def _batch(batch=2, seq=64):
    return harness.token_batch(batch, seq, seed=0)


def _lively(variables):
    """At normal(0.02) and 64 features the gate's logits are ~0.1: a gate
    twenty times as steep makes p differ a token, so a wrong product of the
    (1 - lambda)s cannot hide."""
    variables = {k: jnp.asarray(v) for k, v in variables.items()}
    if GATE in variables:
        variables[GATE] = variables[GATE] * 20.0
        variables[GATE_BIAS] = variables[GATE_BIAS] + 0.3
    return variables


def _build(dtype="float32", lively=True, **extra):
    return harness.build(
        _config(dtype, **extra), data_seed=0, init_seed=11,
        lively=_lively if lively else
        lambda v: {k: jnp.asarray(a) for k, a in v.items()})


def _sides(batch):
    return batch["token_x"][..., 0], batch["token_y"][..., 0]


# ---- the program against the reference ---------------------------------------

def _pass_logits(config, variables, batch, steps):
    """The logits after pass ``steps``: the LAST pass's of the same model
    with ``loop_steps = steps`` over the same weights (``token_out`` is the
    last pass's)."""
    params = ModelParameter(dict(config, loop_steps=steps))
    model = Model(params)
    model.init(batch, seed=11)
    return harness.logits_and_loss(model, variables, batch)[0]


@pytest.mark.parametrize("dtype,tolerance,loss_tolerance", [
    # float32 against float32: only the order of sums differs, so this pins
    # the EQUATIONS: a norm on the wrong side of the mixer, the final norm
    # left out between passes, a gate read before the norm or a p_t missing
    # one (1 - lambda) are off by orders of magnitude more
    ("float32", 2e-5, 2e-5),
    # the configuration's bfloat16: the stream passes four times through
    # bfloat16 layers, and every pass ends in a norm that rescales what the
    # pass added; 2^-4 of the largest logit is the cell's bound on the chip,
    # which a float8 stream misses (the test below).  The loss is a float32
    # sum over bfloat16 logits: 2^-6
    ("bfloat16", 2 ** -4, 2 ** -6)])
def every_pass_matches_the_reference_test(dtype, tolerance, loss_tolerance):
    config, params, model, batch, variables = _build(dtype)
    tokens, targets = _sides(batch)
    want = _reference().outputs(variables, tokens, targets, config)
    for step in range(4):
        got = _pass_logits(config, variables, batch, step + 1)
        ref = np.asarray(want["logits"][step])
        err = np.max(np.abs(ref - got)) / np.max(np.abs(ref))
        assert err <= tolerance, (dtype, step, err)
    info = jax.jit(lambda v, b: model.apply(v, b, layer_stats=True))(
        variables, batch)
    assert abs(float(info.total_loss.data) - float(want["loss"])) \
        <= loss_tolerance
    stats = info.layer_stats
    np.testing.assert_allclose(
        np.asarray(stats["loop_pass_loss"]),
        np.asarray(jnp.mean(want["token_loss"], axis=(1, 2))),
        atol=loss_tolerance)
    np.testing.assert_allclose(
        np.asarray(stats["loop_exit_share"]),
        np.asarray(jnp.mean(want["p"], axis=(1, 2))), atol=loss_tolerance)
    np.testing.assert_allclose(
        float(stats["loop_exit_entropy"][0]),
        float(jnp.mean(want["entropy"])), atol=loss_tolerance)
    # the lively gate: the shares are not the zero gate's
    assert np.max(np.abs(np.asarray(stats["loop_exit_share"])
                         - [0.5, 0.25, 0.125, 0.125])) > 0.02


def gradients_match_the_reference_test():
    """Value and every parameter's gradient against ``jax.grad`` of the
    reference's ``train_loss`` — through the weighted walk's hand-written
    backward, the gate (which receives the token losses as the gradient of
    its weights) and four re-entries of every block."""
    config, params, model, batch, variables = _build()
    tokens, targets = _sides(batch)
    ref = _reference()
    got_loss, got = harness.loss_and_grads(model, variables, batch)
    want_loss, want = harness.reference_loss_and_grads(ref, variables, tokens,
                                                       targets, config)
    assert abs(float(got_loss) - float(want_loss)) <= 2e-5
    # float32 both sides, sums in another order: 1e-4 of the parameter's
    # largest gradient
    harness.assert_grads_match(got, want, 1e-4, alive=True)


def a_shared_gradient_is_the_sum_of_the_passes_test():
    """Give every pass a copy of the weights of its own (the reference's
    ``pass_variables``): the program's gradient of a shared parameter is the
    SUM of the four copies' gradients, and no copy's alone."""
    config, params, model, batch, variables = _build()
    tokens, targets = _sides(batch)
    ref = _reference()
    _, got = harness.loss_and_grads(model, variables, batch)
    body = {k: v for k, v in variables.items()
            if "/body0/" in k or "/lang_out0_0/" in k}
    copies = jax.grad(lambda own: ref.train_loss(
        variables, tokens, targets, config,
        pass_variables=[{**variables, **c} for c in own]))([body] * 4)
    for name in sorted(body):
        total = sum(c[name] for c in copies)
        scale = float(jnp.max(jnp.abs(total)))
        assert float(jnp.max(jnp.abs(got[name] - total))) <= 1e-4 * scale, name
        for c in copies:
            assert float(jnp.max(jnp.abs(got[name] - c[name]))) \
                > 1e-2 * scale, name


def a_lower_precision_fails_the_bound_test():
    """The bound on the chip lies between its two readings: the bfloat16
    program passes 2^-4 (above); the reference with its stream rounded to
    float8 (e4m3, 3 bits of mantissa) after every block and pass misses it,
    and its loss misses the program's loss bound too."""
    config, params, model, batch, variables = _build("bfloat16")
    tokens, targets = _sides(batch)
    ref = _reference()
    want = ref.outputs(variables, tokens, targets, config)
    lower = ref.outputs(variables, tokens, targets, config,
                        stream_dtype=jnp.float8_e4m3fn)
    exact, rounded = (np.asarray(o["logits"][-1]) for o in (want, lower))
    err = np.max(np.abs(exact - rounded)) / np.max(np.abs(exact))
    assert err > 2 ** -4, err
    # and a bfloat16 stream stays inside it
    mid = np.asarray(ref.forward(variables, tokens, config,
                                 stream_dtype=jnp.bfloat16))
    assert np.max(np.abs(exact - mid)) / np.max(np.abs(exact)) <= 2 ** -4


# ---- the exit distribution -----------------------------------------------------

def the_exit_distribution_sums_to_one_test():
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(0, 3, (3, 5, 7)), jnp.float32)
    p = jnp.exp(loop_mod.exit_log_distribution(logits))
    assert p.shape == (4, 5, 7)
    np.testing.assert_allclose(np.asarray(jnp.sum(p, axis=0)), 1.0,
                               atol=1e-6)
    lam = jax.nn.sigmoid(logits)
    # (the plain products lose digits where 1 - lambda is small: absolute)
    np.testing.assert_allclose(np.asarray(p[1]),
                               np.asarray(lam[1] * (1 - lam[0])), atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(p[3]), np.asarray(jnp.prod(1 - lam, axis=0)), atol=1e-6)
    zero = jnp.exp(loop_mod.exit_log_distribution(jnp.zeros((3, 2))))
    np.testing.assert_allclose(np.asarray(zero[:, 0]),
                               [0.5, 0.25, 0.125, 0.125], rtol=1e-6)
    # a gate that saturates loses nothing to a log of 0
    far = loop_mod.exit_log_distribution(jnp.full((3, 1), 80.0))
    assert np.all(np.isfinite(np.asarray(far)))
    two = jnp.exp(loop_mod.exit_log_distribution(jnp.zeros((1, 2))))
    np.testing.assert_allclose(np.asarray(two[:, 0]), [0.5, 0.5])


def a_zero_gate_weighs_the_passes_by_halves_test():
    """With the gate's weights and bias at 0 every token's p is (1/2, 1/4,
    1/8, 1/8), H(p) = 1.75 ln 2, and the loss is the passes' mean
    cross-entropies weighed so, less beta H."""
    config, params, model, batch, variables = _build(lively=False)
    variables = {**variables, GATE: jnp.zeros_like(variables[GATE])}
    # op by op: compiled as one program the entropy's sum rounds 1e-6 apart
    info = model.apply(variables, batch, layer_stats=True)
    stats = info.layer_stats
    np.testing.assert_allclose(np.asarray(stats["loop_exit_share"]),
                               [0.5, 0.25, 0.125, 0.125], rtol=1e-6)
    entropy = 1.75 * np.log(2.0)
    assert float(stats["loop_exit_entropy"][0]) == pytest.approx(entropy,
                                                                 rel=1e-6)
    want = float(np.dot(np.asarray(stats["loop_pass_loss"]),
                        [0.5, 0.25, 0.125, 0.125])) - 0.1 * entropy
    assert float(info.total_loss.data) == pytest.approx(want, abs=1e-5)
    assert float(info.token_loss.data) == pytest.approx(
        want + 0.1 * entropy, abs=1e-5)


# ---- the weighted walk -----------------------------------------------------------

def _walk_inputs(b=2, s=32, v=96, dtype=jnp.float32):
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(0, 1, (b, s, 4, 8)), dtype)
    w = jnp.asarray(rng.normal(0, 0.2, (4, 8, 1, v)), dtype)
    targets = jnp.asarray(rng.integers(0, v, (b, s, 1)), jnp.int32)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, (b, s, 1)), jnp.float32)
    return x, w, targets, weights


@pytest.mark.parametrize("z_loss", [0.0, 1e-3])
@pytest.mark.parametrize("chunk_bytes", [1 << 29, 2 * 8 * 96 * 4])
def the_weighted_walk_is_head_xent_at_uniform_weights_test(monkeypatch,
                                                           z_loss,
                                                           chunk_bytes):
    """``head_xent_tokens`` with every weight ``1 / count``: the loss and
    both gradients of ``head_xent``, in one chunk and in four."""
    monkeypatch.setattr(loss_mod, "CHUNK_BYTES", chunk_bytes)
    x, w, targets, _ = _walk_inputs()
    uniform = jnp.full(targets.shape, 1.0 / targets.size, jnp.float32)
    want, (wx, ww) = jax.value_and_grad(
        lambda x_, w_: loss_mod.head_xent(x_, w_, targets, z_loss),
        argnums=(0, 1))(x, w)
    got, (gx, gw) = jax.value_and_grad(
        lambda x_, w_: loss_mod.head_xent_tokens(x_, w_, targets, uniform,
                                                 z_loss)[0],
        argnums=(0, 1))(x, w)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(wx), atol=1e-7)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(ww), atol=1e-7)


@pytest.mark.parametrize("chunk_bytes", [1 << 29, 2 * 8 * 96 * 4])
def the_weighted_walk_matches_autodiff_test(monkeypatch, chunk_bytes):
    """Arbitrary weights: the token losses, the weighted sum and its
    gradients by ``x``, ``w`` AND the weights (the token losses themselves)
    against plain autodiff of the unchunked form."""
    monkeypatch.setattr(loss_mod, "CHUNK_BYTES", chunk_bytes)
    x, w, targets, weights = _walk_inputs()

    def plain(x_, w_, weights_):
        logits = jnp.einsum("bshk,hkpv->bspv", x_, w_)
        log_z = jax.scipy.special.logsumexp(logits, axis=-1)
        token = log_z - jnp.take_along_axis(
            logits, targets[..., None], axis=-1)[..., 0] \
            + 1e-3 * jnp.square(log_z)
        return jnp.sum(weights_ * token), token

    (want, want_token), want_grads = jax.value_and_grad(
        plain, argnums=(0, 1, 2), has_aux=True)(x, w, weights)
    (got, got_token), got_grads = jax.value_and_grad(
        lambda *a: loss_mod.head_xent_tokens(a[0], a[1], targets, a[2], 1e-3),
        argnums=(0, 1, 2), has_aux=True)(x, w, weights)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_allclose(np.asarray(got_token),
                               np.asarray(want_token), atol=1e-5)
    for g, wanted in zip(got_grads, want_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wanted),
                                   atol=2e-6)


def the_weighted_walk_holds_no_tokens_by_vocabulary_array_test(monkeypatch):
    """Four chunks: neither the walk nor its gradient holds an array of all
    tokens by the vocabulary — the widest is one chunk's."""
    monkeypatch.setattr(loss_mod, "CHUNK_BYTES", 2 * 8 * 96 * 4)
    x, w, targets, weights = _walk_inputs()
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda x_, w_, ws: loss_mod.head_xent_tokens(x_, w_, targets, ws,
                                                     0.0)[0],
        argnums=(0, 1, 2)))(x, w, weights)
    sizes = [int(np.prod(v.aval.shape)) for v in _all_vars(jaxpr.jaxpr)
             if getattr(v.aval, "shape", None) and v.aval.shape[-1] == 96]
    assert sizes and max(sizes) <= max(2 * 8 * 96, 4 * 8 * 96)  # a chunk, w


def _all_vars(jaxpr):
    for eqn in jaxpr.eqns:
        yield from eqn.outvars
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                yield from _all_vars(inner)


# ---- one plan, one set of parameters --------------------------------------------

def init_makes_each_parameter_once_test():
    """Four passes, one plan: the looped model's parameters are the
    un-looped model's plus the gate's two, its plan is the un-looped plan,
    and every value is made once."""
    prev = telemetry.set_registry(telemetry.Registry())
    try:
        config, params, model, batch, variables = _build(lively=False)
        made = telemetry.snapshot()["hbnlp_init_values_total"]["series"][()]
    finally:
        telemetry.set_registry(prev)
    plain_params = ModelParameter(dict(config, loop_steps=1))
    plain = Model(plain_params)
    plain_vars = plain.init(batch, seed=11)
    assert set(variables) == set(plain_vars) | {GATE, GATE_BIAS}
    assert made == len(variables)
    assert model.plan == plain.plan and len(model.plan) == 4
    for name, value in plain_vars.items():
        np.testing.assert_array_equal(np.asarray(variables[name]), value)
    assert variables[GATE].shape == (4, 16) and variables[GATE_BIAS].shape == ()


def loop_steps_one_is_the_unlooped_models_jaxpr_test():
    """``loop_steps`` 1 (the default) traces what a configuration without
    the key traces: value, gradient and every equation."""
    config = _config()
    config.pop("loop_steps"), config.pop("loop_exit_entropy")
    batch = _batch()
    texts = []
    for extra in ({}, {"loop_steps": 1, "loop_exit_entropy": 0.5}):
        params = ModelParameter(dict(config, **extra))
        model = Model(params)
        variables = model.init(batch, seed=11)
        texts.append(str(jax.make_jaxpr(jax.value_and_grad(
            lambda v: model.apply(v, batch).total_loss.data))(variables)))
        assert GATE not in variables
    assert texts[0] == texts[1]
    assert "loop" not in texts[0]


@pytest.mark.parametrize("strategy,scan", [("checkpoint", False),
                                           ("none", False),
                                           ("checkpoint", True)])
def every_form_of_the_body_is_the_same_model_test(strategy, scan):
    """``checkpoint`` and ``none``, unrolled and scanned over depth inside
    each pass: the loss and the gradients of the cell's form."""
    _, _, model, batch, variables = _build()
    want_loss, want = harness.loss_and_grads(model, variables, batch)
    _, _, other, _, _ = _build(memory_reduction_strategy=strategy,
                               scan_layers=scan)
    got_loss, got = harness.loss_and_grads(other, variables, batch)
    assert float(got_loss) == pytest.approx(float(want_loss), abs=1e-6)
    for name in want:
        np.testing.assert_allclose(np.asarray(got[name]),
                                   np.asarray(want[name]), atol=1e-6)


def a_pass_is_a_checkpoint_region_a_block_test():
    """The gradient's jaxpr holds loop_steps x depth x 2 checkpoint regions:
    every pass re-enters every block as a region of its own."""
    _, _, model, batch, variables = _build()
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda v: model.apply(v, batch).total_loss.data))(variables)
    text = str(jaxpr)
    assert len(re.findall(r"\bcheckpoint\[", text)) \
        + len(re.findall(r"\bremat2?\[", text)) >= 16


# ---- the memory rule --------------------------------------------------------------

def the_attention_kind_counts_executions_test():
    """Where a layer runs four times a step its flash call leaves four
    ``(out, lse)``: the plan's count and bytes are the un-looped model's
    times ``loop_steps``, the rule decides over those bytes, and the line
    says executions."""
    extra = {"sequence_length": 128, "remat_policy": "stash"}
    looped = ModelParameter(_config(**extra))
    plain = ModelParameter(_config(**extra, loop_steps=1))
    count, nbytes = stash_plan(plain)["attention"]
    assert count == 2 and nbytes > 0
    assert stash_plan(looped)["attention"] == (4 * count, 4 * nbytes)
    line = stash_line(stash_plan(looped), True)
    assert line.startswith(f"remat stash: attention 8 executions, "
                           f"{4 * nbytes} bytes a device; bottleneck 0 "
                           "executions")
    assert stash_line(stash_plan(plain)).startswith(
        f"remat stash: attention 2 layers, {nbytes} bytes a device")


def the_rule_decides_over_every_execution_test(monkeypatch):
    """The cell's shapes: 12 layers x 4 passes of 34 MB ride 15% of a 16 GB
    chip; at 24 layers the same rule declines, where the un-looped model of
    that depth would still ride."""
    from benchmark.lib.cell import load_cell
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    config = dict(load_cell("train_ouro_2_6b_loop4_s4k").model_config(),
                  model_path="/tmp/ouro_test")
    per_call = 2 * 4096 * 16 * 128 * 2 + 2 * 16 * 4096 * 4
    assert stash_plan(ModelParameter(config))["attention"] \
        == (48, 48 * per_call)
    assert stash_plan(ModelParameter(dict(config, depth=24)))["attention"] \
        == (0, 0)
    assert stash_plan(ModelParameter(dict(config, depth=24, loop_steps=1))
                      )["attention"] == (24, 24 * per_call)


# ---- scopes and gauges ------------------------------------------------------------

def the_scopes_fold_test():
    """Every pass is a region ``loop/pass<t>`` of the compiled program's
    ops; the blocks inside still fold to ``body/<layer>``, the final norm to
    ``output``, the gate to ``exit_gate`` and the walk to ``head_loss``."""
    _, _, model, batch, variables = _build()
    names = harness.traced_op_names(model, variables, batch)
    for step in range(4):
        inside = [n for n in names if f"loop/pass{step}/" in n]
        assert inside, step
        folded = {scope_key(n) for n in inside}
        assert {"body/attention", "body/mlp", "body/norm", "output"} \
            <= folded, (step, folded)
        assert "unscoped" not in folded
    assert not [n for n in names if "loop/pass4" in n]
    assert {scope_key(n) for n in names if "exit_gate" in n} == {"exit_gate"}
    assert [n for n in names if scope_key(n) == "head_loss"]
    for path, scope in [
            ("jit(step_fn)/transpose(jvp(gpt0))/loop/pass2/body0/"
             "checkpoint/block1_0_0/attention_0/dot_general", "body/attention"),
            ("jit(step_fn)/jvp(gpt0)/loop/pass3/output0/lang_out0_0/norm_0/"
             "mul", "output"),
            ("jit(step_fn)/jvp(gpt0)/loss0/exit_gate0/reduce_sum",
             "exit_gate"),
            ("jit(step_fn)/jvp(gpt0)/loss0/head_loss/while/body/dot_general",
             "head_loss")]:
        assert scope_key(path) == scope


def the_step_reports_the_gauges_test():
    """``Trainer.step`` under ``telemetry_enabled``: the metrics hold a
    cross-entropy and an exit share a pass and the entropy; the registry
    gets ``hbnlp_loop_pass_loss{pass}``, ``hbnlp_loop_exit_share{pass}`` and
    ``hbnlp_loop_exit_entropy`` once a step has finished; the loss falls."""
    from homebrewnlp_tpu.train import Trainer
    prev = telemetry.set_registry(telemetry.Registry())
    try:
        config, params, model, batch, _ = _build(
            telemetry_enabled=True, learning_rate=0.01,
            learning_rate_config={})
        trainer = Trainer(params, model)
        state = trainer.init_state(batch)
        line = trainer.publish_stash_plan()
        losses = []
        for _ in range(8):
            state, metrics = trainer.step(state, batch)
            losses.append(float(metrics["loss"]))
        jax.block_until_ready(metrics)
        state, metrics = trainer.step(state, batch)
        snap = telemetry.snapshot()
    finally:
        telemetry.set_registry(prev)
    assert losses[-1] < losses[0] - 0.05
    assert {f"{name}/{i}" for name in ("loop_pass_loss", "loop_exit_share")
            for i in range(4)} | {"loop_exit_entropy"} <= set(metrics)
    assert "executions" in line
    for metric in ("hbnlp_loop_pass_loss", "hbnlp_loop_exit_share"):
        assert set(snap[metric]["series"]) == {(str(i),) for i in range(4)}
    shares = [snap["hbnlp_loop_exit_share"]["series"][(str(i),)]
              for i in range(4)]
    assert sum(shares) == pytest.approx(1.0, abs=1e-5)
    assert 0 < snap["hbnlp_loop_exit_entropy"]["series"][()] <= np.log(4) + 1e-6
    assert snap["hbnlp_remat_stash_layers"]["series"][("attention",)] == 0


# ---- what refuses a looped model --------------------------------------------------

@pytest.mark.parametrize("extra,match", [
    ({"memory_reduction_strategy": "revnet"}, "revnet"),
    ({"memory_reduction_strategy": "momentum"}, "momentum"),
    ({"calc_accuracy": True}, "calc_accuracy"),
    ({"multi_loss_strategy": "pcgrad"}, "pcgrad"),
    ({"contrastive_across_samples": True}, "contrastive"),
    ({"pipeline_stages": 2, "tpu_size": 2, "heads": 1,
      "features_per_head": 64}, "pipeline"),
    ({"loop_steps": 0}, "loop_steps 0"),
    ({"loop_steps": 2.5}, "loop_steps 2.5"),
    ({"loop_steps": True}, "loop_steps True"),
    ({"loop_exit_entropy": -0.1}, "loop_exit_entropy -0.1"),
    ({"loop_exit_entropy": "high"}, "loop_exit_entropy 'high'")])
def bad_keys_and_modes_refuse_by_name_test(extra, match):
    with pytest.raises(ValueError, match=match):
        ModelParameter(_config(**extra))


def decode_and_prefill_refuse_by_name_test():
    _, params, model, batch, variables = _build()
    with pytest.raises(NotImplementedError, match="loop_steps 4"):
        model.apply_decode(variables, batch["token_x"][:, :1], 0, {})
    with pytest.raises(NotImplementedError, match="loop_steps 4"):
        model.apply_prefill(variables, batch["token_x"], 3)
    # an un-looped entropy key alone refuses nothing
    ModelParameter(_config(loop_steps=1, loop_exit_entropy=-1,
                           memory_reduction_strategy="revnet"))


def a_data_and_model_mesh_runs_the_loop_test():
    """The flagship's layout, batch on ``data`` and heads on ``model``: the
    looped loss and its gradients are the one-device ones."""
    from homebrewnlp_tpu.core import sharding as shardlib
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    config, params, model, batch, variables = _build(
        mesh_shape_override={"data": 2, "model": 2}, tpu_size=4)
    mesh = shardlib.build_mesh(params, jax.devices()[:4])
    want_loss, want = harness.loss_and_grads(model, variables, batch)
    placed = shardlib.shard_params(params, variables, model.param_dims, mesh)
    placed_batch = shardlib.shard_batch(params, batch, mesh)
    got_loss, got = jax.jit(jax.value_and_grad(
        lambda v, b: model.apply(v, b, mesh=mesh).total_loss.data))(
        placed, placed_batch)
    assert float(got_loss) == pytest.approx(float(want_loss), abs=1e-5)
    for name in want:
        np.testing.assert_allclose(np.asarray(got[name]),
                                   np.asarray(want[name]), atol=1e-5)


# ---- the configuration files -------------------------------------------------------

def the_repos_config_is_the_published_one_test():
    """``configs/ouro_2_6b.json`` against the published ``config.json`` as
    ``benchmark/configs/ouro_2_6b.json`` copies it, key for key."""
    with open(os.path.join(REPO, "configs", "ouro_2_6b.json")) as f:
        config = json.load(f)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "ouro_2_6b.json")) as f:
        doc = json.load(f)
    published = {**{k: v for k, v in doc.items()
                    if not isinstance(v, dict)}, **doc["published"]}
    params = ModelParameter(dict(config, model_path="/tmp/ouro_test"))
    assert not params.unknown_config_keys
    hidden = config["heads"] * config["features_per_head"]
    assert published["model_type"] == "ouro"
    assert hidden == published["hidden_size"] == 2048
    assert config["heads"] == published["num_attention_heads"] \
        == published["num_key_value_heads"] and params.query_group == 1
    assert config["features_per_head"] == published["head_dim"]
    assert config["intermediate_feed_forward_multiplier"] * hidden \
        == published["intermediate_size"] == 5632
    assert config["depth"] == published["num_hidden_layers"] == 48 \
        == len(published["layer_types"]) == published["max_window_layers"]
    assert set(published["layer_types"]) == {"full_attention"}
    assert config["sequence_length"] \
        == published["max_position_embeddings"] == 65536
    assert config["norm_epsilon"] == published["rms_norm_eps"] == 1e-6
    assert config["rope_theta"] == published["rope_theta"] == 1000000
    assert config["vocab_size"] == published["vocab_size"] == 49152
    assert config["loop_steps"] == published["total_ut_steps"] == 4
    assert published["hidden_act"] == "silu" and all(
        part["layer"][1] in ("attention-rope", "mlp-silu")
        and part["layer"][0] == part["layer"][2] == "norm-rms-scale"
        for part in config["block_config"])
    assert published["tie_word_embeddings"] is False \
        and not params.tie_word_embeddings
    assert published["use_sliding_window"] is False \
        and published["sliding_window"] is None \
        and published["rope_scaling"] is None
    # the cut: depth and length, nothing else
    assert doc["num_hidden_layers"] == doc["config"]["depth"] == 12
    assert doc["max_position_embeddings"] \
        == doc["config"]["sequence_length"] == 4096
    cut = {k for k in doc["config"] if doc["config"][k] != config.get(k)}
    assert cut == set(doc["overrides"]) == {
        "depth", "sequence_length", "train_batch_size", "tpu_size",
        "use_checkpointing", "telemetry_enabled"}
    assert set(doc["reduced"]) == {
        "num_hidden_layers", "depth", "max_position_embeddings",
        "sequence_length", "train_batch_size", "tpu_size"}
    assert {"sandwich_norm", "attention_bias", "norm_between_passes",
            "exit_gate", "loss", "loop_exit_entropy"} <= set(doc["assumed"])


def the_cells_parameters_test():
    """The cut's count, from the program's own shapes: 818.0 M."""
    from benchmark.lib.cell import load_cell
    from benchmark.roofline import ouro_costs
    config = load_cell("train_ouro_2_6b_loop4_s4k").model_config()
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert 12 * layer + 2 * 49152 * 2048 + 2048 + 2049 == 817_991_681
    # ISSUE 49's arithmetic: 119.6 MFLOP a layer application, 6.55 GFLOP a
    # token forward
    assert ouro_costs.layer_flops_per_token(config) / 1e6 \
        == pytest.approx(119.6, abs=0.1)
    assert ouro_costs.forward_flops_per_token(config) / 1e9 \
        == pytest.approx(6.55, abs=0.01)
    assert ouro_costs.train_flops_per_token(config) \
        == 3 * ouro_costs.forward_flops_per_token(config)


def dense_kind_leaves_the_cells_step_alone_test(monkeypatch):
    """PR 52: layer ``mlp`` offers its gate and up, and the cell admits none
    — 96 block inputs and 48 ``(out, lse)`` leave nothing of the 15% at the
    chip's own limit — so every one of the 96 regions (2 blocks x 12 x 4 passes)
    gets the parent's policy, the one object that saves the flash pair."""
    from homebrewnlp_tpu.model import remat
    from homebrewnlp_tpu.model.blocks import (_checkpoint_policy,
                                              _region_policies)
    from homebrewnlp_tpu.utils import flops
    from remat_policy_test import _cell_params
    monkeypatch.setattr(flops, "hbm_capacity",
                        lambda device=None: (16911433728, "memory_stats"))
    params = _cell_params("train_ouro_2_6b_loop4_s4k")
    # one MLP a period, 12 periods, 4 passes: 48 executions of 184.5 MB
    assert [o.nbytes for o in remat.offers(params, "dense")] == [184549376]
    assert params.depth * params.loop_steps == 48
    assert remat.stash_plan(params)["dense"] == (0, 0)
    assert remat.dense_executions(params) == 0
    regions = len(params.block_config) * params.depth * params.loop_steps
    assert regions == 96
    assert remat.stash_names(params) == ("flash_out", "flash_lse")
    assert remat.region_names(params) == [("flash_out", "flash_lse")] * 96
    policies = _region_policies(params)
    assert len(policies) == 96
    assert all(policy is _checkpoint_policy(params) for policy in policies)
