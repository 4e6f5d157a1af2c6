"""What every recurrent layer's Pallas kernels are held to in a step, a row a
kernel (PR 60; until then a copy in each kernel's file): the toy step under
``jax.checkpoint`` + ``jax.grad`` with the kernels (interpreted) against the
XLA form's loss and gradients, and bfloat16 operands rounding no lower than
the XLA form's.  The XLA form's loss and gradients are computed once a
``(toy, dtype)`` and shared by the rows on that toy."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from homebrewnlp_tpu.model import gated_delta as delta_mod
from homebrewnlp_tpu.model import kda as kda_mod
from homebrewnlp_tpu.model import mamba as mamba_mod
from homebrewnlp_tpu.model import recurrent
from homebrewnlp_tpu.parallel import delta_rule as dr
from homebrewnlp_tpu.parallel import delta_solve as ds
from homebrewnlp_tpu.parallel import kda_rule as kr

import delta_rule_test as delta_t
import granite_test
import kda_rule_kernel_test as kda_t
import kimi_linear_test
import olmo_hybrid_test
import ssd_scan_test as ssd_t
import causal_conv_test as conv_t
from causal_conv_test import tiles  # noqa: F401  (a fixture)


# granite's ONE short period that holds each layer kind of its twenty
# blocks, and a second ``mamba`` so that "one trace of the kernel for them
# all" has something to count (the published pattern: ``granite_test.py``'s
# ``twenty_blocks_at_depth_two_are_forty_scopes_test``).  4 heads x 32 = 128
# channels of x, + 2 x 64 of B and C = 256 for the conv, read from channel 128
# of proj on; two sequence tiles of 128
_GRANITE = {"mamba_head_features": 32, "mamba_state": 64,
            "sequence_length": 256, "block_config": granite_test.SHORT}
_TOYS = {
    "granite": lambda dtype: granite_test._build(dtype, **_GRANITE),
    "olmo_hybrid": lambda dtype: olmo_hybrid_test._build(
        dtype, **delta_t._WIDE,
        block_config=olmo_hybrid_test._ONE["gated_delta"]),
    "olmo_hybrid_wide": lambda dtype: olmo_hybrid_test._build(
        dtype, delta_key_features=32, delta_value_features=64,
        sequence_length=128, delta_chunk=32, train_batch_size=1,
        block_config=olmo_hybrid_test._ONE["gated_delta"]),
    # 2 x 16 chunks x 4 heads of 16 x 16: one tile of the solve's pair
    "olmo_hybrid_long": lambda dtype: olmo_hybrid_test._build(
        dtype, sequence_length=256, delta_heads=4,
        block_config=olmo_hybrid_test._ONE["gated_delta"]),
    "kimi_linear": lambda dtype: kimi_linear_test._build(dtype,
                                                         **kda_t._WIDE)}
_XLA_FORM = {}


def _xla_form(toy: str, dtype: str):
    """``(params, model, batch, variables, loss, gradients)`` of a toy with no
    kernel steered: one build and one compile a ``(toy, dtype)``."""
    if (toy, dtype) not in _XLA_FORM:
        _, params, model, batch, variables = _TOYS[toy](dtype)
        assert params.memory_reduction_strategy == "checkpoint"
        _XLA_FORM[toy, dtype] = (params, model, batch, variables,
                                 *harness.loss_and_grads(model, variables,
                                                         batch))
    return _XLA_FORM[toy, dtype]


def _conv(monkeypatch, request, params):
    assert recurrent.conv_kernel_layers(params, "tpu") == 2
    assert recurrent.conv_kernel_layers(params) == 0
    conv_t.steer_to_the_kernel(monkeypatch, request.getfixturevalue("tiles"))
    # one jitted kernel call a layer, one trace of the kernel for them all
    return {"name=_fwd_impl": 2, "mamba_conv_fwd": None}


def _ssd_scan(monkeypatch, request, params):
    harness.steer_mamba_scan(monkeypatch, heads_a_block=2)
    return {"ssd_scan_fwd": None, "intra_chunk": 0}


def _delta_rule(monkeypatch, request, params):
    assert recurrent.rule_kernel_layers(params, "tpu") == 1
    harness.steer(monkeypatch, delta_mod, rule_kernel_applies=functools.partial(
        dr.rule_kernel_applies, backend="tpu"))
    delta_t.steer_interpreted(monkeypatch)
    # the pair where ``lax.map`` over groups stood: traced once, the
    # ``jax.jit`` around it
    return {"name=_fwd_impl": 1, "delta_rule_fwd": None}


def _kda(monkeypatch, request, params):
    assert recurrent.rule_kernel_layers(params, "tpu") == 1
    harness.steer(monkeypatch, kda_mod, kda_kernel_applies=functools.partial(
        kr.kda_kernel_applies, backend="tpu"))
    kda_t.steer_interpreted(monkeypatch)
    return {"name=_fwd_impl": 1, "name=_scores_fwd_impl": 1,
            "kda_rule_fwd": None, "kda_scores_fwd": None}


def _delta_conv(monkeypatch, request, params):
    """``gated_delta``'s bias-free conv over the conv's kernel pair."""
    conv_t.steer_to_the_kernel(monkeypatch, module=delta_mod)
    return {"name=_fwd_impl": 1, "mamba_conv_fwd": None}


def _delta_solve(monkeypatch, request, params):
    """The rule's triangular solve: the Pallas forward where the blocked
    form stood — once, the ``jax.jit`` around it."""
    assert recurrent.solve_kernel_layers(params, "tpu") == 1
    harness.steer(monkeypatch, delta_mod, solve_kernel_applies=functools.partial(
        ds.solve_kernel_applies, backend="tpu"))
    harness.steer_interpreted(monkeypatch, delta_mod, ds, "inverse_unit_lower",
                              "inverse_unit_lower_bwd")
    return {"name=_fwd_impl": 1, "delta_solve_fwd": None}


_BOTH = {"float32": 2e-5, "bfloat16": 2.0 ** -5}
_FLOAT32 = {"float32": 2e-5}
#: kernel -> (the toy it runs in, how the layer is steered to it, the
#: calculation dtypes it is held in with their tolerances)
STEPS = {"conv": ("granite", _conv, _BOTH),
         "ssd_scan": ("granite", _ssd_scan, _BOTH),
         "delta_rule": ("olmo_hybrid", _delta_rule, _BOTH),
         "kda": ("kimi_linear", _kda, _BOTH),
         "delta_conv": ("olmo_hybrid_wide", _delta_conv, _FLOAT32),
         "delta_solve": ("olmo_hybrid_long", _delta_solve, _FLOAT32)}


@pytest.mark.parametrize("kernel,dtype", [
    (kernel, dtype) for kernel, row in STEPS.items() for dtype in row[2]])
def step_with_the_kernel_test(monkeypatch, request, kernel, dtype):
    """The toy step under ``jax.checkpoint`` + ``jax.grad`` as a TPU process
    at kernel shapes traces it: the layer's jaxpr carries the kernels — as
    often as the row says; None: at all — and loss, every gradient (and with
    them the watches the loss's aux carries) equal the XLA form's."""
    toy, steer, tolerances = STEPS[kernel]
    tolerance = tolerances[dtype]
    params, model, batch, variables, want_loss, want = _xla_form(toy, dtype)
    counts = steer(monkeypatch, request, params)
    text = harness.step_jaxpr(model, variables, batch)
    for needle, count in counts.items():
        assert (needle in text) if count is None \
            else text.count(needle) == count, needle
    loss, got = harness.loss_and_grads(model, variables, batch)
    assert abs(float(loss) - float(want_loss)) <= tolerance
    harness.assert_close_tree(got, want, tolerance)


# ---- bfloat16 operands --------------------------------------------------------

def _off(got, exact):
    return [float(np.max(np.abs(np.asarray(g, np.float64) - w))
                  / np.max(np.abs(w)))
            for g, w in zip(got, (np.asarray(t, np.float64) for t in exact))]


def _ssd_scan_rounding(monkeypatch):
    """Against the XLA form in float32, never past the bound
    ``scripts/kernel_parity.py`` holds the pair to on the chip — the
    log-decay's gradient, a difference of sums that cancel, included."""
    forms = {"kernel": functools.partial(ssd_t._kernel, chunk=32,
                                         heads_a_block=2),
             "xla": functools.partial(mamba_mod.ssd_xla, chunk=32)}
    compiled = {}

    def draw(seed):
        inputs, weights = ssd_t._inputs(64, 4, 0.5, p=16, n=32,
                                        dtype=jnp.bfloat16, seed=seed)
        exact, _ = ssd_t._value_and_grads(
            forms["xla"], tuple(t.astype(jnp.float32) for t in inputs),
            weights, compiled)
        return exact, {
            name: ssd_t._value_and_grads(fn, inputs, weights, compiled)[0]
            for name, fn in forms.items()}

    return 6, draw, 2.0 ** -6


def _delta_rule_rounding(monkeypatch):
    """Against the recurrence in float64."""
    delta_t.steer_interpreted(monkeypatch, 2)
    compiled = {}

    def draw(seed):
        inputs, weights = delta_t._inputs(128, 3, 0.3, dk=16, dv=32,
                                          dtype=jnp.bfloat16, seed=seed,
                                          batch=1)
        with jax.enable_x64(True):
            exact, _ = delta_t._value_and_grads(
                delta_t._recurrence_rule,
                tuple(jnp.asarray(np.asarray(t, np.float64)) for t in inputs),
                jnp.asarray(np.asarray(weights, np.float64)), 64, compiled)
            exact = [np.asarray(t) for t in exact]
        return exact, {
            name: delta_t._value_and_grads(rule, inputs, weights, 64,
                                           compiled)[0]
            for name, rule in (("kernel", delta_mod.kernel_rule),
                               ("xla", delta_mod.delta_rule))}

    return 6, draw, 2.0 ** -5


_KDA_XLA = kda_mod.normalised(kda_mod.kda_rule)


def _kda_rounding(monkeypatch):
    """Against the recurrence in float32."""
    kda_t.steer_interpreted(monkeypatch, 2)
    compiled = {}

    def draw(seed):
        inputs, weights = kda_t._inputs(128, 3, -0.3, dk=16, dv=32,
                                        dtype=jnp.bfloat16, seed=seed)
        exact, _ = kda_t._value_and_grads(
            kda_t.recurrence_rule(),
            tuple(t.astype(jnp.float32) for t in inputs), weights, 64,
            compiled)
        return exact, {
            name: kda_t._value_and_grads(rule, inputs, weights, 64,
                                         compiled)[0]
            for name, rule in (("kernel", kda_mod.kernel_rule),
                               ("xla", _KDA_XLA))}

    return 4, draw, 2.0 ** -4


@pytest.mark.parametrize("rounding", [
    _ssd_scan_rounding, _delta_rule_rounding, _kda_rounding],
    ids=["ssd_scan", "delta_rule", "kda"])
def pair_rounds_no_lower_than_the_xla_form_test(monkeypatch, rounding):
    """bfloat16 operands against the row's exact form: the kernels are, in
    the mean over the row's draws, no further off than the XLA form in
    bfloat16 (half as much again, for the rounding's luck) and never past
    the row's bound.  Each form is compiled once, for every draw."""
    draws, draw, bound = rounding(monkeypatch)
    off = {"kernel": [], "xla": []}
    for seed in range(draws):
        exact, got = draw(seed)
        for name in off:
            off[name].append(_off(got[name], exact))
    assert np.max(off["kernel"]) <= bound
    assert np.all(np.mean(off["kernel"], 0) <= 1.5 * np.mean(off["xla"], 0)), \
        (np.mean(off["kernel"], 0), np.mean(off["xla"], 0))
