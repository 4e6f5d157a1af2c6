"""parallel/delta_rule.py: the Pallas pair of layer ``gated_delta``'s chunked
rule (interpret mode on the CPU) against the XLA form ``model/gated_delta.py
delta_rule`` and autodiff's gradients of it, against the recurrence run
position by position, the predicate that chooses between them, and the layer
with and without the kernels."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from homebrewnlp_tpu.model import gated_delta as delta_mod
from homebrewnlp_tpu.model import recurrent
from homebrewnlp_tpu.parallel import delta_rule as dr

import harness
from olmo_hybrid_test import _build, _reference


def _inputs(s, heads, decay, dk=8, dv=16, dtype=jnp.float32, batch=2, seed=0,
            beta_top=2.0):
    """Unit keys that share a direction (the triangular system is then far
    from the identity), ``beta`` over all of ``(0, beta_top)`` and at its top
    every fifth position, log-decays of about ``-decay`` a position."""
    rng = np.random.default_rng(seed)
    shared = rng.normal(size=(batch, 1, heads, dk))

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.normal(size=(batch, s, heads, dk)) + shared) * dk ** -0.5
    k = unit(rng.normal(size=(batch, s, heads, dk)) + 2 * shared)
    v = rng.normal(size=(batch, s, heads, dv))
    beta = rng.uniform(0.0, beta_top, size=(batch, s, heads))
    beta[:, ::5] = beta_top
    g = -decay * rng.uniform(0.5, 1.5, size=(batch, s, heads))
    weights = jnp.asarray(rng.normal(size=v.shape), jnp.float32)
    return (*(jnp.asarray(t, dtype) for t in (q, k, v)),
            *(jnp.asarray(t, jnp.float32) for t in (beta, g))), weights


def steer_interpreted(monkeypatch, heads_a_block=None):
    """``kernel_rule`` with both pairs interpreted, a head block of the
    caller's."""
    harness.steer_interpreted(monkeypatch, delta_mod, dr, "delta_rule_pair", "delta_strict",
                              heads_a_block=heads_a_block)


@pytest.fixture
def interpreted(monkeypatch):
    return functools.partial(steer_interpreted, monkeypatch)


_value_and_grads = harness.rule_value_and_grads
_close = functools.partial(harness.assert_close_each,
                           names="o dq dk dv dbeta dg".split())


# (sequence, chunk, heads, heads a block, decay a position): eight chunks a
# lane tile, two tiles, one block; two chunks a tile and a last block of one
# head; one chunk a tile, three tiles, a last block of two heads of three;
# four chunks a tile; a decay that underflows any product along a chunk
# (masked BEFORE exp); almost none; a whole sublane tile of heads a block
@pytest.mark.parametrize("s,chunk,heads,block,decay", [
    (256, 16, 3, 3, 0.05), (256, 64, 3, 2, 0.05), (384, 128, 5, 3, 0.05),
    (128, 32, 4, 4, 0.5), (256, 64, 2, 1, 6.0), (128, 64, 3, 2, 1e-4),
    (128, 16, 16, 8, 1.0)])
def pair_matches_the_xla_form_test(interpreted, s, chunk, heads, block,
                                   decay):
    """Output, ``max|T|`` and all five gradients (``beta`` up to 2) against
    autodiff through the XLA form over all heads at once."""
    inputs, weights = _inputs(s, heads, decay)
    interpreted(block)
    got, biggest = _value_and_grads(delta_mod.kernel_rule, inputs, weights,
                                    chunk)
    want, want_biggest = _value_and_grads(delta_mod.delta_rule, inputs,
                                          weights, chunk)
    np.testing.assert_allclose(biggest, want_biggest, rtol=1e-6)
    _close(got, want, 2e-5)


@pytest.mark.parametrize("s,chunk,heads,block,decay", [
    (256, 16, 3, 2, 0.05), (256, 64, 5, 2, 6.0), (128, 128, 8, 8, 0.5)])
def strict_pair_matches_the_xla_form_test(s, chunk, heads, block, decay):
    """The solve's input and its three gradients against autodiff through
    the XLA form's ``where(row > col, K K^T o Gamma, 0) diag(beta)``."""
    (_, k, _, beta, g), _ = _inputs(s, heads, decay)
    bsz, c = k.shape[0], s // chunk
    gamma = jnp.cumsum(g.reshape(bsz, c, chunk, heads), 2).reshape(g.shape)
    weights = jnp.asarray(np.random.default_rng(3).normal(
        size=(bsz, c, heads, chunk, chunk)), jnp.float32)

    def xla(k, gamma, beta):
        kc = k.reshape(bsz, c, chunk, heads, -1)
        gam, bet = (jnp.moveaxis(t.reshape(bsz, c, chunk, heads), 2, 3)
                    for t in (gamma, beta))
        row = jnp.arange(chunk)[:, None]
        col = jnp.arange(chunk)[None, :]
        decay = jnp.exp(jnp.where(
            row >= col, gam[..., :, None] - gam[..., None, :], -jnp.inf))
        return jnp.where(row > col, jnp.einsum(
            "bcihd,bcjhd->bchij", kc, kc) * decay, 0.0) * bet[..., :, None]

    def run(fn):
        out, vjp = jax.vjp(fn, k, gamma, beta)
        return (out, *vjp(weights))

    got = run(lambda *a: dr.delta_strict(*a, chunk, block, True))
    want = run(xla)
    for name, a, r in zip("strict dk dgamma dbeta".split(), got, want):
        a, r = np.asarray(a), np.asarray(r)
        assert a.shape == r.shape, name
        assert np.max(np.abs(a - r)) <= 2e-5 * max(np.max(np.abs(r)), 1e-3), \
            name
    assert not np.triu(np.asarray(got[0])).any()


@pytest.mark.parametrize("chunk,s,decay", [(16, 128, 0.05), (64, 128, 6.0),
                                           (128, 256, 0.05)])
def pair_is_the_recurrence_test(interpreted, chunk, s, decay):
    """``o`` and all five gradients against the recurrence of the module
    docstring run position by position (``lax.scan``'s own reverse mode)."""
    inputs, weights = _inputs(s, 3, decay, dk=4, dv=5)
    interpreted(2)
    got, _ = _value_and_grads(delta_mod.kernel_rule, inputs, weights, chunk)
    recurrence = _reference().recurrence
    want, _ = _value_and_grads(lambda *args: (recurrence(*args[:5]), 0.0),
                               inputs, weights, chunk)
    _close(got, want, 1e-4)


def _recurrence(q, k, v, beta, g):
    """The module docstring's recurrence in the inputs' own dtype (the
    reference's carries float32): ``S <- S' + beta (v - S' k) k^T`` with
    ``S' = exp(g) S``, ``o = S q``."""
    def step(state, inp):
        q_t, k_t, v_t, b_t, g_t = inp
        state = state * jnp.exp(g_t)[..., None, None]
        write = (v_t - jnp.einsum("bhvk,bhk->bhv", state, k_t)) \
            * b_t[..., None]
        state = state + jnp.einsum("bhv,bhk->bhvk", write, k_t)
        return state, jnp.einsum("bhvk,bhk->bhv", state, q_t)

    bsz, _, h, dk = q.shape
    _, o = jax.lax.scan(
        step, jnp.zeros((bsz, h, v.shape[-1], dk), q.dtype),
        tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, beta, g)))
    return jnp.moveaxis(o, 0, 1)


def _recurrence_rule(*args):
    """``_recurrence`` as a rule: what bfloat16 operands are held against,
    in float64 (``kernel_steps_test.py``)."""
    return _recurrence(*args[:5]), 0.0


@pytest.mark.parametrize(
    "chunk,heads,d_k,d_v,sequence,backend,takes", [
        (64, 30, 96, 192, 16384, "tpu", True),     # the published widths
        (16, 1, 16, 16, 128, "tpu", True),         # one tile, one head
        (128, 4, 128, 128, 256, "tpu", True),
        (64, 30, 96, 192, 16384, "cpu", False),
        (64, 30, 96, 192, 16384, "gpu", False),
        (48, 30, 96, 192, 16320, "tpu", False),    # no chunk the solve takes
        (256, 30, 96, 192, 16384, "tpu", False),   # beyond a lane tile
        (8, 30, 96, 192, 16384, "tpu", False),
        (64, 30, 96, 192, 16384 + 64, "tpu", False),    # no whole lane tiles
        (64, 3, 8, 16, 128, "tpu", False),         # the toy key width
        (64, 3, 16, 8, 128, "tpu", False),         # the toy value width
        (64, 0, 96, 192, 16384, "tpu", False),
        (64, 256, 96, 192, 16384, "tpu", False)])  # a state beyond VMEM
def predicate_test(chunk, heads, d_k, d_v, sequence, backend, takes):
    assert dr.rule_kernel_applies(chunk, heads, d_k, d_v, sequence,
                                  backend) is takes


def predicate_reads_the_backend_test():
    assert jax.default_backend() == "cpu"
    assert not dr.rule_kernel_applies(64, 30, 96, 192, 16384)


@pytest.mark.parametrize("heads,block", [(30, 8), (8, 8), (3, 3), (1, 1)])
def head_block_test(heads, block):
    assert dr.head_block(heads) == block


_WIDE = {"delta_key_features": 16, "delta_value_features": 16,
         "sequence_length": 128, "delta_chunk": 32, "train_batch_size": 1}


def declining_layer_traces_the_parents_ops_test(monkeypatch):
    """The toy widths (8 key features a head): with the backend steered to
    the TPU the layer still traces ``grouped_rule``'s ops, and no Pallas
    call."""
    _, params, model, batch, variables = _build("bfloat16")
    assert recurrent.rule_kernel_layers(params, "tpu") == 0
    plain = harness.step_jaxpr(model, variables, batch)
    monkeypatch.setattr(delta_mod, "rule_kernel_applies", functools.partial(
        dr.rule_kernel_applies, backend="tpu"))
    assert harness.step_jaxpr(model, variables, batch) == plain
    assert "delta_rule_fwd" not in plain and "pallas_call" not in plain


def rule_fact_counts_the_layers_test(monkeypatch):
    """``hbnlp_delta_rule_kernel_layers``: by the layer's own predicate on
    the shapes it declares; None (no fragment, gauge 0) without such a layer.
    Where the rule is the pair the solve's one call holds every head's
    systems and the chunk states alive are every head's."""
    _, params, _, _, _ = _build("bfloat16", **_WIDE)
    declared = delta_mod.gated_delta.declares.recurrent
    assert declared.rule(params) == (32, 3, 16, 16, 128)
    assert recurrent.rule_kernel_layers(params, "tpu") == 3
    assert recurrent.rule_kernel_layers(params) == 0
    assert declared.solve(params, "tpu") == (32, 1 * 4 * 3)
    one_group = recurrent.ssd_state_bytes(params)
    assert one_group == 1 * 4 * 3 * 16 * 16 * 2       # 48 MiB hold all three
    short = _build("bfloat16", **{**_WIDE, "sequence_length": 64})[1]
    assert recurrent.rule_kernel_layers(short, "tpu") == 0   # half a tile
    from granite_test import _build as _build_granite
    assert recurrent.rule_kernel_layers(_build_granite()[1], "tpu") is None
    monkeypatch.setattr(delta_mod, "GROUP_BYTES", 128 * 32 * 4)
    assert recurrent.ssd_state_bytes(params) == one_group // 3
    assert declared.solve(params) == (32, 4)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert recurrent.ssd_state_bytes(params) == one_group
    assert declared.solve(params) == (32, 12)
