"""parallel/ssd_scan.py: the Pallas pair of layer ``mamba``'s chunked scan
(interpret mode on the CPU) against the XLA form ``model/mamba.py ssd_xla``
and autodiff's gradients of it, against the recurrence run position by
position, the predicate that chooses between them, and the layer with and
without the kernels."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from homebrewnlp_tpu.model import mamba as mamba_mod
from homebrewnlp_tpu.model import recurrent
from homebrewnlp_tpu.parallel import ssd_scan as sk

import harness
from granite_test import _build, _scan_recurrence


def _inputs(s, heads, decay, p=8, n=16, dtype=jnp.float32, batch=2, seed=0):
    """``dt * a`` per position is about ``-decay``."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(batch, s, heads, p)), dtype)
    dt = jnp.asarray(rng.uniform(0.5, 1.5, (batch, s, heads)), jnp.float32)
    a = jnp.asarray(-decay * rng.uniform(0.5, 1.5, (heads,)), jnp.float32)
    b_mat, c_mat = (jnp.asarray(rng.normal(size=(batch, s, n)), dtype)
                    for _ in range(2))
    weights = jnp.asarray(rng.normal(size=x.shape), jnp.float32)
    return (x, dt, a, b_mat, c_mat), weights


def _kernel(x, dt, a, b_mat, c_mat, chunk, heads_a_block=None):
    """``ssd``'s kernel branch, interpreted."""
    a_cum = sk.log_decay(dt, a, chunk)
    return sk.ssd_scan(x, dt, a_cum, b_mat, c_mat, chunk, heads_a_block,
                       True), jnp.min(a_cum)


def _value_and_grads(fn, inputs, weights, compiled=None):
    """``compiled``: where a caller of many draws keeps each form's program."""
    def run(weights, *inputs):
        def loss(*args):
            out, low = fn(*args)
            return jnp.sum(out * weights), (out, low)
        (_, (out, low)), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(*inputs)
        return (out, *grads), low
    return ({} if compiled is None else compiled).setdefault(
        fn, jax.jit(run))(weights, *inputs)


def _close(got, want, tolerance):
    """``dA``, a sum over every position of terms that cancel, is held to
    the terms' size: ``ddt``'s."""
    harness.assert_close_each(
        got, want, tolerance, ("y", "dx", "ddt", "dA", "dB", "dC"),
        {"dA": np.max(np.abs(np.asarray(want[2], np.float32)))})


# (sequence, chunk, heads, heads a block, decay a position): one chunk of one
# block; four chunks of two blocks; eight of one, a decay that underflows any
# product along a chunk; two blocks of a whole sublane tile of heads; a
# chunk's cumulative log-decay past the cell's -480.91 (masked BEFORE exp)
@pytest.mark.parametrize("s,chunk,heads,block,decay", [
    (16, 16, 4, 4, 0.05), (64, 16, 4, 2, 0.05), (64, 8, 3, 3, 6.0),
    (32, 16, 16, 8, 1.0), (48, 16, 4, 1, 40.0)])
def pair_matches_the_xla_form_test(s, chunk, heads, block, decay):
    inputs, weights = _inputs(s, heads, decay)
    got, low = _value_and_grads(
        functools.partial(_kernel, chunk=chunk, heads_a_block=block),
        inputs, weights)
    want, want_low = _value_and_grads(
        functools.partial(mamba_mod.ssd_xla, chunk=chunk), inputs, weights)
    assert float(low) == float(want_low)
    if decay == 40.0:
        assert float(low) < -480
    _close(got, want, 2e-5)


@pytest.mark.parametrize("chunk,s,decay", [(8, 8, 0.05), (8, 32, 6.0),
                                           (16, 64, 0.05)])
def pair_is_the_recurrence_test(chunk, s, decay):
    """``y`` and all five gradients against the recurrence run position by
    position (``lax.scan``'s own reverse mode)."""
    inputs, weights = _inputs(s, 3, decay, p=4, n=5)
    got, _ = _value_and_grads(functools.partial(_kernel, chunk=chunk),
                              inputs, weights)
    want, _ = _value_and_grads(
        lambda *args: (_scan_recurrence(*args), 0.0), inputs, weights)
    _close(got, want, 1e-4)


@pytest.mark.parametrize("sequence,chunk,heads,p,state,backend,takes", [
    (8192, 256, 64, 64, 128, "tpu", True),     # the published widths
    (8192, 128, 64, 16, 256, "tpu", True),
    (128, 128, 1, 64, 128, "tpu", True),       # one chunk, one head
    (8192, 256, 64, 64, 128, "cpu", False),
    (8192, 64, 64, 64, 128, "tpu", False),     # half a lane tile a chunk
    (8192 + 128, 256, 64, 64, 128, "tpu", False),   # no whole chunks
    (8192, 1024, 64, 64, 128, "tpu", False),   # [l, l] tiles beyond VMEM
    (8192, 256, 64, 64, 16, "tpu", False),     # the toy state
    (8192, 256, 64, 8, 128, "tpu", False)])    # the toy head
def predicate_test(sequence, chunk, heads, p, state, backend, takes):
    assert sk.ssd_kernel_applies(sequence, chunk, heads, p, state,
                                 backend) is takes


def predicate_reads_the_backend_test():
    assert jax.default_backend() == "cpu"
    assert not sk.ssd_kernel_applies(8192, 256, 64, 64, 128)


@pytest.mark.parametrize("heads,p,block", [(64, 64, 8), (64, 32, 16),
                                           (4, 8, 4), (24, 64, 8),
                                           (12, 64, 12)])
def head_block_divides_the_heads_test(heads, p, block):
    assert sk.head_block(heads, p) == block


def declining_layer_traces_the_parents_ops_test(monkeypatch):
    """The toy widths (8 features a head, state 16, chunk 16): with the
    backend steered to the TPU the layer still traces the einsums."""
    _, params, model, batch, variables = _build("bfloat16")
    assert recurrent.scan_kernel_layers(params, "tpu") == 0
    plain = harness.step_jaxpr(model, variables, batch)
    harness.steer(monkeypatch, mamba_mod, ssd_kernel_applies=functools.partial(
        sk.ssd_kernel_applies, backend="tpu"))
    assert harness.step_jaxpr(model, variables, batch) == plain
    assert "ssd_scan" not in plain


def scan_fact_counts_the_layers_test():
    """``hbnlp_ssd_scan_kernel_layers``: by the layer's own predicate on the
    shapes it declares; None (no fragment, gauge 0) without such a layer."""
    wide = {"mamba_head_features": 64, "mamba_state": 128, "mamba_chunk": 128,
            "sequence_length": 256}
    _, params, _, _, _ = _build("bfloat16", **wide)
    assert mamba_mod.mamba.declares.recurrent.scan(params) \
        == (256, 128, 4, 64, 128, 1)
    assert recurrent.scan_kernel_layers(params, "tpu") == 9
    assert recurrent.scan_kernel_layers(params) == 0
    short = _build("bfloat16", **{**wide, "sequence_length": 128,
                                  "mamba_chunk": 256})[1]
    assert recurrent.scan_kernel_layers(short, "tpu") == 9   # one chunk
    from olmo_hybrid_test import _build as _build_olmo
    assert recurrent.scan_kernel_layers(_build_olmo()[1], "tpu") is None
