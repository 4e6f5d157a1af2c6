"""parallel/causal_conv.py: the Pallas conv + bias + SiLU kernel pair of layer
``mamba`` against ``silu(causal_depthwise_conv(...))`` and autodiff's
gradients of it (interpret mode on the CPU), the predicate that chooses
between them, and the layer with and without the kernel."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from homebrewnlp_tpu.model import mamba as mamba_mod
from homebrewnlp_tpu.model import recurrent
from homebrewnlp_tpu.parallel import causal_conv as cc

import harness
from granite_test import _build


@pytest.fixture
def tiles(monkeypatch):
    """Set the kernels' tile constants for one test.  ``_fwd_impl`` /
    ``_bwd_impl`` are ``jax.jit``s, whose traces do not see a module
    constant change: drop THEIRS before and after (``jax.clear_caches()``
    would drop every program of the worker with them)."""
    def drop():
        cc._fwd_impl.clear_cache()
        cc._bwd_impl.clear_cache()

    def set_tiles(seq_tile, piece_lanes=None):
        monkeypatch.setattr(cc, "_SEQ_TILE", seq_tile)
        if piece_lanes is not None:
            monkeypatch.setattr(cc, "_FWD_PIECE",
                                (cc._FWD_PIECE[0], piece_lanes))
            monkeypatch.setattr(cc, "_BWD_PIECE",
                                (cc._BWD_PIECE[0], piece_lanes))
        drop()
    yield set_tiles
    monkeypatch.undo()
    drop()


def _reference(x, weight, bias):
    return jax.nn.silu(mamba_mod.causal_depthwise_conv(
        x.astype(jnp.float32), weight, bias)).astype(x.dtype)


def _kernel(x, weight, bias):
    return cc.causal_conv_silu(x, weight, bias, 0, True)


def _inputs(batch, s, channels, taps, dtype, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(batch, s, channels)), dtype)
    g = jnp.asarray(rng.normal(size=(batch, s, channels)), dtype)
    bound = taps ** -0.5
    w = jnp.asarray(rng.uniform(-bound, bound, (taps, channels)), jnp.float32)
    bias = jnp.asarray(rng.uniform(-bound, bound, (channels,)), jnp.float32)
    return x, w, bias, g


# (sequence, the sequence tile's cap, a piece's lanes): one tile of one piece
# (the zeros before position 0); every edge of eight tiles, in both
# directions; one tile of four pieces; two tiles of two pieces
@pytest.mark.optimised
@pytest.mark.parametrize("s,cap,lanes", [
    (128, 2048, 512), (1024, 128, 512), (512, 512, 128), (512, 256, 128)])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("taps", [2, 4])
def kernel_matches_the_shifted_multiplies_test(tiles, taps, dtype, s, cap,
                                               lanes):
    """Forward bit for bit; ``dx`` within an ulp of the calculation dtype,
    ``dw`` / ``db`` within float32's summation order (the kernel adds piece
    by piece and batch by batch, autodiff in one reduction)."""
    tiles(cap, lanes)
    assert cc.seq_tile(s) == min(s, cap)
    # 384 channels: three tiles of 128; two sequences: dw / db add over both
    x, w, bias, g = _inputs(2, s, 384, taps, dtype)
    got, pull = jax.vjp(_kernel, x, w, bias)
    want, pull_ref = jax.vjp(jax.jit(_reference), x, w, bias)
    assert got.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    (dx, dw, db), (dx_r, dw_r, db_r) = pull(g), pull_ref(g)
    assert dx.dtype == dtype and dw.dtype == db.dtype == jnp.float32
    # one rounding of the calculation dtype apart, and where the taps
    # cancel, float32's rounding of the terms
    ulp = 2.0 ** -7 if dtype == jnp.bfloat16 else 2.0 ** -21
    dx_r = np.asarray(dx_r, np.float32)
    np.testing.assert_allclose(np.asarray(dx, np.float32), dx_r, rtol=ulp,
                               atol=2.0 ** -21 * np.abs(dx_r).max())
    for a, r in ((dw, dw_r), (db, db_r)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=2e-5,
                                   atol=1e-5 * float(np.abs(r).max()))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def a_conv_without_a_bias_test(tiles, dtype):
    """``bias`` None (layer ``gated_delta``'s conv): the kernels run with a
    zero bias, bit for bit; against the shifted multiplies the forward is
    within an ulp (XLA starts its sum at the first tap, the kernel at the
    zero), and no cotangent comes back for the bias."""
    tiles(128)
    x, w, _, g = _inputs(2, 256, 256, 4, dtype)
    no_bias = lambda f: lambda x, w: f(x, w, None)  # noqa: E731
    got, pull = jax.vjp(no_bias(_kernel), x, w)
    want, pull_ref = jax.vjp(jax.jit(no_bias(_reference)), x, w)
    ulp = 2.0 ** -7 if dtype == jnp.bfloat16 else 2.0 ** -21
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=ulp,
                               atol=2.0 ** -21)
    (dx, dw), (dx_r, dw_r) = pull(g), pull_ref(g)
    dx_r = np.asarray(dx_r, np.float32)
    np.testing.assert_allclose(np.asarray(dx, np.float32), dx_r, rtol=ulp,
                               atol=2.0 ** -21 * np.abs(dx_r).max())
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_r), rtol=2e-5,
                               atol=1e-5 * float(np.abs(dw_r).max()))
    # with a zero bias: the same program
    zero = jnp.zeros((256,), jnp.float32)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(_kernel(x, w, zero), np.float32))


def halo_rows_are_the_neighbours_test(tiles):
    """An impulse on a tile's last row reaches the next tile's first K - 1
    outputs, and its gradient comes back across the same edge."""
    tiles(128)
    x = jnp.zeros((1, 256, 128), jnp.float32).at[0, 127].set(1.0)
    w = jnp.asarray(np.arange(1, 5, dtype=np.float32)[:, None]
                    * np.ones((1, 128), np.float32))
    bias = jnp.zeros((128,), jnp.float32)
    y = np.asarray(_kernel(x, w, bias))[0, :, 0]
    silu = lambda v: v / (1 + np.exp(-v))  # noqa: E731
    np.testing.assert_allclose(y[127:131], silu(np.array([4., 3., 2., 1.])),
                               rtol=1e-6)
    assert not y[:127].any() and not y[131:].any()
    g = jnp.zeros((1, 256, 128), jnp.float32).at[0, 128].set(1.0)
    dx = np.asarray(jax.vjp(_kernel, x * 0, w, bias)[1](g)[0])[0, :, 0]
    # silu'(0) = 1/2; dx[t] = sum_k w[k] dpre[t + 3 - k]
    np.testing.assert_allclose(dx[125:129], [0.5, 1.0, 1.5, 2.0], rtol=1e-6)
    assert not dx[:125].any() and not dx[129:].any()


def channels_are_read_in_place_test(tiles):
    """``offset``: the conv's channels read out of a wider tensor through
    the block index map, as out of the slice; the cotangent is zero outside
    them."""
    tiles(128)
    wide, w, bias, _ = _inputs(2, 256, 640, 4, jnp.bfloat16)
    w, bias = w[:, :384], bias[:384]
    g = _inputs(2, 256, 384, 4, jnp.bfloat16, seed=1)[3]
    got, pull = jax.vjp(
        lambda x, w, b: cc.causal_conv_silu(x, w, b, 128, True), wide, w, bias)
    want, pull_ref = jax.vjp(_kernel, wide[..., 128:512], w, bias)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    (dx, dw, db), (dx_r, dw_r, db_r) = pull(g), pull_ref(g)
    assert dx.shape == wide.shape
    np.testing.assert_array_equal(np.asarray(dx[..., 128:512], np.float32),
                                  np.asarray(dx_r, np.float32))
    assert not np.asarray(dx[..., :128], np.float32).any()
    assert not np.asarray(dx[..., 512:], np.float32).any()
    np.testing.assert_array_equal(np.asarray(dw), np.asarray(dw_r))
    np.testing.assert_array_equal(np.asarray(db), np.asarray(db_r))


@pytest.mark.parametrize("channels,sequence,taps,offset,backend,takes", [
    (4352, 8192, 4, 4096, "tpu", True),        # the published widths
    (128, 128, 2, 0, "tpu", True),
    (4352, 8192, 4, 4096, "cpu", False),
    (64, 8192, 4, 0, "tpu", False),            # the rehearsal's 64 channels
    (4352, 8192, 4, 4096 + 64, "tpu", False),  # they start inside a tile
    (4352, 8192 + 64, 4, 4096, "tpu", False),  # no whole lane tiles divide
    (4352, 8192 + 8, 4, 4096, "tpu", False),
    (4352, 8192, 130, 4096, "tpu", False)])    # taps beyond one halo block
def predicate_test(channels, sequence, taps, offset, backend, takes):
    assert cc.kernel_applies(channels, sequence, taps, offset,
                             backend) is takes


def predicate_reads_the_backend_test():
    assert jax.default_backend() == "cpu"
    assert not cc.kernel_applies(4352, 8192, 4, 4096)


def steer_to_the_kernel(monkeypatch, tiles=None, module=mamba_mod):
    """``module``'s layer as a TPU process would trace it, the kernels
    interpreted (with ``tiles``: at sequence tiles of 128)."""
    if tiles is not None:
        tiles(128)
    harness.steer(
        monkeypatch, module,
        kernel_applies=functools.partial(cc.kernel_applies, backend="tpu"),
        causal_conv_silu=lambda x, w, b, offset: cc.causal_conv_silu(
            x, w, b, offset, True))


def declining_layer_traces_the_parents_ops_test(monkeypatch, tiles):
    """64 channels: with the backend steered to the TPU the layer still
    traces the shifted multiplies, the very jaxpr it traces here."""
    _, params, model, batch, variables = _build("bfloat16")
    assert recurrent.conv_kernel_layers(params, "tpu") == 0
    plain = harness.step_jaxpr(model, variables, batch)
    steer_to_the_kernel(monkeypatch, tiles)
    assert harness.step_jaxpr(model, variables, batch) == plain
    assert "mamba_conv" not in plain
    assert "pad" in plain
