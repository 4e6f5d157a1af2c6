"""Flash attention under a window (ISSUE 36): key t visible to query i iff
0 <= i - t < window — the band forward and the tiled one, both backwards,
the grids, and what ``window=None`` leaves as it was."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flash_dense as dense_form
import harness
from homebrewnlp_tpu.parallel import flash_attention as fa
from homebrewnlp_tpu.parallel.flash_attention import flash_attention


#: (sequence, window, q tile, k tile): tiles smaller than, equal to and
#: larger than the window, windows that end inside a tile, a window of one,
#: a window one short of the sequence, and uneven tiles both ways
WINDOW_CASES = [(128, 32, 16, 16), (128, 32, 32, 32), (128, 32, 64, 64),
                (128, 1, 16, 16), (128, 50, 16, 32), (128, 50, 32, 16),
                (128, 127, 32, 32), (96, 33, 8, 8), (64, 16, 64, 64)]


def _window_inputs(s, seed=3):
    return dense_form.inputs(s, seed)


@pytest.fixture
def band_form(request, monkeypatch):
    """The windowed FORWARD's form: ``band`` (``_fwd_band``, sub-blocks of 16
    rows so that a toy tile holds one, two or four) or ``tiled``, the
    ``_fwd_flat`` grid the predicate falls back to.  (The kernels' callers
    read both at every call: nothing of jax's is keyed on them.)"""
    form = getattr(request, "param", "band")
    monkeypatch.setattr(fa, "_BAND_SUB", 16)
    if form == "tiled":
        monkeypatch.setattr(fa, "band_applies", lambda *a, **kw: False)
    return form


@pytest.mark.parametrize("band_form", ["band", "tiled"], indirect=True)
@pytest.mark.parametrize("s,window,bq,bk", WINDOW_CASES)
def window_forward_matches_the_band_mask_test(s, window, bq, bk, band_form):
    """``out`` and ``lse`` of both forms, the first tiles (whose band is
    clipped at position 0) included, against the dense form."""
    q, k, v, _ = _window_inputs(s)
    assert fa.band_applies(s, 16, window, 4) == (band_form == "band")
    out, lse = fa._flash_fwd_impl(q, k, v, 0.25, True, bq, bk, True, window)
    (_, grid), = dense_form.forward_kernels(lambda *a: fa._flash_fwd_impl(
        *a, 0.25, True, bq, bk, True, window), q, k, v)
    assert len(grid) == (2 if band_form == "band" else 3)
    ref, ref_lse, _ = dense_form.dense(s, 3, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=2e-5, atol=2e-5)
    # the reference's own mask, written out once more
    i, t = np.arange(s)[:, None], np.arange(s)[None, :]
    score = np.einsum("bqhd,bkhd->bhqk", np.asarray(q), np.asarray(k)) * 0.25
    score = np.where((t <= i) & (i - t < window), score, -np.inf)
    weight = np.exp(score - score.max(-1, keepdims=True))
    weight /= weight.sum(-1, keepdims=True)
    np.testing.assert_allclose(
        np.asarray(ref), np.einsum("bhqk,bkhd->bqhd", weight, np.asarray(v)),
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("form", fa.BACKWARD_FORMS)
@pytest.mark.parametrize("s,window,bq,bk", WINDOW_CASES)
def window_backward_matches_the_band_mask_test(s, window, bq, bk, form,
                                               monkeypatch, band_form):
    """The one-pass backward (dq in VMEM over the band's k walk, dk / dv in
    the head's resident accumulators; or dk / dv over a k block's q walk, dq
    resident) and the split dq / dk-dv pair, all on grids as long as the
    band, all on the ``lse`` the band forward wrote."""
    q, k, v, do = _window_inputs(s)
    monkeypatch.setattr(fa, "backward_form", lambda *a: form)
    assert band_form == "band" and fa.band_applies(s, 16, window, 4)
    got = jax.vjp(lambda q, k, v: flash_attention(
        q, k, v, 0.25, True, bq, bk, True, None, None, window), q, k, v)[1](do)
    dense_form.assert_grads_close(got, dense_form.dense(s, 3, window)[2])


@pytest.mark.parametrize("s,window,bq,bk,inner", [
    (8192, 512, 512, 512, 2), (8192, 512, 1024, 1024, 2),
    (8192, 512, 256, 256, 3), (8192, 512, 128, 128, 5),
    (128, 1, 16, 16, 1)])
def windowed_grids_are_as_long_as_the_band_test(s, window, bq, bk, inner):
    """The inner grid dimension of a windowed call holds the blocks one
    outer block's band touches, both ways round, whatever the sequence."""
    assert fa._window_inner(s // bq, lambda j: fa._window_k_range(
        j, bq, bk, window)) == inner
    assert fa._window_inner(s // bk, lambda kk: fa._window_q_range(
        kk, bq, bk, window, s // bq)) == inner
    assert fa.window_block(8192, 512) == 512
    assert fa.window_block(8192, 100) == 128
    assert fa.window_block(8192, 4096) == fa._WINDOW_BLOCK_CAP


def the_band_forward_is_the_windowed_call_test(monkeypatch):
    """At the Laguna cell's geometry (window 512, head width 128, bfloat16)
    the windowed forward is still named ``flash_fwd_window`` (the trace's
    readers cost it by that name), on a grid of (head-sequences, q tiles)
    with no k dimension; the backward keeps ``window_block``'s tiles, its
    grid the band's 31 live cells a head-sequence (16 q blocks of 512, two k
    blocks each but the first: no step for a dead cell, PR 68); and the
    predicate declines what does not fit a cell."""
    q = jax.ShapeDtypeStruct((1, 8192, 2, 128), jnp.bfloat16)

    def grad(q, k, v):
        return jax.grad(lambda *a: fa.attention(
            *a, interpret=False, window=512).astype(jnp.float32).sum(),
            (0, 1, 2))(q, k, v)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kernels = dict(dense_form.forward_kernels(grad, q, q, q))
    tile = fa.band_block(8192)
    assert kernels == {"flash_fwd_window": (2, 8192 // tile),
                       "flash_bwd_fused_window": (2, 31)}
    assert fa.band_applies(8192, 128, 512, 2)
    # K and V of one head-sequence, resident: 2 x 2 x s x d x 2 bytes
    assert fa.band_applies(32768, 128, 512, 2)
    assert not fa.band_applies(65536, 128, 512, 2)
    assert not fa.band_applies(16384, 512, 512, 2)
    # a sub-block's scores over window + sub keys, float32 twice and bfloat16
    assert fa.band_applies(32768, 128, 8192, 2)
    assert not fa.band_applies(32768, 128, 16384, 2)
    monkeypatch.setattr(fa, "band_applies", lambda *a, **kw: False)
    jax.clear_caches()      # ``attention``'s dispatch is traced once a shape
    assert dict(dense_form.forward_kernels(grad, q, q, q))["flash_fwd_window"] \
        == (2, 16, 2)


@pytest.mark.parametrize("form", ["dkv_resident", "dq_resident", "split"])
def no_window_is_the_parents_call_test(form, monkeypatch):
    """``window=None`` traces to one call whether the argument is left out
    or given as None: this call's jaxpr — kernel bodies, grids, block maps
    and names, source positions stripped — is the pinned one under each of
    the backward's three forms."""
    monkeypatch.setattr(fa, "backward_form", lambda *a: form)
    fused = form != "split"
    q = jax.ShapeDtypeStruct((1, 2048, 2, 128), jnp.bfloat16)

    def loss(q, k, v, *window):
        return flash_attention(q, k, v, 128 ** -0.5, True, 1024, 2048, False,
                               1024, 1024, *window).astype(jnp.float32).sum()

    grad = jax.grad(loss, (0, 1, 2))
    for call in (grad, lambda q, k, v: grad(q, k, v, None)):
        harness.pinned(f"kernel/flash_grad/no_window/{form}",
                       dense_form.jaxpr_text(call, q, q, q))
    names = str(jax.make_jaxpr(lambda q, k, v: jax.grad(
        lambda *a: loss(*a, 512), (0, 1, 2))(q, k, v))(q, q, q))
    assert "flash_fwd_window" in names and "_causal" not in names
    assert ("flash_bwd_fused_window" in names) == fused
    assert ("flash_bwd_dq_window" in names) == (not fused)


def a_window_as_long_as_the_sequence_is_the_causal_call_test():
    q, k, v, _ = _window_inputs(64)
    np.testing.assert_array_equal(
        np.asarray(fa.attention(q, k, v, window=64)),
        np.asarray(fa.attention(q, k, v)))
    with pytest.raises(ValueError, match="window"):
        fa.attention(q, k, v, causal=False, window=8)


