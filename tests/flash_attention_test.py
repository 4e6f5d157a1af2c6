"""Pallas flash attention (interpret mode on CPU) vs dense reference: the
causal and the full forward and backward at toy tiles, a wide head, and a
value narrower or wider than the key.  (A window and the band:
``flash_window_test.py``; PR 55's edge cells: ``flash_edge_cells_test.py``;
the fused backward against the split pair: ``flash_fused_bwd_test.py``.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flash_dense as dense_form
import harness
from flash_dense import GRAD_ATOL, GRAD_RTOL
from homebrewnlp_tpu.parallel import flash_attention as fa
from homebrewnlp_tpu.parallel.flash_attention import (_xla_reference,
                                                      flash_attention)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("seq,block", [(64, 16), (128, 32)])
def flash_matches_dense_test(causal, seq, block):
    rng = np.random.default_rng(0)
    b, h, d = 2, 2, 16
    q = jnp.asarray(rng.standard_normal((b, seq, h, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((b, seq, h, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((b, seq, h, d)).astype(np.float32))
    scale = d ** -0.5
    out = flash_attention(q, k, v, scale, causal, block, block, True)
    ref = _xla_reference(q, k, v, scale, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def flash_uneven_blocks_test():
    """block_q != block_k and diagonal frontier correctness."""
    rng = np.random.default_rng(1)
    b, s, h, d = 1, 64, 1, 8
    q = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
    out = flash_attention(q, k, v, 0.5, True, 16, 32, True)
    ref = _xla_reference(q, k, v, 0.5, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def flash_grad_test():
    rng = np.random.default_rng(2)
    b, s, h, d = 1, 32, 2, 8
    q = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))

    g1 = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, 0.35, True, 16, 16, True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: jnp.sum(
        _xla_reference(q, k, v, 0.35, True) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def flash_grad_uneven_blocks_test(causal):
    """The pallas dq / dkv kernels at block_q != block_k (diagonal frontier
    crosses block boundaries unevenly) against dense autodiff."""
    rng = np.random.default_rng(3)
    b, s, h, d = 1, 64, 2, 8
    q = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
    g1 = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, 0.35, causal, 16, 32, True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: jnp.sum(
        _xla_reference(q, k, v, 0.35, causal) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-5)


def bwd_block_override_parity_test():
    """bwd_block_q/bwd_block_k override the backward kernels' tiles
    independently of the forward's (attention() uses a wider forward k tile
    that exceeds the dq kernel's scoped VMEM in the full model): gradients
    must match dense autodiff and the same-tile baseline exactly."""
    rng = np.random.default_rng(7)
    b, s, h, d = 1, 128, 2, 8
    q = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))

    def g(bwd_q=None, bwd_k=None):
        return jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, 0.35, True, 32, 64, True,
                            bwd_block_q=bwd_q, bwd_block_k=bwd_k) ** 2),
            argnums=(0, 1, 2))(q, k, v)

    g_same = g()
    g_over = g(bwd_q=16, bwd_k=32)
    g_ref = jax.grad(lambda q, k, v: jnp.sum(
        _xla_reference(q, k, v, 0.35, True) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b_, c in zip(g_over, g_same, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=2e-4, atol=2e-5)


def flash_wide_head_dim_test():
    """d=256 head dim through forward + fused backward (the shipped shapes
    use d=128; the kernels must not silently assume it)."""
    rng = np.random.default_rng(14)
    b, s, h, d = 1, 64, 1, 256
    q = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
    out = flash_attention(q, k, v, d ** -0.5, True, 32, 32, True)
    ref = _xla_reference(q, k, v, d ** -0.5, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    g1 = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, d ** -0.5, True, 32, 32, True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: jnp.sum(
        _xla_reference(q, k, v, d ** -0.5, True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=GRAD_RTOL, atol=GRAD_ATOL)


# ---- a value narrower (or wider) than the key (PR 58) ------------------------

def _two_width_inputs(s, d_k, d_v, heads=2, seed=17):
    rng = np.random.default_rng(seed)

    def normal(d):
        return jnp.asarray(rng.standard_normal((1, s, heads, d)), jnp.float32)

    return normal(d_k), normal(d_k), normal(d_v), normal(d_v)


@pytest.mark.parametrize("form", fa.BACKWARD_FORMS)
@pytest.mark.parametrize("window", [None, 96], ids=["causal", "window"])
@pytest.mark.parametrize("d_k,d_v", [(192, 128), (64, 128)])
def two_widths_match_the_dense_form_test(d_k, d_v, window, form,
                                         monkeypatch):
    """``q, k [.., d_k]``, ``v, out [.., d_v]``: the forward (tiled, and the
    band under a window), the one-pass backward either way round and the dq /
    dk-dv pair against
    ``_xla_reference`` — latent attention's 192 / 128, and the other way
    round."""
    monkeypatch.setattr(fa, "backward_form", lambda *a: form)
    fused = form != "split"
    q, k, v, do = _two_width_inputs(256, d_k, d_v)
    scale = d_k ** -0.5

    def kernels(q, k, v):
        return flash_attention(q, k, v, scale, True, 64, 128, True, 64, 64,
                               window)

    out, vjp = jax.vjp(kernels, q, k, v)
    want, want_vjp = jax.vjp(
        lambda q, k, v: _xla_reference(q, k, v, scale, True, window), q, k, v)
    assert out.shape == want.shape == (1, 256, 2, d_v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    for got, ref in zip(vjp(do), want_vjp(do)):
        assert got.shape == ref.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=GRAD_RTOL, atol=GRAD_ATOL)
    names = str(jax.make_jaxpr(lambda *a: jax.vjp(kernels, *a)[1](do))(
        q, k, v))
    assert ("flash_bwd_fused" in names) == fused
    assert ("flash_bwd_dkv" in names) == (not fused)


def a_precomputed_forward_at_two_widths_test():
    q, k, v, do = _two_width_inputs(256, 192, 128, seed=19)
    scale = 192 ** -0.5
    out, lse = fa._xla_reference_with_lse(q, k, v, scale, True)
    assert out.shape == (1, 256, 2, 128) and lse.shape == (2, 256)
    got = jax.vjp(lambda q, k, v: fa.flash_precomputed(
        q, k, v, out, lse, scale, True, 64, 64, True), q, k, v)[1](do)
    want = jax.vjp(lambda q, k, v: _xla_reference(q, k, v, scale, True),
                   q, k, v)[1](do)
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=GRAD_RTOL, atol=GRAD_ATOL)
    # the dispatch: a named pair at the value's width
    stash = {"mode": "name", "min_keys": 0}
    named = jax.jit(lambda q, k, v: fa.attention(q, k, v, scale, stash=stash,
                                                 interpret=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(named), np.asarray(out),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("case,heads,s,d,window", [
    ("long_context", 16, 16384, 512, None), ("olmoe", 16, 4096, 128, None),
    ("laguna_window", 72, 8192, 128, 512)],
    ids=["long_context", "olmoe", "laguna_window"])
def equal_widths_are_the_parents_calls_test(case, heads, s, d, window):
    """At ``d_k == d_v`` every call is the one it was: the jaxprs — kernel
    bodies, grids, block maps, names, source positions stripped — of
    ``flash_attention``'s and ``flash_precomputed``'s gradients at the
    long-context cell's (the one pass with a head's dq resident, at 1,024 x
    512 tiles), OLMoE's and Laguna's window layers' (dk and dv resident; the
    band forward) shapes and tiles are the pinned ones."""
    q = jax.ShapeDtypeStruct((1, s, heads, d), jnp.bfloat16)
    blk, fwd_q, fwd_k, _ = fa.call_tiles(s, d, window, 2)
    assert fa.call_tiles(s, d, window, 2, d) == (blk, fwd_q, fwd_k, _)

    def loss(q, k, v):
        return flash_attention(q, k, v, d ** -0.5, True, fwd_q, fwd_k, False,
                               blk, blk, window).astype(jnp.float32).sum()

    harness.pinned(f"kernel/flash_grad/{case}",
                   dense_form.jaxpr_text(jax.grad(loss, (0, 1, 2)), q, q, q))

    def saved(q, k, v, out, lse):
        return jax.grad(lambda q, k, v: fa.flash_precomputed(
            q, k, v, out, lse, d ** -0.5, True, blk, blk, False, window
        ).astype(jnp.float32).sum(), (0, 1, 2))(q, k, v)

    harness.pinned(f"kernel/flash_precomputed_grad/{case}",
                   dense_form.jaxpr_text(saved, q, q, q, q, jax.ShapeDtypeStruct(
                       (heads, s), jnp.float32)))

