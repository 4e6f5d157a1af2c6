"""Pallas flash attention (interpret mode on CPU) vs dense reference."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from homebrewnlp_tpu.parallel import flash_attention as fa
from homebrewnlp_tpu.parallel.flash_attention import (_xla_reference,
                                                      flash_attention)

# jax-0.4.37's pallas INTERPRET mode (how these kernels run on the CPU
# rig) evaluates the streaming-softmax accumulation with different
# reduction associativity than compiled TPU kernels; at the wide-head
# gradient shapes the measured margin is ~3.5e-4 vs the 2e-4 silicon
# tolerance (ROADMAP re-anchor: a classified jax-0.4.37 environment gap,
# not a kernel bug — the same test passes the tighter bound on TPU).
# Widen ONLY off-TPU so silicon keeps the strict gate.
_INTERPRET = jax.default_backend() != "tpu"
GRAD_RTOL = 5e-4 if _INTERPRET else 2e-4
GRAD_ATOL = 5e-5 if _INTERPRET else 2e-5


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


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("bq,bk", [(16, 16), (16, 32), (32, 16)])
def fused_bwd_matches_split_test(causal, bq, bk, monkeypatch):
    """The one-pass fused backward kernel (default) against the split
    dq / dk/dv kernels and dense autodiff, across uneven tiles (the
    diagonal frontier crossing block boundaries both ways) and both
    causal modes."""
    rng = np.random.default_rng(11)
    b, s, h, d = 1, 96, 2, 8
    q = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))

    def grads():
        return jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, 0.35, causal, bq, bk, True) ** 2),
            argnums=(0, 1, 2))(q, k, v)

    g_fused = grads()
    # no buffer fits a cap of 0: the split pair runs
    monkeypatch.setattr(fa, "_fused_dqp_cap", lambda: 0)
    jax.clear_caches()
    g_split = grads()
    monkeypatch.undo()
    jax.clear_caches()
    g_ref = jax.grad(lambda q, k, v: jnp.sum(
        _xla_reference(q, k, v, 0.35, causal) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b_, c in zip(g_fused, g_split, g_ref):
        # fused vs split: same dots/rounding points, only the dq partial-sum
        # order differs (VMEM sequential vs XLA reduce over nk)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=2e-4, atol=2e-5)


def fused_bwd_uneven_lengths_test(monkeypatch):
    """_bwd_flat with sq != sk (the ring-hop contract allows it): fused vs
    split parity on a rectangular non-causal pair."""
    from homebrewnlp_tpu.parallel.flash_attention import _bwd_flat
    rng = np.random.default_rng(12)
    bh, sq, sk, d = 2, 32, 64, 8
    f32 = np.float32
    qt = jnp.asarray(rng.standard_normal((bh, sq, d)).astype(f32))
    kt = jnp.asarray(rng.standard_normal((bh, sk, d)).astype(f32))
    vt = jnp.asarray(rng.standard_normal((bh, sk, d)).astype(f32))
    dot = jnp.asarray(rng.standard_normal((bh, sq, d)).astype(f32))
    # consistent (lse, delta) residuals from the dense form
    scores = jnp.einsum("zqd,zkd->zqk", qt, kt) * 0.35
    m = scores.max(-1)
    p_un = jnp.exp(scores - m[..., None])
    l = p_un.sum(-1)
    lse = m + jnp.log(l)
    out = jnp.einsum("zqk,zkd->zqd", p_un / l[..., None], vt)
    delta = jnp.sum(dot * out, -1, keepdims=True)

    res_fused = _bwd_flat(qt, kt, vt, dot, lse[..., None], delta, 0.35,
                          False, 16, 16, True)
    monkeypatch.setattr(fa, "_fused_dqp_cap", lambda: 0)
    jax.clear_caches()
    res_split = _bwd_flat(qt, kt, vt, dot, lse[..., None], delta, 0.35,
                          False, 16, 16, True)
    monkeypatch.undo()
    jax.clear_caches()
    for a, b_ in zip(res_fused, res_split):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-5, atol=1e-5)


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


def fused_bwd_random_shapes_property_test():
    """Property sweep: random (seq, tiles, causal, dtype) combinations
    through the fused backward vs dense autodiff — shape-dependent logic
    (frontier clamps, dead-cell zero-fill, partial-slice counts, uneven
    tile ratios) must hold everywhere, not just at the tuned points."""
    rng = np.random.default_rng(99)
    for trial in range(6):
        s = int(rng.choice([48, 64, 80, 96, 128]))
        divisors = [b for b in (8, 16, 32) if s % b == 0]
        bq = int(rng.choice(divisors))
        bk = int(rng.choice(divisors))
        causal = bool(rng.integers(0, 2))
        b, h, d = int(rng.integers(1, 3)), int(rng.integers(1, 3)), 8
        q = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
        k = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
        v = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
        g1 = jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, 0.3, causal, bq, bk, True) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda q, k, v: jnp.sum(
            _xla_reference(q, k, v, 0.3, causal) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g1, g2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), rtol=3e-4, atol=3e-5,
                err_msg=f"trial={trial} s={s} bq={bq} bk={bk} causal={causal}")


@pytest.mark.parametrize("bh,s,d,fused", [
    # train_1b_long_context_s16k: 16 heads x 512, 8.6 GB of dq partials
    (16, 16384, 512, False),
    # train_olmoe_1b_7b_s4k: batch 2 x 16 heads x 128, 268 MB
    (32, 4096, 128, True),
    # one ring hop's chunk pair of configs/1b_long_context.json, 134 MB
    (16, 2048, 512, True),
    # BASELINE.md '32k context single-chip': 8 heads x 128, batch 1, 4.3 GB
    (8, 32768, 128, True),
])
def backward_path_follows_the_buffer_test(bh, s, d, fused, monkeypatch):
    """The one fork the backward keeps is chosen from what the code
    observes — the dq-partial buffer's bytes against the chip's memory, here
    a v5e's 16 GiB — and the benchmark has a cell on each side of it."""
    from homebrewnlp_tpu.utils import flops
    monkeypatch.delenv("HBNLP_FUSED_DQP_CAP_GB", raising=False)
    monkeypatch.setattr(flops, "device_hbm_bytes",
                        lambda device=None: 16 * 1024 ** 3)
    bk = fa.kernel_block(s)
    assert bk == 1024
    assert fa._use_fused_bwd(bh, s, s, d, bk) is fused


# ---- a window (ISSUE 36): key t visible to query i iff 0 <= i - t < window --

#: (sequence, window, q tile, k tile): tiles smaller than, equal to and
#: larger than the window, windows that end inside a tile, a window of one,
#: a window one short of the sequence, and uneven tiles both ways
WINDOW_CASES = [(128, 32, 16, 16), (128, 32, 32, 32), (128, 32, 64, 64),
                (128, 1, 16, 16), (128, 50, 16, 32), (128, 50, 32, 16),
                (128, 127, 32, 32), (96, 33, 8, 8), (64, 16, 64, 64)]


def _window_inputs(s, seed=3):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((1, s, 2, 16))
                             .astype(np.float32)) for _ in range(4))


@pytest.fixture
def band_form(request, monkeypatch):
    """The windowed FORWARD's form: ``band`` (``_fwd_band``, sub-blocks of 16
    rows so that a toy tile holds one, two or four) or ``tiled``, the
    ``_fwd_flat`` grid the predicate falls back to."""
    form = getattr(request, "param", "band")
    monkeypatch.setattr(fa, "_BAND_SUB", 16)
    if form == "tiled":
        monkeypatch.setattr(fa, "band_applies", lambda *a, **kw: False)
    jax.clear_caches()
    yield form
    monkeypatch.undo()
    jax.clear_caches()


def _forward_kernels(fn, *args):
    """``(name, grid)`` of every ``pallas_call`` ``fn`` traces to."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append((eqn.params["name"],
                              tuple(eqn.params["grid_mapping"].grid)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("band_form", ["band", "tiled"], indirect=True)
@pytest.mark.parametrize("s,window,bq,bk", WINDOW_CASES)
def window_forward_matches_the_band_mask_test(s, window, bq, bk, band_form):
    """``out`` and ``lse`` of both forms, the first tiles (whose band is
    clipped at position 0) included, against the dense form."""
    q, k, v, _ = _window_inputs(s)
    assert fa.band_applies(s, 16, window, 4) == (band_form == "band")
    out, lse = fa._flash_fwd_impl(q, k, v, 0.25, True, bq, bk, True, window)
    (_, grid), = _forward_kernels(lambda *a: fa._flash_fwd_impl(
        *a, 0.25, True, bq, bk, True, window), q, k, v)
    assert len(grid) == (2 if band_form == "band" else 3)
    ref, ref_lse = fa._xla_reference_with_lse(q, k, v, 0.25, True, window)
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


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "split"])
@pytest.mark.parametrize("s,window,bq,bk", WINDOW_CASES)
def window_backward_matches_the_band_mask_test(s, window, bq, bk, fused,
                                               monkeypatch, band_form):
    """The fused backward (dq partials in the band's slots, summed by index)
    and the split dq / dk-dv pair, both on grids as long as the band, both
    on the ``lse`` the band forward wrote."""
    q, k, v, do = _window_inputs(s)
    monkeypatch.setattr(fa, "_fused_dqp_cap",
                        (lambda: 1 << 40) if fused else (lambda: 0))
    jax.clear_caches()
    assert band_form == "band" and fa.band_applies(s, 16, window, 4)
    got = jax.vjp(lambda q, k, v: flash_attention(
        q, k, v, 0.25, True, bq, bk, True, None, None, window), q, k, v)[1](do)
    monkeypatch.undo()
    jax.clear_caches()
    want = jax.vjp(lambda q, k, v: _xla_reference(q, k, v, 0.25, True, window),
                   q, k, v)[1](do)
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=GRAD_RTOL, atol=GRAD_ATOL)


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
    with no k dimension; the backward keeps ``window_block``'s grid; and the
    predicate declines what does not fit a cell."""
    q = jax.ShapeDtypeStruct((1, 8192, 2, 128), jnp.bfloat16)

    def grad(q, k, v):
        return jax.grad(lambda *a: fa.attention(
            *a, interpret=False, window=512).astype(jnp.float32).sum(),
            (0, 1, 2))(q, k, v)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kernels = dict(_forward_kernels(grad, q, q, q))
    tile = fa.band_block(8192)
    assert kernels == {"flash_fwd_window": (2, 8192 // tile),
                       "flash_bwd_fused_window": (2, 16, 2)}
    assert fa.band_applies(8192, 128, 512, 2)
    # K and V of one head-sequence, resident: 2 x 2 x s x d x 2 bytes
    assert fa.band_applies(32768, 128, 512, 2)
    assert not fa.band_applies(65536, 128, 512, 2)
    assert not fa.band_applies(16384, 512, 512, 2)
    # a sub-block's scores over window + sub keys, float32 twice and bfloat16
    assert fa.band_applies(32768, 128, 8192, 2)
    assert not fa.band_applies(32768, 128, 16384, 2)
    monkeypatch.setattr(fa, "band_applies", lambda *a, **kw: False)
    jax.clear_caches()
    assert dict(_forward_kernels(grad, q, q, q))["flash_fwd_window"] \
        == (2, 16, 2)


def _normalised_jaxpr_digest(fn, *args) -> str:
    import hashlib
    import re
    text = re.sub(r" at \S+:\d+", "", str(jax.make_jaxpr(fn)(*args)))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("fused,digest", [(True, "ff0effe0be83d952"),
                                          (False, "6c1cc156f207ebf9")])
def no_window_is_the_parents_call_test(fused, digest, monkeypatch):
    """``window=None`` traces to one call whether the argument is left out
    or given as None: the digests are of this call's jaxpr — kernel bodies,
    grids, block maps and names, source positions stripped — for the fused
    and the split backward.  Until PR 55 they were those of the parent
    commit of ISSUE 36 (5f633c2: e8c973467ff66f11 / 9626d241fbf329fd);
    PR 55 MEANT to move the bodies (an edge cell scores its live part), the
    grids, maps and names are as they were (``flops_test.py``)."""
    monkeypatch.setattr(fa, "_fused_dqp_cap",
                        (lambda: 1 << 40) if fused else (lambda: 0))
    q = jax.ShapeDtypeStruct((1, 2048, 2, 128), jnp.bfloat16)

    def loss(q, k, v, *window):
        return flash_attention(q, k, v, 128 ** -0.5, True, 1024, 2048, False,
                               1024, 1024, *window).astype(jnp.float32).sum()

    grad = jax.grad(loss, (0, 1, 2))
    assert _normalised_jaxpr_digest(grad, q, q, q) == digest
    assert _normalised_jaxpr_digest(
        lambda q, k, v: grad(q, k, v, None), q, q, q) == digest
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


# ---- what a cell that an edge crosses scores (PR 55) ------------------------

#: (block_q, block_k): the forward's production shape in small (a k tile of
#: two q tiles: the cell that starts where its k tile starts scores the first
#: half), the backward's (square: quadrants), and the other way round
EDGE_TILES = [(128, 256), (128, 128), (256, 128)]


def _edge_inputs(s, seed=11, heads=2, d=16, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((1, s, heads, d))
                             .astype(np.float32)).astype(dtype)
                 for _ in range(4))


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "split"])
@pytest.mark.parametrize("window", [None, 192], ids=["causal", "window"])
@pytest.mark.parametrize("s", [512, 1024])
@pytest.mark.parametrize("bq,bk", EDGE_TILES)
def edge_cells_score_their_live_part_test(bq, bk, s, window, fused,
                                          monkeypatch):
    """``out``, ``lse``, ``dq``, ``dk``, ``dv`` of the tiled kernels (the
    windowed forward on the tiled grid too) against the dense form and its
    autodiff, at tiles of several cells a side, so that every branch runs:
    the interior, each edge offset's parts, the dead cells."""
    q, k, v, do = _edge_inputs(s)
    monkeypatch.setattr(fa, "_fused_dqp_cap",
                        (lambda: 1 << 40) if fused else (lambda: 0))
    monkeypatch.setattr(fa, "band_applies", lambda *a, **kw: False)
    jax.clear_caches()
    out, lse = fa._flash_fwd_impl(q, k, v, 0.25, True, bq, bk, True, window)
    got = jax.vjp(lambda q, k, v: flash_attention(
        q, k, v, 0.25, True, bq, bk, True, None, None, window), q, k, v)[1](do)
    monkeypatch.undo()
    jax.clear_caches()
    ref, ref_lse = fa._xla_reference_with_lse(q, k, v, 0.25, True, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=2e-5, atol=2e-5)
    want = jax.vjp(lambda q, k, v: _xla_reference(q, k, v, 0.25, True, window),
                   q, k, v)[1](do)
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=GRAD_RTOL, atol=GRAD_ATOL)


@pytest.mark.parametrize("window", [None, 192], ids=["causal", "window"])
@pytest.mark.parametrize("bq,bk", EDGE_TILES)
def wide_forward_bodies_share_the_interior_branch_test(bq, bk, window,
                                                       monkeypatch):
    """Past ``_FORWARD_BODY_CAP`` (here: any body) the forward's whole-tile
    edge cells run in the interior's branch under the position mask — the
    long-context cell's form (1,024 x 2,048 tiles at head width 512) — and
    the part-tile ones keep a branch of their own: ``out`` and ``lse``."""
    q, k, v, _ = _edge_inputs(512, seed=14)
    monkeypatch.setattr(fa, "_FORWARD_BODY_CAP", 0)
    monkeypatch.setattr(fa, "band_applies", lambda *a, **kw: False)
    jax.clear_caches()
    body = str(jax.make_jaxpr(lambda *a: fa._flash_fwd_impl(
        *a, 0.25, True, bq, bk, True, window))(q, k, v))
    out, lse = fa._flash_fwd_impl(q, k, v, 0.25, True, bq, bk, True, window)
    monkeypatch.undo()
    jax.clear_caches()
    parts = [fa._cell_parts(bq, bk, off, window, True)[0]
             for off in fa._edge_offsets(bq, bk, window)]
    own = sum((p.rows, p.cols) != ((0, bq), (0, bk)) for p in parts)
    # init, the shared branch, the part-tile edge cells, finish
    assert body.count(" cond[") == 3 + own
    if window is None:
        assert own == (bq != bk)
    ref, ref_lse = fa._xla_reference_with_lse(q, k, v, 0.25, True, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=2e-5, atol=2e-5)
    # at the tiles ``attention`` gives, only head width 512 is past the cap
    for d, shared in ((128, False), (256, False), (512, True)):
        area = 1024 * 2048 + sum(
            p.pairs for off in fa._edge_offsets(1024, 2048, None)
            for p in fa._cell_parts(1024, 2048, off, None, True))
        assert (d * area > fa._FORWARD_BODY_CAP) == shared


@pytest.mark.parametrize("bq,bk", EDGE_TILES)
def edge_cells_under_a_precomputed_forward_test(bq, bk):
    """``flash_precomputed``: the backward alone, on a provided ``(out,
    lse)`` — the path of every cell whose attention kind is saved."""
    q, k, v, do = _edge_inputs(512, seed=12)
    out, lse = fa._xla_reference_with_lse(q, k, v, 0.25, True)
    got = jax.vjp(lambda q, k, v: fa.flash_precomputed(
        q, k, v, out, lse, 0.25, True, bq, bk, True), q, k, v)[1](do)
    want = jax.vjp(lambda q, k, v: _xla_reference(q, k, v, 0.25, True),
                   q, k, v)[1](do)
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=GRAD_RTOL, atol=GRAD_ATOL)


@pytest.mark.parametrize("bq,bk", EDGE_TILES)
def edge_cells_through_the_ring_hop_test(bq, bk):
    """The flat cores as a ring hop calls them on its diagonal chunk pair:
    bfloat16 operands, ``out_dtype=float32`` partials both ways."""
    q, k, v, do = (x[0].transpose(1, 0, 2) for x in _edge_inputs(
        512, seed=13, dtype=jnp.bfloat16))
    out, lse = fa._fwd_flat(q, k, v, 0.25, True, bq, bk, True,
                            out_dtype=jnp.float32)
    assert out.dtype == jnp.float32

    def dense(q, k, v):
        ref, ref_lse = fa._xla_reference_with_lse(
            *(x.astype(jnp.float32).transpose(1, 0, 2)[None]
              for x in (q, k, v)), 0.25, True)
        return ref[0].transpose(1, 0, 2), ref_lse

    ref, ref_lse = dense(q, k, v)
    # p rounds to bfloat16 before its dot with v
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=2e-5, atol=2e-5)
    delta = jnp.sum(do.astype(jnp.float32) * out, -1, keepdims=True)
    got = fa._bwd_flat(q, k, v, do, lse[..., None], delta, 0.25, True, bq, bk,
                       True, out_dtype=jnp.float32)
    want = jax.vjp(lambda *a: dense(*a)[0], q, k, v)[1](
        do.astype(jnp.float32))
    for a, b_ in zip(got, want):
        assert a.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(a),
                                   np.asarray(b_.astype(jnp.float32)),
                                   rtol=5e-2, atol=5e-2)


def edge_cell_parts_are_the_live_part_test():
    """The static cut of a cell: pair for pair, the parts' masks see exactly
    the pairs the dense mask sees, the parts do not overlap, and what they
    leave out is dead."""
    for bq, bk, window in [(128, 256, None), (128, 128, None),
                           (256, 128, None), (64, 64, 64), (64, 64, 50),
                           (32, 64, 200), (128, 128, 192)]:
        offsets = fa._edge_offsets(bq, bk, window)
        # every offset a grid can show is classified as the splits do
        for off in range(-2 * bq, (window or 0) + 2 * bk, math.gcd(bq, bk)):
            back = off + np.arange(bq)[:, None] - np.arange(bk)[None, :]
            seen = (back >= 0) & (back < (window or 1 << 30))
            assert (off in offsets) == bool(seen.any() and not seen.all())
        for off in offsets:
            back = off + np.arange(bq)[:, None] - np.arange(bk)[None, :]
            seen = (back >= 0) & (back < (window or 1 << 30))
            for carried in (False, True):
                scored = np.zeros((bq, bk), int)
                kept = np.zeros((bq, bk), bool)
                for part in fa._cell_parts(bq, bk, off, window, carried):
                    r, c = slice(*part.rows), slice(*part.cols)
                    scored[r, c] += 1
                    mask = fa._part_mask(part, off, window)
                    ones = jnp.ones((part.rows[1] - part.rows[0],
                                     part.cols[1] - part.cols[0]))
                    kept[r, c] = np.asarray(ones if mask is None
                                            else mask(ones)) > 0
                    assert (mask is None) == bool(seen[r, c].all())
                assert scored.max() == 1
                np.testing.assert_array_equal(kept, seen)
                if carried:
                    # one step, over the live sub-squares' bounding box
                    assert scored.sum() == scored.any(1).sum() \
                        * scored.any(0).sum()
    # the production shapes: the forward's first-half cell is one step over
    # 1,024 keys; a square backward cell is three quadrants
    short, = fa._cell_parts(1024, 2048, 0, None, True)
    assert (short.rows, short.cols) == ((0, 1024), (0, 1024))
    whole, = fa._cell_parts(1024, 2048, 1024, None, True)
    assert (whole.rows, whole.cols) == ((0, 1024), (0, 2048))
    assert [(p.rows, p.cols, p.causal) for p in
            fa._cell_parts(1024, 1024, 0, None, False)] == [
        ((0, 512), (0, 512), True), ((512, 1024), (0, 512), False),
        ((512, 1024), (512, 1024), True)]
    # a window of a tile: the far edge's cell drops its lower-left quadrant
    assert fa._edge_offsets(512, 512, 512) == (0, 512)
    assert [(p.rows, p.cols, p.far) for p in
            fa._cell_parts(512, 512, 512, 512, False)] == [
        ((0, 256), (0, 256), True), ((0, 256), (256, 512), False),
        ((256, 512), (256, 512), True)]


# ---- a value narrower (or wider) than the key (PR 58) ------------------------

def _two_width_inputs(s, d_k, d_v, heads=2, seed=17):
    rng = np.random.default_rng(seed)

    def normal(d):
        return jnp.asarray(rng.standard_normal((1, s, heads, d)), jnp.float32)

    return normal(d_k), normal(d_k), normal(d_v), normal(d_v)


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "split"])
@pytest.mark.parametrize("window", [None, 96], ids=["causal", "window"])
@pytest.mark.parametrize("d_k,d_v", [(192, 128), (64, 128)])
def two_widths_match_the_dense_form_test(d_k, d_v, window, fused,
                                         monkeypatch):
    """``q, k [.., d_k]``, ``v, out [.., d_v]``: the forward (tiled, and the
    band under a window), the fused backward and the dq / dk-dv pair against
    ``_xla_reference`` — latent attention's 192 / 128, and the other way
    round."""
    monkeypatch.setattr(fa, "_fused_dqp_cap",
                        (lambda: 1 << 40) if fused else (lambda: 0))
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


@pytest.mark.parametrize("heads,s,d,window,digest,precomputed", [
    (16, 16384, 512, None, "8085b0b458133d61", "537f93e728be5233"),
    (16, 4096, 128, None, "b7ca317183ec284c", "aca9a0fc8d6e5f85"),
    (72, 8192, 128, 512, "7f26067a62d06811", "5c106b71441b2e30")],
    ids=["long_context", "olmoe", "laguna_window"])
def equal_widths_are_the_parents_calls_test(heads, s, d, window, digest,
                                            precomputed):
    """At ``d_k == d_v`` every call is the one it was: the jaxprs — kernel
    bodies, grids, block maps, names, source positions stripped — of
    ``flash_attention``'s and ``flash_precomputed``'s gradients at the
    long-context cell's, OLMoE's and Laguna's window layers' shapes and
    tiles, digests taken on PR 58's parent (239ac1f)."""
    q = jax.ShapeDtypeStruct((1, s, heads, d), jnp.bfloat16)
    blk, fwd_q, fwd_k, _ = fa.call_tiles(s, d, window, 2)
    assert fa.call_tiles(s, d, window, 2, d) == (blk, fwd_q, fwd_k, _)

    def loss(q, k, v):
        return flash_attention(q, k, v, d ** -0.5, True, fwd_q, fwd_k, False,
                               blk, blk, window).astype(jnp.float32).sum()

    assert _normalised_jaxpr_digest(jax.grad(loss, (0, 1, 2)), q, q, q) \
        == digest

    def saved(q, k, v, out, lse):
        return jax.grad(lambda q, k, v: fa.flash_precomputed(
            q, k, v, out, lse, d ** -0.5, True, blk, blk, False, window
        ).astype(jnp.float32).sum(), (0, 1, 2))(q, k, v)

    assert _normalised_jaxpr_digest(
        saved, q, q, q, q, jax.ShapeDtypeStruct((heads, s), jnp.float32)) \
        == precomputed
