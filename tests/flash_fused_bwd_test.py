"""The one-pass fused flash backward against the split dq / dk-dv pair and
dense autodiff, and the buffer that chooses between them."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flash_dense as dense_form
from homebrewnlp_tpu.parallel import flash_attention as fa
from homebrewnlp_tpu.parallel.flash_attention import (_xla_reference,
                                                      flash_attention)


@functools.lru_cache(maxsize=None)
def _dense_square_grads(causal):
    """Dense autodiff of ``sum(out ** 2)``: one program for the three tile
    pairs of a ``causal``."""
    return jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        _xla_reference(q, k, v, 0.35, causal) ** 2), argnums=(0, 1, 2)))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("bq,bk", [(16, 16), (16, 32), (32, 16)])
def fused_bwd_matches_split_test(causal, bq, bk, monkeypatch):
    """The one-pass fused backward kernel (default) against the split
    dq / dk/dv kernels and dense autodiff, across uneven tiles (the
    diagonal frontier crossing block boundaries both ways) and both
    causal modes."""
    q, k, v, _ = dense_form.inputs(96, 11, d=8)

    def grads():
        return jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, 0.35, causal, bq, bk, True) ** 2),
            argnums=(0, 1, 2))(q, k, v)

    g_fused = grads()
    # no buffer fits a cap of 0: the split pair runs (the cap is read at
    # every call: nothing of jax's is keyed on it)
    monkeypatch.setattr(fa, "_fused_dqp_cap", lambda: 0)
    g_split = grads()
    g_ref = _dense_square_grads(causal)(q, k, v)
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
    res_split = _bwd_flat(qt, kt, vt, dot, lse[..., None], delta, 0.35,
                          False, 16, 16, True)
    for a, b_ in zip(res_fused, res_split):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-5, atol=1e-5)


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
        g2 = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
            _xla_reference(q, k, v, 0.3, causal) ** 2),
            argnums=(0, 1, 2)))(q, k, v)
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


