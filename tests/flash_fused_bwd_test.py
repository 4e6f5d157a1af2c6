"""The one-pass flash backward either way round (q blocks outermost: dq in
VMEM over the k walk, dk / dv in a head's resident accumulators; k blocks
outermost: dk / dv over the q walk, dq resident) against the split dq / dk-dv
pair and dense autodiff, and the fit rule that chooses among the three."""
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


#: the one pass, by the side it holds resident for a head's sweep
ONE_PASS = ("dkv_resident", "dq_resident")


def _one_pass(form, *args, **kwargs):
    return fa._bwd_flat_one_pass(*args, **kwargs,
                                 dq_resident=form == "dq_resident")


@pytest.mark.parametrize("form", ONE_PASS)
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("bq,bk", [(16, 16), (16, 32), (32, 16)])
def one_pass_bwd_matches_split_test(causal, bq, bk, form, monkeypatch):
    """The one-pass backward kernel (what the fit rule picks at this size,
    and the other side resident) against the split dq / dk/dv kernels and
    dense autodiff, across uneven tiles (the diagonal frontier crossing
    block boundaries both ways) and both causal modes."""
    q, k, v, _ = dense_form.inputs(96, 11, d=8)

    def grads():
        return jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, 0.35, causal, bq, bk, True) ** 2),
            argnums=(0, 1, 2))(q, k, v)

    assert fa.backward_form(96, 96, 8, 8, bq, bk, 4) == "dkv_resident"
    # the rule is read at every call: nothing of jax's is keyed on it
    monkeypatch.setattr(fa, "backward_form", lambda *a: form)
    g_one = grads()
    monkeypatch.setattr(fa, "backward_form", lambda *a: "split")
    g_split = grads()
    g_ref = _dense_square_grads(causal)(q, k, v)
    for a, b_, c in zip(g_one, g_split, g_ref):
        # one pass vs split: the same dots, rounding points and float32
        # accumulation in VMEM; dq adds its k blocks, dk / dv their q blocks
        # in the same order
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=2e-4, atol=2e-5)


def _flat_residuals(qt, kt, vt, dot, scale):
    """``(lse [bh, s, 1], delta [bh, s, 1])`` of the dense form."""
    scores = jnp.einsum("zqd,zkd->zqk", qt.astype(jnp.float32),
                        kt.astype(jnp.float32)) * scale
    m = scores.max(-1)
    p_un = jnp.exp(scores - m[..., None])
    l = p_un.sum(-1)
    out = jnp.einsum("zqk,zkd->zqd", p_un / l[..., None],
                     vt.astype(jnp.float32))
    delta = jnp.sum(dot.astype(jnp.float32) * out, -1, keepdims=True)
    return (m + jnp.log(l))[..., None], delta


@pytest.mark.parametrize("form", ONE_PASS)
def one_pass_bwd_uneven_lengths_test(form):
    """_bwd_flat with sq != sk (the ring-hop contract allows it): one pass
    vs split parity on a rectangular non-causal pair."""
    rng = np.random.default_rng(12)
    bh, sq, sk, d = 2, 32, 64, 8
    f32 = np.float32
    qt = jnp.asarray(rng.standard_normal((bh, sq, d)).astype(f32))
    kt = jnp.asarray(rng.standard_normal((bh, sk, d)).astype(f32))
    vt = jnp.asarray(rng.standard_normal((bh, sk, d)).astype(f32))
    dot = jnp.asarray(rng.standard_normal((bh, sq, d)).astype(f32))
    lse, delta = _flat_residuals(qt, kt, vt, dot, 0.35)
    args = (qt, kt, vt, dot, lse, delta, 0.35, False, 16, 16, True)
    res_one = _one_pass(form, *args)
    res_split = fa._bwd_flat_split(*args)
    for a, b_ in zip(res_one, res_split):
        assert a.shape == b_.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-5, atol=1e-5)
    # the dispatcher takes the one pass here, dk and dv resident
    for a, b_ in zip(fa._bwd_flat(*args), _one_pass("dkv_resident", *args)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


def one_pass_bwd_random_shapes_property_test():
    """Property sweep: random (seq, tiles, causal, dtype) combinations
    through the one-pass backward vs dense autodiff — shape-dependent logic
    (frontier clamps, the head's first and last step, the resident
    accumulators' tile index, uneven tile ratios) must hold everywhere, not
    just at the tuned points."""
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


@pytest.mark.parametrize("form", ONE_PASS)
@pytest.mark.parametrize("bq,bk", [(32, 32), (32, 64), (64, 32)])
def one_pass_bwd_at_unequal_widths_test(bq, bk, form):
    """Key width 24, value width 16 (latent attention's 192 / 128 scaled
    down) over several q and k blocks with the diagonal crossing both ways:
    the one pass against the split pair, float32 summation order apart, and
    against the dense form's gradients."""
    q, k, v, do = (x[0].transpose(1, 0, 2) for x in dense_form.inputs(
        256, 21, d=24, d_v=16))
    out, lse = fa._fwd_flat(q, k, v, 0.2, True, bq, bk, True)
    delta = jnp.sum(do * out, -1, keepdims=True)
    args = (q, k, v, do, lse[..., None], delta, 0.2, True, bq, bk, True)
    one, split = _one_pass(form, *args), fa._bwd_flat_split(*args)
    want = dense_form.dense(256, 21, scale=0.2, d=24, d_v=16)[2]
    for a, b_, w, width in zip(one, split, want, (24, 24, 16)):
        assert a.shape == b_.shape == (2, 256, width)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(a),
                                   np.asarray(w[0].transpose(1, 0, 2)),
                                   rtol=dense_form.GRAD_RTOL,
                                   atol=dense_form.GRAD_ATOL)


@pytest.mark.parametrize("form", ONE_PASS)
@pytest.mark.parametrize("step", [4, 16])
def one_pass_bwd_under_the_stepped_diagonal_test(step, form):
    """The block-diffusion mask's far part (``step``: a query sees the keys
    of EARLIER blocks): the one pass against the split pair and against the
    dense stepped form, the first block's rows (which see no key) under the
    zero cotangent the caller's merge hands them."""
    q, k, v, do = (x[0].transpose(1, 0, 2) for x in dense_form.inputs(
        256, 23, d=16))
    do = do.at[:, :step].set(0)
    out, lse = fa._fwd_flat(q, k, v, 0.25, True, 64, 128, True, step=step)
    delta = jnp.sum(do * out, -1, keepdims=True)
    args = (q, k, v, do, lse[..., None], delta, 0.25, True, 64, 64, True)
    one = _one_pass(form, *args, step=step)
    split = fa._bwd_flat_split(*args, step=step)

    def dense(q, k, v):
        return fa._xla_stepped_with_lse(
            *(x.transpose(1, 0, 2)[None] for x in (q, k, v)), 0.25, step
        )[0][0].transpose(1, 0, 2)

    want = jax.vjp(dense, q, k, v)[1](do)
    for a, b_, w in zip(one, split, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   rtol=dense_form.GRAD_RTOL,
                                   atol=dense_form.GRAD_ATOL)


@pytest.mark.parametrize("form", ONE_PASS)
def one_pass_bwd_float32_partials_test(form):
    """The ring hop's contract: bfloat16 operands, ``out_dtype=float32``
    gradients, no rounding between the accumulators and the outputs — the
    one pass hands back the split pair's float32 values."""
    q, k, v, do = (x[0].transpose(1, 0, 2) for x in dense_form.inputs(
        256, 25, d=16, dtype=jnp.bfloat16))
    out, lse = fa._fwd_flat(q, k, v, 0.25, True, 64, 128, True,
                            out_dtype=jnp.float32)
    delta = jnp.sum(do.astype(jnp.float32) * out, -1, keepdims=True)
    args = (q, k, v, do, lse[..., None], delta, 0.25, True, 64, 64, True)
    one = _one_pass(form, *args, out_dtype=jnp.float32)
    split = fa._bwd_flat_split(*args, out_dtype=jnp.float32)
    for a, b_ in zip(one, split):
        assert a.dtype == b_.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-6, atol=1e-6)
    # without it each gradient takes its operand's dtype, as the pair's
    for a, b_ in zip(_one_pass(form, *args), fa._bwd_flat_split(*args)):
        assert a.dtype == b_.dtype == jnp.bfloat16


@pytest.mark.parametrize("window", [48, 100])
@pytest.mark.parametrize("bq,bk", [(32, 32), (32, 64), (64, 32)])
def dq_resident_bwd_under_a_window_test(bq, bk, window):
    """The one pass with dq resident on a windowed call's live cells (a k
    block's band of q blocks, then the next k block's): the split pair's
    dq, dk and dv, float32 summation order apart at most."""
    q, k, v, do = (x[0].transpose(1, 0, 2) for x in dense_form.inputs(
        256, 27, d=24, d_v=16))
    out, lse = fa._fwd_flat(q, k, v, 0.2, True, bq, bk, True, window=window)
    delta = jnp.sum(do * out, -1, keepdims=True)
    args = (q, k, v, do, lse[..., None], delta, 0.2, True, bq, bk, True)
    one = _one_pass("dq_resident", *args, window=window)
    for a, b_ in zip(one, fa._bwd_flat_split(*args, window=window)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("window", [None, 512, 4096],
                         ids=["causal", "window_512", "window_4096"])
@pytest.mark.parametrize("nq,nk,bq,bk", [(16, 16, 1024, 1024),
                                         (16, 8, 512, 1024),
                                         (8, 16, 1024, 512),
                                         (3, 5, 16, 16)])
def live_steps_k_major_walks_the_same_cells_test(nq, nk, bq, bk, window):
    """``_live_steps``' transposed order: the q-major walk's cells, a k
    block at a time with its q blocks ascending, every k block with one
    first and one last step (its accumulators' zeroing and write-back)."""
    causal = nq * bq == nk * bk
    window = window if causal else None
    qi, ki, edge = fa._live_steps(nq, nk, bq, bk, causal, window, True)
    q_major = fa._live_steps(nq, nk, bq, bk, causal, window)
    assert sorted(zip(qi.tolist(), ki.tolist())) \
        == sorted(zip(q_major[0].tolist(), q_major[1].tolist()))
    assert list(zip(ki.tolist(), qi.tolist())) \
        == sorted(zip(ki.tolist(), qi.tolist()))
    assert ki[(edge & 1) == 1].tolist() == list(range(nk)) \
        == ki[(edge & 2) == 2].tolist()
    for c in range(nk):
        steps = np.flatnonzero(ki == c)
        assert (np.diff(steps) == 1).all()
        assert edge[steps[0]] & 1 and edge[steps[-1]] & 2
        assert not (edge[steps[1:]] & 1).any()
        assert not (edge[steps[:-1]] & 2).any()


#: ``(positions, key width, value width, window, block-diffusion block)`` of
#: the flash call of every train cell that makes one (the other four — both
#: mixer cells, MiniCPM-SALA, Keye-VL-2.0 — reach no causal, windowed or
#: block-diffusion kernel), a ring hop's chunk pair, BASELINE.md's 32k recipe
#: and a call no one pass fits, and the FORM the backward takes
_CALLS = {
    # 2 x 33.5 MB of float32 dk / dv accumulators a head do not fit, 33.5 MB
    # of dq do: on the split pair until PR 73
    "train_1b_long_context_s16k": (16384, 512, 512, None, 0, "dq_resident"),
    "train_olmoe_1b_7b_s4k": (4096, 128, 128, None, 0, "dkv_resident"),
    "train_granite_4_0_h_micro_long": (8192, 64, 64, None, 0, "dkv_resident"),
    "train_olmo_hybrid_7b_long": (16384, 128, 128, None, 0, "dkv_resident"),
    "train_laguna_s_2_1_ep32_s8k-global": (8192, 128, 128, None, 0,
                                           "dkv_resident"),
    "train_laguna_s_2_1_ep32_s8k-window": (8192, 128, 128, 512, 0,
                                           "dkv_resident"),
    "train_zaya1_8b_ep2_s16k": (16384, 128, 128, None, 0, "dkv_resident"),
    "train_ouro_2_6b_loop4_s4k": (4096, 128, 128, None, 0, "dkv_resident"),
    "train_nemotron_3_super_tp2_ep64_s16k": (16384, 128, 128, None, 0,
                                             "dkv_resident"),
    # latent attention: until PR 68 on the split pair (6.4 GB of partials)
    "train_kimi_linear_ep32_s16k": (16384, 192, 128, None, 0, "dkv_resident"),
    "train_joyai_llm_flash_ep16_s16k": (16384, 192, 128, None, 0,
                                        "dkv_resident"),
    "train_sdar_30b_a3b_ep8_s8k": (8192, 128, 128, None, 4, "dkv_resident"),
    "train_smallthinker_21b_ep8_s16k-global": (16384, 128, 128, None, 0,
                                               "dkv_resident"),
    "train_smallthinker_21b_ep8_s16k-window": (16384, 128, 128, 4096, 0,
                                               "dkv_resident"),
    "ring-hop-of-1b_long_context": (2048, 512, 512, None, 0, "dkv_resident"),
    "baseline-32k-recipe": (32768, 128, 128, None, 0, "dkv_resident"),
    # 4 x 33.5 MB of dq alone: neither side of a head fits
    "width-512-at-65536": (65536, 512, 512, None, 0, "split"),
}
#: the form with float32 outputs (a ring hop's), which double the output
#: blocks, where it is another
_FLOAT32_OUT = {"train_1b_long_context_s16k": "split",
                "baseline-32k-recipe": "dq_resident"}


@pytest.mark.parametrize("call", sorted(_CALLS))
def backward_form_follows_the_fit_test(call):
    """The fork the backward keeps is chosen from what the code observes — a
    head's accumulators, output blocks, tiles and score planes against the
    VMEM the call asks for, dk and dv resident first, then dq, then neither
    — and the benchmark has cells on the first two sides.  At 1,024 x
    1,024 tiles the long-context call's dq-resident form would be 102 MiB by
    the same account, over the 100 MiB asked for."""
    s, d, d_v, window, block, form = _CALLS[call]
    if block:
        assert fa.stepped_applies(s, d, block, 2, d_v)
    blk = fa.call_tiles(s, d, window, 2, d_v)[0]
    assert blk == (512 if window else 1024)
    # the one pass halves its k tile where its body would pass the cap
    tiles = fa.one_pass_tiles(blk, blk, d, d_v, True, window)
    assert tiles == (blk, blk // 2 if d == 512 else blk)
    assert form in fa.BACKWARD_FORMS
    assert fa.backward_form(s, s, d, d_v, blk, blk, 2, window=window) == form
    assert fa.one_pass_applies(s, d, d_v, *tiles, 2) \
        is (form == "dkv_resident")
    assert fa.backward_form(s, s, d, d_v, blk, blk, 2, 4, window=window) \
        == _FLOAT32_OUT.get(call, form)
    assert fa._ONE_PASS_VMEM_BUDGET < 128 * 1024 ** 2


def long_context_backward_compiles_for_a_v5e_as_one_pass_test(v5e):
    """The long-context recipe's call (16 heads x 16,384 x 512, bfloat16, the
    tiles ``attention`` asks for) through ``_bwd_flat``, compiled for a
    described v5e: Mosaic fits the dq-resident one pass in the VMEM it asks
    for, dq leaves as a plain ``[bh, s, d]``, and no split kernel is named."""
    x = jax.ShapeDtypeStruct((16, 16384, 512), jnp.bfloat16, sharding=v5e)
    col = jax.ShapeDtypeStruct((16, 16384, 1), jnp.float32, sharding=v5e)
    blk = fa.call_tiles(16384, 512, None, 2)[0]
    hlo = jax.jit(lambda *a: fa._bwd_flat(
        *a, 512 ** -0.5, True, blk, blk, False)).lower(
            x, x, x, x, col, col).compile().as_text()
    assert "flash_bwd_fused_causal" in hlo
    assert "(bf16[16,16384,512]{2,1,0:T(8,128)(2,1)}, bf16[16,16384,512]" in hlo
    assert "flash_bwd_dq" not in hlo and "flash_bwd_dkv" not in hlo
