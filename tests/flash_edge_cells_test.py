"""What a flash cell that an edge crosses scores (PR 55): its live part."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flash_dense as dense_form
from homebrewnlp_tpu.parallel import flash_attention as fa


#: (block_q, block_k): the forward's production shape in small (a k tile of
#: two q tiles: the cell that starts where its k tile starts scores the first
#: half), the backward's (square: quadrants), and the other way round
EDGE_TILES = [(128, 256), (128, 128), (256, 128)]


def _edge_inputs(s, seed=11, dtype=np.float32):
    return dense_form.inputs(s, seed, dtype=dtype)


@pytest.mark.parametrize("form", fa.BACKWARD_FORMS)
@pytest.mark.parametrize("window", [None, 192], ids=["causal", "window"])
@pytest.mark.parametrize("s", [512, 1024])
@pytest.mark.parametrize("bq,bk", EDGE_TILES)
def edge_cells_score_their_live_part_test(bq, bk, s, window, form,
                                          monkeypatch):
    """``out``, ``lse``, ``dq``, ``dk``, ``dv`` of the tiled kernels (the
    windowed forward on the tiled grid too) against the dense form and its
    autodiff, at tiles of several cells a side, so that every branch runs:
    the interior, each edge offset's parts, the dead cells."""
    q, k, v, do = _edge_inputs(s)
    monkeypatch.setattr(fa, "backward_form", lambda *a: form)
    monkeypatch.setattr(fa, "band_applies", lambda *a, **kw: False)
    # the pair ``flash_attention``'s ``custom_vjp`` runs, its forward once
    out, saved = fa._flash_fwd(q, k, v, 0.25, True, bq, bk, True, None, None,
                               window)
    lse = saved[-1]
    got = fa._flash_bwd(0.25, True, bq, bk, True, None, None, window, saved,
                        do)
    ref, ref_lse, want = dense_form.dense(s, 11, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=2e-5, atol=2e-5)
    dense_form.assert_grads_close(got, want)


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
    body = str(jax.make_jaxpr(lambda *a: fa._flash_fwd_impl(
        *a, 0.25, True, bq, bk, True, window))(q, k, v))
    out, lse = fa._flash_fwd_impl(q, k, v, 0.25, True, bq, bk, True, window)
    monkeypatch.undo()
    parts = [fa._cell_parts(bq, bk, off, window, True)[0]
             for off in fa._edge_offsets(bq, bk, window)]
    own = sum((p.rows, p.cols) != ((0, bq), (0, bk)) for p in parts)
    # init, the shared branch, the part-tile edge cells, finish
    assert body.count(" cond[") == 3 + own
    if window is None:
        assert own == (bq != bk)
    ref, ref_lse, _ = dense_form.dense(512, 14, window)
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
    out, lse, want = dense_form.dense(512, 12)
    got = jax.vjp(lambda q, k, v: fa.flash_precomputed(
        q, k, v, out, lse, 0.25, True, bq, bk, True), q, k, v)[1](do)
    dense_form.assert_grads_close(got, want)


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


#: ``(key width, value width, (block_q, block_k), window, the ring hop's
#: bfloat16 operands and float32 partials)``: the widths the train cells hand
#: the tiled forward, each at tiles whose diagonal cell is cut to a part — at
#: 64 / 64 one of 64 keys, no whole lane tile of the row statistics
STATISTIC_CASES = {
    "64x64": (64, 64, (64, 128), None, False),
    "128x128": (128, 128, (128, 256), None, False),
    "192x128": (192, 128, (128, 256), None, False),
    "512x512": (512, 512, (128, 256), None, False),
    "window": (128, 128, (128, 128), 192, False),
    "ring_hop": (128, 128, (128, 256), None, True),
}


@pytest.mark.parametrize("case", list(STATISTIC_CASES))
def lane_replicated_statistics_match_the_dense_form_test(case):
    """``_fwd_flat``'s ``(out, lse)`` with the row statistics held ``[rows,
    _STAT_LANES]`` (PR 66) against the dense form: every width of a train
    cell, a windowed call on the tiled grid, a ring hop's partials."""
    d_k, d_v, (bq, bk), window, hop = STATISTIC_CASES[case]
    dtype = jnp.bfloat16 if hop else np.float32
    q, k, v, _ = dense_form.inputs(512, 66, heads=2, d=d_k, d_v=d_v,
                                   dtype=dtype)
    scale = d_k ** -0.5
    out, lse = fa._fwd_flat(
        *(x[0].transpose(1, 0, 2) for x in (q, k, v)), scale, True, bq, bk,
        True, out_dtype=jnp.float32 if hop else None, window=window)
    assert out.dtype == jnp.float32 and lse.dtype == jnp.float32
    ref, ref_lse = fa._xla_reference_with_lse(
        *(x.astype(jnp.float32) for x in (q, k, v)), scale, True, window)
    # a hop's p rounds to bfloat16 before its dot with v
    tol = 2e-2 if hop else 2e-5
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref[0].transpose(1, 0, 2)),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("forward", ["causal", "window", "select_blocks",
                                     "select_keys"])
def forward_statistics_are_lane_replicated_test(forward, monkeypatch):
    """Every tiled forward's ``pallas_call`` carries ``m`` and ``l`` as two
    ``[q tile, _STAT_LANES]`` float32 scratch buffers beside its accumulator,
    and no 1-D one: as ``(q tile,)`` scratch they turn between lanes and
    sublanes at every use (PR 63, PR 66)."""
    s, heads, d_k, d_v = 512, 2, 64, 64
    q = jax.ShapeDtypeStruct((1, s, heads, d_k), jnp.float32)
    v = jax.ShapeDtypeStruct((1, s, heads, d_v), jnp.float32)
    if forward.startswith("select"):
        block = 32 if forward == "select_blocks" else 1
        # the key-at-a-time form's tile is twice the block form's
        monkeypatch.setattr(fa, "_SELECT_TILE", 128 if block == 1 else 256)
        keep = jnp.ones((1, 1, s, s // block), bool)
        keep = fa.pack_keep(keep) if block == 1 else keep.astype(q.dtype)
        tile, name = fa.select_tile(s, block)[0], "flash_fwd_select"
        scratch = dense_form.kernel_scratch(
            lambda q, k, v: fa._select_fwd_impl(q, k, v, keep, 0.125, block,
                                                True), q, q, v)
    else:
        window = 192 if forward == "window" else None
        monkeypatch.setattr(fa, "band_applies", lambda *a, **kw: False)
        tile, name = 256, "flash_fwd_" + forward
        scratch = dense_form.kernel_scratch(
            lambda q, k, v: fa._flash_fwd_impl(q, k, v, 0.125, True, tile,
                                               tile, True, window), q, q, v)
    assert tile != fa._STAT_LANES != d_v
    assert scratch == {name: [((tile, fa._STAT_LANES), jnp.float32)] * 2
                       + [((tile, d_v), jnp.float32)]}

