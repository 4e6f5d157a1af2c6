"""The indexer's ``index_loss`` pass as one Pallas kernel (ISSUE 64,
``parallel/index_loss.py``): interpreted at small tiles against the XLA form
``model/indexer.py xla_index_loss`` in float32 on all five outputs; what the
dispatch runs where; the grid's table; the start-up facts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from homebrewnlp_tpu.model import indexer, spatial
from homebrewnlp_tpu.parallel import flash_attention as fa
from homebrewnlp_tpu.parallel import index_loss as il

NAMES = ("value", "top", "d_q", "d_k", "d_w")


def _operands(seed: int, b: int = 1, s: int = 256, h: int = 4, f: int = 16,
              g: int = 2, index_heads: int = 4, d: int = 8, whole=False):
    """``(qI, kI, w, q, k, v)`` in float32; ``whole``: integer index
    operands, so that many of a row's scores are EQUAL."""
    rng = np.random.default_rng(seed)

    def normal(*shape, rounded=False):
        x = rng.normal(size=shape)
        return jnp.asarray(np.round(x) if rounded else x, jnp.float32)

    return tuple(normal(*shape, rounded=whole) for shape in (
        (b, s, index_heads, d), (b, s, d), (b, s, index_heads))) + tuple(
        normal(b, s, n, f) for n in (h, g, g))


def _planted(kind: str, s: int = 256):
    """A choice that no top-k makes, as bits ``[1, 1, s / 32, s]``."""
    keep = np.eye(s, dtype=bool)
    if kind == "local":
        # a row keeps key 0 and its own: between them whole tiles hold no
        # kept key of any row
        keep[:, 0] = True
    else:
        # the rows of a q tile's second half keep nothing of the first k
        # tile; the first half's keep a tenth of what they see
        keep |= np.random.default_rng(3).random((s, s)) < 0.1
        keep[s // 2 + 32:s // 2 + 64, :128] = False
    return fa.pack_keep(jnp.asarray(np.tril(keep)))[None, None]


@pytest.mark.parametrize("case,tiles,extra", [
    # 64 keys kept of 256: the first 64 rows keep every key they see, the
    # diagonal crosses a cell of every q tile
    ("top_keys", (64, 128), {}),
    ("top_keys", (32, 64), {}),
    ("top_keys", (128, 128), {}),
    ("top_keys", (64, 256), {}),
    # ties at the threshold: integer scores
    ("ties", (64, 128), {"whole": True}),
    # one K/V group of four heads, and four groups of one
    ("one_group", (64, 128), {"g": 1}),
    ("four_groups", (64, 128), {"g": 4}),
    ("batch_of_2", (64, 128), {"b": 2}),
    # rows of a cell with no kept key in it; whole cells with none
    ("empty_rows", (64, 128), {}),
    ("local", (64, 128), {}),
    ("local", (32, 64), {})])
def kernel_matches_the_xla_form_test(case, tiles, extra):
    """The value, the largest kept score and the three gradients, each
    within 2e-5 of its own largest entry."""
    *index, q, k, v = _operands(len(case), **extra)
    keep = _planted(case) if case in ("empty_rows", "local") else jax.jit(
        indexer.select_keys, static_argnums=3)(*index, 64)
    scale = q.shape[-1] ** -0.5
    lse = fa._xla_select_with_lse(q, k, v, keep, scale, 1)[1]
    want = jax.jit(lambda *t: indexer.xla_index_loss(*t, scale))(
        *index, q, k, lse, keep)
    got = il.index_loss_pass(*index, q, k, lse, keep, scale, tiles=tiles,
                             interpret=True)
    assert float(want[0]) > 1e-3
    harness.assert_close_each(got, want, 2e-5, NAMES,
                              {"value": 0, "top": 0})
    if case == "ties":
        score = np.asarray(indexer.scores(*index))
        assert (np.diff(np.sort(score[0, -1])) == 0).sum() > 64


def a_row_that_kept_nothing_in_its_first_cells_stays_finite_test():
    """``local`` at a tile of 32 x 64: a q tile's first sweep runs cells in
    which whole rows — whole lanes of the running maximum — have kept
    nothing yet (the finite first maximum), and every output is finite."""
    *index, q, k, v = _operands(9)
    keep = _planted("local")
    lse = fa._xla_select_with_lse(q, k, v, keep, 0.25, 1)[1]
    got = il.index_loss_pass(*index, q, k, lse, keep, 0.25, tiles=(32, 64),
                             interpret=True)
    assert all(bool(jnp.isfinite(x).all()) for x in got)
    assert float(got[1]) > 0


@pytest.mark.parametrize("s,tiles,steps,walked", [
    # a q tile's k tiles up to its last query's, twice
    (16384, (256, 512), 2 * 1056, 1056 * 256 * 512 / (16384 * 16385 / 2)),
    (16384, (512, 512), 2 * 528, 528 * 512 * 512 / (16384 * 16385 / 2)),
    (512, (256, 512), 4, 2 * 512 / 513),
    (256, (64, 128), 12, 6 * 64 * 128 / (256 * 257 / 2))])
def the_grid_walks_the_tiles_under_the_diagonal_test(s, tiles, steps,
                                                      walked):
    table = il._steps(s, *tiles)
    assert table.shape == (3, steps) and table.dtype == np.int32
    qi, sweep, kk = table
    assert (kk * tiles[1] <= qi * tiles[0] + tiles[0] - 1).all()
    # a q tile's two sweeps follow each other, each over the same k tiles
    for tile in range(s // tiles[0]):
        own = qi == tile
        first, second = kk[own & (sweep == 0)], kk[own & (sweep == 1)]
        assert np.array_equal(first, second)
        assert np.array_equal(first, np.arange(len(first)))
        assert np.array_equal(sweep[own], np.sort(sweep[own]))
    assert il.walked_over_visible(s, tiles) == pytest.approx(walked,
                                                             rel=1e-12)


def _jaxpr_of(fn, chosen: bool) -> str:
    b, s, h, f, g, index_heads, d = 2, 256, 4, 16, 2, 4, 8

    def zeros(*shape):
        return jnp.zeros(shape, jnp.float32)

    keep = jnp.zeros((b, 1, s // 32, s), jnp.int32) if chosen else None
    lse = zeros(b * h, s) if chosen else None
    return str(jax.make_jaxpr(lambda *t: fn(*t, lse, keep, 0.25))(
        zeros(b, s, index_heads, d), zeros(b, s, d), zeros(b, s, index_heads),
        zeros(b, s, h, f), zeros(b, s, g, f)))


@pytest.mark.parametrize("chosen", [True, False], ids=["chosen", "dense"])
def off_the_tpu_the_xla_form_runs_as_on_the_parent_test(chosen):
    """Here ``index_loss`` traces to the pinned jaxpr, letter for letter,
    under a choice and without one."""
    text = _jaxpr_of(indexer.index_loss, chosen)
    assert "pallas_call" not in text
    harness.pinned("layer/index_loss/xla_" + ("chosen" if chosen else "dense"),
                   text)


def as_a_tpu_process_the_dispatch_takes_the_kernel_test(monkeypatch):
    """Traced as a TPU process: ONE ``index_loss_pass`` call under a choice
    of whole tiles; the XLA form without a choice and at a sequence that is
    no whole tiles."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(il, "_TILE", (64, 128))
    assert _jaxpr_of(indexer.index_loss, True).count(
        "name=index_loss_pass") == 1
    assert "pallas_call" not in _jaxpr_of(indexer.index_loss, False)
    monkeypatch.setattr(il, "_TILE", (64, 192))
    assert "pallas_call" not in _jaxpr_of(indexer.index_loss, True)


@pytest.mark.parametrize("s,chosen,backend,applies", [
    (16384, True, "tpu", True), (16384, True, "cpu", False),
    (16384, False, "tpu", False), (16384 + 256, True, "tpu", False),
    (512, True, "tpu", True), (256, True, "tpu", False),
    (32768, True, "tpu", True), (65536, True, "tpu", False),
    (16384, True, None, False)])
def kernel_applies_by_what_the_call_sees_test(s, chosen, backend, applies):
    assert il.kernel_applies(s, chosen, backend) is applies
    assert il.index_loss_tile(16384) == (256, 512)


def startup_facts_read_what_the_shapes_say_test(monkeypatch):
    """The Keye-VL-2.0 cell: 7 layers' index loss is the kernel on a TPU and
    walks 1,056 tiles of 256 x 512 = 1.0312 of the visible pairs; off it none
    is and the XLA form's four bands walk 1.2499; a cell without the flag
    has neither series.  Both are facts of the program's registry, on the
    start-up line."""
    from homebrewnlp_tpu import telemetry
    from homebrewnlp_tpu.model import declare
    from homebrewnlp_tpu.train import Trainer
    from remat_policy_test import _cell_params
    keye = _cell_params("train_keye_vl_2_0_ep8_s16k")
    pairs = 16384 * 16385 / 2
    assert spatial.index_loss_kernel_layers(keye, "tpu") == 7
    assert spatial.index_loss_kernel_layers(keye, "cpu") == 0
    assert spatial.index_loss_walked_over_visible(keye, "tpu") \
        == pytest.approx(1056 * 256 * 512 / pairs)
    assert spatial.index_loss_walked_over_visible(keye, "cpu") \
        == pytest.approx(0.625 * 16384 ** 2 / pairs)
    sala = _cell_params("train_minicpm_sala_tp2_long")
    assert spatial.index_loss_kernel_layers(sala, "tpu") is None
    assert spatial.index_loss_walked_over_visible(sala, "tpu") is None
    metrics = [fact.metric for fact in declare.facts()]
    assert metrics.index("hbnlp_index_loss_kernel_layers") + 1 \
        == metrics.index("hbnlp_index_loss_walked_over_visible_pairs")
    prev = telemetry.set_registry(telemetry.Registry())
    try:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        line = Trainer(keye, None, None).publish_stash_plan()
        snap = telemetry.snapshot()
    finally:
        telemetry.set_registry(prev)
    assert line.endswith("; index loss kernel 7 layers; index loss walked "
                         "over visible pairs 1.03119")
    assert dict(snap["hbnlp_index_loss_kernel_layers"]["series"]) == {(): 7}
    assert dict(snap["hbnlp_index_loss_walked_over_visible_pairs"]["series"]
                )[()] == pytest.approx(1056 * 256 * 512 / pairs)
