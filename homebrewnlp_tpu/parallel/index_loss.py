"""The learned indexer's ``index_loss`` pass (model/indexer.py: the module
docstring there is the specification) as ONE Pallas TPU kernel,
``index_loss_pass``: every ``[q tile, k tile]`` float32 plane of it — the 32
attention heads' probabilities over the kept keys and their head mean
``pbar``, the index heads' ReLU scores and their weighted sum, the
log-softmax over the kept keys, ``d L_I / d I`` — lives and dies in VMEM; what
reaches HBM is the value, the largest kept ``|I|`` and the three hand-made
gradients.

The grid walks, a q tile at a time, the k tiles AT OR UNDER its diagonal and
no other (a table of ``(q tile, sweep, k tile)`` steps made from the shapes:
no dead step), TWICE: a row's ``log softmax_S(I)`` needs its ``logsumexp`` over
ALL its kept keys before any ``d_score``, so sweep 0 makes the scores and an
online ``logsumexp`` only, sweep 1 makes them again beside everything else
(the scores twice: 3.1 of the kernel's 20.9 ms a layer on a v5e at the
Keye-VL-2.0 cell's shape; a q tile's whole score row left in VMEM by the
first sweep for the second measured 1.4 ms less for 16 MB that grow with the
sequence, and was not taken: PERF.md section 6, PR 64).  In a cell the query
heads of a K/V group are stacked under each other for ONE ``[group x tq, f] x
[f, tk]`` matmul a group, the index heads for one ``[H x tq, d] x [d, tk]``,
and their backward is two: ``d_q`` of all heads ``[H x tq, tk] x [tk, d]``
(handed back lane-dense, ``[s, H x d]``: a 64-wide float32 output would be
padded to 128 lanes in HBM) and ``grad_k`` ``[tk, H x tq] x [H x tq, d]``,
the latter into ONE resident ``[s, d]`` output block across the grid.

Every row statistic (a head's ``lse``, an index head's weight, the scores'
running maximum, sum and normaliser, ``d_w``'s partial sums) is ``[tq, 128]``
with the row's value — or its lane's share of it — in the lanes, from start
to end: the online ``logsumexp`` runs a lane at a time (128 streams a row,
merged once a q tile), ``d_w``, the value and the maximum fold a cell's lane
tiles elementwise and cross lanes once a q tile.  Nothing turns between lanes
and sublanes inside a cell (PERF.md section 6, PR 63: that was 63% of the
selected forward's cell).  The kernel is bound by the MXU, on passes that are
half empty (the index scores' contraction and both gradients' outputs are 64
wide): the elementwise work, the ``exp``s among it, hides under the matmuls.

Precision: the model's own — matmul operands as they come (bfloat16 in the
cell) accumulated in float32, every plane, exponential and accumulator
float32; the two gradient contractions take ``d_logits`` rounded to the index
key's dtype, which is what XLA's float32 ``dot`` at the default precision
feeds the MXU on a TPU (one bfloat16 pass; ``scripts/kernel_parity.py
--only-index-loss`` reads it off the chip).  ``index_features ** -0.5`` is
folded into the weights (exact at a power of two: 64 ** -0.5).
"""
from __future__ import annotations

import functools
import typing

import jax
import jax.numpy as jnp
import numpy as np

from .flash_attention import (_KERNEL_VMEM_BUDGET, _NEG_INF, _STAT_LANES,
                              KEEP_WORD, _select_seen)

#: ``(q tile, k tile)``: the q tile as short as the choice's words allow (8
#: sublanes of 32 queries), which keeps the stacked planes of a cell — ``[16
#: x tq, tk]`` float32 — at 8 MB (PR 64, on the chip at the Keye-VL-2.0
#: cell's shape, ms a call: 256 x 512 20.9, 256 x 1,024 21.3, 256 x 256 27.7,
#: 512 x 512 29.0)
_TILE = (256, 512)


#: positions up to which ``grad_k``'s resident ``[s, d]`` float32 block (two
#: buffers of 128 lanes) leaves the planes their room in ``_KERNEL_VMEM_BUDGET``
#: (Mosaic takes 32,768 for a v5e at the Keye-VL-2.0 widths; 65,536 is the
#: whole budget)
_MAX_POSITIONS = 32768


def index_loss_tile(s: int) -> typing.Optional[typing.Tuple[int, int]]:
    """``_TILE`` where the sequence is whole tiles of it and at most
    ``_MAX_POSITIONS``, else None."""
    whole = s % _TILE[0] == 0 and s % _TILE[1] == 0
    return _TILE if whole and s <= _MAX_POSITIONS else None


def kernel_applies(s: int, chosen: bool,
                   backend: typing.Optional[str] = None) -> bool:
    """Whether ``model/indexer.py index_loss`` runs the kernel: on a TPU,
    ``chosen`` (the choice is held as bits and the attention's ``lse`` over it
    came with it), a sequence of whole tiles."""
    if backend is None:
        backend = jax.default_backend()
    return backend == "tpu" and chosen and index_loss_tile(s) is not None


def _steps(s: int, tq: int, tk: int) -> np.ndarray:
    """``[3, steps]`` int32: the ``(q tile, sweep, k tile)`` of every grid
    step — a q tile's k tiles up to the one its last query lies in, twice."""
    return np.asarray([(qi, sweep, kk) for qi in range(s // tq)
                       for sweep in (0, 1)
                       for kk in range((qi * tq + tq - 1) // tk + 1)],
                      np.int32).T


def walked_over_visible(s: int, tiles: typing.Tuple[int, int]) -> float:
    """The (query, key) pairs a sweep of the kernel walks over the ``s (s +
    1) / 2`` a query may see."""
    tq, tk = tiles
    return _steps(s, tq, tk).shape[1] // 2 * tq * tk / (s * (s + 1) / 2)


def _lanes(x, width: int):
    """``[rows, lanes]`` -> ``[rows, width]``: whole copies of ``x`` side by
    side (its vregs again: nothing moves)."""
    from jax.experimental.pallas import tpu as pltpu
    copies = width // x.shape[1]
    return x if copies == 1 else pltpu.repeat(x, copies, axis=1)


def _fold(x, lanes: int, op):
    """``[rows, width]`` -> ``[rows, lanes]``: the lane tiles of ``x`` under
    ``op``, elementwise."""
    parts = [x[:, c:c + lanes] for c in range(0, x.shape[1], lanes)]
    return functools.reduce(op, parts)


def _kernel(qi_ref, sweep_ref, kk_ref, q_ref, k_ref, qx_ref, kx_ref, w_ref,
            lse_ref, keep_ref, val_ref, top_ref, dq_ref, gk_ref, dw_ref,
            lse_rep, w_rep, m_ref, l_ref, norm_ref, dw_acc, val_acc, top_acc,
            dq_acc, *, tq: int, tk: int, lanes: int, heads: int, group: int,
            index_heads: int, scale: float, i_scale: float, inv_rows: float):
    """Grid (batch, steps).  ``q_ref [tq, heads x f]``, ``k_ref [tk, kv heads
    x f]``, ``qx_ref [H, tq, d]``, ``kx_ref [tk, d]``, ``w_ref [H, tq]``,
    ``lse_ref [heads, tq]``, ``keep_ref [tq / 32, tk]`` -> ``val_ref`` /
    ``top_ref [8, lanes]`` partials of the q tile, ``dq_ref [tq, H x d]``,
    ``gk_ref [s, d]`` (resident), ``dw_ref [H, tq]``."""
    from jax.experimental import pallas as pl

    t = pl.program_id(1)
    qi, sweep, kk = qi_ref[t], sweep_ref[t], kk_ref[t]
    last = (qi * tq + tq - 1) // tk
    f = q_ref.shape[1] // heads
    d = kx_ref.shape[1]

    @pl.when(t == 0)
    def _first():
        gk_ref[...] = jnp.zeros_like(gk_ref)

    @pl.when((sweep == 0) & (kk == 0))
    def _start():
        # a row of queries along the lanes -> its values down the sublanes,
        # each in every lane: a sublane broadcast and one aligned transpose
        for h in range(heads):
            lse_rep[h] = jnp.broadcast_to(lse_ref[h:h + 1, :], (lanes, tq)).T
        for j in range(index_heads):
            w_rep[j] = jnp.broadcast_to(w_ref[j:j + 1, :] * i_scale,
                                        (lanes, tq)).T
        # a FINITE first maximum: a lane that has kept nothing yet reads
        # exp(-inf - m) = 0, never exp(-inf + inf)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        for ref in (l_ref, dw_acc, val_acc, top_acc, dq_acc):
            ref[...] = jnp.zeros_like(ref)

    seen = _select_seen(keep_ref, qi, kk, tq, tk, 1)

    def raw_scores():
        """``[H x tq, tk]``: every index head's ``qI . kI``, unscaled."""
        return jax.lax.dot_general(
            qx_ref[...].reshape(index_heads * tq, d), kx_ref[...],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    def weighted(raw):
        total = jnp.zeros((tq, tk), jnp.float32)
        for j in range(index_heads):
            total = total + _lanes(w_rep[j], tk) * jnp.maximum(
                raw[j * tq:(j + 1) * tq], 0.0)
        return total

    @pl.when(sweep == 0)
    def _normaliser():
        score = jnp.where(seen, weighted(raw_scores()), -jnp.inf)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, _fold(score, lanes, jnp.maximum))
        l_new = l_ref[...] * jnp.exp(m_prev - m_new) + _fold(
            jnp.exp(score - _lanes(m_new, tk)), lanes, jnp.add)
        m_ref[...] = m_new
        l_ref[...] = l_new

        @pl.when(kk == last)
        def _merge():
            big = jnp.broadcast_to(m_new.max(-1, keepdims=True), (tq, lanes))
            total = jnp.sum(l_new * jnp.exp(m_new - big), -1, keepdims=True)
            norm_ref[...] = big + jnp.log(jnp.maximum(
                jnp.broadcast_to(total, (tq, lanes)), 1e-30))

    @pl.when(sweep == 1)
    def _loss():
        pbar = jnp.zeros((tq, tk), jnp.float32)
        for kv in range(heads // group):
            stacked = jnp.concatenate(
                [q_ref[:, h * f:(h + 1) * f]
                 for h in range(kv * group, (kv + 1) * group)], axis=0)
            logits = jax.lax.dot_general(
                stacked, k_ref[:, kv * f:(kv + 1) * f],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            for r in range(group):
                pbar = pbar + jnp.exp(logits[r * tq:(r + 1) * tq] - _lanes(
                    lse_rep[kv * group + r], tk))
        pbar = jnp.where(seen, pbar, 0.0) * (1.0 / heads)
        raw = raw_scores()
        score = weighted(raw)
        log_index = score - _lanes(norm_ref[...], tk)
        val_acc[...] += _fold(jnp.where(pbar > 0, pbar * (jnp.log(
            jnp.maximum(pbar, 1e-38)) - log_index), 0.0), lanes, jnp.add)
        top_acc[...] = jnp.maximum(top_acc[...], _fold(
            jnp.where(seen, jnp.abs(score), 0.0), lanes, jnp.maximum))
        # pbar sums to one over the kept keys: d L / d I = softmax - pbar
        d_score = jnp.where(seen, jnp.exp(log_index) - pbar, 0.0) * inv_rows
        d_logits = []
        for j in range(index_heads):
            raw_j = raw[j * tq:(j + 1) * tq]
            dw_acc[j] += _fold(d_score * jnp.maximum(raw_j, 0.0), lanes,
                               jnp.add)
            d_logits.append(jnp.where(
                raw_j > 0, d_score * _lanes(w_rep[j], tk), 0.0
            ).astype(kx_ref.dtype))
        d_logits = jnp.concatenate(d_logits, axis=0)
        dq_acc[...] += jax.lax.dot_general(
            d_logits, kx_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).reshape(index_heads, tq, d)
        rows = pl.ds(pl.multiple_of(kk * tk, tk), tk)
        gk_ref[rows, :] += jax.lax.dot_general(
            d_logits, qx_ref[...].reshape(index_heads * tq, d),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

        @pl.when(kk == last)
        def _finish():
            fold = (tq // val_ref.shape[0], val_ref.shape[0], lanes)
            val_ref[...] = val_acc[...].reshape(fold).sum(0)
            top_ref[...] = top_acc[...].reshape(fold).max(0)
            for j in range(index_heads):
                dw_ref[j:j + 1, :] = jnp.sum(dw_acc[j].T, axis=0,
                                             keepdims=True) * i_scale
                # the heads side by side, as ``[b, s, H, d]`` lies in HBM
                dq_ref[:, j * d:(j + 1) * d] = dq_acc[j]


@functools.partial(jax.jit, static_argnames=(
    "kernel", "name", "scale", "tiles", "interpret", "more_scratch",
    "vmem_limit"))
def _call(kernel, name: str, q_index, k_index, weight, q, k, lse, keep,
          scale: float, tiles: typing.Tuple[int, int], interpret: bool,
          more_scratch=(), vmem_limit: int = _KERNEL_VMEM_BUDGET):
    """``kernel`` (``_kernel``'s signature; float32 scratch of the shapes
    ``more_scratch`` after its own) over the grid, the table and the blocks
    of the pass, and its five results as ``index_loss_pass`` hands them
    back.  A ``jax.jit``: the
    body's ~1,500 operations are traced ONCE a process and shape, not once a
    layer of each of the three traces a run makes of the model (start-up,
    the reference check, the step): half a second to a second each on the
    chip's host, 14 s of the cell's set-up (PR 64)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, f = q.shape
    g = k.shape[2]
    index_heads, d = q_index.shape[2:]
    tq, tk = tiles
    lanes = min(_STAT_LANES, tk)
    fold = min(8, tq)
    steps = _steps(s, tq, tk)

    def q_side(*block):
        return pl.BlockSpec((None,) + block, lambda bi, t, qi, sweep, kk:
                            (bi,) + (0,) * (len(block) - 2) + (qi[t], 0))

    def k_side(width):
        return pl.BlockSpec((None, tk, width), lambda bi, t, qi, sweep, kk:
                            (bi, kk[t], 0))

    def row_side(rows):
        return pl.BlockSpec((None, rows, tq), lambda bi, t, qi, sweep, kk:
                            (bi, 0, qi[t]))

    partial = pl.BlockSpec((None, None, fold, lanes),
                           lambda bi, t, qi, sweep, kk: (bi, qi[t], 0, 0))
    partials = jax.ShapeDtypeStruct((b, s // tq, fold, lanes), jnp.float32)
    stat = pltpu.VMEM((tq, lanes), jnp.float32)
    stats = pltpu.VMEM((index_heads, tq, lanes), jnp.float32)
    val, top, dq, gk, dw = pl.pallas_call(
        functools.partial(
            kernel, tq=tq, tk=tk, lanes=lanes, heads=h, group=h // g,
            index_heads=index_heads, scale=scale, i_scale=d ** -0.5,
            inv_rows=1.0 / (b * s)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, steps.shape[1]),
            in_specs=[q_side(tq, h * f), k_side(g * f),
                      q_side(index_heads, tq, d), k_side(d),
                      row_side(index_heads), row_side(h),
                      pl.BlockSpec((None, tq // KEEP_WORD, tk),
                                   lambda bi, t, qi, sweep, kk:
                                   (bi, qi[t], kk[t]))],
            out_specs=[partial, partial, q_side(tq, index_heads * d),
                       pl.BlockSpec((None, s, d), lambda bi, t, *_:
                                    (bi, 0, 0)),
                       row_side(index_heads)],
            # lse_rep, w_rep, m, l, norm, dw_acc, val_acc, top_acc, dq_acc
            scratch_shapes=[pltpu.VMEM((h, tq, lanes), jnp.float32), stats,
                            stat, stat, stat, stats, stat, stat,
                            pltpu.VMEM((index_heads, tq, d), jnp.float32),
                            *(pltpu.VMEM(shape, jnp.float32)
                              for shape in more_scratch)]),
        out_shape=[partials, partials,
                   jax.ShapeDtypeStruct((b, s, index_heads * d), jnp.float32),
                   jax.ShapeDtypeStruct((b, s, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, index_heads, s), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        name=name, interpret=interpret,
    )(*steps, q.reshape(b, s, h * f), k.reshape(b, s, g * f),
      jnp.swapaxes(q_index, 1, 2), k_index,
      jnp.swapaxes(weight.astype(jnp.float32), 1, 2), lse.reshape(b, h, s),
      keep[:, 0])
    return jnp.sum(val) / (b * s), jnp.max(top), \
        dq.reshape(b, s, index_heads, d), gk, jnp.swapaxes(dw, 1, 2)


def index_loss_pass(q_index, k_index, weight, q, k, lse, keep, scale: float,
                    tiles: typing.Optional[typing.Tuple[int, int]] = None,
                    interpret: bool = False):
    """``model/indexer.py index_loss``'s five results — ``(L_I, the largest
    kept |I|, d L_I / d qI [b, s, H, d], d L_I / d kI [b, s, d], d L_I / d w
    [b, s, H])``, float32 — of ``q_index [b, s, H, d]``, ``k_index [b, s,
    d]``, ``weight [b, s, H]``, the attention's ``q [b, s, h, f]`` and ``k
    [b, s, g, f]``, its ``lse [b * h, s]`` over the kept keys and the choice
    as bits ``keep [b, 1, s / KEEP_WORD, s]``.  ``tiles``: ``(q tile, k
    tile)``, ``index_loss_tile``'s where None."""
    return _call(_kernel, "index_loss_pass", q_index, k_index, weight, q, k,
                 lse, keep, scale, tiles or index_loss_tile(q.shape[1]),
                 interpret)
