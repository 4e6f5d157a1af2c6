"""Pallas TPU kernel pairs for layer ``gated_delta``'s chunked rule
(``model/gated_delta.py delta_rule``, whose module docstring holds the
arithmetic) on either side of the triangular solve (parallel/delta_solve.py):

    strict = delta_strict_fwd(k, gamma, beta)       the solve's input
    dk, dgamma, dbeta = delta_strict_bwd(.., dstrict)
    o, states = delta_rule_fwd(q, k, v, gamma, T)   T = solve(strict) beta
    dq, dk, dv, dgamma, dT = delta_rule_bwd(.., states, do)

Per chunk of ``l`` positions and head, with ``gamma`` the float32 cumulative
log-decay inside the chunk, ``gamma_C`` its last entry and ``S [d_v, d_k]``
the state entering the chunk:

    W = T (K o exp(gamma)),  U = T V,  V' = U - W S^T
    O = (Q o exp(gamma)) S^T + (Q K^T o Gamma o causal) V'
    S <- exp(gamma_C) S + V'^T (K o exp(gamma_C - gamma))

XLA runs this three groups of ten heads at a time (``grouped_rule``): einsums
over ``[chunks, heads, l, l]`` float32 tensors padded to 128 lanes, a
``lax.scan`` of 256 steps a group with the state through HBM, autodiff's
residuals of all of it and a second forward to make them again.  Here the
grid is ``(batch, block of positions, block of heads)``: a block is one lane
tile of ``_LANE`` positions = ``_LANE / l`` chunks, walked in order with ``S
[heads, d_v, d_k]`` float32 for ALL heads in VMEM scratch; the head blocks
are the inner axis and the heads of a block a ``fori_loop`` (whose trip count
stops at the layer's last head: the head count need not be a multiple of the
block).  Only the batch axis is ``parallel``.

Operands keep the layout XLA:TPU gives the layer's activations, the SEQUENCE
MINOR (the conv's kernels write ``[b, channels, s]``): ``q``, ``k`` are ``[b,
heads * d_k, s]``, ``v``, ``o`` and their cotangents ``[b, heads * d_v, s]``,
``gamma`` rows ``[b, heads, s]`` (and ``[b, s, heads]``, for a head's column:
a masked lane sum).  Every product is formed for the whole lane tile at once:
``T`` of the block's chunks is laid block-diagonally into a ``[_LANE,
_LANE]`` scratch and the decay matrix is masked to the chunks' diagonal
blocks (differences masked BEFORE the ``exp``), so ``W^T``, ``U^T``, ``(Q
K^T o Gamma)^T`` and ``V'^T (..)`` are one full-width MXU pass each for all
the chunks of the tile; only the three products that touch the state run a
chunk after another, full width under a lane mask (the MXU takes 64 columns
in the time of 128).  Nothing shaped ``[.., l, l]`` but ``T`` and ``dT``
reaches HBM.

Backward (``jax.custom_vjp``), ONE reverse walk over the blocks with ``dS``
carried in VMEM.  Residuals: the inputs, ``T`` in the calculation dtype (as
the matmuls take it) and the states entering every chunk ``[b, chunks, heads,
d_v, d_k]`` in the calculation dtype, which the forward writes.  It makes
``W``, ``U``, ``V'`` and the two ``[l, l]`` products again and hands out
``dq``, ``dk``, ``dv``, float32 ``dgamma`` (through ``exp(gamma)``,
``exp(gamma_C - gamma)``, ``exp(gamma_C)`` and ``Gamma``: the row and column
sums of ``dM o M`` taken directly, a tile is ``[_LANE, _LANE]``) and float32
``dT``; the solve's own backward and ``beta``'s are XLA's, outside.

The solve's input ``strict_tril(diag(beta) (K K^T o Gamma))`` is made the
same way (``delta_strict``, a ``jax.custom_vjp`` of its own: one ``K^T K``
pass a head and lane tile, the tile's diagonal blocks written as ``[chunks,
heads, l, l]`` float32, which is what the solve's kernel reads; its backward
makes the product again from ``k``): XLA's form wants ``k`` with the features
minor for that product and lays it, and its cotangent, out again.

Precision is the XLA form's: matmul operands in the calculation dtype with
float32 accumulation, ``gamma`` and the carried state float32; ``dM``,
``dV'`` and ``dS`` are not rounded on their way (autodiff rounds them).

Dispatch (``rule_kernel_applies``): the one predicate the layer and the
``hbnlp_delta_rule_kernel_layers`` gauge both read.  Off the TPU and at
shapes it declines ``model/gated_delta.py grouped_rule``'s XLA form runs: the
kernels' oracle.
"""
from __future__ import annotations

import functools
import typing

import jax
import jax.numpy as jnp

_LANE = 128
_CHUNKS = (16, 32, 64, 128)   # what parallel/delta_solve.py's kernel takes
_HEADS = 8                    # heads a grid step: a sublane tile of rows
_STATE_BYTES = 16 << 20       # the carried state of all heads, in VMEM
_VMEM_LIMIT = 64 << 20
_NT = ((1,), (1,))            # a b^T
_TN = ((0,), (0,))            # a^T b


def head_block(heads: int) -> int:
    """Heads a grid step."""
    return min(heads, _HEADS)


def _padded_heads(heads: int, hb: int) -> int:
    return -(-heads // hb) * hb


def rule_kernel_applies(chunk: int, heads: int, d_k: int, d_v: int,
                        sequence: int,
                        backend: typing.Optional[str] = None) -> bool:
    """Whether ``delta_rule_pair`` runs these shapes here: a TPU backend, a
    power-of-two chunk the solve's kernel also takes (whole chunks a lane
    tile), whole lane tiles of positions, head widths in whole sublane tiles
    of a 16-bit operand, a float32 state of all heads that fits VMEM.  Pure
    in its arguments but for the backend's default."""
    if backend is None:
        backend = jax.default_backend()
    if backend != "tpu" or chunk not in _CHUNKS or min(heads, d_k, d_v) <= 0:
        return False
    state = _padded_heads(heads, head_block(heads)) * d_v \
        * -(-d_k // _LANE) * _LANE * 4
    return (sequence > 0 and sequence % _LANE == 0 and d_k % 16 == 0
            and d_v % 16 == 0 and state <= _STATE_BYTES)


def _dot(a, b, contract=((1,), (0,)), precision=None):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _total(x):
    """``[rows, lanes]`` -> ``[1, 1]``."""
    return jnp.sum(jnp.sum(x, axis=0, keepdims=True), axis=1, keepdims=True)


def _pairs(l: int):
    """Row and column index of a ``[_LANE, _LANE]`` tile of positions and
    which pairs fall into one chunk."""
    row = jax.lax.broadcasted_iota(jnp.int32, (_LANE, _LANE), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (_LANE, _LANE), 1)
    return row, col, row // l == col // l


def _rows(n, d: int):
    """Head ``n``'s rows of a ``[heads * d, _LANE]`` block."""
    from jax.experimental import pallas as pl
    return pl.ds(pl.multiple_of(n * d, d), d)


def _column(cols, index):
    """Head ``index``'s column ``[_LANE, 1]`` of a ``[_LANE, heads]`` block:
    a masked lane sum."""
    return jnp.sum(jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, cols.shape, 1) == index, cols,
        0.0), axis=1, keepdims=True)


def _row_sums(x):
    """``sum_j x[i, j]`` as a ROW ``[1, i]``: a lane sum lands on the
    sublanes, so the MXU turns it — ones against ``x`` at ``highest``, all of
    float32's bits."""
    return _dot(jnp.ones((8, x.shape[1]), jnp.float32), x, _NT,
                jax.lax.Precision.HIGHEST)[:1]


def _lay_diagonal(blk, ref, n, l: int):
    """Head ``n``'s ``[l, l]`` matrices of ``ref [chunks, heads, l, l]`` onto
    the diagonal blocks of the scratch ``blk [_LANE, _LANE]``, whose other
    blocks were zeroed at the walk's start and are never written; returns the
    tile."""
    for p in range(_LANE // l):
        blk[p * l:(p + 1) * l, p * l:(p + 1) * l] = ref[p, n]
    return blk[...]


def _take_diagonal(ref, n, tile, l: int):
    """The diagonal blocks of ``tile [_LANE, _LANE]`` into head ``n`` of
    ``ref [chunks, heads, l, l]``."""
    for p in range(_LANE // l):
        ref[p, n] = tile[p * l:(p + 1) * l, p * l:(p + 1) * l]


class _Head(typing.NamedTuple):
    """What a head of either pass of the rule starts from, for the whole
    lane tile: its index in the layer, its rows, the operands as loaded and
    in float32, the block-diagonal ``T [i, j]``, ``exp(gamma)``,
    ``exp(gamma_C - gamma)`` and ``exp(gamma_C)`` as rows, the operands as
    the MXU reads them (``K o exp(gamma)``, ``K o exp(gamma_C - gamma)``, ``Q
    o exp(gamma)``), ``W^T``, ``U^T``, the masked decay and ``(Q K^T o
    Gamma)^T [j, i]`` in float32."""
    index: typing.Any
    krows: typing.Any
    vrows: typing.Any
    q: typing.Any
    key: typing.Any
    v: typing.Any
    qf: typing.Any
    kf: typing.Any
    t: typing.Any
    from_start: typing.Any
    to_end: typing.Any
    chunk_decay: typing.Any
    k_start: typing.Any
    k_end: typing.Any
    q_start: typing.Any
    w: typing.Any
    u: typing.Any
    decay_t: typing.Any
    mixed_t: typing.Any


def _head(i, k, hb: int, l: int, q_ref, k_ref, v_ref, g_ref, end_ref, gcols,
          t_ref, tblk, causal_t) -> _Head:
    from jax.experimental import pallas as pl
    dtype = q_ref.dtype
    krows = _rows(i, q_ref.shape[0] // hb)
    vrows = _rows(i, v_ref.shape[0] // hb)
    g_row, end_row = g_ref[pl.ds(i, 1), :], end_ref[pl.ds(i, 1), :]
    t = _lay_diagonal(tblk, t_ref, i, l)
    from_start, to_end = jnp.exp(g_row), jnp.exp(end_row - g_row)
    q, key, v = q_ref[krows, :], k_ref[krows, :], v_ref[vrows, :]
    qf, kf = q.astype(jnp.float32), key.astype(jnp.float32)
    k_start = (kf * from_start).astype(dtype)
    decay_t = jnp.exp(jnp.where(
        causal_t, g_row - _column(gcols, k * hb + i), -jnp.inf))
    return _Head(
        k * hb + i, krows, vrows, q, key, v, qf, kf, t, from_start, to_end,
        jnp.exp(end_row), k_start, (kf * to_end).astype(dtype),
        (qf * from_start).astype(dtype),
        _dot(k_start, t, _NT).astype(dtype),                      # [d_k, i]
        _dot(v, t, _NT).astype(dtype),                            # [d_v, i]
        decay_t, _dot(key, q, _TN) * decay_t)


def _causal_t(l: int):
    """The lane index ``[1, _LANE]`` and, transposed (``[j, i]``: the key's
    position on the sublanes), which pairs of one chunk are causal."""
    j, i, same = _pairs(l)
    return jax.lax.broadcasted_iota(jnp.int32, (1, _LANE), 1), \
        same & (i >= j)


def _chunk(p, l: int, lane, chunk_decay_row):
    """Chunk ``p`` of the lane tile: its lanes, its last lane and
    ``exp(gamma_C) [1, 1]``."""
    last = lane == p * l + l - 1
    return lane // l == p, last, jnp.sum(
        jnp.where(last, chunk_decay_row, 0.0), axis=1, keepdims=True)


def _heads_here(k, hb: int, heads: int):
    return jnp.minimum(hb, heads - k * hb)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, end_ref, gcol_ref, t_ref, o_ref,
                st_ref, state, tblk, *, hb: int, heads: int, l: int):
    from jax.experimental import pallas as pl
    c, k = pl.program_id(1), pl.program_id(2)
    dtype = q_ref.dtype

    @pl.when(c == 0)
    def _init():
        state[pl.ds(k * hb, hb)] = jnp.zeros((hb,) + state.shape[1:],
                                             jnp.float32)
        tblk[...] = jnp.zeros_like(tblk)     # off the diagonal blocks: stays

    lane, causal_t = _causal_t(l)
    gcols = gcol_ref[...]

    def head(i, carry):
        h = _head(i, k, hb, l, q_ref, k_ref, v_ref, g_ref, end_ref, gcols,
                  t_ref, tblk, causal_t)
        uf = h.u.astype(jnp.float32)

        def chunk(p, acc):
            v_new, through_state = acc
            here, _, decay = _chunk(p, l, lane, h.chunk_decay)
            entering = state[h.index]                             # [d_v, d_k]
            low = entering.astype(dtype)
            st_ref[p, i] = low
            mine = jnp.where(here, uf - _dot(low, h.w), 0.0).astype(dtype)
            state[h.index] = entering * decay + _dot(mine, h.k_end, _NT)
            return (jnp.where(here, mine, v_new),
                    jnp.where(here, _dot(low, h.q_start), through_state))

        v_new, through_state = jax.lax.fori_loop(
            0, _LANE // l, chunk, (jnp.zeros(uf.shape, dtype),
                                   jnp.zeros(uf.shape, jnp.float32)))
        o_ref[h.vrows, :] = (through_state + _dot(
            v_new, h.mixed_t.astype(dtype))).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, _heads_here(k, hb, heads), head, None)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, end_ref, gcol_ref, t_ref, st_ref,
                do_ref, dq_ref, dk_ref, dv_ref, dg_ref, dend_ref, dt_ref,
                dstate, tblk, *, hb: int, heads: int, l: int):
    """Grid step ``(b, c, k)`` holds lane tile ``tiles - 1 - c``.  ``dg_ref``
    takes ``gamma``'s cotangent a position, ``dend_ref`` what reaches
    ``gamma_C`` through each position (and, at a chunk's last, through
    ``exp(gamma_C) S``): the caller adds a chunk's to its last position."""
    from jax.experimental import pallas as pl
    c, k = pl.program_id(1), pl.program_id(2)
    dtype = q_ref.dtype
    chunks = _LANE // l

    @pl.when(c == 0)
    def _init():
        dstate[pl.ds(k * hb, hb)] = jnp.zeros((hb,) + dstate.shape[1:],
                                              jnp.float32)
        tblk[...] = jnp.zeros_like(tblk)

    lane, causal_t = _causal_t(l)
    gcols = gcol_ref[...]

    def head(i, carry):
        h = _head(i, k, hb, l, q_ref, k_ref, v_ref, g_ref, end_ref, gcols,
                  t_ref, tblk, causal_t)
        uf = h.u.astype(jnp.float32)
        do = do_ref[h.vrows, :]                                   # [d_v, i]
        dv_intra = _dot(do, h.mixed_t.astype(dtype), _NT)         # [d_v, j]
        zero_v = jnp.zeros(uf.shape, jnp.float32)
        zero_k = jnp.zeros(h.qf.shape, jnp.float32)

        def chunk(step, acc):
            dv_new, v_new, dw, dq_start, dk_end, dend = acc
            p = chunks - 1 - step
            here, last, decay = _chunk(p, l, lane, h.chunk_decay)
            low = st_ref[p, i]                                    # [d_v, d_k]
            leaving = dstate[h.index]
            leaving_low = leaving.astype(dtype)
            mine = jnp.where(here, uf - _dot(low, h.w), 0.0).astype(dtype)
            dmine = jnp.where(here, dv_intra + _dot(leaving_low, h.k_end),
                              0.0)
            dmine_low = dmine.astype(dtype)
            do_here = jnp.where(here, do, jnp.zeros_like(do))
            dstate[h.index] = leaving * decay \
                + _dot(do_here, h.q_start, _NT) - _dot(dmine_low, h.w, _NT)
            return (dv_new + dmine, jnp.where(here, mine, v_new),
                    dw - _dot(low, dmine_low, _TN),
                    dq_start + _dot(low, do_here, _TN),
                    dk_end + _dot(leaving_low, mine, _TN),
                    dend + jnp.where(last, decay * _total(
                        leaving * low.astype(jnp.float32)), 0.0))

        dv_new, v_new, dw, dq_start, dk_end, dend = jax.lax.fori_loop(
            0, chunks, chunk, (zero_v, jnp.zeros(uf.shape, dtype), zero_k,
                               zero_k, zero_k, jnp.zeros_like(h.from_start)))
        dv_low, dw_low = dv_new.astype(dtype), dw.astype(dtype)
        dmixed_t = _dot(v_new, do, _TN)                           # [j, i]
        dscores_t = (dmixed_t * h.decay_t).astype(dtype)
        dk_start = _dot(dw_low, h.t)                              # [d_k, j]
        dq_ref[h.krows, :] = (_dot(h.key, dscores_t)
                              + dq_start * h.from_start).astype(dq_ref.dtype)
        dk_ref[h.krows, :] = (
            _dot(h.q, dscores_t, _NT) + dk_start * h.from_start
            + dk_end * h.to_end).astype(dk_ref.dtype)
        dv_ref[h.vrows, :] = _dot(dv_low, h.t).astype(dv_ref.dtype)
        _take_diagonal(dt_ref, i, _dot(dw_low, h.k_start, _TN)
                       + _dot(dv_low, h.v, _TN), l)               # [i, j]
        # Gamma_ij = exp(gamma_i - gamma_j): rows add, columns subtract
        through_decay = dmixed_t * h.mixed_t                      # [j, i]
        to_chunk_end = h.to_end * jnp.sum(dk_end * h.kf, axis=0,
                                          keepdims=True)
        dg_ref[pl.ds(i, 1), :] = h.from_start * jnp.sum(
            dq_start * h.qf + dk_start * h.kf, axis=0, keepdims=True) \
            - to_chunk_end + jnp.sum(through_decay, axis=0, keepdims=True) \
            - _row_sums(through_decay)
        dend_ref[pl.ds(i, 1), :] = to_chunk_end + dend
        return carry

    jax.lax.fori_loop(0, _heads_here(k, hb, heads), head, None)


def _below(l: int):
    """Which pairs ``[i, j]`` of the lane tile are one chunk's, ``i > j``."""
    i, j, same = _pairs(l)
    return same & (i > j)


def _strict_head(n, k, hb: int, k_ref, g_ref, gcols, bcols, below):
    """``(the head's rows of k, K, K K^T o Gamma`` below the diagonal ``[i,
    j]``, ``Gamma`` there, ``beta [i, 1])``."""
    from jax.experimental import pallas as pl
    rows = _rows(n, k_ref.shape[0] // hb)
    key = k_ref[rows, :]
    decay = jnp.exp(jnp.where(below, _column(gcols, k * hb + n)
                              - g_ref[pl.ds(n, 1), :], -jnp.inf))
    return (rows, key, jnp.where(below, _dot(key, key, _TN) * decay, 0.0),
            decay, _column(bcols, k * hb + n))


def _strict_fwd_kernel(k_ref, g_ref, gcol_ref, bcol_ref, s_ref, *, hb: int,
                       heads: int, l: int):
    """``strict_tril(diag(beta) (K K^T o Gamma))`` of the lane tile's
    chunks: the solve's input."""
    from jax.experimental import pallas as pl
    k = pl.program_id(2)
    below, gcols, bcols = _below(l), gcol_ref[...], bcol_ref[...]

    def head(n, carry):
        _, _, scores, _, beta = _strict_head(n, k, hb, k_ref, g_ref, gcols,
                                             bcols, below)
        _take_diagonal(s_ref, n, scores * beta, l)
        return carry

    jax.lax.fori_loop(0, _heads_here(k, hb, heads), head, None)


def _strict_bwd_kernel(k_ref, g_ref, b_ref, gcol_ref, bcol_ref, ds_ref,
                       dk_ref, dg_ref, db_ref, dblk, *, hb: int, heads: int,
                       l: int):
    from jax.experimental import pallas as pl
    c, k = pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        dblk[...] = jnp.zeros_like(dblk)     # off the diagonal blocks: stays

    below, gcols, bcols = _below(l), gcol_ref[...], bcol_ref[...]

    def head(n, carry):
        rows, key, scores, decay, beta = _strict_head(
            n, k, hb, k_ref, g_ref, gcols, bcols, below)
        d = _lay_diagonal(dblk, ds_ref, n, l)                     # [i, j]
        dscores = (d * beta * decay).astype(key.dtype)
        dk_ref[rows, :] = (_dot(key, dscores, _NT) + _dot(key, dscores)
                           ).astype(dk_ref.dtype)
        through = d * scores
        dbeta = _row_sums(through)                                # [1, i]
        db_ref[pl.ds(n, 1), :] = dbeta
        # Gamma_ij = exp(gamma_i - gamma_j): rows add, columns subtract
        dg_ref[pl.ds(n, 1), :] = b_ref[pl.ds(n, 1), :] * dbeta \
            - jnp.sum(through * beta, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, _heads_here(k, hb, heads), head, None)


def _specs(hb: int, l: int, h: int, tiles: int, reverse: bool = False):
    """Block specs on grid (batch, lane tile step, head block): ``tile(d)``
    of a ``[b, heads * d, s]`` operand, the ``[b, heads, s]`` rows, the ``[b,
    s, heads]`` columns and ``a_chunk(m, n)`` of a ``[b, chunks, heads, m,
    n]`` operand (``T``, the states) over the tile's chunks; ``reverse``
    walks the tiles from the last."""
    from jax.experimental import pallas as pl
    chunks = _LANE // l

    def at(c):
        return tiles - 1 - c if reverse else c

    def tile(d):
        return pl.BlockSpec((None, hb * d, _LANE),
                            lambda b, c, k: (b, k, at(c)))

    def a_chunk(*matrix):
        return pl.BlockSpec((None, chunks, hb) + matrix,
                            lambda b, c, k: (b, at(c), k, 0, 0))

    rows = pl.BlockSpec((None, hb, _LANE), lambda b, c, k: (b, k, at(c)))
    cols = pl.BlockSpec((None, _LANE, h), lambda b, c, k: (b, at(c), 0))
    return tile, rows, cols, a_chunk


def _params():
    from jax.experimental.pallas import tpu as pltpu
    # the tile axis carries the state; the head blocks share its scratch
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _scratch(h: int, hb: int, dv: int, dk: int, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return [pltpu.VMEM((_padded_heads(h, hb), dv, dk), jnp.float32),
            pltpu.VMEM((_LANE, _LANE), dtype)]


# jitted so that a model traces each kernel once, not once a layer and pass
@functools.partial(jax.jit, static_argnums=(7, 8, 9))
def _fwd_impl(qt, kt, vt, g, g_end, g_cols, t, chunk, hb, interpret):
    """``qt`` / ``kt [b, heads * d_k, s]``, ``vt [b, heads * d_v, s]``, ``g``
    / ``g_end [b, heads, s]`` float32, ``g_cols [b, s, heads]``, ``t [b,
    chunks, heads, l, l]`` in ``qt``'s dtype -> ``(o^T [b, heads * d_v, s]``,
    entering states ``[b, chunks, heads, d_v, d_k])``, both in ``qt``'s
    dtype."""
    from jax.experimental import pallas as pl
    (bsz, _, s), h = qt.shape, g.shape[1]
    dk, dv = qt.shape[1] // h, vt.shape[1] // h
    tile, rows, cols, a_chunk = _specs(hb, chunk, h, s // _LANE)
    qk, val = tile(dk), tile(dv)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, heads=h, l=chunk),
        grid=(bsz, s // _LANE, -(-h // hb)),
        in_specs=[qk, qk, val, rows, rows, cols, a_chunk(chunk, chunk)],
        out_specs=[val, a_chunk(dv, dk)],
        out_shape=[jax.ShapeDtypeStruct(vt.shape, vt.dtype),
                   jax.ShapeDtypeStruct((bsz, s // chunk, h, dv, dk),
                                        qt.dtype)],
        scratch_shapes=_scratch(h, hb, dv, dk, t.dtype),
        compiler_params=_params(),
        name="delta_rule_fwd",
        interpret=interpret,
    )(qt, kt, vt, g, g_end, g_cols, t)


@functools.partial(jax.jit, static_argnums=(9, 10, 11))
def _bwd_impl(qt, kt, vt, g, g_end, g_cols, t, entering, dot, chunk, hb,
              interpret):
    """-> ``(dq^T, dk^T, dv^T`` in their operands' dtypes, ``dgamma`` and
    ``dgamma_C`` a position ``[b, heads, s]`` float32, ``dT [b, chunks,
    heads, l, l]`` float32)``."""
    from jax.experimental import pallas as pl
    (bsz, _, s), h = qt.shape, g.shape[1]
    dk, dv = qt.shape[1] // h, vt.shape[1] // h
    tile, rows, cols, a_chunk = _specs(hb, chunk, h, s // _LANE, True)
    qk, val, transform = tile(dk), tile(dv), a_chunk(chunk, chunk)
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, heads=h, l=chunk),
        grid=(bsz, s // _LANE, -(-h // hb)),
        in_specs=[qk, qk, val, rows, rows, cols, transform, a_chunk(dv, dk),
                  val],
        out_specs=[qk, qk, val, rows, rows, transform],
        out_shape=[jax.ShapeDtypeStruct(qt.shape, qt.dtype),
                   jax.ShapeDtypeStruct(kt.shape, kt.dtype),
                   jax.ShapeDtypeStruct(vt.shape, vt.dtype), f32(g.shape),
                   f32(g.shape), f32(t.shape)],
        scratch_shapes=_scratch(h, hb, dv, dk, t.dtype),
        compiler_params=_params(),
        name="delta_rule_bwd",
        interpret=interpret,
    )(qt, kt, vt, g, g_end, g_cols, t, entering, dot)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _strict_fwd_impl(kt, g, g_cols, b_cols, chunk, hb, interpret):
    """``kt [b, heads * d_k, s]``, ``g [b, heads, s]``, ``g_cols`` / ``b_cols
    [b, s, heads]`` float32 -> ``strict [b, chunks, heads, l, l]``
    float32."""
    from jax.experimental import pallas as pl
    (bsz, _, s), h = kt.shape, g.shape[1]
    tile, rows, cols, a_chunk = _specs(hb, chunk, h, s // _LANE)
    return pl.pallas_call(
        functools.partial(_strict_fwd_kernel, hb=hb, heads=h, l=chunk),
        grid=(bsz, s // _LANE, -(-h // hb)),
        in_specs=[tile(kt.shape[1] // h), rows, cols, cols],
        out_specs=a_chunk(chunk, chunk),
        out_shape=jax.ShapeDtypeStruct((bsz, s // chunk, h, chunk, chunk),
                                       jnp.float32),
        compiler_params=_params(),
        name="delta_strict_fwd",
        interpret=interpret,
    )(kt, g, g_cols, b_cols)


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _strict_bwd_impl(kt, g, b, g_cols, b_cols, dstrict, chunk, hb,
                     interpret):
    """-> ``(dk^T`` in ``kt``'s dtype, ``dgamma`` and ``dbeta [b, heads, s]``
    float32)``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    (bsz, _, s), h = kt.shape, g.shape[1]
    tile, rows, cols, a_chunk = _specs(hb, chunk, h, s // _LANE)
    qk = tile(kt.shape[1] // h)
    return pl.pallas_call(
        functools.partial(_strict_bwd_kernel, hb=hb, heads=h, l=chunk),
        grid=(bsz, s // _LANE, -(-h // hb)),
        in_specs=[qk, rows, rows, cols, cols, a_chunk(chunk, chunk)],
        out_specs=[qk, rows, rows],
        out_shape=[jax.ShapeDtypeStruct(kt.shape, kt.dtype),
                   jax.ShapeDtypeStruct(g.shape, jnp.float32),
                   jax.ShapeDtypeStruct(g.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_LANE, _LANE), jnp.float32)],
        compiler_params=_params(),
        name="delta_strict_bwd",
        interpret=interpret,
    )(kt, g, b, g_cols, b_cols, dstrict)


def _sequence_minor(x):
    """``[b, s, heads, d]`` -> ``[b, heads * d, s]``."""
    return jnp.swapaxes(x.reshape(x.shape[:2] + (-1,)), 1, 2)


def _operands(q, k, v, gamma, chunk: int):
    """The kernels' views of the rule's inputs; ``gamma_C`` a position is
    its chunk's last ``gamma``."""
    bsz, s, h = gamma.shape
    ends = jnp.broadcast_to(
        gamma.reshape(bsz, s // chunk, chunk, h)[:, :, -1:],
        (bsz, s // chunk, chunk, h)).reshape(bsz, s, h)
    return (_sequence_minor(q), _sequence_minor(k), _sequence_minor(v),
            jnp.swapaxes(gamma, 1, 2), jnp.swapaxes(ends, 1, 2), gamma)


def _forward(q, k, v, gamma, transform, chunk, hb, interpret):
    low = transform.astype(q.dtype)
    ot, entering = _fwd_impl(*_operands(q, k, v, gamma, chunk), low, chunk,
                             hb or head_block(q.shape[2]), interpret)
    return jnp.swapaxes(ot, 1, 2).reshape(v.shape), low, entering


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def delta_rule_pair(q, k, v, gamma, transform, chunk: int,
                    heads_a_block: typing.Optional[int] = None,
                    interpret: bool = False):
    """The chunked rule's ``o [b, s, heads, d_v]`` in ``q``'s dtype from ``q``
    / ``k [b, s, heads, d_k]`` (normalised), ``v [b, s, heads, d_v]``,
    float32 ``gamma [b, s, heads]`` (the cumulative log-decay from each
    chunk's start) and the solved float32 ``transform [b, chunks, heads,
    chunk, chunk]``; shapes as ``rule_kernel_applies`` accepts them."""
    return _forward(q, k, v, gamma, transform, chunk, heads_a_block,
                    interpret)[0]


def _vjp_fwd(q, k, v, gamma, transform, chunk, hb, interpret):
    o, low, entering = _forward(q, k, v, gamma, transform, chunk, hb,
                                interpret)
    return o, (q, k, v, gamma, low, entering)


def _vjp_bwd(chunk, hb, interpret, res, g):
    q, k, v, gamma, low, entering = res
    bsz, s, h = gamma.shape
    dqt, dkt, dvt, dg, dend, dt = _bwd_impl(
        *_operands(q, k, v, gamma, chunk), low, entering,
        _sequence_minor(g.astype(q.dtype)), chunk,
        hb or head_block(h), interpret)

    def chunked(rows):
        return jnp.swapaxes(rows, 1, 2).reshape(bsz, s // chunk, chunk, h)

    at_end = jnp.arange(chunk)[:, None] == chunk - 1
    dgamma = chunked(dg) + jnp.where(
        at_end, jnp.sum(chunked(dend), axis=2, keepdims=True), 0.0)
    return (jnp.swapaxes(dqt, 1, 2).reshape(q.shape),
            jnp.swapaxes(dkt, 1, 2).reshape(k.shape),
            jnp.swapaxes(dvt, 1, 2).reshape(v.shape),
            dgamma.reshape(gamma.shape).astype(gamma.dtype), dt)


delta_rule_pair.defvjp(_vjp_fwd, _vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def delta_strict(k, gamma, beta, chunk: int,
                 heads_a_block: typing.Optional[int] = None,
                 interpret: bool = False):
    """The solve's input ``strict_tril(diag(beta) (K K^T o Gamma)) [b,
    chunks, heads, chunk, chunk]`` float32 from ``k [b, s, heads, d_k]``
    (normalised) and float32 ``gamma`` / ``beta [b, s, heads]``; shapes as
    ``rule_kernel_applies`` accepts them."""
    return _strict_fwd_impl(
        _sequence_minor(k), jnp.swapaxes(gamma, 1, 2), gamma, beta, chunk,
        heads_a_block or head_block(k.shape[2]), interpret)


def _strict_vjp_fwd(k, gamma, beta, chunk, hb, interpret):
    return delta_strict(k, gamma, beta, chunk, hb, interpret), (k, gamma,
                                                                beta)


def _strict_vjp_bwd(chunk, hb, interpret, res, g):
    k, gamma, beta = res
    dkt, dg, db = _strict_bwd_impl(
        _sequence_minor(k), jnp.swapaxes(gamma, 1, 2),
        jnp.swapaxes(beta, 1, 2), gamma, beta, g, chunk,
        hb or head_block(k.shape[2]), interpret)
    return (jnp.swapaxes(dkt, 1, 2).reshape(k.shape),
            jnp.swapaxes(dg, 1, 2).astype(gamma.dtype),
            jnp.swapaxes(db, 1, 2).astype(beta.dtype))


delta_strict.defvjp(_strict_vjp_fwd, _strict_vjp_bwd)
