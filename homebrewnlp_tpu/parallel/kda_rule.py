"""Pallas TPU kernel pairs for layer ``kda``'s chunked rule (``model/kda.py
kda_rule``, whose module docstring holds the arithmetic): a delta rule with a
log-decay a CHANNEL of the key, on either side of the triangular solve
(parallel/delta_solve.py):

    strict, mixed, gamma, q~, k~ = kda_scores_fwd(q, k, g)
                                        A below, A' on and below the diagonal
    dq, dk, dg = kda_scores_bwd(.., dstrict, dmixed, dgamma, dq~, dk~)
    o, states = kda_rule_fwd(q~, k~, v, gamma, T, mixed)
                                        T = solve(beta strict) beta
    dq~, dk~, dv, dgamma, dT, dmixed = kda_rule_bwd(.., states, do)

parallel/delta_rule.py's pair takes ONE decay a head: its ``K^T K o Gamma``
is one full-width product a lane tile because the decay factors out of the
sum over the channels.  Here it does not,

    A_ij  = sum_d k_id k_jd exp(gamma_id - gamma_jd)      i > j
    A'_ij = sum_d q_id k_jd exp(gamma_id - gamma_jd)      i >= j

so the scores are a pair of their own, and everything that decays is a ``[d_k,
lanes]`` tile where that file has a row.  What is letter for letter the same
is imported from it (the matmul, the tile's index pairs, a head's rows, the
head block, the block-diagonal layout of ``T``, the grid's parameters).

Layout, grid and walk are that file's: operands with the SEQUENCE MINOR
(``q``, ``k``, ``g`` / ``gamma [b, heads * d_k, s]``, ``v``, ``o [b, heads *
d_v, s]``; both wrappers take and return them so, ``sequence_minor`` /
``positions_major`` turn them), grid ``(batch, lane tile of 128 positions,
block of heads)``, the heads of a block a ``fori_loop`` that stops at the
layer's last head, the float32 state ``S^T [heads, d_v, d_k]`` of ALL heads in
VMEM scratch along a walk over the lane tiles (only the batch axis is
``parallel``).

The scores (``_scores_*``) are the rule's front.  They read ``q`` and ``k``
as the conv left them and the log-decay a step ``g``, and make on their way
what XLA made in passes of its own: the L2 norms (``q rsqrt(|q|^2 + eps)
d_k^-1/2`` and ``k rsqrt(|k|^2 + eps)`` a head and position in float32,
rounded to the calculation dtype) and ``gamma``, the running sum of ``g``
along each chunk (doubling steps of rolled lanes, float32, rounded through
``kept``); the forward hands all three on to the walk, the backward takes what
reaches them from there, adds its own and goes back through the running sum
(its transpose) and the norms (``factor (I - unit unit^T)``).  The products
keep ``model/kda.py _decayed_scores``' arithmetic: sub-chunks of ``sub``
positions; a sub-chunk against every EARLIER sub-chunk of its chunk is one MXU
product with both operands decayed to the sub-chunk's first position ``r`` —
rows ``x o exp(gamma - gamma_r)``, columns ``k o exp(gamma_r - gamma)``, both
exponents <= 0 and the columns masked BEFORE the ``exp`` — made for the whole
lane tile at once: one product a sub-chunk index (``chunk / sub - 1`` of
them), each taken where the row's sub-chunk has that index; the ``sub x sub``
diagonal blocks elementwise over ``d_k``, a diagonal of the block at a time:
the keys and ``gamma`` rolled ``delta`` lanes, the difference masked BEFORE
the ``exp``, one ``exp`` for ``A`` and ``A'`` both, a sum over the sublanes.
No ``exp`` sees a positive operand, forward or backward.

The walk (``_fwd_kernel`` / ``_bwd_kernel``), per chunk and head with ``S^T
[d_v, d_k]`` the state entering the chunk and everything ``[features,
positions]``:

    W^T = (K o exp(gamma)) T^T,  U^T = V T^T,  V'^T = U^T - S^T W^T
    O^T = S^T (Q o exp(gamma)) + V'^T A'^T      A'^T [j, i] as the scores'
                                                kernel writes it
    S^T <- S^T diag(exp(gamma_C)) + V'^T (K o exp(gamma_C - gamma))^T

``exp(gamma)`` and ``exp(gamma_C - gamma)`` are ``[d_k, lanes]`` tiles (the
chunk's last column of ``gamma`` over the chunk's lanes, read in the kernel),
``exp(gamma_C)`` a ``[1, d_k]`` row of a small operand of its own (``[b,
chunks, heads, 1, d_k]``: a column of the tile would have to be turned).
The forward writes ``o`` and the states entering every chunk in the
calculation dtype; the backward is ONE reverse walk with ``dS`` in VMEM,
makes ``W``, ``U``, ``V'`` again and hands out ``dq``, ``dk``, ``dv``, float32
``dgamma`` (through all three ``exp``s; a chunk's sum of what reaches
``gamma_C`` lands on its last position, the row's part leaves as the small
operand's cotangent), float32 ``dT`` and ``dA'``.  The solve, ``T = X
diag(beta)`` and ``max|T|`` are XLA's, between the two pairs.

Precision is the XLA form's: matmul operands in the calculation dtype with
float32 accumulation, the norms, ``gamma``, the solve's input and the carried
state float32 (``kept``: what ``model/kda.py KEPT`` says, rounded through
where a control lowers it).

Dispatch (``kda_kernel_applies``): the one predicate layer ``kda`` and the
``hbnlp_delta_rule_kernel_layers`` gauge both read.  Off the TPU and at
shapes it declines ``model/kda.py grouped_rule``'s XLA form runs: the
kernels' oracle.
"""
from __future__ import annotations

import functools
import typing

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .delta_rule import (_CHUNKS, _LANE, _NT, _STATE_BYTES, _TN, _dot,
                         _heads_here, _lay_diagonal, _padded_heads, _pairs,
                         _params, _rows, _sequence_minor, _take_diagonal,
                         head_block)


#: the names the forward rules give what a kernel hands the backwards (free
#: where no policy names them): ``kda_scores``' five outputs — ``A``, ``A'^T``,
#: ``gamma``, the normalised ``q`` and ``k`` — and the states entering every
#: chunk of ``kda_rule_pair``'s walk.  A ``jax.checkpoint`` that saves them,
#: the walk's ``o`` and the solve's inverse replays neither forward kernel
SCORES_NAMES = ("kda_strict", "kda_mixed", "kda_gamma", "kda_q_unit",
                "kda_k_unit")
STATES_NAME = "kda_states"


def kda_kernel_applies(chunk: int, heads: int, d_k: int, d_v: int,
                       sequence: int,
                       backend: typing.Optional[str] = None) -> bool:
    """Whether the pairs of this file run these shapes here: a TPU backend,
    a power-of-two chunk the solve's kernel also takes (whole chunks a lane
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


def _lanes():
    return jax.lax.broadcasted_iota(jnp.int32, (1, _LANE), 1)


def _at_lane(tile, lane, of_lane):
    """``tile [rows, lanes]`` with every lane holding the column at
    ``of_lane [1, lanes]`` (a few distinct, static lanes): a column a
    distinct lane, broadcast over the lanes that name it."""
    out = None
    for n in sorted(set(of_lane)):
        col = jnp.broadcast_to(tile[:, n:n + 1], tile.shape)
        out = col if out is None else jnp.where(_named(of_lane, n, lane),
                                                col, out)
    return out


def _named(of_lane, n: int, lane):
    """Which lanes ``of_lane`` (a tuple, a lane each) sends to ``n``: runs
    of lanes, so a comparison or two of the lane index."""
    first = of_lane.index(n)
    last = len(of_lane) - 1 - of_lane[::-1].index(n)
    assert all(of_lane[i] == n for i in range(first, last + 1))
    return (lane >= first) & (lane <= last)


def _chunk_last(l: int):
    """The lane of each lane's chunk's last position."""
    return tuple(i // l * l + l - 1 for i in range(_LANE))


class _Head(typing.NamedTuple):
    """What a head of either pass of the walk starts from, for the whole
    lane tile: its index in the layer, its rows, ``v`` as loaded, ``q`` and
    ``k`` in float32, the block-diagonal ``T [i, j]`` and ``A'^T [j, i]``,
    ``exp(gamma)`` and ``exp(gamma_C - gamma)`` as ``[d_k, lanes]`` tiles,
    the operands as the MXU reads them (``K o exp(gamma)``, ``K o
    exp(gamma_C - gamma)``, ``Q o exp(gamma)``), ``W^T`` and ``U^T``."""
    index: typing.Any
    krows: typing.Any
    vrows: typing.Any
    v: typing.Any
    qf: typing.Any
    kf: typing.Any
    t: typing.Any
    a: typing.Any
    from_start: typing.Any
    to_end: typing.Any
    k_start: typing.Any
    k_end: typing.Any
    q_start: typing.Any
    w: typing.Any
    u: typing.Any


def _head(i, k, hb: int, l: int, q_ref, k_ref, v_ref, g_ref, t_ref, a_ref,
          tblk, ablk, lane) -> _Head:
    dtype = q_ref.dtype
    krows = _rows(i, q_ref.shape[0] // hb)
    vrows = _rows(i, v_ref.shape[0] // hb)
    gamma = g_ref[krows, :]
    t = _lay_diagonal(tblk, t_ref, i, l)
    from_start = jnp.exp(gamma)
    to_end = jnp.exp(_at_lane(gamma, lane, _chunk_last(l)) - gamma)
    v = v_ref[vrows, :]
    qf = q_ref[krows, :].astype(jnp.float32)
    kf = k_ref[krows, :].astype(jnp.float32)
    k_start = (kf * from_start).astype(dtype)
    return _Head(
        k * hb + i, krows, vrows, v, qf, kf, t,
        _lay_diagonal(ablk, a_ref, i, l), from_start, to_end, k_start,
        (kf * to_end).astype(dtype), (qf * from_start).astype(dtype),
        _dot(k_start, t, _NT).astype(dtype),                      # [d_k, i]
        _dot(v, t, _NT).astype(dtype))                            # [d_v, i]


def _start(c, k, hb: int, state, *blocks):
    """A walk's first lane tile: this head block's states and the
    block-diagonal scratches (off their diagonal blocks they stay) zeroed."""
    from jax.experimental import pallas as pl

    @pl.when(c == 0)
    def _init():
        state[pl.ds(k * hb, hb)] = jnp.zeros((hb,) + state.shape[1:],
                                             jnp.float32)
        for blk in blocks:
            blk[...] = jnp.zeros_like(blk)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, e_ref, t_ref, a_ref, o_ref,
                st_ref, state, tblk, ablk, *, hb: int, heads: int, l: int,
                kept):
    from jax.experimental import pallas as pl
    c, k = pl.program_id(1), pl.program_id(2)
    dtype = q_ref.dtype
    _start(c, k, hb, state, tblk, ablk)
    lane = _lanes()

    def head(i, carry):
        h = _head(i, k, hb, l, q_ref, k_ref, v_ref, g_ref, t_ref, a_ref,
                  tblk, ablk, lane)
        uf = h.u.astype(jnp.float32)

        def chunk(p, acc):
            v_new, through_state = acc
            here = lane // l == p
            entering = state[h.index]                             # [d_v, d_k]
            low = entering.astype(dtype)
            st_ref[p, i] = low
            mine = jnp.where(here, uf - _dot(low, h.w), 0.0).astype(dtype)
            state[h.index] = (entering * jnp.exp(e_ref[p, i]) + _dot(
                mine, h.k_end, _NT)).astype(kept).astype(jnp.float32)
            return (jnp.where(here, mine, v_new),
                    jnp.where(here, _dot(low, h.q_start), through_state))

        v_new, through_state = jax.lax.fori_loop(
            0, _LANE // l, chunk, (jnp.zeros(uf.shape, dtype),
                                   jnp.zeros(uf.shape, jnp.float32)))
        o_ref[h.vrows, :] = (through_state + _dot(v_new, h.a)
                             ).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, _heads_here(k, hb, heads), head, None)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, e_ref, t_ref, a_ref, st_ref,
                do_ref, dq_ref, dk_ref, dv_ref, dg_ref, de_ref, dt_ref,
                da_ref, dstate, tblk, ablk, *, hb: int, heads: int, l: int):
    """Grid step ``(b, c, k)`` holds lane tile ``tiles - 1 - c``.  ``dg_ref``
    takes ``gamma``'s cotangent a channel and position — what reaches
    ``gamma_C`` through ``exp(gamma_C - gamma)`` summed over the chunk onto
    its last position —, ``de_ref`` what reaches it through ``exp(gamma_C)
    S``, a ``[1, d_k]`` row a chunk."""
    from jax.experimental import pallas as pl
    c, k = pl.program_id(1), pl.program_id(2)
    dtype = q_ref.dtype
    chunks = _LANE // l
    _start(c, k, hb, dstate, tblk, ablk)
    lane = _lanes()

    def head(i, carry):
        h = _head(i, k, hb, l, q_ref, k_ref, v_ref, g_ref, t_ref, a_ref,
                  tblk, ablk, lane)
        uf = h.u.astype(jnp.float32)
        do = do_ref[h.vrows, :]                                   # [d_v, i]
        dv_intra = _dot(do, h.a, _NT)                             # [d_v, j]
        zero_v = jnp.zeros(uf.shape, jnp.float32)
        zero_k = jnp.zeros(h.qf.shape, jnp.float32)

        def chunk(step, acc):
            dv_new, v_new, dw, dq_start, dk_end = acc
            p = chunks - 1 - step
            here = lane // l == p
            decay = jnp.exp(e_ref[p, i])                          # [1, d_k]
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
            de_ref[p, i] = decay * jnp.sum(
                leaving * low.astype(jnp.float32), axis=0, keepdims=True)
            return (dv_new + dmine, jnp.where(here, mine, v_new),
                    dw - _dot(low, dmine_low, _TN),
                    dq_start + _dot(low, do_here, _TN),
                    dk_end + _dot(leaving_low, mine, _TN))

        dv_new, v_new, dw, dq_start, dk_end = jax.lax.fori_loop(
            0, chunks, chunk, (zero_v, jnp.zeros(uf.shape, dtype), zero_k,
                               zero_k, zero_k))
        dv_low, dw_low = dv_new.astype(dtype), dw.astype(dtype)
        dk_start = _dot(dw_low, h.t)                              # [d_k, j]
        dq_ref[h.krows, :] = (dq_start * h.from_start).astype(dq_ref.dtype)
        dk_ref[h.krows, :] = (dk_start * h.from_start
                              + dk_end * h.to_end).astype(dk_ref.dtype)
        dv_ref[h.vrows, :] = _dot(dv_low, h.t).astype(dv_ref.dtype)
        _take_diagonal(dt_ref, i, _dot(dw_low, h.k_start, _TN)
                       + _dot(dv_low, h.v, _TN), l)               # [i, j]
        _take_diagonal(da_ref, i, _dot(v_new, do, _TN), l)        # [j, i]
        to_chunk_end = h.to_end * dk_end * h.kf
        dgamma = h.from_start * (dq_start * h.qf + dk_start * h.kf) \
            - to_chunk_end
        for p in range(chunks):
            dgamma = dgamma + jnp.where(
                lane == p * l + l - 1, jnp.sum(
                    jnp.where(lane // l == p, to_chunk_end, 0.0), axis=1,
                    keepdims=True), 0.0)
        dg_ref[h.krows, :] = dgamma
        return carry

    jax.lax.fori_loop(0, _heads_here(k, hb, heads), head, None)


def _specs(hb: int, l: int, tiles: int, reverse: bool = False):
    """Block specs on grid (batch, lane tile step, head block): ``tile(d)``
    of a ``[b, heads * d, s]`` operand and ``a_chunk(m, n)`` of a ``[b,
    chunks, heads, m, n]`` operand (``T``, ``A'``, the states, the chunk
    decays' rows) over the tile's chunks; ``reverse`` walks the tiles from
    the last."""
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

    return tile, a_chunk


def _scratch(h: int, hb: int, dv: int, dk: int, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return [pltpu.VMEM((_padded_heads(h, hb), dv, dk), jnp.float32),
            pltpu.VMEM((_LANE, _LANE), dtype),
            pltpu.VMEM((_LANE, _LANE), dtype)]


# jitted so that a model traces each kernel once, not once a layer and pass
@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10))
def _fwd_impl(qt, kt, vt, gt, ends, t, a, chunk, hb, kept, interpret):
    """``qt`` / ``kt [b, heads * d_k, s]``, ``vt [b, heads * d_v, s]``, ``gt
    [b, heads * d_k, s]`` float32, ``ends [b, chunks, heads, 1, d_k]``
    float32, ``t`` / ``a [b, chunks, heads, l, l]`` in ``qt``'s dtype ->
    ``(o^T [b, heads * d_v, s]``, entering states ``[b, chunks, heads, d_v,
    d_k])``, both in ``qt``'s dtype."""
    from jax.experimental import pallas as pl
    (bsz, _, s), h, dk = qt.shape, ends.shape[2], ends.shape[4]
    dv = vt.shape[1] // h
    tile, a_chunk = _specs(hb, chunk, s // _LANE)
    qk, val, square = tile(dk), tile(dv), a_chunk(chunk, chunk)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, heads=h, l=chunk, kept=kept),
        grid=(bsz, s // _LANE, -(-h // hb)),
        in_specs=[qk, qk, val, qk, a_chunk(1, dk), square, square],
        out_specs=[val, a_chunk(dv, dk)],
        out_shape=[jax.ShapeDtypeStruct(vt.shape, vt.dtype),
                   jax.ShapeDtypeStruct((bsz, s // chunk, h, dv, dk),
                                        qt.dtype)],
        scratch_shapes=_scratch(h, hb, dv, dk, t.dtype),
        compiler_params=_params(),
        name="kda_rule_fwd",
        interpret=interpret,
    )(qt, kt, vt, gt, ends, t, a)


@functools.partial(jax.jit, static_argnums=(9, 10, 11))
def _bwd_impl(qt, kt, vt, gt, ends, t, a, entering, dot, chunk, hb,
              interpret):
    """-> ``(dq^T, dk^T, dv^T`` in their operands' dtypes, ``dgamma^T [b,
    heads * d_k, s]`` and ``dends`` float32, ``dT`` and ``dA' [b, chunks,
    heads, l, l]`` float32)``."""
    from jax.experimental import pallas as pl
    (bsz, _, s), h, dk = qt.shape, ends.shape[2], ends.shape[4]
    dv = vt.shape[1] // h
    tile, a_chunk = _specs(hb, chunk, s // _LANE, True)
    qk, val, square = tile(dk), tile(dv), a_chunk(chunk, chunk)
    row = a_chunk(1, dk)
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, heads=h, l=chunk),
        grid=(bsz, s // _LANE, -(-h // hb)),
        in_specs=[qk, qk, val, qk, row, square, square, a_chunk(dv, dk),
                  val],
        out_specs=[qk, qk, val, qk, row, square, square],
        out_shape=[jax.ShapeDtypeStruct(qt.shape, qt.dtype),
                   jax.ShapeDtypeStruct(kt.shape, kt.dtype),
                   jax.ShapeDtypeStruct(vt.shape, vt.dtype), f32(gt.shape),
                   f32(ends.shape), f32(t.shape), f32(a.shape)],
        scratch_shapes=_scratch(h, hb, dv, dk, t.dtype),
        compiler_params=_params(),
        name="kda_rule_bwd",
        interpret=interpret,
    )(qt, kt, vt, gt, ends, t, a, entering, dot)


def sequence_minor(x):
    """``[b, s, heads, d]`` -> ``[b, heads * d, s]``: the kernels' layout."""
    return _sequence_minor(x)


def positions_major(xt, shape):
    """``[b, heads * d, s]`` -> ``shape [b, s, heads, d]``."""
    return jnp.swapaxes(xt, 1, 2).reshape(shape)


def _chunk_ends(gt, h: int, chunk: int):
    """``gamma_C`` a chunk, a ``[1, d_k]`` row a head: ``gamma^T [b, heads *
    d_k, s]`` at each chunk's last position -> ``[b, chunks, heads, 1,
    d_k]``."""
    bsz, width, s = gt.shape
    last = gt.reshape(bsz, h, width // h, s // chunk, chunk)[..., -1]
    return jnp.moveaxis(last, 3, 1)[:, :, :, None]


def _forward(qt, kt, vt, gt, transform, mixed, chunk, hb, kept, interpret):
    low = transform.astype(qt.dtype)
    h = transform.shape[2]
    ot, entering = _fwd_impl(qt, kt, vt, gt, _chunk_ends(gt, h, chunk), low,
                             mixed, chunk, hb or head_block(h), kept,
                             interpret)
    return ot, low, entering


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def kda_rule_pair(qt, kt, vt, gt, transform, mixed, chunk: int,
                  heads_a_block: typing.Optional[int] = None,
                  kept=jnp.float32, interpret: bool = False):
    """The chunked rule's ``o^T [b, heads * d_v, s]`` in ``qt``'s dtype from
    the operands with the sequence minor, as ``kda_scores`` hands them out:
    ``qt`` / ``kt [b, heads * d_k, s]`` (normalised), ``vt [b, heads * d_v,
    s]``, float32 ``gt [b, heads * d_k, s]`` (``gamma``: the cumulative
    log-decay a channel from each chunk's start), the solved float32
    ``transform`` and, in ``qt``'s dtype, the decayed scores ``mixed``
    (``A'^T``), both ``[b, chunks, heads, chunk, chunk]``; shapes as
    ``kda_kernel_applies`` accepts them.  The carried state is rounded
    through ``kept``."""
    return _forward(qt, kt, vt, gt, transform, mixed, chunk, heads_a_block,
                    kept, interpret)[0]


def _vjp_fwd(qt, kt, vt, gt, transform, mixed, chunk, hb, kept, interpret):
    ot, low, entering = _forward(qt, kt, vt, gt, transform, mixed, chunk, hb,
                                 kept, interpret)
    return ot, (qt, kt, vt, gt, low, mixed,
                checkpoint_name(entering, STATES_NAME))


def _vjp_bwd(chunk, hb, kept, interpret, res, g):
    qt, kt, vt, gt, low, mixed, entering = res
    bsz, width, s = gt.shape
    h = low.shape[2]
    dqt, dkt, dvt, dgt, dends, dt, da = _bwd_impl(
        qt, kt, vt, gt, _chunk_ends(gt, h, chunk), low, mixed, entering,
        g.astype(qt.dtype), chunk, hb or head_block(h), interpret)
    # what reaches gamma_C through exp(gamma_C) S lands on the chunk's last
    at_end = jnp.arange(chunk) == chunk - 1
    dgt = dgt.reshape(bsz, h, width // h, s // chunk, chunk) + jnp.where(
        at_end, jnp.moveaxis(dends[:, :, :, 0], 1, 3)[..., None], 0.0)
    return (dqt, dkt, dvt, dgt.reshape(gt.shape).astype(gt.dtype), dt,
            da.astype(mixed.dtype))


kda_rule_pair.defvjp(_vjp_fwd, _vjp_bwd)


# ---- the decayed scores ----------------------------------------------------

def _roll(x, shift: int):
    """``x[:, i - shift]`` at lane ``i`` (the lanes wrap)."""
    from jax.experimental.pallas import tpu as pltpu
    shift %= _LANE
    return pltpu.roll(x, shift, 1) if shift else x


def _onto(tile, lands):
    """``tile [rows, lanes]`` summed over the lanes that ``lands [from,
    onto]`` sends to one lane, at that lane: the MXU at ``highest`` (all of
    float32's bits; a lane sum a run of lanes would go through the XLU a
    vreg at a time)."""
    return _dot(tile, lands.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)


def _running_sum(x, lane, l: int, kept, reverse: bool = False):
    """``x [rows, lanes]`` summed along each chunk of ``l`` lanes up to and
    with every lane (``reverse``: from it on: the transpose), in doubling
    steps of rolled lanes; the running sums are rounded through ``kept``."""
    def through(y):
        return y if kept == jnp.float32 else y.astype(kept).astype(
            jnp.float32)

    x = through(x)
    step = 1
    while step < l:
        if reverse:
            x = x + jnp.where(lane % l < l - step, _roll(x, -step), 0.0)
        else:
            x = through(x + jnp.where(lane % l >= step, _roll(x, step), 0.0))
        step *= 2
    return x


class _Scored(typing.NamedTuple):
    """What a head of either pass of the scores starts from, for the whole
    lane tile: its rows, ``gamma`` (the running sum of the log-decays inside
    each chunk), the unit ``q`` and ``k`` in float32 with the factors that
    made them (``rsqrt(|.|^2 + eps)``, a row), both as the layer's rule
    reads them (scaled, rounded to the calculation dtype) and those in
    float32, the rows' decay ``exp(gamma - gamma_r)`` from their sub-chunk's
    first position and both operands under it as the MXU reads them."""
    rows: typing.Any
    gamma: typing.Any
    q_unit: typing.Any
    k_unit: typing.Any
    q_factor: typing.Any
    k_factor: typing.Any
    q_low: typing.Any
    k_low: typing.Any
    kf: typing.Any
    qf: typing.Any
    rows_decay: typing.Any
    k_rows: typing.Any
    q_rows: typing.Any


def _unit(raw, eps: float):
    """``(raw rsqrt(|raw|^2 + eps)``, that factor ``[1, lanes])``: a column
    a position."""
    factor = jax.lax.rsqrt(jnp.sum(jnp.square(raw), axis=0, keepdims=True)
                           + eps)
    return raw * factor, factor


def _scored(n, hb: int, q_ref, k_ref, g_ref, lane, l: int, sub: int,
            q_scale: float, eps: float, kept) -> _Scored:
    dtype = q_ref.dtype
    rows = _rows(n, q_ref.shape[0] // hb)
    gamma = _running_sum(g_ref[rows, :], lane, l, kept)
    q_unit, q_factor = _unit(q_ref[rows, :].astype(jnp.float32), eps)
    k_unit, k_factor = _unit(k_ref[rows, :].astype(jnp.float32), eps)
    q_low, k_low = (q_unit * q_scale).astype(dtype), k_unit.astype(dtype)
    kf, qf = k_low.astype(jnp.float32), q_low.astype(jnp.float32)
    rows_decay = jnp.exp(gamma - _at_lane(
        gamma, lane, tuple(i // sub * sub for i in range(_LANE))))
    return _Scored(rows, gamma, q_unit, k_unit, q_factor, k_factor, q_low,
                   k_low, kf, qf, rows_decay, (kf * rows_decay).astype(dtype),
                   (qf * rows_decay).astype(dtype))


def _sub_start(a: int, l: int, sub: int):
    """The lane of sub-chunk ``a``'s first position in each lane's chunk."""
    return tuple(i // l * l + a * sub for i in range(_LANE))


def _columns(h: _Scored, a: int, lane, l: int, sub: int, dtype):
    """The keys BEFORE sub-chunk ``a`` of their chunk decayed up to its first
    position (0 from there on: masked before the ``exp``): ``(the decay, the
    operand)``."""
    decay = jnp.exp(jnp.where(
        lane % l < a * sub,
        _at_lane(h.gamma, lane, _sub_start(a, l, sub)) - h.gamma, -jnp.inf))
    return decay, (h.kf * decay).astype(dtype)


def _diagonal(h: _Scored, delta: int, lane, sub: int):
    """``(exp(gamma - gamma')``, that times the keys)`` with ``gamma'`` and
    the keys those ``delta`` positions earlier, 0 where that is another
    sub-chunk (masked before the ``exp``)."""
    decay = jnp.exp(jnp.where(
        lane % sub >= delta, h.gamma - _roll(h.gamma, delta), -jnp.inf))
    return decay, _roll(h.kf, delta) * decay


def _scores_fwd_kernel(q_ref, k_ref, g_ref, s_ref, m_ref, gamma_ref, qn_ref,
                       kn_ref, *, hb: int, heads: int, l: int, sub: int,
                       q_scale: float, eps: float, kept):
    """``A [i, j]`` below the diagonal (float32: the solve's input before
    ``diag(beta)``) and ``A'^T [j, i]`` on and below it (the calculation
    dtype: what the walk multiplies by) of the lane tile's chunks, with what
    they were made from for the walk: ``gamma`` and the normalised ``q`` and
    ``k``."""
    from jax.experimental import pallas as pl
    k = pl.program_id(2)
    dtype = q_ref.dtype
    lane = _lanes()
    j, i, same = _pairs(l)
    ahead = i - j

    def head(n, carry):
        h = _scored(n, hb, q_ref, k_ref, g_ref, lane, l, sub, q_scale, eps,
                    kept)
        gamma_ref[h.rows, :] = h.gamma
        qn_ref[h.rows, :] = h.q_low
        kn_ref[h.rows, :] = h.k_low
        zero = jnp.zeros((_LANE, _LANE), jnp.float32)
        strict_t = mixed_t = zero                                 # [j, i]
        for a in range(1, l // sub):
            _, k_cols = _columns(h, a, lane, l, sub, dtype)
            take = same & (i % l // sub == a)
            strict_t = jnp.where(take, _dot(k_cols, h.k_rows, _TN), strict_t)
            mixed_t = jnp.where(take, _dot(k_cols, h.q_rows, _TN), mixed_t)
        within, within_q = zero, zero
        for delta in range(sub):
            _, earlier = _diagonal(h, delta, lane, sub)
            on = ahead == delta
            if delta:
                within = jnp.where(on, jnp.sum(
                    h.kf * earlier, axis=0, keepdims=True), within)
            within_q = jnp.where(on, jnp.sum(
                h.qf * earlier, axis=0, keepdims=True), within_q)
        _take_diagonal(s_ref, n, (strict_t + within).T, l)
        _take_diagonal(m_ref, n, (mixed_t + within_q).astype(dtype), l)
        return carry

    jax.lax.fori_loop(0, _heads_here(k, hb, heads), head, None)


def _scores_bwd_kernel(q_ref, k_ref, g_ref, ds_ref, dm_ref, dgamma_ref,
                       dqn_ref, dkn_ref, dq_ref, dk_ref, dg_ref, sblk, mblk,
                       *, hb: int, heads: int, l: int, sub: int,
                       q_scale: float, eps: float, kept):
    """``dgamma_ref``, ``dqn_ref``, ``dkn_ref``: what reaches ``gamma`` and
    the normalised ``q`` and ``k`` from beyond the scores (the walk).  Each
    joins the scores' own before the running sum's transpose and the
    norms' derivative ``factor (I - unit unit^T)``."""
    from jax.experimental import pallas as pl
    c, k = pl.program_id(1), pl.program_id(2)
    dtype = q_ref.dtype

    @pl.when(c == 0)
    def _init():
        sblk[...] = jnp.zeros_like(sblk)     # off the diagonal blocks: stays
        mblk[...] = jnp.zeros_like(mblk)

    lane = _lanes()
    j, i, same = _pairs(l)
    ahead = i - j

    def head(n, carry):
        h = _scored(n, hb, q_ref, k_ref, g_ref, lane, l, sub, q_scale, eps,
                    kept)
        ds_t = jnp.where(same & (ahead > 0),
                         _lay_diagonal(sblk, ds_ref, n, l).T, 0.0)
        dm_t = jnp.where(same & (ahead >= 0), _lay_diagonal(
            mblk, dm_ref, n, l).astype(jnp.float32), 0.0)         # [j, i]
        zero = jnp.zeros(h.kf.shape, jnp.float32)
        dk_rows = dq_rows = dkf = dgamma = zero
        for a in range(1, l // sub):
            decay, k_cols = _columns(h, a, lane, l, sub, dtype)
            take = same & (i % l // sub == a)
            gs = jnp.where(take, ds_t, 0.0).astype(dtype)
            gm = jnp.where(take, dm_t, 0.0).astype(dtype)
            dk_cols = (_dot(h.k_rows, gs, _NT) + _dot(h.q_rows, gm, _NT)
                       ) * decay                                  # [d_k, j]
            dk_rows = dk_rows + _dot(k_cols, gs)                  # [d_k, i]
            dq_rows = dq_rows + _dot(k_cols, gm)
            dkf = dkf + dk_cols
            # the columns' exponent is gamma at the sub-chunk's first
            # position less gamma: the first adds a chunk's, each subtracts
            through = dk_cols * h.kf
            dgamma = dgamma + _onto(
                through, j // l * l + a * sub == i) - through
        dkf = dkf + dk_rows * h.rows_decay
        dqf = dq_rows * h.rows_decay
        # the rows' exponent is gamma less gamma at their sub-chunk's first
        through = (dk_rows * h.kf + dq_rows * h.qf) * h.rows_decay
        dgamma = dgamma + through - _onto(through, j // sub * sub == i)
        for delta in range(sub):
            decay, earlier = _diagonal(h, delta, lane, sub)
            on = ahead == delta
            dwithin_q = jnp.sum(jnp.where(on, dm_t, 0.0), axis=0,
                                keepdims=True)                    # [1, i]
            dqf = dqf + dwithin_q * earlier
            both = dwithin_q * h.qf
            if delta:
                dwithin = jnp.sum(jnp.where(on, ds_t, 0.0), axis=0,
                                  keepdims=True)
                dkf = dkf + dwithin * earlier
                both = both + dwithin * h.kf
            # ``earlier`` = the rolled keys x the decay: the keys' share
            # goes back ``delta`` lanes, the exponent's adds here and
            # subtracts there
            dkf = dkf + _roll(both * decay, -delta)
            if delta:
                through = both * earlier
                dgamma = dgamma + through - _roll(through, -delta)
        dqf = (dqf + dqn_ref[h.rows, :].astype(jnp.float32)) * q_scale
        dkf = dkf + dkn_ref[h.rows, :].astype(jnp.float32)
        dq_ref[h.rows, :] = (h.q_factor * (dqf - h.q_unit * jnp.sum(
            dqf * h.q_unit, axis=0, keepdims=True))).astype(dq_ref.dtype)
        dk_ref[h.rows, :] = (h.k_factor * (dkf - h.k_unit * jnp.sum(
            dkf * h.k_unit, axis=0, keepdims=True))).astype(dk_ref.dtype)
        dg_ref[h.rows, :] = _running_sum(dgamma + dgamma_ref[h.rows, :],
                                         lane, l, jnp.float32, True)
        return carry

    jax.lax.fori_loop(0, _heads_here(k, hb, heads), head, None)


class _Scores(typing.NamedTuple):
    """The scores' static arguments: heads, chunk and sub-chunk, ``q``'s
    scale and the norms' ``eps``, what the running sums are kept in, heads a
    block, interpret mode."""
    heads: int
    chunk: int
    sub: int
    q_scale: float
    eps: float
    kept: typing.Any
    hb: int
    interpret: bool


def _scores_call(kernel, st: _Scores, name: str, **more):
    from jax.experimental import pallas as pl
    return pl.pallas_call(
        functools.partial(kernel, hb=st.hb, heads=st.heads, l=st.chunk,
                          sub=st.sub, q_scale=st.q_scale, eps=st.eps,
                          kept=st.kept),
        compiler_params=_params(), name=name, interpret=st.interpret, **more)


@functools.partial(jax.jit, static_argnums=(3,))
def _scores_fwd_impl(qt, kt, gt, st: _Scores):
    """``qt`` / ``kt [b, heads * d_k, s]`` as the conv left them, ``gt`` the
    same in float32 (the log-decay a step) -> ``(strict [b, chunks, heads,
    l, l]`` float32 ``[i, j]``, ``mixed^T`` the same shape in ``qt``'s dtype
    ``[j, i]``, ``gamma^T`` float32 and the normalised ``q^T`` and ``k^T`` in
    ``qt``'s dtype, each ``[b, heads * d_k, s])``."""
    bsz, width, s = qt.shape
    tile, a_chunk = _specs(st.hb, st.chunk, s // _LANE)
    qk, square = tile(width // st.heads), a_chunk(st.chunk, st.chunk)
    shape = (bsz, s // st.chunk, st.heads, st.chunk, st.chunk)
    return _scores_call(
        _scores_fwd_kernel, st, "kda_scores_fwd",
        grid=(bsz, s // _LANE, -(-st.heads // st.hb)),
        in_specs=[qk, qk, qk], out_specs=[square, square, qk, qk, qk],
        out_shape=[jax.ShapeDtypeStruct(shape, jnp.float32),
                   jax.ShapeDtypeStruct(shape, qt.dtype),
                   jax.ShapeDtypeStruct(gt.shape, jnp.float32),
                   jax.ShapeDtypeStruct(qt.shape, qt.dtype),
                   jax.ShapeDtypeStruct(kt.shape, kt.dtype)],
    )(qt, kt, gt)


@functools.partial(jax.jit, static_argnums=(8,))
def _scores_bwd_impl(qt, kt, gt, dstrict, dmixed, dgamma, dqn, dkn,
                     st: _Scores):
    """-> ``(dq^T, dk^T`` in their operands' dtype, ``dg^T`` float32)``,
    each ``[b, heads * d_k, s]``."""
    from jax.experimental.pallas import tpu as pltpu
    bsz, width, s = qt.shape
    tile, a_chunk = _specs(st.hb, st.chunk, s // _LANE)
    qk, square = tile(width // st.heads), a_chunk(st.chunk, st.chunk)
    return _scores_call(
        _scores_bwd_kernel, st, "kda_scores_bwd",
        grid=(bsz, s // _LANE, -(-st.heads // st.hb)),
        in_specs=[qk, qk, qk, square, square, qk, qk, qk],
        out_specs=[qk, qk, qk],
        out_shape=[jax.ShapeDtypeStruct(qt.shape, qt.dtype),
                   jax.ShapeDtypeStruct(kt.shape, kt.dtype),
                   jax.ShapeDtypeStruct(gt.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_LANE, _LANE), jnp.float32),
                        pltpu.VMEM((_LANE, _LANE), dmixed.dtype)],
    )(qt, kt, gt, dstrict, dmixed, dgamma, dqn, dkn)


def _scores_static(heads, chunk, sub, q_scale, eps, kept, hb, interpret):
    return _Scores(heads, chunk, sub, float(q_scale), float(eps),
                   jnp.dtype(kept), hb or head_block(heads), interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(3, 11)))
def kda_scores(qt, kt, gt, heads: int, chunk: int, sub: int, q_scale: float,
               eps: float, kept=jnp.float32,
               heads_a_block: typing.Optional[int] = None,
               interpret: bool = False):
    """The front of the rule, from the operands with the sequence minor:
    ``qt`` / ``kt [b, heads * d_k, s]`` as the conv left them and float32
    ``gt`` of the same shape, the log-decay a channel and step (``<= 0``).
    Inside: ``q rsqrt(|q|^2 + eps) q_scale`` and ``k rsqrt(|k|^2 + eps)`` a
    head and position in float32, rounded to ``qt``'s dtype; ``gamma``, the
    running sum of ``gt`` along each chunk (through ``kept``).  Returns ``(A
    [b, chunks, heads, chunk, chunk]`` float32 below the diagonal, rows ``i``
    — the solve's input before ``diag(beta)`` —, ``A'^T`` the same shape in
    ``qt``'s dtype on and below it, rows ``j``, float32 ``gamma^T`` and the
    normalised ``q^T`` and ``k^T`` — what ``kda_rule_pair`` takes)``;
    sub-chunks of ``sub`` positions (a divisor of ``chunk``); shapes as
    ``kda_kernel_applies`` accepts them."""
    return tuple(_scores_fwd_impl(qt, kt, gt, _scores_static(
        heads, chunk, sub, q_scale, eps, kept, heads_a_block, interpret)))


def _scores_vjp_fwd(qt, kt, gt, *static):
    return tuple(checkpoint_name(out, name) for out, name in zip(
        kda_scores(qt, kt, gt, *static), SCORES_NAMES)), (qt, kt, gt)


def _scores_vjp_bwd(*args):
    *static, (qt, kt, gt), g = args
    return tuple(_scores_bwd_impl(qt, kt, gt, *g, _scores_static(*static)))


kda_scores.defvjp(_scores_vjp_fwd, _scores_vjp_bwd)
