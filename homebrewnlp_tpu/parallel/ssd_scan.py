"""Pallas TPU kernel pair for layer ``mamba``'s chunked scan (``model/mamba.py
ssd``, whose docstring holds the arithmetic):

    y, states = ssd_scan_fwd(x, dt, a_cum, B, C)
    dx, ddt, da_cum, dB, dC = ssd_scan_bwd(.., y, states, dy)

Per chunk ``c`` of ``l`` positions and head ``h``, with ``a`` the float32
cumulative ``dt A`` inside the chunk and ``S [p, n]`` the state entering it:

    scores = C B^T                                  shared by the heads
    decay[i, j] = exp(where(i >= j, a_i - a_j, -inf))     masked BEFORE exp
    y = (scores * decay)(x * dt) + (C S^T) * exp(a)
    S <- exp(a_l) S + ((x * dt) * exp(a_l - a))^T B

XLA runs this as einsums over ``[b, chunks, heads, l, l]`` tensors (537 MB in
float32 a layer in the Granite cell, written and read by the forward, by the
``checkpoint`` replay and by autodiff's backward) and a ``lax.scan`` over
chunk states in HBM.  Here nothing shaped ``[.., l, l]`` reaches HBM: the grid
is ``(batch, chunk, block of heads)``, the chunk axis walked in order with
``S [heads, p, n]`` float32 for ALL heads in VMEM scratch, the heads of a
block a ``fori_loop`` in the body.  The head blocks are the INNER axis, so the
``[l, l]`` products the heads share (``scores``; in the backward its
cotangent, summed over the heads in float32) are made once a chunk and the
``dB`` / ``dC`` output blocks are revisited on consecutive steps, as
``causal_conv.py``'s ``dw`` is; only the batch axis is ``parallel`` (a v5e has
one TensorCore a chip).

Operands keep the layout XLA:TPU gives layer ``mamba``'s activations, the
SEQUENCE MINOR: ``x``, ``y`` and their cotangents are ``[b, heads * p, s]``
(``y^T [p, l] = (x * dt)^T M^T`` fills the lanes where ``[l, p = 64]`` half
fills a tile), ``dt`` and ``a`` are rows ``[b, heads, s]``.  A decay matrix
needs ``a`` along the sublanes too: ``a`` also comes as ``[b, s, heads]``
and a head's column is a masked lane sum.  ``B`` and ``C`` are small and come
in both orientations.

Backward (``jax.custom_vjp``), ONE reverse walk over the chunks with ``dS``
carried in VMEM.  Residuals: the inputs, ``y`` (the call's own output) and
the entering states ``[b, chunks, heads, p, n]`` float32 the forward writes
(67 MB a layer in the cell, alive for one block's backward:
``hbnlp_ssd_state_bytes`` declares them).  Per head

    dM = dy (x * dt)^T                   d(x * dt) = M^T dy + (B dS'^T) * exp(a_l - a)
    dscores += dM * decay                dS = exp(a_l) dS' + (dy * exp(a))^T C
    dC += (dy * exp(a)) S                dB += ((x * dt) * exp(a_l - a)) dS'

and after the last head block ``dC += dscores B``, ``dB += dscores^T C``.
The log-decay's gradient needs no ``[l, l]`` reduction: the row sums of ``dM *
M`` plus the state term are ``sum_p dy * y``, the column sums plus theirs
``sum_p (x * dt) * d(x * dt)``, and the chunk's last position takes the two
``a_l`` terms.  ``da_cum`` and ``ddt`` (the part through ``x * dt``) are
float32 rows; the reverse cumulative sum to ``d dt`` / ``dA`` is XLA's,
outside.

Precision is the XLA form's: matmul operands in the calculation dtype with
float32 accumulation, everything else float32; ``dM`` and ``dscores`` are not
rounded on their way (autodiff rounds both).

Groups of ``B`` / ``C`` (``[b, s, groups, n]``; ``[b, s, n]`` = one group,
today's graph): head ``h`` reads group ``h // (heads / groups)``.  A head
block lies inside ONE group (``head_block`` of the group's heads), so the
``B`` / ``C`` blocks of a grid step are its group's ``n`` columns of ``[b, s,
groups * n]``; ``scores`` are made once a GROUP and chunk, at the group's first
head block — not once a head —, the ``dscores`` accumulator starts there and
that group's ``dB`` / ``dC`` blocks, summed over its heads in float32 and
revisited on its consecutive steps, are finished at its last block.  The
state scratch stays ``[heads, p, n]`` for all heads.

Dispatch (``ssd_kernel_applies``): the one predicate the layer and the
``hbnlp_ssd_scan_kernel_layers`` gauge both read.  Off the TPU and at shapes
it declines ``model/mamba.py ssd``'s XLA form runs: the kernels' oracle.
"""
from __future__ import annotations

import functools
import typing

import jax
import jax.numpy as jnp

_LANE = 128
_MAX_CHUNK = 512       # [l, l] float32 temporaries: 1 MB each at 512
_BLOCK_ROWS = 512      # heads x head features a grid step
_VMEM_LIMIT = 64 << 20


def head_block(heads: int, head_features: int, groups: int = 1) -> int:
    """Heads a grid step: the largest divisor of ONE GROUP's heads whose rows
    fit ``_BLOCK_ROWS`` and fill whole sublane tiles of the float32 ``dt`` /
    ``a`` rows (a multiple of 8), else all the group's heads."""
    heads //= groups
    for hb in range(min(heads, max(1, _BLOCK_ROWS // head_features)), 0, -1):
        if heads % hb == 0 and hb % 8 == 0:
            return hb
    return heads


def ssd_kernel_applies(sequence: int, chunk: int, heads: int,
                       head_features: int, state: int,
                       backend: typing.Optional[str] = None,
                       groups: int = 1) -> bool:
    """Whether ``ssd_scan`` runs these shapes here: a TPU backend, whole
    chunks of whole lane tiles (the sequence is on the lanes) no longer than
    ``_MAX_CHUNK``, a state of whole lane tiles, head features in whole
    sublane tiles of a 16-bit operand (16); with more than one group of
    ``B`` / ``C``, whole groups whose head blocks fill whole sublane tiles of
    the ``dt`` / ``a`` rows (8).  Pure in its arguments but for the backend's
    default."""
    if backend is None:
        backend = jax.default_backend()
    return (backend == "tpu" and 0 < chunk <= _MAX_CHUNK
            and chunk % _LANE == 0 and sequence % chunk == 0
            and state % _LANE == 0 and head_features % 16 == 0 and heads > 0
            and (groups == 1 or (heads % groups == 0 and head_block(
                heads, head_features, groups) % 8 == 0)))


def log_decay(dt, a, chunk: int):
    """``a_cum [b, s, heads]`` float32: the cumulative ``dt a`` from each
    chunk's start (``dt [b, s, heads]``, ``a [heads]``)."""
    bsz, s, h = dt.shape
    return jnp.cumsum((dt * a).reshape(bsz, s // chunk, chunk, h),
                      axis=2).reshape(bsz, s, h)


def _dot(a, b, contract=((1,), (0,))):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


def _total(x):
    """``[rows, lanes]`` -> ``[1, 1]``."""
    return jnp.sum(jnp.sum(x, axis=0, keepdims=True), axis=1, keepdims=True)


def _head(i, k, hb: int, p: int, a_ref, dt_ref, acols, lanes):
    """What every head of either pass starts from: its index in the layer,
    its rows of the block, ``a`` and ``dt`` as rows ``[1, l]``, ``a`` as a
    column ``[l, 1]`` and at the chunk's end ``[1, 1]``."""
    from jax.experimental import pallas as pl
    index = k * hb + i
    rows = pl.ds(pl.multiple_of(i * p, p), p)
    a_row, dt_row = a_ref[pl.ds(i, 1), :], dt_ref[pl.ds(i, 1), :]
    a_col = jnp.sum(jnp.where(lanes == index, acols, 0.0), axis=1,
                    keepdims=True)
    l = a_row.shape[1]
    if l > _LANE:
        return index, rows, a_row, dt_row, a_col, a_row[:, -1:]
    # a chunk of ONE lane tile: Mosaic cannot broadcast the [1, 1] slice at
    # lane 127 over sublanes and lanes ("Broadcast in both sublanes and
    # lanes", compiled for a described v5e); a masked lane sum leaves the
    # same value at lane 0
    last = jax.lax.broadcasted_iota(jnp.int32, (1, l), 1) == l - 1
    return index, rows, a_row, dt_row, a_col, jnp.sum(
        jnp.where(last, a_row, 0.0), axis=1, keepdims=True)


def _first(k, per_group: int):
    """Whether head block ``k`` is the first of its group of ``per_group``
    blocks (0: one group, all the layer's blocks)."""
    return k % per_group == 0 if per_group else k == 0


def _last(k, per_group: int):
    from jax.experimental import pallas as pl
    return k % per_group == per_group - 1 if per_group \
        else k == pl.num_programs(2) - 1


def _fwd_kernel(x_ref, dt_ref, a_ref, acol_ref, b_ref, ct_ref, y_ref, st_ref,
                state, scores_t, *, hb: int, p: int, per_group: int):
    from jax.experimental import pallas as pl
    c, k = pl.program_id(1), pl.program_id(2)
    l, dtype = x_ref.shape[1], x_ref.dtype

    @pl.when(_first(k, per_group))
    def _shared():
        scores_t[...] = _dot(b_ref[...], ct_ref[...])           # [j, i]

    @pl.when(c == 0)
    def _init():
        state[pl.ds(k * hb, hb)] = jnp.zeros((hb,) + state.shape[1:],
                                             jnp.float32)

    j = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    causal_t = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1) >= j
    acols = acol_ref[...]
    lanes = jax.lax.broadcasted_iota(jnp.int32, acols.shape, 1)

    def head(i, carry):
        index, rows, a_row, dt_row, a_col, a_end = _head(
            i, k, hb, p, a_ref, dt_ref, acols, lanes)
        decay_t = jnp.exp(jnp.where(causal_t, a_row - a_col, -jnp.inf))
        mixed_t = (scores_t[...] * decay_t).astype(dtype)
        x_dt = x_ref[rows, :].astype(jnp.float32) * dt_row        # [p, l]
        entering = state[index]                                   # [p, n]
        st_ref[i] = entering
        y_ref[rows, :] = _dot(x_dt.astype(dtype), mixed_t) \
            + _dot(entering.astype(dtype), ct_ref[...]) * jnp.exp(a_row)
        weighted = (x_dt * jnp.exp(a_end - a_row)).astype(dtype)
        state[index] = entering * jnp.exp(a_end) + _dot(weighted, b_ref[...])
        return carry

    jax.lax.fori_loop(0, hb, head, None)


def _bwd_kernel(x_ref, dt_ref, a_ref, acol_ref, b_ref, bt_ref, c_ref, ct_ref,
                y_ref, g_ref, st_ref, dx_ref, ddt_ref, da_ref, db_ref,
                dbt_ref, dc_ref, dstate, scores, dscores, *, hb: int, p: int,
                per_group: int):
    """Grid step ``(b, c, k)`` holds chunk ``chunks - 1 - c``.  ``db_ref [l,
    n]`` takes the state terms of the group's ``dB``, ``dbt_ref [n, l]`` the
    ``dscores`` one (transposed: ``C^T dscores`` is a plain matmul); the
    caller adds them."""
    from jax.experimental import pallas as pl
    c, k = pl.program_id(1), pl.program_id(2)
    l, dtype = x_ref.shape[1], x_ref.dtype

    @pl.when(_first(k, per_group))
    def _shared():
        scores[...] = _dot(c_ref[...], bt_ref[...])              # [i, j]
        dscores[...] = jnp.zeros_like(dscores)
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    @pl.when(c == 0)
    def _init():
        dstate[pl.ds(k * hb, hb)] = jnp.zeros((hb,) + dstate.shape[1:],
                                              jnp.float32)

    i_pos = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    causal = i_pos >= jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    acols = acol_ref[...]
    lanes = jax.lax.broadcasted_iota(jnp.int32, acols.shape, 1)
    at_end = jax.lax.broadcasted_iota(jnp.int32, (1, l), 1) == l - 1
    over_p = ((0,), (0,))      # contract the head features, both on dim 0

    def head(i, carry):
        index, rows, a_row, dt_row, a_col, a_end = _head(
            i, k, hb, p, a_ref, dt_ref, acols, lanes)
        decay = jnp.exp(jnp.where(causal, a_col - a_row, -jnp.inf))
        mixed = (scores[...] * decay).astype(dtype)               # [i, j]
        xs = x_ref[rows, :].astype(jnp.float32)                   # [p, l]
        x_dt = xs * dt_row
        g = g_ref[rows, :]
        leaving, entering = dstate[index], st_ref[i]              # [p, n]
        to_end, from_start = jnp.exp(a_end - a_row), jnp.exp(a_row)
        # the operands as the MXU reads them: the log-decay's gradient below
        # is a difference of sums that cancel only where both sides hold the
        # very products the matmuls made
        g_mxu, x_dt_mxu = g.astype(dtype), x_dt.astype(dtype)
        g_state = (g * from_start).astype(dtype)
        weighted = (x_dt * to_end).astype(dtype)
        through_state = _dot(leaving.astype(dtype), bt_ref[...]) * to_end
        d_xdt = _dot(g_mxu, mixed) + through_state
        dx_ref[rows, :] = (d_xdt * dt_row).astype(dx_ref.dtype)
        ddt_ref[pl.ds(i, 1), :] = jnp.sum(d_xdt * xs, axis=0, keepdims=True)
        dscores[...] += _dot(g_mxu, x_dt_mxu, over_p) * decay
        dc_ref[...] += _dot(g_state, entering.astype(dtype), over_p)
        db_ref[...] += _dot(weighted, leaving.astype(dtype), over_p)
        dstate[index] = leaving * jnp.exp(a_end) + _dot(g_state, c_ref[...])
        x_dt = x_dt_mxu.astype(jnp.float32)
        # a_l: the decay to the chunk's end of every position, and of S
        ends = _total(x_dt * through_state) \
            + _total(entering * leaving) * jnp.exp(a_end)
        da = jnp.sum(g_mxu.astype(jnp.float32) * y_ref[rows, :]
                     - x_dt * d_xdt, axis=0, keepdims=True)
        da_ref[pl.ds(i, 1), :] = da + jnp.where(at_end, ends, 0.0)
        return carry

    jax.lax.fori_loop(0, hb, head, None)

    @pl.when(_last(k, per_group))
    def _shared_out():
        d = dscores[...].astype(dtype)
        dc_ref[...] += _dot(d, b_ref[...])
        dbt_ref[...] = _dot(ct_ref[...], d)


def _specs(hb: int, p: int, l: int, h: int, n: int, chunks: int,
           reverse: bool, per_group: int = 0):
    """Block specs on grid (batch, chunk step, head block): the ``[b, heads *
    p, s]`` tile, the ``[b, heads, s]`` rows, ``[b, s, heads]`` /  ``[b, s,
    groups * n]`` columns, ``[b, groups * n, s]`` rows — the ``n`` of the head
    block's group: ``per_group`` blocks a group, 0 = one group — and the
    ``[b, chunks, heads, p, n]`` states; ``reverse`` walks the chunks from
    the last."""
    from jax.experimental import pallas as pl

    def at(c):
        return chunks - 1 - c if reverse else c

    def group(k):
        return k // per_group if per_group else 0

    tile = pl.BlockSpec((None, hb * p, l), lambda b, c, k: (b, k, at(c)))
    rows = pl.BlockSpec((None, hb, l), lambda b, c, k: (b, k, at(c)))
    heads = pl.BlockSpec((None, l, h), lambda b, c, k: (b, at(c), 0))
    cols = pl.BlockSpec((None, l, n), lambda b, c, k: (b, at(c), group(k)))
    cols_t = pl.BlockSpec((None, n, l), lambda b, c, k: (b, group(k), at(c)))
    states = pl.BlockSpec((None, None, hb, p, n),
                          lambda b, c, k: (b, at(c), k, 0, 0))
    return tile, rows, heads, cols, cols_t, states


def _params():
    from jax.experimental.pallas import tpu as pltpu
    # the chunk axis carries the state, the head blocks revisit dB / dC
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _blocks_a_group(heads: int, hb: int, groups: int) -> int:
    """Head blocks a group of ``B`` / ``C``; 0 stands for one group."""
    if groups == 1:
        return 0
    if heads % groups or (heads // groups) % hb:
        raise ValueError(f"a block of {hb} heads does not lie inside one of "
                         f"{groups} groups of {heads} heads")
    return heads // groups // hb


# jitted so that a model traces each kernel once, not once a layer and pass
@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _fwd_impl(xt, dt, a, a_cols, b_mat, ct, chunk, hb, interpret, groups=1):
    """``xt [b, heads * p, s]``, ``dt`` / ``a [b, heads, s]``, ``a_cols [b,
    s, heads]``, ``b_mat [b, s, groups * n]``, ``ct [b, groups * n, s]`` ->
    ``(y^T [b, heads * p, s]`` float32, entering states ``[b, chunks, heads,
    p, n]``)``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    (bsz, rows_, s), h, n = xt.shape, dt.shape[1], b_mat.shape[-1] // groups
    p, chunks = rows_ // h, s // chunk
    per_group = _blocks_a_group(h, hb, groups)
    tile, rows, heads, cols, cols_t, states = _specs(
        hb, p, chunk, h, n, chunks, False, per_group)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, p=p, per_group=per_group),
        grid=(bsz, chunks, h // hb),
        in_specs=[tile, rows, rows, heads, cols, cols_t],
        out_specs=[tile, states],
        out_shape=[jax.ShapeDtypeStruct(xt.shape, jnp.float32),
                   jax.ShapeDtypeStruct((bsz, chunks, h, p, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((h, p, n), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32)],
        compiler_params=_params(),
        name="ssd_scan_fwd",
        interpret=interpret,
    )(xt, dt, a, a_cols, b_mat, ct)


@functools.partial(jax.jit, static_argnums=(11, 12, 13, 14))
def _bwd_impl(xt, dt, a, a_cols, b_mat, bt, c_mat, ct, yt, gt, entering,
              chunk, hb, interpret, groups=1):
    """-> ``(dx^T`` in ``xt``'s dtype, ``ddt`` and ``da [b, heads, s]``
    float32, ``dB``'s state terms ``[b, s, groups * n]``, its ``dscores``
    term ``[b, groups * n, s]``, ``dC [b, s, groups * n]``, float32)``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    (bsz, _, s), h, n = xt.shape, dt.shape[1], b_mat.shape[-1] // groups
    p, chunks = xt.shape[1] // h, s // chunk
    per_group = _blocks_a_group(h, hb, groups)
    tile, rows, heads, cols, cols_t, states = _specs(
        hb, p, chunk, h, n, chunks, True, per_group)
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, p=p, per_group=per_group),
        grid=(bsz, chunks, h // hb),
        in_specs=[tile, rows, rows, heads, cols, cols_t, cols, cols_t, tile,
                  tile, states],
        out_specs=[tile, rows, rows, cols, cols_t, cols],
        out_shape=[jax.ShapeDtypeStruct(xt.shape, xt.dtype), f32(dt.shape),
                   f32(dt.shape), f32(b_mat.shape), f32(ct.shape),
                   f32(b_mat.shape)],
        scratch_shapes=[pltpu.VMEM((h, p, n), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32)],
        compiler_params=_params(),
        name="ssd_scan_bwd",
        interpret=interpret,
    )(xt, dt, a, a_cols, b_mat, bt, c_mat, ct, yt, gt, entering)


def _sequence_minor(x):
    """``[b, s, heads, p]`` -> ``[b, heads * p, s]``."""
    return jnp.swapaxes(x.reshape(x.shape[:2] + (-1,)), 1, 2)


def _groups(b_mat) -> int:
    """Groups of a ``B`` / ``C`` operand: ``[b, s, n]`` is one."""
    return b_mat.shape[2] if b_mat.ndim == 4 else 1


def _flat(mat):
    """``[b, s, groups, n]`` -> ``[b, s, groups * n]``; ``[b, s, n]`` as it
    is."""
    return mat.reshape(mat.shape[:2] + (-1,)) if mat.ndim == 4 else mat


def _forward(x, dt, a_cum, b_mat, c_mat, chunk, hb, interpret):
    groups = _groups(b_mat)
    hb = hb or head_block(*x.shape[2:], groups)
    yt, entering = _fwd_impl(
        _sequence_minor(x), jnp.swapaxes(dt, 1, 2), jnp.swapaxes(a_cum, 1, 2),
        a_cum, _flat(b_mat), jnp.swapaxes(_flat(c_mat), 1, 2), chunk, hb,
        interpret, groups)
    return jnp.swapaxes(yt, 1, 2).reshape(x.shape), entering


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def ssd_scan(x, dt, a_cum, b_mat, c_mat, chunk: int,
             heads_a_block: typing.Optional[int] = None,
             interpret: bool = False):
    """The chunked scan's ``y [b, s, heads, p]`` float32 (without the ``D
    x`` skip) from ``x [b, s, heads, p]``, float32 ``dt`` and ``a_cum [b, s,
    heads]`` (``a_cum``: ``log_decay``, the cumulative ``dt A`` from each
    chunk's start),
    ``b_mat`` / ``c_mat [b, s, n]`` (one group for all heads) or ``[b, s,
    groups, n]``; shapes as ``ssd_kernel_applies`` accepts them."""
    return _forward(x, dt, a_cum, b_mat, c_mat, chunk, heads_a_block,
                    interpret)[0]


def _vjp_fwd(x, dt, a_cum, b_mat, c_mat, chunk, hb, interpret):
    y, entering = _forward(x, dt, a_cum, b_mat, c_mat, chunk, hb, interpret)
    return y, (x, dt, a_cum, b_mat, c_mat, y, entering)


def _vjp_bwd(chunk, hb, interpret, res, g):
    x, dt, a_cum, b_mat, c_mat, y, entering = res
    groups = _groups(b_mat)
    hb = hb or head_block(*x.shape[2:], groups)
    b_flat, c_flat = _flat(b_mat), _flat(c_mat)
    dxt, ddt, da, db, dbt, dc = _bwd_impl(
        _sequence_minor(x), jnp.swapaxes(dt, 1, 2), jnp.swapaxes(a_cum, 1, 2),
        a_cum, b_flat, jnp.swapaxes(b_flat, 1, 2), c_flat,
        jnp.swapaxes(c_flat, 1, 2), _sequence_minor(y),
        _sequence_minor(g.astype(jnp.float32)), entering, chunk, hb,
        interpret, groups)
    return (jnp.swapaxes(dxt, 1, 2).reshape(x.shape),
            jnp.swapaxes(ddt, 1, 2).astype(dt.dtype),
            jnp.swapaxes(da, 1, 2).astype(a_cum.dtype),
            (db + jnp.swapaxes(dbt, 1, 2)).astype(b_mat.dtype
                                                  ).reshape(b_mat.shape),
            dc.astype(c_mat.dtype).reshape(c_mat.shape))


ssd_scan.defvjp(_vjp_fwd, _vjp_bwd)
