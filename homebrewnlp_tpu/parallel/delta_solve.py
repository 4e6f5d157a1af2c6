"""Pallas TPU kernel pair for the gated delta rule's triangular solve (layer
``gated_delta``, ``model/gated_delta.py _inverse_unit_lower``):

    X = (I + N)^-1                         N strictly lower triangular
    d N = -strict_tril(X^T dX X^T)         the inverse's own backward

on ``[..., l, l]`` float32 matrices, one per chunk and head.  XLA runs the
forward as blocked substitution with a doubling block — twelve ``[l, l]``
float32 ``highest`` matmuls a matrix, every level through HBM with a mask of
its own — and the backward as two more.  Here a tile of whole matrices is
read once and written once and every intermediate stays in VMEM.

Operands keep the layout XLA:TPU gives them: ``[matrices * l, l]`` is a
bitcast of ``[..., l, l]`` under the (8, 128) tiling (the dense ``[..., l * l
/ 128, 128]`` view costs a reshaping copy on either side; compiled for a
described v5e, PERF.md section 6, PR 37).  A minor dimension of 64 is padded
to the 128 lanes there, in HBM and in VMEM: the kernels move twice the
matrices' bytes, as XLA's own ops on them do.

Forward: plain forward substitution, exact float32 on the VPU, ``_LANES``
matrices at a time with THE MATRIX INDEX ON THE LANES.  Row ``i`` of every
matrix of the tile is one strided load ``[matrices, l]``, transposed on the
XLU to ``[l, matrices]``; then

    X[i, :] = e_i - sum_{j < i} N[i, j] X[j, :]

is one multiply-subtract of ``[columns, matrices]`` slabs a ``(i, j)``, the
coefficient a row broadcast along the sublanes; ``l^3 / 3`` multiply-adds a
matrix where the doubling form spends ``12 l^3`` on a quarter-full MXU six
times over.  No product term is dropped: there is no product of rounded
operands at all.  Rows are transposed back and written with strided stores.
The rows of a sublane tile and the tiles before them are loops, not unrolled:
the unrolled kernel (2,016 ``(i, j)`` a matrix at 64) ran 0.3 ms a call
faster and added 12 s to every start-up, lowered again at each call site
(PERF.md section 6, PR 37).

Backward: ``P = dX X^T`` and ``X^T P`` on the MXU, float32 operands at
``Precision.HIGHEST`` (all six bfloat16 products), ``_BWD_TILE`` matrices a
grid step, ``P`` never in HBM, the strict lower triangle selected in the
kernel.  Residual: ``X`` alone.

Dispatch (``solve_kernel_applies``): the one predicate the layer and the
``hbnlp_delta_solve_kernel_layers`` gauge both read.  Off the TPU and at
shapes it declines, ``model/gated_delta.py``'s XLA form runs: the kernels'
oracle.
"""
from __future__ import annotations

import functools
import math
import typing

import jax
import jax.numpy as jnp

_LANES = 128          # matrices a forward grid step: one lane tile
_SUBLANES = 8
_BWD_TILE = 32        # matrices a backward grid step
_CHUNKS = (16, 32, 64, 128)
_VMEM_SLACK = 4 << 20


def solve_kernel_applies(chunk: int, matrices: int,
                         backend: typing.Optional[str] = None) -> bool:
    """Whether the kernel pair runs ``matrices`` systems of ``chunk x
    chunk`` here: a TPU backend, a power-of-two chunk from 16 to 128 (whole
    sublane tiles a row, one lane tile at most), whole tiles of ``_LANES``
    matrices.  Pure in its arguments but for the backend's default."""
    if backend is None:
        backend = jax.default_backend()
    return (backend == "tpu" and chunk in _CHUNKS and matrices > 0
            and matrices % _LANES == 0)


def _fwd_kernel(s_ref, x_ref, n_scr, x_scr, *, l: int):
    """``s_ref`` / ``x_ref [_LANES * l, l]``: row ``i`` of matrix ``t`` at
    ``t * l + i``.  ``n_scr`` / ``x_scr [l * l, _LANES]``: entry ``(i, k)``
    of matrix ``t`` at ``[i * l + k, t]``.  Rows are solved a sublane tile
    of them at a time (static: the slab of columns a row can reach is ``0 ..
    its tile's end``), inside it row by row and over the tiles of earlier
    rows in loops, so that the kernel stays a few hundred operations to
    trace and compile whatever ``l``.  The tile on the diagonal is swept
    whole: ``X`` starts as zeros, so a row not solved yet adds nothing."""
    from jax.experimental import pallas as pl

    def gather(tile, carry):
        for within in range(_SUBLANES):
            i = tile * _SUBLANES + within
            rows = s_ref[pl.ds(i, _LANES, stride=l), :]        # [T, l]
            n_scr[pl.ds(pl.multiple_of(i * l, l), l), :] = rows.T
        return carry

    jax.lax.fori_loop(0, l // _SUBLANES, gather, None)
    x_scr[...] = jnp.zeros_like(x_scr)
    sub = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, _LANES), 0)
    for block in range(l // _SUBLANES):
        width = (block + 1) * _SUBLANES   # columns 0 .. the tile's last row

        def row_step(within, carry, block=block, width=width):
            base = pl.multiple_of((block * _SUBLANES + within) * l, l)
            unit = jnp.where(sub == within, 1.0, 0.0).astype(jnp.float32)
            acc = jnp.concatenate(
                [jnp.zeros((width - _SUBLANES, _LANES), jnp.float32), unit],
                0) if block else unit

            def tile_step(j_tile, acc):
                first = pl.multiple_of(j_tile * _SUBLANES, _SUBLANES)
                coef = n_scr[pl.ds(base + first, _SUBLANES), :]
                for jj in range(_SUBLANES):
                    solved = x_scr[pl.ds(pl.multiple_of((first + jj) * l, l),
                                         width), :]
                    acc = acc - coef[jj:jj + 1, :] * solved
                return acc

            x_scr[pl.ds(base, width), :] = jax.lax.fori_loop(
                0, block + 1, tile_step, acc)
            return carry

        jax.lax.fori_loop(0, _SUBLANES, row_step, None)

    def scatter(tile, carry):
        for within in range(_SUBLANES):
            i = tile * _SUBLANES + within
            x_ref[pl.ds(i, _LANES, stride=l), :] = x_scr[
                pl.ds(pl.multiple_of(i * l, l), l), :].T
        return carry

    jax.lax.fori_loop(0, l // _SUBLANES, scatter, None)


def _bwd_kernel(x_ref, g_ref, d_ref, *, l: int):
    """``[_BWD_TILE * l, l]`` each: ``d = -strict_tril(X^T (G X^T))``."""
    tile = x_ref.shape[0] // l
    x = x_ref[...].reshape(tile, l, l)
    g = g_ref[...].reshape(tile, l, l)
    dot = functools.partial(jax.lax.dot_general,
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    # P[t, i, k] = sum_j G[t, i, j] X[t, k, j]
    p = dot(g, x, (((2,), (2,)), ((0,), (0,))))
    # R[t, i, k] = sum_j X[t, j, i] P[t, j, k]
    r = dot(x, p, (((1,), (1,)), ((0,), (0,))))
    row = jax.lax.broadcasted_iota(jnp.int32, (tile, l, l), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (tile, l, l), 2)
    d_ref[...] = jnp.where(row > col, -r, 0.0).reshape(tile * l, l)


def _padded(l: int) -> int:
    """Bytes of one ``[l, l]`` float32 matrix under the (8, 128) tiling."""
    return l * max(l, _LANES) * 4


def _params(vmem_bytes: int):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=("parallel",),
                                vmem_limit_bytes=vmem_bytes + _VMEM_SLACK)


# jitted so that a model traces each kernel once, not once a call site
@functools.partial(jax.jit, static_argnums=(1,))
def _fwd_impl(flat, interpret):
    """``flat [matrices * l, l]`` (``matrices`` a multiple of ``_LANES``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    rows, l = flat.shape
    block = pl.BlockSpec((_LANES * l, l), lambda i: (i, 0))
    scratch = pltpu.VMEM((l * l, _LANES), jnp.float32)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, l=l),
        grid=(rows // (_LANES * l),),
        in_specs=[block], out_specs=block,
        out_shape=jax.ShapeDtypeStruct(flat.shape, flat.dtype),
        scratch_shapes=[scratch, scratch],
        # two blocks, double-buffered, and the two scratches
        compiler_params=_params(_LANES * (4 * _padded(l) + 2 * l * l * 4)),
        name="delta_solve_fwd",
        interpret=interpret,
    )(flat)


@functools.partial(jax.jit, static_argnums=(2,))
def _bwd_impl(inv, g, interpret):
    from jax.experimental import pallas as pl
    rows, l = inv.shape
    block = pl.BlockSpec((_BWD_TILE * l, l), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, l=l),
        grid=(rows // (_BWD_TILE * l),),
        in_specs=[block, block], out_specs=block,
        out_shape=jax.ShapeDtypeStruct(inv.shape, inv.dtype),
        # three blocks, double-buffered, and as much again for P, R, X^T
        compiler_params=_params(_BWD_TILE * 12 * _padded(l)),
        name="delta_solve_bwd",
        interpret=interpret,
    )(inv, g)


def _call(impl, interpret: bool, *tensors):
    """``impl`` on ``[..., l, l]`` tensors viewed as ``[matrices * l, l]``: a
    bitcast where the matrices are whole tiles (``solve_kernel_applies``),
    else zero systems are appended (their inverse is the identity) and cut
    off again."""
    shape = tensors[0].shape
    l, matrices = shape[-1], math.prod(shape[:-2])
    extra = -matrices % _LANES

    def flat(t):
        t = t.reshape(matrices, l, l)
        if extra:
            t = jnp.pad(t, ((0, extra), (0, 0), (0, 0)))
        return t.reshape(-1, l)

    out = impl(*map(flat, tensors), interpret)
    return out[:matrices * l].reshape(shape)


def inverse_unit_lower(strict, interpret: bool = False):
    """``(I + strict)^-1`` for strictly lower triangular float32 ``strict
    [..., l, l]``, ``l`` as ``solve_kernel_applies`` accepts it."""
    return _call(_fwd_impl, interpret, strict)


def inverse_unit_lower_bwd(inv, g, interpret: bool = False):
    """The cotangent of ``strict`` from the inverse ``inv`` and its
    cotangent ``g``, both ``[..., l, l]`` float32."""
    return _call(_bwd_impl, interpret, inv, g)
