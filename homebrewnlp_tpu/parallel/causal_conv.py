"""Pallas TPU causal depthwise conv + bias + SiLU (layer ``mamba``'s conv;
layer ``gated_delta``'s, which has no bias: a zero one):

    y[t] = silu(bias + sum_k weight[k] x[t - (K - 1) + k])     zeros before 0

on ``x [b, s, channels]`` in the calculation dtype with float32 ``weight [K,
channels]`` and ``bias [channels]``.  XLA runs the same arithmetic
(``model/mamba.py causal_depthwise_conv``) as a padded float32 copy, K
shifted slices and autodiff's transpose of them: about thirteen passes over
the float32 tensor a layer.  Here every float32 intermediate stays in VMEM:
the forward reads ``x`` once and writes ``y`` once, the backward reads ``x``
and the cotangent once and writes ``dx`` once.

The kernels see ``[b, channels, s]``: the sequence on the lanes.  That is
the layout XLA:TPU itself gives layer ``mamba``'s activations (the chunked
scan's einsums want the sequence minor), so the two ``swapaxes`` around the
call are bitcasts; kernels on ``[b, s, channels]`` were measured and cost
six transposing copies of the float32 tensor a layer (PERF.md section 6,
PR 31).  A tile is ``[channel tile, sequence tile]``; the ``K - 1``
positions a tile needs from its neighbours come through a second
``BlockSpec`` on the same operand (the 128 lanes before the tile; in the
backward also the 128 after).  A tap is a lane rotation of the rows with
their halo in front.  The arithmetic runs piece by piece of a tile
(``_FWD_PIECE``, ``_BWD_PIECE``: channels x positions).

Backward (``jax.custom_vjp``; residuals ``x``, ``weight``, ``bias``): a piece
recomputes its pre-activation for its own positions and the 128 after them,
forms ``dpre = g * silu'(pre)`` in float32 (zero past the sequence's end),
writes ``dx[t] = sum_k weight[k] dpre[t + (K - 1) - k]`` and adds
``dw[k] = sum_t dpre[t] x[t - (K - 1) + k]`` and ``db = sum_t dpre[t]`` over
its own positions into float32 output blocks, 128 lane-partial sums a
channel, revisited along batch and sequence; the caller adds the lanes.
``dpre`` is never rounded and never reaches HBM.

Dispatch (``kernel_applies``): the one predicate the layer and the
``hbnlp_mamba_conv_kernel_layers`` gauge both read.
"""
from __future__ import annotations

import functools
import typing

import jax
import jax.numpy as jnp

from .flash_attention import kernel_block

_LANE = 128          # the halo blocks' width: one lane tile
# swept on a v5e at [1, 8192, 4352] (PERF.md section 6, PR 31)
_FWD_PIECE = (32, 512)    # channels x positions a piece of arithmetic covers
_BWD_PIECE = (32, 4096)
_SEQ_TILE = 4096          # cap of the sequence tile
_CHANNEL_TILE = 128


def seq_tile(sequence: int) -> typing.Optional[int]:
    """The largest power-of-two number of positions, from ``_SEQ_TILE`` down
    to one lane tile, that divides the sequence; None where none does."""
    if sequence % _LANE:
        return None
    return kernel_block(sequence, cap=_SEQ_TILE)


def kernel_applies(channels: int, sequence: int, taps: int, offset: int = 0,
                   backend: typing.Optional[str] = None) -> bool:
    """Whether ``causal_conv_silu`` runs these shapes here: a TPU backend,
    whole channel tiles starting at a whole tile (``offset``: where the
    conv's channels begin in the tensor they are read out of), a sequence
    tile that divides the sequence and taps that reach no further back than
    one halo block.  Pure in its arguments but for the backend's default."""
    if backend is None:
        backend = jax.default_backend()
    return (backend == "tpu" and channels % _CHANNEL_TILE == 0
            and offset % _CHANNEL_TILE == 0
            and seq_tile(sequence) is not None and 1 <= taps <= _LANE)


def _shifted(ext, by: int, first: int, width: int):
    """``ext[:, first - by : first - by + width]`` in float32 for lane-tile
    aligned ``first`` and ``width``: a lane rotation, then an aligned
    slice.  16-bit rows are sublane-packed in pairs, which rotate together
    as 32-bit lanes (half the rotations, and before the widening)."""
    from jax.experimental.pallas import tpu as pltpu
    by %= ext.shape[1]
    if by and ext.dtype.itemsize == 2:
        packed = pltpu.roll(pltpu.bitcast(ext, jnp.uint32), by, 1)
        ext = pltpu.bitcast(packed, ext.dtype)
    elif by:
        ext = pltpu.roll(ext, by, 1)
    return ext[:, first:first + width].astype(jnp.float32)


def _pre_activation(taps, w, bias):
    # the order ``causal_depthwise_conv`` adds in: bias, then tap 0 .. K - 1
    out = bias
    for j, tap in enumerate(taps):
        out = out + tap * w[:, j:j + 1]
    return out


def _row_blocks(ref, piece, body):
    """``body(rows, lane pieces)`` for every block of ``piece[0]`` channels
    of the tile."""
    from jax.experimental import pallas as pl
    n_rows, lanes = piece[0], min(piece[1], ref.shape[1])
    pieces = [(c, c + lanes) for c in range(0, ref.shape[1], lanes)]

    def step(r, carry):
        body(pl.ds(pl.multiple_of(r * n_rows, n_rows), n_rows), pieces)
        return carry

    jax.lax.fori_loop(0, ref.shape[0] // n_rows, step, None)


def _halo(ref, rows, absent):
    halo = ref[rows, :]
    return jnp.where(absent, jnp.zeros_like(halo), halo)


def _fwd_kernel(x_ref, before_ref, w_ref, b_ref, y_ref, *, k: int):
    from jax.experimental import pallas as pl
    first = pl.program_id(2) == 0

    def row_block(rows, pieces):
        w, bias = w_ref[rows, :], b_ref[rows, :]
        before = _halo(before_ref, rows, first)
        for start, end in pieces:
            ext = jnp.concatenate(
                [x_ref[rows, start - _LANE:start] if start else before,
                 x_ref[rows, start:end]], 1)
            pre = _pre_activation(
                [_shifted(ext, k - 1 - j, _LANE, end - start)
                 for j in range(k)], w, bias)
            y_ref[rows, start:end] = jax.nn.silu(pre).astype(y_ref.dtype)

    _row_blocks(x_ref, _FWD_PIECE, row_block)


def _dpre(pre, g):
    """``g * silu'(pre)``: ``silu'(x) = sigmoid(x) (1 + x (1 - sigmoid(x)))``."""
    sig = jax.nn.sigmoid(pre)
    return g * (sig * (1.0 + pre * (1.0 - sig)))


def _fold(x):
    """``[rows, lanes]`` -> ``[rows, 128]``: whole lane tiles added."""
    return sum(x[:, c:c + _LANE] for c in range(0, x.shape[1], _LANE))


def _bwd_kernel(x_ref, before_ref, after_ref, g_ref, g_after_ref, w_ref,
                b_ref, dx_ref, sums_ref, *, k: int):
    from jax.experimental import pallas as pl
    bi, i = pl.program_id(1), pl.program_id(2)
    first, last = i == 0, i == pl.num_programs(2) - 1
    ts = x_ref.shape[1]

    @pl.when((bi == 0) & first)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    def row_block(rows, pieces):
        w, bias = w_ref[rows, :], b_ref[rows, :]
        before = _halo(before_ref, rows, first)
        x_after = after_ref[rows, :]
        # no cotangent past the sequence's end
        g_after = _halo(g_after_ref, rows, last)
        sums = [jnp.zeros((rows.size, _LANE), jnp.float32)] * (k + 1)
        for start, end in pieces:
            lanes = end - start
            # positions [start - 128, end + 128) of x, [start, end + 128)
            # of the cotangent
            ext = jnp.concatenate(
                [x_ref[rows, start - _LANE:start] if start else before,
                 x_ref[rows, start:end],
                 x_ref[rows, end:end + _LANE] if end < ts else x_after], 1)
            g = jnp.concatenate(
                [g_ref[rows, start:end],
                 g_ref[rows, end:end + _LANE] if end < ts else g_after], 1)
            taps = [_shifted(ext, k - 1 - j, _LANE, lanes + _LANE)
                    for j in range(k)]
            dp = _dpre(_pre_activation(taps, w, bias), g.astype(jnp.float32))
            # tap weight[j] reads dpre[t + K - 1 - j]
            dx = sum(_shifted(dp, -(k - 1 - j), 0, lanes) * w[:, j:j + 1]
                     for j in range(k))
            dx_ref[rows, start:end] = dx.astype(dx_ref.dtype)
            own = dp[:, :lanes]
            sums = [acc + _fold(own * tap[:, :lanes])
                    for acc, tap in zip(sums, taps)] + [sums[k] + _fold(own)]
        for j in range(k + 1):
            sums_ref[j, rows, :] += sums[j]

    _row_blocks(x_ref, _BWD_PIECE, row_block)


def _specs(ts: int, s: int, shift: int = 0):
    """Block specs on grid (channel tile j, batch b, sequence tile i) over
    ``[b, channels, s]``: the tile, the 128 positions before it, the 128
    after it (both clamped into the sequence; the kernels mask what the
    clamp repeats).  ``shift``: the operand's first channel tile (``x``
    read in place out of a wider tensor)."""
    from jax.experimental import pallas as pl
    per, blocks, tc = ts // _LANE, s // _LANE, _CHANNEL_TILE
    tile = pl.BlockSpec((None, tc, ts), lambda j, b, i: (b, j + shift, i))
    before = pl.BlockSpec(
        (None, tc, _LANE),
        lambda j, b, i: (b, j + shift, jnp.maximum(i * per - 1, 0)))
    after = pl.BlockSpec(
        (None, tc, _LANE),
        lambda j, b, i: (b, j + shift,
                         jnp.minimum((i + 1) * per, blocks - 1)))
    return tile, before, after


def _per_channel(columns: int):
    from jax.experimental import pallas as pl
    return pl.BlockSpec((_CHANNEL_TILE, columns), lambda j, b, i: (j, 0))


def _params(semantics):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=semantics)


# jitted so that a model traces each kernel once, not once a layer and pass
@functools.partial(jax.jit, static_argnums=(3, 4))
def _fwd_impl(xt, weight, bias, offset, interpret):
    """``xt [b, channels (+ what surrounds them), s]`` -> ``[b, channels,
    s]``."""
    from jax.experimental import pallas as pl
    (b, _, s), (k, c) = xt.shape, weight.shape
    ts, tc = seq_tile(s), _CHANNEL_TILE
    tile, before, _ = _specs(ts, s, offset // tc)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, k=k),
        grid=(c // tc, b, s // ts),
        in_specs=[tile, before, _per_channel(k), _per_channel(1)],
        out_specs=_specs(ts, s)[0],
        out_shape=jax.ShapeDtypeStruct((b, c, s), xt.dtype),
        compiler_params=_params(("parallel", "parallel", "parallel")),
        name="mamba_conv_fwd",
        interpret=interpret,
    )(xt, xt, weight.T, bias.reshape(c, 1))


@functools.partial(jax.jit, static_argnums=(4, 5))
def _bwd_impl(xt, weight, bias, gt, offset, interpret):
    from jax.experimental import pallas as pl
    (b, _, s), (k, c) = xt.shape, weight.shape
    ts, tc = seq_tile(s), _CHANNEL_TILE
    x_tile, x_before, x_after = _specs(ts, s, offset // tc)
    tile, _, after = _specs(ts, s)
    dxt, sums = pl.pallas_call(
        functools.partial(_bwd_kernel, k=k),
        grid=(c // tc, b, s // ts),
        in_specs=[x_tile, x_before, x_after, tile, after, _per_channel(k),
                  _per_channel(1)],
        out_specs=[tile, pl.BlockSpec((k + 1, tc, _LANE),
                                      lambda j, b, i: (0, j, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, c, s), xt.dtype),
                   jax.ShapeDtypeStruct((k + 1, c, _LANE), jnp.float32)],
        # the sums' blocks are revisited along batch and sequence
        compiler_params=_params(("parallel", "arbitrary", "arbitrary")),
        name="mamba_conv_bwd",
        interpret=interpret,
    )(xt, xt, xt, gt, gt, weight.T, bias.reshape(c, 1))
    sums = sums.sum(-1)
    return dxt, sums[:k], sums[k]


def _or_zeros(bias, weight):
    """A conv without a bias runs the kernels with a zero one."""
    return jnp.zeros(weight.shape[1], weight.dtype) if bias is None else bias


def _forward(x, weight, bias, offset, interpret):
    bias = _or_zeros(bias, weight)
    return jnp.swapaxes(_fwd_impl(jnp.swapaxes(x, 1, 2), weight, bias,
                                  offset, interpret), 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def causal_conv_silu(x, weight, bias, offset: int = 0,
                     interpret: bool = False):
    """``silu(bias + causal depthwise conv(x[..., offset:offset + channels],
    weight))`` in ``x``'s dtype, ``channels = weight.shape[1]``, ``bias``
    None = none; shapes as ``kernel_applies`` accepts them."""
    return _forward(x, weight, bias, offset, interpret)


def _vjp_fwd(x, weight, bias, offset, interpret):
    return _forward(x, weight, bias, offset, interpret), (x, weight, bias)


def _vjp_bwd(offset, interpret, res, g):
    x, weight, bias = res
    dxt, dw, db = _bwd_impl(jnp.swapaxes(x, 1, 2), weight,
                            _or_zeros(bias, weight), jnp.swapaxes(g, 1, 2),
                            offset, interpret)
    dx = jnp.swapaxes(dxt, 1, 2)
    after = x.shape[-1] - offset - weight.shape[1]
    if offset or after:
        dx = jnp.pad(dx, ((0, 0), (0, 0), (offset, after)))
    return dx, dw.astype(weight.dtype), \
        None if bias is None else db.astype(bias.dtype)


causal_conv_silu.defvjp(_vjp_fwd, _vjp_bwd)
