"""Pallas TPU blocked learned-map mixer: out = (bias · causal mask) @ value.

The flagship mixer ``attention-biased_attention_map-absolute-input_as_value``
is NOT dot-product attention: its [heads, s, t] map is a LEARNED embedding
times the causal mask, so the flash kernels' online-softmax machinery does
not apply — but the O(s²) map@value contraction is still the layer's hot op,
and the dense einsum materialises the full masked map in HBM per head.  This
kernel computes (bias·mask)@value blockwise in VMEM: the masked map is lower
triangular, so causally-dead blocks above the diagonal are skipped entirely,
diagonal-crossing blocks mask per element (``_causal_split``, shared with
parallel/flash_attention.py), and interior blocks multiply unmasked.

Backward under ``jax.custom_vjp``: the op is LINEAR in both operands, so the
backward is two more blocked contractions —
``dval = (bias·mask)ᵀ @ g`` with the mirrored dead-block skip, and
``dbias = mask · Σ_batch g @ valᵀ``, ONE contraction over batch and
features: the batch is the innermost grid dimension, so a head's [bq, bk]
block of the [h, s, t] map stays in VMEM for the whole batch sweep,
accumulates there in float32, takes the elementwise mask where it crosses
the diagonal, and is written to HBM once.

Dispatch (``mix``): pallas kernel on TPU, fused XLA reference elsewhere;
``HBNLP_MAP_MIXER_INTERPRET=1`` forces the kernels in interpret mode
off-TPU (the parity tests' route).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from .flash_attention import (_KERNEL_VMEM_BUDGET, _causal_split,
                              kernel_block)


def _xla_reference(bias, v, causal):
    """bias [h, s, t], v [b, t, h, f] -> [b, s, h, f]; f32 accumulation."""
    s, t = bias.shape[1], bias.shape[2]
    m = bias.astype(jnp.float32)
    if causal:
        m = jnp.where(jnp.arange(s)[:, None] >= jnp.arange(t)[None, :],
                      m, 0.0)
    out = jnp.einsum("hst,bthf->bshf", m, v.astype(jnp.float32))
    return out.astype(v.dtype)


def _masked_bias(b_ref, qi, ki, block_q, block_k):
    """Diagonal-block bias tile with causally-dead elements zeroed (the
    linear-map analogue of the flash kernels' -inf masking)."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1)
    return jnp.where(q_pos >= k_pos, b_ref[...], 0)


def _mix_kernel(b_ref, v_ref, o_ref, acc_ref, *, block_q: int, block_k: int,
                num_k: int, causal: bool):
    """Forward: grid (batch·heads, s blocks, t blocks), t innermost; the
    output row block accumulates in VMEM scratch across the t sweep."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _acc(m):
        # the map rounds to the value dtype for the MXU (flash-2 standard —
        # the same precision class as the dense einsum in bf16)
        acc_ref[...] += jax.lax.dot_general(
            m.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        live, full = _causal_split(qi, ki, block_q, block_k)

        @pl.when(full)
        def _interior():
            _acc(b_ref[...])

        @pl.when(live & jnp.logical_not(full))
        def _diagonal():
            _acc(_masked_bias(b_ref, qi, ki, block_q, block_k))
    else:
        _acc(b_ref[...])

    @pl.when(ki == num_k - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _dval_kernel(b_ref, g_ref, dv_ref, acc_ref, *, block_q: int,
                 block_k: int, num_q: int, causal: bool):
    """dval = (bias·mask)ᵀ @ g: grid (batch·heads, t blocks, s blocks), s
    innermost; for a fixed t block only s blocks at-or-after it contribute —
    strictly-earlier (causally dead) s blocks are skipped."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _acc(m):
        acc_ref[...] += jax.lax.dot_general(
            m.astype(g_ref.dtype), g_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        live, full = _causal_split(qi, ki, block_q, block_k)

        @pl.when(full)
        def _interior():
            _acc(b_ref[...])

        @pl.when(live & jnp.logical_not(full))
        def _diagonal():
            _acc(_masked_bias(b_ref, qi, ki, block_q, block_k))
    else:
        _acc(b_ref[...])

    @pl.when(qi == num_q - 1)
    def _finish():
        dv_ref[...] = acc_ref[...].astype(dv_ref.dtype)


def _dbias_kernel(g_ref, v_ref, db_ref, *, block_q: int, block_k: int,
                  num_b: int, causal: bool):
    """dbias = mask · Σ_batch g @ valᵀ: grid (heads, s blocks, t blocks,
    batch), batch innermost; the [bq, bk] float32 output block does not move
    during the batch sweep, so it accumulates in VMEM and reaches HBM once.
    Dead blocks only zero-fill; diagonal-crossing blocks take the
    elementwise mask on the summed block, before it leaves."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    bi = pl.program_id(3)

    @pl.when(bi == 0)
    def _init():
        db_ref[...] = jnp.zeros_like(db_ref)

    def _acc():
        db_ref[...] += jax.lax.dot_general(
            g_ref[...], v_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        live, full = _causal_split(qi, ki, block_q, block_k)
        pl.when(live)(_acc)

        @pl.when(live & jnp.logical_not(full) & (bi == num_b - 1))
        def _mask():
            db_ref[...] = _masked_bias(db_ref, qi, ki, block_q, block_k)
    else:
        _acc()


def _compiler_params(grid_rank: int = 3,
                     vmem_limit_bytes: int = _KERNEL_VMEM_BUDGET):
    """Every kernel here sweeps its innermost grid dimension into one
    resident block; the dimensions before it own their outputs."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (grid_rank - 1) + ("arbitrary",),
        vmem_limit_bytes=vmem_limit_bytes)


def _fwd_impl(bias, v, causal, block_q, block_k, interpret):
    """bias [h, s, t], v [bh, t, f] (batch-major, head-minor) ->
    out [bh, s, f]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    h, s, t = bias.shape
    bh, _, f = v.shape
    bq = min(block_q, s)
    bk = min(block_k, t)
    num_k = t // bk

    if causal:
        # dead cells clamp to the causal frontier so the pipeline skips the
        # dead HBM fetch (parallel/flash_attention.py _frontier_kv_map)
        def _k_idx(j, kk):
            return jnp.minimum(kk, (j * bq + bq - 1) // bk)
    else:
        def _k_idx(j, kk):
            return kk

    kernel = functools.partial(_mix_kernel, block_q=bq, block_k=bk,
                               num_k=num_k, causal=causal)
    return pl.pallas_call(
        kernel,
        grid=(bh, s // bq, num_k),
        in_specs=[
            pl.BlockSpec((None, bq, bk),
                         lambda i, j, kk: (i % h, j, _k_idx(j, kk))),
            pl.BlockSpec((None, bk, f),
                         lambda i, j, kk: (i, _k_idx(j, kk), 0))],
        out_specs=pl.BlockSpec((None, bq, f), lambda i, j, kk: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, f), v.dtype),
        scratch_shapes=[pltpu.VMEM((bq, f), jnp.float32)],
        compiler_params=_compiler_params(),
        # "causal" in the name lets the FLOP counter subtract the skipped
        # dead cells (utils/flops.py count_matmul_flops_split)
        name="map_mixer_fwd_causal" if causal else "map_mixer_fwd",
        interpret=interpret,
    )(bias, v)


def _bwd_impl(bias, v, g, causal, block_q, block_k, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    h, s, t = bias.shape
    bh, _, f = v.shape
    bq = min(block_q, s)
    bk = min(block_k, t)
    nq, nk = s // bq, t // bk

    if causal:
        # dead s blocks before the first live one repeat its index so the
        # pipeline skips the dead fetch (flash _frontier_q_map)
        def _q_idx(kk, j):
            return jnp.maximum(j, (kk * bk) // bq)
    else:
        def _q_idx(kk, j):
            return j

    dv = pl.pallas_call(
        functools.partial(_dval_kernel, block_q=bq, block_k=bk, num_q=nq,
                          causal=causal),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((None, bq, bk),
                         lambda i, kk, j: (i % h, _q_idx(kk, j), kk)),
            pl.BlockSpec((None, bq, f),
                         lambda i, kk, j: (i, _q_idx(kk, j), 0))],
        out_specs=pl.BlockSpec((None, bk, f), lambda i, kk, j: (i, kk, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, f), v.dtype),
        scratch_shapes=[pltpu.VMEM((bk, f), jnp.float32)],
        compiler_params=_compiler_params(),
        name="map_mixer_bwd_dval_causal" if causal else "map_mixer_bwd_dval",
        interpret=interpret,
    )(bias, g)

    nb = bh // h
    if causal:
        # a dead block repeats the indices of the last live fetch before it
        # (the last batch row, the frontier t block), so its whole batch
        # sweep fetches nothing
        def _row_and_t(hh, j, kk, bb):
            frontier = (j * bq + bq - 1) // bk
            return (jnp.where(kk <= frontier, bb, nb - 1) * h + hh,
                    jnp.minimum(kk, frontier))
    else:
        def _row_and_t(hh, j, kk, bb):
            return bb * h + hh, kk

    # the VMEM a call reserves is taken from what XLA can keep there across
    # it — the map the next forward kernel reads (0.48 ms from VMEM, 0.66
    # from HBM at the flagship shape) — so ask for the double-buffered
    # blocks with room for Mosaic's temporaries, never under the compiler's
    # own default, not for the flat budget
    blocks = 2 * (bq * bk * 4 + (bq + bk) * f * v.dtype.itemsize)
    vmem = min(_KERNEL_VMEM_BUDGET, max(4 * blocks, 16 * 1024 * 1024))
    db = pl.pallas_call(
        functools.partial(_dbias_kernel, block_q=bq, block_k=bk, num_b=nb,
                          causal=causal),
        grid=(h, nq, nk, nb),
        in_specs=[
            pl.BlockSpec((None, bq, f),
                         lambda *i: (_row_and_t(*i)[0], i[1], 0)),
            pl.BlockSpec((None, bk, f), lambda *i: (*_row_and_t(*i), 0))],
        out_specs=pl.BlockSpec((None, bq, bk),
                               lambda hh, j, kk, bb: (hh, j, kk)),
        out_shape=jax.ShapeDtypeStruct((h, s, t), jnp.float32),
        compiler_params=_compiler_params(grid_rank=4, vmem_limit_bytes=vmem),
        name="map_mixer_bwd_dbias_causal" if causal
        else "map_mixer_bwd_dbias",
        interpret=interpret,
    )(g, v)
    return db.astype(bias.dtype), dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def map_mixer(bias, v, causal: bool, block_q: int, block_k: int,
              interpret: bool):
    """Flat-core blocked map mixer: bias [h, s, t], v [bh, t, f]
    (batch-major, head-minor fold) -> [bh, s, f]."""
    return _fwd_impl(bias, v, causal, block_q, block_k, interpret)


def _map_fwd(bias, v, causal, block_q, block_k, interpret):
    return _fwd_impl(bias, v, causal, block_q, block_k, interpret), (bias, v)


def _map_bwd(causal, block_q, block_k, interpret, res, g):
    bias, v = res
    return _bwd_impl(bias, v, g, causal, block_q, block_k, interpret)


map_mixer.defvjp(_map_fwd, _map_bwd)


def mix(bias, v, causal: bool = True, interpret=None):
    """Dispatch: pallas kernels on TPU, fused XLA reference elsewhere.

    bias [h, s, t], v [b, t, h, f] -> [b, s, h, f].  Block sizes: the
    largest power-of-two divisors of s/t up to 512 — the kernel is one dot
    per cell with no softmax bookkeeping, so mid-size tiles amortise grid
    overhead without starving the cross-step DMA/compute overlap.  The
    named-scope regions make which implementation ran visible per-op in
    HLO metadata and profiler traces (docs/OBSERVABILITY.md)."""
    on_tpu = jax.default_backend() not in ("cpu",)
    if interpret is None:
        interpret = not on_tpu
    if not on_tpu and not os.environ.get("HBNLP_MAP_MIXER_INTERPRET"):
        with jax.named_scope("map_mixer_dense"):
            return _xla_reference(bias, v, causal)
    b, t, h, f = v.shape
    s = bias.shape[1]
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, t, f)
    with jax.named_scope("map_mixer"):
        out = map_mixer(bias, vt, causal, kernel_block(s, cap=512),
                        kernel_block(t, cap=512), interpret)
    return out.reshape(b, h, s, f).transpose(0, 2, 1, 3)
