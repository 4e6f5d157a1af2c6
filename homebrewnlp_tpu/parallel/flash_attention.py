"""Pallas TPU flash attention (single-device causal softmax attention).

Head layout: multi-head attention only, ``q``, ``k`` ``[b, s, heads, d_k]``
and ``v``, ``out`` ``[b, s, heads, d_v]``: the value's width need not be the
key's (latent attention, model/spatial.py: 192 / 128; every kernel reads the
two widths from its operands, V is never padded to the key's, and at ``d_k ==
d_v`` each call is the one it was).  Grouped-query attention reaches these kernels with each K/V
head already repeated over its group of query heads (``model/spatial.py``
``_standard_attention``; autodiff sums dk and dv over the group): K/V index
maps that read row ``i // group`` with the group's sum inside the dk/dv pass
were built and measured against that in PR 30 and lost end to end (PERF.md
section 6), so they are not here.

The dot-product attention path's hot op for long context: computes
softmax(q·kᵀ)·v blockwise in VMEM with an online softmax so the [seq, seq]
score matrix never reaches HBM.  Complements parallel/ring_attention.py
(which shards sequence *across* chips); this kernel is the within-chip
blockwise pass.  Grid: (batch·heads, q blocks, k blocks) with the
online-softmax state (m, l, acc) carried in VMEM scratch across the
innermost k dimension, so VMEM use is O(block) regardless of sequence
length; causal blocks above the diagonal are skipped via a pl.when
predicate, and a block the diagonal CROSSES scores only its live part
(``_masked_step`` / ``_cell_parts``, PR 55: the forward's cell that starts
where its k tile of twice the q tile's length starts is one online-softmax
step over the first half of the keys; a square backward cell is its
lower-left quadrant mask-free, the two on the diagonal masked, the
upper-right not scored; a windowed cell's far edge likewise).  Backward is
flash-2's, in pallas under ``jax.custom_vjp``: p is
recomputed per block from the saved lse, so training needs neither the O(s²)
residual nor an O(s²) recompute buffer.  ONE pass gives dq, dk and dv with
every accumulation in VMEM (``_bwd_one_pass_kernel``, PR 68: a grid of the
call's LIVE cells, a q block at a time with its k blocks ascending — dq over
that walk, a head's whole dk and dv resident for its sweep; nothing partial
reaches HBM, no grid step for a dead cell) where those accumulators fit the VMEM
the call asks for (key width 192 at 16,384 positions does, 512 does not);
the same pass the other way round (PR 73: a k block at a time with its q
blocks ascending — dk and dv over that walk, the head's whole dq resident,
half the bytes) where that fits (width 512 at 16,384 does); a dq and a dk/dv
kernel where neither does (width 512 at 65,536).  Three forms, one predicate:
``backward_form``.

A WINDOWED call's forward (``window``: query ``i`` sees keys ``i - window + 1
.. i``) is a band kernel instead (``_fwd_band``, PR 41) wherever a cell's band
fits VMEM (``band_applies``): grid (batch·heads, q tiles) with no k
dimension, the head-sequence's K and V resident, the softmax of a sub-block's
``sub + window`` keys in one pass with no carried state; the tiled forward
above, its inner dimension as long as the band, where it does not.  The
backward is the tiled flash-2 pass either way.

The masks the kernels know: ``causal`` (the lower triangle), a ``window``
under it, a kept set of BLOCKS of keys or of single KEYS a query
(``flash_*_select``, further down), and the BLOCK-DIFFUSION mask of
block-diffusion training (``block_diffusion_attention``, PR 67: over
``[noised | clean]`` a noised query sees its own noised block both ways and
the clean keys of earlier blocks, a clean query the clean keys of its own and
earlier blocks; the halves fold into the batch, the far part is the causal
grid under a diagonal in steps of the block — ``flash_*_blockdiff``, one more
compare in the cells the diagonal crosses — and the own blocks and the merge
by log-sum-exp run in XLA: the dead three quarters of the ``[2 L, 2 L]``
square are never scored).

Off the TPU ``attention`` runs the dense XLA form (``_xla_reference``, also
the tests' reference; tests run the kernels in interpret mode).
"""
from __future__ import annotations

import functools
import math
import typing

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..core.stash import (stash_collecting, stash_naming, stash_pop,
                          stash_push)

_NEG_INF = -1e30

#: the names ``attention`` gives a call's ``out`` ``[b, s, heads, d]`` and
#: ``lse`` ``[b * heads, s]`` float32 under a "name" channel (free where no
#: policy names them): where model/remat.py's ``attention`` kind rides the
#: ``checkpoint`` strategy, each block's ``jax.checkpoint`` saves them
#: (model/blocks.py ``_checkpoint_policy``) and the replay runs no forward
#: attention kernel
SAVED_NAMES = ("flash_out", "flash_lse")

# scoped-VMEM budget for the flash kernels: the compiler default (16M)
# fits the d128-tuned tiles exactly; wider head dims scale the operand
# blocks past it (d=256 forward: 16.64M).  v5e/v5p have 128M physical
# VMEM - 64M leaves the pipeline slack while never tile-shrinking
_KERNEL_VMEM_BUDGET = 64 * 1024 * 1024


def _xla_reference(q, k, v, scale, causal, window=None):
    # XLA dead-code-eliminates the unused lse
    return _xla_reference_with_lse(q, k, v, scale, causal, window)[0]


def _xla_reference_with_lse(q, k, v, scale, causal, window=None):
    """(out, lse [b*h, s]) — the fused XLA form for stash COLLECTION off-TPU
    (the pallas kernels' residual contract, without interpret-mode cost).
    ``window``: key ``t`` is visible to query ``i`` iff ``0 <= i - t <
    window`` (a band below the diagonal; HF's ``sliding_window``)."""
    b, s, h, d = q.shape
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                        k.astype(jnp.float32))
    if causal:
        mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        if window is not None:
            mask &= jnp.arange(s)[:, None] - jnp.arange(s)[None, :] < window
        scores = jnp.where(mask[None, None], scores, _NEG_INF)
    m = scores.max(-1)
    p = jnp.exp(scores - m[..., None])
    l = p.sum(-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p / jnp.maximum(l, 1e-30)[..., None],
                     v.astype(jnp.float32))
    lse = (m + jnp.log(jnp.maximum(l, 1e-30))).reshape(b * h, s)
    return out.astype(q.dtype), lse


def _causal_split(qi, ki, block_q: int, block_k: int):
    """(any overlap, fully live) block predicates for the causal mask.

    Only blocks CROSSING the diagonal need the per-element mask; strictly
    below it every pair is live.  The per-element iota/compare/select on a
    [block_q, block_k] f32 tile is real VPU time at d=128 — the kernel is
    VPU-bound on softmax elementwise work, not MXU-bound (measured: the
    dk/dv kernel with twice the dots but no softmax bookkeeping runs ~2x
    faster per cell than the forward), so masking only the ~1/num_blocks
    diagonal cells is a direct win."""
    live = ki * block_k <= qi * block_q + block_q - 1
    full = ki * block_k + block_k - 1 <= qi * block_q
    return live, full


def _window_k_range(qi, block_q: int, block_k: int, window: int):
    """(first, last) k block a q block's band touches: keys ``qi * block_q
    - (window - 1) .. qi * block_q + block_q - 1``, clipped at position 0.
    Works on python ints and on traced indices alike."""
    first = (qi * block_q - (window - 1)) // block_k
    first = max(first, 0) if isinstance(first, int) else jnp.maximum(first, 0)
    return first, (qi * block_q + block_q - 1) // block_k


def _window_q_range(ki, block_q: int, block_k: int, window: int, num_q: int):
    """(first, last) q block whose band touches k block ``ki``: queries ``ki
    * block_k .. ki * block_k + block_k - 1 + window - 1``, clipped at the
    sequence's end."""
    last = (ki * block_k + block_k - 1 + window - 1) // block_q
    last = min(last, num_q - 1) if isinstance(last, int) \
        else jnp.minimum(last, num_q - 1)
    return (ki * block_k) // block_q, last


def _window_q_index(ki, jj, block_q: int, block_k: int, window, seq_q: int):
    """``(q block, valid)`` of inner step ``jj`` of a k-outer grid: the step
    itself without a window; under one the inner dimension walks k block
    ``ki``'s band only, and ``valid`` is false past the band's last block
    (``seq_q`` the sequence's q blocks)."""
    if window is None:
        return jj, None
    first, last = _window_q_range(ki, block_q, block_k, window, seq_q)
    return first + jj, first + jj <= last


def _window_inner(num_outer: int, rng) -> int:
    """Length of a windowed grid's inner dimension: the most blocks any
    outer block's band touches (``rng(i) -> (first, last)``)."""
    return max(last - first + 1 for first, last in map(rng, range(num_outer)))


def _window_split(qi, ki, block_q: int, block_k: int, window: int):
    """``_causal_split`` under a window: a block is live when it holds a
    pair with ``0 <= i - t < window``, full when all its pairs are."""
    live, full = _causal_split(qi, ki, block_q, block_k)
    live &= ki * block_k + block_k - 1 >= qi * block_q - (window - 1)
    full &= qi * block_q + block_q - 1 - ki * block_k <= window - 1
    return live, full


class _Part(typing.NamedTuple):
    """One rectangle of a cell that a kernel scores: rows ``rows[0] ..
    rows[1]`` of the q tile against keys ``cols[0] .. cols[1]`` of the k
    tile; ``causal`` / ``far``: whether the diagonal / the window's far edge
    crosses it (the comparisons its mask needs; neither = mask-free)."""
    rows: typing.Tuple[int, int]
    cols: typing.Tuple[int, int]
    causal: bool
    far: bool

    @property
    def pairs(self) -> int:
        return (self.rows[1] - self.rows[0]) * (self.cols[1] - self.cols[0])


def _half_tile(block_q: int, block_k: int) -> int:
    """Side of the sub-squares a cell is read as: half the smaller tile's."""
    return max(min(block_q, block_k) // 2, 1)


def _rect_state(off: int, rows, cols, window) -> typing.Tuple[bool, bool, bool]:
    """``(live, causal, far)`` of a rectangle of a cell whose q tile starts
    ``off`` positions after its k tile: its pairs are ``off + a - c`` keys
    back (``a`` a row, ``c`` a column), seen iff ``0 <= back < window``.
    ``causal`` / ``far``: some pair is ahead of the diagonal / behind the
    window.  Python ints only."""
    low = off + rows[0] - (cols[1] - 1)
    high = off + rows[1] - 1 - cols[0]
    live = high >= 0 and (window is None or low <= window - 1)
    return live, low < 0, window is not None and high > window - 1


@functools.lru_cache(maxsize=None)
def _edge_offsets(block_q: int, block_k: int, window) -> typing.Tuple[int, ...]:
    """Every ``qi * block_q - ki * block_k`` of a live cell that an edge (the
    diagonal, a window's far side) crosses: the cells ``_causal_split`` /
    ``_window_split`` call live and not full.  A short static list — the
    diagonal's offsets lie within a tile of 0, the far edge's within a tile
    of ``window`` — so a kernel can give each a branch whose slices are
    static."""
    step = math.gcd(block_q, block_k)
    last = block_k if window is None else window + block_k
    found = []
    for off in range(-(block_q - step), last, step):
        live, causal, far = _rect_state(off, (0, block_q), (0, block_k),
                                        window)
        if live and (causal or far):
            found.append(off)
    return tuple(found)


@functools.lru_cache(maxsize=None)
def _cell_parts(block_q: int, block_k: int, off: int, window,
                carried: bool) -> typing.Tuple[_Part, ...]:
    """What a cell at offset ``off`` (``_edge_offsets``) scores, as static
    rectangles.  The cell is read as sub-squares of half the smaller tile
    side: each is wholly live, wholly dead, or crossed by an edge.

    A kernel that carries NO softmax state (the three backward kernels: ``p
    = exp(s - lse)``) walks each band of sub-square rows as runs of like
    columns — a mask-free run, a masked run, nothing for a dead one — so a
    quadrant costs its pairs and nothing else: a square diagonal cell is its
    lower-left quadrant mask-free, the two on the diagonal masked, and the
    upper-right not scored.

    The forward (``carried``) folds every part into ``m`` / ``l`` / ``acc``
    (an online-softmax step; what one costs is in ``attention``'s
    docstring), so its cell stays ONE masked step, over the bounding
    rectangle of the sub-squares that are not dead: where the q tile starts
    where a k tile of twice its length starts, that is the first half of the
    keys."""
    half = _half_tile(block_q, block_k)
    bands = []
    for r0 in range(0, block_q, half):
        rows, runs = (r0, r0 + half), []
        for c0 in range(0, block_k, half):
            live, causal, far = _rect_state(off, rows, (c0, c0 + half),
                                            window)
            if not live:
                continue
            last = runs[-1] if runs else None
            if last is not None and last.cols[1] == c0 \
                    and (last.causal or last.far) == (causal or far):
                runs[-1] = last._replace(cols=(last.cols[0], c0 + half),
                                         causal=last.causal or causal,
                                         far=last.far or far)
            else:
                runs.append(_Part(rows, (c0, c0 + half), causal, far))
        bands += runs
    if not carried or not bands:
        return tuple(bands)
    rows = (min(p.rows[0] for p in bands), max(p.rows[1] for p in bands))
    cols = (min(p.cols[0] for p in bands), max(p.cols[1] for p in bands))
    _, causal, far = _rect_state(off, rows, cols, window)
    return (_Part(rows, cols, causal, far),)


def _part_mask(part: _Part, off, window, step=None):
    """``s -> s`` with the pairs of ``part`` no query sees at ``_NEG_INF``
    (None for a mask-free part).  ``step`` (a power of two that divides the
    q tile; the block-diffusion mask's far part): the diagonal in steps of
    ``step`` positions, a query sees the keys of EARLIER blocks of ``step``
    only — its row moved back to the last key before its own block.  ``off`` is the cell's offset: static on an
    edge branch.  The positions are built where the mask is APPLIED, inside
    the caller's branch (at the body's top level every grid step would pay
    for them, the dead cells too), as a column of query positions against a
    row of key positions: one compare a pair and edge."""
    if not (part.causal or part.far):
        return None

    def mask(s):
        nrows = part.rows[1] - part.rows[0]
        ncols = part.cols[1] - part.cols[0]
        # a pair is ``q_pos - k_pos`` keys back
        q_pos = off + part.rows[0] - part.cols[0] \
            + jax.lax.broadcasted_iota(jnp.int32, (nrows, 1), 0)
        if step is not None:
            row = part.rows[0] \
                + jax.lax.broadcasted_iota(jnp.int32, (nrows, 1), 0)
            q_pos = q_pos - (row & (step - 1)) - 1
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (1, ncols), 1)
        seen = q_pos >= k_pos if part.causal else None
        if part.far:
            near = q_pos - (window - 1) <= k_pos
            seen = near if seen is None else seen & near
        return jnp.where(seen, s, _NEG_INF)
    return mask


#: the most matmul volume (rows x keys x head width, summed over a body's
#: branches) a FORWARD body may hold with a branch of its own for every edge
#: offset: the parent's two whole 1,024 x 2,048 tiles at head width 512, the
#: largest body measured to run at speed.  Two and a half of them (the third
#: branch, the first-half cell's) ran the long-context forward 2.17 x SLOWER
#: on a v5e — 32.78 -> 71.04 ms a call, whatever the VMEM limit — while the
#: same three branches at width 256 and 128 ran 5.6% and 14.8% faster than
#: two (PERF.md section 6, PR 55).  Past the cap the whole-tile edge cells
#: share the interior's branch (``_masked_step``)
_FORWARD_BODY_CAP = 2 * 1024 * 2048 * 512


def _masked_step(qi, ki, block_q: int, block_k: int, causal: bool, step,
                 window=None, valid=None, carried: bool = False,
                 width: int = 0, step_size=None):
    """Shared causal dispatch for the kernels: the mask-free interior
    branch, one branch for each offset at which an edge crosses the cell
    (``_edge_offsets``; all mutually exclusive ``pl.when``s — the FLOP
    counter relies on that, utils/flops.py), or the unconditional non-causal
    form.  ``step(rows, cols, mask)`` scores rows ``rows`` of the q tile
    against keys ``cols`` of the k tile (static ``(start, stop)`` pairs),
    masks the logits with ``mask`` where it is not None and folds them into
    the kernel's state (every kernel ACCUMULATES there: a dead cell, and the
    rows of an edge cell that no part scores, run nothing).  An edge branch
    scores only the LIVE part of its cell, as ``_cell_parts`` cuts it
    (``carried``: the kernel carries softmax state across its steps;
    ``width``: its head width).  Where the forward's branches together pass
    ``_FORWARD_BODY_CAP``, the edge cells whose part is the whole tile run
    in the interior's branch, which then masks by position (one body for
    both, a select a pair on a kernel the MXU bounds at such widths); the
    others keep their own.
    ``window`` (static; None = the whole causal triangle): blocks wholly
    behind the window are dead too, and the blocks its far edge crosses are
    edge cells like the diagonal's.  ``valid`` (windowed k-outer grids):
    false where the inner index ran past the sequence's last q block.
    ``step_size``: ``_part_mask``'s ``step`` — the stepped diagonal crosses
    the cells the plain one does and cuts them into the same parts (a sub-
    square's side is whole blocks: ``stepped_applies``), so only the masks
    differ."""
    from jax.experimental import pallas as pl

    whole = _Part((0, block_q), (0, block_k), False, False)
    if not causal:
        step(whole.rows, whole.cols, None)
        return
    if window is None:
        live, full = _causal_split(qi, ki, block_q, block_k)
    else:
        live, full = _window_split(qi, ki, block_q, block_k, window)
        if valid is not None:
            live, full = live & valid, full & valid
    edge = live & jnp.logical_not(full)
    off = qi * block_q - ki * block_k
    cells = {value: _cell_parts(block_q, block_k, value, window, carried)
             for value in _edge_offsets(block_q, block_k, window)}

    shared, shared_mask = full, None
    if carried and width * _body_pairs(block_q, block_k, window, True) \
            > _FORWARD_BODY_CAP:
        for value, (part,) in list(cells.items()):
            if (part.rows, part.cols) == (whole.rows, whole.cols):
                shared |= edge & (off == value)
                del cells[value]
        shared_mask = _part_mask(whole._replace(
            causal=True, far=window is not None), off, window, step_size)

    @pl.when(shared)
    def _step_interior():
        step(whole.rows, whole.cols, shared_mask)

    for value, parts in cells.items():
        @pl.when(edge & (off == value))
        def _step_edge(parts=parts, value=value):
            for part in parts:
                step(part.rows, part.cols,
                     _part_mask(part, value, window, step_size))


def _frontier_kv_map(block_q: int, block_k: int, causal: bool, window=None):
    """K/V BlockSpec index map with dead cells clamped to the causal
    frontier (grid order (i, q, k) — k innermost): the repeated block index
    makes the pipeline skip the dead HBM fetch, so dead cells cost
    iteration overhead only.  The clamp bound is the last live k block of
    ``_causal_split``'s liveness predicate; forward and dq share it.  Under
    a ``window`` the inner index counts from the band's first k block (the
    grid's inner dimension is ``_window_inner`` long, not ``num_k``)."""
    if window is not None:
        def kv_map(i, j, kk):
            first, last = _window_k_range(j, block_q, block_k, window)
            return (i, jnp.minimum(first + kk, last), 0)
    elif causal:
        def kv_map(i, j, kk):
            return (i, jnp.minimum(kk, (j * block_q + block_q - 1) // block_k),
                    0)
    else:
        def kv_map(i, j, kk):
            return (i, kk, 0)
    return kv_map


def _frontier_q_map(block_q: int, block_k: int, causal: bool, window=None,
                    num_q: int = 0):
    """Q-side twin of ``_frontier_kv_map`` for the k-outer backward grids
    (grid (i, k, q) — q innermost): causally-dead q blocks BEFORE the first
    live one ((kk*bk)//bq, the ``_causal_split`` liveness bound) repeat its
    index so the pipeline skips the dead HBM fetch.  Under a ``window`` the
    inner index counts from the band's first q block."""
    if window is not None:
        def q_map(i, kk, j):
            first, last = _window_q_range(kk, block_q, block_k, window, num_q)
            return (i, jnp.minimum(first + j, last), 0)
    elif causal:
        def q_map(i, kk, j):
            return (i, jnp.maximum(j, (kk * block_k) // block_q), 0)
    else:
        def q_map(i, kk, j):
            return (i, j, 0)
    return q_map


def _make_score(q_ref, k_ref, scale):
    """Scaled QK^T block logits on the RAW operand dtype with f32
    accumulation: for bf16 inputs, bf16 x bf16 -> f32 on the MXU computes
    exact products (the same numerics as an f32 matmul of the upcast
    values) at the native MXU rate; the scale folds in AFTER, in f32."""
    def score(rows=None, cols=None):
        # static (start, stop) rows of the q tile / keys of the k tile; None
        # = the whole tile
        q = q_ref[...] if rows is None else q_ref[slice(*rows), :]
        k = k_ref[...] if cols is None else k_ref[slice(*cols), :]
        return jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32) * scale
    return score


def _make_pair(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, scale):
    """The two per-pair tensors the backward kernels share, of rows ``rows``
    against keys ``cols`` of a cell: ``p = exp(s - lse)`` (the forward's
    softmax, recomputed from the saved lse — no state, so a part of a cell
    costs its pairs and nothing else) and ``ds = p (do v^T - delta)
    scale``, both float32.  Raw-dtype dots with f32 accumulation (see
    ``_make_score``); ``lse`` / ``delta`` blocks are ``[bq, 1]``."""
    score = _make_score(q_ref, k_ref, scale)

    def pair(rows, cols, mask):
        r = slice(*rows)
        s = score(rows, cols)
        if mask is not None:
            s = mask(s)
        p = jnp.exp(s - lse_ref[r, :])
        dp = jax.lax.dot_general(do_ref[r, :], v_ref[slice(*cols), :],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        return p, p * (dp - d_ref[r, :]) * scale
    return pair


#: lanes of a forward's row statistics (a vreg's)
_STAT_LANES = 128


def _lanes(x, width: int):
    """A row statistic ``[rows, _STAT_LANES]``, every lane of a row the same
    value, over ``width`` lanes: whole copies of its vregs where ``width``
    is whole lane tiles (none at ``_STAT_LANES``), else a broadcast."""
    from jax.experimental.pallas import tpu as pltpu
    if width % _STAT_LANES:
        return jnp.broadcast_to(x[:, :1], (x.shape[0], width))
    return pltpu.repeat(x, width // _STAT_LANES, axis=1)


def _stat_scratch(rows: int, d_v: int):
    """The online softmax's carried state as VMEM scratch: ``m`` and ``l``
    ``[rows, _STAT_LANES]`` float32, a row's value in every lane, and the
    accumulator ``[rows, d_v]``."""
    from jax.experimental.pallas import tpu as pltpu
    return [pltpu.VMEM((rows, _STAT_LANES), jnp.float32),
            pltpu.VMEM((rows, _STAT_LANES), jnp.float32),
            pltpu.VMEM((rows, d_v), jnp.float32)]


def _softmax_init(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _softmax_step(s, v_ref, m_ref, l_ref, acc_ref, rows=..., cols=...):
    """One online-softmax step of BOTH forwards (``_flash_kernel``,
    ``_select_fwd_kernel``): the MASKED float32 scores ``s`` of rows ``rows``
    of the q tile against keys ``cols`` of the k tile (static slices; the
    whole tile where left out) folded into the state.  The row statistics
    are ``[rows, _STAT_LANES]``, a row's value in every lane, from the
    reduction to the rescale: the scores' tile and the accumulator read them
    as whole vregs and nothing turns between lanes and sublanes (as 1-D
    ``(rows,)`` scratch that turning was 1.9 of a 512 x 512 selected cell's
    3.1 us, PR 63, and the causal forward's fixed cost a step, PR 66:
    ``attention``'s docstring).  A masked score is the caller's: the causal
    parts' finite ``_NEG_INF``, the selected form's ``-inf`` under the
    FINITE first maximum of ``_softmax_init``."""
    m_prev = m_ref[rows]
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - _lanes(m_new, s.shape[-1]))
    l_ref[rows] = l_ref[rows] * alpha + p.sum(-1, keepdims=True)
    # p rounds to the input dtype for the MXU (p in [0, 1]; flash-2
    # standard — same precision class as a dense bf16 attention)
    acc_ref[rows] = acc_ref[rows] * _lanes(alpha, acc_ref.shape[-1]) \
        + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[cols], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    m_ref[rows] = m_new


def _softmax_finish(o_ref, lse_ref, m_ref, l_ref, acc_ref):
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[...] = (acc_ref[...] / _lanes(l, acc_ref.shape[-1])
                  ).astype(o_ref.dtype)
    # lse rides a [bh, s, 1] buffer: TPU lowering requires the last two
    # block dims divisible by (8, 128) or equal to the array dims, which
    # a [bh, s] row block of (1, block_q) cannot satisfy
    lse_ref[...] = (m_ref[...] + jnp.log(l))[:, :1]


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                  *, block_q: int, block_k: int, num_k: int, scale: float,
                  causal: bool, window=None, step=None):
    """3-D grid (batch*heads, q blocks, k blocks): one K/V block resident in
    VMEM at a time, online-softmax state carried in VMEM scratch across the
    innermost k dimension — VMEM use is O(block) regardless of sequence
    length (a whole-K/V-resident variant OOMs scoped vmem at 16k)."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    kk = ki = pl.program_id(2)
    if window is not None:
        # the inner dimension walks the band only (``num_k`` is its length)
        ki = _window_k_range(qi, block_q, block_k, window)[0] + kk

    @pl.when(kk == 0)
    def _init():
        _softmax_init(m_ref, l_ref, acc_ref)

    score = _make_score(q_ref, k_ref, scale)

    def _step(rows, cols, mask):
        s = score(rows, cols)
        if mask is not None:
            s = mask(s)
        _softmax_step(s, v_ref, m_ref, l_ref, acc_ref, slice(*rows),
                      slice(*cols))

    _masked_step(qi, ki, block_q, block_k, causal, _step, window=window,
                 carried=True, width=q_ref.shape[-1], step_size=step)

    @pl.when(kk == num_k - 1)
    def _finish():
        _softmax_finish(o_ref, lse_ref, m_ref, l_ref, acc_ref)


def _kernel_name(base: str, causal: bool, window, step=None) -> str:
    """``base`` + ``_causal`` (lets the FLOP counter subtract the skipped
    dead cells, utils/flops.py count_matmul_flops_split) or ``_window`` (a
    windowed call's grid holds its band only; the trace tells the two
    apart by it) or ``_blockdiff`` (the block-diffusion mask's far part: the
    causal grid under the stepped diagonal)."""
    if step is not None:
        return base + "_blockdiff"
    if window is not None:
        return base + "_window"
    return base + "_causal" if causal else base


def kernel_block(s: int, cap: int = 1024) -> int:
    """Tuned tile size: the largest power-of-two divisor of ``s`` up to the
    cap (1024 — see ``attention``'s docstring for the measurements).  The
    single source for both the single-chip dispatch and the ring-attention
    hop path, so a retune cannot leave one of them on a stale size."""
    blk = cap
    while s % blk:
        blk //= 2
    return blk


def _fwd_flat(qt, kt, vt, scale, causal, block_q, block_k, interpret,
              out_dtype=None, window=None, step=None):
    """Flat-core forward: q/k [bh, s, d], v [bh, s, d_v] -> (out [bh, s,
    d_v], lse [bh, s]).

    The flat layout is shared with the ring-attention hop path
    (parallel/ring_attention.py) — each ring hop runs this kernel on one
    chunk pair and merges the normalized (out, lse) partials outside;
    ``out_dtype`` lets that caller take f32 partials so the cross-hop
    accumulation rounds once at the end, not per hop."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, s, d = qt.shape
    sk, dv = kt.shape[1], vt.shape[2]
    block_q = min(block_q, s)
    block_k = min(block_k, sk)
    num_k = sk // block_k
    out_dtype = qt.dtype if out_dtype is None else out_dtype

    if window is not None:
        # the inner dimension walks the band only
        num_k = _window_inner(s // block_q, lambda j: _window_k_range(
            j, block_q, block_k, window))
    kernel = functools.partial(_flash_kernel, block_q=block_q, block_k=block_k,
                               num_k=num_k, scale=scale, causal=causal,
                               window=window, step=step)
    _kmap = _frontier_kv_map(block_q, block_k, causal, window)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, s // block_q, num_k),
        in_specs=[pl.BlockSpec((None, block_q, d), lambda i, j, kk: (i, j, 0)),
                  pl.BlockSpec((None, block_k, d), _kmap),
                  pl.BlockSpec((None, block_k, dv), _kmap)],
        out_specs=[pl.BlockSpec((None, block_q, dv), lambda i, j, kk: (i, j, 0)),
                   pl.BlockSpec((None, block_q, 1), lambda i, j, kk: (i, j, 0))],
        out_shape=[jax.ShapeDtypeStruct((bh, s, dv), out_dtype),
                   jax.ShapeDtypeStruct((bh, s, 1), jnp.float32)],
        scratch_shapes=_stat_scratch(block_q, dv),
        # the innermost k dimension carries the online-softmax scratch state
        # and MUST run sequentially ("arbitrary"); the outer two dims are
        # independent and may be partitioned across megacore.  vmem budget:
        # the d128-tuned tiles overflow the compiler's 16M default by <1M at
        # d=256 (the [blk, d] operand blocks scale with d); v5e has 128M
        # physical VMEM, so raise the budget instead of shrinking tiles
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_KERNEL_VMEM_BUDGET),
        name=_kernel_name("flash_fwd", causal, window, step),
        interpret=interpret,
    )(qt, kt, vt)
    return out, lse[..., 0]


#: query rows of one static sub-block of a band cell, each against its own
#: ``sub + reach`` keys.  At a window of 512, 256 rows score 768 keys, 1.5 x
#: the band's pairs; 128 rows score 640, 1.25 x, and ran SLOWER on a v5e
#: (PERF.md section 6, PR 41: ~0.23 us a sub-block beside 4.4 ps a pair — a
#: key tile the MXU loads serves only ``sub`` query rows)
_BAND_SUB = 256
#: q tile cap of the band forward (``band_block``): the cell's sub-blocks are
#: a static loop, so the tile only amortises the grid step and the q / out
#: DMAs (measured 5.38 / 4.99 / 4.92 ms a call at 512 / 1024 / 2048 and the
#: Laguna cell's shape; 2048 doubles the lowering time for 1.4%)
_BAND_BLOCK_CAP = 1024


def band_block(s: int) -> int:
    """Q tile of the band forward: ``kernel_block`` under ``_BAND_BLOCK_CAP``."""
    return kernel_block(s, cap=_BAND_BLOCK_CAP)


def _band_geometry(s: int, window: int, block_q: int):
    """``(sub, reach, span)`` of a band cell: a sub-block of ``sub`` query
    rows (``_BAND_SUB``, or what of it divides the tile) starting at ``q0``
    takes keys ``q0 - reach .. q0 + sub - 1``, its
    start clamped at position 0: ``reach`` is ``window - 1`` rounded up to
    whole sub-blocks (so every start is a multiple of ``sub``), ``span`` the
    keys a sub-block scores (the whole sequence at most)."""
    sub = math.gcd(_BAND_SUB, block_q)
    reach = -(-(window - 1) // sub) * sub
    return sub, reach, min(sub + reach, s)


def band_applies(s: int, d: int, window: int, itemsize: int,
                 d_v: typing.Optional[int] = None) -> bool:
    """Whether a windowed call's FORWARD is the band kernel (``_fwd_band``):
    a cell's VMEM at ``band_block``'s q tile — the head-sequence's K and V
    resident (two pipeline buffers each), the q, out and lse tiles (two
    each; an lse row pads to a lane tile), one sub-block's float32 scores,
    their exponentials and those in the operand dtype — inside the kernels'
    budget.  A window (or a sequence) too long for that keeps the tiled
    forward (``_fwd_flat``).  Pure in its arguments: the one predicate
    ``_flash_fwd_impl``, ``attention`` and the ``hbnlp_flash_band_layers``
    gauge (model/spatial.py) read."""
    block_q = band_block(s)
    sub, _, span = _band_geometry(s, window, block_q)
    d_v = d if d_v is None else d_v
    resident = 2 * s * (d + d_v) * itemsize
    tiles = 2 * (block_q * (d + d_v) * itemsize + block_q * 128 * 4)
    scores = sub * span * (4 + 4 + itemsize)
    return resident + tiles + scores <= _KERNEL_VMEM_BUDGET


def _band_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q: int,
                 sub: int, reach: int, span: int, window: int, scale: float):
    """Grid (batch*heads, q tiles), no k dimension: a cell holds a q tile
    and — resident across the head-sequence's cells, fetched once — its whole
    K and V ``[s, d]``.  Each static sub-block of ``sub`` rows scores its own
    ``span`` keys once and takes the softmax in ONE pass: row maximum, exp,
    row sum, one P V matmul, ``out`` and ``lse = m + log l`` written at once;
    no state is carried between cells, and no cell branches.  The mask is by
    position relative to the span's first key, which the first sub-blocks
    clamp at position 0: one form for every sub-block (masking the interior
    ones by constants, and only the columns the band's edges cross, measured
    no faster: the kernel is not bound by its elementwise passes)."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    # query row - key column of a span's pair, before the span's offset
    rel = jax.lax.broadcasted_iota(jnp.int32, (sub, span), 0) \
        - jax.lax.broadcasted_iota(jnp.int32, (sub, span), 1)
    for r in range(block_q // sub):
        q0 = qi * block_q + r * sub
        start = jnp.maximum(q0 - reach, 0)
        if sub % 8 == 0:
            start = pl.multiple_of(start, sub)
        rows, keys = pl.ds(r * sub, sub), pl.ds(start, span)
        s = jax.lax.dot_general(q_ref[rows, :], k_ref[keys, :],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        # pair (a, c) is q0 - start + a - c keys back
        back = q0 - start
        s = jnp.where((rel >= -back) & (rel < window - back), s, _NEG_INF)
        m = s.max(-1, keepdims=True)
        p = jnp.exp(s - m)
        # a row sees its own key: l >= 1
        l = p.sum(-1, keepdims=True)
        # p rounds to the input dtype for the MXU, as in ``_flash_kernel``
        acc = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[keys, :],
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        o_ref[rows, :] = (acc / l).astype(o_ref.dtype)
        lse_ref[rows, :] = m + jnp.log(l)


def _fwd_band(qt, kt, vt, scale, block_q, window, interpret):
    """Flat-core forward of a windowed call as a band kernel: q/k/v ``[bh,
    s, d]`` -> ``(out [bh, s, d], lse [bh, s])``, the contract of
    ``_fwd_flat``.  K and V are read as they arrive (no padded copy): a
    head-sequence's whole K and V are one block whose index does not change
    across its q tiles, so the pipeline fetches each once."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, s, d = qt.shape
    dv = vt.shape[2]
    block_q = min(block_q, s)
    sub, reach, span = _band_geometry(s, window, block_q)
    kernel = functools.partial(_band_kernel, block_q=block_q, sub=sub,
                               reach=reach, span=span, window=window,
                               scale=scale)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, s // block_q),
        in_specs=[pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
                  pl.BlockSpec((None, s, d), lambda i, j: (i, 0, 0)),
                  pl.BlockSpec((None, s, dv), lambda i, j: (i, 0, 0))],
        out_specs=[pl.BlockSpec((None, block_q, dv), lambda i, j: (i, j, 0)),
                   pl.BlockSpec((None, block_q, 1), lambda i, j: (i, j, 0))],
        out_shape=[jax.ShapeDtypeStruct((bh, s, dv), qt.dtype),
                   jax.ShapeDtypeStruct((bh, s, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_KERNEL_VMEM_BUDGET),
        name=_kernel_name("flash_fwd", True, window),
        interpret=interpret,
    )(qt, kt, vt)
    return out, lse[..., 0]


def _flash_fwd_impl(q, k, v, scale, causal, block_q, block_k, interpret,
                    window=None, step=None):
    """Returns (out [b, s, h, d], lse [b*h, s]) — lse is the backward's
    softmax residual (flash-2: p is recomputed per block as exp(s - lse))."""
    b, s, h, d = q.shape
    dv = v.shape[-1]
    # [b, s, h, d] -> [b*h, s, d]
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, s, dv)
    if window is not None and band_applies(s, d, window, q.dtype.itemsize,
                                           dv):
        out, lse = _fwd_band(qt, kt, vt, scale, block_q, window, interpret)
    else:
        out, lse = _fwd_flat(qt, kt, vt, scale, causal, block_q, block_k,
                             interpret, window=window, step=step)
    return out.reshape(b, h, s, dv).transpose(0, 2, 1, 3), lse


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dq_ref,
                   acc_ref, *, block_q: int, block_k: int, num_k: int,
                   scale: float, causal: bool, window=None, step=None):
    """dq: grid (b*h, q blocks, k blocks), k innermost; dq accumulates in
    VMEM scratch; causally-dead k blocks are skipped."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    kk = ki = pl.program_id(2)
    if window is not None:
        ki = _window_k_range(qi, block_q, block_k, window)[0] + kk

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pair = _make_pair(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, scale)

    def _step(rows, cols, mask):
        # p and ds round to the operand dtype before their MXU dots
        _, ds = pair(rows, cols, mask)
        acc_ref[slice(*rows), :] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[slice(*cols), :],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    _masked_step(qi, ki, block_q, block_k, causal, _step, window=window,
                 step_size=step)

    @pl.when(kk == num_k - 1)
    def _finish():
        dq_ref[...] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dk_ref,
                    dv_ref, dk_acc, dv_acc, *, block_q: int, block_k: int,
                    num_q: int, scale: float, causal: bool, window=None,
                    seq_q: int = 0, step=None):
    """dk/dv: grid (b*h, k blocks, q blocks), q innermost; for a fixed K/V
    block only q blocks at-or-after it contribute — strictly-earlier
    (causally dead) q blocks are skipped."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    jj = pl.program_id(2)
    qi, valid = _window_q_index(ki, jj, block_q, block_k, window, seq_q)

    @pl.when(jj == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    pair = _make_pair(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, scale)

    def _step(rows, cols, mask):
        r, c = slice(*rows), slice(*cols)
        p, ds = pair(rows, cols, mask)
        dk_acc[c, :] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[r, :], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dv_acc[c, :] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[r, :], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _masked_step(qi, ki, block_q, block_k, causal, _step, window=window,
                 valid=valid, step_size=step)

    @pl.when(jj == num_q - 1)
    def _finish():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


@functools.lru_cache(maxsize=None)
def _live_steps(num_q: int, num_k: int, block_q: int, block_k: int,
                causal: bool, window, k_major: bool = False):
    """``(qi, ki, edge)`` int32 ``[steps]``: the LIVE cells of a call's
    ``num_q`` x ``num_k`` rectangle in the order a one-pass backward walks
    them — a q block at a time, its k blocks ascending, or (``k_major``, the
    dq-resident form's) a k block at a time, its q blocks ascending — and
    whether a step is its OUTER block's first (``edge & 1``) and last
    (``edge & 2``).  A cell is live where ``_causal_split`` /
    ``_window_split`` say so (the stepped diagonal's cells are the causal
    ones); every outer block has one."""
    import numpy as np
    cells = [(j, c) for j in range(num_q) for c in range(num_k)
             if not causal or _rect_state(j * block_q - c * block_k,
                                          (0, block_q), (0, block_k),
                                          window)[0]]
    if k_major:
        cells.sort(key=lambda cell: cell[::-1])
    qi, ki = (np.asarray(x, np.int32) for x in zip(*cells))
    outer = ki if k_major else qi
    assert len(set(outer.tolist())) == (num_k if k_major else num_q)
    turn = outer[1:] != outer[:-1]
    edge = np.r_[True, turn] + 2 * np.r_[turn, True]
    return qi, ki, edge.astype(np.int32)


def _bwd_one_pass_kernel(qi_ref, ki_ref, edge_ref, q_ref, k_ref, v_ref,
                         do_ref, lse_ref, d_ref, dq_ref, dk_ref, dv_ref,
                         dq_acc, dk_acc, dv_acc, *, block_q: int,
                         block_k: int, scale: float, causal: bool,
                         window=None, step=None):
    """One-pass backward: grid (b*h, live cells) — a head's live cells an
    OUTER block at a time, its inner blocks ascending (``_live_steps``,
    prefetched: no step for a dead cell).

    The split dq and dk/dv kernels EACH recompute the two shared per-pair
    tensors p = exp(q·kᵀ − lse) and dp = do·vᵀ — 7 dots + 2 exp per live
    pair across the two passes.  This kernel computes them once and gives
    all three gradients — 5 dots + 1 exp — which also lets the dq
    contribution ride the MXU work that hides the exp.  Every gradient
    accumulates in float32 VMEM and nothing partial reaches HBM.  ONE side's
    gradients stay for the HEAD's whole sweep, in ``[tiles, rows, width]``
    scratch (zeroed at its first step; at its last, cast into output blocks
    whose index changes with the head only, so each is written back once);
    the other side's are a ``[rows, width]`` scratch over an outer block's
    inner walk, as in the split kernels.  Which side is which is in the
    scratch the caller hands over (``_bwd_flat_one_pass``):

    * q blocks outermost (PR 68): dq over a q block's k walk, a head's whole
      dk and dv resident — ``sk * (d + d_v)`` floats;
    * k blocks outermost (PR 73): dk and dv over a k block's q walk, the dk/dv
      kernel's order, a head's whole dq resident — ``s * d`` floats, half of
      the above where the widths are equal, which is what lets head width 512
      at 16,384 positions in.

    Either walk adds a block's contributions in the split kernels' order
    (dq its k blocks ascending, dk / dv their q blocks ascending).  What each
    costs in VMEM decides the call's form (``backward_form``)."""
    from jax.experimental import pallas as pl

    t = pl.program_id(1)
    qi, ki, edge = qi_ref[t], ki_ref[t], edge_ref[t]
    grads = ((dq_ref, dq_acc), (dk_ref, dk_acc), (dv_ref, dv_acc))
    # a head-resident accumulator is [tiles, rows, width]
    held = [(ref, acc) for ref, acc in grads if len(acc.shape) == 3]
    walked = [(ref, acc) for ref, acc in grads if len(acc.shape) == 2]

    def _each_held_tile(body):
        def run(c, carry):
            for ref, acc in held:
                body(ref, acc, c)
            return carry
        jax.lax.fori_loop(0, held[0][1].shape[0], run, 0)

    @pl.when(t == 0)
    def _init_head():
        def zero(_, acc, c):
            acc[c] = jnp.zeros(acc.shape[1:], acc.dtype)
        _each_held_tile(zero)

    @pl.when((edge & 1) == 1)
    def _init():
        for _, acc in walked:
            acc[...] = jnp.zeros_like(acc)

    pair = _make_pair(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, scale)

    def at(acc, tile, rows):
        return (tile, rows) if len(acc.shape) == 3 else (rows,)

    def _step(rows, cols, mask):
        # identical dot/rounding structure to the split kernels (numerics
        # match to f32-accumulation order): p and ds round to the operand
        # dtype before their MXU dots, accumulation stays f32
        r, c = slice(*rows), slice(*cols)
        p, ds = pair(rows, cols, mask)
        ds = ds.astype(q_ref.dtype)
        dq_acc[at(dq_acc, qi, r)] += jax.lax.dot_general(
            ds, k_ref[c, :], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[at(dk_acc, ki, c)] += jax.lax.dot_general(
            ds, q_ref[r, :], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dv_acc[at(dv_acc, ki, c)] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[r, :], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _masked_step(qi, ki, block_q, block_k, causal, _step, window=window,
                 step_size=step)

    @pl.when((edge & 2) == 2)
    def _finish():
        for ref, acc in walked:
            ref[...] = acc[...].astype(ref.dtype)

    @pl.when(t == pl.num_programs(1) - 1)
    def _finish_head():
        def cast(ref, acc, c):
            rows = acc.shape[1]
            # the output block is the accumulator's [tiles, rows, width] or
            # the head's plain [tiles * rows, width]
            tile = c if len(ref.shape) == 3 \
                else pl.ds(pl.multiple_of(c * rows, rows), rows)
            ref[tile] = acc[c].astype(ref.dtype)
        _each_held_tile(cast)


#: scoped VMEM the one-pass backward asks for, of the 128 MiB a v5e / v5p
#: core has: its head-resident accumulators are what the call is for
_ONE_PASS_VMEM_BUDGET = 100 * 1024 * 1024
#: the most multiply-adds (dots x rows x keys x head width, summed over the
#: body's branches) a one-pass BACKWARD body may hold: the forward's two whole
#: 1,024 x 2,048 tiles of two dots at head width 512, the largest body
#: measured to run at speed (``_FORWARD_BODY_CAP``).  The one pass at 1,024 x
#: 1,024 tiles and width 512 — the interior's branch and the diagonal cell's
#: three quadrants, 1.75 cells of five dots, 4.70 G — ran 89.06 ms a call on
#: a v5e where the split pair takes 85.43 and the same pass at k tiles of 512
#: (3.02 G) 59.61, 93.6% of its 55.8 ms floor; the dk/dv kernel's 1.75 cells
#: of four dots (3.76 G) run at 93.4% of theirs (PERF.md section 6, PR 73;
#: the cause, as for the forward's, not established)
_ONE_PASS_BODY_CAP = 2 * _FORWARD_BODY_CAP


def _lane_pad(width: int) -> int:
    """A minor dimension as VMEM holds it: whole tiles of 128 lanes."""
    return -(-width // 128) * 128


def _one_pass_fits(held: int, walked: int, block_q: int, block_k: int,
                   wide: int, itemsize: int,
                   out_itemsize: typing.Optional[int]) -> bool:
    """The one-pass backward's VMEM inside ``_ONE_PASS_VMEM_BUDGET``:
    ``held`` elements of gradient resident for a head's sweep (a float32
    accumulator and two buffers of the output block it is cast into),
    ``walked`` elements summed over an outer block's inner walk (two buffers
    of output tile and a float32 accumulator), the q / k / v / do tiles (key
    and value width together ``wide``) and the lse / delta columns (a lane
    tile a row), two buffers each, and a cell's score planes: four float32
    (s, p, dp, ds) and p, ds in the operand dtype."""
    out_itemsize = itemsize if out_itemsize is None else out_itemsize
    resident = held * (4 + 2 * out_itemsize)
    tiles = 2 * ((block_q + block_k) * wide * itemsize
                 + 2 * block_q * 128 * 4 + walked * out_itemsize) \
        + walked * 4
    scores = block_q * block_k * (4 * 4 + 2 * itemsize)
    return resident + tiles + scores <= _ONE_PASS_VMEM_BUDGET


def one_pass_applies(sk: int, d: int, d_v: int, block_q: int, block_k: int,
                     itemsize: int,
                     out_itemsize: typing.Optional[int] = None) -> bool:
    """Whether the one-pass backward with a head's dk and dv RESIDENT (q
    blocks outermost, PR 68) fits: its VMEM over ``sk`` keys a head at tiles
    ``block_q`` x ``block_k`` inside ``_ONE_PASS_VMEM_BUDGET`` — the float32
    dk and dv accumulators of a whole head, the output blocks they are cast
    into, the q / k / v / do tiles, the lse / delta columns (a lane tile a
    row) and the dq tile (two pipeline buffers each), dq's accumulator, and
    a cell's score planes: four float32 (s, p, dp, ds) and p, ds in the
    operand dtype.  At 16,384 keys and widths 192 / 128 that is 50 + 7 + 21
    = 79 MB (a width pads to whole lane tiles: 192 holds 256); head width
    512 there (2 x 33.5 MB of accumulators and as much again of output
    blocks) does not fit.  The first question ``backward_form`` asks."""
    block_q, block_k = min(block_q, sk), min(block_k, sk)
    wide = _lane_pad(d) + _lane_pad(d_v)
    return _one_pass_fits(sk * wide, block_q * _lane_pad(d), block_q, block_k,
                          wide, itemsize, out_itemsize)


def dq_resident_applies(s: int, d: int, d_v: int, block_q: int, block_k: int,
                        itemsize: int,
                        out_itemsize: typing.Optional[int] = None) -> bool:
    """Whether the one-pass backward with a head's dq RESIDENT (k blocks
    outermost, PR 73) fits: its VMEM over ``s`` queries a head inside
    ``_ONE_PASS_VMEM_BUDGET``, by ``one_pass_applies``' account with the
    sides exchanged — the float32 dq accumulator of a whole head and the
    output block it is cast into (two pipeline buffers), the q / k / v / do
    tiles, the lse / delta columns and the dk / dv tiles (two buffers each),
    dk's and dv's accumulators, and a cell's score planes.  At 16,384
    queries and head width 512: 33.5 + 2 x 16.8 = 67.1 MB resident (HALF of
    what dk and dv resident take: one gradient, not two) and, at the 1,024 x
    512 tiles ``one_pass_tiles`` gives that width, 6.3 of operand tiles, 2.1
    of columns, 2.1 + 2.1 of dk / dv tiles and accumulators, 10.5 of score
    planes: 90 MB = 86 MiB (107 MB at 1,024 x 1,024).  The second question
    ``backward_form`` asks."""
    block_q, block_k = min(block_q, s), min(block_k, s)
    wide = _lane_pad(d) + _lane_pad(d_v)
    return _one_pass_fits(s * _lane_pad(d), block_k * wide, block_q, block_k,
                          wide, itemsize, out_itemsize)


def _body_pairs(block_q: int, block_k: int, window, carried: bool) -> int:
    """The pairs a causal kernel's BODY scores over its branches
    (``_masked_step``): the interior's whole tile and each edge offset's
    parts.  What a body holds of matmuls is this by its dots' widths."""
    return block_q * block_k + sum(
        part.pairs for off in _edge_offsets(block_q, block_k, window)
        for part in _cell_parts(block_q, block_k, off, window, carried))


def one_pass_tiles(block_q: int, block_k: int, d: int, d_v: int,
                   causal: bool = True, window=None
                   ) -> typing.Tuple[int, int]:
    """The tiles the one-pass backward runs at where its caller asks for
    ``block_q`` x ``block_k``: the k tile halved (to 128 at least) while the
    body — three dots over the key's width and two over the value's a pair,
    over the pairs of ``_body_pairs`` — passes ``_ONE_PASS_BODY_CAP``.  Head
    width 512 at 1,024 x 1,024 becomes 1,024 x 512; widths 64 to 256 keep
    what they ask for.  Pure in its arguments: ``backward_form`` holds the
    one pass's VMEM account to these, ``_bwd_flat`` runs it at them and
    ``scored_over_live`` counts its pairs by them."""
    width = 3 * _lane_pad(d) + 2 * _lane_pad(d_v)

    def volume(block_k):
        pairs = _body_pairs(block_q, block_k, window, False) if causal \
            else block_q * block_k
        return width * pairs

    while block_k > 128 and volume(block_k) > _ONE_PASS_BODY_CAP:
        block_k //= 2
    return block_q, block_k


#: the backward's forms, in the order ``backward_form`` asks for them
BACKWARD_FORMS = ("dkv_resident", "dq_resident", "split")


def backward_form(s: int, sk: int, d: int, d_v: int, block_q: int,
                  block_k: int, itemsize: int,
                  out_itemsize: typing.Optional[int] = None,
                  causal: bool = True, window=None) -> str:
    """The form a call's BACKWARD takes (one of ``BACKWARD_FORMS``), from
    its ``s`` queries and ``sk`` keys a head, its widths, the tiles it asks
    for (the one pass is held to ``one_pass_tiles`` of them) and its dtypes
    against VMEM:

    * ``"dkv_resident"`` — the one pass, q blocks outermost, where a head's
      dk and dv fit (``one_pass_applies``): every call of widths 64, 128 and
      192 / 128 at 4,096 to 16,384 positions, and a ring hop's chunk pair;
    * else ``"dq_resident"`` — the one pass, k blocks outermost, where a
      head's dq fits (``dq_resident_applies``): head width 512 at 16,384
      positions, the long-context recipe's call;
    * else ``"split"`` — the dq and the dk/dv kernel, O(tile) VMEM whatever
      the length: width 512 at 65,536, or at 16,384 with float32 outputs.

    Pure in its arguments: the one predicate ``_bwd_flat``, the
    ``hbnlp_flash_backward_one_pass_layers`` gauge (model/spatial.py) and
    ``scripts/kernel_parity.py`` read."""
    tiles = one_pass_tiles(block_q, block_k, d, d_v, causal, window)
    if one_pass_applies(sk, d, d_v, *tiles, itemsize, out_itemsize):
        return "dkv_resident"
    if dq_resident_applies(s, d, d_v, *tiles, itemsize, out_itemsize):
        return "dq_resident"
    return "split"


def _grad_dtypes(qt, kt, vt, out_dtype):
    """(dq, dk, dv) dtypes: each operand's own, or ``out_dtype`` for all —
    the same in every backward form (which one runs is a size decision and
    must not change output precision)."""
    return tuple(x.dtype if out_dtype is None else out_dtype
                 for x in (qt, kt, vt))


def _bwd_flat_one_pass(qt, kt, vt, dot, lse3, delta, scale, causal, bq, bk,
                       interpret, out_dtype=None, window=None, step=None,
                       dq_resident: bool = False):
    """The one-pass backward (see ``_bwd_one_pass_kernel``): the grid's
    second dimension is the call's live cells, causal, windowed or all;
    ``dq_resident``: k blocks outermost, a head's dq held, instead of q
    blocks outermost, its dk and dv held."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, s, d = qt.shape
    sk, dv = kt.shape[1], vt.shape[2]
    nq, nk = s // bq, sk // bk
    steps = _live_steps(nq, nk, bq, bk, causal, window, dq_resident)
    dq_dtype, dk_dtype, dv_dtype = _grad_dtypes(qt, kt, vt, out_dtype)

    def q_side(width):
        return pl.BlockSpec((None, bq, width),
                            lambda i, t, qi, ki, edge: (i, qi[t], 0))

    def k_side(width):
        return pl.BlockSpec((None, bk, width),
                            lambda i, t, qi, ki, edge: (i, ki[t], 0))

    def walked(side, rows, length, width, dtype):
        # (out spec, out shape, float32 scratch) of a gradient summed over
        # an outer block's inner walk: a tile
        return (side(width), jax.ShapeDtypeStruct((bh, length, width), dtype),
                pltpu.VMEM((rows, width), jnp.float32))

    def held(tiles, rows, width, dtype):
        # the same of a gradient a head holds whole, [tiles, rows, width]:
        # the index changes with the head only, so each is written back once
        # a head — behind the next head's sweep (two buffers: one measured
        # 0.7-4% slower a call on a v5e, the write-back waited for; PERF.md
        # section 6, PR 68)
        return (pl.BlockSpec((None, tiles, rows, width),
                             lambda i, t, *_: (i, 0, 0, 0)),
                jax.ShapeDtypeStruct((bh, tiles, rows, width), dtype),
                pltpu.VMEM((tiles, rows, width), jnp.float32))

    if dq_resident:
        # dq leaves as the plain [bh, s, d] it is everywhere else (the trace
        # names a call by its first output)
        grads = ((pl.BlockSpec((None, s, d), lambda i, t, *_: (i, 0, 0)),
                  jax.ShapeDtypeStruct((bh, s, d), dq_dtype),
                  pltpu.VMEM((nq, bq, d), jnp.float32)),
                 walked(k_side, bk, sk, d, dk_dtype),
                 walked(k_side, bk, sk, dv, dv_dtype))
    else:
        grads = (walked(q_side, bq, s, d, dq_dtype),
                 held(nk, bk, d, dk_dtype), held(nk, bk, dv, dv_dtype))
    out_specs, out_shape, scratch = (list(x) for x in zip(*grads))
    dq, dk, dv_ = pl.pallas_call(
        functools.partial(_bwd_one_pass_kernel, block_q=bq, block_k=bk,
                          scale=scale, causal=causal, window=window,
                          step=step),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(bh, len(steps[0])),
            in_specs=[q_side(d), k_side(d), k_side(dv), q_side(dv),
                      q_side(1), q_side(1)],
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        # the held gradients accumulate across a head's whole walk: only
        # the heads are independent
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_ONE_PASS_VMEM_BUDGET),
        name=_kernel_name("flash_bwd_fused", causal, window, step),
        interpret=interpret,
    )(*steps, qt, kt, vt, dot, lse3, delta)
    return dq, dk.reshape(bh, sk, d), dv_.reshape(bh, sk, dv)


def _bwd_flat(qt, kt, vt, dot, lse3, delta, scale, causal, bq, bk,
              interpret, out_dtype=None, window=None, step=None):
    """Flat-core backward: q/k [bh, s, d], v/dout [bh, s, d_v], lse/delta
    [bh, s, 1] -> (dq, dk [bh, s, d], dv [bh, s, d_v]).  ``lse``/``delta`` are the GLOBAL softmax
    residuals — flash-2's decomposition makes per-block contributions
    correct under any partitioning of the key space, which is what lets
    the ring-attention backward run this same core per hop pair
    (``out_dtype=f32`` there: per-hop grad pieces accumulate across P hops
    and must not round per hop).

    Three forms, chosen by ``backward_form`` from the call's shapes: the
    ONE-PASS kernel (``_bwd_one_pass_kernel`` — 5 dots + 1 exp per pair
    instead of the split kernels' 7 + 2) with a head's dk and dv resident
    where they fit VMEM, with its dq resident where that does, the split dq
    / dk/dv kernels where neither does.  The one pass runs at
    ``one_pass_tiles`` of the tiles asked for (a narrower k tile where the
    body would be too large: head width 512), the split pair at those."""
    d, dv = qt.shape[2], vt.shape[2]
    out_itemsize = jnp.dtype(_grad_dtypes(qt, kt, vt, out_dtype)[0]).itemsize
    form = backward_form(qt.shape[1], kt.shape[1], d, dv, bq, bk,
                         qt.dtype.itemsize, out_itemsize, causal, window)
    if form == "split":
        return _bwd_flat_split(qt, kt, vt, dot, lse3, delta, scale, causal,
                               bq, bk, interpret, out_dtype, window, step)
    return _bwd_flat_one_pass(qt, kt, vt, dot, lse3, delta, scale, causal,
                              *one_pass_tiles(bq, bk, d, dv, causal, window),
                              interpret, out_dtype, window, step,
                              dq_resident=form == "dq_resident")


def _bwd_flat_split(qt, kt, vt, dot, lse3, delta, scale, causal, bq, bk,
                    interpret, out_dtype=None, window=None, step=None):
    """The split backward: a dq kernel (k innermost) and a dk/dv kernel (q
    innermost), each with O(tile) VMEM whatever the sequence's length."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, s, d = qt.shape
    sk, dv = kt.shape[1], vt.shape[2]
    dq_dtype, dk_dtype, dv_dtype = _grad_dtypes(qt, kt, vt, out_dtype)
    nq, nk = s // bq, sk // bk
    inner_k, inner_q = nk, nq
    if window is not None:
        # the grids' inner dimensions walk the band only
        inner_k = _window_inner(nq, lambda j: _window_k_range(j, bq, bk,
                                                              window))
        inner_q = _window_inner(nk, lambda kk: _window_q_range(kk, bq, bk,
                                                               window, nq))

    _kv_map = _frontier_kv_map(bq, bk, causal, window)
    _q_map_dkv = _frontier_q_map(bq, bk, causal, window, nq)

    row_spec = pl.BlockSpec((None, bq, 1), lambda i, j, kk: (i, j, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_q=bq, block_k=bk,
                          num_k=inner_k, scale=scale, causal=causal,
                          window=window, step=step),
        grid=(bh, nq, inner_k),
        in_specs=[pl.BlockSpec((None, bq, d), lambda i, j, kk: (i, j, 0)),
                  pl.BlockSpec((None, bk, d), _kv_map),
                  pl.BlockSpec((None, bk, dv), _kv_map),
                  pl.BlockSpec((None, bq, dv), lambda i, j, kk: (i, j, 0)),
                  row_spec, row_spec],
        out_specs=pl.BlockSpec((None, bq, d), lambda i, j, kk: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), dq_dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_KERNEL_VMEM_BUDGET),
        name=_kernel_name("flash_bwd_dq", causal, window, step),
        interpret=interpret,
    )(qt, kt, vt, dot, lse3, delta)

    qrow_spec = pl.BlockSpec((None, bq, 1), _q_map_dkv)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=bq, block_k=bk,
                          num_q=inner_q, scale=scale, causal=causal,
                          window=window, seq_q=nq, step=step),
        grid=(bh, nk, inner_q),
        in_specs=[pl.BlockSpec((None, bq, d), _q_map_dkv),
                  pl.BlockSpec((None, bk, d), lambda i, kk, j: (i, kk, 0)),
                  pl.BlockSpec((None, bk, dv), lambda i, kk, j: (i, kk, 0)),
                  pl.BlockSpec((None, bq, dv), _q_map_dkv),
                  qrow_spec, qrow_spec],
        out_specs=[pl.BlockSpec((None, bk, d), lambda i, kk, j: (i, kk, 0)),
                   pl.BlockSpec((None, bk, dv), lambda i, kk, j: (i, kk, 0))],
        out_shape=[jax.ShapeDtypeStruct((bh, sk, d), dk_dtype),
                   jax.ShapeDtypeStruct((bh, sk, dv), dv_dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_KERNEL_VMEM_BUDGET),
        name=_kernel_name("flash_bwd_dkv", causal, window, step),
        interpret=interpret,
    )(qt, kt, vt, dot, lse3, delta)
    return dq, dk, dv


def _flash_bwd_pallas(q, k, v, out, lse, dout, scale, causal, block_q,
                      block_k, interpret, window=None, step=None, dlse=None):
    """Flash-2 pallas backward over [b, s, h, d] operands; every kernel
    skips the causally-dead blocks.  ``dlse`` ``[b * h, s]``: the cotangent
    of ``lse`` where a caller reads it (``d lse / d s_j = p_j``, so it
    leaves ``delta``)."""
    b, s, h, d = q.shape
    dv = v.shape[-1]
    # caller-chosen block sizes, exactly as in the forward — attention()
    # passes the tuned 1024 tiles for both passes; tests pass small blocks
    # to exercise the multi-block causal-skip and diagonal-frontier paths
    bq = min(block_q, s)
    bk = min(block_k, s)
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, s, dv)
    dot = dout.transpose(0, 2, 1, 3).reshape(b * h, s, dv)
    ot = out.transpose(0, 2, 1, 3).reshape(b * h, s, dv)
    # delta_i = dout_i . out_i (rowwise), the softmax-jacobian correction;
    # lse/delta travel as [bh, s, 1] (TPU block-tiling rule, see forward)
    delta = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32), -1,
                    keepdims=True)
    if dlse is not None:
        delta = delta - dlse[..., None].astype(jnp.float32)
    dq, dk, dv = _bwd_flat(qt, kt, vt, dot, lse[..., None], delta, scale,
                           causal, bq, bk, interpret, window=window,
                           step=step)

    def back(x):
        return x.reshape(b, h, s, x.shape[-1]).transpose(0, 2, 1, 3)

    return back(dq), back(dk), back(dv)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def flash_attention(q, k, v, scale: float = None, causal: bool = True,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False, bwd_block_q: int = None,
                    bwd_block_k: int = None, window: int = None):
    """q, k: [batch, seq, heads, d_k], v: [batch, seq, heads, d_v] ->
    [batch, seq, heads, d_v].

    ``bwd_block_q``/``bwd_block_k`` override the backward kernels' tiles
    (None = same as forward): the forward profits from a wider k tile
    (fewer online-softmax rescale steps) that pushes the dq kernel past the
    scoped-VMEM limit in the full model.  ``window`` (with ``causal``):
    query ``i`` sees keys ``i - window + 1 .. i`` only; the backward's grids
    hold the blocks that band touches, not the triangle, and the forward is
    the band kernel at q tile ``block_q`` (``block_k`` unused) where
    ``band_applies``, else the tiled kernel on such a grid."""
    out, _ = _flash_fwd_impl(q, k, v, scale, causal, block_q, block_k,
                             interpret, window)
    return out


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
               bwd_block_q, bwd_block_k, window):
    out, lse = _flash_fwd_impl(q, k, v, scale, causal, block_q, block_k,
                               interpret, window)
    return out, (q, k, v, out, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, bwd_block_q,
               bwd_block_k, window, res, dout):
    bq = block_q if bwd_block_q is None else bwd_block_q
    bk = block_k if bwd_block_k is None else bwd_block_k
    q, k, v, out, lse = res
    return _flash_bwd_pallas(q, k, v, out, lse, dout, scale, causal,
                             bq, bk, interpret, window)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def flash_precomputed(q, k, v, out, lse, scale, causal, block_q, block_k,
                      interpret, window=None):
    """Flash attention whose forward is the PROVIDED (out, lse) — no kernel
    run — while the backward is the full flash-2 pallas pass.

    The revnet/momentum backward re-runs each block's forward inside
    ``jax.vjp`` only to rebuild residuals; with the layer's (out, lse)
    stashed from the original forward (model/blocks.py ``stash`` strategy
    variants), forming the attention vjp needs no forward kernel at all —
    q/k/v come from the replayed (cheap) projections, out/lse from the
    stash.  The replayed q/k/v differ from the originals by revnet
    reconstruction ulps, the same approximation class as revnet gradients
    themselves."""
    return out


def _flash_pre_fwd(q, k, v, out, lse, scale, causal, block_q, block_k,
                   interpret, window):
    return out, (q, k, v, out, lse)


def _flash_pre_bwd(scale, causal, block_q, block_k, interpret, window, res,
                   dout):
    q, k, v, out, lse = res
    dq, dk, dv = _flash_bwd_pallas(q, k, v, out, lse, dout, scale, causal,
                                   block_q, block_k, interpret, window)
    # out/lse are stashed residual constants of the OUTER custom_vjp; their
    # cotangents are discarded upstream
    return dq, dk, dv, jnp.zeros_like(out), jnp.zeros_like(lse)


flash_precomputed.defvjp(_flash_pre_fwd, _flash_pre_bwd)


#: tile cap of a windowed call's TILED kernels, both sides: the backward
#: (one pass, or the dq / dk-dv pair) always, the forward only where the band
#: kernel declines (``band_applies``; its own tiles are ``band_block`` and
#: ``_BAND_SUB``).  A q tile's band is ``tile + window - 1`` keys wide
#: whatever the tile, so tiles much wider than the window compute mostly
#: masked pairs; tiles much narrower pay the per-cell state work the causal
#: kernels' 1024 tiles amortise
_WINDOW_BLOCK_CAP = 512


def window_block(s: int, window: int) -> int:
    """Tile of a windowed call's tiled kernels: ``kernel_block`` capped at
    the window rounded up to a power of two (128 at least,
    ``_WINDOW_BLOCK_CAP`` at most)."""
    cap = 128
    while cap < min(window, _WINDOW_BLOCK_CAP):
        cap *= 2
    return kernel_block(s, cap=cap)


def call_tiles(s: int, d: int, window, itemsize: int,
               d_v: typing.Optional[int] = None
               ) -> typing.Tuple[int, int, int, bool]:
    """``(the backward's q and k tile, the forward's q tile, the forward's k
    tile, whether the forward is the band kernel)`` of a causal call of
    ``attention`` over ``s`` positions (``window``: None, or shorter than
    ``s``; ``d`` the key's width, ``d_v`` the value's where it differs).
    Pure in its arguments: ``attention`` and the
    ``hbnlp_flash_scored_over_live_pairs`` gauge read the same tiles."""
    if window is None:
        return kernel_block(s), kernel_block(s), kernel_block(s, cap=2048), \
            False
    blk = window_block(s, window)
    if band_applies(s, d, window, itemsize, d_v):
        return blk, band_block(s), blk, True
    return blk, blk, blk, False


def scored_pairs(s: int, block_q: int, block_k: int, window=None,
                 carried: bool = False) -> int:
    """The (query, key) pairs a tiled causal kernel scores over ``s`` x ``s``
    positions: its live cells' — an interior cell whole, an edge cell the
    parts ``_cell_parts`` cuts it into (``carried``: the forward's cut).
    Python ints from the geometry the kernels branch on."""
    block_q, block_k = min(block_q, s), min(block_k, s)
    edges = {off: sum(p.pairs for p in _cell_parts(block_q, block_k, off,
                                                   window, carried))
             for off in _edge_offsets(block_q, block_k, window)}
    total = 0
    for qi in range(s // block_q):
        for ki in range(s // block_k):
            off = qi * block_q - ki * block_k
            if off in edges:
                total += edges[off]
            elif _rect_state(off, (0, block_q), (0, block_k), window)[0]:
                total += block_q * block_k
    return total


def live_pairs(s: int, window=None) -> float:
    """The pairs a causal call over ``s`` positions has to score, by AREA:
    the diagonal's own pairs count half, so that the whole triangle is the
    ``s^2 / 2`` the rooflines credit (benchmark/roofline)."""
    w = s if window is None else min(window, s)
    return w * (w + 1) / 2 + (s - w) * w - s / 2


def scored_over_live(s: int, d: int, window, itemsize: int
                     ) -> typing.Dict[str, typing.Optional[float]]:
    """``{"fwd": .., "bwd": ..}``: the pairs the tiled kernels of one causal
    call of ``attention`` score over its live pairs — how much of what an
    edge cell holds the kernels still pay for (1.0 = only the band itself).
    ``fwd`` is None where the forward is the band kernel, which has no cells
    of this kind."""
    if window is not None and window >= s:
        window = None
    blk, fwd_q, fwd_k, band = call_tiles(s, d, window, itemsize)
    live = live_pairs(s, window)
    bwd = blk, blk
    if backward_form(s, s, d, d, blk, blk, itemsize, None, True,
                     window) != "split":
        bwd = one_pass_tiles(blk, blk, d, d, True, window)
    return {"fwd": None if band else scored_pairs(s, fwd_q, fwd_k, window,
                                                  carried=True) / live,
            "bwd": scored_pairs(s, *bwd, window) / live}


def attention(q, k, v, scale: typing.Optional[float] = None,
              causal: bool = True, interpret: typing.Optional[bool] = None,
              stash: typing.Optional[dict] = None,
              window: typing.Optional[int] = None):
    """Dispatch: pallas kernel on TPU, fused XLA elsewhere.

    ``window`` (None = the whole causal triangle, today's kernels bit for
    bit): query ``i`` sees keys ``i - window + 1 .. i``.  A windowed call
    runs the ``flash_*_window`` kernels: the BACKWARD at ``window_block``
    tiles (512 x 512 at most), its grids as long as the band; the FORWARD
    as the band kernel — q tiles of ``band_block`` (1024 at most) walked in
    sub-blocks of ``_BAND_SUB`` rows, each against its own ``sub + window``
    keys of the resident K and V, no k tiles at all — where ``band_applies``
    (a cell's band fits VMEM), else tiled like the backward.  A window that
    covers the sequence is the causal call.

    ``stash``: attention-output stash channel (model/blocks.py): mode
    "collect" computes (out, lse) and appends them to ``stash["items"]``
    (the strategy's forward rule saves them as residuals); mode "provide"
    consumes the next stashed pair and returns ``flash_precomputed`` so the
    recompute-forward inside the strategy backward never runs the kernel.
    The gate (s %% 128) is identical in both modes, keeping collect/provide
    counts symmetric.  Mode "name" (the ``checkpoint`` strategy, where the
    attention kind rides each block's ``jax.checkpoint``): a call whose
    queries see at least the channel's ``min_keys`` keys computes (out, lse)
    once, names both (``SAVED_NAMES``; the block's policy saves them) and
    returns ``flash_precomputed`` on them — the block's replay finds both
    outputs of the forward kernel saved and the call is dead code there.

    Block sizes (``call_tiles``; no window): the largest power-of-two
    divisors of the sequence up to 1024 for q and, in the forward, 2048 for
    k (always terminating at 128 given the s % 128 gate).  The tile
    measurements are round 4's, on a v5e at s=16384, d=128 ONLY (in-jit
    loop): 128x128 tiles are grid-overhead/HBM-read bound (round-4 fix,
    27x); with the diagonal-split kernels the forward is VPU-bound on
    softmax bookkeeping, so bigger tiles amortise the per-cell state ops —
    1024x1024 beats 512x512 by 38%, and widening the FORWARD's k tile to
    2048 (fewer online-softmax rescale steps per q row) another 26%; the
    backward keeps 1024x1024 — measured neutral at wider k standalone, and
    the dq kernel exceeds the in-model scoped-VMEM limit there.

    The backward's form (PR 68, PR 73; ``_bwd_flat``, ``backward_form``):
    ONE pass — 5 dots + 1 exp a
    pair — on a grid of the call's live cells (``_live_steps``: prefetched
    tables, so a dead cell costs no step: 0.58 us each on the rectangular
    grid, 120 of a head's 256 at 16,384), dq in VMEM over a q block's k walk
    and a head's whole dk and dv in float32 VMEM for its sweep, where
    ``one_pass_applies`` (the call's keys, widths and tiles against the 100
    MiB it asks for); the same pass with k blocks outermost, dk and dv over a
    k block's q walk and the head's whole dq resident (half the bytes), where
    ``dq_resident_applies`` (width 512 at 16,384, at the 1,024 x 512 tiles
    ``one_pass_tiles`` gives a body that wide: 86 of the same 100 MiB); the
    split dq / dk-dv pair — 7 + 2 — where neither side of a head
    fits (width 512 at 65,536).  Until PR 68 the one pass
    wrote each pair's dq part to a float32 ``[bh, nk, s, d]`` buffer in HBM
    that XLA summed, and calls whose buffer passed 30% of the chip (width
    192 at 16,384: 6.4 GB) ran the pair.  Measured on a v5e, ms a call: bh
    32 x 16,384 x 192 / 128 70.82 (the pair) -> 49.00; 16 x 16,384 x 128
    20.58 (kernel 16.98 + the sum) -> 16.16 (kernel 15.75); 32 x 4,096 x 128
    2.89 -> 2.39 (PERF.md section 6, PR 68; ``scripts/kernel_parity.py
    --only-flash-backward`` takes the forms again); 16 x 16,384 x 512 85.43
    (the pair) -> 59.61 with dq resident at 1,024 x 512 tiles (89.06 at
    1,024 x 1,024: ``_ONE_PASS_BODY_CAP``; PERF.md section 6, PR 73).  Width
    192 costs the MXU what 256 does (whole 128-lane tiles): the call runs at
    ~93% of THAT floor and 74% of the floor its FLOPs alone give.

    What a cell the diagonal crosses scores (PR 55; ``_cell_parts``).  The
    kernels' time follows the pairs they SCORE (the forward runs at 54-56%
    of the MXU peak on them at 4,096, 8,192 and 16,384 alike), and wide
    tiles make a diagonal cell mostly dead: the forward cell whose q tile
    starts where its k tile starts now scores the first 1,024 keys as ONE
    online-softmax step, the other diagonal cell stays whole; a backward
    diagonal cell is three of its four 512 x 512 quadrants.  Scored over
    live pairs (``scored_over_live``; gauge
    ``hbnlp_flash_scored_over_live_pairs``), forward / backward:

        s        every live cell whole     PR 55
        4,096    1.500 / 1.250             1.250 / 1.125
        8,192    1.250 / 1.125             1.125 / 1.0625
        16,384   1.125 / 1.0625            1.0625 / 1.03125

    Why the forward goes no finer, and what a step costs.  Until PR 66 an
    online-softmax step had a FIXED cost beside its columns': measured on a
    v5e (PR 55, taken again by PR 66 on the parent's body; non-causal
    forward, q tile 1,024, k tiles 1,024 against 2,048), at bh 32, s 4,096,
    d 128 a step cost 3.51 us a 1,024 keys + 2.70 us fixed, at bh 16, s
    16,384, d 512 12.01 + 1.85 us.  Walking the shortened cell as three
    quadrants (one step more for its lower rows) made the d 128 forward 2.6%
    SLOWER than scoring every cell whole; cutting the edge cells' rows into
    bands of 512, which adds no step to any row, measured the same as this
    form: the fixed cost did not shrink with a step's rows.  It was the row
    statistics' layout: ``m`` and ``l`` as 1-D ``(rows,)`` scratch turn
    between lanes and sublanes at every use.  Held ``[rows, 128]`` with a
    row's value in every lane (``_softmax_step``, PR 66) the same fit reads
    4.18 us a 1,024 keys - 0.17 at d 128 and 12.23 - 0.09 at d 512: no fixed
    cost is left, and a 1,024 x 2,048 step is 1.34-1.89 us shorter at every
    width (``flash_fwd_causal`` 27.84 -> 24.75 ms a call at bh 32 x 16,384 x
    192 / 128, 5.32 -> 4.53 at 8 x 16,384 x 128, 1.63 -> 1.28 at 32 x 4,096
    x 128, 30.25 -> 28.07 at 16 x 16,384 x 512, 5.83 -> 4.83 at 32 x 8,192 x
    64; ``scripts/kernel_parity.py --only-flash-forward --flash-bisect``
    takes them again).  What a step's statistics still cost is 1.4-1.9 us
    beside ``p = exp(s)`` alone, now by its columns; whether a finer cut of
    the edge cells gains without the fixed cost has not been measured.  At
    head width 512 a third branch does not fit the body
    (``_FORWARD_BODY_CAP``): there the whole-tile diagonal cell shares the
    interior's branch under the position mask."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    on_tpu = jax.default_backend() not in ("cpu",)
    if interpret is None:
        interpret = not on_tpu
    s = q.shape[1]
    if window is not None:
        if not causal or window < 1:
            raise ValueError(f"window {window}: a positive width, under "
                             "causal attention only")
        if window >= s:
            window = None
    blk, fwd_q, fwd_k, _ = call_tiles(s, q.shape[-1], window,
                                      q.dtype.itemsize, v.shape[-1])
    # named-scope regions (docs/OBSERVABILITY.md 'Cost attribution'): which
    # attention implementation actually ran — flash kernel vs the dense XLA
    # fallback — is visible per-op in HLO metadata and profiler traces
    keys = s if window is None else window
    if stash is not None and s % 128 == 0 \
            and keys >= stash.get("min_keys", 0):
        def forward(q, k, v):
            if on_tpu:
                with jax.named_scope("flash_attention"):
                    return _flash_fwd_impl(q, k, v, scale, causal, fwd_q,
                                           fwd_k, interpret, window)
            with jax.named_scope("attention_dense"):
                return _xla_reference_with_lse(q, k, v, scale, causal,
                                               window)

        if stash_naming(stash):
            # the gradient stops on the INPUTS: a pallas_call under
            # differentiation is traced for its JVP before a stop_gradient
            # on its results is seen
            out_s, lse_s = forward(*(jax.lax.stop_gradient(t)
                                     for t in (q, k, v)))
            out_s = checkpoint_name(out_s, SAVED_NAMES[0])
            lse_s = checkpoint_name(lse_s, SAVED_NAMES[1])
        elif stash_collecting(stash):
            out, lse = forward(q, k, v)
            stash_push(stash, (out, lse))
            return out
        else:
            out_s, lse_s = stash_pop(stash)
        with jax.named_scope("flash_attention"):
            return flash_precomputed(q, k, v, out_s, lse_s, scale, causal,
                                     blk, blk, interpret, window)
    if not on_tpu or s % 128 != 0:
        with jax.named_scope("attention_dense"):
            return _xla_reference(q, k, v, scale, causal, window)
    with jax.named_scope("flash_attention"):
        return flash_attention(q, k, v, scale, causal, fwd_q, fwd_k,
                               interpret, bwd_block_q=blk, bwd_block_k=blk,
                               window=window)


# ---- the block-diffusion mask ------------------------------------------------
#
# Block-diffusion training (model/denoise.py; BD3-LM, arXiv:2503.09573) runs
# the body over ``[noised sequence | clean sequence]``, ``2 L`` positions in
# blocks of ``B``, ``b(i) = i // B``: a noised query sees the noised keys of
# its OWN block (both directions) and the clean keys of EARLIER blocks, a
# clean query the clean keys of its own and earlier blocks.  Read a query at
# a time, BOTH halves are the same two parts: the clean keys of the blocks
# BEFORE the query's (the far part: ``L^2 / 2`` pairs a half, the causal
# triangle under a diagonal in steps of ``B``) and the ``B`` keys of its own
# block in its own half (the own part: ``L B`` pairs a half, no mask).  So
# the halves fold into the batch, the far part is the causal kernels on ``L``
# positions with one more compare in the cells the diagonal crosses
# (``_part_mask``'s ``step``; kernels ``flash_*_blockdiff``), the own part a
# ``[L / B, B, B]`` product in XLA, and the two merge by their
# log-sum-exps, as parallel/ring_attention.py merges its hops.  The dead
# three quarters of the ``[2 L, 2 L]`` square are never scored.


def block_diffusion_mask(length: int, block: int):
    """The mask from its definition: ``[2 length, 2 length]`` bool, noised
    half first.  What the forms here are tested against; no program path
    builds it."""
    import numpy as np
    pos = np.arange(2 * length)
    clean, blk = pos >= length, (pos % length) // block
    q_clean, k_clean = clean[:, None], clean[None, :]
    q_blk, k_blk = blk[:, None], blk[None, :]
    return (~q_clean & ~k_clean & (q_blk == k_blk)) \
        | (~q_clean & k_clean & (k_blk < q_blk)) \
        | (q_clean & k_clean & (k_blk <= q_blk))


def stepped_applies(s: int, d: int, block: int, itemsize: int,
                    d_v: typing.Optional[int] = None) -> bool:
    """Whether the far part of a block-diffusion call over ``s`` positions a
    half runs the ``flash_*_blockdiff`` kernels (on a TPU): whole 128-tiles,
    and a sub-square of every tile (``_half_tile``) whole blocks of a power
    of two, so that the stepped diagonal cuts the cells as the plain one
    does.  Pure in its arguments."""
    if s % 128 or block < 1 or block & (block - 1):
        return False
    blk, fwd_q, fwd_k, _ = call_tiles(s, d, None, itemsize, d_v)
    half = min(_half_tile(blk, blk), _half_tile(fwd_q, fwd_k))
    return half >= 2 and half % block == 0


def block_diffusion_live_pairs(length: int, block: int) -> int:
    """The pairs the mask lets through, a head: two block-causal triangles
    (``length (length + block) / 2`` clean-to-clean, ``length (length -
    block) / 2`` noised-to-clean) and the noised blocks' own ``length x
    block``."""
    return length * length + length * block


def block_diffusion_scored_over_live(length: int, d: int, block: int,
                                     itemsize: int
                                     ) -> typing.Dict[str, float]:
    """``scored_over_live`` of a block-diffusion call of ``length`` positions
    a half: the far part's tiled kernels over both halves and the own part's
    ``2 length x block`` pairs, over the mask's live pairs."""
    blk, fwd_q, fwd_k, _ = call_tiles(length, d, None, itemsize)
    live = block_diffusion_live_pairs(length, block)
    own = 2 * length * block
    return {"fwd": (2 * scored_pairs(length, fwd_q, fwd_k, carried=True)
                    + own) / live,
            "bwd": (2 * scored_pairs(length, blk, blk) + own) / live}


def _xla_stepped_with_lse(q, k, v, scale, step: int):
    """The far part's dense form off the TPU (and the kernels' reference):
    ``softmax(scale q k^T) v`` over the keys of the blocks of ``step``
    BEFORE the query's, ``(out [b, s, h, d_v], lse [b * h, s])``,
    differentiable.  A row that sees no key (the first block's) reads a
    finite ``out`` under an ``lse`` of ``_NEG_INF``: it weighs nothing in a
    merge."""
    b, s, h, _ = q.shape
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                        k.astype(jnp.float32))
    blk = jnp.arange(s) // step
    scores = jnp.where((blk[:, None] > blk[None, :])[None, None], scores,
                       _NEG_INF)
    m = scores.max(-1)
    p = jnp.exp(scores - m[..., None])
    l = p.sum(-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p / l[..., None],
                     v.astype(jnp.float32))
    return out.astype(q.dtype), (m + jnp.log(l)).reshape(b * h, s)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def flash_stepped_precomputed(q, k, v, out, lse, scale, step, block_q,
                              block_k, interpret):
    """``flash_precomputed`` for the far part of a block-diffusion call: the
    forward is the PROVIDED ``(out, lse)``, BOTH handed on (the caller merges
    by ``lse``), the backward the ``flash_*_blockdiff`` pass under both
    cotangents."""
    return out, lse


def _flash_stepped_pre_fwd(q, k, v, out, lse, scale, step, block_q, block_k,
                           interpret):
    return (out, lse), (q, k, v, out, lse)


def _flash_stepped_pre_bwd(scale, step, block_q, block_k, interpret, res,
                           cotangents):
    q, k, v, out, lse = res
    dout, dlse = cotangents
    dq, dk, dv = _flash_bwd_pallas(q, k, v, out, lse, dout, scale, True,
                                   block_q, block_k, interpret, step=step,
                                   dlse=dlse)
    return dq, dk, dv, jnp.zeros_like(out), jnp.zeros_like(lse)


flash_stepped_precomputed.defvjp(_flash_stepped_pre_fwd,
                                 _flash_stepped_pre_bwd)


def _own_block(q, k, v, scale, block: int):
    """The own part: every query against the ``block`` keys of its own block
    (both directions, no mask), ``(out [n, s, h, d_v] float32, lse [n, s, h]
    float32)``; ``k``, ``v`` ``[n, s, g, d]`` a K/V head each (``g`` divides
    ``h``, never repeated).  ``block`` keys a query is no matmul's shape: the
    scores are a product summed over the features and the values a product
    summed over the block's keys, float32 — as an einsum XLA pads each ``[B,
    d] x [d, B]`` to the MXU's tiles and lays the operands out again (14 ms a
    layer and pass at the SDAR cell's shape for this form's 2: PERF.md
    section 6, PR 67)."""
    n, s, h, d = q.shape
    g, nb, f32 = k.shape[2], s // block, jnp.float32
    qb = q.reshape(n, nb, block, 1, g, h // g, d).astype(f32)
    kb = k.reshape(n, nb, 1, block, g, 1, d).astype(f32)
    vb = v.reshape(n, nb, 1, block, g, 1, v.shape[-1]).astype(f32)
    scores = jnp.sum(qb * kb, axis=-1) * scale       # [n, nb, B, B, g, r]
    m = scores.max(3, keepdims=True)
    p = jnp.exp(scores - m)
    l = p.sum(3, keepdims=True)
    out = jnp.sum((p / l)[..., None] * vb, axis=3)   # [n, nb, B, g, r, d_v]
    return out.reshape(n, s, h, v.shape[-1]), \
        (m + jnp.log(l)).reshape(n, s, h)


def block_diffusion_attention(q, k, v, k_clean, v_clean, block: int,
                              scale: typing.Optional[float] = None,
                              stash: typing.Optional[dict] = None,
                              kernels: bool = True):
    """Attention under the block-diffusion mask (the section's comment), the
    halves folded into the batch: ``q [n, s, heads, d]`` and its own half's
    ``k``, ``v`` ``[n, s, kv heads, d]`` one half a row of ``n`` (``s`` = the
    trained tokens a sequence; a K/V head each, never repeated), and
    ``k_clean``, ``v_clean`` ``[n, s, heads, d]`` the CLEAN half's keys and
    values of the same sequence for every row, a query head each (the
    kernels are multi-head only) -> ``[n, s, heads, d_v]``.

    The far part's forward runs ONCE, on detached operands — the
    ``flash_fwd_blockdiff`` kernel on a TPU where ``stepped_applies`` —
    and its differentiable value is ``flash_stepped_precomputed`` over it
    (``key_select_attention``'s way); elsewhere, and with ``kernels``
    false, the dense XLA form, itself differentiated.  ``stash``: under ``attention``'s "name" channel the far
    part's ``(out, lse)`` are named (``SAVED_NAMES``), so a block's replay
    runs no forward kernel, only the own part and the merge."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    n, s, h, d = q.shape
    if kernels and jax.default_backend() != "cpu" and stepped_applies(
            s, d, block, q.dtype.itemsize, v.shape[-1]):
        blk, fwd_q, fwd_k, _ = call_tiles(s, d, None, q.dtype.itemsize,
                                          v.shape[-1])
        with jax.named_scope("flash_attention"):
            out_s, lse_s = _flash_fwd_impl(
                *(jax.lax.stop_gradient(t) for t in (q, k_clean, v_clean)),
                scale, True, fwd_q, fwd_k, False, step=block)
        if stash_naming(stash) and s >= stash.get("min_keys", 0):
            out_s = checkpoint_name(out_s, SAVED_NAMES[0])
            lse_s = checkpoint_name(lse_s, SAVED_NAMES[1])
        with jax.named_scope("flash_attention"):
            far, far_lse = flash_stepped_precomputed(
                q, k_clean, v_clean, out_s, lse_s, scale, block, blk, blk,
                False)
    else:
        with jax.named_scope("attention_dense"):
            far, far_lse = _xla_stepped_with_lse(q, k_clean, v_clean, scale,
                                                 block)
    with jax.named_scope("own_block"):
        own, own_lse = _own_block(q, k, v, scale, block)
    with jax.named_scope("lse_merge"):
        far_lse = far_lse.reshape(n, h, s).transpose(0, 2, 1)
        top = jnp.maximum(far_lse, own_lse)
        w_far, w_own = jnp.exp(far_lse - top), jnp.exp(own_lse - top)
        total = w_far + w_own
        return ((far.astype(jnp.float32) * (w_far / total)[..., None]
                 + own * (w_own / total)[..., None])).astype(q.dtype)



# ---- block-selected attention ------------------------------------------------
#
# A query row keeps whole BLOCKS of ``block`` keys, the same for the ``group``
# query heads of its K/V head and another set for the next row (model/sparse.py
# makes the choice; nothing here scores anything): ``keep [b, kv heads, s, s /
# block]`` bool.  The kernels are the tiled flash-2 ones with two more
# operands: the rows' choice, a ``[tile, 128]`` window of it a cell, widened to
# the cell's ``[tile, tile]`` pairs by one matmul with a 0/1 matrix made of
# iotas (a lane-dimension repeat that Mosaic lowers everywhere); and a table
# of the tiles some row of a q tile kept, prefetched to SMEM, through which
# the index maps and the cells skip every K/V tile no row chose — its fetch
# as well as its matmuls.  K and V come a K/V HEAD each (index ``head //
# group``), never repeated; the dk/dv pass writes a query head's part, the
# caller sums a group's.
#
# A second form keeps single KEYS (``block`` 1; model/indexer.py makes that
# choice, one for ALL the query heads of a layer): ``keep [b, 1, s / 32, s]``
# int32, bit ``r`` of word ``[i, u]`` saying whether query ``32 i + r`` kept
# key ``u`` — a bit a pair.  A cell reads its ``[tile / 32, tile]`` window of
# the words directly and shifts each row of words out over its 32 queries (no
# widening matmul); the tables of live tiles are made from the same words.

#: the name of a sparse layer's choice, beside ``SAVED_NAMES``: saved with
#: ``(out, lse)`` wherever those are, so that a replay chooses nothing
SELECT_NAME = "sparse_keep"
#: the block form's tile, q and k alike (the tables' diagonal is then always
#: live: a row keeps its own block); the key-at-a-time form's is twice that
#: (``select_tile``)
_SELECT_TILE = 512
#: lanes of the window of the rows' choice a cell reads
_KEEP_LANES = 128

#: queries a word of the key-at-a-time choice holds a bit each
KEEP_WORD = 32


def select_tile(s: int, block: int) -> typing.Tuple[int, int]:
    """``(q tile, k tile)`` of the three selected kernels: ``kernel_block``
    under ``_SELECT_TILE``, in whole blocks — the block form's tables skip
    the tiles no row kept, and a wider tile skips fewer.  ``block`` 1, the
    key-at-a-time form: whole words of ``KEEP_WORD`` queries, and twice the
    tile both ways — scattered keys empty no tile of either size, and at
    1,024 x 1,024 a call runs a quarter of the cells, grid steps and rescales
    (PR 63, on the chip at 32 / 4 heads x 16,384 x 128: forward 20.2 -> 17.2
    ms, dq 26.2 -> 23.0, dk/dv 37.0 -> 28.5; 512 x 1,024 and 1,024 x 2,048
    lie between).  The kernels, tables and specs take any pair."""
    if block == 1:
        tile = kernel_block(s, cap=2 * _SELECT_TILE)
        if tile % KEEP_WORD:
            raise ValueError(
                f"the key-at-a-time selected kernels' tile of {tile} queries "
                f"holds no whole words of {KEEP_WORD}")
        return tile, tile
    tile = kernel_block(s, cap=_SELECT_TILE)
    if tile % block or _KEEP_LANES % (tile // block):
        raise ValueError(
            f"a selected kernel's tile of {tile} keys holds no whole "
            f"power-of-two number of blocks of {block}: the forms are blocks "
            f"of keys (a power of two of them a tile, at most {_KEEP_LANES}) "
            "and single keys (block 1, the choice as bits)")
    return tile, tile


def pack_keep(keep):
    """``keep [.., n, s]`` bool -> ``[.., n / KEEP_WORD, s]`` int32: bit ``r``
    of word ``[i, u]`` is ``keep[KEEP_WORD i + r, u]``."""
    *lead, n, s = keep.shape
    bits = keep.reshape(*lead, n // KEEP_WORD, KEEP_WORD, s).astype(
        jnp.uint32) << jnp.arange(KEEP_WORD, dtype=jnp.uint32)[:, None]
    return jax.lax.bitcast_convert_type(jnp.sum(bits, axis=-2,
                                                dtype=jnp.uint32), jnp.int32)


def unpack_keep(words):
    """``pack_keep``'s inverse: ``[.., n / KEEP_WORD, s]`` int32 -> ``[.., n,
    s]`` bool."""
    *lead, rows, s = words.shape
    bits = (words[..., :, None, :] >> jnp.arange(
        KEEP_WORD, dtype=jnp.int32)[:, None]) & 1
    return bits.reshape(*lead, rows * KEEP_WORD, s) != 0


def _keep_mask(keep, block: int, s: int):
    """``keep [b, g, s, s / block]`` (``block`` 1: the packed words) as
    pairs ``[b, g, s, s]``, causal."""
    pairs = unpack_keep(keep) if block == 1 \
        else jnp.repeat(keep, block, axis=-1)[..., :s]
    return pairs & (jnp.arange(s)[:, None] >= jnp.arange(s)[None, :])


def _xla_select_with_lse(q, k, v, keep, scale, block):
    """The dense masked form off the TPU (and the kernels' reference): ``q
    [b, s, h, d]``, ``k`` / ``v`` ``[b, s, g, d]`` -> ``(out [b, s, h, d],
    lse [b * h, s])``, the softmax over exactly the kept keys ``<= t``."""
    b, s, h, d = q.shape
    g = k.shape[2]
    qg = q.reshape(b, s, g, h // g, d).astype(jnp.float32) * scale
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k.astype(jnp.float32))
    mask = _keep_mask(keep, block, s)[:, :, None]
    scores = jnp.where(mask, scores, _NEG_INF)
    m = scores.max(-1)
    p = jnp.where(mask, jnp.exp(scores - m[..., None]), 0.0)
    l = jnp.maximum(p.sum(-1), 1e-30)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", p / l[..., None],
                     v.astype(jnp.float32)).reshape(b, s, h, d)
    return out.astype(q.dtype), (m + jnp.log(l)).reshape(b * h, s)


def _select_tables(keep, tq: int, tk: int, block: int):
    """``(keep_rows [b * g, s, lanes] in bfloat16, fetch_k, fetch_q)`` of a
    choice ``keep [b, g, s, nb]`` at q tiles of ``tq`` and k tiles of ``tk``.
    ``fetch_k [b * g * nq * nk]`` int32: for q tile ``j`` and step ``kk`` the
    k tile to hold — ``kk`` itself where some row of the q tile kept a block
    of it (and not all of it is above the diagonal), else the last such tile
    before it (the first one, before any): a repeated index fetches nothing.
    ``fetch_q`` the same for the k-outer grid, ``[.., nk, nq]``.  ``block``
    1: ``keep`` is the packed words ``[b, g, s / KEEP_WORD, s]`` and goes to
    the kernels as it is."""
    b, g = keep.shape[:2]
    if block == 1:
        s = keep.shape[3]
        nq, nk = s // tq, s // tk
        live = (keep.reshape(b * g, nq, tq // KEEP_WORD, nk, tk)
                != 0).any(axis=(2, 4))
    else:
        s, nb = keep.shape[2:]
        nq, nk = s // tq, s // tk
        live = keep.reshape(b * g, nq, tq, nk, tk // block).any(axis=(2, 4))
    # a k tile whose first key is past the q tile's last row is dead whatever
    # the choice says
    live &= (jnp.arange(nq) * tq + tq - 1)[:, None] \
        >= (jnp.arange(nk) * tk)[None, :]

    def fetch(alive):
        idx = jnp.arange(alive.shape[2], dtype=jnp.int32)
        last = jax.lax.cummax(jnp.where(alive, idx, -1), axis=2)
        first = jnp.argmax(alive, axis=2).astype(jnp.int32)[..., None]
        return jnp.where(last >= 0, last, first).reshape(-1)

    if block == 1:
        return keep.reshape(b * g, s // KEEP_WORD, s), fetch(live), \
            fetch(jnp.swapaxes(live, 1, 2))
    lanes = -(-nb // _KEEP_LANES) * _KEEP_LANES
    rows = jnp.pad(keep.reshape(b * g, s, nb).astype(jnp.bfloat16),
                   ((0, 0), (0, 0), (0, lanes - nb)))
    return rows, fetch(live), fetch(jnp.swapaxes(live, 1, 2))


def _select_kept(keep_ref, ki, tq: int, tk: int, block: int):
    """The pairs ``[tq, tk]`` of a cell on k tile ``ki`` a row kept: the
    window of the rows' choice times the 0/1 matrix that repeats a block's
    lane over its keys.  ``block`` 1: the cell's ``[tq / KEEP_WORD, tk]``
    words, each row of them shifted out over its ``KEEP_WORD`` queries."""
    if block == 1:
        bit = jax.lax.broadcasted_iota(jnp.int32, (KEEP_WORD, tk), 0)
        words = keep_ref[...]
        return jnp.concatenate([
            (jnp.broadcast_to(words[r:r + 1], (KEEP_WORD, tk)) >> bit) & 1
            for r in range(tq // KEEP_WORD)], axis=0) != 0
    return _widened(keep_ref, ki, tk, block)


def _select_seen(keep_ref, qi, ki, tq: int, tk: int, block: int):
    """``_select_kept`` and the diagonal: the pairs of q tile ``qi`` and k
    tile ``ki`` a row kept and may see."""
    kept = _select_kept(keep_ref, ki, tq, tk, block)
    q_pos = qi * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
    k_pos = ki * tk + jax.lax.broadcasted_iota(jnp.int32, (1, tk), 1)
    return kept & (q_pos >= k_pos)


def _widened(keep_ref, ki, tile: int, block: int):
    """The block form's ``[rows, tile]`` pairs a row kept: its window of the
    rows' choice, a block's lane repeated over its keys."""
    per = tile // block
    first = jax.lax.rem(ki * per, _KEEP_LANES)
    lane = jax.lax.broadcasted_iota(jnp.int32, (_KEEP_LANES, tile), 0)
    key = jax.lax.broadcasted_iota(jnp.int32, (_KEEP_LANES, tile), 1)
    widen = (lane == first + jax.lax.div(key, block)).astype(keep_ref.dtype)
    return jax.lax.dot_general(keep_ref[...], widen, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32) > 0.5


def _select_live(fetch_ref, i, outer, step, num_outer: int, num_inner: int,
                 keep_group: int):
    """The table's entry of grid step ``(i, outer, step)``: the inner tile to
    hold — ``step`` itself where the cell is live."""
    return fetch_ref[((i // keep_group) * num_outer + outer) * num_inner
                     + step]


def _select_fwd_kernel(fetch_ref, q_ref, k_ref, v_ref, keep_ref, o_ref,
                       lse_ref, m_ref, l_ref, acc_ref, *, tq: int, tk: int,
                       block: int, num_q: int, num_k: int, keep_group: int,
                       scale: float):
    """Grid (batch * heads, q tiles, k tiles), k innermost: ``_flash_kernel``
    over the tiles some row of the q tile kept, each pair under the rows'
    own mask, the online softmax the causal forward's (``_softmax_step``).
    A masked score is ``-inf`` under a FINITE first maximum, so a row that
    has kept nothing yet reads ``p = 0`` with no second select.  Every
    cell compares positions: a second body for the cells the diagonal does
    not cross is 0.3% of the Keye step and 3 s of every set-up (PR 63)."""
    from jax.experimental import pallas as pl

    i, qi, kk = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        _softmax_init(m_ref, l_ref, acc_ref)

    @pl.when(_select_live(fetch_ref, i, qi, kk, num_q, num_k, keep_group)
             == kk)
    def _step():
        seen = _select_seen(keep_ref, qi, kk, tq, tk, block)
        s = jnp.where(seen, _make_score(q_ref, k_ref, scale)(), -jnp.inf)
        _softmax_step(s, v_ref, m_ref, l_ref, acc_ref)

    @pl.when(kk == num_k - 1)
    def _finish():
        _softmax_finish(o_ref, lse_ref, m_ref, l_ref, acc_ref)


def _select_pair(seen, q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, scale):
    """``(p, ds)`` of one cell of the backward, as the causal kernels form
    them, under the rows' mask."""
    s = jnp.where(seen, _make_score(q_ref, k_ref, scale)(), _NEG_INF)
    p = jnp.exp(s - lse_ref[...])
    dp = jax.lax.dot_general(do_ref[...], v_ref[...],
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return p, (p * (dp - d_ref[...]) * scale).astype(q_ref.dtype)


def _select_dq_kernel(fetch_ref, q_ref, k_ref, v_ref, keep_ref, do_ref,
                      lse_ref, d_ref, dq_ref, acc_ref, *, tq: int, tk: int,
                      block: int, num_q: int, num_k: int, keep_group: int,
                      scale: float):
    """dq: the forward's grid and table."""
    from jax.experimental import pallas as pl

    i, qi, kk = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_select_live(fetch_ref, i, qi, kk, num_q, num_k, keep_group)
             == kk)
    def _step():
        _, ds = _select_pair(_select_seen(keep_ref, qi, kk, tq, tk, block),
                             q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref,
                             scale)
        acc_ref[...] += jax.lax.dot_general(
            ds, k_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kk == num_k - 1)
    def _finish():
        dq_ref[...] = acc_ref[...].astype(dq_ref.dtype)


def _select_dkv_kernel(fetch_ref, q_ref, k_ref, v_ref, keep_ref, do_ref,
                       lse_ref, d_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                       tq: int, tk: int, block: int, num_q: int, num_k: int,
                       keep_group: int, scale: float):
    """dk/dv of ONE query head: grid (batch * heads, k tiles, q tiles), q
    innermost, over the q tiles a row of which kept a block of the k tile."""
    from jax.experimental import pallas as pl

    i, ki, jj = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(jj == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_select_live(fetch_ref, i, ki, jj, num_k, num_q, keep_group)
             == jj)
    def _step():
        p, ds = _select_pair(_select_seen(keep_ref, jj, ki, tq, tk, block),
                             q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref,
                             scale)
        dk_acc[...] += jax.lax.dot_general(
            ds, q_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jj == num_q - 1)
    def _finish():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _flat(x):
    """``[b, s, heads, d]`` -> ``[b * heads, s, d]``."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _select_call(kernel, name, grid, in_specs, out_specs, out_shape, scratch,
                 interpret, operands):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_KERNEL_VMEM_BUDGET),
        name=name, interpret=interpret)(*operands)


def _select_specs(tq: int, tk: int, block: int, d: int, num_q: int,
                  num_k: int, group: int, k_outer: bool, keep_group: int):
    """``(q-side spec of width w, k-side spec, the rows' choice's spec)`` of
    a selected grid: the OUTER tile by its index, the inner one through the
    table.  ``k_outer``: grid (i, k tile, q step), else (i, q tile, k
    step).  ``keep_group``: the query heads that share one choice (the
    block form: a K/V head's ``group``); ``block`` 1 reads the words' ``[tq
    / KEEP_WORD, tk]`` window of the cell itself."""
    from jax.experimental import pallas as pl
    per = tk // block
    nums = (num_k, num_q) if k_outer else (num_q, num_k)

    def inner(i, outer, step, fetch_ref):
        return _select_live(fetch_ref, i, outer, step, *nums, keep_group)

    if block == 1:
        def key_map(i, outer, step, fetch_ref):
            q, k = (inner(i, outer, step, fetch_ref), outer) if k_outer \
                else (outer, inner(i, outer, step, fetch_ref))
            return (i // keep_group, q, k)
        keep_spec = pl.BlockSpec((None, tq // KEEP_WORD, tk), key_map)
    else:
        keep_spec = None

    if k_outer:
        def q_map(i, ki, jj, fetch_ref):
            return (i, inner(i, ki, jj, fetch_ref), 0)

        def k_map(i, ki, jj, fetch_ref):
            return (i // group, ki, 0)

        def keep_map(i, ki, jj, fetch_ref):
            return (i // group, inner(i, ki, jj, fetch_ref),
                    (ki * per) // _KEEP_LANES)
    else:
        def q_map(i, qi, kk, fetch_ref):
            return (i, qi, 0)

        def k_map(i, qi, kk, fetch_ref):
            return (i // group, inner(i, qi, kk, fetch_ref), 0)

        def keep_map(i, qi, kk, fetch_ref):
            return (i // group, qi,
                    (inner(i, qi, kk, fetch_ref) * per) // _KEEP_LANES)

    return (lambda w: pl.BlockSpec((None, tq, w), q_map),
            pl.BlockSpec((None, tk, d), k_map),
            keep_spec or pl.BlockSpec((None, tq, _KEEP_LANES), keep_map))


def _select_fwd_impl(q, k, v, keep, scale, block, interpret):
    """``(out [b, s, h, d], lse [b * h, s])`` of the selected forward."""
    b, s, h, d = q.shape
    group = h // k.shape[2]
    keep_group = h // keep.shape[1]
    tq, tk = select_tile(s, block)
    num_q, num_k = s // tq, s // tk
    rows, fetch_k, _ = _select_tables(keep, tq, tk, block)
    q_spec, k_spec, keep_spec = _select_specs(tq, tk, block, d, num_q, num_k,
                                              group, False, keep_group)
    out, lse = _select_call(
        functools.partial(_select_fwd_kernel, tq=tq, tk=tk, block=block,
                          num_q=num_q, num_k=num_k, keep_group=keep_group,
                          scale=scale),
        "flash_fwd_select", (b * h, num_q, num_k),
        [q_spec(d), k_spec, k_spec, keep_spec], [q_spec(d), q_spec(1)],
        [jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
         jax.ShapeDtypeStruct((b * h, s, 1), jnp.float32)],
        _stat_scratch(tq, d), interpret,
        (fetch_k, _flat(q), _flat(k), _flat(v), rows))
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3), lse[..., 0]


def _select_bwd_impl(q, k, v, keep, out, lse, dout, scale, block, interpret):
    """``(dq, dk, dv)`` of the selected attention: a dq pass on the
    forward's grid and a dk/dv pass on the k-outer one (no fused form: its
    dq partials are a slot a k tile, 2 GB at 16 heads of 16,384 x 128), each
    over the kept tiles only; dk and dv summed over a K/V head's group."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b, s, h, d = q.shape
    g = k.shape[2]
    group = h // g
    keep_group = h // keep.shape[1]
    tq, tk = select_tile(s, block)
    num_q, num_k = s // tq, s // tk
    rows, fetch_k, fetch_q = _select_tables(keep, tq, tk, block)
    qt, kt, vt, dot = _flat(q), _flat(k), _flat(v), _flat(dout)
    delta = jnp.sum(dot.astype(jnp.float32) * _flat(out).astype(jnp.float32),
                    -1, keepdims=True)
    lse3 = lse[..., None]
    sizes = dict(tq=tq, tk=tk, block=block, num_q=num_q, num_k=num_k,
                 keep_group=keep_group, scale=scale)
    q_spec, k_spec, keep_spec = _select_specs(tq, tk, block, d, num_q, num_k,
                                              group, False, keep_group)
    dq = _select_call(
        functools.partial(_select_dq_kernel, **sizes),
        "flash_bwd_dq_select", (b * h, num_q, num_k),
        [q_spec(d), k_spec, k_spec, keep_spec, q_spec(d), q_spec(1),
         q_spec(1)], q_spec(d),
        jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        [pltpu.VMEM((tq, d), jnp.float32)],
        interpret, (fetch_k, qt, kt, vt, rows, dot, lse3, delta))
    q_spec, k_spec, keep_spec = _select_specs(tq, tk, block, d, num_q, num_k,
                                              group, True, keep_group)
    own = pl.BlockSpec((None, tk, d), lambda i, ki, jj, fetch_ref:
                       (i, ki, 0))
    dk, dv = _select_call(
        functools.partial(_select_dkv_kernel, **sizes),
        "flash_bwd_dkv_select", (b * h, num_k, num_q),
        [q_spec(d), k_spec, k_spec, keep_spec, q_spec(d), q_spec(1),
         q_spec(1)], [own, own],
        [jax.ShapeDtypeStruct((b * h, s, d), jnp.float32),
         jax.ShapeDtypeStruct((b * h, s, d), jnp.float32)],
        [pltpu.VMEM((tk, d), jnp.float32),
         pltpu.VMEM((tk, d), jnp.float32)],
        interpret, (fetch_q, qt, kt, vt, rows, dot, lse3, delta))

    def heads(x, n, dtype):
        return x.reshape(b, n, s, d).transpose(0, 2, 1, 3).astype(dtype)

    def grouped(x, dtype):
        return heads(x.reshape(b * g, group, s, d).sum(axis=1), g, dtype)

    return heads(dq, h, q.dtype), grouped(dk, k.dtype), grouped(dv, v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def flash_select(q, k, v, keep, scale, block, interpret):
    """Block-selected causal attention: ``q [b, s, h, d]``, ``k`` / ``v``
    ``[b, s, g, d]`` (``g`` divides ``h``), ``keep [b, g, s, s / block]``
    bool -> ``[b, s, h, d]``.  No gradient reaches ``keep``."""
    return _select_fwd_impl(q, k, v, keep, scale, block, interpret)[0]


def _flash_select_fwd(q, k, v, keep, scale, block, interpret):
    out, lse = _select_fwd_impl(q, k, v, keep, scale, block, interpret)
    return out, (q, k, v, keep, out, lse)


def _flash_select_bwd(scale, block, interpret, res, dout):
    q, k, v, keep, out, lse = res
    return _select_bwd_impl(q, k, v, keep, out, lse, dout, scale, block,
                            interpret) + (None,)


flash_select.defvjp(_flash_select_fwd, _flash_select_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def flash_select_precomputed(q, k, v, keep, out, lse, scale, block,
                             interpret):
    """``flash_precomputed`` for a selected call: the forward is the PROVIDED
    ``(out, lse)``, the backward the selected pass under the provided
    ``keep``."""
    return out


def _flash_select_pre_fwd(q, k, v, keep, out, lse, scale, block, interpret):
    return out, (q, k, v, keep, out, lse)


def _flash_select_pre_bwd(scale, block, interpret, res, dout):
    q, k, v, keep, out, lse = res
    return _select_bwd_impl(q, k, v, keep, out, lse, dout, scale, block,
                            interpret) \
        + (None, jnp.zeros_like(out), jnp.zeros_like(lse))


flash_select_precomputed.defvjp(_flash_select_pre_fwd, _flash_select_pre_bwd)


def _xla_select(q, k, v, keep, scale, block):
    return _xla_select_with_lse(q, k, v, keep, scale, block)[0]


def select_attention(q, k, v, keep, block: int,
                     scale: typing.Optional[float] = None,
                     interpret: typing.Optional[bool] = None,
                     stash: typing.Optional[dict] = None):
    """Dispatch of a block-selected call, as ``attention`` is of a causal
    one: the ``flash_*_select`` kernels on a TPU at a sequence of whole
    128-tiles, the dense masked XLA form elsewhere.  ``keep`` is a constant
    of the call (the caller stops its gradient).  ``stash``: the channel of
    ``attention`` — "name" names ``(out, lse)`` (``SAVED_NAMES``; the caller
    names ``keep``, ``SELECT_NAME``) and returns the precomputed form, so a
    block's replay runs no forward kernel and reads the saved choice.  The
    revnet / momentum channel's "collect" / "provide" are not taken: there
    the replay runs the plain call (and chooses again)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    on_tpu = jax.default_backend() not in ("cpu",)
    if interpret is None:
        interpret = not on_tpu
    s = q.shape[1]
    kernels = on_tpu and s % 128 == 0

    def forward(q, k, v, keep):
        if kernels:
            with jax.named_scope("flash_attention"):
                return _select_fwd_impl(q, k, v, keep, scale, block,
                                        interpret)
        with jax.named_scope("attention_dense"):
            return _xla_select_with_lse(q, k, v, keep, scale, block)

    def backward_of(q, k, v, keep, out_s, lse_s):
        if kernels:
            with jax.named_scope("flash_attention"):
                return flash_select_precomputed(q, k, v, keep, out_s, lse_s,
                                                scale, block, interpret)
        # off the TPU the dense form is its own backward: the saved pair is
        # the value, the gradient flows through the recomputed one
        with jax.named_scope("attention_dense"):
            live = _xla_select(q, k, v, keep, scale, block)
        return live + jax.lax.stop_gradient(out_s - live)

    if stash_naming(stash) and s % 128 == 0 \
            and s >= stash.get("min_keys", 0):
        out_s, lse_s = forward(*(jax.lax.stop_gradient(t)
                                 for t in (q, k, v)), keep)
        out_s = checkpoint_name(out_s, SAVED_NAMES[0])
        lse_s = checkpoint_name(lse_s, SAVED_NAMES[1])
        return backward_of(q, k, v, keep, out_s, lse_s)
    if not kernels:
        with jax.named_scope("attention_dense"):
            return _xla_select(q, k, v, keep, scale, block)
    with jax.named_scope("flash_attention"):
        return flash_select(q, k, v, keep, scale, block, interpret)


def key_select_attention(q, k, v, keep, scale: float,
                         stash: typing.Optional[dict] = None):
    """Dispatch of a key-at-a-time selected call (the module comment's second
    form): ``keep [b, 1, s / KEEP_WORD, s]`` int32, one choice for all of
    ``q``'s heads -> ``(out [b, s, h, d], lse [b * h, s])``, ``lse`` without a
    gradient (the caller's index loss reads it).  The forward runs ONCE, on
    detached operands, and the differentiable value is the precomputed form
    over it — the ``flash_*_select`` kernels at ``block`` 1 on a TPU at a
    sequence of whole 256-tiles, the dense masked XLA form elsewhere.
    ``stash``: under ``attention``'s "name" channel ``(out, lse)`` are named
    (``SAVED_NAMES``; the caller names ``keep``), so a block's replay runs no
    forward kernel."""
    s = q.shape[1]
    kernels = jax.default_backend() != "cpu" and s % 256 == 0
    detached = tuple(jax.lax.stop_gradient(t) for t in (q, k, v))
    if kernels:
        with jax.named_scope("flash_attention"):
            out_s, lse_s = _select_fwd_impl(*detached, keep, scale, 1, False)
    else:
        with jax.named_scope("attention_dense"):
            out_s, lse_s = _xla_select_with_lse(*detached, keep, scale, 1)
    if stash_naming(stash) and s >= stash.get("min_keys", 0):
        out_s = checkpoint_name(out_s, SAVED_NAMES[0])
        lse_s = checkpoint_name(lse_s, SAVED_NAMES[1])
    if kernels:
        with jax.named_scope("flash_attention"):
            return flash_select_precomputed(q, k, v, keep, out_s, lse_s,
                                            scale, 1, False), lse_s
    # off the TPU the dense form is its own backward: the saved pair is the
    # value, the gradient flows through the recomputed one
    with jax.named_scope("attention_dense"):
        live = _xla_select(q, k, v, keep, scale, 1)
    return live + jax.lax.stop_gradient(out_s - live), lse_s
