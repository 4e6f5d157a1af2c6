"""Ring attention: causal flash attention over a sequence-sharded mesh axis.

Long-context sequence parallelism the reference lacks (SURVEY.md §5.7): the
sequence dim is sharded over the ``sequence`` mesh axis; key/value blocks
rotate around the ring with ``lax.ppermute`` over ICI while each device
accumulates its queries' output with an online (streaming) softmax, so the
full [seq, seq] score matrix never materialises and per-device memory is
O(seq/P · d + blockwise scratch).  Communication overlaps compute: XLA
schedules the ppermute of step j+1 against the matmuls of step j.

Training memory is O(seq/P · d) too: ``_ring_core`` is a ``custom_vjp``
whose forward saves only (q, k, v, out, lse) — the flash-attention residual
set — instead of letting autodiff store the per-hop [sq, sq] probability
tensors for all P hops (O(seq²/P) per layer, which made the 32k
sequence-parallel target untrainable).  The backward runs the ring again:
(k, v, dk, dv) rotate together, each hop recomputes its probability block
from the saved log-sum-exp CHUNKED over query rows (a lax.scan, transient
O(block_q · sq) like parallel/flash_attention.py's chunked backward), adds
the visiting block's dk/dv contribution, and after P hops every (dk, dv)
block has completed the full ring and is back on its home device.

Causality across shards: after j rotation steps the local device q-shard
``i`` holds the k/v block originally from shard ``(i - j) mod P``; blocks
from a strictly earlier shard attend fully, the diagonal block uses the
triangular mask, later blocks contribute nothing (their scores are masked
to -1e30, keeping every device in lock-step for the collective).

Zigzag layout (the causal default): CONTIGUOUS sequence sharding wastes
half the causal FLOPs and is load-imbalanced — shard 0's queries have
almost no real work, shard P-1's have all of it, every hop runs the full
matmul and masks afterwards, and the collective keeps everyone in lock-step
with the slowest.  The causal path therefore re-shards into zigzag form:
the sequence splits into 2P chunks and device d holds chunks ``(d,
2P-1-d)`` — one early, one late — reached by TWO half-shard ppermutes
(cost of a single ring hop, inverted on the output).  Then at every hop
j>0 each device computes exactly two fully-LIVE chunk pairs — q_late x
k_early (always causal: late chunk index >= P > any early index) plus
exactly one of q_early x k_early (device d >= j) or q_late x k_late
(d < j) — no masking, no dead work, identical cost on every device.  Hop
j=0 runs the two triangular diagonal pairs (batched into one matmul) plus
q_late x k_early.  Useful-FLOP fraction goes from ~50% to ~100% of what is
computed, halving attention cost at the same balance.

On TPU the zigzag hop pairs run the pallas flash kernels
(parallel/flash_attention.py flat cores) rather than the XLA chunk scans:
the forward merges each pair's normalized (out, lse) by log-sum-exp
arithmetic, the backward feeds the GLOBAL lse/delta so per-hop pieces
accumulate exactly, and k/v rotate in the raw (bf16) dtype — half the ICI
bytes.  ``use_pallas=False`` keeps the scan path (the CPU's default, and
the path of chunks that 128 does not divide).
"""
from __future__ import annotations

import functools
import typing

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from ..core.stash import stash_collecting, stash_pop, stash_push

_NEG_INF = -1e30


def _pick_block(sq: int, want: int) -> int:
    """Largest divisor of sq that is <= want."""
    bq = min(want, sq)
    while sq % bq:
        bq -= 1
    return bq


def _chunk(x, nc):
    """[b, h, sq, ...] -> [nc, b, h, bq, ...] (scan leading axis)."""
    b, h, sq = x.shape[:3]
    return jnp.moveaxis(x.reshape(b, h, nc, sq // nc, *x.shape[3:]), 2, 0)


def _unchunk(x):
    """[nc, b, h, bq, ...] -> [b, h, sq, ...]."""
    nc, b, h, bq = x.shape[:4]
    return jnp.moveaxis(x, 0, 2).reshape(b, h, nc * bq, *x.shape[4:])


def _hop_fwd(qh, k_blk, v_blk, m, l, acc, qpos, kpos, causal, nc):
    """One ring hop of the forward online softmax, scanned over q chunks so
    the transient probability block is [b, h, bq, sk], never [sq, sk]."""

    def chunk_step(_, xs):
        qc, mc, lc, accc, qposc = xs
        s = jnp.einsum("bhqd,bhkd->bhqk", qc, k_blk,
                       preferred_element_type=jnp.float32)
        if causal:
            s = jnp.where(qposc[None, None, :, None] >= kpos[None, None, None, :],
                          s, _NEG_INF)
        m_new = jnp.maximum(mc, s.max(-1))
        alpha = jnp.exp(mc - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = lc * alpha + p.sum(-1)
        acc_new = accc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_blk, preferred_element_type=jnp.float32)
        return None, (m_new, l_new, acc_new)

    bq = qh.shape[2] // nc
    xs = (_chunk(qh, nc), _chunk(m, nc), _chunk(l, nc), _chunk(acc, nc),
          qpos.reshape(nc, bq))
    _, (m2, l2, acc2) = jax.lax.scan(chunk_step, None, xs)
    return _unchunk(m2), _unchunk(l2), _unchunk(acc2)


def _ring_forward(axis_name, n_shards, causal, scale, block_q, q, k, v):
    """Per-shard forward; returns (out [b, sq, h, d], lse [b, h, sq])."""
    my_idx = jax.lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    nc = sq // _pick_block(sq, block_q)
    qh = q.transpose(0, 2, 1, 3).astype(jnp.float32) * scale
    k_blk = k.transpose(0, 2, 1, 3).astype(jnp.float32)
    v_blk = v.transpose(0, 2, 1, 3).astype(jnp.float32)
    m = jnp.full((b, h, sq), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, sq), jnp.float32)
    acc = jnp.zeros((b, h, sq, d), jnp.float32)
    qpos = my_idx * sq + jnp.arange(sq)
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    for j in range(n_shards):  # static unroll: n_shards is small; lets XLA
        # overlap the ppermute with the next hop's matmuls
        src_shard = (my_idx - j) % n_shards
        kpos = src_shard * sq + jnp.arange(sq)
        m, l, acc = _hop_fwd(qh, k_blk, v_blk, m, l, acc, qpos, kpos,
                             causal, nc)
        if j + 1 < n_shards:
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)

    l_safe = jnp.maximum(l, 1e-30)
    out = (acc / l_safe[..., None]).transpose(0, 2, 1, 3).astype(q.dtype)
    lse = m + jnp.log(l_safe)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _ring_core(axis_name, n_shards, causal, scale, block_q, q, k, v):
    out, _ = _ring_forward(axis_name, n_shards, causal, scale, block_q,
                           q, k, v)
    return out


def _ring_fwd_rule(axis_name, n_shards, causal, scale, block_q, q, k, v):
    out, lse = _ring_forward(axis_name, n_shards, causal, scale, block_q,
                             q, k, v)
    return out, (q, k, v, out, lse)


def _ring_bwd_rule(axis_name, n_shards, causal, scale, block_q, res, dout):
    """Memory-efficient backward: rotate (k, v, dk, dv) around the ring,
    recomputing each hop's probabilities from the saved log-sum-exp chunked
    over query rows.  Residuals are O(sq·d); transients O(bq·sq)."""
    q, k, v, out, lse = res
    b, sq, h, d = q.shape
    nc = sq // _pick_block(sq, block_q)
    bq = sq // nc
    my_idx = jax.lax.axis_index(axis_name)
    f32 = jnp.float32
    qh = q.transpose(0, 2, 1, 3).astype(f32) * scale      # pre-scaled
    k_blk = k.transpose(0, 2, 1, 3).astype(f32)
    v_blk = v.transpose(0, 2, 1, 3).astype(f32)
    do = dout.transpose(0, 2, 1, 3).astype(f32)
    ot = out.transpose(0, 2, 1, 3).astype(f32)
    delta = jnp.sum(do * ot, -1)                          # [b, h, sq]
    dq = jnp.zeros((b, h, sq, d), f32)
    dk_blk = jnp.zeros((b, h, sq, d), f32)
    dv_blk = jnp.zeros((b, h, sq, d), f32)
    qpos = my_idx * sq + jnp.arange(sq)
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    def hop(k_blk, v_blk, dk_blk, dv_blk, dq, kpos):
        def chunk_step(carry, xs):
            dk, dv = carry
            qc, doc, dc, lsec, qposc = xs
            s = jnp.einsum("bhqd,bhkd->bhqk", qc, k_blk,
                           preferred_element_type=f32)
            if causal:
                s = jnp.where(
                    qposc[None, None, :, None] >= kpos[None, None, None, :],
                    s, _NEG_INF)
            p = jnp.exp(s - lsec[..., None])              # normalised probs
            dp = jnp.einsum("bhqd,bhkd->bhqk", doc, v_blk,
                            preferred_element_type=f32)
            ds = p * (dp - dc[..., None])
            dqc = jnp.einsum("bhqk,bhkd->bhqd", ds, k_blk,
                             preferred_element_type=f32) * scale
            dk = dk + jnp.einsum("bhqk,bhqd->bhkd", ds, qc,
                                 preferred_element_type=f32)
            dv = dv + jnp.einsum("bhqk,bhqd->bhkd", p, doc,
                                 preferred_element_type=f32)
            return (dk, dv), dqc

        xs = (_chunk(qh, nc), _chunk(do, nc), _chunk(delta, nc),
              _chunk(lse, nc), qpos.reshape(nc, bq))
        (dk_blk, dv_blk), dqs = jax.lax.scan(chunk_step, (dk_blk, dv_blk), xs)
        return dk_blk, dv_blk, dq + _unchunk(dqs)

    for j in range(n_shards):
        src_shard = (my_idx - j) % n_shards
        kpos = src_shard * sq + jnp.arange(sq)
        dk_blk, dv_blk, dq = hop(k_blk, v_blk, dk_blk, dv_blk, dq, kpos)
        if j + 1 < n_shards:
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
            dk_blk = jax.lax.ppermute(dk_blk, axis_name, perm)
            dv_blk = jax.lax.ppermute(dv_blk, axis_name, perm)
        else:
            # one final rotation brings each accumulated (dk, dv) block back
            # to its home shard
            dk_blk = jax.lax.ppermute(dk_blk, axis_name, perm)
            dv_blk = jax.lax.ppermute(dv_blk, axis_name, perm)

    def back(x, like):
        return x.transpose(0, 2, 1, 3).astype(like.dtype)

    return back(dq, q), back(dk_blk, k), back(dv_blk, v)


_ring_core.defvjp(_ring_fwd_rule, _ring_bwd_rule)


# ---- zigzag (load-balanced causal) layout --------------------------------

def _zz_perms(n_shards: int):
    """ppermute tables for the contiguous -> zigzag half-shard exchange.

    Contiguous device d holds chunks (2d, 2d+1) of the 2P-chunk split;
    zigzag owner of chunk c is ``c`` when c < P else ``2P-1-c``.  Each
    device's even chunk travels the lo table, its odd chunk the hi table;
    both are device permutations (each device receives exactly one chunk
    from each — of {t, 2P-1-t} one is even and one odd, their sum being
    odd)."""
    P = n_shards

    def owner(c):
        return c if c < P else 2 * P - 1 - c

    perm_lo = [(d, owner(2 * d)) for d in range(P)]
    perm_hi = [(d, owner(2 * d + 1)) for d in range(P)]
    inv_lo = [(dst, src) for src, dst in perm_lo]
    inv_hi = [(dst, src) for src, dst in perm_hi]
    return perm_lo, perm_hi, inv_lo, inv_hi


def _to_zigzag(x, axis_name, n_shards):
    """[b, sq, h, d] contiguous local shard -> [early_chunk; late_chunk]."""
    if n_shards == 1:
        return x
    perm_lo, perm_hi, _, _ = _zz_perms(n_shards)
    cs = x.shape[1] // 2
    lo = jax.lax.ppermute(x[:, :cs], axis_name, perm_lo)
    hi = jax.lax.ppermute(x[:, cs:], axis_name, perm_hi)
    t = jax.lax.axis_index(axis_name)
    is_even = (t % 2 == 0)
    # device t owns chunks (t, 2P-1-t); the even one arrived via lo
    early = jnp.where(is_even, lo, hi)
    late = jnp.where(is_even, hi, lo)
    return jnp.concatenate([early, late], axis=1)


def _from_zigzag(x, axis_name, n_shards):
    """Inverse of ``_to_zigzag``."""
    if n_shards == 1:
        return x
    _, _, inv_lo, inv_hi = _zz_perms(n_shards)
    cs = x.shape[1] // 2
    early, late = x[:, :cs], x[:, cs:]
    t = jax.lax.axis_index(axis_name)
    is_even = (t % 2 == 0)
    lo = jnp.where(is_even, early, late)   # the even chunk of (t, 2P-1-t)
    hi = jnp.where(is_even, late, early)
    lo = jax.lax.ppermute(lo, axis_name, inv_lo)
    hi = jax.lax.ppermute(hi, axis_name, inv_hi)
    return jnp.concatenate([lo, hi], axis=1)


def _use_pallas_hops(use_pallas, cs: int) -> bool:
    """Route zigzag hop pairs through the pallas flash kernels?

    Default: on TPU.  The kernels need 128-divisible chunks; the XLA path
    remains for everything else and for CPU (tests force ``use_pallas`` to
    exercise the kernel path in interpret mode).  The forward and backward
    gate independently — both produce/consume the same (out, lse) residual
    contract, so mixing paths is numerically sound."""
    if cs % 128:
        return False
    if use_pallas is None:
        return jax.default_backend() not in ("cpu",)
    return use_pallas


def _pair_fwd_pallas(qp, k_blk, v_blk, m, l, acc, tri, scale, interpret):
    """One zigzag chunk pair through the flash forward kernel + a
    log-sum-exp state merge.

    ``qp``/``k_blk``/``v_blk``: [b, h, cs, d] in the RAW input dtype
    (unscaled — the kernel applies ``scale`` after its MXU dot); the
    online-softmax state (m, l, acc) stays f32 outside.  The kernel returns
    normalized (out_h, lse_h); merging into the running state is exact:
    the pair's unnormalized contribution w.r.t. the new max m2 is
    out_h·exp(lse_h - m2) with mass exp(lse_h - m2)."""
    from .flash_attention import _fwd_flat, kernel_block
    b, h, cs, d = qp.shape
    # same asymmetric tiles as the single-chip forward dispatch: wider k
    # halves the per-k-block online-softmax state updates (attention())
    out_h, lse_h = _fwd_flat(qp.reshape(b * h, cs, d),
                             k_blk.reshape(b * h, cs, d),
                             v_blk.reshape(b * h, cs, d),
                             scale, tri, kernel_block(cs),
                             kernel_block(cs, cap=2048), interpret,
                             out_dtype=jnp.float32)
    out_h = out_h.reshape(b, h, cs, d)
    lse_h = lse_h.reshape(b, h, cs)
    m2 = jnp.maximum(m, lse_h)
    em = jnp.exp(m - m2)
    eh = jnp.exp(lse_h - m2)
    acc2 = acc * em[..., None] + out_h * eh[..., None]
    l2 = l * em + eh
    return m2, l2, acc2


def _pair_bwd_pallas(qp, do_p, delta_p, lse_p, k_blk, v_blk, tri, scale,
                     interpret):
    """One zigzag chunk pair through the flash backward kernels.

    ``lse_p``/``delta_p`` are the GLOBAL residuals (flash-2: per-block
    contributions are correct under any key partitioning), so each hop's
    (dq, dk, dv) pieces simply accumulate."""
    from .flash_attention import _bwd_flat, kernel_block
    b, h, cs, d = qp.shape
    blk = kernel_block(cs)
    dq, dk, dv = _bwd_flat(qp.reshape(b * h, cs, d),
                           k_blk.reshape(b * h, cs, d),
                           v_blk.reshape(b * h, cs, d),
                           do_p.reshape(b * h, cs, d),
                           lse_p.reshape(b * h, cs, 1),
                           delta_p.reshape(b * h, cs, 1),
                           scale, tri, blk, blk, interpret,
                           out_dtype=jnp.float32)
    return (dq.reshape(b, h, cs, d), dk.reshape(b, h, cs, d),
            dv.reshape(b, h, cs, d))


def _zz_forward(axis_name, n_shards, scale, block_q, use_pallas, q, k, v):
    """Zigzag per-shard forward; q/k/v local [b, sq, h, d] in zigzag row
    order ([early chunk; late chunk]).  Returns (out, lse) in the same row
    order.  Every hop costs two fully-live cs x cs chunk pairs per device
    (see module docstring) — half the contiguous layout's FLOPs, perfectly
    balanced.  On TPU each pair runs the pallas flash kernel
    (``_pair_fwd_pallas``) — the single-chip A/B showed the XLA chunk
    scans far off the kernel's throughput — with k/v rotating in the raw
    (bf16) dtype, halving ICI bytes per hop."""
    P = n_shards
    my = jax.lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    cs = sq // 2
    pallas = _use_pallas_hops(use_pallas, cs)
    interpret = jax.default_backend() in ("cpu",)
    nc = cs // _pick_block(cs, block_q)
    f32 = jnp.float32
    rows = jnp.arange(cs)
    if pallas:
        qh = q.transpose(0, 2, 1, 3)                        # RAW, unscaled
        kb = k.transpose(0, 2, 1, 3)
        vb = v.transpose(0, 2, 1, 3)

        def pair(qs, ks, vs, m, l, a, tri):
            return _pair_fwd_pallas(qs, ks, vs, m, l, a, tri, scale,
                                    interpret)
    else:
        qh = q.transpose(0, 2, 1, 3).astype(f32) * scale    # [b, h, sq, d]
        kb = k.transpose(0, 2, 1, 3).astype(f32)
        vb = v.transpose(0, 2, 1, 3).astype(f32)

        def pair(qs, ks, vs, m, l, a, tri):
            return _hop_fwd(qs, ks, vs, m, l, a, rows, rows, tri, nc)
    qe, ql = qh[:, :, :cs], qh[:, :, cs:]
    m_e = jnp.full((b, h, cs), _NEG_INF, f32)
    m_l = jnp.full((b, h, cs), _NEG_INF, f32)
    l_e = jnp.zeros((b, h, cs), f32)
    l_l = jnp.zeros((b, h, cs), f32)
    a_e = jnp.zeros((b, h, cs, d), f32)
    a_l = jnp.zeros((b, h, cs, d), f32)
    perm = [(i, (i + 1) % P) for i in range(P)]

    for j in range(P):
        ke, kl = kb[:, :, :cs], kb[:, :, cs:]
        ve, vl = vb[:, :, :cs], vb[:, :, cs:]
        if j == 0:
            # both triangular diagonal pairs, batched into one matmul
            md, ld, ad = pair(
                jnp.concatenate([qe, ql], 0), jnp.concatenate([ke, kl], 0),
                jnp.concatenate([ve, vl], 0), jnp.concatenate([m_e, m_l], 0),
                jnp.concatenate([l_e, l_l], 0), jnp.concatenate([a_e, a_l], 0),
                True)
            m_e, m_l = md[:b], md[b:]
            l_e, l_l = ld[:b], ld[b:]
            a_e, a_l = ad[:b], ad[b:]
            m_l, l_l, a_l = pair(ql, ke, ve, m_l, l_l, a_l, False)
        else:
            # q_late x k_early: always fully live
            m_l, l_l, a_l = pair(ql, ke, ve, m_l, l_l, a_l, False)
            # exactly one of q_early x k_early (d >= j) / q_late x k_late
            cond = my >= j
            q_s = jnp.where(cond, qe, ql)
            k_s = jnp.where(cond, ke, kl)
            v_s = jnp.where(cond, ve, vl)
            m_s = jnp.where(cond, m_e, m_l)
            l_s = jnp.where(cond, l_e, l_l)
            a_s = jnp.where(cond, a_e, a_l)
            m2, l2, a2 = pair(q_s, k_s, v_s, m_s, l_s, a_s, False)
            m_e = jnp.where(cond, m2, m_e)
            l_e = jnp.where(cond, l2, l_e)
            a_e = jnp.where(cond, a2, a_e)
            m_l = jnp.where(cond, m_l, m2)
            l_l = jnp.where(cond, l_l, l2)
            a_l = jnp.where(cond, a_l, a2)
        if j + 1 < P:
            kb = jax.lax.ppermute(kb, axis_name, perm)
            vb = jax.lax.ppermute(vb, axis_name, perm)

    m = jnp.concatenate([m_e, m_l], 2)
    l = jnp.concatenate([l_e, l_l], 2)
    acc = jnp.concatenate([a_e, a_l], 2)
    l_safe = jnp.maximum(l, 1e-30)
    out = (acc / l_safe[..., None]).transpose(0, 2, 1, 3).astype(q.dtype)
    lse = m + jnp.log(l_safe)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _zz_core(axis_name, n_shards, scale, block_q, use_pallas, q, k, v):
    out, _ = _zz_forward(axis_name, n_shards, scale, block_q, use_pallas,
                         q, k, v)
    return out


def _zz_fwd_rule(axis_name, n_shards, scale, block_q, use_pallas, q, k, v):
    out, lse = _zz_forward(axis_name, n_shards, scale, block_q, use_pallas,
                           q, k, v)
    return out, (q, k, v, out, lse)


def _zz_bwd_block(qh_r, do_r, delta_r, lse_r, k_blk, v_blk, tri, nc, scale):
    """(dq_rows, dk_blk, dv_blk) of one chunk pair, scanned over q chunks;
    ``tri``: triangular (diagonal-pair) mask, else fully live."""
    f32 = jnp.float32
    cs = qh_r.shape[2]
    bq = cs // nc
    rows = jnp.arange(cs)
    cols = jnp.arange(k_blk.shape[2])

    def chunk_step(carry, xs):
        dk, dv = carry
        qc, doc, dc, lsec, rowc = xs
        s = jnp.einsum("bhqd,bhkd->bhqk", qc, k_blk,
                       preferred_element_type=f32)
        if tri:
            s = jnp.where(rowc[None, None, :, None] >= cols[None, None, None, :],
                          s, _NEG_INF)
        p = jnp.exp(s - lsec[..., None])
        dp = jnp.einsum("bhqd,bhkd->bhqk", doc, v_blk,
                        preferred_element_type=f32)
        ds = p * (dp - dc[..., None])
        dqc = jnp.einsum("bhqk,bhkd->bhqd", ds, k_blk,
                         preferred_element_type=f32) * scale
        dk = dk + jnp.einsum("bhqk,bhqd->bhkd", ds, qc,
                             preferred_element_type=f32)
        dv = dv + jnp.einsum("bhqk,bhqd->bhkd", p, doc,
                             preferred_element_type=f32)
        return (dk, dv), dqc

    dk0 = jnp.zeros_like(k_blk)
    dv0 = jnp.zeros_like(v_blk)
    xs = (_chunk(qh_r, nc), _chunk(do_r, nc), _chunk(delta_r, nc),
          _chunk(lse_r, nc), rows.reshape(nc, bq))
    (dk, dv), dqs = jax.lax.scan(chunk_step, (dk0, dv0), xs)
    return _unchunk(dqs), dk, dv


def _zz_bwd_rule(axis_name, n_shards, scale, block_q, use_pallas, res, dout):
    """Zigzag memory-efficient backward: (k, v, dk, dv) rotate together,
    each hop recomputes only its two live chunk pairs — through the pallas
    flash backward kernels on TPU (``_pair_bwd_pallas``; global lse/delta
    make per-hop contributions exact), the XLA chunk scans elsewhere."""
    q, k, v, out, lse = res
    P = n_shards
    my = jax.lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    cs = sq // 2
    pallas = _use_pallas_hops(use_pallas, cs)
    interpret = jax.default_backend() in ("cpu",)
    nc = cs // _pick_block(cs, block_q)
    f32 = jnp.float32
    if pallas:
        qh = q.transpose(0, 2, 1, 3)                        # RAW, unscaled
        kb = k.transpose(0, 2, 1, 3)
        vb = v.transpose(0, 2, 1, 3)
        do = dout.transpose(0, 2, 1, 3)
    else:
        qh = q.transpose(0, 2, 1, 3).astype(f32) * scale
        kb = k.transpose(0, 2, 1, 3).astype(f32)
        vb = v.transpose(0, 2, 1, 3).astype(f32)
        do = dout.transpose(0, 2, 1, 3).astype(f32)
    ot = out.transpose(0, 2, 1, 3).astype(f32)
    delta = jnp.sum(do.astype(f32) * ot, -1)                # [b, h, sq]

    def pair_bwd(q_r, do_r, d_r, lse_r, k_s, v_s, tri):
        if pallas:
            return _pair_bwd_pallas(q_r, do_r, d_r, lse_r, k_s, v_s, tri,
                                    scale, interpret)
        return _zz_bwd_block(q_r, do_r, d_r, lse_r, k_s, v_s, tri, nc, scale)
    qe, ql = qh[:, :, :cs], qh[:, :, cs:]
    doe, dol = do[:, :, :cs], do[:, :, cs:]
    de, dl = delta[:, :, :cs], delta[:, :, cs:]
    lse_e, lse_l = lse[:, :, :cs], lse[:, :, cs:]
    dq_e = jnp.zeros((b, h, cs, d), f32)
    dq_l = jnp.zeros((b, h, cs, d), f32)
    dkb = jnp.zeros((b, h, sq, d), f32)
    dvb = jnp.zeros((b, h, sq, d), f32)
    perm = [(i, (i + 1) % P) for i in range(P)]

    for j in range(P):
        ke, kl = kb[:, :, :cs], kb[:, :, cs:]
        ve, vl = vb[:, :, :cs], vb[:, :, cs:]
        dke, dkl = dkb[:, :, :cs], dkb[:, :, cs:]
        dve, dvl = dvb[:, :, :cs], dvb[:, :, cs:]
        if j == 0:
            dq_d, dk_d, dv_d = pair_bwd(
                jnp.concatenate([qe, ql], 0), jnp.concatenate([doe, dol], 0),
                jnp.concatenate([de, dl], 0),
                jnp.concatenate([lse_e, lse_l], 0),
                jnp.concatenate([ke, kl], 0), jnp.concatenate([ve, vl], 0),
                True)
            dq_e = dq_e + dq_d[:b]
            dq_l = dq_l + dq_d[b:]
            dke, dkl = dke + dk_d[:b], dkl + dk_d[b:]
            dve, dvl = dve + dv_d[:b], dvl + dv_d[b:]
            dq2, dk2, dv2 = pair_bwd(ql, dol, dl, lse_l, ke, ve, False)
            dq_l = dq_l + dq2
            dke, dve = dke + dk2, dve + dv2
        else:
            dq2, dk2, dv2 = pair_bwd(ql, dol, dl, lse_l, ke, ve, False)
            dq_l = dq_l + dq2
            dke, dve = dke + dk2, dve + dv2
            cond = my >= j
            q_s = jnp.where(cond, qe, ql)
            do_s = jnp.where(cond, doe, dol)
            d_s = jnp.where(cond, de, dl)
            lse_s = jnp.where(cond, lse_e, lse_l)
            k_s = jnp.where(cond, ke, kl)
            v_s = jnp.where(cond, ve, vl)
            dq3, dk3, dv3 = pair_bwd(q_s, do_s, d_s, lse_s, k_s, v_s, False)
            dq_e = jnp.where(cond, dq_e + dq3, dq_e)
            dq_l = jnp.where(cond, dq_l, dq_l + dq3)
            dke = jnp.where(cond, dke + dk3, dke)
            dkl = jnp.where(cond, dkl, dkl + dk3)
            dve = jnp.where(cond, dve + dv3, dve)
            dvl = jnp.where(cond, dvl, dvl + dv3)
        dkb = jnp.concatenate([dke, dkl], 2)
        dvb = jnp.concatenate([dve, dvl], 2)
        # rotate; the final rotation returns each (dk, dv) block home
        dkb = jax.lax.ppermute(dkb, axis_name, perm)
        dvb = jax.lax.ppermute(dvb, axis_name, perm)
        if j + 1 < P:
            kb = jax.lax.ppermute(kb, axis_name, perm)
            vb = jax.lax.ppermute(vb, axis_name, perm)

    dq = jnp.concatenate([dq_e, dq_l], 2)

    def back(x, like):
        return x.transpose(0, 2, 1, 3).astype(like.dtype)

    return back(dq, q), back(dkb, k), back(dvb, v)


_zz_core.defvjp(_zz_fwd_rule, _zz_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _zz_core_pre(axis_name, n_shards, scale, block_q, use_pallas, q, k, v,
                 out, lse):
    """Zigzag core whose forward IS the provided (out, lse) — no ring run —
    while the backward is the normal zigzag pass (``_zz_bwd_rule``).

    The attention-output stash (model/blocks.py): the strategy backward
    re-runs each block's forward only to rebuild residuals, which for the
    ring means P hops of kernels AND ppermutes; with the per-layer
    (out, lse) stashed from the original forward, forming the vjp costs
    nothing.  ``out``/``lse`` arrive zigzag-LOCAL (the caller re-shards the
    stashed global arrays with the same specs, so the locals round-trip
    bit-exactly)."""
    return out


def _zz_pre_fwd(axis_name, n_shards, scale, block_q, use_pallas, q, k, v,
                out, lse):
    return out, (q, k, v, out, lse)


def _zz_pre_bwd(axis_name, n_shards, scale, block_q, use_pallas, res, dout):
    dq, dk, dv = _zz_bwd_rule(axis_name, n_shards, scale, block_q,
                              use_pallas, res, dout)
    # out/lse are stashed residual constants of the OUTER custom_vjp
    q, k, v, out, lse = res
    return dq, dk, dv, jnp.zeros_like(out), jnp.zeros_like(lse)


_zz_core_pre.defvjp(_zz_pre_fwd, _zz_pre_bwd)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh,
                   axis_name: str = "sequence", causal: bool = True,
                   scale: typing.Optional[float] = None,
                   block_q: int = 512,
                   use_pallas: typing.Optional[bool] = None,
                   stash: typing.Optional[dict] = None) -> jax.Array:
    """q, k, v: [batch, seq, heads, d] (global); returns same shape.

    Sharding: seq over ``axis_name``; batch over 'data' and heads over
    'model' when those axes exist in the mesh.  Differentiable with
    O(seq/P · d) residual memory (see module docstring).

    ``use_pallas``: route zigzag hop pairs through the pallas flash
    kernels (None = auto: TPU yes, CPU no); tests pass True to exercise
    the kernel path in interpret mode.

    ``stash``: attention-output stash channel (model/blocks.py) — the
    zigzag path collects (out, lse-in-zigzag-row-order) globals, and on
    provide runs ``_zz_core_pre`` so the strategy backward's recompute
    skips the entire ring (P hops of kernels AND ppermutes).  The gate
    (the zigzag-path condition) is static, keeping collect/provide counts
    symmetric; the contiguous fallback ignores the channel.
    """
    n_shards = mesh.shape[axis_name]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    spec = P("data" if "data" in mesh.axis_names else None,
             axis_name,
             "model" if "model" in mesh.axis_names else None,
             None)
    seq = q.shape[1]
    if causal and n_shards > 1 and seq % (2 * n_shards) == 0:
        # balanced zigzag layout: re-shard (two half-shard ppermutes, one
        # hop's worth of bytes), run the dead-work-free schedule, un-shard
        lse_spec = P(spec[0], spec[2], axis_name)       # [b, h, seq]

        def to_zz3(q, k, v):
            return (_to_zigzag(q, axis_name, n_shards),
                    _to_zigzag(k, axis_name, n_shards),
                    _to_zigzag(v, axis_name, n_shards))

        if stash is not None and stash_collecting(stash):
            def zz_collect(q, k, v):
                qz, kz, vz = to_zz3(q, k, v)
                out, lse = _zz_forward(axis_name, n_shards, scale, block_q,
                                       use_pallas, qz, kz, vz)
                # out returns in NORMAL row order; lse stays in zigzag row
                # order (an opaque token — provide re-splits it with the
                # same spec, so the locals round-trip bit-exactly)
                return _from_zigzag(out, axis_name, n_shards), lse

            fn = shard_map(zz_collect, mesh=mesh,
                           in_specs=(spec, spec, spec),
                           out_specs=(spec, lse_spec), check_vma=False)
            with jax.named_scope("ring_attention"):
                out, lse = fn(q, k, v)
            stash_push(stash, (out, lse))
            return out

        if stash is not None:
            out_s, lse_s = stash_pop(stash)

            def zz_provide(q, k, v, out_g, lse_l):
                qz, kz, vz = to_zz3(q, k, v)
                oz = _to_zigzag(out_g, axis_name, n_shards)
                res = _zz_core_pre(axis_name, n_shards, scale, block_q,
                                   use_pallas, qz, kz, vz, oz, lse_l)
                return _from_zigzag(res, axis_name, n_shards)

            fn = shard_map(zz_provide, mesh=mesh,
                           in_specs=(spec, spec, spec, spec, lse_spec),
                           out_specs=spec, check_vma=False)
            with jax.named_scope("ring_attention"):
                return fn(q, k, v, out_s, lse_s)

        def zz_fn(q, k, v):
            qz, kz, vz = to_zz3(q, k, v)
            out = _zz_core(axis_name, n_shards, scale, block_q, use_pallas,
                           qz, kz, vz)
            return _from_zigzag(out, axis_name, n_shards)

        fn = shard_map(zz_fn, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
        with jax.named_scope("ring_attention"):
            return fn(q, k, v)
    fn = shard_map(
        functools.partial(_ring_core, axis_name, n_shards, causal, scale,
                          block_q),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    with jax.named_scope("ring_attention"):
        return fn(q, k, v)


def dense_reference(q, k, v, causal=True, scale=None):
    """O(s^2) reference implementation for tests."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                        k.astype(jnp.float32))
    if causal:
        s = q.shape[1]
        mask = jnp.where(jnp.arange(s)[:, None] >= jnp.arange(s)[None, :],
                         0., -jnp.inf)
        scores = scores + mask[None, None]
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bhqd", p, v.astype(jnp.float32))
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)
