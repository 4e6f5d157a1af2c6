"""Token-level sparse attention's learned INDEXER (attention flag ``indexed``,
model/spatial.py; DeepSeek Sparse Attention's lightning indexer, DeepSeek-V3.2
arXiv:2512.02556, as Keye-VL-2.0's ``sa_config`` sizes it): which single KEYS
each query keeps, one choice for ALL the query heads of a layer, made by a
small scorer with parameters and a loss of its own.

The layer hands over, from ``stop_gradient`` of its normed input, ``H =
index_heads`` index queries ``qI [b, s, H, d]`` (``d = index_features``,
rotated), ONE index key ``kI [b, s, d]`` (LayerNorm, rotated) and a weight a
head ``w [b, s, H]`` (already times ``H ** -0.5``).  Then, in float32,

    I[t, u] = d ** -0.5 * sum_j w[t, j] * relu(qI[t, j] . kI[u]),   u <= t
    S_t     = the min(t + 1, index_topk) largest I[t, .] among u <= t, ties to
              the lower u                                        ``select``
    pbar[t, u] = mean over the query heads of the attention's softmax
              probability of key u in S_t, detached
    L_I     = mean_t sum_{u in S_t} pbar (log pbar - log softmax_{S_t}(I[t, .]))
                                                                 ``index_loss``

``select`` is EXACT: the k-th largest score of a row is found by bisection on
the scores' ordered bit pattern (32 counting passes), ties at it go to the
lower positions; no sort, no ``lax.top_k`` over the keys.  The choice is held
as bits, ``[b, 1, s / 32, s]`` int32 (``parallel/flash_attention.py
pack_keep``): a bit a (query, key) pair a layer, never a float ``[s, s]``.
Every XLA pass walks the queries ``QUERY_CHUNK`` at a time (``lax.map`` /
``lax.scan``), one index head — or one attention head — at a time inside a
chunk, so that one ``[b, chunk, s]`` float32 plane a live value is what a
pass holds; and in ``BANDS`` bands of chunks, a band against the keys up to
its last query only (what lies past them no row of it may see).

Which pass runs where: ``select_keys`` (``index``, ``select``) is XLA's
everywhere.  ``index_loss`` is ONE Pallas kernel a layer,
``parallel/index_loss.py index_loss_pass``, where the call can see that it
applies (``kernel_applies``: a TPU, the choice held as bits with the
attention's ``lse`` over it, a sequence of whole tiles) — its ``[q tile, k
tile]`` planes stay in VMEM and it walks the tiles at or under the diagonal,
so chunks and bands mean nothing to it — and ``xla_index_loss`` everywhere
else: off the TPU, up to ``index_topk`` keys (``keep is None``), a sequence
of no whole tiles.  The XLA form is the kernel's reference
(``tests/index_loss_kernel_test.py``, ``scripts/kernel_parity.py
--only-index-loss``); no option chooses between them.

``L_I``'s gradient reaches ``qI``, ``kI`` and ``w`` only, and is made BY HAND
in the pass that makes the loss (``d L_I / d I = (softmax_S(I) - pbar) /
queries`` on the kept pairs, because ``pbar`` sums to one): ``index_loss``
returns the value and the three gradients, all named
(``INDEX_LOSS_NAMES``) so that where they are saved a block's replay runs no
second pass, and ``inject`` — the identity on the attention's output, as
``model/basic.py _router_aux_inject`` is on the router's logits — adds them in
the backward with ``LOSS_WEIGHT``.  The reported loss stays the task loss;
the value is a step statistic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..parallel.flash_attention import KEEP_WORD, pack_keep, unpack_keep
from ..parallel.index_loss import (index_loss_pass, index_loss_tile,
                                   kernel_applies,
                                   walked_over_visible as kernel_walk)
from .loss import _matmul

#: queries a chunk of every pass (Keye-VL-2.0's ``q_chunk_size``: a tiling,
#: no equation reads it)
QUERY_CHUNK = 512
#: bands of chunks a pass is cut into (``_bands``): causality by the band,
#: at one traced copy of a pass a band
BANDS = 4
#: the index loss's weight in the step's gradient (DeepSeek-V3.2's sparse
#: stage: the indexer on its loss alone, weight 1)
LOSS_WEIGHT = 1.0
#: the names of what ``index_loss`` returns, for a memory strategy to keep
INDEX_LOSS_NAMES = ("index_loss_value", "index_score_top",
                    "index_grad_query", "index_grad_key", "index_grad_weight")


def selects(topk: int, s: int) -> bool:
    """Whether a sequence of ``s`` keys is selected at all: past ``topk``
    keys, in whole words of ``KEEP_WORD`` queries."""
    if s <= topk:
        return False
    if s % KEEP_WORD:
        raise ValueError(f"indexed attention past index_topk {topk} takes "
                         f"whole words of {KEEP_WORD} queries: sequence {s}")
    return True


def _chunk(s: int) -> int:
    return QUERY_CHUNK if s % QUERY_CHUNK == 0 else s


def _chunked(x, chunk: int):
    """``[b, s, ..]`` -> ``[s / chunk, b, chunk, ..]``."""
    b, s = x.shape[:2]
    return jnp.moveaxis(x.reshape(b, s // chunk, chunk, *x.shape[2:]), 1, 0)


def _unchunked(x):
    """``_chunked``'s inverse."""
    x = jnp.moveaxis(x, 0, 1)
    return x.reshape(x.shape[0], x.shape[1] * x.shape[2], *x.shape[3:])


def _head_logits(q_head, keys, scale: float):
    """``[b, n, s]`` float32 of ONE head's queries ``[b, n, d]`` against
    ``keys [b, s, d]``."""
    return _matmul("bnd,bsd->bns", q_head, keys).astype(jnp.float32) * scale


def scores(q_index, k_index, weight):
    """``I [b, n, s]`` float32 of a chunk's index queries ``[b, n, H, d]``,
    all index keys ``[b, s, d]`` and head weights ``[b, n, H]``, one index
    head at a time; nothing is masked here."""
    scale = q_index.shape[-1] ** -0.5

    def head(total, args):
        q_head, w_head = args
        return total + w_head[..., None] * jax.nn.relu(
            _head_logits(q_head, k_index, scale)), None

    b, n = q_index.shape[:2]
    total, _ = jax.lax.scan(
        head, jnp.zeros((b, n, k_index.shape[1]), jnp.float32),
        (jnp.moveaxis(q_index, 2, 0),
         jnp.moveaxis(weight.astype(jnp.float32), 2, 0)))
    # -0.0 (a negative weight times a relu's zero) would order below +0.0
    return total + 0.0


def _ordered(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    key = bits ^ ((bits >> 31) & jnp.int32(0x7fffffff))
    return jax.lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(1 << 31)


def top_keys(score, first: int, topk: int):
    """``keep [b, n, s]`` bool of ``score [b, n, s]`` float32, the queries at
    positions ``first ..``: the ``min(t + 1, topk)`` largest scores of a row
    among the keys ``u <= t``, ties to the lower ``u``.  Exact: the k-th
    largest ordered bit pattern by bisection, a counting pass a bit."""
    b, n, s = score.shape
    pos = first + jnp.arange(n)
    visible = jnp.arange(s)[None, :] <= pos[:, None]
    # an invisible key orders below every float (the smallest pattern a float
    # has is -nan's 0x00000000 -> only a NaN score could tie with it)
    key = jnp.where(visible, _ordered(score), jnp.uint32(0))
    want = jnp.minimum(pos + 1, topk).astype(jnp.int32)[None, :]

    def bit(i, found):
        trial = found | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        enough = jnp.sum(key >= trial[..., None], axis=-1,
                         dtype=jnp.int32) >= want
        return jnp.where(enough, trial, found)

    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros((b, n), jnp.uint32))
    above = key > kth[..., None]
    level = (key == kth[..., None]) & visible
    room = want - jnp.sum(above, axis=-1, dtype=jnp.int32)
    ties = jnp.sum(level, axis=-1, dtype=jnp.int32) > room
    level = jax.lax.cond(
        jnp.any(ties),
        lambda: level & (jnp.cumsum(level, axis=-1, dtype=jnp.int32)
                         <= room[..., None]),
        lambda: level)
    return above | level


def _bands(s: int, chunk: int):
    """``(first query, queries, keys)`` of each band of chunks: a band's
    queries see no key past its last one, so its passes walk ``keys``
    columns, not ``s`` — ``BANDS`` bands walk 62.5% of the square."""
    chunks = s // chunk
    bands = BANDS if chunks % BANDS == 0 else 1
    rows = chunks // bands * chunk
    return [(i * rows, rows, (i + 1) * rows) for i in range(bands)]


def select_keys(q_index, k_index, weight, topk: int):
    """``keep [b, 1, s / KEEP_WORD, s]`` int32, the choice as bits (module
    docstring)."""
    s = q_index.shape[1]
    chunk = _chunk(s)

    def band(start: int, rows: int, keys: int):
        def one(args):
            q_chunk, w_chunk, first = args
            with jax.named_scope("index"):
                score = scores(q_chunk, k_index[:, :keys], w_chunk)
            with jax.named_scope("select"):
                return pack_keep(top_keys(score, first, topk))

        words = _unchunked(jax.lax.map(one, (
            _chunked(q_index[:, start:start + rows], chunk),
            _chunked(weight[:, start:start + rows], chunk),
            jnp.arange(start, start + rows, chunk))))
        return jnp.pad(words, ((0, 0), (0, 0), (0, s - keys)))

    return jnp.concatenate([band(*b) for b in _bands(s, chunk)],
                           axis=1)[:, None]


def index_loss(q_index, k_index, weight, q, k, lse, keep, scale: float):
    """``(L_I, the largest |I| among the kept pairs, d L_I / d qI, d L_I / d
    kI, d L_I / d w)`` — float32, the gradients shaped as their operands — of
    a layer (module docstring): ``q [b, s, h, f]`` and
    ``k [b, s, g, f]`` the attention's own (rotated, normalised) queries and
    keys, ``lse [b * h, s]`` its log-normalisers over the kept keys (None:
    made here), ``keep`` the choice as bits (None: every key ``u <= t``).
    Nothing here carries a gradient.  By what the call can see
    (``kernel_applies``): one Pallas kernel on a TPU, ``xla_index_loss``
    everywhere else."""
    if kernel_applies(q.shape[1], keep is not None and lse is not None):
        return index_loss_pass(q_index, k_index, weight, q, k, lse, keep,
                               scale)
    return xla_index_loss(q_index, k_index, weight, q, k, lse, keep, scale)


def walked_over_visible(s: int, kernel: bool) -> float:
    """The (query, key) pairs ``index_loss`` walks over the ``s (s + 1) / 2``
    a query may see: the kernel's tiles at or under the diagonal, the XLA
    form's bands."""
    if kernel:
        return kernel_walk(s, index_loss_tile(s))
    return sum(rows * keys for _, rows, keys in _bands(s, _chunk(s))) \
        / (s * (s + 1) / 2)


def xla_index_loss(q_index, k_index, weight, q, k, lse, keep, scale: float):
    """``index_loss`` as XLA's own passes — the path off the TPU, of the
    ``keep is None`` case and of a sequence of no whole kernel tiles, and the
    kernel's reference: a chunk of queries at a time, an attention head at a
    time for ``pbar``, an index head at a time for the scores and their
    backward."""
    b, s, h, f = q.shape
    g = k.shape[2]
    chunk = _chunk(s)
    i_scale = q_index.shape[-1] ** -0.5
    weight = weight.astype(jnp.float32)
    # [h, b, s]: a head's log-normalisers beside its queries
    lse = None if lse is None else lse.reshape(b, h, s).swapaxes(0, 1)

    def band(start: int, rows: int, keys: int):
        cut = slice(start, start + rows)
        k_band = k_index[:, :keys]
        k_heads = jnp.moveaxis(k[:, :keys], 2, 0)

        def chunk_pass(grad_key, args):
            q_chunk, w_chunk, a_chunk, lse_chunk, kept, first = args
            kept = unpack_keep(kept) if kept is not None else jnp.arange(
                keys)[None, :] <= (first + jnp.arange(chunk))[:, None]

            def attention_head(total, args):
                head, a_head, norm = args
                logits = jnp.where(kept, _head_logits(
                    a_head, k_heads[head // (h // g)], scale), -jnp.inf)
                if norm is None:
                    norm = jax.scipy.special.logsumexp(logits, axis=-1)
                return total + jnp.exp(logits - norm[..., None]), None

            pbar, _ = jax.lax.scan(
                attention_head, jnp.zeros((b, chunk, keys), jnp.float32),
                (jnp.arange(h), jnp.moveaxis(a_chunk, 2, 0), lse_chunk))
            pbar = pbar / h
            score = scores(q_chunk, k_band, w_chunk)
            top = jnp.max(jnp.where(kept, jnp.abs(score), 0.0))
            score = jnp.where(kept, score, -jnp.inf)
            log_index = score - jax.scipy.special.logsumexp(
                score, axis=-1, keepdims=True)
            value = jnp.sum(jnp.where(pbar > 0, pbar * (
                jnp.log(jnp.maximum(pbar, 1e-38)) - log_index), 0.0))
            # pbar sums to one over the kept keys: d L / d I = softmax - pbar
            d_score = jnp.where(kept, jnp.exp(log_index) - pbar, 0.0) \
                / (b * s)

            def index_head(grad_k, args):
                q_head, w_head = args
                logits = _head_logits(q_head, k_band, i_scale)
                d_w = jnp.sum(d_score * jax.nn.relu(logits), axis=-1)
                d_logits = jnp.where(logits > 0, d_score * w_head[..., None],
                                     0.0) * i_scale
                d_q = _matmul("bns,bsd->bnd", d_logits,
                              k_band.astype(jnp.float32))
                return grad_k + _matmul(
                    "bns,bnd->bsd", d_logits,
                    q_head.astype(jnp.float32)), (d_q, d_w)

            grad_key, (d_q, d_w) = jax.lax.scan(
                index_head, grad_key, (jnp.moveaxis(q_chunk, 2, 0),
                                       jnp.moveaxis(w_chunk, 2, 0)))
            return grad_key, (value, top, jnp.moveaxis(d_q, 0, 2),
                              jnp.moveaxis(d_w, 0, 2))

        grad_key, (value, top, grad_q, grad_w) = jax.lax.scan(
            chunk_pass, jnp.zeros(k_band.shape, jnp.float32),
            (_chunked(q_index[:, cut], chunk), _chunked(weight[:, cut], chunk),
             _chunked(q[:, cut], chunk),
             # [chunks, h, b, chunk]
             None if lse is None else jnp.moveaxis(
                 lse[:, :, cut].reshape(h, b, rows // chunk, chunk), 2, 0),
             None if keep is None else _chunked(
                 keep[:, 0, start // KEEP_WORD:(start + rows) // KEEP_WORD,
                      :keys], chunk // KEEP_WORD),
             jnp.arange(start, start + rows, chunk)))
        return jnp.sum(value), jnp.max(top), _unchunked(grad_q), jnp.pad(
            grad_key, ((0, 0), (0, s - keys), (0, 0))), _unchunked(grad_w)

    value, top, grad_q, grad_key, grad_w = zip(*(
        band(*x) for x in _bands(s, chunk)))
    return sum(value) / (b * s), functools.reduce(jnp.maximum, top), \
        jnp.concatenate(grad_q, axis=1), sum(grad_key), \
        jnp.concatenate(grad_w, axis=1)


def named_index_loss(*operands, scale: float):
    """``index_loss`` on detached operands, each result named
    (``INDEX_LOSS_NAMES``)."""
    with jax.named_scope("index_loss"):
        return tuple(checkpoint_name(x, name) for x, name in zip(index_loss(
            *(None if x is None else jax.lax.stop_gradient(x)
              for x in operands), scale), INDEX_LOSS_NAMES))


@jax.custom_vjp
def inject(out, q_index, k_index, weight, grad_q, grad_k, grad_w):
    """The identity on ``out`` (the attention's output); the backward hands
    ``q_index``, ``k_index`` and ``weight`` the index loss's gradients times
    ``LOSS_WEIGHT`` (module docstring)."""
    return out


def _inject_fwd(out, q_index, k_index, weight, grad_q, grad_k, grad_w):
    return out, tuple((grad * LOSS_WEIGHT).astype(x.dtype) for grad, x in (
        (grad_q, q_index), (grad_k, k_index), (grad_w, weight)))


def _inject_bwd(grads, ct):
    return (ct,) + grads + tuple(jnp.zeros(x.shape, jnp.float32)
                                 for x in grads)


inject.defvjp(_inject_fwd, _inject_bwd)


def kept_shares(keep):
    """``(kept keys over visible keys, mean over the queries; the share of
    the queries that left a visible key out)`` of a choice as bits ``[b, 1,
    s / KEEP_WORD, s]``, float32 scalars."""
    s = keep.shape[3]
    kept = jnp.sum(unpack_keep(keep[:, 0]), axis=-1, dtype=jnp.int32)
    visible = jnp.arange(1, s + 1)
    return jnp.mean(kept.astype(jnp.float32) / visible.astype(jnp.float32)), \
        jnp.mean((kept < visible).astype(jnp.float32))
