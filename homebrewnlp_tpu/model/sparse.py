"""Block-selected sparse attention's INDEXER (attention flag ``sparse``,
model/spatial.py; MiniCPM4 / InfLLM-V2's trainable sparse attention as
MiniCPM-SALA's ``minicpm4`` layers run it): which blocks of keys each query
keeps, one choice for the query heads of a K/V group.

For query ``t`` and K/V group ``g``, on the layer's (normalised) queries and
keys, with ``kernel = sparse_kernel_size``, ``stride = sparse_kernel_stride``,
``block = sparse_block_size``:

    Kbar_j = mean(k[stride j : stride j + kernel])       ``compress``
    visible(t, j): stride j + kernel <= t + 1
    p_(t,h,.) = softmax_j(scale q_(t,h) . Kbar_j) over the visible ones
    P_(t,g,j) = sum of p over the group's heads           ``index``
    score(t, g, b) = max of P over the pooled windows that overlap block b
                     (keys block b .. block b + block - 1)
    +inf on the first ``sparse_init_blocks`` blocks and on the ``sparse_window
    / block`` blocks that end at t's own; -inf on blocks past t's own
    keep(t, g, .) = the ``sparse_topk`` best blocks, forced ones among them;
                    ties to the lower block                ``select``

The softmax over the pooled keys is EXACT; InfLLM-V2 approximates its
normaliser from a coarser pooling (a departure the configuration's
``assumed`` states).  Nothing here carries a gradient: the caller stops it on
the inputs.  The queries are walked in chunks (``lax.map``) so that the
``[heads, chunk, pooled keys]`` float32 probabilities of one chunk are alive
at a time.
"""
from __future__ import annotations

import typing

import jax
import jax.numpy as jnp

from ..config import ModelParameter
from .loss import _matmul

#: queries a chunk of the indexer (16 heads x 2,048 x 1,024 float32 = 134 MB)
QUERY_CHUNK = 2048


class Sizes(typing.NamedTuple):
    kernel: int
    stride: int
    block: int
    topk: int
    init_blocks: int
    window: int
    dense_length: int


def sizes_of(params: ModelParameter) -> Sizes:
    return Sizes(params.sparse_kernel_size, params.sparse_kernel_stride,
                 params.sparse_block_size, params.sparse_topk,
                 params.sparse_init_blocks, params.sparse_window,
                 params.sparse_dense_length)


def selects(sizes: Sizes, s: int) -> bool:
    """Whether a sequence of ``s`` keys is selected at all: past the dense
    length, in whole blocks."""
    if s <= sizes.dense_length:
        return False
    if s % sizes.block:
        raise ValueError(f"sparse attention past its dense length takes "
                         f"whole blocks: sequence {s}, sparse_block_size "
                         f"{sizes.block}")
    return True


def compress(k, sizes: Sizes):
    """Pooled keys ``[b, g, pooled, d]`` float32 of ``k [b, s, g, d]``: the
    mean of every ``kernel`` keys, a window each ``stride``."""
    b, s, g, d = k.shape
    per = sizes.kernel // sizes.stride
    cells = k.astype(jnp.float32).reshape(b, s // sizes.stride, sizes.stride,
                                          g, d).sum(axis=2)
    pooled = s // sizes.stride - per + 1
    total = sum(cells[:, m:m + pooled] for m in range(per))
    return jnp.moveaxis(total, 1, 2) / sizes.kernel


def block_scores(q, pooled_keys, first: int, sizes: Sizes, scale: float,
                 blocks: int):
    """``[b, g, queries, blocks]`` float32 scores of the queries ``q [b,
    queries, h, d]`` at positions ``first ..``: the summed probabilities,
    max-pooled to blocks, forced blocks ``+inf``, blocks past the query's
    own ``-inf``."""
    b, n, h, d = q.shape
    g, pooled = pooled_keys.shape[1], pooled_keys.shape[2]
    pos = first + jnp.arange(n)
    with jax.named_scope("index"):
        qg = jnp.moveaxis(q.reshape(b, n, g, h // g, d), 1, 3)
        logits = _matmul("bgrnd,bgjd->bgrnj", qg,
                         pooled_keys.astype(q.dtype)
                         ).astype(jnp.float32) * scale
        visible = (sizes.stride * jnp.arange(pooled) + sizes.kernel
                   )[None, :] <= pos[:, None] + 1
        logits = jnp.where(visible, logits, -jnp.inf)
        top = jnp.max(logits, axis=-1, keepdims=True)
        weights = jnp.where(visible, jnp.exp(logits - jnp.where(
            jnp.isfinite(top), top, 0.0)), 0.0)
        total = jnp.sum(weights, axis=-1, keepdims=True)
        probs = jnp.sum(weights / jnp.maximum(total, 1e-30), axis=2)
    with jax.named_scope("select"):
        # block b meets windows (block b - kernel) / stride + 1 .. (block b +
        # block - 1) / stride: ``per`` of its own and ``lead`` before them
        per, lead = sizes.block // sizes.stride, \
            sizes.kernel // sizes.stride - 1
        padded = jnp.pad(probs, ((0, 0), (0, 0), (0, 0),
                                 (lead, per * blocks - pooled)))
        score = padded[..., 0:per * blocks:per]
        for m in range(1, per + lead):
            score = jnp.maximum(score, padded[..., m:m + per * blocks:per])
        idx = jnp.arange(blocks)
        own = (pos // sizes.block)[:, None]
        forced = (idx[None, :] < sizes.init_blocks) \
            | (idx[None, :] > own - max(1, sizes.window // sizes.block))
        score = jnp.where(forced, jnp.inf, score)
        return jnp.where(idx[None, :] <= own, score, -jnp.inf)


def top_blocks(score, topk: int):
    """``keep`` bool like ``score [.., blocks]``: the ``topk`` largest of a
    row, ties to the lower block, and never a ``-inf`` one.  By rank — how
    many blocks beat this one — so no sort runs."""
    blocks = score.shape[-1]
    idx = jnp.arange(blocks)
    ahead = (score[..., None, :] > score[..., :, None]) \
        | ((score[..., None, :] == score[..., :, None])
           & (idx[None, :] < idx[:, None]))
    return (jnp.sum(ahead, axis=-1) < topk) & (score > -jnp.inf)


def select_blocks(q, k, sizes: Sizes, scale: float):
    """``keep [b, g, s, s / block]`` bool of ``q [b, s, h, d]`` and ``k [b, s,
    g, d]`` (module docstring)."""
    b, s, h, d = q.shape
    blocks = s // sizes.block
    with jax.named_scope("compress"):
        pooled_keys = compress(k, sizes)
    chunk = QUERY_CHUNK if s % QUERY_CHUNK == 0 else s

    def one(args):
        q_chunk, first = args
        score = block_scores(q_chunk, pooled_keys, first, sizes, scale,
                             blocks)
        with jax.named_scope("select"):
            return top_blocks(score, sizes.topk)

    keep = jax.lax.map(one, (
        jnp.moveaxis(q.reshape(b, s // chunk, chunk, h, d), 1, 0),
        jnp.arange(0, s, chunk)))
    # [chunks, b, g, chunk, blocks] -> [b, g, s, blocks]
    return jnp.moveaxis(keep, 0, 2).reshape(b, keep.shape[2], s, blocks)


def kept_shares(keep, block: int):
    """``(kept keys over visible keys, mean over the queries; the share of
    the queries that left a visible block out)`` of a choice ``keep [b, g, s,
    blocks]``, float32 scalars."""
    s, blocks = keep.shape[2], keep.shape[3]
    pos = jnp.arange(s)
    own = pos // block
    # keys of block j that query t may see
    keys = jnp.clip(pos[:, None] + 1 - jnp.arange(blocks)[None, :] * block,
                    0, block)
    kept = jnp.sum(jnp.where(keep, keys, 0), axis=-1)
    share = jnp.mean(kept.astype(jnp.float32)
                     / (pos + 1).astype(jnp.float32))
    chose = jnp.mean((jnp.sum(keep, axis=-1) < own + 1).astype(jnp.float32))
    return share, chose
