"""Softmax cross-entropy with z-loss, fused with the head matmul.

``loss = mean(log_z - logit[target]) + z_loss * mean(log_z ** 2)`` over
``logits = x . w`` (reference: src/mtf_wrapper.py:64-71).  At a vocabulary of
256 the ``[tokens, vocab]`` logits, their exponentials and a one-hot of the
targets were free; at 50,304 columns each float32 copy of 8,192 tokens is
1.65 GB.  So the sequence is walked in chunks: each chunk's logits are made,
reduced to the loss's sums and — under differentiation — to their
contribution to ``dx`` and ``dw`` at once, and dropped.  The forward rule
returns those gradients as its residuals; the backward rule only scales them
by the loss's cotangent, so no chunk is computed twice and no
``[tokens, vocab]`` array outlives its chunk.  The target's logit is taken by
index, not through a one-hot.

Axes: ``b`` lead (batch and what rides with it), ``s`` sequence, ``h, k``
the feature dims, ``p`` token patch, ``v`` vocabulary.  (The features stay
two axes: merged into one, the head's gradient came out in a layout that
cost two more copies of it a step on the v5e; PERF.md section 6, PR 26.)
"""
from __future__ import annotations

import functools
import math
import typing

import jax
import jax.numpy as jnp

from ..core.tensor import transpose_to


#: float32 bytes of one chunk's logits the walk aims to stay under.  Every
#: chunk adds its part to the head's whole float32 gradient (412 MB at 2048 x
#: 50,304: a millisecond of memory traffic a chunk on a v5e), so fewer, larger
#: chunks are faster: 16 chunks cost 20 ms a step there, 4 cost 12
#: (PERF.md section 6, PR 26)
CHUNK_BYTES = 1 << 29


def chunks_for(b: int, s: int, p: int, v: int) -> int:
    """The fewest chunks of the sequence (a divisor of ``s``) whose float32
    logits stay under ``CHUNK_BYTES``."""
    for n in range(1, s + 1):
        if s % n == 0 and b * (s // n) * p * v * 4 <= CHUNK_BYTES:
            return n
    return s


def _matmul(spec: str, a, b):
    """``core.tensor.einsum``'s accumulation rule on plain arrays: float32
    accumulation for bfloat16 operands where the backend has it."""
    prefer = None
    if a.dtype == jnp.bfloat16 and jax.default_backend() != "cpu":
        prefer = jnp.float32
    return jnp.einsum(spec, a, b, preferred_element_type=prefer)


def _softmax_parts(x, w, targets):
    """One chunk's ``(logits, the same in float32, exp(logits - max), its sum
    over the vocabulary, log_z, the target's logit)``."""
    logits = x if w is None else _matmul("bshk,hkpv->bspv", x, w
                                         ).astype(x.dtype)
    lf = logits.astype(jnp.float32)
    top = jnp.max(lf, axis=-1, keepdims=True)
    ex = jnp.exp(lf - top)
    total = jnp.sum(ex, axis=-1, keepdims=True)
    log_z = (jnp.log(total) + top)[..., 0]
    picked = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
    return logits, lf, ex, total, log_z, picked


def _chunk(x, w, targets, z_loss: float, count: int, with_grads: bool):
    """One chunk: ``(sum of log_z - picked, sum of log_z^2, dx, dw)``; the
    gradients are of the MEAN over ``count`` targets, None without
    ``with_grads``.  ``w`` None: ``x`` holds the logits themselves."""
    logits, lf, ex, total, log_z, picked = _softmax_parts(x, w, targets)
    sums = jnp.sum(log_z - picked), jnp.sum(jnp.square(log_z))
    if not with_grads:
        return sums + (None, None)
    # d loss / d logits = (softmax * (1 + 2 z log_z) - [v == target]) / count
    grad = ex * ((1.0 + 2.0 * z_loss * log_z)[..., None] / total)
    hit = jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1) \
        == targets[..., None]
    grad = (jnp.where(hit, grad - 1.0, grad) / count).astype(logits.dtype)
    if w is None:
        return sums + (grad, None)
    return sums + (_matmul("bspv,hkpv->bshk", grad, w).astype(x.dtype),
                   _matmul("bshk,bspv->hkpv", x, grad).astype(jnp.float32))


def _split(n_chunks: int, t):
    """``t [b, s, ...]`` as ``[n_chunks, b, s / n_chunks, ...]``: what a walk
    scans over."""
    return jnp.moveaxis(t.reshape(
        (t.shape[0], n_chunks, t.shape[1] // n_chunks) + t.shape[2:]), 1, 0)


def _walk(x, w, targets, z_loss: float, n_chunks: int, with_grads: bool):
    """The loss (float32) and, with ``with_grads``, ``(dx, dw)``."""
    count = targets.size
    if n_chunks == 1:
        a, z, dx, dw = _chunk(x, w, targets, z_loss, count, with_grads)
    else:
        split = functools.partial(_split, n_chunks)
        keep_dw = with_grads and w is not None

        def step(carry, chunk):
            a, z, dx, dw = _chunk(chunk[0], w, chunk[1], z_loss, count,
                                  with_grads)
            return (carry[0] + a, carry[1] + z,
                    carry[2] + dw if keep_dw else carry[2]), dx

        init = (jnp.float32(0), jnp.float32(0),
                jnp.zeros(w.shape if keep_dw else (), jnp.float32))
        (a, z, dw), dx = jax.lax.scan(step, init, (split(x), split(targets)))
        if not keep_dw:
            dw = None
        if dx is not None:
            dx = jnp.moveaxis(dx, 0, 1).reshape(x.shape)
    loss = a / count
    if z_loss:
        loss = loss + z_loss * z / count
    return loss, dx, (None if dw is None else dw.astype(w.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _xent(x, w, targets, z_loss: float, n_chunks: int):
    return _walk(x, w, targets, z_loss, n_chunks, False)[0]


def _xent_fwd(x, w, targets, z_loss, n_chunks):
    loss, dx, dw = _walk(x, w, targets, z_loss, n_chunks, True)
    return loss, (dx, dw)


def _xent_bwd(z_loss, n_chunks, res, g):
    dx, dw = res
    return ((dx * g.astype(dx.dtype)),
            None if dw is None else dw * g.astype(dw.dtype), None)


_xent.defvjp(_xent_fwd, _xent_bwd)


def head_xent(x, w: typing.Optional[jax.Array], targets, z_loss: float):
    """Mean cross-entropy (+ z-loss) of ``targets [b, s, p]`` under the
    logits ``x [b, s, h, k] . w [h, k, p, v]`` — or, with ``w`` None, under
    ``x [b, s, p, v]`` itself — as a float32 scalar."""
    b, s, p = targets.shape
    v = x.shape[-1] if w is None else w.shape[-1]
    with jax.named_scope("head_loss"):
        return _xent(x, w, targets, float(z_loss), chunks_for(b, s, p, v))


# ---- the walk a token: a loss and a weight each ----------------------------
#
# A looped model (model/loop.py) weighs every token's cross-entropy at every
# pass by that token's exit probability, and the gate that makes the
# probability needs the token's loss back.  The gate reads the passes'
# outputs, not their logits, so the weights are known before the first chunk
# is walked: they go IN, each chunk's gradient is made with them at once
# (as ``_chunk`` makes its with ``1 / count``), and the backward rule scales
# the residuals by a scalar like ``_xent_bwd`` — no chunk's logits are made
# twice, which a walk that waited for a ``[b, s]`` cotangent would have to.
# What comes OUT beside the weighted sum is the losses a token, which are the
# weighted sum's gradient by the weights.  All passes go through ONE walk
# (stacked on the lead axis), so one float32 head gradient takes every
# chunk's part.

def _chunk_tokens(x, w, targets, weights, z_loss: float, with_grads: bool):
    """One chunk: ``(token losses, dx, dw)``, the gradients of ``sum(weights
    * token losses)``, None without ``with_grads``."""
    logits, lf, ex, total, log_z, picked = _softmax_parts(x, w, targets)
    token = log_z - picked
    if z_loss:
        token = token + z_loss * jnp.square(log_z)
    if not with_grads:
        return token, None, None
    grad = ex * (((1.0 + 2.0 * z_loss * log_z) * weights)[..., None] / total)
    hit = jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1) \
        == targets[..., None]
    grad = jnp.where(hit, grad - weights[..., None], grad).astype(logits.dtype)
    return (token, _matmul("bspv,hkpv->bshk", grad, w).astype(x.dtype),
            _matmul("bshk,bspv->hkpv", x, grad).astype(jnp.float32))


def _walk_tokens(x, w, targets, weights, z_loss: float, n_chunks: int,
                 with_grads: bool):
    """The token losses ``[b, s, p]`` (float32) and, with ``with_grads``,
    ``(dx, dw)`` of their weighted sum."""
    if n_chunks == 1:
        token, dx, dw = _chunk_tokens(x, w, targets, weights, z_loss,
                                      with_grads)
    else:
        split = functools.partial(_split, n_chunks)

        def join(t):
            return jnp.moveaxis(t, 0, 1).reshape(
                (t.shape[1], n_chunks * t.shape[2]) + t.shape[3:])

        def step(dw, chunk):
            token, dx, part = _chunk_tokens(chunk[0], w, chunk[1], chunk[2],
                                            z_loss, with_grads)
            return (dw + part if with_grads else dw), (token, dx)

        dw, (token, dx) = jax.lax.scan(
            step, jnp.zeros(w.shape if with_grads else (), jnp.float32),
            (split(x), split(targets), split(weights)))
        token = join(token)
        dx = join(dx) if with_grads else None
    return token, dx, (dw.astype(w.dtype) if with_grads else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _xent_tokens(x, w, targets, weights, z_loss: float, n_chunks: int):
    token = _walk_tokens(x, w, targets, weights, z_loss, n_chunks, False)[0]
    return jnp.sum(weights * token), token


def _xent_tokens_fwd(x, w, targets, weights, z_loss, n_chunks):
    token, dx, dw = _walk_tokens(x, w, targets, weights, z_loss, n_chunks,
                                 True)
    return (jnp.sum(weights * token), token), (dx, dw, token)


def _xent_tokens_bwd(z_loss, n_chunks, res, g):
    # the token losses leave as plain values (head_xent_tokens stops their
    # gradient), so only the weighted sum's cotangent arrives
    dx, dw, token = res
    return (dx * g[0].astype(dx.dtype), dw * g[0].astype(dw.dtype), None,
            token * g[0])


_xent_tokens.defvjp(_xent_tokens_fwd, _xent_tokens_bwd)


def head_xent_tokens(x, w, targets, weights, z_loss: float):
    """``(sum over the tokens of weights * loss, the losses [b, s, p])`` of
    ``targets [b, s, p]`` under the logits ``x [b, s, h, k] . w [h, k, p,
    v]``, float32: each token's cross-entropy (+ ``z_loss`` times its squared
    log-partition) and a float32 weight a token.  The sum carries gradients
    to ``x``, ``w`` and ``weights``; the losses beside it are values only.
    With ``weights`` all ``1 / targets.size`` the sum is ``head_xent``."""
    b, s, p = targets.shape
    with jax.named_scope("head_loss"):
        loss, token = _xent_tokens(x, w, targets, weights, float(z_loss),
                                   chunks_for(b, s, p, w.shape[-1]))
    return loss, jax.lax.stop_gradient(token)


def named_operands(params, stream, head, targets):
    """``head_xent_tokens``' operands of named tensors: ``(x [b, s, h, k], w
    [h, k, p, v], targets [b, s, p])`` of the head's input ``stream``, the
    output embedding ``head`` and ``targets`` — every lead dim folded into
    ``b``."""
    seq = [d for d in targets.dims if d.name == params.sequence_dim.name]
    last = [params.token_patch_dim]
    lead = [d for d in targets.dims if d not in seq + last]
    feats = list(params.feature_dims)
    shape = (math.prod(d.size for d in lead), math.prod(d.size for d in seq))
    x = transpose_to(stream, lead + seq + feats).data.reshape(
        shape + tuple(d.size for d in feats))
    tgt = transpose_to(targets, lead + seq + last).data.reshape(
        shape + (last[0].size,))
    return x, transpose_to(head, feats + last + [params.vocab_dim]).data, tgt
