"""Block-diffusion training (``diffusion_block`` > 0; BD3-LM, arXiv:2503.09573
section 3 and its appendix on the training mask; SDAR, arXiv:2510.06303): on a
clean sequence ``x_0 .. x_(L-1)`` in blocks of ``B``, ``b(i) = i // B``,

    t_b     ~ U[t_min, 1]                  one rate a block of a sequence
    m_i     ~ Bernoulli(t_b(i))            a mask a token
    z_i     = MASK if m_i else x_i         the noised sequence
    stream  = [E[z_0] .. E[z_(L-1)] | E[x_0] .. E[x_(L-1)]]
                                           2 L positions, pos = (0 .. L-1 |
                                           0 .. L-1), ONE pass of the body
    logits  = head(the noised half)        L positions
    loss    = (1 / L) sum_i m_i (1 / t_b(i)) CE(logits_i, x_i)

The target is the SAME position's clean token (no shift) and the weight a
position ``m_i / t_b(i)``: the negative ELBO a token under the linear
schedule.  The attention under the mask is the layers' (``attention`` flag
``block_diffusion``, model/spatial.py; parallel/flash_attention.py): a noised
query sees the noised keys of its own block and the clean keys of earlier
blocks, a clean query the clean keys of its own and earlier blocks.

The noise is ONE pure function of a key (``noise``), drawn inside the step
from the step's key (``Trainer.step``: ``PRNGKey(current_step + counter)``);
``Model.apply`` without a key draws it from ``PRNGKey(0)``, so a forward
outside the step is under noise too, not a special evaluation form.  Scopes:
``denoise/noise`` (the draws), ``denoise/join`` (the two sequences' ids side
by side, before the ONE gather from the table), ``denoise/split`` (the noised
half of the body's output).  ``sequence_length`` stays the TRAINED tokens a
sequence; the body's stream (``params.sequence_dim``) is twice that.

Training and the full forward only: the configuration refuses what it does
not build by name (config.py), ``Model.apply_decode`` / ``apply_prefill``
refuse at the call (a sampler that fills a block in several passes, and a
cache written a clean block at a time, are serving's).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..config import ModelParameter
from ..core import scope
from ..core.tensor import NamedTensor, nt, slice_
from .declare import Fact, Layer, Stat
from .loss import head_xent_tokens, named_operands


def noise(key, tokens, block: int, t_min: float, mask_id: int):
    """``(noised [batch, L] like tokens, weights [batch, L] float32)`` of
    ``tokens [batch, L]``, draw by draw:

        t = t_min + (1 - t_min) * uniform(fold_in(key, 0), [batch, L // block])
        u = uniform(fold_in(key, 1), [batch, L])
        m = u < repeat(t, block)            a token is masked at its block's rate
        noised  = where(m, mask_id, tokens)
        weights = m / repeat(t, block)

    ``jax.random.uniform`` in float32 on ``[0, 1)`` both times."""
    batch, length = tokens.shape
    rate = t_min + (1.0 - t_min) * jax.random.uniform(
        jax.random.fold_in(key, 0), (batch, length // block), jnp.float32)
    rate = jnp.repeat(rate, block, axis=1)
    masked = jax.random.uniform(jax.random.fold_in(key, 1), (batch, length),
                                jnp.float32) < rate
    return jnp.where(masked, jnp.asarray(mask_id, tokens.dtype), tokens), \
        masked.astype(jnp.float32) / rate


def joined_tokens(params: ModelParameter, tokens: NamedTensor, storage: dict
                  ) -> NamedTensor:
    """``[noised | tokens]`` along the sequence, ``[batch, 2 L, patch]``; the
    clean tokens and the weights a position go into ``storage["denoise"]``
    for the loss."""
    ctx = scope.current()
    key = ctx.rng_key if ctx.rng_key is not None else jax.random.PRNGKey(0)
    batch, seq, _ = (d.size for d in tokens.dims)
    with jax.named_scope("denoise"):
        with jax.named_scope("noise"):
            noised, weights = noise(key, tokens.data.reshape(batch, seq),
                                    params.diffusion_block,
                                    params.diffusion_t_min,
                                    # -1 = the last row of the vocabulary
                                    params.diffusion_mask_id
                                    % params.vocab_size)
        with jax.named_scope("join"):
            both = jnp.concatenate([noised.reshape(tokens.data.shape),
                                    tokens.data], axis=1)
    storage["denoise"] = (tokens, weights)
    if ctx.layer_stats is not None:
        ctx.layer_stats.append({
            "denoise_masked_share": jnp.mean((weights > 0).astype(
                jnp.float32)),
            "denoise_weight_mean": jnp.mean(weights)})
    return nt(both, [tokens.dims[0], params.sequence_dim, tokens.dims[2]])


def noised_half(params: ModelParameter, out: NamedTensor) -> NamedTensor:
    """The body's output at the noised positions: the first half of the
    stream."""
    with jax.named_scope("denoise"), jax.named_scope("split"):
        return slice_(out, 0, params.token_sequence_dim.size,
                      params.sequence_dim.name)


def masked_loss(params: ModelParameter, stream: NamedTensor,
                head: NamedTensor, storage: dict) -> jax.Array:
    """``(1 / (batch L)) sum m_i / t_b(i) CE(head(stream_i), x_i)`` (+
    ``z_loss``'s term a position at the same weight) through the chunked walk
    of model/loss.py: float32, no ``[L, vocab]`` logits."""
    targets, weights = storage["denoise"]
    x, w, tgt = named_operands(params, stream, head, targets)
    loss = head_xent_tokens(
        x, w, tgt, weights.reshape(tgt.shape) / tgt.size, params.z_loss)[0]
    ctx = scope.current()
    if ctx.layer_stats is not None:
        ctx.layer_stats.append({"denoise_loss": loss})
    return loss


#: what a block-diffusion step reports of its noise (``Context.layer_stats``)
joined_tokens.declares = Layer(stats=(
    Stat("denoise_masked_share", "gauge", "hbnlp_denoise_masked_share",
         "block-diffusion training: masked positions over the trained "
         "tokens of the newest finished step (near 0.5: the rates are "
         "U[diffusion_t_min, 1] a block)", "mean"),
    Stat("denoise_weight_mean", "gauge", "hbnlp_denoise_weight_mean",
         "block-diffusion training: mean over the trained tokens of the "
         "loss's weight a position, m / t (near 1), newest finished step",
         "mean"),
    Stat("denoise_loss", "gauge", "hbnlp_denoise_loss",
         "block-diffusion training: the step's loss in float32, before it "
         "is reported in the calculation dtype", "mean")),
    facts=(Fact(
        70, "hbnlp_denoise_stream_positions",
        "block-diffusion training: positions a sequence the body of the "
        "built step runs over, noised half and clean half (2 x "
        "sequence_length; no series without diffusion_block)",
        lambda params, mesh, backend: params.sequence_dim.size
        if params.diffusion_block else None,
        "denoise stream {} positions", zero=False),))
