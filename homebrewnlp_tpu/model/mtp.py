"""A multi-token-prediction module (``mtp_depth`` > 0; DeepSeek-V3,
arXiv:2412.19437 section 2.2): one more pass a depth through the SAME token
embedding ``E`` and the SAME head ``W_head`` as the main model, over the last
output joined to the NEXT token's embedding and through blocks of its own
weights (``mtp_block_config``: the body's own layers, ``Offer``s and scopes),
with its own cross-entropy.  With ``h^0`` the main stack's output after its
output blocks (the final norm) and pass ``k = 1 .. mtp_depth``,

    x_i   = [rms(E[t_(i+k)]) w_e | rms(h^(k-1)_i) w_h] W_join   2 x features
                                                                -> features
    h^k   = blocks_k(x), causal over i
    p_i   = rms(h^k_i) w_o W_head                               predicts
                                                                t_(i+k+1)
    L_k   = mean over i = 0 .. T - k - 2 of CE(p_i, t_(i+k+1))
    L_mtp = mean over k of L_k

On a batch ``token_x``, ``token_y`` (= ``token_x`` one on) pass ``k`` embeds
``token_y`` moved ``k - 1`` on and is held to ``token_y`` moved ``k`` on; the
last ``k`` positions have no target and weigh 0 (``head_xent_tokens`` takes a
weight a token), and being the last they reach no earlier position through a
causal block.  The embedding is joined FIRST and ``h`` is taken AFTER the
final norm, as vLLM's and SGLang's DeepSeek-V3 modules read the released
weights (the report's eq. 21 writes the other order).  The head pass is the
chunked walk of model/loss.py: no ``[T, vocab]`` logits are made.

The step's objective is ``L_main + mtp_loss_weight x L_mtp`` (``LossInfo.
objective``); its reported ``loss`` stays ``L_main``, and ``L_mtp`` is the step
statistic ``mtp_loss``.  Everything here runs under scope ``mtp``: ``join``,
``body`` (the blocks), ``output`` (the last norm), ``head_loss``.  Training
and the full forward only: the configuration refuses a looped model, the
revnet / momentum streams, ``scan_layers`` and a pipeline mesh by name,
``Model.apply_decode`` / ``apply_prefill`` refuse at the call (the module as
a self-drafting head is serving's).
"""
from __future__ import annotations

import typing

import jax
import jax.numpy as jnp

from ..config import BlockArgs, ModelParameter
from ..core import scope
from ..core.dims import Dim
from ..core.tensor import NamedTensor, cast, nt, reduce_sum, transpose_to
from .declare import Layer, Stat
from .embedding import batched_gather
from .loss import head_xent_tokens, named_operands
from .normalization import norm
from .spatial import project


def _moved(tokens: NamedTensor, params: ModelParameter, by: int) -> NamedTensor:
    """``tokens`` moved ``by`` positions on along the sequence (what wraps
    round lands on the last ``by`` positions, which weigh 0)."""
    if not by:
        return tokens
    axis = [d.name for d in tokens.dims].index(params.sequence_dim.name)
    return nt(jnp.roll(tokens.data, -by, axis=axis), tokens.dims)


def _rms(params: ModelParameter, x: NamedTensor) -> NamedTensor:
    """RMSNorm with a learned scale over all features, as the blocks'
    ``norm-rms-scale``."""
    return scope.scoped("norm_", norm, BlockArgs(params, x, ["rms", "scale"]))


def _join(params: ModelParameter, table: NamedTensor, tokens: NamedTensor,
          stream: NamedTensor) -> NamedTensor:
    """``[rms(E[tokens]) w_e | rms(stream) w_h] W_join``: parameters in
    creation order ``w_e``, ``w_h``, ``W_join [2, features -> features]``,
    normal(0.02)."""
    feats = list(params.feature_dims)
    with jax.named_scope("join"):
        emb = reduce_sum(cast(batched_gather(table, tokens),
                              params.calculation_dtype),
                         reduced_dim=params.token_patch_dim)
        if params.embedding_multiplier != 1:
            emb = emb * params.embedding_multiplier
        emb = _rms(params, emb)
        stream = transpose_to(_rms(params, stream), emb.dims)
        lead = [d for d in emb.dims if d not in feats]
        pair = Dim("mtp_join", 2)
        joined = nt(jnp.stack([emb.data, stream.data], axis=len(lead)),
                    lead + [pair] + feats)
        return project(BlockArgs(params, joined, []), joined, feats,
                       [pair] + feats)


def _head_loss(params: ModelParameter, stream: NamedTensor,
               head: NamedTensor, targets: NamedTensor, ahead: int
               ) -> jax.Array:
    """Mean cross-entropy (+ ``z_loss``) of ``targets`` under the head on
    ``stream``, over the positions that have a target ``ahead`` on: float32."""
    if params.logits_scaling != 1:
        stream = stream * (1 / params.logits_scaling)
    x, w, tgt = named_operands(params, stream, head, targets)
    shape = tgt.shape
    held = shape[1] - ahead
    weights = jnp.broadcast_to(
        (jnp.arange(shape[1]) < held)[None, :, None].astype(jnp.float32)
        / (shape[0] * held * shape[2]), tgt.shape)
    return head_xent_tokens(x, w, tgt, weights, params.z_loss)[0]


def module_loss(params: ModelParameter, storage: dict, targets: NamedTensor,
                main_loss: NamedTensor,
                plan: typing.Optional[tuple]
                ) -> typing.Tuple[jax.Array, tuple]:
    """``(L_mtp, the module's block plan)``: ``storage`` holds what the main
    model made — ``stream`` (its output after the output blocks), ``head``
    (the head's operands) and ``text_input_embedding`` (the table) —,
    ``targets`` is ``token_y``, ``plan`` the module's blocks' (None: init)."""
    from .blocks import run_body_blocks   # blocks imports the layer table
    ctx = scope.current()
    table, head = storage["text_input_embedding"], storage["head"][1]
    stream, losses, specs = storage["stream"], [], ()
    unit = len(params.mtp_block_config)
    for k in range(params.mtp_depth):
        joined = _join(params, table, _moved(targets, params, k), stream)
        part = None if plan is None else plan[k * unit:(k + 1) * unit]
        stream, made = scope.scoped("body", run_body_blocks, params, joined,
                                    part, 0, k)
        specs += tuple(made)
        normed = scope.scoped("output", _rms, params, stream)
        losses.append(_head_loss(params, normed, head,
                                 _moved(targets, params, k + 1), k + 1))
    loss = sum(losses) / len(losses)
    if ctx.layer_stats is not None:
        ctx.layer_stats.append({
            "mtp_loss": loss,
            "mtp_main_loss": main_loss.data.astype(jnp.float32)})
    return loss, specs


#: what a model with the module reports a step (``Context.layer_stats``)
module_loss.declares = Layer(stats=(
    Stat("mtp_loss", "gauge", "hbnlp_mtp_loss",
         "mean cross-entropy of the multi-token-prediction module's passes "
         "(each over the positions that have its target), nats, newest "
         "finished step; the step's reported loss does not hold it", "mean"),
    Stat("mtp_loss_over_main", "gauge", "hbnlp_mtp_loss_over_main",
         "the module's cross-entropy over the main model's next-token "
         "cross-entropy of the same step (near 1 at initialisation, above it "
         "while the module predicts a token further on)",
         lambda stats, done: done["mtp_loss"]
         / jnp.mean(stats["mtp_main_loss"]), "mtp_loss")))
