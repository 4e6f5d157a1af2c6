"""The exit gate and the loss of a looped (weight-tied-depth) model: the body
and the output blocks run ``loop_steps`` times over the same weights
(``model/__init__.py _build_looped`` runs the passes), and what is here turns
the passes' outputs ``h_1 .. h_T`` into one loss.

After "Scaling Latent Reasoning via Looped Language Models" (Ouro,
arXiv:2510.25741), the stage-one objective of its section on adaptive exit:
one linear gate with a bias, shared by the passes, gives every token a
probability of leaving after pass ``t``,

    lambda_t = sigmoid(w_g . h_t + b_g)
    p_t = lambda_t prod_{j<t} (1 - lambda_j)   (t < T),
    p_T = prod_{j<T} (1 - lambda_j)            so that sum_t p_t = 1,

and the loss is the mean over the tokens of ``sum_t p_t CE_t - beta H(p)``,
``CE_t`` the token's cross-entropy under the head on ``h_t``, ``H(p) = - sum_t
p_t log p_t``, ``beta = loop_exit_entropy``.  The last pass's gate is never
read.  Everything a token is float32, in logarithms (``log sigmoid`` is
``-softplus``).  Training only: stage two (the gate alone, trained on what a
pass improves), exit by ``early_exit_threshold`` and the passes' KV caches are
serving's, and decode and prefill refuse a looped model by name.
"""
from __future__ import annotations

import math
import typing

import jax
import jax.numpy as jnp

from ..config import ModelParameter
from ..core import scope
from ..core.tensor import NamedTensor, transpose_to
from .backend import ConstantInit, NormalInit
from .declare import Layer, Stat
from .loss import head_xent_tokens


def exit_log_distribution(gate_logits: jax.Array) -> jax.Array:
    """``log p [T, ...]`` from the gate's logits at passes ``1 .. T - 1``
    (``[T - 1, ...]``): ``p`` at a zero gate is (1/2, 1/4, .., 2^-(T-1),
    2^-(T-1))."""
    log_stay = -jax.nn.softplus(gate_logits)        # log(1 - lambda_t)
    log_exit = -jax.nn.softplus(-gate_logits)       # log lambda_t
    stayed = jnp.cumsum(log_stay, axis=0)
    return jnp.concatenate([log_exit + stayed - log_stay, stayed[-1:]])


def _gate_logits(params: ModelParameter, streams: jax.Array) -> jax.Array:
    """``w_g . h + b_g`` on ``streams [T - 1, b, s, heads, features]`` in
    float32 (a multiply and a sum, no matmul: a float32 matmul on a TPU would
    round its operands).  Weights normal(0.02) ``[heads, features]`` and a
    bias at 0, stored like every parameter and never rounded to the
    calculation dtype."""
    def var(name, dims, initializer):
        return scope.scoped(name, scope.get_param, "var", dims, initializer,
                            params.slice_dtype, jnp.float32).data
    weight = var("normal_var", list(params.feature_dims), NormalInit(0.02))
    bias = var("constant_var", [], ConstantInit(0.))
    return jnp.sum(streams.astype(jnp.float32) * weight, axis=(-2, -1)) + bias


def gated_loss(params: ModelParameter,
               streams: typing.Sequence[NamedTensor], head: NamedTensor,
               targets: NamedTensor) -> typing.Tuple[jax.Array, jax.Array,
                                                     dict]:
    """``(the loss, its cross-entropy part sum_t p_t CE_t, the step's
    statistics)`` — float32 scalars, and ``{loop_pass_loss [T],
    loop_exit_share [T], loop_exit_entropy}`` (the means over the tokens of
    ``CE_t``, ``p_t`` and ``H(p)``).  ``streams``: the passes' outputs, each
    what the head reads; ``head``: the output embedding."""
    seq = [d for d in targets.dims if d.name == params.sequence_dim.name]
    last = [params.token_patch_dim]
    lead = [d for d in targets.dims if d not in seq + last]
    feats = list(params.feature_dims)
    shape = (math.prod(d.size for d in lead), math.prod(d.size for d in seq))
    steps = len(streams)
    xs = jnp.stack([transpose_to(s, lead + seq + feats).data.reshape(
        shape + tuple(d.size for d in feats)) for s in streams])
    tgt = transpose_to(targets, lead + seq + last).data.reshape(
        shape + (last[0].size,))
    w = transpose_to(head, feats + last + [params.vocab_dim]).data
    with scope.name_scope("exit_gate"):
        log_p = exit_log_distribution(_gate_logits(params, xs[:-1]))
        p = jnp.exp(log_p)                                   # [T, b, s]
        entropy = -jnp.sum(p * log_p, axis=0)
        weights = jnp.broadcast_to((p / tgt.size)[..., None],
                                   (steps,) + tgt.shape)
    # one walk over every pass's tokens, the passes stacked on the lead axis
    merged = (steps * shape[0],)
    cross, token = head_xent_tokens(
        xs.reshape(merged + xs.shape[2:]), w,
        jnp.tile(tgt, (steps, 1, 1)), weights.reshape(merged + tgt.shape[1:]),
        params.z_loss)
    with jax.named_scope("exit_gate"):
        loss = cross - params.loop_exit_entropy * jnp.mean(entropy)
        stats = {"loop_pass_loss": jnp.mean(
                     token.reshape((steps, -1)), axis=1),
                 "loop_exit_share": jnp.mean(p.reshape((steps, -1)), axis=1),
                 "loop_exit_entropy": jnp.mean(entropy)}
    return loss, cross, stats


#: what a looped model reports a step (``Context.layer_stats``): a gauge a
#: pass, labelled by the pass's index, and the entropy
gated_loss.declares = Layer(stats=(
    Stat("loop_pass_loss", "gauge", "hbnlp_loop_pass_loss",
         "mean cross-entropy over the tokens under the head on a pass's "
         "output, newest finished step", "each", label="pass"),
    Stat("loop_exit_share", "gauge", "hbnlp_loop_exit_share",
         "mean over the tokens of the exit gate's probability of leaving "
         "after a pass (the shares sum to 1), newest finished step", "each",
         label="pass"),
    Stat("loop_exit_entropy", "gauge", "hbnlp_loop_exit_entropy",
         "mean over the tokens of the entropy of the exit distribution, "
         "nats (ln loop_steps at uniform), newest finished step", "max")))
