"""What the recurrent mixers (layers ``mamba`` and ``gated_delta``) share:
what they refuse and the layout they take, the per-channel float32
parameters and their initialisers, the causal depthwise conv's XLA form, and what a layer declares of itself for
``model/remat.py`` (its chunk states, its conv, the output it offers to save
across the block's replay, the triangular systems it solves)."""
from __future__ import annotations

import typing

import jax
import jax.numpy as jnp
import numpy as np

from ..config import BlockArgs, ModelParameter
from ..core import scope


class Recurrent(typing.NamedTuple):
    """Set as ``<layer function>.recurrent``: ``state_bytes(params)`` — the
    bytes of chunk states one layer keeps for its backward, for the whole
    batch — and ``conv(params)`` — ``(channels, taps, offset)`` of its causal
    depthwise conv, as ``parallel/causal_conv.kernel_applies`` takes them.

    A layer that re-materialises its own interior in the backward also
    OFFERS ITS OUTPUT to the ``checkpoint`` strategy (``model/remat.py``'s
    ``recurrent`` kind): ``saved_names`` — what it tags with
    ``jax.ad_checkpoint.checkpoint_name`` — and ``saved_bytes(params)`` —
    their bytes for the whole batch.  Where the block's ``jax.checkpoint``
    saves them, the replay runs no forward of the recurrence: everything its
    backward needs the layer's own ``jax.checkpoint`` makes again from the
    recurrence's inputs.  ``gated_delta`` offers the rule's output (``batch x
    sequence x delta_heads x delta_value_features`` in the calculation
    dtype).  ``mamba`` offers nothing: its scan has no inner
    ``jax.checkpoint``, so the replay's forward IS the pass that makes the
    backward's residuals, and a saved output would skip none of it.

    A layer that solves a unit triangular system a chunk declares it:
    ``solve(params)`` — ``(chunk, matrices a call)`` as
    ``parallel/delta_solve.solve_kernel_applies`` takes them
    (``gated_delta``: the systems of one group of heads); None = none."""
    state_bytes: typing.Callable[[ModelParameter], int]
    conv: typing.Callable[[ModelParameter], typing.Tuple[int, int, int]]
    saved_names: typing.Tuple[str, ...] = ()
    saved_bytes: typing.Optional[
        typing.Callable[[ModelParameter], int]] = None
    solve: typing.Optional[
        typing.Callable[[ModelParameter], typing.Tuple[int, int]]] = None


def token_layout(args: BlockArgs, layer: str, chunk: int):
    """What a recurrent mixer checks before it builds anything: training or
    a full-sequence forward on one device, a ``[batch, sequence, features]``
    input, whole chunks.  Returns ``(token dims, batch, sequence, the chunk
    as it runs)``: a sequence shorter than ``chunk`` is one chunk."""
    params, ctx = args.params, scope.current()
    if ctx.decode is not None or getattr(ctx, "prefill", None) is not None:
        raise NotImplementedError(
            f"layer {layer} has no incremental decode / prefill form yet")
    if ctx.mesh is not None and ctx.mesh.size > 1:
        raise NotImplementedError(f"layer {layer} on a mesh is a later issue")
    token_dims = [d for d in args.tensor.dims if d not in params.feature_dims]
    if len(token_dims) != 2 or token_dims[1] != params.sequence_dim:
        raise ValueError(f"layer {layer} mixes [batch, sequence, features]; "
                         f"got {args.tensor.dims}")
    bsz, s = (d.size for d in token_dims)
    chunk = min(chunk, s)
    if s % chunk:
        raise ValueError(f"sequence {s} is not a multiple of {layer}'s chunk "
                         f"{chunk}")
    return token_dims, bsz, s, chunk


def _inverse_softplus_of_exp(log_dt: np.ndarray) -> np.ndarray:
    """``dt_bias`` with ``softplus(dt_bias) = exp(log_dt)``: the Mamba-2
    code's ``dt + log(-expm1(-dt))``."""
    dt = np.exp(log_dt)
    return (dt + np.log(-np.expm1(-dt))).astype(np.float32)


def _small_var(args: BlockArgs, name: str, shape, initializer) -> jax.Array:
    """A per-channel vector the recurrence reads in float32 (``A_log``,
    ``dt_bias``, ``D``, the conv and norm weights): stored in the slice dtype
    like every parameter, never rounded to the calculation dtype."""
    params = args.params
    return scope.scoped(name, scope.get_param, "var", shape, initializer,
                        params.slice_dtype, jnp.float32).data


def causal_depthwise_conv(x, weight, bias=None):
    """``y[t] = bias + sum_k weight[k] x[t - (K - 1) + k]`` on ``x [b, s,
    channels]``, zeros before the sequence, ``bias`` None = none: K shifted
    multiplies.  The path off the TPU and at shapes
    ``parallel/causal_conv.py`` declines, and that kernel's reference."""
    k = weight.shape[0]
    s = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = bias
    for i in range(k):
        tap = padded[:, i:i + s] * weight[i]
        out = tap if out is None else out + tap
    return out
