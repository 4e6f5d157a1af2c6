"""What the recurrent mixers (layers ``mamba``, ``gated_delta``, ``kda`` and
``lightning``) share:
what they refuse and the layout they take, the per-channel float32
parameters and their initialisers, the causal depthwise conv's XLA form, what
a layer declares of its recurrence (:class:`Recurrent`) and the start-up
facts made of it (:data:`FACTS`: the chunk states alive for the backward, the
layers whose conv, whose triangular solve, whose chunked rule and whose
chunked scan are the Pallas pairs)."""
from __future__ import annotations

import typing

import jax
import jax.numpy as jnp
import numpy as np

from ..config import BlockArgs, ModelParameter
from ..core import scope
from ..core.sharding import shard_geometry
from ..parallel.causal_conv import kernel_applies
from ..parallel.delta_rule import rule_kernel_applies
from ..parallel.delta_solve import solve_kernel_applies
from ..parallel.ssd_scan import ssd_kernel_applies
from .declare import Fact, layers


class Recurrent(typing.NamedTuple):
    """Set as ``<layer function>.declares.recurrent``: ``state_bytes(params)``
    — the bytes of chunk states one layer keeps for its backward, for the
    whole batch — and ``conv(params)`` — ``(channels, taps, offset)`` of its
    causal depthwise conv, as ``parallel/causal_conv.kernel_applies`` takes
    them (None: the layer has no conv, ``lightning``).  A layer that solves a unit triangular system a chunk declares it:
    ``solve(params, backend)`` — ``(chunk, matrices a call)`` as
    ``parallel/delta_solve.solve_kernel_applies`` takes them
    (``gated_delta``: the systems of every head where its rule is the Pallas
    pair on that backend, else of one group of heads); None = none.
    A layer whose chunked rule can be a Pallas pair declares its shapes and
    the predicate it calls on them itself: ``rule(params)`` — ``(chunk,
    heads, d_k, d_v, sequence)`` — and ``rule_applies(*shapes, backend)``:
    ``gated_delta`` with ``parallel/delta_rule.py``'s ``rule_kernel_applies``
    (one decay a head; the default), ``kda`` with ``parallel/kda_rule.py``'s
    ``kda_kernel_applies`` (a decay a channel); None = none.
    A layer whose chunked scan can be ``parallel/ssd_scan.py``'s pair declares
    its shapes: ``scan(params)`` — ``(sequence, chunk, heads, head features,
    state, groups)`` as ``ssd_kernel_applies`` takes them round its backend
    (``mamba``); None = none.

    A layer that re-materialises its own interior in the backward also OFFERS
    ITS OUTPUT to the ``checkpoint`` strategy (its ``declares.offer``, kind
    ``recurrent``): where the block's ``jax.checkpoint`` saves it, the replay
    runs no forward of the recurrence.  ``mamba`` has no such interior — its
    saved output would skip none of the replay — and offers its
    IN-PROJECTION's output instead (model/mamba.py ``SAVED_NAMES``): the
    replay of a region that rides runs no in-projection matmul, the layer's
    largest, and the conv and the scan again from the saved value.  Its
    scan's ``y`` and the entering chunk states the forward kernel writes
    (what the scan's backward reads beside the call's inputs) would be the
    offer's interior; they buy a fifth of what the in-projection's bytes do,
    so the layer declares none (ROADMAP S9b(3))."""
    state_bytes: typing.Callable[[ModelParameter], int]
    conv: typing.Optional[
        typing.Callable[[ModelParameter], typing.Tuple[int, int, int]]]
    solve: typing.Optional[typing.Callable[
        [ModelParameter, typing.Optional[str]], typing.Tuple[int, int]]] = None
    scan: typing.Optional[
        typing.Callable[[ModelParameter], typing.Tuple[int, ...]]] = None
    rule: typing.Optional[
        typing.Callable[[ModelParameter], typing.Tuple[int, ...]]] = None
    rule_applies: typing.Callable[..., bool] = rule_kernel_applies


def recurrent_layers(params: ModelParameter) -> typing.List[Recurrent]:
    """What each recurrent mixer of one depth unit declares of its
    recurrence, in execution order."""
    return [spec.recurrent for _, _, spec in layers(params)
            if spec.recurrent is not None]


def ssd_state_bytes(params: ModelParameter, mesh=None) -> int:
    """Per-device bytes of the recurrent mixers' chunk states — what a layer
    declares (``mamba``: ``[batch, sequence / mamba_chunk, mamba_heads,
    mamba_head_features, mamba_state]`` float32, ``gated_delta``: ``[batch,
    sequence / delta_chunk, delta_heads, delta_value_features,
    delta_key_features]`` in the calculation dtype), what the inter-chunk
    scan's backward reads — that are alive at once for the backward: ONE
    layer's (the largest) under ``checkpoint`` / ``revnet`` / ``momentum``
    (no policy saves them across the forward; the block's replay makes them
    again and drops them with the block), every layer's under ``none``.  0
    without such a layer."""
    sizes = [spec.state_bytes(params) for spec in recurrent_layers(params)]
    if not sizes:
        return 0
    shards, _ = shard_geometry(mesh)
    alive = sum(sizes) * params.depth \
        if params.memory_reduction_strategy == "none" else max(sizes)
    return -(-alive // shards)


def conv_kernel_layers(params: ModelParameter, backend=None) -> int:
    """How many recurrent mixers of the step take the Pallas conv kernel
    pair (``parallel/causal_conv.py``), by the predicate the layers
    themselves call on the conv each declares."""
    count = 0
    for spec in recurrent_layers(params):
        if spec.conv is None:
            continue
        channels, taps, offset = spec.conv(params)
        count += kernel_applies(channels, params.sequence_dim.size, taps,
                                offset, backend)
    return count * params.depth


def solve_kernel_layers(params: ModelParameter, backend=None
                        ) -> typing.Optional[int]:
    """How many recurrent mixers of the step take the Pallas pair for their
    triangular solve (``parallel/delta_solve.py``), by the predicate the
    layer itself calls on the systems it declares; None where no layer
    declares a solve."""
    solves = [spec.solve(params, backend)
              for spec in recurrent_layers(params) if spec.solve is not None]
    if not solves:
        return None
    return params.depth * sum(solve_kernel_applies(chunk, matrices, backend)
                              for chunk, matrices in solves)


def scan_kernel_layers(params: ModelParameter, backend=None
                       ) -> typing.Optional[int]:
    """How many recurrent mixers of the step take the Pallas pair for their
    chunked scan (``parallel/ssd_scan.py``), by the predicate the layer
    itself calls on the shapes it declares; None where no layer declares a
    scan."""
    shapes = [spec.scan(params) for spec in recurrent_layers(params)
              if spec.scan is not None]
    if not shapes:
        return None
    # what a layer declares past the five shapes follows the backend
    return params.depth * sum(ssd_kernel_applies(*each[:5], backend, *each[5:])
                              for each in shapes)


def rule_kernel_layers(params: ModelParameter, backend=None
                       ) -> typing.Optional[int]:
    """The same for the chunked delta rule, each layer by its own predicate
    (``parallel/delta_rule.py``'s pair under ``gated_delta``,
    ``parallel/kda_rule.py``'s under ``kda``); None where no layer declares
    a rule."""
    rules = [spec for spec in recurrent_layers(params)
             if spec.rule is not None]
    if not rules:
        return None
    return params.depth * sum(spec.rule_applies(*spec.rule(params), backend)
                              for spec in rules)


#: every recurrent mixer's ``declares.facts``
FACTS = (
    Fact(10, "hbnlp_ssd_state_bytes",
         "per-device bytes of the recurrent mixers' (mamba, gated_delta) "
         "chunk states alive at once for the backward",
         lambda params, mesh, backend: ssd_state_bytes(params, mesh) or None,
         "ssd chunk states {} bytes a device"),
    Fact(20, "hbnlp_mamba_conv_kernel_layers",
         "recurrent mixers (mamba, gated_delta) of the built step whose conv "
         "is the Pallas kernel pair (0 on the XLA fallback)",
         lambda params, mesh, backend: conv_kernel_layers(params, backend)
         if any(spec.conv is not None for spec in recurrent_layers(params))
         else None,
         "conv kernel {} layers"),
    Fact(30, "hbnlp_delta_solve_kernel_layers",
         "gated_delta layers of the built step whose triangular solve is the "
         "Pallas kernel pair (0 on the XLA blocked form, and without such a "
         "layer)",
         lambda params, mesh, backend: solve_kernel_layers(params, backend),
         "solve kernel {} layers"),
    Fact(32, "hbnlp_delta_rule_kernel_layers",
         "gated_delta and kda layers of the built step whose chunked rule is "
         "the Pallas kernel pairs (0 on the XLA form over groups of heads, "
         "and without such a layer)",
         lambda params, mesh, backend: rule_kernel_layers(params, backend),
         "rule kernel {} layers"),
    Fact(35, "hbnlp_ssd_scan_kernel_layers",
         "mamba layers of the built step whose chunked scan is the Pallas "
         "kernel pair (0 on the XLA einsums, and without such a layer)",
         lambda params, mesh, backend: scan_kernel_layers(params, backend),
         "scan kernel {} layers"),
)


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
