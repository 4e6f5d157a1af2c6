"""Weight-only int8 quantization for serving (``serve_quantized_weights``).

The eligibility rules, the per-channel scale-axis selection and
``quantize_variables`` live in ``core`` next to the scope / materialize
machinery that consumes the scales: a loaded checkpoint is quantized ONCE on
the host; ``core.scope.materialize_param`` dequantizes at use so the
convert+scale chain fuses into the consuming dot's operand read (batch-1
decode streams half the weight bytes, measured 99.3% argmax agreement on a
trained checkpoint — docs/PERFORMANCE.md 'Decoding').

Granularity: per-channel symmetric scales over every axis the consuming
einsum does NOT contract (``Model.param_fan_in``, recorded at init); sibling
depths of a block config share ONE scale (joint amax) so the
scan-over-layers replay resolves the same scale array under depth-0
canonical names — the measured quality is at :func:`quantize_variables`.
"""
from __future__ import annotations

import typing

import jax
import jax.numpy as jnp
import numpy as np

from .scope import depth0_name

# quantize only tensors with at least this many elements AND >= 2 dims:
# the big matmul weights are the bandwidth term; norms/biases/rezero
# scalars are noise (and most are accuracy-sensitive)
MIN_QUANT_SIZE = 1 << 16


def eligible(name: str, value, dims) -> bool:
    if np.ndim(value) < 2 or np.size(value) < MIN_QUANT_SIZE:
        return False
    # embeddings feed gathers (position embeddings) or the output logits
    # head; the logits matmul IS bandwidth-heavy but its quantization error
    # lands directly on the sampled distribution — keep full precision
    # (measured: the decode step is dominated by the body matvecs)
    return "embed" not in name


def _scale_axes(dims, fan_in_names, ndim: int) -> typing.Tuple[int, ...]:
    """Axes the amax reduces over — i.e. where a single scale must cover the
    whole axis.  A per-channel scale is only sound along axes the consuming
    einsum does NOT contract (it must commute out of the sum), so reduce
    exactly over the recorded fan-in (contracted) axes.  Fall back to
    everything-but-last when the fan-in record is missing or degenerate
    (keeps the scale array a negligible fraction of the weight)."""
    if dims and fan_in_names:
        contracted = tuple(i for i, d in enumerate(dims)
                           if d.name in fan_in_names)
        n_contracted = 1
        for i in contracted:
            n_contracted *= dims[i].size
        if contracted and n_contracted >= 64:
            return contracted
    # fallback: per-channel along the last axis only.  Finer schemes were
    # measured WORSE on a trained MoE checkpoint (docstring): per-(channel,
    # expert) scales on the 4-dim expert weights dropped teacher-forcing
    # agreement 91% → 85% despite being mathematically commutable — the
    # per-expert amax acts as mild smoothing the finer grid loses
    return tuple(range(ndim - 1))


def _scale_groups(variables: typing.Dict[str, typing.Any],
                  param_dims: typing.Optional[dict],
                  param_fan_in: typing.Optional[dict]
                  ) -> typing.Dict[str, typing.Tuple[list, tuple]]:
    """``{canonical name: ([member names], scale axes)}`` over the eligible
    weights — sibling depths of one block config share ONE group (joint
    amax): the scan-over-layers replay resolves every depth under the
    depth-0 canonical names, so per-depth scales would silently apply
    depth-0's channel pattern to all depths."""
    groups: typing.Dict[str, list] = {}
    for name, value in variables.items():
        dims = (param_dims or {}).get(name, ())
        if eligible(name, value, dims):
            groups.setdefault(depth0_name(name), []).append(name)
    out = {}
    for canon, names in groups.items():
        dims = (param_dims or {}).get(names[0], ())
        axes = _scale_axes(dims, (param_fan_in or {}).get(names[0], ()),
                           np.ndim(variables[names[0]]))
        out[canon] = (names, axes)
    return out


def _quantize_group(variables: typing.Dict[str, typing.Any],
                    names: typing.Sequence[str],
                    axes: typing.Tuple[int, ...]
                    ) -> typing.Tuple[typing.Dict[str, jax.Array],
                                      jax.Array]:
    """``({name: int8 weight}, shared scale)`` for ONE depth-shared group:
    joint amax over the group, ``amax/127`` symmetric scale, clip to ±127."""
    def _w(name):
        return jnp.asarray(variables[name], jnp.float32)

    amax = None
    for name in names:
        a = jnp.max(jnp.abs(_w(name)), axis=axes, keepdims=True)
        amax = a if amax is None else jnp.maximum(amax, a)
    scale = (jnp.maximum(amax, 1e-30) / 127.0).astype(jnp.float32)
    qdata = {name: jnp.clip(jnp.round(_w(name) / scale), -127,
                            127).astype(jnp.int8)
             for name in names}
    return qdata, scale


def quantize_variables(variables: typing.Dict[str, typing.Any],
                       param_dims: typing.Optional[dict] = None,
                       param_fan_in: typing.Optional[dict] = None
                       ) -> typing.Tuple[typing.Dict[str, jax.Array],
                                         typing.Dict[str, jax.Array]]:
    """(quantized variables, scales): eligible weights become int8 arrays
    with per-channel f32 scales such that ``w ≈ w_q * scale``; everything
    else passes through unchanged.  ``param_fan_in`` (Model.param_fan_in)
    names each weight's contracted dims so the scales can be per-channel
    over EVERY non-contracted axis — per-expert × per-column for MoE
    weights, not just per-last-axis.

    Measured on a TRAINED 1000-step checkpoint (the MoE mixer, loss 1.41
    on held-out text): per-tensor scales degrade teacher-forcing argmax
    agreement to 73% / loss +0.59; depth-shared per-channel scales measure
    **99.3% agreement with the loss unchanged to four decimals** — at
    2.31 → 1.38 ms/token decode (with int8 caches) at the flagship.  The
    scales dict carries each group's array under every member name AND the
    canonical name."""
    qvars: typing.Dict[str, jax.Array] = dict(variables)
    scales: typing.Dict[str, jax.Array] = {}
    for canon, (names, axes) in _scale_groups(variables, param_dims,
                                              param_fan_in).items():
        qdata, scale = _quantize_group(variables, names, axes)
        for name in names:
            qvars[name] = qdata[name]
            scales[name] = scale
        scales[canon] = scale
    return qvars, scales
