"""Compressed convolutional attention (layer ``cca``; Zyphra, arXiv:2510.04476,
as ZAYA1 runs it, arXiv:2511.17127).

The WHOLE attention lives in a latent narrower than the stream: ``q_heads``
query heads and ``kv_heads`` key / value heads of ``features_per_head`` each
(flags ``q_heads<n>-kv_heads<m>``; ZAYA1-8B: 8 x 128 = 1,024 and 2 x 128 =
256 on a stream of 2,048), and the output projection leads back.  On the
block's (normed) input ``x [s, features]``, ``x[-1] = 0``:

    q~ = x Wq [s, H, d];  k~ = x Wk [s, G, d]               (no bias)
    m_q[j] = (q~[j] + k~[j // (H / G)]) / 2                  the q-k mean, of
    m_k[g] = mean of m_q[j] over the query heads j of g      the UN-convolved
    c  = [q~ ; k~]  [s, (H + G) d]                           latents
    c1[t] = sum_i w0[i] * c[t - (K0 - 1) + i] + b0           depthwise, K0 taps
    c2[t] = sum_i W1[i] c1[t - (K1 - 1) + i] + b1            grouped by head:
                                                             H + G blocks d x d
                                                             a tap; no activation
    q = c2[: H d] + m_q;  k = c2[H d :] + m_k
    q = q / |q| sqrt(d);  k = k / |k| sqrt(d) tau[g]         over a head's d
                                                             features, float32
    rotary positions on both (``rotary_pct<p>``, ``theta<t>``)
    v = [x Wv1 ; x[t - 1] Wv2]  [s, G, d]                    the value shift:
                                                             the first half of
                                                             the K/V heads is
                                                             the current
                                                             token's, the second
                                                             the previous one's
    o = softmax(q k^T / sqrt(d), causal) v;  out = o Wo

Both convs are causal (zeros left of the sequence); ``K0`` / ``K1`` are the
configuration's ``cca_time0`` / ``cca_time1``.  Because ``|q| = sqrt(d)`` and
``|k| = sqrt(d) |tau|``, no attention logit passes ``sqrt(d) |tau|``: the
layer reports that bound (``hbnlp_cca_logit_scale_max``).

The flash kernel, ``rotary``, ``project`` and the K/V repeat are the standard
attention's (model/spatial.py).  XLA runs the convs: the depthwise one is
``K0`` shifted multiplies, the grouped one ``K1`` contractions ``[s, H + G,
d] x [H + G, d, d]``.  Training and full-sequence forward on one device only:
decode and prefill (a latent K/V cache, the convs' and the value shift's
one-token state) and a mesh refuse by name.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..config import BlockArgs
from ..core import scope
from ..core.dims import Dim
from ..core.tensor import NamedTensor, nt, transpose_to
from . import decode as decode_mod
from .backend import ConstantInit, NormalInit, normal_var
from .recurrent import _small_var, causal_depthwise_conv
from .declare import Layer, Stat
from .spatial import (causal_heads, flash_offer, numbered_flags, project,
                      rotary, rotary_width)

_NUMBERED = ("q_heads", "kv_heads", "rotary_pct", "theta")
#: added to a head's sum of squares before the root: ``F.normalize``'s
_NORM_EPS = 1e-12


def shift_tokens(x, steps: int = 1):
    """``y[t] = x[t - steps]`` along axis 1, zeros before the sequence."""
    if not steps:
        return x
    pad = [(0, 0)] * x.ndim
    pad[1] = (steps, 0)
    return jnp.pad(x, pad)[:, :x.shape[1]]


def causal_grouped_conv(x, weight, bias):
    """``y[t, g] = bias[g] + sum_i x[t - (K - 1) + i, g] @ weight[i, g]`` on
    ``x [b, s, groups, d]`` with ``weight [K, groups, d, d]``: a causal conv
    whose channels mix inside each group (head) only, ``K`` contractions of
    the sequence padded once on the left."""
    taps, s = weight.shape[0], x.shape[1]
    prefer = None if jax.default_backend() == "cpu" else jnp.float32
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0), (0, 0)))
    out = bias.astype(jnp.float32)
    for i in range(taps):
        out = out + jnp.einsum(
            "bsgi,gio->bsgo", padded[:, i:i + s], weight[i],
            preferred_element_type=prefer).astype(jnp.float32)
    return out.astype(x.dtype)


def unit_heads(x, scale):
    """``x / |x| * scale`` over the last axis, in float32."""
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.sum(jnp.square(xf), axis=-1, keepdims=True)
                        + _NORM_EPS)
    return (xf * inv * scale).astype(x.dtype)


def cca(args: BlockArgs) -> NamedTensor:
    """Layer ``cca`` (module docstring).  Parameters in creation order:
    ``Wq``, ``Wk``, ``Wv1``, ``Wv2`` normal(0.02); the depthwise conv's taps
    ``[K0, (H + G) d]`` and the grouped conv's ``[K1, H + G, d, d]``, normal
    with standard deviation ``fan_in ** -0.5`` (``K0``; ``K1 d``: torch's
    Conv1d scale, so that the conv branch and the q-k mean are of one order
    at initialisation), each followed by its bias (0); ``tau [G]`` = 1;
    ``Wo`` normal(0.02).  The small vectors are read in float32."""
    params = args.params
    flags = numbered_flags(args.name_extras, (), _NUMBERED, "layer cca")
    if "q_heads" not in flags:
        raise ValueError("layer cca needs its head counts: "
                         "cca-q_heads<n>-kv_heads<m>")
    ctx = scope.current()
    if ctx.decode is not None or decode_mod.prefill_active() is not None:
        raise NotImplementedError(
            "layer cca has no incremental decode / prefill form yet (a "
            "latent K/V cache, the convs' and the value shift's one-token "
            "state)")
    if ctx.mesh is not None and ctx.mesh.size > 1:
        raise NotImplementedError("layer cca on a mesh")
    dim = params.sequence_dim
    heads, kv_heads = flags["q_heads"], flags["kv_heads"]
    group = heads // kv_heads
    if kv_heads % 2:
        raise ValueError(f"layer cca: the value shift splits kv_heads"
                         f"{kv_heads} in two halves")
    key_dim = params.key_dim
    d = key_dim.size
    feats = list(params.feature_dims)
    q_feats = [Dim("q_heads", heads), key_dim]
    kv_feats = [Dim("kv_heads", kv_heads), key_dim]
    half_feats = [Dim("kv_heads", kv_heads // 2), key_dim]
    lead_dims = [dm for dm in args.tensor.dims if dm not in [dim] + feats]
    if dim not in args.tensor.dims or len(lead_dims) != 1:
        raise ValueError("layer cca mixes [batch, sequence, features]; got "
                         f"{args.tensor.dims}")
    lead = math.prod(dm.size for dm in lead_dims)
    s = dim.size

    def flat(x: NamedTensor, new) -> jax.Array:
        return transpose_to(x, lead_dims + [dim] + new).data.reshape(
            lead, s, new[0].size, d)

    with jax.named_scope("in_proj"):
        q_lat = flat(project(args, args.tensor, q_feats, feats), q_feats)
        k_lat = flat(project(args, args.tensor, kv_feats, feats), kv_feats)
        v_now = flat(project(args, args.tensor, half_feats, feats),
                     half_feats)
        v_prev = flat(project(args, args.tensor, half_feats, feats),
                      half_feats)
    k0, k1 = params.cca_time0, params.cca_time1
    packed = heads + kv_heads
    channels = Dim("cca_latent", packed * d)
    w0 = _small_var(args, "normal_var", [Dim("cca_time0", k0), channels],
                    NormalInit(k0 ** -0.5))
    b0 = _small_var(args, "constant_var", [channels], ConstantInit(0.0))
    w1 = normal_var(args, [Dim("cca_time1", k1), Dim("cca_heads", packed),
                           Dim("_cca_in", d), key_dim],
                    stddev=(k1 * d) ** -0.5).data
    b1 = _small_var(args, "constant_var", [Dim("cca_heads", packed), key_dim],
                    ConstantInit(0.0))
    tau = _small_var(args, "constant_var", [Dim("kv_heads", kv_heads)],
                     ConstantInit(1.0))

    with jax.named_scope("qk_mean"):
        grouped_q = q_lat.reshape(lead, s, kv_heads, group, d)
        mean_q = (grouped_q + k_lat[:, :, :, None, :]) * 0.5
        mean_k = jnp.mean(mean_q.astype(jnp.float32), axis=3
                          ).astype(k_lat.dtype)
        mean_q = mean_q.reshape(lead, s, heads, d)
    with jax.named_scope("conv"):
        latent = jnp.concatenate([q_lat, k_lat], axis=2)
        # recurrent.py's shifted multiplies: float32 taps on the latent
        mixed = causal_depthwise_conv(
            latent.reshape(lead, s, packed * d), w0, b0)
        mixed = causal_grouped_conv(
            mixed.astype(latent.dtype).reshape(lead, s, packed, d), w1, b1)
        q = mixed[:, :, :heads] + mean_q
        k = mixed[:, :, heads:] + mean_k
    with jax.named_scope("qk_norm"):
        root = math.sqrt(d)
        q = unit_heads(q, root)
        k = unit_heads(k, root * tau[:, None])
    if ctx.layer_stats is not None:
        ctx.layer_stats.append(
            {"cca_logit_scale": root * jnp.max(jnp.abs(tau))})
    with jax.named_scope("rope"):
        theta = float(flags.get("theta", params.rope_theta))
        width = rotary_width(d, flags.get("rotary_pct"))
        q = rotary(q, theta, width)
        k = rotary(k, theta, width)
    with jax.named_scope("value_shift"):
        v = jnp.concatenate([v_now, shift_tokens(v_prev)], axis=2)
    out = causal_heads(ctx, params, q, k, v, group, d ** -0.5)
    canonical = lead_dims + [dim] + q_feats
    out_nt = nt(out.reshape([dm.size for dm in canonical]), canonical)
    with jax.named_scope("out_proj"):
        return project(args, transpose_to(
            out_nt, [dm for dm in args.tensor.dims if dm not in feats]
            + q_feats), feats, q_feats,
            stddev=params.residual_out_stddev or 0.02)


def _offer(params, extras):
    """The one causal flash call over ``q_heads`` latent heads, no window."""
    return flash_offer(
        params, numbered_flags(extras, (), _NUMBERED, "layer cca")["q_heads"])


cca.declares = Layer(
    stats=(Stat("cca_logit_scale_max", "gauge", "hbnlp_cca_logit_scale_max",
                "largest sqrt(features_per_head) * |tau| over the cca layers "
                "of the newest finished step: q and k have unit direction, so "
                "no attention logit passes it", "max", "cca_logit_scale"),),
    offer=_offer)
