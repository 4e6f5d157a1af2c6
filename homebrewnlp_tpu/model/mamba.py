"""Mamba-2 mixer (layer ``mamba``): a selective state space recurrence in its
chunked state-space-duality form (Dao & Gu, arXiv:2405.21060, section 6 and
listing 1).

On the block's (already normalised) input ``u [b, s, features]``, with
``d_inner = mamba_heads x mamba_head_features``, ``g = mamba_groups`` groups
of ``B`` / ``C`` (head ``j`` reads group ``G(j) = j // (heads / g)``; 1 = one
group shared by all heads, Granite's), state size ``n = mamba_state``:

    z, xBC, dt = split(u W_in)            W_in: features x (2 d_inner + 2 g n
                                          + heads), no bias
    xBC = silu(conv(xBC))                 causal depthwise conv over the
                                          sequence, width mamba_conv_size,
                                          with bias: one Pallas kernel pair
                                          where parallel/causal_conv.py
                                          ``kernel_applies``, else K shifted
                                          multiplies in XLA
    x, B, C = split(xBC)                  d_inner, g n, g n
    dt = softplus(dt + dt_bias);  A = -exp(A_log)          per head, float32
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_{t,G}^T         S: [width, n]
    y_t = S_t C_{t,G} + D x_t
    y = rms(y * silu(z)) * w_norm         gate FIRST, then RMSNorm over each
                                          group's d_inner / g columns
                                          (g = 1: all of them), eps 1e-5
    out = y W_out                         d_inner x features, no bias

The recurrence is never run position by position: inside a chunk of
``mamba_chunk`` positions it is the masked ``C B^T`` product against ``x``
(``intra_chunk``), every chunk leaves one state (``chunk_states``), the
states are carried across the chunks in order (``inter_chunk``), and
the state entering a chunk adds its part to that chunk's outputs
(``state_out``).  Decays are ``exp`` of DIFFERENCES of a float32 cumulative
sum of ``dt A`` within the chunk — masked before the ``exp``, never a product
or quotient of exponentials, so nothing under- or overflows on the way —
and matmul operands are the calculation dtype with float32 accumulation.
Two implementations of that one arithmetic (``ssd``): on a TPU at whole
tiles (``parallel/ssd_scan.py ssd_kernel_applies``) a Pallas kernel pair
walks the chunks with the decay matrices and the carried state in VMEM,
forward and hand-written backward (PR 48); elsewhere ``ssd_xla`` runs the
four steps as XLA einsums and a ``lax.scan`` and autodiff gives the backward
— the CPU's path and the kernels' oracle.

Under the ``checkpoint`` strategy the layer offers the memory-for-recompute
rule (model/remat.py, kind ``recurrent``) the in-projection's output ``u
W_in`` under one name (``SAVED_NAMES``, ``_offer``): where the rule admits
it, the block's replay runs no in-projection matmul and everything after it
again from the saved value.

Training and full-sequence forward on one device; a decode / prefill form
(a state and a conv window per sequence) is ROADMAP R3's serving half.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..config import BlockArgs, ModelParameter
from ..core import scope
from ..core.dims import Dim
from ..core.tensor import NamedTensor, nt, transpose_to
from ..parallel.causal_conv import causal_conv_silu, kernel_applies
from ..parallel.ssd_scan import log_decay, ssd_kernel_applies, ssd_scan
from .backend import ConstantInit, UniformInit, normal_var
from .loss import _matmul
from .normalization import _norm_core
from .declare import Layer, Offer, Stat
from .recurrent import (FACTS, Recurrent, _inverse_softplus_of_exp,
                        _small_var, causal_depthwise_conv, token_layout)
from .utils import anonymize_dim


#: the name layer ``mamba`` gives its in-projection's output ``proj [b, s,
#: 2 d_inner + 2 g n + heads]`` (``checkpoint_name``; free where no policy
#: names it), before the ``z`` / ``xBC`` / ``dt`` slices and before the conv
#: kernel that reads its channels in place.  Under the ``checkpoint`` strategy
#: the block's ``jax.checkpoint`` saves it where model/remat.py's
#: ``recurrent`` kind rides (model/blocks.py ``_checkpoint_policy``): the
#: block's replay then runs no in-projection matmul — the layer's largest, a
#: quarter of its forward — and the conv, the scan, the gate norm and the
#: out-projection replay from the saved ``proj``.  ``W_in``'s own backward
#: wants the block's normed input, which the replay still makes, and
#: ``d proj``.
SAVED_NAMES = ("mamba_in_proj",)


def ssd(x, dt, a, b_mat, c_mat, chunk: int):
    """The chunked scan.  ``x [b, s, h, p]`` (calculation dtype), ``dt [b,
    s, h]`` and ``a [h]`` float32 (``a`` negative), ``b_mat`` / ``c_mat``
    ``[b, s, n]`` (one group) or ``[b, s, groups, n]``; ``s`` a multiple of
    ``chunk``.  Returns ``(y [b, s, h,
    p]`` in float32 WITHOUT the ``D x`` skip, the most negative within-chunk
    cumulative ``dt a``)``: the Pallas pair of ``parallel/ssd_scan.py`` where
    ``ssd_kernel_applies``, else ``ssd_xla``."""
    _, s, h, p = x.shape
    groups = b_mat.shape[2] if b_mat.ndim == 4 else 1
    if not ssd_kernel_applies(s, chunk, h, p, b_mat.shape[-1], groups=groups):
        return ssd_xla(x, dt, a, b_mat, c_mat, chunk)
    a_cum = log_decay(dt, a, chunk)
    return ssd_scan(x, dt, a_cum, b_mat, c_mat, chunk), jnp.min(a_cum)


def ssd_xla(x, dt, a, b_mat, c_mat, chunk: int):
    """``ssd`` as XLA's einsums and a ``lax.scan`` over the chunk states,
    autodiff its backward: the path off the TPU and at shapes the kernels
    decline, and their oracle.  Groups of ``B`` / ``C`` are one ``vmap`` of
    the one-group form over each group with its heads."""
    bsz, s, h, p = x.shape
    if b_mat.ndim == 4:
        g = b_mat.shape[2]
        y, lowest = jax.vmap(
            functools.partial(ssd_xla, chunk=chunk), (2, 2, 0, 2, 2), (2, 0))(
            x.reshape(bsz, s, g, h // g, p), dt.reshape(bsz, s, g, h // g),
            a.reshape(g, h // g), b_mat, c_mat)
        return y.reshape(x.shape), jnp.min(lowest)
    n = b_mat.shape[-1]
    c, l = s // chunk, chunk
    dtype = x.dtype
    xc = x.reshape(bsz, c, l, h, p)
    bc = b_mat.reshape(bsz, c, l, n)
    cc = c_mat.reshape(bsz, c, l, n)
    dtc = dt.reshape(bsz, c, l, h)
    # log-decay from the chunk's start to each position, [b, c, h, l]
    a_cum = jnp.cumsum(jnp.moveaxis(dtc * a, 3, 2), axis=-1)
    x_dt = (xc.astype(jnp.float32) * dtc[..., None])
    with jax.named_scope("intra_chunk"):
        scores = _matmul("bcin,bcjn->bcij", cc, bc).astype(jnp.float32)
        diff = a_cum[..., :, None] - a_cum[..., None, :]
        causal = jnp.arange(l)[:, None] >= jnp.arange(l)[None, :]
        decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
        mixed = (scores[:, :, None] * decay).astype(dtype)
        y = _matmul("bchij,bcjhp->bcihp", mixed, x_dt.astype(dtype)
                    ).astype(jnp.float32)
    with jax.named_scope("chunk_states"):
        # what each position still contributes at its chunk's end
        to_end = jnp.exp(a_cum[..., -1:] - a_cum)
        weighted = (x_dt * jnp.moveaxis(to_end, 2, 3)[..., None]).astype(dtype)
        states = _matmul("bclhp,bcln->bchpn", weighted, bc
                         ).astype(jnp.float32)
    with jax.named_scope("inter_chunk"):
        chunk_decay = jnp.exp(a_cum[..., -1])                  # [b, c, h]

        def step(carry, inp):
            decay_c, states_c = inp
            return carry * decay_c[..., None, None] + states_c, carry

        _, entering = jax.lax.scan(
            step, jnp.zeros((bsz, h, p, n), jnp.float32),
            (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(states, 1, 0)))
        entering = jnp.moveaxis(entering, 0, 1)                # [b, c, h, p, n]
    with jax.named_scope("state_out"):
        from_start = jnp.moveaxis(jnp.exp(a_cum), 2, 3)        # [b, c, l, h]
        y = y + _matmul("bcln,bchpn->bclhp", cc, entering.astype(dtype)
                        ).astype(jnp.float32) * from_start[..., None]
    return y.reshape(bsz, s, h, p), jnp.min(a_cum)


def mamba(args: BlockArgs) -> NamedTensor:
    """Layer ``mamba`` (module docstring).  Parameters in creation order:
    ``W_in`` normal(0.02); the conv's weight ``[K, channels]`` and bias,
    U(-1/sqrt(K), 1/sqrt(K)) (torch's Conv1d default, as the Mamba-2 code
    leaves it); ``dt_bias`` with ``softplus`` log-uniform in [1e-3, 1e-1],
    ``A_log = log U[1, 16]``, ``D = 1``, the norm's scale 1; ``W_out``
    normal(``residual_out_stddev`` or 0.02): it writes into the stream."""
    params = args.params
    ctx = scope.current()
    token_dims, bsz, s, chunk = token_layout(args, "mamba",
                                             params.mamba_chunk)
    h, p, n = params.mamba_heads, params.mamba_head_features, params.mamba_state
    k, g = params.mamba_conv_size, params.mamba_groups
    d_inner, conv_dim = h * p, h * p + 2 * g * n
    feats = list(params.feature_dims)
    anon = [anonymize_dim(d) for d in feats]
    x = args.tensor
    f_sz = math.prod(d.size for d in feats)
    inner, channels = Dim("mamba_inner", d_inner), Dim("mamba_conv", conv_dim)
    head_dim = Dim("mamba_heads", h)

    w_in = normal_var(args, anon + [Dim("mamba_in", d_inner + conv_dim + h)])
    bound = k ** -0.5
    conv_w = _small_var(args, "uniform_var", [Dim("mamba_conv_k", k), channels],
                        UniformInit(-bound, bound))
    conv_b = _small_var(args, "uniform_var", [channels],
                        UniformInit(-bound, bound))
    dt_bias = _small_var(args, "uniform_var", [head_dim], UniformInit(
        math.log(1e-3), math.log(1e-1), _inverse_softplus_of_exp))
    a_log = _small_var(args, "uniform_var", [head_dim],
                       UniformInit(1.0, 16.0, np.log))
    skip = _small_var(args, "constant_var", [head_dim], ConstantInit(1.0))
    w_norm = _small_var(args, "constant_var", [inner], ConstantInit(1.0))

    dtype = x.dtype
    u = transpose_to(x, token_dims + feats).data.reshape(bsz, s, f_sz)
    with jax.named_scope("in_proj"):
        proj = checkpoint_name(
            _matmul("bsf,fo->bso", u, w_in.data.reshape(f_sz, -1)
                    ).astype(dtype), SAVED_NAMES[0])
        z = proj[..., :d_inner]
        xbc = proj[..., d_inner:d_inner + conv_dim]
        dt = proj[..., d_inner + conv_dim:]
    with jax.named_scope("conv"):
        if kernel_applies(conv_dim, s, k, d_inner):
            # its channels read in place out of proj: no copy of the slice
            xbc = causal_conv_silu(proj, conv_w, conv_b, d_inner)
        else:
            xbc = jax.nn.silu(causal_depthwise_conv(
                xbc.astype(jnp.float32), conv_w, conv_b)).astype(dtype)
    with jax.named_scope("ssd"):
        xs = xbc[..., :d_inner].reshape(bsz, s, h, p)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
        a = -jnp.exp(a_log)
        b_mat = xbc[..., d_inner:d_inner + g * n]
        c_mat = xbc[..., d_inner + g * n:]
        if g > 1:
            b_mat, c_mat = (m.reshape(bsz, s, g, n) for m in (b_mat, c_mat))
        y, log_decay_min = ssd(xs, dt, a, b_mat, c_mat, chunk)
        y = y + xs.astype(jnp.float32) * skip[:, None]
    if ctx.layer_stats is not None:
        ctx.layer_stats.append({"ssd_log_decay_min": log_decay_min})
    with jax.named_scope("gate_norm"):
        gated = (y.reshape(bsz, s, d_inner)
                 * jax.nn.silu(z.astype(jnp.float32))).astype(dtype)
        # each group's columns normalised apart: [b, s, g, d_inner / g]
        grouped = (g, d_inner // g) if g > 1 else (d_inner,)
        gated = _norm_core(gated.reshape((bsz, s) + grouped),
                           w_norm.reshape((1, 1) + grouped),
                           jnp.ones((1,) * (2 + len(grouped)), jnp.float32),
                           (1 + len(grouped),), 1e-5, True, False, False
                           ).reshape(bsz, s, d_inner)
    w_out = normal_var(args, [inner] + feats,
                       stddev=params.residual_out_stddev or 0.02)
    with jax.named_scope("out_proj"):
        out = _matmul("bsi,if->bsf", gated, w_out.data.reshape(d_inner, f_sz)
                      ).astype(dtype)
    out = out.reshape([d.size for d in token_dims + feats])
    return transpose_to(nt(out, token_dims + feats), x.dims)


def _state_bytes(params: ModelParameter) -> int:
    """``[batch, sequence / mamba_chunk, mamba_heads, mamba_head_features,
    mamba_state]`` float32: what the inter-chunk scan's backward reads."""
    return params.batch_dim.size \
        * max(1, params.sequence_dim.size // params.mamba_chunk) \
        * params.mamba_heads * params.mamba_head_features \
        * params.mamba_state * 4


def _conv(params: ModelParameter):
    inner = params.mamba_heads * params.mamba_head_features
    return inner + 2 * params.mamba_groups * params.mamba_state, \
        params.mamba_conv_size, inner


def _scan(params: ModelParameter):
    s = params.sequence_dim.size
    return (s, min(params.mamba_chunk, s), params.mamba_heads,
            params.mamba_head_features, params.mamba_state,
            params.mamba_groups)


def _offer(params: ModelParameter, extras) -> Offer:
    """The in-projection's output ``[batch, sequence, 2 d_inner + 2 groups x
    state + heads]`` in the calculation dtype: ``SAVED_NAMES``.  No interior:
    the scan's ``y`` and entering states buy a fifth of what these bytes do
    (ROADMAP S9b(3))."""
    channels, _, inner = _conv(params)
    return Offer("recurrent", SAVED_NAMES,
                 params.batch_dim.size * params.sequence_dim.size
                 * (inner + channels + params.mamba_heads)
                 * jnp.dtype(params.calculation_dtype).itemsize)


mamba.declares = Layer(
    stats=(Stat("ssd_log_decay_min", "gauge", "hbnlp_ssd_log_decay_min",
                "most negative within-chunk cumulative dt * A of the newest "
                "finished step, all mamba layers: exp of it is the smallest "
                "decay the chunked scan formed", "min"),),
    offer=_offer, facts=FACTS,
    recurrent=Recurrent(_state_bytes, _conv, scan=_scan))
