"""Gated delta-rule linear attention (layer ``gated_delta``): Gated DeltaNet
(Yang, Kautz & Hatamizadeh, arXiv:2412.06464) as flash-linear-attention's
``GatedDeltaNet`` and HF ``modeling_olmo_hybrid`` run it, in its chunked WY /
UT form.

On the block's input ``u [b, s, features]``, ``H = delta_heads`` heads of
``d_k = delta_key_features`` and ``d_v = delta_value_features``:

    q | k | v = u W_qkv                   H d_k, H d_k, H d_v columns
    z = u W_gate;  b | a = u W_ba         H d_v; H, H columns.  Three
                                          bias-free matrices (HF: six; the
                                          same distribution), each its own
                                          matmul: slices of one wide
                                          projection would each pad their
                                          cotangent back to its width
    q, k, v = silu(conv(q | k | v))       causal depthwise conv over the
                                          sequence, delta_conv_size taps, no
                                          bias: the Pallas pair of
                                          parallel/causal_conv.py where
                                          ``kernel_applies``, else K shifted
                                          multiplies in XLA
    q~ = q rsqrt(|q|^2 + 1e-6) d_k^-1/2   per head, float32 (FLA's l2norm:
    k~ = k rsqrt(|k|^2 + 1e-6)            the eps inside the root)
    beta = 2 sigmoid(b)                   2 under delta_allow_neg_eigval, else
                                          1: the write strength, per head
    g = -exp(A_log) softplus(a + dt_bias) float32 log-decay, per head
    S_t = exp(g_t) S_{t-1} (I - beta_t k~_t k~_t^T) + beta_t v_t k~_t^T
                                          S: [d_v, d_k] a head, S_0 = 0
    o_t = S_t q~_t
    y = rms(o) * w_norm * silu(z)         RMSNorm over d_v per head BEFORE the
                                          gate, one scale of d_v entries for
                                          all heads, eps ``norm_epsilon``
    out = y W_out                         H d_v x features, no bias

The recurrence is never run position by position (``delta_rule``): inside a
chunk of ``delta_chunk`` positions, with ``gamma`` the float32 cumulative sum
of ``g`` and ``Gamma_ij = exp(gamma_i - gamma_j)`` (``decay``: differences,
masked BEFORE the ``exp``; never ``exp(-gamma)``),

    T = (I + strict_tril(diag(beta) (K~ K~^T o Gamma)))^-1 diag(beta)
    W = T (K~ o exp(gamma)),  U = T V

(``solve``), and per chunk with the state ``S`` that enters it

    V' = U - W S^T
    O  = (Q~ o exp(gamma)) S^T + (Q~ K~^T o Gamma o causal) V'
    S <- exp(gamma_C) S + V'^T (K~ o exp(gamma_C - gamma))

where a serial ``lax.scan`` over the chunks carries ``S`` in float32 and
hands out ``V'`` and the entering states in the calculation dtype, as the
matmuls take them (``inter_chunk``); the products that need no state
(``intra_chunk``) and ``O`` (``state_out``) are batched over all chunks.

The unit lower triangular system (``_inverse_unit_lower``, a
``jax.custom_vjp`` whose only residual is the inverse) is solved by the
Pallas pair of parallel/delta_solve.py where ``solve_kernel_applies`` (a TPU,
a power-of-two chunk from 16 to 128, whole tiles of 128 systems a group of
heads): plain substitution in exact float32 on the VPU with the system's
index on the lanes, every level in VMEM, and the inverse's own backward ``-X^T
dX X^T`` as two ``highest`` matmuls in one kernel.  Elsewhere — the CPU, odd
chunks, and as the kernels' oracle — XLA runs blocked forward substitution
with a doubling block (``_blocked_inverse``): twelve 64 x 64 matmuls a chunk
a head at chunk 64, in float32 at ``highest`` precision, no serial loop, and
two more for the backward.  (The product ``(I -
N)(I + N^2)(I + N^4) ..`` of the nilpotent ``N`` needs as many matmuls and
is NOT used: with correlated keys the powers of ``N`` grow to 1e7 and beyond
before they cancel, and float32 returns garbage.)  Decays, cumulative
sums, ``beta``, the solve and the carried state are float32; the other
matmuls take the calculation dtype with float32 accumulation.

Two implementations of that one arithmetic, chosen by
parallel/delta_rule.py's ``rule_kernel_applies`` on the backend and the
shapes (no knob).  On a TPU at a chunk the solve's kernel takes, whole lane
tiles of positions and head widths in whole sublane tiles (``kernel_rule``):
all heads at once.  The solve's input ``strict_tril(diag(beta) (K~ K~^T o
Gamma))`` is the Pallas pair ``delta_strict_fwd`` / ``delta_strict_bwd``,
``_inverse_unit_lower`` is called ONCE a layer, XLA keeps ``gamma``'s
cumulative sum, ``T = X diag(beta)`` and ``max|T|``, and everything that
touches the state is the Pallas pair ``delta_rule_fwd`` / ``delta_rule_bwd``
under one ``jax.custom_vjp``: the float32 state of ALL heads stays in VMEM
along a sequential walk over the chunks, the backward is one reverse walk
with ``dS`` carried the same way, and its residuals are the inputs, ``T`` and
the states entering every chunk in the calculation dtype
(``hbnlp_ssd_state_bytes``).  Nothing shaped ``[.., l, l]`` but ``strict``,
the inverse, ``T`` and their cotangents reaches HBM.
Elsewhere — the CPU, odd chunks, toy widths, and as the kernels' oracle —
autodiff gives the backward of the XLA form above, a group of heads at a time
(``grouped_rule``): what it keeps of the rule over all heads at once does not
fit a chip at 16,384 tokens, so each group is rematerialised in its own
backward.

How often the rule runs forward in a step under the ``checkpoint`` strategy:
TWICE on either path, backward once.  The block's ``jax.checkpoint`` saves
the rule's output (``SAVED_NAMES``; model/remat.py's ``recurrent`` kind, 189
MB a layer at 16,384 tokens), so the gate norm and the out-projection
differentiate through the saved ``o``; the second forward is the block's
replay on the kernel path (the three forward kernels run again, for ``T`` and
the entering states the backward reads: neither is offered to the
``recurrent`` kind — 1.0 GB more over the cell's three layers at the step's
peak, which is the attention layer's backward), the group's own
re-materialisation on
the XLA path.  Where the kind does not ride, the XLA path runs forward three
times: the block's replay once more, for nothing but ``o``.

Training and full-sequence forward on one device; a decode / prefill form (a
state and a conv window per sequence) is a later issue.
"""
from __future__ import annotations

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
from ..parallel.delta_rule import (delta_rule_pair, delta_strict,
                                   rule_kernel_applies)
from ..parallel.delta_solve import (inverse_unit_lower, inverse_unit_lower_bwd,
                                    solve_kernel_applies)
from .backend import ConstantInit, UniformInit, normal_var
from .loss import _matmul
from .normalization import _norm_core
from .declare import Layer, Offer, Stat
from .recurrent import (FACTS, Recurrent, _inverse_softplus_of_exp,
                        _small_var, causal_depthwise_conv, token_layout)
from .utils import anonymize_dim

L2_EPS = 1e-6
#: the most bytes of one ``[b, chunks, heads, chunk, chunk]`` float32 matrix a
#: group of heads may have (``grouped_rule``): 10 of 30 heads at 16,384 tokens
GROUP_BYTES = 48 << 20
#: the name layer ``gated_delta`` gives the rule's output ``o [b, s, heads,
#: d_v]`` (``checkpoint_name``; free where no policy names it).  Under the
#: ``checkpoint`` strategy the block's ``jax.checkpoint`` saves it where
#: model/remat.py's ``recurrent`` kind rides (model/blocks.py
#: ``_checkpoint_policy``): the block's replay then runs no forward of the
#: rule on the XLA path — the gate norm and the out-projection differentiate
#: through the saved ``o``, and the rule's own backward makes everything it
#: needs again from ``q, k, v, beta, g`` inside ``grouped_rule``'s
#: ``jax.checkpoint`` — and, on the kernel path, the forward kernels for the
#: pair's residuals alone.  ``transform_max`` is not named: the replay
#: needs no statistic.
SAVED_NAMES = ("gated_delta_out",)


def _dot(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _blocked_inverse(strict):
    """``(I + strict)^-1`` by blocked forward substitution, doubling the
    block — XLA's form: with ``D`` the inverse of the diagonal blocks of
    width ``n`` (``n = 1``: the identity) and ``L`` the part of ``strict``
    that joins two neighbouring blocks into one of width ``2 n``, ``[[A, 0],
    [C, B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1, B^-1]]`` is ``D - D L D`` for all
    blocks at once."""
    l = strict.shape[-1]
    size = 1 << max(0, l - 1).bit_length()
    if size != l:           # the inverse of the leading block is unchanged
        pad = [(0, 0)] * (strict.ndim - 2) + [(0, size - l)] * 2
        strict = jnp.pad(strict, pad)
    row, col = jnp.arange(size)[:, None], jnp.arange(size)[None, :]
    inv = jnp.broadcast_to(jnp.eye(size, dtype=strict.dtype), strict.shape)
    n = 1
    while n < size:
        joins = (row // (2 * n) == col // (2 * n)) & (row // n > col // n)
        inv = inv - _dot(inv, _dot(jnp.where(joins, strict, 0.0), inv))
        n *= 2
    return inv[..., :l, :l]


def _takes_kernel(t) -> bool:
    return solve_kernel_applies(t.shape[-1], math.prod(t.shape[:-2]))


@jax.custom_vjp
def _inverse_unit_lower(strict):
    """``(I + strict)^-1`` for strictly lower triangular ``strict [..., l,
    l]`` (float32): the Pallas pair of parallel/delta_solve.py where
    ``solve_kernel_applies``, else ``_blocked_inverse`` (the CPU, odd chunks;
    the kernels' oracle).  The backward is the inverse's own, ``d strict =
    -X^T dX X^T`` below the diagonal: two matmuls from ``X`` alone, no
    level's intermediate kept."""
    if _takes_kernel(strict):
        return inverse_unit_lower(strict)
    return _blocked_inverse(strict)


def _inverse_fwd(strict):
    inv = _inverse_unit_lower(strict)
    return inv, inv


def _xla_inverse_bwd(inv, g):
    """``-X^T dX X^T`` below the diagonal, as two ``highest`` matmuls."""
    inv_t = jnp.swapaxes(inv, -1, -2)
    l = inv.shape[-1]
    below = jnp.arange(l)[:, None] > jnp.arange(l)[None, :]
    return jnp.where(below, -_dot(inv_t, _dot(g, inv_t)), 0.0)


def _inverse_bwd(inv, g):
    if _takes_kernel(inv):
        return (inverse_unit_lower_bwd(inv, g),)
    return (_xla_inverse_bwd(inv, g),)


_inverse_unit_lower.defvjp(_inverse_fwd, _inverse_bwd)


def _chunk_terms(qc, kc, vc, beta, g):
    """What the rule needs of every chunk before any state: ``qc`` / ``kc
    [b, c, l, h, d_k]``, ``vc [b, c, l, h, d_v]``, ``beta`` / ``g [b, c, h,
    l]``.  Returns ``W``, ``U``, ``Q K^T o Gamma`` (causal), ``K o
    exp(gamma_C - gamma)``, ``Q o exp(gamma)`` in the calculation dtype,
    ``exp(gamma_C) [b, c, h]`` and the largest magnitude in ``T``."""
    dtype, l = qc.dtype, qc.shape[2]
    with jax.named_scope("decay"):
        # log-decay from the chunk's start to each position, [b, c, h, l]
        gamma = jnp.cumsum(g, axis=-1)
        diff = gamma[..., :, None] - gamma[..., None, :]
        row, col = jnp.arange(l)[:, None], jnp.arange(l)[None, :]
        decay = jnp.exp(jnp.where(row >= col, diff, -jnp.inf))
        from_start = jnp.moveaxis(jnp.exp(gamma), 2, 3)[..., None]
        to_end = jnp.moveaxis(jnp.exp(gamma[..., -1:] - gamma), 2, 3
                              )[..., None]                     # [b, c, l, h, 1]
        chunk_decay = jnp.exp(gamma[..., -1])                  # [b, c, h]
    with jax.named_scope("solve"):
        kk = _matmul("bcihd,bcjhd->bchij", kc, kc).astype(jnp.float32)
        strict = jnp.where(row > col, kk * decay, 0.0) * beta[..., :, None]
        transform = _inverse_unit_lower(strict) * beta[..., None, :]
        transform_max = jnp.max(jnp.abs(transform))
    with jax.named_scope("intra_chunk"):
        t_low = transform.astype(dtype)
        k_start = (kc.astype(jnp.float32) * from_start).astype(dtype)
        w = _matmul("bchij,bcjhd->bcihd", t_low, k_start).astype(dtype)
        u = _matmul("bchij,bcjhd->bcihd", t_low, vc).astype(dtype)
        qk = _matmul("bcihd,bcjhd->bchij", qc, kc).astype(jnp.float32)
        mixed = (qk * decay).astype(dtype)
        k_end = (kc.astype(jnp.float32) * to_end).astype(dtype)
        q_start = (qc.astype(jnp.float32) * from_start).astype(dtype)
    return w, u, mixed, k_end, q_start, chunk_decay, transform_max


def delta_rule(q, k, v, beta, g, chunk: int):
    """The chunked gated delta rule.  ``q`` / ``k [b, s, h, d_k]``
    (normalised, calculation dtype), ``v [b, s, h, d_v]``, ``beta`` and ``g
    [b, s, h]`` float32 (``g <= 0``); ``s`` a multiple of ``chunk``.  Returns
    ``(o [b, s, h, d_v]`` in the calculation dtype, the largest magnitude in
    any chunk's solved transform ``T)``."""
    bsz, s, h, dk = q.shape
    dv = v.shape[-1]
    c, l = s // chunk, chunk
    dtype = q.dtype
    w, u, mixed, k_end, q_start, chunk_decay, transform_max = _chunk_terms(
        q.reshape(bsz, c, l, h, dk), k.reshape(bsz, c, l, h, dk),
        v.reshape(bsz, c, l, h, dv),
        jnp.moveaxis(beta.reshape(bsz, c, l, h), 2, 3),
        jnp.moveaxis(g.reshape(bsz, c, l, h), 2, 3))
    with jax.named_scope("inter_chunk"):
        def step(state, inp):
            w_c, u_c, k_end_c, decay_c = inp
            entering = state.astype(dtype)
            v_new = (u_c.astype(jnp.float32) - _matmul(
                "blhk,bhvk->blhv", w_c, entering).astype(jnp.float32)
            ).astype(dtype)
            left = state * decay_c[..., None, None] + _matmul(
                "blhv,blhk->bhvk", v_new, k_end_c).astype(jnp.float32)
            return left, (entering, v_new)

        _, (entering, v_new) = jax.lax.scan(
            step, jnp.zeros((bsz, h, dv, dk), jnp.float32),
            tuple(jnp.moveaxis(t, 1, 0)
                  for t in (w, u, k_end, chunk_decay)))
        entering = jnp.moveaxis(entering, 0, 1)        # [b, c, h, d_v, d_k]
        v_new = jnp.moveaxis(v_new, 0, 1)              # [b, c, l, h, d_v]
    with jax.named_scope("state_out"):
        o = _matmul("bcihk,bchvk->bcihv", q_start, entering
                    ).astype(jnp.float32) \
            + _matmul("bchij,bcjhv->bcihv", mixed, v_new).astype(jnp.float32)
    return o.astype(dtype).reshape(bsz, s, h, dv), transform_max


def _group_heads(bsz: int, s: int, h: int, chunk: int) -> int:
    per_head = bsz * s * chunk * 4
    return max(d for d in range(1, h + 1)
               if h % d == 0 and (d * per_head <= GROUP_BYTES or d == 1))


def over_groups(rule, group: int, q, k, v, beta, g):
    """``rule(q, k, v, beta, g) -> (o, *statistics)`` over groups of
    ``group`` heads (a divisor of all), one after another (``lax.map``),
    each group rematerialised in the backward (``jax.checkpoint``).  Returns
    ``o`` over all heads and every statistic a group, ``[groups]``.  The
    group's backward needs nothing of an enclosing forward but ``q, k, v,
    beta, g``: an enclosing ``jax.checkpoint`` that saves the output
    replays none of this.  Layer ``kda`` runs its rule through it too."""
    h = q.shape[2]

    def split(t):
        return jnp.moveaxis(t.reshape(t.shape[:2] + (h // group, group)
                                      + t.shape[3:]), 2, 0)

    o, *statistics = jax.lax.map(
        jax.checkpoint(lambda heads: rule(*heads), prevent_cse=False),
        tuple(split(t) for t in (q, k, v, beta, g)))
    return (jnp.moveaxis(o, 0, 2).reshape(v.shape), *statistics)


def grouped_rule(q, k, v, beta, g, chunk: int):
    """``delta_rule`` over groups of heads (``over_groups``): autodiff
    keeps a dozen ``[b, chunks, heads, chunk, chunk]`` float32 matrices and
    the scan's float32 states of whatever it differentiates at once, 6.6 GiB
    a layer over 30 heads at 16,384 tokens, and heads are independent.  A
    group is the most heads (a divisor of all) whose one such matrix stays
    within ``GROUP_BYTES``; where that is all of them, one group."""
    bsz, s, h, _ = q.shape
    o, transform_max = over_groups(
        lambda *heads: delta_rule(*heads, chunk),
        _group_heads(bsz, s, h, chunk), q, k, v, beta, g)
    return o, jnp.max(transform_max)


def kernel_rule(q, k, v, beta, g, chunk: int):
    """``delta_rule`` over all heads at once in the Pallas pairs of
    parallel/delta_rule.py (shapes as ``rule_kernel_applies`` accepts them):
    ``delta_strict`` makes the solve's input from ``k``, ``gamma`` and
    ``beta``, ``_inverse_unit_lower`` solves every chunk and head of the
    layer in ONE call, XLA scales the inverse to ``T`` and takes its largest
    magnitude, and ``delta_rule_pair`` does everything that touches the
    state.  The pairs keep their inputs, ``T`` and the entering states of
    all heads for the backward and nothing else shaped ``[.., l, l]``, so no
    group of heads is run or rematerialised alone."""
    bsz, s, h, _ = q.shape
    with jax.named_scope("decay"):
        gamma = jnp.cumsum(g.reshape(bsz, s // chunk, chunk, h),
                           axis=2).reshape(bsz, s, h)
    with jax.named_scope("solve"):
        scale = jnp.moveaxis(beta.reshape(bsz, s // chunk, chunk, h), 2, 3)
        transform = _inverse_unit_lower(delta_strict(k, gamma, beta, chunk)) \
            * scale[..., None, :]
        transform_max = jax.lax.stop_gradient(jnp.max(jnp.abs(transform)))
    return delta_rule_pair(q, k, v, gamma, transform, chunk), transform_max


def gated_delta(args: BlockArgs) -> NamedTensor:
    """Layer ``gated_delta`` (module docstring).  Parameters in creation
    order: ``W_qkv``, ``W_gate``, ``W_ba`` normal(0.02); the conv's weight
    ``[K, channels]`` U(-1/sqrt(K), 1/sqrt(K)) (torch's Conv1d default, which FLA's
    ``ShortConvolution`` leaves); ``dt_bias`` with ``softplus`` log-uniform in
    [1e-3, 1e-1], ``A_log = log U(0, 16)`` (FLA's ``GatedDeltaNet``), the
    norm's scale 1; ``W_out`` normal(0.02)."""
    params = args.params
    ctx = scope.current()
    token_dims, bsz, s, chunk = token_layout(args, "gated_delta",
                                             params.delta_chunk)
    h, dk, dv = (params.delta_heads, params.delta_key_features,
                 params.delta_value_features)
    k = params.delta_conv_size
    d_key, d_value = h * dk, h * dv
    conv_dim = 2 * d_key + d_value
    feats = list(params.feature_dims)
    anon = [anonymize_dim(d) for d in feats]
    x = args.tensor
    f_sz = math.prod(d.size for d in feats)
    inner, channels = Dim("delta_value", d_value), Dim("delta_conv", conv_dim)
    head_dim = Dim("delta_heads", h)

    w_qkv = normal_var(args, anon + [channels])
    w_gate = normal_var(args, anon + [inner])
    w_ba = normal_var(args, anon + [Dim("delta_write_decay", 2 * h)])
    bound = k ** -0.5
    conv_w = _small_var(args, "uniform_var", [Dim("delta_conv_k", k), channels],
                        UniformInit(-bound, bound))
    dt_bias = _small_var(args, "uniform_var", [head_dim], UniformInit(
        math.log(1e-3), math.log(1e-1), _inverse_softplus_of_exp))
    a_log = _small_var(args, "uniform_var", [head_dim],
                       UniformInit(0.0, 16.0, np.log))
    w_norm = _small_var(args, "constant_var",
                        [Dim("delta_value_features", dv)], ConstantInit(1.0))

    dtype = x.dtype
    u = transpose_to(x, token_dims + feats).data.reshape(bsz, s, f_sz)
    with jax.named_scope("in_proj"):
        def project(w):
            return _matmul("bsf,fo->bso", u, w.data.reshape(f_sz, -1)
                           ).astype(dtype)

        qkv, z, ba = project(w_qkv), project(w_gate), project(w_ba)
        b_raw, a_raw = ba[..., :h], ba[..., h:]
    with jax.named_scope("conv"):
        if kernel_applies(conv_dim, s, k):
            qkv = causal_conv_silu(qkv, conv_w, None, 0)
        else:
            qkv = jax.nn.silu(causal_depthwise_conv(
                qkv.astype(jnp.float32), conv_w)).astype(dtype)
    with jax.named_scope("delta_rule"):
        def unit(t, scale):
            t = t.astype(jnp.float32)
            return (t * jax.lax.rsqrt(jnp.sum(jnp.square(t), -1,
                                              keepdims=True) + L2_EPS)
                    * scale).astype(dtype)

        q = unit(qkv[..., :d_key].reshape(bsz, s, h, dk), dk ** -0.5)
        key = unit(qkv[..., d_key:2 * d_key].reshape(bsz, s, h, dk), 1.0)
        beta = jax.nn.sigmoid(b_raw.astype(jnp.float32)) \
            * (2.0 if params.delta_allow_neg_eigval else 1.0)
        g = -jnp.exp(a_log) * jax.nn.softplus(a_raw.astype(jnp.float32)
                                              + dt_bias)
        rule = kernel_rule if rule_kernel_applies(chunk, h, dk, dv, s) \
            else grouped_rule
        o, transform_max = rule(
            q, key, qkv[..., 2 * d_key:].reshape(bsz, s, h, dv), beta, g,
            chunk)
        o = checkpoint_name(o, SAVED_NAMES[0])
    if ctx.layer_stats is not None:
        ctx.layer_stats.append({"delta_transform_abs_max": transform_max})
    with jax.named_scope("gate_norm"):
        normed = _norm_core(o, w_norm.reshape(1, 1, 1, dv),
                            jnp.ones((1, 1, 1, 1), jnp.float32), (3,),
                            params.norm_epsilon, True, False, False)
        gated = (normed.reshape(bsz, s, d_value).astype(jnp.float32)
                 * jax.nn.silu(z.astype(jnp.float32))).astype(dtype)
    w_out = normal_var(args, [inner] + feats)
    with jax.named_scope("out_proj"):
        out = _matmul("bsi,if->bsf", gated, w_out.data.reshape(d_value, f_sz)
                      ).astype(dtype)
    out = out.reshape([d.size for d in token_dims + feats])
    return transpose_to(nt(out, token_dims + feats), x.dims)


def _rule(params: ModelParameter):
    """``(chunk, heads, d_k, d_v, sequence)`` as ``rule_kernel_applies``
    takes them."""
    s = params.sequence_dim.size
    return (min(params.delta_chunk, s), params.delta_heads,
            params.delta_key_features, params.delta_value_features, s)


def _heads_a_call(params: ModelParameter, backend=None) -> int:
    """The heads whose rule runs, and keeps its states, at once: all of them
    where the rule is the Pallas pair, else one group (``grouped_rule``)."""
    if rule_kernel_applies(*_rule(params), backend):
        return params.delta_heads
    bsz, s = params.batch_dim.size, params.sequence_dim.size
    return _group_heads(bsz, s, params.delta_heads, min(params.delta_chunk, s))


def _state_bytes(params: ModelParameter) -> int:
    """``[batch, sequence / delta_chunk, heads a call, delta_value_features,
    delta_key_features]`` in the calculation dtype: the states entering every
    chunk that are alive at once for the backward — of ALL heads where the
    rule is the Pallas pair of parallel/delta_rule.py (its forward writes
    them, its backward reads them), of ONE group of heads on the XLA form
    (``grouped_rule`` rematerialises a group at a time; ``state_out`` and the
    inter-chunk scan's backward read them).  The carried state itself is
    float32, one chunk's."""
    bsz, s = params.batch_dim.size, params.sequence_dim.size
    chunk = min(params.delta_chunk, s)
    return bsz * max(1, s // chunk) * _heads_a_call(params) \
        * params.delta_value_features * params.delta_key_features \
        * jnp.dtype(params.calculation_dtype).itemsize


def _conv(params: ModelParameter):
    return (params.delta_heads * (2 * params.delta_key_features
                                  + params.delta_value_features),
            params.delta_conv_size, 0)


def _offer(params: ModelParameter, extras) -> Offer:
    """The rule's output ``[batch, sequence, delta_heads,
    delta_value_features]`` in the calculation dtype: ``SAVED_NAMES``."""
    return Offer("recurrent", SAVED_NAMES,
                 params.batch_dim.size * params.sequence_dim.size
                 * params.delta_heads * params.delta_value_features
                 * jnp.dtype(params.calculation_dtype).itemsize)


def _solve(params: ModelParameter, backend=None):
    """``(chunk, systems)`` of one call of ``_inverse_unit_lower``: a chunk
    and a head each, over the heads of a call (``_heads_a_call``: every head
    under ``kernel_rule``, one group under ``grouped_rule``)."""
    bsz, s = params.batch_dim.size, params.sequence_dim.size
    chunk = min(params.delta_chunk, s)
    return chunk, bsz * max(1, s // chunk) * _heads_a_call(params, backend)


gated_delta.declares = Layer(
    stats=(Stat("delta_transform_abs_max", "gauge",
                "hbnlp_delta_transform_abs_max",
                "largest magnitude in any chunk's solved transform T = (I + "
                "strict_tril(diag(beta) (K K^T o Gamma)))^-1 diag(beta) of "
                "the newest finished step, all gated_delta layers: what its "
                "lower-precision matmul operands have to carry", "max"),),
    offer=_offer, facts=FACTS,
    recurrent=Recurrent(_state_bytes, _conv, _solve, rule=_rule))
