"""Kimi Delta Attention (layer ``kda``): the gated delta rule with a decay a
CHANNEL of the key (Kimi Linear, arXiv:2510.26692; flash-linear-attention's
``KimiDeltaAttention``), in the chunked WY / UT form of model/gated_delta.py.

On the block's input ``u [b, s, features]``, ``H = kda_heads`` heads of ``d_k
= kda_key_features`` and ``d_v = kda_value_features``:

    q | k | v = u W_qkv                   H d_k, H d_k, H d_v columns, no bias
    q, k, v = silu(conv(q | k | v))       causal depthwise, kda_conv_size
                                          taps, no bias, a weight a channel
                                          (the Pallas pair of
                                          parallel/causal_conv.py where
                                          ``kernel_applies``)
    q~ = q rsqrt(|q|^2 + 1e-6) d_k^-1/2   per head, float32
    k~ = k rsqrt(|k|^2 + 1e-6)
    g = -exp(A_log)[h] softplus(u W_f1 W_f2 + dt_bias)
                                          W_f1 features x d_v, W_f2 d_v x H
                                          d_k (the pair's inner width is a
                                          head's, as the source fixes it); g
                                          [b, s, H, d_k] <= 0, float32: a
                                          log-decay a CHANNEL
    beta = sigmoid(u W_b)                 [b, s, H] (no factor 2)
    S_t = (I - beta_t k~_t k~_t^T) diag(exp(g_t)) S_{t-1} + beta_t k~_t v_t^T
                                          S: [d_k, d_v] a head, S_0 = 0
    o_t = S_t^T q~_t
    y = rms(o) w_norm * sigmoid(u W_g1 W_g2)
                                          RMSNorm over d_v a head, one [d_v]
                                          scale, eps ``norm_epsilon``; the
                                          sigmoid gate (W_g1 features x d_v,
                                          W_g2 d_v x H d_v) on the norm's
                                          OUTPUT
    out = y W_out                         H d_v x features, no bias

With ``g`` equal over a head's channels the recurrence is ``gated_delta``'s
(its ``S`` transposed; ``tests/kimi_linear_test.py`` holds that).

The chunked form (``kda_rule``).  Inside a chunk of ``CHUNK`` positions,
with ``gamma [l, d_k]`` the float32 cumulative sum of ``g`` a channel,

    A_ij  = sum_d k~_id k~_jd exp(gamma_id - gamma_jd)      i > j
    A'_ij = sum_d q~_id k~_jd exp(gamma_id - gamma_jd)      i >= j
    T = (I + strict_tril(diag(beta) A))^-1 diag(beta)
    W = T (K~ o exp(gamma)),  U = T V
    V' = U - W S            S [d_k, d_v] the state entering the chunk
    O  = (Q~ o exp(gamma)) S + A' V'
    S <- diag(exp(gamma_C)) S + (K~ o exp(gamma_C - gamma))^T V'

``gated_delta``'s W / U / V' / O / S algebra with ``exp(gamma)`` an ``[l,
d_k]`` matrix where it was a column.  What does NOT carry over is ``A``: the
decay is inside the sum over the channels, so ``K K^T o Gamma`` no longer
factors.  ``_decayed_scores`` makes both products WITHOUT ever forming the
``exp`` of a positive log-decay difference (a step's log-decay of -20 makes
``exp(-gamma)`` overflow float32 after five positions): the chunk is cut into
sub-chunks of ``_SUB`` positions; for a sub-chunk ``I`` whose first position
is ``r`` and ALL the positions ``J`` before it,

    A_IJ = (X_I o exp(gamma_I - gamma_r)) (K_J o exp(gamma_r - gamma_J))^T

— both exponents <= 0 (``gamma`` falls along the chunk), one matmul a
sub-chunk against everything before it — and the diagonal block of ``I``
elementwise over ``[_SUB, _SUB, d_k]`` with the differences masked BEFORE
the ``exp``.  ``exp(gamma)``,
``exp(gamma_C - gamma)`` and ``exp(gamma_C)`` are <= 1 as they stand.

The unit triangular system is ``gated_delta._inverse_unit_lower`` as it is
(the Pallas pair of parallel/delta_solve.py where ``solve_kernel_applies``).
Round it the rule has two forms, chosen by ``kda_kernel_applies(chunk, heads,
d_k, d_v, sequence)`` on what the code observes (the backend and the shapes;
never a key of the configuration):

* ``kernel_rule`` — a TPU, whole lane tiles of positions, widths in whole
  sublane tiles, a float32 state of all heads within VMEM: the Pallas pairs
  of parallel/kda_rule.py over ALL heads a call.  ``kda_scores`` reads
  ``q`` and ``k`` as the conv left them and ``g``, makes the L2 norms and
  the running sum ``gamma`` on its way and ``A`` and ``A'`` with
  ``_decayed_scores``' arithmetic; XLA scales by ``beta`` round ONE solve
  call over every head's systems; ``kda_rule_pair`` walks the chunks with
  ``S`` of all heads in VMEM and writes ``o`` and the states entering every
  chunk; each pair's backward is one kernel.  XLA keeps ``beta``, the turns
  between the layer's layout and the kernels' and both statistics.
* ``grouped_rule`` — everywhere else, and the kernels' oracle: XLA's
  ``kda_rule`` a group of heads at a time, each group rematerialised in its
  own backward (``gated_delta.over_groups``: what autodiff keeps of the rule
  over all heads at once does not fit beside the train state at 16,384
  tokens); the state walk is a ``lax.scan`` over the chunks with ``S``
  carried in float32.

parallel/delta_rule.py's pair (layer ``gated_delta``) takes ONE decay a head:
its ``K^T K o Gamma`` pass is what a decay a channel forbids, so the two
layers have a file and a predicate each.  In both forms decays, cumulative
sums, ``beta``, the solve and the carried state are float32 (``KEPT``); the
matmuls take the calculation dtype with float32 accumulation.

Under the ``checkpoint`` strategy the layer offers the block's
``jax.checkpoint`` what lets its replay skip the rule (``SAVED_NAMES``, kind
``recurrent``; model/remat.py admits each part an execution at a time from
the step's end).  The rule's output ``o`` first: the gate norm and the
out-projection differentiate through it, and on the XLA form the replay then
runs no forward of the rule (each group's backward makes what it needs again
from ``q, k, v, beta, g``).  On the kernels' form ``o`` alone skips no kernel
(the walk's forward writes it with the states) but the turn of ``o^T`` into
the layer's layout and its consumers' strided reads; so there the offer has
an INTERIOR, what the three forwards hand their backwards, each as it is kept
today — ``gamma``, ``q~``, ``k~``, ``A`` (the solve's input before
``diag(beta)``) and ``A'`` of ``kda_scores``, the solve's inverse, the walk's
entering chunk states —, named inside the forward rules: the replay of an
execution whose interior is admitted runs none of the three forwards.

Training and full-sequence forward on one device; a decode / prefill form (a
``[H, d_k, d_v]`` state and a conv window a sequence) is a later issue.
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
from ..parallel.kda_rule import (SCORES_NAMES, STATES_NAME,
                                 kda_kernel_applies, kda_rule_pair,
                                 kda_scores, positions_major, sequence_minor)
from .backend import ConstantInit, UniformInit, normal_var
from .declare import Layer, Offer, Stat
from .gated_delta import (L2_EPS, _inverse_bwd, _inverse_unit_lower,
                          over_groups)
from .gated_delta import gated_delta as _gated_delta
from .loss import _matmul
from .normalization import _norm_core
from .recurrent import (FACTS, Recurrent, _inverse_softplus_of_exp,
                        _small_var, causal_depthwise_conv, token_layout)
from .utils import anonymize_dim

#: positions a chunk of the WY form (the size of its triangular system), or
#: the sequence where that is shorter; the sequence is a multiple of it
CHUNK = 64
#: positions a sub-chunk of ``_decayed_scores``: the diagonal blocks are
#: elementwise over ``[_SUB, _SUB, d_k]``, what lies before one a matmul
_SUB = 16
#: the most bytes of one group's ``[b, s, _SUB, heads, d_k]`` float32
#: diagonal-block products (``grouped_rule``): 8 of 32 heads at 16,384 tokens
#: and key width 128
GROUP_BYTES = 1 << 30
#: what the rule keeps the log-decays' cumulative sums, the solve's input and
#: the carried state in.  scripts/kimi_rule_control.py reads the benchmark's
#: cell with bfloat16 here: the precision below the one the configuration
#: states, which the cell's ``logit_tolerance`` has to see
KEPT = jnp.float32
#: the name ``kernel_rule`` gives the solve's inverse, its backward's one
#: residual
SOLVED_NAME = "kda_solved"
#: what layer ``kda`` offers model/remat.py's ``recurrent`` kind, by
#: ``checkpoint_name``: the rule's output ``o [b, s, heads, d_v]`` and, where
#: the rule is the Pallas pairs, as the offer's interior what their forwards
#: hand their backwards (parallel/kda_rule.py ``SCORES_NAMES``,
#: ``STATES_NAME``; the inverse)
SAVED_NAMES = ("kda_out", *SCORES_NAMES, SOLVED_NAME, STATES_NAME)


def _decayed_scores(x, k, gamma, strict: bool):
    """``sum_d x_id k_jd exp(gamma_id - gamma_jd)`` for ``i > j`` (``strict``)
    or ``i >= j`` inside each chunk, 0 elsewhere: ``x``, ``k [b, c, l, h,
    d]`` in the calculation dtype, ``gamma [b, c, l, h, d]`` float32 and
    falling along ``l`` -> ``[b, c, h, l, l]`` float32.  No ``exp`` of a
    positive difference is formed (module docstring)."""
    bsz, c, l, h, d = x.shape
    dtype = x.dtype
    sub = math.gcd(l, _SUB)
    xf, kf = x.astype(jnp.float32), k.astype(jnp.float32)
    pos = jnp.arange(sub)
    seen = pos[:, None] > pos[None, :] if strict else pos[:, None] >= pos[None, :]
    bands = []
    for first in range(0, l, sub):
        mine = slice(first, first + sub)
        g_rows = gamma[:, :, mine]
        # the diagonal block: differences masked before the exp
        diff = jnp.where(seen[None, None, :, :, None, None],
                         g_rows[:, :, :, None] - g_rows[:, :, None, :],
                         -jnp.inf)
        within = jnp.moveaxis(jnp.sum(
            xf[:, :, mine, None] * kf[:, :, None, mine] * jnp.exp(diff),
            axis=-1), -1, 2)                                # [b, c, h, i, j]
        parts = [within, jnp.zeros((bsz, c, h, sub, l - first - sub),
                                   jnp.float32)]
        if first:
            # every earlier position, one matmul: the rows decay from their
            # sub-chunk's first position, the columns up to it
            start = g_rows[:, :, :1]
            rows = (xf[:, :, mine] * jnp.exp(g_rows - start)).astype(dtype)
            cols = (kf[:, :, :first]
                    * jnp.exp(start - gamma[:, :, :first])).astype(dtype)
            parts.insert(0, _matmul("bcihd,bcjhd->bchij", rows, cols
                                    ).astype(jnp.float32))
        bands.append(jnp.concatenate(parts, axis=-1))
    return jnp.concatenate(bands, axis=-2)


def kda_rule(q, k, v, beta, g, chunk: int):
    """The chunked rule.  ``q`` / ``k [b, s, h, d_k]`` (normalised,
    calculation dtype), ``v [b, s, h, d_v]``, ``beta [b, s, h]`` and ``g [b,
    s, h, d_k]`` float32 (``g <= 0``); ``s`` a multiple of ``chunk``.
    Returns ``(o [b, s, h, d_v]`` in the calculation dtype, the largest
    magnitude in any chunk's solved transform ``T``, the most negative
    cumulative log-decay of any channel inside a chunk)``."""
    bsz, s, h, dk = q.shape
    dv = v.shape[-1]
    c, l = s // chunk, chunk
    dtype = q.dtype
    qc, kc = q.reshape(bsz, c, l, h, dk), k.reshape(bsz, c, l, h, dk)
    vc = v.reshape(bsz, c, l, h, dv)
    beta = jnp.moveaxis(beta.reshape(bsz, c, l, h), 2, 3)      # [b, c, h, l]
    with jax.named_scope("decay"):
        gamma = jnp.cumsum(g.reshape(bsz, c, l, h, dk).astype(KEPT),
                           axis=2).astype(jnp.float32)
        from_start = jnp.exp(gamma)
        to_end = jnp.exp(gamma[:, :, -1:] - gamma)
        chunk_decay = jnp.exp(gamma[:, :, -1])                 # [b, c, h, d_k]
        log_decay_min = jnp.min(gamma[:, :, -1])
    with jax.named_scope("solve"):
        strict = _decayed_scores(kc, kc, gamma, True) * beta[..., :, None]
        transform = _inverse_unit_lower(
            strict.astype(KEPT).astype(jnp.float32)) * beta[..., None, :]
        transform_max = jnp.max(jnp.abs(transform))
    with jax.named_scope("intra_chunk"):
        t_low = transform.astype(dtype)
        kf = kc.astype(jnp.float32)
        k_start = (kf * from_start).astype(dtype)
        w = _matmul("bchij,bcjhd->bcihd", t_low, k_start).astype(dtype)
        u = _matmul("bchij,bcjhd->bcihd", t_low, vc).astype(dtype)
        mixed = _decayed_scores(qc, kc, gamma, False).astype(dtype)
        k_end = (kf * to_end).astype(dtype)
        q_start = (qc.astype(jnp.float32) * from_start).astype(dtype)
    with jax.named_scope("inter_chunk"):
        def step(state, inp):
            w_c, u_c, k_end_c, decay_c = inp
            entering = state.astype(dtype)
            v_new = (u_c.astype(jnp.float32) - _matmul(
                "blhk,bhkv->blhv", w_c, entering).astype(jnp.float32)
            ).astype(dtype)
            left = state * decay_c[..., None] + _matmul(
                "blhk,blhv->bhkv", k_end_c, v_new).astype(jnp.float32)
            return left.astype(KEPT).astype(jnp.float32), (entering, v_new)

        _, (entering, v_new) = jax.lax.scan(
            step, jnp.zeros((bsz, h, dk, dv), jnp.float32),
            tuple(jnp.moveaxis(t, 1, 0)
                  for t in (w, u, k_end, chunk_decay)))
        entering = jnp.moveaxis(entering, 0, 1)        # [b, c, h, d_k, d_v]
        v_new = jnp.moveaxis(v_new, 0, 1)              # [b, c, l, h, d_v]
    with jax.named_scope("state_out"):
        o = _matmul("bcihk,bchkv->bcihv", q_start, entering
                    ).astype(jnp.float32) \
            + _matmul("bchij,bcjhv->bcihv", mixed, v_new).astype(jnp.float32)
    return (o.astype(dtype).reshape(bsz, s, h, dv), transform_max,
            log_decay_min)


def _group_heads(bsz: int, s: int, h: int, dk: int) -> int:
    per_head = bsz * s * _SUB * dk * 4
    return max(d for d in range(1, h + 1)
               if h % d == 0 and (d * per_head <= GROUP_BYTES or d == 1))


def grouped_rule(q, k, v, beta, g, chunk: int):
    """``kda_rule`` over groups of heads (``gated_delta.over_groups``): a
    group is the most heads (a divisor of all) whose diagonal-block products
    stay within ``GROUP_BYTES``."""
    bsz, s, h, dk = q.shape
    o, transform_max, log_decay_min = over_groups(
        lambda *heads: kda_rule(*heads, chunk), _group_heads(bsz, s, h, dk),
        q, k, v, beta, g)
    return o, jnp.max(transform_max), jnp.min(log_decay_min)


def unit(t, scale: float):
    """``t [.., d]`` over its L2 norm along ``d`` (float32, ``L2_EPS`` under
    the root) times ``scale``, in ``t``'s dtype."""
    f = t.astype(jnp.float32)
    return (f * jax.lax.rsqrt(jnp.sum(jnp.square(f), -1, keepdims=True)
                              + L2_EPS) * scale).astype(t.dtype)


def normalised(rule):
    """``rule`` (``kda_rule``, ``grouped_rule``) behind the layer's norms of
    ``q`` and ``k``: what ``kernel_rule`` is on the same arguments."""
    def run(q, k, *rest):
        return rule(unit(q, q.shape[-1] ** -0.5), unit(k, 1.0), *rest)
    return run


@jax.custom_vjp
def _solved(strict):
    """``gated_delta._inverse_unit_lower`` under a forward rule that NAMES
    the inverse it hands the backward (``SOLVED_NAME``): saved, the block's
    replay runs no solve."""
    return _inverse_unit_lower(strict)


def _solved_fwd(strict):
    inv = checkpoint_name(_inverse_unit_lower(strict), SOLVED_NAME)
    return inv, inv


_solved.defvjp(_solved_fwd, _inverse_bwd)


def kernel_rule(q, k, v, beta, g, chunk: int):
    """``normalised(kda_rule)`` over all heads at once as the Pallas pairs of
    parallel/kda_rule.py (shapes as ``kda_kernel_applies`` accepts them):
    ``q`` and ``k`` as the conv left them.  The scores' pair normalises them
    and sums ``g`` along each chunk on its way; XLA turns the operands
    sequence-minor, scales by ``beta`` round the solve and reads both
    statistics."""
    bsz, s, h, dk = q.shape
    with jax.named_scope("solve"):
        strict, mixed, gamma, q_unit, k_unit = kda_scores(
            sequence_minor(q), sequence_minor(k), sequence_minor(g), h,
            chunk, math.gcd(chunk, _SUB), dk ** -0.5, L2_EPS, KEPT)
        scale = jnp.moveaxis(beta.reshape(bsz, s // chunk, chunk, h), 2, 3)
        strict = strict * scale[..., :, None]
        transform = _solved(
            strict.astype(KEPT).astype(jnp.float32)) * scale[..., None, :]
        transform_max = jax.lax.stop_gradient(jnp.max(jnp.abs(transform)))
    with jax.named_scope("decay"):
        log_decay_min = jax.lax.stop_gradient(jnp.min(
            gamma.reshape(bsz, h * dk, s // chunk, chunk)[..., -1]))
    o = kda_rule_pair(q_unit, k_unit, sequence_minor(v), gamma, transform,
                      mixed, chunk, kept=KEPT)
    return positions_major(o, v.shape), transform_max, log_decay_min


def kda(args: BlockArgs) -> NamedTensor:
    """Layer ``kda`` (module docstring).  Parameters in creation order:
    ``W_qkv``, the decay pair ``W_f1``, ``W_f2``, the gate pair ``W_g1``,
    ``W_g2``, ``W_b`` normal(0.02); the conv's weight ``[K, channels]``
    U(-1/sqrt(K), 1/sqrt(K)); ``dt_bias [H d_k]`` with ``softplus``
    log-uniform in [1e-3, 1e-1] (``gated_delta``'s), ``A_log [H] = log U(1,
    16)``, the norm's scale 1; ``W_out`` normal(``residual_out_stddev`` where
    set, else 0.02)."""
    params = args.params
    ctx = scope.current()
    token_dims, bsz, s, chunk = token_layout(args, "kda", CHUNK)
    h, dk, dv = (params.kda_heads, params.kda_key_features,
                 params.kda_value_features)
    taps, rank = params.kda_conv_size, dv
    d_key, d_value = h * dk, h * dv
    conv_dim = 2 * d_key + d_value
    feats = list(params.feature_dims)
    anon = [anonymize_dim(d) for d in feats]
    x = args.tensor
    f_sz = math.prod(d.size for d in feats)
    inner, keys = Dim("kda_value", d_value), Dim("kda_key", d_key)
    channels, low = Dim("kda_conv", conv_dim), Dim("kda_low_rank", rank)
    head_dim = Dim("kda_heads", h)

    w_qkv = normal_var(args, anon + [channels])
    w_f1 = normal_var(args, anon + [low])
    w_f2 = normal_var(args, [low, keys])
    w_g1 = normal_var(args, anon + [low])
    w_g2 = normal_var(args, [low, inner])
    w_b = normal_var(args, anon + [head_dim])
    bound = taps ** -0.5
    conv_w = _small_var(args, "uniform_var", [Dim("kda_conv_k", taps), channels],
                        UniformInit(-bound, bound))
    dt_bias = _small_var(args, "uniform_var", [keys], UniformInit(
        math.log(1e-3), math.log(1e-1), _inverse_softplus_of_exp))
    a_log = _small_var(args, "uniform_var", [head_dim],
                       UniformInit(1.0, 16.0, np.log))
    w_norm = _small_var(args, "constant_var",
                        [Dim("kda_value_features", dv)], ConstantInit(1.0))

    dtype = x.dtype
    u = transpose_to(x, token_dims + feats).data.reshape(bsz, s, f_sz)

    def project(t, w, rows):
        return _matmul("bsf,fo->bso", t, w.data.reshape(rows, -1)
                       ).astype(dtype)

    with jax.named_scope("in_proj"):
        qkv, b_raw = project(u, w_qkv, f_sz), project(u, w_b, f_sz)
        gate_low = project(u, w_g1, f_sz)
    with jax.named_scope("conv"):
        if kernel_applies(conv_dim, s, taps):
            qkv = causal_conv_silu(qkv, conv_w, None, 0)
        else:
            qkv = jax.nn.silu(causal_depthwise_conv(
                qkv.astype(jnp.float32), conv_w)).astype(dtype)
    with jax.named_scope("decay"):
        raw = project(project(u, w_f1, f_sz), w_f2, rank)
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            (raw.astype(jnp.float32) + dt_bias).reshape(bsz, s, h, dk))
    with jax.named_scope("rule"):
        rule = kernel_rule if kda_kernel_applies(chunk, h, dk, dv, s) \
            else normalised(grouped_rule)
        o, transform_max, log_decay_min = rule(
            qkv[..., :d_key].reshape(bsz, s, h, dk),
            qkv[..., d_key:2 * d_key].reshape(bsz, s, h, dk),
            qkv[..., 2 * d_key:].reshape(bsz, s, h, dv),
            jax.nn.sigmoid(b_raw.astype(jnp.float32)), g, chunk)
        o = checkpoint_name(o, SAVED_NAMES[0])
    if ctx.layer_stats is not None:
        ctx.layer_stats.append({"delta_transform_abs_max": transform_max,
                                "kda_log_decay_min": log_decay_min})
    with jax.named_scope("gate_norm"):
        normed = _norm_core(o, w_norm.reshape(1, 1, 1, dv),
                            jnp.ones((1, 1, 1, 1), jnp.float32), (3,),
                            params.norm_epsilon, True, False, False)
        z = project(gate_low, w_g2, rank)
        gated = (normed.reshape(bsz, s, d_value).astype(jnp.float32)
                 * jax.nn.sigmoid(z.astype(jnp.float32))).astype(dtype)
    w_out = normal_var(args, [inner] + feats,
                       stddev=params.residual_out_stddev or 0.02)
    with jax.named_scope("out_proj"):
        out = _matmul("bsi,if->bsf", gated, w_out.data.reshape(d_value, f_sz)
                      ).astype(dtype)
    out = out.reshape([d.size for d in token_dims + feats])
    return transpose_to(nt(out, token_dims + feats), x.dims)


def _rule(params: ModelParameter):
    """``(chunk, heads, d_k, d_v, sequence)`` as ``kda_kernel_applies`` takes
    them."""
    s = params.sequence_dim.size
    return (min(CHUNK, s), params.kda_heads, params.kda_key_features,
            params.kda_value_features, s)


def _chunks_and_heads(params: ModelParameter, backend=None):
    """``(the chunk as it runs, chunks over the batch, the heads whose rule
    runs and keeps its states at once)``: every head where the rule is the
    Pallas pairs on that backend, else one group (``grouped_rule``)."""
    bsz, s = params.batch_dim.size, params.sequence_dim.size
    chunk = min(CHUNK, s)
    heads = params.kda_heads if kda_kernel_applies(*_rule(params), backend) \
        else _group_heads(bsz, s, params.kda_heads, params.kda_key_features)
    return chunk, bsz * max(1, s // chunk), heads


def _state_bytes(params: ModelParameter) -> int:
    """``[batch, sequence / CHUNK, heads a call, kda_key_features,
    kda_value_features]`` in the calculation dtype: the states entering every
    chunk that are alive at once for the backward — of ALL heads where the
    rule is the Pallas pair of parallel/kda_rule.py (its forward writes them,
    its backward reads them), of ONE group of heads on the XLA form
    (``grouped_rule`` rematerialises a group at a time)."""
    _, chunks, heads = _chunks_and_heads(params)
    return chunks * heads * params.kda_key_features \
        * params.kda_value_features \
        * jnp.dtype(params.calculation_dtype).itemsize


def _conv(params: ModelParameter):
    return (params.kda_heads * (2 * params.kda_key_features
                                + params.kda_value_features),
            params.kda_conv_size, 0)


def _offer(params: ModelParameter, extras) -> Offer:
    """The rule's output ``[batch, sequence, kda_heads, kda_value_features]``
    in the calculation dtype (``SAVED_NAMES[0]``), and where the rule is the
    Pallas pairs, as the offer's interior, what they keep for their backwards
    (the other ``SAVED_NAMES``): ``gamma`` in float32 and the normalised
    ``q`` and ``k`` ``[batch, sequence, heads, d_k]``, ``A``, the inverse
    (float32) and ``A'`` ``[batch, chunks, heads, chunk, chunk]``, the
    entering states ``[batch, chunks, heads, d_v, d_k]``."""
    chunk, h, dk, dv, s = _rule(params)
    low = jnp.dtype(params.calculation_dtype).itemsize
    positions = params.batch_dim.size * s * h
    offer = Offer("recurrent", SAVED_NAMES[:1], positions * dv * low)
    if not kda_kernel_applies(chunk, h, dk, dv, s):
        return offer
    return offer._replace(
        interior_names=SAVED_NAMES[1:],
        interior_nbytes=positions * dk * (4 + 2 * low)
        + positions * chunk * (4 + 4 + low)
        + positions // chunk * dk * dv * low)


def _solve(params: ModelParameter, backend=None):
    """``(chunk, systems)`` of one call of ``_inverse_unit_lower``: a chunk
    and a head each, over the heads of a call (``_chunks_and_heads``)."""
    chunk, chunks, heads = _chunks_and_heads(params, backend)
    return chunk, chunks * heads


kda.declares = Layer(
    stats=_gated_delta.declares.stats + (
        Stat("kda_log_decay_min", "gauge", "hbnlp_kda_log_decay_min",
             "most negative cumulative log-decay of any channel inside a "
             "chunk of the newest finished step, all kda layers: how far "
             "the step runs from where exp(gamma) underflows", "min"),),
    offer=_offer, facts=FACTS,
    recurrent=Recurrent(_state_bytes, _conv, _solve, rule=_rule,
                        rule_applies=kda_kernel_applies))
