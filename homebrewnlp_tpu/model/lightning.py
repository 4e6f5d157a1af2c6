"""Lightning attention (layer ``lightning``): linear attention with a
CONSTANT decay a head and keys and queries of each head's own (Qin et al.,
"Lightning Attention-2", arXiv:2401.04658; MiniCPM-SALA's ``lightning-attn``
layers).

On the block's (already normalised) input ``x [b, s, features]``, with ``H =
lightning_heads`` heads of ``d = lightning_head_features`` in the WHOLE layer,
of which this one holds ``lightning_heads_held`` from ``lightning_heads_first``
(0 held = all: tensor parallelism by heads; the held heads' part of ``W_o``'s
sum is what leaves, nothing stands in for the other ranks' or their
all-reduce):

    q, k, v, z = x W_q, x W_k, x W_v, x W_z        features x (held d) each
    q, k = rms(q) * w_q, rms(k) * w_k              a head, learned [d] scales
    q, k = rotary(q), rotary(k)                    rope_theta, all d features
    S_t = lambda_h S_{t-1} + k_t^T v_t             S: [d, d] a head
    o_t = q_t S_t / sqrt(d)
    y = rms(o) * w_o' * sigmoid(z)                 over a GROUP of heads'
                                                   outputs, a learned scale a
                                                   feature; then the gate
    out = y W_o                                    (held d) x features

``lambda_h = exp(-2^(-8 (h + 1) / H))`` by the head's index ``h`` in the
WHOLE layer (Lightning Attention's ALiBi-style slopes).  The output norm is
over the ``H / lightning_norm_groups`` heads of a group together (Mamba-2's
grouped gated norm: a tensor-parallel rank holds whole groups and normalises
without an exchange).  A norm a HEAD would be discontinuous at the first
position: there ``o_0 = (q_0 . k_0) v_0 / sqrt(d)``, whose per-head norm is
``sign(q_0 . k_0) v_0 / rms(v_0)`` whatever the score's size, so a rounding
of a score near zero turns a head's whole output round (PERF.md section 6, PR
46: 0.13 of the largest logit at position 0 in bfloat16, beside 0.02
everywhere else); over a group a small score weighs little.

The recurrence is never run position by position: inside a chunk of
``lightning_chunk`` positions it is the ``q k^T`` product under the constant
``[heads, chunk, chunk]`` decay (``intra_chunk``), every chunk leaves one
state (``chunk_states``), a serial ``lax.scan`` over the chunks carries the
``[heads, d, d]`` states across in float32 (``inter_chunk``), and the state
entering a chunk adds its part (``state_out``).  Every decay is ``exp`` of a
non-positive multiple of the head's rate, so nothing overflows; matmul
operands are the calculation dtype with float32 accumulation, as
``model/mamba.py ssd``'s.  Autodiff gives the backward.

Training and full-sequence forward on one device: decode, prefill and a mesh
refuse by name (``model/recurrent.py token_layout``), ``scan_layers``, revnet
and the pipeline as every layer of a non-periodic stack does.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..config import BlockArgs, ModelParameter
from ..core import scope
from ..core.dims import Dim
from ..core.tensor import NamedTensor, nt, transpose_to
from .backend import ConstantInit, normal_var
from .declare import Layer, Stat
from .loss import _matmul
from .normalization import _norm_core
from .recurrent import FACTS, Recurrent, _small_var, token_layout
from .spatial import rotary
from .utils import anonymize_dim


def decay_rates(heads: int, first: int = 0, held: int = 0) -> np.ndarray:
    """``-log lambda_h = 2^(-8 (h + 1) / heads)`` of the ``held`` heads from
    ``first`` (0 = all) of a layer of ``heads``, float32."""
    index = np.arange(first, first + (held or heads), dtype=np.float64)
    return np.exp2(-8.0 * (index + 1) / heads).astype(np.float32)


def lightning_rule(q, k, v, rates, chunk: int):
    """The chunked rule.  ``q``, ``k`` ``[b, s, h, d]`` and ``v [b, s, h,
    e]`` (calculation dtype), ``rates [h]`` float32 (``-log lambda``,
    positive); ``s`` a multiple of ``chunk``.  Returns ``(o [b, s, h, e]``
    float32 with ``o_t = q_t S_t`` (no scale), the largest magnitude in any
    carried state)``."""
    bsz, s, h, d = q.shape
    e = v.shape[-1]
    c, l = s // chunk, chunk
    dtype = q.dtype
    qc, kc = q.reshape(bsz, c, l, h, d), k.reshape(bsz, c, l, h, d)
    vc = v.reshape(bsz, c, l, h, e)
    rates = jnp.asarray(rates, jnp.float32)
    pos = jnp.arange(l, dtype=jnp.float32)
    with jax.named_scope("intra_chunk"):
        scores = _matmul("bcihd,bcjhd->bchij", qc, kc).astype(jnp.float32)
        back = pos[:, None] - pos[None, :]
        decay = jnp.where(back >= 0, jnp.exp(
            -rates[:, None, None] * jnp.maximum(back, 0.0)), 0.0)
        y = _matmul("bchij,bcjhe->bcihe", (scores * decay).astype(dtype), vc
                    ).astype(jnp.float32)
    with jax.named_scope("chunk_states"):
        # what each position still contributes at its chunk's end
        to_end = jnp.exp(-rates[None, :] * (l - 1 - pos)[:, None])  # [l, h]
        weighted = (kc.astype(jnp.float32) * to_end[..., None]).astype(dtype)
        states = _matmul("bclhd,bclhe->bchde", weighted, vc
                         ).astype(jnp.float32)
    with jax.named_scope("inter_chunk"):
        chunk_decay = jnp.exp(-rates * l)[:, None, None]

        def step(carry, state):
            return carry * chunk_decay + state, carry

        _, entering = jax.lax.scan(
            step, jnp.zeros((bsz, h, d, e), jnp.float32),
            jnp.moveaxis(states, 1, 0))
        entering = jnp.moveaxis(entering, 0, 1)            # [b, c, h, d, e]
    with jax.named_scope("state_out"):
        from_start = jnp.exp(-rates[None, :] * (pos + 1)[:, None])  # [l, h]
        y = y + _matmul("bclhd,bchde->bclhe", qc, entering.astype(dtype)
                        ).astype(jnp.float32) * from_start[..., None]
    return y.reshape(bsz, s, h, e), jnp.max(jnp.abs(entering))


def _held(params: ModelParameter) -> int:
    return params.lightning_heads_held or params.lightning_heads


def lightning(args: BlockArgs) -> NamedTensor:
    """Layer ``lightning`` (module docstring).  Parameters in creation
    order: ``W_q``, ``W_k``, ``W_v``, ``W_z`` normal(0.02); the norms' scales
    at 1 — ``[d]`` for the query and for the key, ``[held d]`` for the output
    —; ``W_o`` normal(0.02)."""
    params = args.params
    ctx = scope.current()
    token_dims, bsz, s, chunk = token_layout(args, "lightning",
                                             params.lightning_chunk)
    h, d = _held(params), params.lightning_head_features
    if d % 2:
        raise ValueError(f"lightning_head_features {d}: rotary positions "
                         "turn pairs of features")
    inner = Dim("lightning_inner", h * d)
    feats = list(params.feature_dims)
    anon = [anonymize_dim(f) for f in feats]
    x = args.tensor
    f_sz = math.prod(f.size for f in feats)
    w_in = [normal_var(args, anon + [inner]) for _ in range(4)]
    width = Dim("lightning_head_features", d)
    w_q, w_k = (_small_var(args, "constant_var", [width], ConstantInit(1.0))
                for _ in range(2))
    w_o_norm = _small_var(args, "constant_var", [inner], ConstantInit(1.0))
    group = params.lightning_heads // params.lightning_norm_groups * d
    rates = decay_rates(params.lightning_heads, params.lightning_heads_first,
                        params.lightning_heads_held)

    dtype = x.dtype
    eps = params.norm_epsilon
    one = jnp.ones((1, 1, 1, 1), jnp.float32)
    u = transpose_to(x, token_dims + feats).data.reshape(bsz, s, f_sz)
    with jax.named_scope("in_proj"):
        q, k, v, z = (_matmul("bsf,fo->bso", u, w.data.reshape(f_sz, -1)
                              ).astype(dtype) for w in w_in)
    with jax.named_scope("qk_norm"):
        q, k = (_norm_core(t.reshape(bsz, s, h, d), w.reshape(1, 1, 1, d),
                           one, (3,), eps, True, False, False)
                for t, w in ((q, w_q), (k, w_k)))
    with jax.named_scope("rope"):
        q, k = rotary(q, params.rope_theta), rotary(k, params.rope_theta)
    with jax.named_scope("rule"):
        o, state_max = lightning_rule(q, k, v.reshape(bsz, s, h, d), rates,
                                      chunk)
        o = (o * d ** -0.5).astype(dtype)
    if ctx.layer_stats is not None:
        ctx.layer_stats.append({"lightning_state_abs_max": state_max})
    with jax.named_scope("gate_norm"):
        normed = _norm_core(o.reshape(bsz, s, -1, group),
                            w_o_norm.reshape(1, 1, -1, group), one, (3,),
                            eps, True, False, False)
        gated = (normed.reshape(bsz, s, h * d).astype(jnp.float32)
                 * jax.nn.sigmoid(z.astype(jnp.float32))).astype(dtype)
    w_out = normal_var(args, [inner] + feats)
    with jax.named_scope("out_proj"):
        out = _matmul("bsi,if->bsf", gated, w_out.data.reshape(h * d, f_sz)
                      ).astype(dtype)
    out = out.reshape([f.size for f in token_dims + feats])
    return transpose_to(nt(out, token_dims + feats), x.dims)


def _state_bytes(params: ModelParameter) -> int:
    """``[batch, sequence / lightning_chunk, held heads, d, d]`` float32: the
    states entering every chunk, what ``state_out`` and the inter-chunk
    scan's backward read."""
    s = params.sequence_dim.size
    return params.batch_dim.size \
        * max(1, s // min(params.lightning_chunk, s)) * _held(params) \
        * params.lightning_head_features ** 2 * 4


lightning.declares = Layer(
    stats=(Stat("lightning_state_abs_max", "gauge",
                "hbnlp_lightning_state_abs_max",
                "largest magnitude in any state S = sum lambda^(t - j) k_j^T "
                "v_j entering a chunk, newest finished step, all lightning "
                "layers: what the state's lower-precision matmul operand has "
                "to carry", "max"),),
    facts=FACTS, recurrent=Recurrent(_state_bytes, None))
