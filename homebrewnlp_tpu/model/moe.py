"""Dropless routed mixture of gated experts (layer ``moe``).

``out = sum_{e in top-k} p_e * down_e(act(gate_e x) * up_e x)`` with ``p`` the
float32 softmax of the router's logits over ALL experts, the ``k`` largest
taken as they are (not renormalised; OLMoE's ``norm_topk_prob`` false).  No
token is dropped and no expert is padded to a capacity: the (token, choice)
pairs are sorted by expert, their rows gathered, three grouped matmuls run
over the ``experts`` groups of whatever sizes the router made, the rows are
weighted and summed back per token.  Memory for the dispatch is
O(tokens * k * features), never O(tokens * experts * capacity).

``basic.routed_mixture_of_experts`` (one routed linear, capacity-padded
one-hot dispatch) stays beside it until ROADMAP D7 merges the two.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..config import BlockArgs
from ..core import scope
from ..core.dims import Dim
from ..core.tensor import NamedTensor, nt, transpose_to
from .activation import ACTIVATIONS
from .backend import normal_var
from .basic import _router_aux_inject
from .utils import anonymize_dim


# ---- dispatch and combine: gathers both ways ---------------------------------
#
# ``order`` lists the (token, choice) pairs sorted by expert, ``inverse`` is
# where each pair went.  A gather's transpose is a scatter-add, and XLA cannot
# know that these indices are permutations, so both directions of both
# functions are written as gathers, the backward passes by hand.

def _gather_sum(rows, inverse, k: int, weights=None):
    """``sum_j rows[inverse[t, j]] (* weights[t, j])`` in float32."""
    pairs = rows[inverse].reshape(-1, k, rows.shape[-1]).astype(jnp.float32)
    if weights is not None:
        pairs = pairs * weights[..., None]
    return jnp.sum(pairs, axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x, order, inverse, k: int):
    """Rows of ``x [t, f]`` for the sorted pairs: ``[t * k, f]``."""
    return x[order // k]


def _dispatch_fwd(x, order, inverse, k):
    return x[order // k], inverse


def _dispatch_bwd(k, inverse, g):
    return _gather_sum(g, inverse, k).astype(g.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _combine(rows, weights, order, inverse, k: int):
    """``out[t] = sum_j weights[t, j] * rows[inverse[t, j]]``: the sorted
    pairs' rows ``[t * k, f]`` weighted and summed back per token, float32
    sums returned in ``rows``' dtype."""
    return _gather_sum(rows, inverse, k, weights).astype(rows.dtype)


def _combine_fwd(rows, weights, order, inverse, k):
    return _combine(rows, weights, order, inverse, k), \
        (rows, weights, order, inverse)


def _combine_bwd(k, res, g):
    rows, weights, order, inverse = res
    # in sorted space: every pair's row of g, a gather from [t, f]
    spread = g[order // k]
    flat = weights.reshape(-1)
    d_rows = (spread.astype(jnp.float32) * flat[order][:, None]
              ).astype(rows.dtype)
    d_flat = jnp.sum(rows.astype(jnp.float32) * spread.astype(jnp.float32),
                     axis=-1)
    return d_rows, d_flat[inverse].reshape(weights.shape).astype(
        weights.dtype), None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


#: the names layer ``moe`` gives, by ``checkpoint_name``, to what is dear to
#: replay per byte: the three grouped matmuls' outputs and the routing triple.
#: Free where no policy names them; under the ``checkpoint`` strategy the
#: block's ``jax.checkpoint`` saves them where model/remat.py's ``experts``
#: kind rides (model/blocks.py ``_checkpoint_policy``), and the replay then
#: runs neither the three forward kernels (megablox keeps only its INPUTS as
#: residuals) nor the pairs' sort; the router's softmax and top-k stay in
#: it, their ``weights`` feed ``_combine``'s backward.
SAVED_NAMES = ("moe_gate", "moe_up", "moe_down",
               "moe_order", "moe_inverse", "moe_sizes")


#: rows, contraction and columns of one tile of the grouped-matmul kernel;
#: measured on a v5e at [65536, 2048] x [64, 2048, 1024], forward and
#: backward: (512, 1024, 1024) 104 TFLOP/s, (512, 512, 1024) 92,
#: (512, 1024, 512) 95, 1024 rows or 2048 deep exceed VMEM;
#: ``jax.lax.ragged_dot`` 51 (PERF.md section 6, PR 26)
_GMM_TILE = (512, 1024, 1024)


def grouped_dot(lhs, rhs, group_sizes):
    """``lhs [m, k]`` times ``rhs [groups, k, n]``, row ``i`` with the matrix
    of the group it falls in (``group_sizes`` consecutive rows each).  On a
    TPU the Pallas grouped matmul that ships with jax (megablox: tiles of
    rows that never straddle two groups' matrices, its own backward), on
    other backends — and at row counts no tile divides — XLA's
    ``ragged_dot``: the same split as flash attention's."""
    m, k = lhs.shape
    n = rhs.shape[-1]
    tile_m = next((t for t in (_GMM_TILE[0], 256, 128) if m % t == 0), None)
    if jax.default_backend() == "tpu" and tile_m is not None:
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
        return megablox.gmm(lhs, rhs, group_sizes, lhs.dtype,
                            (tile_m, min(k, _GMM_TILE[1]),
                             min(n, _GMM_TILE[2])))
    prefer = jnp.float32 if (lhs.dtype == jnp.bfloat16
                             and jax.default_backend() != "cpu") else None
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=prefer).astype(lhs.dtype)


def route(logits, top_k: int):
    """Float32 softmax over all experts and its ``top_k`` largest per token:
    ``(weights [t, k], experts [t, k])``."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jax.lax.top_k(probs, top_k)


def sort_pairs(experts, n_experts: int):
    """(token, choice) pairs sorted by expert, stably: ``order`` (sorted
    position -> pair), ``inverse`` (pair -> sorted position) and the
    experts' pair counts."""
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32))
    sizes = jnp.zeros((n_experts,), jnp.int32).at[flat].add(1)
    return order, inverse, sizes


def moe(args: BlockArgs) -> NamedTensor:
    """Layer ``moe``: ``experts`` experts of width ``intermediate``,
    ``moe_top_k`` a token; an activation name as flag (default ``silu``).
    Parameters, normal(0.02), in creation order: router
    ``[features, experts]``, gate and up ``[experts, features,
    intermediate]``, down ``[experts, intermediate, features]``."""
    params = args.params
    ctx = scope.current()
    if ctx.decode is not None:
        raise NotImplementedError("layer moe has no incremental decode form yet")
    if ctx.mesh is not None and ctx.mesh.size > 1:
        raise NotImplementedError(
            "layer moe on a mesh (expert-parallel dispatch) is a later issue")
    n_exp = params.expert_dim.size
    top_k = min(params.moe_top_k, n_exp)
    act = next((ACTIVATIONS[a] for a in args.name_extras if a in ACTIVATIONS),
               ACTIVATIONS["silu"])

    feats = list(params.feature_dims)
    anon = [anonymize_dim(d) for d in feats]
    inter = list(params.intermediate)
    x = args.tensor
    token_dims = [d for d in x.dims if d not in feats]
    t_sz = math.prod(d.size for d in token_dims)
    f_sz = math.prod(d.size for d in feats)
    i_sz = math.prod(d.size for d in inter)

    w_router = normal_var(args, anon + [params.expert_dim])
    w_gate = normal_var(args, [params.expert_dim] + anon + inter)
    w_up = normal_var(args, [params.expert_dim] + anon + inter)
    w_down = normal_var(args, [params.expert_dim] + inter + feats)

    xf = transpose_to(x, token_dims + feats).data.reshape(t_sz, f_sz)
    with jax.named_scope("router"):
        logits = jnp.dot(
            xf, w_router.data.reshape(f_sz, n_exp),
            preferred_element_type=None if jax.default_backend() == "cpu"
            else jnp.float32).astype(jnp.float32)
        wb, wz = float(params.moe_balance_loss), float(params.moe_router_z_loss)
        if params.train and (wb or wz):
            # one routing group: the balance term is over the step's tokens
            logits = _router_aux_inject(wb, wz, top_k, logits[None])[0]
        weights, experts = route(logits, top_k)
    with jax.named_scope("dispatch"):
        order, inverse, sizes = sort_pairs(experts, n_exp)
        order = checkpoint_name(order, "moe_order")
        inverse = checkpoint_name(inverse, "moe_inverse")
        sizes = checkpoint_name(sizes, "moe_sizes")
        rows = _dispatch(xf, order, inverse, top_k)
    if ctx.layer_stats is not None:
        ctx.layer_stats.append({
            # the largest expert's pair count over the mean: 1.0 = balanced
            "moe_load_max_over_mean":
                jnp.max(sizes).astype(jnp.float32) * n_exp / (t_sz * top_k),
            "moe_routed_pairs": jnp.sum(sizes).astype(jnp.float32)})
    with jax.named_scope("experts"):
        gate = checkpoint_name(grouped_dot(
            rows, w_gate.data.reshape(n_exp, f_sz, i_sz), sizes), "moe_gate")
        up = checkpoint_name(grouped_dot(
            rows, w_up.data.reshape(n_exp, f_sz, i_sz), sizes), "moe_up")
        hidden = act(args(nt(gate, [Dim("_pairs", t_sz * top_k),
                                    Dim("_width", i_sz)]))).data * up
        out = checkpoint_name(grouped_dot(
            hidden, w_down.data.reshape(n_exp, i_sz, f_sz), sizes), "moe_down")
    with jax.named_scope("combine"):
        out = _combine(out, weights, order, inverse, top_k)
    out = out.reshape([d.size for d in token_dims + feats])
    return transpose_to(nt(out, token_dims + feats), x.dims)
