"""Dropless routed mixture of gated experts (layer ``moe``).

``out = sum_{e in top-k} p_e * down_e(act(gate_e x) * up_e x)`` with ``p`` the
float32 softmax of the router's logits over ALL experts, the ``k`` largest
taken as they are (not renormalised; OLMoE's ``norm_topk_prob`` false).  The
logits are one matrix times the layer's OWN input ``x`` (OLMoE's router) —
unless a flag says otherwise: ``router_mlp`` (an MLP fed by the previous
layer's router state), ``sigmoid_bias`` (sigmoid scores and a selection bias)
and ``routed_early``, which moves the router OUT of the block: the logits were
made a block earlier, from the attention block's input, by layer
``route_early`` (model/route.py) and arrive as a carried side value; each has
its paragraph below.  No
token is dropped and no expert is padded to a capacity: the (token, choice)
pairs are sorted by expert, their rows gathered, three grouped matmuls run
over the ``experts`` groups of whatever sizes the router made, the rows are
weighted and summed back per token.  Memory for the dispatch is
O(tokens * k * features), never O(tokens * experts * capacity).

A layer told which experts it HOLDS (``experts_held`` consecutive ones from
``experts_first``; 0 = all) is one rank of an expert-parallel group: the
router keeps its ``experts`` outputs and its ``moe_top_k`` choices a token,
the layer computes its own experts' part of the sum for the pairs routed to
them, and what the absent experts would have added is left out (on one chip
it runs without the exchange that would bring the other ranks' tokens).
Still dropless, with static shapes: a token's choices are distinct, so at
most ``min(moe_top_k, experts_held)`` of them are held, and the row buffer
has that many SLOTS a token — ``held_rows_bound`` rows, which no routing can
overflow.  Each token's held choices fill its slots from the left, the rest
hold a sentinel group that sorts last, so the held pairs are the first
``n_real`` rows of the sorted buffer; megablox visits the held groups' row
tiles, the rows past them are never written, and nothing reads a sentinel
slot's row.  What surrounds the kernels takes one of two forms, by the fill
the configuration fixes (``walks_real_rows``: ``experts_held / experts x
moe_top_k / slots`` of the buffer when the router is balanced):

* at most an eighth full (Laguna's rank: 3.9%), everything WALKS THE REAL
  ROWS: dispatch, the fan-out to the gate and the up matmul, the gated
  activation and combine — each a ``custom_vjp`` with both directions by
  hand — visit ``ceil(n_real / tile)`` tiles of 512 rows in a ``lax`` loop
  whose trip count is known only on the device (forward, a block's replay
  and backward alike, on every backend).  The buffers keep the bound's
  rows; what lies past the last visited tile is never written (the buffers
  start uninitialised on a TPU, ``_fresh``) and never read, and the last
  tile's rows past ``n_real`` may hold anything: sums drop them by index;
* fuller than that (ZAYA1's rank: half), the whole buffer is gathered both
  ways as where every expert is held (``_dispatch`` / ``_combine``), the
  unreal slots selected away (``real``): a walked row's sum is XLA's row
  scatter-add, one row after another, and loses to the gather above ~10%
  real rows.

``moe_norm_topk`` renormalises the chosen probabilities to sum to one,
``moe_route_scale`` multiplies them; flag ``shared_expert`` (the DSL's
``shared`` is cross-layer weight sharing) adds a shared expert (a
SwiGLU of the experts' width that every token passes through, weight 1,
scope ``shared``) to the routed sum.

Flag ``router_mlp`` replaces the one matrix of logits by ZAYA1's router
(arXiv:2511.17127): a down-projection to ``moe_router_width`` with a bias,
plus the learned per-channel scale ``g`` times the router state ``r`` of the
PREVIOUS ``router_mlp`` layer (after that layer's own addition: depth
averaging; zero before the first), an RMSNorm with a learned scale, and a
three-layer GELU (erf) MLP to the ``experts`` logits, all in float32.  ``r``
leaves the layer beside the stream as a CARRIED SIDE VALUE
(``Context.side["router_state"]``, model/blocks.py): an explicit input and
output of every block's region, with its cotangent in the backward.  Only the
strategies that carry one (``checkpoint`` / ``none``, unrolled) run it.

Flag ``routed_early`` (SmallThinker, arXiv:2507.20984: pre-attention
routing) takes the logits ``[tokens, experts]`` that the ``route_early``
layer of an EARLIER block left in ``Context.side[ROUTER_LOGITS]``
(model/route.py: one matrix times the attention block's normed input) and
removes them from there: this layer makes no router matrix and runs no router
matmul, forward, replay or backward.  Softmax, top-k, ``moe_norm_topk``,
``moe_route_scale`` and the balance and z terms are the one-matrix router's;
their gradient leaves the block through the carried value's cotangent.  The
same strategies as ``router_mlp``; without a ``route_early`` before it the
layer refuses by name.  Such a layer reports the load over ALL the experts
(``moe_all_load_max_over_mean``), since its held share sees an eighth of it.

An activation's name as flag (``silu`` where none is given) is what gates an
expert: ``relu`` makes it ``down(relu(gate x) * up x)`` (SmallThinker's ReGLU),
on every path — all held, a share held walking the real rows, a share held
gathered — and such a layer reports the share of the held pairs' gate values
that ReLU leaves above zero (``moe_gate_live_share``: the zeros are what the
model's deployment skips).

Flag ``sigmoid_bias`` replaces the softmax by DeepSeek-V3's scoring
(arXiv:2412.19437 section 2.1.2): ``s = sigmoid(x W_r)`` in float32, the
choice ``T = top-k(s + b)`` with ``b [experts]`` the SELECTION BIAS, which
chooses only — the weights are ``moe_route_scale x s_e / (sum_T s + 1e-20)``
under ``moe_norm_topk``.  ``b`` is a parameter with NO gradient: the
backward hands the optimizer the step's pair counts of ALL experts as its
cotangent (``_balance_tap``), and ``optim/__init__.py selection_bias_rule``
moves it by ``moe_bias_rate x sign(mean - count)``, outside the chain.
``moe_balance_loss`` then weighs ``experts x sum_e f_e mean_t(s_e / sum s)``
(``f``: the choice's pair shares), injected into the scores' cotangent by the
same tap.  Flag ``plain`` makes an expert ``down(act(up x))``: two grouped
matmuls, no gate (``relu2``: Nemotron's).  Flag ``latent`` (LatentMoE) puts a
projection to ``moe_latent_width`` columns before dispatch (scope
``latent_down``) and one back after combine (``latent_up``): the rows, the
buffers, the gathers and the scatter-add are that wide, the experts are
``latent x width x latent``; the router and the shared expert read the
full-width input, and the shared expert (``shared_expert_width`` wide where
set) is added after the projection up.  Such a layer offers the memory
strategy the combined sum a TOKEN (``LATENT_SUM``) where the others offer
their grouped matmuls' outputs a pair.

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
from ..optim import SELECTION_BIAS
from .activation import ACTIVATIONS
from .backend import ConstantInit, NormalInit, normal_var
from .basic import _router_aux_inject
from .declare import Fact, Layer, Offer, Stat, layers
from .recurrent import _small_var
from .route import NO_SIDE_VALUES, ROUTER_LOGITS, matrix_logits
from .utils import anonymize_dim


# ---- dispatch and combine: gathers both ways ---------------------------------
#
# ``order`` lists the (token, choice) pairs sorted by expert, ``inverse`` is
# where each pair went.  A gather's transpose is a scatter-add, and XLA cannot
# know that these indices are permutations, so both directions of both
# functions are written as gathers, the backward passes by hand.

#
# ``real`` (``[t, k]`` booleans, or None where every pair is an expert's):
# the slots of a layer that holds a share of the experts which carry a held
# choice.  The rows of the others lie past the held groups, where no kernel
# wrote, so they are selected away and never multiplied.

def _gather_sum(rows, inverse, k: int, weights=None, real=None):
    """``sum_j rows[inverse[t, j]] (* weights[t, j])`` in float32, over the
    ``real`` pairs."""
    pairs = rows[inverse].reshape(-1, k, rows.shape[-1]).astype(jnp.float32)
    if weights is not None:
        pairs = pairs * weights[..., None]
    if real is not None:
        pairs = jnp.where(real[..., None], pairs, 0.0)
    return jnp.sum(pairs, axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x, order, inverse, k: int, real=None):
    """Rows of ``x [t, f]`` for the sorted pairs: ``[t * k, f]``."""
    return x[order // k]


def _dispatch_fwd(x, order, inverse, k, real=None):
    return x[order // k], (inverse, real)


def _dispatch_bwd(k, res, g):
    inverse, real = res
    return _gather_sum(g, inverse, k, real=real).astype(g.dtype), None, \
        None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _combine(rows, weights, order, inverse, k: int, real=None):
    """``out[t] = sum_j weights[t, j] * rows[inverse[t, j]]``: the sorted
    pairs' rows ``[t * k, f]`` weighted and summed back per token, float32
    sums returned in ``rows``' dtype."""
    return _gather_sum(rows, inverse, k, weights, real).astype(rows.dtype)


def _combine_fwd(rows, weights, order, inverse, k, real=None):
    return _combine(rows, weights, order, inverse, k, real), \
        (rows, weights, order, inverse, real)


def _combine_bwd(k, res, g):
    rows, weights, order, inverse, real = res
    # in sorted space: every pair's row of g, a gather from [t, f]
    spread = g[order // k]
    flat = weights.reshape(-1)
    d_rows = (spread.astype(jnp.float32) * flat[order][:, None]
              ).astype(rows.dtype)
    d_flat = jnp.sum(rows.astype(jnp.float32) * spread.astype(jnp.float32),
                     axis=-1)
    d_weights = d_flat[inverse].reshape(weights.shape)
    if real is not None:
        d_weights = jnp.where(real, d_weights, 0.0)
    return d_rows, d_weights.astype(weights.dtype), None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


# ---- a layer that holds a small share: the same passes over the real rows ----
#
# The held pairs are the first ``n_real`` rows of the sorted buffer (the
# sentinel group sorts last).  Everything round the grouped matmuls walks
# ``ceil(n_real / tile)`` row tiles of it in a ``lax`` loop whose trip count
# only the device knows, and never reads a row past them: the buffer starts
# uninitialised (``_fresh``), the last tile's rows past ``n_real`` are
# dropped by index, not multiplied away (they may hold anything).  A sum over
# a token's slots is the scatter direction here — a loop over the real rows
# adds each into its token — where the all-held forms above gather every
# slot; each pass is a ``custom_vjp`` with the backward's loop by hand, so
# nothing differentiates through a loop.

def _row_tile(rows: int) -> int:
    """Rows of one tile of the held path's loops: the grouped matmul's own
    row tile, or the largest part of it that divides the buffer."""
    return math.gcd(rows, _GMM_TILE[0])


def _real_tiles(n_real, rows: int):
    """How many tiles of a ``rows``-row buffer hold one of its ``n_real``
    real rows (they come first): the trip count of every loop below."""
    tile = _row_tile(rows)
    return (n_real + tile - 1) // tile


def _over_real_tiles(n_real, rows: int, body, init):
    """``body(first row, rows of the tile, carry) -> carry`` over those
    tiles."""
    tile = _row_tile(rows)
    return jax.lax.fori_loop(
        0, _real_tiles(n_real, rows),
        lambda i, carry: body(i * tile, tile, carry), init)


def _cut(buffer, at, tile: int):
    """Rows ``at .. at + tile - 1`` of ``buffer [rows, width]``."""
    return jax.lax.dynamic_slice(buffer, (at, 0), (tile, buffer.shape[1]))


def _fresh(after, shape, dtype, name: str = "moe_held_rows_alloc"):
    """An uninitialised ``shape`` buffer for a loop to fill, which exists
    only once ``after`` does.  ``lax.empty`` alone is an operand-less
    ``AllocateBuffer`` on a TPU, and XLA schedules every one of a step's at
    its start: all layers' row buffers alive at once, 19 GB for the Laguna
    cell's step (compiled for a described v5e, PR 47).  A Pallas call that
    does nothing is a custom call like any other: scheduled where its
    operand is ready, it hands out its never-written output.  Two of one
    shape after one array take two names, or XLA makes them one call and
    copies its output."""
    if jax.default_backend() != "tpu":
        return jax.lax.empty(shape, dtype)
    from jax.experimental import pallas as pl
    return pl.pallas_call(
        lambda after_ref, out_ref: None,
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        name=name)(after)


def _real_pairs(order, n_real, at, tile: int, k: int):
    """The tile's pairs and their tokens, each ``[tile]``; a row past the
    real ones gets the first index out of range, which a scatter drops (a
    gather clips it)."""
    pairs = jax.lax.dynamic_slice(order, (at,), (tile,))
    live = at + jnp.arange(tile, dtype=jnp.int32) < n_real
    return jnp.where(live, pairs, order.shape[0]), \
        jnp.where(live, pairs // k, order.shape[0] // k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch_held(x, order, n_real, k: int):
    """Rows of ``x [t, f]`` for the real prefix of the sorted pairs, in a
    ``[t * k, f]`` buffer whose other tiles are never written."""
    def body(at, tile, rows):
        _, tokens = _real_pairs(order, n_real, at, tile, k)
        return jax.lax.dynamic_update_slice(
            rows, x.at[tokens].get(mode="clip"), (at, 0))
    return _over_real_tiles(
        n_real, order.shape[0], body,
        _fresh(x, (order.shape[0], x.shape[1]), x.dtype))


def _dispatch_held_fwd(x, order, n_real, k):
    return _dispatch_held(x, order, n_real, k), (order, n_real)


def _dispatch_held_bwd(k, res, g):
    order, n_real = res

    def body(at, tile, d_x):
        _, tokens = _real_pairs(order, n_real, at, tile, k)
        return d_x.at[tokens].add(_cut(g, at, tile).astype(jnp.float32),
                                  mode="drop")
    d_x = _over_real_tiles(
        n_real, order.shape[0], body,
        jnp.zeros((order.shape[0] // k, g.shape[1]), jnp.float32))
    return d_x.astype(g.dtype), None, None


_dispatch_held.defvjp(_dispatch_held_fwd, _dispatch_held_bwd)


@jax.custom_vjp
def _twice_held(rows, n_real):
    """``(rows, rows)`` for the gate and the up matmul: their two cotangents
    are added over the tiles that hold real rows, where autodiff's own sum
    passes over the whole buffer."""
    return rows, rows


def _twice_held_fwd(rows, n_real):
    return (rows, rows), n_real


def _twice_held_bwd(n_real, grads):
    def body(at, tile, total):
        # in place: the first cotangent's buffer takes the sum
        return jax.lax.dynamic_update_slice(
            total, _cut(total, at, tile) + _cut(grads[1], at, tile), (at, 0))
    return _over_real_tiles(n_real, grads[0].shape[0], body, grads[0]), None


_twice_held.defvjp(_twice_held_fwd, _twice_held_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gated_held(gated, operands, n_real):
    """``gated(*operands)`` (``act(gate) * up`` of two row tiles; a ``plain``
    expert's ``act(up)`` of one) on the tiles that hold real rows of the
    ``operands``, each ``[rows, width]``."""
    first = operands[0]

    def body(at, tile, hidden):
        return jax.lax.dynamic_update_slice(
            hidden, gated(*(_cut(o, at, tile) for o in operands)), (at, 0))
    return _over_real_tiles(n_real, first.shape[0], body,
                            _fresh(first, first.shape, first.dtype))


def _gated_held_fwd(gated, operands, n_real):
    return _gated_held(gated, operands, n_real), (operands, n_real)


def _gated_held_bwd(gated, res, g):
    operands, n_real = res

    def body(at, tile, grads):
        parts = jax.vjp(gated, *(_cut(o, at, tile) for o in operands))[1](
            _cut(g, at, tile))
        return tuple(jax.lax.dynamic_update_slice(d, part, (at, 0))
                     for d, part in zip(grads, parts))
    return _over_real_tiles(
        n_real, operands[0].shape[0], body,
        tuple(_fresh(g, o.shape, o.dtype, name) for o, name in zip(
            operands, ("moe_held_rows_alloc", "moe_held_rows_alloc_up")))
    ), None


_gated_held.defvjp(_gated_held_fwd, _gated_held_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _combine_held(rows, weights, order, n_real, k: int):
    """``out[t] = sum of weights[t, j] * rows[position of (t, j)]`` over the
    token's real slots: each real row of ``rows [t * k, f]`` weighted and
    added into its token, float32 sums returned in ``rows``' dtype."""
    flat = weights.reshape(-1)

    def body(at, tile, out):
        pairs, tokens = _real_pairs(order, n_real, at, tile, k)
        return out.at[tokens].add(
            _cut(rows, at, tile).astype(jnp.float32)
            * flat.at[pairs].get(mode="clip")[:, None], mode="drop")
    return _over_real_tiles(
        n_real, rows.shape[0], body,
        jnp.zeros((rows.shape[0] // k, rows.shape[1]), jnp.float32)
    ).astype(rows.dtype)


def _combine_held_fwd(rows, weights, order, n_real, k):
    return _combine_held(rows, weights, order, n_real, k), \
        (rows, weights, order, n_real)


def _combine_held_bwd(k, res, g):
    rows, weights, order, n_real = res
    flat = weights.reshape(-1)

    def body(at, tile, grads):
        d_rows, d_flat = grads
        pairs, tokens = _real_pairs(order, n_real, at, tile, k)
        # every real pair's row of g, a gather from [t, f]
        spread = g.at[tokens].get(mode="clip").astype(jnp.float32)
        d_rows = jax.lax.dynamic_update_slice(
            d_rows, (spread * flat.at[pairs].get(mode="clip")[:, None]
                     ).astype(rows.dtype), (at, 0))
        return d_rows, d_flat.at[pairs].set(
            jnp.sum(_cut(rows, at, tile).astype(jnp.float32) * spread,
                    axis=-1), mode="drop")
    d_rows, d_flat = _over_real_tiles(
        n_real, rows.shape[0], body,
        (_fresh(g, rows.shape, rows.dtype),
         jnp.zeros(flat.shape, jnp.float32)))
    return d_rows, d_flat.reshape(weights.shape).astype(weights.dtype), \
        None, None


_combine_held.defvjp(_combine_held_fwd, _combine_held_bwd)


#: the names layer ``moe`` gives, by ``checkpoint_name``, to what is dear to
#: replay per byte: the three grouped matmuls' outputs, the routing triple and
#: the router's CHOICE.  Free where no policy names them; under the
#: ``checkpoint`` strategy the block's ``jax.checkpoint`` saves them where
#: model/remat.py's ``experts`` kind rides (model/blocks.py
#: ``_checkpoint_policy``), and the replay then runs neither the three
#: forward kernels (megablox keeps only its INPUTS as residuals) nor the
#: pairs' sort; the router's softmax and top-k stay in it, their ``weights``
#: feed ``_combine``'s backward.  The choice (``moe_experts``, which experts each
#: token took) is saved WITH the sort it made: a replay that chose again
#: could choose otherwise where two probabilities lie within a rounding of
#: each other (its fusions are not the forward's), and then read, by the
#: saved ``inverse``, a row that belongs to another choice — or, in a layer
#: that holds a share, a row no kernel wrote (my chip runs, PR 39: one NaN
#: in 16,384 x 2,048 cotangents a step at ZAYA1's top-1 router, whose
#: logits lie ~0.01 apart at initialisation).
SAVED_NAMES = ("moe_gate", "moe_up", "moe_down",
               "moe_order", "moe_inverse", "moe_sizes", "moe_experts")

#: what a ``latent`` layer offers in the place of the grouped matmuls'
#: outputs: the combined sum ``[tokens, moe_latent_width]``, the projection
#: up's operand.  A pair's row costs ``intermediate + latent`` columns to keep
#: and two grouped matmuls at the latent's width to make again; the sum is
#: ``latent`` columns a TOKEN, and the replay that holds it runs no combine:
#: one scatter-add a real row less (0.08 us a row at 1,024 columns; my chip
#: runs, PR 54)
LATENT_SUM = "moe_latent_sum"


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


def route(logits, top_k: int, norm_topk: bool = False, scale: float = 1.0):
    """Float32 softmax over all experts and its ``top_k`` largest per token:
    ``(weights [t, k], experts [t, k])``; with ``norm_topk`` the chosen
    probabilities are divided by their sum, and ``scale`` multiplies them
    (``w_e = scale * p_e / sum_{top-k} p``)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, experts = jax.lax.top_k(probs, top_k)
    # the choice by its name (SAVED_NAMES): a replay that holds the saved
    # choice sorts and selects by it; its own top-k still gives the weights
    # (at a near-tie the j-th largest of two equal probabilities).  Gathering
    # the weights from the saved choice instead cost the Laguna cell 3.7% and
    # the OLMoE cell 1.3-4.4% (my chip runs, PR 39: a [tokens, experts]
    # gather and its scatter a layer)
    experts = checkpoint_name(experts, "moe_experts")
    if norm_topk:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
    return weights, experts


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _balance_tap(wb: float, scores, bias, counts):
    """The scores as they are; the backward hands ``bias`` the step's pair
    counts ``[experts]`` as its cotangent — what
    ``optim/__init__.py selection_bias_rule`` reads; the bias has no gradient
    — and, where ``wb``, adds the balance term's gradient ``wb x experts x
    sum_e f_e mean_t(s_e / sum s)`` (``f = counts / sum counts``, constant) to
    the scores'.  Like ``_router_aux_inject``: nothing has to leave the block
    stack, so a block's replay and gradient accumulation need no care (the
    counts of the micro batches add up)."""
    return scores


def _balance_tap_fwd(wb, scores, bias, counts):
    return scores, (scores, counts)


def _balance_tap_bwd(wb, res, g):
    scores, counts = res
    if wb:
        share = counts / jnp.maximum(jnp.sum(counts), 1.0)
        g = g + jax.grad(lambda s: wb * s.shape[-1] * jnp.sum(share * jnp.mean(
            s / jnp.sum(s, axis=-1, keepdims=True), axis=0)))(scores
                                                              ).astype(g.dtype)
    return g, counts, jnp.zeros_like(counts)


_balance_tap.defvjp(_balance_tap_fwd, _balance_tap_bwd)


def route_sigmoid(logits, bias, top_k: int, norm_topk: bool = True,
                  scale: float = 1.0, balance: float = 0.0,
                  train: bool = False):
    """Float32 sigmoid scores, the ``top_k`` largest of ``scores + bias``
    chosen and the SCORES of the chosen as weights (``norm_topk``: over their
    sum + 1e-20; times ``scale``): ``(weights [t, k], experts [t, k], pair
    counts [experts])``.  The chosen scores by comparing and selecting, no
    gather over ``[t, experts]`` (``held_slots``); a replay that holds the
    saved choice weighs exactly what the forward chose.  ``train``: the
    scores pass ``_balance_tap``."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, experts = jax.lax.top_k(scores + bias, top_k)
    experts = checkpoint_name(experts, "moe_experts")
    chose = experts[..., None] == jnp.arange(scores.shape[-1],
                                             dtype=experts.dtype)
    counts = jnp.sum(chose, axis=(0, 1), dtype=jnp.float32)
    if train:
        scores = _balance_tap(balance, scores, bias, counts)
    weights = jnp.sum(jnp.where(chose, scores[:, None, :], 0.0), axis=-1)
    if norm_topk:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    if scale != 1.0:
        weights = weights * scale
    return weights, experts, counts


#: the key of the router state in ``Context.side``
ROUTER_STATE = "router_state"

#: the flags layer ``moe`` knows beside an activation's name
_FLAGS = ("shared_expert", "router_mlp", "plain", "latent", "sigmoid_bias",
          "routed_early")

# ``SELECTION_BIAS``: the scope the selection bias is made under — its name
# holds it, which is how ``optim/__init__.py OWN_RULES`` knows the leaf its
# own rule moves


def _router_mlp(args: BlockArgs, xf, anon, ctx):
    """ZAYA1's router on the rows ``xf [t, f]`` (module docstring): the
    ``experts`` logits ``[t, experts]`` in float32.  Reads the previous
    layer's router state from ``ctx.side`` and leaves its own there.
    Parameters in creation order: ``Wd [features, width]`` and the three
    MLP matrices ``[width, width]``, ``[width, width]``, ``[width,
    experts]`` normal(0.02), each followed by what goes with it: ``bd`` (0),
    ``g`` (1), the norm's scale (1) after ``Wd``; ``b1``, ``b2`` (0) after
    ``W1``, ``W2``; ``W3`` has no bias."""
    params = args.params
    if ctx.side is None:
        raise NotImplementedError(
            "layer moe's router_mlp hands its router state to the next "
            "layer's router, a carried side value that only the unrolled "
            f"checkpoint / none strategies hold: {NO_SIDE_VALUES}")
    width = Dim("router_width", params.moe_router_width)
    hidden = Dim("_router_width", width.size)
    w_sz, f_sz = width.size, xf.shape[-1]
    f32 = jnp.float32

    def vector(value: float, dim=width):
        return _small_var(args, "constant_var", [dim], ConstantInit(value))

    def matrix(shape):
        # the MLP in float32: the master, not a rounding of it
        return _small_var(args, "normal_var", shape, NormalInit())

    with jax.named_scope("down"):
        # the one wide matrix: read in the calculation dtype with float32
        # accumulation, as the one-matrix router's
        w_down, b_down = normal_var(args, anon + [width]), vector(0.0)
        state = jnp.dot(
            xf, w_down.data.reshape(f_sz, w_sz),
            preferred_element_type=None if jax.default_backend() == "cpu"
            else f32).astype(f32) + b_down
    with jax.named_scope("carry"):
        gain = vector(1.0)
        previous = ctx.side.get(ROUTER_STATE)
        if previous is not None:
            state = state + gain * previous.reshape(state.shape)
        ctx.side[ROUTER_STATE] = state
    with jax.named_scope("mlp"):
        scale = vector(1.0)
        u = state * jax.lax.rsqrt(jnp.mean(jnp.square(state), axis=-1,
                                           keepdims=True)
                                  + params.norm_epsilon) * scale
        w1, b1 = matrix([hidden, width]), vector(0.0)
        u = jax.nn.gelu(jnp.dot(u, w1) + b1, approximate=False)
        w2, b2 = matrix([hidden, width]), vector(0.0)
        u = jax.nn.gelu(jnp.dot(u, w2) + b2, approximate=False)
        return jnp.dot(u, matrix([hidden, params.expert_dim]))


def _early_logits(ctx, tokens: int, experts: int):
    """The logits ``[tokens, experts]`` that layer ``route_early``
    (model/route.py) left in ``ctx.side``, taken out of it: they go no
    further than this layer."""
    if ctx.side is None:
        raise NotImplementedError(
            "layer moe's routed_early takes its router's logits from an "
            "earlier block's route_early layer, a carried side value that "
            "only the unrolled checkpoint / none strategies hold: "
            f"{NO_SIDE_VALUES}")
    if ROUTER_LOGITS not in ctx.side:
        raise ValueError(
            "layer moe's routed_early found no carried side value "
            f"{ROUTER_LOGITS!r}: it needs a route_early layer in an earlier "
            "block (['norm-rms-scale', 'route_early', 'attention-...']), one "
            "for each routed_early layer")
    logits = ctx.side.pop(ROUTER_LOGITS)
    if logits.shape != (tokens, experts):
        raise ValueError(
            f"layer moe's routed_early got logits {logits.shape} from "
            f"route_early where it routes [{tokens}, {experts}]")
    return logits


def _load_max_over_mean(counts):
    """The busiest expert's pairs over the mean of ``counts [experts]``."""
    return jnp.max(counts) * counts.shape[-1] \
        / jnp.maximum(jnp.sum(counts), 1.0)


def _gate_live(gate, n_real):
    """How many values of the first ``n_real`` rows of ``gate [rows, width]``
    lie above zero (float32): the real rows come first on every path, and
    what lies past them may hold anything."""
    def body(at, tile, total):
        live = at + jnp.arange(tile, dtype=jnp.int32) < n_real
        return total + jnp.sum((_cut(gate, at, tile) > 0) & live[:, None],
                               dtype=jnp.float32)
    return _over_real_tiles(n_real, gate.shape[0], body, jnp.float32(0))


def held_rows_bound(tokens: int, top_k: int, held: int) -> int:
    """Rows of the static dispatch buffer of a layer that holds ``held``
    experts: ``min(top_k, held)`` slots a token (a token's choices are
    distinct, so no routing overflows them)."""
    return tokens * min(top_k, held)


def walks_real_rows(experts: int, held: int, top_k: int) -> bool:
    """Whether a layer that holds ``held`` of ``experts`` experts walks the
    real rows of its static buffer (``_dispatch_held`` and the passes after
    it) or moves the whole buffer (``_dispatch`` / ``_combine`` with
    ``real``): by the fill the configuration fixes, ``held / experts x top_k
    / slots`` of the buffer when the router is balanced, at most an eighth.
    A walked row costs a sum 0.25-0.33 us (XLA's row scatter-add, one row
    after another), a gathered slot 0.04-0.06: on a v5e the passes meet at
    9-14% real rows (my chip runs, PR 47: the Laguna cell, 3.9% by this rule,
    +22% with the walk; the ZAYA1 cell, 50%, -8.4% with it)."""
    return 0 < held < experts \
        and 8 * held * top_k <= experts * min(top_k, held)


def held_slots(weights, experts, first: int, held: int):
    """A token's choices among experts ``first .. first + held - 1``, moved
    to the left of ``min(top_k, held)`` slots in the order it made them:
    ``(weights, local expert, real)``, each ``[t, slots]``; a slot without a
    held choice has weight 0, expert ``held`` (the sentinel group, which
    sorts last) and ``real`` false.  By counting — a held choice's slot is
    the number of held choices before it — and selecting: no sort and no
    gather over ``[t, top_k]`` (two ``take_along_axis`` here were 24 ms of
    the Laguna cell's step; my chip run, PR 47)."""
    local = experts - first
    inside = (local >= 0) & (local < held)
    slots = min(experts.shape[-1], held)
    # [t, slots, top_k]: choice c of the token sits in slot j
    sits = inside[:, None, :] & (
        (jnp.cumsum(inside, axis=-1, dtype=jnp.int32) - 1)[:, None, :]
        == jnp.arange(slots, dtype=jnp.int32)[:, None])
    real = jnp.any(sits, axis=-1)
    return jnp.sum(jnp.where(sits, weights[:, None, :], 0.0), axis=-1), \
        jnp.where(real, jnp.sum(jnp.where(sits, local[:, None, :], 0),
                                axis=-1), held), real


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


def sort_held(local, held: int):
    """The slots of ``held_slots`` sorted by expert, stably: ``order``
    (sorted position -> slot ``t * slots + j``; the held pairs come first,
    the sentinel slots after them, where nothing reads it) and the pair
    counts of the ``held`` experts, the sentinel group's last.  The counts
    by comparing, not by a scatter-add over the bound (1.15 ms a call on a
    v5e, the sort 0.1; my chip run, PR 47); no ``inverse``: every sum over a
    token's slots walks the real rows and adds (``_combine_held``)."""
    flat = local.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    sizes = jnp.sum(flat[:, None] == jnp.arange(held + 1, dtype=flat.dtype),
                    axis=0, dtype=jnp.int32)
    return order, sizes


def _dense(lhs, weight, shape):
    """``lhs [t, a]`` times the parameter ``weight`` read as ``shape [a,
    b]``, float32 accumulation off the CPU, in ``lhs``' dtype."""
    return jnp.dot(lhs, weight.data.reshape(shape).astype(lhs.dtype),
                   preferred_element_type=None
                   if jax.default_backend() == "cpu" else jnp.float32
                   ).astype(lhs.dtype)


def _shared_expert(args: BlockArgs, act, xf, anon, inter, feats,
                   plain: bool = False):
    """The shared expert: ``down(act(gate x) * up x)`` on every token's row
    of ``xf [t, f]``, three matrices created in the order gate, up, down;
    ``plain``: ``down(act(up x))``, two."""
    f_sz, i_sz = xf.shape[-1], math.prod(d.size for d in inter)

    def activated(rows):
        return act(args(nt(rows, [Dim("_tokens", xf.shape[0]),
                                  Dim("_width", i_sz)]))).data

    if plain:
        hidden = activated(_dense(xf, normal_var(args, anon + inter),
                                  (f_sz, i_sz)))
    else:
        gate = _dense(xf, normal_var(args, anon + inter), (f_sz, i_sz))
        up = _dense(xf, normal_var(args, anon + inter), (f_sz, i_sz))
        hidden = activated(gate) * up
    return _dense(hidden, normal_var(
        args, inter + feats,
        stddev=args.params.residual_out_stddev or 0.02), (i_sz, f_sz))


def moe(args: BlockArgs) -> NamedTensor:
    """Layer ``moe``: ``experts`` experts of width ``expert_width`` (0 = the
    dense MLP's ``intermediate``), ``moe_top_k`` a token; an activation name
    as flag (default ``silu``), ``shared_expert`` for a shared expert beside
    them.
    The layer holds ``experts_held`` of them from ``experts_first`` on (0 =
    all, OLMoE's form).  Parameters, normal(0.02), in creation order: router
    ``[features, experts]``, gate and up ``[held, features, width]``, down
    ``[held, width, features]``, then the shared expert's gate, up
    ``[features, width]`` and down ``[width, features]``.  Flags
    ``sigmoid_bias``, ``plain`` and ``latent`` (module docstring) add the
    selection bias ``[experts]`` (0) after the router and the latent's
    projection down ``[features, moe_latent_width]`` after it, drop every
    gate, and put the projection up ``[moe_latent_width, features]`` after
    the experts' down.  The three that write towards the stream — the
    experts' down, the latent's projection up and the shared expert's down —
    are normal(``residual_out_stddev``) where that key is set."""
    params = args.params
    ctx = scope.current()
    if ctx.decode is not None:
        raise NotImplementedError("layer moe has no incremental decode form yet")
    if ctx.mesh is not None and ctx.mesh.size > 1:
        raise NotImplementedError(
            "layer moe on a mesh (expert-parallel dispatch) is a later issue")
    unknown = [a for a in args.name_extras
               if a not in ACTIVATIONS
               and a not in _FLAGS]
    if unknown:
        raise ValueError(f"layer moe does not know flag(s) {unknown} (known: "
                         f"an activation's name, {', '.join(_FLAGS)})")
    router_mlp, plain, latent, biased, early = (
        flag in args.name_extras for flag in (
            "router_mlp", "plain", "latent", "sigmoid_bias", "routed_early"))
    if early and (router_mlp or biased):
        raise NotImplementedError(
            "layer moe's routed_early takes the one-matrix softmax router's "
            "logits from a route_early layer: router_mlp and sigmoid_bias "
            "have no early form yet")
    if biased and (router_mlp or params.scan_layers
                   or params.pipeline_stages > 1):
        raise NotImplementedError(
            "layer moe's sigmoid_bias (a selection bias the optimizer moves "
            "by the step's pair counts) runs with the one-matrix router on "
            "the unrolled strategies: router_mlp, scan_layers and "
            "pipeline_stages > 1 have no form of it yet")
    if latent and not params.moe_latent_width:
        raise ValueError("layer moe's flag latent needs moe_latent_width")
    n_exp = params.expert_dim.size
    top_k = min(params.moe_top_k, n_exp)
    held, first = params.experts_held or n_exp, params.experts_first
    partial = held < n_exp
    held_dim = Dim("experts", held) if partial else params.expert_dim
    act_name = next((a for a in args.name_extras if a in ACTIVATIONS), "silu")
    act = ACTIVATIONS[act_name]

    feats = list(params.feature_dims)
    anon = [anonymize_dim(d) for d in feats]
    inter = list(params.expert_intermediate)
    x = args.tensor
    token_dims = [d for d in x.dims if d not in feats]
    t_sz = math.prod(d.size for d in token_dims)
    f_sz = math.prod(d.size for d in feats)
    i_sz = math.prod(d.size for d in inter)

    # what the experts read and write: the stream, or the latent
    row_dims, rows_anon, r_sz = feats, anon, f_sz
    if not router_mlp and not early:
        w_router = normal_var(args, anon + [params.expert_dim])
    if biased:
        bias = _small_var(args, SELECTION_BIAS, [params.expert_dim],
                          ConstantInit(0.0))
    if latent:
        row_dims = [Dim("moe_latent", params.moe_latent_width)]
        rows_anon, r_sz = [anonymize_dim(row_dims[0])], row_dims[0].size
        w_latent_down = normal_var(args, anon + row_dims)
    if not plain:
        w_gate = normal_var(args, [held_dim] + rows_anon + inter)
    w_up = normal_var(args, [held_dim] + rows_anon + inter)
    w_down = normal_var(args, [held_dim] + inter + row_dims,
                        stddev=params.residual_out_stddev or 0.02)
    if latent:
        w_latent_up = normal_var(args, rows_anon + feats,
                                 stddev=params.residual_out_stddev or 0.02)

    xf = transpose_to(x, token_dims + feats).data.reshape(t_sz, f_sz)
    counts = None
    with jax.named_scope("router"):
        if early:
            with jax.named_scope("carried"):
                logits = _early_logits(ctx, t_sz, n_exp)
        else:
            logits = _router_mlp(args, xf, anon, ctx) if router_mlp \
                else matrix_logits(xf, w_router)
        wb, wz = float(params.moe_balance_loss), float(params.moe_router_z_loss)
        if biased:
            if wz:
                raise ValueError("moe_router_z_loss has no form on sigmoid "
                                 "scores (layer moe, flag sigmoid_bias)")
            weights, experts, counts = route_sigmoid(
                logits, bias, top_k, params.moe_norm_topk,
                float(params.moe_route_scale), wb, params.train)
        else:
            if params.train and (wb or wz):
                # one routing group: the balance term is over the step's
                # tokens
                logits = _router_aux_inject(wb, wz, top_k, logits[None])[0]
            weights, experts = route(logits, top_k, params.moe_norm_topk,
                                     float(params.moe_route_scale))
    if ctx.layer_stats is not None and top_k == 1:
        # the chosen expert's probability, the mean over the step's tokens:
        # 1 / experts = a router that says nothing
        ctx.layer_stats.append({"moe_top1_weight_mean": jnp.mean(weights)})
    if ctx.layer_stats is not None and early:
        # over ALL the experts: the held share below sees its own alone
        ctx.layer_stats.append({
            "moe_all_load_max_over_mean": _load_max_over_mean(jnp.sum(
                experts[..., None] == jnp.arange(n_exp, dtype=experts.dtype),
                axis=(0, 1), dtype=jnp.float32))})
    if ctx.layer_stats is not None and biased:
        ctx.layer_stats.append({
            "moe_bias_abs_max": jnp.max(jnp.abs(bias)),
            # over ALL the experts, from the counts the bias's rule reads
            "moe_all_load_max_over_mean": _load_max_over_mean(counts)})
    if latent:
        with jax.named_scope("latent_down"):
            xr = _dense(xf, w_latent_down, (f_sz, r_sz))
    else:
        xr = xf
    # a layer that holds a share sorts SLOTS (held_slots), not choices, into
    # held + 1 groups, the sentinel last; its kernels see the held groups,
    # and where the buffer is mostly empty so does everything round them
    tiled = walks_real_rows(n_exp, held, top_k)
    real, slots, groups, n_real = None, top_k, n_exp, None
    with jax.named_scope("dispatch"):
        if partial:
            weights, experts, real = held_slots(weights, experts, first, held)
            slots, groups = weights.shape[-1], held + 1
        if tiled:
            order, sizes = (checkpoint_name(a, name) for a, name in zip(
                sort_held(experts, held), ("moe_order", "moe_sizes")))
            n_real = jnp.sum(sizes[:held])
            rows = _dispatch_held(xr, order, n_real, slots)
        else:
            order, inverse, sizes = sort_pairs(experts, groups)
            order = checkpoint_name(order, "moe_order")
            inverse = checkpoint_name(inverse, "moe_inverse")
            sizes = checkpoint_name(sizes, "moe_sizes")
            rows = _dispatch(xr, order, inverse, slots, real)
        if partial:
            sizes = sizes[:held]
    if ctx.layer_stats is not None and partial:
        held_pairs = jnp.sum(sizes).astype(jnp.float32)
        ctx.layer_stats.append({
            # over the HELD experts: the busiest one's pairs over their mean
            "moe_load_max_over_mean":
                jnp.max(sizes).astype(jnp.float32) * held
                / jnp.maximum(held_pairs, 1.0),
            "moe_routed_pairs": jnp.float32(t_sz * top_k),
            "moe_held_pairs": held_pairs,
            # the tiled passes' trip count, and the tiles of the bound
            **({"moe_held_row_tiles":
                _real_tiles(n_real, t_sz * slots).astype(jnp.float32),
                "moe_held_bound_tiles":
                jnp.float32(t_sz * slots // _row_tile(t_sz * slots))}
               if tiled else {})})
    elif ctx.layer_stats is not None:
        ctx.layer_stats.append({
            # the largest expert's pair count over the mean: 1.0 = balanced
            "moe_load_max_over_mean":
                jnp.max(sizes).astype(jnp.float32) * n_exp / (t_sz * top_k),
            "moe_routed_pairs": jnp.sum(sizes).astype(jnp.float32)})

    def activated(rows):
        return act(args(nt(rows, [Dim("_pairs", rows.shape[0]),
                                  Dim("_width", i_sz)]))).data

    def gated(gate, up):
        return activated(gate) * up

    with jax.named_scope("experts"):
        if plain:
            up = checkpoint_name(grouped_dot(
                rows, w_up.data.reshape(held, r_sz, i_sz), sizes), "moe_up")
            hidden = _gated_held(activated, (up,), n_real) if tiled \
                else activated(up)
        else:
            rows, rows_up = _twice_held(rows, n_real) if tiled \
                else (rows, rows)
            gate = checkpoint_name(grouped_dot(
                rows, w_gate.data.reshape(held, r_sz, i_sz), sizes),
                "moe_gate")
            up = checkpoint_name(grouped_dot(
                rows_up, w_up.data.reshape(held, r_sz, i_sz), sizes),
                "moe_up")
            hidden = _gated_held(gated, (gate, up), n_real) if tiled \
                else gated(gate, up)
            if ctx.layer_stats is not None and act_name == "relu":
                real_rows = jnp.sum(sizes)
                ctx.layer_stats.append({
                    "moe_gate_live": _gate_live(gate, real_rows),
                    "moe_gate_values": real_rows.astype(jnp.float32) * i_sz})
        out = checkpoint_name(grouped_dot(
            hidden, w_down.data.reshape(held, i_sz, r_sz), sizes), "moe_down")
    with jax.named_scope("combine"):
        out = _combine_held(out, weights, order, n_real, slots) if tiled \
            else _combine(out, weights, order, inverse, slots, real)
    if latent:
        out = checkpoint_name(out, LATENT_SUM)
        with jax.named_scope("latent_up"):
            out = _dense(out, w_latent_up, (r_sz, f_sz))
    if "shared_expert" in args.name_extras:
        with jax.named_scope("shared"):
            wide = [Dim("shared_intermediate", params.shared_expert_width)] \
                if params.shared_expert_width else inter
            out = out + _shared_expert(args, act, xf, anon, wide, feats,
                                       plain)
    out = out.reshape([d.size for d in token_dims + feats])
    return transpose_to(nt(out, token_dims + feats), x.dims)


def moe_held_rows(params) -> int:
    """Rows of the static dispatch buffer of a ``moe`` layer that holds a
    share of the experts (``held_rows_bound``) for one micro batch; 0 where
    no layer holds a share."""
    if not 0 < params.experts_held < params.expert_dim.size \
            or not any(spec is moe.declares for _, _, spec in layers(params)):
        return 0
    return held_rows_bound(
        params.batch_dim.size * params.sequence_dim.size,
        min(params.moe_top_k, params.expert_dim.size), params.experts_held)


def router_carry_bytes(params) -> int:
    """Bytes of the carried side values (``Context.side``) alive between
    blocks for the backward, of both kinds.  The router states: one float32
    ``[batch, sequence, moe_router_width]`` for every ``moe`` layer with flag
    ``router_mlp`` that hands its state to a later one (all but the last); it
    passes the blocks in between unchanged, so it is held once however many
    regions it crosses.  The early routers' logits: one float32 ``[batch,
    sequence, experts]`` for every ``route_early`` layer (model/route.py),
    each held from its block to the ``routed_early`` layer that takes it.  0
    where no layer carries one."""
    found = list(layers(params))
    states = sum(spec is moe.declares and "router_mlp" in extras
                 for _, extras, spec in found) * params.depth
    logits = sum(name == "route_early" for name, _, _ in found) * params.depth
    return (max(0, states - 1) * params.moe_router_width
            + logits * params.expert_dim.size) * 4 \
        * params.batch_dim.size * params.sequence_dim.size \
        * max(1, params.macro_batching)


def _offer(params, extras) -> Offer:
    """The experts kind, a layer: the grouped matmuls' outputs — gate (none
    under flag ``plain``) and up ``[pairs, intermediate]``, down ``[pairs,
    features]``, in the calculation dtype; under flag ``latent`` the
    combined sum ``[tokens, moe_latent_width]`` in their place
    (``LATENT_SUM``) — the routing triple (``order`` and ``inverse``
    ``[pairs]``, ``sizes`` ``[experts]``, int32) and the router's choice
    (``experts`` ``[tokens, moe_top_k]``, int32), ``pairs = tokens x
    min(moe_top_k, experts)``: ``SAVED_NAMES``.  A layer that holds a share
    of the experts saves its whole static buffer: ``moe_held_rows`` rows,
    ``experts_held + 1`` sizes; where it walks the real rows it builds no
    ``inverse`` (``sort_held``), whose 4 bytes a row of ~10 KB the offer
    still counts, so that no decision of model/remat.py moved with ISSUE
    47."""
    held_rows = moe_held_rows(params)
    choices = params.batch_dim.size * params.sequence_dim.size \
        * min(params.moe_top_k, params.expert_dim.size)
    pairs = held_rows or choices
    groups = params.experts_held + 1 if held_rows else params.expert_dim.size
    routing = (2 * pairs + groups + choices) * 4
    itemsize = jnp.dtype(params.calculation_dtype).itemsize
    if "latent" in extras:
        return Offer("experts", SAVED_NAMES[3:] + (LATENT_SUM,),
                     params.batch_dim.size * params.sequence_dim.size
                     * params.moe_latent_width * itemsize + routing)
    # a plain expert has no gate
    width = (1 if "plain" in extras else 2) \
        * math.prod(d.size for d in params.expert_intermediate) \
        + math.prod(d.size for d in params.feature_dims)
    return Offer("experts", SAVED_NAMES["plain" in extras:],
                 pairs * width * itemsize + routing)


moe.declares = Layer(
    stats=(
        Stat("moe_load_max_over_mean", "gauge",
             "hbnlp_moe_load_max_over_mean",
             "pairs of the busiest expert over the mean, worst moe layer of "
             "the newest finished step", "max"),
        Stat("moe_routed_pairs", "counter", "hbnlp_moe_routed_pairs_total",
             "(token, choice) pairs routed to an expert, all moe layers",
             "sum"),
        # layers that hold a share of the experts: the pairs routed to the
        # held ones, and their share of the pairs routed, over all such
        # layers and in the layer where it is largest
        Stat("moe_held_pairs", "counter", "hbnlp_moe_held_pairs_total",
             "(token, choice) pairs routed to an expert this rank holds, all "
             "moe layers that hold a share of the experts", "sum"),
        Stat("moe_held_pair_share", "gauge", "hbnlp_moe_held_pair_share",
             "pairs routed to held experts over pairs routed, all moe layers "
             "of the newest finished step (experts_held / experts when "
             "balanced)",
             lambda stats, done: done["moe_held_pairs"]
             / done["moe_routed_pairs"], "moe_held_pairs"),
        Stat("moe_held_pair_share_max", "gauge",
             "hbnlp_moe_held_pair_share_max",
             "the same share in the moe layer where it is largest: how far "
             "the static row buffer (hbnlp_moe_held_rows_bound) is filled is "
             "this times moe_top_k / min(moe_top_k, experts_held)",
             lambda stats, done: jnp.max(stats["moe_held_pairs"]
                                         / stats["moe_routed_pairs"]),
             "moe_held_pairs"),
        # how often the held path's loops engage: the row tiles dispatch,
        # the activation and combine visit, and their share of the buffer's
        Stat("moe_held_row_tiles", "counter", "hbnlp_moe_held_row_tiles_total",
             "row tiles of the static dispatch buffer that hold a real row "
             "(ceil(held pairs / tile), the tiles every pass over the buffer "
             "visits), all moe layers that hold a share of the experts",
             "sum"),
        Stat("moe_held_tile_share", "gauge", "hbnlp_moe_held_tile_share",
             "row tiles visited over the row tiles of the static dispatch "
             "buffer (hbnlp_moe_held_rows_bound / tile), all moe layers of "
             "the newest finished step that hold a share of the experts",
             lambda stats, done: done["moe_held_row_tiles"]
             / jnp.sum(stats["moe_held_bound_tiles"]), "moe_held_row_tiles"),
        # flag sigmoid_bias: how far the selection bias has moved, and the
        # load it answers — over ALL the experts, not the held ones
        Stat("moe_bias_abs_max", "gauge", "hbnlp_moe_bias_abs_max",
             "largest |selection bias| of a sigmoid_bias moe layer, the "
             "layer where it is largest (moe_bias_rate x steps at most)",
             "max"),
        Stat("moe_all_load_max_over_mean", "gauge",
             "hbnlp_moe_all_load_max_over_mean",
             "pairs of the busiest of ALL the experts over their mean, worst "
             "sigmoid_bias or routed_early moe layer of the newest finished "
             "step: what the selection bias's rule pulls towards 1", "max"),
        # an activation of relu: the gate values it leaves above zero
        Stat("moe_gate_live_share", "gauge", "hbnlp_moe_gate_live_share",
             "gate values above zero over the gate values of the pairs routed "
             "to an expert this layer holds, all relu-gated moe layers of "
             "the newest finished step (what ReLU does not zero: near 0.5 at "
             "initialisation)",
             lambda stats, done: jnp.sum(stats["moe_gate_live"])
             / jnp.maximum(jnp.sum(stats["moe_gate_values"]), 1.0),
             "moe_gate_live"),
        # the layer whose router says least
        Stat("moe_top1_weight_mean", "gauge", "hbnlp_moe_top1_weight_mean",
             "mean probability of the chosen expert over the tokens of the "
             "newest finished step, in the top-1 moe layer where it is "
             "smallest (1 / experts = a router that says nothing)", "min"),
    ),
    offer=_offer,
    carried=router_carry_bytes,
    facts=(
        Fact(40, "hbnlp_moe_held_rows_bound",
             "rows of the static dispatch buffer of a moe layer that holds a "
             "share of the experts: tokens x min(moe_top_k, experts_held), "
             "which no routing overflows",
             lambda params, mesh, backend: moe_held_rows(params) or None,
             "moe held rows bound {}", zero=False),
        Fact(50, "hbnlp_router_carry_bytes",
             "bytes of the carried side values alive between blocks for the "
             "backward: the router states (layer moe, router_mlp), float32 "
             "[batch, sequence, moe_router_width] a carrying layer but the "
             "last, and the early routers' logits (layer route_early), "
             "float32 [batch, sequence, experts] a layer",
             lambda params, mesh, backend: router_carry_bytes(params) or None,
             "router carry {} bytes", zero=False),
    ))
