"""Basic layers (reference: /root/reference/src/model/basic.py).

rezero, dropout, wrapped_linear, soft mixture-of-experts, activated_linear
(glu / glu_add / norm flags with in:/mid:/out: prefix scoping), feed_forward,
group_linear (per-head grouped linear via the anonymized key dim),
sum_heads, transpose_sequence_features, reduced_half_linear, product-key
memory, bottleneck_group_linear.
"""
from __future__ import annotations

import functools
import math
import typing

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..config import BlockArgs
from ..core import scope
from ..core.dims import Dim, shape_sub
from ..core.stash import (stash_channel, stash_collecting, stash_pop,
                          stash_push)
from ..core.tensor import (NamedTensor, cast, dropout as tensor_dropout, nt,
                           einsum, exp, multiply, reduce_max, reduce_sum,
                           reciprocal, rename_dim, reshape, sigmoid,
                           stop_gradient, top_1, transpose_to, unbind)
from .activation import ACTIVATIONS, activate
from .backend import (ConstantInit, get_var, linear, normal_var,
                      orthogonal_var)
from .declare import Layer, Offer
from .embedding import gather_embed
from .normalization import norm
from .utils import anonymize_dim, anonymize_shape, linear_shapes


def rezero(args: BlockArgs) -> NamedTensor:
    return args.tensor * get_var(args, [], ConstantInit(0.))


def dropout(args: BlockArgs) -> NamedTensor:
    keep = 1.
    for extra in args.name_extras:
        if extra.startswith("dropout_rate"):
            keep = 1 - float(extra[len("dropout_rate"):])
    return tensor_dropout(args.tensor, args.params.train, keep,
                          scope.current().next_rng())


def wrapped_linear(args: BlockArgs) -> NamedTensor:
    return linear(args, *linear_shapes(args))


def mixture_of_experts(args: BlockArgs) -> NamedTensor:
    """Dense softmax-gated expert einsum (basic.py:37-44) — no routing, no
    all-to-all; the experts dim can be placed on the mesh for true EP."""
    params = args.params
    old, new = linear_shapes(args)
    gate = linear(args, old, [params.expert_dim])
    gate = gate - stop_gradient(reduce_max(gate, reduced_dim=params.expert_dim))
    gate = exp(gate)
    out_shape = shape_sub(args.tensor.dims, old) + list(new)
    return einsum([reciprocal(reduce_sum(gate, reduced_dim=params.expert_dim)),
                   args.tensor, gate,
                   orthogonal_var(args, list(old) + list(new) + [params.expert_dim])],
                  output_shape=out_shape)


def _topk_dispatch(probs, top_k: int, capacity: int):
    """Vectorized GShard-style greedy top-k dispatch.

    Equivalent to the sequential loop (iteration j: mask previous choices,
    argmax, assign buffer positions): the k-major cumsum gives every token's
    j-th choice a position behind ALL tokens' earlier choices, which is
    exactly the order the loop fills expert buffers in.  Returns
    (combine [g,t,E,C], idx [g,t,k], keep [g,k,t])."""
    g, t, e = probs.shape
    vals, idx = jax.lax.top_k(probs, top_k)            # [g, t, k]
    oh = jax.nn.one_hot(idx, e, dtype=jnp.float32)     # [g, t, k, E]
    oh_k = jnp.transpose(oh, (0, 2, 1, 3))             # [g, k, t, E]
    oh_km = oh_k.reshape(g, top_k * t, e)              # k-major flatten
    pos = jnp.cumsum(oh_km, axis=1) - oh_km            # earlier fills per E
    pos_tok = jnp.sum(pos * oh_km, axis=-1).reshape(g, top_k, t)
    keep = (pos_tok < capacity).astype(jnp.float32)    # [g, k, t]
    gate_w = jnp.transpose(vals, (0, 2, 1))            # [g, k, t]
    slot = jax.nn.one_hot(pos_tok.astype(jnp.int32), capacity,
                          dtype=jnp.float32)           # [g, k, t, C]
    combine = jnp.einsum("gkt,gkte,gktc->gtec", gate_w * keep, oh_k, slot,
                         precision=jax.lax.Precision.HIGHEST)
    return combine, idx, keep


def _router_aux(wb: float, wz: float, top_k: int, logits):
    """Switch/GShard auxiliary losses as a function of the router logits
    alone: ``wb * E * mean_g sum_e f_e P_e`` (f_e = fraction of (token,
    choice) pairs routed to expert e — constant w.r.t. logits, gradient
    flows through the mean-probability term, as in Switch) plus
    ``wz * mean logsumexp(logits)^2`` (router z-loss)."""
    e = logits.shape[-1]
    probs = jax.nn.softmax(logits, axis=-1)
    total = jnp.float32(0)
    if wb:
        _, idx = jax.lax.top_k(logits, top_k)
        oh = jax.nn.one_hot(idx, e, dtype=jnp.float32)     # [g, t, k, E]
        frac = jnp.mean(jnp.sum(oh, axis=2), axis=1)       # [g, E], sums to k
        mean_p = jnp.mean(probs, axis=1)                   # [g, E]
        total = total + wb * e * jnp.mean(
            jnp.sum(jax.lax.stop_gradient(frac) * mean_p, axis=-1)) / top_k
    if wz:
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        total = total + wz * jnp.mean(lse ** 2)
    return total


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _router_aux_inject(wb: float, wz: float, top_k: int, logits):
    """Identity on the forward; the backward ADDS the auxiliary-loss gradient
    to the logits cotangent.  Because the aux losses depend only on the
    logits, this injects their exact gradient without the loss value ever
    having to escape the block stack — which makes it correct under every
    memory strategy (revnet/momentum custom_vjp replays, lax.scan over
    depth, jax.checkpoint, 1F1B per-stage vjp) with zero changes to that
    machinery.  The reported total loss stays the task loss; the aux VALUES
    are observable through the routing-stats probe (Trainer.moe_stats)."""
    return logits


def _router_aux_fwd(wb, wz, top_k, logits):
    return logits, logits


def _router_aux_bwd(wb, wz, top_k, logits, ct):
    aux_grad = jax.grad(lambda l: _router_aux(wb, wz, top_k, l))(logits)
    return (ct + aux_grad.astype(ct.dtype),)


_router_aux_inject.defvjp(_router_aux_fwd, _router_aux_bwd)


def routed_mixture_of_experts(args: BlockArgs) -> NamedTensor:
    """Top-k routed MoE with capacity-bounded dense dispatch (GShard/Switch
    style) — NEW capability: the reference only has the dense soft-MoE above
    (/root/reference/src/model/basic.py:37-44, every expert computes every
    token).  Routing flags: ``routed`` engages it inside activated_linear;
    ``top_k<k>`` and ``capacity_factor<f>`` override config
    ``moe_top_k``/``moe_capacity_factor``.

    Formulation is einsum dispatch/combine (one-hot capacity slots), the
    standard TPU-native shape: with the ``experts`` dim on a mesh axis
    (``layout_override {"experts": "model"}``) GSPMD turns the dispatch and
    combine contractions into all-to-alls over that axis, and expert weights
    shard 1/E per device.  With k = E and unbounded capacity it reproduces
    the dense soft-MoE exactly (parity-tested).
    """
    from ..core.sharding import with_constraint

    params = args.params
    old, new = linear_shapes(args)
    top_k = params.moe_top_k
    capacity_factor = params.moe_capacity_factor
    for extra in args.name_extras:
        if extra.startswith("top_k"):
            top_k = int(extra[len("top_k"):])
        elif extra.startswith("capacity_factor"):
            capacity_factor = float(extra[len("capacity_factor"):])
    n_exp = params.expert_dim.size
    top_k = min(top_k, n_exp)

    # gate: same projection shape + scope order as the dense soft-MoE gate
    gate = linear(args, old, [params.expert_dim])
    weights = orthogonal_var(args, list(old) + list(new) + [params.expert_dim])

    x = args.tensor
    token_dims = [d for d in x.dims if d not in old]   # [batch, seq, ...]
    feat_dims = list(old)
    # flatten: g = batch (routing group), t = positions per group, f = features
    g_sz = token_dims[0].size
    t_sz = math.prod([d.size for d in token_dims[1:]]) if len(token_dims) > 1 else 1
    f_sz = math.prod([d.size for d in feat_dims])
    n_sz = math.prod([d.size for d in new])
    xt = transpose_to(x, token_dims + feat_dims)
    xf = xt.data.reshape(g_sz, t_sz, f_sz)              # [g, t, f]
    gate_t = transpose_to(gate, token_dims + [params.expert_dim])
    logits = gate_t.data.reshape(g_sz, t_sz, n_exp).astype(jnp.float32)

    wb, wz = float(params.moe_balance_loss), float(params.moe_router_z_loss)
    if params.train and (wb or wz):
        logits = _router_aux_inject(wb, wz, top_k, logits)
    probs = jax.nn.softmax(logits, axis=-1)             # [g, t, E]
    capacity = max(1, int(math.ceil(top_k * t_sz / n_exp * capacity_factor)))
    capacity = min(capacity, t_sz)

    combine, idx, keep = _topk_dispatch(probs, top_k, capacity)

    sink = scope.current().stats_sink
    if sink is not None:
        oh = jax.nn.one_hot(idx, n_exp, dtype=jnp.float32)
        frac = jnp.mean(jnp.sum(oh, axis=2), axis=(0, 1))    # [E], sums to k
        util = frac * n_exp / top_k                # 1.0 = perfectly balanced
        sink.append((scope.current().path(), {
            "balance_loss": _router_aux(1.0, 0.0, top_k, logits),
            "router_z_loss": _router_aux(0.0, 1.0, top_k, logits),
            "dropped_fraction": 1.0 - jnp.mean(keep),
            "utilization_min": jnp.min(util),
            "utilization_max": jnp.max(util),
            "utilization": util,
        }))

    # renormalize the kept top-k gate mass (standard top-k softmax renorm)
    denom = jnp.sum(combine, axis=(2, 3), keepdims=True)
    combine = combine / jnp.maximum(denom, 1e-9)
    dispatch = (combine > 0).astype(xf.dtype)

    # dispatch -> expert compute -> combine (all-to-alls materialize here
    # when 'experts' is a mesh axis)
    cap_dim = Dim("_capacity", capacity)
    grp_dim = token_dims[0]
    mesh = scope.current().mesh if scope.in_context() else None

    def constrain(arr, last_dim):
        t = nt(arr, [params.expert_dim, grp_dim, cap_dim, last_dim])
        return with_constraint(t, params, mesh).data

    exp_in = jnp.einsum("gtec,gtf->egcf", dispatch, xf)
    exp_in = constrain(exp_in, Dim("_moe_features", f_sz))

    w_t = transpose_to(weights, [params.expert_dim] + list(old) + list(new))
    wf = w_t.data.reshape(n_exp, f_sz, n_sz).astype(xf.dtype)
    exp_out = jnp.einsum("egcf,efn->egcn", exp_in, wf)
    exp_out = constrain(exp_out, Dim("_moe_out", n_sz))

    out = jnp.einsum("gtec,egcn->gtn", combine.astype(exp_out.dtype), exp_out)
    out_dims = token_dims + list(new)
    out = out.reshape([d.size for d in out_dims]).astype(x.dtype)
    return transpose_to(nt(out, out_dims),
                        shape_sub(x.dims, old) + list(new))


def _provided_contract(inputs, output_shape, provided: jax.Array
                       ) -> NamedTensor:
    """The einsum whose value the forward pass already made: ``provided``
    IS the primal output, and the backward is the einsum's own (the input's
    cotangent from the weight, the weight's from the replayed input) — so
    neither the matmul nor, where it contracts a mesh-sharded axis, its
    all-reduce runs again."""
    dims = [t.dims for t in inputs]
    ctx = scope.current()

    def contract(*arrays):
        # the backward is traced after the block's scope has closed; the
        # einsum reads its accumulation policy from it
        with scope.context(ctx):
            return einsum([nt(a, d) for a, d in zip(arrays, dims)],
                          output_shape).data

    @jax.custom_vjp
    def provide(y, *arrays):
        return y

    def fwd(y, *arrays):
        return y, arrays

    def bwd(arrays, ct):
        # the forward value inside jax.vjp is dead code: nothing reads it
        return (jnp.zeros_like(ct), *jax.vjp(contract, *arrays)[1](ct))

    provide.defvjp(fwd, bwd)
    return nt(provide(provided, *[t.data for t in inputs]), output_shape)


def replay_stashed_linear(args: BlockArgs, chan: dict) -> NamedTensor:
    """``wrapped_linear`` whose output rides the strategy residuals through
    the replay stash channel (model/blocks.py; kind "bottleneck",
    model/remat.py decides): the forward rule's trace pushes the — already
    all-reduced — output, the backward replay pops it instead of making it
    again.  The layout is pinned (batch on 'data', replicated over
    'model') on both sides so that GSPMD does not re-shard the stack."""
    from ..core.sharding import with_constraint
    params, mesh = args.params, scope.current().mesh
    if stash_collecting(chan):
        out = with_constraint(wrapped_linear(args), params, mesh)
        stash_push(chan, out.data)
        return out
    provided = stash_pop(chan)

    def contract(inputs, output_shape):
        y = with_constraint(nt(provided, output_shape), params, mesh)
        return _provided_contract(inputs, output_shape, y.data)

    return linear(args, *linear_shapes(args), contract=contract)


def activated_linear(args: BlockArgs, prefix: str,
                     linear_fn=wrapped_linear) -> NamedTensor:
    args = args([a[len(prefix):] for a in args if a.startswith(prefix)])
    if "mixture_of_experts" in args.name_extras:
        feed_forward_fn = routed_mixture_of_experts \
            if "routed" in args.name_extras else mixture_of_experts
    else:
        feed_forward_fn = linear_fn
    out = dropout(args(activate(args(feed_forward_fn(args)))))
    if "glu" in args.name_extras or "glu_add" in args.name_extras:
        out = multiply(out, sigmoid(feed_forward_fn(args)))
    if "glu_add" in args.name_extras:
        out = out + activate(args(feed_forward_fn(args)))
    if "norm" in args.name_extras:
        out = norm(args(out))
    return out


def activated_linear_in(args: BlockArgs) -> NamedTensor:
    return activated_linear(args, "in:")


def activated_linear_out(args: BlockArgs) -> NamedTensor:
    return activated_linear(args, "out:")


#: the names layer ``mlp`` gives its two matmul outputs ``[batch, sequence,
#: intermediate]`` (``checkpoint_name``; free where no policy names them).
#: With both saved a ``checkpoint`` block's replay runs the activation and the
#: product alone, no matmul; the down matmul's input is made again from them
MLP_SAVED_NAMES = ("mlp_gate", "mlp_up")


def mlp(args: BlockArgs) -> NamedTensor:
    """Layer ``mlp``: the dense gated MLP of today's transformers,
    ``down(act(gate(x)) * up(x))``, all features -> ``intermediate`` -> all
    features, three bias-free matrices, normal(0.02), created in the order
    gate, up, down; an activation name as flag (default ``silu``: SwiGLU).
    (``feed_forward``'s ``glu`` is the reference's sigmoid gate of another
    form; the routed experts of model/moe.py each are one of these.)"""
    params = args.params
    act = next((ACTIVATIONS[a] for a in args.name_extras if a in ACTIVATIONS),
               ACTIVATIONS["silu"])
    feats = list(params.feature_dims)
    anon = [anonymize_dim(d) for d in feats]
    inter = list(params.intermediate)
    x = args.tensor
    for d, a in zip(feats, anon):
        x = rename_dim(x, d.name, a.name)
    hidden_dims = shape_sub(x.dims, anon) + inter

    def hidden(name: str) -> NamedTensor:
        out = einsum([x, normal_var(args, anon + inter)], hidden_dims)
        return nt(checkpoint_name(out.data, name), hidden_dims)

    gate, up = (hidden(name) for name in MLP_SAVED_NAMES)
    return einsum([act(args(gate)) * up, normal_var(args, inter + feats)],
                  list(args.tensor.dims))


def _mlp_offer(params, extras) -> Offer:
    out_dims = [params.batch_dim, params.sequence_dim, *params.intermediate]
    return Offer("dense", MLP_SAVED_NAMES,
                 2 * math.prod(d.size for d in out_dims)
                 * jnp.dtype(params.calculation_dtype).itemsize, count=2)


mlp.declares = Layer(offer=_mlp_offer)


def feed_forward(args: BlockArgs) -> NamedTensor:
    return activated_linear_out(args(activated_linear_in(args)))


def group_linear(args: BlockArgs) -> NamedTensor:
    """Per-head grouped linear: project features -> anonymized key dim and
    rename back (basic.py:72-74).  The reference's reshape round-trip is a
    pure rename here."""
    params = args.params
    anonymous_key = anonymize_shape(params.feature_dims, params.key_dim)
    out = linear(args("group"), list(params.feature_dims), anonymous_key)
    return rename_dim(out, anonymize_dim(params.key_dim), params.key_dim.name)


def sum_heads(args: BlockArgs) -> NamedTensor:
    return reduce_sum(args.tensor, reduced_dim=args.params.head_dim)


def transpose_sequence_features(args: BlockArgs) -> NamedTensor:
    """Swap sequence and feature axes (basic.py:81-86)."""
    from . import decode as decode_mod
    params = args.params
    if decode_mod.active() is not None:
        raise NotImplementedError(
            "transpose_sequence_features mixes sequence into features; "
            "incremental decode falls back to the full-forward sampler")
    assert params.features_per_head == params.sequence_length, \
        "transpose_sequence_features requires features_per_head == sequence_length"
    tensor = rename_dim(args.tensor, params.sequence_dim.name, "intermediate")
    tensor = rename_dim(tensor, params.key_dim.name, params.sequence_dim.name)
    tensor = rename_dim(tensor, "intermediate", params.key_dim.name)
    return transpose_to(tensor, args.tensor.dims)


def reduced_half_linear(args: BlockArgs) -> NamedTensor:
    return group_linear(args(reduce_sum(args.tensor, reduced_dim=args.params.head_dim)))


def product_key_memory(args: BlockArgs) -> NamedTensor:
    """Two/three-axis product-key memory with top-1 per axis + batched gather
    (basic.py:93-115)."""
    params = args.params
    anonymous_key = anonymize_dim(params.key_dim)
    features = [params.pkm_dim, anonymous_key]
    assignment = linear(args, linear_shapes(args).old, [params.head_dim] + features)
    assignment = norm(args(assignment), features)
    assignment = cast(assignment, jnp.float32)  # f64 in reference; f32 on TPU
    normalizer = reduce_max(assignment, reduced_dim=anonymous_key)
    normalizer = reduce_sum(normalizer, reduced_dim=params.pkm_dim)
    assignment = assignment - stop_gradient(normalizer)
    assignment = exp(assignment)
    normalizer = reduce_sum(assignment, output_shape=shape_sub(assignment.dims, [anonymous_key]))
    normalizer = einsum(unbind(normalizer, params.pkm_dim),
                        output_shape=shape_sub(normalizer.dims, [params.pkm_dim]))

    val, idx = top_1(assignment, anonymous_key)
    powers = jnp.asarray([params.features_per_head ** i for i in range(params.pkm_axes)],
                         dtype=jnp.int32)
    from ..core.tensor import nt
    powers_nt = nt(powers, [params.pkm_dim])
    idx = einsum([powers_nt, idx], output_shape=shape_sub(idx.dims, [params.pkm_dim]))
    val = einsum(unbind(val, params.pkm_dim),
                 output_shape=shape_sub(val.dims, [params.pkm_dim])) / normalizer
    val = cast(val, params.calculation_dtype)
    out = gather_embed(args(idx), [params.product_key_value_dim] + list(params.feature_dims),
                       [params.head_dim])
    return out * val


def feed_forward_product_key_memory(args: BlockArgs) -> NamedTensor:
    return product_key_memory(args(activated_linear_in(args)))


def bottleneck_group_linear(args: BlockArgs) -> NamedTensor:
    """features -> bottleneck(intermediate) -> widened grouped mid -> grouped
    out (basic.py:122-126); the workhorse of the flagship mixer configs."""
    chan = stash_channel(scope.current(), "bottleneck")
    linear_in = wrapped_linear if chan is None \
        else functools.partial(replay_stashed_linear, chan=chan)
    args = args(activated_linear(args, "in:", linear_in))
    args.name_extras.extend(["group", "mid:group", "out:group"])
    args = args(activated_linear(args, "mid:"))
    return activated_linear_out(args)


def _bottleneck_offer(params, extras) -> typing.Optional[Offer]:
    """The in-projection outputs ``[batch, sequence, intermediate]`` the
    layer pushes: every ``in:`` linear (one, plus the glu branches; an expert
    in-projection is not a plain linear and is left alone)."""
    if "in:mixture_of_experts" in extras:
        return None
    glu_add = "in:glu_add" in extras
    sites = 1 + ("in:glu" in extras or glu_add) + glu_add
    out_dims = [params.batch_dim, params.sequence_dim, *params.intermediate]
    return Offer("bottleneck", (),
                 sites * math.prod(d.size for d in out_dims)
                 * jnp.dtype(params.calculation_dtype).itemsize, count=sites)


bottleneck_group_linear.declares = Layer(offer=_bottleneck_offer)
