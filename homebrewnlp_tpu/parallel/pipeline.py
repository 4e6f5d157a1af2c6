"""Pipeline parallelism: GPipe microbatch schedule over a 'pipe' mesh axis.

New capability — the reference has no pipeline parallelism at all (SURVEY.md
§2.10: "PP: Absent").  The body's depth x block_config stack is split into
``S = mesh.shape['pipe']`` equal stages; each pipe group holds only its
stage's parameters (stacked leaf-wise with a leading stage axis sharded over
'pipe', so HBM per device holds 1/S of the body weights).  Microbatches flow
through the ring with ``lax.ppermute`` over ICI: at tick ``t`` stage ``s``
processes microbatch ``t - s``, the classic GPipe schedule with an
``(S-1)/(M+S-1)`` bubble.

Composition with the other axes: the shard_map is manual over 'pipe' only
(``axis_names={'pipe'}``); 'data' / 'model' / 'sequence' stay in GSPMD auto
mode, so einsums inside a stage still get their XLA-inserted collectives and
tensor parallelism nests inside each stage unchanged.

Memory-reduction strategies compose: revnet / momentum carry their two
activation streams between stages (the inter-stage ppermute moves the
``[2, microbatch...]`` state), checkpoint wraps each stage application in
``jax.checkpoint`` per microbatch, 'none' carries a single stream.

Constraints (validated): ``depth % S == 0``; every stage must be structurally
identical (same leaf shapes/dtypes block-by-block — true whenever the stage is
a whole number of depth iterations); the attention-axis round-robin must line
up per stage (always true for text models, where the only mixing axis is
``sequence``).
"""
from __future__ import annotations

import typing

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..core.dims import Dim
from ..core.tensor import NamedTensor, nt
from jax import shard_map

AXIS = "pipe"


def _stage_layout(fns: typing.Sequence, subsets: typing.Sequence[dict],
                  plan, n_stages: int):
    """Split the flat block list into stages; return (stage0 fns, stage0 name
    lists, per-stage per-block leaf tuples)."""
    n_blocks = len(fns)
    if n_blocks % n_stages:
        raise ValueError(f"{n_blocks} blocks do not split into {n_stages} stages")
    per_stage = n_blocks // n_stages
    name_lists = [tuple(plan[k][2]) for k in range(per_stage)]
    stage0_fns = tuple(fns[:per_stage])

    stage_leaves = []
    for s in range(n_stages):
        block_tuples = []
        for k_local in range(per_stage):
            k = s * per_stage + k_local
            names = tuple(plan[k][2])
            if len(names) != len(name_lists[k_local]):
                raise ValueError(
                    f"stage {s} block {k_local} has {len(names)} params, "
                    f"stage 0 has {len(name_lists[k_local])}; stages must be "
                    f"structurally identical for pipeline parallelism")
            block_tuples.append(tuple(subsets[k][n] for n in names))
        stage_leaves.append(tuple(block_tuples))

    # shape/dtype uniformity across stages
    for s, blocks in enumerate(stage_leaves[1:], start=1):
        for k_local, (ref_block, blk) in enumerate(zip(stage_leaves[0], blocks)):
            for a, b in zip(ref_block, blk):
                if a.shape != b.shape or a.dtype != b.dtype:
                    raise ValueError(
                        f"stage {s} block {k_local} param shape {b.shape} != "
                        f"stage 0 {a.shape}; cannot stack stages")
    return stage0_fns, name_lists, stage_leaves


def _stack_stages(stage_leaves):
    """Leaf-wise stack over stages -> leading [S, ...] axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *stage_leaves)


def pipeline_body(params, mesh: Mesh, fns, subsets, plan, src: NamedTensor,
                  strategy: str) -> NamedTensor:
    """Run the body block stack as a GPipe pipeline.  Differentiable.

    ``src``: the body input [batch, ...].  Returns the combined body output
    (x1+x2 for revnet, x+v for momentum, plain output otherwise), replicated
    over 'pipe' and GSPMD-sharded over the remaining axes as usual.
    """
    from ..model.blocks import momentum_sequence, rev_sequence

    n_stages = mesh.shape[AXIS]
    n_micro = max(1, int(params.pipeline_microbatches or n_stages))
    batch = src.dims[0]
    if batch.size % n_micro:
        raise ValueError(f"batch {batch.size} not divisible by "
                         f"pipeline_microbatches={n_micro}")
    mb = batch.size // n_micro
    data_par = mesh.shape.get("data", 1)
    if mb % data_par:
        raise ValueError(f"microbatch {mb} not divisible by data={data_par}; "
                         f"lower pipeline_microbatches or data parallelism")

    # attention round-robin must be stage-periodic (text models: cycle len 1)
    from ..model.utils import attention_axis_candidates
    n_mix_dims = max(1, len(attention_axis_candidates(src.dims, params)))
    attn_per_stage = sum(
        layer.split('-')[0] == 'attention'
        for i in range(params.depth // n_stages)
        for bc in params.block_config for layer in bc.layer)
    if n_mix_dims > 1 and attn_per_stage % n_mix_dims:
        raise ValueError(
            f"attention axis cycle ({n_mix_dims} mixing dims) does not align "
            f"with {attn_per_stage} attention layers per stage")

    stage0_fns, name_lists, stage_leaves = _stage_layout(fns, subsets, plan,
                                                         n_stages)
    stacked = _stack_stages(stage_leaves)

    n_stream = 2 if strategy in ("revnet", "momentum") else 1
    mb_dims = (Dim(batch.name, mb),) + tuple(src.dims[1:])
    xm = src.data.reshape((n_micro, mb) + src.data.shape[1:])

    def stage_apply(flat_params, state):
        """state: [n_stream, mb, ...] -> same."""
        subs = [dict(zip(names, arrs))
                for names, arrs in zip(name_lists, flat_params)]
        if strategy == "revnet":
            y1, y2 = rev_sequence(stage0_fns, tuple(subs),
                                  nt(state[0], mb_dims), nt(state[1], mb_dims))
            return jnp.stack([y1.data, y2.data])
        if strategy == "momentum":
            y, v = momentum_sequence(stage0_fns, params.momentumnet_alpha,
                                     tuple(subs),
                                     nt(state[0], mb_dims), nt(state[1], mb_dims))
            return jnp.stack([y.data, v.data])
        out = nt(state[0], mb_dims)
        for f, sub in zip(stage0_fns, subs):
            out = jax.checkpoint(f)(sub, out) if strategy == "checkpoint" \
                else f(sub, out)
        return out.data[None]

    def combine(state):
        if n_stream == 2:
            return state[0] + state[1]
        return state[0]

    ticks = n_micro + n_stages - 1

    def body(stacked_local, xm_local):
        from ..core import scope
        stage = jax.lax.axis_index(AXIS)
        local = jax.tree.map(lambda a: jnp.squeeze(a, 0), stacked_local)
        ctx = scope.current() if scope.in_context() else None
        base_rng = ctx.rng_key if ctx is not None else None

        def tick(carry, t):
            recv, outputs = carry
            t_c = jnp.minimum(t, n_micro - 1)
            x0 = jax.lax.dynamic_index_in_dim(xm_local, t_c, 0, keepdims=False)
            state0 = jnp.broadcast_to(x0[None], (n_stream,) + x0.shape
                                      ).astype(recv.dtype)
            state_in = jnp.where(stage == 0, state0, recv)
            if ctx is not None and base_rng is not None:
                # decorrelate dropout across stages and microbatches: all
                # stages replay stage-0's blocks (same depth_idx fold), so
                # fold the stage index and tick in here; restore before tick
                # returns so no tick-trace tracer survives in python state
                ctx.rng_key = jax.random.fold_in(
                    jax.random.fold_in(base_rng, stage), t)
                try:
                    y = stage_apply(local, state_in)
                finally:
                    ctx.rng_key = base_rng
            else:
                y = stage_apply(local, state_in)
            out_idx = t - (n_stages - 1)
            valid = out_idx >= 0
            oi = jnp.clip(out_idx, 0, n_micro - 1)
            prev = jax.lax.dynamic_index_in_dim(outputs, oi, 0, keepdims=False)
            y_out = combine(y)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(valid, y_out, prev), oi, 0)
            y_send = jax.lax.ppermute(
                y, AXIS, [(i, i + 1) for i in range(n_stages - 1)])
            return (y_send, outputs), None

        dtype = xm_local.dtype
        recv0 = jnp.zeros((n_stream, mb) + xm_local.shape[2:], dtype)
        out0 = jnp.zeros((n_micro, mb) + xm_local.shape[2:], dtype)
        (_, outputs), _ = jax.lax.scan(tick, (recv0, out0), jnp.arange(ticks))
        # only the last stage holds real outputs; reduce to replicate
        outputs = jnp.where(stage == n_stages - 1, outputs,
                            jnp.zeros_like(outputs))
        return jax.lax.psum(outputs, AXIS)

    param_specs = jax.tree.map(lambda _: P(AXIS), stacked)
    fn = shard_map(body, mesh=mesh,
                   in_specs=(param_specs, P()), out_specs=P(),
                   axis_names={AXIS}, check_vma=False)
    # ReplayBlock pins inter-block activation layouts via the scope context's
    # mesh; inside the pipe-manual shard_map those constraints would name
    # manual axes, so blank the mesh while the body traces (GSPMD still
    # auto-shards the data/model/sequence axes within each stage)
    from ..core import scope
    ctx = scope.current() if scope.in_context() else None
    saved_mesh = ctx.mesh if ctx is not None else None
    if ctx is not None:
        ctx.mesh = None
    try:
        out = fn(stacked, xm)
    finally:
        if ctx is not None:
            ctx.mesh = saved_mesh
    return nt(out.reshape(src.data.shape), src.dims)
