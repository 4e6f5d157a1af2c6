"""Memory-reduction strategies over the block stack.

Reference (/root/reference/src/model/__init__.py:101-126) supports four:
  revnet    — reversible residual coupling y1 = x1 + f(x2) (revnet.py:14),
  momentum  — invertible momentum residual v' = αv + (1-α)f(x); x' = x + v'
              (momentumnet.py:20-27),
  checkpoint— gradient checkpointing (mtf.recompute_grad),
  none      — plain.

The reference implements revnet/momentum as custom mtf Operations whose
``gradient()`` clones the forward subgraph and streams per-variable grads
(revnet.py:55-120).  Here each is a ``jax.custom_vjp`` over the whole block
sequence: forward keeps only the two output streams; backward reconstructs
activations layer-by-layer and calls ``jax.vjp`` on the re-traced block —
O(1) activation memory in depth, with XLA-visible (and thus
schedulable/fusable) recomputation.

Each block is re-traced in isolation through a "replay" function that opens a
fresh scope Context seeded with that block's parameter subset — hierarchical
naming (core/scope.py) guarantees the replay resolves identical parameter
names to the original trace.
"""
from __future__ import annotations

import functools
import typing

import jax
import jax.numpy as jnp

from ..config import BlockConfig, ModelParameter
from ..core import scope
from ..core.tensor import NamedTensor
from .frontend import block_part_fn

Subset = typing.Dict[str, jax.Array]
BlockSpec = typing.Tuple[int, int, typing.Tuple[str, ...]]  # (depth, cfg, names)


class ReplayBlock:
    """Hashable callable re-tracing one block under its own param subset."""

    def __init__(self, params: ModelParameter, block_config: BlockConfig,
                 depth_idx: int, cfg_idx: int, prefix: typing.Tuple[str, ...],
                 attention_idx: int):
        self.params = params
        self.block_config = block_config
        self.depth_idx = depth_idx
        self.cfg_idx = cfg_idx
        self.prefix = prefix
        self.attention_idx = attention_idx
        self._key = (id(params), depth_idx, cfg_idx, prefix)

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, ReplayBlock) and self._key == other._key

    def __call__(self, subset: Subset, x: NamedTensor,
                 it: typing.Optional[jax.Array] = None,
                 stash: typing.Optional[dict] = None,
                 layer_stats: typing.Optional[list] = None,
                 side: typing.Optional[dict] = None):
        """The block's output; with ``side`` (the carried side values that
        enter the block, ``Context.side``) ``(output, the side values that
        leave it)``."""
        outer_rng = None
        outer_mesh = None
        outer_decode = None
        outer_prefill = None
        outer_sink = None
        outer_quant = None
        if scope.in_context():
            outer_rng = scope.current().rng_key
            outer_mesh = scope.current().mesh
            outer_decode = scope.current().decode
            outer_prefill = scope.current().prefill
            outer_sink = scope.current().stats_sink
            outer_quant = getattr(scope.current(), "quant_scales", None)
        ctx = scope.Context("apply", params=subset, rng_key=None,
                            mesh=outer_mesh, decode=outer_decode)
        ctx.prefill = outer_prefill
        ctx.stats_sink = outer_sink
        # int8 serving scales key on ABSOLUTE parameter names, which the
        # per-block subsets preserve — without this, replayed blocks (the
        # scan/decode/prefill paths, i.e. every real serving path) would
        # consume raw -127..127 integers
        ctx.quant_scales = outer_quant
        # the replay stash channel (collect/provide, below), handed EXPLICITLY
        # by the strategy code — never inherited from the outer context, so
        # a mode can't leak across custom_vjp replay boundaries
        ctx.replay_stash = stash
        # per-step layer statistics (core/scope.py Context.layer_stats): the
        # caller's own list, so that it can return them out of its region
        ctx.layer_stats = layer_stats
        # carried side values: a copy, so that what the block's layers leave
        # there is this call's OUTPUT and never the caller's dict mutated
        # inside a checkpoint region
        ctx.side = None if side is None else dict(side)
        if outer_rng is not None:
            # `it` is the (possibly traced) depth index under scan-over-layers
            idx = self.depth_idx if it is None else it
            ctx.rng_key = jax.random.fold_in(outer_rng,
                                             idx * 131 + self.cfg_idx)
        for seg in self.prefix:
            ctx.stack.append(scope._Frame(seg))
        # attention axis round-robin must replay identically
        saved = self.params.attention_idx
        self.params.attention_idx = self.attention_idx
        try:
            with scope.context(ctx):
                out = block_part_fn(self.params, self.block_config, x,
                                    f"block{self.depth_idx}_{self.cfg_idx}")
                if outer_mesh is not None:
                    # pin the inter-block activation layout so GSPMD keeps
                    # batch on 'data' / heads on 'model' through the stack
                    from ..core.sharding import with_constraint
                    out = with_constraint(out, self.params, outer_mesh)
                return out if side is None else (out, ctx.side)
        finally:
            self.params.attention_idx = saved


def _block_scope_name(depth_idx: int, cfg_idx: int) -> str:
    return f"block{depth_idx}_{cfg_idx}"


# ---- reversible sequence -------------------------------------------------

def _call_block(f, subset, x, it=None, chan=None):
    """Invoke a block, passing only the kwargs in use — plain test callables
    (and the pipeline's stage fns) keep their two-arg signature."""
    kwargs = {}
    if it is not None:
        kwargs["it"] = it
    if chan is not None:
        kwargs["stash"] = chan
    return f(subset, x, **kwargs)


# The replay stash channel: what is dear to rebuild per byte rides the
# strategy residuals instead of being recomputed in the backward replay.
# The forward rule runs each block part with a "collect" channel; layers
# whose ``kind`` the channel carries (model/remat.py STASH_KINDS:
# "attention" — a flash/ring layer's (out, lse); "bottleneck" —
# bottleneck_group_linear's in-projection output) ``stash_push`` a pytree
# of arrays; the scan forms stack the items over depth.  The backward rule
# replays the part with a "provide" channel and the same layers
# ``stash_pop``.  ORDERING CONTRACT: one channel per block part; items pop
# in push order, whatever their kind, so a consumer's gate (kind, shapes,
# mesh) must be a function of what BOTH traces see — it must push in
# collect mode exactly when it pops in provide mode.
#
# The ``checkpoint`` strategy has no residuals of its own to ride: where the
# attention kind rides each block's ``jax.checkpoint`` instead
# (model/remat.py), the blocks get a third, stateless mode, "name"
# (``_name_chan``): a flash layer whose queries see at least ``min_keys``
# keys NAMES its (out, lse) for the block's policy to save
# (parallel/flash_attention.py ``SAVED_NAMES``) and pushes nothing.

def _collect_chan(stash: typing.FrozenSet[str]):
    return {"mode": "collect", "items": [], "kinds": stash} if stash else None


def _provide_chan(stash: typing.FrozenSet[str], items):
    """items: the block part's stashed pytrees from the forward rule's
    residuals; an empty tuple (no consumer in the part) degrades to the
    plain replay."""
    if not stash or not items:
        return None
    return {"mode": "provide", "items": list(items), "i": 0, "kinds": stash}


def _name_chan(params: ModelParameter, mesh):
    """The channel every block of the ``checkpoint`` strategy gets where the
    attention kind rides its ``jax.checkpoint``
    (model/remat.py ``saved_attention_keys``), else None."""
    from .remat import saved_attention_keys
    keys = saved_attention_keys(params, mesh)
    if keys is None:
        return None
    return {"mode": "name", "kinds": frozenset({"attention"}),
            "min_keys": keys}


def _chan_items(chan):
    return tuple(chan["items"]) if chan is not None else ()


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 4))
def rev_sequence(fns, subsets, x1, x2,
                 stash: typing.FrozenSet[str] = frozenset()):
    for f, s in zip(fns, subsets):
        x1, x2 = x2, x1 + f(s, x2)
    return x1, x2


def _rev_fwd(fns, subsets, x1, x2, stash):
    stashes = []
    for f, s in zip(fns, subsets):
        chan = _collect_chan(stash)
        x1, x2 = x2, x1 + _call_block(f, s, x2, chan=chan)
        stashes.append(_chan_items(chan))
    return (x1, x2), (subsets, (x1, x2), tuple(stashes))


def _rev_bwd(fns, stash, res, cot):
    subsets, (a, b), stashes = res
    da, db = cot
    dsubsets: typing.List[typing.Any] = [None] * len(fns)
    for i in range(len(fns) - 1, -1, -1):
        f, s = fns[i], subsets[i]
        b_prev = a
        chan = _provide_chan(stash, stashes[i])
        fval, fvjp = scope.replay_vjp(
            lambda s_, x_: _call_block(f, s_, x_, chan=chan), s, b_prev)
        a_prev = b - fval
        ds, db_extra = fvjp(db)
        da_prev = db
        db_prev = da + db_extra
        a, b = a_prev, b_prev
        da, db = da_prev, db_prev
        dsubsets[i] = ds
    return tuple(dsubsets), da, db


rev_sequence.defvjp(_rev_fwd, _rev_bwd)


# ---- invertible momentum sequence ---------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 5))
def momentum_sequence(fns, alpha, subsets, x, v,
                      stash: typing.FrozenSet[str] = frozenset()):
    for f, s in zip(fns, subsets):
        v = v * alpha + f(s, x) * (1 - alpha)
        x = x + v
    return x, v


def _mom_fwd(fns, alpha, subsets, x, v, stash):
    stashes = []
    for f, s in zip(fns, subsets):
        chan = _collect_chan(stash)
        v = v * alpha + _call_block(f, s, x, chan=chan) * (1 - alpha)
        x = x + v
        stashes.append(_chan_items(chan))
    return (x, v), (subsets, (x, v), tuple(stashes))


def _mom_bwd(fns, alpha, stash, res, cot):
    subsets, (x, v), stashes = res
    dx, dv = cot
    dsubsets: typing.List[typing.Any] = [None] * len(fns)
    for i in range(len(fns) - 1, -1, -1):
        f, s = fns[i], subsets[i]
        x_prev = x - v
        chan = _provide_chan(stash, stashes[i])
        fval, fvjp = scope.replay_vjp(
            lambda s_, x_: _call_block(f, s_, x_, chan=chan), s, x_prev)
        v_prev = (v - fval * (1 - alpha)) / alpha
        g = dx + dv  # total cotangent on v' (it feeds both outputs)
        ds, dx_f = fvjp(g * (1 - alpha))  # f enters v' scaled by (1 - alpha)
        dx_prev = dx + dx_f
        dv_prev = g * alpha
        x, v = x_prev, v_prev
        dx, dv = dx_prev, dv_prev
        dsubsets[i] = ds
    return tuple(dsubsets), dx, dv


momentum_sequence.defvjp(_mom_fwd, _mom_bwd)


# ---- scan-over-layers (lax.scan over depth) ------------------------------
#
# The unrolled custom-vjp sequences above give XLA one giant program with
# depth x block_config inlined blocks; the scheduler is then free to keep
# dozens of per-block temporaries alive at once (observed: the 32big_mixer
# backward wanted 18GB of HLO temps on a 16GB chip).  lax.scan bounds live
# memory to ONE iteration's working set and makes program size O(1) in depth.
# Per-depth parameters are stacked on a leading depth axis; `shared`
# (cross-layer) weights stay unstacked and their gradients accumulate in the
# scan carry.  Enabled by `scan_layers` (default on) whenever the stack is
# depth-homogeneous; anything irregular falls back to the unrolled forms.

def _rev_scan_run(fns, unroll, stacked, shared, x1, x2, stash):
    def step(carry, sl):
        x1, x2, it = carry
        outs = []
        for c, f in enumerate(fns):
            chan = _collect_chan(stash)
            x1, x2 = x2, x1 + _call_block(f, {**sl[c], **shared[c]}, x2,
                                          it=it, chan=chan)
            outs.append(_chan_items(chan))
        return (x1, x2, it + 1), tuple(outs)

    (x1, x2, _), stashes = jax.lax.scan(step, (x1, x2, jnp.int32(0)), stacked,
                                        unroll=unroll)
    return x1, x2, stashes


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 6))
def rev_scan(fns, unroll, stacked, shared, x1, x2,
             stash: typing.FrozenSet[str] = frozenset()):
    x1, x2, _ = _rev_scan_run(fns, unroll, stacked, shared, x1, x2,
                              frozenset())
    return x1, x2


def _rev_scan_fwd(fns, unroll, stacked, shared, x1, x2, stash):
    x1, x2, stashes = _rev_scan_run(fns, unroll, stacked, shared, x1, x2,
                                    stash)
    return (x1, x2), (stacked, shared, (x1, x2), stashes)


def _rev_scan_bwd(fns, unroll, stash, res, cot):
    stacked, shared, (a, b), stashes = res
    da, db = cot
    depth = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    zero_shared = jax.tree_util.tree_map(jnp.zeros_like, shared)

    def back(carry, sl):
        sl_params, sl_stash = sl
        a, b, da, db, dshared, it = carry
        ds_out: typing.List[typing.Any] = [None] * len(fns)
        dshared_new = list(dshared)
        for c in range(len(fns) - 1, -1, -1):
            f, stk, shr = fns[c], sl_params[c], shared[c]
            b_prev = a
            chan = _provide_chan(stash, sl_stash[c])
            fval, fvjp = scope.replay_vjp(
                lambda stk_, shr_, x_: _call_block(f, {**stk_, **shr_}, x_,
                                                   it=it, chan=chan),
                stk, shr, b_prev)
            a_prev = b - fval
            dstk, dshr, db_extra = fvjp(db)
            a, b = a_prev, b_prev
            da, db = db, da + db_extra
            ds_out[c] = dstk
            dshared_new[c] = jax.tree_util.tree_map(lambda p, g: p + g,
                                                    dshared_new[c], dshr)
        return (a, b, da, db, tuple(dshared_new), it - 1), tuple(ds_out)

    carry0 = (a, b, da, db, zero_shared, jnp.int32(depth - 1))
    (_, _, da, db, dshared, _), ds_stacked = jax.lax.scan(
        back, carry0, (stacked, stashes), reverse=True, unroll=unroll)
    return ds_stacked, dshared, da, db


rev_scan.defvjp(_rev_scan_fwd, _rev_scan_bwd)


def _mom_scan_run(fns, alpha, unroll, stacked, shared, x, v, stash):
    def step(carry, sl):
        x, v, it = carry
        outs = []
        for c, f in enumerate(fns):
            chan = _collect_chan(stash)
            v = v * alpha + _call_block(f, {**sl[c], **shared[c]}, x,
                                        it=it, chan=chan) * (1 - alpha)
            x = x + v
            outs.append(_chan_items(chan))
        return (x, v, it + 1), tuple(outs)

    (x, v, _), stashes = jax.lax.scan(step, (x, v, jnp.int32(0)), stacked,
                                      unroll=unroll)
    return x, v, stashes


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 7))
def momentum_scan(fns, alpha, unroll, stacked, shared, x, v,
                  stash: typing.FrozenSet[str] = frozenset()):
    x, v, _ = _mom_scan_run(fns, alpha, unroll, stacked, shared, x, v,
                            frozenset())
    return x, v


def _mom_scan_fwd(fns, alpha, unroll, stacked, shared, x, v, stash):
    x, v, stashes = _mom_scan_run(fns, alpha, unroll, stacked, shared, x, v,
                                  stash)
    return (x, v), (stacked, shared, (x, v), stashes)


def _mom_scan_bwd(fns, alpha, unroll, stash, res, cot):
    stacked, shared, (x, v), stashes = res
    dx, dv = cot
    depth = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    zero_shared = jax.tree_util.tree_map(jnp.zeros_like, shared)

    def back(carry, sl):
        sl_params, sl_stash = sl
        x, v, dx, dv, dshared, it = carry
        ds_out: typing.List[typing.Any] = [None] * len(fns)
        dshared_new = list(dshared)
        for c in range(len(fns) - 1, -1, -1):
            f, stk, shr = fns[c], sl_params[c], shared[c]
            x_prev = x - v
            chan = _provide_chan(stash, sl_stash[c])
            fval, fvjp = scope.replay_vjp(
                lambda stk_, shr_, x_: _call_block(f, {**stk_, **shr_}, x_,
                                                   it=it, chan=chan),
                stk, shr, x_prev)
            v_prev = (v - fval * (1 - alpha)) / alpha
            g = dx + dv
            dstk, dshr, dx_f = fvjp(g * (1 - alpha))
            dx_prev = dx + dx_f
            dv_prev = g * alpha
            x, v = x_prev, v_prev
            dx, dv = dx_prev, dv_prev
            ds_out[c] = dstk
            dshared_new[c] = jax.tree_util.tree_map(lambda p, q: p + q,
                                                    dshared_new[c], dshr)
        return (x, v, dx, dv, tuple(dshared_new), it - 1), tuple(ds_out)

    carry0 = (x, v, dx, dv, zero_shared, jnp.int32(depth - 1))
    (_, _, dx, dv, dshared, _), ds_stacked = jax.lax.scan(
        back, carry0, (stacked, stashes), reverse=True, unroll=unroll)
    return ds_stacked, dshared, dx, dv


momentum_scan.defvjp(_mom_scan_fwd, _mom_scan_bwd)


def _checkpoint_policy(params: ModelParameter, mesh=None,
                       names: typing.Optional[typing.Tuple[str, ...]] = None):
    """The ``jax.checkpoint`` policy for the 'checkpoint' strategy: the
    named one (``gradient_checkpointing_policy``; the default
    "nothing_saveable" is jax.checkpoint's own default, so reference
    configs are unchanged) and, where a kind of model/remat.py rides it
    (``stash_names``: ``experts`` — layer ``moe``'s named outputs —
    ``recurrent`` — the output a recurrent mixer offers — ``attention`` —
    the (out, lse) every flash layer names under the blocks' "name" channel,
    ``_name_chan``), also those names: what a region of the step saves that
    holds an admitted execution of every kind.  The ``recurrent`` kind and the
    ``dense`` one — layer ``mlp``'s gate and up outputs — are admitted an
    execution at a time from the step's end, so what they add is a region's
    own: ``names``, what one region saves (``_region_policies``)."""
    if names is None:
        from .remat import stash_names
        names = stash_names(params, mesh)
    return _named_policy(params.gradient_checkpointing_policy, names)


def _region_policies(params: ModelParameter, mesh=None) -> list:
    """The ``jax.checkpoint`` policy of every region of the step, by its
    place (``_region``): ``_checkpoint_policy``'s over the names of the kinds
    whose admitted executions the region holds (model/remat.py
    ``region_names``)."""
    from .remat import region_names
    return [_checkpoint_policy(params, mesh, names)
            for names in region_names(params, mesh)]


@functools.lru_cache(maxsize=None)
def _named_policy(named: str, names: typing.Tuple[str, ...]):
    """``jax.checkpoint_policies.<named>`` and, beside it, ``names`` saved:
    ONE object for the regions that save the same, so that a step's jaxpr
    reads the same wherever its regions do."""
    named = getattr(jax.checkpoint_policies, named)
    if not names:
        return named
    return jax.checkpoint_policies.save_from_both_policies(
        named, jax.checkpoint_policies.save_only_these_names(*names))


def _region(params: ModelParameter, loop_pass: int, depth_idx: int,
            cfg_idx: int, module: bool = False) -> int:
    """A body block's ``jax.checkpoint`` region's place among the step's, in
    execution order: a looped model's passes (model/loop.py) outermost.  A
    scanned body traces ONE block a ``cfg_idx`` for all its iterations
    (``depth_idx`` 0): model/remat.py admits a scanned body's executions all
    together or not at all, so the one policy is every iteration's.
    ``module``: a block of the multi-token-prediction module (model/mtp.py),
    whose regions follow the body's (``depth_idx``: the module's pass)."""
    if module:
        return params.loop_steps * params.depth * len(params.block_config) \
            + depth_idx * len(params.mtp_block_config) + cfg_idx
    return (loop_pass * params.depth + depth_idx) \
        * len(params.block_config) + cfg_idx


def _merge_stats(parts) -> dict:
    """One ``{name: 1-D array}`` from layers' ``{name: scalar}`` reports, or
    from blocks' (or a scan's stacked) merged ones, in execution order."""
    return {k: jnp.concatenate([jnp.reshape(p[k], (-1,))
                                for p in parts if k in p])
            for k in sorted({k for p in parts for k in p})}


def _block_with_stats(f, collect: bool, chan=None):
    """``(subset, x, it) -> (out, {name: [n] array})``: the block, and the
    statistics its layers reported (``Context.layer_stats``) as an explicit
    output, so that they can leave a checkpoint or scan region.  With
    ``side`` (the carried side values, ``Context.side``) ``out`` is
    ``(stream, side)``: the values enter and leave the region as explicit
    operands too, so the backward holds their cotangents.  ``chan``: the
    block's stash channel (``_name_chan``), handed on where there is one."""
    def call(subset, x, it=None, side=None):
        kwargs = {} if side is None else {"side": side}
        if chan is not None:
            kwargs["stash"] = chan
        if not collect:
            return f(subset, x, it=it, **kwargs), {}
        sink: list = []
        out = f(subset, x, it=it, layer_stats=sink, **kwargs)
        return out, _merge_stats(sink)
    return call


def _plain_scan(fns, stacked, shared, x, use_checkpoint: bool,
                unroll: int = 1, ckpt_policies=None, collect: bool = False,
                chan=None):
    """Scanned 'checkpoint' / 'none' strategies: O(depth) carries saved by
    scan AD; with use_checkpoint each block recomputes its interior under
    its own of ``ckpt_policies`` (one a traced block).  Returns the output
    and the layers' statistics (empty unless ``collect``)."""
    def step(carry, sl):
        x, it = carry
        parts = []
        for c, (f, stk, shr) in enumerate(zip(fns, sl, shared)):
            call = _block_with_stats(f, collect, chan)
            if use_checkpoint:
                call = jax.checkpoint(call, policy=ckpt_policies[c])
            x, stats = call({**stk, **shr}, x, it)
            parts.append(stats)
        return (x, it + 1), _merge_stats(parts)

    (x, _), stats = jax.lax.scan(step, (x, jnp.int32(0)), stacked,
                                 unroll=unroll)
    return x, stats


def _strategy_scan_save(params: ModelParameter, fns, stacked, shared, src,
                        strategy: str, policy: str):
    """The 'save'/'save_dots' remat policies over the scanned stack: the
    IDENTICAL revnet/momentum primal recurrence, WITHOUT the custom_vjp
    wrapper — native scan AD saves the linearization residuals (stacked
    over depth) instead of re-running each block's forward in the
    backward.  'save_dots' additionally wraps every block in
    ``jax.checkpoint(policy=dots_saveable)`` so only GEMM outputs are
    saved and elementwise work is recomputed (model/remat.py)."""
    from .remat import block_caller
    call = block_caller(policy)
    alpha = params.momentumnet_alpha

    def step(carry, sl):
        if strategy == "revnet":
            x1, x2, it = carry
            for c, f in enumerate(fns):
                x1, x2 = x2, x1 + call(f, {**sl[c], **shared[c]}, x2, it)
            return (x1, x2, it + 1), None
        x, v, it = carry
        for c, f in enumerate(fns):
            v = v * alpha + call(f, {**sl[c], **shared[c]}, x, it) \
                * (1 - alpha)
            x = x + v
        return (x, v, it + 1), None

    (a, b, _), _ = jax.lax.scan(step, (src, src, jnp.int32(0)), stacked,
                                unroll=params.scan_unroll)
    return a + b


def _plan_scan(params: ModelParameter,
               plan: typing.Tuple[BlockSpec, ...]) -> typing.Optional[tuple]:
    """Group the per-block parameter plan by cfg index for scanning.

    Returns (rel_names, shared_names, abs_names) per cfg — rel names are the
    depth-0 forms of per-depth parameters, abs_names[c][i] maps rel -> the
    actual name at depth i — or None when the stack isn't depth-homogeneous."""
    depth, n_cfg = params.depth, len(params.block_config)
    if depth < 2:
        return None
    by = {(i, c): names for i, c, names in plan}
    rel_per_cfg, shared_per_cfg, abs_per_cfg = [], [], []
    for c in range(n_cfg):
        marker1 = f"block1_{c}_"
        names1 = by[(1, c)]
        shared = tuple(n for n in names1 if marker1 not in n)
        rel = tuple(n.replace(marker1, f"block0_{c}_")
                    for n in names1 if marker1 in n)
        abs_names = []
        for i in range(depth):
            marker = f"block{i}_{c}_"
            names_i = by[(i, c)]
            if not set(shared) <= set(names_i):
                return None
            perdepth = [n for n in names_i if n not in shared]
            if any(marker not in n for n in perdepth):
                return None
            rel_i = {n.replace(marker, f"block0_{c}_"): n for n in perdepth}
            if set(rel_i) != set(rel):
                return None
            abs_names.append(rel_i)
        rel_per_cfg.append(rel)
        shared_per_cfg.append(shared)
        abs_per_cfg.append(abs_names)
    return rel_per_cfg, shared_per_cfg, abs_per_cfg


def _scan_prologue(params: ModelParameter, ctx, plan, src: NamedTensor,
                   attn_base: int) -> typing.Optional[tuple]:
    """Shared setup for the train- and decode-time depth scans: homogeneity
    gates, stacked per-depth parameter pytrees, shared subsets, and the
    depth-0 ReplayBlocks.  Returns (stacked, shared, fns) or None when the
    stack cannot be scanned."""
    info = _plan_scan(params, plan)
    if info is None:
        return None
    rel_per_cfg, shared_per_cfg, abs_per_cfg = info
    if not any(rel_per_cfg):
        # nothing to scan over (fully weight-tied / param-free stack):
        # lax.scan would reject an empty xs pytree
        return None
    # attention-axis round-robin must look identical every iteration
    from .utils import attention_axis_candidates
    cycle = max(1, len(attention_axis_candidates(src.dims, params)))
    attn_counts = [sum(layer.split("-")[0] == "attention" for layer in bc.layer)
                   for bc in params.block_config]
    if cycle > 1 and sum(attn_counts) % cycle:
        return None
    try:
        stacked = tuple(
            {r: jnp.stack([ctx.params[abs_per_cfg[c][i][r]]
                           for i in range(params.depth)])
             for r in rel_per_cfg[c]}
            for c in range(len(params.block_config)))
    except (ValueError, TypeError):  # ragged shapes across depth
        return None
    shared = tuple({n: ctx.params[n] for n in shared_per_cfg[c]}
                   for c in range(len(params.block_config)))
    prefix = tuple(f.name for f in ctx.stack[1:])
    fns, off = [], 0
    for c, bc in enumerate(params.block_config):
        fns.append(ReplayBlock(params, bc, 0, c, prefix, attn_base + off))
        off += attn_counts[c]
    return stacked, shared, tuple(fns)


def resolve_stash(params: ModelParameter, mesh=None) -> bool:
    """Back-compat boolean view of the remat policy: ``True`` iff the
    ATTENTION kind rides the strategy residuals (the (out, lse) pairs;
    +23% at 16k ctx, docs/PERFORMANCE.md).  The full decision — every
    kind, and the save-vs-recompute choice — lives in
    :func:`model.remat.stash_kinds` / :func:`model.remat.resolve_remat`."""
    from .remat import stash_kinds
    return "attention" in stash_kinds(params, mesh)


def _try_scan(params: ModelParameter, ctx, plan, src: NamedTensor,
              strategy: str, attn_base: int, loop_pass: int = 0
              ) -> typing.Optional[NamedTensor]:
    pro = _scan_prologue(params, ctx, plan, src, attn_base)
    if pro is None:
        return None
    stacked, shared, fns = pro
    from .remat import resolve_remat, stash_kinds
    policy = resolve_remat(params, ctx.mesh)
    if strategy in ("revnet", "momentum"):
        if policy in ("save", "save_dots"):
            return _strategy_scan_save(params, fns, stacked, shared, src,
                                       strategy, policy)
        stash = stash_kinds(params, ctx.mesh)
        if strategy == "revnet":
            x1, x2 = rev_scan(fns, params.scan_unroll, stacked, shared, src,
                              src, stash)
            return x1 + x2
        x, v = momentum_scan(fns, params.momentumnet_alpha, params.scan_unroll,
                             stacked, shared, src, src, stash)
        return x + v
    policies = _region_policies(params, ctx.mesh)
    out, stats = _plain_scan(fns, stacked, shared, src,
                             strategy == "checkpoint", params.scan_unroll,
                             [policies[_region(params, loop_pass, 0, c)]
                              for c in range(len(fns))],
                             collect=ctx.layer_stats is not None,
                             chan=_name_chan(params, ctx.mesh))
    if stats:
        ctx.layer_stats.append(_merge_stats([stats]))
    return out


def _forward_recurrence(strategy: str, alpha: float, pairs, carry,
                        it=None, call=None):
    """One shared forward-only walk of the block recurrences (decode and the
    decode-scan body both use it): revnet/momentum carry two streams, the
    rest one.  ``pairs`` yields (fn, subset).  ``call`` overrides how a
    block is invoked (the save_dots remat policy wraps each block in
    jax.checkpoint — model/remat.py block_caller)."""
    if call is None:
        def call(f, subset, x, it=None):
            return f(subset, x, it=it)
    if strategy == "revnet":
        x1, x2 = carry
        for f, subset in pairs:
            x1, x2 = x2, x1 + call(f, subset, x2, it=it)
        return x1, x2
    if strategy == "momentum":
        x, v = carry
        for f, subset in pairs:
            v = v * alpha + call(f, subset, x, it=it) * (1 - alpha)
            x = x + v
        return x, v
    (x,) = carry
    for f, subset in pairs:
        x = call(f, subset, x, it=it)
    return (x,)


# marker prefix for depth-stacked decode-cache keys (leading axis = depth)
STACKED_CACHE_PREFIX = "__stacked__/"

_CACHE_BLOCK_RE = None


def _cache_block_re():
    global _CACHE_BLOCK_RE
    if _CACHE_BLOCK_RE is None:
        import re
        _CACHE_BLOCK_RE = re.compile(r"block(\d+)_(\d+)_")
    return _CACHE_BLOCK_RE


def stack_decode_caches(params: ModelParameter,
                        flat: typing.Dict[str, jax.Array]
                        ) -> typing.Dict[str, jax.Array]:
    """Group per-depth block caches into ``[depth, ...]`` arrays keyed
    ``__stacked__/<depth-0 name>``; non-block (and incomplete) caches pass
    through flat.  Keeping the sampler's while_loop carry in this layout
    removes the per-token flat<->stacked restack inside the decode scan
    (hundreds of MB of HBM traffic per token at flagship size —
    docs/PERFORMANCE.md 'Decoding')."""
    block_re = _cache_block_re()
    groups: typing.Dict[str, typing.Dict[int, str]] = {}
    out: typing.Dict[str, jax.Array] = {}
    for name, arr in flat.items():
        m = block_re.search(name)
        if m is None or int(m.group(1)) >= params.depth:
            out[name] = arr
            continue
        rel = name[:m.start()] + f"block0_{m.group(2)}_" + name[m.end():]
        groups.setdefault(rel, {})[int(m.group(1))] = name
    for rel, per in groups.items():
        if set(per) != set(range(params.depth)):
            for name in per.values():
                out[name] = flat[name]
            continue
        try:
            out[STACKED_CACHE_PREFIX + rel] = jnp.stack(
                [flat[per[i]] for i in range(params.depth)])
        except (ValueError, TypeError):
            for name in per.values():
                out[name] = flat[name]
    return out


def unstack_decode_caches(params: ModelParameter,
                          mixed: typing.Dict[str, jax.Array]
                          ) -> typing.Dict[str, jax.Array]:
    """Inverse of :func:`stack_decode_caches` (flat per-block names)."""
    block_re = _cache_block_re()
    out: typing.Dict[str, jax.Array] = {}
    for name, arr in mixed.items():
        if not name.startswith(STACKED_CACHE_PREFIX):
            out[name] = arr
            continue
        rel = name[len(STACKED_CACHE_PREFIX):]
        m = block_re.search(rel)
        assert m is not None, rel
        for i in range(params.depth):
            flat_name = rel[:m.start()] + f"block{i}_{m.group(2)}_" + rel[m.end():]
            out[flat_name] = arr[i]
    return out


def _try_decode_scan(params: ModelParameter, ctx, plan, src: NamedTensor,
                     strategy: str, attn_base: int
                     ) -> typing.Optional[NamedTensor]:
    """Scan the DECODE body over depth (forward-only, no custom_vjp).

    The unrolled decode while_loop body issues thousands of tiny kernels per
    token at depth 32 (measured 207 ms/token vs 4 ms at depth 2 — pure
    dispatch overhead); scanning bounds the program to one iteration.  KV
    caches are name-keyed per block.  Preferred layout: the sampler carries
    them depth-STACKED (``stack_decode_caches``); the scan reads them as
    loop invariants and returns row-sized updates as ys (see the layout
    comment at the step body) with ZERO per-token restacking.  A flat
    carry still works (stacked on entry, unstacked on exit) for callers that
    never adopted the stacked layout.  Runs only when the cache dict is
    complete and depth-homogeneous (the discovery pass with empty caches
    stays unrolled and defines those names)."""
    from . import decode as decode_mod
    state = ctx.decode
    if not state.caches:
        return None  # discovery pass: names must be created unrolled
    pro = _scan_prologue(params, ctx, plan, src, attn_base)
    if pro is None:
        return None
    stacked_params, shared, fns = pro

    block_re = _cache_block_re()
    stacked_in = {k[len(STACKED_CACHE_PREFIX):]: v
                  for k, v in state.caches.items()
                  if k.startswith(STACKED_CACHE_PREFIX)}
    if stacked_in:
        # stacked carry: rel names are the keys; nothing to regroup
        if any(v.shape[0] != params.depth for v in stacked_in.values()):
            return None
        stacked_caches = stacked_in
    else:
        # flat carry: one restack on entry (non-block caches need no
        # handling: DecodeState.out starts as a copy of the full cache dict,
        # so they pass through unchanged).  Any block-named cache that
        # stack_decode_caches could NOT fold (depth-incomplete / ragged)
        # means the stack is not homogeneous: bail to the unrolled body.
        regrouped = stack_decode_caches(params, state.caches)
        if any(not k.startswith(STACKED_CACHE_PREFIX) and block_re.search(k)
               for k in regrouped):
            return None
        stacked_caches = {k[len(STACKED_CACHE_PREFIX):]: v
                          for k, v in regrouped.items()
                          if k.startswith(STACKED_CACHE_PREFIX)}
    rel_cache_names = set(stacked_caches)

    alpha = params.momentumnet_alpha

    # The depth-stacked caches do NOT ride the scan carry: a buffer carried
    # through the INNER while loop defeats XLA's copy elision for the OUTER
    # token loop — the compiled module copies every cache twice per token at
    # the nested-loop boundary (the big-cache decode bug: 60.1 ms/token at
    # 32k vs the ~8 ms read bound, BASELINE.md round 5; reproduced in
    # compiled HLO by tests/decode_inplace_test.py).  Instead the scan READS
    # the stacked buffers as loop invariants (slice per depth) and emits the
    # per-depth updates as ys — row-sized for the KV scatter sites
    # (DecodeState.row_updates), full-block for the small recurrence caches
    # (cumsum totals, conv windows) — and ONE dynamic_update_slice per cache
    # after the scan applies all depth rows at the token position.  The
    # outer-loop carry then sees a read (inside the scan) followed by a
    # single row-granular write: exactly the pattern the aliaser keeps in
    # place.
    row_axis: typing.Dict[str, int] = {}  # filled during the scan trace

    def step(carry, sl_params):
        *streams, it = carry
        sl_caches = {k: jax.lax.dynamic_index_in_dim(v, it, 0, keepdims=False)
                     for k, v in stacked_caches.items()}
        sub = decode_mod.DecodeState(state.pos, state.seq_len, state.seq_name,
                                     sl_caches,
                                     cache_dtype=state.cache_dtype,
                                     model_params=state.model_params,
                                     width=state.width)
        saved_decode = ctx.decode
        ctx.decode = sub
        try:
            pairs = [(f, {**sl_params[c], **shared[c]})
                     for c, f in enumerate(fns)]
            streams = _forward_recurrence(strategy, alpha, pairs,
                                          tuple(streams), it=it)
        finally:
            ctx.decode = saved_decode
        for rel in sub.out:
            # the discovery pass defines every cache name before the scan
            # runs; a cache born lazily inside the scan would be silently
            # dropped from the carry (corrupting decode), so fail loudly
            assert rel in rel_cache_names, (
                f"decode cache {rel!r} created inside the scan body; it is "
                f"not part of the sampler carry — the discovery-pass "
                f"invariant is violated")
        ys = {}
        for rel in rel_cache_names:
            arr = sub.out.get(rel, sl_caches[rel])
            upd = sub.row_updates.get(rel)
            if upd is not None:
                row, axis = upd
                row_axis[rel] = axis
                ys[rel] = row.astype(stacked_caches[rel].dtype)
            else:
                ys[rel] = arr.astype(stacked_caches[rel].dtype)
        return (*streams, it + 1), ys

    carry0 = ((src, src, jnp.int32(0))
              if strategy in ("revnet", "momentum")
              else (src, jnp.int32(0)))
    carry, ys = jax.lax.scan(step, carry0, stacked_params)
    *streams, _ = carry
    for rel, arr in ys.items():
        axis = row_axis.get(rel)
        if axis is None:
            # small recurrence caches: the stacked ys IS the new buffer
            new = arr
        elif decode_mod.is_vector_pos(state.pos):
            # per-slot positions (continuous-batching engine): each row of
            # every depth scatters at its own position — vmap the per-row
            # scatter over the leading depth axis of the stacked buffer
            with jax.named_scope("cache_write"):
                new = jax.vmap(lambda b, r: decode_mod.scatter_rows(
                    b, r, state.pos, axis))(stacked_caches[rel], arr)
        else:
            # all depth rows land in one scatter at the token position
            starts = [jnp.int32(0)] * arr.ndim
            starts[axis + 1] = state.pos
            with jax.named_scope("cache_write"):
                new = jax.lax.dynamic_update_slice(stacked_caches[rel], arr,
                                                   tuple(starts))
        if stacked_in:
            # the sampler carries caches depth-stacked: write back verbatim
            state.out[STACKED_CACHE_PREFIX + rel] = new
        else:
            state.out.update(unstack_decode_caches(
                params, {STACKED_CACHE_PREFIX + rel: new}))
    return sum(streams[1:], streams[0])


def _try_prefill_scan(params: ModelParameter, ctx, plan, src: NamedTensor,
                      strategy: str, attn_base: int
                      ) -> typing.Optional[NamedTensor]:
    """Scan the PREFILL body over depth (forward-only, full sequence).

    Mirrors ``_try_decode_scan``'s structure: each iteration runs one
    depth-unit in prefill mode, and the caches the iteration captures
    (model/decode.py ``PrefillState``) return as scan ys — stacked on a
    leading depth axis, which is exactly the ``__stacked__/<depth-0 name>``
    layout the decode scan's sampler carry uses.  One full forward replaces
    the O(prompt) per-token decode steps the sampler would otherwise spend
    walking the prompt."""
    from . import decode as decode_mod
    state = ctx.prefill
    pro = _scan_prologue(params, ctx, plan, src, attn_base)
    if pro is None:
        return None
    stacked_params, shared, fns = pro
    alpha = params.momentumnet_alpha

    def step(carry, sl_params):
        *streams, it = carry
        sub = decode_mod.PrefillState(state.n, state.seq_len, state.seq_name,
                                      cache_dtype=state.cache_dtype,
                                      model_params=state.model_params)
        saved = ctx.prefill
        ctx.prefill = sub
        try:
            pairs = [(f, {**sl_params[c], **shared[c]})
                     for c, f in enumerate(fns)]
            streams = _forward_recurrence(strategy, alpha, pairs,
                                          tuple(streams), it=it)
        finally:
            ctx.prefill = saved
        return (*streams, it + 1), dict(sub.out)

    carry0 = ((src, src, jnp.int32(0))
              if strategy in ("revnet", "momentum")
              else (src, jnp.int32(0)))
    carry, ys = jax.lax.scan(step, carry0, stacked_params)
    *streams, _ = carry
    for rel, arr in ys.items():
        state.out[STACKED_CACHE_PREFIX + rel] = arr
    return sum(streams[1:], streams[0])


# ---- body assembly -------------------------------------------------------

def run_body_blocks(params: ModelParameter, src: NamedTensor,
                    plan: typing.Optional[typing.Tuple[BlockSpec, ...]],
                    loop_pass: int = 0,
                    module: typing.Optional[int] = None
                    ) -> typing.Tuple[NamedTensor, typing.Tuple[BlockSpec, ...]]:
    """Run depth × block_config with the configured memory strategy.

    In init mode (plan None) blocks run plainly in the outer context and the
    per-block touched-parameter plan is recorded.  In apply mode the plan
    feeds explicit parameter subsets into the custom-vjp sequences.
    ``loop_pass``: which pass of a looped model this is (model/loop.py): the
    ``checkpoint`` strategy's regions count on over the passes (``_region``).
    ``module``: the pass of the multi-token-prediction module (model/mtp.py)
    whose blocks these are in the body's place — ``mtp_block_config`` once,
    named ``block<module>_<c>`` under the caller's scope, ``plan`` theirs;
    the configuration admits the module under ``checkpoint`` / ``none``
    without ``scan_layers`` or a pipeline mesh, and its caller refuses decode
    and prefill.
    """
    ctx = scope.current()
    strategy = params.memory_reduction_strategy
    blocks = [(i, c, bc) for i in range(params.depth)
              for c, bc in enumerate(params.block_config)] \
        if module is None else [(module, c, bc) for c, bc
                                in enumerate(params.mtp_block_config)]

    if ctx.mode == "init" or plan is None:
        specs: typing.List[BlockSpec] = []
        out = src
        prev_touched, prev_side = ctx.touched, ctx.side
        ctx.side = {}       # init only makes parameters: every mode carries
        for i, c, bc in blocks:
            ctx.touched = []
            out = block_part_fn(params, bc, out, _block_scope_name(i, c))
            specs.append((i, c, tuple(ctx.touched)))
        ctx.touched, ctx.side = prev_touched, prev_side
        if strategy in ("revnet", "momentum"):
            # init forward ran the plain composition; the strategies compute
            # x+f stacks whose *values* differ from the plain stack, but init
            # only materialises parameters, so values are irrelevant here.
            pass
        return out, tuple(specs)

    prefix = tuple(f.name for f in ctx.stack[1:])
    fns = []
    subsets = []
    attn_base = params.attention_idx
    attn_idx = attn_base
    for (i, c, bc), (_, _, names) in zip(blocks, plan):
        fns.append(ReplayBlock(params, bc, i, c, prefix, attn_idx))
        attn_idx += sum(layer.split('-')[0] == "attention" for layer in bc.layer)
        subsets.append({n: ctx.params[n] for n in names})
    params.attention_idx = attn_idx

    def forward_only():
        # the shared forward-only unrolled fallback (identical values to the
        # trained forward — no custom_vjp/checkpoint wrappers)
        carry = ((src, src) if strategy in ("revnet", "momentum")
                 else (src,))
        streams = _forward_recurrence(strategy, params.momentumnet_alpha,
                                      zip(fns, subsets), carry)
        return sum(streams[1:], streams[0])

    if ctx.decode is not None:
        # no gradients at decode time: run the invertible-forward recurrences
        # plainly (custom_vjp wrappers would only complicate the while_loop
        # trace)
        if params.scan_layers and params.depth >= 2:
            scanned = _try_decode_scan(params, ctx, plan, src, strategy,
                                       attn_base)
            if scanned is not None:
                return scanned, plan
        return forward_only(), plan

    if getattr(ctx, "prefill", None) is not None:
        # single-pass prompt prefill: forward-only like decode, captures
        # riding ctx.prefill.out — the scan form stacks them per depth, the
        # unrolled form writes the flat per-block names, matching the decode
        # build's cache layouts
        if params.scan_layers and params.depth >= 2:
            scanned = _try_prefill_scan(params, ctx, plan, src, strategy,
                                        attn_base)
            if scanned is not None:
                return scanned, plan
        return forward_only(), plan

    if ctx.stats_sink is not None:
        # forward-only stats probe as a plain python loop so layer stats
        # appended to the sink stay at the consumer's trace level —
        # lax.scan / custom_vjp would strand them in a sub-trace
        return forward_only(), plan

    mesh = ctx.mesh
    from ..core import sharding as shardlib
    if mesh is not None and mesh.shape.get(shardlib.PIPE_AXIS, 1) > 1:
        from ..parallel.pipeline import pipeline_body
        return pipeline_body(params, mesh, fns, subsets, plan, src,
                             strategy), plan

    if params.scan_layers:
        # attention_idx was already advanced to its post-body value by the
        # builder above; the scanned blocks replay from the captured base
        scanned = _try_scan(params, ctx, plan, src, strategy, attn_base,
                            loop_pass)
        if scanned is not None:
            return scanned, plan

    from .remat import block_caller, resolve_remat, stash_kinds
    policy = resolve_remat(params, ctx.mesh)
    stash = stash_kinds(params, ctx.mesh)
    if strategy in ("revnet", "momentum") and policy in ("save",
                                                         "save_dots"):
        # unrolled save modes: the identical primal recurrence under native
        # AD (no custom_vjp) — zero backward recompute, residuals saved
        call = block_caller(policy)
        carry = (src, src)
        streams = _forward_recurrence(strategy, params.momentumnet_alpha,
                                      zip(fns, subsets), carry, call=call)
        return sum(streams[1:], streams[0]), plan
    if strategy == "revnet":
        x1, x2 = rev_sequence(tuple(fns), tuple(subsets), src, src, stash)
        return x1 + x2, plan
    if strategy == "momentum":
        x, v = momentum_sequence(tuple(fns), params.momentumnet_alpha,
                                 tuple(subsets), src, src, stash)
        return x + v, plan
    # checkpoint / none: the plain stream, each block's layer statistics
    # an explicit output of its region, and beside the stream the CARRIED
    # SIDE VALUES (Context.side: what one layer leaves for a later one — layer
    # moe's router state under router_mlp, layer route_early's logits for the
    # routed_early sparse layer of the next block): a dict that enters and
    # leaves every block's region as an operand, empty — no operand at all —
    # in a model whose layers carry nothing
    out, parts, side = src, [], {}
    chan = _name_chan(params, ctx.mesh)
    policies = _region_policies(params, ctx.mesh)
    for (i, c, _), f, s in zip(blocks, fns, subsets):
        call = _block_with_stats(f, ctx.layer_stats is not None, chan)
        if strategy == "checkpoint":
            call = jax.checkpoint(call, policy=policies[_region(
                params, loop_pass, i, c, module is not None)])
        (out, side), stats = call(s, out, None, side)
        parts.append(stats)
    if any(parts):
        ctx.layer_stats.append(_merge_stats(parts))
    return out, plan
