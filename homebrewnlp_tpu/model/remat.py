"""The memory strategies' remat policy: what a strategy's backward does about
re-materialising block interiors, one resolved decision
(:func:`resolve_remat`) that ``model/blocks.py`` consumes.

==============  =============================================================
policy          behavior
==============  =============================================================
``recompute``   the strategy ``custom_vjp`` re-runs each block's forward
                inside ``jax.vjp`` — O(1) activation memory in depth, one
                extra forward of compute
``stash``       recompute, but what is dear to replay per byte is kept for
                the backward, a KIND at a time (:data:`STASH_KINDS`).  What
                a kind is made of the layers DECLARE (``model/declare.py``
                ``Offer``: kind, ``checkpoint_name``s, bytes); this module
                names no layer
``save``        NO ``custom_vjp``: the identical primal recurrence under
                native scan AD; every linearization residual is saved —
                zero recompute, O(depth) residual memory
``save_dots``   ``save`` with each block wrapped in ``jax.checkpoint``
                (policy ``dots_saveable``): GEMM outputs saved, elementwise
                recomputed
``auto``        resolved below, per kind
==============  =============================================================

All four execute the SAME primal recurrence — losses are bit-identical and
gradients agree to reconstruction ulps (tests/remat_policy_test.py).

**The kinds.**  ``attention``: a flash / ring attention layer's ``(out,
lse)`` — the replay runs no forward attention kernel (and no ring hop).
``bottleneck``: an in-projection's output ``[batch, sequence,
intermediate]`` — the replay runs neither that matmul nor, where it
contracts a mesh-sharded axis, its tensor-parallel all-reduce.  Both ride
the revnet / momentum residuals through the stash channel
(``core/stash.py``, ``model/blocks.py``).  ``experts``: a routed layer's
grouped-matmul outputs, routing triple and choice.  ``recurrent``: what a
recurrent mixer offers of its rule — its output, and where Pallas pairs run
the rule, as the offer's interior, what their forwards hand their backwards,
so that the replay runs none of them — or, of a mixer with no inner
re-materialisation (layer ``mamba``), its in-projection's output: the replay
runs no in-projection matmul.
``dense``: layer ``mlp``'s two matmul outputs, gate and up ``[batch,
sequence, intermediate]`` — the replay runs the activation and the product
alone, no dense matmul (2 of an MLP's 11-12 matmul units a step).
The ``checkpoint`` strategy has no residuals of its own: there these three,
and ``attention`` again, are SAVED by each block's ``jax.checkpoint``
through a policy over their names (``model/blocks.py _checkpoint_policy``;
for ``attention`` the blocks get a stateless "name" channel under which a
flash call names its outputs).

**What ``auto`` does** (:func:`stash_kinds`), in order:

1. the explicit ``remat_policy`` where set;
2. the legacy ``stash_attention_outputs`` boolean where the user set one:
   ``false`` is ``recompute``; ``true`` forces the attention kind only (the
   other kinds still resolve by their rules);
3. each kind by its rule.  Under revnet / momentum: ``attention`` where
   the sequence is >= 2,048 in whole 128-tiles and one pair a block fits
   :data:`STASH_HBM_FRACTION` of a chip's HBM; then ``bottleneck`` where the
   in-projection's contraction crosses a ``model`` mesh axis > 1 and its
   per-device bytes fit what ``attention`` left.  Under ``checkpoint`` (the
   whole budget again: the two above take nothing of it there) ONE rule:
   each kind is judged on its own bytes against what the kinds before it
   TOOK, and one that passes it takes nothing and moves no other kind's
   decision.  In order: ``attention`` — every layer whose flash call engages
   (one device, ``use_flash_attention``, a sequence of whole 128-tiles) and
   in which a query sees at least :data:`ATTENTION_MIN_KEYS` keys, all such
   layers or none; first, because its bytes grow with the sequence and the
   forward it skips with the square, so no cheaper kind squeezes it out;
   ``experts`` where the WHOLE depth's offered bytes fit — all layers or
   none; then the two kinds admitted a PART at a time, ``recurrent`` and
   ``dense``: the executions of the step ONE BY ONE FROM THE LAST backwards
   (a looped model's passes outermost) while the next fits — the offers'
   first part (a rule's output), then into what that leaves the INTERIOR of
   those that ride, where a layer declares one (what its kernels keep for
   their backwards), likewise from the end.  From the end because the last
   block's backward comes first: its values are held for the shortest time,
   and through its own backward they stand where the replay would have put
   them anyway.  ``dense``, last, is charged what every earlier kind took AND
   the block inputs ``checkpoint`` itself keeps, one ``[batch, sequence,
   features]`` for every ``jax.checkpoint`` region of the step (with the
   carried side values that enter the regions beside them): the 15%
   bounds what the step holds ACROSS its backward, and those inputs are such
   bytes that no other kind counts.  Both: all the step's executions or none
   under ``scan_layers`` (one traced block for all iterations); every
   execution under an explicit ``"stash"``;
4. else ``recompute``.  The save modes stay measured OPT-INS
   (docs/PERFORMANCE.md 'Round 11': ``recompute`` 204 ms/step, ``save`` 280,
   ``save_dots`` 249 on an hbm-bound rig).

:func:`remat_report` returns the analytic numbers behind the decision.
"""
from __future__ import annotations

import typing

import numpy as np

from ..config import ModelParameter
from ..core import sharding as shardlib
from .declare import carried_bytes, offers, region_offers

#: fraction of per-chip HBM the attention stash may claim (the historical
#: resolve_stash gate)
STASH_HBM_FRACTION = 0.15
#: fraction of per-chip HBM the save-mode residual estimate may claim —
#: residuals coexist with params, optimizer state and the batch
SAVE_HBM_FRACTION = 0.35
#: f32 activation-sized intermediates a mixer block's linearization keeps
#: under native AD (norm stats/xhat, glu branches, relu masks, dot
#: operands) — calibrated against the measured flagship step
SAVE_RESIDUALS_PER_BLOCK = 16

#: keys a query of a flash layer must see (``min(sequence, window)``) before
#: the layer's ``(out, lse)`` is held across a ``checkpoint`` block's replay:
#: the historical ``seq >= 2048``, read a layer (at its floor a window-512
#: forward is 1.5 ms for 302 MB saved)
ATTENTION_MIN_KEYS = 2048

POLICIES = ("recompute", "stash", "save", "save_dots")
#: what a memory strategy can keep for its backward under "stash":
#: ``attention`` and ``bottleneck`` ride the revnet / momentum residuals (the
#: channel's kinds, model/blocks.py ``stash_channel``), ``experts``,
#: ``recurrent``, ``dense`` and, there, ``attention`` too the ``checkpoint``
#: strategy's ``jax.checkpoint`` (``_checkpoint_policy``)
STASH_KINDS = ("attention", "bottleneck", "experts", "recurrent", "dense")


def _stash_bytes(params: ModelParameter) -> int:
    """Global attention-stash estimate: one (out [b,s,h,d], lse [b,h,s])
    pair per block, sized as if every block held one attention layer."""
    seq = params.stream_length // max(1, params.token_patch_size)
    calc_bytes = np.dtype(params.calculation_dtype).itemsize
    per_layer = (params.train_batch_size * seq * params.heads
                 * params.features_per_head * calc_bytes
                 + params.train_batch_size * params.heads * seq * 4)
    return per_layer * params.depth * max(1, params.macro_batching)


def _bottleneck_stash(params: ModelParameter, mesh) -> typing.Tuple[int, int, bool]:
    """``(layers, per-device bytes, crosses)`` of the bottleneck stash: the
    in-projection outputs ``[batch, sequence, intermediate]`` the whole
    depth's layers offer, laid out as ``core/sharding.with_constraint`` pins
    them (batch on 'data', replicated over 'model'), and whether the
    contraction (over the feature dims) crosses a 'model' mesh axis > 1 —
    each chip then holds a partial sum that costs an all-reduce wherever it
    is made."""
    offered = offers(params, "bottleneck")
    layers = sum(offer.count for offer in offered) * params.depth
    nbytes = sum(offer.nbytes for offer in offered) * params.depth \
        * max(1, params.macro_batching)
    crosses = False
    if mesh is not None and getattr(mesh, "devices", None) is not None:
        out_dims = [params.batch_dim, params.sequence_dim,
                    *params.intermediate]
        for axis in shardlib.spec_for_dims(params, out_dims, mesh):
            nbytes //= mesh.shape[axis] if axis is not None else 1
        crosses = mesh.shape.get(shardlib.MODEL_AXIS, 1) > 1 \
            and shardlib.MODEL_AXIS in shardlib.spec_for_dims(
                params, list(params.feature_dims), mesh)
    return layers, nbytes, crosses


def _executions(params: ModelParameter) -> int:
    """How often a step runs each layer of one depth-unit: ``depth`` times,
    and a looped model (model/loop.py) every pass again — each execution
    leaves outputs of its own for the backward."""
    return params.depth * params.loop_steps


def region_count(params: ModelParameter) -> int:
    """The ``jax.checkpoint`` regions of the step: a block of the body each
    time it runs, then the blocks of a multi-token-prediction module
    (model/declare.py ``region_offers``, model/blocks.py ``_region``)."""
    return len(region_offers(params, "attention"))


def _offered_stash(params: ModelParameter, kind: str, shards: int
                   ) -> typing.Tuple[int, int]:
    """``(executions, per-device bytes)`` of the experts or the recurrent
    kind over the whole step: what every layer that offers the kind DECLARES
    (``Offer.nbytes``, for the whole batch), each time it runs."""
    offered = [offer.nbytes for region in region_offers(params, kind)
               for offer in region]
    return len(offered), -(
        -sum(offered) * max(1, params.macro_batching) // shards)


def _forced_attention(params: ModelParameter) -> bool:
    """The configuration itself names the attention kind: an explicit
    ``"stash"``, or the legacy boolean's ``true``."""
    return _explicit_policy(params) == "stash" \
        or getattr(params, "stash_attention_outputs", "auto") is True


def _attention_min_keys(params: ModelParameter) -> int:
    """The fewest keys a query of a flash call sees where, under
    ``checkpoint``, the call's ``(out, lse)`` are saved:
    :data:`ATTENTION_MIN_KEYS` by the rule, 0 — every engaged call — where
    the configuration names the kind itself."""
    return 0 if _forced_attention(params) else ATTENTION_MIN_KEYS


def _saved_attention(params: ModelParameter, mesh, min_keys: int
                     ) -> typing.Tuple[int, int]:
    """``(executions, per-device bytes)`` of the attention kind under
    ``checkpoint`` over the whole step: the ``(out, lse)`` every layer
    OFFERS, each time it runs, whose flash call engages — on one device, under
    ``use_flash_attention``, at a sequence of whole 128-tiles: model/spatial.py
    ``_flash`` — and in which a query sees at least ``min_keys`` keys
    (parallel/flash_attention.py ``attention``'s "name" mode, the same
    test)."""
    if mesh is not None or not params.use_flash_attention \
            or params.sequence_dim.size % 128:
        return 0, 0
    saved = [offer.nbytes for region in region_offers(params, "attention")
             for offer in region if offer.keys >= min_keys]
    return len(saved), sum(saved) * max(1, params.macro_batching)


def _block_input_bytes(params: ModelParameter, shards: int) -> int:
    """Per-device bytes of the block inputs the ``checkpoint`` strategy
    itself keeps across the step's backward: one ``[batch, sequence,
    features]`` in the calculation dtype for every ``jax.checkpoint`` region
    of the step — each block of the body, each time it runs, and each block
    of a multi-token-prediction module — and, beside them, the carried side
    values the layers declare (``declare.carried_bytes``: a router state or
    an early router's logits is an operand of a region like its block input,
    and as alive).  (The input and output blocks run outside any region,
    model/__init__.py: they keep no block input, replay nothing and offer
    nothing.)"""
    one = params.batch_dim.size * params.sequence_dim.size \
        * int(np.prod([d.size for d in params.feature_dims])) \
        * np.dtype(params.calculation_dtype).itemsize
    return -(-(one * region_count(params) * max(1, params.macro_batching)
               + carried_bytes(params)) // shards)


def _regions(params: ModelParameter, kind: str, shards: int,
             interior: bool = False) -> typing.List[typing.Tuple[int, int]]:
    """``(layer executions, per-device bytes)`` of ``kind`` in every
    ``jax.checkpoint`` region of the step, in execution order (a looped
    model's passes outermost): what the region's block OFFERS — of its
    layers' first part, or of their ``interior`` (the layers that have one).
    A block's layers share their names, so a region's executions go
    together."""
    def part(offer):
        return offer.interior_nbytes if interior else offer.nbytes

    return [(sum(1 for offer in offered if part(offer)),
             -(-sum(part(offer) for offer in offered)
               * max(1, params.macro_batching) // shards))
            for offered in region_offers(params, kind)]


def _admit(params: ModelParameter, kind: str, shards: int,
           budget: typing.Optional[int]) -> typing.Tuple[int, int, int, int]:
    """``(layer executions, per-device bytes, first region, first region of
    the interior)`` of ``kind`` (``recurrent``, ``dense``) admitted into
    ``budget`` bytes (None: all of it, an explicit ``"stash"``; 0: none):
    region by region from the step's LAST backwards while the next fits — the
    last region's backward comes first, so its outputs are held for the
    shortest time, and through its own backward they stand where the replay
    would have put them anyway.  The offers' first part, then into what that
    leaves the interior of the executions that ride, likewise from the end.
    The regions from ``first region`` on save the part's names
    (:func:`region_names`).  A scanned body (``scan_layers``) traces ONE
    block for all its iterations: there a part's executions are admitted all
    together or not at all."""
    executions = admitted = stop = 0
    firsts = []
    for interior in (False, True):
        regions = _regions(params, kind, shards, interior)
        total = sum(nbytes for _, nbytes in regions)
        left = total if budget is None else budget - admitted
        if params.scan_layers and total > left:
            left = 0
        first, taken = len(regions), 0
        for region in reversed(range(stop, len(regions))):
            count, nbytes = regions[region]
            if taken + nbytes > left:
                break
            if count:
                first = region
            taken += nbytes
            executions += 0 if interior else count
        firsts.append(first)
        admitted, stop = admitted + taken, first
    return (executions, admitted, *firsts)


def _save_residual_bytes(params: ModelParameter) -> int:
    """Global estimate of the native-AD linearization residuals the save
    policy keeps: f32 activation-sized intermediates per block part,
    stacked over depth by scan AD."""
    seq = params.stream_length // max(1, params.token_patch_size)
    act = params.train_batch_size * seq * params.heads \
        * params.features_per_head * 4
    blocks = params.depth * max(1, len(params.block_config))
    return act * SAVE_RESIDUALS_PER_BLOCK * blocks \
        * max(1, params.macro_batching)


def remat_report(params: ModelParameter, mesh=None) -> typing.Dict[str, typing.Any]:
    """The analytic inputs to :func:`resolve_remat`, for docs and ops
    surfaces: per-device byte estimates, the HBM budget they gate on, and
    the roofline comparison between one block's recompute and its
    residual round-trip on the mesh's device."""
    from ..utils.flops import (device_hbm_bytes, peak_flops,
                               peak_hbm_bandwidth)
    shards, device = shardlib.shard_geometry(mesh)
    hbm = device_hbm_bytes(device)
    seq = params.stream_length // max(1, params.token_patch_size)
    tokens = params.train_batch_size * seq
    d_model = params.heads * params.features_per_head
    # one depth-unit's forward: ~4 d_model^2 GEMMs (the mixer shape) plus
    # ~12 activation-sized passes of elementwise/norm traffic
    calc_bytes = np.dtype(params.calculation_dtype).itemsize
    flops_block = 2 * tokens * d_model * d_model * 4
    bytes_block = tokens * d_model * calc_bytes * 12
    resid_block = tokens * d_model * 4 * SAVE_RESIDUALS_PER_BLOCK
    peak, bw = peak_flops(device), peak_hbm_bandwidth(device)
    layers, bottleneck_bytes, crosses = _bottleneck_stash(params, mesh)
    experts_layers, experts_bytes = _offered_stash(params, "experts",
                                                   shards)
    recurrent_layers, recurrent_bytes = _offered_stash(
        params, "recurrent", shards)
    saved_layers, saved_bytes = _saved_attention(params, mesh,
                                                 ATTENTION_MIN_KEYS)
    return {
        "stash_bytes_per_device": -(-_stash_bytes(params) // shards),
        "saved_attention_layers": saved_layers,
        "saved_attention_bytes_per_device": saved_bytes,
        "bottleneck_stash_layers": layers,
        "bottleneck_stash_bytes_per_device": bottleneck_bytes,
        "bottleneck_crosses_model_axis": crosses,
        "experts_stash_layers": experts_layers,
        "experts_stash_bytes_per_device": experts_bytes,
        "recurrent_stash_layers": recurrent_layers,
        "recurrent_stash_bytes_per_device": recurrent_bytes,
        "save_residual_bytes_per_device":
            -(-_save_residual_bytes(params) // shards),
        "hbm_bytes": hbm,
        "stash_budget_bytes": int(STASH_HBM_FRACTION * hbm),
        "save_budget_bytes": int(SAVE_HBM_FRACTION * hbm),
        "recompute_block_s": flops_block / peak + bytes_block / bw,
        "save_block_s": 2.0 * resid_block / bw,
        "seq": seq,
    }


def _explicit_policy(params: ModelParameter) -> typing.Optional[str]:
    """The policy the configuration itself names (``remat_policy``, else
    the legacy boolean's ``false``), or None where a rule has to decide."""
    v = getattr(params, "remat_policy", "auto")
    if v != "auto":
        return v
    if getattr(params, "stash_attention_outputs", "auto") is False:
        return "recompute"
    return None


def stash_kinds(params: ModelParameter, mesh=None) -> typing.FrozenSet[str]:
    """Which :data:`STASH_KINDS` the strategy keeps for its backward for
    this (config, mesh): all under an explicit ``"stash"``, none under any
    other explicit policy, else each kind by its own rule (the module
    docstring's item 3).  Under revnet / momentum the attention rule is the
    historical one and is decided FIRST: the bottleneck kind only gets what
    it leaves of the budget, so adding that kind moved no configuration's
    attention decision.  Under ``checkpoint`` each kind is judged on its own
    bytes against what the kinds before it TOOK — attention, experts,
    recurrent, dense — and one that passes it takes nothing and moves no
    other's decision; the last two an execution at a time (:func:`_decide`)."""
    return _decide(params, mesh)[0]


#: the kinds the ``checkpoint`` strategy admits an execution at a time
#: (:func:`_admit`), in the order they are decided
BY_EXECUTION = ("recurrent", "dense")


def _decide(params: ModelParameter, mesh
            ) -> typing.Tuple[typing.FrozenSet[str],
                              typing.Dict[str, typing.Tuple[int, ...]]]:
    """``(the kinds, {kind: (layer executions, per-device bytes, first
    region, first region of the interior)})`` — :func:`stash_kinds`, and how
    far each of the kinds that are admitted a part at a time got
    (:data:`BY_EXECUTION`, :func:`_admit`)."""
    shards, _ = shardlib.shard_geometry(mesh)

    def each(budget):
        return {kind: _admit(params, kind, shards, budget)
                for kind in BY_EXECUTION}

    explicit = _explicit_policy(params)
    if explicit is not None:
        stash = explicit == "stash"
        return frozenset(STASH_KINDS if stash else ()), each(
            None if stash else 0)
    rep = remat_report(params, mesh)
    budget = rep["stash_budget_bytes"]
    forced = _forced_attention(params)
    kinds = set()
    if forced or (rep["seq"] >= 2048 and rep["seq"] % 128 == 0
                  and rep["stash_bytes_per_device"] <= budget):
        kinds.add("attention")
        budget -= rep["stash_bytes_per_device"]
    if rep["bottleneck_crosses_model_axis"] \
            and 0 < rep["bottleneck_stash_bytes_per_device"] <= budget:
        kinds.add("bottleneck")
    if params.memory_reduction_strategy != "checkpoint":
        return frozenset(kinds), each(0)
    # the whole budget: what the two kinds above name rides the revnet /
    # momentum residuals, so under "checkpoint" they hold no byte of it, and
    # the attention kind is decided again by what each layer really saves
    kinds.discard("attention")
    budget = rep["stash_budget_bytes"]
    layers, nbytes = _saved_attention(params, mesh,
                                      _attention_min_keys(params))
    if layers and (forced or nbytes <= budget):
        kinds.add("attention")
        budget -= nbytes
    nbytes = rep["experts_stash_bytes_per_device"]
    if 0 < nbytes <= budget:
        kinds.add("experts")
        budget -= nbytes
    recurrent = _admit(params, "recurrent", shards, budget)
    # what is left of a bound on what the step holds ACROSS its backward: the
    # block inputs ``checkpoint`` itself keeps are such bytes too, charged to
    # the last kind alone
    dense = _admit(params, "dense", shards, budget - recurrent[1]
                   - _block_input_bytes(params, shards))
    parts = {"recurrent": recurrent, "dense": dense}
    kinds.update(kind for kind, part in parts.items() if part[0])
    return frozenset(kinds), parts


def _attention_sites(params: ModelParameter, mesh) -> int:
    """Attention layers a depth-unit holds whose kernel route consumes the
    channel: those that offer a flash call, through the one-device flash path
    or the sequence-parallel ring (model/spatial.py; the flash kernel under a
    data x model shard_map keeps the plain kernel)."""
    has_mesh = mesh is not None and getattr(mesh, "devices", None) is not None
    ring = has_mesh and mesh.shape.get(shardlib.SEQUENCE_AXIS, 1) > 1
    if not ring and (has_mesh or not params.use_flash_attention
                     or params.sequence_dim.size % 128):
        return 0
    return len(offers(params, "attention"))


def stash_plan(params: ModelParameter, mesh=None
               ) -> typing.Dict[str, typing.Tuple[int, int]]:
    """``{kind: (layer executions, per-device bytes)}`` of what the memory
    strategy of the step this (config, mesh) builds keeps for its backward,
    from its shapes — a layer counts once each time the step runs it, which
    is once everywhere but in a looped model (``loop_steps``) —; ``(0, 0)``
    for a kind that is not engaged (a strategy that has
    no way to keep it, a pipeline mesh, an explicit policy, a rule that
    declined, no such layer).  ``Trainer`` publishes it as
    ``hbnlp_remat_stash_bytes{kind}`` / ``hbnlp_remat_stash_layers{kind}``
    (docs/OBSERVABILITY.md); ``_checkpoint_policy`` saves the experts and the
    attention kind's names exactly where this says they ride
    (:func:`stash_names`), and the recurrent and the dense kind's in the
    regions of the executions it counts (:func:`region_names`)."""
    plan = {kind: (0, 0) for kind in STASH_KINDS}
    strategy = params.memory_reduction_strategy
    piped = mesh is not None and mesh.shape.get(shardlib.PIPE_AXIS, 1) > 1
    if strategy not in ("revnet", "momentum", "checkpoint") or piped:
        return plan
    kinds, parts = _decide(params, mesh)
    rep = remat_report(params, mesh)
    if strategy == "checkpoint":
        if "experts" in kinds and rep["experts_stash_layers"]:
            plan["experts"] = (rep["experts_stash_layers"],
                               rep["experts_stash_bytes_per_device"])
        if "attention" in kinds:
            plan["attention"] = _saved_attention(
                params, mesh, _attention_min_keys(params))
        plan.update({kind: part[:2] for kind, part in parts.items()})
        return plan
    if "attention" in kinds:
        layers = _attention_sites(params, mesh) * params.depth
        # remat_report sizes one pair a depth-unit
        plan["attention"] = (layers, rep["stash_bytes_per_device"]
                             * layers // params.depth)
    if "bottleneck" in kinds and rep["bottleneck_stash_layers"]:
        plan["bottleneck"] = (rep["bottleneck_stash_layers"],
                              rep["bottleneck_stash_bytes_per_device"])
    return plan


def saved_attention_keys(params: ModelParameter, mesh=None
                         ) -> typing.Optional[int]:
    """Where the attention kind rides the ``checkpoint`` strategy's
    ``jax.checkpoint`` (:func:`stash_plan`): the fewest keys a query of a
    flash call sees where the call names its ``(out, lse)`` — what the
    blocks' "name" channel carries (model/blocks.py ``_name_chan``;
    :data:`ATTENTION_MIN_KEYS`, 0 where the configuration names the kind
    itself).  None where it does not ride."""
    if params.memory_reduction_strategy != "checkpoint" \
            or not stash_plan(params, mesh)["attention"][0]:
        return None
    return _attention_min_keys(params)


def _names(params: ModelParameter, kind: str, interior: bool = False
           ) -> typing.Tuple[str, ...]:
    """The ``checkpoint_name``s the step's layers declare for ``kind`` (or
    for its ``interior``), each once, in execution order."""
    return tuple(dict.fromkeys(
        name for region in region_offers(params, kind) for offer in region
        for name in (offer.interior_names if interior else offer.names)))


def stash_names(params: ModelParameter, mesh=None) -> typing.Tuple[str, ...]:
    """The ``checkpoint_name``s the ``checkpoint`` strategy's
    ``jax.checkpoint`` saves beside its named policy in a region that holds
    an admitted execution of the recurrent kind, interior and all (every
    region, where all its executions are admitted): those the layers declare
    for every kind :func:`stash_plan` says rides it — experts, then
    recurrent, in execution order, then attention.  Empty where none does."""
    plan = stash_plan(params, mesh)
    names = _names(params, "experts") if plan["experts"][0] else ()
    if plan["recurrent"][0]:
        inside = _decide(params, mesh)[1]["recurrent"][3] \
            < region_count(params)
        names += _names(params, "recurrent") \
            + (_names(params, "recurrent", True) if inside else ())
    if saved_attention_keys(params, mesh) is not None:
        names += _names(params, "attention")
    return tuple(dict.fromkeys(names))     # a name once, in order


def dense_executions(params: ModelParameter, mesh=None) -> int:
    """How many ``mlp`` executions of the step save their gate and up outputs
    (:func:`stash_plan`'s ``dense``): the LAST so many of the body, a looped
    model's passes outermost."""
    return stash_plan(params, mesh)["dense"][0]


def region_names(params: ModelParameter, mesh=None
                 ) -> typing.List[typing.Tuple[str, ...]]:
    """The names the ``jax.checkpoint`` of every region of the step saves, a
    tuple a region (the body's blocks in execution order, a looped model's
    passes outermost; a scanned body's one traced block stands for all its
    iterations): :func:`stash_names` — the recurrent kind's from the first
    region that holds an admitted execution of it on, its interior's from
    theirs — and the dense kind's where the region holds one of its own."""
    names = stash_names(params, mesh)
    plan, parts = stash_plan(params, mesh), _decide(params, mesh)[1]
    regions = _regions(params, "dense", shardlib.shard_geometry(mesh)[0])
    # (the plan's count is 0 where the strategy has no region to ride)
    first, inside = parts["recurrent"][2:] if plan["recurrent"][0] \
        else (len(regions),) * 2
    dense_first = parts["dense"][2] if plan["dense"][0] else len(regions)
    outer, inner = (_names(params, "recurrent", interior)
                    for interior in (False, True))
    dense = _names(params, "dense")
    return [tuple(dict.fromkeys(
        (*(name for name in names
           if (name not in outer or region >= first)
           and (name not in inner or region >= inside)),
         *(dense if region >= dense_first and count else ()))))
        for region, (count, _) in enumerate(regions)]


def stash_line(plan: typing.Dict[str, typing.Tuple[int, int]],
               looped: bool = False) -> str:
    """The start-up line beside ``placement_report``'s; ``looped``: a layer
    runs more than once a step, and the counts are its executions."""
    unit = "executions" if looped else "layers"
    return "remat stash: " + "; ".join(
        f"{kind} {count} {unit}, {nbytes} bytes a device"
        for kind, (count, nbytes) in plan.items())


def resolve_remat(params: ModelParameter, mesh=None) -> str:
    """The resolved remat policy for this (config, mesh) — see the module
    docstring for the decision order; ``"stash"`` where any kind rides
    (:func:`stash_kinds` says which)."""
    explicit = _explicit_policy(params)
    if explicit is not None:
        return explicit
    if stash_kinds(params, mesh):
        return "stash"
    # the save modes stay MEASURED opt-ins: the round-11 A/B on the
    # flagship step measured recompute 204 / save 280 / save_dots 249
    # ms/step (the residual round-trip loses on an hbm-bound rig, which is
    # what the committed cost ledger classifies every body scope as), and
    # the nominal roofline constants are not trustworthy enough to flip a
    # default against a measurement — remat_report carries the analytic
    # comparison for whoever measures a compute-bound chip with spare HBM
    return "recompute"


def block_caller(policy: str):
    """How the save-mode recurrences invoke a block: plain for ``save``,
    ``jax.checkpoint(policy=dots_saveable)`` for ``save_dots`` — GEMM
    outputs saved, elementwise recomputed."""
    import jax

    if policy == "save_dots":
        def call(f, subset, x, it=None):
            return jax.checkpoint(
                lambda s_, x_, it_: f(s_, x_, it=it_) if it_ is not None
                else f(s_, x_),
                policy=jax.checkpoint_policies.dots_saveable)(subset, x, it)
        return call

    def call(f, subset, x, it=None):
        return f(subset, x, it=it) if it is not None else f(subset, x)
    return call
