"""Measured remat policy for the memory strategies' backward (PR 11).

Replaces the boolean/``auto`` ``stash_attention_outputs`` tri-state with a
POLICY layer: what the memory-strategy backward does about
re-materializing block interiors is now one resolved decision
(:func:`resolve_remat`) consumed by ``model/blocks.py``:

==============  =============================================================
policy          behavior
==============  =============================================================
``recompute``   the strategy ``custom_vjp`` re-runs each block's forward
                inside ``jax.vjp`` — O(1) activation memory in depth, one
                extra forward of compute (the historical default)
``stash``       recompute, but what is dear to replay per byte rides the
                strategy residuals (the stash channel, model/blocks.py),
                BOTH kinds: ``attention`` — every flash/ring attention
                layer's ``(out, lse)``, so the backward replay runs no
                forward attention kernels (and no ring hops; the old
                ``stash_attention_outputs: true``) — and ``bottleneck`` —
                ``bottleneck_group_linear``'s in-projection output
                ``[b, s, intermediate]``, so the replay runs neither that
                matmul nor, where it contracts a mesh-sharded axis, its
                tensor-parallel all-reduce (PR 27).  Under the
                ``checkpoint`` strategy there is no channel of residuals:
                what rides is SAVED by each block's ``jax.checkpoint``
                through a policy over named values
                (``_checkpoint_policy``).  The third kind, ``experts`` —
                layer ``moe``'s gate, up and down outputs and its routing
                triple — rides it so: the replay runs none of the three
                forward grouped matmuls, 3 of a layer's 12 (PR 29).
                The fourth, ``recurrent``, rides the same policy: the
                OUTPUT a recurrent mixer offers because it re-materialises
                its own interior (``model/recurrent.py`` ``Recurrent``:
                layer ``gated_delta``'s rule, a group of heads at a time) —
                the replay then runs no forward of the recurrence, two
                forwards of the rule a step instead of three (PR 33).
                ``attention`` rides it too (PR 40): the blocks get a
                stateless "name" channel (``model/blocks.py``
                ``_name_chan``) under which a flash call computes ``(out,
                lse)`` once, names both (``parallel/flash_attention.py``
                ``SAVED_NAMES``) and returns ``flash_precomputed`` on
                them; the replay finds both outputs of the forward kernel
                saved, so the call is dead code there and the backward is
                the flash-2 pass on the replayed ``q, k, v`` and the
                forward's own ``(out, lse)``: one ``flash_fwd_*`` call a
                layer a step instead of two
``save``        NO ``custom_vjp``: the identical primal recurrence under
                native scan AD; every linearization residual is saved —
                zero recompute, O(depth) residual memory
``save_dots``   ``save`` with each block wrapped in ``jax.checkpoint``
                (policy ``dots_saveable``): GEMM outputs saved, elementwise
                recomputed — the middle ground for compute-bound chips
``auto``        resolved below, per kind
==============  =============================================================

All four execute the SAME primal recurrence — losses are bit-identical
and gradients agree to reconstruction ulps (tests/remat_policy_test.py).

**What auto does, and why (measured — docs/PERFORMANCE.md 'Round 11').**
The profile-guided A/B on the flagship step measured ``recompute`` 204
ms/step vs ``save`` 280 vs ``save_dots`` 249 on the CPU rig: the rig is
memory-bound, so writing + re-reading the stacked per-depth residuals
costs MORE than re-running the forward — and the committed cost ledger
classifies every body scope hbm-bound there, which is exactly the
classification this resolver keys on.  ``auto`` therefore picks:

1. the explicit ``remat_policy`` value when set;
2. the legacy ``stash_attention_outputs`` boolean when the user set one
   (``true`` → ``stash``, ``false`` → ``recompute``);
3. ``stash`` when a kind's own rule engages (:func:`stash_kinds`).
   Under revnet / momentum (the channel's kinds): ``attention`` when the
   long-context rule pays and fits (seq >= 2048, % 128 == 0, per-device
   stash <= 15% of HBM — the measured +23% at 16k);
   ``bottleneck`` when the in-projection's contraction crosses a ``model``
   mesh axis > 1 (each chip then holds a partial sum and the replay would
   all-reduce it a second time — one of three exposed collectives a layer
   on the {data: 2, model: 2} flagship, PERF.md PR 27) and its per-device
   bytes fit what the attention stash leaves of the same 15%.
   Under ``checkpoint`` (the policy's kinds; the two above ride the revnet
   / momentum residuals and take nothing of the 15% there), in this order:
   ``experts`` when the model has a ``moe`` layer and the saved outputs of
   the WHOLE depth — ``pairs x (2 x intermediate + features) x itemsize``
   a layer, ``pairs = tokens x top-k``, plus the routing triple — fit the
   15%.  All layers or none: OLMoE-1B-7B at depth 2 on 8,192 tokens is
   1.07 GB of ~2.5 and rides, at its published depth 16 it is 8.6 GB and
   the rule declines; saving some layers only is a later issue.
   ``recurrent`` is decided AFTER it, from what ``experts``
   leaves of that 15%: at least one layer that
   DECLARES an output to save (``Recurrent.saved_names`` / ``saved_bytes``;
   the resolver tests no layer's and no model's name) and the whole
   depth's declared bytes within what is left — all layers or none.
   Olmo-Hybrid-7B's period of four layers on 16,384 tokens is 3 x 189 MB =
   566 MB of ~2.5 GB and rides; layer ``mamba`` declares nothing (its scan
   has no inner ``jax.checkpoint``: the replay's forward is the pass that
   makes its backward's residuals).  ``attention`` is decided LAST there
   (PR 40), from what both leave, so it moves neither: the ``(out, lse)``
   of every layer whose flash call engages (the call a layer DECLARES,
   ``<layer function>.flash``: its OWN query heads and window — layer
   ``cca``'s 8 in a 16-head stream, 72 beside 48 in one model — on one
   device, under ``use_flash_attention``, at a sequence of whole
   128-tiles) and in which a query sees at least 2,048 keys,
   ``min(sequence, window)`` (:data:`ATTENTION_MIN_KEYS`: the historical
   ``seq >= 2048`` read a layer), all such layers or none — and it
   declines where an earlier kind had bytes to save and declined for size:
   a step whose expert buffers alone pass the budget regenerates them live
   inside each block's backward and has no room to hold more across
   blocks.  The legacy boolean ``true`` forces
   the attention kind only (its name; the other kinds still resolve by
   their rules), ``false`` is "recompute";
4. else ``recompute``.  The save modes stay measured OPT-INS: the A/B
   lost on the rig, the committed ledger classifies every body scope
   hbm-bound (residual round-trips are the expensive direction there),
   and a nominal roofline constant is not evidence enough to flip a
   default against a measurement.

:func:`remat_report` returns the analytic numbers behind the decision
(stash bytes, residual estimate, HBM budget, per-block recompute vs
residual-traffic seconds on the mesh's device roofline) for docs/ops.
"""
from __future__ import annotations

import typing

import numpy as np

from ..config import ModelParameter

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
#: ``recurrent`` and, there, ``attention`` too the ``checkpoint`` strategy's
#: ``jax.checkpoint`` (``_checkpoint_policy``)
STASH_KINDS = ("attention", "bottleneck", "experts", "recurrent")


def _mesh_geometry(params: ModelParameter, mesh):
    """(per-device shard divisor, device) for capacity estimates — the
    stash/residual arrays shard over every data/model/sequence axis."""
    shards = 1
    device = None
    if mesh is not None and getattr(mesh, "devices", None) is not None:
        for axis in ("data", "model", "sequence"):
            shards *= mesh.shape.get(axis, 1)
        device = np.asarray(mesh.devices).flat[0]
    return shards, device


def _stash_bytes(params: ModelParameter) -> int:
    """Global attention-stash estimate: one (out [b,s,h,d], lse [b,h,s])
    pair per block, sized as if every block held one attention layer."""
    seq = params.sequence_length // max(1, params.token_patch_size)
    calc_bytes = np.dtype(params.calculation_dtype).itemsize
    per_layer = (params.train_batch_size * seq * params.heads
                 * params.features_per_head * calc_bytes
                 + params.train_batch_size * params.heads * seq * 4)
    return per_layer * params.depth * max(1, params.macro_batching)


def _layers(params: ModelParameter):
    """``(name, {flags})`` of every layer of one depth-unit."""
    for block in params.block_config:
        for layer in block.layer:
            name, *extras = layer.split("-")
            yield name, set(extras)


def _bottleneck_sites(params: ModelParameter) -> int:
    """In-projection outputs one depth-unit pushes: every ``in:`` linear of
    every ``bottleneck_group_linear`` layer (one, plus the glu branches;
    an expert in-projection is not a plain linear and is left alone)."""
    sites = 0
    for name, extras in _layers(params):
        if name == "bottleneck_group_linear" \
                and "in:mixture_of_experts" not in extras:
            glu_add = "in:glu_add" in extras
            sites += 1 + ("in:glu" in extras or glu_add) + glu_add
    return sites


def _bottleneck_stash(params: ModelParameter, mesh) -> typing.Tuple[int, int, bool]:
    """``(layers, per-device bytes, crosses)`` of the bottleneck stash: the
    in-projection outputs ``[batch, sequence, intermediate]`` of the whole
    depth, laid out as ``core/sharding.with_constraint`` pins them (batch
    on 'data', replicated over 'model'), and whether the contraction (over
    the feature dims) crosses a 'model' mesh axis > 1 — each chip then
    holds a partial sum that costs an all-reduce wherever it is made."""
    from ..core import sharding as shardlib
    layers = _bottleneck_sites(params) * params.depth
    out_dims = [params.batch_dim, params.sequence_dim, *params.intermediate]
    nbytes = (int(np.prod([d.size for d in out_dims]))
              * np.dtype(params.calculation_dtype).itemsize * layers
              * max(1, params.macro_batching))
    crosses = False
    if mesh is not None and getattr(mesh, "devices", None) is not None:
        for axis in shardlib.spec_for_dims(params, out_dims, mesh):
            nbytes //= mesh.shape[axis] if axis is not None else 1
        crosses = mesh.shape.get(shardlib.MODEL_AXIS, 1) > 1 \
            and shardlib.MODEL_AXIS in shardlib.spec_for_dims(
                params, list(params.feature_dims), mesh)
    return layers, nbytes, crosses


def _experts_stash(params: ModelParameter, shards: int
                   ) -> typing.Tuple[int, int]:
    """``(layers, per-device bytes)`` of the experts kind over the whole
    depth: per ``moe`` layer the three grouped matmuls' outputs — gate and
    up ``[pairs, intermediate]``, down ``[pairs, features]``, in the
    calculation dtype — the routing triple (``order`` and ``inverse``
    ``[pairs]``, ``sizes`` ``[experts]``, int32) and the router's choice
    (``experts`` ``[tokens, moe_top_k]``, int32), ``pairs = tokens x
    min(moe_top_k, experts)``: model/moe.py ``SAVED_NAMES``.  A layer that
    holds a share of the experts saves its whole static buffer:
    ``moe_held_rows`` rows, ``experts_held + 1`` sizes."""
    layers = sum(name == "moe" for name, _ in _layers(params)) * params.depth
    held_rows = moe_held_rows(params)
    choices = params.batch_dim.size * params.sequence_dim.size \
        * min(params.moe_top_k, params.expert_dim.size)
    pairs = held_rows or choices
    width = 2 * int(np.prod([d.size for d in params.expert_intermediate])) \
        + int(np.prod([d.size for d in params.feature_dims]))
    groups = params.experts_held + 1 if held_rows else params.expert_dim.size
    per_layer = pairs * width * np.dtype(params.calculation_dtype).itemsize \
        + (2 * pairs + groups + choices) * 4
    return layers, -(-per_layer * layers * max(1, params.macro_batching)
                     // shards)


def moe_held_rows(params: ModelParameter) -> int:
    """Rows of the static dispatch buffer of a ``moe`` layer that holds a
    share of the experts (model/moe.py ``held_rows_bound``) for one micro
    batch; 0 where no layer holds a share."""
    if not 0 < params.experts_held < params.expert_dim.size \
            or not any(name == "moe" for name, _ in _layers(params)):
        return 0
    from .moe import held_rows_bound
    return held_rows_bound(
        params.batch_dim.size * params.sequence_dim.size,
        min(params.moe_top_k, params.expert_dim.size), params.experts_held)


def router_carry_bytes(params: ModelParameter) -> int:
    """Bytes of the router states alive between blocks for the backward: one
    float32 ``[batch, sequence, moe_router_width]`` for every ``moe`` layer
    with flag ``router_mlp`` that hands its state to a later one (all but
    the last; model/moe.py).  It passes the blocks in between unchanged, so
    it is held once however many regions it crosses.  0 where no layer
    carries one."""
    carrying = sum(name == "moe" and "router_mlp" in extras
                   for name, extras in _layers(params)) * params.depth
    return max(0, carrying - 1) * params.batch_dim.size \
        * params.sequence_dim.size * params.moe_router_width * 4 \
        * max(1, params.macro_batching)


def _recurrent_stash(params: ModelParameter, shards: int
                     ) -> typing.Tuple[int, int]:
    """``(layers, per-device bytes)`` of the recurrent kind over the whole
    depth: what every recurrent mixer that offers its output DECLARES
    (``Recurrent.saved_bytes``, for the whole batch)."""
    offers = [spec.saved_bytes(params) for spec in _recurrent_layers(params)
              if spec.saved_names]
    return len(offers) * params.depth, -(
        -sum(offers) * params.depth * max(1, params.macro_batching) // shards)


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


def _flash_call(params: ModelParameter, name: str, extras: set):
    """``(query heads, window or None)`` of the flash call a layer DECLARES
    (``<layer function>.flash``), or None where it declares none."""
    from .frontend import LAYER_FUNCTIONS
    declare = getattr(LAYER_FUNCTIONS.get(name), "flash", None)
    return declare(params, extras) if declare is not None else None


def _saved_attention(params: ModelParameter, mesh, min_keys: int
                     ) -> typing.Tuple[int, int]:
    """``(layers, per-device bytes)`` of the attention kind under
    ``checkpoint`` over the whole depth: ``out`` ``[batch, sequence, heads,
    features_per_head]`` in the calculation dtype and ``lse`` ``[batch x
    heads, sequence]`` float32 of every layer whose flash call engages — the
    call a layer DECLARES (``<layer function>.flash``: its OWN query heads
    and window), on one device, under ``use_flash_attention``, at a sequence
    of whole 128-tiles: model/spatial.py ``_flash`` — and in which a query
    sees at least ``min_keys`` keys (parallel/flash_attention.py
    ``attention``'s "name" mode, the same test)."""
    seq = params.sequence_dim.size
    if mesh is not None or not params.use_flash_attention or seq % 128:
        return 0, 0
    heads = []
    for name, extras in _layers(params):
        call = _flash_call(params, name, extras)
        if call is not None and min(seq, call[1] or seq) >= min_keys:
            heads.append(call[0])
    per_head = params.batch_dim.size * seq * (
        params.key_dim.size * np.dtype(params.calculation_dtype).itemsize + 4)
    return len(heads) * params.depth, sum(heads) * per_head * params.depth \
        * max(1, params.macro_batching)


def _save_residual_bytes(params: ModelParameter) -> int:
    """Global estimate of the native-AD linearization residuals the save
    policy keeps: f32 activation-sized intermediates per block part,
    stacked over depth by scan AD."""
    seq = params.sequence_length // max(1, params.token_patch_size)
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
    shards, device = _mesh_geometry(params, mesh)
    hbm = device_hbm_bytes(device)
    seq = params.sequence_length // max(1, params.token_patch_size)
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
    experts_layers, experts_bytes = _experts_stash(params, shards)
    recurrent_layers, recurrent_bytes = _recurrent_stash(params, shards)
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
    attention decision.  Under ``checkpoint`` the experts kind is decided
    first, the recurrent kind from what it leaves, and the attention kind
    LAST, from what both leave — so it moves neither — and not at all where
    one of them had bytes to save and declined for size."""
    explicit = _explicit_policy(params)
    if explicit is not None:
        return frozenset(STASH_KINDS if explicit == "stash" else ())
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
        return frozenset(kinds)
    # the whole budget: what the two kinds above name rides the revnet /
    # momentum residuals, so under "checkpoint" they hold no byte of it, and
    # the attention kind is decided again by what each layer really saves
    kinds.discard("attention")
    budget = rep["stash_budget_bytes"]
    fitted = True
    for kind in ("experts", "recurrent"):
        nbytes = rep[f"{kind}_stash_bytes_per_device"]
        if 0 < nbytes <= budget:
            kinds.add(kind)
            budget -= nbytes
        elif nbytes:
            # a step whose expert buffers alone pass the budget regenerates
            # them live inside each block's backward: no room to hold more
            fitted = False
    layers, nbytes = _saved_attention(params, mesh,
                                      _attention_min_keys(params))
    if layers and (forced or (fitted and nbytes <= budget)):
        kinds.add("attention")
    return frozenset(kinds)


def _attention_sites(params: ModelParameter, mesh) -> int:
    """Attention layers a depth-unit holds whose kernel route consumes the
    channel: plain softmax dot-product attention through the one-device
    flash path or the sequence-parallel ring (model/spatial.py; the flash
    kernel under a data x model shard_map keeps the plain kernel)."""
    from ..core.sharding import SEQUENCE_AXIS
    has_mesh = mesh is not None and getattr(mesh, "devices", None) is not None
    ring = has_mesh and mesh.shape.get(SEQUENCE_AXIS, 1) > 1
    if not ring and (has_mesh or not params.use_flash_attention
                     or params.sequence_dim.size % 128):
        return 0
    from .spatial import _DENSE_ONLY
    return sum(name == "attention" and "dot_product" in extras
               and not extras.intersection(_DENSE_ONLY)
               for name, extras in _layers(params))


def stash_plan(params: ModelParameter, mesh=None
               ) -> typing.Dict[str, typing.Tuple[int, int]]:
    """``{kind: (layers, per-device bytes)}`` of what the memory strategy
    of the step this (config, mesh) builds keeps for its backward, from its
    shapes; ``(0, 0)`` for a kind that is not engaged (a strategy that has
    no way to keep it, a pipeline mesh, an explicit policy, a rule that
    declined, no such layer).  ``Trainer`` publishes it as
    ``hbnlp_remat_stash_bytes{kind}`` / ``hbnlp_remat_stash_layers{kind}``
    (docs/OBSERVABILITY.md); ``_checkpoint_policy`` saves the experts, the
    recurrent and the attention kind's names exactly where this says they
    ride (:func:`stash_names`)."""
    from ..core.sharding import PIPE_AXIS
    plan = {kind: (0, 0) for kind in STASH_KINDS}
    strategy = params.memory_reduction_strategy
    piped = mesh is not None and mesh.shape.get(PIPE_AXIS, 1) > 1
    if strategy not in ("revnet", "momentum", "checkpoint") or piped:
        return plan
    kinds = stash_kinds(params, mesh)
    rep = remat_report(params, mesh)
    if strategy == "checkpoint":
        for kind in ("experts", "recurrent"):
            if kind in kinds and rep[f"{kind}_stash_layers"]:
                plan[kind] = (rep[f"{kind}_stash_layers"],
                              rep[f"{kind}_stash_bytes_per_device"])
        if "attention" in kinds:
            plan["attention"] = _saved_attention(
                params, mesh, _attention_min_keys(params))
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


def _recurrent_layers(params: ModelParameter):
    """What each recurrent mixer of one depth unit declares of itself
    (``model/recurrent.py`` ``Recurrent``, set on the layer's function), in
    execution order."""
    from .frontend import LAYER_FUNCTIONS
    found = (getattr(LAYER_FUNCTIONS.get(name), "recurrent", None)
             for name, _ in _layers(params))
    return [spec for spec in found if spec is not None]


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


def stash_names(params: ModelParameter, mesh=None) -> typing.Tuple[str, ...]:
    """The ``checkpoint_name``s the ``checkpoint`` strategy's
    ``jax.checkpoint`` saves beside its named policy: those of every kind
    :func:`stash_plan` says rides it — layer ``moe``'s (model/moe.py
    ``SAVED_NAMES``), then what the recurrent mixers declare, in execution
    order, then the flash layers' (parallel/flash_attention.py
    ``SAVED_NAMES``).  Empty where none does."""
    plan = stash_plan(params, mesh)
    names = []
    if plan["experts"][0]:
        from .moe import SAVED_NAMES
        names += SAVED_NAMES
    if plan["recurrent"][0]:
        names += [name for spec in _recurrent_layers(params)
                  for name in spec.saved_names]
    if saved_attention_keys(params, mesh) is not None:
        from ..parallel.flash_attention import SAVED_NAMES
        names += SAVED_NAMES
    return tuple(dict.fromkeys(names))     # a name once, in order


def ssd_state_bytes(params: ModelParameter, mesh=None) -> int:
    """Per-device bytes of the recurrent mixers' chunk states — what a layer
    declares (``mamba``: ``[batch, sequence / mamba_chunk, mamba_heads,
    mamba_head_features, mamba_state]`` float32, ``gated_delta``: ``[batch,
    sequence / delta_chunk, delta_heads, delta_value_features,
    delta_key_features]`` in the calculation dtype), what the inter-chunk
    scan's backward reads — that are alive at once for the backward: ONE
    layer's (the largest) under ``checkpoint`` / ``revnet`` / ``momentum``
    (no policy saves them across the forward; the block's replay makes them
    again and drops them with the block), every layer's under ``none``.  0 without such a layer.
    ``Trainer`` publishes it as ``hbnlp_ssd_state_bytes``."""
    sizes = [spec.state_bytes(params) for spec in _recurrent_layers(params)]
    if not sizes:
        return 0
    shards, _ = _mesh_geometry(params, mesh)
    alive = sum(sizes) * params.depth \
        if params.memory_reduction_strategy == "none" else max(sizes)
    return -(-alive // shards)


def conv_kernel_layers(params: ModelParameter, backend=None) -> int:
    """How many recurrent mixers of the step (``mamba``, ``gated_delta``)
    take the Pallas conv kernel pair (``parallel/causal_conv.py``), by the
    predicate the layers themselves call on the conv each declares.
    ``Trainer`` publishes it as ``hbnlp_mamba_conv_kernel_layers``."""
    from ..parallel.causal_conv import kernel_applies
    layers = 0
    for spec in _recurrent_layers(params):
        channels, taps, offset = spec.conv(params)
        layers += kernel_applies(channels, params.sequence_dim.size, taps,
                                 offset, backend)
    return layers * params.depth


def solve_kernel_layers(params: ModelParameter, backend=None
                        ) -> typing.Optional[int]:
    """How many recurrent mixers of the step take the Pallas pair for their
    triangular solve (``parallel/delta_solve.py``), by the predicate the
    layer itself calls on the systems it declares; None where no layer
    declares a solve.  ``Trainer`` publishes it as
    ``hbnlp_delta_solve_kernel_layers``."""
    from ..parallel.delta_solve import solve_kernel_applies
    solves = [spec.solve(params) for spec in _recurrent_layers(params)
              if spec.solve is not None]
    if not solves:
        return None
    return params.depth * sum(solve_kernel_applies(chunk, matrices, backend)
                              for chunk, matrices in solves)


def flash_band_layers(params: ModelParameter, backend=None
                      ) -> typing.Optional[int]:
    """How many attention layers of the step run their windowed flash
    FORWARD as the band kernel (``parallel/flash_attention.py _fwd_band``):
    of the layers that DECLARE a flash call with a window shorter than the
    sequence (``<layer function>.flash``, as ``_saved_attention`` reads it;
    the leading and trailing blocks run once, the body ``depth`` times),
    those the predicate ``attention`` itself calls admits, where that call
    reaches the kernels at all (``use_flash_attention``, off the CPU, a
    sequence of whole 128-tiles); None where no layer declares such a
    window.  ``Trainer`` publishes it as ``hbnlp_flash_band_layers``."""
    import jax

    from ..parallel.flash_attention import band_applies
    seq = params.sequence_dim.size
    windows = []
    for blocks, times in ((params.input_block_config, 1),
                          (params.block_config, params.depth),
                          (params.output_block_config, 1)):
        for block in blocks:
            for layer in block.layer:
                name, *extras = layer.split("-")
                call = _flash_call(params, name, set(extras))
                if call is not None and call[1] is not None and call[1] < seq:
                    windows += [call[1]] * times
    if not windows:
        return None
    if backend is None:
        backend = jax.default_backend()
    if backend == "cpu" or not params.use_flash_attention or seq % 128:
        return 0
    itemsize = np.dtype(params.calculation_dtype).itemsize
    return sum(band_applies(seq, params.key_dim.size, window, itemsize)
               for window in windows)


def stash_line(plan: typing.Dict[str, typing.Tuple[int, int]]) -> str:
    """The start-up line beside ``placement_report``'s."""
    return "remat stash: " + "; ".join(
        f"{kind} {layers} layers, {nbytes} bytes a device"
        for kind, (layers, nbytes) in plan.items())


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
