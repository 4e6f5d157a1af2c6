"""Mesh-aware graft-lint: sharding contracts, per-mesh collective budgets,
and HBM liveness audits for every parallel strategy
(docs/STATIC_ANALYSIS.md 'Mesh audit').

The single-device HLO audit (``entry_points.py`` + ``hlo_lint.py``) pins
"zero collectives" — the one regression it CANNOT catch is the one that
matters at pod scale: an accidental resharding or full-gather under a real
parallel strategy, exactly what the Mesh-TF layout claim (PAPERS.md
1811.02084) and the pjit-TPUv4 scaling analysis (2204.06514) attribute
most lost scaling to.  This module lowers the registered entry points
under each ``scripts/pod_lowering.py`` / dryrun strategy on 8 virtual CPU
devices and audits the compiled per-mesh HLO against three contracts:

1. **collective budgets** — measured count AND result-bytes per collective
   kind, committed under the ``meshes`` section of ``budgets.json``
   (tolerance-checked like ``cost_ledger.json``; regenerated via
   ``python -m homebrewnlp_tpu.analysis.mesh_audit --write``).  Replica
   groups are mapped back to mesh coordinates, so a failure NAMES the mesh
   axis the surplus collective reshards over.  An analytic floor per
   strategy (mesh shape x model dims: grad reduction bytes over 'data',
   ring hops over 'sequence', tp partials over 'model') gates ``--write``
   so a degenerate baseline (strategy silently not parallel, or already
   resharded) cannot be committed as the budget.
2. **sharding specs** — protected param / activation-input / KV-cache
   leaves must appear in the compiled module's ENTRY parameters at their
   strategy-contracted shard shapes (the contract is declared HERE, per
   strategy, independent of ``config.layout`` — a broken layout rule fails
   the audit instead of silently replicating).  Silent full replication
   and compiler-inserted all-gathers of model-parallel leaves are findings.
3. **HBM liveness** — per entry x mesh, a buffer-level walk of the
   compiled text (donated arguments stay live; temporaries alloc at
   definition, free at last use; called computations contribute their own
   internal peak at the call site) yields a per-chip peak-bytes estimate,
   budget-checked against the committed value AND the target chip's HBM —
   an OOM-at-32-chips regression fails CI on this CPU-only box.

Environment gaps are classified, not papered over: on jax 0.9.0 XLA:CPU
ABORTS the process compiling the three pipeline strategies at audit scale
(``hlo_instruction.cc] Invalid binary instruction opcode copy`` — a C++
CHECK no ``except`` can catch), so each pipeline strategy is lowered in a
throwaway subprocess first (:func:`_lowering_abort`); a strategy that
kills its probe carries a ``pending`` budget row and is skipped LOUDLY
until an environment that lowers it regenerates its budgets.

jax is imported inside functions only (package convention — the AST-only
consumers must import cheaply).
"""
from __future__ import annotations

import dataclasses
import json
import re
import typing

import numpy as np

from . import entry_points, hlo_lint
from .hlo_lint import Finding

#: every mesh strategy lowers on this many virtual CPU devices — the same
#: count tests/conftest.py forces, and enough for 3-axis meshes
MESH_DEVICES = 8

#: relative drift in committed counts / bytes the audit tolerates
DEFAULT_TOLERANCE = 0.10

#: what :func:`_lowering_abort` reports for a strategy whose lowering
#: killed its probe subprocess
_ABORT_MARKER = "XLA aborted the lowering process"

#: substrings identifying a lowering failure as an ENVIRONMENT gap (the
#: strategy is skipped with a notice) rather than a repo regression.
#: Deliberately NARROW: the partial-manual axis_index gap of old XLA and a
#: compiler abort of the probe subprocess — any python-level error around
#: shard_map is a repo bug that must FAIL, not skip
_ENV_GAP_MARKERS = (
    "PartitionId instruction is not supported",
    _ABORT_MARKER,
)


@dataclasses.dataclass(frozen=True)
class MeshStrategy:
    """One parallel strategy the audit lowers and budgets.

    ``overrides``: audit-config overrides (mesh_shape_override and the
    blocks that exercise the strategy), mirroring the dryrun legs
    (``__graft_entry__.dryrun_multichip``) at ``AUDIT_CONFIG`` scale.
    ``entries``: which registered entry points lower under it (train
    everywhere; decode/engine only where serving runs the strategy).
    ``sharded_dims``: the sharding CONTRACT — named model dims that must
    shard over the given mesh axis (declared here, independent of the
    config's layout rules, so a layout regression is caught).
    ``collective_axes``: mesh axes collectives may legitimately span;
    a censused group over any other axis refuses ``--write``.
    ``hbm_device``: chip whose HBM bounds the liveness estimate.
    """
    name: str
    overrides: typing.Mapping[str, typing.Any]
    entries: typing.Tuple[str, ...] = ("train_step",)
    sharded_dims: typing.Mapping[str, str] = dataclasses.field(
        default_factory=dict)
    collective_axes: typing.FrozenSet[str] = frozenset()
    hbm_device: str = "TPU v5e"
    description: str = ""


_RING_BLOCKS = [{"layer": ["norm-shift-scale-features-group",
                           "attention-dot_product-context"]}]
_MOE_BLOCKS = [{"layer": ["norm-shift-scale-features-group",
                          "feed_forward-in:relu-in:mixture_of_experts"
                          "-in:routed"]}]

#: the registry: keys are budgets.json ``meshes`` keys; meshes mirror the
#: MULTICHIP dryrun legs (dp x tp, ring-attention SP, routed MoE EP, and
#: the three pipeline schedules) at audit scale on 8 devices
MESH_STRATEGIES: typing.Dict[str, MeshStrategy] = {
    "dp_tp": MeshStrategy(
        "dp_tp",
        {"mesh_shape_override": {"data": 4, "model": 2}},
        entries=("train_step", "decode_chunk_step", "engine_chunk_step",
                 "spec_chunk_step", "paged_chunk_step",
                 "spec_paged_chunk_step"),
        sharded_dims={"heads": "model"},
        collective_axes=frozenset({"data", "model"}),
        description="2-D data x tensor parallelism (heads over 'model')"),
    "ring_sp": MeshStrategy(
        "ring_sp",
        {"mesh_shape_override": {"data": 2, "sequence": 4},
         "block_config": _RING_BLOCKS},
        sharded_dims={},  # params replicate; the sequence activations shard
        collective_axes=frozenset({"data", "sequence"}),
        description="ring-attention sequence parallelism (zigzag ring)"),
    "moe_ep": MeshStrategy(
        "moe_ep",
        {"mesh_shape_override": {"data": 4, "model": 2},
         "block_config": _MOE_BLOCKS, "experts": 4, "moe_top_k": 2,
         "moe_capacity_factor": 2.0,
         "layout_override": {"experts": "model", "heads": None}},
        sharded_dims={"experts": "model"},
        collective_axes=frozenset({"data", "model"}),
        description="routed top-k MoE expert parallelism (experts over "
                    "'model')"),
    "pp_gpipe": MeshStrategy(
        "pp_gpipe",
        {"mesh_shape_override": {"data": 2, "pipe": 2, "model": 2},
         "train_batch_size": 8},
        sharded_dims={"heads": "model"},
        collective_axes=frozenset({"data", "pipe", "model"}),
        description="GPipe microbatch pipeline + tensor parallelism"),
    "pp_1f1b": MeshStrategy(
        "pp_1f1b",
        {"mesh_shape_override": {"data": 2, "pipe": 2, "model": 2},
         "train_batch_size": 8, "pipeline_schedule": "1f1b",
         "pipeline_microbatches": 4},
        sharded_dims={"heads": "model"},
        collective_axes=frozenset({"data", "pipe", "model"}),
        description="1F1B pipeline schedule + tensor parallelism"),
    "pp_interleaved": MeshStrategy(
        "pp_interleaved",
        {"mesh_shape_override": {"data": 2, "pipe": 2, "model": 2},
         "train_batch_size": 8, "depth": 4, "pipeline_schedule": "1f1b",
         "pipeline_interleave": 2, "pipeline_microbatches": 2},
        sharded_dims={"heads": "model"},
        collective_axes=frozenset({"data", "pipe", "model"}),
        description="interleaved 1F1B (V=2 virtual stages) + tp"),
}


# ---- shared aval lowering (scripts/pod_lowering.py delegates here) ---------

def cheap_init_patch():
    """Replace the numpy QR/normal initializers with zeros for an
    aval-only lowering (AOT consumes shapes/dtypes/shardings; QR of big
    matrices is minutes of host time buying nothing).  Returns undo()."""
    from ..model import backend

    saved = (backend.OrthogonalInit.__call__, backend.NormalInit.__call__)

    def zeros_init(self, rng, sizes):
        return np.zeros(sizes, np.float32)

    backend.OrthogonalInit.__call__ = zeros_init
    backend.NormalInit.__call__ = zeros_init

    def undo():
        backend.OrthogonalInit.__call__, backend.NormalInit.__call__ = saved

    return undo


def opt_state_avals(optimizer, var_avals, mesh):
    """Optimizer slot avals via the REAL ``Optimizer.init`` slot
    discovery, with materialisation swapped for ShapeDtypeStructs
    (``_zeros_for``'s sharding rule: same-shape slots inherit the
    variable's sharding, reduced-shape slots replicate)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from .. import optim as optim_mod

    saved = optim_mod._zeros_for

    def aval_zeros(variable, shape, dtype):
        sharding = getattr(variable, "sharding", None)
        if sharding is None or tuple(shape) != tuple(variable.shape):
            sharding = NamedSharding(mesh, PartitionSpec())
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)

    optim_mod._zeros_for = aval_zeros
    try:
        return optimizer.init(var_avals)
    finally:
        optim_mod._zeros_for = saved


def train_step_avals(params, model, mesh, cheap_init: bool = True):
    """``(state_avals, batch_avals, rng_aval, info)`` for lowering the
    donated train step without materialising anything on devices — the ONE
    aval-construction path shared by the mesh audit and
    ``scripts/pod_lowering.py`` (which used to carry its own copy)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from .. import optim as optim_mod
    from ..core import sharding as shardlib
    from ..train import TrainState

    seq = params.sequence_length // params.token_patch_size
    batch_np = {
        "token_x": np.zeros((params.train_batch_size, seq,
                             params.token_patch_size), np.int32),
        "token_y": np.zeros((params.train_batch_size, seq,
                             params.token_patch_size), np.int32)}
    undo = cheap_init_patch() if cheap_init else (lambda: None)
    try:
        variables = model.init(batch_np)
    finally:
        undo()
    var_avals = {
        k: jax.ShapeDtypeStruct(
            np.shape(v), np.asarray(v).dtype,
            sharding=shardlib.named_sharding(
                params, model.param_dims.get(k, ()), mesh))
        for k, v in variables.items()}
    n_params = sum(int(np.prod(a.shape)) for a in var_avals.values())
    del variables  # free the host zeros before compiling

    optimizer = optim_mod.Optimizer(params, model.param_dims)
    opt_avals = opt_state_avals(optimizer, var_avals, mesh)
    repl = NamedSharding(mesh, PartitionSpec())
    state_avals = TrainState(
        var_avals, opt_avals,
        jax.ShapeDtypeStruct((), np.int32, sharding=repl))

    batch_entries: typing.List[typing.Optional[str]] = [None] * 3
    if params.train_batch_size % mesh.shape.get(shardlib.DATA_AXIS, 1) == 0:
        batch_entries[0] = shardlib.DATA_AXIS
    batch_sharding = NamedSharding(mesh, PartitionSpec(*batch_entries))
    batch_avals = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                           sharding=batch_sharding)
                   for k, v in batch_np.items()}
    rng_aval = jax.ShapeDtypeStruct((2,), np.uint32, sharding=repl)
    info = {"n_params": n_params, "var_avals": var_avals,
            "optimizer": optimizer}
    return state_avals, batch_avals, rng_aval, info


# ---- strategy lowering ------------------------------------------------------

def audit_devices(n: int = MESH_DEVICES):
    """First ``n`` jax devices; raises with the bootstrap hint when the
    process has fewer (scripts/graft_lint.py re-runs the mesh half in a
    CPU-virtual subprocess in that case)."""
    import jax

    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh audit needs {n} devices, have {len(devices)} — run "
            f"under JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={n} (scripts/graft_lint.py --mesh does this "
            f"automatically)")
    return devices[:n]


def classify_env_gap(exc: BaseException) -> typing.Optional[str]:
    """Non-None (the marker) when a lowering failure is a known gap of the
    CURRENT jax/XLA, not a repo regression."""
    text = f"{type(exc).__name__}: {exc}"
    for marker in _ENV_GAP_MARKERS:
        if marker in text:
            return marker
    return None


def _lowering_abort(name: str) -> typing.Optional[str]:
    """Lower strategy ``name`` in a throwaway subprocess and return why it
    DIED (killed by a signal: an XLA fatal CHECK aborts the interpreter and
    would take the whole lint / test run with it), or None when the process
    survived — python-level failures are left to reproduce in-process,
    where ``classify_env_gap`` sees the exception."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo,
               XLA_FLAGS="--xla_force_host_platform_device_count="
                         f"{MESH_DEVICES}")
    proc = subprocess.run(
        [sys.executable, "-c",
         "from homebrewnlp_tpu.analysis import mesh_audit as m; "
         f"m.lower_strategy(m.MESH_STRATEGIES[{name!r}])"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=1800)
    if proc.returncode >= 0:
        return None
    fatal = next((ln for ln in proc.stderr.splitlines()
                  if re.match(r"F\d{4} ", ln)), "no fatal line on stderr")
    # without glog's date, time and pid: the banked reason must not churn
    fatal = re.sub(r"^F\d{4} [\d:.]+\s+\d+ ", "", fatal)
    return f"{_ABORT_MARKER} (signal {-proc.returncode}): {fatal[-160:]}"


def _strategy_params_model(strategy: MeshStrategy):
    from ..config import ModelParameter
    from ..model import Model

    cfg = dict(entry_points.AUDIT_CONFIG)
    cfg.update(tpu_size=MESH_DEVICES, model_path="/tmp/mesh_audit")
    cfg.update(strategy.overrides)
    params = ModelParameter(cfg)
    return params, Model(params)


def expected_shard_shape(shape: typing.Sequence[int], dims, contract,
                         mesh_shape) -> typing.Tuple[int, ...]:
    """Per-chip shape the strategy contract demands for a leaf with named
    ``dims`` (each mesh axis used at most once, divisibility respected —
    the same visible rules as ``shardlib.spec_for_dims``, but driven by
    the strategy's OWN contract so the two can disagree and fail)."""
    out = list(shape)
    used: typing.Set[str] = set()
    for i, d in enumerate(dims):
        axis = contract.get(getattr(d, "name", None))
        if (axis is not None and axis in mesh_shape and axis not in used
                and out[i] % mesh_shape[axis] == 0):
            out[i] //= mesh_shape[axis]
            used.add(axis)
    return tuple(out)


def _shape_str(dtype, shape) -> str:
    dt = hlo_lint._HLO_DTYPE.get(str(np.dtype(dtype)), str(dtype))
    return f"{dt}[{','.join(str(int(d)) for d in shape)}]"


def _train_protected(params, model, var_avals, strategy, mesh
                     ) -> typing.Dict[str, dict]:
    """Protected-leaf table for the sharding-spec audit: model-parallel
    params (contract-sharded dims) + the batch inputs (data-sharded
    leading dim)."""
    from ..core import sharding as shardlib

    protected: typing.Dict[str, dict] = {}
    for name, aval in var_avals.items():
        dims = model.param_dims.get(name, ())
        exp = expected_shard_shape(aval.shape, dims, strategy.sharded_dims,
                                   mesh.shape)
        if tuple(exp) == tuple(aval.shape):
            continue  # contract leaves it unsharded — nothing to pin
        protected[name] = {
            "kind": "exact",
            "full": _shape_str(aval.dtype, aval.shape),
            "shard": _shape_str(aval.dtype, exp),
            "axes": sorted(set(strategy.sharded_dims.values()))}
    data = mesh.shape.get(shardlib.DATA_AXIS, 1)
    if data > 1 and params.train_batch_size % data == 0:
        seq = params.sequence_length // params.token_patch_size
        full = (params.train_batch_size, seq, params.token_patch_size)
        shard = (params.train_batch_size // data,) + full[1:]
        for key in ("token_x", "token_y"):
            protected[key] = {
                "kind": "exact",
                "full": _shape_str(np.int32, full),
                "shard": _shape_str(np.int32, shard),
                "axes": [shardlib.DATA_AXIS]}
    return protected


def _cache_protected(cache_shapes: typing.Mapping[str, typing.Any]
                     ) -> typing.Dict[str, dict]:
    """KV-cache leaves: the contract is "NOT fully replicated" — a cache
    materialised at its full shape on every chip is the 8x-HBM serving
    regression; which dims shard (batch over 'data', heads over 'model')
    is the compiler's choice the census already pins."""
    return {name: {"kind": "sharded_any",
                   "full": _shape_str(v.dtype, v.shape)}
            for name, v in cache_shapes.items()}


def lower_train_under_mesh(strategy: MeshStrategy, devices=None):
    """``(hlo_text, context)`` of the donated train step compiled under
    the strategy's mesh from avals."""
    from ..core import sharding as shardlib
    from ..train import Trainer

    params, model = _strategy_params_model(strategy)
    devices = audit_devices() if devices is None else devices
    mesh = shardlib.build_mesh(params, devices)
    state_avals, batch_avals, rng_aval, info = train_step_avals(
        params, model, mesh, cheap_init=False)
    trainer = Trainer(params, model, mesh)
    trainer.optimizer = info["optimizer"]
    compiled = trainer._build_step(state=state_avals).lower(
        state_avals, batch_avals, rng_aval).compile()
    hlo = compiled.as_text()
    context = {
        "mesh_shape": dict(mesh.shape),
        "protected": _train_protected(params, model, info["var_avals"],
                                      strategy, mesh),
        "param_bytes": sum(a.size * a.dtype.itemsize
                           for a in info["var_avals"].values()),
        "compiled": compiled,
    }
    return hlo, context


def lower_serving_under_mesh(strategy: MeshStrategy, entry: str,
                             devices=None):
    """``(hlo_text, context)`` of ``decode_chunk_step`` /
    ``engine_chunk_step`` compiled under the strategy's INFERENCE mesh
    (``shardlib.inference_mesh`` — 'pipe'/'sequence' folded into 'data'),
    reusing the registered entry-point lowerings so serving audits the
    exact production program shape."""
    import jax
    import jax.numpy as jnp

    from ..core import sharding as shardlib

    params, model = _strategy_params_model(strategy)
    devices = audit_devices() if devices is None else devices
    mesh = shardlib.inference_mesh(params, devices)
    seq = params.sequence_length // params.token_patch_size
    batch_np = {"token_x": np.zeros((params.train_batch_size, seq,
                                     params.token_patch_size), np.int32),
                "token_y": np.zeros((params.train_batch_size, seq,
                                     params.token_patch_size), np.int32)}
    variables = model.init(batch_np)
    var_avals = {
        k: jax.ShapeDtypeStruct(
            np.shape(v), np.asarray(v).dtype,
            sharding=shardlib.named_sharding(
                params, model.param_dims.get(k, ()), mesh))
        for k, v in variables.items()}
    tok = jnp.zeros(batch_np["token_x"].shape, jnp.int32)
    if entry == "decode_chunk_step":
        hlo, ctx = entry_points.lower_decode_step(model, var_avals, tok,
                                                  mesh=mesh)
    elif entry == "engine_chunk_step":
        hlo, ctx = entry_points.lower_engine_step(model, var_avals, tok,
                                                  mesh=mesh)
    elif entry == "paged_chunk_step":
        # the paged pools inherit the KV layout constraints through the
        # same _constrain_cache path as the slot pool (the views are
        # constrained in-loop; the pools are their storage), so the audit
        # covers the sharded serving shape of the paged program
        hlo, ctx = entry_points.lower_paged_step(model, var_avals, tok,
                                                 mesh=mesh)
    elif entry in ("spec_chunk_step", "spec_paged_chunk_step"):
        # the draft rides the same strategy at DRAFT_AUDIT_OVERRIDES width;
        # its param avals carry the same layout-rule shardings as the
        # target's, so the compiled program shards the draft pool too (the
        # sharding CONTRACT below stays on the target's leaves — the two
        # models' param names collide, and the target pool is the one whose
        # full-replication would be the 8x-HBM regression)
        dstrategy = dataclasses.replace(
            strategy, overrides={**dict(strategy.overrides),
                                 **entry_points.DRAFT_AUDIT_OVERRIDES})
        dparams, dmodel = _strategy_params_model(dstrategy)
        dvariables = dmodel.init(batch_np)
        dvar_avals = {
            k: jax.ShapeDtypeStruct(
                np.shape(v), np.asarray(v).dtype,
                sharding=shardlib.named_sharding(
                    dparams, dmodel.param_dims.get(k, ()), mesh))
            for k, v in dvariables.items()}
        lower = (entry_points.lower_spec_step if entry == "spec_chunk_step"
                 else entry_points.lower_spec_paged_step)
        hlo, ctx = lower(model, var_avals, tok, draft_model=dmodel,
                         draft_variables=dvar_avals, mesh=mesh)
        # two models in one program share every leaf NAME (same scope paths
        # at two widths), so the by-name metadata join cannot tell target
        # from draft parameters: the spec entry keeps the cache-pool
        # sharded_any contract (a full-shape pool replication is the HBM
        # regression this pass exists for) and leaves the exact per-param
        # contract to engine_chunk_step, which audits the identical target
        # params under the identical layout without the collision
        protected = _cache_protected(
            {k: v for k, v in ctx["cache_shapes"].items()
             if not k.startswith("draft/")})
        return hlo, {"mesh_shape": dict(mesh.shape), "protected": protected,
                     "param_bytes": sum(a.size * a.dtype.itemsize
                                        for a in var_avals.values()),
                     "compiled": ctx["compiled"]}
    else:
        raise ValueError(f"unsupported serving entry {entry!r}")
    protected = _cache_protected(ctx["cache_shapes"])
    # model-parallel param leaves keep the training contract at serve time
    for name, aval in var_avals.items():
        dims = model.param_dims.get(name, ())
        exp = expected_shard_shape(aval.shape, dims, strategy.sharded_dims,
                                   mesh.shape)
        if tuple(exp) != tuple(aval.shape):
            protected[name] = {
                "kind": "exact",
                "full": _shape_str(aval.dtype, aval.shape),
                "shard": _shape_str(aval.dtype, exp),
                "axes": sorted(set(strategy.sharded_dims.values()))}
    context = {
        "mesh_shape": dict(mesh.shape),
        "protected": protected,
        "param_bytes": sum(a.size * a.dtype.itemsize
                           for a in var_avals.values()),
        "compiled": ctx["compiled"],
    }
    return hlo, context


def lower_strategy(strategy: MeshStrategy, devices=None
                   ) -> typing.Tuple[typing.Dict[str, typing.Tuple[str, dict]],
                                     typing.Dict[str, str]]:
    """``({entry: (hlo, ctx)}, {entry: env_gap_reason})`` for one
    strategy — entries that lower are KEPT even when a later entry hits
    an environment gap (a dp_tp train audit must not vanish because the
    engine entry gapped); any non-gap exception propagates."""
    out: typing.Dict[str, typing.Tuple[str, dict]] = {}
    gaps: typing.Dict[str, str] = {}
    for entry in strategy.entries:
        try:
            if entry == "train_step":
                out[entry] = lower_train_under_mesh(strategy, devices)
            else:
                out[entry] = lower_serving_under_mesh(strategy, entry,
                                                      devices)
        except Exception as exc:  # noqa: BLE001 — classified below
            reason = classify_env_gap(exc)
            if reason is None:
                raise
            gaps[entry] = reason
    return out, gaps


def lower_strategies(devices=None, strategies=None):
    """``({strategy: {entry: (hlo, ctx)}}, skipped)`` where ``skipped``
    maps ``strategy`` (every entry gapped) or ``strategy/entry`` (partial
    gap) to the environment-gap reason.  Only classified environment gaps
    skip; any other exception propagates — a repo regression must fail
    the lint, not hide as a skip."""
    lowered: typing.Dict[str, dict] = {}
    skipped: typing.Dict[str, str] = {}
    for name in (strategies or MESH_STRATEGIES):
        strategy = MESH_STRATEGIES[name]
        if strategy.overrides.get("mesh_shape_override", {}).get("pipe", 1) > 1:
            # the pipeline schedules are what aborts XLA:CPU (module
            # docstring): probe out of process before compiling in this one
            abort = _lowering_abort(name)
            if abort is not None:
                skipped[name] = abort
                continue
        out, gaps = lower_strategy(strategy, devices)
        if out:
            lowered[name] = out
            for entry, reason in gaps.items():
                skipped[f"{name}/{entry}"] = reason
        elif gaps:
            # every entry gapped: one strategy-level skip, first reason
            skipped[name] = next(iter(gaps.values()))
    return lowered, skipped


# ---- pass 1: per-mesh collective budgets ------------------------------------

def analytic_expectations(strategy: MeshStrategy, mesh_shape,
                          param_bytes: int, entry: str) -> dict:
    """Analytic floor per collective kind, derived from mesh shape x model
    dims — NOT a prediction of XLA's exact op mix (XLA fuses and re-splits
    freely) but a lower bound a real parallel lowering cannot undercut:

    * train under data parallelism: gradients of every
      non-data-sharded param leaf must cross 'data' at least once —
      all-reduce bytes >= ~quarter of param bytes (quarter, not full:
      grads may reduce in bf16 and reduce-scatter splits the kinds).
    * ring SP: at least ``sequence-1`` collective-permutes (one ring).
    * tensor-parallel serving entries: at least one all-reduce (the
      unembed contraction's partial sums).

    ``--write`` refuses budgets below these floors, so the committed
    contract can never encode "the strategy stopped being parallel"."""
    from ..core import sharding as shardlib

    floors: typing.Dict[str, dict] = {}
    data = mesh_shape.get(shardlib.DATA_AXIS, 1)
    seq = mesh_shape.get(shardlib.SEQUENCE_AXIS, 1)
    model = mesh_shape.get(shardlib.MODEL_AXIS, 1)
    if entry.startswith("train_step"):
        if data > 1 or model > 1:
            floors["all-reduce"] = {"min_count": 1,
                                    "min_bytes": param_bytes // 4
                                    if data > 1 else 1}
        if seq > 1:
            floors["collective-permute"] = {"min_count": seq - 1,
                                            "min_bytes": 1}
    elif model > 1:
        floors["all-reduce"] = {"min_count": 1, "min_bytes": 1}
    return floors


def mesh_collective_budget_audit(entry: str, inventory: typing.Mapping,
                                 budget: typing.Mapping,
                                 tolerance: float = DEFAULT_TOLERANCE
                                 ) -> typing.List[Finding]:
    """Fresh census vs the committed per-strategy budget row.  Count and
    bytes are both tolerance-checked; a kind missing from the budget is
    budget 0 (a NEW collective kind is always a finding).  Surplus
    findings name the mesh axes the extra replica groups span."""
    findings: typing.List[Finding] = []
    kinds = sorted(set(inventory) | set(k for k in budget
                                        if isinstance(budget.get(k), dict)))
    for kind in kinds:
        fresh = inventory.get(kind, {"count": 0, "bytes": 0})
        committed = budget.get(kind, {"count": 0, "bytes": 0})
        for metric in ("count", "bytes"):
            a = int(committed.get(metric, 0))
            b = int(fresh.get(metric, 0))
            if abs(b - a) <= max(1 if metric == "count" else 0,
                                 tolerance * a):
                continue
            if b > a:
                axes_new = fresh.get("axes", {})
                axes_old = committed.get("axes", {})
                surplus = {ax: axes_new[ax] - axes_old.get(ax, 0)
                           for ax in axes_new
                           if axes_new[ax] > axes_old.get(ax, 0)}
                where = ", ".join(
                    f"mesh axis '{ax}' (+{n})"
                    for ax, n in sorted(surplus.items())) or "unknown axes"
                findings.append(Finding(
                    "mesh-collective", entry,
                    f"{kind} {metric}={b} over budget {a} "
                    f"(tolerance {tolerance:.0%}) — the surplus "
                    f"collectives reshard over {where}; accidental "
                    "resharding, or if intentional re-run `python -m "
                    "homebrewnlp_tpu.analysis.mesh_audit --write` and "
                    "explain the new comms in the PR"))
            else:
                findings.append(Finding(
                    "mesh-collective", entry,
                    f"{kind} {metric} fell to {b} (budget {a}, tolerance "
                    f"{tolerance:.0%}) — the strategy's comms pattern "
                    "changed underneath the committed budget; if the drop "
                    "is a real win, re-run --write and bank it"))
            break  # one finding per kind is enough signal
    return findings


# ---- pass 2: sharding-spec audit -------------------------------------------

_ENTRY_PARAM_RE = re.compile(
    r"=\s*([a-z0-9]+\[[0-9,]*\])(?:\{[^}]*\})?\s+parameter\((\d+)\)"
    r"(?:[^\n]*?sharding=\{([^}]*)\})?")
_OP_NAME_ATTR_RE = re.compile(r'op_name="([^"]*)"')


def entry_parameters(hlo_text: str) -> typing.List[dict]:
    """``[{index, shape, sharding, op_name}]`` of the ENTRY computation's
    parameters.  jax stamps each with the flattened argument path
    (``op_name="state.variables['...']"``), which is the leaf join — the
    parameter NUMBER shifts when unused args are pruned, the path does
    not."""
    out: typing.List[dict] = []
    in_entry = False
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY "):
            in_entry = True
            continue
        if in_entry and line.startswith("}"):
            break
        if not in_entry or " parameter(" not in line:
            continue
        m = _ENTRY_PARAM_RE.search(line)
        if m is None:
            continue
        op = _OP_NAME_ATTR_RE.search(line)
        op_name = op.group(1).replace("\\'", "'") if op else None
        out.append({"index": int(m.group(2)), "shape": m.group(1),
                    "sharding": m.group(3), "op_name": op_name})
    return out


_GATHER_LINE_RE = re.compile(r"=\s*([^=]*?)\s(all-gather)(-start|-done)?\(")


def _gather_result_shapes(hlo_text: str) -> typing.Set[str]:
    """Result shapes of every all-gather instruction.  Anchored between
    the ``=`` and the op token (like the census regex): the op name must
    be followed by ``(``, so instruction NAMES (``%all-gather.3``) and
    operand references on consumer lines never match — only actual
    gather results count.  Async forms: the ``-start`` tuple lists
    (operand, output) so the gathered shape is among its members; the
    ``-done`` twin's result is the output itself."""
    shapes: typing.Set[str] = set()
    for line in hlo_text.splitlines():
        m = _GATHER_LINE_RE.search(line)
        if m is None:
            continue
        for dt, dims in hlo_lint._SHAPE_TOKEN_RE.findall(m.group(1)):
            shapes.add(f"{dt}[{dims}]")
    return shapes


def full_leaf_gathers(hlo_text: str,
                      protected: typing.Mapping[str, dict]
                      ) -> typing.List[str]:
    """Full shapes of protected leaves that some all-gather materialises —
    recorded at ``--write`` time as the reviewed baseline
    (``gather_ok_shapes``), so the audit flags only NEW full-leaf gathers
    (XLA legitimately gathers a small sharded weight where that beats
    partial-sum reduction; the regression is a gather APPEARING where the
    committed program had none)."""
    gathers = _gather_result_shapes(hlo_text)
    return sorted({spec["full"] for spec in protected.values()
                   if spec["full"] in gathers})


def sharding_spec_audit(entry: str, hlo_text: str,
                        protected: typing.Mapping[str, dict],
                        gather_allow: typing.Container[str] = ()
                        ) -> typing.List[Finding]:
    """Protected leaves carry their contracted shard shapes in the
    compiled ENTRY parameters; none is silently replicated, and no
    all-gather outside the committed baseline materialises a
    model-parallel leaf at full shape."""
    findings: typing.List[Finding] = []
    if not protected:
        return findings
    params_tbl = entry_parameters(hlo_text)
    gathers = _gather_result_shapes(hlo_text)
    for leaf, spec in sorted(protected.items()):
        match = [p for p in params_tbl
                 if p["op_name"] and f"'{leaf}'" in p["op_name"]]
        if not match and spec["kind"] == "exact" and "[" not in leaf:
            # batch leaves are labelled batch['token_x'] in train but ride
            # positional tuples elsewhere — fall back to bare-name match
            match = [p for p in params_tbl
                     if p["op_name"] and leaf in p["op_name"]]
        if not match:
            findings.append(Finding(
                "mesh-sharding", entry,
                f"protected leaf {leaf!r} not found among entry "
                "parameters — pruned or relabelled, the sharding audit "
                "cannot see it"))
            continue
        got = match[0]["shape"]
        if spec["kind"] == "exact":
            if got == spec["full"]:
                axes = "/".join(spec.get("axes", [])) or "its mesh axes"
                findings.append(Finding(
                    "mesh-sharding", entry,
                    f"leaf {leaf!r} is SILENTLY REPLICATED: entry "
                    f"parameter carries the full shape {got} instead of "
                    f"the contracted shard {spec['shard']} over {axes} — "
                    "per-chip memory and compute scale as if the axis "
                    "didn't exist"))
            elif got != spec["shard"]:
                findings.append(Finding(
                    "mesh-sharding", entry,
                    f"leaf {leaf!r} entry parameter is {got}, contract "
                    f"expects shard {spec['shard']} (full {spec['full']})"))
        else:  # sharded_any: full-shape parameter = replicated cache
            if got == spec["full"]:
                findings.append(Finding(
                    "mesh-sharding", entry,
                    f"cache leaf {leaf!r} rides the donated carry at FULL "
                    f"shape {got} — the KV pool is replicated per chip "
                    "instead of sharded"))
        if (spec["full"] in gathers and spec["full"] != got
                and spec["full"] not in gather_allow):
            findings.append(Finding(
                "mesh-sharding", entry,
                f"compiler-inserted all-gather materialises {leaf!r} at "
                f"full shape {spec['full']} — a sharded leaf is being "
                "re-assembled per chip (classic accidental-resharding "
                "signature)"))
    return findings


# ---- pass 3: HBM liveness ---------------------------------------------------

_COMP_HEADER_RE = re.compile(r"^(ENTRY\s+)?%?([A-Za-z0-9_.$-]+)\s+\([^)]*\)")
_INSTR_HEAD_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([A-Za-z0-9_.$-]+)\s*=\s*(.*)$")
_OPERAND_RE = re.compile(r"%([A-Za-z0-9_.$-]+)")
_OP_TOKEN_RE = re.compile(
    r"^((?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s+)?"
    r"([a-zA-Z][\w-]*)\(")

#: result is a VIEW of existing buffers, not an allocation
_VIEW_OPS = frozenset(("parameter", "tuple", "get-tuple-element", "bitcast"))
#: result aliases the operand carry in place (donation-style)
_INPLACE_OPS = frozenset(("while",))


def split_computations(hlo_text: str
                       ) -> typing.Tuple[str, typing.Dict[str, list]]:
    """``(entry_name, {computation: [instruction lines]})``."""
    comps: typing.Dict[str, list] = {}
    entry = ""
    current: typing.Optional[str] = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and "{" in line:
            m = _COMP_HEADER_RE.match(line)
            if m is not None:
                current = m.group(2)
                comps[current] = []
                if m.group(1):
                    entry = current
            continue
        if line.startswith("}"):
            current = None
            continue
        if current is not None and "=" in line:
            comps.setdefault(current, []).append(line)
    return entry, comps


def _segment_bytes(segment: str) -> int:
    return sum(int(np.prod([int(d) for d in dims.split(",") if d]))
               * hlo_lint._DTYPE_BYTES.get(dt, 1)
               for dt, dims in hlo_lint._SHAPE_TOKEN_RE.findall(segment))


def _walk_computation(lines: typing.Sequence[str],
                      comp_peaks: typing.Mapping[str, int],
                      count_params: bool) -> typing.Tuple[int, int]:
    """``(args_bytes, temp_peak)`` of one computation by linear-scan
    liveness: allocations at definition, frees at last textual use, a
    called computation's own internal peak stacked at its call site."""
    parsed = []
    for line in lines:
        m = _INSTR_HEAD_RE.match(line)
        if m is None:
            continue
        name, rhs = m.groups()
        om = _OP_TOKEN_RE.match(rhs)
        if om is None:
            continue
        result_seg, op = om.group(1) or "", om.group(2)
        tail = rhs[om.end():]
        operands = _OPERAND_RE.findall(tail)
        calls = _CALLS_TARGET_RE.findall(tail)
        parsed.append((name, op, _segment_bytes(result_seg), operands,
                       calls))
    last_use: typing.Dict[str, int] = {}
    for i, (_, _, _, operands, _) in enumerate(parsed):
        for o in operands:
            last_use[o] = i
    args_bytes = sum(nbytes for _, op, nbytes, _, _ in parsed
                     if op == "parameter")
    live: typing.Dict[str, int] = {}
    running = 0
    peak = 0
    for i, (name, op, nbytes, operands, calls) in enumerate(parsed):
        alloc = 0
        if op not in _VIEW_OPS and op not in _INPLACE_OPS:
            alloc = nbytes
        running += alloc
        if alloc:
            live[name] = alloc
        # only CONTAINER bodies (while/call/conditional) hold their own
        # HBM-live temporaries; a fusion's intermediates live in
        # registers/scratch, so its ``calls=`` body never stacks here
        callee = 0
        if op in ("while", "call", "conditional"):
            callee = max((comp_peaks.get(c, 0) for c in calls), default=0)
        peak = max(peak, running + callee)
        for o in operands:
            if last_use.get(o) == i and o in live:
                running -= live.pop(o)
    base = args_bytes if count_params else 0
    return args_bytes, base + peak


_CALLS_TARGET_RE = re.compile(
    r"(?:calls|to_apply|body|condition|branch_computations=\{)"
    r"=?%?([A-Za-z0-9_.$-]+)")


def liveness_estimate(hlo_text: str) -> typing.Dict[str, int]:
    """Per-chip peak-HBM estimate of one compiled (per-partition) module:
    ``{"args_bytes", "temp_peak_bytes", "peak_bytes"}``.

    Donated state aliases outputs, so the arguments stay live for the
    whole program and the peak is args + the largest concurrent
    temporaries from the buffer walk.  Fusion bodies allocate nothing
    (fused); while bodies contribute their internal walk at the call
    site.  An ESTIMATE with deterministic bias — the committed value is a
    regression gate (a replicated should-be-sharded buffer inflates it
    far past tolerance), not an allocator reproduction."""
    entry, comps = split_computations(hlo_text)
    # non-entry computations first: their internal peaks feed call sites.
    # Iterate to a fixed point over one dependency level at a time (HLO
    # text orders callees before callers in practice; two passes cover
    # stragglers).
    comp_peaks: typing.Dict[str, int] = {}
    names = [c for c in comps if c != entry]
    for _ in range(2):
        for c in names:
            _, comp_peaks[c] = _walk_computation(comps[c], comp_peaks,
                                                 count_params=False)
    args_bytes, peak = _walk_computation(comps.get(entry, []), comp_peaks,
                                         count_params=True)
    return {"args_bytes": int(args_bytes),
            "temp_peak_bytes": int(peak - args_bytes),
            "peak_bytes": int(peak)}


def hbm_liveness_audit(entry: str, estimate: typing.Mapping[str, int],
                       budget_row: typing.Mapping[str, typing.Any],
                       hbm_bytes: int,
                       tolerance: float = DEFAULT_TOLERANCE
                       ) -> typing.List[Finding]:
    """Fresh liveness estimate within tolerance of the committed
    ``peak_bytes`` AND under the strategy's per-chip HBM."""
    findings: typing.List[Finding] = []
    fresh = int(estimate["peak_bytes"])
    committed = int(budget_row.get("peak_bytes", 0))
    if committed and fresh > committed * (1 + tolerance):
        findings.append(Finding(
            "mesh-liveness", entry,
            f"peak-HBM liveness estimate grew {committed} -> {fresh} "
            f"bytes (> {tolerance:.0%} tolerance) — a buffer that used to "
            "shard is now materialised per chip, or a temporary's live "
            "range exploded; scaled to the real config this is the "
            "OOM-at-32-chips regression.  If intentional, re-run `python "
            "-m homebrewnlp_tpu.analysis.mesh_audit --write`"))
    if fresh > hbm_bytes:
        findings.append(Finding(
            "mesh-liveness", entry,
            f"peak-HBM estimate {fresh} exceeds the strategy's per-chip "
            f"HBM budget {hbm_bytes}"))
    return findings


# ---- budgets: meshes section ------------------------------------------------

def _mesh_budget_row(hlo: str, ctx: dict, strategy: MeshStrategy,
                     entry: str) -> dict:
    inventory = hlo_lint.collective_inventory(hlo, ctx["mesh_shape"])
    est = liveness_estimate(hlo)
    row: typing.Dict[str, typing.Any] = {"collectives": inventory}
    row.update(est)
    baseline_gathers = full_leaf_gathers(hlo, ctx["protected"])
    if baseline_gathers:
        row["gather_ok_shapes"] = baseline_gathers
    ma = getattr(ctx.get("compiled"), "memory_analysis", lambda: None)()
    if ma is not None:
        # informational cross-check, never regression-checked (allocator-
        # and backend-dependent where the walk above is text-determined)
        row["xla_memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes)}
    return row


def _write_gate(strategy: MeshStrategy, entry: str, ctx: dict,
                row: dict) -> None:
    """Refuse to commit a budget the analytic model says is degenerate."""
    floors = analytic_expectations(strategy, ctx["mesh_shape"],
                                   ctx["param_bytes"], entry)
    inv = row["collectives"]
    for kind, floor in floors.items():
        got = inv.get(kind, {"count": 0, "bytes": 0})
        if (got["count"] < floor["min_count"]
                or got["bytes"] < floor["min_bytes"]):
            raise ValueError(
                f"--write refused: {strategy.name}/{entry} census "
                f"{kind}={got} is below the analytic floor {floor} "
                f"derived from mesh {ctx['mesh_shape']} x model dims — "
                "the strategy is not actually parallel in this lowering "
                "(broken layout rule?), committing it would bless the "
                "regression")
    allowed = strategy.collective_axes
    for kind, data in inv.items():
        for axes_key in data.get("axes", {}):
            if axes_key == "none":
                continue
            if not set(axes_key.split("+")) <= allowed:
                raise ValueError(
                    f"--write refused: {strategy.name}/{entry} has "
                    f"{kind} over mesh axes {axes_key!r}, outside the "
                    f"strategy's allowed axes {sorted(allowed)} — that is "
                    "resharding, not a budget")


def build_mesh_budgets(lowered=None, skipped=None,
                       existing: typing.Optional[dict] = None) -> dict:
    """The ``meshes`` section: measured budgets per strategy x entry (the
    analytic write-gate applied), ``pending`` rows for strategies the
    current environment cannot lower.  ``existing``: the meshes section
    being REPLACED (so a capable environment's committed entries survive
    a pipeline-incapable --write) — callers writing an alternate
    --budgets file pass that file's own section, never the default's."""
    if lowered is None:
        lowered, skipped = lower_strategies()
    skipped = skipped or {}
    meshes: typing.Dict[str, typing.Any] = {
        "_comment": [
            "Per-mesh budgets (analysis/mesh_audit.py): for each parallel",
            "strategy x entry point, the measured collective census",
            "(count + result bytes + replica-group mesh axes) and the",
            "peak-HBM liveness estimate of the compiled per-chip module",
            "on 8 virtual CPU devices.  graft_lint --mesh checks a fresh",
            "lowering against these within `tolerance`; surplus",
            "collectives are reported WITH the mesh axis they reshard",
            "over.  Regenerate via `python -m",
            "homebrewnlp_tpu.analysis.mesh_audit --write` (an analytic",
            "floor per strategy gates the write, so a degenerate,",
            "non-parallel baseline cannot be committed).  `pending` rows:",
            "the current jax/XLA cannot lower that strategy (reason",
            "recorded); they are skipped loudly until a capable",
            "environment commits real numbers (docs/STATIC_ANALYSIS.md)."],
        "tolerance": DEFAULT_TOLERANCE}
    if existing is None:
        existing = hlo_lint.load_budgets().get("meshes", {})
    for name, strategy in MESH_STRATEGIES.items():
        if name in lowered:
            mesh_shape = None
            entries = {}
            for entry, (hlo, ctx) in lowered[name].items():
                row = _mesh_budget_row(hlo, ctx, strategy, entry)
                _write_gate(strategy, entry, ctx, row)
                entries[entry] = row
                mesh_shape = mesh_shape or ctx["mesh_shape"]
            meshes[name] = {"mesh": mesh_shape, "entries": entries}
            # entries that env-gapped while siblings lowered: keep their
            # committed rows and mark the strategy pending, so the
            # coverage check stays exact and the skip stays legitimate
            gapped = {k.split("/", 1)[1]: r for k, r in skipped.items()
                      if k.startswith(name + "/")}
            if gapped:
                meshes[name]["pending"] = next(iter(gapped.values()))
                for entry in gapped:
                    old_row = existing.get(name, {}).get("entries",
                                                         {}).get(entry)
                    if old_row is not None:
                        entries[entry] = old_row
        else:
            old = existing.get(name, {})
            meshes[name] = {
                "mesh": old.get("mesh"),
                "pending": skipped.get(
                    name, old.get("pending", "not lowerable here"))}
            if old.get("entries"):
                # keep budgets committed by a capable environment
                meshes[name]["entries"] = old["entries"]
    return meshes


def write_mesh_budgets(path: typing.Optional[str] = None,
                       lowered=None, skipped=None) -> str:
    """Regenerate ONLY the ``meshes`` section of budgets.json (the
    ``entry_points`` section belongs to the single-device audit); the
    TARGET file's own pending/committed rows are the carry-over base."""
    p = path or hlo_lint.BUDGETS_PATH
    budgets = hlo_lint.load_budgets(p)
    budgets["meshes"] = build_mesh_budgets(
        lowered, skipped, existing=budgets.get("meshes", {}))
    with open(p, "w") as f:
        json.dump(budgets, f, indent=1, sort_keys=True)
        f.write("\n")
    return p


# ---- coverage + one-call audit ---------------------------------------------

def budget_coverage_audit(budgets: typing.Optional[dict] = None
                          ) -> typing.List[Finding]:
    """budgets.json keys are EXACTLY the registered entry points x
    registered meshes — a stale or orphan row (entry renamed, strategy
    dropped) fails instead of silently auditing nothing."""
    budgets = budgets if budgets is not None else hlo_lint.load_budgets()
    findings: typing.List[Finding] = []
    per_entry = set(budgets.get("entry_points", {}))
    registered = set(entry_points.ENTRY_POINTS)
    for orphan in sorted(per_entry - registered):
        findings.append(Finding(
            "mesh-budget-keys", "analysis/budgets.json",
            f"entry_points row {orphan!r} matches no registered entry "
            "point (analysis/entry_points.py ENTRY_POINTS) — a stale row "
            "audits nothing; delete it or restore the entry"))
    for missing in sorted(registered - per_entry):
        findings.append(Finding(
            "mesh-budget-keys", "analysis/budgets.json",
            f"registered entry point {missing!r} has no entry_points "
            "budget row"))
    meshes = budgets.get("meshes", {})
    mesh_rows = {k for k in meshes
                 if k not in ("tolerance",) and not k.startswith("_")}
    for orphan in sorted(mesh_rows - set(MESH_STRATEGIES)):
        findings.append(Finding(
            "mesh-budget-keys", "analysis/budgets.json",
            f"meshes row {orphan!r} matches no registered strategy "
            "(analysis/mesh_audit.py MESH_STRATEGIES)"))
    for missing in sorted(set(MESH_STRATEGIES) - mesh_rows):
        findings.append(Finding(
            "mesh-budget-keys", "analysis/budgets.json",
            f"registered mesh strategy {missing!r} has no meshes budget "
            "row — run `python -m homebrewnlp_tpu.analysis.mesh_audit "
            "--write`"))
    for name in sorted(mesh_rows & set(MESH_STRATEGIES)):
        row = meshes[name]
        if "pending" in row and "entries" not in row:
            continue
        have = set(row.get("entries", {}))
        want = set(MESH_STRATEGIES[name].entries)
        for orphan in sorted(have - want):
            findings.append(Finding(
                "mesh-budget-keys", f"meshes/{name}",
                f"budget row for entry {orphan!r} which the strategy no "
                "longer lowers"))
        for missing in sorted(want - have):
            findings.append(Finding(
                "mesh-budget-keys", f"meshes/{name}",
                f"strategy entry {missing!r} has no budget row — re-run "
                "--write"))
    return findings


def audit_lowered_meshes(lowered: typing.Mapping[str, dict],
                         skipped: typing.Mapping[str, str],
                         budgets: typing.Optional[dict] = None
                         ) -> typing.List[Finding]:
    """All three pass families over pre-lowered strategies + the coverage
    check."""
    from ..utils import flops as flops_mod

    budgets = budgets if budgets is not None else hlo_lint.load_budgets()
    meshes = budgets.get("meshes", {})
    tol = float(meshes.get("tolerance", DEFAULT_TOLERANCE))
    findings = budget_coverage_audit(budgets)
    # a skip is only legitimate where the committed row AGREES the
    # environment cannot lower it (its ``pending`` marker): committed
    # non-pending budgets whose strategy/entry stopped lowering would
    # otherwise audit nothing while CI stays green — the exact silent
    # pass the skip notices exist to prevent
    for key, reason in sorted(skipped.items()):
        name = key.split("/")[0]
        srow = meshes.get(name, {})
        if "entries" in srow and "pending" not in srow:
            findings.append(Finding(
                "mesh-lowering", key,
                f"strategy has committed (non-pending) budgets but no "
                f"longer lowers here ({reason}) — either the lowering "
                "regressed, or this environment newly lacks support: fix "
                "the lowering, or run `python -m homebrewnlp_tpu."
                "analysis.mesh_audit --write` in this environment to "
                "mark the row pending (keeping the committed entries)"))
    for name, per_entry in lowered.items():
        strategy = MESH_STRATEGIES[name]
        srow = meshes.get(name, {})
        if "entries" not in srow:
            findings.append(Finding(
                "mesh-pending", name,
                "strategy lowers in this environment but its budget row "
                "is pending — commit real budgets via `python -m "
                "homebrewnlp_tpu.analysis.mesh_audit --write`"))
            continue
        hbm = flops_mod.HBM_BYTES.get(strategy.hbm_device,
                                      flops_mod.HBM_BYTES["cpu"])
        for entry, (hlo, ctx) in per_entry.items():
            tag = f"{name}/{entry}"
            budget_row = srow["entries"].get(entry, {})
            inventory = hlo_lint.collective_inventory(hlo,
                                                      ctx["mesh_shape"])
            findings += mesh_collective_budget_audit(
                tag, inventory, budget_row.get("collectives", {}), tol)
            findings += sharding_spec_audit(
                tag, hlo, ctx["protected"],
                gather_allow=budget_row.get("gather_ok_shapes", ()))
            findings += hbm_liveness_audit(
                tag, liveness_estimate(hlo), budget_row, hbm, tol)
    return findings


def audit_meshes(budgets: typing.Optional[dict] = None,
                 devices=None
                 ) -> typing.Tuple[typing.List[Finding],
                                   typing.Dict[str, str]]:
    """``(findings, skipped)`` — the one-call form ``graft_lint --mesh``
    and tier-1 use."""
    lowered, skipped = lower_strategies(devices)
    return audit_lowered_meshes(lowered, skipped, budgets), skipped


# ---- CLI --------------------------------------------------------------------

def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="mesh-aware graft-lint: build / check the per-mesh "
                    "collective + liveness budgets")
    ap.add_argument("--write", action="store_true",
                    help="regenerate the `meshes` section of "
                         "analysis/budgets.json (the budget-update "
                         "protocol, docs/STATIC_ANALYSIS.md)")
    ap.add_argument("--check", action="store_true",
                    help="audit against the committed budgets (default)")
    ap.add_argument("--budgets", default=None,
                    help="alternate budgets.json path")
    args = ap.parse_args(argv)
    if args.write:
        lowered, skipped = lower_strategies()
        p = write_mesh_budgets(args.budgets, lowered, skipped)
        for name, reason in sorted(skipped.items()):
            print(f"mesh-audit: strategy {name!r} pending — environment "
                  f"gap: {reason}")
        print(f"mesh budgets written to {p}")
        return 0
    budgets = hlo_lint.load_budgets(args.budgets) if args.budgets else None
    findings, skipped = audit_meshes(budgets)
    for name, reason in sorted(skipped.items()):
        print(f"mesh-audit: strategy {name!r} SKIPPED — environment gap: "
              f"{reason}")
    for f in findings:
        print(f)
    if findings:
        print(f"mesh-audit: {len(findings)} finding(s)")
        return 1
    print(f"mesh-audit: clean ({len(MESH_STRATEGIES) - len(skipped)} "
          f"strategies audited, {len(skipped)} skipped)")
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
