"""Deterministic hierarchical naming + two-phase (init/apply) parameter store.

Replaces the reference's global ``NAME_INDICES`` variable-scope counters
(/root/reference/src/utils_core.py:16-19,57-67) and TF1 variable reuse.  Names
are hierarchical rather than global so any subtree (e.g. one reversible block)
can be re-traced in isolation inside a ``jax.custom_vjp`` backward pass and
still resolve the same parameter names.

Two phases, haiku-style but in-tree:
  * init: layer code runs once; ``get_param`` records each parameter and
    its numpy value from a per-name seeded initializer — made on the spot,
    or, under ``Model.init``'s abstract walk, by a pool of threads.
  * apply: same code path; ``get_param`` fetches arrays from the provided
    dict (casting storage/slice dtype -> calculation dtype).

All scope state lives in a context stack that exists only at trace time, so
everything stays compatible with jit/grad/vmap.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import re
import typing
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry import registry as _registry
from .tensor import NamedTensor, nt

Params = typing.Dict[str, jax.Array]

#: the ``jax.named_scope`` under which the program runs a forward AGAIN for
#: a backward (:func:`replay_vjp`).  ``jax.checkpoint`` names its own replay
#: ``rematted_computation``; analysis/cost_ledger.py ``pass_key`` reads both
REPLAY = "replay"


@dataclasses.dataclass
class _Frame:
    name: str
    counters: typing.Dict[str, int] = dataclasses.field(default_factory=dict)


_BLOCK_RE = re.compile(r"(body\d+/)block(\d+)_(\d+)_(\d+)/")


def depth0_name(name: str) -> str:
    """A body block's parameter name at depth 0: sibling depths of one block
    config share it (cross-layer weight sharing, model/backend.py; the int8
    scale groups, core/quant.py)."""
    return _BLOCK_RE.sub(
        lambda m: f"{m.group(1)}block0_{m.group(3)}_{m.group(4)}/", name)


class Context:
    """One build context: either collecting params (init) or reading them."""

    def __init__(self, mode: str, params: typing.Optional[Params] = None,
                 seed: int = 0, rng_key: typing.Optional[jax.Array] = None,
                 record_touched: bool = False, mesh: typing.Any = None,
                 decode: typing.Any = None):
        assert mode in ("init", "apply")
        self.mode = mode
        self.params: Params = {} if params is None else params
        self.seed = seed
        self.rng_key = rng_key
        # jax.sharding.Mesh when running sharded; layers may specialise
        # (e.g. ring attention over a 'sequence' axis)
        self.mesh = mesh
        # model.decode.DecodeState during incremental (KV-cached) decoding
        self.decode = decode
        # model.decode.PrefillState during single-pass prompt prefill: the
        # FULL-length forward runs normally while the sequence-mixing ops
        # additionally capture their decode caches (KV rows, cumsum totals,
        # conv windows) so the sampler can skip the per-token prompt walk
        self.prefill = None
        self.stack: typing.List[_Frame] = [_Frame("")]
        self.touched: typing.Optional[typing.List[str]] = [] if record_touched else None
        # name -> tuple[Dim] recorded at init; consumed by the optimizer's
        # shape-based heuristics and the sharding planner
        self.param_dims: typing.Dict[str, tuple] = {}
        # name -> tuple of contracted-dim NAMES (the linear's fan-in),
        # recorded at init when the initializer knows them; consumed by
        # serving quantization to pick safe per-channel scale axes
        self.param_fan_in: typing.Dict[str, tuple] = {}
        # arbitrary cross-layer caches (shared-variable machinery etc.)
        self.cache: typing.Dict[str, typing.Any] = {}
        # when not None, layers append (scope_path, {stat: scalar}) tuples
        # (e.g. MoE routing stats).  Only set by forward-only probe passes
        # where no lax.scan/custom_vjp separates the layer trace from the
        # consumer — ReplayBlock propagates it into its per-block contexts.
        self.stats_sink: typing.Optional[list] = None
        # when not None, layers append {name: scalar} dicts of per-step
        # statistics (layer moe's expert load) that the block machinery
        # returns out of its checkpoint / scan regions as explicit outputs
        # (model/blocks.py); the plain residual strategies only
        self.layer_stats: typing.Optional[list] = None
        # when not None, the CARRIED SIDE VALUES: ``{name: array}`` that one
        # layer leaves for a later one beside the stream (layer moe's
        # router state under ``router_mlp``; layer ``route_early``'s logits
        # for a ``routed_early`` sparse layer).  The block machinery hands the
        # dict in and out of every block's region as an explicit input and
        # output (model/blocks.py); None under the modes that carry none,
        # where a layer that needs one refuses by name
        self.side: typing.Optional[dict] = None
        # init mode under Model.init: the core.value_pool.ValuePool that
        # makes the values while the walk goes on (new_param); None = each
        # value is made where the walk meets it
        self.value_pool = None
        self._rng_count = 0

    # -- naming ------------------------------------------------------------
    def enter(self, name: str, again: bool = False) -> str:
        """Open scope ``name`` under the next index of the frame — or, with
        ``again``, under the index it was opened with LAST, with counters of
        its own: whatever runs inside resolves the names it resolved then (a
        looped model's passes, model/loop.py; apply mode only, since init
        would make every parameter a second time)."""
        frame = self.stack[-1]
        idx = frame.counters.get(name, 0) - bool(again)
        assert idx >= 0, f"scope {name!r} entered again before it was opened"
        frame.counters[name] = idx + 1
        scoped_name = f"{name}{idx}"
        self.stack.append(_Frame(scoped_name))
        return scoped_name

    def exit(self):
        self.stack.pop()

    def path(self) -> str:
        return "/".join(f.name for f in self.stack[1:])

    def full_name(self, leaf: str) -> str:
        frame = self.stack[-1]
        idx = frame.counters.get(leaf, 0)
        frame.counters[leaf] = idx + 1
        p = self.path()
        return f"{p}/{leaf}{idx}" if p else f"{leaf}{idx}"

    # -- rng ---------------------------------------------------------------
    def next_rng(self) -> typing.Optional[jax.Array]:
        if self.rng_key is None:
            return None
        self._rng_count += 1
        return jax.random.fold_in(self.rng_key, self._rng_count)


_CTX: typing.List[Context] = []


def current() -> Context:
    if not _CTX:
        raise RuntimeError("no active build Context; wrap model code in `with context(...)`")
    return _CTX[-1]


def in_context() -> bool:
    return bool(_CTX)


@contextlib.contextmanager
def context(ctx: Context):
    _CTX.append(ctx)
    try:
        yield ctx
    finally:
        _CTX.pop()


@contextlib.contextmanager
def name_scope(name: str, again: bool = False):
    ctx = current()
    scoped_name = ctx.enter(name, again)
    try:
        # mirror the scope frame into jax's name stack: every op traced
        # inside lands in compiled-HLO ``metadata={op_name=...}`` and in
        # jaxpr ``source_info.name_stack`` with its block/layer identity —
        # what the cost ledger (analysis/cost_ledger.py) and the trace's
        # ``tf_op`` (benchmark/lib/program_readers.py) fold by.  Metadata only:
        # the compiled program is unchanged.
        with jax.named_scope(scoped_name):
            yield
    finally:
        ctx.exit()


def replay_vjp(fn: typing.Callable, *primals):
    """``jax.vjp`` of a forward that already ran and that a backward runs
    AGAIN for its residuals (the revnet / momentum strategies' blocks,
    model/blocks.py; the 1F1B schedule's backward units,
    parallel/pipeline_1f1b.py): the re-traced forward stands under scope
    :data:`REPLAY`, which is how a device trace tells the replay from the
    forward and the backward (analysis/cost_ledger.py ``pass_key``).
    Metadata only: the compiled program is unchanged."""
    with jax.named_scope(REPLAY):
        return jax.vjp(fn, *primals)


def scoped(name: str, fn: typing.Callable, *args, **kwargs):
    """Run fn under a uniquified name scope (src/utils_core.py:16 analogue)."""
    with name_scope(name):
        return fn(*args, **kwargs)


def name_seed(name: str, seed: int) -> np.random.Generator:
    """Per-parameter deterministic RNG derived from (config seed, name)."""
    return np.random.default_rng(np.random.Philox(key=[seed & (2 ** 64 - 1),
                                                       zlib.crc32(name.encode())]))


def init_value(initializer, name: str, seed: int, sizes, dtype) -> np.ndarray:
    """One parameter's value: float32 from its per-name seeded initializer
    (host numpy), cast to the stored ``dtype``.  It depends on nothing but
    its arguments, so any thread may make it at any time
    (``core/value_pool.py``).  Counted in ``hbnlp_init_values_total``."""
    value = np.asarray(initializer(name_seed(name, seed), sizes),
                       dtype=np.float32)
    assert value.shape == tuple(sizes), (name, value.shape, sizes)
    _registry().counter("hbnlp_init_values_total",
                        "parameter values made by an initializer").inc()
    return value.astype(dtype, copy=False)


def new_param(ctx: Context, name: str, dims, initializer, slice_dtype):
    """Init mode: record parameter ``name`` and its value.  Under
    ``Model.init`` the value is a job for ``ctx.value_pool`` and the walk,
    which is abstract and never reads it, goes on with a stand-in of its
    shape and stored dtype; without a pool it is made here.

    ``initializer(rng, sizes) -> np.ndarray`` runs in float32; stored in
    slice_dtype (the mtf VariableDType.slice_dtype analogue,
    /root/reference/src/dataclass.py:253-255).  Host numpy, the "master"
    copy, mtf Saver-style: device placement + sharding happen at train
    setup, so init never touches an accelerator."""
    if name in ctx.params:
        raise ValueError(f"duplicate parameter {name}")
    dims = tuple(dims)
    sizes = tuple(d.size for d in dims)
    make = functools.partial(init_value, initializer, name, ctx.seed, sizes,
                             slice_dtype)
    if ctx.value_pool is None:
        ctx.params[name] = make()
    else:
        ctx.value_pool.submit(name, math.prod(sizes), make)
        ctx.params[name] = jax.ShapeDtypeStruct(sizes, slice_dtype)
    ctx.param_dims[name] = dims
    fan_in = getattr(initializer, "fan_in_names", None)
    if fan_in:
        ctx.param_fan_in[name] = tuple(fan_in)


def param_tensor(ctx: Context, name: str, dims, calc_dtype) -> NamedTensor:
    """Parameter ``name`` of the context, touched, in calc_dtype."""
    if name not in ctx.params:
        raise KeyError(f"parameter {name} missing from provided params")
    if ctx.touched is not None and name not in ctx.touched:
        ctx.touched.append(name)
    data = ctx.params[name]
    sizes = tuple(d.size for d in dims)
    assert tuple(data.shape) == sizes, (name, data.shape, sizes)
    if isinstance(data, jax.ShapeDtypeStruct):
        # init mode's stand-in for a value the pool is still making: the
        # walk is abstract, a traced constant of the shape serves it
        return nt(jax.lax.full(sizes, 0, calc_dtype), dims)
    return nt(materialize_param(ctx, name, data, calc_dtype), dims)


def get_param(name_leaf: str, dims, initializer, slice_dtype, calc_dtype
              ) -> NamedTensor:
    """Create (init) or fetch (apply) a parameter as a NamedTensor, stored
    in slice_dtype and computed in calc_dtype."""
    ctx = current()
    name = ctx.full_name(name_leaf)
    dims = tuple(dims)
    if ctx.mode == "init":
        new_param(ctx, name, dims, initializer, slice_dtype)
    return param_tensor(ctx, name, dims, calc_dtype)


def materialize_param(ctx: Context, name: str, data, calc_dtype):
    """Parameter value in calculation dtype; int8-quantized serving weights
    (core/quant.py) dequantize here — the convert+scale chain fuses into
    the consuming dot's operand read, so the HBM traffic stays int8.

    The dtype gate (not just name-in-scales) makes a stale ``quant_scales``
    harmless: applying the same Model to full-precision variables after a
    quantized InterfaceWrapper touched it must not scale unquantized
    weights."""
    scales = getattr(ctx, "quant_scales", None)
    if scales and data.dtype == jnp.int8 and name in scales:
        # named region: graft-lint's int8-promotion audit allows s8->float
        # converts ONLY inside dequant-tagged scopes (hlo_lint.py)
        with jax.named_scope("dequant"):
            scaled = data.astype(jnp.float32) * scales[name]
            return scaled.astype(calc_dtype)
    return data.astype(calc_dtype)
