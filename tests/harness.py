"""What the cell and kernel test files share (PR 60): a toy model of a
repository configuration, its loss and gradients, a layer steered to trace as
a TPU process would, a program compiled for a described v5e — once a module
for every assertion that reads its text — the comparison of two gradient
trees, and (PR 74) the only readers of ``tests/pins/``: what a cell's step, a
kernel's body and a configuration's start-up are, written once.  A file names
its own sizes; the mechanics are here."""
from __future__ import annotations

import functools
import glob
import hashlib
import importlib
import json
import os
import re
import typing

import jax
import jax.numpy as jnp
import numpy as np

from homebrewnlp_tpu.config import ModelParameter
from homebrewnlp_tpu.model import Model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: what the parent traced and printed, as data (PR 74): ``traces.json`` (name
#: -> sha1), ``startup/<configuration file's path>`` and ``seconds.json``
PINS = os.path.join(REPO, "tests", "pins")


@functools.cache
def _pins(relative: str) -> dict:
    with open(os.path.join(PINS, relative)) as f:
        return json.load(f)


def pinned(name: str, text: str):
    """``text`` (a jaxpr, a Pallas call's equation, a lowered module, a help
    line; object addresses stripped here) is what ``tests/pins/traces.json``
    holds under ``name``.  A change that MEANS to move it copies the new
    digest from the message into that file; ``git log -p`` of the file is
    the history."""
    got = hashlib.sha1(re.sub(r" at 0x[0-9a-f]+", "", text).encode()
                       ).hexdigest()
    want = _pins("traces.json").get(name)
    assert got == want, (
        f"{name}: tests/pins/traces.json holds {want}, this tree gives "
        f"{got}")


def startup_pin(path: str) -> dict:
    """What a trainer of the configuration file ``path`` (from the
    repository's root) prints and publishes at start-up, ``{"tpu": {"line",
    "series"}, "cpu": ..}`` with a cell's own under ``"cells"`` where its
    overrides move them: ``tests/pins/startup/<path>``."""
    return _pins(os.path.join("startup", path))


def config_files() -> list:
    """Every file under ``configs/`` and ``benchmark/configs/``, as a path
    from the repository's root."""
    return sorted(os.path.relpath(path, REPO) for where in (
        "configs", os.path.join("benchmark", "configs"))
        for path in glob.glob(os.path.join(REPO, where, "*.json")))


def cell_config_file(cell: str) -> str:
    """The file of benchmark cell ``cell``'s configuration, as
    ``BENCHMARK.json`` names it."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    config = next(w["config"] for w in bench["workloads"] if w["name"] == cell)
    return next(c["file"] for c in bench["configs"] if c["name"] == config)


@functools.cache
def train_cells(chips: typing.Optional[int] = None) -> tuple:
    """The names of ``BENCHMARK.json``'s cells that the train driver runs
    (on ``chips`` chips), in the file's order."""
    from benchmark.lib.cell import load_cell
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        cells = [load_cell(w["name"]) for w in json.load(f)["workloads"]]
    return tuple(c.name for c in cells if c.spec["driver"] == "train"
                 and chips in (None, c.chips))


@functools.cache
def cell_step_jaxpr(cell: str) -> str:
    """The forward's jaxpr of benchmark cell ``cell`` at its rehearsal size:
    built and traced once a process, whoever asks."""
    from benchmark.lib.cell import load_cell
    config = {**load_cell(cell).model_config(rehearsal=True),
              "model_path": "/tmp/cell_step", "dataset_configs": []}
    _, _, model, batch, variables = build(config)
    return step_jaxpr(model, variables, batch)


def reference(name: str):
    """The plain reference ``benchmark/reference/<name>.py``."""
    return importlib.import_module("benchmark.reference." + name)


def config_of(name: str, tiny: dict, dtype: str = "float32", **extra) -> dict:
    """``configs/<name>.json`` cut to the file's ``tiny`` sizes."""
    with open(os.path.join(REPO, "configs", name + ".json")) as f:
        return {**json.load(f), **tiny, "calculation_dtype": dtype, **extra}


def token_batch(batch: int, sequence: int, seed: int = 5) -> dict:
    tokens = np.random.default_rng(seed).integers(
        0, 256, (batch, sequence, 1)).astype(np.int32)
    return {"token_x": tokens, "token_y": np.roll(tokens, -1, axis=1)}


def build(config: dict, *, data_seed: int = 5, init_seed: int = 13,
          lively: typing.Optional[typing.Callable] = None):
    """``(config, params, model, batch, variables)`` of a toy configuration;
    ``lively`` is the file's own edit of the initial variables."""
    params = ModelParameter(config)
    assert not params.unknown_config_keys
    model = Model(params)
    batch = token_batch(config["train_batch_size"], config["sequence_length"],
                        data_seed)
    variables = model.init(batch, seed=init_seed)
    return config, params, model, batch, \
        variables if lively is None else lively(variables)


def loss_of(model):
    return lambda v, b: model.apply(v, b).total_loss.data


def loss_and_grads(model, variables, batch):
    v = {k: jnp.asarray(a) for k, a in variables.items()}
    return jax.jit(jax.value_and_grad(lambda v: loss_of(model)(v, batch)))(v)


def with_input_grads(fn, inputs, weights):
    """``(fn(*inputs), d sum(fn * weights) / d input ...)`` as one program."""
    def run(*inputs):
        out, pull = jax.vjp(fn, *inputs)
        return (out, *pull(weights.astype(out.dtype)))
    return jax.jit(run)(*inputs)


#: what the program does with its memory: no plain reference reads them
_PROGRAM_ONLY = ("memory_reduction_strategy", "remat_policy")
_REFERENCE_VALUES: dict = {}


def reference_loss_and_grads(ref, variables, tokens, targets, config):
    """``jax.value_and_grad`` of the plain reference's ``train_loss``, as one
    program — and one value a process for the same weights, batch and
    configuration: cases that differ by the program's memory strategy alone
    are held to the same one."""
    content = hashlib.sha1()
    for name, value in sorted({**variables, " x": tokens,
                               " y": targets}.items()):
        value = np.asarray(value)
        content.update(f"{name} {value.dtype} {value.shape}".encode())
        content.update(value.tobytes())
    key = (ref.__name__, content.hexdigest(), json.dumps(
        {k: v for k, v in config.items() if k not in _PROGRAM_ONLY},
        sort_keys=True, default=str))
    if key not in _REFERENCE_VALUES:
        v = {k: jnp.asarray(a) for k, a in variables.items()}
        _REFERENCE_VALUES[key] = jax.jit(jax.value_and_grad(
            lambda v: ref.train_loss(v, tokens, targets, config)))(v)
    return _REFERENCE_VALUES[key]


def traced_op_names(model, variables, batch, compiled: bool = True) -> set:
    """The names the gradient program's ops carry: the compiled program's
    ``op_name``s (what a trace reads), else the lowered module's locations."""
    lowered = jax.jit(jax.grad(loss_of(model))).lower(variables, batch)
    if compiled:
        return set(re.findall(r'op_name="([^"]*)"',
                              lowered.compile().as_text()))
    return set(re.findall(r'loc\("([^"]+)"',
                          lowered.as_text(debug_info=True)))


def step_jaxpr(model, variables, batch) -> str:
    """The forward's jaxpr, object addresses stripped."""
    return re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(
        lambda v: loss_of(model)(v, batch))(variables)))


def pallas_calls(jaxpr) -> list:
    """Every ``pallas_call`` equation under ``jaxpr``, nested ones too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for inner in jax.core.jaxprs_in_params(eqn.params):
            found.extend(pallas_calls(inner))
    return found


def logits_and_loss(model, variables, batch):
    info = jax.jit(lambda v, b: model.apply(v, b))(variables, batch)
    return (np.asarray(info.token_out.data.astype(jnp.float32))[:, :, 0, :],
            float(info.total_loss.data))


def assert_program_matches_reference(ref, built, dtype: str, tolerance):
    """The toy ``built`` (``build``'s tuple) against the plain reference on
    the same weights: the logits within ``tolerance`` of the largest, the
    loss within the calculation dtype's spacing; returns the logits."""
    from benchmark.reference import common
    config, _, model, batch, variables = built
    got, loss = logits_and_loss(model, variables, batch)
    want = np.asarray(ref.forward(variables, batch["token_x"][..., 0], config))
    assert got.shape == want.shape
    assert error(got, want) < tolerance
    want_loss = float(common.loss_of(want, batch["token_y"][..., 0], 0.0))
    assert abs(want_loss - loss) <= (2.0 ** -18 if dtype == "float32"
                                     else 2.0 ** -5)
    return got


def assert_float8_stream_misses(ref, built, bound: float = 2 ** -4):
    """The reference with a float8 (e4m3) residual stream misses the
    ``bound`` that the program in bfloat16 (``built``) holds: a lower
    precision than the configuration states comes out as not correct."""
    config, _, model, batch, variables = built
    tokens = batch["token_x"][..., 0]
    want = np.asarray(ref.forward(variables, tokens, config))
    low = np.asarray(ref.forward(variables, tokens, config,
                                 stream_dtype=jnp.float8_e4m3fn))
    got, _ = logits_and_loss(model, variables, batch)
    assert error(got, want) < bound < error(low, want)


def apply_with_stats(model, variables, batch):
    """``model.apply(.., layer_stats=True)`` as one program."""
    return jax.jit(lambda v, b: model.apply(v, b, layer_stats=True))(
        variables, batch)


def error(got, want) -> float:
    """The largest difference over the largest entry of ``want``."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(want - got)) / max(np.max(np.abs(want)), 1e-12))


def assert_grads_match(got, want, tolerance, alive: bool = False):
    """Every gradient less than ``tolerance`` of its twin's largest entry
    away (of 1 where the twin is all zero; with ``alive`` none is)."""
    assert set(got) == set(want)
    for name in sorted(got):
        g, w = (np.asarray(t[name], np.float32) for t in (got, want))
        scale = float(np.max(np.abs(w)))
        assert scale > 0 or not alive, name
        assert float(np.max(np.abs(g - w))) / (scale or 1.0) < tolerance, name


def assert_close_each(got, want, tolerance, names, floors=()):
    """Each of a kernel's outputs and gradients within ``tolerance`` of its
    twin's largest entry (of ``floors[name]`` where that is larger: a sum of
    terms that cancel is held to the terms' size)."""
    floors = dict(floors)
    for name, g, w in zip(names, got, want):
        g, w = (np.asarray(t, np.float32) for t in (g, w))
        assert g.shape == w.shape and np.all(np.isfinite(g)), name
        assert np.max(np.abs(g - w)) <= tolerance * max(
            np.max(np.abs(w)), floors.get(name, 1e-3)), name


def assert_close_tree(got: dict, want: dict, tolerance: float):
    """Two gradient trees, leaf by leaf, as ``assert_close_each``."""
    assert set(got) == set(want)
    assert_close_each([got[name] for name in want], want.values(), tolerance,
                      list(want))


def steer(monkeypatch, module, **replacements):
    """Trace ``module``'s layer as a TPU process would, kernels interpreted:
    each name of the layer's module is replaced for this test."""
    for name, value in replacements.items():
        monkeypatch.setattr(module, name, value)


def steer_interpreted(monkeypatch, module, kernels, *names, **fixed):
    """``module``'s kernel entry points ``names`` are ``kernels``' own,
    interpreted, with the caller's ``fixed`` arguments."""
    steer(monkeypatch, module, **{
        name: functools.partial(getattr(kernels, name), interpret=True,
                                **fixed) for name in names})


def steer_mamba_scan(monkeypatch, **fixed):
    """Layer ``mamba``'s scan is the Pallas pair, interpreted, whatever the
    shapes."""
    from homebrewnlp_tpu.model import mamba
    from homebrewnlp_tpu.parallel import ssd_scan
    steer(monkeypatch, mamba, ssd_kernel_applies=lambda *_, **__: True)
    steer_interpreted(monkeypatch, mamba, ssd_scan, "ssd_scan", **fixed)


def rule_value_and_grads(rule, inputs, weights, chunk, compiled=None):
    """``((o, dq, dk, dv, dbeta, dg), statistics)`` of a chunked rule ``(q,
    k, v, beta, g, chunk) -> (o, *statistics)`` under the cotangent
    ``weights``, as one program; ``compiled`` is where a caller of many draws
    keeps each rule's."""
    def loss(weights, *args):
        o, *statistics = rule(*args, chunk)
        return jnp.sum(o.astype(jnp.float32) * weights), (o, statistics)
    run = ({} if compiled is None else compiled).setdefault(
        (rule, chunk), jax.jit(jax.value_and_grad(
            loss, argnums=range(1, 6), has_aux=True)))
    (_, (o, statistics)), grads = run(weights, *inputs)
    return (o, *grads), statistics


def exp_operands(closed, args, opaque=("exp",)):
    """Run a closed jaxpr equation by equation, into its inner jaxprs:
    ``(outputs, the largest entry of every exp's operand)``.  What is not
    entered (a ``scan``, a solve, ``opaque``'s primitives) must hold no
    ``exp`` of its own — a ``pallas_call``'s report through a callback of
    the caller's."""
    from jax.extend.core import Literal
    seen = []

    def has_exp(eqn):
        inner = [getattr(p, "jaxpr", p) for p in eqn.params.values()]
        return eqn.primitive.name == "exp" or any(
            has_exp(e) for j in inner if hasattr(j, "eqns") for e in j.eqns)

    def walk(jaxpr, consts, args):
        env = dict(zip(jaxpr.constvars, consts))
        env.update(zip(jaxpr.invars, args))

        def read(var):
            return var.val if isinstance(var, Literal) else env[var]

        for eqn in jaxpr.eqns:
            values = [read(v) for v in eqn.invars]
            if eqn.primitive.name == "exp":
                seen.append(float(jnp.max(values[0])))
            inner = eqn.params.get("jaxpr", eqn.params.get("call_jaxpr"))
            if hasattr(inner, "consts") and eqn.primitive.name != "scan":
                out = walk(inner.jaxpr, inner.consts, values)
            else:
                assert eqn.primitive.name in opaque or not has_exp(eqn), eqn
                out = eqn.primitive.bind(*values, **eqn.params)
                out = out if eqn.primitive.multiple_results else [out]
            env.update(zip(eqn.outvars, out))
        return [read(v) for v in jaxpr.outvars]

    return walk(closed.jaxpr, closed.consts, list(args)), seen


def layer_on(params, fn, names, weights, x, flags=(), side=None):
    """One layer function ``fn`` of the program on ``x [b, s, heads,
    features]`` with the given weights (``names``: the reference's short
    names -> the program's paths): ``(output, the context it ran in)``."""
    from homebrewnlp_tpu.config import BlockArgs
    from homebrewnlp_tpu.core import scope
    from homebrewnlp_tpu.core.tensor import nt
    ctx = scope.Context("apply", params={
        path + "/var0": jnp.asarray(weights[short])
        for short, path in names.items() if short in weights})
    if side is not None:
        ctx.side = side
    base = next(iter(names.values())).split("_0/")[0]
    with scope.context(ctx):
        out = scope.scoped(base + "_", fn, BlockArgs(
            params, nt(x, [params.batch_dim, params.sequence_dim]
                       + list(params.feature_dims)), list(flags)))
    return out.data, ctx


# ---- a program compiled for a described v5e ----------------------------------

_COMPILED: dict = {}


def lowered_for_v5e(v5e, model, variables, batch) -> str:
    """Loss and gradients of ``model`` compiled for one chip of the described
    topology: the optimised HLO's text."""
    avals = [{k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=v5e)
              for k, v in tree.items()} for tree in (variables, batch)]
    return jax.jit(jax.value_and_grad(loss_of(model))).lower(
        *avals).compile().as_text()


def cell_layer_hlo(v5e, monkeypatch, cell: str, layer, policy=None,
                   **overrides):
    """``(params, hlo)`` of ONE block of benchmark cell ``cell`` — the first
    whose layers hold ``layer``, or block number ``layer`` — at the cell's
    published widths, 1 x ``sequence_length`` tokens, traced as a TPU process
    traces it and compiled for a v5e.  One compile a ``(cell, layer, policy,
    overrides)`` and process, shared by every assertion on its text."""
    from benchmark.lib.cell import load_cell
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    config = load_cell(cell).model_config()
    block = config["block_config"][layer] if isinstance(layer, int) else next(
        b for b in config["block_config"] if layer in b["layer"])
    if policy is not None:
        overrides["remat_policy"] = policy
    params = ModelParameter({**config, "block_config": [block],
                             "vocab_size": 512, "model_path": "/tmp/" + cell,
                             **overrides})
    key = (cell, layer, json.dumps(overrides, sort_keys=True))
    if key not in _COMPILED:
        model = Model(params)
        batch = {k: np.zeros((1, params.sequence_length, 1), np.int32)
                 for k in ("token_x", "token_y")}
        _COMPILED[key] = lowered_for_v5e(v5e, model, model.init(batch, seed=1),
                                         batch)
    return params, _COMPILED[key]


def kernel_calls(hlo: str):
    """``(name, op_name)`` of every Pallas call of a compiled program, XLA's
    ``.N`` suffixes dropped."""
    return [(re.sub(r"\.\d+$", "", name), op_name) for name, op_name in
            re.findall(r'%([\w.-]+) = [^\n]*?custom_call_target='
                       r'"tpu_custom_call"[^\n]*?op_name="([^"]+)"', hlo)]


def saved_flash_outputs_keep_their_scope(v5e, monkeypatch, cell: str,
                                         layer: str, scope: str):
    """One flash layer of a ``checkpoint`` cell at its published widths and
    the cell's sequence, compiled for a v5e as a TPU process traces it, with
    the attention kind riding the block's ``jax.checkpoint`` (PR 40): ONE
    forward kernel — the step's, outside ``flash_attention``'s
    ``custom_vjp``, none in the replay — and one fused backward, both still
    named ``flash_*`` (the ``^flash_`` readers) and folded into the layer's
    scope (``scope_mixing_time_share`` / ``scope_cca_time_share``); under
    ``"recompute"`` the same layer runs the forward twice.  A cell's file
    calls this on its own flash layer."""
    from homebrewnlp_tpu.analysis.cost_ledger import scope_key
    from homebrewnlp_tpu.model import remat
    calls = {}
    for policy in ("auto", "recompute"):
        params, hlo = cell_layer_hlo(v5e, monkeypatch, cell, layer, policy,
                                     depth=1)
        assert (remat.stash_plan(params)["attention"][0] == 1) \
            == (policy == "auto")
        calls[policy] = kernel_calls(hlo)
        for name, op_name in calls[policy]:
            assert name.startswith("flash_") and scope_key(op_name) == scope, \
                (name, op_name)
    kinds = {policy: sorted(name for name, _ in found)
             for policy, found in calls.items()}
    assert kinds["auto"] == ["flash_bwd_fused_causal", "flash_fwd_causal"]
    assert kinds["recompute"] == ["flash_bwd_fused_causal",
                                  "flash_fwd_causal", "flash_fwd_causal"]
    assert not any("rematted_computation" in op_name and "flash_fwd" in name
                   for name, op_name in calls["auto"])
    assert sum("rematted_computation" in op_name and "flash_fwd" in name
               for name, op_name in calls["recompute"]) == 1
