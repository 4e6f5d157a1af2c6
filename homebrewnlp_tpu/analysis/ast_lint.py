"""Repo-specific AST lint rules (graft-lint half b).

Source-level discipline over ``homebrewnlp_tpu/`` and ``scripts/`` —
stdlib-only and importable WITHOUT the package (by file path; nothing here
may import numpy, jax, or siblings):

==============  ============================================================
rule            invariant
==============  ============================================================
wallclock       ``time.time()`` is forbidden — durations on an NTP-stepped
                wall clock corrupted steps_per_sec (the PR 4 MetricLogger
                bug); use ``time.monotonic()``.  Epoch stamps that genuinely
                need wall time (tfevents wall_time, filename stamps) carry
                an allow marker.
unseeded-rng    ``np.random.default_rng()`` with no seed is unreproducible;
                the two deliberate sites (shuffle entropy, data_seed
                generation itself) carry allow markers.
donated-jit     every ``jax.jit(..., donate_argnums=...)`` site must be
                registered in ``DONATED_JIT_REGISTRY`` so the HLO donation
                audit (analysis/hlo_lint.py) covers it — an unregistered
                donation is an unaudited 2x-HBM failure mode.
engine-registry donated jit sites under ``infer/`` must be the Engine's
                single chunk-program builder (``engine.py::_chunk_jit``) or
                the batch sampler's — a donated jit anywhere else in the
                serving tier is a forked carry layout escaping the
                composition registry (``ENGINE_PROGRAMS``); new serving
                features compose as registry rows, not new programs.
mesh-axis-literal  hardcoded mesh-axis name strings ("data", "model",
                "sequence", "pipe") in axis-consuming positions —
                PartitionSpec/NamedSharding arguments, ``mesh.shape``
                subscripts/gets, ``axis_names`` membership tests — outside
                the axis-defining layers (``parallel/``,
                ``core/sharding.py``, ``config.py``).  Use the
                ``core.sharding`` constants (``DATA_AXIS`` ...) so an axis
                rename cannot silently strand a PartitionSpec.
config-docs     every ModelParameter knob has a docs/CONFIG.md table row.
env-knob        no ``os.environ`` / ``os.getenv`` read (``.get``, subscript,
                ``in``, ``setdefault``, ``pop``) under
                ``homebrewnlp_tpu/{model,parallel,train,optim,core}``: what
                the step program computes follows the configuration and
                what the code observes, never the shell it was started
                from.  ``ENV_KNOB_ALLOWED`` names the reads that are left,
                each with the debt that removes it.
layering        imports point downwards: no module under
                ``homebrewnlp_tpu/{core,parallel,telemetry,optim}`` imports
                ``model/``, ``train/``, ``infer/`` or ``run/``
                (``LAYERING_EXCEPTIONS``: the two pipeline schedules, with
                their debt), and the declaration readers
                (``model/remat.py``, ``train/__init__.py``) import no layer
                and no kernel module — they read what a layer DECLARES
                (``model/declare.py``), so a new layer edits neither.
metric-docs     every ``hbnlp_*`` metric name registered via a registry
                ``counter()``/``gauge()``/``histogram()`` call must have a
                row in docs/OBSERVABILITY.md's catalog (mirrors the
                config-docs rule; an undocumented series is invisible to
                the operator reading the doc).
==============  ============================================================

Suppression: put ``graft-lint: allow[<rule>]`` in a comment on the
offending line or the line above.  Suppressions are part of the diff and
review like any other code.
"""
from __future__ import annotations

import ast
import dataclasses
import os
import re
import typing

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG_PY = os.path.join(REPO, "homebrewnlp_tpu", "config.py")
CONFIG_MD = os.path.join(REPO, "docs", "CONFIG.md")

#: source trees the repo rules run over (tests/ excluded: harness code
#: times walls and seeds rngs per-test by its own conventions)
LINT_SUBDIRS = ("homebrewnlp_tpu", "scripts")

#: ``file::enclosing-function`` of every ``donate_argnums`` jit site,
#: mapped to the HLO-audit entry point(s) covering it
#: (analysis/entry_points.py).  Adding a donated jit?  Register it here AND
#: give it a lowering + donation audit there — donation is a compiled-
#: artifact property and regresses silently (docs/STATIC_ANALYSIS.md).
DONATED_JIT_REGISTRY: typing.Dict[str, str] = {
    # the donated train step: audited as "train_step"
    "homebrewnlp_tpu/train/__init__.py::_build_step": "train_step",
    # the stepped decode chunk + its cache-initialising first chunk:
    # audited as "decode_chunk_step" and "prefill_entry_step"
    "homebrewnlp_tpu/infer/sampler.py::_jit_sampler":
        "decode_chunk_step, prefill_entry_step",
    # the audit harness's own lowering of the decode step
    "homebrewnlp_tpu/analysis/entry_points.py::lower_decode_step":
        "decode_chunk_step (harness)",
    "homebrewnlp_tpu/analysis/entry_points.py::lower_prefill_entry":
        "prefill_entry_step (harness)",
    # the Engine's single chunk-program builder: every composition in
    # infer/engine.py ENGINE_PROGRAMS (plain / spec / paged /
    # spec-on-paged, each with init/admit/plain phases) lowers through
    # this ONE jit site and is audited under its registry name
    "homebrewnlp_tpu/infer/engine.py::_chunk_jit":
        "engine_chunk_step, spec_chunk_step, paged_chunk_step, "
        "spec_paged_chunk_step",
}

#: the Engine no-fork invariant (the ``engine-registry`` rule): donated
#: jit sites under ``infer/`` build chunk programs, and the ONLY legal
#: chunk-program builders are the Engine's single site and the batch
#: sampler's.  A new donated jit anywhere else in ``infer/`` is a forked
#: carry layout escaping the composition registry — add a row to
#: ``ENGINE_PROGRAMS`` instead of a program.
ENGINE_REGISTRY_SITES = frozenset((
    "homebrewnlp_tpu/infer/engine.py::_chunk_jit",
    "homebrewnlp_tpu/infer/sampler.py::_jit_sampler",
))


#: mesh-axis names the mesh-axis-literal rule polices (mirrors
#: core/sharding.py MESH_AXES — mirrored, not imported: this module must
#: stay importable without jax; tests pin the two in sync)
MESH_AXIS_NAMES = frozenset(("data", "pipe", "model", "sequence"))

#: files/dirs allowed to spell axis names literally: the axis-DEFINING
#: layers.  ``config.py`` derives ``mesh_shape``/``layout`` from knobs and
#: cannot import core.sharding (import cycle), so it stays a defining
#: layer alongside shardlib and the manual-collective kernels
MESH_AXIS_ALLOWED = ("homebrewnlp_tpu/parallel/",
                     "homebrewnlp_tpu/core/sharding.py",
                     "homebrewnlp_tpu/config.py")

#: the layers that build the step program: the env-knob rule's scope
ENV_KNOB_DIRS = tuple(f"homebrewnlp_tpu/{d}/" for d in
                      ("model", "parallel", "train", "optim", "core"))

#: environment reads the env-knob rule still admits there -> the debt
#: (ROADMAP.md) whose payment deletes the entry
ENV_KNOB_ALLOWED: typing.Dict[str, str] = {
    "HBNLP_MAP_MIXER_INTERPRET":
        "runs the map-mixer kernel in interpret mode off the TPU; goes "
        "with the kernel (S1) or when its tests pass interpret themselves",
}

#: the layering rule: the lower layers, what they must not import, and the
#: modules that still do -> the debt (ROADMAP.md) that removes the entry
LOWER_DIRS = tuple(f"homebrewnlp_tpu/{d}/" for d in
                   ("core", "parallel", "telemetry", "optim"))
UPPER_PACKAGES = tuple(f"homebrewnlp_tpu.{d}" for d in
                       ("model", "train", "infer", "run"))
LAYERING_EXCEPTIONS: typing.Dict[str, str] = {
    "homebrewnlp_tpu/parallel/pipeline.py":
        "runs the model's revnet / momentum sequences a stage (D0(c))",
    "homebrewnlp_tpu/parallel/pipeline_1f1b.py":
        "runs the model's revnet / momentum sequences a stage (D0(c))",
}
#: the declaration readers, and all they may import of ``model/`` and
#: ``parallel/``: the package (``Model``), the declarations, the rule
DECLARATION_READERS = ("homebrewnlp_tpu/model/remat.py",
                       "homebrewnlp_tpu/train/__init__.py")
DECLARATION_READERS_MAY = frozenset((
    "homebrewnlp_tpu.model", "homebrewnlp_tpu.model.declare",
    "homebrewnlp_tpu.model.remat"))

_ENV_MAPPINGS = ("os.environ", "environ")
_ENV_READ_CALLS = ("os.getenv", "getenv") + tuple(
    f"{m}.{f}" for m in _ENV_MAPPINGS for f in ("get", "setdefault", "pop"))

#: callee basenames whose string arguments are axis names
_AXIS_CALLEES = ("PartitionSpec", "NamedSharding", "P",
                 "psum", "pmean", "pmax", "pmin", "ppermute", "pshuffle",
                 "all_gather", "psum_scatter", "axis_index", "all_to_all")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One violation: ``rule``, ``entry`` (``relpath:line``), ``message``."""
    rule: str
    entry: str
    message: str

    def __str__(self):
        return f"[{self.rule}] {self.entry}: {self.message}"


def _suppressed(lines: typing.Sequence[str], lineno: int, rule: str) -> bool:
    for ln in (lineno, lineno - 1):
        if 1 <= ln <= len(lines) and f"graft-lint: allow[{rule}]" in lines[ln - 1]:
            return True
    return False


# ---- per-file rules --------------------------------------------------------

def _dotted(node: ast.AST) -> str:
    """Best-effort dotted name of an expression (``np.random.default_rng``)."""
    parts: typing.List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


class _FileVisitor(ast.NodeVisitor):
    def __init__(self, rel: str, lines: typing.Sequence[str]):
        self.rel = rel
        self.lines = lines
        self.fn_stack: typing.List[str] = []
        self.findings: typing.List[Finding] = []
        self.axis_exempt = any(
            rel == allow or (allow.endswith("/") and rel.startswith(allow))
            for allow in MESH_AXIS_ALLOWED)
        #: names bound to the time MODULE (``import time [as t]``) and to
        #: the time.time FUNCTION (``from time import time [as now]``) —
        #: the wallclock rule must catch every spelling, not just
        #: ``time.time()``
        self.time_modules: typing.Set[str] = {"time"}
        self.time_funcs: typing.Set[str] = set()

    def visit_Import(self, node: ast.Import):
        for alias in node.names:
            if alias.name == "time":
                self.time_modules.add(alias.asname or "time")
        self._layering(node, [alias.name for alias in node.names])
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom):
        if node.module == "time":
            for alias in node.names:
                if alias.name == "time":
                    self.time_funcs.add(alias.asname or "time")
        # the absolute name of what a relative import names
        package = self.rel[:-3].split("/")[:-1]
        base = package[:len(package) - node.level + 1] if node.level else []
        base = ".".join(base + ([node.module] if node.module else []))
        self._layering(node, [base] + [f"{base}.{alias.name}"
                                       for alias in node.names])
        self.generic_visit(node)

    # -- layering ------------------------------------------------------------

    def _layering(self, node: ast.AST, imported: typing.Sequence[str]):
        """``imported``: absolute dotted names, modules or attributes of
        modules (an attribute matches no package and no file)."""
        def under(name, packages):
            return any(name == p or name.startswith(p + ".")
                       for p in packages)

        def is_module(name):
            path = os.path.join(REPO, *name.split("."))
            return os.path.isfile(path + ".py") or os.path.isdir(path)

        if self.rel.startswith(LOWER_DIRS) \
                and self.rel not in LAYERING_EXCEPTIONS:
            for name in imported:
                if under(name, UPPER_PACKAGES):
                    self._add("layering", node,
                              f"{name} imported from a lower layer — move "
                              "what is shared down (core/), or name the "
                              "module with its debt in analysis/ast_lint.py "
                              "LAYERING_EXCEPTIONS")
                    break
        if self.rel in DECLARATION_READERS:
            for name in imported:
                if under(name, ("homebrewnlp_tpu.model",
                                "homebrewnlp_tpu.parallel")) \
                        and name not in DECLARATION_READERS_MAY \
                        and is_module(name):
                    self._add("layering", node,
                              f"{name} imported by a declaration reader — "
                              "read what the layer declares "
                              "(model/declare.py) instead of naming it")

    def _add(self, rule: str, node: ast.AST, message: str):
        if not _suppressed(self.lines, node.lineno, rule):
            self.findings.append(
                Finding(rule, f"{self.rel}:{node.lineno}", message))

    def visit_FunctionDef(self, node):
        self.fn_stack.append(node.name)
        self.generic_visit(node)
        self.fn_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def _is_wallclock(self, name: str) -> bool:
        mod, _, attr = name.rpartition(".")
        return ((attr == "time" and mod in self.time_modules)
                or (not mod and name in self.time_funcs))

    # -- mesh-axis-literal ---------------------------------------------------

    def _axis_literal(self, node: ast.AST, context: str):
        """Flag every mesh-axis-name string constant in ``node``'s subtree
        (axis-consuming position established by the caller)."""
        if self.axis_exempt:
            return
        for sub in ast.walk(node):
            if (isinstance(sub, ast.Constant) and isinstance(sub.value, str)
                    and sub.value in MESH_AXIS_NAMES):
                self._add("mesh-axis-literal", sub,
                          f'hardcoded mesh axis "{sub.value}" in {context} — '
                          "an axis rename silently strands this site; use "
                          "the core.sharding constants (DATA_AXIS, "
                          "MODEL_AXIS, SEQUENCE_AXIS, PIPE_AXIS) or mark "
                          "the line `graft-lint: allow[mesh-axis-literal]`")

    # -- env-knob ------------------------------------------------------------

    def _env_read(self, node: ast.AST, key: typing.Optional[ast.AST]):
        if not self.rel.startswith(ENV_KNOB_DIRS):
            return
        name = key.value if isinstance(key, ast.Constant) else None
        if name not in ENV_KNOB_ALLOWED:
            self._add("env-knob", node,
                      f"environment read of {name or 'a computed name'!r} in "
                      "a layer that builds the step program — choose from "
                      "the configuration or from what the code observes "
                      "(shapes, the device), or add the name with its debt "
                      "to analysis/ast_lint.py ENV_KNOB_ALLOWED")

    def visit_Subscript(self, node: ast.Subscript):
        if "mesh" in _dotted(node.value).lower():
            self._axis_literal(node.slice, "a mesh-shape subscript")
        if _dotted(node.value) in _ENV_MAPPINGS:
            self._env_read(node, node.slice)
        self.generic_visit(node)

    def visit_Compare(self, node: ast.Compare):
        if any(isinstance(op, (ast.In, ast.NotIn)) for op in node.ops):
            others = " ".join(_dotted(c) for c in node.comparators)
            if "axis_names" in others or "mesh_shape" in others \
                    or "mesh" in others.lower():
                self._axis_literal(node.left, "an axis-membership test")
            # `"X" in os.environ` is the simplest on/off switch there is
            if any(_dotted(c) in _ENV_MAPPINGS for c in node.comparators):
                self._env_read(node, node.left)
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call):
        name = _dotted(node.func)
        base = name.split(".")[-1]
        if base in _AXIS_CALLEES:
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                self._axis_literal(arg, f"a {base}(...) argument")
        elif base == "get" and "mesh" in name.lower() and node.args:
            self._axis_literal(node.args[0], "a mesh-shape .get() key")
        if name in _ENV_READ_CALLS:
            self._env_read(node, node.args[0] if node.args else None)
        if self._is_wallclock(name):
            self._add("wallclock", node,
                      "time.time() is wall clock — an NTP step corrupts "
                      "elapsed-time arithmetic; use time.monotonic() for "
                      "durations (epoch stamps: add a "
                      "`graft-lint: allow[wallclock]` marker)")
        elif name.endswith("default_rng") and not node.args and not node.keywords:
            self._add("unseeded-rng", node,
                      "np.random.default_rng() without a seed is "
                      "unreproducible; seed it (params.data_seed / an "
                      "explicit constant) or mark the line "
                      "`graft-lint: allow[unseeded-rng]`")
        elif name.split(".")[-1] in ("jit", "pjit") and any(
                kw.arg in ("donate_argnums", "donate_argnames")
                for kw in node.keywords):
            fn = self.fn_stack[-1] if self.fn_stack else "<module>"
            key = f"{self.rel}::{fn}"
            if key not in DONATED_JIT_REGISTRY:
                self._add("donated-jit", node,
                          f"donated jit site {key!r} is not in "
                          "analysis/ast_lint.py DONATED_JIT_REGISTRY — "
                          "register it and give it an HLO donation audit "
                          "(analysis/entry_points.py), or the donation can "
                          "silently stop aliasing")
            if (self.rel.startswith("homebrewnlp_tpu/infer/")
                    and key not in ENGINE_REGISTRY_SITES):
                self._add("engine-registry", node,
                          f"donated jit site {key!r} builds a chunk program "
                          "outside the Engine registry — serving carries "
                          "compose through infer/engine.py _chunk_jit "
                          "(add an ENGINE_PROGRAMS row, not a forked "
                          "program; docs/SERVING.md 'Engine architecture')")
        self.generic_visit(node)


def lint_source(rel: str, source: str) -> typing.List[Finding]:
    """Per-file rules over one source blob (``rel`` is the repo-relative
    path used in findings and registry keys)."""
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Finding("parse", f"{rel}:{e.lineno}", f"syntax error: {e.msg}")]
    visitor = _FileVisitor(rel, source.splitlines())
    visitor.visit(tree)
    return visitor.findings


# ---- config-docs rule ----------------------------------------------------

#: internal bookkeeping assigned in the defaults section that is NOT a
#: config knob (everything else there is)
INTERNAL = {"unknown_config_keys"}


def config_knobs(source: str) -> typing.List[str]:
    """``self.X = default`` names from ModelParameter.__init__, up to the
    unknown-key update loop."""
    tree = ast.parse(source)
    init = None
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == "ModelParameter":
            init = next(n for n in node.body
                        if isinstance(n, ast.FunctionDef)
                        and n.name == "__init__")
            break
    if init is None:
        raise AssertionError("ModelParameter.__init__ not found")
    knobs = []
    for stmt in init.body:
        if isinstance(stmt, ast.For):
            # the `for k, v in config.items()` loop ends the defaults
            # section; later assignments are validation/derivation
            break
        if isinstance(stmt, ast.Assign):
            targets = stmt.targets
        elif isinstance(stmt, ast.AnnAssign):
            targets = [stmt.target]
        else:
            continue
        for t in targets:
            if (isinstance(t, ast.Attribute) and isinstance(t.value, ast.Name)
                    and t.value.id == "self" and not t.attr.startswith("_")
                    and t.attr not in INTERNAL):
                knobs.append(t.attr)
    if len(knobs) < 50:  # the reference schema alone has ~150
        raise AssertionError(f"only {len(knobs)} knobs parsed — the "
                             "defaults-section detection broke")
    return knobs


def documented_keys(md: str) -> typing.Set[str]:
    """Keys of every ``| `name` | ...`` table row."""
    return set(re.findall(r"^\|\s*`([A-Za-z_][A-Za-z_0-9]*)`", md, re.M))


def missing_knobs(config_py: str = CONFIG_PY,
                  config_md: str = CONFIG_MD) -> typing.List[str]:
    with open(config_py) as f:
        knobs = config_knobs(f.read())
    with open(config_md) as f:
        documented = documented_keys(f.read())
    return sorted(set(k for k in knobs if k not in documented))


def config_docs_findings(config_py: str = CONFIG_PY,
                         config_md: str = CONFIG_MD) -> typing.List[Finding]:
    return [Finding("config-docs", "docs/CONFIG.md",
                    f"config knob `{k}` has no docs/CONFIG.md table row "
                    "(add `| `" + k + "` | <default> | <meaning> |`)")
            for k in missing_knobs(config_py, config_md)]


# ---- metric-docs rule (mirrors config-docs) ---------------------------------

OBSERVABILITY_MD = os.path.join(REPO, "docs", "OBSERVABILITY.md")

#: registry factory method names whose first string argument is a metric
#: name (telemetry/registry.py Registry API)
_METRIC_METHODS = frozenset(("counter", "gauge", "histogram"))
#: what a layer declares a metric with (model/declare.py): the name is any
#: literal argument, the trainer registers it from the declaration
_METRIC_DECLARATIONS = frozenset(("Stat", "Fact"))
_METRIC_PREFIX = "hbnlp_"


def registered_metrics(root: str = REPO,
                       subdirs: typing.Sequence[str] = LINT_SUBDIRS
                       ) -> typing.List[typing.Tuple[str, str, int]]:
    """Every ``hbnlp_*`` metric registered through a literal first argument
    of a ``counter``/``gauge``/``histogram`` call, or declared by a layer as
    a literal argument of ``Stat(...)`` / ``Fact(...)``: ``(name, rel,
    lineno)``.  Names passed through variables (e.g. ``SPAN_METRIC``) are
    out of scope — the rule polices the literal idioms every layer uses."""
    out: typing.List[typing.Tuple[str, str, int]] = []
    for path, rel in iter_source_files(root, subdirs):
        with open(path) as f:
            src = f.read()
        try:
            tree = ast.parse(src)
        except SyntaxError:
            continue
        lines = src.splitlines()
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) \
                    or _suppressed(lines, node.lineno, "metric-docs"):
                continue
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _METRIC_METHODS:
                names = node.args[:1]
            elif _dotted(node.func).split(".")[-1] in _METRIC_DECLARATIONS:
                names = node.args
            else:
                continue
            out += [(arg.value, rel, node.lineno) for arg in names
                    if isinstance(arg, ast.Constant)
                    and isinstance(arg.value, str)
                    and arg.value.startswith(_METRIC_PREFIX)]
    return out


def documented_metrics(md: str) -> typing.Set[str]:
    """Every backticked ``hbnlp_*`` name in the doc — generous on purpose:
    a name mentioned anywhere in OBSERVABILITY.md counts as documented."""
    return set(re.findall(r"`(hbnlp_[A-Za-z0-9_]+)`", md))


def metric_docs_findings(root: str = REPO,
                         subdirs: typing.Sequence[str] = LINT_SUBDIRS,
                         obs_md: str = OBSERVABILITY_MD
                         ) -> typing.List[Finding]:
    try:
        with open(obs_md) as f:
            documented = documented_metrics(f.read())
    except OSError:
        documented = set()
    findings, seen = [], set()
    for name, rel, lineno in registered_metrics(root, subdirs):
        if name in documented or name in seen:
            continue
        seen.add(name)
        findings.append(Finding(
            "metric-docs", f"{rel}:{lineno}",
            f"metric `{name}` has no docs/OBSERVABILITY.md catalog row "
            f"(add `| `{name}` | <type> | <labels> | <layer> | <meaning> |`"
            " or mark the line `graft-lint: allow[metric-docs]`)"))
    return findings


# ---- repo walk -------------------------------------------------------------

def iter_source_files(root: str = REPO,
                      subdirs: typing.Sequence[str] = LINT_SUBDIRS
                      ) -> typing.Iterator[typing.Tuple[str, str]]:
    """Yield ``(abs_path, repo_relative_path)`` for every lintable .py."""
    for sub in subdirs:
        base = os.path.join(root, sub)
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fname in sorted(filenames):
                if fname.endswith(".py"):
                    path = os.path.join(dirpath, fname)
                    yield path, os.path.relpath(path, root)


def lint_repo(root: str = REPO,
              subdirs: typing.Sequence[str] = LINT_SUBDIRS,
              config_docs: bool = True,
              metric_docs: bool = True) -> typing.List[Finding]:
    """All AST rules over the repo: per-file rules + the config-docs and
    metric-docs coverage rules."""
    findings: typing.List[Finding] = []
    for path, rel in iter_source_files(root, subdirs):
        with open(path) as f:
            findings += lint_source(rel, f.read())
    if config_docs:
        findings += config_docs_findings(
            os.path.join(root, "homebrewnlp_tpu", "config.py"),
            os.path.join(root, "docs", "CONFIG.md"))
    if metric_docs:
        findings += metric_docs_findings(
            root, subdirs, os.path.join(root, "docs", "OBSERVABILITY.md"))
    return findings
