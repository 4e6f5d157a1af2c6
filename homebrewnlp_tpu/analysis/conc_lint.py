"""Host-concurrency audit (graft-lint ``--conc``, half 1 + 3).

The serving/elastic control plane's guarantees (exactly-one-answer,
never-a-500, refcount conservation, lease liveness) live in host Python
threads, and their correctness rests on lock discipline that — unlike
the compiled-artifact contracts the other graft-lint halves pin — was
enforced only by convention.  This module makes the convention a checked
declaration:

* ``GUARDED_BY`` — per audited class, which lock guards which
  attributes.  Rule ``lock-guard`` flags any access to a guarded
  attribute outside a ``with <lock>`` scope on the same object
  (``__init__`` is exempt: attribute establishment precedes sharing).
  Mode ``"rw"`` checks reads and writes; ``"w"`` checks writes only —
  for benignly-racy monotonic reads (``Replica.inflight`` load-balance
  hints) where a torn read degrades a heuristic, never an invariant.
* Rule ``lock-blocking`` — blocking calls (file IO, ``time.sleep``,
  subprocess, sockets/urlopen, queue get/put, IPC recv/send) inside any
  ``with <...lock>`` scope: a blocked lock-holder stalls every thread
  behind it (and a flush path that blocks under the recorder lock stalls
  the signal handler that shares it).
* Rule ``lock-order`` — nested ``with``-lock scopes build a cross-module
  acquisition graph; a cycle is a deadlock the OS scheduler will
  eventually find.  The graph merges three views: this static pass, the
  interleaving explorer's observed edges (``analysis/interleave.py``),
  and opt-in runtime traces from real marker-suite runs
  (``utils/locks.py``, ``HBNLP_LOCK_TRACE``).
* Rule ``thread-hygiene`` — every ``threading.Thread`` needs an explicit
  ``name=`` (forensics blackbox events carry the thread name) and a
  deliberate ``daemon=`` choice; a ``daemon=False`` thread additionally
  needs a ``join`` somewhere on the file's exit paths.

Same idiom as ``ast_lint``: stdlib-only, ``Finding`` rows, rule-scoped
``graft-lint: allow[rule]`` suppressions on the flagged line or the line
above.  Onboarding protocol for new guarded classes is documented in
docs/STATIC_ANALYSIS.md 'Concurrency audit'.
"""
from __future__ import annotations

import ast
import collections
import glob as _glob
import json
import os
import typing

from .ast_lint import (Finding, LINT_SUBDIRS, REPO, _dotted, _suppressed,
                       iter_source_files)

__all__ = [
    "GUARDED_BY", "lint_source", "lint_repo_conc", "order_findings",
    "registry_findings", "explorer_findings", "load_trace_edges",
    "trace_findings",
]


# ---------------------------------------------------------------- registry

#: "relpath::Class" -> {"lock": attr, "guards": {attr: "rw"|"w"},
#: "aliases": (attrs,)} — aliases are lock-sharing handles (a Condition
#: built over the same lock).  Declaring a class here is a CONTRACT: the
#: lint enforces it forever after (onboarding protocol:
#: docs/STATIC_ANALYSIS.md 'Concurrency audit').  Deliberately-unlocked
#: attrs stay undeclared with the reason recorded here:
#: ``_Metric._children`` (racing creators build equal children; last
#: write wins into the same ``_series`` slot) and ``Router._last_index_sync``
#: (poll-loop throttle; a torn read costs one extra best-effort scrape).
GUARDED_BY: typing.Dict[str, dict] = {
    "homebrewnlp_tpu/infer/router.py::Replica": {
        "lock": "_lock",
        "guards": {"inflight": "w", "requests": "w", "failures": "w"},
    },
    "homebrewnlp_tpu/infer/router.py::GlobalPrefixIndex": {
        "lock": "_lock",
        "guards": {"_map": "rw", "_gen": "rw"},
    },
    "homebrewnlp_tpu/infer/router.py::Router": {
        "lock": "_lock",
        "guards": {"_affinity": "rw"},
    },
    "homebrewnlp_tpu/telemetry/events.py::FlightRecorder": {
        "lock": "_lock",  # RLock: the SIGUSR2 handler re-enters flush
        "guards": {"_events": "rw", "_seq": "rw", "_dirty": "rw",
                   "_last_flush": "rw", "model_path": "w", "tag": "w"},
    },
    "homebrewnlp_tpu/telemetry/registry.py::_Metric": {
        "lock": "_lock",
        "guards": {"_series": "rw"},
    },
    "homebrewnlp_tpu/telemetry/registry.py::Registry": {
        "lock": "_lock",
        "guards": {"_metrics": "rw"},
    },
    "homebrewnlp_tpu/distributed/async_checkpoint.py::AsyncCheckpointer": {
        "lock": "_lock",
        "aliases": ("_idle",),  # Condition(self._lock): same mutex
        "guards": {"_error": "rw", "_inflight": "rw"},
    },
}


def registry_findings(root: str = REPO,
                      registry: typing.Dict[str, dict] = GUARDED_BY
                      ) -> typing.List[Finding]:
    """Rule ``conc-registry``: every GUARDED_BY key must point at a real
    file, class, and lock attribute — a stale entry silently audits
    nothing."""
    out = []
    for key, spec in registry.items():
        rel, _, cls = key.partition("::")
        path = os.path.join(root, rel)
        if not os.path.exists(path):
            out.append(Finding("conc-registry", key,
                               f"file {rel} does not exist"))
            continue
        with open(path, encoding="utf-8") as f:
            source = f.read()
        try:
            tree = ast.parse(source)
        except SyntaxError as e:
            out.append(Finding("conc-registry", key,
                               f"cannot parse {rel}: {e}"))
            continue
        node = next((n for n in ast.walk(tree)
                     if isinstance(n, ast.ClassDef) and n.name == cls),
                    None)
        if node is None:
            out.append(Finding("conc-registry", key,
                               f"class {cls} not found in {rel}"))
            continue
        lock = spec.get("lock", "_lock")
        assigned = {t.attr for n in ast.walk(node)
                    for t in ast.walk(n)
                    if isinstance(t, ast.Attribute)
                    and isinstance(t.ctx, ast.Store)
                    and _dotted(t.value) == "self"}
        for attr in [lock, *spec.get("aliases", ()),
                     *spec.get("guards", {})]:
            if attr not in assigned:
                out.append(Finding(
                    "conc-registry", key,
                    f"attribute {attr!r} is never assigned on "
                    f"self in class {cls}"))
    return out


# ------------------------------------------------------- per-file analysis

#: pure path helpers on the ``utils.fs`` alias — everything else on
#: ``fs.`` is filesystem IO
_FS_PURE = {"join", "basename", "dirname", "split", "splitext"}


def _blocking_reason(call: ast.Call) -> typing.Optional[str]:
    """Name of the blocking primitive this call hits, or None."""
    d = _dotted(call.func)
    if not d:
        return None
    parts = d.split(".")
    last = parts[-1]
    if d in ("time.sleep", "os.system", "open"):
        return d
    if last == "urlopen":
        return d
    if "subprocess" in parts[:-1] and last in (
            "run", "call", "check_call", "check_output", "Popen"):
        return d
    if parts[0] == "socket" and last in ("create_connection", "socket"):
        return d
    if parts[-2:-1] == ["fs"] and last not in _FS_PURE:
        return d
    if last in ("open_",):
        return d
    if last in ("get", "put", "get_nowait", "put_nowait") \
            and len(parts) >= 2 and ("queue" in parts[-2].lower()
                                     or parts[-2] in ("q", "_q")):
        return d
    if last in ("recv", "send", "sendall", "connect", "accept") \
            and len(parts) >= 2 and any(
                s in parts[-2].lower() for s in ("sock", "conn", "pipe")):
        return d
    if last == "join" and len(parts) >= 2 \
            and "thread" in parts[-2].lower():
        return d
    return None


def _lock_names_for(rel: str,
                    registry: typing.Dict[str, dict]) -> typing.Set[str]:
    """Lock + alias attribute names registered for ``rel`` (the
    ``lock-blocking``/``lock-order`` passes also match any name
    containing 'lock')."""
    names: typing.Set[str] = set()
    for key, spec in registry.items():
        if key.partition("::")[0] == rel:
            names.add(spec.get("lock", "_lock"))
            names.update(spec.get("aliases", ()))
    return names


class _ConcVisitor:
    """One file's lock-discipline walk.

    Tracks, per function, the set of dotted PREFIXES currently holding
    their lock (``with self._lock`` holds prefix ``self``; ``with
    m._lock`` holds ``m``) — a guarded access ``<prefix>.<attr>`` is
    legal only while its prefix holds.  Also collects nested-with
    acquisition edges and every blocking call made under any lock."""

    def __init__(self, rel: str, source: str,
                 registry: typing.Dict[str, dict]):
        self.rel = rel
        self.lines = source.splitlines()
        self.registry = registry
        self.module = os.path.splitext(os.path.basename(rel))[0]
        #: union of guarded attrs across classes registered for this file
        self.guards: typing.Dict[str, str] = {}
        for key, spec in registry.items():
            if key.partition("::")[0] == rel:
                self.guards.update(spec.get("guards", {}))
        self.lock_attrs = _lock_names_for(rel, registry)
        self.findings: typing.List[Finding] = []
        self.edges: typing.Set[typing.Tuple[str, str]] = set()
        self.class_stack: typing.List[str] = []
        self.fn_stack: typing.List[str] = []

    # -- helpers -------------------------------------------------------------

    def _add(self, rule: str, node: ast.AST, message: str) -> None:
        if _suppressed(self.lines, node.lineno, rule):
            return
        entry = self.rel
        if self.class_stack or self.fn_stack:
            scope = ".".join(self.class_stack + self.fn_stack[-1:])
            entry = f"{self.rel}:{scope}"
        self.findings.append(
            Finding(rule, entry, f"line {node.lineno}: {message}"))

    def _lock_of(self, expr: ast.AST) -> typing.Optional[
            typing.Tuple[str, str]]:
        """``(holder_prefix, canonical_name)`` when ``expr`` is a lock
        acquisition context, else None.  Lock-ish = a registered
        lock/alias attr, or any name whose last segment contains
        'lock'."""
        d = _dotted(expr)
        if not d:
            return None
        parts = d.split(".")
        last = parts[-1]
        if last not in self.lock_attrs and "lock" not in last.lower():
            return None
        prefix = ".".join(parts[:-1])  # "" for module-level lock names
        if prefix == "self" and self.class_stack:
            canon = f"{self.class_stack[-1]}.{last}"
        elif prefix:
            canon = f"{self.module}.{d}"
        else:
            canon = f"{self.module}.{last}"
        return prefix, canon

    # -- walk ----------------------------------------------------------------

    def visit_module(self, tree: ast.Module) -> None:
        self._walk_body(tree.body, held_prefixes=set(), held_canon=[],
                        in_init=False)

    def _walk_body(self, body, held_prefixes, held_canon, in_init):
        for node in body:
            self._walk(node, held_prefixes, held_canon, in_init)

    def _walk(self, node, held_prefixes, held_canon, in_init):
        if isinstance(node, ast.ClassDef):
            self.class_stack.append(node.name)
            # a class body starts a fresh locking context
            self._walk_body(node.body, set(), [], False)
            self.class_stack.pop()
            return
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            self.fn_stack.append(node.name)
            init = in_init or node.name == "__init__"
            # a nested def runs LATER: locks held at definition time are
            # not held at call time
            self._walk_body(node.body, set(), [], init)
            self.fn_stack.pop()
            return
        if isinstance(node, (ast.With, ast.AsyncWith)):
            new_prefixes = set(held_prefixes)
            new_canon = list(held_canon)
            for item in node.items:
                lk = self._lock_of(item.context_expr)
                if lk is None:
                    continue
                prefix, canon = lk
                for outer in new_canon:
                    if outer != canon:
                        self.edges.add((outer, canon))
                new_prefixes.add(prefix)
                new_canon.append(canon)
            # the context expressions themselves evaluate BEFORE the lock
            # is held
            for item in node.items:
                self._scan_expr(item.context_expr, held_prefixes,
                                held_canon, in_init)
            self._walk_body(node.body, new_prefixes, new_canon, in_init)
            return
        # generic statement: scan expressions at this level, recurse into
        # compound-statement bodies with the same held set
        for field in ast.iter_fields(node):
            value = field[1]
            items = value if isinstance(value, list) else [value]
            for item in items:
                # excepthandler/match_case are statement CONTAINERS, not
                # statements: recurse so `with lock:` inside an except
                # block keeps its held context
                if isinstance(item, (ast.stmt, ast.excepthandler)) or \
                        type(item).__name__ == "match_case":
                    self._walk(item, held_prefixes, held_canon, in_init)
                elif isinstance(item, ast.AST):
                    self._scan_expr(item, held_prefixes, held_canon,
                                    in_init)

    def _scan_expr(self, expr, held_prefixes, held_canon, in_init):
        for node in ast.walk(expr):
            if isinstance(node, ast.Call):
                reason = _blocking_reason(node)
                if reason is not None and held_canon:
                    self._add(
                        "lock-blocking", node,
                        f"blocking call {reason}() while holding "
                        f"{held_canon[-1]} — a stalled holder blocks "
                        "every thread behind the lock")
            if isinstance(node, ast.Attribute) and not in_init:
                mode = self.guards.get(node.attr)
                if mode is None:
                    continue
                prefix = _dotted(node.value)
                if prefix is None or prefix in held_prefixes:
                    continue
                if mode == "w" and isinstance(node.ctx, ast.Load):
                    continue
                kind = ("write to" if not isinstance(node.ctx, ast.Load)
                        else "read of")
                self._add(
                    "lock-guard", node,
                    f"{kind} guarded attribute {prefix}.{node.attr} "
                    f"outside `with {prefix}.<lock>` (GUARDED_BY "
                    "declares it lock-protected)")
            if isinstance(node, ast.Call) \
                    and _dotted(node.func) in ("threading.Thread",
                                               "_threading.Thread",
                                               "Thread"):
                self._thread_hygiene(node)

    def _thread_hygiene(self, call: ast.Call) -> None:
        kwargs = {kw.arg: kw.value for kw in call.keywords
                  if kw.arg is not None}
        if "name" not in kwargs:
            self._add("thread-hygiene", call,
                      "threading.Thread without name= — forensics "
                      "blackbox events carry the thread name")
        if "daemon" not in kwargs:
            self._add("thread-hygiene", call,
                      "threading.Thread without an explicit daemon= "
                      "(the lifetime choice must be deliberate)")
            return
        daemon = kwargs["daemon"]
        if isinstance(daemon, ast.Constant) and daemon.value is False \
                and ".join(" not in "\n".join(self.lines):
            self._add("thread-hygiene", call,
                      "non-daemon thread with no join() in this file — "
                      "it outlives every exit path")


def _analyze(rel: str, source: str,
             registry: typing.Dict[str, dict]
             ) -> typing.Tuple[typing.List[Finding],
                               typing.Set[typing.Tuple[str, str]]]:
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Finding("parse", rel, f"syntax error: {e}")], set()
    v = _ConcVisitor(rel, source, registry)
    v.visit_module(tree)
    return v.findings, v.edges


def lint_source(rel: str, source: str,
                registry: typing.Optional[typing.Dict[str, dict]] = None
                ) -> typing.List[Finding]:
    """Single-source entry point (tests and negative controls): AST
    rules plus an ordering-cycle check over this source's own edges."""
    findings, edges = _analyze(
        rel, source, GUARDED_BY if registry is None else registry)
    return findings + order_findings(edges)


# ------------------------------------------------------------- lock order

def order_findings(edges: typing.Iterable[typing.Tuple[str, str]]
                   ) -> typing.List[Finding]:
    """Rule ``lock-order``: cycles in the merged acquisition graph.  One
    finding per distinct cycle, naming its lock sequence."""
    graph: typing.Dict[str, typing.Set[str]] = collections.defaultdict(set)
    for a, b in edges:
        graph[a].add(b)
    out = []
    seen_cycles: typing.Set[typing.Tuple[str, ...]] = set()
    # iterative DFS with an explicit path: small graphs, exhaustive walk
    for start in sorted(graph):
        stack = [(start, [start])]
        while stack:
            node, path = stack.pop()
            for nxt in sorted(graph.get(node, ())):
                if nxt == start:
                    cycle = tuple(sorted(path))
                    if cycle not in seen_cycles:
                        seen_cycles.add(cycle)
                        out.append(Finding(
                            "lock-order", " -> ".join(path + [start]),
                            "lock acquisition cycle — two threads "
                            "taking these locks in opposite order "
                            "deadlock"))
                elif nxt not in path:
                    stack.append((nxt, path + [nxt]))
    return out


# ------------------------------------------------- runtime trace checking

def load_trace_edges(trace_dir: str) -> typing.Set[
        typing.Tuple[str, str]]:
    """Acquisition-order edges observed by ``utils/locks.py`` traced
    runs: every ``lock_trace_*.jsonl`` row carries the lock acquired and
    the locks already held by that thread."""
    edges: typing.Set[typing.Tuple[str, str]] = set()
    for path in sorted(_glob.glob(
            os.path.join(trace_dir, "lock_trace_*.jsonl"))):
        try:
            with open(path, encoding="utf-8") as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        row = json.loads(line)
                    except json.JSONDecodeError:
                        continue  # torn tail line of a live writer
                    lock = row.get("lock")
                    for held in row.get("held") or ():
                        if lock and held and held != lock:
                            edges.add((str(held), str(lock)))
        except OSError:
            continue
    return edges


def trace_findings(trace_dir: str) -> typing.List[Finding]:
    """Cycle-check ONLY the observed runtime edges (the static pass
    merges them too; this is the standalone checker for a trace dir)."""
    return order_findings(load_trace_edges(trace_dir))


# ------------------------------------------------------ explorer coupling

def explorer_findings(seeds: typing.Optional[typing.Sequence[int]] = None,
                      edges: typing.Optional[set] = None
                      ) -> typing.List[Finding]:
    """Rule ``interleave``: run the scenario library under permuted
    schedules; every violated invariant is a finding.  Scenario prints
    (membership-change banners etc.) are swallowed — findings are the
    CLI's only output channel."""
    import contextlib
    import io

    from . import interleave

    out = []
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        violations = interleave.run_scenarios(
            seeds=seeds if seeds is not None else interleave.CONC_SEEDS,
            edges=edges)
    for name, seed, message in violations:
        out.append(Finding("interleave", f"{name}@seed{seed}", message))
    return out


# ------------------------------------------------------------- repo entry

def lint_repo_conc(root: str = REPO,
                   subdirs: typing.Sequence[str] = LINT_SUBDIRS,
                   registry: typing.Dict[str, dict] = GUARDED_BY,
                   extra_edges: typing.Iterable[
                       typing.Tuple[str, str]] = (),
                   trace_dir: typing.Optional[str] = None
                   ) -> typing.List[Finding]:
    """Static half of ``--conc``: AST rules over every source file, the
    registry validity check, and the ordering cycle check over static +
    ``extra_edges`` (explorer) + runtime-trace edges."""
    findings: typing.List[Finding] = []
    edges: typing.Set[typing.Tuple[str, str]] = set(extra_edges)
    for path, rel in iter_source_files(root, subdirs):
        with open(path, encoding="utf-8") as f:
            source = f.read()
        file_findings, file_edges = _analyze(rel, source, registry)
        findings.extend(file_findings)
        edges.update(file_edges)
    if trace_dir is None:
        trace_dir = os.environ.get("HBNLP_LOCK_TRACE", "")
    if trace_dir and os.path.isdir(trace_dir):
        edges.update(load_trace_edges(trace_dir))
    findings.extend(registry_findings(root, registry))
    findings.extend(order_findings(edges))
    return findings
