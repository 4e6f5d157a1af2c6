"""Readers of what the PROGRAM marks about itself (PR 23): its spans on the
profiler's clock, its registry's set-up and compile counters, and its named
scopes on the device trace.  ``lib/readers.py`` reads the harness's own
annotations; both stay until a benchmark issue retires one.

Every function takes the ``Run`` and returns a number, or ``None`` (with a
line in ``run.notes``) where the program has no such span or counter — as
the parent of PR 23 has not — or the trace no device plane.

The train driver runs the program in the reader's own process, so the
program's registry is read directly (``homebrewnlp_tpu.telemetry.snapshot``).
"""
from __future__ import annotations

import functools
import re
import typing

from ..trace import event_metadata
from ..trace import reduce as reduce_mod
from . import readers, stats

#: the program's per-step spans (homebrewnlp_tpu/train ``Trainer.step`` and
#: ``place_batch``, data/inputs.py ``Prefetcher.__next__``); they do not
#: nest in one another, so their idle covers add up
PROGRAM_SPANS = ("train/step_dispatch", "data/next", "data/place")
SPAN_METRIC = "hbnlp_span_seconds"
COMPILE_SECONDS = "hbnlp_compile_seconds_total"
#: collective instructions named in the notes, largest first
COLLECTIVES_LISTED = 12


# ---- the program's registry -------------------------------------------------

def snapshot() -> dict:
    from homebrewnlp_tpu import telemetry
    return telemetry.snapshot()


def span_seconds(run, name: str) -> typing.Optional[float]:
    """Total seconds the program observed under ``span=name``."""
    state = snapshot().get(SPAN_METRIC, {}).get("series", {}).get((name,))
    if state is None:
        run.notes.append(f"the program's registry holds no span {name!r}")
        return None
    return float(state["sum"])


def counter(run, metric: str) -> typing.Optional[float]:
    """A label-free counter of the program's registry."""
    value = snapshot().get(metric, {}).get("series", {}).get(())
    if value is None:
        run.notes.append(f"the program's registry holds no {metric}")
        return None
    return float(value)


def step_compile_seconds(run, phases: typing.Sequence[str]
                         ) -> typing.Optional[float]:
    """Seconds of ``hbnlp_compile_seconds_total`` in ``phases`` for the
    function behind the cell's ``programs.step`` (module ``jit_step_fn`` is
    function ``step_fn``).  Only that function: the step's own tracing
    already contains the traces of the jitted pieces inside it, and the
    benchmark's reference check compiles programs of its own."""
    series = snapshot().get(COMPILE_SECONDS, {}).get("series")
    if not series:
        run.notes.append(f"the program's registry holds no {COMPILE_SECONDS}")
        return None
    rx = re.compile(run.cell.spec["programs"]["step"])
    found = {k: v for k, v in series.items()
             if k[0] in phases and rx.search("jit_" + k[1])}
    if not found:
        run.notes.append(f"no compile event in phases {list(phases)} for a "
                         f"function matching {rx.pattern!r}")
        return None
    cache = sum(v for k, v in series.items() if k[0] == "cache_load")
    run.notes.append(
        "compile seconds of the step: "
        + ", ".join(f"{k[0]} {v:.3f}" for k, v in sorted(found.items()))
        + f"; cache_load of every program in the process {cache:.3f} "
          f"(inside the backend event)")
    return float(sum(found.values()))


# ---- the program's spans on the trace ---------------------------------------

@functools.lru_cache(maxsize=2)
def _load(path: str) -> reduce_mod.Trace:
    return reduce_mod.load(path)


def raw_trace(run) -> typing.Optional[reduce_mod.Trace]:
    """The run's trace as ``trace/reduce.load`` reads it, loaded once for
    all the readers of a run."""
    if not run.result.trace_path:
        return None
    return _load(run.result.trace_path)


def span_median_ms(run, name: str) -> typing.Optional[float]:
    """Median length of the host span ``name`` inside the traced window."""
    trace = raw_trace(run)
    if trace is None:
        return None
    try:
        lo, hi = reduce_mod.window_of(trace, run.result.trace_window)
    except ValueError as exc:
        run.notes.append(f"{name}: {exc}")
        return None
    hits = [e.seconds for e in trace.host
            if e.name == name and e.start >= lo and e.end <= hi]
    if not hits:
        run.notes.append(f"no host span {name!r} inside the traced window")
        return None
    run.notes.append(f"{name}: {len(hits)} spans in the traced window, "
                     f"max {max(hits) * 1e3:.4f} ms")
    return stats.median(hits) * 1e3


@functools.lru_cache(maxsize=2)
def _reduce_by_program(path: str, window: typing.Optional[str]
                       ) -> typing.Optional[dict]:
    try:
        return reduce_mod.reduce(_load(path), window, PROGRAM_SPANS)
    except ValueError:          # no device plane
        return None


def program_gap_share(run) -> typing.Optional[float]:
    """Device-idle time inside the program's spans over all idle time in
    the window, percent; each span's part goes to the notes."""
    if run.trace is None or not run.result.trace_path:
        return None
    trace = raw_trace(run)
    if not any(e.name in PROGRAM_SPANS for e in trace.host):
        run.notes.append(f"the trace holds none of the program's spans "
                         f"{list(PROGRAM_SPANS)}")
        return None
    reduced = _reduce_by_program(run.result.trace_path,
                                 run.result.trace_window)
    if reduced is None or not reduced["idle_s"]:
        return None
    idle = reduced["idle_by_span"]
    run.notes.append(
        f"idle {reduced['idle_s'] * 1e3:.4f} ms of the window, inside "
        + ", ".join(f"{n} {idle.get(n, 0.0) * 1e3:.4f} ms "
                    f"({100 * idle.get(n, 0.0) / reduced['idle_s']:.2f}%)"
                    for n in PROGRAM_SPANS))
    return readers.share(sum(idle.get(n, 0.0) for n in PROGRAM_SPANS),
                         reduced["idle_s"])


# ---- the model's scopes on the device trace ---------------------------------

def _scope_key(op_name: str) -> str:
    # the PROGRAM's folding, so the benchmark and docs/OBSERVABILITY.md name
    # scopes alike
    from homebrewnlp_tpu.analysis.cost_ledger import scope_key
    return scope_key(op_name)


@functools.lru_cache(maxsize=2)
def _tf_ops(path: str) -> typing.Optional[typing.Dict[str, str]]:
    planes = event_metadata.tf_ops(path)
    if not planes:
        return None
    # one SPMD program on every chip: the first plane names them all
    return planes[sorted(planes)[0]]


@functools.lru_cache(maxsize=2)
def _op_scopes(path: str) -> typing.Optional[typing.Dict[str, str]]:
    """``{instruction short name: scope}`` for the instructions that carry
    ``tf_op``; folded once for all the scope metrics of a run."""
    tf_op = _tf_ops(path)
    if tf_op is None:
        return None
    return {name: _scope_key(op) for name, op in tf_op.items()}


def scope_seconds(run) -> typing.Optional[typing.Dict[str, float]]:
    """``{scope: seconds}`` of device self time in the window (mean over
    the chips), ``unscoped`` for instructions without ``tf_op`` or whose
    path names no model scope."""
    if run.trace is None or not run.result.trace_path:
        return None
    scopes = _op_scopes(run.result.trace_path)
    if scopes is None:
        run.notes.append("the trace's device planes carry no tf_op stat")
        return None
    out: typing.Dict[str, float] = {}
    for name, seconds in run.trace["ops"].items():
        scope = scopes.get(name, "unscoped")
        out[scope] = out.get(scope, 0.0) + seconds
    return out


def scope_share(run, scope: str) -> typing.Optional[float]:
    """Device self time on instructions of ``scope`` over busy time,
    percent; ``None`` where the cell's model has no such scope."""
    scopes = scope_seconds(run)
    if scopes is None:
        return None
    if scope not in scopes:
        run.notes.append(f"no instruction of scope {scope!r} in the trace "
                         f"(scopes: {sorted(scopes)})")
        return None
    return readers.share(scopes[scope], run.trace["busy_s"])


def scope_attributed_share(run, top: int = 8) -> typing.Optional[float]:
    """Share of busy time on instructions whose scope is not ``unscoped``,
    percent.  Notes: every scope's share, the largest unscoped
    instructions, and each collective instruction with its scope and
    ``tf_op``."""
    scopes = scope_seconds(run)
    if scopes is None:
        return None
    busy = run.trace["busy_s"]
    tf_op = _tf_ops(run.result.trace_path)
    op_scopes = _op_scopes(run.result.trace_path)
    ops, labels = run.trace["ops"], run.trace["labels"]
    run.notes.append("scope shares of busy time: " + ", ".join(
        f"{s} {100 * v / busy:.2f}%"
        for s, v in sorted(scopes.items(), key=lambda kv: -kv[1])))
    loose = sorted(((v, n) for n, v in ops.items()
                    if op_scopes.get(n, "unscoped") == "unscoped"),
                   reverse=True)[:top]
    if loose:
        run.notes.append("largest unscoped instructions: " + "; ".join(
            f"{labels.get(n, n)} {100 * v / busy:.2f}% "
            f"(tf_op {tf_op.get(n, 'absent')!r})" for v, n in loose))
    collectives = sorted(((v, n) for n, v in ops.items()
                          if n.startswith(reduce_mod.COLLECTIVES)),
                         reverse=True)
    for seconds, name in collectives[:COLLECTIVES_LISTED]:
        run.notes.append(
            f"collective {labels.get(name, name)}: "
            f"{100 * seconds / busy:.2f}% of busy time, scope "
            f"{op_scopes.get(name, 'unscoped')}"
            f", tf_op {tf_op.get(name, 'absent')!r}")
    if len(collectives) > COLLECTIVES_LISTED:
        rest = collectives[COLLECTIVES_LISTED:]
        run.notes.append(f"{len(rest)} smaller collective instructions, "
                         f"{100 * sum(v for v, _ in rest) / busy:.2f}% of "
                         f"busy time together")
    return readers.share(busy - scopes.get("unscoped", 0.0), busy)
