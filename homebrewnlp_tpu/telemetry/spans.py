"""Span API: ``with span("train/checkpoint_save"): ...`` marks a block on the
PROFILER's clock and, where the site records, in the registry's span
histogram.

A span is a ``jax.profiler.TraceAnnotation`` of its name: while a profile is
being captured (``profile_steps``, SIGUSR2, a harness's own
``start_trace``) it lands on the calling thread's line of the ``/host:CPU``
plane, on the same time base as the ``/device:TPU:*`` planes, so a host
span can be laid against a device gap; while none is, it costs well under
a microsecond.  Spans nest by time on one thread — that is the parent /
child relation a trace reducer needs; there are no ids.

Recording rule (docs/OBSERVABILITY.md): set-up and rare sites observe
``hbnlp_span_seconds{span=...}`` always; per-step sites pass
``record=params.telemetry_enabled``, so a run with telemetry off makes zero
registry calls on the step hot path.  Every site writes the annotation.

A site may also have a LISTENER, told when the block opens and closes with
the times the span read anyway: the step clock (``step_clock.py``), which is
how the per-step sites and ``train()``'s rare ones hand it their durations.
The site passes it (``listener=``), or — where the site knows no trainer,
the prefetcher's ``data/next`` — :func:`listen` names it for the process.  A
site with a listener reads the clock whether it records or not.

Stdlib-only at import, like the registry: ``jax`` is looked up in
``sys.modules`` when a span opens, never imported here — a process that has
not loaded jax (the HTTP child) has no profiler to annotate.
"""
from __future__ import annotations

import sys
import time
import typing

# NOT `from . import registry`: the package __init__ rebinds its `registry`
# attribute to the registry() FUNCTION, shadowing the submodule
from .registry import Registry, registry as _process_registry

#: one histogram for every span, labelled by span name — span names may
#: contain '/', which is legal in a label value but not a metric name
SPAN_METRIC = "hbnlp_span_seconds"


class Phase:
    """A pre-bound histogram child for callers that own the clock:
    ``rec(t0, dt)`` is the whole cost."""

    __slots__ = ("_child", "name")

    def __init__(self, name: str, registry: typing.Optional[Registry] = None):
        r = registry if registry is not None else _process_registry()
        self._child = r.histogram(
            SPAN_METRIC, "span duration in seconds", ("span",)).labels(name)
        self.name = name

    def rec(self, start_s: float, duration_s: float):
        self._child.observe(duration_s)


def _annotation(name: str, **metadata):
    """The profiler's annotation of ``name`` (``metadata``: its stats in the
    trace), or None in a process that has not imported jax."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    return jax.profiler.TraceAnnotation(name, **metadata)


#: span name -> the process's listener of that site (``listen``)
_LISTENERS: typing.Dict[str, typing.Any] = {}


def listen(name: str, listener) -> None:
    """``listener.span_opened(name, t)`` / ``.span_closed(name, t0, t1)``
    (the span's own clock, seconds) for every span ``name`` of this process
    that is not given a listener of its own, in place of the one named
    before."""
    _LISTENERS[name] = listener


class _Span:
    __slots__ = ("name", "_registry", "_record", "_clock", "_t0", "_ann",
                 "_listener", "_metadata")

    def __init__(self, name, registry, record, clock, listener, metadata):
        self.name = name
        self._registry = registry
        self._record = record
        self._clock = clock
        self._listener = listener
        self._metadata = metadata
        self._ann = None

    def __enter__(self):
        self._ann = _annotation(self.name, **self._metadata)
        if self._ann is not None:
            self._ann.__enter__()
        if self._listener is None:
            self._listener = _LISTENERS.get(self.name)
        if self._record or self._listener is not None:
            self._t0 = self._clock()
            if self._listener is not None:
                self._listener.span_opened(self.name, self._t0)
        return self

    def __exit__(self, *exc):
        if self._record or self._listener is not None:
            t1 = self._clock()
            if self._listener is not None:
                self._listener.span_closed(self.name, self._t0, t1)
            if self._record:
                Phase(self.name, self._registry).rec(self._t0, t1 - self._t0)
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        return False


def span(name: str, registry: typing.Optional[Registry] = None,
         clock: typing.Callable[[], float] = time.monotonic,
         record: bool = True, listener=None, **metadata) -> _Span:
    """Context manager marking a block: a trace annotation always (with
    ``metadata`` as its stats: ``step=n``), an observation in the span
    histogram when ``record`` (per-step sites pass ``telemetry_enabled``),
    and its times to ``listener`` (or the process's for this name).  A site
    whose block opens in one call and closes in another (the prefetcher's
    first batch) calls ``__enter__`` / ``__exit__`` itself, on one thread."""
    return _Span(name, registry, record, clock, listener, metadata)
