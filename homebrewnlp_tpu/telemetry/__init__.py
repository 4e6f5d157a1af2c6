"""Telemetry subsystem (docs/OBSERVABILITY.md).

Small stdlib-only pieces every layer shares:

* ``registry`` — process-wide Counter/Gauge/Histogram table with labels,
  picklable ``snapshot()`` for IPC, Prometheus text-exposition and JSONL
  renderers (``GET /metrics`` is ``prometheus_text(snapshot())``).
* ``spans`` — ``with span("name"): ...``: a ``jax.profiler`` trace
  annotation (one clock with the device planes) and, where the site
  records, an observation in ``hbnlp_span_seconds``.
* ``compiles`` — jax's trace / lower / backend-compile / cache-load events
  as counters by phase and function.
* ``memory`` — the one reader of ``device.memory_stats()``: the chip's
  memory at the phase marks of a train run, as gauges, spans and
  flight-recorder events.
* ``step_clock`` — a bounded ring with one entry a call of ``Trainer.step``,
  always on: the dispatch, what the host did until the next one, the queue
  depth and when the device was first seen done; a stalled step is named
  with its cause in any run, traced or not.
* ``profiler`` — on-demand ``jax.profiler`` capture (SIGUSR2 or
  programmatic) written under ``model_path``.

Config knobs: ``telemetry_*`` in docs/CONFIG.md.  The train hot path makes
ZERO registry calls unless ``telemetry_enabled`` is set; rare-event layers
(storage retries, checkpoint IO, serving decode rounds) record always —
their cadence is storage/request-bound, never per-step.
"""
from . import events, memory, step_clock, tracectx
from .buildinfo import build_info, register_build_info
from .compiles import install_compile_listener
from .events import FlightRecorder, RotatingJsonl
from .profiler import OnDemandProfiler, start_capture
from .registry import (Registry, jsonl_line, merge_snapshots,
                       prometheus_text, registry, set_constant_labels,
                       set_registry, snapshot, with_labels)
from .spans import SPAN_METRIC, Phase, span
from .step_clock import StepClock

__all__ = [
    "Registry", "jsonl_line",
    "merge_snapshots", "prometheus_text", "registry",
    "set_constant_labels", "set_registry", "snapshot", "with_labels",
    "SPAN_METRIC", "Phase", "span", "install_compile_listener",
    "OnDemandProfiler", "start_capture", "build_info", "register_build_info",
    "events", "memory", "step_clock", "tracectx", "FlightRecorder",
    "RotatingJsonl", "StepClock",
]
