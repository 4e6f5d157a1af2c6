"""Compilation counters: what jax traced, lowered, compiled or loaded from
its persistent cache, by phase and by function.

One ``jax.monitoring`` duration listener, registered once a process by
:func:`install_compile_listener` (``install_compile_cache`` calls it, so
every run mode has it before its first jit).  It feeds

* ``hbnlp_compile_seconds_total{phase, fun}`` and
* ``hbnlp_compiles_total{phase, fun}``

with ``phase`` one of ``trace`` (jaxpr tracing), ``lower`` (jaxpr to
StableHLO), ``backend`` (``compile_or_get_cached``: XLA's compile OR the
load of a cached executable — jax 0.9.0 times both under one event) and
``cache_load`` (the part of ``backend`` spent reading the persistent cache;
the event carries no function name, so ``fun`` is empty).  ``fun`` is the
jitted function's name as jax reports it (``step_fn`` for the train step).
A step that recompiles shows as ``hbnlp_compiles_total{phase="backend",
fun="step_fn"}`` rising after warm-up.

Compilation is rare by construction, so the listener records always; a
steady-state step raises no event and makes no registry call.
"""
from __future__ import annotations

import re

from .registry import registry as _process_registry

SECONDS_METRIC = "hbnlp_compile_seconds_total"
COUNT_METRIC = "hbnlp_compiles_total"

#: jax.monitoring event -> phase label
PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}

#: the lowering and backend events name the function ``jit(step_fn)``, the
#: tracing event ``step_fn``: one label value for both
_WRAPPER = re.compile(r"^[a-z_]+\((.*)\)$")

_installed = False
#: ``hbnlp_compiles_total{phase="backend"}`` summed over ``fun``, as a plain
#: number: what the step clock reads at every step, where a registry call
#: has no place
_backend_compiles = 0


def backend_compiles() -> int:
    """Backend compiles (or loads of a cached executable) this process has
    made since the listener was installed."""
    return _backend_compiles


def _on_duration(event: str, seconds: float, **kw) -> None:
    phase = PHASES.get(event)
    if phase is None:
        return
    if phase == "backend":
        global _backend_compiles
        _backend_compiles += 1
    fun = str(kw.get("fun_name") or "")
    wrapped = _WRAPPER.match(fun)
    if wrapped:
        fun = wrapped.group(1)
    r = _process_registry()
    r.counter(SECONDS_METRIC, "seconds jax spent compiling, by phase "
              "(backend includes cache_load)",
              ("phase", "fun")).labels(phase, fun).inc(float(seconds))
    r.counter(COUNT_METRIC, "jax compilation events, by phase",
              ("phase", "fun")).labels(phase, fun).inc()


def install_compile_listener() -> None:
    """Register the listener with jax.monitoring; idempotent (jax offers
    no public way to take a listener back, so it is registered once and
    resolves the process registry at event time)."""
    global _installed
    if _installed:
        return
    import jax
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _installed = True
