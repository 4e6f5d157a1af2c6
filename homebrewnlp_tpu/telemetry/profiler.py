"""On-demand XLA profiling: a signal (or programmatic request) captures a
``jax.profiler`` trace of the next N steps into ``<model_path>/profile/``.

The train loop has always supported pre-planned windows
(``train(profile_steps=(a, b))``); this adds the ops workflow the survey
found missing — "the run is slow NOW, show me why" — without restarting
the run: ``kill -USR2 <pid>`` on a run with ``telemetry_profile_on_signal``
set starts a capture at the next loop tick and stops it
``telemetry_profile_steps`` steps later.  A second signal while capturing
stops early.

``start``/``stop`` are injectable so the state machine is testable without
jax; the defaults call ``jax.profiler.start_trace``/``stop_trace`` lazily.
Signal handlers only flip flags (async-signal-safe); all real work happens
in ``poll()`` on the loop thread.
"""
from __future__ import annotations

import signal
import typing


def start_capture(logdir: str):
    """Start a ``jax.profiler`` capture with the PYTHON tracer off: the
    program's spans (telemetry/spans.py) name what the host does, while the
    Python tracer records every call (thousands of events a step), slows
    the host loop it is meant to observe, and buries the spans' line.  Host
    TraceMe events (level 2: the spans, jax's own dispatch and transfer
    annotations) and the device planes are kept.  Used by both capture
    paths: ``train(profile_steps=...)`` and SIGUSR2."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(logdir, profiler_options=opts)


def _default_stop():
    import jax
    jax.profiler.stop_trace()


class OnDemandProfiler:
    def __init__(self, out_dir: str, capture_steps: int = 10,
                 start: typing.Callable[[str], None] = start_capture,
                 stop: typing.Callable[[], None] = _default_stop):
        self.out_dir = out_dir
        self.capture_steps = max(1, int(capture_steps))
        self._start = start
        self._stop = stop
        self._requested = False
        self._stop_early = False
        self.active = False
        self._stop_at: typing.Optional[int] = None
        self.captures: typing.List[str] = []
        self._prev_handler = None
        self._signum: typing.Optional[int] = None

    # -- triggers (signal-handler safe: only flips flags) --------------------

    def request(self):
        """Ask for a capture (or, while one runs, for an early stop)."""
        if self.active:
            self._stop_early = True
        else:
            self._requested = True

    def _on_signal(self, signum, frame):
        self.request()

    def install_signal(self, signum: int = signal.SIGUSR2) -> bool:
        """Install the trigger handler; False when signals are unavailable
        (non-main thread — embedded/test use keeps the programmatic
        ``request()``)."""
        try:
            self._prev_handler = signal.signal(signum, self._on_signal)
            self._signum = signum
            return True
        except ValueError:
            return False

    def uninstall_signal(self):
        if self._signum is not None and self._prev_handler is not None:
            signal.signal(self._signum, self._prev_handler)
        self._signum = self._prev_handler = None

    # -- loop-thread side ----------------------------------------------------

    def poll(self, step: int):
        """Call once per loop iteration with the host-side step counter:
        starts a requested capture, stops a finished (or early-stopped)
        one.  Capture failures are reported, never fatal — a missing
        profiler backend must not kill the training run."""
        if self.active:
            if self._stop_early or (self._stop_at is not None
                                    and step >= self._stop_at):
                self._finish()
            return
        if not self._requested:
            return
        self._requested = False
        import os
        logdir = os.path.join(self.out_dir, f"on_demand_{int(step)}")
        try:
            self._start(logdir)
        except Exception as e:
            print(f"WARNING: on-demand profile capture failed to start: {e}",
                  flush=True)
            return
        self.active = True
        self._stop_early = False
        self._stop_at = step + self.capture_steps
        self.captures.append(logdir)
        print(f"telemetry: capturing XLA profile of ~{self.capture_steps} "
              f"steps into {logdir}", flush=True)

    def _finish(self):
        try:
            self._stop()
        except Exception as e:
            print(f"WARNING: profile capture failed to stop cleanly: {e}",
                  flush=True)
        self.active = False
        self._stop_early = False
        self._stop_at = None
        print(f"telemetry: XLA profile written to {self.captures[-1]}",
              flush=True)

    def close(self):
        """Stop any in-flight capture (run teardown) and drop the signal
        handler."""
        if self.active:
            self._finish()
        self.uninstall_signal()
