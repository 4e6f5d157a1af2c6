"""Replica fleet: the process half of the multi-replica serving tier.

``infer/router.py`` dispatches; this module owns N replica PROCESSES, each
a full isolated serving deployment (``rest_api.serve``: its own device
loop, HTTP child, guard) of the same config on its own port.  It
generalizes two existing runtimes:

* the fan-out/monitor/relaunch loop follows ``scripts/run_manager.py``'s
  fleet semantics (PR 10) — dead replicas relaunch with bounded
  exponential backoff, and the crash budget RESETS after a replica stays
  up through a stability window (it bounds crash LOOPS, not lifetime
  crash count — the ``rest_api`` child-supervision rule);
* processes use the spawn context like the serving HTTP child (forking a
  multithreaded JAX parent can deadlock the child).

Each replica rebuilds the model from the config's ``_raw_config`` dict
(checkpoints restore through the same corruption-tolerant
``restore_latest_valid`` walk as single-replica serving), with
``serve_replicas`` forced to 0 inside the replica — a replica must never
recursively spawn its own tier.  The router's per-replica breaker handles
the WINDOW while a replica relaunches: its port refuses connections, the
breaker opens, dispatch skips it, and the probe recloses it once the
relaunched replica binds.

One process per chip: a TPU chip belongs to the first process that
initialises a backend on it, so N replicas left to find devices on their
own would all contend for every chip of the host.  On a host with TPU chips
the fleet binds replica *i* to chip *i* through the environment its
subprocess inherits (``TPU_VISIBLE_CHIPS`` and single-chip process bounds,
which libtpu reads when the replica's backend starts) and refuses at
start-up — :class:`ReplicaChipError` — when there are more replicas than
chips.  The parent itself never starts a backend; it counts chips from the
device nodes libtpu would open.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import time
import typing


class ReplicaChipError(RuntimeError):
    """More replicas requested than this host has TPU chips."""


def local_tpu_chips() -> typing.List[str]:
    """The TPU chips of this host as libtpu numbers them (the numeric device
    nodes it opens: ``/dev/accel<N>`` or ``/dev/vfio/<N>``), sorted; empty
    on a host without TPU chips or when the caller pinned jax to the CPU.
    Reads the filesystem only — never a jax backend."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return []
    nodes = glob.glob("/dev/accel[0-9]*") or glob.glob("/dev/vfio/[0-9]*")
    ids = [re.search(r"(\d+)$", n).group(1) for n in nodes]
    return sorted(ids, key=int)


@contextlib.contextmanager
def _bound_to_chip(chip: typing.Optional[str]):
    """Bind a subprocess started inside this block to ONE chip: the spawn
    context copies ``os.environ`` at ``Process.start()``.  No-op for
    ``chip=None`` (no TPU chips on the host)."""
    if chip is None:
        yield
        return
    binding = {"TPU_VISIBLE_CHIPS": chip,
               "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
               "TPU_PROCESS_BOUNDS": "1,1,1"}
    saved = {k: os.environ.get(k) for k in binding}
    os.environ.update(binding)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def install_replica_stop():
    """SIGTERM/SIGINT -> a stop event for ``rest_api.serve``: the fleet's
    ``terminate()`` then drains the replica's device loop cleanly (HTTP
    child + IPC Manager torn down) instead of orphaning its subprocesses
    — the default signal disposition kills the replica before its
    ``finally`` teardown runs."""
    import signal
    import threading

    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(sig, lambda *_: stop.set())
        except ValueError:  # not the main thread (embedded/test use)
            break
    return stop


def _replica_main(cfg: dict, port: int, index: int):
    """Subprocess body: load the model, serve one isolated deployment."""
    from ..config import ModelParameter
    from ..infer.interface import InterfaceWrapper
    from ..infer.rest_api import serve
    from ..run.modes import _load_model

    stop = install_replica_stop()
    params = ModelParameter(dict(cfg), serve_replicas=0)
    if getattr(params, "trace_requests", False) and params.model_path:
        # replica-indexed blackbox tag BEFORE serve() (which would default
        # to "serve"): the device loop's event file becomes
        # blackbox_r<i>.jsonl, its HTTP child blackbox_r<i>_http.jsonl —
        # forensics then shows which replica a trace crossed
        from ..telemetry import events as _flight
        _flight.configure(params.model_path, f"r{index}",
                          capacity=getattr(params,
                                           "telemetry_blackbox_events",
                                           4096))
    params, model, variables, mesh = _load_model(params)
    interface = InterfaceWrapper(params, model, variables, mesh=mesh)
    print(f"[replica {index}] serving on :{port}", flush=True)
    serve(params, interface, port=port, isolate=True, stop=stop)


class ReplicaFleet:
    """Spawn + supervise N replica serving processes on consecutive ports.

    ``poll()`` (called from the tier's main loop) relaunches dead replicas
    with bounded exponential backoff per replica; ``stop()`` terminates
    the fleet.  ``target`` is injectable for tests (a device-free stand-in
    for ``_replica_main``)."""

    def __init__(self, params, n: int, base_port: int,
                 max_restarts: typing.Optional[int] = None,
                 restart_backoff_s: typing.Optional[float] = None,
                 target: typing.Callable = _replica_main,
                 classes: typing.Optional[typing.Sequence[str]] = None):
        import multiprocessing as mp

        self.cfg = dict(getattr(params, "_raw_config", params))
        self.n = int(n)
        self.base_port = int(base_port)
        self.target = target
        #: per-replica class for the disaggregated tier (docs/SERVING.md);
        #: rides each replica's cfg as ``serve_replica_class`` so the
        #: 3-arg spawn target (injectable in tests) stays unchanged
        self.classes = [str(c or "") for c in (classes or [])]
        if self.classes and len(self.classes) != self.n:
            raise ValueError(f"classes ({len(self.classes)}) must match "
                             f"replica count ({self.n})")
        self.max_restarts = int(
            getattr(params, "serve_child_max_restarts", 5) or 0
            if max_restarts is None else max_restarts)
        self.base_backoff = float(
            getattr(params, "serve_child_restart_backoff_s", 0.5)
            if restart_backoff_s is None else restart_backoff_s)
        self._ctx = mp.get_context("spawn")
        #: chip id per replica on a TPU host (None entries elsewhere)
        self._chips: typing.List[typing.Optional[str]] = [None] * self.n
        chips = local_tpu_chips()
        if chips:
            if self.n > len(chips):
                raise ReplicaChipError(
                    f"{self.n} replicas requested but this host has "
                    f"{len(chips)} TPU chip(s) ({', '.join(chips)}): a chip "
                    "belongs to one process — lower serve_replicas / "
                    "serve_replica_classes to the chip count")
            self._chips = list(chips[:self.n])
        self._procs: typing.List[typing.Optional[typing.Any]] = [None] * n
        self._restarts = [0] * n
        self._backoff = [self.base_backoff] * n
        self._next_spawn = [0.0] * n
        self._up_since = [0.0] * n
        self.stability_window_s = 60.0

    def port(self, index: int) -> int:
        return self.base_port + int(index)

    def _spawn(self, index: int) -> None:
        # NOT daemonic: a replica spawns its own Manager + HTTP child, and
        # daemonic processes are forbidden children.  stop() (wired to the
        # mode's SIGTERM/SIGINT drain) terminates the fleet instead.
        cfg = self.cfg
        if self.classes:
            cfg = dict(cfg)
            cfg["serve_replica_class"] = self.classes[index]
            # a replica inherits the tier config verbatim; its own class
            # replaces the topology knob (a replica never spawns a tier)
            cfg.pop("serve_replica_classes", None)
        p = self._ctx.Process(
            target=self.target,
            args=(cfg, self.port(index), index), daemon=False)
        with _bound_to_chip(self._chips[index]):
            p.start()
        self._procs[index] = p
        self._up_since[index] = time.monotonic()

    def start(self) -> None:
        for i in range(self.n):
            self._spawn(i)

    def poll(self) -> None:
        """Relaunch dead replicas whose backoff has elapsed.  A replica
        out of restart budget raises — a fleet silently shrinking to zero
        is worse than a loud failure (the router keeps serving the
        surviving replicas until then)."""
        now = time.monotonic()
        for i, p in enumerate(self._procs):
            if p is None or p.is_alive():
                if (p is not None and self._restarts[i]
                        and now - self._up_since[i]
                        > self.stability_window_s):
                    # survived the stability window: the relaunch recovered
                    self._restarts[i] = 0
                    self._backoff[i] = self.base_backoff
                continue
            if self._next_spawn[i] == 0.0:
                self._restarts[i] += 1
                if self._restarts[i] > self.max_restarts:
                    raise RuntimeError(
                        f"replica {i} exited (code {p.exitcode}) and "
                        f"{self.max_restarts} relaunches were exhausted")
                print(f"replica {i} died (code {p.exitcode}); relaunch "
                      f"{self._restarts[i]}/{self.max_restarts} in "
                      f"{self._backoff[i]:.2f}s", flush=True)
                self._next_spawn[i] = now + self._backoff[i]
                self._backoff[i] = min(self._backoff[i] * 2, 30.0)
            elif now >= self._next_spawn[i]:
                self._next_spawn[i] = 0.0
                self._spawn(i)

    def alive(self) -> int:
        return sum(1 for p in self._procs if p is not None and p.is_alive())

    def stop(self) -> None:
        for p in self._procs:
            if p is not None and p.is_alive():
                p.terminate()
        for p in self._procs:
            if p is not None:
                p.join(timeout=15.0)
                if p.is_alive():
                    # the drain is stuck (e.g. wedged mid-decode): escalate
                    # rather than leak the replica + its IPC children
                    p.kill()
                    p.join(timeout=5.0)
