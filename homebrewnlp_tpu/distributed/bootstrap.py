"""jax.distributed bootstrap: coordinator discovery, topology report,
clean teardown (docs/DISTRIBUTED.md).

Two discovery paths, checked in order by ``maybe_initialize``:

1. **Explicit flags** (the CPU multiprocess rig, scripts/run_manager.py
   ``--num-processes`` fan-out): ``HBNLP_COORDINATOR`` (host:port),
   ``HBNLP_NUM_PROCESSES``, ``HBNLP_PROCESS_ID``.  All three must be set;
   a partial set is a configuration error and fails loudly rather than
   silently running single-process.
2. **Standard environment / TPU metadata**: ``JAX_COORDINATOR_ADDRESS``
   (or nothing at all on a Cloud TPU pod slice, where jax's cluster
   detection reads the metadata server).  ``maybe_initialize`` calls the
   no-arg ``jax.distributed.initialize()`` and lets jax autodiscover.

Everything else here is coordination-service plumbing (barriers and a
key-value store over the coordinator's gRPC channel — **no device
collectives**), which makes it safe to call from background threads while
the main thread runs jitted steps: the async checkpoint commit barrier and
the cross-host telemetry merge both depend on that property.
"""
from __future__ import annotations

import os
import time
import typing

#: explicit-flag env vars for the CPU multiprocess rig (docs/DISTRIBUTED.md)
COORDINATOR_ENV = "HBNLP_COORDINATOR"
NUM_PROCESSES_ENV = "HBNLP_NUM_PROCESSES"
PROCESS_ID_ENV = "HBNLP_PROCESS_ID"
#: standard jax env var — set by TPU pod launchers / k8s manifests
JAX_COORDINATOR_ENV = "JAX_COORDINATOR_ADDRESS"

_initialized_here = False


def free_port() -> int:
    """An OS-assigned free localhost port — for launching a coordinator on
    the local rig (run_manager fleet, tests).  One shared
    helper so a future fix (SO_REUSEADDR, IPv6) lands everywhere at once."""
    import socket
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def is_initialized() -> bool:
    """True when this process is part of an initialized jax.distributed
    cluster (whether this module did the initializing or not)."""
    try:
        from jax._src import distributed
        return distributed.global_state.client is not None
    except Exception:
        return False


def maybe_initialize(verbose: bool = True) -> bool:
    """Initialize ``jax.distributed`` when the environment asks for it;
    return True iff this process is (now) part of a multi-process cluster.

    Single-process runs (no coordinator env at all) return False and touch
    nothing — every call site stays valid on a laptop, the CI rig, and a
    pod with the same code path.
    """
    global _initialized_here
    if is_initialized():
        return True
    import jax
    explicit = os.environ.get(COORDINATOR_ENV)
    if (explicit or os.environ.get(JAX_COORDINATOR_ENV)) and \
            os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        # the CPU rig: XLA's default CPU client refuses multi-process
        # computations ("Multiprocess computations aren't implemented on
        # the CPU backend") — gloo-over-TCP collectives make the virtual
        # pod real.  Must be set BEFORE the backend initialises, which is
        # why it lives here and not at a call site.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    if explicit:
        missing = [k for k in (NUM_PROCESSES_ENV, PROCESS_ID_ENV)
                   if not os.environ.get(k)]
        if missing:
            raise RuntimeError(
                f"{COORDINATOR_ENV} is set but {missing} are not: the "
                "explicit-flag rig needs all three (see docs/DISTRIBUTED.md)")
        jax.distributed.initialize(
            coordinator_address=explicit,
            num_processes=int(os.environ[NUM_PROCESSES_ENV]),
            process_id=int(os.environ[PROCESS_ID_ENV]))
        _initialized_here = True
    elif os.environ.get(JAX_COORDINATOR_ENV):
        # standard env: jax reads JAX_COORDINATOR_ADDRESS + cluster metadata
        # (TPU pod slices fill in num_processes/process_id from the metadata
        # server; GKE sets the full set)
        jax.distributed.initialize()
        _initialized_here = True
    else:
        return False
    if verbose:
        print(format_topology(topology_report()), flush=True)
    return True


def topology_report() -> dict:
    """Where this process sits in the cluster: process index/count, local
    devices (with TPU slice indices when the platform reports them), global
    device count, backend.  Safe single-process (reports a 1-process
    topology)."""
    import jax
    local = []
    for d in jax.local_devices():
        entry = {"id": int(d.id), "kind": getattr(d, "device_kind", "?")}
        # TPU v4+ multi-slice: which slice this chip belongs to
        slice_idx = getattr(d, "slice_index", None)
        if slice_idx is not None:
            entry["slice"] = int(slice_idx)
        local.append(entry)
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "backend": jax.default_backend(),
        "local_devices": local,
        "global_device_count": len(jax.devices()),
        "coordinator": os.environ.get(COORDINATOR_ENV)
        or os.environ.get(JAX_COORDINATOR_ENV) or "",
    }


def format_topology(report: dict) -> str:
    slices = sorted({d.get("slice") for d in report["local_devices"]
                     if d.get("slice") is not None})
    slice_note = f" slice(s) {slices}" if slices else ""
    return (f"distributed: process {report['process_index']}/"
            f"{report['process_count']} backend={report['backend']} "
            f"local_devices={len(report['local_devices'])} "
            f"global_devices={report['global_device_count']}{slice_note}")


def coordination_client():
    """The jax coordination-service client, or None single-process.  Its
    barriers and KV ops ride the coordinator's gRPC channel — no device
    collectives — so they are safe from any thread at any time."""
    try:
        from jax._src import distributed
        return distributed.global_state.client
    except Exception:
        return None


def barrier(name: str, timeout_s: float = 600.0) -> None:
    """Block until every process reaches ``barrier(name)``; no-op
    single-process.  Raises ``TimeoutError`` naming the barrier on
    timeout/peer-death — a peer that died mid-protocol surfaces as a
    NAMED error at the caller (which protocol step, how long) instead of
    hanging forever or raising an anonymous gRPC status
    (tests/distributed_test.py::kv_barrier_edge_cases_test)."""
    client = coordination_client()
    if client is None:
        return
    from ..telemetry import events as _flight
    t0 = time.monotonic()
    try:
        client.wait_at_barrier(name, int(timeout_s * 1000))
        # collective-phase marker (docs/OBSERVABILITY.md 'Flight
        # recorder'): barriers are the pod's ordering points — the
        # forensic timeline shows which protocol step each rank reached
        _flight.record("collective", phase=name, status="ok",
                       seconds=round(time.monotonic() - t0, 3))
    except Exception as e:
        # one error type for every barrier failure (callers handle
        # timeout and peer-death identically: the pod is broken), but the
        # message reports the MEASURED wait — an instant gRPC failure
        # (dead coordinator, bad barrier id) must not masquerade as a
        # full timeout_s wait on a wedged peer
        elapsed = time.monotonic() - t0
        _flight.record("collective", phase=name, status="failed",
                       seconds=round(elapsed, 3), error=str(e))
        _flight.flush(reason="barrier-failure")
        raise TimeoutError(
            f"coordination barrier {name!r} failed after {elapsed:.1f}s "
            f"(timeout {timeout_s}s; peer dead or wedged "
            f"mid-protocol?): {e}") from e


def kv_put(key: str, value: str) -> bool:
    """Publish ``value`` under ``key`` in the coordination KV store
    (overwriting any earlier value); False single-process / on error."""
    client = coordination_client()
    if client is None:
        return False
    try:
        client.key_value_set(key, value, allow_overwrite=True)
        return True
    except TypeError:
        # older binding without allow_overwrite: delete-then-set
        try:
            try:
                client.key_value_delete(key)
            except Exception:
                pass
            client.key_value_set(key, value)
            return True
        except Exception:
            return False
    except Exception:
        return False


def kv_dir_get(prefix: str) -> typing.List[typing.Tuple[str, str]]:
    """All (key, value) pairs under ``prefix``; [] single-process or when
    nothing was published."""
    client = coordination_client()
    if client is None:
        return []
    try:
        return list(client.key_value_dir_get(prefix))
    except Exception:
        return []


def shutdown() -> None:
    """Tear down jax.distributed if THIS module initialized it (idempotent,
    never raises).  Called on the preemption/exit path so the coordinator
    sees a clean disconnect instead of a gRPC reset — peers then fail their
    next barrier with a named error rather than a hang."""
    global _initialized_here
    if not _initialized_here:
        return
    _initialized_here = False
    try:
        import jax
        jax.distributed.shutdown()
    except Exception as e:
        print(f"WARNING: jax.distributed.shutdown failed: {e}", flush=True)
