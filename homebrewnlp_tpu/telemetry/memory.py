"""The chip's memory as the runtime reports it: the ONE place the program
calls ``device.memory_stats()`` (docs/OBSERVABILITY.md 'Device memory').

:func:`device_stats` reads one device, :func:`read` every local one, and
:func:`mark` publishes the FULLEST local device's reading at a named point
of the run:

* ``hbnlp_hbm_bytes{point, kind}`` — gauges, ``kind`` one of :data:`KINDS`;
* a ``memory/<point>`` span around the read (``telemetry.span``), so that a
  captured profile shows it on the host's line;
* one flight-recorder event ``memory`` (point, device and the numbers), so
  that the blackbox of a run that dies on ``RESOURCE_EXHAUSTED`` holds the
  last readings before it.

The points (``Trainer.init_state``: ``params_placed``, ``state_ready``;
``Trainer.step``: ``step_loaded``; ``train()`` under ``telemetry_enabled``:
``running``) are rare by construction and none waits for the device, so a
mark records always; a steady-state step makes no call into this module.

A backend that reports nothing (XLA:CPU, a described topology's devices)
reads as ``None`` — absent, never 0: no series, no event, and the start-up
line says ``memory: not reported by this backend``.

What the numbers mean on a TPU (measured, PERF.md section 4): ``in_use`` is
the live buffers — the train state, the batches, a program's outputs, the
loaded programs themselves; ``reserved`` is what the runtime holds OUTSIDE
``in_use`` for the loaded programs' temporaries, so ``in_use + reserved``
is the footprint of a loop of one program.  ``reservable_limit`` is the
most ``reserved`` may reach: the whole limit until a program is loaded,
then the limit minus what was in use when the reservation was made.  The
``peak_*`` kinds are high-water marks of the whole process.

Stdlib-only at import, like ``spans.py``: ``jax`` is looked up in
``sys.modules`` when local devices are wanted.
"""
from __future__ import annotations

import math
import sys
import typing

from . import events
from .registry import registry as _process_registry
from .spans import span

HBM_METRIC = "hbnlp_hbm_bytes"
STATE_METRIC = "hbnlp_train_state_bytes"

#: kind -> the runtime's key of it
RUNTIME_KEYS = {
    "in_use": "bytes_in_use",
    "reserved": "bytes_reserved",
    "peak_in_use": "peak_bytes_in_use",
    "peak_reserved": "peak_bytes_reserved",
    "largest_free_block": "largest_free_block_bytes",
    "limit": "bytes_limit",
    "reservable_limit": "bytes_reservable_limit",
}
KINDS = tuple(RUNTIME_KEYS)
NOT_REPORTED = "memory: not reported by this backend"

Stats = typing.Dict[str, int]


def device_stats(device) -> typing.Optional[Stats]:
    """``{kind: bytes}`` of one device, the kinds the runtime reports and no
    others, or ``None`` where it reports nothing."""
    try:
        raw = device.memory_stats()
    except Exception:  # noqa: BLE001 — a described topology's devices raise
        raw = None
    if not raw:
        return None
    return {kind: int(raw[key]) for kind, key in RUNTIME_KEYS.items()
            if key in raw}


def read(devices: typing.Optional[typing.Sequence] = None
         ) -> typing.List[typing.Tuple[typing.Any, typing.Optional[Stats]]]:
    """``[(device, stats or None)]`` of ``devices``, by default every local
    device of this process."""
    if devices is None:
        devices = sys.modules["jax"].local_devices()
    return [(d, device_stats(d)) for d in devices]


def footprint(stats: Stats) -> int:
    """Live buffers plus the runtime's current reservation for the loaded
    programs' temporaries."""
    return stats.get("in_use", 0) + stats.get("reserved", 0)


class Reading(typing.NamedTuple):
    """One sweep over the local devices: the fullest one's numbers, and
    every reporting device's footprint."""
    point: str
    device: typing.Any
    stats: Stats
    footprints: typing.Dict[int, int]     # device id -> in_use + reserved

    def share(self, nbytes: int) -> str:
        limit = self.stats.get("limit")
        return f"{100.0 * nbytes / limit:.2f}%" if limit else "?%"


def mark(point: str, devices: typing.Optional[typing.Sequence] = None
         ) -> typing.Optional[Reading]:
    """Read once, publish the fullest device's numbers as
    ``hbnlp_hbm_bytes{point, kind}`` and a ``memory`` flight-recorder
    event; ``None``, and nothing published, where no device reports."""
    with span(f"memory/{point}"):
        found = [(d, s) for d, s in read(devices) if s is not None]
        if not found:
            return None
        device, stats = max(found, key=lambda ds: footprint(ds[1]))
        gauge = _process_registry().gauge(
            HBM_METRIC, "device memory as the runtime reports it, the "
            "fullest local device, at a point of the run", ("point", "kind"))
        for kind, value in stats.items():
            gauge.labels(point, kind).set(value)
        events.record("memory", point=point, device=device.id, **stats)
        return Reading(point, device, stats,
                       {d.id: footprint(s) for d, s in found})


# ---- what the train state is made of ----------------------------------------

def leaves_bytes_on(leaves: typing.Iterable, device
                    ) -> typing.Dict[str, int]:
    """``{dtype name: bytes}`` of the shards of ``leaves`` (``jax.Array``s)
    that ``device`` holds: host arithmetic over shapes and shardings, no
    transfer and no shard materialised."""
    out: typing.Dict[str, int] = {}
    for leaf in leaves:
        if device not in leaf.sharding.device_set:
            continue
        nbytes = math.prod(leaf.sharding.shard_shape(leaf.shape)) \
            * leaf.dtype.itemsize
        out[leaf.dtype.name] = out.get(leaf.dtype.name, 0) + nbytes
    return out


def publish_state(reading: typing.Optional[Reading],
                  parts: typing.Mapping[str, typing.Iterable]) -> str:
    """``hbnlp_train_state_bytes{kind}`` for each of ``parts`` (kind ->
    leaves) on ``reading``'s device, and the start-up line: the state by
    kind and dtype, ``in_use`` and what of it the state does not explain
    (a placed batch, the padding of small leaves to the device's tiles, the
    runtime's own), the limit and the reservable limit."""
    if reading is None:
        return NOT_REPORTED
    gauge = _process_registry().gauge(
        STATE_METRIC, "bytes of the train state on the fullest local device "
        "at state_ready, from the leaves' shards", ("kind",))
    total, texts = 0, []
    for kind, leaves in parts.items():
        by_dtype = leaves_bytes_on(leaves, reading.device)
        nbytes = sum(by_dtype.values())
        gauge.labels(kind).set(nbytes)
        total += nbytes
        texts.append(f"{kind} {nbytes} ("
                     + ", ".join(f"{n} {v}" for n, v in sorted(by_dtype.items()))
                     + ")")
    s = reading.stats
    in_use = s.get("in_use", 0)
    return (f"memory: at {reading.point} device {reading.device.id} holds "
            f"in_use {in_use} bytes = {reading.share(in_use)} of limit "
            f"{s.get('limit')} (reservable {s.get('reservable_limit')}): "
            f"train state {total} = " + " + ".join(texts)
            + f"; batch, layout padding and runtime {in_use - total}")


def loaded_line(reading: typing.Optional[Reading]) -> typing.Optional[str]:
    """The second start-up line, at ``step_loaded``: the loop's footprint,
    the step program's scratch and the largest free block, in bytes and
    percent of the limit, and every local device's footprint."""
    if reading is None:
        return None
    s = reading.stats
    reserved, free = s.get("reserved", 0), s.get("largest_free_block", 0)
    total = footprint(s)
    return (f"memory: at {reading.point} device {reading.device.id} "
            f"footprint {total} bytes = {reading.share(total)} of limit "
            f"{s.get('limit')} = in_use {s.get('in_use')} + step scratch "
            f"{reserved} ({reading.share(reserved)}; reservable "
            f"{s.get('reservable_limit')}); largest free block {free} "
            f"({reading.share(free)}); footprint by local device "
            f"{reading.footprints}")
