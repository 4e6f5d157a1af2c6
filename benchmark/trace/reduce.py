"""From a profiler trace (``.xplane.pb``) to the numbers the metrics read.

What a TPU v5e trace under jax 0.9.0 holds (read by hand from
``fixtures/v5e_fixture_step.xplane.pb``, recorded by ``record_fixture.py``):

* one plane ``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one
  event per program run, named ``jit_<fn>(<fingerprint>)``), ``XLA Ops`` (one
  event per executed HLO instruction, named by the instruction's text,
  ``%fusion.1 = bf16[...] fusion(...)``; a Pallas kernel is a
  ``custom-call`` named after the kernel, ``%map_mixer_fwd_causal.1 = ...``)
  and ``Async XLA Ops`` (the in-flight span of every ``*-start``/``*-done``
  pair: DMA copies, async collectives);
* the plane ``/host:CPU`` with one line per host thread;
  ``jax.profiler.TraceAnnotation`` spans sit on the line ``python`` under
  the name they were given.

Device and host events share one time base (nanoseconds); the two clocks
disagreed by ~0.6 ms in the fixture, which is far below the gaps worth
attributing.

Definitions (on-chip-measurement guide, section 4):

* busy: the union of the ``XLA Ops`` intervals inside the window.  The
  tensor core executes instructions one after another, and an instruction
  that waits for a DMA or a collective counts its wait as its own time, so
  this union is "an operation ran on the device".
* idle gap: a maximal interval of the window outside that union; it is
  attributed to the host span (of the names the driver passes) that covers
  most of it, or to ``unattributed``.
* collective time: the union of synchronous collective instructions and of
  the in-flight spans of asynchronous ones; its *exposed* part is what no
  other instruction overlaps.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import typing

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
Interval = typing.Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float          # ns
    end: float            # ns

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9


@dataclasses.dataclass
class Device:
    name: str
    ops: typing.List[Event] = dataclasses.field(default_factory=list)
    modules: typing.List[Event] = dataclasses.field(default_factory=list)
    async_ops: typing.List[Event] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Trace:
    devices: typing.List[Device]
    host: typing.List[Event]       # every event of every host thread


def newest_xplane(trace_dir: typing.Optional[str]) -> typing.Optional[str]:
    """The ``.xplane.pb`` the profiler wrote last under ``trace_dir``."""
    if trace_dir is None:
        return None
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` with nothing but jax."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = Device(plane.name)
            for line in plane.lines:
                target = {"XLA Ops": dev.ops, "XLA Modules": dev.modules,
                          "Async XLA Ops": dev.async_ops}.get(line.name)
                if target is not None:
                    target.extend(
                        Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
            devices.append(dev)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(
                    Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
    devices.sort(key=lambda d: int(DEVICE_PLANE.match(d.name).group(1)))
    return Trace(devices, host)


# ---- names ------------------------------------------------------------------

def short_name(text: str) -> str:
    """``%fusion.1 = bf16[..] fusion(..)`` -> ``fusion.1``."""
    head = text.split(" = ", 1)[0].strip()
    return head.lstrip("%")


def opcode(text: str) -> str:
    """The HLO opcode of an instruction's text (``fusion``, ``custom-call``,
    ``all-reduce-start``, ...); the short name without its number where the
    text holds no ``=``."""
    if " = " in text:
        body = text.split(" = ", 1)[1]
        # the result type may hold parentheses (tuples, tilings); the opcode
        # is the last word before the first '(' that follows a space-free
        # token of letters and dashes
        m = re.search(r"(?:^|[\s)}\]])([a-z][a-z\-]*)\(", body)
        if m:
            return m.group(1)
    return re.sub(r"[.\d]+$", "", short_name(text))


def label(text: str) -> str:
    """A readable label for the breakdown: short name, opcode detail and
    result type (``fusion.1 kOutput bf16[2048,2048]``)."""
    out = short_name(text)
    kind = re.search(r"kind=(k\w+)", text)
    if kind:
        out += " " + kind.group(1)
    elif " = " in text:
        out += " " + opcode(text)
    shape = re.search(r" = \(?([a-z]+\d*\[[\d,]*\])", text)
    if shape:
        out += " " + shape.group(1)
    return out


def is_collective(text: str) -> bool:
    return opcode(text).startswith(COLLECTIVES)


def module_name(text: str) -> str:
    """``jit_step_fn(123)`` -> ``jit_step_fn``."""
    return text.split("(", 1)[0]


# ---- interval arithmetic ----------------------------------------------------

def union(intervals: typing.Iterable[Interval]) -> typing.List[Interval]:
    out: typing.List[Interval] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def clip(intervals: typing.Iterable[Interval], lo: float, hi: float
         ) -> typing.List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def measure(merged: typing.Iterable[Interval]) -> float:
    return sum(b - a for a, b in merged)


def subtract(a: typing.List[Interval], b: typing.List[Interval]
             ) -> typing.List[Interval]:
    """The parts of the merged list ``a`` that the merged list ``b`` does
    not cover."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def overlap(a: typing.List[Interval], b: typing.List[Interval]) -> float:
    return measure(a) - measure(subtract(a, b))


def _spans(events: typing.Iterable[Event], lo: float, hi: float
           ) -> typing.List[Interval]:
    return clip(((e.start, e.end) for e in events), lo, hi)


# ---- the reduction ----------------------------------------------------------

def window_of(trace: Trace, span: typing.Optional[str]) -> Interval:
    """The measured window: the host span named ``span`` where the trace
    holds one (the driver's ``bench_window``), else from the first to the
    last device event."""
    if span:
        hits = [e for e in trace.host if e.name == span]
        if hits:
            return (min(e.start for e in hits), max(e.end for e in hits))
    edges = [(e.start, e.end) for d in trace.devices
             for e in d.ops + d.modules]
    if not edges:
        raise ValueError("the trace holds no device event")
    return (min(a for a, _ in edges), max(b for _, b in edges))


def attribute(gap: Interval, spans: typing.Dict[str, typing.List[Interval]]
              ) -> str:
    """The name that covers most of ``gap``: one of the spans, or
    ``unattributed`` for the part none of them covers."""
    covers = {name: overlap([gap], merged) for name, merged in spans.items()}
    covered = overlap([gap], union(i for m in spans.values() for i in m))
    covers["unattributed"] = (gap[1] - gap[0]) - covered
    return max(covers, key=covers.get)


#: idle gaps shorter than this are rounding between back-to-back
#: instructions (picoseconds cut to nanoseconds); they count as idle time
#: but are not listed
MIN_LISTED_GAP_NS = 1000.0


def leaf_segments(ops: typing.Sequence[Event], lo: float, hi: float
                  ) -> typing.List[typing.Tuple[float, float, Event]]:
    """``[(start, end, event)]``: for every moment of ``[lo, hi)`` in which
    an instruction runs, the INNERMOST one.  The ``XLA Ops`` line nests: a
    ``while`` (a scan over layers) spans the instructions of its body, which
    are events of their own; a parent is cut into what its children leave."""
    out: typing.List[typing.Tuple[float, float, Event]] = []
    stack: typing.List[list] = []        # [event, end, covered up to]

    def close(entry, until):
        if until > entry[2]:
            out.append((entry[2], until, entry[0]))
            entry[2] = until

    for e in sorted(ops, key=lambda e: (e.start, -e.end)):
        start, end = max(e.start, lo), min(e.end, hi)
        if end <= start:
            continue
        while stack and stack[-1][1] <= start:
            done = stack.pop()
            close(done, done[1])
            if stack:
                stack[-1][2] = max(stack[-1][2], done[1])
        if stack:
            close(stack[-1], start)
        stack.append([e, end, start])
    while stack:
        done = stack.pop()
        close(done, done[1])
        if stack:
            stack[-1][2] = max(stack[-1][2], done[1])
    return out


def self_times(ops: typing.Sequence[Event], lo: float, hi: float):
    """``({short name: seconds}, {short name: calls}, {short name: label})``
    of the instructions inside ``[lo, hi)``, each counted for its SELF time
    (``leaf_segments``), so the sums add up to the busy time and a loop does
    not outrank what runs inside it."""
    by_op: typing.Dict[str, float] = {}
    calls: typing.Dict[str, int] = {}
    labels: typing.Dict[str, str] = {}
    for e in ops:
        if min(e.end, hi) > max(e.start, lo):
            key = short_name(e.name)
            calls[key] = calls.get(key, 0) + 1
            labels.setdefault(key, label(e.name))
            by_op.setdefault(key, 0.0)
    for start, end, e in leaf_segments(ops, lo, hi):
        by_op[short_name(e.name)] += (end - start) * 1e-9
    return by_op, calls, labels


def reduce_device(dev: Device, lo: float, hi: float,
                  spans: typing.Dict[str, typing.List[Interval]]) -> dict:
    ops = [e for e in dev.ops if e.end > lo and e.start < hi]
    busy = union(_spans(ops, lo, hi))
    gaps = subtract([(lo, hi)], busy)
    by_op, calls, labels = self_times(ops, lo, hi)
    modules: typing.Dict[str, typing.List[float]] = {}
    for e in dev.modules:
        # whole runs only: a run cut by the window's edge is no step time
        if e.start >= lo and e.end <= hi:
            modules.setdefault(module_name(e.name), []).append(e.seconds)
    # what runs at each moment is the innermost instruction: a synchronous
    # collective inside a scan is exposed although the scan's ``while``
    # spans it
    leaves = leaf_segments(ops, lo, hi)
    coll_sync = [(a, b) for a, b, e in leaves if is_collective(e.name)
                 and not opcode(e.name).endswith(("-start", "-done"))]
    coll_async = _spans((e for e in dev.async_ops if is_collective(e.name)),
                        lo, hi)
    coll = union(coll_sync + coll_async)
    compute = union((a, b) for a, b, e in leaves
                    if not is_collective(e.name))
    idle_by_span: typing.Dict[str, float] = {}
    attributed = []
    for gap in gaps:
        if gap[1] - gap[0] >= MIN_LISTED_GAP_NS:
            attributed.append((attribute(gap, spans),
                               (gap[1] - gap[0]) * 1e-9))
    for name, merged in spans.items():
        idle_by_span[name] = overlap(gaps, merged) * 1e-9
    return {
        "name": dev.name,
        "busy_s": measure(busy) * 1e-9,
        "idle_s": measure(gaps) * 1e-9,
        "ops": by_op, "calls": calls, "labels": labels, "modules": modules,
        "collective_s": measure(coll) * 1e-9,
        "collective_exposed_s": measure(subtract(coll, compute)) * 1e-9,
        "gaps": sorted(attributed, key=lambda g: -g[1]),
        "idle_by_span": idle_by_span,
    }


def reduce(trace: Trace, window_span: typing.Optional[str] = "bench_window",
           span_names: typing.Sequence[str] = ()) -> dict:
    """Everything the per-layer readers and the breakdown take from a
    trace.  Times are seconds; per-device values are under ``per_device``,
    the top level averages them over the chips."""
    if not trace.devices:
        raise ValueError("the trace holds no /device:TPU plane")
    lo, hi = window_of(trace, window_span)
    spans = {n: union(_spans((e for e in trace.host if e.name == n), lo, hi))
             for n in span_names}
    per_device = [reduce_device(d, lo, hi, spans) for d in trace.devices]
    n = len(per_device)

    def mean(key):
        return sum(d[key] for d in per_device) / n

    ops: typing.Dict[str, float] = {}
    for d in per_device:
        for k, v in d["ops"].items():
            ops[k] = ops.get(k, 0.0) + v / n
    first = per_device[0]
    return {
        "window_s": (hi - lo) * 1e-9, "devices": n,
        "busy_s": mean("busy_s"), "idle_s": mean("idle_s"),
        "collective_s": mean("collective_s"),
        "collective_exposed_s": mean("collective_exposed_s"),
        "ops": ops, "calls": first["calls"], "labels": first["labels"],
        "modules": first["modules"], "gaps": first["gaps"],
        "idle_by_span": {k: sum(d["idle_by_span"][k] for d in per_device) / n
                         for k in spans},
        "per_device": per_device,
    }


def kernel_stats(reduced: dict, pattern: str
                 ) -> typing.Dict[str, typing.Tuple[float, int]]:
    """``{kernel kind: (seconds, calls)}`` of the ops whose short name
    matches ``pattern``; the kind is the name without its trailing number
    (``map_mixer_fwd_causal.3`` -> ``map_mixer_fwd_causal``).  Seconds are
    the mean over the chips, calls those of the first chip."""
    rx = re.compile(pattern)
    out: typing.Dict[str, typing.Tuple[float, int]] = {}
    for name, seconds in reduced["ops"].items():
        if rx.search(name):
            kind = re.sub(r"\.\d+$", "", name)
            had = out.get(kind, (0.0, 0))
            out[kind] = (had[0] + seconds,
                         had[1] + reduced["calls"].get(name, 0))
    return out


def breakdown(reduced: dict, top_ops: int = 10, top_gaps: int = 5) -> dict:
    """The ``breakdown`` of a traced run's last line."""
    ops = sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:top_ops]
    return {
        "device_ops": [[reduced["labels"].get(k, k), v] for k, v in ops],
        "idle_gaps": [[name, s] for name, s in reduced["gaps"][:top_gaps]],
    }
