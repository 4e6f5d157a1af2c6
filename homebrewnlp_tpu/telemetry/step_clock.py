"""Step clock: every call of ``Trainer.step`` timed from inside the program,
in any run, traced or not, and every stalled step named with its cause.

A bounded ring, always on, one :class:`Entry` a call.  An entry is one TURN
of the train loop, from this call's enter to the next call's enter: the
dispatch (wall and the calling thread's CPU time), then whatever the host did
before it came back — the waits and the work of the span sites that exist
(``data/next``, ``data/place``, ``train()``'s ``train/metric_log``,
``train/checkpoint_save``, ``train/eval``), the seconds inside the garbage
collector (``gc.callbacks``) and the backend compiles that ended in it
(``compiles.backend_compiles``).  The sites hand their durations over as
they close (``spans.span(listener=...)``); the clock sets no marks of its own.

At each enter the clock asks the losses of the EARLIER steps whether the
device has finished them (``is_ready()``, never a wait — the rule
``Trainer._publish_layer_stats`` keeps).  That gives

* the **queue depth** at enter: how many earlier steps are still on the
  device.  Depth 0 means the device had nothing queued: the host was late;
* each step's **first seen ready**: the time of the first enter that finds
  it done.  Under any bounded run-ahead the differences of these times are
  the device's step intervals (several steps seen at one enter share the
  time since the last one seen evenly).

An interval that exceeds the running median by more than ``STALL_FACTOR``
(and ``MIN_EXCESS_NS``), less what the NEXT interval falls short of the
median, is a stall: a host that was away sees a step done late and the next
one early, and the device lost nothing; time the device lost is not given
back.  So a stall is named one observation after it is seen.
:func:`classify` puts it down to the
part of the host's turns that GREW by at least half the excess — ``compile``,
``dispatch`` (``descheduled`` where its CPU time is far under its wall time),
``data``, ``gc``, ``log_or_save`` — or, when none did, to ``host_other`` if
an enter found the queue empty (the host was late outside every span) and to
``device`` if none did: the host was waiting the whole time.  A stall is
rare, so it records unconditionally: one flight-recorder event ``stall``,
``hbnlp_step_stalls_total{cause}``, ``hbnlp_step_stall_seconds_total{cause}``
and one printed ``step clock:`` line (at most one every ``LINE_EVERY_NS``).
A steady-state step makes no registry call unless the trainer records
(``telemetry_enabled``: the intervals then feed ``hbnlp_step_seconds``).

Stdlib-only, like the rest of the package.  docs/OBSERVABILITY.md 'Step
clock' has the fields, the causes and what a step of it costs.
"""
from __future__ import annotations

import collections
import gc
import statistics
import time
import typing

from . import compiles, events, spans
from .registry import registry as _process_registry

#: entries the ring keeps (the freshest); a week-long run never grows it
CAPACITY = 1024
#: a step is stalled when its interval exceeds the running median by this
#: factor ...
STALL_FACTOR = 1.25
#: ... and by this much: a toy step of a few milliseconds that doubles is the
#: host's jitter, not an event worth a line
MIN_EXCESS_NS = 20_000_000
#: intervals the running median looks back over, and how many it needs
#: before a step is judged against it
MEDIAN_OVER = 64
MIN_INTERVALS = 3
#: at most one printed line in this long (events and counters: every stall)
LINE_EVERY_NS = 5_000_000_000
#: ``dispatch`` reads ``descheduled`` where the thread's CPU time inside it
#: is under this share of its wall time: it was off the core (or blocked
#: inside the runtime), not working
DESCHEDULED_CPU_SHARE = 0.25
#: ... after this allowance: the thread CPU clock of some kernels (the chip
#: machine's among them) moves in ticks of 10 ms
CPU_TICK_NS = 10_000_000

DISPATCH = "train/step_dispatch"
#: span site -> the field of the turn its duration is added to
SITES = {"data/next": "data_next_ns", "data/place": "data_place_ns",
         "train/metric_log": "metric_log_ns",
         "train/checkpoint_save": "checkpoint_save_ns",
         "train/eval": "eval_ns"}
#: cause -> the fields of a turn behind it
CAUSE_FIELDS = {"dispatch": ("dispatch_ns",),
                "data": ("data_next_ns", "data_place_ns"),
                "gc": ("gc_ns",),
                "log_or_save": ("metric_log_ns", "checkpoint_save_ns",
                                "eval_ns")}
#: the causes that are the host's; the one other is ``device``
HOST_CAUSES = ("compile", "dispatch", "descheduled", "data", "gc",
               "log_or_save", "host_other")

STALLS_METRIC = "hbnlp_step_stalls_total"
STALL_SECONDS_METRIC = "hbnlp_step_stall_seconds_total"
STEP_SECONDS_METRIC = "hbnlp_step_seconds"


class Entry:
    """One call of ``Trainer.step`` and the turn of the loop it opens.
    Times are ``time.monotonic`` in ns; ``None`` = not known (yet)."""

    __slots__ = ("index", "enter_ns", "exit_ns", "cpu_ns", "depth",
                 "ready_ns", "ready_at", "interval_ns", "compiles",
                 "data_next_ns", "data_place_ns", "metric_log_ns",
                 "checkpoint_save_ns", "eval_ns", "gc_ns")

    def __init__(self, index: int, enter_ns: int, depth: int):
        #: the step's number since the trainer was built (the ``step`` of
        #: the dispatch span's trace annotation)
        self.index = index
        self.enter_ns = enter_ns
        self.exit_ns: typing.Optional[int] = None
        #: the calling thread's CPU time inside the dispatch
        self.cpu_ns = 0
        #: earlier steps not ``is_ready()`` at this enter
        self.depth = depth
        #: the first enter that found this step's loss ready: its time, and
        #: the index of the step that entered
        self.ready_ns: typing.Optional[int] = None
        self.ready_at: typing.Optional[int] = None
        #: seen-ready to seen-ready, shared evenly among the steps one enter
        #: found ready together
        self.interval_ns: typing.Optional[int] = None
        #: backend compiles that ended in this turn
        self.compiles = 0
        self.data_next_ns = self.data_place_ns = self.metric_log_ns = 0
        self.checkpoint_save_ns = self.eval_ns = self.gc_ns = 0

    @property
    def dispatch_ns(self) -> int:
        return 0 if self.exit_ns is None else self.exit_ns - self.enter_ns


def _total(turns: typing.Sequence[Entry], field: str) -> int:
    return sum(getattr(e, field) for e in turns)


def classify(excess_ns: float, turns: typing.Sequence[Entry],
             usual: typing.Sequence[Entry], starved: bool) -> str:
    """The cause of ``excess_ns`` in an interval during which the host went
    through ``turns``: ``compile`` if one ended there; else ``gc``, or the
    cause whose fields grew most, over what ``usual`` turns spend on them
    (their median, a turn), if that explains at least half the excess; else
    the host at large if the queue ran empty (``starved``), the device if it
    never did."""
    if _total(turns, "compiles"):
        return "compile"
    grew = {}
    for cause, fields in CAUSE_FIELDS.items():
        grew[cause] = sum(
            _total(turns, f) - len(turns) * (
                statistics.median(getattr(e, f) for e in usual)
                if usual else 0)
            for f in fields)
    # a collection runs inside whatever the thread was doing, a span's block
    # as well, whose time then holds it: the collector is asked first
    cause = "gc" if 2 * grew["gc"] >= excess_ns else max(grew, key=grew.get)
    if 2 * grew[cause] >= excess_ns:
        if cause == "dispatch" and _total(turns, "cpu_ns") + CPU_TICK_NS < \
                DESCHEDULED_CPU_SHARE * _total(turns, "dispatch_ns"):
            return "descheduled"
        return cause
    return "host_other" if starved else "device"


def _ms(ns: float) -> str:
    return f"{ns / 1e6:.4g} ms" if ns else "0"


class StepClock:
    """The ring and the stall rule.  Owned by a ``Trainer``
    (``trainer.step_clock``), which passes it as the ``listener`` of its
    span sites and calls :meth:`dispatched` with each step's loss."""

    def __init__(self, record: bool = False, capacity: int = CAPACITY,
                 clock_ns: typing.Callable[[], int] = time.monotonic_ns,
                 cpu_ns: typing.Callable[[], int] = time.thread_time_ns,
                 out: typing.Callable[[str], None] = None):
        self._record = record
        self._clock_ns = clock_ns
        self._cpu_ns = cpu_ns
        self._out = out if out is not None else \
            (lambda line: print(line, flush=True))
        self.ring: typing.Deque[Entry] = collections.deque(maxlen=capacity)
        #: (entry, loss) of the steps not seen ready yet, oldest first
        self._pending: typing.Deque[tuple] = collections.deque(maxlen=capacity)
        self._intervals: typing.Deque[int] = collections.deque(
            maxlen=MEDIAN_OVER)
        #: calls of ``Trainer.step`` so far = the next entry's index
        self.steps = 0
        #: steps seen ready so far (in order: the first ``completed`` ones)
        self.completed = 0
        self._open: typing.Optional[Entry] = None
        self._seen_ns: typing.Optional[int] = None
        self._seen_at: typing.Optional[int] = None
        #: a long interval waiting for the next to confirm it (``_observe``):
        #: (what ``_stall`` is told of it, the excess it must keep, its own)
        self._suspect: typing.Optional[tuple] = None
        self._compiles = compiles.backend_compiles()
        #: a compile ended since the last observation
        self._compiled = False
        self._cpu0 = 0
        self._gc_t0: typing.Optional[int] = None
        self._line_ns: typing.Optional[int] = None
        self._histogram = None

    # -- the ring ---------------------------------------------------------
    def entry(self, index: int) -> typing.Optional[Entry]:
        """The entry of step ``index``, or None once the ring dropped it."""
        if not self.ring:
            return None
        at = index - self.ring[0].index
        return self.ring[at] if 0 <= at < len(self.ring) else None

    def entries(self, lo: int, hi: int) -> typing.List[Entry]:
        """The entries of steps ``lo`` (included) to ``hi`` (not) that the
        ring still holds."""
        return [e for e in map(self.entry, range(lo, hi)) if e is not None]

    # -- the span sites' listener (telemetry/spans.py) ---------------------
    def span_opened(self, name: str, t: float) -> None:
        if name == DISPATCH:
            self._enter(int(t * 1e9))

    def span_closed(self, name: str, t0: float, t1: float) -> None:
        entry = self._open
        if entry is None:
            return
        if name == DISPATCH:
            entry.exit_ns = int(t1 * 1e9)
            entry.cpu_ns = self._cpu_ns() - self._cpu0
            return
        field = SITES[name]
        setattr(entry, field, getattr(entry, field) + int((t1 - t0) * 1e9))

    def _on_gc(self, phase: str, _info) -> None:
        if phase == "start":
            self._gc_t0 = self._clock_ns()
        elif self._gc_t0 is not None and self._open is not None:
            self._open.gc_ns += self._clock_ns() - self._gc_t0
            self._gc_t0 = None

    def dispatched(self, loss) -> None:
        """The step this call dispatched has ``loss`` (anything with
        ``is_ready()``) among its outputs; later enters ask it."""
        self._pending.append((self._open, loss))

    # -- an enter ---------------------------------------------------------
    def _enter(self, t: int) -> None:
        last = self._open
        if last is not None:
            total = compiles.backend_compiles()
            last.compiles = total - self._compiles
            self._compiles = total
            self._compiled |= last.compiles > 0
        pending = self._pending
        seen = []
        while pending and pending[0][1].is_ready():
            seen.append(pending.popleft()[0])
        entry = Entry(self.steps, t, len(pending))
        self.steps += 1
        self.ring.append(entry)
        self._open = entry
        if seen:
            self._observe(t, entry, seen)
        self._cpu0 = self._cpu_ns()

    def _observe(self, t: int, now: Entry, seen: typing.List[Entry]) -> None:
        """``seen`` were first found ready at ``now``'s enter, ``t``."""
        since = self._seen_ns if self._seen_ns is not None \
            else seen[0].enter_ns
        since_at = self._seen_at if self._seen_at is not None \
            else seen[0].index
        took = t - since
        for e in seen:
            e.ready_ns, e.ready_at = t, now.index
            e.interval_ns = took // len(seen)
        self.completed += len(seen)
        self._seen_ns, self._seen_at = t, now.index
        suspect, self._suspect = self._suspect, None
        if len(self._intervals) >= MIN_INTERVALS:
            median = statistics.median(self._intervals)
            excess = took - len(seen) * median
            if suspect is not None:
                # an enter that merely SAW a step late (the host was away,
                # the device was not) is followed by an interval as much
                # too short; time the device lost is not given back
                found, floor, was = suspect
                if was + min(0, excess) > floor:
                    self._stall(t, *found, was + min(0, excess))
            floor = max((STALL_FACTOR - 1) * median, MIN_EXCESS_NS)
            if excess > floor:
                turns = self.entries(since_at, now.index)
                self._suspect = ((now, seen, turns, took, median), floor,
                                 excess)
        if not self._compiled:
            # a step that compiled is no sample of the usual step
            self._intervals.extend([took // len(seen)] * len(seen))
        self._compiled = False
        if self._record:
            if self._histogram is None:
                self._histogram = _process_registry().histogram(
                    STEP_SECONDS_METRIC, "a train step's interval as the "
                    "step clock sees it: first seen ready to first seen "
                    "ready, in seconds")
            for _ in seen:
                self._histogram.observe(took / len(seen) / 1e9)

    def _stall(self, t, now, seen, turns, took, median, excess) -> None:
        """``seen``, found ready at ``now``'s enter after ``took`` and the
        host's ``turns``, cost ``excess`` that the interval after them did
        not give back."""
        depths = [e.depth for e in turns[1:]] + [now.depth]
        first = turns[0] if turns else now     # the ring dropped the turns
        usual = self.entries(first.index - MEDIAN_OVER, first.index)
        cause = classify(excess, turns, usual, starved=0 in depths)
        parts = {f[:-3] + "_s": _total(turns, f) / 1e9
                 for f in ("dispatch_ns", "cpu_ns", *SITES.values(), "gc_ns")}
        events.record(
            "stall", step=seen[-1].index, steps=len(seen),
            interval_s=took / 1e9, median_s=median / 1e9,
            excess_s=excess / 1e9, cause=cause,
            compiles=_total(turns, "compiles"), depth_from=first.depth,
            depth_to=now.depth, depth_min=min(depths), **parts)
        r = _process_registry()
        r.counter(STALLS_METRIC, "steps whose interval exceeded the running "
                  "median by the step clock's factor, by cause",
                  ("cause",)).labels(cause).inc()
        r.counter(STALL_SECONDS_METRIC, "seconds those steps took beyond "
                  "the running median, by cause",
                  ("cause",)).labels(cause).inc(excess / 1e9)
        if self._line_ns is not None and t - self._line_ns < LINE_EVERY_NS:
            return
        self._line_ns = t
        which = f"step {seen[-1].index}" if len(seen) == 1 else \
            f"steps {seen[0].index}-{seen[-1].index}"
        self._out(
            f"step clock: {which} took {took / 1e9:.3f} s for a median of "
            f"{median / 1e9:.3f}{' each' if len(seen) > 1 else ''}: "
            f"+{excess / 1e9:.3f} s, cause {cause}; dispatch "
            f"{_ms(_total(turns, 'dispatch_ns'))} (cpu "
            f"{_ms(_total(turns, 'cpu_ns'))}), data/next "
            f"{_ms(_total(turns, 'data_next_ns'))}, data/place "
            f"{_ms(_total(turns, 'data_place_ns'))}, log/save/eval "
            f"{_ms(sum(_total(turns, f) for f in CAUSE_FIELDS['log_or_save']))}"
            f", gc {_ms(_total(turns, 'gc_ns'))}, compiles "
            f"{_total(turns, 'compiles')}, queue depth {first.depth} -> "
            f"{now.depth}")


# ---- the process's clock ----------------------------------------------------
# ``data/next`` closes inside the prefetcher, which knows no trainer, and the
# collector calls back whoever listens: both reach the clock of the trainer
# built last, which is also what a reader in the same process asks for

_current: typing.Optional[StepClock] = None


def install(clock: StepClock) -> StepClock:
    """Make ``clock`` the process's: the listener of ``data/next`` and of
    the garbage collector, in place of the one installed before."""
    global _current
    if _current is not None and _current._on_gc in gc.callbacks:
        gc.callbacks.remove(_current._on_gc)
    _current = clock
    gc.callbacks.append(clock._on_gc)
    spans.listen("data/next", clock)
    return clock


def current() -> typing.Optional[StepClock]:
    """The clock of the trainer this process built last."""
    return _current
