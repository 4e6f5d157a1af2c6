"""What a driver hands back to ``run.py``, and what ``run.py`` hands to a
per-layer metric's reader."""
from __future__ import annotations

import dataclasses
import typing


class NoAccelerator(Exception):
    """jax found no TPU, or another number of chips than the cell asks."""


def footprint(stats) -> tuple:
    """``(bytes, capacity)`` of the fullest chip from ``memory_stats()``
    dicts read at the end of the window: live buffers plus the largest
    reservation the runtime has held for loaded programs' temporaries.  The
    TPU runtime keeps that reservation OUTSIDE ``bytes_in_use`` (long-context
    step: 3.80 GB in use, 7.22 GB reserved, against 10.54 GiB from the
    compiler's own ``memory_analysis()``; PERF.md section 3), and its
    cumulative ``peak_bytes_in_use`` would also count the benchmark's own
    reference check, which is not the program's memory.  The reservation's
    high-water mark is taken because the current one read lower after a
    traced window in one run (4.10 against 5.13 GB, PERF.md section 6).  For
    a loop of one fixed program this footprint is its peak."""
    return (max((m.get("bytes_in_use", 0) + m.get("peak_bytes_reserved", 0)
                 for m in stats), default=0),
            max((m.get("bytes_limit", 0) for m in stats), default=0))


@dataclasses.dataclass
class Context:
    """One run, as ``run.py`` describes it to the driver."""
    cell: typing.Any             # lib.cell.Cell
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool
    t_start: float               # time.monotonic() at process start
    out_dir: str
    log: typing.Callable[[str], None]
    #: a sweep's override of the cell file's ``weights_seed`` (``run.py
    #: --weights-seed``); the driver's check never gives it
    weights_seed: typing.Optional[int] = None


@dataclasses.dataclass
class Result:
    """One run, as the driver measured it."""
    end_to_end: typing.Dict[str, float]
    correct: bool
    checks: dict                 # what decided ``correct``, with its numbers
    attempted: int
    failed: int
    device: dict                 # platform, kind, count, memory_peak_bytes
    spans: typing.Dict[str, float]        # host clocks, seconds
    counters: dict               # program counters and counts of the run
    #: each number that decided ``correct`` beside its limit: ``{name:
    #: {"value": v, "at_most" | "above": limit}}``; the result line's last key
    compared: dict = dataclasses.field(default_factory=dict)
    trace_path: typing.Optional[str] = None
    trace_window: typing.Optional[str] = None
    trace_spans: typing.Sequence[str] = ()


@dataclasses.dataclass
class Run:
    """What a per-layer reader sees: the cell, the driver's result and —
    in a traced run — the reduced trace (``trace.reduce.reduce``'s dict).
    ``notes`` collects lines a reader wants on the run's log (which bound
    a roofline share met, how many samples a quantile had)."""
    cell: typing.Any
    config: dict
    result: Result
    trace: typing.Optional[dict]
    notes: typing.List[str] = dataclasses.field(default_factory=list)
