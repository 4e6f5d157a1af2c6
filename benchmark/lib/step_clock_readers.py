"""Readers of the program's step clock (PR 51): the ring
``homebrewnlp_tpu/telemetry/step_clock.py`` keeps with one entry a call of
``Trainer.step``, always on, read over the steps of the run's UNTRACED
window.  The train driver runs the program in the reader's own process, so
the ring is the clock's of the trainer that process built
(``step_clock.current()``).

The window is taken by index: ``WARMUP_STEPS`` steps come before it,
``counters["steps"]`` are in it, the traced window's come after and are left
out.  A step's interval runs from the enter that first saw the step before
it done to the enter that first saw it done; the window's first starts at
the window's first enter (the warm-up was fenced), and the steps no enter of
the window saw done — the last ``RUN_AHEAD + 1``, which the driver's closing
fence waits for — share what is left of ``window_s`` evenly.  What an
interval takes beyond the window's median counts less what the NEXT falls
short of it: a host that was away sees one step late and the next early,
and the device lost nothing (the clock's own rule for naming a stall).

Like ``memory_readers`` every reader takes the ``Run`` and returns a number,
or ``None`` with a line in ``run.notes`` where the program has no step clock
— as a parent of PR 51 has not.
"""
from __future__ import annotations

import typing

from . import readers, stats

#: stalls named in the notes, largest first
STALLS_LISTED = 12


def reduce(clock, first: int, steps: int, window_s: float
           ) -> typing.Optional[dict]:
    """What the four metrics read, from the entries ``first`` to ``first +
    steps`` of ``clock``'s ring; None where the ring no longer holds them."""
    from homebrewnlp_tpu.telemetry import step_clock
    window = clock.entries(first, first + steps)
    if steps < 1 or len(window) != steps:
        return None
    end = first + steps
    origin = window[0].enter_ns
    groups, since, since_at = [], origin, first
    for now in window[1:]:
        seen = [e for e in window if e.ready_at == now.index]
        if seen:
            groups.append((seen, now.enter_ns - since, since_at, now.index))
            since, since_at = now.enter_ns, now.index
    left = [e for e in window if e.ready_at is None or e.ready_at >= end]
    if left:
        groups.append((left, origin + int(window_s * 1e9) - since, since_at,
                       end))
    intervals = [took / len(seen) for seen, took, _, _ in groups
                 for _ in seen]
    median = stats.median(intervals)
    stalls = []
    over = [took - len(seen) * median for seen, took, _, _ in groups]
    worst = 0.0
    for (seen, took, lo, hi), excess, after in zip(groups, over,
                                                   over[1:] + [0]):
        # an enter that merely SAW a step late is followed by an interval as
        # much too short (the step clock's own rule); time the device lost
        # is not given back
        excess += min(0, after)
        if excess <= 0:
            continue
        worst = max(worst, excess / len(seen))
        # the enters after the group's first turn opened, its last included
        depths = [e.depth for e in window[lo - first + 1:hi - first + 1]]
        cause = step_clock.classify(excess, window[lo - first:hi - first],
                                    window, starved=0 in depths)
        stalls.append({"step": seen[-1].index, "steps": len(seen),
                       "excess_s": excess / 1e9, "cause": cause,
                       "host": cause in step_clock.HOST_CAUSES})
    return {"median_s": median / 1e9, "max_s": (median + worst) / 1e9,
            "stalls": stalls, "depths": [e.depth for e in window]}


def window(run) -> typing.Optional[dict]:
    """``reduce`` over the run's untraced window."""
    try:
        from homebrewnlp_tpu.telemetry import step_clock
    except ImportError:
        run.notes.append("the program has no step clock "
                         "(homebrewnlp_tpu/telemetry/step_clock.py)")
        return None
    from ..drivers.train import WARMUP_STEPS
    clock = step_clock.current()
    steps = run.result.counters.get("steps")
    window_s = run.result.spans.get("window_s")
    if clock is None or not steps or not window_s:
        run.notes.append("step clock: no trainer was built, or the driver "
                         "counted no window")
        return None
    found = reduce(clock, WARMUP_STEPS, int(steps), float(window_s))
    if found is None:
        run.notes.append(f"step clock: the ring no longer holds steps "
                         f"{WARMUP_STEPS} to {WARMUP_STEPS + int(steps)}")
        return None
    found["window_s"] = float(window_s)
    return found


def stall_share(run, host_only: bool = False) -> typing.Optional[float]:
    """The seconds the window's steps took beyond their median over the
    window's wall time, percent; ``host_only``: the part whose cause is the
    host's."""
    found = window(run)
    if found is None:
        return None
    stalls = found["stalls"]
    if host_only:
        stalls = [s for s in stalls if s["host"]]
    else:
        worst = sorted(stalls, key=lambda s: -s["excess_s"])[:STALLS_LISTED]
        run.notes.append(
            f"step clock: median interval {found['median_s']:.6f} s over "
            f"{len(found['depths'])} steps; beyond it: " + ("; ".join(
                f"step {s['step']}"
                + (f" (with the {s['steps'] - 1} before it)"
                   if s["steps"] > 1 else "")
                + f" +{s['excess_s']:.6f} s {s['cause']}" for s in worst)
                or "nothing"))
    return readers.share(sum(s["excess_s"] for s in stalls),
                         found["window_s"])


def interval_max_over_median(run) -> typing.Optional[float]:
    found = window(run)
    if found is None:
        return None
    return found["max_s"] / found["median_s"]


def starved_dispatch_share(run) -> typing.Optional[float]:
    """Dispatches of the window that entered with nothing queued on the
    device, after the first ``RUN_AHEAD`` (which fill an empty queue by
    construction), percent."""
    found = window(run)
    if found is None:
        return None
    from ..drivers.train import RUN_AHEAD
    depths = found["depths"][RUN_AHEAD:]
    if not depths:
        return None
    run.notes.append(f"step clock: queue depth at enter, the window's steps "
                     f"after the first {RUN_AHEAD}: min {min(depths)}, "
                     f"max {max(depths)}")
    return readers.share(sum(d == 0 for d in depths), len(depths))
