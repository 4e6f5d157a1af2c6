"""Readers of the program's memory gauges (PR 34): ``hbnlp_hbm_bytes{point,
kind}``, what the runtime reported for the fullest local device when the
program reached a point of its run (``homebrewnlp_tpu/telemetry/memory.py``),
and ``hbnlp_train_state_bytes{kind}``, what the train state is made of.

The program sets them long before a traced window opens, so these readers do
not depend on when the harness reads anything.  Like ``program_readers`` they
take the ``Run`` and return a number, or ``None`` with a line in
``run.notes`` where the program's registry holds no such series — as a
parent without the gauges has not, and as XLA:CPU, which reports no memory,
leaves it.
"""
from __future__ import annotations

import typing

from . import program_readers, readers

HBM = "hbnlp_hbm_bytes"
STATE = "hbnlp_train_state_bytes"


def gauge(run, metric: str, **labels: str) -> typing.Optional[float]:
    """The one series of ``metric`` whose labels include ``labels`` (the
    program may stamp constant labels of its own on every series)."""
    entry = program_readers.snapshot().get(metric)
    found = []
    if entry is not None:
        names = tuple(entry.get("labels", ()))
        found = [value for key, value in entry["series"].items()
                 if labels.items() <= dict(zip(names, key)).items()]
    if len(found) != 1:
        run.notes.append(
            f"MISSING: the program's registry holds {len(found)} series of "
            f"{metric} with labels {labels}")
        return None
    return float(found[0])


def hbm(run, point: str, kind: str) -> typing.Optional[float]:
    return gauge(run, HBM, point=point, kind=kind)


def hbm_share(run, point: str, kinds: typing.Sequence[str]
              ) -> typing.Optional[float]:
    """The sum of ``kinds`` at ``point`` over the limit read at the same
    point, percent."""
    limit = hbm(run, point, "limit")
    if limit is None:
        return None
    parts = [hbm(run, point, kind) for kind in kinds]
    if any(p is None for p in parts):
        return None
    run.notes.append(
        f"{point}: " + " + ".join(f"{k} {int(p)}" for k, p in zip(kinds, parts))
        + f" of limit {int(limit)} bytes")
    return readers.share(sum(parts), limit)
