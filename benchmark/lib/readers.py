"""What several per-layer readers share.  Every function takes the ``Run``
(cell, configuration, driver's result, reduced trace) and returns a number,
or None where there is nothing to read."""
from __future__ import annotations

import re
import typing

from ..roofline import costs
from ..trace import reduce as reduce_mod
from . import stats


def span(run, name: str) -> typing.Optional[float]:
    return run.result.spans.get(name)


def share(part: typing.Optional[float], whole: typing.Optional[float]
          ) -> typing.Optional[float]:
    """``part / whole`` in percent."""
    if part is None or not whole:
        return None
    return 100.0 * part / whole


def module_median_ms(run, program: str) -> typing.Optional[float]:
    """Median device time of one run of the program the cell's file names
    under ``programs[program]`` (a regex over the trace's module names)."""
    if run.trace is None or program not in run.cell.spec.get("programs", {}):
        return None
    rx = re.compile(run.cell.spec["programs"][program])
    runs = [d for name, ds in run.trace["modules"].items()
            if rx.search(name) for d in ds]
    if not runs:
        run.notes.append(f"{program}: no module matches {rx.pattern!r} among "
                         f"{sorted(run.trace['modules'])}")
        return None
    run.notes.append(f"{program}: {len(runs)} whole runs in the traced "
                     f"window")
    return stats.median(runs) * 1e3


def per_chip_shape(config: dict) -> typing.Tuple[int, int, int, int]:
    """``(b, s, h, k)`` one chip holds: the batch over the mesh's data
    axis, the heads over its model axis."""
    mesh = config.get("mesh_shape_override") or {}
    return (config["train_batch_size"] // mesh.get("data", 1),
            config["sequence_length"],
            config["heads"] // mesh.get("model", 1),
            config["features_per_head"])


def kernel_time_share(run, pattern: str) -> typing.Optional[float]:
    """The kernel's device time over the device's busy time, percent."""
    if run.trace is None:
        return None
    kinds = reduce_mod.kernel_stats(run.trace, pattern)
    if not kinds:
        return None
    return share(sum(s for s, _ in kinds.values()), run.trace["busy_s"])


def kernel_roofline(run, pattern: str) -> typing.Optional[float]:
    """The least time the chip could take for the kernel's calls (the
    larger of operations over peak FLOP/s and bytes over peak bytes/s, from
    ``roofline/costs.py``) over the time they took, percent."""
    if run.trace is None:
        return None
    kinds = reduce_mod.kernel_stats(run.trace, pattern)
    if not kinds:
        return None
    peak = costs.peaks(run.result.device["kind"])
    shape = per_chip_shape(run.config)
    least = took = 0.0
    for kind, (seconds, calls) in sorted(kinds.items()):
        flops, bytes_ = costs.kernel_cost(kind, *shape)
        floor, bound = costs.least_seconds(flops, bytes_, peak)
        run.notes.append(
            f"{kind}: {calls} calls, {seconds / calls * 1e3:.4f} ms each, "
            f"{flops / 1e9:.3f} GFLOP and {bytes_ / 1e6:.3f} MB a call, "
            f"{bound}-bound floor {floor * 1e3:.4f} ms "
            f"({100 * floor * calls / seconds:.2f}%)")
        least += floor * calls
        took += seconds
    return share(least, took)


def histogram_quantile_ms(run, series: str, q: float
                          ) -> typing.Optional[float]:
    """A quantile of what the server observed in ``series`` during the
    window (bucket deltas between the two scrapes), milliseconds."""
    hist = run.result.counters.get("histograms", {}).get(series)
    if not hist:
        return None
    value = stats.bucket_quantile(hist, q)
    if value is None:
        return None
    run.notes.append(f"{series}: {int(hist['count'])} observations in the "
                     f"window, mean {hist['sum'] / hist['count'] * 1e3:.3f} ms")
    return value * 1e3
