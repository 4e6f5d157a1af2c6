"""Medians, percentiles and Prometheus histogram deltas.

``scrape`` / ``delta`` / ``bucket_quantile`` are copied from
``scripts/bench_serving.py`` (``_scrape_buckets``, ``_quantiles``) and
``telemetry/registry.py`` (``histogram_quantile``), with one change: the
quantile is interpolated inside its bucket instead of reporting the
bucket's upper bound, so a median between 0.25 s and 0.5 s does not read
as 0.5 s.
"""
from __future__ import annotations

import math
import re
import typing


def percentile(values: typing.Sequence[float], q: float) -> float:
    """Linear-interpolated percentile, ``q`` in [0, 100]."""
    if not values:
        raise ValueError("percentile of nothing")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: typing.Sequence[float]) -> float:
    return percentile(values, 50.0)


def parse_metrics(text: str) -> dict:
    """A Prometheus text exposition -> ``{series: value}`` for plain
    samples and ``{name: {"bounds", "cumulative", "sum", "count"}}`` for
    histograms (label-free series only; that is what the server exports
    for the series read here)."""
    out: dict = {}
    buckets: typing.Dict[str, list] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = re.match(r'^(\w+)_bucket\{le="([^"]+)"\} ([-+0-9.eE]+|\+Inf)$',
                     line)
        if m:
            le = math.inf if m.group(2) == "+Inf" else float(m.group(2))
            buckets.setdefault(m.group(1), []).append(
                (le, float(m.group(3))))
            continue
        m = re.match(r"^(\w+) ([-+0-9.eE]+|NaN|\+Inf)$", line)
        if m:
            out[m.group(1)] = float(m.group(2))
    for name, pairs in buckets.items():
        pairs.sort()
        out[name] = {"bounds": [b for b, _ in pairs if b != math.inf],
                     "cumulative": [c for _, c in pairs],
                     "sum": out.pop(f"{name}_sum", 0.0),
                     "count": out.pop(f"{name}_count", 0.0)}
    return out


def histogram_delta(before: typing.Optional[dict], after: dict) -> dict:
    """Per-bucket counts, sum and count of what was observed between two
    scrapes of one histogram."""
    cum = list(after["cumulative"])
    total, count = after["sum"], after["count"]
    if before:
        cum = [a - b for a, b in zip(cum, before["cumulative"])]
        total -= before["sum"]
        count -= before["count"]
    counts = [c - (cum[i - 1] if i else 0) for i, c in enumerate(cum)]
    return {"bounds": list(after["bounds"]), "counts": counts,
            "sum": total, "count": count}


def bucket_quantile(hist: dict, q: float) -> typing.Optional[float]:
    """The ``q`` quantile (0..1) of a histogram delta, interpolated
    linearly inside its bucket; the ``+Inf`` bucket reports the largest
    finite bound.  None when nothing was observed."""
    total = sum(hist["counts"])
    if total <= 0:
        return None
    rank, cum = q * total, 0.0
    bounds = hist["bounds"]
    for i, c in enumerate(hist["counts"]):
        if c and cum + c >= rank:
            if i >= len(bounds):
                return float(bounds[-1])
            lo = bounds[i - 1] if i else 0.0
            return lo + (bounds[i] - lo) * (rank - cum) / c
        cum += c
    return float(bounds[-1])
