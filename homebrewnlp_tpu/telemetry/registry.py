"""Process-wide metrics registry (the tentpole of docs/OBSERVABILITY.md).

The reference framework's only observability was TF1 ``summary_ops_v2``
scalars hosted out via ``tpu.outside_compilation`` (SURVEY §L2); this module
is the measurement substrate every layer records into instead: a
thread-safe registry of Counter / Gauge / Histogram metrics with labels,
rendered as Prometheus text exposition (``GET /metrics``) or JSONL lines,
and snapshottable into a plain picklable dict so the serving path can ship
it across the HTTP-child IPC boundary without the child ever touching the
device loop.

Deliberately stdlib-only (``threading`` + ``bisect``): it must be importable
from the spawned HTTP child subprocess, from utils/retry.py (under fs), and
from tests without jax.  Clocks are injectable for deterministic tests.

Hot-path discipline: the registry itself is cheap (a lock + a bisect per
histogram observation, ~1 µs) but the TRAIN step loop makes exactly ZERO
calls into it unless ``telemetry_enabled`` is set — call sites gate on the
knob once and pre-bind label children outside the loop (run/train_loop.py).
"""
from __future__ import annotations

import bisect
import json
import math
import threading
import typing

from ..utils import locks

#: default latency buckets (seconds): spans from sub-ms host ops to
#: multi-minute checkpoint uploads
DEFAULT_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                   0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0)

LabelValues = typing.Tuple[str, ...]


def _fmt(v: float) -> str:
    """Prometheus sample-value formatting: integral floats render without
    the trailing ``.0`` noise, everything else with full precision."""
    if v != v:
        return "NaN"
    if v in (math.inf, -math.inf):
        return "+Inf" if v > 0 else "-Inf"
    if float(v).is_integer() and abs(v) < 2 ** 53:
        return str(int(v))
    return repr(float(v))


def _escape(value: str) -> str:
    """Label-value escaping per the text exposition format."""
    return (str(value).replace("\\", "\\\\").replace("\n", "\\n")
            .replace('"', '\\"'))


def _escape_help(value: str) -> str:
    """HELP-line escaping: format 0.0.4 escapes ONLY backslash and line
    feed here — a double quote must pass through verbatim (label-value
    escaping is the stricter three-character rule above)."""
    return str(value).replace("\\", "\\\\").replace("\n", "\\n")


def _label_str(names: typing.Sequence[str], values: LabelValues) -> str:
    if not names:
        return ""
    inner = ",".join(f'{n}="{_escape(v)}"' for n, v in zip(names, values))
    return "{" + inner + "}"


class _Child:
    """One labelled series of a metric; the object call sites pre-bind and
    hammer, so every operation is a lock + an arithmetic op."""

    __slots__ = ("_metric", "_key")

    def __init__(self, metric: "_Metric", key: LabelValues):
        self._metric = metric
        self._key = key

    def inc(self, amount: float = 1.0):
        m = self._metric
        if m.kind == "histogram":
            raise TypeError("histograms observe(), they don't inc()")
        with m._lock:
            if m.kind == "counter" and amount < 0:
                raise ValueError("counters only go up")
            m._series[self._key] = m._series.get(self._key, 0.0) + amount

    def set(self, value: float):
        m = self._metric
        if m.kind != "gauge":
            raise TypeError(f"set() is gauge-only, {m.name} is {m.kind}")
        with m._lock:
            m._series[self._key] = float(value)

    def observe(self, value: float):
        m = self._metric
        if m.kind != "histogram":
            raise TypeError(f"observe() is histogram-only, {m.name} is {m.kind}")
        value = float(value)
        i = bisect.bisect_left(m.buckets, value)
        with m._lock:
            state = m._series.get(self._key)
            if state is None:
                state = m._series[self._key] = \
                    {"counts": [0] * (len(m.buckets) + 1), "sum": 0.0}
            state["counts"][i] += 1
            state["sum"] += value

    def get(self) -> typing.Any:
        """Current value (scalar, or the histogram state dict) — test/ops
        convenience, not part of the render path."""
        with self._metric._lock:
            v = self._metric._series.get(self._key)
            return dict(v) if isinstance(v, dict) else v


class _Metric:
    def __init__(self, name: str, help_: str, kind: str,
                 labelnames: typing.Sequence[str] = (),
                 buckets: typing.Sequence[float] = DEFAULT_BUCKETS):
        self.name = name
        self.help = help_
        self.kind = kind
        self.labelnames = tuple(labelnames)
        self.buckets = tuple(sorted(float(b) for b in buckets)) \
            if kind == "histogram" else ()
        self._lock = locks.named_lock(f"_Metric._lock:{name}", meter=False)
        self._series: typing.Dict[LabelValues, typing.Any] = {}
        self._children: typing.Dict[LabelValues, _Child] = {}
        self._default = _Child(self, ())

    def labels(self, *values, **kw) -> _Child:
        if kw:
            values = tuple(str(kw[n]) for n in self.labelnames)
        else:
            values = tuple(str(v) for v in values)
        if len(values) != len(self.labelnames):
            raise ValueError(f"{self.name} takes labels {self.labelnames}, "
                             f"got {values}")
        child = self._children.get(values)
        if child is None:
            # racing creators build equal children; last write wins, both
            # record into the same _series entry — no lock needed here
            child = self._children[values] = _Child(self, values)
        return child

    # label-less metrics are used directly
    def inc(self, amount: float = 1.0):
        self._require_unlabelled().inc(amount)

    def set(self, value: float):
        self._require_unlabelled().set(value)

    def observe(self, value: float):
        self._require_unlabelled().observe(value)

    def _require_unlabelled(self) -> _Child:
        if self.labelnames:
            raise ValueError(f"{self.name} has labels {self.labelnames}; "
                             "bind them with .labels() first")
        return self._default


class Registry:
    """Named-metric table.  ``registry()`` below returns the process-wide
    instance; tests construct private ones (and can swap the global via
    ``set_registry``)."""

    def __init__(self):
        self._lock = locks.named_lock("Registry._lock", meter=False)
        self._metrics: typing.Dict[str, _Metric] = {}

    def _get_or_create(self, name: str, help_: str, kind: str,
                       labelnames: typing.Sequence[str],
                       buckets: typing.Sequence[float] = DEFAULT_BUCKETS
                       ) -> _Metric:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = _Metric(name, help_, kind,
                                                  labelnames, buckets)
            elif m.kind != kind or m.labelnames != tuple(labelnames):
                raise ValueError(
                    f"metric {name} re-registered as {kind}{tuple(labelnames)}"
                    f" but exists as {m.kind}{m.labelnames}")
            return m

    def counter(self, name: str, help_: str = "",
                labelnames: typing.Sequence[str] = ()) -> _Metric:
        return self._get_or_create(name, help_, "counter", labelnames)

    def gauge(self, name: str, help_: str = "",
              labelnames: typing.Sequence[str] = ()) -> _Metric:
        return self._get_or_create(name, help_, "gauge", labelnames)

    def histogram(self, name: str, help_: str = "",
                  labelnames: typing.Sequence[str] = (),
                  buckets: typing.Sequence[float] = DEFAULT_BUCKETS
                  ) -> _Metric:
        return self._get_or_create(name, help_, "histogram", labelnames,
                                   buckets)

    def snapshot(self) -> dict:
        """Plain picklable dict of everything recorded so far — the IPC/
        cross-process form every renderer below consumes.  Series keys are
        label-value tuples; histogram states are copied so the caller can
        ship or mutate them freely."""
        out = {}
        with self._lock:
            metrics = list(self._metrics.values())
        for m in metrics:
            with m._lock:
                series = {
                    k: ({"counts": list(v["counts"]), "sum": v["sum"]}
                        if isinstance(v, dict) else v)
                    for k, v in m._series.items()}
            out[m.name] = {"kind": m.kind, "help": m.help,
                           "labels": m.labelnames,
                           "buckets": list(m.buckets), "series": series}
        return out


# ---- renderers (pure functions over snapshots) -----------------------------

def prometheus_text(*snapshots: dict) -> str:
    """Render snapshot(s) as Prometheus text exposition (format 0.0.4).
    Multiple snapshots are merged first (``merge_snapshots``) — the serving
    path combines the HTTP child's own registry with the device loop's
    IPC-published one."""
    snap = snapshots[0] if len(snapshots) == 1 else merge_snapshots(*snapshots)
    lines = []
    for name in sorted(snap):
        m = snap[name]
        if m["help"]:
            lines.append(f"# HELP {name} {_escape_help(m['help'])}")
        lines.append(f"# TYPE {name} {m['kind']}")
        labelnames = tuple(m.get("labels", ()))
        for key in sorted(m["series"]):
            val = m["series"][key]
            if m["kind"] == "histogram":
                bounds = m["buckets"]
                cum = 0
                for b, c in zip(bounds, val["counts"]):
                    cum += c
                    lines.append(f"{name}_bucket"
                                 f"{_hist_labels(labelnames, key, b)} {cum}")
                cum += val["counts"][len(bounds)]
                lines.append(f"{name}_bucket"
                             f"{_hist_labels(labelnames, key, math.inf)} {cum}")
                ls = _label_str(labelnames, key)
                lines.append(f"{name}_sum{ls} {_fmt(val['sum'])}")
                lines.append(f"{name}_count{ls} {cum}")
            else:
                lines.append(f"{name}{_label_str(labelnames, key)} "
                             f"{_fmt(val)}")
    return "\n".join(lines) + "\n"


def _hist_labels(names, key, bound: float) -> str:
    le = "+Inf" if bound == math.inf else _fmt(bound)
    inner = ",".join([f'{n}="{_escape(v)}"' for n, v in zip(names, key)]
                     + [f'le="{le}"'])
    return "{" + inner + "}"


def render_json(snap: dict) -> dict:
    """JSON-safe form of a snapshot (label tuples joined into flat series
    keys): one ``json.dumps`` of this is a telemetry.jsonl line."""
    out = {}
    for name, m in snap.items():
        series = {}
        for key, val in m["series"].items():
            k = ",".join(f"{n}={v}" for n, v in zip(m.get("labels", ()), key))
            if m["kind"] == "histogram":
                series[k] = {"counts": list(val["counts"]),
                             "sum": val["sum"],
                             "count": sum(val["counts"])}
            else:
                series[k] = val
        out[name] = {"kind": m["kind"], "buckets": list(m.get("buckets", ())),
                     "series": series}
    return out


def jsonl_line(snap: dict, **extra) -> str:
    return json.dumps({**extra, "metrics": render_json(snap)},
                      sort_keys=True)


def merge_snapshots(*snapshots: dict) -> dict:
    """Combine snapshots from different processes: counter and histogram
    series SUM (each process observed disjoint events), gauges take the
    LAST snapshot's value (later argument wins — pass the fresher/local
    one last)."""
    out: dict = {}
    for snap in snapshots:
        for name, m in snap.items():
            if name not in out:
                out[name] = {"kind": m["kind"], "help": m.get("help", ""),
                             "labels": tuple(m.get("labels", ())),
                             "buckets": list(m.get("buckets", ())),
                             "series": {
                                 k: (dict(counts=list(v["counts"]),
                                          sum=v["sum"])
                                     if isinstance(v, dict) else v)
                                 for k, v in m["series"].items()}}
                continue
            tgt = out[name]
            if m["kind"] == "histogram" and \
                    list(m.get("buckets", ())) != list(tgt["buckets"]):
                # zip() over mismatched bucket lists would silently drop
                # counts; processes must agree on boundaries to merge
                raise ValueError(
                    f"histogram {name}: bucket boundaries differ between "
                    f"snapshots ({tgt['buckets']} vs "
                    f"{list(m.get('buckets', ()))}) — cannot merge")
            for key, val in m["series"].items():
                cur = tgt["series"].get(key)
                if cur is None or m["kind"] == "gauge":
                    tgt["series"][key] = (dict(counts=list(val["counts"]),
                                               sum=val["sum"])
                                          if isinstance(val, dict) else val)
                elif m["kind"] == "histogram":
                    cur["counts"] = [a + b for a, b in
                                     zip(cur["counts"], val["counts"])]
                    cur["sum"] += val["sum"]
                else:
                    tgt["series"][key] = cur + val
    return out


def with_labels(snap: dict, labels: typing.Dict[str, str]) -> dict:
    """A copy of ``snap`` with constant ``labels`` appended to EVERY series
    (label names already present on a metric are left alone — the caller's
    per-series value wins).  This is how multi-host snapshots carry their
    process identity: each host tags its own snapshot once, and
    ``merge_snapshots`` then unions the per-process series instead of
    summing counters that belong to different hosts into anonymity."""
    out: dict = {}
    for name, m in snap.items():
        have = tuple(m.get("labels", ()))
        add = [(k, str(v)) for k, v in sorted(labels.items())
               if k not in have]
        names = have + tuple(k for k, _ in add)
        values = tuple(v for _, v in add)
        out[name] = {"kind": m["kind"], "help": m.get("help", ""),
                     "labels": names, "buckets": list(m.get("buckets", ())),
                     "series": {tuple(key) + values:
                                (dict(counts=list(v["counts"]), sum=v["sum"])
                                 if isinstance(v, dict) else v)
                                for key, v in m["series"].items()}}
    return out


# ---- process-wide instance --------------------------------------------------

_registry = Registry()
_registry_lock = locks.named_lock("registry._registry_lock", meter=False)

#: constant labels stamped onto every module-level ``snapshot()`` — the
#: multi-host bootstrap sets {"process": "<index>"} once so every exported
#: series (jsonl, /metrics, cross-host merge) names the host it came from
_constant_labels: typing.Dict[str, str] = {}


def set_constant_labels(labels: typing.Optional[typing.Dict[str, str]]
                        ) -> typing.Dict[str, str]:
    """Install the constant labels ``snapshot()`` applies (None/{} clears);
    returns the previous mapping so tests can restore it."""
    global _constant_labels
    prev = _constant_labels
    _constant_labels = dict(labels or {})
    return prev


def registry() -> Registry:
    """The process-wide registry every instrumented layer records into."""
    return _registry


def set_registry(reg: typing.Optional[Registry]) -> Registry:
    """Swap the process-wide registry (tests isolate themselves with a fresh
    one); ``None`` installs a new empty registry.  Returns the PREVIOUS
    registry so callers can restore it."""
    global _registry
    with _registry_lock:
        prev = _registry
        _registry = reg if reg is not None else Registry()
    return prev


def snapshot() -> dict:
    snap = registry().snapshot()
    return with_labels(snap, _constant_labels) if _constant_labels else snap
