"""Cross-process request tracing (docs/OBSERVABILITY.md 'Request tracing').

A request served through the replica tier crosses four processes (router →
replica HTTP child → device loop → engine slot); the endpoint histograms
(TTFT/ITL) survive the trip but the per-request story does not.  This
module is the trace substrate:

* a **trace id** is minted at the router (or the HTTP edge when
  unreplicated) and propagated via the ``X-HBNLP-Trace-Id`` header onto the
  request tuple, the scheduler's ``EngineRequest``, and the engine hooks;
* each process closes **spans** against its local monotonic clock —
  queue-wait, admission, per-chunk prefill/decode occupancy, paged-KV block
  waits, spec accept/reject rounds — recorded BOTH into the flight-recorder
  ring (kind ``span``: the cross-process form ``scripts/forensics.py``
  merges) and into a per-request :class:`RequestTrace` exported as
  Chrome-trace JSON under ``<model_path>/traces/``;
* spans on one host share CLOCK_MONOTONIC (the same cross-process argument
  the serving deadlines already rely on); across hosts forensics orders on
  causality, with the wall anchor as the tie-break.

Stdlib-only and device-free, like the rest of ``telemetry/``.  Tracing is
gated by ``trace_requests`` (off by default): with it off no id is minted,
no span closes, and served output is byte-identical by construction.
"""
from __future__ import annotations

import json
import re
import typing
import uuid

#: the propagation header (case-insensitive on read, like all HTTP headers)
TRACE_HEADER = "X-HBNLP-Trace-Id"

#: what a trace id may look like: the minted form is a hex uuid, and a
#: CLIENT-SUPPLIED id becomes a server-side filename (trace_<id>.json), so
#: anything outside this charset — path separators, dots, spaces — is
#: rejected as malformed (the edge then mints a fresh id)
_TRACE_ID_RE = re.compile(r"[0-9A-Za-z_-]{1,64}")


def new_trace_id() -> str:
    return uuid.uuid4().hex


def trace_id_from_headers(headers) -> typing.Optional[str]:
    """Extract the trace id from a dict-like of headers (any case); None
    when absent/malformed.  Accepts plain dicts and mapping-likes."""
    if not headers:
        return None
    try:
        items = headers.items()
    except AttributeError:
        return None
    for k, v in items:
        if str(k).lower() == TRACE_HEADER.lower():
            v = str(v).strip()
            if _TRACE_ID_RE.fullmatch(v):
                return v
    return None


class RequestTrace:
    """Span collection for ONE request: closed spans accumulate, then
    ``dump()`` writes the Chrome-trace JSON (the ``[{"ph": "X"}]`` array
    form plus a summary object Perfetto ignores and tools read)."""

    def __init__(self, trace_id: str, rid: typing.Optional[str] = None):
        self.trace_id = str(trace_id)
        self.rid = rid
        self.spans: typing.List[dict] = []

    def add(self, name: str, start_s: float, duration_s: float,
            **fields) -> dict:
        span = {"name": str(name), "t0": round(float(start_s), 6),
                "dur": round(max(0.0, float(duration_s)), 6), **fields}
        self.spans.append(span)
        return span

    def chrome_events(self) -> typing.List[dict]:
        return [{"name": s["name"], "ph": "X", "pid": 0, "tid": 0,
                 "ts": round(s["t0"] * 1e6, 3),
                 "dur": round(s["dur"] * 1e6, 3),
                 "args": {k: v for k, v in s.items()
                          if k not in ("name", "t0", "dur")}}
                for s in self.spans]

    def hops(self) -> typing.Dict[str, float]:
        """Total seconds per hop category, one request's breakdown.  Chunk
        spans sum per phase; singleton spans report their own duration."""
        out: typing.Dict[str, float] = {}
        for s in self.spans:
            name = s["name"]
            if name.startswith("chunk/"):
                key = name.split("/", 1)[1]
            else:
                key = name
            out[key] = round(out.get(key, 0.0) + s["dur"], 6)
        return out

    def dump(self, dir_path: str) -> str:
        from ..utils import fs
        fs.makedirs(dir_path)
        path = fs.join(dir_path, f"trace_{self.trace_id}.json")
        payload = {"traceEvents": self.chrome_events(),
                   "trace_id": self.trace_id, "rid": self.rid,
                   "hops": self.hops(), "spans": self.spans}
        with fs.open_(path, "w") as f:
            json.dump(payload, f)
        return path


def coverage(spans: typing.Sequence[dict], t0: float, t1: float) -> float:
    """Fraction of the window ``[t0, t1]`` covered by the UNION of span
    intervals — the tracing-e2e acceptance metric (merged spans must cover
    >= 95% of measured client wall time).  Spans are ``{"t0", "dur"}``
    dicts on one monotonic clock."""
    if t1 <= t0:
        return 0.0
    intervals = sorted((max(t0, s["t0"]), min(t1, s["t0"] + s["dur"]))
                       for s in spans)
    covered = 0.0
    cur_start: typing.Optional[float] = None
    cur_end = 0.0
    for a, b in intervals:
        if b <= a:
            continue
        if cur_start is None:
            cur_start, cur_end = a, b
        elif a <= cur_end:
            cur_end = max(cur_end, b)
        else:
            covered += cur_end - cur_start
            cur_start, cur_end = a, b
    if cur_start is not None:
        covered += cur_end - cur_start
    return covered / (t1 - t0)


def spans_from_events(events: typing.Iterable[dict],
                      trace_id: str) -> typing.List[dict]:
    """Pull one trace's span events out of a blackbox event stream (the
    cross-process form): kind ``span`` + matching ``trace``."""
    out = []
    for ev in events:
        if ev.get("kind") == "span" and ev.get("trace") == trace_id:
            out.append({"name": ev.get("name", "?"), "t0": ev.get("t0", 0.0),
                        "dur": ev.get("dur", 0.0),
                        "proc": ev.get("proc")})
    return out


def record_span(trace_id: typing.Optional[str], name: str, start_s: float,
                duration_s: float, **fields) -> None:
    """One span into the process flight recorder (no-op without an id) —
    the cross-process export every tracing layer shares."""
    if not trace_id:
        return
    from . import events as _events
    _events.record("span", trace=str(trace_id), name=str(name),
                   t0=round(float(start_s), 6),
                   dur=round(max(0.0, float(duration_s)), 6), **fields)
