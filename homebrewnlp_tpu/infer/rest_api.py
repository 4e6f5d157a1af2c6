"""REST serving mode (reference: /root/reference/src/rest_api.py).

Endpoints: /completion, /token_completion, /encode, /decode, /health,
/ready, /metrics, mirroring the reference's RestAPI surface (:74-89) plus
the reliability surface from docs/RELIABILITY.md 'Serving' and the
Prometheus scrape target from docs/OBSERVABILITY.md.  fastapi/uvicorn
are optional — when absent (as in this image) a dependency-free fallback
HTTP server provides the same JSON endpoints so web_api mode always works.

Process isolation (default): the HTTP server runs in a daemon SUBPROCESS and
talks to the device loop through Manager-dict/queue IPC, the reference's
uvicorn-subprocess + Manager-dict design (rest_api.py:84-87,
interface.py:231-280) — HTTP parsing and slow clients never block the device
loop, and completions are strictly serialized onto the device from one
process.  ``isolate=False`` keeps everything in-process (handy for tests and
notebook use).

The isolated path is guarded by infer/serving_guard.py: admission control
(429 when the pending budget is full, 400 for requests that cannot succeed),
per-request deadlines (504, shed at batch assembly), a circuit breaker (503
fast-fail after consecutive decode failures), a device-loop heartbeat with
/health + /ready answered by the HTTP child WITHOUT crossing the device
loop, and bounded-backoff relaunch of a crashed HTTP child.  Every accepted
request receives exactly one JSON answer.
"""
from __future__ import annotations

import contextlib
import inspect
import json
import time
import typing
import uuid

from .. import telemetry
from ..telemetry import events as flight
from ..telemetry import tracectx
from ..utils import locks
from ..config import ModelParameter
from .interface import InterfaceWrapper
from .serving_guard import (HTTPStatusError, ServingGuard, child_health,
                            child_ready, poll_delay, request_deadline_s,
                            serve_config, state_metrics, validate_request)

DEFAULT_PORT = 62220

BATCHED_PATHS = ("/completion", "/token_completion")
#: KV-block streaming endpoint (docs/SERVING.md 'Disaggregated tier'):
#: registered only on paged deployments with prefix sharing, answered on
#: the device-loop thread (the one place with executor/carry access) via
#: the non-batched inline branch of ``_engine_classify``
KV_BLOCKS_PATH = "/kv/blocks"
# endpoints load balancers / k8s probe with GET (POST works on them too)
PROBE_PATHS = ("/health", "/ready")
# GET-able endpoints: the probes plus the Prometheus scrape target; like the
# probes, /metrics is answered from shared state + the local registry —
# never by crossing the device loop (docs/OBSERVABILITY.md)
GET_PATHS = PROBE_PATHS + ("/metrics",)
#: Prometheus text exposition content type (format version 0.0.4)
METRICS_CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"

# error payloads ride the responses dict as {"_error": ..., "_status": ...,
# "_code": ...[, "_retry_after": ...]}; the HTTP child renders them with the
# recorded status instead of a blanket 500
_BAD_REQUEST = {"_status": 400, "_code": "bad_request"}
_SERVER_ERROR = {"_status": 500, "_code": "server_error"}
_TIMEOUT = {"_status": 504, "_code": "timeout"}
_UNAVAILABLE = {"_status": 503, "_code": "unavailable"}

# exception types request PARSING raises on malformed-but-valid-JSON input
# (np.asarray on nulls -> TypeError, out-of-int32 tokens / int(Infinity) ->
# OverflowError, filters -> ValueError): answered 400 and — critically —
# NEVER counted as decode failures, or one malformed client could trip the
# breaker and 503 the whole server
_CLIENT_ERRORS = (ValueError, TypeError, OverflowError)


def _err(exc_or_msg, kind: dict) -> dict:
    return {"_error": str(exc_or_msg), **kind}


# ---- serving telemetry (docs/OBSERVABILITY.md) ------------------------------
# Recorded unconditionally: a decode round costs milliseconds-to-seconds,
# the observations nanoseconds — and the registry is what GET /metrics
# serves.  Created lazily ONCE per process (device loop and HTTP child each
# have their own registry; the child merges the device side's IPC-published
# snapshot at scrape time).
_SERVE_METRICS = None


def _serve_metrics() -> dict:
    global _SERVE_METRICS
    if _SERVE_METRICS is None:
        r = telemetry.registry()
        _SERVE_METRICS = {
            "queue_wait": r.histogram(
                "hbnlp_serve_queue_wait_seconds",
                "seconds between HTTP-child enqueue and device-loop pickup"),
            "decode": r.histogram(
                "hbnlp_serve_decode_seconds",
                "wall seconds per decode call (batched calls count once)"),
            "tps": r.histogram(
                "hbnlp_serve_tokens_per_second",
                "generated tokens per second per decode call",
                buckets=(1, 2, 5, 10, 20, 50, 100, 200, 500, 1000, 2000,
                         5000, 10000)),
            "batch": r.histogram(
                "hbnlp_serve_batch_size",
                "completion requests sharing one decode round",
                buckets=(1, 2, 4, 8, 16, 32, 64, 128)),
            # latency anatomy (docs/OBSERVABILITY.md 'Cost attribution'):
            # the monolithic decode histogram split into the two numbers
            # serving SLOs are written against — time to FIRST token per
            # request (admission -> first generated token, measured at the
            # stepped loop's prefill/decode chunk boundary) and the
            # inter-token latency per decode chunk.  Stepped decode loop
            # only (the fused while_loop has no observable chunk boundary).
            "ttft": r.histogram(
                "hbnlp_serve_ttft_seconds",
                "admission to first generated token, per request (stepped "
                "decode loop)"),
            "itl": r.histogram(
                "hbnlp_serve_itl_seconds",
                "seconds per token position within one decode chunk "
                "(stepped decode loop; first chunk includes any prompt "
                "walk)"),
            "cache_bps": r.gauge(
                "hbnlp_decode_cache_read_bytes_per_second",
                "achieved KV-cache read bandwidth of the last decode chunk "
                "(cache bytes x steps / chunk seconds)"),
            "cache_bw_frac": r.gauge(
                "hbnlp_decode_cache_bw_fraction_of_peak",
                "last chunk's cache read bandwidth over the device's peak "
                "HBM bandwidth — ~1.0 means decode sits ON the roofline "
                "PR 2 proved governs it"),
            # continuous-batching engine series (docs/OBSERVABILITY.md +
            # docs/SERVING.md): slot occupancy + the two queueing-theory
            # histograms the capacity model needs, plus lifecycle counters
            "slots_occupied": r.gauge(
                "hbnlp_serve_slots_occupied",
                "engine slots holding a resident request (continuous "
                "engine)"),
            "slots_total": r.gauge(
                "hbnlp_serve_slots_total",
                "configured engine slot-pool width (serve_slots)"),
            "queue_age": r.histogram(
                "hbnlp_serve_queue_age_seconds",
                "seconds a request waited in the engine's pending queue "
                "before a slot freed (observed at admission)"),
            "slot_residency": r.histogram(
                "hbnlp_serve_slot_residency_seconds",
                "seconds a request occupied its slot, admission to "
                "answer/eviction"),
            "admitted": r.counter(
                "hbnlp_serve_engine_admitted_total",
                "requests admitted into an engine slot"),
            "evicted": r.counter(
                "hbnlp_serve_engine_evicted_total",
                "deadline-expired residents evicted at a chunk boundary "
                "(each answered 504 exactly once)"),
            "recycled": r.counter(
                "hbnlp_serve_engine_recycled_total",
                "finished slots recycled for the next admission"),
            # speculative decoding (docs/SERVING.md 'Speculative
            # decoding'): acceptance rate IS the economics of the feature —
            # tokens/sec scales with accepted drafts per verify, so the
            # per-slot acceptance distribution and the accepted-tokens
            # yield are first-class series
            "spec_accept_rate": r.histogram(
                "hbnlp_spec_accept_rate",
                "per-slot per-verify draft acceptance fraction "
                "(accepted / drafted, one sample per verify round)",
                buckets=(0.0, 0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875,
                         1.0)),
            "spec_accepted_per_verify": r.gauge(
                "hbnlp_spec_accepted_tokens_per_verify",
                "running mean of accepted draft tokens per verify step "
                "(the speedup numerator: emitted tokens/verify = this + 1)"),
            "spec_drafted": r.counter(
                "hbnlp_spec_drafted_tokens_total",
                "draft tokens scored by a verify step"),
            "spec_accepted": r.counter(
                "hbnlp_spec_accepted_tokens_total",
                "draft tokens accepted by a verify step"),
            "spec_state": r.gauge(
                "hbnlp_spec_state",
                "speculative decoding state: 1 active, 0 self-disabled "
                "(acceptance below spec_min_accept_rate) or off"),
            "spec_disabled": r.counter(
                "hbnlp_spec_disabled_total",
                "acceptance-collapse self-disables (the engine reverted to "
                "the plain continuous program)"),
            # paged KV block pool (docs/SERVING.md 'Paged KV'): occupancy
            # gauges that prove device KV memory tracks LIVE tokens (not
            # slots x worst-case length), plus the prefix-sharing economics
            "kv_blocks_total": r.gauge(
                "hbnlp_kv_blocks_total",
                "device KV block-pool capacity (kv_pool_blocks resolved)"),
            "kv_blocks_free": r.gauge(
                "hbnlp_kv_blocks_free",
                "KV blocks on the free list (unallocated pool capacity)"),
            "kv_blocks_in_use": r.gauge(
                "hbnlp_kv_blocks_in_use",
                "KV blocks referenced by resident requests — the live-token "
                "device footprint"),
            "kv_blocks_cached": r.gauge(
                "hbnlp_kv_blocks_cached",
                "refcount-0 blocks held by the radix prefix cache "
                "(reusable by future prefix hits, LRU-evicted on demand)"),
            "kv_prefix_lookups": r.counter(
                "hbnlp_kv_prefix_lookups_total",
                "admissions that consulted the radix prefix tree"),
            "kv_prefix_hits": r.counter(
                "hbnlp_kv_prefix_hits_total",
                "admissions that matched a cached prefix and skipped "
                "prefill over the shared span"),
            "kv_prefix_hit_tokens": r.counter(
                "hbnlp_kv_prefix_hit_tokens_total",
                "prompt tokens served from shared blocks instead of "
                "prefill"),
            "kv_cow_copies": r.counter(
                "hbnlp_kv_cow_copies_total",
                "copy-on-write block copies at prefix divergence points"),
            "kv_tree_evictions": r.counter(
                "hbnlp_kv_tree_evictions_total",
                "LRU evictions of refcount-0 radix-cached blocks to refill "
                "the free list"),
        }
    return _SERVE_METRICS


# peak HBM bandwidth of the serving device, read once (device loop only —
# the HTTP child never decodes)
_HBM_PEAK = None


def _hbm_peak() -> float:
    global _HBM_PEAK
    if _HBM_PEAK is None:
        from ..utils.flops import peak_hbm_bandwidth
        _HBM_PEAK = float(peak_hbm_bandwidth())
    return _HBM_PEAK


@contextlib.contextmanager
def _decode_progress(enqueues: typing.Sequence[typing.Optional[float]],
                     closed: typing.Optional[typing.List[bool]] = None):
    """Install the sampler decode-progress hook for one decode call: chunk
    events feed the ITL histogram and the cache-bandwidth gauges; the
    first-token event closes one TTFT observation per co-batched request
    (``enqueues``: each request's admission timestamp — monotonic,
    comparable cross-process; None entries fall back to install time, the
    in-process path's admission proxy).

    ``closed`` (row-aligned with ``enqueues``) carries each request's
    TTFT-already-observed flag across decode ATTEMPTS: a failed batch whose
    chunks already fired some rows' first tokens is retried per row, and
    the retry must not observe a second TTFT sample for them.  None = a
    fresh single-attempt decode."""
    from . import sampler as sampler_mod
    m = _serve_metrics()
    t_install = time.monotonic()
    starts = [t_install if ts is None else ts for ts in enqueues]
    if closed is None:
        closed = [False] * len(starts)

    def hook(event: str, **kw):
        now = time.monotonic()
        if event == "first_token":
            # rows: which co-batched requests' first token THIS event marks
            # (per-row thresholds in the stepped loop — longer prompts fire
            # later); absent = all of them, each closed at most once
            rows = kw.get("rows")
            targets = range(len(starts)) if rows is None else rows
            for i in targets:
                if 0 <= i < len(starts) and not closed[i]:
                    closed[i] = True
                    m["ttft"].observe(max(0.0, now - starts[i]))
        elif event == "chunk":
            steps = int(kw.get("steps") or 0)
            dt = float(kw.get("dt") or 0.0)
            if steps > 0 and dt > 0:
                m["itl"].observe(dt / steps)
                cb = int(kw.get("cache_bytes") or 0)
                if cb:
                    bps = cb * steps / dt
                    m["cache_bps"].set(bps)
                    peak = _hbm_peak()
                    if peak:
                        m["cache_bw_frac"].set(bps / peak)

    prev = sampler_mod.set_decode_progress_hook(hook)
    try:
        yield
    finally:
        sampler_mod.set_decode_progress_hook(prev)


def _record_decode(dt: float, generated_tokens: int):
    m = _serve_metrics()
    m["decode"].observe(dt)
    if dt > 0:
        m["tps"].observe(generated_tokens / dt)


def _metrics_exposition(state=None, queue_depth: int = 0) -> dict:
    """The ``/metrics`` payload: local registry + (child-side) the device
    loop's snapshot from shared IPC state and the guard counters reshaped
    as series.  The ``_prometheus`` key makes both server branches render
    text/plain instead of JSON."""
    parts = []
    if state is not None:
        parts.append(state.get("metrics") or {})
        parts.append(state_metrics(state, queue_depth))
    parts.append(telemetry.snapshot())
    return {"_prometheus": telemetry.prometheus_text(*parts)}


def _prompt_capacity(interface) -> int:
    """InterfaceWrapper.prompt_capacity, with the same ``seq - 1`` fallback
    for interface-alikes (test stubs) that don't define it."""
    cap = getattr(interface, "prompt_capacity", None)
    if cap is not None:
        return int(cap)
    p = interface.params
    return p.sequence_length // p.token_patch_size - 1


def _parse_completion(interface, path: str, body: dict):
    """Parse a /completion / /token_completion body into decode arguments
    ``(tokens, temperature, response_len, top_k, top_p, rep_penalty)``.
    Raises on malformed input — the ONE definition of "client error" for
    completion requests, shared by the handlers, the batch parse loop and
    the single-request pre-check so parse failures (400, never
    breaker-counted) and decode failures (500, breaker-counted) cannot
    drift apart."""
    import numpy as np
    if path == "/completion":
        prompt = body.get("prompt", "")
        if not isinstance(prompt, str):
            # tokenizer.encode on a non-str raises AttributeError, which
            # would (rightly) classify as a server fault — name the real
            # problem as the client error it is
            raise ValueError("prompt must be a string")
        toks = interface.tokenizer.encode(prompt)
    else:
        toks = np.asarray(body.get("tokens", []), np.int32).reshape(-1)
    mt = body.get("max_tokens")
    rl = int(mt) if mt else None
    # serve_max_response_tokens bounds the decode cost of EVERY request:
    # explicit values above it were already rejected 400 at the edge, and an
    # omitted / 0 max_tokens (= "decode the full sequence") is capped here —
    # otherwise the default-shaped request would bypass the cap entirely
    cap = int(getattr(interface.params, "serve_max_response_tokens", 0) or 0)
    if cap:
        rl = cap if rl is None else min(rl, cap)
    temp = float(body.get("temperature", 0.0))
    tk, tp, rp = _parse_filters(body)
    return toks, temp, rl, tk, tp, rp


def _format_completion(interface, path: str, prompt_toks, out,
                       kept_limit: int) -> dict:
    kept = min(len(prompt_toks), kept_limit)
    if path == "/completion":
        # slice at the KEPT prompt length: on a clipped prompt, the raw
        # prompt length would cut into (or past) the generated tokens
        r = {"completion": interface.tokenizer.decode(out[kept:])}
    else:
        r = {"tokens": [int(t) for t in out]}
    if len(prompt_toks) > kept_limit:
        # surface the silent prompt clip so a client can tell a short
        # answer from a truncated prompt; absent on unclipped requests
        # so the happy path stays byte-identical
        r["truncated"] = True
        r["prompt_tokens_kept"] = kept_limit
    return r


def _complete_one(interface, path: str, parsed,
                  enqueue_ts: typing.Optional[float] = None) -> dict:
    """Decode + format ONE parsed completion request — the single shared
    decode path for the handlers and the device loop's single-request
    branch (parsing already happened; any exception here is a decode
    failure).  ``enqueue_ts``: admission timestamp for the TTFT
    histogram (None in the in-process path — decode start stands in)."""
    toks, temp, rl, tk, tp, rp = parsed
    t0 = time.monotonic()
    with _decode_progress([enqueue_ts]):
        out = interface.complete_tokens(toks, temp, rl, top_k=tk, top_p=tp,
                                        repetition_penalty=rp)
    kept_limit = _prompt_capacity(interface)
    _record_decode(time.monotonic() - t0,
                   max(0, len(out) - min(len(toks), kept_limit)))
    return _format_completion(interface, path, toks, out, kept_limit)


def _complete_batch(interface: InterfaceWrapper,
                    items: typing.List[typing.Tuple[str, dict]],
                    deadlines: typing.Optional[typing.List[typing.Optional[float]]] = None,
                    guard: typing.Optional[ServingGuard] = None,
                    clock: typing.Callable[[], float] = time.monotonic,
                    enqueues: typing.Optional[typing.List[typing.Optional[float]]] = None
                    ) -> typing.List[dict]:
    """N queued /completion + /token_completion requests -> ONE decode call
    (InterfaceWrapper.complete_tokens_batch).  Per-item parse errors answer
    that item with a 400 ``_error`` payload without failing the batch; a
    FAILED batch decode retries the items individually once (per-row
    isolation — one poisoned request can't fail its co-batched neighbors)
    and counts the event in the failure counter the breaker reads."""
    kept_limit = _prompt_capacity(interface)
    prompts, temps, rls, tks, tps, rps, idx = [], [], [], [], [], [], []
    results: typing.List[typing.Optional[dict]] = [None] * len(items)
    for i, (path, body) in enumerate(items):
        try:
            # parse EVERYTHING before appending to ANY list: a mid-parse
            # exception (e.g. _parse_filters) must not leave the parallel
            # lists misaligned — row j would then decode row j+1's prompt
            # and answer it to the wrong client
            toks, temp, rl, tk, tp, rp = _parse_completion(interface, path,
                                                           body)
        except Exception as e:
            results[i] = _err(e, _BAD_REQUEST)
            continue
        prompts.append(toks)
        temps.append(temp)
        rls.append(rl)
        tks.append(tk)
        tps.append(tp)
        rps.append(rp)
        idx.append(i)

    def _format(i: int, j: int, out) -> dict:
        return _format_completion(interface, items[i][0], prompts[j], out,
                                  kept_limit)

    if idx:
        # TTFT flags shared across the batch attempt AND its per-row
        # retries: a request whose first token fired during the failed
        # batch must not contribute a second sample from the retry
        ttft_closed = [False] * len(idx)
        try:
            t0 = clock()
            with _decode_progress([enqueues[i] if enqueues else None
                                   for i in idx], closed=ttft_closed):
                outs = interface.complete_tokens_batch(prompts, temps, rls,
                                                       top_ks=tks,
                                                       top_ps=tps,
                                                       rep_penalties=rps)
            _record_decode(clock() - t0,
                           sum(max(0, len(o) - min(len(p), kept_limit))
                               for p, o in zip(prompts, outs)))
            for j, i in enumerate(idx):
                results[i] = _format(i, j, outs[j])
            if guard is not None:
                guard.record_decode_success()
        except Exception:
            if guard is not None:
                guard.record_decode_failure()
            # per-row isolation: retry each item individually ONCE, so the
            # poisoned request fails alone instead of taking the batch down
            for j, i in enumerate(idx):
                dl = deadlines[i] if deadlines else None
                if dl is not None and clock() >= dl:
                    results[i] = _err("deadline expired during the batch "
                                      "retry", _TIMEOUT)
                    continue
                try:
                    t1 = clock()
                    # ttft_closed[j:j+1] copies the flag's CURRENT value:
                    # the retry is this request's last decode, so the
                    # guard only needs the prior attempt's state
                    with _decode_progress([enqueues[i] if enqueues
                                           else None],
                                          closed=ttft_closed[j:j + 1]):
                        out = interface.complete_tokens(
                            prompts[j], temps[j], rls[j], top_k=tks[j],
                            top_p=tps[j], repetition_penalty=rps[j])
                    # retry decodes record too — otherwise the latency
                    # histograms go blind exactly during an incident
                    _record_decode(clock() - t1,
                                   max(0, len(out) - min(len(prompts[j]),
                                                         kept_limit)))
                    results[i] = _format(i, j, out)
                    if guard is not None:
                        guard.record_decode_success()
                except Exception as e:
                    # parsing already succeeded in the loop above, so ANY
                    # exception here — ValueError included — is the decode
                    # failing: a server fault the breaker must see
                    if guard is not None:
                        guard.record_decode_failure()
                    results[i] = _err(e, _SERVER_ERROR)
    return results


def _parse_filters(body: dict):
    """Optional per-request logits filters: absent means "use the config
    serving default" (None). An explicit top_k of 0 (or any value <= 0)
    means "disable top-k for this request" — the sampler treats <= 0 as
    off — so a client can override a server default of top_k > 0."""
    tk, tp = body.get("top_k"), body.get("top_p")
    rp = body.get("repetition_penalty")
    if rp is not None and float(rp) <= 0:
        # r <= 0 would turn seen tokens' logits into inf/NaN downstream —
        # reject loudly (the ValueError renders as HTTP 400)
        raise ValueError(f"repetition_penalty must be > 0, got {rp}")
    return (int(tk) if tk is not None else None,
            float(tp) if tp is not None else None,
            float(rp) if rp is not None else None)


def _handlers(interface: InterfaceWrapper):
    def completion(body: dict) -> dict:
        return _complete_one(interface, "/completion",
                             _parse_completion(interface, "/completion",
                                               body))

    def token_completion(body: dict) -> dict:
        return _complete_one(interface, "/token_completion",
                             _parse_completion(interface, "/token_completion",
                                               body))

    def encode(body: dict) -> dict:
        prompt = body.get("prompt", "")
        if not isinstance(prompt, str):
            raise ValueError("prompt must be a string")
        return {"tokens": [int(t) for t in interface.tokenizer.encode(prompt)]}

    def decode(body: dict) -> dict:
        return {"prompt": interface.tokenizer.decode(body.get("tokens", []))}

    def health(body: dict) -> dict:
        """Ops surface: which decode loop serves this deployment (the
        stepped in-place cache carry vs the fused while_loop — the config's
        ``decode_loop`` knob resolved against the actual cache size) plus
        the decode-call counter.  ``width`` selects a batched-serving
        width; default is the deployment's serve width.  In the isolated
        path this handler is only reached from the in-process fallback —
        the HTTP child answers /health itself (serving_guard.child_health)
        so liveness never crosses the device loop."""
        p = interface.params
        width = int(body.get("width") or 0) or None
        return {"status": "ok",
                "decode_calls": interface.decode_calls,
                "serve_batch_size": int(getattr(p, "serve_batch_size", 1)),
                "decode_path": interface.decode_path(width)}

    def ready(body: dict) -> dict:
        """In-process readiness: serving means the model is loaded and there
        is no queue or breaker in front of it."""
        return {"ready": True, "breaker": "closed", "queue_depth": 0}

    def metrics(body: dict) -> dict:
        """In-process scrape target: the local registry is the only metrics
        source (no IPC state exists).  In the isolated path this handler is
        never reached — the HTTP child intercepts /metrics and merges the
        device loop's published snapshot itself."""
        return _metrics_exposition()

    return {"/completion": completion, "/token_completion": token_completion,
            "/encode": encode, "/decode": decode, "/health": health,
            "/ready": ready, "/metrics": metrics}


def _retry_after_header(retry_after: typing.Optional[float]
                        ) -> typing.Optional[str]:
    # Retry-After is integer seconds; round UP so "0.4s left" doesn't tell
    # the client to hammer immediately
    if retry_after is None:
        return None
    return str(max(1, int(retry_after + 0.999)))


def _headers_aware(dispatch) -> typing.Callable:
    """Adapt a dispatch callable to the 3-arg ``(path, body, headers)``
    shape: dispatchers that declare a third parameter (the HTTP child, the
    replica router — they read the trace header) receive the request
    headers; legacy 2-arg dispatchers (in-process serving, tests) are
    called exactly as before."""
    try:
        sig = inspect.signature(dispatch)
        takes = sum(1 for p in sig.parameters.values()
                    if p.kind in (p.POSITIONAL_ONLY,
                                  p.POSITIONAL_OR_KEYWORD)) >= 3 \
            or any(p.kind == p.VAR_POSITIONAL
                   for p in sig.parameters.values())
    except (TypeError, ValueError):
        takes = False
    if takes:
        return dispatch
    return lambda path, body, headers=None: dispatch(path, body)


def _run_http(port: int, paths: typing.List[str],
              dispatch: typing.Callable[[str, dict], dict], workers: int = 1,
              max_body_bytes: typing.Optional[int] = None):
    """Serve the endpoint set over HTTP, blocking.  ``dispatch(path, body)``
    produces the JSON response (directly, or via IPC to the device loop);
    a dispatch declaring a third parameter also receives the lower-cased
    request headers (the trace-id propagation seam).

    Error classification (satellite: client errors are not server faults):
    oversized/malformed bodies and ValueErrors (e.g. _parse_filters
    rejecting ``repetition_penalty <= 0``) answer 400 with a structured
    ``{"error": ..., "code": "bad_request"}`` payload; HTTPStatusError
    carries its own status (429/503/504 from the guard); anything else is a
    genuine server fault and stays 500."""
    dispatch = _headers_aware(dispatch)
    try:
        import fastapi
        import uvicorn
        from fastapi.responses import JSONResponse
        app = fastapi.FastAPI()
        if max_body_bytes:
            # same pre-read rejection as the fallback server: an oversized
            # body must not cost memory, parsing, or a device call
            @app.middleware("http")
            async def _limit_body(request, call_next):
                if "chunked" in request.headers.get("transfer-encoding",
                                                    "").lower():
                    # no upfront length to check against the cap — reject
                    # rather than buffer an unbounded body
                    return JSONResponse(
                        {"error": "chunked request bodies are not accepted "
                                  "(serve_max_body_bytes is enforced on "
                                  "Content-Length)",
                         "code": "bad_request"}, status_code=400)
                try:
                    length = int(request.headers.get("content-length") or 0)
                except ValueError:
                    return JSONResponse(
                        {"error": "malformed Content-Length header",
                         "code": "bad_request"}, status_code=400)
                if length > max_body_bytes:
                    return JSONResponse(
                        {"error": f"request body of {length} bytes exceeds "
                                  f"serve_max_body_bytes={max_body_bytes}",
                         "code": "bad_request"}, status_code=400)
                return await call_next(request)
        from fastapi.responses import PlainTextResponse

        def _run_dispatch(p, body, headers=None):
            # JSONResponse, not HTTPException: the payload must stay at the
            # TOP level ({"error", "code"}), the one contract both server
            # branches share — HTTPException would wrap it under
            # {"detail": ...}
            try:
                out = dispatch(p, body, headers)
                if isinstance(out, dict) and "_prometheus" in out:
                    # /metrics: Prometheus scrapers need text exposition,
                    # not a JSON-encoded string of it
                    return PlainTextResponse(out["_prometheus"],
                                             media_type=METRICS_CONTENT_TYPE)
                return out
            except HTTPStatusError as e:
                ra = _retry_after_header(e.retry_after)
                return JSONResponse(
                    e.payload, status_code=e.status,
                    headers={"Retry-After": ra} if ra else None)
            except _CLIENT_ERRORS as e:
                return JSONResponse(
                    {"error": str(e), "code": "bad_request"},
                    status_code=400)
            except Exception as e:
                return JSONResponse(
                    {"error": str(e), "code": "server_error"},
                    status_code=500)

        from fastapi.concurrency import run_in_threadpool
        for path in paths:
            def make_endpoint(p=path):
                # parse the body by hand (pydantic's `body: dict` would
                # answer 422 {"detail": ...} for non-object bodies, breaking
                # the shared 400 contract) and run the BLOCKING dispatch
                # poll in the threadpool — on the event loop it would stall
                # every concurrent request, /health probes included, for up
                # to the full request deadline
                async def endpoint(request: fastapi.Request):
                    try:
                        body = json.loads(await request.body() or b"{}")
                    except Exception as e:
                        return JSONResponse(
                            {"error": f"malformed JSON body: {e}",
                             "code": "bad_request"}, status_code=400)
                    if not isinstance(body, dict):
                        return JSONResponse(
                            {"error": "JSON object body required",
                             "code": "bad_request"}, status_code=400)
                    hdrs = {k.lower(): v for k, v in request.headers.items()}
                    if p in GET_PATHS:
                        # probes and /metrics are sub-ms shared-state reads:
                        # answered inline, NOT via the threadpool, whose
                        # bounded tokens slow completion polls can exhaust —
                        # they must stay responsive exactly then
                        return _run_dispatch(p, body, hdrs)
                    return await run_in_threadpool(_run_dispatch, p, body,
                                                   hdrs)
                return endpoint
            app.post(path)(make_endpoint())
            if path in GET_PATHS:
                # load balancers / k8s probe with GET; Prometheus scrapes GET
                def make_get(p=path):
                    async def get_endpoint():
                        return _run_dispatch(p, {})
                    return get_endpoint
                app.get(path)(make_get())
        uvicorn.run(app, host="0.0.0.0", port=port, workers=workers)
        return
    except ImportError:
        pass

    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class Handler(BaseHTTPRequestHandler):
        def _reply(self, status: int, payload: dict,
                   retry_after: typing.Optional[float] = None):
            if isinstance(payload, dict) and "_prometheus" in payload:
                # /metrics: scrapers need the text exposition itself
                data = payload["_prometheus"].encode()
                ctype = METRICS_CONTENT_TYPE
            else:
                data = json.dumps(payload).encode()
                ctype = "application/json"
            self.send_response(status)
            ra = _retry_after_header(retry_after)
            if ra is not None:
                self.send_header("Retry-After", ra)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def do_POST(self):
            if self.path not in paths:
                self.send_response(404)
                self.end_headers()
                return
            if "chunked" in (self.headers.get("Transfer-Encoding")
                             or "").lower():
                # this server never decodes chunked bodies — treating one
                # as empty would silently ignore the client's real payload
                # (and sail past the size cap)
                self.close_connection = True
                self._reply(400, {"error": "chunked request bodies are not "
                                           "accepted", "code": "bad_request"})
                return
            try:
                length = int(self.headers.get("Content-Length") or 0)
            except ValueError:
                length = -1
            if length < 0:
                # a negative length would make rfile.read(-N) read to EOF:
                # a held-open connection then pins this handler thread
                # forever and an oversized body sails past the size cap
                self.close_connection = True
                self._reply(400, {"error": "malformed Content-Length header",
                                  "code": "bad_request"})
                return
            if max_body_bytes and length > max_body_bytes:
                # reject before reading: an oversized body must not cost
                # memory, parsing, or a device call
                self.close_connection = True
                self._reply(400, {"error": f"request body of {length} bytes "
                                           f"exceeds serve_max_body_bytes="
                                           f"{max_body_bytes}",
                                  "code": "bad_request"})
                return
            try:
                body = json.loads(self.rfile.read(length) or b"{}")
            except Exception as e:
                self._reply(400, {"error": f"malformed JSON body: {e}",
                                  "code": "bad_request"})
                return
            if not isinstance(body, dict):
                self._reply(400, {"error": "JSON object body required",
                                  "code": "bad_request"})
                return
            self._dispatch_reply(body)

        def do_GET(self):
            # load balancers / k8s probe /health + /ready with GET;
            # Prometheus scrapes /metrics with GET
            if self.path not in GET_PATHS or self.path not in paths:
                self.send_response(404)
                self.end_headers()
                return
            self._dispatch_reply({})

        def _dispatch_reply(self, body: dict):
            retry_after = None
            hdrs = {k.lower(): v for k, v in self.headers.items()}
            try:
                status, payload = 200, dispatch(self.path, body, hdrs)
            except HTTPStatusError as e:
                status, payload, retry_after = e.status, e.payload, e.retry_after
            except _CLIENT_ERRORS as e:  # client error, not a server fault
                status, payload = 400, {"error": str(e), "code": "bad_request"}
            except Exception as e:  # genuine server fault
                status, payload = 500, {"error": str(e), "code": "server_error"}
            self._reply(status, payload, retry_after)

        def log_message(self, *a):
            pass

    ThreadingHTTPServer(("0.0.0.0", port), Handler).serve_forever()


def _http_child(port: int, paths: typing.List[str], requests, responses,
                workers: int, cfg: typing.Optional[dict] = None, state=None):
    """Subprocess body: HTTP in, Manager IPC to the device loop out.

    The guard decisions that must stay fast when the device loop is slow or
    dead run HERE: edge validation (400), admission control (429), breaker
    fast-fail (503), per-request deadline (504), and /health + /ready built
    from the shared state dict — none of them enqueue onto the device loop.
    """
    import threading
    cfg = cfg or {}
    mono = time.monotonic
    # flight recorder + request tracing (docs/OBSERVABILITY.md): armed only
    # when the parent opted in (trace_requests) — the child then leaves its
    # own blackbox behind, flushes on SIGTERM (terminate() is how the
    # device loop tears it down, and finally never runs there), and stamps
    # every accepted completion with the propagated/minted trace id
    trace_on = bool(cfg.get("trace"))
    bb = cfg.get("blackbox") or {}
    if bb.get("model_path"):
        import atexit as _atexit
        import os as _os
        import signal as _signal
        flight.configure(bb["model_path"], bb.get("tag", "http"),
                         capacity=bb.get("events"))

        def _term(signum, frame):
            flight.flush(reason="sigterm")
            _os._exit(0)

        try:
            _signal.signal(_signal.SIGTERM, _term)
        except (ValueError, OSError):
            pass
        # the fastapi branch's uvicorn.run installs ITS OWN signal
        # handlers (replacing _term) and exits gracefully on TERM — the
        # atexit hook covers that path; the fallback server (whose
        # serve_forever never returns) keeps the handler above
        _atexit.register(lambda: flight.flush(reason="atexit"))

        def _bg_flush():
            # the periodic ring rewrite runs OFF the request-serving
            # threads: a response must never wait on a few-hundred-KB
            # file write (the latency tails tracing exists to explain)
            while True:
                time.sleep(2.0)
                flight.maybe_flush(0.0)

        threading.Thread(target=_bg_flush, daemon=True,
                         name="blackbox-flush").start()
    # child-side admission telemetry (the serving_guard admission decisions
    # happen HERE, so their counters live in this process's registry; the
    # scrape handler below merges the device loop's snapshot in)
    _admission = telemetry.registry().counter(
        "hbnlp_serve_admission_total",
        "HTTP-child admission decisions", ("decision",))
    _adm = {k: _admission.labels(decision=k)
            for k in ("accepted", "rejected_invalid", "rejected_overloaded",
                      "breaker_fast_fail", "deadline_timeout")}
    _requests_ctr = telemetry.registry().counter(
        "hbnlp_http_requests_total", "requests dispatched by the HTTP child",
        ("path",))
    # fallback depth for platforms whose Queue.qsize raises (macOS):
    # dispatches outstanding FROM THIS CHILD (queued + in decode) — close
    # enough for the admission budget and the /ready watermark, and far
    # better than silently disabling both by reporting 0
    outstanding = [0]
    outstanding_lock = locks.named_lock("rest_api.outstanding_lock")

    def queue_depth() -> int:
        # queued + in-decode: the device loop publishes how many requests
        # it drained into the current decode round, so a just-drained queue
        # doesn't read as "no pending load" to the 429 budget or /ready
        try:
            depth = requests.qsize()
        except (NotImplementedError, OSError):
            return outstanding[0]  # fallback already counts in-decode
        if state is not None:
            depth += int(state.get("inflight", 0) or 0)
        return depth

    def dispatch(path: str, body: dict, headers=None) -> dict:
        _requests_ctr.labels(path=path).inc()
        if path == "/metrics":
            # scrape target: local (admission) registry + the device loop's
            # snapshot published over the heartbeat IPC + the guard counters
            # from shared state — never crossing the device loop
            return _metrics_exposition(state, queue_depth())
        if state is not None and path == "/health":
            payload = child_health(state, queue_depth(), cfg)
            if payload["status"] != "ok":
                # stale heartbeat (serve_heartbeat_stale_s): non-200 so a
                # status-code-only liveness probe restarts the replica
                raise HTTPStatusError(503, payload)
            return payload
        if state is not None and path == "/ready":
            ok, payload = child_ready(state, queue_depth(), cfg)
            if not ok:
                raise HTTPStatusError(503, payload, retry_after=1.0)
            return payload
        try:
            validate_request(path, body, cfg)
        except HTTPStatusError:
            _adm["rejected_invalid"].inc()
            raise
        if (state is not None and path in BATCHED_PATHS
                and state.get("breaker") == "open"):
            ra = max(0.0, state.get("breaker_open_until", 0.0) - mono())
            _adm["breaker_fast_fail"].inc()
            raise HTTPStatusError(
                503, {"error": "circuit breaker open: decode is failing",
                      "code": "unavailable"}, retry_after=ra)
        limit = int(cfg.get("queue_limit", 0) or 0)
        if limit and queue_depth() >= limit:
            _adm["rejected_overloaded"].inc()
            raise HTTPStatusError(
                429, {"error": f"server at capacity ({limit} pending "
                               "requests)", "code": "overloaded"},
                retry_after=1.0)
        deadline_s = request_deadline_s(body, cfg)
        deadline = mono() + deadline_s
        rid = uuid.uuid4().hex
        # trace propagation (docs/OBSERVABILITY.md 'Request tracing'): the
        # router's header rides through; an unreplicated edge MINTS the id
        # here.  None when tracing is off — the extra tuple slot always
        # exists so the device loop's unpacking never branches on the knob
        trace = None
        if trace_on and path in BATCHED_PATHS:
            trace = tracectx.trace_id_from_headers(headers) \
                or tracectx.new_trace_id()
            flight.record("request", rid=rid, path=path, trace=trace)
        _adm["accepted"].inc()
        with outstanding_lock:
            outstanding[0] += 1
        enqueue_ts = mono()
        try:
            # the 5th field is the enqueue timestamp: the device loop's
            # queue-wait histogram reads it (CLOCK_MONOTONIC is system-wide,
            # same cross-process argument as the deadline); the 6th is the
            # trace id (None when tracing is off)
            requests.put((rid, path, body, deadline, enqueue_ts, trace))
            delay = 0.0
            while True:
                # pop-with-default: ONE Manager round-trip per poll (a
                # membership probe + pop pair would cost two)
                entry = responses.pop(rid, None)
                if entry is not None:
                    break
                if mono() >= deadline:
                    # the device loop writes its own 504 when it sheds the
                    # request; an uncollected answer is pruned by the loop
                    _adm["deadline_timeout"].inc()
                    raise HTTPStatusError(
                        504, {"error": f"request exceeded its {deadline_s:g}s"
                                       " deadline", "code": "timeout"})
                delay = poll_delay(delay)
                time.sleep(delay)
        finally:
            with outstanding_lock:
                outstanding[0] -= 1
            if trace is not None:
                # record only — the background flusher owns the file IO,
                # never this request's response path
                tracectx.record_span(trace, "http/dispatch", enqueue_ts,
                                     mono() - enqueue_ts, rid=rid)
        out = entry["r"]
        if isinstance(out, dict) and "_error" in out:
            raise HTTPStatusError(
                out.get("_status", 500),
                {"error": out["_error"],
                 "code": out.get("_code", "server_error")},
                retry_after=out.get("_retry_after"))
        return out

    _run_http(port, paths, dispatch, workers,
              max_body_bytes=int(cfg.get("max_body_bytes", 0) or 0))


def _process_group(handlers, interface: InterfaceWrapper,
                   guard: typing.Optional[ServingGuard], responses,
                   group: typing.List[tuple],
                   clock: typing.Callable[[], float] = time.monotonic):
    """One device-loop dispatch round: shed expired requests (504), fast-fail
    everything while the breaker is open (503), admit a single probe while
    half-open, then answer the rest — batched completions share ONE decode
    call.  Invariant: every request in ``group`` gets EXACTLY ONE response
    written into ``responses``."""
    now = clock()

    def respond(rid: str, payload: dict):
        responses[rid] = {"t": now, "r": payload}

    live = []
    qw = _serve_metrics()["queue_wait"]
    for g in group:
        deadline = g[3] if len(g) > 3 else None
        if len(g) > 4 and g[4] is not None:
            qw.observe(max(0.0, now - g[4]))
        if deadline is not None and now >= deadline:
            # answered, not silently dropped: the client learns immediately
            # instead of burning the rest of its timeout
            respond(g[0], _err(f"request expired in the queue ({g[1]})",
                               _TIMEOUT))
            continue
        live.append(g)
    if not live:
        return
    batchable = [g for g in live if g[1] in BATCHED_PATHS]
    # tokenizer-only paths (/encode, /decode, in-process /health) never
    # touch the device, so the breaker does not apply to them
    for g in (g for g in live if g[1] not in BATCHED_PATHS):
        rid, path, body = g[0], g[1], g[2]
        try:
            respond(rid, handlers[path](body))
        except _CLIENT_ERRORS as e:
            respond(rid, _err(e, _BAD_REQUEST))
        except Exception as e:
            respond(rid, _err(e, _SERVER_ERROR))
    if not batchable:
        return
    breaker_state = guard.breaker.tick() if guard is not None else "closed"
    if breaker_state == "open":
        ra = guard.breaker.retry_after()
        for g in batchable:
            respond(g[0], {**_err("circuit breaker open: decode is failing",
                                  _UNAVAILABLE), "_retry_after": ra})
        return
    if breaker_state == "half_open" and len(batchable) > 1:
        # exactly ONE probe decides whether the device recovered; the rest
        # fast-fail rather than pile onto a possibly-still-wedged device
        for g in batchable[1:]:
            respond(g[0], {**_err("circuit breaker half-open: probing",
                                  _UNAVAILABLE), "_retry_after": 1.0})
        batchable = batchable[:1]
    _serve_metrics()["batch"].observe(len(batchable))
    if len(batchable) == 1:
        g0 = batchable[0]
        rid, path, body = g0[0], g0[1], g0[2]
        enqueue = g0[4] if len(g0) > 4 else None
        try:
            # parse first (once) so malformed input answers 400 WITHOUT
            # touching the breaker; past this point any exception is the
            # decode failing (a jax/numpy ValueError included) and the
            # breaker must see it — also what lets a half-open probe always
            # reopen or reclose
            parsed = _parse_completion(interface, path, body)
        except Exception as e:
            respond(rid, _err(e, _BAD_REQUEST))
            return
        try:
            out = _complete_one(interface, path, parsed, enqueue_ts=enqueue)
            if guard is not None:
                guard.record_decode_success()
            respond(rid, out)
        except Exception as e:
            if guard is not None:
                guard.record_decode_failure()
            respond(rid, _err(e, _SERVER_ERROR))
    elif batchable:
        deadlines = [g[3] if len(g) > 3 else None for g in batchable]
        outs = _complete_batch(interface, [(g[1], g[2]) for g in batchable],
                               deadlines=deadlines, guard=guard, clock=clock,
                               enqueues=[g[4] if len(g) > 4 else None
                                         for g in batchable])
        for g, out in zip(batchable, outs):
            respond(g[0], out)


# ---- continuous-batching engine wiring (docs/SERVING.md) --------------------

#: what an optional engine component (draft model, block pool) raises when
#: THIS deployment cannot carry it — no or unreadable draft config, a
#: geometry the component refuses.  Under an "auto" knob these drop the
#: component; every other exception (a trace, compile or device failure) is
#: a broken deployment and propagates
_ENGINE_REFUSALS = (NotImplementedError, ValueError, OSError)


def _resolve_engine(params: ModelParameter, interface):
    """Build the continuous engine's executor, or None for the batch path.

    ``serve_engine``: "batch" never builds one; "continuous" requires one
    (construction failure is a config error and raises); "auto" serves
    through the engine when the interface can carry it — a real
    ``InterfaceWrapper`` over a text model with a streaming decode form —
    and falls back to batch-to-completion otherwise (stub interfaces, video
    models, layers without a streaming form).  Which one was taken is on
    ``/health`` (``engine.mode`` / ``engine.program``)."""
    mode = str(getattr(params, "serve_engine", "auto") or "auto")
    spec_mode = str(getattr(params, "spec_decode", "off") or "off")
    paging = str(getattr(params, "kv_paging", "off") or "off")
    if mode == "batch" and paging == "on":
        # "on" promises paged serving or no serving at all; the batch
        # engine has no block pool — a config contradiction, like
        # spec_decode="draft" + serve_engine="batch"
        raise RuntimeError(
            "kv_paging=\"on\" requires the continuous engine, but "
            "serve_engine=\"batch\" disables it — set serve_engine to "
            "\"auto\"/\"continuous\" or kv_paging to \"off\"/\"auto\"")
    if mode == "batch":
        if spec_mode == "draft":
            # "draft" promises speculation or no serving at all; the batch
            # engine cannot speculate, so the combination is a config
            # contradiction — refuse loudly instead of silently serving
            # batch-to-completion under a knob that says "required"
            raise RuntimeError(
                "spec_decode=\"draft\" requires the continuous engine, but "
                "serve_engine=\"batch\" disables it — set serve_engine to "
                "\"auto\"/\"continuous\" or spec_decode to \"off\"/\"auto\"")
        return None
    slots = max(1, int(getattr(params, "serve_slots", 8) or 1))
    if paging != "off" and spec_mode != "off":
        # the composed deployment (the Engine's "spec_paged_chunk_step"
        # composition): draft-and-verify running over the block pool, one
        # program assembled from the two components.  Fallback is
        # component-wise: a refusal drops into the single-component
        # branches below ordered by which knob is HARD ("on"/"draft" —
        # that component must survive); with both knobs hard any failure
        # is fatal, never a silent drop of an explicit requirement
        try:
            from . import spec as spec_mod
            from .paged import SpecPagedEngineExecutor
            draft = getattr(interface, "draft", None)
            if draft is None:
                draft = spec_mod.load_draft(params)
            return SpecPagedEngineExecutor(
                interface, slots, draft,
                draft_tokens=int(getattr(params, "spec_draft_tokens", 4)),
                min_accept_rate=float(getattr(params,
                                              "spec_min_accept_rate", 0.0)),
                block_tokens=int(getattr(params, "kv_block_tokens", 16)),
                pool_blocks=int(getattr(params, "kv_pool_blocks", 0) or 0))
        except _ENGINE_REFUSALS as e:
            if paging == "on" and spec_mode == "draft":
                raise RuntimeError(
                    "kv_paging=\"on\" and spec_decode=\"draft\" but the "
                    "composed spec-on-paged engine cannot serve this "
                    f"deployment: {e!r}") from e
            print(f"composed spec-on-paged unavailable ({e!r}); falling "
                  "back component-wise")
    if paging != "off" and spec_mode != "draft":
        from .paged import PagedEngineExecutor
        try:
            # NotImplementedError is the ONE auto-fallback signal (geometry
            # the pool cannot carry); an explicit misconfiguration
            # (ValueError, e.g. a kv_pool_blocks too small for one request)
            # or a genuine bug must surface, not silently serve unpaged
            executor = PagedEngineExecutor(
                interface, slots,
                block_tokens=int(getattr(params, "kv_block_tokens", 16)),
                pool_blocks=int(getattr(params, "kv_pool_blocks", 0) or 0))
        except NotImplementedError as e:
            if paging == "on":
                raise RuntimeError(
                    "kv_paging=\"on\" but the paged engine cannot serve "
                    f"this deployment: {e!r}") from e
            print(f"paged KV unavailable ({e!r}); serving the plain "
                  "continuous engine")
        else:
            if spec_mode != "off":
                print("kv_paging engaged without speculation; "
                      "spec_decode=auto is skipped (the composed "
                      "spec-on-paged attempt above refused)")
            return executor
    if spec_mode != "off":
        # speculative decoding rides the continuous engine: build the draft
        # (bench/test callers attach a ready triple as interface.draft; the
        # production path loads spec_draft_model_path through the
        # checkpoint walk) and the spec executor.  "draft" makes any
        # failure fatal; "auto" falls back to the PLAIN continuous engine
        # below — never silently to batch-to-completion
        try:
            from . import spec as spec_mod
            from .engine import SpecEngineExecutor
            draft = getattr(interface, "draft", None)
            if draft is None:
                draft = spec_mod.load_draft(params)
            return SpecEngineExecutor(
                interface, slots, draft,
                draft_tokens=int(getattr(params, "spec_draft_tokens", 4)),
                min_accept_rate=float(getattr(params,
                                              "spec_min_accept_rate", 0.0)))
        except _ENGINE_REFUSALS as e:
            if spec_mode == "draft":
                raise RuntimeError(
                    "spec_decode=draft but speculative decoding cannot "
                    f"serve this deployment: {e!r}") from e
            print(f"speculative decoding unavailable ({e!r}); serving the "
                  "plain continuous engine")
    # "auto" gives way to batch-to-completion on exactly two signals: the
    # interface is not an InterfaceWrapper (test stubs), or the executor
    # says this MODEL has no per-slot streaming form (NotImplementedError
    # at construction).  Anything else — a trace, compile or placement
    # failure on the device — is a broken deployment and propagates
    try:
        from .engine import EngineExecutor
        if not hasattr(interface, "_model_for_width"):
            raise NotImplementedError(
                f"{type(interface).__name__} is not an InterfaceWrapper")
        return EngineExecutor(interface, slots)
    except NotImplementedError as e:
        if mode == "continuous":
            raise RuntimeError(
                "serve_engine=continuous but the engine cannot serve this "
                f"deployment: {e!r}") from e
        print(f"continuous engine unavailable ({e!r}); serving "
              "batch-to-completion")
        return None


def _kv_blocks_handler(params, executor) -> typing.Callable[[dict], dict]:
    """The ``/kv/blocks`` device-loop handler (docs/SERVING.md
    'Disaggregated tier'): ``op=export`` streams the cached whole-block
    prefix of ``tokens`` out in the kv_transfer wire format, ``op=import``
    injects a streamed payload into this replica's pool + radix tree (the
    next admission of that prompt then takes the ordinary prefix-hit
    path), ``op=index`` reports the tree's block-key paths for the
    router's global prefix index.  Malformed payloads raise ValueError —
    rendered 400, never a silent corrupt injection."""
    from . import kv_transfer
    r = telemetry.registry()
    exported = r.counter(
        "hbnlp_disagg_exported_blocks_total",
        "KV blocks this replica streamed OUT via /kv/blocks export")
    injected = r.counter(
        "hbnlp_disagg_injected_blocks_total",
        "KV blocks this replica accepted via /kv/blocks import into its "
        "radix cache")
    max_blocks = int(getattr(params, "kv_transfer_max_blocks", 0) or 0)

    def handler(body: dict) -> dict:
        op = body.get("op") or ("import" if "blocks" in body else "export")
        if op == "index":
            return kv_transfer.index_digest(executor)
        if op == "export":
            out = kv_transfer.export_blocks(executor,
                                            body.get("tokens") or [],
                                            max_blocks=max_blocks)
            exported.inc(len(out["blocks"]))
            return out
        if op == "import":
            out = kv_transfer.inject_blocks(executor, body)
            injected.inc(int(out.get("injected") or 0))
            return out
        raise ValueError(f"unknown /kv/blocks op {op!r} "
                         "(expected export/import/index)")

    return handler


def _engine_answer_fn(interface, respond):
    """Adapter: scheduler outcomes -> the responses-dict payload contract
    (same status/code shapes as the batch path, so clients cannot tell the
    engines apart on errors)."""
    kept_limit = _prompt_capacity(interface)

    def answer(req, outcome):
        kind = outcome[0]
        if kind == "ok":
            try:
                payload = _format_completion(interface, req.path, req.toks,
                                             outcome[1], kept_limit)
            except Exception as e:  # e.g. a tokenizer decode fault — the
                # request still gets exactly one (error) answer instead of
                # the exception killing the device loop
                payload = _err(e, _SERVER_ERROR)
        elif kind == "timeout":
            where = ("in its slot" if outcome[1] == "slot"
                     else "in the queue")
            payload = _err(f"request expired {where} ({req.path})", _TIMEOUT)
        elif kind == "unavailable":
            payload = {**_err("circuit breaker open: decode is failing",
                              _UNAVAILABLE), "_retry_after": outcome[1]}
        else:  # ("error", exc) — a failed engine dispatch
            payload = _err(outcome[1], _SERVER_ERROR)
        respond(req.rid, payload)

    return answer


def _engine_hooks_fn(interface, scheduler, executor):
    """Adapter: controller events -> /metrics series (slot occupancy, queue
    age, residency, admitted/evicted/recycled, TTFT/ITL, cache bandwidth)."""
    m = _serve_metrics()
    m["slots_total"].set(executor.slots)
    # speculative engine: state gauge starts at 1 (active) so a scrape can
    # tell "speculating" from "off" before the first verify lands
    spec = hasattr(executor, "take_spec_events")
    if spec:
        m["spec_state"].set(1)
    verifies = [0]
    pool_seen: typing.Dict[str, int] = {}

    def hooks(event, **kw):
        # telemetry must never fail a decode round — but say so (the
        # stepped loop's safe_hook rule)
        try:
            _record(event, **kw)
        except Exception as exc:
            import warnings
            warnings.warn(f"engine metrics hook failed: {exc!r}")

    def _record(event, **kw):
        now = time.monotonic()
        if event == "chunk":
            interface.decode_calls += 1
            dt, steps = float(kw.get("dt") or 0.0), int(kw.get("steps") or 0)
            m["decode"].observe(dt)
            if steps > 0 and dt > 0:
                m["itl"].observe(dt / steps)
                gen = int(kw.get("generated") or 0)
                if gen:
                    m["tps"].observe(gen / dt)
                cb = int(kw.get("cache_bytes") or 0)
                if cb:
                    bps = cb * steps / dt
                    m["cache_bps"].set(bps)
                    peak = _hbm_peak()
                    if peak:
                        m["cache_bw_frac"].set(bps / peak)
        elif event == "first_token":
            for req in kw.get("reqs", ()):
                start = (req.enqueue_ts if req.enqueue_ts is not None
                         else req.submitted_ts)
                m["ttft"].observe(max(0.0, now - start))
        elif event == "admitted":
            m["admitted"].inc()
            m["queue_age"].observe(float(kw.get("queue_age") or 0.0))
        elif event == "evicted":
            m["evicted"].inc()
        elif event == "recycled":
            m["recycled"].inc()
            m["slot_residency"].observe(float(kw.get("residency") or 0.0))
        elif event == "spec_verify":
            drafted = int(kw.get("drafted") or 0)
            accepted = int(kw.get("accepted") or 0)
            if drafted:
                verifies[0] += 1
                m["spec_accept_rate"].observe(accepted / drafted)
                m["spec_drafted"].inc(drafted)
                m["spec_accepted"].inc(accepted)
                m["spec_accepted_per_verify"].set(
                    getattr(executor, "accepted_total", accepted)
                    / verifies[0])
        elif event == "spec_disabled":
            m["spec_disabled"].inc()
            m["spec_state"].set(0)
        elif event == "pool":
            m["kv_blocks_total"].set(int(kw.get("blocks_total") or 0))
            m["kv_blocks_free"].set(int(kw.get("blocks_free") or 0))
            m["kv_blocks_in_use"].set(int(kw.get("blocks_in_use") or 0))
            m["kv_blocks_cached"].set(int(kw.get("blocks_cached") or 0))
            # the executor reports cumulative pool stats; the counters
            # export deltas so scrape-side rate() stays meaningful
            for key, name in (("prefix_lookups", "kv_prefix_lookups"),
                              ("prefix_hits", "kv_prefix_hits"),
                              ("prefix_hit_tokens", "kv_prefix_hit_tokens"),
                              ("cow_copies", "kv_cow_copies"),
                              ("tree_evictions", "kv_tree_evictions")):
                cur = int(kw.get(key) or 0)
                delta = cur - pool_seen.get(key, 0)
                if delta > 0:
                    m[name].inc(delta)
                pool_seen[key] = cur
        m["slots_occupied"].set(len(scheduler.resident))

    return hooks


class _RequestTracer:
    """Per-request span closure for the continuous engine
    (docs/OBSERVABILITY.md 'Request tracing').  Chained IN FRONT of the
    metrics hooks and AROUND the answer fn, it only observes: queue-wait
    (submit → admission), paged-KV block waits, per-chunk prefill/decode
    occupancy, and the request total — each span recorded into the flight
    recorder (the cross-process form forensics merges) and into a
    per-request Chrome-trace JSON under ``<model_path>/traces/``.  Tracing
    failures warn and never fail a decode round."""

    #: per-request export cap: the traces/ directory keeps the LAST this
    #: many trace_<id>.json files (oldest pruned at export time) — the
    #: same boundedness discipline as the blackbox ring and RotatingJsonl;
    #: a week of traced traffic must not exhaust the model dir's inodes
    MAX_EXPORTS = 1024

    def __init__(self, model_path: str,
                 clock: typing.Callable[[], float] = time.monotonic):
        import collections
        from ..utils import fs
        self.dir = fs.join(model_path, "traces") if model_path else None
        self.clock = clock
        #: rid -> {"trace", "req", "spans", "block_wait_t0"}
        self._live: typing.Dict[str, dict] = {}
        self._exported: typing.Deque[str] = collections.deque()

    def begin(self, reqs: typing.Sequence) -> None:
        for req in reqs:
            if getattr(req, "trace", None):
                self._live[req.rid] = {
                    "trace": req.trace, "req": req,
                    "spans": tracectx.RequestTrace(req.trace, rid=req.rid),
                    "block_wait_t0": None}

    def _entry(self, req) -> typing.Optional[dict]:
        if req is None:
            return None
        return self._live.get(getattr(req, "rid", None))

    def _span(self, entry, name, start_s, dur_s, **fields) -> None:
        entry["spans"].add(name, start_s, dur_s, **fields)
        tracectx.record_span(entry["trace"], name, start_s, dur_s,
                             rid=entry["req"].rid, **fields)

    def hook(self, event: str, **kw) -> None:
        try:
            self._record(event, **kw)
        except Exception as exc:
            import warnings
            warnings.warn(f"request tracer hook failed: {exc!r}")

    def _record(self, event: str, **kw) -> None:
        now = self.clock()
        if event == "admitted":
            entry = self._entry(kw.get("req"))
            if entry is None:
                return
            waited = float(kw.get("queue_age") or 0.0)
            self._span(entry, "queue_wait", now - waited, waited)
            t0 = entry.get("block_wait_t0")
            if t0 is not None:
                entry["block_wait_t0"] = None
                self._span(entry, "kv_block_wait", t0, now - t0)
        elif event == "kv_block_wait":
            entry = self._entry(kw.get("req"))
            if entry is not None and entry.get("block_wait_t0") is None:
                entry["block_wait_t0"] = now
        elif event == "chunk":
            dt = float(kw.get("dt") or 0.0)
            phase = kw.get("phase") or "decode"
            # resident is the scheduler's live slot -> (req, admitted_ts)
            # dict, passed by reference (no per-chunk copy on untraced
            # deployments); snapshot the values here, tracer-side
            for req, _ in list((kw.get("resident") or {}).values()):
                entry = self._entry(req)
                if entry is not None:
                    self._span(entry, f"chunk/{phase}", now - dt, dt,
                               steps=int(kw.get("steps") or 0))
        elif event == "spec_verify":
            # accept/reject rounds are fleet-level events (no per-request
            # attribution inside one verify): cross-process record only
            flight.record("spec_verify",
                          drafted=int(kw.get("drafted") or 0),
                          accepted=int(kw.get("accepted") or 0))

    def finish(self, req, outcome: str) -> None:
        entry = self._live.pop(getattr(req, "rid", None), None)
        if entry is None:
            return
        try:
            now = self.clock()
            t0 = req.submitted_ts or now
            self._span(entry, "request", t0, now - t0, outcome=outcome)
            if self.dir is not None:
                self._exported.append(entry["spans"].dump(self.dir))
                while len(self._exported) > self.MAX_EXPORTS:
                    import os as _os
                    try:
                        _os.remove(self._exported.popleft())
                    except OSError:
                        pass
        except Exception as exc:
            import warnings
            warnings.warn(f"request trace export failed: {exc!r}")

    def wrap_answer(self, answer: typing.Callable) -> typing.Callable:
        def wrapped(req, outcome):
            # answer FIRST: the per-request export is file IO on the
            # device-loop thread (a remote model_path makes it an object-
            # store PUT) — it must never sit between a finished request
            # and its response reaching the HTTP child
            out = answer(req, outcome)
            self.finish(req, outcome[0])
            return out
        return wrapped

    def wrap_hooks(self, hooks: typing.Callable) -> typing.Callable:
        def wrapped(event, **kw):
            self.hook(event, **kw)
            return hooks(event, **kw)
        return wrapped


def _engine_classify(handlers, interface, responses, group, clock):
    """Split one drained IPC group for the engine loop: tokenizer-only
    paths answer inline (never touch the device — breaker-exempt, like the
    batch loop), parse failures answer 400 immediately (never
    breaker-counted), and well-formed completions become EngineRequests."""
    from .scheduler import EngineRequest
    now = clock()
    qw = _serve_metrics()["queue_wait"]
    new_requests = []

    def respond(rid, payload):
        responses[rid] = {"t": now, "r": payload}

    for g in group:
        rid, path, body = g[0], g[1], g[2]
        deadline = g[3] if len(g) > 3 else None
        enqueue = g[4] if len(g) > 4 else None
        if enqueue is not None:
            qw.observe(max(0.0, now - enqueue))
        if deadline is not None and now >= deadline:
            respond(rid, _err(f"request expired in the queue ({path})",
                              _TIMEOUT))
            continue
        if path not in BATCHED_PATHS:
            try:
                respond(rid, handlers[path](body))
            except _CLIENT_ERRORS as e:
                respond(rid, _err(e, _BAD_REQUEST))
            except Exception as e:
                respond(rid, _err(e, _SERVER_ERROR))
            continue
        try:
            toks, temp, rl, tk, tp, rp = _parse_completion(interface, path,
                                                           body)
        except Exception as e:
            respond(rid, _err(e, _BAD_REQUEST))
            continue
        new_requests.append(EngineRequest(
            rid=rid, path=path, toks=toks, temperature=temp,
            response_len=rl, top_k=tk, top_p=tp, rep_penalty=rp,
            deadline=deadline, enqueue_ts=enqueue,
            trace=g[5] if len(g) > 5 else None))
    return new_requests


def serve(params: ModelParameter, interface: InterfaceWrapper,
          workers: int = 1, port: int = DEFAULT_PORT, isolate: bool = True,
          stop: typing.Optional[typing.Any] = None,
          control: typing.Optional[dict] = None):
    """Blocking device loop.  ``stop`` (a ``threading.Event``-alike) makes
    shutdown clean: the loop notices it within its 1s poll, terminates the
    HTTP subprocess, and shuts the Manager down — rather than the Manager
    being GC'd out from under a live ``requests.get`` (which surfaced as an
    EOFError traceback from the serve thread at interpreter teardown).
    ``control``, when given, is populated with live handles for tests/ops
    (``child_pid``, ``state``)."""
    handlers = _handlers(interface)
    # build identity on every scrape (both server branches render it via
    # the shared exposition path; in the isolated path it rides the device
    # loop's published snapshot).  Git rev read once, here — never on the
    # request path.
    telemetry.register_build_info()
    _hbm_peak()  # an unknown device kind fails here, not inside a chunk hook
    if not isolate:
        print(f"serving on :{port} (in-process)")
        return _run_http(port, list(handlers),
                         lambda p, b: handlers[p](b), workers,
                         max_body_bytes=int(getattr(params,
                                                    "serve_max_body_bytes",
                                                    0) or 0))

    import multiprocessing as mp
    import queue as queue_mod
    guard = ServingGuard(params)
    cfg = serve_config(params)
    # request tracing + serving blackboxes (docs/OBSERVABILITY.md): armed
    # by trace_requests — the device loop and the HTTP child then each
    # leave a per-process event file next to the model's checkpoints, and
    # every accepted completion carries a trace id end to end
    trace_on = bool(getattr(params, "trace_requests", False)) \
        and bool(params.model_path)
    if trace_on:
        if not flight.recorder().configured:
            # replica processes configure first (their tag carries the
            # replica index); the single-deployment default is "serve"
            flight.configure(params.model_path, "serve",
                             capacity=getattr(params,
                                              "telemetry_blackbox_events",
                                              4096))
        cfg["trace"] = True
        cfg["blackbox"] = {
            "model_path": params.model_path,
            "tag": f"{flight.recorder().tag or 'serve'}_http",
            "events": getattr(params, "telemetry_blackbox_events", 4096)}
    # spawn, not fork: the parent's JAX/TPU runtime is multithreaded by now
    # and forking it can deadlock the child even though the child never
    # touches JAX.  _http_child's args are all picklable.
    ctx = mp.get_context("spawn")
    manager = ctx.Manager()
    requests = manager.Queue()
    responses = manager.dict()
    state = manager.dict()
    try:
        decode_path = interface.decode_path()
    except Exception:
        decode_path = None  # e.g. video models / stub interfaces
    # engine selection (docs/SERVING.md): continuous batching when the
    # deployment can carry it; the executor owns the device-side slot pool,
    # the controller the host-side scheduling, and this loop only feeds them
    executor = _resolve_engine(params, interface)
    controller = None
    tracer = None
    if executor is not None:
        from .scheduler import EngineController, SlotScheduler
        scheduler = SlotScheduler(executor.slots)

        def _respond(rid, payload):
            responses[rid] = {"t": time.monotonic(), "r": payload}

        answer = _engine_answer_fn(interface, _respond)
        hooks = _engine_hooks_fn(interface, scheduler, executor)
        if trace_on:
            # the tracer only OBSERVES (chained in front of the metrics
            # hooks, around the answer fn): greedy output stays
            # byte-identical with tracing on — pinned by test
            tracer = _RequestTracer(params.model_path)
            answer = tracer.wrap_answer(answer)
            hooks = tracer.wrap_hooks(hooks)
        controller = EngineController(
            executor, scheduler, guard=guard,
            decode_chunk=int(getattr(params, "decode_chunk_tokens", 64)),
            prefill_chunk=int(getattr(params, "serve_prefill_chunk_tokens",
                                      128) or 128),
            answer=answer, hooks=hooks)
    if executor is not None and getattr(executor, "tree", None) is not None:
        # KV-block streaming (disaggregated tier): only a paged deployment
        # WITH prefix sharing can export/import blocks — the endpoint's
        # absence elsewhere keeps non-paged tiers byte-identical
        handlers[KV_BLOCKS_PATH] = _kv_blocks_handler(params, executor)
    engine_info = {"mode": "continuous" if controller else "batch",
                   "slots": executor.slots if executor else 0,
                   "kv_transfer": KV_BLOCKS_PATH in handlers,
                   "replica_class": str(getattr(params,
                                                "serve_replica_class", "")
                                        or "")}
    if executor is not None:
        # which ENGINE_PROGRAMS composition this deployment assembled —
        # the same registry name the HLO/mesh audits and budgets key by
        engine_info["program"] = executor.engine.name
    if hasattr(executor, "spec_summary"):
        # speculative engine: surface the acceptance economics on /health
        # (the live rate rides /metrics; this is the startup config view)
        engine_info["spec"] = executor.spec_summary()
    if hasattr(executor, "pool_stats"):
        # paged engine: block geometry + sharing mode on /health (live
        # occupancy rides the hbnlp_kv_* /metrics gauges)
        engine_info["paging"] = executor.pool_stats()
    state.update(model_loaded=True, decode_path=decode_path, inflight=0,
                 engine=engine_info)
    guard.publish(state, interface)

    def spawn_child():
        p = ctx.Process(target=_http_child,
                        args=(port, list(handlers), requests, responses,
                              workers, cfg, state),
                        daemon=True)
        p.start()
        if control is not None:
            control["child_pid"] = p.pid
            control["state"] = state
        return p

    proc = spawn_child()
    print(f"serving on :{port} (HTTP subprocess pid {proc.pid}; device loop "
          f"in main process)")
    # the device loop: strictly serialized completions in the process that
    # owns the model.  Poll with a timeout so a dead HTTP child surfaces;
    # instead of killing the server, the child is relaunched with bounded
    # exponential backoff (serve_child_max_restarts) — already-queued
    # requests and already-written responses survive the restart.  Answers
    # nobody collected are pruned so the Manager dict cannot grow without
    # bound under client-side timeouts.
    batch_limit = max(1, int(getattr(params, "serve_batch_size", 1) or 1))
    max_restarts = int(getattr(params, "serve_child_max_restarts", 5) or 0)
    backoff = max(0.0, float(getattr(params, "serve_child_restart_backoff_s",
                                     0.5)))
    prune_horizon = cfg["deadline_s"] + 30.0
    base_backoff = backoff
    restarts = 0        # crash-loop budget: reset after a stable window
    total_restarts = 0  # cumulative ops counter published to /health
    child_up_since = time.monotonic()
    # a child that has stayed up this long proved the relaunch recovered:
    # the budget bounds crash LOOPS, not lifetime crash count — without the
    # reset a long-lived server would die on its Nth-ever child crash
    stability_window = 60.0
    last_prune, prune_interval = time.monotonic(), 5.0
    try:
        while stop is None or not stop.is_set():
            # heartbeat + breaker/counter mirror BEFORE blocking on the
            # queue: /health's heartbeat age stays ~poll-period fresh when
            # idle and grows exactly while a decode (or a wedge) runs.
            # Same teardown guard as the queue drain below: the publish
            # touches the Manager, which can be torn down under us
            try:
                guard.publish(state, interface, total_restarts)
            except (EOFError, BrokenPipeError, ConnectionError, OSError):
                break
            # a relaunched child that survived the stability window proved
            # the recovery: reset the crash-loop budget and backoff (checked
            # every iteration — under sustained traffic the empty-poll
            # branch below may never run)
            if (restarts and proc.is_alive()
                    and time.monotonic() - child_up_since > stability_window):
                restarts = 0
                backoff = base_backoff
            # the engine keeps working between arrivals: with requests
            # resident or queued it must dispatch the next chunk, not sit in
            # a 1 s blocking poll
            busy = controller is not None and scheduler.depth() > 0
            drain_limit = (max(batch_limit, 4 * executor.slots)
                           if controller is not None else batch_limit)
            group: typing.List[tuple] = []
            try:
                if not busy:
                    group.append(requests.get(timeout=1.0))
                # drain whatever else queued while the last decode ran —
                # concurrent completions then share ONE decode call (batch)
                # or co-reside in the slot pool (continuous)
                while len(group) < drain_limit:
                    try:
                        group.append(requests.get_nowait())
                    except queue_mod.Empty:
                        break
            except queue_mod.Empty:
                pass
            except (EOFError, BrokenPipeError, ConnectionError, OSError):
                # Manager torn down under us (interpreter exit with the loop
                # in a daemon thread) — stop serving instead of tracebacking
                break
            if not group and not busy:
                if not proc.is_alive():
                    restarts += 1
                    total_restarts += 1
                    if restarts > max_restarts:
                        raise RuntimeError(
                            f"HTTP subprocess exited (code {proc.exitcode}) "
                            f"and {max_restarts} relaunches were exhausted; "
                            "is the port already in use?")
                    print(f"HTTP subprocess died (code {proc.exitcode}); "
                          f"relaunch {restarts}/{max_restarts} in "
                          f"{backoff:.2f}s")
                    if stop is not None:
                        stop.wait(backoff)  # returns early on stop.set()
                    else:
                        time.sleep(backoff)
                    backoff = min(backoff * 2, 30.0)
                    if stop is not None and stop.is_set():
                        break
                    proc = spawn_child()
                    child_up_since = time.monotonic()
                continue
            try:
                now = time.monotonic()
                if now - last_prune > prune_interval:
                    # throttled: the engine loop turns over once per chunk,
                    # and a full responses scan is a Manager round-trip per
                    # entry — per-chunk scans would hammer the IPC process
                    last_prune = now
                    for old_rid, entry in list(responses.items()):
                        if now - entry["t"] > prune_horizon:
                            responses.pop(old_rid, None)
                if controller is not None:
                    new_reqs = _engine_classify(handlers, interface,
                                                responses, group,
                                                time.monotonic)
                    if tracer is not None:
                        tracer.begin(new_reqs)
                    controller.round(new_reqs)
                    # THE admission-budget fix (docs/SERVING.md): requests
                    # the loop drained into the engine — queued behind the
                    # slot pool OR resident in it — still hold budget, so
                    # the child's 429 and the /ready watermark see them.
                    # The batch path's len(group) only ever counted the
                    # current drain.
                    state["inflight"] = scheduler.depth()
                else:
                    # drained-but-decoding requests still occupy the
                    # admission budget: the child adds this to qsize for
                    # 429 and /ready
                    state["inflight"] = len(group)
                    # decode errors are answered inside _process_group; only
                    # a Manager teardown mid-respond can raise out of it
                    _process_group(handlers, interface, guard, responses,
                                   group)
                    state["inflight"] = 0
            except (EOFError, BrokenPipeError, ConnectionError, OSError):
                break
            if trace_on:
                flight.maybe_flush(2.0)
    finally:
        if trace_on:
            flight.flush(reason="serve-exit")
        proc.terminate()
        proc.join(timeout=5.0)
        manager.shutdown()
