"""The one generator of request traffic: a schedule drawn from ``--seed``
and a cell's parameters before the window opens, and the open loop that
sends it.

A schedule is a list of requests, each with the time it is DUE (seconds
from the loop's origin), its prompt tokens and the number of new tokens it
asks for.  Parameters (the ``traffic`` object of a cell's file):

  rate_rps        arrivals per second; a schedule of ``d`` seconds holds
                  exactly ``round(rate_rps * d)`` requests
  arrivals        {"kind": "poisson"} — a Poisson process conditioned on
                  that count; or {"kind": "bursts", "period_s": p,
                  "size": [lo, hi]} — every ``p`` seconds on average a
                  burst of lo..hi requests due at once, the same count
  prompt_tokens   {"median", "sigma", "min", "max"}: the quantiles of the
                  clipped lognormal, so every schedule of one count holds
                  the same lengths and the seed only deals them out
  new_tokens      the same, for the tokens asked for
  max_total       prompt + new tokens never exceed it (the model's context)
  shared_prefix   optional {"pool": n, "tokens": t, "share": f}: a share
                  ``f`` of the prompts starts with one of ``n`` fixed
                  prefixes of ``t`` tokens (sessions over a common system
                  prompt); the rest of the prompt is the request's own
  vocab           token ids are drawn from [0, vocab)

The count and the lengths are fixed so that every run of a cell offers the
same work, whatever its seed: with free Poisson counts and free lognormal
draws the offered tokens of a 30-second window at a few requests a second
would differ by ~10% from seed to seed, and so would every number read
from it.  The seed decides when each request is due, which lengths it
gets, and its tokens.

The loop is open: a request is sent when it is due whether or not earlier
ones have been answered, and its latency counts from the time it was DUE,
so a stalled server (or a starved generator) cannot hide its own queue;
how late each send was is recorded beside it.  ``scripts/bench_serving.py``
``_open_loop`` sleeps an exponential AFTER each send and times from the
send — the right idea on the wrong clock; this replaces it.
"""
from __future__ import annotations

import dataclasses
import http.client
import json
import math
import queue
import statistics
import threading
import time
import typing

import numpy as np


@dataclasses.dataclass
class Request:
    index: int
    due_s: float
    prompt: typing.List[int]
    new_tokens: int
    # filled in by the loop
    sent_s: typing.Optional[float] = None
    done_s: typing.Optional[float] = None
    status: typing.Optional[int] = None
    tokens: typing.Optional[typing.List[int]] = None
    error: typing.Optional[str] = None

    def ok(self) -> bool:
        return self.status == 200 and isinstance(self.tokens, list)


def _clipped_lognormal(n: int, spec: dict, rng) -> typing.List[int]:
    """``n`` lengths: the quantiles ``(i + 0.5) / n`` of the clipped
    lognormal, in an order drawn from the seed.  Every schedule of ``n``
    requests therefore holds the SAME lengths — the same work — and the
    seed decides only which request gets which."""
    normal = statistics.NormalDist(math.log(spec["median"]), spec["sigma"])
    values = [int(min(max(round(math.exp(normal.inv_cdf((i + 0.5) / n))),
                          spec["min"]), spec["max"])) for i in range(n)]
    return [values[i] for i in rng.permutation(n)]


def _arrival_times(rng, params: dict, duration_s: float
                   ) -> typing.List[float]:
    """``round(rate * duration)`` due times, whatever the seed: a fixed
    amount of work.  ``poisson``: a Poisson process conditioned on that
    count, which is uniform order statistics.  ``bursts``: a burst of
    lo..hi requests due at once every ``period_s`` on average, the rest of
    the count as Poisson background."""
    total = int(round(float(params["rate_rps"]) * duration_s))
    arrivals = params.get("arrivals", {"kind": "poisson"})
    if arrivals["kind"] == "poisson":
        return sorted(rng.uniform(0.0, duration_s, total).tolist())
    if arrivals["kind"] == "bursts":
        lo, hi = arrivals["size"]
        out: typing.List[float] = []
        for _ in range(int(round(duration_s / float(arrivals["period_s"])))):
            size = min(int(rng.integers(lo, hi + 1)), total - len(out))
            out.extend([float(rng.uniform(0.0, duration_s))] * size)
        out.extend(rng.uniform(0.0, duration_s, total - len(out)).tolist())
        return sorted(out)
    raise ValueError(f"unknown arrivals kind {arrivals['kind']!r}")


def make_schedule(params: dict, seed: int, duration_s: float,
                  offset_s: float = 0.0, part: int = 0
                  ) -> typing.List[Request]:
    """A schedule of ``duration_s`` seconds starting ``offset_s`` after the
    loop's origin, from the seed alone: the same seed and parameters give
    the same requests at the same times.  ``part`` numbers the pieces a
    driver strings together (ramp, window): each draws from its own
    stream, and each holds its own fixed count and lengths."""
    rng = np.random.default_rng([int(seed), int(part)])
    vocab = int(params.get("vocab", 256))
    shared = params.get("shared_prefix")
    pool = [rng.integers(0, vocab, int(shared["tokens"])).tolist()
            for _ in range(int(shared["pool"]))] if shared else []
    out = []
    dues = _arrival_times(rng, params, duration_s)
    prompts = _clipped_lognormal(len(dues), params["prompt_tokens"], rng)
    news = _clipped_lognormal(len(dues), params["new_tokens"], rng)
    for i, (due, n_prompt, n_new) in enumerate(zip(dues, prompts, news)):
        n_new = max(1, min(n_new, int(params["max_total"]) - n_prompt))
        prompt = rng.integers(0, vocab, n_prompt).tolist()
        if shared and rng.random() < float(shared["share"]):
            prefix = pool[int(rng.integers(len(pool)))]
            prompt = (prefix + prompt)[:max(n_prompt, len(prefix) + 1)]
            prompt = prompt[:int(params["max_total"]) - n_new]
        out.append(Request(i + 100_000 * part, offset_s + float(due), prompt,
                           n_new))
    return out


def post_completion(host: str, port: int, req: Request, deadline_s: float
                    ) -> None:
    """One blocking ``/token_completion``; fills in the request's status,
    tokens or error."""
    body = json.dumps({"tokens": req.prompt, "max_tokens": req.new_tokens,
                       "temperature": 0.0, "timeout_s": deadline_s})
    conn = http.client.HTTPConnection(host, port, timeout=deadline_s + 10)
    try:
        conn.request("POST", "/token_completion", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        payload = resp.read()
        req.status = resp.status
        if resp.status == 200:
            req.tokens = json.loads(payload).get("tokens")
        else:
            req.error = payload[:200].decode(errors="replace")
    except (OSError, http.client.HTTPException, ValueError) as exc:
        req.status, req.error = 599, repr(exc)
    finally:
        conn.close()


def run_open_loop(host: str, port: int, schedule: typing.List[Request],
                  deadline_s: float, clients: int,
                  origin: typing.Optional[float] = None) -> float:
    """Send every request of ``schedule`` when it is due (seconds after
    ``origin``, a ``time.monotonic()`` reading; now if None) from this
    thread, through ``clients`` worker threads that each hold one request
    at a time; returns the origin once every request has been answered or
    has failed.  A request all workers are too busy to take waits in the
    hand-over queue, and the wait shows as its lateness."""
    todo: "queue.Queue" = queue.Queue()
    origin = time.monotonic() if origin is None else origin

    def worker():
        while True:
            req = todo.get()
            if req is None:
                return
            req.sent_s = time.monotonic() - origin
            post_completion(host, port, req, deadline_s)
            req.done_s = time.monotonic() - origin

    threads = [threading.Thread(target=worker, daemon=True,
                                name=f"bench-client-{i}")
               for i in range(clients)]
    for t in threads:
        t.start()
    for req in schedule:
        wait = origin + req.due_s - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        todo.put(req)
    for _ in threads:
        todo.put(None)
    for t in threads:
        t.join(timeout=deadline_s + 30)
    return origin


def lateness_ms(schedule: typing.Sequence[Request]) -> typing.List[float]:
    return [(r.sent_s - r.due_s) * 1e3 for r in schedule
            if r.sent_s is not None]
