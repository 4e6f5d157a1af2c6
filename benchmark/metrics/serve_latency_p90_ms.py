"""90th percentile of (reply received - time the request was due) over the
requests due in the window: the highest percentile a window of ~80 requests
supports with ten samples beyond it, so it is read beside the judged median
and carries no bound of its own."""

LAYER = "L1_scheduler"
MOVES = "serve_latency_p50_ms"


def read(run):
    return run.result.end_to_end.get("serve_latency_p90_ms")
