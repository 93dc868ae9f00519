"""Requests waiting or running in the router, mean over the window:
``serving_queue_depth`` + ``serving_inflight`` of ``/metrics``, sampled at
4 Hz by the load generator process (traced run only)."""


def read(ctx):
    win = ctx["window"]
    rows = [s for s in win["samples"] if 0.0 <= s["t"] < win["seconds"]
            and "serving_inflight" in s]
    if not rows:
        return None
    return sum(s.get("serving_queue_depth", 0.0) + s["serving_inflight"]
               for s in rows) / len(rows)
