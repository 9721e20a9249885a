"""Time from a request's due time to the start of the engine step that
admitted it, 95th percentile over the requests due in the window."""

from bench.traffic import percentile


def read(run):
    k = run.counters
    if k.get("kind") != "serve_open" or not k.get("queue_ms"):
        return None
    return percentile(k["queue_ms"], 95)
