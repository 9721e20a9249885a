"""How late the load generator submitted each request due in the window,
95th percentile.  The generator and the engine share one host thread, so
this is at most about one engine step; a larger value is a starved
generator, not a slow server."""

from bench.traffic import percentile


def read(run):
    k = run.counters
    if k.get("kind") != "serve_open" or not k.get("gen_lag_ms"):
        return None
    return percentile(k["gen_lag_ms"], 95)
