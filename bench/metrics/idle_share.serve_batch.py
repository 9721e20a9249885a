"""Share of the traced closed-loop serving window in which no op ran on
the chip."""


def read(run):
    if run.counters.get("kind") != "serve_closed" or run.trace_data is None:
        return None
    return 100.0 * run.trace_data.idle_share()
