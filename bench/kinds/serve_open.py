"""An open-loop serving cell: requests arrive on a seeded Poisson schedule
at a fixed rate whatever the engine does, and each is timed from when it
was due.  Traffic starts ``ramp_s`` before the window; every request due in
the window is followed to its end, for at most ``drain_s`` after the
window, while arrivals go on; one still unfinished then has failed.

``ttft_p95_ms``: due time to the return of the engine step that holds the
request's first token, 95th percentile over the requests due in the window.
``itl_p95_ms``: 95th percentile of the gaps between consecutive tokens of
those requests, as the client sees them at step returns."""

from __future__ import annotations

import time

from bench import harness, serving, trace
from bench.traffic import ArrivalSchedule, RequestStream, percentile


def run(run: harness.Run) -> None:
    c, w = run.config, run.workload
    t = w["traffic"]
    fam = harness.family(c)
    prog = fam.ServeProgram(c, w, run.seed, fault=run.fault)
    loop = serving.Loop(prog.engine, c, prog.slots)
    stream = RequestStream(t, c["vocab"], run.seed)
    arrivals = ArrivalSchedule(t["rate"], t["block"], run.seed)

    t_traffic = time.perf_counter()
    next_due = t_traffic + arrivals.next_due()
    measured = []
    window = [None, None]

    def pump() -> None:
        """Submit everything due by now; step once, or wait for the next
        arrival when there is nothing to do."""
        nonlocal next_due
        now = time.perf_counter()
        while next_due <= now:
            tokens, max_new = next(stream)
            rec = loop.submit(tokens, max_new, next_due)
            if window[0] is not None and window[0] <= next_due < window[1]:
                measured.append(rec)
            next_due = t_traffic + arrivals.next_due()
        if loop.has_work():
            loop.step()
        else:
            with harness.span("gen.wait"):
                time.sleep(max(0.0, min(next_due - time.perf_counter(), 0.01)))

    while time.perf_counter() < t_traffic + w["ramp_s"]:
        pump()
    t_w = run.mark_window_start()
    window[:] = [t_w, t_w + run.seconds]
    backlog_start = len(loop.live)
    win = serving.Accounts()
    loop.accounts.append(win)
    while time.perf_counter() < window[1]:
        pump()
    loop.accounts.remove(win)
    window_s = time.perf_counter() - t_w
    run.mark_window_end()
    backlog_end = len(loop.live)

    if run.trace:
        log_dir = f"{run.out_dir}/trace"
        with harness.traced(log_dir):
            t_end = time.perf_counter() + w["trace_seconds"]
            while time.perf_counter() < t_end:
                pump()
        run.trace_data = trace.load(trace.find_xplane(log_dir), chips=range(len(run.devices)))

    deadline = window[1] + w["drain_s"]
    while any(r.ended is None for r in measured) and time.perf_counter() < deadline:
        pump()
    give_up = time.perf_counter()

    ok = serving.finished(measured)
    run.attempted = len(measured)
    run.failed = len(measured) - len(ok)
    ttft = [((r.first if r.first is not None else give_up) - r.due) * 1e3
            for r in measured]
    itl = [(b - a) * 1e3 for r in measured
           for a, b in zip(r.token_times, r.token_times[1:])]
    run.end_to_end["ttft_p95_ms"] = percentile(ttft, 95)
    run.end_to_end["itl_p95_ms"] = percentile(itl, 95)
    run.counters.update(
        kind="serve_open", window_s=window_s, tokens=win.tokens, flops=win.flops,
        decode_steps=win.decode_steps, rows=win.rows, slots=prog.slots,
        queue_ms=[((r.admitted if r.admitted is not None else give_up) - r.due) * 1e3
                  for r in measured],
        gen_lag_ms=[(r.submitted - r.due) * 1e3 for r in measured],
        ttft_p50_ms=percentile(ttft, 50), requests=len(measured),
        backlog_start=backlog_start, backlog_end=backlog_end,
    )
    run.read_memory_peak()
    serving.check_served(run, prog, measured)
