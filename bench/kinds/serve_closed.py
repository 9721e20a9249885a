"""A closed-loop serving cell: a fixed number of clients, each sending its
next request as soon as its last one has finished, so the engine always has
a full batch and a queue behind it.

``serve_tokens_per_s`` counts every token the engine emitted in the window
(first tokens from prefill included) over the window, which runs from the
return of one engine step to the return of the last step begun within
``--seconds``."""

from __future__ import annotations

import time

from bench import harness, serving, trace
from bench.traffic import RequestStream


def run(run: harness.Run) -> None:
    c, w = run.config, run.workload
    fam = harness.family(c)
    prog = fam.ServeProgram(c, w, run.seed, fault=run.fault)
    loop = serving.Loop(prog.engine, c, prog.slots)
    stream = RequestStream(w["traffic"], c["vocab"], run.seed)

    def top_up():
        while len(loop.live) < w["traffic"]["clients"]:
            tokens, max_new = next(stream)
            loop.submit(tokens, max_new, time.perf_counter())

    def drive(seconds: float) -> float:
        t0 = time.perf_counter()
        while True:
            top_up()
            now = loop.step()
            if now - t0 >= seconds:
                return now - t0

    top_up()
    drive(w["ramp_s"])

    run.mark_window_start()
    win = serving.Accounts()
    loop.accounts.append(win)
    n_done, n_rej = len(loop.done), len(loop.rejected)
    window_s = drive(run.seconds)
    loop.accounts.remove(win)
    run.mark_window_end()
    ended = loop.done[n_done:]
    run.attempted = len(ended) + len(loop.rejected) - n_rej
    run.failed = run.attempted - len(serving.finished(ended))
    run.end_to_end["serve_tokens_per_s"] = win.tokens / window_s
    run.counters.update(kind="serve_closed", window_s=window_s, tokens=win.tokens,
                        flops=win.flops, decode_steps=win.decode_steps,
                        rows=win.rows, slots=prog.slots)

    if run.trace:
        log_dir = f"{run.out_dir}/trace"
        traced = serving.Accounts()
        loop.accounts.append(traced)
        with harness.traced(log_dir):
            drive(w["trace_seconds"])
        loop.accounts.remove(traced)
        run.trace_data = trace.load(trace.find_xplane(log_dir), chips=range(len(run.devices)))
        run.counters.update(traced_decode_steps=traced.decode_steps,
                            traced_decode_bytes=traced.decode_bytes)

    run.read_memory_peak()
    serving.check_served(run, prog, ended)
