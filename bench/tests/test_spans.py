"""Host spans on the device's clock: the clock offset from wait spans,
idle stretches named after the innermost span, the engine's host time per
step from its spans, and the engine's counters against the benchmark's
own accounting of a serving loop."""

import os
import time
from types import SimpleNamespace

import pytest

from bench import harness, serving, spans, trace
from bench.tests import toy
from bench.traffic import RequestStream

RECORDED = os.path.join(os.path.dirname(__file__), "data", "tiny_v5e.xplane.pb")
OFF = 7  # ns the host learns of an execution's end after the device


def op(name, s, e):
    return (f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %a), kind=kLoop", s, e)


def engine_trace():
    """Two engine steps of 100 ns on the host clock: expire, admit holding a
    prefill and its wait, decode, decode wait, retire.  The device runs the
    prefill and decode programs; the host spans run ``OFF`` ns late."""
    host = [("bench.window", 0, 260)]
    ops, modules = [], []
    for base in (0, 120):
        host += [("engine.step", base + 5, base + 105),
                 ("engine.expire", base + 5, base + 10),
                 ("engine.admit", base + 10, base + 40),
                 ("engine.prefill", base + 10, base + 20),
                 ("engine.prefill_wait", base + 20, base + 35),
                 ("engine.decode", base + 40, base + 50),
                 ("engine.decode_wait", base + 50, base + 90),
                 ("engine.retire", base + 90, base + 105)]
        # device: prefill 12-28, decode 45-83 on the device clock
        ops += [op("fusion.1", base + 12, base + 28),
                op("fusion.2", base + 45, base + 83)]
        modules += [("jit_prefill_step(1)", base + 12, base + 28),
                    ("jit_decode_step(2)", base + 45, base + 83)]
    host = [(n, s + OFF, e + OFF) for n, s, e in host]
    return trace.Trace(window=(OFF, 260 + OFF), chips={0: trace.ChipTrace(ops, modules)},
                       host_spans=host)


def test_clock_offset_is_the_median_wait_end_less_execution_end():
    t = engine_trace()
    # decode waits end at 90 + OFF, their executions at 83
    assert spans.clock_offset_ns(t, "engine.decode_wait", "jit_decode_step") == 90 + OFF - 83
    assert spans.clock_offset_ns(t, "train.wait", "jit_decode_step") is None
    assert spans.clock_offset_ns(t, "engine.decode_wait", "jit_other") is None


def test_innermost_span_covers_most_of_a_gap():
    nested = [("engine.step", 0, 100), ("engine.decode_wait", 10, 60), ("engine.retire", 60, 70)]
    assert spans.innermost_cover((50, 80), nested) == \
        {"engine.decode_wait": 10, "engine.retire": 10, "engine.step": 10}
    assert spans.innermost_cover((95, 110), nested) == \
        {"engine.step": 5, spans.OUTSIDE: 10}
    assert spans.innermost_cover((30, 40), [("bench.window", 0, 100)]) == {spans.OUTSIDE: 10}


def test_idle_time_is_named_after_inner_spans_on_the_device_clock():
    t = engine_trace()
    off = spans.clock_offset_ns(t, "engine.decode_wait", "jit_decode_step")
    idle = spans.idle_by_span(t, off)
    window_idle = t.window_s - t.busy_s(0)
    assert sum(idle.values()) == pytest.approx(window_idle)
    inside = spans.idle_by_span(t, off, within="engine.step")
    # on the device clock every idle stretch inside a step lies under an
    # inner span: expire and prefill before the prefill runs, admit,
    # decode and the start of the decode wait after it, retire at the end
    assert set(inside) <= {"engine.expire", "engine.admit", "engine.prefill",
                           "engine.prefill_wait", "engine.decode", "engine.decode_wait",
                           "engine.retire", "engine.step"}
    assert "engine.step" not in inside
    assert sum(inside.values()) < sum(idle.values())
    # the raw clocks would put part of that time under other spans
    assert spans.idle_by_span(t, 0.0, within="engine.step") != inside
    names = [g[0] for g in spans.idle_gaps(t, off)]
    assert "engine.step" not in names and len(names) <= 10


def test_unnested_spans_keep_the_names_of_the_recorded_trace():
    tiny = trace.load(RECORDED)
    assert spans.clock_offset_ns(tiny, "train.wait", "jit__lambda") is None
    assert spans.idle_gaps(tiny, 0.0, 1000) == tiny.idle_gaps(1000)
    idle = spans.idle_by_span(tiny)
    assert sum(idle.values()) == pytest.approx(tiny.window_s - tiny.busy_s(0))
    lines = spans.notes(tiny, "train", "jit__lambda")
    assert lines[0].endswith("not estimated (no wait span paired)")


def fake_serve_run(t):
    return SimpleNamespace(counters={"kind": "serve_closed"}, trace_data=t, notes=[])


def test_engine_host_ms_is_the_step_less_its_waits():
    read = harness.metric_reader("engine_host_ms.serve_batch")
    run = fake_serve_run(engine_trace())
    # each step: 100 ns less 15 ns of prefill wait and 40 ns of decode wait
    assert read(run) == pytest.approx(45e-6)
    assert any("clock offset: 0.0 us" in n for n in run.notes)
    assert read(fake_serve_run(trace.load(RECORDED))) is None  # no engine spans
    assert read(SimpleNamespace(counters={"kind": "train"}, trace_data=engine_trace(),
                                notes=[])) is None


def test_engine_counters_agree_with_the_loops_accounting():
    """The engine's ``stats()`` over a stretch of closed-loop serving: the
    rows it decoded are the tokens the benchmark's loop counts as decoded,
    and its host and wait seconds fit inside the steps' wall time."""
    c, w = toy.cell("smollm-135m.serve-batch")
    prog = harness.family(c).ServeProgram(c, w, 3)
    loop = serving.Loop(prog.engine, c, prog.slots)
    stream = RequestStream(w["traffic"], c["vocab"], 3)
    acc = serving.Accounts()
    loop.accounts.append(acc)
    before = prog.engine.stats()
    wall = 0.0
    for _ in range(12):
        while len(loop.live) < w["traffic"]["clients"]:
            loop.submit(*next(stream), time.perf_counter())
        t0 = time.perf_counter()
        loop.step()
        wall += time.perf_counter() - t0
    after = prog.engine.stats()
    d = {k: after[k] - before[k] for k in after}
    prog.free()
    assert d["steps"] == acc.steps == 12
    assert d["decoded_rows"] == acc.rows > 0
    assert d["decode_steps"] == acc.decode_steps
    assert d["prefills"] > 0 and d["prompt_tokens"] <= d["prefill_tokens"]
    assert d["host_s"] + d["prefill_wait_s"] + d["decode_wait_s"] == pytest.approx(d["step_s"])
    assert 0 < d["step_s"] <= wall
