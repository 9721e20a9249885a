"""Trace reduction on a small trace recorded on a TPU v5e: a jitted
512x512 bfloat16 matmul run three times inside ``train.step`` spans, with
2 ms host sleeps between them, all inside ``bench.window``."""

import os

import jax
import jax.numpy as jnp
import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "tiny_v5e.xplane.pb")


@pytest.fixture(scope="module")
def tiny():
    return trace.load(DATA)


def test_window_and_busy(tiny):
    lo, hi = tiny.window
    assert hi - lo == 9_553_230
    assert tiny.window_s == pytest.approx(9.55323e-3)
    assert 0 < tiny.busy_s(0) < tiny.window_s
    assert tiny.idle_share() == pytest.approx(1 - tiny.busy_s(0) / tiny.window_s)
    assert tiny.mean_busy_s() == tiny.busy_s(0)


def test_module_runs_inside_the_window(tiny):
    runs = tiny.module_runs("jit__lambda")
    assert len(runs) == 2  # the first run started before the host span by the clock offset
    assert all(tiny.window[0] <= s < e <= tiny.window[1] for s, e in runs)
    assert tiny.module_runs("jit_other") == []


def test_gaps_are_named_after_host_spans(tiny):
    gaps = tiny.idle_gaps(3)
    assert [g[0] for g in gaps] == ["train.step"] * 3
    assert gaps[0][1] >= gaps[1][1] >= gaps[2][1] > 0
    busy = tiny.busy_s(0)
    all_gaps = sum(g[1] for g in tiny.idle_gaps(1000))
    assert all_gaps + busy == pytest.approx(tiny.window_s, rel=1e-9)


def test_top_ops_name_program_and_instruction(tiny):
    top = tiny.top_ops(2)
    assert top[0][0].startswith("jit__lambda:fusion")
    assert top[0][1] >= top[1][1]


def test_op_time_counts_named_ops_inside_modules(tiny):
    runs = tiny.module_runs("jit__lambda")
    t = tiny.op_time_s({"fusion"}, runs)
    assert 0 < t <= sum(e - s for s, e in runs) * 1e-9


@pytest.mark.parametrize("intervals,expected", [
    ([], 0),
    ([(0, 10), (5, 15)], 15),
    ([(0, 10), (20, 30)], 20),
    ([(0, 10), (2, 3), (10, 12)], 12),
])
def test_union_length(intervals, expected):
    assert trace.union_length(intervals) == expected


def test_clip_to_window():
    assert trace.clip([(0, 10), (15, 25), (30, 40)], (5, 20)) == [(5, 10), (15, 20)]


def test_names():
    ev = "%fusion.70 = f32[4096]{0} fusion(f32[4] %a), kind=kOutput, calls=%fused_computation.1"
    assert trace.instruction(ev) == "fusion.70"
    assert trace.short_op(ev).endswith("kOutput")
    assert trace.module_name("jit_step_fn(9677080685342000563)") == "jit_step_fn"


def test_matmul_instructions_of_a_compiled_program():
    def f(a, b):
        return jnp.tanh(a @ b) + jnp.sum(a)

    x = jnp.ones((64, 64))
    hlo = jax.jit(f).lower(x, x).compile().as_text()
    mm = trace.matmul_instructions(hlo)
    assert mm, hlo
    names = {line.split("=")[0].strip().lstrip("%").replace("ROOT ", "")
             for line in hlo.splitlines() if "=" in line}
    assert mm <= names
    assert not any("tanh" in n for n in mm)


def test_loading_a_trace_without_the_window_span_fails(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace.find_xplane(str(tmp_path))
