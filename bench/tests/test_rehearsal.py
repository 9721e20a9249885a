"""CPU rehearsals of every cell at toy sizes: the whole run but the look
for a chip, with and without the trace.  A rehearsal never prints a result
line; the command itself refuses to run without a TPU."""

import os
import subprocess
import sys
import time

import pytest

from bench import harness, trace
from bench.tests import toy

SPEC = harness.load_spec()
LISTED = {w["name"] for w in SPEC["workloads"]}
RECORDED = os.path.join(os.path.dirname(__file__), "data", "tiny_v5e.xplane.pb")


def rehearse(name, tmp_path, trace_on=False, fault=None):
    c, w = toy.cell(name)
    return harness.run_cell(name, 2**31 + 77, 0.5, trace_on, time.perf_counter(),
                            workload=w, config=c, require_chip=False, fault=fault,
                            out_dir=str(tmp_path))


@pytest.mark.parametrize("name", toy.CELLS)
def test_rehearsal_is_correct_and_prints_nothing(name, tmp_path, capsys):
    run = rehearse(name, tmp_path)
    assert capsys.readouterr().out == ""
    assert run.correct, harness.check_lines(run)
    assert run.attempted > 0 and run.failed == 0
    out = harness.result(run, SPEC)
    e2e = {m["name"] for m in harness.applicable(SPEC, "end_to_end", name, [])}
    if name in LISTED:
        assert set(out["metrics"]) == e2e
    assert {"setup_s", *run.end_to_end} >= set(out["metrics"]) and run.end_to_end
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks" and out["device"]["platform"] == "cpu"


@pytest.mark.parametrize("name", toy.CELLS)
def test_traced_rehearsal_reads_per_layer_metrics(name, tmp_path, monkeypatch):
    real_load = trace.load
    monkeypatch.setattr(trace, "load", lambda path, **kw: real_load(RECORDED, **kw))
    run = rehearse(name, tmp_path, trace_on=True)
    assert run.correct, harness.check_lines(run)
    out = harness.result(run, SPEC)
    e2e = [m["name"] for m in harness.applicable(SPEC, "end_to_end", name, [])]
    allowed = {m["name"] for m in harness.applicable(SPEC, "per_layer", name, e2e)}
    assert "compile_s" in out["metrics"] and set(out["metrics"]) <= allowed
    readers = sorted(os.path.splitext(f)[0] for f in os.listdir(
        os.path.join(harness.BENCH, "metrics")) if f.endswith(".py"))
    read = {m: harness.metric_reader(m)(run) for m in readers}
    assert any(v is not None for m, v in read.items() if m.startswith("idle_share"))
    assert out["device"]["busy_s"] > 0 and out["device"]["window_s"] > 0
    assert len(out["breakdown"]["device_ops"]) <= 10
    assert len(out["breakdown"]["idle_gaps"]) <= 10


def test_the_command_refuses_to_run_without_a_tpu():
    root = harness.ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"), "--workload",
         toy.CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 2, p.stderr
    assert p.stdout == "" and "not a TPU" in p.stderr
