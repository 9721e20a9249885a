"""BENCHMARK.json names files that exist, and every cell, configuration and
per-layer metric is found by its name alone."""

import json
import os
import re

import pytest

from bench import harness

SPEC = harness.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) < 64 * 1024


def test_names_are_unique_and_well_formed():
    names = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in SPEC[section]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((section in ("end_to_end", "per_layer"), entry["name"]))
    assert len(names) == len(set(names))


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda e: e["name"])
def test_configuration_files(entry):
    c = harness.load_config(entry["name"])
    assert entry["file"] == f"bench/configs/{entry['name']}.json"
    assert c["name"] == entry["name"] and c["reduced"] == entry["reduced"]
    assert harness.family(c)


@pytest.mark.parametrize("entry", SPEC["workloads"], ids=lambda e: e["name"])
def test_cells_are_files_and_report_what_the_contract_asks(entry):
    w = harness.load_workload(entry["name"])
    assert w["config"] == entry["config"] and w["chips"] == entry["chips"] == 1
    assert harness.kind_module(w) and len(entry["why"]) <= 200
    e2e = [m["name"] for m in harness.applicable(SPEC, "end_to_end", entry["name"], [])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.applicable(SPEC, "per_layer", entry["name"], e2e)


@pytest.mark.parametrize("entry", SPEC["workloads"], ids=lambda e: e["name"])
def test_limits_lie_between_the_readings_on_the_chip(entry):
    """Each limit lies above the largest reading of sound runs and below
    the smallest upper reading (the control's where it is three times the
    lower or more, a fault's where it is ten times), and the control fails
    at least one of the cell's limits."""
    w = harness.load_workload(entry["name"])
    limits, readings = w["limits"], w["readings"]
    assert set(readings) == set(limits)
    for name, r in readings.items():
        lower = r["program"]
        assert limits[name] > lower, name
        uppers = [v for src, v in r.items() if src != "program"
                  and v >= (3 if src == "control" else 10) * lower]
        if uppers:
            assert limits[name] < min(uppers), name
    assert any(r["control"] > limits[name] for name, r in readings.items())


@pytest.mark.parametrize("entry", SPEC["per_layer"], ids=lambda e: e["name"])
def test_per_layer_metrics_have_readers(entry):
    assert callable(harness.metric_reader(entry["name"]))
    assert entry["moves"] in {m["name"] for m in SPEC["end_to_end"]}
    for cell in entry.get("workloads", []):
        e2e = [m["name"] for m in harness.applicable(SPEC, "end_to_end", cell, [])]
        assert entry["moves"] in e2e


def test_bounds():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert json.dumps(SPEC)
