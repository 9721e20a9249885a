"""Toy sizes of the benchmark's configurations and cells, for CPU tests."""

import copy
import os

from bench import harness


def lm_config(dtype="bfloat16"):
    c = harness.load_config("smollm-135m")
    c.update(d_model=64, n_heads=4, n_kv=2, d_head=16, d_ff=128, vocab=512,
             n_layers=2, attn_chunk=16, dtype=dtype)
    return c


def fcn_config():
    c = harness.load_config("fcn-synthetic-4h")
    c.update(input_dim=64, hidden=[32, 32], output_dim=48)
    return c


def cell(name):
    """(config, workload) of a cell, cut to toy size."""
    w = copy.deepcopy(harness.load_workload(name))
    if w["config"] == "fcn-synthetic-4h":
        w.update(batch=64, reference_rows=32, trace_seconds=0.2)
        return fcn_config(), w
    c = lm_config()
    if w["kind"] == "train":
        w.update(batch=4, seq=32, reference_rows=2, trace_seconds=0.2)
        return c, w
    w["engine"].update(slots=4, max_seq=64, len_step=16, max_prompt=32,
                       batch_buckets=[4] if w["kind"] == "serve_closed" else [1, 2, 4])
    w["traffic"].update(block=8, prompt={"median": 12, "sigma": 1.0, "min": 2, "max": 32},
                        output={"median": 6, "sigma": 0.8, "min": 2, "max": 16})
    if w["kind"] == "serve_closed":
        w["traffic"]["clients"] = 8
    else:
        w["traffic"]["rate"] = 20.0
        w["drain_s"] = 10
    w.update(ramp_s=0.5, trace_seconds=0.2)
    return c, w


# every cell file, including any not (or not yet) in BENCHMARK.json
CELLS = sorted(os.path.splitext(f)[0]
               for f in os.listdir(os.path.join(harness.BENCH, "workloads")))
