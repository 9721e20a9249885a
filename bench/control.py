"""Readings that set the limits of ``correct``: the program's numbers and
the lower-precision control's, seed by seed, in one process.

    python bench/control.py --workload <cell> --seeds 11,12,13 --control bf16
    python bench/control.py --workload <cell> --seeds 11,12,13 --fault half_batch

For every seed it runs the cell (``--seconds`` of window at the cell's own
load, default 3) and prints one JSON line: the program's readings, and
those of the plain reference computed in ``--control`` precision in the
program's place (training: its first three steps against the float32
reference; serving: at each position of the served sample, the float32
reference's gap of the token the control puts first), with
``control_correct``: whether the control passes the cell's limits.  With ``--fault``
the timed path is broken underneath, and the readings are the broken
program's.  The benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def measure(name: str, seed: int, seconds: float, control=None, fault=None,
            **run_kw) -> dict:
    """One seed's readings of the program (broken by ``fault``, if given)
    and, with ``control``, of the reference in that precision."""
    import numpy as np

    from bench import harness
    from bench.kinds import train
    from bench.numerics import Numerics

    run = harness.run_cell(name, seed, seconds, False, time.perf_counter(),
                           fault=fault, **run_kw)
    line = {"seed": seed, "program": {c["name"]: c["value"] for c in run.checks},
            "end_to_end": run.end_to_end, "setup_s": run.setup_s,
            "memory_peak_bytes": run.memory_peak_bytes}
    c, w = run.config, run.workload
    fam = harness.family(c)
    if w["kind"] == "train":
        ref = run.counters["reference"]
        line["program_loss_steps"] = [abs(a - b) / abs(b) for a, b in
                                      zip(run.counters["first_losses"], ref[0])]
        line["reference_losses"] = ref[0]
    if control and w["kind"] == "train":
        params0, batches = fam.reference_inputs(c, w, seed)
        got = fam.reference_train(c, w, params0, batches, Numerics(control))
        line["control"] = train.readings(got, ref, w["loss_steps"])
        line["control_loss_steps"] = [abs(a - b) / abs(b) for a, b in zip(got[0], ref[0])]
    elif control:
        params = fam.reference_params(c, seed)
        gaps = fam.served_gaps(c, params, run.counters["served_sample"],
                               w["engine"]["max_seq"], control=Numerics(control))
        line["control"] = {"logit_gap": float(np.max(gaps))}
    if control:
        limits = w["limits"]
        line["control_correct"] = all(v <= limits[k] for k, v in line["control"].items())
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", choices=("bf16", "fp8"))
    ap.add_argument("--fault", choices=("state_unchanged", "half_batch", "token_altered"))
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    for seed in (int(s) for s in args.seeds.split(",")):
        line = measure(args.workload, seed, args.seconds, args.control, args.fault)
        print(json.dumps(line), flush=True)
        jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main())
