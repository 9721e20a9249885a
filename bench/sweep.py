"""Find the knee of an open-loop serving cell: run it at several fixed
rates, one after another in one process, and print for each the backlog
(requests queued or in flight) at the start and the end of the window, the
tails, and how many requests due in the window finished.

    python bench/sweep.py --workload smollm-135m.serve-steady --rates 8,10,12 --seed 7

The knee is the highest rate whose backlog does not grow over the window;
the cell's rate is then fixed in its file.  The benchmark's own runs never
run this.
"""

import argparse
import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from bench import harness

    base = harness.load_workload(args.workload)
    for rate in (float(r) for r in args.rates.split(",")):
        w = copy.deepcopy(base)
        w["traffic"]["rate"] = rate
        run = harness.run_cell(args.workload, args.seed, args.seconds, False,
                               time.perf_counter(), workload=w)
        k = run.counters
        print(json.dumps({
            "rate": rate, "backlog_start": k["backlog_start"],
            "backlog_end": k["backlog_end"], "due": run.attempted,
            "failed": run.failed, **run.end_to_end,
            "ttft_p50_ms": k["ttft_p50_ms"],
            "tokens_per_s": k["tokens"] / k["window_s"],
            "correct": run.correct,
        }), flush=True)
        jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main())
