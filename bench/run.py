"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is ``bench/workloads/<cell>.json``; its configuration is
``bench/configs/<config>.json``.  The last line of standard output is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``, each
number compared with the reference beside its limit); the same checks are
the last lines of standard error.  Without a TPU, or with fewer chips than
the cell asks for, it exits 2 and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    if not args.seconds > 0:
        ap.error("--seconds must be positive")

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    spec = harness.load_spec()
    try:
        run = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 2
    harness.print_result(run, spec)
    return 0


if __name__ == "__main__":
    sys.exit(main())
