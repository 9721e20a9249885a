"""Benchmark harness — one benchmark per paper table/figure + beyond-paper.

  PYTHONPATH=src python -m benchmarks.run           # default (fast) grids
  PYTHONPATH=src python -m benchmarks.run --full    # paper-scale grids
  PYTHONPATH=src python -m benchmarks.run --only table4,fig1
"""

from __future__ import annotations

import argparse
import time
import traceback

from . import (
    beyond_paper,
    paper_figures,
    paper_tables,
    policy_overhead,
    roofline_table,
    table10_fcn,
)

BENCHES = {
    "fig1": paper_figures.fig1_nn_vs_nt,
    "fig2": paper_figures.fig2_winner_map,
    "fig3": paper_figures.fig3_tnn_vs_nt,
    "table4": paper_tables.table4_cv,
    "table6": paper_tables.table6_classifiers,
    "fig4": paper_tables.fig4_train_size,
    "table8": paper_tables.table8_selection,
    "table10": table10_fcn.table10,
    "kway": beyond_paper.kway_selector,
    "policy_overhead": policy_overhead.policy_overhead,
    "blocksweep": beyond_paper.kernel_block_sweep,
    "roofline": roofline_table.roofline_table,
}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale grids")
    ap.add_argument("--only", default=None, help="comma-separated subset")
    args = ap.parse_args(argv)

    names = list(BENCHES) if not args.only else args.only.split(",")
    failures = []
    t_start = time.time()
    for name in names:
        t0 = time.time()
        try:
            BENCHES[name](full=args.full)
            print(f"[{name}] done in {time.time()-t0:.1f}s")
        except Exception as e:
            failures.append(name)
            print(f"[{name}] FAILED: {type(e).__name__}: {e}")
            traceback.print_exc()
    print(f"\n== benchmarks: {len(names)-len(failures)}/{len(names)} ok "
          f"in {time.time()-t_start:.0f}s ==")
    return 1 if failures else 0


if __name__ == "__main__":
    from repro.launch.common import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
