"""Serving-engine load benchmark: seeded synthetic traffic, no wall-clock
randomness in the trace.

Builds a ``ServeEngine`` (two request classes with their own dispatch
policies), runs the bucket warmup, then replays a deterministic arrival
trace: request arrival steps, prompt lengths, generation lengths, and
classes are all drawn from one ``np.random.RandomState(seed)`` against
the engine's *virtual* clock (``engine.clock``), so two runs with the
same seed submit byte-identical traffic.  Wall-clock only enters as the
thing being measured (tokens/sec, per-token latency) — never as an input.

Reports, per class and total: tokens/sec, p50/p99 of the gaps between a
request's tokens as they reached the host, and the structured dispatch
rows (op -> candidate -> count) so CI can assert that batched attention
contractions (BNT/BNN) route through each class's own policy.  ``cold_misses_after_warmup`` must be
zero: the bucketed serve loop may only hit OpKeys the warmup pass
already measured.

  PYTHONPATH=src python -m benchmarks.serve_load --quick --out /tmp/BENCH_serve.json
"""

from __future__ import annotations

import argparse
import json
import statistics
import time

import jax
import numpy as np

from repro.configs import smoke_config
from repro.core.engine import policy_from_spec
from repro.models import lm
from repro.serving import ServeEngine

SCHEMA_VERSION = 1


def _percentile_ms(xs, q):
    if not xs:
        return None
    return float(np.percentile(np.asarray(xs, np.float64), q)) * 1e3


def build_trace(rng, n_requests, max_prompt, max_gen, classes):
    """Deterministic arrival trace: (arrival_step, prompt, max_new, cls)."""
    trace = []
    step = 0
    for i in range(n_requests):
        step += int(rng.randint(0, 3))  # 0-2 virtual steps between arrivals
        p_len = int(rng.randint(1, max_prompt + 1))
        prompt = rng.randint(0, 256, (p_len,)).astype(np.int32)
        max_new = int(rng.randint(2, max_gen + 1))
        cls = classes[int(rng.randint(0, len(classes)))]
        trace.append((step, prompt, max_new, cls))
    return trace


def run_load(args) -> dict:
    cfg = smoke_config(args.arch)
    policies = {
        "interactive": policy_from_spec(args.interactive_policy),
        "bulk": policy_from_spec(args.bulk_policy),
    }
    params = lm.init_lm(jax.random.PRNGKey(args.seed), cfg)
    engine = ServeEngine(
        cfg, params, n_slots=args.slots, max_seq=args.max_seq,
        policies=policies,
    )

    t0 = time.perf_counter()
    warm = engine.warmup()
    warm_s = time.perf_counter() - t0

    rng = np.random.RandomState(args.seed)
    classes = sorted(policies)
    trace = build_trace(rng, args.requests, args.max_prompt, args.gen, classes)

    # replay against the virtual clock: submit everything due, then step
    t0 = time.perf_counter()
    pending = list(trace)
    n_steps = 0
    while pending or engine.has_work():
        while pending and pending[0][0] <= engine.clock:
            _, prompt, max_new, cls = pending.pop(0)
            engine.submit(prompt, max_new=max_new, cls=cls)
        engine.step()
        n_steps += 1
        if n_steps > 100_000:
            raise RuntimeError("load run did not drain")
    wall_s = time.perf_counter() - t0

    reqs = list(engine.requests.values())
    misses = engine.cold_misses()
    per_class = {}
    for cls in classes:
        cls_reqs = [r for r in reqs if r.cls == cls]
        # gaps between a request's tokens as they reached the host
        lats = [b - a for r in cls_reqs
                for a, b in zip(r.token_times, r.token_times[1:])]
        per_class[cls] = {
            "policy": repr(policies[cls]),
            "requests": len(cls_reqs),
            "tokens": sum(len(r.generated) for r in cls_reqs),
            "p50_ms": _percentile_ms(lats, 50),
            "p99_ms": _percentile_ms(lats, 99),
            "mean_ms": (statistics.fmean(lats) * 1e3) if lats else None,
            "dispatch": engine.class_dispatch_rows()[cls],
        }

    n_tok = sum(len(r.generated) for r in reqs)
    return {
        "schema_version": SCHEMA_VERSION,
        "mode": "quick" if args.quick else "full",
        "arch": cfg.name,
        "backend": jax.default_backend(),
        "seed": args.seed,
        "n_slots": args.slots,
        "max_seq": args.max_seq,
        "buckets": {
            "decode_batches": list(engine.buckets.decode_batches),
            "len_step": engine.buckets.len_step,
            "prefill_lens": list(engine.buckets.prefill_lens),
        },
        "trace": {
            "requests": args.requests,
            "max_prompt": args.max_prompt,
            "max_gen": args.gen,
            "classes": classes,
        },
        "warmup": {"shapes_traced": warm["shapes_traced"],
                   "wall_s": round(warm_s, 3)},
        "cold_misses_after_warmup": misses,
        "totals": {
            "tokens": n_tok,
            "engine_steps": n_steps,
            "wall_s": round(wall_s, 3),
            "tokens_per_s": round(n_tok / max(wall_s, 1e-9), 2),
        },
        "classes": per_class,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--quick", action="store_true",
                    help="small trace for CI (fewer requests, shorter gens)")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--max-prompt", type=int, default=None)
    ap.add_argument("--gen", type=int, default=None)
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--max-seq", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--interactive-policy", default="autotune")
    ap.add_argument("--bulk-policy", default="analytic")
    ap.add_argument("--out", default=None, help="write the report as json")
    args = ap.parse_args(argv)

    defaults = (
        dict(requests=8, max_prompt=24, gen=8, slots=4, max_seq=48)
        if args.quick
        else dict(requests=32, max_prompt=48, gen=24, slots=8, max_seq=96)
    )
    for k, v in defaults.items():
        if getattr(args, k) is None:
            setattr(args, k, v)

    report = run_load(args)
    print(json.dumps(report, indent=2))
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(report, fh, indent=2)
            fh.write("\n")
        print(f"[serve_load] wrote {args.out}")

    misses = report["cold_misses_after_warmup"]
    if any(misses.values()):
        print(f"[serve_load] FAIL: post-warmup cold misses {misses}")
        return 1
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
