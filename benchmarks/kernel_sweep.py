"""Tile-config sweep over the Pallas kernel family — the perf trajectory
tracker.

The grid spans the op space (``core/opkey.py``): the forward NT family,
the backward NN (data-gradient) and TN (weight-gradient) Pallas
candidates, the batched BNT/BNN attention contractions, and the paired
ATTN plan cells (fused flash kernel vs the unfused BNT+softmax+BNN
pair), each against its op's f64 reference.

For every (op, g, shape, candidate, tile config) cell this benchmark:

  * validates the kernel output bit-for-bit-tolerably against the XLA
    reference (a correctness mismatch fails the run — the CI ``tile-smoke``
    job depends on this), and
  * records the median wall-clock, achieved GFLOP/s and the roofline
    GFLOP/s bound for the shape.

``--json`` writes ``benchmarks/BENCH_kernels.json`` (committed per PR, so
the kernel perf trajectory is diffable across PRs).  Numbers from this CPU
container are interpret-mode Pallas — they track *tiling mechanics* (grid
steps, padding waste), not MXU throughput; the recorded ``mode`` field says
which kind of number you are looking at.

  PYTHONPATH=src python -m benchmarks.kernel_sweep --json          # full grid
  PYTHONPATH=src python -m benchmarks.kernel_sweep --json --quick  # CI smoke
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

# The Pallas kernel family under sweep, per op (XLA candidates are not
# tunable).  NN/TN are the backward GEMMs the op-space dispatch routes;
# BNT/BNN are the batched attention contractions; ATTN is the paired
# attention *plan* — the fused flash kernel against the unfused
# BNT+softmax+BNN pair, the fused-vs-unfused comparison the selector
# learns.
PALLAS_FAMILY = ("PALLAS_NT", "PALLAS_TNN", "PALLAS_TNN_FUSED")
FAMILY_BY_OP = {
    "NT": PALLAS_FAMILY,
    "NN": ("PALLAS_NN",),
    "TN": ("PALLAS_TN",),
    "BNT": ("PALLAS_BNT",),
    "BNN": ("PALLAS_BNN",),
    "ATTN": ("FUSED_ATTN", "UNFUSED_ATTN"),
}

# Ragged / adversarial shapes where the default tile is provably not
# optimal, plus aligned controls.  The full grid is a strict SUPERSET of
# the quick (CI) grid: shared cells are what lets the bench-drift check
# compare a fresh --quick sweep against the committed full grid row for
# row (benchmarks/bench_drift.py).
QUICK_SHAPES: Tuple[Tuple[int, int, int], ...] = (
    (128, 128, 128),
    (1, 256, 200),
    (129, 257, 384),
)
FULL_SHAPES: Tuple[Tuple[int, int, int], ...] = QUICK_SHAPES + (
    (256, 256, 256),     # aligned control
    (512, 512, 512),     # one default tile exactly
    (1, 1000, 1000),     # degenerate m, ragged n/k
    (129, 1000, 1000),   # just over one MXU tile in m
    (127, 129, 1000),    # sub-tile m, ragged n, deep k
    (1000, 127, 129),    # ragged m, thin n/k
    (1000, 1000, 1000),  # ragged everything
)

# Batched (g, m, n, k) cells — attention-like: modest per-slice extents,
# real batch.  Interpret mode pays per grid step, so the grids stay
# small; full is again a superset of quick.
QUICK_BATCHED_SHAPES: Tuple[Tuple[int, int, int, int], ...] = (
    (2, 64, 65, 32),
    (3, 1, 128, 64),
)
FULL_BATCHED_SHAPES: Tuple[Tuple[int, int, int, int], ...] = (
    QUICK_BATCHED_SHAPES
    + (
        (3, 128, 128, 64),    # aligned slices, odd batch
        (8, 1, 256, 64),      # decode-like: one query row per slice
        (4, 129, 127, 64),    # ragged slices
    )
)

# Attention-plan (g, m, n, k, window) cells — k is the head dim.  Every
# cell runs under the train-prefill mask geometry (a causal chunk at the
# end of its kv slab: ``q_start = n - m``, sliding window where noted),
# because masking is part of the *plan*, not a caller-side array: the
# fused kernel skips kv blocks outside the visible band while the
# unfused pair always materialises the full (m, n) logits.  Windowed
# long-kv cells are therefore where the fused plan wins even in
# interpret mode; the decode- and ragged-shaped cells keep the unfused
# pair honest.
QUICK_ATTN_SHAPES: Tuple[Tuple[int, int, int, int, int], ...] = (
    (1, 256, 8192, 64, 256),   # deep-kv windowed: fused wins (banded grid)
    (2, 64, 65, 32, 0),        # ragged causal: unfused wins
)
FULL_ATTN_SHAPES: Tuple[Tuple[int, int, int, int, int], ...] = (
    QUICK_ATTN_SHAPES
    + (
        (1, 512, 8192, 128, 512),  # wide-head windowed: fused wins ~3x
        (1, 512, 4096, 64, 512),   # near-parity windowed race
        (4, 1, 256, 64, 0),        # decode-like: one query row per slice
        (2, 129, 257, 64, 0),      # ragged everything
    )
)


def _cells(shapes, batched_shapes, attn_shapes=()):
    """Uniform (op, g, m, n, k, window) cell list over the shape grids
    (window is only meaningful for ATTN cells; 0 elsewhere)."""
    cells = [
        (op, 1, m, n, k, 0)
        for (m, n, k) in shapes
        for op in ("NT", "NN", "TN")
    ]
    cells += [
        (op, g, m, n, k, 0)
        for (g, m, n, k) in batched_shapes
        for op in ("BNT", "BNN")
    ]
    cells += [("ATTN", g, m, n, k, w) for (g, m, n, k, w) in attn_shapes]
    return cells

BENCH_PATH = os.path.join(os.path.dirname(__file__), "BENCH_kernels.json")


def _median_ms(fn, operands, reps: int) -> float:
    import jax

    jax.block_until_ready(fn(*operands))  # compile + warmup
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*operands))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e3


def _reference(op, operands, attn_mask=None):
    """f64 oracle for one cell (masked softmax oracle for the attention
    plan — the same visibility rule the dispatch engine applies)."""
    o64 = [np.asarray(x, np.float64) for x in operands]
    if op == "NT":
        return o64[0] @ o64[1].T
    if op == "NN":
        return o64[0] @ o64[1]
    if op == "TN":
        return o64[0].T @ o64[1]
    if op == "BNT":
        return o64[0] @ np.swapaxes(o64[1], 1, 2)
    if op == "BNN":
        return o64[0] @ o64[1]
    # ATTN: softmax(Q K^T + mask) V, f64 throughout
    s = np.einsum("gmd,gnd->gmn", o64[0], o64[1])
    if attn_mask is not None:
        m, n = s.shape[1:]
        q_pos = attn_mask["q_start"] + np.arange(m)[:, None]
        k_pos = np.arange(n)[None, :]
        vis = k_pos <= q_pos  # causal
        if attn_mask["window"]:
            vis &= k_pos > q_pos - attn_mask["window"]
        s = np.where(vis[None], s, -1e30)
    p = np.exp(s - s.max(axis=-1, keepdims=True))
    p /= p.sum(axis=-1, keepdims=True)
    return np.einsum("gmn,gnd->gmd", p, o64[2])


def _attn_plan_fn(name, cfg, attn_mask):
    """One attention-plan arm as the dispatch engine itself would run it:
    ``dispatch_attention`` under a fixed policy pinning the plan (and the
    unfused pair's sub-ops), with the cell's mask geometry."""
    from repro.core.engine import dispatch_attention, policy_from_spec
    from repro.kernels.tiling import config_key

    arm = "fused" if name == "FUSED_ATTN" else "unfused"
    cfg_sfx = "" if cfg is None else f"@{config_key(cfg)}"
    pol = policy_from_spec(
        f"fixed:attn={arm}{cfg_sfx},bnt=XLA_BNT,bnn=XLA_BNN"
    )

    def fn(q, k, v):
        return dispatch_attention(
            q, k, v, causal=True, window=attn_mask["window"],
            q_start=attn_mask["q_start"], policy=pol,
        )

    return fn


def sweep(
    shapes=FULL_SHAPES,
    batched_shapes=FULL_BATCHED_SHAPES,
    attn_shapes=FULL_ATTN_SHAPES,
    family_by_op: Optional[Dict[str, Tuple[str, ...]]] = None,
    max_tile_configs: int = 6,
    reps: int = 3,
    dtype: str = "float32",
    cache_path: Optional[str] = None,
    verbose: bool = True,
) -> Dict:
    """Measure the (op x g x shape x candidate x config) grid; returns the
    payload ``--json`` writes.  Raises ``AssertionError`` on the first
    correctness mismatch — a tile config must never change the computed
    function (each op is checked against its own reference)."""
    import jax
    import jax.numpy as jnp

    from repro import core
    from repro.core.hardware import device_spec
    from repro.core.measure import operand_shapes
    from repro.core.simulate import matmul_flops
    from repro.kernels import DEFAULT_BLOCK, should_interpret
    from repro.kernels.tiling import config_key, default_config

    hw = device_spec()
    mode = "interpret" if should_interpret() else "compiled"
    dt = jnp.dtype(dtype)
    rng = np.random.RandomState(0)
    rows: List[Dict] = []
    cache = core.MeasurementCache(cache_path) if cache_path else None
    family_by_op = family_by_op or FAMILY_BY_OP

    for (op, g, m, n, k, w) in _cells(shapes, batched_shapes, attn_shapes):
        candidates = family_by_op.get(op)
        if candidates:
            operands = tuple(
                jnp.asarray(rng.randn(*s) * (0.3 if op == "ATTN" else 1.0), dt)
                for s in operand_shapes(op, m, n, k, g)
            )
            # ATTN cells run the train-prefill geometry: a causal chunk at
            # the end of its kv slab, optionally sliding-window.
            attn_mask = (
                {"window": w, "q_start": n - m} if op == "ATTN" else None
            )
            want = _reference(op, operands, attn_mask)
            if op == "ATTN":
                # Q K^T plus probs @ V: two (m, n, k) contractions
                flops = 2 * g * matmul_flops(m, n, k)
                traffic = g * (2 * m * k + 2 * n * k) * dt.itemsize
            else:
                flops = g * matmul_flops(m, n, k)
                traffic = g * (m * k + n * k + m * n) * dt.itemsize
            # roofline bound for this shape on the host descriptor
            peak = (hw.peak_tflops_bf16 if dt.itemsize <= 2 else hw.peak_tflops_f32)
            roofline_gflops = min(
                peak * 1e3,
                hw.mem_bw_gbps * flops / traffic,
            )
            dflt = default_config(m, n, k)
            shape_rows: List[Dict] = []
            nested: Dict[str, Dict[str, float]] = {}
            for name in candidates:
                cand = core.get_candidate(name)
                configs = list(
                    cand.config_space(
                        m, n, k, dt.itemsize,
                        max_configs=max_tile_configs, hardware=hw,
                    )
                ) or [None]
                for cfg in configs:
                    # Candidate.run is the dispatch engine's own invocation
                    # path — benchmark exactly what dispatch would execute.
                    # ATTN arms go through dispatch_attention itself under
                    # a fixed policy, so masking (plan parameters, not
                    # caller arrays) is part of what gets timed.
                    if op == "ATTN":
                        fn = _attn_plan_fn(name, cfg, attn_mask)
                    else:
                        fn = functools.partial(cand.run, config=cfg)
                    got = np.asarray(jax.jit(fn)(*operands), np.float64)
                    err = np.max(np.abs(got - want)) / max(1.0, np.max(np.abs(want)))
                    assert err < 1e-4, (
                        f"correctness mismatch: {op}:{name} @ {config_key(cfg)} "
                        f"on (g={g}, {m},{n},{k}) rel-err {err:.2e}"
                    )
                    ms = _median_ms(jax.jit(fn), operands, reps)
                    ck = config_key(cfg)
                    nested.setdefault(name, {})[ck] = ms / 1e3
                    shape_rows.append(
                        {
                            "op": op,
                            "g": g,
                            "m": m, "n": n, "k": k,
                            # mask geometry column (ATTN cells only):
                            # gflops stays dense-equivalent, so windowed
                            # fused rows can exceed it honestly
                            **({"window": w} if op == "ATTN" else {}),
                            "candidate": name,
                            "config": ck,
                            "is_default_config": cfg is None or tuple(cfg) == dflt,
                            "median_ms": round(ms, 4),
                            "gflops": round(flops / ms / 1e6, 3),
                            "roofline_gflops": round(roofline_gflops, 3),
                        }
                    )
            best = min(shape_rows, key=lambda r: r["median_ms"])
            for r in shape_rows:
                r["best"] = r is best
            rows.extend(shape_rows)
            if cache is not None:
                # same key layout AutotunePolicy uses, so a sweep warms dispatch
                cache.put(
                    (jax.default_backend(), hw.name, dtype, op, g, m, n, k),
                    nested,
                )
            if verbose:
                tag = "" if best["is_default_config"] else "  <- non-default tile wins"
                print(
                    f"  {op:<3s} g={g} ({m:>4d},{n:>4d},{k:>4d})  best "
                    f"{best['candidate']}@{best['config']}  "
                    f"{best['median_ms']:.2f} ms  "
                    f"{best['gflops']:.2f} GF/s{tag}"
                )

    if cache is not None:
        cache.save()
    return {
        "mode": mode,
        "dtype": dtype,
        "hardware": hw.name,
        "backend": __import__("jax").default_backend(),
        "default_block": list(DEFAULT_BLOCK),
        "results": rows,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", action="store_true",
                    help=f"write {os.path.basename(BENCH_PATH)}")
    ap.add_argument("--out", default=BENCH_PATH, help="json output path")
    ap.add_argument("--quick", action="store_true", help="tiny CI grid")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--max-configs", type=int, default=6)
    ap.add_argument("--cache", default=None,
                    help="also persist timings into this autotune cache file")
    args = ap.parse_args(argv)

    shapes = QUICK_SHAPES if args.quick else FULL_SHAPES
    batched = QUICK_BATCHED_SHAPES if args.quick else FULL_BATCHED_SHAPES
    attn = QUICK_ATTN_SHAPES if args.quick else FULL_ATTN_SHAPES
    n_cands = sum(len(v) for v in FAMILY_BY_OP.values())
    print(f"kernel tile-config sweep over {len(shapes)} shapes "
          f"+ {len(batched)} batched + {len(attn)} attention-plan shapes "
          f"x {len(FAMILY_BY_OP)} ops ({n_cands} candidates)")
    payload = sweep(
        shapes=shapes,
        batched_shapes=batched,
        attn_shapes=attn,
        reps=args.reps,
        max_tile_configs=args.max_configs,
        cache_path=args.cache,
    )
    n_cells = sum(1 for r in payload["results"] if r["best"])
    n_nondefault = sum(
        1 for r in payload["results"] if r["best"] and not r["is_default_config"]
    )
    print(f"  {n_nondefault}/{n_cells} (op, shape) cells won by a "
          f"non-default tile ({payload['mode']} mode)")
    if args.json:
        with open(args.out, "w") as fh:
            json.dump(payload, fh, indent=1)
        print(f"  wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
