"""Paper §VI-C / Table X / Figs. 7-8 — end-to-end FCN training with MTNN.

CaffeNT   = every layer forced through the direct NT candidate
            (``FixedPolicy("XLA_NT")``).
CaffeMTNN = every layer dispatched by a policy wrapping a selector trained
            on *measured* host data (the honest analogue of the paper's
            per-GPU model).

Real wall-clock on this container's CPU backend.  The synthetic net is
dimension-scaled (26752 -> 2048, documented) so a minibatch finishes in
seconds on one core; the MNIST net runs at paper scale.
"""

from __future__ import annotations

import time
from typing import Dict

import jax
import numpy as np

from repro import core
from repro.configs.fcn_paper import MNIST_FCNS
from repro.models.fcn import FCNConfig, fcn_loss, init_fcn

from .common import measured_dataset, save_json, section

# CPU-scaled synthetic nets (paper: 26752-4096^h-26752)
SYN_SCALED = {
    2: FCNConfig("synthetic-2h(cpu)", 2048, 2048, (1024, 1024)),
    3: FCNConfig("synthetic-3h(cpu)", 2048, 2048, (1024, 1024, 1024)),
}


def _bench_phase(cfg: FCNConfig, batch_size: int, policy, reps=3):
    key = jax.random.PRNGKey(0)
    params = init_fcn(key, cfg)
    x = jax.random.normal(key, (batch_size, cfg.input_dim))
    labels = jax.random.randint(key, (batch_size,), 0, cfg.output_dim)
    batch = {"x": x, "labels": labels}

    from repro.models.fcn import fcn_forward

    def fwd(p):
        return fcn_forward(p, batch["x"]).sum()

    def full(p):
        (l, _), g = jax.value_and_grad(
            lambda q: fcn_loss(q, batch), has_aux=True
        )(p)
        return l, g

    # dispatch decisions land at trace time, so the policy scope covers the
    # first (tracing) call of each jitted function; timed re-runs hit the
    # compiled cache and make no further decisions.
    with core.use_policy(policy):
        jf = jax.jit(fwd)
        jfb = jax.jit(full)
        jax.block_until_ready(jf(params))
        jax.block_until_ready(jfb(params)[0])
    t_f = min(
        _timed(lambda: jax.block_until_ready(jf(params))) for _ in range(reps)
    )
    t_fb = min(
        _timed(lambda: jax.block_until_ready(jfb(params)[0])) for _ in range(reps)
    )
    return t_f, max(t_fb - t_f, 0.0)  # (forward, backward) seconds


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def table10(full: bool = False):
    section("Table X / Figs.7-8 — FCN training: always-NT vs MTNN (measured)")
    ds = measured_dataset(full)
    clf, rep = core.train_paper_model(ds)
    mtnn = core.ModelPolicy(core.MTNNSelector(clf, hardware=core.device_spec()))
    nt = core.FixedPolicy("XLA_NT")  # the CaffeNT arm

    out: Dict[str, Dict] = {}
    nets = {"mnist-2h": MNIST_FCNS[2], "mnist-3h": MNIST_FCNS[3],
            "syn-2h": SYN_SCALED[2], "syn-3h": SYN_SCALED[3]}
    batches = (256, 1024) if not full else (128, 512, 2048, 4096)
    print(f"  {'net':<10s} {'batch':>6s} {'fwd NT':>9s} {'fwd MTNN':>9s} "
          f"{'bwd NT':>9s} {'bwd MTNN':>9s} {'fwd speedup':>11s}")
    for name, cfg in nets.items():
        for bs in batches:
            fn, bn = _bench_phase(cfg, bs, policy=nt)
            fm, bm = _bench_phase(cfg, bs, policy=mtnn)
            sp = fn / max(fm, 1e-9)
            out[f"{name}@{bs}"] = {
                "fwd_nt_ms": fn * 1e3, "fwd_mtnn_ms": fm * 1e3,
                "bwd_nt_ms": bn * 1e3, "bwd_mtnn_ms": bm * 1e3,
                "fwd_speedup": sp,
            }
            print(f"  {name:<10s} {bs:6d} {fn*1e3:9.2f} {fm*1e3:9.2f} "
                  f"{bn*1e3:9.2f} {bm*1e3:9.2f} {sp:10.2f}x")
    fwd_sp = [v["fwd_speedup"] for v in out.values()]
    tot_nt = sum(v["fwd_nt_ms"] + v["bwd_nt_ms"] for v in out.values())
    tot_mt = sum(v["fwd_mtnn_ms"] + v["bwd_mtnn_ms"] for v in out.values())
    print(f"  mean fwd speedup {np.mean(fwd_sp):.2f}x; total time ratio "
          f"{tot_nt/max(tot_mt,1e-9):.2f}x (paper: fwd 2.44x/2.15x on the "
          f"large net, total 1.28x avg; CPU signal is weaker per DESIGN.md)")
    out["_summary"] = {
        "mean_fwd_speedup": float(np.mean(fwd_sp)),
        "total_ratio": tot_nt / max(tot_mt, 1e-9),
        "selector_decisions": dict(mtnn.stats.by_candidate),
    }
    save_json("table10", out)
    return out
