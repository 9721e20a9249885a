"""End-to-end driver: train a ~100M-parameter fully connected network with
MTNN-dispatched layers (the paper's §VI-C experiment, as a real training
run with AdamW, LR schedule, grad clipping and checkpointing).

Defaults: 100M params (4096-4096x5-4096), synthetic regression-to-
classification data, 200 steps.  On this CPU container ~1-2 s/step.

  PYTHONPATH=src python examples/train_fcn.py [--steps 200] [--tiny]
  PYTHONPATH=src python examples/train_fcn.py --smoke --policy autotune
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import core
from repro.checkpoint import CheckpointManager
from repro.core.engine import POLICY_SPEC_HELP
from repro.core.faults import add_chaos_argument, chaos_scope
from repro.data import make_fcn_batch
from repro.launch.steps import make_fcn_train_step
from repro.models.fcn import FCNConfig, init_fcn
from repro.optim import adamw_init, warmup_cosine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--tiny", action="store_true", help="1M-param variant")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: tiny model, few steps")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_fcn_ckpt")
    ap.add_argument("--always-nt", action="store_true",
                    help="disable MTNN (the CaffeNT baseline)")
    ap.add_argument("--policy", default=None,
                    help=f"override the trained-here selector; {POLICY_SPEC_HELP}")
    add_chaos_argument(ap)
    args = ap.parse_args()

    with chaos_scope(args.chaos):
        _run(args)


def _run(args):
    if args.smoke:
        args.steps = min(args.steps, 5)
    if args.tiny or args.smoke:
        cfg = FCNConfig("fcn-1m", 256, 64, (512, 512, 512))
    else:
        cfg = FCNConfig("fcn-100m", 4096, 4096, (4096,) * 5)
    n_params = sum(
        (cfg.dims[i] + 1) * cfg.dims[i + 1] for i in range(len(cfg.dims) - 1)
    )
    print(f"[fcn] {cfg.name}: dims {cfg.dims}, {n_params/1e6:.1f}M params")

    # policy: an explicit spec, the forced-NT baseline, or one learned on
    # measured host data right here
    if args.policy:
        policy = core.policy_from_spec(args.policy)
        print(f"[fcn] policy: {policy!r}")
    elif args.always_nt:
        policy = core.FixedPolicy("XLA_NT")
        print("[fcn] MTNN disabled (always XLA_NT)")
    else:
        ds = core.collect_measured(sizes=[64, 256, 1024], reps=2)
        clf, _ = core.train_paper_model(ds)
        policy = core.ModelPolicy(
            core.MTNNSelector(clf, hardware=core.device_spec())
        )
        print(f"[fcn] selector trained on {len(ds)} measured samples")

    key = jax.random.PRNGKey(0)
    params = init_fcn(key, cfg)
    opt = adamw_init(params)
    sched = warmup_cosine(args.lr, warmup=20, total=args.steps)
    ckpt = CheckpointManager(args.ckpt_dir, keep=2)

    # dispatch decisions happen while tracing, inside the policy scope
    step_fn = jax.jit(make_fcn_train_step(policy, sched))

    rng = np.random.RandomState(0)
    w_true = rng.randn(cfg.input_dim, 8).astype(np.float32)
    t_hist = []
    for step in range(args.steps):
        batch = make_fcn_batch(rng, cfg, args.batch, w_true)
        t0 = time.perf_counter()
        params, opt, loss, gnorm = step_fn(params, opt, jnp.asarray(step), batch)
        loss.block_until_ready()
        t_hist.append(time.perf_counter() - t0)
        if step % 20 == 0 or step == args.steps - 1:
            print(f"  step {step:4d} loss={float(loss):.4f} "
                  f"gnorm={float(gnorm):.3f} ({t_hist[-1]*1e3:.0f} ms)")
        if (step + 1) % 100 == 0:
            ckpt.save_async(step + 1, {"params": params, "opt": opt})
    ckpt.wait()
    med = float(np.median(t_hist[2:]))
    print(f"[fcn] done; median {med*1e3:.0f} ms/step "
          f"({2*3*args.batch*n_params/med/1e9:.1f} GFLOP/s effective)")
    print(core.dispatch_report(policy))
    print(core.health_report())


if __name__ == "__main__":
    main()
