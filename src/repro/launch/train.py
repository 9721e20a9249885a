"""Restartable training driver (fault-tolerance deliverable).

  * auto-resume: picks up the newest valid checkpoint in --ckpt-dir;
    the deterministic data pipeline continues byte-identically.
  * async checkpointing every --ckpt-every steps (atomic, keep-N).
  * failure injection: --fail-at N raises mid-run (after the step, before
    its checkpoint) to exercise the restart path in tests/CI.
  * elastic restart: checkpoints are mesh-agnostic; rerun with a different
    --mesh and the state re-shards on restore.
  * straggler watchdog: per-step wall time is tracked; steps slower than
    --straggler-factor x the running median are logged with the step index
    (on real fleets this feeds the controller that re-schedules the slow
    host; in single-process dry runs it logs only).

Example (CPU, reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --smoke \
      --steps 20 --batch 8 --seq 128 --mesh 1x1
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import statistics
import time
from typing import Any, List

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, smoke_config
from repro.core.engine import add_policy_argument, dispatch_report, health_report
from repro.core.faults import add_chaos_argument, chaos_scope
from repro.data import make_train_batch
from repro.distributed import batch_specs, named
from repro.launch.common import (
    add_mesh_argument,
    enable_compile_cache,
    resolve_mesh_and_policy,
)
from repro.launch.steps import (
    TrainStepConfig,
    make_train_step,
    train_state_shapes,
    train_state_specs,
)
from repro.models import lm
from repro.optim import make_optimizer


@dataclasses.dataclass
class TrainResult:
    """What a ``main`` run leaves behind: the final state, the loss of
    every step run, the policy that dispatched it, and the compiled step."""

    state: Any
    losses: List[float]
    policy: Any
    compiled: Any
    compile_s: float


def build_state(cfg, mesh, state_specs, seed: int = 0):
    opt_init, _ = make_optimizer(cfg.optimizer)

    def init():
        params = lm.init_lm(jax.random.PRNGKey(seed), cfg)
        return {
            "params": params,
            "opt": opt_init(params),
            "step": jnp.zeros((), jnp.int32),
        }

    with mesh:
        return jax.jit(init, out_shardings=named(mesh, state_specs))()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--keep", type=int, default=3)
    ap.add_argument("--fail-at", type=int, default=int(os.environ.get("REPRO_FAIL_AT_STEP", -1)))
    ap.add_argument("--straggler-factor", type=float, default=3.0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    add_mesh_argument(ap)
    add_policy_argument(ap)
    add_chaos_argument(ap)
    args = ap.parse_args(argv)
    with chaos_scope(args.chaos):
        return _run(args, ap)


def _run(args, ap):
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh, policy = resolve_mesh_and_policy(args, ap)

    state_shapes = train_state_shapes(cfg)
    state_specs = train_state_specs(state_shapes, mesh)
    step_fn = make_train_step(
        cfg,
        TrainStepConfig(accum=args.accum, lr=args.lr, total_steps=args.steps),
        mesh=mesh,
        policy=policy,
    )

    dummy = make_train_batch(cfg, args.seq, args.batch, 0, seed=args.seed)
    b_specs = batch_specs(jax.tree.map(jnp.asarray, dummy), mesh)
    m_specs = {"loss": P(), "grad_norm": P(), "lr": P()}
    with mesh:
        jitted = jax.jit(
            step_fn,
            in_shardings=(named(mesh, state_specs), named(mesh, b_specs)),
            out_shardings=(named(mesh, state_specs), named(mesh, m_specs)),
            donate_argnums=(0,),
        )

    ckpt = CheckpointManager(args.ckpt_dir, keep=args.keep) if args.ckpt_dir else None
    start_step = 0
    if ckpt is not None and ckpt.latest_step() is not None:
        state, start_step = ckpt.restore(
            state_shapes, shardings=named(mesh, state_specs)
        )
        print(f"[train] resumed from step {start_step}")
    else:
        state = build_state(cfg, mesh, state_specs, seed=args.seed)
        print(f"[train] fresh init ({cfg.name}, {cfg.param_count()/1e6:.1f}M params)")

    # compile ahead of the loop: step times then exclude compilation
    t0 = time.perf_counter()
    with mesh:
        compiled = jitted.lower(
            state, jax.device_put(dummy, named(mesh, b_specs))
        ).compile()
    compile_s = time.perf_counter() - t0
    print(f"[train] step compiled in {compile_s:.1f}s")

    times, losses = [], []
    for step in range(start_step, args.steps):
        if args.fail_at == step:
            raise RuntimeError(f"[train] injected failure at step {step}")
        batch = make_train_batch(cfg, args.seq, args.batch, step, seed=args.seed)
        batch = jax.device_put(batch, named(mesh, b_specs))
        t0 = time.perf_counter()
        state, metrics = compiled(state, batch)
        jax.block_until_ready(metrics["loss"])
        dt = time.perf_counter() - t0
        times.append(dt)
        losses.append(float(metrics["loss"]))
        if len(times) > 5:
            med = statistics.median(times[-50:])
            if dt > args.straggler_factor * med:
                print(f"[straggler] step {step}: {dt:.3f}s vs median {med:.3f}s")
        if step % args.log_every == 0 or step == args.steps - 1:
            print(
                f"step {step:5d} loss={float(metrics['loss']):.4f} "
                f"gnorm={float(metrics['grad_norm']):.3f} lr={float(metrics['lr']):.2e} "
                f"({dt*1e3:.0f} ms)"
            )
        if ckpt is not None and (step + 1) % args.ckpt_every == 0:
            ckpt.save_async(step + 1, state)
    if ckpt is not None:
        ckpt.wait()
        ckpt.save(args.steps, state)
    if times:
        print(f"[train] done: {args.steps - start_step} steps, "
              f"median {statistics.median(times)*1e3:.0f} ms/step")
    print(dispatch_report(policy))
    print(health_report())
    return TrainResult(state, losses, policy, compiled, compile_s)


if __name__ == "__main__":
    enable_compile_cache()
    main()
