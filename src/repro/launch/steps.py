"""Step builders: train / prefill / serve, plus their sharding specs.

``make_train_step`` supports gradient accumulation (``lax.scan`` over
microbatches, f32 accumulators), global-norm clipping, LR schedules, and
either AdamW or Adafactor per the arch config.  All functions are pure and
jit/lower-able with ShapeDtypeStruct inputs — the dry-run compiles them
for the production meshes without allocating a single parameter.
"""

from __future__ import annotations

import contextlib
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.policy import SelectionPolicy, use_policy
from repro.distributed import (
    batch_specs,
    named,
    opt_state_specs,
    param_specs,
)
from repro.models import lm
from repro.models.fcn import fcn_loss
from repro.optim import (
    adamw_update,
    clip_by_global_norm,
    make_optimizer,
    warmup_cosine,
)

__all__ = [
    "make_train_step",
    "make_fcn_train_step",
    "make_prefill_step",
    "make_serve_step",
    "train_state_shapes",
    "train_state_specs",
    "TrainStepConfig",
]


class TrainStepConfig:
    def __init__(
        self,
        accum: int = 1,
        lr: float = 3e-4,
        warmup: int = 100,
        total_steps: int = 10000,
        max_grad_norm: float = 1.0,
        weight_decay: float = 0.1,
        zero1_grads: bool = False,
    ):
        self.accum = accum
        self.lr = lr
        self.warmup = warmup
        self.total_steps = total_steps
        self.max_grad_norm = max_grad_norm
        self.weight_decay = weight_decay
        # §Perf (beyond-paper): ZeRO-2-style gradient accumulation — the
        # f32 accumulator is sharded over the data axes, so each
        # microbatch's gradient lands via reduce-scatter instead of
        # all-reduce and the accumulator read/write traffic shrinks by
        # the DP degree.  See EXPERIMENTS.md §Perf iteration log.
        self.zero1_grads = zero1_grads


def _split_micro(batch: Dict[str, jax.Array], accum: int, mesh: Optional[Mesh]):
    """(B, ...) -> (accum, B/accum, ...) for the microbatch scan.

    CRITICAL: the reshape would otherwise move the data-sharding onto the
    accum axis, leaving each microbatch replicated across DP (16-32x the
    memory and FLOPs — found by the dry-run memory proof).  An explicit
    constraint pins the *microbatch* dim to the data axes.
    """
    from repro.distributed.sharding import data_axes

    daxes = data_axes(mesh) if mesh is not None else ()
    axes_entry = daxes if len(daxes) > 1 else (daxes[0] if daxes else None)

    def r(x):
        B = x.shape[0]
        assert B % accum == 0, f"batch {B} not divisible by accum {accum}"
        y = x.reshape((accum, B // accum) + x.shape[1:])
        if mesh is not None and (B // accum) % max(1, _dp(mesh)) == 0 and B // accum >= _dp(mesh):
            spec = P(None, axes_entry, *([None] * (y.ndim - 2)))
            y = jax.lax.with_sharding_constraint(y, NamedSharding(mesh, spec))
        return y

    return jax.tree.map(r, batch)


def _dp(mesh: Mesh) -> int:
    n = 1
    for a in ("pod", "data"):
        if a in mesh.shape:
            n *= mesh.shape[a]
    return n


def _policy_scope(policy: Optional[SelectionPolicy]):
    """Scope for step bodies: selection runs at trace time, so wrapping the
    traced computation pins every GEMM dispatch in the step to ``policy``.
    For training steps the scope must cover the whole ``value_and_grad``
    call, not just the forward — the engine's custom_vjp re-enters dispatch
    for the backward NN/TN GEMMs at *backward-trace* time."""
    return use_policy(policy) if policy is not None else contextlib.nullcontext()


def make_train_step(
    cfg,
    step_cfg: Optional[TrainStepConfig] = None,
    mesh: Optional[Mesh] = None,
    policy: Optional[SelectionPolicy] = None,
) -> Callable:
    sc = step_cfg or TrainStepConfig()
    opt_kw = {"weight_decay": sc.weight_decay} if cfg.optimizer == "adamw" else {}
    _, opt_update = make_optimizer(cfg.optimizer, **opt_kw)
    sched = warmup_cosine(sc.lr, sc.warmup, sc.total_steps)
    g_shardings = None
    if mesh is not None:
        p_shapes = jax.eval_shape(lambda: lm.init_lm(jax.random.PRNGKey(0), cfg))
        if sc.zero1_grads:
            g32 = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), p_shapes
            )
            g_shardings = named(mesh, opt_state_specs(g32, None, mesh, zero1=True))
        else:
            g_shardings = named(mesh, param_specs(p_shapes, mesh))

    def loss_fn(params, mb):
        loss, _ = lm.lm_loss(params, cfg, mb)
        return loss

    def _grad(params, mb):
        # the scope wraps value_and_grad itself: backward NN/TN dispatches
        # happen while the VJP is traced, after the forward body returned
        with _policy_scope(policy):
            return jax.value_and_grad(loss_fn)(params, mb)

    def train_step(state, batch):
        params = state["params"]
        if sc.accum == 1:
            loss, grads = _grad(params, batch)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        else:
            micro = _split_micro(batch, sc.accum, mesh)
            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            if g_shardings is not None:  # co-shard the f32 accumulators
                g0 = jax.tree.map(
                    jax.lax.with_sharding_constraint, g0, g_shardings
                )

            def body(carry, mb):
                acc_loss, acc_g = carry
                loss_mb, g = _grad(params, mb)
                if g_shardings is not None and sc.zero1_grads:
                    # land each microbatch's grads reduce-scattered
                    g = jax.tree.map(
                        jax.lax.with_sharding_constraint, g, g_shardings
                    )
                acc_g = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), acc_g, g
                )
                return (acc_loss + loss_mb, acc_g), None

            (loss, grads), _ = jax.lax.scan(body, (jnp.zeros(()), g0), micro)
            loss = loss / sc.accum
            grads = jax.tree.map(lambda g: g / sc.accum, grads)

        grads, gnorm = clip_by_global_norm(grads, sc.max_grad_norm)
        lr = sched(state["step"])
        new_params, new_opt = opt_update(grads, state["opt"], params, lr)
        new_state = {
            "params": new_params,
            "opt": new_opt,
            "step": state["step"] + 1,
        }
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        return new_state, metrics

    return train_step


def make_fcn_train_step(
    policy: Optional[SelectionPolicy], sched: Callable, max_grad_norm: float = 1.0
) -> Callable:
    """One AdamW step of the paper's FCN (``models/fcn.py``):
    ``step_fn(params, opt, step, batch) -> (params, opt, loss, gnorm)``.
    Every layer GEMM, forward and backward, dispatches under ``policy``."""

    def step_fn(params, opt, step, batch):
        with _policy_scope(policy):
            (loss, _), grads = jax.value_and_grad(
                lambda p: fcn_loss(p, batch), has_aux=True
            )(params)
        grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
        params, opt = adamw_update(grads, opt, params, sched(step))
        return params, opt, loss, gnorm

    return step_fn


def make_prefill_step(
    cfg, max_seq: int, policy: Optional[SelectionPolicy] = None
) -> Callable:
    def prefill_step(params, batch):
        with _policy_scope(policy):
            return lm.lm_prefill(params, cfg, batch, max_seq=max_seq)

    return prefill_step


def make_serve_step(cfg, policy: Optional[SelectionPolicy] = None) -> Callable:
    def serve_step(params, cache, batch):
        with _policy_scope(policy):
            return lm.lm_decode(params, cfg, cache, batch)

    return serve_step


# -- shapes & shardings -------------------------------------------------------


def train_state_shapes(cfg, key=None):
    """ShapeDtypeStruct pytree of the train state (no allocation)."""
    opt_init, _ = make_optimizer(cfg.optimizer)

    def build():
        params = lm.init_lm(jax.random.PRNGKey(0), cfg)
        return {
            "params": params,
            "opt": opt_init(params),
            "step": jnp.zeros((), jnp.int32),
        }

    return jax.eval_shape(build)


def train_state_specs(state_shapes, mesh: Mesh):
    return {
        "params": param_specs(state_shapes["params"], mesh),
        "opt": opt_state_specs(state_shapes["opt"], None, mesh),
        "step": P(),
    }


def shardings_for_train(cfg, mesh: Mesh, batch_shapes):
    state_shapes = train_state_shapes(cfg)
    state_specs = train_state_specs(state_shapes, mesh)
    b_specs = batch_specs(batch_shapes, mesh)
    metrics_specs = {"loss": P(), "grad_norm": P(), "lr": P()}
    return state_shapes, state_specs, b_specs, metrics_specs
