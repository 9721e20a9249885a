"""Arithmetic of the plain references and of their lower-precision controls.

``f32`` is the reference: float32 storage and every matrix product at
``Precision.HIGHEST``.  The controls compute the same reference one step
lower than the configuration states: ``bf16`` keeps every array and every
product's result in bfloat16; ``fp8`` rounds every product's inputs to
float8 (e4m3) and keeps the rest in bfloat16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

MODES = ("f32", "bf16", "fp8")


class Numerics:
    def __init__(self, mode: str):
        if mode not in MODES:
            raise ValueError(f"unknown numerics {mode!r}; one of {MODES}")
        self.mode = mode
        self.dtype = jnp.float32 if mode == "f32" else jnp.bfloat16

    def cast(self, tree):
        return jax.tree.map(lambda x: x.astype(self.dtype), tree)

    def _operand(self, x):
        if self.mode == "fp8":
            return x.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
        return x.astype(self.dtype)

    def einsum(self, spec: str, a, b):
        a, b = self._operand(a), self._operand(b)
        if self.mode == "f32":
            return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
        return jnp.einsum(spec, a, b, preferred_element_type=jnp.bfloat16)

    def linear(self, x, w):
        """``x @ w.T`` for a weight stored ``(out, in)``."""
        return self.einsum("...i,oi->...o", x, w)


def clip_by_global_norm(grads, max_norm: float):
    sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
    gn = jnp.sqrt(sq)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-12))
    return jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads)


def adamw(grads, m, v, params, count: int, lr: float, hp: dict):
    """One AdamW step with decoupled weight decay, in the arrays' own dtype."""
    b1, b2, eps, wd = hp["b1"], hp["b2"], hp["eps"], hp["weight_decay"]
    bc1, bc2 = 1.0 - b1 ** count, 1.0 - b2 ** count
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
    params = jax.tree.map(
        lambda p, m_, v_: (p - lr * ((m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps)
                                     + wd * p)).astype(p.dtype),
        params, m, v,
    )
    return params, m, v


def learning_rate(hp: dict, step: int) -> float:
    """The configuration's schedule at 0-based ``step``."""
    import math

    peak = hp["lr"]
    if hp["schedule"] == "constant":
        return peak
    if hp["schedule"] == "warmup_cosine":
        warm, total, floor = hp["warmup"], hp["total_steps"], hp["floor"]
        if step < warm:
            return peak * min(1.0, (step + 1) / max(warm, 1))
        frac = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
        return peak * (floor + (1 - floor) * 0.5 * (1 + math.cos(math.pi * frac)))
    raise ValueError(f"unknown schedule {hp['schedule']!r}")


def leaf_norms(tree):
    """Float32 norm of every leaf, in ``jax.tree.leaves`` order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])
