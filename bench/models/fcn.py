"""The paper's fully connected networks (arXiv:1702.03192 §VI-C, Table IX).

Weights are ``{"layers": [{"w": (out, in), "b": (out,)}, ...]}``, the
layout ``repro.models.fcn`` keeps; every layer but the last is followed by
a ReLU, and the loss is the mean softmax cross entropy over the batch.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts
from bench.numerics import Numerics, adamw, clip_by_global_norm, learning_rate, leaf_norms


def dims(c: dict):
    return (c["input_dim"], *c["hidden"], c["output_dim"])


def init_params(c: dict, key, dtype):
    """Seeded weights, made on the device in one call: ``N(0, 1/in)``
    weights and ``N(0, 0.01^2)`` biases."""
    d = dims(c)

    def make(key):
        keys = jax.random.split(key, 2 * (len(d) - 1))
        return {"layers": [
            {"w": (jax.random.normal(keys[2 * i], (o, n), jnp.float32)
                   / np.sqrt(n)).astype(dtype),
             "b": (0.01 * jax.random.normal(keys[2 * i + 1], (o,), jnp.float32)).astype(dtype)}
            for i, (n, o) in enumerate(zip(d[:-1], d[1:]))
        ]}

    return jax.jit(make)(key)


def make_batches(c: dict, key, batch: int, n: int):
    """``n`` distinct batches on the device: standard normal inputs and
    uniform labels."""

    def make(key):
        out = []
        for k in jax.random.split(key, n):
            kx, ky = jax.random.split(k)
            out.append({
                "x": jax.random.normal(kx, (batch, c["input_dim"]), jnp.float32),
                "labels": jax.random.randint(ky, (batch,), 0, c["output_dim"], jnp.int32),
            })
        return tuple(out)

    return jax.jit(make)(key)


# -- work ---------------------------------------------------------------------


def train_gemms(c: dict, w: dict) -> List[counts.Work]:
    return counts.fcn_train_gemms(dims(c), w["batch"], jnp.dtype(c["dtype"]).itemsize)


# -- plain reference ------------------------------------------------------------


def ref_nll_sum(params, x, labels, nm: Numerics):
    h = x.astype(nm.dtype)
    n = len(params["layers"])
    for i, layer in enumerate(params["layers"]):
        h = nm.linear(h, layer["w"]) + layer["b"]
        if i < n - 1:
            h = jnp.maximum(h, 0)
    logz = jax.nn.logsumexp(h, axis=-1)
    gold = jnp.take_along_axis(h, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(logz - gold)


def reference_train(c: dict, w: dict, params0, batches, nm: Numerics, steps: int = 3):
    """The first ``steps`` training steps, plainly: loss and gradient over
    row blocks, global-norm clipping, AdamW.  Returns per-step losses,
    the per-leaf norms of the first clipped gradient, and of the
    parameters' change after the last step."""
    hp = c["optimizer"]
    rows = w["reference_rows"]
    grad_block = jax.jit(jax.value_and_grad(
        lambda p, x, y: ref_nll_sum(p, x, y, nm)))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    p = nm.cast(params0)
    p_start = p
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, g1 = [], None
    for s in range(steps):
        b = batches[s]
        B = b["x"].shape[0]
        total, grads = None, None
        for lo in range(0, B, rows):
            nll, g = grad_block(p, b["x"][lo:lo + rows], b["labels"][lo:lo + rows])
            total = nll if total is None else total + nll
            grads = g if grads is None else add(grads, g)
        grads = jax.tree.map(lambda g: (g / B).astype(g.dtype), grads)
        losses.append(float(total / B))
        grads = clip_by_global_norm(grads, hp["max_grad_norm"])
        if s == 0:
            g1 = np.asarray(leaf_norms(grads))
        p, m, v = adamw(grads, m, v, p, s + 1, learning_rate(hp, s), hp)
    change = np.asarray(leaf_norms(jax.tree.map(lambda a, b: a.astype(jnp.float32)
                                                - b.astype(jnp.float32), p, p_start)))
    return losses, g1, change


def reference_inputs(c: dict, w: dict, seed: int):
    """What the reference needs, made again from the seed once the
    program's state is gone: the initial weights as the program got them,
    in float32, and the first three batches."""
    from bench.traffic import sub_seed

    p0 = init_params(c, jax.random.PRNGKey(sub_seed(seed, "weights")), jnp.dtype(c["dtype"]))
    batches = make_batches(c, jax.random.PRNGKey(sub_seed(seed, "batches")),
                           w["batch"], w["pool"])[:3]
    return jax.tree.map(lambda x: x.astype(jnp.float32), p0), batches


# -- the program -----------------------------------------------------------------


class TrainProgram:
    """The repository's FCN training step (``launch.steps.make_fcn_train_step``
    under the configured policy), compiled once and driven step by step
    through the same call; batches cycle through a pool made on the device."""

    def __init__(self, c: dict, w: dict, seed: int, fault=None):
        from repro.core import policy_from_spec
        from repro.launch.steps import make_fcn_train_step
        from repro.optim import adamw_init

        from bench.traffic import sub_seed

        self.c, self.w, self.seed = c, w, seed
        hp = c["optimizer"]
        if hp["schedule"] != "constant":
            raise ValueError("the FCN step takes a constant learning rate here")
        step_fn = make_fcn_train_step(
            policy_from_spec(w["policy"]),
            lambda step: jnp.asarray(hp["lr"], jnp.float32),
            max_grad_norm=hp["max_grad_norm"],
        )
        step_fn = _faulty(step_fn, fault)
        self.dtype = jnp.dtype(c["dtype"])
        self._pkey = jax.random.PRNGKey(sub_seed(seed, "weights"))
        self._bkey = jax.random.PRNGKey(sub_seed(seed, "batches"))
        params = init_params(c, self._pkey, self.dtype)
        opt = jax.jit(adamw_init)(params)
        self.pool = make_batches(c, self._bkey, w["batch"], w["pool"])
        self.compiled = jax.jit(step_fn, donate_argnums=(0, 1)).lower(
            params, opt, jnp.int32(0), self.pool[0]).compile()
        self.state = (params, opt)
        self._norms = jax.jit(leaf_norms)
        self._change = jax.jit(lambda p, p0: leaf_norms(
            jax.tree.map(lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, p0)))

    def hlo_text(self) -> str:
        return self.compiled.as_text()

    def step(self, i: int):
        params, opt = self.state
        params, opt, loss, _ = self.compiled(
            params, opt, jnp.int32(i), self.pool[i % len(self.pool)])
        self.state = (params, opt)
        return loss

    def first_grad_norms(self):
        """The first gradient as AdamW received it: ``m / (1 - b1)`` after
        one step from zero moments."""
        return self._norms(self.state[1]["m"]) / (1.0 - self.c["optimizer"]["b1"])

    def change_norms(self):
        p0 = init_params(self.c, self._pkey, self.dtype)
        return self._change(self.state[0], p0)

    def reference_inputs(self):
        return reference_inputs(self.c, self.w, self.seed)

    def free(self):
        self.state = None
        self.pool = None
        self.compiled = None


def _faulty(step_fn, fault):
    """Test-only breakage of the timed step (never set by the command line)."""
    if fault is None:
        return step_fn
    if fault == "state_unchanged":
        def f(params, opt, step, batch):
            _, _, loss, gn = step_fn(params, opt, step, batch)
            return params, opt, loss, gn
        return f
    if fault == "half_batch":
        def f(params, opt, step, batch):
            half = jax.tree.map(lambda a: a[: a.shape[0] // 2], batch)
            return step_fn(params, opt, step, half)
        return f
    raise ValueError(f"unknown fault {fault!r}")
