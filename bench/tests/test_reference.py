"""The plain references against the program's own path, at a small size on
the CPU (where float32 products are exact float32)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.models import fcn, lm
from bench.numerics import Numerics, adamw, clip_by_global_norm, learning_rate
from bench.tests import toy

F32 = Numerics("f32")


def test_fcn_reference_matches_the_program_loss_and_gradients():
    from repro.core import FixedPolicy, use_policy
    from repro.models.fcn import fcn_loss

    c = toy.fcn_config()
    params = fcn.init_params(c, jax.random.PRNGKey(0), jnp.float32)
    (batch,) = fcn.make_batches(c, jax.random.PRNGKey(1), 32, 1)
    with use_policy(FixedPolicy("XLA_NT")):
        (loss, _), g = jax.value_and_grad(lambda p: fcn_loss(p, batch), has_aux=True)(params)
    ref, rg = jax.value_and_grad(
        lambda p: fcn.ref_nll_sum(p, batch["x"], batch["labels"], F32) / 32)(params)
    assert float(loss) == pytest.approx(float(ref), rel=1e-6)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(rg)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)


def test_lm_reference_matches_the_program_forward():
    from repro.models import lm as program

    c = toy.lm_config("float32")
    params = lm.init_params(c, jax.random.PRNGKey(0), jnp.float32)
    lm.check_layout(c, params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, c["vocab"])
    got = program.lm_forward(params, lm.arch(c), {"tokens": tokens})
    ref = lm.ref_logits(c, params, tokens, F32)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_lm_reference_train_step_matches_the_program_step():
    from repro.launch.steps import TrainStepConfig, make_train_step
    from repro.optim import adamw_init

    c = toy.lm_config("float32")
    hp = c["optimizer"]
    params = lm.init_params(c, jax.random.PRNGKey(0), jnp.float32)
    batch = lm.synthetic_data(c, {"seq": 32, "batch": 4}, 5).batch(0)
    step = make_train_step(lm.arch(c), TrainStepConfig(
        lr=hp["lr"], warmup=hp["warmup"], total_steps=hp["total_steps"],
        max_grad_norm=hp["max_grad_norm"], weight_decay=hp["weight_decay"]))
    state = {"params": params, "opt": adamw_init(params), "step": jnp.zeros((), jnp.int32)}
    state, metrics = jax.jit(step)(state, jax.tree.map(jnp.asarray, batch))
    losses, g1, change = lm.reference_train(c, {"reference_rows": 2, "batch": 4}, params, [batch], F32, 1)
    assert float(metrics["loss"]) == pytest.approx(losses[0], rel=1e-5)
    delta = jax.tree.leaves(jax.tree.map(jnp.subtract, state["params"], params))
    np.testing.assert_allclose([float(jnp.linalg.norm(d)) for d in delta], change, rtol=1e-3)
    m = jax.tree.leaves(state["opt"]["m"])
    np.testing.assert_allclose([float(jnp.linalg.norm(x)) / (1 - hp["b1"]) for x in m], g1, rtol=1e-4)


def test_served_gaps_are_nought_for_greedy_tokens_and_not_for_others():
    c = toy.lm_config("float32")
    params = lm.init_params(c, jax.random.PRNGKey(3), jnp.float32)
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (10,), 0, c["vocab"]), np.int32)
    seq = list(prompt)
    for _ in range(6):  # greedy by the reference itself
        logits = lm.ref_logits(c, params, jnp.asarray([seq]), F32)[0, -1]
        seq.append(int(jnp.argmax(logits)))
    served = seq[10:]
    gaps = lm.served_gaps(c, params, [(prompt, served)], 32)
    assert gaps.shape == (6,) and np.max(gaps) == pytest.approx(0.0, abs=1e-5)
    wrong = list(served)
    wrong[2] = (wrong[2] + 1) % c["vocab"]
    assert np.max(lm.served_gaps(c, params, [(prompt, wrong)], 32)) > 1e-3


def test_adamw_and_clipping_by_hand():
    g = {"a": jnp.array([3.0, 4.0])}
    clipped = clip_by_global_norm(g, 1.0)
    np.testing.assert_allclose(clipped["a"], [0.6, 0.8], rtol=1e-6)
    hp = {"b1": 0.9, "b2": 0.95, "eps": 0.0, "weight_decay": 0.0}
    p, m, v = adamw(clipped, {"a": jnp.zeros(2)}, {"a": jnp.zeros(2)}, {"a": jnp.ones(2)}, 1, 0.1, hp)
    np.testing.assert_allclose(p["a"], [0.9, 0.9], rtol=1e-6)  # first step moves by lr * sign
    sched = {"lr": 1.0, "schedule": "warmup_cosine", "warmup": 1, "total_steps": 11, "floor": 0.1}
    assert learning_rate(sched, 0) == 1.0 and learning_rate(sched, 1) == 1.0
    assert learning_rate(sched, 11) == pytest.approx(0.1)


def test_controls_compute_lower():
    x = jnp.linspace(-1, 1, 64).reshape(8, 8)
    for mode, dtype in (("bf16", jnp.bfloat16), ("fp8", jnp.bfloat16), ("f32", jnp.float32)):
        assert Numerics(mode).linear(x, x).dtype == dtype
    exact = F32.linear(x, x)
    err = {m: float(jnp.max(jnp.abs(Numerics(m).linear(x, x).astype(jnp.float32) - exact)))
           for m in ("bf16", "fp8")}
    assert 0 < err["bf16"] < err["fp8"]
    with pytest.raises(ValueError):
        Numerics("int4")
