"""Every executed dispatch arm carries a named scope into the compiled HLO.

``core.engine.scope_name`` names the op, its OpKey and the arm that ran:
``dispatch.<op>.<m>x<n>x<k>[.g<g>].d<dsize>.<label>``.  JAX writes the
scope into each instruction's ``op_name`` metadata, forward ops as
``jvp(<scope>)/...`` and backward ones as ``transpose(jvp(<scope>))/...``;
the profiler carries the same ``op_name`` into the device trace, where the
benchmark sums device time per scope.  A scope is metadata only: the
compiled instruction list is the same with and without it."""

import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import smoke_config
from repro.core import faults, policy_from_spec
from repro.core.engine import scope_name
from repro.core.opkey import OpKey
from repro.core.policy import Decision, FixedPolicy
from repro.launch.steps import (
    TrainStepConfig,
    make_fcn_train_step,
    make_train_step,
    train_state_shapes,
)
from repro.models.fcn import FCNConfig, init_fcn
from repro.optim import adamw_init

SCOPE = re.compile(
    r"dispatch\.(?P<op>[A-Z]+)\.(?P<m>\d+)x(?P<n>\d+)x(?P<k>\d+)(?:\.g(?P<g>\d+))?"
    r"\.d(?P<dsize>\d+)\.(?P<arm>[A-Za-z0-9_]+(?:@[0-9x]+)?)"
)
PRODUCT = re.compile(r"^\s+(?:ROOT\s+)?%([\w.\-]+) = .*?\s(dot|convolution)\(")
INSTR = re.compile(r"^\s+(?:ROOT\s+)?%[\w.\-]+ = ")
METADATA = re.compile(r", metadata=\{[^}]*\}")


@pytest.fixture(autouse=True)
def _clean_ledger():
    faults.clear_quarantine()
    yield
    faults.clear_quarantine()


def products(hlo: str):
    """(instruction, op_name) of every dot and convolution."""
    out = []
    for line in hlo.splitlines():
        m = PRODUCT.match(line)
        if m:
            name = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(1), name.group(1) if name else ""))
    return out


def instructions(hlo: str):
    return [METADATA.sub("", line) for line in hlo.splitlines() if INSTR.match(line)]


def fcn_hlo(policy=None):
    cfg = FCNConfig("toy", 64, 48, (32, 32))
    params = init_fcn(jax.random.PRNGKey(0), cfg)
    opt = adamw_init(params)
    data = {"x": jnp.ones((16, 64)), "labels": jnp.zeros((16,), jnp.int32)}
    step = make_fcn_train_step(policy or policy_from_spec("model"),
                               lambda s: jnp.float32(1e-3))
    return jax.jit(step).lower(params, opt, jnp.int32(0), data).compile().as_text()


def lm_hlo(policy_spec):
    cfg = smoke_config("smollm-135m")
    state = train_state_shapes(cfg)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 32), jnp.int32),
             "labels": jax.ShapeDtypeStruct((2, 32), jnp.int32)}
    step = make_train_step(cfg, TrainStepConfig(), policy=policy_from_spec(policy_spec))
    return jax.jit(step).lower(state, batch).compile().as_text()


def test_scope_name_form():
    assert scope_name(OpKey("NT", 4096, 26752, 4096, 4),
                      Decision("PALLAS_TNN_FUSED", (128, 128, 128))) == \
        "dispatch.NT.4096x26752x4096.d4.PALLAS_TNN_FUSED@128x128x128"
    assert scope_name(OpKey("BNT", 3072, 2048, 64, 4, 24), Decision("XLA_BNT", None)) == \
        "dispatch.BNT.3072x2048x64.g24.d4.XLA_BNT"
    m = SCOPE.fullmatch(scope_name(OpKey("ATTN", 8, 16, 4, 2, 3), Decision("FUSED_ATTN", (8, 16))))
    assert m and m["op"] == "ATTN" and m["g"] == "3" and m["arm"] == "FUSED_ATTN@8x16"


def test_every_fcn_product_forward_and_backward_is_scoped():
    prods = products(fcn_hlo())
    assert prods
    seen = set()
    for name, op_name in prods:
        scopes = SCOPE.findall(op_name)
        assert scopes, (name, op_name)
        m = list(SCOPE.finditer(op_name))[-1]
        seen.add((m["op"], "transpose(" in op_name))
        assert m["arm"].startswith(("XLA_", "PALLAS_")), op_name
    # the forward NT of every layer, its NN and TN gradients in the backward
    assert seen == {("NT", False), ("NN", True), ("TN", True)}


@pytest.mark.parametrize("spec", ["model", "fixed:attn=unfused"])
def test_every_lm_product_is_scoped(spec):
    prods = products(lm_hlo(spec))
    assert prods
    ops = set()
    for name, op_name in prods:
        m = list(SCOPE.finditer(op_name))
        assert m, (name, op_name)
        ops.add(m[-1]["op"])
    assert {"NT", "NN", "TN"} <= ops
    if spec == "fixed:attn=unfused":
        # the unfused plan nests its BNT and BNN scopes inside the ATTN scope
        assert {"BNT", "BNN"} <= ops
        nested = [n for _, n in prods if re.search(r"dispatch\.ATTN\.[^/]*UNFUSED_ATTN\)?/.*dispatch\.B", n)]
        assert nested


def test_a_quarantined_arm_is_scoped_with_the_arm_that_ran():
    with faults.inject_faults("raise:PALLAS_NT.NT"):
        hlo = fcn_hlo(FixedPolicy("PALLAS_NT"))
    assert faults.is_quarantined("PALLAS_NT", "NT")
    executed = {ex for (op, sel, ex) in faults.fallback_counts() if op == "NT"}
    arms = {list(SCOPE.finditer(n))[-1]["arm"] for _, n in products(hlo)
            if list(SCOPE.finditer(n))[-1]["op"] == "NT"}
    assert arms and arms == executed and not any(a.startswith("PALLAS_NT") for a in arms)


@pytest.mark.parametrize("build", [fcn_hlo, lambda: lm_hlo("fixed:attn=unfused")],
                         ids=["fcn", "lm"])
def test_scopes_leave_the_instructions_unchanged(build, monkeypatch):
    scoped = build()
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = build()
    assert "dispatch." in scoped and "dispatch." not in plain
    assert instructions(scoped) == instructions(plain)


def test_the_compile_cache_keys_by_scope_and_not_by_where_the_checkout_lies(monkeypatch):
    """A persistent compile cache must not serve an executable compiled
    without scopes (or with other scope names) for a scoped program, and
    must still hit for the same program from another checkout."""
    import numpy as np
    from jax._src import cache_key
    from jax._src.lib import xla_client

    from repro.core.engine import _CHECKOUT

    def key_and_text():
        cfg = FCNConfig("toy", 64, 48, (32, 32))
        params = init_fcn(jax.random.PRNGKey(0), cfg)
        data = {"x": jnp.ones((16, 64)), "labels": jnp.zeros((16,), jnp.int32)}
        step = make_fcn_train_step(policy_from_spec("model"), lambda s: jnp.float32(1e-3))
        low = jax.jit(step).lower(params, adamw_init(params), jnp.int32(0), data)
        dev = jax.devices()[0]
        key = cache_key.get(low.compiler_ir("stablehlo"), np.array([dev]),
                            xla_client.CompileOptions(), dev.client)
        return key, low.as_text(debug_info=True)

    scoped, text = key_and_text()
    assert '"src/repro/core/engine.py"' in text and _CHECKOUT + "/src" not in text
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain, _ = key_and_text()
    assert scoped != plain
