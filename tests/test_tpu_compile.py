"""Compile the main path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler is installed with jaxlib and compiles
for a topology that is described, not attached.  It refuses what interpret
mode accepts — block shapes off the (8, 128) tiling, too much VMEM — so
these tests guard every kernel at real widths.  Each compiled program must
hold the kernel as a ``tpu_custom_call``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and each test worker imports
every test file.  Keep all such compiles in this one file.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import core
from repro.core.measure import operand_shapes
from repro.kernels.attention_fused import MaskParams, attention_fused

# smollm-135m widths (d_model 576, d_ff 1536, 9 heads over 3 kv heads of
# 64, one 2048-token sequence, attention chunk 1024): one (m, n, k, g) per op
SMOLLM_SHAPES = {
    "NT": (2048, 1536, 576, 1),  # MLP up-projection
    "NN": (2048, 576, 1536, 1),  # its input gradient
    "TN": (576, 1536, 2048, 1),  # its weight gradient
    "BNT": (3072, 2048, 64, 24),  # logits: 3-query group fold x 1024 chunk
    "BNN": (3072, 64, 2048, 24),  # probs @ V
    "ATTN": (3072, 2048, 64, 24),  # the fused plan at the same geometry
}
TUNABLE_PAIRS = sorted(
    (name, op)
    for name, cand in core.CANDIDATES.items()
    if cand.tunable
    for op in cand.ops
)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for_tpu(one_chip, monkeypatch):
    """``compile_for_tpu(fn, shapes, dtype)`` -> the compiled text.  Pallas
    lowers through Mosaic (no interpret mode), the persistent compilation
    cache stays off (an entry for a described chip cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, shapes, dtypes):
        args = [
            jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in zip(shapes, dtypes)
        ]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", True)


def _tiles(cand, op):
    m, n, k, _ = SMOLLM_SHAPES[op]
    return [None, cand.config_space(m, n, k, 2, max_configs=1)[0]]


@pytest.mark.parametrize("tile", ["default", "shortlist"])
@pytest.mark.parametrize("name,op", TUNABLE_PAIRS)
def test_tunable_pair_compiles_at_smollm_width(compile_for_tpu, name, op, tile):
    cand = core.get_candidate(name)
    m, n, k, g = SMOLLM_SHAPES[op]
    config = _tiles(cand, op)[tile == "shortlist"]
    shapes = operand_shapes(op, m, n, k, g)
    text = compile_for_tpu(
        lambda *xs: cand.run(*xs, config=config),
        shapes, [jnp.bfloat16] * len(shapes),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n,k", [(4096, 26752), (26752, 4096)])
@pytest.mark.parametrize("name", ["PALLAS_NT", "PALLAS_TNN_FUSED"])
def test_fcn_width_nt_compiles(compile_for_tpu, name, n, k):
    """The paper's synthetic FCN (26752-4096-4096-26752) at batch 1024."""
    cand = core.get_candidate(name)
    text = compile_for_tpu(
        lambda a, b: cand.run(a, b), [(1024, k), (n, k)], [jnp.float32] * 2
    )
    assert "tpu_custom_call" in text


ATTN_CASES = {
    # train prefill: the second 1024-query chunk of a 2048 sequence, the
    # 3-query GQA group folded into the rows
    "causal": ((24, 3072, 64), (24, 2048, 64),
               MaskParams(causal=True, q_seg=1024, q_start=1024)),
    "windowed": ((24, 3072, 64), (24, 2048, 64),
                 MaskParams(causal=True, window=512, q_seg=1024,
                            q_start=1024)),
    # serve decode: one new token per sequence (m = group x 1)
    "decode": ((24, 3, 64), (24, 544, 64), MaskParams()),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("case", sorted(ATTN_CASES))
def test_fused_attention_compiles(compile_for_tpu, case, dtype):
    q_shape, kv_shape, mask = ATTN_CASES[case]
    text = compile_for_tpu(
        lambda q, k, v, lengths: attention_fused(q, k, v, lengths, mask=mask),
        [q_shape, kv_shape, kv_shape, (q_shape[0],)],
        [dtype, dtype, dtype, jnp.int32],
    )
    assert "tpu_custom_call" in text


# -- dispatch scopes in the compiled v5e program ------------------------------

_SCOPE = re.compile(r"dispatch\.[A-Z]+\.[0-9x]+(?:\.g\d+)?\.d\d\.[A-Za-z0-9_@x]+")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%([\w.\-]+) = .*?\s([\w\-]+)\(")


def _innermost_scope(line: str):
    m = re.search(r'op_name="([^"]*)"', line)
    found = _SCOPE.findall(m.group(1)) if m else []
    return found[-1] if found else None


def _scoped_programs():
    """Toy FCN and smollm-shaped training steps, each under a policy that
    runs a Pallas NT arm, as the v5e compiles them."""
    from repro.configs import smoke_config
    from repro.launch.steps import (TrainStepConfig, make_fcn_train_step,
                                    make_train_step, train_state_shapes)
    from repro.models.fcn import FCNConfig, init_fcn
    from repro.optim import adamw_init

    fcn = FCNConfig("toy", 512, 512, (256, 256))
    fcn_params = jax.eval_shape(lambda: init_fcn(jax.random.PRNGKey(0), fcn))
    fcn_step = make_fcn_train_step(core.policy_from_spec("fixed:nt=PALLAS_TNN_FUSED"),
                                   lambda s: jnp.float32(1e-3))
    fcn_args = (fcn_params, jax.eval_shape(adamw_init, fcn_params),
                jax.ShapeDtypeStruct((), jnp.int32),
                {"x": jax.ShapeDtypeStruct((1024, 512), jnp.float32),
                 "labels": jax.ShapeDtypeStruct((1024,), jnp.int32)})
    lm = smoke_config("smollm-135m")
    lm_step = make_train_step(lm, TrainStepConfig(),
                              policy=core.policy_from_spec("fixed:nt=PALLAS_NT,attn=unfused"))
    lm_args = (train_state_shapes(lm),
               {"tokens": jax.ShapeDtypeStruct((2, 256), jnp.int32),
                "labels": jax.ShapeDtypeStruct((2, 256), jnp.int32)})
    return {"fcn": (fcn_step, fcn_args), "lm": (lm_step, lm_args)}


@pytest.mark.parametrize("program", ["fcn", "lm"])
def test_a_fusion_carries_its_products_dispatch_scope(one_chip, monkeypatch, program):
    """The device trace names each op by its instruction's own ``op_name``:
    on the v5e, every instruction the chip runs that multiplies matrices
    (a product, a fusion holding one, a Pallas kernel) names the dispatch
    scope of its product, so the trace attributes it to the arm that ran."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        fn, args = _scoped_programs()[program]
        args = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), args)
        text = jax.jit(fn).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    product_scope, fusions, top = {}, [], []
    comp, fused = None, set()
    for line in text.splitlines():
        m = _COMPUTATION.match(line)  # unindented: instructions are indented
        if m:
            comp = m.group(1)
            continue
        mi = _INSTRUCTION.match(line)
        if not mi:
            continue
        name, opcode = mi.groups()
        calls = re.search(r"calls=%([\w.\-]+)", line)
        if opcode in ("dot", "convolution"):
            product_scope.setdefault(comp, _innermost_scope(line))
        if opcode == "fusion" and calls:
            fused.add(calls.group(1))
        top.append((comp, name, opcode, calls.group(1) if calls else None, line))
    checked = pallas = 0
    for comp, name, opcode, calls, line in top:
        if comp in fused:
            continue  # inside a fusion: the trace shows the fusion alone
        if opcode in ("dot", "convolution"):
            want = product_scope[comp]
        elif opcode == "fusion" and calls in product_scope:
            want = product_scope[calls]
        elif opcode == "custom-call" and "tpu_custom_call" in line:
            want, pallas = _innermost_scope(line), pallas + 1
        else:
            continue
        assert want is not None, line[:300]
        assert _innermost_scope(line) == want, line[:300]
        checked += 1
    assert checked >= 6 and pallas >= 1


# -- the serving engine's decode step at serve-batch widths -------------------


def _output_shape(line: str):
    m = re.search(r"= [a-z0-9]+\[([0-9,]*)\]", line)
    return tuple(int(d) for d in m.group(1).split(",") if d) if m else None


def test_decode_step_reads_the_pool_in_place(compile_for_tpu, one_chip):
    """smollm-135m, 64 slots of 2048, batch bucket 64: the decode step
    holds the pool kernel, and no instruction materialises the whole
    pool leaf, one layer's (slots x max_seq) slab of it, or the batch's
    rows of it.  What has the pool's shape is the donated parameter, its
    while-loop carry, and the in-place scatter of the new tokens."""
    from repro.configs import get_config
    from repro.models import lm
    from repro.serving import ServeEngine, kv_cache

    cfg = get_config("smollm-135m")
    slots, max_seq, batch = 64, 2048, 64
    engine = object.__new__(ServeEngine)  # the step needs cfg alone, not a pool
    engine.cfg = cfg
    step = engine._make_decode_step(core.policy_from_spec("model"))
    params = jax.eval_shape(lambda: lm.init_lm(jax.random.PRNGKey(0), cfg))
    pool = jax.eval_shape(lambda: kv_cache.init_pool(cfg, slots + 1, max_seq))

    def sharded(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree)

    ints = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
            for s in ((slots + 1,), (batch,), (batch,))]
    text = jax.jit(step, donate_argnums=(1,)).lower(
        sharded(params), sharded(pool), *ints).compile().as_text()
    assert "tpu_custom_call" in text

    leaf = jax.tree.leaves(pool)[0].shape  # (layers, slots+1, max_seq, lanes)
    big = {leaf, leaf[1:], (leaf[0], batch) + leaf[2:], (batch,) + leaf[2:]}
    roots, fused, lines, comp = {}, set(), [], None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        mi = _INSTRUCTION.match(line)
        if not mi:
            continue
        calls = re.search(r"calls=%([\w.\-]+)", line)
        if mi.group(2) == "fusion" and calls:
            fused.add(calls.group(1))
        if line.lstrip().startswith("ROOT"):
            roots[comp] = mi.group(2)
        lines.append((comp, mi.group(2), calls.group(1) if calls else None, line))
    seen = 0
    for comp, opcode, calls, line in lines:
        if comp in fused or _output_shape(line) not in big:
            continue  # a fusion's inside runs as the fusion
        seen += 1
        in_place = opcode == "fusion" and roots.get(calls) == "scatter"
        assert opcode in ("parameter", "get-tuple-element", "tuple", "while") or in_place, (
            line[:300])
    assert seen >= 4  # the parameters and the scatters were found


def test_decode_step_compiles_on_a_2x2_mesh(topo, monkeypatch):
    """Serving on a four-chip mesh (``launch/serve.py --mesh 2x2``, the
    parameters sharded): the pool kernel runs whole on every chip, under
    a ``shard_map``, since Mosaic kernels are not partitioned
    automatically."""
    import numpy as np
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.configs import smoke_config
    from repro.distributed import param_specs
    from repro.distributed.context import use_mesh
    from repro.models import lm
    from repro.serving import ServeEngine, kv_cache

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    cfg = smoke_config("smollm-135m")
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    engine = object.__new__(ServeEngine)
    engine.cfg = cfg
    step = engine._make_decode_step(core.policy_from_spec("model", distributed=True))
    params = jax.eval_shape(lambda: lm.init_lm(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(
        lambda s, spec: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=NamedSharding(mesh, spec)),
        params, param_specs(params, mesh))
    whole = NamedSharding(mesh, PartitionSpec())
    pool = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=whole),
                        jax.eval_shape(lambda: kv_cache.init_pool(cfg, 9, 256)))
    ints = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=whole) for s in ((9,), (8,), (8,))]
    try:
        with use_mesh(mesh):
            text = jax.jit(step, donate_argnums=(1,)).lower(params, pool, *ints).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    assert "tpu_custom_call" in text
