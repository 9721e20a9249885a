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
