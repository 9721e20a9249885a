"""The in-place decode-attention kernel over the serving engine's KV slot
pool, in interpret mode: against today's gather + ``dispatch_attention``
path at f32 and bf16 with GQA group 3 (lengths 1, bk-1, bk, bk+1 and
max_seq-1 in one batch, null-slot padding rows), and the model's pool
decode against its uniform-cache decode, global, ring-buffer and
soft-capped, rows outside the batch left bit-identical."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import dispatch_attention
from repro.kernels.attention_decode import (
    PoolLayout,
    attention_decode_pool,
    block_len,
    live_blocks,
)
from repro.models.attention import AttnConfig, attention_decode, init_attention

N_KV, G, DH = 3, 3, 64  # smollm-135m's heads: GQA group 3
T, BK = 64, 16  # positions per slot, positions per grid step
LAYERS, SLOTS = 2, 6
NULL = SLOTS  # the pool's scratch row
LAYOUT = PoolLayout(N_KV, DH)
# one batch: lengths 1, bk-1, bk, bk+1, max_seq-1, and two padding rows
SLOT_IDS = np.array([4, 0, 2, 5, 1, NULL, NULL], np.int32)
LENGTHS = np.array([1, BK - 1, BK, BK + 1, T - 1, 1, 1], np.int32)
TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


def _caches(dtype, seed=0):
    """Random K/V caches ``(layers, slots+1, T, kv, dh)``, and the query."""
    kk, kv, kq = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (LAYERS, SLOTS + 1, T, N_KV, DH)
    q = jax.random.normal(kq, (len(SLOT_IDS), N_KV, G, DH), dtype)
    return jax.random.normal(kk, shape, dtype), jax.random.normal(kv, shape, dtype), q


def _gather_and_dispatch(q, K, V, slot_ids, lengths, layer):
    """Today's path: gather the rows' whole slots, fold heads into the
    batch, and run the attention plan with per-row validity."""
    B = q.shape[0]
    k = jnp.swapaxes(jnp.take(K[layer], slot_ids, axis=0), 1, 2).reshape(B * N_KV, T, DH)
    v = jnp.swapaxes(jnp.take(V[layer], slot_ids, axis=0), 1, 2).reshape(B * N_KV, T, DH)
    out = dispatch_attention(q.reshape(B * N_KV, G, DH), k, v,
                             lengths=jnp.repeat(jnp.asarray(lengths), N_KV))
    return out.reshape(B, N_KV, G, DH)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("layer", [0, 1])
def test_kernel_matches_the_gather_and_dispatch_path(dtype, layer):
    K, V, q = _caches(dtype)
    got = attention_decode_pool(q, LAYOUT.pack(K), LAYOUT.pack(V), SLOT_IDS, LENGTHS,
                                jnp.int32(layer), layout=LAYOUT, block=BK)
    want = _gather_and_dispatch(q, K, V, SLOT_IDS, LENGTHS, layer)
    assert got.shape == want.shape and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_kernel_reads_nothing_past_a_rows_length():
    """Poisoned positions beyond each row's length (nan, inf) and in
    every other slot leave the result unchanged."""
    K, V, q = _caches(jnp.float32, seed=1)
    live = jnp.zeros((SLOTS + 1, T), bool)
    for s, n in zip(SLOT_IDS, LENGTHS):
        live = live.at[s].set(live[s] | (jnp.arange(T) < n))
    mask = live[None, :, :, None, None]
    bad_k = jnp.where(mask, K, jnp.nan)
    bad_v = jnp.where(mask, V, jnp.inf)
    args = (SLOT_IDS, LENGTHS, jnp.int32(1))
    got = attention_decode_pool(q, LAYOUT.pack(bad_k), LAYOUT.pack(bad_v), *args,
                                layout=LAYOUT, block=BK)
    want = attention_decode_pool(q, LAYOUT.pack(K), LAYOUT.pack(V), *args,
                                 layout=LAYOUT, block=BK)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("lengths,bk,blocks", [
    ([1, 15, 16, 17, 63], 16, [1, 1, 1, 2, 4]),
    ([0, 512, 513, 2047], 512, [1, 1, 2, 4]),
])
def test_live_blocks_count_what_the_grid_fetches(lengths, bk, blocks):
    assert live_blocks(lengths, bk).tolist() == blocks
    assert block_len(T, 512) == T and block_len(2048) == 512


CASES = {
    "global": dict(window=None, softcap=0.0),
    "ring": dict(window=8, softcap=0.0),
    "softcap": dict(window=None, softcap=5.0),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_pool_decode_matches_the_uniform_cache_decode(case):
    """The model's decode step on the slot pool gives the outputs and
    the cache rows of its decode step on the rows' own caches; rows of
    the pool outside the batch stay bit-identical."""
    cfg = AttnConfig(d_model=32, n_heads=N_KV * G, n_kv=N_KV, d_head=DH, **CASES[case])
    extent = cfg.window or T
    p = init_attention(jax.random.PRNGKey(2), cfg)
    K, V, _ = _caches(jnp.float32, seed=3)
    K, V = K[:, :, :extent], V[:, :, :extent]
    # a ring wraps positions past its extent; padding rows decode at 0
    pos = [0, 5, extent - 1, extent, 2 * extent + 3] if cfg.window else [0, 5, BK, BK + 1, T - 1]
    pos = jnp.asarray(pos + [0, 0], jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(4), (len(SLOT_IDS), 1, 32))
    layer, real = 1, slice(0, 5)

    out, new = attention_decode(p, x, cfg, {"k": LAYOUT.pack(K), "v": LAYOUT.pack(V),
                                            "slots": jnp.asarray(SLOT_IDS), "layer": layer},
                                pos)
    rows = {"k": K[layer][SLOT_IDS], "v": V[layer][SLOT_IDS]}
    want_out, want_rows = attention_decode(p, x, cfg, rows, pos)

    np.testing.assert_allclose(np.asarray(out[real]), np.asarray(want_out[real]),
                               atol=1e-5, rtol=1e-5)
    for name, pool in (("k", K), ("v", V)):
        got = np.asarray(new[name])
        packed = np.asarray(LAYOUT.pack(want_rows[name]))
        for b in range(5):
            np.testing.assert_array_equal(got[layer, SLOT_IDS[b]], packed[b])
        untouched = np.asarray(LAYOUT.pack(pool))
        for s in range(SLOTS):
            if s not in SLOT_IDS:
                np.testing.assert_array_equal(got[:, s], untouched[:, s])
        np.testing.assert_array_equal(got[1 - layer], untouched[1 - layer])
