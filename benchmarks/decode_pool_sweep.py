"""Time the in-place decode-attention kernel alone on the chip at several
positions per grid step (``bk``).

One "step" is what a serve-batch decode step asks of the kernel: 30
layers of smollm-135m (3 kv heads of 64, GQA group 3), 64 rows drawn
from 64 slots of 2048 positions, each row's live length drawn from the
serve-batch traffic (prompt lognormal median 256, sigma 1.0, in [16,
1536], plus a share of an output lognormal median 96, sigma 0.8, in [8,
512]).  The kernel's K/V pools are filled with seeded noise on the
device; the time is the host clock around 20 jitted steps after a
warm-up, each step a ``fori_loop`` over the layers.

  python -m benchmarks.decode_pool_sweep [--out chiprun_out/decode_pool_sweep.json]

Needs a TPU; ``--rehearse`` runs a tiny geometry anywhere (its times
mean nothing).
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.attention_decode import (PoolLayout, attention_decode_pool,
                                            block_len, live_blocks)

FULL = dict(layers=30, slots=64, max_seq=2048, n_kv=3, group=3, d_head=64, rows=64)
TINY = dict(layers=2, slots=4, max_seq=64, n_kv=3, group=3, d_head=64, rows=4)


def serve_batch_lengths(n: int, max_seq: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    prompt = np.clip(np.exp(rng.normal(np.log(256), 1.0, n)), 16, 1536)
    out = np.clip(np.exp(rng.normal(np.log(96), 0.8, n)), 8, 512)
    return np.minimum(prompt + rng.uniform(0, 1, n) * out, max_seq - 1).astype(np.int32)


def time_blocks(g: dict, blocks, reps: int, seed: int = 0):
    layout = PoolLayout(g["n_kv"], g["d_head"])
    shape = layout.shape((g["layers"], g["slots"] + 1, g["max_seq"], g["n_kv"], g["d_head"]))
    kk, kv, kq = jax.random.split(jax.random.PRNGKey(seed), 3)
    dev = jax.devices()[0]
    before = (dev.memory_stats() or {}).get("bytes_in_use", 0)
    k_pool = jax.random.normal(kk, shape, jnp.bfloat16)
    v_pool = jax.random.normal(kv, shape, jnp.bfloat16)
    jax.block_until_ready((k_pool, v_pool))
    pool_bytes = (dev.memory_stats() or {}).get("bytes_in_use", 0) - before
    q = jax.random.normal(kq, (g["rows"], g["n_kv"], g["group"], g["d_head"]), jnp.bfloat16)
    lengths = serve_batch_lengths(g["rows"], g["max_seq"], seed)
    slots = np.random.default_rng(seed).permutation(g["slots"])[: g["rows"]].astype(np.int32)
    rows = []
    for block in blocks:
        @jax.jit
        def step(q, k_pool, v_pool, slots, lengths, _block=block):
            def body(i, acc):
                return acc + attention_decode_pool(q, k_pool, v_pool, slots, lengths, i,
                                                   layout=layout, block=_block)
            return jax.lax.fori_loop(0, g["layers"], body, jnp.zeros(q.shape, jnp.float32))

        args = (q, k_pool, v_pool, jnp.asarray(slots), jnp.asarray(lengths))
        jax.block_until_ready(step(*args))
        t0 = time.perf_counter()
        for _ in range(reps):
            out = step(*args)
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) / reps * 1e3
        bk = block_len(g["max_seq"], block)
        need = 2 * g["layers"] * int(lengths.sum()) * g["n_kv"] * g["d_head"] * 2
        rows.append(dict(pool_shape=list(shape), pool_bytes_in_use=int(pool_bytes), bk=bk,
                         step_ms=ms, grid_steps=g["rows"] * -(-g["max_seq"] // bk) * g["layers"],
                         live_blocks=int(live_blocks(lengths, bk).sum()) * g["layers"],
                         live_kv_bytes=need, live_kv_gbps=need / ms / 1e6))
        print(json.dumps(rows[-1]), flush=True)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="chiprun_out/decode_pool_sweep.json")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny geometry on any backend; the times mean nothing")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        raise SystemExit(f"no TPU: found {dev.platform} ({dev.device_kind})")
    g = TINY if args.rehearse else FULL
    blocks = (16, 32) if args.rehearse else (256, 512, 1024)
    rows = time_blocks(g, blocks, 2 if args.rehearse else args.reps)
    result = dict(device=dict(platform=dev.platform, device_kind=dev.device_kind,
                              count=jax.device_count()), geometry=g, rows=rows)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(result, fh, indent=1)


if __name__ == "__main__":
    main()
