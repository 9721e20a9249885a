"""Ragged decode attention read in place from the serving engine's KV
slot pool: one query token per row against the cached keys and values of
that row's slot, over the row's live length only.

  attention_decode_pool(q, k_pool, v_pool, slot_ids, lengths, layer)
      q:(B, kv, g, dh)  pools:(layers, slots+1, T, lanes) -> (B, kv, g, dh)

The pool is never gathered, transposed or sliced per layer outside the
kernel.  The grid is a work list of the batch's live (row, block) pairs,
rows in order: ``slot_ids``, ``lengths``, the ``layer`` index and the
list are scalar-prefetched, and the K/V index maps pick block ``j`` of
row ``slot_ids[b]`` of layer ``layer`` straight out of the whole pool
leaf.  The list's length, the sum of the rows' live blocks, is a traced
grid extent, so a dead block costs neither a fetch nor a grid step.  One
grid step covers every kv head and query of a row and ``bk`` positions.
(A static ``(B, cdiv(T, bk))`` grid that clamps dead steps to the last
live block fetches the same bytes but pays a grid step for every dead
block: 4.32 against 2.98 ms for a serve-batch decode step's 30 layers on
a TPU v5e, PERF.md.)

Pool layout (``PoolLayout``): a pool row holds one position of every kv
head, ``kv * d_head`` lanes padded up to a multiple of 128 (192 -> 256 at
smollm-135m widths).  A TPU array keeps row-major order in HBM only when
its minor dimension is a multiple of 128 lanes; with 64 or 192 lanes the
default layout puts the sequence axis minor, and a Mosaic operand would
then cost a transpose of the whole pool per call.  (A kv-major pool,
``(kv, T/2, 128)`` with two 64-wide positions per row, reads a quarter
fewer bytes but ran slower on a TPU v5e: PERF.md.)  Queries are packed
block-diagonally to match: query row ``h * g + i`` carries query ``i`` of
kv head ``h`` in head ``h``'s lanes and zeros elsewhere, so one ``(rows,
lanes)`` dot gives every head's scores exactly, and the output's diagonal
blocks are the heads' results.

Masking follows ``kernels/attention_fused.py``: a position is valid below
the row's ``lengths`` entry (``min(pos + 1, window)`` for ring buffers),
masked scores take the finite ``NEG_INF`` and V beyond the length is
zeroed.  The softmax state and the accumulator are f32; K and V stay in
the pool's dtype up to the query dtype they are multiplied in.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention_fused import NEG_INF
from .common import MXU_EDGE, CompilerParams, cdiv, round_up, should_interpret

__all__ = ["DECODE_BLOCK", "PoolLayout", "attention_decode_pool", "block_len", "live_blocks"]

# positions per grid step (the bk sweep in PERF.md)
DECODE_BLOCK = 512


@dataclass(frozen=True)
class PoolLayout:
    """Where one attention layer's cached K (or V) lives in the pool:
    ``(..., T, lanes)`` for ``T`` positions per slot."""

    n_kv: int
    d_head: int

    @property
    def width(self) -> int:
        return self.n_kv * self.d_head

    @property
    def lanes(self) -> int:
        return round_up(self.width, MXU_EDGE)

    def shape(self, cache_shape) -> tuple:
        """Pool leaf shape for a cache leaf shaped ``(..., T, kv, dh)``."""
        return (*cache_shape[:-2], self.lanes)

    def pack(self, x: jax.Array) -> jax.Array:
        """``(..., kv, dh)`` -> ``(..., lanes)``, padded with zeros."""
        x = x.reshape(*x.shape[:-2], self.width)
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, self.lanes - self.width)])

    def write(self, pool, new, layer, slot_ids, pos):
        """Write each row's new token ``new`` (B, kv, dh) at position
        ``pos`` (B,) of slot ``slot_ids`` (B,) in ``layer``: one
        contiguous run of lanes per row, in place on a donated or
        loop-carried pool."""
        return pool.at[layer, slot_ids, pos].set(self.pack(new).astype(pool.dtype))

    def pack_queries(self, q: jax.Array) -> jax.Array:
        """``(B, kv, g, dh)`` -> ``(B, nq, lanes)``: row ``h * g + i`` holds
        query ``i`` of head ``h`` in head ``h``'s lanes; rows padded to a
        multiple of 8 with zero queries."""
        B, n_kv, g, dh = q.shape
        eye = jnp.eye(n_kv, dtype=bool)[:, None, :, None]
        qp = jnp.where(eye, q[:, :, :, None, :], jnp.zeros((), q.dtype))
        qp = qp.reshape(B, n_kv * g, self.width)
        nq = round_up(n_kv * g, 8)
        return jnp.pad(qp, ((0, 0), (0, nq - n_kv * g), (0, self.lanes - self.width)))

    def unpack(self, o: jax.Array, g: int) -> jax.Array:
        """The kernel's ``(B, nq, lanes)`` output -> ``(B, kv, g, dh)``:
        each head's rows in its own lanes."""
        B, n_kv, dh = o.shape[0], self.n_kv, self.d_head
        o = o[:, : n_kv * g, : self.width].reshape(B, n_kv, g, n_kv, dh)
        return jnp.moveaxis(jnp.diagonal(o, axis1=1, axis2=3), -1, 1)


def block_len(positions: int, block: Optional[int] = None) -> int:
    """Positions per grid step for a slot of ``positions``: ``block``
    (default ``DECODE_BLOCK``), at most the whole slot."""
    return min(block or DECODE_BLOCK, positions)


def live_blocks(lengths, bk: int) -> np.ndarray:
    """Blocks each row of ``lengths`` valid positions has the kernel
    fetch (at least its first), on the host."""
    return np.maximum(1, -(-np.asarray(lengths) // bk))


def _kernel(row_ref, blk_ref, slot_ref, len_ref, layer_ref, q_ref, k_ref, v_ref,
            o_ref, acc_ref, m_ref, l_ref, *, bk: int, softcap: float):
    del slot_ref, layer_ref  # read by the index maps
    w = pl.program_id(0)
    j = blk_ref[w]
    length = len_ref[row_ref[w]]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...]  # (nq, lanes)
    kb = k_ref[...].astype(q.dtype)  # (bk, lanes)
    s = jnp.dot(q, kb.T, preferred_element_type=jnp.float32)  # (nq, bk)
    if softcap:
        cap = jnp.float32(softcap)
        s = cap * jnp.tanh(s / cap)
    # TPU iota must be >= 2-D
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(j * bk + col < length, s, NEG_INF)
    # zero V beyond the length: an all-masked row's probs are 1, not 0
    vrow = jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
    vb = jnp.where(j * bk + vrow < length, v_ref[...].astype(q.dtype), 0)
    m_prev = m_ref[...]  # (nq, 128) replicated
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, :1])
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)[:, None]
    acc_ref[...] = acc_ref[...] * alpha[:, :1] + jnp.dot(
        p.astype(vb.dtype), vb, preferred_element_type=jnp.float32)

    @pl.when((j + 1) * bk >= length)
    def _flush():
        o_ref[...] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


def attention_decode_pool(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    slot_ids: jax.Array,
    lengths: jax.Array,
    layer: jax.Array,
    *,
    layout: PoolLayout,
    softcap: float = 0.0,
    block: Optional[int] = None,
) -> jax.Array:
    """softmax(mask(q K^T)) V for one query token per row, K/V read from
    row ``slot_ids[b]`` of layer ``layer`` of the pools.

    q:(B, kv, g, dh), pre-scaled by ``d_head**-0.5``; pools
    ``(layers, slots+1, T, lanes)`` in ``layout``; ``lengths`` (B,) valid
    positions per row (>= 1); ``layer`` a scalar.  Returns (B, kv, g, dh)
    in q's dtype.  ``block`` positions per grid step (default
    ``DECODE_BLOCK``)."""
    B, _, g, _ = q.shape
    _, _, positions, lanes = k_pool.shape
    assert lanes == layout.lanes and k_pool.shape == v_pool.shape, (
        f"pools {k_pool.shape} / {v_pool.shape} are not in {layout}")
    bk = block_len(positions, block)
    lengths = jnp.clip(jnp.asarray(lengths, jnp.int32).reshape(B), 1, positions)
    # the work list: one grid step per live (row, block), rows in order
    n_live = (lengths + bk - 1) // bk
    ends = jnp.cumsum(n_live)
    w = jnp.arange(B * cdiv(positions, bk), dtype=jnp.int32)
    # row of step w: how many rows end at or before it (one compare-and-
    # sum fusion; a searchsorted loop costs ~12 us a layer on a v5e)
    row = jnp.minimum(jnp.sum(w[:, None] >= ends[None, :], axis=1, dtype=jnp.int32), B - 1)
    blk = w - (ends - n_live)[row]
    qp = layout.pack_queries(q)
    nq = qp.shape[1]

    def kv_index(w, row_ref, blk_ref, slot_ref, len_ref, layer_ref):
        return (layer_ref[0], slot_ref[row_ref[w]], blk_ref[w], 0)

    def row_index(w, row_ref, *_):
        return (row_ref[w], 0, 0)

    kv_spec = pl.BlockSpec((pl.squeezed, pl.squeezed, bk, lanes), kv_index)
    row_spec = pl.BlockSpec((pl.squeezed, nq, lanes), row_index)
    o = pl.pallas_call(
        functools.partial(_kernel, bk=bk, softcap=softcap),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(ends[-1],),
            in_specs=[row_spec, kv_spec, kv_spec],
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM((nq, lanes), jnp.float32),  # output accumulator
                pltpu.VMEM((nq, MXU_EDGE), jnp.float32),  # running max
                pltpu.VMEM((nq, MXU_EDGE), jnp.float32),  # running denominator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, nq, lanes), q.dtype),
        compiler_params=CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=should_interpret(),
        name="attention_decode_pool",
    )(
        row, blk,
        jnp.asarray(slot_ids, jnp.int32).reshape(B),
        lengths,
        jnp.asarray(layer, jnp.int32).reshape(1),
        qp, k_pool, v_pool,
    )
    return layout.unpack(o, g)
