"""Slot-based paged KV cache for the continuous-batching engine.

One device-resident cache pytree (shaped like the ``segments`` half of
``models/lm.py::init_lm_cache``) holds ``n_slots + 1`` sequences: every
leaf is ``(layers, n_slots + 1, ...)`` with the sequence axis at
position 1.  Attention leaves are stored in the decode kernel's layout
(``kernels/attention_decode.PoolLayout``: ``(layers, n_slots + 1, T,
lanes)``), so a decode step reads and writes them in place by slot
id and never copies them; other leaves (SSM state) keep their own shape
and are gathered and scattered by row (``take_rows`` / ``put_rows``).  A
request is admitted by *allocating a slot* and moving its (batch=1)
prefill cache into that row, in the pool's layout; it is evicted by
freeing the slot — no reshapes, no max-batch padding, and ragged
sequence lengths coexist because every slot carries its own write
position (``lengths``, the per-sequence ``pos`` vector
``attention_decode`` consumes).

The extra row — ``null_slot`` — is scratch: decode steps run at bucketed
batch sizes, and the padding rows of a partially-filled bucket all point
at it, so their writes land on trash instead of a live sequence (scatter
order over duplicate indices is undefined; duplicates of a row nobody
reads are harmless).

Beside the pool, ``tokens`` holds the last token of every row (the null
row included) on the device: a prefill's first token lands there with
its cache, each decode's tokens are scattered back (``put_tokens``), and
the next decode gathers its input from it by slot id.  So the host never
uploads a token, and can launch a decode before the previous one's
tokens have reached it.

Device work (insert, put_tokens) is jitted with the big cache and the
token vector donated, so both are updated in place.  Slot bookkeeping
(free list, lengths, owners) is host-side numpy — it changes between jit
calls, never inside them.
"""

from __future__ import annotations

import functools
import threading
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.attention_decode import PoolLayout
from repro.models import lm
from repro.models.blocks import ATTN_MIXERS

__all__ = ["PagedKVCache", "init_pool", "pool_extents", "put_rows", "take_rows"]


def _per_block(cfg, attn: Callable, other: Callable, *trees):
    """Map ``attn`` over the attention blocks' caches of the segment
    trees and ``other`` over the rest, block by block."""
    return [
        tuple(
            (attn if b.mixer in ATTN_MIXERS else other)(*caches)
            for b, *caches in zip(blocks, *segs)
        )
        for (_, blocks), *segs in zip(cfg.segments, *trees)
    ]


def init_pool(cfg, n_rows: int, max_seq: int, dtype=jnp.bfloat16):
    """Zero pool of ``n_rows`` sequences: attention leaves in the decode
    kernel's layout, other leaves as ``lm.init_lm_cache`` makes them."""
    layout = PoolLayout(cfg.n_kv, cfg.d_head)
    shapes = jax.eval_shape(
        lambda: lm.init_lm_cache(cfg, n_rows, max_seq, dtype))["segments"]
    return _per_block(
        cfg,
        lambda c: jax.tree.map(lambda s: jnp.zeros(layout.shape(s.shape), s.dtype), c),
        lambda c: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), c),
        shapes,
    )


def pool_extents(cfg, pool):
    """``(layers, positions per slot)`` of each attention block's leaves."""
    return [
        (c["k"].shape[0], c["k"].shape[2])
        for (_, blocks), seg in zip(cfg.segments, pool)
        for b, c in zip(blocks, seg)
        if b.mixer in ATTN_MIXERS
    ]


def take_rows(cfg, pool, slot_ids):
    """The decode step's view of the pool for rows ``slot_ids``: the
    attention leaves whole (the kernel indexes them by slot), the other
    leaves' rows gathered."""
    return _per_block(
        cfg, lambda c: c,
        lambda c: jax.tree.map(lambda leaf: jnp.take(leaf, slot_ids, axis=1), c),
        pool,
    )


def put_rows(cfg, pool, rows, slot_ids):
    """Inverse of ``take_rows`` after a decode step: the attention leaves
    as the step left them (written in place), the others' rows scattered
    back."""
    return _per_block(
        cfg, lambda big, new: new,
        lambda big, new: jax.tree.map(
            lambda b, r: b.at[:, slot_ids].set(r.astype(b.dtype)), big, new),
        pool, rows,
    )


@functools.partial(jax.jit, donate_argnums=(0,))
def _put_tokens(tokens, slot_ids, tok):
    return tokens.at[slot_ids].set(tok)


class PagedKVCache:
    """Fixed pool of ``n_slots`` sequence slots + 1 null scratch row."""

    def __init__(self, cfg, n_slots: int, max_seq: int, dtype=jnp.bfloat16):
        if n_slots < 1:
            raise ValueError(f"need at least one slot, got {n_slots}")
        self.cfg = cfg
        self.n_slots = int(n_slots)
        self.max_seq = int(max_seq)
        self.null_slot = self.n_slots  # scratch row for bucket padding
        self.data = init_pool(cfg, self.n_slots + 1, max_seq, dtype)
        self.tokens = jnp.zeros(self.n_slots + 1, jnp.int32)
        # slot bookkeeping is shared with the engine's admission path;
        # allocate/free must be atomic under concurrent submitters
        self._lock = threading.Lock()
        self._free: List[int] = list(range(self.n_slots))  # guarded-by: _lock
        self.lengths = np.zeros(self.n_slots + 1, np.int32)
        self.owner: Dict[int, Any] = {}  # slot -> request id; guarded-by: _lock
        layout = PoolLayout(cfg.n_kv, cfg.d_head)
        self._insert = jax.jit(
            lambda big, tokens, rows, token, slot: (_per_block(
                cfg,
                lambda b, r: self._insert_leaves(b, r, slot, layout.pack),
                lambda b, r: self._insert_leaves(b, r, slot, lambda x: x),
                big, rows,
            ), jax.lax.dynamic_update_slice_in_dim(tokens, token, slot, axis=0)),
            donate_argnums=(0, 1),
        )

    @staticmethod
    def _insert_leaves(big, rows, slot, to_pool):
        """Land a batch=1 cache in row ``slot`` (axis 1), in the pool's
        layout."""
        return jax.tree.map(
            lambda b, r: jax.lax.dynamic_update_slice_in_dim(
                b, to_pool(r).astype(b.dtype), slot, axis=1
            ),
            big,
            rows,
        )

    # -- slot lifecycle --------------------------------------------------

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def active_slots(self) -> List[int]:
        return sorted(self.owner)

    def allocate(self, owner: Any) -> Optional[int]:
        """Claim a free slot for ``owner`` (None when the pool is full)."""
        with self._lock:
            if not self._free:
                return None
            slot = self._free.pop(0)
            self.owner[slot] = owner
        self.lengths[slot] = 0
        return slot

    def free(self, slot: int) -> None:
        """Release a slot back to the pool.  The KV rows are left in
        place — the next occupant's prefill overwrites them, and until
        then its zero length masks every stale position."""
        with self._lock:
            if slot not in self.owner:
                raise KeyError(f"slot {slot} is not allocated")
            del self.owner[slot]
            self._free.append(slot)
        self.lengths[slot] = 0

    def insert(self, prefill_cache: Dict[str, Any], slot: int, length: int, token):
        """Land a request's prefill cache (batch=1 pytree from
        ``lm_prefill``) in its slot with its first token (a ``(1,)``
        device array), and record its true length."""
        if slot not in self.owner:
            raise KeyError(f"slot {slot} is not allocated")
        self.data, self.tokens = self._insert(
            self.data, self.tokens, prefill_cache["segments"], token, np.int32(slot)
        )
        self.lengths[slot] = int(length)

    def put_tokens(self, slot_ids, tok) -> None:
        """A decode step's tokens become its rows' next inputs."""
        self.tokens = _put_tokens(self.tokens, slot_ids, tok)

    def advance(self, slots) -> None:
        """One decode step was launched for ``slots`` (distinct): their
        lengths grew."""
        self.lengths[np.asarray(slots, np.intp)] += 1

    def __repr__(self):
        return (
            f"PagedKVCache(slots={self.n_slots}, free={self.n_free}, "
            f"max_seq={self.max_seq})"
        )
