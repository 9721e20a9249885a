"""Grouped-query attention with RoPE, sliding windows, logit soft-capping,
QK-norm and prefix-LM masking — covering every assigned attention arch.

Prefill/training uses a *statically-chunked* causal schedule: an unrolled
loop over query chunks where chunk ``i`` attends the key prefix
``[start_i, (i+1)*chunk)`` with static bounds.  This keeps HLO FLOPs within
~1 diagonal-chunk of the causal optimum (no full S x S materialisation, no
dynamic-trip-count while loops that would blind ``cost_analysis``), and
peak logits memory at ``chunk x S`` per head.

GQA is computed in grouped form (``(kv, group)`` head axes) so K/V are
never materialised at ``n_heads`` width.

The whole ``softmax(mask(Q K^T)) V`` subgraph — in train *and* serve —
routes through ``core.dispatch_attention``, so the same
``use_policy(...)`` scope that governs the dense-layer GEMMs selects
the attention *plan*: the fused flash kernel (``FUSED_ATTN``,
optionally at a learned ``(bq, bk)`` tile) or the unfused pair whose
``Q K^T`` (batched NT) and ``probs @ V`` (batched NN) sub-GEMMs are
dispatched under their own per-op keys.  Masking (causal, window,
prefix-LM, per-row decode validity) is expressed as plan parameters,
not caller-built boolean arrays, so both plan arms apply it
identically and chaos-mode fallback is token-exact.  Gradients
re-enter dispatch through the engine's custom_vjp.  The leading
``(batch, kv)`` axes collapse to the OpKey's batch extent ``g`` and the
GQA group axis folds into the per-slice *query* extent ``m`` (declared
via ``q_seg``) — each kv head's group of queries shares one K/V slice,
so K/V are still never materialised (or broadcast) at ``n_heads``
width.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.engine import dispatch_attention, scope_name
from repro.core.opkey import OpKey
from repro.core.policy import Decision
from repro.distributed.context import current_mesh
from repro.kernels.attention_decode import PoolLayout, attention_decode_pool

from .layers import Param, dense, init_dense, init_rmsnorm, rmsnorm
from .rope import apply_rope

__all__ = [
    "AttnConfig",
    "init_attention",
    "attention",
    "attention_decode",
    "init_attn_cache",
]


@dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv: int
    d_head: int
    window: Optional[int] = None  # None => global attention
    softcap: float = 0.0
    rope_theta: float = 10000.0
    qk_norm: bool = False
    chunk: int = 1024  # query-chunk length for the blocked schedule
    # beyond-paper (§Perf): shard the attention *core* over the model axis
    # on the query-sequence dim — the win when head counts don't divide the
    # axis (smollm: 9 heads on a 16-wide axis => replicated core otherwise)
    sp_attention: bool = False

    @property
    def group(self) -> int:
        assert self.n_heads % self.n_kv == 0
        return self.n_heads // self.n_kv


def init_attention(key: jax.Array, cfg: AttnConfig, dtype=jnp.float32) -> Param:
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": init_dense(kq, cfg.n_heads * cfg.d_head, cfg.d_model, dtype),
        "wk": init_dense(kk, cfg.n_kv * cfg.d_head, cfg.d_model, dtype),
        "wv": init_dense(kv, cfg.n_kv * cfg.d_head, cfg.d_model, dtype),
        "wo": init_dense(ko, cfg.d_model, cfg.n_heads * cfg.d_head, dtype),
    }
    if cfg.qk_norm:
        p["qn"] = init_rmsnorm(cfg.d_head, dtype)
        p["kn"] = init_rmsnorm(cfg.d_head, dtype)
    return p


def _project_qkv(
    p: Param, x: jax.Array, cfg: AttnConfig, positions: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x:(B,S,d) -> q:(B,S,kv,g,dh), k/v:(B,S,kv,dh), RoPE'd and normed."""
    B, S, _ = x.shape
    q = dense(p["wq"], x).reshape(B, S, cfg.n_heads, cfg.d_head)
    k = dense(p["wk"], x).reshape(B, S, cfg.n_kv, cfg.d_head)
    v = dense(p["wv"], x).reshape(B, S, cfg.n_kv, cfg.d_head)
    if cfg.qk_norm:
        q = rmsnorm(p["qn"], q)
        k = rmsnorm(p["kn"], k)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = q.reshape(B, S, cfg.n_kv, cfg.group, cfg.d_head)
    return q, k, v


def _barrier_impl(q, dep):
    q2, _ = jax.lax.optimization_barrier((q, dep))
    return q2


def _barrier_bwd(dep, g):
    # dep's cotangent is mathematically zero, but it must stay *barriered
    # to g*: the zero flows into chunk i's output cotangent, forcing chunk
    # i's backward to schedule after chunk i+1's — the same serialization
    # (and peak-memory bound) the forward barrier provides.  An unchained
    # plain zero would let XLA run every chunk's backward concurrently.
    g2, zero = jax.lax.optimization_barrier((g, jnp.zeros_like(dep)))
    return g2, zero


# optimization_barrier has no differentiation rule on older jax; the barrier
# is an identity, so give it one that keeps the scheduling chain intact in
# both directions.
_chunk_barrier = jax.custom_vjp(_barrier_impl)
_chunk_barrier.defvjp(lambda q, dep: (_barrier_impl(q, dep), dep), _barrier_bwd)


def _chunk_attend(
    q_chunk: jax.Array,  # (B, C, kv, g, dh) already scaled
    k_slab: jax.Array,  # (B, L, kv, dh)
    v_slab: jax.Array,  # (B, L, kv, dh)
    cfg: AttnConfig,
    q_lo: int,  # absolute position of this chunk's first query
    k_lo: int,  # absolute position of the slab's first key
    prefix_len: int,
) -> jax.Array:
    """One query chunk's attention as a policy-dispatched *plan*.

    The GQA group folds into the per-slice query extent (m = g*C) so
    each of the B*kv batch slices attends ONE K/V slice — no broadcast
    or replication across the group, same as the einsum this replaced.
    ``q_seg=C`` tells the plan the fold width, so row ``r`` of a slice
    sits at absolute query position ``q_lo + r % C`` and the causal /
    window / prefix masks land per group member, not per folded row.
    """
    B, C, kv, g, dh = q_chunk.shape
    L = k_slab.shape[1]
    q2 = q_chunk.transpose(0, 2, 3, 1, 4).reshape(B * kv, g * C, dh)
    k2 = jnp.swapaxes(k_slab, 1, 2).reshape(B * kv, L, dh)
    v2 = jnp.swapaxes(v_slab, 1, 2).reshape(B * kv, L, dh)
    out = dispatch_attention(
        q2,
        k2,
        v2,
        causal=True,
        window=cfg.window or 0,
        q_start=q_lo,
        k_start=k_lo,
        prefix_len=prefix_len,
        q_seg=C,
        softcap=cfg.softcap,
    )
    out = out.reshape(B, kv, g, C, dh)
    return out.transpose(0, 3, 1, 2, 4)  # (B, C, kv, g, dh)


def attention(
    p: Param,
    x: jax.Array,
    cfg: AttnConfig,
    positions: Optional[jax.Array] = None,
    prefix_len: int = 0,
    return_kv: bool = False,
    max_seq: Optional[jax.Array] = None,
    cache_dtype=jnp.bfloat16,
    true_len: Optional[jax.Array] = None,
):
    """Training/prefill attention.  x: (B, S, d_model) -> (B, S, d_model).

    With ``return_kv`` also returns a decode cache covering this prefill
    (ring-ordered for windowed layers; padded to ``max_seq`` for global).

    ``true_len`` (scalar or ``(B,)``, traced OK) marks a *right-padded*
    prefill: only the first ``true_len`` positions of each row are real
    tokens.  Causality already keeps the pad junk out of the real rows'
    outputs; ``true_len`` additionally makes the returned cache correct —
    windowed layers ring-order the last ``window`` *real* positions (the
    junk tail never evicts live keys), and decode masks global layers by
    per-sequence length.  This is what lets the serving engine bucket
    prompt lengths without max-len recompiles.
    """
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    if cfg.sp_attention:
        # seq-shard the whole attention block's input: the QKV/O
        # projections (replicated weights for non-dividing head counts)
        # then compute sequence-parallel instead of fully replicated
        from repro.distributed.context import constrain as _c, dp_axes as _d
        from jax.sharding import PartitionSpec as _PP

        x = _c(x, _PP(_d() or None, "model"))
    q, k, v = _project_qkv(p, x, cfg, positions)
    q = q * (cfg.d_head**-0.5)

    chunk = min(cfg.chunk, S)
    if S % chunk != 0:  # ragged tail (tests / odd prefills): single chunk
        chunk = S
    n_chunks = S // chunk

    if cfg.sp_attention:
        from repro.distributed.context import constrain, dp_axes
        from jax.sharding import PartitionSpec as _P

        _daxes = dp_axes() or None

    outs = []
    dep = None  # chains chunks: without an explicit dependency XLA may
    # schedule all chunks concurrently and their f32 logits buffers all
    # stay live (~17 GB at 32k prefill — found by the dry-run memory
    # proof).  The barrier serializes chunk i+1 after chunk i so the
    # buffers get reused; on TPU the chunks run back-to-back anyway.
    for i in range(n_chunks):
        q_lo, q_hi = i * chunk, (i + 1) * chunk
        if cfg.window is not None:
            # earliest key any query in this chunk may see, block-aligned
            lo = max(0, ((q_lo - cfg.window + 1) // chunk) * chunk)
        else:
            lo = 0
        if prefix_len > 0:
            lo = 0  # prefix keys always visible
        k_slab = k[:, lo:q_hi]
        v_slab = v[:, lo:q_hi]
        q_chunk = q[:, q_lo:q_hi]
        if dep is not None:
            q_chunk = _chunk_barrier(q_chunk, dep)
        if cfg.sp_attention:
            # shard queries over 'model' for the chunk; K/V stay replicated
            q_chunk = constrain(q_chunk, _P(_daxes, "model"))
        o = _chunk_attend(q_chunk, k_slab, v_slab, cfg, q_lo, lo, prefix_len)
        if cfg.sp_attention:
            o = constrain(o, _P(_daxes, "model"))
        dep = o
        outs.append(o)
    out = jnp.concatenate(outs, axis=1)  # (B, S, kv, g, dh)
    if cfg.sp_attention:  # return to batch-only sharding for the residual
        out = constrain(out, _P(_daxes, None))
    out = out.reshape(B, S, cfg.n_heads * cfg.d_head)
    out = dense(p["wo"], out)
    if not return_kv:
        return out
    # build the decode cache this prefill implies
    max_seq = max_seq or S
    slots = min(cfg.window, max_seq) if cfg.window is not None else max_seq
    if cfg.window is not None and (true_len is not None or S >= slots):
        # Ring order: slot i holds the newest position p < true_len with
        # p ≡ i (mod slots).  Slots with no such position (short
        # sequences) gather junk that the decode validity mask excludes.
        # Gathering by position (instead of slicing the last `slots`
        # columns) drops the old slots | S alignment requirement and
        # keeps padded-prefill junk out of the live window.
        tl = jnp.asarray(S if true_len is None else true_len, jnp.int32)
        tl_b = jnp.broadcast_to(jnp.atleast_1d(tl), (B,))  # (B,)
        i = jnp.arange(slots)[None, :]
        p_i = tl_b[:, None] - 1 - ((tl_b[:, None] - 1 - i) % slots)
        src = jnp.clip(p_i, 0, S - 1)
        gather = jax.vmap(lambda a, s: jnp.take(a, s, axis=0))
        ck, cv = gather(k, src), gather(v, src)
    else:
        pad = ((0, 0), (0, slots - S), (0, 0), (0, 0))
        ck, cv = jnp.pad(k, pad), jnp.pad(v, pad)
    return out, {"k": ck.astype(cache_dtype), "v": cv.astype(cache_dtype)}


# -- decode (one new token against a cache) ----------------------------------


def init_attn_cache(
    batch: int, cfg: AttnConfig, max_seq: int, dtype=jnp.bfloat16
) -> Dict[str, jax.Array]:
    """Ring buffer of ``window`` slots for local layers, else ``max_seq``."""
    slots = min(cfg.window, max_seq) if cfg.window is not None else max_seq
    shape = (batch, slots, cfg.n_kv, cfg.d_head)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def attention_decode(
    p: Param,
    x: jax.Array,  # (B, 1, d_model)
    cfg: AttnConfig,
    cache: Dict[str, jax.Array],
    pos: jax.Array,  # scalar int32, or (B,) per-sequence positions
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode step.  ``pos`` is the index of each row's new token —
    a scalar (uniform batch: the fixed-batch serve path) or a ``(B,)``
    vector (ragged batch: the continuous-batching engine, where every
    cache slot holds a sequence of a different length).

    Each row writes its K/V at its *own* position and attends only the
    slots its own length has filled: the validity mask is per sequence,
    so short sequences never attend the stale/uninitialised slots beyond
    their length (they used to, whenever ``pos`` under-described a mixed-
    length batch — the mask was shared across rows).
    """
    if "slots" in cache:  # the serving engine's slot pool
        return _decode_in_pool(p, x, cfg, cache, pos)
    B = x.shape[0]
    slots = cache["k"].shape[1]
    pos_b = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(pos, jnp.int32)), (B,))
    q, k_new, v_new = _project_qkv(p, x, cfg, pos_b[:, None])
    q = q * (cfg.d_head**-0.5)

    write = pos_b % slots if cfg.window is not None else pos_b
    upd = jax.vmap(
        lambda c, n, s: jax.lax.dynamic_update_slice_in_dim(c, n, s, axis=0)
    )
    ck = upd(cache["k"], k_new.astype(cache["k"].dtype), write)
    cv = upd(cache["v"], v_new.astype(cache["v"].dtype), write)

    # per-row validity: row i sees exactly the slots its own length has
    # filled, expressed as the plan's `lengths` operand (each kv head of
    # a row shares that row's length) — short sequences never attend the
    # stale/uninitialised slots beyond their length, in either plan arm
    lengths = jnp.repeat(jnp.minimum(pos_b + 1, slots), cfg.n_kv)
    q2 = q.transpose(0, 2, 3, 1, 4).reshape(B * cfg.n_kv, cfg.group, cfg.d_head)
    k2 = jnp.swapaxes(ck.astype(q.dtype), 1, 2).reshape(
        B * cfg.n_kv, slots, cfg.d_head
    )
    v2 = jnp.swapaxes(cv.astype(q.dtype), 1, 2).reshape(
        B * cfg.n_kv, slots, cfg.d_head
    )
    out = dispatch_attention(
        q2, k2, v2, lengths=lengths, softcap=cfg.softcap
    )
    out = out.reshape(B, 1, cfg.n_heads * cfg.d_head)
    return dense(p["wo"], out), {"k": ck, "v": cv}


def _decode_in_pool(p, x, cfg, cache, pos):
    """One decode step read and written in place in the engine's slot
    pool.  ``cache`` holds the whole pool leaves ``k``/``v``
    ``(layers, slots+1, T, lanes)`` (``kernels/attention_decode``
    layout), this layer's index ``layer`` and each row's slot ``slots``
    (B,); ``pos`` (B,) is each row's new-token position.  The new K/V
    land at ``(layer, slots[b], pos[b])`` (``pos % window`` in a ring)
    and the kernel attends each row's ``min(pos + 1, window)`` valid
    positions.  Returns the updated pool leaves."""
    B = x.shape[0]
    pos_b = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(pos, jnp.int32)), (B,))
    q, k_new, v_new = _project_qkv(p, x, cfg, pos_b[:, None])
    q = q[:, 0] * (cfg.d_head**-0.5)  # (B, kv, g, dh)
    if cfg.window is not None:
        write, valid = pos_b % cfg.window, jnp.minimum(pos_b + 1, cfg.window)
    else:
        write, valid = pos_b, pos_b + 1
    layout, layer, slots = PoolLayout(cfg.n_kv, cfg.d_head), cache["layer"], cache["slots"]
    ck = layout.write(cache["k"], k_new[:, 0], layer, slots, write)
    cv = layout.write(cache["v"], v_new[:, 0], layer, slots, write)
    positions = ck.shape[2]
    key = OpKey("ATTN", cfg.group, positions, cfg.d_head,
                jnp.dtype(q.dtype).itemsize, B * cfg.n_kv)
    kernel = functools.partial(attention_decode_pool, layout=layout, softcap=cfg.softcap)
    mesh = current_mesh()
    if mesh is not None and mesh.size > 1:
        # Mosaic kernels are not partitioned automatically: every device
        # runs the kernel on the whole (replicated) operands
        kernel = jax.shard_map(kernel, mesh=mesh, in_specs=P(), out_specs=P(),
                               check_vma=False)
    with jax.named_scope(scope_name(key, Decision("RAGGED_DECODE"))):
        out = kernel(q, ck, cv, slots, valid, layer)
    out = out.astype(q.dtype).reshape(B, 1, cfg.n_heads * cfg.d_head)
    return dense(p["wo"], out), {"k": ck, "v": cv}
