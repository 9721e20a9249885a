"""Fused flash-style attention: the whole ``Q K^T -> softmax -> probs V``
subgraph as ONE Pallas kernel.

  attention_fused   q:(g, m, dh)  k:(g, n, dh)  v:(g, n, dh) -> (g, m, dh)

This is the fused alternative to the unfused pair of batched GEMMs the
dispatch layer otherwise picks per op (``BNT`` then ``BNN`` with an XLA
softmax between them): the grid runs one parallel axis over the batch
slices, one parallel axis over query blocks, and a *sequential* sweep
over key/value blocks carrying an online softmax — the (m, n) logits
matrix never touches HBM.  Accumulation is f32 throughout (running max,
running denominator, output accumulator live in f32 VMEM scratch), so
the kernel is bf16-safe: low-precision inputs only ever feed the MXU,
never the softmax state.

Masking happens *inside* the kernel from static ``MaskParams`` plus a
traced per-slice ``lengths`` operand, so causal / sliding-window /
prefix-LM prefill and validity-masked decode all run the same schedule.
The GQA group fold (engine collapses the group axis into the per-slice
query extent) is expressed by ``q_seg``: query row ``r`` of a slice sits
at sequence position ``q_start + r % q_seg``.

Masked logits use a *finite* ``NEG_INF`` (-1e30) so ``exp`` underflows
to an exact 0.0 instead of producing ``inf - inf = nan``; key/value rows
beyond ``lengths`` additionally zero V before the mix so poisoned or
uninitialised padding can never reach the accumulator through the
``0 * nan`` hole.  A row with no visible key at all converges to the
mean of the (zeroed) value rows — such rows only ever exist in the
sliced-off query padding (causal rows always see themselves; decode
lengths are >= 1).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (
    CompilerParams,
    DEFAULT_BLOCK,
    MXU_EDGE,
    cdiv,
    normalize_block,
    round_up,
    should_interpret,
)
from .gridspec import BlockMap, KernelGridSpec

__all__ = ["MaskParams", "attn_grid_spec", "attention_fused"]

NEG_INF = -1e30  # finite: exp(NEG_INF - finite_max) == 0.0 exactly, no nan


@dataclass(frozen=True)
class MaskParams:
    """Static (hashable) mask description for one fused-attention call.

    Query row ``r`` of a slice sits at absolute position
    ``q_start + r % q_seg`` (``q_seg`` is the per-group query count after
    the engine folds the GQA group axis into the row extent); key column
    ``c`` sits at ``k_start + c``.  Visibility is

        valid(c) AND causal AND window,  OR'd with  valid(c) AND prefix

    where ``valid(c) = c < lengths[slice]`` comes from the traced
    ``lengths`` operand.  The default instance masks nothing beyond
    validity — what the measurement/verification passes run.
    """

    causal: bool = False
    window: int = 0  # 0 => no sliding window
    q_start: int = 0
    k_start: int = 0
    prefix_len: int = 0
    q_seg: int = 0  # 0 => q_seg = full query extent (no group fold)
    softcap: float = 0.0


def _kv_band(mp: int, np_: int, bq: int, bk: int, mask: Optional[MaskParams]):
    """Static kv-band geometry for a sliding-window mask: the widest
    count of kv blocks any q block can see, plus the first-live-block
    index as a function of the q-block index (callable on python ints
    *and* traced grid indices).  Returns ``(None, None)`` when the mask
    cannot shrink the sweep — no window, a prefix (which re-enables
    early blocks), or a band as wide as the dense sweep."""
    if mask is None or not mask.window or mask.prefix_len:
        return None, None
    q_seg = mask.q_seg or mp
    nk = cdiv(np_, bk)

    def lo_block(i):
        # first kv block the window admits for q block i.  `same` is a
        # bool (python or traced); multiplying keeps both paths branch-
        # free: a block spanning segments sees the whole [0, q_seg) fold.
        lo_r = i * bq
        hi_r = lo_r + bq - 1
        same = lo_r // q_seg == hi_r // q_seg
        min_mod = (lo_r % q_seg) * same
        col = mask.q_start + min_mod - mask.window + 1 - mask.k_start
        clip = max if isinstance(col, int) else jnp.maximum
        return clip(col, 0) // bk

    def hi_block(i):  # python ints only — static width computation
        lo_r = i * bq
        hi_r = lo_r + bq - 1
        same = lo_r // q_seg == hi_r // q_seg
        max_mod = hi_r % q_seg if same else q_seg - 1
        col = np_ - 1
        if mask.causal:
            col = min(col, mask.q_start + max_mod - mask.k_start)
        return min(nk - 1, col // bk) if col >= 0 else -1

    mq = cdiv(mp, bq)
    width = max(1, max(hi_block(i) - lo_block(i) + 1 for i in range(mq)))
    if width >= nk:
        return None, None
    return width, lo_block


def attn_grid_spec(
    g: int,
    m: int,
    n: int,
    dh: int,
    block: Optional[Tuple[int, int]] = None,
    mask: Optional[MaskParams] = None,
) -> KernelGridSpec:
    """The fused-attention schedule at logical shape (g, m, n, dh):
    parallel (batch, q-block) axes, sequential kv-block sweep; the head
    dim rides whole (padded to the MXU edge) in every block.  Consumed
    by ``attention_fused`` and verified by ``repro.analysis.coverage``.

    With a sliding-window ``mask`` the sequential axis shrinks to the
    widest visible band and the kv index map offsets each step to the
    first block the window admits — the flash-attention grid-level skip
    (kv blocks outside every q block's band are never scheduled at all).
    Without a mask the schedule is the dense sweep the coverage pass
    proves."""
    bq, bk = normalize_block((m, n), block, (DEFAULT_BLOCK[0], DEFAULT_BLOCK[2]))
    mp, np_ = round_up(m, bq), round_up(n, bk)
    dhp = round_up(max(dh, 1), MXU_EDGE)
    nk = cdiv(np_, bk)
    width, kv_lo = _kv_band(mp, np_, bq, bk, mask)
    if width is None:
        n_kv = nk
        kv_index = lambda gi, i, kk: (gi, kk, 0)  # noqa: E731
    else:
        n_kv = width
        # clamp keeps the read in range; steps past the last dense block
        # are dead (their positions fail the validity/causal predicates)
        kv_index = lambda gi, i, kk: (  # noqa: E731
            gi, jnp.minimum(kv_lo(i) + kk, nk - 1), 0
        )
    kv_map = BlockMap((1, bk, dhp), kv_index, (g, np_, dhp))
    return KernelGridSpec(
        name="attention_fused",
        grid=(g, cdiv(mp, bq), n_kv),
        in_specs=(
            BlockMap((1, bq, dhp), lambda gi, i, kk: (gi, i, 0), (g, mp, dhp)),
            kv_map,  # k
            kv_map,  # v
        ),
        out_spec=BlockMap(
            (1, bq, dhp), lambda gi, i, kk: (gi, i, 0), (g, mp, dhp)
        ),
        sequential=(2,),
        scalar_prefetch=1,  # lengths (g,) int32, whole, in SMEM
    )


def _grid_only(index_map, n_grid: int):
    """Pallas appends the scalar-prefetch refs to every index map's
    arguments; the spec's maps read the grid indices alone."""
    return lambda *args: index_map(*args[:n_grid])


def _kernel(
    len_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    acc_ref,
    max_ref,
    sum_ref,
    *,
    n_kv: int,
    bq: int,
    bk: int,
    mask: MaskParams,
    kv_lo=None,
):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        max_ref[...] = jnp.full_like(max_ref, NEG_INF)
        sum_ref[...] = jnp.zeros_like(sum_ref)

    q_seg = mask.q_seg if mask.q_seg else (pl.num_programs(1) * bq)
    # program ids are read once at the top level: inside the pl.when
    # branch below the primitive has no lowering rule, so the branch
    # closes over these values instead.  Under a banded grid (windowed
    # mask — see attn_grid_spec) step ki visits kv block kv_lo(qi) + ki,
    # so every position below derives from jj, not ki.
    qi, ki = pl.program_id(1), pl.program_id(2)
    jj = ki if kv_lo is None else kv_lo(qi) + ki
    length = len_ref[pl.program_id(0)]  # this slice's valid key count (SMEM)

    # Block-level skip: a kv block with no visible (row, col) pair
    # contributes exactly nothing to the online-softmax state (its exp'd
    # scores are all zero after rescaling), so skip its dots entirely —
    # the flash-attention win for causal / sliding-window geometry, where
    # most kv blocks fall outside the visible band.  Bounds are scalar
    # arithmetic on the program ids; the whole update sits under one cond.
    k_blo = mask.k_start + jj * bk  # lowest k_pos in block
    k_bhi = k_blo + bk - 1
    live = jj * bk < length  # any valid column at all
    if mask.causal or mask.window:
        # q_pos range of this block: rows r in [i*bq, i*bq + bq) map to
        # q_start + r % q_seg — a whole segment unless the block sits
        # inside one.
        lo_r = qi * bq
        hi_r = lo_r + bq - 1
        same_seg = lo_r // q_seg == hi_r // q_seg
        max_mod = jnp.where(same_seg, hi_r % q_seg, q_seg - 1)
        min_mod = jnp.where(same_seg, lo_r % q_seg, 0)
        dead = None
        if mask.causal:
            dead = k_blo > mask.q_start + max_mod
        if mask.window:
            dead_w = k_bhi <= mask.q_start + min_mod - mask.window
            dead = dead_w if dead is None else dead | dead_w
        if mask.prefix_len:
            dead &= k_blo >= mask.prefix_len  # prefix keys stay visible
        live &= ~dead

    @pl.when(live)
    def _update():
        q = q_ref[0]  # (bq, dhp): one slice's query block
        kb = k_ref[0]  # (bk, dhp)
        vb = v_ref[0]

        s = jnp.dot(q, kb.T, preferred_element_type=jnp.float32)  # (bq, bk)
        if mask.softcap:
            cap = jnp.float32(mask.softcap)
            s = cap * jnp.tanh(s / cap)

        # visibility: validity (traced lengths) AND the static position
        # masks.  Row/col indices are *local* to the padded operand;
        # positions add the static offsets.  TPU iota must be >= 2-D.
        rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        q_row = qi * bq + rows
        k_col = jj * bk + cols
        valid = k_col < length
        q_pos = mask.q_start + q_row % q_seg
        k_pos = mask.k_start + k_col
        vis = valid
        if mask.causal:
            vis &= k_pos <= q_pos
        if mask.window:
            vis &= k_pos > q_pos - mask.window
        if mask.prefix_len:
            vis |= valid & (k_pos < mask.prefix_len)
        s = jnp.where(vis, s, NEG_INF)

        # zero V beyond the valid length: an all-masked row's probs are 1
        # (not 0 — exp(NEG_INF - NEG_INF)), so junk V rows must not be
        # summable.
        vcols = jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        vb = jnp.where(jj * bk + vcols < length, vb, 0)

        # online-softmax update: rescale the running state by alpha, fold
        # in this block's exp'd scores.  All state f32.
        m_prev = max_ref[...]  # (bq, lanes) replicated
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])  # (bq, bk) f32
        max_ref[...] = m_new
        sum_ref[...] = sum_ref[...] * alpha + jnp.sum(p, axis=1)[:, None]
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jnp.dot(
            p.astype(vb.dtype), vb, preferred_element_type=jnp.float32
        )

    @pl.when(pl.program_id(2) == n_kv - 1)
    def _flush():
        denom = sum_ref[:, :1]
        denom = jnp.where(denom == 0.0, 1.0, denom)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _pad3(x: jax.Array, rows: int, cols: int) -> jax.Array:
    _, r, c = x.shape
    if r == rows and c == cols:
        return x
    return jnp.pad(x, ((0, 0), (0, rows - r), (0, cols - c)))


@functools.partial(
    jax.jit, static_argnames=("mask", "block", "interpret")
)
def attention_fused(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    lengths: Optional[jax.Array] = None,
    *,
    mask: MaskParams = MaskParams(),
    block: Optional[Tuple[int, int]] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """softmax(mask(Q K^T)) V per batch slice, one fused Pallas kernel.

    q:(g, m, dh), k/v:(g, n, dh) -> (g, m, dh).  ``lengths`` (g,) or
    (g, 1) int32 marks each slice's valid key count (None => all n);
    ``mask`` carries the static causal/window/prefix geometry.  Queries
    are expected pre-scaled (the model scales by ``d_head**-0.5`` before
    dispatch, same as the unfused path).
    """
    g, m, dh = q.shape
    g2, n, dh2 = k.shape
    assert g == g2 and dh == dh2 and k.shape == v.shape, (
        f"attention operand mismatch: {q.shape} vs {k.shape} vs {v.shape}"
    )
    if lengths is None:
        lengths = jnp.full((g,), n, jnp.int32)
    else:
        lengths = jnp.asarray(lengths, jnp.int32).reshape(g)
    spec = attn_grid_spec(g, m, n, dh, block=block, mask=mask)
    _, mp, dhp = spec.out_spec.extent
    np_ = spec.in_specs[1].extent[1]
    bq, bk = spec.out_spec.block[1], spec.in_specs[1].block[1]
    _, kv_lo = _kv_band(mp, np_, bq, bk, mask)
    qp = _pad3(q, mp, dhp)
    kp = _pad3(k, np_, dhp)
    vp = _pad3(v, np_, dhp)
    interp = should_interpret() if interpret is None else interpret
    n_grid = len(spec.grid)

    out = pl.pallas_call(
        functools.partial(
            _kernel, n_kv=spec.grid[2], bq=bq, bk=bk, mask=mask, kv_lo=kv_lo
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=spec.scalar_prefetch,
            grid=spec.grid,
            in_specs=[
                pl.BlockSpec(s.block, _grid_only(s.index_map, n_grid))
                for s in spec.in_specs
            ],
            out_specs=pl.BlockSpec(
                spec.out_spec.block, _grid_only(spec.out_spec.index_map, n_grid)
            ),
            scratch_shapes=[
                pltpu.VMEM((bq, dhp), jnp.float32),  # output accumulator
                pltpu.VMEM((bq, MXU_EDGE), jnp.float32),  # running max
                pltpu.VMEM((bq, MXU_EDGE), jnp.float32),  # running denominator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(spec.out_spec.extent, q.dtype),
        compiler_params=CompilerParams(
            dimension_semantics=spec.dimension_semantics
        ),
        interpret=interp,
        name=spec.name,
    )(lengths, qp, kp, vp)
    return out[:, :m, :dh]
