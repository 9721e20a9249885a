"""Tile-config space for the Pallas matmul family.

The paper selects an *algorithm* per shape; this module widens the space to
*(algorithm x tile config)*: every tunable kernel exposes a set of
admissible ``(bm, bn, bk)`` VMEM tiles, enumerated per shape/dtype under an
explicit VMEM budget, and the dispatch policies (``core/policy.py``) pick
one per decision.  AutoTVM-style configuration selection, scoped to the
three knobs our kernels actually have.

Admissibility of a tile:

  * every edge is a positive multiple of the MXU edge (128), so the
    systolic tiles stay full;
  * no edge exceeds the padded extent of its axis (a sub-128 dim gets one
    128-wide tile, never a 512 tile that is 3/4 padding);
  * the VMEM working set fits the budget: double-buffered A and B operand
    blocks + the f32 accumulator scratch + the staged output block.

``shortlist_tile_configs`` prunes the full space with the roofline tile
model (``core.simulate.tile_time``) so an autotune sweep measures a
handful of promising tiles instead of the whole cross product.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

from .common import DEFAULT_BLOCK, MXU_EDGE, pick_block, round_up

__all__ = [
    "TileConfig",
    "TransposeConfig",
    "TILE_EDGES_MN",
    "TILE_EDGES_K",
    "TRANSPOSE_TILE_EDGES",
    "DEFAULT_VMEM_BUDGET_BYTES",
    "DEFAULT_CONFIG_KEY",
    "config_key",
    "parse_config_key",
    "tile_vmem_bytes",
    "fits_vmem",
    "validate_config",
    "default_config",
    "enumerate_tile_configs",
    "shortlist_tile_configs",
    "transpose_vmem_bytes",
    "default_transpose_config",
    "enumerate_transpose_configs",
    "transpose_config_space",
    "ATTN_TILE_EDGES",
    "AttnConfig",
    "attn_vmem_bytes",
    "default_attn_config",
    "enumerate_attn_configs",
    "attn_config_space",
]

TileConfig = Tuple[int, int, int]
TransposeConfig = Tuple[int, int]
AttnConfig = Tuple[int, int]

# Candidate tile edges per axis.  bk may go deeper than the MN edges: a
# longer contraction strip costs VMEM linearly but halves the number of
# sequential k steps (accumulator flushes + grid overhead).
TILE_EDGES_MN: Tuple[int, ...] = (128, 256, 512)
TILE_EDGES_K: Tuple[int, ...] = (128, 256, 512, 1024)

# ~16 MiB of VMEM per core (TPU architecture guide); the budget covers the
# double-buffered operand blocks, the f32 accumulator and the output block.
DEFAULT_VMEM_BUDGET_BYTES: int = 16 * 1024 * 1024

# Cache/report key for "the candidate ran at its built-in tiling" — used
# for non-tunable candidates (XLA picks its own layout).
DEFAULT_CONFIG_KEY = "default"


def config_key(config: Optional[TileConfig]) -> str:
    """Stable string form used in measurement-cache entries and reports."""
    if config is None:
        return DEFAULT_CONFIG_KEY
    return "x".join(str(int(b)) for b in config)


def parse_config_key(key: str, arity: int = 3):
    """Inverse of ``config_key``; ``'default'`` maps to None.  ``arity`` is
    the expected tuple length — 3 for the matmul tiles, 2 for the transpose
    kernel's (b_rows, b_cols) tiles."""
    if key == DEFAULT_CONFIG_KEY:
        return None
    try:
        parts = tuple(int(p) for p in key.split("x"))
    except ValueError:
        raise ValueError(f"malformed tile-config key {key!r}") from None
    if len(parts) != arity or any(p <= 0 for p in parts):
        raise ValueError(f"malformed tile-config key {key!r}")
    return parts


def validate_config(config: Sequence[int], arity: int = 3) -> TileConfig:
    """A well-formed tile tuple of positive ints, or ValueError.  The
    default arity 3 is the matmul kernels' (bm, bn, bk); the fused
    attention kernel validates its (bq, bk) pairs with ``arity=2``."""
    config = tuple(config)
    if len(config) != arity:
        kinds = "(bq, bk)" if arity == 2 else "(bm, bn, bk)"
        raise ValueError(f"tile config {config} must be {kinds}")
    for b in config:
        if not isinstance(b, int) or isinstance(b, bool) or b <= 0:
            raise ValueError(f"tile config {config} must be positive ints")
    return config


def tile_vmem_bytes(config: TileConfig, dsize: int) -> int:
    """VMEM working set of one grid step of the blocked matmul kernels:
    double-buffered A (bm, bk) and B (bn, bk) operand blocks, the f32
    accumulator scratch, and the staged output block."""
    bm, bn, bk = config
    operands = 2 * (bm * bk + bn * bk) * dsize  # x2: double buffering
    accumulator = bm * bn * 4  # f32 scratch
    out_block = bm * bn * dsize
    return operands + accumulator + out_block


def fits_vmem(
    config: TileConfig, dsize: int, budget: int = DEFAULT_VMEM_BUDGET_BYTES
) -> bool:
    return tile_vmem_bytes(config, dsize) <= budget


def default_config(m: int, n: int, k: int) -> TileConfig:
    """``DEFAULT_BLOCK`` clamped to this shape — what a kernel runs when no
    config is supplied (the pre-autotuning behaviour)."""
    return (
        pick_block(m, DEFAULT_BLOCK[0]),
        pick_block(n, DEFAULT_BLOCK[1]),
        pick_block(k, DEFAULT_BLOCK[2]),
    )


def _axis_tiles(dim: int, edges: Sequence[int]) -> Tuple[int, ...]:
    """Distinct admissible tile widths for one axis: each candidate edge,
    clamped to the axis' padded extent (so sub-128 dims collapse to one
    128-wide option)."""
    padded = round_up(max(dim, 1), MXU_EDGE)
    return tuple(sorted({min(int(e), padded) for e in edges}))


def enumerate_tile_configs(
    m: int,
    n: int,
    k: int,
    dsize: int = 4,
    vmem_budget: int = DEFAULT_VMEM_BUDGET_BYTES,
    edges_mn: Sequence[int] = TILE_EDGES_MN,
    edges_k: Sequence[int] = TILE_EDGES_K,
) -> Tuple[TileConfig, ...]:
    """Every admissible (bm, bn, bk) for this shape/dtype, deterministic
    order.  The clamped default config is a member whenever it fits the
    budget (under the standard budget it always does)."""
    configs = {
        (bm, bn, bk)
        for bm in _axis_tiles(m, edges_mn)
        for bn in _axis_tiles(n, edges_mn)
        for bk in _axis_tiles(k, edges_k)
        if fits_vmem((bm, bn, bk), dsize, vmem_budget)
    }
    dflt = default_config(m, n, k)
    if fits_vmem(dflt, dsize, vmem_budget):
        configs.add(dflt)
    return tuple(sorted(configs))


# -- the transpose kernel's 2-D (b_rows, b_cols) config space ----------------
#
# The out-of-place transpose (kernels/transpose.py) is bandwidth-bound and
# tiles two axes, so its config space is 2-D.  It is the second stage of
# the TNN/TN candidates and autotunable in its own right
# (core.measure.measure_transpose_configs); ``transpose_config_space``
# mirrors ``Candidate.config_space`` for the matmul kernels.

# Wider edges than the matmul MN space: with no accumulator or second
# operand in VMEM, deep strips are cheap and amortise grid overhead.
TRANSPOSE_TILE_EDGES: Tuple[int, ...] = (128, 256, 512, 1024)


def transpose_vmem_bytes(config: TransposeConfig, dsize: int) -> int:
    """VMEM working set of one transpose grid step: double-buffered input
    block plus the staged (re-oriented) output block."""
    br, bc = config
    return (2 + 2) * br * bc * dsize


def default_transpose_config(rows: int, cols: int) -> TransposeConfig:
    """What ``kernels.transpose`` runs when no block is supplied: the
    DEFAULT_BLOCK-derived tile, clamped per axis."""
    return (
        pick_block(rows, DEFAULT_BLOCK[1]),
        pick_block(cols, DEFAULT_BLOCK[2]),
    )


def enumerate_transpose_configs(
    rows: int,
    cols: int,
    dsize: int = 4,
    vmem_budget: int = DEFAULT_VMEM_BUDGET_BYTES,
    edges: Sequence[int] = TRANSPOSE_TILE_EDGES,
) -> Tuple[TransposeConfig, ...]:
    """Every admissible (b_rows, b_cols) for a (rows, cols) transpose:
    MXU-aligned, clamped to the padded extents, VMEM-budgeted.  The clamped
    default is a member whenever it fits."""
    configs = {
        (br, bc)
        for br in _axis_tiles(rows, edges)
        for bc in _axis_tiles(cols, edges)
        if transpose_vmem_bytes((br, bc), dsize) <= vmem_budget
    }
    dflt = default_transpose_config(rows, cols)
    if transpose_vmem_bytes(dflt, dsize) <= vmem_budget:
        configs.add(dflt)
    return tuple(sorted(configs))


def transpose_config_space(
    rows: int,
    cols: int,
    dsize: int = 4,
    max_configs: int = 4,
    hardware=None,
    vmem_budget: int = DEFAULT_VMEM_BUDGET_BYTES,
) -> Tuple[TransposeConfig, ...]:
    """The transpose autotune sweep list — the 2-D analogue of
    ``shortlist_tile_configs``: the admissible space ranked by the roofline
    transpose model (``simulate.transpose_tile_time``), truncated to
    ``max_configs`` but always keeping the clamped default.
    ``max_configs <= 0`` means no truncation."""
    from repro.core.simulate import transpose_tile_time

    if hardware is None:
        from repro.core.hardware import target_spec

        hardware = target_spec()
    configs = enumerate_transpose_configs(rows, cols, dsize, vmem_budget)
    ranked = sorted(
        configs,
        key=lambda c: transpose_tile_time(hardware, rows, cols, dsize, c),
    )
    if 0 < max_configs < len(ranked):
        keep = ranked[:max_configs]
        dflt = default_transpose_config(rows, cols)
        if dflt not in keep and dflt in configs:
            keep = keep[:-1] + [dflt]
        ranked = keep
    return tuple(ranked)


def shortlist_tile_configs(
    m: int,
    n: int,
    k: int,
    dsize: int = 4,
    max_configs: int = 4,
    hardware=None,
    vmem_budget: int = DEFAULT_VMEM_BUDGET_BYTES,
) -> Tuple[TileConfig, ...]:
    """The autotune sweep list: the full admissible space ranked by the
    roofline tile model, truncated to ``max_configs`` — always including
    the clamped default so a sweep can never regress below the status quo.
    ``max_configs <= 0`` means no truncation."""
    from repro.core.simulate import tile_time

    if hardware is None:
        from repro.core.hardware import target_spec

        hardware = target_spec()
    configs = enumerate_tile_configs(m, n, k, dsize, vmem_budget)
    ranked = sorted(configs, key=lambda c: tile_time(hardware, m, n, k, dsize, c))
    if 0 < max_configs < len(ranked):
        keep = ranked[:max_configs]
        dflt = default_config(m, n, k)
        # keep the (budget-admissible) default so a sweep can never
        # regress below the status quo; an over-budget default stays out
        if dflt not in keep and dflt in configs:
            keep = keep[:-1] + [dflt]
        ranked = keep
    return tuple(ranked)


# -- the fused-attention kernel's 2-D (bq, bk) config space ------------------
#
# The flash-style fused attention kernel (kernels/attention_fused.py) tiles
# the query axis (parallel) and the key/value axis (sequential online-
# softmax sweep); the head dim rides whole in every block.  Its config
# space is therefore 2-D like the transpose kernel's, but its VMEM
# accounting differs: both GEMMs of the subgraph, the f32 accumulator and
# the f32 running max/sum live in one grid step.

# Query blocks stay modest (the accumulator is bq x dh_padded f32); key
# blocks may go deeper — a longer kv strip amortises the online-softmax
# rescale per block.
ATTN_TILE_EDGES: Tuple[int, ...] = (128, 256, 512)


def attn_vmem_bytes(config: AttnConfig, dh: int, dsize: int) -> int:
    """VMEM working set of one fused-attention grid step: double-buffered
    q (bq, dh) / k (bk, dh) / v (bk, dh) operand blocks, the (bq, bk) f32
    logits tile, the f32 output accumulator and running max/sum scratches,
    and the staged output block."""
    bq, bk = config
    dhp = round_up(max(dh, 1), MXU_EDGE)
    operands = 2 * (bq * dhp + 2 * bk * dhp) * dsize  # x2: double buffering
    logits = bq * bk * 4  # f32 scores tile
    accum = bq * dhp * 4 + 2 * bq * MXU_EDGE * 4  # acc + running max/sum
    out_block = bq * dhp * dsize
    return operands + logits + accum + out_block


def default_attn_config(m: int, n: int) -> AttnConfig:
    """What the fused kernel runs when no block is supplied: a square-ish
    (bq, bk) derived from DEFAULT_BLOCK, clamped per axis."""
    return (
        pick_block(m, DEFAULT_BLOCK[0]),
        pick_block(n, DEFAULT_BLOCK[2]),
    )


def enumerate_attn_configs(
    m: int,
    n: int,
    dh: int,
    dsize: int = 4,
    vmem_budget: int = DEFAULT_VMEM_BUDGET_BYTES,
    edges: Sequence[int] = ATTN_TILE_EDGES,
) -> Tuple[AttnConfig, ...]:
    """Every admissible (bq, bk) for a (m queries, n keys, dh head-dim)
    attention subgraph: MXU-aligned, clamped to the padded extents,
    VMEM-budgeted.  The clamped default is a member whenever it fits."""
    configs = {
        (bq, bk)
        for bq in _axis_tiles(m, edges)
        for bk in _axis_tiles(n, edges)
        if attn_vmem_bytes((bq, bk), dh, dsize) <= vmem_budget
    }
    dflt = default_attn_config(m, n)
    if attn_vmem_bytes(dflt, dh, dsize) <= vmem_budget:
        configs.add(dflt)
    return tuple(sorted(configs))


def attn_config_space(
    m: int,
    n: int,
    dh: int,
    dsize: int = 4,
    max_configs: int = 4,
    hardware=None,
    vmem_budget: int = DEFAULT_VMEM_BUDGET_BYTES,
) -> Tuple[AttnConfig, ...]:
    """The fused-attention autotune sweep list: the admissible (bq, bk)
    space ranked by the roofline attention-tile model
    (``simulate.attn_tile_time``), truncated to ``max_configs`` but always
    keeping the clamped default.  ``max_configs <= 0`` means no
    truncation."""
    from repro.core.simulate import attn_tile_time

    if hardware is None:
        from repro.core.hardware import target_spec

        hardware = target_spec()
    configs = enumerate_attn_configs(m, n, dh, dsize, vmem_budget)
    ranked = sorted(
        configs,
        key=lambda c: attn_tile_time(hardware, m, n, dh, dsize, c),
    )
    if 0 < max_configs < len(ranked):
        keep = ranked[:max_configs]
        dflt = default_attn_config(m, n)
        if dflt not in keep and dflt in configs:
            keep = keep[:-1] + [dflt]
        ranked = keep
    return tuple(ranked)
