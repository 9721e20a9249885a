"""Declarative grid schedules for the Pallas kernel family.

Every Pallas kernel in this package is described by a ``KernelGridSpec``:
the grid extents, which grid axes are sequential ("arbitrary" dimension
semantics), and one ``BlockMap`` per operand/output — the block shape,
the ``BlockSpec`` index map, and the padded extent of the array the map
indexes into.  The kernel's ``pallas_call`` is built *from* the spec
(see ``matmul_nt.py`` etc.), so the spec is the single source of truth
for the kernel's tiling scheme — not a parallel description that can
drift.

That single-sourcing is what makes the index-map/coverage lint pass
(``repro.analysis.coverage``, rules KC310–KC315) a proof rather than a
spot check: it evaluates these index maps symbolically over the full
grid and shows each output block is written exactly once, every operand
access stays inside the padded extents, and the grid matches
``cdiv(padded extent, block edge)`` — for every registered (candidate,
op) pair and every shortlisted tile.

``GRID_SPEC_BUILDERS`` maps each tunable (Pallas-backed) candidate name
to a builder returning the kernel schedule(s) its dispatch executes —
two specs for the two-kernel TNN/TN arms.  Registering a new tunable
candidate without a builder fails the coverage pass (KC315).

The index maps are plain Python callables over plain ints, so the
verifier evaluates them without tracing; the same callables are handed
to ``pl.BlockSpec`` unchanged.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

__all__ = [
    "BlockMap",
    "KernelGridSpec",
    "GRID_SPEC_BUILDERS",
    "candidate_grid_specs",
    "has_grid_spec",
]

IndexMap = Callable[..., Tuple[int, ...]]


@dataclass(frozen=True)
class BlockMap:
    """One operand's (or the output's) blocking: the ``BlockSpec`` block
    shape, its index map, and the padded extent of the backing array."""

    block: Tuple[int, ...]
    index_map: IndexMap
    extent: Tuple[int, ...]


@dataclass(frozen=True)
class KernelGridSpec:
    """One ``pallas_call``'s schedule: grid, operand maps, output map.

    ``sequential`` names the grid axes with "arbitrary" dimension
    semantics (the revisit axes — for the matmul family, the k loop that
    the VMEM accumulator carries partial sums across).  All other axes
    are "parallel": two grid points that differ on a parallel axis may
    execute concurrently, so they must never write the same output
    block.

    ``scalar_prefetch`` counts the leading kernel operands that are not
    blocked at all: small int32 arrays handed whole to SMEM ahead of the
    grid (``pltpu.PrefetchScalarGridSpec``) — the fused attention's
    per-slice ``lengths``.  They have no ``BlockMap``; ``in_specs`` lists
    the blocked operands that follow them, and every index map takes the
    grid indices only (the kernel drops the prefetch refs Pallas appends).
    """

    name: str
    grid: Tuple[int, ...]
    in_specs: Tuple[BlockMap, ...]
    out_spec: BlockMap
    sequential: Tuple[int, ...] = ()
    scalar_prefetch: int = 0

    @property
    def dimension_semantics(self) -> Tuple[str, ...]:
        return tuple(
            "arbitrary" if i in self.sequential else "parallel"
            for i in range(len(self.grid))
        )


# -- candidate name -> grid-spec builder --------------------------------------
#
# A builder has signature (op, m, n, k, g, block) -> Tuple[KernelGridSpec, ...]
# with (m, n, k, g) the *logical* problem extents in output coordinates and
# ``block`` the (bm, bn, bk) tile config (None = kernel default) — exactly the
# arguments Candidate.run forwards to the kernel.


def _nt_specs(op, m, n, k, g, block):
    from .matmul_nt import nt_grid_spec

    return (nt_grid_spec(m, n, k, block),)


def _nn_specs(op, m, n, k, g, block):
    from .matmul_nn import nn_grid_spec

    return (nn_grid_spec(m, n, k, block),)


def _tnn_fused_specs(op, m, n, k, g, block):
    from .matmul_tnn_fused import tnn_fused_grid_spec

    return (tnn_fused_grid_spec(m, n, k, block),)


def _tnn_specs(op, m, n, k, g, block):
    # ops.matmul_tnn: transpose B:(n,k) -> (k,n), then NN — the transpose
    # tile derives from the matmul block exactly as the op wrapper does
    from .matmul_nn import nn_grid_spec
    from .transpose import transpose_grid_spec

    tb = (block[1], block[2]) if block is not None else None
    return (
        transpose_grid_spec(n, k, tb),
        nn_grid_spec(m, n, k, block),
    )


def _tn_specs(op, m, n, k, g, block):
    # ops.matmul_tn: transpose A:(k,m) -> (m,k), then NN
    from .matmul_nn import nn_grid_spec
    from .transpose import transpose_grid_spec

    tb = (block[2], block[0]) if block is not None else None
    return (
        transpose_grid_spec(k, m, tb),
        nn_grid_spec(m, n, k, block),
    )


def _bnt_specs(op, m, n, k, g, block):
    from .matmul_batched import batched_grid_spec

    return (batched_grid_spec(g, m, n, k, nt=True, block=block),)


def _bnn_specs(op, m, n, k, g, block):
    from .matmul_batched import batched_grid_spec

    return (batched_grid_spec(g, m, n, k, nt=False, block=block),)


def _fused_attn_specs(op, m, n, k, g, block):
    # ATTN OpKey extents: m queries, n keys, k head-dim per slice; the
    # fused kernel's 2-D (bq, bk) tile rides in ``block``.
    from .attention_fused import attn_grid_spec

    return (attn_grid_spec(g, m, n, k, block=block),)


GRID_SPEC_BUILDERS: Dict[str, Callable] = {
    "PALLAS_NT": _nt_specs,
    "PALLAS_NN": _nn_specs,
    "PALLAS_TNN": _tnn_specs,
    "PALLAS_TNN_FUSED": _tnn_fused_specs,
    "PALLAS_TN": _tn_specs,
    "PALLAS_BNT": _bnt_specs,
    "PALLAS_BNN": _bnn_specs,
    "FUSED_ATTN": _fused_attn_specs,
}


def has_grid_spec(name: str) -> bool:
    return name in GRID_SPEC_BUILDERS


def candidate_grid_specs(
    name: str,
    op: str,
    m: int,
    n: int,
    k: int,
    g: int = 1,
    block: Optional[Tuple[int, int, int]] = None,
) -> Tuple[KernelGridSpec, ...]:
    """The Pallas schedule(s) candidate ``name`` executes for one
    dispatch of ``op`` at the logical shape — the verifier's input.
    Raises ``KeyError`` for candidates with no registered builder."""
    try:
        builder = GRID_SPEC_BUILDERS[name]
    except KeyError:
        raise KeyError(
            f"candidate {name!r} has no registered grid-spec builder; "
            "Pallas-backed (tunable) candidates must describe their "
            "schedule in kernels/gridspec.py so the coverage pass can "
            "verify it (KC315)"
        ) from None
    return tuple(builder(op, m, n, k, g, block))
