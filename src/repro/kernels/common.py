"""Shared helpers for the Pallas kernels in this package.

Target hardware is TPU (MXU 128x128, VMEM-staged blocks).  On this CPU
container every kernel runs under ``interpret=True``; on a TPU backend the
same ``pallas_call`` lowers through Mosaic.  ``ops.py`` picks the mode.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "cdiv",
    "round_up",
    "pick_block",
    "normalize_block",
    "pad2",
    "should_interpret",
    "DEFAULT_BLOCK",
    "MXU_EDGE",
    "CompilerParams",
]

CompilerParams = pltpu.CompilerParams

MXU_EDGE = 128
# Default VMEM tile for the matmul family: (bm, bn, bk).  At bf16 this is
# 512KiB per operand block + a 1MiB f32 accumulator — comfortably inside a
# v5e core's VMEM with double buffering.
DEFAULT_BLOCK: Tuple[int, int, int] = (512, 512, 512)


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(x: int, mult: int) -> int:
    return cdiv(x, mult) * mult


def pick_block(dim: int, default: int, align: int = MXU_EDGE) -> int:
    """Largest useful block: the default, shrunk for small dims but kept
    hardware-aligned so the MXU tiles stay full.

    Two invariants, both load-bearing for VMEM accounting:
      * the result is a positive multiple of ``align`` even when the caller
        hands an unaligned default (e.g. ``block=(100, ...)``), and
      * the result never exceeds the padded extent ``round_up(dim, align)``,
        so a sub-128 dim gets exactly one ``align``-wide tile instead of a
        tile that is mostly padding (``pick_block(1, 512) == 128``).
    """
    padded = round_up(max(dim, 1), align)
    return min(round_up(max(default, 1), align), padded)


def normalize_block(
    dims: Tuple[int, ...], block: Optional[Tuple[int, ...]], default: Tuple[int, ...]
) -> Tuple[int, ...]:
    """Validate + clamp a caller-supplied tile config, uniformly for every
    kernel in this package.

    ``dims`` are the logical problem extents (one per tiled axis), ``block``
    the requested tile (or None for ``default``).  Each axis goes through
    ``pick_block``, so the returned tile is MXU-aligned and never exceeds
    the padded extent of its axis.  Malformed configs (wrong arity,
    non-positive or non-integer entries) raise ``ValueError`` with the
    offending value — kernels must not silently mis-tile.
    """
    if block is None:
        block = default
    block = tuple(block)
    if len(block) != len(dims):
        raise ValueError(
            f"tile config {block} has {len(block)} entries; "
            f"this kernel tiles {len(dims)} axes"
        )
    for b in block:
        if not isinstance(b, (int,)) or isinstance(b, bool) or b <= 0:
            raise ValueError(f"tile config {block} must be positive ints")
    return tuple(pick_block(d, b) for d, b in zip(dims, block))


def pad2(x: jax.Array, rows: int, cols: int) -> jax.Array:
    """Zero-pad a 2-D array up to (rows, cols).  Zeros are correctness-safe
    for both transpose and matmul accumulation."""
    r, c = x.shape
    if r == rows and c == cols:
        return x
    return jnp.pad(x, ((0, rows - r), (0, cols - c)))


def should_interpret() -> bool:
    """Interpret Pallas on non-TPU backends (this container is CPU-only).

    Override with REPRO_PALLAS_INTERPRET=0/1.
    """
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None:
        return env not in ("0", "false", "False")
    return jax.default_backend() != "tpu"
