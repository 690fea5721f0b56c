"""Tiling shared by the fleet kernels.

TPU blocks must have their last two dimensions divisible by (8, 128) or
equal to the array's own. So the fleet kernels block rows in multiples
of ``ROW_ALIGN`` streams, bring per-stream scalars in as ``(bm, 1)``
columns, and carry per-tile outputs in a ``(bm, n_tiles)`` block that
stays resident while the tile axis sweeps it (``put_tile_col``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

ROW_ALIGN = 8
BLOCK_M = 128  # streams per program at fleet scale

# grid (row blocks, tiles): row blocks are independent, tiles revisit
# their row block's per-tile outputs and so run in order
ROWS_PARALLEL = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def row_tiling(m: int) -> tuple[int, int]:
    """(rows per program, padded row count) for ``m`` rows: ``BLOCK_M``
    rows per program, or ``m`` rounded up to the row alignment when the
    fleet is smaller than one block."""
    bm = min(BLOCK_M, -(-m // ROW_ALIGN) * ROW_ALIGN)
    return bm, -(-m // bm) * bm


def pad_rows(x, rows: int, value):
    """Pad the leading axis of ``x`` to ``rows`` with ``value``."""
    pad = rows - x.shape[0]
    if not pad:
        return x
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1),
                   constant_values=value)


def put_tile_col(ref, j, col) -> None:
    """Write this tile's ``(bm, 1)`` column into column ``j`` of the
    resident ``(bm, n_tiles)`` output block."""
    lane = jax.lax.broadcasted_iota(jnp.int32, ref.shape, 1)
    ref[...] = jnp.where(lane == j, col, ref[...])
