"""Pallas TPU kernel: 2-D batched threshold filter for M concurrent streams.

The multi-tenant engine (``repro.streams.engine``) maintains one reservoir
per stream. Its hot path is the same scan as ``kernels.topk_filter`` — rank
every arriving candidate against the reservoir "bar" (current K-th score) —
but over a whole fleet at once: scores (M, N) against per-stream bars (M,).
Almost all candidates fail everywhere; the rare survivors go through the
exact per-stream merge.

Grid: (M/bm, N/bn) — program (i, j) reads a (bm, bn) score tile and its
rows' bars as a (bm, 1) column, and emits the survivor mask plus the
tile's per-stream count and maximum into column j of the row block's
(bm, N/bn) outputs, which stay resident while j sweeps the row. So the
host-side exact merge only touches tiles that actually contain
survivors. Embarrassingly parallel across row blocks, bandwidth-bound —
one pass over HBM regardless of M.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import BLOCK_M, ROWS_PARALLEL, put_tile_col


def _kernel(scores_ref, thr_ref, mask_ref, count_ref, tmax_ref):
    j = pl.program_id(1)
    s = scores_ref[...].astype(jnp.float32)  # (bm, bn)
    hit = s > thr_ref[...]  # (bm, 1): each stream's reservoir bar
    mask_ref[...] = hit.astype(jnp.int8)
    put_tile_col(count_ref, j,
                 hit.astype(jnp.int32).sum(axis=1, keepdims=True))
    put_tile_col(tmax_ref, j, s.max(axis=1, keepdims=True))


def batched_topk_pallas(scores, thresholds, *, block_n: int = 512,
                        block_m: int = BLOCK_M, interpret: bool = False):
    """scores: (M, N) float — thresholds: (M,) float32, one bar per stream.
    M must be a multiple of ``block_m`` and N of ``block_n``.
    Returns (mask (M, N) int8, counts (M, N/bn) int32, tile_max (M, N/bn) f32).
    """
    m, n = scores.shape
    assert n % block_n == 0, (n, block_n)
    assert m % block_m == 0, (m, block_m)
    n_tiles = n // block_n
    thr = thresholds.astype(jnp.float32).reshape(m, 1)
    return pl.pallas_call(
        _kernel,
        grid=(m // block_m, n_tiles),
        in_specs=[
            pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
            pl.BlockSpec((block_m, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
            pl.BlockSpec((block_m, n_tiles), lambda i, j: (i, 0)),
            pl.BlockSpec((block_m, n_tiles), lambda i, j: (i, 0)),
        ],
        out_shape=(
            jax.ShapeDtypeStruct((m, n), jnp.int8),
            jax.ShapeDtypeStruct((m, n_tiles), jnp.int32),
            jax.ShapeDtypeStruct((m, n_tiles), jnp.float32),
        ),
        compiler_params=ROWS_PARALLEL,
        interpret=interpret,
        name="batched_topk",
    )(scores, thr)
