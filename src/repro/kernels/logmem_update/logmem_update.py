"""Pallas TPU kernel: fused logmem admission scan for M concurrent streams.

The logarithmic-memory engine backend (``repro.streams.logmem``) admits a
doc iff its score beats the stream's acceptance threshold ``tau`` — the
O(log K) analog of the exact reservoir's bar scan. Its hot path touches
every (score, id) pair exactly once: compare against tau, mask out
padding, and reduce the per-tile admit/live counts the threshold-update
epilogue consumes (the live count sets the chunk's target quantile rank
r = round(W·K/t); the admit counts are the write-law evidence the drift
detector tests).

Grid: (M/bm, W/bn), laid out like ``batched_topk`` but ids-aware:
padding is identified by id < 0 (not by a score sentinel), so pad
columns are inert in every output. Program (i, j) reads one (bm, bn)
score tile, the matching id tile and its rows' tau as a (bm, 1) column,
and emits the admit mask plus the tile's per-stream admit count, live
count and live maximum into column j of the row block's (bm, W/bn)
outputs. Embarrassingly parallel across row blocks, bandwidth-bound —
one pass over HBM regardless of M.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import BLOCK_M, ROWS_PARALLEL, put_tile_col


def _kernel(scores_ref, ids_ref, tau_ref, mask_ref, acount_ref,
            lcount_ref, tmax_ref):
    j = pl.program_id(1)
    s = scores_ref[...].astype(jnp.float32)  # (bm, bn)
    live = ids_ref[...] >= 0
    hit = live & (s > tau_ref[...])  # (bm, 1): each stream's threshold
    mask_ref[...] = hit.astype(jnp.int8)
    put_tile_col(acount_ref, j,
                 hit.astype(jnp.int32).sum(axis=1, keepdims=True))
    put_tile_col(lcount_ref, j,
                 live.astype(jnp.int32).sum(axis=1, keepdims=True))
    put_tile_col(tmax_ref, j,
                 jnp.where(live, s, -jnp.inf).max(axis=1, keepdims=True))


def logmem_admit_pallas(scores, ids, tau, *, block_n: int = 512,
                        block_m: int = BLOCK_M, interpret: bool = False):
    """scores (M, N) float, ids (M, N) int32 (< 0 = padding), tau (M,)
    float32; M a multiple of ``block_m``, N of ``block_n``. Returns
    (mask (M, N) int8, admit_counts (M, N/bn) int32, live_counts
    (M, N/bn) int32, tile_max (M, N/bn) f32 — live maximum, -inf on
    all-pad tiles).
    """
    m, n = scores.shape
    assert n % block_n == 0, (n, block_n)
    assert m % block_m == 0, (m, block_m)
    n_tiles = n // block_n
    tile = pl.BlockSpec((block_m, block_n), lambda i, j: (i, j))
    per_tile = pl.BlockSpec((block_m, n_tiles), lambda i, j: (i, 0))
    return pl.pallas_call(
        _kernel,
        grid=(m // block_m, n_tiles),
        in_specs=[tile, tile,
                  pl.BlockSpec((block_m, 1), lambda i, j: (i, 0))],
        out_specs=[tile, per_tile, per_tile, per_tile],
        out_shape=(
            jax.ShapeDtypeStruct((m, n), jnp.int8),
            jax.ShapeDtypeStruct((m, n_tiles), jnp.int32),
            jax.ShapeDtypeStruct((m, n_tiles), jnp.int32),
            jax.ShapeDtypeStruct((m, n_tiles), jnp.float32),
        ),
        compiler_params=ROWS_PARALLEL,
        interpret=interpret,
        name="logmem_update",
    )(scores.astype(jnp.float32), ids.astype(jnp.int32),
      tau.astype(jnp.float32).reshape(m, 1))
