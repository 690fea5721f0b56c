"""Pallas TPU kernel: streaming threshold filter for top-K maintenance.

The paper's Fig. 2/3 inner loop ranks every arriving document against the
reservoir. At accelerator scale the hot part is scanning a large score
vector against the current K-th score (the reservoir "bar"): almost all
candidates fail, the rare survivors go through the exact (tiny) merge in
``core.topk``. This kernel is that scan — one pass over HBM, tiled through
VMEM, emitting the survivor mask plus per-tile counts and maxima (the
maxima let the host skip entire tiles on the next refinement pass).

It is the one-stream case of ``repro.kernels.batched_topk``: the vector
is laid out as a (1, N) row, whose one-row blocks equal the array's own
row count (TPU blocks take no rank-1 scalars), and the per-tile counts
and maxima come back lane-dense in one (1, N/bn) row.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..batched_topk.batched_topk import batched_topk_pallas


def topk_filter_pallas(scores, threshold, *, block_n: int = 4096,
                       interpret: bool = False):
    """scores: (N,) float — threshold: () float32.
    Returns (mask (N,) int8, counts (N/bn,) int32, tile_max (N/bn,) f32)."""
    n = scores.shape[0]
    mask, counts, tmax = batched_topk_pallas(
        scores.reshape(1, n), jnp.reshape(threshold, (1,)), block_n=block_n,
        block_m=1, interpret=interpret)
    return mask[0], counts[0], tmax[0]
