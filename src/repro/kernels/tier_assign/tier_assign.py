"""Pallas TPU kernel: finalize-time (M, T) tier assignment of survivor
payload batches.

At window end every stream's K survivors must be read from (or flushed
to) their tiers: doc id i belongs to tier t iff b_t <= i < b_{t+1} under
the stream's boundary vector, lifted to the cascade floor for migrated
streams. The host-side meter does this per stream in numpy; at fleet
scale (M × K survivor payloads) it is one embarrassingly-parallel pass
the finalize path runs on device.

Grid: (M/bm, K/bk) — program (i, j) reads a (bm, bk) id tile, its rows'
integer boundary vectors (precomputed as ``ceil(b)`` so the comparison
is exact in int32 — see ``ops``) and cascade floors as a (bm, B) block
and a (bm, 1) column; it emits the per-survivor tier and accumulates the
rows' per-tier survivor counts into a (bm, T) block that stays resident
while j sweeps the row (the bucketed-gather offsets for issuing per-tier
reads). Padding ids (-1) assign tier -1 and count nowhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import BLOCK_M, ROWS_PARALLEL


def _kernel(ids_ref, bounds_ref, floor_ref, tier_ref, counts_ref, *,
            n_tiers: int):
    j = pl.program_id(1)
    ids = ids_ref[...]  # (bm, bk) int32
    valid = ids >= 0
    tier = jnp.zeros_like(ids)
    for b in range(bounds_ref.shape[1]):
        tier = tier + (ids >= bounds_ref[:, b:b + 1]).astype(jnp.int32)
    tier = jnp.maximum(tier, floor_ref[...])
    tier = jnp.minimum(tier, n_tiers - 1)
    tier = jnp.where(valid, tier, -1)
    tier_ref[...] = tier

    @pl.when(j == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    lane = jax.lax.broadcasted_iota(jnp.int32, counts_ref.shape, 1)
    add = jnp.zeros(counts_ref.shape, jnp.int32)
    for t in range(n_tiers):
        cnt = (tier == t).astype(jnp.int32).sum(axis=1, keepdims=True)
        add = jnp.where(lane == t, cnt, add)
    counts_ref[...] += add


def tier_assign_pallas(ids, bounds_int, floor, *, n_tiers: int,
                       block_k: int = 128, block_m: int = BLOCK_M,
                       interpret: bool = False):
    """ids: (M, K) int32 survivor ids (-1 pad); bounds_int: (M, B) int32
    integer boundaries (ceil of the float vector, INT32_MAX pad);
    floor: (M,) int32 cascade floors. M must be a multiple of
    ``block_m`` and K of ``block_k``. Returns (tier (M, K) int32,
    counts (M, n_tiers) int32)."""
    m, k = ids.shape
    assert k % block_k == 0, (k, block_k)
    assert m % block_m == 0, (m, block_m)
    n_b = bounds_int.shape[1]
    return pl.pallas_call(
        functools.partial(_kernel, n_tiers=n_tiers),
        grid=(m // block_m, k // block_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j: (i, j)),
            pl.BlockSpec((block_m, n_b), lambda i, j: (i, 0)),
            pl.BlockSpec((block_m, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j: (i, j)),
            pl.BlockSpec((block_m, n_tiers), lambda i, j: (i, 0)),
        ],
        out_shape=(
            jax.ShapeDtypeStruct((m, k), jnp.int32),
            jax.ShapeDtypeStruct((m, n_tiers), jnp.int32),
        ),
        compiler_params=ROWS_PARALLEL,
        interpret=interpret,
        name="tier_assign",
    )(ids, bounds_int, floor.reshape(m, 1))
