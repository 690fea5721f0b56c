"""Public wrapper for the finalize-time tier assignment: quantize the
float boundary vectors to exact integer thresholds, pad the survivor
axes, run the 2-D kernel (interpret mode on CPU only), strip the padding.

Boundary quantization: survivor ids are integers, so ``id >= b`` for a
float boundary b is exactly ``id >= ceil(b)`` — the comparison the kernel
runs in int32, bit-matching the float64 host meter without float32
precision hazards at large stream positions. +inf boundaries (the
padding convention for mixed-depth fleets) map to INT32_MAX, which no
doc id reaches.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import jaxcompat
from repro.core.topk import quantize_boundaries

from ..common import pad_rows, row_tiling
from . import ref
from .tier_assign import tier_assign_pallas


@partial(jax.jit, static_argnames=("n_tiers", "block_k", "use_pallas"))
def _assign(ids, bounds_int, floor, *, n_tiers, block_k, use_pallas):
    m, k = ids.shape
    bk = min(block_k, max(k, 8))
    pad = (-k) % bk
    idp = jnp.pad(ids.astype(jnp.int32), ((0, 0), (0, pad)),
                  constant_values=-1)
    if use_pallas:
        bm, mp = row_tiling(m)
        tier, counts = tier_assign_pallas(
            pad_rows(idp, mp, -1), pad_rows(bounds_int, mp, 0),
            pad_rows(floor, mp, 0), n_tiers=n_tiers, block_k=bk,
            block_m=bm, interpret=jaxcompat.pallas_interpret())
        tier, counts = tier[:m], counts[:m]
    else:
        tier, counts = ref.tier_assign(idp, bounds_int, floor, n_tiers)
    return tier[:, :k], counts


def tier_assign(ids, bounds, floor=None, *, n_tiers: int | None = None,
                block_k: int = 128, use_pallas: bool = True):
    """ids (M, K) int survivor ids (-1 pad) vs per-stream float boundary
    vectors ``bounds`` (M, B; +inf pads shallower streams) and optional
    cascade floors (M,). Returns (tier (M, K) int32 with -1 at padding,
    counts (M, T) int32 survivors per tier)."""
    ids = jnp.asarray(ids, jnp.int32)
    bq = jnp.asarray(quantize_boundaries(bounds))
    t = n_tiers if n_tiers is not None else bq.shape[1] + 1
    if floor is None:
        floor = jnp.zeros((ids.shape[0],), jnp.int32)
    else:
        floor = jnp.asarray(floor, jnp.int32)
    return _assign(ids, bq, floor, n_tiers=int(t), block_k=block_k,
                   use_pallas=use_pallas)
