"""Public wrapper for the fused logmem admission scan: pad the trailing
axes, run the 2-D kernel (interpret mode on CPU only), strip the padding.

The composed threshold-update epilogue (chunk order statistic, decayed
fold, phase commit) lives in ``repro.streams.logmem.update`` — the
streams layer sits above kernels.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import jaxcompat

from ..common import pad_rows, row_tiling
from . import ref
from .logmem_update import logmem_admit_pallas

NEG_BIG = -1e30
PAD_ID = -1


@partial(jax.jit, static_argnames=("block_n", "use_pallas"))
def logmem_admit(scores, ids, tau, *, block_n: int = 512,
                 use_pallas: bool = True):
    """scores (M, N) / ids (M, N) int (< 0 = padding) vs per-stream
    acceptance thresholds tau (M,) → (mask int8 (M, N), admit_counts
    (M, N/bn) int32, live_counts (M, N/bn) int32, tile_max (M, N/bn)
    f32).

    Padding columns (appended here with id = -1) are inert in every
    output: the kernel gates on ids, not on a score sentinel, so even a
    -inf threshold admits no pad — unlike ``batched_topk``, whose
    unfull-reservoir convention counts finite pad sentinels.
    """
    m, n = scores.shape
    bn = min(block_n, max(n, 128))
    pad = (-n) % bn
    sp = jnp.pad(scores.astype(jnp.float32), ((0, 0), (0, pad)),
                 constant_values=NEG_BIG)
    ip = jnp.pad(ids.astype(jnp.int32), ((0, 0), (0, pad)),
                 constant_values=PAD_ID)
    thr = tau.astype(jnp.float32)
    if use_pallas:
        bm, mp = row_tiling(m)
        mask, acounts, lcounts, tmax = logmem_admit_pallas(
            pad_rows(sp, mp, NEG_BIG), pad_rows(ip, mp, PAD_ID),
            pad_rows(thr, mp, 0.0), block_n=bn, block_m=bm,
            interpret=jaxcompat.pallas_interpret())
        mask, acounts, lcounts, tmax = (mask[:m], acounts[:m], lcounts[:m],
                                        tmax[:m])
    else:
        mask, acounts, lcounts, tmax = ref.logmem_admit(sp, ip, thr, bn)
    return mask[:, :n], acounts, lcounts, tmax
