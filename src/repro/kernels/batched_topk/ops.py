"""Public wrapper for the batched threshold filter: pad both axes, run
the 2-D kernel (interpret mode on CPU only), strip the padding.

The composed survivor-extraction + exact per-stream merge lives in
``repro.streams.engine.filtered_update`` (streams layer sits above kernels).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import jaxcompat

from ..common import pad_rows, row_tiling
from . import ref
from .batched_topk import batched_topk_pallas

NEG_BIG = -1e30


@partial(jax.jit, static_argnames=("block_n", "use_pallas"))
def batched_topk_filter(scores, thresholds, *, block_n: int = 512,
                        use_pallas: bool = True):
    """scores (M, N) vs per-stream bars (M,) → (mask int8 (M, N), counts
    (M, N/bn) int32, tile_max (M, N/bn) f32).

    Padding columns are filled with ``NEG_BIG`` (finite): they are stripped
    from ``mask`` but still counted by ``counts`` for streams whose bar is
    below NEG_BIG (i.e. an unfull reservoir, bar = -inf) — same convention
    as the single-stream ``kernels.topk_filter``.
    """
    m, n = scores.shape
    bn = min(block_n, max(n, 128))
    pad = (-n) % bn
    sp = jnp.pad(scores.astype(jnp.float32), ((0, 0), (0, pad)),
                 constant_values=NEG_BIG)
    thr = thresholds.astype(jnp.float32)
    if use_pallas:
        bm, mp = row_tiling(m)
        mask, counts, tmax = batched_topk_pallas(
            pad_rows(sp, mp, NEG_BIG), pad_rows(thr, mp, 0.0), block_n=bn,
            block_m=bm, interpret=jaxcompat.pallas_interpret())
        mask, counts, tmax = mask[:m], counts[:m], tmax[:m]
    else:
        mask, counts, tmax = ref.batched_topk_filter(sp, thr, bn)
    return mask[:, :n], counts, tmax
