"""Public wrapper: pad, run kernel (interpret mode on CPU only), and the
composed ``filter_then_merge`` used by the streaming reservoir at batch
scale."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import jaxcompat

from . import ref
from .topk_filter import topk_filter_pallas

NEG_BIG = -1e30


@partial(jax.jit, static_argnames=("block_n", "use_pallas"))
def topk_filter(scores, threshold, *, block_n: int = 4096,
                use_pallas: bool = True):
    """scores (N,) vs scalar threshold → (mask int8 (N,), counts, tile_max).

    NaN scores are demoted to the pad value before the kernel runs: a
    NaN fails every compare (it could never pass the bar anyway) but
    would otherwise poison ``tile_max`` with NaN — callers that want NaN
    *accounted for* rather than dropped quarantine upstream
    (``streams.engine`` counts them as ``scores_quarantined``)."""
    n = scores.shape[0]
    bn = min(block_n, max(n, 128))
    pad = (-n) % bn
    sp = jnp.pad(scores.astype(jnp.float32), ((0, pad),),
                 constant_values=NEG_BIG)
    sp = jnp.where(jnp.isnan(sp), NEG_BIG, sp)
    if use_pallas:
        mask, counts, tmax = topk_filter_pallas(
            sp, jnp.asarray(threshold), block_n=bn,
            interpret=jaxcompat.pallas_interpret())
    else:
        mask, counts, tmax = ref.topk_filter(sp, jnp.asarray(threshold), bn)
    return mask[:n], counts, tmax


def filter_then_merge(state, scores, ids, *, block_n: int = 4096):
    """Batched reservoir update for large score batches: kernel-filter the
    stream against the current bar, then exact-merge only survivors.

    Equivalent to ``core.topk.update`` (tests assert equality) but touches
    each candidate once in VMEM instead of sorting the whole batch.
    """
    from repro.core import topk as topk_mod
    k = state.scores.shape[0]
    thr = state.scores[-1]  # -inf while unfull ⇒ filter passes everything
    mask, counts, _ = topk_filter(scores, thr, block_n=block_n)
    # survivors: at most... all of them in the worst case; bound by k
    # candidates that could enter = top-(k) of the batch above the bar.
    surv_scores = jnp.where(mask > 0, scores, -jnp.inf)
    top_scores, top_idx = jax.lax.top_k(surv_scores, min(k, scores.shape[0]))
    top_ids = jnp.where(jnp.isfinite(top_scores), ids[top_idx], -1)
    return topk_mod.update(state, top_scores,
                           jnp.where(top_ids >= 0, top_ids, -(2**31) + 1))
