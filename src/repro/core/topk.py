"""Streaming top-K reservoir — jit-compatible, batched, shard-mergeable.

The paper's per-document ``H.insert / indexof`` loop (Fig. 2/3), vectorized
for accelerators: each update merges a batch of scored documents into the
reservoir with one sort. Deterministic tie-break: lower stream index wins.

State is a pytree, so it can live donated inside a jitted train step and be
sharded/merged across data-parallel sub-streams (``merge``).

Multi-tenant variant: ``repro.streams.engine`` stacks M of these states on
a leading stream axis and advances them in one jitted step; the kernel
fast path for the scan is ``repro.kernels.topk_filter`` (one stream) /
``repro.kernels.batched_topk`` (the fleet).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class ReservoirState(NamedTuple):
    scores: jax.Array  # (K,) float32, sorted descending, -inf padded
    ids: jax.Array  # (K,) int32 global stream index, -1 padded
    seen: jax.Array  # () int32 — total documents observed


def init(k: int) -> ReservoirState:
    return ReservoirState(
        scores=jnp.full((k,), -jnp.inf, dtype=jnp.float32),
        ids=jnp.full((k,), -1, dtype=jnp.int32),
        seen=jnp.zeros((), dtype=jnp.int32),
    )


# The membership methods' costs on a TPU v5e (chip timings, vmapped rows
# of 64 x 1,024 to 65,536 x 65,536): the fused compare tests about 1e12
# needle-haystack pairs a second, the merged sort about 1.3e8 values a
# second, so the compare is the faster while a row has no more than about
# 8,192 pairs for each value the sort would take.
COMPARE_PAIRS_PER_VALUE = 8192


def member_method(n_needles: int, n_haystack: int) -> str:
    """The method ``member`` uses for these static lengths: ``"compare"``
    while ``n_needles * n_haystack`` is at most ``COMPARE_PAIRS_PER_VALUE``
    times ``n_needles + n_haystack``, else ``"sort"``."""
    if (n_needles * n_haystack
            <= COMPARE_PAIRS_PER_VALUE * (n_needles + n_haystack)):
        return "compare"
    return "sort"


@jax.named_scope("member")
def member(needles: jax.Array, haystack: jax.Array) -> jax.Array:
    """Boolean membership mask (``needles[i] in haystack``), by one of two
    methods that the static lengths pick (``member_method``):

    - ``"compare"``: every needle against every haystack entry, reduced
      with ``any``. XLA fuses the compare into the reduction, so the
      (N, H) mask is never stored; no sort, no gather. At the fleet's
      K = W = 1,024 it is one pass of ~1M pairs a stream.
    - ``"sort"``: one sort of haystack and needles together, the haystack
      first among equal values, then a needle is a member iff a haystack
      entry precedes it in its run of equal values (cumulative counts, no
      search). O((H+N)·log(H+N)), for rows where the O(N·H) compare would
      dominate (a K = 65,536 scan of as many needles is 4G pairs a
      stream). ``searchsorted``'s default binary search is a loop of
      gathers, slow on a TPU.
    """
    n, h = needles.shape[0], haystack.shape[0]
    if member_method(n, h) == "compare":
        return (needles[:, None] == haystack[None, :]).any(axis=1)
    idx = jnp.arange(n + h, dtype=jnp.int32)
    tag = (idx >= h).astype(jnp.int32)  # 0 = haystack, 1 = needle
    slot = jnp.where(idx >= h, idx - h, n)  # needle index; n = none
    vals, tag, slot = jax.lax.sort(
        (jnp.concatenate([haystack, needles]), tag, slot), num_keys=2)
    hay = 1 - tag
    hay_upto = jnp.cumsum(hay)
    run_start = jnp.concatenate(
        [jnp.ones((1,), bool), vals[1:] != vals[:-1]])
    # haystack entries before the current run of equal values
    hay_before = jax.lax.cummax(jnp.where(run_start, hay_upto - hay, 0))
    hit = hay_upto > hay_before
    return jnp.zeros((n,), bool).at[slot].set(hit, mode="drop")


def ranks_at_or_above(scores: jax.Array, ids: jax.Array,
                      bar_scores: jax.Array, bar_ids: jax.Array) -> jax.Array:
    """Whether each (score, id) entry ranks at or above the entry
    (``bar_scores``, ``bar_ids``) in the merge's order: score descending,
    then id ascending. Broadcasts, so a reservoir's K-th entry tests a
    whole row."""
    return (scores > bar_scores) | ((scores == bar_scores) & (ids <= bar_ids))


def first_k(scores: jax.Array, ids: jax.Array, k: int):
    """The k first of (scores, ids) in the merge's order (score
    descending, then id ascending), sorted so. One sort takes both as its
    keys and carries them, so nothing is read back through an index (a
    gather on a TPU). Its comparator holds -0.0 and +0.0 equal, as
    ``ranks_at_or_above``'s compares do."""
    neg, ids = jax.lax.sort((-scores, ids), num_keys=2)
    return -neg[:k], ids[:k]


def update(state: ReservoirState, batch_scores: jax.Array,
           batch_ids: jax.Array, *, check_resident: bool = True
           ) -> Tuple[ReservoirState, jax.Array]:
    """Merge a batch into the reservoir.

    Returns (new_state, wrote_mask) where ``wrote_mask[j]`` is True iff batch
    element j entered the reservoir (⇒ one storage write, paper eq. 9/10).
    Batch elements whose id is already resident are dropped — a re-observed
    document neither duplicates its slot nor triggers a storage write.
    Within-batch ids are assumed unique (they are stream indices).
    ``check_resident=False`` skips that ``member`` search, for a caller
    whose batch is already cleared of resident ids (pads as (-inf, -1)).

    The entries it evicted are ``dropped(state, new_state)``, read off the
    merge's order without another search.
    """
    k = state.scores.shape[0]
    batch_scores = batch_scores.astype(jnp.float32).reshape(-1)
    batch_ids = batch_ids.astype(jnp.int32).reshape(-1)
    cand_scores, cand_ids = batch_scores, batch_ids
    if check_resident:
        resident = member(batch_ids, state.ids)
        cand_scores = jnp.where(resident, -jnp.inf, batch_scores)
        cand_ids = jnp.where(resident, -1, batch_ids)
    all_scores = jnp.concatenate([state.scores, cand_scores])
    all_ids = jnp.concatenate([state.ids, cand_ids])
    scores, ids = first_k(all_scores, all_ids, k)
    # written iff it ranks at or above the new K-th entry: valid ids are
    # unique among the merged entries (a re-observed resident enters as a
    # (-inf, -1) pad and never writes), so rank and position agree
    wrote = (cand_ids >= 0) & ranks_at_or_above(cand_scores, cand_ids,
                                                scores[-1:], ids[-1:])
    new_state = ReservoirState(scores=scores, ids=ids,
                               seen=state.seen + batch_ids.shape[0])
    return new_state, wrote


def dropped(old: ReservoirState, new: ReservoirState) -> jax.Array:
    """Mask over ``old.ids`` of entries evicted by the ``update`` that
    made ``new`` from ``old`` — the documents whose storage can be freed
    (overwritten, paper §VI) — read off the merge's order without an id
    search: the merge keeps the K first entries in its order (score
    descending, then id ascending), so an old entry survives iff it
    ranks at or above ``new``'s K-th entry. That is exactly the set of
    old ids absent from ``new``, because valid ids are unique among the
    merged entries (resident re-observations enter as (-inf, -1) pads)
    and scores are never NaN. Works on stacked states too (the last axis
    is the reservoir)."""
    kept = ranks_at_or_above(old.scores, old.ids, new.scores[..., -1:],
                             new.ids[..., -1:])
    return (old.ids >= 0) & ~kept


def merge(a: ReservoirState, b: ReservoirState) -> ReservoirState:
    """Merge two sub-stream reservoirs (cross-shard reduction). Associative
    and commutative up to the deterministic tie-break, so it can be used in
    ``jax.lax`` reductions / psum-style tree merges."""
    k = a.scores.shape[0]
    scores = jnp.concatenate([a.scores, b.scores])
    ids = jnp.concatenate([a.ids, b.ids])
    s, i = first_k(scores, ids, k)
    return ReservoirState(scores=s, ids=i, seen=a.seen + b.seen)


def threshold(state: ReservoirState) -> jax.Array:
    """Current K-th score (entry bar). -inf while the reservoir is unfull."""
    return state.scores[-1]


def tier_of(ids: jax.Array, r: float | jax.Array) -> jax.Array:
    """Algorithm C placement: tier 0 (A) for stream index < r, else 1 (B)."""
    return (ids >= jnp.asarray(r)).astype(jnp.int32)


INT32_MAX = np.iinfo(np.int32).max


def quantize_boundaries(bounds) -> np.ndarray:
    """(..., B) float boundary vectors -> exact int32 thresholds. Doc ids
    are integer positions, so ``id >= b`` is exactly ``id >= ceil(b)``;
    +inf boundaries (the padding of shallower streams) map to INT32_MAX,
    which no position reaches."""
    b = np.asarray(bounds, np.float64)
    return np.where(np.isfinite(b), np.clip(np.ceil(b), 0, INT32_MAX),
                    INT32_MAX).astype(np.int32)


def tiers(ids: jax.Array, bounds: jax.Array) -> jax.Array:
    """(M, N) static tier of each position: the number of the row's
    ``quantize_boundaries`` thresholds (M, B) at or below it. An int32
    compare, exact at every position (a float32 one is not past 2^24)."""
    return (ids[:, :, None] >= bounds[:, None, :]).sum(-1, dtype=jnp.int32)


def tier_counts(tier: jax.Array, mask: jax.Array, n_tiers: int) -> jax.Array:
    """(M, T) int32 count of the masked entries of each row per tier
    (T static: T masked row sums, fused)."""
    return jnp.stack([jnp.sum(mask & (tier == t), axis=1, dtype=jnp.int32)
                      for t in range(n_tiers)], axis=1)
