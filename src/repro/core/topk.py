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


@jax.named_scope("member")
def member(needles: jax.Array, haystack: jax.Array) -> jax.Array:
    """Boolean membership mask (``needles[i] in haystack``) via
    sort + binary search — O((H+N)·log H) instead of ``jnp.isin``'s
    O(N·H) broadcast compare, which dominates the exact path at huge K
    (a K=65536 eviction scan is 4G compares per stream)."""
    hs = jnp.sort(haystack)
    pos = jnp.clip(jnp.searchsorted(hs, needles), 0, hs.shape[0] - 1)
    return hs[pos] == needles


def _merge_sorted(scores: jax.Array, ids: jax.Array, k: int):
    """Top-k of (scores, ids) with lower-id tie-break; returns sorted desc."""
    # lexsort: primary = -score, secondary = id  → stable deterministic order.
    order = jnp.lexsort((ids, -scores))
    top = order[:k]
    return scores[top], ids[top]


def update(state: ReservoirState, batch_scores: jax.Array,
           batch_ids: jax.Array) -> Tuple[ReservoirState, jax.Array]:
    """Merge a batch into the reservoir.

    Returns (new_state, wrote_mask) where ``wrote_mask[j]`` is True iff batch
    element j entered the reservoir (⇒ one storage write, paper eq. 9/10).
    Batch elements whose id is already resident are dropped — a re-observed
    document neither duplicates its slot nor triggers a storage write.
    Within-batch ids are assumed unique (they are stream indices).
    """
    k = state.scores.shape[0]
    batch_scores = batch_scores.astype(jnp.float32).reshape(-1)
    batch_ids = batch_ids.astype(jnp.int32).reshape(-1)
    resident = member(batch_ids, state.ids)
    cand_scores = jnp.where(resident, -jnp.inf, batch_scores)
    cand_ids = jnp.where(resident, -1, batch_ids)
    all_scores = jnp.concatenate([state.scores, cand_scores])
    all_ids = jnp.concatenate([state.ids, cand_ids])
    order = jnp.lexsort((all_ids, -all_scores))
    top = order[:k]
    # positional membership, not id membership: an id collision with a
    # resident entry must not report a write for the colliding batch element.
    selected = jnp.zeros(all_ids.shape, dtype=bool).at[top].set(True)
    wrote = selected[k:] & (cand_ids >= 0)
    new_state = ReservoirState(
        scores=all_scores[top], ids=all_ids[top],
        seen=state.seen + batch_ids.shape[0],
    )
    return new_state, wrote


def evicted(old: ReservoirState, new: ReservoirState) -> jax.Array:
    """Mask over ``old.ids`` of entries no longer present in ``new`` —
    the documents whose storage can be freed (overwritten, paper §VI)."""
    return (old.ids >= 0) & ~member(old.ids, new.ids)


def merge(a: ReservoirState, b: ReservoirState) -> ReservoirState:
    """Merge two sub-stream reservoirs (cross-shard reduction). Associative
    and commutative up to the deterministic tie-break, so it can be used in
    ``jax.lax`` reductions / psum-style tree merges."""
    k = a.scores.shape[0]
    scores = jnp.concatenate([a.scores, b.scores])
    ids = jnp.concatenate([a.ids, b.ids])
    s, i = _merge_sorted(scores, ids, k)
    return ReservoirState(scores=s, ids=i, seen=a.seen + b.seen)


def threshold(state: ReservoirState) -> jax.Array:
    """Current K-th score (entry bar). -inf while the reservoir is unfull."""
    return state.scores[-1]


def tier_of(ids: jax.Array, r: float | jax.Array) -> jax.Array:
    """Algorithm C placement: tier 0 (A) for stream index < r, else 1 (B)."""
    return (ids >= jnp.asarray(r)).astype(jnp.int32)
