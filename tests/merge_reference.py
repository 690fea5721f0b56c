"""The positional forms of the exact reservoir merge, the plain reference
for ``core.topk.update`` and ``streams.engine.filtered_update``.

These are the merge as it was written before it sorted scores and ids as
its own keys: ``lexsort`` for the order, gathers of the rows through it,
a scatter of the selected positions for the write mask, and for the
filtered form ``lax.top_k`` with a ``take_along_axis`` of the ids and a
scatter of the survivors' mask back to batch positions. The filtered form
cuts ties by batch position and admits only scores strictly above the
bar, so it equals ``update`` only when each stream's ids arrive in
increasing order.

Also the id-search form of eviction (``evicted``, ``evicted_ids``), the
plain reference for ``core.topk.dropped`` and
``streams.engine.dropped_ids``, which read evictions off the merge's
order."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import topk
from repro.kernels.batched_topk import ops as btk_ops
from repro.streams import engine


def topk_update(state, batch_scores, batch_ids, check_resident=True):
    """One stream: (new ``ReservoirState``, write mask) by position."""
    k = state.scores.shape[0]
    batch_scores = batch_scores.astype(jnp.float32).reshape(-1)
    batch_ids = batch_ids.astype(jnp.int32).reshape(-1)
    cand_scores, cand_ids = batch_scores, batch_ids
    if check_resident:
        resident = topk.member(batch_ids, state.ids)
        cand_scores = jnp.where(resident, -jnp.inf, batch_scores)
        cand_ids = jnp.where(resident, -1, batch_ids)
    all_scores = jnp.concatenate([state.scores, cand_scores])
    all_ids = jnp.concatenate([state.ids, cand_ids])
    top = jnp.lexsort((all_ids, -all_scores))[:k]
    selected = jnp.zeros(all_ids.shape, dtype=bool).at[top].set(True)
    wrote = selected[k:] & (cand_ids >= 0)
    return topk.ReservoirState(all_scores[top], all_ids[top],
                               state.seen + batch_ids.shape[0]), wrote


def _seen(state, batch_ids):
    return state.seen + (batch_ids >= 0).sum(axis=1).astype(state.seen.dtype)


def engine_update(state, batch_scores, batch_ids):
    """The fleet: ``engine.update`` over ``topk_update``."""
    new, wrote = jax.vmap(topk_update)(engine._as_single(state),
                                       batch_scores, batch_ids)
    return engine.BatchedReservoirState(
        new.scores, new.ids, _seen(state, batch_ids)), wrote


def filtered_update(state, batch_scores, batch_ids, use_pallas=False):
    """The fleet's filter, ``top_k`` cut and positional merge."""
    k, w = state.scores.shape[1], batch_scores.shape[1]
    batch_ids = batch_ids.astype(jnp.int32)
    mask, _, _ = btk_ops.batched_topk_filter(
        batch_scores, state.scores[:, -1], block_n=128,
        use_pallas=use_pallas)
    resident = jax.vmap(topk.member)(batch_ids, state.ids)
    surv = jnp.where((mask > 0) & ~resident,
                     batch_scores.astype(jnp.float32), -jnp.inf)
    top_scores, top_idx = jax.lax.top_k(surv, min(k, w))
    top_ids = jnp.take_along_axis(batch_ids, top_idx, axis=1)
    top_ids = jnp.where(jnp.isfinite(top_scores), top_ids, engine.PAD_ID)
    new, wrote_top = jax.vmap(
        functools.partial(topk_update, check_resident=False))(
            engine._as_single(state), top_scores, top_ids)
    rows = jnp.arange(batch_scores.shape[0])[:, None]
    wrote = jnp.zeros(batch_scores.shape, bool).at[rows, top_idx].set(
        wrote_top)
    return engine.BatchedReservoirState(
        new.scores, new.ids, _seen(state, batch_ids)), \
        wrote & (batch_ids >= 0)


def evicted(old, new):
    """One stream: mask over ``old.ids`` of entries no longer present in
    ``new``, by searching ``new.ids`` for every old id (``member``), for
    any pair of states."""
    return (old.ids >= 0) & ~topk.member(old.ids, new.ids)


def evicted_ids(old, new):
    """The fleet: (M, K) local doc ids of ``old`` absent from ``new``
    (-1 = none), by ``evicted``."""
    ev = jax.vmap(evicted)(engine._as_single(old), engine._as_single(new))
    return jnp.where(ev, old.ids, engine.PAD_ID)
