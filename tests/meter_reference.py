"""The plain reference of the fleet meter's per-chunk accounting, and a
shadow that replays it beside a live engine.

``record_update`` is the scatter form the engine used before the step
metered on the device: per-document tier attribution in float64 and
``np.add.at`` scatters over a chunk's (M, W) write mask, (M, K) evicted
ids and (M, K) post-step reservoir ids. ``Shadow`` feeds it every chunk a
``StreamEngine`` meters — the write mask, evictions and reservoir ids
recomputed from the engine's states before and after the step — and
mirrors every boundary swap and final read, so that after any run the
engine's ``FleetMeter`` can be compared array for array with the
reference's."""
from __future__ import annotations

import copy

import jax
import jax.numpy as jnp
import numpy as np

from repro.streams import logmem

STATE_ARRAYS = ("observed", "writes", "deletes", "occupancy",
                "occupancy_hwm", "doc_steps", "migrations", "mig_reads",
                "mig_writes", "floor", "reads", "relocations", "reloc_reads",
                "reloc_writes", "boundaries")


def record_update(meter, stream_rows, doc_ids, wrote, evicted_ids=None,
                  state_ids=None) -> None:
    """Account one engine step for a bucket, by scatters.

    stream_rows (Mb,): global stream indices of the bucket's rows.
    doc_ids (Mb, W) int: per-stream local doc indices, -1 = padding
    (quarantined docs included).
    wrote (Mb, W) bool: reservoir-entry mask.
    evicted_ids (Mb, K) int, optional: local doc indices evicted by this
    step (-1 = none), for per-tier delete accounting.
    state_ids (Mb, K) int, optional: post-step reservoir ids — needed to
    count the docs that cascade when a migrating stream crosses a
    boundary.
    """
    stream_rows = np.asarray(stream_rows, np.int64)
    doc_ids = np.asarray(doc_ids)
    wrote = np.asarray(wrote, bool)
    np.add.at(meter.observed, stream_rows, (doc_ids >= 0).sum(1))
    # writes: doc index == arrival position, so the static tier is the
    # write destination with or without a later cascade
    write_tiers = meter._static_tier(stream_rows, doc_ids)
    write_mask = wrote & (doc_ids >= 0)
    meter._scatter(meter.writes, stream_rows, write_tiers, write_mask)
    meter._scatter(meter.occupancy, stream_rows, write_tiers, write_mask)
    if evicted_ids is not None:
        evicted_ids = np.asarray(evicted_ids)
        # after a cascade nothing lives below the floor anymore
        ev_tiers = meter._effective_tier(stream_rows, evicted_ids)
        ev_mask = evicted_ids >= 0
        meter._scatter(meter.deletes, stream_rows, ev_tiers, ev_mask)
        rows2 = np.broadcast_to(stream_rows[:, None], ev_tiers.shape)
        np.add.at(meter.occupancy, (rows2[ev_mask], ev_tiers[ev_mask]), -1)
    if state_ids is not None:
        _maybe_migrate(meter, stream_rows, np.asarray(state_ids))
    # accrue the rental integral after the step's moves settled
    meter.doc_steps[stream_rows] += (
        meter.occupancy[stream_rows]
        * (doc_ids >= 0).sum(1).astype(np.int64)[:, None])
    meter.occupancy_hwm[stream_rows] = np.maximum(
        meter.occupancy_hwm[stream_rows], meter.occupancy[stream_rows])


def _maybe_migrate(meter, stream_rows, state_ids) -> None:
    """Fire every boundary whose position the stream just crossed at
    once: residents hop directly to the highest crossed tier (skipping
    zero-width tiers, like the simulator and ``TieredStore``)."""
    b = meter.boundaries[stream_rows]  # (Mb, B)
    crossed = np.where(np.isfinite(b),
                       meter.observed[stream_rows][:, None] >= np.ceil(b),
                       False)
    target = crossed.sum(axis=1)  # highest crossed boundary per stream
    firing = meter.migrate[stream_rows] & (target > meter.floor[stream_rows])
    if not np.any(firing):
        return
    rows = stream_rows[firing]
    ids = state_ids[firing]
    tiers = np.maximum(
        (ids[:, :, None] >= meter.boundaries[rows][:, None, :]).sum(-1),
        meter.floor[rows][:, None])
    resident = (ids >= 0) & (tiers < target[firing][:, None])
    np.add.at(meter.migrations, rows, resident.sum(1))
    # hop billing: read each resident out of its source tier, write it
    # into the target (``SimResult.mig_reads/mig_writes``)
    rows2 = np.broadcast_to(rows[:, None], tiers.shape)
    np.add.at(meter.mig_reads, (rows2[resident], tiers[resident]), 1)
    np.add.at(meter.mig_writes, (rows, target[firing]), resident.sum(1))
    # occupancy: every resident below the target hops into it
    occ = meter.occupancy[rows]
    tgt = target[firing]
    below = np.arange(meter.n_tiers)[None, :] < tgt[:, None]
    moved = np.where(below, occ, 0).sum(1)
    occ = np.where(below, 0, occ)
    occ[np.arange(rows.shape[0]), tgt] += moved
    meter.occupancy[rows] = occ
    meter.floor[rows] = target[firing]


def _isin_rows(needles, haystack):
    """(M, N) row-wise ``needles[i, j] in haystack[i]``."""
    return (needles[:, :, None] == haystack[:, None, :]).any(-1)


def _host_rows(tree, m):
    return jax.tree_util.tree_map(lambda a: np.asarray(a)[:m].copy(), tree)


class Shadow:
    """The scatter reference run beside ``eng``: a copy of its (fresh)
    meter that every metered chunk, boundary swap and final read of the
    engine reaches too. Build it before the first chunk."""

    def __init__(self, eng):
        self.eng = eng
        self.ref = copy.deepcopy(eng.meter)
        self.chunks = []  # (host batches, host pre-step states) per chunk
        self.metered = 0
        dispatch = eng._dispatch
        record = eng.meter.record_update
        apply = eng.meter.apply_boundaries
        reads = eng.meter.record_reads

        def on_dispatch(batches, donate, meter=True):
            if meter:
                self.chunks.append((
                    [_host_rows(pair, b.m)
                     for pair, b in zip(batches, eng.buckets)],
                    [_host_rows(st, b.m)
                     for st, b in zip(eng._states, eng.buckets)]))
            return dispatch(batches, donate, meter)

        def on_record(stream_rows, delta):
            bi = next(j for j, rows in enumerate(eng._global_rows)
                      if rows[0] == stream_rows.start)
            self._replay(bi)
            if bi == len(eng.buckets) - 1:
                self.chunks.pop(0)
                self.metered += 1
            return record(stream_rows, delta)

        def on_apply(row, new_bounds, state_ids):
            self.ref.apply_boundaries(row, new_bounds, state_ids)
            return apply(row, new_bounds, state_ids)

        def on_reads(stream_rows, doc_ids):
            self.ref.record_reads(stream_rows, doc_ids)
            return reads(stream_rows, doc_ids)

        eng._dispatch = on_dispatch
        eng.meter.record_update = on_record
        eng.meter.apply_boundaries = on_apply
        eng.meter.record_reads = on_reads

    def _replay(self, bi: int) -> None:
        """Bucket ``bi`` of the oldest unmetered chunk through the
        scatter reference: the engine's quarantine of non-finite scores,
        then the write mask and evictions recomputed from the states
        around the step (a logmem bucket's mask from its own update)."""
        eng, b = self.eng, self.eng.buckets[bi]
        batches, olds = self.chunks[0]
        s, ids = batches[bi]
        bad = (ids >= 0) & ~np.isfinite(s)
        s = np.where(bad, -np.inf, s).astype(np.float32)
        ids = np.where(bad, -1, ids).astype(np.int32)
        rows = eng._global_rows[bi]
        if b.engine == "logmem":
            old = jax.tree_util.tree_map(jnp.asarray, olds[bi])
            _, wrote = logmem.update(old, jnp.asarray(s), jnp.asarray(ids),
                                     int(b.k), use_pallas=False)
            record_update(self.ref, rows, ids, np.asarray(wrote))
            return
        old_ids = olds[bi].ids
        new_ids = np.asarray(eng._states[bi].ids)[:b.m]
        wrote = (ids >= 0) & _isin_rows(ids, new_ids) \
            & ~_isin_rows(ids, old_ids)
        evicted = np.where((old_ids >= 0) & ~_isin_rows(old_ids, new_ids),
                           old_ids, -1)
        record_update(self.ref, rows, ids, wrote, evicted, new_ids)

    def mismatches(self) -> dict:
        """{array name: rows that differ} between the engine's meter and
        the reference (empty when they agree everywhere)."""
        out = {}
        for name in STATE_ARRAYS:
            a = getattr(self.eng.meter, name)
            r = getattr(self.ref, name)
            if a.shape != r.shape or a.dtype != r.dtype:
                out[name] = f"{a.shape}/{a.dtype} vs {r.shape}/{r.dtype}"
                continue
            diff = a != r
            if diff.ndim > 1:
                diff = diff.any(axis=tuple(range(1, diff.ndim)))
            if diff.any():
                out[name] = np.flatnonzero(diff).tolist()
        return out
