"""Device-side metric accumulators for the jitted engine step.

``MetricsState`` is a tiny pytree carried through ``StreamEngine``'s
jitted multi-bucket step. Every update is computed from values the step
already materializes (the batch ids, the write mask, the eviction ids,
the pre-update reservoir bar, the drift state) — a handful of extra
scalar reductions fused into the same XLA program, with **zero
additional host syncs**: the counters live on device until ``snapshot``
drains them (one transfer, at chunk boundaries or on demand), and with
metrics disabled the step traces the exact pre-obs computation, so
obs-off output is bit-identical.

The integer counters are packed into ONE ``(8,)`` int32 vector (plus a
float32 scalar for the drift score) so the obs variant adds only two
pytree leaves to the step's signature — per-call dispatch cost on small
fleets is dominated by leaf count, not by the reductions themselves.
Drain and rebase into the host-side accumulator before a window
approaches 2^31 docs (x64 stays off on the hot path).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

# slots of the packed counter vector
(DOCS, ADMITS, EVICTIONS, BAR_CANDIDATES, BAR_PASSES, CHUNKS, DRIFT_FIRED,
 SCORES_QUARANTINED) = range(8)
N_SLOTS = 8


class MetricsState(NamedTuple):
    """Fleet-level counters, accumulated on device.

    Under a fleet mesh (``StreamEngine(mesh=...)``) the leaves carry a
    leading shard axis — counts ``(D, 8)``, score ``(D,)`` — split
    across the mesh so each device accumulates its own block inside the
    sharded step with **no collectives on the hot path**; ``snapshot``
    aggregates across shards (integer sums are exact, so fleet-global
    counts are identical to the single-device run's)."""

    counts: jax.Array  # (8,) i32 — or (D, 8) sharded; see slots above
    drift_score_max: jax.Array  # () f32 — or (D,) sharded

    @property
    def sharded(self) -> bool:
        return getattr(self.counts, "ndim", 1) == 2


def init(shards: int = 0) -> MetricsState:
    """``shards > 0`` builds the sharded layout (one counter block per
    mesh device); the caller places it with the fleet row sharding."""
    if shards:
        return MetricsState(counts=jnp.zeros((shards, N_SLOTS), jnp.int32),
                            drift_score_max=jnp.zeros((shards,),
                                                      jnp.float32))
    return MetricsState(counts=jnp.zeros((N_SLOTS,), jnp.int32),
                        drift_score_max=jnp.zeros((), jnp.float32))


def shard_local(ms: MetricsState) -> MetricsState:
    """Inside ``shard_map``: squeeze this shard's (1, 8)/(1,) block to
    the flat single-device layout so every accumulate_* law applies
    unchanged."""
    return MetricsState(counts=ms.counts[0],
                        drift_score_max=ms.drift_score_max[0])


def shard_pack(ms: MetricsState) -> MetricsState:
    """Inverse of ``shard_local``: re-add the leading shard axis before
    the sharded step returns its block."""
    return MetricsState(counts=ms.counts[None],
                        drift_score_max=ms.drift_score_max[None])


@jax.named_scope("obs")
def accumulate_bucket(ms: MetricsState, batch_scores, batch_ids, bar,
                      wrote, evicted) -> MetricsState:
    """Fold one bucket's step outputs into the counters (pure; traced
    inside the jitted step). ``bar`` is the pre-update entry bar
    (``state.scores[:, -1]``): the kernel-filter pass rate is the
    fraction of live candidates scoring above it — on unfull reservoirs
    the bar is -inf and every candidate passes, matching the filter."""
    live = batch_ids >= 0
    i32 = jnp.int32
    docs = live.sum(dtype=i32)
    z = jnp.zeros((), i32)
    delta = jnp.stack([
        docs,                                                # DOCS
        wrote.sum(dtype=i32),                                # ADMITS
        (evicted >= 0).sum(dtype=i32),                       # EVICTIONS
        docs,                                                # BAR_CANDIDATES
        (live & (batch_scores > bar[:, None])).sum(dtype=i32),  # BAR_PASSES
        z, z, z])
    return ms._replace(counts=ms.counts + delta)


@jax.named_scope("obs")
def accumulate_quarantine(ms: MetricsState, count) -> MetricsState:
    """Count non-finite scores the step swapped out for pad slots before
    they could poison the reservoir compares (NaN fails every compare)."""
    return ms._replace(counts=ms.counts.at[SCORES_QUARANTINED].add(
        jnp.asarray(count, jnp.int32)))


@jax.named_scope("obs")
def accumulate_drift(ms: MetricsState, score_max, fired_count
                     ) -> MetricsState:
    """Fold the drift detector's per-step summary (max normalized score,
    latched fire count) into the counters."""
    counts = ms.counts.at[DRIFT_FIRED].set(
        jnp.asarray(fired_count, jnp.int32))
    return MetricsState(
        counts=counts,
        drift_score_max=jnp.maximum(ms.drift_score_max,
                                    jnp.asarray(score_max, jnp.float32)))


@jax.named_scope("obs")
def bump_chunk(ms: MetricsState) -> MetricsState:
    return ms._replace(counts=ms.counts.at[CHUNKS].add(1))


def snapshot(ms: MetricsState) -> dict:
    """Drain the device counters to host scalars (the only sync point).

    Sharded states are aggregated here — the cross-shard sum (max for
    the drift score; every shard bumps CHUNKS once per step, so chunks
    take one shard's count) runs on device before the single transfer,
    so ``obs_snapshot``/Prometheus always report *fleet-global* counts,
    never one shard's block."""
    import numpy as np
    if ms.sharded:
        chunks = int(np.asarray(ms.counts[:, CHUNKS].max()))
        c = np.asarray(ms.counts.sum(axis=0))
        score = float(np.asarray(ms.drift_score_max.max()))
    else:
        c = np.asarray(ms.counts)
        chunks = int(c[CHUNKS])
        score = float(np.asarray(ms.drift_score_max))
    cand, passes = int(c[BAR_CANDIDATES]), int(c[BAR_PASSES])
    return {
        "docs": int(c[DOCS]),
        "admits": int(c[ADMITS]),
        "evictions": int(c[EVICTIONS]),
        "bar_candidates": cand,
        "bar_passes": passes,
        "filter_pass_rate": passes / cand if cand else 0.0,
        "chunks": chunks,
        "drift_score_max": score,
        "drift_fired": int(c[DRIFT_FIRED]),
        "scores_quarantined": int(c[SCORES_QUARANTINED]),
    }


def to_canonical(ms: MetricsState):
    """Collapse a (possibly sharded) state to the mesh-independent host
    form ``(counts (8,) i64-safe, score f32)`` used by checkpoints: the
    same aggregation ``snapshot`` reports (integer sums exact; CHUNKS and
    the drift high-water take the cross-shard max)."""
    import numpy as np
    if ms.sharded:
        c = np.asarray(ms.counts).sum(axis=0).astype(np.int32)
        c[CHUNKS] = np.asarray(ms.counts)[:, CHUNKS].max()
        score = np.float32(np.asarray(ms.drift_score_max).max())
    else:
        c = np.asarray(ms.counts).copy()
        score = np.float32(np.asarray(ms.drift_score_max))
    return c, score


def from_canonical(counts, score, shards: int = 0) -> MetricsState:
    """Rebuild a device state from the canonical form onto ``shards``
    mesh devices (0 = flat). The aggregate lands in shard 0's block with
    the rest zeroed, so subsequent accumulation + ``snapshot``'s
    sum/max aggregation reproduce the uninterrupted run's numbers for
    ANY target shard count (pad blocks are inert zeros)."""
    import numpy as np
    counts = np.asarray(counts, np.int32).reshape(N_SLOTS)
    if shards:
        c = np.zeros((shards, N_SLOTS), np.int32)
        c[0] = counts
        s = np.zeros((shards,), np.float32)
        s[0] = score
        return MetricsState(counts=jnp.asarray(c),
                            drift_score_max=jnp.asarray(s))
    return MetricsState(counts=jnp.asarray(counts),
                        drift_score_max=jnp.asarray(score, jnp.float32))
