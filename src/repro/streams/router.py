"""Router: scatter a mixed multi-tenant batch of scored docs into dense
per-bucket arrays the batched engine can consume.

Streams are bucketed by K — every stream in a bucket shares one reservoir
width, so the bucket's state is a dense ``(M_bucket, K)`` array and one
vectorized sort-merge updates all of them. Each stream owns one global
row: its bucket's offset plus its row in the bucket, so every bucket is a
contiguous run of global rows. A mixed ingest batch — (stream_id, score,
doc_id) triples in arbitrary order — is mapped to global rows (a dense
table where the registered ids are dense, else a binary search), grouped
by row in one stable radix pass, and scattered into ``(M_bucket, W)``
matrices padded with ``(-inf, -1)``. Each stream's row is ordered by doc
id (= stream position), which makes routing deterministic: the stable
grouping keeps each stream's input order, so the doc-id sort runs only
for the streams whose documents arrive out of position order. ``W`` is
rounded up to a power of two to bound the number of distinct shapes the
jitted engine step compiles for.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import numpy as np

PAD_SCORE = -np.inf
PAD_ID = -1
# the router keeps a dense stream id -> row table where it holds at most
# this many entries per registered stream; sparser id sets are searched
_TABLE_ENTRIES_PER_STREAM = 4


@dataclass(frozen=True)
class Bucket:
    """All streams sharing one reservoir width K *and* one engine
    backend (``"exact"`` O(K) reservoir or ``"logmem"`` O(log K)
    threshold tracker — the per-bucket state pytrees differ, so mixed
    backends cannot share a bucket). ``stream_ids[row]`` maps the
    bucket-local row back to the global stream id."""

    k: int
    stream_ids: Tuple[int, ...]
    engine: str = "exact"

    @property
    def m(self) -> int:
        return len(self.stream_ids)


def bucket_streams(ks: Dict[int, int],
                   engines: Dict[int, str] | None = None) -> List[Bucket]:
    """Group streams (stream_id → K, optionally stream_id → engine) into
    per-(K, engine) buckets, ordered by (K, engine) ascending and rows
    ordered by stream id — deterministic layout."""
    by_key: Dict[Tuple[int, str], List[int]] = {}
    for sid, k in ks.items():
        eng = engines.get(sid, "exact") if engines else "exact"
        by_key.setdefault((int(k), str(eng)), []).append(int(sid))
    return [Bucket(k=k, stream_ids=tuple(sorted(by_key[(k, eng)])),
                   engine=eng)
            for k, eng in sorted(by_key)]


def _next_pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length() if x > 1 else 1


def blank_dense(m: int, w: int) -> Tuple[np.ndarray, np.ndarray]:
    """(scores (m, w) f32, doc_ids (m, w) i32) of all-pad rows — the one
    inert filler every staging path shares: ``route`` scatters live docs
    into it, and the engine's shard padding appends whole blank rows.
    Every law (update, drift, metrics, meter) treats (PAD_SCORE, PAD_ID)
    entries as absent; tests assert the inertness through both engine
    backends."""
    return (np.full((m, w), PAD_SCORE, np.float32),
            np.full((m, w), PAD_ID, np.int32))


def _group_by_row(rows: np.ndarray, m: int) -> np.ndarray:
    """Stable permutation that groups ``rows`` (ints in ``[0, m)``)
    ascending. NumPy radix-sorts integer keys of 16 bits or fewer, so this
    is one pass on 8- or 16-bit keys, and above 65,536 rows two 16-bit
    passes: the low half, then the high half."""
    if m <= 1 << 8:
        return np.argsort(rows.astype(np.uint8), kind="stable")
    if m <= 1 << 16:
        return np.argsort(rows.astype(np.uint16), kind="stable")
    low = np.argsort(rows.astype(np.uint16), kind="stable")  # wraps: low bits
    high = (rows[low] >> 16).astype(np.uint16)
    return low[np.argsort(high, kind="stable")]


class StreamRouter:
    """Routes mixed batches to bucket-dense matrices (numpy, host-side).

    Counts, over every ``route`` call, the dense slots it emitted
    (``route_slots``) and the documents among them (``route_useful``):
    the rest is padding to each bucket's power-of-two width. Batches in
    which some stream's doc ids did not strictly ascend needed the doc-id
    sort: ``route_resorted`` counts them, ``route_resorted_rows`` the
    streams (rows) it re-ordered."""

    def __init__(self, buckets: Sequence[Bucket]):
        self.buckets = list(buckets)
        self.route_slots = 0
        self.route_useful = 0
        self.route_resorted = 0
        self.route_resorted_rows = 0
        ms = [b.m for b in self.buckets]
        self._offsets = np.cumsum([0] + ms)
        self.m = int(self._offsets[-1])
        # stream id of each global row (buckets in order, rows within)
        self._sid_of_row = np.asarray(
            [sid for b in self.buckets for sid in b.stream_ids], np.int64)
        order = np.argsort(self._sid_of_row, kind="stable")
        self._sids = self._sid_of_row[order]
        if np.any(np.diff(self._sids) == 0):
            raise ValueError("duplicate stream id across buckets")
        self._row_of_sid = order
        self._table = None  # dense id -> global row, -1 where unregistered
        if not self.m or (self._sids[0] >= 0 and self._sids[-1]
                          < _TABLE_ENTRIES_PER_STREAM * self.m):
            self._table = np.full(int(self._sids[-1]) + 1 if self.m else 1,
                                  -1, np.int64)
            self._table[self._sids] = order

    def lookup(self, stream_ids) -> np.ndarray:
        """stream_ids (S,) → global rows (S,): bucket ``b`` holds the
        rows ``[offset_b, offset_b + m_b)``, in its ``stream_ids`` order.
        Raises ``KeyError`` for an id no bucket registered."""
        stream_ids = np.asarray(stream_ids, np.int64).reshape(-1)
        if self._table is not None:
            # one unsigned compare bounds both ends: negatives wrap high
            ok = stream_ids.view(np.uint64) < self._table.size
            rows = np.take(self._table, stream_ids, mode="clip")
            ok &= rows >= 0
        else:
            pos = np.minimum(np.searchsorted(self._sids, stream_ids),
                             self._sids.size - 1)
            ok = self._sids[pos] == stream_ids
            rows = self._row_of_sid[pos]
        if not np.all(ok):
            bad = np.unique(stream_ids[~ok])
            raise KeyError(f"unregistered stream ids: {bad[:8].tolist()}")
        return rows

    def route(self, stream_ids, scores, doc_ids, *, pad_to: int | None = None
              ) -> List[Tuple[np.ndarray, np.ndarray]]:
        """Scatter a mixed batch into one dense (scores, doc_ids) pair per
        bucket, aligned with ``self.buckets``.

        Returns ``[(scores (M_b, W_b) f32, doc_ids (M_b, W_b) i32), ...]``
        padded with ``(PAD_SCORE, PAD_ID)``. ``W_b`` = max docs routed to
        any stream of the bucket this batch, rounded up to a power of two
        (or ``pad_to`` if given and larger). Each row is sorted by doc id.

        The batch is grouped by global row in one stable radix pass, which
        keeps each stream's input order; the doc-id sort runs only for the
        streams whose doc ids do not strictly ascend in it (out of position
        order, or a doc id repeated, which raises ``ValueError``).
        """
        scores = np.asarray(scores, np.float32).reshape(-1)
        doc_ids = np.asarray(doc_ids, np.int32).reshape(-1)
        rows = self.lookup(stream_ids)
        perm = _group_by_row(rows, self.m)
        counts = np.bincount(rows, minlength=self.m)
        rs = np.repeat(np.arange(self.m), counts)
        ds = doc_ids[perm]
        ss = scores[perm]
        unsorted = (rs[1:] == rs[:-1]) & (ds[1:] <= ds[:-1])
        resorted = 0
        if unsorted.any():
            resorted = self._sort_rows(rs, ds, ss, np.unique(rs[1:][unsorted]))
        bounds = np.r_[0, np.cumsum(counts)]
        out = []
        for b_idx, bucket in enumerate(self.buckets):
            lo, hi = self._offsets[b_idx], self._offsets[b_idx + 1]
            n_b = counts[lo:hi]
            w = _next_pow2(max(int(n_b.max(initial=0)), 1))
            if pad_to is not None:
                w = max(w, int(pad_to))
            dense_s, dense_i = blank_dense(bucket.m, w)
            # each row's documents are contiguous in grouped order, and in
            # row-major order the mask's True slots take them in turn
            live = np.arange(w) < n_b[:, None]
            dense_s[live] = ss[bounds[lo]:bounds[hi]]
            dense_i[live] = ds[bounds[lo]:bounds[hi]]
            out.append((dense_s, dense_i))
        self.route_slots += sum(i.size for _, i in out)
        self.route_useful += scores.size
        self.route_resorted += bool(resorted)
        self.route_resorted_rows += resorted
        return out

    def _sort_rows(self, rs, ds, ss, bad_rows) -> int:
        """Re-order the grouped batch (``rs`` rows, ``ds`` doc ids, ``ss``
        scores, in place) by doc id within ``bad_rows``; raise on a doc id
        routed twice to one stream. Returns the number of rows sorted."""
        mark = np.zeros(self.m, bool)
        mark[bad_rows] = True
        sel = np.flatnonzero(mark[rs])
        r, d = rs[sel], ds[sel]
        # one int64 key: row in the high half, doc id (offset) in the low
        o = np.argsort((r.astype(np.int64) << 32)
                       | (d.astype(np.int64) + (1 << 31)))
        d = d[o]
        ds[sel] = d
        ss[sel] = ss[sel][o]
        dup = (r[1:] == r[:-1]) & (d[1:] == d[:-1])
        if np.any(dup):
            j = int(np.flatnonzero(dup)[0])
            raise ValueError(
                f"duplicate (stream, doc) in one batch: stream "
                f"{self._sid_of_row[r[j]]} doc {d[j]} — a doc id may "
                f"appear once per stream per ingest")
        return int(bad_rows.size)
