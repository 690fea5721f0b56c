"""Plain reference of one exact top-K tenant and its meter, in NumPy.

A tenant's documents arrive in chunks. After each chunk its reservoir is
the top K of every document seen so far, ordered by score and then by
position (the earlier position wins a tie). A document is written to
storage when it enters the reservoir, on the tier its position falls in
under the tenant's boundary vector (tier = number of boundaries <= the
position). An evicted document is deleted from the tier it lives on. A
cascading tenant, once its position count crosses boundary b (at
ceil(b)), moves every resident below that tier up to it, and from then on
its documents live no lower than that tier. The final read takes every
survivor from the tier it lives on.

``precision="bfloat16"`` rounds the scores to bfloat16 first: that is the
control, the same reference a step of precision lower.
"""
from __future__ import annotations

import numpy as np


def _round(scores: np.ndarray, precision: str) -> np.ndarray:
    if precision == "float32":
        return np.asarray(scores, np.float32)
    if precision == "bfloat16":
        import ml_dtypes
        return np.asarray(scores, np.float32).astype(
            ml_dtypes.bfloat16).astype(np.float32)
    raise ValueError(f"unknown precision {precision!r}")


def replay(chunks, k: int, bounds, migrate: bool,
           precision: str = "float32") -> dict:
    """``chunks``: (scores (W,), positions (W,)) per chunk, padding = -1
    positions. ``bounds``: (B,) boundaries, +inf padded. Returns the
    survivors (sorted positions) and the metered counts per tier."""
    bounds = np.asarray(bounds, np.float64)
    t = bounds.shape[0] + 1

    def tier(pos):
        return (pos[:, None] >= bounds[None, :]).sum(1)

    res_s = np.empty(0, np.float32)
    res_i = np.empty(0, np.int64)
    floor = 0
    observed = 0
    migrations = 0
    writes = np.zeros(t, np.int64)
    deletes = np.zeros(t, np.int64)
    for scores, pos in chunks:
        pos = np.asarray(pos, np.int64)
        live = pos >= 0
        s = _round(scores, precision)[live]
        i = pos[live]
        all_s = np.concatenate([res_s, s])
        all_i = np.concatenate([res_i, i])
        top = np.lexsort((all_i, -all_s.astype(np.float64)))[:k]
        new_s, new_i = all_s[top], all_i[top]
        wrote = np.isin(i, new_i)
        gone = res_i[~np.isin(res_i, new_i)]
        observed += i.size
        np.add.at(writes, tier(i[wrote]), 1)
        np.add.at(deletes, np.maximum(tier(gone), floor), 1)
        if migrate:
            target = int((np.isfinite(bounds)
                          & (observed >= np.ceil(bounds))).sum())
            if target > floor:
                migrations += int((np.maximum(tier(new_i), floor)
                                   < target).sum())
                floor = target
        res_s, res_i = new_s, new_i
    reads = np.bincount(np.maximum(tier(res_i), floor), minlength=t)
    return {"survivors": np.sort(res_i), "observed": observed,
            "writes": writes, "deletes": deletes, "migrations": migrations,
            "reads": reads[:t]}
