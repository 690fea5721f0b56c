"""Plain reference of one logmem tenant and its meter, in NumPy float32.

The logmem backend keeps no reservoir: it admits a document iff its score
beats the tenant's threshold tau, and re-estimates tau from each chunk.
After a chunk of w live documents, with t documents seen in all and K the
tenant's K:

* while t <= K every document is admitted;
* once t > K, a tenant that has no threshold yet admits the top B of the
  chunk by score (ties to the earlier position), B = round(min(t, K) w / t)
  clipped to [0, w];
* otherwise a document is admitted iff its score is above tau as it stood
  before the chunk.

Then, when t > K and r = w K / t is at least one half, the r-th largest
score of the chunk (r rounded, clipped to [1, w]) is an estimate of the
K-th largest score of the stream. Estimates are folded into a decayed
mean (weights w, old weight halved per chunk). The phase p = floor(log2
(max(t / K, 1))) counts doublings; when a chunk with a threshold moves p
up, the finished phase's mean is committed to a floor that never falls,
and the mean restarts. tau is the larger of the floor and the current
mean (the floor alone while the mean is empty). All arithmetic is
float32, as the backend states.

Each admitted document is written to the tier its position falls in
under the tenant's boundaries. ``precision="bfloat16"`` rounds scores and
the running estimates to bfloat16: the control.
"""
from __future__ import annotations

import numpy as np

DECAY = 0.5


def _caster(precision: str):
    if precision == "float32":
        return lambda x: np.float32(x) if np.ndim(x) == 0 else \
            np.asarray(x, np.float32)
    if precision == "bfloat16":
        import ml_dtypes

        def cast(x):
            y = np.asarray(x, np.float32).astype(ml_dtypes.bfloat16)
            y = y.astype(np.float32)
            return np.float32(y) if np.ndim(x) == 0 else y
        return cast
    raise ValueError(f"unknown precision {precision!r}")


class Tenant:
    """One tenant's tracker and meter, fed chunk by chunk."""

    def __init__(self, k: int, bounds, precision: str = "float32"):
        self.f = _caster(precision)
        self.bounds = np.asarray(bounds, np.float64)
        self.k, self.kf = k, np.float32(k)
        self.seen = 0
        self.admits = 0
        self.tau = np.float32(-np.inf)
        self.tau_floor = np.float32(-np.inf)
        self.q_num = np.float32(0.0)
        self.q_den = np.float32(0.0)
        self.phase = -1
        self.writes = np.zeros(self.bounds.shape[0] + 1, np.int64)

    def feed(self, scores, pos) -> None:
        """One chunk: the tenant's (scores (w,), positions (w,)) in
        position order."""
        f, k, kf = self.f, self.k, self.kf
        s = f(scores)
        pos = np.asarray(pos, np.int64)
        wl = s.shape[0]
        wl_f = np.float32(wl)
        t = self.seen + wl
        t_f = np.float32(t)
        admit_all = t <= k
        cold = (not admit_all) and np.isneginf(self.tau)
        steady = (not admit_all) and not cold
        order = np.argsort(-s, kind="stable")
        if admit_all:
            wrote = np.ones(wl, bool)
        elif cold:
            budget = np.float32(np.minimum(t_f, kf) * wl_f
                                / np.maximum(t_f, np.float32(1.0)))
            budget = int(np.clip(np.round(budget), 0.0, wl_f))
            wrote = np.zeros(wl, bool)
            wrote[order[:budget]] = True
        else:
            wrote = s > self.tau
        r_raw = np.float32(wl_f * kf / np.maximum(t_f, np.float32(1.0)))
        resolvable = (not admit_all) and r_raw >= 0.5 and wl > 0
        r = int(np.clip(np.round(r_raw), 1.0, max(wl_f, np.float32(1.0))))
        est = s[order[r - 1]] if wl else np.float32(-np.inf)
        p = int(np.floor(np.log2(np.maximum(t_f / kf, np.float32(1.0)))))
        boundary = steady and p > self.phase
        ratio_old = f(self.q_num / np.maximum(self.q_den, np.float32(1e-30)))
        if boundary and self.q_den > 0:
            self.tau_floor = max(self.tau_floor, ratio_old)
        if boundary:
            self.q_num, self.q_den = np.float32(0.0), np.float32(0.0)
            self.phase = p
        if resolvable:
            self.q_num = f(np.float32(DECAY) * self.q_num + wl_f * est)
            self.q_den = f(np.float32(DECAY) * self.q_den + wl_f)
        if self.q_den > 0:
            self.tau = max(self.tau_floor,
                           f(self.q_num / np.maximum(self.q_den,
                                                     np.float32(1e-30))))
        else:
            self.tau = self.tau_floor
        self.seen = t
        self.admits += int(wrote.sum())
        adm = pos[wrote]
        np.add.at(self.writes,
                  (adm[:, None] >= self.bounds[None, :]).sum(1), 1)

    def result(self) -> dict:
        return {"admits": self.admits, "observed": self.seen,
                "writes": self.writes}

