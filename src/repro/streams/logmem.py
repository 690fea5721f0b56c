"""Logarithmic-memory reservoir backend: huge-K tenants in O(log K) state.

The exact engine keeps ``(K,)`` score/id rows per stream, which caps
tenants-per-device — at K = 64k a single tenant costs 512 KB of device
state. Following "Optimal k-Secretary with Logarithmic Memory"
(arXiv 2502.09834: 1−O(1/√k)-competitive selection with O(log k)
words), this backend replaces the reservoir with a phase-bucketed
acceptance-threshold tracker:

* Admission is a single threshold compare: a doc enters iff its score
  beats the stream's active threshold ``tau`` (the estimate of the
  running K-th largest score — the same "bar" the exact engine reads
  off ``scores[:, -1]``, but maintained without storing the top K).
* ``tau`` is re-estimated from each ingest chunk's *transient* order
  statistics: the r-th largest of a W-wide chunk at position t targets
  the K/t quantile when r = round(W·K/t). Chunk estimates are folded
  into a decayed accumulator (weights halve per chunk, so the estimate
  tracks the bar as t grows) and committed into a monotone floor at
  phase boundaries — phases are the doubling intervals
  p = ⌊log₂(t/K)⌋, which is what makes the persistent state
  O(log(n/K)): one committed threshold and one admit counter per
  phase, plus seven scalars.
* Before t reaches K every doc is admitted (the exact engine fills its
  reservoir too); the crossing chunk admits its top-B by score, with
  B the hypergeometric chunk-law mean — so admit *counts* stay on the
  closed-form write law E[writes] = Σ min(1, K/j) that the planner,
  drift detector and obs residuals already consume. Measured on
  uniform/normal/lognormal traces the realized competitive ratio is
  ≥ 1 − c/√K with c ≤ ~0.25 and admits within a few percent of the
  law (``trace_competitive_ratio`` quantifies both per trace).

The admission scan (threshold compare + admit mask + per-tile counts)
is the ``kernels.logmem_update`` Pallas kernel — a 2-D (stream, tile)
grid, one HBM pass; the O(M) scalar threshold epilogue (sort of the
chunk, gather of the r-th order statistic, phase commit) runs in jnp
inside the same jitted step.

Contract differences vs the exact backend (documented, test-asserted):

* No ids are stored, so re-observed doc ids are **not** deduped
  (streams are position-indexed; each id arrives once), ``survivors``
  returns an empty id set, and evictions are never reported — storage
  written by a logmem tenant stays until window end (≈ K·ln(n/K) docs
  instead of peaking near K: the device-memory/storage tradeoff).
* Admission follows the write law only up to a 1−O(1/√K) slack;
  ``law_slack(k)`` is the per-chunk fractional budget the drift
  detector and obs residual monitor fold into their thresholds so an
  undrifted logmem fleet stays quiet (null FPR ≤ alpha) while an 8×
  rate drift still fires.
* Chunks too narrow to resolve the K/t quantile (W·K < t/2) fall back
  to law-budgeted admission for that chunk instead of folding a noisy
  estimate; steady-state admission is *uncapped* threshold-compare, so
  drift stays visible in the admit counts.
"""
from __future__ import annotations

import math
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import router

PAD_ID = router.PAD_ID

# persistent phase buckets: covers windows up to K·2^N_PHASES docs
N_PHASES = 24
# per-chunk EMA decay of the quantile accumulator: recent chunks aim at
# the current K/t quantile, old chunks at stale (larger) ones
DECAY = 0.5
# admit-count slack constant: |admits − law| ≤ LAW_SLACK_C/√K · law holds
# empirically across traces (prototype sweep: bias ≤ 3.3% at K=4096,
# ≤ 1% at K=65536); consumers add slack·expected to their thresholds
LAW_SLACK_C = 4.0


def law_slack(k, c: float = LAW_SLACK_C) -> float:
    """Fractional admit-count slack of the logmem backend at width K —
    the 1−O(1/√K) approximation budget folded into drift/residual
    thresholds (z-score denominators only grow, so null FPR ≤ alpha is
    preserved)."""
    return float(c) / math.sqrt(float(k))


class LogmemState(NamedTuple):
    """M logmem streams stacked on a leading axis — O(log K) per stream
    (7 scalars + 2 per-phase vectors) vs the exact backend's O(K)."""

    seen: jax.Array  # (M,) i32 — docs observed (padding excluded)
    admits: jax.Array  # (M,) i32 — total docs admitted (threshold writes)
    tau: jax.Array  # (M,) f32 — active acceptance threshold (-inf cold)
    tau_floor: jax.Array  # (M,) f32 — monotone floor from committed phases
    q_num: jax.Array  # (M,) f32 — decayed quantile accumulator (numerator)
    q_den: jax.Array  # (M,) f32 — decayed quantile accumulator (weight)
    phase: jax.Array  # (M,) i32 — current phase ⌊log₂(t/K)⌋ (-1 pre-warm)
    phase_tau: jax.Array  # (M, P) f32 — committed threshold per phase
    phase_admits: jax.Array  # (M, P) i32 — admits per phase bucket


def init(m: int, k: int | None = None, phases: int = N_PHASES) -> LogmemState:
    """Fresh state for M streams. ``k`` is accepted for signature parity
    with the exact ``engine.init`` but not stored — the reservoir width
    is a static of the bucket's update, not of the state."""
    del k
    return LogmemState(
        seen=jnp.zeros((m,), jnp.int32),
        admits=jnp.zeros((m,), jnp.int32),
        tau=jnp.full((m,), -jnp.inf, jnp.float32),
        tau_floor=jnp.full((m,), -jnp.inf, jnp.float32),
        q_num=jnp.zeros((m,), jnp.float32),
        q_den=jnp.zeros((m,), jnp.float32),
        phase=jnp.full((m,), -1, jnp.int32),
        phase_tau=jnp.full((m, phases), -jnp.inf, jnp.float32),
        phase_admits=jnp.zeros((m, phases), jnp.int32),
    )


def state_bytes_per_stream(state: LogmemState) -> float:
    """Device bytes per stream of this state (pytree leaves / M)."""
    m = state.seen.shape[0]
    return sum(np.prod(leaf.shape) * leaf.dtype.itemsize
               for leaf in state) / max(m, 1)


def exact_bytes_per_stream(k: int) -> float:
    """Device bytes per stream of the exact backend at width K
    (f32 scores + i32 ids + i32 seen)."""
    return 8.0 * k + 4.0


@jax.named_scope("logmem")
def update(state: LogmemState, batch_scores: jax.Array,
           batch_ids: jax.Array, k: int, *, block_n: int = 512,
           use_pallas: bool = True) -> Tuple[LogmemState, jax.Array]:
    """Advance M logmem streams by one chunk: scores/ids (M, W), padding
    = (-inf, -1). Returns (new_state, wrote (M, W) bool) — the same
    contract as the exact ``engine.update``, so the engine step, meter,
    drift detector and metrics consume it unchanged.

    The admission scan (compare vs tau, admit mask, per-tile admit/live
    counts) is one ``kernels.logmem_update`` pass; the threshold
    epilogue (chunk sort → r-th order statistic → decayed fold → phase
    commit) is O(M·W log W) jnp in the same jitted program. Live scores
    must be finite (the router guarantees it); pad rows/columns are
    inert.
    """
    from repro.kernels.logmem_update import ops as lm_ops
    m, w = batch_scores.shape
    rows = jnp.arange(m)
    scores = batch_scores.astype(jnp.float32)
    ids = batch_ids.astype(jnp.int32)
    kf = jnp.float32(k)

    mask, acounts, lcounts, _ = lm_ops.logmem_admit(
        scores, ids, state.tau, block_n=block_n, use_pallas=use_pallas)
    live = ids >= 0
    wl = lcounts.sum(axis=1)  # (M,) live docs this chunk
    wl_f = wl.astype(jnp.float32)
    t_after = state.seen + wl
    t_f = t_after.astype(jnp.float32)

    # one descending sort per row serves both the cold-start top-B
    # selection (ranks) and the quantile estimate (r-th largest)
    s_masked = jnp.where(live, scores, -jnp.inf)
    order = jnp.argsort(-s_masked, axis=1)
    sorted_desc = jnp.take_along_axis(s_masked, order, axis=1)
    ranks = jnp.zeros((m, w), jnp.int32).at[rows[:, None], order].set(
        jnp.broadcast_to(jnp.arange(w, dtype=jnp.int32)[None, :], (m, w)))

    admit_all = t_after <= k  # reservoir not yet full: everything enters
    cold = (~admit_all) & jnp.isneginf(state.tau)
    steady = (~admit_all) & ~cold
    # cold/unresolvable fallback: admit the chunk-law mean count (top-B
    # by score), keeping admit counts on the closed-form write law
    budget = jnp.clip(jnp.round(jnp.minimum(t_f, kf) * wl_f
                                / jnp.maximum(t_f, 1.0)),
                      0.0, wl_f).astype(jnp.int32)
    topb = live & (ranks < budget[:, None])
    wrote = jnp.where(admit_all[:, None], live,
                      jnp.where(cold[:, None], topb, mask > 0))

    # quantile estimate: the r-th largest of the chunk targets the K/t
    # quantile when r = round(W·K/t); chunks too narrow to resolve it
    # (r_raw < 1/2) contribute nothing — tau holds, admission for such
    # cold rows stays on the law budget above
    r_raw = wl_f * kf / jnp.maximum(t_f, 1.0)
    resolvable = (~admit_all) & (r_raw >= 0.5) & (wl > 0)
    r = jnp.clip(jnp.round(r_raw), 1.0, jnp.maximum(wl_f, 1.0)) \
        .astype(jnp.int32)
    est = jnp.take_along_axis(sorted_desc, (r - 1)[:, None], axis=1)[:, 0]

    # phase boundary: commit the finished phase's estimate into the
    # monotone floor (the running bar never decreases under i.u.d.
    # arrivals), restart the accumulator
    p = jnp.floor(jnp.log2(jnp.maximum(t_f / kf, 1.0))).astype(jnp.int32)
    boundary = steady & (p > state.phase)
    ratio_old = state.q_num / jnp.maximum(state.q_den, 1e-30)
    commit_ok = boundary & (state.q_den > 0)
    tau_floor = jnp.where(commit_ok,
                          jnp.maximum(state.tau_floor, ratio_old),
                          state.tau_floor)
    q_num = jnp.where(boundary, 0.0, state.q_num)
    q_den = jnp.where(boundary, 0.0, state.q_den)
    phase = jnp.where(boundary, p, state.phase)

    q_num = jnp.where(resolvable, DECAY * q_num + wl_f * est, q_num)
    q_den = jnp.where(resolvable, DECAY * q_den + wl_f, q_den)
    tau = jnp.where(q_den > 0,
                    jnp.maximum(tau_floor, q_num / jnp.maximum(q_den,
                                                               1e-30)),
                    tau_floor)

    # O(log K) diagnostics: the committed threshold of the finished
    # phase, and admits attributed to the (post-commit) current phase
    n_ph = state.phase_tau.shape[1]
    ph_idx = jnp.arange(n_ph, dtype=jnp.int32)[None, :]
    pt_hot = ph_idx == jnp.clip(state.phase, 0, n_ph - 1)[:, None]
    phase_tau = jnp.where(pt_hot & commit_ok[:, None],
                          ratio_old[:, None], state.phase_tau)
    chunk_admits = wrote.sum(axis=1, dtype=jnp.int32)
    pa_hot = ph_idx == jnp.clip(phase, 0, n_ph - 1)[:, None]
    phase_admits = state.phase_admits + \
        pa_hot.astype(jnp.int32) * chunk_admits[:, None]

    return LogmemState(seen=t_after, admits=state.admits + chunk_admits,
                       tau=tau, tau_floor=tau_floor, q_num=q_num,
                       q_den=q_den, phase=phase, phase_tau=phase_tau,
                       phase_admits=phase_admits), wrote


def thresholds(state: LogmemState) -> jax.Array:
    """(M,) active acceptance thresholds — the logmem analog of the
    exact backend's entry bar ``scores[:, -1]`` (-inf while unfull)."""
    return state.tau


def expected_admits(n, k: int) -> np.ndarray:
    """Closed-form E[total admits] after n docs — the same write law
    E[writes] = Σ_{j≤n} min(1, K/j) both backends are metered against
    (eq. 9/10; ``shp.expected_cum_writes_batched`` at batch=1)."""
    from repro.core import shp
    n = np.asarray(n, np.int64)
    out = shp.expected_cum_writes_batched(np.maximum(n, 1) - 1, int(k), 1)
    return np.where(n > 0, out, 0.0)


def trace_competitive_ratio(scores, k: int, chunk: int, *,
                            use_pallas: bool = False,
                            block_n: int = 512) -> Dict:
    """Simulator-trace harness: replay score traces through the jitted
    logmem update and quantify the realized gap vs the exact reservoir.

    ``scores``: (n,) or (M, n) float — one window per row. Returns per
    stream the realized competitive ratio (top-K mass retained by the
    admitted set over the trace's true top-K mass), the constant
    ``c = (1 − ratio)·√K`` of the 1 − c/√K guarantee, and the admit
    count against the closed-form write law. The final (possibly
    partial) chunk is padded with (-inf, -1), so the harness also
    exercises pad inertness.
    """
    arr = np.atleast_2d(np.asarray(scores, np.float32))
    m, n = arr.shape
    if n <= 0 or chunk <= 0:
        raise ValueError("need a non-empty trace and chunk > 0")
    step = jax.jit(lambda st, s, i: update(st, s, i, k,
                                           block_n=block_n,
                                           use_pallas=use_pallas))
    st = init(m)
    admitted = [[] for _ in range(m)]
    for start in range(0, n, chunk):
        sl = arr[:, start:start + chunk]
        wl = sl.shape[1]
        s = np.full((m, chunk), router.PAD_SCORE, np.float32)
        i = np.full((m, chunk), PAD_ID, np.int32)
        s[:, :wl] = sl
        i[:, :wl] = np.arange(start, start + wl, dtype=np.int32)[None, :]
        st, wrote = step(st, jnp.asarray(s), jnp.asarray(i))
        wr = np.asarray(wrote)
        for row in range(m):
            admitted[row].append(sl[row][wr[row, :wl]])
    admits = np.asarray(st.admits, np.int64)
    ratio = np.empty(m, np.float64)
    for row in range(m):
        got = np.concatenate(admitted[row]) if admitted[row] else \
            np.empty(0, np.float32)
        top_all = np.sort(arr[row].astype(np.float64))[-k:].sum()
        top_got = np.sort(got.astype(np.float64))[-min(k, got.size):].sum()
        ratio[row] = top_got / top_all if top_all else 1.0
    law = float(expected_admits(np.asarray([n]), k)[0])
    return {
        "k": k, "n": n, "chunk": chunk,
        "ratio": ratio,
        "c": (1.0 - ratio) * math.sqrt(k),
        "admits": admits,
        "expected_admits": law,
        "admit_ratio": admits / max(law, 1e-12),
        "min_ratio": float(ratio.min()),
        "max_c": float(((1.0 - ratio) * math.sqrt(k)).max()),
        "bytes_per_stream": state_bytes_per_stream(st),
        "exact_bytes_per_stream": exact_bytes_per_stream(k),
    }
