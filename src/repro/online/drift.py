"""Sequential drift detection for the fleet engine — observed
reservoir-entry counts tested against the analytic top-K entry law.

Under the paper's i.u.d. assumption, a merge that extends a stream's
prefix from ``a`` to ``b`` docs admits a hypergeometric number of new
reservoir entries: the top-``min(b, K)`` of ``b`` exchangeable docs are
uniformly located, so the count of them landing in the last ``b − a``
positions has mean ``min(b,K)·(b−a)/b`` (the batched form of eq. 9/10 —
``shp.expected_cum_writes_batched`` summed per chunk) and the matching
hypergeometric variance. Real streams drift: bursty scoring functions
make entries arrive faster (or slower) than the law predicts.

``DriftEstimator`` maintains, per stream and fully batched as (M,) arrays
inside the jitted engine step:

* a cumulative deviation ``dev = Σ (observed − expected)`` and its
  variance budget ``var = Σ Var`` since the last reset, tested each chunk
  against a Bernstein bound calibrated from half the ``alpha`` budget
  (Bonferroni over ``max_checks`` chunk checkpoints) — the GLR-style
  whole-window test, rigorous for onset at the window start;
* one-sided CUSUM excursions ``S± = max(0, S± ± (observed − expected))``
  with their own variance budgets (reset whenever the excursion touches
  zero), tested against the same Bernstein form from the other half of
  the budget — the Page-style test that keeps its power when the drift
  begins mid-window, because each excursion re-anchors at its running
  argmin instead of diluting against the clean prefix;
* exponentially-windowed recent observed/expected totals, whose ratio is
  the re-planner's rate-multiplier estimate ``rho_hat``.

The whole-window test's false-positive bound is exact up to the negative
association of entry indicators (the Bernoulli-sum tail bound applies
conservatively); the excursion test's data-dependent anchor adds scan
multiplicity the Bernstein slack absorbs in practice. The null property
test asserts the *combined* empirical false-positive rate stays below
``alpha``.

Detection is *latched* (``fired`` stays up until ``reset_where``); the
engine re-plans the flagged streams between chunks and resets them.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class DriftConfig:
    """Static detector configuration (hashable — closed over by the jitted
    engine step)."""

    alpha: float = 0.01  # total false-positive budget per stream-window
    max_checks: int = 1024  # Bonferroni budget: checkpoints at full power
    decay: float = 0.9  # per-chunk decay of the recent-rate window
    rho_min: float = 0.125  # clip range of the rate-multiplier estimate
    rho_max: float = 16.0

    @property
    def bernstein_a(self) -> float:
        """Whole-window test exponent: ln(2·max_checks/(alpha/2)).

        Checkpoints beyond ``max_checks`` keep testing with a
        quadratically decaying per-check budget (exponent grows by
        ``2·ln(checks/max_checks)``), which adds at most ~alpha/2 of
        lifetime false-positive mass instead of going permanently blind
        on long windows."""
        return math.log(4.0 * self.max_checks / self.alpha)

    @property
    def bernstein_a_cusum(self) -> float:
        """Per-side excursion test exponent (alpha/4 each side; same
        decaying extension beyond ``max_checks``)."""
        return math.log(4.0 * self.max_checks / self.alpha)


class DriftState(NamedTuple):
    """Per-stream sequential statistics, one leading (M,) axis."""

    seen: jax.Array  # (M,) f32 — docs observed (the law's prefix length)
    dev: jax.Array  # (M,) f32 — Σ (observed − expected) since reset
    var: jax.Array  # (M,) f32 — Σ chunk variance since reset
    expected: jax.Array  # (M,) f32 — Σ expected entries since reset
    dev_recent: jax.Array  # (M,) f32 — decayed deviation window
    exp_recent: jax.Array  # (M,) f32 — decayed expectation window
    cusum_pos: jax.Array  # (M,) f32 — positive excursion sum
    cusum_pos_var: jax.Array  # (M,) f32 — its variance budget
    cusum_pos_exp: jax.Array  # (M,) f32 — expected entries in excursion
    cusum_pos_seen: jax.Array  # (M,) f32 — docs seen at excursion anchor
    cusum_neg: jax.Array  # (M,) f32
    cusum_neg_var: jax.Array  # (M,) f32
    cusum_neg_exp: jax.Array  # (M,) f32
    cusum_neg_seen: jax.Array  # (M,) f32
    checks: jax.Array  # (M,) i32 — chunk checkpoints consumed
    fired: jax.Array  # (M,) bool — latched detection flag


def init(m: int) -> DriftState:
    z = jnp.zeros((m,), jnp.float32)
    return DriftState(seen=z, dev=z, var=z, expected=z, dev_recent=z,
                      exp_recent=z, cusum_pos=z, cusum_pos_var=z,
                      cusum_pos_exp=z, cusum_pos_seen=z, cusum_neg=z,
                      cusum_neg_var=z, cusum_neg_exp=z, cusum_neg_seen=z,
                      checks=jnp.zeros((m,), jnp.int32),
                      fired=jnp.zeros((m,), bool))


def chunk_law(seen_before, seen_after, k):
    """(mean, var) of the null entry count for a merge extending the
    prefix from ``seen_before`` to ``seen_after`` docs — hypergeometric:
    the top-``min(b,K)`` of b exchangeable docs, sampled by the last
    ``b − a`` positions."""
    a = jnp.asarray(seen_before, jnp.float32)
    b = jnp.asarray(seen_after, jnp.float32)
    kf = jnp.asarray(k, jnp.float32)
    w = b - a
    kc = jnp.minimum(b, kf)
    mean = jnp.where(b > 0, kc * w / jnp.maximum(b, 1.0), 0.0)
    frac = kc / jnp.maximum(b, 1.0)
    var = jnp.where(b > 1,
                    w * frac * (1.0 - frac) * (b - w)
                    / jnp.maximum(b - 1.0, 1.0), 0.0)
    return mean, var


def bernstein_threshold(var, a_const):
    """Deviation bound t with P(|Σ increments| > t) <= 2·exp(−a_const)
    for centered increments bounded by 1 with variance budget ``var``."""
    return a_const / 3.0 + jnp.sqrt(a_const * a_const / 9.0
                                    + 2.0 * a_const * var)


def _budget_overrun(checks, cfg: DriftConfig):
    """Extra threshold exponent past the Bonferroni budget: checkpoints
    j > max_checks spend a per-check budget decaying like
    (max_checks/j)², so testing never stops but the added lifetime
    false-positive mass stays bounded (~alpha/2)."""
    over = jnp.maximum(checks.astype(jnp.float32) / cfg.max_checks, 1.0)
    return 2.0 * jnp.log(over)


@jax.named_scope("drift")
def update(state: DriftState, wrote_count, seen_after,
           k, cfg: DriftConfig, slack: float = 0.0) -> DriftState:
    """One chunk of evidence per stream (jit-friendly, (M,) batched).

    ``wrote_count``: reservoir entries this chunk; ``seen_after``: docs
    observed after the merge; ``k``: per-stream (or scalar) reservoir
    width. Streams that observed nothing this chunk are untouched.

    ``slack`` is the fractional admit-count tolerance of an approximate
    engine backend (``streams.logmem.law_slack`` — the 1−O(1/√K)
    budget): each test's threshold grows by ``slack × expected mass``
    accumulated since its anchor, so the backend's systematic law bias
    is absorbed without loosening the null guarantee (thresholds only
    grow; slack = 0 reproduces the exact-backend test bitwise).
    """
    w = jnp.asarray(wrote_count, jnp.float32)
    b = jnp.asarray(seen_after, jnp.float32)
    active = b > state.seen
    mean, var_c = chunk_law(state.seen, b, k)
    mean = jnp.where(active, mean, 0.0)
    var_c = jnp.where(active, var_c, 0.0)
    d = jnp.where(active, w - mean, 0.0)
    dev = state.dev + d
    var = state.var + var_c
    expected = state.expected + mean
    dev_recent = cfg.decay * state.dev_recent + d
    exp_recent = cfg.decay * state.exp_recent + mean
    cusum_pos = jnp.maximum(0.0, state.cusum_pos + d)
    pos_live = cusum_pos > 0.0
    was_pos = state.cusum_pos > 0.0
    cusum_pos_var = jnp.where(pos_live, state.cusum_pos_var + var_c, 0.0)
    cusum_pos_exp = jnp.where(pos_live, state.cusum_pos_exp + mean, 0.0)
    cusum_pos_seen = jnp.where(
        pos_live, jnp.where(was_pos, state.cusum_pos_seen, state.seen), 0.0)
    cusum_neg = jnp.maximum(0.0, state.cusum_neg - d)
    neg_live = cusum_neg > 0.0
    was_neg = state.cusum_neg > 0.0
    cusum_neg_var = jnp.where(neg_live, state.cusum_neg_var + var_c, 0.0)
    cusum_neg_exp = jnp.where(neg_live, state.cusum_neg_exp + mean, 0.0)
    cusum_neg_seen = jnp.where(
        neg_live, jnp.where(was_neg, state.cusum_neg_seen, state.seen), 0.0)
    checks = state.checks + active.astype(jnp.int32)
    extra = _budget_overrun(checks, cfg)
    hit = (jnp.abs(dev) > bernstein_threshold(var, cfg.bernstein_a + extra)
           + slack * expected) \
        | (cusum_pos > bernstein_threshold(cusum_pos_var,
                                           cfg.bernstein_a_cusum + extra)
           + slack * cusum_pos_exp) \
        | (cusum_neg > bernstein_threshold(cusum_neg_var,
                                           cfg.bernstein_a_cusum + extra)
           + slack * cusum_neg_exp)
    fired = state.fired | (active & hit)
    return DriftState(seen=jnp.where(active, b, state.seen), dev=dev,
                      var=var, expected=expected, dev_recent=dev_recent,
                      exp_recent=exp_recent, cusum_pos=cusum_pos,
                      cusum_pos_var=cusum_pos_var,
                      cusum_pos_exp=cusum_pos_exp,
                      cusum_pos_seen=cusum_pos_seen, cusum_neg=cusum_neg,
                      cusum_neg_var=cusum_neg_var,
                      cusum_neg_exp=cusum_neg_exp,
                      cusum_neg_seen=cusum_neg_seen, checks=checks,
                      fired=fired)


def rho_hat(state: DriftState, cfg: DriftConfig) -> jax.Array:
    """(M,) rate-multiplier estimate for the re-planner.

    The re-planner's suffix laws are parametrized by the *instantaneous*
    observed/expected ratio (the drifted weight cancels out of the
    conditioned write law — see ``replan._w_suffix``), so the primary
    estimate is the short decayed recent window. When that window carries
    too little expected mass to be informative (tiny K, sparse chunks)
    the active CUSUM excursion's average ratio stands in. Clipped to the
    configured range."""
    recent = ((state.exp_recent + state.dev_recent)
              / jnp.maximum(state.exp_recent, 1e-6))
    pos_r = 1.0 + state.cusum_pos / jnp.maximum(state.cusum_pos_exp, 1e-6)
    neg_r = 1.0 - state.cusum_neg / jnp.maximum(state.cusum_neg_exp, 1e-6)
    s_pos = state.cusum_pos / jnp.sqrt(jnp.maximum(state.cusum_pos_var,
                                                   1.0))
    s_neg = state.cusum_neg / jnp.sqrt(jnp.maximum(state.cusum_neg_var,
                                                   1.0))
    exc = jnp.where(s_pos >= s_neg, pos_r, neg_r)
    exc = jnp.where(jnp.maximum(s_pos, s_neg) >= 1.0, exc, 1.0)
    rho = jnp.where(state.exp_recent >= 3.0, recent, exc)
    return jnp.clip(rho, cfg.rho_min, cfg.rho_max)


def anchor_seen(state: DriftState) -> jax.Array:
    """(M,) estimated drift-onset position: the dominant excursion's
    anchor (docs seen when it left zero), falling back to the current
    position when neither excursion carries signal. Diagnostic: the
    suffix laws themselves are anchor-free (the instantaneous ratio is a
    sufficient statistic for the conditioned write law)."""
    s_pos = state.cusum_pos / jnp.sqrt(jnp.maximum(state.cusum_pos_var,
                                                   1.0))
    s_neg = state.cusum_neg / jnp.sqrt(jnp.maximum(state.cusum_neg_var,
                                                   1.0))
    anchor = jnp.where(s_pos >= s_neg, state.cusum_pos_seen,
                       state.cusum_neg_seen)
    return jnp.where(jnp.maximum(s_pos, s_neg) >= 1.0, anchor, state.seen)


@jax.named_scope("drift")
def scores(state: DriftState, cfg: DriftConfig,
           slack: float = 0.0) -> jax.Array:
    """(M,) normalized change score: the largest of the three test
    statistics over its own threshold — >= 1 means the stream has (or
    would have) fired. ``slack`` widens the thresholds exactly as in
    ``update`` (approximate-backend law tolerance)."""
    extra = _budget_overrun(state.checks, cfg)
    whole = jnp.abs(state.dev) / jnp.maximum(
        bernstein_threshold(state.var, cfg.bernstein_a + extra)
        + slack * state.expected, 1e-9)
    pos = state.cusum_pos / jnp.maximum(
        bernstein_threshold(state.cusum_pos_var,
                            cfg.bernstein_a_cusum + extra)
        + slack * state.cusum_pos_exp, 1e-9)
    neg = state.cusum_neg / jnp.maximum(
        bernstein_threshold(state.cusum_neg_var,
                            cfg.bernstein_a_cusum + extra)
        + slack * state.cusum_neg_exp, 1e-9)
    return jnp.maximum(whole, jnp.maximum(pos, neg))


def reset_where(state: DriftState, mask) -> DriftState:
    """Restart the sequential statistics of the masked streams (after a
    re-plan consumed their evidence); ``seen`` is preserved — the law's
    prefix keeps growing."""
    mask = jnp.asarray(mask, bool)
    z = jnp.zeros_like(state.dev)

    def keep(old, fresh):
        return jnp.where(mask, fresh, old)

    return DriftState(
        seen=state.seen, dev=keep(state.dev, z), var=keep(state.var, z),
        expected=keep(state.expected, z),
        dev_recent=keep(state.dev_recent, z),
        exp_recent=keep(state.exp_recent, z),
        cusum_pos=keep(state.cusum_pos, z),
        cusum_pos_var=keep(state.cusum_pos_var, z),
        cusum_pos_exp=keep(state.cusum_pos_exp, z),
        cusum_pos_seen=keep(state.cusum_pos_seen, z),
        cusum_neg=keep(state.cusum_neg, z),
        cusum_neg_var=keep(state.cusum_neg_var, z),
        cusum_neg_exp=keep(state.cusum_neg_exp, z),
        cusum_neg_seen=keep(state.cusum_neg_seen, z),
        checks=keep(state.checks, jnp.zeros_like(state.checks)),
        fired=keep(state.fired, jnp.zeros_like(state.fired)))


class DriftEstimator:
    """Host-side convenience wrapper: owns a ``DriftState`` and a jitted
    update for one (M,) fleet slice (the engine embeds the pure
    ``update`` inside its own multi-bucket step instead)."""

    def __init__(self, m: int, k, cfg: DriftConfig | None = None):
        self.cfg = cfg if cfg is not None else DriftConfig()
        self.k = jnp.asarray(np.broadcast_to(np.asarray(k), (m,)),
                             jnp.float32)
        self.state = init(m)
        self._update = jax.jit(
            lambda st, w, s: update(st, w, s, self.k, self.cfg))

    def observe(self, wrote_count, seen_after) -> np.ndarray:
        """Feed one chunk; returns the (M,) latched detection flags."""
        self.state = self._update(self.state, jnp.asarray(wrote_count),
                                  jnp.asarray(seen_after))
        return np.asarray(self.state.fired)

    def rho_hat(self) -> np.ndarray:
        return np.asarray(rho_hat(self.state, self.cfg))

    def reset(self, mask) -> None:
        self.state = reset_where(self.state, jnp.asarray(mask))
