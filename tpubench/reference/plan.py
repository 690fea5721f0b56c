"""Plain reference of the SHP tier plan of a T-tier tenant, in NumPy
float64 (arXiv:1901.07335, generalized to T tiers).

A tenant streams n documents and keeps the top K; its documents are
placed on tiers by position: boundaries 0 <= b_1 <= ... <= b_{T-1} <= n
send position i to tier t iff b_t <= i < b_{t+1}. The expected number of
writes among the first b positions is W(b) = b for b <= K and
K (1 + ln(b / K)) beyond. Per document, tier t costs cw_t to write, cr_t
to read and cs_t to rent for the window. A plan uses a subset of the
tiers, in order:

* without migration it costs, over its tiers' segments [e_t, e_{t+1}),
  sum_t cw_t (W(e_{t+1}) - W(e_t)) + (K / n) cr_t (e_{t+1} - e_t), plus the
  rental bound K max(cs_t) over its tiers;
* with migration (a cascade ending on the last tier, boundaries in
  [K, n)) the read term becomes the time-split rental
  (K / n) cs_t (e_{t+1} - e_t), and each hop between consecutive tiers
  u -> v of the subset costs K (cr_u + cw_v).

The plan is the cheapest over both families and every subset. Boundaries
are searched over a grid of 0 (K for a cascade), K, n (just below n for a
cascade) and the crossover K (cw_s - cw_t) / (lin_t - lin_s) of every
tier pair of the subset, where the continuous optimum lies.

``precision="bfloat16"`` rounds the per-document costs and every
intermediate to bfloat16: the control.
"""
from __future__ import annotations

import itertools

import numpy as np

def caster(precision: str):
    if precision == "float64":
        return lambda x: np.asarray(x, np.float64)
    if precision == "bfloat16":
        import ml_dtypes
        return lambda x: np.asarray(x, np.float64).astype(
            ml_dtypes.bfloat16).astype(np.float64)
    raise ValueError(f"unknown precision {precision!r}")


def case_costs(case: dict):
    """Per-document (cw, cr, cs), each (2,), of one of the paper's case
    studies (Tables I and II) from its raw prices: tier a is the
    producer's store, tier b the consumer's; a write to b and a read from
    a cross the wire, at the case's transfer rates."""
    g = float(case["doc_mb"]) / 1000.0
    months = float(case["window_days"]) / 30.0
    a, b = case["tier_a"], case["tier_b"]
    cw = np.array([a["put_per_doc"],
                   b["put_per_doc"] + case["xfer_producer_to_b_per_gb"] * g])
    cr = np.array([a["get_per_doc"] + case["xfer_a_to_consumer_per_gb"] * g,
                   b["get_per_doc"]])
    cs = np.array([a["storage_per_gb_month"] * g * months,
                   b["storage_per_gb_month"] * g * months])
    return cw, cr, cs


def fleet_costs(cases, which, k: int):
    """(cw, cr, cs, n, k) of tenants dealt over ``cases`` (``which``: each
    tenant's case index), each case at K = ``k`` with its K/n kept."""
    per = [case_costs(c) for c in cases]
    cw, cr, cs = (np.stack([per[c][j] for c in which]) for j in range(3))
    n = np.array([round(k / float(cases[c]["k_over_n"])) for c in which],
                 np.float64)
    return cw, cr, cs, n, np.full(len(which), float(k))


def w_law(b, k):
    b = np.asarray(b, np.float64)
    return np.where(b <= k, b, k * (1.0 + np.log(np.maximum(b, 1e-300) / k)))


def _segments(bounds, n, k, f):
    m = n.shape[0]
    edges = np.concatenate([np.zeros((m, 1)), np.asarray(bounds, np.float64),
                            n[:, None]], 1)
    return f(np.diff(edges, axis=1)), f(np.diff(w_law(edges, k[:, None]),
                                                axis=1))


def _hops(cr, cw, used):
    """(M,) cost of the hops between consecutive used tiers."""
    fee = np.zeros(cr.shape[0])
    t = cr.shape[1]
    for a, b in itertools.combinations(range(t), 2):
        between = used[:, a + 1:b].any(axis=1)
        fee = fee + np.where(used[:, a] & used[:, b] & ~between,
                             cr[:, a] + cw[:, b], 0.0)
    return fee


def _total(cw, cr, cs, n, k, bounds, used, mig, f):
    width, dw = _segments(bounds, n, k, f)
    lin = f((k / n)[:, None] * (cs if mig else cr))
    body = f(np.where(used, f(cw * dw + lin * width), 0.0).sum(1))
    if mig:
        return f(body + k * f(_hops(cr, cw, used)))
    return f(body + k * np.where(used, cs, -np.inf).max(1))


def cost(cw, cr, cs, n, k, bounds, migrate):
    """(M,) float64 expected cost of given plans (``bounds`` (M, T-1),
    ``migrate`` (M,)), a plan using the tiers its boundaries give room."""
    f = caster("float64")
    n = np.asarray(n, np.float64)
    k = np.asarray(k, np.float64)
    width, _ = _segments(bounds, n, k, f)
    used = width > 0
    mig = np.asarray(migrate, bool)
    return np.where(mig, _total(cw, cr, cs, n, k, bounds, used, True, f),
                    _total(cw, cr, cs, n, k, bounds, used, False, f))


def plan(cw, cr, cs, n, k, precision: str = "float64"):
    """Optimal plans of M tenants: (total (M,), bounds (M, T-1), migrate
    (M,)), every monotone boundary vector on the grid tried."""
    f = caster(precision)
    cw, cr, cs = f(cw), f(cr), f(cs)
    n = np.asarray(n, np.float64)
    k = np.asarray(k, np.float64)
    m, t = cw.shape
    best = np.full(m, np.inf)
    best_b = np.zeros((m, t - 1))
    best_mig = np.zeros(m, bool)
    families = [(sub, False) for size in range(1, t + 1)
                for sub in itertools.combinations(range(t), size)]
    families += [(sub + (t - 1,), True) for size in range(1, t)
                 for sub in itertools.combinations(range(t - 1), size)]
    for sub, mig in families:
        sa = list(sub)
        in_sub = np.zeros((m, t), bool)
        in_sub[:, sa] = True
        lin = f((k / n)[:, None] * (cs if mig else cr)[:, sa])
        lo = np.minimum(k, n) if mig else np.zeros(m)
        hi = np.nextafter(n, 0.0) if mig else n
        cols = [lo, np.minimum(k, hi), hi]
        for s, u in itertools.combinations(range(len(sa)), 2):
            with np.errstate(divide="ignore", invalid="ignore"):
                b = k * (cw[:, sa[s]] - cw[:, sa[u]]) / (lin[:, u] - lin[:, s])
            cols.append(np.clip(np.where(np.isfinite(b), b, 0.0), lo, hi))
        grid = np.stack(cols, 1)
        nb = len(sa) - 1
        for combo in itertools.combinations_with_replacement(
                range(grid.shape[1]), nb):
            b_sub = np.sort(grid[:, list(combo)], axis=1)
            edges = np.concatenate([np.zeros((m, 1)), b_sub, n[:, None]], 1)
            width = np.zeros((m, t))
            width[:, sa] = np.diff(edges, axis=1)
            bounds = np.cumsum(width, axis=1)[:, :-1]
            total = _total(cw, cr, cs, n, k, bounds, in_sub, mig, f)
            upd = total < best
            best = np.where(upd, total, best)
            best_b = np.where(upd[:, None], bounds, best_b)
            best_mig = np.where(upd, mig, best_mig)
    return best, best_b, best_mig


def plan_precision(precision: str) -> str:
    """The planner's reference is float64 where a cell's reference is
    float32; the control plans a step lower (bfloat16), like the rest of
    its reference."""
    return "float64" if precision == "float32" else precision


def regret(costs, optimum, bounds, migrate) -> float:
    """Widest relative excess of the true cost of plans (``bounds``,
    ``migrate``) over the reference ``optimum``."""
    true = cost(*costs, bounds, migrate)
    return float(np.max((true - optimum) / np.abs(optimum)))
