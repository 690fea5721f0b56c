"""Device-resident batched N-tier constrained planner: the jit/vmap port
of ``core.shp``'s candidate-grid solver.

``shp.plan_ntier_arrays_numpy`` minimizes the separable boundary
objective per tier subset with host-side NumPy: a Python loop over the
2^T subsets, per-subset candidate grids, and a chunked ``itertools``
enumeration for the constrained joint solve. This module materializes
the same finite candidate structure as dense per-subset tensors and
evaluates objective terms, feasibility masks, and the joint argmin in
one jitted XLA program per (T, constraint-signature) key. The heavy
constrained reduction is ``kernels.plan_solve``: a Pallas kernel
(compiled on TPU, 2-D grid over M × subset blocks) or its jnp
reference (fused by XLA elsewhere); unconstrained subsets run the same
monotone running-minimum DP the host uses.

Structure of the port (all decisions the host makes by looking at the
data become *static jit keys* computed on the host before tracing):

* ``capfin`` (per-tier any-finite-capacity) and ``slo_any`` replicate
  the ``np.any``-gates of ``BoundaryObjective.candidates`` /
  ``pair_lower_bound`` / ``budget_deltas``, so the device candidate
  grid has exactly the host's columns and the DP-vs-enumeration
  dispatch is decided per subset exactly as the host decides it.
* candidate columns are *pooled per family*: a crossover, capacity
  corner, or SLO-tight point depends only on the global tier pair, so
  W(b) — the expensive log — is evaluated once per distinct column and
  carried through a vectorized odd-even sorting network into each
  subset's sorted grid (XLA's comparator sort is serial on CPU).
* consecutive subsets with one structural signature stack on an S axis
  and reduce in a single fused pass, preserving the host's
  first-minimum-wins precedence (strict-< running minima in subset
  order: no-migration subsets ascending by size, then cascades).

Float64 / x64 policy (documented in the README): the solver computes
in float64 via the scoped ``jaxcompat.enable_x64`` context
(CPU default), matching the NumPy oracle to a few ulps — the
residual divergence is transcendental (``log``) codegen and XLA fma
contraction, bounded by ~1e-12 relative on totals; the property tests
pin this. On TPU (or with ``precision="float32"``) the solver runs
float32 — Pallas TPU has no f64 — and matches the oracle only to
float32 tolerance (near-ties may pick a different, equal-cost plan).
"""
from __future__ import annotations

import functools
import itertools

import numpy as np

import jax
import jax.numpy as jnp

from . import constraints as constraints_mod
from . import jaxcompat

MAX_DEVICE_TIERS = 4  # the exact joint enumeration (shp._ENUM_MAX_STEPS + 1)
_MIN_PAD = 8  # M is padded to a power of two >= this (bounds jit cache)
_TOL = 1.0 + 1e-12

# Shipped defaults (see the module docstring's float64/x64 policy).
# Unconstrained solves default to float32: measured against the f64
# oracle, the f32 plans are optimal to ~1e-8 relative (only the
# *reported* totals carry float32 accuracy, ~1e-4) and the solve is
# memory-bound, so halving the traffic matters. Constrained solves
# default to float64: float32's catastrophic cancellation in crossover
# candidates near binding capacities/SLOs mis-places plans by up to
# tens of percent and breaks the 1e-9 occupancy-tolerance contracts, so
# f32 is opt-in there (and the TPU default, where Pallas has no f64).
DEFAULT_PRECISION_UNCONSTRAINED = "float32"
DEFAULT_PRECISION_CONSTRAINED = "float64"
_WORKERS = 2  # chunk-parallel host threads (each core streams its own L2)
_POOL = None


def _executor():
    global _POOL
    if _POOL is None:
        from concurrent.futures import ThreadPoolExecutor
        _POOL = ThreadPoolExecutor(_WORKERS)
    return _POOL


class DeviceSolverUnavailable(RuntimeError):
    """Raised when the device solver cannot take this problem (a
    hierarchy deeper than the exact enumeration supports) — the caller
    falls back to the NumPy oracle."""


@functools.lru_cache(maxsize=None)
def _groups(t: int):
    """Subset groups in the host solver's precedence order. Each entry is
    (interior, ts, subsets): the no-migration subsets ascending by size,
    then the migration cascades (all ending at tier t-1)."""
    nm = tuple((False, ts, tuple(itertools.combinations(range(t), ts)))
               for ts in range(1, t + 1))
    mg = tuple((True, size + 1,
                tuple(s + (t - 1,)
                      for s in itertools.combinations(range(t - 1), size)))
               for size in range(1, t))
    return nm + mg


@functools.lru_cache(maxsize=None)
def _mid_triples(t: int):
    """Distinct (prev, mid, next) consecutive-tier triples across the
    no-migration subsets — the middle-capacity stationary columns are
    the only candidate columns owned by a triple rather than a pair."""
    seen, out = set(), []
    for interior, ts, subs in _groups(t):
        if interior or ts < 3:
            continue
        for sa in subs:
            for i in range(1, ts - 1):
                tri = (sa[i - 1], sa[i], sa[i + 1])
                if tri not in seen:
                    seen.add(tri)
                    out.append(tri)
    return tuple(out)


# ---------------------------------------------------------------------------
# Traced mirrors of BoundaryObjective's candidate/term/feasibility laws
# ---------------------------------------------------------------------------

def w_approx(b, k):
    """Traced ``shp._w_approx``: W(b) = b below K, K(1 + ln(b/K)) above."""
    safe = jnp.maximum(b, jnp.finfo(b.dtype).tiny)
    return jnp.where(b <= k, b, k * (1.0 + jnp.log(safe / k)))


@functools.lru_cache(maxsize=None)
def _batcher_pairs(n: int):
    """Batcher odd-even mergesort comparator network for n columns
    (virtual +inf tail elements filtered out — they never swap down, so
    dropping their comparators leaves the first n sorted)."""
    if n < 2:
        return ()
    p2 = 1 << (n - 1).bit_length()
    pairs = []
    p = 1
    while p < p2:
        k = p
        while k >= 1:
            for j in range(k % p, p2 - k, 2 * k):
                for i in range(0, min(k, p2 - j - k)):
                    if (i + j) // (p * 2) == (i + j + k) // (p * 2):
                        pairs.append((i + j, i + j + k))
            k //= 2
        p *= 2
    return tuple((a, b) for a, b in pairs if b < n)


def sort_network(col_lists):
    """Sort candidate columns ascending by the first list's values via a
    Batcher odd-even merge network, applying the same exchanges to every
    companion list, and stack the results into (M, C) grids — XLA's
    comparator sort is serial on CPU and dominated the solve; the
    network's vectorized selects fuse, and the sorted values are the
    identical multiset (no NaNs by construction)."""
    lists = [list(cols) for cols in col_lists]
    keys = lists[0]
    for a, b in _batcher_pairs(len(keys)):
        keep = keys[a] <= keys[b]
        for cols in lists:
            x, y = cols[a], cols[b]
            cols[a] = jnp.where(keep, x, y)
            cols[b] = jnp.where(keep, y, x)
    return [jnp.stack(cols, axis=1) for cols in lists]


def crossover_cols(cw_s, lin_s, kf, lo, hi):
    """Traced ``shp._crossover_candidates``: the eq. 17/21-style pairwise
    stationary points, one column per tier pair, clipped into [lo, hi]."""
    out = []
    ts = cw_s.shape[1]
    for s, t in itertools.combinations(range(ts), 2):
        b = kf * (cw_s[:, s] - cw_s[:, t]) / (lin_s[:, t] - lin_s[:, s])
        b = jnp.where(jnp.isfinite(b), b, 0.0)
        out.append(jnp.clip(b, lo, hi))
    return out


def mid_cap_cols(cw_p, cw_m, cw_n, lin_p, lin_m, lin_n, cap_m, kf, lo, hi):
    """Traced ``BoundaryObjective._middle_cap_stationary`` for one
    (prev, mid, next) tier triple: 4 columns (log/mixed branch × the
    γ-image), sanitized to ``lo`` where the capacity curve is inactive."""
    active = jnp.isfinite(cap_m) & (cap_m < kf)
    gamma = 1.0 - cap_m / kf
    dcw_p, dcw_d = cw_p - cw_m, cw_m - cw_n
    dlin_p, dlin_d = lin_p - lin_m, lin_m - lin_n
    b_log = -kf * (dcw_p + dcw_d) / (gamma * dlin_p + dlin_d)
    b_mix = -kf * dcw_d / (gamma * (dcw_p + dlin_p) + dlin_d)
    out = []
    for b in (b_log, b_mix):
        b = jnp.where(active & jnp.isfinite(b) & (b > 0), b, 0.0)
        out.append(jnp.clip(b, lo, hi))
        out.append(jnp.clip(b * jnp.where(active, gamma, 0.0), lo, hi))
    return out


def subset_feasible(m, ts, interior, kf, nf, cap_s, lat_s, slo):
    """Traced ``BoundaryObjective.subset_feasible``."""
    if cap_s is None:
        return jnp.ones((m,), bool)
    kmin = jnp.minimum(kf, nf)
    if ts == 1:
        return (kmin <= cap_s[:, 0] * _TOL) & (lat_s[:, 0] <= slo * _TOL)
    if interior:
        return (jnp.all(cap_s * _TOL >= kmin[:, None], axis=1)
                & (lat_s[:, -1] <= slo * _TOL))
    return jnp.ones((m,), bool)


# ---------------------------------------------------------------------------
# Per-family candidate pools
# ---------------------------------------------------------------------------

def _build_pool(t, interior, constrained, capfin, slo_any, cw, lin, cap,
                lat, slo, kf, nf, lo, hi):
    """One family's pooled candidate columns + their W values.

    Every candidate column the host generates per subset is owned by a
    global tier pair/tier/triple, so each distinct column — and the
    expensive W(log) on it — is computed once. Returns (pool (M, P),
    w_pool (M, P), {key: column index})."""
    cols, key_idx = [], {}

    def add(key, col):
        key_idx[key] = len(cols)
        cols.append(col)

    add(("b", 0), lo)
    add(("b", 1), jnp.minimum(kf, nf))
    add(("b", 2), hi)
    for u, v in itertools.combinations(range(t), 2):
        b = kf * (cw[:, u] - cw[:, v]) / (lin[:, v] - lin[:, u])
        b = jnp.where(jnp.isfinite(b), b, 0.0)
        add(("x", u, v), jnp.clip(b, lo, hi))
    if constrained:
        for j in range(t):
            if not capfin[j]:
                continue
            cap_j = cap[:, j]
            fin = jnp.isfinite(cap_j)
            add(("cap", j, 0), jnp.clip(jnp.where(fin, cap_j, 0.0), lo, hi))
            tight = nf * (1.0 - cap_j / kf)
            add(("cap", j, 1), jnp.clip(jnp.where(fin, tight, 0.0), lo, hi))
        if not interior and slo_any:
            for u, v in itertools.combinations(range(t), 2):
                dl = lat[:, u] - lat[:, v]
                b = nf * (slo - lat[:, v]) / dl
                b = jnp.where(jnp.isfinite(b), b, 0.0)
                add(("slo", u, v), jnp.clip(b, lo, hi))
        if not interior:
            for (p, md, nx) in _mid_triples(t):
                if not capfin[md]:
                    continue
                mids = mid_cap_cols(cw[:, p], cw[:, md], cw[:, nx],
                                    lin[:, p], lin[:, md], lin[:, nx],
                                    cap[:, md], kf, lo, hi)
                for q, col in enumerate(mids):
                    add(("mid", p, md, nx, q), col)
    return cols, [w_approx(col, kf) for col in cols], key_idx


def _subset_keys(sa, interior, constrained, capfin, slo_any):
    """The pool columns of one subset's candidate grid — the same
    columns, under the same any-finite gates, the host appends in
    ``BoundaryObjective.candidates``."""
    ts = len(sa)
    keys = [("b", 0), ("b", 1), ("b", 2)]
    keys += [("x", sa[s], sa[t])
             for s, t in itertools.combinations(range(ts), 2)]
    if constrained:
        for j in sa:
            if capfin[j]:
                keys += [("cap", j, 0), ("cap", j, 1)]
        if not interior and slo_any:
            keys += [("slo", sa[s], sa[t])
                     for s, t in itertools.combinations(range(ts), 2)]
        if not interior:
            for i in range(1, ts - 1):
                if capfin[sa[i]]:
                    keys += [("mid", sa[i - 1], sa[i], sa[i + 1], q)
                             for q in range(4)]
    return keys


# ---------------------------------------------------------------------------
# Group assembly + reduction
# ---------------------------------------------------------------------------

def decode_bounds(s_idx, sel, cand_stack, subs, nf, t):
    """Winning (subset row, candidate tuple) -> (M, t-1) full-topology
    boundary vectors: select the winner's grid, gather the boundary
    values, place the widths on the subset's real tier columns, rebuild
    by cumulative sum — the host's edges→widths→cumsum construction.
    Subset selection and width placement are static select chains (S
    and T are tiny; XLA CPU scatter/gather lower to scalar loops)."""
    m = s_idx.shape[0]
    dtype = cand_stack.dtype
    cand_sel = cand_stack[:, 0]
    for i in range(1, len(subs)):
        cand_sel = jnp.where((s_idx == i)[:, None], cand_stack[:, i],
                             cand_sel)
    bvec = jnp.take_along_axis(cand_sel, sel, axis=1)  # (M, J)
    edges = jnp.concatenate(
        [jnp.zeros((m, 1), dtype), bvec, nf[:, None]], axis=1)
    widths = jnp.diff(edges, axis=1)  # (M, ts)
    zero = jnp.zeros((m,), dtype)
    bounds = None
    for i, sa in enumerate(subs):
        wfull = [zero] * t
        for j, tier in enumerate(sa):
            wfull[tier] = wfull[tier] + widths[:, j]
        acc, cum = zero, []
        for tier in range(t - 1):
            acc = acc + wfull[tier]
            cum.append(acc)
        bi = jnp.stack(cum, axis=1)
        bounds = bi if bounds is None else jnp.where(
            (s_idx == i)[:, None], bi, bounds)
    return bounds


def _fold_cap_masks(f, c, j, ts, sa, sub_con, capfin, cap, kf, nf):
    """Fold the first/last-tier capacity masks into step ``j``'s terms
    as +inf on grid ``c`` — ``BoundaryObjective.terms``'s convention."""
    if sub_con and j == 1 and capfin[sa[0]]:
        ok = jnp.minimum(c, kf[:, None]) <= cap[:, sa[0]][:, None] * _TOL
        f = jnp.where(ok, f, jnp.inf)
    if sub_con and j == ts - 1 and capfin[sa[-1]]:
        occ = jnp.minimum(nf, kf)[:, None] * (1.0 - c / nf[:, None])
        ok = occ <= cap[:, sa[-1]][:, None] * _TOL
        f = jnp.where(ok, f, jnp.inf)
    return f


def _subset_grid(sa, interior, pool, w_pool, key_idx, constrained, capfin,
                 slo_any, cw, lin, cap, lat, slo, kf, nf, fold_masks,
                 sort=True):
    """One subset's candidate grid and per-step term grids ((M, C)
    arrays), masks folded as +inf when ``fold_masks`` (the host's
    ``terms`` convention) or kept as (M, C) bools for the Pallas path,
    plus enum metadata. ``sort=False`` skips the comparator network for
    solvers that enforce monotonicity as a value mask."""
    ts = len(sa)
    idxs = [key_idx[key]
            for key in _subset_keys(sa, interior, constrained, capfin,
                                    slo_any)]
    if sort:
        c, w = sort_network([[pool[i] for i in idxs],
                             [w_pool[i] for i in idxs]])
    else:
        c = jnp.stack([pool[i] for i in idxs], axis=1)
        w = jnp.stack([w_pool[i] for i in idxs], axis=1)
    sub_con = (constrained and not interior
               and (any(capfin[j] for j in sa) or slo_any))
    lb_pattern = tuple(constrained and not interior and capfin[sa[i]]
                       for i in range(1, ts - 1))
    budget = sub_con and slo_any
    mode = "enum" if (any(lb_pattern) or budget) else "dp"
    fs, masks = [], []
    for j in range(1, ts):
        u, v = sa[j - 1], sa[j]
        f = ((cw[:, u] - cw[:, v])[:, None] * w
             + (lin[:, u] - lin[:, v])[:, None] * c)
        mk = None
        if sub_con and j == 1 and capfin[sa[0]]:
            mk = jnp.minimum(c, kf[:, None]) <= cap[:, sa[0]][:, None] * _TOL
        if sub_con and j == ts - 1 and capfin[sa[-1]]:
            occ = jnp.minimum(nf, kf)[:, None] * (1.0 - c / nf[:, None])
            l_ok = occ <= cap[:, sa[-1]][:, None] * _TOL
            mk = l_ok if mk is None else mk & l_ok
        if mk is not None and fold_masks:
            f = jnp.where(mk, f, jnp.inf)
            mk = None
        fs.append(f)
        masks.append(mk)
    out = {"sa": sa, "cand": c, "fs": fs, "masks": masks, "mode": mode,
           "lb_pattern": lb_pattern, "budget": budget}
    if budget:
        cmax = jnp.max(c, axis=1)
        alphas, scale = [], None
        for j in range(1, ts):
            al = (lat[:, sa[j - 1]] - lat[:, sa[j]]) / nf
            alphas.append(al)
            sc = jnp.abs(cmax * al)
            scale = sc if scale is None else scale + sc
        rhs = slo - lat[:, sa[-1]]
        out.update(alpha=alphas, rhs=rhs,
                   atol=1e-9 * (jnp.abs(rhs) + scale) + 1e-15)
    return out


def _subset_bounds_cols(sa, t, bvec_cols, nf):
    """Full-topology boundary columns from one subset's chosen boundary
    values — the host's edges→widths→cumsum, as static column sums."""
    zero = jnp.zeros_like(nf)
    edges = [zero] + list(bvec_cols) + [nf]
    widths = [edges[j + 1] - edges[j] for j in range(len(sa))]
    wfull = [zero] * t
    for j, tier in enumerate(sa):
        wfull[tier] = wfull[tier] + widths[j]
    acc, cum = zero, []
    for tier in range(t - 1):
        acc = acc + wfull[tier]
        cum.append(acc)
    return cum


def _plan_impl(cw, cr, cs, n, k, rpw, cap, lat, slo, *, t, constrained,
               capfin, slo_any, use_pallas):
    from repro.kernels.plan_solve import ops as solve_ops
    from repro.kernels.plan_solve import ref as solve_ref
    m = cw.shape[0]
    dtype = cw.dtype
    kf, nf = k, n
    w_n = w_approx(n, k)
    lin_nm = (rpw * k / n)[:, None] * cr
    lin_mg = (k / n)[:, None] * cs
    pools = {}
    for interior in (False, True):
        lin = lin_mg if interior else lin_nm
        lo = jnp.minimum(kf, nf) if interior else jnp.zeros_like(nf)
        hi = jnp.nextafter(nf, jnp.zeros_like(nf)) if interior else nf
        pools[interior] = _build_pool(
            t, interior, constrained, capfin, slo_any, cw, lin, cap, lat,
            slo, kf, nf, lo, hi) + (lin,)

    # every subset contributes (total, bounds columns, static mig flag);
    # the cross-subset winner is one first-minimum argmin at the end,
    # which preserves the host loop's strict-< precedence because
    # candidates are appended in the host's subset order
    cand_totals, cand_bounds, cand_mig = [], [], []

    def fold(val, bounds_cols, interior):
        cand_totals.append(val)
        cand_bounds.append(bounds_cols)
        cand_mig.append(interior)

    def subset_consts(sa, interior, lin):
        ts = len(sa)
        sl = list(sa)
        cap_s = cap[:, sl] if constrained else None
        lat_s = lat[:, sl] if constrained else None
        ok = subset_feasible(m, ts, interior, kf, nf, cap_s, lat_s, slo)
        a = w_n * (cw[:, -1] if interior else cw[:, sa[-1]])
        b = nf * lin[:, -1] if interior else nf * lin[:, sa[-1]]
        if interior:
            fee = jnp.zeros_like(nf)
            for u, v in zip(sa, sa[1:]):
                fee = fee + cr[:, u] + cw[:, v]
            cc = kf * fee
        else:
            cc = kf * jnp.max(cs[:, sl], axis=1)
        return jnp.where(ok, a, jnp.inf), b, cc

    for interior, ts, subs in _groups(t):
        pool, w_pool, key_idx, lin = pools[interior]
        if ts == 1:
            for sa in subs:
                a, b, cc = subset_consts(sa, interior, lin)
                bounds_cols = [nf if j >= sa[0] else jnp.zeros((m,), dtype)
                               for j in range(t - 1)]
                fold(((a + b) + cc), bounds_cols, interior)
            continue
        if use_pallas:
            _pallas_group(solve_ops, subs, ts, interior, pool, w_pool,
                          key_idx, constrained, capfin, slo_any, cw, lin,
                          cap, lat, slo, kf, nf, m, t, dtype, fold,
                          subset_consts)
            continue
        for sa in subs:
            a, b, cc = subset_consts(sa, interior, lin)
            if ts < 4:
                # exact solve on the subset's own grid, unsorted: J=1 is
                # a plain masked minimum, J=2 enumerates (origin ≤
                # destination) value pairs — both cover the host's DP
                # *and* constrained-enum dispatch outcomes exactly
                sub = _subset_grid(sa, interior, pool, w_pool, key_idx,
                                   constrained, capfin, slo_any, cw, lin,
                                   cap, lat, slo, kf, nf, True, sort=False)
                cand = sub["cand"]
                kw = {}
                if sub["budget"]:
                    kw = dict(alpha=sub["alpha"], rhs=sub["rhs"],
                              atol=sub["atol"])
                if ts == 2:
                    interior_val, bvec = solve_ref.single_arr(
                        sub["fs"][0], cand, **kw)
                else:
                    if sub["lb_pattern"][0]:
                        kw.update(kf=kf, cap_m=cap[:, sa[1]])
                    interior_val, bvec = solve_ref.tri_arr(
                        sub["fs"][0], sub["fs"][1], cand, **kw)
            else:  # ts == 4: sorted grid (DP or gathered enumeration)
                sub = _subset_grid(sa, interior, pool, w_pool, key_idx,
                                   constrained, capfin, slo_any, cw, lin,
                                   cap, lat, slo, kf, nf, True)
                cand = sub["cand"]
                if sub["mode"] == "dp":
                    interior_val, sel = solve_ref.dp_arr(sub["fs"])
                else:
                    fs4 = jnp.stack(sub["fs"], 1)[:, None]
                    kw4 = {}
                    if any(sub["lb_pattern"]):
                        kw4["pair_caps"] = [
                            cap[:, sa[j]][:, None]
                            if sub["lb_pattern"][j - 1] else None
                            for j in range(1, ts - 1)]
                        kw4["kf"] = kf
                    if sub["budget"]:
                        kw4.update(
                            alpha=jnp.stack(sub["alpha"], 1)[:, None],
                            rhs=sub["rhs"][:, None],
                            atol=sub["atol"][:, None])
                    interior_val, _, selm = solve_ref.enum_solve(
                        fs4, (jnp.zeros((m, 1), dtype),),
                        solve_ops.monotone_combos(cand.shape[1], ts - 1),
                        cand=cand[:, None], **kw4)
                    sel = [selm[:, j] for j in range(ts - 1)]
                bvec = [solve_ref.pick_col(cand, sj) for sj in sel]
            total = ((interior_val + a) + b) + cc
            fold(total, _subset_bounds_cols(sa, t, bvec, nf), interior)
    best_val, s_idx = solve_ref.first_argmin(jnp.stack(cand_totals, axis=1))
    best_bounds = []
    for j in range(t - 1):
        col = cand_bounds[0][j]
        for i in range(1, len(cand_bounds)):
            col = jnp.where(s_idx == i, cand_bounds[i][j], col)
        best_bounds.append(col)
    # no-migration subsets all precede the cascades, so the migrate flag
    # is one index compare (gathers are scalar loops on CPU)
    first_mig = (cand_mig.index(True) if True in cand_mig
                 else len(cand_mig))
    best_mig = (s_idx >= first_mig) & jnp.isfinite(best_val)
    return best_val, jnp.stack(best_bounds, axis=1), best_mig


def _pallas_group(solve_ops, subs, ts, interior, pool, w_pool, key_idx,
                  constrained, capfin, slo_any, cw, lin, cap, lat, slo,
                  kf, nf, m, t, dtype, fold, subset_consts):
    """TPU path: stack one (family, size) group's subsets — candidate
    grids padded to the group max by duplicating each subset's lowest
    column (value, term AND mask), which keeps grids sorted and cannot
    introduce a tuple the unpadded grid lacked — and reduce with the
    fused Pallas kernel (2-D grid over M × subset blocks, running
    first-minimum argmin)."""
    entries = []
    for sa in subs:
        sub = _subset_grid(sa, interior, pool, w_pool, key_idx,
                           constrained, capfin, slo_any, cw, lin, cap,
                           lat, slo, kf, nf, False)
        sub["consts"] = subset_consts(sa, interior, lin)
        entries.append(sub)
    cmax = max(e["cand"].shape[1] for e in entries)

    def pad_front(x, npad):
        return jnp.concatenate(
            [jnp.repeat(x[:, :1], npad, axis=1), x], axis=1) if npad else x

    for e in entries:
        npad = cmax - e["cand"].shape[1]
        e["cand"] = pad_front(e["cand"], npad)
        e["fs"] = [pad_front(f, npad) for f in e["fs"]]
        e["masks"] = [None if mk is None else pad_front(mk, npad)
                      for mk in e["masks"]]
    fs = jnp.stack([jnp.stack(e["fs"], 1) for e in entries], 1)
    cand = jnp.stack([e["cand"] for e in entries], 1)
    consts = tuple(jnp.stack([e["consts"][p] for e in entries], 1)
                   for p in range(3))
    kw = {}
    if constrained and not interior:
        if ts > 2 and any(any(e["lb_pattern"]) for e in entries):
            kw["pair_caps"] = [
                jnp.stack([cap[:, e["sa"][j]] if e["lb_pattern"][j - 1]
                           else jnp.full((m,), jnp.inf, dtype)
                           for e in entries], 1)
                for j in range(1, ts - 1)]
            kw["kf"] = kf
        if slo_any:
            kw["alpha"] = jnp.stack(
                [jnp.stack(e["alpha"], 1) for e in entries], 1)
            kw["rhs"] = jnp.stack([e["rhs"] for e in entries], 1)
            kw["atol"] = jnp.stack([e["atol"] for e in entries], 1)
        ones = jnp.ones((m, cmax), bool)
        kw["masks"] = [
            jnp.stack([ones if e["masks"][j] is None else e["masks"][j]
                       for e in entries], 1)
            for j in range(ts - 1)]
    val, s_idx, sel = solve_ops.enum_solve(fs, consts, cand=cand,
                                           use_pallas=True, **kw)
    bounds = decode_bounds(s_idx, sel, cand, [e["sa"] for e in entries],
                           nf, t)
    fold(val, [bounds[:, j] for j in range(t - 1)], interior)


@functools.partial(jax.jit,
                   static_argnames=("t", "constrained", "capfin",
                                    "slo_any", "use_pallas"))
def _plan_jit(cw, cr, cs, n, k, rpw, cap, lat, slo, *, t, constrained,
              capfin, slo_any, use_pallas):
    return _plan_impl(cw, cr, cs, n, k, rpw, cap, lat, slo, t=t,
                      constrained=constrained, capfin=capfin,
                      slo_any=slo_any, use_pallas=use_pallas)


def _pad_pow2(m: int) -> int:
    return 1 << max(m - 1, _MIN_PAD - 1).bit_length()


_CHUNK_M = 8192  # fleet chunk: keeps every (chunk,) intermediate in L2
# — the solve is elementwise over streams, and on CPU the unchunked
# 64k-row program ran ~2× slower purely on cache misses


def plan_ntier_arrays_jax(cw, cr, cs, n, k, rpw, *, cap=None, lat=None,
                          slo=None, force_constrained=False,
                          precision=None, use_pallas=None):
    """Device-resident ``shp.plan_ntier_arrays``: same contract, same
    returns, one jitted program per (T, constraint-signature, padded-M)
    key.

    ``precision``: "float64" (default off-TPU; scoped x64,
    oracle-matching to ~1e-12 relative) or "float32" (TPU default —
    Pallas has no f64). ``use_pallas`` defaults to compiled-TPU only;
    elsewhere the jnp reference reduction runs (one fused XLA program,
    no interpret overhead).

    Raises ``DeviceSolverUnavailable`` for hierarchies the exact joint
    enumeration does not cover (T > 4) — callers fall back to the
    NumPy oracle.
    """
    cw = np.asarray(cw, np.float64)
    m, t = cw.shape
    if not 2 <= t <= MAX_DEVICE_TIERS:
        raise DeviceSolverUnavailable(
            f"device solver covers 2..{MAX_DEVICE_TIERS} tiers, got {t}")
    if m == 0:
        return {"total": np.zeros(0), "bounds": np.zeros((0, t - 1)),
                "migrate": np.zeros(0, bool)}
    constrained = bool(force_constrained
                       or not constraints_mod.trivial(cap, slo))
    cap_h = (np.full((m, t), np.inf) if cap is None
             else np.asarray(cap, np.float64))
    lat_h = np.zeros((m, t)) if lat is None else np.asarray(lat, np.float64)
    slo_h = (np.full(m, np.inf) if slo is None
             else np.asarray(slo, np.float64))
    # the host's np.any data gates, lifted to static jit keys
    capfin = tuple(bool(np.any(np.isfinite(cap_h[:, j]))) for j in range(t))
    slo_any = bool(np.any(np.isfinite(slo_h)))
    on_tpu = jaxcompat.platform() == "tpu"
    if use_pallas is None:
        use_pallas = on_tpu
    if precision is None:
        precision = ("float32" if on_tpu
                     else (DEFAULT_PRECISION_CONSTRAINED if constrained
                           else DEFAULT_PRECISION_UNCONSTRAINED))
    np_dtype = np.float64 if precision == "float64" else np.float32
    chunk = min(_pad_pow2(m), _CHUNK_M)

    args = [np.asarray(x, np_dtype).reshape(m, t) for x in (cw, cr, cs)]
    args += [np.asarray(x, np_dtype).reshape(m) for x in (n, k, rpw)]
    args += [cap_h.astype(np_dtype, copy=False),
             lat_h.astype(np_dtype, copy=False),
             slo_h.astype(np_dtype, copy=False)]

    # active fleet mesh (parallel.fleet): shard the M axis across devices
    # and run the solve per shard — replaces the L2-chunk host thread
    # fan-out below, which stays the single-device fallback
    from repro.parallel import fleet as fleet_mod
    mesh = fleet_mod.get_fleet_mesh()
    if mesh is not None and fleet_mod.n_shards(mesh) > 1:
        return _plan_sharded(args, m, t, mesh, constrained, capfin,
                             slo_any, use_pallas, precision)

    def _chunk_args(lo_i):
        hi_i = min(lo_i + chunk, m)
        part = [a[lo_i:hi_i] for a in args]
        if hi_i - lo_i < chunk:  # pad the tail chunk only (rows ignored)
            part = [np.concatenate(
                [p, np.broadcast_to(p[:1],
                                    (chunk - (hi_i - lo_i),) + p.shape[1:])])
                for p in part]
        return part

    # jit-cache probe (repro.obs.jits): one compiled signature per
    # (T, constraint-signature, padded-M) key — the probe makes compile
    # storms (a signature varying call-to-call) visible as miss counts
    from repro.obs import jits as obs_jits
    _probe = obs_jits.probe("shp_jax.plan")
    _key = (t, constrained, capfin, slo_any, use_pallas, chunk, precision)

    def _solve(lo_i):
        with jaxcompat.enable_x64(precision == "float64"):
            out = _probe.track(_plan_jit, *_chunk_args(lo_i), key=_key,
                               t=t, constrained=constrained, capfin=capfin,
                               slo_any=slo_any, use_pallas=use_pallas)
            return [np.asarray(o) for o in out]

    starts = list(range(0, m, chunk))
    if len(starts) > 1:
        outs = list(_executor().map(_solve, starts))
    else:
        outs = [_solve(starts[0])]
    val, bounds, mig = (np.concatenate([o[i] for o in outs])
                        for i in range(3))
    total = np.asarray(val, np.float64)[:m]
    bounds = np.asarray(bounds, np.float64)[:m]
    mig = np.asarray(mig)[:m]
    feas = np.isfinite(total)
    return {"total": total,
            "bounds": np.where(feas[:, None], bounds, 0.0),
            "migrate": mig & feas}


# ---------------------------------------------------------------------------
# Fleet-mesh dispatch: shard_map the M axis instead of thread fan-out
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _plan_sharded_fn(mesh, t, constrained, capfin, slo_any, use_pallas):
    """One jitted ``shard_map`` of ``_plan_impl`` per (mesh, static-key):
    every input splits row-wise along the fleet axis and each shard runs
    the identical single-device program on its rows — no collectives, so
    sharded plans are bit-identical to the fallback path's."""
    from repro.parallel import fleet as fleet_mod
    fn = functools.partial(_plan_impl, t=t, constrained=constrained,
                           capfin=capfin, slo_any=slo_any,
                           use_pallas=use_pallas)
    spec = fleet_mod.row_spec()
    return jax.jit(jaxcompat.shard_map(
        fn, mesh=mesh, in_specs=(spec,) * 9,
        out_specs=(spec, spec, spec)))


def _plan_sharded(args, m, t, mesh, constrained, capfin, slo_any,
                  use_pallas, precision):
    """Mesh path of ``plan_ntier_arrays_jax``: pad M to shards × a
    power-of-two per-shard block (bounding the jit cache exactly like
    the chunked path), stage the inputs row-sharded, and solve all
    shards in one XLA dispatch."""
    from repro.obs import jits as obs_jits
    from repro.parallel import fleet as fleet_mod
    shards = fleet_mod.n_shards(mesh)
    per = _pad_pow2(-(-m // shards))
    mp = per * shards

    def _padr(a):
        if mp > m:
            a = np.concatenate(
                [a, np.broadcast_to(a[:1], (mp - m,) + a.shape[1:])])
        return a

    fn = _plan_sharded_fn(mesh, t, constrained, capfin, slo_any,
                          use_pallas)
    probe = obs_jits.probe("shp_jax.plan_sharded")
    key = (obs_jits.mesh_key(mesh), t, constrained, capfin, slo_any,
           use_pallas, per, precision)
    sh = fleet_mod.row_sharding(mesh)
    with jaxcompat.enable_x64(precision == "float64"):
        dev = [jax.device_put(_padr(a), sh) for a in args]
        out = probe.track(fn, *dev, key=key)
        val, bounds, mig = (np.asarray(o) for o in out)
    total = np.asarray(val, np.float64)[:m]
    bounds = np.asarray(bounds, np.float64)[:m]
    mig = np.asarray(mig)[:m]
    feas = np.isfinite(total)
    return {"total": total,
            "bounds": np.where(feas[:, None], bounds, 0.0),
            "migrate": mig & feas}
