"""Pure-jnp oracle for the fused plan-solve reduction.

Solvers over one tier subset's sorted candidate grid (M, C). Every
reduction is a running strict-< update over static column slices or
one variadic ``(value, key)`` minimum (``_lexmin``): the minimum and the
key that attains it come out of the same reduction. (Testing equality
against a separately reduced minimum is not safe: XLA may recompute the
operand per consumer with different fma contraction, and then no entry
equals the minimum.)

* ``dp_arr`` — the monotone running-minimum DP
  (``core.shp._solve_unconstrained``): exact when no pairwise lower
  bound or latency budget couples the boundaries.
* ``tri_arr`` — the exact joint J=2 enumeration
  (``core.shp._solve_constrained_enum``): a static loop over the
  destination candidate; each step is a fused masked reduction over
  the origin prefix slice. The middle-tier capacity law and the
  latency budget are evaluated from the candidate values (the host
  computes them on the grid and gathers — same elementwise ops on the
  same bits, so feasible totals agree bitwise).
* ``single_arr`` — the J=1 case, fully vectorized.
* ``enum_solve`` — the gathered tuple enumeration kept for J=3 (4-tier
  constrained solves are test-scale) and as the Pallas kernel's shape
  contract.

All mirror the host arithmetic: per-step values summed in step order,
masks folded as +inf by the caller (``BoundaryObjective.terms``'s
convention), first-minimum-wins tie-breaks in the host's iteration
order. (``tri_arr`` resolves exact ties between equal-cost tuples
destination-major where the host resolves them origin-major; tied
tuples carry bitwise-equal totals.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _lexmin(vals, keys, axis):
    """Minimum of ``vals`` along ``axis`` and, among the entries that
    attain it, the smallest ``key`` — one variadic reduction. NaN values
    lose to every number (rows of NaN only return NaN)."""
    def pick(a, b):
        (va, ka), (vb, kb) = a, b
        take_b = ((vb < va) | ((vb == va) & (kb < ka))
                  | (jnp.isnan(va) & ~jnp.isnan(vb)))
        return jnp.where(take_b, vb, va), jnp.where(take_b, kb, ka)

    big = (jnp.inf if jnp.issubdtype(keys.dtype, jnp.floating)
           else np.iinfo(keys.dtype).max)
    init = (jnp.asarray(jnp.inf, vals.dtype), jnp.asarray(big, keys.dtype))
    return jax.lax.reduce((vals, keys), init, pick, (axis % vals.ndim,))


def first_argmin(x, axis=-1):
    """(min, first index attaining it): first-minimum-wins tie-break.
    NaN-poisoned rows return index 0 with a NaN min, which the callers'
    strict-< folds then discard — the same outcome as the host's
    NaN-discarding comparisons."""
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis % x.ndim)
    vmin, amin = _lexmin(x, iota, axis)
    poisoned = jnp.isnan(x).any(axis=axis)
    return jnp.where(poisoned, jnp.nan, vmin), jnp.where(poisoned, 0, amin)


def pick_col(x, idx):
    """x[:, idx] per row via a one-hot reduce (dynamic gather is a
    scalar loop on CPU). ``x`` (M, C), ``idx`` (M,) int."""
    onehot = idx[:, None] == jnp.arange(x.shape[1], dtype=idx.dtype)
    zero = jnp.zeros((), x.dtype)
    return jnp.sum(jnp.where(onehot, x, zero), axis=1)


def _cummin_with_arg(g):
    """Column-sliced ``shp._cummin_with_arg`` over (M, C): running
    minima and the column where each was first attained (strict-<
    update, first minimum wins)."""
    c = g.shape[1]
    best = g[:, 0]
    barg = jnp.zeros(best.shape, jnp.int32)
    vals, args = [best], [barg]
    for j in range(1, c):
        col = g[:, j]
        upd = col < best
        best = jnp.where(upd, col, best)
        barg = jnp.where(upd, jnp.int32(j), barg)
        vals.append(best)
        args.append(barg)
    return jnp.stack(vals, axis=1), jnp.stack(args, axis=1)


def dp_arr(fs):
    """Monotone DP over per-step term grids ``fs`` (list of J (M, C)).
    Bitwise the host DP: g_j = f_j + cummin(g_{j-1}). Returns
    (interior (M,), sel list of J (M,) int32 candidate indices)."""
    g = fs[0]
    args = []
    for j in range(1, len(fs)):
        vals, arg = _cummin_with_arg(g)
        args.append(arg)
        g = fs[j] + vals
    interior, best_c = first_argmin(g)
    sel_rev = [best_c]
    for arg in reversed(args):
        best_c = pick_col(arg, best_c)
        sel_rev.append(best_c)
    return interior, list(reversed(sel_rev))


def pair_lb_law(cval, cap_m, kf):
    """Traced ``BoundaryObjective.pair_lower_bound`` evaluated at
    candidate values ``cval``."""
    slack = 1.0 - cap_m / jnp.minimum(cval, kf)
    lb = cval * jnp.maximum(0.0, slack)
    return jnp.where(jnp.isfinite(cap_m) & (cval > 0),
                     jnp.nan_to_num(lb, nan=0.0, posinf=0.0), 0.0)


def value_argmin(f, cand):
    """(min of f, boundary value attaining it) over an *unsorted* grid:
    among minimal-cost candidates the smallest boundary value wins —
    exactly the host's first-index tie-break on its value-sorted grid.
    All-inf rows return a +inf minimum and NaN-poisoned rows a NaN one,
    which the callers' strict-< folds discard."""
    vmin, bval = _lexmin(f, cand, 1)
    poisoned = jnp.isnan(f).any(axis=1)
    return (jnp.where(poisoned, jnp.nan, vmin),
            jnp.where(poisoned, jnp.inf, bval))


def single_arr(f0, cand, *, alpha=None, rhs=None, atol=None):
    """Exact J=1 reduction: masked minimum over the (unsorted) candidate
    grid (the budget, when active, is the per-candidate value test
    δ_0 = α_0·value ≤ rhs + atol). Returns (interior (M,), [bval])."""
    if alpha is not None:
        ok = cand * alpha[0][:, None] <= (rhs + atol)[:, None]
        f0 = jnp.where(ok, f0, jnp.inf)
    interior, bval = value_argmin(f0, cand)
    return interior, [bval]


def tri_arr(f0, f1, cand, *, kf=None, cap_m=None, alpha=None, rhs=None,
            atol=None):
    """Exact J=2 enumeration as a static destination loop over (M, C)
    grids — *unsorted* grids welcome: monotonicity (origin value ≤
    destination value) is enforced as a mask, so the value-pair set
    enumerated is identical to the host's index-monotone tuples over
    the sorted grid. Origins are further filtered by the lower-bound
    law (middle-tier capacity ``cap_m``) and the latency budget
    (δ_j = α_j·value, Σδ ≤ rhs + atol). The winner's interior is
    assembled with the same adds as the host: f0 + f1. Returns
    (interior (M,), sel [c0, c1])."""
    c = cand.shape[1]
    budget_cap = (rhs + atol) if alpha is not None else None
    best = jnp.full(f0.shape[:1], jnp.inf, f0.dtype)
    bv0 = jnp.zeros(best.shape, f0.dtype)
    bv1 = jnp.zeros(best.shape, f0.dtype)
    for c1 in range(c):
        c1v = cand[:, c1]
        feas = cand <= c1v[:, None]
        if cap_m is not None:
            lbd = pair_lb_law(c1v, cap_m, kf) * (1 - 1e-12) - 1e-12
            feas = feas & (cand >= lbd[:, None])
        if alpha is not None:
            acc = cand * alpha[0][:, None] + (c1v * alpha[1])[:, None]
            feas = feas & (acc <= budget_cap[:, None])
        # the best origin for this destination: the smallest candidate
        # value attaining the feasible minimum
        m0, v0 = _lexmin(jnp.where(feas, f0, jnp.inf), cand, 1)
        tot = m0 + f1[:, c1]
        upd = tot < best
        best = jnp.where(upd, tot, best)
        bv0 = jnp.where(upd, v0, bv0)
        bv1 = jnp.where(upd, c1v, bv1)
    return best, [bv0, bv1]


def enum_solve(fs, consts, combos, *, cand, kf=None, pair_caps=None,
               alpha=None, rhs=None, atol=None):
    """Gathered exact enumeration over monotone tuples ``combos``
    (G, J) on stacked (M, S, J, C) tensors — the J = 3 path (test-scale
    fleets) and the shape contract shared with the Pallas kernel.
    ``consts`` are ordered (M, S) addends (+inf = infeasible subset).
    Returns (val (M,), s_idx (M,), sel (M, J))."""
    m, s, j_steps, c = fs.shape
    combos = np.asarray(combos)
    g = combos.shape[0]
    idxs = [jnp.asarray(combos[:, j]) for j in range(j_steps)]
    cvals = [jnp.take(cand, idxs[j], axis=2) for j in range(j_steps)]
    tot = None
    for j in range(j_steps):
        gj = jnp.take(fs[:, :, j, :], idxs[j], axis=2)
        tot = gj if tot is None else tot + gj
    bad = None
    if pair_caps is not None:
        for j in range(1, j_steps):
            cap_m = pair_caps[j - 1]
            if cap_m is None:
                continue
            lbd = pair_lb_law(cvals[j], cap_m[:, :, None],
                              kf[:, None, None])
            viol = cvals[j - 1] < lbd * (1 - 1e-12) - 1e-12
            bad = viol if bad is None else bad | viol
    if alpha is not None:
        acc = None
        for j in range(j_steps):
            dj = cvals[j] * alpha[:, :, j][:, :, None]
            acc = dj if acc is None else acc + dj
        over = acc > (rhs + atol)[:, :, None]
        bad = over if bad is None else bad | over
    for cc in consts:
        tot = tot + cc[:, :, None]
    if bad is not None:
        tot = jnp.where(bad, jnp.inf, tot)
    val, idx = first_argmin(tot.reshape(m, s * g))
    s_idx = idx // g
    sel = jnp.asarray(combos, jnp.int32)[idx % g]
    return val, s_idx, sel
