"""Device-resident constrained suffix re-solve: the jitted port of
``Replanner._solve_group``'s per-subset boundary optimization.

The host path loops ``shp._tier_subsets`` in Python, building per-subset
candidate grids and drift-conditioned term matrices in NumPy and running
``shp.solve_separable_terms`` — at fleet re-plan scale (hundreds of
drift-flagged tenants between chunks) that host round-trip capped the
``online.resolve_*`` throughput. This module evaluates the same suffix
objective — drift-conditioned write law W(b) = K·ln(1 + ρ(b − n0)/n0),
weighted survivor read mass, hop-priced relocation terms, pinned-boundary
relocation constants — and the same constraint structure (first/last-tier
capacity masks, middle-tier pairwise lower bounds, the exact latency
budget) in one jitted XLA program per (T, constraint-signature,
allow-moves, padded-R) key, reducing with the ``kernels.plan_solve``
solvers (value-pair enumeration / masked minima).

Exactness mirrors ``core.shp_jax``: the host's data-dependent ``np.any``
gates become static jit keys, sums keep the host's order and
association, and first-minimum-wins tie-breaks survive as strict-<
folds (ties between equal-cost tuples may resolve to a different,
equal-cost boundary — see the shp_jax policy note). Always float64
(scoped x64): re-plan decisions feed hysteresis and billing
comparisons, and R is hundreds, not tens of thousands.
"""
from __future__ import annotations

import functools
import itertools

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import jaxcompat, shp, shp_jax

_MOVE_TOL = 1e-6  # == replan._MOVE_TOL


def available(t: int) -> bool:
    return 2 <= t <= shp_jax.MAX_DEVICE_TIERS


def _w_suffix(x, n0, rho, k):
    """Traced ``replan._w_suffix`` (drift-conditioned suffix write law)."""
    x = jnp.maximum(x, n0)
    head = jnp.maximum(jnp.minimum(x, k) - n0, 0.0)
    start = jnp.maximum(n0, k)
    u = start + rho * (jnp.maximum(x, start) - start)
    return head + k * jnp.log(u / start)


def _mass(x, anchor, rho, n):
    """Traced ``replan._mass`` (weighted survivor mass of [0, x))."""
    return (jnp.minimum(x, anchor)
            + rho * (jnp.clip(x, anchor, n) - anchor))


def _reloc_cols(c, b0_j, n0, dens, price_up, price_dn, allow_moves):
    """Traced ``replan._reloc_terms`` on grid ``c`` (M, C). With
    ``allow_moves`` False returns (zeros, blocked-mask) instead of the
    host's +inf fold so the caller can fold it once."""
    delta = jnp.clip(c, 0.0, n0[:, None]) - jnp.clip(b0_j, 0.0, n0)[:, None]
    if not allow_moves:
        return None, jnp.abs(delta) > _MOVE_TOL
    cost = dens[:, None] * jnp.where(delta > 0, delta * price_up[:, None],
                                     -delta * price_dn[:, None])
    return cost, None


def _pinned_reloc(b0, n0, dens, cr, cw, sa, t, allow_moves):
    """Traced ``replan._pinned_reloc_const``."""
    const = jnp.zeros_like(n0)
    moves = jnp.zeros_like(n0)
    for j in range(1, sa[0] + 1):
        cnt = dens * jnp.clip(b0[:, j - 1], 0.0, n0)
        const = const + cnt * (cr[:, j - 1] + cw[:, j])
        moves = moves + cnt
    for j in range(sa[-1] + 1, t):
        cnt = dens * (n0 - jnp.clip(b0[:, j - 1], 0.0, n0))
        const = const + cnt * (cr[:, j] + cw[:, j - 1])
        moves = moves + cnt
    if not allow_moves:
        const = jnp.where(moves > _MOVE_TOL, jnp.inf, 0.0)
    return const


def _subset_candidate_cols(sa, cw_obj, lin, kf, nf, lo, hi, constrained,
                           capfin, slo_any, cap, lat, slo):
    """``BoundaryObjective.candidates``'s columns for the suffix
    objective (cw_s = ρ·cw, lin_s = drift-weighted read coefficients),
    under the host's any-finite gates — unsorted column list."""
    ts = len(sa)
    cols = [lo, jnp.minimum(kf, nf), hi]
    cols += shp_jax.crossover_cols(cw_obj, lin, kf, lo, hi)
    if constrained:
        for j in sa:
            if not capfin[j]:
                continue
            cap_j = cap[:, j]
            fin = jnp.isfinite(cap_j)
            cols.append(jnp.clip(jnp.where(fin, cap_j, 0.0), lo, hi))
            tight = nf * (1.0 - cap_j / kf)
            cols.append(jnp.clip(jnp.where(fin, tight, 0.0), lo, hi))
        if slo_any:
            for s, u in itertools.combinations(range(ts), 2):
                dl = lat[:, sa[s]] - lat[:, sa[u]]
                b = nf * (slo - lat[:, sa[u]]) / dl
                b = jnp.where(jnp.isfinite(b), b, 0.0)
                cols.append(jnp.clip(b, lo, hi))
        for i in range(1, ts - 1):
            if capfin[sa[i]]:
                cols += shp_jax.mid_cap_cols(
                    cw_obj[:, i - 1], cw_obj[:, i], cw_obj[:, i + 1],
                    lin[:, i - 1], lin[:, i], lin[:, i + 1],
                    cap[:, sa[i]], kf, lo, hi)
    return cols


def _solve_impl(cw, cr, cs, n, k, rpw, cap, lat, slo, n0, rho, b0, *, t,
                constrained, capfin, slo_any, allow_moves):
    from repro.kernels.plan_solve import ops as solve_ops
    from repro.kernels.plan_solve import ref as solve_ref
    m = cw.shape[0]
    dtype = cw.dtype
    kf, nf = k, n
    s_n = n0 + rho * (n - n0)
    dens = jnp.minimum(n0, k) / jnp.maximum(n0, 1.0)
    start = jnp.maximum(n0, k)
    w_n = _w_suffix(n, n0, rho, k)
    lo = jnp.zeros_like(nf)
    best_val = jnp.full((m,), jnp.inf, dtype)
    best_bounds = [jnp.zeros((m,), dtype) for _ in range(t - 1)]
    for sa in shp._tier_subsets(t):
        ts = len(sa)
        sl = list(sa)
        lin = (rpw * k * rho / s_n)[:, None] * cr[:, sl]
        cw_obj = rho[:, None] * cw[:, sl]
        cap_s = cap[:, sl] if constrained else None
        lat_s = lat[:, sl] if constrained else None
        ok = shp_jax.subset_feasible(m, ts, False, kf, nf, cap_s, lat_s,
                                     slo)
        reloc_const = _pinned_reloc(b0, n0, dens, cr, cw, sa, t,
                                    allow_moves)
        const = (w_n * cw[:, sa[-1]] + rpw * k * cr[:, sa[-1]]
                 + reloc_const + k * jnp.max(cs[:, sl], axis=1))
        if ts == 1:
            total = jnp.where(ok, const, jnp.inf)
            bounds_cols = [nf if j >= sa[0] else jnp.zeros((m,), dtype)
                           for j in range(t - 1)]
        else:
            cols = _subset_candidate_cols(sa, cw_obj, lin, kf, nf, lo, nf,
                                          constrained, capfin, slo_any,
                                          cap, lat, slo)
            ustars = shp_jax.crossover_cols(cw[:, sl], lin, rho * k, lo,
                                            jnp.full_like(nf, jnp.inf))
            cols.append(jnp.clip(n0, 0.0, nf))
            cols += [jnp.clip(start + (u - start) / rho, 0.0, nf)
                     for u in ustars]
            cols += [jnp.clip(b0[:, j], 0.0, nf) for j in range(t - 1)]
            c = jnp.stack(cols, axis=1)
            sub_con = (constrained
                       and (any(capfin[j] for j in sa) or slo_any))

            def build_fs(grid):
                """The drift-conditioned per-step suffix terms on one
                candidate grid: write law + survivor mass + hop-priced
                relocation columns, capacity masks folded as +inf."""
                out = []
                for s in range(1, ts):
                    u, v = sa[s - 1], sa[s]
                    f = ((cw[:, u] - cw[:, v])[:, None]
                         * _w_suffix(grid, n0[:, None], rho[:, None],
                                     k[:, None])
                         + ((cr[:, u] - cr[:, v]) * rpw * k / s_n)[:, None]
                         * _mass(grid, n0[:, None], rho[:, None],
                                 n[:, None]))
                    blocked = None
                    for j in range(u + 1, v + 1):
                        cost, blk = _reloc_cols(
                            grid, b0[:, j - 1], n0, dens,
                            cr[:, j] + cw[:, j - 1],
                            cr[:, j - 1] + cw[:, j], allow_moves)
                        if cost is not None:
                            f = f + cost
                        if blk is not None:
                            blocked = blk if blocked is None else \
                                blocked | blk
                    f = shp_jax._fold_cap_masks(f, grid, s, ts, sa,
                                                sub_con, capfin, cap, kf,
                                                nf)
                    if blocked is not None:
                        f = jnp.where(blocked, jnp.inf, f)
                    out.append(f)
                return out

            fs = build_fs(c)
            kw = {}
            if sub_con and slo_any:
                cmax = jnp.max(c, axis=1)
                alphas, scale = [], None
                for j in range(1, ts):
                    al = (lat[:, sa[j - 1]] - lat[:, sa[j]]) / nf
                    alphas.append(al)
                    sc = jnp.abs(cmax * al)
                    scale = sc if scale is None else scale + sc
                rhs = slo - lat[:, sa[-1]]
                kw = dict(alpha=alphas, rhs=rhs,
                          atol=1e-9 * (jnp.abs(rhs) + scale) + 1e-15)
            if ts == 2:
                interior, bvec = solve_ref.single_arr(fs[0], c, **kw)
            elif ts == 3:
                if sub_con and capfin[sa[1]]:
                    kw.update(kf=kf, cap_m=cap[:, sa[1]])
                interior, bvec = solve_ref.tri_arr(fs[0], fs[1], c, **kw)
            else:  # ts == 4: gathered enumeration on a sorted grid
                c_s = shp_jax.sort_network(
                    [[c[:, i] for i in range(c.shape[1])]])[0]
                fs4 = jnp.stack(build_fs(c_s), 1)[:, None]
                kw4 = {}
                if sub_con and any(capfin[sa[i]] for i in range(1, ts - 1)):
                    kw4["pair_caps"] = [
                        cap[:, sa[j]][:, None]
                        if capfin[sa[j]] else None
                        for j in range(1, ts - 1)]
                    kw4["kf"] = kf
                if kw:
                    kw4.update(alpha=jnp.stack(kw["alpha"], 1)[:, None],
                               rhs=kw["rhs"][:, None],
                               atol=kw["atol"][:, None])
                interior, _, selm = solve_ref.enum_solve(
                    fs4, (jnp.zeros((m, 1), dtype),),
                    solve_ops.monotone_combos(c_s.shape[1], ts - 1),
                    cand=c_s[:, None], **kw4)
                bvec = [solve_ref.pick_col(c_s, selm[:, j])
                        for j in range(ts - 1)]
            total = jnp.where(ok, interior + const, jnp.inf)
            bounds_cols = shp_jax._subset_bounds_cols(sa, t, bvec, nf)
        upd = total < best_val
        best_val = jnp.where(upd, total, best_val)
        best_bounds = [jnp.where(upd, bc, bb)
                       for bc, bb in zip(bounds_cols, best_bounds)]
    # traced mirror of ``replan.suffix_cost`` at the old boundaries —
    # the like-for-like comparison side of the hysteresis decision
    edges = [jnp.zeros_like(nf)] \
        + [b0[:, j] for j in range(t - 1)] + [nf]
    writes = jnp.zeros_like(nf)
    reads = jnp.zeros_like(nf)
    storage = jnp.full_like(nf, -jnp.inf)
    for j in range(t):
        wj = (_w_suffix(edges[j + 1], n0, rho, k)
              - _w_suffix(edges[j], n0, rho, k))
        writes = writes + wj * cw[:, j]
        mj = (_mass(edges[j + 1], n0, rho, n)
              - _mass(edges[j], n0, rho, n))
        reads = reads + mj * cr[:, j]
        used = edges[j + 1] - edges[j] > 0
        storage = jnp.maximum(storage, jnp.where(used, cs[:, j], -jnp.inf))
    cost_old = writes + reads * (rpw * k / s_n) + k * storage
    return best_val, jnp.stack(best_bounds, axis=1), cost_old


@functools.partial(jax.jit,
                   static_argnames=("t", "constrained", "capfin",
                                    "slo_any", "allow_moves"))
def _solve_jit(cw, cr, cs, n, k, rpw, cap, lat, slo, n0, rho, b0, *, t,
               constrained, capfin, slo_any, allow_moves):
    return _solve_impl(cw, cr, cs, n, k, rpw, cap, lat, slo, n0, rho, b0,
                       t=t, constrained=constrained, capfin=capfin,
                       slo_any=slo_any, allow_moves=allow_moves)


@functools.lru_cache(maxsize=None)
def _solve_sharded_fn(mesh, t, constrained, capfin, slo_any, allow_moves):
    """Jitted ``shard_map`` of ``_solve_impl`` over the fleet axis: each
    shard runs the identical single-device suffix re-solve on its rows
    (no collectives), so sharded totals/bounds are bit-identical."""
    from repro.parallel import fleet as fleet_mod
    fn = functools.partial(_solve_impl, t=t, constrained=constrained,
                           capfin=capfin, slo_any=slo_any,
                           allow_moves=allow_moves)
    spec = fleet_mod.row_spec()
    return jax.jit(jaxcompat.shard_map(
        fn, mesh=mesh, in_specs=(spec,) * 12,
        out_specs=(spec, spec, spec)))


def solve_group(cw, cr, cs, n, k, rpw, cap, lat, slo, n0, rho, b0, *,
                allow_moves=True):
    """Device re-solve of one uniform-tier-count drift-flagged group.
    Inputs mirror ``Replanner._solve_group``'s stacked arrays; returns
    (total (R,), bounds (R, t-1), cost_old (R,)) with +inf totals where
    no feasible plan exists. R is padded to a power of two to bound the jit cache."""
    r, t = cw.shape
    from repro.core import constraints as constraints_mod
    constrained = not constraints_mod.trivial(np.asarray(cap),
                                              np.asarray(slo))
    capfin = tuple(bool(np.any(np.isfinite(np.asarray(cap)[:, j])))
                   for j in range(t))
    slo_any = bool(np.any(np.isfinite(np.asarray(slo))))
    # active fleet mesh: split R across shards, each padded to a
    # power-of-two block (same jit-cache bound, one signature per
    # (mesh, per-shard-R) instead of per total R)
    from repro.obs import jits as obs_jits
    from repro.parallel import fleet as fleet_mod
    mesh = fleet_mod.get_fleet_mesh()
    shards = fleet_mod.n_shards(mesh)
    if shards > 1:
        per = 1 << max(-(-r // shards) - 1, 3).bit_length()
        rp = per * shards
    else:
        rp = 1 << max(r - 1, 3).bit_length()

    def _pad(x):
        x = np.asarray(x, np.float64)
        if rp > r:
            x = np.concatenate(
                [x, np.broadcast_to(x[:1], (rp - r,) + x.shape[1:])])
        return x

    args = [_pad(x) for x in (cw, cr, cs, n, k, rpw, cap, lat, slo, n0,
                              rho, b0)]
    # jit-cache probe (repro.obs.jits): one compiled signature per
    # (T, constraint-signature, padded-R) static key
    with jaxcompat.enable_x64():
        if shards > 1:
            fn = _solve_sharded_fn(mesh, t, constrained, capfin, slo_any,
                                   bool(allow_moves))
            probe = obs_jits.probe("replan_device.solve_sharded")
            key = (obs_jits.mesh_key(mesh), t, constrained, capfin,
                   slo_any, bool(allow_moves), per)
            sh = fleet_mod.row_sharding(mesh)
            dev = [jax.device_put(a, sh) for a in args]
            total, bounds, cost_old = probe.track(fn, *dev, key=key)
        else:
            probe = obs_jits.probe("replan_device.solve")
            key = (t, constrained, capfin, slo_any, bool(allow_moves), rp)
            total, bounds, cost_old = probe.track(
                _solve_jit, *args, key=key, t=t, constrained=constrained,
                capfin=capfin, slo_any=slo_any,
                allow_moves=bool(allow_moves))
        total = np.asarray(total, np.float64)[:r]
        bounds = np.asarray(bounds, np.float64)[:r]
        cost_old = np.asarray(cost_old, np.float64)[:r]
    return total, bounds, cost_old
