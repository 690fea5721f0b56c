"""Million-stream sharded serving demo: plan, ingest, finalize one
top-K retention window for 1M tenant streams with the fleet axis
shard_map-ped across devices.

Phases (on every local device — a TPU host's chips, or a CPU split
into ``--devices`` virtual devices, so no hardware is needed):

1. **Plan** — one sharded ``core.shp_jax`` candidate-grid solve over all
   M streams' 3-tier cost arrays, then cross-shard water-filling
   (``streams.planner.waterfill`` → psum bisection) of a fleet-shared
   hot-tier budget, and a constrained sharded re-solve of only the
   streams the budget actually binds.
2. **Ingest** — a ``StreamEngine`` over the mesh: reservoir, metrics and
   drift state live device-resident and row-sharded; chunks stream
   through the async double-buffered ``ingest_chunks`` loop (chunk t+1's
   host→device transfer overlaps chunk t's compute, buffers donated).
3. **Finalize** — final top-K reads metered per stream; the obs
   snapshot reports fleet-global (cross-shard aggregated) counters.

Beside the million small-K exact reservoirs the window co-runs a pack of
huge-K ``engine="logmem"`` tenants (K = 65536 by default): O(log K)
device state advanced by the same sharded step, with the admit counts
asserted against the closed-form write law within the backend's
1−O(1/√K) slack and the bytes-per-stream advantage checked >= 8x.

Run:
  PYTHONPATH=src python examples/million_streams.py [--streams 1000000]
  PYTHONPATH=src python examples/million_streams.py --ci   # 64k, CI scale

``--devices N`` shards over N devices (default: all local devices). On
the CPU it also splits the host into N virtual devices
(``jax_num_cpu_devices``, which only the CPU backend reads);
``--devices 1`` runs the same window unsharded for comparison.
"""
import argparse
import json
import os
import time

import numpy as np

import jax

from repro.core import constraints as cons
from repro.core import shp_jax
from repro.obs import Observability, ObsConfig
from repro.parallel import fleet
from repro.streams import StreamEngine, StreamSpec, logmem, planner


def fleet_cost_arrays(rng, m, n_docs, k):
    """Per-stream 3-tier (hot/warm/cold) cost arrays: write-cheap
    read-expensive hot tier, the reverse cold, jittered per stream so
    the fleet plan is genuinely heterogeneous."""
    jit = lambda lo, hi: rng.uniform(lo, hi, m)  # noqa: E731
    cw = np.stack([jit(0.8, 1.2) * 1e-6, jit(0.8, 1.2) * 2e-5,
                   jit(0.8, 1.2) * 8e-5], axis=1)
    cr = np.stack([jit(0.8, 1.2) * 2.7e-4, jit(0.8, 1.2) * 4e-5,
                   jit(0.8, 1.2) * 1e-6], axis=1)
    cs = np.stack([jit(0.8, 1.2) * 2.5e-6, jit(0.8, 1.2) * 1e-6,
                   jit(0.8, 1.2) * 2.5e-7], axis=1)
    n = np.full(m, float(n_docs))
    kv = np.full(m, float(k))
    rpw = rng.uniform(0.5, 4.0, m)
    return cw, cr, cs, n, kv, rpw


def plan_phase(mesh, rng, m, n_docs, k, hot_frac):
    """Sharded fleet plan + shared hot-tier water-filling."""
    cw, cr, cs, n, kv, rpw = fleet_cost_arrays(rng, m, n_docs, k)
    t0 = time.time()
    with fleet.use_fleet_mesh(mesh):
        plan = shp_jax.plan_ntier_arrays_jax(cw, cr, cs, n, kv, rpw)
    t_solve = time.time() - t0
    bounds, mig = plan["bounds"], plan["migrate"]
    desired = cons.peak_occupancy_arrays(bounds, n, kv, mig)[:, 0]
    budget = float(desired.sum()) * hot_frac
    t0 = time.time()
    grants = planner.waterfill(desired, budget, mesh=mesh)
    t_wf = time.time() - t0
    binding = grants < desired - 1e-9
    t0 = time.time()
    if binding.any():
        idx = np.flatnonzero(binding)
        cap = np.full((idx.size, 3), np.inf)
        cap[:, 0] = grants[idx]
        with fleet.use_fleet_mesh(mesh):
            re = shp_jax.plan_ntier_arrays_jax(
                cw[idx], cr[idx], cs[idx], n[idx], kv[idx], rpw[idx],
                cap=cap)
        bounds = bounds.copy()
        mig = mig.copy()
        bounds[idx] = re["bounds"]
        mig[idx] = re["migrate"]
    t_resolve = time.time() - t0
    hot_occ = cons.peak_occupancy_arrays(bounds, n, kv, mig)[:, 0]
    assert hot_occ.sum() <= budget * (1 + 1e-9) + 1e-6, \
        "hot-tier budget oversubscribed after re-solve"
    return {
        "bounds": bounds, "migrate": mig,
        "stats": {
            "solve_s": round(t_solve, 3),
            "waterfill_s": round(t_wf, 3),
            "resolve_s": round(t_resolve, 3),
            "binding_streams": int(binding.sum()),
            "hot_budget_docs": budget,
            "hot_peak_docs": float(hot_occ.sum()),
        },
    }


def dense_chunks(rng, m, w, n_chunks, lm=0, lw=0):
    """Generator of ingest_dense-shaped chunks: the main uniform-K exact
    bucket, plus (when ``lm`` > 0) a second pair for the huge-K logmem
    bucket — wider chunks, so the big-K tenants get past their admit-all
    warmup inside the same window. Produced lazily so chunk t+1's
    materialization and host→device copy overlap chunk t's sharded
    step."""
    for c in range(n_chunks):
        sc = rng.standard_normal((m, w)).astype(np.float32)
        ids = np.tile(np.arange(c * w, (c + 1) * w, dtype=np.int32),
                      (m, 1))
        pairs = [(sc, ids)]
        if lm:
            ls = rng.standard_normal((lm, lw)).astype(np.float32)
            lids = np.tile(np.arange(c * lw, (c + 1) * lw, dtype=np.int32),
                           (lm, 1))
            pairs.append((ls, lids))
        yield pairs


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--devices", type=int, default=None,
                    help="fleet shards (default: all local devices)")
    ap.add_argument("--streams", type=int, default=1_000_000)
    ap.add_argument("--docs", type=int, default=256,
                    help="docs per stream in the window")
    ap.add_argument("--chunk", type=int, default=16,
                    help="docs per stream per ingest chunk")
    ap.add_argument("--topk", type=int, default=8)
    ap.add_argument("--hot-frac", type=float, default=0.6,
                    help="fleet-shared hot-tier budget as a fraction of "
                         "the unconstrained plan's hot occupancy")
    ap.add_argument("--meter", action="store_true",
                    help="keep the per-stream host ledgers during ingest "
                         "(the default is pure-throughput: device metrics "
                         "only, ledgers at finalize)")
    ap.add_argument("--logmem-streams", type=int, default=None,
                    help="huge-K O(log K) tenants co-run beside the main "
                         "fleet (default: 64 under --ci, else 0)")
    ap.add_argument("--logmem-k", type=int, default=65_536,
                    help="reservoir width of the logmem tenants")
    ap.add_argument("--logmem-chunk", type=int, default=8_192,
                    help="docs per logmem stream per ingest chunk")
    ap.add_argument("--ci", action="store_true",
                    help="CI scale: 64k streams + 64 K=65536 logmem "
                         "tenants")
    ap.add_argument("--out", default="bench_out/million_streams.json")
    args = ap.parse_args()
    if args.devices is not None:
        # the CPU backend only: a TPU host's device count is its chips
        jax.config.update("jax_num_cpu_devices", args.devices)
    if args.ci:
        args.streams = min(args.streams, 64_000)
    lm = (args.logmem_streams if args.logmem_streams is not None
          else (64 if args.ci else 0))
    lk, lw = args.logmem_k, args.logmem_chunk

    mesh = fleet.fleet_mesh(args.devices)
    shards = fleet.n_shards(mesh)
    m, k = args.streams, args.topk
    if lm and lm % max(shards, 1):
        lm = (-(-lm // shards)) * shards  # keep the logmem bucket even
    print(f"{m} streams on {jax.local_device_count()} devices "
          f"({shards} shards)"
          + (f" + {lm} logmem tenants at K={lk}" if lm else ""))
    rng = np.random.default_rng(0)

    # --- phase 1: sharded plan + cross-shard water-filling ---------------
    plan = plan_phase(mesh, rng, m, args.docs, k, args.hot_frac)
    st = plan["stats"]
    print(f"plan: solve {st['solve_s']}s, waterfill {st['waterfill_s']}s, "
          f"re-solve of {st['binding_streams']} binding streams "
          f"{st['resolve_s']}s; hot occupancy {st['hot_peak_docs']:.0f} "
          f"<= budget {st['hot_budget_docs']:.0f}")

    # --- phase 2: sharded double-buffered ingest -------------------------
    t0 = time.time()
    specs = [StreamSpec(stream_id=i, k=k, boundaries=bt, migrate=bool(mg))
             for i, (bt, mg) in enumerate(zip(
                 map(tuple, plan["bounds"]), plan["migrate"]))]
    # huge-K tenants: O(log K) device state, admission by threshold
    # compare — the same fleet step advances both buckets
    specs += [StreamSpec(stream_id=m + i, k=lk, r=float(4 * lk),
                         engine="logmem") for i in range(lm)]
    obs = Observability(ObsConfig(residuals=False))
    eng = StreamEngine(specs, obs=obs, mesh=mesh)
    t_build = time.time() - t0
    n_chunks = args.docs // args.chunk
    t0 = time.time()
    done = eng.ingest_chunks(
        dense_chunks(rng, m, args.chunk, n_chunks, lm, lw),
        meter=args.meter)
    t_ingest = time.time() - t0
    docs = (m * args.chunk + lm * lw) * done
    print(f"ingest: {done} chunks, {docs / 1e6:.1f}M docs in "
          f"{t_ingest:.2f}s ({docs / t_ingest / 1e6:.2f}M docs/s)")

    # --- phase 3: finalize + fleet-global obs ----------------------------
    t0 = time.time()
    for bi, b in enumerate(eng.buckets):
        if b.engine == "logmem":
            continue  # no device-resident ids to read back
        eng.meter.record_reads(eng._global_rows[bi],
                               np.asarray(eng._states[bi].ids)[:b.m])
    t_final = time.time() - t0
    snap = eng.obs_snapshot()
    em = snap["engine"]
    assert em["docs"] == docs, (em["docs"], docs)
    assert int(eng.meter.reads.sum()) == m * k
    print(f"finalize: {t_final:.2f}s; fleet-global obs: "
          f"docs={em['docs']} admits={em['admits']} "
          f"evictions={em['evictions']} chunks={em['chunks']}")

    lm_stats = None
    if lm:
        lb = next(bi for bi, b in enumerate(eng.buckets)
                  if b.engine == "logmem")
        admits = np.asarray(eng._states[lb].admits, np.float64)[:lm]
        n_lm = lw * done
        law = float(logmem.expected_admits(np.asarray([n_lm]), lk)[0])
        slack = logmem.law_slack(lk)
        admit_ratio = float(admits.mean()) / law
        bps = logmem.state_bytes_per_stream(eng._states[lb])
        exact_bps = logmem.exact_bytes_per_stream(lk)
        assert abs(admit_ratio - 1.0) <= 3.0 * slack, \
            (f"logmem admits {admit_ratio:.4f}x law, beyond the "
             f"{3.0 * slack:.4f} slack budget")
        assert exact_bps / bps >= 8.0, (bps, exact_bps)
        lm_stats = {
            "streams": lm, "k": lk, "docs_per_stream": n_lm,
            "admits_mean": float(admits.mean()),
            "expected_admits": law,
            "admit_ratio": round(admit_ratio, 5),
            "law_slack": round(slack, 5),
            "bytes_per_stream": round(bps, 1),
            "exact_bytes_per_stream": exact_bps,
            "memory_ratio": round(exact_bps / bps, 1),
        }
        print(f"logmem: {lm} tenants at K={lk}: admits "
              f"{admit_ratio:.4f}x law (slack {slack:.4f}), "
              f"{bps:.0f} B/stream vs {exact_bps:.0f} exact "
              f"({exact_bps / bps:.0f}x leaner)")

    out = {
        "streams": m, "devices": jax.local_device_count(),
        "shards": shards, "docs_per_stream": args.docs,
        "chunk": args.chunk, "topk": k,
        "plan": st,
        "engine_build_s": round(t_build, 3),
        "ingest_s": round(t_ingest, 3),
        "ingest_docs_per_s": round(docs / t_ingest, 1),
        "finalize_s": round(t_final, 3),
        "obs_engine": em,
        "meter": snap["meter"],
        "logmem": lm_stats,
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
