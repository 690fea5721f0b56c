#!/usr/bin/env python3
"""Chip smoke test: the fleet engine's served path, plan -> ingest ->
finalize, through ``repro.streams.StreamEngine``'s normal entry points on
one TPU chip, checked against plain references.

* plan: 65,536 exact tenants at K = 1,024, planned from 3-tier
  HBM -> DRAM -> disk cost models (``topology.hbm_dram_disk_preset``) by
  the device planner (``core.shp_jax`` with the ``plan_solve`` kernel).
  Totals must agree with the NumPy oracle (``shp.plan_ntier_arrays_numpy``)
  within the float32 band the README documents.
* ingest: 64 huge-K ``engine="logmem"`` tenants (K = 65,536) ride along.
  Four seeded chunks (W = 1,024 docs per exact stream, 8,192 per logmem
  stream) go through ``ingest_chunks`` with obs metrics on: once with the
  Pallas filter (``batched_topk``, ``logmem_update``) and the host meter,
  once on the default jnp path. The two must agree bitwise; survivors
  and metered writes and reads of 64 sampled streams must equal
  ``core.simulator`` replays; logmem admits must sit within the
  backend's law slack.
* finalize: ``finalize_tiers`` (the ``tier_assign`` kernel) must equal
  its jnp reference.

``--chips 4`` runs only the sharded path and what it is compared with:
the fleet plan and ``StreamEngine`` over ``fleet.fleet_mesh(4)`` against
the same rows on one device (bitwise), and the ``psum`` water-fill
against the exact host law — at 16,384 exact tenants.

``--small`` shrinks every size for a rehearsal on the CPU
(``JAX_PLATFORMS=cpu``, kernels in interpret mode); only that option
accepts a platform other than ``tpu``.

Any failed check raises, and the script exits non-zero without printing
a result. On success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Run: ``python chip_smoke.py [--chips 4] [--small]`` from the repo root.
"""
import argparse
import itertools
import json
import os
import re
import sys
import time

import jax
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

FULL = dict(m=65_536, k=1_024, w=1_024, lm=64, lk=65_536, lw=8_192,
            chunks=4, sample=64)
SMALL = dict(m=512, k=16, w=64, lm=8, lk=128, lw=64, chunks=4, sample=16)
# --chips 4 runs a quarter of the exact fleet: its one-device reference
# is what costs, and four chips are charged four times
SHARDED_M = dict(full=16_384, small=512)


def log(msg):
    print(msg, flush=True)


def fleet_models(rng, m, n_docs, k):
    """Seeded 3-tier tenants: the preset's bandwidths, rental premium,
    document size and window vary per tenant, so the plan mixes
    single-tier placements with migration cascades."""
    from repro.core import topology

    def logu(lo, hi):
        return np.exp(rng.uniform(np.log(lo), np.log(hi), m))

    cols = zip(logu(1e-6, 1e-1), logu(60.0, 90 * 86_400.0),
               logu(1.0, 5_000.0), logu(4.0, 64.0), logu(0.5, 8.0))
    return [topology.hbm_dram_disk_preset(
        n_docs=n_docs, k=k, doc_gb=float(g), window_seconds=float(s),
        hbm_capacity_premium=float(p), host_link_gbps=float(link),
        disk_bw_gbps=float(d)) for g, s, p, link, d in cols]


def fleet_specs(models, S):
    from repro.streams import StreamSpec
    specs = [StreamSpec(stream_id=i, k=S["k"], cost_model=cm)
             for i, cm in enumerate(models)]
    specs += [StreamSpec(stream_id=S["m"] + i, k=S["lk"],
                         r=float(4 * S["lk"]), engine="logmem")
              for i in range(S["lm"])]
    return specs


def chunks(eng, S, seed, n_chunks):
    """Seeded ``ingest_chunks`` input, one (scores, ids) pair per bucket.
    Chunk c is a pure function of (seed, c)."""
    for c in range(n_chunks):
        rng = np.random.default_rng([seed, c])
        dense = []
        for b in eng.buckets:
            w = S["lw"] if b.engine == "logmem" else S["w"]
            s = rng.standard_normal((b.m, w), dtype=np.float32)
            ids = np.broadcast_to(
                np.arange(c * w, (c + 1) * w, dtype=np.int32), (b.m, w))
            dense.append((s, ids))
        yield dense


def bucket_index(eng, engine):
    return next(bi for bi, b in enumerate(eng.buckets) if b.engine == engine)


def device_state(eng):
    """Every device state leaf of every bucket, as host arrays."""
    return [{name: np.asarray(leaf) for name, leaf in st._asdict().items()}
            for st in eng.states()]


def assert_same(a, b, what):
    for x, y in zip(a, b):
        assert x.keys() == y.keys(), what
        for name in x:
            np.testing.assert_array_equal(x[name], y[name],
                                          err_msg=f"{what}: {name}")


def block(eng):
    jax.block_until_ready(eng.states())


_KERNEL = re.compile(r'@tpu_custom_call\(.*?kernel_name = "(\w+)"')


def kernels_in(jitted, *args, **kw):
    """Names of the compiled Pallas kernels in ``jitted``'s program."""
    return set(_KERNEL.findall(jitted.lower(*args, **kw).as_text()))


def ingest(specs, S, seed, *, kernel, meter):
    """Build an engine through the normal constructor and stream the
    window through ``ingest_chunks``. Returns (engine, timings)."""
    from repro.obs import Observability, ObsConfig
    from repro.streams import StreamEngine
    t0 = time.perf_counter()
    eng = StreamEngine(specs, use_kernel_filter=kernel,
                       obs=Observability(ObsConfig()))
    block(eng)
    t = {"build_s": time.perf_counter() - t0}
    gen = chunks(eng, S, seed, S["chunks"])
    t0 = time.perf_counter()
    eng.ingest_chunks(itertools.islice(gen, 1), meter=meter)
    block(eng)
    t["first_chunk_s"] = time.perf_counter() - t0  # compile + one chunk
    t0 = time.perf_counter()
    done = eng.ingest_chunks(gen, meter=meter)
    block(eng)
    t["chunk_s"] = (time.perf_counter() - t0) / max(done, 1)
    assert eng.chunks_ingested == S["chunks"], eng.chunks_ingested
    return eng, t


def check_plan(eng, models):
    """Device plan totals vs the NumPy oracle on the same models."""
    from repro.core import shp
    from repro.streams import planner
    prev = shp.set_planner_backend("numpy")
    try:
        oracle = planner.plan_fleet_mixed(models)
    finally:
        shp.set_planner_backend(prev)
    dev = np.asarray(eng.plan.totals[:len(models)], np.float64)
    # float32 device solve: totals carry float32 accuracy (README,
    # "float64 / x64 policy"; tests/test_plan_device.py pins 5e-3)
    np.testing.assert_allclose(dev, oracle.totals, rtol=5e-3)
    rel = np.abs(dev - oracle.totals) / np.abs(oracle.totals)
    log(f"plan: {len(models)} tenants vs NumPy oracle, max rel diff "
        f"{rel.max():.3e}; strategies {eng.plan.strategy_histogram()}")


def check_simulator(eng, S, seed):
    """Survivors, metered writes per tier (chunk by chunk) and final
    reads of sampled streams — half of them migration cascades, where
    the plan has them — vs ``core.simulator`` replays."""
    from repro.core import placement, simulator
    k, w, n_chunks = S["k"], S["w"], S["chunks"]
    bi = bucket_index(eng, "exact")
    sids = eng.buckets[bi].stream_ids
    rows = np.asarray([eng.stream_row(sid) for sid in sids])
    rng = np.random.default_rng(seed)
    mig = np.flatnonzero(eng.meter.migrate[rows])
    pick = rng.choice(mig, min(mig.size, S["sample"] // 2), replace=False)
    rest = np.setdiff1d(np.arange(len(sids)), pick)
    pick = np.sort(np.concatenate([pick, rng.choice(
        rest, S["sample"] - pick.size, replace=False)]))
    traces = np.concatenate(
        [dense[bi][0][pick] for dense in chunks(eng, S, seed, n_chunks)],
        axis=1).astype(np.float64)
    surv = eng.survivors()
    for j, trace in zip(pick, traces):
        sid = sids[j]
        row = eng.stream_row(sid)
        bounds = eng.meter.boundaries[row]
        pol = placement.Policy(boundaries=tuple(bounds),
                               migrate_at_r=bool(eng.meter.migrate[row]))
        full = simulator.simulate(trace, k, pol)
        np.testing.assert_array_equal(surv[sid], full.survivor_ids,
                                      err_msg=f"stream {sid} survivors")
        # a chunk writes exactly its docs that survive the chunk's prefix
        writes = np.zeros(eng.meter.n_tiers, np.int64)
        for c in range(1, n_chunks + 1):
            end = c * w
            if end <= k:
                alive = np.arange(end)
            elif end == trace.size:
                alive = full.survivor_ids
            else:
                alive = simulator.simulate(trace[:end], k, pol).survivor_ids
            new = alive[alive >= end - w]
            np.add.at(writes, (new[:, None] >= bounds[None, :]).sum(1), 1)
        np.testing.assert_array_equal(eng.meter.writes[row], writes,
                                      err_msg=f"stream {sid} writes")
        np.testing.assert_array_equal(eng.meter.reads[row],
                                      full.reads_per_tier,
                                      err_msg=f"stream {sid} reads")
    n_mig = int(eng.meter.migrate[rows[pick]].sum())
    log(f"simulator: {pick.size} sampled streams ({n_mig} migrating) match "
        "survivors, per-tier writes and final reads")


def check_logmem(eng, S):
    from repro.streams import logmem
    lb = bucket_index(eng, "logmem")
    admits = np.asarray(eng.states()[lb].admits, np.float64)[:S["lm"]]
    n = S["lw"] * S["chunks"]
    law = float(logmem.expected_admits(np.asarray([n]), S["lk"])[0])
    slack = logmem.law_slack(S["lk"])
    ratio = float(admits.mean()) / law
    assert abs(ratio - 1.0) <= 3.0 * slack, (ratio, slack)
    phase = "threshold tracking" if n > S["lk"] else "admit-all warmup"
    log(f"logmem: {S['lm']} tenants at K={S['lk']} after {n} docs "
        f"({phase}): admits {ratio:.5f}x law (band +-{3.0 * slack:.5f})")


def check_tiers(eng, S):
    got = eng.finalize_tiers()
    ref = eng.finalize_tiers(use_pallas=False)
    assert got.keys() == ref.keys() and len(got) == S["m"]
    for sid in got:
        for key in ("tiers", "counts"):
            np.testing.assert_array_equal(got[sid][key], ref[sid][key],
                                          err_msg=f"stream {sid} {key}")
    log(f"finalize_tiers: tier_assign kernel == jnp reference on "
        f"{len(got)} streams")


def census(eng, S):
    """The kernels compiled into the programs that ran: the engine's
    donating step, one planner chunk and the finalize assignment."""
    from repro.core import shp_jax
    from repro.kernels.tier_assign import ops as ta_ops
    bi = bucket_index(eng, "exact")
    states = tuple(eng.states())
    batches = tuple(
        (jax.ShapeDtypeStruct((st.seen.shape[0], w), np.float32),
         jax.ShapeDtypeStruct((st.seen.shape[0], w), np.int32))
        for st, w in ((s, S["lw"] if b.engine == "logmem" else S["w"])
                      for s, b in zip(states, eng.buckets)))
    found = kernels_in(eng._donating_step, states, batches, (),
                       eng._metrics_state, tuple(eng._meter_states))
    f32 = jax.ShapeDtypeStruct((8192, 3), np.float32)
    v32 = jax.ShapeDtypeStruct((8192,), np.float32)
    found |= kernels_in(shp_jax._plan_jit, f32, f32, f32, v32, v32, v32,
                        f32, f32, v32, t=3, constrained=False,
                        capfin=(False,) * 3, slo_any=False, use_pallas=True)
    ids = eng.states()[bi].ids
    found |= kernels_in(ta_ops._assign, ids,
                        jax.ShapeDtypeStruct((ids.shape[0], 2), np.int32),
                        jax.ShapeDtypeStruct((ids.shape[0],), np.int32),
                        n_tiers=3, block_k=128, use_pallas=True)
    want = {"batched_topk", "logmem_update", "plan_solve", "tier_assign"}
    assert want <= found, f"kernels not in the programs: {want - found}"
    log(f"compiled Pallas kernels in the programs that ran: {sorted(found)}")


def one_chip(S, seed, on_tpu):
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    models = fleet_models(rng, S["m"], S["w"] * S["chunks"], S["k"])
    specs = fleet_specs(models, S)
    log(f"fleet: {S['m']} exact tenants at K={S['k']} + {S['lm']} logmem "
        f"at K={S['lk']}; {S['chunks']} chunks of W={S['w']} "
        f"(logmem {S['lw']}); models built in "
        f"{time.perf_counter() - t0:.3f}s")
    kern, tk = ingest(specs, S, seed, kernel=True, meter=True)
    log(f"ingest (Pallas filter, metered): engine build+plan "
        f"{tk['build_s']:.3f}s, first chunk (compile) "
        f"{tk['first_chunk_s']:.3f}s, then {tk['chunk_s']:.3f}s/chunk")
    check_plan(kern, models)
    kern_state = device_state(kern)
    kern_obs = kern.obs_snapshot()["engine"]
    check_logmem(kern, S)
    check_tiers(kern, S)
    if on_tpu:
        census(kern, S)
    kern.finalize()
    exact = kern.meter.reads[~kern.meter.logmem].sum()
    assert exact == S["m"] * S["k"], exact
    check_simulator(kern, S, seed)
    del kern

    plain, tp = ingest(specs, S, seed, kernel=False, meter=False)
    log(f"ingest (default jnp path): engine build+plan {tp['build_s']:.3f}s,"
        f" first chunk (compile) {tp['first_chunk_s']:.3f}s, then "
        f"{tp['chunk_s']:.3f}s/chunk")
    assert_same(kern_state, device_state(plain), "kernel vs jnp path state")
    assert plain.obs_snapshot()["engine"] == kern_obs
    log("ingest paths: Pallas filter == jnp path, bitwise (every state "
        "leaf, device metrics)")


def sharded(S, seed, m):
    """4-chip path: sharded plan + engine vs one device, and the psum
    water-fill vs the exact host law."""
    from repro.core import constraints as cons
    from repro.parallel import fleet
    from repro.streams import planner
    mesh = fleet.fleet_mesh(4)
    assert fleet.n_shards(mesh) == 4
    S = dict(S, m=m)
    rng = np.random.default_rng(seed)
    models = fleet_models(rng, S["m"], S["w"] * S["chunks"], S["k"])
    specs = fleet_specs(models, S)

    def run(mesh_arg):
        from repro.obs import Observability, ObsConfig
        from repro.streams import StreamEngine
        t0 = time.perf_counter()
        eng = StreamEngine(specs, obs=Observability(ObsConfig()),
                           mesh=mesh_arg)
        block(eng)
        t_build = time.perf_counter() - t0
        t0 = time.perf_counter()
        eng.ingest_chunks(chunks(eng, S, seed, S["chunks"]), meter=False)
        block(eng)
        log(f"{'sharded' if mesh_arg else 'one device'}: build+plan "
            f"{t_build:.3f}s, {S['chunks']} chunks "
            f"{time.perf_counter() - t0:.3f}s")
        return eng

    ref = run(None)
    ref_state, ref_obs = device_state(ref), ref.obs_snapshot()["engine"]
    ref_plan = ref.plan
    del ref
    shd = run(mesh)
    for st in shd.states():
        assert len(st.seen.sharding.device_set) == 4, st.seen.sharding
    for name in ("totals", "migrate_flags"):
        np.testing.assert_array_equal(getattr(shd.plan, name),
                                      getattr(ref_plan, name), err_msg=name)
    assert shd.plan.boundaries == ref_plan.boundaries
    log(f"plan: sharded == one device, bitwise, on {S['m']} tenants")
    shd_state = device_state(shd)
    # sharded buckets pad rows to the shard count: compare the real rows
    for a, b, bk in zip(shd_state, ref_state, shd.buckets):
        for name in a:
            if a[name].ndim and a[name].shape[0] != b[name].shape[0]:
                a[name] = a[name][:bk.m]
                b[name] = b[name][:bk.m]
    assert_same(shd_state, ref_state, "sharded vs one-device state")
    assert shd.obs_snapshot()["engine"] == ref_obs
    log("engine: sharded ingest_chunks == one device, bitwise (every state "
        "leaf, device metrics)")

    plan = shd.plan
    bounds = np.asarray(plan.boundaries[:S["m"]], np.float64)
    n = np.full(S["m"], float(S["w"] * S["chunks"]))
    kv = np.full(S["m"], float(S["k"]))
    desired = cons.peak_occupancy_arrays(
        bounds, n, kv, np.asarray(plan.migrate_flags[:S["m"]]))[:, 0]
    budget = 0.6 * float(desired.sum())
    t0 = time.perf_counter()
    grants = planner.waterfill(desired, budget, mesh=mesh)
    t_wf = time.perf_counter() - t0
    exact = cons.waterfill_grants(desired, budget)
    assert grants.sum() <= budget * (1 + 1e-12), (grants.sum(), budget)
    # psum reorders the grant sums, so the bisection's level differs from
    # the host sort's by rounding only (tests/test_sharded.py's band)
    np.testing.assert_allclose(grants, exact, rtol=1e-7, atol=1e-7)
    log(f"waterfill: psum bisection over 4 shards in {t_wf:.3f}s, "
        f"{int((grants < desired).sum())} streams capped, matches the host "
        "law")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--small", action="store_true",
                    help="tiny sizes; accepts the CPU (rehearsal)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core import jaxcompat

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.small:
        log(f"no TPU: JAX found {dev.platform} ({dev.device_kind})")
        return 2
    if len(devices) < args.chips:
        log(f"--chips {args.chips} needs {args.chips} devices, JAX found "
            f"{len(devices)}")
        return 2
    cache = jaxcompat.compile_cache(ROOT)
    S = SMALL if args.small else FULL
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"pallas interpret={jaxcompat.pallas_interpret()}; "
        f"compile cache {cache}")
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded(S, args.seed, SHARDED_M["small" if args.small else "full"])
    else:
        one_chip(S, args.seed, dev.platform == "tpu")
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(f"total {time.perf_counter() - t0:.3f}s; peak device bytes in use "
        f"{peak if peak is not None else 'not reported'}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
