"""Fleet-engine throughput: docs/sec of one jitted multi-stream step vs M.

Times the device-side batched update (the jitted sort-merge over all
streams), the kernel-filtered path, and the online drift detector
(``repro.online.drift.update`` — the (M,)-batched sequential statistics
that ride inside the engine step). The Pallas-backed filtered path is
*compiled* when a real TPU backend is present and timed across the full
sweep; on CPU/GPU it falls back to interpret mode at a token size
(correctness only) and the row label says so — the perf trajectory then
carries compiled numbers only where they mean something. Standalone entry
point writes ``BENCH_streams.json`` under ``--out-dir`` (default
``bench_out/``; the committed repo-root copy is the canonical snapshot);
also wired into ``benchmarks/run.py``.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import Observability, ObsConfig, timers
from repro.online import drift
from repro.streams import engine

K, BATCH = 16, 64
SWEEP_M = (64, 256, 1024)
DRIFT_M = (1024, 16384)
# engine-backend pairs: matched (K, M, W) fleets, exact vs logmem — the
# rows carry bytes_per_stream extras that run.py --check holds to the
# memory-regression floor (logmem >= 8x leaner at K >= 4096)
# reps/rounds shrink with K: the exact step's narrow-batch path pays an
# O(W*K) resident-id dedupe per call (seconds at K=65536 on CPU), and
# the floor guards deterministic bytes, not the timing
BACKEND_SWEEP = ((256, 256, 512, 5, 4), (4_096, 128, 1_024, 3, 2),
                 (65_536, 8, 1_024, 1, 2))  # (K, M, W, reps, rounds)
# competitive-ratio harness traces: (K, M, n, chunk)
RATIO_SWEEP = ((256, 64, 16_384, 512), (4_096, 8, 131_072, 2_048),
               (65_536, 2, 262_144, 8_192))
# fleet-mesh scaling rows: (M, W) pairs; emitted only when jax sees a
# multi-device mesh (CI forces 8 CPU devices via
# XLA_FLAGS=--xla_force_host_platform_device_count=8)
SHARD_SWEEP = ((65_536, 64), (1_000_000, 16))

_time = timers.time_jax  # the shared device-dispatch discipline


def _engine_step_pair(emit, m, rng):
    """The full fleet-engine jitted step, telemetry off vs on: the row
    pair the obs layer's overhead is read from (same routed batch, same
    bucket structure; the obs variant carries the device
    ``MetricsState`` accumulators through the step)."""
    specs = [engine.StreamSpec(stream_id=i, k=K, r=4096.0)
             for i in range(m)]
    sids = np.repeat(np.arange(m), BATCH)
    dids = np.tile(np.arange(BATCH), m)
    sc = rng.standard_normal(m * BATCH)
    labels = {"": "telemetry off", "_obs": "device metrics on"}
    variants = []
    for suffix, obs in (
            ("", None),
            ("_obs", Observability(ObsConfig(residuals=False)))):
        eng = engine.StreamEngine(specs, obs=obs)
        routed = eng.router.route(sids, sc, dids)
        batches = tuple((jnp.asarray(s), jnp.asarray(i)) for s, i in routed)
        mstate = (eng._metrics_state
                  if eng._metrics_state is not None else ())
        variants.append((suffix, eng, batches, mstate, [float("inf")]))
    # interleaved min-of-rounds: the delta inside the pair is the obs
    # overhead, so both variants must sample the same machine
    # weather — alternating rounds and keeping the min is robust to the
    # contention spikes a single long rep window averages in
    for _ in range(32):
        for _, eng, batches, mstate, best in variants:
            best[0] = min(best[0],
                          _time(eng._step, tuple(eng._states), batches,
                                (), mstate, tuple(eng._meter_states),
                                reps=25))
    for suffix, _, _, _, best in variants:
        us = best[0]
        emit(f"streams.engine_step{suffix}_m{m}_k{K}_b{BATCH}", us,
             f"{m * BATCH / us * 1e6:.0f} docs/s fleet step "
             f"({labels[suffix]})")


def _state_bytes_per_stream(states) -> float:
    """Device bytes per stream across a fleet's bucket states (pytree
    leaves / total rows) — the number the memory floor guards."""
    total = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                for st in states for leaf in st)
    rows = sum(int(st[0].shape[0]) for st in states)
    return total / max(rows, 1)


def _backend_rows(emit, rng):
    """Paired exact/logmem engine-step rows at matched (K, M, W): same
    batch, same bucket structure, interleaved min-of-rounds so the
    pair's delta is the backend, not machine weather. Each row carries
    ``bytes_per_stream`` + ``k`` extras; ``run.py --check`` pairs the
    ``.exact``/``.logmem`` suffixes same-run and fails when logmem's
    memory advantage drops under the floor."""
    for k, m, w, reps, rounds in BACKEND_SWEEP:
        sc = rng.standard_normal((m, w)).astype(np.float32)
        ids = np.tile(np.arange(w, dtype=np.int32), (m, 1))
        batches = ((jnp.asarray(sc), jnp.asarray(ids)),)
        variants = []
        for backend in ("exact", "logmem"):
            specs = [engine.StreamSpec(stream_id=i, k=k, r=float(4 * k),
                                       engine=backend) for i in range(m)]
            eng = engine.StreamEngine(specs)
            variants.append((backend, eng, [float("inf")]))
        for _ in range(rounds):
            for _, eng, best in variants:
                best[0] = min(best[0],
                              _time(eng._step, tuple(eng._states), batches,
                                    (), (), tuple(eng._meter_states),
                                    reps=reps))
        for backend, eng, best in variants:
            us = best[0]
            bps = _state_bytes_per_stream(eng._states)
            emit(f"streams.engine_backend_k{k}_m{m}_w{w}.{backend}", us,
                 f"{m * w / us * 1e6:.0f} docs/s {backend} step, "
                 f"{bps:.0f} B/stream device state",
                 bytes_per_stream=bps, k=k)


def _logmem_ratio_rows(emit, rng):
    """Simulator-trace harness rows: replay i.u.d. traces through the
    logmem backend and report the realized competitive ratio (top-K mass
    retained vs the true top-K) and its 1 − c/√K constant, plus the
    admit count against the closed-form write law."""
    from repro.streams import logmem
    for k, m, n, chunk in RATIO_SWEEP:
        sc = rng.standard_normal((m, n)).astype(np.float32)
        t0 = time.perf_counter()
        rep = logmem.trace_competitive_ratio(sc, k, chunk)
        us = (time.perf_counter() - t0) * 1e6
        emit(f"streams.logmem_ratio_k{k}_n{n}_c{chunk}", us,
             f"ratio>={rep['min_ratio']:.5f} (c<={rep['max_c']:.3f}), "
             f"admits {np.mean(rep['admit_ratio']):.3f}x law, "
             f"{rep['bytes_per_stream']:.0f} vs "
             f"{rep['exact_bytes_per_stream']:.0f} B/stream",
             min_ratio=rep["min_ratio"], max_c=rep["max_c"],
             admit_ratio=float(np.mean(rep["admit_ratio"])), k=k)


# checkpoint-overhead pair: (M, W, save cadence, chunks-per-round,
# rounds) — the README's cadence guidance regime: wide chunks (the
# fleet-scale ingest shape) and a save every 8 chunks, so the async npy
# write hides behind ~8 chunks of compute and the residual per-chunk
# cost is the synchronous host snapshot plus the final drain's tail,
# amortized over the round
CKPT_SWEEP = ((256, 1024, 8, 16, 5),)


def _ckpt_rows(emit, rng):
    """Chunk-boundary checkpointing overhead: the same double-buffered
    ``ingest_chunks`` loop with a ``FleetCheckpointer`` saving every
    chunk (async npy writes on the manager's worker thread) vs an
    identical no-checkpoint twin. Emitted as a same-run pair
    (``engine_step_ckpt_*`` / ``engine_step_ckptoff_*``, interleaved
    rounds, min-of-rounds) so ``run.py --check`` holds the snapshot +
    handoff cost within its ceiling without cross-machine assumptions.
    The timed region includes the final ``wait()`` — the tail I/O is
    part of the overhead, not free."""
    import shutil
    import tempfile

    from repro.resilience import FleetCheckpointer
    for m, w, every, n_chunks, rounds in CKPT_SWEEP:
        sc = rng.standard_normal((m, w)).astype(np.float32)
        ids = np.tile(np.arange(w, dtype=np.int32), (m, 1))
        chunk = [(sc, ids)]
        specs = [engine.StreamSpec(stream_id=i, k=K, r=float(4 * K))
                 for i in range(m)]
        tmp = tempfile.mkdtemp(prefix="bench_ckpt_")
        try:
            eng_off = engine.StreamEngine(specs)
            eng_on = engine.StreamEngine(specs)
            ck = FleetCheckpointer(tmp, every=every, keep_latest=2)
            eng_on.attach_checkpointer(ck)
            for eng in (eng_off, eng_on):  # warm the jitted step
                eng.ingest_dense(chunk)
            ck.save(eng_on, blocking=True)  # warm the save path too
            ck.wait()
            variants = [("_ckptoff", eng_off, None),
                        ("_ckpt", eng_on, ck)]
            best = {name: float("inf") for name, _, _ in variants}
            for _ in range(rounds):
                for name, eng, cw in variants:
                    t0 = time.perf_counter()
                    eng.ingest_chunks(chunk for _ in range(n_chunks))
                    if cw is not None:
                        cw.wait()
                    us = (time.perf_counter() - t0) * 1e6 / n_chunks
                    best[name] = min(best[name], us)
            for name, _, _ in variants:
                us = best[name]
                what = (f"per-chunk ingest + async checkpoint "
                        f"(every {every} chunks)" if name == "_ckpt"
                        else "per-chunk ingest, checkpointing off")
                emit(f"streams.engine_step{name}_m{m}_k{K}_w{w}", us,
                     f"{m * w / us * 1e6:.0f} docs/s {what}")
        finally:
            shutil.rmtree(tmp, ignore_errors=True)


def _sharded_step_rows(emit, rng):
    """Fleet-axis scaling: the same jitted engine step, single-device vs
    shard_map-ped over the mesh, on identical inputs — emitted as a
    same-run pair (``.ref1`` / ``.sharded_dN``) so ``run.py --check``
    can guard the speedup without cross-machine assumptions. Throughput
    only; bit-identity is asserted in tests/test_sharded.py."""
    from repro.parallel import fleet
    mesh = fleet.fleet_mesh(min(jax.local_device_count(), 8))
    if mesh is None:
        return
    shards = fleet.n_shards(mesh)
    for m, w in SHARD_SWEEP:
        reps, rounds = (10, 8) if m <= 100_000 else (2, 2)
        step1 = engine._make_step(False, 512)
        stepd = engine._make_step(False, 512, mesh=mesh)
        sc = rng.standard_normal((m, w)).astype(np.float32)
        ids = np.tile(np.arange(w, dtype=np.int32), (m, 1))
        st = engine.init(m, K)
        sh = fleet.row_sharding(mesh)
        variants = [
            ("ref1", step1, ((st,), ((jnp.asarray(sc),
                                      jnp.asarray(ids)),), (), (), ())),
            (f"sharded_d{shards}", stepd,
             (((fleet.shard_rows(mesh, st)),),
              ((jax.device_put(sc, sh), jax.device_put(ids, sh)),),
              (), (), ())),
        ]
        best = {name: float("inf") for name, _, _ in variants}
        for _ in range(rounds):  # interleaved: same machine weather
            for name, step, args in variants:
                best[name] = min(best[name], _time(step, *args, reps=reps))
        us1 = best["ref1"]
        emit(f"streams.engine_step_m{m}_k{K}_b{w}.ref1", us1,
             f"{m * w / us1 * 1e6:.0f} docs/s single-device reference")
        usd = best[f"sharded_d{shards}"]
        emit(f"streams.engine_step_m{m}_k{K}_b{w}.sharded_d{shards}", usd,
             f"{m * w / usd * 1e6:.0f} docs/s on {shards} shards "
             f"({us1 / usd:.2f}x vs same-run 1-device ref)")


def run(emit):
    rng = np.random.default_rng(0)
    on_tpu = jax.default_backend() == "tpu"
    upd = jax.jit(engine.update)
    filt = jax.jit(lambda st, s, i: engine.filtered_update(
        st, s, i, use_pallas=False))
    pal = jax.jit(lambda st, s, i: engine.filtered_update(st, s, i))
    for m in SWEEP_M:
        state = engine.init(m, K)
        sc = jnp.asarray(rng.standard_normal((m, BATCH)), jnp.float32)
        ids = jnp.tile(jnp.arange(BATCH, dtype=jnp.int32), (m, 1))
        # headline row first: the jnp filter+merge is what StreamEngine
        # runs on exact buckets
        us = _time(filt, state, sc, ids)
        emit(f"streams.filtered_update_m{m}_k{K}_b{BATCH}", us,
             f"{m * BATCH / us * 1e6:.0f} docs/s filter+merge "
             f"(engine default path)")
        us = _time(upd, state, sc, ids)
        emit(f"streams.update_m{m}_k{K}_b{BATCH}", us,
             f"{m * BATCH / us * 1e6:.0f} docs/s vmap sort-merge "
             f"(engine.update)")
        if on_tpu:
            us = _time(pal, state, sc, ids)
            emit(f"streams.filtered_update_pallas_m{m}_k{K}_b{BATCH}", us,
                 f"{m * BATCH / us * 1e6:.0f} docs/s Pallas 2-D grid "
                 f"(compiled, tpu)")
        _engine_step_pair(emit, m, rng)
    if not on_tpu:
        # interpret-mode fallback at a token size: correctness only, kept
        # out of the compiled perf trajectory by the explicit label
        state = engine.init(8, K)
        sc = jnp.asarray(rng.standard_normal((8, 256)), jnp.float32)
        ids = jnp.tile(jnp.arange(256, dtype=jnp.int32), (8, 1))
        small = jax.jit(lambda st, s, i: engine.filtered_update(
            st, s, i, block_n=128))
        us = _time(small, state, sc, ids, reps=3)
        emit("streams.filtered_update_pallas_interpret_m8_b256", us,
             f"Pallas 2-D grid (interpret fallback, "
             f"{jax.default_backend()}; correctness only)")
    # online drift detector: the (M,)-batched per-chunk update
    cfg = drift.DriftConfig()
    for m in DRIFT_M:
        kf = jnp.full((m,), float(K), jnp.float32)
        step = jax.jit(lambda st, w, s: drift.update(st, w, s, kf, cfg))
        # one BATCH-doc chunk per stream: prefix 512-BATCH -> 512
        st = drift.init(m)._replace(
            seen=jnp.full((m,), float(512 - BATCH), jnp.float32))
        w = jnp.asarray(rng.poisson(2.0, m), jnp.float32)
        seen = jnp.full((m,), 512.0, jnp.float32)
        us = _time(step, st, w, seen)
        emit(f"online.drift_update_m{m}", us,
             f"{m * BATCH / us * 1e6:.0f} docs/s detector "
             f"(M-batched {BATCH}-doc chunk stats)")
    _backend_rows(emit, rng)
    _logmem_ratio_rows(emit, rng)
    _ckpt_rows(emit, rng)
    _sharded_step_rows(emit, rng)


def main():
    try:
        from benchmarks.run import write_trajectory
    except ImportError:  # bare-script invocation: benchmarks/ is sys.path[0]
        from run import write_trajectory
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="explicit output path (overrides --out-dir)")
    ap.add_argument("--out-dir", default="bench_out",
                    help="directory for BENCH_streams.json")
    args = ap.parse_args()
    rows = []

    def emit(name, us, derived="", **extra):
        print(f"{name},{us:.1f},{derived}")
        rows.append({"name": name, "us_per_call": us, "derived": derived,
                     **extra, "ts": time.time()})

    run(emit)
    print(f"wrote {write_trajectory('streams', rows, args.json, args.out_dir)}")


if __name__ == "__main__":
    main()
