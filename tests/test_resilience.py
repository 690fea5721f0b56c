"""repro.resilience — crash-consistent checkpointing, fault injection,
and tier-outage degradation.

The acceptance bar is bitwise: kill the run at an arbitrary chunk,
restore the last checkpoint onto a freshly built engine, resume — final
reservoirs, every meter ledger, and the f64-priced cost ledgers must
equal the uninterrupted run's, on exact and logmem backends. Delivery
faults (transients, duplicates, reordering) must be absorbed by the
at-least-once delivery / exactly-once application guard, NaN/Inf scores
by the step's quarantine, and a tier outage must evacuate the failed
tier through the constrained re-solve without burn-alert false fires."""
import numpy as np
import pytest

from repro.obs import Observability, ObsConfig
from repro.obs import metrics as obs_metrics
from repro.online import DriftConfig, ReplanConfig
from repro.resilience import (DeviceLossError, FaultyChunkSource,
                              FleetCheckpointer, TierOutage,
                              TransientDeliveryError, fleet_restore,
                              fleet_snapshot, ingest_with_faults,
                              run_with_recovery)
from repro.resilience.faults import fetch_with_retry
from repro.streams import StreamEngine, StreamSpec

W = 8  # docs per stream per chunk


def _specs(backend="mixed"):
    """Small heterogeneous fleet: three 3-tier exact streams plus (for
    ``mixed``) one logmem stream — two buckets, both reservoir kinds."""
    specs = [StreamSpec(stream_id=i, k=8, boundaries=(16.0, 64.0))
             for i in range(3)]
    if backend == "mixed":
        specs.append(StreamSpec(stream_id=10, k=16, r=32.0,
                                engine="logmem"))
    elif backend == "logmem":
        specs = [StreamSpec(stream_id=i, k=16, r=32.0, engine="logmem")
                 for i in range(3)]
    return specs


def _build(backend="mixed", obs=False):
    return StreamEngine(_specs(backend),
                        obs=Observability(ObsConfig()) if obs else None)


def _chunk_maker(engine, seed=1000):
    """ingest_dense-shaped chunks as a pure function of the index."""
    buckets = [(b.m,) for b in engine.buckets]

    def make_chunk(i):
        r = np.random.default_rng(seed + i)
        dense = []
        for (m,) in buckets:
            s = r.random((m, W)).astype(np.float32)
            ids = np.tile(np.arange(i * W, (i + 1) * W, dtype=np.int32),
                          (m, 1))
            dense.append((s, ids))
        return dense
    return make_chunk


def _assert_same_finals(ref, eng):
    s_ref, s_eng = ref.finalize(), eng.finalize()
    assert set(s_ref) == set(s_eng)
    for sid in s_ref:
        np.testing.assert_array_equal(s_ref[sid], s_eng[sid])
    d_ref, d_eng = ref.meter.state_dict(), eng.meter.state_dict()
    assert set(d_ref) == set(d_eng)
    for key in d_ref:
        np.testing.assert_array_equal(d_ref[key], d_eng[key],
                                      err_msg=f"meter.{key}")


# ---------------------------------------------------------------------------
# snapshot / checkpoint: kill-and-restore is bitwise invisible
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["exact", "mixed", "logmem"])
def test_snapshot_restore_roundtrip_bitwise(backend):
    """fleet_snapshot → fleet_restore into a fresh engine, then resume:
    finals bitwise equal to the uninterrupted run."""
    ref, eng = _build(backend), _build(backend)
    make_chunk = _chunk_maker(ref)
    for i in range(10):
        ref.ingest_dense(make_chunk(i))
    for i in range(6):
        eng.ingest_dense(make_chunk(i))
    tree, meta = fleet_snapshot(eng)
    eng2 = _build(backend)
    fleet_restore(eng2, tree, meta)
    assert eng2.chunks_ingested == 6
    for i in range(6, 10):
        eng2.ingest_dense(make_chunk(i))
    _assert_same_finals(ref, eng2)


@pytest.mark.parametrize("kill_at", [1, 4, 9])
def test_checkpoint_kill_restore_resume_bitwise(tmp_path, kill_at):
    """Checkpoints ride chunk boundaries; dying at ANY chunk and
    restoring the latest committed checkpoint resumes to bitwise-equal
    finals (the cursor names the next chunk to redeliver)."""
    ref = _build()
    make_chunk = _chunk_maker(ref)
    for i in range(10):
        ref.ingest_dense(make_chunk(i))

    eng = _build()
    ck = FleetCheckpointer(str(tmp_path), every=2, blocking=True)
    eng.attach_checkpointer(ck)
    for i in range(kill_at):
        eng.ingest_dense(make_chunk(i))
    del eng  # the crash

    eng2 = _build()
    ck2 = FleetCheckpointer(str(tmp_path), every=2)
    if kill_at < 2:  # no checkpoint committed yet — cold start
        with pytest.raises(FileNotFoundError):
            ck2.restore(eng2)
        cursor = 0
    else:
        gen = ck2.restore(eng2)
        assert gen >= 1
        cursor = eng2.chunks_ingested
        assert cursor == (kill_at // 2) * 2
    for i in range(cursor, 10):
        eng2.ingest_dense(make_chunk(i))
    _assert_same_finals(ref, eng2)


def test_checkpoint_full_obs_replan_roundtrip(tmp_path):
    """Full-fat engine (metrics + residual monitor + cost monitor +
    drift/replan state): restore mid-run and resume — replan events,
    cost attribution, and the obs snapshot all land bitwise."""
    from repro.core import costs as core_costs
    rng = np.random.default_rng(7)
    m, n, k, batch = 4, 1024, 16, 64
    cm = core_costs.hbm_host_preset(n_docs=n, k=k, doc_gb=1e-4,
                                    window_seconds=60.0)
    traces = rng.standard_normal((m, n)).astype(np.float32)
    traces[:, n // 4:] += 6.0  # drift so the replanner actually fires

    def build():
        specs = [StreamSpec(stream_id=i, k=k, cost_model=cm)
                 for i in range(m)]
        return StreamEngine(
            specs, obs=Observability(ObsConfig(costs=True)),
            replan=ReplanConfig(drift=DriftConfig(alpha=0.05)))

    def chunk(i):
        sids = np.repeat(np.arange(m), batch)
        dids = np.tile(np.arange(i * batch, (i + 1) * batch), m)
        return sids, traces[:, i * batch:(i + 1) * batch].reshape(-1), dids

    n_chunks = n // batch
    ref = build()
    for i in range(n_chunks):
        ref.ingest(*chunk(i))
    assert len(ref.replan_events) > 0

    eng = build()
    ck = FleetCheckpointer(str(tmp_path), every=3, blocking=True)
    eng.attach_checkpointer(ck)
    for i in range(10):
        eng.ingest(*chunk(i))
    eng2 = build()
    FleetCheckpointer(str(tmp_path)).restore(eng2)
    assert eng2.chunks_ingested == 9
    for i in range(9, n_chunks):
        eng2.ingest(*chunk(i))

    _assert_same_finals(ref, eng2)
    assert len(ref.replan_events) == len(eng2.replan_events)
    for a, b in zip(ref.replan_events, eng2.replan_events):
        assert a.stream_id == b.stream_id and a.position == b.position
        np.testing.assert_array_equal(np.asarray(a.new_bounds),
                                      np.asarray(b.new_bounds))
    sa, sb = ref.cost_summary(), eng2.cost_summary()
    for key in ("total", "planned", "regret"):
        np.testing.assert_array_equal(sa[key], sb[key])
    oa, ob = ref.obs_snapshot(), eng2.obs_snapshot()
    assert oa["engine"] == ob["engine"]
    assert oa["meter"] == ob["meter"]


def test_checkpoint_with_retired_cost_ledger_restores(tmp_path):
    """A checkpoint that still holds the per-bucket device cost ledger
    (``device["costs"]``: bounds, writes, deletes, resident_steps, int32,
    as fleet steps carried it before the bill was priced from the meter)
    restores onto a costs-on engine: the entry is skipped, and the
    resumed run bills bitwise like the uninterrupted one."""
    from repro.checkpoint.manager import CheckpointManager

    def build():
        return StreamEngine(_specs(),
                            obs=Observability(ObsConfig(costs=True)))

    ref = build()
    make_chunk = _chunk_maker(ref)
    for i in range(8):
        ref.ingest_dense(make_chunk(i))
    eng = build()
    for i in range(5):
        eng.ingest_dense(make_chunk(i))
    tree, meta = fleet_snapshot(eng)
    t = eng.meter.n_tiers
    tree["device"]["costs"] = [
        (np.zeros((b.m, t - 1), np.int32),)
        + tuple(np.ones((b.m, t), np.int32) for _ in range(3))
        for b in eng.buckets]
    CheckpointManager(str(tmp_path)).save(tree, step=5, blocking=True,
                                          extra=meta)
    eng2 = build()
    FleetCheckpointer(str(tmp_path)).restore(eng2)
    assert eng2.chunks_ingested == 5
    for i in range(5, 8):
        eng2.ingest_dense(make_chunk(i))
    _assert_same_finals(ref, eng2)
    assert ref.obs_snapshot()["costs"] == eng2.obs_snapshot()["costs"]


def test_restore_rejects_mismatched_fleet(tmp_path):
    eng = _build("exact")
    make_chunk = _chunk_maker(eng)
    eng.ingest_dense(make_chunk(0))
    tree, meta = fleet_snapshot(eng)
    other = _build("mixed")  # different fleet shape
    with pytest.raises(ValueError, match="does not match"):
        fleet_restore(other, tree, meta)


def test_obs_snapshot_reports_resilience(tmp_path):
    eng = _build()
    ck = FleetCheckpointer(str(tmp_path), every=1, blocking=True)
    eng.attach_checkpointer(ck)
    eng.ingest_dense(_chunk_maker(eng)(0))
    res = eng.obs_snapshot()["resilience"]
    assert res["chunks_ingested"] == 1
    assert res["checkpoint"]["checkpoints_written"] == 1
    assert res["checkpoint"]["latest_step"] == 1
    assert res["failed_tiers"] == []


# ---------------------------------------------------------------------------
# fault injection: at-least-once delivery, exactly-once application
# ---------------------------------------------------------------------------

def test_faulty_delivery_exactly_once():
    """Transients + duplicates + reordering: the guard drops and buffers
    so each chunk applies exactly once — finals bitwise equal a clean
    run, and the harness actually saw every fault kind."""
    ref = _build()
    make_chunk = _chunk_maker(ref)
    for i in range(12):
        ref.ingest_dense(make_chunk(i))

    eng = _build()
    src = FaultyChunkSource(make_chunk, 12, seed=3, transient_rate=0.4,
                            duplicate_rate=0.5, reorder_rate=0.5)
    stats = ingest_with_faults(eng, src, sleep_scale=0.0)
    assert stats["chunks_applied"] == 12
    assert src.failures_injected > 0 and stats["delivery_retries"] > 0
    assert src.duplicates_injected > 0
    assert stats["redeliveries_dropped"] >= src.duplicates_injected
    _assert_same_finals(ref, eng)


def test_fetch_with_retry_backoff_exhausts():
    make = lambda i: []  # noqa: E731 — never reached
    src = FaultyChunkSource(make, 4, seed=5, transient_rate=1.0,
                            max_transient=3)
    # enough attempts: the capped failure count always clears
    fetch_with_retry(src, 0, max_attempts=4, sleep_scale=0.0)
    src2 = FaultyChunkSource(make, 4, seed=5, transient_rate=1.0,
                             max_transient=3)
    with pytest.raises(TransientDeliveryError):
        fetch_with_retry(src2, 0, max_attempts=2, sleep_scale=0.0)


def test_device_loss_recovery_bitwise(tmp_path):
    """Simulated device loss mid-stream: rebuild, restore the last
    checkpoint, replay — the redelivery guard absorbs the replayed
    prefix and the finals are bitwise the uninterrupted run's."""
    ref = _build()
    make_chunk = _chunk_maker(ref)
    for i in range(10):
        ref.ingest_dense(make_chunk(i))

    ck = FleetCheckpointer(str(tmp_path), every=2, blocking=True)
    src = FaultyChunkSource(make_chunk, 10, seed=3, transient_rate=0.3,
                            duplicate_rate=0.3, reorder_rate=0.3,
                            device_loss_at=7)
    eng, stats = run_with_recovery(lambda: _build(), src, ck,
                                   sleep_scale=0.0)
    assert stats["restarts"] == 1
    assert stats["chunks_applied"] >= 10  # pre-crash progress + replay
    _assert_same_finals(ref, eng)


def test_device_loss_without_checkpoint_raises(tmp_path):
    eng = _build()
    make_chunk = _chunk_maker(eng)
    src = FaultyChunkSource(make_chunk, 6, seed=0, device_loss_at=2,
                            max_transient=0)
    with pytest.raises(DeviceLossError):
        ingest_with_faults(eng, src, sleep_scale=0.0)


# ---------------------------------------------------------------------------
# NaN/Inf quarantine (kernel + jitted step regression)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["exact", "logmem"])
def test_nan_scores_quarantined(backend):
    """A NaN/Inf-laced delivery is bitwise a delivery where those slots
    were never sent (demoted to pad), except the quarantine counter —
    non-finite scores must never reach a reservoir or the meter."""
    ref = _build(backend, obs=True)
    eng = _build(backend, obs=True)
    make_chunk = _chunk_maker(ref)
    n_bad = 0
    for i in range(6):
        clean = make_chunk(i)
        laced, blanked = [], []
        r = np.random.default_rng(9000 + i)
        for s, ids in clean:
            s_l, ids_b = s.copy(), ids.copy()
            s_b = s.copy()
            if i % 2 == 0:  # lace every other chunk
                row = int(r.integers(0, s.shape[0]))
                col = int(r.integers(0, s.shape[1]))
                s_l[row, col] = np.nan if i % 4 == 0 else np.inf
                s_b[row, col] = -np.inf
                ids_b[row, col] = -1
                n_bad += 1
            laced.append((s_l, ids))
            blanked.append((s_b, ids_b))
        ref.ingest_dense(blanked)
        eng.ingest_dense(laced)
    assert n_bad > 0
    snap = eng.obs_snapshot()["engine"]
    assert snap["scores_quarantined"] == n_bad
    assert ref.obs_snapshot()["engine"]["scores_quarantined"] == 0
    s_ref, s_eng = ref.finalize(), eng.finalize()
    for sid in s_ref:
        np.testing.assert_array_equal(s_ref[sid], s_eng[sid])
    for key, val in ref.meter.state_dict().items():
        np.testing.assert_array_equal(val, eng.meter.state_dict()[key],
                                      err_msg=f"meter.{key}")


def test_all_finite_input_not_perturbed():
    """The quarantine path is inert on clean data: counter stays zero
    and finals match an engine without the obs layer entirely."""
    plain, obs_eng = _build(obs=False), _build(obs=True)
    make_chunk = _chunk_maker(plain)
    for i in range(5):
        plain.ingest_dense(make_chunk(i))
        obs_eng.ingest_dense(make_chunk(i))
    assert obs_eng.obs_snapshot()["engine"]["scores_quarantined"] == 0
    _assert_same_finals(plain, obs_eng)


def test_faulty_source_laces_and_engine_survives():
    """End-to-end: seeded NaN lacing through the fault source, engine
    quarantines — survivors all finite, counter matches the injection."""
    eng = _build(obs=True)
    make_chunk = _chunk_maker(eng)
    src = FaultyChunkSource(make_chunk, 8, seed=11, nan_rate=0.75,
                            nan_docs=2)
    ingest_with_faults(eng, src, sleep_scale=0.0)
    assert src.nan_injected > 0
    assert (eng.obs_snapshot()["engine"]["scores_quarantined"]
            == src.nan_injected)
    for sid, scores in eng.finalize().items():
        assert np.isfinite(np.asarray(scores)).all() or scores.size == 0


# ---------------------------------------------------------------------------
# tier outage: masked feasible set, evacuation, hysteresis, burn grace
# ---------------------------------------------------------------------------

def _outage_engine():
    """3-tier exact streams with cost attribution on (so the outage's
    burn suppression and planned-credit paths are exercised)."""
    specs = [StreamSpec(stream_id=i, k=8, boundaries=(16.0, 64.0))
             for i in range(3)]
    return StreamEngine(specs, obs=Observability(ObsConfig(costs=True)))


def test_tier_outage_evacuates_and_recovers():
    eng = _outage_engine()
    make_chunk = _chunk_maker(eng)
    for i in range(4):
        eng.ingest_dense(make_chunk(i))
    assert eng.meter.occupancy[:, 1].sum() > 0  # tier 1 is populated
    summary = eng.tier_outage(1)
    assert summary["rows_evacuated"] > 0
    assert eng.meter.occupancy[:, 1].sum() == 0  # evacuated
    assert eng._excluded_tier_set() == frozenset({1})
    # double declaration is idempotent
    again = eng.tier_outage(1)
    assert again.get("already_failed")
    # ingest through the outage: nothing lands on the failed tier
    for i in range(4, 7):
        eng.ingest_dense(make_chunk(i))
    assert eng.meter.occupancy[:, 1].sum() == 0
    eng.tier_recover(1, hysteresis=2)
    assert eng._excluded_tier_set() == frozenset({1})  # flap damping
    for i in range(7, 10):
        eng.ingest_dense(make_chunk(i))
    assert eng._excluded_tier_set() == frozenset()
    res = eng.obs_snapshot()["resilience"]
    assert res["tier_outages"] == 1 and res["failed_tiers"] == []


def test_tier_outage_no_burn_false_fire():
    """The evacuation bill is planned spend, not tenant overspend: the
    burn-rate alert must not fire on the outage's relocation costs."""
    eng = _outage_engine()
    make_chunk = _chunk_maker(eng)
    for i in range(4):
        eng.ingest_dense(make_chunk(i))
    summary = eng.tier_outage(1, burn_grace=8)
    mon = eng._cost_monitor
    evac = np.zeros(eng.m, bool)
    evac[summary["rows"]] = True
    assert (mon.burn_suppressed_until[evac] > mon.steps).all()
    assert summary["bill"] >= 0.0
    for i in range(4, 10):
        eng.ingest_dense(make_chunk(i))
    assert not mon.burn_alerted[evac].any()
    # the bill was credited to planned spend → no phantom regret
    summ = eng.cost_summary()
    assert np.isfinite(summ["regret"]).all()


def test_tier_outage_context_manager():
    eng = _outage_engine()
    make_chunk = _chunk_maker(eng)
    for i in range(3):
        eng.ingest_dense(make_chunk(i))
    with TierOutage(eng, tier=1, hysteresis=1) as out:
        assert out.summary["rows_evacuated"] > 0
        assert 1 in eng._failed_tiers
    assert 1 not in eng._failed_tiers  # recovered on exit
    # recovery applies even when the body raises
    eng2 = _outage_engine()
    for i in range(3):
        eng2.ingest_dense(_chunk_maker(eng2)(i))
    with pytest.raises(RuntimeError, match="drill"):
        with TierOutage(eng2, tier=1):
            raise RuntimeError("drill gone wrong")
    assert 1 not in eng2._failed_tiers


def test_tier_outage_validates_tier():
    eng = _outage_engine()
    eng.ingest_dense(_chunk_maker(eng)(0))
    with pytest.raises(ValueError):
        eng.tier_outage(99)
    with pytest.raises(ValueError):
        eng.tier_recover(1)  # not failed


def test_outage_state_survives_checkpoint(tmp_path):
    """An outage declared before the crash is still masking the tier
    after restore — recovery state is part of the checkpoint."""
    eng = _outage_engine()
    make_chunk = _chunk_maker(eng)
    for i in range(4):
        eng.ingest_dense(make_chunk(i))
    eng.tier_outage(1)
    tree, meta = fleet_snapshot(eng)
    eng2 = _outage_engine()
    fleet_restore(eng2, tree, meta)
    assert eng2._excluded_tier_set() == frozenset({1})
    assert eng2._tier_outages == 1
    for i in range(4, 6):
        eng2.ingest_dense(make_chunk(i))
    assert eng2.meter.occupancy[:, 1].sum() == 0
