"""Cost-attribution observability (repro.obs.costs).

The contract under test, layer by layer:

* pricing the bill must not perturb the computation (bit-identity with
  costs off);
* the bill priced from the meter's integer (stream, tier) counts must
  reconcile — at W=1, where the engine's chunk timing equals the
  simulator's per-doc timing — with the trace-driven simulator's priced
  write/read components bit-exactly (storage to fp tolerance: same
  integer doc-steps, host-priced in one f64 dot product each side), on
  static and on migration-cascade streams;
* the sharded fleet must bill the same as the single-device run
  (row-independent accumulation);
* the ``CostMonitor`` cost-residual / budget burn-rate channels hold
  their false-positive budget on null (undrifted) fleets, and catch a
  genuine overspend (drift into an expensive tier) fast enough to drive
  a cost-triggered re-plan that lowers the realized-cost slope.
"""
import numpy as np
import pytest

import jax

from repro.core import constraints as cons, costs as cc, simulator
from repro.obs import Observability, ObsConfig
from repro.obs import costs as costs_mod
from repro.online import DriftConfig, ReplanConfig, evaluate
from repro.streams.engine import StreamEngine, StreamSpec

needs_mesh = pytest.mark.skipif(
    jax.local_device_count() < 2,
    reason="needs a multi-device mesh (XLA_FLAGS="
           "--xla_force_host_platform_device_count=8)")


def _w1_fleet(n=512, k=8, m=3, seed=0, engines=None, cascade_at=None):
    """Per-doc (W=1) ingest: engine chunk timing == simulator timing.
    ``cascade_at`` places every stream explicitly, one boundary there
    with the migration cascade on (priced by the same cost model)."""
    cm = cc.hbm_host_preset(n_docs=n, k=k, doc_gb=1e-4, window_seconds=60.0)
    rng = np.random.default_rng(seed)
    traces = [simulator.random_rank_trace(n, rng) for _ in range(m)]
    place = ({} if cascade_at is None
             else {"boundaries": (float(cascade_at),), "migrate": True})
    specs = [StreamSpec(stream_id=i, k=k, cost_model=cm,
                        engine=engines[i] if engines else "exact", **place)
             for i in range(m)]
    return cm, traces, specs


def _run_w1(traces, specs, mesh=None, trail=None):
    """Ingest one doc per stream per step; ``trail`` (a list) collects
    the meter's cumulative (M, T) writes after every step."""
    m, n = len(traces), len(traces[0])
    obs = Observability(ObsConfig(costs=True))
    eng = StreamEngine(specs, obs=obs, mesh=mesh)
    for pos in range(n):
        eng.ingest(np.arange(m),
                   np.array([t[pos] for t in traces], np.float32),
                   np.full(m, pos, np.int64))
        if trail is not None:
            trail.append(eng.meter.writes.copy())
    eng.finalize()
    return eng


# ---------------------------------------------------------------------------
# bit-identity
# ---------------------------------------------------------------------------

def test_costs_off_and_on_bit_identical_output():
    """Pricing the bill must not change survivors, reservoir state, or
    the meter ledger — the bill only reads the meter's counts."""
    rng = np.random.default_rng(11)
    n, m, k = 2048, 5, 16
    traces = rng.standard_normal((m, n)).astype(np.float32)
    specs = [StreamSpec(stream_id=i, k=k, r=600.0) for i in range(m)]

    def run(obs):
        eng = StreamEngine(specs, obs=obs)
        sids = np.arange(m)
        for t0 in range(0, n, 64):
            eng.ingest(np.repeat(sids, 64),
                       traces[:, t0:t0 + 64].reshape(-1),
                       np.tile(np.arange(t0, t0 + 64), m))
        return eng, eng.finalize()

    e_off, s_off = run(Observability(ObsConfig()))
    e_on, s_on = run(Observability(ObsConfig(costs=True)))
    assert sorted(s_off) == sorted(s_on)
    for sid in s_off:
        np.testing.assert_array_equal(s_off[sid], s_on[sid])
    np.testing.assert_array_equal(e_off.meter.writes, e_on.meter.writes)
    np.testing.assert_array_equal(e_off.meter.deletes, e_on.meter.deletes)
    for b_off, b_on in zip(e_off._states, e_on._states):
        np.testing.assert_array_equal(np.asarray(b_off.ids),
                                      np.asarray(b_on.ids))
        np.testing.assert_array_equal(np.asarray(b_off.scores),
                                      np.asarray(b_on.scores))


# ---------------------------------------------------------------------------
# bill reconciliation: meter-priced bill == simulator
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cascade_at", [None, 200],
                         ids=["static", "cascade"])
def test_cost_ledger_reconciles_with_simulator_at_w1(cascade_at):
    """Exact engine, one doc per ingest: the bill priced from the meter's
    counts equals the trace-driven simulator's — writes and reads
    bit-exactly on static streams (identical integers through identical
    f64 dot products), storage to 1e-9 of the identical integer doc-step
    rental. On cascade streams the write, read and hop counts are equal
    and their prices agree to 1e-12, and the residents are billed where
    they live: doc-steps equal the simulator's in total, and per tier to
    within K per boundary crossed (the meter settles the hop before the
    crossing step's rent accrues, the simulator after)."""
    n, k = 512, 8
    cm, traces, specs = _w1_fleet(n=n, k=k, m=3, seed=0,
                                  cascade_at=cascade_at)
    eng = _run_w1(traces, specs)
    summ = eng.cost_summary()
    dev = summ["device"]
    nt = cm if isinstance(cm, cc.NTierCostModel) else cm.as_ntier()
    slot = nt.workload.window_months / n
    depth = int(np.isfinite(eng.meter.boundaries[0]).sum())
    for i, t in enumerate(traces):
        res = evaluate.realized(t, k, cm,
                                tuple(eng.meter.boundaries[i][:depth]),
                                migrate=cascade_at is not None)
        np.testing.assert_array_equal(res.writes_per_tier,
                                      eng.meter.writes[i])
        np.testing.assert_array_equal(res.writes_per_tier, dev["writes"][i])
        np.testing.assert_array_equal(res.reads_per_tier, eng.meter.reads[i])
        assert res.migrated == eng.meter.migrations[i]
        dm = np.rint(res.doc_months_per_tier / slot).astype(np.int64)
        steps = dev["resident_steps"][i]
        assert dm.sum() == steps.sum()
        if cascade_at is None:
            assert res.cost_writes == summ["writes"][i]
            assert res.cost_reads == summ["reads"][i]
            assert res.cost_migration == summ["migration"][i] == 0.0
            np.testing.assert_array_equal(dm, steps)
            assert np.isclose(res.cost_storage, summ["storage"][i],
                              rtol=1e-9)
            assert np.isclose(res.cost_total, summ["total"][i], rtol=1e-9)
        else:
            # the counts under them are equal (above); the simulator's
            # dot product may round a two-term sum differently
            for name in ("writes", "reads", "migration"):
                assert np.isclose(getattr(res, f"cost_{name}"),
                                  summ[name][i], rtol=1e-12, atol=0.0)
            crossed = int(eng.meter.floor[i])
            assert crossed == depth and res.migrated > 0
            assert np.abs(dm - steps).max() <= k * crossed
            # so the storage bill is off by at most K doc-steps per
            # boundary priced at the spread of the tiers' step rates
            rate = eng._pricing["step_rate"][i]
            assert np.isclose(rate @ dm, res.cost_storage, rtol=1e-9)
            assert abs(summ["storage"][i] - res.cost_storage) \
                <= k * crossed * np.ptp(rate) * (1 + 1e-9)


def test_logmem_ledger_reconciles_with_meter_at_w1():
    """Logmem rows store no ids, so occupancy is cumulative writes: the
    storage bill is the rental of the cumulative writes after every
    ingested doc, priced at the per-step rate; the write bill prices the
    meter's writes, and nothing deletes."""
    cm, traces, specs = _w1_fleet(n=512, k=16, m=4, seed=2,
                                  engines=["logmem"] * 4)
    trail = []
    eng = _run_w1(traces, specs, trail=trail)
    summ = eng.cost_summary()
    p = eng._pricing
    assert int(summ["device"]["deletes"].sum()) == 0
    assert summ["device"]["writes"].sum() > 0
    rental = np.sum(trail, axis=0)
    np.testing.assert_array_equal(summ["device"]["resident_steps"], rental)
    np.testing.assert_array_equal(summ["writes"],
                                  (trail[-1] * p["cw"]).sum(1))
    np.testing.assert_array_equal(summ["storage"],
                                  (rental * p["step_rate"]).sum(1))


@needs_mesh
def test_sharded_cost_ledger_matches_unsharded():
    """The meter's rows shard with the fleet axis; the sharded fleet must
    bill the same per-stream components, and give the same priced
    snapshot, as the single-device run — on a mixed exact/logmem
    fleet."""
    from repro.parallel import fleet
    n, k, m = 512, 16, 8
    cm, traces, _ = _w1_fleet(n=n, k=k, m=m, seed=1)
    specs = [StreamSpec(stream_id=i, k=k, cost_model=cm,
                        engine="logmem" if i % 2 else "exact")
             for i in range(m)]
    e1 = _run_w1(traces, specs)
    e2 = _run_w1(traces, specs,
                 mesh=fleet.fleet_mesh(min(jax.local_device_count(), 8)))
    s1, s2 = e1.cost_summary(), e2.cost_summary()
    for name in ("writes", "reads", "storage", "migration", "total"):
        np.testing.assert_array_equal(s1[name], s2[name])
    for name in s1["device"]:
        np.testing.assert_array_equal(s1["device"][name], s2["device"][name])
    assert e1.obs_snapshot()["costs"] == e2.obs_snapshot()["costs"]


# ---------------------------------------------------------------------------
# CostMonitor: null FPR and the overspend -> re-plan chain
# ---------------------------------------------------------------------------

def _cost_null_fpr(seed: int, alpha: float, m: int = 48) -> float:
    """Fraction of null (i.u.d.) priced streams either cost channel
    (residual or budget burn) flags across a full window, engine-fed."""
    n, k = 4096, 16
    cm = cc.hbm_host_preset(n_docs=n, k=k, doc_gb=1e-4, window_seconds=60.0)
    rng = np.random.default_rng(seed)
    traces = np.stack([simulator.random_rank_trace(n, rng)
                       for _ in range(m)])
    specs = [StreamSpec(stream_id=i, k=k, cost_model=cm) for i in range(m)]
    obs = Observability(ObsConfig(costs=True, cost_alpha=alpha))
    eng = StreamEngine(specs, obs=obs)
    sids = np.arange(m)
    for t0 in range(0, n, 64):
        eng.ingest(np.repeat(sids, 64), traces[:, t0:t0 + 64].reshape(-1),
                   np.tile(np.arange(t0, t0 + 64), m))
    mon = eng._cost_monitor
    return float((mon.alerted | mon.burn_alerted).mean())


@pytest.mark.parametrize("seed,alpha", [(0, 0.05), (1, 0.01)])
def test_cost_monitor_null_fpr(seed, alpha):
    assert _cost_null_fpr(seed, alpha) <= alpha


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @given(seed=st.integers(min_value=0, max_value=2 ** 16))
    @settings(max_examples=5, deadline=None)
    def test_cost_monitor_null_fpr_property(seed):
        assert _cost_null_fpr(seed, 0.05) <= 0.05


def test_budget_burn_drives_replan_and_bends_cost_curve():
    """The acceptance chain: tenants drift into an expensive-write cold
    tier, the budget burn-rate rule fires, the alert (not the near-blind
    drift detector) triggers the suffix re-solve, and the post-re-plan
    realized-cost slope drops below the pre-re-plan slope."""
    m, n, k, drift_at, chunk = 4, 12000, 64, 3000, 64
    wl = cc.WorkloadSpec(n_docs=n, k=k, doc_gb=1e-4, window_months=0.5)
    hot = cc.TierCosts("hot", put_per_doc=1e-6, get_per_doc=2.7e-4,
                       storage_per_gb_month=0.05)
    cold = cc.TierCosts("cold", put_per_doc=8e-5, get_per_doc=1e-6,
                        storage_per_gb_month=0.02)
    cm = cc.TwoTierCostModel(tier_a=hot, tier_b=cold, workload=wl)
    rng = np.random.default_rng(7)
    drifted = np.array([i < m // 2 for i in range(m)])
    traces = np.stack([
        simulator.drifted_rank_trace(n, rng, [(drift_at, 8.0)])
        if drifted[i] else simulator.random_rank_trace(n, rng)
        for i in range(m)])
    specs = [StreamSpec(stream_id=i, k=k, cost_model=cm) for i in range(m)]
    obs = Observability(ObsConfig(costs=True, cost_trigger=True,
                                  cost_alpha=0.01))
    eng = StreamEngine(
        specs, obs=obs,
        constraints=cons.ConstraintSet(cons.TierCapacity(0, 4 * k)),
        replan=ReplanConfig(drift=DriftConfig(alpha=1e-9)))
    sids = np.arange(m)
    realized = []
    for t0 in range(0, n, chunk):
        c = min(chunk, n - t0)
        eng.ingest(np.repeat(sids, c), traces[:, t0:t0 + c].reshape(-1),
                   np.tile(t0 + np.arange(c), m))
        realized.append(eng._cost_monitor.realized_total[drifted].sum())
    eng.finalize()
    realized = np.asarray(realized)

    events = obs.tracer.events
    fired = [e["attrs"] for e in events
             if e["name"] in ("cost_alert", "budget_burn")]
    assert any(drifted[a["row"]] for a in fired), \
        "no cost/burn alert on a drifted stream"
    applied = [e["attrs"] for e in events
               if e["name"] == "replan_decision"
               and e["attrs"]["cost_triggered"] and e["attrs"]["applied"]]
    assert applied, "no applied re-plan was cost-triggered"
    rc = min(min(a["position"] for a in applied) // chunk,
             len(realized) - 3)
    dc = drift_at // chunk
    pre = (realized[rc] - realized[dc]) / max(rc - dc, 1)
    post = (realized[-1] - realized[rc + 1]) / max(len(realized) - rc - 2, 1)
    assert post < pre, (pre, post)
    # alerts surface through the public API with their channel
    kinds = {v["kind"] for v in eng.cost_alerts().values()}
    assert kinds <= {"residual", "burn"} and kinds


def test_expected_cost_trajectory_matches_simulator_mean():
    """The closed-form planned write+storage trajectory tracks the
    realized i.u.d. bill: terminal value within a few sigma (Monte Carlo
    over seeds would be exact; one seed stays within 15%)."""
    n, k = 512, 8
    cm, traces, specs = _w1_fleet(n=n, k=k, m=3, seed=4)
    eng = _run_w1(traces, specs)
    nt = cm if isinstance(cm, cc.NTierCostModel) else cm.as_ntier()
    pricing = costs_mod.stream_pricing(eng)
    depth = int(np.isfinite(eng.meter.boundaries[0]).sum())
    traj = costs_mod.expected_cost_trajectory(
        eng.meter.boundaries[0][:depth], n, k,
        pricing["cw"][0], pricing["step_rate"][0])
    assert traj.shape == (n,)
    assert np.all(np.diff(traj) >= -1e-12)  # cumulative, non-decreasing
    summ = eng.cost_summary()
    realized_ws = summ["writes"] + summ["storage"]
    assert np.isclose(traj[-1], np.mean(realized_ws), rtol=0.15)


def test_cost_monitor_snapshot_and_export_shape():
    """The costs block is scalars-only (Prometheus-exportable) and the
    counter leaves are typed counters in the exposition."""
    from repro.obs import export
    cm, traces, specs = _w1_fleet(n=256, k=8, m=2, seed=3)
    eng = _run_w1(traces, specs)
    obs = eng._obs
    snap = eng.obs_snapshot()["costs"]
    for group in ("realized", "regret", "device", "alerts"):
        assert all(np.isscalar(v) or isinstance(v, (int, float))
                   for v in snap[group].values()), group
    text = obs.prometheus()
    assert ("# TYPE repro_obs_engines_engine0_costs_device_resident_steps "
            "counter") in text
    assert "costs_realized_total" in text
