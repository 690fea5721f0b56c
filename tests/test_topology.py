"""N-tier topology stack (core/topology.py + the multi-threshold planner):
closed form vs brute force, exact T=2 backward compatibility, boundary-
vector policies/stores/simulator, and mixed-depth fleets."""
import math

import numpy as np
import pytest

from repro.core import costs, placement, shp, simulator, tiers, topology
from repro.streams import StreamEngine, StreamSpec, planner


def random_ntier_model(rng, t):
    n = int(rng.integers(2_000, 200_000))
    k = int(rng.integers(1, max(2, n // 10)))
    specs = tuple(
        topology.TierSpec(
            costs.TierCosts(f"t{i}", *(10.0 ** rng.uniform(-8, -3, 3))),
            xfer_in_per_gb=float(10.0 ** rng.uniform(-7, -3)),
            xfer_out_per_gb=float(10.0 ** rng.uniform(-6, -2)))
        for i in range(t))
    wl = costs.WorkloadSpec(n_docs=n, k=k,
                            doc_gb=float(rng.uniform(1e-4, 1.0)),
                            window_months=float(rng.uniform(0.03, 3.0)))
    return topology.TierTopology(tiers=specs).cost_model(wl)


# ---------------------------------------------------------------------------
# T=2 backward compatibility: the N-tier path reproduces the paper exactly
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", [costs.case_study_1, costs.case_study_2])
def test_as_ntier_cost_vectors_bit_identical(case):
    cm = case()
    nt = cm.as_ntier()
    assert nt.t == 2
    np.testing.assert_array_equal(nt.cw, [cm.cw_a, cm.cw_b])
    np.testing.assert_array_equal(nt.cr, [cm.cr_a, cm.cr_b])
    np.testing.assert_array_equal(nt.cs, [cm.cs_a, cm.cs_b])
    assert nt.cs_max == cm.cs_max
    assert float(nt.migration_per_boundary[0]) == cm.migration_per_doc


@pytest.mark.parametrize("case", [costs.case_study_1, costs.case_study_2])
def test_case_studies_identical_through_ntier_path(case):
    """The acceptance bar: same chosen strategy, same printed totals, and
    per-strategy costs matching at every valid r."""
    cm = case()
    nt = cm.as_ntier()
    legacy = shp.plan_placement(cm)
    npl = shp.plan_placement(nt)
    assert isinstance(npl, shp.NTierPlacementPlan)
    assert npl.strategy == legacy.strategy
    assert f"{npl.total:.2f}" == f"{legacy.best.total:.2f}"
    assert math.isclose(npl.total, legacy.best.total, rel_tol=1e-9)
    assert math.isclose(npl.boundaries[0], legacy.r, rel_tol=1e-9)
    n = cm.workload.n_docs
    for r in [cm.workload.k + 1.0, n / 3, n / 2, n - 1.0]:
        two = shp.cost_no_migration(cm, r).total
        gen = shp.cost_ntier_no_migration(nt, (r,)).total
        assert math.isclose(two, gen, rel_tol=1e-12), (r, two, gen)
        two = shp.cost_with_migration(cm, r).total
        gen = shp.cost_ntier_migration(nt, (r,)).total
        assert math.isclose(two, gen, rel_tol=1e-12), (r, two, gen)


def test_ntier_policy_from_plan_matches_two_tier_policy():
    for case in (costs.case_study_1, costs.case_study_2):
        cm = case()
        pol2 = placement.optimal_policy(cm)
        poln = placement.optimal_policy(cm.as_ntier())
        assert poln.n_tiers == 2
        assert math.isclose(poln.boundaries[0], pol2.r, rel_tol=1e-9)
        assert poln.migrate_at_r == pol2.migrate_at_r


# ---------------------------------------------------------------------------
# N-tier correctness: closed form vs brute-force grid search
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,seed,count", [(2, 0, 20), (3, 1, 60), (4, 2, 40)])
def test_closed_form_matches_brute_force(t, seed, count):
    """>= 100 random 3- and 4-tier models in total (plus T=2 sanity): the
    DP optimum must never lose to the grid, and must match it within grid
    resolution."""
    rng = np.random.default_rng(seed)
    for trial in range(count):
        m = random_ntier_model(rng, t)
        plan = shp.plan_placement_ntier(m)
        bt, bb, bm = shp.brute_force_plan_ntier(m, grid=48)
        assert np.isfinite(plan.total)
        assert plan.total <= bt * (1 + 1e-9) + 1e-12, \
            (t, trial, plan.total, bt, plan.strategy)
        assert abs(plan.total - bt) <= 2e-2 * abs(bt) + 1e-12, \
            (t, trial, plan.total, bt)
        assert all(b1 <= b2 for b1, b2 in
                   zip(plan.boundaries, plan.boundaries[1:]))


def test_duplicate_tier_collapses_to_two_tier_plan():
    """A topology with a duplicated middle tier must plan no worse than the
    two-tier topology it degenerates to, without inf/nan."""
    rng = np.random.default_rng(7)
    for _ in range(20):
        m2 = random_ntier_model(rng, 2)
        a, b = m2.topology.tiers
        m3 = topology.TierTopology(tiers=(a, a, b)).cost_model(m2.workload)
        p2 = shp.plan_placement_ntier(m2)
        p3 = shp.plan_placement_ntier(m3)
        assert np.isfinite(p3.total)
        assert p3.total <= p2.total * (1 + 1e-9) + 1e-12


def test_plan_ntier_batch_matches_scalar():
    rng = np.random.default_rng(11)
    models = [random_ntier_model(rng, 3) for _ in range(32)]
    out = shp.solve_ntier_batch(models)
    tot, bounds, mig = out["total"], out["bounds"], out["migrate"]
    strats = shp.ntier_strategies(out, models)
    for i, m in enumerate(models):
        p = shp.plan_placement_ntier(m)
        assert strats[i] == p.strategy
        np.testing.assert_allclose(tot[i], p.total, rtol=1e-9)
        np.testing.assert_allclose(bounds[i], p.boundaries, rtol=1e-9,
                                   atol=1e-9)
        assert bool(mig[i]) == p.migrate


def test_efs_s3_glacier_produces_three_tier_migration_plan():
    topo = topology.aws_efs_s3_glacier()
    wl = costs.WorkloadSpec(n_docs=int(1e8), k=int(1e5), doc_gb=1e-3,
                            window_months=3.0)
    plan = shp.plan_placement_ntier(topo.cost_model(wl))
    assert plan.migrate and plan.strategy == "ntier_migration"
    widths = np.diff([0.0, *plan.boundaries, wl.n_docs])
    assert np.all(widths > 0)  # all three tiers genuinely used


def test_s3_lifecycle_gate_collapses_ia_tier():
    """Standard -> Standard-IA -> Glacier-IR: IA's per-request touch cost
    always outweighs its rental edge, so the optimal cascade skips it —
    the N-tier validity gate collapsing a degenerate tier."""
    topo = topology.aws_s3_tiering()
    wl = costs.WorkloadSpec(n_docs=int(1e8), k=int(1e5), doc_gb=1e-3,
                            window_months=3.0)
    plan = shp.plan_placement_ntier(topo.cost_model(wl))
    widths = np.diff([0.0, *plan.boundaries, wl.n_docs])
    assert widths[1] == 0.0  # IA never used
    assert plan.migrate  # but Standard -> Glacier still cascades


# ---------------------------------------------------------------------------
# Boundary-vector Policy
# ---------------------------------------------------------------------------

def test_policy_boundary_vector_semantics():
    pol = placement.Policy(boundaries=(4.0, 9.0))
    assert pol.n_tiers == 3
    assert [pol.tier_of(i) for i in (0, 3, 4, 8, 9, 100)] == [0, 0, 1, 1, 2, 2]
    assert pol.r == 4.0  # two-tier shim: the first boundary
    assert pol.migration_indices() == ()
    mig = placement.Policy(boundaries=(4.5, 9.0), migrate_at_r=True)
    assert mig.migration_indices() == (5, 9)
    assert mig.migration_index() == 5
    legacy = placement.Policy(r=7.0)
    assert legacy.boundaries == (7.0,)
    assert legacy.tier_of(6) == placement.TIER_A
    assert legacy.tier_of(7) == placement.TIER_B
    with pytest.raises(ValueError):
        placement.Policy(boundaries=(5.0, 3.0))
    with pytest.raises(ValueError):
        placement.Policy()


# ---------------------------------------------------------------------------
# Three-tier TieredStore cascade
# ---------------------------------------------------------------------------

def test_tiered_store_three_tier_cascade(tmp_path):
    import jax.numpy as jnp
    pol = placement.Policy(boundaries=(3.0, 6.0), migrate_at_r=True)
    store = tiers.TieredStore(
        pol, tiers.HotTier(k=8, payload_shape=(2,), dtype=jnp.float32),
        tiers.ColdTier(), tiers.ColdTier(directory=str(tmp_path)))
    assert store.n_tiers == 3 and store.ledger.n_tiers == 3
    for i in range(3):
        assert store.write(i, jnp.full((2,), float(i))) == 0
    assert store.maybe_migrate(2) == 0  # before the first boundary
    assert store.maybe_migrate(3) == 3  # tier 0 -> tier 1
    assert [store.tier_index_of(i) for i in range(3)] == [1, 1, 1]
    assert store.write(4, jnp.full((2,), 4.0)) == 1  # floor lifts placement
    assert store.maybe_migrate(6) == 4  # tier 1 -> tier 2
    assert [store.tier_index_of(i) for i in (0, 1, 2, 4)] == [2, 2, 2, 2]
    assert store.write(7, jnp.full((2,), 7.0)) == 2
    assert store.ledger.migrations == 7
    # tier 1: 3 cascade hops + direct write of doc 4; tier 2: 4 hops + doc 7
    assert store.ledger.writes.tolist() == [3, 3 + 1, 4 + 1]
    got = store.read_all([0, 4, 7])
    np.testing.assert_allclose(np.asarray(got[4]), 4.0)


def test_tiered_store_coincident_boundaries_skip_empty_tier():
    import jax.numpy as jnp
    pol = placement.Policy(boundaries=(2.0, 2.0), migrate_at_r=True)
    store = tiers.TieredStore(
        pol, tiers.HotTier(k=4, payload_shape=(1,), dtype=jnp.float32),
        tiers.ColdTier(), tiers.ColdTier())
    store.write(0, jnp.zeros((1,)))
    store.write(1, jnp.zeros((1,)))
    # both boundaries fire at i=2: docs hop 0 -> 2 directly, skipping the
    # zero-width middle tier (one charged hop each, matching the planner)
    assert store.maybe_migrate(2) == 2
    assert store.tier_index_of(0) == 2
    assert store.ledger.migrations == 2
    assert store.ledger.writes.tolist() == [2, 0, 2]
    assert store.ledger.reads.tolist() == [2, 0, 0]


# ---------------------------------------------------------------------------
# Simulator: 3-tier reconciliation against the analytic expectations
# ---------------------------------------------------------------------------

def three_tier_sim_model(n=30_000, k=300):
    topo = topology.aws_s3_tiering()
    wl = costs.WorkloadSpec(n_docs=n, k=k, doc_gb=1e-3, window_months=6.0)
    return topo.cost_model(wl)


def test_simulator_three_tier_writes_match_analytic_per_tier():
    m = three_tier_sim_model()
    n, k = m.workload.n_docs, m.workload.k
    bounds = (0.08 * n, 0.2 * n)
    pol = placement.Policy(boundaries=bounds)
    rng = np.random.default_rng(17)
    writes = np.zeros(3)
    trials = 6
    for _ in range(trials):
        res = simulator.simulate(simulator.random_rank_trace(n, rng), k, pol, m)
        writes += res.writes_per_tier
    writes /= trials
    edges = np.array([0.0, *bounds, float(n)])
    exact = np.diff(np.where(edges > 0,
                             shp.expected_cum_writes(edges - 1.0, k), 0.0))
    np.testing.assert_allclose(writes, exact, rtol=0.08)


def test_simulator_three_tier_migration_cost_reconciles():
    m = three_tier_sim_model()
    n, k = m.workload.n_docs, m.workload.k
    bounds = (0.08 * n, 0.2 * n)
    pol = placement.Policy(boundaries=bounds, migrate_at_r=True)
    rng = np.random.default_rng(23)
    totals = []
    for _ in range(4):
        res = simulator.simulate(simulator.random_rank_trace(n, rng), k,
                                 pol, m)
        # each cascade moves the (full) reservoir: K hops per boundary
        np.testing.assert_array_equal(res.migrated_per_boundary, [k, k])
        assert res.reads_per_tier.tolist()[:2] == [0, 0]  # all reads last tier
        totals.append(res.cost_total - res.cost_reads)  # eq. 20 convention
    expected = shp.cost_ntier_migration(m, bounds, exact=True).total
    assert abs(np.mean(totals) - expected) / expected < 0.12


def test_simulator_rejects_policy_deeper_than_cost_model():
    m = costs.case_study_1()
    pol = placement.Policy(boundaries=(10.0, 20.0))
    with pytest.raises(ValueError):
        simulator.simulate(np.arange(100.0), 5, pol, m)


# ---------------------------------------------------------------------------
# Mixed-depth fleets: engine + meter vs independent simulator replays
# ---------------------------------------------------------------------------

def test_engine_mixed_two_and_three_tier_matches_simulator():
    rng = np.random.default_rng(42)
    docs, k = 64, 4
    specs = [
        StreamSpec(stream_id=0, k=k, r=float(docs / 3)),
        StreamSpec(stream_id=1, k=k, boundaries=(16.0, 40.0), migrate=True),
        StreamSpec(stream_id=2, k=k, boundaries=(10.0, 30.0)),
        StreamSpec(stream_id=3, k=k, r=float(docs / 2), migrate=True),
    ]
    eng = StreamEngine(specs)
    traces = np.stack([simulator.random_rank_trace(docs, rng)
                       for _ in specs]).astype(np.float32)
    for t in range(docs):
        eng.ingest([s.stream_id for s in specs], traces[:, t],
                   [t] * len(specs))
    eng.finalize()
    for i, s in enumerate(specs):
        pol = placement.Policy(boundaries=s.explicit_boundaries(),
                               migrate_at_r=s.migrate)
        sim = simulator.simulate(traces[i].astype(np.float64), k, pol)
        led = eng.meter.ledger(eng.stream_row(s.stream_id))
        t_sim = sim.writes_per_tier.shape[0]
        assert led.writes[:t_sim].tolist() == sim.writes_per_tier.tolist()
        assert led.writes[t_sim:].sum() == 0
        assert led.reads[:t_sim].tolist() == sim.reads_per_tier.tolist()
        assert led.migrations == sim.migrated


def test_engine_placements_boundary_vectors():
    """Per-slot tier assignment with per-stream boundary vectors, including
    the meter's +inf padding for shallower streams."""
    import jax.numpy as jnp
    from repro.streams import engine
    state = engine.init(2, 4)
    state, _ = engine.update(
        state, jnp.array([[4.0, 3.0, 2.0, 1.0]] * 2, jnp.float32),
        jnp.array([[0, 5, 10, 15]] * 2, jnp.int32))
    b = jnp.array([[6.0, 12.0], [8.0, jnp.inf]], jnp.float32)
    tiers_out = np.asarray(engine.placements(state, b))
    by_id = [dict(zip(np.asarray(state.ids[r]).tolist(), tiers_out[r]))
             for r in range(2)]
    assert [by_id[0][i] for i in (0, 5, 10, 15)] == [0, 0, 1, 2]
    assert [by_id[1][i] for i in (0, 5, 10, 15)] == [0, 0, 1, 1]  # inf pad
    # scalar per-stream r still works
    scalar = np.asarray(engine.placements(state, jnp.array([6.0, 11.0])))
    by_id0 = dict(zip(np.asarray(state.ids[0]).tolist(), scalar[0]))
    assert [by_id0[i] for i in (0, 5, 10, 15)] == [0, 0, 1, 1]


def test_meter_three_tier_static_accounting():
    docs = 9
    eng = StreamEngine([StreamSpec(stream_id=0, k=2, boundaries=(3.0, 6.0))])
    for t in range(docs):  # ascending scores: every doc writes
        eng.ingest([0], [float(t)], [t])
    eng.finalize()
    led = eng.meter.ledger(0)
    assert led.writes.tolist() == [3, 3, 3]
    # evicted docs 0..6: three lived in tier 0, three in tier 1, one in 2
    assert led.deletes.tolist() == [3, 3, 1]
    assert led.reads.tolist() == [0, 0, 2]  # survivors 7, 8


def test_plan_fleet_mixed_agrees_with_scalar_planners():
    rng = np.random.default_rng(3)
    models = []
    for i in range(24):
        if i % 3 == 0:
            models.append(random_ntier_model(rng, 3))
        elif i % 3 == 1:
            models.append(random_ntier_model(rng, 4))
        else:
            n = int(rng.integers(2_000, 100_000))
            wl = costs.WorkloadSpec(n_docs=n,
                                    k=int(rng.integers(1, n // 10)),
                                    doc_gb=1.0, window_months=1.0)
            models.append(costs.TwoTierCostModel(
                tier_a=costs.TierCosts("a", *(rng.uniform(1e-8, 1e-3, 3))),
                tier_b=costs.TierCosts("b", *(rng.uniform(1e-8, 1e-3, 3))),
                workload=wl))
    plan = planner.plan_fleet_mixed(models)
    assert plan.m == len(models)
    hist = plan.strategy_histogram()
    assert sum(hist.values()) == len(models)
    for i, cm in enumerate(models):
        ref = shp.plan_placement(cm)
        if isinstance(cm, costs.TwoTierCostModel):
            assert plan.strategy(i) == ref.strategy
            np.testing.assert_allclose(plan.totals[i], ref.best.total,
                                       rtol=1e-9)
            assert len(plan.boundaries[i]) == 1
        else:
            assert plan.strategy(i) == ref.strategy
            np.testing.assert_allclose(plan.totals[i], ref.total, rtol=1e-9)
            np.testing.assert_allclose(plan.boundaries[i], ref.boundaries,
                                       rtol=1e-9, atol=1e-9)
        pol = plan.policy(i)
        assert pol.migrate_at_r == plan.migrate(i)


def test_engine_planned_mixed_fleet_runs_end_to_end():
    docs, k = 96, 4
    specs = []
    for i in range(6):
        if i % 2 == 0:
            cm = costs.hbm_host_preset(n_docs=docs, k=k, doc_gb=1e-5,
                                       window_seconds=60.0 * (1 + i))
        else:
            cm = topology.hbm_dram_disk_preset(n_docs=docs, k=k, doc_gb=1e-5,
                                               window_seconds=60.0 * (1 + i))
        specs.append(StreamSpec(stream_id=i, k=k, cost_model=cm))
    eng = StreamEngine(specs)
    assert eng.plan is not None and eng.plan.m == 6
    rng = np.random.default_rng(5)
    traces = np.stack([simulator.random_rank_trace(docs, rng)
                       for _ in specs]).astype(np.float32)
    for t in range(docs):
        eng.ingest(np.arange(6), traces[:, t], np.full(6, t))
    survivors = eng.finalize()
    for i in range(6):
        pol = eng.plan.policy(i)
        sim = simulator.simulate(traces[i].astype(np.float64), k, pol)
        np.testing.assert_array_equal(survivors[i], sim.survivor_ids)
        led = eng.meter.ledger(eng.stream_row(i))
        t_sim = sim.writes_per_tier.shape[0]
        assert led.writes[:t_sim].tolist() == sim.writes_per_tier.tolist()
        assert led.migrations == sim.migrated
