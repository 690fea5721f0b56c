"""The plain references agree with the program's own oracles where both
define the same thing (CPU, small sizes): the SHP plan with the NumPy
planner, the case studies' costs with the program's presets, the exact
tenant at one document a chunk with the simulator."""
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

from harness import deploy, source  # noqa: E402
from reference import exact_fleet, plan as plan_ref  # noqa: E402

CFG = os.path.join(BENCH, "configs", "fleet_exact_k1024.json")


def _cases():
    import json
    with open(CFG) as f:
        return json.load(f)["cases"]


def test_plan_matches_the_numpy_planner():
    from repro.core import shp
    cases = _cases()
    which = deploy.deal(source.rng(7, 0), 512, len(cases))
    cw, cr, cs, nv, kv = plan_ref.fleet_costs(cases, which, 1024)
    tot, bounds, mig = plan_ref.plan(cw, cr, cs, nv, kv)
    oracle = shp.plan_ntier_arrays_numpy(cw, cr, cs, nv, kv, np.ones(512))
    np.testing.assert_allclose(tot, oracle["total"], rtol=1e-12)
    np.testing.assert_array_equal(mig, oracle["migrate"])
    assert mig.any() and (~mig).any()
    # the reference prices its own optimum at its total
    np.testing.assert_allclose(
        plan_ref.cost(cw, cr, cs, nv, kv, bounds, mig), tot, rtol=1e-12)


def test_case_costs_match_the_programs_case_studies():
    """The raw prices of the configuration's cases give the per-document
    costs of the program's Table I and Table II presets."""
    from repro.core import costs
    for case, preset in zip(_cases(), (costs.case_study_1(),
                                       costs.case_study_2())):
        nt = preset.as_ntier()
        for mine, theirs in zip(plan_ref.case_costs(case),
                                (nt.cw, nt.cr, nt.cs)):
            np.testing.assert_allclose(mine, theirs, rtol=1e-12)
        assert case["published"] == {"n_docs": preset.workload.n_docs,
                                     "k": preset.workload.k}
        assert abs(case["k_over_n"] - preset.workload.k
                   / preset.workload.n_docs) < 1e-12


def test_exact_tenant_matches_the_simulator_one_doc_a_chunk():
    from repro.core import placement, simulator
    g = np.random.default_rng(11)
    k, n = 8, 300
    scores = g.standard_normal(n).astype(np.float32)
    for bounds, migrate in [((40.0, 120.0), False), ((40.0, 120.0), True),
                            ((0.0, 300.0), False)]:
        chunks = [(scores[i:i + 1], np.array([i])) for i in range(n)]
        ref = exact_fleet.replay(chunks, k, np.asarray(bounds), migrate)
        sim = simulator.simulate(
            scores.astype(np.float64), k,
            placement.Policy(boundaries=bounds, migrate_at_r=migrate))
        np.testing.assert_array_equal(ref["survivors"], sim.survivor_ids)
        np.testing.assert_array_equal(ref["writes"], sim.writes_per_tier)
        np.testing.assert_array_equal(ref["reads"], sim.reads_per_tier)
