"""The fleet step meters each chunk on the device (``metering.fold``) and
the host adds the per-(stream, tier) counts (``FleetMeter.record_update``).
Every case runs an engine beside the plain scatter reference
(``meter_reference``: per-document tier attribution and ``np.add.at``
over the chunk's write mask, evictions and reservoir ids) and asserts the
two meters equal, array for array: observed, writes, deletes, occupancy
and its high-water mark, doc-steps, migrations and hop reads/writes, the
cascade floor, relocations and the final reads.

Cases by bucket kind (exact wide W >= K, exact narrow W < K, logmem),
tier depth (2, 3, 4), cascading and static tenants, a mid-stream
re-plan, a tier outage with evacuation, non-finite scores, an unmetered
chunk, and a 4-device CPU mesh (a subprocess with
``--xla_force_host_platform_device_count=4``)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

import meter_reference
from repro.core import costs, simulator, topk
from repro.obs import Observability, ObsConfig
from repro.online import DriftConfig, ReplanConfig
from repro.streams import StreamEngine, StreamSpec, metering


def _dense(eng, c, w, rng, nonfinite=False):
    """Chunk ``c`` of width ``w``: every stream sends positions c*w ...;
    with ``nonfinite`` a few scores are NaN or +-inf."""
    out = []
    for b in eng.buckets:
        s = rng.standard_normal((b.m, w)).astype(np.float32)
        if nonfinite:
            bad = rng.random((b.m, w)) < 0.1
            s[bad] = rng.choice(np.array([np.nan, np.inf, -np.inf],
                                         np.float32), bad.sum())
        i = np.broadcast_to(np.arange(c * w, (c + 1) * w, dtype=np.int32),
                            (b.m, w)).copy()
        out.append((s, i))
    return out


def _routed(eng, c, w, rng):
    """Chunk ``c`` as one shuffled mixed batch of (stream, score, doc)
    triples through the router."""
    sids = np.array([sid for b in eng.buckets for sid in b.stream_ids])
    mixed_sids = np.repeat(sids, w)
    dids = np.tile(np.arange(c * w, (c + 1) * w), sids.size)
    scores = rng.standard_normal(mixed_sids.size)
    perm = rng.permutation(mixed_sids.size)
    eng.ingest(mixed_sids[perm], scores[perm], dids[perm])


def _two_tier_model(n, k):
    wl = costs.WorkloadSpec(n_docs=n, k=k, doc_gb=1e-4, window_months=0.5)
    hot = costs.TierCosts("hot", put_per_doc=1e-6, get_per_doc=2.7e-4,
                          storage_per_gb_month=0.05)
    cold = costs.TierCosts("cold", put_per_doc=8e-5, get_per_doc=1e-6,
                           storage_per_gb_month=0.02)
    return costs.TwoTierCostModel(tier_a=hot, tier_b=cold, workload=wl)


def _exact_wide_2tier(mesh):
    rs = (40.5, 23.0, 70.0)
    specs = [StreamSpec(stream_id=i, k=8, r=rs[i % 3], migrate=i % 2 == 0)
             for i in range(7)]
    eng = StreamEngine(specs, mesh=mesh)
    sh = meter_reference.Shadow(eng)
    rng = np.random.default_rng(0)
    for c in range(8):
        eng.ingest_dense(_dense(eng, c, 16, rng))
    return eng, sh


def _exact_narrow_3tier(mesh):
    bounds = ((10.0, 37.2), (5.5, 5.5), (30.0, 90.0))
    specs = [StreamSpec(stream_id=i, k=16, boundaries=bounds[i % 3],
                        migrate=i % 2 == 1) for i in range(5)]
    eng = StreamEngine(specs, mesh=mesh)
    sh = meter_reference.Shadow(eng)
    rng = np.random.default_rng(1)
    eng.ingest_chunks(_dense(eng, c, 4, rng) for c in range(30))
    return eng, sh


def _logmem_2tier(mesh):
    specs = [StreamSpec(stream_id=i, k=32, r=40.0 + 17.5 * i,
                        engine="logmem") for i in range(5)]
    eng = StreamEngine(specs, mesh=mesh)
    sh = meter_reference.Shadow(eng)
    rng = np.random.default_rng(2)
    for c in range(8):
        _routed(eng, c, 24, rng)
    return eng, sh


def _mixed_4tier(mesh):
    specs = [StreamSpec(stream_id=i, k=8, boundaries=(12.0, 30.5, 61.0),
                        migrate=i != 1) for i in range(3)]
    specs += [StreamSpec(stream_id=10 + i, k=32,
                         boundaries=(20.0, 50.5, 90.0)[: 1 + i],
                         engine="logmem") for i in range(3)]
    eng = StreamEngine(specs, mesh=mesh, obs=Observability(
        ObsConfig(costs=True)))
    sh = meter_reference.Shadow(eng)
    rng = np.random.default_rng(3)
    for c in range(10):
        _routed(eng, c, 12, rng)
    return eng, sh


def _replan(mesh):
    m, n, k, batch = 5, 4096, 16, 64
    cm = _two_tier_model(n, k)
    rng = np.random.default_rng(7)
    traces = np.stack([simulator.drifted_rank_trace(n, rng, [(1024, 8.0)])
                       for _ in range(m)]).astype(np.float32)
    specs = [StreamSpec(stream_id=i, k=k, cost_model=cm) for i in range(m)]
    eng = StreamEngine(specs, mesh=mesh,
                       obs=Observability(ObsConfig(costs=True)),
                       replan=ReplanConfig(drift=DriftConfig(alpha=0.05)))
    sh = meter_reference.Shadow(eng)
    for c in range(n // batch):
        ids = np.broadcast_to(np.arange(c * batch, (c + 1) * batch,
                                        dtype=np.int32), (m, batch))
        eng.ingest_dense([(traces[:, c * batch:(c + 1) * batch], ids)])
    assert any(e.applied for e in eng.replan_events)
    return eng, sh


def _tier_outage(mesh):
    specs = [StreamSpec(stream_id=i, k=8, boundaries=(16.0, 64.0))
             for i in range(3)]
    specs.append(StreamSpec(stream_id=10, k=16, boundaries=(20.0, 48.0),
                            engine="logmem"))
    eng = StreamEngine(specs, mesh=mesh,
                       obs=Observability(ObsConfig(costs=True)))
    sh = meter_reference.Shadow(eng)
    rng = np.random.default_rng(4)
    for c in range(4):
        eng.ingest_dense(_dense(eng, c, 8, rng))
    assert eng.tier_outage(1)["rows_evacuated"] > 0
    for c in range(4, 7):
        eng.ingest_dense(_dense(eng, c, 8, rng))
    eng.tier_recover(1, hysteresis=1)
    for c in range(7, 10):
        eng.ingest_dense(_dense(eng, c, 8, rng))
    assert eng.meter.relocations.sum() > 0
    return eng, sh


def _nonfinite(mesh):
    specs = [StreamSpec(stream_id=i, k=8, r=20.0, migrate=i == 0)
             for i in range(3)]
    specs += [StreamSpec(stream_id=10 + i, k=16, r=24.0, engine="logmem")
              for i in range(2)]
    eng = StreamEngine(specs, mesh=mesh, obs=Observability(ObsConfig()))
    sh = meter_reference.Shadow(eng)
    rng = np.random.default_rng(5)
    for c in range(6):
        eng.ingest_dense(_dense(eng, c, 8, rng, nonfinite=True))
    assert eng.obs_snapshot()["engine"]["scores_quarantined"] > 0
    return eng, sh


def _unmetered_chunk(mesh):
    """A ``meter=False`` chunk advances the reservoirs but neither meter:
    the host's nor the device's observed count, floor or ledgers."""
    specs = [StreamSpec(stream_id=i, k=8, r=30.0, migrate=True)
             for i in range(3)]
    eng = StreamEngine(specs, mesh=mesh)
    sh = meter_reference.Shadow(eng)
    rng = np.random.default_rng(6)
    for c in range(6):
        eng.ingest_dense(_dense(eng, c, 8, rng), meter=c != 2)
    assert sh.metered == 5
    return eng, sh


CASES = {
    "exact_wide_2tier": _exact_wide_2tier,
    "exact_narrow_3tier": _exact_narrow_3tier,
    "logmem_2tier": _logmem_2tier,
    "mixed_4tier": _mixed_4tier,
    "replan": _replan,
    "tier_outage": _tier_outage,
    "nonfinite": _nonfinite,
    "unmetered_chunk": _unmetered_chunk,
}
MESH_CASES = ("exact_wide_2tier", "exact_narrow_3tier", "mixed_4tier",
              "replan", "tier_outage")


def check(case: str, mesh=None) -> None:
    eng, sh = CASES[case](mesh)
    eng.finalize()
    assert sh.chunks == []
    assert sh.metered > 0
    assert sh.mismatches() == {}, case
    if case in ("exact_wide_2tier", "exact_narrow_3tier", "mixed_4tier"):
        assert eng.meter.migrations.sum() > 0  # a cascade fired
        assert eng.meter.deletes.sum() > 0


_MESH = r"""
import sys
sys.path.insert(0, {tests!r})
from repro.parallel import fleet
import test_device_meter as t
mesh = fleet.fleet_mesh(4)
assert fleet.n_shards(mesh) == 4
for case in t.MESH_CASES:
    t.check(case, mesh)
print("MESH-OK")
"""


@pytest.mark.parametrize("case", list(CASES) + ["mesh4"])
def test_device_meter_matches_scatter_reference(case):
    if case != "mesh4":
        check(case)
        return
    tests = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in (env.get("PYTHONPATH"),) if p]
        + [os.path.join(tests, "..", "src")])
    out = subprocess.run([sys.executable, "-c", _MESH.format(tests=tests)],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MESH-OK" in out.stdout


@pytest.mark.parametrize("n_tiers", [2, 3, 4])
def test_fold_matches_scatter_at_paper_positions(n_tiers):
    """The fold alone, two chunks, at positions near the paper's n = 1e8
    (past 2^24, where a float32 tier compare rounds): writes, deletes
    and a cascade that fires in the second chunk."""
    rng = np.random.default_rng(n_tiers)
    m, w, k, base = 6, 32, 16, 99_999_000
    cuts = np.sort(rng.uniform(base + 8, base + 60, (m, n_tiers - 1)), 1)
    cuts[0, :] = base + 40.5  # every boundary at one point
    cuts[1, -1] = np.inf  # a shallower stream
    migrate = np.arange(m) % 2 == 0
    meter = metering.FleetMeter([k] * m, migrate=migrate,
                                boundaries=[tuple(r) for r in cuts])
    meter.observed[:] = base
    ref = metering.FleetMeter([k] * m, migrate=migrate,
                              boundaries=[tuple(r) for r in cuts])
    ref.observed[:] = base
    rows = np.arange(m)
    old = np.full((m, k), -1, np.int64)
    for c in range(2):
        ids = base + c * w + np.arange(w)[None, :].repeat(m, 0)
        ids[:, -3:] = -1  # padding
        wrote = (rng.random((m, w)) < 0.6) & (ids >= 0)
        # the reservoir: keep up to k of the old and new written ids
        pool = np.concatenate([old, np.where(wrote, ids, -1)], 1)
        new = np.full((m, k), -1, np.int64)
        for r in range(m):
            live = pool[r][pool[r] >= 0]
            new[r, :min(k, live.size)] = rng.permutation(live)[:k]
        evicted = np.where((old >= 0) & ~meter_reference._isin_rows(old, new),
                           old, -1)
        ms = meter.device_state(rows, m)
        _, delta = metering.fold(
            metering.MeterState(*map(jnp.asarray, ms)),
            jnp.asarray(ids, jnp.int32), jnp.asarray(wrote),
            jnp.asarray(evicted, jnp.int32), jnp.asarray(new, jnp.int32))
        meter.record_update(rows, metering.MeterDelta(
            *(np.asarray(a) for a in delta)))
        meter_reference.record_update(ref, rows, ids, wrote, evicted, new)
        old = new
    assert meter.migrations.sum() > 0 and meter.floor.max() > 0
    for name in meter_reference.STATE_ARRAYS:
        np.testing.assert_array_equal(getattr(meter, name),
                                      getattr(ref, name), err_msg=name)
    # the shared attribution: int32 thresholds against positions past 2^24
    q = topk.quantize_boundaries(cuts)
    got = np.asarray(topk.tiers(jnp.asarray(ids, jnp.int32),
                                jnp.asarray(q)))
    want = (ids[:, :, None] >= cuts[:, None, :]).sum(-1)
    np.testing.assert_array_equal(got, want)
