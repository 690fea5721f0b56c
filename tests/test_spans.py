"""Program spans, counters and device scopes: the per-chunk ``ingest.*``
spans of both ingest paths, the planner's ``plan.*`` spans, the tracer's
parent key, the router's padding counters, the fleet step's jit probe,
and the named scopes the lowered step carries."""
import re

import jax
import numpy as np
import pytest

from repro.core import costs
from repro.obs import Observability, ObsConfig, jits, timers, trace
from repro.online.drift import DriftConfig
from repro.online.replan import ReplanConfig
from repro.streams import StreamEngine, StreamSpec, planner, router

CHUNK_SPANS = ("ingest.stage", "ingest.dispatch", "ingest.wait",
               "ingest.fetch", "ingest.meter", "ingest.monitors",
               "ingest.checkpoint")


class _Hook:
    def on_chunk(self, eng):
        pass


def _model(n=4096, k=8):
    wl = costs.WorkloadSpec(n_docs=n, k=k, doc_gb=1e-4, window_months=0.5)
    hot = costs.TierCosts("hot", put_per_doc=1e-6, get_per_doc=2.7e-4,
                          storage_per_gb_month=0.05)
    cold = costs.TierCosts("cold", put_per_doc=8e-5, get_per_doc=1e-6,
                           storage_per_gb_month=0.02)
    return costs.TwoTierCostModel(tier_a=hot, tier_b=cold, workload=wl)


def _engine(trace_ingest=True, m=4, k=8, logmem=False, **kw):
    specs = [StreamSpec(stream_id=i, k=k, r=float(4 * k)) for i in range(m)]
    if logmem:
        specs += [StreamSpec(stream_id=100 + i, k=64, r=256.0,
                             engine="logmem") for i in range(2)]
    obs = Observability(ObsConfig(trace_ingest=trace_ingest, **kw))
    eng = StreamEngine(specs, obs=obs)
    eng.attach_checkpointer(_Hook())
    return eng, obs.tracer


def _dense(eng, c, w=16, seed=0):
    rng = np.random.default_rng(seed + c)
    out = []
    for b in eng.buckets:
        s = rng.standard_normal((b.m, w)).astype(np.float32)
        i = np.broadcast_to(np.arange(c * w, (c + 1) * w, dtype=np.int32),
                            (b.m, w)).copy()
        out.append((s, i))
    return out


def _chunk_spans(tr):
    return [e for e in tr.events
            if e["kind"] == "span" and e["name"].startswith("ingest.")]


def test_ingest_chunks_spans_once_per_chunk_in_pipelined_order():
    eng, tr = _engine()
    n = 3
    assert eng.ingest_chunks(_dense(eng, c) for c in range(n)) == n
    spans = _chunk_spans(tr)
    for name in CHUNK_SPANS:
        chunks = [e["attrs"]["chunk"] for e in spans if e["name"] == name]
        assert chunks == list(range(n)), name
    for e in spans:
        assert "parent" in e and e["parent"] is None
    order = [(e["name"], e["attrs"]["chunk"]) for e in spans]
    for t in range(n - 1):
        # dispatch t, stage t+1, then consume t
        assert order.index(("ingest.dispatch", t)) \
            < order.index(("ingest.stage", t + 1)) \
            < order.index(("ingest.wait", t)) \
            < order.index(("ingest.fetch", t)) \
            < order.index(("ingest.meter", t)) \
            < order.index(("ingest.dispatch", t + 1))
    stage = [e for e in spans if e["name"] == "ingest.stage"]
    assert all(e["attrs"]["bytes"] == 4 * 16 * 8 for e in stage)
    fetch = [e for e in spans if e["name"] == "ingest.fetch"]
    # the meter's int32 counts per tenant: observed, migrations and floor,
    # and writes, deletes and hop reads per tier
    assert all(e["attrs"]["bytes"] == 4 * 4 * (3 + 3 * eng.meter.n_tiers)
               for e in fetch)


def test_routed_ingest_spans_carry_chunk_parent_and_router_counts():
    eng, tr = _engine(logmem=True)
    rng = np.random.default_rng(1)
    sids = np.array([0, 1, 1, 2, 100, 101, 101, 101])
    for c in range(2):
        eng.ingest(sids, rng.standard_normal(sids.size),
                   c * 10 + np.arange(sids.size))
    spans = _chunk_spans(tr)
    for name in ("ingest.route",) + CHUNK_SPANS:
        recs = [e for e in spans if e["name"] == name]
        assert [e["attrs"]["chunk"] for e in recs] == [0, 1], name
        assert all(e["parent"] == "ingest" for e in recs), name
    route = [e for e in spans if e["name"] == "ingest.route"]
    # exact bucket: 4 tenants x width 2; logmem bucket: 2 tenants x 4
    assert [e["attrs"]["slots"] for e in route] == [16, 16]
    assert [e["attrs"]["useful"] for e in route] == [8, 8]
    assert [e["parent"] for e in tr.spans("ingest")] == [None, None]


def test_router_counts_slots_and_documents():
    rt = router.StreamRouter(router.bucket_streams(
        {0: 4, 1: 4, 2: 8}, {0: "exact", 1: "exact", 2: "logmem"}))
    sids = [0, 0, 0, 1, 2, 2]
    routed = rt.route(sids, np.arange(6.0), [0, 1, 2, 0, 0, 1])
    assert rt.route_slots == sum(i.size for _, i in routed) == 2 * 4 + 2
    assert rt.route_useful == sum(int((i >= 0).sum()) for _, i in routed)
    assert rt.route_useful == 6
    rt.route([1], [1.0], [5])
    assert (rt.route_slots, rt.route_useful) == (10 + 2 + 1, 7)
    with pytest.raises(ValueError):
        rt.route([0, 0], [1.0, 2.0], [3, 3])  # duplicate: nothing counted
    assert (rt.route_slots, rt.route_useful) == (13, 7)


def test_router_counters_reach_the_snapshot_and_exposition():
    eng, _ = _engine()
    eng.ingest([0, 1, 1], [1.0, 2.0, 3.0], [0, 0, 1])
    snap = eng.obs_snapshot()["router"]
    assert snap == {"route_slots": 4 * 2, "route_useful": 3,
                    "route_resorted": 0, "route_resorted_rows": 0}
    text = eng._obs.prometheus()
    assert "# TYPE repro_obs_engines_engine0_router_route_slots counter" \
        in text.splitlines()


def test_router_counts_streams_it_resorted():
    eng, tr = _engine()
    # in position order: no sort, whatever the order of the streams
    eng.ingest([2, 0, 1, 0, 1], np.arange(5.0), [0, 0, 0, 1, 1])
    # streams 1 and 3 out of position order, stream 0 in it
    eng.ingest([1, 0, 3, 1, 3, 0, 3], np.arange(7.0),
               [3, 2, 9, 2, 8, 3, 7])
    eng.ingest([3, 3], [1.0, 2.0], [10, 11])
    snap = eng.obs_snapshot()["router"]
    assert (snap["route_resorted"], snap["route_resorted_rows"]) == (1, 2)
    route = [e for e in _chunk_spans(tr) if e["name"] == "ingest.route"]
    assert [e["attrs"]["resorted"] for e in route] == [0, 2, 0]
    text = eng._obs.prometheus().splitlines()
    assert "# TYPE repro_obs_engines_engine0_router_route_resorted " \
        "counter" in text
    assert "repro_obs_engines_engine0_router_route_resorted_rows 2" in text


def test_trace_ingest_off_records_none_and_outputs_bit_equal():
    outs = []
    for on in (True, False):
        eng, tr = _engine(trace_ingest=on, logmem=True)
        eng.ingest_chunks(_dense(eng, c) for c in range(3))
        eng.ingest(np.array([0, 3, 100]), np.array([9.0, 8.0, 7.0]),
                   np.array([100, 100, 100]))
        assert bool(_chunk_spans(tr)) == on
        outs.append((eng.states(), eng.meter.writes.copy(),
                     eng.meter.deletes.copy(), eng.survivors()))
    (s1, w1, d1, v1), (s2, w2, d2, v2) = outs
    for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(s2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(w1, w2)
    np.testing.assert_array_equal(d1, d2)
    assert v1.keys() == v2.keys()
    for sid in v1:
        np.testing.assert_array_equal(v1[sid], v2[sid])


def test_engine_without_obs_opens_bare_spans(monkeypatch):
    monkeypatch.setattr(timers, "_TALLY", {})
    eng = StreamEngine([StreamSpec(stream_id=0, k=8, r=16.0)])
    eng.ingest_dense(_dense(eng, 0))
    for name in CHUNK_SPANS[:-1]:  # no checkpoint hook attached
        assert len(timers.recent(name)) == 1, name
    assert timers.recent("ingest.checkpoint") == []


def test_step_probe_hits_repeated_shape_and_misses_new_width():
    # a reservoir width no other test uses, so its jitted step is fresh
    eng, _ = _engine(m=3, k=5)
    probe = jits.probe("streams.engine.step")
    key = str(((16,), False, ("compare",)))
    eng.ingest_dense(_dense(eng, 0))
    first = dict(probe.by_key[key])
    assert first["misses"] >= 1
    eng.ingest_dense(_dense(eng, 1))
    again = probe.by_key[key]
    assert again["calls"] == first["calls"] + 1
    assert again["misses"] == first["misses"]  # a hit
    wide = str(((32,), False, ("compare",)))
    misses = probe.by_key.get(wide, {"misses": 0})["misses"]
    eng.ingest_dense(_dense(eng, 2, w=32))
    assert probe.by_key[wide]["misses"] == misses + 1


@pytest.mark.parametrize("fleet,w,members", [
    ("exact", 16, ("compare",)),
    ("exact_wide", 16400, ("sort",)),  # K = W = 16,400
    ("mixed", 16, ("compare",)),
    ("logmem", 16, ()),
])
def test_step_probe_reports_membership_method(fleet, w, members):
    """The step probe's key names the membership search each exact
    bucket was compiled with; logmem buckets search nothing."""
    specs = {
        "exact": [StreamSpec(stream_id=i, k=8, r=32.0) for i in range(2)],
        "exact_wide": [StreamSpec(stream_id=0, k=16400, r=65600.0)],
        "mixed": [StreamSpec(stream_id=0, k=8, r=32.0),
                  StreamSpec(stream_id=1, k=64, r=256.0, engine="logmem")],
        "logmem": [StreamSpec(stream_id=i, k=64, r=256.0, engine="logmem")
                   for i in range(2)],
    }[fleet]
    obs = Observability(ObsConfig())
    eng = StreamEngine(specs, obs=obs)
    before = jits.probe("streams.engine.step").snapshot()["by_key"]
    eng.ingest_dense(_dense(eng, 0, w=w))
    after = obs.snapshot()["jit"]["streams.engine.step"]["by_key"]
    grown = [key for key, v in after.items()
             if v["calls"] > before.get(key, {"calls": 0})["calls"]]
    widths = (w,) * len(eng.buckets)
    assert grown == [str((widths, False, members))]


def test_lowered_step_carries_named_scopes():
    specs = [StreamSpec(stream_id=i, k=8, r=32.0) for i in range(2)]
    specs += [StreamSpec(stream_id=10 + i, k=64, r=256.0, engine="logmem")
              for i in range(2)]
    eng = StreamEngine(specs, obs=Observability(ObsConfig()),
                       replan=ReplanConfig(drift=DriftConfig()))
    batches = eng._stage_batches(_dense(eng, 0))
    text = eng._step.lower(
        tuple(eng._states), batches, tuple(eng._drift_states),
        eng._metrics_state, tuple(eng._meter_states)
    ).compile().as_text()
    # a scope entered under vmap reads "vmap(<scope>)" in the name stack
    stacks = [[re.sub(r"^vmap\((.+)\)$", r"\1", part)
               for part in n.split("/")]
              for n in set(re.findall(r'op_name="([^"]*)"', text))]
    scopes = {part for st in stacks for part in st}
    for scope in ("member", "filter", "merge", "evicted", "logmem",
                  "drift", "obs", "meter"):
        assert scope in scopes, scope
    assert any("filter" in st and "member" in st for st in stacks)


def test_plan_fleet_mixed_opens_pack_solve_unpack_in_order(monkeypatch):
    tr = trace.Tracer()
    models = [_model(n=4096 * (i + 1)).as_ntier() for i in range(3)]
    plan = planner.plan_fleet_mixed(models, tracer=tr)
    assert plan.m == 3
    recs = tr.spans()
    assert [e["name"] for e in recs] == ["plan.pack", "plan.solve",
                                         "plan.unpack"]
    assert recs[0]["attrs"] == {"tenants": 3}
    # without a tracer the same spans still reach the process tally
    monkeypatch.setattr(timers, "_TALLY", {})
    planner.plan_fleet_mixed(models)
    for k in ("plan.pack", "plan.solve", "plan.unpack"):
        assert len(timers.recent(k)) == 1, k


def test_engine_plan_spans_nest_under_plan():
    obs = Observability(ObsConfig())
    StreamEngine([StreamSpec(stream_id=i, k=8,
                             cost_model=_model().as_ntier())
                  for i in range(2)], obs=obs)
    recs = obs.tracer.spans()
    assert [e["name"] for e in recs] == ["plan.pack", "plan.solve",
                                         "plan.unpack", "plan"]
    assert [e["parent"] for e in recs] == ["plan"] * 3 + [None]


def test_tracer_parent_stack_unwinds_on_error():
    tr = trace.Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("a"):
            raise RuntimeError
    with tr.span("b"):
        with tr.span("c"):
            pass
    # the failed span records nothing and leaves no parent behind
    assert [(e["name"], e["parent"]) for e in tr.spans()] == [
        ("c", "b"), ("b", None)]


def test_span_tally_keeps_the_newest_durations(monkeypatch):
    monkeypatch.setattr(timers, "_TALLY", {})
    monkeypatch.setattr(timers, "RECENT", 3)
    name = "test.tally"
    durs = []
    for _ in range(4):
        with timers.span(name) as sp:
            sp.attrs["x"] = 1
        durs.append(sp.dur_s)
    assert timers.recent(name) == durs[1:]
    assert timers.recent(name, 2) == durs[2:]
    assert timers.recent(name, 0) == []
    assert timers.recent("test.never") == []
    tr = trace.Tracer()
    with timers.span(name, tr, a=1) as sp:
        sp.attrs["b"] = 2
    assert tr.spans(name)[0]["attrs"] == {"a": 1, "b": 2}
    assert timers.recent(name, 1) == [sp.dur_s]
