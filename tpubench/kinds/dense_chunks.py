"""Dense chunks through ``StreamEngine.ingest_chunks``: every tenant of an
exact fleet sends its next ``docs_per_tenant`` documents per chunk, in a
closed loop against a backlogged source.

Compared after the window, on tenants sampled from the seed (half of them
cascading where the plan has such): the survivors after ``finalize`` and
the meter's observed, writes, deletes, migrations and reads per tier,
against a plain replay under the reference planner's own placement, and
the true cost of the placement the window ran under over the reference
optimum."""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from harness import common, deploy, source, spans as sp
from reference import exact_fleet, plan as plan_ref

PRECISION = "float32"
METERED = ("observed", "writes", "deletes", "migrations", "reads")


def chunk(seed: int, c: int, m: int, w: int):
    """Chunk ``c``: (scores (m, w) f32, positions (m, w) i32). Every tenant
    sends positions c*w .. (c+1)*w - 1."""
    s = source.rng(seed, 1, c).standard_normal((m, w), dtype=np.float32)
    ids = np.broadcast_to(np.arange(c * w, (c + 1) * w, dtype=np.int32),
                          (m, w))
    return s, ids


def inputs(cfg: dict, tr: dict, seed: int, units: int) -> Dict:
    """What the reference needs to recompute a run with ``units`` chunks in
    its window: the tenants it compares, half of them cascading under the
    reference's plan where it has such."""
    m, k = int(cfg["tenants"]), int(cfg["k"])
    which = deploy.fleet_cases(cfg, seed)
    migrate = plan_ref.plan(*plan_ref.fleet_costs(cfg["cases"], which,
                                                  k))[2]
    pick = common.sample(source.rng(seed, 9), m, int(tr["sample_tenants"]),
                         np.flatnonzero(migrate))
    return {"seed": seed, "m": m, "k": k, "w": int(tr["docs_per_tenant"]),
            "chunks": int(tr["warmup_chunks"]) + units, "pick": pick,
            "cases": cfg["cases"], "which": which[pick]}


def run(ctx) -> common.Outcome:
    cfg, tr, seed = ctx.cfg, ctx.traffic, ctx.seed
    m, k, w = int(cfg["tenants"]), int(cfg["k"]), int(tr["docs_per_tenant"])
    eng = deploy.engine(cfg, seed, annotations=ctx.trace)
    if len(eng.buckets) != 1:
        raise ValueError("the dense mix feeds a fleet of one bucket")
    layout = deploy.fleet_layout(eng)
    backlog = source.Backlog(lambda c: [chunk(seed, c, m, w)],
                             tr.get("prefetch", 2))
    spans = ctx.spans
    try:
        eng.ingest_chunks(backlog.get()
                          for _ in range(int(tr["warmup_chunks"])))
        common.block(eng)
        setup_s = time.perf_counter() - ctx.t_start
        get = spans.wrap(sp.SOURCE, backlog.get) if spans else backlog.get
        if spans:
            eng.meter.record_update = spans.wrap(sp.METER,
                                                 eng.meter.record_update)
        clock = common.ChunkClock()
        eng.attach_checkpointer(clock)
        asks: List[float] = []

        def feed(deadline):
            while True:
                t = time.perf_counter()
                if t >= deadline:
                    return
                asks.append(t)
                yield get()

        with ctx.window():
            t0 = time.perf_counter()
            eng.ingest_chunks(feed(t0 + ctx.seconds))
        waited = backlog.waited_s
    finally:
        backlog.close()
    n = len(clock.done)
    inp = inputs(cfg, tr, seed, n)
    eng.finalize()
    rows = layout["rows"][inp["pick"]]
    ids = np.asarray(eng.states()[0].ids)[rows]
    got = {"survivors": [np.sort(r[r >= 0]).astype(np.int64) for r in ids],
           "bounds": layout["bounds"][rows],
           "migrate": layout["migrate"][rows]}
    for key in METERED:
        got[key] = getattr(eng.meter, key)[rows].copy()
    return common.Outcome(
        setup_s=setup_s, window_s=clock.done[-1] - t0,
        latencies_s=[d - a for a, d in zip(asks, clock.done)],
        attempted=n, failed=0, docs=n * m * w,
        shapes={"engine": "exact", "m": m, "k": k, "w": w},
        late_s=waited, inputs=inp, got=got)


def _costs(inp: Dict):
    return plan_ref.fleet_costs(inp["cases"], inp["which"], inp["k"])


def reference(inp: Dict, precision: str) -> Dict:
    """Plans the sampled tenants and replays them under that plan."""
    pick = inp["pick"]
    out = {}
    out["plan_total"], out["bounds"], out["migrate"] = plan_ref.plan(
        *_costs(inp), precision=plan_ref.plan_precision(precision))
    traces = [[] for _ in pick]
    for c in range(inp["chunks"]):
        s, i = chunk(inp["seed"], c, inp["m"], inp["w"])
        for j, row in enumerate(pick):
            traces[j].append((s[row], i[row]))
    refs = [exact_fleet.replay(traces[j], inp["k"], out["bounds"][j],
                               bool(out["migrate"][j]), precision)
            for j in range(len(pick))]
    out["survivors"] = [r["survivors"] for r in refs]
    for key in METERED:
        out[key] = np.asarray([r[key] for r in refs], np.int64)
    return out


def gaps(ref: Dict, got: Dict, inp: Dict) -> Dict[str, float]:
    surv = sum(np.setxor1d(a, b).size
               for a, b in zip(ref["survivors"], got["survivors"]))
    meter = sum(int(np.abs(np.asarray(ref[key], np.int64)
                           - np.asarray(got[key], np.int64)).sum())
                for key in METERED)
    return {"survivor_mismatch": float(surv), "meter_mismatch": float(meter),
            "plan_regret": plan_ref.regret(_costs(inp), ref["plan_total"],
                                           got["bounds"], got["migrate"])}
