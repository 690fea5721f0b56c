"""Mixed batches through ``StreamEngine.ingest`` (the host router): each
batch holds ``batch`` (tenant, score, position) triples in arbitrary
order, tenants drawn by the mix's popularity law (``popularity/<law>.py``),
each tenant's positions continuing where its previous batch left off.
A closed loop against a backlogged source.

Compared after the window, on tenants sampled from the seed: the
documents each tenant saw and the meter observed (exact counts), its
admits and metered writes (as shares of the reference's), against a plain
float32 replay of the logmem tracker under the reference planner's own
placement, and the true cost of the placement the window ran under over
the reference optimum."""
from __future__ import annotations

import os
import time
from typing import Dict, List

import numpy as np

from harness import common, deploy, registry, source, spans as sp
from reference import logmem_fleet, plan as plan_ref

PRECISION = "float32"
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def by_tenant(tenant: np.ndarray, m: int) -> np.ndarray:
    """Stable order of a batch by tenant (a radix sort on 16-bit keys where
    the fleet is small enough)."""
    key = tenant.astype(np.uint16) if m <= 1 << 16 else tenant
    return np.argsort(key, kind="stable")


class Stream:
    """The mix's batches, in order: ``next(b)`` is batch ``b``."""

    def __init__(self, seed: int, m: int, batch: int, popularity: str):
        self.draw = registry.load(BENCH_DIR, "popularity", popularity).draw
        self.seed, self.m, self.batch = seed, m, batch
        self.offsets = np.zeros(m, np.int64)

    def next(self, b: int):
        g = source.rng(self.seed, 3, b)
        tenant = np.asarray(self.draw(g, self.m, self.batch), np.int32)
        scores = g.standard_normal(self.batch, dtype=np.float32)
        counts = np.bincount(tenant, minlength=self.m)
        order = by_tenant(tenant, self.m)
        starts = np.cumsum(counts) - counts
        pos = np.empty(self.batch, np.int64)
        pos[order] = np.arange(self.batch) - np.repeat(starts, counts)
        ids = (self.offsets[tenant] + pos).astype(np.int32)
        self.offsets += counts
        return tenant, scores, ids


def inputs(cfg: dict, tr: dict, seed: int, units: int) -> Dict:
    """What the reference needs to recompute a run with ``units`` batches
    in its window."""
    m = int(cfg["tenants"])
    pick = common.sample(source.rng(seed, 9), m, int(tr["sample_tenants"]))
    return {"seed": seed, "m": m, "k": int(cfg["k"]),
            "batch": int(tr["batch"]), "popularity": tr["popularity"],
            "batches": int(tr["warmup_batches"]) + units, "pick": pick,
            "case": {key: cfg[key] for key in (
                "doc_mb", "window_days", "tier_a", "tier_b",
                "xfer_producer_to_b_per_gb", "xfer_a_to_consumer_per_gb")},
            "n": int(cfg["n_docs"])}


def _count_routed(dense, counters):
    for _, ids in dense:
        counters["route_useful"] += float((ids >= 0).sum())
        counters["route_slots"] += float(ids.size)
        counters["route_width"] = float(ids.shape[1])


def run(ctx) -> common.Outcome:
    cfg, tr, seed = ctx.cfg, ctx.traffic, ctx.seed
    m, k, batch = int(cfg["tenants"]), int(cfg["k"]), int(tr["batch"])
    eng = deploy.engine(cfg, seed, annotations=ctx.trace)
    layout = deploy.fleet_layout(eng)
    stream = Stream(seed, m, batch, tr["popularity"])
    backlog = source.Backlog(stream.next, tr.get("prefetch", 2))
    spans = ctx.spans
    try:
        for _ in range(int(tr["warmup_batches"])):
            eng.ingest(*backlog.get())
        common.block(eng)
        setup_s = time.perf_counter() - ctx.t_start
        get = spans.wrap(sp.SOURCE, backlog.get) if spans else backlog.get
        if spans:
            eng.router.route = spans.wrap(sp.ROUTE, eng.router.route,
                                          count=_count_routed)
            eng.meter.record_update = spans.wrap(sp.METER,
                                                 eng.meter.record_update)
        asks: List[float] = []
        done: List[float] = []
        with ctx.window():
            t0 = time.perf_counter()
            deadline = t0 + ctx.seconds
            while True:
                t = time.perf_counter()
                if t >= deadline:
                    break
                asks.append(t)
                eng.ingest(*get())
                common.block(eng)
                done.append(time.perf_counter())
        waited = backlog.waited_s
    finally:
        backlog.close()
    n = len(done)
    inp = inputs(cfg, tr, seed, n)
    rows = layout["rows"][inp["pick"]]
    st = eng.states()[0]
    got = {"admits": np.asarray(st.admits)[rows].astype(np.int64),
           "seen": np.asarray(st.seen)[rows].astype(np.int64),
           "observed": eng.meter.observed[rows].copy(),
           "writes": eng.meter.writes[rows].copy(),
           "bounds": layout["bounds"][rows],
           "migrate": layout["migrate"][rows]}
    w = int(spans.counters.get("route_width", 0)) if spans else 0
    return common.Outcome(
        setup_s=setup_s, window_s=done[-1] - t0,
        latencies_s=[d - a for a, d in zip(asks, done)],
        attempted=n, failed=0, docs=n * batch,
        shapes={"engine": "logmem", "m": m, "k": k, "w": w},
        late_s=waited, inputs=inp, got=got)


def _costs(inp: Dict):
    n = len(inp["pick"])
    cw, cr, cs = (np.repeat(np.asarray(c, np.float64)[None, :], n, 0)
                  for c in plan_ref.case_costs(inp["case"]))
    return (cw, cr, cs, np.full(n, float(inp["n"])),
            np.full(n, float(inp["k"])))


def reference(inp: Dict, precision: str) -> Dict:
    """Plans the sampled tenants and replays them under that plan."""
    m, pick = inp["m"], inp["pick"]
    out = {}
    out["plan_total"], out["bounds"], out["migrate"] = plan_ref.plan(
        *_costs(inp), precision=plan_ref.plan_precision(precision))
    tenants = [logmem_fleet.Tenant(inp["k"], out["bounds"][j], precision)
               for j in range(len(pick))]
    gen = Stream(inp["seed"], m, inp["batch"], inp["popularity"])
    for b in range(inp["batches"]):
        tenant, scores, ids = gen.next(b)
        # a stable sort keeps each tenant's documents in position order
        order = by_tenant(tenant, m)
        cuts = np.searchsorted(tenant[order], np.arange(m + 1))
        for ref, t in zip(tenants, pick):
            sel = order[cuts[t]:cuts[t + 1]]
            ref.feed(scores[sel], ids[sel])
    refs = [t.result() for t in tenants]
    out["admits"] = np.asarray([r["admits"] for r in refs], np.int64)
    out["seen"] = np.asarray([r["observed"] for r in refs], np.int64)
    out["observed"] = out["seen"].copy()
    out["writes"] = np.asarray([r["writes"] for r in refs], np.int64)
    return out


def gaps(ref: Dict, got: Dict, inp: Dict) -> Dict[str, float]:
    """The documents seen and observed are exact counts: any difference
    is a fault. Admits and metered writes are compared as shares of the
    reference's, since they are not exact on every sound chip run: the
    tracker rounds r = W K / t to an integer, and where the float32
    quotient lies within an ulp of a half the chip's division can round r
    the other way, which moves tau by one order statistic and flips a
    few admits."""
    def share(key):
        return float(np.abs(ref[key] - got[key]).sum() / ref[key].sum())
    counts = sum(int(np.abs(ref[key] - got[key]).sum())
                 for key in ("seen", "observed"))
    return {"count_mismatch": float(counts),
            "admit_mismatch_share": share("admits"),
            "write_mismatch_share": share("writes"),
            "plan_regret": plan_ref.regret(_costs(inp), ref["plan_total"],
                                           got["bounds"], got["migrate"])}
