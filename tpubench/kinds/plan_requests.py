"""Plan requests through ``streams.planner.plan_fleet_mixed``, the entry
the engine's constructor plans with: one admission client, in a closed
loop, submits a batch of ``tenants_per_request`` arriving tenants (a
fresh cost model each, dealt over the configuration's cases in an order
drawn from the seed and the request) as soon as the previous plan is
back. A request's latency is the planner call; building the next batch's
models is the client's own time.

Compared after the window, on requests sampled from the seed: each
reported total against the reference planner's optimum, and the true
cost of each chosen plan over it."""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from harness import common, deploy, source, spans as sp
from reference import plan as plan_ref

PRECISION = "float64"


def inputs(cfg: dict, tr: dict, seed: int, units: int) -> Dict:
    """What the reference needs to recompute a run with ``units``
    requests in its window."""
    pick = common.sample(source.rng(seed, 9), units,
                         int(tr["sample_requests"]))
    return {"seed": seed, "first": int(tr["warmup_requests"]),
            "requests": pick.tolist(), "m": int(tr["tenants_per_request"]),
            "cfg": {"k": int(cfg["k"]), "cases": cfg["cases"]}}


def run(ctx) -> common.Outcome:
    from repro.streams import planner
    cfg, tr, seed = ctx.cfg, ctx.traffic, ctx.seed
    m = int(tr["tenants_per_request"])
    spans = ctx.spans
    plan_call = (spans.wrap(sp.PLAN, planner.plan_fleet_mixed) if spans
                 else planner.plan_fleet_mixed)

    def request(r):
        return deploy.ntier_models(cfg, deploy.request_cases(cfg, seed, r, m))
    if spans:
        request = spans.wrap(sp.CLIENT, request)

    warm = int(tr["warmup_requests"])
    for r in range(warm):
        plan_call(request(r))
    setup_s = time.perf_counter() - ctx.t_start
    lat: List[float] = []
    answers = []
    failed = 0
    with ctx.window():
        t0 = time.perf_counter()
        deadline = t0 + ctx.seconds
        while time.perf_counter() < deadline:
            models = request(warm + len(lat))
            t = time.perf_counter()
            plan = plan_call(models)
            lat.append(time.perf_counter() - t)
            answers.append((np.asarray(plan.totals, np.float64),
                            np.asarray(plan.boundaries, np.float64),
                            np.asarray(plan.migrate_flags, bool)))
            failed += int(not np.all(np.isfinite(answers[-1][0])))
        window_s = time.perf_counter() - t0
    inp = inputs(cfg, tr, seed, len(lat))
    got = {i: answers[i] for i in inp["requests"]}
    return common.Outcome(setup_s=setup_s, window_s=window_s,
                          latencies_s=lat, attempted=len(lat),
                          failed=failed, shapes={"m": m}, inputs=inp,
                          got=got)


def _costs(inp: Dict, i: int):
    cfg = inp["cfg"]
    which = deploy.request_cases(cfg, inp["seed"], inp["first"] + i,
                                 inp["m"])
    return plan_ref.fleet_costs(cfg["cases"], which, cfg["k"])


def reference(inp: Dict, precision: str) -> Dict:
    return {i: plan_ref.plan(*_costs(inp, i), precision=precision)
            for i in inp["requests"]}


def gaps(ref: Dict, got: Dict, inp: Dict) -> Dict[str, float]:
    """Widest relative gap of a reported total from the reference optimum,
    and widest relative excess of a chosen plan's true cost over it."""
    gap = regret = 0.0
    for i, (tot, bounds, mig) in got.items():
        opt = ref[i][0]
        gap = max(gap, float(np.max(np.abs(tot - opt) / np.abs(opt))))
        regret = max(regret, plan_ref.regret(_costs(inp, i), opt, bounds,
                                             mig))
    return {"plan_total_gap": gap, "plan_regret": regret}
