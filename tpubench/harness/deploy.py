"""Build a configuration's deployment through the program's own entry
points: tenant cost models, ``StreamSpec``s and a ``StreamEngine``.

A configuration prices its tenants from the paper's case studies: one
case at its top level (the logmem fleet), or a list of ``cases`` that
the tenants are dealt over in equal shares, in an order drawn from the
seed (the exact fleet)."""
from __future__ import annotations

import numpy as np

from . import source


def case_n(case: dict, k: int) -> int:
    """A case's window length at K = ``k``, its published K/n kept."""
    return int(round(k / float(case["k_over_n"])))


def case_model(case: dict, k: int, n_docs: int):
    """One tenant's two-tier model of a case study (its raw prices, as the
    paper's table gives them) at its own K and n."""
    from repro.core import costs
    wl = costs.WorkloadSpec(
        n_docs=int(n_docs), k=int(k),
        doc_gb=float(case["doc_mb"]) * costs.GB_PER_MB,
        window_months=float(case["window_days"]) / costs.DAYS_PER_MONTH)
    return costs.TwoTierCostModel(
        tier_a=costs.TierCosts(**case["tier_a"]),
        tier_b=costs.TierCosts(**case["tier_b"]), workload=wl,
        xfer_producer_to_b_per_gb=float(case["xfer_producer_to_b_per_gb"]),
        xfer_a_to_consumer_per_gb=float(case["xfer_a_to_consumer_per_gb"]))


def deal(g: np.random.Generator, m: int, n_cases: int) -> np.ndarray:
    """(m,) case index per tenant: equal shares, in an order drawn from
    ``g`` (every seed deals the same set of tenants)."""
    return g.permutation(np.arange(m) % n_cases)


def fleet_cases(cfg: dict, seed: int) -> np.ndarray:
    return deal(source.rng(seed, 0), int(cfg["tenants"]), len(cfg["cases"]))


def request_cases(cfg: dict, seed: int, r: int, m: int) -> np.ndarray:
    """Case per tenant of plan request ``r``."""
    return deal(source.rng(seed, 2, r), m, len(cfg["cases"]))


def ntier_models(cfg: dict, which: np.ndarray):
    """A fresh N-tier model per tenant (``which``: its case index), as a
    tenant that declares its tiers brings it to the planner."""
    k = int(cfg["k"])
    cases = cfg["cases"]
    return [case_model(cases[c], k, case_n(cases[c], k)).as_ntier()
            for c in which]


def specs(cfg: dict, seed: int):
    """The fleet's ``StreamSpec``s, tenant i as stream id i."""
    from repro.streams import StreamSpec
    m, k = int(cfg["tenants"]), int(cfg["k"])
    if cfg["engine"] == "exact":
        models = ntier_models(cfg, fleet_cases(cfg, seed))
        return [StreamSpec(stream_id=i, k=k, cost_model=cm)
                for i, cm in enumerate(models)]
    if cfg["engine"] == "logmem":
        cm = case_model(cfg, k, int(cfg["n_docs"]))
        return [StreamSpec(stream_id=i, k=k, cost_model=cm, engine="logmem")
                for i in range(m)]
    raise ValueError(f"unknown engine {cfg['engine']!r}")


def engine(cfg: dict, seed: int, *, annotations: bool = False):
    """The deployment's ``StreamEngine``, planned by its constructor, with
    the obs device metrics on (and the program's spans mirrored into the
    profiler when ``annotations``)."""
    from repro.obs import ObsConfig, Observability
    from repro.streams import StreamEngine
    obs = Observability(ObsConfig(metrics=bool(cfg.get("obs_metrics", True)),
                                  profiler_annotations=annotations))
    return StreamEngine(specs(cfg, seed),
                        use_kernel_filter=bool(cfg["use_kernel_filter"]),
                        obs=obs)


def fleet_layout(eng) -> dict:
    """Per tenant (stream id i = row i): the plan's boundaries (inf padded)
    and cascade flag, as the meter holds them — the placement the window
    runs under."""
    rows = np.asarray([eng.stream_row(i) for i in range(eng.m)])
    return {"bounds": eng.meter.boundaries[rows].copy(),
            "migrate": eng.meter.migrate[rows].copy(), "rows": rows}
