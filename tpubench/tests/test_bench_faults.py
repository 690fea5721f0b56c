"""The comparison that decides ``correct`` fails where it must: a rehearsed
run (the chip check skipped) with the timed path broken underneath, and
the control (the reference at bfloat16 in the program's place), come
out not correct in every cell."""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(ROOT, "src"))

from harness import registry  # noqa: E402

CELLS = registry.Registry(ROOT).cell_names()


def _run(workload, seed=5):
    from harness import runner
    return runner.run(workload, seed, 1.0, False, rehearse=True,
                      use_cache=False, root=ROOT, log=lambda msg: None)


def _stale(orig):
    def f(state, scores, ids, *a, **kw):
        new, wrote = orig(state, scores, ids, *a, **kw)
        import jax.numpy as jnp
        return state, jnp.zeros_like(wrote)
    return f


def _half(orig):
    def f(state, scores, ids, *a, **kw):
        import jax.numpy as jnp
        keep = jnp.arange(scores.shape[1])[None, :] < scores.shape[1] // 2
        return orig(state, jnp.where(keep, scores, -jnp.inf),
                    jnp.where(keep, ids, -1), *a, **kw)
    return f


def _altered_exact(orig):
    def f(state, scores, ids, *a, **kw):
        new, wrote = orig(state, scores, ids, *a, **kw)
        return new._replace(ids=new.ids.at[:, 0].add(1)), wrote
    return f


def _altered_logmem(orig):
    def f(state, scores, ids, *a, **kw):
        new, wrote = orig(state, scores, ids, *a, **kw)
        return new, wrote.at[:, 0].set(~wrote[:, 0] & (ids[:, 0] >= 0))
    return f


INGEST_FAULTS = {
    ("exact_dense", "stale"): ("repro.streams.engine", "filtered_update",
                               _stale),
    ("exact_dense", "half"): ("repro.streams.engine", "filtered_update",
                              _half),
    ("exact_dense", "altered"): ("repro.streams.engine", "filtered_update",
                                 _altered_exact),
    ("logmem_routed", "stale"): ("repro.streams.logmem", "update", _stale),
    ("logmem_routed", "half"): ("repro.streams.logmem", "update", _half),
    ("logmem_routed", "altered"): ("repro.streams.logmem", "update",
                                   _altered_logmem),
}


@pytest.mark.parametrize("workload,fault", sorted(INGEST_FAULTS))
def test_ingest_fault_is_not_correct(workload, fault, monkeypatch):
    import importlib
    mod_name, attr, make = INGEST_FAULTS[(workload, fault)]
    mod = importlib.import_module(mod_name)
    monkeypatch.setattr(mod, attr, make(getattr(mod, attr)))
    line = _run(workload)
    assert line["correct"] is False, line["checks"]


def _plan_fault(kind):
    from repro.core import shp
    orig = shp.plan_ntier_arrays
    seen = {}

    def f(cw, cr, cs, n, k, rpw, **kw):
        if kind == "stale":
            out = seen.setdefault("first", orig(cw, cr, cs, n, k, rpw, **kw))
            return {key: v.copy() for key, v in out.items()}
        if kind == "half":
            h = len(n) // 2
            out = orig(cw[:h], cr[:h], cs[:h], n[:h], k[:h], rpw[:h], **kw)
            return {key: np.concatenate([v, v])[:len(n)]
                    for key, v in out.items()}
        out = orig(cw, cr, cs, n, k, rpw, **kw)
        out["total"] = out["total"].copy()
        out["total"][0] *= 1.01
        return out
    return f


@pytest.mark.parametrize("fault", ["stale", "half", "altered"])
def test_plan_fault_is_not_correct(fault, monkeypatch):
    from repro.core import shp
    monkeypatch.setattr(shp, "plan_ntier_arrays", _plan_fault(fault))
    line = _run("exact_plan_arrivals")
    assert line["correct"] is False, line["checks"]


CONTROL_UNITS = {"exact_dense": 6, "logmem_routed": 40,
                 "exact_plan_arrivals": 4}


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    import control
    out, limits = control.readings(workload, [3, 2**31 + 5],
                                   CONTROL_UNITS[workload], rehearse=True)
    for seed, nums in out.items():
        assert any(v > limits[k] for k, v in nums.items()), (seed, nums)
