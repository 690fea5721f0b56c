"""Whole-engine snapshot/restore: the state surface a crash must not lose.

``fleet_snapshot`` captures a ``StreamEngine`` as ``(pytree, meta)``:

* the pytree holds every fixed-shape array — per-bucket reservoir /
  logmem states and drift evidence sliced to the TRUE row count (shard
  padding stripped, so a checkpoint written on one mesh restores onto
  any other), the metrics counters collapsed to their mesh-independent
  canonical form, and the host monitors' state dicts (meter ledgers —
  which the cost bill prices — residual and cost monitor evidence) —
  plus the ingest cursor;
* ``meta`` is a JSON-able dict carrying everything variable-length or
  structural: the replan/admission event logs, tier-outage bookkeeping,
  and a fleet fingerprint that restore validates against.

Every leaf is a fresh host copy at snapshot time, so an async checkpoint
write can proceed while the engine mutates on. ``fleet_restore`` is the
exact inverse: it re-pads device rows to the target engine's shard
multiple (pad rows take fresh-init values — inert under every law),
re-pins the fleet sharding, and rebuilds the host monitors, after which
resumed ingestion is bit-identical to the uninterrupted run (asserted in
``tests/test_resilience.py`` on both backends and across mesh sizes).
"""
from __future__ import annotations

from dataclasses import asdict
from typing import Dict, Tuple

import jax
import numpy as np

import jax.numpy as jnp

from repro.streams import engine as engine_mod
from repro.streams import logmem


def _slice_rows(state, m: int):
    """Host copies of a per-bucket device pytree, shard padding cut."""
    return jax.tree_util.tree_map(
        lambda leaf: np.asarray(leaf)[:m].copy(), state)


def _fingerprint(engine) -> Dict:
    return {
        "m": int(engine.m),
        "buckets": [{"k": int(b.k), "m": int(b.m), "engine": b.engine,
                     "stream_ids": [int(s) for s in b.stream_ids]}
                    for b in engine.buckets],
        "n_tiers": int(engine.meter.n_tiers),
    }


def fleet_snapshot(engine) -> Tuple[Dict, Dict]:
    """(pytree, meta) capturing the engine's full mutable state. The
    pytree's structure depends only on the engine's configuration (same
    specs + same obs/replan switches → same leaves), never on the mesh,
    so it doubles as the restore template."""
    device: Dict = {
        "states": [_slice_rows(st, b.m)
                   for st, b in zip(engine._states, engine.buckets)],
    }
    if engine._drift_states is not None:
        device["drift"] = [_slice_rows(ds, b.m)
                           for ds, b in zip(engine._drift_states,
                                            engine.buckets)]
    if engine._metrics_state is not None:
        from repro.obs import metrics as metrics_mod
        counts, score = metrics_mod.to_canonical(engine._metrics_state)
        device["metrics"] = {"counts": counts, "score": score}
    host: Dict = {"meter": engine.meter.state_dict()}
    if engine._residuals is not None:
        host["residuals"] = engine._residuals.state_dict()
    if engine._cost_monitor is not None:
        host["cost_monitor"] = engine._cost_monitor.state_dict()
    tree = {"device": device, "host": host,
            "cursor": np.int64(engine.chunks_ingested)}
    meta = {
        "fleet": _fingerprint(engine),
        "chunks_ingested": int(engine.chunks_ingested),
        "replan_events": [asdict(e) for e in engine.replan_events],
        # the admission decision's plan object is not JSON-able; the
        # negotiated terms are what downstream consumers act on
        "admission_events": [
            {"stream_id": e.stream_id, "row": e.row,
             "position": e.position,
             "decision": {k: v for k, v in asdict(e.decision).items()
                          if k != "plan"}}
            for e in engine.admission_events],
        "failed_tiers": {str(t): c
                         for t, c in engine._failed_tiers.items()},
        "recovering_tiers": {str(t): c
                             for t, c in engine._recovering_tiers.items()},
        "tier_outages": int(engine._tier_outages),
    }
    return tree, meta


# leaves per bucket of the device cost ledger that fleet steps carried
# before the bill was priced from the meter (bounds, writes, deletes,
# resident_steps under ``device["costs"]``)
_RETIRED_COST_LEAVES = 4


def restore_template(engine, n_leaves: int) -> Dict:
    """The template a stored tree of ``n_leaves`` leaves restores
    through: the engine's own snapshot, with a placeholder
    ``device["costs"]`` entry when the checkpoint still holds the
    retired per-bucket cost ledger. ``fleet_restore`` reads only the
    entries it needs, so it skips that one: the meter's ledgers in
    ``host["meter"]`` already hold the counts the bill prices."""
    tree, _ = fleet_snapshot(engine)
    retired = _RETIRED_COST_LEAVES * len(engine.buckets)
    if n_leaves == len(jax.tree_util.tree_leaves(tree)) + retired:
        tree["device"]["costs"] = [
            (np.zeros(0, np.int32),) * _RETIRED_COST_LEAVES
            for _ in engine.buckets]
    return tree


def _restore_bucket(engine, bi: int, restored, fresh):
    """Re-pad one bucket's restored rows to the engine's shard multiple
    (pad rows keep fresh-init values) and re-pin the fleet sharding."""
    m = engine.buckets[bi].m

    def leaf(r, f):
        out = np.asarray(f).copy()
        out[:m] = np.asarray(r)
        return jnp.asarray(out)

    state = jax.tree_util.tree_map(leaf, restored, fresh)
    if engine.mesh is not None:
        from repro.parallel import fleet
        state = fleet.shard_rows(engine.mesh, state)
    return state


def fleet_restore(engine, tree: Dict, meta: Dict) -> None:
    """Load a snapshot into a freshly built engine (same specs and
    obs/replan configuration; ANY mesh size). Mutates the engine in
    place; raises ``ValueError`` on a fleet-shape mismatch. Entries of
    ``tree`` the engine has no state for (a retired ``device["costs"]``)
    are ignored."""
    fp = _fingerprint(engine)
    if meta.get("fleet") not in (None, fp):
        raise ValueError(
            f"checkpoint fleet {meta.get('fleet')} does not match the "
            f"target engine {fp} — restore needs an identically "
            "configured fleet (mesh size may differ)")
    device = tree["device"]
    fresh_states = [
        (logmem.init(pm) if b.engine == "logmem"
         else engine_mod.init(pm, b.k))
        for pm, b in zip(engine._pad_m, engine.buckets)]
    engine._states = [
        _restore_bucket(engine, bi, device["states"][bi],
                        jax.tree_util.tree_map(np.asarray,
                                               fresh_states[bi]))
        for bi in range(len(engine.buckets))]
    if engine._drift_states is not None:
        if "drift" not in device:
            raise ValueError("checkpoint has no drift state but the "
                             "engine was built with replan=")
        from repro.online import drift as drift_mod
        fresh = [jax.tree_util.tree_map(np.asarray, drift_mod.init(pm))
                 for pm in engine._pad_m]
        engine._drift_states = [
            _restore_bucket(engine, bi, device["drift"][bi], fresh[bi])
            for bi in range(len(engine.buckets))]
    if engine._metrics_state is not None:
        if "metrics" not in device:
            raise ValueError("checkpoint has no metrics state but the "
                             "engine was built with obs metrics on")
        from repro.obs import metrics as metrics_mod
        ms = metrics_mod.from_canonical(
            np.asarray(device["metrics"]["counts"]),
            np.float32(device["metrics"]["score"]),
            shards=engine._shards if engine.mesh is not None else 0)
        if engine.mesh is not None:
            from repro.parallel import fleet
            ms = fleet.shard_rows(engine.mesh, ms)
        engine._metrics_state = ms
    engine.meter.load_state(tree["host"]["meter"])
    # the meter fold's device rows are copies of the host meter's:
    # reload them from the restored ledgers
    engine._load_device_meter()
    if engine._residuals is not None:
        engine._residuals.load_state(tree["host"]["residuals"])
    if engine._cost_monitor is not None:
        engine._cost_monitor.load_state(tree["host"]["cost_monitor"])
    engine.chunks_ingested = int(tree["cursor"])
    engine.replan_events = [
        engine_mod.ReplanEvent(**{
            **e, "old_bounds": tuple(e["old_bounds"]),
            "new_bounds": tuple(e["new_bounds"])})
        for e in meta.get("replan_events", [])]
    engine.admission_events = []
    if meta.get("admission_events"):
        from repro.online.admission import AdmissionDecision
        for e in meta["admission_events"]:
            engine.admission_events.append(engine_mod.AdmissionEvent(
                stream_id=e["stream_id"], row=e["row"],
                position=e["position"],
                decision=AdmissionDecision(plan=None, **e["decision"])))
    engine._failed_tiers = {int(t): int(c)
                            for t, c in meta.get("failed_tiers",
                                                 {}).items()}
    engine._recovering_tiers = {
        int(t): int(c)
        for t, c in meta.get("recovering_tiers", {}).items()}
    engine._tier_outages = int(meta.get("tier_outages", 0))
