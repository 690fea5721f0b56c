"""Batched multi-tenant top-K stream engine.

One ``jax.jit``-ed step advances M concurrent reservoirs at once: state
carries a leading stream axis (``BatchedReservoirState``), the update is a
vectorized sort-merge over all streams (``jax.vmap`` of ``core.topk``, so
the per-stream semantics — deterministic tie-break, id dedupe, write mask —
are bit-identical to M independent single-stream replays), and the
accelerated path pre-filters candidates with the 2-D Pallas kernel
``kernels.batched_topk`` before the exact merge.

Heterogeneous fleets (per-stream K) are handled by bucketing streams by K
(``streams.router``); ``StreamEngine`` runs every bucket inside one jitted
multi-bucket step, plans placement proactively for the whole fleet
(``streams.planner``) and meters every transaction per stream
(``streams.metering``). Per-stream state is O(K) under the default
``engine="exact"`` backend; huge-K tenants can opt into the O(log K)
``engine="logmem"`` threshold tracker (``streams.logmem``) per
``StreamSpec`` — buckets are keyed by (K, engine), and both backends mix
freely inside one fleet step.
"""
from __future__ import annotations

import contextlib
import functools
from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import topk
from repro.core.costs import NTierCostModel, TwoTierCostModel
from repro.obs import jits, timers

from . import logmem, metering, planner, router

PAD_ID = router.PAD_ID


class BatchedReservoirState(NamedTuple):
    """M reservoirs stacked on a leading stream axis."""

    scores: jax.Array  # (M, K) float32, each row sorted desc, -inf padded
    ids: jax.Array  # (M, K) int32 per-stream local doc index, -1 padded
    seen: jax.Array  # (M,) int32 — docs observed per stream (padding excluded)


def init(m: int, k: int) -> BatchedReservoirState:
    return BatchedReservoirState(
        scores=jnp.full((m, k), -jnp.inf, dtype=jnp.float32),
        ids=jnp.full((m, k), -1, dtype=jnp.int32),
        seen=jnp.zeros((m,), dtype=jnp.int32),
    )


def _as_single(state: BatchedReservoirState) -> topk.ReservoirState:
    return topk.ReservoirState(scores=state.scores, ids=state.ids,
                               seen=state.seen)


@jax.named_scope("merge")
def update(state: BatchedReservoirState, batch_scores: jax.Array,
           batch_ids: jax.Array) -> Tuple[BatchedReservoirState, jax.Array]:
    """Fused update of all M streams: scores/ids (M, W), padding = (-inf, -1).

    Returns (new_state, wrote (M, W) bool). Padding never writes and does
    not advance ``seen``.
    """
    new, wrote = jax.vmap(topk.update)(_as_single(state), batch_scores,
                                       batch_ids)
    seen = state.seen + (batch_ids >= 0).sum(axis=1).astype(state.seen.dtype)
    return BatchedReservoirState(new.scores, new.ids, seen), wrote


def filtered_update(state: BatchedReservoirState, batch_scores: jax.Array,
                    batch_ids: jax.Array, *, block_n: int = 512,
                    use_pallas: bool = True
                    ) -> Tuple[BatchedReservoirState, jax.Array]:
    """The fleet step's exact update: one 2-D Pallas scan of all
    streams' candidates against their reservoir bars, then an exact
    merge over at most K survivors per stream (a batch wider than K is
    cut to its K first survivors by one sort; a narrower one is merged
    as it is).

    Equal to ``update`` for any order of doc ids (tests assert it): the
    scan keeps candidates tied with the bar, the cut and the merge both
    order by score descending, then id ascending, and the write mask is
    read by rank against the new K-th entry.
    """
    from repro.kernels.batched_topk import ops as btk_ops
    k = state.scores.shape[1]
    w = batch_scores.shape[1]
    bar = state.scores[:, -1:]
    batch_scores = batch_scores.astype(jnp.float32)
    batch_ids = batch_ids.astype(jnp.int32)
    with jax.named_scope("filter"):
        mask, _, _ = btk_ops.batched_topk_filter(batch_scores, bar[:, 0],
                                                 block_n=block_n,
                                                 use_pallas=use_pallas)
        # the scan passes scores above the bar; one equal to it may still
        # outrank the bar's entry by id. Re-observed resident ids go
        # *before* the cut, so they cannot take a survivor slot that a
        # fresh candidate (which plain ``update`` would admit) should get;
        # the merge below then needs no resident search of its own
        resident = jax.vmap(topk.member)(batch_ids, state.ids)
        keep = (((mask > 0) | (batch_scores == bar)) & ~resident
                & (batch_ids >= 0))
        surv = jnp.where(keep, batch_scores, -jnp.inf)
        surv_ids = jnp.where(keep, batch_ids, PAD_ID)
        if w > k:  # cut to the K first; at W <= K the cut keeps them all
            surv, surv_ids = jax.vmap(
                functools.partial(topk.first_k, k=k))(surv, surv_ids)
    with jax.named_scope("merge"):
        new, _ = jax.vmap(
            functools.partial(topk.update, check_resident=False))(
                _as_single(state), surv, surv_ids)
        # a kept candidate that the cut left out has K kept entries ahead
        # of it, so it ranks below the new K-th entry too
        wrote = keep & topk.ranks_at_or_above(
            batch_scores, batch_ids, new.scores[:, -1:], new.ids[:, -1:])
    seen = state.seen + (batch_ids >= 0).sum(axis=1).astype(state.seen.dtype)
    return BatchedReservoirState(new.scores, new.ids, seen), wrote


def merge(a: BatchedReservoirState,
          b: BatchedReservoirState) -> BatchedReservoirState:
    """Row-wise cross-shard reduction (see ``topk.merge``)."""
    new = jax.vmap(topk.merge)(_as_single(a), _as_single(b))
    return BatchedReservoirState(new.scores, new.ids, a.seen + b.seen)


def thresholds(state: BatchedReservoirState) -> jax.Array:
    """(M,) current per-stream entry bars (-inf while unfull)."""
    return state.scores[:, -1]


def placements(state: BatchedReservoirState, r) -> jax.Array:
    """Per-slot tier with per-stream changeovers: ``r`` is (M,) scalar
    boundaries (the two-tier case, via ``topk.tier_of``) or (M, B)
    boundary vectors (tier = number of boundaries <= id). -1 = empty."""
    r = jnp.asarray(r)
    if r.ndim <= 1:
        t = topk.tier_of(state.ids, r.reshape(-1, 1))
    else:
        t = (state.ids[:, :, None] >= r[:, None, :]).sum(-1).astype(jnp.int32)
    return jnp.where(state.ids >= 0, t, -1)


@jax.named_scope("evicted")
def dropped_ids(old: BatchedReservoirState,
                new: BatchedReservoirState) -> jax.Array:
    """(M, K) local doc ids evicted by the step (-1 = none) — the storage
    the fleet can free (paper §VI). ``new`` is ``update``'s or
    ``filtered_update``'s result from ``old``, so the evictions are read
    off the merge's order (``topk.dropped``), with no id search."""
    return jnp.where(topk.dropped(_as_single(old), _as_single(new)),
                     old.ids, PAD_ID)


@functools.lru_cache(maxsize=64)
def _make_step(use_kernel_filter: bool, block_n: int, drift_cfg=None,
               bucket_ks: Tuple[int, ...] = (),
               with_metrics: bool = False, mesh=None, donate: bool = False,
               bucket_engines: Tuple[str, ...] = ()):
    """One jitted step over ALL buckets: states/batches are same-length
    tuples (the pytree structure is static, so the whole fleet advances in
    a single XLA computation). With ``drift_cfg`` (online re-planning) the
    step also advances each bucket's drift-detector state from the chunk's
    write counts — the sequential statistics stay (M,)-batched on device.

    ``bucket_engines`` tags each bucket's backend (empty = all
    ``"exact"``): ``"logmem"`` buckets carry ``logmem.LogmemState``
    pytrees and advance through ``logmem.update`` (threshold-compare
    admission via the ``kernels.logmem_update`` Pallas scan when
    ``use_kernel_filter``); they report no evictions, their metrics bar
    is the active threshold ``tau``, and their drift evidence is tested
    with the backend's ``law_slack`` tolerance folded into the
    thresholds.

    Exact buckets take ``filtered_update`` at every batch width. On a
    TPU v5e it came within 0.7 % of ``update``'s one sort of K + W
    columns below K and beat it from W = K up, ms a chunk at K = 1,024:
    7.56 against 7.57 at 8,192 rows and W = 64, 13.21 against 13.13 at
    W = 512, 19.65 against 20.22 at W = 1,024, 18.14 against 20.95 at
    2,048 rows and W = 4,096 (where its cut to K first survivors spares
    the merge's sort half its width). ``use_kernel_filter`` upgrades its
    candidate scan to the Pallas kernel.

    With ``with_metrics`` (repro.obs) the step additionally folds a
    device-side ``obs.metrics.MetricsState`` — a few scalar reductions
    over values the step already materializes, fused into the same XLA
    program; when off, ``mstate`` is an empty tuple and the traced
    computation is exactly the pre-obs step (bit-identical outputs).

    With ``meters`` (one ``metering.MeterState`` per bucket) the step
    also meters each bucket on the device (``metering.fold``): it returns
    the advanced meter states and each bucket's per-(stream, tier)
    ``MeterDelta`` for ``FleetMeter.record_update``, so no per-document
    array leaves the device. ``meters = ()`` skips the fold (both
    outputs are then empty tuples). The cost bill (``obs.costs``) is
    priced from those meter counts on the host; nothing of it rides the
    step.

    The step is ``step(states, batches, dstates, mstate, meters)`` and
    returns ``(states, dstates, mstate, meters, deltas)``.

    With ``mesh`` (a ``parallel.fleet`` mesh) the whole step is
    ``shard_map``-ped over the fleet axis: every leading-M leaf —
    reservoir state, batch, drift state — splits across devices and each
    shard runs the exact single-device program on its rows (every update
    is row-independent, so sharded outputs are bit-identical; tests
    assert it). The metrics state keeps one counter block per shard
    (aggregated at snapshot), so the step stays collective-free.
    ``donate`` builds the double-buffered ingestion variant: the previous
    chunk's state/drift/metrics buffers are donated to XLA, letting the
    outputs reuse them while the next chunk's host→device copy is in
    flight (``StreamEngine.ingest_chunks``).

    Steps are cached by their configuration, so engines built alike
    share one jitted step and a second one traces and compiles nothing.
    """
    if drift_cfg is not None:
        from repro.online import drift as drift_mod
    if with_metrics:
        from repro.obs import metrics as metrics_mod

    def step(states, batches, dstates, mstate, meters):
        if with_metrics and mesh is not None:
            # inside shard_map: squeeze this shard's (1, 8) counter
            # block to the flat layout the accumulate laws expect
            mstate = metrics_mod.shard_local(mstate)
        new_states, new_dstates = [], []
        new_meters, deltas = [], []
        for bi, (st, (s, i)) in enumerate(zip(states, batches)):
            # quarantine non-finite scores before any compare sees them:
            # NaN fails every comparison (it would never be admitted and
            # never counted) and ±inf corrupts the entry bar / tile max.
            # Both demote to inert pad slots; the count is folded into
            # the metrics state (SCORES_QUARANTINED). With all-finite
            # input the wheres are identity, so outputs are bit-equal to
            # the unsanitized step.
            bad = (i >= 0) & ~jnp.isfinite(s)
            s = jnp.where(bad, -jnp.inf, s)
            i = jnp.where(bad, PAD_ID, i)
            if with_metrics:
                mstate = metrics_mod.accumulate_quarantine(
                    mstate, bad.sum(dtype=jnp.int32))
            if bucket_engines and bucket_engines[bi] == "logmem":
                new, wrote = logmem.update(st, s, i, int(bucket_ks[bi]),
                                           block_n=block_n,
                                           use_pallas=use_kernel_filter)
                # no ids stored → nothing evictable; the meter sees an
                # empty delete set and occupancy = cumulative writes
                ev = jnp.full((s.shape[0], 0), PAD_ID, jnp.int32)
                bar = st.tau
                slack = logmem.law_slack(bucket_ks[bi])
                stored = ()
            else:
                new, wrote = filtered_update(st, s, i, block_n=block_n,
                                             use_pallas=use_kernel_filter)
                ev = dropped_ids(st, new)
                bar = st.scores[:, -1]
                slack = 0.0
                stored = (ev, new.ids)
            new_states.append(new)
            if meters:
                ms, delta = metering.fold(meters[bi], i, wrote, *stored)
                new_meters.append(ms)
                deltas.append(delta)
            if drift_cfg is not None:
                new_dstates.append(drift_mod.update(
                    dstates[bi], wrote.sum(axis=1), new.seen,
                    float(bucket_ks[bi]), drift_cfg, slack=slack))
            if with_metrics:
                mstate = metrics_mod.accumulate_bucket(
                    mstate, s, i, bar, wrote, ev)
        if with_metrics:
            if drift_cfg is not None and new_dstates:
                score_max = jnp.asarray(0.0, jnp.float32)
                fired = jnp.asarray(0, jnp.int32)
                for bi, ds in enumerate(new_dstates):
                    sl = (logmem.law_slack(bucket_ks[bi])
                          if bucket_engines and bucket_engines[bi] == "logmem"
                          else 0.0)
                    score_max = jnp.maximum(
                        score_max,
                        drift_mod.scores(ds, drift_cfg, slack=sl).max())
                    fired = fired + ds.fired.sum(dtype=jnp.int32)
                mstate = metrics_mod.accumulate_drift(mstate, score_max,
                                                      fired)
            mstate = metrics_mod.bump_chunk(mstate)
        if with_metrics and mesh is not None:
            mstate = metrics_mod.shard_pack(mstate)
        return tuple(new_states), tuple(new_dstates), mstate, \
            tuple(new_meters), tuple(deltas)

    if mesh is not None:
        from repro.core import jaxcompat
        from repro.parallel import fleet
        spec = fleet.row_spec()
        step = jaxcompat.shard_map(step, mesh=mesh, in_specs=(spec,) * 5,
                                   out_specs=(spec,) * 5)
    return jax.jit(step, donate_argnums=(0, 2, 3) if donate else ())


# ---------------------------------------------------------------------------
# Fleet orchestration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ReplanEvent:
    """One online re-planning decision (``StreamEngine.replan_events``)."""

    stream_id: int
    row: int
    position: int  # docs the stream had observed at decision time
    rho: float  # detector's rate-multiplier estimate
    old_bounds: Tuple[float, ...]
    new_bounds: Tuple[float, ...]
    applied: bool
    feasible: bool  # constrained suffix re-solve found a feasible plan
    suffix_cost_old: float
    suffix_cost_new: float
    move_bill: float  # expected relocation cost priced into the decision
    moved_docs: int  # residents actually re-tiered by the meter


@dataclass(frozen=True)
class AdmissionEvent:
    """Advisory terms for a stream whose constrained suffix re-solve was
    infeasible (``StreamEngine.admission_events``): the negotiated K /
    window apply at the tenant's next window — a live reservoir row
    cannot be resized mid-window."""

    stream_id: int
    row: int
    position: int
    decision: object  # online.admission.AdmissionDecision


@dataclass(frozen=True)
class StreamSpec:
    """One tenant stream: its K, and either an explicit placement — a
    changeover index ``r`` (two-tier) or a ``boundaries`` vector (N-tier),
    with ``migrate`` choosing Algorithm C's cascade at the boundaries — or
    a cost model (two-tier or N-tier topology) for the proactive planner
    to derive both. Streams of different tier depths mix freely in one
    fleet.

    ``engine`` picks the reservoir backend: ``"exact"`` (default) keeps
    the full (K,) score/id rows; ``"logmem"`` keeps O(log K) state
    (``streams.logmem`` — huge-K tenants) at a 1−O(1/√K) admission
    slack. Logmem streams cannot run the migration cascade (no resident
    ids to cascade) — the planner's derived ``migrate`` is forced off
    for them and an explicit ``migrate=True`` is rejected."""

    stream_id: int
    k: int
    cost_model: Optional[TwoTierCostModel | NTierCostModel] = None
    r: Optional[float] = None
    migrate: bool = False
    boundaries: Optional[Tuple[float, ...]] = None
    engine: str = "exact"

    def explicit_boundaries(self) -> Optional[Tuple[float, ...]]:
        if self.boundaries is not None:
            return tuple(float(b) for b in self.boundaries)
        return (float(self.r),) if self.r is not None else None


class StreamEngine:
    """Host-side orchestrator: buckets streams by K, plans placement for
    the whole fleet in one vectorized pass, routes mixed ingest batches,
    advances every bucket inside one jitted step, and meters per-stream
    ledgers against the analytic expectations.

    Usage::

        engine = StreamEngine(specs)
        engine.ingest(stream_ids, scores, doc_ids)   # mixed batch, any order
        survivors = engine.finalize()                # {stream_id: top-K ids}
        engine.meter.reconcile(batch=W)              # vs analytic write law
    """

    def __init__(self, specs: Sequence[StreamSpec], *,
                 use_kernel_filter: bool = False, block_n: int = 512,
                 constraints=None, replan=None, obs=None, mesh=None):
        if not specs:
            raise ValueError("need at least one stream")
        # fleet-axis sharding (parallel.fleet): with a >=2-device mesh
        # every per-bucket state splits row-wise across devices, the
        # jitted step runs shard_map-ped, and the planner entry points
        # below dispatch per shard; a 1-device mesh is the plain path
        self._shards = 1
        if mesh is not None:
            from repro.parallel import fleet
            self._shards = fleet.n_shards(mesh)
            if self._shards < 2:
                mesh, self._shards = None, 1
        self.mesh = mesh
        by_id = {s.stream_id: s for s in specs}
        if len(by_id) != len(specs):
            raise ValueError("duplicate stream ids")
        for s in specs:
            if s.engine not in ("exact", "logmem"):
                raise ValueError(f"stream {s.stream_id}: unknown engine "
                                 f"{s.engine!r} (exact|logmem)")
            if s.engine == "logmem" and s.migrate:
                raise ValueError(
                    f"stream {s.stream_id}: engine='logmem' stores no "
                    "resident ids — the migration cascade needs the exact "
                    "backend")
        self.buckets = router.bucket_streams(
            {s.stream_id: s.k for s in specs},
            {s.stream_id: s.engine for s in specs})
        self.router = router.StreamRouter(self.buckets)
        self.constraints = constraints
        # observability (repro.obs): device metric pytree in the step,
        # residual alert channel off the meter drain, span/event timeline
        self._obs = obs
        self._tracer = obs.tracer if obs is not None else None
        if obs is not None:
            obs.attach(self)
        # fleet plan for streams that carry a cost model (2- and N-tier mix)
        planned = [s for s in specs if s.explicit_boundaries() is None]
        if planned:
            if any(s.cost_model is None for s in planned):
                raise ValueError(
                    "each stream needs r, boundaries, or a cost_model")
            if self._tracer is not None:
                with self._tracer.span("plan", streams=len(planned)):
                    plan = planner.plan_fleet_mixed(
                        [s.cost_model for s in planned],
                        constraints=constraints, mesh=mesh,
                        tracer=self._tracer)
            else:
                plan = planner.plan_fleet_mixed(
                    [s.cost_model for s in planned],
                    constraints=constraints, mesh=mesh)
            bad = [s.stream_id for i, s in enumerate(planned)
                   if not plan.feasible(i)]
            if bad:
                raise ValueError(
                    f"streams {bad} have no feasible plan under the given "
                    "constraints — relax capacities/SLO or drop the streams")
            b_of = {s.stream_id: plan.boundaries[i]
                    for i, s in enumerate(planned)}
            mig_of = {s.stream_id: plan.migrate(i)
                      for i, s in enumerate(planned)}
            self.plan: Optional[planner.MixedFleetPlan] = plan
        else:
            b_of, mig_of = {}, {}
            self.plan = None
        # global row order = bucket order × row order (the meter's layout)
        self._global_rows: List[np.ndarray] = []
        ks, bounds, migs, logmems = [], [], [], []
        offset = 0
        self._row_of: Dict[int, int] = {}
        self._model_of_row: Dict[int, object] = {}
        for b in self.buckets:
            rows = np.arange(offset, offset + b.m, dtype=np.int64)
            self._global_rows.append(rows)
            for j, sid in enumerate(b.stream_ids):
                self._row_of[sid] = offset + j
                spec = by_id[sid]
                if spec.cost_model is not None:
                    self._model_of_row[offset + j] = spec.cost_model
                ks.append(spec.k)
                logmems.append(spec.engine == "logmem")
                explicit = spec.explicit_boundaries()
                if explicit is not None:
                    bounds.append(explicit)
                    migs.append(spec.migrate)
                elif spec.engine == "logmem":
                    # planner-derived cascades need resident ids; logmem
                    # tenants take the plan's boundaries statically
                    bounds.append(b_of[sid])
                    migs.append(False)
                else:
                    bounds.append(b_of[sid])
                    migs.append(mig_of[sid])
            offset += b.m
        self._sid_of_row = {row: sid for sid, row in self._row_of.items()}
        self.meter = metering.FleetMeter(ks, migrate=migs, boundaries=bounds,
                                         logmem=logmems)
        # sharded buckets pad their row count to a multiple of the shard
        # count; pad rows carry (-inf, -1, seen=0) reservoirs and all-pad
        # batches, which every law (update, drift, metrics) treats as
        # inert — host-facing reads slice back to the true m
        self._pad_m: List[int] = [
            (-(-b.m // self._shards)) * self._shards for b in self.buckets]
        self._states: List = [
            (logmem.init(pm) if b.engine == "logmem" else init(pm, b.k))
            for pm, b in zip(self._pad_m, self.buckets)]
        if mesh is not None:
            from repro.parallel import fleet
            self._states = [fleet.shard_rows(mesh, st)
                            for st in self._states]
        # online re-planning: drift detector inside the jitted step,
        # boundary deltas applied between chunks (repro.online)
        self.replan_config = replan
        self.replan_events: List[ReplanEvent] = []
        self.admission_events: List[AdmissionEvent] = []
        self._drift_states = None
        self._replanner = None
        if replan is not None:
            from repro.online import drift as drift_mod
            from repro.online.replan import Replanner
            cset_arg = constraints
            if isinstance(constraints, (list, tuple)):
                # per-spec constraint lists align with the specs sequence;
                # the replanner indexes by global row
                by_sid = {s.stream_id: c
                          for s, c in zip(specs, constraints)}
                cset_arg = [by_sid[self._sid_of_row[row]]
                            for row in range(self.m)]
            self._replanner = Replanner(
                [self._model_of_row.get(row) for row in range(self.m)],
                constraints=cset_arg, config=replan)
            self._drift_states = [drift_mod.init(pm) for pm in self._pad_m]
            if mesh is not None:
                from repro.parallel import fleet
                self._drift_states = [fleet.shard_rows(mesh, ds)
                                      for ds in self._drift_states]
        self._metrics_state = None
        self._residuals = None
        if obs is not None:
            if obs.config.metrics:
                from repro.obs import metrics as metrics_mod
                self._metrics_state = metrics_mod.init(
                    shards=self._shards if mesh is not None else 0)
                if mesh is not None:
                    from repro.parallel import fleet
                    self._metrics_state = fleet.shard_rows(
                        mesh, self._metrics_state)
            if obs.config.residuals:
                from repro.obs.residuals import ResidualMonitor
                slack_rows = np.where(
                    self.meter.logmem,
                    np.array([logmem.law_slack(int(k))
                              for k in self.meter.ks]), 0.0)
                self._residuals = ResidualMonitor(
                    self.meter.ks, alpha=obs.config.residual_alpha,
                    max_checks=obs.config.residual_max_checks,
                    law_slack=slack_rows)
        # live cost attribution (obs.costs): the bill is priced from the
        # meter's counts, and the host CostMonitor (cost residuals +
        # budget burn rate) runs off the meter drain
        self._cost_monitor = None
        self._pricing = None
        if obs is not None and obs.config.costs:
            from repro.obs import costs as costs_mod
            self._pricing = costs_mod.stream_pricing(self)
            slack_rows = np.where(
                self.meter.logmem,
                np.array([logmem.law_slack(int(k))
                          for k in self.meter.ks]), 0.0)
            self._cost_monitor = costs_mod.CostMonitor(
                self.meter.ks, self.meter.boundaries,
                self._pricing["cw"], self._pricing["step_rate"],
                alpha=obs.config.cost_alpha,
                max_checks=obs.config.cost_max_checks,
                law_slack=slack_rows, logmem=self.meter.logmem,
                budget_factor=obs.config.budget_factor,
                burn_windows=obs.config.burn_windows)
        # the meter rows the step's meter fold reads, on the device
        self._meter_states: List[metering.MeterState] = [None] * len(
            self.buckets)
        self._load_device_meter()
        self._step_factory = lambda donate: _make_step(
            use_kernel_filter, block_n,
            drift_cfg=None if replan is None else replan.drift,
            bucket_ks=tuple(b.k for b in self.buckets),
            with_metrics=self._metrics_state is not None,
            mesh=mesh, donate=donate,
            bucket_engines=tuple(b.engine for b in self.buckets))
        self._step = self._step_factory(False)
        self._donating_step = None  # built lazily by ingest_chunks
        # every dispatch is counted per (bucket widths, donate, exact
        # buckets' membership methods) signature, so a recompile of the
        # step is named in the jit snapshot
        self._step_probe = jits.probe("streams.engine.step")
        # resilience (repro.resilience): the ingest cursor is the chunk
        # sequence number — checkpoint step, and the idempotent
        # redelivery guard's high-water mark; a checkpointer attached via
        # ``attach_checkpointer`` is invoked at every chunk boundary
        # (after the host meter drain, before the next dispatch, so the
        # device buffers it snapshots are final and not yet donated)
        self.chunks_ingested = 0
        self._checkpoint = None
        # tier-outage bookkeeping: failed tiers are masked out of the
        # re-planner's feasible set; a recovered tier stays masked for a
        # hysteresis window (flap damping) before plans may use it again
        self._failed_tiers: Dict[int, int] = {}
        self._recovering_tiers: Dict[int, int] = {}
        self._tier_outages = 0

    @property
    def m(self) -> int:
        return sum(b.m for b in self.buckets)

    def stream_row(self, stream_id: int) -> int:
        """Global (meter) row of a stream."""
        return self._row_of[stream_id]

    def ingest(self, stream_ids, scores, doc_ids, *,
               pad_to: Optional[int] = None) -> None:
        """Feed a mixed batch of scored docs — (stream_id, score, local doc
        index) triples in arbitrary order — through one jitted fleet step.

        The router groups the batch by stream in one stable radix pass,
        which keeps each stream's documents in batch order; it sorts a
        stream's documents by doc id only where they arrive out of
        position order (``route_resorted`` counts those batches). A doc
        id may appear at most once per stream per batch (they are stream
        positions); the router rejects within-batch duplicates.
        Re-observations across batches are deduped by the merge itself."""
        if self._tracer is not None and self._obs.config.trace_ingest:
            with self._tracer.span("ingest", docs=int(len(stream_ids))):
                self._ingest(stream_ids, scores, doc_ids, pad_to)
        else:
            self._ingest(stream_ids, scores, doc_ids, pad_to)

    def _ingest(self, stream_ids, scores, doc_ids, pad_to) -> None:
        r = self.router
        slots, useful = r.route_slots, r.route_useful
        resorted = r.route_resorted_rows
        with self._span("ingest.route") as sp:
            routed = r.route(stream_ids, scores, doc_ids, pad_to=pad_to)
            sp.attrs["slots"] = r.route_slots - slots
            sp.attrs["useful"] = r.route_useful - useful
            sp.attrs["resorted"] = r.route_resorted_rows - resorted
        self._run_chunk(routed)

    def _span(self, name: str, chunk: Optional[int] = None, **attrs):
        """One per-chunk ingest span (``ingest.*``): recorded by the
        engine's tracer, or a bare ``timers.span`` on an engine without
        one; nothing at all when ``ObsConfig.trace_ingest`` is off.
        ``chunk`` defaults to the chunk being consumed."""
        if self._obs is not None and not self._obs.config.trace_ingest:
            return contextlib.nullcontext(timers.Span(name))
        return timers.span(name, self._tracer,
                           chunk=(self.chunks_ingested if chunk is None
                                  else chunk), **attrs)

    def _stage_batches(self, dense, chunk: Optional[int] = None) -> tuple:
        """Host dense per-bucket (scores, ids) pairs → device batches:
        plain ``jnp.asarray`` single-device, or row-padded + fleet-
        sharded ``device_put`` under a mesh (the transfer is async, which
        is what ``ingest_chunks`` overlaps with the previous compute).
        ``chunk`` is the index of the chunk staged (its span's tag)."""
        nbytes = sum(getattr(a, "nbytes", 0) for pair in dense for a in pair)
        with self._span("ingest.stage", chunk, bytes=nbytes):
            if self.mesh is None:
                return tuple((jnp.asarray(s), jnp.asarray(i))
                             for s, i in dense)
            from repro.parallel import fleet
            sh = fleet.row_sharding(self.mesh)
            out = []
            for bi, (s, i) in enumerate(dense):
                pad = self._pad_m[bi] - s.shape[0]
                if pad:
                    ps, pi = router.blank_dense(pad, s.shape[1])
                    s = np.concatenate([s, ps])
                    i = np.concatenate([i, pi])
                out.append((jax.device_put(s, sh), jax.device_put(i, sh)))
            return tuple(out)

    def _place(self, tree):
        """Host arrays of per-row leaves -> device (row-sharded under a
        mesh)."""
        if self.mesh is not None:
            from repro.parallel import fleet
            return fleet.shard_rows(self.mesh, tree)
        return jax.tree_util.tree_map(jnp.asarray, tree)

    def _load_device_meter(self, buckets: Optional[Sequence[int]] = None
                           ) -> None:
        """(Re)load the device copies of the host meter's rows — the
        state the step's meter fold reads — after the host changed them
        (a re-plan, an evacuation, a restore). Shapes are unchanged, so
        nothing recompiles."""
        for bi in (range(len(self.buckets)) if buckets is None
                   else buckets):
            self._meter_states[bi] = self._place(self.meter.device_state(
                self._global_rows[bi], self._pad_m[bi]))

    def _dispatch(self, batches, donate: bool, meter: bool = True):
        """Run one (already staged) fleet step and swap in the new
        device states (the meter's only when ``meter``). Returns
        (deltas, new_states) for the host meter, still in flight."""
        dstates = (tuple(self._drift_states)
                   if self._drift_states is not None else ())
        mstate = (self._metrics_state
                  if self._metrics_state is not None else ())
        if donate:
            if self._donating_step is None:
                self._donating_step = self._step_factory(True)
            step = self._donating_step
        else:
            step = self._step
        widths = tuple(int(s.shape[1]) for s, _ in batches)
        # the membership search each exact bucket was compiled with
        members = tuple(topk.member_method(w, b.k)
                        for w, b in zip(widths, self.buckets)
                        if b.engine == "exact")
        key = (widths, donate, members)
        with self._span("ingest.dispatch"):
            new_states, new_dstates, mstate, new_meters, deltas = \
                self._step_probe.track(
                    step, tuple(self._states), batches, dstates, mstate,
                    tuple(self._meter_states), key=key)
        self._states = list(new_states)
        if self._metrics_state is not None:
            self._metrics_state = mstate
        if self._drift_states is not None:
            self._drift_states = list(new_dstates)
        if meter:
            self._meter_states = list(new_meters)
        return deltas, new_states

    def _consume(self, deltas, new_states, meter: bool = True) -> None:
        """Host side of one step: wait for its outputs, copy the meter's
        per-(stream, tier) counts to the host (slicing any sharded
        padding back off), add them to the ledgers, drain residuals,
        maybe re-plan."""
        if not meter:
            return
        with self._span("ingest.wait"):
            jax.block_until_ready((deltas, new_states))
        with self._span("ingest.fetch") as sp:
            fetched = [metering.MeterDelta(*(a[:b.m] for a in d))
                       for d, b in zip(jax.device_get(deltas),
                                       self.buckets)]
            sp.attrs["bytes"] = sum(a.nbytes for d in fetched for a in d)
        with self._span("ingest.meter"):
            for rows, delta in zip(self._global_rows, fetched):
                # a bucket's rows are contiguous: index them by a slice
                self.meter.record_update(
                    slice(int(rows[0]), int(rows[-1]) + 1), delta)
        with self._span("ingest.monitors"):
            residual_rows, cost_rows = self._update_monitors()
        if self._drift_states is not None:
            self._maybe_replan(residual_rows, cost_rows)

    def _update_monitors(self) -> Tuple[tuple, tuple]:
        """Chunk-boundary drain of the residual and cost alert channels
        from the meter; returns the rows each channel flags for re-plan
        (empty unless it is configured as a trigger)."""
        residual_rows = ()
        if self._residuals is not None:
            # the alert channel tests the meter's cumulative write
            # residual against its concentration bound
            newly = self._residuals.update(self.meter.observed,
                                           self.meter.writes.sum(1))
            if newly.any() and self._tracer is not None:
                sc = self._residuals.scores()
                for row in np.flatnonzero(newly):
                    self._tracer.emit(
                        "residual_alert", stream_id=self._sid_of_row[row],
                        row=int(row), position=int(self.meter.observed[row]),
                        score=float(sc[row]),
                        step=int(self._residuals.steps))
            if (self._obs.config.residual_trigger
                    and self._drift_states is not None):
                residual_rows = tuple(
                    int(r) for r in np.flatnonzero(self._residuals.alerted))
        cost_rows = ()
        if self._cost_monitor is not None:
            # the cost channel runs off the same meter drain: realized
            # spend vs the closed-form expected-cost trajectory
            newly_cost, newly_burn = self._cost_monitor.update(
                self.meter.observed, self.meter.writes,
                self.meter.doc_steps)
            if self._tracer is not None and newly_cost.any():
                sc = self._cost_monitor.scores()
                for row in np.flatnonzero(newly_cost):
                    self._tracer.emit(
                        "cost_alert", stream_id=self._sid_of_row[row],
                        row=int(row),
                        position=int(self.meter.observed[row]),
                        score=float(sc[row]),
                        step=int(self._cost_monitor.steps))
            if self._tracer is not None and newly_burn.any():
                br = self._cost_monitor.burn_ratio()
                for row in np.flatnonzero(newly_burn):
                    self._tracer.emit(
                        "budget_burn", stream_id=self._sid_of_row[row],
                        row=int(row),
                        position=int(self.meter.observed[row]),
                        burn_ratio=float(br[row]),
                        realized=float(
                            self._cost_monitor.realized_total[row]),
                        planned=float(
                            self._cost_monitor.planned_total[row]),
                        step=int(self._cost_monitor.steps))
            if (self._obs.config.cost_trigger
                    and self._drift_states is not None):
                cost_rows = tuple(int(r) for r in np.flatnonzero(
                    self._cost_monitor.alerted
                    | self._cost_monitor.burn_alerted))
        return residual_rows, cost_rows

    def _run_chunk(self, dense, *, meter: bool = True,
                   donate: bool = False) -> None:
        batches = self._stage_batches(dense)
        deltas, new_states = self._dispatch(batches, donate, meter)
        self._consume(deltas, new_states, meter=meter)
        self._chunk_boundary()

    def _chunk_boundary(self) -> None:
        """Advance the ingest cursor and fire the chunk-boundary
        checkpoint hook (device buffers are final here and the next
        chunk has not been dispatched, so a snapshot is consistent and
        its device→host copies cannot race a donation)."""
        chunk = self.chunks_ingested
        self.chunks_ingested += 1
        if self._checkpoint is not None:
            with self._span("ingest.checkpoint", chunk):
                self._checkpoint.on_chunk(self)

    def attach_checkpointer(self, checkpointer) -> None:
        """Install a chunk-boundary checkpoint hook (an object with
        ``on_chunk(engine)`` — see ``resilience.FleetCheckpointer``)."""
        if not hasattr(checkpointer, "on_chunk"):
            raise TypeError("checkpointer needs an on_chunk(engine) hook")
        self._checkpoint = checkpointer

    def ingest_dense(self, dense, *, meter: bool = True) -> None:
        """Dense per-bucket ingestion, bypassing the host router: one
        ``(scores (M_b, W), doc_ids (M_b, W))`` pair per bucket, aligned
        with ``self.buckets``, rows ordered by doc id and padded with
        ``(-inf, -1)`` — the layout ``router.route`` would produce. This
        is the million-stream path: at fleet scale the router's host
        scatter dominates, and producers that already emit per-stream
        chunks can feed the jitted step directly.

        ``meter=False`` skips the per-stream host ledgers *and* the
        online re-plan/residual hooks for this chunk (pure-throughput
        mode; the device states and obs counters still advance).
        """
        if len(dense) != len(self.buckets):
            raise ValueError(f"need one (scores, ids) pair per bucket "
                             f"({len(self.buckets)}), got {len(dense)}")
        dense = [(np.asarray(s, np.float32), np.asarray(i, np.int32))
                 for s, i in dense]
        for bi, (s, i) in enumerate(dense):
            if s.shape != i.shape or s.shape[0] != self.buckets[bi].m:
                raise ValueError(
                    f"bucket {bi}: scores {s.shape} / ids {i.shape} do "
                    f"not match the bucket's {self.buckets[bi].m} streams")
        self._run_chunk(dense, meter=meter)

    def ingest_chunks(self, chunks, *, meter: bool = True) -> int:
        """Async double-buffered dense ingestion: consume an iterable of
        ``ingest_dense``-shaped chunk lists, keeping chunk t+1's
        host→device transfer in flight while chunk t computes, and
        donating the previous state/drift/metrics buffers to the step so
        XLA reuses them for the outputs (no steady-state allocation).
        Returns the number of chunks processed."""
        it = iter(chunks)
        nxt = next(it, None)
        staged = self._stage_batches(nxt) if nxt is not None else None
        count = 0
        while staged is not None:
            # dispatch is async: the step runs while we stage chunk t+1
            deltas, new_states = self._dispatch(staged, True, meter)
            nxt = next(it, None)
            staged = (self._stage_batches(nxt, self.chunks_ingested + 1)
                      if nxt is not None else None)
            # host consumption blocks on chunk t's outputs last
            self._consume(deltas, new_states, meter=meter)
            # chunk-boundary checkpoint: the device→host copies read
            # finished buffers, the npy write runs on the manager's
            # worker thread while chunk t+1 (already staged) computes
            self._chunk_boundary()
            count += 1
        return count

    def _maybe_replan(self, residual_rows: Sequence[int] = (),
                      cost_rows: Sequence[int] = ()) -> None:
        """Between chunks: re-plan the streams whose drift detector fired
        — unioned with the obs residual-alert channel when it is
        configured as an earlier trigger (``ObsConfig.residual_trigger``)
        and with the cost/budget-burn channel under
        ``ObsConfig.cost_trigger`` — apply the boundary deltas to the
        meter (re-tiering residents, with the relocation bill already
        priced into the decision), and reset the consumed detector (and
        residual/cost) evidence."""
        from repro.online import drift as drift_mod
        fired_rows, rhos = [], []
        bucket_of, row_in_bucket = [], []
        extra = set(residual_rows) | set(cost_rows)
        for bi in range(len(self.buckets)):
            ds = self._drift_states[bi]
            fired = np.asarray(ds.fired)[:self.buckets[bi].m]
            rows_b = self._global_rows[bi]
            flag = fired.copy()
            if extra:
                flag |= np.isin(rows_b, list(extra))
            if not flag.any():
                continue
            rho_b = np.asarray(drift_mod.rho_hat(ds,
                                                 self.replan_config.drift))
            for j in np.flatnonzero(flag):
                fired_rows.append(int(rows_b[j]))
                rhos.append(float(rho_b[j]))
                bucket_of.append(bi)
                row_in_bucket.append(int(j))
        if not fired_rows:
            return
        rows = np.asarray(fired_rows, np.int64)
        bounds = []
        for row in rows:
            cm = self._model_of_row.get(row)
            b = self.meter.boundaries[row]
            depth = (cm.t - 1 if hasattr(cm, "t")
                     else int(np.isfinite(b).sum()))
            bounds.append(tuple(b[:depth]))
        exclude = self._excluded_tier_set()
        if self._tracer is not None:
            with self._tracer.span("replan", flagged=len(fired_rows)):
                dec = self._replanner.replan(
                    rows, self.meter.observed[rows], np.asarray(rhos),
                    bounds, self.meter.migrate[rows],
                    hwm=self.meter.occupancy_hwm[rows],
                    exclude_tiers=exclude)
        else:
            dec = self._replanner.replan(rows, self.meter.observed[rows],
                                         np.asarray(rhos), bounds,
                                         self.meter.migrate[rows],
                                         hwm=self.meter.occupancy_hwm[rows],
                                         exclude_tiers=exclude)
        touched_buckets = set()
        for j, row in enumerate(rows):
            if not dec.considered[j]:
                continue  # no model / cascade / window over: nothing to log
            moved = 0
            if not dec.feasible[j]:
                self._negotiate_admission(int(row), int(dec.n_seen[j]))
            if dec.applied[j]:
                moved = self._apply_row_bounds(int(row), dec.new_bounds[j])
                touched_buckets.add(bucket_of[j])
            self.replan_events.append(ReplanEvent(
                stream_id=self._sid_of_row[int(row)], row=int(row),
                position=int(dec.n_seen[j]), rho=float(dec.rho[j]),
                old_bounds=dec.old_bounds[j], new_bounds=dec.new_bounds[j],
                applied=bool(dec.applied[j]), feasible=bool(dec.feasible[j]),
                suffix_cost_old=float(dec.suffix_cost_old[j]),
                suffix_cost_new=float(dec.suffix_cost_new[j]),
                move_bill=float(dec.move_bill[j]), moved_docs=moved))
            if self._tracer is not None:
                self._tracer.emit(
                    "replan_decision", stream_id=self._sid_of_row[int(row)],
                    row=int(row), position=int(dec.n_seen[j]),
                    rho=float(dec.rho[j]), applied=bool(dec.applied[j]),
                    feasible=bool(dec.feasible[j]), moved_docs=moved,
                    residual_triggered=int(row) in set(residual_rows),
                    cost_triggered=int(row) in set(cost_rows))
        # boundary deltas are placement metadata: the reservoirs themselves
        # must be untouched — every affected bucket keeps the sorted-desc
        # score invariant the merge relies on
        for bi in touched_buckets:
            if self.buckets[bi].engine == "logmem":
                continue  # no reservoir rows to corrupt
            scores = np.asarray(self._states[bi].scores)
            # note -inf pads diff to NaN on unfull rows — only a strictly
            # positive diff is a genuine order violation
            assert not np.any(np.diff(scores, axis=1) > 0), \
                "re-plan corrupted reservoir score order"
        for bi in set(bucket_of):
            mask = np.zeros(self._pad_m[bi], bool)
            mask[[row_in_bucket[j] for j in range(len(rows))
                  if bucket_of[j] == bi]] = True
            self._drift_states[bi] = drift_mod.reset_where(
                self._drift_states[bi], jnp.asarray(mask))
            if self.mesh is not None:
                # the eager where may have gathered — re-pin the fleet layout
                from repro.parallel import fleet
                self._drift_states[bi] = fleet.shard_rows(
                    self.mesh, self._drift_states[bi])
        self._load_device_meter(sorted(touched_buckets))
        if self._residuals is not None:
            # the re-plan consumed this evidence — restart the residual
            # channel for the processed rows, like the detector
            rmask = np.zeros(self.m, bool)
            rmask[rows] = True
            self._residuals.reset_where(rmask)
        if self._cost_monitor is not None:
            cmask = np.zeros(self.m, bool)
            cmask[rows] = True
            self._cost_monitor.reset_where(cmask)

    def _negotiate_admission(self, row: int, position: int) -> None:
        """A constrained suffix re-solve found no feasible plan (or the
        observed occupancy already violates a capacity): negotiate
        next-window terms for the tenant instead of silently dropping the
        event."""
        from repro.online.admission import AdmissionController
        cm = self._model_of_row.get(row)
        if cm is None:
            return
        cset = self._replanner.csets[row]
        decision = AdmissionController(cset).admit(
            cm.as_ntier() if isinstance(cm, TwoTierCostModel) else cm)
        self.admission_events.append(AdmissionEvent(
            stream_id=self._sid_of_row[row], row=row, position=position,
            decision=decision))
        if self._tracer is not None:
            self._tracer.emit("admission", stream_id=self._sid_of_row[row],
                              row=row, position=position,
                              admitted=bool(getattr(decision, "admitted",
                                                    False)))

    # ---- tier-outage graceful degradation -------------------------------

    def _bucket_of(self, row: int) -> Tuple[int, int]:
        """(bucket index, row within bucket) of a global meter row."""
        for bi, rows in enumerate(self._global_rows):
            if rows.size and rows[0] <= row <= rows[-1]:
                return bi, int(row - rows[0])
        raise KeyError(row)

    def _apply_row_bounds(self, row: int, new_bounds) -> int:
        """Apply a new boundary vector to one stream on the host: the
        meter (re-tiering residents) and the cost monitor's planned
        trajectory. The caller then reloads the touched buckets' device
        copies (``_load_device_meter``). Returns the number of relocated
        residents."""
        bi, jb = self._bucket_of(row)
        ids_arg = (None if self.buckets[bi].engine == "logmem"
                   else self._row_ids(bi, jb))
        moved = self.meter.apply_boundaries(row, new_bounds, ids_arg)
        if self._cost_monitor is not None:
            self._cost_monitor.set_bounds(row, self.meter.boundaries[row])
        return moved

    def _row_ids(self, bi: int, jb: int) -> np.ndarray:
        """Row ``jb`` of bucket ``bi``'s reservoir ids on the host, read
        from the one device shard that holds it (a row-sharded array
        cannot be indexed eagerly)."""
        for shard in self._states[bi].ids.addressable_shards:
            lo = shard.index[0].start or 0
            if lo <= jb < lo + shard.data.shape[0]:
                return np.asarray(shard.data[jb - lo])
        raise KeyError((bi, jb))

    def _excluded_tier_set(self) -> frozenset:
        """Tiers no plan may place onto right now: failed tiers, plus
        recovered tiers still inside their hysteresis window (expired
        entries are purged — flap damping)."""
        expired = [t for t, until in self._recovering_tiers.items()
                   if self.chunks_ingested >= until]
        for t in expired:
            del self._recovering_tiers[t]
        return frozenset(self._failed_tiers) | frozenset(
            self._recovering_tiers)

    def tier_outage(self, tier: int, *, burn_grace: int = 8) -> Dict:
        """Declare a storage tier failed: mask it out of every future
        re-plan's feasible set and evacuate affected streams onto the
        surviving tiers now — a forced constrained suffix re-solve for
        streams with a cost model (relocation hop-priced, applied on
        feasibility rather than savings), a geometric boundary merge
        (``core.constraints.evacuation_boundaries``) for the rest.

        The relocation spend spike is operator-induced, so the cost
        channel is kept honest rather than silenced wholesale: the
        evacuation bill is credited to each stream's planned trajectory
        (``CostMonitor.add_planned`` — regret does not blame the
        placement) and budget-burn alerts are suppressed for
        ``burn_grace`` chunks on the evacuated rows only.

        Returns a summary dict; emits ``tier_outage`` (and per-stream
        ``tier_evacuation``) on the obs event log. Idempotent: a tier
        already failed returns ``{"already_failed": True}`` without
        re-evacuating (flap protection on the failure side)."""
        nt = self.meter.n_tiers
        if not 0 <= tier < nt:
            raise ValueError(f"tier {tier} out of range (fleet has {nt} "
                             "tiers)")
        if tier in self._failed_tiers:
            return {"tier": tier, "already_failed": True,
                    "rows_evacuated": 0, "rows": [], "moved_docs": 0,
                    "bill": 0.0, "skipped_rows": [],
                    "infeasible_rows": []}
        # a re-failure during recovery hysteresis folds into the outage
        self._recovering_tiers.pop(tier, None)
        self._failed_tiers[tier] = self.chunks_ingested
        self._tier_outages += 1
        summary = self._evacuate_tier(tier, burn_grace=burn_grace)
        if self._tracer is not None:
            self._tracer.emit(
                "tier_outage", tier=tier, chunk=self.chunks_ingested,
                rows_evacuated=summary["rows_evacuated"],
                moved_docs=summary["moved_docs"], bill=summary["bill"],
                skipped=len(summary["skipped_rows"]),
                infeasible=len(summary["infeasible_rows"]))
        return summary

    def tier_recover(self, tier: int, *, hysteresis: int = 2) -> None:
        """Clear a tier's outage. The tier stays masked from re-plans
        for ``hysteresis`` more chunks (flap damping) before placements
        may use it again; evacuated streams migrate back only through
        the ordinary re-plan channel — there is no forced
        un-evacuation."""
        if tier not in self._failed_tiers:
            raise ValueError(f"tier {tier} is not failed")
        del self._failed_tiers[tier]
        self._recovering_tiers[tier] = self.chunks_ingested + int(hysteresis)
        if self._tracer is not None:
            self._tracer.emit(
                "tier_recovered", tier=tier, chunk=self.chunks_ingested,
                masked_until_chunk=int(self._recovering_tiers[tier]))

    def _evacuate_tier(self, tier: int, *, burn_grace: int) -> Dict:
        """Move every affected stream off a failed tier. Affected =
        the tier exists in the stream's placement AND (residents live
        there now, or future arrivals would land there). Cascade
        (migrating) streams cannot re-tier residents and are skipped,
        as are single-tier streams (no surviving tier to move into) —
        both are reported, not silently dropped."""
        from repro.core import constraints as cons_mod
        meter = self.meter
        b = meter.boundaries
        m = self.m
        observed = meter.observed.astype(np.float64)
        lo = b[:, tier - 1] if tier > 0 else np.zeros(m)
        hi = (b[:, tier] if tier < b.shape[1] else np.full(m, np.inf))
        exists = np.isfinite(lo) if tier > 0 else np.ones(m, bool)
        resident = ((meter.occupancy[:, tier] > 0)
                    if tier < meter.n_tiers else np.zeros(m, bool))
        future = (hi > lo) & (hi > observed)
        affected = exists & (resident | future)
        rr0 = meter.reloc_reads.copy()
        rw0 = meter.reloc_writes.copy()
        evacuated: List[int] = []
        skipped: List[int] = []
        infeasible: List[int] = []
        touched: set = set()
        moved_total = 0
        exclude = self._excluded_tier_set()
        for row in np.flatnonzero(affected):
            row = int(row)
            if meter.migrate[row]:
                skipped.append(row)
                continue
            depth = int(np.isfinite(b[row]).sum())
            if depth == 0:
                skipped.append(row)  # single-tier: nowhere to go
                continue
            old = tuple(float(x) for x in b[row, :depth])
            moved = 0
            applied = False
            if (self._model_of_row.get(row) is not None
                    and self._replanner is not None):
                rho = 1.0
                if self._drift_states is not None:
                    from repro.online import drift as drift_mod
                    bi, jb = self._bucket_of(row)
                    rho = float(np.asarray(drift_mod.rho_hat(
                        self._drift_states[bi],
                        self.replan_config.drift))[jb])
                dec = self._replanner.replan(
                    np.asarray([row], np.int64), meter.observed[[row]],
                    np.asarray([rho]), [old], meter.migrate[[row]],
                    hwm=meter.occupancy_hwm[[row]],
                    exclude_tiers=exclude, force=True)
                if not dec.feasible[0]:
                    # the surviving tiers cannot honor the constraints:
                    # negotiate next-window terms, but still evacuate —
                    # data cannot stay on a dead tier
                    infeasible.append(row)
                    self._negotiate_admission(row,
                                              int(meter.observed[row]))
                if dec.applied[0]:
                    moved = self._apply_row_bounds(row, dec.new_bounds[0])
                    applied = True
            if not applied:
                newb = cons_mod.evacuation_boundaries(old, tier)
                moved = self._apply_row_bounds(row, tuple(newb))
            evacuated.append(row)
            touched.add(self._bucket_of(row)[0])
            moved_total += moved
            if self._tracer is not None:
                self._tracer.emit(
                    "tier_evacuation", stream_id=self._sid_of_row[row],
                    row=row, tier=tier, moved_docs=moved,
                    replanned=applied,
                    position=int(meter.observed[row]))
        bill = 0.0
        bills = np.zeros(m, np.float64)
        if self._pricing is not None:
            d_rr = (meter.reloc_reads - rr0).astype(np.float64)
            d_rw = (meter.reloc_writes - rw0).astype(np.float64)
            bills = (d_rr * self._pricing["cr"]).sum(1) \
                + (d_rw * self._pricing["cw"]).sum(1)
            bill = float(bills.sum())
        if evacuated:
            emask = np.zeros(m, bool)
            emask[evacuated] = True
            # the evacuation consumed whatever evidence the monitors had
            # anchored to the old placement — restart it, like a re-plan
            if self._drift_states is not None:
                from repro.online import drift as drift_mod
                for bi in sorted(touched):
                    rows_b = self._global_rows[bi]
                    bmask = np.zeros(self._pad_m[bi], bool)
                    bmask[[r - int(rows_b[0]) for r in evacuated
                           if rows_b[0] <= r <= rows_b[-1]]] = True
                    self._drift_states[bi] = drift_mod.reset_where(
                        self._drift_states[bi], jnp.asarray(bmask))
                    if self.mesh is not None:
                        from repro.parallel import fleet
                        self._drift_states[bi] = fleet.shard_rows(
                            self.mesh, self._drift_states[bi])
            self._load_device_meter(sorted(touched))
            if self._residuals is not None:
                self._residuals.reset_where(emask)
            if self._cost_monitor is not None:
                self._cost_monitor.reset_where(emask)
                self._cost_monitor.suppress_burn(emask, burn_grace)
                for row in evacuated:
                    self._cost_monitor.add_planned(row, float(bills[row]))
        return {"tier": tier, "already_failed": False,
                "rows_evacuated": len(evacuated),
                "rows": [int(r) for r in evacuated],
                "moved_docs": int(moved_total), "bill": bill,
                "skipped_rows": skipped, "infeasible_rows": infeasible}

    def drift_scores(self) -> Dict[int, float]:
        """{stream_id: normalized change score} (>= 1 fires; online mode
        only)."""
        from repro.online import drift as drift_mod
        if self._drift_states is None:
            raise ValueError("engine built without replan=")
        out = {}
        for bi, b in enumerate(self.buckets):
            sl = logmem.law_slack(b.k) if b.engine == "logmem" else 0.0
            sc = np.asarray(drift_mod.scores(self._drift_states[bi],
                                             self.replan_config.drift,
                                             slack=sl))
            out.update({sid: float(sc[j])
                        for j, sid in enumerate(b.stream_ids)})
        return out

    def states(self) -> List[BatchedReservoirState]:
        return list(self._states)

    def thresholds(self) -> Dict[int, float]:
        out = {}
        for bi, b in enumerate(self.buckets):
            bar_fn = (logmem.thresholds if b.engine == "logmem"
                      else thresholds)
            bars = np.asarray(bar_fn(self._states[bi]))
            out.update({sid: float(bars[j])
                        for j, sid in enumerate(b.stream_ids)})
        return out

    def survivors(self) -> Dict[int, np.ndarray]:
        """{stream_id: sorted local doc ids currently in the reservoir}.
        Logmem streams store no ids — they report an empty set (their
        admitted docs live in tiered storage, not in device state)."""
        out = {}
        for bi, b in enumerate(self.buckets):
            if b.engine == "logmem":
                for sid in b.stream_ids:
                    out[sid] = np.empty(0, np.int64)
                continue
            ids = np.asarray(self._states[bi].ids)
            for j, sid in enumerate(b.stream_ids):
                v = ids[j]
                out[sid] = np.sort(v[v >= 0]).astype(np.int64)
        return out

    def residual_alerts(self) -> Dict[int, int]:
        """{stream_id: docs observed at first alert} of the obs residual
        channel — directly comparable to ``replan_events[i].position``
        (streams that never alerted are absent; obs mode only)."""
        if self._residuals is None:
            raise ValueError("engine built without obs= (or residuals off)")
        out = {}
        for row in np.flatnonzero(self._residuals.first_alert_seen >= 0):
            out[self._sid_of_row[int(row)]] = int(
                self._residuals.first_alert_seen[row])
        return out

    def obs_snapshot(self) -> Dict:
        """Everything the obs layer exports for this engine: drained
        device counters, meter ledger aggregates (per-tier occupancy
        high-water marks, relocations), and the model-referenced
        residual metrics (realized / expected / z for the write law;
        realized / expected for the occupancy law)."""
        from repro.obs import residuals as res_mod
        out: Dict = {"fleet": {"m": self.m, "buckets": len(self.buckets),
                               "logmem_streams":
                                   int(self.meter.logmem.sum())},
                     "router": {"route_slots": self.router.route_slots,
                                "route_useful": self.router.route_useful,
                                "route_resorted": self.router.route_resorted,
                                "route_resorted_rows":
                                    self.router.route_resorted_rows}}
        if self._metrics_state is not None:
            from repro.obs import metrics as metrics_mod
            out["engine"] = metrics_mod.snapshot(self._metrics_state)
        out["meter"] = {
            "observed": int(self.meter.observed.sum()),
            "writes": int(self.meter.writes.sum()),
            "reads": int(self.meter.reads.sum()),
            "deletes": int(self.meter.deletes.sum()),
            "migrations": int(self.meter.migrations.sum()),
            "relocations": int(self.meter.relocations.sum()),
            "occupancy_hwm": [int(x)
                              for x in self.meter.occupancy_hwm.sum(0)],
        }
        # the monitor's totals evaluate the write law at the actual
        # ingest chunking; without it fall back to the per-doc law
        wr = (self._residuals.write_z() if self._residuals is not None
              else res_mod.write_residuals(self.meter))
        occ = res_mod.occupancy_residuals(self.meter)
        out["residuals"] = {
            "writes": {
                "fleet_realized": float(wr["realized"].sum()),
                "fleet_expected": float(wr["expected"].sum()),
                "max_abs_z": float(np.abs(wr["z"]).max()) if self.m else 0.0,
                "mean_z": float(wr["z"].mean()) if self.m else 0.0,
            },
            "occupancy": {
                "fleet_realized": float(np.nansum(occ["realized"])),
                "fleet_expected": float(np.nansum(occ["expected"])),
                # all-NaN before any metered chunk (pure-throughput mode)
                "max_normalized": float(np.nanmax(np.abs(occ["normalized"])))
                if self.m and not np.isnan(occ["normalized"]).all() else 0.0,
            },
        }
        if self._residuals is not None:
            out["residuals"]["alerts"] = self._residuals.snapshot()
        if self._cost_monitor is not None:
            from repro.obs import costs as costs_mod
            out["costs"] = costs_mod.snapshot(self)
        out["resilience"] = {
            "chunks_ingested": int(self.chunks_ingested),
            "failed_tiers": sorted(self._failed_tiers),
            "recovering_tiers": sorted(self._recovering_tiers),
            "tier_outages": int(self._tier_outages),
        }
        if (self._checkpoint is not None
                and hasattr(self._checkpoint, "snapshot")):
            out["resilience"]["checkpoint"] = self._checkpoint.snapshot()
        return out

    def cost_summary(self) -> Dict:
        """Per-stream realized / planned / regret cost arrays from the
        meter's counts + host monitor (``obs.costs.cost_summary``)."""
        if self._cost_monitor is None:
            raise ValueError("engine built without obs= (or costs off)")
        from repro.obs import costs as costs_mod
        return costs_mod.cost_summary(self)

    def cost_alerts(self) -> Dict[int, Dict]:
        """{stream_id: {"position", "kind"}} of the cost channel's first
        alert per stream — ``kind`` is "residual" or "burn" (whichever
        fired first; streams that never alerted are absent)."""
        if self._cost_monitor is None:
            raise ValueError("engine built without obs= (or costs off)")
        mon = self._cost_monitor
        out: Dict[int, Dict] = {}
        for row in range(self.m):
            res_at = int(mon.first_alert_seen[row])
            burn_at = int(mon.first_burn_seen[row])
            if res_at < 0 and burn_at < 0:
                continue
            if burn_at < 0 or (0 <= res_at <= burn_at):
                out[self._sid_of_row[row]] = {"position": res_at,
                                              "kind": "residual"}
            else:
                out[self._sid_of_row[row]] = {"position": burn_at,
                                              "kind": "burn"}
        return out

    def _record_final_reads(self) -> None:
        # logmem buckets keep no survivor ids on device — their final
        # top-K read is issued by the storage layer from the admitted
        # set, so the meter cannot attribute it per tier here
        for bi, b in enumerate(self.buckets):
            if b.engine == "logmem":
                continue
            self.meter.record_reads(self._global_rows[bi],
                                    np.asarray(self._states[bi].ids)[:b.m])

    def finalize(self) -> Dict[int, np.ndarray]:
        """End-of-window: meter the final top-K read per stream (tiered by
        each stream's r) and return the survivors. Logmem streams meter
        no reads (no ids on device) and return empty survivor sets."""
        if self._tracer is not None:
            with self._tracer.span("finalize"):
                self._record_final_reads()
                return self.survivors()
        self._record_final_reads()
        return self.survivors()

    def finalize_tiers(self, use_pallas: bool = True) -> Dict[int, Dict]:
        """Device-side finalize-time tier assignment: one 2-D
        ``kernels.tier_assign`` pass per bucket maps every survivor id
        against its stream's boundary vector (and cascade floor) to the
        tier its final read must hit, plus the per-tier survivor counts —
        the bucketed gather for issuing per-tier reads. Bit-matches the
        host meter's tier attribution (asserted in tests).

        Returns {stream_id: {"ids", "tiers", "counts"}}. Logmem streams
        are absent (no survivor ids to assign).
        """
        from repro.kernels import tier_assign as ta
        out: Dict[int, Dict] = {}
        for bi, b in enumerate(self.buckets):
            if b.engine == "logmem":
                continue
            rows = self._global_rows[bi]
            tier, counts = ta.tier_assign(
                self._states[bi].ids[:b.m], self.meter.boundaries[rows],
                self.meter.floor[rows], n_tiers=self.meter.n_tiers,
                use_pallas=use_pallas)
            tier = np.asarray(tier)
            counts = np.asarray(counts)
            ids = np.asarray(self._states[bi].ids)
            for j, sid in enumerate(b.stream_ids):
                out[sid] = {"ids": ids[j], "tiers": tier[j],
                            "counts": counts[j]}
        return out

    def check_constraints(self, constraints=None, latencies=None,
                          doc_gb=None) -> Dict:
        """Reconciliation-time violation report against the engine's (or
        an explicit) ``ConstraintSet``: metered occupancy high-water marks
        vs capacities, realized read latency vs the SLO (see
        ``FleetMeter.check_constraints``). Streams planned from cost
        models are checked against the ``effective_capacity`` merge, so
        topology-declared ``TierSpec.capacity_docs`` are enforced at
        reconciliation exactly as at planning time.

        The report's ``"violations"`` key is the structured per-stream
        list ({stream_id, row, tier, kind, measured, limit, margin});
        with ``obs=`` configured every entry is also emitted on the obs
        event log as a ``constraint_violation`` event."""
        from repro.core.constraints import effective_capacity
        cset = constraints if constraints is not None else self.constraints
        if cset is None:
            raise ValueError("no ConstraintSet given or configured")
        per_stream_caps = None
        if self._model_of_row:
            nt_meter = self.meter.n_tiers
            has_bytes = any(c.max_bytes is not None for c in cset.capacities)
            per_stream_caps = np.empty((self.m, nt_meter))
            sizes = (np.broadcast_to(np.asarray(doc_gb, np.float64),
                                     (self.m,))
                     if doc_gb is not None else None)
            for row in range(self.m):
                cm = self._model_of_row.get(row)
                if cm is not None:
                    nt = (cm.as_ntier()
                          if isinstance(cm, TwoTierCostModel) else cm)
                    cap = np.full(nt_meter, np.inf)
                    cap[:min(nt.t, nt_meter)] = \
                        effective_capacity(cset, nt)[:nt_meter]
                else:
                    if has_bytes and sizes is None:
                        raise ValueError(
                            "byte-denominated capacities need doc_gb for "
                            "streams without a cost model")
                    g = float(sizes[row]) if sizes is not None else 0.0
                    cap = cset.capacity_array(nt_meter, g)
                per_stream_caps[row] = cap
        report = self.meter.check_constraints(cset, latencies=latencies,
                                              doc_gb=doc_gb,
                                              per_stream_caps=per_stream_caps)
        for v in report["violations"]:
            if v["row"] is not None:
                v["stream_id"] = self._sid_of_row[v["row"]]
            if self._tracer is not None:
                self._tracer.emit("constraint_violation", **v)
        return report
