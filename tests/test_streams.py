"""repro.streams — batched fleet engine vs M independent single-stream
replays, the 2-D batched_topk kernel vs its oracle, the vectorized planner
vs per-stream plan_placement, plus reservoir regression/algebra coverage
that must run without hypothesis installed."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import costs, placement, shp, simulator, topk
from repro.kernels.batched_topk import ops as btk_ops
from repro.kernels.batched_topk import ref as btk_ref
from repro.streams import StreamEngine, StreamSpec, engine, planner, router

import merge_reference


# ---------------------------------------------------------------------------
# core.topk regressions (satellites: wrote-mask collision, merge algebra)
# ---------------------------------------------------------------------------

def test_update_id_collision_with_resident_does_not_report_write():
    state = topk.init(3)
    state, wrote = topk.update(state, jnp.array([5.0, 4.0, 3.0]),
                               jnp.array([0, 1, 2], jnp.int32))
    assert list(np.asarray(wrote)) == [True, True, True]
    # id 1 is resident; a colliding batch id must not report a write even
    # though id 1 remains in the reservoir (the old isin-based mask did)
    state2, wrote2 = topk.update(state, jnp.array([1.0, 10.0]),
                                 jnp.array([1, 7], jnp.int32))
    assert list(np.asarray(wrote2)) == [False, True]
    ids = sorted(np.asarray(state2.ids).tolist())
    assert ids == [0, 1, 7]  # no duplicate id 1


def test_update_id_collision_never_duplicates_slot():
    state = topk.init(4)
    state, _ = topk.update(state, jnp.array([2.0, 1.0]),
                           jnp.array([10, 11], jnp.int32))
    # re-observe id 10 with a huge score while the reservoir is unfull:
    # first observation wins, no duplicate, no write
    state, wrote = topk.update(state, jnp.array([99.0]),
                               jnp.array([10], jnp.int32))
    assert not bool(wrote[0])
    ids = np.asarray(state.ids)
    assert np.sum(ids == 10) == 1
    assert float(state.scores[ids.tolist().index(10)]) == 2.0


def test_signed_zero_scores_tie_by_id():
    """-0.0 and +0.0 are one score to the merge's sort and to the rank
    compares alike: the lower id wins, and the write mask and evictions
    read by rank agree with the merge."""
    state = topk.init(2)
    state, _ = topk.update(state, jnp.array([1.0, 0.0]),
                           jnp.array([5, 6], jnp.int32))
    new, wrote = topk.update(state, jnp.array([-0.0]),
                             jnp.array([2], jnp.int32))
    assert np.asarray(new.ids).tolist() == [5, 2]
    assert np.asarray(new.scores).tolist() == [1.0, 0.0]
    assert np.asarray(wrote).tolist() == [True]
    assert (np.asarray(topk.dropped(state, new)).tolist()
            == np.asarray(merge_reference.evicted(state, new)).tolist()
            == [False, True])


def _random_state(rng, k, lo, hi):
    n = hi - lo
    state = topk.init(k)
    state, _ = topk.update(
        state, jnp.asarray(rng.standard_normal(n), jnp.float32),
        jnp.arange(lo, hi, dtype=jnp.int32))
    return state


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_merge_commutative_and_associative(seed):
    rng = np.random.default_rng(seed)
    k = 8
    a = _random_state(rng, k, 0, 40)
    b = _random_state(rng, k, 40, 60)
    c = _random_state(rng, k, 60, 110)
    ab = topk.merge(a, b)
    ba = topk.merge(b, a)
    np.testing.assert_array_equal(np.asarray(ab.ids), np.asarray(ba.ids))
    np.testing.assert_array_equal(np.asarray(ab.scores), np.asarray(ba.scores))
    left = topk.merge(topk.merge(a, b), c)
    right = topk.merge(a, topk.merge(b, c))
    np.testing.assert_array_equal(np.asarray(left.ids), np.asarray(right.ids))
    np.testing.assert_array_equal(np.asarray(left.scores),
                                  np.asarray(right.scores))
    assert int(left.seen) == int(right.seen) == 110


# ---------------------------------------------------------------------------
# batched_topk kernel vs oracle (interpret mode off-TPU)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,bn", [(1, 128, 128), (3, 500, 128),
                                    (8, 1024, 512), (16, 4096, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_batched_topk_matches_ref(m, n, bn, dtype):
    rng = np.random.default_rng(m * 10_000 + n)
    scores = jnp.asarray(rng.standard_normal((m, n)), dtype)
    thr = jnp.asarray(rng.uniform(-1, 1, m), jnp.float32)
    thr = thr.at[0].set(-jnp.inf)  # unfull-reservoir bar
    mask_k, counts_k, tmax_k = btk_ops.batched_topk_filter(
        scores, thr, block_n=bn)
    bn_eff = min(bn, max(n, 128))
    pad = (-n) % bn_eff
    sp = jnp.pad(scores.astype(jnp.float32), ((0, 0), (0, pad)),
                 constant_values=btk_ops.NEG_BIG)
    mask_r, counts_r, tmax_r = btk_ref.batched_topk_filter(sp, thr, bn_eff)
    np.testing.assert_array_equal(np.asarray(mask_k),
                                  np.asarray(mask_r[:, :n]))
    np.testing.assert_array_equal(np.asarray(counts_k), np.asarray(counts_r))
    np.testing.assert_allclose(np.asarray(tmax_k), np.asarray(tmax_r))


def test_batched_topk_per_stream_bars_differ():
    scores = jnp.tile(jnp.arange(8, dtype=jnp.float32), (3, 1))
    thr = jnp.asarray([-jnp.inf, 3.5, 100.0], jnp.float32)
    mask, counts, _ = btk_ops.batched_topk_filter(scores, thr, block_n=128)
    assert int(mask[0].sum()) == 8
    assert int(mask[1].sum()) == 4
    assert int(mask[2].sum()) == 0


# ---------------------------------------------------------------------------
# batched engine
# ---------------------------------------------------------------------------

def test_batched_update_equals_independent_single_streams():
    rng = np.random.default_rng(3)
    m, k, w = 8, 8, 16
    bstate = engine.init(m, k)
    singles = [topk.init(k) for _ in range(m)]
    for step in range(5):
        sc = rng.standard_normal((m, w)).astype(np.float32)
        ids = np.tile(np.arange(step * w, (step + 1) * w, dtype=np.int32),
                      (m, 1))
        bstate, bwrote = engine.update(bstate, jnp.asarray(sc),
                                       jnp.asarray(ids))
        for i in range(m):
            singles[i], swrote = topk.update(singles[i],
                                             jnp.asarray(sc[i]),
                                             jnp.asarray(ids[i]))
            np.testing.assert_array_equal(np.asarray(bwrote[i]),
                                          np.asarray(swrote))
            np.testing.assert_array_equal(np.asarray(bstate.ids[i]),
                                          np.asarray(singles[i].ids))
            np.testing.assert_array_equal(np.asarray(bstate.scores[i]),
                                          np.asarray(singles[i].scores))


@pytest.mark.parametrize("use_pallas", [True, False])
def test_filtered_update_drops_resident_reobservation(use_pallas):
    """A re-observed resident id above the bar must not occupy a survivor
    slot that a fresh candidate (admitted by plain update) should get."""
    st_plain = engine.init(1, 4)
    st_filt = engine.init(1, 4)
    sc0 = jnp.array([[4.0, 3.0, 2.0, 1.0]], jnp.float32)
    ids0 = jnp.array([[0, 1, 2, 3]], jnp.int32)
    st_plain, _ = engine.update(st_plain, sc0, ids0)
    st_filt, _ = engine.filtered_update(st_filt, sc0, ids0, block_n=128,
                                        use_pallas=use_pallas)
    sc1 = jnp.array([[100.0, 9.0, 8.0, 7.0, 6.0]], jnp.float32)
    ids1 = jnp.array([[0, 10, 11, 12, 13]], jnp.int32)  # id 0 is resident
    st_plain, w_plain = engine.update(st_plain, sc1, ids1)
    st_filt, w_filt = engine.filtered_update(st_filt, sc1, ids1, block_n=128,
                                             use_pallas=use_pallas)
    np.testing.assert_array_equal(np.sort(np.asarray(st_plain.ids), 1),
                                  np.sort(np.asarray(st_filt.ids), 1))
    np.testing.assert_array_equal(np.asarray(w_plain), np.asarray(w_filt))


@pytest.mark.parametrize("order", ["increasing", "permuted"])
@pytest.mark.parametrize("use_pallas", [True, False])
def test_filtered_update_equals_plain_update(use_pallas, order):
    """Bit-equal states and write masks, also when a stream's ids arrive
    in no order and scores tie: the cut and the merge both break ties by
    id, and the scan keeps candidates tied with the bar."""
    rng = np.random.default_rng(4)
    m, k, w = 6, 16, 256
    ids_all = np.tile(np.arange(3 * w, dtype=np.int32), (m, 1))
    if order == "permuted":
        ids_all = rng.permuted(ids_all, axis=1)
    st_plain = engine.init(m, k)
    st_filt = engine.init(m, k)
    for step in range(3):
        sc = (rng.standard_normal((m, w)) if order == "increasing"
              else rng.integers(0, 8, (m, w)))
        sc = jnp.asarray(sc, jnp.float32)
        ids = jnp.asarray(ids_all[:, step * w:(step + 1) * w])
        st_plain, w_plain = engine.update(st_plain, sc, ids)
        st_filt, w_filt = engine.filtered_update(st_filt, sc, ids,
                                                 block_n=128,
                                                 use_pallas=use_pallas)
        np.testing.assert_array_equal(np.asarray(w_plain),
                                      np.asarray(w_filt))
        for a, b in zip(st_plain, st_filt):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


MERGE_CASES = ["unfull", "full", "reobserved", "padded", "tied", "neginf",
               "permuted"]


def _eviction_batch(case, rng, state, step, m, w):
    """One batch per stream for ``case`` (of three chunks): increasing
    fresh ids, plus residents re-sent at a top score, pads, tied scores,
    valid ids at -inf, two live documents a chunk (``unfull``), or tied
    scores with ids in no order (``permuted``)."""
    tied = case in ("tied", "permuted")
    scores = (rng.integers(0, 3, (m, w)) if tied
              else rng.standard_normal((m, w))).astype(np.float32)
    ids = np.tile(np.arange(step * w, (step + 1) * w, dtype=np.int32),
                  (m, 1))
    if case == "permuted":
        order = np.random.default_rng(m * w).permuted(
            np.tile(np.arange(3 * w, dtype=np.int32), (m, 1)), axis=1)
        ids = order[:, step * w:(step + 1) * w].copy()
    if case == "reobserved" and step:
        res = np.asarray(state.ids)
        ids[:, 0], scores[:, 0] = res[:, 0], 100.0
    if case == "padded":
        ids[:, w // 2:], scores[:, w // 2:] = -1, -np.inf
        ids[0], scores[0] = -1, -np.inf
    if case == "unfull":
        ids[:, 2:], scores[:, 2:] = -1, -np.inf
    if case == "neginf":
        scores[:, ::3] = -np.inf
    return jnp.asarray(scores), jnp.asarray(ids)


@pytest.mark.parametrize("path", ["update", "filtered_jnp",
                                  "filtered_pallas"])
@pytest.mark.parametrize("case", MERGE_CASES)
def test_step_evictions_by_rank_equal_id_search(path, case):
    """The step's evictions (``dropped_ids``, read off the merge's order)
    equal the id search ``merge_reference.evicted_ids`` after each update
    path."""
    rng = np.random.default_rng(11)
    m, k = 4, 8
    w = 2 if case == "unfull" else 16  # unfull: 3 chunks never fill K
    fns = {"update": engine.update,
           "filtered_jnp": lambda *a: engine.filtered_update(
               *a, block_n=128, use_pallas=False),
           "filtered_pallas": lambda *a: engine.filtered_update(
               *a, block_n=128, use_pallas=True)}
    st = engine.init(m, k)
    evicted = 0
    for step in range(3):
        sc, ids = _eviction_batch(case, rng, st, step, m, w)
        new, _ = fns[path](st, sc, ids)
        want = np.asarray(merge_reference.evicted_ids(st, new))
        np.testing.assert_array_equal(np.asarray(engine.dropped_ids(st, new)),
                                      want)
        evicted += int((want >= 0).sum())
        st = new
    assert (evicted == 0) == (case == "unfull")


def _single_update(st, sc, ids):
    new, wrote = jax.vmap(topk.update)(engine._as_single(st), sc, ids)
    return engine.BatchedReservoirState(new.scores, new.ids, new.seen), wrote


def _single_reference(st, sc, ids):
    new, wrote = jax.vmap(merge_reference.topk_update)(
        engine._as_single(st), sc, ids)
    return engine.BatchedReservoirState(new.scores, new.ids, new.seen), wrote


@pytest.mark.parametrize("w", [4, 8, 16])
@pytest.mark.parametrize("path", ["topk", "update", "filtered_jnp",
                                  "filtered_pallas"])
@pytest.mark.parametrize("case", MERGE_CASES)
def test_update_paths_equal_positional_reference(case, path, w):
    """Each update path, merging by sort keys and reading its write mask
    by rank, is bit-equal to the positional merge (``merge_reference``:
    lexsort, gathers, scatters) in survivors, scores, write masks and
    ``seen``, with W below, at and above K. The filtered paths also equal
    the positional filter, save where ids arrive in no order (there that
    form cuts ties by batch position)."""
    rng = np.random.default_rng(w)
    m, k = 4, 8
    fns = {"topk": _single_update, "update": engine.update,
           "filtered_jnp": lambda *a: engine.filtered_update(
               *a, block_n=128, use_pallas=False),
           "filtered_pallas": lambda *a: engine.filtered_update(
               *a, block_n=128, use_pallas=True)}
    refs = [_single_reference if path == "topk"
            else merge_reference.engine_update]
    if path.startswith("filtered") and case != "permuted":
        refs.append(lambda *a: merge_reference.filtered_update(
            *a, use_pallas=path == "filtered_pallas"))
    st = engine.init(m, k)
    for step in range(3):
        sc, ids = _eviction_batch(case, rng, st, step, m, w)
        new, wrote = fns[path](st, sc, ids)
        for ref in refs:
            want, want_wrote = ref(st, sc, ids)
            np.testing.assert_array_equal(np.asarray(wrote),
                                          np.asarray(want_wrote))
            for a, b in zip(new, want):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        st = new
    if case == "unfull":
        assert not (np.asarray(st.ids) >= 0).all(axis=1).any()


@pytest.mark.parametrize("path", ["update", "filtered_update", "merge"])
def test_exact_merge_lowers_without_gather_or_scatter(path):
    """At a compare-method shape the exact merge moves no entry through an
    index: its lowered program holds sorts, and no gather or scatter."""
    m, k, w = 8, 1024, 1024
    assert topk.member_method(w, k) == "compare"
    st = jax.eval_shape(lambda: engine.init(m, k))
    batch = (jax.ShapeDtypeStruct((m, w), jnp.float32),
             jax.ShapeDtypeStruct((m, w), jnp.int32))
    fn, args = {
        "update": (engine.update, (st, *batch)),
        "filtered_update": (lambda *a: engine.filtered_update(
            *a, use_pallas=False), (st, *batch)),
        "merge": (engine.merge, (st, st))}[path]
    text = jax.jit(fn).lower(*args).as_text()
    assert "stablehlo.sort" in text
    assert "stablehlo.gather" not in text
    assert "stablehlo.scatter" not in text


def test_engine_bit_matches_simulator_replays():
    """The acceptance property at test scale: heterogeneous fleet through
    shuffled mixed batches == M independent core.simulator replays."""
    rng = np.random.default_rng(5)
    m, docs, batch = 48, 96, 8
    ks = [2, 4, 8] * (m // 3)
    specs = [StreamSpec(stream_id=1000 + i, k=ks[i], r=float(docs / 3))
             for i in range(m)]
    eng = StreamEngine(specs)
    traces = np.stack([simulator.random_rank_trace(docs, rng)
                       for _ in range(m)]).astype(np.float32)
    sids = np.array([s.stream_id for s in specs])
    for t in range(0, docs, batch):
        mixed_sids = np.repeat(sids, batch)
        mixed_dids = np.tile(np.arange(t, t + batch), m)
        mixed_scores = traces[:, t:t + batch].reshape(-1)
        perm = rng.permutation(mixed_sids.size)
        eng.ingest(mixed_sids[perm], mixed_scores[perm], mixed_dids[perm])
    survivors = eng.finalize()
    for i, spec in enumerate(specs):
        sim = simulator.simulate(traces[i].astype(np.float64), spec.k,
                                 placement.Policy(r=float(docs / 3)))
        np.testing.assert_array_equal(survivors[spec.stream_id],
                                      sim.survivor_ids)


def test_engine_kernel_filter_matches_plain_on_tied_scores():
    """Quantized scores produce ties; shuffled ingest through the
    kernel-filtered engine must still match the exact path (both break
    ties by the lowest id)."""
    rng = np.random.default_rng(11)
    m, k, docs, batch = 3, 3, 24, 4
    specs_a = [StreamSpec(stream_id=i, k=k, r=float(docs)) for i in range(m)]
    specs_b = [StreamSpec(stream_id=i, k=k, r=float(docs)) for i in range(m)]
    plain = StreamEngine(specs_a)
    kern = StreamEngine(specs_b, use_kernel_filter=True)
    traces = rng.integers(0, 4, (m, docs)).astype(np.float32)  # heavy ties
    for t in range(0, docs, batch):
        sids = np.repeat(np.arange(m), batch)
        dids = np.tile(np.arange(t, t + batch), m)
        sc = traces[:, t:t + batch].reshape(-1)
        perm = rng.permutation(sids.size)
        plain.ingest(sids[perm], sc[perm], dids[perm])
        kern.ingest(sids[perm], sc[perm], dids[perm])
    sp, sk = plain.survivors(), kern.survivors()
    for i in range(m):
        np.testing.assert_array_equal(sp[i], sk[i])


def test_engine_batch1_write_counts_match_simulator():
    """With W=1 the batched engine's write mask is the paper's per-doc
    eq. 9/10 event — totals must equal the exact simulator replay."""
    rng = np.random.default_rng(6)
    m, docs = 12, 64
    specs = [StreamSpec(stream_id=i, k=4, r=float(docs)) for i in range(m)]
    eng = StreamEngine(specs)
    traces = np.stack([simulator.random_rank_trace(docs, rng)
                       for _ in range(m)]).astype(np.float32)
    for t in range(docs):
        eng.ingest(np.arange(m), traces[:, t], np.full(m, t))
    for i in range(m):
        sim = simulator.simulate(traces[i].astype(np.float64), 4,
                                 placement.all_tier_a(docs))
        row = eng.stream_row(i)
        assert eng.meter.writes[row].sum() == sim.cum_writes[-1]
        assert eng.meter.deletes[row].sum() == sim.evictions


def test_engine_metering_tiers_and_reads():
    docs = 8
    specs = [StreamSpec(stream_id=0, k=2, r=4.0)]
    eng = StreamEngine(specs)
    # per-doc ingest of ascending scores: every doc writes, each (after the
    # first two) evicting the then-weakest member
    for t in range(docs):
        eng.ingest([0], [float(t)], [t])
    eng.finalize()
    led = eng.meter.ledger(0)
    # docs 0..3 land in tier A (index < r=4), 4..7 in tier B
    assert led.writes.tolist() == [4, 4]
    # evicted docs are 0..5: four lived in tier A, two in tier B
    assert led.deletes.tolist() == [4, 2]
    # survivors are docs 6, 7 — both tier B
    assert led.reads.tolist() == [0, 2]
    assert led.writes.sum() - led.deletes.sum() == 2


def test_engine_migrating_stream_matches_simulator_accounting():
    """A stream planned with Algorithm C + migration: per-doc replay must
    agree with core.simulator on writes per tier, migrated count, and the
    final read coming entirely from tier B."""
    rng = np.random.default_rng(9)
    docs, k, r = 64, 4, 24.0
    trace = simulator.random_rank_trace(docs, rng).astype(np.float32)
    eng = StreamEngine([StreamSpec(stream_id=0, k=k, r=r, migrate=True)])
    for t in range(docs):
        eng.ingest([0], [trace[t]], [t])
    eng.finalize()
    sim = simulator.simulate(trace.astype(np.float64), k,
                             placement.Policy(r=r, migrate_at_r=True))
    led = eng.meter.ledger(0)
    assert led.writes.tolist() == sim.writes_per_tier.tolist()
    assert led.migrations == sim.migrated
    assert led.reads.tolist() == sim.reads_per_tier.tolist()
    assert led.reads.tolist()[0] == 0  # everything reads from B post-mig


def test_engine_single_batch_uses_batch_boundary_write_law():
    # the whole window in ONE batch ⇒ only the final top-K ever write
    # (shp.expected_cum_writes_batched with batch = N), and they write at
    # the placement of their own doc index
    eng = StreamEngine([StreamSpec(stream_id=0, k=2, r=4.0)])
    eng.ingest(np.zeros(8, np.int64), np.arange(8, dtype=np.float32),
               np.arange(8))
    led = eng.meter.ledger(0)
    assert led.writes.tolist() == [0, 2]  # docs 6, 7 → tier B
    assert led.deletes.tolist() == [0, 0]


def test_engine_rejects_bad_specs():
    with pytest.raises(ValueError):
        StreamEngine([])
    with pytest.raises(ValueError):
        StreamEngine([StreamSpec(stream_id=0, k=2), ])  # no r, no cost model
    with pytest.raises(ValueError):
        StreamEngine([StreamSpec(stream_id=0, k=2, r=1.0),
                      StreamSpec(stream_id=0, k=4, r=1.0)])


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------

def test_router_roundtrip_preserves_per_stream_order():
    rng = np.random.default_rng(7)
    buckets = router.bucket_streams({10: 2, 11: 4, 12: 2, 13: 4})
    assert [b.k for b in buckets] == [2, 4]
    rt = router.StreamRouter(buckets)
    sids = np.repeat([10, 11, 12, 13], 5)
    dids = np.tile(np.arange(5), 4)
    scores = rng.standard_normal(20).astype(np.float32)
    # rows come out in doc-id (stream) order, shuffled ingest or not
    perm = rng.permutation(20)
    for order in (np.arange(20), perm):
        routed = rt.route(sids[order], scores[order], dids[order])
        for bi, bucket in enumerate(buckets):
            dense_s, dense_i = routed[bi]
            assert dense_s.shape == (2, 8)  # 5 docs → pow2 pad to 8
            for row, sid in enumerate(bucket.stream_ids):
                sel = sids == sid
                np.testing.assert_array_equal(dense_i[row, :5], dids[sel])
                np.testing.assert_array_equal(dense_s[row, :5], scores[sel])
                assert np.all(dense_i[row, 5:] == router.PAD_ID)
                assert np.all(np.isneginf(dense_s[row, 5:]))


def test_router_rejects_unknown_stream():
    rt = router.StreamRouter(router.bucket_streams({1: 2}))
    with pytest.raises(KeyError):
        rt.route([1, 99], [0.0, 0.0], [0, 0])


def test_router_rejects_within_batch_duplicate_doc():
    # same (stream, doc) twice in one batch would occupy two reservoir
    # slots and double-count writes — must be rejected, not corrupted
    eng = StreamEngine([StreamSpec(stream_id=0, k=4, r=8.0)])
    with pytest.raises(ValueError, match="duplicate"):
        eng.ingest([0, 0, 0], [1.0, 1.0, 0.5], [5, 5, 6])
    # same doc id on different streams is fine
    rt = router.StreamRouter(router.bucket_streams({1: 2, 2: 2}))
    rt.route([1, 2], [0.0, 0.0], [5, 5])


def _route_reference(buckets, stream_ids, scores, doc_ids, pad_to=None):
    """Plain ``StreamRouter.route``: a dict lookup, then one lexsort by
    (row, doc id) per bucket and a 2-D scatter."""
    where = {sid: (bi, row) for bi, b in enumerate(buckets)
             for row, sid in enumerate(b.stream_ids)}
    bi = np.asarray([where[int(s)][0] for s in stream_ids], np.int64)
    row = np.asarray([where[int(s)][1] for s in stream_ids], np.int64)
    scores = np.asarray(scores, np.float32)
    doc_ids = np.asarray(doc_ids, np.int32)
    out = []
    for b_idx, bucket in enumerate(buckets):
        sel = np.flatnonzero(bi == b_idx)
        order = np.lexsort((doc_ids[sel], row[sel]))
        rows = row[sel][order]
        counts = np.bincount(rows, minlength=bucket.m)
        pos = np.arange(rows.size) - np.repeat(np.cumsum(counts) - counts,
                                               counts)
        w = 1
        while w < counts.max(initial=0):
            w *= 2
        w = max(w, pad_to or 0)
        s = np.full((bucket.m, w), router.PAD_SCORE, np.float32)
        i = np.full((bucket.m, w), router.PAD_ID, np.int32)
        s[rows, pos] = scores[sel][order]
        i[rows, pos] = doc_ids[sel][order]
        out.append((s, i))
    return out


def _fleet(m, sparse):
    """m streams over three K and both engines (six buckets where m allows);
    sparse ids are spread out and start negative, so the router
    binary-searches them instead of keeping a dense table."""
    sids = 1000 * np.arange(m) - 5 if sparse else np.arange(m) + 3
    ks = {int(s): (2, 4, 8)[j % 3] for j, s in enumerate(sids)}
    engines = {int(s): ("exact", "logmem")[j % 2] for j, s in enumerate(sids)}
    return sids, router.bucket_streams(ks, engines)


def _batch(sids, docs, order, seed=0):
    """``docs`` (stream, score, doc id) triples, each stream's doc ids
    ascending in batch order (its positions), then re-ordered."""
    rng = np.random.default_rng(seed)
    j = rng.integers(0, sids.size, docs)
    ranks = np.empty(docs, np.int64)
    grouped = np.argsort(j, kind="stable")
    counts = np.bincount(j, minlength=sids.size)
    ranks[grouped] = np.arange(docs) - np.repeat(np.cumsum(counts) - counts,
                                                 counts)
    stream = sids[j]
    scores = rng.standard_normal(docs).astype(np.float32)
    ids = (1_000_000 + 3 * ranks).astype(np.int32)
    if order == "shuffled":
        p = rng.permutation(docs)
        stream, scores, ids = stream[p], scores[p], ids[p]
    elif order == "one_row":
        # swap two documents of one stream: only its row is out of order
        a, b = np.flatnonzero(j == np.argmax(counts >= 2))[:2]
        scores[[a, b]], ids[[a, b]] = scores[[b, a]], ids[[b, a]]
    return stream, scores, ids


ROUTE_CASES = {
    # name: (streams, sparse ids, docs, order, pad_to)
    "in_order": (300, False, 1200, "in_order", None),
    "shuffled": (300, False, 1200, "shuffled", None),
    "sparse_in_order": (300, True, 1200, "in_order", None),
    "sparse_shuffled": (300, True, 1200, "shuffled", None),
    "pad_to": (300, False, 1200, "shuffled", 64),
    "empty_batch": (300, False, 0, "in_order", None),
    "single_stream": (1, False, 37, "shuffled", None),
    "one_row_out_of_order": (300, True, 1200, "one_row", None),
    "few_streams": (5, False, 40, "shuffled", None),
    "wide_fleet_in_order": ((1 << 16) + 40, False, 150_000, "in_order",
                            None),
    "wide_fleet_shuffled": ((1 << 16) + 40, False, 150_000, "shuffled",
                            None),
}


@pytest.mark.parametrize("case", list(ROUTE_CASES))
def test_router_matches_lexsort_reference(case):
    m, sparse, docs, order, pad_to = ROUTE_CASES[case]
    sids, buckets = _fleet(m, sparse)
    rt = router.StreamRouter(buckets)
    assert (rt._table is None) == sparse  # both lookup branches run
    stream, scores, ids = _batch(sids, docs, order)
    got = rt.route(stream, scores, ids, pad_to=pad_to)
    want = _route_reference(buckets, stream, scores, ids, pad_to)
    assert len(got) == len(want) == len(buckets)
    for (gs, gi), (ws, wi) in zip(got, want):
        assert (gs.dtype, gi.dtype) == (np.float32, np.int32)
        np.testing.assert_array_equal(gs, ws)
        np.testing.assert_array_equal(gi, wi)
    shuffled_rows = {"in_order": 0, "one_row": 1}.get(order)
    if shuffled_rows is not None:
        assert rt.route_resorted_rows == shuffled_rows
        assert rt.route_resorted == int(shuffled_rows > 0)
    elif docs > m:
        assert rt.route_resorted == 1 and rt.route_resorted_rows > 0


@pytest.mark.parametrize("sparse", [False, True])
@pytest.mark.parametrize("order", ["in_order", "shuffled"])
def test_router_rejects_duplicate_on_both_paths(sparse, order):
    sids, buckets = _fleet(8, sparse)
    rt = router.StreamRouter(buckets)
    stream, scores, ids = _batch(sids, 64, "in_order", seed=3)
    first = np.flatnonzero(stream == sids[5])[:2]
    ids[first[1]] = doc = ids[first[0]]  # stream sids[5] sees doc twice
    if order == "shuffled":
        p = np.random.default_rng(4).permutation(stream.size)
        stream, scores, ids = stream[p], scores[p], ids[p]
    counted = (rt.route_slots, rt.route_useful, rt.route_resorted,
               rt.route_resorted_rows)
    msg = (f"duplicate (stream, doc) in one batch: stream {sids[5]} doc "
           f"{doc} — a doc id may appear once per stream per ingest")
    with pytest.raises(ValueError) as err:
        rt.route(stream, scores, ids)
    assert str(err.value) == msg
    assert (rt.route_slots, rt.route_useful, rt.route_resorted,
            rt.route_resorted_rows) == counted  # nothing counted


@pytest.mark.parametrize("sparse", [False, True])
@pytest.mark.parametrize("bad", ["negative", "out_of_range", "unregistered"])
def test_router_rejects_unregistered_ids(sparse, bad):
    sids = (1000 * np.arange(6) + 7) if sparse else 2 * np.arange(6)
    rt = router.StreamRouter(router.bucket_streams(
        {int(s): 4 for s in sids}))
    assert (rt._table is None) == sparse
    bad_id = {"negative": -2, "out_of_range": int(sids.max()) * 10 + 1,
              "unregistered": int(sids[2]) + 1}[bad]
    with pytest.raises(KeyError, match=rf"unregistered stream ids: "
                                       rf"\[{bad_id}\]"):
        rt.route([int(sids[0]), bad_id, int(sids[1])], [0.0] * 3, [0] * 3)
    assert (rt.route_slots, rt.route_useful) == (0, 0)


def test_reconcile_ignores_idle_streams():
    eng = StreamEngine([StreamSpec(stream_id=0, k=2, r=8.0),
                        StreamSpec(stream_id=1, k=2, r=8.0)])
    eng.ingest([0, 0, 0], [3.0, 1.0, 2.0], [0, 1, 2])  # stream 1 idle
    rec = eng.meter.reconcile()
    assert rec["expected"][eng.stream_row(1)] == 0.0
    assert rec["rel_err"][eng.stream_row(1)] == 0.0


# ---------------------------------------------------------------------------
# planner vs per-stream shp.plan_placement (satellite coverage)
# ---------------------------------------------------------------------------

def _random_models(rng, count):
    models = []
    for _ in range(count):
        n = int(rng.integers(1_000, 1_000_000))
        k = int(rng.integers(1, max(2, n // 10)))
        tier_a = costs.TierCosts("a", *(float(x) for x in
                                        rng.uniform(1e-8, 1e-3, 3)))
        tier_b = costs.TierCosts("b", *(float(x) for x in
                                        rng.uniform(1e-8, 1e-3, 3)))
        wl = costs.WorkloadSpec(n_docs=n, k=k,
                                doc_gb=float(rng.uniform(0.1, 2.0)),
                                window_months=float(rng.uniform(0.1, 3.0)))
        models.append(costs.TwoTierCostModel(tier_a=tier_a, tier_b=tier_b,
                                             workload=wl))
    return models


def test_plan_fleet_agrees_with_per_stream_plan_placement():
    rng = np.random.default_rng(8)
    models = _random_models(rng, 200)
    plan = planner.plan_fleet(models)
    saw = set()
    for i, cm in enumerate(models):
        ref = shp.plan_placement(cm)
        assert ref.strategy == plan.strategy(i), i
        np.testing.assert_allclose(plan.best_total[i], ref.best.total,
                                   rtol=1e-9)
        np.testing.assert_allclose(plan.r[i], ref.r, rtol=1e-9, atol=1e-12)
        saw.add(ref.strategy)
    assert len(saw) >= 2  # the sweep actually exercises several strategies


def test_plan_fleet_case_studies_match_scalar_planner():
    models = [costs.case_study_1(), costs.case_study_2()]
    plan = planner.plan_fleet(models)
    for i, cm in enumerate(models):
        ref = shp.plan_placement(cm)
        assert plan.strategy(i) == ref.strategy
        np.testing.assert_allclose(plan.best_total[i], ref.best.total,
                                   rtol=1e-12)
        pol = plan.policy(i)
        ref_pol = placement.from_plan(ref)
        assert pol.migrate_at_r == ref_pol.migrate_at_r
        np.testing.assert_allclose(pol.r, ref_pol.r, rtol=1e-9)


def test_plan_fleet_validity_gate_matches_scalar():
    # cw_a > cw_b flips the second-order condition: two-tier must be gated
    tier_a = costs.TierCosts("a", 1e-3, 1e-5, 0.0)
    tier_b = costs.TierCosts("b", 1e-6, 1e-3, 0.0)
    wl = costs.WorkloadSpec(n_docs=10_000, k=10, doc_gb=1.0,
                            window_months=1.0)
    cm = costs.TwoTierCostModel(tier_a=tier_a, tier_b=tier_b, workload=wl)
    plan = planner.plan_fleet([cm])
    assert np.isinf(plan.totals[0, 2]) and np.isinf(plan.totals[0, 3])
    assert plan.strategy(0) == shp.plan_placement(cm).strategy
