"""Property tests for the jit streaming reservoir (core/topk.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (requirements-dev)")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import topk

import merge_reference


def oracle_topk(scores: np.ndarray, k: int):
    """Exact top-k with earlier-index tie-break."""
    order = np.lexsort((np.arange(len(scores)), -scores))
    return set(order[:k].tolist())


def run_stream(scores: np.ndarray, k: int, batch: int):
    state = topk.init(k)
    upd = jax.jit(topk.update)
    wrote = np.zeros(len(scores), dtype=bool)
    for off in range(0, len(scores), batch):
        sl = slice(off, min(off + batch, len(scores)))
        ids = jnp.arange(sl.start, sl.stop, dtype=jnp.int32)
        state, w = upd(state, jnp.asarray(scores[sl], jnp.float32), ids)
        wrote[sl] = np.asarray(w)
    return state, wrote


@given(
    st.lists(st.floats(min_value=-1e6, max_value=1e6, allow_nan=False,
                       allow_subnormal=False,  # XLA CPU flushes subnormals
                       width=32), min_size=3, max_size=120, unique=True),
    st.integers(min_value=1, max_value=8),
    st.integers(min_value=1, max_value=17),
)
@settings(max_examples=40, deadline=None)
def test_reservoir_equals_oracle(scores, k, batch):
    scores = np.asarray(scores, dtype=np.float32)
    if k >= len(scores):
        k = len(scores) - 1
    state, wrote = run_stream(scores, k, batch)
    got = set(int(i) for i in np.asarray(state.ids) if i >= 0)
    assert got == oracle_topk(scores, k)
    # every final member must have triggered a write when it arrived
    for i in got:
        assert wrote[i]
    assert int(state.seen) == len(scores)
    # state scores sorted descending
    s = np.asarray(state.scores)
    assert np.all(np.diff(s[~np.isinf(s)]) <= 0)


@given(st.integers(min_value=2, max_value=64), st.integers(min_value=0, max_value=2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_write_mask_matches_per_element_rule(n, seed):
    """wrote[i] ⟺ doc i ranks in top-k of docs 0..i — with batch=1 this is
    the paper's eq. 9/10 event exactly."""
    rng = np.random.default_rng(seed)
    scores = rng.permutation(n).astype(np.float32)
    k = max(1, n // 4)
    _, wrote = run_stream(scores, k, batch=1)
    for i in range(n):
        rank = int(np.sum(scores[: i + 1] > scores[i]))
        assert wrote[i] == (rank < k)


def test_merge_equals_single_stream():
    rng = np.random.default_rng(0)
    scores = rng.standard_normal(200).astype(np.float32)
    k = 16
    full, _ = run_stream(scores, k, batch=10)
    # split across two "shards"
    a = topk.init(k)
    b = topk.init(k)
    upd = jax.jit(topk.update)
    a, _ = upd(a, jnp.asarray(scores[:100]), jnp.arange(0, 100, dtype=jnp.int32))
    b, _ = upd(b, jnp.asarray(scores[100:]), jnp.arange(100, 200, dtype=jnp.int32))
    merged = topk.merge(a, b)
    np.testing.assert_array_equal(np.sort(np.asarray(merged.ids)),
                                  np.sort(np.asarray(full.ids)))
    assert int(merged.seen) == 200


def test_tie_break_prefers_earlier_doc():
    state = topk.init(2)
    s = jnp.array([1.0, 1.0, 1.0], jnp.float32)
    state, wrote = topk.update(state, s, jnp.array([0, 1, 2], jnp.int32))
    assert set(np.asarray(state.ids).tolist()) == {0, 1}
    assert list(np.asarray(wrote)) == [True, True, False]


@pytest.mark.parametrize("batch", [1, 32])
def test_expected_writes_statistics_match_analytic(batch):
    """Monte-Carlo over random permutations ≈ the analytic write law:
    eq. 11/12 for batch=1, the batched generalization otherwise."""
    from repro.core import shp
    rng = np.random.default_rng(42)
    n, k, trials = 400, 8, 200
    totals = []
    for _ in range(trials):
        scores = rng.permutation(n).astype(np.float32)
        _, wrote = run_stream(scores, k, batch=batch)
        totals.append(wrote.sum())
    analytic = float(shp.expected_cum_writes_batched(n - 1, k, batch))
    if batch == 1:
        assert abs(analytic - float(shp.expected_cum_writes(n - 1, k))) < 1e-9
    mc = np.mean(totals)
    se = np.std(totals) / np.sqrt(trials)
    assert abs(mc - analytic) < 4 * se + 0.5, (mc, analytic, se)


def test_tier_of_threshold():
    ids = jnp.array([0, 5, 10, 99], jnp.int32)
    t = topk.tier_of(ids, r=10)
    assert list(np.asarray(t)) == [0, 0, 1, 1]


EVICTION_CASES = ("unfull", "full", "reobserved", "padded", "tied",
                  "neginf", "permuted")


def _eviction_case(case, m=6, k=8, w=12, seed=7):
    """A stacked fleet of ``m`` reservoirs after one warm-up batch, and
    the next batch, shaped to exercise ``case``."""
    rng = np.random.default_rng(seed)
    upd = jax.vmap(topk.update)
    fill = 3 if case == "unfull" else 2 * k
    if case == "unfull":
        w = k - fill - 1  # the batch fits: no row fills up
    draw = ((lambda n: rng.integers(0, 3, (m, n)).astype(np.float32))
            if case in ("tied", "permuted") else
            (lambda n: rng.standard_normal((m, n)).astype(np.float32)))
    state = jax.vmap(lambda _: topk.init(k))(jnp.arange(m))
    ids = np.tile(np.arange(fill + w, dtype=np.int32), (m, 1))
    if case == "permuted":
        # ids in no order: the batch brings ids below the residents'
        ids = rng.permuted(ids, axis=1)
    ids0, ids = ids[:, :fill], ids[:, fill:]
    state, _ = upd(state, jnp.asarray(draw(fill)), jnp.asarray(ids0))
    scores = draw(w)
    if case == "neginf":
        scores[:, ::3] = -np.inf  # valid ids at -inf never enter
    if case == "reobserved":
        # every row re-sends two of its residents, one at a top score
        res = np.asarray(state.ids)
        ids[:, :2] = res[:, :2]
        scores[:, 0] = 100.0
    if case == "padded":
        ids[:, w // 2:] = -1
        scores[:, w // 2:] = -np.inf
        ids[0], scores[0] = -1, -np.inf  # an all-pad row
    return state, jnp.asarray(scores), jnp.asarray(ids)


@pytest.mark.parametrize("case", EVICTION_CASES)
def test_dropped_equals_evicted(case):
    old, scores, ids = _eviction_case(case)
    new, _ = jax.vmap(topk.update)(old, scores, ids)
    want = np.asarray(jax.vmap(merge_reference.evicted)(old, new))
    np.testing.assert_array_equal(np.asarray(topk.dropped(old, new)), want)
    np.testing.assert_array_equal(
        np.asarray(jax.vmap(topk.dropped)(old, new)), want)
    if case == "unfull":
        assert not want.any()
    if case in ("full", "tied", "reobserved", "neginf", "permuted"):
        assert want.any()


@pytest.mark.parametrize("case", EVICTION_CASES)
def test_update_without_resident_check_on_cleared_batch(case):
    """``check_resident=False`` on a batch already cleared of residents
    is bit-equal to the checked update of the raw batch."""
    old, scores, ids = _eviction_case(case)
    new, wrote = jax.vmap(topk.update)(old, scores, ids)
    resident = jax.vmap(topk.member)(ids, old.ids)
    cleared = (jnp.where(resident, -jnp.inf, scores),
               jnp.where(resident, -1, ids))
    new2, wrote2 = jax.vmap(
        lambda s, sc, i: topk.update(s, sc, i, check_resident=False))(
            old, *cleared)
    for a, b in zip(new, new2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(wrote), np.asarray(wrote2))


@pytest.mark.parametrize("n,h,natural", [
    (16, 64, "compare"), (1024, 1024, "compare"), (64, 131072, "compare"),
    (16384, 16384, "compare"), (16400, 16400, "sort")])
def test_member_methods_agree_with_isin(n, h, natural, monkeypatch):
    """Both methods equal ``np.isin`` on both sides of the threshold,
    with -1 pads and repeats among needles and haystack."""
    assert topk.member_method(n, h) == natural
    rng = np.random.default_rng(n + h)
    hay = rng.choice(4 * h, h, replace=False).astype(np.int32)
    hay[-(h // 8):] = -1
    hay[:3] = hay[3]
    needles = rng.integers(-1, 4 * h, n).astype(np.int32)
    needles[: n // 4] = rng.choice(hay, n // 4)
    needles[-3:] = -1
    want = np.isin(needles, hay)
    per_value = n * h // (n + h)
    for method, cap in (("compare", per_value + 1), ("sort", per_value - 1)):
        monkeypatch.setattr(topk, "COMPARE_PAIRS_PER_VALUE", cap)
        assert topk.member_method(n, h) == method
        # a fresh jit per method: the method is read while tracing
        got = jax.jit(lambda a, b: topk.member(a, b))(jnp.asarray(needles),
                                                      jnp.asarray(hay))
        np.testing.assert_array_equal(np.asarray(got), want, err_msg=method)
