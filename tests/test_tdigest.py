"""Batched t-digest kernel tests.

Mirrors the reference's statistical test strategy (tdigest/histo_test.go:11-128):
quantile error vs exact order statistics within epsilon, merge correctness,
plus batched-vs-scalar golden equivalence (SURVEY.md section 4 port note).
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from veneur_tpu.ops import tdigest as td
from veneur_tpu.samplers.scalar import ScalarTDigest

EPS = 0.02  # reference tolerance at its default test compression


_merge_jit = jax.jit(td.merge_samples)


def ingest_all(state, values, weights=None, chunk=64):
    """Feed a 1-D array of samples through merge_samples in chunks, like the
    temp-buffer drain in the reference."""
    values = np.asarray(values, np.float32)
    if weights is None:
        weights = np.ones_like(values)
    n = len(values)
    pad = (-n) % chunk
    values = np.pad(values, (0, pad))
    weights = np.pad(np.asarray(weights, np.float32), (0, pad))
    for i in range(0, n + pad, chunk):
        v = jnp.asarray(values[i:i + chunk])[None, :]
        w = jnp.asarray(weights[i:i + chunk])[None, :]
        state = _merge_jit(state, v, w)
    return state


class TestSingleDigest:
    def test_empty(self):
        state = td.init((1,))
        q = td.quantile(state, jnp.array([0.5]))
        assert np.isnan(np.asarray(q)).all()
        assert float(state.count()[0]) == 0.0

    def test_single_value(self):
        state = td.init((1,))
        state = td.merge_samples(state, jnp.array([[42.0]]), jnp.array([[1.0]]))
        qs = np.asarray(td.quantile(state, jnp.array([0.0, 0.5, 1.0])))[0]
        np.testing.assert_allclose(qs, [42.0, 42.0, 42.0], atol=1e-5)
        assert float(state.min[0]) == 42.0
        assert float(state.max[0]) == 42.0

    def test_uniform_quantiles(self):
        rng = np.random.RandomState(5)
        samples = rng.uniform(100, 200, size=20000).astype(np.float32)
        state = ingest_all(td.init((1,)), samples)
        assert abs(float(state.count()[0]) - 20000) < 1e-3 * 20000
        probes = np.array([0.01, 0.1, 0.25, 0.5, 0.75, 0.9, 0.99], np.float32)
        got = np.asarray(td.quantile(state, jnp.asarray(probes)))[0]
        want = np.quantile(samples, probes)
        # compare in rank space: |CDF(got) - p| <= EPS
        srt = np.sort(samples)
        ranks = np.searchsorted(srt, got) / len(srt)
        np.testing.assert_allclose(ranks, probes, atol=EPS)
        # and values should be in the right ballpark on a uniform distribution
        np.testing.assert_allclose(got, want, rtol=0.05)

    def test_normal_quantiles_rank_error(self):
        rng = np.random.RandomState(7)
        samples = rng.normal(50, 10, size=50000).astype(np.float32)
        state = ingest_all(td.init((1,)), samples)
        probes = np.array([0.05, 0.25, 0.5, 0.75, 0.95], np.float32)
        got = np.asarray(td.quantile(state, jnp.asarray(probes)))[0]
        srt = np.sort(samples)
        ranks = np.searchsorted(srt, got) / len(srt)
        np.testing.assert_allclose(ranks, probes, atol=EPS)

    def test_cdf_uniform(self):
        rng = np.random.RandomState(11)
        samples = rng.uniform(0, 1, size=20000).astype(np.float32)
        state = ingest_all(td.init((1,)), samples)
        xs = np.array([0.1, 0.3, 0.5, 0.7, 0.9], np.float32)
        got = np.asarray(td.cdf(state, jnp.asarray(xs)))[0]
        np.testing.assert_allclose(got, xs, atol=EPS)
        # boundary semantics (merging_digest.go:267-272)
        lo_hi = np.asarray(td.cdf(state, jnp.asarray([-1.0, 2.0], np.float32)))[0]
        assert lo_hi[0] == 0.0 and lo_hi[1] == 1.0

    def test_weighted_samples(self):
        # weight w at value v must behave like w copies of v
        state = td.init((1,))
        v = jnp.array([[10.0, 20.0, 30.0, 0.0]])
        w = jnp.array([[1.0, 2.0, 1.0, 0.0]])  # padding slot ignored
        state = td.merge_samples(state, v, w)
        assert float(state.count()[0]) == 4.0
        med = float(np.asarray(td.quantile(state, jnp.array([0.5])))[0, 0])
        assert 15.0 <= med <= 25.0

    def test_capacity_bound_holds(self):
        rng = np.random.RandomState(3)
        state = ingest_all(td.init((1,)), rng.exponential(size=30000))
        live = int(np.sum(np.asarray(state.weight)[0] > 0))
        assert live <= td.size_bound(100.0)
        # floor-k binning caps live clusters at compression+1
        assert live <= 101


class TestMerge:
    def test_merge_two_digests(self):
        rng = np.random.RandomState(13)
        a_samples = rng.uniform(0, 50, size=10000)
        b_samples = rng.uniform(50, 100, size=10000)
        a = ingest_all(td.init((1,)), a_samples)
        b = ingest_all(td.init((1,)), b_samples)
        merged = td.merge(a, b)
        allsamp = np.concatenate([a_samples, b_samples])
        probes = np.array([0.1, 0.5, 0.9], np.float32)
        got = np.asarray(td.quantile(merged, jnp.asarray(probes)))[0]
        srt = np.sort(allsamp)
        ranks = np.searchsorted(srt, got) / len(srt)
        np.testing.assert_allclose(ranks, probes, atol=EPS)
        assert abs(float(merged.count()[0]) - 20000) < 1
        assert float(merged.min[0]) == pytest.approx(allsamp.min(), rel=1e-6)
        assert float(merged.max[0]) == pytest.approx(allsamp.max(), rel=1e-6)

    def test_merge_empty_is_identity(self):
        rng = np.random.RandomState(17)
        a = ingest_all(td.init((1,)), rng.uniform(size=1000))
        e = td.init((1,))
        m = td.merge(a, e)
        probes = jnp.array([0.25, 0.5, 0.75])
        np.testing.assert_allclose(np.asarray(td.quantile(m, probes)),
                                   np.asarray(td.quantile(a, probes)), rtol=1e-3)

    def test_merge_associative_within_eps(self):
        rng = np.random.RandomState(19)
        parts = [rng.normal(size=5000) for _ in range(4)]
        digs = [ingest_all(td.init((1,)), p) for p in parts]
        left = td.merge(td.merge(digs[0], digs[1]), td.merge(digs[2], digs[3]))
        right = td.merge(td.merge(td.merge(digs[0], digs[1]), digs[2]), digs[3])
        probes = jnp.array([0.1, 0.5, 0.9])
        srt = np.sort(np.concatenate(parts))
        for m in (left, right):
            got = np.asarray(td.quantile(m, probes))[0]
            ranks = np.searchsorted(srt, got) / len(srt)
            np.testing.assert_allclose(ranks, np.asarray(probes), atol=EPS)


class TestBatched:
    def test_many_series_at_once(self):
        """The point of the project: S series in one XLA program."""
        S, N = 64, 2048
        rng = np.random.RandomState(23)
        offsets = rng.uniform(0, 1000, size=(S, 1)).astype(np.float32)
        samples = rng.uniform(0, 100, size=(S, N)).astype(np.float32) + offsets
        state = td.init((S,))
        T = 64
        assert N % T == 0
        for i in range(0, N, T):
            state = _merge_jit(state, jnp.asarray(samples[:, i:i + T]),
                               jnp.ones((S, T), jnp.float32))
        probes = np.array([0.1, 0.5, 0.9], np.float32)
        got = np.asarray(td.quantile(state, jnp.asarray(probes)))
        for s in range(S):
            srt = np.sort(samples[s])
            ranks = np.searchsorted(srt, got[s]) / N
            np.testing.assert_allclose(ranks, probes, atol=EPS)

    def test_batched_matches_scalar_reference(self):
        """Golden equivalence vs the greedy scalar port, in rank space."""
        rng = np.random.RandomState(29)
        samples = rng.gamma(2.0, 10.0, size=8000).astype(np.float32)
        batched = ingest_all(td.init((1,)), samples)
        scalar = ScalarTDigest(compression=100.0)
        for v in samples:
            scalar.add(float(v))
        srt = np.sort(samples)
        for p in [0.01, 0.25, 0.5, 0.75, 0.99]:
            qb = float(np.asarray(td.quantile(batched, jnp.array([p])))[0, 0])
            qs = scalar.quantile(p)
            rb = np.searchsorted(srt, qb) / len(srt)
            rs = np.searchsorted(srt, qs) / len(srt)
            assert abs(rb - p) <= EPS, f"batched rank err at p={p}"
            assert abs(rs - p) <= EPS, f"scalar rank err at p={p}"
            assert abs(rb - rs) <= 2 * EPS

    def test_determinism(self):
        rng = np.random.RandomState(31)
        samples = rng.uniform(size=(8, 512)).astype(np.float32)
        def run():
            s = td.init((8,))
            for i in range(0, 512, 64):
                s = _merge_jit(s, jnp.asarray(samples[:, i:i + 64]),
                               jnp.ones((8, 64), jnp.float32))
            return np.asarray(td.quantile(s, jnp.array([0.5, 0.9])))
        np.testing.assert_array_equal(run(), run())

    def test_jit_merge_samples(self):
        fn = jax.jit(td.merge_samples)
        state = td.init((4,))
        out = fn(state, jnp.ones((4, 8)), jnp.ones((4, 8)))
        assert out.mean.shape == state.mean.shape
        np.testing.assert_allclose(np.asarray(out.count()), 8.0)

    def test_from_centroids_roundtrip(self):
        rng = np.random.RandomState(37)
        samples = rng.uniform(0, 10, size=5000)
        a = ingest_all(td.init((1,)), samples)
        b = td.from_centroids(a.mean, a.weight, a.min, a.max)
        probes = jnp.array([0.1, 0.5, 0.9])
        np.testing.assert_allclose(np.asarray(td.quantile(b, probes)),
                                   np.asarray(td.quantile(a, probes)), rtol=5e-2)
        np.testing.assert_allclose(float(b.count()[0]), float(a.count()[0]), rtol=1e-5)


C = 100.0
K = td.size_bound(C)


def flush_state(rows, seed=0, samples=6000):
    """A group's planes at flush time: a digest from an earlier drain,
    fresh bins on top, imported extrema. Every row is live; a flush
    told ``n`` must leave rows past its last slab exactly like this."""
    rng = np.random.default_rng(seed)
    temp = td.init_temp(rows, K, C)
    digest = td.init((rows,), C, K)
    ones = jnp.ones(samples, jnp.float32)
    for drain in (True, False):
        r = jnp.asarray(rng.integers(0, rows, samples).astype(np.int32))
        v = jnp.asarray(rng.lognormal(0, 1, samples).astype(np.float32))
        temp = td.ingest_chunk(temp, r, v, ones, C)
        if drain:
            digest = td.drain_temp(digest, temp, C, use_pallas=False)
            temp = td.init_temp(rows, K, C)
    dmin = jnp.asarray(rng.normal(0, 1, rows).astype(np.float32))
    return digest, temp, dmin, dmin + 5.0


FLUSH_QS = jnp.asarray([0.5, 0.75, 0.99, 0.5], jnp.float32)


def _flush_both(state, n, **kw):
    """(bounded by ``n``, full width): each (mean, weight, min, max,
    pcts) as NumPy."""
    out = []
    for count in (np.int32(n), None):
        drained, pcts = jax.jit(
            lambda *a, c=count: td.drain_and_quantile(
                *a, FLUSH_QS, C, n=c, **kw))(*state)
        out.append([np.asarray(x) for x in tuple(drained) + (pcts,)])
    return out


def assert_bounded_matches_full(state, n, run, **kw):
    """Rows [:run] are the full-width program's bit for bit (so rows
    [:n] are), the rest are the input's (percentiles 0)."""
    got, full = _flush_both(state, n, **kw)
    assert run >= n
    for g, f in zip(got, full):
        np.testing.assert_array_equal(g[:run], f[:run])
    digest = state[0]
    for g, was in zip(got[:4], digest):
        np.testing.assert_array_equal(g[run:], np.asarray(was)[run:])
    assert not got[4][run:].any()


class TestLiveRowBound:
    """drain_and_quantile with a row count (the XLA rung): the flush's
    work follows the live rows, its answers do not change."""

    SLAB = 64

    @pytest.fixture(autouse=True)
    def small_slab(self, monkeypatch):
        from veneur_tpu.ops import tdigest_pallas as tp

        monkeypatch.setattr(tp, "_FLUSH_SLAB_ROWS", self.SLAB)

    @pytest.mark.parametrize("n", [1, 63, 64, 65, 2 * 64 + 5, 256])
    def test_rows_equal_full_width(self, n):
        state = flush_state(256)
        run = td.flush_rows_run(256, n)
        assert run == -(-n // 64) * 64
        assert_bounded_matches_full(state, n, run, use_pallas=False)

    @pytest.mark.parametrize("n", [1, 128, 129, 200])
    def test_capacity_no_multiple_of_the_slab(self, n):
        """200 rows end in a slab clamped back over rows 136-191: what
        the trip before drained there is not drained twice."""
        state = flush_state(200, seed=1)
        run = td.flush_rows_run(200, n)
        assert run == min(-(-n // 64) * 64, 200)
        assert_bounded_matches_full(state, n, run, use_pallas=False)

    def test_no_rows_runs_no_slab(self):
        state = flush_state(256, seed=2)
        assert td.flush_rows_run(256, 0) == 0
        assert_bounded_matches_full(state, 0, 0, use_pallas=False)

    @pytest.mark.parametrize("rows,looped", [(32, False), (64, False),
                                             (65, True), (256, True)])
    def test_one_slab_or_less_is_straight_line(self, rows, looped):
        shapes = jax.eval_shape(lambda: flush_state(rows, samples=8))
        jaxpr = jax.make_jaxpr(
            lambda *a: td.drain_and_quantile(
                *a, FLUSH_QS, C, use_pallas=False, n=np.int32(3)))(*shapes)
        assert ("while" in str(jaxpr)) is looped
        assert td.flush_rows_run(rows, 3) == (64 if looped else rows)

    def test_no_count_is_the_straight_line_program(self):
        shapes = jax.eval_shape(lambda: flush_state(256, samples=8))
        jaxpr = jax.make_jaxpr(
            lambda *a: td.drain_and_quantile(
                *a, FLUSH_QS, C, use_pallas=False))(*shapes)
        assert "while" not in str(jaxpr)

    def test_one_trace_for_every_count(self):
        traces = []

        def flush(*a):
            traces.append(1)
            return td.drain_and_quantile(*a[:4], FLUSH_QS, C,
                                         use_pallas=False, n=a[4])

        fn = jax.jit(flush)
        state = flush_state(256, seed=3)
        for n in (1, 70, 256):
            fn(*state, np.int32(n))
        assert len(traces) == 1


# ---------------------------------------------------------------------------
# The temp's flat planes against the plain [S, K] formulation
# ---------------------------------------------------------------------------


class PlainTemp:
    """The bin accumulation as it was written before the planes went
    flat, kept here as the reference: ``[S, K]`` / ``[S, A]`` planes,
    ``.at[r, b].add``, the sample path's two drains at whole width (the
    held rows up to ``ROW_DRAIN_MAX_ARRIVALS``, then the shift guard's
    every row)."""

    def __init__(self, rows):
        self.rows = rows
        self.sum_w = jnp.zeros((rows, K), jnp.float32)
        self.sum_wm = jnp.zeros((rows, K), jnp.float32)
        self.seg_w = jnp.zeros((rows, td.BELOW_MASS_ANCHORS), jnp.float32)
        self.seg_wm = jnp.zeros((rows, td.BELOW_MASS_ANCHORS), jnp.float32)
        self.digest = td.init((rows,), C, K)
        self.drained, self.guard_drains = 0, 0

    def _drain(self, which, use_pallas):
        """Rows ``which`` ([S] bool) compressed into the digest, their
        bins and anchors emptied."""
        mean, weight = td._merge_bins(
            self.digest.mean, self.digest.weight,
            jnp.where(which[:, None], self.sum_w, 0.0),
            jnp.where(which[:, None], self.sum_wm, 0.0), C, K, use_pallas)
        self.digest = self.digest._replace(
            mean=jnp.where(which[:, None], mean, self.digest.mean),
            weight=jnp.where(which[:, None], weight, self.digest.weight))
        keep = ~which[:, None]
        self.sum_w, self.sum_wm = self.sum_w * keep, self.sum_wm * keep
        self.seg_w, self.seg_wm = self.seg_w * keep, self.seg_wm * keep

    def ingest(self, rows, values, weights, guarded=False,
               use_pallas=False, count=None):
        """``guarded``: the sample path's two drains first. ``count``
        ([S], the interval's weight a row so far) is what the row drain
        reads beside the held mass."""
        if guarded:
            r = np.asarray(rows)
            w = np.asarray(weights)
            live = (r < self.rows) & (w > 0)
            held = np.asarray(self.seg_w.sum(axis=1)) > 0
            due = np.zeros(self.rows, bool)
            at = np.minimum(r, self.rows - 1)
            due[at[live & held[at] & (
                np.asarray(count)[at] <= td.ROW_DRAIN_MAX_ARRIVALS * w)]] \
                = True
            self.drained = int(due.sum())
            if due.any():
                self._drain(jnp.asarray(due), use_pallas)
            if bool(td.shift_pred(self.seg_w, self.seg_wm, rows, values,
                                  weights, self.rows)):
                self.guard_drains += 1
                self._drain(jnp.ones(self.rows, bool), use_pallas)
        r, v, w, b = td.bin_flat_samples(
            rows, values, weights, self.rows, K, C, acc_seg_w=self.seg_w,
            acc_seg_wm=self.seg_wm)
        vz = jnp.where(w > 0, v, 0.0)
        sg = td.seg_of_bins(b, K)
        self.sum_w = self.sum_w.at[r, b].add(w, mode="drop")
        self.sum_wm = self.sum_wm.at[r, b].add(w * vz, mode="drop")
        self.seg_w = self.seg_w.at[r, sg].add(w, mode="drop")
        self.seg_wm = self.seg_wm.at[r, sg].add(w * vz, mode="drop")


def assert_planes_equal(temp, plain):
    """Bit for bit: bins and anchors, through both of the temp's views."""
    for got, want in zip(temp.bins() + temp.anchors(),
                         (plain.sum_w, plain.sum_wm, plain.seg_w,
                          plain.seg_wm)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(temp.sum_w), np.asarray(plain.sum_w).reshape(-1))
    np.testing.assert_array_equal(
        np.asarray(temp.seg_w), np.asarray(plain.seg_w).T.reshape(-1))


def _flat_case(name):
    """(rows reserved, [(rows, values, weights) a chunk])."""
    rng = np.random.default_rng(5)

    def chunk(series, n, rows, shift=0.0):
        return (rng.integers(0, series, n).astype(np.int32),
                (shift + rng.lognormal(0, 1, n)).astype(np.float32),
                np.ones(n, np.float32))

    if name == "one_chunk":
        return 64, [chunk(64, 2048, 64)]
    if name == "sixteen_chunks_same_rows":
        return 64, [chunk(16, 1024, 64) for _ in range(16)]
    if name == "padding_rows_and_zero_weights":
        out = []
        for _ in range(3):
            r, v, w = chunk(32, 1024, 32)
            r[::3] = 32          # the padding sentinel: rows == S
            w[1::5] = 0.0
            out.append((r, v, w))
        return 32, out
    if name == "spread_over_every_row":
        # one sample a row over the whole capacity, twice
        return 4096, [(rng.permutation(4096).astype(np.int32),
                       rng.lognormal(0, 1, 4096).astype(np.float32),
                       np.ones(4096, np.float32)) for _ in range(2)]
    if name == "a_group_of_eight_rows":
        return 8, [chunk(8, 512, 8) for _ in range(4)]
    if name == "sampled_rows_past_and_under_the_arrivals":
        # rows 0-3 sent @0.01 (11 arrivals weigh 1,100), rows 4-7 @1.0
        out = []
        for _ in range(3):
            r, v, w = chunk(8, 88, 8)
            r[:] = np.arange(88) % 8
            w[r < 4] = 100.0
            out.append((r, v, w))
        return 8, out
    # 512 samples a row a chunk: the fourth chunk finds every row past
    # ROW_DRAIN_MAX_ARRIVALS, so the step is the shift guard's to catch
    assert name == "guard_drain_in_the_middle"
    return 16, ([chunk(16, 8192, 16) for _ in range(3)]
                + [chunk(16, 8192, 16, shift=1e4)]
                + [chunk(16, 8192, 16, shift=1e4) for _ in range(2)])


FLAT_CASES = ["one_chunk", "sixteen_chunks_same_rows",
              "padding_rows_and_zero_weights", "spread_over_every_row",
              "a_group_of_eight_rows",
              "sampled_rows_past_and_under_the_arrivals",
              "guard_drain_in_the_middle"]


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["xla_rung", "kernel_rung"])
@pytest.mark.parametrize("case", FLAT_CASES)
def test_flat_accumulation_equals_the_plain_planes(case, use_pallas):
    """``ingest_chunk_rowdrained`` on the flat planes leaves the bins,
    the anchors and the digest of the ``[S, K]`` formulation, bit for
    bit (the scatter keeps its order of additions in both), drains the
    rows it drains, and leaves the step change to the shift guard once
    the rows are past ``ROW_DRAIN_MAX_ARRIVALS``."""
    rows, chunks = _flat_case(case)
    plain = PlainTemp(rows)
    temp, digest = td.init_temp(rows, K, C), td.init((rows,), C, K)
    step = jax.jit(lambda d, t, *c: td.ingest_chunk_rowdrained(
        d, t, *c, C, use_pallas=use_pallas))
    for c in chunks:
        c = tuple(jnp.asarray(x) for x in c)
        plain.ingest(*c, guarded=True, use_pallas=use_pallas,
                     count=temp.count)
        digest, temp, drained = step(digest, temp, *c)
        assert int(drained) == plain.drained
        assert_planes_equal(temp, plain)
    np.testing.assert_array_equal(np.asarray(digest.weight),
                                  np.asarray(plain.digest.weight))
    np.testing.assert_array_equal(np.asarray(digest.mean),
                                  np.asarray(plain.digest.mean))
    assert bool(np.asarray(digest.weight).any()) is (case != "one_chunk")
    assert plain.guard_drains == (case == "guard_drain_in_the_middle")
    if case == "sampled_rows_past_and_under_the_arrivals":
        # 11 arrivals @0.01 weigh 1,100: counted as arrivals the row is
        # drained at every later chunk like its neighbours @1.0
        assert plain.drained == 8


class TestFlatPlanes:
    def test_shape_contract(self):
        temp = td.init_temp(24, K, C)
        assert (temp.num_series, temp.capacity) == (24, K)
        assert temp.sum_w.shape == temp.sum_wm.shape == (24 * K,)
        assert temp.seg_w.shape == (td.BELOW_MASS_ANCHORS * 24,)
        assert [x.shape for x in temp.bins()] == [(24, K)] * 2
        assert [x.shape for x in temp.anchors()] == [
            (24, td.BELOW_MASS_ANCHORS)] * 2

    def test_windows_of_a_bin_plane(self):
        plane = jnp.arange(10 * K, dtype=jnp.float32)
        whole = np.asarray(plane).reshape(10, K)
        np.testing.assert_array_equal(
            np.asarray(jax.jit(lambda s: td.bin_rows(plane, s, 4, K))(3)),
            whole[3:7])
        at = jnp.asarray([9, 0, 4, 4], jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(td.gather_bin_rows(plane, at, K)), whole[[9, 0, 4, 4]])
        # the first two of three rows moved out; the third stays
        rows = jnp.asarray([2, 7, 5], jnp.int32)
        left, left2, w, wm = jax.jit(
            lambda n: td.take_bin_rows(plane, 2 * plane, rows, n, K))(2)
        np.testing.assert_array_equal(np.asarray(w)[:2], whole[[2, 7]])
        np.testing.assert_array_equal(np.asarray(wm)[:2], 2 * whole[[2, 7]])
        assert not np.asarray(w)[2].any() and not np.asarray(wm)[2].any()
        left, left2 = (np.asarray(x).reshape(10, K) for x in (left, left2))
        assert not left[[2, 7]].any() and not left2[[2, 7]].any()
        keep = [0, 1, 3, 4, 5, 6, 8, 9]
        np.testing.assert_array_equal(left[keep], whole[keep])
        np.testing.assert_array_equal(left2[keep], 2 * whole[keep])

    @pytest.mark.parametrize("pad", [8, 24])
    def test_grown_temp_keeps_every_row(self, pad):
        rng = np.random.default_rng(9)
        c = (jnp.asarray(rng.integers(0, 8, 512).astype(np.int32)),
             jnp.asarray(rng.lognormal(0, 1, 512).astype(np.float32)),
             jnp.ones(512, jnp.float32))
        small = td.ingest_chunk(td.init_temp(8, K, C), *c, C)
        grown = td.grow_temp(small, pad)
        assert grown.num_series == 8 + pad and grown.capacity == K
        for g, s in zip(grown.bins() + grown.anchors(),
                        small.bins() + small.anchors()):
            np.testing.assert_array_equal(np.asarray(g)[:8], np.asarray(s))
            assert not np.asarray(g)[8:].any()
        np.testing.assert_array_equal(np.asarray(grown.vmin)[8:], np.inf)
        # and bins on as one that was made at that size
        big = td.ingest_chunk(td.init_temp(8 + pad, K, C), *c, C)
        again = td.ingest_chunk(grown, *c, C)
        twice = td.ingest_chunk(big, *c, C)
        for g, w in zip(again, twice):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# -- sparse rows: a row's few samples an interval, a handful a chunk ----------

SPARSE_SERIES = 256
SPARSE_CHUNK = 8192
SPARSE_QS = (0.5, 0.75, 0.99)
SAMPLE_INGEST = jax.jit(
    lambda d, t, r, v, w: td.ingest_chunk_rowdrained(
        d, t, r, v, w, 100.0, False)[:2])
_sparse_flush = jax.jit(lambda d, t, qs: td.drain_and_quantile(
    d, t, jnp.full((SPARSE_SERIES,), jnp.inf),
    jnp.full((SPARSE_SERIES,), -jnp.inf), qs, 100.0, use_pallas=False)[1])


def _rank_error_f64(samples, x, q):
    """How far ``q`` lies outside the rank interval of the emitted ``x``
    among ``samples`` (float64 NumPy order statistics, nothing of
    veneur_tpu): 0 inside it. Among a few samples a value strictly
    between two neighbours counts as either of them, since an
    interpolated quantile would otherwise be charged a sample's rank."""
    s = np.sort(np.asarray(samples, np.float64))
    below = (s < x).sum() / len(s)
    upto = (s <= x).sum() / len(s)
    if below <= q <= upto:
        return 0.0
    err = min(abs(below - q), abs(upto - q))
    if below == upto and 0 < below < 1:
        err = max(err - 1.0 / len(s), 0.0)
    return err if np.isfinite(x) else 1.0


@pytest.mark.parametrize("sample_rate", [1.0, 0.1, 0.01])
@pytest.mark.parametrize("chunks", [1, 4, 12])
@pytest.mark.parametrize("samples", [2, 3, 8, 60, 500])
def test_sparse_rows_spread_over_chunks_stay_inside_the_bound(
        samples, chunks, sample_rate):
    """Rows of ``samples`` samples an interval, delivered a few a chunk
    over ``chunks`` ingest dispatches, then the flush: every percentile
    within 0.04 of its rank. Binned against the 8-anchor summary alone
    (the parent) the spread cases alias value-distant samples into one
    bin: 0.05-0.12 here, 0.3233 on the chip (PERF.md, PR 39). A line
    sent ``|@0.1`` or ``|@0.01`` weighs 10 or 100: the row drain counts
    arrivals, so such a row is held to the same bound."""
    rows_each = 16 if samples == 500 else 128
    worst = 0.0
    for seed in range(3):
        rng = np.random.default_rng(1000 * samples + 10 * chunks + seed)
        vals = rng.integers(0, 400000, size=(rows_each, samples)) / 4.0
        which = rng.integers(0, chunks, size=(rows_each, samples))
        digest = td.init((SPARSE_SERIES,), 100.0)
        temp = td.init_temp(SPARSE_SERIES, td.size_bound(100.0), 100.0)
        for c in range(chunks):
            r, j = np.nonzero(which == c)
            order = rng.permutation(len(r))
            rows = np.full(SPARSE_CHUNK, SPARSE_SERIES, np.int32)
            v = np.zeros(SPARSE_CHUNK, np.float32)
            w = np.zeros(SPARSE_CHUNK, np.float32)
            rows[:len(r)] = r[order]
            v[:len(r)] = vals[r, j][order]
            w[:len(r)] = 1.0 / sample_rate
            digest, temp = SAMPLE_INGEST(digest, temp, jnp.asarray(rows),
                                         jnp.asarray(v), jnp.asarray(w))
        pcts = np.asarray(_sparse_flush(
            digest, temp, jnp.asarray(SPARSE_QS, jnp.float32)))
        for i in range(rows_each):
            for k, q in enumerate(SPARSE_QS):
                worst = max(worst, _rank_error_f64(
                    vals[i], float(pcts[i, k]), q))
    assert worst < 0.04, worst


@pytest.mark.parametrize("sample_rate", [1.0, 0.1, 0.01])
def test_a_row_past_the_arrivals_keeps_the_anchored_binning(sample_rate):
    """The row drain is for rows that still need it: one to which the
    interval has brought more than ``ROW_DRAIN_MAX_ARRIVALS`` samples
    is left alone (no drain counted, its bins keep their mass), one
    under it is drained: by arrivals, whatever a sample weighs (150
    samples sent ``|@0.1`` weigh 1,500)."""
    digest = td.init((8,), 100.0)
    temp = td.init_temp(8, td.size_bound(100.0), 100.0)
    rng = np.random.default_rng(3)
    big, few, weight = int(td.ROW_DRAIN_MAX_ARRIVALS) + 76, 150, \
        1.0 / sample_rate
    rows = np.concatenate([np.zeros(big, np.int32), np.ones(few, np.int32)])
    step = jax.jit(lambda d, t, r, v: td.ingest_chunk_rowdrained(
        d, t, r, v, jnp.full(r.shape, weight, jnp.float32), 100.0, False))
    digest, temp, drained = step(digest, temp, jnp.asarray(rows),
                                 jnp.asarray(rng.normal(0, 1, len(rows)),
                                             jnp.float32))
    assert int(drained) == 0                    # nothing held yet
    digest, temp, drained = step(digest, temp, jnp.asarray(rows),
                                 jnp.asarray(rng.normal(0, 1, len(rows)),
                                             jnp.float32))
    assert int(drained) == 1                    # row 1 alone
    bins = np.asarray(temp.bins()[0]).sum(axis=1)
    np.testing.assert_allclose(bins[:2], [2 * big * weight, few * weight],
                               rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(digest.weight)[:2].sum(axis=1), [0.0, few * weight],
        rtol=1e-6)
