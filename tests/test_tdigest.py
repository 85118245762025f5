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
