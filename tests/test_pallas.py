"""The fused Pallas compress kernel vs the XLA compress.

Runs the kernel in interpreter mode (no TPU in CI; the real lowering is
compiled by ``tests/test_chip_compile.py`` and run on hardware by the
benchmark's cells, which require rung ``pallas``), asserting the merge
of two sorted centroid lists produces a digest whose mass is exact and
whose quantiles agree with the sort-based XLA `_compress` within the
t-digest tolerance.
The only sanctioned deviation is the kernel's polynomial asin
(|err| <= 6.8e-5 rad), which can shift bin edges by < 0.003 of a bin.
"""

import numpy as np
import pytest
import jax.numpy as jnp

from veneur_tpu.ops import tdigest as td
from veneur_tpu.ops import tdigest_pallas as tp
from tests.test_tdigest import assert_bounded_matches_full, flush_state

C = 100.0
K = td.size_bound(C)


def _sorted_centroids(rng, s, k, scale, frac_live):
    mean = np.sort(rng.gamma(2.0, scale, (s, k)).astype(np.float32), axis=1)
    w = (rng.random((s, k)) < frac_live).astype(np.float32) * \
        rng.integers(1, 5, (s, k)).astype(np.float32)
    return jnp.asarray(mean), jnp.asarray(w)


class TestCompressKernel:
    def test_mass_exact_and_quantiles_close(self):
        rng = np.random.default_rng(3)
        s = 64
        ma, wa = _sorted_centroids(rng, s, K, 30.0, 0.7)
        mb, wb = _sorted_centroids(rng, s, K, 25.0, 0.5)
        pm, pw = tp.compress_presorted(ma, wa, mb, wb, C, K, interpret=True)
        xm, xw = td._compress(jnp.concatenate([ma, mb], axis=1),
                              jnp.concatenate([wa, wb], axis=1), C, K)
        # total mass per row is conserved exactly
        np.testing.assert_allclose(np.asarray(pw.sum(1)),
                                   np.asarray(wa.sum(1) + wb.sum(1)),
                                   rtol=1e-6)
        # live centroids stay ascending within each row (gaps interleave)
        pm_np, pw_np = np.asarray(pm), np.asarray(pw)
        for r in range(s):
            lv = pm_np[r][pw_np[r] > 0]
            assert (np.diff(lv) >= -1e-6).all()
        # quantiles agree with the XLA compress within digest tolerance
        mins = jnp.minimum(ma[:, 0], mb[:, 0])
        maxs = jnp.full(s, 500.0, jnp.float32)
        qs = jnp.asarray([0.05, 0.25, 0.5, 0.75, 0.95, 0.99], jnp.float32)
        qp = np.asarray(td.quantile(td.TDigest(pm, pw, mins, maxs), qs))
        qx = np.asarray(td.quantile(td.TDigest(xm, xw, mins, maxs), qs))
        span = np.asarray(maxs)[:, None] - np.asarray(mins)[:, None]
        assert (np.abs(qp - qx) / span < 0.02).all()

    def test_empty_rows(self):
        s = 8
        ma = jnp.full((s, K), jnp.inf, jnp.float32)
        wa = jnp.zeros((s, K), jnp.float32)
        pm, pw = tp.compress_presorted(ma, wa, ma, wa, C, K, interpret=True)
        assert float(pw.sum()) == 0.0

    def test_single_centroid(self):
        s = 8
        ma = jnp.full((s, K), jnp.inf, jnp.float32).at[:, 0].set(42.0)
        wa = jnp.zeros((s, K), jnp.float32).at[:, 0].set(7.0)
        mb = jnp.full((s, K), jnp.inf, jnp.float32)
        wb = jnp.zeros((s, K), jnp.float32)
        pm, pw = tp.compress_presorted(ma, wa, mb, wb, C, K, interpret=True)
        live = np.asarray(pw) > 0
        assert live.sum() == s
        assert np.allclose(np.asarray(pm)[live], 42.0)
        assert np.allclose(np.asarray(pw)[live], 7.0)

    def test_row_padding(self):
        """S not a multiple of the kernel block is padded and sliced."""
        rng = np.random.default_rng(5)
        s = 37
        ma, wa = _sorted_centroids(rng, s, K, 30.0, 0.6)
        mb, wb = _sorted_centroids(rng, s, K, 20.0, 0.6)
        pm, pw = tp.compress_presorted(ma, wa, mb, wb, C, K, interpret=True)
        assert pm.shape == (s, K)
        np.testing.assert_allclose(np.asarray(pw.sum(1)),
                                   np.asarray(wa.sum(1) + wb.sum(1)),
                                   rtol=1e-6)

    def test_drain_quantile_fused_matches_xla(self):
        """The fused drain+quantile kernel == drain_temp + quantile."""
        rng = np.random.default_rng(9)
        s = 64
        ma, wa = _sorted_centroids(rng, s, K, 30.0, 0.6)
        # an unsorted temp accumulator (several chunks' worth)
        temp = td.init_temp(s, K, C)
        rows = jnp.asarray(rng.integers(0, s, 4000).astype(np.int32))
        vals = jnp.asarray(rng.gamma(2.0, 40.0, 4000).astype(np.float32))
        temp = td.ingest_chunk(temp, rows, vals,
                               jnp.ones(4000, jnp.float32), C)
        state = td.TDigest(ma, wa, jnp.zeros(s), jnp.full(s, 800.0))
        qs = jnp.asarray([0.05, 0.5, 0.95, 0.99], jnp.float32)
        dmin = jnp.full(s, jnp.inf)
        dmax = jnp.full(s, -jnp.inf)
        # XLA reference
        xd = td.drain_temp(state, temp, C)
        xq = np.asarray(td.quantile(xd, qs))
        # fused kernel (interpret mode), fed the same sorted halves
        sum_w, sum_wm = temp.bins()
        t_live = sum_w > 0
        t_mean = jnp.where(t_live,
                           sum_wm / jnp.where(t_live, sum_w, 1.0),
                           jnp.inf)
        import jax.lax as lax
        t_mean, t_w = lax.sort((t_mean, sum_w), dimension=-1,
                               num_keys=1, is_stable=False)
        mn = jnp.minimum(jnp.minimum(state.min, temp.vmin), dmin)
        mx = jnp.maximum(jnp.maximum(state.max, temp.vmax), dmax)
        nm, nw, pq = tp.drain_quantile(state.mean, state.weight, t_mean,
                                       t_w, mn, mx, qs, C, K,
                                       interpret=True)
        np.testing.assert_allclose(np.asarray(nw.sum(1)),
                                   np.asarray(xd.weight.sum(1)), rtol=1e-5)
        span = (np.asarray(mx) - np.asarray(mn))[:, None]
        assert (np.abs(np.asarray(pq) - xq) / span < 0.02).all()

    def test_constant_series_percentiles_not_nan(self):
        """All mass in one mid-row k-bin leaves leading gap slots; queries
        landing in the first live centroid must fall back to min, never
        propagate a gap slot's -inf bound (round-2 review regression)."""
        s = 8
        temp = td.init_temp(s, K, C)
        rows = jnp.repeat(jnp.arange(s, dtype=jnp.int32), 100)
        vals = jnp.full(s * 100, 5.0, jnp.float32)
        temp = td.ingest_chunk(temp, rows, vals,
                               jnp.ones(s * 100, jnp.float32), C)
        state = td.init((s,), C)
        qs = jnp.asarray([0.01, 0.5, 0.99], jnp.float32)
        dinf = jnp.full(s, jnp.inf)
        # XLA path
        drained, pcts = td.drain_and_quantile(state, temp, dinf, -dinf,
                                              qs, C)
        assert np.allclose(np.asarray(pcts), 5.0), np.asarray(pcts)
        # fused kernel path, fed a digest whose first live bin is mid-row
        sum_w, sum_wm = temp.bins()
        t_live = sum_w > 0
        t_mean = jnp.where(t_live,
                           sum_wm / jnp.where(t_live, sum_w, 1.0),
                           jnp.inf)
        import jax.lax as lax
        t_mean, t_w = lax.sort((t_mean, sum_w), dimension=-1,
                               num_keys=1, is_stable=False)
        nm, nw, pq = tp.drain_quantile(
            state.mean, state.weight, t_mean, t_w, temp.vmin, temp.vmax,
            qs, C, K, interpret=True)
        assert np.allclose(np.asarray(pq), 5.0), np.asarray(pq)
        # and quantile over the gap-filled kernel output digest directly
        q2 = td.quantile(td.TDigest(nm, nw, temp.vmin, temp.vmax), qs)
        assert np.allclose(np.asarray(q2), 5.0), np.asarray(q2)

    def test_asin_poly_accuracy(self):
        x = np.linspace(-1, 1, 20001).astype(np.float32)
        got = np.asarray(tp._asin_poly(jnp.asarray(x)))
        want = np.arcsin(x)
        assert np.abs(got - want).max() < 1e-4
        # strictly monotone (bin edges must not reorder)
        assert (np.diff(got) >= 0).all()


class TestInKernelSort:
    """sort_b: the in-VMEM descending bitonic sort of the b half. Unused
    by the default pipelines (measured slower on v5e, where the kernel is
    VMEM-bound — see tdigest.drain_temp) but kept as a tested capability
    for shapes/hardware where the external lax.sort loses."""

    def test_sort_b_matches_presorted(self):
        # narrow digest (C=20 -> K=24, half=32): the full-width interpret
        # lowering of the 28-stage sort compiles pathologically slowly on
        # XLA CPU; the network logic is width-generic
        S, C, K = 130, 20.0, td.size_bound(20.0)
        rng = np.random.default_rng(0)
        ma = jnp.asarray(np.sort(rng.normal(0, 1, (S, K)), axis=1)
                         .astype(np.float32))
        wa = jnp.asarray(rng.uniform(0.5, 2, (S, K)).astype(np.float32))
        mb_raw = rng.normal(0, 1, (S, K)).astype(np.float32)
        wb_raw = rng.uniform(0.5, 2, (S, K)).astype(np.float32)
        dead = rng.uniform(0, 1, (S, K)) < 0.3
        mb_raw[dead] = np.inf
        wb_raw[dead] = 0.0
        order = np.argsort(np.where(wb_raw > 0, mb_raw, np.inf), axis=1)
        mb_s = jnp.asarray(np.take_along_axis(mb_raw, order, 1))
        wb_s = jnp.asarray(np.take_along_axis(wb_raw, order, 1))
        mb, wb = jnp.asarray(mb_raw), jnp.asarray(wb_raw)

        nm1, nw1 = tp.compress_presorted(ma, wa, mb_s, wb_s, C, K,
                                         interpret=True)
        nm2, nw2 = tp.compress_presorted(ma, wa, mb, wb, C, K,
                                         interpret=True, sort_b=True)
        np.testing.assert_allclose(np.asarray(nw1), np.asarray(nw2),
                                   rtol=1e-6, atol=1e-6)
        live = np.asarray(nw1) > 0
        np.testing.assert_allclose(np.asarray(nm1)[live],
                                   np.asarray(nm2)[live], rtol=1e-5)

        mn = jnp.full((S,), -5.0, jnp.float32)
        mx = jnp.full((S,), 5.0, jnp.float32)
        qs = jnp.asarray([0.1, 0.5, 0.9], jnp.float32)
        o1 = tp.drain_quantile(ma, wa, mb_s, wb_s, mn, mx, qs, C, K,
                               interpret=True)
        o2 = tp.drain_quantile(ma, wa, mb, wb, mn, mx, qs, C, K,
                               interpret=True, sort_b=True)
        np.testing.assert_allclose(np.asarray(o1[2]), np.asarray(o2[2]),
                                   rtol=1e-5, atol=1e-5)


class TestLiveRowBoundKernel:
    """tdigest.drain_and_quantile with a row count, on the kernel rung
    (interpret mode): each slab is the same kernel over the same
    128-row blocks, so rows [:n] are the full-width program's bit for
    bit and rows past the last slab are not touched."""

    SLAB = 128
    ROWS = 512

    @pytest.fixture(autouse=True)
    def kernel_rung(self, monkeypatch):
        import functools

        monkeypatch.setattr(tp, "_FLUSH_SLAB_ROWS", self.SLAB)
        monkeypatch.setattr(tp, "pallas_ok", lambda a: True)
        monkeypatch.setattr(tp, "drain_quantile", functools.partial(
            tp.drain_quantile, interpret=True))

    @pytest.fixture(scope="class")
    def state(self):
        return flush_state(self.ROWS, seed=5, samples=12000)

    @pytest.mark.parametrize("n", [1, 127, 128, 129, 2 * 128 + 5, 512])
    def test_rows_equal_full_width(self, state, n):
        run = td.flush_rows_run(self.ROWS, n)
        assert run == -(-n // 128) * 128
        assert_bounded_matches_full(state, n, run)

    def test_kernel_is_in_the_loop(self, state):
        import jax

        shapes = jax.eval_shape(lambda: state)
        text = str(jax.make_jaxpr(
            lambda *a: td.drain_and_quantile(
                *a, jnp.asarray([0.5], jnp.float32), C,
                n=np.int32(3)))(*shapes))
        assert "while" in text and "pallas_call" in text
