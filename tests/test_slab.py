"""SlabDigestBank: the capacity-planned large-cardinality digest bank.

Oracles: the dense single-plane ops path (veneur_tpu.ops.tdigest) on the
same samples — per-row results must match across slab boundaries, storage
dtypes, and roles, mirroring the per-sampler merge semantics of the
reference (samplers_test.go:49-560, histo_test.go:11-25)."""

import jax.numpy as jnp
import numpy as np
import pytest

from veneur_tpu.core.slab import SlabDigestBank
from veneur_tpu.ops import tdigest as td_ops

C = 100.0
QS = [0.25, 0.5, 0.9, 0.99]


def _exact_check(pcts, rows, vals, stride=7, tol=0.05):
    """Rank-error oracle: the RANK of each reported quantile value among
    the row's exact samples stays within tol of q. (Value-space checks
    are the wrong oracle at tail jumps: the reference's uniform
    centroid interpolation — merging_digest.go:297-327, no singleton
    special case — can legitimately land anywhere inside the gap next to
    an outlier; its own accuracy tests are rank-based, histo_test.go:11-25.)
    """
    for row in range(0, int(rows.max()) + 1, stride):
        mine = np.sort(vals[rows == row])
        n = len(mine)
        if n < 32:
            continue
        for j, q in enumerate(QS):
            lo = np.searchsorted(mine, pcts[row, j], "left") / n
            hi = np.searchsorted(mine, pcts[row, j], "right") / n
            err = 0.0 if lo <= q <= hi else min(abs(lo - q), abs(hi - q))
            assert err < tol, (
                f"row {row} q{q}: value {pcts[row, j]} has rank "
                f"[{lo:.3f},{hi:.3f}], want {q}")


class TestLocalRole:
    def test_multi_slab_matches_dense_path(self):
        """3 slabs of 64 rows == one dense 192-row digest batch."""
        S, N = 192, 20000
        rng = np.random.default_rng(0)
        rows = rng.integers(0, S, N).astype(np.int32)
        vals = rng.gamma(2.0, 30.0, N).astype(np.float32)
        wts = np.ones(N, np.float32)

        bank = SlabDigestBank(S, C, slab_rows=64)
        bank.ingest(rows, vals, wts)
        out = bank.flush(QS)

        k = td_ops.size_bound(C)
        temp = td_ops.init_temp(S, k, C)
        temp = td_ops.ingest_chunk(temp, jnp.asarray(rows),
                                   jnp.asarray(vals), jnp.asarray(wts), C)
        digest = td_ops.init((S,), C, k)
        drained, pcts = td_ops.drain_and_quantile(
            digest, temp, jnp.full((S,), jnp.inf), jnp.full((S,), -jnp.inf),
            jnp.asarray(QS, jnp.float32), C)

        np.testing.assert_allclose(out["percentiles"], np.asarray(pcts),
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(out["count"],
                                   np.bincount(rows, weights=wts,
                                               minlength=S), rtol=1e-6)
        np.testing.assert_allclose(out["min"],
                                   [vals[rows == r].min() for r in range(S)],
                                   rtol=1e-6)
        _exact_check(out["percentiles"], rows, vals)

    def test_ingest_slab_local_rows(self):
        """Pre-partitioned per-slab ingest equals global-row ingest."""
        S, N = 128, 8000
        rng = np.random.default_rng(1)
        rows = rng.integers(0, S, N).astype(np.int32)
        vals = rng.normal(50, 12, N).astype(np.float32)
        wts = np.ones(N, np.float32)

        a = SlabDigestBank(S, C, slab_rows=64)
        a.ingest(rows, vals, wts)
        b = SlabDigestBank(S, C, slab_rows=64)
        for i in range(b.num_slabs):
            sel = (rows >= i * 64) & (rows < (i + 1) * 64)
            b.ingest_slab(i, rows[sel] - i * 64, vals[sel], wts[sel])
        oa, ob = a.flush(QS), b.flush(QS)
        np.testing.assert_allclose(oa["percentiles"], ob["percentiles"],
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_array_equal(oa["count"], ob["count"])

    def test_flush_resets_state(self):
        S = 64
        rng = np.random.default_rng(2)
        bank = SlabDigestBank(S, C, slab_rows=64)
        rows = rng.integers(0, S, 4000).astype(np.int32)
        vals = rng.normal(0, 1, 4000).astype(np.float32)
        bank.ingest(rows, vals, np.ones(4000, np.float32))
        first = bank.flush(QS)
        assert first["count"].sum() > 0
        second = bank.flush(QS)
        assert second["count"].sum() == 0
        assert np.isnan(second["percentiles"]).all()

    def test_bf16_storage_within_tolerance(self):
        """bf16 resident digests: same flush results within 2^-8 relative
        (storage rounding), still inside the digest error envelope."""
        S, N = 96, 30000
        rng = np.random.default_rng(3)
        rows = rng.integers(0, S, N).astype(np.int32)
        vals = rng.gamma(3.0, 20.0, N).astype(np.float32)
        wts = np.ones(N, np.float32)

        f32 = SlabDigestBank(S, C, slab_rows=32, digest_dtype=jnp.float32)
        b16 = SlabDigestBank(S, C, slab_rows=32, digest_dtype=jnp.bfloat16)
        for bank in (f32, b16):
            bank.ingest(rows, vals, wts)
        of, ob = f32.flush(QS), b16.flush(QS)
        # counts come from the f32 scalar stats: exact in BOTH banks
        np.testing.assert_array_equal(of["count"], ob["count"])
        span = of["max"] - of["min"]
        assert (np.abs(of["percentiles"] - ob["percentiles"])
                / np.maximum(span[:, None], 1e-6)).max() < 0.01
        _exact_check(ob["percentiles"], rows, vals, stride=5)

    def test_multi_interval_bf16(self):
        """bf16 rounding must not accumulate across drains within an
        interval: 8 successive chunks, then flush."""
        S = 32
        rng = np.random.default_rng(4)
        bank = SlabDigestBank(S, C, slab_rows=32, digest_dtype=jnp.bfloat16)
        allr, allv = [], []
        for _ in range(8):
            rows = rng.integers(0, S, 5000).astype(np.int32)
            vals = rng.normal(100, 25, 5000).astype(np.float32)
            bank.ingest(rows, vals, np.ones(5000, np.float32))
            allr.append(rows)
            allv.append(vals)
        out = bank.flush(QS)
        _exact_check(out["percentiles"], np.concatenate(allr),
                     np.concatenate(allv), stride=3)


class TestMergeRole:
    def _forwarded(self, rng, S, k):
        """A host's forwarded digest batch: [S, k] centroids + extrema."""
        rows = rng.integers(0, S, 20000).astype(np.int32)
        vals = rng.gamma(2.0, 40.0, 20000).astype(np.float32)
        temp = td_ops.init_temp(S, k, C)
        temp = td_ops.ingest_chunk(temp, jnp.asarray(rows),
                                   jnp.asarray(vals),
                                   jnp.ones((20000,), jnp.float32), C)
        d = td_ops.drain_temp(td_ops.init((S,), C, k), temp, C)
        return d, rows, vals

    def test_merge_matches_ops_merge(self):
        """Slab-wise merge of two hosts == td_ops.merge on the dense path."""
        S = 128
        k = td_ops.size_bound(C)
        rng = np.random.default_rng(5)
        d1, r1, v1 = self._forwarded(rng, S, k)
        d2, r2, v2 = self._forwarded(rng, S, k)

        bank = SlabDigestBank(S, C, slab_rows=64, mode="merge")
        for d in (d1, d2):
            for i in range(bank.num_slabs):
                sl = slice(i * 64, (i + 1) * 64)
                bank.merge_digests(i, np.asarray(d.mean[sl]),
                                   np.asarray(d.weight[sl]),
                                   np.asarray(d.min[sl]),
                                   np.asarray(d.max[sl]))
        out = bank.flush(QS)

        # oracle: merge into an empty dense digest, then quantile
        merged = td_ops.merge(d1, d2, C)
        pcts = td_ops.quantile(merged, jnp.asarray(QS, jnp.float32))
        span = np.asarray(merged.max - merged.min)
        diff = (np.abs(out["percentiles"] - np.asarray(pcts))
                / np.maximum(span[:, None], 1e-6))
        assert diff.max() < 0.02
        np.testing.assert_allclose(out["count"],
                                   np.asarray(merged.count()), rtol=1e-5)
        _exact_check(out["percentiles"], np.concatenate([r1, r2]),
                     np.concatenate([v1, v2]), stride=11)

    def test_bf16_merge_counts_exact(self):
        """Counts must not stall on bf16 weight rounding: a hot series
        receives many small imported batches; the reported count is the
        exact sum (the f32 count plane), not the rounded weight total."""
        S = 64
        k = td_ops.size_bound(C)
        bank = SlabDigestBank(S, C, slab_rows=64, mode="merge",
                              digest_dtype=jnp.bfloat16)
        # one centroid per import, always the same mean: the resident
        # centroid's weight grows past bf16's integer range (256) where
        # +3.0 increments round away
        mean = np.full((S, 1), 50.0, np.float32)
        w = np.full((S, 1), 3.0, np.float32)
        mins = np.full(S, 50.0, np.float32)
        maxs = np.full(S, 50.0, np.float32)
        n_batches = 400
        for _ in range(n_batches):
            bank.merge_digests(0, mean, w, mins, maxs)
        out = bank.flush(QS)
        np.testing.assert_array_equal(out["count"],
                                      np.full(S, 3.0 * n_batches))

    def test_merge_mode_has_no_temp(self):
        bank = SlabDigestBank(256, C, slab_rows=128, mode="merge")
        assert all(t is None for t in bank.temps)
        with pytest.raises(AssertionError):
            bank.ingest(np.zeros(4, np.int32), np.ones(4, np.float32),
                        np.ones(4, np.float32))


class TestStoreWiring:
    """digest_storage='slab' must be behaviorally identical to the dense
    store on the same traffic (the store-level oracle that makes the
    capacity plan a product path, not a bench harness): to the last
    digit where no series' samples span a dispatch (``chunk=1024``),
    and every exact row identical with every percentile inside the rank
    bound where they do (``chunk=128``: since PR 40 the dense store
    drains a row that holds bin mass before it bins more into it,
    ops/tdigest.py ingest_chunk_rowdrained, and the slab store still
    bins it against the anchor summary)."""

    def _stores(self, chunk=1024):
        from veneur_tpu.core.store import MetricStore

        dense = MetricStore(initial_capacity=64, chunk=chunk)
        slab = MetricStore(initial_capacity=64, chunk=chunk,
                           digest_storage="slab", slab_rows=64)
        return dense, slab

    def _drive(self, store, rng):
        from veneur_tpu.samplers.parser import (MetricKey, UDPMetric,
                                                LOCAL_ONLY, MIXED_SCOPE)

        sent = {}
        for i in range(150):
            for name, kind, high, tags, rate, scope in (
                    (f"lat{i % 20}", "timer", 500, ["route:a"], 1.0,
                     MIXED_SCOPE),
                    (f"hist{i % 7}", "histogram", 100, [], 0.5,
                     LOCAL_ONLY)):
                value = float(rng.integers(1, high))
                sent.setdefault(name, []).append(value)
                store.process_metric(UDPMetric(
                    key=MetricKey(name=name, type=kind), value=value,
                    tags=tags, sample_rate=rate, scope=scope, digest=0))
        store.import_digest(MetricKey(name="fleet.lat", type="histogram"),
                            ["dc:x"], np.asarray([10.0, 20.0, 30.0]),
                            np.asarray([1.0, 2.0, 1.0]), 10.0, 30.0)
        return sent

    @staticmethod
    def _rank_error(samples, x, q):
        """How far ``q`` lies outside the rank interval of ``x`` among
        ``samples`` (a value strictly between two neighbours counts as
        either)."""
        s = np.sort(np.asarray(samples, np.float64))
        below, upto = (s < x).sum() / len(s), (s <= x).sum() / len(s)
        if below <= q <= upto:
            return 0.0
        err = min(abs(below - q), abs(upto - q))
        if below == upto and 0 < below < 1:
            err = max(err - 1.0 / len(s), 0.0)
        return err

    def test_store_parity_dense_vs_slab(self):
        from veneur_tpu.samplers.intermetric import HistogramAggregates

        agg = HistogramAggregates.from_names(
            ["min", "max", "count", "median"])
        outs = []
        for store in self._stores():
            self._drive(store, np.random.default_rng(9))
            final, fwd, ms = store.flush([0.5, 0.99], agg, is_local=False,
                                         now=1000, forward=False)
            outs.append(sorted((m.name, tuple(m.tags), round(m.value, 2))
                               for m in final))
            assert ms.timers == 20 and ms.local_histograms == 7
        assert outs[0] == outs[1]

    def test_store_parity_across_dispatches(self):
        """A series' samples over several dispatches: min, max and count
        of every series and the imported digest's rows identical, every
        percentile of either store inside the rank bound of the samples
        sent."""
        from veneur_tpu.samplers.intermetric import HistogramAggregates

        agg = HistogramAggregates.from_names(
            ["min", "max", "count", "median"])
        quantile_of = {"50percentile": 0.5, "99percentile": 0.99,
                       "median": 0.5}
        outs = []
        for store in self._stores(chunk=128):
            sent = self._drive(store, np.random.default_rng(9))
            final, fwd, ms = store.flush([0.5, 0.99], agg, is_local=False,
                                         now=1000, forward=False)
            exact = []
            for m in final:
                series, _, suffix = m.name.rpartition(".")
                if series in sent and suffix in quantile_of:
                    assert self._rank_error(
                        sent[series], m.value, quantile_of[suffix]) <= 0.02
                else:
                    exact.append((m.name, tuple(m.tags), round(m.value, 2)))
            outs.append(sorted(exact))
            assert ms.timers == 20 and ms.local_histograms == 7
        assert len(outs[0]) == 3 * 27 + 3 and outs[0] == outs[1]

    def _forwarded(self, chunk):
        from veneur_tpu.samplers.intermetric import HistogramAggregates

        agg = HistogramAggregates.from_names(["count"])
        fwds = []
        for store in self._stores(chunk):
            self._drive(store, np.random.default_rng(11))
            _, fwd, _ = store.flush([0.5], agg, is_local=True, now=0,
                                    forward=True)
            fwds.append(fwd)
        a, b = fwds
        assert len(a.timers) == len(b.timers) == 20
        return zip(sorted(a.timers), sorted(b.timers))

    def test_store_slab_forwardable(self):
        """is_local=True: digests export for forwarding from the slab
        store exactly as from the dense one."""
        for (n1, t1, m1, w1, lo1, hi1), (n2, t2, m2, w2, lo2, hi2) in \
                self._forwarded(chunk=1024):
            assert n1 == n2 and t1 == t2 and lo1 == lo2 and hi1 == hi2
            np.testing.assert_allclose(m1, m2, rtol=1e-6)
            np.testing.assert_allclose(w1, w2, rtol=1e-6)

    def test_store_slab_forwardable_across_dispatches(self):
        """Across dispatches the forwarded digests carry the same mass,
        first moment and extrema; the centroids differ where the dense
        store's row drain kept apart what the slab's bins merged."""
        for (n1, t1, m1, w1, lo1, hi1), (n2, t2, m2, w2, lo2, hi2) in \
                self._forwarded(chunk=128):
            assert n1 == n2 and t1 == t2 and lo1 == lo2 and hi1 == hi2
            assert np.sum(w1) == np.sum(w2)
            np.testing.assert_allclose(np.dot(m1, w1), np.dot(m2, w2),
                                       rtol=1e-5)
            assert lo1 <= np.min(m1) and np.max(m1) <= hi1
            assert lo2 <= np.min(m2) and np.max(m2) <= hi2

    def test_slab_group_grows(self):
        from veneur_tpu.core.slab import SlabDigestGroup
        from veneur_tpu.samplers.parser import MetricKey

        g = SlabDigestGroup(slab_rows=8, chunk=32)
        for i in range(50):
            g.sample(MetricKey(name=f"m{i}", type="histogram"), [],
                     float(i), 1.0)
        assert g.capacity >= 50 and len(g.digests) >= 7
        interner, out = g.flush([0.5])
        assert len(interner.rows) == 50
        np.testing.assert_allclose(out["count"], np.ones(50))
        np.testing.assert_allclose(out["median"], np.arange(50.0))

    def test_config_validation(self):
        from veneur_tpu.config import Config

        Config(digest_storage="slab", digest_dtype="packed16").validate()
        with pytest.raises(ValueError, match="digest_storage"):
            Config(digest_storage="mmap").validate()
        with pytest.raises(ValueError, match="digest_dtype"):
            Config(digest_dtype="float8").validate()
        # the 16-bit planes hold coded means, not bfloat16 ones: a
        # configuration that asks for bfloat16 is refused at load
        with pytest.raises(ValueError, match="digest_dtype"):
            Config(digest_storage="slab", digest_dtype="bfloat16").validate()
        with pytest.raises(ValueError, match="packed16 requires"):
            Config(digest_dtype="packed16").validate()


class TestCapacityPlan:
    def test_hbm_accounting(self):
        k = td_ops.size_bound(C)
        bank = SlabDigestBank(1 << 21, C, slab_rows=1 << 20,
                              digest_dtype=jnp.bfloat16)
        plan = bank.hbm_bytes()
        assert plan["num_slabs"] == 2
        # the digest planes, and five f32 planes a row: the means'
        # frames, the imported extrema and the exact count
        assert plan["digest_bytes"] == 2 * ((1 << 20) * k * 2 * 2
                                            + (1 << 20) * 4 * 5)
        # 5 scalar stat planes + the round-5 anchor-summary planes
        # (2 x BELOW_MASS_ANCHORS f32 per row)
        assert plan["temp_bytes"] == 2 * (
            (1 << 20) * k * 4 * 2
            + (1 << 20) * 4 * (5 + 2 * td_ops.BELOW_MASS_ANCHORS))

    def test_north_star_fits_v5e(self):
        """The 10M bf16 local plan stays under a 16 GB v5e-1 HBM —
        with 256k-row slabs since round 5: the anchor-summary planes
        cost 64 B/row of residency, and the per-slab flush transients
        (which scale with slab rows) must fit what is left."""
        bank = SlabDigestBank(10_000_000, C, slab_rows=1 << 18,
                              digest_dtype=jnp.bfloat16)
        plan = bank.hbm_bytes()
        resident = plan["total_bytes"] + plan["slab_transient_bytes"]
        assert resident < 15 * 2**30, f"{resident / 2**30:.1f} GB"

    def test_partial_last_slab(self):
        """num_series not a slab multiple: padded rows stay silent."""
        S = 100
        rng = np.random.default_rng(6)
        bank = SlabDigestBank(S, C, slab_rows=64)
        assert bank.num_slabs == 2
        rows = rng.integers(0, S, 5000).astype(np.int32)
        vals = rng.normal(10, 2, 5000).astype(np.float32)
        bank.ingest(rows, vals, np.ones(5000, np.float32))
        out = bank.flush(QS)
        assert out["percentiles"].shape == (S, len(QS))
        assert out["count"].sum() == 5000


class TestPackedCompaction:
    """The device-side pack (quantize + lane-sort to row prefixes) and
    its two fetch paths must reproduce the exact flat live-centroid
    layout regardless of row skew."""

    def _pack_and_fetch(self, mean, weight, dmin, dmax):
        import jax.numpy as jnp

        from veneur_tpu.core.slab import _fetch_packed, _pack_slab

        S, K = mean.shape
        cts, qp, wp = _pack_slab(
            jnp.asarray(mean.reshape(-1)), jnp.asarray(weight.reshape(-1)),
            jnp.asarray(dmin), jnp.asarray(dmax), S, K)
        return _fetch_packed(cts, qp, wp, S)

    def _golden(self, mean, weight, dmin, dmax):
        """Flat (means, weights) in row-major live order, dequantized
        the same way the wire decodes."""
        means, weights = [], []
        for r in range(len(mean)):
            live = weight[r] > 0
            span = (float(dmax[r]) - float(dmin[r])) / 65535.0
            if not np.isfinite(span):
                span = 0.0
            q = np.clip(np.round((mean[r][live] - dmin[r])
                                 / (span * 65535.0 if span else 1.0)
                                 * 65535.0), 0, 65535)
            means.append(dmin[r] + q * span)
            weights.append(weight[r][live].astype(np.float32))
        return np.concatenate(means), np.concatenate(weights)

    def _check(self, mean, weight, dmin, dmax):
        counts, mq, wb = self._pack_and_fetch(mean, weight, dmin, dmax)
        live_per_row = (weight > 0).sum(axis=1)
        assert np.array_equal(counts.astype(np.int64), live_per_row)
        total = int(live_per_row.sum())
        assert len(mq) == len(wb) == total
        # dequantize and compare to the golden flat layout
        span = ((dmax - dmin) / 65535.0).astype(np.float64)
        span[~np.isfinite(span)] = 0.0
        rows = np.repeat(np.arange(len(mean)), live_per_row)
        got_means = dmin[rows] + mq.astype(np.float64) * span[rows]
        got_weights = (wb.astype(np.uint32) << 16).view(np.float32)
        gold_means, gold_weights = self._golden(mean, weight, dmin, dmax)
        # mean quantization error bounded by one step PER ROW (a global
        # max would let a narrow-span row be off by several steps)
        assert np.all(np.abs(got_means - gold_means)
                      <= span[rows] * 1.01 + 1e-12)
        assert np.allclose(got_weights,
                           gold_weights.astype(np.float32), rtol=1/256)

    def test_uniform_rows_slice_path(self):
        rng = np.random.default_rng(1)
        S, K = 256, 104
        weight = (rng.random((S, K)) < 0.05).astype(np.float32) * 2.0
        mean = rng.normal(100, 20, (S, K)).astype(np.float32)
        dmin = mean.min(axis=1) - 1
        dmax = mean.max(axis=1) + 1
        self._check(mean, weight, dmin, dmax)

    def test_skewed_rows_gather_path(self):
        # one heavy row (all K live) + many 1-live rows: the column
        # slice would fetch S*pow2(K) elements, so _fetch_packed must
        # take the device flat-gather path — and produce the identical
        # layout
        rng = np.random.default_rng(2)
        S, K = 4096, 104
        weight = np.zeros((S, K), np.float32)
        weight[np.arange(S), rng.integers(0, K, S)] = 1.0
        weight[7, :] = 3.0  # the skew row
        mean = rng.normal(50, 10, (S, K)).astype(np.float32)
        dmin = np.full(S, 0.0, np.float32)
        dmax = np.full(S, 100.0, np.float32)
        # route check: replicate _fetch_packed's EXACT slice-vs-gather
        # predicate so this test provably exercises the gather branch
        from veneur_tpu.core.slab import _next_pow2
        counts = (weight > 0).sum(axis=1)
        total = int(counts.sum())
        rows = min(_next_pow2(S), S)
        width = min(_next_pow2(int(counts.max())), K)
        assert rows * width > 3 * _next_pow2(total)
        self._check(mean, weight, dmin, dmax)

    def test_empty_and_full_rows(self):
        S, K = 64, 104
        weight = np.zeros((S, K), np.float32)
        weight[3, :] = 1.0           # fully live row
        weight[10, 50] = 7.0         # single middle slot
        mean = np.linspace(0, 1, S * K).astype(np.float32).reshape(S, K)
        dmin = np.zeros(S, np.float32)
        dmax = np.ones(S, np.float32)
        counts, mq, wb = self._pack_and_fetch(mean, weight, dmin, dmax)
        assert counts[3] == K and counts[10] == 1
        assert counts.astype(np.int64).sum() == K + 1
        w = (wb.astype(np.uint32) << 16).view(np.float32)
        assert w[-1] == 7.0  # row 10 comes after row 3 in flat order


class TestSelectiveStatFetch:
    def test_unfetched_stats_zero_filled_and_masked(self):
        from veneur_tpu.core.store import MetricStore
        from veneur_tpu.samplers import parser as P
        from veneur_tpu.samplers.intermetric import HistogramAggregates

        def fill(store):
            for v in (1.0, 5.0, 9.0):
                store.process_metric(
                    P.parse_metric(f"h:{v}|h".encode()))

        # full aggregate set vs the min/max/count default: the shared
        # stats must agree exactly; the restricted flush must not emit
        # the unfetched aggregates at all
        full = MetricStore(initial_capacity=32, chunk=64)
        fill(full)
        agg_all = HistogramAggregates.from_names(
            ["min", "max", "count", "sum", "avg", "median", "hmean"])
        out_all, _, _ = full.flush([0.5], agg_all, is_local=False, now=1)
        m_all = {m.name: m.value for m in out_all}

        small = MetricStore(initial_capacity=32, chunk=64)
        fill(small)
        agg_mmc = HistogramAggregates.from_names(["min", "max", "count"])
        out_mmc, _, _ = small.flush([], agg_mmc, is_local=False, now=1)
        m_mmc = {m.name: m.value for m in out_mmc}

        for key in ("h.min", "h.max", "h.count"):
            assert m_mmc[key] == m_all[key]
        for absent in ("h.sum", "h.avg", "h.median", "h.hmean",
                       "h.50percentile"):
            assert absent in m_all
            assert absent not in m_mmc


class TestRetiredRelease:
    """Release-order audit (PR 5): a RETIRED twin frees its device
    planes first and its host staging immediately after the flush —
    it outlives the flush by the whole sink fan-out and must not pin
    chunk-sized buffers (or allocate fresh ones) for that window."""

    def _group(self):
        from veneur_tpu.core.slab import SlabDigestGroup

        g = SlabDigestGroup(slab_rows=8, chunk=32)
        from veneur_tpu.samplers.parser import MetricKey

        for i in range(12):
            g.sample(MetricKey(name=f"h{i}", type="histogram",
                               joined_tags=""), [], float(i + 1), 1.0)
        return g

    def test_retired_slab_twin_frees_planes_and_staging(self):
        g = self._group()
        g._retired = True
        interner, out = g.flush([0.5])
        assert len(interner) == 12 and "percentiles" in out
        assert g.digests == [] and g.temps == []
        assert g._rows is None and g._vals is None and g._wts is None
        assert g._imp_rows is None and g._imp_stat_rows is None

    def test_retired_empty_twin_allocates_nothing(self):
        """The n==0 path used to hand a dead twin six fresh
        chunk-sized buffers; now it drops the ones it has."""
        from veneur_tpu.core.slab import SlabDigestGroup

        g = SlabDigestGroup(slab_rows=8, chunk=32)
        g._retired = True
        interner, out = g.flush([0.5])
        assert out == {}
        assert g.digests == [] and g.temps == []
        assert g._rows is None and g._imp_rows is None

    def test_live_group_keeps_staging(self):
        g = self._group()
        interner, out = g.flush([0.5])
        assert g._rows is not None and len(g.digests) >= 1
        # and it still aggregates the next interval
        from veneur_tpu.samplers.parser import MetricKey

        g.sample(MetricKey(name="h0", type="histogram",
                           joined_tags=""), [], 5.0, 1.0)
        assert len(g.interner) == 1

    def test_dense_retired_twin_frees_staging_too(self):
        from veneur_tpu.core.store import DigestGroup
        from veneur_tpu.samplers.parser import MetricKey

        g = DigestGroup(capacity=16, chunk=32)
        for i in range(5):
            g.sample(MetricKey(name=f"h{i}", type="histogram",
                               joined_tags=""), [], float(i + 1), 1.0)
        g._retired = True
        interner, out = g.flush([0.5])
        assert g.digest is None and g.temp is None
        assert g._rows is None and g._imp_rows is None

    def test_store_flush_releases_the_retired_generation(self):
        """End to end through the swap: after MetricStore.flush the
        retired groups (exclusively owned by the flush) are drained
        AND stripped of device planes + staging."""
        from veneur_tpu.core.store import MetricStore
        from veneur_tpu.samplers.intermetric import HistogramAggregates
        from veneur_tpu.samplers.parser import parse_metric

        store = MetricStore(initial_capacity=16, chunk=32,
                            digest_storage="slab", slab_rows=16)
        for v in range(1, 20):
            store.process_metric(parse_metric(f"h1:{v}|h".encode()))
        gen = {}
        orig = MetricStore._swap_generation

        def spy(self):
            g = orig(self)
            gen["histograms"] = g.histograms
            return g

        MetricStore._swap_generation = spy
        try:
            store.flush([0.5], HistogramAggregates(), is_local=False,
                        now=0, forward=False)
        finally:
            MetricStore._swap_generation = orig
        retired = gen["histograms"]
        assert retired._retired
        assert retired.digests == [] and retired._rows is None


class TestSlabOnTheServedPath:
    """What the slab store owes on the served path (``digest_storage:
    slab``): the dense store's ingest and flush ops on its flat planes.
    A sparse row whose samples span several dispatches is drained
    before more is binned into it, so it stays inside the documented
    0.02 by rank; at float32 the slab store is the dense store to the
    last bit; a lone sample comes back as itself in either storage
    dtype; and the flush compiles nothing as the live count wanders."""

    QS = [0.5, 0.75, 0.99]

    @staticmethod
    def _keys(n, prefix="h"):
        from veneur_tpu.samplers.parser import MetricKey

        return [MetricKey(name=f"{prefix}{i}", type="histogram",
                          joined_tags="") for i in range(n)]

    def _feed(self, group, rows, vals, chunk):
        keys = self._keys(int(rows.max()) + 1)
        for k in keys:
            group._row(k, [])
        for s in range(0, len(rows), chunk):
            group.sample_many(rows[s:s + chunk], vals[s:s + chunk],
                              np.ones(len(rows[s:s + chunk]), np.float32))
        return group.flush(self.QS, want_digests=False)[1]

    @staticmethod
    def _sparse(seed, series=240):
        """Rows of 2 to 127 samples each, interleaved in a random order,
        values in quarters to 400,000 (the cell's law)."""
        rng = np.random.default_rng(seed)
        counts = rng.integers(2, 128, series)
        rows = np.repeat(np.arange(series, dtype=np.int32), counts)
        rng.shuffle(rows)
        vals = (rng.integers(0, 4 * 400000, len(rows)) / 4.0).astype(
            np.float32)
        return rows, vals

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("seed,chunk", [(1, 32), (2, 64), (3, 128)])
    def test_sparse_rows_across_dispatches_inside_the_bound(self, seed,
                                                            chunk, dtype):
        from veneur_tpu.core.slab import SlabDigestGroup

        rows, vals = self._sparse(seed)
        # no row fits one dispatch: every chunk is shorter than the
        # shortest row's share of the interleaved stream would need
        assert chunk < len(rows) // 240 * 4
        out = self._feed(SlabDigestGroup(slab_rows=64, chunk=chunk,
                                         digest_dtype=dtype), rows,
                         vals, chunk)
        worst = 0.0
        for r in range(int(rows.max()) + 1):
            mine = vals[rows == r]
            assert out["count"][r] == len(mine)
            assert out["min"][r] == mine.min() and out["max"][r] == mine.max()
            for j, q in enumerate(self.QS):
                worst = max(worst, TestStoreWiring._rank_error(
                    mine, out["percentiles"][r, j], q))
        assert worst <= 0.02, worst

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sparse_rows_through_the_kernels(self, dtype, monkeypatch):
        """The same through the Pallas kernels the chip runs (interpret
        mode): a digest row handed back to the kernels is ascending
        across its slots, as they write it, whatever its storage (a
        16-bit row decoded with +inf at its empty slots read 0.5 by rank
        on the chip and here)."""
        from veneur_tpu.core.slab import SlabDigestGroup
        from veneur_tpu.ops import tdigest_pallas as tp

        compress, drain = tp._compress_presorted_pallas, \
            tp._drain_quantile_pallas
        monkeypatch.setattr(tp, "pallas_ok", lambda m: (
            m.ndim == 2 and m.dtype == jnp.float32))
        monkeypatch.setattr(tp, "_compress_presorted_pallas", lambda *a, **k:
                            compress(*a, **dict(k, interpret=True)))
        monkeypatch.setattr(tp, "_drain_quantile_pallas", lambda *a, **k:
                            drain(*a, **dict(k, interpret=True)))
        rows, vals = self._sparse(5, series=120)
        # shapes no other test traces, so no program cached off the
        # kernels' path is reused
        out = self._feed(SlabDigestGroup(slab_rows=72, chunk=40,
                                         digest_dtype=dtype), rows, vals, 40)
        worst = max(TestStoreWiring._rank_error(
            vals[rows == r], out["percentiles"][r, j], q)
            for r in range(120) for j, q in enumerate(self.QS))
        assert worst <= 0.02, worst

    @pytest.mark.parametrize("slab_rows", [64, 512])
    def test_float32_slabs_are_the_dense_store_bit_for_bit(self, slab_rows):
        """The same chunks through a dense group and a float32 slab
        group, one slab or several: every result identical. (Values are
        stationary, so the chunk-wide shift guard, which a slab asks of
        its own rows, fires in neither.)"""
        from veneur_tpu.core.slab import SlabDigestGroup
        from veneur_tpu.core.store import DigestGroup

        rows, vals = self._sparse(7)
        dense = self._feed(DigestGroup(capacity=256, chunk=96), rows, vals,
                           96)
        slab = self._feed(SlabDigestGroup(slab_rows=slab_rows, chunk=96),
                          rows, vals, 96)
        for key in ("percentiles", "median", "count", "sum", "min", "max",
                    "recip"):
            np.testing.assert_array_equal(slab[key], dense[key], key)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_a_lone_sample_comes_back_as_itself(self, dtype):
        """Values bfloat16 cannot hold, one a row, among rows that are
        drained mid-interval: each lone row's percentiles are its sample
        to the last bit (the flush reads its f32 bins, never the
        storage planes)."""
        from veneur_tpu.core.slab import SlabDigestGroup

        rng = np.random.default_rng(11)
        lone = (rng.integers(0, 4 * 400000, 100) / 4.0 + 0.25).astype(
            np.float32)
        rows, vals = self._sparse(12, series=60)
        rows = np.concatenate([rows, np.arange(60, 160, dtype=np.int32)])
        vals = np.concatenate([vals, lone])
        g = SlabDigestGroup(slab_rows=64, chunk=64, digest_dtype=dtype)
        out = self._feed(g, rows, vals, 64)
        for key in ("min", "max", "median"):
            np.testing.assert_array_equal(out[key][60:], lone, key)
        np.testing.assert_array_equal(
            out["percentiles"][60:], np.repeat(lone[:, None], 3, axis=1))
        np.testing.assert_array_equal(out["count"][60:], np.ones(100))

    def test_the_flush_compiles_nothing_as_the_count_wanders(self):
        """Three flushes of a group of 4,096-row slabs, the live count in
        the second slab's one pow2 bucket each time: no program compiles
        after the first flush (the counts come off the device as their
        bucket, cut on the host; the flush program takes the count as a
        device scalar); each interval's two slabs were placed as its
        rows interned."""
        from veneur_tpu.core.slab import SlabDigestGroup
        from veneur_tpu.obs import kernels as obs_kernels

        g = SlabDigestGroup(slab_rows=4096, chunk=1024)
        seen = []
        for n in (5000, 4700, 5100):      # 904, 604, 1004 in slab 1
            rows = np.asarray([g._row(k, []) for k in self._keys(n)],
                              np.int32)
            g.sample_many(rows, np.arange(n, dtype=np.float32) + 0.5,
                          np.ones(n, np.float32))
            before = obs_kernels._compile["programs"]
            snap = g.snapshot_state()      # the checkpoint's slices too
            assert len(snap["count"]) == n
            _interner, out = g.flush_begin(self.QS)()
            seen.append(obs_kernels._compile["programs"] - before)
            np.testing.assert_array_equal(
                out["median"], np.arange(n, dtype=np.float32) + 0.5)
        assert seen[1:] == [0, 0], seen
        assert g.grows == 2 and len(g.digests) == 2
