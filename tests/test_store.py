"""MetricStore behavior: scope routing, flush semantics, merge equivalence.

Plays the role of the reference's samplers_test.go + worker_test.go: golden
scalar samplers (ScalarTDigest / ScalarHLL) check the batched device path
within documented error bounds.
"""

import numpy as np
import pytest

from veneur_tpu.core import MetricStore
from veneur_tpu.ops import tdigest_pallas
from veneur_tpu.samplers import (
    Aggregate,
    HistogramAggregates,
    MetricType,
    ScalarHLL,
    ScalarTDigest,
    parse_metric,
)
from veneur_tpu.samplers.parser import MetricKey

ALL_AGGS = HistogramAggregates(
    Aggregate.MIN | Aggregate.MAX | Aggregate.MEDIAN | Aggregate.AVERAGE |
    Aggregate.COUNT | Aggregate.SUM | Aggregate.HARMONIC_MEAN)
DEFAULT_AGGS = HistogramAggregates()


def make_store(**kw):
    kw.setdefault("initial_capacity", 32)
    kw.setdefault("chunk", 128)
    return MetricStore(**kw)


def flush_map(metrics):
    return {m.name: m for m in metrics}


class TestCounters:
    def test_accumulate(self):
        s = make_store()
        for _ in range(3):
            s.process_metric(parse_metric(b"x:2|c"))
        final, _, _ = s.flush([], DEFAULT_AGGS, is_local=True, now=1)
        assert flush_map(final)["x"].value == 6.0
        assert flush_map(final)["x"].type == MetricType.COUNTER

    def test_sample_rate_integer_semantics(self):
        # Go: value += int64(sample) * int64(1/rate) — 1/0.3 truncates to 3
        s = make_store()
        s.process_metric(parse_metric(b"x:5|c|@0.3"))
        final, _, _ = s.flush([], DEFAULT_AGGS, is_local=True, now=1)
        assert flush_map(final)["x"].value == 5 * 3

    def test_global_counter_forwarded_not_flushed(self):
        s = make_store()
        s.process_metric(parse_metric(b"x:1|c|#veneurglobalonly"))
        final, fwd, _ = s.flush([], DEFAULT_AGGS, is_local=True, now=1)
        assert "x" not in flush_map(final)
        assert fwd.counters == [("x", [], 1)]

    def test_global_counter_flushed_on_global(self):
        s = make_store()
        key = MetricKey("x", "counter", "")
        s.import_counter(key, [], 5)
        s.import_counter(key, [], 7)
        final, _, _ = s.flush([], DEFAULT_AGGS, is_local=False, now=1)
        assert flush_map(final)["x"].value == 12.0

    def test_reset_between_intervals(self):
        s = make_store()
        s.process_metric(parse_metric(b"x:1|c"))
        s.flush([], DEFAULT_AGGS, is_local=True, now=1)
        final, _, _ = s.flush([], DEFAULT_AGGS, is_local=True, now=2)
        assert final == []


class TestGauges:
    def test_last_write_wins(self):
        s = make_store()
        s.process_metric(parse_metric(b"g:1|g"))
        s.process_metric(parse_metric(b"g:9|g"))
        final, _, _ = s.flush([], DEFAULT_AGGS, is_local=True, now=1)
        assert flush_map(final)["g"].value == 9.0

    def test_tag_separates_series(self):
        s = make_store()
        s.process_metric(parse_metric(b"g:1|g|#env:a"))
        s.process_metric(parse_metric(b"g:2|g|#env:b"))
        final, _, _ = s.flush([], DEFAULT_AGGS, is_local=True, now=1)
        assert len(final) == 2


class TestHistograms:
    def test_aggregates_match_exact_values(self):
        s = make_store()
        vals = [1.0, 2.0, 3.0, 4.0, 5.0]
        for v in vals:
            s.process_metric(parse_metric(f"h:{v}|h".encode()))
        final, _, _ = s.flush([], ALL_AGGS, is_local=True, now=1)
        fm = flush_map(final)
        assert fm["h.min"].value == 1.0
        assert fm["h.max"].value == 5.0
        assert fm["h.sum"].value == 15.0
        assert fm["h.avg"].value == 3.0
        assert fm["h.count"].value == 5.0
        assert fm["h.count"].type == MetricType.COUNTER
        hmean = 5.0 / sum(1.0 / v for v in vals)
        assert fm["h.hmean"].value == pytest.approx(hmean, rel=1e-6)

    def test_quantiles_vs_golden_model(self):
        rng = np.random.RandomState(42)
        vals = rng.uniform(0, 100, size=2000)
        s = make_store(chunk=256)
        golden = ScalarTDigest(compression=100.0)
        for v in vals:
            s.process_metric(parse_metric(f"h:{v:.6f}|h".encode()))
            golden.add(float(f"{v:.6f}"))
        final, _, _ = s.flush([0.25, 0.5, 0.9, 0.99], ALL_AGGS,
                              is_local=False, now=1)
        fm = flush_map(final)
        for p, name in ((0.25, "h.25percentile"), (0.5, "h.50percentile"),
                        (0.9, "h.90percentile"), (0.99, "h.99percentile")):
            # eps=0.02 of the value range, the reference's own tolerance
            # (tdigest/histo_test.go:11-25)
            assert abs(fm[name].value - np.quantile(vals, p)) < 2.0, name

    def test_local_instance_suppresses_mixed_percentiles(self):
        s = make_store()
        s.process_metric(parse_metric(b"h:1|h"))
        final, _, _ = s.flush([0.5], DEFAULT_AGGS, is_local=True, now=1)
        assert "h.50percentile" not in flush_map(final)

    def test_local_only_histo_gets_percentiles_even_on_local(self):
        s = make_store()
        s.process_metric(parse_metric(b"h:1|h|#veneurlocalonly"))
        final, fwd, _ = s.flush([0.5], DEFAULT_AGGS, is_local=True, now=1)
        assert "h.50percentile" in flush_map(final)
        assert fwd.histograms == []

    def test_timer_is_histogram(self):
        s = make_store()
        s.process_metric(parse_metric(b"t:5|ms"))
        final, fwd, _ = s.flush([], DEFAULT_AGGS, is_local=True, now=1)
        assert "t.count" in flush_map(final)
        assert len(fwd.timers) == 1

    def test_forward_then_import_preserves_quantiles(self):
        rng = np.random.RandomState(7)
        vals = rng.normal(50, 10, size=3000)
        # two locals each see half the samples
        locals_ = [make_store(chunk=256), make_store(chunk=256)]
        for i, v in enumerate(vals):
            locals_[i % 2].process_metric(parse_metric(f"h:{v:.6f}|h".encode()))
        g = make_store(chunk=256)
        for loc in locals_:
            _, fwd, _ = loc.flush([], DEFAULT_AGGS, is_local=True, now=1)
            for (name, tags, means, weights, dmin, dmax) in fwd.histograms:
                g.import_digest(MetricKey(name, "histogram", ",".join(tags)),
                                tags, means, weights, dmin, dmax)
        final, _, _ = g.flush([0.5, 0.99], ALL_AGGS, is_local=False, now=2)
        fm = flush_map(final)
        assert abs(fm["h.50percentile"].value - np.quantile(vals, 0.5)) < 1.0
        assert abs(fm["h.99percentile"].value - np.quantile(vals, 0.99)) < 2.5
        # imported digests must NOT produce local aggregates
        assert "h.min" not in fm
        assert "h.count" not in fm
        # but median is emitted when selected
        assert "h.median" in fm

    def test_sample_rate_weights(self):
        s = make_store()
        s.process_metric(parse_metric(b"h:10|h|@0.25"))
        final, _, _ = s.flush([], ALL_AGGS, is_local=True, now=1)
        fm = flush_map(final)
        assert fm["h.count"].value == 4.0
        assert fm["h.sum"].value == 40.0


class TestSets:
    def test_estimate_accuracy(self):
        s = make_store(chunk=256)
        n = 5000
        for i in range(n):
            s.process_metric(parse_metric(f"u:user{i}|s".encode()))
        final, _, _ = s.flush([], DEFAULT_AGGS, is_local=False, now=1)
        est = flush_map(final)["u"].value
        assert abs(est - n) / n < 0.05

    def test_duplicates_not_double_counted(self):
        s = make_store()
        for _ in range(100):
            s.process_metric(parse_metric(b"u:same|s"))
        final, _, _ = s.flush([], DEFAULT_AGGS, is_local=False, now=1)
        assert flush_map(final)["u"].value == pytest.approx(1.0, abs=0.01)

    def test_mixed_set_not_flushed_on_local(self):
        s = make_store()
        s.process_metric(parse_metric(b"u:x|s"))
        final, fwd, _ = s.flush([], DEFAULT_AGGS, is_local=True, now=1)
        assert "u" not in flush_map(final)
        assert len(fwd.sets) == 1

    def test_local_set_flushed_on_local(self):
        s = make_store()
        s.process_metric(parse_metric(b"u:x|s|#veneurlocalonly"))
        final, fwd, _ = s.flush([], DEFAULT_AGGS, is_local=True, now=1)
        assert flush_map(final)["u"].value == pytest.approx(1.0, abs=0.01)
        assert fwd.sets == []

    def test_forward_merge_matches_union(self):
        a, b = make_store(chunk=256), make_store(chunk=256)
        for i in range(1000):
            a.process_metric(parse_metric(f"u:x{i}|s".encode()))
        for i in range(500, 1500):
            b.process_metric(parse_metric(f"u:x{i}|s".encode()))
        g = make_store()
        for loc in (a, b):
            _, fwd, _ = loc.flush([], DEFAULT_AGGS, is_local=True, now=1)
            for (name, tags, regs, prec) in fwd.sets:
                g.import_set(MetricKey(name, "set", ",".join(tags)), tags, regs)
        final, _, _ = g.flush([], DEFAULT_AGGS, is_local=False, now=2)
        est = flush_map(final)["u"].value
        assert abs(est - 1500) / 1500 < 0.05


class TestNonDefaultConfig:
    def test_custom_compression_quantiles(self):
        # regression: compression must reach the jitted kernels, or k-binning
        # clips against the wrong capacity and upper quantiles collapse
        rng = np.random.RandomState(3)
        vals = rng.uniform(0, 100, size=2000)
        s = make_store(chunk=256, compression=50.0)
        for v in vals:
            s.process_metric(parse_metric(f"h:{v:.4f}|h".encode()))
        final, _, _ = s.flush([0.9, 0.99], ALL_AGGS, is_local=False, now=1)
        fm = flush_map(final)
        assert abs(fm["h.90percentile"].value - 90.0) < 4.0
        assert abs(fm["h.99percentile"].value - 99.0) < 4.0

    def test_hll_precision_mismatch_rejected(self):
        s = make_store()
        key = MetricKey("u", "set", "")
        with pytest.raises(ValueError, match="precision mismatch"):
            s.import_set(key, [], np.zeros(1 << 10, np.uint8))


class TestStatusChecks:
    def test_flush(self):
        from veneur_tpu.samplers import parse_service_check
        s = make_store()
        s.process_metric(parse_service_check(b"_sc|svc|2|h:host1|m:bad", now=5))
        final, _, _ = s.flush([], DEFAULT_AGGS, is_local=True, now=9)
        m = flush_map(final)["svc"]
        assert m.type == MetricType.STATUS
        assert m.value == 2.0
        assert m.message == "bad"
        assert m.hostname == "host1"


class TestGrowth:
    def test_capacity_growth_preserves_data(self):
        s = MetricStore(initial_capacity=4, chunk=16)
        n = 40
        for i in range(n):
            s.process_metric(parse_metric(f"h{i}:5|h".encode()))
            s.process_metric(parse_metric(f"c{i}:1|c".encode()))
            s.process_metric(parse_metric(f"u{i}:m{i}|s".encode()))
        final, fwd, ms = s.flush([], ALL_AGGS, is_local=False, now=1)
        fm = flush_map(final)
        assert ms.histograms == n and ms.counters == n and ms.sets == n
        for i in range(n):
            assert fm[f"h{i}.max"].value == 5.0
            assert fm[f"c{i}"].value == 1.0
            assert fm[f"u{i}"].value == pytest.approx(1.0, abs=0.01)


class TestRouting:
    def test_veneursinkonly_restricts_sinks(self):
        s = make_store()
        s.process_metric(parse_metric(b"x:1|c|#veneursinkonly:datadog"))
        final, _, _ = s.flush([], DEFAULT_AGGS, is_local=True, now=1)
        m = flush_map(final)["x"]
        assert m.sinks == frozenset({"datadog"})
        assert m.is_acceptable_to("datadog")
        assert not m.is_acceptable_to("kafka")


class TestSwapOnFlush:
    """The store lock is held only for the generation swap; the device
    programs and fetches run on the retired generation off-lock, so
    ingest never stalls behind a multi-second flush (the reference's
    design point: worker.go:402-429, flusher.go:134-184)."""

    def test_ingest_not_blocked_by_slow_flush(self, monkeypatch):
        import threading
        import time as _t

        s = make_store()
        for v in range(100):
            s.process_metric(parse_metric(f"lat:{v}|ms".encode()))

        started, release = threading.Event(), threading.Event()
        orig = MetricStore._flush_generation

        def slow(self, gen, *a, **k):
            started.set()
            release.wait(10)  # a long device flush, off-lock
            return orig(self, gen, *a, **k)

        monkeypatch.setattr(MetricStore, "_flush_generation", slow)
        result = {}

        def run():
            result["flush"] = s.flush([0.5], ALL_AGGS, is_local=False,
                                      now=1)

        t = threading.Thread(target=run, daemon=True)
        t.start()
        assert started.wait(5)
        # ingest during the flush: must return immediately, not after
        # the 10 s "device program"
        t0 = _t.perf_counter()
        for v in range(50):
            s.process_metric(parse_metric(f"lat:{100 + v}|ms".encode()))
        s.process_metric(parse_metric(b"c:1|c"))
        ingest_s = _t.perf_counter() - t0
        release.set()
        t.join(timeout=30)
        assert ingest_s < 1.0, f"ingest stalled {ingest_s:.1f}s behind flush"
        # interval isolation: the slow flush carries ONLY pre-swap data...
        final, _, ms = result["flush"]
        m = flush_map(final)
        assert m["lat.count"].value == 100
        assert ms.processed == 100
        # ...and the next flush carries exactly the mid-flush ingest
        final2, _, ms2 = s.flush([0.5], ALL_AGGS, is_local=False, now=2)
        m2 = flush_map(final2)
        assert m2["lat.count"].value == 50
        assert m2["c"].value == 1
        assert ms2.processed == 51

    def test_concurrent_ingest_conserves_counts(self):
        import threading

        s = make_store(digest_storage="slab", slab_rows=1 << 10)
        stop = threading.Event()
        sent = [0]

        def pump():
            i = 0
            while not stop.is_set():
                s.process_metric(
                    parse_metric(f"h:{i % 97}|h".encode()))
                s.process_metric(b_ctr)
                sent[0] += 2
                i += 1

        b_ctr = parse_metric(b"total:1|c")
        t = threading.Thread(target=pump, daemon=True)
        t.start()
        totals = {"h.count": 0.0, "total": 0.0}
        try:
            for it in range(4):
                final, _, _ = s.flush([], ALL_AGGS, is_local=False,
                                      now=it)
                for mname in list(totals):
                    mm = flush_map(final).get(mname)
                    if mm is not None:
                        totals[mname] += mm.value
        finally:
            stop.set()
            t.join(timeout=10)
        # drain the tail after the pump stops
        final, _, _ = s.flush([], ALL_AGGS, is_local=False, now=99)
        for mname in list(totals):
            mm = flush_map(final).get(mname)
            if mm is not None:
                totals[mname] += mm.value
        assert sent[0] > 0
        # every sample landed in exactly one interval: no loss, no dupes
        assert totals["total"] == sent[0] / 2
        assert totals["h.count"] == sent[0] / 2


class TestFlushLiveRows:
    """The dense flush program is told the interval's interned rows:
    it works on the slabs that hold them (ops/tdigest.py
    drain_and_quantile) and every answer is the full-width program's."""

    SLAB = tdigest_pallas._FLUSH_SLAB_ROWS
    CAPACITY = 4 * SLAB

    @staticmethod
    def _group(n, capacity=CAPACITY, seed=0):
        from veneur_tpu.core.store import DigestGroup

        g = DigestGroup(capacity=capacity, chunk=4096)
        rows = np.asarray(
            [g._row(MetricKey(name=f"h{i}", type="histogram",
                              joined_tags=""), []) for i in range(n)],
            np.int32)
        rng = np.random.default_rng(seed)
        for _ in range(2):  # a sample a row, then a few rows many
            g.sample_many(rows, rng.lognormal(0, 1, n).astype(np.float32),
                          np.ones(n, np.float32))
            rows = rows[rng.integers(0, n, 3 * n)]
            n = len(rows)
        return g

    @pytest.mark.parametrize(
        "n", [1, SLAB - 1, SLAB, SLAB + 1, 2 * SLAB + 5, CAPACITY])
    def test_flush_equals_the_full_width_program(self, n):
        import jax.numpy as jnp

        from veneur_tpu.core import store as store_mod

        g = self._group(n)
        g._drain_staging()
        state = [jnp.copy(x) for x in
                 (*g.digest, *g.temp, g.dmin, g.dmax)]
        digest = type(g.digest)(*state[:4])
        temp = type(g.temp)(*state[4:-2])
        qs = jnp.asarray([0.5, 0.99, 0.5], jnp.float32)
        full, pcts, *stats = store_mod._flush_digests(
            digest, temp, state[-2], state[-1], qs, None, g.compression,
            False)
        _interner, out = g.flush([0.5, 0.99])
        want = {"digest_mean": full.mean, "digest_weight": full.weight,
                "digest_min": full.min, "digest_max": full.max,
                "percentiles": pcts[:, :-1], "median": pcts[:, -1],
                **dict(zip(("count", "sum", "min", "max", "recip"),
                           stats))}
        for key, ref in want.items():
            np.testing.assert_array_equal(out[key], np.asarray(ref)[:n],
                                          err_msg=key)

    def test_no_rows_takes_the_empty_path(self, monkeypatch):
        from veneur_tpu.core import store as store_mod

        def never(*args):
            raise AssertionError("an empty group ran the flush program")

        monkeypatch.setattr(store_mod, "_flush_digests", never)
        assert self._group(0).flush([0.5])[1] == {}
        assert self._group(0).flush_begin([0.5])()[1] == {}

    def test_one_compiled_variant_for_every_count(self):
        from veneur_tpu.obs import kernels as obs_kernels

        program = "veneur_tpu/core/store.py::_flush_digests"
        capacity = 3 * self.SLAB  # a shape no other test flushes
        before = obs_kernels.compile_snapshot()[program]
        for n in (3, self.SLAB + 9, capacity):
            _interner, out = self._group(n, capacity).flush([0.5])
            assert len(out["count"]) == n
        assert obs_kernels.compile_snapshot()[program] == before + 1

    @pytest.mark.parametrize("capacity,n,run", [
        (64, 5, 64), (SLAB, 5, SLAB), (CAPACITY, 5, SLAB),
        (CAPACITY, SLAB + 1, 2 * SLAB), (CAPACITY, CAPACITY, CAPACITY)])
    def test_rows_run_on_the_stage(self, capacity, n, run):
        """A group of at most one slab is the straight-line program
        over all its rows; a larger one runs the live rows' slabs."""
        from veneur_tpu.obs import recorder as obs_rec

        g = self._group(n, capacity)
        rec = obs_rec.StageRecorder()
        with obs_rec.activate(rec), rec.stage("store"):
            g.flush_begin([0.5])()
        stage = next(s for s in rec.finish()["stages"]
                     if s["name"].endswith("compute"))
        assert (stage["rows_live"], stage["rows_run"]) == (n, run)


class TestFlushCompilesNothing:
    """Three flushes with three different live-row counts compile no
    program after the first: what comes off the device is the count's
    pow2 bucket of rows, cut to the count on the host. Sliced at the
    count itself every new count compiled a program a shape inside the
    flush (two a flush under churning names: PERF.md, PR 39)."""

    COUNTS = (700, 650, 900)    # one bucket, three counts

    @staticmethod
    def _compiles():
        from veneur_tpu.obs import kernels as obs_kernels

        return obs_kernels._compile["programs"]

    @staticmethod
    def _keys(kind, n):
        return [MetricKey(name=f"{kind}{i}", type=kind, joined_tags="")
                for i in range(n)]

    def _flushes(self, group, fill, flush, rows_of):
        seen = []
        for n in self.COUNTS:
            fill(group, n)
            before = self._compiles()
            out = flush(group)
            seen.append(self._compiles() - before)
            assert rows_of(out) == n
        assert seen[1:] == [0, 0], seen

    def test_digest_group(self):
        from veneur_tpu.core.store import DigestGroup

        def fill(g, n):
            rows = np.asarray([g._row(k, []) for k in
                               self._keys("histogram", n)], np.int32)
            g.sample_many(rows, np.arange(n, dtype=np.float32),
                          np.ones(n, np.float32))

        def flush(g):
            snap = g.snapshot_state()       # the checkpoint's slices too
            assert len(snap["count"]) == len(g)
            return g.flush_begin([0.5, 0.99])()

        self._flushes(DigestGroup(capacity=4096, chunk=1024), fill, flush,
                      lambda out: len(out[1]["percentiles"]))

    def test_digest_group_answers_are_the_rows_own(self):
        """Cut on the host, every row still reads its own sample."""
        from veneur_tpu.core.store import DigestGroup

        g = DigestGroup(capacity=4096, chunk=1024)
        n = 700
        rows = np.asarray([g._row(k, []) for k in
                           self._keys("histogram", n)], np.int32)
        g.sample_many(rows, np.arange(n, dtype=np.float32) + 0.25,
                      np.ones(n, np.float32))
        _interner, out = g.flush([0.5])
        for key in ("min", "max", "median"):
            np.testing.assert_array_equal(
                out[key], np.arange(n, dtype=np.float32) + 0.25, key)
        assert out["percentiles"].shape == (n, 1)
        assert out["digest_mean"].shape[0] == n

    def test_set_group(self):
        from veneur_tpu.core.store import SetGroup

        def fill(g, n):
            for i, k in enumerate(self._keys("set", n)):
                g.sample(k, [], f"m{i}")

        def flush(g):
            assert len(g.snapshot_state()["registers"]) == len(g)
            return g.flush_begin()()

        self._flushes(SetGroup(capacity=4096, chunk=1024, precision=10),
                      fill, flush, lambda out: len(out[1]))

    def test_heavy_hitter_group(self):
        from veneur_tpu.core.store import HeavyHitterGroup

        def fill(g, n):
            for i, k in enumerate(self._keys("set", n)):
                g.sample(k, [], f"m{i % 7}")

        def flush(g):
            assert len(g.snapshot_state()["series"]) == len(g)
            return g.flush_begin()()

        self._flushes(
            HeavyHitterGroup(capacity=4096, chunk=1024, width=1 << 12, k=4),
            fill, flush, lambda out: len({row for row, _m, _c in out[1]}))
