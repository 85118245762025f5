"""Overload-safe hot path (ISSUE 4): admission watermarks, bounded
cardinality with overflow-row spill, the numerics quarantine ledger, and
the flush-kernel compute breaker's fallback ladder.

The two acceptance scenarios:

* a seeded burst at 10x ``max_series`` with 5% NaN/Inf poison keeps the
  process alive with bounded memory, flush keeps running, and the
  accounting balances: ingested == aggregated + spilled + shed +
  quarantined;
* a forced Pallas-merge failure trips the compute breaker, the SAME
  interval completes on the XLA fallback (equivalent output), and the
  breaker recovers half-open -> closed once injection stops — composing
  with PR 2's checkpoints (no regression in snapshot/restore).
"""

import queue
import types

import numpy as np
import pytest

import veneur_tpu.core.store as store_mod
from veneur_tpu.core.store import MetricStore
from veneur_tpu.overload import (LEVEL_NORMAL, LEVEL_SHED_NEW_SERIES,
                                 LEVEL_SHED_PACKETS, LEVEL_SHED_SPANS,
                                 OverloadController, Quarantine)
from veneur_tpu.resilience.compute import ComputeBreaker
from veneur_tpu.samplers.intermetric import HistogramAggregates
from veneur_tpu.samplers.parser import QuarantineError, parse_metric

AGG = HistogramAggregates.from_names(["min", "max", "count"])


def _flush(store, now=1):
    return store.flush([0.5], AGG, is_local=False, now=now)


class _PressureHarness:
    """A fake just-enough server for OverloadController.attach: one
    bounded span channel and the store's group occupancy as pressure
    sources."""

    def __init__(self, store, chan_cap=10):
        self.store = store
        self.span_chan = queue.Queue(chan_cap)
        self._span_workers = []


class TestOverloadController:
    def _ctl(self, fake_clock, **kw):
        return OverloadController(clock=fake_clock,
                                  recompute_interval=0.0, **kw)

    def test_levels_follow_watermarks(self, fake_clock):
        store = MetricStore(max_series=10)
        harness = _PressureHarness(store)
        ctl = self._ctl(fake_clock).attach(harness)
        assert ctl.level() == LEVEL_NORMAL
        for i in range(8):  # 8/10 occupancy in one group
            store.process_metric(parse_metric(b"s%d:1|c" % i))
        fake_clock.advance(1)
        assert ctl.level() == LEVEL_SHED_NEW_SERIES
        for _ in range(9):  # span channel 9/10
            harness.span_chan.put_nowait(object())
        fake_clock.advance(1)
        assert ctl.level() == LEVEL_SHED_SPANS
        harness.span_chan.put_nowait(object())  # 10/10 >= hard
        fake_clock.advance(1)
        assert ctl.level() == LEVEL_SHED_PACKETS

    def test_admission_priorities(self, fake_clock):
        """Spans shed before statsd datagrams; every drop accounted."""
        store = MetricStore(max_series=100)
        harness = _PressureHarness(store)
        ctl = self._ctl(fake_clock).attach(harness)
        for _ in range(9):
            harness.span_chan.put_nowait(object())
        fake_clock.advance(1)
        assert ctl.level() == LEVEL_SHED_SPANS
        assert not ctl.admit_span()
        assert not ctl.admit_packet("ssf")
        assert ctl.admit_packet("statsd")  # aggregates still flow
        harness.span_chan.put_nowait(object())
        fake_clock.advance(1)
        assert ctl.level() == LEVEL_SHED_PACKETS
        assert not ctl.admit_packet("statsd")
        assert ctl.shed == {"statsd": 1, "ssf": 1, "spans": 1}
        assert ctl.shed_total() == 3

    def test_freeze_spills_new_series_not_existing(self, fake_clock):
        store = MetricStore(max_series=1000)
        harness = _PressureHarness(store)
        ctl = self._ctl(fake_clock).attach(harness)
        store.set_overload(ctl)
        store.process_metric(parse_metric(b"known:1|c"))
        for _ in range(8):
            harness.span_chan.put_nowait(object())
        fake_clock.advance(1)
        assert ctl.freeze_new_series()
        store.process_metric(parse_metric(b"known:1|c"))   # existing: ok
        store.process_metric(parse_metric(b"fresh:1|c"))   # new: spills
        # self-metrics are exempt from the freeze
        store.process_metric(parse_metric(b"veneur.something:1|c"))
        names = set(store.counters.interner.names)
        assert "known" in names and "veneur.something" in names
        assert "fresh" not in names
        assert "veneur.overload.overflow" in names
        assert store.counters.spilled == 1

    def test_bad_watermark_order_rejected(self, fake_clock):
        with pytest.raises(ValueError):
            OverloadController(low=0.9, high=0.8, clock=fake_clock)


class TestBoundedCardinality:
    def test_burst_accounting_balances(self, fake_clock):
        """THE acceptance scenario: 10x max_series burst, 5% poison,
        a mid-burst admission brown-out — alive, bounded, balanced."""
        max_series = 32
        store = MetricStore(max_series=max_series)
        harness = _PressureHarness(store, chan_cap=10)
        ctl = OverloadController(clock=fake_clock,
                                 recompute_interval=0.0).attach(harness)
        store.set_overload(ctl)

        rng = np.random.default_rng(1234)
        lines = []
        for i in range(10 * max_series):
            lines.append(b"series%04d:2|c" % i)
            if rng.random() < 0.05:
                lines.append(b"poison:nan|h" if rng.random() < 0.5
                             else b"poison:1e308|h")
        ingested = len(lines)
        shed = quarantined = reached_store = 0
        for j, line in enumerate(lines):
            if j == 200:  # the span channel floods mid-burst
                for _ in range(10):
                    harness.span_chan.put_nowait(object())
                fake_clock.advance(1)
            if j == 260:  # ...and drains again
                while not harness.span_chan.empty():
                    harness.span_chan.get_nowait()
                fake_clock.advance(1)
            if not ctl.admit_packet("statsd"):
                shed += 1
                continue
            try:
                store.process_metric(parse_metric(
                    line, quarantine=store.quarantine))
                reached_store += 1
            except QuarantineError as e:
                store.quarantine.count(e.reason)
                quarantined += 1

        # memory bounded: NO group past the cap, before and after flush
        for name in MetricStore._GEN_GROUPS:
            assert len(getattr(store, name)) <= max_series
        spilled = store.counters.spilled
        assert spilled > 0 and shed > 0 and quarantined > 0
        assert quarantined == store.quarantine.total()
        # the ledger balances exactly
        assert ingested == reached_store + shed + quarantined
        assert store.processed == reached_store
        aggregated = reached_store - spilled

        final, _, ms = _flush(store)
        assert ms.spilled["counters"] == spilled
        counters = {m.name: m.value for m in final
                    if m.name != "poison.count" and "percentile" not in
                    m.name and not m.name.startswith("poison.")}
        overflow = counters.pop("veneur.overload.overflow")
        # counts preserved: the overflow row absorbed every spilled
        # sample's contribution (value 2 each), real rows the rest
        assert overflow == 2.0 * spilled
        assert sum(counters.values()) == 2.0 * aggregated
        # flush keeps running, and the fresh twins keep the cap
        _flush(store, now=2)
        for i in range(10 * max_series):
            store.process_metric(parse_metric(b"other%04d:1|c" % i))
        assert len(store.counters) <= max_series

    def test_cap_includes_overflow_row(self):
        store = MetricStore(max_series=4)
        for i in range(50):
            store.process_metric(parse_metric(b"h%02d:%d|h" % (i, i)))
        assert len(store.histograms) == 4  # 3 real + overflow
        assert store.histograms.spilled == 47

    def test_direct_group_construction_is_unbounded(self):
        # tests/benches building groups directly see the old behavior
        from veneur_tpu.core.store import ScalarGroup
        from veneur_tpu.samplers.parser import MetricKey

        g = ScalarGroup("counter")
        for i in range(5000):
            g.sample(MetricKey(name=f"s{i}", type="counter"), [], 1, 1.0)
        assert len(g) == 5000 and g.spilled == 0

    def test_oversized_tags_truncate_at_store_boundary(self):
        store = MetricStore(max_tag_length=32)
        joined = ",".join(f"t{i}:{'v' * 10}" for i in range(50))
        t, _, _ = store._intern_native(
            0, 0, b"name", joined.encode())
        assert store.quarantine.snapshot()["oversized_tags"] == 1
        assert all(len(j) <= 32 for j in store.counters.interner.joined)

    def test_ssf_tag_bomb_capped_at_process_metric(self):
        # the SSF lanes skip the DogStatsD parser's cap; process_metric
        # is the choke point every lane shares
        from veneur_tpu.protocol import ssf_pb2
        from veneur_tpu.samplers.parser import parse_metric_ssf

        store = MetricStore(max_tag_length=64)
        sample = ssf_pb2.SSFSample(
            metric=ssf_pb2.SSFSample.COUNTER, name="bomb", value=1.0,
            sample_rate=1.0)
        for i in range(40):
            sample.tags[f"tag{i:03d}"] = "v" * 30
        store.process_metric(parse_metric_ssf(sample))
        assert store.quarantine.snapshot()["oversized_tags"] == 1
        assert all(len(j) <= 64 for j in store.counters.interner.joined)


class TestComputeLadder:
    def _poisoned_store(self, fake_clock, threshold=2):
        store = MetricStore(compute=ComputeBreaker(
            failure_threshold=threshold, reset_timeout=30.0,
            clock=fake_clock))
        return store

    def _ingest(self, store, n=64):
        rng = np.random.default_rng(7)
        for v in rng.normal(100.0, 15.0, n):
            store.process_metric(parse_metric(b"lat:%f|h" % v))

    def _arm(self, monkeypatch, fail_on=lambda use_pallas: use_pallas):
        orig = store_mod._flush_digests
        calls = []

        def raiser(*args):
            calls.append(args[-1])
            if fail_on(args[-1]):
                raise RuntimeError("injected kernel failure")
            return orig(*args)

        monkeypatch.setattr(store_mod, "_flush_digests", raiser)
        return calls

    def test_same_interval_completes_on_fallback(self, fake_clock,
                                                 monkeypatch):
        store = self._poisoned_store(fake_clock)
        clean = MetricStore()
        self._ingest(store)
        self._ingest(clean)
        want, _, _ = _flush(clean)
        want_by = {m.name: m.value for m in want}

        calls = self._arm(monkeypatch)
        got, _, _ = _flush(store)
        got_by = {m.name: m.value for m in got}
        # rung 1 attempted with the kernel, rung 2 without
        assert calls == [True, False]
        # the SAME interval emitted, equivalent within digest tolerance
        assert set(got_by) == set(want_by)
        for name, val in want_by.items():
            assert got_by[name] == pytest.approx(val, rel=1e-5)
        assert store.compute.fallback_total == 1
        assert not store.compute.degraded()  # threshold is 2

    def test_breaker_opens_then_recovers(self, fake_clock, monkeypatch):
        store = self._poisoned_store(fake_clock)
        calls = self._arm(monkeypatch)
        for now in (1, 2):
            self._ingest(store, 16)
            final, _, _ = _flush(store, now)
            assert any(m.name == "lat.count" for m in final)
        assert store.compute.degraded()  # 2 consecutive failures: open
        # open breaker: rung 1 never dispatched, straight to fallback
        before = len(calls)
        self._ingest(store, 16)
        _flush(store, 3)
        assert calls[before:] == [False]
        assert store.compute.fallback_total == 3
        # injection stops + reset timeout elapses: half-open probe
        # succeeds and the breaker closes
        monkeypatch.undo()
        fake_clock.advance(60.0)
        self._ingest(store, 16)
        final, _, _ = _flush(store, 4)
        assert any(m.name == "lat.count" for m in final)
        assert not store.compute.degraded()

    def test_rung3_requeues_interval_late_not_lost(self, fake_clock,
                                                   monkeypatch):
        store = self._poisoned_store(fake_clock, threshold=1)
        self._ingest(store, 32)
        self._arm(monkeypatch, fail_on=lambda use_pallas: True)
        final, _, _ = _flush(store, 1)
        # this interval's histograms did NOT emit...
        assert not any(m.name.startswith("lat.") for m in final)
        assert store.compute.requeued_total == 1
        assert store.compute.lost_total == 0
        # ...but the data re-merged into the live store: next flush
        # (injection over) emits it late with full fidelity
        monkeypatch.undo()
        fake_clock.advance(60.0)
        final, _, _ = _flush(store, 2)
        by = {m.name: m.value for m in final}
        assert by["lat.count"] == 32.0

    def test_checkpoint_composes_mid_degradation(self, fake_clock,
                                                 monkeypatch):
        """No checkpoint regression: snapshot/restore still round-trips
        while the breaker is open and flushes run on the fallback."""
        store = self._poisoned_store(fake_clock, threshold=1)
        self._arm(monkeypatch)
        self._ingest(store, 16)
        _flush(store, 1)  # trips the breaker (threshold 1)
        assert store.compute.degraded()
        self._ingest(store, 16)
        groups, epoch = store.snapshot_state()
        other = MetricStore()
        assert other.restore_state(groups) > 0
        final, _, _ = _flush(other, 2)
        by = {m.name: m.value for m in final}
        assert by["lat.count"] == 16.0

    def test_ingest_drains_avoid_kernel_while_degraded(self, fake_clock):
        store = self._poisoned_store(fake_clock, threshold=1)
        store.compute.record_failure()
        assert store.compute.degraded()
        assert store.histograms._pallas_allowed() is False
        # staging and flushing still work on the fallback path
        self._ingest(store, 2 * store.histograms.chunk // 16)
        final, _, _ = _flush(store, 1)
        assert any(m.name == "lat.count" for m in final)


def test_rung_reads_xla_on_the_cpu():
    """``rung`` says ``pallas`` only when the kernel was admitted into
    the program; off the TPU the same dispatch is the XLA program."""
    import time

    from veneur_tpu.obs import recorder as obs_rec

    store = MetricStore(initial_capacity=32, chunk=128)
    assert store.compute.snapshot()["last_rung"] is None
    for v in (1.0, 2.0, 3.0):
        store.process_metric(parse_metric(b"lat:%f|h" % v))
    rec = obs_rec.StageRecorder()
    with obs_rec.activate(rec), rec.stage("store.histograms"):
        store.flush([0.5], HistogramAggregates.from_names(["count"]),
                    is_local=False, now=int(time.time()))
    assert store.compute.snapshot()["last_rung"] == "xla"
    assert store.compute.fallback_total == 0


class TestOverloadSamples:
    def test_emitted_names_and_deltas(self, fake_clock):
        from veneur_tpu import flusher

        store = MetricStore(max_series=4)
        harness = _PressureHarness(store)
        ctl = OverloadController(clock=fake_clock,
                                 recompute_interval=0.0).attach(harness)
        store.set_overload(ctl)
        ctl.shed["statsd"] = 7
        store.quarantine.count("not_finite", 3)
        for i in range(9):
            store.process_metric(parse_metric(b"x%d:1|c" % i))
        store.compute.count_fallback()
        store.compute.probe()  # materialize the kernel breaker
        server = types.SimpleNamespace(overload=ctl, store=store)
        _, _, ms = _flush(store)
        samples = flusher._overload_samples(server, ms)
        by = {}
        for s in samples:
            by.setdefault(s.name, []).append(s)
        assert "veneur.overload.level" in by
        assert by["veneur.overload.quarantined_total"][0].name
        sheds = {tuple(sorted(s.tags.items())): s.value
                 for s in by["veneur.overload.shed_total"]}
        assert sheds[(("lane", "statsd"),)] == 7.0
        spills = by["veneur.overload.samples_spilled_total"]
        assert any(s.value == 6.0 for s in spills)  # 9 - 3 real rows
        assert "veneur.overload.compute_fallback_total" in by
        assert "veneur.overload.compute_requeued_total" in by
        assert "veneur.breaker.state" in by
        # second interval: counter deltas reset
        _, _, ms2 = _flush(store, now=2)
        samples2 = flusher._overload_samples(server, ms2)
        q2 = [s for s in samples2
              if s.name == "veneur.overload.quarantined_total"]
        assert all(s.value == 0.0 for s in q2)

    def test_span_lane_depth_gauges(self):
        import threading

        from veneur_tpu import flusher
        from veneur_tpu.server import _SinkIngestor

        class _Sink:
            name = "stub"

            def ingest(self, span):
                pass

        lane = _SinkIngestor(_Sink(), threading.Event())
        for _ in range(5):
            lane.offer(object())
        assert lane.depth_hwm >= 1
        server = types.SimpleNamespace(
            _span_workers=[types.SimpleNamespace(_lanes=[lane])],
            packet_errors=0, packet_drops=0, spans_dropped=0)
        ms = types.SimpleNamespace(
            processed=0, imported=0, counters=0, gauges=0, histograms=0,
            sets=0, timers=0)
        samples = flusher._worker_samples(server, ms)
        names = [s.name for s in samples]
        assert "veneur.server.span_lane.depth" in names
        assert "veneur.server.span_lane.depth_hwm" in names
        # hwm is read-and-reset per interval
        assert lane.depth_hwm == 0


class TestIngestFaults:
    def test_seeded_mangle_is_deterministic(self):
        from veneur_tpu.resilience.faults import FaultInjector

        def run():
            inj = FaultInjector(rate=0.5, seed=99,
                                kinds=("truncate", "burst"))
            return [inj.mangle_packet("ingest.statsd", b"abc:1|c\n" * 4)
                    for _ in range(50)]

        a, b = run(), run()
        assert a == b
        lens = {len(outs) for outs in a}
        assert max(lens) > 1          # bursts amplified
        assert any(len(outs[0]) < 32 for outs in a)  # truncations cut

    def test_mangled_stream_never_crashes_the_pipeline(self):
        from veneur_tpu.resilience.faults import FaultInjector

        inj = FaultInjector(rate=0.6, seed=5,
                            kinds=("truncate", "burst"))
        store = MetricStore()
        from veneur_tpu.samplers.parser import ParseError, split_lines

        ingested = errors = 0
        for i in range(200):
            datagram = b"m%d:5|ms|@0.5|#a:b\n" % (i % 10)
            for out in inj.mangle_packet("ingest.statsd", datagram):
                for line in split_lines(out):
                    try:
                        store.process_metric(parse_metric(line))
                        ingested += 1
                    except ParseError:
                        errors += 1
        assert ingested > 200  # bursts got through
        final, _, _ = _flush(store)
        assert any(m.name.endswith(".count") for m in final)

    def test_transport_schedules_unperturbed(self):
        # adding the ingest kinds must NOT change existing seeded
        # transport schedules (soak reproducibility)
        from veneur_tpu.resilience.faults import ALL_KINDS, FaultInjector

        assert ALL_KINDS == ("connect", "timeout", "http_5xx",
                             "partial_write")
        inj = FaultInjector(rate=1.0, seed=3)
        assert all(k in ALL_KINDS for k in inj.schedule(16))


class TestLogLimiter:
    def test_one_warning_per_interval_with_suppressed_count(self,
                                                            fake_clock):
        from veneur_tpu.networking import _LogLimiter

        lim = _LogLimiter(interval=10.0, clock=fake_clock)
        for _ in range(25):
            lim.warn("recv error: %s", "boom")
        assert lim.emitted == 1 and lim.suppressed == 24
        fake_clock.advance(10.0)
        lim.warn("recv error: %s", "boom")
        assert lim.emitted == 2 and lim.suppressed == 0


class TestConfigKeys:
    def _cfg(self, **kw):
        from veneur_tpu.config import Config

        cfg = Config(**kw)
        cfg.apply_defaults()
        cfg.validate()
        return cfg

    def test_defaults_applied(self):
        cfg = self._cfg()
        assert cfg.max_series == 1 << 20
        assert cfg.max_tag_length == 1024
        assert cfg.overload_low_watermark == 0.7
        assert cfg.overload_high_watermark == 0.85
        assert cfg.overload_hard_watermark == 0.97
        assert cfg.compute_breaker_failure_threshold == 2
        assert cfg.compute_breaker_reset_timeout_seconds == 60.0

    @pytest.mark.parametrize("kw", [
        {"max_series": -1},
        {"max_tag_length": -5},
        {"compute_breaker_failure_threshold": -1},
        {"overload_low_watermark": 0.9, "overload_high_watermark": 0.8},
        {"overload_hard_watermark": 1.5},
    ])
    def test_invalid_rejected(self, kw):
        with pytest.raises(ValueError):
            self._cfg(**kw)

    def test_ingest_fault_kinds_accepted(self):
        cfg = self._cfg(fault_injection_kinds="truncate,burst",
                        fault_injection_rate=0.1)
        assert cfg.fault_injection_kinds == "truncate,burst"


class TestDebugAndReadiness:
    def test_debug_vars_expose_overload_state(self, fake_clock):
        from veneur_tpu import debug

        store = MetricStore(max_series=4)
        harness = _PressureHarness(store)
        ctl = OverloadController(clock=fake_clock,
                                 recompute_interval=0.0).attach(harness)
        store.set_overload(ctl)
        store.quarantine.count("bad_rate", 2)
        for i in range(9):
            store.process_metric(parse_metric(b"x%d:1|c" % i))
        server = types.SimpleNamespace(
            store=store, overload=ctl, packet_errors=0, packet_drops=0)
        out = debug.collect_vars(server)
        ov = out["overload"]
        # the counters group sits at its cap: cardinality pressure puts
        # the ladder at the freeze tier (and never higher — see
        # OverloadController._compute_pressure)
        assert ov["level"] == LEVEL_SHED_NEW_SERIES
        assert ov["quarantined"]["bad_rate"] == 2
        assert ov["spilled_this_interval"]["counters"] == 6
        assert ov["max_series"] == 4
        assert "compute" in ov

    def test_quarantine_ledger_threadsafe_shape(self):
        q = Quarantine()
        q.count("not_finite")
        q.count("custom_reason", 5)
        snap = q.snapshot()
        assert snap["not_finite"] == 1 and snap["custom_reason"] == 5
        assert q.total() == 6
