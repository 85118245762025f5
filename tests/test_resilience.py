"""The unified egress resilience layer (veneur_tpu/resilience/):
retry/backoff under a flush deadline, circuit breakers, deterministic
fault injection — unit tests against the fake clock, plus wired-in
coverage over the HTTP forwarder, the Datadog sink, the Kafka sink's
``kafka_retry_max``, and the proxy's per-destination breakers
(ISSUE 1 acceptance: 30% fault injection over 20 intervals delivers
every interval; a black-holed destination's breaker opens within the
threshold and flush wall-time stays bounded)."""

import json
import random
import socket
import threading
import time
import zlib
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from veneur_tpu import flusher
from veneur_tpu.config import Config, ProxyConfig
from veneur_tpu.resilience import (BreakerOpen, BreakerRegistry,
                                   CircuitBreaker, Deadline, FaultInjector,
                                   RetryPolicy, call_with_retry,
                                   post_with_retry)
from veneur_tpu.resilience.breaker import CLOSED, HALF_OPEN, OPEN
from veneur_tpu.resilience.faults import INJECTED_STATUS
from veneur_tpu.samplers.intermetric import InterMetric, MetricType


class _MaxJitter:
    """Deterministic rng: backoff always draws the cap."""

    def uniform(self, lo, hi):
        return hi


# ---------------------------------------------------------------------------
# deadline


class TestDeadline:
    def test_remaining_and_expiry(self, fake_clock):
        d = Deadline.after(2.0, clock=fake_clock)
        assert d.remaining() == pytest.approx(2.0)
        assert not d.expired()
        fake_clock.advance(1.5)
        assert d.remaining() == pytest.approx(0.5)
        fake_clock.advance(1.0)
        assert d.expired() and d.remaining() == 0.0

    def test_clamp_bounds_attempt_timeouts(self, fake_clock):
        d = Deadline.after(2.0, clock=fake_clock)
        assert d.clamp(10.0) == pytest.approx(2.0)
        assert d.clamp(0.5) == pytest.approx(0.5)
        fake_clock.advance(5.0)
        # expired clamps to a small positive floor, never 0/negative
        assert d.clamp(10.0) > 0.0

    def test_unbounded(self):
        d = Deadline.unbounded()
        assert d.remaining() == float("inf") and not d.expired()


# ---------------------------------------------------------------------------
# retry


class TestRetry:
    def test_succeeds_after_transient_failures(self, fake_clock):
        calls = []

        def fn():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("transient")
            return "ok"

        retries = []
        result = call_with_retry(
            fn, RetryPolicy(max_attempts=5, base_interval=0.1),
            on_retry=lambda i, e, p: retries.append(p),
            rng=_MaxJitter(), sleep=fake_clock.sleep)
        assert result == "ok" and len(calls) == 3
        # exponential: cap doubles per retry (full jitter drew the cap)
        assert fake_clock.sleeps == [0.1, 0.2]
        assert len(retries) == 2

    def test_budget_exhausted_reraises(self, fake_clock):
        calls = []

        def fn():
            calls.append(1)
            raise OSError("down")

        with pytest.raises(OSError):
            call_with_retry(fn, RetryPolicy(max_attempts=3,
                                            base_interval=0.01),
                            rng=_MaxJitter(), sleep=fake_clock.sleep)
        assert len(calls) == 3

    def test_non_retryable_raises_immediately(self, fake_clock):
        calls = []

        def fn():
            calls.append(1)
            raise ValueError("bug, not weather")

        with pytest.raises(ValueError):
            call_with_retry(fn, RetryPolicy(max_attempts=5),
                            sleep=fake_clock.sleep)
        assert len(calls) == 1 and fake_clock.sleeps == []

    def test_retry_if_filter(self, fake_clock):
        calls = []

        def fn():
            calls.append(1)
            raise OSError("permission denied")

        with pytest.raises(OSError):
            call_with_retry(fn, RetryPolicy(max_attempts=5),
                            retry_if=lambda e: "transient" in str(e),
                            sleep=fake_clock.sleep)
        assert len(calls) == 1

    def test_deadline_expiry_mid_retry(self, fake_clock):
        """The attempt budget says 10; the deadline stops it first, and
        total sleep never exceeds the budget."""
        calls = []

        def fn():
            calls.append(1)
            raise OSError("down")

        deadline = Deadline.after(1.0, clock=fake_clock)
        with pytest.raises(OSError):
            call_with_retry(
                fn, RetryPolicy(max_attempts=10, base_interval=0.5,
                                max_interval=0.5),
                deadline=deadline, rng=_MaxJitter(),
                sleep=fake_clock.sleep)
        assert len(calls) == 2  # stopped by the deadline, not the budget
        assert sum(fake_clock.sleeps) == pytest.approx(1.0)

    def test_backoff_schedule_is_seeded_deterministic(self):
        p = RetryPolicy(max_attempts=8, base_interval=0.1, max_interval=2.0)
        a = [p.backoff(i, random.Random(42)) for i in range(6)]
        b = [p.backoff(i, random.Random(42)) for i in range(6)]
        assert a == b
        # full jitter stays within [0, min(cap, base * 2^n)]
        for i, v in enumerate(a):
            assert 0.0 <= v <= min(2.0, 0.1 * 2 ** i)

    def test_post_with_retry_retries_5xx_then_returns_final(self, fake_clock):
        statuses = [503, 500, 202]

        result = post_with_retry(
            lambda: statuses.pop(0),
            RetryPolicy(max_attempts=5, base_interval=0.01),
            rng=_MaxJitter(), sleep=fake_clock.sleep)
        assert result == 202 and len(fake_clock.sleeps) == 2

    def test_post_with_retry_does_not_retry_4xx(self, fake_clock):
        statuses = [400, 202]
        assert post_with_retry(
            lambda: statuses.pop(0), RetryPolicy(max_attempts=5),
            sleep=fake_clock.sleep) == 400
        assert fake_clock.sleeps == []

    def test_post_with_retry_returns_final_transient_status(self, fake_clock):
        assert post_with_retry(
            lambda: 503, RetryPolicy(max_attempts=3, base_interval=0.01),
            rng=_MaxJitter(), sleep=fake_clock.sleep) == 503


# ---------------------------------------------------------------------------
# breaker


class TestCircuitBreaker:
    def test_open_half_open_close_cycle(self, fake_clock):
        b = CircuitBreaker(failure_threshold=3, reset_timeout=5.0,
                           clock=fake_clock, name="dest")
        assert b.state == CLOSED and b.allow()
        for _ in range(3):
            b.record_failure()
        assert b.state == OPEN and not b.allow()
        # before the reset timeout: still rejected
        fake_clock.advance(4.9)
        assert not b.allow()
        # after: half-open admits exactly half_open_max probes
        fake_clock.advance(0.2)
        assert b.state == HALF_OPEN
        assert b.allow()
        assert not b.allow()  # second concurrent probe rejected
        b.record_success()
        assert b.state == CLOSED and b.allow()

    def test_failed_probe_reopens_and_restarts_timer(self, fake_clock):
        b = CircuitBreaker(failure_threshold=1, reset_timeout=5.0,
                           clock=fake_clock)
        b.record_failure()
        assert b.state == OPEN
        fake_clock.advance(5.1)
        assert b.allow()          # the half-open probe
        b.record_failure()        # probe failed
        assert b.state == OPEN and b.trips == 2
        fake_clock.advance(2.0)   # timer restarted: still open
        assert not b.allow()

    def test_success_resets_consecutive_failures(self, fake_clock):
        b = CircuitBreaker(failure_threshold=3, clock=fake_clock)
        b.record_failure()
        b.record_failure()
        b.record_success()
        b.record_failure()
        b.record_failure()
        assert b.state == CLOSED  # never 3 consecutive

    def test_call_wrapper(self, fake_clock):
        b = CircuitBreaker(failure_threshold=1, reset_timeout=5.0,
                           clock=fake_clock, name="d")
        with pytest.raises(OSError):
            b.call(lambda: (_ for _ in ()).throw(OSError("down")))
        with pytest.raises(BreakerOpen):
            b.call(lambda: "never runs")
        fake_clock.advance(5.1)
        assert b.call(lambda: "ok") == "ok"
        assert b.state == CLOSED

    def test_registry_per_destination(self, fake_clock):
        reg = BreakerRegistry(failure_threshold=1, reset_timeout=5.0,
                              clock=fake_clock)
        assert reg.get("a") is reg.get("a")
        reg.get("a").record_failure()
        states = dict(reg.states())
        assert states["a"] == 2.0  # open
        assert reg.get("b").state == CLOSED

    def test_registry_retain_evicts_departed_destinations(self, fake_clock):
        reg = BreakerRegistry(clock=fake_clock)
        for name in ("a", "b", "c"):
            reg.get(name)
        reg.retain({"a", "c"})
        assert dict(reg.states()).keys() == {"a", "c"}
        # a departed destination coming back gets a fresh breaker
        assert reg.get("b").state == CLOSED

    def test_blocked_never_consumes_the_half_open_probe(self, fake_clock):
        b = CircuitBreaker(failure_threshold=1, reset_timeout=5.0,
                           clock=fake_clock)
        b.record_failure()
        assert b.blocked()
        fake_clock.advance(5.1)
        # half-open: blocked() says "go ahead" any number of times
        # without eating the probe budget...
        assert not b.blocked()
        assert not b.blocked()
        # ...which allow() then consumes exactly once
        assert b.allow()
        assert not b.allow()


# ---------------------------------------------------------------------------
# fault injection


class TestFaultInjection:
    def test_same_seed_same_schedule(self):
        a = FaultInjector(rate=0.5, seed=123).schedule(200)
        b = FaultInjector(rate=0.5, seed=123).schedule(200)
        assert a == b
        assert any(k is not None for k in a)
        assert any(k is None for k in a)

    def test_different_seed_different_schedule(self):
        a = FaultInjector(rate=0.5, seed=1).schedule(200)
        b = FaultInjector(rate=0.5, seed=2).schedule(200)
        assert a != b

    def test_rate_bounds(self):
        assert all(k is None
                   for k in FaultInjector(rate=0.0, seed=1).schedule(50))
        assert all(k is not None
                   for k in FaultInjector(rate=1.0, seed=1).schedule(50))
        with pytest.raises(ValueError):
            FaultInjector(rate=1.5)
        with pytest.raises(ValueError):
            FaultInjector(rate=0.5, kinds=("nonsense",))

    def test_scope_filters_operations(self):
        inj = FaultInjector(rate=1.0, seed=0, scope="sink.datadog")
        assert inj.should_fail("forward.http") is None
        assert inj.should_fail("sink.datadog") is not None

    def test_wrap_post_injects_5xx_without_calling_through(self):
        calls = []
        inj = FaultInjector(rate=1.0, seed=0, kinds=("http_5xx",))
        wrapped = inj.wrap_post(lambda: calls.append(1) or 202, "op")
        assert wrapped() == INJECTED_STATUS
        assert calls == []  # the far side never saw the request

    def test_maybe_fail_raises_oserrors(self):
        inj = FaultInjector(rate=1.0, seed=0, kinds=("connect",))
        with pytest.raises(OSError):
            inj.maybe_fail("forward.native")

    def test_config_construction_and_validation(self):
        from veneur_tpu.resilience import faults_from_config

        cfg = Config(fault_injection_rate=0.25, fault_injection_seed=9,
                     fault_injection_kinds="connect,timeout",
                     fault_injection_scope="sink.")
        inj = faults_from_config(cfg)
        assert inj.rate == 0.25 and inj.seed == 9
        assert inj.kinds == ("connect", "timeout")
        assert faults_from_config(Config()) is None
        with pytest.raises(ValueError):
            Config(fault_injection_rate=2.0).validate()
        with pytest.raises(ValueError):
            Config(fault_injection_kinds="bogus").validate()


# ---------------------------------------------------------------------------
# config parse-once


class TestResilienceConfig:
    def test_server_config_parses_durations_once(self):
        cfg = Config(forward_timeout="250ms", retry_base_interval="50ms",
                     breaker_reset_timeout="2s").apply_defaults()
        assert cfg.forward_timeout_seconds == pytest.approx(0.25)
        assert cfg.retry_base_interval_seconds == pytest.approx(0.05)
        assert cfg.breaker_reset_timeout_seconds == pytest.approx(2.0)

    def test_server_config_defaults(self):
        cfg = Config().apply_defaults()
        assert cfg.forward_timeout == "10s"
        assert cfg.retry_max == 2
        assert cfg.breaker_failure_threshold == 5
        policy = RetryPolicy.from_config(cfg)
        assert policy.max_attempts == 3
        assert policy.base_interval == pytest.approx(0.1)

    def test_retry_max_zero_means_single_attempt(self):
        cfg = Config(retry_max=0).apply_defaults()
        assert RetryPolicy.from_config(cfg).max_attempts == 1

    def test_proxy_config_finalize(self):
        cfg = ProxyConfig(forward_timeout="3s", retry_max=1).finalize()
        assert cfg.forward_timeout_seconds == pytest.approx(3.0)
        assert cfg.retry_max == 1
        assert cfg.breaker_failure_threshold == 5
        # idempotent
        cfg.finalize()
        assert cfg.forward_timeout_seconds == pytest.approx(3.0)

    def test_validation_rejects_bad_values(self):
        with pytest.raises(ValueError):
            Config(breaker_failure_threshold=-1).validate()
        with pytest.raises(ValueError):
            ProxyConfig(fault_injection_rate=-0.5).finalize()


# ---------------------------------------------------------------------------
# HTTP fixtures


class _ScriptedImportHandler(BaseHTTPRequestHandler):
    """Replies with the next scripted status; records request bodies."""

    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):
        pass

    def do_POST(self):
        length = int(self.headers.get("Content-Length") or 0)
        body = self.rfile.read(length)
        if (self.headers.get("Content-Encoding") or "") == "deflate":
            body = zlib.decompress(body)
        with self.server.lock:
            statuses = self.server.statuses
            status = statuses.pop(0) if statuses else 202
            if 200 <= status < 300:
                self.server.received.append(
                    (self.path, json.loads(body) if body else None))
        self.send_response(status)
        self.send_header("Content-Length", "0")
        self.end_headers()


def scripted_server(statuses):
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _ScriptedImportHandler)
    srv.daemon_threads = True
    srv.statuses = list(statuses)
    srv.received = []
    srv.lock = threading.Lock()
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv


def dead_port() -> int:
    """A port with nothing listening: instant connection-refused."""
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def forwardable_state():
    """A tiny local-role ForwardableState with a global counter."""
    from veneur_tpu.core.store import MetricStore
    from veneur_tpu.samplers import parser as p
    from veneur_tpu.samplers.intermetric import HistogramAggregates

    store = MetricStore(initial_capacity=32, chunk=128)
    store.process_metric(p.parse_metric(b"gctr:5|c|#veneurglobalonly"))
    agg = HistogramAggregates.from_names(["min", "max", "count"])
    _, fwd, _ = store.flush([0.5], agg, is_local=True,
                            now=int(time.time()), forward=True)
    return fwd


# ---------------------------------------------------------------------------
# HTTP forwarder wired in


class TestHTTPForwarderResilience:
    def test_retries_5xx_until_success_and_counts(self):
        from veneur_tpu.forward import HTTPForwarder

        srv = scripted_server([503, 503, 202])
        try:
            f = HTTPForwarder(
                f"127.0.0.1:{srv.server_address[1]}",
                retry_policy=RetryPolicy(max_attempts=5,
                                         base_interval=0.005,
                                         max_interval=0.02))
            f.forward(forwardable_state())
            assert f.errors == 0
            assert f.forwarded > 0
            assert f.retries == 2
            # the flusher's self-metric path reports the retry delta
            class _Stub:
                _forwarder = f
            samples = {s.name: s for s in flusher._forward_samples(_Stub())}
            assert samples["veneur.forward.retries_total"].value == 2
        finally:
            srv.shutdown()

    def test_expired_deadline_means_single_attempt(self, fake_clock):
        from veneur_tpu.forward import HTTPForwarder

        port = dead_port()
        f = HTTPForwarder(f"127.0.0.1:{port}", timeout=0.3,
                          retry_policy=RetryPolicy(max_attempts=5,
                                                   base_interval=0.2))
        deadline = Deadline.after(0.0, clock=fake_clock)
        t0 = time.perf_counter()
        f.forward(forwardable_state(), deadline=deadline)
        assert f.errors == 1
        assert f.retries == 0  # no retry budget left
        assert time.perf_counter() - t0 < 2.0

    def test_breaker_open_skips_the_post_entirely(self, fake_clock):
        from veneur_tpu.forward import HTTPForwarder

        breaker = CircuitBreaker(failure_threshold=1, reset_timeout=60.0,
                                 clock=fake_clock, name="upstream")
        port = dead_port()
        f = HTTPForwarder(f"127.0.0.1:{port}", timeout=0.3,
                          retry_policy=RetryPolicy(max_attempts=1),
                          breaker=breaker)
        f.forward(forwardable_state())
        assert breaker.state == OPEN
        t0 = time.perf_counter()
        f.forward(forwardable_state())
        # rejected instantly, no connect attempt
        assert time.perf_counter() - t0 < 0.25
        assert f.errors == 2

    def test_persistent_4xx_does_not_trip_the_breaker(self, fake_clock):
        """A destination that is alive but rejecting (400s) must never
        be black-holed by its breaker — only transport errors and
        transient statuses (5xx/429) count toward tripping."""
        from veneur_tpu.forward import HTTPForwarder

        srv = scripted_server([400] * 10)
        try:
            breaker = CircuitBreaker(failure_threshold=2, clock=fake_clock,
                                     name="upstream")
            f = HTTPForwarder(
                f"127.0.0.1:{srv.server_address[1]}",
                retry_policy=RetryPolicy(max_attempts=1),
                breaker=breaker)
            for _ in range(4):
                f.forward(forwardable_state())
            assert f.errors == 4          # still counted as errors
            assert breaker.state == CLOSED  # but never tripped
        finally:
            srv.shutdown()

    def test_forward_samples_report_breaker_state(self, fake_clock):
        from veneur_tpu.forward import HTTPForwarder

        breaker = CircuitBreaker(failure_threshold=1, clock=fake_clock,
                                 name="http://dest:8127")
        f = HTTPForwarder("127.0.0.1:1", breaker=breaker)

        class _Stub:
            _forwarder = f

        samples = {s.name: s for s in flusher._forward_samples(_Stub())}
        assert samples["veneur.breaker.state"].value == 0.0
        breaker.record_failure()
        samples = {s.name: s for s in flusher._forward_samples(_Stub())}
        assert samples["veneur.breaker.state"].value == 2.0


# ---------------------------------------------------------------------------
# Datadog sink wired in (the 20-interval acceptance loop)


def _recording_post(delivered):
    def post(url, payload, compress=True, method="POST",
             precompressed=False, out_info=None):
        delivered.append((url, payload))
        return 202
    return post


class TestSinkFaultAcceptance:
    def _sink(self, delivered, **kw):
        from veneur_tpu.sinks.datadog import DatadogMetricSink

        return DatadogMetricSink(
            interval=10.0, flush_max_per_body=1000, hostname="h",
            tags=[], dd_hostname="http://dd.test", api_key="k",
            post=_recording_post(delivered), **kw)

    def test_thirty_percent_faults_twenty_intervals_all_delivered(self):
        """ISSUE 1 acceptance: with 30% of POSTs failing, every one of
        20 flush intervals still delivers (retries succeed within the
        deadline), and the retry self-metric is emitted."""
        delivered = []
        inj = FaultInjector(rate=0.3, seed=11)
        sink = self._sink(
            delivered,
            retry_policy=RetryPolicy(max_attempts=6, base_interval=0.001,
                                     max_interval=0.004),
            fault_injector=inj)
        for i in range(20):
            sink.set_flush_deadline(Deadline.after(5.0))
            sink.flush([InterMetric(name=f"interval.m{i}", timestamp=i,
                                    value=1.0, type=MetricType.GAUGE)])
        assert len(delivered) == 20          # every interval delivered
        assert sink.retries > 0              # and it took retries
        assert sum(inj.injected.values()) > 0
        assert sink.flush_errors == 0

        # veneur.sink.<name>.retries_total rides the flusher drain
        class _Stub:
            metric_sinks = [sink]
        samples = {s.name: s
                   for s in flusher._sink_samples(_Stub(), {})}
        assert samples["veneur.sink.datadog.retries_total"].value \
            == sink.retries
        assert "veneur.flush.error_total" in samples

    def test_black_holed_sink_breaker_opens_within_threshold(self, fake_clock):
        """ISSUE 1 acceptance: a dead destination trips the breaker
        after breaker_failure_threshold flushes; once open, flushes
        reject instantly so wall-time stays far under the interval."""
        def dead_post(url, payload, **kw):
            raise ConnectionRefusedError("black hole")

        from veneur_tpu.sinks.datadog import DatadogMetricSink

        breaker = CircuitBreaker(failure_threshold=3, reset_timeout=60.0,
                                 clock=fake_clock, name="dd")
        sink = DatadogMetricSink(
            interval=10.0, flush_max_per_body=1000, hostname="h",
            tags=[], dd_hostname="http://dd.test", api_key="k",
            post=dead_post,
            retry_policy=RetryPolicy(max_attempts=2, base_interval=0.001,
                                     max_interval=0.002),
            breaker=breaker)
        metric = [InterMetric(name="m", timestamp=1, value=1.0,
                              type=MetricType.GAUGE)]
        for _ in range(3):
            sink.set_flush_deadline(Deadline.after(5.0))
            sink.flush(metric)
        assert breaker.state == OPEN
        assert sink.flush_errors == 3
        t0 = time.perf_counter()
        sink.flush(metric)
        elapsed = time.perf_counter() - t0
        assert elapsed < 0.5                 # instant rejection, no POST
        assert sink.flush_errors == 4

        class _Stub:
            metric_sinks = [sink]
        samples = [s for s in flusher._sink_samples(_Stub(), {})
                   if s.name == "veneur.breaker.state"]
        assert samples and samples[0].value == 2.0

    @pytest.mark.slow
    def test_soak_two_hundred_intervals_under_faults(self):
        """Longer soak of the same acceptance loop (excluded from the
        tier-1 gate by the slow marker)."""
        delivered = []
        sink = self._sink(
            delivered,
            retry_policy=RetryPolicy(max_attempts=8, base_interval=0.001,
                                     max_interval=0.01),
            fault_injector=FaultInjector(rate=0.3, seed=1337))
        for i in range(200):
            sink.set_flush_deadline(Deadline.after(5.0))
            sink.flush([InterMetric(name=f"soak.m{i}", timestamp=i,
                                    value=1.0, type=MetricType.GAUGE)])
        assert len(delivered) == 200
        assert sink.flush_errors == 0


# ---------------------------------------------------------------------------
# kafka_retry_max


class _FlakyProducer:
    def __init__(self, fail_first: int):
        self.fail_first = fail_first
        self.attempts = 0
        self.messages = []

    def produce(self, topic, value):
        self.attempts += 1
        if self.attempts <= self.fail_first:
            raise OSError("broker down")
        self.messages.append((topic, value))

    def close(self):
        pass


class TestKafkaRetryMax:
    def _flush_one(self, producer, retries):
        from veneur_tpu.sinks.kafka import KafkaMetricSink, ProducerConfig

        sink = KafkaMetricSink(
            brokers="b:9092", metric_topic="t",
            config=ProducerConfig(retries=retries), producer=producer)
        sink.set_flush_deadline(Deadline.after(5.0))
        sink.flush([InterMetric(name="k", timestamp=1, value=2.0,
                                type=MetricType.COUNTER)])
        return sink

    def test_retry_max_drives_attempt_count(self):
        producer = _FlakyProducer(fail_first=2)
        sink = self._flush_one(producer, retries=3)
        # kafka_retry_max=3 → up to 4 attempts; succeeded on the third
        assert producer.attempts == 3
        assert len(producer.messages) == 1
        assert sink.metrics_flushed == 1
        assert sink.retries == 2
        assert sink.flush_errors == 0

    def test_retry_max_zero_is_single_attempt(self):
        producer = _FlakyProducer(fail_first=1)
        sink = self._flush_one(producer, retries=0)
        assert producer.attempts == 1       # the knob really is 0
        assert sink.metrics_flushed == 0
        assert sink.flush_errors == 1

    def test_configured_backoff_shape_reaches_the_sink(self):
        from veneur_tpu.sinks.kafka import KafkaMetricSink, ProducerConfig

        sink = KafkaMetricSink(
            brokers="b:9092", metric_topic="t",
            config=ProducerConfig(retries=1),
            producer=_FlakyProducer(0),
            retry_policy=RetryPolicy(max_attempts=99, base_interval=0.42,
                                     max_interval=7.0))
        # attempt budget comes from kafka_retry_max, backoff shape from
        # the shared retry knobs
        assert sink.retry_policy.max_attempts == 2
        assert sink.retry_policy.base_interval == pytest.approx(0.42)
        assert sink.retry_policy.max_interval == pytest.approx(7.0)

    def test_budget_exhausted_drops_only_that_metric(self):
        from veneur_tpu.sinks.kafka import KafkaMetricSink, ProducerConfig

        class AlwaysDown(_FlakyProducer):
            def __init__(self):
                super().__init__(fail_first=1 << 30)

        producer = AlwaysDown()
        sink = KafkaMetricSink(
            brokers="b:9092", metric_topic="t",
            config=ProducerConfig(retries=1), producer=producer)
        sink.set_flush_deadline(Deadline.after(5.0))
        sink.flush([InterMetric(name="a", timestamp=1, value=1.0,
                                type=MetricType.COUNTER)])
        assert producer.attempts == 2
        assert sink.flush_errors == 1


# ---------------------------------------------------------------------------
# proxy ring fan-out with a black-holed destination


class TestProxyBreakers:
    def test_fan_out_with_one_destination_black_holed(self):
        from veneur_tpu.discovery import StaticDiscoverer
        from veneur_tpu.proxy.proxy import Proxy, metric_ring_key

        h1 = scripted_server([])
        h2 = scripted_server([])
        try:
            dests = [f"http://127.0.0.1:{h1.server_address[1]}",
                     f"http://127.0.0.1:{h2.server_address[1]}",
                     f"http://127.0.0.1:{dead_port()}"]
            proxy = Proxy(
                ProxyConfig(http_address="127.0.0.1:0",
                            forward_timeout="500ms", retry_max=0,
                            breaker_failure_threshold=2,
                            breaker_reset_timeout="60s"),
                discoverer=StaticDiscoverer(dests))
            proxy.refresh_destinations()
            # enough keys that no draw of the three ports leaves a
            # destination without one (30 did, once in a few hundred)
            metrics = [{"name": f"fan.m{i}", "type": "counter",
                        "tags": [], "value": 1} for i in range(240)]
            by_dest = {}
            for m in metrics:
                by_dest.setdefault(proxy.ring.get(metric_ring_key(m)),
                                   []).append(m["name"])
            # the ring spread the keys over all three destinations
            assert len(by_dest) == 3
            dead = dests[2]
            rounds = 4
            for _ in range(rounds):
                proxy.proxy_metrics(metrics)

            # every healthy destination got its full share every round
            for srv, dest in ((h1, dests[0]), (h2, dests[1])):
                got = [m["name"] for _, batch in srv.received
                       for m in batch]
                assert sorted(got) == sorted(by_dest[dest] * rounds)
            # the black-holed destination tripped within the threshold
            # and was then rejected without a connect attempt
            assert proxy.breakers.get(dead).state == OPEN
            assert proxy.breaker_rejections == rounds - 2
            assert proxy.forward_errors == rounds
            assert proxy.proxied == sum(
                len(v) for d, v in by_dest.items() if d != dead) * rounds
        finally:
            h1.shutdown()
            h2.shutdown()

    def test_4xx_destination_errors_but_never_trips(self):
        from veneur_tpu.discovery import StaticDiscoverer
        from veneur_tpu.proxy.proxy import Proxy

        srv = scripted_server([413] * 20)
        try:
            dest = f"http://127.0.0.1:{srv.server_address[1]}"
            proxy = Proxy(
                ProxyConfig(http_address="127.0.0.1:0",
                            forward_timeout="500ms", retry_max=0,
                            breaker_failure_threshold=2),
                discoverer=StaticDiscoverer([dest]))
            proxy.refresh_destinations()
            metrics = [{"name": "m", "type": "counter", "tags": [],
                        "value": 1}]
            for _ in range(4):
                proxy.proxy_metrics(metrics)
            assert proxy.forward_errors == 4
            assert proxy.breaker_rejections == 0
            from veneur_tpu.resilience.breaker import CLOSED as _CLOSED
            assert proxy.breakers.get(dest).state == _CLOSED
        finally:
            srv.shutdown()

    def test_refresh_prunes_breakers_for_departed_destinations(self):
        from veneur_tpu.discovery import StaticDiscoverer
        from veneur_tpu.proxy.proxy import Proxy

        class Shrinking:
            def __init__(self):
                self.calls = 0

            def get_destinations_for_service(self, name):
                self.calls += 1
                if self.calls == 1:
                    return ["http://a:1", "http://b:1"]
                return ["http://a:1"]

        proxy = Proxy(
            ProxyConfig(http_address="127.0.0.1:0",
                        consul_forward_service_name="veneur"),
            discoverer=Shrinking())
        proxy.refresh_destinations()
        proxy.breakers.get("http://a:1")
        proxy.breakers.get("http://b:1")
        proxy.refresh_destinations()  # b departed
        assert dict(proxy.breakers.states()).keys() == {"http://a:1"}

    def test_refresh_retries_then_keeps_last_good_ring(self):
        from veneur_tpu.discovery import StaticDiscoverer
        from veneur_tpu.proxy.proxy import Proxy

        class FlakyOnce:
            def __init__(self):
                self.calls = 0

            def get_destinations_for_service(self, name):
                self.calls += 1
                if self.calls == 2:
                    # one transient failure: the retry absorbs it and
                    # the refresh SUCCEEDS (no fallback to the old ring)
                    raise OSError("consul hiccup")
                return ["http://10.0.0.1:8127", "http://10.0.0.2:8127"]

        disc = FlakyOnce()
        proxy = Proxy(
            ProxyConfig(http_address="127.0.0.1:0",
                        consul_forward_service_name="veneur",
                        retry_max=2, retry_base_interval="1ms"),
            discoverer=disc)
        proxy.refresh_destinations()
        proxy.refresh_destinations()  # call 2 fails, retry (call 3) wins
        assert len(proxy.ring) == 2
        assert proxy.refresh_failures == 0
        assert proxy.refresh_retries == 1


# ---------------------------------------------------------------------------
# discovery wrapper


class TestLightStepRetryWiring:
    def test_retry_policy_reaches_the_tracer_factory(self):
        from veneur_tpu.sinks.lightstep import LightStepSpanSink

        seen = []

        def factory(**kw):
            seen.append(kw)

            class T:
                def report(self, span):
                    pass
            return T()

        policy = RetryPolicy(max_attempts=1, base_interval=2.5)
        LightStepSpanSink(collector="http://collector",
                          tracer_factory=factory, retry_policy=policy)
        assert seen[0]["retry_policy"] is policy
        # without a policy the kwarg stays out entirely (custom
        # factories need not accept it)
        seen.clear()
        LightStepSpanSink(collector="http://collector",
                          tracer_factory=factory)
        assert "retry_policy" not in seen[0]


class TestRetryingDiscoverer:
    def test_absorbs_transient_failures(self):
        from veneur_tpu.discovery import RetryingDiscoverer

        class Flaky:
            def __init__(self):
                self.calls = 0

            def get_destinations_for_service(self, name):
                self.calls += 1
                if self.calls < 3:
                    raise OSError("down")
                return ["http://a:1"]

        d = RetryingDiscoverer(
            Flaky(), RetryPolicy(max_attempts=5, base_interval=0.001,
                                 max_interval=0.004))
        assert d.get_destinations_for_service("svc") == ["http://a:1"]
        assert d.retries == 2
