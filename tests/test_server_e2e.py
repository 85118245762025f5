"""End-to-end server tests over real sockets, in-process.

Port of the reference's dominant test pattern (server_test.go:60-231):
a real server on ephemeral ports with a channel sink, driven by real
UDP/TCP/UNIX traffic, short flush intervals, assertions on flushed batches.
"""

import os
import socket
import time

import pytest

from veneur_tpu.config import Config
from veneur_tpu.protocol import ssf_pb2, wire
from veneur_tpu.server import Server, calculate_tick_delay
from veneur_tpu.sinks import ChannelMetricSink, ChannelSpanSink


def make_server(tmp_path=None, **cfg_kwargs):
    cfg_kwargs.setdefault("statsd_listen_addresses", ["udp://127.0.0.1:0"])
    cfg_kwargs.setdefault("interval", "86400s")  # flush manually in tests
    cfg_kwargs.setdefault("store_initial_capacity", 32)
    cfg_kwargs.setdefault("store_chunk", 128)
    cfg_kwargs.setdefault("aggregates", ["min", "max", "count"])
    config = Config(**cfg_kwargs)
    sink = ChannelMetricSink()
    server = Server(config, metric_sinks=[sink])
    server.start()
    return server, sink


def send_udp(addr, payload: bytes):
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    s.sendto(payload, addr)
    s.close()


def wait_for(predicate, timeout=5.0, interval=0.01):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return False


class TestUDPMetrics:
    def test_counter_over_udp(self):
        server, sink = make_server()
        try:
            addr = server.statsd_addrs[0]
            send_udp(addr, b"a.b.c:1|c")
            assert wait_for(lambda: server.store.processed >= 1)
            server.flush()
            batch = sink.get_flush()
            assert any(m.name == "a.b.c" and m.value == 1.0 for m in batch)
        finally:
            server.shutdown()

    def test_multiline_datagram(self):
        server, sink = make_server()
        try:
            addr = server.statsd_addrs[0]
            send_udp(addr, b"x:1|c\ny:2|g\nz:3.5|h|#env:dev")
            assert wait_for(lambda: server.store.processed >= 3)
            server.flush()
            names = {m.name for m in sink.get_flush()}
            assert {"x", "y", "z.count", "z.max", "z.min"} <= names
        finally:
            server.shutdown()

    def test_mixed_metrics_local_flush(self):
        # port of TestLocalServerMixedMetrics (server_test.go:294-408):
        # a local instance flushes counters + histogram aggregates but
        # keeps percentiles for the global tier
        server, sink = make_server(forward_address="http://upstream.invalid",
                                   percentiles=[0.5, 0.9])
        try:
            addr = server.statsd_addrs[0]
            for v in (1, 2, 3, 4, 5):
                send_udp(addr, f"a.b.latency:{v}|ms".encode())
            send_udp(addr, b"a.b.hits:100|c")
            assert wait_for(lambda: server.store.processed >= 6)
            server.flush()
            batch = sink.get_flush()
            by_name = {m.name: m for m in batch}
            assert by_name["a.b.hits"].value == 100.0
            assert by_name["a.b.latency.min"].value == 1.0
            assert by_name["a.b.latency.max"].value == 5.0
            assert by_name["a.b.latency.count"].value == 5.0
            assert "a.b.latency.50percentile" not in by_name
        finally:
            server.shutdown()

    def test_multiple_udp_readers_share_port(self):
        server, sink = make_server(num_readers=4)
        try:
            addr = server.statsd_addrs[0]
            # all readers must be on the same port
            assert len({a[1] for a in server.statsd_addrs}) == 1
            for i in range(100):
                send_udp(addr, f"c{i % 10}:1|c".encode())
            assert wait_for(lambda: server.store.processed >= 100)
            server.flush()
            batch = sink.get_flush()
            assert sum(m.value for m in batch) == 100.0
        finally:
            server.shutdown()

    def test_events_reach_flush_other_samples(self):
        server, sink = make_server()

        received = []
        sink.flush_other_samples = received.extend
        try:
            addr = server.statsd_addrs[0]
            send_udp(addr, b"_e{5,4}:title|text")
            assert wait_for(lambda: len(server.event_worker._samples) >= 1)
            server.flush()
            assert received and received[0].name == "title"
        finally:
            server.shutdown()

    def test_bad_packets_counted_not_fatal(self):
        server, sink = make_server()
        try:
            addr = server.statsd_addrs[0]
            send_udp(addr, b"garbage")
            send_udp(addr, b"ok:1|c")
            assert wait_for(lambda: server.store.processed >= 1)
            assert wait_for(lambda: server.packet_errors >= 1)
            server.flush()
            assert {m.name for m in sink.get_flush()} == {"ok"}
        finally:
            server.shutdown()


class TestTCPMetrics:
    def test_counter_over_tcp(self):
        server, sink = make_server(
            statsd_listen_addresses=["tcp://127.0.0.1:0"])
        try:
            addr = server.statsd_addrs[0]
            c = socket.create_connection(addr)
            c.sendall(b"t.c.p:7|c\n")
            c.close()
            assert wait_for(lambda: server.store.processed >= 1)
            server.flush()
            assert sink.get_flush()[0].value == 7.0
        finally:
            server.shutdown()


class TestSSF:
    def _span(self, with_metric=True):
        span = ssf_pb2.SSFSpan(
            id=1, trace_id=1, name="a.span", service="svc",
            start_timestamp=10**18, end_timestamp=10**18 + 5 * 10**6)
        if with_metric:
            span.metrics.add(
                metric=ssf_pb2.SSFSample.COUNTER, name="ssf.count",
                value=2.0, sample_rate=1.0)
        return span

    def test_udp_ssf_metrics_extracted(self):
        server, sink = make_server(ssf_listen_addresses=["udp://127.0.0.1:0"])
        try:
            addr = server.ssf_addrs[0]
            send_udp(addr, self._span().SerializeToString())
            assert wait_for(lambda: server.store.processed >= 1)
            server.flush()
            by_name = {m.name: m for m in sink.get_flush()}
            assert by_name["ssf.count"].value == 2.0
        finally:
            server.shutdown()

    def test_unix_framed_ssf(self, tmp_path):
        sock_path = str(tmp_path / "ssf.sock")
        server, sink = make_server(
            ssf_listen_addresses=[f"unix://{sock_path}"])
        try:
            assert wait_for(lambda: os.path.exists(sock_path))
            c = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            c.connect(sock_path)
            f = c.makefile("wb")
            for _ in range(3):
                wire.write_ssf(f, self._span())
            f.flush()
            c.close()
            assert wait_for(lambda: server.store.processed >= 3)
            server.flush()
            by_name = {m.name: m for m in sink.get_flush()}
            assert by_name["ssf.count"].value == 6.0
        finally:
            server.shutdown()

    def test_spans_reach_span_sinks(self):
        span_sink = ChannelSpanSink()
        config = Config(statsd_listen_addresses=[],
                        ssf_listen_addresses=["udp://127.0.0.1:0"],
                        interval="86400s")
        server = Server(config, metric_sinks=[], span_sinks=[span_sink])
        server.start()
        try:
            addr = server.ssf_addrs[0]
            send_udp(addr, self._span(with_metric=False).SerializeToString())
            assert wait_for(lambda: not span_sink.queue.empty())
            got = span_sink.queue.get_nowait()
            assert got.name == "a.span"
        finally:
            server.shutdown()

    def test_blocked_span_sink_does_not_stall_extraction(self):
        """A hung span sink must not stall other sinks — critically the
        metric-extraction sink, the path SSF metrics take to the store
        (the reference bounds each sink's Ingest at 9s, worker.go:541-590;
        here each sink drains on its own bounded lane)."""
        import threading

        release = threading.Event()

        class BlockedSink(ChannelSpanSink):
            @property
            def name(self):
                return "blocked"

            def ingest(self, span):
                release.wait(30.0)

        blocked = BlockedSink()
        config = Config(statsd_listen_addresses=[],
                        ssf_listen_addresses=["udp://127.0.0.1:0"],
                        interval="86400s")
        sink = ChannelMetricSink()
        server = Server(config, metric_sinks=[sink], span_sinks=[blocked])
        server.start()
        try:
            # spans with metrics keep arriving while "blocked" is wedged
            for _ in range(3):
                send_udp(server.ssf_addrs[0],
                         self._span().SerializeToString())
            # extraction proceeds: the SSF counters reach the store even
            # though the blocked sink never returns from ingest
            assert wait_for(lambda: server.store.processed >= 3)
            server.flush()
            batch = sink.get_flush()
            assert any(m.name == "ssf.count" and m.value == 6.0
                       for m in batch)
        finally:
            release.set()
            server.shutdown()

    def test_indicator_span_timer(self):
        server, sink = make_server(
            ssf_listen_addresses=["udp://127.0.0.1:0"],
            indicator_span_timer_name="indicator.timer")
        try:
            span = self._span(with_metric=False)
            span.indicator = True
            send_udp(server.ssf_addrs[0], span.SerializeToString())
            assert wait_for(lambda: server.store.processed >= 1)
            server.flush()
            by_name = {m.name: m for m in sink.get_flush()}
            # duration is 5e6 ns
            assert by_name["indicator.timer.max"].value == pytest.approx(5e6)
        finally:
            server.shutdown()


class TestFlushTicker:
    def test_tick_delay_alignment(self):
        assert calculate_tick_delay(10.0, 1000.0) == pytest.approx(10.0)
        assert calculate_tick_delay(10.0, 1003.5) == pytest.approx(6.5)

    def test_periodic_flush(self):
        server, sink = make_server(interval="200ms")
        try:
            send_udp(server.statsd_addrs[0], b"tick:1|c")
            batch = sink.get_flush(timeout=5.0)
            assert batch[0].name == "tick"
        finally:
            server.shutdown()


class TestSighupReload:
    """Graceful in-process reload (the reference's HUP path,
    server.go:1048-1076): hot-swap sinks/interval/percentiles, keep
    sockets, store state, and frozen geometry."""

    def test_reload_swaps_tunables_and_keeps_sockets(self):
        server, sink = make_server(percentiles=[0.5], tags=["env:a"])
        try:
            from veneur_tpu.samplers import parser as p

            old_addrs = list(server.statsd_addrs)
            old_store = server.store
            server.store.process_metric(p.parse_metric(b"pre:1|c"))

            new_cfg = Config(
                statsd_listen_addresses=["udp://127.0.0.1:0"],
                interval="7s", percentiles=[0.9], tags=["env:b"],
                aggregates=["count"], store_initial_capacity=32,
                store_chunk=128,
                # frozen key changes must be rejected, not applied
                digest_storage="slab",
                native_import_address="127.0.0.1:45678")
            server.reload(new_cfg)
            assert server.config.native_import_address == ""

            assert server.interval == 7.0
            assert server.histogram_percentiles == [0.9]
            assert server.tags == ["env:b"]
            # sockets and store survive; frozen geometry kept
            assert server.statsd_addrs == old_addrs
            assert server.store is old_store
            assert server.config.digest_storage == "dense"
            # injected sinks survive the reload
            assert sink in server.metric_sinks
            # pre-reload data still flushes
            server.flush()
            names = {m.name for m in sink.get_flush()}
            assert "pre" in names
            # ingest keeps working on the same socket
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            s.sendto(b"post:1|c", server.statsd_addrs[0])
            deadline = time.time() + 5
            while server.store.processed < 1 and time.time() < deadline:
                time.sleep(0.01)
            assert server.store.processed >= 1
        finally:
            server.shutdown()

    def test_reload_sink_lifecycle(self, monkeypatch):
        """Config-driven sinks from a reload are start()ed; the sinks
        they replace close on the NEXT reload (after their in-flight
        flushes finished) and at shutdown."""
        from veneur_tpu.sinks import factory

        class FakeSink:
            name = "fake"

            def __init__(self, gen):
                self.gen = gen
                self.started = False
                self.closed = False

            def start(self, trace_client=None):
                self.started = True

            def close(self):
                self.closed = True

            def flush(self, metrics):
                pass

            def flush_other_samples(self, samples):
                pass

        made = []

        def fake_create(config):
            s = FakeSink(len(made))
            made.append(s)
            return [s], [], []

        server, injected = make_server()
        try:
            monkeypatch.setattr(factory, "create_sinks", fake_create)
            cfg = Config(statsd_listen_addresses=["udp://127.0.0.1:0"],
                         interval="86400s", store_initial_capacity=32,
                         store_chunk=128)
            server.reload(cfg)
            assert made[0].started
            assert made[0] in server.metric_sinks
            assert injected in server.metric_sinks  # injected survives
            assert not made[0].closed
            server.reload(cfg)
            assert made[1].started and not made[1].closed
            assert made[0] not in server.metric_sinks
            # made[0] is RETIRED but not yet closed (its in-flight flush
            # threads get until the next reload); the third reload
            # closes it
            assert not made[0].closed
            server.reload(cfg)
            assert made[0].closed
            assert not made[1].closed  # retired now, closes later
        finally:
            server.shutdown()
        # shutdown closes everything still retired
        assert made[1].closed

    def test_reload_rebuilds_forwarder(self):
        server, _ = make_server(forward_address="127.0.0.1:1",
                                forward_use_grpc=True)
        try:
            first = server._forwarder
            assert first is not None
            cfg = Config(statsd_listen_addresses=["udp://127.0.0.1:0"],
                         interval="86400s", store_initial_capacity=32,
                         store_chunk=128,
                         forward_address="127.0.0.1:2",
                         forward_use_grpc=True)
            server.reload(cfg)
            assert server._forwarder is not None
            assert server._forwarder is not first
            assert server.forward_fn is not None
            # role change is refused
            cfg2 = Config(statsd_listen_addresses=["udp://127.0.0.1:0"],
                          interval="86400s", store_initial_capacity=32,
                          store_chunk=128)
            server.reload(cfg2)
            assert server.config.forward_address  # still local
        finally:
            server.shutdown()


# -- one interval of a small zipf-churn mix against a float64 reference ------


def _zipf_draw(rng, universe, lines, s=0.99):
    import numpy as np

    law = np.arange(1, universe + 1, dtype=np.float64) ** -s
    return rng.choice(universe, lines, p=law / law.sum())


def _rank_error(samples, x, q):
    """Float64 NumPy order statistics, nothing of veneur_tpu: how far
    ``q`` lies outside the rank interval of ``x`` among ``samples``; a
    value strictly between two neighbours counts as either."""
    import numpy as np

    s = np.sort(np.asarray(samples, np.float64))
    below, upto = (s < x).sum() / len(s), (s <= x).sum() / len(s)
    if below <= q <= upto:
        return 0.0
    err = min(abs(below - q), abs(upto - q))
    if below == upto and 0 < below < 1:
        err = max(err - 1.0 / len(s), 0.0)
    return err


class TestZipfChurnInterval:
    """6,000 Zipf(0.99) timer lines over 8,192 names, 500 counters, 500
    gauges and 4 top-k streams of 500 lines over 100,000 keys through
    the server's own packet path, store and flusher, with ``store_chunk``
    cut to 512 so that a row's samples span a dozen ingest dispatches
    and a stream's lines four count-min updates: counters, gauges,
    ``count`` / ``min`` / ``max`` exact, percentiles by rank, the top-k
    lists with no count under the exact one and no key left out that
    exceeds a list's last."""

    @pytest.mark.parametrize("seed", [11, 12])
    def test_against_the_reference(self, seed):
        import collections

        import numpy as np

        rng = np.random.default_rng(seed)
        hist = collections.defaultdict(list)
        lines = []
        for name, v in zip(_zipf_draw(rng, 8192, 6000),
                           rng.integers(0, 400000, 6000) / 4.0):
            hist[f"z.{name}"].append(float(np.float32(v)))
            lines.append(f"z.{name}:{float(v)!r}|h")
        counters = rng.integers(1, 1000, 500)
        lines += [f"c.{i}:{v}|c" for i, v in enumerate(counters)]
        gauges = collections.defaultdict(float)
        for i, v in zip(rng.integers(0, 500, 1000),
                        rng.integers(0, 400000, 1000) / 4.0):
            gauges[f"g.{i}"] = float(v)
            lines.append(f"g.{i}:{float(v)!r}|g")
        hot = collections.Counter()
        for stream in range(4):
            for key in _zipf_draw(rng, 100_000, 500):
                hot[(f"hot.{stream}.topk", f"k{key}")] += 1
                lines.append(f"hot.{stream}:k{key}|s|#veneurtopk")
        # timers and top-k lines interleaved as they arrive; a gauge's
        # writes keep their order
        order = rng.permutation(len(lines))
        gauge_at = sorted(i for i in order if "|g" in lines[i])
        it = iter(gauge_at)
        lines = [lines[next(it)] if "|g" in lines[i] else lines[i]
                 for i in order]

        server, sink = make_server(
            store_initial_capacity=8192, store_chunk=512,
            percentiles=[0.5, 0.75, 0.99], topk_k=32)
        try:
            for i in range(0, len(lines), 40):
                server.handle_packet("\n".join(lines[i:i + 40]).encode())
            server.flush()
            batch = sink.get_flush()
        finally:
            server.shutdown()

        got = {}
        topk = collections.defaultdict(dict)
        for m in batch:
            if m.name.endswith(".topk"):
                key = [t for t in m.tags if t.startswith("key:")][0][4:]
                topk[m.name][key] = m.value
            elif not m.name.startswith("veneur."):
                got[m.name] = m.value
        for i, v in enumerate(counters):
            assert got[f"c.{i}"] == float(v)
        for name, v in gauges.items():
            assert got[name] == v
        worst = 0.0
        for name, samples in hist.items():
            assert got[name + ".count"] == len(samples)
            assert got[name + ".min"] == min(samples)
            assert got[name + ".max"] == max(samples)
            for q in (0.5, 0.75, 0.99):
                x = got[f"{name}.{int(q * 100)}percentile"]
                worst = max(worst, _rank_error(samples, x, q))
        assert worst < 0.04, worst
        live = {n for n in got if n.startswith("z.")}
        assert len(live) == 6 * len(hist)       # no row for a name not sent
        for stream in range(4):
            name = f"hot.{stream}.topk"
            listed = topk[name]
            assert len(listed) == 32
            exact = {k: f for (n, k), f in hot.items() if n == name}
            assert not [k for k, c in listed.items() if c < exact[k]]
            last = min(listed.values())
            assert not [(k, f) for k, f in exact.items()
                        if k not in listed and f > last]
