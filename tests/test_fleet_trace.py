"""The fleet trace plane (obs/tracectx.py, obs/fleet.py): the
X-Veneur-Trace cross-hop contract, the ingest-path stage trees and
ingest-era freshness stamps, the hop log, the /debug/fleet keep-last-
good peer aggregation, and /debug/trace stitching local flush →
forward → global import → global flush into one distributed trace.

The load-bearing contracts: a single trace id stitches across
instances; the stitched hop durations union-cover the e2e wall clock;
the ingest stamp survives every hop and becomes
``veneur.fleet.e2e_age_ns`` (exact percentiles through the
self-telemetry digest group); peer pulls and membership are both
keep-last-good; the timeline endpoints survive concurrent readers
against ring-bound eviction.
"""

import json
import socket
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from veneur_tpu.config import Config
from veneur_tpu.core.store import MetricStore
from veneur_tpu.discovery import (FilePeersDiscoverer, RingWatcher,
                                  StaticDiscoverer)
from veneur_tpu.forward import HTTPForwarder
from veneur_tpu.ingest import IngestFleet
from veneur_tpu.obs import FlushTimeline, HopLog, StageRecorder, TraceContext
from veneur_tpu.obs.fleet import FleetAggregator, stitch_trace
from veneur_tpu.obs.tracectx import TRACED_ROUTES
from veneur_tpu.protocol.addr import resolve_addr
from veneur_tpu.server import Server
from veneur_tpu.sinks import ChannelMetricSink

from tests.test_forward import flush_local, local_store_with_data


def _wait(predicate, timeout=20.0, msg="condition"):
    # 1ms poll: the import->global-flush gap in the stitched trace is
    # exactly this wait, and a coarse poll would read as missing hop
    # coverage that the SYSTEM never lost
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return
        time.sleep(0.001)
    raise AssertionError(f"timed out waiting for {msg}")


def get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return r.status, r.read().decode()


# ---------------------------------------------------------------------------
# the context + hop log primitives
# ---------------------------------------------------------------------------


class TestTraceContext:
    def test_encode_decode_round_trip(self):
        ctx = TraceContext(trace_id=123, parent_id=456, ingest_ns=789)
        back = TraceContext.decode(ctx.encode())
        assert (back.trace_id, back.parent_id, back.ingest_ns) \
            == (123, 456, 789)

    def test_decode_tolerates_unknown_fields_and_order(self):
        back = TraceContext.decode("ingest=9;future=1;trace=7;parent=3")
        assert (back.trace_id, back.parent_id, back.ingest_ns) == (7, 3, 9)

    def test_decode_garbage_is_none(self):
        assert TraceContext.decode("") is None
        assert TraceContext.decode("not-a-context") is None
        assert TraceContext.decode("trace=nope;parent=1") is None
        assert TraceContext.decode("parent=1;ingest=2") is None  # no trace

    def test_from_headers_case_insensitive(self):
        ctx = TraceContext(5, 6, 7)
        for key in ("X-Veneur-Trace", "x-veneur-trace"):
            back = TraceContext.from_headers({key: ctx.encode()})
            assert back.trace_id == 5
        assert TraceContext.from_headers({}) is None
        assert TraceContext.from_headers(None) is None

    def test_child_reparents_keeping_trace_and_ingest(self):
        ctx = TraceContext(5, 6, 7)
        child = ctx.child(99)
        assert (child.trace_id, child.parent_id, child.ingest_ns) \
            == (5, 99, 7)

    def test_traced_routes_registry(self):
        # the lint-checked header contract (lint/stagenames.py)
        assert "/import" in TRACED_ROUTES
        assert "/handoff" in TRACED_ROUTES


class TestHopLog:
    def test_record_drain_peek(self):
        hl = HopLog()
        ctx = TraceContext(11, 22, 33)
        hl.record("global.import", ctx, 100.0, 100.5, metrics=4)
        assert hl.peek()[0]["trace_id"] == 11
        assert hl.peek(), "peek must not consume"
        hops = hl.drain()
        assert len(hops) == 1
        h = hops[0]
        assert h["hop"] == "global.import"
        assert h["parent_span_id"] == 22
        assert h["ingest_ns"] == 33
        assert h["duration_ns"] == pytest.approx(5e8)
        assert h["span_id"] > 0
        assert hl.drain() == []

    def test_oldest_ingest_tracking_and_reset(self):
        hl = HopLog()
        hl.record("h", TraceContext(1, 0, 500), 0, 1)
        hl.record("h", TraceContext(2, 0, 300), 0, 1)
        hl.record("h", TraceContext(3, 0, 400), 0, 1)
        assert hl.take_oldest_ingest_ns() == 300
        assert hl.take_oldest_ingest_ns() is None

    def test_untraced_hop_still_records(self):
        hl = HopLog()
        hl.record("global.import", None, 0.0, 0.1, metrics=2)
        h = hl.drain()[0]
        assert "trace_id" not in h and h["metrics"] == 2

    def test_bounded(self):
        hl = HopLog(capacity=16)
        for i in range(40):
            hl.record("h", TraceContext(i + 1, 0, 0), 0, 1)
        assert len(hl.peek()) == 16
        assert hl.dropped_total == 24


class TestRecorderTraceStamp:
    def test_adopted_trace_stamps_the_entry(self):
        rec = StageRecorder()
        rec.adopt_trace(77, span_id=88, parent_id=66, hop="local.flush")
        with rec.stage("store"):
            pass
        entry = rec.finish()
        assert entry["trace_id"] == 77
        assert entry["span_id"] == 88
        assert entry["parent_span_id"] == 66
        assert entry["hop"] == "local.flush"

    def test_unadopted_recorder_stays_unstitched(self):
        rec = StageRecorder()
        entry = rec.finish()
        assert "trace_id" not in entry

    def test_adopt_without_span_id_mints_one(self):
        rec = StageRecorder()
        rec.adopt_trace(5, hop="handoff.send")
        assert rec.span_id > 0


# ---------------------------------------------------------------------------
# stitching
# ---------------------------------------------------------------------------


def _entry(trace_id=None, hop=None, wall=0.0, dur_s=1.0, stages=(),
           import_traces=None, interval=0):
    e = {"wall_start": wall, "wall_end": wall + dur_s,
         "total_duration_ns": int(dur_s * 1e9), "coverage_ratio": 1.0,
         "stages": list(stages), "tree": [], "interval": interval}
    if trace_id is not None:
        e["trace_id"] = trace_id
        e["span_id"] = 1000 + interval
        e["parent_span_id"] = 0
        e["hop"] = hop or "local.flush"
    if import_traces:
        e["import_traces"] = import_traces
        e["hop"] = hop or "global.flush"
    return e


class TestStitchTrace:
    def test_orders_hops_and_union_coverage(self):
        tid = 42
        local = _entry(trace_id=tid, hop="local.flush", wall=100.0,
                       dur_s=1.0, stages=[
                           {"name": "forward", "off_path": True,
                            "start_ns": int(0.9e9),
                            "duration_ns": int(0.3e9), "series": 5}])
        imp = {"hop": "global.import", "trace_id": tid,
               "parent_span_id": 1, "span_id": 2, "ingest_ns": int(95e9),
               "wall_start": 101.3, "wall_end": 101.4,
               "duration_ns": int(0.1e9)}
        gflush = _entry(import_traces=[tid], wall=101.5, dur_s=0.5,
                        interval=3)
        out = stitch_trace(tid, [
            ("local", [local], []),
            ("global", [gflush], [imp]),
        ])
        hops = [h["hop"] for h in out["hops"]]
        assert hops == ["local.flush", "forward", "global.import",
                        "global.flush"]
        # e2e = 100.0 -> 102.0; union covered = [100,101.2] (flush +
        # overlapping forward) + [101.3,101.4] + [101.5,102] = 1.8 of
        # 2.0 — the two 0.1s transport/tick gaps are the holes
        assert out["e2e_wall_ns"] == pytest.approx(2e9)
        assert out["hop_coverage_ratio"] == pytest.approx(0.9, abs=0.01)
        assert len(out["gaps"]) == 2
        for gap in out["gaps"]:
            assert gap["gap_ns"] == pytest.approx(1e8)
        # the propagated ingest stamp -> e2e age at the last hop's end
        assert out["ingest_ns"] == int(95e9)
        assert out["e2e_age_ns"] == pytest.approx((102.0 - 95.0) * 1e9)

    def test_unknown_trace_is_empty(self):
        out = stitch_trace(7, [("x", [_entry(trace_id=9)], [])])
        assert out["hops"] == []

    def test_stage_hops_inside_entries_are_found(self):
        tid = 13
        gentry = _entry(wall=10.0, dur_s=1.0, stages=[
            {"name": "global.import", "trace_id": tid, "off_path": True,
             "start_ns": 0, "duration_ns": int(1e8), "metrics": 3}])
        out = stitch_trace(tid, [("g", [gentry], [])])
        assert out["hops"][0]["hop"] == "global.import"
        assert out["hops"][0]["metrics"] == 3


# ---------------------------------------------------------------------------
# ingest lanes: stage tracing + the ingest-era stamp
# ---------------------------------------------------------------------------


def make_fleet(store, lanes=1, **kw):
    return IngestFleet(store, resolve_addr("udp://127.0.0.1:0"), lanes,
                       1 << 20, 4096, **kw)


def close_fleet(fleet):
    for lane in fleet.lanes:
        try:
            lane.sock.close()
        except OSError:
            pass


class TestIngestTracing:
    def test_stamp_and_stage_counters(self):
        store = MetricStore(initial_capacity=32, chunk=128)
        fleet = make_fleet(store, use_native=False)
        try:
            lane = fleet.lanes[0]
            t0 = time.time_ns()
            lane._stage_python([b"a:1|c", b"b:2.5|g", b"h:3|ms"])
            assert lane._first_stage_wall_ns >= t0
            lane._seal()
            chunk = lane.sealed[0]
            assert t0 <= chunk.ingest_wall_ns <= time.time_ns()
            fleet.merge_sealed()
            assert fleet.take_oldest_ingest_ns() == chunk.ingest_wall_ns
            # read-and-reset: the next interval accumulates its own
            assert fleet.take_oldest_ingest_ns() is None
            stages = fleet.take_ingest_stages()
            assert stages["decode"] > 0
            assert stages["seal"] > 0
            assert stages["lanes"] == 1
            # nothing new accrued -> None (the flusher records no tree)
            assert fleet.take_ingest_stages() is None
        finally:
            close_fleet(fleet)

    def test_next_chunk_gets_a_fresh_stamp(self):
        store = MetricStore(initial_capacity=32, chunk=128)
        fleet = make_fleet(store, use_native=False)
        try:
            lane = fleet.lanes[0]
            lane._stage_python([b"a:1|c"])
            lane._seal()
            first = lane.sealed[-1].ingest_wall_ns
            assert lane._first_stage_wall_ns == 0
            time.sleep(0.002)
            lane._stage_python([b"b:1|c"])
            lane._seal()
            assert lane.sealed[-1].ingest_wall_ns > first
        finally:
            close_fleet(fleet)

    def test_trace_stages_off_keeps_stamp_but_no_counters(self):
        store = MetricStore(initial_capacity=32, chunk=128)
        fleet = make_fleet(store, use_native=False, trace_stages=False)
        try:
            lane = fleet.lanes[0]
            lane._stage_python([b"a:1|c"])
            lane._seal()
            assert lane.sealed[0].ingest_wall_ns > 0  # freshness stays
            assert lane.stage_ns == {"recv": 0, "decode": 0, "stage": 0,
                                     "seal": 0}
            fleet.merge_sealed()
            assert fleet.take_ingest_stages() is None
        finally:
            close_fleet(fleet)

    @pytest.mark.skipif(
        not __import__("veneur_tpu.native", fromlist=["native"]
                       ).available(),
        reason="native library unavailable")
    def test_native_decode_path_counts_decode_and_stage(self):
        store = MetricStore(initial_capacity=32, chunk=128)
        fleet = make_fleet(store, use_native=True)
        try:
            lane = fleet.lanes[0]
            lane._stage_native([b"a:1|c", b"h:2|ms"])
            lane._seal()
            assert lane.stage_ns["decode"] > 0
            assert lane.stage_ns["stage"] > 0
        finally:
            close_fleet(fleet)


# ---------------------------------------------------------------------------
# the forward stamps the header
# ---------------------------------------------------------------------------


class _CaptureHandler(BaseHTTPRequestHandler):
    def log_message(self, *a):
        pass

    def do_POST(self):
        self.server.captured.append(dict(self.headers))
        self.rfile.read(int(self.headers.get("Content-Length") or 0))
        body = b"accepted"
        self.send_response(202)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


def _capture_server():
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _CaptureHandler)
    srv.captured = []
    srv.daemon_threads = True
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv


class TestForwardHeader:
    def test_http_forwarder_stamps_x_veneur_trace(self):
        srv = _capture_server()
        try:
            store = local_store_with_data(n_hist=5)
            _final, fwd_state = flush_local(store)
            fwd = HTTPForwarder(f"127.0.0.1:{srv.server_address[1]}",
                                timeout=5.0)
            fwd.forward(fwd_state,
                        trace_ctx=TraceContext(123, 456, 789))
            assert srv.captured, "nothing POSTed"
            hdr = srv.captured[0].get("X-Veneur-Trace")
            assert hdr == "trace=123;parent=456;ingest=789"
        finally:
            srv.shutdown()
            srv.server_close()

    def test_no_ctx_no_header(self):
        srv = _capture_server()
        try:
            store = local_store_with_data(n_hist=5)
            _final, fwd_state = flush_local(store)
            fwd = HTTPForwarder(f"127.0.0.1:{srv.server_address[1]}",
                                timeout=5.0)
            fwd.forward(fwd_state)
            assert "X-Veneur-Trace" not in srv.captured[0]
        finally:
            srv.shutdown()
            srv.server_close()


# ---------------------------------------------------------------------------
# local -> global over HTTP: one trace id end to end
# ---------------------------------------------------------------------------


@pytest.fixture()
def local_global():
    gcfg = Config(statsd_listen_addresses=[], interval="86400s",
                  http_address="127.0.0.1:0", percentiles=[0.5, 0.99],
                  aggregates=["count"], store_initial_capacity=32,
                  store_chunk=128)
    gsink = ChannelMetricSink()
    g = Server(gcfg, metric_sinks=[gsink])
    g.start()
    lcfg = Config(statsd_listen_addresses=[], interval="86400s",
                  http_address="127.0.0.1:0",
                  forward_address=f"http://127.0.0.1:{g.ops_server.port}",
                  aggregates=["count"], store_initial_capacity=32,
                  store_chunk=128)
    lsink = ChannelMetricSink()
    lo = Server(lcfg, metric_sinks=[lsink])
    lo.start()
    yield g, gsink, lo, lsink
    lo.shutdown()
    g.shutdown()


class TestEndToEndStitch:
    def test_single_trace_id_stitches_all_hops(self, local_global):
        g, gsink, lo, lsink = local_global
        # a warm-up interval through every hop first: the first
        # local flush, forward, import and global flush compile and
        # import their code between hops, and that wall-clock is
        # set-up, not a gap in the traced interval's hop coverage
        lo.handle_metric_packet(b"fleet.warm:3|c|#veneurglobalonly")
        lo.handle_metric_packet(b"local.only:1|c")
        lo.flush()
        lsink.get_flush()
        _wait(lambda: g.obs_hops.snapshot()["pending"] >= 1,
              msg="warm-up import hop")
        g.flush()
        gsink.get_flush()

        for i in range(5):
            lo.handle_metric_packet(
                f"fleet.c{i}:3|c|#veneurglobalonly".encode())
        # a host-local metric too, so the local flush reaches its sink
        lo.handle_metric_packet(b"local.only:1|c")
        lo.flush()
        lsink.get_flush()
        lentry = lo.obs_timeline.entries()[-1]
        assert lentry["hop"] == "local.flush"
        tid = lentry["trace_id"]
        assert tid > 0
        # the forward runs off the flush thread; the import hop lands
        # in the global's hop log when the POST completes
        _wait(lambda: g.obs_hops.snapshot()["pending"] >= 1,
              msg="import hop")
        assert g.obs_hops.peek()[0]["trace_id"] == tid
        g.flush()
        gsink.get_flush()
        gentry = g.obs_timeline.entries()[-1]
        assert gentry["hop"] == "global.flush"
        assert tid in gentry["import_traces"]
        # the propagated ingest stamp became the e2e freshness measure
        assert gentry["e2e_age_ns"] > 0
        import_stages = [s for s in gentry["stages"]
                         if s["name"] == "global.import"]
        assert import_stages and import_stages[0]["trace_id"] == tid
        assert import_stages[0]["off_path"]

        # stitch on the global, with the local as a /debug/fleet peer
        g.fleet_aggregator.watcher = RingWatcher(
            StaticDiscoverer([f"127.0.0.1:{lo.ops_server.port}"]), "t")
        status, body, _ctype = g.fleet_aggregator.trace_route(
            {"id": str(tid)})
        assert status == 200
        data = json.loads(body)
        hops = [h["hop"] for h in data["hops"]]
        assert "local.flush" in hops
        assert "forward" in hops
        assert "global.import" in hops
        assert "global.flush" in hops
        # hop order follows the wall clock
        assert hops.index("local.flush") < hops.index("global.import") \
            < hops.index("global.flush")
        # hop durations union-cover the e2e wall clock (the bench
        # drive gates this at 0.9; in-test the import->flush gap is
        # scheduler noise, so a slightly looser floor avoids flakes)
        assert data["hop_coverage_ratio"] >= 0.8
        assert data["e2e_age_ns"] > 0

    def test_e2e_age_emitted_through_self_telemetry(self, local_global):
        g, gsink, lo, lsink = local_global
        lo.handle_metric_packet(b"fleet.x:1|c|#veneurglobalonly")
        lo.handle_metric_packet(b"local.only:1|c")
        lo.flush()
        lsink.get_flush()
        _wait(lambda: g.obs_hops.snapshot()["pending"] >= 1,
              msg="import hop")
        g.flush()   # samples e2e into the self-telemetry group
        gsink.get_flush()
        g.flush()   # the next interval emits the digest rows
        metrics = gsink.get_flush()
        names = {m.name for m in metrics}
        assert "veneur.fleet.e2e_age_ns.50percentile" in names
        assert "veneur.fleet.e2e_age_ns.99percentile" in names
        row = next(m for m in metrics
                   if m.name == "veneur.fleet.e2e_age_ns.50percentile")
        assert row.value > 0
        assert "stage:e2e" in row.tags

    def test_debug_trace_endpoint_and_unknown_id(self, local_global):
        g, _gsink, lo, lsink = local_global
        lo.handle_metric_packet(b"fleet.y:1|c|#veneurglobalonly")
        lo.handle_metric_packet(b"local.only:1|c")
        lo.flush()
        lsink.get_flush()
        tid = lo.obs_timeline.entries()[-1]["trace_id"]
        _wait(lambda: g.obs_hops.snapshot()["pending"] >= 1,
              msg="import hop")
        # pending (not yet drained into an entry) hops stitch too
        status, body = get(g.ops_server.port, f"/debug/trace?id={tid}")
        assert status == 200
        data = json.loads(body)
        assert any(h["hop"] == "global.import" and h.get("pending")
                   for h in data["hops"])
        import urllib.error

        with pytest.raises(urllib.error.HTTPError) as e:
            get(g.ops_server.port, "/debug/trace?id=999999999")
        assert e.value.code == 404
        with pytest.raises(urllib.error.HTTPError) as e:
            get(g.ops_server.port, "/debug/trace?id=nope")
        assert e.value.code == 400

    def test_debug_fleet_pulls_local_peer(self, local_global):
        g, _gsink, lo, lsink = local_global
        lo.handle_metric_packet(b"fleet.z:1|c")
        lo.flush()
        lsink.get_flush()
        peer = f"127.0.0.1:{lo.ops_server.port}"
        g.fleet_aggregator.watcher = RingWatcher(
            StaticDiscoverer([peer]), "t")
        status, body = get(g.ops_server.port, "/debug/fleet?refresh=1")
        assert status == 200
        data = json.loads(body)
        assert peer in data["peers"]
        assert data["peers"][peer]["ok"] is True
        assert data["peers"][peer]["published_total"] >= 1
        assert data["peers"][peer]["last_interval"]["coverage_ratio"] \
            is not None


# ---------------------------------------------------------------------------
# keep-last-good peer pulls + concurrent readers
# ---------------------------------------------------------------------------


class _PeerHandler(BaseHTTPRequestHandler):
    def log_message(self, *a):
        pass

    def do_GET(self):
        if self.path.startswith("/debug/flush-timeline"):
            body = json.dumps(self.server.timeline_body).encode()
        else:
            body = b"{}"
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


def _peer_server(published=7):
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _PeerHandler)
    srv.timeline_body = {
        "published_total": published, "ring_capacity": 64,
        "intervals": [{"interval": published - 1,
                       "total_duration_ns": 1000,
                       "coverage_ratio": 0.99, "stages": []}]}
    srv.daemon_threads = True
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv


class TestFleetAggregatorKeepLastGood:
    def test_pull_then_peer_death_serves_stale(self, tmp_path):
        peer_srv = _peer_server()
        addr = f"127.0.0.1:{peer_srv.server_address[1]}"
        peers_file = tmp_path / "peers"
        peers_file.write_text(addr + "\n")
        agg = FleetAggregator(
            self_addr="me",
            watcher=RingWatcher(FilePeersDiscoverer(str(peers_file)),
                                "t"),
            pull_interval=0.0, pull_timeout=1.0)
        agg.refresh(force=True)
        _status, body, _ = agg.fleet_route({})
        data = json.loads(body)
        assert data["peers"][addr]["ok"] is True
        assert data["peers"][addr]["published_total"] == 7
        # kill the peer: the next pull fails but the LAST GOOD pull is
        # served, marked stale
        peer_srv.shutdown()
        peer_srv.server_close()
        agg.refresh(force=True)
        _status, body, _ = agg.fleet_route({})
        data = json.loads(body)
        assert data["peers"][addr]["stale"] is True
        assert data["peers"][addr]["published_total"] == 7  # last good
        assert agg.pull_errors_total >= 1

    def test_file_peer_set_change_mid_pull(self, tmp_path):
        a = _peer_server(published=3)
        b = _peer_server(published=5)
        addr_a = f"127.0.0.1:{a.server_address[1]}"
        addr_b = f"127.0.0.1:{b.server_address[1]}"
        peers_file = tmp_path / "peers"
        peers_file.write_text(addr_a + "\n")
        agg = FleetAggregator(
            self_addr="me",
            watcher=RingWatcher(FilePeersDiscoverer(str(peers_file)),
                                "t"),
            pull_interval=0.0, pull_timeout=1.0)
        try:
            agg.refresh(force=True)
            assert json.loads(agg.fleet_route({})[1])["peers"].keys() \
                == {addr_a}
            # the operator rewrites the file: next refresh sees the new
            # set (FilePeersDiscoverer re-reads per refresh)
            peers_file.write_text(addr_b + "\n")
            agg.refresh(force=True)
            data = json.loads(agg.fleet_route({})[1])
            assert set(data["peers"]) == {addr_b}  # departed peer pruned
            assert data["peers"][addr_b]["published_total"] == 5
            # membership keep-last-good: an unreadable file keeps the
            # previous member set (and its cached pulls)
            peers_file.unlink()
            agg.refresh(force=True)
            data = json.loads(agg.fleet_route({})[1])
            assert set(data["peers"]) == {addr_b}
            assert data["members"] == [addr_b]
        finally:
            b.shutdown()
            b.server_close()

    def test_pull_rate_limit(self):
        clock = [0.0]
        agg = FleetAggregator(self_addr="me", watcher=None,
                              pull_interval=5.0,
                              clock=lambda: clock[0])
        agg.refresh()          # first pull window opens
        t0 = agg._last_pull
        agg.refresh()          # inside the window: no new round
        assert agg._last_pull == t0
        clock[0] = 6.0
        agg.refresh()
        assert agg._last_pull == 6.0

    def test_self_pull_not_stitched_twice(self):
        """fleet_peers lists EVERY instance including the puller
        (handoff_self is empty in tracing-only deployments, so no
        address can tell) — the timeline's per-process uid recognizes
        the self-pull, and /debug/trace never duplicates a hop."""
        tl = FlushTimeline(intervals=4)
        rec = StageRecorder()
        rec.adopt_trace(909, hop="local.flush")
        tl.publish(rec.finish())
        # membership lists both "instances" (dead ports: the failed
        # re-pull keeps the seeded last-good entries, marked stale)
        agg = FleetAggregator(
            self_addr="", timeline=tl, pull_timeout=0.2,
            watcher=RingWatcher(
                StaticDiscoverer(["127.0.0.1:1", "127.0.0.1:2"]), "t"))
        # a pull of ourselves (same uid) and a real peer (another uid)
        peer_tl = FlushTimeline(intervals=4)
        agg._cache["127.0.0.1:1"] = {
            "ok": True, "stale": False,
            "timeline": {"instance_uid": tl.uid,
                         "intervals": tl.entries()}}
        agg._cache["127.0.0.1:2"] = {
            "ok": True, "stale": False,
            "timeline": {"instance_uid": peer_tl.uid, "intervals": []}}
        origins = [src[0] for src in agg._sources()]
        assert origins == ["self", "127.0.0.1:2"]
        stitched = stitch_trace(909, agg._sources())
        assert len(stitched["hops"]) == 1  # not doubled
        _status, body, _ct = agg.fleet_route({})
        peers = json.loads(body)["peers"]
        assert peers["127.0.0.1:1"]["self"] is True
        assert peers["127.0.0.1:2"]["self"] is False


class TestConcurrentReaders:
    def test_timeline_readers_survive_ring_eviction(self):
        tl = FlushTimeline(intervals=4)
        stop = threading.Event()
        errors = []

        def read():
            while not stop.is_set():
                try:
                    tl.entries()
                    tl.handler({"n": "3"})
                    tl.snapshot()
                except Exception as e:  # pragma: no cover - the bug
                    errors.append(e)
                    return

        threads = [threading.Thread(target=read) for _ in range(4)]
        for t in threads:
            t.start()
        for i in range(3000):
            tl.publish({"total_duration_ns": i, "coverage_ratio": 1.0,
                        "stages": [], "tree": []})
        stop.set()
        for t in threads:
            t.join(timeout=10)
        assert not errors, errors[:1]
        assert len(tl.entries()) == 4
        assert tl.published_total == 3000

    def test_debug_fleet_concurrent_with_publishes(self, tmp_path):
        peer_srv = _peer_server()
        addr = f"127.0.0.1:{peer_srv.server_address[1]}"
        tl = FlushTimeline(intervals=4)
        agg = FleetAggregator(
            self_addr="me", timeline=tl, hop_log=HopLog(),
            watcher=RingWatcher(StaticDiscoverer([addr]), "t"),
            pull_interval=0.0, pull_timeout=1.0)
        stop = threading.Event()
        errors = []

        def read():
            while not stop.is_set():
                try:
                    status, _body, _ = agg.fleet_route({"refresh": "1",
                                                        "n": "2"})
                    assert status == 200
                except Exception as e:  # pragma: no cover
                    errors.append(e)
                    return

        threads = [threading.Thread(target=read) for _ in range(3)]
        for t in threads:
            t.start()
        try:
            for i in range(500):
                tl.publish({"total_duration_ns": i,
                            "coverage_ratio": 1.0, "stages": [],
                            "tree": []})
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10)
            peer_srv.shutdown()
            peer_srv.server_close()
        assert not errors, errors[:1]


# ---------------------------------------------------------------------------
# the handoff hop
# ---------------------------------------------------------------------------


class TestHandoffHop:
    def test_receiver_records_trace_hop(self):
        from veneur_tpu.fleet.handoff import HandoffManager, \
            encode_handoff

        store = MetricStore(initial_capacity=32, chunk=128)
        donor = MetricStore(initial_capacity=32, chunk=128)
        from veneur_tpu.samplers.parser import MetricKey

        for i in range(4):
            donor.import_counter(
                MetricKey(name=f"m{i}", type="counter",
                          joined_tags=""), [], 5)
        groups = {"global_counters":
                  donor.global_counters.snapshot_state()}
        blob = encode_handoff(groups, {"id": "t-1", "sender": "x",
                                       "epoch": 1, "series": 4}, 0.0)
        hop_log = HopLog()
        mgr = HandoffManager(store, "self",
                             RingWatcher(StaticDiscoverer(["self"]),
                                         "t"),
                             hop_log=hop_log)
        ctx = TraceContext(321, 654, 0)
        status, body, _ = mgr.handle_handoff(
            blob, headers={"X-Veneur-Trace": ctx.encode()})
        assert status == 200 and json.loads(body)["merged"] == 4
        hop = hop_log.drain()[0]
        assert hop["hop"] == "handoff.receive"
        assert hop["trace_id"] == 321
        assert hop["parent_span_id"] == 654
        assert hop["series"] == 4

    def test_sender_entry_carries_handoff_trace(self):
        """A live transition's timeline entry is a stitched
        handoff.send hop, and the receiver's hop parents under it."""
        from veneur_tpu.fleet.handoff import HandoffManager

        from tests.test_handoff import (MutableDiscoverer,
                                        make_handoff_global)

        a, _sink_a, addr_a = make_handoff_global("tra")
        b, _sink_b, addr_b = make_handoff_global("trb")
        try:
            disc = MutableDiscoverer([addr_a])
            mgr = a.handoff_manager
            mgr.watcher = RingWatcher(disc, "test")
            mgr.refresh()
            from veneur_tpu.samplers.parser import MetricKey

            for i in range(20):
                a.store.import_counter(
                    MetricKey(name=f"m{i}", type="counter",
                              joined_tags=""), [], 3)
            disc.members = [addr_a, addr_b]
            summary = mgr.refresh()
            assert summary["sent"] == [addr_b]
            entries = [e for e in a.obs_timeline.entries()
                       if e.get("kind") == "handoff"]
            assert entries
            sender_entry = entries[-1]
            assert sender_entry["hop"] == "handoff.send"
            tid = sender_entry["trace_id"]
            assert tid > 0
            recv_hops = b.obs_hops.peek()
            assert recv_hops
            assert recv_hops[0]["trace_id"] == tid
            assert recv_hops[0]["parent_span_id"] \
                == sender_entry["span_id"]
            # one id stitches sender extract/stream + receiver merge
            stitched = stitch_trace(tid, [
                ("a", a.obs_timeline.entries(), []),
                ("b", [], b.obs_hops.peek())])
            hops = [h["hop"] for h in stitched["hops"]]
            assert "handoff.send" in hops
            assert "handoff.receive" in hops
        finally:
            a.shutdown()
            b.shutdown()


# ---------------------------------------------------------------------------
# the proxy fan-out hop
# ---------------------------------------------------------------------------


class TestProxyFanOutHop:
    def _proxy(self):
        from veneur_tpu.config import ProxyConfig
        from veneur_tpu.proxy import Proxy

        proxy = Proxy(ProxyConfig(http_address="127.0.0.1:0",
                                  forward_timeout="5s", retry_max=0),
                      discoverer=StaticDiscoverer(["d1", "d2"]))
        proxy.refresh_destinations()
        posts = []
        lock = threading.Lock()

        def fake_post(url, batch, headers=None, **kw):
            with lock:
                posts.append((url, len(batch), dict(headers or {})))
            return 202

        proxy._post = fake_post
        return proxy, posts

    def test_fan_out_reparents_header_and_publishes_hop(self):
        """A trace-bearing batch through the proxy publishes a
        ``proxy.fan_out`` hop entry into the proxy's own timeline, and
        every destination POST carries the context RE-PARENTED under
        the fan-out's span — the global's import then parents under
        the proxy hop, not under the local flush it already left."""
        proxy, posts = self._proxy()
        ctx = TraceContext(trace_id=777, parent_id=111,
                           ingest_ns=123456789)
        metrics = [{"name": f"m{i}", "type": "counter", "tags": [],
                    "value": 1} for i in range(32)]
        proxy.proxy_metrics(metrics, trace_header=ctx.encode())
        entries = proxy.obs_timeline.entries()
        assert len(entries) == 1
        entry = entries[0]
        assert entry["hop"] == "proxy.fan_out"
        assert entry["trace_id"] == 777
        assert entry["parent_span_id"] == 111
        assert entry["items"] == 32
        assert entry["destinations"] == 2
        assert posts
        for _url, _n, headers in posts:
            fwd = TraceContext.decode(headers["X-Veneur-Trace"])
            assert fwd.trace_id == 777
            assert fwd.parent_id == entry["span_id"]
            assert fwd.ingest_ns == 123456789  # stamp rides untouched
        # each destination's POST is a child stage of the hop
        stage_names = {s["name"] for s in entry["stages"]}
        assert {"post.d1", "post.d2"} <= stage_names
        # and /debug/trace stitches the proxy hop by the shared id
        stitched = stitch_trace(777, [
            ("proxy", proxy.obs_timeline.entries(), [])])
        assert [h["hop"] for h in stitched["hops"]] == ["proxy.fan_out"]

    def test_untraced_batch_publishes_nothing(self):
        """No header, no hop: legacy senders cost the proxy zero
        tracing work (no recorder, no timeline entry)."""
        proxy, posts = self._proxy()
        proxy.proxy_metrics([{"name": "m", "type": "counter",
                              "tags": [], "value": 1}])
        assert posts
        assert all(h.get("X-Veneur-Trace") is None
                   for _u, _n, h in posts)
        assert proxy.obs_timeline.entries() == []
