"""The feeds (``benchmark/feeds/``) without the server behind them: the
UDP feed keeps the schedule of the sender it was made from, the forwards'
bytes decode with the program's own generated classes field for field,
and the gRPC feed fires every message at its own due time, the late
forwarders' after the tick. A few seconds.

    python -m pytest benchmark/tests/test_feeds.py -q
"""

import socket
import time
from concurrent import futures

import grpc
import numpy as np
import pytest
from google.protobuf import unknown_fields

from benchmark.feeds import forward_grpc, udp_statsd
from benchmark.generators import forwarded_groups, series_groups

MIX = {"guard_s": 0.5, "forwarders": 8, "stagger": True, "late_share": 0.25,
       "late_after_s": 0.05, "message_metrics": 150, "compression": 100,
       "groups": [
           {"prefix": "t.h.", "type": "h", "series": 100, "fan_in": 4,
            "samples": 16, "values": {"dist": "lognormal_64ths", "mu": 3.0,
                                      "sigma": 0.25, "scale_low": 0.5,
                                      "scale_high": 20.0}},
           {"prefix": "t.p.", "type": "h", "series": 8, "fan_in": 1,
            "samples": 1, "values": {"dist": "quarters", "high": 400000}},
           {"prefix": "t.c.", "type": "c", "series": 100, "fan_in": 4,
            "values": {"dist": "integers", "low": 1, "high": 100000}},
           {"prefix": "t.g.", "type": "g", "series": 100, "fan_in": 8,
            "values": {"dist": "quarters", "high": 400000}},
           {"prefix": "t.m.", "type": "c", "marker": True, "series": 16}]}


# -- udp_statsd -----------------------------------------------------------


class SenderAsItWas:
    """``lib/load.py``'s ``Sender`` at the parent commit, word for word."""

    def __init__(self, port: int, sockets: int):
        self.addr = ("127.0.0.1", port)
        self.socks = [socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
                      for _ in range(sockets)]

    def send(self, datagrams: list, start: float, span_s: float) -> list:
        total = sum(n for _p, n in datagrams) or 1
        log, done = [], 0
        for i, (payload, n) in enumerate(datagrams):
            due = start + span_s * done / total
            now = time.time()
            if now < due:
                time.sleep(due - now)
                now = time.time()
            self.socks[i % len(self.socks)].sendto(payload, self.addr)
            log.append((due, now, n))
            done += n
        return log

    def close(self):
        for s in self.socks:
            s.close()


def _drain(sock, n):
    got = [sock.recvfrom(65536) for _ in range(n)]
    return [payload for payload, _a in got], [a[1] for _p, a in got]


def test_udp_feed_keeps_the_senders_schedule():
    units = series_groups.build(
        {"datagram_bytes": 1400, "groups": [
            {"prefix": "t.h.", "type": "h", "series": 700, "samples": 2,
             "values": {"dist": "quarters", "high": 400000}},
            {"prefix": "t.c.", "type": "c", "series": 90, "samples": 1,
             "values": {"dist": "integers", "low": 1, "high": 1000}}]},
        2**31 + 11, 1).units
    assert len(units) > 8
    logs, wire = [], []
    start = time.time() + 0.05
    for make in (lambda port: SenderAsItWas(port, 4),
                 lambda port: udp_statsd.Feed({"statsd_port": port},
                                              {"sockets": 4})):
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as sink:
            sink.bind(("127.0.0.1", 0))
            sink.settimeout(5.0)
            sender = make(sink.getsockname()[1])
            try:
                logs.append(sender.send(units, start, 0.2))
                payloads, ports = _drain(sink, len(units))
            finally:
                sender.close()
            by_port = {p: i for i, p in enumerate(dict.fromkeys(ports))}
            wire.append((payloads, [by_port[p] for p in ports]))
    was, now = logs
    # the same function of the same inputs: due times and lines to the
    # last bit, the same bytes in the same order from the same sockets
    assert [(due, n) for due, _s, n in now] == \
        [(due, n) for due, _s, n in was]
    assert wire[0] == wire[1]
    assert all(sent >= due for due, sent, _n in now[1:])
    assert udp_statsd.PORTS == {"statsd_port": socket.SOCK_DGRAM}


# -- the forwards' bytes --------------------------------------------------


def test_encoder_decodes_field_for_field_with_the_programs_classes():
    # a test may import the program; the harness may not
    from veneur_tpu.protocol import forward_pb2, metricpb_pb2

    groups = MIX["groups"]
    rnd = forwarded_groups.build(MIX, 2**31 + 7, 2)
    per = forwarded_groups.messages_per_forwarder(MIX)
    assert per == 2 and len(rnd.units) == 8 * per
    seen = {g: np.zeros(np.shape(rnd.slot[g]), dtype=int)
            for g, grp in enumerate(groups) if not grp.get("marker")}
    slots_of = {}
    for payload, entries, forwarder, _share, _after in rnd.units:
        forwards = forward_pb2.MetricList.FromString(payload)
        assert len(forwards.metrics) == entries
        # canonical bytes: the program's encoder writes the same
        assert forwards.SerializeToString() == payload
        marker = forwards.metrics[-1]
        assert marker.name.startswith("t.m.")
        slot = int(marker.name[len("t.m."):])
        assert slot // per == forwarder
        slots_of.setdefault(forwarder, []).append(slot)
        assert marker.WhichOneof("value") == "counter"
        assert marker.counter.value == entries == rnd.entries[slot]
        for m in forwards.metrics:
            g = [i for i, grp in enumerate(groups)
                 if m.name.startswith(grp["prefix"])][0]
            grp = groups[g]
            assert m.tags == []
            digits = m.name[len(grp["prefix"]):]
            assert len(digits) == len(str(grp["series"] - 1))
            scope = [(u.field_number, u.data)
                     for u in unknown_fields.UnknownFieldSet(m)]
            if grp.get("marker"):
                assert scope == [(9, 2)]
                continue
            i = int(digits)
            j = [j for j in range(rnd.slot[g].shape[1])
                 if rnd.slot[g][i, j] == slot and not seen[g][i, j]][0]
            seen[g][i, j] += 1
            want = rnd.values[g][i, j]
            if grp["type"] == "h":
                # mixed scope is the enum's zero: not on the wire
                assert scope == []
                assert m.type == metricpb_pb2.Histogram
                d = m.histogram.t_digest
                assert [c.mean for c in d.main_centroids] == sorted(want)
                assert all(c.weight == 1.0 and not c.samples
                           for c in d.main_centroids)
                assert d.compression == MIX["compression"]
                assert (d.min, d.max) == (want.min(), want.max())
                assert not d.packed_means and not d.quantized_means
            elif grp["type"] == "c":
                assert scope == [(9, 2)]            # metricpb.Scope.Global
                assert m.type == metricpb_pb2.Counter
                assert m.counter.value == want
            else:
                assert scope == [(9, 2)]
                assert m.type == metricpb_pb2.Gauge
                assert m.gauge.value == want
    # every entry of every series went out once, in its own message
    assert all((s == 1).all() for s in seen.values())
    assert sorted(sum(slots_of.values(), [])) == list(range(8 * per))


def test_every_seed_gives_the_same_names_and_sizes():
    a = forwarded_groups.build(MIX, 3, 1)
    b = forwarded_groups.build(MIX, 2**31 + 9, 4)
    assert a.lines == b.lines == (100 * 4 + 8 + 100 * 4 + 100 * 8 + 16)
    assert sorted(a.entries) == sorted(b.entries)
    assert [u[1:] for u in a.units] == [u[1:] for u in b.units]
    assert [u[0] for u in a.units] != [u[0] for u in b.units]


# -- forward_grpc ---------------------------------------------------------


class Importer(grpc.GenericRpcHandler):
    """Stands where the server's import service does: takes the bytes,
    stamps the clock, answers with an empty message."""

    def __init__(self):
        self.got = []

    def service(self, details):
        if details.method != forward_grpc.METHOD:
            return None
        return grpc.unary_unary_rpc_method_handler(
            self._take, request_deserializer=None, response_serializer=None)

    def _take(self, request, context):
        self.got.append((time.time(), request))
        return b""


@pytest.fixture
def importer():
    handler = Importer()
    server = grpc.server(futures.ThreadPoolExecutor(max_workers=8),
                         handlers=[handler])
    port = server.add_insecure_port("127.0.0.1:0")
    server.start()
    yield handler, port
    server.stop(None)


def test_forwarders_fire_at_their_own_offsets_and_the_late_after_the_tick(
        importer):
    handler, port = importer
    rnd = forwarded_groups.build(MIX, 5, 1)
    span, guard = 0.8, MIX["guard_s"]
    feed = forward_grpc.Feed({"grpc_port": port}, MIX)
    try:
        start = time.time() + 0.05
        log = feed.send(rnd.units, start, span)
        # the six forwarders that are meant for this tick have their
        # replies; the two late ones are still to be fired
        returned = time.time()
        assert returned < start + span + guard
        assert len(log) == 12 and len(handler.got) == 12
        # the next round's call hands them on, once they were fired
        time.sleep(start + span + guard + 0.3 - time.time())
        log += feed.send([], time.time(), 0.0)
    finally:
        feed.close()
    assert len(log) == 16 and len(handler.got) == 16
    assert sum(n for _d, _s, n in log) == rnd.lines == feed.entries
    due = np.array(sorted(d for d, _s, _n in log))
    want = np.sort(rnd.due(start, span, span + guard))
    assert np.allclose(due, want, atol=1e-9)
    on_time = want[:12] - start
    assert np.allclose(on_time, np.repeat(np.arange(6) / 8 * span, 2))
    assert np.allclose(want[12:] - (start + span + guard),
                       [0.05, 0.05, 0.1, 0.1])
    # open loop: none fired before it was due, none more than a little after
    assert all(-1e-6 <= sent - d < 0.25 for d, sent, _n in log)
    assert sorted(p for _t, p in handler.got) \
        == sorted(u[0] for u in rnd.units)
    assert not feed.refused and feed.messages == 16
