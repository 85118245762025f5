"""Native egress codecs + columnar flush path.

Covers veneur_tpu/native/veneur_egress.cpp through native/egress.py:
Datadog series JSON correctness vs the Python sink's finalize rules
(sinks/datadog/datadog.go:245-330), MetricList encode/decode round-trips
vs python-protobuf (forwardrpc/metricpb wire), the import intern table,
and the columnar flush producing the same metrics as the legacy per-row
path.
"""

import json
import zlib

import numpy as np
import pytest

from veneur_tpu.native import egress

pytestmark = pytest.mark.skipif(not egress.available(),
                                reason="no native toolchain")


def arenas(strs):
    from veneur_tpu.core.columnar import build_arenas

    return build_arenas(strs)


class TestDDSeriesJSON:
    def _one(self, name="m.x", tags="", value=1.5, type_code=0,
             suffix=b"", **kw):
        kw.setdefault("timestamp", 1000)
        kw.setdefault("interval", 10)
        kw.setdefault("default_host", "h0")
        bodies = egress.dd_series_bodies(
            arenas([name]), arenas([tags]), [suffix],
            np.array([0], np.uint32), np.array([0], np.uint8),
            np.array([value], np.float64), np.array([type_code], np.uint8),
            **kw)
        assert len(bodies) == 1
        return json.loads(zlib.decompress(bodies[0]))["series"]

    def test_gauge_shape_matches_reference_ddmetric(self):
        (m,) = self._one(name="svc.lat", tags="env:prod,route:r1")
        assert m == {"metric": "svc.lat", "points": [[1000, 1.5]],
                     "tags": ["env:prod", "route:r1"], "type": "gauge",
                     "host": "h0", "interval": 10}

    def test_counter_becomes_rate(self):
        (m,) = self._one(type_code=1, value=0.3)
        assert m["type"] == "rate" and m["points"][0][1] == 0.3

    def test_magic_host_device_tags(self):
        (m,) = self._one(tags="host:db7,device:sda,a:b")
        assert m["host"] == "db7" and m["device_name"] == "sda"
        assert m["tags"] == ["a:b"]

    def test_empty_tags_omitted(self):
        (m,) = self._one(tags="")
        assert "tags" not in m and "device_name" not in m

    def test_common_tags_prepended(self):
        (m,) = self._one(tags="a:b", common_tags_json=b'"team:x","q:1"')
        assert m["tags"] == ["team:x", "q:1", "a:b"]

    def test_json_escaping(self):
        (m,) = self._one(name='bad"na\\me\n', tags='k:v"w')
        assert m["metric"] == 'bad"na\\me\n'
        assert m["tags"] == ['k:v"w']

    def test_suffix_appended(self):
        (m,) = self._one(suffix=b".99percentile")
        assert m["metric"] == "m.x.99percentile"

    def test_integer_and_float_formatting(self):
        for v, want in ((7.0, 7), (-3.0, -3), (0.125, 0.125),
                        (123.456, 123.456), (1e-3, 0.001),
                        (float("nan"), 0), (float("inf"), 0)):
            (m,) = self._one(value=v)
            got = m["points"][0][1]
            if want:
                assert got == pytest.approx(want, rel=1e-8), (v, got)
            else:
                assert got == want, (v, got)

    def test_float32_values_roundtrip(self):
        # every flush value derives from float32 planes; 9 significant
        # digits must reproduce them exactly
        rng = np.random.default_rng(0)
        vals = rng.gamma(2.0, 50.0, 256).astype(np.float32)
        bodies = egress.dd_series_bodies(
            arenas(["m"] * 256), arenas([""] * 256), [b""],
            np.arange(256, dtype=np.uint32), np.zeros(256, np.uint8),
            vals.astype(np.float64), np.zeros(256, np.uint8),
            timestamp=1, interval=10, default_host="h")
        got = [m["points"][0][1]
               for m in json.loads(zlib.decompress(bodies[0]))["series"]]
        assert np.array_equal(np.asarray(got, np.float32), vals)

    def test_chunking_by_max_per_body(self):
        n = 10
        bodies = egress.dd_series_bodies(
            arenas(["m"] * n), arenas([""] * n), [b""],
            np.arange(n, dtype=np.uint32), np.zeros(n, np.uint8),
            np.ones(n), np.zeros(n, np.uint8),
            timestamp=1, interval=10, default_host="h", max_per_body=4)
        assert len(bodies) == 3
        sizes = [len(json.loads(zlib.decompress(b))["series"])
                 for b in bodies]
        assert sizes == [4, 4, 2]

    def test_uncompressed_mode(self):
        bodies = egress.dd_series_bodies(
            arenas(["m"]), arenas([""]), [b""],
            np.array([0], np.uint32), np.array([0], np.uint8),
            np.array([2.0]), np.array([0], np.uint8),
            timestamp=1, interval=10, default_host="h", compress_level=0)
        assert json.loads(bodies[0])["series"][0]["points"][0][1] == 2.0


class TestDDBodiesSideBySide:
    """A block's bodies are encoded and deflated by several workers
    inside one native call, and nobody can tell from the bytes."""

    SUFFIXES = [b".min", b".max", b".count", b".50percentile",
                b".99percentile", b""]

    def _block(self, n_bodies, ragged, per_body=12):
        """Emissions for ``n_bodies`` bodies of ``per_body`` (the last
        one short where ``ragged``) over rows that take every turn of
        the per-row pre-pass: escaped names, ``host:`` / ``device:``
        tags, no tags at all."""
        nem = n_bodies * per_body - (5 if ragged else 0)
        nrows = max(1, nem // 3)
        names = ['svc"%d\\lat\n' % r if r % 4 == 0 else "svc.%d.lat" % r
                 for r in range(nrows)]
        tags = [("", "env:prod,host:db%d" % r, "device:sd%d,k:v\"%d" % (r, r),
                 "a:b,host:h,device:d,c:%d" % r)[r % 4] for r in range(nrows)]
        rng = np.random.default_rng(nem)
        return dict(
            names=arenas(names), tags=arenas(tags), suffixes=self.SUFFIXES,
            em_rows=rng.integers(0, nrows, nem).astype(np.uint32),
            em_suffix=rng.integers(0, len(self.SUFFIXES), nem).astype(
                np.uint8),
            em_values=rng.gamma(2.0, 50.0, nem).astype(np.float32).astype(
                np.float64),
            em_type=(rng.random(nem) < 0.3).astype(np.uint8),
            timestamp=1000, interval=10, default_host="h0",
            common_tags_json=b'"team:x","q:\\"1"', max_per_body=per_body)

    @pytest.mark.parametrize("level", [0, 1])
    @pytest.mark.parametrize("workers", [1, 2, 8, 64])
    @pytest.mark.parametrize("n_bodies,ragged", [
        (1, False), (2, False), (7, False), (7, True)])
    def test_bodies_are_the_one_worker_calls(self, n_bodies, ragged,
                                             workers, level):
        blk = self._block(n_bodies, ragged)
        one, timing = {}, {}
        want = egress.dd_series_bodies(**blk, compress_level=level,
                                       workers=1, timing=one)
        got = egress.dd_series_bodies(**blk, compress_level=level,
                                      workers=workers, timing=timing)
        assert got == want                       # byte for byte, in order
        assert len(got) == n_bodies
        assert one["workers"] == 1
        assert timing["bodies"] == one["bodies"] == n_bodies
        assert timing["workers"] == min(workers, n_bodies)
        series = [json.loads(zlib.decompress(b) if level else b)["series"]
                  for b in got]
        nem = len(blk["em_rows"])
        assert [len(s) for s in series] == \
            [12] * (n_bodies - 1) + [nem - 12 * (n_bodies - 1)]
        # spot-check the rows against the inputs, across a body's edge
        flat = [m for s in series for m in s]
        names = arenas_strings(blk["names"])
        for e in (0, 11, 12, nem - 1)[:4 if n_bodies > 1 else 2]:
            r, sfx = blk["em_rows"][e], blk["em_suffix"][e]
            assert flat[e]["metric"] == \
                names[r] + self.SUFFIXES[sfx].decode()
            assert flat[e]["type"] == ("rate" if blk["em_type"][e]
                                       else "gauge")
            assert flat[e]["tags"][:2] == ["team:x", 'q:"1']

    def test_workers_follow_the_bodies_and_the_cores(self, monkeypatch):
        import os

        for cores, want in ((1, [1, 1, 1]), (4, [1, 2, 2]), (13, [1, 6, 6]),
                            (30, [1, 7, 8])):
            monkeypatch.setattr(os, "sched_getaffinity",
                                lambda _pid, n=cores: set(range(n)))
            assert [egress.dd_workers(b) for b in (1, 7, 50)] == want

    def test_no_emissions_make_no_body(self):
        blk = self._block(1, False)
        for key in ("em_rows", "em_suffix", "em_values", "em_type"):
            blk[key] = blk[key][:0]
        timing = {}
        assert egress.dd_series_bodies(**blk, workers=8,
                                       timing=timing) == []
        assert timing["bodies"] == 0 and timing["workers"] == 1

    def test_two_callers_at_once_get_their_own_bodies(self):
        """The batch path beside a stream worker: two Python threads in
        the native call at the same time, each with workers of its own."""
        import threading

        blocks = [self._block(7, True), self._block(5, False, per_body=40)]
        want = [egress.dd_series_bodies(**b, workers=1) for b in blocks]
        got = [[], []]
        start = threading.Barrier(2)

        def call(i):
            start.wait()
            for _ in range(20):
                got[i].append(egress.dd_series_bodies(**blocks[i],
                                                      workers=4))

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        for i in range(2):
            assert len(got[i]) == 20
            assert all(bodies == want[i] for bodies in got[i])

    def test_timing_splits_the_wall_and_sums_the_workers(self):
        import time

        blk = self._block(16, True, per_body=4000)
        timing = {}
        t0 = time.perf_counter_ns()
        bodies = egress.dd_series_bodies(**blk, workers=8, timing=timing)
        wall = time.perf_counter_ns() - t0
        assert len(bodies) == 16 and timing["workers"] == 8
        assert 0 < timing["deflate_ns"] and 0 < timing["encode_ns"]
        assert timing["encode_ns"] + timing["deflate_ns"] <= wall
        # the last worker's deflate is one of those summed
        assert timing["deflate_cpu_ns"] >= timing["deflate_ns"]
        assert timing["encode_cpu_ns"] > 0
        # eight spans over one wall: the workers' seconds cover the split
        assert timing["encode_cpu_ns"] + timing["deflate_cpu_ns"] \
            >= timing["encode_ns"] + timing["deflate_ns"]
        again = dict(timing)
        egress.dd_series_bodies(**blk, workers=1, timing=timing)
        assert timing["bodies"] == 32 and timing["workers"] == 8
        for key in ("encode_ns", "deflate_ns", "encode_cpu_ns",
                    "deflate_cpu_ns"):
            assert timing[key] > again[key]      # a second block adds on


class TestDDSeriesStream:
    """The same bodies handed over as the workers make them
    (``dd_series_stream``): the one-worker call's bytes and timing, and
    the handle and its threads gone on every way out."""

    SUFFIXES = TestDDBodiesSideBySide.SUFFIXES
    _block = TestDDBodiesSideBySide._block

    @pytest.mark.parametrize("level", [1, 6])
    @pytest.mark.parametrize("bodies", ["1", "W", "W+1", "50"])
    @pytest.mark.parametrize("workers", [1, 3, 8])
    def test_bodies_and_timing_are_the_one_worker_calls(self, workers,
                                                        bodies, level):
        import time

        n_bodies = {"1": 1, "W": workers, "W+1": workers + 1,
                    "50": 50}[bodies]
        blk = self._block(n_bodies, ragged=n_bodies > 1)
        one, timing = {}, {}
        want = egress.dd_series_bodies(**blk, compress_level=level,
                                       workers=1, timing=one)
        got, ready = [], []
        t0 = time.monotonic_ns()
        with egress.dd_series_stream(**blk, compress_level=level,
                                     workers=workers,
                                     timing=timing) as stream:
            assert stream.count == n_bodies
            for body, ready_ns in stream:
                got.append(body)
                ready.append(ready_ns)
        t1 = time.monotonic_ns()
        assert got == want                       # byte for byte, in order
        assert sorted(timing) == sorted(one)
        assert timing["bodies"] == one["bodies"] == n_bodies
        assert one["workers"] == 1
        assert timing["workers"] == min(workers, n_bodies)
        for key in ("encode_ns", "deflate_ns", "encode_cpu_ns",
                    "deflate_cpu_ns"):
            assert timing[key] > 0 and one[key] > 0, key
        # each body stamped when it was made, on time.monotonic_ns's
        # clock (the sink sets its stages by it)
        assert all(t0 <= r <= t1 for r in ready)

    def test_a_consumer_that_raises_leaves_no_thread_and_no_handle(self):
        blk = self._block(50, True)
        before = egress.dd_stream_live()
        with pytest.raises(RuntimeError, match="POST failed"):
            with egress.dd_series_stream(**blk, workers=8) as stream:
                assert egress.dd_stream_live() == (before[0] + 1,
                                                   before[1] + 8)
                for k, _made in enumerate(stream):
                    if k == 2:
                        raise RuntimeError("POST failed")
        assert egress.dd_stream_live() == before
        # a stream iterated to its end closes itself
        stream = egress.dd_series_stream(**blk, workers=3)
        assert len(list(stream)) == 50
        assert egress.dd_stream_live() == before


def arenas_strings(arena):
    blob, off, ln = arena
    return [bytes(blob[o:o + n]).decode() for o, n in zip(off, ln)]


class TestMetricListCodec:
    def _digest_planes(self, s=4, k=8, live=5):
        rng = np.random.default_rng(1)
        means = np.sort(rng.gamma(2, 30, (s, k)).astype(np.float32), axis=1)
        weights = np.zeros((s, k), np.float32)
        weights[:, :live] = rng.integers(1, 4, (s, live))
        return means, weights, means[:, 0].copy(), means[:, live - 1].copy()

    def test_encode_matches_python_protobuf(self):
        from veneur_tpu.protocol import forward_pb2

        means, weights, dmins, dmaxs = self._digest_planes()
        chunks = egress.encode_digest_metrics(
            arenas([f"h{i}" for i in range(4)]), arenas(["a:1,b:2"] * 4),
            means, weights, dmins, dmaxs, pb_type=2, compression=100.0,
            reference_compat=True)
        ml = forward_pb2.MetricList.FromString(b"".join(chunks))
        assert len(ml.metrics) == 4
        m = ml.metrics[1]
        assert m.name == "h1" and list(m.tags) == ["a:1", "b:2"]
        td = m.histogram.t_digest
        live = weights[1] > 0
        assert np.allclose(td.packed_means, means[1][live])
        assert np.allclose(td.packed_weights, weights[1][live])
        # reference_compat also writes the repeated Centroid schema
        assert [c.mean for c in td.main_centroids] == \
            pytest.approx(list(means[1][live]))
        assert td.compression == 100.0
        assert td.min == pytest.approx(dmins[1])

    def test_native_decode_of_python_protobuf(self):
        from veneur_tpu.protocol import forward_pb2

        mlist = forward_pb2.MetricList()
        m = mlist.metrics.add(name="c", tags=["x:1"], type=0)
        m.counter.value = -12
        m = mlist.metrics.add(name="g", type=1)
        m.gauge.value = 6.5
        m = mlist.metrics.add(name="t", type=4)
        td = m.histogram.t_digest
        td.compression = 100.0
        td.min, td.max = 1.0, 3.0
        td.packed_means.extend([1.0, 3.0])
        td.packed_weights.extend([2.0, 2.0])
        m = mlist.metrics.add(name="ref", type=2)
        td = m.histogram.t_digest
        td.min, td.max = 0.0, 5.0
        td.main_centroids.add(mean=2.5, weight=4.0)
        m = mlist.metrics.add(name="s", type=3)
        m.set.hyper_log_log = b"\x00\x01\x02"
        data = mlist.SerializeToString()
        dec = egress.decode_metric_list(data)
        assert dec.count == 5
        assert dec.payload[0] == egress.PAYLOAD_COUNTER
        assert dec.ivalue[0] == -12 and dec.joined_tags(0) == "x:1"
        assert dec.dvalue[1] == 6.5
        o, n = int(dec.cent_off[2]), int(dec.cent_len[2])
        assert list(dec.means[o:o + n]) == [1.0, 3.0]
        o, n = int(dec.cent_off[3]), int(dec.cent_len[3])
        assert list(dec.means[o:o + n]) == [2.5]
        assert list(dec.weights[o:o + n]) == [4.0]
        ho, hn = int(dec.hll_off[4]), int(dec.hll_len[4])
        assert data[ho:ho + hn] == b"\x00\x01\x02"

    def test_roundtrip_native_to_native(self):
        means, weights, dmins, dmaxs = self._digest_planes(s=3)
        chunks = egress.encode_digest_metrics(
            arenas(["a", "b", "c"]), arenas(["", "t:1", ""]),
            means, weights, dmins, dmaxs, pb_type=4)
        dec = egress.decode_metric_list(b"".join(chunks))
        assert dec.count == 3 and all(dec.type == 4)
        assert dec.joined_tags(1) == "t:1"
        for r in range(3):
            o, n = int(dec.cent_off[r]), int(dec.cent_len[r])
            live = weights[r] > 0
            assert np.allclose(dec.means[o:o + n], means[r][live])

    def test_chunked_bodies_all_parse(self):
        from veneur_tpu.protocol import forward_pb2

        means, weights, dmins, dmaxs = self._digest_planes(s=50)
        chunks = egress.encode_digest_metrics(
            arenas([f"m{i}" for i in range(50)]), arenas([""] * 50),
            means, weights, dmins, dmaxs, pb_type=2, max_body_bytes=2000)
        assert len(chunks) > 1
        total = sum(len(forward_pb2.MetricList.FromString(c).metrics)
                    for c in chunks)
        assert total == 50

    def test_zero_min_max_decodes_as_zero(self):
        """proto3 omits zero-valued scalars: a digest whose true min or
        max is 0.0 arrives with the field absent and must decode as 0.0,
        not as 'unknown' (regression: inf extrema made the global's
        quantile NaN)."""
        from veneur_tpu.protocol import forward_pb2

        mlist = forward_pb2.MetricList()
        m = mlist.metrics.add(name="z", type=2)
        td = m.histogram.t_digest
        td.compression = 100.0
        td.min, td.max = 0.0, 0.0  # both omitted on the wire
        td.packed_means.extend([0.0])
        td.packed_weights.extend([5.0])
        dec = egress.decode_metric_list(mlist.SerializeToString())
        assert dec.dmin[0] == 0.0 and dec.dmax[0] == 0.0

    def test_empty_digest_normalizes_extrema(self):
        means = np.zeros((1, 4), np.float32)
        weights = np.zeros((1, 4), np.float32)
        chunks = egress.encode_digest_metrics(
            arenas(["e"]), arenas([""]), means, weights,
            np.array([np.inf], np.float32), np.array([-np.inf], np.float32),
            pb_type=2)
        dec = egress.decode_metric_list(b"".join(chunks))
        assert dec.cent_len[0] == 0
        assert dec.dmin[0] == np.inf and dec.dmax[0] == -np.inf

    def test_intern_table_teach_and_reset(self):
        from veneur_tpu.protocol import forward_pb2

        mlist = forward_pb2.MetricList()
        for i in range(4):
            m = mlist.metrics.add(name=f"n{i}", tags=[f"t:{i}"], type=0)
            m.counter.value = i
        dec = egress.decode_metric_list(mlist.SerializeToString())
        tbl = egress.MListInternTable()
        rows, miss = tbl.assign(dec)
        assert list(miss) == [0, 1, 2, 3]
        for i in miss:
            i = int(i)
            no, nl = dec.name_off[i], dec.name_len[i]
            to, tl = dec.tags_off[i], dec.tags_len[i]
            tbl.put(int(dec.type[i]), int(dec.payload[i]),
                    dec.arena[no:no + nl], dec.arena[to:to + tl], 10 + i)
        rows, miss = tbl.assign(dec)
        assert len(miss) == 0 and list(rows) == [10, 11, 12, 13]
        tbl.reset()
        _, miss = tbl.assign(dec)
        assert len(miss) == 4

    def test_intern_table_payload_kind_in_key(self):
        # same (type, name, tags) but a DIFFERENT value-oneof must MISS:
        # row indices are per-group, and the applying group is chosen by
        # the payload at apply time (ADVICE round-3, medium)
        from veneur_tpu.protocol import forward_pb2

        mlist = forward_pb2.MetricList()
        m = mlist.metrics.add(name="n", tags=["t:1"], type=0)
        m.counter.value = 7
        dec = egress.decode_metric_list(mlist.SerializeToString())
        tbl = egress.MListInternTable()
        _, miss = tbl.assign(dec)
        tbl.put(int(dec.type[0]), int(dec.payload[0]),
                b"n", b"t:1", 5)
        rows, miss = tbl.assign(dec)
        assert len(miss) == 0 and rows[0] == 5
        # adversarial re-send: identical key fields, gauge oneof instead
        evil = forward_pb2.MetricList()
        m2 = evil.metrics.add(name="n", tags=["t:1"], type=0)
        m2.gauge.value = 1.0
        dec2 = egress.decode_metric_list(evil.SerializeToString())
        rows2, miss2 = tbl.assign(dec2)
        assert list(miss2) == [0]


class TestColumnarFlush:
    """The columnar flush must produce the same metrics as the legacy
    per-row path (to_intermetrics is the equivalence bridge)."""

    def _fill(self, store):
        from veneur_tpu.samplers import parser as P

        store.process_metric(P.parse_metric(b"c.a:3|c|#env:prod"))
        store.process_metric(P.parse_metric(b"c.a:2|c|#env:prod"))
        store.process_metric(P.parse_metric(b"g.b:7.5|g"))
        for v in (1.0, 2.0, 3.0, 10.0):
            store.process_metric(P.parse_metric(f"h.c:{v}|h|#r:1".encode()))
        store.process_metric(P.parse_metric(b"s.d:alice|s"))
        store.process_metric(P.parse_metric(b"s.d:bob|s"))

    def _flush(self, columnar):
        from veneur_tpu.core.store import MetricStore
        from veneur_tpu.samplers.intermetric import HistogramAggregates

        store = MetricStore(initial_capacity=32, chunk=64)
        self._fill(store)
        agg = HistogramAggregates.from_names(
            ["min", "max", "count", "sum", "avg", "median", "hmean"])
        out, fwd, ms = store.flush([0.5, 0.99], agg, is_local=False,
                                   now=500, columnar=columnar)
        return out, fwd

    def test_matches_legacy_flush(self):
        legacy, _ = self._flush(columnar=False)
        col, _ = self._flush(columnar=True)
        mats = col.to_intermetrics()
        want = {(m.name, tuple(sorted(m.tags))): m.value for m in legacy}
        got = {(m.name, tuple(sorted(m.tags))): m.value for m in mats}
        assert want.keys() == got.keys(), \
            set(want) ^ set(got)
        for k in want:
            assert got[k] == pytest.approx(want[k], rel=1e-6,
                                           abs=1e-9), k
        types_want = {m.name: m.type for m in legacy}
        types_got = {m.name: m.type for m in mats}
        assert types_want == types_got

    def test_routed_metrics_fall_back_to_extras(self):
        from veneur_tpu.core.store import MetricStore
        from veneur_tpu.samplers import parser as P
        from veneur_tpu.samplers.intermetric import HistogramAggregates

        store = MetricStore(initial_capacity=32, chunk=64)
        store.process_metric(
            P.parse_metric(b"r.a:1|c|#veneursinkonly:kafka"))
        store.process_metric(P.parse_metric(b"r.b:1|g"))
        agg = HistogramAggregates.from_names(["count"])
        col, _, _ = store.flush([], agg, is_local=False, now=1,
                                columnar=True)
        # the routed counter group fell back to per-row extras with its
        # routing intact; the (unrouted) gauge group stayed columnar
        routed = [m for m in col.extras if m.name == "r.a"]
        assert routed and routed[0].sinks == frozenset({"kafka"})
        assert sum(len(b) for b in col.blocks) == 1
        assert any(m.name == "r.b" for m in col.to_intermetrics())

    def test_columnar_forward_state_matches_materialized(self):
        _, fwd_legacy = self._flush_fwd(columnar=False)
        _, fwd_col = self._flush_fwd(columnar=True)
        assert fwd_col.histograms_columnar is not None
        fwd_col.materialize_digests()
        assert len(fwd_col.histograms) == len(fwd_legacy.histograms) == 1
        (n1, t1, m1, w1, mn1, mx1) = fwd_legacy.histograms[0]
        (n2, t2, m2, w2, mn2, mx2) = fwd_col.histograms[0]
        assert n1 == n2 and t1 == t2
        assert np.allclose(m1, m2) and np.allclose(w1, w2)
        assert mn1 == pytest.approx(mn2) and mx1 == pytest.approx(mx2)

    def _flush_fwd(self, columnar):
        from veneur_tpu.core.store import MetricStore
        from veneur_tpu.samplers.intermetric import HistogramAggregates

        store = MetricStore(initial_capacity=32, chunk=64)
        self._fill(store)
        agg = HistogramAggregates.from_names(["count"])
        out, fwd, _ = store.flush([], agg, is_local=True, now=500,
                                  forward=True, columnar=columnar)
        return out, fwd


class TestNativeImport:
    def test_import_columnar_equals_python_apply(self):
        """The native import lane must merge identically to the Python
        apply_metric_list path."""
        from veneur_tpu.core.store import ForwardableState, MetricStore
        from veneur_tpu.forward.convert import (apply_metric_list,
                                                metric_list_from_state)
        from veneur_tpu.samplers.intermetric import HistogramAggregates

        rng = np.random.default_rng(2)
        state = ForwardableState()
        state.counters.append(("c.x", ["a:1"], 5))
        state.gauges.append(("g.y", [], 2.5))
        for i in range(6):
            means = np.sort(rng.gamma(2, 30, 16))
            state.histograms.append(
                (f"h{i}", [f"s:{i % 2}"], means, np.ones(16),
                 float(means[0]), float(means[-1])))
        regs = np.zeros(1 << 14, np.uint8)
        regs[:100] = 3
        state.sets.append(("s.z", [], regs, 14))
        mlist = metric_list_from_state(state)
        data = mlist.SerializeToString()

        agg = HistogramAggregates.from_names(["count"])
        s_py = MetricStore(initial_capacity=64, chunk=256)
        n_ok, n_err = apply_metric_list(s_py, mlist)
        assert (n_ok, n_err) == (9, 0)
        s_nat = MetricStore(initial_capacity=64, chunk=256)
        dec = egress.decode_metric_list(data)
        n_ok, n_err = s_nat.import_columnar(dec, data)
        assert (n_ok, n_err) == (9, 0)
        assert s_nat.imported == 9

        out_py, _, _ = s_py.flush([0.5, 0.9], agg, is_local=False, now=7)
        out_nat, _, _ = s_nat.flush([0.5, 0.9], agg, is_local=False, now=7)
        py = {(m.name, tuple(m.tags)): m.value for m in out_py}
        nat = {(m.name, tuple(m.tags)): m.value for m in out_nat}
        assert py.keys() == nat.keys()
        for k in py:
            assert nat[k] == pytest.approx(py[k], rel=1e-5), k

    def test_malformed_metric_counted_not_fatal(self):
        from veneur_tpu.core.store import MetricStore
        from veneur_tpu.protocol import forward_pb2

        mlist = forward_pb2.MetricList()
        m = mlist.metrics.add(name="ok", type=0)
        m.counter.value = 1
        mlist.metrics.add(name="novalue", type=0)  # empty oneof
        m = mlist.metrics.add(name="badset", type=3)
        m.set.hyper_log_log = b"XX"  # bad magic
        data = mlist.SerializeToString()
        store = MetricStore(initial_capacity=16, chunk=64)
        dec = egress.decode_metric_list(data)
        n_ok, n_err = store.import_columnar(dec, data)
        assert n_ok == 1 and n_err == 2
