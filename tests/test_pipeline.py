"""Overlapped flush egress (core/pipeline.py + the two-phase
``flush_begin`` surface): pipelined-vs-sequential parity, per-group
compute-ladder isolation under the pipeline, streamed-chunk
conservation through sink faults, the checkpoint-truncate race, and
the timeline's overlap measures.

The conservation invariant under test everywhere: ingested ==
emitted(acked) + requeued — a chunk that could not POST is late,
never lost.
"""

import json
import threading
import zlib

import numpy as np
import pytest

from veneur_tpu.core import MetricStore
from veneur_tpu.core.pipeline import ChunkStream, SerializerLane
from veneur_tpu.core.store import DigestGroup
from veneur_tpu.samplers import HistogramAggregates, parse_metric

AGGS = HistogramAggregates.from_names(["min", "max", "count"])


def make_store(**kw):
    kw.setdefault("initial_capacity", 32)
    kw.setdefault("chunk", 128)
    return MetricStore(**kw)


def fill(store, n_hist=6, n_counters=4, n_sets=3, samples=5):
    """A mixed interval with exactly known counts."""
    for i in range(n_hist):
        for v in range(samples):
            store.process_metric(
                parse_metric(f"lat.{i}:{v * 10 + i}|ms".encode()))
    for i in range(n_counters):
        store.process_metric(parse_metric(f"hits.{i}:3|c".encode()))
    for i in range(n_sets):
        store.process_metric(parse_metric(f"uniq.{i}:u{i}|s".encode()))


def emission_map(final):
    if hasattr(final, "to_intermetrics"):
        final = final.to_intermetrics()
    return {(m.name, tuple(sorted(m.tags))): m.value for m in final}


class TestPipelineParity:
    """The pipelined drain must emit exactly what the sequential one
    does — same names, same values — for every flush shape."""

    @pytest.mark.parametrize("columnar", [False, True])
    @pytest.mark.parametrize("is_local", [False, True])
    def test_same_emissions(self, columnar, is_local):
        if columnar:
            from veneur_tpu.native import egress

            if not egress.available():
                pytest.skip("no native toolchain")
        results = {}
        for depth in (0, 3):
            s = make_store(flush_pipeline_depth=depth)
            fill(s)
            final, fwd, ms = s.flush([0.5, 0.99], AGGS,
                                     is_local=is_local, now=7,
                                     forward=False, columnar=columnar)
            results[depth] = (emission_map(final), ms)
        assert results[0][0] == results[3][0]
        assert results[0][0], "vacuous parity: nothing emitted"
        assert results[0][1].histograms == results[3][1].histograms

    def test_forwarding_parity(self):
        """A forwarding local's ForwardableState is identical either
        way (counters/digest rows/sets)."""
        out = {}
        for depth in (0, 2):
            s = make_store(flush_pipeline_depth=depth)
            fill(s)
            s.process_metric(parse_metric(b"g:1|c|#veneurglobalonly"))
            _final, fwd, _ms = s.flush([], AGGS, is_local=True, now=7,
                                       forward=True)
            out[depth] = (sorted(fwd.counters),
                          sorted((n, tuple(t), float(w.sum()))
                                 for n, t, _m, w, _mn, _mx
                                 in fwd.timers),
                          sorted(n for n, _t, _r, _p in fwd.sets))
        assert out[0] == out[2]
        assert out[0][1], "vacuous: no forwarded digests"


class TestLadderIsolation:
    """(a) of the fault matrix: a kernel failure mid-dispatch retries
    ONLY the failed group through the ladder while every other group
    streams on."""

    def test_pallas_dispatch_failure_falls_to_xla_rung(self):
        s = make_store(flush_pipeline_depth=2)
        fill(s)
        orig = DigestGroup._run_flush
        g = s.timers  # `|ms` samples; retires at the swap

        def failing(qs, use_pallas, n):
            if use_pallas:
                raise RuntimeError("injected pallas dispatch failure")
            return orig(g, qs, use_pallas, n)

        g._run_flush = failing
        final, _fwd, ms = s.flush([0.5], AGGS, is_local=False, now=7,
                                  forward=False)
        em = emission_map(final)
        # the failed group still emitted this interval (XLA rung)...
        assert any(n.startswith("lat.0") for n, _t in em)
        assert ms.timers == 6
        # ...and the breaker counted exactly one fallback
        assert s.compute.fallback_total == 1

    def test_double_failure_requeues_only_that_group(self):
        s = make_store(flush_pipeline_depth=2)
        fill(s)
        g = s.timers

        def always_failing(qs, use_pallas, n):
            raise RuntimeError("injected kernel failure, both rungs")

        g._run_flush = always_failing
        final, _fwd, _ms = s.flush([0.5], AGGS, is_local=False, now=7,
                                   forward=False)
        em = emission_map(final)
        # every OTHER unit of the plan emitted normally
        assert ("hits.0", ()) in em
        assert any(n.startswith("uniq.0") or n == "uniq.0"
                   for n, _t in em)
        # the failed group re-merged into the LIVE store: late, not lost
        assert not any(n.startswith("lat.") for n, _t in em)
        assert s.compute.requeued_total == 1
        final2, _fwd2, _ms2 = s.flush([0.5], AGGS, is_local=False,
                                      now=8, forward=False)
        em2 = emission_map(final2)
        counts = sum(v for (n, _t), v in em2.items()
                     if n.startswith("lat.") and n.endswith(".count"))
        assert counts == 6 * 5  # the whole requeued interval, exactly once


@pytest.fixture
def native_egress():
    from veneur_tpu.native import egress

    if not egress.available():
        pytest.skip("no native toolchain")
    return egress


class _FaultyPost:
    """Datadog post stub: 5xx for a configured chunk body range, 202
    otherwise; remembers every acked body's series payload."""

    def __init__(self, fail_calls=()):
        self.calls = 0
        self.fail_calls = set(fail_calls)
        self.acked_rows = 0

    def __call__(self, url, payload, compress=True, method="POST",
                 precompressed=False, out_info=None):
        self.calls += 1
        if self.calls in self.fail_calls:
            return 500
        if precompressed:
            body = json.loads(zlib.decompress(payload))
            self.acked_rows += len(body["series"])
        return 202


def make_dd_sink(post, **kw):
    from veneur_tpu.resilience import RetryPolicy
    from veneur_tpu.sinks.datadog import DatadogMetricSink

    kw.setdefault("interval", 10)
    kw.setdefault("flush_max_per_body", 4)
    sink = DatadogMetricSink(hostname="h0", tags=[], dd_hostname="http://dd",
                             api_key="k", post=post,
                             retry_policy=RetryPolicy(max_attempts=1),
                             **kw)
    sink.set_flush_deadline(None)
    return sink


class TestStreamedSinkConservation:
    """(b) of the fault matrix: a sink 5xx on chunk k of n — the
    unacked bodies requeue exactly once; everything else acks."""

    def test_clean_stream_acks_every_row(self, native_egress):
        post = _FaultyPost()
        sink = make_dd_sink(post)
        s = make_store(flush_pipeline_depth=2)
        fill(s)
        stream = ChunkStream([sink], 7, depth=2)
        final, _fwd, _ms = s.flush([0.5], AGGS, is_local=False, now=7,
                                   forward=False, columnar=True,
                                   stream=stream)
        stream.close()
        assert stream.chunks >= 2  # scalars + digest groups + sets
        assert sink.chunk_rows_acked == stream.rows
        assert sink.chunk_rows_pending() == 0
        assert post.acked_rows == stream.rows

    def test_5xx_chunk_requeues_once_with_exact_conservation(
            self, native_egress):
        post = _FaultyPost(fail_calls={2})  # the 2nd body POST 5xxes
        sink = make_dd_sink(post)
        s = make_store(flush_pipeline_depth=2)
        fill(s)
        stream = ChunkStream([sink], 7, depth=2)
        s.flush([0.5], AGGS, is_local=False, now=7, forward=False,
                columnar=True, stream=stream)
        stream.close()
        pending = sink.chunk_rows_pending()
        assert pending > 0
        # conservation: every emitted row is acked or parked, none lost
        assert sink.chunk_rows_acked + pending == stream.rows
        assert sink.chunk_rows_dropped == 0
        total_first = stream.rows

        # next interval: the parked bodies get their ONE retry first
        fill(s)
        stream2 = ChunkStream([sink], 8, depth=2)
        s.flush([0.5], AGGS, is_local=False, now=8, forward=False,
                columnar=True, stream=stream2)
        stream2.close()
        assert sink.chunks_requeued_total == 1
        assert sink.chunk_rows_pending() == 0
        assert sink.chunk_rows_acked == total_first + stream2.rows

    @pytest.mark.parametrize("workers", [1, 3, 8])
    @pytest.mark.parametrize("fail_calls,budget", [
        ((), None), ((2,), None), ((3, 5), None),
        (range(1, 1000), None), (range(1, 1000), 600)],
        ids=["clean", "5xx-on-body-2", "5xx-on-bodies-3-and-5",
             "always-5xx", "always-5xx-past-the-budget"])
    def test_chunk_of_several_bodies_by_several_workers_conserves(
            self, native_egress, monkeypatch, fail_calls, budget, workers):
        """The matrix once more where a chunk's bodies are made side by
        side and each is POSTed as it is made: the sink POSTs the
        one-worker serial call's bodies in the same order, so ``acked +
        requeued + dropped == rows`` whatever the POSTs do, and the
        chunk's stages say what of its POSTs the serializer hid."""
        from veneur_tpu import obs

        asked = []

        def dd_workers(n_bodies):
            asked.append((n_bodies, min(n_bodies, workers)))
            return asked[-1][1]

        monkeypatch.setattr(native_egress, "dd_workers", dd_workers)

        class _Logged(_FaultyPost):
            def __call__(self, url, payload, **kw):
                self.payloads.append(payload)
                return super().__call__(url, payload, **kw)

        post = _Logged(fail_calls)
        post.payloads = []
        sink = make_dd_sink(post, flush_max_per_body=4)
        if budget is not None:
            sink.requeue_max_bytes = budget
        chunks = []
        flush_chunk = sink.flush_chunk
        monkeypatch.setattr(sink, "flush_chunk",
                            lambda c: flush_chunk(chunks.append(c) or c))
        s = make_store(flush_pipeline_depth=2)
        fill(s, n_hist=9)     # the timers' chunk: 36 rows, nine bodies
        rec = obs.StageRecorder()
        stream = ChunkStream([sink], 7, depth=2, rec=rec)
        s.flush([0.5], AGGS, is_local=False, now=7, forward=False,
                columnar=True, stream=stream)
        stream.close()
        assert max(asked) == (9, min(9, workers))
        assert (sink.chunk_rows_acked + sink.chunk_rows_pending()
                + sink.chunk_rows_dropped) == stream.rows
        assert post.acked_rows == sink.chunk_rows_acked
        failed = sum(1 for c in fail_calls if c <= post.calls)
        assert sink.flush_errors == failed
        if budget is None:
            assert sink.chunk_rows_dropped == 0
            assert len(sink._requeued) == failed
        else:
            assert sink.chunk_rows_dropped > 0
            assert sink.chunk_requeue_bytes() <= budget
        # every body holds its rows, in the serial call's order
        sizes = [len(json.loads(zlib.decompress(b))["series"])
                 for b in post.payloads]
        assert sum(sizes) == stream.rows and max(sizes) == 4
        monkeypatch.setattr(native_egress, "dd_workers", lambda n: 1)
        serial = []
        for chunk in chunks:
            for blk in chunk.blocks:
                with sink._serialize_block(blk, chunk.timestamp) as bodies:
                    serial.extend(body for body, _ready_ns in bodies)
        assert post.payloads == serial
        # each chunk's POSTs, and what of them the serializer did not
        # hide; no chunk of one body POSTs one early
        stages = rec.finish()["stages"]

        def by_chunk(name):
            return {st["chunk"]: st for st in stages if st["name"] == name}

        made = by_chunk("post.datadog.serialize")
        posted = by_chunk("post.datadog.post")
        tails = by_chunk("post.datadog.post.tail")
        assert sorted(made) == sorted(posted) == sorted(tails) \
            == list(range(len(chunks)))
        assert max(m["bodies"] for m in made.values()) == 9
        assert 1 in {m["bodies"] for m in made.values()}
        for seq, p in posted.items():
            assert 0 <= p["bodies_posted_early"] < made[seq]["bodies"]
            t = tails[seq]
            assert p["start_ns"] <= t["start_ns"]
            assert t["start_ns"] + t["duration_ns"] \
                == p["start_ns"] + p["duration_ns"]

    def test_requeued_body_failing_again_reparks_in_budget(
            self, native_egress):
        """A multi-interval outage holds every unacked body inside the
        bytes budget (late, never lost) instead of dropping after one
        retry — the PR 16 bounded-bytes requeue semantics."""
        post = _FaultyPost(fail_calls=set(range(1, 100)))  # always 5xx
        sink = make_dd_sink(post)
        s = make_store(flush_pipeline_depth=2)
        fill(s)
        stream = ChunkStream([sink], 7, depth=2)
        s.flush([0.5], AGGS, is_local=False, now=7, forward=False,
                columnar=True, stream=stream)
        stream.close()
        parked = sink.chunk_rows_pending()
        assert parked == stream.rows
        fill(s)
        stream2 = ChunkStream([sink], 8, depth=2)
        s.flush([0.5], AGGS, is_local=False, now=8, forward=False,
                columnar=True, stream=stream2)
        stream2.close()
        # the retry failed too: bodies re-park (budget allows), so
        # both intervals stay pending — counted, bounded, recoverable
        assert sink.chunk_rows_dropped == 0
        assert sink.chunk_rows_pending() == parked + stream2.rows
        assert sink.chunk_requeue_bytes() <= sink.requeue_max_bytes
        assert sink.chunk_rows_acked == 0

    def test_requeue_budget_evicts_oldest_counted(self, native_egress):
        """Past the bytes budget the OLDEST parked bodies drop counted
        — conservation holds as acked + pending + dropped."""
        post = _FaultyPost(fail_calls=set(range(1, 1000)))  # always 5xx
        sink = make_dd_sink(post)
        s = make_store(flush_pipeline_depth=2)
        fill(s)
        stream = ChunkStream([sink], 7, depth=2)
        s.flush([0.5], AGGS, is_local=False, now=7, forward=False,
                columnar=True, stream=stream)
        stream.close()
        # shrink the budget below what is parked: the next interval's
        # repost + re-park must evict down to the budget
        sink.requeue_max_bytes = max(1, sink.chunk_requeue_bytes() // 2)
        total_first = stream.rows
        fill(s)
        stream2 = ChunkStream([sink], 8, depth=2)
        s.flush([0.5], AGGS, is_local=False, now=8, forward=False,
                columnar=True, stream=stream2)
        stream2.close()
        assert sink.chunk_requeue_bytes() <= sink.requeue_max_bytes
        assert sink.chunk_rows_dropped > 0
        # exact conservation across both intervals
        assert (sink.chunk_rows_acked + sink.chunk_rows_pending()
                + sink.chunk_rows_dropped) == total_first + stream2.rows

    def test_20_interval_blackhole_conserves_then_drains(
            self, native_egress):
        """A 20-interval API black hole (every POST raises): the parked
        bytes stay inside the budget the whole outage — the oldest
        bodies drop COUNTED, never silently — and exact conservation
        (offered == acked + pending + dropped) holds at every interval.
        When the API heals, one repost drains everything still parked."""

        class _BlackHolePost:
            healed = False
            acked_rows = 0

            def __call__(self, url, payload, compress=True,
                         method="POST", precompressed=False,
                         out_info=None):
                if not self.healed:
                    raise OSError("connection refused (black hole)")
                if precompressed:
                    body = json.loads(zlib.decompress(payload))
                    self.acked_rows += len(body["series"])
                return 202

        post = _BlackHolePost()
        sink = make_dd_sink(post)
        s = make_store(flush_pipeline_depth=2)
        offered = 0
        for i in range(20):
            fill(s)
            stream = ChunkStream([sink], 100 + i, depth=2)
            s.flush([0.5], AGGS, is_local=False, now=100 + i,
                    forward=False, columnar=True, stream=stream)
            stream.close()
            offered += stream.rows
            if i == 0:
                # a budget ~2 outage intervals wide: drops must start
                # within a few intervals, never an unbounded park
                sink.requeue_max_bytes = sink.chunk_requeue_bytes() * 2
            assert sink.chunk_requeue_bytes() <= sink.requeue_max_bytes
            assert (sink.chunk_rows_acked + sink.chunk_rows_pending()
                    + sink.chunk_rows_dropped) == offered, f"interval {i}"
        assert sink.chunk_rows_acked == 0
        assert sink.chunk_rows_dropped > 0       # eviction happened...
        assert sink.chunk_rows_pending() > 0     # ...but the newest wait
        # the API heals: the next interval's repost drains the park
        post.healed = True
        fill(s)
        stream = ChunkStream([sink], 200, depth=2)
        s.flush([0.5], AGGS, is_local=False, now=200, forward=False,
                columnar=True, stream=stream)
        stream.close()
        offered += stream.rows
        assert sink.chunk_rows_pending() == 0
        assert sink.chunk_requeue_bytes() == 0
        assert (sink.chunk_rows_acked
                + sink.chunk_rows_dropped) == offered
        assert post.acked_rows == sink.chunk_rows_acked


class TestStreamedForwardConservation:
    """A terminally-failed streamed forward part re-merges into the
    live store with import semantics (late, never lost)."""

    def test_failed_part_requeues_into_live_store(self):
        from veneur_tpu import flusher as flusher_mod

        s = make_store(flush_pipeline_depth=2)
        fill(s, n_counters=0, n_sets=0)
        parts = []

        def failing_forward(attr, part):
            parts.append(attr)
            return False

        stream = ChunkStream(
            [], 7, depth=2, forward_fn=failing_forward,
            forward_requeue=lambda attr, part:
                flusher_mod._requeue_forward_part(s, attr, part))
        _final, fwd, _ms = s.flush([], AGGS, is_local=True, now=7,
                                   forward=True, columnar=False,
                                   stream=stream)
        stream.close()
        assert parts == ["timers_columnar"] or parts == []
        if not parts:
            pytest.skip("non-columnar flush forwards per-row lists")

    def test_failed_columnar_part_reemits_next_flush(self, native_egress):
        from veneur_tpu import flusher as flusher_mod

        s = make_store(flush_pipeline_depth=2)
        fill(s, n_counters=0, n_sets=0)

        stream = ChunkStream(
            [], 7, depth=2, forward_fn=lambda attr, part: False,
            forward_requeue=lambda attr, part:
                flusher_mod._requeue_forward_part(s, attr, part))
        _final, fwd, _ms = s.flush([], AGGS, is_local=True, now=7,
                                   forward=True, columnar=True,
                                   stream=stream)
        stream.close()
        assert stream.forward_parts == 1
        assert stream.forward_requeued_rows == 6
        # the streamed attr never landed on the batch ForwardableState
        assert fwd.timers_columnar is None
        # next flush forwards the re-merged interval, exactly once
        _f2, fwd2, _m2 = s.flush([], AGGS, is_local=True, now=8,
                                 forward=True, columnar=True)
        fwd2.materialize_digests()
        names = {n for n, *_rest in fwd2.timers}
        assert names == {f"lat.{i}" for i in range(6)}
        total_w = sum(float(np.sum(w))
                      for _n, _t, _m, w, _mn, _mx in fwd2.timers)
        assert total_w == 6 * 5  # every requeued sample, once


class TestCheckpointTruncateRace:
    """(c) of the fault matrix: checkpoint truncation racing a
    streaming flush never deadlocks and never double-counts."""

    def test_truncate_races_streaming_flush(self, tmp_path,
                                            native_egress):
        from veneur_tpu.persist.checkpoint import Checkpointer

        post = _FaultyPost()
        sink = make_dd_sink(post)
        s = make_store(flush_pipeline_depth=2)
        path = str(tmp_path / "race.ckpt")
        ck = Checkpointer(s, path, interval_s=3600.0, max_age_s=3600)
        fill(s)
        ck.write_once()
        stop = threading.Event()

        def truncator():
            while not stop.is_set():
                ck.truncate(blocking=False)
                ck.write_once()

        t = threading.Thread(target=truncator, daemon=True)
        t.start()
        try:
            for now in (7, 8, 9):
                stream = ChunkStream([sink], now, depth=2)
                s.flush([0.5], AGGS, is_local=False, now=now,
                        forward=False, columnar=True, stream=stream)
                stream.close()
                fill(s)
        finally:
            stop.set()
            t.join(timeout=10)
        assert not t.is_alive()
        assert sink.chunk_rows_acked == post.acked_rows
        assert sink.chunk_rows_pending() == 0
        # a restore of whatever checkpoint survived must not explode
        fresh = make_store(flush_pipeline_depth=2)
        ck2 = Checkpointer(fresh, path, interval_s=3600.0,
                           max_age_s=3600)
        ck2.restore()


class TestFlusherStreaming:
    """The flusher's end of the pipe: _build_stream wires chunk-capable
    sinks into the interval, streamed sinks get only extras at the
    batch fan-out, and the published entry carries the chunks' stages
    — through a REAL Server."""

    def test_server_streams_chunks_into_the_timeline(self, native_egress):
        from veneur_tpu.config import Config
        from veneur_tpu.server import Server
        from veneur_tpu.sinks import ChannelMetricSink

        post = _FaultyPost()
        dd = make_dd_sink(post)
        cfg = Config(statsd_listen_addresses=[], interval="86400s",
                     http_address="127.0.0.1:0", percentiles=[0.5],
                     obs_timeline_intervals=4,
                     store_initial_capacity=32, store_chunk=128,
                     flush_pipeline_depth=2, flush_streaming=True)
        chan = ChannelMetricSink()
        srv = Server(cfg, metric_sinks=[dd, chan])
        try:
            srv.start()
            for pkt in (b"to:3.5|h", b"tc:1|c", b"tu:u1|s"):
                srv.handle_metric_packet(pkt)
            srv.flush()
            chan.get_flush()
            # the datadog sink took the interval as streamed chunks
            assert dd.chunks_flushed >= 2
            assert dd.chunk_rows_acked > 0
            assert dd.chunk_rows_pending() == 0
            entry = srv.obs_timeline.entries()[-1]
            names = {s["name"] for s in entry["stages"]}
            assert "post.datadog.post" in names
            assert any(n.startswith("serialize.") for n in names)
        finally:
            srv.shutdown()


class TestSerializerLane:
    def test_order_preserved_and_errors_reraise(self):
        lane = SerializerLane(2)
        out = []
        for i in range(5):
            lane.submit(f"u{i}", out.append, i)
        lane.close()
        assert out == [0, 1, 2, 3, 4]

        lane = SerializerLane(1)

        def boom(_):
            raise ValueError("emit failed")

        lane.submit("bad", boom, None)
        lane.submit("after", out.append, 99)
        with pytest.raises(ValueError, match="emit failed"):
            lane.close()
        # the lane drained (no deadlock) but skipped work after the error
        assert 99 not in out
