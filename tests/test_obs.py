"""Flush-interval observability (veneur_tpu/obs/): the StageRecorder,
the /debug/flush-timeline ring, dogfooded self-telemetry through the
dedicated digest group, and the kernel-scope coverage of the compiled-
program inventory.

The load-bearing contracts: the flusher's leaf stages cover >= 90% of
every interval's wall-clock (the coverage tripwire, by the one rule
for unnamed time), a stage lies on a profiler capture where its
``wall_start_ns`` puts it, the ring stays bounded, self-telemetry
percentiles are exact and survive an overload freeze, and
PROGRAM_SCOPES cannot drift from the recompile pass's inventory (same
contract as the generated docs table).
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from veneur_tpu.obs import FlushTimeline, StageRecorder, activate
from veneur_tpu.obs import kernels as obs_kernels
from veneur_tpu.obs import recorder as obs_rec
from veneur_tpu.samplers import HistogramAggregates

AGGS = HistogramAggregates.from_names(["min", "max", "count"])


def get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return r.status, r.read().decode(), dict(r.headers)


# ---------------------------------------------------------------------------
# StageRecorder
# ---------------------------------------------------------------------------


class TestStageRecorder:
    def test_nested_paths_and_tree(self):
        clock = iter(range(0, 10000, 10))
        rec = StageRecorder(clock_ns=lambda: next(clock) * 1000)
        with rec.stage("store"):
            with rec.stage("histograms", series=7):
                with rec.stage("fetch"):
                    pass
        entry = rec.finish()
        names = [s["name"] for s in entry["stages"]]
        assert names == ["store", "store.histograms",
                         "store.histograms.fetch"]
        histo = entry["stages"][1]
        assert histo["series"] == 7
        # tree nests by dotted path
        root = entry["tree"][0]
        assert root["name"] == "store"
        assert root["children"][0]["name"] == "store.histograms"
        assert root["children"][0]["children"][0]["name"] == \
            "store.histograms.fetch"

    def test_note_attaches_to_innermost_open_stage(self):
        rec = StageRecorder()
        with rec.stage("store"):
            with rec.stage("timers"):
                rec.note(rung="xla")
        stages = {s["name"]: s for s in rec.finish()["stages"]}
        assert stages["store.timers"]["rung"] == "xla"
        assert "rung" not in stages["store"]

    def test_module_hooks_are_noops_without_recorder(self):
        # deep call sites run these on every flush with obs off
        assert obs_rec.current() is None
        with obs_rec.maybe_stage("anything") as frame:
            assert frame is None
        obs_rec.note(rung="pallas")  # must not raise

    def test_activate_scopes_current(self):
        rec = StageRecorder()
        with activate(rec):
            assert obs_rec.current() is rec
            with obs_rec.maybe_stage("s"):
                obs_rec.note(k="v")
        assert obs_rec.current() is None
        stages = rec.finish()["stages"]
        assert stages[0]["name"] == "s" and stages[0]["k"] == "v"

    def test_record_abs(self):
        rec = StageRecorder()
        t0 = rec.t0_ns
        rec.record_abs("post.datadog", t0 + 10, t0 + 510, bytes=42)
        stages = {s["name"]: s for s in rec.finish()["stages"]}
        assert stages["post.datadog"]["duration_ns"] == 500
        assert stages["post.datadog"]["bytes"] == 42

    def test_a_wrapper_covers_nothing_of_its_own(self):
        """The leaf rule: a parent's time outside its children is
        unstaged; the leaves' union is what covers."""
        clock = iter([0, 0, 100, 700, 1000, 1000])
        rec = StageRecorder(clock_ns=lambda: next(clock))
        with rec.stage("a"):          # 0 -> 1000
            with rec.stage("b"):      # 100 -> 700
                pass
        entry = rec.finish(total_ns=1000)
        assert entry["unstaged_ns"] == 400
        assert entry["coverage_ratio"] == 0.6

    def test_another_threads_stage_does_not_cover_the_flusher(self):
        rec = StageRecorder(clock_ns=lambda: 0)
        t = threading.Thread(target=lambda: rec.record_abs(
            "post.datadog.post", 0, 1000))
        t.start()
        t.join()
        entry = rec.finish(total_ns=1000)
        assert entry["unstaged_ns"] == 1000
        assert entry["coverage_ratio"] == 0.0
        stage = entry["stages"][0]
        assert stage["thread"] != entry["thread"] == \
            threading.current_thread().name

    def test_a_wait_leaf_covers_the_wait(self):
        """The flusher's wait for another thread is a leaf of its own:
        the stretch it waits is covered, and the other thread's stage
        inside it changes nothing."""
        clock = iter([0, 0, 0, 1000, 1000, 1000])
        rec = StageRecorder(clock_ns=lambda: next(clock))
        with rec.stage("post"):
            with rec.stage("sinks_wait"):   # 0 -> 1000
                t = threading.Thread(target=lambda: rec.record_abs(
                    "post.datadog", 10, 990))
                t.start()
                t.join()
        entry = rec.finish(total_ns=1000)
        assert entry["unstaged_ns"] == 0
        assert entry["coverage_ratio"] == 1.0

    def test_record_late_before_finish_stays_off_path(self):
        """A forward that completes BEFORE finish() lands via the
        event-stream fallback but keeps the off-path marker, so the
        concurrently-running forward never inflates coverage past 1.0
        or double-counts against the post stage it overlapped."""
        clock = iter([0, 0, 1000, 1000])
        rec = StageRecorder(clock_ns=lambda: next(clock))
        with rec.stage("post"):      # 0 -> 1000
            pass
        rec.record_late("forward", 0, 900)  # overlaps post; pre-finish
        entry = rec.finish(total_ns=1000)
        fwd = next(s for s in entry["stages"] if s["name"] == "forward")
        assert fwd["off_path"]
        assert entry["coverage_ratio"] == 1.0  # post only, not 1.9

    def test_record_late_lands_in_published_entry(self):
        rec = StageRecorder()
        entry = rec.finish()
        n = len(entry["stages"])
        rec.record_late("forward", rec.t0_ns, rec.t0_ns + 5000, series=3)
        assert len(entry["stages"]) == n + 1
        late = entry["stages"][-1]
        assert late["name"] == "forward" and late["off_path"]
        assert late["duration_ns"] == 5000 and late["series"] == 3

    def test_recorder_is_single_writer_per_thread(self):
        """Stages recorded from several threads at once all land (the
        deque append hand-off, like the ingest lanes)."""
        rec = StageRecorder()

        def work(i):
            rec.record_abs(f"post.sink{i}", rec.t0_ns, rec.t0_ns + i)

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(rec.finish()["stages"]) == 8


class TestFlushTimeline:
    def test_ring_is_bounded(self):
        tl = FlushTimeline(intervals=3)
        for i in range(7):
            tl.publish({"total_duration_ns": i, "coverage_ratio": 1.0,
                        "stages": [], "tree": []})
        entries = tl.entries()
        assert len(entries) == 3
        assert [e["interval"] for e in entries] == [4, 5, 6]
        assert tl.published_total == 7

    def test_handler_limits_and_rejects_bad_n(self):
        tl = FlushTimeline(intervals=8)
        for i in range(5):
            tl.publish({"total_duration_ns": i, "coverage_ratio": 1.0,
                        "stages": [], "tree": []})
        status, body, _ = tl.handler({"n": "2"})
        assert status == 200
        data = json.loads(body)
        assert [e["interval"] for e in data["intervals"]] == [3, 4]
        status, _, _ = tl.handler({"n": "nope"})
        assert status == 400


# ---------------------------------------------------------------------------
# the server end-to-end: timeline entries, coverage, endpoints
# ---------------------------------------------------------------------------


@pytest.fixture()
def obs_server():
    from veneur_tpu.config import Config
    from veneur_tpu.server import Server
    from veneur_tpu.sinks import ChannelMetricSink

    cfg = Config(statsd_listen_addresses=[], interval="86400s",
                 http_address="127.0.0.1:0", percentiles=[0.5, 0.99],
                 obs_timeline_intervals=4,
                 store_initial_capacity=32, store_chunk=128)
    sink = ChannelMetricSink()
    srv = Server(cfg, metric_sinks=[sink])
    srv.start()
    yield srv, sink
    srv.shutdown()


class TestServerTimeline:
    def flush(self, srv, sink, packets=(b"to:3.5|h", b"tc:1|c")):
        for pkt in packets:
            srv.handle_metric_packet(pkt)
        srv.flush()
        sink.get_flush()

    def test_every_interval_yields_an_entry_with_coverage(self, obs_server):
        srv, sink = obs_server
        # a warm-up interval first: a first flush compiles its programs
        # between stages, and that wall-clock is set-up, not a gap in
        # the stage coverage of a steady interval
        for _ in range(3):
            self.flush(srv, sink)
        entries = srv.obs_timeline.entries()
        assert len(entries) == 3
        for e in entries[1:]:
            assert e["total_duration_ns"] > 0
            # the acceptance tripwire: the flusher's leaves cover
            # >= 90% of the interval's wall-clock
            assert e["coverage_ratio"] >= 0.9, e
            assert e["unstaged_ns"] <= 0.1 * e["total_duration_ns"]

    def test_stage_tree_shape(self, obs_server):
        srv, sink = obs_server
        self.flush(srv, sink)
        e = srv.obs_timeline.entries()[-1]
        names = {s["name"] for s in e["stages"]}
        # pipelined flush shape (docs/internals.md "Life of a flush"):
        # dispatch stages carry the async program enqueue (compute),
        # the per-group stages carry the blocking fetch, and the
        # serializer lane's emission work rides serialize.<group>
        for expected in ("events", "store", "store.swap",
                         "store.dispatch", "store.dispatch.histograms",
                         "store.dispatch.histograms.compute",
                         "store.histograms", "store.histograms.fetch",
                         "store.self_timers", "serialize.histograms",
                         "post", "post.channel", "span_join"):
            assert expected in names, (expected, sorted(names))
        histo = next(s for s in e["stages"]
                     if s["name"] == "store.histograms")
        assert histo["series"] == 1
        assert histo["rung"] in ("pallas", "xla")
        # stages nest in the tree exactly like their dotted paths
        store = next(t for t in e["tree"] if t["name"] == "store")
        child_names = {c["name"] for c in store["children"]}
        assert "store.histograms" in child_names

    def test_flush_rows_live_and_run(self, obs_server):
        """The dense flush program says what it worked on: each dense
        group's compute stage carries rows_live and rows_run, and the
        entry their sums (what benchmark metric flush.rows_run reads)."""
        from veneur_tpu.ops import tdigest as td_ops

        srv, sink = obs_server
        self.flush(srv, sink)
        e = srv.obs_timeline.entries()[-1]
        compute = {s["name"]: s for s in e["stages"] if "rows_run" in s}
        histo = compute["store.dispatch.histograms.compute"]
        assert histo["rows_live"] == 1
        assert histo["rows_run"] == td_ops.flush_rows_run(
            srv.store.histograms.capacity, 1)
        assert all(name.startswith("store.dispatch.")
                   and name.endswith(".compute") for name in compute)
        assert e["digest_flush_rows"] == {
            "live": sum(s["rows_live"] for s in compute.values()),
            "run": sum(s["rows_run"] for s in compute.values())}

    def test_mesh_flush_rows_live_and_run(self):
        """A mesh group's flush says the same: rows_run is the sum of
        the slabs each shard ran for its own fill, not the rows
        reserved (what benchmark metric mesh_flush.rows_run reads)."""
        from veneur_tpu.config import Config
        from veneur_tpu.core.mesh_store import MeshDigestGroup
        from veneur_tpu.ops import tdigest as td_ops
        from veneur_tpu.server import Server
        from veneur_tpu.sinks import ChannelMetricSink

        # four shards of two slabs each
        cfg = Config(statsd_listen_addresses=[], interval="86400s",
                     percentiles=[0.5], obs_timeline_intervals=4,
                     store_initial_capacity=4 * 4096, store_chunk=128,
                     mesh_enabled=True, mesh_hosts=2)
        sink = ChannelMetricSink()
        srv = Server(cfg, metric_sinks=[sink])
        srv.start()
        try:
            histo = srv.store.histograms
            assert isinstance(histo, MeshDigestGroup)
            for i in range(9):
                srv.handle_metric_packet(b"mesh.to%d:3.5|h" % i)
            fills = histo.placement.fills.copy()
            block = histo.capacity // histo.shards
            srv.flush()
            sink.get_flush()
            e = srv.obs_timeline.entries()[-1]
        finally:
            srv.shutdown()
        compute = {s["name"]: s for s in e["stages"] if "rows_run" in s}
        stage = compute["store.dispatch.histograms.compute"]
        assert fills.sum() == 9 and block == 4096
        assert stage["rows_live"] == 9
        assert stage["rows_run"] == sum(
            td_ops.flush_rows_run(block, int(f)) for f in fills)
        assert stage["rows_run"] == 2048 * np.count_nonzero(fills)
        assert e["digest_flush_rows"] == {
            "live": sum(s["rows_live"] for s in compute.values()),
            "run": sum(s["rows_run"] for s in compute.values())}

    def test_slab_flush_counts_and_leaves(self):
        """A slab group's flush says what its sample path drained, what
        its programs ran against what was live and the slabs it grew
        (timeline ``slab``: what the benchmark's ``slab_*`` metrics
        read), under the leaves a dense group's has; ``/debug/vars``
        names its planes."""
        from veneur_tpu.config import Config
        from veneur_tpu.core.slab import SlabDigestGroup
        from veneur_tpu.debug import device_section
        from veneur_tpu.ops import tdigest as td_ops
        from veneur_tpu.server import Server
        from veneur_tpu.sinks import ChannelMetricSink

        cfg = Config(statsd_listen_addresses=[], interval="86400s",
                     percentiles=[0.5], obs_timeline_intervals=4,
                     store_chunk=64, digest_storage="slab", slab_rows=4096)
        sink = ChannelMetricSink()
        srv = Server(cfg, metric_sinks=[sink])
        srv.start()
        try:
            assert isinstance(srv.store.histograms, SlabDigestGroup)
            # 5,000 rows over two slabs, a row's second sample in a
            # later dispatch than its first
            for rep in range(2):
                for i in range(5000):
                    srv.handle_metric_packet(b"slab.h%d:%d.5|h" % (i, rep))
            planes = device_section(srv.store)["digest_planes"]
            srv.flush()
            sink.get_flush()
            e = srv.obs_timeline.entries()[-1]
        finally:
            srv.shutdown()
        assert planes["shape"] == [4096 * td_ops.size_bound(100.0)]
        slab = e["slab"]
        assert slab["rows_live"] == 5000 and slab["grows"] == 2
        assert slab["rows_run"] == 4096 + td_ops.flush_rows_run(4096, 904)
        assert slab["dispatches"] >= 2 * 5000 // 64
        assert slab["rows_drained"] == 5000 and slab["drain_trips"] >= 1
        names = {s["name"] for s in e["stages"]}
        for leaf in ("store.dispatch.histograms.drain",
                     "store.dispatch.histograms.compute",
                     "store.histograms.fetch.wait",
                     "store.histograms.fetch.copy",
                     "store.histograms.commit"):
            assert leaf in names, leaf

    def test_flush_timeline_endpoint_schema_and_bound(self, obs_server):
        srv, sink = obs_server
        for _ in range(6):  # ring holds 4 (obs_timeline_intervals)
            self.flush(srv, sink)
        status, body, _ = get(srv.ops_server.port,
                              "/debug/flush-timeline?n=10")
        assert status == 200
        data = json.loads(body)
        assert data["ring_capacity"] == 4
        assert data["published_total"] == 6
        assert len(data["intervals"]) == 4
        assert [e["interval"] for e in data["intervals"]] == [2, 3, 4, 5]
        for e in data["intervals"]:
            for s in e["stages"]:
                assert {"name", "start_ns", "duration_ns"} <= set(s)

    def test_debug_vars_obs_section(self, obs_server):
        srv, sink = obs_server
        self.flush(srv, sink)
        status, body, _ = get(srv.ops_server.port, "/debug/vars")
        data = json.loads(body)
        assert data["obs"]["timeline"]["published_total"] == 1
        assert "flush.digest.dense" in data["obs"]["kernels"]["dispatches"]

    def test_self_telemetry_reenters_the_pipeline(self, obs_server):
        """Stage durations sampled in interval N emit exact digest
        percentiles in interval N+1 — through the same sketches the
        server sells."""
        srv, sink = obs_server
        self.flush(srv, sink)
        srv.flush()
        batch = sink.get_flush()
        by_name = {}
        for m in batch:
            by_name.setdefault(m.name, []).append(m)
        assert "veneur.obs.stage_duration_ns.50percentile" in by_name
        counts = by_name["veneur.obs.stage_duration_ns.count"]
        tags = {t for m in counts for t in m.tags}
        assert "stage:store" in tags
        # every sampled duration is one observation: a stage name
        # counts as often as the interval recorded it
        seen = {}
        for st in srv.obs_timeline.entries()[0]["stages"]:
            seen[st["name"]] = seen.get(st["name"], 0) + 1
        for m in counts:
            assert m.value == seen[m.tags[0][len("stage:"):]], m

    def test_xprof_endpoint_captures(self, obs_server, tmp_path):
        srv, _sink = obs_server
        status, body, _ = get(srv.ops_server.port,
                              "/debug/xprof?seconds=0.05")
        assert status == 200, body
        data = json.loads(body)
        assert data["trace_dir"]
        assert data["files"], "capture produced no trace files"
        assert "flush.digest.dense" in data["scopes"]

    def test_xprof_bad_param_is_400(self, obs_server):
        import urllib.error

        srv, _sink = obs_server
        with pytest.raises(urllib.error.HTTPError) as e:
            get(srv.ops_server.port, "/debug/xprof?seconds=nope")
        assert e.value.code == 400


class TestObsDisabled:
    def test_disabled_means_no_recorder_and_404(self):
        from veneur_tpu.config import Config
        from veneur_tpu.server import Server
        from veneur_tpu.sinks import ChannelMetricSink

        cfg = Config(statsd_listen_addresses=[], interval="86400s",
                     http_address="127.0.0.1:0", obs_enabled=False,
                     store_initial_capacity=32, store_chunk=128)
        sink = ChannelMetricSink()
        srv = Server(cfg, metric_sinks=[sink])
        srv.start()
        try:
            assert srv.obs_timeline is None
            srv.handle_metric_packet(b"x:1|c")
            srv.flush()
            sink.get_flush()
            # no self-telemetry rows accrue with obs off
            assert len(srv.store.self_timers) == 0
            import urllib.error

            with pytest.raises(urllib.error.HTTPError) as e:
                get(srv.ops_server.port, "/debug/flush-timeline")
            assert e.value.code == 404
            # the kernel counters are independent of obs_enabled (they
            # back /debug/xprof): still visible, no timeline section
            _s, body, _h = get(srv.ops_server.port, "/debug/vars")
            obs = json.loads(body)["obs"]
            assert "dispatches" in obs["kernels"]
            assert "timeline" not in obs
        finally:
            srv.shutdown()

    def test_negative_ring_size_rejected(self):
        from veneur_tpu.config import Config

        with pytest.raises(ValueError, match="obs_timeline_intervals"):
            Config(interval="10s",
                   obs_timeline_intervals=-1).apply_defaults().validate()


# ---------------------------------------------------------------------------
# dogfooded self-telemetry: the dedicated digest group
# ---------------------------------------------------------------------------


class TestSelfTelemetryGroup:
    def make_store(self, **kw):
        from veneur_tpu.core import MetricStore

        kw.setdefault("initial_capacity", 32)
        kw.setdefault("chunk", 128)
        return MetricStore(**kw)

    def test_exact_stats_through_the_digest_pipeline(self):
        store = self.make_store()
        durations = [1000.0, 2000.0, 3000.0, 4000.0, 5000.0]
        for d in durations:
            store.sample_self_timing("store.histograms", d)
        store.sample_self_timing("post", 7000.0)
        final, _, _ = store.flush([0.5], AGGS, is_local=True, now=1,
                                  forward=False)
        by = {(m.name, tuple(m.tags)): m.value for m in final}
        key = ("veneur.obs.stage_duration_ns.count",
               ("stage:store.histograms",))
        assert by[key] == len(durations)
        assert by[("veneur.obs.stage_duration_ns.max",
                   ("stage:store.histograms",))] == 5000.0
        assert by[("veneur.obs.stage_duration_ns.min",
                   ("stage:store.histograms",))] == 1000.0
        p50 = by[("veneur.obs.stage_duration_ns.50percentile",
                  ("stage:store.histograms",))]
        assert abs(p50 - float(np.median(durations))) <= 500.0
        assert by[("veneur.obs.stage_duration_ns.count",
                   ("stage:post",))] == 1

    def test_exempt_from_overload_freeze(self):
        """Under a level-1 freeze customer first-sight series spill to
        the overflow row; self-telemetry rows still intern."""
        from veneur_tpu.overload import (OVERFLOW_NAME,
                                         OverloadController)
        from veneur_tpu.samplers.parser import MetricKey

        ctl = OverloadController(clock=lambda: 0.0)
        ctl._level = 1  # forced freeze; no recompute (clock frozen)
        ctl._next_recompute = float("inf")
        store = self.make_store(overload=ctl, max_series=1000)
        store.sample_self_timing("store", 123.0)
        assert len(store.self_timers) == 1
        names = store.self_timers.interner.names
        assert OVERFLOW_NAME not in names
        # a customer histogram first-sight series DOES spill
        store.local_timers.sample(
            MetricKey(name="cust.t", type="timer"), [], 1.0, 1.0)
        assert OVERFLOW_NAME in store.local_timers.interner.names

    def test_group_survives_checkpoint_round_trip(self):
        store = self.make_store()
        store.sample_self_timing("store", 1000.0)
        store.sample_self_timing("store", 3000.0)
        groups, _epoch = store.snapshot_state()
        assert "self_timers" in groups
        fresh = self.make_store()
        fresh.restore_state(groups)
        final, _, _ = fresh.flush([], AGGS, is_local=True, now=1,
                                  forward=False)
        by = {(m.name, tuple(m.tags)): m.value for m in final}
        assert by[("veneur.obs.stage_duration_ns.count",
                   ("stage:store",))] == 2
        assert by[("veneur.obs.stage_duration_ns.max",
                   ("stage:store",))] == 3000.0


# ---------------------------------------------------------------------------
# kernel scopes: inventory coverage + live counters
# ---------------------------------------------------------------------------


class TestKernelScopes:
    def test_program_scopes_cover_the_inventory_exactly(self):
        """Drift check, same contract as the generated docs table: the
        recompile pass's compiled-program inventory and
        obs/kernels.PROGRAM_SCOPES must name the same programs."""
        import os

        from veneur_tpu.lint import recompile
        from veneur_tpu.lint.framework import Project

        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        project = Project(repo_root)
        p = recompile._build(project)
        inventory = {f"{key[0]}::{key[1]}" for key in p.programs}
        assert inventory, "recompile pass found no programs (vacuous)"
        mapped = set(obs_kernels.PROGRAM_SCOPES)
        assert mapped == inventory, (
            f"PROGRAM_SCOPES drift: missing={sorted(inventory - mapped)} "
            f"extra={sorted(mapped - inventory)}")

    def test_bindings_resolve_to_jit_objects(self):
        import importlib

        for program, (_scope, binding) in \
                obs_kernels.PROGRAM_SCOPES.items():
            if binding is None:
                continue
            fn = getattr(importlib.import_module(binding[0]), binding[1])
            assert hasattr(fn, "_cache_size"), \
                f"{program}: {binding} is not a jit binding"

    def test_scope_counts_dispatches(self):
        before = obs_kernels.dispatch_snapshot().get("test.scope", 0)
        with obs_kernels.scope("test.scope"):
            pass
        assert obs_kernels.dispatch_snapshot()["test.scope"] == before + 1

    def test_compile_snapshot_tracks_imported_programs(self):
        # core.store is imported by this test module's dependencies;
        # its programs have run at least once in this session
        snap = obs_kernels.compile_snapshot()
        assert "veneur_tpu/core/store.py::_flush_digests" in snap
        assert obs_kernels.compiles_total() >= 0

    def test_xprof_capture_serializes_concurrent_requests(self):
        results = []

        def capture():
            results.append(obs_kernels.capture_xprof(0.3))

        t = threading.Thread(target=capture)
        with obs_kernels._xprof_lock:
            t.start()
            time.sleep(0.05)
        t.join(timeout=10)
        # the thread hit the held lock and returned 409 (one capture
        # at a time), never a double start_trace
        assert results and results[0][0] == 409


# ---------------------------------------------------------------------------
# the opened stages: merger, store.dispatch, the sink's serializer; the
# capture without the Python tracer; threads by name
# ---------------------------------------------------------------------------


class _BodyLog:
    """Datadog post stub: 202 for everything, keeps the bodies."""

    def __init__(self):
        self.bodies = []

    def __call__(self, url, payload, compress=True, method="POST",
                 precompressed=False, out_info=None):
        self.bodies.append(payload)
        return 202


@pytest.fixture()
def lane_server():
    """A server with the UDP ingest lanes (and so the merger thread) up
    and the Datadog sink streaming chunks into a stub."""
    from veneur_tpu.config import Config
    from veneur_tpu.native import egress
    from veneur_tpu.resilience import RetryPolicy
    from veneur_tpu.server import Server
    from veneur_tpu.sinks import ChannelMetricSink
    from veneur_tpu.sinks.datadog import DatadogMetricSink

    if not egress.available():
        pytest.skip("no native toolchain")
    post = _BodyLog()
    dd = DatadogMetricSink(hostname="h0", tags=[], dd_hostname="http://dd",
                           api_key="k", post=post, interval=10,
                           flush_max_per_body=25000,
                           retry_policy=RetryPolicy(max_attempts=1))
    dd.set_flush_deadline(None)
    cfg = Config(statsd_listen_addresses=["udp://127.0.0.1:0"],
                 num_readers=2, interval="86400s",
                 http_address="127.0.0.1:0", percentiles=[0.5, 0.99],
                 obs_timeline_intervals=8, store_initial_capacity=64,
                 store_chunk=128, flush_pipeline_depth=2,
                 flush_streaming=True)
    chan = ChannelMetricSink()
    srv = Server(cfg, metric_sinks=[dd, chan])
    srv.start()
    yield srv, chan, post
    srv.shutdown()


def send_and_merge(srv, lines, sock=None, timeout=10.0):
    """Send one datagram a line at the lanes (from one socket, so that
    SO_REUSEPORT hands them all to one lane) and wait until the merger
    has folded every one into the store."""
    import socket

    fleet = srv.ingest_fleet
    want = fleet.totals()["merged"] + len(lines)
    own = sock is None
    sock = sock or socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        for line in lines:
            sock.sendto(line, srv.statsd_addrs[0])
    finally:
        if own:
            sock.close()
    deadline = time.monotonic() + timeout
    while fleet.totals()["merged"] < want:
        assert time.monotonic() < deadline, fleet.totals()
        time.sleep(0.01)


def stages_of(entry):
    return {s["name"]: s for s in entry["stages"]}


class TestMergerStages:
    LINES = ([b"m.h%d:%d.5|h" % (i, i) for i in range(40)]
             + [b"m.c%d:1|c" % i for i in range(25)])

    def test_four_stages_off_path_and_parts_inside_the_whole(
            self, lane_server):
        import socket

        srv, chan, _post = lane_server
        # two intervals: the interners restart at each flush, so every
        # series is first-sight again in the second, where the lane
        # (one socket, so one lane) has every identity cached
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as sock:
            for interval in range(2):
                send_and_merge(srv, self.LINES, sock)
                srv.flush()
                chan.get_flush()
                entry = srv.obs_timeline.entries()[-1]
                st = stages_of(entry)
                parts = ("ingest.merge.lock_wait", "ingest.merge.remap",
                         "ingest.merge.stage")
                for name in ("ingest.merge",) + parts:
                    assert st[name]["off_path"] is True, name
                    assert st[name]["start_ns"] == 0, name
                merge = st["ingest.merge"]
                assert merge["rows_interned"] == len(self.LINES)
                assert merge["rows_reinterned"] == interval * len(self.LINES)
                assert entry["ingest_merge"] == {
                    "rows_interned": merge["rows_interned"],
                    "rows_reinterned": merge["rows_reinterned"]}
                assert merge["chunks"] >= 1
                assert st["ingest.merge.remap"]["duration_ns"] > 0
                assert st["ingest.merge.stage"]["duration_ns"] > 0
                assert 0 < sum(st[p]["duration_ns"] for p in parts) \
                    <= merge["duration_ns"]

    def test_known_rows_are_not_interned_again(self, lane_server):
        import socket

        srv, chan, _post = lane_server
        # one socket, so one lane: a lane row counts once an interval
        # (a second lane that carried the series would count it too)
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as sock:
            send_and_merge(srv, self.LINES, sock)
            send_and_merge(srv, self.LINES[:10], sock)   # all known
        srv.flush()
        chan.get_flush()
        merge = stages_of(srv.obs_timeline.entries()[-1])["ingest.merge"]
        assert merge["rows_interned"] == len(self.LINES)

    def test_tracing_off_reads_no_clock_and_publishes_nothing(self):
        from veneur_tpu.core.store import MetricStore
        from veneur_tpu.ingest import IngestFleet
        from veneur_tpu.protocol.addr import resolve_addr

        store = MetricStore(initial_capacity=32, chunk=128)
        fleet = IngestFleet(store, resolve_addr("udp://127.0.0.1:0"), 1,
                            1 << 16, 4096, chunk_records=128,
                            use_native=False, trace_stages=False)
        try:
            lane = fleet.lanes[0]
            lane._stage_python([b"a:1|c", b"h:3|ms"])
            lane._seal()
            assert fleet.merge_sealed() == 2
            assert fleet.merge_ns is None
            assert fleet.take_ingest_stages() is None
        finally:
            for lane in fleet.lanes:
                lane.sock.close()


@pytest.fixture()
def mesh_lane_server(monkeypatch):
    """A datagram-fed server on a 2 x 2 mesh of four of the process's
    devices (the program builds its mesh from every visible device:
    the test steers that)."""
    import jax

    from veneur_tpu.config import Config
    from veneur_tpu.parallel.mesh import fleet_mesh
    from veneur_tpu.server import Server
    from veneur_tpu.sinks import ChannelMetricSink

    monkeypatch.setattr(
        "veneur_tpu.fleet.build_mesh",
        lambda config: fleet_mesh(jax.devices()[:4], hosts=2))
    cfg = Config(statsd_listen_addresses=["udp://127.0.0.1:0"],
                 num_readers=2, interval="86400s",
                 http_address="127.0.0.1:0", percentiles=[0.5, 0.99],
                 obs_timeline_intervals=8, store_initial_capacity=128,
                 store_chunk=64, mesh_enabled=True,
                 digest_storage="sharded")
    chan = ChannelMetricSink()
    srv = Server(cfg, metric_sinks=[chan])
    srv.start()
    yield srv, chan
    srv.shutdown()


class TestMeshSampleStages:
    """A mesh fed datagrams: what the merger spends placing first-sight
    rows, the host's side of the sample dispatches, the flush's gather,
    and the counters of the retired groups' sample path."""

    LINES = ([b"m.h%d:%d.5|h" % (i % 50, i) for i in range(150)]
             + [b"m.c%d:1|c" % i for i in range(25)])

    @pytest.mark.parametrize("stage", [
        "ingest.merge.route", "ingest.dispatch.mesh",
        "store.dispatch.histograms.gather"])
    def test_stage_is_in_the_timeline(self, mesh_lane_server, stage):
        srv, chan = mesh_lane_server
        for _ in range(2):  # every series first-sight again in the second
            send_and_merge(srv, self.LINES)
            srv.flush()
            chan.get_flush()
            st = stages_of(srv.obs_timeline.entries()[-1])
            assert st[stage]["duration_ns"] > 0
        if stage == "ingest.merge.route":
            assert st[stage]["off_path"] is True
            assert st[stage]["duration_ns"] \
                <= st["ingest.merge.remap"]["duration_ns"]
        elif stage == "ingest.dispatch.mesh":
            assert st[stage]["off_path"] is True
            assert st[stage]["dispatches"] == 3  # 150 samples / 64
        else:
            parent = st["store.dispatch.histograms"]
            assert parent["start_ns"] <= st[stage]["start_ns"]
            assert (st[stage]["start_ns"] + st[stage]["duration_ns"]
                    <= parent["start_ns"] + parent["duration_ns"])

    def test_counters_of_the_sample_path(self, mesh_lane_server):
        srv, chan = mesh_lane_server
        send_and_merge(srv, self.LINES)
        srv.flush()
        chan.get_flush()
        counted = srv.obs_timeline.entries()[-1]["mesh_ingest"]
        assert counted["dispatches"] == 3 and counted["samples"] == 150
        # a device's slice of the 64-sample chunk (row, value and
        # weight: 3 x 32 four-byte words at hosts 2) into the gather
        # and the guard's two masses, a dispatch
        assert counted["collective_bytes"] == 3 * 4 * (3 * 64 // 2 + 2)
        assert counted["guard_drains"] == 0
        names = srv.obs_timeline.entries()[-1]["stages"]
        assert not [s for s in names if s["name"].startswith("import.")]

    def test_a_store_without_a_mesh_has_none_of_them(self, lane_server):
        srv, chan, _post = lane_server
        send_and_merge(srv, self.LINES)
        srv.flush()
        chan.get_flush()
        entry = srv.obs_timeline.entries()[-1]
        assert "mesh_ingest" not in entry
        assert not {"ingest.merge.route", "ingest.dispatch.mesh",
                    "store.dispatch.histograms.gather"} & set(
                        stages_of(entry))


class TestDispatchAndFetchParts:
    @pytest.mark.parametrize("parent,child", [
        ("store.dispatch.histograms", "store.dispatch.histograms.drain"),
        ("store.dispatch.self_timers", "store.dispatch.self_timers.drain"),
        ("store.dispatch.sets", "store.dispatch.sets.drain"),
        ("store.dispatch.topk", "store.dispatch.topk.drain"),
        ("store.histograms.fetch", "store.histograms.fetch.wait"),
        ("store.self_timers.fetch", "store.self_timers.fetch.wait"),
        ("store.sets.fetch", "store.sets.fetch.wait"),
    ])
    def test_child_lands_inside_its_parent(self, obs_server, parent, child):
        srv, sink = obs_server
        for _ in range(2):   # self_timers has rows from the second on
            for pkt in (b"to:3.5|h", b"tc:1|c", b"tu:u1|s"):
                srv.handle_metric_packet(pkt)
            srv.flush()
            sink.get_flush()
        entry = srv.obs_timeline.entries()[-1]
        st = stages_of(entry)
        p, c = st[parent], st[child]
        assert p["start_ns"] <= c["start_ns"]
        assert c["start_ns"] + c["duration_ns"] \
            <= p["start_ns"] + p["duration_ns"]
        assert c["duration_ns"] <= p["duration_ns"]

        def find(nodes, name):
            for n in nodes:
                if n["name"] == name:
                    return n
                hit = find(n["children"], name)
                if hit is not None:
                    return hit
            return None

        node = find(entry["tree"], parent)
        assert child in {n["name"] for n in node["children"]}


class TestSerializerSplit:
    GOLDEN = (b'{"series":[{"metric":"svc.lat.max","points":[[1000,1.5]],'
              b'"tags":["env:prod","route:r1"],"type":"gauge","host":"h0",'
              b'"interval":10}]}')

    def _bodies(self, **kw):
        import zlib

        from veneur_tpu.core.columnar import build_arenas
        from veneur_tpu.native import egress

        if not egress.available():
            pytest.skip("no native toolchain")
        bodies = egress.dd_series_bodies(
            build_arenas(["svc.lat"]), build_arenas(["env:prod,route:r1"]),
            [b".max"], np.array([0], np.uint32), np.array([0], np.uint8),
            np.array([1.5], np.float64), np.array([0], np.uint8),
            timestamp=1000, interval=10, default_host="h0", **kw)
        return bodies, [zlib.decompress(b) if kw.get("compress_level", 1)
                        else b for b in bodies]

    def test_bodies_are_what_they_were_and_the_timing_adds_up(self):
        plain, plain_text = self._bodies()
        timing = {}
        timed, timed_text = self._bodies(timing=timing)
        assert timed == plain                 # byte for byte, deflated
        assert timed_text == [self.GOLDEN]
        assert timing["encode_ns"] > 0 and timing["deflate_ns"] > 0
        first = dict(timing)
        self._bodies(timing=timing)           # a second block adds on
        assert timing["encode_ns"] > first["encode_ns"]
        assert timing["deflate_ns"] > first["deflate_ns"]

    def test_uncompressed_bodies_spend_nothing_in_deflate(self):
        timing = {}
        _, text = self._bodies(timing=timing, compress_level=0)
        assert text == [self.GOLDEN]
        assert timing["deflate_ns"] == 0 and timing["encode_ns"] > 0

    def test_chunk_stages_lie_inside_serialize(self, lane_server):
        srv, chan, post = lane_server
        send_and_merge(srv, TestMergerStages.LINES)
        srv.flush()
        chan.get_flush()
        assert post.bodies
        stages = srv.obs_timeline.entries()[-1]["stages"]
        by_chunk = {}
        for s in stages:
            if s["name"].startswith("post.datadog.serialize"):
                by_chunk.setdefault(s["chunk"], {})[s["name"]] = s
        assert by_chunk
        for chunk, st in by_chunk.items():
            whole = st["post.datadog.serialize"]
            enc = st["post.datadog.serialize.encode"]
            dfl = st["post.datadog.serialize.deflate"]
            assert enc["duration_ns"] > 0 and dfl["duration_ns"] > 0, chunk
            assert enc["start_ns"] == dfl["start_ns"] == whole["start_ns"]
            assert enc["duration_ns"] + dfl["duration_ns"] \
                <= whole["duration_ns"], chunk

    @pytest.mark.parametrize("per_body,workers", [(25000, 3), (7, 3),
                                                  (7, 1)])
    def test_serialize_stage_says_its_bodies_and_workers(
            self, lane_server, monkeypatch, per_body, workers):
        """``workers`` 1 is the serial path: a chunk of one body takes it
        whatever the cores, a chunk of several where one worker is all
        there is; the workers' summed seconds ride beside."""
        from veneur_tpu.native import egress

        monkeypatch.setattr(egress, "dd_workers",
                            lambda n_bodies: min(n_bodies, workers))
        srv, chan, post = lane_server
        srv.metric_sinks[0].flush_max_per_body = per_body
        send_and_merge(srv, TestMergerStages.LINES)
        srv.flush()
        chan.get_flush()
        stages = [s for s in srv.obs_timeline.entries()[-1]["stages"]
                  if s["name"] == "post.datadog.serialize"]
        assert stages
        assert sum(s["bodies"] for s in stages) == len(post.bodies)
        for s in stages:
            # a block's bodies: its rows over the body size, rounded up
            assert s["workers"] == min(s["bodies"], workers), s
            assert s["encode_cpu_ns"] > 0 and s["deflate_cpu_ns"] > 0
        if per_body == 7:   # 40 histograms x 5 rows: many bodies a chunk
            assert max(s["bodies"] for s in stages) > 20
        else:
            assert {s["bodies"] for s in stages} == {1}
            assert {s["workers"] for s in stages} == {1}


class TestCaptureAndThreads:
    def test_host_scope_is_not_a_dispatch(self):
        before = obs_kernels.dispatch_snapshot()
        with obs_kernels.host_scope("test.host"):
            pass
        assert obs_kernels.dispatch_snapshot() == before
        assert "test.host" not in before
        host = {"merge", "swap", "fetch"}
        assert not host & {s for s, _ in obs_kernels.PROGRAM_SCOPES.values()}

    @staticmethod
    def capture_during_flushes(srv, chan, tmp_path):
        """A 2 s ``/debug/xprof`` capture while the server merges and
        flushes: its events as ``(name, start on the wall clock in
        ns)``, its line names, and its ``(start, stop)``."""
        import glob

        from jax.profiler import ProfileData

        result = []
        t = threading.Thread(target=lambda: result.append(
            obs_kernels.capture_xprof(2.0, base_dir=str(tmp_path))))
        t.start()
        # drive merges and flushes until the capture ends: some of them
        # fall inside it wherever its start and stop land
        while t.is_alive():
            send_and_merge(srv, TestMergerStages.LINES[:8])
            srv.flush()
            chan.get_flush()
            time.sleep(0.1)
        t.join(timeout=60)
        assert not t.is_alive()
        status, body, _ctype = result[0]
        assert status == 200, body
        events, lines, window = [], set(), None
        for path in glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                              recursive=True):
            for plane in ProfileData.from_file(path).planes:
                stats = dict(plane.stats)
                if "profile_start_time" in stats:
                    window = (stats["profile_start_time"],
                              stats["profile_stop_time"])
                for line in plane.lines:
                    lines.add(line.name)
                    events.extend((ev.name, ev.start_ns)
                                  for ev in line.events)
        # an event's start is relative to the capture's own
        start = window[0]
        return [(n, start + int(t)) for n, t in events], lines, window

    def test_capture_holds_the_host_scopes_and_no_python_tracer(
            self, lane_server, tmp_path):
        srv, chan, _post = lane_server
        events, lines, _window = self.capture_during_flushes(
            srv, chan, tmp_path)
        names = {n for n, _t in events}
        for scope in ("veneur.merge", "veneur.store.swap.twins",
                      "veneur.store.histograms.fetch.copy",
                      "veneur.serialize.histograms.arenas",
                      "veneur.serialize.histograms.block",
                      "veneur.post.datadog.serialize",
                      "veneur.post.datadog.post.wire",
                      "veneur.self_metrics",
                      "veneur.flush.digest.dense"):
            assert scope in names, (scope, sorted(
                n for n in names if n.startswith("veneur.")))
        # the Python tracer writes every call as "$file:line function"
        assert not [n for n in names if n.startswith("$")]
        # a thread named when the server turned ready has its name in
        # the trace too
        assert "ingest-merger" in lines

    def test_a_stage_lands_on_its_capture_event(self, lane_server,
                                                tmp_path):
        """The clock map: a ``scope=True`` stage placed on the capture
        at ``wall_start_ns + start_ns`` lies within 1 ms of its
        ``veneur.*`` event."""
        srv, chan, _post = lane_server
        events, _lines, _window = self.capture_during_flushes(
            srv, chan, tmp_path)
        seen = [t for n, t in events if n == "veneur.store.swap.twins"]
        assert seen
        placed = [e["wall_start_ns"] + st["start_ns"]
                  for e in srv.obs_timeline.entries()
                  for st in e["stages"] if st["name"] == "store.swap.twins"]
        # the stages between the capture's first and last such event: a
        # loaded host starts and stops recording some way inside the
        # window the capture states, and a flush at either edge can
        # fall in the window yet outside what was recorded
        lo, hi = min(seen) - 1_000_000, max(seen) + 1_000_000
        inside = [t for t in placed if lo <= t <= hi]
        assert inside
        for t in inside:
            assert min(abs(t - ev) for ev in seen) <= 1_000_000, (
                t, sorted(seen))

    def test_no_host_scope_opens_inside_another(self, lane_server,
                                                monkeypatch):
        """A parent gives its host scope to its children: during a flush
        no ``veneur.*`` host scope opens while another is open on the
        same thread (a capture's idle gap goes to the scope that
        overlaps it most, and an enclosing one would swallow it)."""
        from contextlib import contextmanager

        real = obs_kernels.host_scope
        open_scopes = {}
        opened, nested = [], []

        @contextmanager
        def tracking(name):
            stack = open_scopes.setdefault(threading.get_ident(), [])
            if stack:
                nested.append((stack[-1], name))
            stack.append(name)
            opened.append(name)
            try:
                with real(name):
                    yield
            finally:
                stack.pop()

        monkeypatch.setattr(obs_kernels, "host_scope", tracking)
        srv, chan, _post = lane_server
        for _ in range(2):
            send_and_merge(srv, TestMergerStages.LINES)
            srv.flush()
            chan.get_flush()
        assert {"store.swap.twins", "store.histograms.fetch.copy",
                "post.datadog.post.wire"} <= set(opened)
        assert not nested, nested

    def test_obs_threads_by_name_with_cpu_that_does_not_fall(
            self, lane_server):
        srv, chan, _post = lane_server
        _, body, _ = get(srv.ops_server.port, "/debug/vars")
        first = json.loads(body)["obs"]["threads"]
        for name in ("ingest-merger", "flush-ticker", "ingest-lane-0",
                     "ingest-lane-1", "MainThread"):
            assert first[name]["cpu_s"] >= 0.0, (name, sorted(first))
        send_and_merge(srv, TestMergerStages.LINES)
        srv.flush()
        chan.get_flush()
        _, body, _ = get(srv.ops_server.port, "/debug/vars")
        second = json.loads(body)["obs"]["threads"]
        for name in ("ingest-merger", "flush-ticker", "ingest-lane-0"):
            assert second[name]["cpu_s"] >= first[name]["cpu_s"], name
        merger = srv.ingest_fleet._merger
        with open(f"/proc/self/task/{merger.native_id}/comm") as f:
            assert f.read().strip() == "ingest-merger"

    def test_threads_that_share_a_name_are_numbered(self):
        from veneur_tpu import debug

        stop = threading.Event()
        twins = [threading.Thread(target=stop.wait, name="obs-twin")
                 for _ in range(3)]
        for t in twins:
            t.start()
        try:
            got = debug.thread_cpu()
        finally:
            stop.set()
            for t in twins:
                t.join(timeout=10)
        assert {"obs-twin", "obs-twin#2", "obs-twin#3"} <= set(got)
        assert "MainThread" in got
        with open("/proc/self/comm") as f:       # the process keeps its name
            assert f.read().strip() != "MainThread"


# ---------------------------------------------------------------------------
# the flush wall, accounted: the leaves, the one rule, the self-telemetry
# rows they take
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def leaf_entries():
    """Three steady flushes of a datagram-fed server with every group
    kind live, the Datadog sink streaming chunks of several bodies into
    a stub and a channel sink beside it (the batch fan-out's
    materialized path): the published entries, the first (which
    compiles) left out, and the self-telemetry group's rows."""
    from veneur_tpu.config import Config
    from veneur_tpu.native import egress
    from veneur_tpu.resilience import RetryPolicy
    from veneur_tpu.server import Server
    from veneur_tpu.sinks import ChannelMetricSink
    from veneur_tpu.sinks.datadog import DatadogMetricSink

    if not egress.available():
        pytest.skip("no native toolchain")
    dd = DatadogMetricSink(hostname="h0", tags=[], dd_hostname="http://dd",
                           api_key="k", post=_BodyLog(), interval=10,
                           flush_max_per_body=7,
                           retry_policy=RetryPolicy(max_attempts=1))
    dd.set_flush_deadline(None)
    cfg = Config(statsd_listen_addresses=["udp://127.0.0.1:0"],
                 num_readers=2, interval="86400s",
                 http_address="127.0.0.1:0", percentiles=[0.5, 0.99],
                 obs_timeline_intervals=8, store_initial_capacity=256,
                 store_chunk=128, flush_pipeline_depth=2,
                 flush_streaming=True)
    chan = ChannelMetricSink()
    srv = Server(cfg, metric_sinks=[dd, chan])
    srv.start()
    lines = (TestMergerStages.LINES
             + [b"m.t%d:%d|ms" % (i, i) for i in range(10)]
             + [b"m.g%d:%d|g" % (i, i) for i in range(10)]
             + [b"m.s:u%d|s" % i for i in range(10)]
             + [b"m.k:k%d|s|#veneurtopk" % (i % 3) for i in range(10)]
             + [b"m.gc:1|c|#veneurglobalonly"])
    try:
        for _ in range(3):
            send_and_merge(srv, lines)
            srv.flush()
            chan.get_flush()
        yield srv.obs_timeline.entries()[1:], srv.store.self_timers
    finally:
        srv.shutdown()


class TestFlushLeaves:
    @pytest.mark.parametrize("leaf", [
        "store.swap.lock_wait", "store.swap.twins",
        "store.scalars.snapshot", "store.scalars.block",
        "store.scalars.handoff", "store.summarize", "store.status",
        "store.globals", "store.release", "store.lane_wait",
        "store.dispatch.sets.compute", "store.dispatch.topk.compute",
        "store.histograms.fetch.copy", "store.histograms.commit",
        "serialize.histograms.arenas", "serialize.histograms.block",
        "serialize.histograms.handoff", "self_metrics",
        "post.stream_wait", "post.materialize", "post.sinks_wait",
        "post.datadog.marshal", "post.datadog.send",
        "post.datadog.serialize.first_body", "post.datadog.post.wire",
        "span_start", "stream_open", "epoch_handoff", "sink_metrics",
        "store.scalars.release", "publish.drain", "publish.lock_wait",
        "release"])
    def test_every_flush_has_the_leaf(self, leaf_entries, leaf):
        entries, _self_timers = leaf_entries
        for e in entries:
            assert leaf in stages_of(e), (leaf, sorted(stages_of(e)))

    def test_the_leaves_cover_nine_tenths(self, leaf_entries):
        entries, _self_timers = leaf_entries
        for e in entries:
            assert e["coverage_ratio"] >= 0.9, e["unstaged_ns"]

    def test_the_flushers_leaves_and_the_others_threads(self,
                                                        leaf_entries):
        """The flusher's own leaves are on its thread, and the stages
        that explain its waits on the others': the serializer lane's,
        the stream worker's, each sink's."""
        entries, _self_timers = leaf_entries
        for e in entries:
            st = stages_of(e)
            owner = e["thread"]
            for name in ("store.swap.twins", "store.lane_wait",
                         "post.sinks_wait", "self_metrics"):
                assert st[name]["thread"] == owner, name
            for name in ("serialize.histograms.arenas",
                         "post.datadog.post.wire", "post.datadog.send"):
                assert st[name]["thread"] != owner, name

    def test_stage_names_fit_the_self_timers_rows(self, leaf_entries):
        """Every stage name a flush records is a row of the
        self-telemetry group, whose 128 starting rows it has to fit: a
        group that grows compiles its programs anew inside the next
        flush (PR 29)."""
        entries, self_timers = leaf_entries
        names = {s["name"] for e in entries for s in e["stages"]}
        assert len(names) + 1 <= 128, len(names)   # + seal_to_merge
        assert self_timers.capacity == 128

    def test_unstaged_and_coverage_agree_and_a_gap_shows(self, obs_server,
                                                         monkeypatch):
        from veneur_tpu import flusher

        srv, sink = obs_server
        for _ in range(2):
            TestServerTimeline().flush(srv, sink)
        real = flusher._take_oldest_ingest_ns

        def late(*args):
            time.sleep(0.05)   # between egress_detect and stream_open
            return real(*args)

        monkeypatch.setattr(flusher, "_take_oldest_ingest_ns", late)
        TestServerTimeline().flush(srv, sink)
        entries = srv.obs_timeline.entries()
        for e in entries:
            assert e["coverage_ratio"] == round(
                1 - e["unstaged_ns"] / e["total_duration_ns"], 4)
        assert entries[-1]["unstaged_ns"] >= 50_000_000 \
            > entries[-2]["unstaged_ns"]


class TestSwapAndChunkLeaves:
    def test_lock_wait_reads_the_hold_of_another_thread(self):
        from veneur_tpu.core import MetricStore

        store = MetricStore(initial_capacity=32, chunk=128)
        held = threading.Event()

        def hold():
            with store._lock:
                held.set()
                time.sleep(0.05)

        t = threading.Thread(target=hold)
        t.start()
        held.wait()
        rec = StageRecorder()
        with activate(rec):
            store.flush([0.5], AGGS, is_local=False, now=1, forward=False)
        t.join()
        st = stages_of(rec.finish())
        assert st["swap.lock_wait"]["duration_ns"] >= 40_000_000
        assert st["swap.twins"]["start_ns"] >= \
            st["swap.lock_wait"]["start_ns"] + 40_000_000

    @pytest.fixture()
    def chunk_stages(self, monkeypatch):
        """One streamed flush of 40 histograms into a Datadog sink of 7
        rows a body whose POSTs sleep: the stages and the sleeps."""
        from veneur_tpu.core import MetricStore
        from veneur_tpu.core.pipeline import ChunkStream
        from veneur_tpu.native import egress
        from veneur_tpu.resilience import RetryPolicy
        from veneur_tpu.samplers.parser import parse_metric
        from veneur_tpu.sinks.datadog import DatadogMetricSink

        if not egress.available():
            pytest.skip("no native toolchain")
        dd = DatadogMetricSink(hostname="h0", tags=[],
                               dd_hostname="http://dd", api_key="k",
                               post=_BodyLog(), interval=10,
                               flush_max_per_body=7,
                               retry_policy=RetryPolicy(max_attempts=1))
        dd.set_flush_deadline(None)
        slept = []

        def post_chunk_body(body, nrows, requeued=False):
            t0 = time.monotonic_ns()
            time.sleep(0.004)
            slept.append(time.monotonic_ns() - t0)
            return True

        monkeypatch.setattr(dd, "_post_chunk_body", post_chunk_body)
        store = MetricStore(initial_capacity=64, chunk=128,
                            flush_pipeline_depth=2)
        for i in range(40):
            store.process_metric(parse_metric(b"lat.%d:%d|h" % (i, i)))
        rec = StageRecorder()
        stream = ChunkStream([dd], 1, rec=rec)
        with activate(rec):
            store.flush([0.5], AGGS, is_local=False, now=1, forward=False,
                        columnar=True, stream=stream)
            stream.close()
        by_chunk = {}
        for s in rec.finish()["stages"]:
            if s["name"].startswith("post.datadog."):
                by_chunk.setdefault(s["chunk"], {})[s["name"]] = s
        return by_chunk, slept

    def test_post_wire_is_the_posts_own_time(self, chunk_stages):
        by_chunk, slept = chunk_stages
        wires = [st["post.datadog.post.wire"] for st in by_chunk.values()]
        assert sum(w["posts"] for w in wires) == len(slept) > 20
        total = sum(w["duration_ns"] for w in wires)
        # the sleeps' own clocks, and the call around each
        assert sum(slept) <= total <= sum(slept) + 1_000_000 * len(slept)
        for st in by_chunk.values():
            assert st["post.datadog.post.wire"]["duration_ns"] <= \
                st["post.datadog.post"]["duration_ns"]

    def test_first_body_is_inside_serialize(self, chunk_stages):
        by_chunk, _slept = chunk_stages
        multi = [st for st in by_chunk.values()
                 if st["post.datadog.serialize"]["bodies"] > 1]
        assert multi
        for st in multi:
            first = st["post.datadog.serialize.first_body"]
            whole = st["post.datadog.serialize"]
            assert 0 < first["duration_ns"] <= whole["duration_ns"]
            assert first["start_ns"] == whole["start_ns"]


class TestStageSpanMirror:
    """The stage tree's SSF mirror is a burst into the server's own span
    channel, whose fill is an overload pressure source: it is held well
    under the low watermark however many stages an interval has."""

    def _server(self):
        from veneur_tpu.config import Config
        from veneur_tpu.server import Server
        from veneur_tpu.sinks import ChannelMetricSink

        cfg = Config(statsd_listen_addresses=[], interval="86400s",
                     store_initial_capacity=32, store_chunk=128)
        # NOT started: no span worker drains the channel
        return Server(cfg, metric_sinks=[ChannelMetricSink()])

    @staticmethod
    def _entry(n):
        stages = [{"name": "store", "start_ns": 0, "duration_ns": 9}]
        for i in range(n - 1):
            name = "store.g%d" % i if i % 3 == 0 else \
                "store.g%d.part%d" % (i - i % 3, i % 3)
            stages.append({"name": name, "start_ns": i + 1,
                           "duration_ns": 1})
        return {"wall_start": 1000.0, "stages": stages}

    @pytest.mark.parametrize("stages,queued", [(20, 0), (120, 0), (120, 30),
                                               (120, 90)])
    def test_burst_stays_under_half_the_low_watermark(self, stages, queued):
        import queue

        from veneur_tpu import flusher
        from veneur_tpu.trace import Trace

        srv = self._server()
        for _ in range(queued):           # other traffic already waiting
            srv.span_chan.put_nowait(object())
        cap = int(srv.span_chan.maxsize * srv.overload.low
                  * flusher.STAGE_SPAN_SHARE)
        entry = self._entry(stages)
        root = Trace.start_trace("veneur.flush")
        flusher._record_stage_spans(srv, root, entry)
        sent = []
        while True:
            try:
                item = srv.span_chan.get_nowait()
            except queue.Empty:
                break
            if hasattr(item, "name"):
                sent.append(item)
        want = min(stages, max(0, cap - queued))
        assert len(sent) == want
        assert queued + len(sent) <= max(cap, queued)
        assert entry.get("stage_spans_skipped", 0) == stages - want
        # shallowest first: no span went while a shallower stage stayed
        depth = [s.name.count(".") for s in sent]
        left = [st["name"].count(".") + 2 for st in entry["stages"]
                if "veneur.flush." + st["name"] not in
                {s.name for s in sent}]
        assert not sent or not left or max(depth) <= min(left)
        # and every span that went hangs off its parent's span or the root
        by_name = {s.name: s for s in sent}
        for s in sent:
            parent = by_name.get(s.name.rsplit(".", 1)[0])
            assert s.parent_id == (parent.id if parent else root.span_id)
