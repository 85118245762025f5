"""Flush orchestration: drain the store, fan out to sinks, forward upstream.

Behavioral port of ``/root/reference/flusher.go:26-132``: events flush to
every metric sink's ``flush_other_samples``; span sinks flush; the store
drains into InterMetrics (percentiles suppressed for mixed histograms on a
local instance); a local instance hands forwardable sketch state to the
forwarding layer; each metric sink gets the final batch on its own thread;
plugins run after the sinks.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import TYPE_CHECKING

from veneur_tpu.overload import DEFAULT_LOW_WATERMARK
from veneur_tpu.sinks.base import filter_acceptable

if TYPE_CHECKING:
    from veneur_tpu.server import Server

log = logging.getLogger("veneur.flusher")


def flush_once(server: "Server"):
    """One interval flush, wrapped in a self-trace span (flusher.go:26-29).
    Records flush-staleness state on the server: a completed pass stamps
    ``last_flush_time`` (what /healthcheck/ready and
    ``veneur.flush.age_seconds`` read); a raising one marks
    ``last_flush_ok`` False and leaves the stamp stale.

    With observability on (``obs_enabled``), the whole pass runs under a
    :class:`veneur_tpu.obs.StageRecorder`: every stage lands in the
    ``/debug/flush-timeline`` ring, becomes a child SSF span under this
    root span, and dogfoods into the store's self-telemetry digest
    group (docs/observability.md)."""
    from veneur_tpu import obs
    from veneur_tpu.trace import Trace
    span = Trace.start_trace("veneur.flush")
    span.name = "flush"
    timeline = getattr(server, "obs_timeline", None)
    rec = obs.StageRecorder() if timeline is not None else None
    if rec is not None:
        # join the fleet trace plane (obs/tracectx.py): this interval's
        # stage tree publishes under the flush span's ids, so the hop a
        # forward stamps downstream (X-Veneur-Trace) parents back here
        rec.adopt_trace(span.trace_id, span_id=span.span_id,
                        hop="local.flush" if server.is_local()
                        else "global.flush")
    try:
        with obs.activate(rec):
            _flush_once(server, span, rec)
        server.last_flush_time = time.time()
        server.last_flush_ok = True
    except Exception:
        server.last_flush_ok = False
        raise
    finally:
        # the interval's ChunkStream must be joined on EVERY unwind
        # path (an exception between the store drain and the post
        # barrier would otherwise leak its workers); close() is
        # idempotent, so the normal path's barrier already ran
        stream = getattr(server, "_active_stream", None)
        if stream is not None:
            server._active_stream = None
            try:
                stream.close()
            except Exception:
                log.exception("stream close failed")
        if rec is not None:
            try:
                _publish_interval(server, span, rec, timeline)
            except Exception:  # telemetry must never fail a flush
                log.exception("flush-timeline publication failed")
        span.client_record(getattr(server, "trace_client", None))


def _publish_interval(server, span, rec, timeline):
    """Interval-end merge: finish the stage record, publish it to the
    timeline ring, mirror the stage tree as child SSF spans under the
    flush root, and sample every stage duration (plus the ingest
    lanes' seal->merge latencies) into the self-telemetry group.

    Fleet trace plane additions (obs/tracectx.py): the interval's
    received cross-hop records (imports, handoffs) drain out of the
    server's HopLog into this entry as off-path stages carrying their
    trace ids, the entry is stamped with the contributing trace-id set
    (``import_traces`` — what /debug/trace matches the global flush
    on), the ingest lanes' per-stage trees land under an off-path
    ``ingest`` stage, and on a global the oldest ingest-era stamp
    aboard becomes ``veneur.fleet.e2e_age_ns`` — measured HERE, after
    the sink joins, so the age really covers ingest → sink 2xx."""
    from veneur_tpu.obs import kernels as obs_kernels
    from veneur_tpu.obs import tracectx
    from veneur_tpu.trace import samples as ssf_samples

    # the publication runs on the flusher's thread inside the
    # interval's wall (which ends at finish()), so it has its leaves:
    # the hop log and the lanes' cumulative trees read, then the wait
    # for the store lock that the import workers' clock sits under
    with rec.stage("publish"):
        with rec.stage("drain", scope=True):
            hop_log = getattr(server, "obs_hops", None)
            hops = hop_log.drain() if hop_log is not None else []
            for h in hops:
                # the true wall times ride as attrs: a hop that landed BEFORE
                # this interval started gets its start clamped to 0 in the
                # recorder's relative frame, and the /debug/trace stitcher
                # needs the real ordering
                attrs = {k: v for k, v in h.items()
                         if k not in ("hop", "duration_ns")}
                rec.record_abs(h["hop"],
                               tracectx.wall_to_mono_ns(rec, h["wall_start"]),
                               tracectx.wall_to_mono_ns(rec, h["wall_end"]),
                               off_path=True, **attrs)
            ingest_stages = _drain_ingest_stages(server)
            if ingest_stages:
                # the ingest-path stage tree: cumulative lane-time since the
                # last interval (recv includes socket wait), anchored at the
                # interval start and off-path — ingest overlaps the whole
                # interval, so it must not count against flush coverage
                total = sum(ingest_stages[s]
                            for s in ("recv", "decode", "stage", "seal"))
                rec.record_abs("ingest", rec.t0_ns, rec.t0_ns + total,
                               off_path=True, lanes=ingest_stages["lanes"],
                               iters=ingest_stages["iters"])
                for stage in ("recv", "decode", "stage", "seal"):
                    rec.record_abs(f"ingest.{stage}", rec.t0_ns,
                                   rec.t0_ns + ingest_stages[stage],
                                   off_path=True)
                # the merger thread's own busy time over the same stretch, and
                # inside it the lock wait, the remap + interning and the
                # staging calls (core/store.py import_lane_chunk)
                merger = ingest_stages["merger"]
                rec.record_abs("ingest.merge", rec.t0_ns,
                               rec.t0_ns + merger["merge"], off_path=True,
                               chunks=merger["chunks"],
                               rows_interned=merger["rows_interned"])
                stages = ["lock_wait", "remap", "stage"]
                if getattr(getattr(server, "store", None), "mesh",
                           None) is not None:
                    # of the remap: a mesh's placing of the first-sight rows
                    stages.append("route")
                for stage in stages:
                    rec.record_abs(f"ingest.merge.{stage}", rec.t0_ns,
                                   rec.t0_ns + merger[stage], off_path=True)
        with rec.stage("lock_wait"):
            imports = _take_import_stages(server)
        if imports:
            # a global's import path over the same stretch, clocked a
            # message at a time by its gRPC workers (core/store.py
            # import_columnar): cumulative and off-path like the merger's
            messages = imports.pop("messages")
            for stage, ns in imports.items():
                rec.record_abs(f"import.{stage}", rec.t0_ns, rec.t0_ns + ns,
                               off_path=True)
    entry = rec.finish()
    if imports:
        entry["import"] = {"messages": messages}
    # what the retired digest groups' import drains counted (DigestGroup
    # notes them on its drain and fetch stages)
    staged = [s for s in entry["stages"] if "import_dispatches" in s]
    if staged:
        entry["import_digests"] = {
            "dispatches": sum(s["import_dispatches"] for s in staged),
            "centroids": sum(s["import_centroids"] for s in staged),
            "guard_drains": sum(s.get("import_guard_drains", 0)
                                for s in entry["stages"])}
    # and the sample path of a mesh's retired digest groups
    # (MeshDigestGroup notes them the same way)
    sampled = [s for s in entry["stages"] if "mesh_ingest_dispatches" in s]
    if sampled:
        entry["mesh_ingest"] = {
            "dispatches": sum(s["mesh_ingest_dispatches"] for s in sampled),
            "samples": sum(s["mesh_ingest_samples"] for s in sampled),
            "collective_bytes": sum(s["mesh_ingest_collective_bytes"]
                                    for s in sampled),
            "guard_drains": sum(s.get("mesh_ingest_guard_drains", 0)
                                for s in entry["stages"])}
    # and the dense sample path's: its dispatches, the rows they
    # drained before binning into them, the drain loop's trips
    sampled = [s for s in entry["stages"] if "ingest_samples_dispatches" in s]
    if sampled:
        entry["ingest_samples"] = {
            "dispatches": sum(s["ingest_samples_dispatches"]
                              for s in sampled),
            "rows_drained": sum(s.get("ingest_samples_rows_drained", 0)
                                for s in entry["stages"]),
            "drain_trips": sum(s.get("ingest_samples_drain_trips", 0)
                               for s in entry["stages"])}
    # the slab store's (core/slab.py SlabDigestGroup notes them on its
    # drain, compute and fetch stages): the sample path's dispatches,
    # the rows they drained and the drain loop's trips, the slabs a
    # generation placed and the host's seconds in it, and what
    # the flush programs worked on against what was live
    slabbed = [s for s in entry["stages"] if "slab_grows" in s]
    if slabbed:
        stages = entry["stages"]
        entry["slab"] = {
            "dispatches": sum(s["slab_ingest_dispatches"] for s in slabbed),
            "rows_drained": sum(s.get("slab_ingest_rows_drained", 0)
                                for s in stages),
            "drain_trips": sum(s.get("slab_ingest_drain_trips", 0)
                               for s in stages),
            "grows": sum(s["slab_grows"] for s in slabbed),
            "grow_s": sum(s["slab_grow_ns"] for s in slabbed) / 1e9,
            "rows_live": sum(s.get("slab_rows_live", 0) for s in stages),
            "rows_run": sum(s.get("slab_rows_run", 0) for s in stages)}
    # the heavy-hitter group's count-min updates (HeavyHitterGroup
    # notes them on its drain stage)
    topk = [s["topk_dispatches"] for s in entry["stages"]
            if "topk_dispatches" in s]
    if topk:
        entry["topk"] = {"dispatches": sum(topk)}
    if hops:
        tids = sorted({h["trace_id"] for h in hops if h.get("trace_id")})
        if tids:
            entry["import_traces"] = tids
    latencies = _drain_ingest_latencies(server)
    if latencies:
        entry["ingest_seal_to_merge"] = {
            "count": len(latencies),
            "max_ns": int(max(latencies)),
            "avg_ns": int(sum(latencies) / len(latencies))}
    # what the dense flush programs worked on against what was live
    # (core/store.py DigestGroup._run_flush notes both on its stage)
    ran = [s for s in entry["stages"] if "rows_run" in s]
    if ran:
        entry["digest_flush_rows"] = {
            "live": sum(s["rows_live"] for s in ran),
            "run": sum(s["rows_run"] for s in ran)}
    # freshness: the oldest ingest-era stamp this interval aggregated —
    # own lanes and received hops, both taken AT the swap boundary in
    # _flush_once (a post-swap arrival ages the next interval)
    oldest = getattr(server, "_interval_oldest_ingest_ns", None)
    e2e_ns = None
    if oldest:
        age_ns = max(0, time.time_ns() - oldest)
        entry["oldest_sample_age_ns"] = age_ns
        if not server.is_local():
            # the sink threads joined before this runs: the age spans
            # ingest stamp -> global sink 2xx, the true e2e freshness
            e2e_ns = age_ns
            entry["e2e_age_ns"] = e2e_ns
    timeline.publish(entry)
    _record_stage_spans(server, span, entry)
    store = getattr(server, "store", None)
    if store is not None and hasattr(store, "sample_self_timing"):
        for stage in entry["stages"]:
            store.sample_self_timing(stage["name"], stage["duration_ns"])
        for ns in latencies:
            store.sample_self_timing("ingest.seal_to_merge", float(ns))
        if e2e_ns is not None:
            # exact p50/p99 through the dedicated digest group, under
            # its own metric name (docs/observability.md "Fleet
            # tracing")
            store.sample_self_timing("e2e", float(e2e_ns),
                                     name="veneur.fleet.e2e_age_ns")
    for hop_name, n in sorted(
            _count_by(hops, "hop").items()):
        span.add(ssf_samples.count("veneur.trace.hops_total", float(n),
                                   {"hop": hop_name}))
    agg = getattr(server, "fleet_aggregator", None)
    if agg is not None:
        span.add(ssf_samples.count(
            "veneur.trace.fleet_pull_errors_total",
            float(_delta_since(agg, "_last_pull_errors",
                               agg.pull_errors_total)), None))
    # live device observability: coverage of the interval's stages plus
    # compile/dispatch deltas per kernel scope (what the recompile lint
    # pass proves statically, observed at runtime)
    span.add(
        ssf_samples.gauge("veneur.obs.stage_coverage_ratio",
                          float(entry["coverage_ratio"]), None),
        # clamped: live _cache_size sums SHRINK when jax caches clear,
        # and a negative compile count would read as a leak reversing
        ssf_samples.count(
            "veneur.obs.kernel_compiles_total",
            max(0.0, float(_delta_since(server, "_last_kernel_compiles",
                                        obs_kernels.compiles_total()))),
            None))
    for scope_name, n in sorted(obs_kernels.dispatch_snapshot().items()):
        span.add(ssf_samples.count(
            "veneur.obs.kernel_dispatches_total",
            float(_delta_since(server, f"_last_dispatch_{scope_name}", n)),
            {"scope": scope_name}))


def _count_by(records: list, key: str) -> dict:
    out: dict = {}
    for r in records:
        k = r.get(key)
        if k:
            out[k] = out.get(k, 0) + 1
    return out


def _take_import_stages(server):
    """The store's import-path clock since the last interval
    (MetricStore.take_import_stages); None where nothing was imported
    or the store keeps none."""
    take = getattr(getattr(server, "store", None), "take_import_stages",
                   None)
    if take is None:
        return None
    try:
        return take()
    except Exception:  # pragma: no cover - telemetry only
        log.exception("import stage drain failed")
        return None


def _drain_ingest_stages(server):
    """Sum the interval's per-stage ingest-lane time over every fleet
    (ingest/lanes.py take_ingest_stages); None when lanes are absent
    or stage tracing is off."""
    total = None
    for fleet in getattr(server, "_ingest_fleets", None) or ():
        try:
            stages = fleet.take_ingest_stages()
        except Exception:  # pragma: no cover - telemetry only
            log.exception("ingest stage drain failed")
            continue
        if not stages:
            continue
        if total is None:
            total = stages
        else:
            for k in ("recv", "decode", "stage", "seal", "iters",
                      "lanes"):
                total[k] += stages[k]
            for k, v in stages["merger"].items():
                total["merger"][k] += v
    return total


def _take_oldest_ingest_ns(server):
    """The oldest ingest-era stamp among lane chunks merged since the
    last flush (read-and-reset per fleet)."""
    oldest = None
    for fleet in getattr(server, "_ingest_fleets", None) or ():
        try:
            v = fleet.take_oldest_ingest_ns()
        except Exception:  # pragma: no cover - telemetry only
            continue
        if v and (oldest is None or v < oldest):
            oldest = v
    return oldest


def _drain_ingest_latencies(server) -> list:
    """Collect the interval's seal->merge latencies (ns) from every
    ingest fleet (ingest/lanes.py stamps each SealedChunk at seal; the
    merger measures the gap when it folds the chunk in)."""
    out: list = []
    for fleet in getattr(server, "_ingest_fleets", None) or ():
        try:
            out.extend(fleet.take_merge_latencies())
        except Exception:  # pragma: no cover - telemetry only
            log.exception("ingest latency drain failed")
    return out


# the share of the low overload watermark's worth of the span channel
# that one interval's stage spans may fill (_record_stage_spans)
STAGE_SPAN_SHARE = 0.5


def _record_stage_spans(server, root, entry):
    """Mirror the interval's stage tree as child SSF spans: one span
    per stage, parented on its dotted-path parent's span (top-level
    stages hang off the flush root), start/end mapped onto the root's
    wall clock. Same nonblocking client as the root — a full span
    channel drops them.

    The mirror is a burst into the server's own span channel, whose
    fill is an overload pressure source (overload.py): a span for each
    of 70 stages read as pressure 0.70 of the default 100-slot channel
    and froze first-sight series for a tick (PERF.md, PR 29). So the
    burst is held to ``STAGE_SPAN_SHARE`` of the low watermark's worth
    of the channel, less what is queued: the shallowest stages go
    first (so a stage that goes has its ancestors with it), and
    ``stage_spans_skipped`` on the entry says how many stayed in the
    timeline only."""
    cl = getattr(server, "trace_client", None)
    if cl is None:
        return
    stages = entry["stages"]
    chan = getattr(server, "span_chan", None)
    if chan is not None and chan.maxsize > 0:
        low = getattr(getattr(server, "overload", None), "low",
                      DEFAULT_LOW_WATERMARK)
        room = int(chan.maxsize * low * STAGE_SPAN_SHARE) - chan.qsize()
        if len(stages) > room:
            by_depth = sorted(stages, key=lambda s: s["name"].count("."))
            keep = {id(s) for s in by_depth[:max(0, room)]}
            entry["stage_spans_skipped"] = len(stages) - len(keep)
            stages = [s for s in stages if id(s) in keep]
    wall0 = entry["wall_start"]
    by_path = {}
    for stage in stages:
        path = stage["name"]
        parent = by_path.get(path.rsplit(".", 1)[0]) \
            if "." in path else None
        if parent is None:
            parent = root
        child = parent.start_child_span()
        child.name = f"veneur.flush.{path}"
        child.start = wall0 + stage["start_ns"] / 1e9
        child.end = child.start + stage["duration_ns"] / 1e9
        for key, value in stage.items():
            if key not in ("name", "start_ns", "duration_ns"):
                child.tags[key] = str(value)
        by_path[path] = child
        child.client_record(cl)


def _flush_once(server: "Server", span, rec=None):
    from veneur_tpu import obs
    from veneur_tpu.trace import samples as ssf_samples
    now = int(time.time())

    # events → FlushOtherSamples on each metric sink (flusher.go:42-47)
    with obs.maybe_stage("events"):
        samples = server.event_worker.flush()
        for sink in server.metric_sinks:
            try:
                sink.flush_other_samples(samples)
            except Exception:
                log.exception("sink %s flush_other_samples failed",
                              sink.name)

    # span sinks flush concurrently with the metric path (flusher.go:49).
    # A wedged span sink can hold its barrier for 9s, so with short
    # intervals the previous flusher may still be running — never stack a
    # second concurrent flush onto the same sinks
    span_flusher = getattr(server, "_span_flush_thread", None)
    if span_flusher is None or not span_flusher.is_alive():
        span_flusher = threading.Thread(
            target=_flush_spans, args=(server,), daemon=True)
        server._span_flush_thread = span_flusher
        # a wait: a thread's start returns once the thread runs
        with obs.maybe_stage("span_start"):
            span_flusher.start()
    else:
        # degradation must be observable, not just logged: counted here,
        # emitted below as veneur.flush.span_flush_skipped_total
        server._span_flush_skipped = getattr(
            server, "_span_flush_skipped", 0) + 1
        log.warning("previous span flush still running; skipping this "
                    "interval's span flush")

    # the flush deadline (resilience/deadline.py): egress retries across
    # forwarders and sinks share one budget — min(forward_timeout,
    # interval) — so backoff can never push a flush past the boundary
    from veneur_tpu.resilience import Deadline

    budget = min(server.interval,
                 getattr(server.config, "forward_timeout_seconds", 10.0))
    # seeded deadline-pressure faults (resilience/faults.py SOAK_KINDS)
    # shrink one interval's budget: the retry ladder gives up early and
    # the requeue paths must absorb the interval — one schedule draw
    # per flush keeps the fault cadence aligned with the interval
    soak_inj = getattr(server, "soak_injector", None)
    if soak_inj is not None:
        budget = soak_inj.scale_deadline("flush.deadline", budget)
    deadline = Deadline.after(budget)

    is_local = server.is_local()
    if is_local and server.forward_fn is None and not server._warned_no_forward:
        server._warned_no_forward = True
        log.warning("forward_address is set but no forwarding layer is "
                    "registered; global-scope state (sets, digests, global "
                    "counters/gauges) will be dropped each interval")
    percentiles = server.histogram_percentiles
    forwarding = is_local and server.forward_fn is not None
    # the heavy-hitter sketch rides both transports (JSON entry /
    # MetricList.topk extension) EXCEPT when forwarding into a reference
    # fleet (forward_reference_compatible): then the local emits its own
    # top-k instead — say so once
    topk_ok = getattr(server._forwarder, "supports_topk", True) \
        if server._forwarder is not None else True
    if forwarding and not topk_ok and not getattr(
            server, "_warned_topk_grpc", False):
        server._warned_topk_grpc = True
        log.warning("reference-compatible forwarding cannot carry the "
                    "heavy-hitter sketch (a framework extension); "
                    "topk series emit locally instead of fleet-merged")
    # columnar egress: flush results stay flat arrays end-to-end for
    # native sinks; anything else materializes InterMetrics once, lazily
    use_columnar = bool(getattr(server.config, "flush_columnar", True))
    if use_columnar:
        from veneur_tpu.native import egress

        # the first call may BUILD the native egress library (seconds);
        # without a stage of its own it reads as unaccounted time on
        # the first interval's timeline
        with obs.maybe_stage("egress_detect"):
            use_columnar = egress.available()
    # device-compacted digest forwarding (PackedDigestPlanes) whenever
    # the forwarder can take it: the raw [S,K] f32 plane fetch is what
    # blew the interval at 1M+ forwarded series
    digest_format = "packed" if (
        forwarding and use_columnar
        and getattr(server._forwarder, "wants_packed_digests", False)) \
        else "dense"
    # freshness anchor, read-and-reset AT the swap boundary: the
    # oldest lane chunk merged before the swap plus the oldest
    # received-hop stamp recorded before it — the samples THIS flush
    # drains. A stamp arriving after the swap merges into the next
    # generation and must age the NEXT interval (taking it at publish
    # time would attribute a late import's age to an interval that
    # never emitted its samples, and rob the interval that does).
    # _publish_interval and the forward's trace context read the stash.
    oldest_ingest = _take_oldest_ingest_ns(server)
    hop_log = getattr(server, "obs_hops", None)
    if hop_log is not None:
        hop_oldest = hop_log.take_oldest_ingest_ns()
        if hop_oldest and (oldest_ingest is None
                           or hop_oldest < oldest_ingest):
            oldest_ingest = hop_oldest
    server._interval_oldest_ingest_ns = oldest_ingest
    # streaming egress (docs/internals.md "Life of a flush"): with the
    # pipeline on, every sink that can take chunked bodies gets each
    # completed group's blocks POSTed WHILE later groups still compute/
    # fetch, and (when the forwarder takes chunks) forwardable digest
    # shards ship upstream the same way — behind the same retry/
    # breaker/deadline ladder, with per-chunk requeue accounting
    with obs.maybe_stage("stream_open"):   # a wait: its workers' start
        stream, stream_sinks = _build_stream(server, now, deadline, rec,
                                             use_columnar, forwarding,
                                             span)
    # flush_once's finally closes this on every unwind path; the happy
    # path's post barrier below closes it first (close is idempotent)
    server._active_stream = stream
    # warm-standby replication (fleet/standby.py): capture the state
    # this flush is about to drain — non-destructively, BEFORE the
    # generation swap consumes it — and hand it to the replicator only
    # AFTER the flush lands (post-flush ordering is what makes the
    # promoted standby's counter exclusion exactly right: everything
    # replicated was already emitted). Capture only while leading; a
    # fenced ex-active must stop streaming immediately.
    ha_snapshot = None
    sby = getattr(server, "standby_manager", None)
    if sby is not None and sby.is_leader \
            and (sby.peers or sby._peers_file):
        # top-level stage name (no dot): a dotted name would read as a
        # child of a nonexistent parent and its wall time would fall
        # out of the timeline's coverage_ratio numerator
        with obs.maybe_stage("ha_capture"):
            try:
                ha_snapshot = server.store.snapshot_state()
            except Exception:
                log.exception("HA replication capture failed; this "
                              "epoch will not replicate")
    t0 = time.perf_counter()
    with obs.maybe_stage("store"):
        final_metrics, forwardable, ms = server.store.flush(
            percentiles, server.histogram_aggregates,
            is_local=is_local, now=now, forward=forwarding,
            forward_topk=topk_ok, columnar=use_columnar,
            digest_format=digest_format, stream=stream)
    flush_elapsed = time.perf_counter() - t0
    log.debug("store flush took %.1f ms (%s)", flush_elapsed * 1e3, ms)
    # the store just drained: any existing checkpoint captured state
    # that is now flushing to sinks — truncate it so a restart can
    # never merge (and double-flush) an already-emitted interval.
    # Non-blocking: a checkpoint write in flight holds the IO lock for
    # its full write+fsync, and the writer's own post-commit epoch
    # check removes the stale file instead
    with obs.maybe_stage("epoch_handoff", scope=True):
        ckpt = getattr(server, "checkpointer", None)
        if ckpt is not None:
            ckpt.truncate(blocking=False)
        if ha_snapshot is not None:
            # the flush landed: the captured (now-retired) epoch may
            # stream to the standbys off the flush path (depth-1
            # drop-oldest)
            groups, flush_epoch = ha_snapshot
            sby.capture(groups, flush_epoch)
    # the canonical self-metric set (README.md:248-277) rides on the
    # flush span and re-enters the pipeline through the extraction sink
    with obs.maybe_stage("self_metrics", scope=True):
        span.add(
            ssf_samples.timing("veneur.flush.total_duration_ns",
                               flush_elapsed, {"part": "store"}),
            ssf_samples.count("veneur.flush.post_metrics_total",
                              float(len(final_metrics)), None),
            ssf_samples.count(
                "veneur.flush.span_flush_skipped_total",
                float(_delta_since(
                    server, "_last_span_flush_skipped",
                    getattr(server, "_span_flush_skipped", 0))),
                None),
            ssf_samples.gauge("veneur.flush.age_seconds",
                              server.flush_age_seconds()
                              if hasattr(server, "flush_age_seconds")
                              else 0.0, None),
            ssf_samples.count(
                "veneur.flush.overrun_total",
                float(_delta_since(server, "_last_flush_overruns",
                                   getattr(server, "flush_overruns", 0))),
                None),
            *_worker_samples(server, ms),
            *_overload_samples(server, ms),
            *_fleet_samples(server),
            *_handoff_samples(server),
            *_ha_samples(server),
            *_forward_samples(server),
            *_import_samples(server),
            *_checkpoint_samples(server),
            *_trace_client_samples(server),
            *_runtime_samples())

    # local → global forwarding happens off the flush path
    # (flusher.go:66-75); the flush span rides along so the global's
    # import span joins this trace (http/http.go:184-188)
    if is_local and server.forward_fn is not None and len(forwardable):
        import inspect

        try:
            fwd_params = inspect.signature(server.forward_fn).parameters
        except (TypeError, ValueError):
            fwd_params = {}  # lint: ok(swallowed-exception) introspection fallback: the forward below still runs, just without optional kwargs
        kwargs = {}
        if "parent_span" in fwd_params:
            kwargs["parent_span"] = span
        if "deadline" in fwd_params:
            # the forward runs off the flush path but shares the flush
            # budget: its retries must finish before the next interval
            kwargs["deadline"] = deadline
        if "trace_ctx" in fwd_params:
            # the fleet trace plane's hop baggage (obs/tracectx.py):
            # this flush's span ids + the oldest ingest-era stamp
            # aboard the forwarded state (interval start when the
            # legacy readers left no stamp)
            from veneur_tpu.obs import TraceContext

            ingest_ns = (getattr(server, "_interval_oldest_ingest_ns",
                                 None) or int(now * 1e9))
            kwargs["trace_ctx"] = TraceContext(span.trace_id,
                                               span.span_id, ingest_ns)
        def fwd():
            # the forward runs off the flush path; with observability
            # on it lands in the interval's already-published timeline
            # entry as an off-path stage (recorder.record_late)
            t_fwd = time.monotonic_ns()
            try:
                server.forward_fn(forwardable, **kwargs)
            finally:
                if rec is not None:
                    rec.record_late("forward", t_fwd, time.monotonic_ns(),
                                    series=len(forwardable))
        threading.Thread(target=fwd, daemon=True).start()

    if not final_metrics:
        if stream is not None:
            stream.close()
        with obs.maybe_stage("span_join"):
            span_flusher.join(timeout=10.0)
        return

    # one thread per metric sink (flusher.go:82-93). ``post`` starts
    # BEFORE the stream barrier so it covers the streamed chunks' tail
    # as well as the batch fan-out; by the time the overrun check runs
    # every chunk is acked or requeued. The flusher's waits here are
    # its leaves (``post.stream_wait``, and ``post.sinks_wait`` for
    # each sink thread's start and for their join); each sink's thread
    # opens ``post.<sink>`` on its own stage stack, so the sink's
    # ``marshal`` and ``send`` nest under it.
    t0 = time.perf_counter()
    with obs.maybe_stage("post", sinks=len(server.metric_sinks)):
        if stream is not None:
            with obs.maybe_stage("stream_wait"):
                stream.close()
        threads = []
        sink_elapsed: dict = {}

        def timed(fn, sink, arg):
            def run():
                ts = time.perf_counter()
                try:
                    with obs.activate(rec), \
                            obs.maybe_stage(f"post.{sink.name}"):
                        fn(sink, arg)
                finally:
                    sink_elapsed[sink.name] = time.perf_counter() - ts
            return run

        for sink in server.metric_sinks:
            # the interval's shared egress budget, read by each sink's retry
            # loop (set before the thread starts; sinks only read it)
            if hasattr(sink, "set_flush_deadline"):
                sink.set_flush_deadline(deadline)
            if sink in stream_sinks:
                # the emission blocks already streamed out chunk by chunk;
                # only the extras (status checks, routed rows, per-row
                # fallbacks) remain for this sink
                t = threading.Thread(
                    target=timed(_flush_sink, sink,
                                 list(final_metrics.extras)),
                    daemon=True)
            elif use_columnar and hasattr(sink, "flush_columnar"):
                t = threading.Thread(
                    target=timed(_flush_sink_columnar, sink, final_metrics),
                    daemon=True)
            else:
                metrics = final_metrics
                if use_columnar:
                    with obs.maybe_stage("materialize", scope=True):
                        metrics = final_metrics.to_intermetrics()
                t = threading.Thread(target=timed(_flush_sink, sink, metrics),
                                     daemon=True)
            # a thread's start waits for it to run, which under a busy GIL
            # (the merger, the import workers) is a wait of its own
            with obs.maybe_stage("sinks_wait"):
                t.start()
            threads.append(t)
        with obs.maybe_stage("sinks_wait"):
            for t in threads:
                t.join(timeout=30.0)
    with obs.maybe_stage("sink_metrics", scope=True):
        _check_flush_overrun(server, deadline, budget, sink_elapsed)
        # total time across the parallel sink POSTs (README.md:264),
        # plus the per-sink breakdown and each sink's
        # errors/marshal/post parts
        span.add(ssf_samples.timing("veneur.flush.total_duration_ns",
                                    time.perf_counter() - t0,
                                    {"part": "post"}))
        span.add(*_sink_samples(server, sink_elapsed))

    # plugins run after the sinks (flusher.go:95-109)
    with obs.maybe_stage("plugins"):
        for plugin in server.plugins:
            try:
                if use_columnar and hasattr(plugin, "flush_columnar"):
                    plugin.flush_columnar(final_metrics)
                else:
                    plugin.flush(final_metrics.to_intermetrics()
                                 if use_columnar else final_metrics)
            except Exception:
                log.exception("plugin %s flush failed", plugin.name)

    with obs.maybe_stage("span_join"):
        span_flusher.join(timeout=10.0)
    # what the interval emitted (the columnar blocks, the extras' rows
    # as Python objects), freed here and not unseen in the return
    with obs.maybe_stage("release", scope=True):
        del final_metrics


def _build_stream(server, now, deadline, rec, use_columnar, forwarding,
                  span):
    """The interval's :class:`veneur_tpu.core.pipeline.ChunkStream`
    when streaming egress is on (``flush_streaming`` +
    ``flush_pipeline_depth > 0``): every chunk-capable sink POSTs each
    completed group the moment it exists, and — when the forwarder
    takes parts — forwardable digest shards ship upstream the same
    way, with a terminally-failed part re-merged into the live store
    (late, never lost). Returns ``(stream-or-None, streaming sinks)``;
    the flusher later hands those sinks only the extras."""
    cfg = server.config
    if not use_columnar or not getattr(cfg, "flush_streaming", False) \
            or getattr(server.store, "flush_pipeline_depth", 0) <= 0:
        return None, []
    sinks = [s for s in server.metric_sinks if hasattr(s, "flush_chunk")]
    for sink in sinks:
        # the shared egress budget must be on the sink BEFORE its first
        # chunk arrives (the batch fan-out re-stamps it harmlessly)
        if hasattr(sink, "set_flush_deadline"):
            sink.set_flush_deadline(deadline)
    fwd_fn = fwd_requeue = None
    fwder = server._forwarder
    if forwarding and fwder is not None and \
            getattr(fwder, "supports_chunked_forward", False):
        from veneur_tpu.core.store import ForwardableState
        from veneur_tpu.obs import TraceContext

        def fwd_fn(attr, part):
            mini = ForwardableState()
            setattr(mini, attr, part)
            # the fleet trace plane's hop baggage rides every streamed
            # part exactly like the batch forward (the PR-13 contract):
            # this flush's span ids + the oldest ingest-era stamp,
            # stashed at the swap boundary before any chunk flows
            ingest_ns = (getattr(server, "_interval_oldest_ingest_ns",
                                 None) or int(now * 1e9))
            return fwder.forward(
                mini, parent_span=span, deadline=deadline,
                trace_ctx=TraceContext(span.trace_id, span.span_id,
                                       ingest_ns))

        def fwd_requeue(attr, part):
            _requeue_forward_part(server.store, attr, part)
    if not sinks and fwd_fn is None:
        return None, []
    from veneur_tpu.core.pipeline import ChunkStream

    return ChunkStream(sinks, now,
                       depth=getattr(server.store,
                                     "flush_pipeline_depth", 2),
                       rec=rec, forward_fn=fwd_fn,
                       forward_requeue=fwd_requeue), sinks


def _requeue_forward_part(store, attr, part):
    """Conservation for a terminally-failed streamed forward part:
    re-merge the digest shard into the LIVE store with import
    semantics — the compute ladder's rung-3 contract (late, never
    lost); it forwards again with the next interval."""
    from veneur_tpu.core.store import ForwardableState
    from veneur_tpu.samplers.parser import MetricKey

    mini = ForwardableState()
    setattr(mini, attr, part)
    mini.materialize_digests()
    mtype = "histogram" if attr.startswith("histogram") else "timer"
    rows = mini.histograms if mtype == "histogram" else mini.timers
    entries = [
        (MetricKey(name=name, type=mtype, joined_tags=",".join(tags)),
         tags, means, weights, dmin, dmax)
        for name, tags, means, weights, dmin, dmax in rows]
    if entries:
        store.import_digests_bulk(entries)
        log.warning("re-merged %d forwarded %s series into the live "
                    "store after a streamed-forward failure; they ship "
                    "with the next flush", len(entries), mtype)


def _check_flush_overrun(server, deadline, budget: float,
                         sink_elapsed: dict):
    """Flush watchdog: the egress deadline (resilience/deadline.py) is
    supposed to make an overrun impossible — retries clamp to it — so
    one actually expiring means a sink ignored its budget (wedged
    socket, un-clamped path). Count it (veneur.flush.overrun_total) and
    name the slowest sink, rate-limited to one warning per 30s so a
    persistently slow sink can't flood the log every interval."""
    if not deadline.expired():
        return
    server.flush_overruns = getattr(server, "flush_overruns", 0) + 1
    now = time.monotonic()
    if now - getattr(server, "_last_overrun_warn", 0.0) < 30.0:
        return
    server._last_overrun_warn = now
    # a sink whose thread outlived the join timeout never reported a
    # timing — IT is the culprit, not the slowest completed one
    wedged = [s.name for s in getattr(server, "metric_sinks", [])
              if s.name not in sink_elapsed]
    if wedged:
        slowest = f"sink(s) still running: {', '.join(wedged)}"
    elif sink_elapsed:
        name, took = max(sink_elapsed.items(), key=lambda kv: kv[1])
        slowest = f"slowest sink: {name} ({took:.2f}s)"
    else:
        slowest = "no sink timings recorded"
    log.warning("flush overran its %.1fs egress deadline; %s "
                "(%d overruns since start)", budget, slowest,
                server.flush_overruns)


def _checkpoint_samples(server):
    """veneur.checkpoint.* self-metrics (persist/checkpoint.py):
    last write's duration/bytes, current checkpoint age, and
    restore/discard counters as interval deltas."""
    from veneur_tpu.trace import samples as ssf_samples

    ckpt = getattr(server, "checkpointer", None)
    if ckpt is None:
        return []
    out = [
        ssf_samples.timing("veneur.checkpoint.write_duration_ns",
                           ckpt.last_write_duration_s, None),
        ssf_samples.gauge("veneur.checkpoint.bytes",
                          float(ckpt.last_write_bytes), None),
        ssf_samples.gauge("veneur.checkpoint.age_seconds",
                          ckpt.age_seconds(), None),
        ssf_samples.count(
            "veneur.checkpoint.restore_total",
            float(_delta_since(ckpt, "_last_reported_restores",
                               ckpt.restore_total)), None),
        ssf_samples.count(
            "veneur.checkpoint.discard_total",
            float(_delta_since(ckpt, "_last_reported_discards",
                               ckpt.discard_total)), None),
        # a checkpointer that can never write (bad path, full/read-only
        # disk) must be visible before the next crash proves it
        ssf_samples.count(
            "veneur.checkpoint.write_errors_total",
            float(_delta_since(ckpt, "_last_reported_write_errors",
                               ckpt.write_errors)), None),
    ]
    return out


def _trace_client_samples(server):
    """The trace client's own backpressure counters
    (``veneur.trace_client.*``): drained + reset once per interval via
    ``send_client_statistics`` (trace/client.py, the reference's
    client.go:446-452) so queue drops on the self-telemetry path are
    themselves visible as self-metrics."""
    from veneur_tpu.trace import samples as ssf_samples
    from veneur_tpu.trace.client import send_client_statistics

    cl = getattr(server, "trace_client", None)
    if cl is None:
        return []
    stats: dict = {}
    try:
        send_client_statistics(cl, lambda name, value:
                               stats.__setitem__(name, value))
    except Exception:  # pragma: no cover - telemetry must not abort
        log.exception("trace-client statistics drain failed")
        return []
    return [
        ssf_samples.count("veneur.trace_client.flushes_failed_total",
                          stats.get("trace_client.flushes_failed_total",
                                    0.0), None),
        ssf_samples.count("veneur.trace_client.flushes_succeeded_total",
                          stats.get("trace_client.flushes_succeeded_total",
                                    0.0), None),
        ssf_samples.count("veneur.trace_client.records_failed_total",
                          stats.get("trace_client.records_failed_total",
                                    0.0), None),
        ssf_samples.count("veneur.trace_client.records_succeeded_total",
                          stats.get("trace_client.records_succeeded_total",
                                    0.0), None),
    ]


def _fleet_samples(server):
    """Fleet-mode shard balance (veneur_tpu/fleet/): per-shard resident
    row occupancy summed over the mesh groups, tagged ``shard:<i>`` —
    the self-metric twin of the ``/debug/vars`` mesh section, so shard
    skew shows up in dashboards before it becomes one chip's OOM.
    Empty off the mesh (the common case costs one attribute read)."""
    store = getattr(server, "store", None)
    if store is None or getattr(store, "mesh", None) is None:
        return []
    from veneur_tpu.trace import samples as ssf_samples

    # stamped at the generation swap: the RETIRED interval's fills (the
    # live store is near-empty right after the swap)
    occ = getattr(store, "last_fleet_occupancy", None)
    if not occ:
        return []
    from veneur_tpu.fleet import balance_ratio

    out = []
    for i, rows in enumerate(occ):
        out.append(ssf_samples.gauge("veneur.fleet.shard_occupancy",
                                     float(rows), {"shard": str(i)}))
    out.append(ssf_samples.gauge("veneur.fleet.balance_ratio",
                                 balance_ratio(occ), None))
    return out


def _handoff_samples(server):
    """The veneur.handoff.* set (docs/resilience.md "Elastic
    resharding"): resize transitions, moved/requeued/received series,
    duplicate-and-stale guard hits, and the last transition's
    wall-clock — counters as interval deltas like every other set.
    Empty when elastic resharding is off (one attribute read)."""
    mgr = getattr(server, "handoff_manager", None)
    if mgr is None:
        return []
    from veneur_tpu.trace import samples as ssf_samples

    out = [
        ssf_samples.count(
            "veneur.handoff.resizes_total",
            float(_delta_since(mgr, "_last_resizes",
                               mgr.resizes_total)), None),
        ssf_samples.count(
            "veneur.handoff.moved_series_total",
            float(_delta_since(mgr, "_last_moved",
                               mgr.moved_series_total)), None),
        ssf_samples.count(
            "veneur.handoff.sent_total",
            float(_delta_since(mgr, "_last_sent", mgr.sent_total)),
            None),
        ssf_samples.count(
            "veneur.handoff.failed_total",
            float(_delta_since(mgr, "_last_failed",
                               mgr.send_failures_total)), None),
        ssf_samples.count(
            "veneur.handoff.requeued_series_total",
            float(_delta_since(mgr, "_last_requeued",
                               mgr.requeued_series_total)), None),
        ssf_samples.count(
            "veneur.handoff.received_series_total",
            float(_delta_since(mgr, "_last_received",
                               mgr.received_series_total)), None),
        ssf_samples.count(
            "veneur.handoff.duplicate_total",
            float(_delta_since(mgr, "_last_duplicates",
                               mgr.duplicates_total)), None),
        ssf_samples.count(
            "veneur.handoff.retries_total",
            float(_delta_since(mgr, "_last_retries",
                               mgr.retries_total)), None),
        # requeued ranges retried on the refresh cadence (no
        # membership change needed) — docs/resilience.md
        ssf_samples.count(
            "veneur.handoff.requeue_retries_total",
            float(_delta_since(mgr, "_last_requeue_retries",
                               mgr.requeue_retries_total)), None),
        # spool commits the disk refused (ENOSPC): the handoff went
        # out unspooled — crash protection degraded, counted
        ssf_samples.count(
            "veneur.handoff.spool_errors_total",
            float(_delta_since(mgr, "_last_spool_errors",
                               mgr.spool_errors_total)), None),
        ssf_samples.gauge("veneur.handoff.epoch", float(mgr.epoch),
                          None),
    ]
    if mgr.last_duration_ns:
        out.append(ssf_samples.timing(
            "veneur.handoff.duration_ns",
            mgr.last_duration_ns / 1e9, None))
    for dest, gauge in mgr.breakers.states():
        out.append(ssf_samples.gauge(
            "veneur.breaker.state", gauge, {"destination": dest}))
    return out


def _ha_samples(server):
    """The veneur.ha.* set (docs/resilience.md "Global HA"):
    replication stream tallies on the active, receive-side guard hits
    and replication age on the standby, and the lease's leadership
    gauges — counters as interval deltas like the handoff set. Empty
    when warm-standby HA is off (one attribute read)."""
    sby = getattr(server, "standby_manager", None)
    if sby is None:
        return []
    from veneur_tpu.trace import samples as ssf_samples

    out = [
        ssf_samples.count(
            "veneur.ha.replicated_total",
            float(_delta_since(sby, "_last_replicated",
                               sby.replicated_total)), None),
        ssf_samples.count(
            "veneur.ha.replicated_series_total",
            float(_delta_since(sby, "_last_replicated_series",
                               sby.replicated_series_total)), None),
        ssf_samples.count(
            "veneur.ha.replicate_failures_total",
            float(_delta_since(sby, "_last_replicate_failures",
                               sby.replicate_failures_total)), None),
        # the replicator fell a full flush behind and the older pending
        # epoch was superseded: widens the loss window past one interval
        ssf_samples.count(
            "veneur.ha.dropped_epochs_total",
            float(_delta_since(sby, "_last_dropped_epochs",
                               sby.dropped_epochs_total)), None),
        ssf_samples.count(
            "veneur.ha.received_series_total",
            float(_delta_since(sby, "_last_received_series",
                               sby.received_series_total)), None),
        ssf_samples.count(
            "veneur.ha.duplicate_total",
            float(_delta_since(sby, "_last_duplicates",
                               sby.duplicates_total)), None),
        ssf_samples.count(
            "veneur.ha.stale_total",
            float(_delta_since(sby, "_last_stale",
                               sby.stale_total)), None),
        ssf_samples.count(
            "veneur.ha.fenced_total",
            float(_delta_since(sby, "_last_fenced",
                               sby.fenced_total)), None),
        ssf_samples.count(
            "veneur.ha.promotions_total",
            float(_delta_since(sby, "_last_promotions",
                               sby.promotions_total)), None),
        ssf_samples.count(
            "veneur.ha.promoted_series_total",
            float(_delta_since(sby, "_last_promoted_series",
                               sby.promoted_series_total)), None),
        ssf_samples.count(
            "veneur.ha.retries_total",
            float(_delta_since(sby, "_last_retries",
                               sby.retries_total)), None),
        ssf_samples.gauge("veneur.ha.is_leader",
                          1.0 if sby.is_leader else 0.0, None),
        ssf_samples.gauge("veneur.ha.lease_epoch",
                          float(sby.lease_epoch), None),
    ]
    age = sby.replication_age_seconds()
    if age >= 0:
        out.append(ssf_samples.gauge(
            "veneur.ha.replication_age_seconds", float(age), None))
    elector = getattr(server, "lease_elector", None)
    if elector is not None:
        out.append(ssf_samples.count(
            "veneur.ha.lease_acquires_total",
            float(_delta_since(elector, "_last_acquires",
                               elector.acquires_total)), None))
        out.append(ssf_samples.count(
            "veneur.ha.lease_demotions_total",
            float(_delta_since(elector, "_last_demotions",
                               elector.demotions_total)), None))
        out.append(ssf_samples.count(
            "veneur.ha.lease_renew_failures_total",
            float(_delta_since(elector, "_last_renew_failures",
                               elector.renew_failures_total)), None))
    for dest, gauge in sby.breakers.states():
        out.append(ssf_samples.gauge(
            "veneur.breaker.state", gauge, {"destination": dest}))
    return out


def _worker_samples(server, ms):
    """Ingest/worker tallies (veneur.worker.* / veneur.packet.* from the
    canonical list, README.md:256-276). Counters are since-last-flush
    deltas, like the reference's per-interval worker counters."""
    from veneur_tpu.trace import samples as ssf_samples

    errs = _delta_since(server, "_last_packet_errors",
                        server.packet_errors)
    drops = _delta_since(server, "_last_packet_drops",
                         server.packet_drops)
    span_drops = _delta_since(server, "_last_spans_dropped",
                              server.spans_dropped)
    out = [
        ssf_samples.count("veneur.worker.spans_dropped_total",
                          float(span_drops), None),
        ssf_samples.count("veneur.worker.metrics_processed_total",
                          float(ms.processed), None),
        ssf_samples.count("veneur.worker.metrics_imported_total",
                          float(ms.imported), None),
        ssf_samples.count("veneur.packet.error_total", float(errs),
                          {"packet_type": "statsd"}),
        ssf_samples.count("veneur.packet.drop_total", float(drops),
                          {"packet_type": "statsd"}),
    ]
    for mtype in ("counters", "gauges", "histograms", "sets", "timers"):
        out.append(ssf_samples.count(
            "veneur.worker.metrics_flushed_total", float(getattr(ms, mtype)),
            {"metric_type": mtype.rstrip("s")}))
    # per-lane span-queue pressure: the current depth plus the
    # interval's high watermark (read-and-reset), tagged by sink, so an
    # operator sees a lane backing up BEFORE ingest_timeout_total drops
    # begin (each lane sheds only once its bounded queue fills)
    workers = getattr(server, "_span_workers", None) or ()
    for w in workers[:1]:  # lanes are shared across workers
        for lane in getattr(w, "_lanes", ()):
            hwm, lane.depth_hwm = lane.depth_hwm, 0
            out.append(ssf_samples.gauge(
                "veneur.server.span_lane.depth",
                float(lane.queue.qsize()), {"sink": lane.sink.name}))
            out.append(ssf_samples.gauge(
                "veneur.server.span_lane.depth_hwm", float(hwm),
                {"sink": lane.sink.name}))
    return out


def _overload_samples(server, ms):
    """The veneur.overload.* set (docs/resilience.md "Degradation
    ladder"): admission level + per-lane sheds, per-reason quarantine,
    per-group overflow spills, and the flush-kernel breaker's
    fallback/requeue tallies. Counters are interval deltas like the
    worker set; spills/scrubs ride the generation summary (exact for
    the flushed interval)."""
    from veneur_tpu.trace import samples as ssf_samples

    out = []
    ov = getattr(server, "overload", None)
    if ov is not None:
        out.append(ssf_samples.gauge("veneur.overload.level",
                                     float(ov.level()), None))
        for lane, shed in sorted(ov.shed.items()):
            out.append(ssf_samples.count(
                "veneur.overload.shed_total",
                float(_delta_since(ov, f"_last_shed_{lane}", shed)),
                {"lane": lane}))
    quarantine = getattr(getattr(server, "store", None), "quarantine",
                         None)
    if quarantine is not None:
        for reason, total in sorted(quarantine.snapshot().items()):
            out.append(ssf_samples.count(
                "veneur.overload.quarantined_total",
                float(_delta_since(quarantine, f"_last_{reason}", total)),
                {"reason": reason}))
    for group, spilled in sorted(getattr(ms, "spilled", {}).items()):
        out.append(ssf_samples.count(
            "veneur.overload.samples_spilled_total", float(spilled),
            {"group": group}))
    compute = getattr(getattr(server, "store", None), "compute", None)
    if compute is not None:
        out.append(ssf_samples.count(
            "veneur.overload.compute_fallback_total",
            float(_delta_since(compute, "_last_reported_fallbacks",
                               compute.fallback_total)), None))
        out.append(ssf_samples.count(
            "veneur.overload.compute_requeued_total",
            float(_delta_since(compute, "_last_reported_requeues",
                               compute.requeued_total)), None))
        for kernel, gauge in compute.states():
            out.append(ssf_samples.gauge(
                "veneur.breaker.state", gauge, {"destination": kernel}))
    return out


def _delta_since(obj, last_attr: str, cur):
    """Snapshot-once interval delta: ``cur`` must be read EXACTLY once by
    the caller (re-reading the live counter for the reset would lose
    anything counted between the reads)."""
    delta = cur - getattr(obj, last_attr, 0)
    setattr(obj, last_attr, cur)
    return delta


def _forward_samples(server):
    """The documented veneur.forward.* set (README.md:260-266):
    post_metrics_total, error_total, per-POST duration_ns, and
    content_length_bytes — drained from whichever forwarder flavor
    (HTTP / gRPC / native) is configured. Deltas cover the PREVIOUS
    interval's forward, which runs off the flush path."""
    from veneur_tpu.trace import samples as ssf_samples

    f = server._forwarder
    if f is None or not hasattr(f, "forwarded"):
        return []
    with f._lock:
        fwd, errs = f.forwarded, f.errors
        retries = getattr(f, "retries", 0)
        durs = list(f.post_durations)
        lens = list(f.post_content_lengths)
        f.post_durations.clear()
        f.post_content_lengths.clear()
    d_fwd = _delta_since(f, "_last_reported_forwarded", fwd)
    d_err = _delta_since(f, "_last_reported_errors", errs)
    d_retries = _delta_since(f, "_last_reported_retries", retries)
    out = [
        ssf_samples.count("veneur.forward.post_metrics_total",
                          float(d_fwd), None),
        ssf_samples.count("veneur.forward.error_total", float(d_err),
                          None),
        ssf_samples.count("veneur.forward.retries_total",
                          float(d_retries), None),
    ]
    breaker = getattr(f, "breaker", None)
    if breaker is not None:
        out.append(ssf_samples.gauge(
            "veneur.breaker.state", breaker.state_gauge(),
            {"destination": breaker.name or "forward"}))
    out.extend(ssf_samples.timing("veneur.forward.duration_ns", s,
                                  {"part": "post"}) for s in durs)
    out.extend(ssf_samples.histogram(
        "veneur.forward.content_length_bytes", float(n), None)
        for n in lens)
    return out


def _import_samples(server):
    """veneur.import.request_error_total (README.md:275), summed per
    protocol over whichever import servers this (global) instance runs."""
    from veneur_tpu.trace import samples as ssf_samples

    out = []
    for attr, proto in (("import_server", "grpc"),
                        ("native_import_server", "native")):
        srv = getattr(server, attr, None)
        if srv is None or not hasattr(srv, "import_errors"):
            continue
        delta = _delta_since(srv, "_last_reported_import_errors",
                             srv.import_errors)
        out.append(ssf_samples.count("veneur.import.request_error_total",
                                     float(delta), {"protocol": proto}))
    return out


def _sink_samples(server, sink_elapsed: dict):
    """Per-sink flush telemetry (README.md:260-264): duration_ns tagged
    by sink (with marshal/post part tags where the sink records them),
    error_total deltas, and POST content_length_bytes."""
    from veneur_tpu.trace import samples as ssf_samples

    out = []
    for sink in server.metric_sinks:
        name = sink.name
        if name in sink_elapsed:
            out.append(ssf_samples.timing(
                "veneur.flush.duration_ns", sink_elapsed[name],
                {"sink": name}))
        if hasattr(sink, "flush_errors"):
            delta = _delta_since(sink, "_last_reported_flush_errors",
                                 sink.flush_errors)
            out.append(ssf_samples.count("veneur.flush.error_total",
                                         float(delta), {"sink": name}))
        if hasattr(sink, "retries"):
            delta = _delta_since(sink, "_last_reported_retries",
                                 sink.retries)
            out.append(ssf_samples.count(
                f"veneur.sink.{name}.retries_total", float(delta), None))
        if hasattr(sink, "chunks_requeued_total"):
            # streamed-chunk bodies that got their one next-interval
            # retry (docs/internals.md "Life of a flush")
            delta = _delta_since(sink, "_last_reported_chunk_requeues",
                                 sink.chunks_requeued_total)
            out.append(ssf_samples.count(
                f"veneur.sink.{name}.chunks_requeued_total",
                float(delta), None))
        if hasattr(sink, "chunk_rows_dropped"):
            # rows the bounded requeue budget gave up on (counted
            # loss under a long sink outage — docs/resilience.md)
            delta = _delta_since(sink, "_last_reported_chunk_drops",
                                 sink.chunk_rows_dropped)
            out.append(ssf_samples.count(
                f"veneur.sink.{name}.chunk_rows_dropped_total",
                float(delta), None))
        if hasattr(sink, "chunk_requeue_bytes"):
            # host memory parked for retry, bounded by
            # sink_requeue_max_bytes — the soak's no-pileup gate
            out.append(ssf_samples.gauge(
                f"veneur.sink.{name}.chunk_requeue_bytes",
                float(sink.chunk_requeue_bytes()), None))
        breaker = getattr(sink, "breaker", None)
        if breaker is not None:
            out.append(ssf_samples.gauge(
                "veneur.breaker.state", breaker.state_gauge(),
                {"destination": breaker.name or name, "sink": name}))
        if hasattr(sink, "drain_flush_telemetry"):
            # the batch fan-out's and the streamed chunks' parts alike
            # (their stages are post.<sink>.marshal / .send and
            # post.<sink>.serialize / .post)
            for kind, value in sink.drain_flush_telemetry():
                if kind in ("marshal_s", "chunk_marshal_s"):
                    out.append(ssf_samples.timing(
                        "veneur.flush.duration_ns", value,
                        {"sink": name, "part": "marshal"}))
                elif kind in ("post_s", "chunk_post_s"):
                    out.append(ssf_samples.timing(
                        "veneur.flush.duration_ns", value,
                        {"sink": name, "part": "post"}))
                elif kind == "content_length_bytes":
                    out.append(ssf_samples.histogram(
                        "veneur.flush.content_length_bytes", float(value),
                        {"sink": name}))
    return out


def _runtime_samples():
    """The Go-runtime gauges' Python analogues (veneur.gc.*,
    veneur.mem.*, README.md:267-269). Telemetry must never abort a
    flush, so everything here is best-effort."""
    import gc
    import sys

    from veneur_tpu.trace import samples as ssf_samples

    out = [ssf_samples.gauge(
        "veneur.gc.number",
        float(sum(s["collections"] for s in gc.get_stats())), None)]
    try:
        import resource

        maxrss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        # Linux reports KB, macOS bytes; Windows has no resource module
        rss_bytes = maxrss if sys.platform == "darwin" else maxrss * 1024
        out.append(ssf_samples.gauge("veneur.mem.heap_alloc_bytes",
                                     float(rss_bytes), None))
    except ImportError:  # pragma: no cover - non-POSIX
        pass
    return out


def _flush_sink(sink, metrics):
    try:
        sink.flush(filter_acceptable(metrics, sink.name))
    except Exception:
        log.exception("sink %s flush failed", sink.name)


def _flush_sink_columnar(sink, batch):
    # columnar blocks are guaranteed routing-free (the store falls back
    # to per-row emission for any veneursinkonly: group); extras carry
    # routing and each columnar sink filters them itself
    try:
        sink.flush_columnar(batch)
    except Exception:
        log.exception("sink %s columnar flush failed", sink.name)


def _flush_spans(server: "Server"):
    for w in server._span_workers:
        w.flush()
        break  # sinks are shared between workers; flush each sink once
