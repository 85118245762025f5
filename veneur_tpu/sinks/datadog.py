"""Datadog sinks: series/check/event metric sink + trace-agent span sink.

Behavioral port of ``/root/reference/sinks/datadog/datadog.go``:

- ``DatadogMetricSink.flush`` finalizes InterMetrics (magic ``host:`` /
  ``device:`` tags, counters→rates, status→service check;
  datadog.go:245-322) and POSTs them to ``/api/v1/series`` in
  approximately equal chunks of ≤ ``flush_max_per_body``, in parallel
  (datadog.go:324-330). Service checks go to ``/api/v1/check_run``
  uncompressed; DogStatsD events arrive via ``flush_other_samples`` and
  go to ``/intake`` (datadog.go:155-243).
- ``DatadogSpanSink`` keeps the newest ``buffer_size`` spans in a ring
  (datadog.go:387-397), and each flush groups them by trace id and PUTs
  ``[[span…]…]`` to the trace agent's ``/v0.3/traces`` (datadog.go:460-530).

Transport is injectable (``post``) so tests run against a local fixture,
the role ``httptest.Server`` plays in the reference's tests
(datadog_test.go).
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from veneur_tpu.forward.http_forward import post_helper
from veneur_tpu.obs import recorder as obs_rec
from veneur_tpu.protocol import constants as dogstatsd
from veneur_tpu.protocol import wire
from veneur_tpu.resilience import RetryPolicy, post_with_retry
from veneur_tpu.samplers.intermetric import InterMetric, MetricType
from veneur_tpu.sinks.base import MetricSink, SpanSink

log = logging.getLogger("veneur.sinks.datadog")

DATADOG_NAME_KEY = "name"
DATADOG_RESOURCE_KEY = "resource"
DATADOG_SPAN_TYPE = "web"

# post(url, payload, compress, method) -> status
PostFn = Callable[..., int]


def _default_post(url: str, payload, compress: bool = True,
                  method: str = "POST", precompressed: bool = False,
                  out_info: dict = None) -> int:
    return post_helper(url, payload, compress=compress, method=method,
                       precompressed=precompressed, out_info=out_info)


def _ok(status: int) -> bool:
    """Success statuses per the reference's PostHelper
    (http/http.go:230-236): 200 or 202."""
    return status in (200, 202)


def _body_rows(n: int, max_per_body: int) -> list:
    """Per-body emission counts for one block's serialized bodies: the
    native serializer (veneur_egress.cpp vt_dd_stream_begin) closes a
    body at exactly ``max_per_body`` emissions, so every body holds
    max_per_body rows except the last — the split the per-chunk
    conservation accounting relies on."""
    return [min(max_per_body, n - i) for i in range(0, n, max_per_body)]


class DatadogMetricSink(MetricSink):
    """Flushes InterMetrics to the Datadog v1 series API
    (datadog.go:34-357)."""

    def __init__(self, interval: float, flush_max_per_body: int,
                 hostname: str, tags: Sequence[str], dd_hostname: str,
                 api_key: str, post: Optional[PostFn] = None,
                 compress_level: int = 1,
                 retry_policy: Optional[RetryPolicy] = None,
                 breaker=None, fault_injector=None,
                 requeue_max_bytes: int = 32 * 1048576):
        self.interval = interval
        self.flush_max_per_body = max(1, flush_max_per_body)
        self.hostname = hostname
        self.tags = list(tags)
        self.dd_hostname = dd_hostname.rstrip("/")
        self.api_key = api_key
        self.post = post or _default_post
        if fault_injector is not None:
            self.post = fault_injector.wrap_post(self.post, "sink.datadog")
        # resilience: transport errors and 5xx retry with backoff inside
        # the flush deadline the flusher sets each interval; a
        # black-holed API endpoint trips the breaker and is rejected
        # instantly until its half-open probe succeeds
        self.retry_policy = retry_policy or RetryPolicy()
        self.breaker = breaker
        self.retries = 0
        # deflate level for the native columnar serializer (level 1 runs
        # ~2x the throughput of zlib's default 6 at a ~12% ratio cost —
        # deflate is most of what a large flush's serializer costs, a
        # body a worker at a time: native/egress.py dd_workers)
        self.compress_level = compress_level
        self.metrics_flushed = 0
        self.flush_errors = 0
        self._common_json: Optional[bytes] = None
        # _flush_part runs on one thread per chunk; guard the counter
        self._err_lock = threading.Lock()
        # streaming egress (core/pipeline.py ChunkStream): serialized-
        # but-unacked chunk bodies park here and retry once per
        # interval until acked, bounded by a BYTES budget
        # (config sink_requeue_max_bytes) — per-chunk conservation:
        # every emission row is acked, pending requeue, or (evicted
        # past the budget) counted dropped. The budget evicts OLDEST
        # first: under a long outage the buffer stays fresh and the
        # loss is the counted old tail, never unbounded host growth.
        self._requeued: deque = deque()
        self.requeue_max_bytes = max(0, requeue_max_bytes)
        self.requeue_max_bodies = 256  # belt-and-braces count bound
        self._requeued_bytes = 0
        self._last_repost_ts = None
        self.chunks_flushed = 0
        self.chunks_requeued_total = 0
        self.chunk_rows_acked = 0
        self.chunk_rows_requeued = 0
        self.chunk_rows_dropped = 0
        # ("marshal_s"|"post_s"|"content_length_bytes", value) pairs the
        # flusher drains into the canonical veneur.flush.* self-metrics
        # (duration_ns part tags + content_length_bytes, README.md:260-264)
        self._telemetry: List = []

    def _count_error(self) -> None:
        with self._err_lock:
            self.flush_errors += 1

    def _count_retry(self, retry_index, exc, pause) -> None:
        with self._err_lock:
            self.retries += 1

    def _resilient_post(self, call) -> int:
        """Run a POST closure under the shared retry loop (transport
        errors and 5xx/429, backoff clamped to the flush deadline) and
        the destination breaker. An open breaker raises OSError so call
        sites count it through their existing error path."""
        from veneur_tpu.resilience import is_transient_status

        if self.breaker is not None and not self.breaker.allow():
            raise OSError("datadog circuit breaker open")
        try:
            status = post_with_retry(call, self.retry_policy,
                                     deadline=self.flush_deadline,
                                     on_retry=self._count_retry)
        except OSError:
            if self.breaker is not None:
                self.breaker.record_failure()
            raise
        if self.breaker is not None:
            # a 4xx still proves the destination is alive; only
            # transient statuses count toward tripping the breaker
            if is_transient_status(status):
                self.breaker.record_failure()
            else:
                self.breaker.record_success()
        return status

    def drain_flush_telemetry(self) -> List:
        with self._err_lock:
            out, self._telemetry = self._telemetry, []
        return out

    @property
    def name(self) -> str:
        return "datadog"

    def flush_columnar(self, batch) -> None:
        """Columnar flush: serialize emission blocks to deflated series
        bodies in C++ (native/veneur_egress.cpp — the vectorized twin of
        finalize_metrics + chunked POST, datadog.go:245-330) and POST
        them in parallel. Extras (status checks, routed metrics) take
        the per-row path."""
        bodies: List[bytes] = []
        n_metrics = 0
        t_marshal = time.perf_counter()
        with obs_rec.maybe_stage("marshal", scope=True):
            for blk in batch.blocks:
                with self._serialize_block(blk, batch.timestamp) as stream:
                    bodies.extend(body for body, _ready_ns in stream)
                n_metrics += len(blk)
        t_marshal = time.perf_counter() - t_marshal
        threads = []
        t_post = time.perf_counter()
        with obs_rec.maybe_stage("send", scope=True,
                                 bytes=sum(len(b) for b in bodies)):
            for body in bodies:
                t = threading.Thread(target=self._flush_body, args=(body,),
                                     daemon=True)
                t.start()
                threads.append(t)
            for t in threads:
                t.join()
        t_post = time.perf_counter() - t_post
        with self._err_lock:
            self._telemetry.append(("marshal_s", t_marshal))
            self._telemetry.append(("post_s", t_post))
            self._telemetry.extend(
                ("content_length_bytes", len(b)) for b in bodies)
        self.metrics_flushed += n_metrics
        if batch.extras:
            self.flush(batch.extras)

    def flush_chunk(self, chunk) -> None:
        """Streaming egress (docs/internals.md "Life of a flush"):
        serialize + deflate + POST ONE pipeline chunk the moment the
        store completes it, while later groups still compute/fetch.
        Runs on the interval's stream worker behind the same retry/
        breaker/deadline ladder as the batch path. Body ``k`` of a
        block is POSTed as soon as the native serializer has made it,
        while its workers make ``k+1..`` (``egress.dd_series_stream``,
        as upstream POSTs a chunk the moment its goroutine has
        marshalled it, datadog.go:324-330); the POSTs stay one at a
        time, in body order, and the chunk returns after the last.

        Per-chunk conservation: every emission row either reaches a
        2xx body (``chunk_rows_acked``) or its serialized body parks
        for retry on later intervals (``chunk_rows_requeued``, late
        never lost) inside the ``requeue_max_bytes`` budget; past the
        budget the OLDEST parked bodies drop counted
        (``chunk_rows_dropped``), so memory stays bounded and a long
        outage degrades by counted drop."""
        from veneur_tpu.obs.kernels import host_scope

        # normally a no-op: the stream worker already reposted for this
        # interval before any chunk flowed (core/pipeline.py); kept for
        # direct flush_chunk callers. The flush-cycle id is the dedup
        # key — the integer-second timestamp collides across sub-second
        # driven intervals (hand-built test chunks carry cycle 0 and
        # fall back to it)
        self.repost_requeued(getattr(chunk, "cycle", 0) or chunk.timestamp)
        rec = obs_rec.current()
        serialize = f"post.{self.name}.serialize"
        wire = f"post.{self.name}.post.wire"
        t0_ns = time.monotonic_ns()
        # where the native serializer's wall went (encoding JSON, in
        # deflate), the same summed over its workers, the bodies it made:
        # added up over the chunk's blocks; the most workers a block's
        # call ran (native/egress.py)
        native_ns = dict.fromkeys(
            ("encode_ns", "deflate_ns", "encode_cpu_ns", "deflate_cpu_ns",
             "bodies", "workers"), 0)
        made_ns = t0_ns     # when the chunk's last body was made
        first_ns = 0        # the blocks' native calls to their first body
        posts = []          # each body's POST: (start ns, return ns)
        sizes = []
        for blk in chunk.blocks:
            b0 = time.monotonic_ns()
            with host_scope(serialize):
                stream = self._serialize_block(blk, chunk.timestamp,
                                               native_ns)
            with stream:
                for i, nrows in enumerate(
                        _body_rows(len(blk), self.flush_max_per_body)):
                    with host_scope(serialize):
                        body, ready_ns = next(stream)
                    if i == 0:
                        first_ns += ready_ns - b0
                    made_ns = max(made_ns, ready_ns)
                    p0 = time.monotonic_ns()
                    with host_scope(wire):
                        self._post_chunk_body(body, nrows)
                    posts.append((p0, time.monotonic_ns()))
                    sizes.append(len(body))
        post_t0, post_t1 = (posts[0][0], posts[-1][1]) if posts \
            else (made_ns, made_ns)
        if rec is not None:
            rec.record_abs(f"post.{self.name}.serialize", t0_ns, made_ns,
                           chunk=chunk.seq, bodies=native_ns["bodies"],
                           workers=native_ns["workers"],
                           encode_cpu_ns=native_ns["encode_cpu_ns"],
                           deflate_cpu_ns=native_ns["deflate_cpu_ns"])
            for part in ("encode", "deflate"):
                rec.record_abs(f"post.{self.name}.serialize.{part}", t0_ns,
                               t0_ns + native_ns[part + "_ns"],
                               chunk=chunk.seq)
            # the stream worker's waits for the workers' first wave,
            # summed over the blocks like the two parts above
            rec.record_abs(f"post.{self.name}.serialize.first_body", t0_ns,
                           t0_ns + first_ns, chunk=chunk.seq)
            # the POSTs overlap the serializer: `post` spans the first
            # POST's start to the last one's return, its `tail` what
            # the serializer did not hide (from the last body made),
            # its `wire` the POSTs' own time, summed
            rec.record_abs(f"post.{self.name}.post", post_t0, post_t1,
                           chunk=chunk.seq, rows=chunk.rows,
                           bytes=sum(sizes), bodies_posted_early=sum(
                               1 for _p0, p1 in posts if p1 < made_ns))
            rec.record_abs(f"post.{self.name}.post.tail",
                           max(made_ns, post_t0), post_t1, chunk=chunk.seq)
            rec.record_abs(wire, post_t0, post_t0 + sum(
                p1 - p0 for p0, p1 in posts), chunk=chunk.seq,
                posts=len(posts))
        with self._err_lock:
            # chunk_* kinds: the same part-tagged duration self-metrics
            # as the batch path (veneur.flush.duration_ns)
            self._telemetry.append(("chunk_marshal_s",
                                    (made_ns - t0_ns) / 1e9))
            self._telemetry.append(("chunk_post_s",
                                    (post_t1 - post_t0) / 1e9))
            self._telemetry.extend(("content_length_bytes", n)
                                   for n in sizes)
            self.chunks_flushed += 1
        self.metrics_flushed += chunk.rows

    def _serialize_block(self, blk, timestamp: int,
                         timing: Optional[dict] = None):
        """One emission block → its deflated series bodies, as the
        native serializer makes them (``egress.DDSeriesStream``, closed
        by the caller): the counter-to-rate finalization
        (datadog.go:295-297) + the native call, shared by the batch and
        streamed paths so the wire format can never diverge between
        them. ``timing`` is ``dd_series_stream``'s: where the native
        serializer's time went."""
        from veneur_tpu.core.columnar import TYPE_COUNTER
        from veneur_tpu.native import egress

        values = blk.values
        if (blk.type_codes == TYPE_COUNTER).any():
            values = np.where(blk.type_codes == TYPE_COUNTER,
                              values / self.interval, values)
        return egress.dd_series_stream(
            blk.names, blk.tags, blk.suffixes, blk.rows,
            blk.suffix_idx, values, blk.type_codes,
            timestamp=timestamp, interval=int(self.interval),
            default_host=self.hostname,
            common_tags_json=self._common_tags_json(),
            max_per_body=self.flush_max_per_body,
            compress_level=self.compress_level, timing=timing)

    def _post_chunk_body(self, body: bytes, nrows: int,
                         requeued: bool = False) -> bool:
        """POST one serialized chunk body; terminal failure parks it
        for retry on later intervals inside the requeue budget. The
        catch is deliberately broad — transport OSErrors AND
        protocol-level HTTPExceptions (BadStatusLine from a garbage
        proxy is not an OSError) — because ANY escape here would leave
        the body's rows neither acked, requeued, nor dropped, silently
        breaking the conservation invariant."""
        import http.client

        try:
            status = self._resilient_post(lambda: self.post(
                f"{self.dd_hostname}/api/v1/series"
                f"?api_key={self.api_key}", body, precompressed=True))
            if _ok(status):
                with self._err_lock:
                    self.chunk_rows_acked += nrows
                return True
            log.warning("Datadog chunk POST returned HTTP %d", status)
            self._count_error()
        except (OSError, http.client.HTTPException):
            log.warning("error POSTing chunk body to Datadog",
                        exc_info=True)
            self._count_error()
        with self._err_lock:
            self._park_locked(body, nrows)
        return False

    def _park_locked(self, body: bytes, nrows: int) -> None:
        """Park one unacked body for the next interval's repost,
        evicting OLDEST parked bodies (counted ``chunk_rows_dropped``)
        until the bytes budget and the body-count bound admit it; a
        body alone past the whole budget drops outright. Caller holds
        ``_err_lock``."""
        if len(body) > self.requeue_max_bytes:
            self.chunk_rows_dropped += nrows
            return
        while self._requeued and (
                self._requeued_bytes + len(body) > self.requeue_max_bytes
                or len(self._requeued) >= self.requeue_max_bodies):
            old_body, old_rows = self._requeued.popleft()
            # caller holds _err_lock (see docstring)
            self._requeued_bytes -= len(old_body)  # lint: ok(inconsistent-lockset) caller holds _err_lock (docstring contract) — the pass cannot see through the call boundary
            self.chunk_rows_dropped += old_rows
        self._requeued.append((body, nrows))
        self._requeued_bytes += len(body)  # lint: ok(inconsistent-lockset) caller holds _err_lock (docstring contract) — the pass cannot see through the call boundary
        self.chunk_rows_requeued += nrows

    def repost_requeued(self, timestamp: int) -> None:
        """Unacked bodies from previous intervals get one more POST
        per interval (``timestamp`` is the interval's dedup key — the
        stream's flush-cycle id, or the chunk timestamp for hand-built
        chunks); a body that
        fails again re-parks through the same bytes-budgeted path, so
        a multi-interval outage holds the freshest budget's worth and
        drops (counted) only past it. The stream worker fires this at
        interval start — even when the interval produces no chunks for
        this sink — so parked bodies can never strand un-retried."""
        with self._err_lock:
            if timestamp == self._last_repost_ts:
                return
            self._last_repost_ts = timestamp
            if not self._requeued:
                return
            pending, self._requeued = list(self._requeued), deque()
            self._requeued_bytes = 0
            self.chunks_requeued_total += len(pending)
        for body, nrows in pending:
            self._post_chunk_body(body, nrows, requeued=True)

    def chunk_rows_pending(self) -> int:
        """Rows currently parked for the next-interval retry (the
        conservation tests' requeued term)."""
        with self._err_lock:
            return sum(n for _b, n in self._requeued)

    def chunk_requeue_bytes(self) -> int:
        """Serialized bytes currently parked — the host-memory cost of
        the requeue buffer, bounded by ``requeue_max_bytes``."""
        with self._err_lock:
            return self._requeued_bytes

    def _common_tags_json(self) -> bytes:
        """The sink's fixed tags as a pre-escaped JSON fragment
        (``"a:1","b:2"``) the native serializer prepends per metric."""
        import json as _json

        if self._common_json is None:
            self._common_json = ",".join(
                _json.dumps(t) for t in self.tags).encode("utf-8")
        return self._common_json

    def _flush_body(self, body: bytes) -> None:
        try:
            status = self._resilient_post(lambda: self.post(
                f"{self.dd_hostname}/api/v1/series"
                f"?api_key={self.api_key}", body, precompressed=True))
            if not _ok(status):
                log.warning("Datadog series flush returned HTTP %d", status)
                self._count_error()
        except OSError:
            log.warning("error flushing metrics to Datadog", exc_info=True)
            self._count_error()

    def flush(self, metrics: List[InterMetric]) -> None:
        t_marshal = time.perf_counter()
        with obs_rec.maybe_stage("marshal", scope=True):
            dd_metrics, checks = self.finalize_metrics(metrics)
        t_marshal = time.perf_counter() - t_marshal
        with obs_rec.maybe_stage("send", scope=True):
            if checks:
                # check_run takes an array but not deflate
                # (datadog.go:113-116)
                try:
                    status = self._resilient_post(lambda: self.post(
                        f"{self.dd_hostname}/api/v1/check_run"
                        f"?api_key={self.api_key}", checks,
                        compress=False))
                    if not _ok(status):
                        log.warning("Datadog check_run returned HTTP %d",
                                    status)
                        self._count_error()
                except OSError:
                    log.warning("error flushing checks to Datadog",
                                exc_info=True)
                    self._count_error()
            if not dd_metrics:
                return
            # equal-size chunks under flush_max_per_body, rounding-up
            # division (datadog.go:127-146)
            workers = ((len(dd_metrics) - 1) // self.flush_max_per_body) + 1
            chunk_size = ((len(dd_metrics) - 1) // workers) + 1
            threads = []
            t_post = time.perf_counter()
            for i in range(workers):
                chunk = dd_metrics[i * chunk_size:(i + 1) * chunk_size]
                t = threading.Thread(target=self._flush_part,
                                     args=(chunk,), daemon=True)
                t.start()
                threads.append(t)
            for t in threads:
                t.join()
            t_post = time.perf_counter() - t_post
            # same part-tagged telemetry the columnar path records, so
            # the documented veneur.flush.* set does not depend on which
            # flush path a deployment runs
            with self._err_lock:
                self._telemetry.append(("marshal_s", t_marshal))
                self._telemetry.append(("post_s", t_post))
            self.metrics_flushed += len(dd_metrics)

    def _flush_part(self, chunk: List[dict]) -> None:
        info = {}
        try:
            status = self._resilient_post(
                lambda: self.post(f"{self.dd_hostname}/api/v1/series"
                                  f"?api_key={self.api_key}",
                                  {"series": chunk}, out_info=info))
            if not _ok(status):
                log.warning("Datadog series flush returned HTTP %d", status)
                self._count_error()
        except OSError:
            log.warning("error flushing metrics to Datadog", exc_info=True)
            self._count_error()
        finally:
            if "content_length" in info:
                with self._err_lock:
                    self._telemetry.append(
                        ("content_length_bytes", info["content_length"]))

    def finalize_metrics(self, metrics: List[InterMetric]):
        """InterMetric → DDMetric/DDServiceCheck dicts (datadog.go:245-322)."""
        dd_metrics: List[dict] = []
        checks: List[dict] = []
        for m in metrics:
            if not m.is_acceptable_to(self.name):
                continue
            tags = list(self.tags)
            hostname = ""
            devicename = ""
            for tag in m.tags:
                if tag.startswith("host:"):
                    hostname = tag[5:]
                elif tag.startswith("device:"):
                    devicename = tag[7:]
                else:
                    tags.append(tag)
            if not hostname:
                hostname = m.hostname or self.hostname

            if m.type == MetricType.STATUS:
                checks.append({
                    "check": m.name,
                    "status": int(m.value),
                    "timestamp": m.timestamp,
                    "message": m.message,
                    "host_name": hostname,
                    "tags": tags,
                })
                continue

            if m.type == MetricType.COUNTER:
                # counters become rates for Datadog (datadog.go:295-297)
                metric_type = "rate"
                value = m.value / self.interval
            elif m.type == MetricType.GAUGE:
                metric_type = "gauge"
                value = m.value
            else:
                log.warning("unknown metric type %s", m.type)
                continue

            dd_metrics.append({
                "metric": m.name,
                "points": [[float(m.timestamp), value]],
                "tags": tags,
                "type": metric_type,
                "interval": int(self.interval),
                "host": hostname,
                "device_name": devicename,
            })
        return dd_metrics, checks

    def flush_other_samples(self, samples) -> None:
        """DogStatsD events → ``/intake`` (datadog.go:155-243)."""
        events = []
        for sample in samples:
            tags = dict(sample.tags)
            if dogstatsd.EVENT_IDENTIFIER_KEY not in tags:
                log.warning("received a non-event SSF sample in "
                            "flush_other_samples")
                continue
            del tags[dogstatsd.EVENT_IDENTIFIER_KEY]
            event = {
                "msg_title": sample.name,
                "msg_text": sample.message,
                "timestamp": sample.timestamp,
                "priority": "normal",
                "alert_type": "info",
            }
            if dogstatsd.EVENT_AGGREGATION_KEY_TAG in tags:
                event["aggregation_key"] = tags.pop(
                    dogstatsd.EVENT_AGGREGATION_KEY_TAG)
            if dogstatsd.EVENT_PRIORITY_TAG in tags:
                event["priority"] = tags.pop(dogstatsd.EVENT_PRIORITY_TAG)
            if dogstatsd.EVENT_SOURCE_TYPE_TAG in tags:
                event["source_type_name"] = tags.pop(
                    dogstatsd.EVENT_SOURCE_TYPE_TAG)
            if dogstatsd.EVENT_ALERT_TYPE_TAG in tags:
                event["alert_type"] = tags.pop(dogstatsd.EVENT_ALERT_TYPE_TAG)
            if dogstatsd.EVENT_HOSTNAME_TAG in tags:
                event["host"] = tags.pop(dogstatsd.EVENT_HOSTNAME_TAG)
            else:
                event["host"] = self.hostname
            event["tags"] = [f"{k}:{v}" for k, v in tags.items()] + self.tags
            events.append(event)
        if not events:
            return
        try:
            status = self._resilient_post(lambda: self.post(
                f"{self.dd_hostname}/intake?api_key={self.api_key}",
                {"events": {"api": events}}))
            if not _ok(status):
                log.warning("Datadog event intake returned HTTP %d", status)
                self._count_error()
        except OSError:
            log.warning("error flushing events to Datadog", exc_info=True)
            self._count_error()


class DatadogSpanSink(SpanSink):
    """Ring-buffered span sink for the Datadog trace agent
    (datadog.go:359-530)."""

    def __init__(self, trace_address: str, buffer_size: int = 16384,
                 post: Optional[PostFn] = None,
                 retry_policy: Optional[RetryPolicy] = None):
        self.trace_address = trace_address.rstrip("/")
        self.buffer_size = buffer_size
        # deque(maxlen) == the reference's container/ring: newest
        # buffer_size spans win (datadog.go:395-397)
        self._buffer: deque = deque(maxlen=buffer_size)
        self._lock = threading.Lock()
        self.post = post or _default_post
        self.retry_policy = retry_policy or RetryPolicy()
        self.retries = 0
        self.spans_flushed = 0

    def _count_retry(self, retry_index, exc, pause) -> None:
        with self._lock:
            self.retries += 1

    @property
    def name(self) -> str:
        return "datadog"

    def ingest(self, span) -> None:
        if not wire.valid_trace(span):
            raise ValueError("invalid span for datadog sink")
        with self._lock:
            self._buffer.append(span)

    def flush(self) -> None:
        with self._lock:
            spans = list(self._buffer)
            self._buffer.clear()
        if not spans:
            return
        trace_map: Dict[int, List[dict]] = {}
        for span in spans:
            tags = dict(span.tags)
            resource = tags.pop(DATADOG_RESOURCE_KEY, "") or "unknown"
            trace_map.setdefault(span.trace_id, []).append({
                "trace_id": span.trace_id,
                "span_id": span.id,
                "parent_id": max(span.parent_id, 0),
                "service": span.service,
                "name": span.name or "unknown",
                "resource": resource,
                "start": span.start_timestamp,
                "duration": span.end_timestamp - span.start_timestamp,
                "type": DATADOG_SPAN_TYPE,
                "error": 2 if span.error else 0,
                "meta": tags,
            })
        # two-dimensional: spans grouped per trace (datadog.go:503-508)
        final_traces = list(trace_map.values())
        try:
            # /v0.3/traces takes PUT without deflate (datadog.go:510-515)
            status = post_with_retry(
                lambda: self.post(f"{self.trace_address}/v0.3/traces",
                                  final_traces, compress=False,
                                  method="PUT"),
                self.retry_policy, on_retry=self._count_retry)
            if _ok(status):
                self.spans_flushed += len(spans)
            else:
                log.warning("Datadog trace flush returned HTTP %d", status)
        except OSError:
            log.warning("error flushing traces to Datadog", exc_info=True)
