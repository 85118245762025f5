"""gRPC forwarding: ``Forward.SendMetrics`` client and import server.

Client mirrors ``forwardGRPC`` (``/root/reference/flusher.go:424-473``;
channel dialed once at startup, server.go:626-635). Server mirrors
``importsrv.Server`` (``importsrv/server.go:37-147``): receive a
MetricList, merge every metric into the aggregation state. The reference
groups metrics by fnv1a hash across worker goroutines to keep one series
on one worker (importsrv/server.go:99-132); the dense store already
guarantees that — the interner maps a series to exactly one row — so the
grouping step disappears.
"""

from __future__ import annotations

import logging
import threading
import time
from concurrent import futures
from typing import Callable, Optional

import grpc
from google.protobuf import empty_pb2

from veneur_tpu.forward.convert import apply_metric, metric_list_from_state
from veneur_tpu.protocol import forward_pb2

log = logging.getLogger("veneur.forward.grpc")

_METHOD = "/forwardrpc.Forward/SendMetrics"
# forward messages scale with active-series cardinality; 256 MB covers
# ~2.5M digests per interval per local before chunking is needed
_MAX_MESSAGE = 256 * 1024 * 1024


def encode_forwardable_frames(state, compression: float,
                              reference_compat: bool,
                              chunk_bytes: int) -> list:
    """ForwardableState → ``[(serialized MetricList bytes, row_count)]``,
    transport-agnostic: columnar/packed digest planes encode natively
    (C++), everything else through the protobuf builder. Used by the
    gRPC forwarder and the framed-TCP native forwarder — protobuf
    messages concatenate, so each frame is a complete MetricList."""
    from veneur_tpu.core.store import PackedDigestPlanes
    from veneur_tpu.native import egress

    frames = []
    if egress.available():
        for attr, pb_type in (("histograms_columnar", 2),
                              ("timers_columnar", 4)):
            col = getattr(state, attr)
            if col is None:
                continue
            if isinstance(col[2], PackedDigestPlanes):
                # device-compacted planes: quantized arrays go on the
                # wire verbatim (or dequantize in C++ for a reference
                # global) — the 1M+-series forward path
                names, tags, planes = col
                chunks = egress.encode_digest_metrics_packed(
                    names, tags, planes, pb_type, compression,
                    max_body_bytes=chunk_bytes,
                    reference_compat=reference_compat)
                n_raw = planes.nrows
            else:
                names, tags, means, weights, dmins, dmaxs = col
                chunks = egress.encode_digest_metrics(
                    names, tags, means, weights, dmins, dmaxs, pb_type,
                    compression, max_body_bytes=chunk_bytes,
                    reference_compat=reference_compat)
                n_raw = len(means)
            setattr(state, attr, None)  # consumed
            # rows credit per chunk: a mid-loop transport failure must
            # not misreport rows the global already merged
            per = n_raw // len(chunks) if chunks else 0
            for i, c in enumerate(chunks):
                frames.append((c, n_raw - per * (len(chunks) - 1)
                               if i == len(chunks) - 1 else per))
    else:
        state.materialize_digests()
    mlist = metric_list_from_state(state, compression,
                                   reference_compat=reference_compat)
    # a list can be topk-sketch-only (every series was columnar or
    # heavy-hitter): HasField, not len(metrics), decides emptiness
    if mlist.metrics or mlist.HasField("topk"):
        frames.append((mlist.SerializeToString(), len(mlist.metrics)))
    return frames


class GRPCForwarder:
    """Per-flush gRPC forward of ForwardableState (flusher.go:424-473)."""

    def __init__(self, addr: str, timeout: float = 10.0,
                 compression: float = 100.0,
                 reference_compat: bool = False,
                 retry_policy=None, breaker=None, fault_injector=None):
        from veneur_tpu.resilience import RetryPolicy

        if addr.startswith(("http://", "grpc://")):
            addr = addr.split("://", 1)[1]
        self.addr = addr
        self.timeout = timeout
        self.compression = compression
        self.reference_compat = reference_compat
        # resilience: per-frame retry within the flush deadline (the
        # channel redials transparently; the retry covers the RPC),
        # optional destination breaker, optional fault injection
        self.retry_policy = retry_policy or RetryPolicy()
        self.breaker = breaker
        self._faults = fault_injector
        # the heavy-hitter sketch rides MetricList.topk, an extension
        # field a reference global would skip — keep it off the wire
        # entirely when forwarding into a reference fleet (the local
        # then emits its own top-k, flusher.py)
        self.supports_topk = not reference_compat
        # ask the store for device-compacted digest planes (tdigest
        # fields 16/17): live centroids only, 4 bytes each, instead of
        # the raw [S,K] f32 plane fetch. Reference-compat forwarding
        # keeps the dense f32 path so the f64 centroids a Go global
        # imports carry full float32 precision.
        self.wants_packed_digests = not reference_compat
        self._channel = grpc.insecure_channel(
            addr,
            options=[("grpc.max_receive_message_length", _MAX_MESSAGE),
                     ("grpc.max_send_message_length", _MAX_MESSAGE)])
        # identity-serialized: every frame arrives pre-serialized, either
        # natively encoded (native/veneur_egress.cpp writes the
        # serialization directly) or SerializeToString'd by the builder
        self._send_raw = self._channel.unary_unary(
            _METHOD,
            request_serializer=lambda b: b,
            response_deserializer=empty_pb2.Empty.FromString,
        )
        # telemetry counters (flusher.go:440-470 metric names); the flusher
        # calls forward() from a fresh thread each interval, so guard them
        self._lock = threading.Lock()
        self.forwarded = 0
        self.errors = 0
        self.retries = 0
        # per-send telemetry, drained into veneur.forward.* self-metrics
        self.post_durations = []
        self.post_content_lengths = []

    # native MetricList chunks cap well under the channel's 256 MB limit
    CHUNK_BYTES = 64 * 1024 * 1024

    # status codes worth a retry: transient server/transport conditions,
    # the gRPC analogue of 5xx/429 (a failed-precondition or invalid-
    # argument response would fail identically on every attempt)
    _RETRYABLE_CODES = frozenset((
        grpc.StatusCode.UNAVAILABLE,
        grpc.StatusCode.DEADLINE_EXCEEDED,
        grpc.StatusCode.RESOURCE_EXHAUSTED,
        grpc.StatusCode.ABORTED,
        grpc.StatusCode.UNKNOWN,
    ))

    def retarget(self, addr: str) -> None:
        """Re-dial a new destination — the membership-refresh hook a
        :class:`~veneur_tpu.discovery.LeaderDiscoverer` consumer uses
        to chase a promoted standby. The swap is atomic under the
        counter lock; the old channel closes after (an in-flight RPC
        it cancels fails into the ordinary retry/error accounting)."""
        if addr.startswith(("http://", "grpc://")):
            addr = addr.split("://", 1)[1]
        if addr == self.addr:
            return
        channel = grpc.insecure_channel(
            addr,
            options=[("grpc.max_receive_message_length", _MAX_MESSAGE),
                     ("grpc.max_send_message_length", _MAX_MESSAGE)])
        send = channel.unary_unary(
            _METHOD,
            request_serializer=lambda b: b,
            response_deserializer=empty_pb2.Empty.FromString,
        )
        with self._lock:
            old, self._channel = self._channel, channel
            self._send_raw = send
            self.addr = addr
        old.close()

    def _retryable_rpc(self, e) -> bool:
        code = e.code() if isinstance(e, grpc.RpcError) else None
        return code in self._RETRYABLE_CODES or isinstance(e, OSError)

    def _count_retry(self, retry_index, exc, pause):
        with self._lock:
            self.retries += 1

    def _rejected_by_breaker(self, consume_probe: bool) -> bool:
        """The shared breaker gate: blocked() before the (expensive)
        digest encode is paid (never consumes a half-open probe),
        allow() at the send site (counts the probe)."""
        if self.breaker is None:
            return False
        rejected = (not self.breaker.allow()) if consume_probe \
            else self.breaker.blocked()
        if rejected:
            with self._lock:
                self.errors += 1
            log.warning("gRPC forward to %s skipped: circuit breaker "
                        "open", self.addr)
        return rejected

    def forward(self, state, parent_span=None, deadline=None,
                trace_ctx=None):
        if self._rejected_by_breaker(consume_probe=False):
            return
        # columnar digest planes encode natively — serialized MetricList
        # chunks straight from the packed arrays, no per-row Python
        # (flusher.go:424-473; the chunking bounds message size the way
        # the reference's proxy batches do)
        frames = encode_forwardable_frames(
            state, self.compression, self.reference_compat,
            self.CHUNK_BYTES)
        if not frames:
            return
        metadata = []
        if parent_span is not None:
            # same propagation as the HTTP path, as gRPC metadata
            metadata = [(k.lower(), v)
                        for k, v in parent_span.context_as_parent().items()]
        if trace_ctx is not None:
            # the fleet trace plane's hop contract (obs/tracectx.py),
            # lowercased per gRPC metadata rules
            from veneur_tpu.obs import tracectx

            metadata.append((tracectx.HEADER.lower(), trace_ctx.encode()))
        metadata = tuple(metadata) or None
        from veneur_tpu.resilience import Deadline, call_with_retry

        total = sum(rows for _, rows in frames)
        sent_rows = 0
        attempted_lens = []  # only frames actually put on the wire
        t0 = time.perf_counter()
        if deadline is None:
            deadline = Deadline.after(self.timeout)
        if self._rejected_by_breaker(consume_probe=True):
            return
        try:
            # per-frame retry: already-sent frames are merged upstream
            # and never resend; each attempt's RPC deadline is clamped
            # so retries cannot overrun the flush interval
            for payload, rows in frames:
                def send_frame(payload=payload):
                    if self._faults is not None:
                        self._faults.maybe_fail("forward.grpc")
                    attempted_lens.append(len(payload))
                    self._send_raw(payload,
                                   timeout=deadline.clamp(self.timeout),
                                   metadata=metadata)

                call_with_retry(
                    send_frame, self.retry_policy, deadline=deadline,
                    retryable=(grpc.RpcError, OSError),
                    retry_if=self._retryable_rpc,
                    on_retry=self._count_retry)
                sent_rows += rows
            if self.breaker is not None:
                self.breaker.record_success()
            with self._lock:
                self.forwarded += sent_rows
        except (grpc.RpcError, OSError) as e:
            # the gRPC analogue of the 4xx rule: a permanent status
            # (INVALID_ARGUMENT, FAILED_PRECONDITION, ...) proves the
            # destination is alive and must not trip its breaker —
            # only transport-level/transient codes count
            if self.breaker is not None:
                if self._retryable_rpc(e):
                    self.breaker.record_failure()
                else:
                    self.breaker.record_success()
            with self._lock:
                self.errors += 1
                self.forwarded += sent_rows
            log.warning("failed to forward %d metrics to %s "
                        "(~%d sent before the failure): %s",
                        total, self.addr, sent_rows, e)
        finally:
            with self._lock:
                self.post_durations.append(time.perf_counter() - t0)
                self.post_content_lengths.extend(attempted_lens)

    def close(self):
        self._channel.close()


WORKER_THREAD_PREFIX = "grpc-import"


class ImportServer:
    """The global tier's gRPC ingest (importsrv/server.go:37-147).

    ``apply`` defaults to merging into a server's MetricStore; tests can
    pass any callable taking a metricpb.Metric.
    """

    def __init__(self, store=None,
                 apply: Optional[Callable] = None, workers: int = 4,
                 trace_client=None, hop_log=None):
        from veneur_tpu.native import egress

        self._trace_client = trace_client
        self._hop_log = hop_log  # fleet trace plane (obs/tracectx.py)
        self._store = store if apply is None else None
        if apply is None:
            if store is None:
                raise ValueError("need a store or an apply callable")
            apply = lambda m: apply_metric(store, m)  # noqa: E731
        self._apply = apply
        # native lane: requests arrive as raw bytes, decode + intern +
        # bulk-stage in C++/numpy (store.import_columnar) — the fix for
        # the Python-protobuf-decode ceiling (~35k series/s) on the
        # global tier's ingest
        self._native = self._store is not None and egress.available()
        self.received = 0
        self.import_errors = 0
        self._lock = threading.Lock()
        # a big local's per-interval MetricList (one digest per active
        # series) easily passes gRPC's 4 MB default — 20k digests with
        # ~50 centroids each is ~20 MB on the wire
        self._grpc = grpc.server(
            # named, so /debug/vars obs.threads tells the workers'
            # CPU from the rest (grpc_import.workers_cpu_s sums them)
            futures.ThreadPoolExecutor(
                max_workers=workers,
                thread_name_prefix=WORKER_THREAD_PREFIX),
            options=[("grpc.max_receive_message_length", _MAX_MESSAGE),
                     ("grpc.max_send_message_length", _MAX_MESSAGE)])
        deserializer = ((lambda b: b) if self._native
                        else forward_pb2.MetricList.FromString)
        handler = grpc.method_handlers_generic_handler(
            "forwardrpc.Forward",
            {"SendMetrics": grpc.unary_unary_rpc_method_handler(
                self._send_metrics,
                request_deserializer=deserializer,
                response_serializer=empty_pb2.Empty.SerializeToString)})
        self._grpc.add_generic_rpc_handlers((handler,))
        self.port: Optional[int] = None

    def _send_metrics(self, request: forward_pb2.MetricList, context):
        from veneur_tpu import trace as vtrace

        carrier = {k: v for k, v in (context.invocation_metadata() or ())}
        span = vtrace.from_headers(carrier, resource="veneur.import")
        span.name = "import"
        t0 = time.perf_counter()
        n_ok = 0
        if self._native:
            # request is raw bytes: C++ decode + intern, numpy bulk apply
            from veneur_tpu.native import egress
            from veneur_tpu.obs import kernels as obs_kernels

            with obs_kernels.host_scope("import"):
                t_dec = time.monotonic_ns()
                # zero-copy views: import_columnar only gathers/stages
                # from them and they die with close() below
                dec = egress.decode_metric_list(request, copy=False)
                try:
                    n_ok, n_err = self._store.import_columnar(
                        dec, request,
                        decode_ns=time.monotonic_ns() - t_dec)
                finally:
                    dec.close()
            if n_err:
                with self._lock:
                    self.import_errors += n_err
        elif self._store is not None:
            # batched digest staging: one bulk store call instead of a
            # per-metric chain — the import tier's actual throughput
            # ceiling. Malformed metrics are validated out BEFORE
            # anything is applied (no double-apply fallback).
            from veneur_tpu.forward.convert import apply_metric_list

            n_ok, n_err = apply_metric_list(self._store, request)
            if n_err:
                with self._lock:
                    self.import_errors += n_err
        else:
            for m in request.metrics:
                try:
                    self._apply(m)
                    n_ok += 1
                except Exception as e:  # one bad metric must not drop it all
                    with self._lock:
                        self.import_errors += 1
                    log.debug("failed to import metric %s: %s", m.name, e)
        with self._lock:
            self.received += n_ok
        from veneur_tpu.trace import samples as ssf_samples

        span.add(ssf_samples.timing("veneur.import.response_duration_ns",
                                    time.perf_counter() - t0,
                                    {"part": "merge"}),
                 ssf_samples.count("veneur.import.metrics_total", float(n_ok),
                                   None))
        span.finish()
        span.client_record(self._trace_client)
        if self._hop_log is not None:
            from veneur_tpu.obs import tracectx

            # a contextless legacy import still records (unstitchable
            # but counted) — same contract as the HTTP carrier
            ctx = tracectx.TraceContext.from_headers(carrier)
            self._hop_log.record("global.import", ctx, span.start,
                                 time.time(), metrics=n_ok,
                                 protocol="grpc")
        return empty_pb2.Empty()

    def start(self, addr: str = "[::]:0") -> int:
        """Bind + serve; returns the bound port (server.go:1079-1093)."""
        # grpc-core binds with SO_REUSEPORT by default on Linux, which
        # is what the SIGUSR2 upgrade overlap needs — but it also means
        # an accidental second instance silently splits gRPC ingest,
        # so run the same probe every other listener type gets
        from veneur_tpu.networking import warn_for_stream_addr

        warn_for_stream_addr(addr)
        self.port = self._grpc.add_insecure_port(addr)
        if self.port == 0:
            raise RuntimeError(f"could not bind gRPC import server to {addr}")
        self._grpc.start()
        log.info("gRPC import server listening on %s (port %d)",
                 addr, self.port)
        return self.port

    def stop(self, grace: float = 1.0):
        self._grpc.stop(grace).wait(timeout=grace + 1.0)
