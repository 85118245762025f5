"""Configuration: one YAML file + ``VENEUR_*`` environment overrides.

Behavioral port of ``/root/reference/config.go`` + ``config_parse.go``:
the same key set (plus TPU-specific extensions at the bottom), semi-strict
YAML parsing that warns on unknown keys instead of failing, envconfig-style
overrides, defaults and deprecation shims.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import re
import socket
from dataclasses import dataclass, field
from typing import Any, Dict, List

import yaml

log = logging.getLogger("veneur")


class UnknownConfigKeys(Exception):
    """The file is usable but contains unknown keys (config_parse.go:119-127)."""

    def __init__(self, keys):
        super().__init__(f"unknown configuration keys: {sorted(keys)}")
        self.keys = keys


@dataclass
class Config:
    """Server configuration (config.go:3-89). Field names are the YAML keys."""

    aggregates: List[str] = field(default_factory=list)
    aws_access_key_id: str = ""
    aws_region: str = ""
    aws_s3_bucket: str = ""
    aws_secret_access_key: str = ""
    # accepted for reference-config compatibility but REJECTED when set:
    # Go-runtime block/mutex profiling has no Python equivalent, and a key
    # that parses-and-does-nothing is worse than an error
    block_profile_rate: int = 0
    datadog_api_hostname: str = ""
    datadog_api_key: str = ""
    datadog_flush_max_per_body: int = 0
    datadog_span_buffer_size: int = 0
    datadog_trace_api_address: str = ""
    debug: bool = False
    debug_flushed_metrics: bool = False
    debug_ingested_spans: bool = False
    enable_profiling: bool = False
    falconer_address: str = ""
    flush_file: str = ""
    flush_max_per_body: int = 0  # deprecated → datadog_flush_max_per_body
    forward_address: str = ""
    forward_use_grpc: bool = False
    grpc_address: str = ""
    # framed-TCP MetricList import listener (framework extension — the
    # fast lane past python-grpc's HTTP/2 overhead; forward/
    # native_transport.py). Locals point at it with
    # forward_address: "native://host:port".
    native_import_address: str = ""
    hostname: str = ""
    http_address: str = ""
    indicator_span_timer_name: str = ""
    interval: str = ""
    kafka_broker: str = ""
    kafka_check_topic: str = ""
    kafka_event_topic: str = ""
    kafka_metric_buffer_bytes: int = 0
    kafka_metric_buffer_frequency: str = ""
    kafka_metric_buffer_messages: int = 0
    kafka_metric_require_acks: str = ""
    kafka_metric_topic: str = ""
    kafka_partitioner: str = ""
    kafka_retry_max: int = 0
    kafka_span_buffer_bytes: int = 0
    kafka_span_buffer_frequency: str = ""
    kafka_span_buffer_mesages: int = 0  # (sic — reference key has the typo)
    kafka_span_require_acks: str = ""
    kafka_span_sample_rate_percent: int = 0
    kafka_span_sample_tag: str = ""
    kafka_span_serialization_format: str = ""
    kafka_span_topic: str = ""
    lightstep_access_token: str = ""
    lightstep_collector_host: str = ""
    lightstep_maximum_spans: int = 0
    lightstep_num_clients: int = 0
    lightstep_reconnect_period: str = ""
    metric_max_length: int = 0
    # like block_profile_rate: accepted for reference-config
    # compatibility but REJECTED when set (Go-runtime mutex profiling
    # has no Python equivalent; validate() errors)
    mutex_profile_fraction: int = 0
    num_readers: int = 0
    num_span_workers: int = 0
    num_workers: int = 0
    omit_empty_hostname: bool = False
    percentiles: List[float] = field(default_factory=list)
    read_buffer_size_bytes: int = 0
    sentry_dsn: str = ""
    signalfx_api_key: str = ""
    signalfx_endpoint_base: str = ""
    signalfx_hostname_tag: str = ""
    signalfx_per_tag_api_keys: List[Dict[str, str]] = field(default_factory=list)
    signalfx_vary_key_by: str = ""
    span_channel_capacity: int = 0
    ssf_buffer_size: int = 0  # deprecated → datadog_span_buffer_size
    ssf_listen_addresses: List[str] = field(default_factory=list)
    stats_address: str = ""
    statsd_listen_addresses: List[str] = field(default_factory=list)
    synchronize_with_interval: bool = False
    tags: List[str] = field(default_factory=list)
    tags_exclude: List[str] = field(default_factory=list)
    tls_authority_certificate: str = ""
    tls_certificate: str = ""
    tls_key: str = ""
    trace_lightstep_access_token: str = ""   # deprecated
    trace_lightstep_collector_host: str = ""  # deprecated
    trace_lightstep_maximum_spans: int = 0    # deprecated
    trace_lightstep_num_clients: int = 0      # deprecated
    trace_lightstep_reconnect_period: str = ""  # deprecated
    trace_max_length_bytes: int = 0

    # ---- TPU-framework extensions (not in the reference) -----------------
    # t-digest compression δ; the reference hard-codes 100 (samplers.go:502)
    tdigest_compression: float = 100.0
    # HyperLogLog precision p (2^p registers); the reference hard-codes the
    # axiomhq default 14 (samplers.go:380-388)
    hll_precision: int = 14
    # staging-chunk length for device scatters
    store_chunk: int = 16384
    # initial dense-series capacity per scope-class (grows by doubling;
    # every capacity is a new shape of the ingest and flush programs, so
    # a deployment that knows its cardinality fixes it here). Digest
    # groups place their device state on first write; set and
    # heavy-hitter groups start at no more than 4096 rows whatever this
    # says (a set row is 16 KiB of registers); a slab store's digest
    # groups ignore it and grow a slab_rows slab at a time
    store_initial_capacity: int = 4096
    # histogram/timer digest backing store: "dense" (one [S,K] plane per
    # group, default), "slab" (flat per-slab planes, the multi-million-
    # series capacity plan of core/slab.py; grows one slab at a time), or
    # "tiered" (core/tiered.py: cold series in a packed u16/bf16 quantized
    # pool at ~228 B/row, promotion to dense full-K slots on sustained
    # activity — the 5-10x series-capacity plan at realistic density),
    # or "sharded" (a mesh global's dense planes, made and grown in
    # shards over the series axis, never whole on one device: what
    # "dense" gives under mesh_enabled, said outright, so that a build
    # whose mesh store cannot make them so does not know the value and
    # refuses the file at load; needs mesh_enabled)
    digest_storage: str = "dense"
    # tiered store: packed-pool centroid slots per series (power of two
    # >= 8; more slots = finer cold-row quantiles, more resident bytes)
    tier_pool_centroids: int = 16
    # tiered store: interval sample count at/above which a series counts
    # as HOT (0 = default 64); a HOT pool series is promoted to a dense
    # slot mid-interval once its hot streak meets tier_promote_intervals
    tier_promote_samples: int = 0
    # tiered store: consecutive HOT intervals a pool series needs before
    # it takes a dense slot (0 = default 2) — promotion-side hysteresis
    # so a series oscillating around the activity bar doesn't grab a
    # dense slot on one spike
    tier_promote_intervals: int = 0
    # tiered store: consecutive idle (below-bar) intervals after which a
    # dense series demotes back to the packed pool at the next flush
    # boundary (0 = default 3) — demotion-side hysteresis against dense
    # slot ping-ponging
    tier_demote_intervals: int = 0
    # resident digest dtype for the slab store: "float32" or "packed16"
    # (16-bit planes, the wire's packed format: bfloat16 weights and each
    # mean a uint16 code against its row's [min, max]; the 10M-series
    # plan; kernel math, counts, minima and maxima stay f32, and sparse
    # rows keep the rank bound, docs/tdigest_accuracy.md)
    digest_dtype: str = "float32"
    # rows per slab for the slab store (clamped to 1M by Mosaic's 2 GiB
    # operand bound; smaller slabs bound flush transients tighter). A
    # 10M-series deployment sets 262144 (40 slabs) with max_series
    # 16777216, the 0.7-occupancy freeze clear of 10M names
    slab_rows: int = 1 << 20
    # drain plain-IPv4 UDP statsd listeners with the C++ recvmmsg reader
    # pool + batch parser when the native library is available
    native_ingest: bool = True
    # sharded ingest-lane fleet for UDP statsd listeners
    # (veneur_tpu/ingest/): each reader thread owns a lock-free lane
    # (SO_REUSEPORT socket, recvmmsg batches, native parse, lane-local
    # interner + columnar staging) merged into the store one chunk at a
    # time at the group boundary. 0 = auto (one lane per reader,
    # num_readers); N > 0 = explicit lane count; -1 = disabled (legacy
    # readers: the C++ reader pool, else the Python read loops)
    ingest_lanes: int = 0
    # gRPC forward writes the reference's repeated-Centroid schema IN
    # ADDITION to the packed arrays, so a Go global — or any importer
    # predating the packed extension — can read this local's digests.
    # Doubles digest wire size. Needed when forwarding INTO a reference
    # fleet, or temporarily during a rolling upgrade where locals would
    # otherwise be upgraded before their global (upgrade globals first
    # and this can stay off: the import side reads both schemas).
    forward_reference_compatible: bool = False
    # gRPC forward ships digests as device-compacted quantized arrays
    # (tdigest fields 16/17, 4 bytes/centroid — the mode that fits the
    # flush interval at 1M+ series). Disable during a rolling upgrade
    # whose globals predate the quantized extension (they would skip
    # the unknown fields and import empty digests); reference-compat
    # forwarding ignores this and always writes the dense schema.
    forward_packed_digests: bool = True
    # columnar flush egress: emissions stay flat arrays from the store
    # through native sink serialization (falls back automatically when
    # the native egress library cannot build)
    flush_columnar: bool = True
    # overlapped flush egress (docs/internals.md "Life of a flush"):
    # every retired group's flush program dispatches before any
    # blocking device->host fetch, one serializer thread builds chunks
    # while the next group's fetch blocks, and this depth bounds BOTH
    # the fetched-but-unserialized chunks resident host-side and the
    # slab groups' dispatch-ahead window on device. 0 = fully
    # sequential drain (the pre-pipeline shape); negative rejected.
    flush_pipeline_depth: int = 2
    # streaming egress: chunk-capable sinks (and a chunk-capable
    # forwarder) POST each completed group the moment it exists
    # instead of batching the whole interval; unacked chunks requeue
    # exactly once (late, never lost). Needs flush_columnar and
    # flush_pipeline_depth > 0; other sinks keep the batch fan-out.
    flush_streaming: bool = True
    # bounded-BYTES budget for streamed-chunk requeue: serialized
    # bodies a sink could not ack park for retry on later intervals
    # until their total size reaches this budget, then the OLDEST
    # parked bodies drop (counted) to admit fresher ones — a
    # multi-interval sink outage degrades by counted drop instead of
    # either unbounded host growth or losing everything after one
    # retry. 0 = default (32 MiB); negative rejected.
    sink_requeue_max_bytes: int = 0
    # POST /import backpressure (the reference's bounded worker
    # channels, http.go:54-142): merge worker threads and the bounded
    # batch queue behind them — past capacity, requests shed with 429
    http_import_workers: int = 2
    http_import_queue: int = 64
    # heavy-hitter (veneurtopk) count-min sketch geometry: point-estimate
    # overcount <= e/width of the stream's total weight with probability
    # 1 - e^-depth; size width from the key cardinality you track
    # (BASELINE #5's 100M-key config runs width 2^17)
    topk_depth: int = 4
    topk_width: int = 1 << 16
    topk_k: int = 32
    # shard the global-tier store over a (series, hosts) device mesh;
    # only meaningful on a global instance (forward_address unset)
    mesh_enabled: bool = False
    # mesh fan-in axis width (0 = auto: 2 when the device count is even):
    # a sample chunk is split over it and every digest row is held by
    # each device along it, so an instance fed by forwards alone sets 1
    mesh_hosts: int = 0

    # ---- egress resilience (veneur_tpu/resilience/, docs/resilience.md) --
    # per-flush egress deadline budget: retries and breaker probes never
    # push a flush past min(forward_timeout, interval). Parsed ONCE at
    # load into forward_timeout_seconds; call sites never re-parse.
    forward_timeout: str = ""
    # number of RE-tries per egress operation (0 = single attempt;
    # -1 = unset, defaults to 2)
    retry_max: int = -1
    # first backoff interval; subsequent retries double it with full
    # jitter (uniform over [0, min(cap, base * 2^n)])
    retry_base_interval: str = ""
    # consecutive failures before a destination's breaker opens
    breaker_failure_threshold: int = 0
    # how long an open breaker waits before admitting a half-open probe
    breaker_reset_timeout: str = ""
    # deterministic fault injection for tests and soak runs (rate 0 =
    # off). Same seed → same fault schedule. kinds: comma-separated
    # subset of connect,timeout,http_5xx,partial_write; scope substring-
    # filters operation names (forward.http, sink.datadog, proxy.post…)
    fault_injection_rate: float = 0.0
    fault_injection_seed: int = 0
    fault_injection_kinds: str = ""
    fault_injection_scope: str = ""

    # ---- hot-path overload safety (veneur_tpu/overload.py) ---------------
    # hard per-scope-class series cap (INCLUDING the one overflow row):
    # past it, first-sight series collapse into veneur.overload.overflow
    # (counts preserved, identities dropped) instead of growing device
    # state. 0 = default (1M); negative rejected. A cardinality flood
    # then costs one row, not an OOM plus grow-ladder recompiles.
    max_series: int = 0
    # joined-tag-string length cap per series; oversized tag sets
    # truncate at a tag boundary (counted as quarantined
    # oversized_tags). 0 = default (1024); negative rejected.
    max_tag_length: int = 0
    # admission-control watermarks over the pipeline pressure signal
    # (span-channel/lane fill, group occupancy): >= low freezes
    # first-sight series, >= high sheds raw spans, >= hard sheds statsd
    # datagrams at the socket. 0 = defaults (0.7 / 0.85 / 0.97); must
    # satisfy 0 < low < high < hard <= 1.
    overload_low_watermark: float = 0.0
    overload_high_watermark: float = 0.0
    overload_hard_watermark: float = 0.0
    # flush-kernel compute breaker (resilience/compute.py): consecutive
    # Pallas-merge failures before flushes stop attempting the kernel
    # (0 = default 2), and how long an open breaker waits before one
    # flush probes it again (parse-once; default 60s)
    compute_breaker_failure_threshold: int = 0
    compute_breaker_reset_timeout: str = ""

    # ---- flush-interval observability (veneur_tpu/obs/) ------------------
    # per-stage flush self-tracing: the StageRecorder threads through
    # the whole flush path (store swap, per-group device compute/fetch,
    # serialize, per-sink POST, forward), each interval lands in the
    # /debug/flush-timeline ring as a stage tree + child SSF spans, and
    # stage durations dogfood into the store's own self-telemetry
    # digest group. Off = zero recorders allocated and every stage hook
    # is one thread-local read; the kernel-scope profiler annotations
    # and dispatch counters (obs/kernels.py — a dict bump per
    # chunk-level dispatch, never per packet) stay on either way, as
    # they also serve /debug/xprof and /debug/vars. The 10_obs_overhead
    # bench lane measures the on-cost of this setting.
    obs_enabled: bool = True
    # flush intervals the /debug/flush-timeline ring retains (0 =
    # default 64; negative rejected) — bounds the timeline's memory on
    # a long-lived server
    obs_timeline_intervals: int = 0
    # fleet trace plane (obs/fleet.py, docs/observability.md "Fleet
    # tracing"): peers whose /debug/flush-timeline + /debug/vars the
    # GET /debug/fleet aggregation pulls — comma-separated addresses,
    # or "file:///path" re-read each refresh (one address per line).
    # Empty = fall back to handoff_peers when elastic resharding is
    # on, else this instance serves only its own entries at
    # /debug/trace. List LOCAL instances too: the stitched trace view
    # needs their flush entries.
    fleet_peers: str = ""
    # minimum seconds between /debug/fleet peer-pull rounds (a
    # hammered endpoint costs peers one pull per window); parsed ONCE
    # at load. Empty = 5s
    fleet_pull_interval: str = ""
    # per-peer HTTP budget for one /debug/fleet pull; parsed ONCE at
    # load. Empty = 2s
    fleet_pull_timeout: str = ""

    # ---- elastic fleet resharding (veneur_tpu/fleet/handoff.py) ----------
    # live resharding for the GLOBAL tier (docs/resilience.md "Elastic
    # resharding"): on a fleet membership change, the moved key ranges
    # stream as packed digests to their new owner with zero sample
    # loss. Requires handoff_self, a membership source (handoff_peers
    # or Consul via handoff_service_name), and http_address (peers
    # stream into POST /handoff on it). Only valid on a global.
    handoff_enabled: bool = False
    # this instance's address exactly as the membership source reports
    # it — the ring identity handoffs route around
    handoff_self: str = ""
    # static membership: comma-separated peer addresses (including
    # handoff_self), or "file:///path" to re-read one address per line
    # each refresh (the configmap/orchestrator-managed flavor)
    handoff_peers: str = ""
    # Consul service to discover the global fleet from when
    # handoff_peers is unset (default service name: veneur-global)
    handoff_service_name: str = ""
    # how often membership is re-resolved (a ring change is detected
    # within one refresh); parsed ONCE at load. Empty = 10s
    handoff_refresh_interval: str = ""
    # per-destination transfer budget: retries + backoff for one
    # handoff POST never exceed this before the state re-queues
    # locally; parsed ONCE at load. Empty = forward_timeout
    handoff_timeout: str = ""

    # ---- global HA: warm standby + leased failover (fleet/standby.py) ----
    # standby peers the active global replicates each flush's retired
    # snapshot to (POST /replicate): comma-separated addresses, or
    # "file:///path" (one address per line, re-read each dispatch).
    # Empty = no replication. Only valid on a global; requires
    # http_address (the standbys' /replicate lives on theirs).
    standby_peers: str = ""
    # replicated epochs each standby retains per sender (the shadow
    # ring promotion merges the newest of); 0 = default 2
    standby_shadow_epochs: int = 0
    # where the leadership lease lives: "file:///path" (flock-serialized
    # shared file — one host / one shared filesystem) or "consul://key"
    # (session-TTL'd KV key). Empty = no election (every instance with
    # standby_peers replicates unconditionally)
    lease_path: str = ""
    # how long one acquisition holds the lease without renewal — the
    # detection bound on active death; parsed ONCE at load. Empty = 15s
    lease_ttl: str = ""
    # how often the elector acquires-or-renews; parsed ONCE at load.
    # Empty = lease_ttl / 3
    lease_renew_interval: str = ""

    # ---- crash-safe aggregation state (veneur_tpu/persist/) --------------
    # where the interval checkpoint lives; empty disables checkpointing.
    # The atomic-write scratch file is checkpoint_path + ".tmp".
    checkpoint_path: str = ""
    # how often the background thread snapshots the store — the at-most
    # bound on data lost to a crash. Empty = interval / 4. Parsed ONCE
    # at load into checkpoint_interval_seconds (0.0 = derive from the
    # flush interval at server start).
    checkpoint_interval: str = ""
    # a checkpoint older than this many flush intervals at startup is
    # stale (its data belongs to long-gone intervals) and is discarded
    # instead of merged; 0 = default 2.0
    checkpoint_max_age_intervals: float = 0.0

    def parse_interval(self) -> float:
        return parse_duration(self.interval)

    def validate(self):
        """Reject keys that cannot take effect in this runtime (the
        round-1 audit flagged silently-dead keys as worse than absent)."""
        if self.block_profile_rate:
            raise ValueError(
                "block_profile_rate is a Go-runtime profile knob with no "
                "equivalent here; remove it (enable_profiling drives the "
                "Python profiler)")
        if self.mutex_profile_fraction:
            raise ValueError(
                "mutex_profile_fraction is a Go-runtime profile knob with "
                "no equivalent here; remove it (enable_profiling drives "
                "the Python profiler)")
        if self.sentry_dsn:
            from veneur_tpu.crash import SentryReporter

            SentryReporter(self.sentry_dsn)  # raises on malformed DSN
        if self.digest_storage not in ("dense", "slab", "tiered",
                                       "sharded"):
            raise ValueError(
                f"digest_storage must be 'dense', 'slab', 'tiered' or "
                f"'sharded', got {self.digest_storage!r}")
        if self.digest_storage == "sharded" and not self.mesh_enabled:
            raise ValueError(
                "digest_storage: sharded is the mesh store's dense "
                "planes made in shards and needs mesh_enabled: true "
                "(one chip runs digest_storage: dense)")
        pk = self.tier_pool_centroids
        if pk < 8 or pk & (pk - 1):
            raise ValueError(
                f"tier_pool_centroids must be a power of two >= 8 (the "
                f"packed pool's per-row centroid budget), got {pk}")
        for knob in ("tier_promote_samples", "tier_promote_intervals",
                     "tier_demote_intervals"):
            if getattr(self, knob) < 0:
                raise ValueError(
                    f"{knob} must be >= 0 (0 = use the default), "
                    f"got {getattr(self, knob)}")
        if self.digest_dtype not in ("float32", "packed16"):
            raise ValueError(
                f"digest_dtype must be 'float32' or 'packed16', got "
                f"{self.digest_dtype!r}")
        if self.digest_dtype == "packed16" and self.digest_storage != "slab":
            raise ValueError(
                "digest_dtype: packed16 requires digest_storage: slab "
                "(the dense store is f32-only)")
        if self.slab_rows <= 0:
            raise ValueError(f"slab_rows must be positive, got "
                             f"{self.slab_rows}")
        if self.digest_storage == "slab" and self.mesh_enabled:
            raise ValueError(
                "digest_storage: slab cannot combine with mesh_enabled: "
                "the slab layout is the single-chip capacity plan and "
                "fleet mode supersedes it. Run the mesh dense, or use "
                "digest_storage: tiered — fleet mode composes with the "
                "tiered packed-pool residency (fleet/mesh_tiered.py, "
                "docs/internals.md \"Fleet mode\")")
        if self.mesh_enabled and self.forward_address:
            raise ValueError(
                "mesh_enabled requires a GLOBAL instance, but "
                "forward_address is set (a local forwards its sketches "
                "upstream instead of sharding a store over the mesh). "
                "Unset one of them: mesh_enabled belongs on the "
                "instance the fleet forwards INTO")
        if self.ingest_lanes < -1:
            raise ValueError(
                f"ingest_lanes must be -1 (disabled), 0 (auto: one lane "
                f"per reader) or a positive lane count, got "
                f"{self.ingest_lanes}")
        if self.breaker_failure_threshold < 0:
            raise ValueError(
                f"breaker_failure_threshold must be >= 0 (0 = use the "
                f"default, {_BREAKER_THRESHOLD_DEFAULT}; breakers cannot "
                f"be disabled), got {self.breaker_failure_threshold}")
        if self.span_channel_capacity < 0:
            # queue.Queue treats maxsize <= 0 as UNBOUNDED, which would
            # silently defeat the span-shedding overload design; 0 takes
            # the default (100) in apply_defaults, so only a negative
            # could ever reach the Queue constructor — reject it
            raise ValueError(
                f"span_channel_capacity must be positive (0 = use the "
                f"default, 100; a queue.Queue maxsize <= 0 is unbounded "
                f"and defeats span shedding), got "
                f"{self.span_channel_capacity}")
        if self.max_series < 0:
            raise ValueError(
                f"max_series must be positive (0 = use the default, "
                f"{_MAX_SERIES_DEFAULT}; an unbounded store fails open "
                f"under a cardinality flood), got {self.max_series}")
        if self.max_tag_length < 0:
            raise ValueError(
                f"max_tag_length must be positive (0 = use the default, "
                f"{_MAX_TAG_LENGTH_DEFAULT}), got {self.max_tag_length}")
        if self.compute_breaker_failure_threshold < 0:
            raise ValueError(
                f"compute_breaker_failure_threshold must be >= 0 (0 = "
                f"use the default, 2; the compute breaker cannot be "
                f"disabled), got {self.compute_breaker_failure_threshold}")
        marks = (self.overload_low_watermark or _OVERLOAD_LOW_DEFAULT,
                 self.overload_high_watermark or _OVERLOAD_HIGH_DEFAULT,
                 self.overload_hard_watermark or _OVERLOAD_HARD_DEFAULT)
        if not 0.0 < marks[0] < marks[1] < marks[2] <= 1.0:
            raise ValueError(
                f"overload watermarks must satisfy 0 < low < high < "
                f"hard <= 1 (after 0-means-default substitution), got "
                f"{marks[0]}/{marks[1]}/{marks[2]}")
        if self.obs_timeline_intervals < 0:
            raise ValueError(
                f"obs_timeline_intervals must be >= 0 (0 = use the "
                f"default, 64; the flush-timeline ring cannot be "
                f"unbounded), got {self.obs_timeline_intervals}")
        if self.flush_pipeline_depth < 0:
            raise ValueError(
                f"flush_pipeline_depth must be >= 0 (0 = sequential "
                f"flush, N = overlapped pipeline bounded at N in-flight "
                f"chunks), got {self.flush_pipeline_depth}")
        if self.sink_requeue_max_bytes < 0:
            raise ValueError(
                f"sink_requeue_max_bytes must be >= 0 (0 = use the "
                f"default, 32 MiB; the parked-body budget cannot be "
                f"unbounded), got {self.sink_requeue_max_bytes}")
        if self.checkpoint_max_age_intervals < 0:
            raise ValueError(
                f"checkpoint_max_age_intervals must be >= 0 (0 = use "
                f"the default, 2.0), got "
                f"{self.checkpoint_max_age_intervals}")
        if not 0.0 <= self.fault_injection_rate <= 1.0:
            raise ValueError(
                f"fault_injection_rate must be in [0, 1], got "
                f"{self.fault_injection_rate}")
        if self.handoff_enabled:
            if self.forward_address:
                raise ValueError(
                    "handoff_enabled requires a GLOBAL instance, but "
                    "forward_address is set (a local owns no ring "
                    "ranges to hand off). Unset one of them")
            if not self.handoff_self:
                raise ValueError(
                    "handoff_enabled requires handoff_self: the address "
                    "this instance appears as in the fleet membership "
                    "(handoff_peers / discovery)")
            if not self.handoff_peers and not self.handoff_service_name:
                raise ValueError(
                    "handoff_enabled requires a membership source: set "
                    "handoff_peers (static CSV or file://...) or "
                    "handoff_service_name (Consul)")
            if not self.http_address:
                raise ValueError(
                    "handoff_enabled requires http_address: peers "
                    "stream moved ranges into POST /handoff on it")
        if self.standby_peers or self.lease_path:
            if self.forward_address:
                raise ValueError(
                    "standby_peers/lease_path require a GLOBAL instance, "
                    "but forward_address is set (a local has no merged "
                    "store to replicate). Unset one of them")
            if self.standby_peers and not self.http_address:
                raise ValueError(
                    "standby_peers requires http_address: standbys "
                    "receive replication on POST /replicate and serve "
                    "GET /ha-status on it")
        if self.standby_shadow_epochs < 0:
            raise ValueError(
                f"standby_shadow_epochs must be >= 0 (0 = use the "
                f"default, 2), got {self.standby_shadow_epochs}")
        if self.lease_path and not (
                self.lease_path.startswith("file://")
                or self.lease_path.startswith("consul://")):
            raise ValueError(
                f"lease_path must be file:///path or consul://key, got "
                f"{self.lease_path!r}")
        if self.fault_injection_kinds:
            from veneur_tpu.resilience.faults import (ALL_KINDS,
                                                      CHURN_KINDS,
                                                      INGEST_KINDS,
                                                      SOAK_KINDS)

            known = ALL_KINDS + INGEST_KINDS + CHURN_KINDS + SOAK_KINDS
            bad = [k.strip()
                   for k in self.fault_injection_kinds.split(",")
                   if k.strip() and k.strip() not in known]
            if bad:
                raise ValueError(
                    f"unknown fault_injection_kinds {bad}; known: "
                    f"{list(known)}")

    def apply_defaults(self):
        """Defaults + deprecation shims (config_parse.go:118-185)."""
        if not self.aggregates:
            self.aggregates = ["min", "max", "count"]
        if not self.hostname and not self.omit_empty_hostname:
            self.hostname = socket.gethostname()
        if not self.interval:
            self.interval = "10s"
        if not self.metric_max_length:
            self.metric_max_length = 4096
        if not self.read_buffer_size_bytes:
            self.read_buffer_size_bytes = 2 * 1048576
        if self.ssf_buffer_size:
            log.warning("ssf_buffer_size has been replaced by "
                        "datadog_span_buffer_size and will be removed")
            if not self.datadog_span_buffer_size:
                self.datadog_span_buffer_size = self.ssf_buffer_size
        if self.flush_max_per_body:
            log.warning("flush_max_per_body has been replaced by "
                        "datadog_flush_max_per_body and will be removed")
            if not self.datadog_flush_max_per_body:
                self.datadog_flush_max_per_body = self.flush_max_per_body
        for old, new in (("trace_lightstep_access_token", "lightstep_access_token"),
                         ("trace_lightstep_collector_host", "lightstep_collector_host"),
                         ("trace_lightstep_maximum_spans", "lightstep_maximum_spans"),
                         ("trace_lightstep_num_clients", "lightstep_num_clients"),
                         ("trace_lightstep_reconnect_period", "lightstep_reconnect_period")):
            oldv = getattr(self, old)
            if oldv:
                log.warning("%s has been replaced by %s and will be removed",
                            old, new)
                if not getattr(self, new):
                    setattr(self, new, oldv)
        if not self.datadog_flush_max_per_body:
            self.datadog_flush_max_per_body = 25000
        if not self.span_channel_capacity:
            self.span_channel_capacity = 100
        if not self.num_workers:
            self.num_workers = 1
        if not self.num_readers:
            self.num_readers = 1
        if not self.num_span_workers:
            self.num_span_workers = 1
        if not self.datadog_span_buffer_size:
            self.datadog_span_buffer_size = 16384
        if not self.trace_max_length_bytes:
            self.trace_max_length_bytes = 16 * 1024
        if not self.checkpoint_max_age_intervals:
            self.checkpoint_max_age_intervals = 2.0
        if not self.sink_requeue_max_bytes:
            self.sink_requeue_max_bytes = 32 * 1048576
        # overload-safety defaults (veneur_tpu/overload.py); the
        # compute-breaker timeout follows the parse-once policy
        if not self.max_series:
            self.max_series = _MAX_SERIES_DEFAULT
        if not self.max_tag_length:
            self.max_tag_length = _MAX_TAG_LENGTH_DEFAULT
        if not self.overload_low_watermark:
            self.overload_low_watermark = _OVERLOAD_LOW_DEFAULT
        if not self.overload_high_watermark:
            self.overload_high_watermark = _OVERLOAD_HIGH_DEFAULT
        if not self.overload_hard_watermark:
            self.overload_hard_watermark = _OVERLOAD_HARD_DEFAULT
        if not self.compute_breaker_failure_threshold:
            self.compute_breaker_failure_threshold = 2
        if not self.compute_breaker_reset_timeout:
            self.compute_breaker_reset_timeout = "60s"
        if not self.obs_timeline_intervals:
            self.obs_timeline_intervals = 64
        # tiered-residency hysteresis defaults (core/tiered.py)
        if not self.tier_promote_samples:
            self.tier_promote_samples = 64
        if not self.tier_promote_intervals:
            self.tier_promote_intervals = 2
        if not self.tier_demote_intervals:
            self.tier_demote_intervals = 3
        self.compute_breaker_reset_timeout_seconds = parse_duration(
            self.compute_breaker_reset_timeout)
        # elastic-resharding durations, parse-once like every other
        # duration knob (handoff_timeout defaults to the forward
        # budget, resolved after apply_resilience_defaults below)
        self.handoff_refresh_interval_seconds = (
            parse_duration(self.handoff_refresh_interval)
            if self.handoff_refresh_interval else 10.0)
        # fleet trace plane pull knobs (obs/fleet.py), parse-once
        self.fleet_pull_interval_seconds = (
            parse_duration(self.fleet_pull_interval)
            if self.fleet_pull_interval else 5.0)
        self.fleet_pull_timeout_seconds = (
            parse_duration(self.fleet_pull_timeout)
            if self.fleet_pull_timeout else 2.0)
        # parse-once (round-1 audit policy): 0.0 = unset, the server
        # derives interval / 4 at start
        self.checkpoint_interval_seconds = (
            parse_duration(self.checkpoint_interval)
            if self.checkpoint_interval else 0.0)
        # global-HA knobs (fleet/standby.py, discovery/lease.py),
        # parse-once like every other duration
        if not self.standby_shadow_epochs:
            self.standby_shadow_epochs = 2
        self.lease_ttl_seconds = (
            parse_duration(self.lease_ttl) if self.lease_ttl else 15.0)
        self.lease_renew_interval_seconds = (
            parse_duration(self.lease_renew_interval)
            if self.lease_renew_interval else self.lease_ttl_seconds / 3.0)
        self.apply_resilience_defaults()
        self.handoff_timeout_seconds = (
            parse_duration(self.handoff_timeout) if self.handoff_timeout
            else self.forward_timeout_seconds)
        return self

    def apply_resilience_defaults(self):
        return _apply_resilience_defaults(self)


# the 0-means-default convention matches the other int knobs
# (num_workers etc.); a breaker cannot be disabled, only tuned
_BREAKER_THRESHOLD_DEFAULT = 5
# overload-safety defaults (see veneur_tpu/overload.py, which holds the
# canonical copies the controller falls back to)
_MAX_SERIES_DEFAULT = 1 << 20
_MAX_TAG_LENGTH_DEFAULT = 1024
_OVERLOAD_LOW_DEFAULT = 0.7
_OVERLOAD_HIGH_DEFAULT = 0.85
_OVERLOAD_HARD_DEFAULT = 0.97


def _apply_resilience_defaults(cfg):
    """Default + parse the shared egress-resilience knobs ONCE (the
    round-1 audit policy: durations parse at load, call sites read the
    float attributes, never re-parse). Idempotent; raises on malformed
    durations. Shared by Config.apply_defaults and ProxyConfig.finalize."""
    if not cfg.forward_timeout:
        cfg.forward_timeout = "10s"
    if cfg.retry_max < 0:
        cfg.retry_max = 2
    if not cfg.retry_base_interval:
        cfg.retry_base_interval = "100ms"
    if not cfg.breaker_failure_threshold:
        cfg.breaker_failure_threshold = _BREAKER_THRESHOLD_DEFAULT
    if not cfg.breaker_reset_timeout:
        cfg.breaker_reset_timeout = "30s"
    cfg.forward_timeout_seconds = parse_duration(cfg.forward_timeout)
    cfg.retry_base_interval_seconds = parse_duration(cfg.retry_base_interval)
    cfg.breaker_reset_timeout_seconds = parse_duration(
        cfg.breaker_reset_timeout)
    return cfg


@dataclass
class ProxyConfig:
    """Proxy configuration (config_proxy.go:3-18), plus the shared
    egress-resilience knobs (docs/resilience.md)."""

    consul_forward_service_name: str = ""
    consul_refresh_interval: str = ""
    consul_trace_service_name: str = ""
    debug: bool = False
    enable_profiling: bool = False
    forward_address: str = ""
    forward_timeout: str = ""
    http_address: str = ""
    runtime_metrics_interval: str = ""
    sentry_dsn: str = ""
    ssf_destination_address: str = ""
    stats_address: str = ""
    trace_address: str = ""
    trace_api_address: str = ""
    grpc_forward_address: str = ""  # extension: gRPC proxy listener
    # egress resilience, same semantics as the server Config's keys
    retry_max: int = -1
    retry_base_interval: str = ""
    breaker_failure_threshold: int = 0
    breaker_reset_timeout: str = ""
    fault_injection_rate: float = 0.0
    fault_injection_seed: int = 0
    fault_injection_kinds: str = ""
    fault_injection_scope: str = ""

    def finalize(self) -> "ProxyConfig":
        """Defaults + parse-once durations; idempotent (the Proxy calls
        this defensively for configs constructed directly in tests)."""
        if not self.consul_refresh_interval:
            self.consul_refresh_interval = "30s"
        if self.breaker_failure_threshold < 0:
            raise ValueError(
                f"breaker_failure_threshold must be >= 0 (0 = use the "
                f"default, {_BREAKER_THRESHOLD_DEFAULT}; breakers cannot "
                f"be disabled), got {self.breaker_failure_threshold}")
        if not 0.0 <= self.fault_injection_rate <= 1.0:
            raise ValueError(
                f"fault_injection_rate must be in [0, 1], got "
                f"{self.fault_injection_rate}")
        return _apply_resilience_defaults(self)


_DURATION_RE = re.compile(r"(\d+(?:\.\d+)?)(ns|us|µs|ms|s|m|h)")
_DURATION_UNITS = {"ns": 1e-9, "us": 1e-6, "µs": 1e-6, "ms": 1e-3,
                   "s": 1.0, "m": 60.0, "h": 3600.0}


def parse_duration(s: str) -> float:
    """Go-style duration string → seconds ("10s", "1m30s", "50ms")."""
    if not s:
        raise ValueError("empty duration")
    pos = 0
    total = 0.0
    for m in _DURATION_RE.finditer(s):
        if m.start() != pos:
            raise ValueError(f"invalid duration {s!r}")
        total += float(m.group(1)) * _DURATION_UNITS[m.group(2)]
        pos = m.end()
    if pos != len(s):
        raise ValueError(f"invalid duration {s!r}")
    return total


def _coerce(value: str, target_type: Any):
    if target_type is bool:
        return value.lower() in ("1", "true", "yes", "on")
    if target_type is int:
        return int(value)
    if target_type is float:
        return float(value)
    if target_type in (List[str], List[float], List[Dict[str, str]]):
        items = [v.strip() for v in value.split(",") if v.strip()]
        if target_type is List[float]:
            return [float(v) for v in items]
        return items
    return value


def _apply_env_overrides(cfg, environ=None):
    """envconfig-style overrides (config_parse.go:107-115): VENEUR_<FIELD>
    where <FIELD> is the field name uppercased, with or without underscores
    (the Go library strips them from struct field names)."""
    import typing

    environ = environ if environ is not None else os.environ
    hints = typing.get_type_hints(type(cfg))
    names = {f.name for f in dataclasses.fields(cfg)}
    compact = {name.replace("_", "").upper(): name for name in names}
    for env_key, raw in environ.items():
        if not env_key.startswith("VENEUR_"):
            continue
        suffix = env_key[len("VENEUR_"):]
        name = (suffix.lower() if suffix.lower() in names
                else compact.get(suffix.replace("_", "").upper()))
        if name is None:
            continue
        setattr(cfg, name, _coerce(raw, hints[name]))
    return cfg


def _load_semi_strict(text: str, cls):
    """Strict-then-loose YAML load: unknown keys are reported but do not
    fail the load (unmarshalSemiStrictly, config_parse.go:83-96)."""
    data = yaml.safe_load(text) or {}
    if not isinstance(data, dict):
        raise ValueError("config must be a YAML mapping")
    known = {f.name for f in dataclasses.fields(cls)}
    unknown = set(data) - known
    cfg = cls(**{k: v for k, v in data.items() if k in known and v is not None})
    return cfg, unknown


def read_config(path: str, environ=None) -> Config:
    """Load + env-override + defaults (ReadConfig, config_parse.go:66-79).
    Raises UnknownConfigKeys *after* producing a usable config only when
    the caller inspects .partial — here we just warn, as the binaries do."""
    with open(path) as f:
        text = f.read()
    cfg, unknown = _load_semi_strict(text, Config)
    _apply_env_overrides(cfg, environ)
    cfg.apply_defaults()
    cfg.validate()
    if unknown:
        log.warning("config contains unknown keys: %s", sorted(unknown))
    return cfg


def read_proxy_config(path: str, environ=None) -> ProxyConfig:
    with open(path) as f:
        text = f.read()
    cfg, unknown = _load_semi_strict(text, ProxyConfig)
    _apply_env_overrides(cfg, environ)
    if unknown:
        log.warning("proxy config contains unknown keys: %s", sorted(unknown))
    return cfg.finalize()
