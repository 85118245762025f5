"""The dense metric store: every series is a row in device-resident tensors.

This is the TPU re-expression of the reference's per-worker sampler maps
(``/root/reference/worker.go:54-157``): where the reference keeps a
``map[MetricKey]*sampler`` per goroutine and merges each sketch one at a time,
here every scope-class is ONE dense group —

    =====================  =============================================
    scope-class            state
    =====================  =============================================
    counters               host   int64  [S]   (exact, like Go int64)
    global_counters        host   int64  [S]
    gauges                 host   float64[S]   (last-write-wins)
    global_gauges          host   float64[S]
    local_status_checks    host   float64[S] + message/hostname strings
    histograms             device t-digest [S, K] + temp bins [S, K]
    timers                 device t-digest [S, K] + temp bins [S, K]
    local_histograms       device t-digest [S, K] + temp bins [S, K]
    local_timers           device t-digest [S, K] + temp bins [S, K]
    sets                   device HLL registers [S, 2^p] (int8)
    local_sets             device HLL registers [S, 2^p] (int8)
    =====================  =============================================

(or, for the two set groups of a store reserved past 4096 rows without a
mesh, each series' filled registers as ``[S, 128]`` entries until it
fills them, core/sets.py)

— so the per-interval flush (the hot path, ``flusher.go:26-132``) is a handful
of jitted XLA programs over ``[S, ...]`` tensors instead of S sequential
sketch walks. Counters/gauges stay host-side numpy: they are exact integer /
last-write scalars whose per-interval cost is one vectorized pass; the
FLOP/bandwidth-heavy mergeable-sketch math (t-digest compress, HLL
estimate) is what rides the TPU.

The EGRESS stays columnar too (``flush(columnar=True)``, the server
default): results leave as flat arrays + interner string arenas
(``core/columnar.py``) that native sinks serialize directly
(``native/veneur_egress.cpp``) and the gRPC forwarder encodes from the
``[S, K]`` digest planes — never ~15 Python objects per series. The
import side mirrors it: natively decoded MetricLists bulk-stage through
``import_columnar``.

Scope semantics (which group a sample lands in, and which groups a local vs
global instance flushes or forwards) follow ``worker.go:96-157`` and
``flusher.go:189-254`` exactly; see ``MetricStore.process_metric`` and
``MetricStore.flush``.
"""

from __future__ import annotations

import logging
import math
import threading
import time
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from veneur_tpu.ops import hll as hll_ops
from veneur_tpu.ops import tdigest_pallas
from veneur_tpu.core.bucketing import bucketed, pow2_cap
from veneur_tpu.core.locking import acquires_lock, requires_lock
from veneur_tpu.obs import kernels as obs_kernels
from veneur_tpu.obs import recorder as obs_rec
from veneur_tpu.ops import tdigest as td_ops
from veneur_tpu.overload import (F32_ABS_MAX, MIN_SAMPLE_RATE,
                                 OVERFLOW_NAME, Quarantine, freeze_exempt)
from veneur_tpu.samplers.intermetric import (
    Aggregate,
    HistogramAggregates,
    InterMetric,
    MetricType,
    route_info,
)
from veneur_tpu.samplers.parser import (
    GLOBAL_ONLY,
    LOCAL_ONLY,
    MetricKey,
    UDPMetric,
)

log = logging.getLogger("veneur.store")

DEFAULT_CHUNK = 1 << 14
DEFAULT_INITIAL_CAPACITY = 1 << 10
_GROW_FACTOR = 2
# HLL register imports drain in fixed batches of this size; the mesh store's
# scatter buffers are sized to it, so both sites must agree
IMPORT_DRAIN_BATCH = 256

# native ParsedBatch record types (RecordType in native/veneur_ingest.cpp)
_NATIVE_TYPE_NAMES = ("counter", "gauge", "histogram", "timer", "set")
# scope-class kinds for the native batch dispatch; must mirror kind_of()
# in native/veneur_ingest.cpp
(_K_COUNTER, _K_GLOBAL_COUNTER, _K_GAUGE, _K_GLOBAL_GAUGE, _K_HISTO,
 _K_LOCAL_HISTO, _K_TIMER, _K_LOCAL_TIMER, _K_SET, _K_LOCAL_SET,
 _K_TOPK) = range(11)
_TOPK_SCOPE = 3  # veneur_ingest.cpp Scope::kTopK
_KIND_RAW = 255  # kind_of()'s sentinel for event/service-check records


class Interner:
    """MetricKey -> dense row index, plus per-row name/tags for flush-time
    emission. The moral equivalent of the reference's
    map[MetricKey]*sampler keys (worker.go:54-91). ``joined`` keeps the
    comma-joined tag string per row for the columnar egress arenas."""

    __slots__ = ("rows", "names", "tags", "joined")

    def __init__(self):
        self.rows: Dict[MetricKey, int] = {}
        self.names: List[str] = []
        self.tags: List[List[str]] = []
        self.joined: List[str] = []

    def __len__(self) -> int:
        return len(self.rows)

    def intern(self, key: MetricKey, tags: List[str]) -> int:
        row = self.rows.get(key)
        if row is None:
            row = len(self.rows)
            self.rows[key] = row
            self.names.append(key.name)
            self.tags.append(tags)
            self.joined.append(key.joined_tags)
        return row

    def reset(self):
        self.rows.clear()
        self.names.clear()
        self.tags.clear()
        self.joined.clear()


# ---------------------------------------------------------------------------
# Overload limits shared by every group (bounded cardinality + quarantine)
# ---------------------------------------------------------------------------

# int64 counter lanes: reject any sample whose Go-semantics contribution
# int64(value) * int64(1/rate) could overflow (a crash via numpy's
# OverflowError, or a silent wrap in the bulk path)
COUNTER_CONTRIB_MAX = float(1 << 63)


def _scrub_counter_batch(quarantine, vals, rates) -> np.ndarray:
    """Admissibility mask for a bulk counter span; rejects counted per
    reason into the shared quarantine ledger (None = just mask). The
    bound mirrors the lane's ACTUAL Go-truncation semantics —
    int64(value) * int64(float32(1)/float32(rate)) — so a sample the
    statsd scalar path admits is never miscounted as poison here, and
    a rate whose f32 reciprocal overflows to inf (rate < ~3e-39) is
    caught before the undefined inf->int64 cast."""
    finite = np.isfinite(vals)
    with np.errstate(divide="ignore", invalid="ignore", over="ignore"):
        recip = np.where((rates > 0) & np.isfinite(rates),
                         np.float32(1.0) / rates.astype(np.float32),
                         np.inf)
    rate_ok = np.isfinite(recip)
    mult = np.trunc(np.where(rate_ok, recip, 1.0)).astype(np.float64)
    # the bound backs off from 2^63 by more than f64's representation
    # spacing there (2^10): a float-compared product a hair past the
    # boundary must quarantine, never silently wrap int64
    inrange = (np.abs(np.trunc(vals)) * np.maximum(mult, 1.0)
               < COUNTER_CONTRIB_MAX - 4096.0)
    ok = finite & rate_ok & inrange
    if quarantine is not None and not ok.all():
        n_nf = int((~finite).sum())
        n_br = int((finite & ~rate_ok).sum())
        n_or = int((finite & rate_ok & ~inrange).sum())
        if n_nf:
            quarantine.count("not_finite", n_nf)
        if n_br:
            quarantine.count("bad_rate", n_br)
        if n_or:
            quarantine.count("out_of_range", n_or)
    return ok


def _scrub_float_batch(quarantine, vals, abs_max=None,
                       weights=None) -> np.ndarray:
    """Admissibility mask for bulk float samples. Gauges (float64
    host-side) pass abs_max=None; digest staging passes
    abs_max=F32_ABS_MAX plus the 1/rate weights — for already-f32
    inputs the range check is redundant with isfinite (an overflow is
    inf by then), but it keeps a future float64 caller from laundering
    1e308 into the planes."""
    finite = np.isfinite(vals)
    ok = finite
    n_or = 0
    if abs_max is not None:
        inr = np.abs(vals) <= abs_max
        n_or = int((finite & ~inr).sum())
        ok = ok & inr
    n_br = 0
    if weights is not None:
        wok = np.isfinite(weights) & (weights > 0)
        n_br = int((ok & ~wok).sum())
        ok = ok & wok
    if quarantine is not None:
        n_nf = int((~finite).sum())
        if n_nf:
            quarantine.count("not_finite", n_nf)
        if n_or:
            quarantine.count("out_of_range", n_or)
        if n_br:
            quarantine.count("bad_rate", n_br)
    return ok


class OverloadLimited:
    """Bounded-cardinality + quarantine plumbing every store group
    shares. All knobs are class-attribute defaults (unbounded, inert):
    ``MetricStore`` stamps the instance attributes at construction and
    re-stamps each generation's fresh twin at the flush swap, so groups
    constructed directly (tests, benches) behave exactly as before.

    Past ``max_series`` (which INCLUDES the overflow row itself) — or
    while the overload controller freezes first-sight series — new
    series collapse into one per-group overflow row named
    ``veneur.overload.overflow`` tagged ``group:<name>``: counts are
    preserved and flushed, identities are dropped, and the slab/dense
    planes stop growing (the pow2 grow ladder cannot be recompile-churned
    by a cardinality flood). ``veneur.``-prefixed self-metrics are
    exempt from the FREEZE (they are the operator's only view into the
    overload) but not from the hard cap."""

    max_series = 0          # 0 = unbounded
    overflow_label = ""     # group attr name, tags the overflow row
    _overflow_type = "gauge"
    _overflow_row = -1
    spilled = 0             # samples absorbed by the overflow row
    scrubbed = 0            # samples quarantined at the group boundary
    _overload = None        # overload.OverloadController
    _quarantine = None      # overload.Quarantine (shared ledger)
    _compute = None         # resilience.compute.ComputeBreaker
    # True where the group's ``_row`` is ``_intern_row`` plus a capacity
    # grow and nothing else, so ``intern_many`` may intern a batch
    # without it; a group whose ``_row`` acts on each new row (a mesh's
    # shard routing, a top-k sid, a tiered dense slot) leaves it False
    _bulk_intern = False

    def _intern_row(self, key: MetricKey, tags: List[str]) -> int:
        """Interner hit -> its row; first-sight -> a fresh row, or the
        overflow row past the cap / under an admission freeze. Callers
        still grow capacity when the returned row is new."""
        interner = self.interner
        row = interner.rows.get(key)
        if row is not None:
            return row
        ms = self.max_series
        if ms and len(interner) >= (ms if self._overflow_row >= 0
                                    else ms - 1):
            return self._spill_row()
        ctl = self._overload
        if (ctl is not None and ctl.freeze_new_series()
                and not freeze_exempt(key.name)):
            return self._spill_row()
        return interner.intern(key, tags)

    @requires_lock("store")
    def intern_many(self, keys: List[MetricKey],
                    tags: List[List[str]]) -> List[int]:
        """``_row`` of each key in order, in one pass where nothing can
        spill: the group interns plainly (``_bulk_intern``), the cap
        leaves room for every key being new, and the overload
        controller is not freezing first-sight series (read once for
        the batch). Otherwise each key goes through ``_row``, so spill,
        freeze and a group's own per-row work are the same row for row.
        The one pass grows no capacity: the caller grows it to the
        largest row before staging, as the bulk paths do."""
        interner = self.interner
        ms = self.max_series
        ctl = self._overload
        if (not self._bulk_intern
                or (ms and len(interner) + len(keys)
                    > (ms if self._overflow_row >= 0 else ms - 1))
                or (ctl is not None and ctl.freeze_new_series())):
            row = self._row
            return [row(k, t) for k, t in zip(keys, tags)]
        rows = interner.rows
        setdefault = rows.setdefault
        names, row_tags, joined = (interner.names, interner.tags,
                                   interner.joined)
        out = []
        for key, t in zip(keys, tags):
            n = len(rows)
            row = setdefault(key, n)
            if row == n:
                names.append(key.name)
                row_tags.append(t)
                joined.append(key.joined_tags)
            out.append(row)
        return out

    def _spill_row(self) -> int:
        if self._overflow_row < 0:
            tag = f"group:{self.overflow_label or 'unknown'}"
            okey = MetricKey(name=OVERFLOW_NAME, type=self._overflow_type,
                             joined_tags=tag)
            self._overflow_row = self.interner.intern(okey, [tag])
        self.spilled += 1
        return self._overflow_row

    def _quarantine_samples(self, reason: str, n: int = 1) -> None:
        self.scrubbed += n
        q = self._quarantine
        if q is not None:
            q.count(reason, n)

    def _pallas_allowed(self) -> bool:
        """Staging drains stay off the Pallas kernel while its breaker
        is not closed (never consumes the half-open probe — only the
        flush path probes)."""
        c = self._compute
        return c is None or not c.degraded()


def _note_rung(compute, kernel: bool) -> None:
    """Record which program actually ran: ``pallas`` only when the fused
    kernel was admitted into it, ``xla`` otherwise — in the timeline
    and on the breaker (``/debug/vars`` overload.compute.last_rung)."""
    rung = "pallas" if kernel else "xla"
    obs_rec.note(rung=rung)
    if compute is not None:
        compute.last_rung = rung


def run_compute_ladder(compute, attempt, plane):
    """The flush-kernel ladder shared by the dense and slab digest
    groups (resilience/compute.py): ``attempt(use_pallas)`` runs one
    complete device-program-plus-fetch pass. Pallas rung while the
    breaker is closed (or as its half-open probe) → XLA rung; raises
    only once BOTH rungs fail (the store's re-merge rung follows).
    ``plane`` is the [S, K] digest plane as the ops see it: the rung is
    reported as ``pallas`` only where ``pallas_ok`` admits the kernel
    for it, the same predicate the ops consult at trace time.

    Honesty note on rung 2's reach: the flush programs DONATE their
    device inputs, so on a backend that honors donation a failure
    mid-execution (true TPU preemption) consumes them and the retry —
    and the re-merge snapshot — fail too; the interval then degrades to
    PR 2's checkpoint bound. Rung 2 fully covers the failures that
    raise BEFORE execution: Mosaic compile errors after a config
    change, injected preflight faults, and trace-time errors."""
    kernel = tdigest_pallas.pallas_ok(plane)
    if compute is None:
        out = attempt(True)
        _note_rung(compute, kernel)
        return out
    if compute.probe():
        try:
            compute.preflight()
            out = attempt(True)
            compute.record_success()
            _note_rung(compute, kernel)
            return out
        except Exception:
            compute.record_failure()
            log.warning("digest flush kernel failed; re-running this "
                        "interval on the XLA fallback path",
                        exc_info=True)
    out = attempt(False)
    compute.count_fallback()
    _note_rung(compute, False)
    return out


def begin_compute_ladder(compute, dispatch, collect, plane):
    """Two-phase twin of :func:`run_compute_ladder` for the pipelined
    flush: ``dispatch(use_pallas)`` (async device-program enqueue) runs
    NOW on the first viable rung, and the returned ``finish()`` runs
    ``collect(pending, use_pallas)`` — the blocking device→host fetch —
    later, so the caller can dispatch every group before blocking on
    any. Failure semantics are identical rung for rung: a dispatch or
    collect failure on the Pallas rung records the breaker failure and
    re-runs the COMPLETE attempt (dispatch + collect) on the XLA rung
    inside ``finish``; only a double failure raises (the store's
    re-merge rung follows). Same donation caveat, and the same
    ``plane`` predicate for the reported rung, as the one-phase
    ladder."""
    kernel = tdigest_pallas.pallas_ok(plane)
    pending = None
    pallas = False
    if compute is None:
        pending = dispatch(True)
        pallas = True
    elif compute.probe():
        try:
            compute.preflight()
            pending = dispatch(True)
            pallas = True
        except Exception:
            compute.record_failure()
            log.warning("digest flush kernel failed at dispatch; this "
                        "interval will run on the XLA fallback path",
                        exc_info=True)

    def finish():
        if pallas:
            if compute is None:
                out = collect(pending, True)
                _note_rung(compute, kernel)
                return out
            try:
                out = collect(pending, True)
                compute.record_success()
                _note_rung(compute, kernel)
                return out
            except Exception:
                compute.record_failure()
                log.warning("digest flush kernel failed; re-running "
                            "this interval on the XLA fallback path",
                            exc_info=True)
        out = collect(dispatch(False), False)
        compute.count_fallback()
        _note_rung(compute, False)
        return out

    return finish


@bucketed("pow2")
def live_bucket(n: int, capacity: int) -> int:
    """Rows a flush or snapshot takes off the device for ``n`` live
    ones: the count's pow2 bucket, at most the rows there are. The cut
    to ``n`` is the host's, after the fetch (``cut_rows``)."""
    return min(pow2_cap(n), capacity)


@partial(jax.jit, static_argnums=(1,))
def _prefix_rows(arrays, rows: int):
    """The first ``rows`` rows of every array, as fresh buffers, in one
    program a bucket. Sliced op by op at the live count itself
    (``x[:n]``) every new count compiled a program a shape inside the
    flush: under churn no two intervals share one, and two compiles
    made ``store.dispatch.histograms.compute`` 0.183-0.191 s (PERF.md,
    PR 39)."""
    return tuple(a[:rows] for a in arrays)


def cut_rows(fetched, n: int):
    """What ``_prefix_rows`` brought, cut to the ``n`` live rows on the
    host."""
    return tuple(a[:n] for a in fetched)


@contextmanager
def fetch_stage(refs):
    """One group's ``fetch`` stage around the caller's
    ``jax.device_get``, in two leaves: ``fetch.wait``, the wait for the
    device to have produced ``refs``, which the ``device_get`` would
    otherwise absorb unseen, then ``fetch.copy`` (and its host scope in
    a profiler capture), the transfer itself with the caller's cut."""
    with obs_rec.maybe_stage("fetch"):
        with obs_rec.maybe_stage("wait"):
            jax.block_until_ready(refs)
        with obs_rec.maybe_stage("copy", scope=True):
            yield


def _no_stage(name: str, scope: bool = False):
    """``obs_rec.maybe_stage``'s shape, recording nothing: for a caller
    that is a leaf as a whole."""
    return nullcontext()


# ---------------------------------------------------------------------------
# Host-side scalar groups
# ---------------------------------------------------------------------------


class ScalarGroup(OverloadLimited):
    """Counters / gauges / status checks: host numpy state.

    kind: "counter" (int64 accumulate, samplers.go:141-143),
    "gauge" (float64 last-write, samplers.go:225-227),
    "status" (gauge + message/hostname, samplers.go:307-313).
    """

    _retired = False  # see DigestGroup._retired
    _bulk_intern = True

    def __init__(self, kind: str, capacity: int = DEFAULT_INITIAL_CAPACITY):
        self.kind = kind
        self.interner = Interner()
        self.capacity = capacity
        if kind == "counter":
            self.values = np.zeros(capacity, np.int64)
        else:
            self.values = np.zeros(capacity, np.float64)
        self.messages: Optional[List[str]] = [] if kind == "status" else None
        self.hostnames: Optional[List[str]] = [] if kind == "status" else None
        if kind == "status":
            self._bulk_intern = False  # _row grows messages a new row

    def __len__(self):
        return len(self.interner)

    @requires_lock("store")
    def _row(self, key: MetricKey, tags: List[str]) -> int:
        row = self._intern_row(key, tags)
        if row >= self.capacity:
            self.capacity *= _GROW_FACTOR
            self.values = np.concatenate(
                [self.values, np.zeros(self.capacity - len(self.values),
                                       self.values.dtype)])
        if self.messages is not None and row >= len(self.messages):
            self.messages.append("")
            self.hostnames.append("")
        return row

    @requires_lock("store")
    def sample(self, key: MetricKey, tags: List[str], value: float,
               sample_rate: float, message: str = "", hostname: str = ""):
        # defensive numerics quarantine: the parser rejects these on the
        # statsd/SSF lanes, but samples also arrive via restore/import
        # shims — a NaN gauge or an int64-overflowing counter must never
        # reach state (numpy raises OverflowError on the latter)
        if not math.isfinite(value):
            self._quarantine_samples("not_finite")
            return
        if self.kind == "counter":
            # Go semantics: value += int64(sample) * int64(1/rate)
            # (samplers.go:141-143) — both factors truncate toward zero,
            # and the reciprocal is a float32 division (UDPMetric's
            # SampleRate is float32), matching the native batch path.
            # The rate is bounded BEFORE the reciprocal: a denormal-tiny
            # rate underflows f32, 1/rate overflows to inf, and int(inf)
            # raises OverflowError — one poisoned packet would kill the
            # reader thread
            if not MIN_SAMPLE_RATE <= sample_rate <= 1:
                self._quarantine_samples("bad_rate")
                return
            contrib = (int(value)
                       * int(np.float32(1.0) / np.float32(sample_rate)))
            if abs(contrib) >= COUNTER_CONTRIB_MAX:
                self._quarantine_samples("out_of_range")
                return
            # _row may grow (replace) the values array: resolve it first
            row = self._row(key, tags)
            self.values[row] += contrib
        else:
            row = self._row(key, tags)
            self.values[row] = value
            if self.messages is not None:
                self.messages[row] = message
                self.hostnames[row] = hostname

    @requires_lock("store")
    def ensure_capacity(self, max_row: int):
        """Grow so max_row is addressable (bulk paths bypass _row)."""
        while max_row >= self.capacity:
            self.capacity *= _GROW_FACTOR
        if self.capacity > len(self.values):
            self.values = np.concatenate(
                [self.values, np.zeros(self.capacity - len(self.values),
                                       self.values.dtype)])

    @requires_lock("store")
    def add_many(self, rows: np.ndarray, contribs: np.ndarray):
        """Bulk counter accumulate (native ingest path); contribs already
        carry the truncating int64(value) * int64(1/rate) Go semantics."""
        np.add.at(self.values, rows, contribs)

    @requires_lock("store")
    def set_many(self, rows: np.ndarray, vals: np.ndarray):
        """Bulk gauge write, last-write-wins per row in input order."""
        # np fancy assignment leaves duplicate-index order unspecified, so
        # pick each row's last value explicitly
        urows, last = np.unique(rows[::-1], return_index=True)
        self.values[urows] = vals[::-1][last]

    @requires_lock("store")
    def combine(self, key: MetricKey, tags: List[str], value: float):
        """Merge imported state: counters add, gauges/status overwrite
        (samplers.go:195-212, 276-289)."""
        if not math.isfinite(value):
            self._quarantine_samples("not_finite")
            return
        row = self._row(key, tags)
        if self.kind == "counter":
            if abs(value) >= COUNTER_CONTRIB_MAX:
                self._quarantine_samples("out_of_range")
                return
            self.values[row] += int(value)
        else:
            self.values[row] = value

    def snapshot_and_reset(self):
        n = len(self.interner)
        interner, self.interner = self.interner, Interner()
        values = self.values[:n].copy()
        if not self._retired:
            # a retired group is never written again and goes with its
            # generation: zeroing it would fault in every reserved page
            # of an array about to be freed (32 MiB at 2^22 rows)
            self.values[:] = 0
        messages = hostnames = None
        if self.messages is not None:
            messages, self.messages = self.messages, []
            hostnames, self.hostnames = self.hostnames, []
        return interner, values, messages, hostnames

    def flush_begin(self):
        """Two-phase flush slot: scalar state is host numpy, so the
        snapshot IS the whole flush — it runs eagerly and ``finish()``
        just hands it back. Every group exposes the same begin/finish
        surface; the store's scalar drain (``_flush_scalars``) goes
        through it like the device groups go through theirs."""
        res = self.snapshot_and_reset()
        return lambda: res

    @requires_lock("store")
    def snapshot_begin(self):
        """Phase 1 of the two-phase checkpoint snapshot (the caller
        holds the store lock): scalar state is host numpy, so the copy
        itself is the whole snapshot — no off-lock fetch phase. Returns
        ``(snap, None)`` matching the device groups' contract."""
        n = len(self.interner)
        snap = {"kind": "scalar", "names": list(self.interner.names),
                "joined": list(self.interner.joined),
                "values": self.values[:n].copy()}
        if self.messages is not None:
            snap["messages"] = list(self.messages[:n])
            snap["hostnames"] = list(self.hostnames[:n])
        return snap, None

    @requires_lock("store")
    def snapshot_state(self) -> dict:
        """Host copy of the live group WITHOUT resetting it (the
        checkpoint path, veneur_tpu/persist/): the caller holds the
        store lock, so the copies are interval-coherent."""
        return self.snapshot_begin()[0]

    def fresh(self) -> "ScalarGroup":
        """Empty same-config twin (swap-on-flush generation swap)."""
        return ScalarGroup(self.kind, self.capacity)


# ---------------------------------------------------------------------------
# Device-side digest groups (histograms and timers)
# ---------------------------------------------------------------------------


@partial(jax.jit, donate_argnums=(0, 1), static_argnums=(7, 8))
def _ingest_samples(digest: td_ops.TDigest, temp: td_ops.TempCentroids,
                    rows, values, weights, drained, trips, compression,
                    use_pallas=True):
    """The sample path's ingest (ops/tdigest.py ingest_chunk_rowdrained):
    the chunk's rows that hold bin mass and few samples are drained
    into their digests before more is binned into them, and behind that
    the shift guard drains every bin where the distribution steps, so
    neither sparse nor ordered/shifting arrival aliases values across
    bins. ``drained`` and ``trips`` (int32 scalars) count the rows
    drained and the drain loop's trips. ``use_pallas`` is a trace-time
    static: False keeps the drains on the XLA path while the compute
    breaker is open."""
    digest, temp, n = td_ops.ingest_chunk_rowdrained(
        digest, temp, rows, values, weights, compression,
        use_pallas=use_pallas)
    return (digest, temp, drained + n,
            trips + td_ops.row_drain_trips(n, rows.shape[0]))


@partial(jax.jit, donate_argnums=(0, 1, 2, 3), static_argnums=(11, 12))
def _ingest_centroids(digest: td_ops.TDigest, temp: td_ops.TempCentroids,
                      dmin, dmax, rows, means,
                      weights, stat_rows, stat_mins, stat_maxs, drains,
                      compression, use_pallas=True):
    """Fold imported digest centroids into the bin accumulators WITHOUT
    touching the local scalar stats (samplers.go:473-480). Imported
    per-digest min/max land in separate dmin/dmax arrays that only bound the
    final digest. A row that already holds bin mass is drained before its
    run is binned (td_ops.ingest_centroids_rowdrained); ``drains`` (an
    int32 scalar) counts the dispatches in which any was."""
    digest, temp, drained = td_ops.ingest_centroids_rowdrained(
        digest, temp, rows, means, weights, compression,
        use_pallas=use_pallas)
    dmin = dmin.at[stat_rows].min(stat_mins, mode="drop")
    dmax = dmax.at[stat_rows].max(stat_maxs, mode="drop")
    return digest, temp, dmin, dmax, drains + drained


@partial(jax.jit, donate_argnums=(0, 1), static_argnums=(6, 7))
def _flush_digests(digest: td_ops.TDigest, temp: td_ops.TempCentroids,
                   dmin, dmax, qs, n, compression, use_pallas=True):
    """The per-interval flush program: one compress + one batched quantile
    gather for the whole group (the Histo.Flush hot loop of
    samplers.go:511-636 over all series at once). ``n`` is the
    interval's interned rows as a traced int32 scalar: the program
    works on the slabs that hold rows ``[:n]`` and leaves the rest of
    the reserved rows alone (td_ops.drain_and_quantile), one compiled
    variant whatever ``n``. ``use_pallas=False`` is the compute
    breaker's fallback rung: the same math compiled without the fused
    kernel (resilience/compute.py)."""
    drained, pcts = td_ops.drain_and_quantile(digest, temp, dmin, dmax, qs,
                                              compression,
                                              use_pallas=use_pallas, n=n)
    return (drained, pcts, temp.count, temp.vsum, temp.vmin, temp.vmax,
            temp.recip)


@jax.jit
def _restore_temp_stats(temp, rows, count, vsum, vmin, vmax, recip):
    """Scatter a recovered interval's per-row scalar stats back into the
    temp accumulators (checkpoint restore). The centroid half of a
    restore rides the import path, which deliberately skips these
    (update_stats=False, samplers.go:473-480); without this hook a warm
    restart would keep the percentiles but lose the .count/.min/.max/
    .sum/.hmean emissions of the recovered samples."""
    return temp._replace(
        count=temp.count.at[rows].add(count, mode="drop"),
        vsum=temp.vsum.at[rows].add(vsum, mode="drop"),
        vmin=temp.vmin.at[rows].min(vmin, mode="drop"),
        vmax=temp.vmax.at[rows].max(vmax, mode="drop"),
        recip=temp.recip.at[rows].add(recip, mode="drop"),
    )


def flatten_digest_state(mean: np.ndarray, weight: np.ndarray,
                         bin_w: np.ndarray, bin_wm: np.ndarray) -> dict:
    """Flatten [n, K] digest planes plus [n, K] pending temp bins into
    per-row centroid runs sorted by (row, mean) — the exact layout
    ``bulk_stage_import_centroids`` expects back at restore time.
    Pending bins become centroids at (sum_wm/sum_w, sum_w), which is
    how a drain would cluster them anyway."""
    r1, c1 = np.nonzero(weight > 0)
    r2, c2 = np.nonzero(bin_w > 0)
    w2 = bin_w[r2, c2]
    rows = np.concatenate([r1, r2]).astype(np.int32)
    means = np.concatenate([mean[r1, c1],
                            bin_wm[r2, c2] / w2]).astype(np.float64)
    weights = np.concatenate([weight[r1, c1], w2]).astype(np.float64)
    order = np.lexsort((means, rows))
    return {"rows": rows[order], "means": means[order],
            "weights": weights[order]}


@requires_lock("store")
def bulk_stage_import_centroids(group, rows: np.ndarray, means: np.ndarray,
                                weights: np.ndarray, stat_rows,
                                stat_mins, stat_maxs):
    """Shared bulk-import staging protocol for digest groups (dense and
    slab share the ``_imp_*`` buffer layout and drain rules): span copies
    into the import buffers, then drain when either the centroid buffer
    or the stat lists fill.

    Drains align to ROW-RUN boundaries: a row's centroids arrive as one
    sorted-by-mean run, and splitting that run across two staging
    drains hands each drain a systematically skewed half — the
    per-chunk quantile binning then aliases the halves into the same
    bins (a single straddling row is far below the aggregate shift
    guard's threshold). Runs longer than the whole chunk (can't happen
    for digests: a run is <= K centroids << chunk) would fall back to
    splitting."""
    n = len(rows)
    # equal-row run boundaries (each run = one digest's sorted
    # centroids), so span copies stay O(n/chunk), not O(runs)
    if n:
        run_ends = np.concatenate(
            (np.flatnonzero(rows[1:] != rows[:-1]) + 1, [n]))
    else:
        run_ends = np.empty(0, np.int64)
    start = 0
    while start < n:
        if group._imp_fill == group.chunk:
            group._drain_imports()
        avail = group.chunk - group._imp_fill
        limit = start + avail
        if limit >= n:
            end = n
        else:
            # largest run boundary that fits; a run longer than the
            # remaining space drains first (partial buffer) or, when
            # longer than a whole chunk, splits as a last resort
            j = int(np.searchsorted(run_ends, limit, "right"))
            end = int(run_ends[j - 1]) if j > 0 else 0
            if end <= start:
                if avail < group.chunk:
                    group._drain_imports()
                    continue
                end = limit
        take = end - start
        i = group._imp_fill
        group._imp_rows[i:i + take] = rows[start:start + take]
        group._imp_means[i:i + take] = means[start:start + take]
        group._imp_wts[i:i + take] = weights[start:start + take]
        group._imp_fill = i + take
        start = end
    # stat triples stage in chunk-bounded spans too: one oversized drain
    # would pad the stat arrays past the bounded pow2 ladder and compile
    # a one-off _ingest_centroids variant (~20s each on TPU)
    ns = len(stat_rows)
    pos = 0
    while pos < ns:
        if group._imp_stat_fill == group.chunk:
            group._drain_imports()
        take = min(group.chunk - group._imp_stat_fill, ns - pos)
        i = group._imp_stat_fill
        group._imp_stat_rows[i:i + take] = stat_rows[pos:pos + take]
        group._imp_stat_mins[i:i + take] = stat_mins[pos:pos + take]
        group._imp_stat_maxs[i:i + take] = stat_maxs[pos:pos + take]
        group._imp_stat_fill = i + take
        pos += take
    if (group._imp_fill == group.chunk
            or group._imp_stat_fill == group.chunk):
        group._drain_imports()


class DigestGroup(OverloadLimited):
    """One scope-class of histograms/timers as a dense t-digest batch."""

    # set by MetricStore._swap_generation: a retired group's flush drops
    # its device state instead of reallocating it (the group is never
    # used again), keeping the swap-on-flush HBM peak at the old
    # in-place-reset level instead of 3 planes (retired + fresh twin +
    # pointless post-flush reinit)
    _retired = False
    _bulk_intern = True

    def __init__(self, capacity: int = DEFAULT_INITIAL_CAPACITY,
                 chunk: int = DEFAULT_CHUNK,
                 compression: float = td_ops.DEFAULT_COMPRESSION):
        self.interner = Interner()
        self.capacity = capacity
        self.chunk = chunk
        self.compression = compression
        self.k = td_ops.size_bound(compression)
        self._device_dirty = False
        # the import path's counters of this generation (read at its
        # flush: timeline ``import_digests``) and the ns its drains
        # spent routing a chunk to shards and dispatching it (summed by
        # the store into the ``import.route`` / ``import.dispatch``
        # stages); ``_imp_drains`` is the device's own count of the
        # dispatches that drained a row before binning
        self.imp_dispatches = 0
        self.imp_centroids = 0
        self.imp_route_ns = 0
        self.imp_dispatch_ns = 0
        self._imp_drains = None
        # the sample path's: its dispatches, and the device's own
        # counts of the rows they drained before binning into them and
        # of the drain loop's trips (timeline ``ingest_samples``)
        self.smp_dispatches = 0
        self._row_drains = None
        self._init_staging()

    _DEVICE_STATE = ("temp", "digest", "dmin", "dmax")

    def __getattr__(self, name):
        """Device state is allocated on first touch, not at
        construction: a scope-class this deployment never writes
        (timers, the local-only twins) and every flush's fresh twin
        hold no accelerator memory until a sample arrives. At
        ``store_initial_capacity: 1048576`` a dense group is 1.8 GB of
        planes; four idle ones, twice over at the generation swap, do
        not fit one chip. (``_drop_device`` leaves the names bound to
        None, so a retired group never re-allocates.)"""
        if name in DigestGroup._DEVICE_STATE:
            dirty = self._device_dirty
            self._init_device()
            self._device_dirty = dirty
            return self.__dict__[name]
        raise AttributeError(name)

    def _init_device(self):
        self.temp = td_ops.init_temp(self.capacity, self.k, self.compression)
        self.digest = td_ops.init((self.capacity,), self.compression, self.k)
        self.dmin = jnp.full((self.capacity,), jnp.inf, jnp.float32)
        self.dmax = jnp.full((self.capacity,), -jnp.inf, jnp.float32)
        self._device_dirty = False

    def _init_staging(self):
        self._new_sample_buffers()
        self._new_import_buffers()

    def _new_sample_buffers(self):
        # Fresh buffers per drain: jnp.asarray zero-copies aligned numpy
        # arrays and dispatch is async, so a buffer handed to the device
        # must never be written again from the host.
        self._rows = np.full(self.chunk, self.capacity, np.int32)
        self._vals = np.zeros(self.chunk, np.float32)
        self._wts = np.zeros(self.chunk, np.float32)
        self._fill = 0

    def _new_import_buffers(self):
        self._imp_rows = np.full(self.chunk, self.capacity, np.int32)
        self._imp_means = np.zeros(self.chunk, np.float32)
        self._imp_wts = np.zeros(self.chunk, np.float32)
        self._imp_fill = 0
        # stat triples as preallocated numpy, not Python lists: a 20k-
        # digest import message would otherwise pay ~20k list appends +
        # a list->array conversion per drain (the global-import hot
        # path). Sentinel padding (out-of-range row, +inf/-inf extrema)
        # doubles as the pow2 drain padding.
        self._imp_stat_rows = np.full(self.chunk, self.capacity, np.int32)
        self._imp_stat_mins = np.full(self.chunk, np.inf, np.float32)
        self._imp_stat_maxs = np.full(self.chunk, -np.inf, np.float32)
        self._imp_stat_fill = 0

    def __len__(self):
        return len(self.interner)

    @requires_lock("store")
    def _row(self, key: MetricKey, tags: List[str]) -> int:
        row = self._intern_row(key, tags)
        if row >= self.capacity:
            self._grow()
        return row

    def _grow(self):
        self._drain_staging()
        old = self.capacity
        self.capacity *= _GROW_FACTOR
        pad = self.capacity - old
        # nothing placed yet: the first touch allocates at the new size
        if "temp" in self.__dict__:
            self.temp = td_ops.grow_temp(self.temp, pad)
            self.digest = td_ops.TDigest(
                mean=jnp.pad(self.digest.mean, ((0, pad), (0, 0)),
                             constant_values=np.inf),
                weight=jnp.pad(self.digest.weight, ((0, pad), (0, 0))),
                min=jnp.pad(self.digest.min, (0, pad),
                            constant_values=np.inf),
                max=jnp.pad(self.digest.max, (0, pad),
                            constant_values=-np.inf),
            )
            self.dmin = jnp.pad(self.dmin, (0, pad), constant_values=np.inf)
            self.dmax = jnp.pad(self.dmax, (0, pad), constant_values=-np.inf)
        # re-point staging padding at the new out-of-range row id
        self._rows[self._fill:] = self.capacity
        self._imp_rows[self._imp_fill:] = self.capacity
        self._imp_stat_rows[self._imp_stat_fill:] = self.capacity

    @requires_lock("store")
    def ensure_capacity(self, max_row: int):
        """Grow so max_row is addressable (bulk paths bypass _row)."""
        while max_row >= self.capacity:
            self._grow()

    def fresh(self) -> "DigestGroup":
        """Empty same-config twin (swap-on-flush generation swap).
        Carries the grown capacity so a steady-state cardinality never
        re-grows interval over interval."""
        return DigestGroup(self.capacity, self.chunk, self.compression)

    @requires_lock("store")
    def sample_many(self, rows: np.ndarray, vals: np.ndarray,
                    wts: np.ndarray):
        """Bulk staging append for the native ingest path: one numpy copy
        per chunk span instead of a Python call per sample. Non-finite
        values/weights are scrubbed here — after the f32 cast, so a
        1e308 that became inf is caught too — rather than laundered
        into digest state."""
        ok = _scrub_float_batch(self._quarantine, vals,
                                abs_max=F32_ABS_MAX, weights=wts)
        nbad = len(rows) - int(ok.sum())
        if nbad:
            self.scrubbed += nbad
            rows, vals, wts = rows[ok], vals[ok], wts[ok]
        n = len(rows)
        start = 0
        while start < n:
            if self._fill == self.chunk:
                self._drain_samples()
            take = min(self.chunk - self._fill, n - start)
            i = self._fill
            self._rows[i:i + take] = rows[start:start + take]
            self._vals[i:i + take] = vals[start:start + take]
            self._wts[i:i + take] = wts[start:start + take]
            self._fill = i + take
            start += take
        if self._fill == self.chunk:
            self._drain_samples()

    @requires_lock("store")
    def sample(self, key: MetricKey, tags: List[str], value: float,
               sample_rate: float):
        # numerics quarantine (defense in depth behind the parser): a
        # NaN/Inf or f32-overflowing value would poison the digest's
        # centroid means; a rate outside [MIN_SAMPLE_RATE, 1] yields a
        # non-finite or non-positive f32 weight
        if not math.isfinite(value) or abs(value) > F32_ABS_MAX:
            self._quarantine_samples(
                "not_finite" if not math.isfinite(value)
                else "out_of_range")
            return
        if not MIN_SAMPLE_RATE <= sample_rate <= 1:
            self._quarantine_samples("bad_rate")
            return
        row = self._row(key, tags)
        i = self._fill
        self._rows[i] = row
        self._vals[i] = value
        # float32 reciprocal, bit-identical to the native batch path
        self._wts[i] = np.float32(1.0) / np.float32(sample_rate)
        self._fill = i + 1
        if self._fill == self.chunk:
            self._drain_samples()

    @requires_lock("store")
    def import_centroids(self, key: MetricKey, tags: List[str],
                         means: np.ndarray, weights: np.ndarray,
                         dmin: float, dmax: float):
        """Merge a forwarded digest: its centroids re-enter the binning
        pipeline as weighted samples, which is exactly the reference's
        Merge-by-re-adding-centroids (merging_digest.go:358-370) without
        the shuffle."""
        row = self._row(key, tags)
        n = len(means)
        # keep one digest's sorted centroid run inside one staging
        # drain: a split run hands each drain a skewed half that the
        # per-chunk binning aliases (see bulk_stage_import_centroids)
        if self._imp_fill + n > self.chunk and n <= self.chunk:
            self._drain_imports()
        start = 0
        while start < n:  # digests larger than one chunk span several drains
            if self._imp_fill == self.chunk:
                self._drain_imports()
            take = min(self.chunk - self._imp_fill, n - start)
            i = self._imp_fill
            self._imp_rows[i:i + take] = row
            self._imp_means[i:i + take] = means[start:start + take]
            self._imp_wts[i:i + take] = weights[start:start + take]
            self._imp_fill = i + take
            start += take
        if math.isfinite(dmin):
            i = self._imp_stat_fill
            self._imp_stat_rows[i] = row
            self._imp_stat_mins[i] = dmin
            self._imp_stat_maxs[i] = dmax
            self._imp_stat_fill = i + 1
            # zero-centroid imports never advance _imp_fill, so the stat
            # buffers need their own drain bound (the mesh drain scatters
            # them through fixed chunk-sized buffers)
            if self._imp_stat_fill == self.chunk:
                self._drain_imports()

    @requires_lock("store")
    def import_centroids_bulk(self, rows: np.ndarray, means: np.ndarray,
                              weights: np.ndarray, stat_rows,
                              stat_mins, stat_maxs):
        """Bulk staging append for the import path (rows pre-interned by
        the caller): span copies into the import buffers instead of a
        Python call per digest."""
        bulk_stage_import_centroids(self, rows, means, weights, stat_rows,
                                    stat_mins, stat_maxs)

    def _drain_samples(self):
        if self._fill == 0:
            return
        self._device_dirty = True
        self.smp_dispatches += 1
        rows, vals, wts = self._rows, self._vals, self._wts
        self._new_sample_buffers()
        drained, trips = self._row_drains or (np.int32(0), np.int32(0))
        with obs_kernels.scope("drain.digest.dense"):
            self.digest, self.temp, drained, trips = _ingest_samples(
                self.digest, self.temp, jnp.asarray(rows),
                jnp.asarray(vals), jnp.asarray(wts), drained, trips,
                self.compression, self._pallas_allowed())
        self._row_drains = (drained, trips)

    def _drain_imports(self):
        if self._imp_fill == 0 and self._imp_stat_fill == 0:
            return
        self._device_dirty = True
        ns = self._imp_stat_fill
        # pad the stat arrays to a power-of-two bucket: every distinct
        # length would otherwise compile its own _ingest_centroids
        # variant (~20s each on TPU) — bulk imports produce a different
        # ns per batch phase. The staged buffers are pre-filled with
        # identity sentinels (row=capacity, +inf/-inf), so a pow2 prefix
        # slice IS the padded array.
        cap = pow2_cap(ns)
        stat_rows = self._imp_stat_rows[:cap]
        stat_mins = self._imp_stat_mins[:cap]
        stat_maxs = self._imp_stat_maxs[:cap]
        imp_rows, imp_means, imp_wts = (self._imp_rows, self._imp_means,
                                        self._imp_wts)
        self.imp_dispatches += 1
        self.imp_centroids += self._imp_fill
        self._new_import_buffers()
        t0 = time.monotonic_ns()
        with obs_kernels.scope("drain.digest.dense"):
            (self.digest, self.temp, self.dmin, self.dmax,
             self._imp_drains) = _ingest_centroids(
                    self.digest, self.temp, self.dmin, self.dmax,
                    jnp.asarray(imp_rows), jnp.asarray(imp_means),
                    jnp.asarray(imp_wts), jnp.asarray(stat_rows),
                    jnp.asarray(stat_mins), jnp.asarray(stat_maxs),
                    np.int32(0) if self._imp_drains is None
                    else self._imp_drains,
                    self.compression, self._pallas_allowed())
        self.imp_dispatch_ns += time.monotonic_ns() - t0

    def _drain_staging(self):
        self._drain_samples()
        self._drain_imports()

    def _run_flush(self, qs, use_pallas: bool, n: int):
        """Execute the jitted flush program over the ``n`` live rows
        (override point for the mesh-sharded store; ``use_pallas=False``
        is the compute breaker's fallback rung — same math, no fused
        kernel). ``n`` goes in as a device scalar, so every interval
        runs the one compiled variant."""
        obs_rec.note(rows_live=n,
                     rows_run=td_ops.flush_rows_run(self.capacity, n))
        return _flush_digests(self.digest, self.temp, self.dmin, self.dmax,
                              qs, np.int32(n), self.compression, use_pallas)

    def flush(self, percentiles: List[float], want_digests=True,
              want_stats=None):
        """Run the flush program; returns (interner, host result dict) and
        resets the group.

        want_digests=False skips fetching the [n, K] mean/weight planes —
        only a FORWARDING flush needs the digests host-side, and at
        millions of series the planes are the bulk of the transfer.
        want_digests="packed" compacts + quantizes them on device first
        (core/slab.py:_pack_slab) and fetches only the live centroids at
        4 bytes each — see SlabDigestGroup.flush, which also documents
        the ``want_stats`` fetch selection.

        The device half runs behind the compute-breaker ladder
        (resilience/compute.py): a runtime kernel failure retries this
        same interval on the XLA fallback, and only a double failure
        raises — the store then re-merges the generation (rung 3)."""
        self._drain_staging()
        n = len(self.interner)
        if n == 0:
            return self._flush_empty()
        out = run_compute_ladder(
            self._compute,
            lambda use_pallas: self._flush_fetch(
                n, percentiles, want_digests, want_stats, use_pallas),
            self.digest.mean)
        return self._flush_commit(out)

    def flush_begin(self, percentiles: List[float], want_digests=True,
                    want_stats=None):
        """Two-phase flush for the pipelined egress (the overlapped
        twin of :meth:`flush`, same contract once finished): drain
        staging and DISPATCH the flush program asynchronously NOW, and
        return a ``finish()`` whose blocking ``jax.device_get`` runs
        later — so the store can dispatch every retired group before
        any fetch blocks, and group k+1's device execution overlaps
        group k's host transfer. ``finish()`` returns ``(interner,
        out)`` and only then resets the group; the compute-breaker
        ladder retries inside ``finish`` per group
        (:func:`begin_compute_ladder`), and a double failure raises
        with the group state intact for the store's re-merge rung."""
        with obs_rec.maybe_stage("drain"):
            self._drain_staging()
            self._note_drains()
        n = len(self.interner)
        if n == 0:
            res = self._flush_empty()
            return lambda: res
        fin = begin_compute_ladder(
            self._compute,
            lambda use_pallas: self._flush_dispatch(
                n, percentiles, want_digests, want_stats, use_pallas),
            lambda pending, use_pallas: self._flush_collect(
                pending, n, percentiles, want_digests),
            self.digest.mean)
        return lambda: self._flush_commit(fin())

    def _note_drains(self) -> None:
        """What this generation's drains counted, on the flush's open
        ``drain`` stage (the flusher sums the notes into the timeline
        entry)."""
        if self.imp_dispatches:
            obs_rec.note(import_dispatches=self.imp_dispatches,
                         import_centroids=self.imp_centroids)
        if self._row_drains is not None:
            obs_rec.note(ingest_samples_dispatches=self.smp_dispatches)

    def _guard_counters(self) -> dict:
        """The device's own drain counts, by the name they are noted
        under: fetched with the flush's results, in the one transfer
        (None where the path never ran)."""
        rows, trips = self._row_drains or (None, None)
        return {"import_guard_drains": self._imp_drains,
                "ingest_samples_rows_drained": rows,
                "ingest_samples_drain_trips": trips}

    def _flush_empty(self):
        """The n==0 flush path: skip the flush program AND the
        device->host fetches (each fetch is a full host-device round
        trip)."""
        with obs_rec.maybe_stage("commit", scope=True):
            interner, self.interner = self.interner, Interner()
            if self._retired:
                self._drop_device()
            elif self._device_dirty:
                # bulk paths can stage data without interning; never
                # let it leak into the next interval's rows
                self._init_device()
                self._init_staging()
        return interner, {}

    def _flush_commit(self, out: dict):
        """Interner swap + device reset, only AFTER the device programs
        + fetches succeeded: on a ladder failure the group still holds
        its state for the store's re-merge rung."""
        with obs_rec.maybe_stage("commit", scope=True):
            interner, self.interner = self.interner, Interner()
            if self._retired:
                self._drop_device()
            else:
                self._init_device()
                self._init_staging()
        return interner, out

    def _flush_fetch(self, n: int, percentiles, want_digests, want_stats,
                     use_pallas: bool) -> dict:
        """One complete flush attempt: device program + host fetch into
        the result dict (dispatch and collect composed back to back —
        the one-phase shape the ladder and the tiered dense bank call).
        No group state besides the (donated) device planes is touched,
        so an attempt that failed before execution can be retried."""
        pending = self._flush_dispatch(n, percentiles, want_digests,
                                       want_stats, use_pallas)
        return self._flush_collect(pending, n, percentiles, want_digests)

    def _flush_dispatch(self, n: int, percentiles, want_digests,
                        want_stats, use_pallas: bool):
        """Async half of one flush attempt: enqueue the flush program
        (plus the on-device pack when forwarding packed) and slice out
        the device refs the collect phase fetches. Nothing here blocks
        on device execution."""
        packed = want_digests == "packed"
        from veneur_tpu.core.slab import _select_stats

        sel = _select_stats(want_stats)
        # compute = the program's dispatch (plus any synchronous
        # compile and the quantiles' host->device put); it returns at
        # once. The dispatch PHASE does block, though, one group later:
        # on the v5e the next group that has samples staged
        # (self_timers always has) waits in its drain (flush_begin) for
        # as long as the device still runs what is queued: the
        # interval's last ingest dispatch (0.0044 s since PR 34, more
        # where it drains held rows) and the program enqueued here
        # (0.3 ms for 320 live rows, 0.027 s for 205,280), 0.016 and
        # 0.036 of a 0.025 and 0.045 s store.dispatch (PERF.md section
        # 5). A fresh twin's first touch allocates its planes there,
        # and that host -> device put queues behind the running program
        # (the CPU backend does the same). So fetch, opened by
        # fetch.wait, finds the results ready (fetch.wait 0.1-0.9 ms on
        # the chip).
        with obs_rec.maybe_stage("compute"), \
                obs_kernels.scope("flush.digest.dense"):
            qs = jnp.asarray(list(percentiles) + [0.5], jnp.float32)
            digest, pcts, count, vsum, vmin, vmax, recip = self._run_flush(
                qs, use_pallas, n)
            planes = ()
            packed_refs = None
            if packed:
                from veneur_tpu.core.slab import _pack_slab

                packed_refs = _pack_slab(
                    digest.mean.reshape(-1), digest.weight.reshape(-1),
                    digest.min, digest.max, self.capacity, self.k)
                planes = (digest.min, digest.max)
            elif want_digests:
                planes = (digest.mean, digest.weight, digest.min,
                          digest.max)
            stats = {"pcts": pcts, "count": count, "sum": vsum,
                     "min": vmin, "max": vmax, "recip": recip}
            # the count's pow2 bucket of rows; _flush_collect cuts what
            # it fetched back to n
            refs = _prefix_rows(planes + tuple(stats[nm] for nm in sel),
                                live_bucket(n, self.capacity))
        return (sel, packed, packed_refs, refs)

    def _flush_collect(self, pending, n: int, percentiles,
                       want_digests) -> dict:
        """Blocking half of one flush attempt: one batched device->host
        transfer instead of eleven round trips."""
        from veneur_tpu.core.slab import _fetch_packed, _fill_stat_results

        sel, packed, packed_refs, refs = pending
        out = {}
        with fetch_stage((packed_refs, refs)):
            if packed:
                (out["packed_counts"], out["packed_means"],
                 out["packed_weights"]) = _fetch_packed(*packed_refs, n)
            fetched, counters = jax.device_get(
                (refs, self._guard_counters()))
            fetched = cut_rows(fetched, n)
            for name, drains in counters.items():
                if drains is not None:
                    # per dispatch and device program: a mesh's shards
                    # each count their own import drains
                    obs_rec.note(**{name: int(np.sum(drains))})
        if packed:
            out["digest_min"], out["digest_max"] = fetched[:2]
            fetched = fetched[2:]
        elif want_digests:
            (out["digest_mean"], out["digest_weight"], out["digest_min"],
             out["digest_max"]) = fetched[:4]
            fetched = fetched[4:]
        _fill_stat_results(sel, fetched, n, percentiles, out)
        return out

    def _drop_device(self):
        """Free a retired generation's device state at the earliest
        point (it is never read again), then the host staging buffers —
        same release order as ``SlabDigestGroup._drop_staging``: the
        generation object outlives its flush by the sink fan-out and
        must not pin chunk-sized buffers for that window."""
        self.digest = self.temp = self.dmin = self.dmax = None
        self._device_dirty = False
        self._rows = self._vals = self._wts = None
        self._imp_rows = self._imp_means = self._imp_wts = None
        self._imp_stat_rows = self._imp_stat_mins = None
        self._imp_stat_maxs = None
        self._fill = 0
        self._imp_fill = 0
        self._imp_stat_fill = 0

    @requires_lock("store")
    def snapshot_begin(self):
        """Phase 1 of the two-phase checkpoint snapshot (the caller
        holds the store lock): drain staging, then DISPATCH device
        slices of every live plane. Op-by-op slicing enqueues
        asynchronously and yields fresh buffers, so the returned
        ``finish`` closure can run the blocking ``jax.device_get``
        OFF-lock — a later drain donating the originals cannot touch
        the captured slices, and ingest never stalls behind the fetch
        (the lock-order pass flags the old hold-across-device_get
        shape). ``finish(…)`` completes ``snap`` in place."""
        self._drain_staging()
        n = len(self.interner)
        snap = {"kind": "digest", "names": list(self.interner.names),
                "joined": list(self.interner.joined)}
        if n == 0:
            return snap, None
        # the count's pow2 bucket of rows (a checkpoint an interval
        # compiles nothing new while the count wanders); the bin planes
        # are flat, a row's bins contiguous
        b = live_bucket(n, self.capacity)
        refs = _prefix_rows(
            (self.digest.mean, self.digest.weight,
             self.dmin, self.dmax, self.digest.min, self.digest.max,
             self.temp.count, self.temp.vsum, self.temp.vmin,
             self.temp.vmax, self.temp.recip), b)
        bins = _prefix_rows((self.temp.sum_w, self.temp.sum_wm),
                            b * self.k)

        def finish():
            (mean, weight, imp_min, imp_max, dmn, dmx, cnt, vsum, vmin,
             vmax, recip) = cut_rows(jax.device_get(refs), n)
            bin_w, bin_wm = (a.reshape(n, self.k) for a in cut_rows(
                jax.device_get(bins), n * self.k))
            snap.update(flatten_digest_state(
                np.asarray(mean, np.float32),
                np.asarray(weight, np.float32),
                np.asarray(bin_w, np.float32),
                np.asarray(bin_wm, np.float32)))
            # digest-bound extrema (import path stat args); the
            # interval's observed extrema travel separately as temp stats
            snap["mins"] = np.minimum(np.asarray(imp_min, np.float32),
                                      np.asarray(dmn, np.float32))
            snap["maxs"] = np.maximum(np.asarray(imp_max, np.float32),
                                      np.asarray(dmx, np.float32))
            for nm, arr in (("count", cnt), ("vsum", vsum),
                            ("vmin", vmin), ("vmax", vmax),
                            ("recip", recip)):
                snap[nm] = np.asarray(arr, np.float32)

        return snap, finish

    @requires_lock("store")
    def snapshot_state(self) -> dict:
        """Host copy of the live sketch state WITHOUT resetting it (the
        checkpoint path, veneur_tpu/persist/): digest-plane centroids
        plus pending temp-bin centroids flatten to per-row runs, and the
        interval's scalar stats ride alongside so a restore rebuilds
        both the mergeable sketch and the local-aggregate emissions.
        One-shot begin+finish for callers that exclusively own the
        group (the re-merge rung, tests)."""
        snap, finish = self.snapshot_begin()
        if finish is not None:
            finish()
        return snap

    @requires_lock("store")
    def restore_stats(self, rows: np.ndarray, count: np.ndarray,
                      vsum: np.ndarray, vmin: np.ndarray,
                      vmax: np.ndarray, recip: np.ndarray):
        """Fold recovered per-row scalar stats into the temp
        accumulators (see ``_restore_temp_stats``)."""
        if not len(rows):
            return
        self.ensure_capacity(int(rows.max()))
        self._device_dirty = True
        self.temp = _restore_temp_stats(
            self.temp, jnp.asarray(rows, jnp.int32),
            jnp.asarray(count, jnp.float32),
            jnp.asarray(vsum, jnp.float32),
            jnp.asarray(vmin, jnp.float32),
            jnp.asarray(vmax, jnp.float32),
            jnp.asarray(recip, jnp.float32))


# ---------------------------------------------------------------------------
# Device-side set groups (HyperLogLog)
# ---------------------------------------------------------------------------


@partial(jax.jit, donate_argnums=(0,))
def _ingest_hashes(registers, rows, hi, lo):
    idx, rho = hll_ops.idx_rho(hi, lo, _precision_of(registers))
    return registers.at[rows, idx].max(rho.astype(registers.dtype),
                                       mode="drop")


def _precision_of(registers) -> int:
    return int(math.log2(registers.shape[-1]))


@partial(jax.jit, donate_argnums=(0,))
def _merge_registers(registers, rows, updates):
    return registers.at[rows].max(updates.astype(registers.dtype),
                                  mode="drop")


@jax.jit
def _estimate_all(registers):
    return hll_ops.estimate(registers.astype(jnp.int32),
                            _precision_of(registers))


class SetGroup(OverloadLimited):
    """One scope-class of Set metrics as a dense [S, 2^p] register tensor.

    Registers are int8 (max value 64-p+1 = 51): at the reference's precision
    14 a series costs 16 KiB of HBM, which is what bounds single-chip set
    cardinality — shard the series axis across a mesh to scale (SURVEY §5).
    """

    _retired = False  # see DigestGroup._retired
    _bulk_intern = True

    def __init__(self, capacity: int = DEFAULT_INITIAL_CAPACITY,
                 chunk: int = DEFAULT_CHUNK,
                 precision: int = hll_ops.DEFAULT_PRECISION):
        self.interner = Interner()
        self.capacity = capacity
        self.chunk = chunk
        self.precision = precision
        self.m = hll_ops.num_registers(precision)
        self._reset_registers()
        self._init_staging()

    def _init_staging(self):
        self._new_sample_buffers()
        self._imp_rows: List[int] = []
        self._imp_regs: List[np.ndarray] = []

    def _new_sample_buffers(self):
        # Fresh buffers per drain; see DigestGroup._new_sample_buffers.
        self._rows = np.full(self.chunk, self.capacity, np.int32)
        self._hi = np.zeros(self.chunk, np.uint32)
        self._lo = np.zeros(self.chunk, np.uint32)
        self._fill = 0

    def __len__(self):
        return len(self.interner)

    @requires_lock("store")
    def _row(self, key: MetricKey, tags: List[str]) -> int:
        row = self._intern_row(key, tags)
        if row >= self.capacity:
            self._grow()
        return row

    def _grow(self):
        self._drain_staging()
        old = self.capacity
        self.capacity *= _GROW_FACTOR
        self.registers = jnp.pad(self.registers,
                                 ((0, self.capacity - old), (0, 0)))
        self._rows[self._fill:] = self.capacity

    @requires_lock("store")
    def ensure_capacity(self, max_row: int):
        """Grow so max_row is addressable (bulk paths bypass _row)."""
        while max_row >= self.capacity:
            self._grow()

    def fresh(self) -> "SetGroup":
        """Empty same-config twin (swap-on-flush generation swap)."""
        return SetGroup(self.capacity, self.chunk, self.precision)

    @requires_lock("store")
    def sample_many(self, rows: np.ndarray, hashes: np.ndarray):
        """Bulk staging append of pre-hashed members (uint64) from the
        native ingest path."""
        n = len(rows)
        his = (hashes >> np.uint64(32)).astype(np.uint32)
        los = (hashes & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        start = 0
        while start < n:
            if self._fill == self.chunk:
                self._drain_samples()
            take = min(self.chunk - self._fill, n - start)
            i = self._fill
            self._rows[i:i + take] = rows[start:start + take]
            self._hi[i:i + take] = his[start:start + take]
            self._lo[i:i + take] = los[start:start + take]
            self._fill = i + take
            start += take
        if self._fill == self.chunk:
            self._drain_samples()

    @requires_lock("store")
    def sample(self, key: MetricKey, tags: List[str], member: str):
        row = self._row(key, tags)
        h = hll_ops.hash_member(member.encode("utf-8"))
        i = self._fill
        self._rows[i] = row
        self._hi[i] = h >> 32
        self._lo[i] = h & 0xFFFFFFFF
        self._fill = i + 1
        if self._fill == self.chunk:
            self._drain_samples()

    @requires_lock("store")
    def import_registers(self, key: MetricKey, tags: List[str],
                         registers: np.ndarray):
        """Merge a forwarded sketch: elementwise register max
        (samplers.go:423-435). Rejects precision mismatches per import
        (cf. Set.Combine's error, samplers.go:424-435) rather than
        poisoning the whole batch."""
        registers = np.asarray(registers)
        if registers.shape != (self.m,):
            raise ValueError(
                f"HLL precision mismatch: got {registers.shape}, "
                f"want ({self.m},)")
        row = self._row(key, tags)
        self._imp_rows.append(row)
        self._imp_regs.append(registers)
        if len(self._imp_rows) >= IMPORT_DRAIN_BATCH:
            self._drain_imports()

    @requires_lock("store")
    def import_registers_row(self, row: int, registers: np.ndarray):
        """Row-addressed variant for the native import path (the row was
        already interned through the C++ table)."""
        registers = np.asarray(registers)
        if registers.shape != (self.m,):
            raise ValueError(
                f"HLL precision mismatch: got {registers.shape}, "
                f"want ({self.m},)")
        self._imp_rows.append(row)
        self._imp_regs.append(registers)
        if len(self._imp_rows) >= IMPORT_DRAIN_BATCH:
            self._drain_imports()

    def _drain_samples(self):
        if self._fill == 0:
            return
        self._device_dirty = True
        rows, hi, lo = self._rows, self._hi, self._lo
        self._new_sample_buffers()
        self.registers = _ingest_hashes(self.registers, jnp.asarray(rows),
                                        jnp.asarray(hi), jnp.asarray(lo))

    def _drain_imports(self):
        if not self._imp_rows:
            return
        self._device_dirty = True
        rows = jnp.asarray(np.asarray(self._imp_rows, np.int32))
        regs = jnp.asarray(np.stack(self._imp_regs).astype(np.int8))
        self.registers = _merge_registers(self.registers, rows, regs)
        self._imp_rows.clear()
        self._imp_regs.clear()

    def _drain_staging(self):
        self._drain_samples()
        self._drain_imports()

    def flush(self, want_estimates: bool = True, want_registers: bool = True):
        """Estimate/export only what the caller will consume: a local
        instance forwards registers without estimating; a discarding flush
        (no sinks, no forwarding) skips both device passes."""
        return SetGroup.flush_begin(self, want_estimates, want_registers)()

    def flush_begin(self, want_estimates: bool = True,
                    want_registers: bool = True):
        """Two-phase flush for the pipelined egress: the estimate
        program and the live-row register slice DISPATCH now (op
        outputs own fresh buffers, so the device reset below cannot
        touch them — the snapshot_begin pattern), and the returned
        ``finish()`` runs the blocking fetch; a later group's device
        execution overlaps it."""
        with obs_rec.maybe_stage("drain"):
            self._drain_staging()
        with obs_rec.maybe_stage("compute"):
            n = len(self.interner)
            interner, self.interner = self.interner, Interner()
            if n == 0:
                if self._retired:
                    self.registers = None
                    self._device_dirty = False
                elif self._device_dirty:
                    self._reset_registers()
                    self._init_staging()
                return lambda: (interner, None, None)
            est_ref = self._estimate_refs(n) if want_estimates else None
            reg_ref = self._register_refs(n) if want_registers else None
            if self._retired:
                # retired generation: drop the [S, 2^p] plane now
                # instead of allocating a third one (16 KiB/series at
                # p=14); the sliced op outputs above keep the live rows
                # alive until the fetch lands
                self.registers = None
                self._device_dirty = False
            else:
                self._reset_registers()
                self._init_staging()

        def finish():
            with fetch_stage((est_ref, reg_ref)):
                # the refs hold the count's bucket of rows
                estimates = (np.asarray(jax.device_get(est_ref))[:n]
                             if want_estimates else None)
                registers = (np.asarray(jax.device_get(reg_ref),
                                        np.uint8)[:n]
                             if want_registers else None)
            return interner, estimates, registers

        return finish

    def _estimates(self):
        """Batched cardinality estimates (override point for the mesh store)."""
        return _estimate_all(self.registers)

    def _estimate_refs(self, n: int):
        """Device refs of the live rows' estimates, interner order (the
        mesh store gathers its shard-placed physical rows here)."""
        return _prefix_rows((self._estimates(),),
                            live_bucket(n, self.capacity))[0]

    def _register_refs(self, n: int):
        """Device refs of the live rows' registers, interner order."""
        return _prefix_rows((self.registers,),
                            live_bucket(n, self.capacity))[0]

    def _snapshot_refs(self, n: int):
        """Device refs of the live rows for the two-phase snapshot
        (override point for the mesh store's permutation gather)."""
        return self._register_refs(n)

    def _reset_registers(self):
        self.registers = jnp.zeros((self.capacity, self.m), jnp.int8)
        self._device_dirty = False

    @requires_lock("store")
    def snapshot_begin(self):
        """Phase 1 of the two-phase checkpoint snapshot: drain staging
        and dispatch the register-plane slice under the store lock; the
        returned ``finish`` fetches it off-lock (see
        ``DigestGroup.snapshot_begin``)."""
        self._drain_staging()
        n = len(self.interner)
        snap = {"kind": "set", "precision": self.precision,
                "names": list(self.interner.names),
                "joined": list(self.interner.joined)}
        if n == 0:
            return snap, None
        refs = self._snapshot_refs(n)

        def finish():
            snap["registers"] = np.asarray(jax.device_get(refs),
                                           np.uint8)[:n]

        return snap, finish

    @requires_lock("store")
    def snapshot_state(self) -> dict:
        """Host copy of the live registers WITHOUT resetting (the
        checkpoint path, veneur_tpu/persist/). One-shot begin+finish
        for callers that exclusively own the group."""
        snap, finish = self.snapshot_begin()
        if finish is not None:
            finish()
        return snap


# ---------------------------------------------------------------------------
# Heavy hitters (count-min + top-k) — BASELINE config #5, a sampler type
# the reference does not have
# ---------------------------------------------------------------------------


class HeavyHitterGroup(OverloadLimited):
    """Set-type metrics tagged ``veneurtopk``: instead of cardinality,
    count per-member frequencies in one shared salted count-min table
    (veneur_tpu/ops/countmin.py) and keep a per-series top-k list.

    Flush emits ``{name}.topk`` counters tagged ``key:<member>`` for each
    surviving heavy hitter. Member strings are memoized host-side (the
    sketch itself only sees 64-bit hashes); the memo is bounded and
    unknown hashes emit as hex, so unbounded key cardinality cannot
    exhaust host memory. Cross-instance aggregation: locals forward
    (table, top-k candidates, members) over the JSON forward path
    (convert.py "topk_sketch") or the gRPC ``MetricList.topk`` extension
    field (skipped by reference globals; suppressed entirely under
    forward_reference_compatible); the global adds tables elementwise
    and re-ranks the fleet top-k (import_sketch).
    """

    MEMO_LIMIT = 1 << 20
    _retired = False  # see DigestGroup._retired

    def __init__(self, capacity: int = DEFAULT_INITIAL_CAPACITY,
                 chunk: int = DEFAULT_CHUNK, depth: int = 4,
                 width: int = 1 << 16, k: int = 32):
        from veneur_tpu.ops import countmin as cm_ops

        self._cm = cm_ops
        self.interner = Interner()
        self.capacity = capacity
        self.chunk = chunk
        self.depth, self.width, self.k = depth, width, k
        self.sketch = cm_ops.init(capacity, depth, width, k)
        self._device_dirty = False
        self._members: Dict[int, str] = {}
        # this generation's sample dispatches (timeline ``topk``)
        self.dispatches = 0
        self._update = jax.jit(cm_ops.update, donate_argnums=(0,))
        self._add_table = jax.jit(cm_ops.add_table, donate_argnums=(0,))
        self._inject = jax.jit(cm_ops.inject_candidates,
                               donate_argnums=(0,))
        # stable per-row series ids (+1 slot for the staging sentinel);
        # see CountMin.sids for why these must be instance-independent
        self._sids_np = np.zeros(capacity + 1, np.uint32)
        self._new_sample_buffers()

    def fresh(self) -> "HeavyHitterGroup":
        """Empty same-config twin (swap-on-flush generation swap);
        reuses the instance-bound jitted programs so the swap never
        retraces."""
        g = HeavyHitterGroup(self.capacity, self.chunk, self.depth,
                             self.width, self.k)
        g._update = self._update
        g._add_table = self._add_table
        g._inject = self._inject
        return g

    def _new_sample_buffers(self):
        self._rows = np.full(self.chunk, self.capacity, np.int32)
        self._hi = np.zeros(self.chunk, np.uint32)
        self._lo = np.zeros(self.chunk, np.uint32)
        self._wts = np.zeros(self.chunk, np.float32)
        self._fill = 0

    def __len__(self):
        return len(self.interner)

    @staticmethod
    def stable_sid(name: str, joined_tags: str) -> int:
        """Instance-independent 32-bit series id: fnv1a over the series
        identity. Every instance MUST derive the same sid for the same
        series — count-min columns are salted with it (CountMin.sids)."""
        h = 2166136261
        for b in f"{name}|set|{joined_tags}".encode("utf-8"):
            h = ((h ^ b) * 16777619) & 0xFFFFFFFF
        return h

    @requires_lock("store")
    def _row(self, key: MetricKey, tags: List[str]) -> int:
        row = self._intern_row(key, tags)
        if row >= self.capacity:
            self.ensure_capacity(row)
        if self._sids_np[row] == 0:  # first sight (or the 2^-32 rehash)
            # derive the sid from the row's INTERNED identity, not the
            # sample's key: past the cardinality cap the row is the
            # overflow row and must hash as such on every instance
            self._sids_np[row] = self.stable_sid(self.interner.names[row],
                                                 self.interner.joined[row])
        return row

    @requires_lock("store")
    def ensure_capacity(self, max_row: int):
        while max_row >= self.capacity:
            self._drain_samples()
            old = self.capacity
            self.capacity *= _GROW_FACTOR
            pad = ((0, self.capacity - old), (0, 0))
            self.sketch = self.sketch._replace(
                topk_hi=jnp.pad(self.sketch.topk_hi, pad),
                topk_lo=jnp.pad(self.sketch.topk_lo, pad),
                topk_counts=jnp.pad(self.sketch.topk_counts, pad),
                sids=jnp.pad(self.sketch.sids, (0, self.capacity - old)))
            sids = np.zeros(self.capacity + 1, np.uint32)
            sids[:old + 1] = self._sids_np
            sids[old] = 0  # the old sentinel slot is now a real row
            self._sids_np = sids
            self._rows[self._fill:] = self.capacity

    def _memoize(self, h: int, member: str):
        if len(self._members) < self.MEMO_LIMIT:
            self._members[h] = member

    @requires_lock("store")
    def sample(self, key: MetricKey, tags: List[str], member: str,
               weight: float = 1.0):
        row = self._row(key, tags)
        h = hll_ops.hash_member(member.encode("utf-8"))
        self._memoize(h, member)
        i = self._fill
        self._rows[i] = row
        self._hi[i] = h >> 32
        self._lo[i] = h & 0xFFFFFFFF
        self._wts[i] = weight
        self._fill = i + 1
        if self._fill == self.chunk:
            self._drain_samples()

    @requires_lock("store")
    def sample_many(self, rows: np.ndarray, hashes: np.ndarray,
                    members=None):
        """Bulk append from the native batch path; members (bytes) feed
        the host-side memo when provided."""
        if members is not None:
            for h, mb in zip(hashes, members):
                self._memoize(int(h), mb.decode("utf-8", "replace"))
        his = (hashes >> np.uint64(32)).astype(np.uint32)
        los = (hashes & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        n = len(rows)
        start = 0
        while start < n:
            if self._fill == self.chunk:
                self._drain_samples()
            take = min(self.chunk - self._fill, n - start)
            i = self._fill
            self._rows[i:i + take] = rows[start:start + take]
            self._hi[i:i + take] = his[start:start + take]
            self._lo[i:i + take] = los[start:start + take]
            self._wts[i:i + take] = 1.0
            self._fill = i + take
            start += take
        if self._fill == self.chunk:
            self._drain_samples()

    def _drain_samples(self):
        if self._fill == 0:
            return
        self._device_dirty = True
        self.dispatches += 1
        rows, hi, lo, wts = self._rows, self._hi, self._lo, self._wts
        self._new_sample_buffers()
        sids = self._sids_np[rows]
        with obs_kernels.scope("drain.topk.dense"):
            self.sketch = self._update(self.sketch, rows, sids, hi, lo,
                                       wts)

    def _drain_staging(self):
        self._drain_samples()

    @requires_lock("store")
    def import_sketch(self, table: np.ndarray, series: List[tuple]):
        """Merge a forwarded heavy-hitter sketch: the count-min table
        adds elementwise, and each series' forwarded top-k keys become
        candidates re-estimated against the combined table.

        table: [depth, width] float32 (shape must match — both ends run
        the same config, like hll precision). series: [(key, tags,
        [(hi, lo), ...], [member-or-None, ...])]."""
        if table.shape != (self.depth, self.width):
            raise ValueError(
                f"forwarded count-min shape {table.shape} != local "
                f"({self.depth}, {self.width})")
        self._drain_samples()  # candidates estimate against a settled table
        self._device_dirty = True
        rows, sids, his, los, slots = [], [], [], [], []
        for key, tags, keys, members in series:
            row = self._row(key, list(tags))
            sid = int(self._sids_np[row])
            for j, (hi, lo) in enumerate(keys):
                rows.append(row)
                sids.append(sid)
                his.append(hi)
                los.append(lo)
                slots.append(j)
                if members and j < len(members) and members[j]:
                    self._memoize((int(hi) << 32) | int(lo), members[j])
        self.sketch = self._add_table(self.sketch,
                                      jnp.asarray(table, jnp.float32))
        if rows:
            self.sketch = self._inject(
                self.sketch,
                jnp.asarray(self._scatter_rows(
                    np.asarray(rows, np.int32))),
                jnp.asarray(np.asarray(sids, np.uint32)),
                jnp.asarray(np.asarray(his, np.uint32)),
                jnp.asarray(np.asarray(los, np.uint32)),
                jnp.asarray(slots, jnp.int32))

    def flush(self, want_forward: bool = False):
        """Returns (interner, [(row, member, count), ...], forwardable)
        and resets. forwardable is None unless want_forward: then it is
        (table ndarray, [(name, tags, [(hi, lo)...], [member...])])."""
        return HeavyHitterGroup.flush_begin(self, want_forward)()

    def flush_begin(self, want_forward: bool = False):
        """Two-phase flush for the pipelined egress: the live top-k
        plane slices (and the count-min table ref when forwarding)
        dispatch now, the group resets immediately, and ``finish()``
        runs the blocking fetch plus the host-side member/emission
        assembly later."""
        with obs_rec.maybe_stage("drain"):
            self._drain_samples()
            if self.dispatches:
                obs_rec.note(topk_dispatches=self.dispatches)
                self.dispatches = 0
        with obs_rec.maybe_stage("compute"):
            n = len(self.interner)
            interner, self.interner = self.interner, Interner()
            if n == 0 and not self._device_dirty:
                # pristine sketch: skip the device reallocation entirely
                return lambda: (interner, [], None)
            refs = self._live_topk(n) if n else None
            table_ref = self.sketch.table if (n and want_forward) \
                else None
            members, self._members = self._members, {}
            if self._retired:
                self.sketch = None  # free the table now, never reused
            else:
                self._reset_sketch()
                self._sids_np = np.zeros(self.capacity + 1, np.uint32)
                self._new_sample_buffers()
            self._device_dirty = False

        def finish():
            out = []
            fwd = None
            if n:
                with fetch_stage(refs):
                    hi, lo, ct = cut_rows(jax.device_get(refs), n)
                # one pass builds both the emission rows and (when
                # asked) the per-row forwardable candidate lists
                by_row = {} if want_forward else None
                for row in range(n):
                    for j in range(self.k):
                        c = float(ct[row, j])
                        if c <= 0:
                            continue
                        pair = (int(hi[row, j]), int(lo[row, j]))
                        h = (pair[0] << 32) | pair[1]
                        member = members.get(h)
                        out.append((row, member or f"0x{h:016x}", c))
                        if by_row is not None:
                            keys, mems = by_row.setdefault(row, ([], []))
                            keys.append(pair)
                            mems.append(member)
                if want_forward:
                    with fetch_stage(table_ref):
                        table = np.asarray(jax.device_get(table_ref))
                    series = [
                        (key.name, interner.tags[row]) + by_row[row]
                        for key, row in interner.rows.items()
                        if row in by_row]
                    fwd = (table, series)
            return interner, out, fwd

        return finish

    def _live_topk(self, n: int):
        """Device refs of the live rows' top-k planes, interner order
        (override point for the mesh store's permutation gather)."""
        return _prefix_rows(
            (self.sketch.topk_hi, self.sketch.topk_lo,
             self.sketch.topk_counts), live_bucket(n, self.capacity))

    def _scatter_rows(self, rows: np.ndarray) -> np.ndarray:
        """Row ids as the device scatter sees them (override point for
        the mesh store's logical→physical placement translation)."""
        return rows

    def _reset_sketch(self):
        self.sketch = self._cm.init(self.capacity, self.depth,
                                    self.width, self.k)

    @requires_lock("store")
    def snapshot_begin(self):
        """Phase 1 of the two-phase checkpoint snapshot: dispatch the
        top-k plane slices and a device-side table copy (the count-min
        update program donates the table, so the captured handle must
        be a fresh buffer), and copy the host member memo — all under
        the store lock. The returned ``finish`` fetches and assembles
        off-lock (see ``DigestGroup.snapshot_begin``)."""
        self._drain_samples()
        n = len(self.interner)
        snap = {"kind": "topk", "depth": self.depth, "width": self.width,
                "names": list(self.interner.names),
                "joined": list(self.interner.joined)}
        if n == 0:
            return snap, None
        refs = self._live_topk(n) + (jnp.copy(self.sketch.table),)
        members = dict(self._members)

        def finish():
            *topk, table = jax.device_get(refs)
            hi, lo, ct = cut_rows(topk, n)
            snap["table"] = np.asarray(table, np.float32)
            # vectorized live-slot extraction: no O(n*k) Python loop
            live_r, live_c = np.nonzero(np.asarray(ct) > 0)
            series = [{"keys": [], "members": []} for _ in range(n)]
            for r, c in zip(live_r.tolist(), live_c.tolist()):
                pair = (int(hi[r, c]), int(lo[r, c]))
                s = series[r]
                s["keys"].append(pair)
                s["members"].append(
                    members.get((pair[0] << 32) | pair[1]))
            snap["series"] = series

        return snap, finish

    @requires_lock("store")
    def snapshot_state(self) -> dict:
        """Host copy of the live sketch WITHOUT resetting (the
        checkpoint path, veneur_tpu/persist/): the count-min table plus
        each series' top-k candidates in the import_sketch layout.
        One-shot begin+finish for callers that exclusively own the
        group."""
        snap, finish = self.snapshot_begin()
        if finish is not None:
            finish()
        return snap


# ---------------------------------------------------------------------------
# The store
# ---------------------------------------------------------------------------


@dataclass
class MetricsSummary:
    """Per-flush tallies (flusher.go:121-132)."""

    counters: int = 0
    gauges: int = 0
    histograms: int = 0
    sets: int = 0
    timers: int = 0
    global_counters: int = 0
    global_gauges: int = 0
    local_histograms: int = 0
    local_sets: int = 0
    local_timers: int = 0
    local_status_checks: int = 0
    # per-interval ingest tallies, snapshotted under the store lock at
    # flush so concurrent increments are never lost
    processed: int = 0
    imported: int = 0
    # overload accounting (veneur.overload.*): samples absorbed by each
    # group's overflow row and samples scrubbed at the group boundary,
    # keyed by group attr name; only non-zero groups appear
    spilled: Dict[str, int] = field(default_factory=dict)
    scrubbed: Dict[str, int] = field(default_factory=dict)


class PackedDigestPlanes(NamedTuple):
    """Device-compacted digest planes for the forward path: only LIVE
    centroids, 4 bytes each (u16 range-quantized mean + u16 bfloat16
    weight bits), produced on device by ``core/slab.py:_pack_slab`` so
    a million-series forward never fetches raw ``[S, K]`` f32 planes
    (the reference forwards at fleet cardinality every interval,
    flusher.go:292-473). Row r owns
    ``means_q[starts[r]:starts[r]+counts[r]]`` with
    ``mean = dmin[r] + q/65535 * (dmax[r]-dmin[r])``."""

    counts: np.ndarray      # [S] u16 live centroids per row
    means_q: np.ndarray     # [L] u16 quantized means
    weights_bf: np.ndarray  # [L] u16 bfloat16 bit patterns
    dmin: np.ndarray        # [S] f32 per-digest minima (+inf when empty)
    dmax: np.ndarray        # [S] f32 per-digest maxima (-inf when empty)

    @property
    def nrows(self) -> int:
        return len(self.counts)

    @property
    def nbytes(self) -> int:
        return (self.counts.nbytes + self.means_q.nbytes
                + self.weights_bf.nbytes + self.dmin.nbytes
                + self.dmax.nbytes)

    def weights_f32(self) -> np.ndarray:
        return (self.weights_bf.astype(np.uint32) << 16).view(np.float32)

    def means_f64(self) -> np.ndarray:
        """Dequantized means, flat over all rows in row order."""
        counts = self.counts.astype(np.int64)
        span = (self.dmax.astype(np.float64)
                - self.dmin.astype(np.float64)) / 65535.0
        base = np.repeat(self.dmin.astype(np.float64), counts)
        scale = np.repeat(span, counts)
        return base + self.means_q.astype(np.float64) * scale

    def row_slices(self):
        """Host-side dequantization for per-row consumers: returns
        (starts, ends, means f64 [L], weights f64 [L]) so row r's
        centroids are ``means[starts[r]:ends[r]]`` — the ONE place the
        quantization contract is decoded in Python."""
        counts = self.counts.astype(np.int64)
        ends = np.cumsum(counts)
        return (ends - counts, ends, self.means_f64(),
                self.weights_f32().astype(np.float64))


def _packed_planes_from_result(r: dict) -> PackedDigestPlanes:
    """Assemble PackedDigestPlanes from a group's packed flush result."""
    return PackedDigestPlanes(
        r["packed_counts"], r["packed_means"], r["packed_weights"],
        np.asarray(r["digest_min"], np.float32),
        np.asarray(r["digest_max"], np.float32))


@dataclass
class ForwardableState:
    """Sketch state destined for the global tier (worker.go:161-183):
    global counters/gauges by value, digests as centroid arrays, sets as
    register arrays.

    A columnar flush puts digests in ``histograms_columnar`` /
    ``timers_columnar`` instead — (names arenas, tags arenas, planes)
    where planes is either the dense 4-field layout (mean [S,K] f32,
    weight [S,K] f32, dmin [S], dmax [S], spread inline as a 6-tuple)
    or a :class:`PackedDigestPlanes` — which the native gRPC encoder
    serializes without per-row tuples; call ``materialize_digests`` for
    consumers that need the per-row lists (the JSON forward path)."""

    counters: List[Tuple[str, List[str], int]] = field(default_factory=list)
    gauges: List[Tuple[str, List[str], float]] = field(default_factory=list)
    # (name, tags, means, weights, min, max), one per series
    histograms: List[tuple] = field(default_factory=list)
    timers: List[tuple] = field(default_factory=list)
    histograms_columnar: Optional[tuple] = None
    timers_columnar: Optional[tuple] = None
    # (name, tags, registers-uint8, precision)
    sets: List[tuple] = field(default_factory=list)
    # heavy hitters: (table ndarray [depth, width],
    # [(name, tags, [(hi, lo)...], [member-or-None...])]) or None
    topk: Optional[tuple] = None

    @staticmethod
    def _columnar_rows(block) -> int:
        if block is None:
            return 0
        planes = block[2]
        return (planes.nrows if isinstance(planes, PackedDigestPlanes)
                else len(planes))

    def __len__(self):
        return (len(self.counters) + len(self.gauges) + len(self.histograms)
                + len(self.timers) + len(self.sets)
                + self._columnar_rows(self.histograms_columnar)
                + self._columnar_rows(self.timers_columnar)
                + (len(self.topk[1]) if self.topk else 0))

    def materialize_digests(self):
        """Convert columnar digest planes to the per-row tuple lists
        (consumers: HTTP/JSON forwarding; the gRPC path encodes the
        columns natively and never calls this)."""
        for attr, col_attr in (("histograms", "histograms_columnar"),
                               ("timers", "timers_columnar")):
            col = getattr(self, col_attr)
            if col is None:
                continue
            out = getattr(self, attr)
            if isinstance(col[2], PackedDigestPlanes):
                (nb, no, nl), (tb, to, tl), p = col
                starts, ends, means_f, weights_f = p.row_slices()
                for r in range(p.nrows):
                    name = nb[no[r]:no[r] + nl[r]].decode(
                        "utf-8", "replace")
                    joined = tb[to[r]:to[r] + tl[r]].decode(
                        "utf-8", "replace")
                    tags = joined.split(",") if joined else []
                    s, e = starts[r], ends[r]
                    out.append((name, tags, means_f[s:e], weights_f[s:e],
                                float(p.dmin[r]), float(p.dmax[r])))
                setattr(self, col_attr, None)
                continue
            (nb, no, nl), (tb, to, tl), means, weights, dmins, dmaxs = col
            for r in range(len(means)):
                name = nb[no[r]:no[r] + nl[r]].decode("utf-8", "replace")
                joined = tb[to[r]:to[r] + tl[r]].decode("utf-8", "replace")
                tags = joined.split(",") if joined else []
                w = weights[r]
                live = w > 0
                out.append((name, tags,
                            means[r][live].astype(np.float64),
                            w[live].astype(np.float64),
                            float(dmins[r]), float(dmaxs[r])))
            setattr(self, col_attr, None)


_DIGEST_GROUPS = ("histograms", "timers", "local_histograms", "local_timers")
_SET_GROUPS = ("sets", "local_sets")


def _digest_want(percentiles, aggregates: HistogramAggregates,
                 forwarding: bool, digest_format: str):
    """(want_digests, want_stats) for one digest group's flush: fetch
    only the per-row stat arrays this aggregate config reads (each is
    4 MB/1M rows of device->host transfer); the zero-fill for unfetched
    ones is never emitted because the same mask gates the emissions and
    columnar.digest_block."""
    want = forwarding
    if forwarding and digest_format == "packed":
        want = "packed"
    agg = aggregates.value
    want_stats = set()
    if agg & (Aggregate.COUNT | Aggregate.AVERAGE
              | Aggregate.HARMONIC_MEAN):
        want_stats.add("count")
    if agg & Aggregate.MIN:
        want_stats.add("min")
    if agg & Aggregate.MAX:
        want_stats.add("max")
    if agg & (Aggregate.SUM | Aggregate.AVERAGE):
        want_stats.add("sum")
    if agg & Aggregate.HARMONIC_MEAN:
        want_stats.add("recip")
    if (agg & Aggregate.MEDIAN) or percentiles:
        want_stats.add("pcts")
    return want, want_stats


class _Generation:
    """The retired group set a flush drains off-lock (swap-on-flush)."""

    __slots__ = ("counters", "global_counters", "gauges", "global_gauges",
                 "local_status_checks", "histograms", "timers",
                 "local_histograms", "local_timers", "self_timers", "sets",
                 "local_sets", "heavy_hitters", "processed", "imported")


def _summarize(g) -> "MetricsSummary":
    """Group-count summary for any group container (the live store or a
    retired generation) — one mapping, two callers."""
    spilled = {}
    scrubbed = {}
    for name in MetricStore._GEN_GROUPS:
        grp = getattr(g, name, None)
        if grp is None:
            continue
        if getattr(grp, "spilled", 0):
            spilled[name] = grp.spilled
        if getattr(grp, "scrubbed", 0):
            scrubbed[name] = grp.scrubbed
    return MetricsSummary(
        counters=len(g.counters), gauges=len(g.gauges),
        histograms=len(g.histograms), sets=len(g.sets),
        timers=len(g.timers), global_counters=len(g.global_counters),
        global_gauges=len(g.global_gauges),
        local_histograms=len(g.local_histograms),
        local_sets=len(g.local_sets), local_timers=len(g.local_timers),
        local_status_checks=len(g.local_status_checks),
        spilled=spilled, scrubbed=scrubbed)


class MetricStore:
    """All eleven scope-classes plus dispatch, flush and import logic."""

    def __init__(self, initial_capacity: int = DEFAULT_INITIAL_CAPACITY,
                 chunk: int = DEFAULT_CHUNK,
                 compression: float = td_ops.DEFAULT_COMPRESSION,
                 hll_precision: int = hll_ops.DEFAULT_PRECISION,
                 mesh=None, digest_storage: str = "dense",
                 digest_dtype: str = "float32", slab_rows: int = 1 << 20,
                 topk_depth: int = 4, topk_width: int = 1 << 16,
                 topk_k: int = 32, max_series: int = 0,
                 max_tag_length: int = 0, compute=None, overload=None,
                 tier_pool_centroids: int = 16,
                 tier_promote_samples: int = 64,
                 tier_promote_intervals: int = 2,
                 tier_demote_intervals: int = 3,
                 flush_pipeline_depth: int = 2):
        self._lock = threading.RLock()
        # serializes whole flush() calls (the store lock itself is held
        # only for the generation swap — see flush())
        self._flush_gate = threading.Lock()
        # overlapped flush egress (docs/internals.md "Life of a
        # flush"): 0 = fully sequential drain; N > 0 = dispatch-all-
        # then-fetch with at most N fetched-but-unserialized chunks
        # resident (and an N-slab dispatch-ahead window inside the
        # slab-backed digest groups)
        self.flush_pipeline_depth = max(0, int(flush_pipeline_depth))
        self.mesh = mesh
        self.shard_router = None
        if mesh is not None and digest_storage == "slab":
            raise ValueError(
                "digest_storage: slab cannot combine with mesh_enabled: "
                "the slab layout is the single-chip capacity plan and "
                "the mesh supersedes it — run the mesh dense, or "
                "digest_storage: tiered (fleet mode composes with the "
                "tiered packed-pool residency; fleet/mesh_tiered.py)")
        if mesh is None and digest_storage == "sharded":
            raise ValueError(
                "digest_storage: sharded needs a mesh (mesh_enabled)")
        if mesh is not None:
            # one router for every mesh group: a series owns the same
            # shard across scalars, digests, sets and heavy hitters
            from veneur_tpu.fleet import ShardRouter
            from veneur_tpu.parallel.mesh import SERIES_AXIS

            self.shard_router = ShardRouter(mesh.shape[SERIES_AXIS])

        def _slab_group():
            # the multi-million-series capacity plan (core/slab.py): flat
            # per-slab planes, optional 16-bit residency, slab-wise growth
            from veneur_tpu.core.slab import SlabDigestGroup

            return SlabDigestGroup(slab_rows=slab_rows, chunk=chunk,
                                   compression=compression,
                                   digest_dtype=digest_dtype)

        def _tiered_group():
            # the ragged-residency capacity plan (core/tiered.py):
            # packed pool + activity-promoted dense slots; each group
            # owns ONE TierDirectory shared by its generation twins
            from veneur_tpu.core.tiered import TieredDigestGroup

            return TieredDigestGroup(
                slab_rows=min(slab_rows, 1 << 18), chunk=chunk,
                compression=compression,
                pool_centroids=tier_pool_centroids,
                promote_samples=tier_promote_samples,
                promote_intervals=tier_promote_intervals,
                demote_intervals=tier_demote_intervals,
                dense_capacity=initial_capacity)

        self._slab_group = _slab_group
        # store_initial_capacity pre-sizes the digest and scalar groups,
        # so a deployment that knows its cardinality never compiles the
        # doubling ladder. A set row is 2^p bytes of registers (16 KiB
        # at p=14) and a heavy-hitter row three [k] planes, resident
        # whether or not a series ever arrives: 2^20 set rows alone
        # would be 16 GiB. Those groups start at no more than the
        # configuration's default (4096 rows, 64 MiB of registers) and
        # grow by doubling as before.
        wide_capacity = min(initial_capacity, 4096)

        def _set_group():
            # Past that cap, and off a mesh (whose set groups stay dense
            # and sharded, and whose local groups hold only the
            # instance's own telemetry), a set group keeps a series'
            # registers sparse until it fills them (core/sets.py): 512 B
            # a row, so the whole capacity is reserved and never grows.
            if mesh is None and initial_capacity > wide_capacity:
                from veneur_tpu.core.sets import SparseSetGroup

                return SparseSetGroup(initial_capacity, chunk,
                                      hll_precision)
            return SetGroup(wide_capacity, chunk, hll_precision)

        if mesh is not None:
            # Fleet mode: every group (scalars included) places series
            # by the shared router, so one shard owns a series across
            # the WHOLE store; local-only groups stay single-device
            # (they hold only this instance's own telemetry).
            from veneur_tpu.core.mesh_store import MeshScalarGroup

            self.counters = MeshScalarGroup("counter", initial_capacity,
                                            mesh, self.shard_router)
            self.global_counters = MeshScalarGroup(
                "counter", initial_capacity, mesh, self.shard_router)
            self.gauges = MeshScalarGroup("gauge", initial_capacity,
                                          mesh, self.shard_router)
            self.global_gauges = MeshScalarGroup(
                "gauge", initial_capacity, mesh, self.shard_router)
        else:
            self.counters = ScalarGroup("counter", initial_capacity)
            self.global_counters = ScalarGroup("counter", initial_capacity)
            self.gauges = ScalarGroup("gauge", initial_capacity)
            self.global_gauges = ScalarGroup("gauge", initial_capacity)
        self.local_status_checks = ScalarGroup("status", initial_capacity)
        if mesh is not None and digest_storage == "tiered":
            # Fleet mode × tiered residency: the packed pool shards over
            # the series axis, the hot tier is a mesh bank, promotion is
            # shard-local (fleet/mesh_tiered.py) — the capacity win of
            # PR 6 across chips
            from veneur_tpu.core.mesh_store import MeshSetGroup
            from veneur_tpu.fleet.mesh_tiered import MeshTieredDigestGroup

            def _mesh_tiered():
                return MeshTieredDigestGroup(
                    mesh, self.shard_router,
                    slab_rows=min(slab_rows, 1 << 18), chunk=chunk,
                    compression=compression,
                    pool_centroids=tier_pool_centroids,
                    promote_samples=tier_promote_samples,
                    promote_intervals=tier_promote_intervals,
                    demote_intervals=tier_demote_intervals,
                    dense_capacity=initial_capacity)

            self.histograms = _mesh_tiered()
            self.timers = _mesh_tiered()
            self.sets = MeshSetGroup(mesh, wide_capacity, chunk,
                                     hll_precision,
                                     router=self.shard_router)
        elif mesh is not None:
            from veneur_tpu.core.mesh_store import (MeshDigestGroup,
                                                    MeshSetGroup)
            self.histograms = MeshDigestGroup(mesh, initial_capacity, chunk,
                                              compression,
                                              router=self.shard_router)
            self.timers = MeshDigestGroup(mesh, initial_capacity, chunk,
                                          compression,
                                          router=self.shard_router)
            self.sets = MeshSetGroup(mesh, wide_capacity, chunk,
                                     hll_precision,
                                     router=self.shard_router)
        elif digest_storage == "slab":
            self.histograms = self._slab_group()
            self.timers = self._slab_group()
            self.sets = _set_group()
        elif digest_storage == "tiered":
            self.histograms = _tiered_group()
            self.timers = _tiered_group()
            self.sets = _set_group()
        else:
            self.histograms = DigestGroup(initial_capacity, chunk, compression)
            self.timers = DigestGroup(initial_capacity, chunk, compression)
            self.sets = _set_group()
        if digest_storage == "slab":
            self.local_histograms = self._slab_group()
            self.local_timers = self._slab_group()
        elif digest_storage == "tiered":
            self.local_histograms = _tiered_group()
            self.local_timers = _tiered_group()
        else:
            self.local_histograms = DigestGroup(initial_capacity, chunk,
                                                compression)
            self.local_timers = DigestGroup(initial_capacity, chunk,
                                            compression)
        self.local_sets = _set_group()
        # the dedicated self-telemetry group (veneur_tpu/obs/): the
        # server's own stage durations, always a small dense DigestGroup
        # regardless of digest_storage — bounded cardinality (one row
        # per instrumented stage), local-only, never forwarded. 128
        # rows hold the stage vocabulary with room: a streamed flush
        # that uses two digest groups has 71 stage names (PR 29), and
        # a group that outgrows its rows doubles them, which compiles
        # its programs anew at the next flush
        self.self_timers = DigestGroup(min(128, initial_capacity), chunk,
                                       compression)
        if mesh is not None:
            from veneur_tpu.core.mesh_store import MeshHeavyHitterGroup

            self.heavy_hitters = MeshHeavyHitterGroup(
                wide_capacity, chunk, topk_depth, topk_width, topk_k,
                mesh, self.shard_router)
        else:
            self.heavy_hitters = HeavyHitterGroup(wide_capacity, chunk,
                                                  depth=topk_depth,
                                                  width=topk_width,
                                                  k=topk_k)
        self.hll_precision = hll_precision
        # overload-safety plumbing (veneur_tpu/overload.py,
        # resilience/compute.py): bounded per-group cardinality, the
        # shared quarantine ledger, the flush-kernel breaker, and the
        # (optional, attached by the server) admission controller
        from veneur_tpu.resilience.compute import ComputeBreaker

        self.max_series = max_series
        self.max_tag_length = max_tag_length
        self.compute = compute if compute is not None else ComputeBreaker()
        self.quarantine = Quarantine()
        self._overload = overload
        self._configure_overload_groups()
        self.processed = 0
        self.imported = 0
        # bumped at every generation swap; a checkpoint writer snapshots
        # (groups, epoch) under the lock and must discard the write if
        # the epoch moved before it commits (the flush drained — and
        # will emit — the state the snapshot captured)
        self.flush_epoch = 0
        # C++ memos of the Interner's series -> row mappings (ingest batch
        # path and MetricList import path); reset at flush (rows restart
        # with fresh interners)
        self._native_table = None
        self._mlist_table = None
        self._kind_groups = None
        # the lanes' series identities a kind, keyed by a lane registry
        # entry's (name, tags) bytes (_lane_remap): kept across flush
        # epochs and lanes, so a returning series is decoded once
        self._lane_identities: List[dict] = [{} for _ in range(11)]
        # import_columnar's clock, drained by take_import_stages
        self.import_ns = dict.fromkeys(
            ("messages", "decode", "lock_wait", "intern", "stage",
             "route", "dispatch"), 0)
        # set by the ingest-lane fleet (veneur_tpu/ingest/): invoked by
        # snapshot_state so sealed-but-unmerged lane chunks reach the
        # checkpoint
        self._ingest_drain = None

    # -- overload plumbing (veneur_tpu/overload.py) ------------------------

    def set_overload(self, controller) -> None:
        """Attach the server's admission controller; groups consult it
        for the first-sight series freeze (level >= 1)."""
        self._overload = controller
        self._configure_overload_groups()

    def _configure_overload_groups(self) -> None:
        for name in self._GEN_GROUPS:
            self._apply_overload_attrs(name, getattr(self, name))

    def _apply_overload_attrs(self, name: str, g) -> None:
        """Stamp one group's overload instance attrs (OverloadLimited's
        class defaults keep directly-constructed groups inert). Re-run
        on every fresh twin at the generation swap."""
        g.max_series = self.max_series
        g.overflow_label = name
        g._overflow_type = self._GROUP_TYPES[name]
        # the self-telemetry group is exempt from the admission FREEZE
        # (it is the operator's view into the overload — the veneur.*
        # name carve-out in overload.freeze_exempt already covers its
        # rows, and detaching the controller makes the exemption hold
        # even if a non-veneur stage name ever lands here); the hard
        # cardinality cap above still applies
        g._overload = None if name == "self_timers" else self._overload
        g._quarantine = self.quarantine
        g._compute = self.compute
        # the slab-backed groups' per-slab dispatch-ahead window rides
        # the same knob as the store-level pipeline
        g._pipeline_window = max(1, self.flush_pipeline_depth)

    def _truncate_tags(self, joined: str) -> str:
        """Hard per-series tag-length cap: cut the joined tag string at
        the last whole tag inside ``max_tag_length`` (identities merge —
        that is the point: an adversarial tag bomb must stop costing
        memory at the cap). Counted per occurrence."""
        from veneur_tpu.samplers.parser import truncate_joined_tags

        limit = self.max_tag_length
        if not limit or len(joined) <= limit:
            return joined
        self.quarantine.count("oversized_tags")
        return truncate_joined_tags(joined, limit)

    # -- dogfooded self-telemetry (veneur_tpu/obs/) ------------------------

    @acquires_lock("store")
    def sample_self_timing(self, stage: str, duration_ns: float,
                           name: str = "veneur.obs.stage_duration_ns"
                           ) -> None:
        """One observed stage duration into the dedicated self-telemetry
        digest group: the flusher feeds every interval's stage
        durations (and the ingest lanes' seal->merge latencies) here,
        so the next flush emits exact p50/p99 of the server's own
        stages through the same t-digest pipeline it sells
        (``veneur.obs.stage_duration_ns`` tagged ``stage:<name>``).
        ``name`` overrides the metric for the few rows that are their
        own metric (``veneur.fleet.e2e_age_ns``, the fleet-freshness
        measure — docs/observability.md "Fleet tracing"). Exempt from
        the overload freeze (_apply_overload_attrs)."""
        tag = f"stage:{stage}"
        key = MetricKey(name=name, type="timer", joined_tags=tag)
        with self._lock:
            self.self_timers.sample(key, [tag], float(duration_ns), 1.0)

    # -- ingest ------------------------------------------------------------

    @acquires_lock("store")
    def process_metric(self, m: UDPMetric):
        """Dispatch one parsed sample to its scope-class (worker.go:267-310).

        The tag-length cap re-checks here because this is the ONE choke
        point every lane shares: the statsd parser caps at parse, but
        SSF-borne samples (UDP spans, the native slow lane, extraction-
        sink metrics) arrive with unbounded joined tags."""
        key = m.key
        if (self.max_tag_length
                and len(key.joined_tags) > self.max_tag_length):
            joined = self._truncate_tags(key.joined_tags)
            m.key = key = MetricKey(name=key.name, type=key.type,
                                    joined_tags=joined)
            m.tags = joined.split(",") if joined else []
        with self._lock:
            self.processed += 1
            t = m.key.type
            if t == "counter":
                group = self.global_counters if m.scope == GLOBAL_ONLY else self.counters
                group.sample(m.key, m.tags, m.value, m.sample_rate)
            elif t == "gauge":
                group = self.global_gauges if m.scope == GLOBAL_ONLY else self.gauges
                group.sample(m.key, m.tags, m.value, m.sample_rate)
            elif t == "histogram":
                group = self.local_histograms if m.scope == LOCAL_ONLY else self.histograms
                group.sample(m.key, m.tags, m.value, m.sample_rate)
            elif t == "timer":
                group = self.local_timers if m.scope == LOCAL_ONLY else self.timers
                group.sample(m.key, m.tags, m.value, m.sample_rate)
            elif t == "set":
                # bare-tag form from DogStatsD, scope form from the SSF
                # lanes (whose "k:v" tag encoding never yields the bare
                # string)
                if "veneurtopk" in m.tags or m.scope == _TOPK_SCOPE:
                    self.heavy_hitters.sample(m.key, m.tags, str(m.value))
                else:
                    group = (self.local_sets if m.scope == LOCAL_ONLY
                             else self.sets)
                    group.sample(m.key, m.tags, str(m.value))
            elif t == "status":
                self.local_status_checks.sample(
                    m.key, m.tags, float(m.value), m.sample_rate,
                    message=m.message, hostname=m.hostname)
            # unknown types are dropped, as in the reference

    @acquires_lock("store")
    def process_batch(self, batch) -> List[bytes]:
        """Vectorized ingest of a native ParsedBatch (veneur_tpu.native):
        one lock acquisition per batch, one interning dict hit per record,
        and per-group numpy bulk appends into the staging buffers — instead
        of the per-sample parse/lock/branch chain (the GIL-bound path the
        round-1 verdict flagged). Returns the raw event/service-check lines
        for the caller to route through the Python parser.

        Matches the reference's ingest semantics exactly: worker sharding
        collapses to row interning (server.go:670-720), Go counter
        truncation and gauge last-write-wins follow samplers.go:141-143,
        225-227.
        """
        raws: List[bytes] = []
        if batch.count == 0:
            return raws
        arena = batch.arena
        values, rates = batch.value, batch.sample_rate
        with self._lock:
            if self._native_table is None:
                from veneur_tpu import native

                self._native_table = native.InternTable()
            # the C++ table maps every record to its memoized row in one
            # pass; only first-sight series fall into the Python slow path
            rows, kinds, miss = self._native_table.assign(batch)
            if len(miss):
                types, scopes = batch.type, batch.scope
                noffs, nlens = batch.name_off, batch.name_len
                toffs, tlens = batch.tags_off, batch.tags_len
                # intra-batch dedup only: once put() teaches the C++ table
                # a key, later batches never miss on it again
                cache: Dict[Tuple, Tuple] = {}
                table = self._native_table
                for j in miss:
                    j = int(j)
                    t, sc = int(types[j]), int(scopes[j])
                    no, nl = noffs[j], nlens[j]
                    to, tl = toffs[j], tlens[j]
                    ck = (t, sc, arena[no:no + nl], arena[to:to + tl])
                    ent = cache.get(ck)
                    if ent is None:
                        ent = self._intern_native(t, sc, ck[2], ck[3])
                        cache[ck] = ent
                        table.put(ent[0], ck[2], ck[3], ent[2])
                    rows[j] = ent[2]
            self.processed += int(batch.count)
            member_hashes = None
            for kind in np.unique(kinds):
                sel = np.nonzero(kinds == kind)[0]
                if kind == _KIND_RAW:  # raw events / service checks
                    aoffs, alens = batch.aux_off, batch.aux_len
                    for j in sel:
                        raws.append(arena[aoffs[j]:aoffs[j] + alens[j]])
                    self.processed -= len(sel)  # counted when re-parsed
                    continue
                grp_rows = rows[sel].astype(np.int32)
                group = self._group_for_kind(kind)
                group.ensure_capacity(int(grp_rows.max()))
                if kind in (_K_COUNTER, _K_GLOBAL_COUNTER):
                    # numerics quarantine: NaN/Inf values cast to int64
                    # garbage and oversized contributions overflow the
                    # exact counter lanes — scrub before the cast
                    ok = _scrub_counter_batch(self.quarantine,
                                              values[sel], rates[sel])
                    if not ok.all():
                        group.scrubbed += len(sel) - int(ok.sum())
                        sel = sel[ok]
                        grp_rows = grp_rows[ok]
                        if not len(sel):
                            continue
                    # int64(value) * int64(float32(1)/float32(rate)),
                    # both truncating (samplers.go:141-143) — the SAME
                    # f32 reciprocal the scrub mask bounded, so nothing
                    # admitted can wrap the int64 product
                    recips = (np.float32(1.0)
                              / rates[sel].astype(np.float32))
                    contribs = (values[sel].astype(np.int64)
                                * recips.astype(np.int64))
                    group.add_many(grp_rows, contribs)
                elif kind in (_K_GAUGE, _K_GLOBAL_GAUGE):
                    ok = _scrub_float_batch(self.quarantine, values[sel])
                    if not ok.all():
                        group.scrubbed += len(sel) - int(ok.sum())
                        sel = sel[ok]
                        grp_rows = grp_rows[ok]
                        if not len(sel):
                            continue
                    group.set_many(grp_rows, values[sel])
                elif kind in (_K_SET, _K_LOCAL_SET):
                    if member_hashes is None:
                        member_hashes = batch.member_hashes()
                    group.sample_many(grp_rows, member_hashes[sel])
                elif kind == _K_TOPK:
                    if member_hashes is None:
                        member_hashes = batch.member_hashes()
                    aoffs, alens = batch.aux_off, batch.aux_len
                    members = [arena[aoffs[j]:aoffs[j] + alens[j]]
                               for j in sel]
                    group.sample_many(grp_rows, member_hashes[sel],
                                      members)
                else:
                    group.sample_many(
                        grp_rows, values[sel].astype(np.float32),
                        (1.0 / rates[sel]).astype(np.float32))
        return raws

    @requires_lock("store")
    def _group_for_kind(self, kind: int):
        if self._kind_groups is None:
            self._kind_groups = (
                self.counters, self.global_counters, self.gauges,
                self.global_gauges, self.histograms, self.local_histograms,
                self.timers, self.local_timers, self.sets, self.local_sets,
                self.heavy_hitters)
        return self._kind_groups[kind]

    @requires_lock("store")
    def _native_identity(self, t: int, name_b: bytes,
                         tags_b: bytes) -> Tuple[MetricKey, List[str], bool]:
        """A native record's series identity: its ``MetricKey``, its tag
        list and whether the tag-length cap cut the tags (counted here,
        once; a caller that interns the identity again counts again)."""
        raw = tags_b.decode("utf-8", "replace")
        joined = self._truncate_tags(raw)
        key = MetricKey(name=name_b.decode("utf-8", "replace"),
                        type=_NATIVE_TYPE_NAMES[t], joined_tags=joined)
        return (key, joined.split(",") if joined else [],
                len(joined) < len(raw))

    @requires_lock("store")
    def _intern_native(self, t: int, sc: int, name_b: bytes,
                       tags_b: bytes) -> Tuple[int, object, int]:
        """Slow path of the native-batch cache: decode strings, pick the
        scope-class group (worker.go:96-157), intern the row."""
        key, tags, _ = self._native_identity(t, name_b, tags_b)
        if t == 0:
            if sc == GLOBAL_ONLY:
                kind, group = _K_GLOBAL_COUNTER, self.global_counters
            else:
                kind, group = _K_COUNTER, self.counters
        elif t == 1:
            if sc == GLOBAL_ONLY:
                kind, group = _K_GLOBAL_GAUGE, self.global_gauges
            else:
                kind, group = _K_GAUGE, self.gauges
        elif t == 2:
            if sc == LOCAL_ONLY:
                kind, group = _K_LOCAL_HISTO, self.local_histograms
            else:
                kind, group = _K_HISTO, self.histograms
        elif t == 3:
            if sc == LOCAL_ONLY:
                kind, group = _K_LOCAL_TIMER, self.local_timers
            else:
                kind, group = _K_TIMER, self.timers
        else:
            if sc == _TOPK_SCOPE:
                kind, group = _K_TOPK, self.heavy_hitters
            elif sc == LOCAL_ONLY:
                kind, group = _K_LOCAL_SET, self.local_sets
            else:
                kind, group = _K_SET, self.sets
        return kind, group, group._row(key, tags)

    # -- import (global-aggregator ingest) ---------------------------------

    @acquires_lock("store")
    def import_counter(self, key: MetricKey, tags: List[str], value: int):
        """Imported counters are global by definition (worker.go:313-326)."""
        with self._lock:
            self.imported += 1
            self.global_counters.combine(key, tags, value)

    @acquires_lock("store")
    def import_gauge(self, key: MetricKey, tags: List[str], value: float):
        with self._lock:
            self.imported += 1
            self.global_gauges.combine(key, tags, value)

    @acquires_lock("store")
    def import_digest(self, key: MetricKey, tags: List[str],
                      means: np.ndarray, weights: np.ndarray,
                      dmin: float, dmax: float):
        with self._lock:
            self.imported += 1
            group = self.timers if key.type == "timer" else self.histograms
            group.import_centroids(key, tags, means, weights, dmin, dmax)

    @acquires_lock("store")
    def import_digests_bulk(self, entries: List[tuple]):
        """Merge many forwarded digests in one pass: one lock hold, one
        flat staging append per group instead of a per-metric call chain
        (the gRPC import server's hot path; cf. the reference's
        per-worker chunking, importsrv/server.go:99-132).

        entries: [(key, tags, means, weights, dmin, dmax)]."""
        with self._lock:
            self.imported += len(entries)
            for want_timer, group in ((False, self.histograms),
                                      (True, self.timers)):
                sel = [e for e in entries
                       if (e[0].type == "timer") == want_timer]
                if not sel:
                    continue  # lint: ok(silent-drop) emptiness guard: zero entries selected for this group, nothing in flight to credit
                if not hasattr(group, "import_centroids_bulk"):
                    for key, tags, means, weights, dmin, dmax in sel:
                        group.import_centroids(key, tags, means, weights,
                                               dmin, dmax)
                    continue
                total = sum(len(e[2]) for e in sel)
                flat_rows = np.empty(total, np.int32)
                flat_means = np.empty(total, np.float32)
                flat_wts = np.empty(total, np.float32)
                stat_rows: List[int] = []
                stat_mins: List[float] = []
                stat_maxs: List[float] = []
                pos = 0
                for key, tags, means, weights, dmin, dmax in sel:
                    row = group._row(key, tags)
                    n = len(means)
                    flat_rows[pos:pos + n] = row
                    flat_means[pos:pos + n] = means
                    flat_wts[pos:pos + n] = weights
                    pos += n
                    if math.isfinite(dmin):
                        stat_rows.append(row)
                        stat_mins.append(dmin)
                        stat_maxs.append(dmax)
                group.import_centroids_bulk(flat_rows, flat_means,
                                            flat_wts, stat_rows,
                                            stat_mins, stat_maxs)

    @acquires_lock("store")
    def import_set(self, key: MetricKey, tags: List[str],
                   registers: np.ndarray):
        with self._lock:
            self.imported += 1
            self.sets.import_registers(key, tags, registers)

    @acquires_lock("store")
    def import_columnar(self, dec, data: bytes,
                        decode_ns: int = 0) -> Tuple[int, int]:
        """Merge a natively-decoded MetricList (native/egress.py
        DecodedMetricList) in one pass: C++ row assignment, numpy bulk
        staging per payload kind — the import-side twin of process_batch,
        and the fix for the 35k series/s Python-decode ceiling the
        round-2 verdict flagged. ``data`` is the original request bytes
        (set register spans point into it). Returns (n_ok, n_err).

        The message's way through is clocked into ``import_ns`` (four
        clock reads a message, the merger's pattern): ``decode_ns`` as
        the caller measured it, the wait for the store lock, interning
        (the native table's assign and the miss loop, with a mesh's
        first-sight placement), staging (the bulk appends), and out of
        staging what the digest groups' drains spent routing chunks to
        shards and dispatching them.

        Reference path: importsrv.SendMetrics group-by-worker +
        ImportMetricGRPC → per-sampler Merge (importsrv/server.go:101-132,
        worker.go:354-398)."""
        from veneur_tpu.forward.convert import decode_hll, type_name
        from veneur_tpu.native import egress

        PB_TIMER = 4
        n_err = 0
        t0 = time.monotonic_ns()
        with self._lock:
            t1 = time.monotonic_ns()
            if self._mlist_table is None:
                self._mlist_table = egress.MListInternTable()
            table = self._mlist_table
            rows, miss = table.assign(dec)
            if len(miss):
                arena = dec.arena
                for i in miss:
                    i = int(i)
                    t = int(dec.type[i])
                    pay = int(dec.payload[i])
                    no, nl = dec.name_off[i], dec.name_len[i]
                    to, tl = dec.tags_off[i], dec.tags_len[i]
                    name_b, tags_b = arena[no:no + nl], arena[to:to + tl]
                    try:
                        tname = type_name(t)
                        if pay == egress.PAYLOAD_COUNTER:
                            group = self.global_counters
                        elif pay == egress.PAYLOAD_GAUGE:
                            group = self.global_gauges
                        elif pay == egress.PAYLOAD_HISTOGRAM:
                            group = (self.timers if t == PB_TIMER
                                     else self.histograms)
                        elif pay == egress.PAYLOAD_SET:
                            group = self.sets
                        else:
                            raise ValueError("metric has no value")
                    except ValueError:
                        # unknown type enum / empty oneof: rows stays
                        # MISS and the apply phase counts it
                        continue  # lint: ok(silent-drop, swallowed-exception) deferred credit: the row stays MISS and the apply phase folds the miss mask into n_err below
                    name = name_b.decode("utf-8", "replace")
                    joined = self._truncate_tags(
                        tags_b.decode("utf-8", "replace"))
                    tags = joined.split(",") if joined else []
                    key = MetricKey(name=name, type=tname,
                                    joined_tags=joined)
                    row = group._row(key, tags)
                    rows[i] = row
                    table.put(t, pay, name_b, tags_b, row)

            t2 = time.monotonic_ns()
            routed0, dispatched0 = self._digest_drain_ns()
            ok = rows != egress.MISS
            n_err += int((~ok).sum())
            payload = dec.payload
            n_ok = 0

            sel = np.flatnonzero(ok & (payload == egress.PAYLOAD_COUNTER))
            if len(sel):
                grp_rows = rows[sel].astype(np.int64)
                self.global_counters.ensure_capacity(int(grp_rows.max()))
                self.global_counters.add_many(grp_rows, dec.ivalue[sel])
                n_ok += len(sel)

            sel = np.flatnonzero(ok & (payload == egress.PAYLOAD_GAUGE))
            if len(sel):
                grp_rows = rows[sel].astype(np.int64)
                self.global_gauges.ensure_capacity(int(grp_rows.max()))
                self.global_gauges.set_many(grp_rows, dec.dvalue[sel])
                n_ok += len(sel)

            histo_sel = ok & (payload == egress.PAYLOAD_HISTOGRAM)
            for group, type_match in ((self.histograms,
                                       dec.type != PB_TIMER),
                                      (self.timers, dec.type == PB_TIMER)):
                sel = np.flatnonzero(histo_sel & type_match)
                if not len(sel):
                    continue
                grp_rows = rows[sel]
                group.ensure_capacity(int(grp_rows.max()))
                lens = dec.cent_len[sel].astype(np.int64)
                starts = dec.cent_off[sel].astype(np.int64)
                total = int(lens.sum())
                if total:
                    # grouped-arange gather of each digest's centroid span
                    span_ends = np.cumsum(lens)
                    within = (np.arange(total, dtype=np.int64)
                              - np.repeat(span_ends - lens, lens))
                    idx = np.repeat(starts, lens) + within
                    flat_rows = np.repeat(grp_rows, lens).astype(np.int32)
                    means = dec.means[idx]
                    weights = dec.weights[idx]
                else:
                    flat_rows = np.empty(0, np.int32)
                    means = weights = np.empty(0, np.float64)
                stat_mask = np.isfinite(dec.dmin[sel])
                try:
                    # every digest group (dense, slab, mesh) shares the
                    # module-level staging protocol
                    bulk_stage_import_centroids(
                        group, flat_rows, means, weights,
                        grp_rows[stat_mask].astype(np.int32),
                        dec.dmin[sel][stat_mask].astype(np.float32),
                        dec.dmax[sel][stat_mask].astype(np.float32))
                    n_ok += len(sel)
                except Exception:
                    n_err += len(sel)
                    log.exception("bulk digest import failed; "
                                  "dropping %d digests", len(sel))

            sel = np.flatnonzero(ok & (payload == egress.PAYLOAD_SET))
            for i in sel:
                i = int(i)
                try:
                    ho, hn = int(dec.hll_off[i]), int(dec.hll_len[i])
                    registers, _ = decode_hll(data[ho:ho + hn])
                    self.sets.import_registers_row(int(rows[i]), registers)
                    n_ok += 1
                except Exception as e:
                    n_err += 1
                    log.debug("store rejected imported set: %s", e)

            if dec.topk_len:
                # MetricList.topk extension: a small submessage — parse
                # with protobuf and merge through the sketch path
                from veneur_tpu.forward.convert import decode_topk_sketch
                from veneur_tpu.protocol import forward_pb2

                try:
                    pb = forward_pb2.TopKSketch.FromString(
                        data[dec.topk_off:dec.topk_off + dec.topk_len])
                    cm_table, series = decode_topk_sketch(pb)
                    entries = [(MetricKey(name=name, type="set",
                                          joined_tags=",".join(tags)),
                                tags, keys, members)
                               for name, tags, keys, members in series]
                    self.heavy_hitters.import_sketch(cm_table, entries)
                    n_ok += 1
                except Exception as e:
                    n_err += 1
                    log.debug("store rejected imported topk sketch: %s", e)

            self.imported += n_ok
            routed, dispatched = self._digest_drain_ns()
            routed -= routed0
            dispatched -= dispatched0
            ns = self.import_ns
            ns["messages"] += 1
            ns["decode"] += decode_ns
            ns["lock_wait"] += t1 - t0
            ns["intern"] += t2 - t1
            ns["route"] += routed
            ns["dispatch"] += dispatched
            ns["stage"] += (time.monotonic_ns() - t2 - routed
                            - dispatched)
            return n_ok, n_err

    def _digest_drain_ns(self) -> Tuple[int, int]:
        """What the digest groups' import drains have spent so far
        routing chunks to shards and dispatching them (a slab group
        keeps no such clock)."""
        groups = (self.histograms, self.timers)
        return (sum(getattr(g, "imp_route_ns", 0) for g in groups),
                sum(getattr(g, "imp_dispatch_ns", 0) for g in groups))

    @acquires_lock("store")
    def warm_mesh(self, percentiles, aggregates: HistogramAggregates,
                  samples: bool, imports: bool) -> None:
        """A mesh store's start, whatever feeds it: have the programs
        an interval runs compiled before a listener opens
        (MeshDigestGroup.warm), for the ways in that are open and with
        the fetch selection the flushes will ask for; histograms and
        timers are one shape, so one of them does."""
        warm = getattr(self.histograms, "warm", None)
        if warm is not None:
            _, want_stats = _digest_want(percentiles, aggregates, False,
                                         "")
            with self._lock:
                warm(percentiles, want_stats, samples, imports)

    @acquires_lock("store")
    def take_import_stages(self) -> Optional[Dict[str, int]]:
        """The import path's ns by stage, and its messages, since the
        last call (the flusher publishes them as the interval's
        ``import.*`` stages); None where nothing was imported."""
        with self._lock:
            taken = self.import_ns
            if not taken["messages"]:
                return None
            self.import_ns = dict.fromkeys(taken, 0)
        return taken

    # -- ingest-lane merge (veneur_tpu/ingest/) ----------------------------

    # lane kind -> (native record type, scope) for re-interning lane
    # entries through _intern_native: the inverse of kind_of() in
    # native/veneur_ingest.cpp (MIXED_SCOPE falls through to the
    # non-global/non-local branch for every type)
    _KIND_NATIVE = {
        _K_COUNTER: (0, 0), _K_GLOBAL_COUNTER: (0, GLOBAL_ONLY),
        _K_GAUGE: (1, 0), _K_GLOBAL_GAUGE: (1, GLOBAL_ONLY),
        _K_HISTO: (2, 0), _K_LOCAL_HISTO: (2, LOCAL_ONLY),
        _K_TIMER: (3, 0), _K_LOCAL_TIMER: (3, LOCAL_ONLY),
        _K_SET: (4, 0), _K_LOCAL_SET: (4, LOCAL_ONLY),
        _K_TOPK: (4, _TOPK_SCOPE)}

    def set_ingest_drain(self, drain) -> None:
        """Register the ingest fleet's sealed-chunk drain; the
        checkpoint snapshot calls it so mid-flight lane chunks are
        captured (veneur_tpu/ingest/IngestFleet.merge_sealed)."""
        self._ingest_drain = drain

    @acquires_lock("store")
    def import_lane_chunk(self, chunk, resolver,
                          timing: Optional[Dict[str, int]] = None
                          ) -> List[bytes]:
        """Merge one sealed ingest-lane chunk under ONE store-lock hold
        — the group-boundary half of the reader-lane design
        (veneur_tpu/ingest/lanes.py): readers stage lock-free against
        lane-local rows; this is the only place their samples meet
        shared state, one lock acquisition per CHUNK instead of per
        metric.

        ``resolver`` is the merger's per-lane LaneResolver: its
        accumulated (name, tags) registry remaps lane rows onto the
        store interners. The remap invalidates whole when the flush
        epoch moved (fresh generation twins restart their interners)
        and rebuilds lazily from the registry. Values arrive already
        scrubbed and in Go semantics (contribs truncated, weights as
        f32 reciprocals) — the same bits process_batch would stage.

        ``timing`` (the merger's ``IngestFleet.merge_ns``, or None with
        stage tracing off) gains the chunk's ns waiting for the lock
        (``lock_wait``), remapping every kind's lane rows (``remap``;
        ``rows_interned`` counts the first-sight rows it interned and
        ``rows_reinterned`` those of them interned from an identity the
        store had cached; of it ``route``, a mesh's placing of those
        rows on their shards, read off the router's own clock) and
        staging them (``stage``): four clock reads a chunk, which is
        why the kinds are remapped first and staged after.

        Returns the chunk's raw event/service-check lines for the
        caller to route through the Python parser OUTSIDE the lock."""
        t0 = time.monotonic_ns() if timing is not None else 0
        with self._lock:
            t1 = time.monotonic_ns() if timing is not None else 0
            if resolver.epoch != self.flush_epoch:
                resolver.remap = [None] * len(resolver.remap)
                resolver.epoch = self.flush_epoch
            for kind, new in chunk.new_entries.items():
                resolver.entries[kind].extend(new)
            interned = reinterned = 0
            staged = []
            router = self.shard_router if timing is not None else None
            placed = router.place_ns if router is not None else 0
            for kind, span in chunk.spans.items():
                remap, first_sight, cached = self._lane_remap(
                    kind, resolver, span[0])
                interned += first_sight
                reinterned += cached
                staged.append((kind, span, remap[span[0]]))
            t2 = time.monotonic_ns() if timing is not None else 0
            for kind, span, grp_rows in staged:
                group = self._group_for_kind(kind)
                group.ensure_capacity(int(grp_rows.max()))
                if kind in (_K_COUNTER, _K_GLOBAL_COUNTER):
                    group.add_many(grp_rows, span[1])
                elif kind in (_K_GAUGE, _K_GLOBAL_GAUGE):
                    group.set_many(grp_rows, span[1])
                elif kind in (_K_SET, _K_LOCAL_SET):
                    group.sample_many(grp_rows.astype(np.int32), span[1])
                elif kind == _K_TOPK:
                    group.sample_many(grp_rows.astype(np.int32), span[1],
                                      span[3])
                else:
                    group.sample_many(grp_rows.astype(np.int32), span[1],
                                      span[2])
            self.processed += chunk.records
            if timing is not None:
                timing["lock_wait"] += t1 - t0
                timing["remap"] += t2 - t1
                if router is not None:
                    timing["route"] += router.place_ns - placed
                timing["stage"] += time.monotonic_ns() - t2
                timing["rows_interned"] += interned
                timing["rows_reinterned"] += reinterned
        return chunk.raws

    @requires_lock("store")
    def _lane_remap(self, kind: int, resolver, rows) -> tuple:
        """Lane-row -> store-row array for one kind, resolved LAZILY
        per referenced row (-1 = unresolved): only rows the incoming
        chunk actually carries re-intern after a flush-epoch bump, so
        an idle series the lane once saw is NOT resurrected into every
        fresh store generation (it would emit as zero forever), and
        the under-lock work is bounded by the chunk's live rows, not
        the lane's lifetime registry.

        A series is decoded once: ``_native_identity``'s key, tag list
        and truncation flag are kept under the entry's (name, tags)
        bytes in ``_lane_identities``, where every other lane that
        carries the series finds them, and in place of the bytes in the
        lane's registry, so every later flush epoch interns them again
        as they are. The memo of a kind starts over when it holds as
        many series as a lane's registry may (``max_series``, else
        2^20); the registries keep what they hold.
        The chunk's unresolved rows of the kind intern as one batch
        (``intern_many``), so the tag-length cap (counted each time an
        identity is interned) and the overload spill/freeze semantics
        apply to lane-merged series exactly as to every other ingest
        path. Returns the array, how many rows this call interned and
        how many of them from a cached identity."""
        entries = resolver.entries[kind]
        remap = resolver.remap[kind]
        if remap is None or len(remap) < len(entries):
            grown = np.full(len(entries), -1, np.int64)
            if remap is not None and len(remap):
                grown[:len(remap)] = remap
            remap = resolver.remap[kind] = grown
        needed = np.unique(rows)
        todo = needed[remap[needed] < 0]
        if not len(todo):
            return remap, 0, 0
        t = self._KIND_NATIVE[kind][0]
        memo = self._lane_identities[kind]
        limit = self.max_series or (1 << 20)
        keys, tags = [], []
        decoded = cut = 0
        for r in todo.tolist():
            ident = entries[r]
            if len(ident) == 2:  # the lane's bytes: its first resolve
                raw, ident = ident, memo.get(ident)
                if ident is None:
                    if len(memo) >= limit:
                        memo.clear()
                    # a decode counts its own cut (_truncate_tags)
                    ident = memo[raw] = self._native_identity(t, *raw)
                    decoded += 1
                    cut -= ident[2]
                entries[r] = ident
            cut += ident[2]
            keys.append(ident[0])
            tags.append(ident[1])
        if cut:
            self.quarantine.count("oversized_tags", cut)
        remap[todo] = self._group_for_kind(kind).intern_many(keys, tags)
        return remap, len(todo), len(todo) - decoded

    @acquires_lock("store")
    def import_topk(self, table: np.ndarray, series: List[tuple]):
        """Merge a forwarded heavy-hitter sketch (see
        HeavyHitterGroup.import_sketch); series entries carry plain
        (name, tags, keys, members) — MetricKeys are built here."""
        with self._lock:
            self.imported += 1
            entries = [(MetricKey(name=name, type="set",
                                  joined_tags=",".join(tags)),
                        tags, keys, members)
                       for name, tags, keys, members in series]
            self.heavy_hitters.import_sketch(table, entries)

    # -- checkpoint snapshot / restore (veneur_tpu/persist/) ---------------

    # the metric-type string each group's keys carry, for rebuilding
    # MetricKeys at restore time
    _GROUP_TYPES = {
        "counters": "counter", "global_counters": "counter",
        "gauges": "gauge", "global_gauges": "gauge",
        "local_status_checks": "status",
        "histograms": "histogram", "local_histograms": "histogram",
        "timers": "timer", "local_timers": "timer",
        "self_timers": "timer",
        "sets": "set", "local_sets": "set", "heavy_hitters": "set"}

    @acquires_lock("store")
    def snapshot_state(self) -> Tuple[Dict[str, dict], int]:
        """Host-side snapshot of every group WITHOUT resetting
        anything, in two phases: under each group's own lock hold only
        host copies are taken and device reads are DISPATCHED
        (``snapshot_begin`` — async slices of immutable buffers); the
        blocking ``jax.device_get`` fetches then run entirely OFF-lock
        (``finish``), so ingest never stalls behind a checkpoint's
        device→host transfer (the lock-order pass flags the held-fetch
        shape) and disk IO stays the caller's job. Returns ``(groups,
        flush_epoch)``: the writer must discard the snapshot if the
        epoch moved before it commits — which also covers a flush swap
        landing BETWEEN group holds (the mixed snapshot's epoch no
        longer matches, so it is dropped and the next cadence
        retries; the swapped-out groups' captured slices stay valid —
        they are fresh buffers the retired flush cannot donate)."""
        # ingest lanes first: sealed-but-unmerged chunks carry real
        # samples — fold them in (off-lock; the drain takes the store
        # lock per chunk itself) so the snapshot's coverage matches
        # what the lanes have already accepted
        drain = self._ingest_drain
        if drain is not None:
            try:
                drain()
            except Exception:
                log.exception("pre-snapshot ingest drain failed")
        with self._lock:
            epoch = self.flush_epoch
        groups = {}
        fetches = []
        for name in self._GEN_GROUPS:
            with self._lock:
                snap, finish = getattr(self, name).snapshot_begin()
            groups[name] = snap
            if finish is not None:
                fetches.append(finish)
        for finish in fetches:  # blocking device reads, no lock held
            finish()
        return groups, epoch

    @acquires_lock("store")
    def restore_state(self, groups: Dict[str, dict],
                      prefer_live_scalars: bool = False) -> int:
        """Merge a recovered snapshot into the live store with the same
        semantics as the import path (counters add, gauges last-write,
        digests re-enter the centroid binning pipeline, sets register-
        max, count-min tables add) — so recovery composes with global
        aggregation exactly like a forwarded sketch would. Returns the
        number of series merged. Unknown groups and config mismatches
        (HLL precision, count-min geometry) skip that group with a
        warning; nothing here raises.

        ``prefer_live_scalars=True`` is for re-merging RETIRED state
        into a store that kept ingesting (the handoff kept-half and
        requeue paths): an overwrite-semantics scalar row (gauge,
        status) that already exists live carries a NEWER sample than
        the retired snapshot — last-write-wins must let the live value
        win, so those rows are skipped instead of clobbered. Counters
        always add; a cold startup restore (empty store) is
        unaffected either way."""
        merged = 0
        with self._lock:
            for name, snap in groups.items():
                tname = self._GROUP_TYPES.get(name)
                target = getattr(self, name, None)
                if (tname is None or target is None
                        or not isinstance(snap, dict)):
                    log.warning("checkpoint restore: unknown group %r; "
                                "skipping", name)
                    continue
                try:
                    merged += self._restore_group(
                        name, tname, target, snap,
                        prefer_live_scalars=prefer_live_scalars)
                except Exception:
                    log.exception("checkpoint restore: group %s failed; "
                                  "skipping it", name)
        return merged

    @requires_lock("store")
    def _restore_group(self, name: str, tname: str, target,
                       snap: dict, prefer_live_scalars: bool = False) -> int:
        kind = snap.get("kind")
        names, joined = snap.get("names", []), snap.get("joined", [])
        n = len(names)

        def keys():
            for i in range(n):
                jt = joined[i]
                yield i, MetricKey(name=names[i], type=tname,
                                   joined_tags=jt), \
                    (jt.split(",") if jt else [])

        if kind == "scalar":
            values = snap.get("values", ())
            messages = snap.get("messages")
            hostnames = snap.get("hostnames")
            # overwrite-semantics rows (gauges, status): when the live
            # store kept ingesting past the snapshot, its value is the
            # newer write — skip, don't clobber (see restore_state)
            skip_live = (prefer_live_scalars
                         and getattr(target, "kind", "") != "counter")
            merged = 0
            for i, key, tags in keys():
                if skip_live and key in target.interner.rows:
                    continue
                merged += 1
                if messages is not None:
                    target.sample(key, tags, float(values[i]), 1.0,
                                  message=messages[i],
                                  hostname=hostnames[i])
                else:
                    target.combine(key, tags, values[i])
            return merged
        if kind == "digest":
            if n == 0:
                return 0
            row_map = np.empty(n, np.int32)
            for i, key, tags in keys():
                row_map[i] = target._row(key, tags)
            rows = row_map[np.asarray(snap["rows"], np.int64)]
            mins, maxs = snap["mins"], snap["maxs"]
            finite = np.isfinite(mins)
            bulk_stage_import_centroids(
                target, rows, snap["means"], snap["weights"],
                row_map[finite], mins[finite], maxs[finite])
            target.restore_stats(row_map, snap["count"], snap["vsum"],
                                 snap["vmin"], snap["vmax"],
                                 snap["recip"])
            return n
        if kind == "set":
            if snap.get("precision") != target.precision:
                log.warning("checkpoint restore: %s has HLL precision "
                            "%s, store runs %d; skipping the group",
                            name, snap.get("precision"),
                            target.precision)
                return 0
            registers = snap.get("registers", ())
            for i, key, tags in keys():
                target.import_registers(key, tags, registers[i])
            return n
        if kind == "topk":
            table = snap.get("table")
            if table is None or n == 0:
                return 0
            if (snap.get("depth"), snap.get("width")) != (target.depth,
                                                          target.width):
                log.warning("checkpoint restore: %s count-min geometry "
                            "%sx%s != store %dx%d; skipping the group",
                            name, snap.get("depth"), snap.get("width"),
                            target.depth, target.width)
                return 0
            series = snap.get("series", [])
            entries = []
            for i, key, tags in keys():
                s = series[i] if i < len(series) else {"keys": [],
                                                       "members": []}
                entries.append((key, tags,
                                [tuple(p) for p in s["keys"]],
                                s["members"]))
            target.import_sketch(np.asarray(table, np.float32), entries)
            return n
        log.warning("checkpoint restore: group %s has unknown kind %r; "
                    "skipping", name, kind)
        return 0

    # -- elastic resharding (veneur_tpu/fleet/handoff.py) ------------------

    # the ring-routed groups: the state the import path feeds, i.e.
    # what locals forward through the proxy ring and what a fleet
    # resize therefore moves. Mixed scalars/locals are this host's own
    # telemetry and always stay. Heavy hitters move too: the candidate
    # series split by the ring rule like any set, and the count-min
    # table — cross-series, not partitionable by key — rides WHOLE with
    # every part (a linear sketch merges by element-wise add, so the
    # new owner's estimates stay one-sided upper bounds; the accuracy
    # cost is the documented e/w · ΣN overcount widening with the
    # donor's full table weight — docs/tiered.md "Merging count-min
    # tables").
    _HANDOFF_GROUPS = ("global_counters", "global_gauges", "histograms",
                       "timers", "sets", "heavy_hitters")

    @acquires_lock("store")
    def handoff_extract(self, route_fn,
                        route_many=None) -> Tuple[Dict[str, Dict[str, dict]],
                                                  int]:
        """Elastic-resharding range extraction (docs/resilience.md
        "Elastic resharding"): atomically retire the live generation —
        the same swap a flush performs, so the flush-epoch guard covers
        it (checkpoint commits and lane resolvers straddling the swap
        invalidate exactly as they do for a flush) — snapshot the
        retired groups OFF-lock (two-phase, exclusively owned), split
        the ring-routed groups by ``route_fn``, and re-merge everything
        that STAYS into the live store with import semantics. Owned
        state lives in exactly one place at every instant: samples
        arriving during the extraction land in the fresh live
        generation, so a resize can neither lose nor double-count.

        ``route_fn(name, type_str, joined_tags)`` returns the new
        owner's address, or None to keep; ``route_many`` is the
        optional batched form (one ring-lock hold per group — see
        ``split_group_snapshot``). Returns ``(moved, moved_series)``:
        ``moved`` maps destination -> {group: snapshot} ready for the
        handoff wire."""
        from veneur_tpu.fleet.handoff import split_group_snapshot

        # the gate serializes the swap+snapshot against a concurrent
        # flush (same contract as flush(): ingest proceeds on _lock);
        # the snapshot's blocking device fetches run under it by design
        # — a flush racing a resize would interleave two generation
        # drains otherwise
        with self._flush_gate:  # lint: ok(lock-across-blocking) the gate exists to hold across the blocking snapshot: it serializes swap+drain against a concurrent flush while ingest proceeds on _lock
            with self._lock:
                gen = self._swap_generation()
            snaps: Dict[str, dict] = {}
            for name in self._GEN_GROUPS:
                # retired generation: this thread is the sole owner,
                # the store lock is not required (cf. _requeue_group)
                group = getattr(gen, name)
                snaps[name] = group.snapshot_state()  # lint: ok(unlocked-call) retired generation — this thread is the sole owner, the store lock is not required
        moved: Dict[str, Dict[str, dict]] = {}
        kept: Dict[str, dict] = {}
        moved_series = 0
        for name, snap in snaps.items():
            if name in self._HANDOFF_GROUPS:
                parts = split_group_snapshot(
                    snap, self._GROUP_TYPES[name], route_fn,
                    route_many=route_many)
            else:
                parts = {None: snap}
            for dest, part in parts.items():
                if dest is None:
                    kept[name] = part
                else:
                    moved.setdefault(dest, {})[name] = part
                    moved_series += len(part.get("names") or ())
        self.restore_state(kept, prefer_live_scalars=True)
        with self._lock:
            # re-credit the retired interval's tallies: the samples are
            # back (kept) or leaving as owned state (moved) — either
            # way this instance processed them this interval
            self.processed += gen.processed
            self.imported += gen.imported
        return moved, moved_series

    # -- flush -------------------------------------------------------------

    def summary(self) -> MetricsSummary:
        return _summarize(self)

    @acquires_lock("store")
    def flush(self, percentiles: List[float], aggregates: HistogramAggregates,
              is_local: bool, now: int, forward: bool = True,
              forward_topk: bool = True, columnar: bool = False,
              digest_format: str = "dense", stream=None):
        """Drain everything: returns (final metrics for sinks, forwardable
        sketch state, tallies) and resets all groups.

        Mirrors generateInterMetrics (flusher.go:189-254): a local instance
        suppresses percentiles on mixed histograms/timers and does not flush
        mixed sets or global counters/gauges (those are forwarded instead);
        local-only groups always flush in full.

        columnar=True returns a ``ColumnarFlush`` instead of the
        InterMetric list (and columnar digest planes in the forwardable
        state): emissions stay flat arrays end-to-end, the fix for the
        per-row assembly that dominated large flushes. Low-cardinality
        paths (status checks, top-k, sink-routed groups) emit as extras.

        digest_format="packed" asks the forwarding digest groups to
        compact + quantize their planes on device (PackedDigestPlanes)
        instead of fetching raw f32 [S,K] planes — the mode that fits
        the flush interval at 1M+ forwarded series. Only meaningful
        with columnar=True on a forwarding local.

        SWAP-ON-FLUSH: the store lock is held only for the generation
        swap (every group object replaced by an empty same-config twin
        via ``fresh()``); the multi-second device programs and fetches
        then run on the retired generation OFF-LOCK, so ingest
        (process_batch / imports) never stalls behind a flush. This is
        the reference's design point — a brief mutex swap of
        WorkerMetrics, then flush off-lock (worker.go:402-429,
        flusher.go:134-184) — which the round-3 build inverted.
        ``_flush_gate`` serializes overlapping flush() calls so retired
        generations drain in order.

        ``stream`` (optional, a :class:`veneur_tpu.core.pipeline
        .ChunkStream`-shaped object) enables STREAMING egress: each
        completed group's emission blocks are handed over as a chunk
        the moment they exist — serialized and POSTed by the stream's
        workers while later groups are still computing/fetching —
        instead of batching the whole interval (docs/internals.md
        "Life of a flush"). With ``flush_pipeline_depth > 0`` the
        retired groups' device programs all DISPATCH before any
        blocking fetch runs, so device execution, device→host
        transfer, serialization and POST overlap as four pipeline
        lanes.
        """
        # the gate's entire job is to hold across the retired drain:
        # it serializes overlapping flush() calls (only the flusher and
        # shutdown ever contend) while ingest proceeds on _lock
        with self._flush_gate:  # lint: ok(lock-across-blocking) the gate's entire job is to hold across the multi-second retired drain; ingest never waits on it (it proceeds on _lock)
            with obs_rec.maybe_stage("swap"):
                # the swap's two leaves: the wait for the lock (the
                # merger and the import workers hold it a chunk at a
                # time), then the twins under it
                t0 = time.monotonic_ns()
                with self._lock:
                    obs_rec.record_child("lock_wait", t0,
                                         time.monotonic_ns())
                    with obs_rec.maybe_stage("twins", scope=True):
                        gen = self._swap_generation()
            out = self._flush_generation(
                gen, percentiles, aggregates, is_local, now, forward,
                forward_topk, columnar, digest_format, stream)
            # the retired generation's last reference: its groups' host
            # state is freed here
            with obs_rec.maybe_stage("release", scope=True):
                del gen
            return out

    # every group swapped per flush, in flush order (self_timers is the
    # dedicated self-telemetry group — the server's own stage durations,
    # docs/observability.md)
    _GEN_GROUPS = ("counters", "global_counters", "gauges", "global_gauges",
                   "local_status_checks", "histograms", "timers",
                   "local_histograms", "local_timers", "self_timers",
                   "sets", "local_sets", "heavy_hitters")

    @requires_lock("store")
    def _swap_generation(self) -> "_Generation":
        """Retire every group behind an empty twin; caller holds _lock.
        Also snapshots the interval tallies and invalidates the native
        intern memos (rows restart in the fresh interners)."""
        gen = _Generation()
        for attr in self._GEN_GROUPS:
            old = getattr(self, attr)
            old._retired = True  # its flush frees state, not reinits it
            setattr(gen, attr, old)
            fresh = old.fresh()
            # fresh twins start with the class-default overload attrs;
            # re-stamp the cap/ledger/breaker plumbing each swap
            self._apply_overload_attrs(attr, fresh)
            setattr(self, attr, fresh)
        gen.processed = self.processed
        gen.imported = self.imported
        self.processed = 0
        self.imported = 0
        if self.mesh is not None:
            # fleet mode: stamp the RETIRED interval's per-shard row
            # occupancy (the veneur.fleet.shard_occupancy self-metric;
            # the live /debug/vars mesh section reads current fills)
            from veneur_tpu.fleet import sum_shard_occupancy

            self.last_fleet_occupancy = sum_shard_occupancy(
                getattr(gen, attr) for attr in self._GEN_GROUPS)
        self.flush_epoch += 1
        self._kind_groups = None  # holds refs to the retired groups
        if self._native_table is not None:
            self._native_table.reset()
        if self._mlist_table is not None:
            self._mlist_table.reset()
        return gen

    def _flush_generation(self, g: "_Generation", percentiles, aggregates,
                          is_local, now, forward, forward_topk, columnar,
                          digest_format, stream=None):
        """Drain a retired generation into emissions + forwardable state.
        Runs off-lock: ``g``'s groups are exclusively owned here.

        The drain is a PLAN of per-group flush units executed by
        :meth:`_run_flush_units` — sequentially when
        ``flush_pipeline_depth`` is 0 (the pre-pipeline shape), or as
        the overlapped dispatch→fetch→serialize pipeline otherwise,
        with each completed group streamed out through ``stream`` as
        its own egress chunk."""
        with obs_rec.maybe_stage("summarize", scope=True):
            ms = _summarize(g)
        ms.processed = g.processed
        ms.imported = g.imported
        col: Optional["ColumnarFlush"] = None
        if columnar:
            from veneur_tpu.core.columnar import ColumnarFlush

            col = ColumnarFlush(timestamp=now)
            final = col.extras  # oddballs land in the legacy list
        else:
            final = []
        fwd = ForwardableState()

        # counters & gauges (mixed scope) always flush locally; host
        # numpy, so they run — and stream as the interval's first
        # chunk — before any device fetch can block
        mark = len(col.blocks) if col is not None else 0
        with obs_rec.maybe_stage("scalars"):
            self._flush_scalars(g.counters, MetricType.COUNTER, final,
                                now, col)
            self._flush_scalars(g.gauges, MetricType.GAUGE, final, now,
                                col)
            if stream is not None and col is not None \
                    and len(col.blocks) > mark:
                blocks = col.blocks[mark:]
                stream.emit("scalars", blocks,
                            sum(len(b) for b in blocks))

        # mixed histograms/timers: no percentiles on a local instance
        mixed_pcts = [] if is_local else list(percentiles)
        fwd_digests = is_local and forward
        units: List[tuple] = []

        def digest_unit(gen_name, group, pcts, fwd_list, fwd_state,
                        fwd_attr):
            forwarding = fwd_list is not None or fwd_state is not None
            want, want_stats = _digest_want(pcts, aggregates, forwarding,
                                            digest_format)

            def begin():
                return group.flush_begin(pcts, want_digests=want,
                                         want_stats=want_stats)

            def emit(res):
                interner, r = res
                self._emit_digest_result(
                    gen_name, interner, r, pcts, aggregates, final, now,
                    fwd_list, col, fwd_state, fwd_attr, stream)

            units.append((gen_name, len(group), begin, emit, group))

        digest_unit("histograms", g.histograms, mixed_pcts,
                    fwd.histograms if fwd_digests else None,
                    fwd if fwd_digests else None, "histograms_columnar")
        digest_unit("timers", g.timers, mixed_pcts,
                    fwd.timers if fwd_digests else None,
                    fwd if fwd_digests else None, "timers_columnar")
        # local-only histograms/timers: full flush with percentiles
        digest_unit("local_histograms", g.local_histograms,
                    list(percentiles), None, None, "")
        digest_unit("local_timers", g.local_timers, list(percentiles),
                    None, None, "")
        # the dedicated self-telemetry group: the server's own stage
        # durations (sample_self_timing), always local, full
        # percentiles — the server reports exact p50/p99 of its own
        # flush stages through the same sketches it sells
        digest_unit("self_timers", g.self_timers, list(percentiles),
                    None, None, "")

        # local sets always flush; mixed sets flush only on a global
        # instance (they are forwarded from locals)
        def set_unit(name, group, out_list, fwd_list, set_col):
            def begin():
                return group.flush_begin(
                    want_estimates=out_list is not None,
                    want_registers=fwd_list is not None)

            def emit(res):
                interner, estimates, registers = res
                self._emit_set_result(name, interner, estimates,
                                      registers, out_list, now,
                                      fwd_list, set_col, stream)

            units.append((name, len(group), begin, emit, None))

        set_unit("local_sets", g.local_sets, final, None, col)
        set_unit("sets", g.sets, final if not is_local else None,
                 fwd.sets if (is_local and forward) else None,
                 col if not is_local else None)

        # heavy hitters follow the mixed-SET rule (flusher.go:231-249):
        # a forwarding local ships its sketch upstream and does NOT
        # emit — the global merges tables additively, re-ranks, and
        # emits the fleet top-k under the same names (no double
        # counting downstream). When the transport cannot carry the
        # sketch (gRPC: forward_topk=False), the local emits its own
        # view instead so the data is never silently dropped.
        want_hh_fwd = is_local and forward and forward_topk

        def topk_emit(res):
            hh_interner, hh, hh_fwd = res
            fwd.topk = hh_fwd
            if want_hh_fwd:
                hh = []
            for row, member, count in hh:
                tags = hh_interner.tags[row]
                final.append(InterMetric(
                    name=f"{hh_interner.names[row]}.topk", timestamp=now,
                    value=count, tags=list(tags) + [f"key:{member}"],
                    type=MetricType.COUNTER, sinks=route_info(tags)))

        units.append((
            "topk", len(g.heavy_hitters),
            lambda: g.heavy_hitters.flush_begin(want_forward=want_hh_fwd),
            topk_emit, None))

        self._run_flush_units(units)

        # status checks are always local
        with obs_rec.maybe_stage("status", scope=True):
            self._flush_status(g.local_status_checks, final, now)

        # global counters/gauges: forwarded by locals, flushed by
        # globals, row by row either way
        with obs_rec.maybe_stage("globals", scope=True):
            if not is_local:
                self._flush_scalars(g.global_counters, MetricType.COUNTER,
                                    final, now, staged=False)
                self._flush_scalars(g.global_gauges, MetricType.GAUGE,
                                    final, now, staged=False)
            elif forward:
                interner, values, _, _ = \
                    g.global_counters.snapshot_and_reset()
                for key, row in interner.rows.items():
                    fwd.counters.append((key.name, interner.tags[row],
                                         int(values[row])))
                interner, values, _, _ = \
                    g.global_gauges.snapshot_and_reset()
                for key, row in interner.rows.items():
                    fwd.gauges.append((key.name, interner.tags[row],
                                       float(values[row])))
            else:
                g.global_counters.snapshot_and_reset()
                g.global_gauges.snapshot_and_reset()

        return (col if col is not None else final), fwd, ms

    def _flush_scalars(self, group: ScalarGroup, mtype: MetricType,
                       out: List[InterMetric], now: int, col=None,
                       staged: bool = True):
        """One scalar group's drain; ``staged`` gives its snapshot, its
        block and the snapshot's release the leaves ``snapshot``,
        ``block`` and ``release`` (the globals' row-by-row drain is a
        leaf as a whole)."""
        stage = obs_rec.maybe_stage if staged else _no_stage
        with stage("snapshot", scope=True):
            interner, values, _, _ = group.flush_begin()()
        if col is not None and len(interner):
            from veneur_tpu.core import columnar as cb

            with stage("block", scope=True):
                block = cb.scalar_block(
                    interner, values,
                    cb.TYPE_COUNTER if mtype == MetricType.COUNTER
                    else cb.TYPE_GAUGE)
            if not cb.has_sink_routing(block.tags[0]):
                col.add_block(block)
                # the snapshot's interner, a Python object a row, freed
                # here and not unseen in the return
                with stage("release", scope=True):
                    del interner, values
                return
            # sink-routed rows present (rare): per-row path keeps routing
        for key, row in interner.rows.items():
            tags = interner.tags[row]
            out.append(InterMetric(
                name=key.name, timestamp=now, value=float(values[row]),
                tags=tags, type=mtype, sinks=route_info(tags)))

    def _flush_status(self, group: ScalarGroup, out: List[InterMetric],
                      now: int):
        interner, values, messages, hostnames = group.snapshot_and_reset()
        for key, row in interner.rows.items():
            tags = interner.tags[row]
            out.append(InterMetric(
                name=key.name, timestamp=now, value=float(values[row]),
                tags=tags, type=MetricType.STATUS,
                message=messages[row], hostname=hostnames[row],
                sinks=route_info(tags)))

    def _run_flush_units(self, units: List[tuple]):
        """Execute the generation's flush plan.

        Sequential (``flush_pipeline_depth == 0``): begin + finish +
        emit per unit, in plan order — the pre-pipeline shape, one
        group fully drained before the next dispatches.

        Pipelined (the default): every unit's device program DISPATCHES
        first (async — the ``dispatch.<group>`` stages), then the
        fetches run in plan order on this thread while ONE serializer
        thread (core/pipeline.py SerializerLane) builds and streams
        each completed group's emission chunk — so group k+1's device
        execution overlaps group k's device→host fetch, and group k's
        serialization/POST overlaps group k+1's fetch. The lane's
        bounded handoff queue (``flush_pipeline_depth`` chunks) keeps
        host memory flat, and emission ORDER stays deterministic.

        Failure ladder per unit is unchanged: a digest unit (``group``
        set) that fails dispatch or fetch past the compute ladder
        re-merges into the live store (:meth:`_requeue_group`) while
        every other unit keeps streaming; non-digest units propagate."""
        depth = getattr(self, "flush_pipeline_depth", 0)
        if depth <= 0:
            for name, series, begin, emit, group in units:
                with obs_rec.maybe_stage(name, series=series):
                    try:
                        res = begin()()
                    except Exception:
                        if not self._unit_failed(name, group, "flush"):
                            raise
                        continue
                    emit(res)
            return
        from veneur_tpu.core.pipeline import SerializerLane

        plan = []
        with obs_rec.maybe_stage("dispatch"):
            for name, series, begin, emit, group in units:
                with obs_rec.maybe_stage(name):
                    try:
                        fin = begin()
                    except Exception:
                        if not self._unit_failed(name, group,
                                                 "dispatch"):
                            raise
                        fin = None
                plan.append((name, series, fin, emit, group))
        with obs_rec.maybe_stage("lane_wait"):  # its thread's start
            lane = SerializerLane(depth, obs_rec.current())
        try:
            for name, series, fin, emit, group in plan:
                if fin is None:
                    continue
                with obs_rec.maybe_stage(name, series=series):
                    try:
                        res = fin()
                    except Exception:
                        if not self._unit_failed(name, group, "fetch"):
                            raise
                        continue
                # the flusher's waits on the lane: for room in its
                # queue, then for its last group
                with obs_rec.maybe_stage("lane_wait"):
                    lane.submit(name, emit, res)
        finally:
            # joins the serializer; re-raises the first emit error
            with obs_rec.maybe_stage("lane_wait"):
                lane.close()
        # the plan's closures and what they fetched, freed here and not
        # unseen in the return
        with obs_rec.maybe_stage("release", scope=True):
            del plan
            res = fin = emit = None

    def _unit_failed(self, name: str, group, phase: str) -> bool:
        """The flush plan's shared failure edge (call from an except
        block): a digest unit that failed past the compute ladder
        re-merges into the live store — late, never lost — and the
        plan continues (True); anything else propagates (False)."""
        if group is None:
            return False
        log.exception("digest flush for %s failed at %s past the "
                      "fallback ladder; re-merging the interval into "
                      "the live store", name, phase)
        self._requeue_group(name, group)
        return True

    def _emit_digest_result(self, gen_name: str, interner, r: dict,
                            percentiles: List[float],
                            aggregates: HistogramAggregates,
                            out: List[InterMetric], now: int,
                            fwd_list: Optional[list], col=None,
                            fwd_state=None, fwd_attr: str = "",
                            stream=None):
        """Emission half of one digest group's flush: build the
        columnar block (or the per-row fallback), capture the
        forwardable planes, and hand the chunk to the egress stream.
        Runs on the serializer lane in pipelined mode — everything here
        is host-side work on the already-fetched result."""
        agg = aggregates.value
        packed = ("packed_counts" in r) if r else False
        if col is not None and len(interner):
            from veneur_tpu.core import columnar as cb

            with obs_rec.maybe_stage("arenas", scope=True):
                names = cb.build_arenas(interner.names)
                tags = cb.build_arenas(interner.joined)
            if not cb.has_sink_routing(tags[0]):
                with obs_rec.maybe_stage("block", scope=True):
                    block = cb.digest_block(names, tags, r, agg,
                                            percentiles)
                col.add_block(block)
                if fwd_state is not None:
                    if packed:
                        part = (names, tags,
                                _packed_planes_from_result(r))
                    else:
                        part = (
                            names, tags,
                            np.asarray(r["digest_mean"], np.float32),
                            np.asarray(r["digest_weight"], np.float32),
                            np.asarray(r["digest_min"], np.float32),
                            np.asarray(r["digest_max"], np.float32))
                    if stream is not None and stream.forward_streaming:
                        # streamed forward: this shard's planes POST
                        # upstream NOW, overlapping the next group's
                        # fetch; a terminal failure re-merges into the
                        # live store (late, never lost) instead of
                        # riding fwd_state
                        stream.emit_forward(gen_name, fwd_attr, part,
                                            len(interner))
                    else:
                        setattr(fwd_state, fwd_attr, part)
                if stream is not None:
                    stream.emit(gen_name, [block], len(block))
                return
            # sink-routed rows present (rare): per-row path keeps routing
        if packed and fwd_list is not None:
            # dequantize once for the per-row fallback
            pk = _packed_planes_from_result(r)
            pk_starts, pk_ends, pk_means, pk_weights = pk.row_slices()
        for key, row in interner.rows.items():
            tags = interner.tags[row]
            sinks = route_info(tags)
            name = key.name

            def emit(suffix: str, value: float,
                     mtype: MetricType = MetricType.GAUGE):
                out.append(InterMetric(
                    name=f"{name}.{suffix}", timestamp=now, value=value,
                    tags=list(tags), type=mtype, sinks=sinks))

            # emission rules of Histo.Flush (samplers.go:511-636)
            vmax, vmin = float(r["max"][row]), float(r["min"][row])
            vsum, cnt = float(r["sum"][row]), float(r["count"][row])
            recip = float(r["recip"][row])
            if (agg & Aggregate.MAX) and math.isfinite(vmax):
                emit("max", vmax)
            if (agg & Aggregate.MIN) and math.isfinite(vmin):
                emit("min", vmin)
            if (agg & Aggregate.SUM) and vsum != 0:
                emit("sum", vsum)
            if (agg & Aggregate.AVERAGE) and vsum != 0 and cnt != 0:
                emit("avg", vsum / cnt)
            if (agg & Aggregate.COUNT) and cnt != 0:
                emit("count", cnt, MetricType.COUNTER)
            if agg & Aggregate.MEDIAN:
                emit("median", float(r["median"][row]))
            if (agg & Aggregate.HARMONIC_MEAN) and recip != 0 and cnt != 0:
                emit("hmean", cnt / recip)
            for i, p in enumerate(percentiles):
                out.append(InterMetric(
                    name=f"{name}.{int(p * 100)}percentile", timestamp=now,
                    value=float(r["percentiles"][row, i]), tags=list(tags),
                    type=MetricType.GAUGE, sinks=sinks))

            if fwd_list is not None:
                if packed:
                    s, e = pk_starts[row], pk_ends[row]
                    fwd_list.append((
                        name, tags, pk_means[s:e], pk_weights[s:e],
                        float(pk.dmin[row]), float(pk.dmax[row])))
                else:
                    w = r["digest_weight"][row]
                    live = w > 0
                    fwd_list.append((
                        name, tags,
                        r["digest_mean"][row][live].astype(np.float64),
                        w[live].astype(np.float64),
                        float(r["digest_min"][row]),
                        float(r["digest_max"][row])))

    def _requeue_group(self, gen_name: str, group) -> None:
        """Rung 3 of the flush-kernel ladder: snapshot the retired
        group (exclusively owned here — the flush swap already replaced
        it) and merge the snapshot back into the LIVE group with import
        semantics, exactly like a forwarded sketch or a checkpoint
        restore. The interval is late, never lost; a total device
        failure (snapshot raising too) degrades to the checkpoint
        bound: at most checkpoint_interval of data."""
        compute = self.compute
        obs_rec.note(rung="requeue")
        if not gen_name:
            compute.count_lost()
            return
        try:
            # retired generation: this thread is the sole owner, the
            # store lock is not required (cf. _flush_generation)
            snap = group.snapshot_state()  # lint: ok(unlocked-call) retired generation — this thread is the sole owner, the store lock is not required
            with self._lock:
                self._restore_group(gen_name, self._GROUP_TYPES[gen_name],
                                    getattr(self, gen_name), snap)
            compute.count_requeued()
            log.warning("re-merged %s into the live store; its interval "
                        "will emit with the next flush", gen_name)
        except Exception:
            compute.count_lost()
            log.exception("could not re-merge %s after the flush "
                          "failure; its interval is lost (the last "
                          "checkpoint bounds the damage)", gen_name)

    def _emit_set_result(self, name: str, interner, estimates, registers,
                         out: Optional[List[InterMetric]], now: int,
                         fwd_list: Optional[list], col=None, stream=None):
        """Emission half of one set group's flush (host-side; runs on
        the serializer lane in pipelined mode). ``hll_precision`` rides
        the store — the retired group already dropped its plane."""
        if out is None and fwd_list is None:
            return
        if (col is not None and fwd_list is None and out is not None
                and len(interner)):
            from veneur_tpu.core import columnar as cb

            block = cb.scalar_block(interner, estimates, cb.TYPE_GAUGE)
            if not cb.has_sink_routing(block.tags[0]):
                col.add_block(block)
                if stream is not None:
                    stream.emit(name, [block], len(block))
                return
        for key, row in interner.rows.items():
            tags = interner.tags[row]
            if out is not None:
                out.append(InterMetric(
                    name=key.name, timestamp=now,
                    value=float(estimates[row]), tags=tags,
                    type=MetricType.GAUGE, sinks=route_info(tags)))
            if fwd_list is not None:
                fwd_list.append((key.name, tags, registers[row],
                                 self.hll_precision))
