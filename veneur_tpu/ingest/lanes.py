"""Ingest lanes and the fleet merger.

One ``IngestLane`` per reader thread: an SO_REUSEPORT socket drained in
``recvmmsg`` batches, a reusable native parse batch (the C++ parser
releases the GIL), a lane-local C++ intern table assigning LANE rows,
lane-local columnar staging arrays per store kind, and single-writer
counters. The recv -> decode -> stage loop (``_ingest_once``) is
``@lockfree_hot_path``-asserted: the lock-order lint pass fails the
build if its call graph ever reaches a registered lock.

Hand-off happens at the **group boundary only**: a full (or idle)
staging chunk seals into an immutable ``SealedChunk`` on the lane's
deque (GIL-atomic append, no lock), and the fleet's merger thread folds
it into the store with ONE lock hold per chunk
(``MetricStore.import_lane_chunk``), remapping lane rows onto the store
interners through a per-lane, flush-epoch-aware ``LaneResolver``.

Reference shape: per-core readers (socket_linux.go:12-76) feeding
share-nothing workers (worker.go:54-91), with the merge-at-flush role
played here by the merge-at-chunk boundary (the store's group staging
is already the batch seam).
"""

from __future__ import annotations

import collections
import logging
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from veneur_tpu.core.locking import lockfree_hot_path
from veneur_tpu.core.store import (_K_COUNTER, _K_GAUGE, _K_GLOBAL_COUNTER,
                                   _K_GLOBAL_GAUGE, _K_HISTO, _K_LOCAL_HISTO,
                                   _K_LOCAL_SET, _K_LOCAL_TIMER, _K_SET,
                                   _K_TIMER, _K_TOPK, _KIND_RAW,
                                   COUNTER_CONTRIB_MAX, _scrub_counter_batch,
                                   _scrub_float_batch)
from veneur_tpu.ingest.counters import LaneLedger
from veneur_tpu.ingest.recvmmsg import BatchReceiver
from veneur_tpu.obs import kernels as obs_kernels
from veneur_tpu.overload import (F32_ABS_MAX, LEVEL_SHED_PACKETS,
                                 MIN_SAMPLE_RATE)
from veneur_tpu.samplers.parser import GLOBAL_ONLY, LOCAL_ONLY

log = logging.getLogger("veneur.ingest")

KIND_COUNT = 11

# merger wake cadence: sealed chunks wait at most this long before the
# group-boundary merge (the "drain ticker" of the lane lifecycle)
DRAIN_TICK = 0.01
# a partially-filled staging chunk seals after this long even under
# continuous traffic, bounding stage->merge latency
SEAL_MAX_AGE = 0.05
# lane recv timeout: bounds both stop-latency and the idle-residue seal
RECV_TIMEOUT = 0.2
# sealed chunks a lane may queue before it sheds payloads (a wedged
# merger must cost bounded memory, like every other queue here)
DEFAULT_MAX_BACKLOG = 64
# decode-span accumulation: while the socket stays hot (every recvmmsg
# comes back full), keep draining before decoding — the numpy staging
# cost is per-CALL far more than per-record (32-record spans stage at
# ~0.18M records/s, 2048-record spans at ~1.8M on the bench host), and
# recv syscalls release the GIL where staging cannot. Bounded by
# datagram count AND bytes so the native parse arena is never outgrown.
DECODE_BATCH = 1024
DECODE_BYTES = 1 << 18

_COUNTER_KINDS = (_K_COUNTER, _K_GLOBAL_COUNTER)
_GAUGE_KINDS = (_K_GAUGE, _K_GLOBAL_GAUGE)
_SET_KINDS = (_K_SET, _K_LOCAL_SET)


class _KindStage:
    """One kind's lane-local staging columns — the same rows/vals/wts
    layout the store group's own staging buffers use, so a sealed span
    feeds ``add_many``/``set_many``/``sample_many`` without reshaping."""

    __slots__ = ("kind", "rows", "a", "b", "members", "fill")

    def __init__(self, kind: int, chunk: int):
        self.kind = kind
        self.rows = np.empty(chunk, np.int64)
        if kind in _COUNTER_KINDS:
            self.a = np.empty(chunk, np.int64)      # Go-semantics contribs
            self.b = None
        elif kind in _GAUGE_KINDS:
            self.a = np.empty(chunk, np.float64)    # last-write values
            self.b = None
        elif kind in _SET_KINDS or kind == _K_TOPK:
            self.a = np.empty(chunk, np.uint64)     # member hashes
            self.b = None
        else:
            self.a = np.empty(chunk, np.float32)    # digest values
            self.b = np.empty(chunk, np.float32)    # digest weights
        self.members: Optional[list] = [] if kind == _K_TOPK else None
        self.fill = 0

    def put(self, rows, a, b=None, members=None) -> None:
        i, n = self.fill, len(rows)
        self.rows[i:i + n] = rows
        self.a[i:i + n] = a
        if b is not None:
            self.b[i:i + n] = b
        if members is not None:
            self.members.extend(members)
        self.fill = i + n

    def put_one(self, row: int, a, b=None, member=None) -> None:
        i = self.fill
        self.rows[i] = row
        self.a[i] = a
        if b is not None:
            self.b[i] = b
        if member is not None:
            self.members.append(member)
        self.fill = i + 1

    def take(self):
        """Trimmed copies of the staged span; resets the stage. The
        copies are what seal publishes — the preallocated columns are
        immediately reusable by the lane thread."""
        n = self.fill
        self.fill = 0
        rows = self.rows[:n].copy()
        a = self.a[:n].copy()
        b = self.b[:n].copy() if self.b is not None else None
        members = None
        if self.members is not None:
            members, self.members = self.members, []
        return (rows, a, b, members)


class SealedChunk:
    """An immutable hand-off unit: per-kind staged spans plus the lane
    intern entries minted since the previous seal (the resolver learns
    them even when a backlogged chunk's payload is shed).

    ``sealed_ns`` stamps the hand-off (monotonic): the merger measures
    seal->merge latency from it (veneur.obs.stage_duration_ns tagged
    ``stage:ingest.seal_to_merge``). ``ingest_wall_ns`` is the WALL
    clock of the chunk's first staged record — the ingest-era stamp
    the fleet trace plane threads through every downstream hop
    (obs/tracectx.py) to measure true end-to-end freshness
    (``veneur.fleet.e2e_age_ns``). Both stamps are one clock read on
    the lane thread — the ``@lockfree_hot_path`` assertion on the lane
    loop still holds."""

    __slots__ = ("lane_id", "gen", "records", "spans", "new_entries",
                 "raws", "sealed_ns", "ingest_wall_ns")

    def __init__(self, lane_id: int, gen: int, records: int,
                 spans: Dict[int, tuple],
                 new_entries: Dict[int, list], raws: list,
                 ingest_wall_ns: int = 0):
        self.lane_id = lane_id
        self.gen = gen
        self.records = records
        self.spans = spans
        self.new_entries = new_entries
        self.raws = raws
        self.sealed_ns = time.monotonic_ns()
        self.ingest_wall_ns = ingest_wall_ns or time.time_ns()


class LaneResolver:
    """Merger-side lane-row -> store-row state for one lane intern
    generation. ``entries[kind]`` accumulates the lane's (name, tags)
    registry in row order; ``remap[kind]`` is the resolved store-row
    array, dropped whole when the store's flush epoch moves (fresh
    generation twins restart their interners) and rebuilt lazily under
    the store lock (``MetricStore._lane_remap``)."""

    __slots__ = ("gen", "epoch", "entries", "remap")

    def __init__(self, gen: int):
        self.gen = gen
        self.epoch = -1
        self.entries: List[list] = [[] for _ in range(KIND_COUNT)]
        self.remap: List[Optional[np.ndarray]] = [None] * KIND_COUNT


def _kind_of_metric(m) -> Optional[int]:
    """Scope-class kind for a Python-parsed UDPMetric (the fallback
    decode path); mirrors MetricStore.process_metric's dispatch.
    None routes the line through the raw slow lane (status checks)."""
    t = m.key.type
    if t == "counter":
        return _K_GLOBAL_COUNTER if m.scope == GLOBAL_ONLY else _K_COUNTER
    if t == "gauge":
        return _K_GLOBAL_GAUGE if m.scope == GLOBAL_ONLY else _K_GAUGE
    if t == "histogram":
        return _K_LOCAL_HISTO if m.scope == LOCAL_ONLY else _K_HISTO
    if t == "timer":
        return _K_LOCAL_TIMER if m.scope == LOCAL_ONLY else _K_TIMER
    if t == "set":
        if "veneurtopk" in m.tags:
            return _K_TOPK
        return _K_LOCAL_SET if m.scope == LOCAL_ONLY else _K_SET
    return None


class IngestLane:
    """One reader thread's share-nothing lane. Every mutable field on
    the hot path is single-writer (this lane's thread); the sealed
    deque is the only cross-thread surface, and deque append/popleft
    are GIL-atomic — no lock anywhere per packet."""

    def __init__(self, lane_id: int, sock, max_len: int,
                 chunk_records: int, stop: threading.Event,
                 overload=None, recv_batch: int = 32,
                 max_backlog: int = DEFAULT_MAX_BACKLOG,
                 intern_limit: int = 1 << 20,
                 use_native: Optional[bool] = None,
                 limiter=None, trace_stages: bool = True):
        self.lane_id = lane_id
        self.sock = sock
        self._stop = stop
        self._overload = overload
        self._chunk = max(256, chunk_records)
        self._max_backlog = max(1, max_backlog)
        self._intern_limit = max(1024, intern_limit)
        self._limiter = limiter
        self._receiver = BatchReceiver(sock, max_len, batch=recv_batch)
        self.sealed: "collections.deque" = collections.deque()
        self.gen = 0
        self.ledger = LaneLedger()
        self.thread: Optional[threading.Thread] = None

        # single-writer counters (read-side sums never lock)
        self.packets = 0
        self.shed_packets = 0
        self.parsed = 0
        self.parse_errors = 0
        self.staged = 0
        self.raws_staged = 0
        self.shed_records = 0
        self.shed_raws = 0
        self.sealed_chunks = 0
        self.shed_chunks = 0
        self._shed_reported = 0  # merger-side rollup watermark

        # staging state
        self._stages: List[Optional[_KindStage]] = [None] * KIND_COUNT
        self._staged_total = 0
        self._raws: list = []
        self._pending_entries: Dict[int, list] = {}
        self._nrows = [0] * KIND_COUNT
        self._intern_total = 0
        self._first_stage_t = 0.0
        # the current chunk's ingest-era stamp (wall ns of its first
        # staged record; always on — one clock read per chunk, cheaper
        # than the freshness blindness of not having it)
        self._first_stage_wall_ns = 0
        # ingest-path stage tracing (obs_enabled): per-stage cumulative
        # ns, single-writer (this lane's thread), diffed read-side by
        # IngestFleet.take_ingest_stages — recv includes socket wait
        # (lane-idle time is real, and hiding it would fake utilization)
        self._obs = trace_stages
        self.stage_ns = {"recv": 0, "decode": 0, "stage": 0, "seal": 0}
        self.stage_iters = 0

        # native decode: a reusable C++ parse batch + this lane's own
        # intern table; both bound ONCE here so the hot loop never
        # touches the library loader (and never pays its init lock)
        self._vt = None
        self._table = None
        self._batch = None
        self._py_interner: Dict[tuple, int] = {}
        if use_native is not False:
            from veneur_tpu import native

            if native.available():
                lib = native._load()
                self._vt = lib
                self._pb_cls = native.ParsedBatch
                self._table = native.InternTable()
                # sized for a full accumulated decode span: DECODE_BYTES
                # of small lines plus one worst-case recvmmsg burst of
                # max_len datagrams (6 B is the shortest parseable line)
                arena = DECODE_BYTES + recv_batch * max_len + 4096
                cap = max(4096, arena // 6)
                self._batch = lib.vt_batch_new(cap, arena)
            elif use_native:
                raise RuntimeError("native decode requested but the "
                                   "native library is unavailable")

    @property
    def using_native(self) -> bool:
        return self._vt is not None

    @property
    def quarantined(self) -> int:
        return self.ledger.total()

    def backlog(self) -> int:
        return len(self.sealed)

    # -- hot path ----------------------------------------------------------

    @lockfree_hot_path("ingest")
    def _ingest_once(self) -> int:
        """One hot-path iteration: recv a datagram batch, admission-
        check at the socket, decode, stage columnar, seal at the chunk
        boundary. Returns the number of datagrams received (0 on
        timeout). The lock-order lint pass asserts this call graph
        reaches no lock."""
        obs = self._obs
        t_recv0 = time.monotonic_ns() if obs else 0
        datagrams = self._receiver.recv_batch(RECV_TIMEOUT)
        if not datagrams:
            if obs:
                self.stage_ns["recv"] += time.monotonic_ns() - t_recv0
            if self._staged_total or self._raws:
                self._seal()
            return 0
        # decode-span accumulation: a FULL recvmmsg means the socket
        # queue is hot — keep draining (GIL-released syscalls) so the
        # per-call staging cost amortizes over a big span
        hot = len(datagrams) == self._receiver.batch
        nbytes = 0
        if hot:
            nbytes = sum(map(len, datagrams))
            while len(datagrams) < DECODE_BATCH and nbytes < DECODE_BYTES:
                more = self._receiver.recv_batch(0.0)
                if not more:
                    hot = False
                    break
                datagrams.extend(more)
                nbytes += sum(map(len, more))
                if len(more) < self._receiver.batch:
                    hot = False
                    break
        if obs:
            self.stage_ns["recv"] += time.monotonic_ns() - t_recv0
            self.stage_iters += 1
        now = time.monotonic()
        n = len(datagrams)
        self.packets += n
        shed = False
        ctl = self._overload
        if ctl is not None and ctl.level_nowait() >= LEVEL_SHED_PACKETS:
            # statsd sheds AT the socket (overload ladder tier 3); the
            # count is lane-local, rolled up by the merger
            shed = True
        elif len(self.sealed) >= self._max_backlog:
            # a wedged merger must cost BOUNDED memory: shed whole
            # packets before decode so neither sealed chunks nor intern
            # entries keep accumulating (the _seal-side payload strip
            # only covers the small overshoot window past this check)
            shed = True
        if shed:
            self.shed_packets += n
            # samples accepted BEFORE the shed started still honor the
            # SEAL_MAX_AGE stage->merge bound: a sustained shed must
            # not strand staged residue outside flushes and checkpoints
            if (self._staged_total or self._raws) and (
                    now - self._first_stage_t >= SEAL_MAX_AGE):
                self._seal()
            return n
        if self._staged_total == 0 and not self._raws:
            self._first_stage_t = now
        if self._vt is not None:
            self._stage_native(datagrams)
        else:
            self._stage_python(datagrams)
        if (self._staged_total or self._raws) and (
                not hot
                or now - self._first_stage_t >= SEAL_MAX_AGE):
            # the socket went momentarily idle (short recv batch) or
            # the residue aged out: publish rather than sit on it
            self._seal()
        return n

    def _stage_native(self, datagrams: list) -> None:
        """Decode a recv batch with the C++ parser (GIL released) into
        the reusable batch, assign lane rows through the lane's own
        intern table, scrub, and stage columnar per kind."""
        if self._intern_total >= self._intern_limit:
            self._reset_interner()
        obs = self._obs
        t0 = time.monotonic_ns() if obs else 0
        vt = self._vt
        buf = b"\n".join(datagrams)
        b = self._batch
        vt.vt_batch_reset(b)
        vt.vt_parse_lines(buf, len(buf), b)
        pb = self._pb_cls(b.contents)
        self.parse_errors += int(pb.parse_errors)
        if pb.count == 0:
            if obs:
                self.stage_ns["decode"] += time.monotonic_ns() - t0
            return
        self.parsed += int(pb.count)
        rows, kinds, miss = self._table.assign(pb)
        if len(miss):
            self._intern_misses(pb, rows, kinds, miss)
        if obs:
            t1 = time.monotonic_ns()
            self.stage_ns["decode"] += t1 - t0
            t0 = t1
        arena = pb.arena
        values, rates = pb.value, pb.sample_rate
        member_hashes = None
        for kind in np.unique(kinds):
            kind = int(kind)
            sel = np.nonzero(kinds == kind)[0]
            if kind == _KIND_RAW:
                aoffs, alens = pb.aux_off, pb.aux_len
                for j in sel:
                    self._raws.append(arena[aoffs[j]:aoffs[j] + alens[j]])
                self.raws_staged += len(sel)
                self.parsed -= len(sel)  # counted when re-parsed
                continue
            krows = rows[sel].astype(np.int64)
            if kind in _COUNTER_KINDS:
                ok = _scrub_counter_batch(self.ledger, values[sel],
                                          rates[sel])
                if not ok.all():
                    sel, krows = sel[ok], krows[ok]
                    if not len(sel):
                        continue
                # Go truncation semantics, bit-identical to
                # MetricStore.process_batch's counter lane
                recips = (np.float32(1.0)
                          / rates[sel].astype(np.float32))
                contribs = (values[sel].astype(np.int64)
                            * recips.astype(np.int64))
                self._stage_span(kind, krows, contribs)
            elif kind in _GAUGE_KINDS:
                ok = _scrub_float_batch(self.ledger, values[sel])
                if not ok.all():
                    sel, krows = sel[ok], krows[ok]
                    if not len(sel):
                        continue
                self._stage_span(kind, krows, values[sel])
            elif kind in _SET_KINDS:
                if member_hashes is None:
                    member_hashes = pb.member_hashes()
                self._stage_span(kind, krows, member_hashes[sel])
            elif kind == _K_TOPK:
                if member_hashes is None:
                    member_hashes = pb.member_hashes()
                aoffs, alens = pb.aux_off, pb.aux_len
                members = [arena[aoffs[j]:aoffs[j] + alens[j]]
                           for j in sel]
                self._stage_span(kind, krows, member_hashes[sel],
                                 members=members)
            else:  # digests: histograms / timers, both scopes
                # scrub the float64 values BEFORE the f32 cast so an
                # out-of-f32-range sample quarantines as out_of_range
                # instead of laundering into inf
                vals64 = values[sel]
                wts = (1.0 / rates[sel]).astype(np.float32)
                ok = _scrub_float_batch(self.ledger, vals64,
                                        abs_max=F32_ABS_MAX, weights=wts)
                if not ok.all():
                    krows, vals64, wts = krows[ok], vals64[ok], wts[ok]
                    if not len(krows):
                        continue
                self._stage_span(kind, krows, vals64.astype(np.float32),
                                 wts)
        if obs:
            self.stage_ns["stage"] += time.monotonic_ns() - t0

    def _intern_misses(self, pb, rows, kinds, miss) -> None:
        arena = pb.arena
        noffs, nlens = pb.name_off, pb.name_len
        toffs, tlens = pb.tags_off, pb.tags_len
        cache: Dict[tuple, int] = {}  # intra-batch dedup (assign ran once)
        table = self._table
        pending = self._pending_entries
        for j in miss:
            j = int(j)
            k = int(kinds[j])
            name_b = arena[noffs[j]:noffs[j] + nlens[j]]
            tags_b = arena[toffs[j]:toffs[j] + tlens[j]]
            ck = (k, name_b, tags_b)
            row = cache.get(ck)
            if row is None:
                row = self._nrows[k]
                self._nrows[k] = row + 1
                self._intern_total += 1
                pending.setdefault(k, []).append((name_b, tags_b))
                table.put(k, name_b, tags_b, row)
                cache[ck] = row
            rows[j] = row

    def _stage_python(self, datagrams: list) -> None:
        """Pure-Python decode fallback (no native library): per-line
        parse into the same columnar stages. Slower, same semantics.
        Parse and staging interleave per line here, so the whole call
        reports as ``decode`` (the native path splits the two)."""
        from veneur_tpu.samplers import parser as p

        obs = self._obs
        t0 = time.monotonic_ns() if obs else 0
        if self._intern_total >= self._intern_limit:
            self._reset_interner()
        interner = self._py_interner
        for d in datagrams:
            for line in p.split_lines(d):
                if not line:
                    continue  # lint: ok(silent-drop) empty split artifact (trailing newline), not a sample
                if line.startswith(b"_e{") or line.startswith(b"_sc"):
                    self._raws.append(bytes(line))
                    self.raws_staged += 1
                    continue
                try:
                    m = p.parse_metric(line)
                except p.QuarantineError as e:
                    self.parsed += 1
                    self.ledger.count(e.reason)
                    continue
                except p.ParseError:
                    self.parse_errors += 1
                    continue
                self.parsed += 1
                kind = _kind_of_metric(m)
                if kind is None:
                    self._raws.append(bytes(line))
                    self.raws_staged += 1
                    self.parsed -= 1
                    continue
                ik = (kind, m.key.name, m.key.joined_tags)
                row = interner.get(ik)
                if row is None:
                    row = self._nrows[kind]
                    self._nrows[kind] = row + 1
                    self._intern_total += 1
                    interner[ik] = row
                    self._pending_entries.setdefault(kind, []).append(
                        (m.key.name.encode("utf-8"),
                         m.key.joined_tags.encode("utf-8")))
                self._stage_one_metric(kind, row, m)
        if obs:
            self.stage_ns["decode"] += time.monotonic_ns() - t0

    def _stage_one_metric(self, kind: int, row: int, m) -> None:
        from veneur_tpu.ops import hll as hll_ops

        if kind in _COUNTER_KINDS:
            if not MIN_SAMPLE_RATE <= m.sample_rate <= 1:
                self.ledger.count("bad_rate")
                return
            contrib = (int(m.value)
                       * int(np.float32(1.0) / np.float32(m.sample_rate)))
            if abs(contrib) >= COUNTER_CONTRIB_MAX:
                self.ledger.count("out_of_range")
                return
            self._put_one(kind, row, contrib)
        elif kind in _GAUGE_KINDS:
            self._put_one(kind, row, float(m.value))
        elif kind in _SET_KINDS or kind == _K_TOPK:
            member = str(m.value)
            h = hll_ops.hash_member(member.encode("utf-8"))
            self._put_one(kind, row, np.uint64(h),
                          member=(member.encode("utf-8")
                                  if kind == _K_TOPK else None))
        else:
            if abs(m.value) > F32_ABS_MAX:
                self.ledger.count("out_of_range")
                return
            if not MIN_SAMPLE_RATE <= m.sample_rate <= 1:
                self.ledger.count("bad_rate")
                return
            self._put_one(kind, row, np.float32(m.value),
                          b=np.float32(1.0) / np.float32(m.sample_rate))

    def _put_one(self, kind, row, a, b=None, member=None) -> None:
        if not self._first_stage_wall_ns:
            self._first_stage_wall_ns = time.time_ns()
        if self._chunk - self._staged_total == 0:
            self._seal()
        st = self._stages[kind]
        if st is None:
            st = self._stages[kind] = _KindStage(kind, self._chunk)
        st.put_one(row, a, b, member)
        self._staged_total += 1

    def _stage_span(self, kind, rows, a, b=None, members=None) -> None:
        if not self._first_stage_wall_ns:
            # the chunk's ingest-era stamp: one wall-clock read per
            # chunk (per staged SPAN at most, never per record)
            self._first_stage_wall_ns = time.time_ns()
        st = self._stages[kind]
        if st is None:
            st = self._stages[kind] = _KindStage(kind, self._chunk)
        n = len(rows)
        start = 0
        while start < n:
            room = self._chunk - self._staged_total
            if room == 0:
                self._seal()
                st = self._stages[kind]
                if st is None:
                    st = self._stages[kind] = _KindStage(kind, self._chunk)
                room = self._chunk
            take = min(room, n - start)
            end = start + take
            st.put(rows[start:end], a[start:end],
                   b[start:end] if b is not None else None,
                   members[start:end] if members is not None else None)
            self._staged_total += take
            start = end

    def _reset_interner(self) -> None:
        """Bound the lane's intern memory: past the limit (default: the
        store's max_series), seal what's staged, drop the table and
        start a new intern GENERATION — the resolver keys on ``gen`` so
        stale lane rows can never alias fresh ones."""
        self._seal()
        if self._table is not None:
            self._table.reset()
        self._py_interner.clear()
        self._nrows = [0] * KIND_COUNT
        self._pending_entries = {}
        self._intern_total = 0
        self.gen += 1

    def _seal(self) -> None:
        """Publish the staged chunk to the merge deque. Past the
        backlog cap the PAYLOAD is shed (bounded memory under a wedged
        merger) but the intern entries still ship — later chunks
        reference rows this lane's table already assigned."""
        total = self._staged_total
        if total == 0 and not self._raws and not self._pending_entries:
            return
        obs = self._obs
        t0 = time.monotonic_ns() if obs else 0
        spans: Dict[int, tuple] = {}
        for kind, st in enumerate(self._stages):
            if st is not None and st.fill:
                spans[kind] = st.take()
        chunk = SealedChunk(self.lane_id, self.gen, total, spans,
                            self._pending_entries, self._raws,
                            ingest_wall_ns=self._first_stage_wall_ns)
        self._pending_entries = {}
        self._raws = []
        self._staged_total = 0
        self._first_stage_wall_ns = 0
        self.staged += total
        if len(self.sealed) >= self._max_backlog:
            self.shed_records += total
            self.shed_raws += len(chunk.raws)
            self.shed_chunks += 1
            chunk.records = 0
            chunk.spans = {}
            chunk.raws = []
        self.sealed_chunks += 1
        self.sealed.append(chunk)
        if obs:
            self.stage_ns["seal"] += time.monotonic_ns() - t0

    # -- reader loop ---------------------------------------------------------

    def _run(self) -> None:
        try:
            while not self._stop.is_set():
                try:
                    self._ingest_once()
                except OSError as e:
                    if self._stop.is_set():
                        break
                    self._warn("ingest lane %d recv error: %s",
                               self.lane_id, e)
                    time.sleep(0.01)
                except Exception as e:
                    # the lane must NEVER die with its socket open: the
                    # kernel would keep hashing this lane's REUSEPORT
                    # share of datagrams into a queue nobody drains
                    self._warn("ingest lane %d hot-path error: %r",
                               self.lane_id, e)
                    time.sleep(0.05)
        finally:
            try:
                self._seal()  # residue rides the fleet's final drain
            except Exception:
                log.exception("ingest lane %d final seal failed",
                              self.lane_id)
            try:
                self.sock.close()
            except OSError:
                pass

    def _warn(self, fmt: str, *args) -> None:
        if self._limiter is not None:
            self._limiter.warn(fmt, *args)
        else:
            log.warning(fmt, *args)

    def counters_snapshot(self) -> dict:
        return {
            "packets": self.packets,
            "shed_packets": self.shed_packets,
            "syscalls": self._receiver.syscalls,
            "recvmmsg": self._receiver.using_recvmmsg,
            "parsed": self.parsed,
            "parse_errors": self.parse_errors,
            "quarantined": self.quarantined,
            "staged": self.staged,
            "raws": self.raws_staged,
            "shed_records": self.shed_records,
            "sealed_chunks": self.sealed_chunks,
            "shed_chunks": self.shed_chunks,
            "backlog": len(self.sealed),
            "intern_rows": self._intern_total,
            "intern_gen": self.gen,
            "native_decode": self.using_native,
            "stage_ns": dict(self.stage_ns) if self._obs else None,
        }


class IngestFleet:
    """N lanes on one SO_REUSEPORT UDP address plus the merger thread
    that folds sealed chunks into the store at the group boundary.

    The merger also drives the overload controller's periodic pressure
    recompute and rolls lane-local shed/quarantine tallies into the
    shared ledgers — all the locked accounting the lanes refuse to do
    per packet happens here, once per tick."""

    def __init__(self, store, addr, num_lanes: int, recv_buf: int,
                 max_len: int, chunk_records: int = 1 << 14,
                 stop: Optional[threading.Event] = None,
                 overload=None,
                 raw_handler: Optional[Callable[[bytes], None]] = None,
                 thread_wrap: Optional[Callable] = None,
                 recv_batch: int = 32,
                 drain_tick: float = DRAIN_TICK,
                 max_backlog: int = DEFAULT_MAX_BACKLOG,
                 use_native: Optional[bool] = None,
                 intern_limit: int = 0,
                 limiter=None, trace_stages: bool = True):
        from veneur_tpu import networking

        self._store = store
        self._stop = stop if stop is not None else threading.Event()
        self._overload = overload
        self._raw_handler = raw_handler
        self._tick = drain_tick
        self._wrap = thread_wrap or (lambda fn: fn)
        self._merge_lock = threading.Lock()
        self._resolvers: Dict[int, LaneResolver] = {}
        self.merged_records: Dict[int, int] = {}
        self.merged_raws: Dict[int, int] = {}
        # seal->merge latency observability: the merger (single writer)
        # appends each merged chunk's latency; the flusher drains the
        # deque per interval into the self-telemetry group, the running
        # aggregates ride /debug/vars. deque append/popleft are
        # GIL-atomic — no lock between merger and flusher.
        self._merge_latencies: "collections.deque" = collections.deque(
            maxlen=4096)
        self.merge_latency_count = 0
        self.merge_latency_max_ns = 0
        self._merge_latency_sum_ns = 0
        # fleet freshness: the oldest ingest-era stamp (wall ns) among
        # chunks merged since the last flush took it; written by the
        # merger under _merge_lock, read-and-reset the same way
        self._oldest_ingest_ns: Optional[int] = None
        # per-lane stage-tracing watermarks (take_ingest_stages diffs
        # the lanes' cumulative single-writer counters per interval)
        self._stage_reported: Dict[tuple, int] = {}
        # the merger's own busy time, cumulative like the lanes'
        # stage_ns and diffed by the same reader: ns inside
        # _merge_chunk (``merge``) and, filled by import_lane_chunk,
        # the wait for the store lock, the lane-row remap with its
        # first-sight interning (of it ``route``, a mesh's placing of
        # those rows), and the staging calls; plus chunks merged and
        # rows interned. Written under _merge_lock only;
        # None when stage tracing is off (no clock is read then).
        self.merge_ns: Optional[Dict[str, int]] = dict.fromkeys(
            ("merge", "lock_wait", "remap", "route", "stage", "chunks",
             "rows_interned"), 0) if trace_stages else None
        self.unrouted_raws: list = []  # only without a raw_handler (tests)
        intern_limit = (intern_limit
                        or getattr(store, "max_series", 0) or (1 << 20))
        self.lanes: List[IngestLane] = []
        self.bound: List[tuple] = []
        for i in range(max(1, num_lanes)):
            sock = networking.new_udp_socket(addr, recv_buf,
                                             reuse_port=True)
            self.bound.append(sock.getsockname())
            if addr.port == 0:
                # later lanes must share the port the first one got
                from veneur_tpu.protocol.addr import ResolvedAddr

                addr = ResolvedAddr(scheme=addr.scheme, family="udp",
                                    host=addr.host,
                                    port=sock.getsockname()[1])
            self.lanes.append(IngestLane(
                i, sock, max_len, chunk_records, self._stop,
                overload=overload, recv_batch=recv_batch,
                max_backlog=max_backlog, intern_limit=intern_limit,
                use_native=use_native, limiter=limiter,
                trace_stages=trace_stages))
        self._threads: List[threading.Thread] = []
        self._merger: Optional[threading.Thread] = None

    @property
    def num_lanes(self) -> int:
        return len(self.lanes)

    def start(self) -> None:
        for lane in self.lanes:
            t = threading.Thread(target=self._wrap(lane._run),
                                 name=f"ingest-lane-{lane.lane_id}",
                                 daemon=True)
            t.start()
            lane.thread = t
            self._threads.append(t)
        self._merger = threading.Thread(target=self._wrap(self._merge_loop),
                                        name="ingest-merger", daemon=True)
        self._merger.start()

    # -- the group boundary --------------------------------------------------

    def merge_sealed(self) -> int:
        """Drain every lane's sealed deque into the store: one store-
        lock hold per chunk. Serialized against concurrent callers (the
        merger tick, the pre-snapshot drain, shutdown) by the merge
        lock — the RESOLVER state is single-merger, the lanes never
        wait on it."""
        merged = 0
        with self._merge_lock:
            for lane in self.lanes:
                while True:
                    try:
                        chunk = lane.sealed.popleft()
                    except IndexError:
                        break  # lint: ok(swallowed-exception) empty-deque sentinel: the lane's sealed queue is drained, nothing in flight
                    merged += self._merge_chunk(lane, chunk)
                self._fold_ledger(lane)
        return merged

    def _merge_chunk(self, lane: IngestLane, chunk: SealedChunk) -> int:
        res = self._resolvers.get(chunk.lane_id)
        if res is None or res.gen != chunk.gen:
            # the lane reset its intern table (bounded-memory rollover):
            # rows restart at 0 under a new gen, so the old registry
            # must never remap them
            res = self._resolvers[chunk.lane_id] = LaneResolver(chunk.gen)
        timing = self.merge_ns
        t0 = time.monotonic_ns() if timing is not None else 0
        with obs_kernels.host_scope("merge"):
            raws = self._store.import_lane_chunk(chunk, res, timing)
        if chunk.records and chunk.ingest_wall_ns:
            # caller (merge_sealed) holds _merge_lock — the same hold
            # take_oldest_ingest_ns resets under
            if (self._oldest_ingest_ns is None
                    or chunk.ingest_wall_ns < self._oldest_ingest_ns):
                self._oldest_ingest_ns = chunk.ingest_wall_ns
        now = time.monotonic_ns()
        if timing is not None:
            # raw routing below is the Python parser's time, not the
            # merger's fold: the busy span ends where the latency does
            timing["merge"] += now - t0
            timing["chunks"] += 1
        latency = now - chunk.sealed_ns
        if latency >= 0:
            self._merge_latencies.append(latency)
            self.merge_latency_count += 1
            self._merge_latency_sum_ns += latency
            if latency > self.merge_latency_max_ns:
                self.merge_latency_max_ns = latency
        if chunk.records:
            self.merged_records[chunk.lane_id] = (
                self.merged_records.get(chunk.lane_id, 0) + chunk.records)
        if raws:
            self.merged_raws[chunk.lane_id] = (
                self.merged_raws.get(chunk.lane_id, 0) + len(raws))
            handler = self._raw_handler
            if handler is not None:
                for raw in raws:  # outside the store lock
                    handler(raw)
            elif len(self.unrouted_raws) < 65536:
                self.unrouted_raws.extend(raws)
        return chunk.records

    def _fold_ledger(self, lane: IngestLane) -> None:
        q = getattr(self._store, "quarantine", None)
        if q is None:
            return
        for reason, d in lane.ledger.take_deltas().items():
            q.count(reason, d)

    def _rollup_sheds(self, ctl) -> None:
        for lane in self.lanes:
            d = lane.shed_packets - lane._shed_reported
            if d:
                lane._shed_reported += d
                ctl.account_shed("statsd", d)

    def _merge_loop(self) -> None:
        while not self._stop.is_set():
            try:
                self.merge_sealed()
                ctl = self._overload
                if ctl is not None:
                    ctl.level()  # periodic pressure recompute, off-lane
                    self._rollup_sheds(ctl)
            except Exception:
                log.exception("ingest merge pass failed")
            self._stop.wait(self._tick)
        # lanes seal their residue on exit; collect it before returning
        for t in self._threads:
            t.join(timeout=5.0)
        try:
            self.merge_sealed()
            if self._overload is not None:
                self._rollup_sheds(self._overload)
        except Exception:
            log.exception("final ingest merge failed")

    def shutdown(self, timeout: float = 10.0) -> None:
        """Stop lanes, collect their sealed residue, stop the merger.
        The caller's stop event may already be set; setting it twice is
        harmless."""
        self._stop.set()
        deadline = time.monotonic() + timeout
        for t in self._threads:
            t.join(timeout=max(0.1, deadline - time.monotonic()))
        if self._merger is not None:
            self._merger.join(timeout=max(0.1,
                                          deadline - time.monotonic()))
        self.merge_sealed()  # idempotent; covers a wedged merger thread

    # -- read-side telemetry -------------------------------------------------

    def take_merge_latencies(self) -> List[int]:
        """Drain the interval's seal->merge latencies (ns) for the
        flusher's self-telemetry sampling; running aggregates stay for
        /debug/vars. popleft-until-empty is safe against the merger's
        concurrent appends (GIL-atomic deque ops, no lock)."""
        out: List[int] = []
        latencies = self._merge_latencies
        while True:
            try:
                out.append(latencies.popleft())
            except IndexError:
                return out

    def take_oldest_ingest_ns(self) -> Optional[int]:
        """Read-and-reset the oldest ingest-era stamp (wall ns) among
        chunks merged since the last call — the flusher's freshness
        anchor (chunks merged after the generation swap attribute to
        the NEXT interval, which only over-estimates age: freshness
        reads conservative, never optimistic)."""
        with self._merge_lock:
            oldest, self._oldest_ingest_ns = self._oldest_ingest_ns, None
        return oldest

    def take_ingest_stages(self) -> Optional[dict]:
        """The interval's ingest-path stage tree: per-stage ns summed
        over every lane since the last call (recv includes socket
        wait, so the sums are lane-seconds of wall clock, up to
        ``lanes`` x the interval). None when stage tracing is off or
        nothing accrued. ``merger`` holds the merger thread's own
        counters over the same stretch (``merge_ns``'s keys). Single
        reader (the flusher); lane and merger counters are
        single-writer ints, read GIL-atomically."""
        out = {"recv": 0, "decode": 0, "stage": 0, "seal": 0}
        iters = 0
        traced = False
        for lane in self.lanes:
            if not lane._obs:
                continue
            traced = True
            for stage in out:
                cur = lane.stage_ns[stage]
                key = (lane.lane_id, stage)
                out[stage] += cur - self._stage_reported.get(key, 0)
                self._stage_reported[key] = cur
            cur = lane.stage_iters
            key = (lane.lane_id, "iters")
            iters += cur - self._stage_reported.get(key, 0)
            self._stage_reported[key] = cur
        if not traced or not any(out.values()):
            return None
        out["iters"] = iters
        out["lanes"] = len(self.lanes)
        out["merger"] = merged = {}
        for key, cur in self.merge_ns.items():  # traced: never None
            merged[key] = cur - self._stage_reported.get(("merger", key), 0)
            self._stage_reported[("merger", key)] = cur
        return out

    def merge_latency_snapshot(self) -> dict:
        n = self.merge_latency_count
        return {"count": n,
                "max_ns": self.merge_latency_max_ns,
                "avg_ns": (self._merge_latency_sum_ns // n) if n else 0}

    def pressure(self) -> float:
        """Backlog fill ratio feeding the overload watermarks: sealed
        chunks waiting on the merger, against the per-lane shed cap."""
        p = 0.0
        for lane in self.lanes:
            p = max(p, len(lane.sealed) / lane._max_backlog)
        return min(p, 1.0)

    def parse_errors(self) -> int:
        return sum(lane.parse_errors for lane in self.lanes)

    def totals(self) -> dict:
        t = {"lanes": len(self.lanes), "packets": 0, "shed_packets": 0,
             "syscalls": 0, "parsed": 0, "parse_errors": 0,
             "quarantined": 0, "staged": 0, "raws": 0, "shed_records": 0,
             "sealed_chunks": 0, "shed_chunks": 0, "backlog": 0}
        for lane in self.lanes:
            c = lane.counters_snapshot()
            for k in list(t):
                if k != "lanes" and k in c:
                    t[k] += c[k]
        t["merged"] = sum(self.merged_records.values())
        t["merged_raws"] = sum(self.merged_raws.values())
        pkts = t["packets"]
        t["syscalls_per_packet"] = (round(t["syscalls"] / pkts, 4)
                                    if pkts else None)
        return t

    def balance(self) -> dict:
        """Count conservation per lane: everything a lane parsed is
        merged, quarantined, shed, or still in flight — nothing
        vanishes. ``ok`` only once backlogs and staging are drained."""
        lanes = []
        ok = True
        for lane in self.lanes:
            pending = sum(c.records for c in list(lane.sealed))
            pending += lane._staged_total
            merged = self.merged_records.get(lane.lane_id, 0)
            ingested = lane.parsed
            accounted = (merged + lane.quarantined + lane.shed_records
                         + pending)
            lane_ok = ingested == accounted
            ok = ok and lane_ok
            lanes.append({"lane": lane.lane_id, "ingested": ingested,
                          "merged": merged,
                          "quarantined": lane.quarantined,
                          "shed": lane.shed_records, "pending": pending,
                          "ok": lane_ok})
        return {"ok": ok, "lanes": lanes}

    def snapshot(self) -> dict:
        """Best-effort state dump for /debug/vars."""
        return {"totals": self.totals(),
                "balance": self.balance(),
                "pressure": round(self.pressure(), 4),
                "seal_to_merge": self.merge_latency_snapshot(),
                "per_lane": [lane.counters_snapshot()
                             for lane in self.lanes]}
