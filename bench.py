"""Benchmark suite for the BASELINE.md configs.

Headline (the driver-recorded JSON line): config #2 — the per-interval
flush program at 4M histogram series on one chip (capacity-planned
SlabDigestBank, core/slab.py), reported as p99 over >= 20 iterations
against a MEASURED scalar baseline. The 10M-series north-star configs
(bf16 resident digests, local + global-merge roles) report alongside.

Baseline measurement: no Go toolchain ships in this image, so
``veneur_tpu/native/baseline_tdigest.cpp`` reimplements the reference's
per-series flush (Dunning merging t-digest: temp drain + 8 quantile
walks, ``/root/reference/tdigest/merging_digest.go:111-327``) in C++
-O2 and times it single-core. C++ is within ~1.0-1.5x of Go on this
kind of float loop, and the greedy scan produces slightly MORE centroids
than the reference's (189 vs ~160 at C=100), so the derived speedup is,
if anything, understated. The measurement is re-taken every run at 1M
series (cardinality-matched cache behavior; see
measure_scalar_baseline_us) and reported as baseline_us_per_series
(observed ~3.4-4.6 us/series on this host). It remains conservative in
the baseline's favor: the real Go path additionally pays a map walk +
interface dispatch per series that the flat C++ arrays do not.

Other configs (reported in the ``configs`` field of the same line):
  #0 loopback-UDP ingest throughput through the C++ reader pool +
     batch parser + store (reference bar: >60k pps, README.md:285-289)
  #1 10k counters + 10k gauges scalar flush (host path, example.yaml)
  #3 HLL register merge + estimate at 2^18 series x 2^14 registers
     (1M x 2^14 int8 registers is 16 GB — past one v5e-1's HBM; the
     mesh store shards the series axis for that, see core/mesh_store.py)
  #4 mesh-sharded global-aggregator flush on an 8-device virtual CPU
     mesh (one real chip in this harness; the sharding is the same
     program that runs over ICI on a pod slice)
  #5 count-min/top-k heavy hitters at high key cardinality

Prints exactly one JSON line on stdout.
"""

import ctypes
import fnmatch
import json
import os
import subprocess
import sys
import time
from functools import partial

import numpy as np

FALLBACK_GO_US_PER_SERIES = 10.0  # used only if the C++ baseline can't build
QS = (0.01, 0.10, 0.25, 0.50, 0.75, 0.90, 0.95, 0.99)
# >= 100 samples so the headline p99 is a real percentile, not the max
# of 20 (VERDICT round-4 weak #6 / item #8)
ITERS = 100

_HERE = os.path.dirname(os.path.abspath(__file__))
_BASE_SRC = os.path.join(_HERE, "veneur_tpu", "native",
                         "baseline_tdigest.cpp")
_BASE_SO = os.path.join(_HERE, "veneur_tpu", "native",
                        "libbaseline_tdigest.so")


def measure_scalar_baseline_us(num_series: int = 1 << 20) -> tuple:
    """(us/series, provenance) for the sequential reference algorithm.

    Measured at 1M series so the per-series digest walks see the same
    cache behavior the reference would at the headline cardinalities: a
    20k-series probe runs entirely cache-hot and measures ~15% cheaper
    per series, understating the baseline's true cost at scale (and so
    understating the derived speedup)."""
    try:
        if (not os.path.exists(_BASE_SO)
                or os.path.getmtime(_BASE_SO) < os.path.getmtime(_BASE_SRC)):
            subprocess.run(["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
                            "-o", _BASE_SO, _BASE_SRC],
                           check=True, capture_output=True, timeout=120)
        lib = ctypes.CDLL(_BASE_SO)
        lib.vt_baseline_flush_ns.restype = ctypes.c_double
        lib.vt_baseline_flush_ns.argtypes = [
            ctypes.c_uint32, ctypes.c_uint32,
            ctypes.POINTER(ctypes.c_double), ctypes.c_uint32,
            ctypes.c_uint32]
        qs = (ctypes.c_double * len(QS))(*QS)
        # FLUSH-only timing, mirroring the TPU bench: 16 samples/series
        # are staged untimed (<= the 32-entry temp buffer, so all merge
        # work lands inside the timed drain), then the drain + 8
        # quantile walks are timed
        ns = lib.vt_baseline_flush_ns(num_series, 16, qs, len(QS), 5)
        return ns / 1000.0, "measured_cpp_single_core"
    except Exception as e:  # pragma: no cover - no compiler
        print(f"baseline build failed ({e}); using documented estimate",
              file=sys.stderr)
        return FALLBACK_GO_US_PER_SERIES, "estimated"


def bench_histo_flush(num_series: int, digest_dtype: str = "float32",
                      iters: int = ITERS, stage_chunks: int = 8,
                      slab_rows: int = 1 << 20):
    """Config #2: the per-interval drain + 8-quantile flush at num_series,
    through the capacity-planned SlabDigestBank (core/slab.py): flat
    resident planes, <= 1M-row slabs per device program, optional bf16
    digest storage for the 10M-series north-star config.

    Ingest is staged UNTIMED (it streams during the interval in both
    systems; the reference's BenchmarkServerFlush likewise times Flush on
    pre-populated workers), and its on-device throughput is reported
    separately as ingest_msamples_s."""
    import jax.numpy as jnp
    from veneur_tpu.core.slab import SlabDigestBank

    bank = SlabDigestBank(num_series, compression=100.0,
                          slab_rows=slab_rows,
                          digest_dtype=jnp.dtype(digest_dtype))
    nslabs, slab = bank.num_slabs, bank.slab_rows
    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.permutation(slab).astype(np.int32))
    valsets = [jnp.asarray(rng.gamma(2.0, 50.0, slab).astype(np.float32))
               for _ in range(4)]
    wts = jnp.ones((slab,), jnp.float32)

    def stage():
        for i in range(nslabs):
            for j in range(stage_chunks):
                bank.ingest_slab(i, rows, valsets[j % 4], wts)
        # scalar readback forces completion
        float(bank.temps[-1].count.sum())

    def flush():
        outs = bank.flush(QS, fetch=False)
        # ONE completion barrier over every slab's output (a scalar that
        # depends on all of them): per-slab scalar fetches add a
        # serialized host-device round trip per slab to every iteration
        # — measurement overhead, not flush work
        float(sum(jnp.nansum(o["percentiles"]) for o in outs))

    stage()
    flush()  # warmup: compile + first run

    # on-device ingest throughput (reported, not part of flush latency)
    t0 = time.perf_counter()
    stage()
    ingest_rate = nslabs * stage_chunks * slab / (time.perf_counter() - t0) / 1e6
    flush()  # drop the extra staged interval

    # A stall of the host-device link during the sync readback can add
    # seconds that have nothing to do with flush latency (p99 of 20 iters = max, so one stall
    # poisons the headline). Post-filter against the MEDIAN OF ALL
    # samples (a stall on any single iteration, including the first,
    # cannot move the median) and re-measure the discarded ones —
    # transparently reported, never silently dropped.
    raw = []
    for _ in range(iters + 3):
        stage()
        t0 = time.perf_counter()
        flush()
        raw.append(time.perf_counter() - t0)
        if len(raw) >= iters:
            med = float(np.median(raw))
            clean = [t for t in raw if t <= 5 * med]
            if len(clean) >= iters:
                break
    med = float(np.median(raw))
    clean = [t for t in raw if t <= 5 * med]
    stalls = len(raw) - len(clean)
    times = np.asarray(clean[:iters]) * 1e3
    plan = bank.hbm_bytes()
    out = {"p50_ms": round(float(np.percentile(times, 50)), 3),
           "p99_ms": round(float(np.percentile(times, 99)), 3),
           "iters": len(times),
           "digest_dtype": digest_dtype,
           "resident_gb": round(plan["total_bytes"] / 2**30, 2),
           "ingest_msamples_s": round(ingest_rate, 1)}
    if stalls:
        out["transport_stalls_discarded"] = stalls
    return out


class _RangeInterner:
    """Interner stand-in for the tiered bench: 10M real MetricKeys are
    GBs of Python objects, but the flush path only needs __len__ plus
    name/joined lookups for the HOT rows (_end_interval)."""

    class _Names:
        def __getitem__(self, i):
            return f"s{i}"

    class _Joined:
        def __getitem__(self, i):
            return ""

    def __init__(self, n: int):
        self._n = n
        self.rows = {}
        self.names = self._Names()
        self.joined = self._Joined()

    def __len__(self):
        return self._n


def bench_tiered_10m(num_series: int = 10 * (1 << 20),
                     hot_rows: int = 10000, cold_samples: int = 4,
                     iters: int = 5, oracle_rows: int = 2048):
    """Config 2g: realistic-density flush on the TIERED store
    (core/tiered.py). Bench 2d measured the fleet-realistic workload at
    ~3.9 live centroids against the dense-48 plane; here every series
    gets ``cold_samples`` samples per interval (the realistic density)
    except ``hot_rows`` hot ones, which cross the promotion bar and land
    in dense full-K slots. Reports flush p50 directly comparable to
    ``2b_histo_10m_bf16``'s dense-shape flush, resident bytes (the >= 5x
    reduction claim), and ``merged_ok``: quantile agreement with a dense
    DigestGroup oracle over a sampled row subset, within the pool
    compression's t-digest error envelope, plus exact count equality."""
    import warnings

    warnings.filterwarnings("ignore", message="Some donated buffers")
    import jax.numpy as jnp  # noqa: F401  (ensures backend init here)
    from veneur_tpu.core.store import DigestGroup
    from veneur_tpu.core.tiered import TieredDigestGroup
    from veneur_tpu.samplers.parser import MetricKey

    rng = np.random.default_rng(0)
    chunk = 1 << 16
    g = TieredDigestGroup(slab_rows=1 << 18, chunk=chunk,
                          promote_samples=32, promote_intervals=1)
    g.ensure_capacity(num_series - 1)
    g.interner = _RangeInterner(num_series)
    hot = rng.choice(num_series, size=min(hot_rows, num_series),
                     replace=False).astype(np.int64)
    # the sampled oracle subset: cold rows + a few hot ones
    osel = np.concatenate([
        rng.choice(num_series, size=oracle_rows - 64, replace=False),
        hot[:64]]).astype(np.int64)
    osel = np.unique(osel)
    omap = {int(r): i for i, r in enumerate(osel)}
    oracle_vals = {i: [] for i in range(len(osel))}

    def stage(record_oracle=False):
        # cold pass: every series, cold_samples rounds of one sample
        for _ in range(cold_samples):
            start = 0
            while start < num_series:
                n = min(chunk, num_series - start)
                rows = np.arange(start, start + n, dtype=np.int64)
                vals = rng.gamma(2.0, 50.0, n).astype(np.float32)
                g.sample_many(rows, vals, np.ones(n, np.float32))
                if record_oracle:
                    for r in rows[np.isin(rows, osel)]:
                        oracle_vals[omap[int(r)]].append(
                            float(vals[int(r) - start]))
                start += n
        # hot pass: promotion-bar volume on the hot subset
        for _ in range(40):
            vals = rng.gamma(2.0, 50.0, len(hot)).astype(np.float32)
            g.sample_many(hot, vals, np.ones(len(hot), np.float32))
            if record_oracle:
                for j, r in enumerate(hot):
                    i = omap.get(int(r))
                    if i is not None:
                        oracle_vals[i].append(float(vals[j]))

    def flush():
        _, r = g.flush(list(QS), want_digests=False,
                       want_stats=("pcts", "count"))
        ni = _RangeInterner(num_series)
        g.interner = ni
        # production re-enters each series through _row(), which gives
        # directory-resident keys their dense slot back at first sight
        # in the new generation; the range interner bypasses _row, so
        # re-stamp here — without this the timed intervals run 100%
        # pool-tier and the p50 omits the dense bank's flush cost
        for row in hot:
            if g.directory.is_dense((ni.names[int(row)],
                                     ni.joined[int(row)])):
                g._assign_dense(int(row))
        return r

    stage(record_oracle=True)
    r0 = flush()  # warmup: compile + first run, and the oracle interval
    # merged_ok: dense oracle over the sampled subset, fed identically
    oracle = DigestGroup(capacity=1 << (len(osel) - 1).bit_length(),
                         chunk=chunk)
    for i in range(len(osel)):
        key = MetricKey(name=f"s{osel[i]}", type="histogram",
                        joined_tags="")
        for v in oracle_vals[i]:
            oracle.sample(key, [], v, 1.0)
    _, ro = oracle.flush(list(QS), want_digests=False,
                         want_stats=("pcts", "count"))
    tp = np.asarray(r0["percentiles"])[osel]
    tc = np.asarray(r0["count"])[osel]
    oc = np.asarray(ro["count"])
    # the acceptance criterion is "identical to the DENSE PATH within
    # the t-digest error bound", so the gate is per-cell EXCESS rank
    # error over the dense oracle: both paths share the reference's
    # quantile interpolation (merging_digest.go:297-327 walks min ->
    # first-centroid upper bound), so p01 on a 4-sample row sits an
    # epsilon above the row minimum and costs a full 1/n under exact
    # searchsorted bracketing — on the ORACLE TOO (measured 0.24 on
    # both, identically). Excess cancels the shared convention and
    # leaves only what the tiered representation adds: the pool's PK-2
    # k-scale envelope caps mid-q cluster mass at ~2/C (C=14 -> ~0.14
    # worst-case), and a splice/merge/promotion bug lands far past it
    # (the pre-fix promotion clump measured 0.27 where the oracle was
    # exact).
    op = np.asarray(ro["percentiles"])
    rank_err = 0.0
    excess_err = 0.0
    for m in range(len(osel)):
        t_sorted = np.sort(np.asarray(oracle_vals[m], np.float64))
        nroww = len(t_sorted)
        if nroww == 0:
            continue

        def _bracket(v):
            lo = np.searchsorted(t_sorted, v, "left") / nroww
            hi = np.searchsorted(t_sorted, v, "right") / nroww
            return lo, hi

        for qi, q in enumerate(QS):
            lo, hi = _bracket(float(tp[m, qi]))
            e_t = float(max(0.0, lo - q, q - hi))
            lo, hi = _bracket(float(op[m, qi]))
            e_o = float(max(0.0, lo - q, q - hi))
            rank_err = max(rank_err, e_t)
            excess_err = max(excess_err, e_t - e_o)
    counts_ok = bool(np.allclose(tc, oc))
    merged_ok = counts_ok and bool(excess_err <= 0.15)
    times = []
    for _ in range(iters):
        stage()
        t0 = time.perf_counter()
        flush()
        times.append(time.perf_counter() - t0)
    plan = g.hbm_bytes()
    # the dense-shape comparison footprint: what 2b's bf16 slab plan
    # would hold resident at the same series count (core/slab.py)
    from veneur_tpu.core.slab import SlabDigestBank

    dense_plan = SlabDigestBank(num_series, slab_rows=1 << 18,
                                digest_dtype="bfloat16").hbm_bytes()
    # per-ROW ratio: the pool allocates pow2 slabs, so at small probe
    # sizes the allocated-bytes ratio would be padding, not plan
    dense_per_row = dense_plan["total_bytes"] / num_series
    tier_per_row = plan["total_bytes"] / plan["pool_rows"]
    return {"p50_ms": round(float(np.median(times)) * 1e3, 3),
            "series": num_series,
            "hot_rows": int(len(hot)),
            "live_centroids_per_row": cold_samples,
            "resident_gb": round(plan["total_bytes"] / 2**30, 3),
            "dense_bf16_resident_gb": round(
                dense_plan["total_bytes"] / 2**30, 3),
            "resident_reduction_x": round(dense_per_row / tier_per_row,
                                          2),
            "merged_ok": merged_ok,
            "counts_exact": counts_ok,
            "quantile_rank_err": round(rank_err, 4),
            "quantile_excess_err": round(excess_err, 4),
            "promotions": g.directory.promotions}


def bench_import_throughput(num_series: int = 20000, duration: float = 4.0):
    """Config #2d: metrics/sec MERGED through the whole import path —
    the second north-star metric (BASELINE.md: 'flush latency + metrics/
    sec merged'). A real gRPC ImportServer backed by the store receives
    pre-serialized MetricList batches of forwarded histogram digests;
    reported as series merged per second including wire decode, host
    staging, and the device scatter path. The Go counterpart is
    BenchmarkImportServerSendMetrics (importsrv/server_test.go:115)."""
    import grpc
    from google.protobuf import empty_pb2

    from veneur_tpu.core.store import ForwardableState, MetricStore
    from veneur_tpu.forward.convert import metric_list_from_state
    from veneur_tpu.forward.grpc_forward import _METHOD, ImportServer
    from veneur_tpu.protocol import forward_pb2

    rng = np.random.default_rng(0)
    K = 48
    # one host's forwarded batch: num_series digests, K centroids each
    means2d = np.sort(rng.gamma(2.0, 30.0, (num_series, K)), axis=1)
    state = ForwardableState()
    for i in range(num_series):
        state.histograms.append(
            (f"svc.latency.{i}", [f"shard:{i % 13}"], means2d[i],
             np.ones(K), float(means2d[i, 0]), float(means2d[i, -1])))
    # legacy wire: packed f64 arrays (what a pre-round-4 local sends)
    legacy_payload = metric_list_from_state(state).SerializeToString()
    # round-4 wire: quantized u16 centroids (what a local sends now),
    # built exactly as the packed flush would
    from veneur_tpu.core import columnar as cbv
    from veneur_tpu.core.store import PackedDigestPlanes
    from veneur_tpu.native import egress as eg

    quant_payload = None
    light_payload = None
    if eg.available():
        names = cbv.build_arenas(
            [f"svc.latency.{i}" for i in range(num_series)])
        tags = cbv.build_arenas(
            [f"shard:{i % 13}" for i in range(num_series)])

        def packed_payload(live_counts: np.ndarray) -> bytes:
            # ragged packed wire exactly as the packed flush emits it:
            # per-row live centroid counts, u16 range-quantized means,
            # bf16 weight bits
            total = int(live_counts.sum())
            q = np.empty(total, np.uint16)
            dmin = np.empty(num_series, np.float32)
            dmax = np.empty(num_series, np.float32)
            pos = 0
            for i in range(num_series):
                n = int(live_counts[i])
                m = means2d[i, :n]
                dmin[i], dmax[i] = m[0], m[-1]
                span = m[-1] - m[0]
                q[pos:pos + n] = np.clip(np.round(
                    (m - m[0]) / (span if span > 0 else 1) * 65535),
                    0, 65535).astype(np.uint16)
                pos += n
            wbf = (np.ones(total, np.float32).view(np.uint32)
                   >> 16).astype(np.uint16)
            planes = PackedDigestPlanes(
                live_counts.astype(np.uint16), q, wbf, dmin, dmax)
            return b"".join(eg.encode_digest_metrics_packed(
                names, tags, planes, 2))

        quant_payload = packed_payload(np.full(num_series, K, np.int64))
        # realistic forwarded density: each 10s interval leaves most
        # digests with a handful of live centroids (config 2e measures
        # ~1-5 on real intervals); 1-8 here, mean ~3.9
        light_payload = packed_payload(
            np.clip(rng.poisson(3.0, num_series) + 1, 1, 8))

    # 2^17 staging chunks: a 20k x 48-centroid batch drains in 8 device
    # dispatches instead of 30 — dispatch latency, not decode, is the
    # ceiling once the wire parse is native
    store = MetricStore(initial_capacity=1 << 15, chunk=1 << 17)
    srv = ImportServer(store)
    port = srv.start("127.0.0.1:0")
    payload = quant_payload if quant_payload is not None else legacy_payload

    def sender_loop(deadline, counter, lock, pl, messages=1 << 30):
        # each sender is one forwarding host with its own channel
        chan = grpc.insecure_channel(
            f"127.0.0.1:{port}",
            options=[("grpc.max_send_message_length", 256 << 20),
                     ("grpc.max_receive_message_length", 256 << 20)])
        send = chan.unary_unary(
            _METHOD,
            request_serializer=lambda b: b,
            response_deserializer=empty_pb2.Empty.FromString)
        try:
            for _ in range(messages):
                if time.perf_counter() > deadline:
                    return
                send(pl, timeout=300)
                with lock:
                    counter[0] += num_series
        finally:
            chan.close()

    try:
        import threading

        from veneur_tpu.forward.native_transport import (MAGIC,
                                                         NativeImportServer)
        from veneur_tpu.samplers.intermetric import HistogramAggregates

        def reset_store():
            # fresh generation between lanes: an unflushed store
            # accumulates device state across the merged intervals and
            # whatever lane measured last would read slow (swap-on-flush
            # makes this cheap; module-level programs survive the swap)
            store.flush([], HistogramAggregates.from_names(["count"]),
                        is_local=False, now=0, forward=False)

        chan = grpc.insecure_channel(
            f"127.0.0.1:{port}",
            options=[("grpc.max_send_message_length", 256 << 20),
                     ("grpc.max_receive_message_length", 256 << 20)])
        warm_send = chan.unary_unary(
            _METHOD,
            request_serializer=lambda b: b,
            response_deserializer=empty_pb2.Empty.FromString)
        # warm until sends run compile-free: the staging drains change
        # phase between the first calls, each new shape compiling a
        # scatter variant
        for _ in range(6):
            t0 = time.perf_counter()
            warm_send(payload, timeout=600)
            if time.perf_counter() - t0 < 1.5:
                break
        chan.close()

        import jax as _jax

        def barrier():
            # the import path dispatches device scatters asynchronously;
            # a rate without a completion barrier measures DISPATCH
            # throughput while backlog piles on the device queue (and
            # the next lane pays for it). Sustained = work + barrier.
            g = store.histograms
            g._drain_staging()
            count = (g.temps[-1].count if getattr(g, "temps", None)
                     else g.temp.count)
            float(np.asarray(_jax.device_get(count[:1]))[0])

        def run_grpc_round(seconds, pl=None):
            # two concurrent forwarding hosts: decode runs GIL-free in
            # C++, so a second stream overlaps transport with staging
            pl = payload if pl is None else pl
            counter, lock = [0], threading.Lock()
            deadline = time.perf_counter() + seconds
            t0 = time.perf_counter()
            senders = [threading.Thread(target=sender_loop,
                                        args=(deadline, counter, lock, pl))
                       for _ in range(2)]
            for t in senders:
                t.start()
            for t in senders:
                t.join()
            t_work = time.perf_counter() - t0
            barrier()
            return counter[0] / t_work, counter[0] / (time.perf_counter()
                                                      - t0)

        nsrv = NativeImportServer(store)
        nport = nsrv.start("127.0.0.1:0")

        def native_sender(deadline, counter, lock, pl):
            import socket as _socket
            import struct as _struct

            s = _socket.create_connection(("127.0.0.1", nport), 30)
            s.sendall(MAGIC)
            header = _struct.pack(">I", len(pl))
            try:
                while time.perf_counter() < deadline:
                    s.sendall(header)
                    s.sendall(pl)
                    got = 0
                    while got < 4:
                        r = s.recv(4 - got)
                        if not r:
                            raise OSError("server closed mid-ack")
                        got += len(r)
                    with lock:
                        counter[0] += num_series
            finally:
                s.close()

        def run_native_round(seconds, pl=None):
            pl = payload if pl is None else pl
            counter, lock = [0], threading.Lock()
            deadline = time.perf_counter() + seconds
            t0 = time.perf_counter()
            senders = [threading.Thread(target=native_sender,
                                        args=(deadline, counter, lock, pl))
                       for _ in range(2)]
            for t in senders:
                t.start()
            for t in senders:
                t.join()
            t_work = time.perf_counter() - t0
            barrier()
            return counter[0] / t_work, counter[0] / (time.perf_counter()
                                                      - t0)

        def run_store_round(pl, iters=4):
            t1 = time.perf_counter()
            for _ in range(iters):
                dec = eg.decode_metric_list(pl, copy=False)
                store.import_columnar(dec, pl)
                dec.close()
            t_work = time.perf_counter() - t1
            barrier()
            n = iters * num_series
            return n / t_work, n / (time.perf_counter() - t1)

        def run_store_round_mt(pl, threads=2, iters=4):
            # two importer threads: decode is GIL-free C++, staging
            # serializes under the store lock — the shape a 2-core
            # importer host runs. On THIS 1-core harness the aggregate
            # can only show no-collapse, not scaling; the GIL-release
            # proof below carries the parallelism claim.
            def worker():
                for _ in range(iters):
                    dec = eg.decode_metric_list(pl, copy=False)
                    store.import_columnar(dec, pl)
                    dec.close()

            t1 = time.perf_counter()
            ts = [threading.Thread(target=worker) for _ in range(threads)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            t_work = time.perf_counter() - t1
            barrier()
            n = threads * iters * num_series
            return n / t_work, n / (time.perf_counter() - t1)

        def measure_gil_release(pl, decodes=6):
            # prove the C++ MetricList decode drops the GIL: a spin
            # thread's progress while decodes run, vs its free-running
            # rate. A GIL-holding decode would freeze the spinner.
            stop = [False]
            ticks = [0]

            def spin():
                while not stop[0]:
                    ticks[0] += 1

            t = threading.Thread(target=spin)
            t.start()
            try:
                time.sleep(0.25)
                base0 = ticks[0]
                time.sleep(0.25)
                base_rate = (ticks[0] - base0) / 0.25
                d0 = ticks[0]
                t1 = time.perf_counter()
                for _ in range(decodes):
                    eg.decode_metric_list(pl, copy=False).close()
                dt = time.perf_counter() - t1
                during_rate = (ticks[0] - d0) / dt if dt > 0 else 0.0
            finally:
                stop[0] = True
                t.join()
            frac = during_rate / base_rate if base_rate else 0.0
            return {"spin_rate_during_decode_frac": round(frac, 2),
                    "released": bool(frac > 0.3),
                    "decode_only_series_per_s": int(
                        decodes * num_series / dt) if dt > 0 else None}

        # INTERLEAVED duration-based rounds, per-lane medians of TWO
        # rates: the PIPELINE rate (senders' wall only — transport +
        # C++ decode + intern + staging dispatch; round-3-comparable
        # methodology) and the SUSTAINED rate whose clock also covers
        # the post-round device barrier, i.e. the host-to-device upload
        # of the staged centroids. The reset between lanes stops queue
        # backlog from bleeding across them.
        rounds = 5
        lanes = {k: ([], []) for k in ("grpc", "native", "light",
                                       "light_grpc", "quant", "legacy",
                                       "quant_2t")}

        def record(key, pair):
            lanes[key][0].append(pair[0])
            lanes[key][1].append(pair[1])

        gil = None
        try:
            run_native_round(0.2)  # warm the native path
            if light_payload is not None:
                run_native_round(0.2, light_payload)  # + its shapes
            for _ in range(rounds):
                reset_store()
                record("grpc", run_grpc_round(duration / 2))
                reset_store()
                record("native", run_native_round(duration / 2))
                reset_store()
                if light_payload is not None:
                    # realistic forwarded density on BOTH transports:
                    # the per-core rate a fleet actually sees
                    record("light",
                           run_native_round(duration / 2, light_payload))
                    reset_store()
                    record("light_grpc",
                           run_grpc_round(duration / 2, light_payload))
                    reset_store()
                if eg.available():
                    record("quant", run_store_round(quant_payload))
                    reset_store()
                    record("quant_2t", run_store_round_mt(quant_payload))
                    reset_store()
                    record("legacy", run_store_round(legacy_payload))
            if eg.available():
                gil = measure_gil_release(quant_payload)
        finally:
            nsrv.stop()
        med = lambda xs: int(np.median(xs)) if xs else None  # noqa: E731

        def spread(xs):
            # half-range around the median over the interleaved rounds,
            # as a percentage: the in-artifact run-to-run stability
            # claim (VERDICT round-4 item #2b)
            if not xs or not np.median(xs):
                return None
            return round(100.0 * (max(xs) - min(xs)) / 2
                         / float(np.median(xs)), 1)

        return {"series_merged_per_s": med(lanes["grpc"][0]),
                "native_transport_series_per_s": med(lanes["native"][0]),
                "realistic_density_series_per_s": med(lanes["light"][0]),
                "realistic_density_grpc_series_per_s": med(
                    lanes["light_grpc"][0]),
                "store_path_series_per_s": med(lanes["quant"][0]),
                "store_path_2thread_series_per_s": med(lanes["quant_2t"][0]),
                "store_path_legacy_wire_per_s": med(lanes["legacy"][0]),
                "decode_gil_release": gil,
                "pipeline_spread_pct": {
                    "grpc": spread(lanes["grpc"][0]),
                    "native": spread(lanes["native"][0]),
                    "realistic": spread(lanes["light"][0]),
                    "realistic_grpc": spread(lanes["light_grpc"][0]),
                    "store_path": spread(lanes["quant"][0])},
                "wire_bytes_per_series": round(len(payload) / num_series),
                "wire_bytes_per_series_realistic": (
                    round(len(light_payload) / num_series)
                    if light_payload is not None else None),
                "senders": 2, "rounds": rounds,
                "batch_series": num_series,
                "centroids_per_digest": K,
                "single_core_harness": os.cpu_count() == 1,
                "note": "medians over %d interleaved rounds. Headline "
                        % rounds +
                        "rates are the HOST PIPELINE (transport + C++ "
                        "decode + intern + staging dispatch). "
                        "All lanes share one "
                        "core with their own bench clients. Ceilings "
                        "for THIS 48-centroid workload: host pipeline "
                        "per core (above), device scatter ~10-15M "
                        "centroids/s per chip (~250k series/s); the "
                        "fleet scales both axes — N importer cores and "
                        "mesh-sharded chips. realistic_density lanes "
                        "MEASURE the fleet-realistic workload on BOTH "
                        "transports (framed-TCP and gRPC): ragged "
                        "packed digests at 1-8 live centroids (mean "
                        "~3.9, matching what config 2e observes on "
                        "real forwarded intervals) instead of the "
                        "dense-48 stress shape the stress lanes carry. "
                        "store_path_2thread runs two importer threads "
                        "(GIL-free C++ decode, lock-serialized "
                        "staging); on this 1-core harness it can only "
                        "show no-collapse — decode_gil_release carries "
                        "the multi-core parallelism proof"}
    finally:
        srv.stop()


def bench_tls_handshakes(seconds: float = 2.5):
    """Config #7: TLS connection-establishment rate through the
    production TLS statsd listener (networking.py). The reference's
    README publishes its only non-pps perf numbers here: ~700
    connections/s with ECDH prime256v1 and ~110/s with RSA 2048, on
    localhost with 1 CPU (README.md:346). Same shape: localhost, the
    client hammering full handshakes on the same core as the server."""
    # When `cryptography` is absent (it only mints the bench's
    # self-signed certs — the server's TLS itself is stdlib ssl), the
    # lane degrades to measuring the PLAINTEXT TCP accept/connect path
    # on the same production listener and records tls: module-missing
    # alongside, instead of skipping the whole lane (which left 7_tls
    # blocked from r05 through r08). Install the bench extras
    # (docs/development.md) to get the TLS numbers.
    import datetime
    import ipaddress
    import socket
    import ssl
    import tempfile
    import threading

    from veneur_tpu.networking import make_server_tls_context, start_statsd

    try:
        from cryptography import x509
        from cryptography.hazmat.primitives import hashes, serialization
        from cryptography.hazmat.primitives.asymmetric import ec, rsa
        from cryptography.x509.oid import NameOID
    except ImportError:
        stop = threading.Event()
        _readers, bound = start_statsd(
            "tcp://127.0.0.1:0", num_readers=1, recv_buf=0,
            metric_max_length=4096, handle_packet=lambda b: None,
            stop=stop)
        port = bound[0][1]
        n = errs = 0
        deadline = time.perf_counter() + seconds
        t0 = time.perf_counter()
        while time.perf_counter() < deadline:
            try:
                conn = socket.create_connection(("127.0.0.1", port),
                                                timeout=2.0)
                conn.close()
                n += 1
            except OSError:
                errs += 1
        took = time.perf_counter() - t0
        stop.set()
        return {
            "tls": "module-missing",
            "note": "cryptography absent (cert minting only; server "
                    "TLS is stdlib ssl): measured the plaintext-TCP "
                    "handshake path on the same listener. Install the "
                    "bench extras (docs/development.md) for TLS",
            "plaintext_tcp_conn_s": round(n / took, 1),
            "connections": n, "errors": errs}

    def self_signed(key):
        name = x509.Name(
            [x509.NameAttribute(NameOID.COMMON_NAME, "localhost")])
        now = datetime.datetime.now(datetime.timezone.utc)
        return (x509.CertificateBuilder()
                .subject_name(name).issuer_name(name)
                .public_key(key.public_key())
                .serial_number(x509.random_serial_number())
                .not_valid_before(now - datetime.timedelta(minutes=5))
                .not_valid_after(now + datetime.timedelta(days=1))
                .add_extension(x509.SubjectAlternativeName(
                    [x509.DNSName("localhost"),
                     x509.IPAddress(ipaddress.ip_address("127.0.0.1"))]),
                    critical=False)
                .sign(key, hashes.SHA256()))

    from veneur_tpu import native

    out = {}
    for label, key in (
            ("ecdsa_p256", ec.generate_private_key(ec.SECP256R1())),
            ("rsa_2048", rsa.generate_private_key(public_exponent=65537,
                                                  key_size=2048))):
        cert = self_signed(key)
        stop = threading.Event()
        cert_path = key_path = None
        reader = None
        try:
            with tempfile.NamedTemporaryFile("wb", suffix=".pem",
                                             delete=False) as cf:
                cert_path = cf.name
                cf.write(cert.public_bytes(serialization.Encoding.PEM))
            with tempfile.NamedTemporaryFile("wb", suffix=".pem",
                                             delete=False) as kf:
                key_path = kf.name
                kf.write(key.private_bytes(
                    serialization.Encoding.PEM,
                    serialization.PrivateFormat.PKCS8,
                    serialization.NoEncryption()))

            # the PRODUCTION listener: the native C++ TCP/TLS reader
            # when it builds (the server's default wiring), the Python
            # readers otherwise
            use_native = native.available() and native.tls_available()
            if use_native:
                reader = native.NativeTLSReader(
                    cert_path=cert_path, key_path=key_path)
                port = reader.port
            else:
                ctx = make_server_tls_context(cert_path, key_path)
                _, bound = start_statsd(
                    "tcp://127.0.0.1:0", num_readers=1, recv_buf=0,
                    metric_max_length=4096, handle_packet=lambda b: None,
                    stop=stop, tls_config=ctx)
                port = bound[0][1]
            out[f"{label}_native_listener"] = use_native

            def rate(max_ver, secs):
                # pre-resolved AF_INET connect: getaddrinfo per
                # connection is bench-client tax, not server capacity
                cctx = ssl.create_default_context()
                cctx.load_verify_locations(cert_path)
                if max_ver is not None:
                    cctx.maximum_version = max_ver
                n = errs = 0
                deadline = time.perf_counter() + secs
                t0 = time.perf_counter()
                while time.perf_counter() < deadline:
                    raw = socket.socket(socket.AF_INET,
                                        socket.SOCK_STREAM)
                    try:
                        raw.connect(("127.0.0.1", port))
                        cctx.wrap_socket(
                            raw, server_hostname="localhost").close()
                        n += 1
                    except OSError:
                        # the failed fd must not leak toward EMFILE
                        raw.close()
                        errs += 1
                        if errs > 50:
                            raise
                return n / (time.perf_counter() - t0), errs

            rate(None, 0.3)  # warm
            # interleaved rounds + medians: single-window numbers swing
            # +-20% run to run on this shared harness. A mid-run
            # failure still reports the rounds measured up to that
            # point (0 when nothing succeeded — a failed config must
            # be distinguishable from a skipped one).
            r13, r12, errs = [], [], 0
            try:
                for _ in range(5):
                    r, e = rate(None, seconds / 2)
                    r13.append(r)
                    errs += e
                    r, e = rate(ssl.TLSVersion.TLSv1_2, seconds / 2)
                    r12.append(r)
                    errs += e
            finally:
                # the headline matches the reference's workload era:
                # its ~700/s claim is "ECDH prime256v1", a
                # TLS1.2-generation handshake; TLS1.3 rides alongside
                out[f"{label}_conn_s"] = int(np.median(r12)) if r12 else 0
                out[f"{label}_tls13_conn_s"] = \
                    int(np.median(r13)) if r13 else 0
                if r12 or r13:
                    out[f"{label}_conn_s_max"] = int(max(r12 + r13))
                if len(r12) < 5:
                    out[f"{label}_partial"] = True
                if errs:
                    out[f"{label}_transient_errors"] = errs
                if reader is not None:
                    out[f"{label}_handshake_failures"] = \
                        reader.handshake_failures()
        except Exception as e:
            # keep the other key type's result (guarded() would drop all)
            out[f"{label}_error"] = f"{type(e).__name__}: {e}"[:120]
            if f"{label}_conn_s" in out:
                out[f"{label}_partial"] = True
        finally:
            stop.set()
            if reader is not None:
                reader.stop()
            for p in (cert_path, key_path):
                if p is not None:
                    try:
                        os.unlink(p)
                    except OSError:
                        pass
    out["reference_readme_conn_s"] = {"ecdh_prime256v1": 700,
                                      "rsa_2048": 110}
    out["note"] = ("full handshake + close per connection against the "
                   "production statsd listener (native C++ TLS "
                   "termination when available); client and server "
                   "share one core, as in the reference's "
                   "localhost/1-CPU claim (README.md:346); medians "
                   "over 5 interleaved rounds per TLS version")
    return out


def bench_ssf_spans(duration: float = 3.0):
    """Config #8: SSF span ingest end-to-end — bare SSFSpan protobuf UDP
    datagrams through the REAL server: protocol/wire parse, span
    channel, SpanWorker lanes into a blackhole span sink, metric
    samples riding each span for the ssfmetrics extraction path. The
    reference ships the Go counterparts as unpublished microbenchmarks
    (BenchmarkSendSSFUDP server_test.go:1004, BenchmarkHandleSSF
    :1381, BenchmarkHandleTracePacket :1365)."""
    import socket

    from veneur_tpu.config import Config
    from veneur_tpu.protocol import ssf_pb2
    from veneur_tpu.server import Server
    from veneur_tpu.sinks import BlackholeSpanSink

    span = ssf_pb2.SSFSpan()
    span.id = 12345
    span.trace_id = 67890
    span.start_timestamp = 1_700_000_000 * 10**9
    span.end_timestamp = span.start_timestamp + 5 * 10**6
    span.service = "bench"
    span.name = "bench.op"
    span.tags["host"] = "bench-host"
    for i in range(2):
        m = span.metrics.add()
        m.metric = ssf_pb2.SSFSample.COUNTER
        m.name = f"bench.sample.{i}"
        m.value = 1.0
        m.sample_rate = 1.0
    payload = span.SerializeToString()

    cfg = Config(statsd_listen_addresses=[],
                 ssf_listen_addresses=["udp://127.0.0.1:0"],
                 interval="86400s", num_readers=1, num_span_workers=2,
                 store_initial_capacity=1 << 10, store_chunk=1 << 12)
    server = Server(cfg, metric_sinks=[], span_sinks=[BlackholeSpanSink()])
    server.start()

    def ingested_total():
        return sum(w.ingested for w in server._span_workers)

    def settle():
        deadline = time.time() + 10.0
        last = -1
        while time.time() < deadline:
            got = ingested_total()
            if got == last:
                return got
            last = got
            time.sleep(0.2)
        return ingested_total()

    try:
        # phase 1 — the Go-microbench shape (BenchmarkHandleSSF calls
        # the handler, no socket): parse + channel + worker lanes, the
        # caller sharing the core with the workers. The caller paces on
        # channel depth: an unpaced caller just hogs the GIL and the
        # bounded channel sheds, which measures drop rate, not pipeline
        # capacity (ingested_frac reports how lossless the run was)
        chan = server.span_chan
        n_direct = 0
        deadline = time.perf_counter() + duration
        t0 = time.perf_counter()
        while time.perf_counter() < deadline:
            if chan.qsize() > 48:
                time.sleep(0.0002)
                continue
            for _ in range(32):
                server.handle_ssf_packet(payload)
            n_direct += 32
        direct_wall = time.perf_counter() - t0
        direct_ingested = settle()

        # phase 2 — UDP e2e blast. With native_ingest (the default) the
        # datagrams decode as SSFSpans ON the C++ reader threads and
        # their embedded metrics ride the vectorized store lane
        # (round-4 verdict item #5); the kernel load-balances to the
        # reader while the sender hogs the same core, so the
        # sent/ingested gap is drop behavior under overload, reported
        # rather than hidden
        base = ingested_total()
        native_lane = bool(server._native_ssf_readers)
        port = server.ssf_addrs[0][1]
        sender = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        sender.connect(("127.0.0.1", port))
        sent = 0
        deadline = time.perf_counter() + duration
        t0 = time.perf_counter()
        while time.perf_counter() < deadline:
            for _ in range(64):
                sender.send(payload)
            sent += 64
        udp_wall = time.perf_counter() - t0
        sender.close()
        udp_ingested = settle() - base
        udp_decoded = (server._native_ssf_readers[0].packets()
                       if native_lane else None)

        # phase 3 — the C++ batch decoder's own ceiling: spans decoded
        # + samples converted per second, GIL-free (parallelizable
        # across reader threads on a multi-core host)
        decode_per_s = None
        from veneur_tpu import native as _nat
        if _nat.available():
            batch = [payload] * 4096
            _nat.decode_spans(batch)  # warm
            t0 = time.perf_counter()
            reps = 8
            for _ in range(reps):
                db = _nat.decode_spans(batch)
            decode_per_s = int(reps * len(batch)
                               / (time.perf_counter() - t0))
            assert db.count == len(batch)

        return {"handle_ssf_per_s": int(direct_ingested / direct_wall),
                "handle_ssf_called_per_s": int(n_direct / direct_wall),
                "handle_ssf_ingested_frac": round(
                    direct_ingested / max(n_direct, 1), 3),
                "udp_sent_per_s": int(sent / udp_wall),
                "udp_ingested_per_s": int(udp_ingested / udp_wall),
                "udp_ingested_frac": round(udp_ingested / max(sent, 1), 3),
                "udp_native_lane": native_lane,
                "udp_decoded_spans": udp_decoded,
                "native_decode_spans_per_s": decode_per_s,
                "span_bytes": len(payload),
                "samples_per_span": 2,
                "note": "one core shared by caller/sender and the "
                        "span workers. handle_ssf = the PYTHON "
                        "pipeline (parse + channel + worker lanes, "
                        "the reference's BenchmarkHandleSSF shape); "
                        "the UDP blast rides the native C++ span lane "
                        "when udp_native_lane is true, and its "
                        "sent/ingested gap is bounded-channel shedding "
                        "under overload, the designed behavior. "
                        "native_decode_spans_per_s is the GIL-free C++ "
                        "decode+convert ceiling per core"}
    finally:
        server.shutdown()


def bench_proxy_fanout(duration: float = 3.0, n_dests: int = 3,
                       batch: int = 20000):
    """Config #9: the consistent-hash proxy's metric fan-out end to end
    — JSON metric batches through the REAL Proxy (ring hash, per-dest
    bucketing, deflate, parallel POSTs) into in-process receivers that
    read and 202 each body. Counterpart of the reference's unpublished
    BenchmarkProxyServerSendMetrics (proxysrv/server_test.go:225) and
    the sort-by-destination half of BenchmarkNewSortableJSONMetrics
    (http_test.go:381); proxy + all receivers share one core here."""
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from veneur_tpu.config import ProxyConfig
    from veneur_tpu.discovery import StaticDiscoverer
    from veneur_tpu.proxy.proxy import Proxy

    received = [0]
    rlock = threading.Lock()

    class _Recv(BaseHTTPRequestHandler):
        def do_POST(self):
            n = int(self.headers.get("Content-Length") or 0)
            while n > 0:
                n -= len(self.rfile.read(min(n, 1 << 16)))
            # count BEFORE the 202: the proxy unblocks on the response,
            # so a post-response increment can land after the bench
            # reads the counter
            with rlock:
                received[0] += 1
            self.send_response(202)
            self.end_headers()

        def log_message(self, *a):  # noqa: N802 - stdlib naming
            pass

    servers, dests = [], []
    for _ in range(n_dests):
        srv = ThreadingHTTPServer(("127.0.0.1", 0), _Recv)
        srv.daemon_threads = True
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        servers.append(srv)
        dests.append(f"http://127.0.0.1:{srv.server_address[1]}")

    proxy = Proxy(ProxyConfig(http_address="127.0.0.1:0",
                              forward_timeout="10s"),
                  discoverer=StaticDiscoverer(dests))
    proxy.start()
    try:
        # one forwarding host's /import body: mixed counter/gauge JSON
        # metrics across distinct series, the wire the proxy actually
        # shards (handlers_global.go:28-43)
        metrics = [{"name": f"svc.m.{i % 8192}",
                    "type": "counter" if i % 2 else "gauge",
                    "tags": [f"shard:{i % 13}"],
                    "value": [float(i)]}
                   for i in range(batch)]
        proxy.proxy_metrics(metrics)  # warm connections/ring
        with rlock:
            received[0] = 0
        base_proxied, base_errors = proxy.proxied, proxy.forward_errors
        sent = 0
        deadline = time.perf_counter() + duration
        t0 = time.perf_counter()
        while time.perf_counter() < deadline:
            proxy.proxy_metrics(metrics)
            sent += batch
        wall = time.perf_counter() - t0
        # a failed run must be distinguishable from a clean one: the
        # headline only counts metrics the proxy ACKNOWLEDGED (its own
        # proxied counter), with errors reported alongside
        proxied = proxy.proxied - base_proxied
        return {"metrics_per_s": int(proxied / wall),
                "metrics_sent_per_s": int(sent / wall),
                "forward_errors": proxy.forward_errors - base_errors,
                "batch": batch,
                "destinations": n_dests,
                "bodies_received": received[0],
                "note": "proxy + receivers on one shared core; each "
                        "batch rides ring hash + per-dest bucketing + "
                        "deflate + parallel POST, fully acknowledged "
                        "before the next batch (proxy_metrics joins "
                        "its POST threads)"}
    finally:
        proxy.shutdown()
        for srv in servers:
            srv.shutdown()
            srv.server_close()  # shutdown() alone leaks the listen fd


def bench_merge_global(num_series: int, digest_dtype: str = "bfloat16",
                       iters: int = 5):
    """Config #2c: the single-chip global-aggregator kernel — merge one
    full imported host batch of digests into the resident bank, then the
    percentile flush. The Go equivalent is ImportMetricGRPC -> Merge per
    series (worker.go:354-398) + the quantile walks of Histo.Flush."""
    import jax.numpy as jnp
    from veneur_tpu.core.slab import SlabDigestBank
    from veneur_tpu.ops import tdigest as td_ops

    bank = SlabDigestBank(num_series, compression=100.0,
                          digest_dtype=jnp.dtype(digest_dtype), mode="merge")
    nslabs, slab, k = bank.num_slabs, bank.slab_rows, bank.k
    rng = np.random.default_rng(0)
    # one forwarded batch: per-slab [slab, k] sorted centroids (generated
    # on device, untimed — the wire decode is benched separately in
    # tests/test_forward.py scale runs)
    base = jnp.sort(jnp.asarray(
        rng.gamma(2.0, 40.0, (slab, k)).astype(np.float32)), axis=1)
    w_in = jnp.ones((slab, k), jnp.float32)
    mins = base[:, 0]
    maxs = base[:, -1]

    def merge_batch():
        for i in range(nslabs):
            bank.merge_digests(i, base, w_in, mins, maxs)
        float(bank.digests[-1].dmax.max())

    def flush():
        outs = bank.flush(QS, fetch=False)
        # ONE completion barrier over every slab's output (a scalar that
        # depends on all of them): per-slab scalar fetches add a
        # serialized host-device round trip per slab to every iteration
        # — measurement overhead, not flush work
        float(sum(jnp.nansum(o["percentiles"]) for o in outs))

    merge_batch()
    flush()  # warmup
    m_times, f_times = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        merge_batch()
        m_times.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        flush()
        f_times.append(time.perf_counter() - t0)
    plan = bank.hbm_bytes()
    return {"merge_p50_ms": round(float(np.median(m_times)) * 1e3, 3),
            "flush_p50_ms": round(float(np.median(f_times)) * 1e3, 3),
            "iters": iters, "series": num_series,
            "digest_dtype": digest_dtype,
            "resident_gb": round(plan["total_bytes"] / 2**30, 2)}


def bench_ingest_pps(duration: float = 3.0, senders: int = 3):
    """Ingest throughput over real loopback UDP: the C++ recvmmsg reader
    pool + batch parser + vectorized store ingest, single process.
    Reported as packets/s received and records/s fully processed into
    the store — the reference's >60k pps claim (README.md:285-289) is
    the bar."""
    import socket

    from veneur_tpu.config import Config
    from veneur_tpu.server import Server

    # ingest_lanes: -1 pins the LEGACY C++ reader-pool path — this lane
    # is the single-pipeline baseline the 0b_ingest_fleet lane scales
    # against (the default 0 would route UDP through the lane fleet)
    cfg = Config(statsd_listen_addresses=["udp://127.0.0.1:0"],
                 interval="86400s", aggregates=["count"], num_readers=4,
                 ingest_lanes=-1)
    srv = Server(cfg, metric_sinks=[])
    srv.start()
    procs = []
    try:
        if not srv._native_readers:
            return {"error": "native ingest unavailable"}
        port = srv.statsd_addrs[0][1]
        payload = b"svc.req.latency:%d|ms|@0.5|#route:r1,env:prod"

        # warm the whole path first: the first chunk-full staging drain
        # triggers the device scatter-program compile (~30-60 s on TPU),
        # during which the pump blocks and everything drops. processed
        # advances at batch entry, so "one record processed" proves
        # nothing — push enough traffic for SEVERAL full chunks to have
        # drained (compile done, steady state reached) before timing.
        warm = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        warm.connect(("127.0.0.1", port))
        deadline = time.time() + 240
        want = cfg.store_chunk * 4
        while srv.store.processed < want and time.time() < deadline:
            for _ in range(256):
                warm.send(payload % 1)
            time.sleep(0.02)
        warm.close()
        if srv.store.processed < want:
            return {"error": "ingest path did not warm up"}

        # senders are SUBPROCESSES: in-process threads would contend for
        # this interpreter's GIL with the drain pump, measuring sender
        # overhead instead of server capacity
        blast = (
            "import socket,sys,time\n"
            f"s=socket.socket(socket.AF_INET,socket.SOCK_DGRAM)\n"
            f"s.connect(('127.0.0.1',{port}))\n"
            "msgs=[('svc.req.latency:%d|ms|@0.5|#route:r%d,env:prod'"
            " % (i%497,i%7)).encode() for i in range(64)]\n"
            f"end=time.time()+{duration + 2.0}\n"
            "n=0\n"
            "while time.time()<end:\n"
            "    s.send(msgs[n&63]); n+=1\n")
        procs = [subprocess.Popen([sys.executable, "-c", blast],
                                  env={"PATH": os.environ.get("PATH", "")})
                 for _ in range(senders)]
        time.sleep(0.7)
        reader = srv._native_readers[0]
        p0, r0, d0 = reader.packets(), srv.store.processed, reader.drops()
        t0 = time.perf_counter()
        time.sleep(duration)
        p1, r1, d1 = reader.packets(), srv.store.processed, reader.drops()
        dt = time.perf_counter() - t0
        return {"packets_per_s": int((p1 - p0) / dt),
                "records_per_s": int((r1 - r0) / dt),
                "drops": int(d1 - d0),
                "duration_s": duration}
    finally:
        for p in procs:
            p.wait(timeout=30)
        srv.shutdown()


_FLEET_BLAST = r'''
import os, socket, sys, time
# recvmmsg.py is stdlib-only: import it by file so the sender skips the
# package __init__ (and with it the multi-second jax import)
sys.path.insert(0, os.path.join(os.getcwd(), "veneur_tpu", "ingest"))
from recvmmsg import BatchSender
port, dur, burst, gap = (int(sys.argv[1]), float(sys.argv[2]),
                         int(sys.argv[3]), float(sys.argv[4]))
msgs = [("svc.req.latency:%d|ms|@0.5|#route:r%d,env:prod"
         % (i % 497, i % 7)).encode() for i in range(64)]
senders = []
for i in range(16):
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    s.connect(("127.0.0.1", port))
    senders.append(BatchSender(s, msgs[(i % 2) * 32:(i % 2) * 32 + 32]))
end = time.time() + dur
i = 0
while time.time() < end:
    for _ in range(burst):
        senders[i % 16].send_cycle()
        i += 1
    if gap:
        time.sleep(gap)
'''


def bench_ingest_fleet(duration: float = 3.0, lane_counts=(1, 2, 4, 8),
                       senders: int = 2):
    """Ingest-lane fleet scaling (veneur_tpu/ingest/): packets/s over
    real loopback UDP vs ``ingest_lanes``, plus the share-nothing
    decode+stage capacity of one lane in isolation.

    The fleet is driven directly (MetricStore + IngestFleet, no server
    shell) by subprocess load generators that batch with ``sendmmsg``
    across 16 source ports each — one ``send()`` syscall per datagram
    would saturate the sender core long before any lane, and 16 flows
    per sender keep SO_REUSEPORT's 4-tuple hash spreading datagrams
    over every lane. ``linearity_ratio_4x`` is the 4-lane/1-lane
    packets/s ratio; on hosts with fewer cores than
    lanes + senders + merger the wire ratio measures the scheduler,
    not the subsystem — ``core_limited`` flags that, and the
    ``lane_decode_rps`` section (in-process spans, no sockets) shows
    the per-lane staging capacity and its thread-scaling ceiling."""
    import os as _os
    import socket as _socket
    import threading

    from veneur_tpu.core.store import MetricStore
    from veneur_tpu.ingest import IngestFleet, recvmmsg_available
    from veneur_tpu.ingest.lanes import IngestLane
    from veneur_tpu.protocol.addr import resolve_addr

    chunk = 1 << 14
    configs = {}
    for lanes in lane_counts:
        store = MetricStore(initial_capacity=1 << 14, chunk=chunk)
        fleet = IngestFleet(store, resolve_addr("udp://127.0.0.1:0"),
                            lanes, 1 << 21, 4096)
        fleet.start()
        port = fleet.bound[0][1]
        procs = [subprocess.Popen(
            [sys.executable, "-c", _FLEET_BLAST, str(port), "600",
             "3", "0.001"], cwd=_HERE) for _ in range(senders)]
        entry = {"lanes": lanes}
        try:
            # warm until the store has drained several full staging
            # chunks: the first drain compiles the device scatter, and
            # a compile inside the timed window measures XLA, not
            # ingest (same contract as 0_ingest_udp's warmup)
            deadline = time.time() + 60
            while (fleet.totals()["merged"] < 4 * chunk
                   and time.time() < deadline):
                time.sleep(0.25)
            if fleet.totals()["merged"] < 4 * chunk:
                entry["error"] = "fleet did not warm up"
                continue
            t0 = time.perf_counter()
            p0 = fleet.totals()["packets"]
            time.sleep(duration)
            p1 = fleet.totals()["packets"]
            dt = time.perf_counter() - t0
            entry["packets_per_s"] = int((p1 - p0) / dt)
        finally:
            for p in procs:
                p.terminate()
            for p in procs:
                p.wait(timeout=30)
            fleet.shutdown()
            t = fleet.totals()
            bal = fleet.balance()
            entry.update({
                "syscalls_per_packet": t["syscalls_per_packet"],
                "merged": t["merged"], "shed": t["shed_records"],
                "quarantined": t["quarantined"],
                "balance_ok": bal["ok"]})
            configs[str(lanes)] = entry

    # lane decode+stage capacity in isolation: prebuilt datagram spans
    # through the real native parse + columnar staging, no sockets —
    # the per-lane ceiling the wire number approaches as cores allow,
    # and (at 2/4 threads) how far the GIL lets lanes overlap
    msgs = [("svc.req.latency:%d|ms|@0.5|#route:r%d,env:prod"
             % (i % 497, i % 7)).encode() for i in range(64)]
    span = [msgs[i % 64] for i in range(2048)]

    def lane_only():
        s = _socket.socket(_socket.AF_INET, _socket.SOCK_DGRAM)
        s.bind(("127.0.0.1", 0))
        return IngestLane(0, s, 4096, chunk, threading.Event())

    def stage_for(lane, dur, out):
        stage = (lane._stage_native if lane.using_native
                 else lane._stage_python)
        n = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < dur:
            stage(span)
            lane.sealed.clear()
            n += len(span)
        out.append(int(n / (time.perf_counter() - t0)))

    decode_rps = {}
    native_decode = None
    for nthreads in (1, 2, 4):
        pool = [lane_only() for _ in range(nthreads)]
        if native_decode is None:
            native_decode = pool[0].using_native
        for lane in pool:
            stage_for(lane, 0.2, [])  # warm
        out = []
        threads = [threading.Thread(target=stage_for, args=(lane, 1.5, out))
                   for lane in pool]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        decode_rps[str(nthreads)] = sum(out)

    pps1 = configs.get("1", {}).get("packets_per_s")
    pps4 = configs.get("4", {}).get("packets_per_s")
    cpus = _os.cpu_count() or 1
    out = {"configs": configs,
           "lane_decode_rps": decode_rps,
           "cpu_count": cpus,
           # senders + merger + lanes all need a core for the wire
           # ratio to measure the fleet rather than the scheduler
           "core_limited": cpus < 4 + senders + 1,
           "recvmmsg": recvmmsg_available(),
           "native_decode": native_decode,
           "duration_s": duration}
    if pps1 and pps4:
        out["linearity_ratio_4x"] = round(pps4 / pps1, 2)
    return out


def bench_scalar_flush():
    """Config #1: 10k counters + 10k gauges through the host scalar path
    (example.yaml's default shape). Columnar egress (the server default)
    plus the legacy per-row InterMetric path for comparison."""
    from veneur_tpu.core.store import MetricStore
    from veneur_tpu.samplers.intermetric import HistogramAggregates
    from veneur_tpu.samplers.parser import MetricKey

    agg = HistogramAggregates.from_names(["count"])

    def run(columnar):
        times = []
        for it in range(5):
            store = MetricStore(initial_capacity=1 << 14, chunk=1 << 14)
            for i in range(10000):
                store.counters.sample(
                    MetricKey(name=f"c{i}", type="counter"), [], 1.0, 1.0)
                store.gauges.sample(
                    MetricKey(name=f"g{i}", type="gauge"), [], float(i), 1.0)
            t0 = time.perf_counter()
            final, _, _ = store.flush([], agg, is_local=True, now=0,
                                      forward=False, columnar=columnar)
            times.append(time.perf_counter() - t0)
            assert len(final) == 20000
        return round(float(np.median(times)) * 1e3, 3)

    out = {"p50_ms": run(True), "series": 20000,
           "p50_legacy_ms": run(False)}
    return out


def bench_obs_overhead(iters: int = 12, num_series: int = 8192,
                       samples_per_series: int = 6):
    """Lane 10: the observability tax. Full server flush p50/p99 with
    stage instrumentation ON (obs_enabled, the default) vs OFF, same
    workload — the acceptance gate (instrumented p50 <= 3% over
    baseline) becomes a measured number instead of a claim. The
    workload mixes digests (device programs, where the per-stage hooks
    nest deepest) with scalars.

    Methodology (r08 fix): a PAIRED design — BOTH servers live in one
    process, fed identical samples, flushed back to back every
    iteration with the flush order alternating; the statistic is the
    median per-iteration (on − off) difference. The old
    baseline-run-then-instrumented-run ordering charged whatever the
    host drifted between the two runs to the instrumentation: this
    container drifts ±10-25% at the minutes scale (allocator
    fragmentation, co-tenancy, frequency scaling) — an A/A control
    measured a larger "overhead" than the real A/B delta, and two
    isolated-subprocess r08 runs of the SAME lane measured −2.5% and
    +16.6% an hour apart. Pairing cancels exactly that drift: both
    modes see the same machine moment, order alternation cancels the
    first/second flush bias, and the median absorbs per-pair jitter.

    Honesty note on scale: the instrumentation cost is FIXED per
    interval (one extra small digest-group flush for the self-telemetry
    rows, ~20 deque appends, ~17 child spans), not proportional to
    cardinality — so the percentage gate only means something at a
    flush large enough to represent production (the tax against a toy
    512-series flush reads ~10x worse). The record carries the absolute
    ms delta alongside the percentage so both readings are visible."""
    from veneur_tpu.config import Config
    from veneur_tpu.samplers import parser as p
    from veneur_tpu.server import Server
    from veneur_tpu.sinks import ChannelMetricSink

    metrics = []
    for i in range(num_series):
        for j in range(samples_per_series):
            metrics.append(p.parse_metric(
                f"obs.h{i}:{(i * 7 + j) % 100}|h".encode()))
        metrics.append(p.parse_metric(f"obs.c{i}:1|c".encode()))

    def boot(obs_enabled: bool):
        cfg = Config(statsd_listen_addresses=[], interval="86400s",
                     percentiles=[0.5, 0.99], obs_enabled=obs_enabled,
                     store_initial_capacity=max(1024, num_series),
                     store_chunk=1 << 13)
        sink = ChannelMetricSink()
        srv = Server(cfg, metric_sinks=[sink])
        srv.start()
        return srv, sink

    srv_off, sink_off = boot(False)
    srv_on, sink_on = boot(True)
    offs, ons, diffs = [], [], []
    try:
        for it in range(iters + 2):
            for m in metrics:
                srv_off.store.process_metric(m)
                srv_on.store.process_metric(m)
            took = {}
            order = (srv_off, srv_on) if it % 2 == 0 \
                else (srv_on, srv_off)
            for srv in order:
                t0 = time.perf_counter()
                srv.flush()
                took[srv is srv_on] = time.perf_counter() - t0
            sink_off.get_flush()
            sink_on.get_flush()
            if it >= 2:  # first two intervals pay compiles
                offs.append(took[False])
                ons.append(took[True])
                diffs.append(took[True] - took[False])
    finally:
        srv_off.shutdown()
        srv_on.shutdown()
    base_p50 = round(float(np.percentile(offs, 50)) * 1e3, 3)
    inst_p50 = round(float(np.percentile(ons, 50)) * 1e3, 3)
    delta_ms = round(float(np.median(diffs)) * 1e3, 3)
    overhead_pct = round(delta_ms / base_p50 * 100.0, 2) \
        if base_p50 else 0.0
    lane = _obs_lane_overhead()
    out = {"series": num_series, "iters": iters,
           "p50_ms_baseline": base_p50,
           "p99_ms_baseline":
           round(float(np.percentile(offs, 99)) * 1e3, 3),
           "p50_ms_instrumented": inst_p50,
           "p99_ms_instrumented":
           round(float(np.percentile(ons, 99)) * 1e3, 3),
           "paired_diff_ms": [round(d * 1e3, 1) for d in diffs],
           "overhead_abs_ms_p50": delta_ms,
           "overhead_pct_p50": overhead_pct,
           # the acceptance gate: the paired median within 3% of
           # baseline (negative overhead = noise floor), AND — since
           # the trace plane extended tracing onto the ingest path —
           # the lane decode+stage rate within 3% of untraced
           "within_3pct_gate": overhead_pct <= 3.0
           and lane["lane_overhead_pct"] <= 3.0}
    out.update(lane)
    return out


def _obs_lane_overhead(duration: float = 1.5):
    """The ingest-path tracing tax (PR 13): lane decode+stage records/s
    with per-stage tracing ON (obs_enabled, the default: ~4 monotonic
    clock reads per recv ITERATION, never per record, plus the
    always-on per-chunk ingest-era wall stamp) vs trace_stages=False.
    Same single-lane decode loop the 0b_ingest_fleet lane rates."""
    import socket as _socket
    import threading

    from veneur_tpu.ingest import IngestLane

    span = [f"obs.h{i % 64}:{i % 97}|ms".encode() for i in range(1024)]

    def rate(trace_stages: bool) -> int:
        s = _socket.socket(_socket.AF_INET, _socket.SOCK_DGRAM)
        s.bind(("127.0.0.1", 0))
        lane = IngestLane(0, s, 4096, 1 << 14, threading.Event(),
                          trace_stages=trace_stages)
        try:
            stage = (lane._stage_native if lane.using_native
                     else lane._stage_python)
            for _ in range(5):  # warm
                stage(span)
                lane.sealed.clear()
            n = 0
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < duration:
                stage(span)
                lane._seal()
                lane.sealed.clear()
                n += len(span)
            return int(n / (time.perf_counter() - t0))
        finally:
            s.close()

    off = rate(False)
    on = rate(True)
    pct = round((off - on) / off * 100.0, 2) if off else 0.0
    return {"lane_rps_untraced": off, "lane_rps_traced": on,
            "lane_overhead_pct": pct}


def bench_egress_1m(num_series: int = 1 << 20):
    """Config #6: the SERVER's egress — now the OVERLAPPED pipeline
    (core/pipeline.py; ROADMAP open item 2). The r05 measurement showed
    this interval as the SUM of its lanes (4.6 s = compute + per-group
    fetch + serialize/deflate + POST, each waiting for the previous);
    the pipelined flush dispatches every group's program before any
    blocking fetch, serializes completed groups on the serializer lane
    while the next group's fetch blocks, and STREAMS each chunk to a
    real DatadogMetricSink (native serialize, deflate level 1) POSTing
    to a loopback HTTP server — live sockets, so the POST lane is real.

    The gate comes from the timeline itself (obs/timeline.py
    annotate_overlap over a StageRecorder wrapping the flush): egress
    wall-clock <= 1.2 x max(compute, transfer, POST). The same shape
    also runs SEQUENTIALLY (flush_pipeline_depth 0, batch sink flush)
    so the sum-vs-max win is measured in one container, not across
    artifact generations. Production server shape: the 1M series split
    across the four digest scope-classes (histograms, timers, and the
    local-only pair), which is also what gives the pipeline group
    boundaries to overlap."""
    import http.server
    import threading

    from veneur_tpu import obs
    from veneur_tpu.core.pipeline import ChunkStream
    from veneur_tpu.core.store import MetricStore
    from veneur_tpu.native import egress
    from veneur_tpu.obs.timeline import annotate_overlap
    from veneur_tpu.samplers.intermetric import HistogramAggregates
    from veneur_tpu.samplers.parser import MetricKey
    from veneur_tpu.sinks.datadog import DatadogMetricSink

    if not egress.available():
        return {"error": "native egress unavailable"}
    import jax

    scaled = False
    if jax.default_backend() == "cpu" and num_series > (1 << 18):
        # no-TPU containers: the 1M shape runs ~3x the 900s lane budget
        # on one CPU core (the digest drain math that rides the chip in
        # production runs on the host here). 256k keeps the lane inside
        # the budget and measures the same pipeline structure; the flag
        # keeps the record honest. Chip runs keep the full shape.
        num_series = 1 << 18
        scaled = True

    class _Sink(http.server.BaseHTTPRequestHandler):
        bodies = 0
        rbytes = 0

        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            while n > 0:
                n -= len(self.rfile.read(min(n, 1 << 20)))
            _Sink.bodies += 1
            _Sink.rbytes += int(self.headers.get("Content-Length", 0))
            self.send_response(202)
            self.end_headers()

        def log_message(self, *a):  # noqa: D102 - quiet
            pass

    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Sink)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()

    # small initial capacity: the slab digest groups grow by slabs, and
    # the OTHER groups (sets at 16 KB/row of registers!) must not
    # pre-allocate num_series rows
    agg = HistogramAggregates.from_names(["min", "max", "count"])
    groups = ("histograms", "timers", "local_histograms", "local_timers")
    per = num_series // len(groups)
    # one slab per group at the full shape (the slab program runs over
    # slab_rows regardless of fill, so smaller probe runs must not pay
    # full-slab compute)
    store = MetricStore(initial_capacity=1 << 10, chunk=1 << 16,
                        digest_storage="slab",
                        slab_rows=min(1 << 18, max(1 << 13, per)),
                        flush_pipeline_depth=2)
    rng = np.random.default_rng(0)
    rows = np.arange(per, dtype=np.int32)
    wts = np.ones(per, np.float32)

    def reintern():
        for gname in groups:
            gg = getattr(store, gname)
            gg.ensure_capacity(per - 1)
            for i in range(per):
                gg.interner.intern(
                    MetricKey(name=f"svc.{gname}.{i}", type="histogram",
                              joined_tags=f"shard:{i % 13},env:prod"),
                    [f"shard:{i % 13}", "env:prod"])

    def stage():
        for gname in groups:
            gg = getattr(store, gname)
            for _r in range(2):
                gg.sample_many(rows, rng.gamma(2.0, 50.0, per)
                               .astype(np.float32), wts)
            gg._drain_staging()

    def sink():
        return DatadogMetricSink(
            interval=10, flush_max_per_body=1 << 17,
            hostname="bench-host", tags=["team:obs"],
            dd_hostname=f"http://127.0.0.1:{httpd.server_port}",
            api_key="k", compress_level=1)

    def run(now, pipelined):
        store.flush_pipeline_depth = 2 if pipelined else 0
        dd = sink()
        rec = obs.StageRecorder()
        t0 = time.perf_counter()
        with obs.activate(rec):
            if pipelined:
                stream = ChunkStream([dd], now, depth=2, rec=rec)
                with rec.stage("store"):
                    col, _fwd, _ms = store.flush(
                        [], agg, is_local=False, now=now, forward=False,
                        columnar=True, stream=stream)
                t_post = time.monotonic_ns()
                stream.close()
                rec.record_abs("post", t_post, time.monotonic_ns())
            else:
                with rec.stage("store"):
                    col, _fwd, _ms = store.flush(
                        [], agg, is_local=False, now=now, forward=False,
                        columnar=True)
                t_post = time.monotonic_ns()
                dd.flush_columnar(col)
                rec.record_abs("post", t_post, time.monotonic_ns())
        total = time.perf_counter() - t0
        entry = annotate_overlap(rec.finish())
        out = {"total_s": round(total, 3),
               "emissions": len(col),
               "rows_acked": dd.chunk_rows_acked,
               "rows_requeued": dd.chunk_rows_pending()}
        for k in ("lanes", "egress_wall_ns", "overlap_ratio",
                  "sum_vs_max_gap_ns"):
            if k in entry:
                out[k] = entry[k]
        if "lanes" in entry:
            out["lanes_s"] = {k: round(v / 1e9, 3)
                              for k, v in entry["lanes"].items()}
            del out["lanes"]
        # amended batch telemetry (serialize_ns/post_ns) lands in
        # finish() amends only for streamed runs; the sequential run's
        # split rides the sink telemetry instead
        for kind, value in dd.drain_flush_telemetry():
            if kind in ("marshal_s", "chunk_marshal_s"):
                out.setdefault("serialize_deflate_s", 0.0)
                out["serialize_deflate_s"] = round(
                    out["serialize_deflate_s"] + value, 3)
            elif kind in ("post_s", "chunk_post_s"):
                out.setdefault("post_s", 0.0)
                out["post_s"] = round(out["post_s"] + value, 3)
        return out

    # warmup interval: compile the flush programs once (first compile
    # is ~20-40s on TPU and is not per-interval cost)
    reintern()
    stage()
    run(1753900000, pipelined=True)
    reintern()
    stage()
    sequential = run(1753900001, pipelined=False)
    reintern()
    stage()
    pipelined = run(1753900002, pipelined=True)
    httpd.shutdown()

    lanes = pipelined.get("lanes_s", {})
    gate_max = max(lanes.get("compute", 0.0), lanes.get("fetch", 0.0),
                   lanes.get("post", 0.0))
    wall = pipelined.get("egress_wall_ns", 0) / 1e9
    out = {
        "total_s": pipelined["total_s"],
        "sequential_total_s": sequential["total_s"],
        "pipeline_speedup_x": round(
            sequential["total_s"] / pipelined["total_s"], 2)
        if pipelined["total_s"] else None,
        "series": num_series,
        "emissions": pipelined["emissions"],
        "overlap_ratio": pipelined.get("overlap_ratio"),
        "sum_vs_max_gap_s": round(
            pipelined.get("sum_vs_max_gap_ns", 0) / 1e9, 3),
        "lanes_s": lanes,
        "egress_wall_s": round(wall, 3),
        # THE gate (ROADMAP item 2): wall <= 1.2 x max(compute,
        # transfer, POST) — serialize is the lane overlap must hide
        "gate_wall_le_1.2x_max_lane": bool(
            gate_max > 0 and wall <= 1.2 * gate_max),
        "gate_max_lane_s": round(gate_max, 3),
        "conserved": pipelined["rows_acked"] + pipelined["rows_requeued"]
        == pipelined["emissions"],
        "sequential": sequential,
    }
    if scaled:
        out["scaled_down"] = True
        out["scaled_reason"] = ("no TPU on this container; the 1M "
                                "shape needs the chip")
    return out


def bench_forward_1m(num_series: int = 1 << 20):
    """Config #2e: a 1M-series local's full forward path — columnar
    flush, native MetricList encode, gRPC transmit, native decode + bulk
    merge on a real global ImportServer — inside one 10 s interval
    (VERDICT round-2 item #3; reference path flusher.go:424-473 →
    importsrv/server.go:101-132). Local and global share this host's
    single core and chip, so the measured wall is conservative."""
    import grpc  # noqa: F401  (ensures grpc present before server start)

    from veneur_tpu.core.store import MetricStore
    from veneur_tpu.forward import GRPCForwarder, ImportServer
    from veneur_tpu.native import egress
    from veneur_tpu.samplers.intermetric import HistogramAggregates
    from veneur_tpu.samplers.parser import MetricKey

    if not egress.available():
        return {"error": "native egress unavailable"}
    local = MetricStore(initial_capacity=1 << 10, chunk=1 << 16,
                        digest_storage="slab", slab_rows=1 << 19)
    agg = HistogramAggregates.from_names(["min", "max", "count"])
    g = local.histograms
    for i in range(num_series):
        g.interner.intern(
            MetricKey(name=f"svc.lat.{i}", type="histogram",
                      joined_tags=f"shard:{i % 13}"), [f"shard:{i % 13}"])
    g.ensure_capacity(num_series - 1)
    rng = np.random.default_rng(0)
    rows = np.arange(num_series, dtype=np.int32)

    def stage():
        for _ in range(4):  # ~4 live centroids per series on the wire
            g.sample_many(rows,
                          rng.gamma(2.0, 50.0, num_series)
                          .astype(np.float32),
                          np.ones(num_series, np.float32))
        g._drain_staging()

    stage()

    # 2^17 staging chunks on the GLOBAL: ~20% faster bulk merge at 1M
    # rows than 2^16 (fewer device dispatches; swept on-chip)
    gstore = MetricStore(initial_capacity=1 << 10, chunk=1 << 17,
                         digest_storage="slab", slab_rows=1 << 19)
    srv = ImportServer(gstore)
    port = srv.start("127.0.0.1:0")
    # a 64 MB chunk's decode+merge exceeds the 10 s production default
    # when local and global share one core and one chip
    client = GRPCForwarder(f"127.0.0.1:{port}", timeout=180.0)

    import jax

    import veneur_tpu.core.slab as slab_mod

    # Instrument EVERY slab-flush device->host transfer (packed planes
    # AND the per-row stat arrays) through a jax proxy: each device_get
    # first forces completion with a 1-element fetch (compute waits land
    # OUTSIDE the timed transfer), then times the full fetch and sums
    # the bytes — so flush_s - transfer_s is host+device work.
    fetch_s = [0.0]
    fetch_bytes = [0]

    class _JaxProxy:
        def __getattr__(self, name):
            return getattr(jax, name)

        @staticmethod
        def device_get(x):
            leaves = jax.tree.leaves(x)
            for leaf in leaves[:1]:
                if hasattr(leaf, "reshape") and getattr(leaf, "size", 0):
                    np.asarray(jax.device_get(leaf.reshape(-1)[:1]))
            t0 = time.perf_counter()
            out = jax.device_get(x)
            fetch_s[0] += time.perf_counter() - t0
            fetch_bytes[0] += sum(
                getattr(a, "nbytes", 0) for a in jax.tree.leaves(out))
            return out

    orig_jax = slab_mod.jax
    slab_mod.jax = _JaxProxy()
    try:
        # warmup interval: compiles the local flush+pack and the global's
        # scatter programs once (not per-interval cost), then restage
        col, fwd, ms = local.flush([], agg, is_local=True, now=0,
                                   forward=True, columnar=True,
                                   digest_format="packed")
        client.forward(fwd)
        def reintern_and_stage():
            # re-fetch the group: store.flush swaps in a fresh generation
            gg = local.histograms
            gg.ensure_capacity(num_series - 1)
            for i in range(num_series):
                gg.interner.intern(
                    MetricKey(name=f"svc.lat.{i}", type="histogram",
                              joined_tags=f"shard:{i % 13}"),
                    [f"shard:{i % 13}"])
            for _ in range(4):  # ~4 live centroids per series on the wire
                gg.sample_many(rows,
                               rng.gamma(2.0, 50.0, num_series)
                               .astype(np.float32),
                               np.ones(num_series, np.float32))
            gg._drain_staging()
            # force the async ingest scatters to FINISH before the flush
            # timer starts: in production they stream during the interval
            # (the reference's BenchmarkServerFlush likewise times Flush
            # on pre-populated workers); a 1-element fetch is the sync
            float(np.asarray(jax.device_get(
                gg.temps[-1].count[:1]))[0])

        # three timed intervals; report medians (dispatch latency
        # swings single-interval numbers run to run)
        flushes, forwards, nofetches, fetches = [], [], [], []
        fetched_mb = upload_mb = packed_mb = 0.0
        intervals_ok = []
        for it in range(3):
            reintern_and_stage()
            fetch_s[0] = 0.0
            fetch_bytes[0] = 0
            t0 = time.perf_counter()
            col, fwd, ms = local.flush([], agg, is_local=True,
                                       now=1753900000 + it, forward=True,
                                       columnar=True,
                                       digest_format="packed")
            flushes.append(time.perf_counter() - t0)
            fetches.append(fetch_s[0])
            fetched_mb = fetch_bytes[0] / 1e6
            hcol = fwd.histograms_columnar
            if hcol is not None:
                p = hcol[2]  # PackedDigestPlanes
                packed_mb = p.nbytes / 1e6
                # the global's merge upload: decoded centroids re-stage
                # as (row i32, mean f32, weight f32)
                upload_mb = float(p.counts.astype(np.int64).sum()) \
                    * 12 / 1e6
            before = gstore.imported
            t0 = time.perf_counter()
            client.forward(fwd)
            # completion barrier: the global's scatter dispatches are
            # async; force the staged merge to finish
            gs = gstore.histograms
            gs._drain_staging()
            float(np.asarray(jax.device_get(gs.temps[-1].count[:1]))[0])
            forwards.append(time.perf_counter() - t0)
            intervals_ok.append(client.errors == 0 and
                                gstore.imported - before == num_series)

            # the same interval re-staged, flushed WITHOUT any digest
            # output: the flush's pure compute cost.
            reintern_and_stage()
            t0 = time.perf_counter()
            local.flush([], agg, is_local=True, now=2, forward=False,
                        columnar=True)
            nofetches.append(time.perf_counter() - t0)
        med = lambda xs: float(np.median(xs))  # noqa: E731
        t_flush, t_forward, t_nofetch, t_fetch = (
            med(flushes), med(forwards), med(nofetches), med(fetches))
        ok = all(intervals_ok)
        total = t_flush + t_forward
        return {"total_s": round(total, 3),
                "flush_s": round(t_flush, 3),
                "flush_nofetch_s": round(t_nofetch, 3),
                "fetch_transfer_s": round(t_fetch, 3),
                "forward_merge_s": round(t_forward, 3),
                "flush_s_all": [round(x, 2) for x in flushes],
                "forward_s_all": [round(x, 2) for x in forwards],
                "series": num_series, "merged_ok": bool(ok),
                "flush_fetch_mb": round(fetched_mb, 1),
                "packed_wire_mb": round(packed_mb, 1),
                "merge_upload_mb": round(upload_mb, 0),
                "note": "packed digest forward (device-side sort-compact "
                        "+ u16/bf16 quantization, tdigest fields 16/17); "
                        "medians over 3 intervals; single chip + single "
                        "core shared by local and global"}
    finally:
        slab_mod.jax = orig_jax
        client.close()
        srv.stop()


def bench_forward_10m(num_series: int = 10 * (1 << 20), intervals: int = 2,
                      rounds: int = 4, oracle_rows: int = 2048,
                      oracle_extra: int = 252, slab_rows: int = 1 << 18):
    """Config #2f: the flagship 10M-series packed forward as a DRIVER-
    RECORDED number (VERDICT round-4 item #1 — previously README prose).

    A bf16 SlabDigestGroup — the production ``digest_storage: slab``
    store layer — holds 10M interned histogram series on one chip
    (~12.6 GB resident; core/slab.py capacity table). Each interval
    stages ``rounds`` samples/series untimed (ingest streams during the
    interval in production; reference BenchmarkServerFlush also times
    Flush on pre-populated workers), then TIMES the forward flush:
    drain + quantile + device pack (_pack_slab) + packed fetch, with
    want_stats=("count","min","max") — the production local-forward
    aggregate config: a forwarding local emits aggregates and ships the
    digests; fleet percentiles come from the global tier
    (flusher.go:292-473, samplers.go:511-636).

    Every device->host transfer is timed through a jax proxy, exactly
    like config 2e.

    Merge-correctness oracle, sampled (a 10M local + 10M global pair
    cannot co-reside in one 16 GB chip — the global tier at scale is
    configs 2c/4): ``oracle_rows`` random rows get ``oracle_extra``
    extra tracked samples; after the last timed flush their packed
    centroids are dequantized through the production PackedDigestPlanes
    contract and re-imported into a small f32 global SlabDigestGroup,
    whose flushed percentiles must have rank error <= 0.05 against the
    rows' true sample sets (eps envelope 0.02 + u16/bf16 quantization
    at n=64/row). The local flush's count/min/max for those rows must
    match the true samples EXACTLY (they ride exact f32 stat planes).
    """
    import jax
    import jax.numpy as jnp

    import veneur_tpu.core.slab as slab_mod
    from veneur_tpu.core.slab import SlabDigestGroup
    from veneur_tpu.core.store import PackedDigestPlanes
    from veneur_tpu.samplers.parser import MetricKey

    if jax.default_backend() == "cpu" and num_series > (1 << 18):
        # staged sub-probe for no-TPU containers: the 10M shape has
        # budget-skipped since r05 (r07 measured it mid-staging at
        # 3500s on one CPU core; even 512k blows the 900s lane budget
        # here). 256k rows fits the budget and records a trajectory
        # point; the honest flag keeps the record from ever being read
        # as the 10M chip number. Chip runs keep the full shape (this
        # branch never triggers off-CPU).
        out = bench_forward_10m(num_series=1 << 18, intervals=intervals,
                                rounds=rounds, oracle_rows=oracle_rows,
                                oracle_extra=oracle_extra,
                                slab_rows=min(slab_rows, 1 << 16))
        out["scaled_down"] = True
        out["scaled_series"] = 1 << 18
        out["scaled_reason"] = ("no TPU on this container; the 10M "
                                "shape needs the chip")
        return out

    g = SlabDigestGroup(slab_rows=slab_rows, chunk=1 << 19,
                        digest_dtype=jnp.bfloat16)
    g.ensure_capacity(num_series - 1)
    # real interning of 10M keys (host setup, untimed: interning is
    # ingest-side work that amortizes over the streaming interval);
    # the interner is restored after each flush swap so the rows stay
    # valid without paying 10M re-interns per interval
    interner = g.interner
    intern = interner.intern
    t0 = time.perf_counter()
    for i in range(num_series):
        intern(MetricKey(name=f"svc.lat.{i}", type="histogram",
                         joined_tags=""), [])
    intern_s = time.perf_counter() - t0

    rng = np.random.default_rng(7)
    rows = np.arange(num_series, dtype=np.int32)
    ones = np.ones(num_series, np.float32)
    valsets = [rng.gamma(2.0, 50.0, num_series).astype(np.float32)
               for _ in range(rounds)]
    sample_rows = np.sort(rng.choice(num_series, oracle_rows,
                                     replace=False)).astype(np.int64)
    extra_rows = np.repeat(sample_rows, oracle_extra).astype(np.int32)
    extra_vals = rng.gamma(2.0, 50.0, len(extra_rows)).astype(np.float32)
    extra_ones = np.ones(len(extra_rows), np.float32)
    # true per-row sample sets for the oracle: bulk rounds + extras
    true = np.concatenate(
        [np.stack([vs[sample_rows] for vs in valsets], axis=1),
         extra_vals.reshape(oracle_rows, oracle_extra)], axis=1)

    def stage(with_extras: bool):
        for vs in valsets:
            g.sample_many(rows, vs, ones)
        if with_extras:
            g.sample_many(extra_rows, extra_vals, extra_ones)
        g._drain_staging()
        # 1-element fetch as the completion barrier: the flush timer
        # must not absorb async ingest
        float(np.asarray(jax.device_get(g.temps[-1].count[:1]))[0])

    fetch_s = [0.0]
    sync_s = [0.0]
    fetch_bytes = [0]

    class _JaxProxy:
        def __getattr__(self, name):
            return getattr(jax, name)

        @staticmethod
        def device_get(x):
            # the 1-element pre-fetch forces completion so the timed
            # transfer below is pure bytes; its own wait (device compute
            # + one host-device round trip, entangled) is tracked
            # separately as sync_s — at 20 slabs x 3 fetches that is 60
            # round trips
            leaves = jax.tree.leaves(x)
            for leaf in leaves[:1]:
                if hasattr(leaf, "reshape") and getattr(leaf, "size", 0):
                    t_s = time.perf_counter()
                    np.asarray(jax.device_get(leaf.reshape(-1)[:1]))
                    sync_s[0] += time.perf_counter() - t_s
            t0 = time.perf_counter()
            out = jax.device_get(x)
            fetch_s[0] += time.perf_counter() - t0
            fetch_bytes[0] += sum(
                getattr(a, "nbytes", 0) for a in jax.tree.leaves(out))
            return out

    want = ("count", "min", "max")
    orig_jax = slab_mod.jax
    slab_mod.jax = _JaxProxy()
    try:
        # warmup interval: compiles drain/quantile/pack once — WITH the
        # oracle extras, so the wider pack-fetch variant their
        # 64-centroid rows trigger compiles here, not in a timed
        # interval (every timed interval then stages identically)
        stage(with_extras=True)
        _, res = g.flush(list(QS), want_digests="packed", want_stats=want)
        g.interner = interner

        flushes, fetches, syncs, fetched_mbs, packed_mbs = \
            [], [], [], [], []
        for it in range(intervals):
            stage(with_extras=True)
            fetch_s[0] = 0.0
            sync_s[0] = 0.0
            fetch_bytes[0] = 0
            t0 = time.perf_counter()
            _, res = g.flush(list(QS), want_digests="packed",
                             want_stats=want)
            flushes.append(time.perf_counter() - t0)
            fetches.append(fetch_s[0])
            syncs.append(sync_s[0])
            fetched_mbs.append(fetch_bytes[0] / 1e6)
            g.interner = interner
            planes = PackedDigestPlanes(
                res["packed_counts"], res["packed_means"],
                res["packed_weights"],
                np.asarray(res["digest_min"], np.float32),
                np.asarray(res["digest_max"], np.float32))
            packed_mbs.append(planes.nbytes / 1e6)

        # pure device compute of the SAME interval's programs: a staged
        # interval, every slab's drain+quantile+pack dispatched, ONE
        # completion barrier at the end (per-slab sync waits in the
        # timed flush are host-device round trips, not compute — this
        # pass separates them). Runs twice: the first compiles the
        # barrier reduction, the second is the measurement.
        qs_dev = jnp.asarray(list(QS) + [0.5], jnp.float32)

        def device_only_pass():
            t0 = time.perf_counter()
            barriers = []
            for i in range(len(g.digests)):
                (g.digests[i], g.temps[i], mean, weight, dmin, dmax,
                 _pc, cnt, _vs, _vm, _vx, _rc) = slab_mod._flush_slab(
                    g.digests[i], g.temps[i], qs_dev, g.slab_rows,
                    g.compression, True, True)
                cts, pm, pw = slab_mod._pack_slab(
                    mean, weight, dmin, dmax, g.slab_rows, g.k)
                barriers.append(cts.astype(jnp.int32).sum()
                                + pm[0, :1].astype(jnp.int32).sum()
                                + pw[0, :1].astype(jnp.int32).sum()
                                + cnt[:1].astype(jnp.int32).sum())
            float(np.asarray(jax.device_get(sum(barriers))))
            return time.perf_counter() - t0

        stage(with_extras=True)
        device_only_pass()
        stage(with_extras=True)
        device_compute_s = device_only_pass()

        # -- merge-correctness oracle on the sampled rows ----------------
        n_per_row = rounds + oracle_extra
        count_ok = bool(np.all(
            res["count"][sample_rows] == np.float32(n_per_row)))
        tmin = true.min(axis=1)
        tmax = true.max(axis=1)
        stats_ok = bool(np.all(res["min"][sample_rows] == tmin)
                        and np.all(res["max"][sample_rows] == tmax))
        starts, ends, means_f, weights_f = planes.row_slices()
        # production global-store chunk (2^17, cf. configs 2d/2e): all
        # sampled rows' centroids merge in ONE staging drain — a 2^14
        # chunk split rows across drains, paying intermediate
        # compressions no production import batch of this size pays
        gg = SlabDigestGroup(slab_rows=max(4096, oracle_rows),
                             chunk=1 << 17)
        for m, r in enumerate(sample_rows):
            s, e = int(starts[r]), int(ends[r])
            gg.import_centroids(
                MetricKey(name=f"svc.lat.{r}", type="histogram",
                          joined_tags=""), [],
                means_f[s:e].astype(np.float32),
                weights_f[s:e].astype(np.float32),
                float(planes.dmin[r]), float(planes.dmax[r]))
        _, gres = gg.flush(list(QS), want_digests=False)
        gp = gres["percentiles"]
        from veneur_tpu.samplers.scalar import ScalarTDigest

        # two separate questions, two oracles:
        # (1) MERGE correctness — does pack -> dequantize -> import ->
        #     device merge -> quantile reproduce the distribution of
        #     the decoded centroids themselves? Checked against the
        #     scalar golden model's cdf of the SAME centroids, so
        #     ingest-side binning (already baked into the centroids)
        #     cancels out. This gates merged_ok.
        # (2) end-to-end accuracy vs the rows' TRUE samples — reported,
        #     with a loose sanity bound: chunked ingest bins samples
        #     against a range that later chunks can widen, which costs
        #     tail rank error beyond the 0.02 digest envelope on
        #     worst-case rows (the accuracy-sweep harness quantifies
        #     this; see docs/tdigest_accuracy.md).
        max_merge_err = 0.0
        max_rank_err = 0.0
        for m in range(oracle_rows):
            r = sample_rows[m]
            s, e = int(starts[r]), int(ends[r])
            golden = ScalarTDigest(compression=100.0)
            for mu, w in zip(means_f[s:e], weights_f[s:e]):
                golden.add(float(mu), float(w))
            t_sorted = np.sort(true[m])
            for qi, q in enumerate(QS):
                v = float(gp[m, qi])
                max_merge_err = max(max_merge_err,
                                    abs(golden.cdf(v) - q))
                lo = np.searchsorted(t_sorted, v, "left") / n_per_row
                hi = np.searchsorted(t_sorted, v, "right") / n_per_row
                max_rank_err = max(max_rank_err,
                                   max(0.0, lo - q, q - hi))
        # tolerance derivation, for the MAX over rows x qs (~16k checks
        # at n=256/row): import re-binning k-width <= 1 (~0.01 rank)
        # + quantile-interpolation convention deltas vs the golden cdf
        # (~2/n) + u16-quantization ties; measured worst 0.033 at
        # n=256. A real merge-path bug (e.g. the chunk-split regression
        # this oracle caught during round 5) lands at 0.08+.
        merged_ok = bool(count_ok and stats_ok and max_merge_err <= 0.04
                         and max_rank_err <= 0.08)

        med = lambda xs: float(np.median(xs))  # noqa: E731
        t_flush, t_fetch, t_sync = med(flushes), med(fetches), med(syncs)
        fetched_mb, packed_mb = med(fetched_mbs), med(packed_mbs)
        host_python_s = max(0.0, t_flush - t_fetch - t_sync)
        return {"flush_s": round(t_flush, 3),
                "host_python_s": round(host_python_s, 3),
                "device_compute_s": round(device_compute_s, 3),
                "sync_wait_s": round(t_sync, 3),
                "fetch_transfer_s": round(t_fetch, 3),
                "flush_s_all": [round(x, 2) for x in flushes],
                "series": num_series, "digest_dtype": "bfloat16",
                "intern_10m_s": round(intern_s, 1),
                "packed_wire_mb": round(packed_mb, 1),
                "flush_fetch_mb": round(fetched_mb, 1),
                "merged_ok": merged_ok,
                "oracle": {"rows": oracle_rows,
                           "samples_per_row": n_per_row,
                           "max_merge_rank_err": round(max_merge_err, 4),
                           "max_rank_err_vs_true": round(max_rank_err, 4),
                           "count_exact": count_ok,
                           "min_max_exact": stats_ok},
                "note": "packed digest forward at 10M bf16 rows through "
                        "the production slab store layer; "
                        "want_stats=(count,min,max) is the forwarding-"
                        "local aggregate config; medians over "
                        "%d intervals" % intervals}
    finally:
        slab_mod.jax = orig_jax


def bench_hll(num_series: int = 1 << 18, updates: int = 1 << 17,
              precision: int = 14):
    """Config #3: register scatter-max + batched estimate.

    At the reference's precision 14 a dense [S, 2^14] int8 plane costs
    16 KB/series — 1M series is 16 GB, past one v5e-1's HBM, so the
    full-precision run benches 2^18 series (4 GB) and the 1M-series run
    uses precision 12 (4 GB; standard error 1.04/sqrt(2^12) ≈ 1.6% vs
    0.8%). 1M series AT precision 14 takes two chips or the mesh store
    (the series axis shards; core/mesh_store.py)."""
    import jax
    import jax.numpy as jnp
    from veneur_tpu.ops import hll as hll_ops

    m = hll_ops.num_registers(precision)

    @partial(jax.jit, donate_argnums=(0,))
    def step(regs, rows, hi, lo):
        idx, rho = hll_ops.idx_rho(hi, lo, precision)
        regs = regs.at[rows, idx].max(rho.astype(regs.dtype), mode="drop")
        est = hll_ops.estimate(regs.astype(jnp.int32), precision)
        return regs, jnp.sum(est)

    rng = np.random.default_rng(1)
    rows = jnp.asarray(rng.integers(0, num_series, updates).astype(np.int32))
    hashes = rng.integers(0, 1 << 64, updates, dtype=np.uint64)
    hi = jnp.asarray((hashes >> np.uint64(32)).astype(np.uint32))
    lo = jnp.asarray((hashes & np.uint64(0xFFFFFFFF)).astype(np.uint32))
    regs = jnp.zeros((num_series, m), jnp.int8)
    regs, chk = step(regs, rows, hi, lo)
    float(chk)
    times = []
    for _ in range(10):
        t0 = time.perf_counter()
        regs, chk = step(regs, rows, hi, lo)
        float(chk)
        times.append(time.perf_counter() - t0)
    return {"p50_ms": round(float(np.median(times)) * 1e3, 3),
            "series": num_series, "registers": m}


def bench_sets_1m_p14():
    """Config #3c: BASELINE #3 at spec — 1M Set series x 2^14 registers.

    16 GB of int8 registers exceeds one v5e-1's HBM, so the stated scale
    path is the mesh-sharded store (core/mesh_store.py MeshSetGroup: the
    series axis shards, 2 chips hold the plane). Two halves reported:

    - ``mesh_1m``: the FULL 1M x p14 plane on the 8-device virtual CPU
      mesh (subprocess), timing one update+estimate step and asserting
      register-exact accuracy vs the scalar golden model for sampled
      series. Same program runs over ICI on real chips.
    - ``chip_half_512k``: the per-chip half-shard (512k x p14, 8 GB) on
      the real TPU — the single-chip perf number of the 2-chip plan.
    """
    out = {"plan": "1M x p14 = 16 GB registers = 2 v5e chips "
                   "(series-sharded mesh)"}
    out["chip_half_512k"] = bench_hll(1 << 19, 1 << 17, 14)
    code = """
import jax
jax.config.update('jax_platforms', 'cpu')
import json, time
import numpy as np
from veneur_tpu.core.mesh_store import MeshSetGroup
from veneur_tpu.parallel.mesh import fleet_mesh
from veneur_tpu.samplers.scalar import ScalarHLL

# Correctness of the SHARDED programs at a size one CPU core emulating 8
# devices can execute in full (scatter + estimate over every shard); the
# identical programs scale to 1M series on 2+ real chips, where each
# chip runs exactly the chip_half_512k workload measured on real HBM.
P = 14
mesh = fleet_mesh(hosts=2)
rng = np.random.default_rng(0)
S = 1 << 16
g = MeshSetGroup(mesh, capacity=S, chunk=1 << 16, precision=P)
golden = {0: 5000, 1: 137, 2: 1}
rows = rng.integers(3, S, 1 << 18).astype(np.int32)
hashes = rng.integers(0, 1 << 64, 1 << 18, dtype=np.uint64)
gr, gh = [rows], [hashes]
for row, n in golden.items():
    gr.append(np.full(n, row, np.int32))
    gh.append(rng.integers(0, 1 << 64, n, dtype=np.uint64))
g.sample_many(np.concatenate(gr), np.concatenate(gh))
g._drain_staging()
float(np.asarray(g._estimates()[:1])[0])  # compile + settle
t0 = time.perf_counter()
g.sample_many(rows, hashes)
g._drain_staging()
est = np.asarray(g._estimates())
dt = time.perf_counter() - t0
regs = np.asarray(g.registers[:3], np.uint8)
ok = True
for j, (row, n) in enumerate(golden.items()):
    m = ScalarHLL(P)
    for h in np.concatenate([hashes[rows == row]] * 2 + [gh[j + 1]]):
        m.insert_hash(int(h))
    ok = ok and np.array_equal(regs[row],
                               np.frombuffer(bytes(m.registers), np.uint8))
    ok = ok and abs(est[row] - m.estimate()) < max(2.0, 0.05 * n)
print(json.dumps({
    "series": S, "registers": 1 << P, "devices": 8,
    "update_estimate_ms": round(dt * 1e3, 3),
    "registers_match_scalar_model": bool(ok),
    "note": "virtual CPU mesh, sharded-program correctness; per-chip "
            "perf is the real-TPU chip_half_512k entry"}))
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    try:
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, timeout=560, text=True,
                           cwd=_HERE)
        out["mesh_sharded_correctness"] = json.loads(
            r.stdout.strip().splitlines()[-1])
    except Exception as e:  # pragma: no cover
        print(f"mesh set bench failed: {e}", file=sys.stderr)
        out["mesh_sharded_correctness"] = {"error": str(e)[:160]}
    return out


def _splitmix64(x: np.ndarray) -> np.ndarray:
    """Vectorized splitmix64: spreads synthetic key ids into the
    well-distributed 64-bit hashes the sketch expects (members normally
    arrive pre-hashed by fnv/xx)."""
    x = (x + np.uint64(0x9E3779B97F4A7C15)).astype(np.uint64)
    x ^= x >> np.uint64(30)
    x = (x * np.uint64(0xBF58476D1CE4E5B9)) & np.uint64(0xFFFFFFFFFFFFFFFF)
    x ^= x >> np.uint64(27)
    x = (x * np.uint64(0x94D049BB133111EB)) & np.uint64(0xFFFFFFFFFFFFFFFF)
    return x ^ (x >> np.uint64(31))


def bench_heavy_hitters_100m(n_cold: int = 100_000_000,
                             width: int = 1 << 17):
    """Config #5b: BASELINE #5 at spec — 100M distinct keys through the
    count-min/top-k sketch, with ground-truth accuracy bounds.

    Stream construction gives EXACT ground truth: 100M distinct cold
    keys appear once each; 256 hot keys get zipf-shaped extra counts on
    top. Width follows the epsilon = e/width bound: at width 2^17 a
    point estimate overcounts by <= eps*N ~= 2.2k of the ~105M-count
    stream with probability 1 - e^-depth (~98.2%); the hot keys'
    thousands-to-millions counts clear that bound, which is what makes
    a 100M-key top-k recoverable from a 2 MB table. (Round-2 verdict:
    the old 2^16-wide bench at 262k updates proved nothing at this
    scale.)"""
    import jax
    import jax.numpy as jnp

    from veneur_tpu.ops import countmin as cm

    depth, k = 4, 128
    hot_n = 256
    rng = np.random.default_rng(5)
    # hot key j gets ~2e6/(j+1)^0.9 extra occurrences
    hot_counts = (2e6 / np.power(np.arange(1, hot_n + 1), 0.9)).astype(
        np.int64)
    hot_keys = _splitmix64(np.arange(1 << 40, (1 << 40) + hot_n,
                                     dtype=np.uint64))
    warm = 1 << 21  # the compile-warmup chunk also enters the stream
    total = int(n_cold + hot_counts.sum() + warm)

    sk = cm.init(1, depth=depth, width=width, k=k)
    update = jax.jit(cm.update, donate_argnums=(0,))
    chunk = 1 << 21
    zero_rows = jnp.zeros(chunk, jnp.int32)
    zero_sids = jnp.zeros(chunk, jnp.uint32)
    ones = jnp.ones(chunk, jnp.float32)

    def feed(keys: np.ndarray):
        hi = jnp.asarray((keys >> np.uint64(32)).astype(np.uint32))
        lo = jnp.asarray((keys & np.uint64(0xFFFFFFFF)).astype(np.uint32))
        n = len(keys)
        return update(sk, zero_rows[:n], zero_sids[:n], hi, lo, ones[:n])

    # warmup/compile on one chunk
    sk = feed(_splitmix64(np.arange(chunk, dtype=np.uint64)
                          + np.uint64(1 << 50)))
    t0 = time.perf_counter()
    pos = 0  # the warmup chunk used a disjoint id range (offset 2^50)
    timed_updates = 0
    while pos < n_cold:
        n = min(chunk, n_cold - pos)
        sk = feed(_splitmix64(np.arange(pos, pos + n, dtype=np.uint64)))
        pos += n
        timed_updates += n
    # hot keys: repeat each to its count, streamed in chunks
    hot_stream = np.repeat(hot_keys, hot_counts)
    rng.shuffle(hot_stream)
    for i in range(0, len(hot_stream), chunk):
        sk = feed(hot_stream[i:i + chunk])
    timed_updates += len(hot_stream)
    hi, lo, ct = jax.device_get((sk.topk_hi[0], sk.topk_lo[0],
                                 sk.topk_counts[0]))
    dt = time.perf_counter() - t0

    got = {(int(h) << 32) | int(l): float(c)
           for h, l, c in zip(hi, lo, ct) if c > 0}
    true_top = {int(hk): int(c) for hk, c in zip(hot_keys, hot_counts)}
    top64 = sorted(true_top, key=true_top.get, reverse=True)[:64]
    got64 = sorted(got, key=got.get, reverse=True)[:64]
    recall = len(set(top64) & set(got)) / 64
    precision = len(set(got64) & set(true_top)) / 64
    eps_bound = np.e / width * total
    errs = [got[key] - true_top[key] for key in top64 if key in got]
    max_err = max(errs) if errs else float("nan")
    return {"updates": total, "distinct_keys": n_cold + hot_n + warm,
            "updates_per_s": int(timed_updates / dt),
            "seconds": round(dt, 1),
            "depth": depth, "width": width, "topk": k,
            "table_mb": round(depth * width * 4 / 1e6, 1),
            "recall_at_64": round(recall, 3),
            "precision_at_64": round(precision, 3),
            "epsilon_bound_counts": int(eps_bound),
            "max_overcount_top64": int(max_err),
            "overcount_within_bound": bool(max_err <= eps_bound)}


def bench_mesh_subprocess(num_series: int = 1 << 13):
    """Config #4: the mesh-sharded global flush on an 8-device virtual
    CPU mesh, in a subprocess so the TPU-initialized parent is untouched."""
    code = f"""
import jax
jax.config.update('jax_platforms', 'cpu')  # before any backend use
import json, time
import numpy as np
import jax.numpy as jnp
from veneur_tpu.core.store import MetricStore
from veneur_tpu.parallel.mesh import fleet_mesh
from veneur_tpu.samplers.intermetric import HistogramAggregates
from veneur_tpu.samplers.parser import MetricKey
mesh = fleet_mesh(hosts=2)
store = MetricStore(initial_capacity={num_series}, chunk=1 << 16, mesh=mesh)
rng = np.random.default_rng(0)
g = store.histograms
rows = np.arange({num_series}, dtype=np.int32)
agg = HistogramAggregates.from_names(["count"])
vals = rng.gamma(2.0, 30.0, (4, {num_series})).astype(np.float32)
wts = np.ones({num_series}, np.float32)
def fill():
    for i in range({num_series}):
        g.interner.intern(MetricKey(name=f"h{{i}}", type="histogram"), [])
    for r in range(4):
        g.sample_many(rows, vals[r], wts)
    g._drain_staging()
fill()
g.flush([0.5, 0.99])  # warmup: XLA CPU compile of the sharded programs
fill()
t0 = time.perf_counter()
interner, out = g.flush([0.5, 0.99])
dt = time.perf_counter() - t0
print(json.dumps({{"p50_ms": round(dt * 1e3, 3),
                   "series": {num_series}, "devices": 8,
                   "note": "virtual CPU mesh; same program runs over ICI"}}))
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env.pop("PYTHONSTARTUP", None)
    try:
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, timeout=420, text=True,
                             cwd=_HERE)
        return json.loads(out.stdout.strip().splitlines()[-1])
    except Exception as e:  # pragma: no cover
        print(f"mesh bench failed: {e}", file=sys.stderr)
        return {"error": str(e)[:120]}


def bench_fleet_mesh(num_series: int = 1 << 13):
    """Config #11: fleet mode — the mesh-sharded TIERED store's global
    merge (shard-routed import drains + sharded flush) vs shard count
    on the 8-device virtual CPU mesh, in a subprocess so the
    TPU-initialized parent is untouched. The wall-clock-vs-shards curve
    is the program-structure signal (collective + partitioning
    overhead); absolute speedup needs real chips — all 8 virtual
    devices share this host's cores, so ratios ~1.0 here are expected
    and honest."""
    code = f"""
import jax
jax.config.update('jax_platforms', 'cpu')  # before any backend use
import json, time
import numpy as np
from veneur_tpu.fleet import ShardRouter
from veneur_tpu.fleet.mesh_tiered import MeshTieredDigestGroup
from veneur_tpu.parallel.mesh import fleet_mesh
from veneur_tpu.samplers.parser import MetricKey
N = {num_series}
rng = np.random.default_rng(0)
vals = rng.gamma(2.0, 30.0, (4, N)).astype(np.float32)
imp_means = np.sort(rng.gamma(2.0, 30.0, (N, 8)), axis=1)
out = {{}}
for shards in (1, 2, 4, 8):
    mesh = fleet_mesh(jax.devices()[:shards], hosts=1)
    router = ShardRouter(shards)
    def build():
        g = MeshTieredDigestGroup(mesh, router, slab_rows=1 << 14,
                                  chunk=1 << 14, promote_samples=1 << 30,
                                  dense_capacity=256)
        rows = np.asarray([g._row(MetricKey(name=f'f{{i}}',
                                            type='histogram'), [])
                           for i in range(N)], np.int64)
        return g, rows
    def drive(g, rows):
        wts = np.ones(N, np.float32)
        for r in range(4):
            g.sample_many(rows, vals[r], wts)
        # shard-routed import: one 8-centroid run per series
        g.import_centroids_bulk(
            np.repeat(rows, 8), imp_means.reshape(-1),
            np.ones(N * 8, np.float32), rows,
            imp_means[:, 0], imp_means[:, -1])
        g._drain_staging()
        occ = g.placement.occupancy()  # before flush resets placement
        g.flush([0.5, 0.99])
        return occ
    g, rows = build()
    drive(g, rows)          # warmup: compile the sharded programs
    times = []
    occ = None
    for _ in range(3):
        g, rows = build()
        t0 = time.perf_counter()
        occ = drive(g, rows)
        times.append(time.perf_counter() - t0)
    out[str(shards)] = {{
        "merge_flush_ms": round(sorted(times)[1] * 1e3, 1),
        "balance_ratio": occ["balance_ratio"]}}
base = out["1"]["merge_flush_ms"]
for k, v in out.items():
    v["vs_1_shard"] = round(base / v["merge_flush_ms"], 2)
print(json.dumps({{"series": N, "per_shards": out,
                   "note": "virtual CPU mesh shares host cores; the "
                           "curve is structure, not speedup"}}))
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env.pop("PYTHONSTARTUP", None)
    try:
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, timeout=600, text=True,
                             cwd=_HERE)
        return json.loads(out.stdout.strip().splitlines()[-1])
    except Exception as e:  # pragma: no cover
        print(f"fleet bench failed: {e}", file=sys.stderr)
        return {"error": str(e)[:160]}


def bench_heavy_hitters():
    """Config #5: count-min + top-k at high key cardinality."""
    import jax
    import jax.numpy as jnp

    try:
        from veneur_tpu.ops import countmin as cm
    except ImportError:
        return {"error": "countmin sampler not present"}
    rng = np.random.default_rng(3)
    n = 1 << 18
    # zipf-ish key stream over a large id space
    keys = (rng.zipf(1.3, n) % (1 << 26)).astype(np.uint64)
    hi = jnp.asarray((keys >> np.uint64(32)).astype(np.uint32))
    lo = jnp.asarray((keys & np.uint64(0xFFFFFFFF)).astype(np.uint32))
    counts = jnp.ones(n, jnp.float32)
    rows = jnp.zeros(n, jnp.int32)  # one series over a 2^26-key space
    sk = cm.init(1, depth=4, width=1 << 16, k=128)

    @partial(jax.jit, donate_argnums=(0,))
    def step(s, rows, hi, lo, c):
        s = cm.update(s, rows, rows.astype(jnp.uint32), hi, lo, c)
        return s, jnp.sum(s.topk_counts)

    sk, chk = step(sk, rows, hi, lo, counts)
    float(chk)
    times = []
    for _ in range(10):
        t0 = time.perf_counter()
        sk, chk = step(sk, rows, hi, lo, counts)
        float(chk)
        times.append(time.perf_counter() - t0)
    return {"p50_ms": round(float(np.median(times)) * 1e3, 3),
            "updates": n, "depth": 4, "width": 1 << 16, "topk": 128}


def run_isolated(fn_name: str, timeout: float = 560.0):
    """Run one bench function in a fresh subprocess (own TPU runtime):
    the multi-GB configs must not inherit the parent's HBM fragmentation
    (compile caches persist across processes, so the cost is startup)."""
    code = (f"import bench, json; "
            f"print('\\n' + json.dumps(bench.{fn_name}()))")
    try:
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, timeout=timeout,
                           text=True, cwd=_HERE)
        return json.loads(r.stdout.strip().splitlines()[-1])
    except subprocess.TimeoutExpired:
        # the lane-budget contract: a lane that blows its budget is
        # recorded as skipped-with-reason, never an rc=124 for the run
        print(f"{fn_name} exceeded its {timeout:.0f}s budget; skipped",
              file=sys.stderr)
        return {"skipped": f"lane budget exceeded ({timeout:.0f}s)"}
    except Exception as e:  # pragma: no cover
        print(f"{fn_name} subprocess failed: {e}", file=sys.stderr)
        return {"error": str(e)[:160]}


def bench_reshard(num_series: int = 1 << 16, centroids: int = 8,
                  counters: int = 8192):
    """Config #12: elastic-resharding handoff (fleet/handoff.py) —
    wall-clock of extract → packed-wire encode → decode → import-
    semantics merge at two moved-key fractions (grow 2→3 ≈ 1/3 of the
    keyspace; drain 1→2 = all of it), with the exact-conservation
    check built into the lane (counter totals + digest centroid mass
    across sender + receivers must equal the ingested totals). The
    stream here is the in-process wire round trip: socket time is the
    ordinary POST the 9_proxy lane already prices, while the extract/
    quantize/merge compute measured here is what handoff adds. Scales
    with the chip via num_series; the default is probe scale for this
    container's CPU."""
    from veneur_tpu.core.store import MetricStore
    from veneur_tpu.fleet import RingTransition
    from veneur_tpu.fleet.handoff import decode_handoff, encode_handoff
    from veneur_tpu.samplers.intermetric import HistogramAggregates
    from veneur_tpu.samplers.parser import MetricKey

    agg = HistogramAggregates.from_names(["count"])
    rng = np.random.default_rng(0)
    means = np.sort(rng.gamma(2.0, 40.0, (num_series, centroids)), axis=1)
    w_run = np.ones(centroids, np.float64)

    def fill(store, owns):
        """Populate only the series the OLD ring assigns to this
        instance (the proxy routed them here), so the moved fraction
        is the realistic ring-movement share, not a whole-keyspace
        sweep."""
        n_c = n_t = 0
        for i in range(counters):
            if not owns(f"c{i}", "counter"):
                continue
            store.import_counter(
                MetricKey(name=f"c{i}", type="counter",
                          joined_tags=""), [], 3)
            n_c += 1
        entries = []
        for i in range(num_series):
            if not owns(f"t{i}", "timer"):
                continue
            entries.append(
                (MetricKey(name=f"t{i}", type="timer",
                           joined_tags=""), [], means[i], w_run,
                 float(means[i, 0]), float(means[i, -1])))
            n_t += 1
        store.import_digests_bulk(entries)
        return n_c + n_t, 3 * n_c, float(n_t * centroids)

    def totals(store):
        _final, fwd, _ms = store.flush([0.5], agg, is_local=True,
                                       now=0, forward=True,
                                       columnar=False)
        c = sum(v for _n, _t, v in fwd.counters)
        w = sum(float(np.sum(wts)) for _n, _t, _m, wts, _mn, _mx
                in fwd.histograms + fwd.timers)
        return c, w

    def phase(old_members, new_members, self_addr):
        store = MetricStore(initial_capacity=1 << 12, chunk=16384)
        tr = RingTransition(old_members, new_members)
        resident, total_c, total_w = fill(
            store, lambda name, mtype:
            tr.old_owner(name, mtype, "") == self_addr)

        def route(name, mtype, joined):
            dest = tr.new_owner(name, mtype, joined)
            return None if dest == self_addr else dest

        def route_many(names, mtype, joineds):
            return [None if d == self_addr else d
                    for d in tr.new_owners(names, mtype, joineds)]

        t0 = time.perf_counter()
        moved, n_moved = store.handoff_extract(route,
                                               route_many=route_many)
        t_extract = time.perf_counter() - t0
        t0 = time.perf_counter()
        blobs = {d: encode_handoff(g, {"id": d, "sender": self_addr,
                                       "epoch": 1}, 0.0)
                 for d, g in moved.items()}
        t_encode = time.perf_counter() - t0
        wire_mb = sum(len(b) for b in blobs.values()) / 2 ** 20
        t0 = time.perf_counter()
        recv_c = recv_w = 0.0
        for _dest, blob in sorted(blobs.items()):
            groups, _meta = decode_handoff(blob)
            recv = MetricStore(initial_capacity=1 << 12, chunk=16384)
            recv.restore_state(groups)
            c, w = totals(recv)
            recv_c += c
            recv_w += w
        t_merge = time.perf_counter() - t0
        live_c, live_w = totals(store)
        conserved = (live_c + recv_c == total_c
                     and abs(live_w + recv_w - total_w)
                     <= 1e-6 * total_w)
        return {
            "resident_series": resident,
            "moved_fraction": round(n_moved / max(1, resident), 3),
            "extract_s": round(t_extract, 2),
            "wire_encode_s": round(t_encode, 2),
            "merge_s": round(t_merge, 2),
            "total_s": round(t_extract + t_encode + t_merge, 2),
            "wire_mb": round(wire_mb, 1),
            "conserved": conserved,
        }

    out = {
        "series": num_series + counters,
        "centroids_per_series": centroids,
        # grow 2→3: every incumbent loses ~1/3 of the ring to the
        # newcomer — the weekly scale-out shape
        "grow_2_to_3": phase(["g-a", "g-b"], ["g-a", "g-b", "g-c"],
                             "g-a"),
        # drain 1→2: a departing instance hands off its whole keyspace
        # — the scale-in / decommission shape
        "drain_all": phase(["g-a"], ["g-b", "g-c"], "g-a"),
    }
    out["conserved"] = (out["grow_2_to_3"]["conserved"]
                        and out["drain_all"]["conserved"])
    return out


_E2E_CHILD = r"""
import json, sys
from veneur_tpu.config import Config
from veneur_tpu.server import Server

# driven cadence: the parent commands each flush over stdin (one line
# = one flush, acked on stdout) instead of a free-running ticker — on
# a contended bench core an overrunning ticker measures scheduler lag,
# not the pipeline, and strands the last volleys when the drive stops
cfg = Config(statsd_listen_addresses=["udp://127.0.0.1:0"],
             interval="86400s", http_address="127.0.0.1:0",
             forward_address="http://127.0.0.1:%d",
             aggregates=["count"], store_initial_capacity=2048,
             store_chunk=4096)
srv = Server(cfg)
srv.start()
print(json.dumps({"udp": srv.statsd_addrs[0][1],
                  "ops": srv.ops_server.port}), flush=True)
for _line in sys.stdin:
    srv.flush()
    print("{}", flush=True)
srv.shutdown()
"""


def bench_e2e_trace(intervals: int = 8, counters: int = 512,
                    timers: int = 512):
    """Config #13: the fleet trace plane end to end (PR 13) — a REAL
    second process runs a local instance (UDP ingest lanes, commanded
    flush cadence, HTTP forward), this process runs the global; the
    drive measures, per interval, the ingest→sink-2xx freshness
    (``veneur.fleet.e2e_age_ns``: the lane chunks' wall stamp rides
    the X-Veneur-Trace header through the forward and is measured on
    the global after its sink joins) and the stitched
    ``GET /debug/trace`` hop view (local.flush → forward →
    global.import → global.flush), with the union-coverage and exact
    counter conservation asserted in the record."""
    import json as _json
    import socket as _socket

    from veneur_tpu.config import Config
    from veneur_tpu.discovery import RingWatcher, StaticDiscoverer
    from veneur_tpu.obs.fleet import stitch_trace
    from veneur_tpu.server import Server
    from veneur_tpu.sinks import ChannelMetricSink

    gcfg = Config(statsd_listen_addresses=[], interval="86400s",
                  http_address="127.0.0.1:0", percentiles=[0.5, 0.99],
                  aggregates=["count"], store_initial_capacity=2048,
                  store_chunk=4096)
    gsink = ChannelMetricSink()
    g = Server(gcfg, metric_sinks=[gsink])
    g.start()
    child = subprocess.Popen(
        [sys.executable, "-c", _E2E_CHILD % g.ops_server.port],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        cwd=_HERE)
    e2e_ages = []
    traces = []
    sent_counters = 0
    flushed_counter_sum = 0.0
    stitched = {}
    warmup = 3  # first child/global flushes pay jit compiles
    try:
        ports = _json.loads(child.stdout.readline())
        peer = f"127.0.0.1:{ports['ops']}"
        g.fleet_aggregator.watcher = RingWatcher(
            StaticDiscoverer([peer]), "bench")
        sock = _socket.socket(_socket.AF_INET, _socket.SOCK_DGRAM)

        def wait_for(pred, timeout=60.0):
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                v = pred()
                if v:
                    return v
                time.sleep(0.001)
            raise RuntimeError("e2e drive timed out")

        def child_flush():
            """One commanded local flush (acked after the flush path —
            though not necessarily the off-path forward — completes)."""
            child.stdin.write("f\n")
            child.stdin.flush()
            child.stdout.readline()

        def drain_global():
            """One global flush; returns (entry, counter sum)."""
            g.flush()
            batch = gsink.get_flush()
            entry = g.obs_timeline.entries()[-1]
            return entry, sum(m.value for m in batch
                              if m.name.startswith("e2e.c"))

        for it in range(warmup + intervals):
            for i in range(counters):
                sock.sendto(f"e2e.c{i}:1|c|#veneurglobalonly".encode(),
                            ("127.0.0.1", ports["udp"]))
            for i in range(timers):
                sock.sendto(f"e2e.t{i}:{(i * 7) % 100}|ms|"
                            f"#veneurglobalonly".encode(),
                            ("127.0.0.1", ports["udp"]))
            sent_counters += counters
            # let the lanes drain the volley off the socket and seal
            # (idle-residue seal rides the lane recv timeout)
            time.sleep(0.25)
            child_flush()
            # a hop only appears for a data-carrying forward (an empty
            # tick forwards nothing), and its context names the trace
            hop = wait_for(lambda: (g.obs_hops.peek() or [None])[0])
            gentry, flushed = drain_global()
            flushed_counter_sum += flushed
            if it < warmup:
                continue
            if "e2e_age_ns" in gentry:
                e2e_ages.append(gentry["e2e_age_ns"])
            tid = hop.get("trace_id")
            if tid and tid in gentry.get("import_traces", ()):
                traces.append(tid)
        # settle: residue that straddled a commanded flush (lane seal
        # raced the volley) rides the next one; close the ledger
        deadline = time.monotonic() + 20.0
        while (int(flushed_counter_sum) < sent_counters
               and time.monotonic() < deadline):
            time.sleep(0.3)
            child_flush()
            time.sleep(0.2)
            _entry, flushed = drain_global()
            flushed_counter_sum += flushed
        # stitch the last fully-observed trace WHILE the local still
        # serves its timeline
        if traces:
            g.fleet_aggregator.refresh(force=True)
            stitched = stitch_trace(traces[-1],
                                    g.fleet_aggregator._sources())
        sock.close()
    finally:
        try:
            child.stdin.close()  # EOF ends the command loop cleanly
        except Exception:
            pass
        try:
            child.wait(timeout=10)
        except subprocess.TimeoutExpired:
            child.kill()
    g.shutdown()
    ages = np.asarray(e2e_ages, np.float64)
    hop_share = {}
    if stitched.get("hops") and stitched.get("e2e_wall_ns"):
        for h in stitched["hops"]:
            hop_share[h["hop"]] = round(
                hop_share.get(h["hop"], 0.0)
                + h["duration_ns"] / stitched["e2e_wall_ns"], 4)
    return {
        "intervals": len(e2e_ages),
        "traces_stitched": len(traces),
        "e2e_age_ms_p50": round(float(np.percentile(ages, 50)) / 1e6, 3)
        if len(ages) else None,
        "e2e_age_ms_p99": round(float(np.percentile(ages, 99)) / 1e6, 3)
        if len(ages) else None,
        "hop_share_of_e2e": hop_share,
        "hop_coverage_ratio": stitched.get("hop_coverage_ratio"),
        "coverage_ok": (stitched.get("hop_coverage_ratio") or 0) >= 0.9,
        "stitched_hops": sorted({h["hop"]
                                 for h in stitched.get("hops", ())}),
        "sent_counters": sent_counters,
        "flushed_counters": int(flushed_counter_sum),
        "conserved": int(flushed_counter_sum) == sent_counters,
    }


def bench_soak(intervals: int = 200, kills: int = 3):
    """Config #14: the production soak plane end to end (PR 16,
    ``veneur_tpu/soak/``) — a REAL multi-process fleet (local UDP →
    proxy → global, each its own OS process) driven through a seeded
    200-interval chaos schedule: every role SIGKILLed at least once
    (checkpoint-epoch folding keeps the ledger exact across the
    restarts), sink black-hole/5xx/slow windows, injected
    disk-full (ENOSPC) and flush-deadline-pressure faults. The record
    is the full machine-checked gate vector — exact end-to-end
    conservation, post-warmup RSS slope, post-chaos compile drift,
    timeline coverage, e2e freshness p99, recovery, bounded requeue —
    plus the drive rate. ``all_gates_ok`` is the acceptance bit."""
    import shutil
    import tempfile

    from veneur_tpu.soak import (GateThresholds, ProcessFleet,
                                 SoakScenario, run_soak)

    thr = GateThresholds(warmup_intervals=20,
                         rss_slope_pct_per_100=5.0,
                         recovery_intervals=5)
    sc = SoakScenario.generate(seed=1608, intervals=intervals,
                               kills=kills, thresholds=thr)
    root = tempfile.mkdtemp(prefix="veneur-soak-")
    t0 = time.perf_counter()
    try:
        report = run_soak(sc, ProcessFleet(sc, root),
                          enforce_gates=False)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    took = time.perf_counter() - t0
    vec = report.vector()
    led = report.ledger
    g = vec["gates"]
    return {
        "intervals": intervals, "kills": len(sc.kills), "seed": sc.seed,
        "sink_windows": [(w.mode, w.start, w.end)
                         for w in sc.sink_windows],
        "elapsed_s": round(took, 1),
        "intervals_per_s": round(intervals / took, 2),
        "all_gates_ok": vec["all_ok"],
        "gates_ok": {k: v["ok"] for k, v in g.items()},
        "rss_slope_pct_per_100": g["rss_slope"]["value"],
        "compile_drift": g["compile_drift"]["value"],
        "coverage_median": g["coverage"]["value"],
        "e2e_age_p99_s": g["e2e_age_p99"]["value"],
        "sent_global": led.sent_global,
        "emitted_global": led.emitted_global,
        "shed": led.shed,
        "dd_offered": led.dd_offered, "dd_acked": led.dd_acked,
        "dd_dropped": led.dd_dropped,
        "dd_crash_lost": led.dd_crash_lost,
        "restarts": dict(led.restarts),
        "ckpt_write_errors": led.ckpt_write_errors,
        "spool_errors": led.spool_errors,
        # the LedgerAudit runtime twin (lint/ledger_audit.py) rides
        # every soak: per-interval conservation timeline, asserted at
        # terminal settlement — the smoke proof the drop-flow static
        # pass's invariant holds with live traffic and real SIGKILLs
        "ledger_audit_snapshots": len(report.ledger_timeline),
        "ledger_audit_settled_ok": all(
            s["ok"] for s in report.ledger_timeline if s["settled"]),
        # and the BufferCensus twin (lint/buffer_census.py) beside it:
        # the donation-safety pass's runtime proof that no retired
        # device plane outlives its generation in the driver process
        "buffer_census_snapshots": len(report.buffer_timeline),
        "buffer_census_settled_ok": all(
            s["ok"] is not False for s in report.buffer_timeline
            if s["settled"]),
        "device_buffer_growth_bytes": led.device_buffer_growth_bytes,
    }


def bench_ha_takeover(intervals: int = 30):
    """Config #15: the global-aggregator HA takeover end to end (PR 17,
    ``veneur_tpu/fleet/standby.py`` + ``veneur_tpu/discovery/lease.py``)
    — a REAL multi-process fleet where the active global replicates
    each retired flush snapshot to a warm standby and holds a file
    lease. Mid-run the active is SIGKILLed and NEVER restarted: the
    standby's elector wins the lapsed lease, promotes the merged shadow
    (non-counter groups), the proxy re-routes through the
    lease-follower discoverer, and the drive keeps going. The record is
    the takeover wall clock (kill → leader, kill → first standby-served
    flush), the exact bounded-loss accounting (the un-flushed counter
    tail of the dead active, ``accounted_lost <= loss_bound`` = one
    interval's send), and the full gate vector including the
    ``takeover`` gate. ``all_gates_ok`` is the acceptance bit."""
    import shutil
    import tempfile

    from veneur_tpu.soak import (KIND_KILL_FOREVER, GateThresholds,
                                 ProcessFleet, SoakScenario, run_soak)

    thr = GateThresholds(warmup_intervals=5, rss_slope_pct_per_100=50.0,
                         recovery_intervals=3)
    sc = SoakScenario.generate(seed=1709, intervals=intervals,
                               thresholds=thr, kind=KIND_KILL_FOREVER)
    root = tempfile.mkdtemp(prefix="veneur-ha-")
    t0 = time.perf_counter()
    try:
        report = run_soak(sc, ProcessFleet(sc, root),
                          enforce_gates=False)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    took = time.perf_counter() - t0
    vec = report.vector()
    led = report.ledger
    g = vec["gates"]
    return {
        "intervals": intervals, "seed": sc.seed,
        "kill_at": sc.kills[0][0],
        "elapsed_s": round(took, 1),
        "intervals_per_s": round(intervals / took, 2),
        "all_gates_ok": vec["all_ok"],
        "gates_ok": {k: v["ok"] for k, v in g.items()},
        "promotions": led.promotions,
        "takeover_detect_s": round(led.takeover_detect_s, 2),
        "takeover_first_flush_s": round(led.takeover_first_flush_s, 2),
        "accounted_lost": led.accounted_lost,
        "loss_bound": led.takeover_loss_bound,
        "loss_within_bound":
            0 <= led.accounted_lost <= led.takeover_loss_bound,
        "sent_global": led.sent_global,
        "emitted_global": led.emitted_global,
        "shed": led.shed,
        "restarts": dict(led.restarts),
    }


def bench_lint(budget_s: float = 60.0):
    """Config #16: the static-analysis plane itself (PR 18,
    ``veneur_tpu/lint/``) — all nineteen passes over the live package
    with the shared parsed-Project cache, recording per-pass wall
    clock, the finding count (must be 0 against the empty baseline),
    and the hot-set size the conservation passes analyze. The lint
    suite runs inside every tier-1 invocation AND as the pre-commit
    gate, so its cost is a direct tax on iteration speed; this lane
    makes a pathologically-slowed pass a visible regression, the same
    way 14_soak pins the runtime ledger."""
    from veneur_tpu.lint import PASSES, Project, run_passes
    from veneur_tpu.lint.dropflow import iter_hot_functions

    t0 = time.perf_counter()
    project = Project(_HERE)
    parse_s = time.perf_counter() - t0
    timings = {}
    findings = run_passes(project, timings=timings)
    total_s = time.perf_counter() - t0
    slowest = max(timings, key=timings.get) if timings else None
    return {
        "passes": len(PASSES),
        "files_analyzed": len(project.files),
        "hot_set_functions": sum(1 for _ in iter_hot_functions(project)),
        "findings": len(findings),
        "parse_s": round(parse_s, 3),
        "total_s": round(total_s, 3),
        "under_budget": total_s < budget_s,
        "slowest_pass": slowest,
        "slowest_pass_s": round(timings[slowest], 3) if slowest else None,
        "timings_s": {k: round(v, 3)
                      for k, v in sorted(timings.items(),
                                         key=lambda kv: -kv[1])},
    }


def bench_devflow(budget_s: float = 60.0):
    """Config #17: the device-flow plane of the lint suite (PR 20,
    ``veneur_tpu/lint/deviceflow.py`` / ``meshflow.py`` /
    ``devregistry.py``) — the four donation/transfer/sharding passes
    over the live package plus the registry inventories they audit:
    auto-discovered donating jit programs (decorator- and
    binding-form), justified per-row transfer choke points, declared
    shard_map parameter placements, and the resolved-vs-declared
    sharding table. The registry sizes are non-vacuity floors: a
    refactor that silently empties the donating-program inventory (so
    every donation check passes on nothing) shows up here as a count
    regression even though findings stay 0."""
    from veneur_tpu.lint import Project, run_passes
    from veneur_tpu.lint import deviceflow, meshflow

    t0 = time.perf_counter()
    project = Project(_HERE)
    parse_s = time.perf_counter() - t0
    timings = {}
    findings = run_passes(
        project, only=["donation-safety", "transfer-budget",
                       "sharding-soundness", "device-registry"],
        timings=timings)
    total_s = time.perf_counter() - t0
    inv = deviceflow.collect_programs(project)
    # call sites are tallied by the table generator, not collect_programs
    table_don = deviceflow.donation_table(project)
    call_sites = sum(
        int(ln.rsplit("|", 2)[-2].strip())
        for ln in table_don.splitlines()
        if ln.startswith("| `") and ln.rsplit("|", 2)[-2].strip().isdigit())
    boundaries = meshflow.shard_map_boundaries(project)
    table = meshflow.shardstate_table(project)
    return {
        "findings": len(findings),
        "parse_s": round(parse_s, 3),
        "total_s": round(total_s, 3),
        "under_budget": total_s < budget_s,
        "timings_s": {k: round(v, 3)
                      for k, v in sorted(timings.items(),
                                         key=lambda kv: -kv[1])},
        # the audited surface — each a floor the test suite also pins
        "donating_programs": len(inv.programs),
        "donation_call_sites": call_sites,
        "choke_points": len(deviceflow.CHOKE_POINTS),
        "shard_map_boundaries": len(
            {(rel, name) for rel, name, _c, _s, _f in boundaries}),
        "shardstate_entries": len(meshflow.SHARD_STATE),
        "device_placements": len(meshflow.DEVICE_PLACEMENTS),
        "shardstate_all_resolved": "| \u2014 |" not in table,
    }


# Per-lane wall-clock budgets (seconds). BENCH_r05 died rc=124 at the
# driver's GLOBAL timeout mid-lane, leaving 2f/5b/7/9 unmeasured; with
# budgets, a lane that cannot fit the remaining deadline is recorded as
# skipped-with-reason and the run keeps emitting. Subprocess lanes
# enforce their budget hard (subprocess timeout); in-process lanes
# cannot be preempted safely (they share the parent's TPU runtime), so
# an overrun is recorded on the lane and eats into the deadline the
# later lanes check against.
_DEADLINE_DEFAULT = 3300.0


def _lane_plan(result, guarded):
    """The lane registry: (name, thunk(budget_s) -> config dict,
    budget_s) in run order; ``guarded`` wraps in-process callables."""

    def headline_histo():
        num_series = 1 << 22
        histo = None
        while num_series >= 1 << 16:
            try:
                histo = bench_histo_flush(num_series)
                break
            except Exception as e:
                print(f"histo bench at {num_series} failed "
                      f"({type(e).__name__}); retrying at "
                      f"{num_series // 2}", file=sys.stderr)
                num_series //= 2
        if histo is None:
            raise SystemExit("histo bench failed at all sizes")
        # the headline is valid from this point on
        base_us = result["baseline_us_per_series"]
        result["metric"] = f"flush_p99_{num_series // 1000}k_histo_series"
        result["value"] = histo["p99_ms"]
        result["vs_baseline"] = round(
            num_series * base_us / 1e3 / histo["p99_ms"], 2)
        # p99 of N iters rides the max sample, so one hiccup moves it
        # run-to-run; the p50 ratio is the steady number
        result["vs_baseline_p50"] = round(
            num_series * base_us / 1e3 / histo["p50_ms"], 2)
        return dict(histo, series=num_series)

    return [
        ("0_ingest_udp", guarded(bench_ingest_pps), 180),
        # lane-fleet scaling: packets/s vs ingest_lanes in {1,2,4,8}
        # with the linearity ratio in the record; 0_ingest_udp above
        # stays the single-pipeline (legacy reader-pool) baseline
        ("0b_ingest_fleet", guarded(bench_ingest_fleet), 420),
        ("1_scalar_10k", guarded(bench_scalar_flush), 120),
        ("2_histo_4m", guarded(headline_histo), 900),
        # north-star scale: 10M series on the one chip — bf16 resident
        # digests (~13.2 GB local incl. the round-5 anchor-summary
        # planes; see core/slab.py). 256k-row slabs keep the per-slab
        # flush transients inside the free HBM.
        ("2b_histo_10m_bf16",
         guarded(bench_histo_flush, 10 * (1 << 20), "bfloat16", 5, 4,
                 1 << 18), 600),
        ("2c_merge_global_10m",
         guarded(bench_merge_global, 10 * (1 << 20)), 420),
        # gRPC import path (wire decode + bulk staging + device
        # scatter); isolated so it does not inherit the 10M configs'
        # HBM fragmentation (inline it measured ~100k/s lower)
        ("2d_import_grpc",
         lambda t: run_isolated("bench_import_throughput", timeout=t),
         300),
        # the server's own egress, now the overlapped pipeline: the
        # same 1M shape runs BOTH sequentially and pipelined/streamed
        # (hence the wider budget), with the overlap gate read off the
        # flush timeline; isolated subprocesses keep the multi-GB
        # configs off the parent's fragmented HBM
        ("6_egress_1m",
         lambda t: run_isolated("bench_egress_1m", timeout=t), 900),
        ("2e_forward_1m",
         lambda t: run_isolated("bench_forward_1m", timeout=t), 560),
        # the flagship: 10M-series packed forward, with sampled merge
        # oracle — staging 40M+ samples and fetching ~500 MB takes
        # minutes, hence the wide budget
        ("2f_forward_10m",
         lambda t: run_isolated("bench_forward_10m", timeout=t), 900),
        # tiered residency at realistic density (core/tiered.py):
        # flush p50 at ~4 live centroids/row, resident-bytes reduction
        # vs the dense-shape 2b plan, merged_ok oracle agreement
        ("2g_tiered_10m",
         lambda t: run_isolated("bench_tiered_10m", timeout=t), 900),
        ("3_hll", guarded(bench_hll), 240),
        ("3b_hll_1m_p12", guarded(bench_hll, 1 << 20, 1 << 17, 12), 240),
        ("3c_sets_1m_p14",
         lambda t: run_isolated("bench_sets_1m_p14", timeout=t), 560),
        ("4_mesh_global", guarded(bench_mesh_subprocess), 300),
        ("5_heavy_hitters", guarded(bench_heavy_hitters), 240),
        ("5b_heavy_hitters_100m",
         lambda t: run_isolated("bench_heavy_hitters_100m", timeout=t),
         560),
        ("7_tls_handshakes", guarded(bench_tls_handshakes), 240),
        ("8_ssf_spans", guarded(bench_ssf_spans), 240),
        ("9_proxy_fanout", guarded(bench_proxy_fanout), 300),
        # the observability tax: flush p50/p99 with stage tracing on vs
        # obs_enabled: false — the <=3% acceptance gate, measured as a
        # PAIRED per-iteration difference (host drift between separate
        # runs otherwise reads as instrumentation cost); isolated so
        # the twin 8k-series servers stay off the parent's heap
        ("10_obs_overhead",
         lambda t: run_isolated("bench_obs_overhead", timeout=t), 560),
        # fleet mode: the mesh-sharded tiered store's global merge
        # (shard-routed import + sharded flush) vs shard count on the
        # 8-device virtual mesh (subprocess; see bench_fleet_mesh for
        # why the curve, not the speedup, is the signal here)
        ("11_fleet", guarded(bench_fleet_mesh), 600),
        # elastic resharding: handoff wall-clock vs moved-key fraction
        # with the conservation check built in (fleet/handoff.py;
        # isolated so the stores never touch the parent's HBM)
        ("12_reshard",
         lambda t: run_isolated("bench_reshard", timeout=t), 560),
        # the fleet trace plane end to end: a REAL second process runs
        # the local (UDP lanes + commanded flushes + HTTP forward), the
        # global stitches GET /debug/trace and measures ingest->sink
        # freshness (veneur.fleet.e2e_age_ns) with conservation built
        # in (obs/tracectx.py, obs/fleet.py)
        ("13_e2e_trace",
         lambda t: run_isolated("bench_e2e_trace", timeout=t), 420),
        # the production soak plane: a real multi-process fleet through
        # a seeded 200-interval chaos schedule (SIGKILL every role,
        # sink outage windows, ENOSPC + deadline-pressure faults) with
        # the full steady-state gate vector in the record
        # (veneur_tpu/soak/, docs/resilience.md "Soak & chaos")
        ("14_soak",
         lambda t: run_isolated("bench_soak", timeout=t), 540),
        # global-aggregator HA: active global SIGKILLed forever
        # mid-run, warm standby wins the lapsed file lease, promotes
        # its replicated shadow and serves the rest of the drive —
        # records takeover wall clock + exact bounded-loss accounting
        # (veneur_tpu/fleet/standby.py, docs/resilience.md "Global HA")
        ("15_ha_takeover",
         lambda t: run_isolated("bench_ha_takeover", timeout=t), 240),
        # the static-analysis plane itself: all nineteen passes over the
        # live package (shared parse, per-pass wall clock, 0 findings
        # against the empty baseline) — pure AST, no jax, runs inline
        ("16_lint", guarded(bench_lint), 120),
        # the device-flow slice on its own clock: the four
        # donation/transfer/sharding passes plus the registry-size
        # non-vacuity floors (donating programs, choke points,
        # shard-state rows) — pure AST, runs inline
        ("17_devflow", guarded(bench_devflow), 120),
    ]


def _run_all(result, lanes_filter=None, deadline=None):
    # record machine contention alongside the numbers: every lane here
    # (and the C++ baseline) shares the host cores with whatever else is
    # running, so a loaded box shifts host-bound rates and the baseline
    # ratio — an artifact reader can judge a run by its loadavg
    try:
        result["host"] = {"cpus": os.cpu_count(),
                          "loadavg_at_start": round(os.getloadavg()[0], 2)}
    except OSError:  # pragma: no cover
        pass
    t_start = time.monotonic()
    if deadline is None:
        deadline = float(os.environ.get("BENCH_DEADLINE",
                                        _DEADLINE_DEFAULT))
    base_us, base_src = measure_scalar_baseline_us()
    result["baseline_us_per_series"] = round(base_us, 2)
    result["baseline_source"] = base_src

    def guarded(fn, *args):
        # the headline line must print even if one config dies
        def thunk(_budget):
            try:
                return fn(*args)
            except Exception as e:
                print(f"{fn.__name__} failed: {e}", file=sys.stderr)
                return {"error": f"{type(e).__name__}: {e}"[:160]}

        return thunk

    configs = result["configs"]
    for name, thunk, budget in _lane_plan(result, guarded):
        if lanes_filter is not None and not any(
                fnmatch.fnmatchcase(name, pat) for pat in lanes_filter):
            continue
        elapsed = time.monotonic() - t_start
        remaining = deadline - elapsed
        if remaining < min(budget, 60):
            # never die rc=124 mid-lane again: record WHY the lane went
            # unmeasured and keep emitting the lanes that still fit
            configs[name] = {"skipped":
                             f"deadline: {elapsed:.0f}s elapsed of "
                             f"{deadline:.0f}s, lane budget {budget}s"}
            continue
        t0 = time.monotonic()
        out = thunk(min(budget, remaining))
        took = time.monotonic() - t0
        if isinstance(out, dict) and took > budget:
            out["over_budget_s"] = round(took - budget, 1)
        configs[name] = out


def _headline(result) -> dict:
    """Compact summary that must survive the driver's 2000-byte tail cap
    (BENCH_r03.json lost its headline to truncation — VERDICT round-3
    weak #7): metric/value/vs_baseline and the north-star configs' key
    numbers. Full configs live in BENCH_DETAIL.json."""
    c = result.get("configs", {})

    def pick(cfg, *keys):
        d = c.get(cfg) or {}
        out = {k: d[k] for k in keys if k in d}
        if not out and "error" in d:
            return {"error": d["error"][:60]}
        if not out and "skipped" in d:
            return {"skipped": d["skipped"][:60]}
        return out

    head = {
        "metric": result.get("metric"),
        "value": result.get("value"),
        "unit": result.get("unit"),
        "vs_baseline": result.get("vs_baseline"),
        "vs_baseline_p50": result.get("vs_baseline_p50"),
        "summary": {
            "2_histo": pick("2_histo_4m", "p50_ms", "p99_ms", "series"),
            "2b_10m_bf16": pick("2b_histo_10m_bf16", "p50_ms", "p99_ms"),
            "2c_merge_10m": pick("2c_merge_global_10m", "merge_p50_ms",
                                 "flush_p50_ms"),
            "2d_import": pick("2d_import_grpc", "series_merged_per_s",
                              "store_path_series_per_s",
                              "realistic_density_series_per_s",
                              "realistic_density_grpc_series_per_s"),
            "2e_forward_1m": pick("2e_forward_1m", "total_s",
                                  "merged_ok"),
            "2f_forward_10m": pick("2f_forward_10m", "flush_s",
                                   "packed_wire_mb",
                                   "merged_ok"),
            "2g_tiered_10m": pick("2g_tiered_10m", "p50_ms",
                                  "resident_gb", "resident_reduction_x",
                                  "merged_ok", "promotions"),
            "5b_topk_100m": pick("5b_heavy_hitters_100m",
                                 "updates_per_s", "recall_at_64"),
            "6_egress_1m": pick("6_egress_1m", "total_s",
                                "sequential_total_s", "overlap_ratio",
                                "gate_wall_le_1.2x_max_lane",
                                "conserved"),
            "7_tls": pick("7_tls_handshakes", "ecdsa_p256_conn_s",
                          "rsa_2048_conn_s", "tls",
                          "plaintext_tcp_conn_s"),
            "9_proxy": pick("9_proxy_fanout", "metrics_per_s",
                            "forward_errors"),
            "11_fleet": pick("11_fleet", "per_shards", "series"),
            "12_reshard": pick("12_reshard", "grow_2_to_3",
                               "drain_all", "series", "conserved"),
            "13_e2e_trace": pick("13_e2e_trace", "e2e_age_ms_p50",
                                 "e2e_age_ms_p99",
                                 "hop_coverage_ratio", "conserved"),
            "14_soak": pick("14_soak", "all_gates_ok", "intervals",
                            "restarts", "rss_slope_pct_per_100",
                            "intervals_per_s"),
            "15_ha": pick("15_ha_takeover", "all_gates_ok",
                          "promotions", "takeover_detect_s",
                          "takeover_first_flush_s", "accounted_lost",
                          "loss_within_bound"),
            "16_lint": pick("16_lint", "passes", "findings", "total_s",
                            "slowest_pass", "slowest_pass_s",
                            "under_budget"),
            "17_devflow": pick("17_devflow", "findings",
                               "donating_programs", "choke_points",
                               "shardstate_entries",
                               "shardstate_all_resolved", "total_s"),
        },
        "detail_file": "BENCH_DETAIL.json",
    }
    if "truncated_by_signal" in result:
        head["truncated_by_signal"] = result["truncated_by_signal"]
    return head


def _emit(result):
    """Full detail to BENCH_DETAIL.json + stderr; the compact headline
    is the LAST stdout line so a tail-capped capture always parses."""
    detail = json.dumps(result)
    try:
        with open(os.path.join(_HERE, "BENCH_DETAIL.json"), "w") as f:
            f.write(detail + "\n")
    except OSError as e:  # pragma: no cover
        print(f"could not write BENCH_DETAIL.json: {e}", file=sys.stderr)
    print(detail, file=sys.stderr, flush=True)
    print(json.dumps(_headline(result)), flush=True)


def main():
    import argparse
    import signal
    import threading

    ap = argparse.ArgumentParser(
        description="veneur-tpu bench suite (one JSON line on stdout)")
    ap.add_argument(
        "--lanes", default="",
        help="comma-separated lane names to run (globs ok, e.g. "
             "'2*,3_hll'); default: every lane")
    ap.add_argument(
        "--deadline", type=float, default=None,
        help=f"global wall-clock budget in seconds (default "
             f"$BENCH_DEADLINE or {_DEADLINE_DEFAULT:.0f}); lanes that "
             f"no longer fit are recorded skipped-with-reason")
    args = ap.parse_args()
    lanes_filter = [p.strip() for p in args.lanes.split(",")
                    if p.strip()] or None

    # The full suite runs tens of minutes; if the harness times us out
    # mid-run, emit the one-line result with every config completed so
    # far rather than dying silently. The bench work runs on a WORKER
    # thread: Python delivers signals only to the main thread between
    # bytecodes, and the worker spends most of its life blocked inside C
    # calls (XLA compiles, device waits) — the main thread's short
    # interruptible joins are what make the handler actually fire.
    result = {
        "metric": "flush_p99_histo_series",
        "value": None,
        "unit": "ms",
        "configs": {},
    }
    if lanes_filter:
        result["lanes_filter"] = lanes_filter

    def emit_and_exit(signum, frame):  # pragma: no cover - timeout path
        result.setdefault("truncated_by_signal", signum)
        _emit(result)
        os._exit(0)

    signal.signal(signal.SIGTERM, emit_and_exit)
    signal.signal(signal.SIGINT, emit_and_exit)

    worker = threading.Thread(target=_run_all,
                              args=(result, lanes_filter, args.deadline),
                              daemon=True)
    worker.start()
    while worker.is_alive():
        worker.join(0.2)
    _emit(result)


if __name__ == "__main__":
    main()
