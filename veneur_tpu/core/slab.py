"""Capacity-planned t-digest bank for multi-million-series cardinality.

The dense ``DigestGroup`` (core/store.py) keeps one resident ``[S, K]``
plane per digest field. Two things stop that layout short of the 10M-series
north star (BASELINE.md) on a 16 GB v5e-1:

  * TPU tiling pads the trailing axis to 128 lanes, so a ``[S, 104]`` f32
    plane costs 1.23x its logical bytes (and the old K=160 cost 1.6x);
  * the flush program (sort + drain + quantile over the whole plane) peaks
    at several times the resident size.

This bank re-plans the capacity:

  * state lives in **flat 1-D planes** per slab (``[slab*K]``), which tile
    without lane padding — resident bytes == logical bytes;
  * the digest planes can be held in 16 bits (``digest_dtype:
    packed16``): bfloat16 weights, and each centroid's mean a uint16
    code against its row's [min, max] frame (the wire's packed format,
    ops/tdigest.py ``code_means``). All kernel math stays f32 (widened
    at the rows a program works on), exact counts, minima and maxima
    ride the separate f32 stats, so nothing the flusher emits as a
    counter is ever rounded. A mean is coded, not rounded to bfloat16:
    a row drained mid-interval keeps its later samples exact in the
    f32 bins, and an 8-bit mean crossed them (0.5 by rank in the 2-15
    band); a code is 1/65,535 of the row's span, and sparse rows keep
    the rank bound (docs/tdigest_accuracy.md);
  * every device program touches ONE slab (<= 1M rows): peak transient
    memory is slab-sized, and each Pallas operand stays under Mosaic's
    2 GiB (32-bit byte offset) limit.

Bytes of one slab of 262,144 rows, K=104 (``hbm_bytes``, from the
shapes: a local slab is the digest, five f32 planes a row (the means'
frames, the imported extrema, the count), the interval's bins, the
8-anchor summary and five stats; a merge slab the digest alone):

  | digest dtype | role  | one slab | 10M rows (39 slabs) |
  |--------------|-------|----------|---------------------|
  | f32          | local | 0.463 GB | 18.1 GB             |
  | packed16     | local | 0.354 GB | 13.8 GB             |
  | packed16     | merge | 0.114 GB |  4.5 GB             |

So 10M live rows on one 16 GB v5e-1 are the packed16 local plan, or two
chips (or the mesh store, core/mesh_store.py): the series axis is
embarrassingly shardable, so N chips multiply every row in this table
by N. What has run through ``Server`` on the chip (one v5e) is one slab
a generation under ``standalone-slab10m.steady``'s some 100,000 live
rows an interval: ``memory_peak_bytes`` read 1,041,818,624 at packed16
with two slabs resident (the retired and the fresh generation's at the
swap) and 1,235,260,928 at f32 (PERF.md section 4); the 39-slab
figures are reckoned from the shapes, not run.

Reference behavior re-expressed here: Worker.Flush + Histo.Flush
(flusher.go:134-254, samplers/samplers.go:511-636) for the local role,
ImportMetricGRPC -> tdigest.Merge (worker.go:354-398) for the global one.
"""

from __future__ import annotations

import logging
import math
import time
from functools import partial
from typing import List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from veneur_tpu.obs import kernels as obs_kernels
from veneur_tpu.obs import recorder as obs_rec
from veneur_tpu.ops import tdigest as td_ops
from veneur_tpu.core.locking import requires_lock
from veneur_tpu.ops.tdigest_pallas import _next_pow2

log = logging.getLogger("veneur.slab")

SLAB_ROWS_DEFAULT = 1 << 20

# ``digest_dtype`` as configured (config.py) -> the weight plane's dtype:
# "packed16" is the wire's packed format held resident, bfloat16 weights
# beside uint16 coded means (``DigestSlab``); a numpy dtype passes as is
STORAGE_DTYPES = {"float32": jnp.float32, "packed16": jnp.bfloat16}


def storage_dtype(digest_dtype) -> jnp.dtype:
    return jnp.dtype(STORAGE_DTYPES.get(digest_dtype, digest_dtype))


class DigestSlab(NamedTuple):
    """Resident state for one slab of series rows (flat planes).

    The digest is float32, or, held ``bfloat16``, 16 bits a plane:
    bfloat16 weights and each mean a uint16 code against its row's
    frame ``[fmin, fmax]`` (ops/tdigest.py ``coded``; a bfloat16 mean
    written by a mid-interval drain crossed the row's later samples).
    fmin/fmax bound every live mean of the row in either dtype: the
    shared ops take them as the digest's min/max. dmin/dmax are the
    imported digests' extrema, which only bound the final digest (as
    ``DigestGroup.dmin``/``dmax``).

    count is an EXACT f32 per-series total maintained alongside the
    (possibly bf16) centroid weights: merge-mode flushes report it
    instead of summing rounded weights, so counts never stall on bf16
    round-to-nearest even when a hot centroid's weight ULP exceeds an
    imported batch's contribution. (Local mode reports temp.count, which
    is f32 already; there this plane just rides along.)"""

    mean: jax.Array      # [slab*K] f32 (+inf = empty slot) or u16 codes
    weight: jax.Array    # [slab*K] f32 or bf16; 0 = empty slot
    fmin: jax.Array      # [slab] f32 least live mean's bound (+inf empty)
    fmax: jax.Array      # [slab] f32 greatest live mean's bound
    dmin: jax.Array      # [slab] f32 imported minima (+inf when empty)
    dmax: jax.Array      # [slab] f32 imported maxima (-inf when empty)
    count: jax.Array     # [slab] f32 exact total weight


def _init_digest_slab(slab: int, k: int, dtype) -> DigestSlab:
    dtype = jnp.dtype(dtype)
    edge = lambda v: jnp.full((slab,), v, jnp.float32)  # noqa: E731
    return DigestSlab(
        mean=(jnp.zeros((slab * k,), jnp.uint16) if dtype == jnp.bfloat16
              else jnp.full((slab * k,), jnp.inf, dtype)),
        weight=jnp.zeros((slab * k,), dtype),
        fmin=edge(jnp.inf), fmax=edge(-jnp.inf), dmin=edge(jnp.inf),
        dmax=edge(-jnp.inf), count=jnp.zeros((slab,), jnp.float32),
    )


def _as_digest(digest: DigestSlab) -> td_ops.TDigest:
    """A slab's digest as the shared ops take it: its flat storage
    planes and their frames (ops/tdigest.py reads the layout from the
    planes)."""
    return td_ops.TDigest(mean=digest.mean, weight=digest.weight,
                          min=digest.fmin, max=digest.fmax)


def _with_digest(digest: DigestSlab, d: td_ops.TDigest) -> DigestSlab:
    return digest._replace(mean=d.mean, weight=d.weight, fmin=d.min,
                           fmax=d.max)


def _host_rows(mean: np.ndarray, weight: np.ndarray, lo: np.ndarray,
               hi: np.ndarray):
    """Fetched ``[n, K]`` rows of a slab's storage planes (``lo``/``hi``
    their frames) as float32 (mean, weight) on the host."""
    weight = np.asarray(weight, np.float32)
    if mean.dtype != np.uint16:
        return np.asarray(mean, np.float32), weight
    lo, hi = np.asarray(lo, np.float32), np.asarray(hi, np.float32)
    with np.errstate(invalid="ignore"):
        span = np.where(np.isfinite(hi - lo), hi - lo, np.float32(0))
    base = np.where(np.isfinite(lo), lo, np.float32(0))
    means = base[:, None] + mean.astype(np.float32) * (
        span / np.float32(65535))[:, None]
    return np.where(weight > 0, means, np.float32(np.inf)), weight


@partial(jax.jit, donate_argnums=(0, 1), static_argnums=(7, 8))
def _ingest_slab(temp: td_ops.TempCentroids, digest: DigestSlab, rows,
                 values, weights, drained, trips, compression: float,
                 use_pallas: bool = True):
    """The sample path's ingest into one slab: the dense store's op
    (ops/tdigest.py ``ingest_chunk_rowdrained``) on the slab's flat
    storage planes. A held row of at most ``ROW_DRAIN_MAX_ARRIVALS``
    arrivals is drained into its digest before more is binned into it,
    behind that the shift guard, both in one ``lax.cond``. ``drained``
    and ``trips`` (int32 scalars) count the rows drained and the drain
    loop's trips. Returns (temp, digest, drained, trips).

    rows: [N] LOCAL row ids; the slab's row count (or more) is padding,
    which scatters nowhere."""
    d, temp, n = td_ops.ingest_chunk_rowdrained(
        _as_digest(digest), temp, rows, values, weights, compression,
        use_pallas=use_pallas)
    return (temp, _with_digest(digest, d), drained + n,
            trips + td_ops.row_drain_trips(n, rows.shape[0]))


@partial(jax.jit, donate_argnums=(0, 1), static_argnums=(8, 9))
def _import_slab(temp: td_ops.TempCentroids, digest: DigestSlab, rows,
                 means, weights, stat_rows, stat_mins, stat_maxs,
                 compression: float, use_pallas: bool = True):
    """Fold imported digest CENTROIDS into a slab's accumulators without
    touching the local scalar stats (samplers.go:473-480): the dense
    store's op (``ingest_centroids_rowdrained``: a row that holds bin
    mass is drained before its run is binned). Imported per-digest
    extrema land on the digest's dmin/dmax planes and only bound the
    final digest."""
    d, temp, _ = td_ops.ingest_centroids_rowdrained(
        _as_digest(digest), temp, rows, means, weights, compression,
        use_pallas=use_pallas)
    digest = _with_digest(digest, d)
    return temp, digest._replace(
        dmin=digest.dmin.at[stat_rows].min(stat_mins, mode="drop"),
        dmax=digest.dmax.at[stat_rows].max(stat_maxs, mode="drop"))


@partial(jax.jit, donate_argnums=(0, 1), static_argnums=(4, 5, 6))
def _flush_slab(digest: DigestSlab, temp: td_ops.TempCentroids, qs, n,
                compression: float, want_fresh: bool = True,
                use_pallas: bool = True):
    """Drain one slab's temp into its digests and emit percentiles: the
    dense store's flush op (``drain_and_quantile``) on the flat storage
    planes. ``n`` (an int32 scalar, or None for every row) is the
    slab's live rows, which the interner hands out as a prefix: the
    program works on the kernel slabs that hold them, one compiled
    variant whatever ``n``.

    Returns (fresh empty digest+temp for the next interval — or None/None
    when want_fresh=False: a RETIRED generation's slabs are never reused,
    so skipping the zero-fill lets the donated planes free outright —
    the drained digest planes in their storage dtypes, written in place
    of the donated ones and coded against the drained extrema that come
    beside them, percentiles [slab, P], scalar stats)."""
    slab, k = temp.num_series, temp.capacity
    drained, pcts = td_ops.drain_and_quantile(
        _as_digest(digest), temp, digest.dmin, digest.dmax, qs, compression,
        use_pallas=use_pallas, n=n)
    if want_fresh:
        fresh_d = _init_digest_slab(slab, k, digest.weight.dtype)
        fresh_t = td_ops.init_temp(slab, k)
    else:
        fresh_d = fresh_t = None
    return (fresh_d, fresh_t, drained.mean, drained.weight, drained.min,
            drained.max, pcts, temp.count, temp.vsum, temp.vmin, temp.vmax,
            temp.recip)


@partial(jax.jit, donate_argnums=(0, 1), static_argnums=(4, 5))
def _pack_slab(mean_flat, weight_flat, dmin, dmax, slab: int, k: int):
    """Compact + quantize one slab's drained digest planes ON DEVICE so
    the forward path never fetches raw f32 ``[S, K]`` planes (the 881 MB
    device→host transfer that blew the flush interval at 1M series;
    the reference forwards at fleet cardinality every interval,
    flusher.go:292-473).

    Means quantize to uint16 against the row's [dmin, dmax] span
    (absolute error ≤ span/65535 — orders of magnitude inside the
    t-digest ε=.02 envelope); weights round to bfloat16 bit patterns
    (relative error ≤ 2^-9, and exact counts ride the separate f32
    scalar stats). Live slots then move to each row's PREFIX via a
    per-row lane sort (the k axis is one vreg wide, so this is ~8x
    faster on TPU than the flat scatter it replaced: 119 ms vs 943 ms
    per 512k-row slab).

    Returns (counts uint16 [slab], q_pref uint16 [slab, k],
    wb_pref uint16 [slab, k]) — row r's live centroids are
    ``q_pref[r, :counts[r]]``; the caller (:func:`_fetch_packed`)
    fetches counts first and then only live bytes. Coded storage planes
    (``DigestSlab``) are coded against ``[dmin, dmax]``."""
    m, w = td_ops.rows_f32(mean_flat.reshape(slab, k),
                           weight_flat.reshape(slab, k), dmin, dmax)
    live = w > 0
    counts = jnp.sum(live, axis=1, dtype=jnp.int32)          # [slab]
    span = dmax - dmin
    scale = jnp.where(span > 0, 65535.0 / span, 0.0)
    q = jnp.clip(jnp.round((m - dmin[:, None]) * scale[:, None]),
                 0.0, 65535.0).astype(jnp.uint16)
    wb = lax.bitcast_convert_type(w.astype(jnp.bfloat16), jnp.uint16)
    col = jnp.broadcast_to(jnp.arange(k, dtype=jnp.int32), (slab, k))
    key = jnp.where(live, col, k + col)  # unique keys: live-first, stable
    _, q_pref, wb_pref = lax.sort((key, q, wb), dimension=-1, num_keys=1,
                                  is_stable=False)
    return counts.astype(jnp.uint16), q_pref, wb_pref


_STAT_NAMES = ("pcts", "count", "sum", "min", "max", "recip")


def _select_stats(want_stats):
    """Fetch order for the per-row stat arrays; None = all."""
    return [nm for nm in _STAT_NAMES
            if want_stats is None or nm in want_stats]


def _fill_stat_results(sel, cols, n: int, percentiles, out: dict) -> dict:
    """Map fetched stat columns into the flush result dict, zero-filling
    the unfetched ones. The zero-fill contract is load-bearing: it only
    holds because the SAME aggregate mask that excluded a key from the
    fetch (core/store.py _digest_want) gates its emissions — so
    this mapping lives in exactly one place for both the dense and slab
    digest groups. The shared zeros array is read-only: an accidental
    in-place write would otherwise corrupt every aliased key at once."""
    fetched = dict(zip(sel, cols))
    zeros = np.zeros(n, np.float32)
    zeros.flags.writeable = False
    for nm in _STAT_NAMES:
        if nm != "pcts":
            out[nm] = fetched.get(nm, zeros)
    if "pcts" in fetched:
        out["percentiles"] = fetched["pcts"][:, :-1]
        out["median"] = fetched["pcts"][:, -1]
    else:
        out["percentiles"] = np.zeros((n, len(percentiles)), np.float32)
        out["median"] = zeros
    return out


@partial(jax.jit, static_argnums=(2, 3))
def _slice_pack(q_pref, wb_pref, rows: int, width: int):
    return q_pref[:rows, :width], wb_pref[:rows, :width]


@partial(jax.jit, static_argnums=(3,))
def _gather_pack(counts, q_pref, wb_pref, P: int):
    """Flat-compact the prefix planes on device: output position i maps
    to (row via searchsorted over the count prefix-sum, rank within the
    row). One u32 take (q<<16 | wb) instead of two u16 gathers."""
    slab, k = q_pref.shape
    c = counts.astype(jnp.int32)
    cum = jnp.cumsum(c)
    i = jnp.arange(P, dtype=jnp.int32)
    row = jnp.clip(jnp.searchsorted(cum, i, side="right"),
                   0, slab - 1).astype(jnp.int32)
    j = jnp.clip(i - (cum - c)[row], 0, k - 1)
    packed = ((q_pref.astype(jnp.uint32) << 16)
              | wb_pref.astype(jnp.uint32)).reshape(-1)
    return jnp.take(packed, row * k + j)


def _fetch_packed(counts_dev, q_pref, wb_pref, need: int):
    """Host side of the packed fetch: counts first (tiny), then the
    cheaper of two live-bytes transfers —

    * uniform rows: a ``[:rows_pow2, :pow2(max_count)]`` column slice of
      the prefix planes, flattened host-side (one cheap device slice);
    * skewed rows (one heavy row would widen the slice): a device-side
      flat compaction (:func:`_gather_pack`) sized pow2(total).

    pow2 padding bounds the compiled variant count at ~log2 each: the
    counts come off the device as their pow2 bucket of rows and the
    compaction at its pow2 length, each cut to size on the host."""
    slab, k = q_pref.shape
    (counts,) = cut_rows(jax.device_get(
        _prefix_rows((counts_dev,), live_bucket(need, slab))), need)
    counts = np.asarray(counts)
    total = int(counts.astype(np.int64).sum())
    if total == 0:
        empty = np.empty(0, np.uint16)
        return counts, empty, empty
    maxc = int(counts.max())
    width = min(_next_pow2(maxc), k)
    rows = min(_next_pow2(need), slab)
    P = _next_pow2(total)
    if rows * width <= 3 * P:
        qs, wbs = jax.device_get(_slice_pack(q_pref, wb_pref, rows, width))
        qs = np.asarray(qs)[:need]
        wbs = np.asarray(wbs)[:need]
        mask = np.arange(width, dtype=np.int32)[None, :] < \
            counts[:, None].astype(np.int32)
        return counts, qs[mask], wbs[mask]
    packed = np.asarray(jax.device_get(
        _gather_pack(counts_dev, q_pref, wb_pref, P)))[:total]
    return counts, (packed >> 16).astype(np.uint16), \
        (packed & 0xFFFF).astype(np.uint16)


@partial(jax.jit, donate_argnums=(0,), static_argnums=(5, 6))
def _merge_slab(digest: DigestSlab, in_mean, in_weight, in_min, in_max,
                slab: int, compression: float) -> DigestSlab:
    """Merge one slab of imported digests into the resident state (the
    global-aggregator path: tdigest.Merge, worker.go:354-398).

    in_mean/in_weight: [slab, M] f32, weight==0 padding; rows need not be
    sorted. in_min/in_max: [slab] f32. The merged extrema are the
    frame the stored means are coded against."""
    k = digest.mean.shape[0] // slab
    d = _as_digest(digest)
    own_m, own_w = td_ops.rows_f32(d.mean.reshape(slab, k),
                                   d.weight.reshape(slab, k), d.min, d.max)
    live = in_weight > 0
    key = jnp.where(live, in_mean, jnp.inf)
    key, w_in = lax.sort((key, in_weight), dimension=-1, num_keys=1,
                         is_stable=False)
    new_m, new_w = td_ops._dispatch_compress_presorted(
        own_m, own_w, key, w_in, compression, k)
    lo = jnp.minimum(digest.dmin, in_min)
    hi = jnp.maximum(digest.dmax, in_max)
    new_m, new_w = td_ops.rows_stored(d, new_m, new_w, lo, hi)
    return DigestSlab(
        mean=new_m.reshape(-1), weight=new_w.reshape(-1),
        fmin=lo, fmax=hi, dmin=lo, dmax=hi,
        # exact f32 running total, immune to bf16 weight rounding
        count=digest.count + jnp.sum(jnp.where(live, in_weight, 0.0),
                                     axis=-1),
    )


@partial(jax.jit, donate_argnums=(0,), static_argnums=(2, 3))
def _quantile_slab(digest: DigestSlab, qs, slab: int, compression: float):
    """Flush a merge-mode slab: percentiles + counts from the resident
    digests alone, then reset (the global role has no temp accumulators)."""
    k = digest.mean.shape[0] // slab
    d = td_ops.digest_as_rows(_as_digest(digest), k)._replace(
        min=digest.dmin, max=digest.dmax)
    pcts = td_ops.quantile(d, qs)
    return (_init_digest_slab(slab, k, digest.weight.dtype), pcts,
            digest.count, d.min, d.max)


class SlabDigestBank:
    """A bank of ``num_series`` t-digests held as flat per-slab planes.

    mode='local': samples stream in via :meth:`ingest` / :meth:`ingest_slab`
    into per-slab temp accumulators; :meth:`flush` drains them (the fused
    Pallas program per slab) and returns percentiles + scalar stats.

    mode='merge': no temp planes; imported digests merge straight into the
    resident state via :meth:`merge_digests`; :meth:`flush` emits
    percentiles/counts and resets — the single-chip global-aggregator
    kernel (BASELINE config #4's on-chip half).
    """

    def __init__(self, num_series: int,
                 compression: float = td_ops.DEFAULT_COMPRESSION,
                 slab_rows: int = SLAB_ROWS_DEFAULT,
                 digest_dtype=jnp.float32,
                 mode: str = "local"):
        if mode not in ("local", "merge"):
            raise ValueError(f"unknown mode {mode!r}")
        if slab_rows <= 0 or num_series <= 0:
            raise ValueError(
                f"slab_rows and num_series must be positive, got "
                f"{slab_rows}/{num_series}")
        self.num_series = num_series
        self.compression = compression
        self.k = td_ops.size_bound(compression)
        # <= 1M rows per slab (Mosaic 2 GiB operand bound), and never a
        # slab wider than the bank itself — small banks must not allocate
        # or time a full default-width slab (rounded up to the kernel's
        # 128-row block)
        self.slab_rows = min(slab_rows, 1 << 20,
                             max(-(-num_series // 128) * 128, 8))
        self.num_slabs = -(-num_series // self.slab_rows)
        self.digest_dtype = storage_dtype(digest_dtype)
        self.mode = mode
        self.digests: List[DigestSlab] = [
            _init_digest_slab(self.slab_rows, self.k, self.digest_dtype)
            for _ in range(self.num_slabs)]
        self.temps: List[Optional[td_ops.TempCentroids]] = [
            td_ops.init_temp(self.slab_rows, self.k) if mode == "local"
            else None
            for _ in range(self.num_slabs)]

    # -- capacity plan ----------------------------------------------------

    def hbm_bytes(self) -> dict:
        """Resident-plane byte accounting (flat planes tile unpadded)."""
        dsz = self.digest_dtype.itemsize
        per_slab_digest = self.slab_rows * self.k * dsz * 2 \
            + self.slab_rows * 4 * 5
        per_slab_temp = (self.slab_rows * self.k * 4 * 2
                         + self.slab_rows * 4
                         * (5 + 2 * td_ops.BELOW_MASS_ANCHORS)) \
            if self.mode == "local" else 0
        total = self.num_slabs * (per_slab_digest + per_slab_temp)
        return {
            "digest_bytes": self.num_slabs * per_slab_digest,
            "temp_bytes": self.num_slabs * per_slab_temp,
            "total_bytes": total,
            "slab_transient_bytes": self.slab_rows * self.k * 4 * 6,
            "num_slabs": self.num_slabs,
            "k": self.k,
        }

    # -- local role: sample ingest ---------------------------------------

    def ingest_slab(self, slab_idx: int, rows, values, weights):
        """Fold a flat chunk of samples whose rows are LOCAL to one slab."""
        assert self.mode == "local"
        with obs_kernels.scope("drain.digest.slab"):
            self.temps[slab_idx], self.digests[slab_idx], _, _ = \
                _ingest_slab(self.temps[slab_idx], self.digests[slab_idx],
                             jnp.asarray(rows), jnp.asarray(values),
                             jnp.asarray(weights), np.int32(0), np.int32(0),
                             self.compression)

    def ingest(self, rows, values, weights):
        """Fold a flat chunk with GLOBAL row ids: each slab scatters the
        in-range subset (out-of-range ids drop on-device, so one chunk
        costs num_slabs scatter programs — pre-partition by slab where the
        producer can, cf. the native reader's shard split)."""
        assert self.mode == "local"
        rows = jnp.asarray(rows)
        values = jnp.asarray(values)
        weights = jnp.asarray(weights)
        with obs_kernels.scope("drain.digest.slab"):
            for i in range(self.num_slabs):
                base = i * self.slab_rows
                local = jnp.where((rows >= base)
                                  & (rows < base + self.slab_rows),
                                  rows - base, self.slab_rows)
                self.temps[i], self.digests[i], _, _ = _ingest_slab(
                    self.temps[i], self.digests[i], local, values, weights,
                    np.int32(0), np.int32(0), self.compression)

    # -- global role: digest import --------------------------------------

    def merge_digests(self, slab_idx: int, mean, weight, mins, maxs):
        """Merge imported digests for one slab: mean/weight [slab, M] f32
        (weight==0 padding), mins/maxs [slab] f32."""
        with obs_kernels.scope("drain.digest.slab"):
            self.digests[slab_idx] = _merge_slab(
                self.digests[slab_idx], jnp.asarray(mean, jnp.float32),
                jnp.asarray(weight, jnp.float32),
                jnp.asarray(mins, jnp.float32),
                jnp.asarray(maxs, jnp.float32),
                self.slab_rows, self.compression)

    # -- flush ------------------------------------------------------------

    def flush(self, percentiles: Sequence[float], fetch: bool = True,
              want_digest: bool = False):
        """Drain every slab; returns a dict of np arrays over all series
        (or per-slab device arrays when fetch=False, for benchmarking).

        want_digest=True additionally keeps each slab's drained digest
        planes (for the forward/export path). At 10M series that is
        ~4 GB of extra live output — leave it off unless the caller
        actually forwards."""
        qs = jnp.asarray(list(percentiles), jnp.float32)
        outs = []
        with obs_kernels.scope("flush.digest.slab"):
            for i in range(self.num_slabs):
                if self.mode == "local":
                    (self.digests[i], self.temps[i], mean, weight, dmin,
                     dmax, pcts, count, vsum, vmin, vmax,
                     recip) = _flush_slab(
                        self.digests[i], self.temps[i], qs, None,
                        self.compression)
                    out = {"percentiles": pcts, "count": count,
                           "sum": vsum, "min": vmin, "max": vmax,
                           "recip": recip}
                    if want_digest:
                        out["digest_mean"], out["digest_weight"] = \
                            td_ops.rows_f32(
                                mean.reshape(self.slab_rows, self.k),
                                weight.reshape(self.slab_rows, self.k),
                                dmin, dmax)
                    outs.append(out)
                else:
                    (self.digests[i], pcts, counts, dmin,
                     dmax) = _quantile_slab(
                        self.digests[i], qs, self.slab_rows,
                        self.compression)
                    outs.append({"percentiles": pcts, "count": counts,
                                 "min": dmin, "max": dmax})
        if not fetch:
            return outs
        n = self.num_series
        host = [jax.device_get(o) for o in outs]
        result = {}
        for key in host[0].keys():
            cols = [h[key] for h in host]
            if key in ("digest_mean", "digest_weight"):
                # flat [slab*K] planes -> [S, K] rows
                cols = [c.reshape(self.slab_rows, self.k) for c in cols]
            result[key] = np.concatenate(cols, axis=0)[:n]
        return result

    def block_until_ready(self):
        for d in self.digests:
            jax.block_until_ready(d.weight)
        for t in self.temps:
            if t is not None:
                jax.block_until_ready(t.sum_w)


# cycle-safe: store imports nothing from slab at module top level
from veneur_tpu.core.store import (  # noqa: E402
    OverloadLimited, _prefix_rows, cut_rows, fetch_stage, live_bucket)
from veneur_tpu.overload import F32_ABS_MAX, MIN_SAMPLE_RATE  # noqa: E402


class SlabDigestGroup(OverloadLimited):
    """Drop-in ``DigestGroup`` replacement backed by slab state: the
    store-facing adapter that makes the 10M-series capacity plan a server
    configuration (``digest_storage: slab``) rather than a bench harness.

    Same public surface as ``core.store.DigestGroup`` — interner, sample /
    sample_many / import_centroids staging, flush -> (interner, result
    dict) with identical keys — but state lives in flat per-slab planes
    (optionally bf16), capacity grows slab-at-a-time instead of
    reallocating one dense plane, and the flush fetches each slab's
    results right after its device program so peak extra memory stays
    slab-sized.

    Its sample, import and flush programs are the dense store's ops on
    the flat planes (ops/tdigest.py ``ingest_chunk_rowdrained``,
    ``ingest_centroids_rowdrained``, ``drain_and_quantile``): a held row
    is drained before more is binned into it, and the flush works on
    each slab's live rows. Staged chunks are partitioned by slab on the
    host, each part padded to the chunk's length, so each program
    compiles once a group.
    """

    _retired = False  # see core.store.DigestGroup._retired

    def __init__(self, slab_rows: int = SLAB_ROWS_DEFAULT,
                 chunk: int = 1 << 16,
                 compression: float = td_ops.DEFAULT_COMPRESSION,
                 digest_dtype=jnp.float32, slabs: int = 0):
        from veneur_tpu.core.store import Interner

        self._interner_cls = Interner
        self.interner = Interner()
        self.compression = compression
        self.k = td_ops.size_bound(compression)
        self.chunk = chunk
        if slab_rows <= 0:
            raise ValueError(f"slab_rows must be positive, got {slab_rows}")
        self.slab_rows = min(slab_rows, 1 << 20)
        self.digest_dtype = storage_dtype(digest_dtype)
        # ``slabs`` placed now (a fresh generation gets its retired
        # one's count, ``fresh``), any more as rows intern: a
        # scope-class this deployment never writes holds no device
        # memory (a slab placed for each of four such groups made the
        # swap 48-238 ms a flush on the chip, PERF.md section 6)
        self.digests: List[DigestSlab] = [
            _init_digest_slab(self.slab_rows, self.k, self.digest_dtype)
            for _ in range(slabs)]
        self.temps: List[td_ops.TempCentroids] = [
            td_ops.init_temp(self.slab_rows, self.k) for _ in range(slabs)]
        self._device_dirty = False
        # this generation's counts, read at its flush (timeline ``slab``):
        # the sample path's program dispatches, the device's own counts
        # of the rows they drained before binning into them and of the
        # drain loop's trips, and the slabs placed with the host's
        # nanoseconds in placing them
        self.smp_dispatches = 0
        self._row_drains = None
        self.grows = 0
        self.grow_ns = 0
        self._new_sample_buffers()
        self._new_import_buffers()

    # -- capacity ---------------------------------------------------------

    @property
    def capacity(self) -> int:
        return len(self.digests) * self.slab_rows

    def __len__(self):
        return len(self.interner)

    def fresh(self) -> "SlabDigestGroup":
        """Empty same-config twin (swap-on-flush generation swap),
        holding as many slabs as this generation placed, so an interval
        that interns no more rows than the last grows nothing on the
        merger's thread; past that it grows slab-at-a-time as rows
        intern (zero-fill appends, no copies; the retired generation's
        slabs free one by one as the off-lock flush donates them into
        its programs). The grows are counted and timed (``grows``,
        ``grow_ns``: timeline ``slab``)."""
        return SlabDigestGroup(self.slab_rows, self.chunk,
                               self.compression, self.digest_dtype,
                               slabs=len(self.digests))

    @requires_lock("store")
    def ensure_capacity(self, max_row: int):
        if max_row < self.capacity:
            return
        t0 = time.monotonic_ns()
        while max_row >= self.capacity:
            self.grows += 1
            self.digests.append(
                _init_digest_slab(self.slab_rows, self.k, self.digest_dtype))
            self.temps.append(td_ops.init_temp(self.slab_rows, self.k))
            # stale sentinels from before the grow are harmless (their
            # weights are 0) but re-point them anyway, like DigestGroup
            self._rows[self._fill:] = self.capacity
            self._imp_rows[self._imp_fill:] = self.capacity
            self._imp_stat_rows[self._imp_stat_fill:] = self.capacity
        self.grow_ns += time.monotonic_ns() - t0

    @requires_lock("store")
    def _row(self, key, tags) -> int:
        row = self._intern_row(key, tags)
        if row >= self.capacity:
            self.ensure_capacity(row)
        return row

    # -- staging ----------------------------------------------------------

    def _new_sample_buffers(self):
        self._rows = np.full(self.chunk, self.capacity, np.int32)
        self._vals = np.zeros(self.chunk, np.float32)
        self._wts = np.zeros(self.chunk, np.float32)
        self._fill = 0

    def _new_import_buffers(self):
        self._imp_rows = np.full(self.chunk, self.capacity, np.int32)
        self._imp_means = np.zeros(self.chunk, np.float32)
        self._imp_wts = np.zeros(self.chunk, np.float32)
        self._imp_fill = 0
        # numpy stat staging, matching DigestGroup._new_import_buffers
        self._imp_stat_rows = np.full(self.chunk, self.capacity, np.int32)
        self._imp_stat_mins = np.full(self.chunk, np.inf, np.float32)
        self._imp_stat_maxs = np.full(self.chunk, -np.inf, np.float32)
        self._imp_stat_fill = 0

    @requires_lock("store")
    def sample(self, key, tags, value: float, sample_rate: float):
        # numerics quarantine, mirroring DigestGroup.sample: nothing
        # non-finite (or that goes non-finite in f32) reaches the planes
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
        self._wts[i] = np.float32(1.0) / np.float32(sample_rate)
        self._fill = i + 1
        if self._fill == self.chunk:
            self._drain_samples()

    @requires_lock("store")
    def sample_many(self, rows: np.ndarray, vals: np.ndarray,
                    wts: np.ndarray):
        from veneur_tpu.core.store import _scrub_float_batch

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
    def import_centroids(self, key, tags, means: np.ndarray,
                         weights: np.ndarray, dmin: float, dmax: float):
        row = self._row(key, tags)
        n = len(means)
        # keep one digest's sorted centroid run inside one staging
        # drain (see store.bulk_stage_import_centroids)
        if self._imp_fill + n > self.chunk and n <= self.chunk:
            self._drain_imports()
        start = 0
        while start < n:
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
            if self._imp_stat_fill == self.chunk:
                self._drain_imports()

    @requires_lock("store")
    def import_centroids_bulk(self, rows: np.ndarray, means: np.ndarray,
                              weights: np.ndarray, stat_rows,
                              stat_mins, stat_maxs):
        """Bulk staging append for the import path (rows pre-interned by
        the caller); shares DigestGroup's staging protocol."""
        from veneur_tpu.core.store import bulk_stage_import_centroids

        bulk_stage_import_centroids(self, rows, means, weights, stat_rows,
                                    stat_mins, stat_maxs)

    # -- drains -----------------------------------------------------------

    def _per_slab(self, rows, *arrays):
        """Partition staged entries by slab; yields (slab_idx, local_rows,
        arrays...), each slab's part padded to the chunk's length: one
        compiled program a group however a chunk splits over slabs and
        however full the flush's last chunk is (a length a part made
        every new count compile inside the window)."""
        slabs = rows // self.slab_rows
        for i in np.unique(slabs):
            if i < 0 or i >= len(self.digests):
                continue  # sentinel padding rows
            sel = slabs == i
            m = int(sel.sum())
            pad = self.chunk
            local = np.full(pad, self.slab_rows, np.int32)
            local[:m] = rows[sel] - i * self.slab_rows
            padded = []
            for a in arrays:
                buf = np.zeros(pad, a.dtype)
                buf[:m] = a[sel]
                padded.append(buf)
            yield int(i), local, padded

    def _drain_samples(self):
        if self._fill == 0:
            return
        self._device_dirty = True
        rows, vals, wts = self._rows, self._vals, self._wts
        self._new_sample_buffers()
        drained, trips = self._row_drains or (np.int32(0), np.int32(0))
        with obs_kernels.scope("drain.digest.slab"):
            for i, local, (v, w) in self._per_slab(rows, vals, wts):
                self.smp_dispatches += 1
                self.temps[i], self.digests[i], drained, trips = \
                    _ingest_slab(self.temps[i], self.digests[i],
                                 jnp.asarray(local), jnp.asarray(v),
                                 jnp.asarray(w), drained, trips,
                                 self.compression, self._pallas_allowed())
        self._row_drains = (drained, trips)

    def _drain_imports(self):
        if self._imp_fill == 0 and self._imp_stat_fill == 0:
            return
        self._device_dirty = True
        rows, means, wts = self._imp_rows, self._imp_means, self._imp_wts
        ns = self._imp_stat_fill
        stat_rows = self._imp_stat_rows[:ns]
        stat_mins = self._imp_stat_mins[:ns]
        stat_maxs = self._imp_stat_maxs[:ns]
        self._new_import_buffers()
        # centroid scatter per touched slab
        by_slab = {i: (local, padded)
                   for i, local, padded in self._per_slab(rows, means, wts)}
        # extrema per touched slab
        stats = {i: (local, padded) for i, local, padded in
                 self._per_slab(stat_rows, stat_mins, stat_maxs)} \
            if len(stat_rows) else {}
        empty_f = np.zeros(self.chunk, np.float32)
        empty_r = np.full(self.chunk, self.slab_rows, np.int32)
        with obs_kernels.scope("drain.digest.slab"):
            for i in sorted(set(by_slab) | set(stats)):
                c_local, c_pad = by_slab.get(
                    i, (empty_r, [empty_f, empty_f]))
                s_local, s_pad = stats.get(
                    i, (empty_r, [np.full(self.chunk, np.inf, np.float32),
                                  np.full(self.chunk, -np.inf,
                                          np.float32)]))
                self.temps[i], self.digests[i] = _import_slab(
                    self.temps[i], self.digests[i],
                    jnp.asarray(c_local), jnp.asarray(c_pad[0]),
                    jnp.asarray(c_pad[1]), jnp.asarray(s_local),
                    jnp.asarray(s_pad[0]), jnp.asarray(s_pad[1]),
                    self.compression, self._pallas_allowed())

    def _drain_staging(self):
        self._drain_samples()
        self._drain_imports()

    # -- flush ------------------------------------------------------------

    def _kernel_plane(self):
        """The digest plane as the flush ops see it: whatever the
        storage dtype, a slab is widened to f32 [slab_rows, K] before
        the drain, and that is what ``pallas_ok`` judges."""
        return jax.ShapeDtypeStruct((self.slab_rows, self.k), jnp.float32)

    def _reset_device(self):
        nslabs = len(self.digests)
        self.digests = [
            _init_digest_slab(self.slab_rows, self.k, self.digest_dtype)
            for _ in range(nslabs)]
        self.temps = [td_ops.init_temp(self.slab_rows, self.k)
                      for _ in range(nslabs)]
        self._device_dirty = False

    def _drop_staging(self):
        """Release a RETIRED twin's host staging buffers at the
        earliest point — the round-5 release-order audit: the retired
        generation object outlives its flush by the whole sink fan-out,
        and before this the dead twin kept ~6 chunk-sized numpy buffers
        (plus, on the n==0 path, allocated FRESH ones) pinned for that
        entire window. Device planes free first (donated slab by slab
        or dropped by the caller), host staging immediately after;
        fills reset so a stray drain on the dead twin is a no-op
        instead of a crash."""
        self._rows = self._vals = self._wts = None
        self._imp_rows = self._imp_means = self._imp_wts = None
        self._imp_stat_rows = self._imp_stat_mins = None
        self._imp_stat_maxs = None
        self._fill = 0
        self._imp_fill = 0
        self._imp_stat_fill = 0

    def flush(self, percentiles: List[float], want_digests=True,
              want_stats=None):
        """Drain + percentile every slab; identical contract to
        DigestGroup.flush: (old interner, dict of host arrays [:n]).

        want_digests=False skips fetching the [n, K] mean/weight planes
        (only a FORWARDING flush needs them on the host — a multi-million
        -series plane is hundreds of MB of device->host transfer).
        want_digests="packed" compacts + quantizes the planes on device
        (:func:`_pack_slab`) and fetches only live centroids at
        4 bytes each — the forwarding mode that fits the flush interval
        at 1M+ series. Packed keys: ``packed_counts`` (u16 [n]),
        ``packed_means`` / ``packed_weights`` (u16 [L]).

        want_stats (None = all) selects which per-row scalar stat arrays
        to FETCH, from {"pcts", "count", "sum", "min", "max", "recip"}:
        at 1M rows every f32 array is 4 MB of transfer, and a default
        min/max/count aggregate config never reads sum/recip/median.
        Unfetched keys come back zero-filled (their emissions are masked
        off by the aggregate config that chose not to fetch them).

        Like ``DigestGroup.flush``, the device half runs behind the
        compute-breaker ladder (resilience/compute.py); the interner
        swap happens only after the programs + fetches succeed, so a
        failed ladder leaves the group recoverable for the store's
        re-merge rung."""
        self._drain_staging()
        n = len(self.interner)
        if n == 0:
            return self._flush_empty()
        from veneur_tpu.core.store import run_compute_ladder

        out = run_compute_ladder(
            self._compute,
            lambda use_pallas: self._flush_fetch(
                n, percentiles, want_digests, want_stats, use_pallas),
            self._kernel_plane())
        return self._flush_commit(out)

    def flush_begin(self, percentiles: List[float], want_digests=True,
                    want_stats=None):
        """Two-phase flush for the pipelined egress (see
        ``DigestGroup.flush_begin``): the first ``_pipeline_window``
        slabs' flush programs DISPATCH now; the returned ``finish()``
        runs the windowed fetch loop — fetching slab j while slab
        j+window executes — then commits. The compute ladder retries
        inside ``finish`` (:func:`begin_compute_ladder` semantics)."""
        with obs_rec.maybe_stage("drain"):
            self._drain_staging()
            self._note_drains()
        n = len(self.interner)
        if n == 0:
            res = self._flush_empty()
            return lambda: res
        from veneur_tpu.core.store import begin_compute_ladder

        fin = begin_compute_ladder(
            self._compute,
            lambda use_pallas: self._flush_dispatch(
                n, percentiles, want_digests, want_stats, use_pallas),
            lambda st, use_pallas: self._flush_collect(
                st, n, percentiles, want_digests),
            self._kernel_plane())
        return lambda: self._flush_commit(fin())

    def _note_drains(self) -> None:
        """What this generation's sample path and growth counted, on the
        flush's open ``drain`` stage (the flusher sums the notes into the
        timeline entry's ``slab``)."""
        if self.smp_dispatches or self.grows:
            obs_rec.note(slab_ingest_dispatches=self.smp_dispatches,
                         slab_grows=self.grows, slab_grow_ns=self.grow_ns)

    def _flush_empty(self):
        with obs_rec.maybe_stage("commit", scope=True):
            interner, self.interner = self.interner, self._interner_cls()
            if self._retired:
                # release order: device planes first, then host staging;
                # a dead twin must not allocate fresh buffers
                self.digests = []
                self.temps = []
                self._device_dirty = False
                self._drop_staging()
                return interner, {}
            if self._device_dirty:
                self._reset_device()
            self._new_sample_buffers()
            self._new_import_buffers()
        return interner, {}

    def _flush_commit(self, out: dict):
        with obs_rec.maybe_stage("commit", scope=True):
            interner, self.interner = self.interner, self._interner_cls()
            self._device_dirty = False
            if self._retired:
                # release order: drained device planes first (their
                # donated buffers already freed slab by slab), host
                # staging second
                self.digests = []
                self.temps = []
                self._drop_staging()
            else:
                self._new_sample_buffers()
                self._new_import_buffers()
        return interner, out

    def _flush_fetch(self, n: int, percentiles, want_digests, want_stats,
                     use_pallas: bool) -> dict:
        """One complete flush attempt over every slab (device programs +
        host fetches into the result dict), dispatch and collect
        composed back to back. The fresh planes each slab's program
        returns are committed to ``self`` only once EVERY slab
        succeeded: a mid-loop kernel failure must leave the group's
        references intact for the fallback rung / the store's re-merge
        (on a backend that honors donation the consumed inputs are gone
        either way, and the ladder degrades to the checkpoint bound)."""
        st = self._flush_dispatch(n, percentiles, want_digests,
                                  want_stats, use_pallas)
        return self._flush_collect(st, n, percentiles, want_digests)

    def _slab_live(self, n: int, i: int) -> int:
        """Rows of slab ``i`` among the ``n`` interned (a prefix)."""
        return max(0, min(n - i * self.slab_rows, self.slab_rows))

    def _flush_dispatch(self, n: int, percentiles, want_digests,
                        want_stats, use_pallas: bool) -> dict:
        """Async half of one flush attempt: dispatch the first
        ``_pipeline_window`` slabs' flush (+pack) programs and slice
        out their device refs. The window bounds how many slabs are
        in flight at once — each in-flight slab holds its drained
        output planes alive until its fetch lands — so device memory
        stays flat at window size instead of doubling across every
        slab."""
        st = {"packed": want_digests == "packed",
              "sel": _select_stats(want_stats),
              "qs": jnp.asarray(list(percentiles) + [0.5], jnp.float32),
              "use_pallas": use_pallas,
              "want_digests": want_digests,
              "n": n,
              "nslabs": len(self.digests),
              "new_digests": list(self.digests),
              "new_temps": list(self.temps),
              "refs": [],
              "next": 0}
        window = max(1, getattr(self, "_pipeline_window", 1))
        with obs_rec.maybe_stage("compute"):
            obs_rec.note(slab_rows_live=n, slab_rows_run=sum(
                td_ops.flush_rows_run(self.slab_rows, self._slab_live(n, i))
                for i in range(st["nslabs"])))
            for _ in range(min(window, st["nslabs"])):
                self._dispatch_slab(st)
        return st

    def _dispatch_slab(self, st: dict) -> None:
        """Dispatch one slab's flush program (async) over its live rows
        and record its fetchable refs, each the live count's pow2 bucket
        of rows (``_flush_collect`` cuts them to the count on the host),
        in dispatch order."""
        i = st["next"]
        st["next"] = i + 1
        need = self._slab_live(st["n"], i)
        # a retired generation skips allocating fresh slabs (its
        # donated planes free outright, slab by slab)
        with obs_kernels.scope("flush.digest.slab"):
            (st["new_digests"][i], st["new_temps"][i], mean, weight,
             dmin, dmax, pcts, count, vsum, vmin, vmax, recip) = \
                _flush_slab(
                    self.digests[i], self.temps[i], st["qs"],
                    np.int32(need), self.compression, not self._retired,
                    st["use_pallas"])
            if need == 0:
                st["refs"].append(None)
                return
            b = live_bucket(need, self.slab_rows)
            planes = ()
            pk_refs = digest_refs = None
            if st["packed"]:
                pk_refs = _pack_slab(mean, weight, dmin, dmax,
                                     self.slab_rows, self.k)
                planes = (dmin, dmax)
            elif st["want_digests"]:
                digest_refs = _prefix_rows((mean, weight), b * self.k)
                planes = (dmin, dmax)
            stats = {"pcts": pcts, "count": count, "sum": vsum,
                     "min": vmin, "max": vmax, "recip": recip}
            st["refs"].append((need, pk_refs, digest_refs, _prefix_rows(
                planes + tuple(stats[nm] for nm in st["sel"]), b)))

    def _flush_collect(self, st: dict, n: int, percentiles,
                       want_digests) -> dict:
        """Blocking half: fetch each dispatched slab's interned prefix
        in order, dispatching slab j+window while slab j's fetch
        blocks — device execution overlaps the host transfer instead
        of idling behind it. The device's own counts of the sample
        path's drains come with the first fetch."""
        window = max(1, getattr(self, "_pipeline_window", 1))
        parts, digests = [], []
        pk_counts, pk_means, pk_wts = [], [], []
        counters = self._row_drains
        for j in range(st["nslabs"]):
            while st["next"] < st["nslabs"] and st["next"] - j < window:
                with obs_rec.maybe_stage("compute"):
                    self._dispatch_slab(st)
            ref = st["refs"][j]
            if ref is None:
                continue
            need, pk_refs, digest_refs, refs = ref
            st["refs"][j] = None  # drop the fetched slab's refs promptly
            with fetch_stage((pk_refs, digest_refs, refs)):
                if st["packed"]:
                    c_h, pm_h, pw_h = _fetch_packed(*pk_refs, need)
                    pk_counts.append(c_h)
                    pk_means.append(pm_h)
                    pk_wts.append(pw_h)
                fetched, planes, drains = jax.device_get(
                    (refs, digest_refs, counters))
                part = cut_rows(fetched, need)
                parts.append(part)
                if planes is not None:
                    # the planes' frames are the drained extrema, the
                    # part's first two columns
                    digests.append(_host_rows(
                        *(p.reshape(-1, self.k) for p in cut_rows(
                            planes, need * self.k)), *part[:2]))
                if counters is not None:
                    rows, trips = drains
                    obs_rec.note(slab_ingest_rows_drained=int(rows),
                                 slab_ingest_drain_trips=int(trips))
                    counters = None
        cols = [np.concatenate(c, axis=0) for c in zip(*parts)]
        # every slab's program + fetch succeeded: commit the fresh planes
        self.digests, self.temps = st["new_digests"], st["new_temps"]
        out = {}
        if st["packed"] or want_digests:
            out["digest_min"], out["digest_max"] = cols[:2]
            cols = cols[2:]
        if st["packed"]:
            out["packed_counts"] = np.concatenate(pk_counts)
            out["packed_means"] = np.concatenate(pk_means)
            out["packed_weights"] = np.concatenate(pk_wts)
        elif want_digests:
            out["digest_mean"], out["digest_weight"] = (
                np.concatenate(c, axis=0) for c in zip(*digests))
        return _fill_stat_results(st["sel"], cols, n, percentiles, out)

    # -- checkpoint snapshot / restore (veneur_tpu/persist/) --------------

    @requires_lock("store")
    def snapshot_begin(self):
        """Slab twin of ``DigestGroup.snapshot_begin``: phase 1 under
        the store lock drains staging and dispatches per-slab plane
        slices (fresh buffers, async); the returned ``finish`` runs the
        blocking fetches OFF-lock and flattens each slab's interned
        prefix into the shared per-row centroid-run layout."""
        self._drain_staging()
        n = len(self.interner)
        snap = {"kind": "digest", "names": list(self.interner.names),
                "joined": list(self.interner.joined)}
        if n == 0:
            return snap, None
        k = self.k
        slab_refs = []
        for i, d in enumerate(self.digests):
            need = self._slab_live(n, i)
            if need == 0:
                break
            # the count's pow2 bucket of rows (a checkpoint compiles
            # nothing new while the count wanders), cut on the host
            t = self.temps[i]
            b = live_bucket(need, self.slab_rows)
            slab_refs.append((i, need, _prefix_rows(
                (d.mean, d.weight, t.sum_w, t.sum_wm), b * k), _prefix_rows(
                (d.fmin, d.fmax, d.dmin, d.dmax, t.count, t.vsum, t.vmin,
                 t.vmax, t.recip), b)))

        def finish():
            from veneur_tpu.core.store import flatten_digest_state

            rows_p, means_p, weights_p, scalars_p = [], [], [], []
            for i, need, plane_refs, row_refs in slab_refs:
                planes, scalars = jax.device_get((plane_refs, row_refs))
                mean, weight, bin_w, bin_wm = (
                    p.reshape(need, k) for p in cut_rows(planes,
                                                         need * k))
                fmin, fmax, dmin, dmax, *stats = cut_rows(scalars, need)
                flat = flatten_digest_state(
                    *_host_rows(mean, weight, fmin, fmax),
                    np.asarray(bin_w, np.float32),
                    np.asarray(bin_wm, np.float32))
                rows_p.append(flat["rows"] + np.int32(i * self.slab_rows))
                means_p.append(flat["means"])
                weights_p.append(flat["weights"])
                # digest-bound extrema, as DigestGroup.snapshot_begin's
                scalars_p.append(tuple(np.asarray(x, np.float32) for x in (
                    np.minimum(fmin, dmin), np.maximum(fmax, dmax),
                    *stats)))
            snap["rows"] = np.concatenate(rows_p)
            snap["means"] = np.concatenate(means_p)
            snap["weights"] = np.concatenate(weights_p)
            for j, nm in enumerate(("mins", "maxs", "count", "vsum",
                                    "vmin", "vmax", "recip")):
                snap[nm] = np.concatenate([s[j] for s in scalars_p])

        return snap, finish

    @requires_lock("store")
    def snapshot_state(self) -> dict:
        """Slab twin of ``DigestGroup.snapshot_state``: flattened host
        snapshot WITHOUT resetting device state. One-shot begin+finish
        for callers that exclusively own the group."""
        snap, finish = self.snapshot_begin()
        if finish is not None:
            finish()
        return snap

    @requires_lock("store")
    def restore_stats(self, rows: np.ndarray, count: np.ndarray,
                      vsum: np.ndarray, vmin: np.ndarray,
                      vmax: np.ndarray, recip: np.ndarray):
        """Fold recovered per-row scalar stats into the per-slab temp
        accumulators (see ``core.store._restore_temp_stats``; _per_slab
        pads with out-of-range rows, which the scatter drops)."""
        from veneur_tpu.core.store import _restore_temp_stats

        if not len(rows):
            return
        self.ensure_capacity(int(rows.max()))
        self._device_dirty = True
        for i, local, (c, s, mn, mx, rc) in self._per_slab(
                np.asarray(rows, np.int64), np.asarray(count, np.float32),
                np.asarray(vsum, np.float32), np.asarray(vmin, np.float32),
                np.asarray(vmax, np.float32),
                np.asarray(recip, np.float32)):
            self.temps[i] = _restore_temp_stats(
                self.temps[i], jnp.asarray(local), jnp.asarray(c),
                jnp.asarray(s), jnp.asarray(mn), jnp.asarray(mx),
                jnp.asarray(rc))
