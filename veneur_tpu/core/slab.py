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
  * the digest planes can be stored **bfloat16** (``digest_dtype``): all
    kernel math stays f32 (upcast per slab), only storage is rounded.
    Weight rounding perturbs quantile positions by <= 2^-8 relative — far
    inside the t-digest error envelope (eps=.02, histo_test.go:11-25) —
    and exact counts ride the separate f32 scalar stats, so nothing the
    flusher emits as a counter is ever rounded;
  * every device program touches ONE slab (<= 1M rows): peak transient
    memory is slab-sized, and each Pallas operand stays under Mosaic's
    2 GiB (32-bit byte offset) limit.

Capacity plan this buys on one 16 GB v5e-1 (K=104; resident figures
include the round-5 anchor-summary planes, 64 B/row in local mode):

  | series | digest dtype | resident | role |
  |--------|--------------|----------|------|
  |  4M    | f32          |  7.0 GB  | local (samples -> temp -> drain) |
  | 10M    | bf16         | 13.2 GB  | local, the north-star config     |
  | 10M    | bf16, merge  |  4.3 GB  | global (imported digest merges)  |

The 10M local config uses 256k-row slabs: per-slab flush transients
scale with slab rows, and the ~2.3 GB the resident planes leave free
no longer fits 512k-row transients.

The 10M f32 local config needs ~16.7 GB resident and therefore two chips
(or DP sharding via the mesh store, core/mesh_store.py) — that is the
stated path beyond 10M as well: the series axis is embarrassingly
shardable, so N chips multiply every row in this table by N.

Reference behavior re-expressed here: Worker.Flush + Histo.Flush
(flusher.go:134-254, samplers/samplers.go:511-636) for the local role,
ImportMetricGRPC -> tdigest.Merge (worker.go:354-398) for the global one.
"""

from __future__ import annotations

import logging
import math
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


class DigestSlab(NamedTuple):
    """Resident state for one slab of series rows (flat planes).

    count is an EXACT f32 per-series total maintained alongside the
    (possibly bf16) centroid weights: merge-mode flushes report it
    instead of summing rounded weights, so counts never stall on bf16
    round-to-nearest even when a hot centroid's weight ULP exceeds an
    imported batch's contribution. (Local mode reports temp.count, which
    is f32 already; there this plane just rides along.)"""

    mean: jax.Array      # [slab*K] storage dtype; +inf = empty slot
    weight: jax.Array    # [slab*K] storage dtype; 0 = empty slot
    dmin: jax.Array      # [slab] f32 observed minima (+inf when empty)
    dmax: jax.Array      # [slab] f32 observed maxima (-inf when empty)
    count: jax.Array     # [slab] f32 exact total weight


class TempSlab(NamedTuple):
    """Interval accumulators for one slab (local role only), flat planes.
    seg_w/seg_wm: the incremental anchor summary (ops/tdigest.py
    TempCentroids.seg_*), flat [slab*A]."""

    sum_w: jax.Array     # [slab*K] f32
    sum_wm: jax.Array    # [slab*K] f32
    seg_w: jax.Array     # [slab*A] f32
    seg_wm: jax.Array    # [slab*A] f32
    count: jax.Array     # [slab] f32
    vsum: jax.Array      # [slab] f32
    vmin: jax.Array      # [slab] f32
    vmax: jax.Array      # [slab] f32
    recip: jax.Array     # [slab] f32


def _init_digest_slab(slab: int, k: int, dtype) -> DigestSlab:
    return DigestSlab(
        mean=jnp.full((slab * k,), jnp.inf, dtype),
        weight=jnp.zeros((slab * k,), dtype),
        dmin=jnp.full((slab,), jnp.inf, jnp.float32),
        dmax=jnp.full((slab,), -jnp.inf, jnp.float32),
        count=jnp.zeros((slab,), jnp.float32),
    )


def _init_temp_slab(slab: int, k: int) -> TempSlab:
    a = td_ops.BELOW_MASS_ANCHORS
    return TempSlab(
        sum_w=jnp.zeros((slab * k,), jnp.float32),
        sum_wm=jnp.zeros((slab * k,), jnp.float32),
        seg_w=jnp.zeros((slab * a,), jnp.float32),
        seg_wm=jnp.zeros((slab * a,), jnp.float32),
        count=jnp.zeros((slab,), jnp.float32),
        vsum=jnp.zeros((slab,), jnp.float32),
        vmin=jnp.full((slab,), jnp.inf, jnp.float32),
        vmax=jnp.full((slab,), -jnp.inf, jnp.float32),
        recip=jnp.zeros((slab,), jnp.float32),
    )


def _guard_drain_slab(temp: TempSlab, digest: DigestSlab, rows, values,
                      weights, slab: int, compression: float,
                      use_pallas: bool = True):
    """The slab form of ops/tdigest.py's shift guard: when the chunk's
    per-row value ranges are disjoint from what the accumulated bins
    cover for enough chunk mass, drain the bins into the (storage-dtype)
    digest planes first so the fresh bins re-anchor — a lax.cond, so
    stationary traffic pays one cheap reduction, never the drain. Temp
    scalar stats survive (interval aggregates; only the bins move)."""
    k = temp.sum_w.shape[0] // slab
    pred = td_ops.shift_pred(temp.seg_w, temp.seg_wm, rows, values,
                             weights, slab)

    def do_drain(args):
        t, d = args
        dt = d.mean.dtype
        d32 = td_ops.TDigest(
            mean=d.mean.reshape(slab, k).astype(jnp.float32),
            weight=d.weight.reshape(slab, k).astype(jnp.float32),
            min=d.dmin, max=d.dmax)
        # the drain reads the flat bin planes and the scalar stats; the
        # anchors (in the slab's row-major order) ride along unread
        drained = td_ops.drain_temp(d32, td_ops.TempCentroids(*t),
                                    compression, use_pallas=use_pallas)
        d2 = DigestSlab(
            mean=drained.mean.astype(dt).reshape(-1),
            weight=drained.weight.astype(dt).reshape(-1),
            dmin=drained.min, dmax=drained.max, count=d.count)
        t2 = t._replace(sum_w=jnp.zeros_like(t.sum_w),
                        sum_wm=jnp.zeros_like(t.sum_wm),
                        seg_w=jnp.zeros_like(t.seg_w),
                        seg_wm=jnp.zeros_like(t.seg_wm))
        return t2, d2

    return lax.cond(pred, do_drain, lambda a: a, (temp, digest))


@partial(jax.jit, donate_argnums=(0, 1), static_argnums=(5, 6, 7))
def _ingest_slab(temp: TempSlab, digest: DigestSlab, rows, values, weights,
                 slab: int, compression: float, use_pallas: bool = True):
    """Scatter one flat sample chunk into a slab's flat accumulators,
    with the shift guard (returns (temp, digest)).

    rows: [N] LOCAL row ids; anything >= slab is padding / out-of-slab and
    must scatter nowhere (flat index >= slab*K with mode='drop')."""
    k = temp.sum_w.shape[0] // slab
    oor = rows >= slab
    rows = jnp.where(oor, slab, rows)
    weights = jnp.where(oor, 0.0, weights)
    temp, digest = _guard_drain_slab(temp, digest, rows, values, weights,
                                     slab, compression,
                                     use_pallas=use_pallas)
    r, v, w, b = td_ops.bin_flat_samples(
        rows, values, weights, slab, k, compression,
        acc_seg_w=temp.seg_w, acc_seg_wm=temp.seg_wm)
    live = w > 0
    vz = jnp.where(live, v, 0.0)
    a = td_ops.BELOW_MASS_ANCHORS
    flat = jnp.where(r >= slab, slab * k, r * k + b)
    flat_seg = jnp.where(r >= slab, slab * a,
                         r * a + td_ops.seg_of_bins(b, k))
    return TempSlab(
        sum_w=temp.sum_w.at[flat].add(w, mode="drop"),
        sum_wm=temp.sum_wm.at[flat].add(w * vz, mode="drop"),
        seg_w=temp.seg_w.at[flat_seg].add(w, mode="drop"),
        seg_wm=temp.seg_wm.at[flat_seg].add(w * vz, mode="drop"),
        count=temp.count.at[r].add(w, mode="drop"),
        vsum=temp.vsum.at[r].add(w * vz, mode="drop"),
        vmin=temp.vmin.at[r].min(jnp.where(live, v, jnp.inf), mode="drop"),
        vmax=temp.vmax.at[r].max(jnp.where(live, v, -jnp.inf), mode="drop"),
        recip=temp.recip.at[r].add(jnp.where(live, w / v, 0.0), mode="drop"),
    ), digest


@partial(jax.jit, donate_argnums=(0, 1), static_argnums=(8, 9, 10))
def _import_slab(temp: TempSlab, digest: DigestSlab, rows, means, weights,
                 stat_rows, stat_mins, stat_maxs, slab: int,
                 compression: float, use_pallas: bool = True):
    """Fold imported digest CENTROIDS into a slab's accumulators without
    touching the local scalar stats (samplers.go:473-480); imported
    per-digest extrema land on the digest's dmin/dmax planes and only
    bound the final digest."""
    k = temp.sum_w.shape[0] // slab
    oor = rows >= slab
    rows = jnp.where(oor, slab, rows)
    weights = jnp.where(oor, 0.0, weights)
    temp, digest = _guard_drain_slab(temp, digest, rows, means, weights,
                                     slab, compression,
                                     use_pallas=use_pallas)
    r, v, w, b = td_ops.bin_flat_samples(
        rows, means, weights, slab, k, compression,
        acc_seg_w=temp.seg_w, acc_seg_wm=temp.seg_wm)
    live = w > 0
    vz = jnp.where(live, v, 0.0)
    a = td_ops.BELOW_MASS_ANCHORS
    flat = jnp.where(r >= slab, slab * k, r * k + b)
    flat_seg = jnp.where(r >= slab, slab * a,
                         r * a + td_ops.seg_of_bins(b, k))
    temp = temp._replace(
        sum_w=temp.sum_w.at[flat].add(w, mode="drop"),
        sum_wm=temp.sum_wm.at[flat].add(w * vz, mode="drop"),
        seg_w=temp.seg_w.at[flat_seg].add(w, mode="drop"),
        seg_wm=temp.seg_wm.at[flat_seg].add(w * vz, mode="drop"))
    digest = digest._replace(
        dmin=digest.dmin.at[stat_rows].min(stat_mins, mode="drop"),
        dmax=digest.dmax.at[stat_rows].max(stat_maxs, mode="drop"))
    return temp, digest


@partial(jax.jit, donate_argnums=(0, 1), static_argnums=(3, 4, 5, 6, 7))
def _flush_slab(digest: DigestSlab, temp: TempSlab, qs, slab: int,
                compression: float, want_digest: bool = True,
                want_fresh: bool = True, use_pallas: bool = True):
    """Drain one slab's temp into its digests and emit percentiles.

    Returns (fresh empty digest+temp for the next interval — or None/None
    when want_fresh=False: a RETIRED generation's slabs are never reused,
    so skipping the zero-fill lets the donated planes free outright —
    drained digest planes in storage dtype — or None/None when
    want_digest=False, which saves a full-plane cast+write per flush —
    percentiles [slab, P], scalar stats)."""
    k = digest.mean.shape[0] // slab
    dt = digest.mean.dtype
    d = td_ops.TDigest(
        mean=digest.mean.reshape(slab, k).astype(jnp.float32),
        weight=digest.weight.reshape(slab, k).astype(jnp.float32),
        min=digest.dmin, max=digest.dmax)
    # as in _guard_drain_slab: the anchors ride along unread
    t = td_ops.TempCentroids(*temp)
    inf = jnp.full((slab,), jnp.inf, jnp.float32)
    drained, pcts = td_ops.drain_and_quantile(d, t, inf, -inf, qs,
                                              compression,
                                              use_pallas=use_pallas)
    if want_digest:
        out_mean = drained.mean.astype(dt).reshape(-1)
        out_weight = drained.weight.astype(dt).reshape(-1)
    else:
        out_mean = out_weight = None
    if want_fresh:
        fresh_d = _init_digest_slab(slab, k, dt)
        fresh_t = _init_temp_slab(slab, k)
    else:
        fresh_d = fresh_t = None
    return (fresh_d, fresh_t, out_mean, out_weight, drained.min, drained.max,
            pcts, temp.count, temp.vsum, temp.vmin, temp.vmax, temp.recip)


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
    fetches counts first and then only live bytes."""
    m = mean_flat.reshape(slab, k).astype(jnp.float32)
    w = weight_flat.reshape(slab, k).astype(jnp.float32)
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

    pow2 padding bounds the compiled variant count at ~log2 each."""
    counts = np.asarray(jax.device_get(counts_dev[:need]))
    total = int(counts.astype(np.int64).sum())
    if total == 0:
        empty = np.empty(0, np.uint16)
        return counts, empty, empty
    slab, k = q_pref.shape
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
        _gather_pack(counts_dev, q_pref, wb_pref, P)[:total]))
    return counts, (packed >> 16).astype(np.uint16), \
        (packed & 0xFFFF).astype(np.uint16)


@partial(jax.jit, donate_argnums=(0,), static_argnums=(5, 6))
def _merge_slab(digest: DigestSlab, in_mean, in_weight, in_min, in_max,
                slab: int, compression: float) -> DigestSlab:
    """Merge one slab of imported digests into the resident state (the
    global-aggregator path: tdigest.Merge, worker.go:354-398).

    in_mean/in_weight: [slab, M] f32, weight==0 padding; rows need not be
    sorted. in_min/in_max: [slab] f32."""
    k = digest.mean.shape[0] // slab
    dt = digest.mean.dtype
    own_m = digest.mean.reshape(slab, k).astype(jnp.float32)
    own_w = digest.weight.reshape(slab, k).astype(jnp.float32)
    live = in_weight > 0
    key = jnp.where(live, in_mean, jnp.inf)
    key, w_in = lax.sort((key, in_weight), dimension=-1, num_keys=1,
                         is_stable=False)
    new_m, new_w = td_ops._dispatch_compress_presorted(
        own_m, own_w, key, w_in, compression, k)
    return DigestSlab(
        mean=new_m.astype(dt).reshape(-1),
        weight=new_w.astype(dt).reshape(-1),
        dmin=jnp.minimum(digest.dmin, in_min),
        dmax=jnp.maximum(digest.dmax, in_max),
        # exact f32 running total, immune to bf16 weight rounding
        count=digest.count + jnp.sum(jnp.where(live, in_weight, 0.0),
                                     axis=-1),
    )


@partial(jax.jit, donate_argnums=(0,), static_argnums=(2, 3))
def _quantile_slab(digest: DigestSlab, qs, slab: int, compression: float):
    """Flush a merge-mode slab: percentiles + counts from the resident
    digests alone, then reset (the global role has no temp accumulators)."""
    k = digest.mean.shape[0] // slab
    dt = digest.mean.dtype
    d = td_ops.TDigest(
        mean=digest.mean.reshape(slab, k).astype(jnp.float32),
        weight=digest.weight.reshape(slab, k).astype(jnp.float32),
        min=digest.dmin, max=digest.dmax)
    pcts = td_ops.quantile(d, qs)
    return (_init_digest_slab(slab, k, dt), pcts, digest.count, d.min,
            d.max)


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
        self.digest_dtype = jnp.dtype(digest_dtype)
        self.mode = mode
        self.digests: List[DigestSlab] = [
            _init_digest_slab(self.slab_rows, self.k, self.digest_dtype)
            for _ in range(self.num_slabs)]
        self.temps: List[Optional[TempSlab]] = [
            _init_temp_slab(self.slab_rows, self.k) if mode == "local"
            else None
            for _ in range(self.num_slabs)]

    # -- capacity plan ----------------------------------------------------

    def hbm_bytes(self) -> dict:
        """Resident-plane byte accounting (flat planes tile unpadded)."""
        dsz = self.digest_dtype.itemsize
        per_slab_digest = self.slab_rows * self.k * dsz * 2 \
            + self.slab_rows * 4 * 2
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
            self.temps[slab_idx], self.digests[slab_idx] = _ingest_slab(
                self.temps[slab_idx], self.digests[slab_idx],
                jnp.asarray(rows), jnp.asarray(values),
                jnp.asarray(weights), self.slab_rows, self.compression)

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
                self.temps[i], self.digests[i] = _ingest_slab(
                    self.temps[i], self.digests[i], local, values, weights,
                    self.slab_rows, self.compression)

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
                        self.digests[i], self.temps[i], qs, self.slab_rows,
                        self.compression, want_digest)
                    out = {"percentiles": pcts, "count": count,
                           "sum": vsum, "min": vmin, "max": vmax,
                           "recip": recip}
                    if want_digest:
                        out["digest_mean"] = mean
                        out["digest_weight"] = weight
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
from veneur_tpu.core.store import OverloadLimited, fetch_stage  # noqa: E402
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

    Staged chunks are partitioned by slab on the host and padded to
    power-of-two lengths, so each (slab width, chunk pow2) pair compiles
    once — at most ~log2(chunk) program variants per group.
    """

    _retired = False  # see core.store.DigestGroup._retired

    def __init__(self, slab_rows: int = SLAB_ROWS_DEFAULT,
                 chunk: int = 1 << 16,
                 compression: float = td_ops.DEFAULT_COMPRESSION,
                 digest_dtype=jnp.float32):
        from veneur_tpu.core.store import Interner

        self._interner_cls = Interner
        self.interner = Interner()
        self.compression = compression
        self.k = td_ops.size_bound(compression)
        self.chunk = chunk
        if slab_rows <= 0:
            raise ValueError(f"slab_rows must be positive, got {slab_rows}")
        self.slab_rows = min(slab_rows, 1 << 20)
        self.digest_dtype = jnp.dtype(digest_dtype)
        self.digests: List[DigestSlab] = [
            _init_digest_slab(self.slab_rows, self.k, self.digest_dtype)]
        self.temps: List[TempSlab] = [
            _init_temp_slab(self.slab_rows, self.k)]
        self._device_dirty = False
        self._new_sample_buffers()
        self._new_import_buffers()

    # -- capacity ---------------------------------------------------------

    @property
    def capacity(self) -> int:
        return len(self.digests) * self.slab_rows

    def __len__(self):
        return len(self.interner)

    def fresh(self) -> "SlabDigestGroup":
        """Empty same-config twin (swap-on-flush generation swap).
        Starts with ONE slab and re-grows slab-at-a-time as rows intern:
        fresh slabs are zero-fill appends (no copies), and lazy growth
        keeps the flush window's HBM peak at resident + touched-slabs
        instead of a full 2x (the retired generation's slabs free one by
        one as the off-lock flush donates them into its programs)."""
        return SlabDigestGroup(self.slab_rows, self.chunk,
                               self.compression, self.digest_dtype)

    @requires_lock("store")
    def ensure_capacity(self, max_row: int):
        while max_row >= self.capacity:
            self.digests.append(
                _init_digest_slab(self.slab_rows, self.k, self.digest_dtype))
            self.temps.append(_init_temp_slab(self.slab_rows, self.k))
            # stale sentinels from before the grow are harmless (their
            # weights are 0) but re-point them anyway, like DigestGroup
            self._rows[self._fill:] = self.capacity
            self._imp_rows[self._imp_fill:] = self.capacity
            self._imp_stat_rows[self._imp_stat_fill:] = self.capacity

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
        arrays...) padded to power-of-two lengths (bounded jit variants)."""
        slabs = rows // self.slab_rows
        for i in np.unique(slabs):
            if i < 0 or i >= len(self.digests):
                continue  # sentinel padding rows
            sel = slabs == i
            m = int(sel.sum())
            pad = _next_pow2(m)
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
        with obs_kernels.scope("drain.digest.slab"):
            for i, local, (v, w) in self._per_slab(rows, vals, wts):
                self.temps[i], self.digests[i] = _ingest_slab(
                    self.temps[i], self.digests[i], jnp.asarray(local),
                    jnp.asarray(v), jnp.asarray(w), self.slab_rows,
                    self.compression, self._pallas_allowed())

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
        empty_f = np.zeros(2, np.float32)
        empty_r = np.full(2, self.slab_rows, np.int32)
        with obs_kernels.scope("drain.digest.slab"):
            for i in sorted(set(by_slab) | set(stats)):
                c_local, c_pad = by_slab.get(
                    i, (empty_r, [empty_f, empty_f]))
                s_local, s_pad = stats.get(
                    i, (empty_r, [np.full(2, np.inf, np.float32),
                                  np.full(2, -np.inf, np.float32)]))
                self.temps[i], self.digests[i] = _import_slab(
                    self.temps[i], self.digests[i],
                    jnp.asarray(c_local), jnp.asarray(c_pad[0]),
                    jnp.asarray(c_pad[1]), jnp.asarray(s_local),
                    jnp.asarray(s_pad[0]), jnp.asarray(s_pad[1]),
                    self.slab_rows, self.compression,
                    self._pallas_allowed())

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
        self.temps = [_init_temp_slab(self.slab_rows, self.k)
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

    def _flush_empty(self):
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
        interner, self.interner = self.interner, self._interner_cls()
        self._device_dirty = False
        if self._retired:
            # release order: drained device planes first (their donated
            # buffers already freed slab by slab), host staging second
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
        for _ in range(min(window, st["nslabs"])):
            self._dispatch_slab(st)
        return st

    def _dispatch_slab(self, st: dict) -> None:
        """Dispatch one slab's flush program (async) and record its
        fetchable refs in dispatch order."""
        i = st["next"]
        st["next"] = i + 1
        need = min(st["n"] - i * self.slab_rows, self.slab_rows)
        # want_digest=False also skips the device-side cast+write of
        # the drained planes, not just the host fetch; a retired
        # generation additionally skips allocating fresh slabs (its
        # donated planes free outright, slab by slab)
        with obs_kernels.scope("flush.digest.slab"):
            (st["new_digests"][i], st["new_temps"][i], mean, weight,
             dmin, dmax, pcts, count, vsum, vmin, vmax, recip) = \
                _flush_slab(
                    self.digests[i], self.temps[i], st["qs"],
                    self.slab_rows, self.compression,
                    bool(st["want_digests"]), not self._retired,
                    st["use_pallas"])
            if need <= 0:
                st["refs"].append(None)
                return
            k = self.k
            planes = ()
            pk_refs = None
            if st["packed"]:
                pk_refs = _pack_slab(mean, weight, dmin, dmax,
                                     self.slab_rows, k)
                planes = (dmin[:need], dmax[:need])
            elif st["want_digests"]:
                planes = (
                    mean.reshape(self.slab_rows, k)[:need]
                        .astype(jnp.float32),
                    weight.reshape(self.slab_rows, k)[:need]
                          .astype(jnp.float32),
                    dmin[:need], dmax[:need])
            stats = {"pcts": pcts, "count": count, "sum": vsum,
                     "min": vmin, "max": vmax, "recip": recip}
            st["refs"].append(
                (need, pk_refs,
                 planes + tuple(stats[nm][:need] for nm in st["sel"])))

    def _flush_collect(self, st: dict, n: int, percentiles,
                       want_digests) -> dict:
        """Blocking half: fetch each dispatched slab's interned prefix
        in order, dispatching slab j+window while slab j's fetch
        blocks — device execution overlaps the host transfer instead
        of idling behind it."""
        window = max(1, getattr(self, "_pipeline_window", 1))
        parts = []
        pk_counts, pk_means, pk_wts = [], [], []
        for j in range(st["nslabs"]):
            while st["next"] < st["nslabs"] and st["next"] - j < window:
                self._dispatch_slab(st)
            ref = st["refs"][j]
            if ref is None:
                continue
            need, pk_refs, refs = ref
            st["refs"][j] = None  # drop the fetched slab's refs promptly
            with fetch_stage((pk_refs, refs)):
                if st["packed"]:
                    c_h, pm_h, pw_h = _fetch_packed(*pk_refs, need)
                    pk_counts.append(c_h)
                    pk_means.append(pm_h)
                    pk_wts.append(pw_h)
                parts.append(jax.device_get(refs))
        cols = [np.concatenate(c, axis=0) for c in zip(*parts)]
        # every slab's program + fetch succeeded: commit the fresh planes
        self.digests, self.temps = st["new_digests"], st["new_temps"]
        out = {}
        if st["packed"]:
            out["digest_min"], out["digest_max"] = cols[:2]
            cols = cols[2:]
            out["packed_counts"] = np.concatenate(pk_counts)
            out["packed_means"] = np.concatenate(pk_means)
            out["packed_weights"] = np.concatenate(pk_wts)
        elif want_digests:
            (out["digest_mean"], out["digest_weight"], out["digest_min"],
             out["digest_max"]) = cols[:4]
            cols = cols[4:]
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
            need = min(n - i * self.slab_rows, self.slab_rows)
            if need <= 0:
                break
            t = self.temps[i]
            slab_refs.append((i, (
                d.mean.reshape(self.slab_rows, k)[:need],
                d.weight.reshape(self.slab_rows, k)[:need],
                t.sum_w.reshape(self.slab_rows, k)[:need],
                t.sum_wm.reshape(self.slab_rows, k)[:need],
                d.dmin[:need], d.dmax[:need], t.count[:need],
                t.vsum[:need], t.vmin[:need], t.vmax[:need],
                t.recip[:need])))

        def finish():
            from veneur_tpu.core.store import flatten_digest_state

            rows_p, means_p, weights_p, scalars_p = [], [], [], []
            for i, refs in slab_refs:
                (mean, weight, bin_w, bin_wm, dmn, dmx, cnt, vsum, vmin,
                 vmax, recip) = jax.device_get(refs)
                flat = flatten_digest_state(
                    np.asarray(mean, np.float32),
                    np.asarray(weight, np.float32),
                    np.asarray(bin_w, np.float32),
                    np.asarray(bin_wm, np.float32))
                rows_p.append(flat["rows"] + np.int32(i * self.slab_rows))
                means_p.append(flat["means"])
                weights_p.append(flat["weights"])
                scalars_p.append((np.asarray(dmn, np.float32),
                                  np.asarray(dmx, np.float32),
                                  np.asarray(cnt, np.float32),
                                  np.asarray(vsum, np.float32),
                                  np.asarray(vmin, np.float32),
                                  np.asarray(vmax, np.float32),
                                  np.asarray(recip, np.float32)))
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
