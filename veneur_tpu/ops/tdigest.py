"""Batched merging t-digest as dense XLA tensor ops.

The reference implementation (Dunning's merging t-digest,
``/root/reference/tdigest/merging_digest.go``) maintains, per metric series, a
sorted list of (mean, weight) centroids and merges new samples with an
inherently sequential greedy scan (``mergeAllTemps``, ``merging_digest.go:135``)
that walks centroids in mean order and fuses neighbours while the k-scale index
``k(q) = C * (asin(2q-1)/pi + 1/2)`` (``merging_digest.go:254-257``) advances by
less than one.

That scan does not vectorise. This module re-derives the merge for TPU as a
data-parallel program over *all* series at once:

    1. sort         -- per-row sort of the concatenated centroid/sample list
    2. prefix sum   -- cumulative weight gives each centroid its quantile q
    3. k-binning    -- cluster id = floor(k(q_mid)); k-width of every cluster
                       is <= 1, the same invariant the greedy scan enforces
    4. segmented reduce -- per-cluster weight and weighted-mean via two more
                       prefix sums + a row-wise binary search over the
                       (monotone) cluster ids

Everything is fixed-shape: a digest is a ``[..., K]`` pair of mean/weight
arrays (weight==0 marks an empty slot), so the whole state for S series is a
dense ``[S, K]`` tensor that jit/vmap/shard_map can slice across a device mesh.
Quantile/CDF queries mirror the uniform-centroid interpolation of the
reference (``merging_digest.go:261-327``) as gathers over cumulative weights.

Accuracy contract: same k-scale, same size bound (ceil(pi*C/2) slots), so
quantile error stays within the documented t-digest bounds used by the
reference's tests (eps=0.02, ``tdigest/histo_test.go:11-25``).
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax import lax

DEFAULT_COMPRESSION = 100.0


def size_bound(compression: float) -> int:
    """Slots a digest needs under this module's floor(k) binning, rounded up
    to a multiple of 8 for TPU sublane alignment.

    The reference's greedy scan can pack up to ceil(pi*C/2) centroids
    (merging_digest.go:66-68); our re-derivation assigns cluster id
    floor(k(q_mid)) with k in [0, C), so at most C+1 bins are ever live
    (+1 more of fp headroom: the clipped asin can round k to exactly C).
    Tighter rows mean ~35% less HBM per digest plane and a narrower
    bitonic merge in the Pallas kernel, with bit-identical results: the
    extra slots were provably always empty."""
    raw = int(compression) + 2
    return (raw + 7) // 8 * 8


def temp_buffer_size(compression: float) -> int:
    """Heuristic ingest-buffer size per merge pass (merging_digest.go:101-107),
    rounded up to a multiple of 8."""
    c = min(925.0, max(20.0, compression))
    raw = int(7.5 + 0.37 * c - 2e-4 * c * c)
    return (raw + 7) // 8 * 8


class TDigest(NamedTuple):
    """A batch of t-digests as dense arrays.

    mean / weight: ``[..., K]``; liveness is defined SOLELY by
    weight > 0. Live means ascend within a row, but dead slots may sit
    anywhere with any placeholder mean (+inf from the sort-based compress,
    gap-filled running-max values or -inf from the Pallas compress) —
    consumers must mask on weight, never on the mean.
    min / max: ``[...]`` observed extrema (+inf/-inf when empty).
    """

    mean: jax.Array
    weight: jax.Array
    min: jax.Array
    max: jax.Array

    @property
    def batch_shape(self):
        return self.mean.shape[:-1]

    @property
    def capacity(self) -> int:
        return self.mean.shape[-1]

    def count(self) -> jax.Array:
        return jnp.sum(self.weight, axis=-1)


def init(batch_shape: Sequence[int] = (), compression: float = DEFAULT_COMPRESSION,
         capacity: int | None = None, dtype=jnp.float32) -> TDigest:
    """Create empty digests for a batch of series."""
    k = capacity if capacity is not None else size_bound(compression)
    shape = tuple(batch_shape)
    return TDigest(
        mean=jnp.full(shape + (k,), jnp.inf, dtype),
        weight=jnp.zeros(shape + (k,), dtype),
        min=jnp.full(shape, jnp.inf, dtype),
        max=jnp.full(shape, -jnp.inf, dtype),
    )


def _shift_last(x: jax.Array, d: int, fill) -> jax.Array:
    """out[..., i] = x[..., i-d], left-filled — building block for the
    log-step cumulative ops below."""
    pad_shape = x.shape[:-1] + (d,)
    pad = jnp.full(pad_shape, fill, x.dtype)
    return jnp.concatenate([pad, x[..., :-d]], axis=-1)


def _cumsum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum along the last axis via log-step shifted adds.
    XLA lowers cumsum through reduce-window on TPU, which for the short
    trailing axes used here costs ~10x more than these O(log n) passes."""
    d, n = 1, x.shape[-1]
    while d < n:
        x = x + _shift_last(x, d, 0)
        d *= 2
    return x


def _cummax(x: jax.Array) -> jax.Array:
    """Inclusive running max along the last axis (log-step)."""
    d, n = 1, x.shape[-1]
    while d < n:
        x = jnp.maximum(x, _shift_last(x, d, -jnp.inf))
        d *= 2
    return x


def _cummin_rev(x: jax.Array) -> jax.Array:
    """Suffix (right-to-left) running min along the last axis, without the
    flip-materializing lax.cummin formulation."""
    d, n = 1, x.shape[-1]
    while d < n:
        shifted = jnp.concatenate(
            [x[..., d:], jnp.full(x.shape[:-1] + (d,), jnp.inf, x.dtype)],
            axis=-1)
        x = jnp.minimum(x, shifted)
        d *= 2
    return x


def _rowwise_searchsorted(a: jax.Array, v: jax.Array, side: str) -> jax.Array:
    """searchsorted along the last axis for every row of a batch.

    a: [..., M] row-sorted values; v: [..., P] (or [P], broadcast) queries.

    Computed as a fused broadcast-compare-reduce (count of elements before
    the insertion point) rather than a vmapped binary search: the scan-based
    search lowers to ~1000x slower code on TPU, while the [.., P, M] compare
    fuses into one VPU reduction and never materializes.
    """
    batch = a.shape[:-1]
    if v.ndim == 1:
        v = jnp.broadcast_to(v, batch + v.shape)
    av = a[..., None, :]          # [..., 1, M]
    vv = v[..., :, None]          # [..., P, 1]
    before = (av < vv) if side == "left" else (av <= vv)
    return jnp.sum(before, axis=-1, dtype=jnp.int32)


def _select_at(arr: jax.Array, idx: jax.Array) -> jax.Array:
    """Fused per-row gather: out[..., p] = arr[..., idx[..., p]].

    arr: [..., M]; idx: [..., P] int32. TPU's native row-gather
    (take_along_axis) runs ~20x slower than this one-hot compare+reduce for
    small P, which fuses into a single VPU pass and never materializes the
    [..., P, M] intermediate.
    """
    return _select_many_at([arr], idx)[0]


def _select_many_at(arrs: Sequence[jax.Array], idx: jax.Array):
    """_select_at for several arrays sharing one index set: the one-hot
    compare is computed once and reused for every gather."""
    m = arrs[0].shape[-1]
    pos = jnp.arange(m, dtype=jnp.int32)
    hit = idx[..., :, None] == pos        # [..., P, M]
    return [jnp.sum(jnp.where(hit, a[..., None, :], 0), axis=-1)
            for a in arrs]


def _compress(mean: jax.Array, weight: jax.Array, compression: float,
              out_size: int) -> tuple[jax.Array, jax.Array]:
    """Re-cluster per-row centroid lists down to <= out_size centroids.

    mean/weight: [..., M] unsorted; weight==0 slots ignored. Returns sorted,
    front-compacted [..., out_size] arrays (empty slots mean=+inf, weight=0).
    """
    dtype = mean.dtype
    live = weight > 0
    key = jnp.where(live, mean, jnp.inf)
    # Sort each row by mean; empties ride to the back.
    key, w = lax.sort((key, weight), dimension=-1, num_keys=1, is_stable=True)
    live = w > 0
    m0 = jnp.where(live, key, 0.0)  # inf*0 would poison the weighted sums

    incl = _cumsum(w)
    total = incl[..., -1:]
    safe_total = jnp.maximum(total, jnp.finfo(dtype).tiny)
    q_mid = (incl - 0.5 * w) / safe_total
    # k-scale (merging_digest.go:254-257); arcsin arg clipped for fp safety.
    k = compression * (jnp.arcsin(jnp.clip(2.0 * q_mid - 1.0, -1.0, 1.0)) / jnp.pi + 0.5)
    cluster = jnp.clip(jnp.floor(k), 0, out_size - 1).astype(jnp.int32)
    cluster = jnp.where(live, cluster, out_size)  # park empties out of range

    # Segmented sums per cluster id as a fused mask-reduce: the [.., K, M]
    # compare broadcasts fuse into one VPU reduction. (The boundary-gather
    # formulation — prefix sums + searchsorted + take_along_axis — is ~20x
    # slower on TPU because row-gathers don't vectorize.)
    targets = jnp.arange(out_size, dtype=jnp.int32)
    hit = cluster[..., None, :] == targets[:, None]          # [.., K, M]
    sum_w = jnp.sum(jnp.where(hit, w[..., None, :], 0), axis=-1)
    sum_wm = jnp.sum(jnp.where(hit, (w * m0)[..., None, :], 0), axis=-1)

    new_live = sum_w > 0
    new_mean = jnp.where(new_live, sum_wm / jnp.where(new_live, sum_w, 1.0), jnp.inf)
    # Bins that floor(k) skipped are empty and interleave; one more sort
    # compacts live centroids (already in ascending mean order) to the front.
    new_mean, new_w = lax.sort((new_mean, sum_w), dimension=-1, num_keys=1, is_stable=True)
    return new_mean, new_w


def _dispatch_compress_presorted(mean_a, weight_a, mean_b, weight_b,
                                 compression: float, out_size: int,
                                 sort_b: bool = False,
                                 use_pallas: bool = True):
    """Compress the union of a row-ASCENDING centroid list with a second
    list (ascending, or any order with sort_b=True and +inf empties):
    the fused Pallas merge kernel on TPU, the sort-based _compress
    elsewhere (which orders everything itself). ``use_pallas=False``
    forces the sort-based path even on TPU — the compute breaker's
    fallback rung (resilience/compute.py); trace-time static, so each
    value compiles its own program variant."""
    from veneur_tpu.ops import tdigest_pallas

    if use_pallas and tdigest_pallas.pallas_ok(mean_a):
        return tdigest_pallas.compress_presorted(
            mean_a, weight_a, mean_b, weight_b, compression, out_size,
            sort_b=sort_b)
    mean = jnp.concatenate([mean_a, mean_b], axis=-1)
    weight = jnp.concatenate([weight_a, weight_b], axis=-1)
    return _compress(mean, weight, compression, out_size)


def merge_samples(state: TDigest, values: jax.Array, weights: jax.Array,
                  compression: float = DEFAULT_COMPRESSION) -> TDigest:
    """Fold a padded batch of raw samples into every digest.

    values/weights: [..., T]; weight==0 marks padding. The TPU analogue of
    draining tempCentroids (merging_digest.go:111-132 + mergeAllTemps).
    """
    values = values.astype(state.mean.dtype)
    weights = weights.astype(state.weight.dtype)
    live = weights > 0
    vmin = jnp.min(jnp.where(live, values, jnp.inf), axis=-1)
    vmax = jnp.max(jnp.where(live, values, -jnp.inf), axis=-1)
    mean = jnp.concatenate([state.mean, jnp.where(live, values, jnp.inf)], axis=-1)
    weight = jnp.concatenate([state.weight, weights], axis=-1)
    new_mean, new_weight = _compress(mean, weight, compression, state.capacity)
    return TDigest(
        mean=new_mean,
        weight=new_weight,
        min=jnp.minimum(state.min, vmin),
        max=jnp.maximum(state.max, vmax),
    )


def merge(a: TDigest, b: TDigest, compression: float = DEFAULT_COMPRESSION) -> TDigest:
    """Merge digest batches elementwise: the associative op behind the global
    aggregation tree (samplers.Histo.Combine / Merge, samplers.go:657-691).

    Deterministic (sorted merge order) unlike the reference's shuffled re-add
    (merging_digest.go:358-370); accuracy bound is the same.
    """
    new_mean, new_weight = _dispatch_compress_presorted(
        a.mean, a.weight, b.mean, b.weight, compression, a.capacity)
    return TDigest(
        mean=new_mean,
        weight=new_weight,
        min=jnp.minimum(a.min, b.min),
        max=jnp.maximum(a.max, b.max),
    )


def _upper_bounds(state: TDigest) -> jax.Array:
    """Per-centroid upper bound: midpoint to the next live centroid, or max
    for the last live one (merging_digest.go:339-354). [..., K].

    Rows may contain weight==0 gap slots anywhere (the compress skips the
    compaction sort), so "next" means the next LIVE centroid: a reversed
    running min over masked means, which the ascending-row invariant makes
    exact."""
    m, w = state.mean, state.weight
    live = w > 0
    masked = jnp.where(live, m, jnp.inf)
    suffix = _cummin_rev(masked)
    next_m = jnp.concatenate(
        [suffix[..., 1:], jnp.full_like(suffix[..., :1], jnp.inf)], axis=-1)
    mx = state.max[..., None]
    live_ub = jnp.where(jnp.isfinite(next_m), 0.5 * (m + next_m), mx)
    # gaps inherit the previous live slot's bound (leading gaps get -inf,
    # below every query) so cumulative searches stay monotone
    gapped = jnp.where(live, live_ub, -jnp.inf)
    return _cummax(gapped)


def quantile(state: TDigest, qs: jax.Array) -> jax.Array:
    """Batched inverse-CDF (merging_digest.go:297-327).

    qs: [P] in [0, 1] (shared across the batch). Returns [..., P]; NaN for
    empty digests.
    """
    qs = jnp.asarray(qs, state.mean.dtype)
    w = state.weight
    incl = _cumsum(w)                                   # [..., K]
    total = incl[..., -1:]                              # [..., 1]
    excl = incl - w
    ub = _upper_bounds(state)
    target = qs * total                                  # [..., P]
    # First centroid i with incl[i] >= target  <=>  Go's q <= weightSoFar + c.W
    idx = jnp.clip(_rowwise_searchsorted(incl, target, "left"), 0, state.capacity - 1)
    lb0 = state.min[..., None]
    # ub shifted right one slot: gathering it at idx yields ub[idx-1]
    ub_prev = jnp.concatenate([ub[..., :1], ub[..., :-1]], axis=-1)
    ub_i, prev_ub, w_i, excl_i = _select_many_at([ub, ub_prev, w, excl], idx)
    # leading gap slots carry ub == -inf; a query landing in the first
    # live centroid must fall back to min, not -inf
    lb = jnp.where(idx == 0, lb0, jnp.maximum(prev_ub, lb0))
    prop = (target - excl_i) / jnp.where(w_i > 0, w_i, 1.0)
    out = lb + prop * (ub_i - lb)
    return jnp.where(total > 0, out, jnp.nan)


def cdf(state: TDigest, xs: jax.Array) -> jax.Array:
    """Batched CDF (merging_digest.go:261-293). xs: [P] shared queries.
    Returns [..., P]; NaN for empty digests."""
    xs = jnp.asarray(xs, state.mean.dtype)
    w = state.weight
    incl = _cumsum(w)
    total = incl[..., -1:]
    excl = incl - w
    ub = _upper_bounds(state)
    # First centroid whose upper bound exceeds x (the one x falls inside).
    idx = jnp.clip(_rowwise_searchsorted(ub, xs, "right"), 0, state.capacity - 1)
    mn = state.min[..., None]
    mx = state.max[..., None]
    ub_prev = jnp.concatenate([ub[..., :1], ub[..., :-1]], axis=-1)
    ub_i, prev_ub, w_i, excl_i = _select_many_at([ub, ub_prev, w, excl], idx)
    lb = jnp.where(idx == 0, mn, jnp.maximum(prev_ub, mn))
    span = ub_i - lb
    frac = jnp.where(span > 0, (xs - lb) / jnp.where(span > 0, span, 1.0), 0.0)
    est = (excl_i + w_i * frac) / jnp.maximum(total, jnp.finfo(w.dtype).tiny)
    est = jnp.where(xs <= mn, 0.0, est)
    est = jnp.where(xs >= mx, 1.0, est)
    return jnp.where(total > 0, est, jnp.nan)


# 8 anchors = 64 B/row of f32 summary state: the 10M-series bf16
# capacity plan (core/slab.py) has ~3 GB of headroom, and 32 anchors'
# 256 B/row (2.6 GB at 10M) blew it — measured as RESOURCE_EXHAUSTED
# across the 10M bench configs. f32 stays: bf16 scatter-adds stop
# accumulating once a segment's mass crosses ~2^8 (8 mantissa bits),
# which would silently re-chunk-relativize the anchoring for hot rows.
BELOW_MASS_ANCHORS = 8


def seg_of_bins(bins: jax.Array, capacity: int) -> jax.Array:
    """Map k-bin ids onto the BELOW_MASS_ANCHORS quantile segments of
    the incremental anchor summary (seg planes in TempCentroids)."""
    return (bins * BELOW_MASS_ANCHORS) // max(capacity, 1)


def bin_flat_samples(rows: jax.Array, values: jax.Array, weights: jax.Array,
                     num_series: int, capacity: int,
                     compression: float = DEFAULT_COMPRESSION,
                     acc_seg_w: jax.Array | None = None,
                     acc_seg_wm: jax.Array | None = None,
                     acc_anchors: int = BELOW_MASS_ANCHORS):
    """Pre-cluster a flat batch of (row, value, weight) samples into k-bins.

    The streaming-ingest half of the TPU t-digest: instead of a per-digest
    temp buffer drained by a sequential scan (merging_digest.go:111-219),
    a whole chunk of samples — any mix of series, any skew — is

        1. sorted by (row, value),
        2. given within-row quantiles via one global prefix sum plus a
           cummax-propagated segment base (no data-dependent shapes),
        3. assigned cluster id floor(k(q_mid)) under the same k-scale the
           reference uses, so every bin spans k-width <= 1.

    rows: [N] int32 in [0, num_series); padding entries must use
    ``rows == num_series`` (they sort to the back and scatter with
    mode='drop'). Returns (rows, values, weights, bins) sorted by row.

    acc_seg_w / acc_seg_wm ([S, A] or flat [S*A], A=BELOW_MASS_ANCHORS):
    the temp's INCREMENTAL anchor summary as accumulated BEFORE this
    chunk (TempCentroids.seg_w/seg_wm — maintained by two extra
    scatters per ingest, so the correction never re-reads the full
    [S, K] bin planes). When given, each sample's quantile is
    estimated against the accumulated-plus-chunk distribution
    (interpolated below-mass from the summary + the exact within-chunk
    rank), so bins stay VALUE-COHERENT across chunks. Without the
    correction, bin ids are chunk-relative, and ordered arrival (a
    sorted replay, a step change, a strong in-interval trend) aliases
    low early values with high late values in the same bin — measured
    up to 0.44 rank error in the accuracy sweep
    (analysis/tdigest_sweep.py, the regression this argument fixes).
    On the first chunk the summary is empty and the behavior is
    exactly the uncorrected one.
    """
    values = values.astype(jnp.float32)
    weights = weights.astype(jnp.float32)
    r, v, w = lax.sort((rows, values, weights), dimension=-1, num_keys=2,
                       is_stable=False)
    cw = _cumsum(w)
    excl = cw - w
    seg_start = jnp.concatenate(
        [jnp.ones((1,), bool), r[1:] != r[:-1]])
    base = jnp.where(seg_start, excl, -jnp.inf)
    base = _cummax(base)
    q_excl = excl - base
    totals = jnp.zeros((num_series + 1,), w.dtype).at[r].add(w, mode="drop")
    tot = jnp.maximum(totals[jnp.minimum(r, num_series)], jnp.finfo(w.dtype).tiny)
    if acc_seg_w is not None:
        below, acc_tot = _acc_below_mass(
            r, v, acc_seg_w, acc_seg_wm, num_series, acc_anchors)
        q_mid = (below + q_excl + 0.5 * w) / jnp.maximum(
            tot + acc_tot, jnp.finfo(w.dtype).tiny)
    else:
        q_mid = (q_excl + 0.5 * w) / tot
    k = compression * (jnp.arcsin(jnp.clip(2.0 * q_mid - 1.0, -1.0, 1.0)) / jnp.pi + 0.5)
    bins = jnp.clip(jnp.floor(k), 0, capacity - 1).astype(jnp.int32)
    return r, v, w, bins


def _packed_below_mass(r: jax.Array, v: jax.Array, mq: jax.Array,
                       wb: jax.Array, fmin: jax.Array, fmax: jax.Array,
                       num_series: int, capacity: int):
    """Per-sample accumulated mass below its value from the PACKED
    centroid planes (step attribution at centroid granularity — the
    pool compression keeps centroid mass within the k-scale envelope,
    so the half-centroid error is the same bound a t-digest admits).
    Gathers only the chunk's rows before dequantizing: [N, PK] work,
    the same cost class as the bracket compares."""
    rc = jnp.minimum(r, num_series - 1)
    pm, pw = dequantize_centroids(
        mq.reshape(num_series, capacity)[rc],
        wb.reshape(num_series, capacity)[rc], fmin[rc], fmax[rc])
    live = pw > 0
    below = (jnp.sum(jnp.where(live & (pm < v[:, None]), pw, 0.0), axis=1)
             + 0.5 * jnp.sum(jnp.where(live & (pm == v[:, None]), pw, 0.0),
                             axis=1))
    ptot = jnp.sum(jnp.where(live, pw, 0.0), axis=1)
    return below, ptot


def bin_pool_samples(rows: jax.Array, values: jax.Array,
                     weights: jax.Array, num_series: int, capacity: int,
                     compression: float, acc_w: jax.Array,
                     acc_wm: jax.Array, mq: jax.Array | None = None,
                     wb: jax.Array | None = None,
                     fmin: jax.Array | None = None,
                     fmax: jax.Array | None = None):
    """Pool-tier binning: value-bracketed against the row's LIVE bin
    means for sparse arrival, merged-rank quantile-anchored when the
    chunk itself dominates the row's accumulated mass.

    The dense/slab temps bin by estimated global quantile against an
    [S, A] anchor *summary* (``bin_flat_samples``) — fine at K=48,
    where the k-scale leaves slack between consecutive order
    statistics. The tiered pool's PK (16) bins are too coarse for
    that: under one-sample-per-row chunks (the realistic fleet
    arrival shape) consecutive samples arrive with nearly the same
    *estimated* quantile, so value-distant samples alias into the same
    bin — measured up to 0.75 rank error on 4-sample rows. But in the
    pool the bins ARE the anchors (A == PK == capacity), so each
    sample can be placed directly against the live bin means instead:
    find the bracketing live bins (lo, hi), then

      * room in between -> value-interpolated bin inside the open gap
        (rows with <= PK spread-out samples get exact singleton bins),
      * no room -> the nearer-by-value neighbor (local smearing only,
        the same bound a t-digest centroid admits),
      * outside the envelope (a new row min/max) -> BISECT the open
        side's bin range: the quantile estimate would place every new
        extreme hard against the last-placed bin (estimated quantiles
        of consecutive order statistics nearly coincide), exhausting
        the side after two arrivals; halving the remaining range
        instead supports log2 more distinct extremes before any
        sharing, and keeps interior room for in-between arrivals,
      * empty summary -> the quantile-anchored bin (the first chunk
        degrades to exactly the uncorrected behavior, where the
        within-chunk ranks are exact).

    Value-bracketing exists to compensate for the MISSING relative-rank
    information of chunk-solo samples; when one chunk carries more of a
    row's mass than everything accumulated so far (a ramping series
    about to cross the promotion bar, the refill after a guard drain, a
    demotion re-import of a whole centroid run), the exact within-chunk
    ranks ARE that information, and the bracket scheme fails in the
    opposite direction: every sample of the run brackets against the
    same PRE-chunk state, so a run of new maxima all bisect onto the
    same bin (measured as a 43%-of-row-mass clump on promoted rows in
    the 2g bench shape). Such rows use the merged-rank estimate
    (accumulated below-mass + exact within-chunk rank) instead.

    The accumulated mass feeding both the estimate and the dominance
    test includes the PACKED planes (mq/wb/fmin/fmax, when given):
    after a guard drain compacts the bins the row's history lives
    there, and binning as though the row were empty re-anchored every
    post-drain arrival chunk-relative — the blindness that made the
    drain's "re-anchor" hurt the rows it meant to help.

    Bin ids track the k-scale position only approximately under the
    mixed scheme; the below-mass summary tolerates transient
    non-monotonicity (cummax) and the flush compact re-sorts bins by
    value, so correctness never depends on id order. All extra work is
    [N, PK] compares + reductions, the same cost class as the
    below-mass correction itself.

    Returns (rows, values, weights, bins) sorted by row, like
    ``bin_flat_samples``.
    """
    values = values.astype(jnp.float32)
    weights = weights.astype(jnp.float32)
    r, v, w = lax.sort((rows, values, weights), dimension=-1, num_keys=2,
                       is_stable=False)
    cw = _cumsum(w)
    excl = cw - w
    seg_start = jnp.concatenate([jnp.ones((1,), bool), r[1:] != r[:-1]])
    base = _cummax(jnp.where(seg_start, excl, -jnp.inf))
    q_excl = excl - base
    totals = jnp.zeros((num_series + 1,), w.dtype).at[r].add(w, mode="drop")
    tot = totals[jnp.minimum(r, num_series)]
    below, acc_tot = _acc_below_mass(r, v, acc_w, acc_wm, num_series,
                                     capacity)
    if mq is not None:
        pbelow, ptot = _packed_below_mass(r, v, mq, wb, fmin, fmax,
                                          num_series, capacity)
        below = below + pbelow
        acc_tot = acc_tot + ptot
    q_mid = (below + q_excl + 0.5 * w) / jnp.maximum(
        tot + acc_tot, jnp.finfo(w.dtype).tiny)
    kk = compression * (jnp.arcsin(jnp.clip(2.0 * q_mid - 1.0, -1.0, 1.0))
                        / jnp.pi + 0.5)
    qb = jnp.clip(jnp.floor(kk), 0, capacity - 1).astype(jnp.int32)
    a_w = acc_w.reshape(num_series, capacity)
    a_wm = acc_wm.reshape(num_series, capacity)
    live = a_w > 0
    means = jnp.where(live, a_wm / jnp.where(live, a_w, 1.0), jnp.nan)
    rc = jnp.minimum(r, num_series - 1)
    m_r = means[rc]                                   # [N, PK]
    live_r = live[rc]
    idx = jnp.arange(capacity, dtype=jnp.int32)
    below = live_r & (m_r < v[:, None])
    above = live_r & (m_r > v[:, None])
    lo = jnp.max(jnp.where(below, idx, -1), axis=1)
    hi = jnp.min(jnp.where(above, idx, capacity), axis=1)
    m_lo = jnp.max(jnp.where(below, m_r, -jnp.inf), axis=1)
    m_hi = jnp.min(jnp.where(above, m_r, jnp.inf), axis=1)
    gap = hi - lo - 1                                 # free/equal bins
    span = m_hi - m_lo
    interp_ok = jnp.isfinite(span) & (span > 0)
    frac = jnp.clip((v - m_lo) / jnp.where(interp_ok, span, 1.0),
                    0.0, 1.0)
    # frac 0 -> first free bin, 1 -> last; while >= 3 free bins remain,
    # keep off the bins ADJACENT to the brackets — placing v flush
    # against a bracket forecloses the whole value range between them
    # for later arrivals (the repeated two-samples-merge failures the
    # 4-sample rank-error sweep caught all reduce to this)
    off = jnp.round(frac * (gap - 1).astype(v.dtype)).astype(jnp.int32)
    off = jnp.clip(off, jnp.where(gap >= 3, 1, 0),
                   jnp.where(gap >= 3, gap - 2, gap - 1))
    b_interp = lo + 1 + off
    low_open = (lo < 0) & (hi < capacity)     # new row minimum
    high_open = (lo >= 0) & (hi >= capacity)  # new row maximum
    b_onesided = jnp.where(low_open, (hi - 1) // 2, (lo + capacity) // 2)
    b_room = jnp.where(interp_ok, b_interp,
                       jnp.where(low_open | high_open, b_onesided, qb))
    b_room = jnp.clip(b_room, lo + 1, hi - 1)
    # gap == 0: share with the nearer-by-value neighbor — UNLESS that
    # bin already holds more than the k-scale mid-q envelope
    # (~2*total/C) and the other bracket is lighter, in which case the
    # lighter bracket takes it. Nearest-only sharing has no mass cap:
    # under chunk-solo arrival a mode-concentrated distribution piles
    # every mid sample onto the single bin nearest the mode (measured
    # 7/44 of a promoted row's mass on one bin = 0.16 rank error at the
    # median, past the envelope the flush compact maintains). Both
    # brackets span the same value interval, so the switch stays the
    # local smearing a t-digest centroid admits; singleton/balanced
    # bins never switch (strict <), and the -inf/inf sentinels push a
    # value outside a one-sided envelope onto the single live
    # bracketing bin (never onto a dead side).
    wt_r = a_w[rc]
    w_lo = jnp.take_along_axis(wt_r, jnp.clip(lo, 0, capacity - 1)[:, None],
                               1)[:, 0]
    w_hi = jnp.take_along_axis(wt_r, jnp.clip(hi, 0, capacity - 1)[:, None],
                               1)[:, 0]
    nearer_lo = (v - m_lo) <= (m_hi - v)
    w_near = jnp.where(nearer_lo, w_lo, w_hi)
    w_far = jnp.where(nearer_lo, w_hi, w_lo)
    cap_w = 2.0 * (tot + acc_tot) / compression
    switch = ((lo >= 0) & (hi < capacity) & (w_near + w > cap_w)
              & (w_far < w_near))
    b_full = jnp.where(nearer_lo ^ switch, lo, hi)
    b = jnp.where(gap >= 1, b_room, b_full)
    # chunk-dominant rows: the exact within-chunk ranks carry more
    # information than the bracket state every run member shares
    # (tot/acc_tot are row-level, so the whole run switches together);
    # strict > keeps a second chunk-solo sample on the bracket path
    b = jnp.where(tot > acc_tot, qb, b)
    return r, v, w, jnp.clip(b, 0, capacity - 1).astype(jnp.int32)


def _acc_below_mass(r: jax.Array, v: jax.Array, acc_seg_w: jax.Array,
                    acc_seg_wm: jax.Array, num_series: int,
                    anchors: int = BELOW_MASS_ANCHORS):
    """Per-sample accumulated mass below its value, from the temp's
    incremental ``anchors``-segment summary (BELOW_MASS_ANCHORS for the
    dense/slab temps; the tiered pool passes its own bin planes, whose
    per-bin means are quantile-ordered by the same construction).

    Segments are quantile-ordered by construction (every previous
    chunk was binned by estimated global quantile and its mass
    scattered into seg_of_bins segments), so a cummax over the A
    segment means gives a monotone coarse CDF; LINEAR interpolation
    inside the segment a value falls in keeps the estimate sharp for
    stationary traffic (a step attribution would smear bins by a whole
    segment's mass as the accumulated total grows). All work is
    [S, A] + [N, A] — the full [S, K] bin planes are never read.

    Returns (below [N], acc_total [N]) with zeros for rows that have
    accumulated nothing (first chunk == uncorrected behavior).
    """
    a_w = acc_seg_w.reshape(num_series, anchors)
    a_wm = acc_seg_wm.reshape(num_series, anchors)
    live = a_w > 0
    means = jnp.where(live, a_wm / jnp.where(live, a_w, 1.0), -jnp.inf)
    mono = jax.lax.cummax(means, axis=1)              # [S, A] envelope
    rc = jnp.minimum(r, num_series - 1)
    s_mean = mono[rc]                                 # [N, A]
    s_dw = a_w[rc]                                    # [N, A]
    # segment j spans (mean_{j-1}, mean_j]; its mass counts fully below
    # v when v clears the segment, fractionally (linear in value) when
    # v falls inside it. -inf lower bounds (leading empty segments)
    # degrade to the step attribution.
    s_prev = jnp.concatenate(
        [jnp.full_like(s_mean[:, :1], -jnp.inf), s_mean[:, :-1]], axis=1)
    span = s_mean - s_prev
    frac = jnp.where(
        jnp.isfinite(span) & (span > 0),
        jnp.clip((v[:, None] - s_prev) / jnp.where(span > 0, span, 1.0),
                 0.0, 1.0),
        (s_mean < v[:, None]).astype(jnp.float32))
    below = jnp.sum(s_dw * frac, axis=1)
    # the summary's own accumulated mass, not temp.count: imports bin
    # with update_stats=False, so count and bin mass can differ
    acc_tot = jnp.sum(s_dw, axis=1)
    return below, acc_tot


class TempCentroids(NamedTuple):
    """Per-series accumulation of pre-clustered samples: the batched analogue
    of the reference's tempCentroids list, plus the Histo sampler's local
    scalar stats (samplers.go:467-494).

    The bin planes are FLAT: ingest only ever writes them by scatter,
    and on the chip a scatter is native in a flat array alone. Held
    ``[S, K]`` the planes live column-major there, and every ingest
    dispatch relaid a whole plane flat for its scatter and back in a
    K-trip loop: 30.6 of a dispatch's 40.6 ms at 2^20 rows, set by the
    rows reserved whatever the chunk carried (PERF.md, PR 34). sum_w /
    sum_wm hold bin ``b`` of row ``r`` at ``r*K + b`` (a row's bins
    contiguous: the drains read them a window of rows at a time,
    ``bin_rows`` / ``gather_bin_rows``).

    seg_w/seg_wm are the incremental BELOW_MASS_ANCHORS-segment anchor
    summary (updated by the same scatters that fill the bins): the
    quantile-anchoring correction and the shift guard read ONLY these
    planes, never the full bins — keeping the per-chunk ingest cost at
    scatter level. They hold anchor ``a`` of row ``r`` at ``a*S + r``:
    both readers take every row at once, and ``anchor_rows``' ``[S, A]``
    view of that order is the one the chip computes in (row-major
    ``r*A + a`` read 5.5 ms a dispatch slower there: PERF.md, PR 34)."""

    sum_w: jax.Array       # [S*K] per-bin weight
    sum_wm: jax.Array      # [S*K] per-bin weighted mean sum
    seg_w: jax.Array       # [A*S] anchor-segment weight
    seg_wm: jax.Array      # [A*S] anchor-segment weighted mean sum
    count: jax.Array       # [S] total weight
    vsum: jax.Array        # [S] weighted sample sum
    vmin: jax.Array        # [S]
    vmax: jax.Array        # [S]
    recip: jax.Array       # [S] weighted reciprocal sum (for hmean)

    @property
    def num_series(self) -> int:
        return self.count.shape[0]

    @property
    def capacity(self) -> int:
        return self.sum_w.shape[0] // self.count.shape[0]

    def bins(self):
        """(sum_w, sum_wm) as ``[S, K]``: a relayout of both whole
        planes on the chip, for a drain of every row."""
        shape = (self.num_series, self.capacity)
        return self.sum_w.reshape(shape), self.sum_wm.reshape(shape)

    def anchors(self):
        """(seg_w, seg_wm) as the ``[S, A]`` views the guard and the
        quantile anchoring read."""
        return (anchor_rows(self.seg_w, self.num_series),
                anchor_rows(self.seg_wm, self.num_series))


def anchor_rows(seg: jax.Array, num_series: int) -> jax.Array:
    """A flat anchor plane (``a*S + r``) as ``[S, A]``."""
    return seg.reshape(-1, num_series).T


def bin_rows(plane: jax.Array, start, rows: int, width: int) -> jax.Array:
    """Rows ``[start, start + rows)`` of a flat ``[S*width]`` bin plane
    as ``[rows, width]``; ``start`` may be traced. What is relaid is the
    window, never the plane."""
    return lax.dynamic_slice_in_dim(plane, start * width, rows * width
                                    ).reshape(rows, width)


def gather_bin_rows(plane: jax.Array, rows: jax.Array,
                    width: int) -> jax.Array:
    """Rows ``rows`` ([R] int32, in range) of a flat ``[S*width]`` bin
    plane as ``[R, width]``, a window a row (the snapshots' read; the
    row drain moves its rows out with ``take_bin_rows``)."""
    return jax.vmap(
        lambda r: lax.dynamic_slice_in_dim(plane, r * width, width))(rows)


def take_bin_rows(sum_w: jax.Array, sum_wm: jax.Array, rows: jax.Array,
                  n, width: int):
    """Move the bins of ``rows[:n]`` ([R] int32, in range; ``n`` traced)
    out of the flat planes: (sum_w, sum_wm) with those rows zeroed and
    their bins as two ``[R, width]`` slabs, zero past ``n``. One loop
    of ``n`` trips, each a window of a row read and zeroed in place
    (2.8 ms a 1,024 held rows): as a gather and a scatter of windows
    the chip runs one serial loop an operation over all ``R`` entries,
    held or not (7.2 ms; 0.5 on ``[S, K]`` planes, which paid 30 ms of
    conversion a dispatch for it; PERF.md, PR 34)."""
    blank = jnp.zeros((rows.shape[0], width), sum_w.dtype)
    zero = jnp.zeros((width,), sum_w.dtype)

    def one(i, carry):
        sum_w, sum_wm, w, wm = carry
        at = rows[i] * width
        w = lax.dynamic_update_slice_in_dim(
            w, lax.dynamic_slice_in_dim(sum_w, at, width)[None], i, 0)
        wm = lax.dynamic_update_slice_in_dim(
            wm, lax.dynamic_slice_in_dim(sum_wm, at, width)[None], i, 0)
        return (lax.dynamic_update_slice_in_dim(sum_w, zero, at, 0),
                lax.dynamic_update_slice_in_dim(sum_wm, zero, at, 0), w, wm)

    return lax.fori_loop(0, n, one, (sum_w, sum_wm, blank, blank))


# A digest is held in one of three storage layouts, and the shared ops
# below read which from its planes: ``[S, K]`` float32 (the dense and
# mesh stores), or the slab bank's flat ``[S*K]`` planes (core/slab.py),
# either float32 or coded in 16 bits: bfloat16 weights, and each mean a
# uint16 code against its row's frame, the digest's ``min``/``max``
# (the packed format of the wire, ``quantize_centroids``). The kernels
# take float32 ``[R, K]`` rows: a flat digest is read and written a
# window of rows at a time, widened on the way in and stored on the way
# out, and never relaid whole. Whatever writes a coded row writes its
# frame, and the frame holds every live mean of the row.
#
# A mean is coded, not rounded to bfloat16: a drain writes a row
# mid-interval while the row's later samples stay exact in the float32
# bins, and a mean kept in 8 bits (1,024 apart at 300,000) lands past a
# later sample of its row, a sparse row's percentile past both (0.5 by
# rank in the 2-15 band on the chip, PERF.md section 2). A code is
# 1/65,535 of the row's own span, and the span's low end is exact.


def coded(digest: TDigest) -> bool:
    """Whether ``digest`` holds its means as codes against its frame."""
    return digest.mean.dtype == jnp.uint16


def code_means(mean: jax.Array, weight: jax.Array, lo: jax.Array,
               hi: jax.Array) -> jax.Array:
    """float32 ``[..., K]`` means as uint16 codes against the frame
    ``[lo, hi]`` (``[...]``, holding every live mean): 0 at an empty
    slot (``weight`` 0)."""
    live = weight > 0
    span = hi - lo
    scale = jnp.where(span > 0, 65535.0 / span, 0.0)
    off = jnp.where(live & jnp.isfinite(lo)[..., None],
                    mean - lo[..., None], 0.0)
    return jnp.clip(jnp.round(off * scale[..., None]), 0.0,
                    65535.0).astype(jnp.uint16)


def rows_f32(mean: jax.Array, weight: jax.Array, lo: jax.Array,
             hi: jax.Array):
    """``[R, K]`` rows of a digest's storage planes (``lo``/``hi`` their
    ``[R]`` frame) as float32 (mean, weight), ascending along a row as
    the Pallas kernels write and read it: an empty slot carries the mean
    before it, -inf ahead of the first (+inf there took a sparse row's
    centroids out of order: 0.5 by rank in every band, PERF.md section
    6)."""
    if mean.dtype != jnp.uint16:
        return mean.astype(jnp.float32), weight.astype(jnp.float32)
    mean, weight = dequantize_centroids(
        mean, lax.bitcast_convert_type(weight, jnp.uint16), lo, hi)
    return _cummax(jnp.where(weight > 0, mean, -jnp.inf)), weight


def rows_stored(stored: TDigest, mean: jax.Array, weight: jax.Array,
                lo: jax.Array, hi: jax.Array):
    """float32 ``[R, K]`` rows as ``stored``'s planes hold them: in its
    dtypes, each mean coded against ``[lo, hi]`` where it is coded."""
    weight_s = weight.astype(stored.weight.dtype)
    if coded(stored):
        return code_means(mean, weight, lo, hi), weight_s
    return mean.astype(stored.mean.dtype), weight_s


def digest_as_rows(digest: TDigest, width: int) -> TDigest:
    """``digest`` as the float32 ``[S, width]`` batch the kernels take:
    a flat one widened whole (a program that works on every row), an
    ``[S, K]`` one as it is."""
    if digest.mean.ndim == 2:
        return digest
    mean, weight = rows_f32(digest.mean.reshape(-1, width),
                            digest.weight.reshape(-1, width), digest.min,
                            digest.max)
    return digest._replace(mean=mean, weight=weight)


def digest_like(stored: TDigest, digest: TDigest) -> TDigest:
    """``digest`` (``[S, K]`` float32, its min/max holding every live
    mean) back in ``stored``'s layout and dtypes."""
    if stored.mean.ndim == 2:
        return digest
    mean, weight = rows_stored(stored, digest.mean, digest.weight,
                               digest.min, digest.max)
    return digest._replace(mean=mean.reshape(-1), weight=weight.reshape(-1))


def take_digest_rows(digest: TDigest, rows: jax.Array, n, width: int):
    """Rows ``rows[:n]`` ([R] int32, in range; ``n`` traced) of a
    digest as float32 ``[R, width]`` (mean, weight): a gather of rows,
    or, of a flat digest, a loop of ``n`` trips that reads a window a
    row (as ``take_bin_rows`` reads the bins), empty past ``n``. One
    gather and one scatter over the rows' entries instead read 0.033 s
    a dispatch on the chip, against 0.027 for these loops (PERF.md
    section 7)."""
    if digest.mean.ndim == 2:
        return digest.mean[rows], digest.weight[rows]

    def one(i, carry):
        m, w = carry
        at = rows[i] * width
        return (lax.dynamic_update_slice_in_dim(
                    m, lax.dynamic_slice_in_dim(digest.mean, at, width)[None],
                    i, 0),
                lax.dynamic_update_slice_in_dim(
                    w, lax.dynamic_slice_in_dim(digest.weight, at,
                                                width)[None], i, 0))

    empty = 0 if coded(digest) else jnp.inf
    m, w = lax.fori_loop(0, n, one, (
        jnp.full((rows.shape[0], width), empty, digest.mean.dtype),
        jnp.zeros((rows.shape[0], width), digest.weight.dtype)))
    return rows_f32(m, w, digest.min[rows], digest.max[rows])


def put_digest_rows(digest: TDigest, rows: jax.Array, n, mean: jax.Array,
                    weight: jax.Array) -> TDigest:
    """``digest`` with rows ``rows`` set to the float32 ``[R, width]``
    rows given: a scatter of rows (an id past the digest's rows is
    dropped), or, on a flat digest, the first ``n`` (in range) written
    a window a row in a loop of ``n`` trips, in place. A coded row takes
    the live span of its means as its frame."""
    if digest.mean.ndim == 2:
        return digest._replace(mean=digest.mean.at[rows].set(mean,
                                                             mode="drop"),
                               weight=digest.weight.at[rows].set(
                                   weight, mode="drop"))
    lo, hi = digest.min, digest.max
    if coded(digest):
        mean, wb, lo_r, hi_r = quantize_centroids(mean, weight)
        weight = lax.bitcast_convert_type(wb, jnp.bfloat16)
        lo = lo.at[rows].set(lo_r, mode="drop")
        hi = hi.at[rows].set(hi_r, mode="drop")
    mean = mean.astype(digest.mean.dtype)
    weight = weight.astype(digest.weight.dtype)
    width = mean.shape[1]

    def one(i, carry):
        pm, pw = carry
        at = rows[i] * width
        return (lax.dynamic_update_slice_in_dim(pm, mean[i], at, 0),
                lax.dynamic_update_slice_in_dim(pw, weight[i], at, 0))

    pm, pw = lax.fori_loop(0, n, one, (digest.mean, digest.weight))
    return digest._replace(mean=pm, weight=pw, min=lo, max=hi)


def grow_temp(temp: TempCentroids, pad: int) -> TempCentroids:
    """``temp`` with ``pad`` empty rows appended (the one place outside
    the ingest that knows the planes' orders)."""
    def anchor_pad(seg):
        return jnp.pad(seg.reshape(-1, temp.num_series),
                       ((0, 0), (0, pad))).reshape(-1)

    return TempCentroids(
        sum_w=jnp.pad(temp.sum_w, (0, pad * temp.capacity)),
        sum_wm=jnp.pad(temp.sum_wm, (0, pad * temp.capacity)),
        seg_w=anchor_pad(temp.seg_w), seg_wm=anchor_pad(temp.seg_wm),
        count=jnp.pad(temp.count, (0, pad)),
        vsum=jnp.pad(temp.vsum, (0, pad)),
        vmin=jnp.pad(temp.vmin, (0, pad), constant_values=jnp.inf),
        vmax=jnp.pad(temp.vmax, (0, pad), constant_values=-jnp.inf),
        recip=jnp.pad(temp.recip, (0, pad)))


def init_temp(num_series: int, capacity: int | None = None,
              compression: float = DEFAULT_COMPRESSION) -> TempCentroids:
    k = capacity if capacity is not None else size_bound(compression)
    # NB: each field gets its own buffer — ingest donates the whole tuple,
    # and XLA rejects donating one buffer twice. Machine-checked: the
    # donation-safety pass (lint/deviceflow.py DISTINCT_BUFFER_INITS)
    # flags any field sharing a buffer name here.
    return TempCentroids(
        sum_w=jnp.zeros((num_series * k,), jnp.float32),
        sum_wm=jnp.zeros((num_series * k,), jnp.float32),
        seg_w=jnp.zeros((BELOW_MASS_ANCHORS * num_series,), jnp.float32),
        seg_wm=jnp.zeros((BELOW_MASS_ANCHORS * num_series,), jnp.float32),
        count=jnp.zeros((num_series,), jnp.float32),
        vsum=jnp.zeros((num_series,), jnp.float32),
        vmin=jnp.full((num_series,), jnp.inf, jnp.float32),
        vmax=jnp.full((num_series,), -jnp.inf, jnp.float32),
        recip=jnp.zeros((num_series,), jnp.float32),
    )


def ingest_chunk(temp: TempCentroids, rows: jax.Array, values: jax.Array,
                 weights: jax.Array,
                 compression: float = DEFAULT_COMPRESSION,
                 update_stats: bool = True,
                 anchors=None) -> TempCentroids:
    """Fold one flat chunk of samples into the temp accumulator, bin ids
    anchored on ``temp``'s own anchor summary (the quantile-anchoring
    state for bin coherence). ``anchors`` is that summary as the
    ``[S, A]`` views ``temp.anchors()`` gives, for a caller that has
    made them already (on the chip each view is a relayout of a whole
    anchor plane).

    All scatters use mode='drop' so padding (rows == S) is free: its
    flat index lies past the plane's end. Repeated
    chunks accumulate into the same bins, with bin ids anchored to the
    estimated GLOBAL quantile against the accumulated state (see
    bin_flat_samples' acc_* args), so bins stay value-coherent across
    chunks even under ordered arrival. The anchor summary is
    maintained by two extra scatters here.

    update_stats=False skips the local scalar stats: used when re-binning
    *imported* digest centroids, which contribute to percentiles but not to
    the host-local min/max/sum/avg/count/hmean (samplers.go:473-480).
    """
    num_series, capacity = temp.num_series, temp.capacity
    acc_w, acc_wm = temp.anchors() if anchors is None else anchors
    r, v, w, b = bin_flat_samples(
        rows, values, weights, num_series, capacity, compression,
        acc_seg_w=acc_w, acc_seg_wm=acc_wm)
    live = w > 0
    vz = jnp.where(live, v, 0.0)
    # a padding row's bins lie past the plane's end by themselves; its
    # anchors would land in the next anchor's rows
    flat = r * capacity + b
    flat_seg = jnp.where(r < num_series,
                         seg_of_bins(b, capacity) * num_series + r,
                         temp.seg_w.shape[0])
    temp = temp._replace(
        sum_w=temp.sum_w.at[flat].add(w, mode="drop"),
        sum_wm=temp.sum_wm.at[flat].add(w * vz, mode="drop"),
        seg_w=temp.seg_w.at[flat_seg].add(w, mode="drop"),
        seg_wm=temp.seg_wm.at[flat_seg].add(w * vz, mode="drop"),
    )
    if not update_stats:
        return temp
    return temp._replace(
        count=temp.count.at[r].add(w, mode="drop"),
        vsum=temp.vsum.at[r].add(w * vz, mode="drop"),
        vmin=temp.vmin.at[r].min(jnp.where(live, v, jnp.inf), mode="drop"),
        vmax=temp.vmax.at[r].max(jnp.where(live, v, -jnp.inf), mode="drop"),
        recip=temp.recip.at[r].add(jnp.where(live, w / v, 0.0), mode="drop"),
    )


SHIFT_GUARD_FRAC = 0.01
# a row votes "shifted" only once its bins hold this much mass: with
# 1-2 accumulated samples the summary's value range is a point, and
# ANY new value reads as disjoint — which made the guard drain on
# every chunk of ordinary traffic (a 4x ingest regression caught by
# the round-5 bench artifact). Rows this small cannot alias anyway:
# their handful of samples spread across distinct anchored bins.
SHIFT_GUARD_MIN_MASS = 8.0
# ... and only when the CHUNK brings this much mass for the row: a
# single stationary sample lands outside the accumulated segment-mean
# envelope with probability ~2/(n+1) (~20% at n=8), so 1-sample-per-row
# chunks — the realistic fleet shape — would re-trigger the churn at
# reduced frequency. Four samples all clearing the envelope on the
# same side by chance is ~(1/(n+1))^4; a genuine step change with
# >=4-sample chunks still fires, and sparser rows rely on the
# quantile anchoring, whose misassignments stay value-local.
SHIFT_GUARD_MIN_CHUNK_MASS = 4.0


def shift_masses(acc_seg_w: jax.Array, acc_seg_wm: jax.Array,
                 rows: jax.Array, values: jax.Array, weights: jax.Array,
                 num_series: int, anchors: int = BELOW_MASS_ANCHORS):
    """(shifted_mass, total_mass) of a chunk against the accumulated
    anchor summary — the raw inputs of ``shift_pred``, exposed
    separately so the mesh store can psum them over its axes before
    thresholding (every shard must take the SAME drain decision the
    dense store would). Reads only the [S, A] summary planes.

    rows may carry the padding sentinel (== num_series); padding and
    zero weights are excluded everywhere."""
    acc_w2 = acc_seg_w.reshape(num_series, anchors)
    acc_m2 = acc_seg_wm.reshape(num_series, anchors)
    live_b = acc_w2 > 0
    means = jnp.where(live_b, acc_m2 / jnp.where(live_b, acc_w2, 1.0),
                      jnp.nan)
    amin = jnp.min(jnp.where(live_b, means, jnp.inf), axis=1)
    amax = jnp.max(jnp.where(live_b, means, -jnp.inf), axis=1)
    acc_mass = acc_w2.sum(axis=1)
    live = weights > 0
    v_lo = jnp.where(live, values, jnp.inf)
    v_hi = jnp.where(live, values, -jnp.inf)
    w_live = jnp.where(live, weights, 0.0)
    cmin = jnp.full((num_series + 1,), jnp.inf,
                    jnp.float32).at[rows].min(v_lo, mode="drop")[:num_series]
    cmax = jnp.full((num_series + 1,), -jnp.inf,
                    jnp.float32).at[rows].max(v_hi, mode="drop")[:num_series]
    cmass = jnp.zeros((num_series + 1,),
                      jnp.float32).at[rows].add(w_live,
                                                mode="drop")[:num_series]
    disjoint = (acc_mass >= SHIFT_GUARD_MIN_MASS) \
        & (cmass >= SHIFT_GUARD_MIN_CHUNK_MASS) \
        & ((cmin > amax) | (cmax < amin))
    shifted = jnp.sum(jnp.where(disjoint, cmass, 0.0))
    total = jnp.sum(cmass)
    return shifted, total


def shift_pred(acc_seg_w: jax.Array, acc_seg_wm: jax.Array,
               rows: jax.Array, values: jax.Array, weights: jax.Array,
               num_series: int,
               frac: float = SHIFT_GUARD_FRAC,
               anchors: int = BELOW_MASS_ANCHORS) -> jax.Array:
    """True when >= ``frac`` of the chunk's mass lands in rows whose
    value range is DISJOINT from what those rows' accumulated bins
    cover — a distribution step/shift that per-bin accumulation cannot
    absorb (even quantile-anchored bins mix tails across a hard shift;
    see analysis/tdigest_sweep.py's ordered-arrival regime). Callers
    guard with lax.cond: drain the temp into the digest first, then
    ingest against fresh bins. Stationary traffic never triggers."""
    shifted, total = shift_masses(acc_seg_w, acc_seg_wm, rows, values,
                                  weights, num_series, anchors)
    return shifted > frac * jnp.maximum(total,
                                        jnp.finfo(jnp.float32).tiny)


def drain_every_bin(digest: TDigest, temp: TempCentroids,
                    compression: float = DEFAULT_COMPRESSION,
                    use_pallas: bool = True):
    """The shift guard's drain (``shift_pred`` -> this, behind a
    ``lax.cond``, then the chunk is binned against fresh anchors):
    every row's bins into its digest, bins and anchors emptied. The
    temp's scalar stats (count/vsum/vmin/vmax/recip) survive: they are
    interval aggregates, only the BINS move. A flat digest (the slab
    bank's) is widened whole for it and stored back. Returns (digest,
    temp)."""
    digest = digest_like(digest, drain_temp(
        digest_as_rows(digest, temp.capacity), temp, compression,
        use_pallas=use_pallas))
    return digest, temp._replace(sum_w=jnp.zeros_like(temp.sum_w),
                                 sum_wm=jnp.zeros_like(temp.sum_wm),
                                 seg_w=jnp.zeros_like(temp.seg_w),
                                 seg_wm=jnp.zeros_like(temp.seg_wm))


def drain_every_bin_by_slab(digest: TDigest, temp: TempCentroids,
                            compression: float = DEFAULT_COMPRESSION):
    """``drain_every_bin`` on ``[S, K]`` digests, a slab of
    ``tdigest_pallas._FLUSH_SLAB_ROWS`` rows at a time, each read from
    the carried planes and written back in place: the whole-batch form
    relays both bin planes to ``[S, K]`` and sorts them whole (6.0 of
    the 6.3 GB of scratch of a mesh's sample ingest at 2^21 rows a
    device, compiled for a v5e; 0.27 GB this way). The arithmetic
    is ``_merge_bins`` row by row either way, so the result is the same
    bit for bit. A batch of at most one slab is ``drain_every_bin``
    itself. Returns (digest, temp)."""
    from veneur_tpu.ops import tdigest_pallas

    slab = tdigest_pallas._FLUSH_SLAB_ROWS
    rows, k = temp.num_series, temp.capacity
    if rows <= slab:
        return drain_every_bin(digest, temp, compression)

    def one_slab(i, planes):
        # a batch that is no multiple of the slab ends in a slab clamped
        # back over rows the trip before drained: those keep what they
        # have
        start = jnp.minimum(i * slab, rows - slab)
        old = tuple(lax.dynamic_slice_in_dim(x, start, slab, 0)
                    for x in planes)
        new = _merge_bins(*old, bin_rows(temp.sum_w, start, slab, k),
                          bin_rows(temp.sum_wm, start, slab, k),
                          compression, k, True)
        fresh = (start + jnp.arange(slab) >= i * slab)[:, None]
        return tuple(lax.dynamic_update_slice_in_dim(
            x, jnp.where(fresh, n, o), start, 0)
            for x, n, o in zip(planes, new, old))

    mean, weight = lax.fori_loop(0, -(-rows // slab), one_slab,
                                 (digest.mean, digest.weight))
    digest = TDigest(mean=mean, weight=weight,
                     min=jnp.minimum(digest.min, temp.vmin),
                     max=jnp.maximum(digest.max, temp.vmax))
    return digest, temp._replace(sum_w=jnp.zeros_like(temp.sum_w),
                                 sum_wm=jnp.zeros_like(temp.sum_wm),
                                 seg_w=jnp.zeros_like(temp.seg_w),
                                 seg_wm=jnp.zeros_like(temp.seg_wm))


# Rows one trip of the row-local drain compresses: eight kernel blocks.
# A staged chunk of whole digests (dozens of centroids a row) touches
# fewer rows than this and takes one trip; lone centroids or samples
# over many rows take more. Swept on a v5e at 2^20 rows and 16,384
# staged centroids (PERF.md, PR 33), a dispatch by the host's clock at
# 256 / 1,024 / 4,096 rows: a merging chunk of 256 rows 55.1 / 53.1 /
# 54.5 ms, lone centroids over 2,048 held rows 55.4 / 52.2 / 53.8.
# Again with the temp's planes flat (PERF.md, PR 34), 128 / 256 / 512 /
# 1,024 rows: a merging chunk of 256 rows 13.8 / 13.7 / 14.1 / 14.0 ms,
# lone centroids over 16,384 held rows 65.0 / 64.9 / 64.7 / 55.7.
ROW_DRAIN_SLAB_ROWS = 1024

# The sample path drains a held row only while the interval has brought
# it at most this many samples: arrivals, not weight (a timer sent
# ``|@0.1`` weighs 10 a sample; ``held_rows`` reads a row's count in
# units of the arriving sample's own weight). Binned against the
# 8-anchor summary, a row's few samples a chunk alias: rows of 2 to
# 1,023 samples an interval read rank errors of 0.03 to 0.32 on the
# chip, rows of 1,024 and more 0.005-0.009 (PERF.md, PR 39). Past the
# constant a row keeps the anchored binning, which holds the envelope
# there, and costs a dispatch nothing (PERF.md, PR 40 has the sweep).
ROW_DRAIN_MAX_ARRIVALS = 1024.0


def held_rows(temp: TempCentroids, rows: jax.Array, weights: jax.Array,
              max_arrivals=None):
    """The rows a chunk brings mass to that already hold bin mass, for
    ``drain_rows``: (touched, count), the distinct rows sorted to the
    front of a chunk-long array, the rest the sentinel ``num_series``,
    and how many there are. ``max_arrivals`` (the sample path:
    ``ROW_DRAIN_MAX_ARRIVALS``) leaves out a row whose ``temp.count``
    has passed that many samples of the arriving sample's weight: a
    series keeps one sample rate, so its count over the weight is its
    arrivals, and no plane counts them."""
    num_series = temp.num_series
    rows = rows.astype(jnp.int32)
    at = jnp.minimum(rows, num_series - 1)
    mass = temp.anchors()[0].sum(axis=1)
    if max_arrivals is None:
        held = (mass > 0)[at]
    else:
        # a row at full width (its count, +inf where it holds nothing),
        # so that the chunk gathers once
        held = jnp.where(mass > 0, temp.count, jnp.inf)[at] \
            <= max_arrivals * weights
    due = (rows < num_series) & (weights > 0) & held
    # one entry a row: the sorted candidates' run starts, sorted once
    # more to the front; the rest carry the sentinel the scatters drop
    cand = jnp.sort(jnp.where(due, rows, num_series))
    first = jnp.concatenate([jnp.ones((1,), bool), cand[1:] != cand[:-1]])
    touched = jnp.sort(jnp.where(first, cand, num_series))
    return touched, jnp.sum(touched < num_series).astype(jnp.int32)


def drain_rows(digest: TDigest, temp: TempCentroids, touched: jax.Array,
               count, compression: float = DEFAULT_COMPRESSION,
               use_pallas: bool = True):
    """The row-local drain both ingest paths share: the ``count`` rows
    at the front of ``touched`` (``held_rows``) are compressed into
    their digests, bins and anchors emptied, so that the chunk's run of
    such a row is binned by its own exact ranks into empty bins and
    meets the earlier mass in a compress, not in a bin.

    The rows are compressed ``ROW_DRAIN_SLAB_ROWS`` at a time in a loop
    whose trip count is ``ceil(count / slab)``: one compiled compress
    for every count, no trip where nothing is held, its cost bounded by
    the chunk (at most one row a staged entry) and never by the rows
    reserved. Nothing is decided across rows, so a shard of a mesh
    takes it alone and agrees with the dense store, and a flat digest
    (the slab bank's) has its rows read and written a window at a time
    (``take_digest_rows`` / ``put_digest_rows``). Returns (digest,
    temp)."""
    num_series, k = temp.num_series, temp.capacity
    # the planes the loop writes: a coded digest's frames with its rows
    written = TDigest._fields[:4 if coded(digest) else 2]
    chunk_len = touched.shape[0]
    slab = min(ROW_DRAIN_SLAB_ROWS, chunk_len)
    touched = jnp.concatenate([touched, jnp.full(
        ((-chunk_len) % slab,), num_series, jnp.int32)])
    lanes = jnp.arange(BELOW_MASS_ANCHORS, dtype=jnp.int32) * num_series

    def drain_slab(i, planes):
        kept, sum_w, sum_wm, seg_w, seg_wm = planes
        d = digest._replace(**dict(zip(written, kept)))
        to = lax.dynamic_slice_in_dim(touched, i * slab, slab)
        at = jnp.minimum(to, num_series - 1)
        held = jnp.minimum(count - i * slab, slab)
        sum_w, sum_wm, t_w, t_wm = take_bin_rows(sum_w, sum_wm, at, held, k)
        m, w = _merge_bins(*take_digest_rows(d, at, held, k), t_w, t_wm,
                           compression, k, use_pallas)
        to_a = jnp.where(to[:, None] < num_series, lanes + to[:, None],
                         seg_w.shape[0])
        d = put_digest_rows(d, to, held, m, w)
        return (tuple(getattr(d, f) for f in written), sum_w, sum_wm,
                seg_w.at[to_a].set(0.0, mode="drop"),
                seg_wm.at[to_a].set(0.0, mode="drop"))

    kept, sum_w, sum_wm, seg_w, seg_wm = lax.fori_loop(
        0, row_drain_trips(count, chunk_len), drain_slab,
        (tuple(getattr(digest, f) for f in written), temp.sum_w,
         temp.sum_wm, temp.seg_w, temp.seg_wm))
    return (digest._replace(**dict(zip(written, kept))),
            temp._replace(sum_w=sum_w, sum_wm=sum_wm, seg_w=seg_w,
                          seg_wm=seg_wm))


def row_drain_trips(drained, chunk_len: int):
    """Trips ``drain_rows``' loop makes for ``drained`` rows of a
    ``chunk_len``-entry chunk."""
    slab = min(ROW_DRAIN_SLAB_ROWS, chunk_len)
    return (drained + slab - 1) // slab


def ingest_chunk_rowdrained(digest: TDigest, temp: TempCentroids,
                            rows: jax.Array, values: jax.Array,
                            weights: jax.Array,
                            compression: float = DEFAULT_COMPRESSION,
                            use_pallas: bool = True):
    """The sample path's ingest. A series' few samples an interval
    arrive a handful a chunk; binned by their estimated quantile
    against the row's 8-anchor summary, value-distant samples share a
    bin (three samples 58,293.75 / 58,295.0 / one lower emitted a 99th
    percentile under both upper ones: rank error 0.3233 on every chip
    run of PERF.md, PR 39). So the rows of the chunk that hold bin mass
    and at most ``ROW_DRAIN_MAX_ARRIVALS`` samples are drained first
    (``drain_rows``, the import path's drain); the rows past it keep
    the anchored binning behind the chunk-wide shift guard
    (``shift_pred`` -> every bin drained into the digest), which is
    asked once the held rows are out of the vote. The guard is still
    needed there: ordered arrival at 256 samples a row a chunk reads
    0.149 by rank without it from the sixth chunk on, 0.0024 with it
    (docs/tdigest_accuracy.md, ``tdigest_sweep --guard``).

    Both drains sit in one ``lax.cond`` taken where either has
    something to do: the branches of a conditional agree on their
    results' layout, and the row drain's loop holds the digest planes
    row-major, so a branch of its own made the chunk that drains
    nothing relay both planes there and back (four copies of 436 MB at
    2^20 rows, compiled for a v5e; tests/test_chip_compile.py). As it
    is, such a chunk, every one of a series' first sight and of a row
    past the count, costs what the guard alone cost. The temp's scalar
    stats survive the drains: they are interval aggregates, only the
    BINS move into the digest. Returns (digest, temp, drained): the
    rows drained, an int32 scalar."""
    touched, count = held_rows(temp, rows, weights, ROW_DRAIN_MAX_ARRIVALS)
    shifted = shift_pred(*temp.anchors(), rows, values, weights,
                         temp.num_series)

    def drains(args):
        digest, temp = drain_rows(*args, touched, count, compression,
                                  use_pallas)
        # the drained rows hold nothing now and cannot vote
        return lax.cond(
            shift_pred(*temp.anchors(), rows, values, weights,
                       temp.num_series),
            lambda a: drain_every_bin(*a, compression, use_pallas),
            lambda a: a, (digest, temp))

    digest, temp = lax.cond((count > 0) | shifted, drains, lambda a: a,
                            (digest, temp))
    temp = ingest_chunk(temp, rows, values, weights, compression)
    return digest, temp, count


def ingest_centroids_rowdrained(digest: TDigest, temp: TempCentroids,
                                rows: jax.Array, means: jax.Array,
                                weights: jax.Array,
                                compression: float = DEFAULT_COMPRESSION,
                                use_pallas: bool = True):
    """The import path's ingest: a forwarded digest's centroids merge
    the way a t-digest merges, row by row: every row the chunk brings
    mass to that already holds bin mass is drained first (``held_rows``,
    ``drain_rows``).

    The chunk-wide shift guard of the sample path does not do here:
    two forwarders' digests of one series are two distributions by
    nature, and binned against the 8-anchor summary of the first the
    second aliases (rank errors of 0.04-0.24 were read wherever 1 % of
    a staging chunk's mass did not happen to lie in disjoint rows). The
    decision is taken where the aliasing happens, the row.

    Imported centroids feed percentiles only, never the local scalar
    stats (samplers.go:473-480). Returns (digest, temp, drained): the
    last is 1 where any row was drained, as an int32 scalar."""
    rows = rows.astype(jnp.int32)
    touched, count = held_rows(temp, rows, weights)
    digest, temp = drain_rows(digest, temp, touched, count, compression,
                              use_pallas)
    temp = ingest_chunk(temp, rows, means, weights, compression,
                        update_stats=False)
    return digest, temp, (count > 0).astype(jnp.int32)


def _merge_bins(mean, weight, sum_w, sum_wm, compression: float,
                capacity: int, use_pallas: bool):
    """One compress of [R, K] digests with their [R, K] temp bins: the
    new (mean, weight) planes."""
    from veneur_tpu.ops import tdigest_pallas

    t_live = sum_w > 0
    t_mean = jnp.where(t_live, sum_wm / jnp.where(t_live, sum_w, 1.0),
                       jnp.inf)
    if use_pallas and tdigest_pallas.pallas_ok(mean):
        # bin means are NOT monotone in bin index once several chunks with
        # shifting distributions accumulate, so the temp half needs a real
        # sort. Measured on v5e: lax.sort + presorted kernel beats the
        # in-kernel bitonic sort (sort_b) in the fused pipeline — the
        # kernel is VMEM-temporary-bound, so the 28 extra in-VMEM stages
        # cost more than XLA's external sort passes.
        t_mean, t_w = lax.sort((t_mean, sum_w), dimension=-1,
                               num_keys=1, is_stable=False)
        return tdigest_pallas.compress_presorted(
            mean, weight, t_mean, t_w, compression, capacity)
    return _compress(jnp.concatenate([mean, t_mean], axis=-1),
                     jnp.concatenate([weight, sum_w], axis=-1),
                     compression, capacity)


def drain_temp(state: TDigest, temp: TempCentroids,
               compression: float = DEFAULT_COMPRESSION,
               use_pallas: bool = True) -> TDigest:
    """Merge the accumulated temp centroids into the digests (one compress
    per interval — the batched mergeAllTemps). ``use_pallas=False``
    forces the sort-based XLA path (compute-breaker fallback rung)."""
    new_mean, new_weight = _merge_bins(state.mean, state.weight,
                                       *temp.bins(), compression,
                                       state.capacity, use_pallas)
    return TDigest(
        mean=new_mean,
        weight=new_weight,
        min=jnp.minimum(state.min, temp.vmin),
        max=jnp.maximum(state.max, temp.vmax),
    )


def drain_and_quantile(state: TDigest, temp: TempCentroids, dmin, dmax,
                       qs: jax.Array,
                       compression: float = DEFAULT_COMPRESSION,
                       use_pallas: bool = True, n=None):
    """The whole per-interval digest flush as one op: drain the temp bins
    into the digests, fold in the imported extrema (dmin/dmax), and return
    (drained digests, per-series percentiles). On TPU this is a single
    fused Pallas program; elsewhere — or with ``use_pallas=False``, the
    compute breaker's fallback rung — it composes drain_temp +
    quantile.

    ``n`` (a traced int32 scalar) says that only rows ``[:n]`` are live —
    the interner hands rows out as a dense prefix, and so does a mesh's
    placement inside every shard's block (there ``n`` is the shard's
    own fill). The same pipeline then runs slab by slab
    (``tdigest_pallas._FLUSH_SLAB_ROWS`` rows) in a loop whose trip
    count is ``ceil(n / slab)``: one compiled program for every ``n``,
    its work bounded by the interval's series and not by the rows
    reserved. Rows past the last slab run keep their input values
    (their percentiles read 0); nothing reads them. A batch of at most
    one slab, or no ``n``, is the straight-line program. A flat digest
    (the slab bank's storage layout) comes back in its layout and
    dtypes, a coded one against its drained min/max; the loop widens
    and stores a slab's window of it, never the whole plane."""
    from veneur_tpu.ops import tdigest_pallas

    slab = tdigest_pallas._FLUSH_SLAB_ROWS
    flat = state.mean.ndim == 1
    qs = jnp.asarray(qs, jnp.float32 if flat else state.mean.dtype)
    rows, k = state.min.shape[0], temp.capacity
    if n is None or rows <= slab:
        drained, pcts = _drain_and_quantile_rows(
            digest_as_rows(state, k), *temp.bins(), temp.vmin, temp.vmax,
            dmin, dmax, qs, compression, use_pallas)
        return digest_like(state, drained), pcts
    # the slab's inputs: temp's anchors and scalar stats are not read
    planes = (state.mean, state.weight, state.min, state.max)
    reads = (temp.vmin, temp.vmax, dmin, dmax)

    def one_slab(i, carry):
        # a capacity that is no multiple of the slab ends in a slab
        # clamped back over rows the trip before already drained:
        # those keep what they have (``fresh`` is all true otherwise)
        start = jnp.minimum(i * slab, rows - slab)
        cut = lambda x: lax.dynamic_slice_in_dim(x, start, slab, 0)
        put = lambda old, x: lax.dynamic_update_slice_in_dim(old, x, start,
                                                             0)
        if flat:
            window = lambda x: bin_rows(x, start, slab, k)
            put_window = lambda old, x: lax.dynamic_update_slice_in_dim(
                old, x.reshape(-1), start * k, 0)
        else:
            window, put_window = cut, put
        cuts = (window, window, cut, cut, cut)
        puts = (put_window, put_window, put, put, put)
        mean, weight, mn, mx = (c(x) for c, x in zip(cuts, carry[:4]))
        drained, pcts = _drain_and_quantile_rows(
            TDigest(*rows_f32(mean, weight, mn, mx), mn, mx),
            bin_rows(temp.sum_w, start, slab, k),
            bin_rows(temp.sum_wm, start, slab, k),
            *(cut(x) for x in reads), qs, compression, use_pallas)
        new = rows_stored(state, drained.mean, drained.weight, drained.min,
                          drained.max) + (drained.min, drained.max, pcts)
        if rows % slab:
            fresh = start + jnp.arange(slab) >= i * slab
            new = tuple(
                jnp.where(fresh.reshape((slab,) + (1,) * (x.ndim - 1)), x,
                          c(old)) for x, old, c in zip(new, carry, cuts))
        return tuple(p(old, x) for old, x, p in zip(carry, new, puts))

    trips = (jnp.asarray(n, jnp.int32) + (slab - 1)) // slab
    out = lax.fori_loop(
        0, jnp.minimum(trips, -(-rows // slab)), one_slab,
        planes + (jnp.zeros((rows, qs.shape[0]), qs.dtype),))
    return TDigest(*out[:4]), out[4]


def flush_rows_run(rows: int, n: int) -> int:
    """Rows ``drain_and_quantile`` works on for ``n`` live rows of a
    ``rows``-row batch (the host's count of what the loop above does)."""
    from veneur_tpu.ops import tdigest_pallas

    slab = tdigest_pallas._FLUSH_SLAB_ROWS
    if rows <= slab:
        return rows
    return min(-(-n // slab) * slab, rows)


def _drain_and_quantile_rows(state: TDigest, sum_w, sum_wm, vmin, vmax,
                             dmin, dmax, qs: jax.Array, compression: float,
                             use_pallas: bool):
    """``drain_and_quantile`` over every row it is given: ``[R, K]``
    digests with their ``[R, K]`` temp bins and ``[R]`` extrema."""
    from veneur_tpu.ops import tdigest_pallas

    mn = jnp.minimum(jnp.minimum(state.min, vmin), dmin)
    mx = jnp.maximum(jnp.maximum(state.max, vmax), dmax)
    if use_pallas and tdigest_pallas.pallas_ok(state.mean):
        t_live = sum_w > 0
        t_mean = jnp.where(
            t_live, sum_wm / jnp.where(t_live, sum_w, 1.0), jnp.inf)
        # external sort + presorted kernel: measured faster than sort_b
        # (see _merge_bins)
        t_mean, t_w = lax.sort((t_mean, sum_w), dimension=-1,
                               num_keys=1, is_stable=False)
        nm, nw, pcts = tdigest_pallas.drain_quantile(
            state.mean, state.weight, t_mean, t_w, mn, mx, qs, compression,
            state.capacity)
        return TDigest(mean=nm, weight=nw, min=mn, max=mx), pcts
    new_mean, new_weight = _merge_bins(state.mean, state.weight, sum_w,
                                       sum_wm, compression, state.capacity,
                                       use_pallas)
    drained = TDigest(mean=new_mean, weight=new_weight, min=mn, max=mx)
    return drained, quantile(drained, qs)


def from_centroids(mean: jax.Array, weight: jax.Array, mins: jax.Array,
                   maxs: jax.Array, compression: float = DEFAULT_COMPRESSION,
                   capacity: int | None = None) -> TDigest:
    """Build digests from imported centroid arrays (the deserialization path
    of forwarded sketch state, cf. NewMergingFromData, merging_digest.go:83-99).

    mean/weight: [..., M] with weight==0 padding; M may differ from capacity.
    """
    k = capacity if capacity is not None else size_bound(compression)
    new_mean, new_weight = _compress(mean, weight, compression, k)
    return TDigest(mean=new_mean, weight=new_weight,
                   min=jnp.asarray(mins, mean.dtype), max=jnp.asarray(maxs, mean.dtype))


# ---------------------------------------------------------------------------
# Quantized (packed) centroid storage — the tiered pool's resident format
# ---------------------------------------------------------------------------
#
# The packed wire format of core/slab.py:_pack_slab, promoted into a
# RESIDENT representation (core/tiered.py): per row, means quantize to
# uint16 against the row's own [fmin, fmax] frame (absolute error <=
# span/65535) and weights round to bfloat16 bit patterns (relative
# error <= 2^-9; exact counts ride separate f32 stats). Liveness is
# weight > 0 exactly as in TDigest — a wb of 0 is the empty slot.


def quantize_centroids(mean: jax.Array, weight: jax.Array):
    """Quantize sorted, front-compacted [..., P] f32 centroid planes into
    (means_q u16, weights_bf u16, fmin f32, fmax f32): the row frame is
    the live-mean span, so quantization never clips. Rows with no live
    centroids get an empty frame (+inf/-inf) and all-zero planes."""
    live = weight > 0
    fmin = jnp.min(jnp.where(live, mean, jnp.inf), axis=-1)
    fmax = jnp.max(jnp.where(live, mean, -jnp.inf), axis=-1)
    mq = code_means(mean, weight, fmin, fmax)
    wb = lax.bitcast_convert_type(
        jnp.where(live, weight, 0.0).astype(jnp.bfloat16), jnp.uint16)
    return mq, wb, fmin, fmax


def dequantize_centroids(mq: jax.Array, wb: jax.Array, fmin: jax.Array,
                         fmax: jax.Array):
    """Inverse of :func:`quantize_centroids`: (mean f32 [..., P] with
    +inf empties, weight f32). The one in-kernel place the packed
    residency contract is decoded (host consumers go through
    core.store.PackedDigestPlanes)."""
    weight = lax.bitcast_convert_type(wb, jnp.bfloat16).astype(jnp.float32)
    live = weight > 0
    base = jnp.where(jnp.isfinite(fmin), fmin, 0.0)[..., None]
    span = jnp.where(jnp.isfinite(fmax - fmin), fmax - fmin, 0.0)
    mean = base + mq.astype(jnp.float32) * (span[..., None] / 65535.0)
    return jnp.where(live, mean, jnp.inf), weight
