"""Tiered packed↔dense digest residency: ragged pool + activity promotion.

Bench ``2d`` measures the fleet-realistic workload at ~3.9 live centroids
against the dense-48 centroid plane: the 13 GB resident footprint at 10M
bf16 series (``2b_histo_10m_bf16``) is >90 % zeros, and flush/merge time
is paid on the dense shape. This module promotes PR 5's packed *wire*
format (device-side sort-compact + u16/bf16 quantization,
``core/slab.py:_pack_slab``) into *residency*:

  * **Pool tier** (default home of every series): per row, a packed
    quantized centroid list — u16 range-quantized means + u16 bfloat16
    weight bits, ``pool_centroids`` (PK, default 16) slots — plus a PK-bin
    f32 accumulator the staged chunks scatter into, and the per-row f32
    scalar stats. ~228 B/row at PK=16 vs ~1.4-1.8 KB/row for the
    slab/dense planes: the 5-10× capacity headroom ROADMAP item 2 asks
    for. The bins double as the row's value-bracketing anchor summary
    (``bin_pool_samples``) and as the shift-guard input;
    a guard trip sort-compact-merges the bins into the packed planes
    mid-interval (``lax.cond``, so stationary traffic never pays it).
  * **Dense tier**: rows with *sustained* activity get a slot in an
    embedded full-K ``DigestGroup`` bank (same kernels, same breaker
    ladder). Promotion happens mid-interval the moment a row's interval
    activity crosses ``promote_samples`` (with a ``promote_intervals``
    streak of hysteresis carried across generations by the
    :class:`TierDirectory`); the promotion program moves the row's pool
    state — dequantized packed centroids + bins + scalar stats — into the
    dense temp ON DEVICE and clears the pool row, so counts are conserved
    exactly. Demotion back to the pool happens at flush boundaries after
    ``demote_intervals`` idle intervals (swap-on-flush makes it free:
    the next generation simply assigns the series to the pool).

Flush/merge runs DIRECTLY on the packed representation: the pool flush
program dequantizes, sort-compact-merges the bins
(``_dispatch_compress_presorted`` — the fused Pallas kernel on TPU, the
sort-based XLA path elsewhere and under the compute breaker's fallback
rung), computes quantiles, and — for a forwarding flush — re-packs via
``_pack_slab`` without ever materializing a dense ``[S, K]`` plane.

Every existing store contract holds: ``snapshot_begin/finish`` two-phase
checkpointing flattens both tiers into the shared per-row centroid-run
layout (so a restore merges into ANY digest store, whatever its tier
assignment), the OverloadLimited cardinality cap and quarantine apply at
the interner, and the requeue rung re-merges a failed interval through
``snapshot_state`` + the import path. Enabled with
``digest_storage: tiered`` (config.py; see docs/tiered.md).
"""

from __future__ import annotations

import logging
import math
import threading
from functools import partial
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from veneur_tpu.core.locking import requires_lock
from veneur_tpu.obs import kernels as obs_kernels
from veneur_tpu.obs import recorder as obs_rec
from veneur_tpu.ops import tdigest as td_ops
from veneur_tpu.ops.tdigest_pallas import _next_pow2

log = logging.getLogger("veneur.tiered")

POOL_SLAB_ROWS_DEFAULT = 1 << 18
DEFAULT_POOL_CENTROIDS = 16
DEFAULT_PROMOTE_SAMPLES = 64
DEFAULT_PROMOTE_INTERVALS = 2
DEFAULT_DEMOTE_INTERVALS = 3


class PoolSlab(NamedTuple):
    """Resident pool state for one slab of series rows (flat planes).

    mq/wb: the packed digest — u16 quantized means against the row's
    [fmin, fmax] frame and u16 bfloat16 weight bits (wb == 0 is the
    empty slot, exactly TDigest's weight-liveness contract). bw/bwm:
    the PK-bin in-flight accumulator staged chunks scatter into; its
    per-bin means are quantile-ordered by construction, so it doubles
    as the row's anchor summary and shift-guard input. dmin/dmax:
    imported-digest extrema (bound the final digest only, like
    DigestGroup.dmin/dmax); the interval's observed extrema ride the
    vmin/vmax stats."""

    mq: jax.Array      # [slab*PK] u16 quantized means
    wb: jax.Array      # [slab*PK] u16 bfloat16 weight bits
    fmin: jax.Array    # [slab] f32 quantization frame minima (+inf empty)
    fmax: jax.Array    # [slab] f32 frame maxima (-inf empty)
    bw: jax.Array      # [slab*PK] f32 in-flight bin weights
    bwm: jax.Array     # [slab*PK] f32 in-flight bin weighted means
    dmin: jax.Array    # [slab] f32 imported digest minima (+inf empty)
    dmax: jax.Array    # [slab] f32 imported digest maxima (-inf empty)
    count: jax.Array   # [slab] f32 total weight
    vsum: jax.Array    # [slab] f32 weighted sample sum
    vmin: jax.Array    # [slab] f32 observed minima
    vmax: jax.Array    # [slab] f32 observed maxima
    recip: jax.Array   # [slab] f32 weighted reciprocal sum (hmean)


def _init_pool_slab(slab: int, pk: int) -> PoolSlab:
    return PoolSlab(
        mq=jnp.zeros((slab * pk,), jnp.uint16),
        wb=jnp.zeros((slab * pk,), jnp.uint16),
        fmin=jnp.full((slab,), jnp.inf, jnp.float32),
        fmax=jnp.full((slab,), -jnp.inf, jnp.float32),
        bw=jnp.zeros((slab * pk,), jnp.float32),
        bwm=jnp.zeros((slab * pk,), jnp.float32),
        dmin=jnp.full((slab,), jnp.inf, jnp.float32),
        dmax=jnp.full((slab,), -jnp.inf, jnp.float32),
        count=jnp.zeros((slab,), jnp.float32),
        vsum=jnp.zeros((slab,), jnp.float32),
        vmin=jnp.full((slab,), jnp.inf, jnp.float32),
        vmax=jnp.full((slab,), -jnp.inf, jnp.float32),
        recip=jnp.zeros((slab,), jnp.float32),
    )


def pool_bytes_per_row(pk: int) -> int:
    """Resident pool bytes per series row (flat planes tile unpadded):
    the capacity-plan number docs/tiered.md quotes."""
    return 2 * pk * 2 + 2 * pk * 4 + 9 * 4


def _pool_compact(pool: PoolSlab, slab: int, pk: int, pcomp: float,
                  use_pallas: bool):
    """Sort-compact-merge the in-flight bins with the packed centroid
    planes: dequantize, sort the bin centroids, fuse through the shared
    compress kernel (Pallas on TPU, sort-based XLA elsewhere / under
    the breaker). Returns drained f32 (mean, weight) [slab, PK] — the
    caller either requantizes (guard drain) or flushes them."""
    m, w = td_ops.dequantize_centroids(
        pool.mq.reshape(slab, pk), pool.wb.reshape(slab, pk),
        pool.fmin, pool.fmax)
    b_w = pool.bw.reshape(slab, pk)
    b_live = b_w > 0
    b_m = jnp.where(b_live,
                    pool.bwm.reshape(slab, pk) / jnp.where(b_live, b_w, 1.0),
                    jnp.inf)
    b_m, b_w = lax.sort((b_m, b_w), dimension=-1, num_keys=1,
                        is_stable=False)
    return td_ops._dispatch_compress_presorted(m, w, b_m, b_w, pcomp, pk,
                                               use_pallas=use_pallas)


def _pool_guard_masses(pool: PoolSlab, rows, values, weights, slab: int,
                       pk: int, pcomp: float):
    """The three guard-trigger signals of :func:`_guard_drain_pool`,
    exposed UN-thresholded so the mesh pool (``fleet/mesh_tiered.py``)
    can psum them over the series axis before deciding — every shard
    must take the SAME drain the single-device pool would on the same
    data (the ``ops/tdigest.py shift_masses`` decomposition, pool
    form). Returns ``(shifted, total, over_dom)``: the shift-guard
    mass pair plus the count of rows tripping the clump/dominance
    triggers (an any() that sums exactly over disjoint row sets)."""
    shifted, total = td_ops.shift_masses(pool.bw, pool.bwm, rows, values,
                                         weights, slab, anchors=pk)
    inc = jnp.zeros((slab + 1,), jnp.float32).at[rows].add(
        weights.astype(jnp.float32), mode="drop")[:slab]
    _, pw = td_ops.dequantize_centroids(
        pool.mq.reshape(slab, pk), pool.wb.reshape(slab, pk),
        pool.fmin, pool.fmax)
    bw2 = pool.bw.reshape(slab, pk)
    tot = jnp.sum(pw, axis=1) + jnp.sum(bw2, axis=1)
    over = ((inc > 0) & (tot > float(pk))
            & (jnp.max(bw2, axis=1) + inc > 2.0 * (tot + inc) / pcomp))
    # Third trigger: a chunk-DOMINANT row (inc > tot — the same condition
    # that routes the row onto merged-rank k-scale bin ids in
    # bin_pool_samples) whose live bins still carry bracket/bisect-path
    # ids. Those ids encode insertion order, not k-scale position, so
    # the dominant chunk's mid-rank mass scatters ONTO them: measured on
    # 2g's promoted rows, a row's two cold extremes sat at mid ids and
    # absorbed the ramp chunk's median samples, dragging the merged
    # cluster mean half a distribution away (0.16 rank error at p50).
    # Draining first hands the chunk empty, cleanly k-scale-id'd bins
    # and turns the history into value-sorted packed centroids the
    # merged-rank anchor reads exactly.
    dom = (inc > tot) & (jnp.sum(bw2, axis=1) > 0)
    over_dom = (jnp.sum(over.astype(jnp.float32))
                + jnp.sum(dom.astype(jnp.float32)))
    return shifted, total, over_dom


def _pool_guard_apply(pool: PoolSlab, pred, slab: int, pk: int,
                      pcomp: float, use_pallas: bool) -> PoolSlab:
    """Conditionally sort-compact-merge the bins into the packed planes
    (the drain half of the guard; pred must already be reduced to a
    scalar — threshold the :func:`_pool_guard_masses` signals first)."""

    def do_drain(p):
        nm, nw = _pool_compact(p, slab, pk, pcomp, use_pallas)
        mq, wb, fmin, fmax = td_ops.quantize_centroids(nm, nw)
        return p._replace(mq=mq.reshape(-1), wb=wb.reshape(-1),
                          fmin=fmin, fmax=fmax,
                          bw=jnp.zeros_like(p.bw),
                          bwm=jnp.zeros_like(p.bwm))

    return lax.cond(pred, do_drain, lambda p: p, pool)


def _guard_drain_pool(pool: PoolSlab, rows, values, weights, slab: int,
                      pk: int, pcomp: float, use_pallas: bool) -> PoolSlab:
    """The pool form of the shift guard: when the chunk's per-row value
    ranges are disjoint from what the bins cover for enough chunk mass,
    sort-compact-merge the bins into the packed planes first so fresh
    bins re-anchor (lax.cond — stationary traffic pays one reduction).

    A second trigger bounds bin CLUMPING: value-bracketed placement has
    no per-bin mass cap, and the ID-bisection used for new extremes
    leaves some bin ids unreachable, so under chunk-solo arrival an
    oversubscribed row (count > PK) can pile 0.16+ of its mass onto
    one shared bin (measured on 2g's promoted rows) — past the ~2/C
    k-scale envelope the compact maintains and the quantile error
    budget assumes. Draining is only useful BEFORE a clump forms (the
    compressor merges, it cannot split), so the trip fires when a
    targeted row's heaviest bin WOULD cross its envelope with this
    chunk's mass added: the bins compact into the packed planes (each
    cluster k-scale-capped) and all PK bin ids free up to re-anchor.
    Rows with count <= PK sit in exact singleton bins and never trip,
    so stationary sparse traffic stays one reduction per chunk. The
    third (dominance) trigger is documented in _pool_guard_masses."""
    shifted, total, over_dom = _pool_guard_masses(
        pool, rows, values, weights, slab, pk, pcomp)
    pred = (shifted > td_ops.SHIFT_GUARD_FRAC
            * jnp.maximum(total, jnp.finfo(jnp.float32).tiny)) \
        | (over_dom > 0)
    return _pool_guard_apply(pool, pred, slab, pk, pcomp, use_pallas)


def _pool_ingest_impl(pool: PoolSlab, rows, values, weights, slab: int,
                      pk: int, pcomp: float,
                      use_pallas: bool = True) -> PoolSlab:
    """Scatter one flat sample chunk into a pool slab's bins + stats,
    behind the shift guard. rows are slab-LOCAL; >= slab is padding.
    Plain function: the jitted single-device program and the mesh
    store's shard_map body (fleet/mesh_tiered.py, which swaps in a
    psum'd guard decision) both build on the pieces below."""
    oor = rows >= slab
    rows = jnp.where(oor, slab, rows)
    weights = jnp.where(oor, 0.0, weights)
    pool = _guard_drain_pool(pool, rows, values, weights, slab, pk, pcomp,
                             use_pallas)
    return _pool_scatter_samples(pool, rows, values, weights, slab, pk,
                                 pcomp)


def _pool_scatter_samples(pool: PoolSlab, rows, values, weights,
                          slab: int, pk: int, pcomp: float) -> PoolSlab:
    """The post-guard half of the sample ingest: bin + scatter."""
    r, v, w, b = td_ops.bin_pool_samples(
        rows, values, weights, slab, pk, pcomp, pool.bw, pool.bwm,
        pool.mq, pool.wb, pool.fmin, pool.fmax)
    live = w > 0
    vz = jnp.where(live, v, 0.0)
    flat = jnp.where(r >= slab, slab * pk, r * pk + b)
    return pool._replace(
        bw=pool.bw.at[flat].add(w, mode="drop"),
        bwm=pool.bwm.at[flat].add(w * vz, mode="drop"),
        count=pool.count.at[r].add(w, mode="drop"),
        vsum=pool.vsum.at[r].add(w * vz, mode="drop"),
        vmin=pool.vmin.at[r].min(jnp.where(live, v, jnp.inf), mode="drop"),
        vmax=pool.vmax.at[r].max(jnp.where(live, v, -jnp.inf), mode="drop"),
        recip=pool.recip.at[r].add(jnp.where(live, w / v, 0.0),
                                   mode="drop"),
    )


@partial(jax.jit, donate_argnums=(0,), static_argnums=(4, 5, 6, 7))
def _pool_ingest(pool: PoolSlab, rows, values, weights, slab: int, pk: int,
                 pcomp: float, use_pallas: bool = True) -> PoolSlab:
    """The jitted single-device sample-ingest program (see
    ``_pool_ingest_impl``)."""
    return _pool_ingest_impl(pool, rows, values, weights, slab, pk, pcomp,
                             use_pallas)


def _pool_import_impl(pool: PoolSlab, rows, means, weights, stat_rows,
                      stat_mins, stat_maxs, slab: int, pk: int,
                      pcomp: float, use_pallas: bool = True) -> PoolSlab:
    """Fold imported digest CENTROIDS into a pool slab without touching
    the local scalar stats (samplers.go:473-480); imported per-digest
    extrema land on dmin/dmax and only bound the final digest."""
    oor = rows >= slab
    rows = jnp.where(oor, slab, rows)
    weights = jnp.where(oor, 0.0, weights)
    pool = _guard_drain_pool(pool, rows, means, weights, slab, pk, pcomp,
                             use_pallas)
    return _pool_scatter_imports(pool, rows, means, weights, stat_rows,
                                 stat_mins, stat_maxs, slab, pk, pcomp)


def _pool_scatter_imports(pool: PoolSlab, rows, means, weights, stat_rows,
                          stat_mins, stat_maxs, slab: int, pk: int,
                          pcomp: float) -> PoolSlab:
    """The post-guard half of the centroid import: bin + scatter."""
    r, v, w, b = td_ops.bin_pool_samples(
        rows, means, weights, slab, pk, pcomp, pool.bw, pool.bwm,
        pool.mq, pool.wb, pool.fmin, pool.fmax)
    live = w > 0
    vz = jnp.where(live, v, 0.0)
    flat = jnp.where(r >= slab, slab * pk, r * pk + b)
    return pool._replace(
        bw=pool.bw.at[flat].add(w, mode="drop"),
        bwm=pool.bwm.at[flat].add(w * vz, mode="drop"),
        dmin=pool.dmin.at[stat_rows].min(stat_mins, mode="drop"),
        dmax=pool.dmax.at[stat_rows].max(stat_maxs, mode="drop"),
    )


@partial(jax.jit, donate_argnums=(0,), static_argnums=(7, 8, 9, 10))
def _pool_import(pool: PoolSlab, rows, means, weights, stat_rows,
                 stat_mins, stat_maxs, slab: int, pk: int, pcomp: float,
                 use_pallas: bool = True) -> PoolSlab:
    """The jitted single-device centroid-import program (see
    ``_pool_import_impl``)."""
    return _pool_import_impl(pool, rows, means, weights, stat_rows,
                             stat_mins, stat_maxs, slab, pk, pcomp,
                             use_pallas)


def _pool_flush_impl(pool: PoolSlab, qs, slab: int, pk: int, pcomp: float,
                     use_pallas: bool = True):
    """Flush one pool slab directly from the packed representation:
    sort-compact-merge bins into the (dequantized) packed centroids,
    quantile over the result — never a dense [S, K] densify. Returns
    flat drained planes (so a forwarding flush can feed them straight
    to ``_pack_slab``) plus extrema and the scalar stats."""
    nm, nw = _pool_compact(pool, slab, pk, pcomp, use_pallas)
    mn = jnp.minimum(pool.vmin, pool.dmin)
    mx = jnp.maximum(pool.vmax, pool.dmax)
    d = td_ops.TDigest(mean=nm, weight=nw, min=mn, max=mx)
    pcts = td_ops.quantile(d, qs)
    return (nm.reshape(-1), nw.reshape(-1), mn, mx, pcts, pool.count,
            pool.vsum, pool.vmin, pool.vmax, pool.recip)


@partial(jax.jit, donate_argnums=(0,), static_argnums=(2, 3, 4, 5))
def _pool_flush(pool: PoolSlab, qs, slab: int, pk: int, pcomp: float,
                use_pallas: bool = True):
    """The jitted single-device pool-flush program (see
    ``_pool_flush_impl``)."""
    return _pool_flush_impl(pool, qs, slab, pk, pcomp, use_pallas)


def _promote_rows_impl(pool: PoolSlab, temp: td_ops.TempCentroids, ddmin,
                       ddmax, rows, slots, slab: int, pk: int,
                       compression: float):
    """Move candidate rows' pool state into the dense tier ON DEVICE:
    dequantized packed centroids + bin centroids re-enter the dense
    temp's binning pipeline as weighted samples (update_stats=False,
    like any centroid import), the scalar stats scatter-add into the
    dense accumulators, and the pool rows clear — counts conserved
    exactly. rows are slab-LOCAL (>= slab is padding); slots are dense
    slot ids (rows past the dense capacity drop, which padding uses)."""
    nslots = temp.num_series
    valid = rows < slab
    rc = jnp.minimum(rows, slab - 1)
    sl = jnp.where(valid, slots, nslots)
    m, w = td_ops.dequantize_centroids(
        pool.mq.reshape(slab, pk)[rc], pool.wb.reshape(slab, pk)[rc],
        pool.fmin[rc], pool.fmax[rc])
    b_w = pool.bw.reshape(slab, pk)[rc]
    b_live = b_w > 0
    b_m = jnp.where(b_live,
                    pool.bwm.reshape(slab, pk)[rc]
                    / jnp.where(b_live, b_w, 1.0), 0.0)
    w = jnp.where(valid[:, None], w, 0.0)
    b_w = jnp.where(valid[:, None], b_w, 0.0)
    mflat = jnp.concatenate([jnp.where(w > 0, m, 0.0), b_m],
                            axis=1).reshape(-1)
    wflat = jnp.concatenate([w, b_w], axis=1).reshape(-1)
    srep = jnp.broadcast_to(sl[:, None], (sl.shape[0], 2 * pk)).reshape(-1)
    srep = jnp.where(wflat > 0, srep, nslots)
    temp = td_ops.ingest_chunk(temp, srep, mflat, wflat, compression,
                               update_stats=False)
    temp = temp._replace(
        count=temp.count.at[sl].add(
            jnp.where(valid, pool.count[rc], 0.0), mode="drop"),
        vsum=temp.vsum.at[sl].add(
            jnp.where(valid, pool.vsum[rc], 0.0), mode="drop"),
        vmin=temp.vmin.at[sl].min(
            jnp.where(valid, pool.vmin[rc], jnp.inf), mode="drop"),
        vmax=temp.vmax.at[sl].max(
            jnp.where(valid, pool.vmax[rc], -jnp.inf), mode="drop"),
        recip=temp.recip.at[sl].add(
            jnp.where(valid, pool.recip[rc], 0.0), mode="drop"),
    )
    ddmin = ddmin.at[sl].min(jnp.where(valid, pool.dmin[rc], jnp.inf),
                             mode="drop")
    ddmax = ddmax.at[sl].max(jnp.where(valid, pool.dmax[rc], -jnp.inf),
                             mode="drop")
    rz = jnp.where(valid, rows, slab)
    pool = PoolSlab(
        mq=pool.mq.reshape(slab, pk).at[rz].set(
            0, mode="drop").reshape(-1),
        wb=pool.wb.reshape(slab, pk).at[rz].set(
            0, mode="drop").reshape(-1),
        fmin=pool.fmin.at[rz].set(jnp.inf, mode="drop"),
        fmax=pool.fmax.at[rz].set(-jnp.inf, mode="drop"),
        bw=pool.bw.reshape(slab, pk).at[rz].set(
            0.0, mode="drop").reshape(-1),
        bwm=pool.bwm.reshape(slab, pk).at[rz].set(
            0.0, mode="drop").reshape(-1),
        dmin=pool.dmin.at[rz].set(jnp.inf, mode="drop"),
        dmax=pool.dmax.at[rz].set(-jnp.inf, mode="drop"),
        count=pool.count.at[rz].set(0.0, mode="drop"),
        vsum=pool.vsum.at[rz].set(0.0, mode="drop"),
        vmin=pool.vmin.at[rz].set(jnp.inf, mode="drop"),
        vmax=pool.vmax.at[rz].set(-jnp.inf, mode="drop"),
        recip=pool.recip.at[rz].set(0.0, mode="drop"),
    )
    return pool, temp, ddmin, ddmax


@partial(jax.jit, donate_argnums=(0, 1, 2, 3), static_argnums=(6, 7, 8))
def _promote_rows(pool: PoolSlab, temp: td_ops.TempCentroids, ddmin, ddmax,
                  rows, slots, slab: int, pk: int, compression: float):
    """The jitted single-device promotion program (see
    ``_promote_rows_impl``)."""
    return _promote_rows_impl(pool, temp, ddmin, ddmax, rows, slots, slab,
                              pk, compression)


def _pool_restore_stats_impl(pool: PoolSlab, rows, count, vsum, vmin,
                             vmax, recip, slab: int) -> PoolSlab:
    """Scatter recovered per-row scalar stats into a pool slab (the
    checkpoint-restore twin of ``core.store._restore_temp_stats``)."""
    rz = jnp.where(rows >= slab, slab, rows)
    return pool._replace(
        count=pool.count.at[rz].add(count, mode="drop"),
        vsum=pool.vsum.at[rz].add(vsum, mode="drop"),
        vmin=pool.vmin.at[rz].min(vmin, mode="drop"),
        vmax=pool.vmax.at[rz].max(vmax, mode="drop"),
        recip=pool.recip.at[rz].add(recip, mode="drop"),
    )


@partial(jax.jit, donate_argnums=(0,), static_argnums=(7,))
def _pool_restore_stats(pool: PoolSlab, rows, count, vsum, vmin, vmax,
                        recip, slab: int) -> PoolSlab:
    """The jitted single-device restore-stats program (see
    ``_pool_restore_stats_impl``)."""
    return _pool_restore_stats_impl(pool, rows, count, vsum, vmin, vmax,
                                    recip, slab)


def dequantize_host(mq: np.ndarray, wb: np.ndarray, fmin: np.ndarray,
                    fmax: np.ndarray):
    """Host-side (numpy) twin of ``ops/tdigest.dequantize_centroids``:
    the PackedDigestPlanes u16 contract. Shared by the checkpoint
    snapshot's flatten and the mesh tiered group's promotion path."""
    weight = (wb.astype(np.uint32) << 16).view(np.float32)
    span = np.where(np.isfinite(fmax - fmin), fmax - fmin, 0.0)
    base = np.where(np.isfinite(fmin), fmin, 0.0)
    mean = base[:, None] + mq.astype(np.float32) * (span[:, None]
                                                    / 65535.0)
    return mean, weight.astype(np.float32)


class TierDirectory:
    """Cross-generation promote/demote memory, shared by every
    generation's twin of one tiered group (``fresh()`` hands it on).

    Keys are (name, joined_tags) pairs — the group's rows re-intern
    every interval, so tier residency must key on series identity.
    Guarded by its OWN lock: the live generation reads it at intern
    time under the store lock (a one-way store→directory edge), while
    the retired generation's flush updates it off-lock; the directory
    never acquires any other lock, so no cycle is possible. Size is
    bounded by the dense row count plus the rows hot in the last
    interval (cold entries are dropped, not idled)."""

    def __init__(self, promote_samples: int = DEFAULT_PROMOTE_SAMPLES,
                 promote_intervals: int = DEFAULT_PROMOTE_INTERVALS,
                 demote_intervals: int = DEFAULT_DEMOTE_INTERVALS):
        self._lock = threading.Lock()
        self.promote_samples = max(int(promote_samples), 1)
        self.promote_intervals = max(int(promote_intervals), 1)
        self.demote_intervals = max(int(demote_intervals), 1)
        self._dense: Dict[Tuple[str, str], int] = {}  # key -> idle count
        self._warm: Dict[Tuple[str, str], int] = {}   # key -> hot streak
        self.promotions = 0
        self.demotions = 0

    def is_dense(self, key: Tuple[str, str]) -> bool:
        with self._lock:
            return key in self._dense

    def dense_count(self) -> int:
        with self._lock:
            return len(self._dense)

    def should_promote(self, key: Tuple[str, str]) -> bool:
        """Mid-interval check once a row's interval activity crossed
        ``promote_samples``: the streak carried from past intervals
        plus the current one must reach ``promote_intervals``."""
        with self._lock:
            if key in self._dense:
                return False
            return self._warm.get(key, 0) + 1 >= self.promote_intervals

    def note_promoted(self, keys) -> None:
        with self._lock:
            for k in keys:
                self._warm.pop(k, None)
                if k not in self._dense:
                    self._dense[k] = 0
                    self.promotions += 1

    def end_interval(self, hot_keys) -> None:
        """Flush-boundary bookkeeping (called off-lock on the retired
        generation): hot pool keys build their promotion streak; dense
        keys idle below the activity bar for ``demote_intervals``
        consecutive intervals demote back to the pool — the hysteresis
        that keeps a series oscillating around the threshold from
        ping-ponging a dense slot."""
        hot = set(hot_keys)
        with self._lock:
            new_warm = {}
            for k in hot:
                if k in self._dense:
                    continue
                streak = self._warm.get(k, 0) + 1
                if streak >= self.promote_intervals:
                    self._dense[k] = 0
                    self.promotions += 1
                else:
                    new_warm[k] = streak
            self._warm = new_warm
            dropped = []
            for k, idle in self._dense.items():
                if k in hot:
                    self._dense[k] = 0
                else:
                    idle += 1
                    if idle >= self.demote_intervals:
                        dropped.append(k)
                    else:
                        self._dense[k] = idle
            for k in dropped:
                del self._dense[k]
                self.demotions += 1


def _splice_packed(n: int, pool_counts: np.ndarray, pool_mq: np.ndarray,
                   pool_wb: np.ndarray, dense_rows: np.ndarray,
                   d_counts: np.ndarray, d_mq: np.ndarray,
                   d_wb: np.ndarray):
    """Stitch the pool tier's packed output (global-row order, zero
    counts at dense-assigned rows) with the dense tier's (slot order)
    into one global-row-ordered packed triple. Pure numpy, O(L)."""
    counts = pool_counts.astype(np.int64)
    if len(dense_rows):
        counts[dense_rows] = d_counts.astype(np.int64)
    out_ends = np.cumsum(counts)
    out_starts = out_ends - counts
    total = int(out_ends[-1]) if n else 0
    mq = np.zeros(total, np.uint16)
    wb = np.zeros(total, np.uint16)
    pc = pool_counts.astype(np.int64)
    if pool_mq.size:
        rows_rep = np.repeat(np.arange(n, dtype=np.int64), pc)
        pstarts = np.cumsum(pc) - pc
        within = np.arange(pool_mq.size, dtype=np.int64) \
            - np.repeat(pstarts, pc)
        pos = out_starts[rows_rep] + within
        mq[pos] = pool_mq
        wb[pos] = pool_wb
    if len(dense_rows) and d_mq.size:
        dc = d_counts.astype(np.int64)
        drep = np.repeat(dense_rows, dc)
        dstarts = np.cumsum(dc) - dc
        dwithin = np.arange(d_mq.size, dtype=np.int64) \
            - np.repeat(dstarts, dc)
        pos = out_starts[drep] + dwithin
        mq[pos] = d_mq
        wb[pos] = d_wb
    return counts.astype(np.uint16), mq, wb


from veneur_tpu.core.store import (  # noqa: E402  (cycle-safe: store
    # imports tiered lazily inside MetricStore.__init__, like slab)
    DEFAULT_CHUNK, DEFAULT_INITIAL_CAPACITY, DigestGroup, Interner,
    OverloadLimited, bulk_stage_import_centroids, run_compute_ladder)
from veneur_tpu.core.slab import (  # noqa: E402
    _fetch_packed, _fill_stat_results, _pack_slab, _select_stats)
from veneur_tpu.overload import F32_ABS_MAX, MIN_SAMPLE_RATE  # noqa: E402


class TieredDigestGroup(OverloadLimited):
    """Drop-in ``DigestGroup`` replacement with packed↔dense residency
    (``digest_storage: tiered``). Same public surface — interner,
    sample / sample_many / import_centroids staging, flush ->
    (interner, result dict) with identical keys, two-phase snapshot —
    but every series lives in the packed pool until the
    :class:`TierDirectory` promotes it, and the flush runs the pool
    directly from the packed representation."""

    _retired = False  # see core.store.DigestGroup._retired

    def __init__(self, slab_rows: int = POOL_SLAB_ROWS_DEFAULT,
                 chunk: int = DEFAULT_CHUNK,
                 compression: float = td_ops.DEFAULT_COMPRESSION,
                 pool_centroids: int = DEFAULT_POOL_CENTROIDS,
                 promote_samples: int = DEFAULT_PROMOTE_SAMPLES,
                 promote_intervals: int = DEFAULT_PROMOTE_INTERVALS,
                 demote_intervals: int = DEFAULT_DEMOTE_INTERVALS,
                 dense_capacity: int = DEFAULT_INITIAL_CAPACITY,
                 directory: Optional[TierDirectory] = None):
        self.interner = Interner()
        self.compression = compression
        self.k = td_ops.size_bound(compression)
        self.chunk = chunk
        if slab_rows <= 0:
            raise ValueError(f"slab_rows must be positive, got {slab_rows}")
        self.slab_rows = min(slab_rows, 1 << 20)
        pk = int(pool_centroids)
        if pk < 8 or pk & (pk - 1):
            raise ValueError(
                f"pool_centroids must be a power of two >= 8, got {pk}")
        # the pool can never hold more centroids per row than the dense
        # tier's K (flush stitching widens pool rows into [n, K] planes)
        self.pk = min(pk, self.k)
        if self.pk != pk:
            log.warning(
                "tier_pool_centroids=%d exceeds the dense tier's %d-slot "
                "digest at compression %.0f; clamped to %d (non-pow2 "
                "slabs, higher resident bytes/row than configured)",
                pk, self.k, compression, self.pk)
        # k-scale compression for the pool's binning: c+2 clusters fill
        # exactly the PK slots (ops/tdigest.py size_bound rationale)
        self.pcomp = float(self.pk - 2)
        self.promote_samples = max(int(promote_samples), 1)
        self.directory = directory if directory is not None else \
            TierDirectory(promote_samples, promote_intervals,
                          demote_intervals)
        self._dense = self._make_dense_bank(dense_capacity, chunk,
                                            compression)
        self.pools: List[PoolSlab] = [self._new_pool_slab()]
        self._device_dirty = False
        self._slot = np.full(self.slab_rows, -1, np.int32)
        self._activity = np.zeros(self.slab_rows, np.int64)
        self._dense_rows: List[int] = []
        self._new_sample_buffers()
        self._new_import_buffers()

    def _make_dense_bank(self, dense_capacity: int, chunk: int,
                         compression: float) -> DigestGroup:
        """The hot-tier bank (override point: the mesh tiered group
        embeds a series-sharded MeshDigestGroup in slot mode)."""
        return DigestGroup(dense_capacity, chunk, compression)

    def _new_pool_slab(self) -> PoolSlab:
        """One empty pool slab (override point: the mesh tiered group
        places the planes onto the series axis)."""
        return _init_pool_slab(self.slab_rows, self.pk)

    # -- capacity ---------------------------------------------------------

    @property
    def capacity(self) -> int:
        return len(self.pools) * self.slab_rows

    def hbm_bytes(self) -> dict:
        """Resident-plane byte accounting (flat planes tile unpadded):
        the capacity-plan numbers the ``2g_tiered_10m`` bench lane and
        docs/tiered.md report. Dense rows cost the full-K footprint
        (digest + temp + anchor summary + scalars); pool rows cost
        ~228 B at PK=16."""
        a = td_ops.BELOW_MASS_ANCHORS
        dense_per_row = self.k * 4 * 4 + a * 2 * 4 + 9 * 4
        pool_bytes = self.capacity * pool_bytes_per_row(self.pk)
        dense_bytes = self._dense.capacity * dense_per_row
        return {"pool_bytes": pool_bytes,
                "dense_bytes": dense_bytes,
                "total_bytes": pool_bytes + dense_bytes,
                "pool_bytes_per_row": pool_bytes_per_row(self.pk),
                "dense_bytes_per_row": dense_per_row,
                "dense_rows": len(self._dense_rows),
                "pool_rows": self.capacity}

    def __len__(self):
        return len(self.interner)

    def fresh(self) -> "TieredDigestGroup":
        """Empty same-config twin (swap-on-flush generation swap); the
        shared TierDirectory carries the promote/demote state across
        the swap — residency is a property of the SERIES, not of one
        generation's rows."""
        return TieredDigestGroup(
            self.slab_rows, self.chunk, self.compression, self.pk,
            self.directory.promote_samples,
            self.directory.promote_intervals,
            self.directory.demote_intervals,
            self._dense.capacity, directory=self.directory)

    @requires_lock("store")
    def ensure_capacity(self, max_row: int):
        while max_row >= self.capacity:
            self.pools.append(self._new_pool_slab())
            self._rows[self._fill:] = self.capacity
            self._imp_rows[self._imp_fill:] = self.capacity
            self._imp_stat_rows[self._imp_stat_fill:] = self.capacity
        if max_row >= len(self._slot):
            grow = self.capacity - len(self._slot)
            self._slot = np.concatenate(
                [self._slot, np.full(grow, -1, np.int32)])
            self._activity = np.concatenate(
                [self._activity, np.zeros(grow, np.int64)])

    @requires_lock("store")
    def _row(self, key, tags) -> int:
        first_sight = len(self.interner)
        row = self._intern_row(key, tags)
        if row >= self.capacity:
            self.ensure_capacity(row)
        # a first-sight spill interns the overflow row at exactly
        # first_sight too — it must not inherit the SAMPLED key's
        # dense residency
        if (row == first_sight and row != self._overflow_row
                and self.directory.is_dense(
                    (key.name, key.joined_tags))):
            self._assign_dense(row)
        return row

    @requires_lock("store")
    def _assign_dense(self, row: int) -> int:
        slot = len(self._dense_rows)
        self._dense_rows.append(row)
        self._slot[row] = slot
        self._dense.ensure_capacity(slot)
        return slot

    def _sync_plumbing(self):
        """Thread the outer group's breaker onto the embedded dense
        bank (MetricStore stamps overload attrs on the OUTER group at
        each generation swap); the dense bank's quarantine stays off —
        the outer staging already scrubbed everything it forwards."""
        self._dense._compute = self._compute

    # -- staging ----------------------------------------------------------

    def _new_sample_buffers(self):
        # fresh buffers per drain; see DigestGroup._new_sample_buffers
        self._rows = np.full(self.chunk, self.capacity, np.int32)
        self._vals = np.zeros(self.chunk, np.float32)
        self._wts = np.zeros(self.chunk, np.float32)
        self._fill = 0

    def _new_import_buffers(self):
        self._imp_rows = np.full(self.chunk, self.capacity, np.int32)
        self._imp_means = np.zeros(self.chunk, np.float32)
        self._imp_wts = np.zeros(self.chunk, np.float32)
        self._imp_fill = 0
        self._imp_stat_rows = np.full(self.chunk, self.capacity, np.int32)
        self._imp_stat_mins = np.full(self.chunk, np.inf, np.float32)
        self._imp_stat_maxs = np.full(self.chunk, -np.inf, np.float32)
        self._imp_stat_fill = 0

    @requires_lock("store")
    def sample(self, key, tags, value: float, sample_rate: float):
        # numerics quarantine, mirroring DigestGroup.sample
        if not math.isfinite(value) or abs(value) > F32_ABS_MAX:
            self._quarantine_samples(
                "not_finite" if not math.isfinite(value)
                else "out_of_range")
            return
        if not MIN_SAMPLE_RATE <= sample_rate <= 1:
            self._quarantine_samples("bad_rate")
            return
        row = self._row(key, tags)
        self._activity[row] += 1
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
        if len(rows):
            np.add.at(self._activity, rows, 1)
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
        self._activity[row] += n
        # keep one digest's sorted centroid run inside one staging drain
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
        """Bulk staging append (rows pre-interned by the caller); shares
        DigestGroup's staging protocol."""
        if len(rows):
            np.add.at(self._activity, rows, 1)
        bulk_stage_import_centroids(self, rows, means, weights, stat_rows,
                                    stat_mins, stat_maxs)

    # -- drains -----------------------------------------------------------

    def _partition(self, rows: np.ndarray, *arrays):
        """Split staged entries into (dense slots, arrays) plus per-pool-
        slab (slab_idx, local_rows, arrays) pow2-padded spans. Sentinel
        rows (== capacity) and dense-assigned rows drop out of the pool
        spans; order within a row's run is preserved (partition masks
        are order-stable)."""
        valid = rows < self.capacity
        slot = np.where(valid, self._slot[np.minimum(rows,
                                                     self.capacity - 1)],
                        -1)
        dmask = valid & (slot >= 0)
        dense = None
        if dmask.any():
            dense = (slot[dmask].astype(np.int32),
                     [a[dmask] for a in arrays])
        pmask = valid & (slot < 0)
        pool_spans = []
        if pmask.any():
            prow = rows[pmask]
            parrs = [a[pmask] for a in arrays]
            slabs = prow // self.slab_rows
            for i in np.unique(slabs):
                sel = slabs == i
                m = int(sel.sum())
                pad = _next_pow2(m)
                local = np.full(pad, self.slab_rows, np.int32)
                local[:m] = prow[sel] - i * self.slab_rows
                padded = []
                for a in parrs:
                    buf = np.zeros(pad, a.dtype)
                    buf[:m] = a[sel]
                    padded.append(buf)
                pool_spans.append((int(i), local, padded))
        return dense, pool_spans

    @requires_lock("store")
    def _drain_samples(self):
        if self._fill == 0:
            return
        self._device_dirty = True
        self._sync_plumbing()
        rows, vals, wts = self._rows, self._vals, self._wts
        fill = self._fill
        self._new_sample_buffers()
        dense, pool_spans = self._partition(rows, vals, wts)
        if dense is not None:
            slots, (v, w) = dense
            self._dense.sample_many(slots, v, w)
        up = self._pallas_allowed()
        for i, local, (v, w) in pool_spans:
            self._pool_drain_samples(i, local, v, w, up)
        self._maybe_promote(np.unique(rows[:fill]))

    def _pool_drain_samples(self, i: int, local, vals, wts,
                            use_pallas: bool):
        """Dispatch one slab's routed sample span (override point: the
        mesh tiered group re-routes the span per shard and runs the
        sharded program)."""
        with obs_kernels.scope("drain.digest.tiered"):
            self.pools[i] = _pool_ingest(
                self.pools[i], jnp.asarray(local), jnp.asarray(vals),
                jnp.asarray(wts), self.slab_rows, self.pk, self.pcomp,
                use_pallas)

    @requires_lock("store")
    def _drain_imports(self):
        if self._imp_fill == 0 and self._imp_stat_fill == 0:
            return
        self._device_dirty = True
        self._sync_plumbing()
        rows, means, wts = self._imp_rows, self._imp_means, self._imp_wts
        ns = self._imp_stat_fill
        nf = self._imp_fill
        stat_rows = self._imp_stat_rows[:ns]
        stat_mins = self._imp_stat_mins[:ns]
        stat_maxs = self._imp_stat_maxs[:ns]
        self._new_import_buffers()
        dense_c, pool_c = self._partition(rows, means, wts)
        dense_s, pool_s = self._partition(stat_rows, stat_mins, stat_maxs)
        if dense_c is not None or dense_s is not None:
            slots, (m, w) = dense_c if dense_c is not None else \
                (np.empty(0, np.int32),
                 [np.empty(0, np.float32), np.empty(0, np.float32)])
            s_slots, (s_mn, s_mx) = dense_s if dense_s is not None else \
                (np.empty(0, np.int32),
                 [np.empty(0, np.float32), np.empty(0, np.float32)])
            self._dense.import_centroids_bulk(slots, m, w, s_slots, s_mn,
                                              s_mx)
        stats_by_slab = {i: (local, padded) for i, local, padded in pool_s}
        up = self._pallas_allowed()
        empty_r = np.full(2, self.slab_rows, np.int32)
        cents_by_slab = {i: (local, padded) for i, local, padded in pool_c}
        for i in sorted(set(cents_by_slab) | set(stats_by_slab)):
            c_local, c_pad = cents_by_slab.get(
                i, (empty_r, [np.zeros(2, np.float32),
                              np.zeros(2, np.float32)]))
            s_local, s_pad = stats_by_slab.get(
                i, (empty_r, [np.full(2, np.inf, np.float32),
                              np.full(2, -np.inf, np.float32)]))
            self._pool_drain_imports(i, c_local, c_pad[0], c_pad[1],
                                     s_local, s_pad[0], s_pad[1], up)
        self._maybe_promote(np.unique(rows[:nf]))

    def _pool_drain_imports(self, i: int, c_local, c_means, c_wts,
                            s_local, s_mins, s_maxs, use_pallas: bool):
        """Dispatch one slab's routed import span (override point, like
        ``_pool_drain_samples``)."""
        with obs_kernels.scope("drain.digest.tiered"):
            self.pools[i] = _pool_import(
                self.pools[i], jnp.asarray(c_local),
                jnp.asarray(c_means), jnp.asarray(c_wts),
                jnp.asarray(s_local), jnp.asarray(s_mins),
                jnp.asarray(s_maxs), self.slab_rows, self.pk,
                self.pcomp, use_pallas)

    @requires_lock("store")
    def _drain_staging(self):
        self._drain_samples()
        self._drain_imports()

    # -- promotion --------------------------------------------------------

    @requires_lock("store")
    def _maybe_promote(self, touched_rows: np.ndarray):
        """Promote pool rows whose interval activity crossed the bar
        (checked only over the rows the drained chunk touched, so the
        scan is O(chunk), never O(capacity)). The directory supplies
        the cross-interval hysteresis; the device program moves each
        row's pool state into its fresh dense slot and clears it."""
        n = len(self.interner)
        if not len(touched_rows):
            return
        cand = touched_rows[(touched_rows < n)
                            & (self._slot[touched_rows] < 0)
                            & (self._activity[touched_rows]
                               >= self.promote_samples)]
        if not len(cand):
            return
        names, joined = self.interner.names, self.interner.joined
        promote = [int(r) for r in cand
                   if self.directory.should_promote((names[r], joined[r]))]
        if not promote:
            return
        rows = np.asarray(promote, np.int64)
        slots = np.asarray([self._assign_dense(int(r)) for r in promote],
                           np.int32)
        self._sync_plumbing()
        d = self._dense
        d._drain_staging()  # promoted mass must land on settled bins
        d._device_dirty = True
        slabs = rows // self.slab_rows
        with obs_kernels.scope("drain.digest.tiered"):
            for i in np.unique(slabs):
                sel = slabs == i
                m = int(sel.sum())
                pad = _next_pow2(m)
                local = np.full(pad, self.slab_rows, np.int32)
                local[:m] = rows[sel] - i * self.slab_rows
                sl = np.full(pad, d.capacity, np.int32)
                sl[:m] = slots[sel]
                (self.pools[int(i)], d.temp, d.dmin,
                 d.dmax) = _promote_rows(
                    self.pools[int(i)], d.temp, d.dmin, d.dmax,
                    jnp.asarray(local), jnp.asarray(sl), self.slab_rows,
                    self.pk, self.compression)
        self.directory.note_promoted(
            [(names[r], joined[r]) for r in promote])
        log.debug("promoted %d series to the dense tier", len(promote))

    # -- flush ------------------------------------------------------------

    def _reset_device(self):
        nslabs = len(self.pools)
        self.pools = [self._new_pool_slab() for _ in range(nslabs)]
        self._dense._init_device()
        self._dense._init_staging()
        self._device_dirty = False

    def _drop_staging(self):
        """Release a RETIRED twin's host buffers (see
        SlabDigestGroup._drop_staging for the release-order audit)."""
        self._rows = self._vals = self._wts = None
        self._imp_rows = self._imp_means = self._imp_wts = None
        self._imp_stat_rows = self._imp_stat_mins = None
        self._imp_stat_maxs = None
        self._fill = 0
        self._imp_fill = 0
        self._imp_stat_fill = 0

    def flush(self, percentiles: List[float], want_digests=True,
              want_stats=None):
        """Identical contract to DigestGroup.flush: (old interner, dict
        of host arrays [:n]); want_digests="packed" re-packs BOTH tiers
        on device (the pool from its already-compacted flush output)
        and returns the spliced global-row-ordered packed triple. The
        device half runs behind the compute-breaker ladder; the
        interner swap and the directory's interval bookkeeping happen
        only after the programs + fetches succeed, so a failed ladder
        leaves the group recoverable for the store's re-merge rung."""
        # flush runs on the RETIRED generation, which this thread
        # exclusively owns (cf. MetricStore._flush_generation); direct
        # callers (tests, benches) own their group outright
        self._drain_staging()  # lint: ok(unlocked-call) flush runs on the RETIRED generation this thread exclusively owns; direct callers own their group outright
        n = len(self.interner)
        return self._flush_tiers(n, percentiles, want_digests, want_stats)

    def flush_begin(self, percentiles: List[float], want_digests=True,
                    want_stats=None):
        """Two-phase slot for the pipelined egress: the staged-chunk
        drains (pool binning + dense-bank ingest programs) DISPATCH
        asynchronously now, and the two-tier flush itself runs in
        ``finish()`` — the tiered group overlaps at the STORE level
        (other groups serialize/POST while this one computes and
        fetches); its internal per-slab fetch loop stays one phase."""
        with obs_rec.maybe_stage("drain"):
            self._drain_staging()  # lint: ok(unlocked-call) two-phase flush slot still runs on the RETIRED generation this thread exclusively owns
        n = len(self.interner)
        return lambda: self._flush_tiers(n, percentiles, want_digests,
                                         want_stats)

    def _flush_tiers(self, n: int, percentiles, want_digests, want_stats):
        if n == 0:
            interner, self.interner = self.interner, Interner()
            if self._retired:
                self.pools = []
                self._dense._drop_device()
                self._device_dirty = False
                self._drop_staging()
                return interner, {}
            if self._device_dirty:
                self._reset_device()
            self._new_sample_buffers()
            self._new_import_buffers()
            return interner, {}
        self._sync_plumbing()
        out = run_compute_ladder(
            self._compute,
            lambda use_pallas: self._flush_fetch(
                n, percentiles, want_digests, want_stats, use_pallas),
            # both tiers hand the ops f32 [rows, K] planes (the pool is
            # dequantized first), so one plane stands for the group
            jax.ShapeDtypeStruct((self.slab_rows, self.pk), jnp.float32))
        self._end_interval(n)
        interner, self.interner = self.interner, Interner()
        self._device_dirty = False
        if self._retired:
            self.pools = []
            self._dense._drop_device()
            self._drop_staging()
        else:
            # _flush_fetch already committed fresh pool slabs at its
            # commit point; only the dense bank still needs re-init
            self._dense._init_device()
            self._dense._init_staging()
            self._new_sample_buffers()
            self._new_import_buffers()
        self._slot = np.full(max(len(self._slot), self.slab_rows), -1,
                             np.int32)
        self._activity = np.zeros(len(self._slot), np.int64)
        self._dense_rows = []
        return interner, out

    def _end_interval(self, n: int):
        """Directory bookkeeping at the flush boundary: which series
        were hot this interval (promotion streaks build, idle dense
        rows demote). Host-only; safe off-lock on the retired twin."""
        act = self._activity[:n]
        hot_rows = np.flatnonzero(act >= self.promote_samples)
        names, joined = self.interner.names, self.interner.joined
        self.directory.end_interval(
            (names[r], joined[r]) for r in hot_rows)

    def _flush_fetch(self, n: int, percentiles, want_digests, want_stats,
                     use_pallas: bool) -> dict:
        """One complete flush attempt over both tiers. Pool slabs flush
        from the packed representation and fetch slab by slab (peak
        extra memory stays slab-sized); the dense bank reuses
        DigestGroup's program; results stitch into global-row order
        host-side. Fresh pool slabs commit only once every program +
        fetch succeeded (same donation caveat as the slab store)."""
        packed = want_digests == "packed"
        sel = _select_stats(want_stats)
        qs = jnp.asarray(list(percentiles) + [0.5], jnp.float32)
        R, pk = self.slab_rows, self.pk
        parts = []
        pk_counts, pk_means, pk_wts = [], [], []
        new_pools = list(self.pools)
        with obs_kernels.scope("flush.digest.tiered"):
            for i in range(len(self.pools)):
                need = min(n - i * R, R)
                (mean_flat, weight_flat, mn, mx, pcts, count, vsum, vmin,
                 vmax, recip) = _pool_flush(self.pools[i], qs, R, pk,
                                            self.pcomp, use_pallas)
                new_pools[i] = None if self._retired else \
                    self._new_pool_slab()
                if need <= 0:
                    continue
                planes = ()
                if packed:
                    cts, pm, pw = _pack_slab(mean_flat, weight_flat, mn,
                                             mx, R, pk)
                    c_h, pm_h, pw_h = _fetch_packed(cts, pm, pw, need)
                    pk_counts.append(c_h)
                    pk_means.append(pm_h)
                    pk_wts.append(pw_h)
                    planes = (mn[:need], mx[:need])
                elif want_digests:
                    planes = (mean_flat.reshape(R, pk)[:need],
                              weight_flat.reshape(R, pk)[:need],
                              mn[:need], mx[:need])
                stats = {"pcts": pcts, "count": count, "sum": vsum,
                         "min": vmin, "max": vmax, "recip": recip}
                with obs_rec.maybe_stage("fetch"):
                    parts.append(jax.device_get(
                        planes + tuple(stats[nm][:need] for nm in sel)))
        nd = len(self._dense_rows)
        dense_out = None
        if nd:
            self._dense._drain_staging()
            dense_out = self._dense._flush_fetch(
                nd, percentiles, want_digests, want_stats, use_pallas)
        # every program + fetch succeeded: commit the fresh pool slabs
        self.pools = [] if self._retired else \
            [p for p in new_pools if p is not None]
        cols = [np.concatenate(c, axis=0) for c in zip(*parts)]
        out = {}
        dense_rows = np.asarray(self._dense_rows, np.int64)
        if packed:
            pool_mn, pool_mx = cols[:2]
            cols = cols[2:]
            p_counts = np.concatenate(pk_counts) if pk_counts else \
                np.zeros(n, np.uint16)
            p_mq = np.concatenate(pk_means) if pk_means else \
                np.empty(0, np.uint16)
            p_wb = np.concatenate(pk_wts) if pk_wts else \
                np.empty(0, np.uint16)
            if nd:
                d_counts = dense_out["packed_counts"]
                d_mq = dense_out["packed_means"]
                d_wb = dense_out["packed_weights"]
            else:
                d_counts = np.empty(0, np.uint16)
                d_mq = d_wb = np.empty(0, np.uint16)
            (out["packed_counts"], out["packed_means"],
             out["packed_weights"]) = _splice_packed(
                n, p_counts, p_mq, p_wb, dense_rows, d_counts, d_mq,
                d_wb)
            out["digest_min"] = np.asarray(pool_mn, np.float32).copy()
            out["digest_max"] = np.asarray(pool_mx, np.float32).copy()
            if nd:
                out["digest_min"][dense_rows] = dense_out["digest_min"]
                out["digest_max"][dense_rows] = dense_out["digest_max"]
        elif want_digests:
            pm, pw, pool_mn, pool_mx = cols[:4]
            cols = cols[4:]
            mean_full = np.full((n, self.k), np.inf, np.float32)
            weight_full = np.zeros((n, self.k), np.float32)
            mean_full[:, :pk] = pm
            weight_full[:, :pk] = pw
            dmin_full = np.asarray(pool_mn, np.float32).copy()
            dmax_full = np.asarray(pool_mx, np.float32).copy()
            if nd:
                mean_full[dense_rows] = dense_out["digest_mean"]
                weight_full[dense_rows] = dense_out["digest_weight"]
                dmin_full[dense_rows] = dense_out["digest_min"]
                dmax_full[dense_rows] = dense_out["digest_max"]
            out["digest_mean"] = mean_full
            out["digest_weight"] = weight_full
            out["digest_min"] = dmin_full
            out["digest_max"] = dmax_full
        _fill_stat_results(sel, cols, n, percentiles, out)
        if nd:
            # stat arrays fetched via sel are fresh writable copies;
            # unfetched keys are zero on BOTH tiers, so only the
            # fetched ones need the dense overwrite
            for nm in sel:
                if nm == "pcts":
                    out["percentiles"] = out["percentiles"].copy()
                    out["median"] = out["median"].copy()
                    out["percentiles"][dense_rows] = \
                        dense_out["percentiles"]
                    out["median"][dense_rows] = dense_out["median"]
                else:
                    out[nm][dense_rows] = dense_out[nm]
        return out

    # -- checkpoint snapshot / restore (veneur_tpu/persist/) --------------

    @requires_lock("store")
    def snapshot_begin(self):
        """Two-phase snapshot over BOTH tiers (see
        DigestGroup.snapshot_begin): phase 1 under the store lock
        drains staging and dispatches per-slab pool slices plus the
        dense bank's slot-prefix slices; ``finish`` fetches off-lock,
        dequantizes the packed planes host-side, and flattens
        everything into the shared per-row centroid-run layout — so a
        restore merges into ANY digest store, whatever its tier
        assignment (rows appear in exactly one tier's runs)."""
        self._drain_staging()
        # the dense bank buffers its own staging (the pool drains hand
        # it promoted rows' samples via sample_many, which only drains
        # FULL chunks) — flush drains it in _flush_fetch, and a
        # snapshot must too or a promoted row's staged tail silently
        # misses the checkpoint
        self._dense._drain_staging()
        n = len(self.interner)
        snap = {"kind": "digest", "names": list(self.interner.names),
                "joined": list(self.interner.joined)}
        if n == 0:
            return snap, None
        R, pk = self.slab_rows, self.pk
        slab_refs = []
        for i, p in enumerate(self.pools):
            need = min(n - i * R, R)
            if need <= 0:
                break
            slab_refs.append((i, (
                p.mq.reshape(R, pk)[:need], p.wb.reshape(R, pk)[:need],
                p.fmin[:need], p.fmax[:need],
                p.bw.reshape(R, pk)[:need], p.bwm.reshape(R, pk)[:need],
                p.dmin[:need], p.dmax[:need], p.count[:need],
                p.vsum[:need], p.vmin[:need], p.vmax[:need],
                p.recip[:need])))
        nd = len(self._dense_rows)
        dense_rows = np.asarray(self._dense_rows, np.int64)
        dense_refs = None
        if nd:
            d = self._dense
            dense_refs = (
                d.digest.mean[:nd], d.digest.weight[:nd],
                td_ops.bin_rows(d.temp.sum_w, 0, nd, d.k),
                td_ops.bin_rows(d.temp.sum_wm, 0, nd, d.k), d.dmin[:nd],
                d.dmax[:nd], d.digest.min[:nd], d.digest.max[:nd],
                d.temp.count[:nd], d.temp.vsum[:nd], d.temp.vmin[:nd],
                d.temp.vmax[:nd], d.temp.recip[:nd])

        def finish():
            from veneur_tpu.core.store import flatten_digest_state

            rows_p, means_p, weights_p = [], [], []
            scal = {nm: np.zeros(n, np.float32)
                    for nm in ("count", "vsum", "recip")}
            scal["mins"] = np.full(n, np.inf, np.float32)
            scal["maxs"] = np.full(n, -np.inf, np.float32)
            scal["vmin"] = np.full(n, np.inf, np.float32)
            scal["vmax"] = np.full(n, -np.inf, np.float32)
            for i, refs in slab_refs:
                (mq, wb, fmin, fmax, bw, bwm, dmn, dmx, cnt, vsum, vmn,
                 vmx, recip) = [np.asarray(a) for a in
                                jax.device_get(refs)]
                # host-side dequantize (the PackedDigestPlanes contract)
                mean, weight = dequantize_host(mq, wb, fmin, fmax)
                flat = flatten_digest_state(
                    np.where(weight > 0, mean, np.inf).astype(np.float32),
                    weight.astype(np.float32), bw, bwm)
                base_row = np.int32(i * R)
                rows_p.append(flat["rows"] + base_row)
                means_p.append(flat["means"])
                weights_p.append(flat["weights"])
                lo, hi = i * R, i * R + len(cnt)
                scal["mins"][lo:hi] = np.minimum(dmn, vmn)
                scal["maxs"][lo:hi] = np.maximum(dmx, vmx)
                scal["count"][lo:hi] = cnt
                scal["vsum"][lo:hi] = vsum
                scal["vmin"][lo:hi] = vmn
                scal["vmax"][lo:hi] = vmx
                scal["recip"][lo:hi] = recip
            if dense_refs is not None:
                (mean, weight, bin_w, bin_wm, imp_min, imp_max, dmn,
                 dmx, cnt, vsum, vmn, vmx, recip) = [
                    np.asarray(a) for a in jax.device_get(dense_refs)]
                flat = flatten_digest_state(
                    mean.astype(np.float32), weight.astype(np.float32),
                    bin_w.astype(np.float32), bin_wm.astype(np.float32))
                rows_p.append(
                    dense_rows[flat["rows"]].astype(np.int32))
                means_p.append(flat["means"])
                weights_p.append(flat["weights"])
                scal["mins"][dense_rows] = np.minimum(imp_min, dmn)
                scal["maxs"][dense_rows] = np.maximum(imp_max, dmx)
                scal["count"][dense_rows] = cnt
                scal["vsum"][dense_rows] = vsum
                scal["vmin"][dense_rows] = vmn
                scal["vmax"][dense_rows] = vmx
                scal["recip"][dense_rows] = recip
            snap["rows"] = np.concatenate(rows_p) if rows_p else \
                np.empty(0, np.int32)
            snap["means"] = np.concatenate(means_p) if means_p else \
                np.empty(0, np.float64)
            snap["weights"] = np.concatenate(weights_p) if weights_p \
                else np.empty(0, np.float64)
            snap["mins"] = scal["mins"]
            snap["maxs"] = scal["maxs"]
            snap["count"] = scal["count"]
            snap["vsum"] = scal["vsum"]
            snap["vmin"] = scal["vmin"]
            snap["vmax"] = scal["vmax"]
            snap["recip"] = scal["recip"]

        return snap, finish

    @requires_lock("store")
    def snapshot_state(self) -> dict:
        """One-shot begin+finish for exclusive owners (the requeue
        rung, tests) — see DigestGroup.snapshot_state."""
        snap, finish = self.snapshot_begin()
        if finish is not None:
            finish()
        return snap

    @requires_lock("store")
    def restore_stats(self, rows: np.ndarray, count: np.ndarray,
                      vsum: np.ndarray, vmin: np.ndarray,
                      vmax: np.ndarray, recip: np.ndarray):
        """Fold recovered per-row scalar stats into whichever tier each
        row is assigned to (rows were mapped through ``_row`` by the
        restore, so the assignment already exists)."""
        if not len(rows):
            return
        rows = np.asarray(rows, np.int64)
        self.ensure_capacity(int(rows.max()))
        self._device_dirty = True
        dense, pool_spans = self._partition(
            rows, np.asarray(count, np.float32),
            np.asarray(vsum, np.float32), np.asarray(vmin, np.float32),
            np.asarray(vmax, np.float32), np.asarray(recip, np.float32))
        if dense is not None:
            slots, (c, s, mn, mx, rc) = dense
            self._dense.restore_stats(slots, c, s, mn, mx, rc)
        for i, local, (c, s, mn, mx, rc) in pool_spans:
            # pow2 padding zero-fills; min/max identities re-stamp
            pad_rows = local >= self.slab_rows
            mn[pad_rows] = np.inf
            mx[pad_rows] = -np.inf
            self._pool_restore(i, local, c, s, mn, mx, rc)

    def _pool_restore(self, i: int, local, count, vsum, vmin, vmax,
                      recip):
        """Dispatch one slab's restore-stat span (override point, like
        ``_pool_drain_samples``)."""
        with obs_kernels.scope("drain.digest.tiered"):
            self.pools[i] = _pool_restore_stats(
                self.pools[i], jnp.asarray(local), jnp.asarray(count),
                jnp.asarray(vsum), jnp.asarray(vmin), jnp.asarray(vmax),
                jnp.asarray(recip), self.slab_rows)
