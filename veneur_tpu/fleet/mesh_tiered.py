"""Mesh-sharded tiered digest residency: the packed pool across chips.

``mesh_enabled: true`` + ``digest_storage: tiered`` — the composition
the PR 7 config error used to forbid. The pool slabs' flat planes shard
over the mesh's series axis (each device owns a contiguous block of
every slab, placed by the fleet :class:`~veneur_tpu.fleet.router.
ShardRouter`), the hot tier is a :class:`~veneur_tpu.core.mesh_store.
MeshDigestGroup` bank in slot mode, and the whole tiered lifecycle —
binning, shift guard, promotion, flush, checkpoint — runs sharded:

- **drains are shard-routed**: staged chunks partition per slab (as on
  one chip) and then per shard (``route_stack``), so each device bins
  only its own rows' sub-chunk. Per-row binning is independent by
  construction (``ops/tdigest.bin_pool_samples`` is row-segmented), so
  a row's bins are bit-identical to the single-device pool's.
- **the guard DECISION psums**: the three drain triggers of
  ``core/tiered.py`` (``_pool_guard_masses``) reduce over the series
  axis before thresholding, so every shard takes the same drain the
  single-device pool would on the same chunk — the property the
  quantile-parity oracle tests pin.
- **promotion is shard-local**: a series' dense slot is allocated on
  the SAME shard as its pool row, so ``_mesh_promote_rows`` moves pool
  state into the bank's temp entirely on the owning device — no
  collective, no host bounce, exact count conservation
  (``_promote_rows_impl``, shared with the single-device program).
  Demotion stays a host decision (the shared
  :class:`~veneur_tpu.core.tiered.TierDirectory` survives the swap).
- **flush fetches the placement permutation**: pool rows are
  shard-placed, not sequential, so every flush/snapshot gathers back to
  interner order before the assembly the store expects.

The compiled programs are module-level ``jax.jit`` definitions with the
``Mesh`` static (inventory-visible, one compile per mesh shared by the
histogram and timer groups).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from veneur_tpu.core.locking import requires_lock
from veneur_tpu.core.mesh_store import MeshDigestGroup, _round_up
from veneur_tpu.core.tiered import (PoolSlab, TieredDigestGroup,
                                    _init_pool_slab, _pool_flush_impl,
                                    _pool_guard_apply, _pool_guard_masses,
                                    _pool_restore_stats_impl,
                                    _pool_scatter_imports,
                                    _pool_scatter_samples,
                                    _promote_rows_impl, dequantize_host)
from veneur_tpu.fleet.router import (PoolPlacement, ShardRouter,
                                     inverse_perm, route_stack)
from veneur_tpu.obs import kernels as obs_kernels
from veneur_tpu.obs import recorder as obs_rec
from veneur_tpu.ops import tdigest as td_ops
from veneur_tpu.ops.tdigest_pallas import _next_pow2
from veneur_tpu.parallel.mesh import SERIES_AXIS


def _pool_spec() -> PoolSlab:
    """Every PoolSlab plane is flat ([slab*PK] or [slab]), so one
    series-axis spec shards each into per-device row blocks (row-major
    layout keeps a row's PK bins contiguous inside its block)."""
    s = P(SERIES_AXIS)
    return PoolSlab(mq=s, wb=s, fmin=s, fmax=s, bw=s, bwm=s, dmin=s,
                    dmax=s, count=s, vsum=s, vmin=s, vmax=s, recip=s)


def _temp_spec():
    s = P(SERIES_AXIS)
    return td_ops.TempCentroids(sum_w=s, sum_wm=s, seg_w=s, seg_wm=s,
                                count=s, vsum=s, vmin=s, vmax=s, recip=s)


def _relocal_slab(rows: jax.Array, loc: int):
    """Slab-local rows → this device's block-local rows (sentinel loc)
    plus the ownership mask."""
    start = lax.axis_index(SERIES_AXIS) * loc
    mine = (rows >= start) & (rows < start + loc)
    return jnp.where(mine, rows - start, loc), mine


def _mesh_guard_drain(pool: PoolSlab, rows, values, weights, loc: int,
                      pk: int, pcomp: float, use_pallas: bool) -> PoolSlab:
    """The pool shift guard with the DECISION psum'd over the series
    axis: per-shard trigger signals sum over the disjoint sub-chunks to
    exactly the single-device whole-chunk signals, so every shard takes
    the same drain (the drain itself is row-local; no collective rides
    inside the lax.cond)."""
    shifted, total, over_dom = _pool_guard_masses(pool, rows, values,
                                                  weights, loc, pk, pcomp)
    shifted = lax.psum(shifted, SERIES_AXIS)
    total = lax.psum(total, SERIES_AXIS)
    over_dom = lax.psum(over_dom, SERIES_AXIS)
    pred = (shifted > td_ops.SHIFT_GUARD_FRAC
            * jnp.maximum(total, jnp.finfo(jnp.float32).tiny)) \
        | (over_dom > 0)
    return _pool_guard_apply(pool, pred, loc, pk, pcomp, use_pallas)


@partial(jax.jit, donate_argnums=(0,), static_argnums=(4, 5, 6, 7, 8))
def _mesh_pool_ingest(pool: PoolSlab, rows, vals, wts, mesh: Mesh,
                      slab: int, pk: int, pcomp: float,
                      use_pallas: bool) -> PoolSlab:
    """Shard-routed pool sample ingest: ``[shards, b]`` stacks sharded
    over the series axis (each device scatters only its own rows'
    sub-chunk into its slab block). rows are slab-LOCAL; >= slab is
    padding. The chunk replicates over the hosts axis: the pool is the
    COLD tier (its chunks are small by definition — hot rows live in
    the dense bank, whose ingest fans in over hosts), and the
    dominant-chunk binning path needs exact within-chunk ranks, which a
    hosts split would break."""
    shards = mesh.shape[SERIES_AXIS]
    loc = slab // shards
    st = P(SERIES_AXIS, None)

    def local_ingest(pool, rows, vals, wts):
        r, _ = _relocal_slab(rows.reshape(-1), loc)
        v = vals.reshape(-1)
        w = jnp.where(r >= loc, 0.0, wts.reshape(-1))
        pool = _mesh_guard_drain(pool, r, v, w, loc, pk, pcomp,
                                 use_pallas)
        return _pool_scatter_samples(pool, r, v, w, loc, pk, pcomp)

    return shard_map(local_ingest, mesh=mesh,
                     in_specs=(_pool_spec(), st, st, st),
                     out_specs=_pool_spec(),
                     check_vma=False)(pool, rows, vals, wts)


@partial(jax.jit, donate_argnums=(0,),
         static_argnums=(7, 8, 9, 10, 11))
def _mesh_pool_import(pool: PoolSlab, rows, means, wts, srows, smins,
                      smaxs, mesh: Mesh, slab: int, pk: int, pcomp: float,
                      use_pallas: bool) -> PoolSlab:
    """Shard-routed pool centroid import (the fleet import path):
    whole sorted centroid runs stay on their owning device — a row's
    run lives on exactly one shard by the router invariant."""
    shards = mesh.shape[SERIES_AXIS]
    loc = slab // shards
    st = P(SERIES_AXIS, None)

    def local_import(pool, rows, means, wts, srows, smins, smaxs):
        r, _ = _relocal_slab(rows.reshape(-1), loc)
        m = means.reshape(-1)
        w = jnp.where(r >= loc, 0.0, wts.reshape(-1))
        pool = _mesh_guard_drain(pool, r, m, w, loc, pk, pcomp,
                                 use_pallas)
        sr, _ = _relocal_slab(srows.reshape(-1), loc)
        return _pool_scatter_imports(pool, r, m, w, sr,
                                     smins.reshape(-1),
                                     smaxs.reshape(-1), loc, pk, pcomp)

    return shard_map(local_import, mesh=mesh,
                     in_specs=(_pool_spec(), st, st, st, st, st, st),
                     out_specs=_pool_spec(),
                     check_vma=False)(pool, rows, means, wts, srows,
                                      smins, smaxs)


@partial(jax.jit, donate_argnums=(0,), static_argnums=(2, 3, 4, 5, 6))
def _mesh_pool_flush(pool: PoolSlab, qs, mesh: Mesh, slab: int, pk: int,
                     pcomp: float, use_pallas: bool):
    """Per-interval pool flush, entirely row-local per shard: the
    sort-compact-merge and quantile of ``_pool_flush_impl`` run on each
    device's block with no collective (a series' whole state already
    lives on its shard)."""
    shards = mesh.shape[SERIES_AXIS]
    loc = slab // shards
    s, sq = P(SERIES_AXIS), P(SERIES_AXIS, None)

    def local_flush(pool, qs):
        return _pool_flush_impl(pool, qs, loc, pk, pcomp, use_pallas)

    return shard_map(local_flush, mesh=mesh,
                     in_specs=(_pool_spec(), P()),
                     out_specs=(s, s, s, s, sq, s, s, s, s, s),
                     check_vma=False)(pool, qs)


@partial(jax.jit, donate_argnums=(0, 1, 2, 3),
         static_argnums=(6, 7, 8, 9))
def _mesh_promote_rows(pool: PoolSlab, temp: td_ops.TempCentroids, ddmin,
                       ddmax, rows, slots, mesh: Mesh, slab: int, pk: int,
                       compression: float):
    """Shard-local promotion: a promoted series' dense slot lives on the
    SAME shard as its pool row (``MeshTieredDigestGroup._assign_dense``),
    so each device dequantizes its own pool rows straight into its own
    block of the dense bank's temp — the single-device
    ``_promote_rows_impl`` math, no collective, counts conserved
    exactly. rows are slab-local, slots are bank-physical; both
    replicate (promotion batches are hysteresis-bounded small)."""
    shards = mesh.shape[SERIES_AXIS]
    loc = slab // shards
    s = P(SERIES_AXIS)

    def local_promote(pool, temp, ddmin, ddmax, rows, slots):
        bank_loc = temp.count.shape[0]
        rl, mine = _relocal_slab(rows, loc)
        start_b = lax.axis_index(SERIES_AXIS) * bank_loc
        sl = jnp.where(mine, slots - start_b, bank_loc)
        return _promote_rows_impl(pool, temp, ddmin, ddmax, rl, sl, loc,
                                  pk, compression)

    return shard_map(local_promote, mesh=mesh,
                     in_specs=(_pool_spec(), _temp_spec(), s, s, P(),
                               P()),
                     out_specs=(_pool_spec(), _temp_spec(), s, s),
                     check_vma=False)(pool, temp, ddmin, ddmax, rows,
                                      slots)


@partial(jax.jit, donate_argnums=(0,), static_argnums=(7, 8))
def _mesh_pool_restore_stats(pool: PoolSlab, rows, count, vsum, vmin,
                             vmax, recip, mesh: Mesh,
                             slab: int) -> PoolSlab:
    """Shard-routed checkpoint-restore scalar-stat scatter."""
    shards = mesh.shape[SERIES_AXIS]
    loc = slab // shards
    st = P(SERIES_AXIS, None)

    def local_restore(pool, rows, count, vsum, vmin, vmax, recip):
        r, mine = _relocal_slab(rows.reshape(-1), loc)
        return _pool_restore_stats_impl(
            pool, r, jnp.where(mine, count.reshape(-1), 0.0),
            jnp.where(mine, vsum.reshape(-1), 0.0),
            jnp.where(mine, vmin.reshape(-1), jnp.inf),
            jnp.where(mine, vmax.reshape(-1), -jnp.inf),
            jnp.where(mine, recip.reshape(-1), 0.0), loc)

    return shard_map(local_restore, mesh=mesh,
                     in_specs=(_pool_spec(), st, st, st, st, st, st),
                     out_specs=_pool_spec(),
                     check_vma=False)(pool, rows, count, vsum, vmin,
                                      vmax, recip)


class MeshTieredDigestGroup(TieredDigestGroup):
    """``TieredDigestGroup`` sharded over a fleet mesh (see module
    docstring). Same public surface; the physical row space is managed
    by a :class:`~veneur_tpu.fleet.router.PoolPlacement` (slab-append,
    rows never move) and the dense bank is a series-sharded
    :class:`~veneur_tpu.core.mesh_store.MeshDigestGroup` in slot mode."""

    def __init__(self, mesh: Mesh, router: ShardRouter,
                 slab_rows: int = 1 << 18, chunk: int = 1 << 14,
                 compression: float = td_ops.DEFAULT_COMPRESSION,
                 pool_centroids: int = 16, promote_samples: int = 64,
                 promote_intervals: int = 2, demote_intervals: int = 3,
                 dense_capacity: int = 1 << 10, directory=None):
        self.mesh = mesh
        self.router = router
        self.shards = mesh.shape[SERIES_AXIS]
        self._s = NamedSharding(mesh, P(SERIES_AXIS))
        self._dense_shard: list = []
        self._dense_idx: list = []
        self._dense_slots: list = []
        self._bank_fills = np.zeros(self.shards, np.int64)
        slab_rows = _round_up(min(slab_rows, 1 << 20), self.shards)
        super().__init__(slab_rows, chunk, compression, pool_centroids,
                         promote_samples, promote_intervals,
                         demote_intervals, dense_capacity,
                         directory=directory)
        self.placement = PoolPlacement(self.shards, self.slab_rows)
        self._logical = np.full(len(self._slot), -1, np.int64)

    # -- placement --------------------------------------------------------

    def _make_dense_bank(self, dense_capacity, chunk, compression):
        # slot mode (no router): this group assigns bank slots itself,
        # on the same shard as the pool row
        return MeshDigestGroup(self.mesh, dense_capacity, chunk,
                               compression)

    def _new_pool_slab(self) -> PoolSlab:
        return self._place_pool(_init_pool_slab(self.slab_rows, self.pk))

    def _place_pool(self, p: PoolSlab) -> PoolSlab:
        return PoolSlab(*(jax.device_put(a, self._s) for a in p))

    def _append_slab(self):
        self.pools.append(self._new_pool_slab())
        grow = self.capacity - len(self._slot)
        if grow > 0:
            self._slot = np.concatenate(
                [self._slot, np.full(grow, -1, np.int32)])
            self._activity = np.concatenate(
                [self._activity, np.zeros(grow, np.int64)])
            self._logical = np.concatenate(
                [self._logical, np.full(grow, -1, np.int64)])
        # staged sentinel rows must track the new out-of-range id
        self._rows[self._fill:] = self.capacity
        self._imp_rows[self._imp_fill:] = self.capacity
        self._imp_stat_rows[self._imp_stat_fill:] = self.capacity

    @requires_lock("store")
    def ensure_capacity(self, max_row: int):
        while max_row >= self.capacity:
            self._append_slab()

    @requires_lock("store")
    def _row(self, key, tags) -> int:
        row = self._intern_row(key, tags)  # logical
        if self.placement.assigned(row):
            return self.placement.phys(row)
        mtype = (self._overflow_type if row == self._overflow_row
                 else key.type)
        shard = self.router.shard_for(self.interner.names[row], mtype,
                                      self.interner.joined[row])
        phys, appended = self.placement.assign(row, shard)
        if appended:
            self._append_slab()
        self._logical[phys] = row
        if (row != self._overflow_row
                and self.directory.is_dense((key.name, key.joined_tags))):
            self._assign_dense(phys)
        return phys

    @requires_lock("store")
    def _assign_dense(self, row: int) -> int:
        """A dense slot ON THE SAME SHARD as the pool row — the
        invariant that keeps promotion shard-local."""
        shard = int((row % self.slab_rows) // self.placement.block)
        bank = self._dense
        bank_block = bank.capacity // self.shards
        if self._bank_fills[shard] >= bank_block:
            bank._grow()  # blocked pad doubles every shard's block
            bank_block = bank.capacity // self.shards
            self._dense_slots = [
                s * bank_block + i
                for s, i in zip(self._dense_shard, self._dense_idx)]
            for r, sl in zip(self._dense_rows, self._dense_slots):
                self._slot[r] = sl
        idx = int(self._bank_fills[shard])
        self._bank_fills[shard] += 1
        slot = shard * bank_block + idx
        self._dense_rows.append(row)
        self._dense_shard.append(shard)
        self._dense_idx.append(idx)
        self._dense_slots.append(slot)
        self._slot[row] = slot
        return slot

    # -- drains -----------------------------------------------------------

    def _route_spans(self, local: np.ndarray, arrays) -> tuple:
        """Per-slab slab-local spans → [shards, b] routed stacks
        (sentinel rows == slab_rows route anywhere and drop device-side
        like every scatter sentinel)."""
        shard_idx = self.placement.shard_of_local(local)
        return route_stack(self.shards, shard_idx, local, arrays,
                           self.slab_rows)

    def _pool_drain_samples(self, i: int, local, vals, wts,
                            use_pallas: bool):
        """The base drain body, with the per-slab span re-routed into a
        ``[shards, b]`` stack for the sharded program."""
        r_st, (v_st, w_st) = self._route_spans(local, [vals, wts])
        with obs_kernels.scope("drain.digest.mesh_tiered"):
            self.pools[i] = _mesh_pool_ingest(
                self.pools[i], jnp.asarray(r_st), jnp.asarray(v_st),
                jnp.asarray(w_st), self.mesh, self.slab_rows, self.pk,
                self.pcomp, use_pallas)

    def _pool_drain_imports(self, i: int, c_local, c_means, c_wts,
                            s_local, s_mins, s_maxs, use_pallas: bool):
        r_st, (m_st, w_st) = self._route_spans(c_local, [c_means, c_wts])
        sr_st, (mn_st, mx_st) = self._route_spans(s_local,
                                                  [s_mins, s_maxs])
        with obs_kernels.scope("drain.digest.mesh_tiered"):
            self.pools[i] = _mesh_pool_import(
                self.pools[i], jnp.asarray(r_st), jnp.asarray(m_st),
                jnp.asarray(w_st), jnp.asarray(sr_st),
                jnp.asarray(mn_st), jnp.asarray(mx_st), self.mesh,
                self.slab_rows, self.pk, self.pcomp, use_pallas)

    def _pool_restore(self, i: int, local, count, vsum, vmin, vmax,
                      recip):
        r_st, (c_st, s_st, mn_st, mx_st, rc_st) = \
            self._route_spans(local, [count, vsum, vmin, vmax, recip])
        with obs_kernels.scope("drain.digest.mesh_tiered"):
            self.pools[i] = _mesh_pool_restore_stats(
                self.pools[i], jnp.asarray(r_st), jnp.asarray(c_st),
                jnp.asarray(s_st), jnp.asarray(mn_st),
                jnp.asarray(mx_st), jnp.asarray(rc_st), self.mesh,
                self.slab_rows)

    # -- promotion --------------------------------------------------------

    @requires_lock("store")
    def _maybe_promote(self, touched_rows: np.ndarray):
        """Base logic with the physical row space: candidates are
        ASSIGNED physical rows (``_logical`` maps back to the interner
        identity the directory keys on); the promotion program is the
        shard-local mesh one."""
        if not len(touched_rows):
            return
        touched_rows = touched_rows[touched_rows < len(self._logical)]
        cand = touched_rows[(self._logical[touched_rows] >= 0)
                            & (self._slot[touched_rows] < 0)
                            & (self._activity[touched_rows]
                               >= self.promote_samples)]
        if not len(cand):
            return
        names, joined = self.interner.names, self.interner.joined

        def ident(phys: int):
            lr = int(self._logical[phys])
            return names[lr], joined[lr]

        promote = [int(r) for r in cand
                   if self.directory.should_promote(ident(r))]
        if not promote:
            return
        rows = np.asarray(promote, np.int64)
        for r in promote:
            self._assign_dense(int(r))
        # slots re-read AFTER the whole batch: a mid-batch bank _grow
        # (one shard's block filling) remaps every existing slot, and
        # _assign_dense keeps _slot current while any ints captured
        # earlier would scatter at pre-grow positions
        slots = self._slot[rows].astype(np.int32)
        self._sync_plumbing()
        d = self._dense
        d._drain_staging()  # promoted mass must land on settled bins
        d._device_dirty = True
        slabs = rows // self.slab_rows
        with obs_kernels.scope("drain.digest.mesh_tiered"):
            for i in np.unique(slabs):
                sel = slabs == i
                m = int(sel.sum())
                pad = _next_pow2(m)
                local = np.full(pad, self.slab_rows, np.int32)
                local[:m] = rows[sel] - i * self.slab_rows
                sl = np.full(pad, d.capacity, np.int32)
                sl[:m] = slots[sel]
                (self.pools[int(i)], d.temp, d.dmin,
                 d.dmax) = _mesh_promote_rows(
                    self.pools[int(i)], d.temp, d.dmin, d.dmax,
                    jnp.asarray(local), jnp.asarray(sl), self.mesh,
                    self.slab_rows, self.pk, self.compression)
        self.directory.note_promoted([ident(r) for r in promote])

    # -- flush ------------------------------------------------------------

    def flush(self, percentiles, want_digests=True, want_stats=None):
        interner, out = super().flush(percentiles, want_digests,
                                      want_stats)
        self._reset_mesh_plumbing()
        return interner, out

    def flush_begin(self, percentiles, want_digests=True,
                    want_stats=None):
        """Two-phase slot (see ``TieredDigestGroup.flush_begin``): the
        sharded staged-chunk drains dispatch now; the two-tier flush
        and the placement reset run in ``finish``."""
        fin = super().flush_begin(percentiles, want_digests, want_stats)

        def finish():
            out = fin()
            self._reset_mesh_plumbing()
            return out

        return finish

    def _reset_mesh_plumbing(self):
        if not self._retired:
            self.placement = PoolPlacement(self.shards, self.slab_rows,
                                           slabs=len(self.pools))
            self._logical = np.full(len(self._slot), -1, np.int64)
            self._bank_fills[:] = 0
        self._dense_shard, self._dense_idx, self._dense_slots = [], [], []

    def _end_interval(self, n: int):
        # gather the LIVE rows' activity through the permutation (the
        # base scans _activity[:n]; physical rows are shard-placed, and
        # a full-capacity scan would pay O(slabs * slab_rows) per flush)
        perm = self.placement.perm(n)
        act = self._activity[perm]
        names, joined = self.interner.names, self.interner.joined
        self.directory.end_interval(
            (names[lr], joined[lr])
            for lr in np.flatnonzero(act >= self.promote_samples))

    def _flush_fetch(self, n: int, percentiles, want_digests, want_stats,
                     use_pallas: bool) -> dict:
        """One complete flush attempt over both sharded tiers; results
        gather through the placement permutation back to interner
        order. Fresh (placed) pool slabs commit only once every program
        + fetch succeeded, like the base."""
        if want_digests == "packed":
            raise NotImplementedError(
                "packed digest export is a forwarding-local concern; a "
                "mesh global emits percentiles and never re-forwards")
        from veneur_tpu.core.slab import _fill_stat_results, _select_stats

        sel = _select_stats(want_stats)
        qs = jnp.asarray(list(percentiles) + [0.5], jnp.float32)
        R, pk = self.slab_rows, self.pk
        parts = []
        new_pools = list(self.pools)
        with obs_kernels.scope("flush.digest.mesh_tiered"):
            for i in range(len(self.pools)):
                (mean_flat, weight_flat, mn, mx, pcts, count, vsum, vmin,
                 vmax, recip) = _mesh_pool_flush(
                    self.pools[i], qs, self.mesh, R, pk, self.pcomp,
                    use_pallas)
                new_pools[i] = None if self._retired else \
                    self._new_pool_slab()
                planes = ()
                if want_digests:
                    planes = (mean_flat.reshape(R, pk),
                              weight_flat.reshape(R, pk), mn, mx)
                stats = {"pcts": pcts, "count": count, "sum": vsum,
                         "min": vmin, "max": vmax, "recip": recip}
                with obs_rec.maybe_stage("fetch"):
                    # full-slab fetch: live rows are shard-placed, not a
                    # prefix — the permutation gather below restores
                    # interner order host-side
                    parts.append(jax.device_get(
                        planes + tuple(stats[nm] for nm in sel)))
        nd = len(self._dense_rows)
        dense_out = None
        if nd:
            self._dense._drain_staging()
            self._dense._ext_rows = np.asarray(self._dense_slots,
                                               np.int64)
            dense_out = self._dense._flush_fetch(
                nd, percentiles, want_digests, want_stats, use_pallas)
        # every program + fetch succeeded: commit the fresh pool slabs
        self.pools = [] if self._retired else \
            [p for p in new_pools if p is not None]
        perm = self.placement.perm(n)
        cols = [np.concatenate(c, axis=0)[perm]
                for c in zip(*parts)]
        log_dense = (self._logical[np.asarray(self._dense_rows,
                                              np.int64)]
                     if nd else np.empty(0, np.int64))
        out = {}
        if want_digests:
            pm, pw, pool_mn, pool_mx = cols[:4]
            cols = cols[4:]
            mean_full = np.full((n, self.k), np.inf, np.float32)
            weight_full = np.zeros((n, self.k), np.float32)
            mean_full[:, :pk] = pm
            weight_full[:, :pk] = pw
            dmin_full = np.asarray(pool_mn, np.float32).copy()
            dmax_full = np.asarray(pool_mx, np.float32).copy()
            if nd:
                mean_full[log_dense] = dense_out["digest_mean"]
                weight_full[log_dense] = dense_out["digest_weight"]
                dmin_full[log_dense] = dense_out["digest_min"]
                dmax_full[log_dense] = dense_out["digest_max"]
            out["digest_mean"] = mean_full
            out["digest_weight"] = weight_full
            out["digest_min"] = dmin_full
            out["digest_max"] = dmax_full
        _fill_stat_results(sel, cols, n, percentiles, out)
        if nd:
            for nm in sel:
                if nm == "pcts":
                    out["percentiles"] = out["percentiles"].copy()
                    out["median"] = out["median"].copy()
                    out["percentiles"][log_dense] = \
                        dense_out["percentiles"]
                    out["median"][log_dense] = dense_out["median"]
                else:
                    out[nm] = out[nm].copy()
                    out[nm][log_dense] = dense_out[nm]
        return out

    # -- checkpoint snapshot / restore ------------------------------------

    @requires_lock("store")
    def snapshot_begin(self):
        """Two-phase snapshot over both sharded tiers: full-slab slices
        dispatch under the lock; ``finish`` fetches off-lock, flattens
        per slab in PHYSICAL rows, then translates through the inverse
        permutation so the snapshot carries interner (logical) rows —
        restorable into ANY digest store like the base."""
        from veneur_tpu.core.store import flatten_digest_state

        self._drain_staging()
        # staged bank residue must reach the snapshot (see the base
        # snapshot_begin — the flush path drains it in _flush_fetch)
        self._dense._drain_staging()
        n = len(self.interner)
        snap = {"kind": "digest", "names": list(self.interner.names),
                "joined": list(self.interner.joined)}
        if n == 0:
            return snap, None
        R, pk = self.slab_rows, self.pk
        slab_refs = []
        for i, p in enumerate(self.pools):
            # every captured ref must be an OP OUTPUT, never the live
            # buffer: the pool programs donate self.pools[i], so a
            # drain landing between this locked begin and the off-lock
            # finish() would delete a raw capture under device_get.
            # Machine-checked: lint/deviceflow.py DONATION_PRONE_PLANES
            # registers `pools` and the donation-safety pass flags any
            # raw capture here (the reshapes produce fresh arrays; the
            # flat planes need the explicit copy).
            slab_refs.append((i, (
                p.mq.reshape(R, pk), p.wb.reshape(R, pk),
                jnp.copy(p.fmin), jnp.copy(p.fmax),
                p.bw.reshape(R, pk), p.bwm.reshape(R, pk),
                jnp.copy(p.dmin), jnp.copy(p.dmax), jnp.copy(p.count),
                jnp.copy(p.vsum), jnp.copy(p.vmin), jnp.copy(p.vmax),
                jnp.copy(p.recip))))
        nd = len(self._dense_rows)
        dense_refs = None
        log_dense = None
        if nd:
            d = self._dense
            slots = jnp.asarray(self._dense_slots, jnp.int32)
            dense_refs = (
                d.digest.mean[slots], d.digest.weight[slots],
                td_ops.gather_bin_rows(d.temp.sum_w, slots, d.k),
                td_ops.gather_bin_rows(d.temp.sum_wm, slots, d.k),
                d.dmin[slots], d.dmax[slots], d.digest.min[slots],
                d.digest.max[slots], d.temp.count[slots],
                d.temp.vsum[slots], d.temp.vmin[slots],
                d.temp.vmax[slots], d.temp.recip[slots])
            log_dense = self._logical[np.asarray(self._dense_rows,
                                                 np.int64)]
        perm = self.placement.perm(n)
        inv = inverse_perm(perm, self.capacity)

        def finish():
            rows_p, means_p, weights_p = [], [], []
            cap = len(inv)
            scal = {nm: np.zeros(cap, np.float32)
                    for nm in ("count", "vsum", "recip")}
            scal["mins"] = np.full(cap, np.inf, np.float32)
            scal["maxs"] = np.full(cap, -np.inf, np.float32)
            scal["vmin"] = np.full(cap, np.inf, np.float32)
            scal["vmax"] = np.full(cap, -np.inf, np.float32)
            for i, refs in slab_refs:
                (mq, wb, fmin, fmax, bw, bwm, dmn, dmx, cnt, vsum, vmn,
                 vmx, recip) = [np.asarray(a) for a in
                                jax.device_get(refs)]
                mean, weight = dequantize_host(mq, wb, fmin, fmax)
                flat = flatten_digest_state(
                    np.where(weight > 0, mean, np.inf).astype(np.float32),
                    weight.astype(np.float32), bw, bwm)
                base_row = np.int64(i * R)
                # physical → logical (unassigned rows carry no weight,
                # so flatten never emits them)
                rows_p.append(inv[flat["rows"].astype(np.int64)
                                  + base_row].astype(np.int32))
                means_p.append(flat["means"])
                weights_p.append(flat["weights"])
                lo, hi = i * R, (i + 1) * R
                scal["mins"][lo:hi] = np.minimum(dmn, vmn)
                scal["maxs"][lo:hi] = np.maximum(dmx, vmx)
                scal["count"][lo:hi] = cnt
                scal["vsum"][lo:hi] = vsum
                scal["vmin"][lo:hi] = vmn
                scal["vmax"][lo:hi] = vmx
                scal["recip"][lo:hi] = recip
            for nm in scal:
                scal[nm] = scal[nm][perm]
            if dense_refs is not None:
                (mean, weight, bin_w, bin_wm, imp_min, imp_max, dmn,
                 dmx, cnt, vsum, vmn, vmx, recip) = [
                    np.asarray(a) for a in jax.device_get(dense_refs)]
                flat = flatten_digest_state(
                    mean.astype(np.float32), weight.astype(np.float32),
                    bin_w.astype(np.float32), bin_wm.astype(np.float32))
                rows_p.append(log_dense[flat["rows"]].astype(np.int32))
                means_p.append(flat["means"])
                weights_p.append(flat["weights"])
                scal["mins"][log_dense] = np.minimum(imp_min, dmn)
                scal["maxs"][log_dense] = np.maximum(imp_max, dmx)
                scal["count"][log_dense] = cnt
                scal["vsum"][log_dense] = vsum
                scal["vmin"][log_dense] = vmn
                scal["vmax"][log_dense] = vmx
                scal["recip"][log_dense] = recip
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

    def fresh(self) -> "MeshTieredDigestGroup":
        """Empty same-config twin; the shared TierDirectory carries
        promote/demote state across the swap, the sharded programs are
        cached per mesh."""
        return MeshTieredDigestGroup(
            self.mesh, self.router, self.slab_rows, self.chunk,
            self.compression, self.pk, self.directory.promote_samples,
            self.directory.promote_intervals,
            self.directory.demote_intervals, self._dense.capacity,
            directory=self.directory)
