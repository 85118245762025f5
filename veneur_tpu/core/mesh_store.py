"""Mesh-sharded scope-class groups: the global tier's store on many chips.

This wires the sharded global-aggregation design (``parallel/global_agg.py``)
into the *serving* store: a global instance whose import servers
(``forward/grpc_forward.py`` gRPC ``SendMetrics``, ``httpserv.py`` HTTP
``/import``) feed device state sharded over a ``(series, hosts)`` mesh — the
TPU form of the reference's global veneur merging forwarded sketches across
its worker shards (``/root/reference/importsrv/server.go:101-132`` +
``flusher.go:56-58``).

Layout (cf. ``parallel/mesh.py``; shard placement in ``fleet/router.py``):

- **series axis** — every device owns a contiguous block of physical rows,
  exactly like one reference worker owns its ``map[MetricKey]*sampler``
  (``worker.go:54-91``). A series' physical row is chosen at intern time
  by the fleet :class:`~veneur_tpu.fleet.router.ShardRouter` — the SAME
  consistent-hash rule the proxy ring uses — so ownership is balanced
  from the first interval and agrees with any ring-routed upstream.
  The interner stays dense/sequential; flushes and snapshots gather the
  placement's permutation so every consumer still sees interner order.
- **hosts axis** — sample chunks come in *sharded* over this axis, each
  device handed its slice; one ``all_gather`` over ICI gives every
  device the whole chunk, which it bins against its own series block
  in place. What crosses is the chunk (12 B a sample), never a plane.
- **shard-routed import** — staged import chunks drain as ``[shards, b]``
  stacks sharded over the series axis: each device receives exactly its
  own rows' sub-chunk (whole centroid runs, order preserved) and bins
  only that — no replicated full-chunk binning, no device-side
  re-scatter. A row that already holds bin mass is drained before its
  run is binned: a decision of the row, which its shard takes alone,
  so the import holds no collective and agrees with the dense store.

The compiled programs are module-level ``jax.jit`` definitions taking the
``Mesh`` as a static argument (one compile per mesh per dtype-config, all
four digest groups of one store share it) — which also puts them in the
static-analysis compiled-program inventory and under the
``obs/kernels.py`` scope drift-check like every other program.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from veneur_tpu.core.bucketing import pow2_cap
from veneur_tpu.core.store import (IMPORT_DRAIN_BATCH, _GROW_FACTOR,
                                   DigestGroup, HeavyHitterGroup,
                                   ScalarGroup, SetGroup)
from veneur_tpu.core.locking import requires_lock
from veneur_tpu.fleet.router import ShardPlacement, ShardRouter, route_stack
from veneur_tpu.obs import kernels as obs_kernels
from veneur_tpu.obs import recorder as obs_rec
from veneur_tpu.ops import hll as hll_ops
from veneur_tpu.ops import tdigest as td_ops
from veneur_tpu.parallel.mesh import HOSTS_AXIS, SERIES_AXIS


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def _relocal(rows: jax.Array, s_loc: int) -> jax.Array:
    """Global row ids → this device's local ids; out-of-block rows map to
    s_loc so scatters drop them (the proxy's destForMetric invariant,
    reshaped: a series belongs to exactly one shard)."""
    r = rows.astype(jnp.int32)
    start = lax.axis_index(SERIES_AXIS) * s_loc
    return jnp.where((r >= start) & (r < start + s_loc), r - start, s_loc)


@partial(jax.jit, static_argnums=(1, 2, 3))
def _blocked_pad(arr: jax.Array, mesh: Mesh, fill=0,
                 planes: int = 1) -> jax.Array:
    """Double every shard's contiguous block of dim 0, each on the
    device that holds it: the device twin of ``ShardPlacement.grow`` —
    physical row (shard, local) moves from ``shard*B + local`` to
    ``shard*2B + local`` on both sides, and no plane is ever whole on
    one device. A block that is a flat stack of ``planes`` planes of
    its rows (the temp's anchors) has each of them doubled."""
    spec = P(SERIES_AXIS, *([None] * (arr.ndim - 1)))

    def local_pad(x):
        y = x.reshape((planes, x.shape[0] // planes) + x.shape[1:])
        pad = [(0, 0), (0, y.shape[1])] + [(0, 0)] * (x.ndim - 1)
        return jnp.pad(y, pad, constant_values=fill).reshape(
            (-1,) + x.shape[1:])

    return shard_map(local_pad, mesh=mesh, in_specs=(spec,),
                     out_specs=spec, check_vma=False)(arr)


@partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _mesh_init_digests(mesh: Mesh, capacity: int, k: int,
                       compression: float):
    """A digest group's empty device state, every plane made in shards:
    each device of the series axis fills its own block. Built whole and
    then placed, the planes of a deployment's 2^22 rows are 7 GB on the
    first device before a byte moves (PR 24 read 5.29 GB on the fullest
    of four devices against 4.03 GB on one: the mesh bought no memory)."""
    temp_spec, dig_spec, _, s = _digest_specs()
    s_loc = capacity // mesh.shape[SERIES_AXIS]

    def local_init():
        return (td_ops.init_temp(s_loc, k, compression),
                td_ops.init((s_loc,), compression, k),
                jnp.full((s_loc,), jnp.inf, jnp.float32),
                jnp.full((s_loc,), -jnp.inf, jnp.float32))

    return shard_map(local_init, mesh=mesh, in_specs=(),
                     out_specs=(temp_spec, dig_spec, s, s),
                     check_vma=False)()


@partial(jax.jit, static_argnums=(0, 1, 2))
def _mesh_zero_registers(mesh: Mesh, capacity: int, m: int):
    """A set group's empty ``[capacity, m]`` registers, made in shards."""
    s_loc = capacity // mesh.shape[SERIES_AXIS]
    return shard_map(lambda: jnp.zeros((s_loc, m), jnp.int8), mesh=mesh,
                     in_specs=(), out_specs=P(SERIES_AXIS, None),
                     check_vma=False)()


def _digest_specs():
    sk, s = P(SERIES_AXIS, None), P(SERIES_AXIS)
    # the temp's flat planes split into the shards' row blocks, each
    # block in a ``TempCentroids``' own order
    temp_spec = td_ops.TempCentroids(sum_w=s, sum_wm=s, seg_w=s, seg_wm=s,
                                     count=s, vsum=s, vmin=s, vmax=s,
                                     recip=s)
    dig_spec = td_ops.TDigest(mean=sk, weight=sk, min=s, max=s)
    return temp_spec, dig_spec, sk, s


def _guarded_ingest(temp, digest, rows_l, vals, wts, compression):
    """The dense/slab stores' shift guard and binning, mesh form: the
    DECISION psums the shift/total masses over the series axis, so every
    shard takes the drain the dense store would on the same chunk; the
    drain is row-local (no collective inside the cond) and works on a
    slab of the block's rows at a time, in place. The chunk is then
    binned against the anchors' ``[S, A]`` views the branch taken
    returns: the emptied ones after a drain, else the ones the guard
    read, which the chip relays from the flat anchor planes once a
    dispatch, not twice. Returns the decision too (the same on every
    device), for the dispatch's drain count."""
    s_loc = temp.num_series
    anchors = temp.anchors()
    shifted, total = td_ops.shift_masses(*anchors, rows_l, vals, wts, s_loc)
    shifted = lax.psum(shifted, SERIES_AXIS)
    total = lax.psum(total, SERIES_AXIS)
    pred = shifted > td_ops.SHIFT_GUARD_FRAC * jnp.maximum(
        total, jnp.finfo(jnp.float32).tiny)

    def drain(state):
        digest, temp = td_ops.drain_every_bin_by_slab(*state, compression)
        return digest, temp, temp.anchors()

    digest, temp, anchors = lax.cond(
        pred, drain, lambda state: state + (anchors,), (digest, temp))
    temp = td_ops.ingest_chunk(temp, rows_l, vals, wts, compression,
                               anchors=anchors)
    return temp, digest, pred


@partial(jax.jit, donate_argnums=(0, 1, 2), static_argnums=(6, 7, 8))
def _mesh_ingest_samples(temp, digest, drains, rows, vals, wts, mesh: Mesh,
                         compression: float, k: int):
    """Hosts-sharded sample ingest: the chunk comes in split over the
    hosts axis, ONE all-gather there hands every device the whole chunk
    (its original order), and each device bins it against its series
    block into the accumulated temp in place (``td_ops.ingest_chunk``,
    the dense store's binning), so what crosses the hosts axis is the
    chunk's 12 B a sample, not the block's planes. ``drains`` (int32,
    the same on every device) counts the dispatches whose shift guard
    drained the temp: the mesh's decision is one for all shards
    (``_guarded_ingest``). ``k`` is the temp's bin count, which its
    shape carries too."""
    hosts = mesh.shape.get(HOSTS_AXIS, 1)
    temp_spec, dig_spec, _, _ = _digest_specs()
    h = P(HOSTS_AXIS)

    def local_ingest(temp, digest, drains, rows, vals, wts):
        if hosts > 1:
            rows, vals, wts = lax.all_gather((rows, vals, wts), HOSTS_AXIS,
                                             tiled=True)
        temp, digest, drained = _guarded_ingest(
            temp, digest, _relocal(rows, temp.num_series), vals, wts,
            compression)
        return temp, digest, drains + drained.astype(jnp.int32)

    return shard_map(local_ingest, mesh=mesh,
                     in_specs=(temp_spec, dig_spec, P(), h, h, h),
                     out_specs=(temp_spec, dig_spec, P()),
                     check_vma=False)(temp, digest, drains, rows, vals,
                                      wts)


@partial(jax.jit, donate_argnums=(0, 1, 2, 3), static_argnums=(11, 12))
def _mesh_import_routed(temp, digest, dmin, dmax, drains, rows, means, wts,
                        srows, smins, smaxs, mesh: Mesh,
                        compression: float):
    """Shard-routed centroid import: the staged chunk arrives as a
    ``[shards, b]`` stack partitioned by the fleet router's placement
    (``route_stack``), sharded over the series axis — each device bins
    ONLY its own rows' sub-chunk (whole sorted centroid runs: a row's
    run lives on exactly one shard). A row that already holds bin mass
    is drained before its run is binned
    (``td_ops.ingest_centroids_rowdrained``): a decision of the row,
    so every shard takes it alone, the program holds no collective,
    and the result is the dense store's on the same data. ``drains``
    ([shards] int32) counts the dispatches in which a shard drained."""
    temp_spec, dig_spec, _, s = _digest_specs()
    st = P(SERIES_AXIS, None)  # [shards, b] stacks: dim 0 = shard

    def local_import(temp, digest, dmin, dmax, drains, rows, means, wts,
                     srows, smins, smaxs):
        s_loc = temp.num_series
        rows_l = _relocal(rows.reshape(-1), s_loc)
        # imported centroids feed percentiles only, never local stats
        # (samplers.go:473-480)
        digest, temp, drained = td_ops.ingest_centroids_rowdrained(
            digest, temp, rows_l, means.reshape(-1), wts.reshape(-1),
            compression)
        sr = _relocal(srows.reshape(-1), s_loc)
        dmin = dmin.at[sr].min(smins.reshape(-1), mode="drop")
        dmax = dmax.at[sr].max(smaxs.reshape(-1), mode="drop")
        return temp, digest, dmin, dmax, drains + drained

    return shard_map(local_import, mesh=mesh,
                     in_specs=(temp_spec, dig_spec, s, s, s, st, st, st,
                               st, st, st),
                     out_specs=(temp_spec, dig_spec, s, s, s),
                     check_vma=False)(temp, digest, dmin, dmax, drains,
                                      rows, means, wts, srows, smins,
                                      smaxs)


@partial(jax.jit, donate_argnums=(0, 1), static_argnums=(6, 7))
def _mesh_flush_digests(digest, temp, dmin, dmax, qs, fills, mesh: Mesh,
                        compression: float):
    """Per-interval flush: row-local compress + quantile per shard — the
    merge already happened at scatter time (a series's whole fleet
    state lives on its owning shard), so the flush itself needs no
    collective at all. ``fills`` ([shards] int32, an element a shard)
    is each block's live rows: a shard's rows are handed out as a
    prefix of its block, so each runs the slabs that hold its own
    (``td_ops.drain_and_quantile``'s ``n``) and leaves the rest of the
    rows reserved alone, one compiled variant whatever the fills."""
    temp_spec, dig_spec, sk, s = _digest_specs()

    def local_flush(digest, temp, dmin, dmax, qs, fills):
        drained, pcts = td_ops.drain_and_quantile(
            digest, temp, dmin, dmax, qs, compression, n=fills[0])
        return (drained, pcts, temp.count, temp.vsum, temp.vmin,
                temp.vmax, temp.recip)

    return shard_map(local_flush, mesh=mesh,
                     in_specs=(dig_spec, temp_spec, s, s, P(), s),
                     out_specs=(dig_spec, sk, s, s, s, s, s),
                     check_vma=False)(digest, temp, dmin, dmax, qs, fills)


# The least rows a flush gathers: a slab of the flush loop. An interval
# of fewer live series takes the variant the warm-up compiled.
GATHER_MIN_ROWS = 2048


@jax.jit
def _mesh_gather_rows(arrays, rows):
    """The flush's way back to interner order, as one program: every
    array's rows ``rows`` (physical rows are shard-placed, not
    sequential). ``rows`` comes padded to the pow2 bucket of the live
    count, so an interval whose series count moves compiles nothing
    (taken op by op, each new count compiled some ten small programs
    inside the flush: the compiles PR 31 counted in a warm window)."""
    return tuple(a[rows] for a in arrays)


@partial(jax.jit, donate_argnums=(0,), static_argnums=(4, 5))
def _mesh_ingest_hashes(regs, rows, hi, lo, mesh: Mesh, precision: int):
    """Hosts-sharded HLL ingest: per-slice register scatter + one pmax
    over the hosts axis (Set.Combine's register max, samplers.go:423)."""
    hosts = mesh.shape.get(HOSTS_AXIS, 1)
    sk, h = P(SERIES_AXIS, None), P(HOSTS_AXIS)

    def local_hash(regs, rows, hi, lo):
        s_loc = regs.shape[0]
        idx, rho = hll_ops.idx_rho(hi, lo, precision)
        regs = regs.at[_relocal(rows, s_loc), idx].max(
            rho.astype(regs.dtype), mode="drop")
        if hosts > 1:
            regs = lax.pmax(regs, HOSTS_AXIS)
        return regs

    return shard_map(local_hash, mesh=mesh, in_specs=(sk, h, h, h),
                     out_specs=sk, check_vma=False)(regs, rows, hi, lo)


@partial(jax.jit, donate_argnums=(0,), static_argnums=(3,))
def _mesh_merge_registers(regs, rows, updates, mesh: Mesh):
    """Shard-routed register import: ``[shards, b]`` row /
    ``[shards, b, m]`` register stacks land each forwarded sketch on
    its owning device without replicating the 2^p-register payload to
    every shard."""
    sk = P(SERIES_AXIS, None)
    st2, st3 = P(SERIES_AXIS, None), P(SERIES_AXIS, None, None)

    def local_merge(regs, rows, updates):
        s_loc = regs.shape[0]
        r = _relocal(rows.reshape(-1), s_loc)
        u = updates.reshape((-1,) + updates.shape[2:])
        return regs.at[r].max(u.astype(regs.dtype), mode="drop")

    return shard_map(local_merge, mesh=mesh, in_specs=(sk, st2, st3),
                     out_specs=sk, check_vma=False)(regs, rows, updates)


@partial(jax.jit, static_argnums=(1, 2))
def _mesh_estimate(regs, mesh: Mesh, precision: int):
    sk, s = P(SERIES_AXIS, None), P(SERIES_AXIS)

    def local_estimate(regs):
        return hll_ops.estimate(regs.astype(jnp.int32), precision)

    return shard_map(local_estimate, mesh=mesh, in_specs=(sk,),
                     out_specs=s, check_vma=False)(regs)


class _PlacementMixin:
    """Router-driven shard assignment shared by every mesh group.

    The id contract: everything that crosses the group boundary —
    ``_row`` results, staged buffers, the native intern memos, lane
    resolvers, bulk-ingest row lists — speaks LOGICAL (interner) rows,
    which are stable for the life of a generation. The placement's
    shard-blocked PHYSICAL rows appear only inside the drains
    (``_to_phys`` translates each chunk at drain time against the
    CURRENT placement) and the flush/snapshot permutation gathers — so
    a mid-interval ``_grow``, which moves every physical id, can never
    stale a cached row."""

    router: Optional[ShardRouter]
    placement: Optional[ShardPlacement]

    def _route_new_row(self, row: int, key) -> None:
        """Assign a freshly interned logical row to its shard (the
        overflow row routes by its own interned identity, so every
        instance of the fleet places it identically)."""
        t0 = time.monotonic_ns()
        mtype = (self._overflow_type if row == self._overflow_row
                 else key.type)
        shard = self.router.shard_for(self.interner.names[row], mtype,
                                      self.interner.joined[row])
        while self.placement.full(shard):
            self._grow()
        self.placement.assign(row, shard)
        # the store's groups share one router, and every caller holds
        # the store lock: a reader takes the difference over its hold
        self.router.place_ns += time.monotonic_ns() - t0

    @requires_lock("store")
    def _row(self, key, tags) -> int:
        row = self._intern_row(key, tags)
        # bank mode (fleet/mesh_tiered.py) has no placement: the owner
        # assigns physical slots directly and never interns here
        if self.placement is not None and not self.placement.assigned(row):
            self._route_new_row(row, key)
        return row

    @requires_lock("store")
    def ensure_capacity(self, max_row: int):
        while max_row >= self.capacity:
            self._grow()

    def _to_phys(self, rows: np.ndarray) -> np.ndarray:
        """One staged chunk's logical rows → current physical rows
        (sentinels and unassigned → capacity, the scatter-drop id). In
        bank mode the caller already speaks physical slots."""
        if self.placement is None:
            return rows
        return self.placement.to_phys(rows, self.capacity)

    def _shard_of_phys(self, phys: np.ndarray) -> np.ndarray:
        """Owning shard of physical rows — the ONE copy of the
        block-layout rule (sentinels clamp to the last shard; their
        payloads drop device-side regardless of lane)."""
        return np.minimum(np.asarray(phys) // (self.capacity
                                               // self.shards),
                          self.shards - 1)

    def _reset_placement(self) -> None:
        """In-place (non-retired) flush reset: the interner swapped, so
        the placement must too — the next interval's first series must
        consult the router, not inherit last interval's slot (the
        generation-swap path gets this for free via ``fresh()``)."""
        if self.placement is not None and not getattr(self, "_retired",
                                                      False):
            self.placement = ShardPlacement(self.shards, self.capacity)

    def _flush_rows(self, n: int) -> np.ndarray:
        """Physical rows of logical rows 0..n-1 — the gather that
        restores interner order in flush/snapshot output."""
        if self.placement is not None:
            return self.placement.perm(n)
        if self._ext_rows is not None:  # bank mode: owner-assigned slots
            return np.asarray(self._ext_rows[:n], np.int64)
        # router-less direct construction: rows intern sequentially,
        # physical == logical
        return np.arange(n, dtype=np.int64)

    def _shard_fills(self, n: int) -> np.ndarray:
        """Live rows of every shard's block for the ``n`` logical rows
        being flushed: within a block rows are handed out as a prefix,
        whoever places them (the three cases of ``_flush_rows``)."""
        if self.placement is not None:
            return self.placement.fills
        block = self.capacity // self.shards
        if self._ext_rows is not None:  # bank mode: highest slot + 1
            fills = np.zeros(self.shards, np.int64)
            slots = np.asarray(self._ext_rows[:n], np.int64)
            np.maximum.at(fills, slots // block, slots % block + 1)
            return fills
        return np.clip(n - np.arange(self.shards) * block, 0, block)


class MeshDigestGroup(_PlacementMixin, DigestGroup):
    """A DigestGroup whose device state is sharded over a fleet mesh.

    With a ``router``, series place via the fleet consistent hash
    (balanced shards + ring-aligned ownership); without one (bank mode)
    the owning :class:`~veneur_tpu.fleet.mesh_tiered.
    MeshTieredDigestGroup` assigns physical slots itself."""

    def __init__(self, mesh: Mesh, capacity: int, chunk: int,
                 compression: float, router: Optional[ShardRouter] = None):
        self.mesh = mesh
        self.shards = mesh.shape[SERIES_AXIS]
        self.hosts = mesh.shape.get(HOSTS_AXIS, 1)
        self.router = router
        cap = _round_up(capacity, self.shards)
        self.placement = (ShardPlacement(self.shards, cap)
                          if router is not None else None)
        self._ext_rows: Optional[np.ndarray] = None  # bank mode
        # the sample path's counters of this generation, read at its
        # flush like the import's (timeline ``mesh_ingest`` and the
        # ``ingest.dispatch.mesh`` stage); ``_smp_drains`` is the
        # device's own count of the dispatches whose shift guard drained
        self.smp_dispatches = 0
        self.smp_samples = 0
        self.smp_dispatch_ns = 0
        self._smp_drains = None
        super().__init__(cap, _round_up(chunk, self.hosts), compression)

    def _init_device(self):
        self.temp, self.digest, self.dmin, self.dmax = _mesh_init_digests(
            self.mesh, self.capacity, self.k, self.compression)
        self._device_dirty = False

    def _grow(self):
        """x2 growth that preserves the shard-blocked layout: every
        plane pads PER SHARD BLOCK (``_blocked_pad``) and the placement
        recomputes physical ids to match — a tail pad would hand the
        new rows entirely to the last shard."""
        self._drain_staging()
        self.capacity *= _GROW_FACTOR
        # nothing placed yet: the first touch allocates at the new size
        if "temp" in self.__dict__:
            def pad(x, fill=0.0, planes=1):
                return _blocked_pad(x, self.mesh, fill, planes)

            anchors = td_ops.BELOW_MASS_ANCHORS
            self.temp = td_ops.TempCentroids(
                sum_w=pad(self.temp.sum_w), sum_wm=pad(self.temp.sum_wm),
                seg_w=pad(self.temp.seg_w, planes=anchors),
                seg_wm=pad(self.temp.seg_wm, planes=anchors),
                count=pad(self.temp.count), vsum=pad(self.temp.vsum),
                vmin=pad(self.temp.vmin, np.inf),
                vmax=pad(self.temp.vmax, -np.inf),
                recip=pad(self.temp.recip))
            self.digest = td_ops.TDigest(
                mean=pad(self.digest.mean, np.inf),
                weight=pad(self.digest.weight),
                min=pad(self.digest.min, np.inf),
                max=pad(self.digest.max, -np.inf))
            self.dmin = pad(self.dmin, np.inf)
            self.dmax = pad(self.dmax, -np.inf)
        if self.placement is not None:
            self.placement.grow()
        # re-point staging padding at the new out-of-range row id
        self._rows[self._fill:] = self.capacity
        self._imp_rows[self._imp_fill:] = self.capacity
        self._imp_stat_rows[self._imp_stat_fill:] = self.capacity

    def _drain_samples(self):
        if self._fill == 0:
            return
        self._device_dirty = True
        self.smp_dispatches += 1
        self.smp_samples += self._fill
        rows, vals, wts = self._rows, self._vals, self._wts
        self._new_sample_buffers()
        t0 = time.monotonic_ns()
        if self._smp_drains is None:
            self._smp_drains = self._replicated_zero()
        # the chunk goes in as the NumPy arrays it is, as the import's
        # stacks do: the program puts each hosts-axis slice on its
        # devices; through jnp.asarray it landed whole on the first
        # device and was resharded from there
        with obs_kernels.scope("drain.digest.mesh"):
            self.temp, self.digest, self._smp_drains = \
                _mesh_ingest_samples(
                    self.temp, self.digest, self._smp_drains,
                    self._to_phys(rows), vals, wts, self.mesh,
                    self.compression, self.k)
        self.smp_dispatch_ns += time.monotonic_ns() - t0

    def sample_collective_bytes(self) -> int:
        """Bytes one device puts through collectives in one sample
        dispatch, from the shapes the program is called with: its
        hosts-axis slice of the chunk (row, value and weight, 4 B each,
        into the ``all_gather``) and the guard's two float32 masses.
        0 where the hosts axis is 1: the chunk needs no exchange there,
        and the guard's 8 B alone are not counted."""
        if self.hosts == 1:
            return 0
        return 4 * (3 * self.chunk // self.hosts + 2)

    def _replicated_zero(self) -> jax.Array:
        """The sample path's drain counter at its start: one int32, the
        same on every device, as the program returns it."""
        return jax.device_put(np.int32(0), NamedSharding(self.mesh, P()))

    def _guard_counters(self) -> dict:
        return dict(super()._guard_counters(),
                    mesh_ingest_guard_drains=self._smp_drains)

    def _note_drains(self) -> None:
        super()._note_drains()
        rec = obs_rec.current()
        if rec is None or not self.smp_dispatches:
            return
        rec.note(mesh_ingest_dispatches=self.smp_dispatches,
                 mesh_ingest_samples=self.smp_samples,
                 mesh_ingest_collective_bytes=(
                     self.smp_dispatches * self.sample_collective_bytes()))
        # the host's side of the generation's sample dispatches
        # (_to_phys, handing the chunk over, the call), cumulative and
        # off-path like the merger's stages: most of them ran in the
        # merger thread during the interval
        rec.record_abs("ingest.dispatch.mesh", rec.t0_ns,
                       rec.t0_ns + self.smp_dispatch_ns, off_path=True,
                       dispatches=self.smp_dispatches)

    def _per_shard(self, values) -> jax.Array:
        """A number a shard, each on its shard's devices, as the
        programs take and return such counts: handed over as NumPy it
        would be another signature, and the program's second call
        another compile."""
        return jax.device_put(np.asarray(values, np.int32),
                              NamedSharding(self.mesh, P(SERIES_AXIS)))

    def _shard_zeros(self) -> jax.Array:
        """A zero a shard: the drain counter's start, and the fills of
        a flush of no row."""
        return self._per_shard(np.zeros(self.shards))

    def _drain_imports(self):
        if self._imp_fill == 0 and self._imp_stat_fill == 0:
            return
        self._device_dirty = True
        nf, ns = self._imp_fill, self._imp_stat_fill
        self.imp_dispatches += 1
        self.imp_centroids += nf
        t0 = time.monotonic_ns()
        rows = self._to_phys(self._imp_rows[:nf])
        means = self._imp_means[:nf]
        wts = self._imp_wts[:nf]
        srows = self._to_phys(self._imp_stat_rows[:ns])
        smins = self._imp_stat_mins[:ns]
        smaxs = self._imp_stat_maxs[:ns]
        self._new_import_buffers()
        # every stack at the staging buffers' own width: the fullest
        # shard's share of a chunk is the traffic's to choose, and a
        # width that followed it (its pow2 bucket) compiled a program
        # for each bucket it met, inside the interval (PR 31 counted 10
        # and 11 in a warm window); one shape, compiled before ready
        r_st, (m_st, w_st) = route_stack(
            self.shards, self._shard_of_phys(rows), rows, [means, wts],
            self.capacity, width=self.chunk)
        sr_st, (mn_st, mx_st) = route_stack(
            self.shards, self._shard_of_phys(srows), srows,
            [smins, smaxs], self.capacity, width=self.chunk)
        if self._imp_drains is None:
            self._imp_drains = self._shard_zeros()
        t1 = time.monotonic_ns()
        # the stacks go in as the NumPy arrays they are: the program
        # puts each shard on its device. Through jnp.asarray they land
        # whole on the first device and are resharded from there, and
        # on four chips that made every call wait for the one before
        # (50 ms a call against 4 ms, my chip run, PR 33): the workers
        # held the store lock for as long as the device was busy
        with obs_kernels.scope("drain.digest.mesh"):
            (self.temp, self.digest, self.dmin, self.dmax,
             self._imp_drains) = _mesh_import_routed(
                    self.temp, self.digest, self.dmin, self.dmax,
                    self._imp_drains, r_st, m_st, w_st, sr_st, mn_st,
                    mx_st, self.mesh, self.compression)
        self.imp_route_ns += t1 - t0
        self.imp_dispatch_ns += time.monotonic_ns() - t1

    def _run_flush(self, qs, use_pallas: bool, n: int):
        # the sharded programs compile once per mesh; the compute
        # ladder's retry re-runs the same program here (the mesh path
        # has no separate kernel variant to fall back to). Rows are
        # placed by shard, and inside a shard as a prefix: each shard
        # runs its own live rows
        fills = self._shard_fills(n)
        block = self.capacity // self.shards
        obs_rec.note(rows_live=n,
                     rows_run=sum(td_ops.flush_rows_run(block, int(f))
                                  for f in fills))
        return _mesh_flush_digests(self.digest, self.temp, self.dmin,
                                   self.dmax,
                                   jnp.asarray(qs, jnp.float32),
                                   self._per_shard(fills), self.mesh,
                                   self.compression)

    def _flush_dispatch(self, n: int, percentiles, want_digests,
                        want_stats, use_pallas: bool):
        """Async half of one flush attempt: the sharded flush program
        plus a permutation gather back to interner order (physical rows
        are shard-placed, not sequential); the base ``_flush_collect``
        fetches the gathered refs in one transfer."""
        if want_digests == "packed":
            raise NotImplementedError(
                "packed digest export is a forwarding-local concern; a "
                "mesh global emits percentiles and never re-forwards")
        from veneur_tpu.core.slab import _select_stats

        sel = _select_stats(want_stats)
        qs = jnp.asarray(list(percentiles) + [0.5], jnp.float32)
        with obs_rec.maybe_stage("compute"), \
                obs_kernels.scope("flush.digest.mesh"):
            digest, pcts, count, vsum, vmin, vmax, recip = \
                self._run_flush(qs, use_pallas, n)
        planes = ()
        if want_digests:
            planes = (digest.mean, digest.weight, digest.min, digest.max)
        stats = {"pcts": pcts, "count": count, "sum": vsum,
                 "min": vmin, "max": vmax, "recip": recip}
        # the host's side of the way back to interner order: the
        # placement's permutation, handing it over, the gather's call
        with obs_rec.maybe_stage("gather"), \
                obs_kernels.scope("flush.digest.mesh"):
            # padded with row 0 to the count's pow2 bucket, a slab at
            # least (the bucket the warm-up compiled); the base class's
            # _flush_collect cuts what it fetched back to n
            rows = np.zeros(min(max(pow2_cap(n), GATHER_MIN_ROWS),
                                self.capacity), np.int32)
            rows[:n] = self._flush_rows(n)
            refs = _mesh_gather_rows(
                planes + tuple(stats[nm] for nm in sel), jnp.asarray(rows))
        return (sel, False, None, refs)

    @requires_lock("store")
    def warm(self, percentiles, want_stats=None, samples: bool = True,
             imports: bool = True) -> None:
        """Compile, or load from the persistent cache, what an interval
        of this group runs, before the first datagram or forward
        arrives: the planes' initialiser, for each way in that is open
        one dispatch that stages nothing (``samples``: the hosts-sharded
        sample ingest; ``imports``: the routed import), one flush of no
        row and its gather, through the flush's own dispatch and so
        under its signatures. The planes go again afterwards: a group
        nothing has touched holds no device memory. The programs are
        the module's, keyed by mesh and shapes, so every group of this
        size and each generation's twin finds them compiled."""
        if samples:
            with obs_kernels.scope("drain.digest.mesh"):
                self.temp, self.digest, _ = _mesh_ingest_samples(
                    self.temp, self.digest, self._replicated_zero(),
                    np.full(self.chunk, self.capacity, np.int32),
                    np.zeros(self.chunk, np.float32),
                    np.zeros(self.chunk, np.float32), self.mesh,
                    self.compression, self.k)
        if imports:
            rows = np.full((self.shards, self.chunk), self.capacity,
                           np.int32)
            zeros = np.zeros((self.shards, self.chunk), np.float32)
            with obs_kernels.scope("drain.digest.mesh"):
                (self.temp, self.digest, self.dmin, self.dmax,
                 _) = _mesh_import_routed(
                    self.temp, self.digest, self.dmin, self.dmax,
                    self._shard_zeros(), rows, zeros, zeros, rows, zeros,
                    zeros, self.mesh, self.compression)
        pending = self._flush_dispatch(0, percentiles, False, want_stats,
                                       True)
        jax.block_until_ready(pending)  # lint: ok(lock-across-blocking) start-up, before any listener opens: nobody waits on the lock yet
        for name in DigestGroup._DEVICE_STATE:
            self.__dict__.pop(name, None)
        self._device_dirty = False

    @requires_lock("store")
    def snapshot_begin(self):
        """Two-phase snapshot, mesh form: the permutation gather back to
        interner order dispatches under the lock (fresh buffers), the
        blocking fetch runs off-lock — same contract as the base."""
        from veneur_tpu.core.store import flatten_digest_state

        self._drain_staging()
        n = len(self.interner)
        snap = {"kind": "digest", "names": list(self.interner.names),
                "joined": list(self.interner.joined)}
        if n == 0:
            return snap, None
        rows = jnp.asarray(self._flush_rows(n), jnp.int32)
        refs = (self.digest.mean[rows], self.digest.weight[rows],
                td_ops.gather_bin_rows(self.temp.sum_w, rows, self.k),
                td_ops.gather_bin_rows(self.temp.sum_wm, rows, self.k),
                self.dmin[rows], self.dmax[rows],
                self.digest.min[rows], self.digest.max[rows],
                self.temp.count[rows], self.temp.vsum[rows],
                self.temp.vmin[rows], self.temp.vmax[rows],
                self.temp.recip[rows])

        def finish():
            (mean, weight, bin_w, bin_wm, imp_min, imp_max, dmn, dmx,
             cnt, vsum, vmin, vmax, recip) = jax.device_get(refs)
            snap.update(flatten_digest_state(
                np.asarray(mean, np.float32),
                np.asarray(weight, np.float32),
                np.asarray(bin_w, np.float32),
                np.asarray(bin_wm, np.float32)))
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
    def restore_stats(self, rows: np.ndarray, count: np.ndarray,
                      vsum: np.ndarray, vmin: np.ndarray,
                      vmax: np.ndarray, recip: np.ndarray):
        """Logical rows from the restore path scatter at their CURRENT
        physical placement."""
        if not len(rows):
            return
        super().restore_stats(self._to_phys(np.asarray(rows, np.int64)),
                              count, vsum, vmin, vmax, recip)

    def flush(self, percentiles, want_digests=True, want_stats=None):
        interner, out = super().flush(percentiles, want_digests,
                                      want_stats)
        self._reset_placement()
        return interner, out

    def flush_begin(self, percentiles, want_digests=True,
                    want_stats=None):
        """Two-phase flush (see ``DigestGroup.flush_begin``): the
        sharded flush program + permutation gather dispatch now; the
        placement resets with the interner once ``finish`` commits."""
        fin = super().flush_begin(percentiles, want_digests, want_stats)

        def finish():
            out = fin()
            self._reset_placement()
            return out

        return finish

    def fresh(self) -> "MeshDigestGroup":
        """Empty same-config twin (swap-on-flush generation swap); the
        module-level sharded programs are cached per mesh, so the swap
        never retraces."""
        return MeshDigestGroup(self.mesh, self.capacity, self.chunk,
                               self.compression, router=self.router)


class MeshSetGroup(_PlacementMixin, SetGroup):
    """A SetGroup whose [S, 2^p] register tensor is series-sharded — the
    scaling story for HLL HBM cost (16 KiB/series at p=14)."""

    def __init__(self, mesh: Mesh, capacity: int, chunk: int,
                 precision: int, router: Optional[ShardRouter] = None):
        self.mesh = mesh
        self.shards = mesh.shape[SERIES_AXIS]
        self.hosts = mesh.shape.get(HOSTS_AXIS, 1)
        self.router = router
        cap = _round_up(capacity, self.shards)
        self.placement = (ShardPlacement(self.shards, cap)
                          if router is not None else None)
        self._ext_rows = None
        super().__init__(cap, _round_up(chunk, self.hosts), precision)

    def _grow(self):
        self._drain_staging()
        self.capacity *= _GROW_FACTOR
        self.registers = _blocked_pad(self.registers, self.mesh)
        if self.placement is not None:
            self.placement.grow()
        self._rows[self._fill:] = self.capacity

    def _reset_registers(self):
        self.registers = _mesh_zero_registers(self.mesh, self.capacity,
                                              self.m)
        self._device_dirty = False

    def _drain_samples(self):
        if self._fill == 0:
            return
        self._device_dirty = True
        rows, hi, lo = self._rows, self._hi, self._lo
        self._new_sample_buffers()
        with obs_kernels.scope("drain.set.mesh"):
            self.registers = _mesh_ingest_hashes(
                self.registers, jnp.asarray(self._to_phys(rows)),
                jnp.asarray(hi), jnp.asarray(lo), self.mesh,
                self.precision)

    def _drain_imports(self):
        if not self._imp_rows:
            return
        self._device_dirty = True
        # shard-routed over the LIVE rows only (route_stack pads each
        # shard's lane to its own pow2 bucket): each forwarded sketch's
        # 2^p registers travel to their owning device only — padding to
        # IMPORT_DRAIN_BATCH first would funnel every sentinel into the
        # last shard's lane and re-replicate near-full batches
        rows = self._to_phys(np.asarray(self._imp_rows, np.int32))
        regs = np.stack(self._imp_regs).astype(np.int8)
        self._imp_rows.clear()
        self._imp_regs.clear()
        r_st, (regs_st,) = route_stack(
            self.shards, self._shard_of_phys(rows), rows, [regs],
            self.capacity, min_width=IMPORT_DRAIN_BATCH // self.shards)
        with obs_kernels.scope("drain.set.mesh"):
            self.registers = _mesh_merge_registers(
                self.registers, jnp.asarray(r_st), jnp.asarray(regs_st),
                self.mesh)

    def _estimates(self):
        with obs_kernels.scope("flush.set.mesh"):
            return _mesh_estimate(self.registers, self.mesh,
                                  self.precision)

    def _estimate_refs(self, n: int):
        rows = jnp.asarray(self._flush_rows(n), jnp.int32)
        return self._estimates()[rows]

    def _register_refs(self, n: int):
        rows = jnp.asarray(self._flush_rows(n), jnp.int32)
        return self.registers[rows]

    def _snapshot_refs(self, n: int):
        return self._register_refs(n)

    def flush(self, want_estimates: bool = True,
              want_registers: bool = True):
        out = super().flush(want_estimates, want_registers)
        self._reset_placement()
        return out

    def flush_begin(self, want_estimates: bool = True,
                    want_registers: bool = True):
        """Two-phase flush: the permutation-gathered estimate/register
        refs dispatch now; the placement resets once ``finish`` runs."""
        fin = super().flush_begin(want_estimates, want_registers)

        def finish():
            out = fin()
            self._reset_placement()
            return out

        return finish

    def fresh(self) -> "MeshSetGroup":
        """Empty same-config twin; sharded programs cached per mesh."""
        return MeshSetGroup(self.mesh, self.capacity, self.chunk,
                            self.precision, router=self.router)


class MeshScalarGroup(_PlacementMixin, ScalarGroup):
    """Counters/gauges under fleet mode: state stays host numpy (exact
    int64 accumulation / f64 last-write — one vectorized pass per
    interval is never the multi-chip bottleneck), but rows place
    through the SAME shard router as the device groups, so one shard
    owns a series across every group of the store — the ownership
    invariant per-shard handoff (elastic resharding) builds on, and the
    occupancy the ``/debug/vars`` mesh section reports."""

    def __init__(self, kind: str, capacity: int, mesh: Mesh,
                 router: ShardRouter):
        if kind == "status":
            raise ValueError("status checks are local-only; they never "
                             "ride the mesh")
        self.mesh = mesh
        self.shards = mesh.shape[SERIES_AXIS]
        self.router = router
        cap = _round_up(capacity, self.shards)
        super().__init__(kind, cap)
        self.placement = ShardPlacement(self.shards, cap)

    def _grow(self):
        # host state stays LOGICAL-indexed (there are no device planes
        # to lay out; the placement is ownership accounting only), so
        # growth is the base tail pad
        self.capacity *= _GROW_FACTOR
        self.values = np.concatenate(
            [self.values, np.zeros(self.capacity - len(self.values),
                                   self.values.dtype)])
        self.placement.grow()

    def snapshot_and_reset(self):
        out = super().snapshot_and_reset()
        self._reset_placement()
        return out

    def fresh(self) -> "MeshScalarGroup":
        return MeshScalarGroup(self.kind, self.capacity, self.mesh,
                               self.router)


class MeshHeavyHitterGroup(_PlacementMixin, HeavyHitterGroup):
    """Heavy hitters under fleet mode: the per-series top-k planes
    ([S, k] ids + counts) and sid vector shard over the series axis —
    the per-series residency that scales with fleet cardinality — while
    the count-min TABLE stays replicated: it is series-SHARED state
    (every row salts into the same [depth, width] grid), and replicas
    keep the update/estimate programs identical to the single-chip
    semantics (GSPMD partitions the scatter across the sharded top-k
    planes). Sharding the table itself is future work the honest way:
    per-shard partial tables change the collision population and thus
    the point estimates."""

    def __init__(self, capacity: int, chunk: int, depth: int, width: int,
                 k: int, mesh: Mesh, router: ShardRouter):
        self.mesh = mesh
        self.shards = mesh.shape[SERIES_AXIS]
        self.router = router
        self._sk = NamedSharding(mesh, P(SERIES_AXIS, None))
        self._s = NamedSharding(mesh, P(SERIES_AXIS))
        self._rep = NamedSharding(mesh, P())
        cap = _round_up(capacity, self.shards)
        self.placement = ShardPlacement(self.shards, cap)
        super().__init__(cap, chunk, depth, width, k)
        self._place_sketch()

    def _place_sketch(self):
        self.sketch = self.sketch._replace(
            table=jax.device_put(self.sketch.table, self._rep),
            topk_hi=jax.device_put(self.sketch.topk_hi, self._sk),
            topk_lo=jax.device_put(self.sketch.topk_lo, self._sk),
            topk_counts=jax.device_put(self.sketch.topk_counts,
                                       self._sk),
            sids=jax.device_put(self.sketch.sids, self._s))

    @requires_lock("store")
    def _row(self, key, tags) -> int:
        # mixin placement routing; _sids_np stays LOGICAL-indexed (the
        # sid is a per-sample VALUE gathered host-side at drain time,
        # so it follows the stable id like everything else)
        row = _PlacementMixin._row(self, key, tags)
        if self._sids_np[row] == 0:  # first sight (or the 2^-32 rehash)
            self._sids_np[row] = self.stable_sid(self.interner.names[row],
                                                 self.interner.joined[row])
        return row

    def _grow(self):
        self._drain_samples()
        self.capacity *= _GROW_FACTOR
        self.sketch = self.sketch._replace(
            topk_hi=_blocked_pad(self.sketch.topk_hi, self.mesh),
            topk_lo=_blocked_pad(self.sketch.topk_lo, self.mesh),
            topk_counts=_blocked_pad(self.sketch.topk_counts, self.mesh),
            sids=_blocked_pad(self.sketch.sids, self.mesh))
        self._place_sketch()
        self.placement.grow()
        sids = np.zeros(self.capacity + 1, np.uint32)
        sids[:len(self._sids_np) - 1] = self._sids_np[:-1]
        self._sids_np = sids
        self._rows[self._fill:] = self.capacity

    def _drain_samples(self):
        if self._fill == 0:
            return
        self._device_dirty = True
        self.dispatches += 1
        rows, hi, lo, wts = self._rows, self._hi, self._lo, self._wts
        self._new_sample_buffers()
        sids = self._sids_np[np.minimum(rows, self.capacity)]
        with obs_kernels.scope("drain.topk.mesh"):
            self.sketch = self._update(self.sketch, self._to_phys(rows),
                                       sids, hi, lo, wts)

    def _scatter_rows(self, rows: np.ndarray) -> np.ndarray:
        return self._to_phys(rows)

    def _live_topk(self, n: int):
        rows = jnp.asarray(self._flush_rows(n), jnp.int32)
        return (self.sketch.topk_hi[rows], self.sketch.topk_lo[rows],
                self.sketch.topk_counts[rows])

    def _reset_sketch(self):
        self.sketch = self._cm.init(self.capacity, self.depth,
                                    self.width, self.k)
        self._place_sketch()

    def flush(self, want_forward: bool = False):
        out = super().flush(want_forward)
        self._reset_placement()
        return out

    def flush_begin(self, want_forward: bool = False):
        """Two-phase flush: the gathered top-k plane refs dispatch now;
        the placement resets once ``finish`` runs."""
        fin = super().flush_begin(want_forward)

        def finish():
            out = fin()
            self._reset_placement()
            return out

        return finish

    def fresh(self) -> "MeshHeavyHitterGroup":
        g = MeshHeavyHitterGroup(self.capacity, self.chunk, self.depth,
                                 self.width, self.k, self.mesh,
                                 self.router)
        g._update = self._update
        g._add_table = self._add_table
        g._inject = self._inject
        return g
