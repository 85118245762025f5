"""Shard placement for the fleet-mode store: which device owns a series.

The proxy tier already answers "which *instance* owns a series" with a
consistent-hash ring (``proxy/consistent.py``, the vendored
``stathat.com/c/consistent`` contract; ``proxy.go:437-478``). Fleet mode
asks the same question one level down — which *device shard* of the
global's mesh owns a series — and answers it with the SAME ring rule:
:class:`ShardRouter` builds a :class:`~veneur_tpu.proxy.consistent.
ConsistentRing` whose members are the series-shards, and hashes the
identical ``name + type + joined_tags`` key string the proxy's
``metric_ring_key`` uses. One hash function, two tiers: a proxy ring
over per-shard import endpoints and a shard router over the mesh agree
on ownership by construction, so a forwarded batch that a proxy already
routed lands on one series-shard without a device-side re-scatter.

The placements turn that shard choice into a *physical row id* inside a
group's device planes. Mesh planes shard dim 0 contiguously
(``NamedSharding(P("series"))``): device ``d`` of ``S`` shards owns rows
``[d*cap/S, (d+1)*cap/S)``. The interner stays dense and sequential
(logical rows 0..n-1, the order every flush/snapshot consumer expects);
a placement maps logical → physical so that a series' state lives inside
its shard's block:

- :class:`ShardPlacement` — the doubling row space of the dense mesh
  groups: physical row = ``shard * (capacity/S) + local_index``. Growth
  doubles every shard's block; existing state remaps with one blocked
  pad (``grow_blocked``: reshape → pad the per-shard block → reshape),
  and the placement recomputes every physical id vectorized.
- :class:`PoolPlacement` — the slab-append row space of the mesh tiered
  pool: a series takes the first free slot of its shard's block in the
  lowest slab with room, and growth APPENDS a slab — physical ids never
  move, matching the tiered store's slab-wise growth.

Both report per-shard occupancy and a balance ratio (max/mean fill) —
the ``/debug/vars`` ``mesh`` section and the
``veneur.fleet.shard_occupancy`` self-metric read them. Sequential
interning over a contiguous block layout would fill shard 0 completely
before shard 1 ever saw a row (balance ratio ≈ S at low fill); hash
placement keeps the ratio near 1 from the first interval.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from veneur_tpu.proxy.consistent import ConsistentRing, ring_key

__all__ = ["ring_key", "RingTransition", "ShardRouter",
           "ShardPlacement", "PoolPlacement", "route_stack",
           "inverse_perm"]


class RingTransition:
    """One fleet-membership change as a routing object: which instance
    owned a series before, which owns it after, and whether a given
    instance loses it. Built from a discovery refresh diff
    (``discovery.RingWatcher``); consumed by the handoff manager's
    moved-range extraction (``fleet/handoff.py``) and by tests that
    assert the proxy and the handoff agree on ownership."""

    def __init__(self, old_members: Sequence[str],
                 new_members: Sequence[str], replicas: int = 20):
        self.old_members = sorted(set(old_members))
        self.new_members = sorted(set(new_members))
        self.old_ring = ConsistentRing(self.old_members, replicas=replicas) \
            if self.old_members else None
        self.new_ring = ConsistentRing(self.new_members, replicas=replicas) \
            if self.new_members else None

    def new_owner(self, name: str, mtype: str, joined_tags: str) -> Optional[str]:
        if self.new_ring is None:
            return None
        return self.new_ring.get(ring_key(name, mtype, joined_tags))

    def new_owners(self, names: Sequence[str], mtype: str,
                   joined_tags: Sequence[str]) -> List[Optional[str]]:
        """Batched :meth:`new_owner`: one ring-lock hold for the whole
        series list (``ConsistentRing.get_many``) — the handoff
        extraction's moved-range computation routes per group batch,
        not per key."""
        if self.new_ring is None:
            return [None] * len(names)
        return self.new_ring.get_many(
            [ring_key(n, mtype, j) for n, j in zip(names, joined_tags)])

    def old_owner(self, name: str, mtype: str, joined_tags: str) -> Optional[str]:
        if self.old_ring is None:
            return None
        return self.old_ring.get(ring_key(name, mtype, joined_tags))

    def moved(self, name: str, mtype: str, joined_tags: str) -> bool:
        """Whether this series' owner changed across the transition."""
        return (self.old_owner(name, mtype, joined_tags)
                != self.new_owner(name, mtype, joined_tags))

    def loses_ranges(self, member: str) -> bool:
        """Whether ``member`` can lose any range: it owned ranges
        before (was a member) and the membership actually changed.
        The single-member degenerate cases fall out naturally: 1→N
        loses ranges, N→1 loses everything on the departing members,
        1→1 (same member) never does."""
        return (member in self.old_members
                and self.old_members != self.new_members)


class ShardRouter:
    """series identity → series-shard index, by the proxy's ring rule.

    Stateless per series (the ring is fixed at mesh construction): every
    group of one store shares one router, so a series owns the SAME
    shard across scalars, digests, sets and heavy hitters — the
    property a per-shard handoff (elastic resharding, ROADMAP item 4)
    needs."""

    # The proxy ring's 20 virtual points a member are too few for a
    # ring of 2-8 members whose names differ in one digit: CRC32 of
    # "shard-<i><r>" clusters, and one of two shards took 84% of the
    # series (balance ratio 1.69; 1.62 at four shards). Measured over
    # 40k sequentially named series, 160 points give 1.23 / 1.16 / 1.13
    # at 2 / 4 / 8 shards. A mesh shard's block is capacity/shards
    # rows, so skew is capacity lost on every chip.
    def __init__(self, shards: int, replicas: int = 160):
        if shards < 1:
            raise ValueError(f"need >= 1 shard, got {shards}")
        self.replicas = replicas
        self.shards = shards
        self._index: Dict[str, int] = {
            f"shard-{i}": i for i in range(shards)}
        self._ring = ConsistentRing(list(self._index), replicas=replicas)
        # ns the store's groups have spent placing first-sight rows
        # (the lookup here, the placement's assign and any growth):
        # cumulative, written under the store lock
        # (core/mesh_store.py _route_new_row)
        self.place_ns = 0

    def shard_for(self, name: str, mtype: str, joined_tags: str) -> int:
        """The shard owning one series — the shared :func:`ring_key`
        rule against a ring of shards."""
        if self.shards == 1:
            return 0
        return self._index[self._ring.get(ring_key(name, mtype,
                                                   joined_tags))]


class ShardPlacement:
    """Logical (interner) rows → shard-blocked physical rows, with
    doubling growth. All host-side numpy; the owning group calls under
    the store lock."""

    def __init__(self, shards: int, capacity: int):
        if capacity % shards:
            raise ValueError(
                f"capacity {capacity} not divisible by {shards} shards")
        self.shards = shards
        self.capacity = capacity
        self.block = capacity // shards
        self.fills = np.zeros(shards, np.int64)
        self._shard_of = np.empty(0, np.int32)
        self._local_of = np.empty(0, np.int32)
        self._phys = np.empty(0, np.int64)
        self._count = 0

    def __len__(self) -> int:
        return self._count

    def assigned(self, logical: int) -> bool:
        return logical < self._count

    def full(self, shard: int) -> bool:
        return int(self.fills[shard]) >= self.block

    def assign(self, logical: int, shard: int) -> int:
        """Place the next logical row on ``shard``; rows assign in
        logical order (the interner is sequential)."""
        assert logical == self._count, (logical, self._count)
        local = int(self.fills[shard])
        if local >= self.block:
            raise IndexError(f"shard {shard} full at {self.block} rows")
        self.fills[shard] = local + 1
        if self._count >= len(self._shard_of):
            grow = max(256, len(self._shard_of))
            self._shard_of = np.concatenate(
                [self._shard_of, np.empty(grow, np.int32)])
            self._local_of = np.concatenate(
                [self._local_of, np.empty(grow, np.int32)])
            self._phys = np.concatenate(
                [self._phys, np.empty(grow, np.int64)])
        self._shard_of[self._count] = shard
        self._local_of[self._count] = local
        phys = shard * self.block + local
        self._phys[self._count] = phys
        self._count += 1
        return phys

    def phys(self, logical: int) -> int:
        return int(self._phys[logical])

    def perm(self, n: Optional[int] = None) -> np.ndarray:
        """Physical row of each logical row 0..n-1 — the flush/snapshot
        gather order that restores interner ordering."""
        n = self._count if n is None else n
        return self._phys[:n].copy()

    def to_phys(self, rows: np.ndarray, sentinel: int) -> np.ndarray:
        """Vectorized logical → physical translation for one staged
        chunk, AT DRAIN TIME. Logical rows are the ids that cross the
        group boundary (and live in the native intern memos / lane
        resolvers / bulk-ingest loops): they are stable forever, so a
        mid-interval ``grow`` — which moves every physical id — can
        never stale a cached row. Unassigned/sentinel entries map to
        ``sentinel`` (the scatter-drop convention)."""
        rows = np.asarray(rows)
        out = np.full(rows.shape, sentinel, rows.dtype)
        valid = rows < self._count
        out[valid] = self._phys[rows[valid]]
        return out

    def grow(self) -> None:
        """Double every shard's block (mirrors the owning group's
        blocked-pad device grow); physical ids recompute vectorized."""
        self.block *= 2
        self.capacity *= 2
        n = self._count
        self._phys[:n] = (self._shard_of[:n].astype(np.int64) * self.block
                          + self._local_of[:n])

    def occupancy(self) -> dict:
        return _occupancy(self.fills, self.block)


class PoolPlacement:
    """Slab-append placement for the mesh tiered pool: physical row =
    ``slab * slab_rows + shard * block + index``; growth appends slabs
    and never moves a row."""

    def __init__(self, shards: int, slab_rows: int, slabs: int = 1):
        if slab_rows % shards:
            raise ValueError(
                f"slab_rows {slab_rows} not divisible by {shards} shards")
        self.shards = shards
        self.slab_rows = slab_rows
        self.block = slab_rows // shards
        # fills[slab][shard]
        self.fills: List[np.ndarray] = [np.zeros(shards, np.int64)
                                        for _ in range(max(1, slabs))]
        self._phys = np.empty(0, np.int64)
        self._count = 0

    def __len__(self) -> int:
        return self._count

    @property
    def slabs(self) -> int:
        return len(self.fills)

    def assigned(self, logical: int) -> bool:
        return logical < self._count

    def assign(self, logical: int, shard: int) -> Tuple[int, bool]:
        """Place the next logical row on ``shard``; returns
        ``(physical_row, appended_slab)`` — the owner must append a
        device slab when the second element is True."""
        assert logical == self._count, (logical, self._count)
        appended = False
        slab = None
        for i, f in enumerate(self.fills):
            if int(f[shard]) < self.block:
                slab = i
                break
        if slab is None:
            self.fills.append(np.zeros(self.shards, np.int64))
            slab = len(self.fills) - 1
            appended = True
        local = int(self.fills[slab][shard])
        self.fills[slab][shard] = local + 1
        if self._count >= len(self._phys):
            grow = max(256, len(self._phys))
            self._phys = np.concatenate(
                [self._phys, np.empty(grow, np.int64)])
        phys = slab * self.slab_rows + shard * self.block + local
        self._phys[self._count] = phys
        self._count += 1
        return phys, appended

    def phys(self, logical: int) -> int:
        return int(self._phys[logical])

    def perm(self, n: Optional[int] = None) -> np.ndarray:
        n = self._count if n is None else n
        return self._phys[:n].copy()

    def shard_of_local(self, slab_local: np.ndarray) -> np.ndarray:
        """Series-shard of slab-LOCAL physical rows (the tiered drains
        partition per slab first)."""
        return np.minimum(np.asarray(slab_local) // self.block,
                          self.shards - 1)

    def occupancy(self) -> dict:
        fills = np.sum(np.stack(self.fills), axis=0)
        return _occupancy(fills, self.block * len(self.fills))


def _occupancy(fills: np.ndarray, block: int) -> dict:
    total = int(fills.sum())
    mean = total / len(fills)
    return {
        "per_shard": [int(f) for f in fills],
        "rows": total,
        "block": int(block),
        # max/mean fill: 1.0 = perfectly balanced, S = everything on
        # one shard (what sequential block interning degraded to)
        "balance_ratio": round(float(fills.max()) / mean, 4) if total
        else 1.0,
    }


def inverse_perm(perm: np.ndarray, capacity: int) -> np.ndarray:
    """physical row → logical row (-1 = hole); the snapshot paths use it
    to translate per-slab flatten output back to interner order."""
    inv = np.full(capacity, -1, np.int64)
    inv[perm] = np.arange(len(perm), dtype=np.int64)
    return inv


def route_stack(shards: int, shard_idx: np.ndarray,
                rows: np.ndarray, arrays: Sequence[np.ndarray],
                sentinel_row: int,
                min_width: int = 8,
                width: Optional[int] = None) -> Tuple[np.ndarray, list]:
    """Partition one staged chunk into a ``[shards, b]`` stack whose
    dim 0 shards over the series axis — each device then receives
    exactly its own rows' sub-chunk (whole, order-preserved) and bins
    only that, instead of binning a replicated full chunk and dropping
    foreign rows. ``b`` is the pow2 bucket of the fullest shard's count
    (``core/bucketing.py`` ladder: the compiled-program variant count
    stays log-bounded), or ``width`` where the caller gives one: a
    staging buffer of fixed size reaches the device at that one shape
    whatever share of it the fullest shard owns, so the traffic never
    chooses a program. Padding rows carry ``sentinel_row`` and zeroed
    payloads, the drop convention every scatter program shares."""
    from veneur_tpu.core.bucketing import pow2_cap

    per_shard: List[np.ndarray] = []
    for s in range(shards):
        per_shard.append(np.flatnonzero(shard_idx == s))
    fullest = max((len(ix) for ix in per_shard), default=0)
    if width is None:
        b = pow2_cap(max(min_width, fullest))
    elif fullest > width:
        raise ValueError(f"route_stack: a shard owns {fullest} of the "
                         f"chunk's entries, over the width {width}")
    else:
        b = width
    out_rows = np.full((shards, b), sentinel_row, rows.dtype)
    out_arrays = [np.zeros((shards, b) + a.shape[1:], a.dtype)
                  for a in arrays]
    for s, ix in enumerate(per_shard):
        m = len(ix)
        if not m:
            continue
        out_rows[s, :m] = rows[ix]
        for dst, a in zip(out_arrays, arrays):
            dst[s, :m] = a[ix]
    return out_rows, out_arrays
