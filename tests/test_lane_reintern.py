"""Lane rows re-interned from a cached identity.

``MetricStore._lane_remap`` decodes a series' (name, tags) bytes once,
keeps the ``MetricKey`` and tag list in the lane's registry and in a
memo every lane shares, and at every later flush epoch interns them
again in one batch a chunk (``OverloadLimited.intern_many``). The
contract under test: a store driven that way ends every chunk in the
state a store reaches when every unresolved row is decoded and interned
one at a time through ``_intern_native`` (the per-row path): the same
rows in the same order, the same names, tags and joined tags, the same
staged values, the same spill, freeze and truncation counts, and the
same emissions.
"""

import copy
import types

import numpy as np
import pytest

from veneur_tpu.core import MetricStore
from veneur_tpu.core.store import (_K_COUNTER, _K_GAUGE, _K_GLOBAL_COUNTER,
                                   _K_GLOBAL_GAUGE, _K_HISTO, _K_LOCAL_HISTO,
                                   _K_LOCAL_SET, _K_LOCAL_TIMER, _K_SET,
                                   _K_TIMER, _K_TOPK)
from veneur_tpu.ingest.lanes import LaneResolver, SealedChunk
from veneur_tpu.samplers import HistogramAggregates

AGGS = HistogramAggregates()

KINDS = {
    "counter": _K_COUNTER, "global_counter": _K_GLOBAL_COUNTER,
    "gauge": _K_GAUGE, "global_gauge": _K_GLOBAL_GAUGE,
    "histogram": _K_HISTO, "local_histogram": _K_LOCAL_HISTO,
    "timer": _K_TIMER, "local_timer": _K_LOCAL_TIMER,
    "set": _K_SET, "local_set": _K_LOCAL_SET, "topk": _K_TOPK,
}

# lane rows each chunk of an epoch carries: epoch 1 brings rows 16..23
# to the registry and leaves rows 0..3 idle, epoch 2 brings them back
EPOCHS = [
    [np.r_[0:10, 3, 7], np.r_[8:16, 0]],
    [np.r_[4:20], np.r_[16:24, 5, 5]],
    [np.r_[0:12], np.r_[20:24, 1]],
]
SERIES = 24


def _entries(n, prefix="s", tag_bytes=16):
    """``n`` registry entries: every other one tagged, one tag string
    a row of ``tag_bytes`` or more."""
    out = []
    for i in range(n):
        tags = b"" if i % 2 else (b"a:%d,b:%s" % (i, b"x" * tag_bytes))
        out.append((b"%s%d" % (prefix.encode(), i), tags))
    return out


def _span(kind, rows, rng):
    rows = np.asarray(rows, np.int64)
    n = len(rows)
    if kind in (_K_COUNTER, _K_GLOBAL_COUNTER):
        return (rows, rng.integers(1, 100, n).astype(np.int64), None, None)
    if kind in (_K_GAUGE, _K_GLOBAL_GAUGE):
        return (rows, rng.random(n) * 100.0, None, None)
    if kind in (_K_SET, _K_LOCAL_SET, _K_TOPK):
        hashes = rng.integers(1, 1 << 62, n).astype(np.uint64)
        members = ([b"m%d" % h for h in hashes] if kind == _K_TOPK
                   else None)
        return (rows, hashes, None, members)
    return (rows, (rng.random(n) * 100.0).astype(np.float32),
            np.ones(n, np.float32), None)


def _chunks(kind, entries, seed=1, lane=0):
    """Per epoch, lane ``lane``'s sealed chunks; each chunk brings the
    registry entries of the rows it is first to reference."""
    rng = np.random.default_rng(seed)
    known = 0
    out = []
    for epoch in EPOCHS:
        chunks = []
        for rows in epoch:
            top = int(rows.max()) + 1
            new = {kind: entries[known:top]} if top > known else {}
            known = max(known, top)
            chunks.append(SealedChunk(lane, 0, len(rows),
                                      {kind: _span(kind, rows, rng)},
                                      new, []))
        out.append(chunks)
    return out


def _per_row_remap(self, kind, resolver, rows):
    """The per-row path: each unresolved row decoded from the lane's
    bytes and interned on its own through ``_intern_native``."""
    entries = resolver.entries[kind]
    remap = resolver.remap[kind]
    if remap is None or len(remap) < len(entries):
        grown = np.full(len(entries), -1, np.int64)
        if remap is not None and len(remap):
            grown[:len(remap)] = remap
        remap = resolver.remap[kind] = grown
    needed = np.unique(rows)
    todo = needed[remap[needed] < 0]
    t, sc = self._KIND_NATIVE[kind]
    for r in todo:
        remap[r] = self._intern_native(t, sc, *entries[int(r)])[2]
    return remap, len(todo), 0


def _reference(store):
    store._lane_remap = types.MethodType(_per_row_remap, store)
    return store


def _group_state(group):
    it = group.interner
    state = {"rows": list(it.rows.items()), "names": list(it.names),
             "tags": list(it.tags), "joined": list(it.joined),
             "spilled": group.spilled}
    n = len(it)
    if hasattr(group, "values"):
        state["values"] = group.values[:n].tolist()
    fill = getattr(group, "_fill", None)
    if fill is not None:
        for attr in ("_rows", "_vals", "_wts", "_hi", "_lo"):
            if hasattr(group, attr):
                state[attr] = getattr(group, attr)[:fill].tolist()
    if hasattr(group, "grows"):  # a slab group: its slabs placed
        state["slabs"] = (len(group.digests), group.grows)
    if hasattr(group, "_sids_np"):
        state["sids"] = group._sids_np[:n].tolist()
    placement = getattr(group, "placement", None)
    if placement is not None:
        state["placement"] = (placement.perm().tolist(),
                              placement.fills.tolist())
    return state


def _store_state(store):
    with store._lock:
        return {k: _group_state(store._group_for_kind(k))
                for k in KINDS.values()}


def _emissions(store):
    final, fwd, _ = store.flush([0.5], AGGS, is_local=True, now=1)
    return (sorted((m.name, m.type, m.value, tuple(m.tags)) for m in final),
            fwd.counters, fwd.gauges)


def _drive(new, ref, chunks_by_epoch):
    """Merge the same chunks into both stores, comparing after each chunk
    and each flush; returns the cached store's rows_reinterned an epoch
    and the samples its groups spilled in all."""
    resolvers = {}
    reinterned = []
    spilled = 0
    for chunks in chunks_by_epoch:
        timing_new = dict.fromkeys(("lock_wait", "remap", "route", "stage",
                                    "rows_interned", "rows_reinterned"), 0)
        timing_ref = dict(timing_new)
        for chunk in chunks:
            res_new, res_ref = resolvers.setdefault(
                chunk.lane_id, (LaneResolver(0), LaneResolver(0)))
            new.import_lane_chunk(chunk, res_new, timing_new)
            ref.import_lane_chunk(copy.deepcopy(chunk), res_ref, timing_ref)
            assert _store_state(new) == _store_state(ref)
            assert (new.quarantine.snapshot()
                    == ref.quarantine.snapshot())
        assert timing_new["rows_interned"] == timing_ref["rows_interned"]
        reinterned.append(timing_new["rows_reinterned"])
        spilled += sum(g["spilled"] for g in _store_state(new).values())
        assert _emissions(new) == _emissions(ref)
    return reinterned, spilled


def _store(**kw):
    kw.setdefault("initial_capacity", 32)
    kw.setdefault("chunk", 128)
    return MetricStore(**kw)


def _expected_reinterned(chunks_by_epoch):
    """Rows an epoch interns whose series some lane had decoded before:
    a lane row resolves once an epoch, a series decodes once."""
    registries, decoded, out = {}, set(), []
    for chunks in chunks_by_epoch:
        resolved, hits = set(), 0
        for chunk in chunks:
            entries = registries.setdefault(chunk.lane_id, [])
            for new in chunk.new_entries.values():
                entries.extend(new)
            for r in set(next(iter(chunk.spans.values()))[0].tolist()):
                if (chunk.lane_id, r) in resolved:
                    continue
                resolved.add((chunk.lane_id, r))
                hits += entries[r] in decoded
                decoded.add(entries[r])
        out.append(hits)
    return out


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_reintern_matches_per_row_path_across_flushes(kind):
    k = KINDS[kind]
    chunks = _chunks(k, _entries(SERIES))
    reinterned, _ = _drive(_store(), _reference(_store()), chunks)
    assert reinterned == _expected_reinterned(chunks) == [0, 12, 16]


@pytest.mark.parametrize("kind", ["histogram", "local_timer"])
def test_slab_group_places_slabs_as_per_row_path(kind):
    # 24 series on slabs of 8 rows: each epoch's fresh generation grows
    # past the slabs the last one placed, at the chunk's staging here
    # and inside the remap on the per-row path, before either stages
    chunks = _chunks(KINDS[kind], _entries(SERIES))
    cfg = dict(digest_storage="slab", slab_rows=8)
    reinterned, _ = _drive(_store(**cfg), _reference(_store(**cfg)),
                           chunks)
    assert reinterned == [0, 12, 16]


@pytest.mark.parametrize("kind", ["gauge", "timer"])
def test_two_lanes_intern_one_series_once(kind):
    # lane 1 mints the same series in the opposite row order: one
    # lane's batch finds the other's rows already interned, and the
    # other's decoded identities
    entries = _entries(SERIES)
    a = _chunks(KINDS[kind], entries, seed=1, lane=0)
    b = _chunks(KINDS[kind], entries[::-1], seed=2, lane=1)
    chunks = [ca + cb for ca, cb in zip(a, b)]
    reinterned, _ = _drive(_store(), _reference(_store()), chunks)
    # epoch 0: lane 1 finds the 8 series lane 0 decoded; then every row
    assert reinterned == _expected_reinterned(chunks) == [8, 40, 32]


class _Freeze:
    """OverloadController stand-in: first-sight series freeze while
    ``on``."""

    def __init__(self, on):
        self.on = on

    def freeze_new_series(self):
        return self.on


@pytest.mark.parametrize("kind", ["counter", "histogram", "set", "topk"])
@pytest.mark.parametrize("limit", [
    pytest.param(dict(max_series=64), id="cap-clear"),
    pytest.param(dict(max_series=18), id="cap-crossed"),
    pytest.param(dict(overload=_Freeze(True)), id="freeze"),
    pytest.param(dict(overload=_Freeze(False)), id="no-freeze"),
])
def test_overload_matches_per_row_path(kind, limit):
    # half the names are self-telemetry: exempt from the freeze, not
    # from the cap
    entries = [(b"veneur." + name if i % 2 else name, tags)
               for i, (name, tags) in enumerate(_entries(SERIES))]
    chunks = _chunks(KINDS[kind], entries)
    new, ref = _store(**limit), _reference(_store(**limit))
    _, spilled = _drive(new, ref, chunks)
    crossed = limit.get("max_series", SERIES) < SERIES
    frozen = "overload" in limit and limit["overload"].on
    assert (spilled > 0) == (crossed or frozen)


def test_oversized_tags_counted_once_per_reintern():
    # every tagged row's tags pass the cap: 12 of the 24 rows, each
    # counted each time an epoch interns it
    entries = _entries(SERIES, tag_bytes=64)
    chunks = _chunks(_K_COUNTER, entries)
    new = _store(max_tag_length=32)
    ref = _reference(_store(max_tag_length=32))
    _drive(new, ref, chunks)
    tagged = 0
    for epoch in EPOCHS:
        rows = set(np.concatenate(epoch).tolist())
        tagged += sum(1 for r in rows if r % 2 == 0)
    assert new.quarantine.snapshot()["oversized_tags"] == tagged
    joined = new.counters.interner.joined  # flushed: the last epoch's
    assert all(len(j) <= 32 for j in joined)


def test_lane_reset_never_reuses_a_cached_identity():
    from veneur_tpu.ingest import IngestFleet
    from veneur_tpu.protocol.addr import resolve_addr

    store = _store()
    fleet = IngestFleet(store, resolve_addr("udp://127.0.0.1:0"), 1,
                        1 << 20, 4096, chunk_records=256,
                        intern_limit=1024)
    try:
        lane = fleet.lanes[0]
        lane._stage_python([b"old:5|c"])
        lane._seal()
        fleet.merge_sealed()
        final, _, _ = store.flush([], AGGS, is_local=True, now=1)
        assert {m.name: m.value for m in final} == {"old": 5}
        # the bounded-memory rollover re-mints row 0 for another series
        # under a new generation, in a new flush epoch; the old series
        # comes back on row 1 and finds its identity by its bytes
        lane._intern_total = lane._intern_limit
        lane._stage_python([b"fresh:7|c", b"old:3|c"])
        lane._seal()
        before = dict(fleet.merge_ns)
        fleet.merge_sealed()
        assert lane.gen == 1
        assert fleet.merge_ns["rows_interned"] - before["rows_interned"] == 2
        assert (fleet.merge_ns["rows_reinterned"]
                - before["rows_reinterned"]) == 1
        final, _, _ = store.flush([], AGGS, is_local=True, now=2)
        assert {m.name: m.value for m in final} == {"fresh": 7, "old": 3}
    finally:
        fleet.shutdown()


@pytest.mark.parametrize("kind", ["counter", "histogram", "set", "topk"])
def test_mesh_routes_reinterned_rows_as_per_row_path(kind):
    from veneur_tpu.parallel.mesh import fleet_mesh

    mesh = fleet_mesh(hosts=2)
    chunks = _chunks(KINDS[kind], _entries(SERIES))
    new = _store(mesh=mesh)
    ref = _reference(_store(mesh=mesh))
    # _group_state carries each group's placement: the same logical row
    # on the same shard slot after every chunk of every epoch
    assert _drive(new, ref, chunks)[0] == [0, 12, 16]
