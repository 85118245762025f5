"""The sparse set store (core/sets.py, a store reserved past the dense
group's 4096-row cap) against the dense ``SetGroup`` and ``ScalarHLL`` on
seeded members.

The sparse form is lossless: its registers, handed out dense, equal the
dense group's bit for bit, and its estimates equal ``hll_ops.estimate``
of those registers within ``RTOL``. The two estimators add the same
float32 terms in a different order: the dense one 2^p terms 2^-register,
the sparse one the (m - k) empty registers' ones as one number and its k
entries' terms. Each float32 sum of at most 2^14 terms is off by at most
some log2(2^14) = 14 roundings of 2^-24 (6e-8) in a pairwise reduction,
8.3e-7 of the sum; 2e-6 holds both sides. The linear-counting branch
reads only the count of empty registers, which is exact on both.
"""

import numpy as np
import pytest

from veneur_tpu.core.sets import SparseSetGroup, _sparse_set_estimate
from veneur_tpu.core.store import MetricStore, SetGroup
from veneur_tpu.obs import StageRecorder, activate
from veneur_tpu.ops import axiomhq
from veneur_tpu.ops import hll as hll_ops
from veneur_tpu.persist import deserialize, serialize
from veneur_tpu.samplers.intermetric import HistogramAggregates
from veneur_tpu.samplers.parser import MetricKey, parse_metric
from veneur_tpu.samplers.scalar import ScalarHLL

RTOL = 2e-6
AGG = HistogramAggregates.from_names(["count"])
# the smallest reservation whose set groups are sparse: one row past the
# dense group's cap (MetricStore)
SPARSE_CAPACITY = 4096 + 1


def a_store(storage, **kw):
    """A store whose set groups are dense or sparse, by its capacity."""
    cap = SPARSE_CAPACITY if storage == "sparse" else 64
    return MetricStore(initial_capacity=cap, **kw)


def key(i: int) -> MetricKey:
    return MetricKey(name=f"s{i}", type="set", joined_tags="")


def members(rng, sizes):
    """Rows and 64-bit hashes: row i gets ``sizes[i]`` random members
    (a tenth of them twice), in one shuffled stream."""
    rows = np.repeat(np.arange(len(sizes), dtype=np.int32), sizes)
    hashes = rng.integers(0, 1 << 64, len(rows), dtype=np.uint64)
    again = rng.random(len(rows)) < 0.1
    rows = np.concatenate([rows, rows[again]])
    hashes = np.concatenate([hashes, hashes[again]])
    order = rng.permutation(len(rows))
    return rows[order], hashes[order]


def fill(group, sizes, rng):
    for i in range(len(sizes)):
        group._row(key(i), [])
    rows, hashes = members(rng, sizes)
    group.sample_many(rows, hashes)
    return rows, hashes


def hashes_at(precision: int, pairs):
    """A hash whose register is ``idx`` and whose rho is ``rho``."""
    return np.array([(idx << (64 - precision)) | (1 << (64 - precision - rho))
                     for idx, rho in pairs], np.uint64)


# sizes that promote at each width: most rows small, a few past E
CASES = {
    "p14-E128": (14, 128, 4, [1, 2, 5, 40, 127, 128, 129, 300, 2000, 9000]),
    "p8-E8": (8, 8, 2, [1, 3, 7, 8, 9, 12, 30, 200, 2, 1, 60, 5]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_registers_and_estimates_equal_the_dense_group(case):
    precision, width, pool, sizes = CASES[case]
    dense = SetGroup(capacity=64, chunk=512, precision=precision)
    sparse = SparseSetGroup(capacity=64, chunk=512, precision=precision,
                            dense_rows=pool, width=width)
    fill(dense, sizes, np.random.default_rng(46))
    rows, hashes = fill(sparse, sizes, np.random.default_rng(46))
    sparse._drain_staging()
    promoted = np.asarray(sparse.slot)[:len(sizes)] >= 0
    _, d_est, d_regs = dense.flush()
    _, s_est, s_regs = sparse.flush()
    np.testing.assert_array_equal(s_regs, d_regs)
    # a row is dense exactly where it has more than E registers
    np.testing.assert_array_equal(
        promoted, np.count_nonzero(d_regs, axis=1) > width)
    assert promoted.any() and not promoted.all()
    np.testing.assert_allclose(s_est, d_est, rtol=RTOL)
    # and both are the plain reference's
    for i in range(len(sizes)):
        ref = ScalarHLL(precision)
        for h in hashes[rows == i]:
            ref.insert_hash(int(h))
        np.testing.assert_array_equal(
            s_regs[i], np.frombuffer(bytes(ref.registers), np.uint8))
        # float64 there, float32 here: the linear count's log of m /
        # zeros near 1 keeps some 1e-4 of its value (test_hll.py's bound)
        assert s_est[i] == pytest.approx(ref.estimate(), rel=1e-3,
                                         abs=1e-6)


@pytest.mark.parametrize("distinct,dense_after", [(8, False), (9, True)])
def test_a_row_at_e_entries_stays_sparse_and_one_more_promotes(
        distinct, dense_after):
    precision, width = 8, 8
    group = SparseSetGroup(capacity=16, chunk=64, precision=precision,
                           dense_rows=2, width=width)
    group._row(key(0), [])
    pairs = [(5 * j + 1, 1 + j % 3) for j in range(distinct)]
    # the same registers again, lower: nothing new
    group.sample_many(np.zeros(2 * distinct, np.int32), np.concatenate([
        hashes_at(precision, pairs), hashes_at(precision, [
            (i, 1) for i, _ in pairs])]))
    group._drain_staging()
    entries = np.asarray(group.entries[0])
    assert bool(np.asarray(group.slot)[0] >= 0) == dense_after
    assert int(np.sum(entries != 0)) == (0 if dense_after else distinct)
    _, est, regs = group.flush()
    want = np.zeros(1 << precision, np.uint8)
    for idx, rho in pairs:
        want[idx] = rho
    np.testing.assert_array_equal(regs[0], want)
    assert est[0] == pytest.approx(float(hll_ops.estimate(
        want[None].astype(np.int32), precision)[0]), rel=RTOL)


def test_a_full_pool_grows_and_drops_nothing():
    sizes = [40] * 9 + [1] * 7
    dense = SetGroup(capacity=16, chunk=128, precision=8)
    sparse = SparseSetGroup(capacity=16, chunk=128, precision=8,
                            dense_rows=1, width=8)
    fill(dense, sizes, np.random.default_rng(5))
    fill(sparse, sizes, np.random.default_rng(5))
    sparse._drain_staging()
    assert sparse.pool_grows >= 1 and sparse.dense_rows >= 9
    assert int(np.asarray(sparse.counters)[hll_ops.FILL]) == 9
    _, d_est, d_regs = dense.flush()
    _, s_est, s_regs = sparse.flush()
    np.testing.assert_array_equal(s_regs, d_regs)
    np.testing.assert_allclose(s_est, d_est, rtol=RTOL)


@pytest.mark.parametrize("filled", [3, 40, 500])
def test_imported_dense_rows_merge_as_the_dense_group_merges(filled):
    """A forwarded row of ``filled`` registers into a sparse row, one
    that promotes it, and one into a row already dense; a batch with the
    same series twice."""
    rng = np.random.default_rng(filled)
    precision = 10
    dense = SetGroup(capacity=16, chunk=256, precision=precision)
    sparse = SparseSetGroup(capacity=16, chunk=256, precision=precision,
                            dense_rows=2, width=32)
    sizes = [2, 10, 200]
    fill(dense, sizes, np.random.default_rng(1))
    fill(sparse, sizes, np.random.default_rng(1))
    for i in (0, 1, 2, 3, 1):
        regs = np.zeros(1 << precision, np.uint8)
        at = rng.choice(1 << precision, filled, replace=False)
        regs[at] = rng.integers(1, 20, filled)
        for group in (dense, sparse):
            group.import_registers(key(i), [], regs)
    _, d_est, d_regs = dense.flush()
    _, s_est, s_regs = sparse.flush()
    np.testing.assert_array_equal(s_regs, d_regs)
    np.testing.assert_allclose(s_est, d_est, rtol=RTOL)


def test_an_axiomhq_sparse_blob_imports_as_on_the_dense_store():
    from tests.test_axiomhq import ref_encode_hash, sparse_blob

    rng = np.random.default_rng(7)
    keys = [ref_encode_hash(int(x), 14)
            for x in rng.integers(0, 1 << 64, 500, dtype=np.uint64)]
    regs, p = axiomhq.decode(sparse_blob(14, keys[:50], keys[50:]))
    out = {}
    for storage in ("dense", "sparse"):
        store = a_store(storage, chunk=64)
        store.import_set(MetricKey(name="users", type="set"), [], regs)
        final, _, _ = store.flush([], AGG, is_local=False, now=1,
                                  columnar=False)
        (m,) = [m for m in final if m.name == "users"]
        out[storage] = m.value
    assert out["sparse"] == pytest.approx(out["dense"], rel=RTOL)
    assert out["sparse"] == pytest.approx(500, rel=0.1)


def _populated(storage, rng_seed=3):
    store = a_store(storage, chunk=256)
    rng = np.random.default_rng(rng_seed)
    for name, n in (("few", 3), ("many", 900), ("some", 60)):
        for member in rng.integers(0, 10**9, n):
            store.process_metric(parse_metric(
                f"{name}:{member}|s|#env:x".encode()))
    store.process_metric(parse_metric(b"c:1|c"))
    return store


def test_a_forward_exports_the_dense_groups_registers():
    fwd = {}
    for storage in ("dense", "sparse"):
        _, state, _ = _populated(storage).flush(
            [], AGG, is_local=True, now=1, forward=True, columnar=False)
        fwd[storage] = {(name, tuple(tags)): (regs, p)
                        for name, tags, regs, p in state.sets}
    assert fwd["sparse"].keys() == fwd["dense"].keys() and fwd["dense"]
    for k, (regs, p) in fwd["dense"].items():
        assert fwd["sparse"][k][1] == p
        np.testing.assert_array_equal(fwd["sparse"][k][0], regs)


def test_a_checkpoint_round_trips_through_persist():
    import time

    store = _populated("sparse")
    groups, _ = store.snapshot_state()
    assert groups["sets"]["registers"].dtype == np.uint8
    blob = serialize(groups, created_at=time.time(), interval=10.0)
    restored = a_store("sparse", chunk=256)
    assert restored.restore_state(deserialize(blob)[0]) > 0

    def emitted(s):
        final, _, _ = s.flush([], AGG, is_local=False, now=1,
                              columnar=False)
        return {(m.name, tuple(m.tags)): m.value for m in final}

    want, got = emitted(store), emitted(restored)
    assert want.keys() == got.keys()
    for k, v in want.items():
        assert got[k] == pytest.approx(v, rel=RTOL), k


def test_fresh_keeps_the_reservation_and_the_retired_planes_go():
    store = _populated("sparse")
    retired = store.sets
    retired.dense_rows = 8    # as a grown pool
    rec = StageRecorder()
    with activate(rec):
        final, _, _ = store.flush([], AGG, is_local=False, now=1,
                                  columnar=False)
    fresh = store.sets
    assert isinstance(fresh, SparseSetGroup) and fresh is not retired
    assert (fresh.capacity, fresh.dense_rows) == (SPARSE_CAPACITY, 8)
    assert "entries" not in fresh.__dict__          # placed on first write
    assert all(getattr(retired, name) is None
               for name in SparseSetGroup._DEVICE_STATE)
    notes = {}
    for stage in rec.finish()["stages"]:
        notes.update({k: v for k, v in stage.items()
                      if k.startswith("sets_")})
    assert notes["sets_rows_live"] == 3 and notes["sets_rows_run"] == 4
    assert notes["sets_promotions"] == notes["sets_rows_dense"] == 1
    assert notes["sets_dispatches"] == 4 and notes["sets_hashes"] == 963
    assert notes["sets_pool_grows"] == 0
    # the next interval starts from nothing
    store.process_metric(parse_metric(b"few:1|s|#env:x"))
    final, _, _ = store.flush([], AGG, is_local=False, now=2,
                              columnar=False)
    (m,) = [m for m in final if m.name == "few"]
    assert m.value == pytest.approx(1.0, rel=1e-3)


def test_the_estimate_runs_on_the_live_bucket(monkeypatch):
    from veneur_tpu.core import sets

    ran = []

    def spy(entries, slot, pool, rows):
        ran.append(rows)
        return _sparse_set_estimate(entries, slot, pool, rows)

    monkeypatch.setattr(sets, "_sparse_set_estimate", spy)
    group = SparseSetGroup(capacity=1024, chunk=64, precision=10)
    fill(group, [3] * 5, np.random.default_rng(0))
    group.flush(want_registers=False)
    assert ran == [8]


@pytest.mark.parametrize("capacity,kind", [
    (4096, SetGroup),                 # at the dense cap: dense
    (SPARSE_CAPACITY, SparseSetGroup),
    (1 << 16, SparseSetGroup),
])
def test_the_reservation_chooses_the_set_store(capacity, kind):
    store = MetricStore(initial_capacity=capacity)
    assert type(store.sets) is kind and type(store.local_sets) is kind
    if kind is SparseSetGroup:      # reserved whole, with no cap
        assert store.sets.capacity == capacity
        assert store.sets.dense_rows == 4096
    else:
        assert store.sets.capacity == min(capacity, 4096)


def test_a_mesh_keeps_its_dense_set_groups():
    from veneur_tpu.core.mesh_store import MeshSetGroup
    from veneur_tpu.parallel.mesh import fleet_mesh

    store = MetricStore(initial_capacity=8192, chunk=128,
                        mesh=fleet_mesh(hosts=2))
    assert isinstance(store.sets, MeshSetGroup)
    assert type(store.local_sets) is SetGroup


def test_the_device_section_reads_the_set_planes_without_a_digest():
    from veneur_tpu.debug import device_section

    store = a_store("sparse", chunk=64)
    assert "digest_planes" not in device_section(store)  # nothing placed
    assert "entries" not in store.sets.__dict__   # and the read placed none
    for i in range(70):
        store.process_metric(parse_metric(b"users:%d|s" % i))
    store.sets._drain_staging()
    planes = device_section(store)["digest_planes"]
    assert planes["group"] == "sets" and planes["platform"] == "cpu"
    assert planes["shape"] == [SPARSE_CAPACITY, hll_ops.SPARSE_ENTRIES]
    for i in range(70):
        store.process_metric(parse_metric(b"t:%d|h" % i))
    store.histograms._drain_staging()
    assert device_section(store)["digest_planes"]["group"] == "histograms"
