"""The import path's digest merge, held to the union of what the
forwarders sampled.

A global's digest of a series is the merge of its forwarders' digests.
The yardstick is a float64 NumPy union of every forwarder's samples
(nothing of the program in it): the rank error of each emitted
percentile among them stays within the documented 0.02, on the dense
store and on the mesh, for a forwarder's interval in one message and
split in two, at 16 centroids a digest (a union under the digest's
bins) and at 64 (a union five times over them), and the two stores
agree with each other. Before the row-local drain
(``ops/tdigest.py ingest_centroids_rowdrained``) the 64-centroid cases
read 0.03-0.24 wherever the staging chunk joined one message's block to
the next one's rows.
"""

import functools

import jax
import numpy as np
import pytest

from veneur_tpu.core.mesh_store import MeshDigestGroup
from veneur_tpu.core.store import DigestGroup, MetricKey
from veneur_tpu.fleet import ShardRouter
from veneur_tpu.ops import tdigest as td_ops
from veneur_tpu.parallel.mesh import fleet_mesh

SERIES, FORWARDERS, FAN_IN = 192, 8, 4
CHUNK = 2048
PERCENTILES = [0.5, 0.75, 0.99]
SEEDS = [3, 11, 2_147_483_777]


def _group(kind: str):
    if kind == "dense":
        return DigestGroup(2 * SERIES, CHUNK, 100.0)
    mesh = fleet_mesh(jax.devices()[:4], hosts=1)
    return MeshDigestGroup(mesh, 2 * SERIES, CHUNK, 100.0,
                           router=ShardRouter(4))


def _rank_error(union_sorted: np.ndarray, x: np.ndarray, q: float):
    n = union_sorted.shape[1]
    below = (union_sorted < x[:, None]).sum(axis=1) / n
    upto = (union_sorted <= x[:, None]).sum(axis=1) / n
    err = np.where((below <= q) & (q <= upto), 0.0,
                   np.minimum(np.abs(below - q), np.abs(upto - q)))
    return np.where(np.isfinite(x), err, 1.0)


@functools.lru_cache(maxsize=None)
def _merged(kind: str, split: bool, samples: int, seed: int):
    """(union of the samples [SERIES, FAN_IN * samples] sorted, emitted
    percentiles [SERIES, 3], the group's import counters)."""
    rng = np.random.default_rng(seed)
    group = _group(kind)
    vals = np.floor(rng.lognormal(3.0, 0.25, (SERIES, FAN_IN, samples))
                    * rng.uniform(0.5, 20.0, (SERIES, FAN_IN, 1))
                    * 64.0) / 64.0
    vals.sort(axis=2)
    row_of = np.zeros(SERIES, dtype=np.int64)

    def message(j, series):
        # a message's block of whole digests, then a few lone
        # centroids of series nobody else reports (the probe series
        # of the benchmark's mix: where a block ends and fresh rows
        # begin is where the chunk-wide guard let rows alias)
        names = [f"t.{i:04d}" for i in series]
        lone = [f"p.{j}.{series[0] if len(series) else 0}.{k}"
                for k in range(3)]
        rows = np.array([group._row(MetricKey(
            name=nm, type="histogram", joined_tags=""), [])
            for nm in names + lone], np.int32)
        base, probes = rows[:len(names)], rows[len(names):]
        row_of[series] = base
        group.import_centroids_bulk(
            np.concatenate([np.repeat(base, samples), probes]),
            np.concatenate([vals[series, j].reshape(-1),
                            np.full(3, 7.25)]),
            np.ones(len(base) * samples + 3),
            rows,
            np.concatenate([vals[series, j, 0],
                            np.full(3, 7.25)]).astype(np.float32),
            np.concatenate([vals[series, j, -1],
                            np.full(3, 7.25)]).astype(np.float32))

    for f in range(FORWARDERS):
        mine = np.arange(f % 2, SERIES, 2)
        if split:
            first = rng.random(len(mine)) < 0.667
            message(f // 2, mine[first])
            message(f // 2, mine[~first])
        else:
            message(f // 2, mine)
    _interner, out = group.flush(PERCENTILES, want_digests=False)
    counters = (group.imp_dispatches, group.imp_centroids)
    got = np.asarray(out["percentiles"], np.float64)[row_of]
    union = np.sort(vals.reshape(SERIES, -1), axis=1)
    return union, got, counters


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("samples", [16, 64])
@pytest.mark.parametrize("split", [False, True],
                         ids=["whole", "split"])
@pytest.mark.parametrize("kind", ["dense", "mesh"])
def test_merged_percentiles_lie_within_the_documented_rank_error(
        kind, split, samples, seed):
    union, got, (dispatches, centroids) = _merged(kind, split, samples,
                                                  seed)
    assert centroids == SERIES * FAN_IN * samples + 3 * FORWARDERS * (
        2 if split else 1)
    assert dispatches >= -(-centroids // CHUNK)
    worst = max(_rank_error(union, got[:, qi], q).max()
                for qi, q in enumerate(PERCENTILES))
    assert worst <= 0.02, worst


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("samples", [16, 64])
@pytest.mark.parametrize("split", [False, True],
                         ids=["whole", "split"])
def test_dense_and_mesh_agree(split, samples, seed):
    """Nothing of the merge is decided across rows, so four shards,
    each alone, give what one device gives."""
    _u, dense, _c = _merged("dense", split, samples, seed)
    _u, mesh, _c = _merged("mesh", split, samples, seed)
    np.testing.assert_allclose(mesh, dense, rtol=1e-6, atol=0)


def test_a_row_is_drained_only_where_it_holds_mass():
    """First sight bins into empty rows and drains nothing; a second
    digest of the same rows drains them first, and only them."""
    import jax.numpy as jnp

    rows = np.repeat(np.arange(8, dtype=np.int32), 16)
    vals = np.tile(np.arange(16, dtype=np.float32), 8)
    ones = np.ones(128, np.float32)
    temp = td_ops.init_temp(32, None, 100.0)
    digest = td_ops.init((32,), 100.0)
    digest, temp, drained = td_ops.ingest_centroids_rowdrained(
        digest, temp, jnp.asarray(rows), jnp.asarray(vals),
        jnp.asarray(ones))
    assert int(drained) == 0
    assert float(digest.weight.sum()) == 0.0
    assert float(temp.sum_w.sum()) == 128.0
    again = np.where(rows < 4, rows, 32).astype(np.int32)  # 32: padding
    digest, temp, drained = td_ops.ingest_centroids_rowdrained(
        digest, temp, jnp.asarray(again), jnp.asarray(vals + 100.0),
        jnp.asarray(ones))
    assert int(drained) == 1
    per_row = np.asarray(digest.weight.sum(axis=1))
    np.testing.assert_array_equal(per_row[:4], 16.0)   # drained
    np.testing.assert_array_equal(per_row[4:], 0.0)    # left alone
    np.testing.assert_array_equal(
        np.asarray(temp.bins()[0].sum(axis=1))[:8], 16.0)
    # the scalar stats are the local samples': imports leave them
    assert float(temp.count.sum()) == 0.0


@pytest.mark.parametrize("slab", [2, 4, 64])
def test_a_row_drains_the_same_in_any_trip(slab, monkeypatch):
    """The rows to drain are compressed a slab at a time; whether a row
    falls in the first trip, a later one or the only one, its digest is
    the same, and rows past the count are left alone."""
    import jax.numpy as jnp

    n = 64
    base = td_ops.ingest_centroids_rowdrained(
        td_ops.init((16,), 100.0), td_ops.init_temp(16, None, 100.0),
        jnp.asarray(np.repeat(np.arange(8, dtype=np.int32), 8)),
        jnp.asarray(np.tile(np.arange(8, dtype=np.float32), 8)),
        jnp.ones(n))

    def second(rows_touched):
        rows = np.full(n, 16, np.int32)
        rows[:len(rows_touched)] = rows_touched
        d, t, drained = td_ops.ingest_centroids_rowdrained(
            base[0], base[1], jnp.asarray(rows),
            jnp.asarray(np.full(n, 50.0, np.float32)), jnp.ones(n))
        assert int(drained) == 1
        return (np.asarray(d.mean), np.asarray(d.weight),
                np.asarray(t.bins()[0].sum(axis=1)))

    want_m, want_w, _ = second(np.arange(3, dtype=np.int32))
    monkeypatch.setattr(td_ops, "ROW_DRAIN_SLAB_ROWS", slab)
    few_m, few_w, few_t = second(np.arange(3, dtype=np.int32))
    all_m, all_w, all_t = second(np.arange(8, dtype=np.int32))
    for m, w in ((few_m, few_w), (all_m, all_w)):
        np.testing.assert_array_equal(w[:3], want_w[:3])
        np.testing.assert_array_equal(m[:3], want_m[:3])
    np.testing.assert_array_equal(all_w.sum(axis=1)[:8], 8.0)
    assert few_w[3:].sum() == 0.0 and all_w[8:].sum() == 0.0
    # a drained row holds the new centroid alone, the others both
    np.testing.assert_array_equal(few_t[:8], [1, 1, 1, 8, 8, 8, 8, 8])
    np.testing.assert_array_equal(all_t[:8], 1.0)


def _plain_rowdrained(digest, planes, rows, means, weights):
    """``ingest_centroids_rowdrained`` as it was written on ``[S, K]`` /
    ``[S, A]`` planes (``planes`` = sum_w, sum_wm, seg_w, seg_wm), every
    held row drained at once: the reference the flat planes answer to."""
    import jax.numpy as jnp

    sum_w, sum_wm, seg_w, seg_wm = planes
    s, k = sum_w.shape
    held = np.asarray(seg_w.sum(axis=1))
    r = np.asarray(rows)
    live = (r < s) & (np.asarray(weights) > 0)
    drain = np.unique(r[live][held[r[live]] > 0])
    if len(drain):
        at = jnp.asarray(drain)
        m, w = td_ops._merge_bins(digest.mean[at], digest.weight[at],
                                  sum_w[at], sum_wm[at], 100.0, k, False)
        digest = digest._replace(mean=digest.mean.at[at].set(m),
                                 weight=digest.weight.at[at].set(w))
        sum_w, sum_wm, seg_w, seg_wm = (
            p.at[at].set(0.0) for p in (sum_w, sum_wm, seg_w, seg_wm))
    r, v, w, b = td_ops.bin_flat_samples(rows, means, weights, s, k, 100.0,
                                         acc_seg_w=seg_w, acc_seg_wm=seg_wm)
    sg = td_ops.seg_of_bins(b, k)
    return digest, (sum_w.at[r, b].add(w, mode="drop"),
                    sum_wm.at[r, b].add(w * v, mode="drop"),
                    seg_w.at[r, sg].add(w, mode="drop"),
                    seg_wm.at[r, sg].add(w * v, mode="drop"))


@pytest.mark.parametrize("slab", [4, 1024])
@pytest.mark.parametrize("shape", ["digests", "lone_centroids"])
def test_flat_planes_equal_the_plain_planes(shape, slab, monkeypatch):
    """Four forwarders' chunks through the row-local drain: the flat
    temp's bins and anchors and the digests are those of the ``[S, K]``
    formulation bit for bit, whether the held rows drain in one trip or
    in many; padding rows (== S) drop."""
    import jax.numpy as jnp

    monkeypatch.setattr(td_ops, "ROW_DRAIN_SLAB_ROWS", slab)
    s, n = 48, 512
    k = td_ops.size_bound(100.0)
    rng = np.random.default_rng(17)
    temp, digest = td_ops.init_temp(s, k, 100.0), td_ops.init((s,), 100.0)
    plain_digest = digest
    plain = (jnp.zeros((s, k)), jnp.zeros((s, k)),
             jnp.zeros((s, td_ops.BELOW_MASS_ANCHORS)),
             jnp.zeros((s, td_ops.BELOW_MASS_ANCHORS)))
    for forwarder in range(4):
        if shape == "digests":   # 32 rows x 16 centroids, a sorted run each
            rows = np.repeat(rng.permutation(s)[:32], 16).astype(np.int32)
            vals = np.sort(rng.lognormal(0, 1, (32, 16)), axis=1).reshape(-1)
        else:
            rows = rng.integers(0, s, n).astype(np.int32)
            vals = rng.lognormal(0, 1, n)
        rows[::9] = s
        chunk = (jnp.asarray(np.sort(rows)), jnp.asarray(
            vals.astype(np.float32)), jnp.ones(n, jnp.float32))
        digest, temp, drained = td_ops.ingest_centroids_rowdrained(
            digest, temp, *chunk, use_pallas=False)
        assert int(drained) == (forwarder > 0)
        plain_digest, plain = _plain_rowdrained(plain_digest, plain, *chunk)
        for got, want in zip(temp.bins() + temp.anchors(), plain):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(digest.weight),
                                      np.asarray(plain_digest.weight))
        np.testing.assert_array_equal(np.asarray(digest.mean),
                                      np.asarray(plain_digest.mean))
    assert float(temp.count.sum()) == 0.0
