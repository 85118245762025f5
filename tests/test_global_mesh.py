"""The mesh-backed global store, end to end.

VERDICT r1 item 2: a real global instance (grpc/http address set) must
aggregate in device state sharded over the fleet mesh, fed by the import
servers, and its flushed fleet percentiles must match a single-device
oracle — the sharded form of the reference's importsrv merge invariant
(``importsrv/server.go:101-132`` + ``flusher.go:56-58``).

Runs on the conftest-forced 8-device virtual CPU mesh.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veneur_tpu.config import Config
from veneur_tpu.core.store import MetricStore
from veneur_tpu.parallel.mesh import fleet_mesh
from veneur_tpu.samplers import parser as p
from veneur_tpu.samplers.intermetric import HistogramAggregates
from veneur_tpu.server import Server
from veneur_tpu.sinks import ChannelMetricSink

AGG = HistogramAggregates.from_names(["min", "max", "count"])
QS = [0.5, 0.99]


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8, "conftest must force 8 CPU devices"
    return fleet_mesh(hosts=2)  # 4 series shards x 2-way ingest fan-in


def _fill_store(store, rng, n_hist=40, n_samples=64):
    for i in range(n_hist):
        for v in rng.normal(100 + i, 10, n_samples):
            store.process_metric(p.parse_metric(
                f"mesh.h{i}:{v:.4f}|h".encode()))
    for i in range(10):
        store.process_metric(p.parse_metric(f"mesh.c{i}:{i+1}|c".encode()))
    for i in range(5):
        for member in range(20 * (i + 1)):
            store.process_metric(p.parse_metric(
                f"mesh.s{i}:m{member}|s".encode()))


class TestMeshStoreOracle:
    """MetricStore(mesh=...) == MetricStore() on identical input."""

    def test_ingest_flush_matches_single_device(self, mesh):
        mstore = MetricStore(initial_capacity=64, chunk=128, mesh=mesh)
        sstore = MetricStore(initial_capacity=64, chunk=128)
        _fill_store(mstore, np.random.default_rng(7))
        _fill_store(sstore, np.random.default_rng(7))
        now = int(time.time())
        mfinal, _, _ = mstore.flush(QS, AGG, is_local=False, now=now)
        sfinal, _, _ = sstore.flush(QS, AGG, is_local=False, now=now)
        # rel=1e-4 works because each hosts-axis slice of a staged chunk
        # (chunk=128 / hosts=2 = 64) contains exactly one series' 64
        # samples, so per-slice binning equals single-device binning; if
        # n_samples stops dividing the slice size, loosen this toward the
        # 5% digest bound used below
        mby = {m.name: m.value for m in mfinal}
        sby = {m.name: m.value for m in sfinal}
        assert set(mby) == set(sby)
        for name, want in sby.items():
            assert mby[name] == pytest.approx(want, rel=1e-4, abs=1e-4), name

    def test_store_grow_on_mesh(self, mesh):
        store = MetricStore(initial_capacity=8, chunk=16, mesh=mesh)
        rng = np.random.default_rng(3)
        # 3 doublings of the histograms group while staged data is in flight
        for i in range(70):
            for v in rng.normal(50, 5, 8):
                store.process_metric(p.parse_metric(
                    f"grow.h{i}:{v:.3f}|h".encode()))
        final, _, _ = store.flush([0.5], AGG, is_local=False,
                                  now=int(time.time()))
        medians = {m.name: m.value for m in final
                   if m.name.endswith("50percentile")}
        assert len(medians) == 70
        for v in medians.values():
            assert v == pytest.approx(50, abs=6)

    def test_zero_centroid_import_flood(self, mesh):
        """>chunk imported digests with stats but no centroids must not
        overflow the fixed-size stat scatter buffers (JSON /import can
        produce min/max-only digests)."""
        g = MetricStore(initial_capacity=16, chunk=32, mesh=mesh).histograms
        key = p.MetricKey(name="flood.h", type="histogram")
        empty = np.zeros(0, np.float32)
        for i in range(80):
            g.import_centroids(key, [], empty, empty, float(i), float(i + 1))
        g._drain_staging()
        assert np.asarray(g.dmin).min() <= 0.0
        assert np.asarray(g.dmax).max() >= 80.0

    def test_imported_digests_merge_on_mesh(self, mesh):
        """Forwarded centroid state from two locals merges in device state."""
        from veneur_tpu.forward import apply_metric, metric_list_from_state

        gstore = MetricStore(initial_capacity=32, chunk=128, mesh=mesh)
        rng = np.random.default_rng(11)
        all_vals = {}
        for seed in range(2):
            lstore = MetricStore(initial_capacity=32, chunk=128)
            for i in range(6):
                vals = rng.normal(10 * i, 2, 200)
                all_vals.setdefault(i, []).extend(vals)
                for v in vals:
                    lstore.process_metric(p.parse_metric(
                        f"imp.h{i}:{v:.4f}|h".encode()))
            _, fwd, _ = lstore.flush(QS, AGG, is_local=True,
                                     now=int(time.time()))
            for m in metric_list_from_state(fwd).metrics:
                apply_metric(gstore, m)
        final, _, _ = gstore.flush(QS, AGG, is_local=False,
                                   now=int(time.time()))
        by = {m.name: m.value for m in final}
        for i, vals in all_vals.items():
            vals = np.asarray(vals)
            span = vals.max() - vals.min()
            for q in QS:
                got = by[f"imp.h{i}.{int(q*100)}percentile"]
                assert abs(got - np.quantile(vals, q)) / span < 0.05, (i, q)


class TestMeshGlobalServerE2E:
    """N local Servers → real gRPC → global Server on the 8-device mesh."""

    def test_two_locals_grpc_to_mesh_global(self):
        gcfg = Config(statsd_listen_addresses=[], interval="86400s",
                      grpc_address="127.0.0.1:0", percentiles=QS,
                      aggregates=["count"], store_initial_capacity=32,
                      store_chunk=128, mesh_enabled=True, mesh_hosts=2)
        gsink = ChannelMetricSink()
        gserver = Server(gcfg, metric_sinks=[gsink])
        gserver.start()
        try:
            from veneur_tpu.core.mesh_store import MeshDigestGroup
            assert isinstance(gserver.store.histograms, MeshDigestGroup)
            gport = gserver.import_server.port
            # single-device oracle store fed the identical forwarded state
            ostore = MetricStore(initial_capacity=32, chunk=128)
            rng = np.random.default_rng(5)
            all_vals = {}
            for li in range(2):
                lcfg = Config(statsd_listen_addresses=[], interval="86400s",
                              forward_address=f"127.0.0.1:{gport}",
                              forward_use_grpc=True, aggregates=["count"],
                              store_initial_capacity=32, store_chunk=128)
                lserver = Server(lcfg, metric_sinks=[ChannelMetricSink()])
                lserver.start()
                try:
                    for i in range(8):
                        vals = rng.gamma(2.0, 30.0, 300)
                        all_vals.setdefault(i, []).extend(vals)
                        for v in vals:
                            lserver.store.process_metric(p.parse_metric(
                                f"fleet.lat{i}:{v:.4f}|ms".encode()))
                    lserver.store.process_metric(
                        p.parse_metric(b"fleet.req:7|c|#veneurglobalonly"))
                    # mirror the forwardable state into the oracle store
                    # through the SAME wire format the real local uses
                    # (packed/quantized digests since round 4), so the
                    # mesh-vs-single-chip comparison sees identical
                    # imported centroids
                    from veneur_tpu.forward import (apply_metric,
                                                    metric_list_from_state)
                    _, ofwd, _ = lserver.store.flush(
                        QS, AGG, is_local=True, now=int(time.time()),
                        columnar=True, digest_format="packed")
                    ofwd.materialize_digests()
                    for m in metric_list_from_state(ofwd).metrics:
                        apply_metric(ostore, m)
                    # re-ingest so the real flush + forward still happens
                    for i in range(8):
                        for v in all_vals[i][-300:]:
                            lserver.store.process_metric(p.parse_metric(
                                f"fleet.lat{i}:{v:.4f}|ms".encode()))
                    lserver.store.process_metric(
                        p.parse_metric(b"fleet.req:7|c|#veneurglobalonly"))
                    lserver.flush()
                    # the forward runs off-thread (flusher.go:66-75); let it
                    # land before closing this local's channel
                    want = 9 * (li + 1)
                    deadline = time.time() + 10
                    while (time.time() < deadline
                           and gserver.store.imported < want):
                        time.sleep(0.02)
                finally:
                    lserver.shutdown()
            assert gserver.store.imported >= 18
            gserver.flush()
            by = {m.name: m.value for m in gsink.get_flush()}
            # fleet-wide counter total: 2 locals x 7
            assert by["fleet.req"] == 14.0
            # the load-bearing oracle: the mesh-sharded global's percentiles
            # equal a single-device store's on the identical forwarded state
            ofinal, _, _ = ostore.flush(QS, AGG, is_local=False,
                                        now=int(time.time()))
            oby = {m.name: m.value for m in ofinal}
            for i in range(8):
                for q in QS:
                    name = f"fleet.lat{i}.{int(q*100)}percentile"
                    assert by[name] == pytest.approx(oby[name], rel=1e-5), name
            # sanity vs the exact quantiles of all raw samples (two-stage
            # digest error bound; q99 on heavy tails is the loose case)
            for i, vals in all_vals.items():
                vals = np.asarray(vals)
                span = vals.max() - vals.min()
                for q in QS:
                    got = by[f"fleet.lat{i}.{int(q*100)}percentile"]
                    exact = np.quantile(vals, q)
                    assert abs(got - exact) / span < 0.10, (i, q, got, exact)
        finally:
            gserver.shutdown()


# ---------------------------------------------------------------------------
# the flush runs each shard's live rows (PERF.md, PR 36)
# ---------------------------------------------------------------------------

BLOCK = 4096            # a shard's rows: two of the flush loop's slabs
FLUSH_QS = [0.5, 0.75, 0.99, 0.5]


class _NamedShards:
    """A router that reads a series' shard off its name (``s.<shard>.<i>``):
    the fills of a test are then the test's to choose."""

    place_ns = 0  # the router's clock of the rows placed

    def shard_for(self, name, mtype, joined_tags):
        return int(name.split(".")[1])


def _routed_rows(group, fills):
    """Intern ``fills[s]`` series on shard ``s``, the shards taking
    turns, so that physical rows are no prefix of the logical ones."""
    left = list(fills)
    while any(left):
        for shard, more in enumerate(left):
            if more:
                i = left[shard] = more - 1
                group._row(p.MetricKey(name=f"s.{shard}.{i}",
                                       type="histogram"), [])
    return np.arange(sum(fills))


def _feed(group, rows, seed):
    """Local samples on every row of ``rows`` and a forwarded digest on
    every fifth (rows as the group's staging takes them)."""
    rng = np.random.default_rng(seed)
    for _ in range(2):
        group.sample_many(rows.astype(np.int32),
                          rng.normal(100, 10, len(rows)).astype(np.float32),
                          np.ones(len(rows), np.float32))
    held = rows[::5].astype(np.int32)
    means = np.sort(rng.normal(90, 20, (len(held), 16)).astype(np.float32))
    group.import_centroids_bulk(
        np.repeat(held, 16), means.reshape(-1),
        np.ones(means.size, np.float32), held, means[:, 0], means[:, -1])
    group._drain_staging()


def _group(mesh, source, fills):
    """A loaded ``MeshDigestGroup`` whose shards hold ``fills`` live
    rows, placed by ``source``; returns it with its live-row count."""
    from veneur_tpu.core.mesh_store import MeshDigestGroup

    block = np.arange(4) * BLOCK
    if source == "router":
        g = MeshDigestGroup(mesh, 4 * BLOCK, 4096, 100.0,
                            router=_NamedShards())
        rows = _routed_rows(g, fills)
    elif source == "grown":
        # the blocks double mid-interval, with rows held and staged
        g = MeshDigestGroup(mesh, 2 * BLOCK, 4096, 100.0,
                            router=_NamedShards())
        early = [min(f, BLOCK // 2) for f in fills]
        _feed(g, _routed_rows(g, early), 3)
        for shard, f in enumerate(fills):
            for i in range(early[shard], f):
                g._row(p.MetricKey(name=f"s.{shard}.late{i}",
                                   type="histogram"), [])
        assert g.capacity == 4 * BLOCK
        rows = np.arange(sum(early), sum(fills))
    elif source == "bank":
        # slot mode: the owner hands out each block's slots in order
        g = MeshDigestGroup(mesh, 4 * BLOCK, 4096, 100.0)
        rows = np.concatenate([block[s] + np.arange(f)
                               for s, f in enumerate(fills)])
        rows = np.random.default_rng(5).permutation(rows)
        g._ext_rows = rows.astype(np.int64)
    else:
        # no router: logical rows are the physical ones, in order
        g = MeshDigestGroup(mesh, 4 * BLOCK, 4096, 100.0)
        rows = np.arange(sum(fills))
    _feed(g, rows, 4)
    return g, sum(fills)


@jax.jit
def _straight_line(digest, temp, dmin, dmax, qs):
    """The flush over every reserved row, on one device."""
    from veneur_tpu.ops import tdigest as td_ops

    return td_ops.drain_and_quantile(digest, temp, dmin, dmax, qs, 100.0)


class TestMeshFlushLiveRows:
    """``_mesh_flush_digests`` handed the shards' fills against the
    straight-line program over every reserved row: the same bits on
    every live row, and nothing touched past a fill."""

    @pytest.mark.parametrize("source,fills", [
        ("router", (0, 1, 2047, 2049)),
        ("router", (BLOCK, 2049, 0, 1)),
        ("bank", (2049, 0, BLOCK, 2047)),
        ("direct", (BLOCK, 2049, 0, 0)),
        ("direct", (2047, 0, 0, 0)),
        ("grown", (2049, 1, 0, 2047)),
    ])
    def test_live_rows_bit_for_bit(self, mesh, source, fills):
        from veneur_tpu.core.mesh_store import _mesh_flush_digests

        g, n = _group(mesh, source, fills)
        assert tuple(int(f) for f in g._shard_fills(n)) == fills
        state = jax.tree.map(np.asarray,
                             (g.digest, g.temp, g.dmin, g.dmax))
        qs = np.asarray(FLUSH_QS, np.float32)
        want_digest, want_pcts = jax.tree.map(
            np.asarray, _straight_line(*state, qs))
        got = jax.tree.map(np.asarray, _mesh_flush_digests(
            g.digest, g.temp, g.dmin, g.dmax, jnp.asarray(qs),
            g._per_shard(fills), mesh, 100.0))
        local = np.arange(4 * BLOCK) % BLOCK
        live = local < np.repeat(fills, BLOCK)
        # the gather's rows are live rows, every one of them once
        perm = g._flush_rows(n)
        assert live[perm].all() and len(set(perm.tolist())) == n == live.sum()
        # rows the loop ran: the slabs that hold a shard's live rows
        ran = local < np.repeat([-(-f // 2048) * 2048 for f in fills], BLOCK)
        got_digest, got_pcts = got[0], got[1]
        for name in ("mean", "weight", "min", "max"):
            have, want = getattr(got_digest, name), getattr(want_digest,
                                                            name)
            assert np.array_equal(have[ran], want[ran]), name
            # past the last slab run a row is left as it was
            was = getattr(state[0], name)
            assert np.array_equal(have[~ran], was[~ran]), name
        # (an empty row of a slab that ran reads NaN on both sides)
        assert np.array_equal(got_pcts[ran], want_pcts[ran], equal_nan=True)
        assert not got_pcts[~ran].any()
        assert (got_pcts[live] > 0).all()
        for have, name in zip(got[2:], ("count", "vsum", "vmin", "vmax",
                                        "recip")):
            assert np.array_equal(have, getattr(state[1], name)), name

    def test_group_flush_reads_what_the_whole_planes_give(self, mesh):
        """Through the group: the emitted percentiles and the drained
        digests of a routed interval, in interner order."""
        fills = (2049, 1, 0, 2047)
        g, n = _group(mesh, "router", fills)
        state = jax.tree.map(np.asarray,
                             (g.digest, g.temp, g.dmin, g.dmax))
        perm = g._flush_rows(n)
        want_digest, want_pcts = jax.tree.map(np.asarray, _straight_line(
            *state, np.asarray(FLUSH_QS, np.float32)))
        _, out = g.flush(FLUSH_QS[:3])
        assert np.array_equal(out["percentiles"], want_pcts[perm, :3])
        assert np.array_equal(out["median"], want_pcts[perm, 3])
        assert np.array_equal(out["digest_mean"], want_digest.mean[perm])
        assert np.array_equal(out["digest_weight"],
                              want_digest.weight[perm])
        assert np.array_equal(out["count"], state[1].count[perm])

    def test_warm_import_and_a_first_flush_are_one_program(self, mesh):
        """The fills go in under the warm-up's signature: an import
        and the interval's first flush compile nothing more, so the
        import cell's ``start.compiles_in_window`` stays 0."""
        from veneur_tpu.core.mesh_store import (MeshDigestGroup,
                                                _mesh_flush_digests,
                                                _mesh_import_routed)
        from veneur_tpu.fleet.router import ShardRouter

        # a shape no other test of this process compiles
        g = MeshDigestGroup(mesh, 4 * 3072, 1024, 100.0,
                            router=ShardRouter(4))
        flushes = _mesh_flush_digests._cache_size()
        imports = _mesh_import_routed._cache_size()
        g.warm(FLUSH_QS[:3], samples=False)
        assert _mesh_flush_digests._cache_size() == flushes + 1
        assert _mesh_import_routed._cache_size() == imports + 1
        assert "temp" not in g.__dict__  # warmed, and holding nothing
        means = np.sort(np.random.default_rng(1).normal(
            50, 5, 32).astype(np.float32))
        for i in range(40):
            g.import_centroids(
                p.MetricKey(name=f"warm.h{i}", type="histogram"), [],
                means, np.ones(32, np.float32), float(means[0]),
                float(means[-1]))
        _, out = g.flush(FLUSH_QS[:3])
        assert out["median"] == pytest.approx(np.full(40, np.median(means)),
                                              rel=0.02)
        assert _mesh_flush_digests._cache_size() == flushes + 1
        assert _mesh_import_routed._cache_size() == imports + 1


@pytest.mark.multidevice
class TestFleetSoak:
    """The opt-in fleet lane (VENEUR_MULTIDEVICE_TESTS=1): multi-interval
    mesh soaks that need more wall-clock than the tier-1 budget allows.
    Runs on the same conftest-forced 8-device virtual mesh; the marker
    only gates TIME, not devices, so tier-1 stays flat."""

    def test_multi_interval_mesh_soak_matches_oracle(self, mesh):
        """5 flush intervals of sustained mixed traffic with mid-soak
        capacity growth: the mesh store's per-interval emissions track a
        single-device oracle fed identically, every interval."""
        mstore = MetricStore(initial_capacity=32, chunk=128, mesh=mesh)
        sstore = MetricStore(initial_capacity=32, chunk=128)
        rng_m = np.random.default_rng(77)
        rng_s = np.random.default_rng(77)
        for interval in range(5):
            # growth mid-soak: interval k adds series beyond interval
            # k-1's capacity, exercising grow-under-traffic on the mesh
            n_hist = 24 + 16 * interval
            _fill_store(mstore, rng_m, n_hist=n_hist, n_samples=64)
            _fill_store(sstore, rng_s, n_hist=n_hist, n_samples=64)
            now = int(time.time()) + interval
            mby = {m.name: m.value
                   for m in mstore.flush(QS, AGG, is_local=False,
                                         now=now)[0]}
            sby = {m.name: m.value
                   for m in sstore.flush(QS, AGG, is_local=False,
                                         now=now)[0]}
            assert set(mby) == set(sby), f"interval {interval}"
            for name, want in sby.items():
                assert mby[name] == pytest.approx(
                    want, rel=1e-4, abs=1e-4), (interval, name)

    def test_sharded_store_conserves_counts_across_intervals(self, mesh):
        """Exact count conservation through 4 intervals of ingest +
        flush on the sharded store (the mesh form of the swap-on-flush
        conservation invariant)."""
        store = MetricStore(initial_capacity=16, chunk=64, mesh=mesh)
        total = 0
        rng = np.random.default_rng(13)
        for interval in range(4):
            n = int(rng.integers(100, 400))
            for j in range(n):
                store.process_metric(p.parse_metric(
                    b"soak.h%d:%.3f|h" % (j % 37, rng.normal(50, 5))))
            total += n
            final, _, _ = store.flush(QS, AGG, is_local=False,
                                      now=interval + 1)
            got = sum(m.value for m in final
                      if m.name.startswith("soak.")
                      and m.name.endswith(".count"))
            # per-interval totals: every ingested sample lands in
            # exactly one row's count
            assert got == float(n), interval
