"""Fleet mode: mesh-sharded tiered store + shard-routed aggregation.

Tier-1 covers the router/placement machinery, the config surface, and a
small mesh-tiered-vs-single-device oracle (the conftest always forces
the 8-device virtual CPU mesh, so the sharded programs compile here
too). The ``multidevice``-marked class holds the fleet acceptance
criteria — ingest → import → flush → checkpoint round-trip at soak
scale — and runs in the default verify path via
``VENEUR_MULTIDEVICE_TESTS=1`` (see .claude/skills/verify/SKILL.md).
"""

import time

import jax
import numpy as np
import pytest

from veneur_tpu.config import Config
from veneur_tpu.core.store import MetricStore
from veneur_tpu.fleet import (PoolPlacement, ShardPlacement, ShardRouter,
                              fleet_snapshot, route_stack)
from veneur_tpu.parallel.mesh import fleet_mesh
from veneur_tpu.samplers import parser as p
from veneur_tpu.samplers.intermetric import HistogramAggregates

AGG = HistogramAggregates.from_names(["min", "max", "count"])
QS = [0.5, 0.99]

TIER_KW = dict(store_initial_capacity=32, store_chunk=128,
               tier_promote_samples=48, tier_promote_intervals=1,
               tier_demote_intervals=2)


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8, "conftest must force 8 CPU devices"
    return fleet_mesh(hosts=2)  # 4 series shards x 2-way ingest fan-in


def _tiered_store(mesh=None):
    return MetricStore(initial_capacity=32, chunk=128, mesh=mesh,
                       digest_storage="tiered", slab_rows=64,
                       tier_promote_samples=48, tier_promote_intervals=1,
                       tier_demote_intervals=2)


def _fill(store, rng, n_hist=24, hot_every=3):
    """Mixed hot/cold traffic: every ``hot_every``-th series crosses the
    promotion bar, the rest stay pool-resident."""
    counts = {}
    for i in range(n_hist):
        n = 64 if i % hot_every == 0 else 8
        counts[f"fleet.h{i}"] = counts.get(f"fleet.h{i}", 0) + n
        for v in rng.normal(100 + 10 * i, 5 + i, n):
            store.process_metric(p.parse_metric(
                f"fleet.h{i}:{v:.4f}|h".encode()))
    for i in range(8):
        store.process_metric(p.parse_metric(
            f"fleet.c{i}:{i + 1}|c|#veneurglobalonly".encode()))
    for i in range(4):
        for member in range(15 * (i + 1)):
            store.process_metric(p.parse_metric(
                f"fleet.s{i}:m{member}|s".encode()))
    return counts


class TestShardRouter:
    def test_deterministic_and_ring_aligned(self):
        """The router IS the proxy ring rule: same CRC32 ring, members
        named shard-<i>, same ``name + type + joined_tags`` key (with
        the router's own count of virtual points a member)."""
        from veneur_tpu.proxy.consistent import ConsistentRing

        router = ShardRouter(4)
        ring = ConsistentRing([f"shard-{i}" for i in range(4)],
                              replicas=router.replicas)
        for i in range(200):
            name, jt = f"api.latency.{i}", "env:prod,az:b"
            want = int(ring.get(name + "timer" + jt).split("-")[1])
            assert router.shard_for(name, "timer", jt) == want
            # stable across calls
            assert router.shard_for(name, "timer", jt) == want

    def test_spreads_series(self):
        router = ShardRouter(4)
        hits = np.zeros(4, np.int64)
        for i in range(2000):
            hits[router.shard_for(f"svc.metric.{i}", "histogram", "")] += 1
        assert hits.min() > 0
        assert hits.max() / hits.mean() < 1.5

    @pytest.mark.parametrize("shards", [2, 4, 8])
    def test_balance_at_small_shard_counts(self, shards):
        """A four-chip host is two series-shards: the ring must not
        hand one of them most of the series."""
        router = ShardRouter(shards)
        hits = np.zeros(shards, np.int64)
        for i in range(20000):
            hits[router.shard_for(f"smoke.h.{i}", "histogram", "")] += 1
        assert hits.max() / hits.mean() < 1.3, hits

    def test_single_shard_short_circuit(self):
        assert ShardRouter(1).shard_for("x", "counter", "") == 0


class TestPlacements:
    def test_shard_placement_grow_remaps(self):
        pl = ShardPlacement(4, 16)  # block of 4
        phys = [pl.assign(i, i % 4) for i in range(12)]
        assert phys[0] == 0 and phys[1] == 4 and phys[4] == 1
        assert pl.occupancy()["balance_ratio"] == 1.0
        pl.grow()
        # same (shard, local) → new blocks of 8
        assert pl.phys(0) == 0 and pl.phys(1) == 8 and pl.phys(4) == 1
        assert np.array_equal(pl.perm(3), [0, 8, 16])

    def test_shard_placement_full(self):
        pl = ShardPlacement(2, 4)  # block of 2
        pl.assign(0, 0)
        pl.assign(1, 0)
        assert pl.full(0) and not pl.full(1)
        with pytest.raises(IndexError):
            pl.assign(2, 0)

    def test_pool_placement_appends_never_moves(self):
        pl = PoolPlacement(2, 4)  # block of 2 per slab
        ph = []
        for i in range(6):
            phys, appended = pl.assign(i, 0)  # all on shard 0
            ph.append(phys)
        # shard 0's block fills slab 0 (rows 0,1), then slab 1 (4,5)...
        assert ph == [0, 1, 4, 5, 8, 9]
        assert pl.slabs == 3
        # earlier physical ids never moved
        assert [pl.phys(i) for i in range(6)] == ph

    def test_route_stack_partitions_in_order(self):
        rows = np.array([0, 5, 1, 6, 2], np.int64)
        shard = rows // 4
        vals = np.arange(5, dtype=np.float32)
        r_st, (v_st,) = route_stack(2, shard, rows, [vals], 99,
                                    min_width=2)
        assert r_st.shape[0] == 2
        assert list(r_st[0][:3]) == [0, 1, 2]      # order preserved
        assert list(r_st[1][:2]) == [5, 6]
        assert list(v_st[0][:3]) == [0.0, 2.0, 4.0]
        assert (r_st[1][2:] == 99).all()           # sentinel padding


class TestFleetConfig:
    def test_mesh_plus_slab_rejected(self):
        cfg = Config(digest_storage="slab", mesh_enabled=True)
        cfg.apply_defaults()
        with pytest.raises(ValueError, match="slab"):
            cfg.validate()

    def test_mesh_plus_tiered_validates(self):
        cfg = Config(digest_storage="tiered", mesh_enabled=True)
        cfg.apply_defaults()
        cfg.validate()  # the PR 7 mutual-exclusion error is gone

    def test_mesh_on_local_rejected_at_validate(self):
        cfg = Config(mesh_enabled=True, forward_address="127.0.0.1:1")
        cfg.apply_defaults()
        with pytest.raises(ValueError, match="forward_address"):
            cfg.validate()

    def test_mesh_on_local_rejected_by_server(self):
        # directly constructed configs bypass validate(); the server
        # must hard-error, not silently ignore the key (the old
        # behavior hid mis-deployed fleets in a log line)
        from veneur_tpu.server import Server

        cfg = Config(statsd_listen_addresses=[], interval="10s",
                     mesh_enabled=True, forward_address="127.0.0.1:1")
        with pytest.raises(ValueError, match="forward_address"):
            Server(cfg)

    def test_store_rejects_mesh_slab(self, mesh):
        with pytest.raises(ValueError, match="slab"):
            MetricStore(mesh=mesh, digest_storage="slab")

    def test_sharded_needs_mesh(self):
        cfg = Config(digest_storage="sharded")
        cfg.apply_defaults()
        with pytest.raises(ValueError, match="mesh_enabled"):
            cfg.validate()
        with pytest.raises(ValueError, match="mesh"):
            MetricStore(digest_storage="sharded")

    def test_sharded_is_the_mesh_dense_store(self, mesh):
        from veneur_tpu.core.mesh_store import MeshDigestGroup

        cfg = Config(digest_storage="sharded", mesh_enabled=True)
        cfg.apply_defaults()
        cfg.validate()
        for storage in ("sharded", "dense"):
            store = MetricStore(initial_capacity=32, chunk=128, mesh=mesh,
                                digest_storage=storage)
            assert type(store.histograms) is MeshDigestGroup
            assert type(store.timers) is MeshDigestGroup

    def test_global_fanin64_asks_for_sharded_planes(self, tmp_path):
        """The benchmark's deployment names the storage a build from
        before the sharded allocation does not know: such a build
        refuses the file at load (its ``validate`` raises on an unknown
        ``digest_storage``), this one reads it."""
        import json
        import os
        import re

        from veneur_tpu.config import read_config

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "benchmark", "configs",
                               "global-fanin64.json")) as f:
            server = json.load(f)["server"]
        assert server["digest_storage"] == "sharded"
        path = tmp_path / "config.yaml"
        # as the harness writes it: a key and its JSON value a line,
        # the ports filled in
        path.write_text(re.sub(r"\{\w+_port\}", "1", "".join(
            f"{k}: {json.dumps(v)}\n" for k, v in server.items())))
        cfg = read_config(str(path), environ={})
        assert cfg.mesh_enabled and cfg.mesh_hosts == 1
        assert cfg.store_initial_capacity == 1 << 22


class TestStableRowIds:
    """The id contract of the mesh groups: ``_row`` hands out LOGICAL
    rows, which stay valid across a mid-interval grow — the native
    intern memos, lane resolvers and bulk-ingest loops all cache them
    (a physical id would move at every blocked-pad grow)."""

    def test_cached_rows_survive_grow(self, mesh):
        from veneur_tpu.core.mesh_store import MeshDigestGroup

        g = MeshDigestGroup(mesh, 8, 16, 100.0, router=ShardRouter(4))
        r0 = g._row(p.MetricKey(name="cache.h0", type="histogram"), [])
        old_cap = g.capacity
        for i in range(60):  # force at least one grow
            g._row(p.MetricKey(name=f"cache.x{i}", type="histogram"), [])
        assert g.capacity > old_cap
        # stage with the id cached BEFORE the grow: the mass must land
        # on cache.h0, not another series' slot or a dropped hole
        g.sample_many(np.full(5, r0, np.int64),
                      np.full(5, 7.0, np.float32),
                      np.ones(5, np.float32))
        interner, out = g.flush([0.5])
        assert interner.names[r0] == "cache.h0"
        assert out["count"][r0] == 5.0

    def test_inplace_flush_resets_placement(self, mesh):
        """A non-retired in-place flush swaps the interner; the
        placement must reset with it, or the next interval's first
        series inherits the previous series' shard without consulting
        the router (and occupancy reports stale, ever-growing fills)."""
        from veneur_tpu.core.mesh_store import MeshDigestGroup

        router = ShardRouter(4)
        g = MeshDigestGroup(mesh, 16, 32, 100.0, router=router)
        for i in range(10):
            g.sample(p.MetricKey(name=f"gen1.h{i}", type="histogram"),
                     [], 1.0, 1.0)
        g.flush([0.5])
        assert len(g.placement) == 0
        assert sum(g.placement.occupancy()["per_shard"]) == 0
        key = p.MetricKey(name="gen2.h0", type="histogram")
        g._row(key, [])
        want = router.shard_for("gen2.h0", "histogram", "")
        assert g.placement.occupancy()["per_shard"][want] == 1


class TestMeshTieredOracle:
    """mesh+tiered MetricStore == single-device tiered on identical
    input — the composition the old config error forbade."""

    def test_boot_and_flush_matches_oracle(self, mesh):
        mstore = _tiered_store(mesh)
        sstore = _tiered_store()
        from veneur_tpu.fleet.mesh_tiered import MeshTieredDigestGroup
        assert isinstance(mstore.histograms, MeshTieredDigestGroup)
        counts = _fill(mstore, np.random.default_rng(7))
        _fill(sstore, np.random.default_rng(7))
        now = int(time.time())
        mby = {m.name: m.value
               for m in mstore.flush(QS, AGG, is_local=False, now=now)[0]}
        sby = {m.name: m.value
               for m in sstore.flush(QS, AGG, is_local=False, now=now)[0]}
        assert set(mby) == set(sby)
        for name, want in sby.items():
            assert mby[name] == pytest.approx(want, rel=1e-4,
                                              abs=1e-4), name
        # exact count conservation: every ingested histogram sample
        # lands in exactly one row of exactly one shard
        for name, n in counts.items():
            assert mby[f"{name}.count"] == float(n)
        # promotions actually happened (the hot rows crossed the bar)
        assert mstore.histograms.directory.promotions > 0

    def test_shard_occupancy_balanced_and_observable(self, mesh):
        store = _tiered_store(mesh)
        _fill(store, np.random.default_rng(3), n_hist=40)
        snap = fleet_snapshot(store)
        assert snap["axes"] == {"series": 4, "hosts": 2}
        assert "histograms" in snap["groups"]
        occ = snap["shard_occupancy"]
        assert sum(occ) > 0 and min(occ) > 0
        assert snap["balance_ratio"] < 3.0  # hash-placed, not block 0
        # the flush stamps the retired interval's occupancy for the
        # veneur.fleet.shard_occupancy self-metric
        store.flush(QS, AGG, is_local=False, now=int(time.time()))
        assert sum(store.last_fleet_occupancy) == sum(occ)

    def test_debug_vars_mesh_section(self, mesh):
        from veneur_tpu.debug import collect_vars

        class FakeServer:
            pass

        srv = FakeServer()
        srv.store = _tiered_store(mesh)
        _fill(srv.store, np.random.default_rng(1), n_hist=10)
        out = collect_vars(srv)
        assert out["mesh"]["devices"] == 8
        assert out["mesh"]["groups"]["histograms"]["rows"] > 0

    def test_promotion_batch_across_bank_grow_conserves(self, mesh):
        """Regression: one _maybe_promote batch promoting enough series
        to fill a shard's dense-bank block mid-batch triggers the
        bank's blocked-pad _grow, which remaps every existing slot —
        the promotion scatter must use the POST-grow slots (a stale
        pre-grow int scatters onto another shard's block and drops the
        mass while the pool row still clears)."""
        from veneur_tpu.fleet.mesh_tiered import MeshTieredDigestGroup
        from veneur_tpu.fleet import ShardRouter

        g = MeshTieredDigestGroup(
            mesh, ShardRouter(4), slab_rows=64, chunk=2048,
            promote_samples=8, promote_intervals=1,
            dense_capacity=8)  # bank block of 2: grows mid-batch
        rng = np.random.default_rng(9)
        total = 0
        # one giant chunk: every row crosses the bar, ONE drain
        # promotes all 24 at once (~6 per shard >> block 2)
        for i in range(24):
            for v in rng.normal(5 * i, 1, 16):
                g.sample(p.MetricKey(name=f"pb.h{i}", type="histogram"),
                         [], float(v), 1.0)
                total += 1
        interner, out = g.flush([0.5])
        assert g._dense.capacity > 8  # the bank grew
        assert float(out["count"].sum()) == float(total)

    def test_checkpoint_roundtrip_conserves(self, mesh):
        """snapshot_state → restore_state into a FRESH mesh store (the
        persist protocol): counts conserved exactly, percentiles sane."""
        store = _tiered_store(mesh)
        counts = _fill(store, np.random.default_rng(11), n_hist=12)
        groups, _epoch = store.snapshot_state()
        fresh = _tiered_store(mesh)
        fresh.restore_state(groups)
        by = {m.name: m.value
              for m in fresh.flush(QS, AGG, is_local=False,
                                   now=int(time.time()))[0]}
        for name, n in counts.items():
            assert by[f"{name}.count"] == float(n), name


def _rank_error(samples: np.ndarray, value: float, q: float) -> float:
    below = np.sum(samples < value) + 0.5 * np.sum(samples == value)
    return abs(below / len(samples) - q)


@pytest.mark.multidevice
class TestFleetAcceptance:
    """The ISSUE 11 acceptance lane (VENEUR_MULTIDEVICE_TESTS=1, runs
    in the default verify path): a tiered store sharded over the
    series×hosts mesh through ingest → import → flush → checkpoint."""

    def test_ingest_import_flush_checkpoint_roundtrip(self, mesh):
        mstore = _tiered_store(mesh)
        sstore = _tiered_store()
        rng_m = np.random.default_rng(23)
        rng_s = np.random.default_rng(23)
        raw = {}

        def ingest(rng, store, record):
            for i in range(20):
                n = 96 if i % 4 == 0 else 12
                vals = rng.gamma(2.0, 20.0 + i, n)
                if record:
                    raw.setdefault(f"soak.h{i}", []).extend(vals)
                for v in vals:
                    store.process_metric(p.parse_metric(
                        f"soak.h{i}:{v:.4f}|ms".encode()))

        ingest(rng_m, mstore, True)
        ingest(rng_s, sstore, False)

        # import: forwarded packed digests from two locals, through the
        # real wire conversion, into BOTH the mesh store and the oracle
        from veneur_tpu.forward import apply_metric, metric_list_from_state

        rng_l = np.random.default_rng(5)
        for li in range(2):
            lstore = MetricStore(initial_capacity=32, chunk=128)
            for i in range(6):
                vals = rng_l.gamma(2.0, 30.0, 200)
                raw.setdefault(f"soak.imp{i}", []).extend(vals)
                for v in vals:
                    lstore.process_metric(p.parse_metric(
                        f"soak.imp{i}:{v:.4f}|ms".encode()))
            _, fwd, _ = lstore.flush(QS, AGG, is_local=True,
                                     now=int(time.time()),
                                     columnar=True,
                                     digest_format="packed")
            fwd.materialize_digests()
            for m in metric_list_from_state(fwd).metrics:
                apply_metric(mstore, m)
                apply_metric(sstore, m)

        now = int(time.time())
        mby = {m.name: m.value
               for m in mstore.flush(QS, AGG, is_local=False, now=now)[0]}
        sby = {m.name: m.value
               for m in sstore.flush(QS, AGG, is_local=False, now=now)[0]}
        assert set(mby) == set(sby)

        # exact count conservation through ingest + import
        for name, vals in raw.items():
            if name.startswith("soak.h"):
                assert mby[f"{name}.count"] == float(len(vals)), name

        # quantile parity: excess rank error of the mesh store over the
        # single-device tiered oracle, measured against the raw samples
        worst = 0.0
        for name, vals in raw.items():
            vals = np.asarray(vals)
            for q in QS:
                key = f"{name}.{int(q * 100)}percentile"
                excess = (_rank_error(vals, mby[key], q)
                          - _rank_error(vals, sby[key], q))
                worst = max(worst, excess)
        assert worst <= 0.15, worst

        # checkpoint round-trip on the SECOND interval's data: ingest
        # again into the flushed mesh store (fresh generation), snapshot,
        # restore into a brand-new mesh store, flush, counts conserved
        rng2 = np.random.default_rng(99)
        total2 = 0
        for i in range(10):
            n = int(rng2.integers(20, 120))
            total2 += n
            for v in rng2.normal(40, 4, n):
                mstore.process_metric(p.parse_metric(
                    f"ck.h{i}:{v:.4f}|h".encode()))
        groups, _ = mstore.snapshot_state()
        restored = _tiered_store(mesh)
        restored.restore_state(groups)
        rby = {m.name: m.value
               for m in restored.flush(QS, AGG, is_local=False,
                                       now=now + 1)[0]}
        got = sum(v for k, v in rby.items()
                  if k.startswith("ck.") and k.endswith(".count"))
        assert got == float(total2)

    def test_server_boots_mesh_tiered(self):
        """mesh_enabled: true + digest_storage: tiered boots a real
        global Server and emits fleet percentiles — the config
        combination PR 7 hard-errored on."""
        from veneur_tpu.fleet.mesh_tiered import MeshTieredDigestGroup
        from veneur_tpu.server import Server
        from veneur_tpu.sinks import ChannelMetricSink

        cfg = Config(statsd_listen_addresses=[], interval="86400s",
                     percentiles=QS, aggregates=["count"],
                     digest_storage="tiered", mesh_enabled=True,
                     mesh_hosts=2, **TIER_KW)
        sink = ChannelMetricSink()
        server = Server(cfg, metric_sinks=[sink])
        server.start()
        try:
            assert isinstance(server.store.histograms,
                              MeshTieredDigestGroup)
            rng = np.random.default_rng(2)
            for i in range(12):
                for v in rng.normal(25, 2, 64):
                    server.store.process_metric(p.parse_metric(
                        f"boot.h{i}:{v:.4f}|h".encode()))
            server.flush()
            by = {m.name: m.value for m in sink.get_flush()}
            for i in range(12):
                assert by[f"boot.h{i}.count"] == 64.0
                assert by[f"boot.h{i}.50percentile"] == pytest.approx(
                    25, abs=2)
        finally:
            server.shutdown()

    def test_multi_interval_soak_with_demotion(self, mesh):
        """4 intervals: hot rows promote, go cold, and demote back to
        the pool (directory hysteresis across mesh generation twins);
        per-interval counts conserved throughout."""
        store = _tiered_store(mesh)
        rng = np.random.default_rng(41)
        for interval in range(4):
            total = 0
            for i in range(16):
                hot = (i % 4 == 0) and interval < 2  # hot rows go cold
                n = 96 if hot else 8
                total += n
                for v in rng.normal(10 * (i + 1), 2, n):
                    store.process_metric(p.parse_metric(
                        f"soak2.h{i}:{v:.4f}|h".encode()))
            by = {m.name: m.value
                  for m in store.flush(QS, AGG, is_local=False,
                                       now=interval + 1)[0]}
            got = sum(v for k, v in by.items()
                      if k.startswith("soak2.") and k.endswith(".count"))
            assert got == float(total), interval
        d = store.histograms.directory
        assert d.promotions > 0
        assert d.demotions > 0
