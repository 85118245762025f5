"""The global's import path on the mesh: where its state lives, how
many programs it compiles, what it reports of itself, and that a
forward landing while the generation swaps is emitted once.

Runs on the conftest-forced virtual CPU devices (four of them, as one
v5e host has chips: series 4 x hosts 1, the layout ``global-fanin64``
deploys).
"""

import json
import threading
import time
import urllib.request

import grpc
import jax
import numpy as np
import pytest

from veneur_tpu.config import Config
from veneur_tpu.core.mesh_store import (MeshDigestGroup, MeshSetGroup,
                                        _mesh_import_routed)
from veneur_tpu.core.store import MetricKey, MetricStore
from veneur_tpu.fleet import ShardRouter
from veneur_tpu.forward import metric_list_from_state
from veneur_tpu.parallel.mesh import fleet_mesh
from veneur_tpu.samplers import parser as p
from veneur_tpu.samplers.intermetric import HistogramAggregates
from veneur_tpu.server import Server
from veneur_tpu.sinks import ChannelMetricSink

AGG = HistogramAggregates.from_names(["min", "max", "count"])


@pytest.fixture(scope="module")
def mesh():
    return fleet_mesh(jax.devices()[:4], hosts=1)


def _planes(group):
    return {**{f"temp.{k}": v for k, v in group.temp._asdict().items()},
            **{f"digest.{k}": v for k, v in group.digest._asdict().items()},
            "dmin": group.dmin, "dmax": group.dmax}


def _held_in_quarters(name, arr, devices):
    shards = arr.addressable_shards
    assert {s.device for s in shards} == set(devices), name
    assert {s.data.shape[0] for s in shards} == {arr.shape[0] // 4}, name
    assert arr.sharding.spec[0] == "series", name


class TestPlanesAreMadeInShards:
    def test_digest_planes_first_touch_and_growth(self, mesh):
        devices = list(mesh.devices.flat)
        before = {id(a) for a in jax.live_arrays()}
        group = MeshDigestGroup(mesh, 4096, 256, 100.0,
                                router=ShardRouter(4))
        assert "temp" not in group.__dict__  # nothing until first touch
        group._init_device()
        for name, arr in _planes(group).items():
            _held_in_quarters(name, arr, devices)
        # nothing of a plane's whole size was ever made on one device:
        # what the initialiser left alive is the sharded planes alone
        # (the temp's planes are flat: K bins or A anchors a row)
        sizes = {4096 * n for n in (1, group.k, 8)}
        whole = [a for a in jax.live_arrays()
                 if id(a) not in before and a.ndim and a.shape[0] in sizes
                 and len(a.sharding.device_set) == 1]
        assert whole == []
        was = {name: arr.shape[0] for name, arr in _planes(group).items()}
        group._grow()
        assert group.capacity == 8192
        assert (group.temp.num_series, group.temp.capacity) == (8192,
                                                                group.k)
        for name, arr in _planes(group).items():
            assert arr.shape[0] == 2 * was[name], name
            _held_in_quarters(name, arr, devices)

    def test_growth_keeps_every_shard_block_in_place(self, mesh):
        group = MeshDigestGroup(mesh, 16, 64, 100.0,
                                router=ShardRouter(4))
        stamp = jax.device_put(np.arange(16, dtype=np.float32),
                               group.dmin.sharding)
        group.dmin = stamp
        group._grow()
        got = np.asarray(group.dmin).reshape(4, 8)
        np.testing.assert_array_equal(
            got[:, :4], np.arange(16, dtype=np.float32).reshape(4, 4))
        assert np.isinf(got[:, 4:]).all()

    @pytest.mark.parametrize("plane", ["sum_w", "seg_wm"])
    def test_growth_keeps_the_temps_blocks_in_their_order(self, mesh,
                                                          plane):
        """A shard's block of a flat temp plane is a ``TempCentroids``
        plane of its rows (bins row by row, anchors anchor by anchor):
        grown, each block is what ``grow_temp`` makes of it alone."""
        from veneur_tpu.ops import tdigest as td_ops

        group = MeshDigestGroup(mesh, 16, 64, 100.0,
                                router=ShardRouter(4))
        shape = getattr(group.temp, plane).shape
        stamp = np.arange(1, shape[0] + 1, dtype=np.float32)
        group.temp = group.temp._replace(**{plane: jax.device_put(
            stamp, getattr(group.temp, plane).sharding)})
        group._grow()
        got = np.asarray(getattr(group.temp, plane)).reshape(4, -1)
        for block, was in zip(got, stamp.reshape(4, -1)):
            alone = td_ops.grow_temp(
                td_ops.init_temp(4, group.k, 100.0)._replace(
                    **{plane: jax.numpy.asarray(was)}), 4)
            np.testing.assert_array_equal(
                block, np.asarray(getattr(alone, plane)))

    def test_set_registers(self, mesh):
        group = MeshSetGroup(mesh, 64, 64, 10, router=ShardRouter(4))
        _held_in_quarters("registers", group.registers,
                          list(mesh.devices.flat))
        group._grow()
        assert group.registers.shape == (128, 1 << 10)
        _held_in_quarters("registers", group.registers,
                          list(mesh.devices.flat))


def test_one_import_program_whatever_the_fullest_shard(mesh):
    """A chunk's share on its fullest shard is the traffic's to choose;
    the staged import reaches the device at the staging buffers' width
    whatever it is, so nothing compiles once the first chunk has."""
    group = MeshDigestGroup(mesh, 1024, 256, 100.0, router=ShardRouter(4))
    rng = np.random.default_rng(4)
    rows = np.array([group._row(MetricKey(
        name=f"s{i}", type="histogram", joined_tags=""), [])
        for i in range(400)], np.int32)
    shard = group._shard_of_phys(group._to_phys(rows))

    def stage(sel, n_stats):
        group.import_centroids_bulk(
            np.repeat(sel, 4), rng.normal(size=4 * len(sel)),
            np.ones(4 * len(sel)), sel[:n_stats],
            np.zeros(n_stats, np.float32), np.ones(n_stats, np.float32))
        group._drain_imports()

    stage(rows[:8], 8)
    compiled = _mesh_import_routed._cache_size()
    widths = []
    for sel, n_stats in ((rows[shard == 0][:60], 3),   # all on one shard
                         (rows[:64], 64),              # spread over four
                         (rows[shard == 3][:1], 0),    # one lone row
                         (rows[shard != 1][:40], 17)):
        stage(sel, n_stats)
        widths.append(np.bincount(shard[np.isin(rows, sel)],
                                  minlength=4).max())
    assert len(set(widths)) == len(widths)  # the fullest shard differed
    assert _mesh_import_routed._cache_size() == compiled
    assert group.imp_dispatches == 5
    assert group.imp_centroids == 4 * (8 + 60 + 64 + 1 + 40)


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=10) as r:
        return json.loads(r.read().decode())


def _local_message(names, rng, samples=24, counter=None):
    """One forwarder's ``MetricList`` as upstream's local sends it."""
    store = MetricStore(initial_capacity=64, chunk=256)
    for name in names:
        for v in rng.lognormal(3.0, 0.3, samples):
            store.process_metric(p.parse_metric(
                f"{name}:{v:.4f}|h".encode()))
    if counter:
        store.process_metric(p.parse_metric(
            f"{counter[0]}:{counter[1]}|c|#veneurglobalonly".encode()))
    _, fwd, _ = store.flush([0.5], AGG, is_local=True,
                            now=int(time.time()), columnar=True,
                            digest_format="packed")
    fwd.materialize_digests()
    return metric_list_from_state(fwd).SerializeToString()


def _global(**over):
    over.setdefault("grpc_address", "127.0.0.1:0")
    cfg = Config(statsd_listen_addresses=[], interval="86400s",
                 http_address="127.0.0.1:0",
                 percentiles=[0.5], aggregates=["count"],
                 store_initial_capacity=64, store_chunk=256,
                 mesh_enabled=True, mesh_hosts=2, **over)
    sink = ChannelMetricSink()
    server = Server(cfg, metric_sinks=[sink])
    server.start()
    return server, sink


def _sender(port):
    channel = grpc.insecure_channel(f"127.0.0.1:{port}")
    call = channel.unary_unary("/forwardrpc.Forward/SendMetrics",
                               request_serializer=lambda b: b,
                               response_deserializer=lambda b: b)
    return channel, call


def test_spans_and_counters_after_an_import():
    server, _sink = _global()
    channel, call = _sender(server.import_server.port)
    try:
        rng = np.random.default_rng(8)
        names = [f"svc.lat{i}" for i in range(12)]
        for _ in range(3):  # three forwarders report the same series
            call(_local_message(names, rng, counter=("svc.req", 5)),
                 timeout=60)
        port = server.ops_server.port
        seen = _get(port, "/debug/vars")
        assert seen["grpc_import"]["received"] == 3 * 13
        # the clock ticks in hundredths of a second: three small
        # messages may cost the workers less than one
        assert seen["grpc_import"]["workers_cpu_s"] >= 0
        workers = [n for n in seen["obs"]["threads"]
                   if n.startswith("grpc-import")]
        assert workers
        assert seen["grpc_import"]["workers_cpu_s"] == pytest.approx(
            sum(seen["obs"]["threads"][n]["cpu_s"] for n in workers),
            abs=0.05)
        server.flush()
        entry = _get(port, "/debug/flush-timeline?n=1")["intervals"][-1]
        stages = {s["name"]: s for s in entry["stages"]}
        for stage in ("decode", "lock_wait", "intern", "stage", "route",
                      "dispatch"):
            assert stages[f"import.{stage}"]["off_path"], stage
        for stage in ("decode", "intern", "stage", "route", "dispatch"):
            assert stages[f"import.{stage}"]["duration_ns"] > 0, stage
        assert entry["import"] == {"messages": 3}
        counted = entry["import_digests"]
        assert counted["dispatches"] >= 1
        assert counted["centroids"] >= 3 * 12 * 20
        # the second and third forwarder met rows that held mass
        assert counted["guard_drains"] >= 1
        # the mesh's flush has the spans the dense one has
        assert "store.dispatch.histograms.drain" in stages
        assert "store.histograms.fetch.wait" in stages
        # the next interval imported nothing: no stale numbers
        server.flush()
        entry = _get(port, "/debug/flush-timeline?n=1")["intervals"][-1]
        assert "import" not in entry and "import_digests" not in entry
    finally:
        channel.close()
        server.shutdown()


def test_an_import_during_the_swap_is_emitted_once():
    """Forwarders keep sending while the global flushes, on gRPC's
    worker threads against the flush thread: every message's counter
    entry and every digest's weight is in exactly one emission."""
    server, sink = _global()
    channel, call = _sender(server.import_server.port)
    rng = np.random.default_rng(21)
    names = [f"swap.lat{i}" for i in range(6)]
    payloads = [_local_message(names, rng, samples=10,
                               counter=("swap.marker", 1))
                for _ in range(8)]
    sent = []
    stop = threading.Event()

    def forward():
        i = 0
        while not stop.is_set():
            call(payloads[i % len(payloads)], timeout=60)
            sent.append(i)
            i += 1

    thread = threading.Thread(target=forward, daemon=True)
    try:
        call(payloads[0], timeout=120)  # the first import compiles
        sent.append(-1)
        thread.start()
        emissions = []
        for _ in range(4):
            time.sleep(0.15)
            server.flush()
            emissions.append(sink.get_flush())
        stop.set()
        thread.join(timeout=60)
        server.flush()
        emissions.append(sink.get_flush())
    finally:
        stop.set()
        channel.close()
        server.shutdown()
    markers = [sum(m.value for m in e if m.name == "swap.marker")
               for e in emissions]
    assert sum(markers) == len(sent)
    assert sum(1 for m in markers if m) >= 2  # the swap was crossed
    for name in names:
        counts = sum(m.value for e in emissions for m in e
                     if m.name == f"{name}.count")
        # a mixed-scope histogram's count is the local's to emit; the
        # global's percentile rows say which emissions held the series
        assert counts == 0
        held = sum(1 for e in emissions for m in e
                   if m.name == f"{name}.50percentile")
        assert held == sum(1 for m in markers if m)


@pytest.mark.parametrize("grpc_address", ["127.0.0.1:0", ""],
                         ids=["global", "no_import_listener"])
def test_import_programs_are_warmed_where_forwards_are_taken(
        grpc_address, caplog):
    """A mesh that listens for forwards has its import programs ready
    before it says so; one that does not (a sharded local or
    standalone) compiles none of them at start."""
    import logging

    with caplog.at_level(logging.INFO, logger="veneur.server"):
        server, _sink = _global(grpc_address=grpc_address)
        server.shutdown()
    warmed = [r.getMessage() for r in caplog.records
              if "mesh programs ready" in r.getMessage()]
    assert len(warmed) == 1  # every mesh warms its flush and gather
    assert ("imports" in warmed[0]) == bool(grpc_address)
    assert "samples" not in warmed[0]  # no datagram listener either way
