"""A mesh ``Server`` fed datagrams: the native lanes, the merger with
its per-row shard routing, the hosts-sharded sample ingest with its
collectives, the sharded flush and the gather back to interner order,
held to a plain float64 reference and to the dense store on the same
lines. Four virtual devices in every shape a four-chip host can take
(``mesh4-hist1m`` runs the first).
"""

import logging
import socket
import time

import jax
import numpy as np
import pytest

from veneur_tpu.config import Config
from veneur_tpu.server import Server
from veneur_tpu.sinks.channel import ChannelMetricSink

PCTS = [0.5, 0.75, 0.99]
BIG, BIG_SAMPLES, LONE, SCALARS = 2, 2048, 30, 20
# (series axis, hosts axis) of four devices
SHAPES = {"2x2": (2, 2), "4x1": (4, 1), "1x4": (1, 4)}


def _lines(seed: int = 11):
    """The interval's lines in send order, and what they add up to."""
    rng = np.random.default_rng(seed)
    lines, want = [], {"c": {}, "g": {}, "h": {}}
    for i in range(SCALARS):
        want["c"][f"mu.c{i}"] = 0
        for v in rng.integers(1, 1000, 3):
            lines.append(f"mu.c{i}:{v}|c")
            want["c"][f"mu.c{i}"] += int(v)
        for v in rng.integers(0, 1_600_000, 3) / 4.0:
            lines.append(f"mu.g{i}:{v}|g")
            want["g"][f"mu.g{i}"] = float(v)  # the last write
    for i in range(LONE):
        v = float(rng.integers(0, 1_600_000)) / 4.0
        lines.append(f"mu.lone{i}:{v}|h")
        want["h"][f"mu.lone{i}"] = np.array([v])
    big = (np.rint(rng.lognormal(3.0, 1.0, (BIG, BIG_SAMPLES)) * 64)
           / 64.0)
    # interleaved: every chunk carries samples of both series, and a
    # chunk boundary falls inside each
    for j in range(BIG_SAMPLES):
        for i in range(BIG):
            lines.append(f"mu.big{i}:{big[i, j]}|h")
    for i in range(BIG):
        want["h"][f"mu.big{i}"] = big[i]
    return lines, want


def _send_and_merge(srv, lines, per_datagram: int = 40):
    """From one socket, so that SO_REUSEPORT hands every datagram to
    one lane and a gauge's writes keep their order."""
    fleet = srv.ingest_fleet
    want = fleet.totals()["merged"] + len(lines)
    with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as sock:
        for i in range(0, len(lines), per_datagram):
            sock.sendto("\n".join(lines[i:i + per_datagram]).encode(),
                        srv.statsd_addrs[0])
            if i % (50 * per_datagram) == 0:
                time.sleep(0.01)  # loopback's receive buffer is finite
    deadline = time.monotonic() + 60.0
    while fleet.totals()["merged"] < want:
        assert time.monotonic() < deadline, fleet.totals()
        time.sleep(0.01)


def _server(monkeypatch, shape, chunk, capacity=256, **over):
    """A datagram-fed ``Server``; ``shape`` None is the dense store on
    one device, else a mesh of that shape over four of the process's
    devices (the program builds its mesh from every visible device: the
    test steers that, not an option)."""
    if shape is not None:
        from veneur_tpu.parallel.mesh import fleet_mesh

        series, hosts = shape
        monkeypatch.setattr(
            "veneur_tpu.fleet.build_mesh",
            lambda config: fleet_mesh(jax.devices()[:series * hosts],
                                      hosts=hosts))
        over.update(mesh_enabled=True, digest_storage="sharded")
    cfg = Config(statsd_listen_addresses=["udp://127.0.0.1:0"],
                 num_readers=2, interval="86400s",
                 http_address="127.0.0.1:0", percentiles=PCTS,
                 aggregates=["min", "max", "count"],
                 store_initial_capacity=capacity, store_chunk=chunk,
                 **over)
    chan = ChannelMetricSink()
    srv = Server(cfg, metric_sinks=[chan])
    srv.start()
    return srv, chan


def _emitted(monkeypatch, shape, chunk) -> dict:
    """One interval of ``_lines`` through a server: name -> value."""
    srv, chan = _server(monkeypatch, shape, chunk)
    try:
        _send_and_merge(srv, _lines()[0])
        srv.flush()
        out = {}
        for m in chan.get_flush():
            if m.name.startswith("mu."):
                assert m.name not in out, m.name  # no row twice
                out[m.name] = m.value
        if shape is not None:
            axes = dict(srv.store.mesh.shape)
            assert (axes["series"], axes["hosts"]) == shape
        return out
    finally:
        srv.shutdown()


_DENSE: dict = {}


def _dense(monkeypatch, chunk) -> dict:
    if chunk not in _DENSE:
        _DENSE[chunk] = _emitted(monkeypatch, None, chunk)
    return _DENSE[chunk]


def _rank_error(samples: np.ndarray, value: float, q: float) -> float:
    ranked = np.sort(samples)
    lo = np.searchsorted(ranked, value, "left") / len(ranked)
    hi = np.searchsorted(ranked, value, "right") / len(ranked)
    return 0.0 if lo <= q <= hi else min(abs(lo - q), abs(hi - q))


# 8,192: the interval's 4,126 histogram lines are one dispatch (the
# flush's); 1,024: four full chunks and the flush's, and every boundary
# falls inside both big series
@pytest.mark.parametrize("chunk", [8192, 1024],
                         ids=["one_chunk", "boundary_inside_a_series"])
@pytest.mark.parametrize("shape", list(SHAPES.values()),
                         ids=list(SHAPES))
def test_mesh_under_datagrams_against_reference_and_dense(
        shape, chunk, monkeypatch):
    _lines_sent, want = _lines()
    got = _emitted(monkeypatch, shape, chunk)
    dense = _dense(monkeypatch, chunk)
    assert set(got) == set(dense)
    # counters, gauges, count, min, max: exact
    for name, total in want["c"].items():
        assert got[name] == total
    for name, last in want["g"].items():
        assert got[name] == last
    for name, samples in want["h"].items():
        assert got[name + ".count"] == len(samples)
        assert got[name + ".min"] == np.float32(samples.min())
        assert got[name + ".max"] == np.float32(samples.max())
        for q in PCTS:
            value = got[f"{name}.{int(q * 100)}percentile"]
            if len(samples) == 1:
                # a lone sample comes back as itself, to the last bit
                assert value == np.float32(samples[0]), (name, q)
            else:
                assert _rank_error(samples, value, q) <= 0.02, (name, q)
    # the dense store on the same lines: bit for bit wherever the
    # arithmetic is the same. Every device bins the whole chunk against
    # its block, whatever the hosts axis, so one dispatch an interval is
    # the dense store's in every shape. Over several chunks the dense
    # store drains a big series' held rows before it bins more (its row
    # drain), and the mesh does not: there the rank error above holds
    # both
    same = chunk == 8192
    for name, value in got.items():
        if same or ".big" not in name or "percentile" not in name:
            assert value == dense[name], name


def test_a_datagram_fed_mesh_warms_its_programs_before_ready(
        monkeypatch, caplog):
    """The first interval's sample dispatches (the merger's and the
    flush's), its flush and its gather run the variants the start-up
    compiled; the import's program is not among them."""
    from veneur_tpu.core import mesh_store

    programs = {name: getattr(mesh_store, name) for name in (
        "_mesh_init_digests", "_mesh_ingest_samples",
        "_mesh_flush_digests", "_mesh_gather_rows", "_mesh_import_routed")}
    before = {name: fn._cache_size() for name, fn in programs.items()}
    with caplog.at_level(logging.INFO, logger="veneur.server"):
        # a capacity no other test of this process runs a server at
        srv, chan = _server(monkeypatch, (2, 2), 512, capacity=1024)
    try:
        warmed = [r.getMessage() for r in caplog.records
                  if "mesh programs ready" in r.getMessage()]
        assert len(warmed) == 1 and "samples" in warmed[0]
        assert "imports" not in warmed[0]
        ready = {name: fn._cache_size() for name, fn in programs.items()}
        for name in programs:
            grew = ready[name] - before[name]
            assert grew == (0 if name == "_mesh_import_routed" else 1), name
        assert "temp" not in srv.store.histograms.__dict__  # holds nothing
        lines = [f"warm.h{i}:{i}.25|h" for i in range(700)] * 2
        _send_and_merge(srv, lines)
        srv.flush()
        got = {m.name: m.value for m in chan.get_flush()}
        assert got["warm.h699.count"] == 2
        assert got["warm.h699.50percentile"] == 699.25
        assert {name: fn._cache_size()
                for name, fn in programs.items()} == ready
        entry = srv.obs_timeline.entries()[-1]
        assert entry["mesh_ingest"]["dispatches"] == 3  # 1,400 / 512
        assert entry["mesh_ingest"]["samples"] == 1400
    finally:
        srv.shutdown()
