"""Hardware smoke subset (@pytest.mark.tpu): the accuracy oracles that
normally run on the virtual CPU mesh, executed on the REAL accelerator.

Run it with ``VENEUR_TPU_TESTS=1`` in a process that may hold the chip.
It checks the kernels alone; the proof that the served path
(``veneur_tpu.cli.server``, UDP or forwards in, sink out) is right on
the chip is a cell of ``benchmark/run.py``, with one process per chip.
Accuracy bounds match the reference's own test envelopes
(t-digest eps=.02 over 100k uniform samples, histo_test.go:11-25; HLL
~2% at precision 14)."""

import numpy as np
import pytest

pytestmark = pytest.mark.tpu


@pytest.fixture(scope="module")
def accel():
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu":
        pytest.skip("no accelerator visible")
    return devs[0]


class TestDigestParityOnHardware:
    def test_quantiles_match_scalar_golden(self, accel):
        from veneur_tpu.ops import tdigest as td_ops
        from veneur_tpu.samplers.scalar import ScalarTDigest

        rng = np.random.default_rng(7)
        vals = rng.uniform(0, 100, 100_000).astype(np.float32)
        golden = ScalarTDigest(compression=100.0)
        for v in vals:
            golden.add(float(v))

        k = td_ops.size_bound(100.0)
        temp = td_ops.init_temp(1, k, 100.0)
        digest = td_ops.init((1,), 100.0, k)
        rows = np.zeros(1 << 14, np.int32)
        wts = np.ones(1 << 14, np.float32)
        import jax.numpy as jnp
        for start in range(0, len(vals), 1 << 14):
            chunk = vals[start:start + (1 << 14)]
            pad = np.zeros(1 << 14, np.float32)
            pad[:len(chunk)] = chunk
            w = wts if len(chunk) == len(wts) else np.pad(
                np.ones(len(chunk), np.float32),
                (0, (1 << 14) - len(chunk)))
            temp = td_ops.ingest_chunk(temp, jnp.asarray(rows),
                                       jnp.asarray(pad), jnp.asarray(w),
                                       100.0)
        qs = jnp.asarray([0.01, 0.25, 0.5, 0.75, 0.99], np.float32)
        inf = jnp.full((1,), jnp.inf, jnp.float32)
        drained, pcts = td_ops.drain_and_quantile(digest, temp, inf, -inf,
                                                  qs, 100.0)
        pcts = np.asarray(pcts)[0]
        for i, q in enumerate([0.01, 0.25, 0.5, 0.75, 0.99]):
            want = golden.quantile(q)
            # eps=.02 rank error over U(0,100) => ~2.0 absolute
            assert abs(pcts[i] - want) <= 2.5, (q, pcts[i], want)

    def test_packed_forward_roundtrip_on_hardware(self, accel):
        from veneur_tpu.core.store import MetricStore, PackedDigestPlanes
        from veneur_tpu.samplers.intermetric import HistogramAggregates
        from veneur_tpu.samplers.parser import MetricKey

        store = MetricStore(initial_capacity=64, chunk=1 << 12,
                            digest_storage="slab", slab_rows=1 << 12)
        g = store.histograms
        rng = np.random.default_rng(3)
        raw = {}
        for i in range(32):
            key = MetricKey(name=f"tpu.h{i}", type="histogram",
                            joined_tags="")
            v = rng.gamma(2.0, 40.0, 256).astype(np.float32)
            raw[key.name] = v
            for start in range(0, 256, 64):
                g.sample_many(
                    np.full(64, g.interner.intern(key, []), np.int32),
                    v[start:start + 64], np.ones(64, np.float32))
        agg = HistogramAggregates.from_names(["min", "max", "count"])
        _, fwd, _ = store.flush([], agg, is_local=True, now=1,
                                forward=True, columnar=True,
                                digest_format="packed")
        col = fwd.histograms_columnar
        assert col is not None and isinstance(col[2], PackedDigestPlanes)
        fwd.materialize_digests()
        assert len(fwd.histograms) == 32
        for name, tags, means, weights, dmin, dmax in fwd.histograms:
            v = raw[name]
            assert weights.sum() == pytest.approx(256.0, rel=0.01)
            assert dmin == pytest.approx(v.min(), rel=1e-5)
            assert dmax == pytest.approx(v.max(), rel=1e-5)
            est_mean = float((means * weights).sum() / weights.sum())
            assert est_mean == pytest.approx(float(v.mean()), rel=0.02)


class TestHLLParityOnHardware:
    def test_estimates_match_scalar_golden(self, accel):
        from veneur_tpu.core.store import SetGroup
        from veneur_tpu.ops import hll as hll_ops
        from veneur_tpu.samplers.parser import MetricKey
        from veneur_tpu.samplers.scalar import ScalarHLL

        group = SetGroup(capacity=8, chunk=1 << 12, precision=14)
        golden = ScalarHLL(precision=14)
        key = MetricKey(name="tpu.s", type="set", joined_tags="")
        for i in range(20_000):
            member = f"user-{i}"
            group.sample(key, [], member)
            golden.insert_hash(hll_ops.hash_member(member.encode("utf-8")))
        interner, estimates, registers = group.flush(want_registers=True)
        # the registers themselves must match the golden model EXACTLY
        # (same hashes, same rho, max-merge) — the strongest hardware
        # correctness oracle
        assert np.array_equal(registers[0],
                              np.frombuffer(bytes(golden.registers),
                                            np.uint8))
        est = float(estimates[0])
        # estimate runs in f32 on device vs f64 in the golden model
        assert est == pytest.approx(golden.estimate(), rel=1e-3)
        assert est == pytest.approx(20_000, rel=0.03)


class TestServerFlushOnHardware:
    def test_udp_to_sink_e2e(self, accel):
        import socket
        import time

        from veneur_tpu.config import Config
        from veneur_tpu.server import Server
        from veneur_tpu.sinks import ChannelMetricSink

        cfg = Config(statsd_listen_addresses=["udp://127.0.0.1:0"],
                     interval="86400s", store_initial_capacity=32,
                     store_chunk=128, percentiles=[0.5],
                     aggregates=["min", "max", "count"])
        sink = ChannelMetricSink()
        server = Server(cfg, metric_sinks=[sink])
        server.start()
        try:
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            for v in range(100):
                s.sendto(f"tpu.lat:{v}|ms".encode(),
                         server.statsd_addrs[0])
            deadline = time.time() + 15
            while server.store.processed < 100 and time.time() < deadline:
                time.sleep(0.02)
            assert server.store.processed == 100
            server.flush()
            by = {m.name: m.value for m in sink.get_flush()}
            assert by["tpu.lat.count"] == 100
            assert by["tpu.lat.50percentile"] == pytest.approx(49.5,
                                                               abs=2.5)
        finally:
            server.shutdown()
