"""Micro-benchmarks in the test tree, mirroring the reference's Go
bench list (BASELINE.md "Benchmark code present"): parser, SSF decode,
scalar t-digest add/quantile, batched kernel ops, import-path merge,
native batch parse, columnar Datadog serialize+deflate, and native
MetricList decode. Like the Go benches they record numbers rather than
assert thresholds (CI hosts vary) — each test prints ns/op and asserts
only that the op ran; `python -m pytest tests/test_microbench.py -s`
shows the table. The system-level numbers are the benchmark's cells
(``benchmark/run.py``), taken on the chip.
"""

import time

import numpy as np

from veneur_tpu.protocol import ssf_pb2, wire
from veneur_tpu.samplers import parser
from veneur_tpu.samplers.scalar import ScalarTDigest


def _bench(label: str, fn, n: int = 2000) -> float:
    fn()  # warm
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    per = (time.perf_counter() - t0) / n
    print(f"{label:40s} {per * 1e9:12.0f} ns/op")
    return per


def test_bench_parse_metric():
    # cf. BenchmarkParseMetric (parser_test.go:691)
    line = b"a.b.c:1.234|ms|@0.5|#tag1:val,tag2:quux"
    per = _bench("parse_metric (dogstatsd)", lambda: parser.parse_metric(line))
    assert per > 0


def test_bench_parse_ssf():
    # cf. BenchmarkParseSSF
    span = ssf_pb2.SSFSpan(trace_id=1, id=2, start_timestamp=1,
                           end_timestamp=2, service="svc", name="op")
    span.metrics.append(ssf_pb2.SSFSample(
        metric=ssf_pb2.SSFSample.HISTOGRAM, name="x", value=3.0,
        sample_rate=1.0))
    raw = span.SerializeToString()
    per = _bench("parse_ssf (protobuf decode)", lambda: wire.parse_ssf(raw))
    assert per > 0


def test_bench_parse_metric_ssf():
    # cf. BenchmarkParseMetricSSF (samplers_test.go:562)
    sample = ssf_pb2.SSFSample(metric=ssf_pb2.SSFSample.COUNTER,
                               name="c", value=1.0, sample_rate=1.0)
    sample.tags["foo"] = "bar"
    per = _bench("parse_metric_ssf",
                 lambda: parser.parse_metric_ssf(sample))
    assert per > 0


def test_bench_scalar_tdigest_add_quantile():
    # cf. BenchmarkAdd / BenchmarkQuantile (tdigest/histo_test.go:109-128)
    rng = np.random.default_rng(0)
    vals = rng.normal(100, 20, 4096)
    td = ScalarTDigest()
    i = [0]

    def add():
        td.add(float(vals[i[0] & 4095]), 1.0)
        i[0] += 1

    per_add = _bench("scalar t-digest add", add, n=20000)
    per_q = _bench("scalar t-digest quantile(0.99)",
                   lambda: td.quantile(0.99), n=5000)
    assert per_add > 0 and per_q > 0


def test_bench_batched_kernel_ops():
    """The batched XLA path those scalar walks are replaced by: per-series
    cost of one full drain+quantile over 4096 series (CPU here; the TPU
    numbers are ``flush_digests.device_s`` in the benchmark's cells)."""
    import jax.numpy as jnp

    from veneur_tpu.ops import tdigest as td_ops

    S, C = 4096, 100.0
    k = td_ops.size_bound(C)
    rng = np.random.default_rng(1)
    rows = jnp.asarray(rng.integers(0, S, 1 << 15).astype(np.int32))
    vals = jnp.asarray(rng.gamma(2.0, 30.0, 1 << 15).astype(np.float32))
    wts = jnp.ones((1 << 15,), jnp.float32)
    qs = jnp.asarray([0.5, 0.99], jnp.float32)

    def step():
        temp = td_ops.init_temp(S, k, C)
        temp = td_ops.ingest_chunk(temp, rows, vals, wts, C)
        d, pcts = td_ops.drain_and_quantile(
            td_ops.init((S,), C, k), temp,
            jnp.full((S,), jnp.inf), jnp.full((S,), -jnp.inf), qs, C)
        pcts.block_until_ready()

    per = _bench("batched drain+quantile 4096 series", step, n=10)
    print(f"{'  -> per series':40s} {per / S * 1e9:12.0f} ns/op")
    assert per > 0


def test_bench_import_merge():
    # cf. BenchmarkImportServerSendMetrics (importsrv/server_test.go:115):
    # the store-side merge of one forwarded digest
    from veneur_tpu.core.store import MetricStore
    from veneur_tpu.samplers.parser import MetricKey

    store = MetricStore(initial_capacity=64, chunk=256)
    means = np.linspace(1, 100, 50)
    weights = np.ones(50)
    i = [0]

    def imp():
        store.import_digest(MetricKey(name=f"m{i[0] % 32}",
                                      type="histogram"),
                            [], means, weights, 1.0, 100.0)
        i[0] += 1

    per = _bench("import_digest (forwarded merge)", imp, n=2000)
    assert per > 0


def test_bench_native_parse_lines():
    # cf. the reference's parser benches, through the C++ batch path
    from veneur_tpu import native

    if not native.available():
        import pytest

        pytest.skip("native library unavailable")
    lines = b"\n".join(
        b"svc.latency:%d|ms|@0.5|#route:r%d,env:prod" % (i % 497, i % 7)
        for i in range(64))

    def parse():
        b = native.parse_lines(lines)
        assert b.count == 64

    per = _bench("native parse_lines (64-metric buffer)", parse, n=5000)
    print(f"{'  -> per metric':40s} {per / 64 * 1e9:12.0f} ns/op")
    assert per > 0


def test_bench_egress_serialize():
    """Datadog series serialization through the native columnar path
    (the Go counterpart is json.Marshal+zlib inside the datadog sink)."""
    from veneur_tpu.core.columnar import build_arenas
    from veneur_tpu.native import egress

    if not egress.available():
        import pytest

        pytest.skip("no native toolchain")
    n = 50_000
    rng = np.random.default_rng(0)
    names = build_arenas([f"svc.lat.{i % 997}" for i in range(n)])
    tags = build_arenas([f"shard:{i % 13},env:prod" for i in range(n)])
    rows = np.arange(n, dtype=np.uint32)
    sfx = np.zeros(n, np.uint8)
    vals = rng.gamma(2, 50, n)
    types = np.zeros(n, np.uint8)

    def run():
        egress.dd_series_bodies(names, tags, [b".max"], rows, sfx, vals,
                                types, 1, 10, "h", compress_level=1)

    per = _bench("dd serialize+deflate (50k metrics)", run, n=5)
    print(f"{'':40s} {n / per / 1e6:12.2f} M metrics/s")
    assert per > 0


def test_bench_mlist_decode():
    """MetricList wire decode, native vs python-protobuf (the import
    server's hot parse; cf. BenchmarkImportServerSendMetrics)."""
    from veneur_tpu.core.store import ForwardableState
    from veneur_tpu.forward.convert import metric_list_from_state
    from veneur_tpu.native import egress
    from veneur_tpu.protocol import forward_pb2

    if not egress.available():
        import pytest

        pytest.skip("no native toolchain")
    rng = np.random.default_rng(0)
    state = ForwardableState()
    for i in range(2000):
        means = np.sort(rng.gamma(2, 30, 48))
        state.histograms.append((f"h{i}", [f"s:{i % 7}"], means,
                                 np.ones(48), float(means[0]),
                                 float(means[-1])))
    data = metric_list_from_state(state).SerializeToString()

    def native():
        egress.decode_metric_list(data).close()

    def python():
        # FromString alone is lazy C parsing; the real Python-path cost
        # is extracting each metric's fields/arrays (what
        # apply_metric_list had to do before the native lane)
        ml = forward_pb2.MetricList.FromString(data)
        for m in ml.metrics:
            m.name
            list(m.tags)
            td = m.histogram.t_digest
            np.asarray(td.packed_means)
            np.asarray(td.packed_weights)

    p_nat = _bench("mlist decode 2k digests (native)", native, n=20)
    p_py = _bench("mlist decode+extract (python pb)", python, n=20)
    print(f"{'native speedup':40s} {p_py / p_nat:12.1f} x")
    assert p_nat > 0
