"""``chip_smoke.py`` rehearsed on the CPU, and the three small promises
the bring-up made next to it: a placed compile cache, a true ``rung``,
and a non-zero exit when the kernel rung falls back.

With the chip hidden the smoke must exit non-zero with ``"ok": false``
while every comparison with its NumPy reference passes: exactly the
three chip-only checks fail. That is the rehearsal's pass.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP_ONLY = ["platform", "rung", "kernel_compiled"]
REFERENCE_CHECKS = {
    "wide_distinct_series", "wide_count", "wide_min", "wide_max",
    "counters_sum", "gauges_last_write", "dense_one_emission",
    "dense_count", "dense_min", "dense_max", "dense_percentiles",
    "sets_estimate", "datagrams_received",
    "nothing_shed_quarantined_spilled", "overload_level_zero",
    "native_ingest", "no_compile_after_warm_up"}


def _rehearse(tmp_path, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # one device, as on the one-chip machine (conftest's eight virtual
    # devices are for the mesh tests)
    env.pop("XLA_FLAGS", None)
    env.update(extra_env or {})
    run = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), "--series",
         "4096", "--interval", "3s", "--out", str(tmp_path / "out")],
        env=env, capture_output=True, text=True, timeout=300)
    lines = [json.loads(ln) for ln in run.stdout.splitlines() if ln]
    checks = {ln["check"]: ln["ok"] for ln in lines if "check" in ln}
    return run, lines, checks


def test_rehearsal_fails_only_the_chip_checks(tmp_path):
    run, lines, checks = _rehearse(tmp_path)
    assert run.returncode != 0, run.stdout[-2000:]
    last = lines[-1]
    assert last["ok"] is False
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    failed = [name for name, ok in checks.items() if not ok]
    assert failed == CHIP_ONLY, (failed, run.stdout[-3000:])
    # every comparison with the float64 reference ran, and passed
    assert REFERENCE_CHECKS <= {n for n, ok in checks.items() if ok}
    assert checks["parent_never_imported_jax"]
    # what a bring-up reads besides: set-up apart from steady state,
    # live series and flush wall per interval
    warm = next(ln for ln in lines if ln.get("phase") == "warm_up")
    assert warm["compile"]["programs"] > 0 and warm["setup_s"] > 0
    flushes = [ln["flush"] for ln in lines if "flush" in ln]
    assert max(f["live_histogram_series"] for f in flushes) >= 2048
    assert all(f["rungs"] == ["xla"] for f in flushes)


def test_kernel_fallback_is_not_hidden(tmp_path):
    """The Pallas rung made to raise (as TestComputeLadder does): the
    server keeps flushing on the XLA rung and every answer stays right,
    but the smoke says so and exits non-zero."""
    site = tmp_path / "site"
    site.mkdir()
    (site / "sitecustomize.py").write_text(textwrap.dedent("""
        import veneur_tpu.core.store as store_mod

        _orig = store_mod._flush_digests

        def _raiser(*args):
            if args[-1]:
                raise RuntimeError("injected kernel failure")
            return _orig(*args)

        store_mod._flush_digests = _raiser
    """))
    run, lines, checks = _rehearse(
        tmp_path, {"PYTHONPATH": os.pathsep.join([str(site), ROOT])})
    assert run.returncode != 0
    assert lines[-1]["ok"] is False
    assert checks["compute_breaker_closed"] is False
    breaker = next(ln for ln in lines
                   if ln.get("check") == "compute_breaker_closed")
    assert breaker["compute"]["fallback_total"] > 0
    # the ladder did its job: nothing the sink received is wrong
    for name in ("wide_count", "wide_min", "wide_max", "counters_sum",
                 "dense_count", "dense_percentiles", "sets_estimate"):
        assert checks[name], name


class TestCompileCachePlacement:
    @pytest.fixture()
    def restore(self):
        import jax

        was = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", was)

    def test_sets_nothing_when_the_variable_is_set(self, monkeypatch,
                                                   restore):
        import jax

        from veneur_tpu.cli.server import place_compile_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        was = jax.config.jax_compilation_cache_dir
        assert place_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir == was

    def test_names_the_fixed_in_checkout_path(self, monkeypatch, restore):
        import jax

        from veneur_tpu.cli.server import place_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(ROOT, ".jax_cache")
        assert place_compile_cache() == want
        assert place_compile_cache() == want  # no pid, no time, no temp
        assert jax.config.jax_compilation_cache_dir == want


def test_rung_reads_xla_on_the_cpu():
    """``rung`` says ``pallas`` only when the kernel was admitted into
    the program; off the TPU the same dispatch is the XLA program."""
    import time

    from veneur_tpu.core.store import MetricStore
    from veneur_tpu.obs import recorder as obs_rec
    from veneur_tpu.samplers.intermetric import HistogramAggregates
    from veneur_tpu.samplers.parser import parse_metric

    store = MetricStore(initial_capacity=32, chunk=128)
    assert store.compute.snapshot()["last_rung"] is None
    for v in (1.0, 2.0, 3.0):
        store.process_metric(parse_metric(b"lat:%f|h" % v))
    rec = obs_rec.StageRecorder()
    with obs_rec.activate(rec), rec.stage("store.histograms"):
        store.flush([0.5], HistogramAggregates.from_names(["count"]),
                    is_local=False, now=int(time.time()))
    assert store.compute.snapshot()["last_rung"] == "xla"
    assert store.compute.fallback_total == 0
