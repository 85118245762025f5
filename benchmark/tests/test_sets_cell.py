"""What ``standalone-sets1m.pareto`` adds to the benchmark, without a
chip: the configuration is ``standalone-hist1m``'s, key for key, one the
program accepts, and reserved past the dense set group's cap; every new per-layer metric
has its file, agrees with its ``BENCHMARK.json`` entry and lists the new
cell alone; the work functions count what they say and the reader gives
nothing (and does not raise) where the program's timeline has no such
counter, as a build from before the sparse store has not. Then the
cell's configuration and mix at the rehearsal's size
(``tests/rehearsal/configs/sets-small.json``, ``traffic/pareto.json``:
2,048 sets of the cell's law, 6 of them past 128 registers an interval)
through the harness's whole path on the CPU: every comparison with the
reference passes and only the chip-only checks fail, the new counters
read, and the estimate ran on the live bucket, not the capacity. The
sets control fails it, and so does a store that keeps a set's first 128
registers instead of promoting it. About four minutes.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_sets_cell.py -q
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from benchmark.lib import cells, set_roofline
from benchmark.readers import set_roofline as reader

CELL = "standalone-sets1m.pareto"
NEW = ["set_ingest.device_s", "set_flush.device_s", "sets.promotions",
       "set_ingest_roofline", "set_flush_roofline"]
SHARED = ("unit", "better", "source", "layer", "moves")
REHEARSAL = os.path.join(cells.BENCH_DIR, "tests", "rehearsal")


def test_every_new_metric_has_its_file_and_lists_the_cell_alone():
    manifest = cells.read_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        spec = cells.read_json(os.path.join(
            cells.BENCH_DIR, "layer_metrics", name + ".json"))
        for key in SHARED:
            assert spec[key] == entries[name][key], (name, key)
        assert entries[name]["workloads"] == [CELL]
        assert hasattr(cells.reader(spec["reader"]), "read")
    cell = cells.Cell(CELL)
    assert set(NEW) <= {m["name"] for m, _spec in cell.per_layer()}
    assert {m["name"] for m in cell.end_to_end()} == {
        "emit_lag_s", "freshness_p95_s", "cpu_s_per_mline", "setup_s"}
    from benchmark import kinds

    lines = sum(kinds.of(g).lines_a_round(g) for g in cell.traffic["groups"])
    assert lines == 228_743               # 24,078 lines/s over 9.5 s


def test_the_configuration_is_one_the_program_accepts(tmp_path):
    from veneur_tpu.config import read_config

    cell = cells.Cell(CELL)
    path = tmp_path / "config.yaml"
    path.write_text(cell.server_config_text(
        {"statsd_port": 1, "http_port": 2, "receiver_port": 3}))
    config = read_config(str(path))
    # past the dense set group's 4,096-row cap: the sparse store
    assert config.store_initial_capacity == 1 << 20
    base = cells.Cell("standalone-hist1m.wide").config["server"]
    assert cell.config["server"] == base
    # between the largest chip reading and the sets control's
    assert 0.0303 < cell.config["set_error_limit"] < 0.1029


def test_the_work_functions_count_the_filled_registers():
    flush = set_roofline.set_flush_bytes(1000, 5000, 3, 16384)
    # every entry of a sparse set and every register of a dense one
    # read, an estimate a set written
    assert flush["reads"] == 5000 * 4 + 3 * 16384
    assert flush["writes"] == 1000 * 4
    ingest = set_roofline.set_ingest_bytes(16384, 20000, 3000, 2, 128,
                                           16384)
    assert ingest["reads"] == 16384 * 12 + 20000 * 4 + 3000 + 2 * 128 * 4
    assert ingest["writes"] == 20000 * 4 + 3000 + 2 * 16384


def _ctx(timeline, trace=True):
    return {"trace": trace and {"devices": [{"programs": {
        "jit__sparse_set_estimate(2)": [0.0004],
        "jit__sparse_set_insert(7)": [0.002, 0.0021]}}]},
        "timeline": timeline, "device_kind": "TPU v5 lite", "notes": [],
        "config": cells.Cell(CELL).config, "traffic": {"groups": []}}


def _args(name):
    return cells.read_json(os.path.join(
        cells.BENCH_DIR, "layer_metrics", name + ".json"))["args"]


SETS = {"dispatches": 13, "hashes": 200551, "promotions": 108,
        "rows_live": 36864, "rows_dense": 108, "entries_live": 150000,
        "entries_touched": 400000, "dense_writes": 60000, "rows_run": 65536,
        "pool_grows": 0}


def test_the_readers_take_the_work_from_the_timeline():
    ctx = _ctx([{"sets": SETS}] * 4)
    flush = reader.read(_args("set_flush_roofline"), ctx)
    work = set_roofline.set_flush_bytes(36864.0, 150000.0, 108.0, 16384)
    assert flush == pytest.approx(100 * (work["total"] / 819e9) / 0.0004)
    ingest = reader.read(_args("set_ingest_roofline"), ctx)
    work = set_roofline.set_ingest_bytes(200551 / 13, 400000 / 13,
                                         60000 / 13, 108 / 13, 128, 16384)
    assert ingest == pytest.approx(100 * (work["total"] / 819e9) / 0.00205)
    assert 0.0 < flush < 105.0 and 0.0 < ingest < 105.0


@pytest.mark.parametrize("name", ["set_flush_roofline",
                                  "set_ingest_roofline"])
@pytest.mark.parametrize("timeline,trace", [
    ([], True), ([{}], True), ([{"slab": {"rows_live": 10}}], True),
    ([{"sets": SETS}], False)])
def test_a_program_without_the_counters_reads_nothing(name, timeline,
                                                      trace):
    assert reader.read(_args(name), _ctx(timeline, trace)) is None


# -- the cell at the rehearsal's size, through the harness on the CPU ------


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    """A manifest that holds the cell with the rehearsal's configuration,
    its traffic directory, and the per-layer metrics as committed."""
    root = tmp_path_factory.mktemp("sets_small")
    cell = cells.Cell(CELL)
    manifest = json.loads(json.dumps(cell.manifest))
    manifest["configs"] = [dict(c, file=os.path.join(
        REHEARSAL, "configs", "sets-small.json"))
        for c in manifest["configs"] if c["name"] == cell.entry["config"]]
    manifest["workloads"] = [cell.entry]
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return ["--manifest", str(root / "BENCHMARK.json"), "--traffic-dir",
            os.path.join(REHEARSAL, "traffic")]


def _harness(*argv, env=None, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    return subprocess.run([sys.executable, *argv], cwd=cells.ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


def _rehearse(small, seed, trace, env=None):
    proc = _harness(os.path.join(cells.BENCH_DIR, "run.py"), "--workload",
                    CELL, "--seed", str(seed), "--seconds", "10",
                    "--trace", str(trace), "--rehearse", *small, env=env)
    assert proc.returncode == 3, proc.stderr[-3000:]
    refused = json.loads(proc.stderr.strip().splitlines()[-1])
    line = json.loads(proc.stdout.strip().splitlines()[-1])["rehearsed"]
    return refused, line


def test_the_small_cell_through_the_harness(small):
    refused, line = _rehearse(small, 4600000011, 1)
    assert refused["other_failed"] == []
    assert sorted(refused["chip_only_failed"]) == [
        "kernel_compiled", "platform", "rung"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["compared"]["set_rows_wrong"]["value"] == 0
    assert line["compared"]["set_error_max"]["value"] <= 0.0303
    metrics = line["metrics"]
    # the six sets past 128 members, a row of the pool each where its
    # registers pass 128 too
    assert 4 <= metrics["sets.promotions"]["value"] <= 6
    assert metrics["start.compiles_in_window"]["value"] == 0
    for name in ("flush.drain_s", "flush.fetch_wait_s", "flush.fetch_s",
                 "flush.dispatch_s", "flush.unstaged_s"):
        assert name in metrics, name
    timeline = cells.read_json(os.path.join(cells.BENCH_DIR, "out", CELL,
                                            "timeline.json"))
    for entry in timeline:
        sets = entry["sets"]
        # the live bucket, not the 32,768 rows reserved
        assert sets["rows_live"] == sets["rows_run"] == 2048
        assert sets["pool_grows"] == 0
        assert sets["rows_dense"] == sets["promotions"]
        assert sets["dispatches"] >= 2
        assert 0 < sets["entries_live"] < 2048 * 128
    assert sum(e["sets"]["hashes"] for e in timeline) == \
        30723 * len(timeline)
    stages = {s["name"] for s in timeline[0]["stages"]}
    assert {"store.dispatch.sets.drain", "store.dispatch.sets.compute",
            "store.sets.fetch.wait"} <= stages


def test_the_control_fails_the_small_cell(small):
    proc = _harness(os.path.join(cells.BENCH_DIR, "tools", "control.py"),
                    "--workload", CELL, "--seeds", "4600000023",
                    "--seconds", "10", *small)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    out = {r["precision"]: r for r in map(json.loads,
                                           proc.stdout.splitlines())}
    assert out["float32"]["correct"] is True
    assert out["bfloat16"]["correct"] is False
    limit = cells.read_json(os.path.join(
        REHEARSAL, "configs", "sets-small.json"))["set_error_limit"]
    assert out["bfloat16"]["compared"]["set_error_max"]["value"] > limit


TRUNCATING = textwrap.dedent('''
    """Loaded by the server child alone: a sparse store that keeps a
    set's first 128 registers and drops the rest, never promoting."""
    import importlib.abc
    import importlib.util
    import sys

    NAME = "veneur_tpu.ops.hll"


    class _Truncate(importlib.abc.MetaPathFinder):
        def find_spec(self, name, path, target=None):
            if name != NAME:
                return None
            sys.meta_path.remove(self)
            spec = importlib.util.find_spec(name)
            run = spec.loader.exec_module

            def exec_module(module):
                run(module)
                module.outgrows = lambda filled, added, width: filled < 0

            spec.loader.exec_module = exec_module
            return spec


    sys.meta_path.insert(0, _Truncate())
''')


def test_a_store_that_truncates_at_e_fails_the_small_cell(small, tmp_path):
    (tmp_path / "sitecustomize.py").write_text(TRUNCATING)
    path = os.pathsep.join(p for p in (str(tmp_path),
                                       os.environ.get("PYTHONPATH")) if p)
    refused, line = _rehearse(small, 4600000031, 0,
                              env={"PYTHONPATH": path})
    assert line["correct"] is False
    assert line["compared"]["set_error_max"]["value"] > 0.5
    assert line["compared"]["set_rows_wrong"]["value"] == 0
