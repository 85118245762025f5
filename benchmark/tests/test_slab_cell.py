"""What ``standalone-slab10m.steady`` adds to the benchmark, without a
chip: the configuration is ``standalone-hist1m``'s with the slab store's
keys and one the program accepts; every new per-layer metric has its
file, agrees with its ``BENCHMARK.json`` entry and lists the new cell
alone; the flush's work function counts what it says and its reader
gives nothing (and does not raise) where the program's timeline has no
such counter, as a build from before the slab counters has not. Then
the cell's configuration and mix at a small size (a universe of 100,000
names, 4,096-row slabs, several of them live every interval)
through the harness's whole path on the CPU: every comparison with the
reference passes and only the chip-only checks fail, the new metrics
read; and the control (the reference in bfloat16) fails it where the
stated float32 passes. About two minutes.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_slab_cell.py -q
"""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import cells, slab_roofline
from benchmark.readers import slab_roofline as reader

CELL = "standalone-slab10m.steady"
NEW = ["slab_ingest.device_s", "slab_ingest.rows_drained",
       "slab_flush.device_s", "slab_flush.rows_run", "slab.grows_in_window",
       "slab_flush_roofline"]
SHARED = ("unit", "better", "source", "layer", "moves")
SLAB_KEYS = {"digest_storage": "slab", "digest_dtype": "packed16",
             "slab_rows": 262144, "max_series": 16777216}


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
    lines = sum(int(g.get("lines", 0)) or int(g["series"]) * int(g["samples"])
                for g in cell.traffic["groups"])
    assert lines == 249_032               # 26,214 lines/s over 9.5 s


def test_the_configuration_is_one_the_program_accepts(tmp_path):
    from veneur_tpu.config import read_config

    cell = cells.Cell(CELL)
    path = tmp_path / "config.yaml"
    path.write_text(cell.server_config_text(
        {"statsd_port": 1, "http_port": 2, "receiver_port": 3}))
    config = read_config(str(path))
    assert (config.digest_storage, config.slab_rows, config.max_series) == (
        "slab", 262144, 1 << 24)
    base = cells.Cell("standalone-hist1m.wide").config["server"]
    server = cell.config["server"]
    assert {k: v for k, v in server.items() if k not in SLAB_KEYS} == {
        k: v for k, v in base.items() if k not in SLAB_KEYS}
    assert {k: server[k] for k in SLAB_KEYS} == SLAB_KEYS
    # the freeze at 0.7 occupancy admits the whole universe
    universe = cell.traffic["groups"][0]["universe"]
    assert universe < 0.7 * server["max_series"]


def test_the_work_function_counts_the_live_rows_bytes():
    f32 = slab_roofline.slab_flush_bytes(1000, 104, 8, [0.5, 0.75, 0.99],
                                         "float32")
    b16 = slab_roofline.slab_flush_bytes(1000, 104, 8, [0.5, 0.75, 0.99],
                                         "packed16")
    # a row: the digest (2 x 104 in storage + two bounds) read and
    # written, bins, anchors, five stats and two imported extrema read,
    # four quantiles written
    assert f32["reads"] == 1000 * ((208 * 4 + 8) + (208 + 16 + 5 + 2) * 4)
    assert f32["writes"] == 1000 * ((208 * 4 + 8) + 4 * 4)
    assert f32["total"] - b16["total"] == 2 * 1000 * 208 * 2


def _ctx(timeline, trace=True):
    return {"trace": trace and {"devices": [{"programs": {
        "jit__flush_slab(3)": [0.004, 0.0002],
        "jit__ingest_slab(5)": [0.010]}}]},
        "timeline": timeline, "device_kind": "TPU v5 lite", "notes": [],
        "config": cells.Cell(CELL).config, "traffic": {"groups": []}}


def _args(name):
    return cells.read_json(os.path.join(
        cells.BENCH_DIR, "layer_metrics", name + ".json"))["args"]


def test_the_reader_takes_the_live_rows_from_the_timeline():
    ctx = _ctx([{"slab": {"rows_live": 100000, "rows_run": 100352}}] * 4)
    share = reader.read(_args("slab_flush_roofline"), ctx)
    work = slab_roofline.slab_flush_bytes(100000.0, 104, 8,
                                          [0.5, 0.75, 0.99], "packed16")
    assert share == pytest.approx(100 * (work["total"] / 819e9) / 0.004)
    assert 0.0 < share < 105.0
    assert ctx["notes"][0]["roofline"] == "slab_flush_bytes"


@pytest.mark.parametrize("timeline,trace", [
    ([], True), ([{}], True), ([{"ingest_samples": {}}], True),
    ([{"slab": {"rows_live": 10}}], False)])
def test_a_program_without_the_counters_reads_nothing(timeline, trace):
    assert reader.read(_args("slab_flush_roofline"),
                       _ctx(timeline, trace)) is None


# -- the cell at a small size, through the harness on the CPU --------------

SMALL = {"universe": 100000, "lines": 24000, "scalars": 500,
         "slab_rows": 4096, "interval_s": 5}


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    """A manifest, configuration and mix of the cell's shape at the
    small size (every key and law as committed but the scale)."""
    root = tmp_path_factory.mktemp("slab_small")
    cell = cells.Cell(CELL)
    config = json.loads(json.dumps(cell.config))
    config["interval_s"] = SMALL["interval_s"]
    config["server"].update(interval=f"{SMALL['interval_s']}s",
                            slab_rows=SMALL["slab_rows"],
                            store_initial_capacity=32768)
    traffic = json.loads(json.dumps(cell.traffic))
    traffic["sockets"] = 32
    ragged, *scalars = traffic["groups"]
    ragged.update(universe=SMALL["universe"], lines=SMALL["lines"])
    for g in scalars:
        g["series"] = SMALL["scalars"]
    manifest = json.loads(json.dumps(cell.manifest))
    manifest["configs"] = [dict(c, file=str(root / "config.json"))
                           for c in manifest["configs"]
                           if c["name"] == cell.entry["config"]]
    manifest["workloads"] = [cell.entry]
    (root / "config.json").write_text(json.dumps(config))
    (root / "traffic").mkdir()
    (root / "traffic" / "steady.json").write_text(json.dumps(traffic))
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return ["--manifest", str(root / "BENCHMARK.json"), "--traffic-dir",
            str(root / "traffic")]


def _harness(*argv, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *argv], cwd=cells.ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_the_small_cell_through_the_harness(small):
    proc = _harness(os.path.join(cells.BENCH_DIR, "run.py"), "--workload",
                    CELL, "--seed", "4400000011", "--seconds",
                    str(2 * SMALL["interval_s"]), "--trace", "1",
                    "--rehearse", *small)
    assert proc.returncode == 3, proc.stderr[-3000:]
    refused = json.loads(proc.stderr.strip().splitlines()[-1])
    assert refused["other_failed"] == []
    assert sorted(refused["chip_only_failed"]) == [
        "kernel_compiled", "platform", "rung"]
    line = json.loads(proc.stdout.strip().splitlines()[-1])["rehearsed"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["compared"]["rank_error_max"]["value"] <= 0.02
    metrics = line["metrics"]
    # several slabs live, each generation starting with its retired
    # one's count, so an interval grows one only where its rows pass
    # the last's; the flush runs the live rows' kernel slabs, not the
    # slabs' rows
    assert 0 <= metrics["slab.grows_in_window"]["value"] < 1
    live = SMALL["universe"]
    rows_run = metrics["slab_flush.rows_run"]["value"]
    assert SMALL["slab_rows"] < rows_run < live
    assert metrics["slab_ingest.rows_drained"]["value"] > 0
    assert metrics["start.compiles_in_window"]["value"] == 0
    # the leaves every cell reads are there for a slab group too
    for name in ("flush.drain_s", "flush.fetch_wait_s", "flush.fetch_s",
                 "flush.dispatch_s", "flush.unstaged_s"):
        assert name in metrics, name


def test_the_control_fails_the_small_cell(small):
    proc = _harness(os.path.join(cells.BENCH_DIR, "tools", "control.py"),
                    "--workload", CELL, "--seeds", "4400000023",
                    "--seconds", str(2 * SMALL["interval_s"]), *small)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    out = {r["precision"]: r for r in map(json.loads,
                                           proc.stdout.splitlines())}
    assert out["float32"]["correct"] is True
    assert out["bfloat16"]["correct"] is False
    assert out["bfloat16"]["compared"]["rank_error_max"]["value"] > 0.04
