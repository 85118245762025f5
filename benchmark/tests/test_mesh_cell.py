"""The cell of a datagram-fed mesh, rehearsed on the CPU: ``mesh-small``
(5 s, 8,192 rows, four virtual devices in the default series 2 x hosts
2) under ``wide_mesh`` (``wide``'s shape at a few hundred series, with
eight 2,600-sample series so that an interval is two sample dispatches
and a chunk boundary falls inside a series). The run is ``correct``,
nothing compiles inside the window, and every per-layer metric that
``mesh4-hist1m.wide`` reports comes back as a number (the device's own
seconds excepted: a chip's trace alone has them). A fault behind the
server reads ``correct`` false; the control in bfloat16 fails the mix
and float32 passes it; the committed configuration is one the program
accepts. About two minutes a rehearsal.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_mesh_cell.py -q
"""

import json
import os
import subprocess
import sys

import pytest

from benchmark import run as bench_run
from benchmark.lib import cells, mesh_roofline
from test_faults import Altered, Unchanged

TESTS = os.path.dirname(os.path.abspath(__file__))
CELL = "mesh4-hist1m.wide"
SMALL = "mesh-small.wide"
NEW = ["mesh_ingest.device_s", "mesh_ingest.dispatches",
       "mesh_ingest.collective_bytes", "mesh_ingest.route_s",
       "mesh_ingest.put_s", "mesh_flush.gather_s", "mesh_ingest_roofline"]
FROM_A_CHIP = ("mesh_ingest.device_s", "mesh_ingest_roofline")
SHARED = ("unit", "better", "source", "layer", "moves")


def _manifest() -> dict:
    return cells.read_json(os.path.join(cells.ROOT, "BENCHMARK.json"))


def _small_manifest(tmp_path) -> str:
    """The rehearsal's manifest with ``mesh-small`` and its cell in it,
    reporting what ``mesh4-hist1m.wide`` reports."""
    manifest = cells.read_json(
        os.path.join(TESTS, "rehearsal", "manifest.json"))
    manifest["configs"].append({
        "name": "mesh-small", "source": "rehearsal of mesh4-hist1m",
        "file": "benchmark/tests/rehearsal/configs/mesh-small.json",
        "reduced": [], "why": "CPU rehearsal on four virtual devices"})
    manifest["workloads"].append({
        "name": SMALL, "config": "mesh-small", "traffic": "wide_mesh",
        "chips": 4, "why": "CPU rehearsal of a datagram-fed mesh"})
    have = {m["name"] for m in manifest["per_layer"]}
    manifest["per_layer"] += [
        dict({k: v for k, v in m.items() if k != "workloads"},
             **({"workloads": [SMALL]} if "workloads" in m else {}))
        for m in _manifest()["per_layer"]
        if m["name"] not in have and CELL in m.get("workloads", [CELL])]
    path = tmp_path / "manifest.json"
    path.write_text(json.dumps(manifest))
    return str(path)


@pytest.mark.parametrize("name", NEW)
def test_file_loads_and_agrees_with_the_manifest(name):
    spec = cells.read_json(os.path.join(
        cells.BENCH_DIR, "layer_metrics", name + ".json"))
    entry = {m["name"]: m for m in _manifest()["per_layer"]}[name]
    assert entry["workloads"] == [CELL]
    assert spec["name"] == name
    assert {k: spec[k] for k in SHARED} == {k: entry[k] for k in SHARED}
    # a program without the span or the counter (the parent commit):
    # nothing to read gives nothing, and does not raise
    empty = {"timeline": [{"stages": []}], "vars_start": {}, "vars_end": {},
             "polls": [], "trace": None, "harness": {}, "notes": []}
    assert cells.reader(spec["reader"]).read(spec["args"], empty) is None


def test_the_cell_is_what_the_issue_asked_for(tmp_path):
    cell = cells.Cell(CELL)
    assert cell.chips == 4 and cell.interval_s == 10.0
    server = cell.config["server"]
    standalone = cells.Cell("standalone-hist1m.wide").config["server"]
    added = {"mesh_enabled": True, "digest_storage": "sharded",
             "store_initial_capacity": 1 << 22, "max_series": 1 << 23}
    assert server == dict(standalone, **added)
    assert "mesh_hosts" not in server  # the default: series 2 x hosts 2
    assert "grpc_address" not in server and "forward_address" not in server
    assert cell.config["rank_error_limit"] == 0.04
    assert cell.config["precision"] == "float32"
    entry = {c["name"]: c for c in _manifest()["configs"]}["mesh4-hist1m"]
    assert sorted(entry["reduced"]) == ["live_histogram_series",
                                        "reserved_rows"]
    for key in entry["reduced"]:
        assert {"source", "run", "why"} <= set(cell.config[key])
    assert len(entry["source"]) <= 200 and len(cell.entry["why"]) <= 200
    # the program takes the file as its configuration
    from veneur_tpu import config as vconfig

    path = tmp_path / "config.yaml"
    path.write_text(cell.server_config_text(
        {"statsd_port": 1, "http_port": 2, "receiver_port": 3}))
    loaded = vconfig.read_config(str(path), environ={})
    assert loaded.mesh_enabled and loaded.mesh_hosts == 0
    assert loaded.digest_storage == "sharded"
    # its reported metrics: every one without a list, and the new ones
    names = {m["name"] for m, _spec in cell.per_layer()}
    assert set(NEW) <= names
    assert {"start.compiles_in_window", "flush.drain_s", "sink.deflate_s",
            "generator.worst_lag_ms"} <= names


def test_the_roofline_counts_one_devices_part_of_a_chunk():
    work = mesh_roofline.mesh_sample_ingest_bytes(16384, 2, 2)
    # half the samples read; half the chunk's entries read and written
    assert work["reads"] == 8192 * 12 + 8192 * 36
    assert work["writes"] == 8192 * 36
    assert work["total"] == 688_128  # no plane of 2^21 rows in it
    whole = mesh_roofline.mesh_sample_ingest_bytes(16384, 1, 1)
    assert whole["total"] == 16384 * (12 + 72)
    # a share of it on a recorded trace's shape
    from benchmark.readers import mesh_roofline as reader

    ctx = {"trace": {"devices": [
        {"programs": {"jit__mesh_ingest_samples": [0.067, 0.060]}},
        {"programs": {"jit__mesh_ingest_samples": [0.050]}}]},
        "device_kind": "TPU v5 lite", "notes": []}
    share = reader.read({"match": ["mesh_ingest_samples"],
                         "work": "mesh_sample_ingest_bytes",
                         "shapes": {"samples": 16384, "series_axis": 2,
                                    "hosts_axis": 2}}, ctx)
    assert share == pytest.approx(100 * (688_128 / 819e9) / 0.067)
    assert 0.0 < share < 105.0


def test_rehearsal_reports_every_metric_of_the_cell(tmp_path,
                                                    four_virtual_devices):
    proc = subprocess.run(
        [sys.executable, os.path.join(cells.BENCH_DIR, "run.py"),
         "--workload", SMALL, "--seed", "2147483693", "--seconds", "15",
         "--trace", "1", "--rehearse", "--manifest",
         _small_manifest(tmp_path), "--traffic-dir",
         os.path.join(TESTS, "rehearsal", "traffic")],
        cwd=cells.ROOT, env=dict(os.environ), capture_output=True,
        text=True, timeout=900)
    assert proc.returncode == 3, proc.stderr[-2000:]
    refused = json.loads(proc.stderr.strip().splitlines()[-1])
    assert refused["other_failed"] == []
    line = json.loads(proc.stdout.strip().splitlines()[-1])["rehearsed"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["compared"]["rank_error_max"]["value"] <= 0.02
    got = line["metrics"]
    on_cpu = [n for n in NEW if n not in FROM_A_CHIP]
    assert [n for n in on_cpu if n not in got] == []
    assert got["start.compiles_in_window"]["value"] == 0.0
    # 21,400 histogram lines a round: one full chunk and the flush's
    assert got["mesh_ingest.dispatches"]["value"] == 2.0
    # 2 dispatches x 4 B x (4,096 rows a device x 229 + the guard's 2)
    assert got["mesh_ingest.collective_bytes"]["value"] == \
        2 * 4 * (4096 * 229 + 2)
    for n in ("mesh_ingest.route_s", "mesh_ingest.put_s",
              "mesh_flush.gather_s", "flush.drain_s", "flush.dispatch_s"):
        assert 0.0 < got[n]["value"] < 5.0, n  # seconds an interval
    report = [json.loads(ln) for ln in proc.stdout.splitlines()[:-1]]
    platform = [r for r in report if r.get("check") == "platform"][0]
    assert platform["mesh_axes"] == {"series": 2, "hosts": 2}
    assert len(set(platform["planes"]["devices"])) == 4


def _run_with(receiver, tmp_path):
    cell = cells.Cell(SMALL, _small_manifest(tmp_path),
                      os.path.join(TESTS, "rehearsal", "traffic"))
    rep = bench_run.Report(str(tmp_path / "report.jsonl"), quiet=True)
    try:
        out = bench_run.run_cell(cell, 2147483693, 10.0, False, rep,
                                 str(tmp_path), rehearse=True,
                                 receiver=receiver)
    finally:
        rep.close()
    assert out["other_failed"] == []
    return out["rehearsed"]


@pytest.mark.parametrize("fault", [Altered, Unchanged])
def test_fault_behind_the_mesh_reads_not_correct(fault, tmp_path,
                                                 four_virtual_devices):
    line = _run_with(fault(), tmp_path)
    assert line["correct"] is False
    bad = {k for k, n in line["compared"].items() if n["value"] > n["limit"]}
    assert bad and "run_checks_failed" not in bad


def test_the_control_fails_the_mix_and_float32_passes_it(tmp_path):
    """``tools/control.py`` on the rehearsal's cell, as it is run on the
    deployment's: the reference in bfloat16 in the program's place is
    not correct, at float32 it is."""
    proc = subprocess.run(
        [sys.executable, os.path.join(cells.BENCH_DIR, "tools",
                                      "control.py"),
         "--workload", SMALL, "--seeds", "2147483693", "3000000019",
         "--seconds", "10", "--manifest", _small_manifest(tmp_path),
         "--traffic-dir", os.path.join(TESTS, "rehearsal", "traffic")],
        cwd=cells.ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    assert [(ln["precision"], ln["correct"]) for ln in lines] == [
        ("float32", True), ("bfloat16", False)] * 2
    for ln in lines:
        if ln["precision"] == "bfloat16":
            failed = {k for k, n in ln["compared"].items()
                      if n["value"] > n["limit"]}
            assert {"hist_rows_wrong", "scalar_rows_wrong"} <= failed
