"""What ``standalone-hot1m.zipf-churn`` adds to the benchmark, without a
chip: the two work functions of ``lib/sketch_roofline.py`` count what
they say, the reader ``readers/sketch_roofline.py`` turns them into a
share on a trace's shape and gives nothing (and does not raise) where
the program's timeline has no such counter, as a build from before
PR 40 has not; every new per-layer metric has its file, agrees with its
``BENCHMARK.json`` entry and lists the new cell alone; the committed
configuration is one the program accepts.

    python -m pytest benchmark/tests/test_hot_cell.py -q
"""

import os

import pytest

from benchmark.lib import cells, sketch_roofline
from benchmark.readers import sketch_roofline as reader

CELL = "standalone-hot1m.zipf-churn"
NEW = ["flush.compute_s", "ingest_samples.rows_drained",
       "topk_update.dispatches", "topk_update.device_s",
       "topk_update_roofline", "ingest_rowdrain_roofline"]
SHARED = ("unit", "better", "source", "layer", "moves")


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
    reported = {m["name"] for m, _spec in cell.per_layer()}
    assert set(NEW) <= reported
    assert {m["name"] for m in cell.end_to_end()} == {
        "emit_lag_s", "freshness_p95_s", "cpu_s_per_mline", "setup_s"}
    lines = sum(int(g.get("lines", 0)) or int(g["series"]) * int(g["samples"])
                for g in cell.traffic["groups"])
    assert lines == 245_760             # 25,869 lines/s over 9.5 s


def test_the_work_functions_count_a_dispatch_s_own_bytes():
    work = sketch_roofline.topk_update_bytes(lines=16384, streams=16,
                                             depth=4, k=32)
    # a line: 20 B staged, 4 columns read and written; a list: 12 B a
    # place read and written, 4 columns a place read
    assert work["reads"] == 16384 * (20 + 16) + 16 * 32 * (12 + 16)
    assert work["writes"] == 16384 * 16 + 16 * 32 * 12
    none = sketch_roofline.sample_rowdrain_bytes(16384, 0, 104, 8)
    assert none["total"] == 16384 * (12 + 72)   # the binning alone
    some = sketch_roofline.sample_rowdrain_bytes(16384, 1000, 104, 8)
    # a drained row: digest and bins (4 x 104) and anchors (2 x 8),
    # read and written; no plane of 2^20 rows in it
    assert some["total"] - none["total"] == 2 * 1000 * (416 + 16) * 4


def _ctx(timeline):
    return {"trace": {"devices": [{"programs": {
        "jit_update(123)": [0.002, 0.001],
        "jit__ingest_samples(7)": [0.020, 0.010, 0.0001]}}]},
        "timeline": timeline, "device_kind": "TPU v5 lite", "notes": [],
        "config": {"server": {"topk_depth": 4, "topk_k": 32}},
        "traffic": {"groups": [
            {"prefix": "z.", "kind": "ragged", "type": "h", "lines": 9},
            {"prefix": "hot.", "kind": "topk", "type": "s", "series": 16,
             "lines": 25280, "members": 10, "zipf_s": 0.99}]}}


def _args(name):
    return cells.read_json(os.path.join(
        cells.BENCH_DIR, "layer_metrics", name + ".json"))["args"]


def test_the_reader_takes_a_dispatch_s_shapes_from_the_timeline():
    flushes = [{"topk": {"dispatches": 2},
                "ingest_samples": {"dispatches": 13, "rows_drained": 52000,
                                   "drain_trips": 60}}] * 4
    ctx = _ctx(flushes)
    share = reader.read(_args("topk_update_roofline"), ctx)
    work = sketch_roofline.topk_update_bytes(12640.0, 16, 4, 32)
    assert share == pytest.approx(100 * (work["total"] / 819e9) / 0.0015)
    share = reader.read(_args("ingest_rowdrain_roofline"), ctx)
    work = sketch_roofline.sample_rowdrain_bytes(16384, 4000.0, 104, 8)
    # the mean of the events at least a tenth of the longest
    assert share == pytest.approx(100 * (work["total"] / 819e9) / 0.015)
    assert 0.0 < share < 105.0
    assert [n["roofline"] for n in ctx["notes"]] == [
        "topk_update_bytes", "sample_rowdrain_bytes"]


@pytest.mark.parametrize("timeline", [[], [{}], [{"topk": {}}]])
def test_a_program_without_the_counters_reads_nothing(timeline):
    for name in ("topk_update_roofline", "ingest_rowdrain_roofline"):
        assert reader.read(_args(name), _ctx(timeline)) is None
    ctx = dict(_ctx([{"topk": {"dispatches": 2}}]), trace=None)
    assert reader.read(_args("topk_update_roofline"), ctx) is None


def test_the_configuration_is_one_the_program_accepts(tmp_path):
    from veneur_tpu.config import read_config

    cell = cells.Cell(CELL)
    path = tmp_path / "config.yaml"
    path.write_text(cell.server_config_text(
        {"statsd_port": 1, "http_port": 2, "receiver_port": 3}))
    config = read_config(str(path))
    assert (config.topk_depth, config.topk_k, config.topk_width) == (
        4, 32, 1 << 20)
    assert config.store_initial_capacity == 1 << 20
    base = cells.Cell("standalone-hist1m.wide").config["server"]
    assert {k: v for k, v in cell.config["server"].items()
            if not k.startswith("topk_")} == base
