"""The trace reduction: known busy and idle time, program time and
roofline share, on planes built by hand and on ``recorded_planes.json.gz``
(the events ``lib/trace.py read_xplanes`` took from a v5e trace of this
server under the dense mix, my chip run, PR 28; host events other than
``veneur.*`` scopes are not read, which keeps it small).

    python -m pytest benchmark/tests/test_trace.py -q
"""

import gzip
import json
import os

import pytest

from benchmark.lib import cells, roofline, trace
from benchmark.readers import roofline as roofline_reader
from benchmark.readers import trace_program_time

TESTS = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000


def _planes(capture_ns=None):
    ops = [("fusion.1", 0, 10 * MS), ("fusion.2", 5 * MS, 10 * MS),
           ("copy.3", 40 * MS, 20 * MS)]
    modules = [("jit__ingest_samples(1)", 0, 15 * MS),
               ("jit__flush_digests(2)", 40 * MS, 20 * MS)]
    host = [("veneur.flush.digest.dense", 38 * MS, 30 * MS),
            ("veneur.drain.digest.dense", 0, 16 * MS),
            ("other", 0, 100 * MS)]
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": trace.OPS, "events": ops},
            {"name": trace.MODULES, "events": modules}]},
        {"name": "/host:CPU", "lines": [{"name": "thread", "events": host}]},
    ]
    if capture_ns:
        wall = 1_790_792_740_000_000_000     # the stats are wall-clock ns
        planes.append({"name": trace.ENVIRONMENT, "lines": [], "stats": {
            trace.START: wall, trace.STOP: wall + capture_ns}})
    return planes


def test_busy_is_the_union_of_operations():
    out = trace.reduce_planes(_planes())
    dev = out["devices"][0]
    assert dev["busy_s"] == pytest.approx(0.035)       # 0-15 and 40-60 ms
    assert out["window_s"] == pytest.approx(0.068)     # 0 .. 68 ms
    assert dev["ops"][0] == ("copy.3", pytest.approx(0.020))
    assert out["window_from"] == "event_span"


def test_the_window_is_what_the_trace_says_the_capture_lasted():
    out = trace.reduce_planes(_planes(capture_ns=200 * MS))
    assert out["window_s"] == pytest.approx(0.200)
    assert out["window_from"] == "profile_start_stop"
    assert out["devices"][0]["busy_s"] == pytest.approx(0.035)
    assert out["idle_gaps"][0][1] == pytest.approx(0.132)   # 200 - 68 ms


def test_idle_gaps_are_named_by_the_host_scope_over_them():
    out = trace.reduce_planes(_planes())
    (what, seconds), *rest = out["idle_gaps"]
    assert seconds == pytest.approx(0.025)             # 15 .. 40 ms
    assert what == "veneur.flush.digest.dense"         # 2 ms of it, vs 1
    assert rest[0][1] == pytest.approx(0.008)          # 60 .. 68 ms


def test_program_time_and_roofline():
    out = json.loads(json.dumps(trace.reduce_planes(_planes())))
    live = 205280 + 64
    ctx = {"trace": out, "device_kind": "TPU v5 lite", "notes": [],
           "config": {"server": {"store_initial_capacity": 1 << 20,
                                 "percentiles": [0.5, 0.75, 0.99]}},
           "traffic": {"groups": [{"type": "h", "series": 205280},
                                  {"type": "h", "series": 64},
                                  {"type": "c", "series": 10000}]}}
    assert trace_program_time.read(
        {"match": ["flush_digests"], "per": "event"}, ctx) == \
        pytest.approx(0.020)
    assert trace_program_time.read(
        {"match": ["nothing_by_this_name"]}, ctx) is None
    spec = cells.read_json(os.path.join(
        cells.BENCH_DIR, "layer_metrics", "flush_digests_roofline.json"))
    share = roofline_reader.read(spec["args"], ctx)
    work = roofline.digest_flush_bytes(live, 104, 8, 4)
    assert work["total"] == 4 * live * (
        2 * (2 * 104 + 2) + (2 * 104 + 16 + 5) + 2 + 4 + 5) + 16
    assert share == pytest.approx(100 * work["total"] / 819e9 / 0.020)
    assert ctx["notes"][0]["bound"] == "memory"


def test_no_trace_reads_nothing_not_zero():
    ctx = {"trace": None}
    assert trace_program_time.read({"match": ["x"]}, ctx) is None
    assert roofline_reader.read({"match": ["x"]}, ctx) is None


def test_unknown_chip_is_an_error():
    with pytest.raises(KeyError):
        cells.peaks("TPU v9 imaginary")


RECORDED = os.path.join(TESTS, "recorded_planes.json.gz")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in this checkout")
def test_recorded_v5e_trace():
    with gzip.open(RECORDED, "rt") as f:
        recorded = json.load(f)
    out = json.loads(json.dumps(trace.reduce_planes(recorded["planes"])))
    want = recorded["expected"]
    assert len(out["devices"]) == want["devices"]
    assert out["window_s"] == pytest.approx(want["window_s"])
    assert out["window_from"] == want["window_from"]
    assert out["devices"][0]["busy_s"] == pytest.approx(want["busy_s"])
    assert out["idle_gaps"][0][1] == pytest.approx(
        want["window_s"] - 0.226420206)   # the burst's own span
    ctx = {"trace": out, "device_kind": "TPU v5 lite", "notes": [],
           "config": {"server": {"percentiles": [0.5, 0.75, 0.99]}},
           "traffic": {"groups": [{"type": "h", "series": 256},
                                  {"type": "h", "series": 64}]}}
    assert trace_program_time.read(
        {"match": ["flush_digests"], "per": "event"}, ctx) == \
        pytest.approx(want["flush_digests_event_s"])
    assert trace_program_time.read(
        {"match": ["ingest_samples"], "per": "event_mean"}, ctx) == \
        pytest.approx(want["ingest_samples_event_mean_s"])
    spec = cells.read_json(os.path.join(
        cells.BENCH_DIR, "layer_metrics", "flush_digests_roofline.json"))
    assert roofline_reader.read(spec["args"], ctx) == pytest.approx(
        want["roofline_rows_320_percent"])
