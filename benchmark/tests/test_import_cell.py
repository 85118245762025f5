"""The cell fed by forwards at the deployment's 64 centroids a digest,
rehearsed on the CPU: ``global-small`` on four virtual devices under a
mix of whole messages (``fanin_whole64``) and under the mix of split
ones that showed the import's fault (``fanin_split``). Both read
``rank_error_max`` inside the documented 0.02, nothing compiles inside
the window, and every per-layer metric that ``global-fanin64.import``
reports (those PR 33 added and those without a ``workloads`` list)
comes back as a number. Each new metric's file loads and says what
``BENCHMARK.json`` says. About two minutes a case.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_import_cell.py -q
"""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import cells

TESTS = os.path.dirname(os.path.abspath(__file__))
CELL = "global-fanin64.import"
NEW = ["mesh.balance_ratio", "import.decode_s", "import.lock_wait_s",
       "import.intern_s", "import.stage_s", "import.route_s",
       "import.workers_cpu_s", "import_digests.dispatches",
       "import_digests.guard_drains", "import_digests.device_s",
       "mesh_flush.device_s"]
SHARED = ("unit", "better", "source", "layer", "moves")


def _manifest() -> dict:
    return cells.read_json(os.path.join(cells.ROOT, "BENCHMARK.json"))


@pytest.mark.parametrize("name", NEW)
def test_file_loads_and_agrees_with_the_manifest(name):
    spec = cells.read_json(os.path.join(
        cells.BENCH_DIR, "layer_metrics", name + ".json"))
    entry = {m["name"]: m for m in _manifest()["per_layer"]}[name]
    # the cell it came with; a later cell on a mesh may read it too
    assert entry["workloads"][0] == CELL
    assert spec["name"] == name
    assert {k: spec[k] for k in SHARED} == {k: entry[k] for k in SHARED}
    # a program without the span or the counter (the parent commit):
    # nothing to read gives nothing, and does not raise
    empty = {"timeline": [{"stages": []}], "vars_start": {}, "vars_end": {},
             "polls": [], "trace": None, "harness": {}, "notes": []}
    assert cells.reader(spec["reader"]).read(spec["args"], empty) is None


def test_the_cell_is_what_the_issue_asked_for():
    cell = cells.Cell(CELL)
    assert cell.chips == 4 and cell.interval_s == 10.0
    server = cell.config["server"]
    assert server["store_initial_capacity"] == 1 << 22
    assert server["mesh_enabled"] and server["mesh_hosts"] == 1
    # the storage a build from before the sharded allocation refuses
    assert server["digest_storage"] == "sharded"
    assert "statsd_listen_addresses" not in server
    assert "forward_address" not in server
    assert cell.config["rank_error_limit"] <= 0.04
    mix = cell.traffic
    assert (mix["generator"], mix["feed"]) == ("forwarded_groups",
                                               "forward_grpc")
    assert mix["forwarders"] == 64 and mix["stagger"] is True
    assert mix["late_share"] == 0.125 and mix["late_after_s"] == 0.05
    timers, probes, counters, gauges, marker = mix["groups"]
    assert (timers["fan_in"], timers["samples"]) == (8, 64)
    assert (probes["series"], probes["fan_in"], probes["samples"]) == (
        64, 1, 1)
    assert counters["series"] == gauges["series"] == timers["series"] // 2
    assert marker["series"] == 64 * cell.generator(
        ).messages_per_forwarder(mix)
    # its reported metrics: every one without a list, and the new ones
    names = {m["name"] for m, _spec in cell.per_layer()}
    assert set(NEW) <= names
    assert {"start.compiles_in_window", "flush.drain_s",
            "flush.fetch_wait_s", "sink.deflate_s"} <= names
    assert not names & {"lanes.backlog_max", "merge.busy_s",
                        "flush_digests_roofline"}


@pytest.mark.parametrize("traffic", ["fanin_whole64", "fanin_split"])
def test_rehearsal_at_64_centroids_a_digest(traffic, tmp_path,
                                           four_virtual_devices):
    manifest = cells.read_json(
        os.path.join(TESTS, "rehearsal", "manifest.json"))
    name = "global-small." + traffic
    manifest["workloads"].append({
        "name": name, "config": "global-small", "traffic": traffic,
        "chips": 4, "why": "CPU rehearsal at 64 centroids a digest"})
    # the metrics global-fanin64.import reports, on the rehearsal's cell
    have = {m["name"] for m in manifest["per_layer"]}
    for m in manifest["per_layer"]:
        if m["name"] in NEW:
            m["workloads"] = m["workloads"] + [name]
    manifest["per_layer"] += [
        dict({k: v for k, v in m.items() if k != "workloads"},
             **({"workloads": [name]} if "workloads" in m else {}))
        for m in _manifest()["per_layer"]
        if m["name"] not in have
        and CELL in m.get("workloads", [CELL])]
    path = tmp_path / "manifest.json"
    path.write_text(json.dumps(manifest))
    proc = subprocess.run(
        [sys.executable, os.path.join(cells.BENCH_DIR, "run.py"),
         "--workload", name, "--seed", "2147483693", "--seconds", "15",
         "--trace", "1", "--rehearse", "--manifest", str(path),
         "--traffic-dir", os.path.join(TESTS, "rehearsal", "traffic")],
        cwd=cells.ROOT, env=dict(os.environ), capture_output=True,
        text=True, timeout=900)
    assert proc.returncode == 3, proc.stderr[-2000:]
    refused = json.loads(proc.stderr.strip().splitlines()[-1])
    assert refused["other_failed"] == []
    line = json.loads(proc.stdout.strip().splitlines()[-1])["rehearsed"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["compared"]["rank_error_max"]["value"] <= 0.02
    got = line["metrics"]
    # the device's own seconds come from a chip's trace alone
    on_cpu = [n for n in NEW if not n.endswith(".device_s")]
    assert [n for n in on_cpu if n not in got] == []
    for n in on_cpu:
        assert isinstance(got[n]["value"], float) and \
            got[n]["value"] >= 0.0, (n, got[n])
    assert got["start.compiles_in_window"]["value"] == 0.0
    assert got["import_digests.dispatches"]["value"] >= 1.0
    assert 1.0 <= got["import_digests.guard_drains"]["value"]
    assert got["import.stage_s"]["value"] > 0.0
    assert got["import.workers_cpu_s"]["value"] > 0.0
    for n in ("flush.drain_s", "flush.fetch_wait_s", "sink.encode_s",
              "sink.deflate_s", "flush.dispatch_s", "flush.fetch_s"):
        assert got[n]["value"] > 0.0, n
