"""The per-layer metrics that read the flush wall's leaves (PR 42): the
generation swap's two, the scalar snapshot, a global's row-by-row
globals, the serializer lane's arenas and block, the stream worker's
wait for a block's first body and its POSTs' own time, and
``flush.unstaged_s``, what no leaf of the flusher names. Each file loads
and says what ``BENCHMARK.json`` says, reads nothing from a program
without the stage (the parent commit), and on a CPU rehearsal of the
dense mix and of the global's fan-in every one of them comes back as a
number. A few minutes.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_flush_leaves.py -q
"""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import cells

TESTS = os.path.dirname(os.path.abspath(__file__))
NEW = ["flush.swap_lock_wait_s", "flush.swap_twins_s",
       "flush.scalars_snapshot_s", "flush.globals_s", "serialize.arenas_s",
       "serialize.block_s", "flush.unstaged_s", "sink.first_body_s",
       "sink.post_wire_s"]
GLOBAL_ONLY = {"flush.globals_s": "global-fanin64.import"}
SHARED = ("unit", "better", "source", "layer", "moves")


def _declared() -> dict:
    manifest = cells.read_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    return {m["name"]: m for m in manifest["per_layer"]}


@pytest.mark.parametrize("name", NEW)
def test_file_loads_and_agrees_with_the_manifest(name):
    spec = cells.read_json(os.path.join(
        cells.BENCH_DIR, "layer_metrics", name + ".json"))
    entry = _declared()[name]
    assert spec["name"] == name
    assert {k: spec[k] for k in SHARED} == {k: entry[k] for k in SHARED}
    assert entry["moves"] == "emit_lag_s"
    if name in GLOBAL_ONLY:
        assert entry["workloads"] == [GLOBAL_ONLY[name]]
    else:
        assert "workloads" not in entry
    # a program without the stage or the field (the parent commit):
    # nothing to read gives nothing, and does not raise
    empty = {"timeline": [{"stages": []}], "vars_start": {}, "vars_end": {},
             "polls": [], "trace": None, "harness": {}, "notes": []}
    assert cells.reader(spec["reader"]).read(spec["args"], empty) is None


@pytest.mark.parametrize("workload,devices", [
    ("standalone-small.dense", False), ("global-small.import", True)])
def test_every_one_reads_a_number_on_a_rehearsal(workload, devices,
                                                  tmp_path):
    manifest = cells.read_json(
        os.path.join(TESTS, "rehearsal", "manifest.json"))
    declared = _declared()
    # without the list of the accepted cells they are read in: the
    # rehearsal's cells have names of their own
    manifest["per_layer"] += [
        {k: v for k, v in declared[name].items() if k != "workloads"}
        for name in NEW]
    path = tmp_path / "manifest.json"
    path.write_text(json.dumps(manifest))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if devices:
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    proc = subprocess.run(
        [sys.executable, os.path.join(cells.BENCH_DIR, "run.py"),
         "--workload", workload, "--seed", "2147483693",
         "--seconds", "15", "--trace", "1", "--rehearse",
         "--manifest", str(path), "--traffic-dir",
         os.path.join(TESTS, "rehearsal", "traffic")],
        cwd=cells.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 3, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])["rehearsed"]
    assert line["correct"] is True
    got = line["metrics"]
    assert [n for n in NEW if n not in got] == []
    for name in NEW:
        assert isinstance(got[name]["value"], float) and \
            got[name]["value"] >= 0.0, (name, got[name])
    # the parts lie inside their wholes
    assert got["sink.first_body_s"]["value"] <= \
        got["sink.serialize_s"]["value"]
    assert got["sink.post_wire_s"]["value"] <= got["sink.post_s"]["value"]
    assert got["serialize.arenas_s"]["value"] > 0.0
    # what no leaf names is a small part of the flush wall
    timeline = json.load(open(os.path.join(
        cells.BENCH_DIR, "out", workload, "timeline.json")))
    for entry in timeline:
        assert entry["unstaged_ns"] <= max(
            0.1 * entry["total_duration_ns"], 5_000_000), entry["unstaged_ns"]
