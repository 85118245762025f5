"""The per-layer metrics that read the spans and the counter PR 29 put
inside the merger, ``store.dispatch`` and ``post.datadog.serialize``:
each one's file loads and says what ``BENCHMARK.json`` says, and on a
CPU rehearsal of the dense mix (the rehearsal's manifest with these
entries appended, written to a temporary directory) every one of them
comes back as a number. About a minute.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_layer_spans.py -q
"""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import cells

TESTS = os.path.dirname(os.path.abspath(__file__))
NEW = ["merge.busy_s", "merge.remap_s", "merge.lock_wait_s",
       "merge.thread_cpu_s", "flush.drain_s", "flush.fetch_wait_s",
       "sink.encode_s", "sink.deflate_s"]
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
    assert callable(cells.reader(spec["reader"]).read)
    # a program without the span or the counter (the parent commit):
    # nothing to read gives nothing, and does not raise
    empty = {"timeline": [{"stages": []}], "vars_start": {}, "vars_end": {},
             "polls": [], "trace": None, "harness": {}, "notes": []}
    assert cells.reader(spec["reader"]).read(spec["args"], empty) is None


def test_every_one_reads_a_number_on_a_rehearsal(tmp_path):
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
    proc = subprocess.run(
        [sys.executable, os.path.join(cells.BENCH_DIR, "run.py"),
         "--workload", "standalone-small.dense", "--seed", "2147483693",
         "--seconds", "9", "--trace", "1", "--rehearse",
         "--manifest", str(path), "--traffic-dir",
         os.path.join(TESTS, "rehearsal", "traffic")],
        cwd=cells.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 3, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])["rehearsed"]
    assert line["correct"] is True
    got = line["metrics"]
    assert [n for n in NEW if n not in got] == []
    for name in NEW:
        assert isinstance(got[name]["value"], float) and \
            got[name]["value"] >= 0.0, (name, got[name])
    # the parts lie inside their wholes
    assert got["merge.remap_s"]["value"] + got["merge.lock_wait_s"][
        "value"] <= got["merge.busy_s"]["value"]
    assert got["sink.encode_s"]["value"] + got["sink.deflate_s"][
        "value"] <= got["sink.serialize_s"]["value"]
    assert got["flush.drain_s"]["value"] <= got["flush.dispatch_s"]["value"]
    assert got["flush.fetch_wait_s"]["value"] > 0.0
    assert got["sink.deflate_s"]["value"] > 0.0
