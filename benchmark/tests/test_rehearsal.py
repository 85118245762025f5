"""The CPU rehearsal of each cell: the whole run is driven on a small
configuration (3 s interval, 32,768 rows), every comparison with the
reference passes, and the command exits non-zero naming only the
chip-only checks. Also: with no ``--rehearse`` a machine without a chip
is refused early, with no result line. About a minute a case.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_rehearsal.py -q
"""

import json
import os
import subprocess
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(TESTS))
MANIFEST = os.path.join(TESTS, "rehearsal", "manifest.json")
COMMON = ["--manifest", MANIFEST, "--traffic-dir",
          os.path.join(TESTS, "rehearsal", "traffic")]


def _run(workload, *more):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", workload, "--seed", "2147483659", "--seconds", "9",
         *more, *COMMON], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=600)


@pytest.mark.parametrize("workload,trace", [
    ("standalone-small.wide", "0"),
    ("standalone-small.dense", "1"),
])
def test_rehearsal_fails_only_the_chip_only_checks(workload, trace):
    proc = _run(workload, "--trace", trace, "--rehearse")
    assert proc.returncode == 3, proc.stderr[-2000:]
    refused = json.loads(proc.stderr.strip().splitlines()[-1])
    assert refused["other_failed"] == []
    assert sorted(refused["chip_only_failed"]) == [
        "kernel_compiled", "platform", "rung"]
    line = json.loads(proc.stdout.strip().splitlines()[-1])["rehearsed"]
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0


def test_no_chip_no_result():
    proc = _run("standalone-small.dense", "--trace", "0")
    assert proc.returncode == 3
    last = proc.stdout.strip().splitlines()[-1]
    assert "correct" not in json.loads(last)
