"""The CPU rehearsal of each cell: the whole run is driven on a small
configuration (3 s interval, 32,768 rows; the global fed by forwards 5 s
on four virtual devices), every comparison with the reference passes,
and the command exits non-zero naming only the chip-only checks; so do
the mixes whose groups are not rectangles (256 sets; Zipf draws over
churning names with four top-k streams, where the one number that the
program misses today is an expected failure of its own). Also: with no
``--rehearse`` a machine without a chip is refused early, with no result
line. About a minute a case.

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


def _run(workload, *more, seconds="9"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", workload, "--seed", "2147483659", "--seconds",
         seconds, *more, *COMMON], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=600)


def _rehearsed(proc):
    assert proc.returncode == 3, proc.stderr[-2000:]
    refused = json.loads(proc.stderr.strip().splitlines()[-1])
    assert refused["other_failed"] == []
    assert sorted(refused["chip_only_failed"]) == [
        "kernel_compiled", "platform", "rung"]
    line = json.loads(proc.stdout.strip().splitlines()[-1])["rehearsed"]
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    return line


@pytest.mark.parametrize("workload,trace", [
    ("standalone-small.wide", "0"),
    ("standalone-small.dense", "1"),
    ("standalone-small.sets", "0"),
])
def test_rehearsal_fails_only_the_chip_only_checks(workload, trace):
    _rehearsed(_run(workload, "--trace", trace, "--rehearse"))


@pytest.fixture(scope="module")
def zipf_churn():
    """One rehearsal of the rounds that are not rectangles, with its
    four top-k streams; the numbers over their limits."""
    proc = _run("standalone-small.zipf-churn", "--trace", "0", "--rehearse",
                "--keep-input")
    assert proc.returncode == 3, proc.stderr[-2000:]
    refused = json.loads(proc.stderr.strip().splitlines()[-1])
    assert refused["other_failed"] == []
    line = json.loads(proc.stdout.strip().splitlines()[-1])["rehearsed"]
    assert line["failed"] == 0 and line["attempted"] == 3 * 9000
    return line, {k for k, n in line["compared"].items()
                  if n["value"] > n["limit"]}


def test_rehearsal_of_rounds_that_are_not_rectangles(zipf_churn):
    line, over = zipf_churn
    # every number of the ragged group, the scalars and the top-k rows
    # that are there is inside its limit ...
    assert over <= {"topk_missed"}
    assert line["compared"]["rank_error_max"]["value"] <= 0.02


def test_replay_reads_the_kept_input_as_the_run_did(zipf_churn):
    line, _over = zipf_churn
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "tools",
                                      "replay.py"),
         "--workload", "standalone-small.zipf-churn", "--input",
         os.path.join(ROOT, "benchmark", "out",
                      "standalone-small.zipf-churn", "input.pickle"),
         *COMMON], cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = [json.loads(ln) for ln in proc.stdout.splitlines()]
    compared = dict(line["compared"])
    compared.pop("run_checks_failed")
    assert out[0]["compared"] == compared
    assert out[1]["lines_sent"] == line["attempted"]
    assert out[4]["measures"]["flush_to_last_body_mean_s"] == \
        line["metrics"]["emit_lag_s"]["value"]


@pytest.mark.xfail(strict=True, reason="the program's fault, shown by PR 39 "
                   "(PERF.md section 7): a top-k candidate that loses its "
                   "slot of the ring in the interval's one drain never comes "
                   "back, so members of frequency 18 and 21 are left out "
                   "beside rows of frequency 1; ops/countmin.py is the "
                   "model_config PR's to mend, and this marker goes with it")
def test_no_heavy_hitter_is_left_out(zipf_churn):
    line, over = zipf_churn
    assert "topk_missed" not in over and line["correct"] is True


def test_rehearsal_of_the_cell_fed_by_forwards(four_virtual_devices):
    proc = _run("global-small.import", "--trace", "1", "--rehearse",
                seconds="15")
    line = _rehearsed(proc)
    report = [json.loads(ln) for ln in proc.stdout.splitlines()[:-1]]
    # every message accounted for, the late forwarder's on time in the
    # next emission: nothing late, and each emission of the window holds
    # a whole round
    compared = [r for r in report if r.get("phase") == "compared"][0]
    assert compared["lines_late"] == 0
    assert compared["lines_held"] >= compared["lines_sent"] \
        == line["attempted"]
    per_round = line["attempted"] // 3
    assert [e["lines"] for e in compared["emissions"]][-4:-1] == \
        [per_round] * 3
    checks = {r["check"]: r for r in report if "check" in r}
    assert checks["forwards_received"]["ok"]
    assert checks["forwards_answered"]["ok"]
    assert "datagrams_received" not in checks
    # the mesh's section only exists while an interval is live: the
    # watcher kept it because the cell's metric asks for it
    assert line["metrics"]["mesh.balance_ratio"]["value"] >= 1.0
    assert checks["platform"]["mesh_devices"] == 4
    assert len(set(checks["platform"]["planes"]["devices"])) == 4


def test_no_chip_no_result():
    proc = _run("standalone-small.dense", "--trace", "0")
    assert proc.returncode == 3
    last = proc.stdout.strip().splitlines()[-1]
    assert "correct" not in json.loads(last)
