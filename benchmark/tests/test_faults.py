"""Drives the rest of a run with the timed path broken underneath, and
sees ``correct`` come out false: once for an answer altered where it is
produced, once for half of the batch left out, once for a state left
unchanged (a flush that repeats the one before); and, where the cell is
fed by forwards, once for a message that is lost after it was taken,
once for one merged twice, once for one forwarder's centroids merged at
half their weight. The harness's look for a chip is skipped
(``rehearse``); everything else is the run's own. About a minute a case
on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_faults.py -q
"""

import os
import re
import zlib

import pytest

from benchmark import run as bench_run
from benchmark.feeds import forward_grpc
from benchmark.lib import cells
from benchmark.lib.load import Receiver

TESTS = os.path.dirname(os.path.abspath(__file__))
SERIES = re.compile(rb'"metric":"bench\.(?!warm)')


STAMP = re.compile(rb'"points":\[\[(\d+),')


class Tampering(Receiver):
    """Keeps the series bodies of the mix by flush (the points' stamp)
    and stores what ``tamper`` makes of each."""

    def __init__(self):
        super().__init__()
        self.flushes: dict = {}

    def as_stored(self, body, encoding):
        text = zlib.decompress(body) if encoding == "deflate" else body
        if not SERIES.search(text):
            return text, ""
        stamp = STAMP.search(text).group(1)
        mine = self.flushes.setdefault(stamp, [])
        mine.append(text)
        # flushes 1 and 2 are the warm-up rounds; the window starts with 3
        return self.tamper(text, stamp, len(self.flushes),
                           len(mine) - 1), ""


class Altered(Tampering):
    """One digit of one value changes on its way out of the server."""

    done = False

    def tamper(self, text, stamp, flush, ordinal):
        m = re.search(rb'("metric":"bench\.[a-z]\.\d+\.max","points":'
                      rb'\[\[\d+,)(\d)', text)
        if self.done or flush < 3 or not m:
            return text
        self.done = True
        digit = b"%d" % ((int(m.group(2)) + 1) % 10 or 1)
        return text[:m.start(2)] + digit + text[m.end(2):]


class HalfLeftOut(Tampering):
    """Every second series of a body never arrives."""

    def tamper(self, text, stamp, flush, ordinal):
        head, _, rest = text.partition(b"[")
        rows = rest.rsplit(b"]", 1)[0].split(b'},{"metric"')
        if len(rows) < 4:
            return text
        kept = b'},{"metric"'.join(rows[::2])
        if not kept.endswith(b"}"):
            kept += b"}"
        return head + b"[" + kept + b"]}"


class Unchanged(Tampering):
    """From the window's second flush on, the server posts the flush
    before it again: its state did not move."""

    def tamper(self, text, stamp, flush, ordinal):
        if flush < 4:
            return text
        before = list(self.flushes.values())[flush - 2]
        if ordinal >= len(before):
            return text
        return STAMP.sub(b'"points":[[' + stamp + b",", before[ordinal])


def _run(workload, seconds, tmp_path, receiver=None):
    cell = cells.Cell(workload,
                      os.path.join(TESTS, "rehearsal", "manifest.json"),
                      os.path.join(TESTS, "rehearsal", "traffic"))
    rep = bench_run.Report(str(tmp_path / "report.jsonl"), quiet=True)
    try:
        out = bench_run.run_cell(cell, 2147483693, seconds, False, rep,
                                 str(tmp_path), rehearse=True,
                                 receiver=receiver)
    finally:
        rep.close()
    line = out["rehearsed"]
    assert out["other_failed"] == []
    assert line["correct"] is False
    return {k for k, n in line["compared"].items()
            if n["value"] > n["limit"]}


@pytest.mark.parametrize("fault,workload", [
    (Altered, "standalone-small.dense"),
    (HalfLeftOut, "standalone-small.wide"),
    (Unchanged, "standalone-small.dense"),
])
def test_fault_reads_not_correct(fault, workload, tmp_path):
    bad = _run(workload, 9.0, tmp_path, fault())
    assert bad and "run_checks_failed" not in bad


class Faulty(forward_grpc.Feed):
    """The feed with a fault behind it, as if on the wire: ``tamper``
    sees the rounds of the window (the first three calls are the
    warm-up), and the feed's own tallies are of what it hands on, so its
    checks pass and the comparison alone has to see the fault."""

    sends = 0

    def send(self, units, start, span_s):
        self.sends += 1
        if self.sends > 3:
            units = self.tamper(list(units))
        return super().send(units, start, span_s)


class Dropped(Faulty):
    """One message a round never arrives."""

    def tamper(self, units):
        return units[:3] + units[4:]


class Twice(Faulty):
    """One message a round arrives, and is merged, twice."""

    def tamper(self, units):
        return units + [units[3]]


class HalfWeight(Faulty):
    """One forwarder's centroids are merged at half their weight."""

    def tamper(self, units):
        from veneur_tpu.protocol import forward_pb2

        out = []
        for payload, n, forwarder, share, after in units:
            if forwarder == 2:
                forwards = forward_pb2.MetricList.FromString(payload)
                for m in forwards.metrics:
                    for c in m.histogram.t_digest.main_centroids:
                        c.weight = 0.5
                payload = forwards.SerializeToString()
            out.append((payload, n, forwarder, share, after))
        return out


@pytest.mark.parametrize("fault,number", [
    (Dropped, "lines_unaccounted"),
    (Twice, "lines_unaccounted"),
    (HalfWeight, "rank_error_max"),
])
def test_forward_fault_reads_not_correct(fault, number, tmp_path,
                                         monkeypatch, four_virtual_devices):
    monkeypatch.setattr(forward_grpc, "Feed", fault)
    bad = _run("global-small.import", 15.0, tmp_path)
    assert number in bad and "run_checks_failed" not in bad
