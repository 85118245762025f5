"""Drives the rest of a run with the timed path broken underneath, and
sees ``correct`` come out false: once for an answer altered where it is
produced, once for half of the batch left out, once for a state left
unchanged (a flush that repeats the one before); and, where the cell is
fed by forwards, once for a message that is lost after it was taken,
once for one merged twice, once for one forwarder's centroids merged at
half their weight; and, where the mix has groups that are not rectangles
(``standalone-small.zipf-churn``, ``standalone-small.sets``): a top-k
member swapped for another, a top-k count one under the exact, a set
estimate 5 % off, a row for a series the round did not send, a
churned-out name that comes back. The harness's look for a chip is skipped
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
# a body in either of the sink's two hands (lib/emissions.py POINT_STAMP)
SERIES = re.compile(rb'"metric": ?"bench\.(?!warm)')


STAMP = re.compile(rb'"points": ?\[\[(\d+)[.,]')


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


SEED = 2147483693


def _cell(workload):
    return cells.Cell(workload,
                      os.path.join(TESTS, "rehearsal", "manifest.json"),
                      os.path.join(TESTS, "rehearsal", "traffic"))


def _run(workload, seconds, tmp_path, receiver=None):
    cell = _cell(workload)
    rep = bench_run.Report(str(tmp_path / "report.jsonl"), quiet=True)
    try:
        out = bench_run.run_cell(cell, SEED, seconds, False, rep,
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


TOPK_ROW = re.compile(rb'\{"metric": ?"bench\.hot\.\d+\.topk", ?"points": ?'
                      rb'\[\[[\d.]+, ?([\d.e+-]+)\]\], ?"tags": ?\[[^\]]*'
                      rb'"key:(k\d+)"')


class Once(Tampering):
    """One row of the window's first flush, changed by ``change(match)``
    to what it returns in place of the match's group ``self.group``."""

    done = False

    def tamper(self, text, stamp, flush, ordinal):
        m = self.row.search(text)
        if self.done or flush < 3 or not m:
            return text
        self.done = True
        return (text[:m.start(self.group)] + self.change(m)
                + text[m.end(self.group):])


class TopkSwapped(Once):
    """A heavy hitter comes out under another member's key."""

    row, group = TOPK_ROW, 2

    def change(self, m):
        return b"k999999"


class TopkOneUnder(Once):
    """A top-k count comes out one under what the table holds (a rate
    of the rehearsal's 3 s interval on its way out)."""

    row, group = TOPK_ROW, 1

    def change(self, m):
        return repr(float(m.group(1)) - 1.0 / 3.0).encode()


class SetsOff(Tampering):
    """Every set estimate of the window's first flush comes out 5 % over."""

    def tamper(self, text, stamp, flush, ordinal):
        if flush != 3:
            return text
        return re.sub(
            rb'("metric":"bench\.s\.\d+","points":\[\[\d+,)([\d.e+-]+)',
            lambda m: m.group(1) + repr(float(m.group(2)) * 1.05).encode(),
            text)


class RowOfANameNotSent(Tampering):
    """A histogram's rows are posted once more under the name
    ``self.name``, in the flush ``self.flush``."""

    done = False

    def tamper(self, text, stamp, flush, ordinal):
        found = re.search(rb'\{"metric":"bench\.z\.(\d+)\.count"[^}]*\}',
                          text)
        if self.done or flush != self.flush or not found:
            return text
        self.done = True
        row = found.group(0).replace(b"bench.z." + found.group(1),
                                     b"bench.z.%d" % self.name)
        return text.replace(found.group(0), found.group(0) + b"," + row, 1)


def _names(workload):
    """The generator's own account of the ragged group's rounds: the
    names round ``j`` sent, and the names alive in it."""
    from benchmark.kinds import ragged

    cell = _cell(workload)
    group = cell.traffic["groups"][0]
    sent = {j: cell.generator().build(cell.traffic, SEED, j).values[0].series
            for j in (3, 5)}
    return sent, {j: ragged.names_at(group, SEED, j) for j in (3, 5)}


def test_a_row_for_a_series_the_round_did_not_send(tmp_path):
    import numpy as np

    sent, alive = _names("standalone-small.zipf-churn")
    fault = RowOfANameNotSent()
    # alive in the window's first round (the third that is sent), and
    # not among the names it sent
    fault.name = int(np.setdiff1d(alive[3], sent[3])[0])
    fault.flush = 3
    bad = _run("standalone-small.zipf-churn", 9.0, tmp_path, fault)
    assert fault.done and "rows_twice_or_stray" in bad
    assert "run_checks_failed" not in bad


def test_a_churned_out_name_that_comes_back(tmp_path):
    import numpy as np

    sent, alive = _names("standalone-small.zipf-churn")
    fault = RowOfANameNotSent()
    # sent in the window's first round, retired by its third
    fault.name = int(np.setdiff1d(sent[3], alive[5])[0])
    fault.flush = 5
    bad = _run("standalone-small.zipf-churn", 9.0, tmp_path, fault)
    assert fault.done and "rows_twice_or_stray" in bad
    assert "run_checks_failed" not in bad


@pytest.mark.parametrize("fault,workload,number", [
    (TopkSwapped, "standalone-small.zipf-churn", "rows_twice_or_stray"),
    (TopkOneUnder, "standalone-small.zipf-churn", "topk_undercount"),
    (SetsOff, "standalone-small.sets", "set_error_max"),
])
def test_sketch_fault_reads_not_correct(fault, workload, number, tmp_path):
    bad = _run(workload, 9.0, tmp_path, fault())
    assert number in bad and "run_checks_failed" not in bad


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
