"""``emit_lag_s`` and ``freshness_p95_s`` on a synthetic receiver log:
one stalled flush moves both as it should.

    python -m pytest benchmark/tests/test_metrics.py -q
"""

import numpy as np
import pytest

from benchmark.lib import emissions

GROUPS = [{"prefix": "t.h.", "type": "h", "series": 100, "samples": 1}]
PERCENTILES = []


def _run(stall_s):
    """Four flushes 10 s + lag apart; flushes 1..3 are the window, each
    holding the 100 lines sent over the 9.5 s after the tick before."""
    lag = [0.5, 0.5 + stall_s, 0.5, 0.5]
    ticks, t = [], 100.0
    for k in range(4):
        ticks.append(t)
        t += lag[k] + 10.0
    ems = []
    for k in range(4):
        em = emissions.Emission(GROUPS, PERCENTILES)
        em.last_stamp = ticks[k] + lag[k]
        em.bodies = 1
        if k >= 1:
            em.cols[0]["count"][:] = 1.0
        ems.append(em)
    send_log = []
    for k in range(1, 4):
        start = ticks[k - 1] + lag[k - 1]       # the flush before ended
        send_log += [(start + 9.5 * i / 100, start + 9.5 * i / 100, 1)
                     for i in range(100)]
    out = emissions.end_to_end(send_log, ems, ticks, range(1, 4), GROUPS)
    return {"emit_lag_s": out["measures"]["flush_to_last_body_mean_s"],
            "freshness_p95_s": out["measures"]["line_age_p95_s"],
            "lines_sent": out["lines_sent"], "lines_held": out["lines_held"]}


def test_steady_run():
    out = _run(0.0)
    assert out["emit_lag_s"] == pytest.approx(0.5)
    # the line due first waits 10 s + 0.5 s; the 95th percentile is the
    # line due 5 % into the 9.5 s
    assert out["freshness_p95_s"] == pytest.approx(10.5 - 0.05 * 9.5,
                                                   abs=0.1)
    assert out["lines_sent"] == out["lines_held"] == 300


def test_a_stalled_flush_raises_both():
    steady, stalled = _run(0.0), _run(6.0)
    assert stalled["emit_lag_s"] == pytest.approx(0.5 + 6.0 / 3)
    assert stalled["freshness_p95_s"] > steady["freshness_p95_s"] + 5.0


def test_warm_up_lines_carried_into_the_window_are_no_window_lines():
    # what one dense run of twelve did (my chip run, PR 28): 132,424
    # lines of the last warm-up round came with the window's first flush.
    # Counted as window lines they made the first lines of every later
    # round, the oldest at their flush, look a flush younger.
    lag = 0.5
    ticks = [100.0 + 10.5 * k for k in range(4)]
    ems = [emissions.Emission(GROUPS, PERCENTILES) for _ in ticks]
    for k, em in enumerate(ems):
        em.last_stamp = ticks[k] + lag
        em.cols[0]["count"][:] = 1.0 if k else np.nan
    ems[1].cols[0]["count"][:40] = 2.0     # 40 lines carried in
    send_log = [(t + lag + 0.095 * i, t + lag + 0.095 * i, 1)
                for t in ticks[:3] for i in range(100)]
    window = range(1, 4)
    right = emissions.end_to_end(send_log, ems, ticks, window, GROUPS,
                                 carried_in=40)
    assert right["measures"]["line_age_p95_s"] == pytest.approx(
        10.5 - 0.05 * 9.5, abs=0.1)
    wrong = emissions.end_to_end(send_log, ems, ticks, window, GROUPS)
    assert wrong["measures"]["line_age_p95_s"] < \
        right["measures"]["line_age_p95_s"] - 0.5


def test_a_line_no_emission_holds_waits_for_ever():
    lag = 0.5
    ticks = [100.0, 110.5]
    ems = [emissions.Emission(GROUPS, PERCENTILES) for _ in ticks]
    for k, em in enumerate(ems):
        em.last_stamp = ticks[k] + lag
    ems[1].cols[0]["count"][:50] = 1.0           # half the lines lost
    send_log = [(100.5 + 0.09 * i, 100.5 + 0.09 * i, 1) for i in range(100)]
    out = emissions.end_to_end(send_log, ems, ticks, range(1, 2), GROUPS)
    assert out["lines_held"] == 50
    assert np.isinf(out["measures"]["line_age_p95_s"])


def _body(stamp, point_stamp=None):
    text = b'{"series":[]}' if point_stamp is None else (
        b'{"series":[{"metric":"t.h.1.count","points":[[%d,1]],'
        b'"type":"rate"}]}' % point_stamp)
    return (stamp, "/api/v1/series", "", text)


def test_bodies_go_to_the_flush_whose_tick_precedes_them():
    bodies = [_body(99.0), _body(100.2), _body(105.0), _body(110.6),
              _body(130.0)]
    assert emissions.assign_emissions(bodies, [100.0, 110.5]) == \
        [-1, 0, 0, 1, 1]


def test_a_body_posted_again_later_stays_with_its_own_emission():
    # flush 0's second body could not be delivered and comes with flush 1
    bodies = [_body(100.2, 1000), _body(110.6, 1000), _body(110.7, 1010),
              _body(110.9, 1010)]
    owner = emissions.assign_emissions(bodies, [100.0, 110.5, 121.0])
    assert owner == [0, 0, 1, 1]
    ems = emissions.parse(bodies, owner, 3, GROUPS, [], 10.0)
    assert ems[0].last_stamp == 110.6          # late, and it shows
    assert ems[0].dup == 1 and ems[1].dup == 1  # (the same row, here)
