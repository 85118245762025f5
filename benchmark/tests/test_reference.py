"""The comparison that decides ``correct``: the reference in the
program's place passes at float64 and fails in bfloat16 (the control),
and every fault a cell can have from outside is flagged.

    python -m pytest benchmark/tests/test_reference.py -q
"""

import copy

import numpy as np
import pytest

from benchmark.generators import forwarded_groups, series_groups
from benchmark.lib import emissions, reference

PERCENTILES = [0.5, 0.75, 0.99]
LIMITS = {"rank_error_limit": 0.02}
MIXES = {
    "wide": {"datagram_bytes": 1400, "groups": [
        {"prefix": "t.h.", "type": "h", "series": 3000, "samples": 1,
         "values": {"dist": "quarters", "high": 400000}},
        {"prefix": "t.c.", "type": "c", "series": 200, "samples": 1,
         "values": {"dist": "integers", "low": 1, "high": 1000}},
        {"prefix": "t.g.", "type": "g", "series": 200, "samples": 1,
         "values": {"dist": "quarters", "high": 400000}}]},
    "dense": {"datagram_bytes": 1400, "groups": [
        {"prefix": "t.d.", "type": "h", "series": 16, "samples": 2048,
         "values": {"dist": "lognormal_64ths", "mu": 3.0, "sigma": 1.0,
                    "scale_low": 0.5, "scale_high": 20.0}},
        {"prefix": "t.p.", "type": "h", "series": 8, "samples": 1,
         "values": {"dist": "quarters", "high": 400000}},
        {"prefix": "t.c.", "type": "c", "series": 200, "samples": 1,
         "values": {"dist": "integers", "low": 1, "high": 1000}}]},
}
WINDOW = range(2, 5)


def _case(mix, seed, precision):
    params = MIXES[mix]
    rounds = {k: series_groups.build(params, seed, k - 1) for k in WINDOW}
    ems = reference.synthesize(rounds, WINDOW, 6, params["groups"],
                               PERCENTILES, precision)
    return params["groups"], rounds, ems


def _numbers(groups, rounds, ems):
    return reference.compare(ems, rounds, WINDOW, groups, PERCENTILES,
                             LIMITS)["numbers"]


def _correct(numbers):
    return all(n["value"] <= n["limit"] for n in numbers.values())


@pytest.mark.parametrize("mix", sorted(MIXES))
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_reference_in_its_own_place_is_correct(mix, seed):
    assert _correct(_numbers(*_case(mix, seed, "float64")))
    assert _correct(_numbers(*_case(mix, seed, "float32")))


@pytest.mark.parametrize("mix", sorted(MIXES))
@pytest.mark.parametrize("seed", [1, 8, 2**31 + 6])
def test_control_in_bfloat16_is_not_correct(mix, seed):
    numbers = _numbers(*_case(mix, seed, "bfloat16"))
    assert not _correct(numbers)
    assert numbers["hist_rows_wrong"]["value"] > 0
    # a lone sample comes back rounded: the probe series of the dense mix
    # give the rank error an upper reading that the hot series do not
    assert numbers["rank_error_max"]["value"] > 0.2


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_an_altered_answer_is_flagged(mix):
    groups, rounds, ems = _case(mix, 3, "float64")
    col = ems[3].cols[0][emissions.percentile_suffix(0.5)]
    col[5] = col[5] * 1.5 + 1.0
    numbers = _numbers(groups, rounds, ems)
    assert numbers["rank_error_max"]["value"] > 0.02
    ems[3].cols[0][emissions.percentile_suffix(0.5)][5] = np.nan
    assert not _correct(_numbers(groups, rounds, ems))


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_half_the_batch_left_out_is_flagged(mix):
    groups, rounds, ems = _case(mix, 4, "float64")
    for cols in ems[3].cols:
        for col in cols.values():
            col[::2] = np.nan
    numbers = _numbers(groups, rounds, ems)
    assert numbers["hist_rows_wrong"]["value"] > 0
    assert numbers["scalar_rows_wrong"]["value"] > 0


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_a_state_left_unchanged_is_flagged(mix):
    groups, rounds, ems = _case(mix, 5, "float64")
    ems[4] = copy.deepcopy(ems[3])      # the flush repeats the last one
    assert not _correct(_numbers(groups, rounds, ems))


def test_a_late_line_is_late_not_wrong():
    groups, rounds, ems = _case("wide", 6, "float64")
    # series 9's line of flush 3 comes with flush 4 instead
    h3, h4 = ems[3].cols[0], ems[4].cols[0]
    v3 = rounds[3].values[0][9, 0]
    v4 = rounds[4].values[0][9, 0]
    for col in h3.values():
        col[9] = np.nan
    h4["count"][9] = 2
    h4["min"][9], h4["max"][9] = min(v3, v4), max(v3, v4)
    out = reference.compare(ems, rounds, WINDOW, groups, PERCENTILES, LIMITS)
    assert _correct(out["numbers"])
    assert out["lines_late"] == 2


def test_a_warm_up_line_that_slips_into_the_window_is_late_not_wrong():
    # what one wide run of twelve did at 25,600 lines/s (my chip run, PR
    # 28): lines of the last warm-up round missed its tick
    params = MIXES["wide"]
    groups = params["groups"]
    span = range(WINDOW.start - 1, WINDOW.stop)
    rounds = {k: series_groups.build(params, 6, k - 1) for k in span}
    ems = reference.synthesize(rounds, span, 6, groups, PERCENTILES,
                               "float64")
    warm, first = ems[span.start], ems[WINDOW.start]
    v0 = rounds[span.start].values[0][9, 0]
    v1 = rounds[WINDOW.start].values[0][9, 0]
    for col in warm.cols[0].values():
        col[9] = np.nan
    first.cols[0]["count"][9] = 2
    first.cols[0]["min"][9] = min(v0, v1)
    first.cols[0]["max"][9] = max(v0, v1)
    # and a counter's two lines come as one row with their sum
    c0 = rounds[span.start].values[1][3, 0]
    warm.cols[1]["value"][3] = np.nan
    first.cols[1]["value"][3] += c0
    out = reference.compare(ems, rounds, WINDOW, groups, PERCENTILES, LIMITS)
    assert _correct(out["numbers"])
    assert out["numbers"]["lines_unaccounted"]["value"] == 0
    assert out["lines_late"] == 2
    # held to the window alone, the same emissions hold a line too many
    alone = {k: rounds[k] for k in WINDOW}
    out = reference.compare(ems, alone, WINDOW, groups, PERCENTILES, LIMITS)
    assert out["numbers"]["lines_unaccounted"]["value"] > 0
    assert not _correct(out["numbers"])


def test_a_line_that_never_comes_is_unaccounted():
    groups, rounds, ems = _case("wide", 6, "float64")
    for col in ems[3].cols[0].values():
        col[9] = np.nan
    ems[4].cols[1]["value"][3] = np.nan
    numbers = _numbers(groups, rounds, ems)
    assert numbers["lines_unaccounted"]["value"] == 1 + len(WINDOW)
    assert not _correct(numbers)


def test_rank_error_measure():
    ordered = np.sort(np.arange(100.0))[None, :]
    assert reference.rank_error(ordered, np.array([49.0]), 0.5)[0] == 0.0
    assert reference.rank_error(ordered, np.array([59.5]), 0.5)[0] == \
        pytest.approx(0.1)
    assert reference.rank_error(ordered, np.array([np.nan]), 0.5)[0] == 1.0


# -- forwarded groups: a global's answer is a merge over forwarders -------

FORWARDED = {
    "guard_s": 0.5, "forwarders": 8, "stagger": True, "late_share": 0.125,
    "late_after_s": 0.05, "message_metrics": 100, "compression": 100,
    "groups": [
        {"prefix": "t.t.", "type": "h", "series": 120, "fan_in": 4,
         "samples": 64,
         "values": {"dist": "lognormal_64ths", "mu": 3.0, "sigma": 0.25,
                    "scale_low": 0.5, "scale_high": 20.0}},
        {"prefix": "t.p.", "type": "h", "series": 16, "fan_in": 1,
         "samples": 1, "values": {"dist": "quarters", "high": 400000}},
        {"prefix": "t.c.", "type": "c", "series": 120, "fan_in": 4,
         "values": {"dist": "integers", "low": 1, "high": 1000}},
        {"prefix": "t.g.", "type": "g", "series": 120, "fan_in": 4,
         "values": {"dist": "quarters", "high": 400000}},
        {"prefix": "t.m.", "type": "c", "marker": True, "series": 16}]}
MARKER = 4


def _forwarded_case(seed, precision, moved=None):
    groups = FORWARDED["groups"]
    rounds = {k: forwarded_groups.build(FORWARDED, seed, k - 1)
              for k in WINDOW}
    ems = reference.synthesize(rounds, WINDOW, 6, groups, PERCENTILES,
                               precision, moved)
    out = reference.compare(ems, rounds, WINDOW, groups, PERCENTILES, LIMITS)
    return rounds, ems, out


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_forwarded_reference_in_its_own_place_is_correct(seed):
    for precision in ("float64", "float32"):
        rounds, ems, out = _forwarded_case(seed, precision)
        assert _correct(out["numbers"]), out["numbers"]
        assert out["lines_late"] == 0
    # one forwarder of the eight is due after the tick: its two messages
    # stand in the next emission, on time there
    late = rounds[WINDOW[0]].late
    assert late.sum() == 2
    first = ems[WINDOW[0]].cols[MARKER]["value"]
    after = ems[WINDOW[-1] + 1].cols[MARKER]["value"]
    assert np.isnan(first[late]).all() and not np.isnan(first[~late]).any()
    assert (after[late] == rounds[WINDOW[-1]].entries[late]).all()
    assert np.isnan(after[~late]).all()
    # a forwarded histogram has its percentiles and nothing else
    assert sorted(ems[3].cols[0]) == sorted(
        emissions.percentile_suffix(q) for q in PERCENTILES)


@pytest.mark.parametrize("seed", [1, 8, 2**31 + 6])
def test_forwarded_control_in_bfloat16_is_not_correct(seed):
    numbers = _forwarded_case(seed, "bfloat16")[2]["numbers"]
    assert not _correct(numbers)
    assert numbers["scalar_rows_wrong"]["value"] > 0
    # the probe series: one forwarder, one sample, back rounded
    assert numbers["rank_error_max"]["value"] > 0.2


def test_a_late_forward_is_late_not_wrong():
    rounds = _forwarded_case(6, "float64")[0]
    slot = 3        # forwarder 1's second message, due before the tick
    assert not rounds[3].late[slot]
    _r, ems, out = _forwarded_case(6, "float64", {(3, slot): 4})
    assert _correct(out["numbers"]), out["numbers"]
    assert out["lines_late"] == rounds[3].entries[slot]
    assert emissions.lines_in(ems[4], FORWARDED["groups"]) == \
        rounds[4].lines + rounds[3].entries[slot]


def test_a_forward_that_no_emission_holds_is_unaccounted():
    rounds, _ems, out = _forwarded_case(6, "float64", {(3, 3): 99})
    assert not _correct(out["numbers"])
    assert out["numbers"]["lines_unaccounted"]["value"] == \
        rounds[3].entries[3]


def test_a_forward_merged_twice_is_flagged():
    groups = FORWARDED["groups"]
    rounds, ems, _out = _forwarded_case(6, "float64")
    # slot 3's entries of round 3 once more: its marker and its counters
    ems[3].cols[MARKER]["value"][3] *= 2
    mine = rounds[3].slot[2] == 3
    ems[3].cols[2]["value"][:] += np.where(
        mine, rounds[3].values[2], 0.0).sum(axis=1)
    numbers = reference.compare(ems, rounds, WINDOW, groups, PERCENTILES,
                                LIMITS)["numbers"]
    assert numbers["lines_unaccounted"]["value"] == rounds[3].entries[3]
    assert numbers["scalar_rows_wrong"]["value"] == mine.any(axis=1).sum()


def test_a_stray_count_row_of_a_forwarded_histogram_is_stray():
    groups = FORWARDED["groups"]
    body = (b'{"series":[{"metric":"t.t.007.count","points":[[100,4.0]],'
            b'"type":"rate"},{"metric":"t.t.007.50percentile",'
            b'"points":[[100,4.5]],"type":"gauge"}]}')
    ems = emissions.parse([(100.5, "/api/v1/series", "identity", body)],
                          [0], 1, groups, PERCENTILES, 10.0)
    assert ems[0].stray == 1
    assert ems[0].cols[0][emissions.percentile_suffix(0.5)][7] == 4.5


def test_rank_error_among_few_samples():
    # two samples: the reference's own interpolated median lies between
    # them and is charged nothing; a value outside them is
    two = np.array([[1.0, 3.0, np.nan, np.nan]])
    n = np.array([2])
    assert reference.rank_error(two, np.array([2.0]), 0.5, n)[0] == 0.0
    assert reference.rank_error(two, np.array([3.5]), 0.5, n)[0] == 0.5
    assert reference.rank_error(two, np.array([1.0]), 0.5, n)[0] == 0.0
