"""The comparison that decides ``correct``: the reference in the
program's place passes at float64 and fails in bfloat16 (the control),
and every fault a cell can have from outside is flagged.

    python -m pytest benchmark/tests/test_reference.py -q
"""

import copy

import numpy as np
import pytest

from benchmark.generators import series_groups
from benchmark.lib import emissions, reference

PERCENTILES = [0.5, 0.75, 0.99]
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
                             0.02)["numbers"]


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
    out = reference.compare(ems, rounds, WINDOW, groups, PERCENTILES, 0.02)
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
    out = reference.compare(ems, rounds, WINDOW, groups, PERCENTILES, 0.02)
    assert _correct(out["numbers"])
    assert out["numbers"]["lines_unaccounted"]["value"] == 0
    assert out["lines_late"] == 2
    # held to the window alone, the same emissions hold a line too many
    alone = {k: rounds[k] for k in WINDOW}
    out = reference.compare(ems, alone, WINDOW, groups, PERCENTILES, 0.02)
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
