"""The kinds of group that are not rectangles (``benchmark/kinds/``):
for each, the reference in the program's place passes at float64 and
float32 and its control fails, on three seeds; what only such a group can
get wrong is flagged; and a mix of rectangles comes out of the by-kind
generator as out of ``series_groups.py``.

    python -m pytest benchmark/tests/test_kinds.py -q
"""

import copy

import numpy as np
import pytest

from benchmark.generators import groups_by_kind, series_groups
from benchmark.kinds import ragged, topk
from benchmark.lib import emissions, reference

PERCENTILES = [0.5, 0.75, 0.99]
LIMITS = {"rank_error_limit": 0.02, "set_error_limit": 0.03,
          "server": {"topk_k": 8, "topk_width": 65536}}
COUNTERS = {"prefix": "t.c.", "type": "c", "series": 200, "samples": 1,
            "values": {"dist": "integers", "low": 1, "high": 1000}}
MIXES = {
    "ragged": {"datagram_bytes": 1400, "groups": [
        {"prefix": "t.z.", "kind": "ragged", "type": "h", "universe": 4096,
         "lines": 3000, "zipf_s": 0.99, "churn_share": 0.05,
         "values": {"dist": "quarters", "high": 400000}},
        {"prefix": "t.zc.", "kind": "ragged", "type": "c", "universe": 512,
         "lines": 700, "zipf_s": 0.99, "churn_share": 0.05,
         "values": {"dist": "integers", "low": 1, "high": 1000}},
        {"prefix": "t.zg.", "kind": "ragged", "type": "g", "universe": 512,
         "lines": 700, "zipf_s": 0.99, "churn_share": 0.05,
         "values": {"dist": "quarters", "high": 400000}},
        COUNTERS]},
    "sets": {"datagram_bytes": 1400, "groups": [
        {"prefix": "t.s.", "kind": "sets", "type": "s", "series": 64,
         "members": {"low": 1, "high": 2000, "tail": 0.6},
         "repeats": 0.25}, COUNTERS]},
    "topk": {"datagram_bytes": 1400, "groups": [
        {"prefix": "t.hot.", "kind": "topk", "type": "s", "series": 4,
         "lines": 4000, "members": 100000, "zipf_s": 0.99}, COUNTERS]},
}
# what each kind's control has to fail; a ragged group's is the float
# precision below the configuration's, the sketches' one line in ten
CONTROL = {"ragged": ("bfloat16", {"hist_rows_wrong", "scalar_rows_wrong",
                                   "rank_error_max"}),
           "sets": ("float32", {"set_error_max"}),
           "topk": ("float32", {"topk_undercount"})}
WINDOW = range(2, 5)


def _case(mix, seed, precision, control=False):
    params = MIXES[mix]
    rounds = {k: groups_by_kind.build(params, seed, k - 1) for k in WINDOW}
    ems = reference.synthesize(rounds, WINDOW, 6, params["groups"],
                               PERCENTILES, precision, control=control,
                               limits=LIMITS)
    return params["groups"], rounds, ems


def _compare(groups, rounds, ems):
    return reference.compare(ems, rounds, WINDOW, groups, PERCENTILES,
                             LIMITS)


def _over(out):
    return {k for k, n in out["numbers"].items() if n["value"] > n["limit"]}


@pytest.mark.parametrize("mix", sorted(MIXES))
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_reference_in_its_own_place_is_correct(mix, seed):
    for precision in ("float64", "float32"):
        groups, rounds, ems = _case(mix, seed, precision)
        out = _compare(groups, rounds, ems)
        assert _over(out) == set(), out["numbers"]
        assert out["lines_late"] == 0
        # every emission of the window accounts for its round's lines
        assert [emissions.lines_in(ems[k], groups, rounds[k])
                for k in WINDOW] == [rounds[k].lines for k in WINDOW]


@pytest.mark.parametrize("mix", sorted(MIXES))
@pytest.mark.parametrize("seed", [1, 8, 2**31 + 6])
def test_control_is_not_correct(mix, seed):
    precision, fails = CONTROL[mix]
    over = _over(_compare(*_case(mix, seed, precision, control=True)))
    assert fails <= over


def test_every_seed_gives_the_same_number_of_lines_of_every_group():
    for mix in MIXES.values():
        a = groups_by_kind.build(mix, 3, 2)
        b = groups_by_kind.build(mix, 2**31 + 9, 2)
        assert a.lines == b.lines and a.units != b.units
        again = groups_by_kind.build(mix, 3, 2)
        assert a.units == again.units


def test_rectangles_come_out_of_both_generators_alike():
    mix = {"datagram_bytes": 1400, "groups": [
        COUNTERS, {"prefix": "t.h.", "type": "h", "series": 50,
                   "samples": 3, "values": {"dist": "quarters",
                                            "high": 400}}]}
    a, b = series_groups.build(mix, 5, 2), groups_by_kind.build(mix, 5, 2)
    assert a.units == b.units and a.lines == b.lines
    for x, y in zip(a.values + a.last, b.values + b.last):
        assert (x == y).all()
    assert series_groups.warm_lines(mix) == groups_by_kind.warm_lines(mix)


def test_names_churn_for_good_and_the_hot_set_drifts():
    group = MIXES["ragged"]["groups"][0]
    first, third = (ragged.names_at(group, 11, j) for j in (1, 3))
    gone = np.setdiff1d(first, third)
    assert len(gone) > 0.09 * len(first)       # two rounds of a twentieth
    # a name that left is in no later round, and the new ones are new
    assert not np.isin(gone, ragged.names_at(group, 11, 5)).any()
    assert np.setdiff1d(third, first).min() >= group["universe"]
    assert len(np.unique(third)) == group["universe"]


def test_a_row_for_a_series_the_round_did_not_send_is_stray():
    groups, rounds, ems = _case("ragged", 4, "float64")
    sent = rounds[3].values[0].series
    idle = np.setdiff1d(np.arange(groups[0]["universe"]), sent)[0]
    for col in ems[3].cols[0].values():
        col[idle] = 1.0
    out = _compare(groups, rounds, ems)
    assert out["numbers"]["rows_twice_or_stray"]["value"] == 1
    assert out["numbers"]["hist_rows_wrong"]["value"] == 1


def test_a_churned_out_name_that_comes_back_is_flagged():
    groups, rounds, ems = _case("ragged", 4, "float64")
    group = groups[0]
    gone = np.setdiff1d(rounds[2].values[0].series,
                        ragged.names_at(group, 4, 3))[0]
    for name, col in ems[4].cols[0].items():
        col[gone] = ems[2].cols[0][name][gone]
    assert "rows_twice_or_stray" in _over(_compare(groups, rounds, ems))


def test_a_ragged_line_that_comes_late_is_late_not_wrong():
    groups, rounds, ems = _case("ragged", 6, "float64")
    s3, s4 = rounds[3].values[0], rounds[4].values[0]
    # a series with one sample in round 3 and none in round 4: its line
    # comes with flush 4 instead
    lone = s3.series[(s3.counts == 1) & ~np.isin(s3.series, s4.series)][0]
    for name, col in ems[3].cols[0].items():
        ems[4].cols[0][name][lone], col[lone] = col[lone], np.nan
    out = _compare(groups, rounds, ems)
    assert _over(out) == set(), out["numbers"]
    assert out["lines_late"] == 2


def test_rank_errors_are_reported_by_band_of_samples():
    groups, rounds, ems = _case("ragged", 4, "bfloat16")
    bands = _compare(groups, rounds, ems)["rank_errors"]["by_band"]
    assert list(bands) == ["1", "2-15", "16-127", "128-1023", "1024+"]
    assert bands["1"]["max"] > 0.4         # a lone sample back rounded
    assert sum(b["readings"] for b in bands.values()) == 3 * sum(
        len(rounds[k].values[0].series) for k in WINDOW)


def test_a_set_estimate_five_percent_off_is_flagged():
    groups, rounds, ems = _case("sets", 4, "float64")
    ems[3].cols[0]["value"][-1] *= 1.05
    assert _over(_compare(groups, rounds, ems)) == {"set_error_max"}
    # one member short of five is the sketch's own to miss
    groups, rounds, ems = _case("sets", 4, "float64")
    five = int(np.flatnonzero(rounds[3].values[0] == 5)[0])
    ems[3].cols[0]["value"][five] = 4.0
    assert _over(_compare(groups, rounds, ems)) == set()


def test_a_set_row_that_is_missing_is_wrong_and_its_lines_unaccounted():
    groups, rounds, ems = _case("sets", 4, "float64")
    ems[3].cols[0]["value"][-1] = np.nan
    out = _compare(groups, rounds, ems)
    assert _over(out) == {"set_rows_wrong", "lines_unaccounted"}
    assert out["numbers"]["lines_unaccounted"]["value"] == 2000 + 500


def _topk_rows(em):
    cols = em.cols[0]
    stream, member, value = topk.rows_of(cols)
    cols["series"], cols["member"], cols["value"] = (
        [stream], [member], [value])
    return stream, member, value


def test_a_topk_member_swapped_for_another_is_flagged():
    groups, rounds, ems = _case("topk", 4, "float64")
    _stream, member, _value = _topk_rows(ems[3])
    member[0] = 10**9              # a key the stream never held
    over = _over(_compare(groups, rounds, ems))
    assert {"rows_twice_or_stray", "topk_missed"} <= over


def test_a_topk_count_one_under_the_exact_is_flagged():
    groups, rounds, ems = _case("topk", 4, "float64")
    _topk_rows(ems[3])[2][5] -= 1.0
    assert _over(_compare(groups, rounds, ems)) == {"topk_undercount"}


def test_a_topk_overcount_reads_as_a_share_of_the_rounds_lines():
    groups, rounds, ems = _case("topk", 4, "float64")
    _topk_rows(ems[3])[2][5] += 2.0
    out = _compare(groups, rounds, ems)
    assert _over(out) == {"topk_overcount_max"}
    assert out["numbers"]["topk_overcount_max"]["value"] == 2.0 / 4000
    assert out["numbers"]["topk_overcount_max"]["limit"] == \
        pytest.approx(np.e / 65536)


def test_a_ninth_row_of_a_stream_and_a_row_twice_are_stray():
    groups, rounds, ems = _case("topk", 4, "float64")
    cols = ems[3].cols[0]
    stream, member, value = _topk_rows(ems[3])
    cols["series"].append(stream[:1])
    cols["member"].append(member[:1])
    cols["value"].append(value[:1])
    assert _compare(groups, rounds, ems)["numbers"][
        "rows_twice_or_stray"]["value"] == 1
    ninth = copy.deepcopy(LIMITS)
    ninth["server"]["topk_k"] = 7
    out = reference.compare(ems, rounds, WINDOW, groups, PERCENTILES, ninth)
    assert out["numbers"]["rows_twice_or_stray"]["value"] >= 4


def test_topk_rows_are_parsed_by_their_key_tag():
    groups = MIXES["topk"]["groups"]
    body = (b'{"series": [{"metric": "t.hot.2.topk", "points": [[100.0, '
            b'4.0]], "tags": ["veneurtopk", "key:k77"], "type": "rate"}, '
            b'{"metric": "t.hot.2.topk", "points": [[100.0, 1.5]], '
            b'"tags": ["veneurtopk", "key:0x00ab"], "type": "rate"},'
            b'{"metric":"t.c.7","points":[[100,2.0]],"type":"rate"}]}')
    em = emissions.parse([(100.5, "/api/v1/series", "identity", body)],
                         [0], 1, groups, PERCENTILES, 10.0)[0]
    assert [a.tolist() for a in topk.rows_of(em.cols[0])] == [
        [2], [77], [40.0]]
    assert em.stray == 1 and em.cols[1]["value"][7] == 20.0
    assert emissions.lines_in(em, groups) == 1000 + 1


def test_a_stream_with_no_row_leaves_its_lines_unaccounted():
    groups, rounds, ems = _case("topk", 4, "float64")
    ems[3].cols[0].update(series=[], member=[], value=[])
    out = _compare(groups, rounds, ems)
    assert out["numbers"]["lines_unaccounted"]["value"] == 4000
    assert out["numbers"]["topk_missed"]["value"] == len(
        topk.frequencies(rounds[3].values[0], 100000)[0])
