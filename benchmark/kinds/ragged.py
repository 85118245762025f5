"""Rounds that are not rectangles: a group whose lines are drawn anew
every interval from a Zipf law over names that churn.

    prefix, type    names ``<prefix><i>``; ``h``, ``c`` or ``g``
    universe        names alive at any time: the ranks of the Zipf law
    lines           lines drawn every round, whatever the seed
    zipf_s          a line goes to rank r with probability ~ r^-zipf_s
    churn_share     of the universe's names, the share that retires for
                    good from one round to the next; as many new names
                    (``i`` from ``universe`` upwards) take their ranks,
                    so hot names leave and the hot set drifts
    values          a distribution of ``generators/series_groups.py``,
                    drawn a line at a time (``lognormal_64ths``' scale
                    is then a line's own)

The map from rank to name is a permutation drawn from the seed; which
ranks retire in round j is drawn from the seed and j. A round keeps, for
the series it sent, their samples (ragged: offsets and values) and the
sample sent last.

What such a group owes: ``count``, ``min`` and ``max`` exact for every
series the round sent and **no row for a series it did not send** (a row
there is late where the run's totals still account for every line, as
anywhere; stray where they do not); totals over the run; percentiles by
rank error among the series' own samples of the interval, each with its
own ``n``, the largest reading reported by band of ``n`` beside the
worst; a counter the sum, a gauge the last write. A histogram row's
``count`` is its lines; a counter or gauge row stands for the lines its
round sent that series.
"""

from __future__ import annotations

import numpy as np

from benchmark.generators.groups_by_kind import zipf_cdf, zipf_ranks
from benchmark.generators.series_groups import DISTRIBUTIONS, _text
from benchmark.lib.emissions import (SUFFIX_COUNT, SUFFIX_MAX, SUFFIX_MIN,
                                     histogram_suffixes, land_rectangle,
                                     percentile_suffix, rectangle)
from benchmark.lib.reference import cast, f32_differs, rank_error, sum_in

CHURN = 0xC4027          # salts of the seed sequences that are not a round's


class Sent:
    """What a round sent the group: ``series`` the names' indices,
    ascending; series j's samples are ``samples[offsets[j]:offsets[j +
    1]]``, ascending once ``settle`` has seen them."""

    def __init__(self, series, offsets, samples):
        self.series, self.offsets, self.samples = series, offsets, samples
        self.counts = np.diff(offsets)

    def rows(self, pick: np.ndarray, width: int) -> np.ndarray:
        """``[len(pick), width]``: the samples of the series at ``pick``,
        each row filled up with NaN at its end."""
        n = self.counts[pick]
        out = np.full((len(pick), width), np.nan)
        col = np.arange(n.sum()) - np.repeat(np.cumsum(n) - n, n)
        out[np.repeat(np.arange(len(pick)), n), col] = self.samples[
            np.repeat(self.offsets[pick], n) + col]
        return out

    def by_width(self, pick: np.ndarray):
        """``pick`` in parts whose series have about as many samples:
        ``(part, width)`` with every count in ``(width / 2, width]``."""
        n = self.counts[pick]
        width = 1
        while len(pick):
            inside = n <= width
            if inside.any():
                yield pick[inside], width
            pick, n = pick[~inside], n[~inside]
            width *= 2

    def spread(self, size: int, per_series: np.ndarray, fill=np.nan):
        out = np.full(size, fill, dtype=np.float64)
        out[self.series] = per_series
        return out


def names_at(group: dict, seed: int, index: int) -> np.ndarray:
    """``[universe]``: the name's index at each rank in round ``index``.
    Round 1 has the seed's permutation; from each round to the next
    ``churn_share`` of the ranks get a name that was never used."""
    universe = int(group["universe"])
    names = np.random.default_rng([seed, CHURN]).permutation(universe)
    leave = churned(group)
    for j in range(2, index + 1):
        ranks = np.random.default_rng([seed, CHURN, j]).choice(
            universe, size=leave, replace=False)
        names[ranks] = universe + (j - 2) * leave + np.arange(leave)
    return names


def churned(group: dict) -> int:
    return int(round(float(group["churn_share"]) * int(group["universe"])))


def generate(group: dict, rng, seed: int, index: int) -> tuple:
    universe, n = int(group["universe"]), int(group["lines"])
    ranks = zipf_ranks(rng, universe, group["zipf_s"], n)
    series = np.sort(names_at(group, seed, index)[ranks])
    vals = DISTRIBUTIONS[group["values"]["dist"]](rng, (n, 1),
                                                  group["values"])[:, 0]
    prefix, kind = group["prefix"].encode(), group["type"].encode()
    lines = [prefix + b"%d:" % i + text + b"|" + kind
             for i, text in zip(series.tolist(), _text(vals, group["type"]))]
    uniq, first = np.unique(series, return_index=True)
    return lines, Sent(uniq, np.append(first, n), vals)


def settle(group: dict, sent: Sent, position: np.ndarray) -> tuple:
    row = np.repeat(np.arange(len(sent.series)), sent.counts)
    by_time = np.lexsort((position, row))
    last = sent.samples[by_time[sent.offsets[1:] - 1]]
    sent.samples = sent.samples[np.lexsort((sent.samples, row))]
    return sent, last


def lines_a_round(group: dict) -> int:
    return int(group["lines"])


def warm_line(group: dict) -> bytes:
    kind = group["type"].encode()
    return b"bench.warm.%s:1|%s" % (kind, kind)


def live_series(group: dict) -> int:
    """Expected names that get a line in a round."""
    p = np.diff(zipf_cdf(int(group["universe"]), float(group["zipf_s"])),
                prepend=0.0)
    return int(round((1.0 - (1.0 - p) ** int(group["lines"])).sum()))


def table(group: dict, percentiles: list, flushes: int) -> dict:
    names = int(group["universe"]) + churned(group) * flushes
    if group["type"] != "h":
        return rectangle(names, ["value"])
    return rectangle(names, histogram_suffixes(percentiles))


def land(em, cols, group, idx, suf, tags, val) -> None:
    land_rectangle(em, cols, idx, suf, val)


def lines_in(cols: dict, group: dict, sent: Sent = None) -> int:
    if group["type"] == "h":
        return int(np.nansum(cols[SUFFIX_COUNT]))
    rows = ~np.isnan(cols["value"])
    if sent is None:
        return int(rows.sum())
    return int(np.maximum(sent.spread(len(rows), sent.counts, 0.0), 1.0)[
        rows].sum())


def compare(t, mine, emissions, rounds, window, span, tail, groups,
            percentiles, limits, sent) -> None:
    for g in mine:
        if groups[g]["type"] == "h":
            _histogram(t, g, emissions, rounds, window, span, tail,
                       percentiles)
        else:
            _scalar(t, g, groups[g]["type"] == "c", emissions, rounds,
                    window, span, tail)


def _least(s: Sent) -> np.ndarray:
    return np.minimum.reduceat(s.samples, s.offsets[:-1])


def _most(s: Sent) -> np.ndarray:
    return np.maximum.reduceat(s.samples, s.offsets[:-1])


def _histogram(t, g, emissions, rounds, window, span, tail,
               percentiles) -> None:
    size = len(emissions[tail.start].cols[g][SUFFIX_COUNT])
    total, want = np.zeros(size), np.zeros(size)
    lo, want_lo = np.full(size, np.inf), np.full(size, np.inf)
    hi, want_hi = np.full(size, -np.inf), np.full(size, -np.inf)
    for k in tail:
        cols = emissions[k].cols[g]
        total += np.nan_to_num(cols[SUFFIX_COUNT])
        lo = np.fmin(lo, cols[SUFFIX_MIN])
        hi = np.fmax(hi, cols[SUFFIX_MAX])
    for k in span:
        s = rounds[k].values[g]
        want[s.series] += s.counts
        want_lo[s.series] = np.minimum(want_lo[s.series], _least(s))
        want_hi[s.series] = np.maximum(want_hi[s.series], _most(s))
    bad = total != want
    t["unaccounted"] += int(np.abs(total - want).sum())
    bad |= f32_differs(lo, want_lo) | f32_differs(hi, want_hi)
    t["hist_wrong"] += int(bad.sum())
    for k in window:
        cols, s = emissions[k].cols[g], rounds[k].values[g]
        got = cols[SUFFIX_COUNT]
        sent_k = s.spread(size, s.counts, 0.0)
        on_time = (got == sent_k) & (sent_k > 0)
        t["late"] += int(np.abs(np.nan_to_num(got) - sent_k)[~on_time].sum())
        rows = np.zeros(size, dtype=bool)
        for col in cols.values():
            rows |= ~np.isnan(col)
        t["stray"] += int((rows & (sent_k == 0) & bad).sum())
        wrong = f32_differs(cols[SUFFIX_MIN], s.spread(size, _least(s)))
        wrong |= f32_differs(cols[SUFFIX_MAX], s.spread(size, _most(s)))
        t["hist_wrong"] += int((wrong & on_time & ~bad).sum())
        for part, width in s.by_width(np.flatnonzero(on_time[s.series])):
            ordered, n = s.rows(part, width), s.counts[part]
            for q in percentiles:
                err = rank_error(ordered, cols[percentile_suffix(q)][
                    s.series[part]], q, n)
                t["ranks"].append(err)
                t["banded"].append((n, err))


def _scalar(t, g, counter, emissions, rounds, window, span, tail) -> None:
    size = len(emissions[tail.start].cols[g]["value"])
    total, final = np.zeros(size), np.full(size, np.nan)
    for k in tail:
        v = emissions[k].cols[g]["value"]
        total += np.nan_to_num(v)
        final = np.where(np.isnan(v), final, v)
    want, lines = np.zeros(size), np.zeros(size)
    want_last = np.full(size, np.nan)
    for k in span:
        s = rounds[k].values[g]
        want[s.series] += np.add.reduceat(s.samples, s.offsets[:-1])
        want_last[s.series] = rounds[k].last[g]
        lines[s.series] += s.counts
    bad = (total != want if counter else _differs(final, want_last))
    t["scalar_wrong"] += int(bad.sum())
    t["unaccounted"] += int(np.maximum(lines[bad], 1).sum())
    for k in window:
        v, s = emissions[k].cols[g]["value"], rounds[k].values[g]
        want_k = s.spread(size, np.add.reduceat(s.samples, s.offsets[:-1])
                          if counter else rounds[k].last[g])
        off = _differs(v, want_k)
        t["stray"] += int((~np.isnan(v) & np.isnan(want_k) & bad).sum())
        t["late"] += int(np.maximum(s.spread(size, s.counts, 0.0), 1)[
            off & ~bad].sum())


def _differs(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """As float32, where no row on both sides is no difference."""
    return f32_differs(got, want) & ~(np.isnan(got) & np.isnan(want))


def synthesize(out, mine, rounds, window, groups, percentiles, precision,
               moved, control, limits) -> None:
    for k in window:
        for g in mine:
            s, cols = rounds[k].values[g], out[k].cols[g]
            held = Sent(s.series, s.offsets, cast(s.samples, precision))
            if groups[g]["type"] == "g":
                cols["value"][s.series] = cast(rounds[k].last[g], precision)
                continue
            if groups[g]["type"] == "h":
                cols[SUFFIX_COUNT][s.series] = s.counts
                cols[SUFFIX_MIN][s.series] = _least(held)
                cols[SUFFIX_MAX][s.series] = _most(held)
            if groups[g]["type"] == "c":
                for part, width in held.by_width(np.arange(len(s.series))):
                    cols["value"][s.series[part]] = sum_in(
                        held.rows(part, width), precision)
                continue
            for q in percentiles:
                cols[percentile_suffix(q)][s.series] = cast(
                    _quantile(held, q), precision)


def _quantile(s: Sent, q: float) -> np.ndarray:
    """Per series, the q-quantile of its samples (ascending), between
    two neighbours on a straight line, as ``np.quantile`` takes it."""
    at = (s.counts - 1) * q
    low = np.floor(at).astype(np.int64)
    below = s.samples[s.offsets[:-1] + low]
    above = s.samples[s.offsets[:-1] + np.minimum(low + 1, s.counts - 1)]
    return below + (above - below) * (at - low)
