"""A rectangle of series that this instance aggregates itself: every
series of the group gets ``samples`` lines ``<prefix><i>:<value>|<type>``
every interval, ``type`` ``h``, ``c`` or ``g``. What a group is that
names no kind and is not forwarded.

A histogram owes ``count``, ``min``, ``max`` exact and its percentiles
by rank error among the samples the interval sent; a counter the sum, a
gauge the last write. A histogram row's ``count`` is its lines; a
counter or gauge row stands for the ``samples`` lines a round sends
that series.
"""

from __future__ import annotations

import numpy as np

from benchmark.generators.series_groups import DISTRIBUTIONS, _text
from benchmark.lib.emissions import (SUFFIX_COUNT, SUFFIX_MAX, SUFFIX_MIN,
                                     histogram_suffixes, land_rectangle,
                                     percentile_suffix, rectangle)
from benchmark.lib.reference import cast, f32_differs, rank_error, sum_in


def table(group: dict, percentiles: list, flushes: int) -> dict:
    if group["type"] != "h":
        return rectangle(group["series"], ["value"])
    return rectangle(group["series"], histogram_suffixes(percentiles))


def land(em, cols, group, idx, suf, tags, val) -> None:
    land_rectangle(em, cols, idx, suf, val)


def lines_in(cols: dict, group: dict, sent=None) -> int:
    if group["type"] == "h":
        return int(np.nansum(cols[SUFFIX_COUNT]))
    return int((~np.isnan(cols["value"])).sum()) * int(group["samples"])


def live_series(group: dict) -> int:
    return int(group["series"])


def compare(t, mine, emissions, rounds, window, span, tail, groups,
            percentiles, limits, sent) -> None:
    for g in mine:
        if groups[g]["type"] == "h":
            _local_histogram(t, g, groups[g], emissions, rounds, window,
                             span, tail, percentiles)
        else:
            _local_scalar(t, g, groups[g], emissions, rounds, window, span,
                          tail)


def _local_histogram(t, g, grp, emissions, rounds, window, span, tail,
                     percentiles) -> None:
    series = int(grp["series"])
    sent = float(grp["samples"])
    total = np.zeros(series)
    lo = np.full(series, np.inf)
    hi = np.full(series, -np.inf)
    for k in tail:
        cols = emissions[k].cols[g]
        total += np.nan_to_num(cols[SUFFIX_COUNT])
        lo = np.fmin(lo, cols[SUFFIX_MIN])
        hi = np.fmax(hi, cols[SUFFIX_MAX])
    every = np.concatenate([rounds[k].values[g] for k in span], axis=1)
    bad = (total != sent * len(span))
    t["unaccounted"] += int(np.abs(total - sent * len(span)).sum())
    bad |= f32_differs(lo, every.min(axis=1))
    bad |= f32_differs(hi, every.max(axis=1))
    t["hist_wrong"] += int(bad.sum())
    for k in window:
        cols = emissions[k].cols[g]
        vals = rounds[k].values[g]
        on_time = cols[SUFFIX_COUNT] == sent
        t["late"] += int(np.abs(np.nan_to_num(cols[SUFFIX_COUNT])
                                - sent)[~on_time].sum())
        wrong = f32_differs(cols[SUFFIX_MIN], vals.min(axis=1))
        wrong |= f32_differs(cols[SUFFIX_MAX], vals.max(axis=1))
        t["hist_wrong"] += int((wrong & on_time & ~bad).sum())
        ordered = np.sort(vals[on_time], axis=1)
        for q in percentiles:
            x = cols[percentile_suffix(q)][on_time]
            if len(x):
                t["ranks"].append(rank_error(ordered, x, q))


def _local_scalar(t, g, grp, emissions, rounds, window, span, tail) -> None:
    series = int(grp["series"])
    total = np.zeros(series)
    final = np.full(series, np.nan)
    for k in tail:
        v = emissions[k].cols[g]["value"]
        total += np.nan_to_num(v)
        final = np.where(np.isnan(v), final, v)
    if grp["type"] == "c":
        want = sum(rounds[k].values[g].sum(axis=1) for k in span)
        bad = total != want
    else:
        bad = f32_differs(final, rounds[window[-1]].last[g])
    t["scalar_wrong"] += int(bad.sum())
    t["unaccounted"] += int(bad.sum()) * int(grp["samples"]) * len(span)
    for k in window:
        v = emissions[k].cols[g]["value"]
        want = (rounds[k].values[g].sum(axis=1)
                if grp["type"] == "c" else rounds[k].last[g])
        off = f32_differs(v, want)
        t["late"] += int((off & ~bad).sum()) * int(grp["samples"])


def synthesize(out, mine, rounds, window, groups, percentiles, precision,
               moved, control, limits) -> None:
    for k in window:
        for g in mine:
            grp = groups[g]
            vals = cast(rounds[k].values[g], precision)
            cols = out[k].cols[g]
            if grp["type"] == "h":
                cols[SUFFIX_COUNT][:] = vals.shape[1]
                cols[SUFFIX_MIN][:] = vals.min(axis=1)
                cols[SUFFIX_MAX][:] = vals.max(axis=1)
                for q in percentiles:
                    cols[percentile_suffix(q)][:] = cast(
                        np.quantile(vals, q, axis=1), precision)
            elif grp["type"] == "c":
                cols["value"][:] = sum_in(vals, precision)
            else:
                cols["value"][:] = cast(rounds[k].last[g], precision)


# -- for a mix that generators/groups_by_kind.py builds --------------------


def lines_a_round(group: dict) -> int:
    return int(group["series"]) * int(group["samples"])


def warm_line(group: dict) -> bytes:
    kind = group["type"].encode()
    return b"bench.warm.%s:1|%s" % (kind, kind)


def generate(group: dict, rng, seed: int, index: int) -> tuple:
    """As ``generators/series_groups.py`` draws and writes a group."""
    shape = (int(group["series"]), int(group["samples"]))
    vals = DISTRIBUTIONS[group["values"]["dist"]](rng, shape,
                                                  group["values"])
    texts = _text(vals, group["type"])
    prefix, kind = group["prefix"].encode(), group["type"].encode()
    heads = np.repeat(np.arange(shape[0]), shape[1])
    return [prefix + b"%d:" % i + text + b"|" + kind
            for i, text in zip(heads.tolist(), texts)], vals


def settle(group: dict, vals: np.ndarray, position: np.ndarray) -> tuple:
    pos = position.reshape(vals.shape)
    return vals, vals[np.arange(vals.shape[0]), pos.argmax(axis=1)]
