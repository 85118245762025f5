"""Series that reach the server as forwards of other instances
(``generators/forwarded_groups.py``, feed ``forward_grpc``): what a
group is that names no kind and states how many forwarders report a
series (``fan_in``), or is their messages' ``marker``.

A forwarded histogram has its percentiles only (count, min and max are
the forwarders' own to emit), held by rank error against the union of
every forwarder's samples; a counter is the sum over the entries of the
messages an emission holds, a gauge the entry due last. Which emission
holds a message is the marker's to say: its rows alone count lines,
each the entries of the messages it stands for.
"""

from __future__ import annotations

import numpy as np

from benchmark.lib.emissions import (land_rectangle, percentile_suffix,
                                     rectangle)
from benchmark.lib.reference import (cast, f32_differs, rank_error, sum_in,
                                     synthetic_clock)

# Two forwards that are due within this many seconds of each other may
# be merged in either order: a gauge may come back as either's.
TIE_S = 0.1



def table(group: dict, percentiles: list, flushes: int) -> dict:
    if group["type"] != "h":
        return rectangle(group["series"], ["value"])
    return rectangle(group["series"],
                     [percentile_suffix(q) for q in percentiles])


def land(em, cols, group, idx, suf, tags, val) -> None:
    land_rectangle(em, cols, idx, suf, val)


def lines_in(cols: dict, group: dict, sent=None) -> int:
    if group.get("marker"):
        return int(np.nansum(cols["value"]))
    return 0


def live_series(group: dict) -> int:
    return int(group["series"])


def compare(t, mine, emissions, rounds, window, span, tail, groups,
            percentiles, limits, sent) -> None:
    _forwarded(t, mine, emissions, rounds, span, tail, groups, percentiles,
               sent)


def synthesize(out, mine, rounds, window, groups, percentiles, precision,
               moved, control, limits) -> None:
    _synthesize_forwarded(out, mine, rounds, window, groups, percentiles,
                          precision, moved)


def held_by(emissions: list, rounds: dict, span: range, tail: range,
            marker: int) -> tuple:
    """Which emission holds each message, read from outside. Every
    message carries its marker with the number of its entries, and a
    forwarder's messages arrive in the order in which it sent them: so
    the marker's rows, cumulated over the emissions, say how many of a
    slot's messages each emission has merged. Returns ``{round:
    [slots]}`` with the emission's index, or one past the last where
    none holds the message, and the entries that the markers' totals
    miss or have over."""
    got = np.cumsum([np.nan_to_num(emissions[e].cols[marker]["value"])
                     for e in tail], axis=0)
    want = np.cumsum([rounds[k].entries for k in span], axis=0)
    holds = {k: tail.start + (got < want[i]).sum(axis=0)
             for i, k in enumerate(span)}
    return holds, int(np.abs(got[-1] - want[-1]).sum())


def _forwarded(t, mine, emissions, rounds, span, tail, groups, percentiles,
               sent) -> None:
    """What a global owes for what was forwarded to it. Per emission and
    series, over the messages that the emission holds by their markers: a
    counter is the sum over their entries, a gauge the entry that was
    due last, a histogram's percentiles are held by rank error against
    the union of the forwarders' samples; a series none of them reports
    has no row. A message in a later emission than the one it was due
    for is late, not wrong; one in none is unaccounted for."""
    marker = [g for g in mine if groups[g].get("marker")][0]
    holds, off = held_by(emissions, rounds, span, tail, marker)
    t["unaccounted"] += off
    for k in span:
        meant = k + rounds[k].late
        t["late"] += int(rounds[k].entries[holds[k] > meant].sum())
    due = {k: rounds[k].due(*sent[k]) for k in span}
    for g in mine:
        grp = groups[g]
        if grp.get("marker"):
            continue
        series = int(grp["series"])
        total = np.zeros(series)
        last_off = np.zeros(series, dtype=bool)
        off_rows = np.zeros(series, dtype=np.int64)
        for e in tail:
            inside = [(k, holds[k][rounds[k].slot[g]] == e) for k in span]
            inside = [(k, m) for k, m in inside if m.any()]
            present = np.zeros(series, dtype=bool)
            for _k, m in inside:
                present |= m.any(axis=1)
            cols = emissions[e].cols[g]
            if grp["type"] == "h":
                t["hist_wrong"] += _percentiles_against_union(
                    t, cols, [np.where(m[:, :, None], rounds[k].values[g],
                                       np.nan).reshape(series, -1)
                              for k, m in inside], present, percentiles)
                continue
            v = cols["value"]
            total += np.nan_to_num(v)
            ok = np.zeros(series, dtype=bool)
            if grp["type"] == "c":
                ok = v == sum((np.where(m, rounds[k].values[g], 0.0)
                               .sum(axis=1) for k, m in inside),
                              np.zeros(series))
            elif inside:
                when = np.concatenate(
                    [np.where(m, due[k][rounds[k].slot[g]], -np.inf)
                     for k, m in inside], axis=1)
                vals = np.concatenate([rounds[k].values[g]
                                       for k, _m in inside], axis=1)
                last = when >= when.max(axis=1, keepdims=True) - TIE_S
                ok = (last & ~f32_differs(
                    np.broadcast_to(v[:, None], vals.shape),
                    vals)).any(axis=1)
            off = np.where(present, ~ok, ~np.isnan(v))
            last_off = np.where(present | ~np.isnan(v), off, last_off)
            off_rows += off
        if grp["type"] == "h":
            continue
        # a row that is off where the run's total (a counter) or its
        # last row (a gauge) is right stands in another emission than its
        # message's marker: late, as a local row is
        bad = (total != sum(rounds[k].values[g].sum(axis=1) for k in span)
               if grp["type"] == "c" else last_off)
        t["scalar_wrong"] += int(bad.sum())
        t["late"] += int(off_rows[~bad].sum())


def _percentiles_against_union(t, cols, parts, present, percentiles) -> int:
    """Rank errors of one emission's percentile rows of one group, each
    against the samples in ``parts`` that are not NaN; returns the series
    that have a row and no sample, or samples and a row missing."""
    wrong = np.zeros(len(present), dtype=bool)
    rows = [cols[percentile_suffix(q)] for q in percentiles]
    for x in rows:
        wrong |= np.isnan(x) == present
    if present.any():
        union = np.sort(np.concatenate(parts, axis=1)[present], axis=1)
        n = (~np.isnan(union)).sum(axis=1)
        for q, x in zip(percentiles, rows):
            t["ranks"].append(rank_error(union, x[present], q, n))
    return int(wrong.sum())


def _synthesize_forwarded(out, own, rounds, window, groups, percentiles,
                          precision, moved) -> None:
    holds = {k: k + rounds[k].late for k in window}
    for (k, s), e in moved.items():
        holds[k][s] = e
    due = {k: rounds[k].due(*synthetic_clock(k)) for k in window}
    for e, em in enumerate(out):
        for g in own:
            grp = groups[g]
            series = int(grp["series"])
            if grp.get("marker"):
                # written whole, as a local histogram's count is
                mine = np.stack([np.where(holds[k] == e, rounds[k].entries,
                                          np.nan) for k in window], axis=1)
                em.cols[g]["value"][:] = np.where(
                    np.isnan(mine).all(axis=1), np.nan,
                    np.nansum(mine, axis=1))
                continue
            inside = np.concatenate(
                [holds[k][rounds[k].slot[g]] == e for k in window], axis=1)
            present = inside.any(axis=1)
            shape = (series, -1) + rounds[window[0]].values[g].shape[2:]
            vals = cast(np.concatenate(
                [rounds[k].values[g] for k in window], axis=1), precision)
            vals = np.where(inside.reshape(inside.shape + (1,) * (
                vals.ndim - 2)), vals, np.nan).reshape(shape)
            if grp["type"] == "h":
                union = vals.reshape(series, -1)[present]
                for q in percentiles:
                    em.cols[g][percentile_suffix(q)][present] = cast(
                        np.nanquantile(union, q, axis=1), precision)
            elif grp["type"] == "c":
                em.cols[g]["value"][:] = np.where(
                    present, sum_in(vals, precision), np.nan)
            else:
                when = np.where(inside, np.concatenate(
                    [due[k][rounds[k].slot[g]] for k in window], axis=1),
                    -np.inf)
                em.cols[g]["value"][:] = np.where(
                    present, vals[np.arange(series), when.argmax(axis=1)],
                    np.nan)
