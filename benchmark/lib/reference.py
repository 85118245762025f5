"""The plain reference and the comparison that decides ``correct``.

The reference is float64 NumPy over the lines the generator sent; it
imports nothing of ``veneur_tpu`` and takes nothing the server made.
``compare`` holds one run's emissions against it and returns every
number compared beside its limit. ``synthesize`` is the reference put
in the program's place (at float64 it has to pass; in a lower
precision it is the control and has to fail).
"""

from __future__ import annotations

import numpy as np

from benchmark.lib.emissions import (SUFFIX_COUNT, SUFFIX_MAX, SUFFIX_MIN,
                                     Emission, forwarded, percentile_suffix)

# Two forwards that are due within this many seconds of each other may
# be merged in either order: a gauge may come back as either's.
TIE_S = 0.1


def _synthetic_clock(k: int) -> tuple:
    """``(start, span_s, interval_s)`` of round k where no run was sent:
    the reference in the program's place, and the tests."""
    return (1000.0 * k, 900.0, 1000.0)


def rank_error(samples_sorted: np.ndarray, x: np.ndarray, q: float,
               n=None) -> np.ndarray:
    """Per series: how far ``q`` lies outside the rank interval of the
    emitted value ``x`` among that series' samples (0 inside it). The
    measure ``chip_smoke.py`` and ``tests/test_tpu_smoke.py`` hold to
    0.02. A value that is not finite reads 1. Where series differ in
    their number of samples, ``n`` gives it for each and the rows are
    filled up with NaN at their ends; a value strictly between two
    neighbouring samples then counts as either of them, since among a
    few samples the measure would charge an interpolated quantile, the
    reference's own too, up to one sample's rank (0.49 at the 99th
    percentile of two)."""
    few = n is not None
    if n is None:
        n = samples_sorted.shape[1]
    below = (samples_sorted < x[:, None]).sum(axis=1) / n
    upto = (samples_sorted <= x[:, None]).sum(axis=1) / n
    err = np.where((below <= q) & (q <= upto), 0.0,
                   np.minimum(np.abs(below - q), np.abs(upto - q)))
    if few:
        between = (below == upto) & (below > 0) & (upto < 1)
        err = np.where(between, np.maximum(err - 1.0 / n, 0.0), err)
    return np.where(np.isfinite(x), err, 1.0)


def _f32_differs(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    with np.errstate(invalid="ignore"):
        return ~(got.astype(np.float32) == want.astype(np.float32))


def compare(emissions: list, rounds: dict, window: range, groups: list,
            percentiles: list, rank_limit: float, sent: dict = None) -> dict:
    """``rounds[k]`` is the generator's round flushed by ``emissions[k]``
    for k in ``window`` and, where the caller has them, for the warm-up
    rounds just before it; emissions after the window hold stragglers.
    ``sent[k]`` is ``(start, span_s, interval_s)`` as round k was sent
    (forwarded groups read the order of two forwards from it).

    A series whose ``count`` (or whose counter or gauge row) in one
    emission is not what the round sent is *late* there if the run's
    totals still account for every line: late is not wrong, and the
    wait shows in the lines' age. Rows that are on time are held
    to the round exactly; totals are held over the whole run, from the
    first round given, so that a warm-up line that slips into the window
    is late too, and not one line too many. ``lines_unaccounted`` is
    what those totals miss or have over, in lines.
    """
    span = range(min(rounds), window.stop)
    tail = range(span.start, len(emissions))
    t = {"hist_wrong": 0, "scalar_wrong": 0, "late": 0, "unaccounted": 0,
         "ranks": []}
    dup = sum(emissions[k].dup for k in tail)
    stray = sum(emissions[k].stray for k in tail)
    if any(forwarded(grp) for grp in groups):
        _forwarded(t, emissions, rounds, span, tail, groups, percentiles,
                   sent or {k: _synthetic_clock(k) for k in span})
    for g, grp in enumerate(groups):
        if forwarded(grp):
            continue
        if grp["type"] == "h":
            _local_histogram(t, g, grp, emissions, rounds, window, span,
                             tail, percentiles)
        else:
            _local_scalar(t, g, grp, emissions, rounds, window, span, tail)
    ranks = np.concatenate(t["ranks"]) if t["ranks"] else np.zeros(1)
    worst_rank = float(ranks.max())
    return {
        # beside the worst, which is compared: the steadier readings a
        # later limit could stand on (PERF.md, open questions)
        "rank_errors": {"readings": len(ranks),
                        "mean": float(ranks.mean()),
                        "p99": float(np.quantile(ranks, 0.99)),
                        "p999": float(np.quantile(ranks, 0.999)),
                        "over_0.02": int((ranks > 0.02).sum())},
        "numbers": {
            "hist_rows_wrong": {"value": t["hist_wrong"], "limit": 0},
            "scalar_rows_wrong": {"value": t["scalar_wrong"], "limit": 0},
            "rows_twice_or_stray": {"value": dup + stray, "limit": 0},
            "rank_error_max": {"value": worst_rank, "limit": rank_limit},
            "lines_unaccounted": {"value": t["unaccounted"], "limit": 0},
        },
        "lines_late": t["late"],
    }


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
    bad |= _f32_differs(lo, every.min(axis=1))
    bad |= _f32_differs(hi, every.max(axis=1))
    t["hist_wrong"] += int(bad.sum())
    for k in window:
        cols = emissions[k].cols[g]
        vals = rounds[k].values[g]
        on_time = cols[SUFFIX_COUNT] == sent
        t["late"] += int(np.abs(np.nan_to_num(cols[SUFFIX_COUNT])
                                - sent)[~on_time].sum())
        wrong = _f32_differs(cols[SUFFIX_MIN], vals.min(axis=1))
        wrong |= _f32_differs(cols[SUFFIX_MAX], vals.max(axis=1))
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
        bad = _f32_differs(final, rounds[window[-1]].last[g])
    t["scalar_wrong"] += int(bad.sum())
    t["unaccounted"] += int(bad.sum()) * int(grp["samples"]) * len(span)
    for k in window:
        v = emissions[k].cols[g]["value"]
        want = (rounds[k].values[g].sum(axis=1)
                if grp["type"] == "c" else rounds[k].last[g])
        off = _f32_differs(v, want)
        t["late"] += int((off & ~bad).sum()) * int(grp["samples"])


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


def _forwarded(t, emissions, rounds, span, tail, groups, percentiles,
               sent) -> None:
    """What a global owes for what was forwarded to it. Per emission and
    series, over the messages that the emission holds by their markers: a
    counter is the sum over their entries, a gauge the entry that was
    due last, a histogram's percentiles are held by rank error against
    the union of the forwarders' samples; a series none of them reports
    has no row. A message in a later emission than the one it was due
    for is late, not wrong; one in none is unaccounted for."""
    marker = [g for g, grp in enumerate(groups) if grp.get("marker")][0]
    holds, off = held_by(emissions, rounds, span, tail, marker)
    t["unaccounted"] += off
    for k in span:
        meant = k + rounds[k].late
        t["late"] += int(rounds[k].entries[holds[k] > meant].sum())
    due = {k: rounds[k].due(*sent[k]) for k in span}
    for g, grp in enumerate(groups):
        if grp.get("marker") or not forwarded(grp):
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
                ok = (last & ~_f32_differs(
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


# -- the reference in the program's place ---------------------------------


def _cast(values: np.ndarray, precision: str) -> np.ndarray:
    if precision == "float64":
        return values
    if precision == "float32":
        return values.astype(np.float32).astype(np.float64)
    if precision == "bfloat16":
        import ml_dtypes

        return values.astype(ml_dtypes.bfloat16).astype(np.float64)
    raise ValueError(f"no precision {precision!r}")


def synthesize(rounds: dict, window: range, n_flushes: int, groups: list,
               percentiles: list, precision: str, moved: dict = None
               ) -> list:
    """Emissions as the reference itself would post them, every value
    held and summed in ``precision``. Of forwarded groups emission k
    holds the messages of round k that are due before its tick and those
    of the round before that were due after it; ``moved`` puts single
    messages elsewhere: ``{(round, slot): emission}``."""
    out = [Emission(groups, percentiles) for _ in range(n_flushes)]
    for k in window:
        for g, grp in enumerate(groups):
            if forwarded(grp):
                continue
            vals = _cast(rounds[k].values[g], precision)
            cols = out[k].cols[g]
            if grp["type"] == "h":
                cols[SUFFIX_COUNT][:] = vals.shape[1]
                cols[SUFFIX_MIN][:] = vals.min(axis=1)
                cols[SUFFIX_MAX][:] = vals.max(axis=1)
                for q in percentiles:
                    cols[percentile_suffix(q)][:] = _cast(
                        np.quantile(vals, q, axis=1), precision)
            elif grp["type"] == "c":
                cols["value"][:] = _sum(vals, precision)
            else:
                cols["value"][:] = _cast(rounds[k].last[g], precision)
    if any(forwarded(grp) for grp in groups):
        _synthesize_forwarded(out, rounds, window, groups, percentiles,
                              precision, moved or {})
    return out


def _sum(vals: np.ndarray, precision: str) -> np.ndarray:
    """Along each row, one term after the other, NaN for no term."""
    acc = np.zeros(vals.shape[0])
    for j in range(vals.shape[1]):
        acc = _cast(acc + np.nan_to_num(vals[:, j]), precision)
    return acc


def _synthesize_forwarded(out, rounds, window, groups, percentiles,
                          precision, moved) -> None:
    holds = {k: k + rounds[k].late for k in window}
    for (k, s), e in moved.items():
        holds[k][s] = e
    due = {k: rounds[k].due(*_synthetic_clock(k)) for k in window}
    for e, em in enumerate(out):
        for g, grp in enumerate(groups):
            if not forwarded(grp):
                continue
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
            vals = _cast(np.concatenate(
                [rounds[k].values[g] for k in window], axis=1), precision)
            vals = np.where(inside.reshape(inside.shape + (1,) * (
                vals.ndim - 2)), vals, np.nan).reshape(shape)
            if grp["type"] == "h":
                union = vals.reshape(series, -1)[present]
                for q in percentiles:
                    em.cols[g][percentile_suffix(q)][present] = _cast(
                        np.nanquantile(union, q, axis=1), precision)
            elif grp["type"] == "c":
                em.cols[g]["value"][:] = np.where(
                    present, _sum(vals, precision), np.nan)
            else:
                when = np.where(inside, np.concatenate(
                    [due[k][rounds[k].slot[g]] for k in window], axis=1),
                    -np.inf)
                em.cols[g]["value"][:] = np.where(
                    present, vals[np.arange(series), when.argmax(axis=1)],
                    np.nan)
