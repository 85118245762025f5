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
                                     Emission, percentile_suffix)


def rank_error(samples_sorted: np.ndarray, x: np.ndarray,
               q: float) -> np.ndarray:
    """Per series: how far ``q`` lies outside the rank interval of the
    emitted value ``x`` among that series' samples (0 inside it). The
    measure ``chip_smoke.py`` and ``tests/test_tpu_smoke.py`` hold to
    0.02. A value that is not finite reads 1."""
    n = samples_sorted.shape[1]
    below = (samples_sorted < x[:, None]).sum(axis=1) / n
    upto = (samples_sorted <= x[:, None]).sum(axis=1) / n
    err = np.where((below <= q) & (q <= upto), 0.0,
                   np.minimum(np.abs(below - q), np.abs(upto - q)))
    return np.where(np.isfinite(x), err, 1.0)


def _f32_differs(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    with np.errstate(invalid="ignore"):
        return ~(got.astype(np.float32) == want.astype(np.float32))


def compare(emissions: list, rounds: dict, window: range, groups: list,
            percentiles: list, rank_limit: float) -> dict:
    """``rounds[k]`` is the generator's round flushed by ``emissions[k]``
    for k in ``window`` and, where the caller has them, for the warm-up
    rounds just before it; emissions after the window hold stragglers.

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
    hist_wrong = scalar_wrong = late = unaccounted = 0
    ranks = []
    dup = sum(emissions[k].dup for k in tail)
    stray = sum(emissions[k].stray for k in tail)
    for g, grp in enumerate(groups):
        series = int(grp["series"])
        if grp["type"] == "h":
            sent = float(grp["samples"])
            total = np.zeros(series)
            lo = np.full(series, np.inf)
            hi = np.full(series, -np.inf)
            for k in tail:
                cols = emissions[k].cols[g]
                total += np.nan_to_num(cols[SUFFIX_COUNT])
                lo = np.fmin(lo, cols[SUFFIX_MIN])
                hi = np.fmax(hi, cols[SUFFIX_MAX])
            every = np.concatenate([rounds[k].values[g] for k in span],
                                   axis=1)
            bad = (total != sent * len(span))
            unaccounted += int(np.abs(total - sent * len(span)).sum())
            bad |= _f32_differs(lo, every.min(axis=1))
            bad |= _f32_differs(hi, every.max(axis=1))
            hist_wrong += int(bad.sum())
            for k in window:
                cols = emissions[k].cols[g]
                vals = rounds[k].values[g]
                on_time = cols[SUFFIX_COUNT] == sent
                late += int(np.abs(np.nan_to_num(cols[SUFFIX_COUNT])
                                   - sent)[~on_time].sum())
                wrong = _f32_differs(cols[SUFFIX_MIN], vals.min(axis=1))
                wrong |= _f32_differs(cols[SUFFIX_MAX], vals.max(axis=1))
                hist_wrong += int((wrong & on_time & ~bad).sum())
                ordered = np.sort(vals[on_time], axis=1)
                for q in percentiles:
                    x = cols[percentile_suffix(q)][on_time]
                    if len(x):
                        ranks.append(rank_error(ordered, x, q))
        else:
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
            scalar_wrong += int(bad.sum())
            unaccounted += int(bad.sum()) * int(grp["samples"]) * len(span)
            for k in window:
                v = emissions[k].cols[g]["value"]
                want = (rounds[k].values[g].sum(axis=1)
                        if grp["type"] == "c" else rounds[k].last[g])
                off = _f32_differs(v, want)
                late += int((off & ~bad).sum()) * int(grp["samples"])
    ranks = np.concatenate(ranks) if ranks else np.zeros(1)
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
            "hist_rows_wrong": {"value": hist_wrong, "limit": 0},
            "scalar_rows_wrong": {"value": scalar_wrong, "limit": 0},
            "rows_twice_or_stray": {"value": dup + stray, "limit": 0},
            "rank_error_max": {"value": worst_rank, "limit": rank_limit},
            "lines_unaccounted": {"value": unaccounted, "limit": 0},
        },
        "lines_late": late,
    }


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
               percentiles: list, precision: str) -> list:
    """Emissions as the reference itself would post them, every value
    held and summed in ``precision``."""
    suffixes = [SUFFIX_COUNT, SUFFIX_MIN, SUFFIX_MAX] + [
        percentile_suffix(q) for q in percentiles]
    out = [Emission(groups, suffixes) for _ in range(n_flushes)]
    for k in window:
        for g, grp in enumerate(groups):
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
                acc = np.zeros(vals.shape[0])
                for j in range(vals.shape[1]):
                    acc = _cast(acc + vals[:, j], precision)
                cols["value"][:] = acc
            else:
                cols["value"][:] = _cast(rounds[k].last[g], precision)
    return out
