"""The plain reference and the comparison that decides ``correct``.

The reference is float64 NumPy over the lines the generator sent; it
imports nothing of ``veneur_tpu`` and takes nothing the server made.
``compare`` holds one run's emissions against it and returns every
number compared beside its limit. ``synthesize`` is the reference put
in the program's place (at float64 it has to pass; in a lower
precision, or with ``control``, it is the control and has to fail).
What a group owes, and what the reference posts for it, is its kind's
to say (``benchmark/kinds/``); here are the tallies the kinds add to
and the measures they share.
"""

from __future__ import annotations

import numpy as np

from benchmark import kinds
from benchmark.lib.emissions import Emission

# The bands of samples an interval by which the rank error's largest
# reading is reported beside the worst: a lone sample, a few, tens,
# hundreds, a thousand and more.
BANDS = ((1, 1), (2, 15), (16, 127), (128, 1023), (1024, None))


def synthetic_clock(k: int) -> tuple:
    """``(start, span_s, interval_s)`` of round k where no run was sent:
    the reference in the program's place, and the tests."""
    return (1000.0 * k, 900.0, 1000.0)


def rank_error(samples_sorted: np.ndarray, x: np.ndarray, q: float,
               n=None) -> np.ndarray:
    """Per series: how far ``q`` lies outside the rank interval of the
    emitted value ``x`` among that series' samples (0 inside it). The
    measure ``tests/test_tpu_smoke.py`` holds to 0.02 (as the smoke did
    that PR 35 deleted). A value that is not finite reads 1. Where series differ in
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


def f32_differs(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    with np.errstate(invalid="ignore"):
        return ~(got.astype(np.float32) == want.astype(np.float32))


def compare(emissions: list, rounds: dict, window: range, groups: list,
            percentiles: list, limits: dict, sent: dict = None) -> dict:
    """``rounds[k]`` is the generator's round flushed by ``emissions[k]``
    for k in ``window`` and, where the caller has them, for the warm-up
    rounds just before it; emissions after the window hold stragglers.
    ``limits`` is the configuration, which states them
    (``rank_error_limit`` and what a kind reads beside it). ``sent[k]``
    is ``(start, span_s, interval_s)`` as round k was sent (groups fed
    by forwards read the order of two forwards from it).

    A series whose ``count`` (or whose counter or gauge row) in one
    emission is not what the round sent is *late* there if the run's
    totals still account for every line: late is not wrong, and the
    wait shows in the lines' age. Rows that are on time are held
    to the round exactly; totals are held over the whole run, from the
    first round given, so that a warm-up line that slips into the window
    is late too, and not one line too many. ``lines_unaccounted`` is
    what those totals miss or have over, in lines.

    Every kind adds to the same tallies ``t``: ``hist_wrong``,
    ``scalar_wrong``, ``stray``, ``late``, ``unaccounted``; ``ranks``
    (arrays of rank errors) and ``banded`` (``(samples, rank errors)``
    of the readings whose series differ in their samples an interval);
    ``numbers``, what a kind compares beside these, each ``{"value",
    "limit"}`` under a name of its own.
    """
    span = range(min(rounds), window.stop)
    tail = range(span.start, len(emissions))
    t = {"hist_wrong": 0, "scalar_wrong": 0, "late": 0, "unaccounted": 0,
         "ranks": [], "banded": [], "numbers": {},
         "stray": sum(emissions[k].dup + emissions[k].stray for k in tail)}
    sent = sent or {k: synthetic_clock(k) for k in span}
    for kind, mine in kinds.by_kind(groups):
        kind.compare(t, mine, emissions, rounds, window, span, tail, groups,
                     percentiles, limits, sent)
    ranks = np.concatenate(t["ranks"]) if t["ranks"] else np.zeros(1)
    worst_rank = float(ranks.max())
    rank_errors = {"readings": len(ranks),
                   "mean": float(ranks.mean()),
                   "p99": float(np.quantile(ranks, 0.99)),
                   "p999": float(np.quantile(ranks, 0.999)),
                   "over_0.02": int((ranks > 0.02).sum())}
    if t["banded"]:
        rank_errors["by_band"] = by_band(t["banded"])
    return {
        # beside the worst, which is compared: the steadier readings a
        # later limit could stand on (PERF.md, open questions)
        "rank_errors": rank_errors,
        "numbers": {
            "hist_rows_wrong": {"value": t["hist_wrong"], "limit": 0},
            "scalar_rows_wrong": {"value": t["scalar_wrong"], "limit": 0},
            "rows_twice_or_stray": {"value": t["stray"], "limit": 0},
            "rank_error_max": {"value": worst_rank,
                               "limit": float(limits["rank_error_limit"])},
            "lines_unaccounted": {"value": t["unaccounted"], "limit": 0},
            **t["numbers"],
        },
        "lines_late": t["late"],
    }


def by_band(banded: list) -> dict:
    """The largest rank error, and how many readings, by the samples an
    interval sent the series (``BANDS``)."""
    n = np.concatenate([b[0] for b in banded])
    err = np.concatenate([b[1] for b in banded])
    out = {}
    for low, high in BANDS:
        inside = (n >= low) & (n <= (high or n.max(initial=low)))
        out[str(low) if high == low else f"{low}-{high}" if high
            else f"{low}+"] = {
            "readings": int(inside.sum()),
            "max": float(err[inside].max(initial=0.0))}
    return out


# -- the reference in the program's place ---------------------------------


def cast(values: np.ndarray, precision: str) -> np.ndarray:
    if precision == "float64":
        return values
    if precision == "float32":
        return values.astype(np.float32).astype(np.float64)
    if precision == "bfloat16":
        import ml_dtypes

        return values.astype(ml_dtypes.bfloat16).astype(np.float64)
    raise ValueError(f"no precision {precision!r}")


def synthesize(rounds: dict, window: range, n_flushes: int, groups: list,
               percentiles: list, precision: str, moved: dict = None,
               control: bool = False, limits: dict = None) -> list:
    """Emissions as the reference itself would post them, every value
    held and summed in ``precision``. Of groups fed by forwards emission k
    holds the messages of round k that are due before its tick and those
    of the round before that were due after it; ``moved`` puts single
    messages elsewhere: ``{(round, slot): emission}``. ``control``: a
    kind whose numbers no float precision decides breaks its own
    guarantee instead (``kinds/<kind>.py`` says which). ``limits`` is
    the configuration, for a kind whose rows depend on what it states."""
    out = [Emission(groups, percentiles, n_flushes)
           for _ in range(n_flushes)]
    for kind, mine in kinds.by_kind(groups):
        kind.synthesize(out, mine, rounds, window, groups, percentiles,
                        precision, moved or {}, control, limits or {})
    return out


def sum_in(vals: np.ndarray, precision: str) -> np.ndarray:
    """Along each row, one term after the other, NaN for no term."""
    acc = np.zeros(vals.shape[0])
    for j in range(vals.shape[1]):
        acc = cast(acc + np.nan_to_num(vals[:, j]), precision)
    return acc
