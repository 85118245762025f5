"""Sets: ``<prefix><i>:m<member>|s`` lines; a series' row is the
estimate of how many distinct members its interval held (the program's
HyperLogLog), a gauge with no suffix.

    prefix, type    names ``<prefix><i>``; ``s``
    series          series, every one sent every round
    members         ``low``, ``high``, ``tail``: series i holds the
                    i / (series - 1) quantile of a Pareto law of shape
                    ``tail`` bounded to ``[low, high]`` distinct members:
                    heavy-tailed sizes, the same for every seed
    repeats         a series with d distinct members gets d + floor(
                    repeats x d) lines: the extra ones repeat a member

The seed draws the members (so the sketch's hashes) and the order. The
reference is the exact number of distinct members. Compared:
``set_error_max``, the worst |emitted - exact| / max(exact, 128) over the
window's rows, against the configuration's ``set_error_limit`` (kept
beside ``set_error_documented``, the sketch's standard error). Under 128
members (the root of the sketch's 2^14 registers) the error is taken in
members: two of d members share a register with probability d^2 / 2^15,
the estimate then reads one member short, and as a share of 5 members
that is 0.2 in a sound run (CPU, correctness, PR 39);
``set_rows_wrong``, limit 0: a series of the window with no row, or a
set row in an emission after the window (a set's line that comes late
cannot be told from a wrong estimate). A row for a series the group has
not is stray. A row stands for the lines its round sent that series.

The control (no float precision decides an estimate): one member in
ten is left out of what is counted.
"""

from __future__ import annotations

import numpy as np

from benchmark.lib.emissions import land_rectangle, rectangle
from benchmark.lib.reference import cast

LEFT_OUT = 10
SMALL = 128.0


def distinct(group: dict) -> np.ndarray:
    """``[series]``: the distinct members of each series, a round."""
    p = group["members"]
    low, high, a = float(p["low"]), float(p["high"]), float(p["tail"])
    u = np.arange(int(group["series"])) / max(int(group["series"]) - 1, 1)
    return np.floor(low / (1.0 - u * (1.0 - (low / high) ** a))
                    ** (1.0 / a)).astype(np.int64)


def lines_of(group: dict) -> np.ndarray:
    d = distinct(group)
    return d + np.floor(float(group["repeats"]) * d).astype(np.int64)


def generate(group: dict, rng, seed: int, index: int) -> tuple:
    d = distinct(group)
    extra = lines_of(group) - d
    series = np.arange(len(d))
    base = rng.integers(0, 2**31, size=len(d))
    first = np.repeat(base, d) + np.arange(d.sum()) - np.repeat(
        np.cumsum(d) - d, d)
    again = np.repeat(base, extra) + np.floor(
        rng.random(extra.sum()) * np.repeat(d, extra)).astype(np.int64)
    prefix = group["prefix"].encode()
    lines = [prefix + b"%d:m%d|s" % pair for pair in zip(
        np.concatenate([np.repeat(series, d),
                        np.repeat(series, extra)]).tolist(),
        np.concatenate([first, again]).tolist())]
    return lines, d.astype(np.float64)


def settle(group: dict, exact: np.ndarray, position: np.ndarray) -> tuple:
    return exact, None


def lines_a_round(group: dict) -> int:
    return int(lines_of(group).sum())


def warm_line(group: dict) -> bytes:
    return b"bench.warm.s:1|s"


def live_series(group: dict) -> int:
    return int(group["series"])


def table(group: dict, percentiles: list, flushes: int) -> dict:
    return rectangle(group["series"], ["value"])


def land(em, cols, group, idx, suf, tags, val) -> None:
    land_rectangle(em, cols, idx, suf, val)


def lines_in(cols: dict, group: dict, sent=None) -> int:
    return int(lines_of(group)[~np.isnan(cols["value"])].sum())


def compare(t, mine, emissions, rounds, window, span, tail, groups,
            percentiles, limits, sent) -> None:
    worst, wrong = 0.0, 0
    for g in mine:
        lines = lines_of(groups[g])
        for k in tail:
            v = emissions[k].cols[g]["value"]
            if k in window:
                missing = np.isnan(v)
                wrong += int(missing.sum())
                t["unaccounted"] += int(lines[missing].sum())
                exact = rounds[k].values[g]
                worst = max(worst, float(np.nanmax(
                    np.abs(v - exact) / np.maximum(exact, SMALL),
                    initial=0.0)))
            elif k >= window.stop:
                wrong += int((~np.isnan(v)).sum())
    t["numbers"]["set_error_max"] = {
        "value": worst, "limit": float(limits["set_error_limit"])}
    t["numbers"]["set_rows_wrong"] = {"value": wrong, "limit": 0}


def synthesize(out, mine, rounds, window, groups, percentiles, precision,
               moved, control, limits) -> None:
    for k in window:
        for g in mine:
            exact = rounds[k].values[g]
            if control:
                exact = exact - np.floor(exact / LEFT_OUT)
            out[k].cols[g]["value"][:] = cast(exact, precision)
