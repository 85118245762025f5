"""From the receiver's log to emissions, and from emissions to the
end-to-end metrics. Everything here is read from outside the server:
the bodies it posted, the clock at their last byte, the sender's own
schedule and the flush timeline's wall clock for each tick."""

from __future__ import annotations

import re
import zlib

import numpy as np

from benchmark import kinds

SUFFIX_COUNT, SUFFIX_MIN, SUFFIX_MAX = "count", "min", "max"


def percentile_suffix(q: float) -> str:
    return "%gpercentile" % (q * 100.0)


# The sink writes a body in either of two hands: its native encoder's,
# with nothing between the tokens and whole seconds, and the standard
# library's, with a space after each colon and comma and seconds as a
# float (the groups that only a global has go that way).
POINT_STAMP = re.compile(rb'"points": ?\[\[(\d+)[.,]')


def assign_emissions(bodies: list, ticks: list) -> list:
    """Index of the flush (by its ``wall_start`` in ``ticks``, ascending)
    that each body belongs to. A series body carries its flush's own
    timestamp in every point: bodies with one timestamp are one emission,
    and the emission is the flush in whose span its *first* body came
    (flushes are synchronous in the server's loop). So a body that the
    sink could not deliver and posts again an interval later still
    belongs to its own emission: it is late there, and the emission's
    last stamp says so. A body with no point goes by its own stamp;
    bodies before the first tick get -1."""
    ticks = np.array(ticks)
    keys, first = [], {}
    for stamp, _path, encoding, raw in bodies:
        # the first point is in the first few hundred bytes
        head = (zlib.decompressobj().decompress(raw, 4096)
                if encoding == "deflate" else raw[:4096])
        m = POINT_STAMP.search(head)
        key = m.group(1) if m else None
        keys.append(key)
        if key is not None:
            first[key] = min(first.get(key, stamp), stamp)
    return [int(np.searchsorted(ticks, first.get(key, body[0]), "right")) - 1
            for key, body in zip(keys, bodies)]


class Emission:
    """One flush's rows for the mix's groups: ``cols[g]`` is what the
    group's kind keeps of them (``kinds/<kind>.py`` ``table``; for a
    rectangle of series ``cols[g][suffix]`` is a ``[series]`` float64
    array, NaN where the sink got no row, ``"value"`` for a row with no
    suffix). ``dup`` counts rows seen twice, ``stray`` rows outside a
    group. ``flushes`` is how many emissions the run has: a kind whose
    names grow with the rounds sizes its columns from it."""

    def __init__(self, groups: list, percentiles: list, flushes: int = 1):
        self.cols = [kinds.of(g).table(g, percentiles, flushes)
                     for g in groups]
        self.dup = 0
        self.stray = 0
        self.rows = 0
        self.last_stamp = None
        self.bodies = 0


def histogram_suffixes(percentiles: list) -> list:
    """The rows of a histogram series that this instance aggregates."""
    return [SUFFIX_COUNT, SUFFIX_MIN, SUFFIX_MAX] + [
        percentile_suffix(q) for q in percentiles]


def rectangle(series: int, suffixes: list) -> dict:
    """Empty columns of ``series`` rows, one a suffix."""
    return {s: np.full(int(series), np.nan) for s in suffixes}


def land_rectangle(em: Emission, cols: dict, idx: np.ndarray,
                   suf: np.ndarray, val: np.ndarray) -> None:
    """Rows ``<prefix><idx>.<suffix>`` into the columns of their
    suffixes (``"value"``: none); a row past the columns' end, or with a
    suffix that is no column, is stray."""
    inside = idx < len(next(iter(cols.values())))
    known = np.zeros(len(idx), dtype=bool)
    for s, col in cols.items():
        m = inside & (suf == (b"" if s == "value" else s.encode()))
        put(em, col, idx[m], val[m])
        known |= m
    em.stray += int((~known).sum())


def parse(bodies: list, owner: list, n_flushes: int, groups: list,
          percentiles: list, interval_s: float) -> list:
    """Decompress and parse every series body into one ``Emission`` a
    flush. Rows of type ``rate`` (counters and a histogram's ``count``)
    come back from rates to counts: the sink divides them by the
    interval (sinks/datadog.py ``_serialize_block``). A row is
    ``<prefix><i>[.suffix]`` with its value and the text of its ``tags``
    array (made into an array only for a kind that asks: ``tags()``);
    where it lands is its group's kind's to say."""
    out = [Emission(groups, percentiles, n_flushes)
           for _ in range(n_flushes)]
    prefixes = [g["prefix"].encode() for g in groups]
    pattern = re.compile(
        rb'"metric": ?"(' + b"|".join(re.escape(p) for p in prefixes)
        + rb')(\d+)(?:\.([\w.]+))?", ?"points": ?\[\[[\d.]+, ?([^\]]+)\]\]'
        rb'(?:, ?"tags": ?\[([^\]]*)\])?, ?"type": ?"(\w+)"')
    index = {p: i for i, p in enumerate(prefixes)}
    land = [kinds.of(g).land for g in groups]
    for (stamp, path, encoding, raw), k in zip(bodies, owner):
        if k < 0 or k >= n_flushes:
            continue
        em = out[k]
        em.bodies += 1
        em.last_stamp = stamp if em.last_stamp is None else max(
            em.last_stamp, stamp)
        if "/series" not in path:
            continue
        text = zlib.decompress(raw) if encoding == "deflate" else raw
        found = pattern.findall(text)
        if not found:
            continue
        em.rows += len(found)
        gi = np.array([index[f[0]] for f in found])
        idx = np.array([f[1] for f in found], dtype=np.int64)
        val = np.array([f[3] for f in found], dtype=np.float64)
        rate = np.array([f[5] == b"rate" for f in found])
        val[rate] = np.round(val[rate] * interval_s)
        suf = np.array([f[2] for f in found])
        for g, grp in enumerate(groups):
            rows = np.flatnonzero(gi == g)
            if not len(rows):
                continue
            if len(rows) == len(found):       # the whole body is one group's
                rows = slice(None)
            land[g](em, em.cols[g], grp, idx[rows], suf[rows],
                    lambda: np.array([f[4] for f in found])[rows], val[rows])
    return out


def put(em: Emission, col: np.ndarray, idx: np.ndarray,
         val: np.ndarray) -> None:
    em.dup += int((~np.isnan(col[idx])).sum()) + len(idx) - len(
        np.unique(idx))
    col[idx] = val


def lines_in(em: Emission, groups: list, sent=None) -> int:
    """Lines an emission accounts for, read from outside; each group's
    kind says what its rows stand for (a histogram row's ``count`` is
    its lines). ``sent`` is the round that the emission flushed, where
    the caller has it: a row of a series that gets another number of
    lines every round stands for what that round sent it."""
    return sum(kinds.of(grp).lines_in(
        cols, grp, None if sent is None else sent.values[g])
        for g, (grp, cols) in enumerate(zip(groups, em.cols)))


def weighted_quantile(values: np.ndarray, weights: np.ndarray,
                      q: float) -> float:
    order = np.argsort(values)
    cum = np.cumsum(weights[order])
    return float(values[order][np.searchsorted(cum, q * cum[-1])])


def end_to_end(send_log: list, emissions: list, ticks: list,
               window: range, groups: list, carried_in: int = 0,
               rounds: dict = None) -> dict:
    """The measures taken from outside. Flush to last body: over the
    window's flushes, the last body's stamp minus the tick. Line age:
    over all lines sent in the window, the emission's stamp minus the
    line's due time; which emission holds a line is the emissions' line
    totals, cumulated, against the send order. ``carried_in`` lines of
    the warm-up rounds missed their tick and stand first in the window's
    emissions: they are no window line's. A line no emission holds waits
    for ever. ``rounds[k]`` is the round that emission k flushed
    (``lines_in``)."""
    rounds = rounds or {}
    lags = [emissions[k].last_stamp - ticks[k] for k in window
            if emissions[k].last_stamp is not None]
    due = np.array([d for d, _s, _n in send_log])
    n = np.array([n for _d, _s, n in send_log], dtype=np.float64)
    sent_before = np.cumsum(n) - n        # lines sent before this datagram
    # emissions from the window's first flush on, stragglers included
    held = np.cumsum([lines_in(emissions[k], groups, rounds.get(k))
                      for k in range(window.start, len(emissions))])
    held = np.maximum(held - carried_in, 0)
    which = np.searchsorted(held, sent_before, "right")
    stamps = np.array([emissions[k].last_stamp or np.inf
                       for k in range(window.start, len(emissions))]
                      + [np.inf])
    age = stamps[np.minimum(which, len(stamps) - 1)] - due
    return {"measures": {
                "flush_to_last_body_mean_s": (float(np.mean(lags)) if lags
                                              else float("inf")),
                "line_age_p95_s": weighted_quantile(age, n, 0.95),
                "line_age_p50_s": weighted_quantile(age, n, 0.50)},
            "flush_to_last_body_each_s": lags,
            "lines_sent": int(n.sum()),
            "lines_held": int(held[-1]) if len(held) else 0}
