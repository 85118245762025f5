"""From the receiver's log to emissions, and from emissions to the
end-to-end metrics. Everything here is read from outside the server:
the bodies it posted, the clock at their last byte, the sender's own
schedule and the flush timeline's wall clock for each tick."""

from __future__ import annotations

import re
import zlib

import numpy as np

SUFFIX_COUNT, SUFFIX_MIN, SUFFIX_MAX = "count", "min", "max"


def percentile_suffix(q: float) -> str:
    return "%gpercentile" % (q * 100.0)


def forwarded(group: dict) -> bool:
    """Whether the group's series reach the server as forwards of other
    instances: it states how many forwarders report a series
    (``fan_in``), or it is their messages' ``marker``."""
    return "fan_in" in group or bool(group.get("marker"))


def columns(group: dict, percentiles: list) -> list:
    """The rows a series of the group has in an emission. A histogram
    that was forwarded with mixed scope has its percentiles only: count,
    min and max are the forwarders' own to emit."""
    if group["type"] != "h":
        return ["value"]
    local = [] if forwarded(group) else [SUFFIX_COUNT, SUFFIX_MIN,
                                         SUFFIX_MAX]
    return local + [percentile_suffix(q) for q in percentiles]


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
    """One flush's rows for the mix's groups. For a histogram group
    ``cols[suffix]`` is a ``[series]`` float64 array, NaN where the sink
    got no row; for a counter or a gauge group ``cols["value"]``.
    ``dup`` counts rows seen twice, ``stray`` rows outside a group."""

    def __init__(self, groups: list, percentiles: list):
        self.cols = [{s: np.full(int(g["series"]), np.nan)
                      for s in columns(g, percentiles)} for g in groups]
        self.dup = 0
        self.stray = 0
        self.rows = 0
        self.last_stamp = None
        self.bodies = 0


def parse(bodies: list, owner: list, n_flushes: int, groups: list,
          percentiles: list, interval_s: float) -> list:
    """Decompress and parse every series body into one ``Emission`` a
    flush. Rows of type ``rate`` (counters and a histogram's ``count``)
    come back from rates to counts: the sink divides them by the
    interval (sinks/datadog.py ``_serialize_block``)."""
    out = [Emission(groups, percentiles) for _ in range(n_flushes)]
    prefixes = [g["prefix"].encode() for g in groups]
    pattern = re.compile(
        rb'"metric": ?"(' + b"|".join(re.escape(p) for p in prefixes)
        + rb')(\d+)(?:\.([\w.]+))?", ?"points": ?\[\[[\d.]+, ?([^\]]+)\]\]'
        rb'(?:, ?"tags": ?\[[^\]]*\])?, ?"type": ?"(\w+)"')
    index = {p: i for i, p in enumerate(prefixes)}
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
        rate = np.array([f[4] == b"rate" for f in found])
        val[rate] = np.round(val[rate] * interval_s)
        suf = np.array([f[2] for f in found])
        for g, grp in enumerate(groups):
            mine = gi == g
            if not mine.any():
                continue
            inside = mine & (idx < int(grp["series"]))
            em.stray += int((mine & ~inside).sum())
            if grp["type"] == "h":
                for s, col in em.cols[g].items():
                    m = inside & (suf == s.encode())
                    _put(em, col, idx[m], val[m])
                known = np.isin(suf, [s.encode() for s in em.cols[g]])
                em.stray += int((inside & ~known).sum())
            else:
                m = inside & (suf == b"")
                _put(em, em.cols[g]["value"], idx[m], val[m])
                em.stray += int((inside & (suf != b"")).sum())
    return out


def _put(em: Emission, col: np.ndarray, idx: np.ndarray,
         val: np.ndarray) -> None:
    em.dup += int((~np.isnan(col[idx])).sum()) + len(idx) - len(
        np.unique(idx))
    col[idx] = val


def lines_in(em: Emission, groups: list) -> int:
    """Lines an emission accounts for, read from outside: a histogram
    row's ``count`` is its lines; a counter or gauge row stands for the
    one line a round sends that series. Of forwarded groups the marker's
    rows alone say it: each carries the entries of the messages it
    stands for, and an entry is such a group's line."""
    total = 0
    for g, cols in zip(groups, em.cols):
        if forwarded(g):
            if g.get("marker"):
                total += int(np.nansum(cols["value"]))
        elif g["type"] == "h":
            total += int(np.nansum(cols[SUFFIX_COUNT]))
        else:
            total += int((~np.isnan(cols["value"])).sum()) * int(
                g["samples"])
    return total


def weighted_quantile(values: np.ndarray, weights: np.ndarray,
                      q: float) -> float:
    order = np.argsort(values)
    cum = np.cumsum(weights[order])
    return float(values[order][np.searchsorted(cum, q * cum[-1])])


def end_to_end(send_log: list, emissions: list, ticks: list,
               window: range, groups: list, carried_in: int = 0) -> dict:
    """The measures taken from outside. Flush to last body: over the
    window's flushes, the last body's stamp minus the tick. Line age:
    over all lines sent in the window, the emission's stamp minus the
    line's due time; which emission holds a line is the emissions' line
    totals, cumulated, against the send order. ``carried_in`` lines of
    the warm-up rounds missed their tick and stand first in the window's
    emissions: they are no window line's. A line no emission holds waits
    for ever."""
    lags = [emissions[k].last_stamp - ticks[k] for k in window
            if emissions[k].last_stamp is not None]
    due = np.array([d for d, _s, _n in send_log])
    n = np.array([n for _d, _s, n in send_log], dtype=np.float64)
    sent_before = np.cumsum(n) - n        # lines sent before this datagram
    # emissions from the window's first flush on, stragglers included
    held = np.cumsum([lines_in(emissions[k], groups)
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
