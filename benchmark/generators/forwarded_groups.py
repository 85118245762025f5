"""The traffic of a global tier: what a fleet of locals forwards.

A traffic mix (``benchmark/traffic/<name>.json``) that names this
generator gives:

    guard_s          seconds at the end of the interval in which no
                     forward is due that is meant for this tick
    forwarders       how many locals forward, each over its own channel
    stagger          true: forwarder ``f`` is due ``f / forwarders`` of
                     the way through the span (hosts whose ticks are not
                     aligned); false: all at its start
    late_share       share of the forwarders (the last ones) whose
                     forward is due *after* the global's tick, so that it
                     belongs to the next emission
    late_after_s     how long after the tick the first of those is due;
                     the next ones follow at the same distance
    message_metrics  most series entries of one ``MetricList``; a
                     forwarder's interval is split into as many messages
    compression      the t-digest compression a local states
    groups           [{prefix, type, series, fan_in, samples, values}]:
                     ``series`` series named ``<prefix><i>``, ``i`` with
                     leading zeros to the width of the last; in every
                     interval ``fan_in`` of the forwarders report each
                     of them, a histogram (``h``) as a t-digest of the
                     forwarder's own ``samples`` samples, a counter
                     (``c``) or a gauge (``g``) as one value. One more
                     group ``{prefix, type: "c", marker: true, series}``
                     names the counter that every message carries with
                     the number of its entries (itself among them), one
                     name a forwarder and message: it is how the
                     emissions say, from outside, which of them holds a
                     forward. ``series`` there is forwarders x messages.

A forwarder sends what a local with upstream's defaults sends: a
histogram of mixed scope as ``MergingDigestData`` whose centroids are
its samples, each of weight 1 (``samples`` is at most the compression,
so that is a valid digest and the harness needs no t-digest code), a
counter and a gauge of global scope; in a message the histograms come
first, then the counters, the gauges and the marker. The bytes are
upstream's schema (``forwardrpc/forward.proto``,
``samplers/metricpb/metric.proto``, ``tdigest/tdigest.proto``), written
here a group at a time as rows of one byte matrix; nothing of
``veneur_tpu`` is imported.

Every interval (a *round*) holds the same names, the same number of
messages and the same number of entries in each, whatever the seed; the
seed draws the values, which forwarders report a series, and which of a
forwarder's messages holds an entry. Values are exact in float32, as the
other generator's; a forwarder's samples of a series have a scale of
their own, as hosts do.
"""

from __future__ import annotations

import numpy as np

from benchmark.generators.series_groups import DISTRIBUTIONS

# metricpb.Scope's Global as field 9 (Mixed is the enum's zero and is
# not written)
SCOPE_GLOBAL = b"\x48\x02"


# -- the encoder: proto3 wire format, the fields a local writes -----------


def _varint(n: int) -> bytes:
    out = bytearray()
    while n >= 0x80:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _doubles(x: np.ndarray) -> np.ndarray:
    """``[...]`` float64 to ``[..., 8]`` bytes, little endian."""
    return np.ascontiguousarray(x, dtype="<f8").view(np.uint8).reshape(
        x.shape + (8,))


def _const(text: bytes, rows: int) -> np.ndarray:
    return np.tile(np.frombuffer(text, dtype=np.uint8), (rows, 1))


def _name_width(series: int) -> int:
    return len(str(int(series) - 1))


def _rows(metric_parts: list, prefix: bytes, width: int,
          idx: np.ndarray) -> np.ndarray:
    """``[entries, length]`` bytes, one ``MetricList.metrics`` entry a
    row: field 1 of the list, the metric's length, its name (field 1)
    and ``metric_parts``, the byte columns of what follows the name."""
    rows = len(idx)
    digits = (idx[:, None] // 10 ** np.arange(width - 1, -1, -1)) % 10 + 48
    body = sum(p.shape[1] for p in metric_parts)
    name = b"\x0a" + _varint(len(prefix) + width) + prefix
    head = b"\x0a" + _varint(len(name) + width + body) + name
    return np.concatenate([_const(head, rows), digits.astype(np.uint8)]
                          + metric_parts, axis=1)


def _histogram_parts(samples: np.ndarray, compression: float) -> list:
    """``samples`` is ``[entries, samples]``, sorted along each row.
    type = 3; histogram = 7 {t_digest = 1 {main_centroids = 1 {mean = 1,
    weight = 2}, compression = 2, min = 3, max = 4}}."""
    rows, n = samples.shape
    cents = np.empty((rows, n, 20), dtype=np.uint8)
    cents[:, :, :3] = (0x0A, 18, 0x09)
    cents[:, :, 3:11] = _doubles(samples)
    cents[:, :, 11] = 0x11
    cents[:, :, 12:] = _doubles(np.ones(1))
    tail = np.empty((rows, 27), dtype=np.uint8)
    tail[:, 0], tail[:, 9], tail[:, 18] = 0x11, 0x19, 0x21
    tail[:, 1:9] = _doubles(np.array([compression]))
    tail[:, 10:18] = _doubles(samples[:, 0])
    tail[:, 19:] = _doubles(samples[:, -1])
    digest = 20 * n + 27
    inner = b"\x0a" + _varint(digest)
    head = b"\x18\x02\x3a" + _varint(len(inner) + digest) + inner
    return [_const(head, rows), cents.reshape(rows, 20 * n), tail]


def _gauge_parts(values: np.ndarray) -> list:
    """type = 3; gauge = 6 {value = 1, a double}; scope = 9."""
    rows = len(values)
    return [_const(b"\x18\x01\x32\x09\x09", rows), _doubles(values),
            _const(SCOPE_GLOBAL, rows)]


def _varint_length(values: np.ndarray) -> np.ndarray:
    return 1 + sum((values >= 1 << 7 * k).astype(np.int64)
                   for k in range(1, 10))


def _counter_parts(values: np.ndarray, length: int) -> list:
    """counter = 5 {value = 1, an int64}; scope = 9. The type is the
    enum's zero. ``values`` all have varints of ``length`` bytes."""
    rows = len(values)
    shifts = 7 * np.arange(length)
    digits = (values[:, None] >> shifts) & 0x7F
    digits[:, :-1] |= 0x80
    return [_const(b"\x2a" + _varint(1 + length) + b"\x08", rows),
            digits.astype(np.uint8), _const(SCOPE_GLOBAL, rows)]


def _blocks(group: dict, idx: np.ndarray, values: np.ndarray,
            compression: float) -> list:
    """[(which entries, their rows)] for the entries ``idx`` (series
    numbers) of one group: a block holds rows of one length."""
    prefix = group["prefix"].encode()
    width = _name_width(group["series"])
    every = np.arange(len(idx))
    if group["type"] == "h":
        return [(every, _rows(_histogram_parts(np.sort(values, axis=1),
                                               compression),
                              prefix, width, idx))]
    if group["type"] == "g":
        return [(every, _rows(_gauge_parts(values), prefix, width, idx))]
    values = values.astype(np.int64)
    if (values < 0).any():
        raise ValueError("a counter below nought")
    lengths = _varint_length(values)
    return [(every[lengths == n],
             _rows(_counter_parts(values[lengths == n], int(n)), prefix,
                   width, idx[lengths == n]))
            for n in np.unique(lengths)]


# -- the schedule ---------------------------------------------------------


def _late_forwarders(params: dict) -> int:
    return int(round(float(params.get("late_share", 0.0))
                     * int(params["forwarders"])))


def _due(params: dict, forwarder: int) -> tuple:
    """(share of the span, seconds after the tick): a forwarder's place
    in the interval. The second is ``None`` for a forward that is meant
    for this tick."""
    n = int(params["forwarders"])
    first_late = n - _late_forwarders(params)
    if forwarder >= first_late:
        return (1.0, float(params["late_after_s"])
                * (1 + forwarder - first_late))
    return (forwarder / n if params.get("stagger") else 0.0, None)


def _who(params: dict, group: dict, rng) -> np.ndarray:
    """``[series, fan_in]``: the forwarders that report each series this
    interval, evenly spaced round the ring from a start the seed draws,
    so that every forwarder holds the same number of entries in every
    interval."""
    n, fan_in = int(params["forwarders"]), int(group["fan_in"])
    if fan_in > n:
        raise ValueError(f"fan_in {fan_in} over {n} forwarders")
    first = np.arange(int(group["series"])) + int(rng.integers(n))
    return (first[:, None] + np.arange(fan_in) * (n // fan_in)) % n


def messages_per_forwarder(params: dict) -> int:
    n = int(params["forwarders"])
    most = max(sum(-(-int(g["series"]) * int(g["fan_in"]) // n)
                   for g in params["groups"] if not g.get("marker")), 1)
    return -(-most // int(params["message_metrics"]))


class Round:
    """One interval's forwards. ``units``: one tuple a message,
    ``(payload, entries, forwarder, share of the span, seconds after the
    tick or None)``, in the order in which they are due. For the
    reference: ``values[g]`` is ``[series, fan_in, samples]`` (a
    histogram) or ``[series, fan_in]``; ``slot[g]`` ``[series, fan_in]``
    the message (forwarder x messages + ordinal) that holds the entry;
    ``entries[slot]`` a message's entries, ``late[slot]`` whether it is
    due after the tick, ``due(...)[slot]`` the clock at which it is."""

    def __init__(self, units, values, slot, entries, share, after):
        self.units = units
        self.values = values
        self.slot = slot
        self.entries = entries
        self.late = ~np.isnan(after)
        self.lines = int(entries.sum())
        self._share, self._after = share, after

    def due(self, start: float, span_s: float,
            interval_s: float) -> np.ndarray:
        """As the feed works it out from a unit."""
        return np.where(self.late, start + interval_s + self._after,
                        start + span_s * self._share)


def build(params: dict, seed: int, index: int) -> Round:
    """Round ``index`` of the mix under ``seed``."""
    rng = np.random.default_rng([int(seed), int(index)])
    n = int(params["forwarders"])
    per = messages_per_forwarder(params)
    cap = int(params["message_metrics"])
    compression = float(params["compression"])
    groups = params["groups"]
    markers = [g for g, grp in enumerate(groups) if grp.get("marker")]
    if len(markers) != 1 or int(groups[markers[0]]["series"]) != n * per:
        raise ValueError(
            f"the mix needs one marker group of {n} forwarders x {per} "
            f"messages = {n * per} series: nothing else says which "
            "emission holds a forward")
    values, who = [], []
    for grp in groups:
        if grp.get("marker"):
            values.append(None)
            who.append(np.empty((0, 0), dtype=np.int64))
            continue
        series, fan_in = int(grp["series"]), int(grp["fan_in"])
        who.append(_who(params, grp, rng))
        dist = DISTRIBUTIONS[grp["values"]["dist"]]
        if grp["type"] == "h":
            samples = int(grp["samples"])
            if samples > compression:
                raise ValueError("samples over the compression: the "
                                 "centroids would be no valid digest")
            values.append(dist(rng, (series * fan_in, samples),
                               grp["values"]).reshape(series, fan_in,
                                                      samples))
        else:
            values.append(dist(rng, (series, fan_in), grp["values"]))
    # which of its forwarder's messages holds an entry: the seed's order
    forwarder = np.concatenate([w.ravel() for w in who])
    shuffled = np.lexsort((rng.permutation(len(forwarder)), forwarder))
    starts = np.cumsum(np.bincount(forwarder, minlength=n))
    rank = np.empty(len(forwarder), dtype=np.int64)
    rank[shuffled] = (np.arange(len(forwarder))
                      - (starts - np.bincount(forwarder, minlength=n)
                         )[forwarder[shuffled]])
    slot_of = forwarder * per + rank // cap
    entries = np.bincount(slot_of, minlength=n * per) + 1
    slot, chunks, at = [], [[] for _ in range(n * per)], 0
    for grp, vals, w in zip(groups, values, who):
        if grp.get("marker"):
            slot.append(None)
            continue
        mine = slot_of[at:at + w.size]
        at += w.size
        slot.append(mine.reshape(w.shape))
        idx = np.repeat(np.arange(w.shape[0]), w.shape[1])
        flat = vals.reshape((w.size,) + vals.shape[2:])
        for which, rows in _blocks(grp, idx, flat, compression):
            by_slot = np.argsort(mine[which], kind="stable")
            rows = rows[by_slot]
            ends = np.searchsorted(mine[which][by_slot],
                                   np.arange(n * per + 1))
            for s in range(n * per):
                if ends[s + 1] > ends[s]:
                    chunks[s].append(rows[ends[s]:ends[s + 1]].tobytes())
    for which, rows in _blocks(groups[markers[0]], np.arange(n * per),
                               entries, compression):
        for s, row in zip(which, rows):
            chunks[s].append(row.tobytes())
    due = [_due(params, s // per) for s in range(n * per)]
    place = np.array([share + (after or 0.0) for share, after in due])
    units = [(b"".join(chunks[s]), int(entries[s]), int(s) // per) + due[s]
             for s in np.argsort(place, kind="stable")]
    return Round(units, values, slot, entries,
                 np.array([share for share, _after in due]),
                 np.array([np.nan if after is None else after
                           for _share, after in due]))


def warm_lines(params: dict) -> list:
    """One message with every metric type the mix uses, on names of its
    own, from the first forwarder."""
    kinds = sorted({g["type"] for g in params["groups"]})
    rows = [_blocks({"prefix": "bench.warm." + k + ".", "type": k,
                     "series": 1}, np.zeros(1, dtype=np.int64),
                    np.ones((1, 1) if k == "h" else 1),
                    float(params["compression"]))[0][1].tobytes()
            for k in kinds]
    return [(b"".join(rows), len(kinds), 0, 0.0, None)]
