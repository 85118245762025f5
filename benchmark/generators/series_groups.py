"""The general traffic generator: groups of series, each series with a
number of samples an interval.

A traffic mix is a data file (``benchmark/traffic/<name>.json``) that
names this generator and gives its parameters:

    guard_s         seconds at the end of the interval in which nothing
                    is due, so that no line straddles a tick
    sockets         sender sockets (SO_REUSEPORT lanes hash the source)
    datagram_bytes  most bytes of one datagram
    groups          [{prefix, type, series, samples, values}]: ``series``
                    series named ``<prefix><i>``, each with ``samples``
                    lines ``<name>:<value>|<type>`` every interval;
                    ``values`` names a distribution below

Every interval (a *round*) holds the same names and the same number of
lines, whatever the seed; the seed draws the values and the order. All
values are exact in float32 and print as short decimals, so that the
reference can hold min, max and gauges to equality.
"""

from __future__ import annotations

import numpy as np


def _quarters(rng, shape, p):
    return rng.integers(0, int(p["high"]), size=shape) / 4.0


def _integers(rng, shape, p):
    return rng.integers(int(p["low"]), int(p["high"]),
                        size=shape).astype(np.float64)


def _lognormal_64ths(rng, shape, p):
    raw = rng.lognormal(p["mu"], p["sigma"], size=shape)
    scale = rng.uniform(p["scale_low"], p["scale_high"],
                        size=(shape[0], 1))
    return np.floor(raw * scale * 64.0) / 64.0


DISTRIBUTIONS = {"quarters": _quarters, "integers": _integers,
                 "lognormal_64ths": _lognormal_64ths}


def _text(values: np.ndarray, kind: str) -> list:
    if kind == "c":
        return [b"%d" % v for v in values.astype(np.int64).ravel()]
    return [repr(v).encode() for v in values.ravel().tolist()]


def _pack(lines: list, limit: int) -> list:
    """Greedy newline-joined datagrams of at most ``limit`` bytes:
    [(payload, n_lines)]."""
    out, cur, size = [], [], 0
    for ln in lines:
        if cur and size + 1 + len(ln) > limit:
            out.append((b"\n".join(cur), len(cur)))
            cur, size = [], 0
        cur.append(ln)
        size += len(ln) + (1 if size else 0)
    if cur:
        out.append((b"\n".join(cur), len(cur)))
    return out


class Round:
    """One interval's lines as ``units``, the datagrams ``(payload,
    lines)`` in send order, with what was sent to each series:
    ``values[g]`` is ``[series, samples]`` float64 and ``last[g]`` the
    sample of each series that is sent last."""

    def __init__(self, units, values, last, lines):
        self.units = units
        self.values = values
        self.last = last
        self.lines = lines


def build(params: dict, seed: int, index: int) -> Round:
    """Round ``index`` of the mix under ``seed``."""
    rng = np.random.default_rng([int(seed), int(index)])
    lines, values, spans = [], [], []
    for g in params["groups"]:
        shape = (int(g["series"]), int(g["samples"]))
        vals = DISTRIBUTIONS[g["values"]["dist"]](rng, shape, g["values"])
        texts = _text(vals, g["type"])
        prefix, kind = g["prefix"].encode(), g["type"].encode()
        start = len(lines)
        k = 0
        for i in range(shape[0]):
            head = prefix + b"%d:" % i
            for _ in range(shape[1]):
                lines.append(head + texts[k] + b"|" + kind)
                k += 1
        values.append(vals)
        spans.append((start, shape))
    perm = rng.permutation(len(lines))
    position = np.empty(len(lines), dtype=np.int64)
    position[perm] = np.arange(len(lines))
    last = []
    for vals, (start, shape) in zip(values, spans):
        pos = position[start:start + shape[0] * shape[1]].reshape(shape)
        last.append(vals[np.arange(shape[0]), pos.argmax(axis=1)])
    datagrams = _pack([lines[j] for j in perm],
                      int(params["datagram_bytes"]))
    return Round(datagrams, values, last, len(lines))


def warm_lines(params: dict) -> list:
    """One line of every metric type the mix uses, on names of its own."""
    kinds = sorted({g["type"] for g in params["groups"]})
    return [(b"\n".join(b"bench.warm.%s:1|%s" % (k.encode(), k.encode())
                        for k in kinds), len(kinds))]
