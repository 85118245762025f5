"""The generator of a mix whose groups are not all rectangles: each
group's lines of a round are its kind's to draw (``benchmark/kinds/``,
``generate`` and ``settle``; a group names its ``kind``, and one that
names none is a rectangle as ``series_groups.py`` builds it). The mix
gives, beside its ``groups``:

    guard_s         seconds at the end of the interval in which nothing
                    is due, so that no line straddles a tick
    sockets         sender sockets (SO_REUSEPORT lanes hash the source)
    datagram_bytes  most bytes of one datagram

Every round holds the same number of lines of every group, whatever the
seed; the seed draws which series get them, the values and the order,
which is one shuffle of all the round's lines. Values are exact in
float32.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark import kinds
from benchmark.generators.series_groups import Round, _pack


@functools.lru_cache(maxsize=4)
def zipf_cdf(universe: int, s: float) -> np.ndarray:
    """``[universe]``: the chance that a draw falls on rank r or a lower
    one, rank r drawn with probability ~ r^-s (0.99: YCSB's constant)."""
    mass = np.arange(1, universe + 1, dtype=np.float64) ** -s
    return np.cumsum(mass) / mass.sum()


def zipf_ranks(rng, universe: int, s: float, shape) -> np.ndarray:
    """Ranks from 0, drawn by the Zipf law over ``universe`` ranks."""
    return np.searchsorted(zipf_cdf(int(universe), float(s)),
                           rng.random(shape))


def build(params: dict, seed: int, index: int) -> Round:
    """Round ``index`` of the mix under ``seed``: ``values[g]`` and
    ``last[g]`` are what group g's kind keeps for the reference."""
    rng = np.random.default_rng([int(seed), int(index)])
    lines, sent, spans = [], [], []
    for group in params["groups"]:
        texts, record = kinds.of(group).generate(group, rng, int(seed),
                                                 int(index))
        spans.append((len(lines), len(texts)))
        lines += texts
        sent.append(record)
    perm = rng.permutation(len(lines))
    position = np.empty(len(lines), dtype=np.int64)
    position[perm] = np.arange(len(lines))
    settled = [kinds.of(group).settle(group, record,
                                      position[start:start + n])
               for group, record, (start, n) in zip(params["groups"], sent,
                                                    spans)]
    datagrams = _pack([lines[j] for j in perm],
                      int(params["datagram_bytes"]))
    return Round(datagrams, [v for v, _last in settled],
                 [last for _v, last in settled], len(lines))


def warm_lines(params: dict) -> list:
    """One line of every metric type the mix uses, on names of its own."""
    lines = sorted({kinds.of(g).warm_line(g) for g in params["groups"]})
    return [(b"\n".join(lines), len(lines))]
