"""Top-k (heavy hitters): ``<prefix><i>:k<member>|s|#veneurtopk`` lines;
a series' rows are ``<prefix><i>.topk`` counters tagged ``key:k<member>``,
at most ``topk_k`` of them: the members the program's count-min table
and per-series list hold as the most frequent of the interval.

    prefix, type    names ``<prefix><i>``; ``s``
    series          streams, every one sent every round
    lines           lines a round over all of them, each stream an
                    equal part (``series`` divides it)
    members         keys ``k<j>``, ``j`` below this
    zipf_s          a line names the key of rank r with probability
                    ~ r^-zipf_s; which key has which rank is a stream's
                    own (a rotation drawn from the seed) and stays

The reference is every member's exact frequency, per stream and round.
The configuration states the table: ``server.topk_width``,
``topk_depth``, ``topk_k`` (the program's defaults where it does not),
whose documented guarantee is an overcount of at most e / ``topk_width``
of the weight the shared table took in the interval (the round's top-k
lines), with probability 1 - e^-``topk_depth``. Compared:

    topk_undercount     rows below the exact frequency: limit 0 (a
                        count-min never undercounts)
    topk_overcount_max  the largest (emitted - exact) as a share of that
                        weight, against the configuration's
                        ``topk_overcount_limit``, or the documented
                        e / width where it states none
    topk_missed         members not emitted whose exact frequency
                        exceeds, by more than the documented overcount,
                        that of the stream's last row by emitted count
                        (of the rows tied there, the most frequent; any
                        member at all where the stream has fewer than
                        ``topk_k`` rows): limit 0

A row for a member the stream never held, a row past ``topk_k`` of a
stream, a row seen twice and a top-k row in an emission after the
window are stray; a stream of the window with no row leaves its lines
unaccounted for. The rows of a stream stand for its lines of the round.

The control (no float precision decides a count): one line in ten
never reaches the table.
"""

from __future__ import annotations

import math
import re

import numpy as np

from benchmark.generators.groups_by_kind import zipf_ranks
from benchmark.lib.reference import cast

SUFFIX = b"topk"
KEY = re.compile(rb'"key:k(\d+)"')
ROTATION = 0x70B4           # salt of the seed sequence of the streams' keys
LEFT_OUT = 10
DEFAULT_K, DEFAULT_WIDTH = 32, 1 << 16          # veneur_tpu/config.py


def per_stream(group: dict) -> int:
    lines, series = int(group["lines"]), int(group["series"])
    if lines % series:
        raise ValueError(f"{group['prefix']}: {series} streams do not "
                         f"divide {lines} lines")
    return lines // series


def generate(group: dict, rng, seed: int, index: int) -> tuple:
    series, members = int(group["series"]), int(group["members"])
    turn = np.random.default_rng([seed, ROTATION]).integers(
        members, size=(series, 1))
    keys = (zipf_ranks(rng, members, group["zipf_s"],
                       (series, per_stream(group))) + turn) % members
    prefix = group["prefix"].encode()
    lines = [prefix + b"%d:k%d|s|#veneurtopk" % pair for pair in zip(
        np.repeat(np.arange(series), keys.shape[1]).tolist(),
        keys.ravel().tolist())]
    return lines, keys


def settle(group: dict, keys: np.ndarray, position: np.ndarray) -> tuple:
    return keys, None


def lines_a_round(group: dict) -> int:
    return int(group["lines"])


def warm_line(group: dict) -> bytes:
    return b"bench.warm.t:1|s|#veneurtopk"


def live_series(group: dict) -> int:
    return int(group["series"])


def table(group: dict, percentiles: list, flushes: int) -> dict:
    return {"series": [], "member": [], "value": []}


def land(em, cols, group, idx, suf, tags, val) -> None:
    found = [KEY.search(tag) for tag in tags().tolist()]
    member = np.array([int(m.group(1)) if m else -1 for m in found],
                      dtype=np.int64)
    ok = (idx < int(group["series"])) & (suf == SUFFIX) & (member >= 0)
    em.stray += int((~ok).sum())
    cols["series"].append(idx[ok])
    cols["member"].append(member[ok])
    cols["value"].append(val[ok])


def rows_of(cols: dict) -> tuple:
    return tuple(np.concatenate(cols[name]) if cols[name]
                 else np.zeros(0, dtype=kind) for name, kind in (
                     ("series", np.int64), ("member", np.int64),
                     ("value", np.float64)))


def lines_in(cols: dict, group: dict, sent=None) -> int:
    return len(np.unique(rows_of(cols)[0])) * per_stream(group)


def frequencies(keys: np.ndarray, members: int) -> tuple:
    """Every (stream, member) a round sent, as ``stream x members +
    member`` ascending, with how often."""
    return np.unique(keys + np.arange(len(keys))[:, None] * members,
                     return_counts=True)


def compare(t, mine, emissions, rounds, window, span, tail, groups,
            percentiles, limits, sent) -> None:
    server = limits.get("server", {})
    most = int(server.get("topk_k", DEFAULT_K))
    documented = math.e / int(server.get("topk_width", DEFAULT_WIDTH))
    under, over, missed = 0, 0.0, 0
    for k in tail:
        if k not in window:
            if k >= window.stop:
                t["stray"] += sum(len(rows_of(emissions[k].cols[g])[0])
                                  for g in mine)
            continue
        weight = sum(rounds[k].values[g].size for g in mine)
        for g in mine:
            series, members = (int(groups[g]["series"]),
                               int(groups[g]["members"]))
            sent_key, freq = frequencies(rounds[k].values[g], members)
            stream, member, val = rows_of(emissions[k].cols[g])
            got_key, first = np.unique(stream * members + member,
                                       return_index=True)
            t["stray"] += len(stream) - len(got_key)
            stream, val = stream[first], val[first]
            at = np.minimum(np.searchsorted(sent_key, got_key),
                            len(sent_key) - 1)
            held = sent_key[at] == got_key
            t["stray"] += int((~held).sum())
            got_key, stream, val, exact = (got_key[held], stream[held],
                                           val[held], freq[at][held])
            rows = np.bincount(stream, minlength=series)
            t["stray"] += int(np.maximum(rows - most, 0).sum())
            t["unaccounted"] += int((rows == 0).sum()) * per_stream(
                groups[g])
            under += int((val < exact).sum())
            over = max(over, float(np.max(val - exact, initial=0.0))
                       / weight)
            # what a member left out has to exceed: the exact frequency
            # of the stream's last row by emitted count (of those tied
            # there, the most frequent) and the documented overcount,
            # where the stream's list is full; nothing where it is not
            order = np.lexsort((-exact, val, stream))
            streams, head = np.unique(stream[order], return_index=True)
            full = rows[streams] >= most
            least = np.zeros(series)
            least[streams[full]] = (exact[order[head]][full]
                                    + documented * weight)
            out = ~np.isin(sent_key, got_key)
            missed += int((out & (freq > least[sent_key // members])).sum())
    t["numbers"]["topk_undercount"] = {"value": under, "limit": 0}
    t["numbers"]["topk_overcount_max"] = {
        "value": over,
        "limit": float(limits.get("topk_overcount_limit", documented))}
    t["numbers"]["topk_missed"] = {"value": missed, "limit": 0}


def synthesize(out, mine, rounds, window, groups, percentiles, precision,
               moved, control, limits) -> None:
    most = int(limits.get("server", {}).get("topk_k", DEFAULT_K))
    for k in window:
        for g in mine:
            keys, members = rounds[k].values[g], int(groups[g]["members"])
            if control:
                keys = keys[:, np.arange(keys.shape[1]) % LEFT_OUT
                            != LEFT_OUT - 1]
            sent_key, freq = frequencies(keys, members)
            stream = sent_key // members
            # the most frequent first within a stream, the lower key
            # where two are as frequent
            order = np.lexsort((sent_key, -freq, stream))
            rank = np.arange(len(order)) - np.searchsorted(
                stream[order], stream[order])
            top = order[rank < most]
            cols = out[k].cols[g]
            cols["series"].append(stream[top])
            cols["member"].append(sent_key[top] % members)
            cols["value"].append(cast(freq[top].astype(np.float64),
                                      precision))
