"""Batched count-min sketch + top-k heavy hitters as dense XLA ops.

BASELINE.md config #5 asks for a streaming heavy-hitter sampler the
reference does not have: count-min (Cormode-Muthukrishnan) for frequency
estimates over an unbounded key space, plus a fixed-size top-k list.
TPU-first design:

- ONE shared ``[depth, width]`` float32 table serves every series: the
  per-row hash mixes the series row id in as a salt, so series never
  need per-series tables (the classic shared-sketch trick). Updates are
  scatter-adds; estimates are a min over ``depth`` gathered rows.
- the top-k list is per series, ``[S, K]`` id/count planes. Each drain
  takes of every series' distinct keys in the batch the 4K with the
  largest estimates (``_batch_candidates``), concatenates (current
  top-k, its counts read again from the table, ++ those candidates),
  deduplicates by id with a sort + segment-head mask (fixed shapes, no
  data-dependent control flow), and keeps the K largest counts via
  ``lax.top_k``.
- keys are 64-bit hashes carried as (hi, lo) uint32 pairs — uint64 is
  unavailable without jax x64 — and every mixing step is a murmur3
  finalizer, matching ops/hll.py's member hashing so the native parser's
  member hash feeds both sketches.

What the list guarantees: estimates are upward-biased only (the
count-min guarantee), so no emitted count is under the key's exact
frequency; and the list is kept by estimate, so a key that is left out
has an exact frequency no larger than the last listed count. (A key's
estimate at its last line is at least its frequency; if it is not among
its batch's 4K candidates, or loses to the standing list, K keys of the
series stand at or above it, and counts only grow until the flush.) An
estimate exceeds the exact frequency by at most e / width of the
table's mass with probability 1 - e^-depth, so a left-out key can
exceed a listed key's *exact* frequency by no more than that. The
golden tests assert both against an exact dict.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

DEFAULT_DEPTH = 4
DEFAULT_WIDTH = 1 << 16
DEFAULT_TOPK = 32

# distinct odd constants per hash row (splitmix64-derived)
_ROW_SALTS = (0x9E3779B9, 0x85EBCA6B, 0xC2B2AE35, 0x27D4EB2F,
              0x165667B1, 0xD3A2646C, 0xFD7046C5, 0xB55A4F09)


class CountMin(NamedTuple):
    """table: [depth, width] f32 shared across series.
    topk_hi/lo: [S, K] uint32 key-id halves (0/0 = empty slot).
    topk_counts: [S, K] f32 estimated counts (0 = empty).
    sids: [S] uint32 INSTANCE-INDEPENDENT series ids (a stable hash of
    name+type+tags) — table columns are salted with these, NOT with the
    local row index, so tables forwarded between instances that interned
    the same series at different rows still align column-for-column."""

    table: jax.Array
    topk_hi: jax.Array
    topk_lo: jax.Array
    topk_counts: jax.Array
    sids: jax.Array

    @property
    def depth(self) -> int:
        return self.table.shape[0]

    @property
    def width(self) -> int:
        return self.table.shape[1]


def init(num_series: int = 1, depth: int = DEFAULT_DEPTH,
         width: int = DEFAULT_WIDTH, k: int = DEFAULT_TOPK) -> CountMin:
    assert depth <= len(_ROW_SALTS)
    return CountMin(
        table=jnp.zeros((depth, width), jnp.float32),
        topk_hi=jnp.zeros((num_series, k), jnp.uint32),
        topk_lo=jnp.zeros((num_series, k), jnp.uint32),
        topk_counts=jnp.zeros((num_series, k), jnp.float32),
        sids=jnp.zeros((num_series,), jnp.uint32),
    )


def _mix32(x: jax.Array) -> jax.Array:
    """murmur3 32-bit finalizer."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def _col_index(sids: jax.Array, hi: jax.Array, lo: jax.Array, salt: int,
               width: int) -> jax.Array:
    """Table column for one depth row: mixes (stable series id, key
    hash, row salt) so one table serves every series and depth row
    independently. The series component MUST be the instance-independent
    sid, never a local row index — forwarded tables merge elementwise
    and both ends have to hash a given (series, key) to the same column."""
    h = _mix32(hi ^ jnp.uint32(salt))
    h = _mix32(h ^ lo)
    h = _mix32(h ^ sids.astype(jnp.uint32) * jnp.uint32(0x9E3779B1))
    return (h % jnp.uint32(width)).astype(jnp.int32)


def update(sk: CountMin, rows: jax.Array, sids: jax.Array, hi: jax.Array,
           lo: jax.Array, counts: jax.Array) -> CountMin:
    """Fold one flat batch of (series row, series sid, key hash, count)
    increments into the table and refresh each touched series' top-k.

    rows: [N] int32; sids: [N] uint32 stable series ids (see CountMin);
    padding uses counts == 0 (its updates add zero and its candidates
    lose every top-k comparison).
    """
    depth, width = sk.depth, sk.width
    s, k = sk.topk_counts.shape
    counts = counts.astype(jnp.float32)
    # teach the sketch its rows' stable ids (idempotent writes)
    sk = sk._replace(sids=sk.sids.at[rows].set(sids, mode="drop"))
    table = sk.table
    idxs = []
    for d in range(depth):
        idx = _col_index(sids, hi, lo, _ROW_SALTS[d], width)
        idxs.append(idx)
        table = table.at[d, idx].add(counts)
    # conservative estimate after the adds: min over depth rows
    est = jnp.full(rows.shape, jnp.inf, jnp.float32)
    for d in range(depth):
        est = jnp.minimum(est, table[d, idxs[d]])
    est = jnp.where(counts > 0, est, 0.0)

    # refresh the standing top-k entries from the table: their counts
    # must track later increments even in a drain that does not bring
    # the key again
    cur_ct = jnp.full(sk.topk_counts.shape, jnp.inf, jnp.float32)
    for d in range(depth):
        idx = _col_index(jnp.broadcast_to(sk.sids[:, None],
                                          sk.topk_hi.shape),
                         sk.topk_hi, sk.topk_lo, _ROW_SALTS[d], width)
        cur_ct = jnp.minimum(cur_ct, table[d, idx])
    cur_ct = jnp.where(sk.topk_counts > 0, cur_ct, 0.0)

    cand_hi, cand_lo, cand_ct = _batch_candidates(rows, hi, lo, est, s,
                                                  4 * k)
    all_hi = jnp.concatenate([sk.topk_hi, cand_hi], axis=1)
    all_lo = jnp.concatenate([sk.topk_lo, cand_lo], axis=1)
    all_ct = jnp.concatenate([cur_ct, cand_ct], axis=1)
    top_hi, top_lo, top_ct = _dedupe_topk(all_hi, all_lo, all_ct, k)
    return sk._replace(table=table, topk_hi=top_hi, topk_lo=top_lo,
                       topk_counts=top_ct)


def _batch_candidates(rows, hi, lo, est, s: int, ring: int):
    """A batch's candidates as ``[s, ring]`` planes: of every series'
    distinct keys in the batch the ``ring`` with the largest estimates,
    the largest first (``est`` 0 marks padding). Two sorts of the flat
    batch and one scatter to (series, rank within the series): fixed
    shapes, and no two candidates meet in a slot."""
    srows = jnp.where(est > 0, rows, s).astype(jnp.int32)
    # the lines of one (series, key) carry one estimate (the table
    # after the batch's adds): keep the first of each run
    r, h, lw, e = lax.sort((srows, hi, lo, est), dimension=-1, num_keys=3,
                           is_stable=False)
    dup = jnp.concatenate(
        [jnp.zeros((1,), bool),
         (r[1:] == r[:-1]) & (h[1:] == h[:-1]) & (lw[1:] == lw[:-1])])
    r, neg, h, lw = lax.sort((r, -jnp.where(dup, 0.0, e), h, lw),
                             dimension=-1, num_keys=2, is_stable=False)
    at = jnp.arange(r.shape[0], dtype=jnp.int32)
    head = jnp.concatenate([jnp.ones((1,), bool), r[1:] != r[:-1]])
    rank = at - lax.cummax(jnp.where(head, at, 0), axis=0)
    to = jnp.where((neg < 0) & (rank < ring), r, s)
    blank = jnp.zeros((s, ring), jnp.uint32)
    return (blank.at[to, rank].set(h, mode="drop"),
            blank.at[to, rank].set(lw, mode="drop"),
            jnp.zeros((s, ring), jnp.float32).at[to, rank].set(
                -neg, mode="drop"))


def _dedupe_topk(all_hi, all_lo, all_ct, k: int):
    """Per-series candidate selection: sort by (hi, lo), keep each id's
    max count at its first occurrence, zero the duplicates, take top k."""
    shi, slo, sct = lax.sort((all_hi, all_lo, all_ct), dimension=-1,
                             num_keys=2, is_stable=False)
    same = jnp.concatenate(
        [jnp.zeros_like(shi[:, :1], bool),
         (shi[:, 1:] == shi[:, :-1]) & (slo[:, 1:] == slo[:, :-1])], axis=1)
    # max count within each equal-id run, propagated left to the head
    run_max = _rev_seg_max(sct, same)
    sct = jnp.where(same, 0.0, run_max)
    sct = jnp.where((shi == 0) & (slo == 0), 0.0, sct)  # empty slots
    top_ct, top_i = lax.top_k(sct, k)
    top_hi = jnp.take_along_axis(shi, top_i, axis=1)
    top_lo = jnp.take_along_axis(slo, top_i, axis=1)
    live = top_ct > 0
    return (jnp.where(live, top_hi, 0), jnp.where(live, top_lo, 0), top_ct)


def add_table(sk: CountMin, table: jax.Array) -> CountMin:
    """Merge another instance's count-min table: elementwise add (the
    sketch is additively mergeable — columns align across instances
    because both hash with stable sids), then refresh every standing
    top-k member's estimate against the combined table — a forwarded
    table can raise counts for keys this instance already tracks."""
    table = sk.table + table.astype(jnp.float32)
    cur_ct = jnp.full(sk.topk_counts.shape, jnp.inf, jnp.float32)
    for d in range(sk.depth):
        idx = _col_index(jnp.broadcast_to(sk.sids[:, None],
                                          sk.topk_hi.shape),
                         sk.topk_hi, sk.topk_lo, _ROW_SALTS[d],
                         sk.width)
        cur_ct = jnp.minimum(cur_ct, table[d, idx])
    cur_ct = jnp.where(sk.topk_counts > 0, cur_ct, 0.0)
    return sk._replace(table=table, topk_counts=cur_ct)


def inject_candidates(sk: CountMin, rows: jax.Array, sids: jax.Array,
                      hi: jax.Array, lo: jax.Array,
                      slots: jax.Array) -> CountMin:
    """Offer forwarded top-k candidates (no count contribution — their
    mass arrived via add_table): estimate each against the current table
    and merge into the per-series top-k lists.

    rows: [N] int32 with out-of-range = padding; sids: [N] uint32 stable
    series ids; (hi, lo) == (0, 0) is also padding. slots: [N] int32,
    the candidate's index within its series' forwarded list — callers
    know it exactly (a forwarded list has at most K entries), which
    makes the scatter collision-free without any ring hashing."""
    s, k = sk.topk_counts.shape
    live = (rows >= 0) & (rows < s) & ((hi != 0) | (lo != 0))
    sk = sk._replace(sids=sk.sids.at[rows].set(sids, mode="drop"))
    est = jnp.full(rows.shape, jnp.inf, jnp.float32)
    for d in range(sk.depth):
        idx = _col_index(sids, hi, lo, _ROW_SALTS[d], sk.width)
        est = jnp.minimum(est, sk.table[d, idx])
    est = jnp.where(live, est, 0.0)
    ring = k
    srows = jnp.where(live, rows, s).astype(jnp.int32)
    slot = jnp.minimum(slots.astype(jnp.int32), ring - 1)
    cand_hi = jnp.zeros((s, ring), jnp.uint32).at[srows, slot].set(
        hi, mode="drop")
    cand_lo = jnp.zeros((s, ring), jnp.uint32).at[srows, slot].set(
        lo, mode="drop")
    cand_ct = jnp.zeros((s, ring), jnp.float32).at[srows, slot].set(
        est, mode="drop")
    all_hi = jnp.concatenate([sk.topk_hi, cand_hi], axis=1)
    all_lo = jnp.concatenate([sk.topk_lo, cand_lo], axis=1)
    all_ct = jnp.concatenate([sk.topk_counts, cand_ct], axis=1)
    top_hi, top_lo, top_ct = _dedupe_topk(all_hi, all_lo, all_ct, k)
    return sk._replace(topk_hi=top_hi, topk_lo=top_lo,
                       topk_counts=top_ct)


def _rev_seg_max(x: jax.Array, same: jax.Array) -> jax.Array:
    """Per segment (runs where ``same`` is True continue the previous
    element's segment), the max of the whole run written at every element,
    via a right-to-left log-step segmented scan.

    same[i] says element i belongs to i-1's segment; prop[i] tracks
    whether position i can absorb from i+1 (initially same[i+1]), and
    composes as prop'[i] = prop[i] & prop[i+d] so absorption never
    crosses a segment boundary."""
    def shl(a, d, fill):
        pad = jnp.full(a.shape[:-1] + (d,), fill, a.dtype)
        return jnp.concatenate([a[:, d:], pad], axis=1)

    n = x.shape[-1]
    prop = shl(same, 1, False)
    val = x
    d = 1
    while d < n:
        val = jnp.where(prop, jnp.maximum(val, shl(val, d, 0.0)), val)
        prop = prop & shl(prop, d, False)
        d *= 2
    return val


def estimate(sk: CountMin, rows: jax.Array, hi: jax.Array,
             lo: jax.Array) -> jax.Array:
    """Point-query frequency estimates for (series row, key) pairs;
    rows resolve to stable sids through the sketch's sid plane."""
    sids = sk.sids[jnp.clip(rows, 0, sk.sids.shape[0] - 1)]
    est = jnp.full(rows.shape, jnp.inf, jnp.float32)
    for d in range(sk.depth):
        idx = _col_index(sids, hi, lo, _ROW_SALTS[d], sk.width)
        est = jnp.minimum(est, sk.table[d, idx])
    return est
