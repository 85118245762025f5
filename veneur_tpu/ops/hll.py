"""Batched dense HyperLogLog as XLA tensor ops.

The reference's ``Set`` sampler wraps the vendored axiomhq/hyperloglog
(``/root/reference/samplers/samplers.go:367-435``): a 2^14-register sketch
whose member-insert takes a 64-bit hash, indexes a register with the top ``p``
bits, and stores the max leading-zero-run (+1) of the remaining bits; merge is
an elementwise register ``max`` and the cardinality estimate is the classic
bias-corrected harmonic mean with linear-counting small-range correction.

Here the state for S series is one dense ``[S, m]`` (``m = 2^p``) int32 tensor
so that:

    * insert   = a scatter-max of (row, register, rho) triples — rho/idx are
      derived from the raw 64-bit hash *on device* from two uint32 halves
      (JAX runs without 64-bit types enabled) using ``lax.clz``;
    * merge    = ``jnp.maximum`` — and across a device mesh, ``pmax`` over ICI,
      which is the whole global-aggregation story for sets
      (cf. ``samplers.Set.Combine/Merge``, ``samplers.go:423-435``);
    * estimate = two row-reductions (harmonic sum + zero count), all series at
      once.

Registers are int32 rather than uint8: TPU vector ops prefer 32-bit lanes and
the value range is [0, 64-p+1].
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

DEFAULT_PRECISION = 14  # axiomhq New() default (hyperloglog.go:31-37)


def num_registers(precision: int = DEFAULT_PRECISION) -> int:
    if not 4 <= precision <= 18:
        raise ValueError("precision must be in [4, 18]")
    return 1 << precision


def init(batch_shape: Sequence[int] = (), precision: int = DEFAULT_PRECISION,
         dtype=jnp.int32) -> jax.Array:
    """Empty register tensors for a batch of series: [..., 2^p] zeros."""
    return jnp.zeros(tuple(batch_shape) + (num_registers(precision),), dtype)


def _clz32(x: jax.Array) -> jax.Array:
    """Count leading zeros of a uint32 array (clz(0) == 32)."""
    return lax.clz(x.astype(jnp.uint32)).astype(jnp.int32)


def idx_rho(hash_hi: jax.Array, hash_lo: jax.Array, precision: int = DEFAULT_PRECISION):
    """Split 64-bit hashes (as two uint32 halves) into (register index, rho).

    Mirrors the reference insert path: idx = top p bits, rho = leading zeros
    of the remaining 64-p bits + 1, capped at 64-p+1.
    """
    p = precision
    hi = hash_hi.astype(jnp.uint32)
    lo = hash_lo.astype(jnp.uint32)
    idx = (hi >> (32 - p)).astype(jnp.int32)
    # rest = (hash << p) in 64 bits, carried as two 32-bit halves.
    top = (hi << p) | (lo >> (32 - p))
    bot = lo << p
    clz = jnp.where(top != 0, _clz32(top), 32 + _clz32(bot))
    rho = jnp.minimum(clz + 1, 64 - p + 1)
    return idx, rho


def insert(registers: jax.Array, rows: jax.Array, hash_hi: jax.Array,
           hash_lo: jax.Array, mask: jax.Array | None = None,
           precision: int = DEFAULT_PRECISION) -> jax.Array:
    """Scatter a flat batch of hashed members into their series' sketches.

    registers: [S, m]; rows/hash_hi/hash_lo: [N] int32/uint32; mask: [N] bool
    (False = padding). Duplicate (row, idx) pairs resolve by max, so the op is
    idempotent and order-free like the reference's register update.
    """
    idx, rho = idx_rho(hash_hi, hash_lo, precision)
    if mask is not None:
        rho = jnp.where(mask, rho, 0)  # rho 0 never beats an existing register
        rows = jnp.where(mask, rows, 0)
        idx = jnp.where(mask, idx, 0)
    return registers.at[rows, idx].max(rho.astype(registers.dtype))


def merge(a: jax.Array, b: jax.Array) -> jax.Array:
    """Elementwise register max — the associative merge (samplers.go:423-435).
    Across a mesh this is simply ``lax.pmax`` on the same tensors."""
    return jnp.maximum(a, b)


def estimate(registers: jax.Array, precision: int = DEFAULT_PRECISION) -> jax.Array:
    """Batched cardinality estimate: [..., m] -> [...] float32.

    Classic HLL estimator with linear-counting small-range correction,
    matching ScalarHLL (the golden model for axiomhq's dense path).
    """
    r = registers.astype(jnp.float32)
    raw_inv = jnp.sum(jnp.exp2(-r), axis=-1)
    zeros = jnp.sum((registers == 0).astype(jnp.float32), axis=-1)
    return _estimate_from(raw_inv, zeros, precision)


def _estimate_from(raw_inv: jax.Array, zeros: jax.Array,
                   precision: int) -> jax.Array:
    """The estimator from a sketch's harmonic sum ``raw_inv`` (the sum
    of 2^-register over all m registers) and its count of empty
    registers."""
    p = precision
    m = float(1 << p)
    if p >= 7:
        alpha = 0.7213 / (1 + 1.079 / m)
    else:
        alpha = {4: 0.673, 5: 0.697, 6: 0.709}[p]
    est = alpha * m * m / raw_inv
    lc = m * jnp.log(m / jnp.maximum(zeros, 1.0))
    use_lc = (est <= 2.5 * m) & (zeros > 0)
    return jnp.where(use_lc, lc, est)


# ---------------------------------------------------------------------------
# Sparse-then-dense registers (upstream's sketch keeps a small set sparse
# too, samplers.go:370-435 over axiomhq): a series holds its filled
# registers as ``[S, E]`` entries ``idx << 8 | rho`` (0 = empty, since
# rho >= 1), unique by register and packed at the front of its row,
# until it has more than E of them; then it takes a dense row of a pool
# ``[P, m]`` (``slot[s]``, -1 while sparse) and its entries are cleared.
# Both forms hold the same registers, so every estimate and every
# register handed out equals the dense sketch's.
# ---------------------------------------------------------------------------

SPARSE_ENTRIES = 128  # E: 512 B a row, 1/32 of a dense row at p = 14
_RHO_BITS = 8
_RHO_MASK = (1 << _RHO_BITS) - 1
# the device's counters of a sparse store: the pool's next free row
# (every promotion takes one, so it counts them), the entries of the
# sparse rows the inserts touched, and the dense registers they wrote
FILL, ENTRIES_TOUCHED, DENSE_WRITES = range(3)
N_COUNTERS = 3
# promoted rows moved into the pool a loop trip
_PROMOTE_BATCH = 64


def entry_key(idx: jax.Array, rho: jax.Array) -> jax.Array:
    return (idx << _RHO_BITS) | rho


def entry_idx(entries: jax.Array) -> jax.Array:
    return entries >> _RHO_BITS


def entry_rho(entries: jax.Array) -> jax.Array:
    return entries & _RHO_MASK


def outgrows(filled: jax.Array, added: jax.Array, width: int) -> jax.Array:
    """Whether a sparse row with ``filled`` registers, ``added`` more,
    needs a dense row."""
    return filled + added > width


def _dense_rows_of(entries: jax.Array, m: int, dtype) -> jax.Array:
    """``[b, E]`` entries -> ``[b, m]`` registers."""
    b = entries.shape[0]
    return jnp.zeros((b, m), dtype).at[
        jnp.arange(b)[:, None], entry_idx(entries)].max(
            entry_rho(entries).astype(dtype))


def sparse_insert(entries: jax.Array, slot: jax.Array, pool: jax.Array,
                  counters: jax.Array, rows: jax.Array, idx: jax.Array,
                  rho: jax.Array):
    """Insert one chunk of (row, register, rho) triples; rows ``>=
    S`` are padding. Returns the four arrays updated.

    The chunk is sorted by (row, register, rho), so a pair's largest rho
    comes last and a row's triples lie together. A dense row takes a
    scatter-max into its pool row. A sparse row's triples either raise
    the entry that holds their register or append after its last; a row
    whose registers would pass E takes the pool's next rows, in chunk
    order, first with its entries and then with the chunk's triples."""
    cap, width = entries.shape
    p_rows = pool.shape[0]
    n = rows.shape[0]
    i32 = jnp.int32
    at = jnp.arange(n, dtype=i32)
    rows, idx, rho = lax.sort((rows.astype(i32), idx.astype(i32),
                               rho.astype(i32)), num_keys=3)
    valid = rows < cap
    none = jnp.full((1,), -1, i32)
    row_end = rows != jnp.concatenate([rows[1:], none])
    row_start = rows != jnp.concatenate([none, rows[:-1]])
    keep = valid & (row_end | (idx != jnp.concatenate([idx[1:], none])))
    safe = jnp.where(valid, rows, 0)
    ent = entries[safe]
    cur = slot[safe]
    sparse = valid & (cur < 0)
    filled = ent != 0
    k = jnp.sum(filled, axis=1, dtype=i32)
    match = filled & (entry_idx(ent) == idx[:, None])
    found = jnp.any(match, axis=1)
    pos = jnp.argmax(match, axis=1).astype(i32)
    # a register new to its sparse row: its rank among the row's new
    # ones, and how many the row gets
    added = (keep & sparse & ~found).astype(i32)
    upto = jnp.cumsum(added)
    first = lax.cummax(jnp.where(row_start, at, 0), axis=0)
    last = lax.cummin(jnp.where(row_end, at, n - 1), axis=0, reverse=True)
    before = upto[first] - added[first]
    rank = upto - added - before
    row_added = upto[last] - before
    need = sparse & outgrows(k, row_added, width)
    promote = need & row_start
    pick = promote.astype(i32)
    new_slot = counters[FILL] + jnp.cumsum(pick) - pick
    n_promote = jnp.sum(pick)
    slot = slot.at[jnp.where(promote, rows, cap)].set(new_slot, mode="drop")
    listed = jnp.nonzero(promote, size=n + _PROMOTE_BATCH,
                         fill_value=0)[0]

    def move(t, carry):
        pool, entries = carry
        sel = lax.dynamic_slice(listed, (t * _PROMOTE_BATCH,),
                                (_PROMOTE_BATCH,))
        ok = t * _PROMOTE_BATCH + jnp.arange(_PROMOTE_BATCH) < n_promote
        moved = ent[sel]
        to = jnp.where(ok, new_slot[sel], p_rows)
        pool = pool.at[to[:, None], entry_idx(moved)].max(
            entry_rho(moved).astype(pool.dtype), mode="drop")
        entries = entries.at[jnp.where(ok, rows[sel], cap)].set(
            0, mode="drop")
        return pool, entries

    trips = (n_promote + _PROMOTE_BATCH - 1) // _PROMOTE_BATCH
    pool, entries = lax.fori_loop(0, trips, move, (pool, entries))
    dense_at = jnp.where(need, new_slot[first], cur)
    to = jnp.where(keep & (dense_at >= 0), dense_at, p_rows)
    pool = pool.at[to, idx].max(rho.astype(pool.dtype), mode="drop")
    stay = keep & sparse & ~need
    entries = entries.at[jnp.where(stay, rows, cap),
                         jnp.where(found, pos, k + rank)].max(
        entry_key(idx, rho), mode="drop")
    touched = jnp.sum(jnp.where(row_start & sparse & ~need, k + row_added,
                                0))
    counters = counters + jnp.stack(
        [n_promote, touched, jnp.sum(to < p_rows, dtype=i32)])
    return entries, slot, pool, counters


def sparse_import(entries: jax.Array, slot: jax.Array, pool: jax.Array,
                  counters: jax.Array, rows: jax.Array, regs: jax.Array):
    """Merge dense register rows ``regs`` ``[B, m]`` into rows ``rows``
    (distinct; ``>= S`` is padding) by elementwise max: a dense row in
    its pool row, a sparse one as its nonzero registers, or in the
    pool's next row where it has more than E of them."""
    cap, width = entries.shape
    p_rows, m = pool.shape
    i32 = jnp.int32
    valid = rows < cap
    safe = jnp.where(valid, rows, 0)
    cur = slot[safe]
    mine = jnp.where((cur >= 0)[:, None],
                     pool[jnp.clip(cur, 0, p_rows - 1)],
                     _dense_rows_of(entries[safe], m, pool.dtype))
    merged = jnp.maximum(mine, regs.astype(pool.dtype))
    nnz = jnp.sum(merged != 0, axis=1, dtype=i32)
    sparse = valid & (cur < 0)
    promote = sparse & outgrows(nnz, 0, width)
    pick = promote.astype(i32)
    new_slot = counters[FILL] + jnp.cumsum(pick) - pick
    to = jnp.where(valid & (cur >= 0), cur,
                   jnp.where(promote, new_slot, p_rows))
    pool = pool.at[to].set(merged, mode="drop")
    slot = slot.at[jnp.where(promote, rows, cap)].set(new_slot, mode="drop")
    spots = jax.vmap(lambda r: jnp.nonzero(r, size=width,
                                           fill_value=m)[0])(merged)
    got = jnp.take_along_axis(merged, jnp.minimum(spots, m - 1), axis=1)
    keys = jnp.where(spots < m, entry_key(spots.astype(i32),
                                          got.astype(i32)), 0)
    entries = entries.at[jnp.where(sparse, rows, cap)].set(
        jnp.where(promote[:, None], 0, keys), mode="drop")
    return entries, slot, pool, counters.at[FILL].add(jnp.sum(pick))


def sparse_estimate(entries: jax.Array, slot: jax.Array, pool: jax.Array,
                    precision: int = DEFAULT_PRECISION):
    """Estimates of rows ``[b]`` as :func:`estimate` gives them for the
    same registers, the live dense rows and the live sparse entries.
    A sparse row with k entries has m - k empty registers, each adding
    1 to the harmonic sum."""
    m = 1 << precision
    filled = entries != 0
    k = jnp.sum(filled, axis=1, dtype=jnp.int32)
    tail = jnp.sum(jnp.where(
        filled, jnp.exp2(-entry_rho(entries).astype(jnp.float32)), 0.0),
        axis=1)
    zeros = (m - k).astype(jnp.float32)
    dense = slot >= 0
    est = jnp.where(dense,
                    estimate(pool, precision)[
                        jnp.clip(slot, 0, pool.shape[0] - 1)],
                    _estimate_from(zeros + tail, zeros, precision))
    return (est, jnp.sum(dense, dtype=jnp.int32),
            jnp.sum(jnp.where(dense, 0, k)))


def sparse_registers(entries: jax.Array, slot: jax.Array,
                     pool: jax.Array) -> jax.Array:
    """Rows ``[b]`` as dense registers ``[b, m]``: the entries
    scattered, the pool rows written over their series' rows."""
    b = entries.shape[0]
    out = _dense_rows_of(entries, pool.shape[1], pool.dtype)
    owner = jnp.full((pool.shape[0],), b, jnp.int32).at[
        jnp.where(slot >= 0, slot, pool.shape[0])].set(
            jnp.arange(b, dtype=jnp.int32), mode="drop")
    return out.at[owner].set(pool, mode="drop")


# ---------------------------------------------------------------------------
# Host-side helpers (not jitted): hashing members to 64-bit values.
# ---------------------------------------------------------------------------

_FNV64_OFFSET = 14695981039346656037
_FNV64_PRIME = 1099511628211
_MASK64 = (1 << 64) - 1


def _fmix64(h: int) -> int:
    """murmur3 64-bit finalizer: full avalanche so every input bit diffuses
    into the top p bits that pick the register."""
    h ^= h >> 33
    h = h * 0xFF51AFD7ED558CCD & _MASK64
    h ^= h >> 33
    h = h * 0xC4CEB9FE1A85EC53 & _MASK64
    h ^= h >> 33
    return h


def hash_member(member: bytes) -> int:
    """64-bit hash of a set member: FNV-1a core + murmur3 finalizer
    (host-side; the reference hashes members with metrohash inside axiomhq —
    any well-mixed 64-bit hash preserves the HLL accuracy contract). FNV-1a
    alone has weak high-bit avalanche for common-prefix names, which are the
    norm for metric members, so the finalizer is required."""
    h = _FNV64_OFFSET
    for byte in member:
        h = (h ^ byte) * _FNV64_PRIME & _MASK64
    return _fmix64(h)


def split_hashes(hashes: np.ndarray):
    """uint64 [N] -> (hi, lo) uint32 halves for device transfer."""
    hashes = np.asarray(hashes, np.uint64)
    hi = (hashes >> np.uint64(32)).astype(np.uint32)
    lo = (hashes & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return hi, lo
