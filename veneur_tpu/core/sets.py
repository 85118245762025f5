"""A set group whose registers are sparse until a series fills them.

The dense ``SetGroup`` (core/store.py) keeps every series as a ``[2^p]``
register row: 16 KiB a series at the reference's p = 14, so a chip holds
some 2^19 set series and a group grows by doubling, a new shape of every
program each time. Upstream's sketch (the vendored axiomhq/hyperloglog
behind ``samplers.Set``, samplers.go:370-435) stays sparse while a set is
small and turns dense once it fills; this group does the same on the
device (ops/hll.py ``sparse_insert``). A store reserved past the dense
group's 4096-row cap, off a mesh, holds its set groups so
(``MetricStore``):

  * ``entries [S, E]`` int32: a series' filled registers, ``idx << 8 |
    rho`` each, 0 where empty. Int32 and not uint32: an entry is under
    2^26 for every precision, and int32 is the TPU's own lane type for
    the sort, the compare and the scatter-max;
  * ``slot [S]`` int32: the series' row of the pool once it has more
    than E registers, -1 while sparse;
  * ``pool [P, 2^p]`` int8: the dense rows, handed out in order; the
    device counts them (``counters``).

Bytes at the sizes a deployment of 2^20 set series reserves (E = 128,
P = 4096, p = 14): entries 512 MiB, slot 4 MiB, pool 64 MiB, about
580 MiB a generation against 16 GiB dense; twice that at the swap. The
planes are placed on the first write, so an idle group holds nothing.

The store is reserved at ``store_initial_capacity`` rows and does not
grow in normal operation. A full pool never drops a register: a series
can need a pool row only once it has had more than E members or
imported registers, so the host counts both a row and grows the pool
(doubling, a new shape, counted in ``pool_grows``) before the dispatch
that could overfill it, with no round trip to the device. Registers in
and out are dense rows, as ``SetGroup`` takes and gives them.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from veneur_tpu.core.store import (DEFAULT_CHUNK, DEFAULT_INITIAL_CAPACITY,
                                   IMPORT_DRAIN_BATCH, Interner, SetGroup,
                                   fetch_stage, live_bucket)
from veneur_tpu.obs import kernels as obs_kernels
from veneur_tpu.obs import recorder as obs_rec
from veneur_tpu.ops import hll as hll_ops

# the pool's first rows: the rows the dense group starts with (64 MiB of
# registers at p = 14), and some 0.3 % of 2^20 series of a Pareto-sized
# fleet, the share past 128 registers an interval (PERF.md section 4)
POOL_ROWS = 4096


def _precision_of(pool) -> int:
    return int(math.log2(pool.shape[-1]))


@partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _sparse_set_insert(entries, slot, pool, counters, rows, hi, lo):
    idx, rho = hll_ops.idx_rho(hi, lo, _precision_of(pool))
    return hll_ops.sparse_insert(entries, slot, pool, counters, rows, idx,
                                 rho)


@partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _sparse_set_import(entries, slot, pool, counters, rows, regs):
    return hll_ops.sparse_import(entries, slot, pool, counters, rows, regs)


@partial(jax.jit, static_argnums=(3,))
def _sparse_set_estimate(entries, slot, pool, rows: int):
    """The first ``rows`` series' estimates (a live bucket), the dense
    ones among them and their sparse entries."""
    return hll_ops.sparse_estimate(entries[:rows], slot[:rows], pool,
                                   _precision_of(pool))


@partial(jax.jit, static_argnums=(3,))
def _sparse_set_registers(entries, slot, pool, rows: int):
    """The first ``rows`` series as dense registers."""
    return hll_ops.sparse_registers(entries[:rows], slot[:rows], pool)


def _merge_repeats(rows: np.ndarray, regs: np.ndarray):
    """One row a series: a batch's imports of one series maxed on the
    host (the device program takes distinct rows)."""
    uniq, inverse = np.unique(rows, return_inverse=True)
    if len(uniq) == len(rows):
        return rows, regs
    out = np.zeros((len(uniq), regs.shape[1]), regs.dtype)
    np.maximum.at(out, inverse, regs)
    return uniq.astype(np.int32), out


class SparseSetGroup(SetGroup):
    """A scope-class of Set metrics, sparse until a series fills (see the
    module docstring). The host side (interning, staging, the two-phase
    flush and snapshot) is ``SetGroup``'s."""

    _DEVICE_STATE = ("entries", "slot", "pool", "counters")

    def __init__(self, capacity: int = DEFAULT_INITIAL_CAPACITY,
                 chunk: int = DEFAULT_CHUNK,
                 precision: int = hll_ops.DEFAULT_PRECISION,
                 dense_rows: int = POOL_ROWS,
                 width: int = hll_ops.SPARSE_ENTRIES):
        self.width = width
        self.dense_rows = dense_rows
        # this generation's: insert dispatches, hashes they carried,
        # pool growths
        self.dispatches = 0
        self.hashes = 0
        self.pool_grows = 0
        super().__init__(capacity, chunk, precision)

    def __getattr__(self, name):
        """Device state is placed on first touch (``DigestGroup``'s
        rule): an idle group, and every fresh twin until its first
        write, holds no accelerator memory. A retired group's names are
        bound to None and never re-placed."""
        if name in SparseSetGroup._DEVICE_STATE:
            self._place()
            return self.__dict__[name]
        raise AttributeError(name)

    def _place(self):
        self.entries = jnp.zeros((self.capacity, self.width), jnp.int32)
        self.slot = jnp.full((self.capacity,), -1, jnp.int32)
        self.pool = jnp.zeros((self.dense_rows, self.m), jnp.int8)
        self.counters = jnp.zeros((hll_ops.N_COUNTERS,), jnp.int32)

    def _placed(self) -> bool:
        return self.__dict__.get("entries") is not None

    def _reset_registers(self):
        for name in SparseSetGroup._DEVICE_STATE:
            self.__dict__.pop(name, None)
        self._device_dirty = False
        # what the host sent the device: (rows, members or imported
        # registers a row) a drain, and their sum (a bound on each row's
        # registers, and on the rows past E)
        self._sent = []
        self._sent_total = 0

    def _grow(self):
        self._drain_staging()
        pad = self.capacity
        self.capacity += pad
        if self._placed():
            self.entries = jnp.pad(self.entries, ((0, pad), (0, 0)))
            self.slot = jnp.pad(self.slot, (0, pad), constant_values=-1)
        self._rows[self._fill:] = self.capacity

    def fresh(self) -> "SparseSetGroup":
        """Empty twin with this generation's capacity and pool rows."""
        return SparseSetGroup(self.capacity, self.chunk, self.precision,
                              self.dense_rows, self.width)

    def _bound_pool(self, rows: np.ndarray, added, total: int) -> None:
        """Note what ``rows`` are about to receive and grow the pool
        first where the rows that could pass E could outnumber its rows.
        A row's registers are at most its members and imported
        registers, so the rows past E among the host's counts bound the
        promotions, and the counts' sum over E + 1 bounds those rows: the
        counts a row are summed only once that bound passes the pool."""
        self._sent.append((rows, added))
        self._sent_total += total
        if self._sent_total // (self.width + 1) <= self.dense_rows:
            return
        seen = np.zeros(self.capacity, np.int64)
        for sent, n in self._sent:
            np.add.at(seen, sent, n)
        held = np.flatnonzero(seen)
        self._sent = [(held, seen[held])]
        need = int(np.count_nonzero(seen > self.width))
        if need <= self.dense_rows:
            return
        grown = self.dense_rows
        while grown < need:
            grown *= 2
        if self._placed():
            self.pool = jnp.pad(self.pool,
                                ((0, grown - self.dense_rows), (0, 0)))
        self.dense_rows = grown
        self.pool_grows += 1

    def _drain_samples(self):
        if self._fill == 0:
            return
        self._device_dirty = True
        rows, hi, lo, fill = self._rows, self._hi, self._lo, self._fill
        self._new_sample_buffers()
        self._bound_pool(rows[:fill], 1, fill)
        self.dispatches += 1
        self.hashes += fill
        with obs_kernels.scope("drain.set.sparse"):
            (self.entries, self.slot, self.pool,
             self.counters) = _sparse_set_insert(
                self.entries, self.slot, self.pool, self.counters,
                jnp.asarray(rows), jnp.asarray(hi), jnp.asarray(lo))

    def _drain_imports(self):
        if not self._imp_rows:
            return
        self._device_dirty = True
        rows, regs = _merge_repeats(
            np.asarray(self._imp_rows, np.int32),
            np.stack(self._imp_regs).astype(np.int8))
        self._imp_rows.clear()
        self._imp_regs.clear()
        nnz = np.count_nonzero(regs, axis=1)
        self._bound_pool(rows, nnz, int(nnz.sum()))
        pad = IMPORT_DRAIN_BATCH - len(rows)
        rows = np.pad(rows, (0, pad), constant_values=self.capacity)
        regs = np.pad(regs, ((0, pad), (0, 0)))
        with obs_kernels.scope("drain.set.sparse"):
            (self.entries, self.slot, self.pool,
             self.counters) = _sparse_set_import(
                self.entries, self.slot, self.pool, self.counters,
                jnp.asarray(rows), jnp.asarray(regs))

    def flush(self, want_estimates: bool = True,
              want_registers: bool = True):
        return self.flush_begin(want_estimates, want_registers)()

    def flush_begin(self, want_estimates: bool = True,
                    want_registers: bool = True):
        """``SetGroup.flush_begin``'s two phases over the live bucket:
        the estimate program and the registers' materialization dispatch
        now, ``finish()`` fetches them with the device's counters. What
        the generation counted goes on the stages as notes (the
        flusher's timeline ``sets``)."""
        with obs_rec.maybe_stage("drain"):
            self._drain_staging()
            if self.dispatches or self._placed():
                obs_rec.note(sets_dispatches=self.dispatches,
                             sets_hashes=self.hashes,
                             sets_pool_grows=self.pool_grows)
        with obs_rec.maybe_stage("compute"):
            n = len(self.interner)
            interner, self.interner = self.interner, Interner()
            if n == 0:
                if self._retired or self._device_dirty:
                    self._release()
                return lambda: (interner, None, None)
            rows = live_bucket(n, self.capacity)
            obs_rec.note(sets_rows_live=n, sets_rows_run=rows)
            with obs_kernels.scope("flush.set.sparse"):
                est_ref = (_sparse_set_estimate(
                    self.entries, self.slot, self.pool, rows)
                    if want_estimates else None)
                reg_ref = self._register_refs(n) if want_registers else None
            counters = self.counters
            self._release()

        def finish():
            with fetch_stage((est_ref, reg_ref, counters)):
                est, regs, ctr = jax.device_get((est_ref, reg_ref,
                                                 counters))
                estimates = registers = None
                if est is not None:
                    estimates = np.asarray(est[0])[:n]
                    obs_rec.note(sets_rows_dense=int(est[1]),
                                 sets_entries_live=int(est[2]))
                if regs is not None:
                    registers = np.asarray(regs, np.uint8)[:n]
            obs_rec.note(
                sets_promotions=int(ctr[hll_ops.FILL]),
                sets_entries_touched=int(ctr[hll_ops.ENTRIES_TOUCHED]),
                sets_dense_writes=int(ctr[hll_ops.DENSE_WRITES]))
            return interner, estimates, registers

        return finish

    def _release(self):
        """After a flush's dispatch: a retired generation lets its
        planes go (the programs' outputs keep what the fetch needs); a
        live one starts over."""
        if self._retired:
            for name in SparseSetGroup._DEVICE_STATE:
                setattr(self, name, None)
            self._device_dirty = False
            return
        self._reset_registers()
        self._init_staging()
        self.dispatches = self.hashes = self.pool_grows = 0

    def _register_refs(self, n: int):
        """The live bucket's registers, dense, interner order."""
        return _sparse_set_registers(self.entries, self.slot, self.pool,
                                     live_bucket(n, self.capacity))
