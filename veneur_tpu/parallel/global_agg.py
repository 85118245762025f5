"""The sharded global-aggregator interval step.

This is the multi-chip form of the reference's global veneur: N forwarding
hosts deliver sketch contributions each interval, the global tier merges
them and emits fleet-wide percentiles / cardinalities / totals
(``importsrv/server.go:101-132`` + ``flusher.go:26-132``, behavior; the
mechanics are re-designed for a TPU mesh).

Layout (see ``parallel/mesh.py``): a 2-D ``(series, hosts)`` mesh. Metric
series are sharded over the ``series`` axis — each device owns a contiguous
slab of rows, the analogue of one reference worker's sampler map
(``worker.go:54-91``). Per-host contributions are sharded over the ``hosts``
axis and replicated across series shards; every device filters the incoming
flat chunks down to its own row range (out-of-range rows scatter with
``mode='drop'``), accumulates locally, and one ``psum``/``pmax`` per state
kind completes the fleet-wide merge over ICI. No host↔device chatter happens
inside the interval: ingest is scatter-shaped, merge is collective-shaped,
flush is a batched quantile/estimate gather.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from veneur_tpu.ops import hll as hll_ops
from veneur_tpu.ops import tdigest as td_ops
from veneur_tpu.ops.tdigest import TDigest
from veneur_tpu.parallel import collectives
from veneur_tpu.parallel.mesh import HOSTS_AXIS, SERIES_AXIS


class AggState(NamedTuple):
    """Device-resident global-tier state, all sharded over the series axis."""

    digest: TDigest          # [S, K] histogram/timer sketch state
    registers: jax.Array     # [S, m] HLL registers (int32)
    counters: jax.Array      # [S] int32 totals


class HostBatch(NamedTuple):
    """One interval's per-host contributions, sharded over the hosts axis.

    Flat padded chunks; padding rows must equal ``num_series`` (they drop in
    the scatter). Every leading dim is the total host count H.
    """

    h_rows: jax.Array        # [H, N] int32 histogram sample rows
    h_vals: jax.Array        # [H, N] float32 values
    h_wts: jax.Array         # [H, N] float32 weights (0 = padding)
    s_rows: jax.Array        # [H, M] int32 set rows
    s_hi: jax.Array          # [H, M] uint32 member-hash high halves
    s_lo: jax.Array          # [H, M] uint32 low halves
    c_rows: jax.Array        # [H, C] int32 counter rows
    c_incs: jax.Array        # [H, C] int32 increments (0 = padding)


class GlobalAggregator:
    """Compiles and runs the sharded interval step on a fleet mesh."""

    def __init__(self, mesh: Mesh, num_series: int,
                 compression: float = td_ops.DEFAULT_COMPRESSION,
                 precision: int = hll_ops.DEFAULT_PRECISION):
        self.mesh = mesh
        self.series_devices = mesh.shape[SERIES_AXIS]
        self.hosts = mesh.shape[HOSTS_AXIS]
        if num_series % self.series_devices != 0:
            raise ValueError(
                f"num_series={num_series} must divide over "
                f"{self.series_devices} series shards")
        self.num_series = num_series
        self.compression = compression
        self.precision = precision
        self.k = td_ops.size_bound(compression)
        self.m = hll_ops.num_registers(precision)

        s = P(SERIES_AXIS)
        sk = P(SERIES_AXIS, None)
        h = P(HOSTS_AXIS, None)
        state_spec = AggState(
            digest=TDigest(mean=sk, weight=sk, min=s, max=s),
            registers=sk, counters=s)
        batch_spec = HostBatch(*([h] * 8))

        self._step = jax.jit(
            shard_map(
                self._local_step, mesh=mesh,
                in_specs=(state_spec, batch_spec, P(None)),
                out_specs=(state_spec, sk, s, s),
                check_vma=False),
            donate_argnums=(0,))

        # the forwarded-digest butterfly merge, compiled once (calling
        # jax.jit on a fresh closure per flush would retrace every interval)
        hk = P(HOSTS_AXIS, None, None)
        hs = P(HOSTS_AXIS, None)

        def _merge_local(mean, weight, mins, maxs):
            d = TDigest(mean=mean[0], weight=weight[0], min=mins[0],
                        max=maxs[0])
            d = collectives.allmerge_digest(d, HOSTS_AXIS, self.hosts,
                                            self.compression)
            return d.mean, d.weight, d.min, d.max

        self._merge_forwarded = jax.jit(shard_map(
            _merge_local, mesh=mesh,
            in_specs=(hk, hk, hs, hs),
            out_specs=(P(None, None), P(None, None), P(None), P(None)),
            check_vma=False))

    # -- state construction -------------------------------------------------

    def init_state(self) -> AggState:
        sharding_sk = NamedSharding(self.mesh, P(SERIES_AXIS, None))
        sharding_s = NamedSharding(self.mesh, P(SERIES_AXIS))
        s, k, m = self.num_series, self.k, self.m
        return AggState(
            digest=TDigest(
                mean=jax.device_put(jnp.full((s, k), jnp.inf, jnp.float32),
                                    sharding_sk),
                weight=jax.device_put(jnp.zeros((s, k), jnp.float32),
                                      sharding_sk),
                min=jax.device_put(jnp.full((s,), jnp.inf, jnp.float32),
                                   sharding_s),
                max=jax.device_put(jnp.full((s,), -jnp.inf, jnp.float32),
                                   sharding_s),
            ),
            registers=jax.device_put(jnp.zeros((s, m), jnp.int32), sharding_sk),
            counters=jax.device_put(jnp.zeros((s,), jnp.int32), sharding_s),
        )

    def shard_batch(self, batch: HostBatch) -> HostBatch:
        sharding = NamedSharding(self.mesh, P(HOSTS_AXIS, None))
        return HostBatch(*(jax.device_put(jnp.asarray(x), sharding)
                           for x in batch))

    # -- the per-device program --------------------------------------------

    def _local_step(self, state: AggState, batch: HostBatch, qs: jax.Array):
        s_loc = state.digest.mean.shape[0]
        start = lax.axis_index(SERIES_AXIS) * s_loc

        def relocal(rows):
            r = rows.reshape(-1).astype(jnp.int32)
            in_range = (r >= start) & (r < start + s_loc)
            return jnp.where(in_range, r - start, s_loc)

        # t-digest path: bin this device's host chunk, psum bins over hosts,
        # one compress drains them into the owned digests.
        temp = td_ops.init_temp(s_loc, self.k, self.compression)
        temp = td_ops.ingest_chunk(
            temp, relocal(batch.h_rows), batch.h_vals.reshape(-1),
            batch.h_wts.reshape(-1), self.compression)
        temp = collectives.merge_temp(temp, HOSTS_AXIS)
        digest = td_ops.drain_temp(state.digest, temp, self.compression)
        pcts = td_ops.quantile(digest, qs)

        # HLL path: scatter-max locally, pmax completes the union.
        idx, rho = hll_ops.idx_rho(batch.s_hi.reshape(-1),
                                   batch.s_lo.reshape(-1), self.precision)
        registers = state.registers.at[relocal(batch.s_rows), idx].max(
            rho, mode="drop")
        registers = collectives.merge_registers(registers, HOSTS_AXIS)
        estimates = hll_ops.estimate(registers, self.precision)

        # counter path: scatter-add locally, psum totals.
        contrib = jnp.zeros((s_loc,), jnp.int32).at[relocal(batch.c_rows)].add(
            batch.c_incs.reshape(-1).astype(jnp.int32), mode="drop")
        counters = state.counters + collectives.merge_counters(
            contrib, HOSTS_AXIS)

        new_state = AggState(digest=digest, registers=registers,
                             counters=counters)
        return new_state, pcts, estimates, counters

    # -- public API ---------------------------------------------------------

    def step(self, state: AggState, batch: HostBatch, qs):
        """Run one interval: returns (new_state, percentiles [S, P],
        set estimates [S], counter totals [S]).

        CONSUMES ``state``: the dispatch donates its buffers
        (``donate_argnums=(0,)``) and they are deleted the moment it
        lands. The caller MUST rebind — ``state, *rest =
        agg.step(state, ...)`` — and never touch the old handle again;
        ``step`` cannot rebind for the caller because the pre-donation
        pytree is the caller's own local. Reviewed under the
        donation-safety pass (this was the one call boundary predating
        every audit)."""
        return self._step(state, batch, jnp.asarray(qs, jnp.float32))  # lint: ok(donated-param-escape) documented consume-and-rebind contract: the caller rebinds state to the returned pytree, as every call site in tests/test_parallel.py does

    def merge_forwarded_digests(self, mean, weight, mins, maxs):
        """All-reduce pre-compressed per-host digests over the hosts axis —
        the collective form of importing already-flushed centroid state
        (Histo.Merge, samplers.go:676-691). Inputs [H, S, K] / [H, S],
        sharded over hosts; returns the merged [S, K] digest replicated
        across the hosts axis (butterfly ppermute, log2(H) rounds)."""
        sharding_hk = NamedSharding(self.mesh, P(HOSTS_AXIS, None, None))
        sharding_hs = NamedSharding(self.mesh, P(HOSTS_AXIS, None))
        args = (jax.device_put(jnp.asarray(mean, jnp.float32), sharding_hk),
                jax.device_put(jnp.asarray(weight, jnp.float32), sharding_hk),
                jax.device_put(jnp.asarray(mins, jnp.float32), sharding_hs),
                jax.device_put(jnp.asarray(maxs, jnp.float32), sharding_hs))
        m, w, mn, mx = self._merge_forwarded(*args)
        return TDigest(mean=m, weight=w, min=mn, max=mx)


def make_host_batch(num_hosts: int, num_series: int, n: int = 64,
                    m: int = 64, c: int = 64, seed: int = 0) -> HostBatch:
    """Synthetic per-host contributions for tests/dryrun (host-side numpy)."""
    rng = np.random.default_rng(seed)
    return HostBatch(
        h_rows=rng.integers(0, num_series, (num_hosts, n)).astype(np.int32),
        h_vals=rng.normal(100.0, 25.0, (num_hosts, n)).astype(np.float32),
        h_wts=np.ones((num_hosts, n), np.float32),
        s_rows=rng.integers(0, num_series, (num_hosts, m)).astype(np.int32),
        s_hi=rng.integers(0, 1 << 32, (num_hosts, m), dtype=np.uint64
                          ).astype(np.uint32),
        s_lo=rng.integers(0, 1 << 32, (num_hosts, m), dtype=np.uint64
                          ).astype(np.uint32),
        c_rows=rng.integers(0, num_series, (num_hosts, c)).astype(np.int32),
        c_incs=rng.integers(1, 10, (num_hosts, c)).astype(np.int32),
    )
