"""Global-aggregation collectives: the fleet-wide sketch merge over ICI.

These functions run *inside* ``shard_map`` over a named mesh axis. They are
the TPU re-expression of the reference's global-aggregator merge loop
(``worker.go:313-398``: gob/proto decode + one-at-a-time ``Combine``/
``Merge`` per imported sketch) as single collective ops over dense state:

    counters            psum        (Counter.Combine adds, samplers.go:195-200)
    gauges              last-write  (host concern; not a collective)
    HLL registers       pmax        (Set.Combine register max, samplers.go:423-435)
    t-digest temp bins  psum        (bin accumulators are linear in samples)
    t-digest centroids  butterfly ppermute merge / all-gather + one compress
                        (MergingDigest.Merge, merging_digest.go:358-370)

The t-digest temp-bin trick: because ingest pre-clusters samples into
k-scale bins whose (sum_w, sum_wm) accumulators are *additive*, the
cross-host merge of in-progress digest state is a plain ``psum`` — no
sequential centroid walk crosses the wire, and ICI carries ``[S_shard, K]``
float32 tensors. ``parallel/global_agg.py``'s step uses it. The serving
mesh's sample path does not: a plane-sized psum costs the rows reserved
whatever the chunk carried, so ``core/mesh_store.py`` gathers the chunk
over the hosts axis instead and bins it on every device of a series shard.
"""

from __future__ import annotations


import jax
from jax import lax

from veneur_tpu.ops import tdigest as td_ops
from veneur_tpu.ops.tdigest import TDigest, TempCentroids


def merge_counters(values: jax.Array, axis: str) -> jax.Array:
    """Fleet-wide counter totals: one psum (Counter.Combine, samplers.go:195)."""
    return lax.psum(values, axis)


def merge_registers(registers: jax.Array, axis: str) -> jax.Array:
    """Fleet-wide HLL union: elementwise pmax over the mesh axis
    (Set.Combine, samplers.go:423-435)."""
    return lax.pmax(registers, axis)


def merge_temp(temp: TempCentroids, axis: str) -> TempCentroids:
    """Merge in-progress digest state across hosts: additive fields psum,
    extrema pmin/pmax. Exact — no approximation is introduced by the
    collective itself (binning already happened per-host under the same
    k-scale the reference uses)."""
    return TempCentroids(
        sum_w=lax.psum(temp.sum_w, axis),
        sum_wm=lax.psum(temp.sum_wm, axis),
        seg_w=lax.psum(temp.seg_w, axis),
        seg_wm=lax.psum(temp.seg_wm, axis),
        count=lax.psum(temp.count, axis),
        vsum=lax.psum(temp.vsum, axis),
        vmin=lax.pmin(temp.vmin, axis),
        vmax=lax.pmax(temp.vmax, axis),
        recip=lax.psum(temp.recip, axis),
    )


def allmerge_digest(digest: TDigest, axis: str, axis_size: int,
                    compression: float = td_ops.DEFAULT_COMPRESSION) -> TDigest:
    """All-reduce pre-compressed digests over a mesh axis.

    Power-of-two axis: recursive-doubling butterfly — log2(N) ppermute
    rounds, each concatenating partner centroids ([S, 2K]) and compressing
    back to K. Every round's exchange is nearest-neighbour-friendly on ICI
    and the compress keeps wire volume constant per round.

    Non-power-of-two axis: one all_gather then a single [S, N*K] compress.

    Digest merge is associative and commutative (same k-scale invariant as
    MergingDigest.Merge, merging_digest.go:358-370), so the butterfly's
    pairing order does not change the accuracy bound.
    """
    if axis_size == 1:
        return digest
    if axis_size & (axis_size - 1) == 0:
        step = 1
        while step < axis_size:
            perm = [(i, i ^ step) for i in range(axis_size)]
            partner = TDigest(
                mean=lax.ppermute(digest.mean, axis, perm),
                weight=lax.ppermute(digest.weight, axis, perm),
                min=lax.ppermute(digest.min, axis, perm),
                max=lax.ppermute(digest.max, axis, perm),
            )
            digest = td_ops.merge(digest, partner, compression)
            step *= 2
        return digest
    # Fallback: gather every host's centroids and re-cluster once.
    mean = lax.all_gather(digest.mean, axis, axis=-2)    # [..., N, K]
    weight = lax.all_gather(digest.weight, axis, axis=-2)
    flat_mean = mean.reshape(mean.shape[:-2] + (axis_size * mean.shape[-1],))
    flat_w = weight.reshape(flat_mean.shape)
    return td_ops.from_centroids(
        flat_mean, flat_w,
        lax.pmin(digest.min, axis), lax.pmax(digest.max, axis),
        compression, digest.capacity)
