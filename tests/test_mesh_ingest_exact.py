"""The mesh's sample ingest against the dense ops, bit for bit.

``_mesh_ingest_samples`` hands every device the whole chunk over the
hosts axis and bins it against the device's series block in place: per
series shard, the dense store's ``shift_pred`` -> ``drain_every_bin``
-> ``ingest_chunk`` on the same chunk. So after every chunk each temp
and digest plane, gathered back to row order, is the dense ops' over
the whole capacity, to the last bit, and the drain counter is the
dense decision's count; the guard's drain of a block, a slab at a
time, is ``drain_every_bin``'s. Weights are whole numbers, as datagrams
without a sample rate carry: the chunk's prefix sums are then exact
whatever rows come before a shard's block. Four of the process's
virtual devices, in every shape a four-chip host can take.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from veneur_tpu.core.mesh_store import (_mesh_ingest_samples,
                                        _mesh_init_digests)
from veneur_tpu.ops import tdigest as td
from veneur_tpu.parallel.mesh import fleet_mesh

# 5,120 rows: a shard's block of 2,560 or 5,120 rows drains in slabs of
# 2,048, the last one clamped back over the one before, one of 1,280 in
# one piece
ROWS, CHUNK, C = 5120, 512, 100.0
K = td.size_bound(C)
# (series axis, hosts axis) of four devices
SHAPES = {"2x2": (2, 2), "4x1": (4, 1), "1x4": (1, 4)}


def _chunk(rows, vals, wts):
    """One staged chunk: padding rows are the capacity, weight 0."""
    out_r = np.full(CHUNK, ROWS, np.int32)
    out_v = np.zeros(CHUNK, np.float32)
    out_w = np.zeros(CHUNK, np.float32)
    n = len(rows)
    out_r[:n], out_v[:n], out_w[:n] = rows, vals, wts
    return out_r, out_v, out_w


def _one_sample_a_row(rng):
    """``wide``'s shape: each live row once a chunk, in shuffled order,
    across every shard's block."""
    live = rng.choice(ROWS, 200, replace=False)
    for _ in range(4):
        rows = rng.permutation(live)
        yield _chunk(rows, rng.lognormal(3.0, 1.0, len(rows)),
                     rng.integers(1, 4, len(rows)))


def _many_a_row(rng):
    """Six series of 340 samples each, interleaved, so that every chunk
    boundary falls inside each of them."""
    rows = np.tile(rng.choice(ROWS, 6, replace=False), 340)
    vals = rng.lognormal(3.0, 1.0, len(rows))
    wts = rng.integers(1, 4, len(rows))
    for i in range(0, len(rows), CHUNK):
        yield _chunk(rows[i:i + CHUNK], vals[i:i + CHUNK], wts[i:i + CHUNK])


def _ordered_arrival(rng):
    """Sixteen rows spread over every slab of every block, sixteen
    samples each a chunk; the values step up and then down past
    everything the rows hold, so the shift guard drains every bin
    twice."""
    rows = np.repeat(np.arange(16) * (ROWS // 16) + 7, 16)
    for lo in (0.0, 0.0, 0.0, 1000.0, 1000.0, -100.0):
        yield _chunk(rows, lo + rng.uniform(0.0, 10.0, len(rows)),
                     np.ones(len(rows)))


TRAFFIC = {"one_sample_a_row": _one_sample_a_row,
           "many_a_row": _many_a_row,
           "ordered_arrival": _ordered_arrival}


@jax.jit
def _dense_step(digest, temp, rows, vals, wts):
    pred = td.shift_pred(*temp.anchors(), rows, vals, wts, ROWS)
    digest, temp = lax.cond(pred, lambda a: td.drain_every_bin(*a, C),
                            lambda a: a, (digest, temp))
    return digest, td.ingest_chunk(temp, rows, vals, wts, C), pred


def _in_row_order(temp, digest, shards):
    """The mesh's planes as the dense store lays them out: every plane
    but the anchors concatenates the shards' row blocks already; the
    anchors are ``[A, block]`` a shard."""
    temp, digest = jax.device_get((temp, digest))
    out = {f"temp.{f}": np.asarray(x) for f, x in temp._asdict().items()}
    out.update({f"digest.{f}": np.asarray(x)
                for f, x in digest._asdict().items()})
    for f in ("seg_w", "seg_wm"):
        out[f"temp.{f}"] = out[f"temp.{f}"].reshape(
            shards, td.BELOW_MASS_ANCHORS, -1).transpose(1, 0, 2).reshape(-1)
    return out


@pytest.mark.parametrize("traffic", list(TRAFFIC))
@pytest.mark.parametrize("shape", list(SHAPES.values()), ids=list(SHAPES))
def test_mesh_sample_ingest_is_the_dense_ingest_bit_for_bit(shape, traffic):
    series, hosts = shape
    mesh = fleet_mesh(jax.devices()[:series * hosts], hosts=hosts)
    temp, digest, _, _ = _mesh_init_digests(mesh, ROWS, K, C)
    drains = jax.device_put(np.int32(0), NamedSharding(mesh, P()))
    d_temp = td.init_temp(ROWS, K, C)
    d_digest = td.init((ROWS,), C, K)
    d_drains = 0
    for rows, vals, wts in TRAFFIC[traffic](np.random.default_rng(7)):
        temp, digest, drains = _mesh_ingest_samples(
            temp, digest, drains, rows, vals, wts, mesh, C, K)
        d_digest, d_temp, pred = _dense_step(
            d_digest, d_temp, jnp.asarray(rows), jnp.asarray(vals),
            jnp.asarray(wts))
        d_drains += int(pred)
        got = _in_row_order(temp, digest, series)
        want = _in_row_order(d_temp, d_digest, 1)
        for name, plane in want.items():
            assert got[name].shape == plane.shape, name
            assert np.array_equal(got[name].view(np.uint32),
                                  plane.view(np.uint32)), name
        assert int(drains) == d_drains
    assert d_drains == (2 if traffic == "ordered_arrival" else 0)
