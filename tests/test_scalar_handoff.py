"""A retired scalar group is handed off, not zeroed.

The generation swap retires every group behind an empty twin, and the
retired group goes with its generation once it is flushed. Its
``snapshot_and_reset`` returns the live rows and writes nothing into
its values: zeroing the reserved array would fault in every page of an
array about to be freed (32 MiB a group at 2^22 rows). A group that is
not retired is still reset in place. What a store emits does not depend
on the rows it reserves.
"""

import jax
import numpy as np
import pytest

from veneur_tpu.core import MetricStore
from veneur_tpu.core.mesh_store import MeshScalarGroup
from veneur_tpu.core.store import ScalarGroup
from veneur_tpu.fleet import ShardRouter
from veneur_tpu.parallel.mesh import fleet_mesh
from veneur_tpu.samplers import (HistogramAggregates, parse_metric,
                                 parse_service_check)
from veneur_tpu.samplers.parser import MetricKey

ROWS = 300
GROUPS = [("dense", "counter"), ("dense", "gauge"), ("dense", "status"),
          ("mesh", "counter"), ("mesh", "gauge")]


def _mesh():
    return fleet_mesh(jax.devices()[:4], hosts=1)


def _group(impl: str, kind: str, capacity: int = 4096):
    if impl == "dense":
        return ScalarGroup(kind, capacity)
    return MeshScalarGroup(kind, capacity, _mesh(), ShardRouter(4))


def _fill(group, kind: str, seed: int) -> dict:
    """ROWS series, two writes each; returns what each should read as
    (value, message, hostname) by name."""
    rng = np.random.default_rng(seed)
    want = {}
    for i in range(ROWS):
        name = f"s.{seed % 97}.{i:04d}"
        key = MetricKey(name=name, type=kind, joined_tags=f"i:{i}")
        if kind == "counter":
            vals = rng.integers(-10**6, 10**6, 2)
            value = float(vals.sum())
        else:
            vals = rng.normal(0.0, 1e3, 2)
            value = float(vals[1])
        for v in vals:
            group.sample(key, [f"i:{i}"], float(v), 1.0,
                         message=f"m{i}.{seed}", hostname=f"h{i % 7}")
        want[name] = (value, f"m{i}.{seed}", f"h{i % 7}")
    return want


def _read(snap) -> dict:
    interner, values, messages, hostnames = snap
    assert len(values) == len(interner)
    return {key.name: (float(values[row]),
                       messages[row] if messages is not None else None,
                       hostnames[row] if hostnames is not None else None)
            for key, row in interner.rows.items()}


def _expect(want: dict, kind: str) -> dict:
    if kind == "status":
        return want
    return {k: (v, None, None) for k, (v, _, _) in want.items()}


@pytest.mark.parametrize("impl,kind", GROUPS)
def test_retired_snapshot_writes_nothing(impl, kind):
    group = _group(impl, kind)
    want = _fill(group, kind, 2_147_483_777)
    group._retired = True
    values = group.values
    before = values.copy()
    snap = group.snapshot_and_reset()
    assert _read(snap) == _expect(want, kind)
    # the same array, untouched: the live prefix still holds its values
    # and not one reserved row was written
    assert group.values is values
    np.testing.assert_array_equal(values, before)
    np.testing.assert_array_equal(values[:ROWS], snap[1])
    assert len(group.interner) == 0


@pytest.mark.parametrize("impl,kind", GROUPS)
def test_live_group_resets_and_is_reused(impl, kind):
    group = _group(impl, kind)
    first = _fill(group, kind, 11)
    assert _read(group.snapshot_and_reset()) == _expect(first, kind)
    assert not group.values.any()
    assert len(group.interner) == 0
    # a second interval on the same group: fresh rows, no carry-over
    second = _fill(group, kind, 4_000_000_019)
    assert _read(group.snapshot_and_reset()) == _expect(second, kind)
    assert not group.values.any()


def _feed(store: MetricStore, seed: int):
    rng = np.random.default_rng(seed)
    lines = []
    for i in range(ROWS):
        c = int(rng.integers(-10**9, 10**9))
        g = float(rng.normal(0.0, 1e6))
        lines += [f"c.{i}:{c}|c|#i:{i}", f"g.{i}:{g!r}|g|#i:{i}",
                  f"gc.{i}:{c // 3}|c|#veneurglobalonly,i:{i}",
                  f"gg.{i}:{g / 7!r}|g|#veneurglobalonly,i:{i}"]
    for line in lines:
        store.process_metric(parse_metric(line.encode()))
    for i in range(ROWS // 3):
        store.process_metric(parse_service_check(
            f"_sc|sc.{i}|{i % 4}|h:host{i % 5}|#i:{i}|m:msg {i}".encode(),
            now=5))


def _emitted(out, fwd, columnar: bool) -> tuple:
    final = out.to_intermetrics() if columnar else out
    rows = sorted((m.name, tuple(m.tags), str(m.type), m.value.hex(),
                   m.message, m.hostname) for m in final)
    return (rows, sorted((n, tuple(t), v) for n, t, v in fwd.counters),
            sorted((n, tuple(t), v.hex()) for n, t, v in fwd.gauges))


def _flush_twice(capacity: int, storage: str, is_local: bool,
                 columnar: bool) -> list:
    mesh = _mesh() if storage == "mesh" else None
    store = MetricStore(initial_capacity=capacity, chunk=128, mesh=mesh)
    got = []
    for now, seed in ((1, 3), (2, 2_147_483_659)):
        _feed(store, seed)
        out, fwd, _ = store.flush([0.5], HistogramAggregates(),
                                  is_local=is_local, now=now,
                                  columnar=columnar)
        got.append(_emitted(out, fwd, columnar))
    return got


@pytest.mark.parametrize("columnar", [False, True])
@pytest.mark.parametrize("is_local", [True, False])
@pytest.mark.parametrize("storage", ["dense", "mesh"])
def test_emission_does_not_depend_on_rows_reserved(storage, is_local,
                                                   columnar):
    big = _flush_twice(1 << 22, storage, is_local, columnar)
    small = _flush_twice(4096, storage, is_local, columnar)
    assert big == small
    for rows, counters, gauges in big:
        names = {r[0] for r in rows}
        assert {"c.0", "g.0", "sc.0"} <= names
        # a global emits the global counters and gauges; a forwarding
        # local hands them upstream instead
        if is_local:
            assert "gc.0" not in names
            assert len(counters) == len(gauges) == ROWS
        else:
            assert {"gc.0", "gg.0"} <= names
            assert counters == gauges == []
