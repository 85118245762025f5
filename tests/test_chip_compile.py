"""The main path's programs, compiled for a described v5e with no chip
attached (the only file in the repository that describes a chip).

The TPU's compiler is installed here and compiles for a topology that is
described, not attached: what it refuses — a misaligned slice, too much
fast memory, a kernel that cannot be partitioned, a program that does
not fit 16 GB — it refuses before any chip time is spent. Nothing runs,
so this says nothing about results or times; a cell of
``benchmark/run.py`` on the chip does.

Only one process may load the TPU's library, so the topology is
described inside a module-scoped fixture (never at import time, not
``autouse``, not in ``conftest.py``) and every compile happens in the
test's own process, with the persistent compilation cache off around it
(an entry written without a chip cannot be read back without one).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

ROWS = 1 << 17
K = 104                 # size_bound(compression=100)
CHUNK = 16384           # example.yaml store_chunk
COMPRESSION = 100.0


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    from veneur_tpu.parallel.mesh import fleet_mesh

    m = fleet_mesh(topo.devices, hosts=2)
    assert dict(m.shape) == {"series": 2, "hosts": 2}
    return m


@pytest.fixture(scope="module")
def mesh_series4(topo):
    from veneur_tpu.parallel.mesh import fleet_mesh

    return fleet_mesh(topo.devices, hosts=1)


@pytest.fixture(scope="module")
def kernel_admitted():
    """``pallas_ok`` asks ``jax.default_backend()``, which is the CPU
    here: steer it in the test, as the ops would answer on the chip."""
    from veneur_tpu.ops import tdigest_pallas

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(tdigest_pallas, "pallas_ok",
                      lambda a: a.ndim == 2 and a.dtype == jnp.float32)
        yield


@pytest.fixture(scope="module")
def one_chip_flush(one_chip, kernel_admitted):
    """``_flush_digests`` compiled for one described chip (two tests
    read it; it compiles once)."""
    from veneur_tpu.core.store import _flush_digests

    digest, temp = (_on(t, one_chip) for t in _digest_state(ROWS))
    rows = _f32((ROWS,), one_chip)
    return _flush_digests.lower(
        digest, temp, rows, rows, _f32((4,), one_chip),
        _i32((), one_chip), COMPRESSION, True).compile()


def _on(tree, sharding):
    """Shapes of ``tree`` placed by ``sharding`` (one sharding, or a
    matching tree of them)."""
    if isinstance(sharding, jax.sharding.Sharding):
        sharding = jax.tree.map(lambda _: sharding, tree)
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, sharding)


def _f32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _digest_state(rows):
    from veneur_tpu.ops import tdigest as td

    digest = jax.eval_shape(lambda: td.init((rows,), COMPRESSION, K))
    temp = jax.eval_shape(lambda: td.init_temp(rows, K, COMPRESSION))
    return digest, temp


def _nbytes(tree):
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(tree))


@pytest.mark.parametrize("sort_b", [False, True])
def test_drain_quantile_kernel(one_chip, sort_b):
    from veneur_tpu.ops.tdigest_pallas import _drain_quantile_pallas

    plane = _f32((ROWS, K), one_chip)
    compiled = _drain_quantile_pallas.lower(
        plane, plane, plane, plane, _f32((ROWS,), one_chip),
        _f32((ROWS,), one_chip), _f32((4,), one_chip),
        compression=COMPRESSION, out_size=K, sort_b=sort_b).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_compress_presorted_kernel(one_chip):
    from veneur_tpu.ops.tdigest_pallas import _compress_presorted_pallas

    plane = _f32((ROWS, K), one_chip)
    compiled = _compress_presorted_pallas.lower(
        plane, plane, plane, plane, compression=COMPRESSION,
        out_size=K).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flush_and_ingest_programs_hold_the_kernel(one_chip,
                                                   one_chip_flush,
                                                   kernel_admitted):
    from veneur_tpu.core.store import _ingest_samples

    digest, temp = (_on(t, one_chip) for t in _digest_state(ROWS))
    flush = one_chip_flush
    assert "tpu_custom_call" in flush.as_text()
    ingest = _ingest_samples.lower(
        digest, temp, _i32((CHUNK,), one_chip), _f32((CHUNK,), one_chip),
        _f32((CHUNK,), one_chip), _i32((), one_chip), _i32((), one_chip),
        COMPRESSION, True).compile()
    assert "tpu_custom_call" in ingest.as_text()
    # one generation's planes, the flush's scratch and its outputs fit
    # the chip's 16 GB many times over at this size
    mem = flush.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < (16 << 30) // 8


def test_flush_loop_updates_the_planes_in_place(one_chip_flush):
    """The live-row-bounded flush for the chip: one loop whose body
    holds the kernel, works on a slab and writes it back into the
    carried planes (the output planes alias the donated digest's; a
    copy of a whole plane a trip would eat the gain)."""
    body_text = _flush_loop_body(one_chip_flush.as_text(),
                                 [f"f32[{ROWS},{K}]"])
    updates = [ln for ln in body_text.splitlines()
               if " dynamic-update-slice(" in ln and f"f32[{ROWS},{K}]" in ln]
    assert len(updates) == 2  # digest mean and weight
    planes = 2 * ROWS * K * 4
    assert one_chip_flush.memory_analysis().alias_size_in_bytes >= planes


def _flush_loop_body(text, wholes):
    """The body of a flush program's one loop: it holds the kernel,
    works on a slab, and copies or transposes none of the ``wholes``
    (shapes of whole planes, as the HLO prints them)."""
    import re

    from veneur_tpu.ops.tdigest_pallas import _FLUSH_SLAB_ROWS

    assert len(re.findall(r" while\(", text)) == 1
    body = re.search(r"body=%?([\w.\-]+)", text).group(1)
    start = text.index("%" + body + " (")  # the body's definition
    body_text = text[start:text.index("\n}", start)]
    assert "tpu_custom_call" in body_text
    assert f"f32[{_FLUSH_SLAB_ROWS},{K}]" in body_text
    for line in body_text.splitlines():
        if re.search(r"= \S+ (copy|transpose)\(", line):
            for whole in wholes:
                assert whole not in line.split("=")[1].split("(")[0], line
    return body_text


def test_hll_insert_and_estimate(one_chip):
    """The set group's two programs at p=14: the register scatter-max
    of an ingest chunk and the batched estimate of a flush."""
    from veneur_tpu.core.store import _estimate_all, _ingest_hashes
    from veneur_tpu.ops import hll

    regs = jax.ShapeDtypeStruct((4096, hll.num_registers(14)), jnp.int8,
                                sharding=one_chip)
    u32 = jax.ShapeDtypeStruct((CHUNK,), jnp.uint32, sharding=one_chip)
    _ingest_hashes.lower(regs, _i32((CHUNK,), one_chip), u32,
                         u32).compile()
    _estimate_all.lower(regs).compile()


SET_ROWS, SET_WIDTH, SET_POOL = 1 << 20, 128, 4096


def test_sparse_set_programs_at_the_cell_s_store(one_chip):
    """``standalone-sets1m``'s sparse store (2^20 series of 128 entries,
    a pool of 4,096 dense rows, p = 14) for one described chip: the
    insert of a 16,384-hash chunk updates every plane in place, the
    flush's estimate runs the live bucket of 65,536 rows (the cell's
    36,864 live sets), and neither needs scratch of the store's size.
    What each counts is printed for PERF.md: the runtime's peak does
    not see a program's scratch."""
    from veneur_tpu.core.sets import (_sparse_set_estimate,
                                      _sparse_set_insert)
    from veneur_tpu.ops import hll

    m = hll.num_registers(14)
    state = (_i32((SET_ROWS, SET_WIDTH), one_chip),
             _i32((SET_ROWS,), one_chip),
             jax.ShapeDtypeStruct((SET_POOL, m), jnp.int8,
                                  sharding=one_chip),
             _i32((hll.N_COUNTERS,), one_chip))
    u32 = jax.ShapeDtypeStruct((CHUNK,), jnp.uint32, sharding=one_chip)
    insert = _sparse_set_insert.lower(*state, _i32((CHUNK,), one_chip),
                                      u32, u32).compile()
    estimate = _sparse_set_estimate.lower(*state[:3], 1 << 16).compile()
    held = _nbytes(state)
    for name, program in (("insert", insert), ("estimate", estimate)):
        mem = program.memory_analysis()
        print(f"sparse set {name}: argument "
              f"{mem.argument_size_in_bytes} B, scratch "
              f"{mem.temp_size_in_bytes} B, output "
              f"{mem.output_size_in_bytes} B")
        assert mem.temp_size_in_bytes < held // 8
    mem = insert.memory_analysis()
    assert mem.alias_size_in_bytes >= held
    assert mem.argument_size_in_bytes < 700 * 2**20


def test_mesh_programs_shard_state_over_series(one_chip_flush, mesh,
                                               kernel_admitted):
    """The four-chip global: the flush and the routed import compile
    with the kernel inside ``shard_map``, and each device is handed its
    series block of the state, not a replica of all of it."""
    from veneur_tpu.core.mesh_store import (_digest_specs,
                                            _mesh_flush_digests,
                                            _mesh_import_routed)

    temp_spec, dig_spec, _sk, s = _digest_specs()
    named = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
    digest, temp = _digest_state(ROWS)
    state_bytes = _nbytes(digest) + _nbytes(temp)
    m_digest = _on(digest, jax.tree.map(named, dig_spec))
    m_temp = _on(temp, jax.tree.map(named, temp_spec))
    m_rows = _f32((ROWS,), named(s))

    flush = _mesh_flush_digests.lower(
        m_digest, m_temp, m_rows, m_rows, _f32((4,), named(P())),
        _i32((mesh.shape["series"],), named(s)), mesh,
        COMPRESSION).compile()
    assert "tpu_custom_call" in flush.as_text()
    per_device = flush.memory_analysis().argument_size_in_bytes
    assert (per_device
            < one_chip_flush.memory_analysis().argument_size_in_bytes)
    assert per_device <= 0.55 * state_bytes + (1 << 20)

    shards = mesh.shape["series"]
    st = named(P("series", None))
    stack_f = _f32((shards, CHUNK), st)
    stack_i = _i32((shards, CHUNK), st)
    imp = _mesh_import_routed.lower(
        m_temp, m_digest, m_rows, m_rows, _i32((shards,), named(s)),
        stack_i, stack_f, stack_f, stack_i, stack_f, stack_f, mesh,
        COMPRESSION).compile()
    assert "tpu_custom_call" in imp.as_text()
    assert (imp.memory_analysis().argument_size_in_bytes
            <= 0.55 * state_bytes + (8 << 20))


DEPLOYED_ROWS = 1 << 22  # global-fanin64's store_initial_capacity


def _deployed_state(mesh):
    """Shapes of ``global-fanin64``'s digest state on the series-4 mesh:
    (digest, temp, a ``[rows]`` plane, bytes of a device's quarter)."""
    from veneur_tpu.core.mesh_store import _digest_specs

    temp_spec, dig_spec, _sk, s = _digest_specs()
    named = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
    digest, temp = _digest_state(DEPLOYED_ROWS)
    quarter = (_nbytes(digest) + _nbytes(temp)) // 4
    return (_on(digest, jax.tree.map(named, dig_spec)),
            _on(temp, jax.tree.map(named, temp_spec)),
            _f32((DEPLOYED_ROWS,), named(s)), quarter)


@pytest.fixture(scope="module")
def deployed_flush(mesh_series4, kernel_admitted):
    """``_mesh_flush_digests`` at ``global-fanin64``'s size on four
    described chips (two tests read it; it compiles once)."""
    from veneur_tpu.core.mesh_store import _mesh_flush_digests

    mesh = mesh_series4
    m_digest, m_temp, m_rows, _ = _deployed_state(mesh)
    return _mesh_flush_digests.lower(
        m_digest, m_temp, m_rows, m_rows,
        _f32((4,), NamedSharding(mesh, P())),
        _i32((4,), NamedSharding(mesh, P("series"))), mesh,
        COMPRESSION).compile()


def test_mesh_flush_loop_runs_a_shards_live_rows(deployed_flush):
    """The sharded global's flush for the chip (PERF.md, PR 36): every
    shard runs one loop over the slabs that hold its own live rows. The
    body holds the kernel on a slab, relays neither a digest plane nor
    a flat bin plane of the block (the straight-line program took both
    bin planes ``[S, K]`` whole: 6 ms a flush), the program holds no
    collective, so each shard's trip count is its own, and the donated
    digest planes are updated in place."""
    block = DEPLOYED_ROWS // 4
    text = deployed_flush.as_text()
    _flush_loop_body(text, [f"f32[{block},{K}]", f"f32[{block * K}]"])
    for collective in ("all-reduce", "all-gather", "collective-permute",
                       "all-to-all"):
        assert collective not in text
    assert (deployed_flush.memory_analysis().alias_size_in_bytes
            >= 2 * block * K * 4)


def test_global_at_deployment_size_fits_a_chip(mesh_series4, deployed_flush,
                                               kernel_admitted):
    """``global-fanin64`` as it is deployed: 2^22 digest rows over the
    four chips of a host (series 4 x hosts 1). The planes are made in
    shards, the routed import (with its row-local drain, the kernel
    inside) and the flush compile, and none of the three asks a device
    for more than its quarter of the state and a chunk's worth beside
    it. The import holds no collective: two threads dispatch it and
    the flush."""
    from veneur_tpu.core.mesh_store import (_mesh_import_routed,
                                            _mesh_init_digests)

    rows = DEPLOYED_ROWS
    mesh = mesh_series4
    assert dict(mesh.shape) == {"series": 4, "hosts": 1}
    named = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
    s = P("series")
    m_digest, m_temp, m_rows, quarter = _deployed_state(mesh)

    init = _mesh_init_digests.lower(mesh, rows, K, COMPRESSION).compile()
    made = init.memory_analysis()
    assert made.output_size_in_bytes <= quarter + (16 << 20)
    assert made.temp_size_in_bytes <= 1 << 20

    st = named(P("series", None))
    stack_f = _f32((4, CHUNK), st)
    stack_i = _i32((4, CHUNK), st)
    imp = _mesh_import_routed.lower(
        m_temp, m_digest, m_rows, m_rows, _i32((4,), named(s)),
        stack_i, stack_f, stack_f, stack_i, stack_f, stack_f, mesh,
        COMPRESSION).compile()
    text = imp.as_text()
    assert "tpu_custom_call" in text
    for collective in ("all-reduce", "all-gather", "collective-permute",
                       "all-to-all"):
        assert collective not in text
    held = imp.memory_analysis()
    assert held.argument_size_in_bytes <= quarter + (16 << 20)
    # donated planes are updated in place; what is left is chunk-sized
    assert held.temp_size_in_bytes <= 2 * quarter

    assert (deployed_flush.memory_analysis().argument_size_in_bytes
            <= quarter + (16 << 20))


def test_datagram_fed_mesh_at_deployment_size_fits_a_chip(mesh,
                                                          kernel_admitted):
    """``mesh4-hist1m`` as it is deployed: 2^22 digest rows on the
    default mesh of a four-chip host (series 2 x hosts 2), every row
    on the two chips of its hosts pair. The hosts-sharded sample
    ingest and the flush compile for the described chips, each inside
    a chip's memory beside the fresh generation a swap holds; the
    ingest gathers the chunk over the hosts axis and psums the guard's
    masses, the flush holds no collective. A dispatch puts a device's
    slice of the chunk through them, 8,192 x 12 B, and the guard's two
    masses; it bins into the planes in place, with chunk- and
    anchor-sized scratch, not a fresh temp of the block."""
    from veneur_tpu.core.mesh_store import (MeshDigestGroup, _digest_specs,
                                            _mesh_flush_digests,
                                            _mesh_ingest_samples)

    rows = DEPLOYED_ROWS
    assert dict(mesh.shape) == {"series": 2, "hosts": 2}
    temp_spec, dig_spec, _sk, s = _digest_specs()
    named = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
    digest, temp = _digest_state(rows)
    half = (_nbytes(digest) + _nbytes(temp)) // 2  # a device's share
    assert half == (1 << 21) * (229 + 210) * 4     # 3.68 GB
    m_digest = _on(digest, jax.tree.map(named, dig_spec))
    m_temp = _on(temp, jax.tree.map(named, temp_spec))
    m_rows = _f32((rows,), named(s))
    h = named(P("hosts"))

    ingest = _mesh_ingest_samples.lower(
        m_temp, m_digest, _i32((), named(P())), _i32((CHUNK,), h),
        _f32((CHUNK,), h), _f32((CHUNK,), h), mesh, COMPRESSION,
        K).compile()
    text = ingest.as_text()
    assert "all-gather" in text and "all-reduce" in text
    held = ingest.memory_analysis()
    assert held.argument_size_in_bytes <= half + (16 << 20)
    assert held.temp_size_in_bytes < 1e9, held.temp_size_in_bytes
    # the program's own peak beside the generation it works on, and the
    # fresh twin a swap holds beside both: under the chip's 16 GB
    peak = (held.argument_size_in_bytes + held.temp_size_in_bytes
            + held.output_size_in_bytes - held.alias_size_in_bytes)
    assert peak + half < 16e9, peak
    # what one device sends through hosts-axis collectives a dispatch,
    # as the timeline's mesh_ingest.collective_bytes counts it
    group = MeshDigestGroup.__new__(MeshDigestGroup)
    group.hosts, group.shards, group.capacity, group.k = 2, 2, rows, K
    group.chunk = CHUNK
    assert group.sample_collective_bytes() == 4 * (3 * 8192 + 2) == 98_312
    group.hosts = 1  # series 4 x hosts 1: no hosts-axis collective
    assert group.sample_collective_bytes() == 0

    flush = _mesh_flush_digests.lower(
        m_digest, m_temp, m_rows, m_rows, _f32((4,), named(P())),
        _i32((2,), named(s)), mesh, COMPRESSION).compile()
    text = flush.as_text()
    assert "tpu_custom_call" in text
    for collective in ("all-reduce", "all-gather", "collective-permute",
                       "all-to-all"):
        assert collective not in text
    assert (flush.memory_analysis().argument_size_in_bytes
            <= half + (32 << 20))


def test_dense_import_holds_the_kernel(one_chip, kernel_admitted):
    """The dense global's import at 2^20 rows on one chip: the row-local
    drain is one loop whose body holds the kernel, once, works on a
    slab of the rows to drain and copies no whole plane a trip."""
    import re

    from veneur_tpu.core.store import _ingest_centroids
    from veneur_tpu.ops.tdigest import ROW_DRAIN_SLAB_ROWS

    rows = 1 << 20
    digest, temp = (_on(t, one_chip) for t in _digest_state(rows))
    plane = _f32((rows,), one_chip)
    imp = _ingest_centroids.lower(
        digest, temp, plane, plane, _i32((CHUNK,), one_chip),
        _f32((CHUNK,), one_chip), _f32((CHUNK,), one_chip),
        _i32((CHUNK,), one_chip), _f32((CHUNK,), one_chip),
        _f32((CHUNK,), one_chip), _i32((), one_chip), COMPRESSION,
        True).compile()
    text = imp.as_text()
    bodies = []
    for body in re.findall(r"body=%?([\w.\-]+)", text):
        start = text.index("%" + body + " (")
        bodies.append(text[start:text.index("\n}", start)])
    drains = [b for b in bodies if "tpu_custom_call" in b]
    assert len(drains) == 1
    assert f"f32[{ROW_DRAIN_SLAB_ROWS},{K}]" in drains[0]
    whole = f"f32[{rows},{K}]"
    for line in drains[0].splitlines():
        if re.search(r"= \S+ (copy|transpose)\(", line):
            assert whole not in line.split("=")[1].split("(")[0], line
    assert imp.memory_analysis().alias_size_in_bytes >= 4 * rows * K * 4


def test_sample_ingest_drains_held_rows_behind_a_branch(one_chip,
                                                        kernel_admitted):
    """The row-drained sample ingest at 2^20 rows on one chip: the
    import path's drain, one loop whose body holds the kernel and works
    on a slab of the rows to drain, inside a ``conditional``, so that a
    chunk that meets no held row runs neither the loop nor what carries
    the planes into it (a dispatch of ``dense``'s last ones or of
    ``wide`` costs what it cost)."""
    import re

    from veneur_tpu.core.store import _ingest_samples
    from veneur_tpu.ops.tdigest import ROW_DRAIN_SLAB_ROWS

    rows = 1 << 20
    digest, temp = (_on(t, one_chip) for t in _digest_state(rows))
    compiled = _ingest_samples.lower(
        digest, temp, _i32((CHUNK,), one_chip), _f32((CHUNK,), one_chip),
        _f32((CHUNK,), one_chip), _i32((), one_chip), _i32((), one_chip),
        COMPRESSION, True).compile()
    text = compiled.as_text()
    comps = _computations(text)
    loops = [ln for lines in comps.values() for ln in lines
             if " while(" in ln]
    drains = [ln for ln in loops
              if any("tpu_custom_call" in x for c in _called(ln)
                     for x in comps.get(c, []))]
    assert len(drains) == 1
    body = re.search(r"body=%?([\w.\-]+)", drains[0]).group(1)
    assert any(f"f32[{ROW_DRAIN_SLAB_ROWS},{K}]" in ln for ln in comps[body])
    # the loop sits in a branch, not in the entry computation
    entry = re.search(r"ENTRY %?([\w.\-]+)", text).group(1)
    assert drains[0] not in comps[entry]
    branches = {n for lines in comps.values() for ln in lines
                if " conditional(" in ln for n in _called(ln)}
    holder = [n for n, lines in comps.items() if drains[0] in lines]
    assert set(holder) <= branches, (holder, branches)
    # ... of the entry's one conditional, whose other branch, the chunk
    # that drains nothing, makes no plane: the branches agree on their
    # results' layout, and a row drain in a branch of its own made
    # that chunk relay both digest planes row-major and back
    outer = [ln for ln in comps[entry] if " conditional(" in ln]
    assert len(outer) == 1
    idle = [n for n in _called(outer[0])
            if not any(drains[0] in comps[c] for c in _reach(comps, [n]))]
    assert len(idle) == 1
    made = [ln for ln in comps[idle[0]]
            if rows * K in _result_elements(ln)[0]
            and _result_elements(ln)[1] not in ("parameter", "tuple",
                                                "get-tuple-element")]
    assert not made, made
    # every plane is updated in place: digest and bin planes aliased
    assert compiled.memory_analysis().alias_size_in_bytes >= 4 * rows * K * 4


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_slab_programs_at_the_cell_s_slab(one_chip, kernel_admitted, dtype):
    """``standalone-slab10m``'s slab of 262,144 rows in its flat storage
    planes, both storage dtypes, for one described chip. The sample
    ingest is the dense store's: its row drain is a loop that holds the
    kernel, inside the entry's one conditional, whose other branch makes
    no plane. The flush is one loop over the live rows' kernel slabs
    that widens a window of the planes, never a whole one, and writes
    it back in place."""
    import re

    from veneur_tpu.core.slab import (_flush_slab, _ingest_slab,
                                      _init_digest_slab)
    from veneur_tpu.ops import tdigest as td

    rows = 1 << 18
    digest = _on(jax.eval_shape(lambda: _init_digest_slab(rows, K, dtype)),
                 one_chip)
    temp = _on(jax.eval_shape(lambda: td.init_temp(rows, K)), one_chip)
    ingest = _ingest_slab.lower(
        temp, digest, _i32((CHUNK,), one_chip), _f32((CHUNK,), one_chip),
        _f32((CHUNK,), one_chip), _i32((), one_chip), _i32((), one_chip),
        COMPRESSION, True).compile()
    text = ingest.as_text()
    comps = _computations(text)
    entry = re.search(r"ENTRY %?([\w.\-]+)", text).group(1)
    outer = [ln for ln in comps[entry] if " conditional(" in ln]
    assert len(outer) == 1
    drains = [ln for lines in comps.values() for ln in lines
              if " while(" in ln and any(
                  "tpu_custom_call" in x for c in _called(ln)
                  for x in comps.get(c, []))]
    assert drains and not set(drains) & set(comps[entry])
    idle = [n for n in _called(outer[0])
            if not any(d in comps[c] for d in drains
                       for c in _reach(comps, [n]))]
    assert len(idle) == 1
    made = [ln for ln in comps[idle[0]]
            if rows * K in _result_elements(ln)[0]
            and _result_elements(ln)[1] not in ("parameter", "tuple",
                                                "get-tuple-element")]
    assert not made, made
    storage = jnp.dtype(dtype).itemsize
    assert ingest.memory_analysis().alias_size_in_bytes >= \
        2 * rows * K * (storage + 4)
    flush = _flush_slab.lower(
        digest, temp, _f32((4,), one_chip), _i32((), one_chip), COMPRESSION,
        False, True).compile()
    flat = f"[{rows * K}]"
    body = _flush_loop_body(flush.as_text(), [f"f32{flat}",
                                              f"f32[{rows},{K}]"])
    mem = flush.memory_analysis()
    # the retired generation's flush: no fresh slab, the drained planes
    # written in place of the donated ones
    assert mem.alias_size_in_bytes >= 2 * rows * K * storage
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < 2 * _nbytes((digest, temp))


def _reach(comps, names):
    """The computations ``names`` call, themselves included."""
    seen, todo = set(), list(names)
    while todo:
        n = todo.pop()
        if n in seen or n not in comps:
            continue
        seen.add(n)
        for line in comps[n]:
            todo += _called(line)
    return seen


def test_topk_update_at_the_cell_s_table(one_chip):
    """The count-min update at ``standalone-hot1m``'s shapes (a 4 x 2^20
    table, 4,096 rows of 32 places, a 16,384-line chunk) compiles for
    one chip: the table is updated in place, the candidates are ranked
    by two sorts of the chunk, and nothing of it needs more than a
    fraction of the chip."""
    from veneur_tpu.ops import countmin as cm

    sk = _on(jax.eval_shape(lambda: cm.init(4096, 4, 1 << 20, 32)),
             one_chip)
    u32 = jax.ShapeDtypeStruct((CHUNK,), jnp.uint32, sharding=one_chip)
    compiled = jax.jit(cm.update, donate_argnums=(0,)).lower(
        sk, _i32((CHUNK,), one_chip), u32, u32, u32,
        _f32((CHUNK,), one_chip)).compile()
    text = compiled.as_text()
    assert " sort(" in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 4 * (1 << 20) * 4     # the table
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < (16 << 30) // 8


def _result_elements(line):
    """Element counts of the arrays an HLO instruction produces (a
    tuple's members each), and its opcode."""
    import re

    rhs = line.split(" = ", 1)[1]
    if rhs.startswith("("):
        depth = 0
        for end, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, rest = rhs[:end + 1], rhs[end + 1:]
    else:
        shape, _, rest = rhs.partition(" ")
    counts = [int(np.prod([int(d) for d in dims.split(",") if d]))
              for dims in re.findall(r"\w+\[([\d,]*)\]", shape)]
    return counts, re.match(r"\s*([\w\-]+)\(", rest).group(1)


def _computations(text):
    """name -> lines of every computation of an optimised HLO module."""
    import re

    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None and " = " in line:
            comps[name].append(line)
    return comps


def _called(line):
    import re

    names = re.findall(
        r"(?:calls|to_apply|body|condition|true_computation|"
        r"false_computation)=%?([\w.\-]+)", line)
    for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
        names += [n.strip().lstrip("%") for n in group.split(",")]
    return names


def _plane_traffic(text, plane):
    """What an ingest program does to arrays of ``plane`` elements
    outside the guard ``conditional``'s branches: (whiles that carry
    one and are no part of the row drain's loop, copies that produce
    one, other producers that are not an in-place update)."""
    comps = _computations(text)
    reach = lambda names: _reach(comps, names)  # noqa: E731

    def holds_kernel(names):
        return any("tpu_custom_call" in ln for c in reach(names)
                   for ln in comps[c])

    every = [line for lines in comps.values() for line in lines]
    guarded = reach(n for line in every if " conditional(" in line
                    for n in _called(line))
    # the row drain: the loop that holds the kernel, and what it calls
    # (a loop in it that moves a row's window a trip converts nothing)
    drain = reach(n for line in every if " while(" in line
                  and holds_kernel(_called(line)) for n in _called(line))
    in_place = ("scatter", "dynamic-update-slice")
    passed = ("parameter", "get-tuple-element", "bitcast", "tuple",
              "conditional", "constant")
    loops, copies, others = [], [], []
    for name, lines in comps.items():
        if name in guarded:
            continue
        for line in lines:
            counts, op = _result_elements(line)
            if plane not in counts:
                continue
            if op in passed + in_place:
                continue
            # an async start's tuple names its operands too; what it
            # produces is read off its done (a copy's off its start)
            if op.endswith("-start") and op != "copy-start":
                continue
            if op == "while":
                if name not in drain and not holds_kernel(_called(line)):
                    loops.append(line)
            elif op.startswith("copy"):
                if op != "copy-done":  # counted at its copy-start
                    copies.append(line)
            elif op == "fusion":
                # in place: what makes the plane inside is an update
                made = [_result_elements(ln) for c in _called(line)
                        for ln in comps[c]]
                made = {o for counts, o in made if plane in counts} - set(
                    passed)
                if not made or made - set(in_place):
                    others.append(line)
            else:
                others.append(line)
    return loops, copies, others


@pytest.mark.parametrize("program", ["_ingest_samples", "_ingest_centroids",
                                     "_mesh_import_routed"])
def test_ingest_converts_no_bin_plane(program, one_chip, mesh_series4,
                                      kernel_admitted):
    """An ingest dispatch costs the chunk, not the rows reserved: held
    ``[S, K]`` the temp's bin planes live column-major on the chip, and
    each of the two bin scatters relaid its whole plane flat (a
    ``copy``) and back (a K-trip ``while`` of plane-sized
    ``dynamic-update-slice``): 30.6 of a dispatch's 40.6 ms at 2^20
    rows whatever the chunk carried (PERF.md, PR 34). With the planes
    held flat nothing outside the guard's drain branch produces a
    plane-sized array but the in-place scatters; the import's
    row-local drain still relays the digest planes around its loop
    (at most six copies: PERF.md section 7), and its loop, the one
    that holds the kernel, is the only one that carries a plane.
    Compiled at the 2^20 rows a chip of the benchmark's cells reserves:
    at this file's ``ROWS`` a plane fits the chip's fast memory and the
    compiler stages it there whole, which is no conversion."""
    rows = 1 << 20
    chunk_i, chunk_f = _i32((CHUNK,), one_chip), _f32((CHUNK,), one_chip)
    if program == "_ingest_samples":
        from veneur_tpu.core.store import _ingest_samples

        digest, temp = (_on(t, one_chip) for t in _digest_state(rows))
        compiled = _ingest_samples.lower(
            digest, temp, chunk_i, chunk_f, chunk_f, _i32((), one_chip),
            _i32((), one_chip), COMPRESSION, True).compile()
        most_copies = 0
    elif program == "_ingest_centroids":
        from veneur_tpu.core.store import _ingest_centroids

        digest, temp = (_on(t, one_chip) for t in _digest_state(rows))
        plane = _f32((rows,), one_chip)
        compiled = _ingest_centroids.lower(
            digest, temp, plane, plane, chunk_i, chunk_f, chunk_f, chunk_i,
            chunk_f, chunk_f, _i32((), one_chip), COMPRESSION,
            True).compile()
        most_copies = 6
    else:
        from veneur_tpu.core.mesh_store import (_digest_specs,
                                                _mesh_import_routed)

        mesh = mesh_series4
        temp_spec, dig_spec, _sk, s = _digest_specs()
        named = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
        digest, temp = _digest_state(4 * rows)  # a device's block: rows
        m_rows = _f32((4 * rows,), named(s))
        st = named(P("series", None))
        stack_f, stack_i = _f32((4, CHUNK), st), _i32((4, CHUNK), st)
        compiled = _mesh_import_routed.lower(
            _on(temp, jax.tree.map(named, temp_spec)),
            _on(digest, jax.tree.map(named, dig_spec)), m_rows, m_rows,
            _i32((4,), named(s)), stack_i, stack_f, stack_f, stack_i,
            stack_f, stack_f, mesh, COMPRESSION).compile()
        most_copies = 6
    loops, copies, others = _plane_traffic(compiled.as_text(), rows * K)
    assert not loops, loops
    assert not others, others
    assert len(copies) <= most_copies, copies
