"""Device observability: profiler scopes, compile/dispatch counters,
and the on-demand xprof capture.

The recompile lint pass (lint/recompile.py) proves statically which
compiled programs exist and what can retrigger their compilation; this
module surfaces the same inventory LIVE:

- :func:`scope` wraps every host-side dispatch choke point in a
  ``jax.profiler.TraceAnnotation`` named scope, so an xprof capture of
  a running server labels device work by pipeline stage instead of by
  mangled HLO module names. Entering a scope also counts a dispatch.
- :data:`PROGRAM_SCOPES` maps every program in the generated
  compiled-program inventory (docs/static-analysis.md) to the scope
  that covers its dispatches; tests drift-check the mapping against
  the lint pass exactly like the docs table, so a new program cannot
  ship unannotated.
- :func:`compile_snapshot` reads each program's live compiled-variant
  count (``PjitFunction._cache_size``), turning the lint pass's
  "bounded static args" proof into an observable number: a variant
  count that grows interval over interval is a recompile leak.
- :func:`host_scope` labels the leaves where the HOST works between
  device operations (the merger's chunk, the generation swap, a
  group's fetch, the serializer lane, a sink's serialize and POST), so
  a capture's idle gaps can be put down to what the host did in them.
- :func:`capture_xprof` runs a bounded ``jax.profiler``
  start/stop_trace capture for ``GET /debug/xprof?seconds=N`` —
  one at a time, clamped, like ``/debug/profile`` — without the
  Python tracer, so it can run over a whole interval under load.
"""

from __future__ import annotations

import importlib
import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Dict, Optional, Tuple

import jax
from jax.profiler import TraceAnnotation

# profiler scope names carry this prefix in xprof captures
SCOPE_PREFIX = "veneur."

MAX_XPROF_SECONDS = 30.0

# /debug/xprof's host tracer level: TraceMe level 1 is where
# TraceAnnotation (the veneur.* scopes) records, and the device's
# ``XLA Modules`` / ``XLA Ops`` lines come from the device tracer, which
# the level does not gate; 0 drops the scopes, 2 (jax's default) adds
# the runtime's own per-call events. The Python tracer is off: it hooks
# every Python call of every thread, which at a merger's rate of calls
# is what made a capture under load shed (PERF.md, PR 28 / PR 29).
XPROF_HOST_TRACER_LEVEL = 1

# one capture at a time (mirrors debug._profile_lock for /debug/profile)
_xprof_lock = threading.Lock()

# scope -> dispatch count. Plain dict int bumps: every writer holds the
# GIL across the read-modify-write (single bytecode effects are close
# enough for telemetry; dispatches are chunk-scale, not packet-scale).
_dispatches: Dict[str, int] = {}

# what compiling cost this process, in JAX's own accounting: seconds
# inside the backend compile (a persistent-cache hit counts the load it
# paid instead), programs compiled, and persistent-cache hits. A
# bring-up reads these to tell set-up from steady state.
_compile = {"seconds": 0.0, "programs": 0, "cache_hits": 0}


def _on_duration(event: str, seconds: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _compile["seconds"] += seconds
        _compile["programs"] += 1


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _compile["cache_hits"] += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)

# ---------------------------------------------------------------------------
# the scope coverage map — drift-checked against the lint inventory
# ---------------------------------------------------------------------------

# Every compiled program in the static-analysis inventory, mapped to
# the named scope whose dispatch site covers it (tests/test_obs.py
# fails when the inventory and this map drift apart — same contract as
# the generated docs table). Third field: the importable module-level
# jit binding for compile counting, or None when the program has no
# module-level PjitFunction.
PROGRAM_SCOPES: Dict[str, Tuple[str, Optional[Tuple[str, str]]]] = {
    "veneur_tpu/core/store.py::_flush_digests":
        ("flush.digest.dense", ("veneur_tpu.core.store", "_flush_digests")),
    "veneur_tpu/core/store.py::_ingest_samples":
        ("drain.digest.dense", ("veneur_tpu.core.store", "_ingest_samples")),
    "veneur_tpu/core/store.py::_ingest_centroids":
        ("drain.digest.dense",
         ("veneur_tpu.core.store", "_ingest_centroids")),
    "veneur_tpu/core/store.py::_prefix_rows":
        ("flush.digest.dense", ("veneur_tpu.core.store", "_prefix_rows")),
    "veneur_tpu/ops/tdigest_pallas.py::_compress_presorted_pallas":
        ("flush.digest.dense",
         ("veneur_tpu.ops.tdigest_pallas", "_compress_presorted_pallas")),
    "veneur_tpu/ops/tdigest_pallas.py::_drain_quantile_pallas":
        ("flush.digest.dense",
         ("veneur_tpu.ops.tdigest_pallas", "_drain_quantile_pallas")),
    "veneur_tpu/core/slab.py::_ingest_slab":
        ("drain.digest.slab", ("veneur_tpu.core.slab", "_ingest_slab")),
    "veneur_tpu/core/slab.py::_import_slab":
        ("drain.digest.slab", ("veneur_tpu.core.slab", "_import_slab")),
    "veneur_tpu/core/slab.py::_merge_slab":
        ("drain.digest.slab", ("veneur_tpu.core.slab", "_merge_slab")),
    "veneur_tpu/core/slab.py::_flush_slab":
        ("flush.digest.slab", ("veneur_tpu.core.slab", "_flush_slab")),
    "veneur_tpu/core/slab.py::_quantile_slab":
        ("flush.digest.slab", ("veneur_tpu.core.slab", "_quantile_slab")),
    "veneur_tpu/core/slab.py::_pack_slab":
        ("flush.digest.slab", ("veneur_tpu.core.slab", "_pack_slab")),
    "veneur_tpu/core/slab.py::_slice_pack":
        ("flush.digest.slab", ("veneur_tpu.core.slab", "_slice_pack")),
    "veneur_tpu/core/slab.py::_gather_pack":
        ("flush.digest.slab", ("veneur_tpu.core.slab", "_gather_pack")),
    "veneur_tpu/core/sets.py::_sparse_set_estimate":
        ("flush.set.sparse",
         ("veneur_tpu.core.sets", "_sparse_set_estimate")),
    "veneur_tpu/core/sets.py::_sparse_set_registers":
        ("flush.set.sparse",
         ("veneur_tpu.core.sets", "_sparse_set_registers")),
    "veneur_tpu/core/tiered.py::_pool_ingest":
        ("drain.digest.tiered", ("veneur_tpu.core.tiered", "_pool_ingest")),
    "veneur_tpu/core/tiered.py::_pool_import":
        ("drain.digest.tiered", ("veneur_tpu.core.tiered", "_pool_import")),
    "veneur_tpu/core/tiered.py::_pool_restore_stats":
        ("drain.digest.tiered",
         ("veneur_tpu.core.tiered", "_pool_restore_stats")),
    "veneur_tpu/core/tiered.py::_promote_rows":
        ("drain.digest.tiered", ("veneur_tpu.core.tiered", "_promote_rows")),
    "veneur_tpu/core/tiered.py::_pool_flush":
        ("flush.digest.tiered", ("veneur_tpu.core.tiered", "_pool_flush")),
    # fleet mode (veneur_tpu/fleet/, core/mesh_store.py): the sharded
    # shard_map programs — module-level jit defs with the Mesh static,
    # so the inventory drift-check covers them like any other program
    "veneur_tpu/core/mesh_store.py::_mesh_ingest_samples":
        ("drain.digest.mesh",
         ("veneur_tpu.core.mesh_store", "_mesh_ingest_samples")),
    "veneur_tpu/core/mesh_store.py::_mesh_import_routed":
        ("drain.digest.mesh",
         ("veneur_tpu.core.mesh_store", "_mesh_import_routed")),
    "veneur_tpu/core/mesh_store.py::_mesh_init_digests":
        ("flush.digest.mesh",
         ("veneur_tpu.core.mesh_store", "_mesh_init_digests")),
    "veneur_tpu/core/mesh_store.py::_mesh_zero_registers":
        ("flush.set.mesh",
         ("veneur_tpu.core.mesh_store", "_mesh_zero_registers")),
    "veneur_tpu/core/mesh_store.py::_blocked_pad":
        ("drain.digest.mesh",
         ("veneur_tpu.core.mesh_store", "_blocked_pad")),
    "veneur_tpu/core/mesh_store.py::_mesh_flush_digests":
        ("flush.digest.mesh",
         ("veneur_tpu.core.mesh_store", "_mesh_flush_digests")),
    "veneur_tpu/core/mesh_store.py::_mesh_ingest_hashes":
        ("drain.set.mesh",
         ("veneur_tpu.core.mesh_store", "_mesh_ingest_hashes")),
    "veneur_tpu/core/mesh_store.py::_mesh_merge_registers":
        ("drain.set.mesh",
         ("veneur_tpu.core.mesh_store", "_mesh_merge_registers")),
    "veneur_tpu/core/mesh_store.py::_mesh_estimate":
        ("flush.set.mesh",
         ("veneur_tpu.core.mesh_store", "_mesh_estimate")),
    "veneur_tpu/fleet/mesh_tiered.py::_mesh_pool_ingest":
        ("drain.digest.mesh_tiered",
         ("veneur_tpu.fleet.mesh_tiered", "_mesh_pool_ingest")),
    "veneur_tpu/fleet/mesh_tiered.py::_mesh_pool_import":
        ("drain.digest.mesh_tiered",
         ("veneur_tpu.fleet.mesh_tiered", "_mesh_pool_import")),
    "veneur_tpu/fleet/mesh_tiered.py::_mesh_promote_rows":
        ("drain.digest.mesh_tiered",
         ("veneur_tpu.fleet.mesh_tiered", "_mesh_promote_rows")),
    "veneur_tpu/fleet/mesh_tiered.py::_mesh_pool_restore_stats":
        ("drain.digest.mesh_tiered",
         ("veneur_tpu.fleet.mesh_tiered", "_mesh_pool_restore_stats")),
    "veneur_tpu/fleet/mesh_tiered.py::_mesh_pool_flush":
        ("flush.digest.mesh_tiered",
         ("veneur_tpu.fleet.mesh_tiered", "_mesh_pool_flush")),
}


@contextmanager
def scope(name: str):
    """One named dispatch region: counts the dispatch and labels the
    region in xprof captures. Cheap
    enough for the per-chunk drain paths (a dict bump + one context
    object); NOT for per-packet paths."""
    _dispatches[name] = _dispatches.get(name, 0) + 1
    with TraceAnnotation(SCOPE_PREFIX + name):
        yield


def host_scope(name: str) -> TraceAnnotation:
    """One named stretch of HOST work between device operations, for
    the profiler's trace only: not a dispatch (not counted, not in
    PROGRAM_SCOPES). Put it on leaves, never inside another ``veneur.*``
    scope — a trace's idle gap is named by the scope that overlaps it
    most, so an enclosing scope would swallow its children. Inactive
    (no capture running) it costs one atomic load."""
    return TraceAnnotation(SCOPE_PREFIX + name)


def dispatch_snapshot() -> Dict[str, int]:
    return dict(_dispatches)


def compile_snapshot() -> Dict[str, Optional[int]]:
    """program -> live compiled-variant count (None = the program has
    no module-level jit binding to read). Only programs whose module is
    ALREADY imported are counted — a debug read must not pull the slab
    or tiered stack into a dense-only process."""
    import sys

    out: Dict[str, Optional[int]] = {}
    for program, (_scope_name, binding) in PROGRAM_SCOPES.items():
        count: Optional[int] = None
        if binding is not None and binding[0] in sys.modules:
            fn = getattr(importlib.import_module(binding[0]), binding[1],
                         None)
            cache_size = getattr(fn, "_cache_size", None)
            if cache_size is not None:
                try:
                    count = int(cache_size())
                except Exception:  # pragma: no cover - jax API drift
                    count = None
        out[program] = count
    return out


def compiles_total() -> int:
    """Sum of live compiled variants across tracked programs (the
    interval-delta self-metric veneur.obs.kernel_compiles_total)."""
    return sum(v for v in compile_snapshot().values() if v)


def snapshot() -> dict:
    """The /debug/vars "kernels" section: dispatches per scope plus
    compiled-variant counts per inventory program and the process's
    compile bill."""
    from veneur_tpu.ops import tdigest_pallas

    return {"dispatches": dispatch_snapshot(),
            "compiled_variants": compile_snapshot(),
            "kernel_traces": dict(tdigest_pallas.TRACED),
            "compile": dict(_compile, seconds=round(_compile["seconds"],
                                                    3))}


def capture_xprof(seconds: float, base_dir: Optional[str] = None) -> tuple:
    """Run one bounded xprof capture; returns the (status, body, ctype)
    triple for the /debug/xprof route. The trace lands on local disk
    (xprof traces are directory trees, not a streamable body) and the
    response names the directory + files so an operator can pull them
    with scp / TensorBoard's profile plugin."""
    seconds = max(0.05, min(float(seconds), MAX_XPROF_SECONDS))
    if not _xprof_lock.acquire(blocking=False):
        return 409, "another xprof capture is already running", "text/plain"
    try:
        import tempfile

        import jax

        trace_dir = tempfile.mkdtemp(prefix="veneur-xprof-", dir=base_dir)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = XPROF_HOST_TRACER_LEVEL
        t0 = time.perf_counter()
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()
        took = time.perf_counter() - t0
        files = []
        for root, _dirs, names in os.walk(trace_dir):
            for name in names:
                path = os.path.join(root, name)
                files.append({"path": path,
                              "bytes": os.path.getsize(path)})
        body = json.dumps({"trace_dir": trace_dir,
                           "seconds": round(took, 3),
                           "files": files,
                           "scopes": sorted({s for s, _ in
                                             PROGRAM_SCOPES.values()})})
        return 200, body, "application/json"
    except Exception as e:  # profiler unavailable / double-start etc.
        return 500, f"xprof capture failed: {e!r}", "text/plain"
    finally:
        _xprof_lock.release()
