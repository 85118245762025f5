"""Live debug endpoints: inspect a RUNNING server, not a shutdown dump.

The reference mounts net/http/pprof on every mux
(``/root/reference/http.go:43-48``, ``proxy.go:383-388``) and exposes
mutex/block profile rates (``server.go:217-230``); a wedged instance can
be profiled in place. The Python equivalents here:

    GET /debug/threads              all-thread stack dump (goroutine dump)
    GET /debug/profile?seconds=N    statistical profiler over ALL threads
                                    (samples sys._current_frames; cProfile
                                    only sees the calling thread, which is
                                    useless for a server wedged elsewhere);
                                    output is collapsed-stack lines, flame-
                                    graph-ready, hottest stack first
    GET /debug/vars                 JSON of store/lane/queue depths and
                                    ingest counters (expvar's role), and
                                    each Python thread's CPU seconds
    GET /debug/flush-timeline       last-N flush intervals as stage
                                    trees (veneur_tpu/obs/; server only)
    GET /debug/xprof?seconds=N      on-demand jax.profiler capture —
                                    device kernels labeled by the named
                                    scopes of obs/kernels.py (server
                                    only; gated one-at-a-time + clamped
                                    like /debug/profile)
    GET /debug/fleet                peers' timelines + vars, pulled
                                    keep-last-good (obs/fleet.py)
    GET /debug/trace?id=N           the stitched per-trace cross-hop
                                    view (the fleet trace plane)

Mounted on both the server's OpsServer and the proxy's mux.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import traceback
from collections import Counter
from typing import Dict, Tuple

MAX_PROFILE_SECONDS = 60.0
PROFILE_HZ = 200.0

# one profile at a time: overlapping samplers would double the overhead
# and interleave their results
_profile_lock = threading.Lock()


def dump_threads() -> str:
    """Every live thread's stack, newest frame last (the SIGQUIT /
    /debug/pprof/goroutine?debug=2 equivalent)."""
    names = {t.ident: t for t in threading.enumerate()}
    out = []
    for ident, frame in sys._current_frames().items():
        t = names.get(ident)
        name = t.name if t else "?"
        daemon = " daemon" if t is not None and t.daemon else ""
        out.append(f"--- thread {ident} [{name}]{daemon} ---")
        out.append("".join(traceback.format_stack(frame)).rstrip())
        out.append("")
    return "\n".join(out)


def sample_profile(seconds: float, hz: float = PROFILE_HZ) -> str:
    """Statistical whole-process profile: poll every thread's stack at
    ``hz`` for ``seconds``, aggregate identical stacks. Lines are
    ``frames;joined;by;semicolon <count>`` (collapsed-stack format).

    The sampler excludes ITSELF from what it reports: its own thread
    (by ident) and any thread currently inside ``sample_profile`` (by
    code object — a second /debug/profile request waits up to 1s on
    the lock INSIDE this function, and without the filter that waiter
    shows up as a bogus hot stack in the winner's profile)."""
    seconds = max(0.1, min(float(seconds), MAX_PROFILE_SECONDS))
    interval = 1.0 / hz
    stacks: Counter = Counter()
    me = threading.get_ident()
    my_code = sample_profile.__code__
    samples = 0
    if not _profile_lock.acquire(timeout=1.0):
        return "another profile is already running\n"
    try:
        deadline = time.perf_counter() + seconds
        while time.perf_counter() < deadline:
            for ident, frame in sys._current_frames().items():
                if ident == me:
                    continue
                parts = []
                f = frame
                sampler = False
                while f is not None:
                    code = f.f_code
                    if code is my_code:
                        sampler = True
                        break
                    parts.append(f"{code.co_filename.rsplit('/', 1)[-1]}"
                                 f":{code.co_name}:{f.f_lineno}")
                    f = f.f_back
                if sampler:
                    continue
                stacks[";".join(reversed(parts))] += 1
            samples += 1
            time.sleep(interval)
    finally:
        _profile_lock.release()
    head = (f"# {samples} sampling rounds over {seconds:.1f}s "
            f"at {hz:.0f} Hz; one line per distinct stack\n")
    body = "\n".join(f"{stack} {n}"
                     for stack, n in stacks.most_common())
    return head + body + "\n"


def _group_depths(store) -> Dict[str, Dict[str, int]]:
    out = {}
    for attr in getattr(store, "_GEN_GROUPS", ()):
        g = getattr(store, attr, None)
        if g is None:
            continue
        d = {"series": len(g)}
        for staged, key in (("_fill", "staged_samples"),
                            ("_imp_fill", "staged_imports"),
                            ("_imp_stat_fill", "staged_import_stats")):
            v = getattr(g, staged, None)
            if isinstance(v, int):
                d[key] = v
        cap = getattr(g, "capacity", None)
        if isinstance(cap, int):
            d["capacity"] = cap
        out[attr] = d
    return out


def device_section(store=None) -> dict:
    """The device in the program's own words: what JAX reports, and
    where the histogram digest planes actually live (``.devices()`` of
    the arrays, not ``jax.devices()`` alone — code that has only seen
    one chip may leave everything on the first)."""
    import jax

    devs = jax.devices()
    out = {"platform": devs[0].platform,
           "device_kind": devs[0].device_kind,
           "count": len(devs)}
    # read through __dict__: a debug read must not be the first touch
    # that allocates a lazily-placed group's device state
    group = getattr(store, "histograms", None)
    held = getattr(group, "__dict__", {})
    # a dense group's one digest, or a slab group's first slab
    digest = held.get("digest") or next(iter(held.get("digests") or ()),
                                        None)
    if digest is not None:
        held = sorted(digest.mean.devices(), key=lambda d: d.id)
        out["digest_planes"] = {
            "devices": [str(d) for d in held],
            "platform": held[0].platform,
            "shape": list(digest.mean.shape),
            "dtype": str(digest.mean.dtype)}
    # the CPU backend reports no memory statistics
    peaks = [stats["peak_bytes_in_use"]
             for stats in (d.memory_stats() for d in devs)
             if stats and "peak_bytes_in_use" in stats]
    if peaks:
        out["peak_bytes_in_use"] = max(peaks)
        if len(peaks) > 1:  # a mesh: every chip's own
            out["peak_bytes_by_device"] = peaks
    return out


def name_threads() -> None:
    """Give every live Python thread its ``threading`` name in the
    kernel too (``/proc/self/task/<tid>/comm``, 15 bytes; a process may
    write its own threads'), so ``top -H`` and ``/proc/<pid>/task/*/stat``
    show ``ingest-merger`` where CPython 3.12 leaves ``python``. Done
    from outside the threads because not every one passes through a
    wrapper of ours. The main thread keeps the process's name. Linux
    only; anywhere else, and for a thread that ended meanwhile, the
    write fails and is skipped."""
    main = threading.main_thread()
    for t in threading.enumerate():
        if t is main or t.native_id is None:
            continue
        try:
            with open(f"/proc/self/task/{t.native_id}/comm", "w") as f:
                f.write(t.name[:15])
        except OSError:
            continue


def thread_cpu() -> Dict[str, dict]:
    """``{thread name: {"cpu_s": user + system seconds}}`` for the live
    Python threads, read from ``/proc/self/task/<tid>/stat`` now (so it
    costs nothing between requests); threads that share a name follow
    the first as ``name#2``, ``name#3`` in the order they were created.
    Also (re)names the threads, which catches those started since the
    server turned ready."""
    name_threads()
    tick = os.sysconf("SC_CLK_TCK")
    out: Dict[str, dict] = {}
    seen: Counter = Counter()
    for t in sorted(threading.enumerate(), key=lambda t: t.native_id or 0):
        try:
            with open(f"/proc/self/task/{t.native_id}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
        except OSError:
            continue
        seen[t.name] += 1
        name = t.name if seen[t.name] == 1 else f"{t.name}#{seen[t.name]}"
        # fields 14 and 15 of the line: utime and stime, in ticks
        out[name] = {"cpu_s": (int(fields[11]) + int(fields[12])) / tick}
    return out


def collect_vars(server) -> dict:
    """Store/lane/queue depth snapshot (expvar's role). Every field is
    best-effort: a debug endpoint must never take down the server."""
    out: dict = {"time": time.time(),
                 "threads": len(threading.enumerate())}
    try:
        store = getattr(server, "store", None)
        if store is not None:
            out["store"] = {
                "processed_this_interval": store.processed,
                "imported_this_interval": store.imported,
                "groups": _group_depths(store),
            }
    except Exception as e:  # pragma: no cover - diagnostic only
        out["store_error"] = repr(e)
    try:
        out["device"] = device_section(getattr(server, "store", None))
    except Exception as e:  # pragma: no cover - diagnostic only
        out["device_error"] = repr(e)
    for counter in ("packet_errors", "packet_drops", "spans_dropped"):
        # packet_errors/spans_dropped are read-side sums over sharded
        # per-thread cells + per-lane tallies (veneur_tpu/ingest/):
        # reading here never takes a lock the hot path could contend on
        v = getattr(server, counter, None)
        if v is not None:
            out[counter] = v
    try:
        fleets = getattr(server, "_ingest_fleets", None) or ()
        if fleets:
            out["ingest_fleet"] = [f.snapshot() for f in fleets]
        receivers = getattr(server, "_udp_receivers", None) or ()
        if receivers:
            pkts = sum(r.packets for r in receivers)
            calls = sum(r.syscalls for r in receivers)
            out["udp_readers"] = {
                "packets": pkts, "syscalls": calls,
                "recvmmsg": all(r.using_recvmmsg for r in receivers),
                "syscalls_per_packet": (round(calls / pkts, 4)
                                        if pkts else None)}
    except Exception as e:  # pragma: no cover - diagnostic only
        out["ingest_fleet_error"] = repr(e)
    try:
        workers = getattr(server, "_span_workers", None) or ()
        lanes = []
        for w in workers:
            q = getattr(w, "queue", None) or getattr(w, "_queue", None)
            lanes.append({"depth": q.qsize() if q is not None else None})
        if lanes:
            out["span_lanes"] = lanes
        ew = getattr(server, "event_worker", None)
        q = getattr(ew, "queue", None) or getattr(ew, "_queue", None)
        if q is not None:
            out["event_queue_depth"] = q.qsize()
    except Exception as e:  # pragma: no cover - diagnostic only
        out["lanes_error"] = repr(e)
    threads = None
    imp = getattr(server, "import_server", None)
    if imp is not None:
        from veneur_tpu.forward.grpc_forward import WORKER_THREAD_PREFIX

        threads = thread_cpu()
        out["grpc_import"] = {
            "received": imp.received, "errors": imp.import_errors,
            # the pool's threads as one number: a reader of one path
            # cannot sum obs.threads' grpc-import_0, _1, ... itself
            "workers_cpu_s": round(sum(
                t["cpu_s"] for name, t in threads.items()
                if name.startswith(WORKER_THREAD_PREFIX)), 4)}
    nimp = getattr(server, "native_import_server", None)
    if nimp is not None:
        out["native_import"] = {"received": nimp.received,
                                "errors": nimp.import_errors}
    ops = getattr(server, "ops_server", None)
    pool = getattr(ops, "import_pool", None)
    if pool is not None:
        out["http_import"] = {"queue_depth": pool.qsize(),
                              "merged_batches": pool.merged_batches,
                              "shed_batches": pool.shed}
    try:
        # overload / degradation state (the ladder of
        # docs/resilience.md): admission level + sheds, per-reason
        # quarantine, per-group spill/scrub tallies, compute breaker
        ov = getattr(server, "overload", None)
        store = getattr(server, "store", None)
        section: dict = {}
        if ov is not None:
            section.update(ov.snapshot())
        if store is not None:
            q = getattr(store, "quarantine", None)
            if q is not None:
                section["quarantined"] = q.snapshot()
            compute = getattr(store, "compute", None)
            if compute is not None:
                section["compute"] = compute.snapshot()
            spilled = {}
            for attr in getattr(store, "_GEN_GROUPS", ()):
                g = getattr(store, attr, None)
                if g is not None and getattr(g, "spilled", 0):
                    spilled[attr] = g.spilled
            if spilled:
                section["spilled_this_interval"] = spilled
            section["max_series"] = getattr(store, "max_series", 0)
        if section:
            out["overload"] = section
        if hasattr(server, "degradation"):
            out["degraded"] = server.degradation()
    except Exception as e:  # pragma: no cover - diagnostic only
        out["overload_error"] = repr(e)
    try:
        # fleet mode (veneur_tpu/fleet/): mesh axes + per-group
        # per-shard row occupancy and balance ratio — shard skew must
        # be visible before it becomes one chip's OOM
        store = getattr(server, "store", None)
        if store is not None and getattr(store, "mesh", None) is not None:
            from veneur_tpu.fleet import fleet_snapshot

            out["mesh"] = fleet_snapshot(store)
    except Exception as e:  # pragma: no cover - diagnostic only
        out["mesh_error"] = repr(e)
    try:
        # elastic resharding (veneur_tpu/fleet/handoff.py): membership,
        # handoff epoch, moved/requeued/received tallies and breakers
        mgr = getattr(server, "handoff_manager", None)
        if mgr is not None:
            out["handoff"] = mgr.snapshot()
    except Exception as e:  # pragma: no cover - diagnostic only
        out["handoff_error"] = repr(e)
    try:
        # flush-interval observability (veneur_tpu/obs/): timeline ring
        # summary + per-scope kernel dispatches and live compiled-
        # variant counts (the recompile lint pass's inventory,
        # observed). The kernel counters run regardless of obs_enabled
        # (they also back /debug/xprof), so they are reported even when
        # the timeline ring is off.
        if hasattr(server, "obs_timeline"):
            from veneur_tpu.obs import kernels

            section = {"kernels": kernels.snapshot(),
                       "threads": threads or thread_cpu()}
            timeline = server.obs_timeline
            if timeline is not None:
                section["timeline"] = timeline.snapshot()
            hops = getattr(server, "obs_hops", None)
            if hops is not None:
                section["hops"] = hops.snapshot()
            agg = getattr(server, "fleet_aggregator", None)
            if agg is not None:
                section["fleet"] = agg.snapshot()
            out["obs"] = section
    except Exception as e:  # pragma: no cover - diagnostic only
        out["obs_error"] = repr(e)
    return out


def mount(add_route, server=None, extra_vars=None):
    """Register the /debug/* routes on a mux via its add_route(path, fn).

    Handlers receive the parsed query dict and return
    ``(status, body, content_type[, headers])`` — the optional fourth
    element carries extra response headers (the profile handler sets
    ``Content-Disposition`` so its output drops straight into
    flamegraph tooling). ``extra_vars`` is an optional callable
    returning a dict merged into /debug/vars (the proxy passes its
    ring stats)."""

    def threads(query) -> Tuple[int, str, str]:
        return 200, dump_threads(), "text/plain"

    def profile(query):
        try:
            seconds = float(query.get("seconds", "5"))
        except ValueError:
            return 400, "seconds must be a number", "text/plain"
        body = sample_profile(seconds)
        # a curl -O / browser fetch lands as a .collapsed file that
        # flamegraph.pl / speedscope / inferno ingest directly
        return (200, body, "text/plain",
                {"Content-Disposition":
                 'attachment; filename="veneur-profile.collapsed"'})

    def dvars(query) -> Tuple[int, str, str]:
        data = collect_vars(server) if server is not None else {
            "time": time.time(),
            "threads": len(threading.enumerate())}
        if extra_vars is not None:
            try:
                data.update(extra_vars())
            except Exception as e:  # pragma: no cover
                data["extra_vars_error"] = repr(e)
        return 200, json.dumps(data, default=str), "application/json"

    def flush_timeline(query) -> Tuple[int, str, str]:
        timeline = getattr(server, "obs_timeline", None)
        if timeline is None:
            return (404, "flush timeline disabled (obs_enabled: false)",
                    "text/plain")
        return timeline.handler(query)

    def xprof(query) -> Tuple[int, str, str]:
        from veneur_tpu.obs import kernels

        try:
            seconds = float(query.get("seconds", "2"))
        except ValueError:
            return 400, "seconds must be a number", "text/plain"
        return kernels.capture_xprof(seconds)

    add_route("/debug/threads", threads)
    add_route("/debug/profile", profile)
    add_route("/debug/vars", dvars)
    if server is not None and hasattr(server, "obs_timeline"):
        # server-only observability routes (the proxy has no flush
        # pipeline and no device programs to capture)
        add_route("/debug/flush-timeline", flush_timeline)
        add_route("/debug/xprof", xprof)
        agg = getattr(server, "fleet_aggregator", None)
        if agg is not None:
            # the fleet trace plane (obs/fleet.py): peer aggregation +
            # the stitched per-trace hop view
            add_route("/debug/fleet", agg.fleet_route)
            add_route("/debug/trace", agg.trace_route)
