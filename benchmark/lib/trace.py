"""The reduction from a profiler trace (``.xplane.pb``) to what the
per-layer readers and the result line need. Run as a module in a process
of its own with ``JAX_PLATFORMS=cpu`` once the child has exited (only
``jax.profiler.ProfileData`` reads the file):

    python -m benchmark.lib.trace <trace_dir> <out.json>

Device planes are ``/device:TPU:<n>``; on each, the line ``XLA Modules``
holds one event a dispatched program and ``XLA Ops`` one an operation.
Host planes hold ``TraceAnnotation`` scopes (``veneur.<scope>``). The
plane ``Task Environment`` has no events; its statistics
``profile_start_time`` and ``profile_stop_time`` (ns) say how long the
capture lasted.
"""

from __future__ import annotations

import glob
import json
import os
import sys

MODULES, OPS = "XLA Modules", "XLA Ops"
SCOPE_PREFIX = "veneur."
ENVIRONMENT = "Task Environment"
START, STOP = "profile_start_time", "profile_stop_time"


def union_ns(spans: list) -> int:
    """Total length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(spans):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(spans: list, lo: float, hi: float) -> list:
    """The idle ``(start, end)`` stretches of ``[lo, hi]`` that no span
    covers."""
    out, edge = [], lo
    for s, e in sorted(spans):
        if s > edge:
            out.append((edge, min(s, hi)))
        edge = max(edge, e)
    if hi > edge:
        out.append((edge, hi))
    return out


def reduce_planes(planes: list) -> dict:
    """``planes``: [{"name", "lines": [{"name", "events": [(name,
    start_ns, duration_ns)]}], "stats": {name: value}}]. Returns, for
    every device: busy seconds (union of its operations), per-program
    event durations, the operations that took most time; and the longest
    idle gaps of the busiest device by the host scope that overlaps
    each. The window is what the trace itself says the capture lasted
    (``Task Environment``: stop less start; ``window_from`` says so),
    and only in a trace without that plane the span of the device
    operations and program scopes (the events' own clock does not say
    when the capture began: threads' long waits carry starts from
    before it). What the capture holds outside that span is one more
    idle gap."""
    devices, host_scopes = [], []
    lo, hi = None, None
    capture_s = None
    for plane in planes:
        stats = plane.get("stats", {})
        if plane["name"] == ENVIRONMENT and START in stats and STOP in stats:
            capture_s = (stats[STOP] - stats[START]) / 1e9
        is_device = plane["name"].startswith("/device:") and \
            "CUSTOM" not in plane["name"].upper()
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if is_device and line["name"] in (MODULES, OPS):
                    lo = start if lo is None else min(lo, start)
                    hi = start + dur if hi is None else max(hi, start + dur)
                elif not is_device and name.startswith(SCOPE_PREFIX):
                    host_scopes.append((start, start + dur, name))
        if not is_device:
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        ops = lines.get(OPS, [])
        modules = lines.get(MODULES, [])
        spans = [(s, s + d) for _n, s, d in (ops or modules)]
        by_op: dict = {}
        for name, _s, d in ops:
            name = name.split(" = ")[0][:80]     # not the whole HLO line
            by_op[name] = by_op.get(name, 0) + d
        programs: dict = {}
        for name, s, d in modules:
            programs.setdefault(name, []).append(d / 1e9)
        devices.append({
            "plane": plane["name"], "busy_s": union_ns(spans) / 1e9,
            "spans": spans, "programs": programs,
            "ops": sorted(((n, d / 1e9) for n, d in by_op.items()),
                          key=lambda x: -x[1])[:10]})
    if not devices:
        return {"devices": [], "window_s": 0.0, "idle_gaps": [],
                "window_from": None}
    host_lo = min((s for s, _e, _n in host_scopes), default=lo)
    host_hi = max((e for _s, e, _n in host_scopes), default=hi)
    lo = min(x for x in (lo, host_lo) if x is not None)
    hi = max(x for x in (hi, host_hi) if x is not None)
    busiest = max(devices, key=lambda d: d["busy_s"])
    idle = []
    for s, e in sorted(gaps(busiest["spans"], lo, hi),
                       key=lambda g: g[0] - g[1])[:10]:
        over: dict = {}
        for hs, he, name in host_scopes:
            cover = min(e, he) - max(s, hs)
            if cover > 0:
                over[name] = over.get(name, 0) + cover
        what = max(over, key=over.get) if over else "no veneur scope"
        idle.append((what, (e - s) / 1e9))
    for d in devices:
        del d["spans"]
    window_s, window_from = (hi - lo) / 1e9, "event_span"
    if capture_s is not None:
        if capture_s > window_s:
            idle.append(("before the first and after the last device "
                         "operation of the capture", capture_s - window_s))
            idle = sorted(idle, key=lambda g: -g[1])[:10]
        window_s, window_from = capture_s, "profile_start_stop"
    return {"devices": devices, "window_s": window_s, "idle_gaps": idle,
            "window_from": window_from}


def read_xplanes(trace_dir: str) -> list:
    from jax.profiler import ProfileData

    planes = []
    for path in sorted(glob.glob(os.path.join(
            trace_dir, "**", "*.xplane.pb"), recursive=True)):
        data = ProfileData.from_file(path)
        for plane in data.planes:
            lines = []
            for line in plane.lines:
                keep_all = line.name in (MODULES, OPS)
                events = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                          for ev in line.events
                          if keep_all or ev.name.startswith(SCOPE_PREFIX)]
                if events:
                    lines.append({"name": line.name, "events": events})
            stats = {}
            if plane.name == ENVIRONMENT:
                stats = {k: v for k, v in plane.stats if k in (START, STOP)}
            if lines or stats:
                planes.append({"name": plane.name, "lines": lines,
                               "stats": stats})
    return planes


def main(argv: list) -> int:
    trace_dir, out_path = argv
    planes = read_xplanes(trace_dir)
    reduced = reduce_planes(planes)
    reduced["plane_names"] = [p["name"] for p in planes]
    with open(out_path, "w") as f:
        json.dump(reduced, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
