#!/usr/bin/env python3
"""The benchmark's command: one run of one cell.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Starts ``python -m veneur_tpu.cli.server`` as a child (the only process
that holds the chip; this parent never imports JAX while it lives) on
the cell's configuration, with the Datadog sink pointed at a receiver
on loopback. Sends the cell's traffic through the mix's feed (datagrams
at the statsd port, or forwards at the import port), one round an
interval from each tick, for ``seconds // interval`` whole intervals
after two warm-up rounds of the same shape. Then compares what the
receiver got with the float64 reference and prints the contract's line
last. Everything else goes on earlier lines, one JSON object each, and
into ``benchmark/out/<workload>/``.

A cell, its configuration, its traffic mix, the mix's generator and
feed, every per-layer metric and its reader are files found by name
(``README.md``).
"""

from __future__ import annotations

import argparse
import json
import os
import pickle
import shutil
import socket
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark.lib import cells, emissions, reference  # noqa: E402
from benchmark.lib.child import Child, Watcher  # noqa: E402
from benchmark.lib.load import Receiver, free_port  # noqa: E402

NO_ACCELERATOR = 3
# Two warm-up rounds of the window's own shape: the first loaded flush
# compiles the programs whose shapes follow this interval's series, the
# second those that follow the interval before (PERF.md, findings).
WARM_ROUNDS = 2
# A ``--trace 1`` capture starts this long after the last round's last
# line is due, inside the guard, and lasts the rest of the guard, that
# round's flush and this share of an interval after it. The profiler's
# host tracing slows the merger: a whole interval of it under load sheds,
# and one second of it before the tick made lines miss the tick (PERF.md,
# findings). After the last round nothing is sent that it could delay.
TRACE_AFTER_LAST_LINE_S = 0.1
TRACE_TAIL_SHARE = 0.6
CHIP_ONLY = ("platform", "rung", "kernel_compiled")


class Report:
    """One JSON object a line on stdout and in ``report.jsonl``."""

    def __init__(self, path: str, quiet: bool = False):
        self._file = open(path, "w")
        self.quiet = quiet
        self.failed: list = []

    def line(self, **obj) -> None:
        text = json.dumps(obj, default=str)
        if not self.quiet:
            print(text, flush=True)
        self._file.write(text + "\n")
        self._file.flush()

    def check(self, name: str, ok, **detail) -> bool:
        if not ok:
            self.failed.append(name)
        self.line(check=name, ok=bool(ok), **detail)
        return bool(ok)

    def close(self):
        self._file.close()


def worst_lag(send_log: list) -> float:
    """How late the open-loop sender ran at worst: sent minus due."""
    return max((sent - due for due, sent, _n in send_log), default=0.0)


def _xprof(child: Child, seconds: float, box: dict) -> None:
    try:
        box["reply"] = child.get(f"/debug/xprof?seconds={seconds}",
                                 timeout=seconds + 120.0)
    except (OSError, ValueError) as e:
        box["error"] = repr(e)


def _reduce_trace(trace_dir: str, out_path: str) -> dict:
    """The trace is reduced in a process of its own, on the CPU, once
    the child has let go of the chip."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    subprocess.run([sys.executable, "-m", "benchmark.lib.trace", trace_dir,
                    out_path], cwd=cells.ROOT, env=env, check=True,
                   timeout=240)
    return cells.read_json(out_path)


def read_input(cell: cells.Cell, kept: dict, rounds: dict) -> tuple:
    """What a run's input says once the child has exited: the receiver's
    bodies, the flush timeline and the send logs (``kept``; a run with
    ``--keep-input`` writes them out, ``tools/replay.py`` reads them
    again) against the rounds that were sent. Returns the emissions, the
    measures taken from outside, the comparison's verdict and the
    ``compared`` line of the report."""
    groups = cell.traffic["groups"]
    percentiles = cell.config["server"]["percentiles"]
    interval = cell.interval_s
    span = interval - float(cell.traffic["guard_s"])
    window, timeline, bodies = kept["window"], kept["timeline"], kept["bodies"]
    n_rounds = kept["n_rounds"]
    ticks = [e["wall_start"] for e in timeline]
    owner = emissions.assign_emissions(bodies, ticks)
    ems = emissions.parse(bodies, owner, len(timeline), groups,
                          percentiles, interval)
    # the warm-up rounds too: a line of theirs that slips into the
    # window is late, and the run's totals say so
    by_round = {window.start - 1 + k: rounds[k]
                for k in range(1 - WARM_ROUNDS, n_rounds + 1)}
    warm = range(window.start - WARM_ROUNDS, window.start)
    carried_in = max(0, sum(n for _due, _sent, n in kept["warm_log"]) - sum(
        emissions.lines_in(ems[k], groups, by_round[k]) for k in warm))
    e2e = emissions.end_to_end(kept["send_log"], ems, ticks, window, groups,
                               carried_in, by_round)
    verdict = reference.compare(
        ems, by_round, window, groups, percentiles, cell.config,
        {window.start - 1 + k: (tick, span, interval)
         for k, tick in kept["started"].items()})
    compared = dict(
        bodies=len(bodies), rows=sum(e.rows for e in ems),
        bodies_late=sum(1 for b, k in zip(bodies, owner)
                        if 0 <= k < len(ticks) - 1
                        and b[0] >= ticks[k + 1]),
        lines_late=verdict["lines_late"],
        lines_sent=e2e["lines_sent"], lines_held=e2e["lines_held"],
        lines_carried_in=carried_in, rank_errors=verdict["rank_errors"],
        emissions=[{"bodies": e.bodies, "rows": e.rows,
                    "lines": emissions.lines_in(e, groups, by_round.get(k))}
                   for k, e in enumerate(ems)],
        flush_to_last_body_each_s=e2e["flush_to_last_body_each_s"],
        measures=e2e["measures"])
    return ems, e2e, verdict, compared


def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool,
             rep: Report, out_dir: str, rehearse: bool = False,
             receiver: Receiver = None, keep_trace: bool = False,
             keep_input: bool = False) -> dict:
    """Drives one run; returns the contract's line as a dict, or
    ``{"refused": why}`` where the machine cannot run the cell."""
    traffic, interval = cell.traffic, cell.interval_s
    gen = cell.generator()
    span = interval - float(traffic["guard_s"])
    n_rounds = max(1, int(seconds // interval))

    receiver = receiver or Receiver()
    receiver.start()
    feed_kind = cell.feed()
    ports = {name: free_port(kind) for name, kind in feed_kind.PORTS.items()}
    ports.update(http_port=free_port(socket.SOCK_STREAM),
                 receiver_port=receiver.port)
    config_path = os.path.join(out_dir, "config.yaml")
    with open(config_path, "w") as f:
        f.write(cell.server_config_text(ports))
    child = Child(config_path, os.path.join(out_dir, "server.log"),
                  ports["http_port"], dict(os.environ))
    feed = feed_kind.Feed(ports, traffic)
    watcher = Watcher(child, cell.polled_sections())
    box: dict = {}
    tracer = None
    try:
        # rounds 0, -1, ... are the warm-up; built while the child starts
        t0 = time.time()
        rounds = {k: gen.build(traffic, seed, k + WARM_ROUNDS)
                  for k in range(1 - WARM_ROUNDS, n_rounds + 1)}
        rep.line(phase="load_built", rounds=len(rounds),
                 lines_per_round=rounds[0].lines,
                 units_per_round=len(rounds[0].units),
                 lines_per_s=round(rounds[0].lines / interval, 1),
                 seconds=round(time.time() - t0, 2))
        if not child.wait_ready(900.0):
            rep.check("child_ready", False)
            return {"refused": "the server did not become ready"}
        ready_s = time.time() - child.started
        device = child.get("/debug/vars").get("device", {})
        on_chip = (device.get("platform") == "tpu"
                   and device.get("count", 0) >= cell.chips)
        if not on_chip and not rehearse:
            rep.line(refused="no accelerator, or fewer chips than the "
                     "cell asks for", device=device, chips=cell.chips)
            return {"refused": "no accelerator"}

        # -- set-up: every metric type once, the first flush (the flush
        #    program compiles or loads), then WARM_ROUNDS rounds of the
        #    window's own shape, so that nothing compiles inside it --
        first = child.published()
        feed.send(gen.warm_lines(traffic), time.time(), 0.0)
        tick = child.wait_flushes(first + 1, 900.0)
        warm_log = []
        started = {}                # when each round's span began
        for w in range(WARM_ROUNDS):
            started[1 - WARM_ROUNDS + w] = tick
            warm_log += feed.send(rounds[1 - WARM_ROUNDS + w].units,
                                  tick, span)
            tick = child.wait_flushes(first + 2 + w, 4 * interval + 900.0,
                                      quiet_until=tick + span)
        first += WARM_ROUNDS - 1
        vars_start = child.get("/debug/vars")
        cpu_start = child.cpu_seconds()
        threads_start = child.thread_cpu_seconds()
        window_start = tick
        set_up_seconds = window_start - child.started
        rep.line(phase="set_up", ready_s=round(ready_s, 2),
                 window_after_start_s=round(set_up_seconds, 2),
                 compile=vars_start["obs"]["kernels"]["compile"],
                 device=vars_start.get("device"))
        watcher.start()

        # -- the window: one round an interval, each from its tick --
        send_log: list = []
        trace_seconds = min(30.0, float(traffic["guard_s"])
                            + TRACE_TAIL_SHARE * interval)
        for k in range(1, n_rounds + 1):
            if trace and k == n_rounds:
                tracer = threading.Timer(
                    max(0.0, tick + span + TRACE_AFTER_LAST_LINE_S
                        - time.time()),
                    _xprof, args=(child, trace_seconds, box))
                tracer.daemon = True
                tracer.start()
            started[k] = tick
            sent = feed.send(rounds[k].units, tick, span)
            rep.line(phase="round", k=k, worst_lag_s=worst_lag(sent))
            send_log += sent
            tick = child.wait_flushes(first + 2 + k, 4 * interval + 900.0,
                                      quiet_until=tick + span)
        cpu_end = child.cpu_seconds()
        threads_end = child.thread_cpu_seconds()
        vars_end = child.get("/debug/vars")
        window_end = tick
        # -- one more emission for stragglers; the child's final flush
        #    at SIGTERM takes whatever a lane still held --
        child.wait_flushes(first + 3 + n_rounds, 4 * interval + 900.0,
                           quiet_until=tick + span)
        watcher.stop()
        if tracer is not None:
            tracer.join(timeout=trace_seconds + 150.0)
        vars_last = child.get("/debug/vars")
        timeline = child.timeline()
    finally:
        watcher.stop()
        feed.close()
        rc = child.stop()
        receiver.stop()

    # -- the child has exited: parse, compare, reduce --
    window = range(first + 2, first + 2 + n_rounds)
    kept = {"seed": seed, "n_rounds": n_rounds, "window": window,
            "bodies": sorted(receiver.bodies, key=lambda b: b[0]),
            "timeline": timeline, "send_log": send_log,
            "warm_log": warm_log, "started": started}
    if keep_input:
        with open(os.path.join(out_dir, "input.pickle"), "wb") as f:
            pickle.dump(kept, f)
    t0 = time.time()
    ems, e2e, verdict, compared = read_input(cell, kept, rounds)
    rep.line(phase="compared", seconds=round(time.time() - t0, 2),
             **compared)

    lines_sent = e2e["lines_sent"]
    # an import that arrives while a flush runs waits for it
    feed.checks(rep, vars_last, interval + max(
        e["total_duration_ns"] for e in timeline) / 1e9)
    _run_checks(rep, cell, timeline, window, vars_last, watcher, child, rc,
                ems)

    numbers = dict(verdict["numbers"])
    failed_checks = [c for c in rep.failed
                     if not (rehearse and c in CHIP_ONLY)]
    numbers["run_checks_failed"] = {"value": len(failed_checks),
                                    "limit": 0}
    correct = all(n["value"] <= n["limit"] for n in numbers.values())

    device = vars_last.get("device", {})
    dev_out = {"platform": device.get("platform"),
               "kind": device.get("device_kind"),
               "count": device.get("count"),
               "memory_peak_bytes": device.get("peak_bytes_in_use")}
    # what the harness measures itself; benchmark/end_to_end/<name>.json
    # says which of them an end-to-end metric is
    measures = dict(e2e["measures"],
                    child_cpu_s_per_mline=(cpu_end - cpu_start)
                    / (lines_sent / 1e6),
                    child_start_to_window_s=set_up_seconds)
    values = {m["name"]: measures[cell.measure_of(m)]
              for m in cell.end_to_end()}
    line = {"correct": bool(correct), "attempted": lines_sent,
            "failed": numbers["lines_unaccounted"]["value"]}
    if not trace:
        line["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end()}
    else:
        reduced = None
        if box.get("reply"):
            trace_dir = box["reply"]["trace_dir"]
            try:
                reduced = _reduce_trace(
                    trace_dir, os.path.join(out_dir, "trace_reduced.json"))
            finally:
                if keep_trace:
                    shutil.move(trace_dir, os.path.join(out_dir, "trace"))
                else:
                    shutil.rmtree(trace_dir, ignore_errors=True)
        rep.line(phase="traced", error=box.get("error"),
                 asked_s=trace_seconds,
                 reply_s=box.get("reply", {}).get("seconds"),
                 window_s=reduced and reduced["window_s"],
                 window_from=reduced and reduced["window_from"],
                 files=box.get("reply", {}).get("files"))
        ctx = {"timeline": [timeline[k] for k in window],
               "vars_start": vars_start, "vars_end": vars_end,
               "polls": watcher.polls, "trace": reduced,
               "config": cell.config, "traffic": traffic, "notes": [],
               "device_kind": device.get("device_kind"),
               "harness": {"generator_worst_lag_s": worst_lag(send_log)}}
        metrics = {}
        for m, spec in cell.per_layer():
            value = cells.reader(spec["reader"]).read(spec["args"], ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        line["metrics"] = metrics
        rep.line(phase="per_layer", notes=ctx["notes"],
                 end_to_end_while_traced=values)
        if reduced and reduced["devices"]:
            devs = reduced["devices"]
            dev_out["busy_s"] = sum(d["busy_s"] for d in devs) / len(devs)
            dev_out["window_s"] = reduced["window_s"]
            busiest = max(devs, key=lambda d: d["busy_s"])
            line["breakdown"] = {"device_ops": busiest["ops"],
                                 "idle_gaps": reduced["idle_gaps"]}
    line["device"] = dev_out
    line["compared"] = numbers
    with open(os.path.join(out_dir, "timeline.json"), "w") as f:
        json.dump([timeline[k] for k in window], f)
    by_thread = [(f"{name}/{tid}",
                  round(secs - threads_start.get(tid, (name, 0.0))[1], 2))
                 for tid, (name, secs) in threads_end.items()]
    rep.line(phase="child_cpu", window_cpu_s=cpu_end - cpu_start,
             by_thread_s=sorted(by_thread, key=lambda x: -x[1])[:12])
    rep.line(failed_checks=rep.failed, window_s=window_end - window_start,
             rounds=n_rounds, generator_worst_lag_s=worst_lag(send_log),
             backlog_polls=[f["totals"].get("backlog") for p in
                            watcher.polls for f in p["ingest_fleet"][:1]],
             pressure_max=max((p["overload"]["pressure"]
                               for p in watcher.polls), default=0.0),
             flush_wall_s=[timeline[k]["total_duration_ns"] / 1e9
                           for k in window])
    if not on_chip:
        return {"refused": "no accelerator", "rehearsed": line,
                "chip_only_failed": [c for c in rep.failed
                                     if c in CHIP_ONLY],
                "other_failed": failed_checks}
    return line


def _run_checks(rep: Report, cell, timeline, window, v, watcher, child, rc,
                ems) -> None:
    """What the configuration guarantees besides the numbers, whatever
    feeds it: nothing refused, no hidden fallback.
    The feed's own checks say that nothing was lost on its way in."""
    ov = v["overload"]
    rep.check("nothing_shed_quarantined_spilled",
              not any(ov["shed"].values())
              and not any(ov["quarantined"].values())
              and not watcher.spilled,
              shed=ov["shed"], quarantined=ov["quarantined"],
              spilled=watcher.spilled)
    rep.check("overload_level_zero", watcher.max_level == 0
              and ov["level"] == 0 and ov["level_changes"] == 0,
              max_level_seen=watcher.max_level,
              level_changes=ov["level_changes"],
              polls_failed=watcher.errors)
    device = v.get("device", {})
    # a flush's fresh generation places its planes on first write, so a
    # read between intervals may find none: the watcher keeps the last
    planes = device.get("digest_planes") or watcher.digest_planes
    # a cell on several chips is one store sharded over a mesh of them
    mesh = v.get("mesh", {}) if cell.chips > 1 else {"devices": 1}
    rep.check("platform", device.get("platform") == "tpu"
              and device.get("count") == cell.chips
              and planes.get("platform") == "tpu"
              and mesh.get("devices") == cell.chips
              and len(set(planes.get("devices", []))) == cell.chips,
              device=device, chips_wanted=cell.chips, planes=planes,
              mesh_devices=mesh.get("devices"), mesh_axes=mesh.get("axes"))
    rungs = sorted({s["rung"] for k in window for s in
                    timeline[k]["stages"] if "rung" in s})
    rep.check("rung", rungs == ["pallas"], rungs_seen=rungs)
    kernels = v["obs"]["kernels"]
    rep.check("kernel_compiled",
              sum(kernels["kernel_traces"].values()) >= 1,
              kernel_traces=kernels["kernel_traces"])
    compute = ov["compute"]
    rep.check("compute_breaker_closed",
              all(s == 0.0 for s in compute["kernels"].values())
              and compute["fallback_total"] == 0
              and compute["requeued_total"] == 0
              and compute["lost_total"] == 0, compute=compute)
    rep.check("one_emission_an_interval",
              all(ems[k].bodies >= 1 for k in window),
              bodies=[ems[k].bodies for k in window])
    errors, warnings = child.log_errors()
    rep.check("child_log_clean", not errors, errors=errors[:8],
              warnings=len(warnings), first_warnings=warnings[:4])
    rep.check("child_exit_zero", rc == 0, returncode=rc)
    rep.check("parent_never_imported_jax", "jax" not in sys.modules)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="on a machine with no chip: drive the whole run "
                         "anyway, print no result line, exit non-zero "
                         "naming the checks that failed")
    ap.add_argument("--manifest", default="",
                    help="another BENCHMARK.json (rehearsals, the sweep)")
    ap.add_argument("--traffic-dir", default="",
                    help="another directory of traffic mixes")
    ap.add_argument("--keep-trace", action="store_true")
    ap.add_argument("--keep-input", action="store_true",
                    help="keep the receiver's bodies, the timeline and the "
                         "send logs under out/<cell>/input.pickle, for "
                         "tools/replay.py")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(cells.ROOT, "veneur_tpu")):
        print("no veneur_tpu/ beside benchmark/: nothing to measure",
              file=sys.stderr)
        return 2
    cell = cells.Cell(args.workload, args.manifest, args.traffic_dir)
    out_dir = os.path.join(HERE, "out", cell.name)
    os.makedirs(out_dir, exist_ok=True)
    rep = Report(os.path.join(out_dir, "report.jsonl"))
    try:
        line = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                        rep, out_dir, rehearse=args.rehearse,
                        keep_trace=args.keep_trace,
                        keep_input=args.keep_input)
    finally:
        rep.close()
    if "refused" in line:
        print(json.dumps({k: v for k, v in line.items()
                          if k != "rehearsed"}), file=sys.stderr)
        if "rehearsed" in line:
            print(json.dumps({"rehearsed": line["rehearsed"]}), flush=True)
        return NO_ACCELERATOR
    for name, n in line["compared"].items():
        print(f"{name} {n['value']} limit {n['limit']}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
