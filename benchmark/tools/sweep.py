#!/usr/bin/env python3
"""The sweep that fixes a mix's rate: one short run of a cell at each of
a few rates, everything else as committed. Writes the swept copies of
the traffic file and a manifest for them under ``benchmark/out/sweep/``
and prints one line a rate. Rates are taken in the order given and the
sweep stops after the first that is not clean: a run that exits
non-zero, has a line late in its window or unaccounted for, or fails a
run check (something shed, an overload level, a compile is not one).
``correct`` and the numbers over their limits are printed beside: a
number that the rate does not move (a sketch's guarantee) fails at every
rate and says nothing of the knee. The knee is the last clean rate, with
the backlog polls it printed not growing (PERF.md says how it is read).

    python benchmark/tools/sweep.py --workload <name> --group <prefix> \\
        --rates 16384 20480 ... [--seconds 20] [--seed N] \\
        [--manifest <file> --traffic-dir <dir>]

A rate is lines over the whole interval: the swept group gets
``interval * rate`` lines less the other groups' an interval, as its
``lines`` where it has the key (a group whose kind draws them) and as
``series`` of ``samples`` lines each where it is a rectangle.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))

from benchmark import kinds  # noqa: E402
from benchmark.lib import cells  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--group", required=True,
                    help="prefix of the group whose series are swept")
    ap.add_argument("--rates", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=2100000011)
    ap.add_argument("--manifest", default="")
    ap.add_argument("--traffic-dir", default="")
    args = ap.parse_args(argv)

    cell = cells.Cell(args.workload, args.manifest, args.traffic_dir)
    out = os.path.join(BENCH, "out", "sweep")
    os.makedirs(os.path.join(out, "traffic"), exist_ok=True)
    manifest = dict(cell.manifest, workloads=[])
    names = []
    for rate in args.rates:
        traffic = json.loads(json.dumps(cell.traffic))
        others = sum(kinds.of(g).lines_a_round(g)
                     for g in traffic["groups"] if g["prefix"] != args.group)
        for g in traffic["groups"]:
            if g["prefix"] != args.group:
                continue
            lines = int(cell.interval_s * rate) - others
            if "lines" in g:
                g["lines"] = lines
            else:
                g["series"] = lines // g["samples"]
        mix = f"{cell.entry['traffic']}-{rate}"
        with open(os.path.join(out, "traffic", mix + ".json"), "w") as f:
            json.dump(traffic, f, indent=1)
        name = f"{cell.entry['config']}.{mix}"
        manifest["workloads"].append(dict(cell.entry, name=name,
                                          traffic=mix))
        names.append((rate, name))
    for m in manifest["per_layer"] + manifest["end_to_end"]:
        m.pop("workloads", None)
    path = os.path.join(out, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(manifest, f, indent=1)

    for i, (rate, name) in enumerate(names):
        proc = subprocess.run(
            [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
             name, "--seed", str(args.seed + i), "--seconds",
             str(args.seconds), "--trace", "0", "--manifest", path,
             "--traffic-dir", os.path.join(out, "traffic")],
            capture_output=True, text=True)
        report = os.path.join(BENCH, "out", name, "report.jsonl")
        if os.path.exists(report):
            shutil.copy(report, os.path.join(out, f"{rate}.jsonl"))
        last = proc.stdout.strip().splitlines()[-1:] or [""]
        tail = [ln for ln in proc.stdout.splitlines()
                if '"backlog_polls"' in ln or '"phase": "compared"' in ln]
        late = [json.loads(ln).get("lines_late") for ln in tail
                if '"phase": "compared"' in ln]
        result = json.loads(last[0]) if proc.returncode == 0 else {}
        over = {k: n for k, n in result.get("compared", {}).items()
                if n["value"] > n["limit"]}
        clean = (proc.returncode == 0 and late == [0] and not (
            {"lines_unaccounted", "run_checks_failed"} & set(over)))
        print(json.dumps({"rate": rate, "rc": proc.returncode,
                          "clean": clean, "correct": result.get("correct"),
                          "over_their_limits": over}))
        for ln in tail + last:
            print(ln[:3000])
        if proc.returncode:
            print(proc.stderr[-2000:])
        sys.stdout.flush()
        if not clean:
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
