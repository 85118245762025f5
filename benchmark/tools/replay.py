#!/usr/bin/env python3
"""Reads a run's kept input again: the receiver's bodies, the flush
timeline and the send logs that ``run.py --keep-input`` wrote to
``out/<cell>/input.pickle``, parsed and compared by the harness of
``--root`` (another checkout's; this one's where it is not given).
Prints the ``compared`` numbers, ``lines_late``, the rank errors'
summary, every emission's lines and the ``measures`` taken from outside,
one JSON object a line with its keys in order, and last the seconds that
parse and compare took. Two trees read the same input alike where
their lines are equal:

    python benchmark/tools/replay.py --workload <cell> --input <file> > new
    python benchmark/tools/replay.py --workload <cell> --input <file> \\
        --root <a checkout of the parent> > old
    diff <(head -n -1 old) <(head -n -1 new)

Only a pickle that this harness wrote is read.
"""

from __future__ import annotations

import argparse
import json
import os
import pickle
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def _read_as_before_kinds(run, cell, kept: dict, rounds: dict) -> tuple:
    """The calls ``run.py`` made of ``lib/`` before a group had a kind
    (the parent of PR 39), for a tree that has no ``read_input``."""
    from benchmark.lib import emissions, reference

    groups = cell.traffic["groups"]
    percentiles = cell.config["server"]["percentiles"]
    interval = cell.interval_s
    span = interval - float(cell.traffic["guard_s"])
    window, timeline, bodies = (kept["window"], kept["timeline"],
                                kept["bodies"])
    ticks = [e["wall_start"] for e in timeline]
    owner = emissions.assign_emissions(bodies, ticks)
    ems = emissions.parse(bodies, owner, len(timeline), groups,
                          percentiles, interval)
    warm = range(window.start - run.WARM_ROUNDS, window.start)
    carried_in = max(0, sum(n for _d, _s, n in kept["warm_log"]) - sum(
        emissions.lines_in(ems[k], groups) for k in warm))
    e2e = emissions.end_to_end(kept["send_log"], ems, ticks, window, groups,
                               carried_in)
    by_round = {window.start - 1 + k: rounds[k]
                for k in range(1 - run.WARM_ROUNDS, kept["n_rounds"] + 1)}
    verdict = reference.compare(
        ems, by_round, window, groups, percentiles,
        float(cell.config["rank_error_limit"]),
        {window.start - 1 + k: (tick, span, interval)
         for k, tick in kept["started"].items()})
    return ems, e2e, verdict, {
        "lines_carried_in": carried_in,
        "emissions": [{"bodies": e.bodies, "rows": e.rows,
                       "lines": emissions.lines_in(e, groups)}
                      for e in ems]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--input", required=True)
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(HERE)),
                    help="the checkout whose benchmark/ reads the input")
    ap.add_argument("--manifest", default="")
    ap.add_argument("--traffic-dir", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.abspath(args.root))
    from benchmark import run
    from benchmark.lib import cells

    cell = cells.Cell(args.workload, os.path.abspath(
        args.manifest or os.path.join(args.root, "BENCHMARK.json")),
        args.traffic_dir and os.path.abspath(args.traffic_dir))
    with open(args.input, "rb") as f:
        kept = pickle.load(f)
    gen = cell.generator()
    rounds = {k: gen.build(cell.traffic, kept["seed"], k + run.WARM_ROUNDS)
              for k in range(1 - run.WARM_ROUNDS, kept["n_rounds"] + 1)}
    t0 = time.time()
    if hasattr(run, "read_input"):
        _ems, e2e, verdict, compared = run.read_input(cell, kept, rounds)
    else:
        _ems, e2e, verdict, compared = _read_as_before_kinds(
            run, cell, kept, rounds)
    seconds = time.time() - t0
    rank_errors = dict(verdict["rank_errors"])
    rank_errors.pop("by_band", None)
    for line in ({"compared": verdict["numbers"]},
                 {"lines_late": verdict["lines_late"],
                  "lines_sent": e2e["lines_sent"],
                  "lines_held": e2e["lines_held"],
                  "lines_carried_in": compared["lines_carried_in"]},
                 {"rank_errors": rank_errors},
                 {"emissions": compared["emissions"]},
                 {"measures": e2e["measures"],
                  "flush_to_last_body_each_s":
                      e2e["flush_to_last_body_each_s"]},
                 {"root": os.path.abspath(args.root),
                  "parse_and_compare_s": round(seconds, 2)}):
        print(json.dumps(line, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
