#!/usr/bin/env python3
"""The control, at a cell's own size: the reference put in the program's
place and computed one precision step below the one the configuration
states (bfloat16 for float32), a group whose numbers no float precision
decides with its kind's own guarantee broken (``benchmark/kinds/``: one
line in ten left out). It has to come out as not correct; the
same reference at the stated precision has to pass. Prints one line a
seed and precision with every number compared beside its limit.

    python benchmark/tools/control.py --workload <name> --seeds 1 2 3
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark.lib import cells, reference  # noqa: E402

BELOW = {"float64": "float32", "float32": "bfloat16"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--manifest", default="")
    ap.add_argument("--traffic-dir", default="")
    args = ap.parse_args(argv)
    cell = cells.Cell(args.workload, args.manifest, args.traffic_dir)
    seconds = args.seconds or cell.manifest["run_seconds"]
    n_rounds = max(1, int(seconds // cell.interval_s))
    groups = cell.traffic["groups"]
    percentiles = cell.config["server"]["percentiles"]
    stated = cell.config["precision"]
    window = range(2, 2 + n_rounds)
    ok = True
    for seed in args.seeds:
        t0 = time.time()
        rounds = {window[k - 1]: cell.generator().build(cell.traffic,
                                                        seed, k)
                  for k in range(1, n_rounds + 1)}
        for precision in (stated, BELOW[stated]):
            ems = reference.synthesize(rounds, window, 3 + n_rounds, groups,
                                       percentiles, precision,
                                       control=precision != stated,
                                       limits=cell.config)
            out = reference.compare(ems, rounds, window, groups,
                                    percentiles, cell.config)["numbers"]
            correct = all(n["value"] <= n["limit"] for n in out.values())
            ok &= correct == (precision == stated)
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "precision": precision, "correct": correct,
                              "compared": out,
                              "seconds": round(time.time() - t0, 1)}),
                  flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
