#!/usr/bin/env python3
"""Not a test (pytest does not collect it): the program's dense
``DigestGroup`` alone, fed as a global's import feeds it, to show how
far its merged percentiles lie from the union of the forwarders'
samples (PERF.md section 7). 4,096 series, each reported by four of
eight forwarders with ``--samples`` weight-1 centroids, a forwarder's
series in one message or split into two; the whole is flushed once.
About 20 s on the CPU; imports the program, as a test may.

    JAX_PLATFORMS=cpu python benchmark/tests/probe_import_accuracy.py --split 0.667
    ... --guard 0     a drain before every chunk: exact
    ... --guard 2     the shift guard never fires: thousands of series off

PR 31 read (CPU, at the median): --split 0.667: 10 series over 0.02,
worst 0.14; with --guard 2: 1,934 series, worst 0.24; with --guard 0:
none, worst 0.0156; --split 1.0: worst 0.0117; --split 0.667 --samples
16: 0 on every series.
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import reference  # noqa: E402

SERIES, FORWARDERS, FAN_IN = 4096, 8, 4
PERCENTILES = [0.5, 0.75, 0.99]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--split", type=float, default=1.0,
                    help="share of a forwarder's series in its first "
                         "message; 1.0 sends one message")
    ap.add_argument("--samples", type=int, default=64)
    ap.add_argument("--probes", type=int, default=4,
                    help="lone-centroid series after a message's block")
    ap.add_argument("--guard", type=float, default=None,
                    help="shift_pred's frac (the program's is 0.01)")
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)

    from veneur_tpu.ops import tdigest as td_ops
    if args.guard is not None:
        shipped = td_ops.shift_pred
        td_ops.shift_pred = lambda *a, **k: shipped(
            *a, **dict(k, frac=args.guard))
    from veneur_tpu.core.store import DigestGroup, MetricKey

    rng = np.random.default_rng(args.seed)
    group = DigestGroup(2 * SERIES, 1 << 14, 100.0)

    def rows_for(names):
        return np.array([group._row(MetricKey(
            name=nm, type="histogram", joined_tags=""), []) for nm in names],
            np.int32)

    vals = np.floor(rng.lognormal(3.0, 0.25, (SERIES, FAN_IN, args.samples))
                    * rng.uniform(0.5, 20.0, (SERIES, FAN_IN, 1))
                    * 64.0) / 64.0
    vals.sort(axis=2)
    row_of = np.zeros(SERIES, dtype=np.int64)
    sent = 0

    def message(forwarder, series):
        nonlocal sent
        j = forwarder // 2
        base = rows_for([f"t.{i:04d}" for i in series])
        row_of[series] = base
        probes = rows_for([f"p.{sent}.{k}" for k in range(args.probes)])
        sent += 1
        lone = np.full(args.probes, 7.25)
        group.import_centroids_bulk(
            np.concatenate([np.repeat(base, args.samples), probes]
                           ).astype(np.int32),
            np.concatenate([vals[series, j].reshape(-1), lone]),
            np.ones(len(base) * args.samples + args.probes),
            np.concatenate([base, probes]),
            np.concatenate([vals[series, j, 0], lone]).astype(np.float32),
            np.concatenate([vals[series, j, -1], lone]).astype(np.float32))

    for f in range(FORWARDERS):
        mine = np.arange(f % 2, SERIES, 2)
        if args.split < 1.0:
            first = rng.random(len(mine)) < args.split
            message(f, mine[first])
            message(f, mine[~first])
        else:
            message(f, mine)
    _interner, out = group.flush(PERCENTILES, want_digests=False)
    got = np.asarray(out["pcts"] if "pcts" in out else out["percentiles"])
    union = np.sort(vals.reshape(SERIES, -1), axis=1)
    n = np.full(SERIES, union.shape[1])
    for qi, q in enumerate(PERCENTILES):
        err = reference.rank_error(union, got[row_of, qi], q, n)
        off = np.flatnonzero(err > 0.02)
        print(f"q={q}: worst {err.max():.4f}, {len(off)} series over 0.02"
              f"{', the first ' + str(off[:12].tolist()) if len(off) else ''}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
