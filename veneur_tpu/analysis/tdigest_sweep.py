"""t-digest accuracy sweep: the reference's ``tdigest/analysis`` role
(``/root/reference/tdigest/analysis/README.md:1-9`` — "compare the
accuracy of the t-digest implementation across distributions",
emitting CSVs for offline study).

This harness quantifies QUANTILE RANK ERROR — ``|F_true(v_q) - q``
interval distance against the exact empirical CDF — of the TPU kernel
pipeline, side by side with the scalar golden model
(``samplers/scalar.py``), across:

* distributions: uniform, normal, lognormal, pareto, and
  adversarially ORDERED arrival (ascending / descending), which
  stresses chunked ingest the way production never quite does;
* compressions: 50 / 100 / 200;
* merge depths (the production paths):
    - ``chunks1``   one ``merge_samples`` call (the library path at
      temp-buffer granularity, merging_digest.go:111-132);
    - ``chunks16``  16 sequential merge_samples compressions;
    - ``binned16``  the SERVER path: 16 staged chunks through the
      dense store's sample ingest (``sample_ingest``) + ONE
      ``drain_temp`` per interval (store.py);
    - ``binned4x4`` four intervals of 4 chunks each, digests
      accumulating across drains;
    - ``fanin8``    8 per-host digests combined with ``merge`` — the
      global import depth (samplers.go:657-691);
* storage dtypes: f32, and bf16 with a round-trip through storage
  after every kernel step, exactly what ``core/slab.py`` bf16 planes
  do at program boundaries.

Run: ``python -m veneur_tpu.analysis.tdigest_sweep [--quick]
[--out docs/tdigest_accuracy.csv]``; ``--sparse`` prints the grid of
rows with few samples an interval spread over several ingest dispatches
(``run_sparse``), before and after the dense store's row drain;
``--guard`` the binned paths with and without the shift guard. The
companion summary table
lives at ``docs/tdigest_accuracy.md``.

The reference's test envelope is eps=0.02
(``tdigest/histo_test.go:11-25``) for direct adds at its temp-buffer
granularity — the ``chunks1`` / ``fanin8`` regimes here. Chunked
arrival against an evolving value range (``binned16`` with ordered
arrival) is a strictly harder regime the reference never measures;
this sweep reports it honestly instead of hiding it.
"""

from __future__ import annotations

import argparse
import csv
import io
import sys
from typing import Dict, List

import numpy as np

QS = (0.01, 0.05, 0.25, 0.50, 0.75, 0.90, 0.95, 0.99, 0.999)

DISTS = ("uniform", "normal", "lognormal", "pareto",
         "sorted_asc", "sorted_desc")
COMPRESSIONS = (50.0, 100.0, 200.0)
PATHS = ("chunks1", "chunks16", "binned16", "binned4x4", "fanin8")
DTYPES = ("float32", "bfloat16")


def sample_dist(dist: str, rng: np.random.Generator,
                shape) -> np.ndarray:
    if dist == "uniform":
        v = rng.uniform(0.0, 100.0, shape)
    elif dist == "normal":
        v = rng.normal(100.0, 15.0, shape)
    elif dist == "lognormal":
        v = rng.lognormal(3.0, 1.0, shape)
    elif dist == "pareto":
        v = (rng.pareto(2.0, shape) + 1.0) * 10.0
    elif dist == "sorted_asc":
        v = np.sort(rng.normal(100.0, 15.0, shape), axis=-1)
    elif dist == "sorted_desc":
        v = -np.sort(-rng.normal(100.0, 15.0, shape), axis=-1)
    else:
        raise ValueError(dist)
    return v.astype(np.float32)


def rank_err(true_sorted: np.ndarray, v: float, q: float) -> float:
    """Distance from q to the closed rank interval [F(v-), F(v)] of v
    under the exact empirical CDF (ties handled by the interval)."""
    n = len(true_sorted)
    lo = np.searchsorted(true_sorted, v, "left") / n
    hi = np.searchsorted(true_sorted, v, "right") / n
    return max(0.0, lo - q, q - hi)


def _bf16_roundtrip(digest):
    import jax.numpy as jnp

    return digest._replace(
        mean=digest.mean.astype(jnp.bfloat16).astype(jnp.float32),
        weight=digest.weight.astype(jnp.bfloat16).astype(jnp.float32))


INGESTS = ("rowdrained", "guarded", "rowdrained_unguarded")


def sample_ingest(ingest: str, compression: float,
                  use_pallas: bool = False):
    """A jitted (digest, temp, rows, values, weights) -> (digest, temp)
    ingest of one staged chunk: ``rowdrained`` is the dense store's
    sample path (ops/tdigest.py ingest_chunk_rowdrained); ``guarded``
    the chunk-wide shift guard and the anchored binning alone, what
    the slab store runs and the dense store ran before PR 40;
    ``rowdrained_unguarded`` the row drain with no shift guard behind
    it (the reading that says whether the guard is still needed)."""
    import jax
    from jax import lax

    from veneur_tpu.ops import tdigest as td

    def guarded(d, t, r, v, w):
        d, t = lax.cond(
            td.shift_pred(*t.anchors(), r, v, w, t.num_series),
            lambda a: td.drain_every_bin(*a, compression, use_pallas),
            lambda a: a, (d, t))
        return d, td.ingest_chunk(t, r, v, w, compression)

    def rowdrained_unguarded(d, t, r, v, w):
        touched, count = td.held_rows(t, r, w, td.ROW_DRAIN_MAX_ARRIVALS)
        d, t = td.drain_rows(d, t, touched, count, compression, use_pallas)
        return d, td.ingest_chunk(t, r, v, w, compression)

    def rowdrained(d, t, r, v, w):
        return td.ingest_chunk_rowdrained(d, t, r, v, w, compression,
                                          use_pallas)[:2]

    return jax.jit({"rowdrained": rowdrained, "guarded": guarded,
                    "rowdrained_unguarded": rowdrained_unguarded}[ingest])


def run_config(dist: str, compression: float, path: str, dtype: str,
               rows: int = 16, n: int = 4096, seed: int = 0,
               golden_rows: int = 2, ingest: str = "rowdrained") -> Dict:
    """One sweep cell. Returns max/mean kernel rank error across
    rows x quantiles, plus the scalar golden model's max on a row
    subset for calibration. ``ingest`` (``sample_ingest``) is read by
    the binned paths alone."""
    import jax.numpy as jnp

    from veneur_tpu.ops import tdigest as td
    from veneur_tpu.samplers.scalar import ScalarTDigest

    rng = np.random.default_rng(seed)
    vals = sample_dist(dist, rng, (rows, n))
    k = td.size_bound(compression)
    bf16 = dtype == "bfloat16"

    def storage(d):
        return _bf16_roundtrip(d) if bf16 else d

    if path in ("chunks1", "chunks16"):
        chunks = 1 if path == "chunks1" else 16
        digest = td.init((rows,), compression, k)
        for c in range(chunks):
            part = vals[:, c * (n // chunks):(c + 1) * (n // chunks)]
            digest = storage(td.merge_samples(
                digest, jnp.asarray(part),
                jnp.ones_like(jnp.asarray(part)), compression))
    elif path in ("binned16", "binned4x4"):
        # the server path: a staged chunk's ingest (sample_ingest)
        # into the temp accumulator, one scheduled drain per interval
        intervals, chunks = (1, 16) if path == "binned16" else (4, 4)
        per = n // (intervals * chunks)
        digest = td.init((rows,), compression, k)
        pos = 0
        step = sample_ingest(ingest, compression)
        for _ in range(intervals):
            temp = td.init_temp(rows, compression=compression)
            for _ in range(chunks):
                part = vals[:, pos:pos + per]
                pos += per
                flat_rows = np.repeat(np.arange(rows, dtype=np.int32), per)
                digest, temp = step(
                    digest, temp, jnp.asarray(flat_rows),
                    jnp.asarray(part.reshape(-1)),
                    jnp.ones(part.size, jnp.float32))
                digest = storage(digest)
            digest = storage(td.drain_temp(digest, temp, compression))
    elif path == "fanin8":
        fanin = 8
        per = n // fanin
        parts = []
        for f in range(fanin):
            d = td.init((rows,), compression, k)
            part = vals[:, f * per:(f + 1) * per]
            parts.append(storage(td.merge_samples(
                d, jnp.asarray(part), jnp.ones_like(jnp.asarray(part)),
                compression)))
        digest = parts[0]
        for d in parts[1:]:
            digest = storage(td.merge(digest, d, compression))
    else:
        raise ValueError(path)

    pcts = np.asarray(td.quantile(digest, jnp.asarray(QS, jnp.float32)))

    errs = np.zeros((rows, len(QS)))
    for r in range(rows):
        t_sorted = np.sort(vals[r])
        for qi, q in enumerate(QS):
            errs[r, qi] = rank_err(t_sorted, float(pcts[r, qi]), q)

    golden_max = 0.0
    for r in range(min(golden_rows, rows)):
        g = ScalarTDigest(compression=compression)
        for v in vals[r]:
            g.add(float(v))
        t_sorted = np.sort(vals[r])
        for q in QS:
            golden_max = max(golden_max,
                             rank_err(t_sorted, g.quantile(q), q))

    per_q_max = errs.max(axis=0)
    return {"dist": dist, "compression": compression, "path": path,
            "dtype": dtype, "rows": rows, "n": n,
            "max_rank_err": round(float(errs.max()), 5),
            "mean_rank_err": round(float(errs.mean()), 5),
            "golden_max_rank_err": round(golden_max, 5),
            "per_q_max": {q: round(float(e), 5)
                          for q, e in zip(QS, per_q_max)}}


SPARSE_QS = (0.5, 0.75, 0.99)
# (samples a row an interval, chunks they are spread over, rows): the
# last is ROADMAP.md R2's shape, 1,024 series x 500 samples interleaved
# through 16,384-sample chunks
SPARSE_GRID = tuple((n, c, 256) for n in (2, 3, 8, 60, 500)
                    for c in (1, 4, 12)) + ((500, 32, 1024),)


def sparse_rank_err(true_sorted: np.ndarray, v: float, q: float) -> float:
    """``rank_err`` as the benchmark reads it among a few samples: a
    value strictly between two neighbouring samples counts as either
    (an interpolated quantile is otherwise charged a sample's rank)."""
    n = len(true_sorted)
    lo = np.searchsorted(true_sorted, v, "left") / n
    hi = np.searchsorted(true_sorted, v, "right") / n
    err = max(0.0, lo - q, q - hi)
    if lo == hi and 0 < lo < 1:
        err = max(err - 1.0 / n, 0.0)
    return err


def run_sparse(samples: int, chunks: int, rows: int = 256,
               ingest: str = "rowdrained", compression: float = 100.0,
               seed: int = 0, sample_rate: float = 1.0) -> Dict:
    """A sparse-row cell: ``rows`` series of ``samples`` samples an
    interval (multiples of 0.25 under 100,000), each sample in one of
    ``chunks`` ingest dispatches at random, then one drain, through
    ``sample_ingest(ingest)``; every sample weighs 1 / ``sample_rate``
    as a line sent ``|@<rate>`` does."""
    import jax.numpy as jnp

    from veneur_tpu.core.bucketing import next_pow2
    from veneur_tpu.ops import tdigest as td

    rng = np.random.default_rng(seed)
    vals = rng.integers(0, 400000, size=(rows, samples)) / 4.0
    which = rng.integers(0, chunks, size=(rows, samples))
    width = next_pow2(max(int((which == c).sum()) for c in range(chunks)))

    step = sample_ingest(ingest, compression)

    digest = td.init((rows,), compression)
    temp = td.init_temp(rows, compression=compression)
    for c in range(chunks):
        r, j = np.nonzero(which == c)
        order = rng.permutation(len(r))
        row = np.full(width, rows, np.int32)
        v = np.zeros(width, np.float32)
        w = np.zeros(width, np.float32)
        row[:len(r)] = r[order]
        v[:len(r)] = vals[r, j][order]
        w[:len(r)] = 1.0 / sample_rate
        digest, temp = step(digest, temp, jnp.asarray(row),
                            jnp.asarray(v), jnp.asarray(w))
    digest = td.drain_temp(digest, temp, compression, use_pallas=False)
    pcts = np.asarray(td.quantile(digest,
                                  jnp.asarray(SPARSE_QS, jnp.float32)))
    errs = np.zeros((rows, len(SPARSE_QS)))
    for r in range(rows):
        t_sorted = np.sort(vals[r].astype(np.float32))
        for qi, q in enumerate(SPARSE_QS):
            errs[r, qi] = sparse_rank_err(t_sorted, float(pcts[r, qi]), q)
    return {"samples": samples, "chunks": chunks, "rows": rows,
            "ingest": ingest, "sample_rate": sample_rate,
            "max_rank_err": round(float(errs.max()), 5),
            "rows_over_0.02": int((errs.max(axis=1) > 0.02).sum())}


def sparse_table(seeds: int = 2) -> str:
    """Markdown: the sparse grid through each of ``INGESTS`` (before:
    the guarded binning alone; after: the row-drained sample path; the
    row drain with no guard behind it) and through the sample path at
    sample rates 0.1 and 0.01, worst of ``seeds`` seeds."""
    columns = [(i, 1.0) for i in INGESTS] + [("rowdrained", 0.1),
                                             ("rowdrained", 0.01)]
    lines = ["| samples a row | chunks | rows | " + " | ".join(
        f"{i}{'' if rate == 1.0 else f' @{rate}'}" for i, rate in columns)
        + " |", "|---" * (3 + len(columns)) + "|"]
    for samples, chunks, rows in SPARSE_GRID:
        cells = []
        for ingest, rate in columns:
            runs = [run_sparse(samples, chunks, rows, ingest, seed=s,
                               sample_rate=rate) for s in range(seeds)]
            cells.append(f'{max(r["max_rank_err"] for r in runs):.4f} '
                         f'({max(r["rows_over_0.02"] for r in runs)})')
        lines.append(f"| {samples} | {chunks} | {rows} | "
                     + " | ".join(cells) + " |")
    return "\n".join(lines)


def guard_table(rows: int = 16, n: int = 4096) -> str:
    """Markdown: the binned paths (16 chunks of ``n`` / 16 samples a
    row, so a row passes ``ROW_DRAIN_MAX_ARRIVALS`` in the interval)
    through each of ``INGESTS``, f32 at compression 100: max rank
    error a distribution."""
    lines = ["| path | dist | " + " | ".join(INGESTS) + " |",
             "|---" * (2 + len(INGESTS)) + "|"]
    for path in ("binned16", "binned4x4"):
        for dist in DISTS:
            cells = [run_config(dist, 100.0, path, "float32", rows=rows,
                                n=n, golden_rows=0, ingest=i)
                     for i in INGESTS]
            lines.append(f"| {path} | {dist} | " + " | ".join(
                f'{c["max_rank_err"]:.4f}' for c in cells) + " |")
    return "\n".join(lines)


def run_sweep(quick: bool = False, rows: int = 16, n: int = 4096,
              progress=None) -> List[Dict]:
    dists = DISTS[:3] + DISTS[4:5] if quick else DISTS
    comps = (100.0,) if quick else COMPRESSIONS
    paths = ("chunks1", "binned16", "fanin8") if quick else PATHS
    dtypes = DTYPES
    out = []
    for path in paths:
        for dtype in dtypes:
            for dist in dists:
                for comp in comps:
                    cell = run_config(dist, comp, path, dtype,
                                      rows=rows, n=n)
                    out.append(cell)
                    if progress:
                        progress(cell)
    return out


def write_csv(cells: List[Dict], fh) -> None:
    cols = ["path", "dtype", "dist", "compression", "rows", "n",
            "max_rank_err", "mean_rank_err", "golden_max_rank_err"] + \
        [f"q{q}" for q in QS]
    w = csv.writer(fh)
    w.writerow(cols)
    for c in cells:
        w.writerow([c["path"], c["dtype"], c["dist"], c["compression"],
                    c["rows"], c["n"], c["max_rank_err"],
                    c["mean_rank_err"], c["golden_max_rank_err"]]
                   + [c["per_q_max"][q] for q in QS])


def summarize(cells: List[Dict]) -> str:
    """Markdown summary: worst-case rank error per (path, dtype) regime
    across all distributions and compressions, vs the golden model."""
    by = {}
    for c in cells:
        key = (c["path"], c["dtype"])
        cur = by.setdefault(key, {"max": 0.0, "golden": 0.0, "cells": 0,
                                  "worst": None})
        cur["cells"] += 1
        cur["golden"] = max(cur["golden"], c["golden_max_rank_err"])
        if c["max_rank_err"] >= cur["max"]:
            cur["max"] = c["max_rank_err"]
            cur["worst"] = f'{c["dist"]}/c{int(c["compression"])}'
    lines = ["| path | dtype | max rank err | worst cell | golden max |",
             "|---|---|---|---|---|"]
    for (path, dtype), v in sorted(by.items()):
        lines.append(f'| {path} | {dtype} | {v["max"]:.4f} | '
                     f'{v["worst"]} | {v["golden"]:.4f} |')
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="tdigest_sweep",
        description="t-digest accuracy sweep (CSV + summary)")
    ap.add_argument("--quick", action="store_true",
                    help="reduced sweep for CI")
    ap.add_argument("--rows", type=int, default=16)
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--out", default="-",
                    help="CSV path ('-' for stdout)")
    ap.add_argument("--sparse", action="store_true",
                    help="the sparse-row grid alone (samples a row x "
                         "chunks they are spread over), as markdown")
    ap.add_argument("--guard", action="store_true",
                    help="the binned paths with and without the shift "
                         "guard and the row drain, as markdown")
    args = ap.parse_args(argv)
    if args.sparse:
        print(sparse_table())
        return 0
    if args.guard:
        print(guard_table(args.rows, args.n))
        return 0

    def progress(c):
        print(f'{c["path"]:9s} {c["dtype"]:8s} {c["dist"]:11s} '
              f'c={int(c["compression"]):3d} max={c["max_rank_err"]:.4f} '
              f'golden={c["golden_max_rank_err"]:.4f}', file=sys.stderr)

    cells = run_sweep(quick=args.quick, rows=args.rows, n=args.n,
                      progress=progress)
    buf = io.StringIO()
    write_csv(cells, buf)
    if args.out == "-":
        sys.stdout.write(buf.getvalue())
    else:
        with open(args.out, "w") as fh:
            fh.write(buf.getvalue())
    print("\n" + summarize(cells), file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
