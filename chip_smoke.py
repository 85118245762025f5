#!/usr/bin/env python3
"""Proof that the served path starts and answers correctly on the chip.

Starts ``python -m veneur_tpu.cli.server`` as a child on one standalone
instance with upstream ``example.yaml`` settings (10 s interval,
percentiles 0.5/0.75/0.99, aggregates min/max/count, dense f32 digests,
compression 100, UDP lanes with native ingest, ``flush_file`` as the
sink), sends it DogStatsD datagrams built from ``--seed``, and compares
what the sink received with a plain float64 NumPy reference kept in
this file. The parent never imports JAX: one process per chip, and that
process is the server.

    python chip_smoke.py              one chip, 2^20 histogram series
    python chip_smoke.py --chips 4    the mesh-sharded global instance
                                      (series=2 x hosts=2), and nothing else

Every check prints one JSON line; no option or environment variable
turns a check off. The last line of standard output is the contract's
line, ``{"ok": ..., "device": {"platform", "kind", "count"}}``, and the
exit code is 0 only when every check passed on a TPU.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

import numpy as np
import pandas as pd

HERE = os.path.dirname(os.path.abspath(__file__))

# The wide rounds' constant pace. Every interval interns every series
# again, and on the one-chip machine first-sight series went into the
# store at 22-28k a second (my chip run, PR 24): at 65,536 lines/s the
# lanes fell behind and 4.5% of the datagrams were lost at the socket.
# A quarter of that pace stays under what the host can take.
LINES_PER_S = 16_384
N_SCALARS = 10_000          # counters, and gauges, per wide round
DENSE_SAMPLES = 2048        # samples per dense series (see Load)
N_SETS = 16
DATAGRAM_BYTES = 1400       # stay inside one loopback-safe MTU
SENDER_SOCKETS = 8          # SO_REUSEPORT lanes hash the source port
RANK_ERROR = 0.02           # the t-digest bound tests/test_tpu_smoke.py holds
SET_ERROR = 0.02
KERNELS = ("_drain_quantile_pallas", "_compress_presorted_pallas")
# the flush timeline's stages worth a column in the per-flush lines
STAGES = ("store.swap", "store.dispatch.histograms.compute",
          "store.histograms.fetch", "serialize.histograms", "post",
          "plugins")


# ---------------------------------------------------------------------------
# reporting
# ---------------------------------------------------------------------------


class Report:
    """One JSON object per line on stdout (and in ``report.jsonl``)."""

    def __init__(self, path: str):
        self._file = open(path, "w")
        self.failed: list = []

    def line(self, **obj) -> None:
        text = json.dumps(obj, default=str)
        print(text, flush=True)
        self._file.write(text + "\n")
        self._file.flush()

    def check(self, name: str, ok: bool, **detail) -> bool:
        ok = bool(ok)
        if not ok:
            self.failed.append(name)
        self.line(check=name, ok=ok, **detail)
        return ok


# ---------------------------------------------------------------------------
# the load and its float64 reference (independent of veneur_tpu)
# ---------------------------------------------------------------------------


def _fmt(values: np.ndarray) -> list:
    """Decimal text of f32-exact values (multiples of 1/64 below 2^18:
    a few decimals, no exponent), so min/max compare exactly."""
    return [repr(float(v)) for v in values]


def _pack(lines: list) -> list:
    """Greedy newline-joined datagrams of at most DATAGRAM_BYTES;
    returns [(payload, n_lines)]."""
    out, cur, size = [], [], 0
    for ln in lines:
        if cur and size + 1 + len(ln) > DATAGRAM_BYTES:
            out.append((b"\n".join(cur), len(cur)))
            cur, size = [], 0
        cur.append(ln)
        size += len(ln) + (1 if size else 0)
    if cur:
        out.append((b"\n".join(cur), len(cur)))
    return out


class Load:
    """Everything that will be sent, built once from the seed, with the
    expected emissions next to it."""

    def __init__(self, series: int, seed: int):
        rng = np.random.default_rng(seed)
        self.series = series
        # The dense interval: 256 series x 2,048 samples and 16 sets x
        # 10,000 members at the default --series, fewer series and
        # members in a small rehearsal. 2,048 samples a series is the
        # regime docs/tdigest_accuracy.md measured (<= 0.0127 rank
        # error). The binned ingest is coarser where a series has few
        # samples in each store chunk: 1,024 series x 500 samples, the
        # same half-million samples interleaved, reads 0.044 on the CPU
        # path alone, outside the 0.02 bound before any chip is
        # involved (PERF.md, open questions).
        self.n_dense = max(16, min(256, series // 256))
        self.set_members = max(1000, min(10_000, series))

        # wide rounds: every series once a round, two rounds. The
        # server's own flush telemetry keeps two series in the same
        # histogram group, so an interval that held all of a 2^N-series
        # round at once would grow the group past its fixed 2^N rows
        # (a new shape of every program, twice the planes). A round is
        # therefore sent as two halves, each right after a tick: no
        # interval holds more than half the series, whatever the flush
        # wall turns out to be.
        self.wide = rng.integers(0, 400_000, size=(2, series)) / 4.0
        self.counters = rng.integers(1, 1000, size=(2, N_SCALARS))
        self.gauges = rng.integers(0, 400_000, size=(2, N_SCALARS)) / 4.0
        self.rounds = []
        for r in range(2):
            lines = [b"smoke.h.%d:%s|h" % (i, v.encode()) for i, v in
                     enumerate(_fmt(self.wide[r]))]
            lines += [b"smoke.c.%d:%d|c" % (i, n) for i, n in
                      enumerate(self.counters[r])]
            lines += [b"smoke.g.%d:%s|g" % (i, v.encode()) for i, v in
                      enumerate(_fmt(self.gauges[r]))]
            perm = rng.permutation(len(lines))
            mixed = [lines[j] for j in perm]
            half = len(mixed) // 2
            self.rounds.append((_pack(mixed[:half]), _pack(mixed[half:])))

        # one dense interval: a skewed distribution per series
        raw = rng.lognormal(3.0, 1.0, size=(self.n_dense, DENSE_SAMPLES))
        scale = rng.uniform(0.5, 20.0, size=(self.n_dense, 1))
        self.dense = np.floor(raw * scale * 64.0) / 64.0
        lines = [b"smoke.d.%d:%s|h" % (i, v.encode())
                 for i in range(self.n_dense) for v in _fmt(self.dense[i])]
        lines += [b"smoke.s.%d:m%d_%d|s" % (j, seed, k)
                  for j in range(N_SETS) for k in range(self.set_members)]
        perm = rng.permutation(len(lines))
        self.dense_datagrams = _pack([lines[j] for j in perm])

        self.warm = [(b"smoke.warm.h:1.5|h\nsmoke.warm.c:1|c\n"
                      b"smoke.warm.g:2.5|g\nsmoke.warm.s:a|s\n"
                      b"smoke.warm.t:3.5|ms", 5)]


# ---------------------------------------------------------------------------
# the child and its endpoints
# ---------------------------------------------------------------------------


def _free_port(kind: int) -> int:
    with socket.socket(socket.AF_INET, kind) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _config(args, capacity: int, statsd_port: int, http_port: int,
            flush_file: str) -> str:
    mesh = ("# --chips 4: a global instance (no forward_address) whose\n"
            "# store is sharded over every visible device\n"
            "mesh_enabled: true\n" if args.chips == 4 else "")
    return f"""# chip_smoke.py: BASELINE.json config 2 (histogram series, t-digest
# compression 100, one v5e) with config 1's 10k counters + 10k gauges,
# on one standalone instance with upstream example.yaml settings.
statsd_listen_addresses:
  - "udp://127.0.0.1:{statsd_port}"
num_readers: 4
interval: "{args.interval}"
percentiles: [0.5, 0.75, 0.99]
aggregates: ["min", "max", "count"]
http_address: "127.0.0.1:{http_port}"
flush_file: "{flush_file}"
# The two departures from example.yaml, and why:
# 1. Groups grow by doubling from 4096 rows and every capacity is a new
#    shape of the ingest and flush programs (about a minute of compiling
#    each on the v5e): a cold start would compile eight shapes inside
#    the ingest path. The capacity is fixed at the deployment's size.
store_initial_capacity: {capacity}
# 2. max_series defaults to 2^20 and occupancy over the 0.7 low
#    watermark freezes first-sight series: the last quarter of 2^20
#    series would go to the overflow row. Twice the capacity (and,
#    in a small rehearsal, room for the 10k counters and gauges).
max_series: {2 * max(capacity, 16384)}
{mesh}"""


class Child:
    def __init__(self, config_path: str, log_path: str, http_port: int):
        self.http = f"http://127.0.0.1:{http_port}"
        self.log_path = log_path
        self._log = open(log_path, "w")
        self.started = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "veneur_tpu.cli.server", "-f",
             config_path], cwd=HERE, stdout=self._log,
            stderr=subprocess.STDOUT)

    def get(self, path: str, timeout: float = 10.0):
        with urllib.request.urlopen(self.http + path,
                                    timeout=timeout) as resp:
            body = resp.read()
        return json.loads(body) if path.startswith("/debug") else body

    def wait_ready(self, deadline_s: float) -> bool:
        while time.monotonic() - self.started < deadline_s:
            if self.proc.poll() is not None:
                return False
            try:
                self.get("/healthcheck/ready", timeout=2.0)
                return True
            except OSError:
                time.sleep(0.25)
        return False

    def timeline(self) -> list:
        return self.get("/debug/flush-timeline")["intervals"]

    def wait_flushes(self, count: int, deadline_s: float) -> list:
        """Block until the timeline has published ``count`` intervals."""
        end = time.monotonic() + deadline_s
        while time.monotonic() < end and self.proc.poll() is None:
            data = self.get("/debug/flush-timeline?n=1")
            if data["published_total"] >= count:
                return data["intervals"]
            time.sleep(0.05)
        raise TimeoutError(f"no flush #{count} within {deadline_s:.0f}s")

    def published(self) -> int:
        return self.get("/debug/flush-timeline?n=1")["published_total"]

    def stop(self) -> int:
        """SIGTERM, wait for the final flush, return the exit code;
        never leaves the child behind."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=180)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()
        return self.proc.returncode


class Watcher(threading.Thread):
    """Polls /debug/vars while the load runs: the overload level and the
    per-interval spill tallies reset, so they have to be watched, and
    the mesh's shard occupancy only exists while an interval is live."""

    def __init__(self, child: Child):
        super().__init__(daemon=True)
        self.child = child
        self.done = threading.Event()
        self.max_level = 0
        self.spilled: dict = {}
        self.peak_occupancy: list = []
        # a flush's fresh generation places its planes on first write,
        # so a read between intervals may find none: keep the last seen
        self.digest_planes: dict = {}
        self.balance_at_peak = None
        self.errors = 0

    def run(self):
        while not self.done.wait(1.0):
            try:
                v = self.child.get("/debug/vars", timeout=5.0)
            except (OSError, ValueError):
                self.errors += 1
                continue
            ov = v.get("overload", {})
            self.max_level = max(self.max_level, ov.get("level", 0))
            for group, n in ov.get("spilled_this_interval", {}).items():
                self.spilled[group] = max(self.spilled.get(group, 0), n)
            self.digest_planes = v.get("device", {}).get(
                "digest_planes", self.digest_planes)
            occ = v.get("mesh", {}).get("shard_occupancy")
            if occ and sum(occ) > sum(self.peak_occupancy or [0]):
                self.peak_occupancy = occ
                self.balance_at_peak = v["mesh"].get("balance_ratio")


class Sender:
    def __init__(self, port: int):
        self.addr = ("127.0.0.1", port)
        self.socks = [socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
                      for _ in range(SENDER_SOCKETS)]
        self.datagrams = 0
        self.lines = 0

    def send(self, datagrams: list, lines_per_s: float) -> dict:
        """Open-loop paced send; returns how late the generator ran."""
        t0 = time.monotonic()
        sent_lines, worst_lag = 0, 0.0
        for i, (payload, n) in enumerate(datagrams):
            due = t0 + sent_lines / lines_per_s
            now = time.monotonic()
            if now < due:
                time.sleep(due - now)
            else:
                worst_lag = max(worst_lag, now - due)
            self.socks[i % SENDER_SOCKETS].sendto(payload, self.addr)
            sent_lines += n
        self.datagrams += len(datagrams)
        self.lines += sent_lines
        return {"datagrams": len(datagrams), "lines": sent_lines,
                "seconds": round(time.monotonic() - t0, 3),
                "generator_worst_lag_s": round(worst_lag, 4)}

    def close(self):
        for s in self.socks:
            s.close()


# ---------------------------------------------------------------------------
# reading the sink back
# ---------------------------------------------------------------------------


def read_flush_file(path: str) -> pd.DataFrame:
    """All ``smoke.*`` rows of the gzip TSV archive (one gzip member per
    flush; Name, Tags, MetricType, Host, Interval, Timestamp, Value,
    Partition), counters turned back from rates into counts."""
    frames = []
    with gzip.open(path, "rb") as f:
        for chunk in pd.read_csv(
                f, sep="\t", header=None, quoting=3, chunksize=2_000_000,
                usecols=[0, 2, 4, 5, 6],
                names=["name", "tags", "type", "host", "interval", "ts",
                       "value", "partition"],
                dtype={"name": str, "type": str, "interval": np.float64,
                       "ts": str, "value": np.float64}):
            name = chunk["name"]
            # the wide series' percentile rows are half of the archive
            # and no check reads them
            keep = ((name.str.startswith("smoke.")
                     & ~(name.str.startswith("smoke.h.")
                         & name.str.endswith("percentile")))
                    | name.str.startswith("veneur.overload."))
            frames.append(chunk[keep])
    df = pd.concat(frames, ignore_index=True)
    rate = df["type"] == "rate"
    df.loc[rate, "value"] = (df.loc[rate, "value"]
                             * df.loc[rate, "interval"]).round()
    return df


def _split(df: pd.DataFrame, prefix: str):
    """(index array, suffix array, values, timestamps) of the rows
    named ``<prefix><i>[.<suffix>]``."""
    sub = df[df["name"].str.startswith(prefix)]
    if sub.empty:
        none = np.empty(0)
        return none.astype(np.int64), none.astype(object), none, none
    parts = sub["name"].str.slice(len(prefix)).str.split(".", n=1,
                                                         expand=True)
    idx = parts[0].astype(np.int64).to_numpy()
    suffix = (parts[1].to_numpy() if parts.shape[1] > 1
              else np.full(len(sub), None))
    return idx, suffix, sub["value"].to_numpy(), sub["ts"].to_numpy()


def compare(rep: Report, load: Load, df: pd.DataFrame) -> None:
    n = load.series

    # wide rounds, by totals over all flushes of the run
    idx, suffix, val, _ts = _split(df, "smoke.h.")
    in_range = (idx >= 0) & (idx < n)
    count = np.zeros(n)
    lo = np.full(n, np.inf)
    hi = np.full(n, -np.inf)
    m = suffix == "count"
    np.add.at(count, idx[m & in_range], val[m & in_range])
    m = suffix == "min"
    np.minimum.at(lo, idx[m & in_range], val[m & in_range])
    m = suffix == "max"
    np.maximum.at(hi, idx[m & in_range], val[m & in_range])
    distinct = len(np.unique(idx))
    rep.check("wide_distinct_series", distinct == n and in_range.all(),
              distinct=distinct, expected=n)
    rep.check("wide_count", np.array_equal(count, np.full(n, 2.0)),
              emitted=int(count.sum()), sent=2 * n,
              series_wrong=int((count != 2).sum()))
    want_lo = load.wide.min(axis=0).astype(np.float32)
    want_hi = load.wide.max(axis=0).astype(np.float32)
    rep.check("wide_min", np.array_equal(lo.astype(np.float32), want_lo),
              series_wrong=int((lo.astype(np.float32) != want_lo).sum()))
    rep.check("wide_max", np.array_equal(hi.astype(np.float32), want_hi),
              series_wrong=int((hi.astype(np.float32) != want_hi).sum()))

    idx, _s, val, _ts = _split(df, "smoke.c.")
    total = np.zeros(N_SCALARS)
    np.add.at(total, idx, val)
    want = load.counters.sum(axis=0).astype(np.float64)
    rep.check("counters_sum", np.array_equal(total, want),
              emitted=int(total.sum()), sent=int(want.sum()),
              series_wrong=int((total != want).sum()))

    idx, _s, val, _ts = _split(df, "smoke.g.")
    last = np.full(N_SCALARS, np.nan)
    # flushes append in time order and, where an index repeats, NumPy
    # assigns the last value: what is left is each gauge's last emission
    last[idx] = val
    rep.check("gauges_last_write", np.array_equal(last, load.gauges[1]),
              series_wrong=int((last != load.gauges[1]).sum()))

    # the dense interval, for its one emission
    idx, suffix, val, ts = _split(df, "smoke.d.")
    nd = load.n_dense
    stamps = np.unique(ts)
    rep.check("dense_one_emission", len(stamps) == 1
              and len(idx) == 6 * nd, flushes=len(stamps), rows=len(idx),
              expected_rows=6 * nd)
    got = {s: np.full(nd, np.nan) for s in
           ("count", "min", "max", "50percentile", "75percentile",
            "99percentile")}
    first = ts == (stamps[0] if len(stamps) else None)
    for s, arr in got.items():
        m = (suffix == s) & first
        arr[idx[m]] = val[m]
    rep.check("dense_count",
              np.array_equal(got["count"], np.full(nd, DENSE_SAMPLES)),
              series_wrong=int((got["count"] != DENSE_SAMPLES).sum()))
    d32 = load.dense.astype(np.float32)
    rep.check("dense_min", np.array_equal(
        got["min"].astype(np.float32), d32.min(axis=1)))
    rep.check("dense_max", np.array_equal(
        got["max"].astype(np.float32), d32.max(axis=1)))
    ordered = np.sort(load.dense, axis=1)
    worst = 0.0
    worst_value_gap = 0.0
    for q, s in ((0.5, "50percentile"), (0.75, "75percentile"),
                 (0.99, "99percentile")):
        ref = np.quantile(load.dense, q, axis=1)
        for i in range(nd):
            x = got[s][i]
            below = np.searchsorted(ordered[i], x, "left") / DENSE_SAMPLES
            upto = np.searchsorted(ordered[i], x, "right") / DENSE_SAMPLES
            err = 0.0 if below <= q <= upto else min(abs(below - q),
                                                     abs(upto - q))
            if not np.isfinite(x):
                err = 1.0
            worst = max(worst, err)
            worst_value_gap = max(worst_value_gap,
                                  abs(x - ref[i]) / max(ref[i], 1e-9))
    rep.check("dense_percentiles", worst <= RANK_ERROR,
              worst_rank_error=round(worst, 5), bound=RANK_ERROR,
              worst_relative_gap_to_np_quantile=round(worst_value_gap, 5))

    idx, _s, val, _ts = _split(df, "smoke.s.")
    est = np.full(N_SETS, np.nan)
    est[idx] = val
    err = np.abs(est - load.set_members) / load.set_members
    rep.check("sets_estimate", len(idx) == N_SETS
              and np.nanmax(err) <= SET_ERROR and np.isfinite(err).all(),
              emissions=len(idx), members=load.set_members,
              worst_relative_error=round(float(np.nanmax(err)), 5),
              bound=SET_ERROR)

    spill = df[df["name"].str.startswith("veneur.overload.")
               & (df["value"] != 0)]
    rep.check("sink_no_overload_metric", spill.empty,
              rows=spill[["name", "value"]].head(8).values.tolist())


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def _seconds(text: str) -> float:
    for unit, mult in (("ms", 1e-3), ("s", 1.0), ("m", 60.0)):
        if text.endswith(unit):
            return float(text[:-len(unit)]) * mult
    return float(text)


def _flush_rows(entries: list) -> list:
    rows = []
    for e in entries:
        stages = {s["name"]: s for s in e["stages"]}
        histo = stages.get("store.histograms", {})
        rows.append({
            "interval": e.get("interval"),
            "live_histogram_series": histo.get("series", 0),
            "flush_wall_s": round(e["total_duration_ns"] / 1e9, 4),
            "stage_s": {name: round(stages[name]["duration_ns"] / 1e9, 3)
                        for name in STAGES if name in stages},
            "rungs": sorted({s["rung"] for s in e["stages"]
                             if "rung" in s}),
        })
    return rows


def run(args, rep: Report, out_dir: str, scratch: str) -> dict:
    """Returns the device section the child reported ({} if none)."""
    interval = _seconds(args.interval)
    capacity = 1 << max(10, (args.series - 1).bit_length())
    statsd_port = _free_port(socket.SOCK_DGRAM)
    http_port = _free_port(socket.SOCK_STREAM)
    flush_file = os.path.join(scratch, "flush.tsv.gz")
    config_path = os.path.join(out_dir, "config.yaml")
    with open(config_path, "w") as f:
        f.write(_config(args, capacity, statsd_port, http_port, flush_file))

    t0 = time.monotonic()
    load = Load(args.series, args.seed)
    rep.line(phase="load_built", series=args.series, seed=args.seed,
             chips=args.chips, capacity=capacity,
             wide_datagrams_per_round=sum(map(len, load.rounds[0])),
             dense_series=load.n_dense, set_members=load.set_members,
             dense_datagrams=len(load.dense_datagrams),
             seconds=round(time.monotonic() - t0, 2))

    child = Child(config_path, os.path.join(out_dir, "server.log"),
                  http_port)
    sender = Sender(statsd_port)
    watcher = Watcher(child)
    device: dict = {}
    try:
        if not rep.check("child_ready", child.wait_ready(600.0),
                         seconds=round(time.monotonic() - child.started,
                                       2)):
            return device
        ready_s = time.monotonic() - child.started

        # -- warm-up, reported as set-up: every program compiles here --
        sender.send(load.warm, LINES_PER_S)
        child.wait_flushes(1, 600.0)
        sender.send(load.warm, LINES_PER_S)
        warm = child.wait_flushes(2, 600.0)
        v = child.get("/debug/vars")
        device = v.get("device", {})
        kernels = v["obs"]["kernels"]
        variants_warm = {k: c for k, c in
                         kernels["compiled_variants"].items() if c}
        rep.line(phase="warm_up", setup="not steady state",
                 ready_s=round(ready_s, 2),
                 setup_s=round(time.monotonic() - child.started, 2),
                 compile=kernels["compile"],
                 compiled_variants=variants_warm,
                 kernel_traces=kernels["kernel_traces"], device=device,
                 last_flush_wall_s=round(
                     warm[-1]["total_duration_ns"] / 1e9, 3))
        watcher.start()

        # -- wide rounds: every series once a round, constant pace.
        #    Each half starts at a tick (the warm-up's second flush is
        #    the first) and the next waits for the first tick after it
        #    was sent, however many intervals the sending spanned --
        for r, halves in enumerate(load.rounds):
            for h, datagrams in enumerate(halves):
                rep.line(phase=f"wide_round_{r + 1}_half_{h + 1}",
                         **sender.send(datagrams, LINES_PER_S))
                child.wait_flushes(child.published() + 1,
                                   4 * interval + 600.0)

        # -- one dense interval: starts right after a tick, ends by 0.6
        #    of the interval --
        tick = time.monotonic()
        budget = 0.5 * interval
        lines = sum(n for _p, n in load.dense_datagrams)
        sent = sender.send(load.dense_datagrams, lines / budget)
        took = time.monotonic() - tick
        rep.check("dense_sent_in_window", took <= 0.6 * interval,
                  seconds_after_tick=round(took, 3),
                  window_s=round(0.6 * interval, 3), **sent)

        # -- the dense interval's flush; SIGTERM's final flush takes
        #    whatever a lane still held --
        child.wait_flushes(child.published() + 1, 4 * interval + 600.0)
        watcher.done.set()
        watcher.join()
        v = child.get("/debug/vars")
        entries = child.timeline()
    finally:
        watcher.done.set()
        sender.close()
        rc = child.stop()

    device = v.get("device", device)
    device.setdefault("digest_planes", watcher.digest_planes)
    flushes = _flush_rows(entries)
    for row in flushes:
        rep.line(flush=row)
    loaded = [r["flush_wall_s"] for r in flushes
              if r["live_histogram_series"] >= args.series // 8]
    rep.line(observations="not benchmark results", series=args.series,
             seed=args.seed, interval=args.interval,
             flush_wall_s_median_loaded=(float(np.median(loaded))
                                         if loaded else None),
             flush_wall_s_max=max(r["flush_wall_s"] for r in flushes),
             peak_bytes_in_use=device.get("peak_bytes_in_use"),
             compile=v["obs"]["kernels"]["compile"])

    # -- nothing lost, nothing refused --
    totals = v["ingest_fleet"][0]["totals"]
    lanes = v["ingest_fleet"][0]["per_lane"]
    rep.check("datagrams_received", totals["packets"] == sender.datagrams,
              sent=sender.datagrams, received=totals["packets"],
              lines_sent=sender.lines, lines_parsed=totals["parsed"])
    rep.check("native_ingest", all(ln["native_decode"] and ln["recvmmsg"]
                                   for ln in lanes),
              lanes=[{"native_decode": ln["native_decode"],
                      "recvmmsg": ln["recvmmsg"],
                      "packets": ln["packets"]} for ln in lanes])
    ov = v["overload"]
    refused = {"shed": ov["shed"], "quarantined": ov["quarantined"],
               "spilled": watcher.spilled,
               "lane_shed": {k: totals[k] for k in
                             ("shed_packets", "shed_records", "shed_chunks",
                              "quarantined", "parse_errors")},
               "packet_errors": v.get("packet_errors"),
               "packet_drops": v.get("packet_drops")}
    clean = (not any(ov["shed"].values())
             and not any(ov["quarantined"].values())
             and not watcher.spilled
             and not any(refused["lane_shed"].values())
             and not v.get("packet_errors") and not v.get("packet_drops"))
    rep.check("nothing_shed_quarantined_spilled", clean, **refused)
    rep.check("overload_level_zero", watcher.max_level == 0
              and ov["level"] == 0 and ov["level_changes"] == 0,
              max_level_seen=watcher.max_level,
              level_changes=ov["level_changes"],
              polls_failed=watcher.errors)

    # -- no hidden fallback --
    rep.check("platform", device.get("platform") == "tpu"
              and device.get("count") == args.chips
              and device.get("digest_planes", {}).get("platform") == "tpu",
              device=device, chips_wanted=args.chips)
    rungs = sorted({r for row in flushes for r in row["rungs"]})
    rep.check("rung", rungs == ["pallas"], rungs_seen=rungs,
              last_rung=ov["compute"].get("last_rung"))
    kernels = v["obs"]["kernels"]
    rep.check("kernel_compiled",
              sum(kernels["kernel_traces"][k] for k in KERNELS) >= 1,
              kernel_traces=kernels["kernel_traces"])
    compute = ov["compute"]
    rep.check("compute_breaker_closed",
              all(s == 0.0 for s in compute["kernels"].values())
              and compute["fallback_total"] == 0
              and compute["requeued_total"] == 0
              and compute["lost_total"] == 0, compute=compute)
    variants_end = {k: c for k, c in
                    kernels["compiled_variants"].items() if c}
    rep.check("no_compile_after_warm_up", variants_end == variants_warm,
              after_warm_up=variants_warm, after_last_flush=variants_end)
    with open(child.log_path, errors="replace") as f:
        errors = [ln.rstrip() for ln in f
                  if " ERROR " in ln or " CRITICAL " in ln
                  or ln.startswith("Traceback")]
    rep.check("child_log_clean", not errors, errors=errors[:8])
    rep.check("child_exit_zero", rc == 0, returncode=rc)

    if args.chips == 4:
        mesh = v.get("mesh", {})
        planes = device.get("digest_planes", {}).get("devices", [])
        occ = watcher.peak_occupancy
        rep.check("mesh_four_devices", mesh.get("devices") == 4
                  and mesh.get("axes") == {"series": 2, "hosts": 2}
                  and len(set(planes)) == 4, mesh_devices=mesh.get(
                      "devices"), axes=mesh.get("axes"),
                  digest_plane_devices=planes)
        rep.check("mesh_shards_balanced", len(occ) == 2
                  and all(o > 0 for o in occ)
                  and watcher.balance_at_peak is not None
                  and watcher.balance_at_peak < 1.5,
                  peak_shard_occupancy=occ,
                  balance_ratio=watcher.balance_at_peak)

    # -- what came out, against the float64 reference --
    t0 = time.monotonic()
    df = read_flush_file(flush_file)
    rep.line(phase="sink_read", rows=len(df),
             seconds=round(time.monotonic() - t0, 2))
    compare(rep, load, df)
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--series", type=int, default=1 << 20,
                    help="distinct histogram series of a wide round")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--interval", default="10s")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = the mesh-sharded global instance, alone")
    ap.add_argument("--out", default=None,
                    help="directory for config.yaml, server.log and "
                         "report.jsonl (default: a temporary one)")
    args = ap.parse_args(argv)

    scratch = tempfile.mkdtemp(prefix="chip_smoke-")
    out_dir = args.out or scratch
    os.makedirs(out_dir, exist_ok=True)
    rep = Report(os.path.join(out_dir, "report.jsonl"))
    device: dict = {}
    try:
        device = run(args, rep, out_dir, scratch)
    except Exception as e:  # a phase that raised is a phase that failed
        rep.check("run_completed", False, error=repr(e))
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    # one process per chip: the server is that process, never this one
    rep.check("parent_never_imported_jax", "jax" not in sys.modules)
    ok = not rep.failed
    rep.line(failed_checks=rep.failed)
    print(json.dumps({"ok": ok, "device": {
        "platform": device.get("platform"),
        "kind": device.get("device_kind"),
        "count": device.get("count")}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
