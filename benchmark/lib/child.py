"""The server child (the one process that holds the chip) and the
thread that polls its ``/debug/vars`` while the load runs. Copied from
the smoke that PR 35 deleted (``chip_smoke.py``), so that no change to
the program's own tools can move the yardstick; the parent never imports
JAX."""

from __future__ import annotations

import json
import os
import re
import signal
import subprocess
import sys
import threading
import time
import urllib.request

from benchmark.lib.cells import ROOT

CLK_TCK = os.sysconf("SC_CLK_TCK")
LOG_RECORD = re.compile(
    r"^\d{4}-\d\d-\d\d \S+ (DEBUG|INFO|WARNING|ERROR|CRITICAL) ")


class Child:
    def __init__(self, config_path: str, log_path: str, http_port: int,
                 env: dict):
        self.http = f"http://127.0.0.1:{http_port}"
        self.log_path = log_path
        self._log = open(log_path, "w")
        self.started = time.time()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "veneur_tpu.cli.server", "-f",
             config_path], cwd=ROOT, stdout=self._log,
            stderr=subprocess.STDOUT, env=env)

    def get(self, path: str, timeout: float = 10.0, text: bool = False):
        with urllib.request.urlopen(self.http + path,
                                    timeout=timeout) as resp:
            body = resp.read()
        if text or not path.startswith("/debug"):
            return body
        return json.loads(body)

    def wait_ready(self, deadline_s: float) -> bool:
        while time.time() - self.started < deadline_s:
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

    def published(self) -> int:
        return self.get("/debug/flush-timeline?n=1")["published_total"]

    def wait_flushes(self, count: int, deadline_s: float,
                     quiet_until: float = 0.0) -> float:
        """Block until the timeline has published ``count`` intervals;
        returns the wall clock at which that was seen. Asks nothing of
        the child before ``quiet_until``."""
        end = time.time() + deadline_s
        time.sleep(max(0.0, quiet_until - time.time()))
        while time.time() < end and self.proc.poll() is None:
            data = self.get("/debug/flush-timeline?n=1")
            if data["published_total"] >= count:
                return time.time()
            time.sleep(0.02)
        raise TimeoutError(f"no flush #{count} within {deadline_s:.0f}s")

    def cpu_seconds(self) -> float:
        """User + system CPU seconds of the child, all threads
        (``/proc/<pid>/stat`` fields 14 and 15)."""
        with open(f"/proc/{self.proc.pid}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        return (int(fields[11]) + int(fields[12])) / CLK_TCK

    def thread_cpu_seconds(self) -> dict:
        """{tid: (thread name, user + system CPU seconds)}."""
        out = {}
        base = f"/proc/{self.proc.pid}/task"
        for tid in os.listdir(base):
            try:
                with open(f"{base}/{tid}/stat") as f:
                    head, tail = f.read().rsplit(")", 1)
            except OSError:
                continue            # the thread ended meanwhile
            fields = tail.split()
            out[tid] = (head.split("(", 1)[1],
                        (int(fields[11]) + int(fields[12])) / CLK_TCK)
        return out

    def stop(self) -> int:
        """SIGTERM, wait for the final flush, return the exit code;
        never leaves the child behind."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=120)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()
        return self.proc.returncode

    def log_errors(self) -> tuple:
        """(errors, warnings) of the child's log. An error is a record
        at ERROR or CRITICAL, an uncaught exception, or a traceback that
        belongs to either; a traceback under a WARNING record is the
        program saying that it handled the exception, and is a warning."""
        errors, warnings, level = [], [], ""
        with open(self.log_path, errors="replace") as f:
            for ln in f:
                ln = ln.rstrip()
                m = LOG_RECORD.match(ln)
                if m:
                    level = m.group(1)
                    if level in ("ERROR", "CRITICAL"):
                        errors.append(ln)
                    elif level == "WARNING":
                        warnings.append(ln)
                elif ln.startswith("Exception in thread"):
                    level = "ERROR"
                    errors.append(ln)
                elif ln.startswith("Traceback") and level != "WARNING":
                    errors.append(ln)
        return errors, warnings


class Watcher(threading.Thread):
    """Polls /debug/vars at 1 Hz while the window runs: the overload
    level and the per-interval spill tallies reset, and the lane backlog
    and a mesh's shard occupancy are levels that only exist while an
    interval is live. Keeps of every poll the lanes' totals, the
    overload level and the ``sections`` of ``/debug/vars`` that the
    cell's metrics ask for (``readers/vars_path.py`` ``polled``)."""

    def __init__(self, child: Child, sections=()):
        super().__init__(daemon=True)
        self.child = child
        self.sections = set(sections) - {"ingest_fleet", "overload"}
        self.done = threading.Event()
        self.polls: list = []
        self.max_level = 0
        self.spilled: dict = {}
        self.digest_planes: dict = {}
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
            self.polls.append({
                "time": time.time(),
                "ingest_fleet": [{"totals": f.get("totals", {})}
                                 for f in v.get("ingest_fleet", [])],
                "overload": {"level": ov.get("level", 0),
                             "pressure": ov.get("pressure", 0.0)},
                **{name: v[name] for name in self.sections if name in v}})

    def stop(self):
        self.done.set()
        if self.is_alive():
            self.join()
