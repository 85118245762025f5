"""Finds a cell and everything that belongs to it by name.

``BENCHMARK.json`` names the cells, configurations and per-layer
metrics; every one of them is a file of its own under ``benchmark/``.
Nothing in this module, or in any other module of the harness, knows a
cell, a configuration, a traffic mix or a metric by name.
"""

from __future__ import annotations

import importlib
import json
import os

from benchmark import feeds

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with its configuration, its traffic
    mix and the metrics it has to report."""

    def __init__(self, workload: str, manifest_path: str = "",
                 traffic_dir: str = ""):
        self.manifest = read_json(
            manifest_path or os.path.join(ROOT, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if workload not in cells:
            raise SystemExit(f"no workload {workload!r}; have "
                             f"{sorted(cells)}")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in self.manifest["configs"]}
        self.config = read_json(
            os.path.join(ROOT, configs[self.entry["config"]]["file"]))
        self.traffic = read_json(os.path.join(
            traffic_dir or os.path.join(BENCH_DIR, "traffic"),
            self.entry["traffic"] + ".json"))
        self.interval_s = float(self.config["interval_s"])

    def reports(self, metric: dict) -> bool:
        """Whether this cell is among the metric's ``workloads`` (a
        metric without the key is reported in every cell)."""
        return self.name in metric.get("workloads", [self.name])

    def end_to_end(self) -> list:
        return [m for m in self.manifest["end_to_end"] if self.reports(m)]

    def measure_of(self, metric: dict) -> str:
        """Which of the harness's own measures an end-to-end metric is
        (``benchmark/end_to_end/<name>.json``)."""
        return read_json(os.path.join(
            BENCH_DIR, "end_to_end", metric["name"] + ".json"))["measure"]

    def per_layer(self) -> list:
        """[(manifest entry, the metric's own file)] for this cell."""
        out = []
        for m in self.manifest["per_layer"]:
            if self.reports(m):
                spec = read_json(os.path.join(
                    BENCH_DIR, "layer_metrics", m["name"] + ".json"))
                out.append((m, spec))
        return out

    def polled_sections(self) -> set:
        """The sections of ``/debug/vars`` that every poll has to keep:
        what the cell's per-layer metrics' readers say they read from
        the polls (a reader's ``polled(args)``, where it has one)."""
        out = set()
        for _m, spec in self.per_layer():
            polled = getattr(reader(spec["reader"]), "polled", None)
            if polled is not None:
                out.update(polled(spec["args"]))
        return out

    def generator(self):
        """The traffic mix's generator module, found by name."""
        return importlib.import_module(
            "benchmark.generators." + self.traffic["generator"])

    def feed(self):
        """The module that carries the mix to the server, found by the
        mix's ``feed``."""
        return importlib.import_module(
            "benchmark.feeds." + self.traffic.get("feed", feeds.DEFAULT))

    def server_config_text(self, ports: dict) -> str:
        """The configuration as the server reads it: the file's
        ``server`` keys, one ``key: <JSON value>`` a line (which is
        YAML), with ``{http_port}``, ``{receiver_port}`` and the feed's
        own ports filled in."""
        lines = []
        for key, value in self.config["server"].items():
            text = json.dumps(value)
            for name, port in ports.items():
                text = text.replace("{" + name + "}", str(port))
            lines.append(f"{key}: {text}")
        return "\n".join(lines) + "\n"


def reader(name: str):
    """A per-layer metric's reader module, found by name."""
    return importlib.import_module("benchmark.readers." + name)


def peaks(device_kind: str) -> dict:
    table = read_json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmark/peaks.json; add it with its source")
    return table["devices"][device_kind]
