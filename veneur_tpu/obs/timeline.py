"""The flush-interval timeline ring: last-N interval records as JSON.

Each completed flush publishes its :class:`StageRecorder` record here;
``GET /debug/flush-timeline`` (debug.py) serves the ring. The ring is
bounded (``obs_timeline_intervals``, default 64) so a long-lived
server's timeline costs fixed memory, and entries are plain dicts so
the late off-path forward stage can land in an already-published
interval (recorder.record_late)."""

from __future__ import annotations

import collections
import json
import threading
import uuid
from typing import List, Optional

DEFAULT_INTERVALS = 64


class FlushTimeline:
    """Bounded ring of per-interval stage records."""

    def __init__(self, intervals: int = DEFAULT_INTERVALS):
        self.capacity = max(1, int(intervals))
        # per-process identity served at /debug/flush-timeline: how
        # the fleet aggregator recognizes a pull of ITSELF (fleet_peers
        # lists every instance, including the puller)
        self.uid = uuid.uuid4().hex
        self._ring: "collections.deque" = collections.deque(
            maxlen=self.capacity)
        # shared by publish and the read side: list(deque) raises
        # RuntimeError if an append lands mid-iteration, and the debug
        # endpoints read from arbitrary request threads while the
        # flusher (and the fleet aggregator's pulls) publish
        self._lock = threading.Lock()
        self.published_total = 0

    def publish(self, entry: dict) -> dict:
        with self._lock:
            entry["interval"] = self.published_total
            self.published_total += 1
            self._ring.append(entry)
        return entry

    def entries(self, last: Optional[int] = None) -> List[dict]:
        with self._lock:
            snap = list(self._ring)
        if last is not None and last > 0:
            snap = snap[-last:]
        return snap

    def snapshot(self) -> dict:
        """Summary for /debug/vars (the full ring rides its own
        endpoint)."""
        with self._lock:
            snap = list(self._ring)
        return {"published_total": self.published_total,
                "ring_capacity": self.capacity,
                "last_total_duration_ns":
                    snap[-1]["total_duration_ns"] if snap else None,
                "last_coverage_ratio":
                    snap[-1]["coverage_ratio"] if snap else None}

    def handler(self, query) -> tuple:
        """The GET /debug/flush-timeline route body: ``?n=K`` limits to
        the most recent K intervals. ``instance_uid`` identifies this
        process: the fleet aggregator (obs/fleet.py) drops a pulled
        peer whose uid matches its own timeline's, so an operator
        listing every instance in one shared ``fleet_peers`` never
        gets its hops stitched twice."""
        try:
            last = int(query.get("n", "0") or 0)
        except ValueError:
            return 400, "n must be an integer", "text/plain"
        body = json.dumps({
            "published_total": self.published_total,
            "ring_capacity": self.capacity,
            "instance_uid": self.uid,
            "intervals": self.entries(last or None),
        }, default=str)
        return 200, body, "application/json"
