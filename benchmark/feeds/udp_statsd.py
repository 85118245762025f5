"""DogStatsD datagrams at the server's UDP statsd port. A unit is
``(payload, lines)``; the mix gives ``sockets``, the number of source
ports the clients send from."""

from __future__ import annotations

import socket
import time

PORTS = {"statsd_port": socket.SOCK_DGRAM}


class Feed:
    """Open loop: every datagram has a due time fixed before the round
    starts, and the sender never waits for the server."""

    def __init__(self, ports: dict, traffic: dict):
        self.addr = ("127.0.0.1", ports["statsd_port"])
        self.socks = [socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
                      for _ in range(int(traffic["sockets"]))]
        self.datagrams = 0

    def send(self, units: list, start: float, span_s: float) -> list:
        """Lines due evenly over ``[start, start + span_s]``; returns
        [(due, sent, n_lines)] a datagram."""
        total = sum(n for _p, n in units) or 1
        log, done = [], 0
        for i, (payload, n) in enumerate(units):
            due = start + span_s * done / total
            now = time.time()
            if now < due:
                time.sleep(due - now)
                now = time.time()
            self.socks[i % len(self.socks)].sendto(payload, self.addr)
            log.append((due, now, n))
            done += n
        self.datagrams += len(units)
        return log

    def checks(self, rep, v: dict, patience_s: float) -> None:
        """What this path guarantees: every datagram sent was received,
        by the native lanes, and no lane shed or refused one."""
        totals = v["ingest_fleet"][0]["totals"]
        lanes = v["ingest_fleet"][0]["per_lane"]
        rep.check("datagrams_received", totals["packets"] == self.datagrams,
                  sent=self.datagrams, received=totals["packets"],
                  lines_parsed=totals["parsed"])
        rep.check("native_ingest", all(ln["native_decode"] and ln["recvmmsg"]
                                       for ln in lanes))
        lane_shed = {k: totals[k] for k in (
            "shed_packets", "shed_records", "shed_chunks", "quarantined",
            "parse_errors")}
        rep.check("lanes_shed_nothing", not any(lane_shed.values())
                  and not v.get("packet_errors")
                  and not v.get("packet_drops"), lane_shed=lane_shed)

    def close(self):
        for s in self.socks:
            s.close()
