"""What a global is fed by: ``forwardrpc.Forward/SendMetrics`` on the
server's ``grpc_address``, from as many clients as the mix has
``forwarders``, each over a channel and a connection of its own. A unit
is ``(payload, entries, forwarder, share, after)``: the serialized
``MetricList``, the series entries in it, who sends it, and when it is
due: ``share`` of the way through the span, or, where ``after`` is a
number, that many seconds after the tick that ends the interval (the
span and the mix's ``guard_s`` after the round's start), so that it
lands in the next emission.

Open loop: every message has its due time before the round starts, and
one thread fires each at that time whatever is still unanswered; the
replies come in on gRPC's own threads. ``send`` returns once every
message that is due before the tick has its reply, with the log of all
that were fired since the call before: those due after an earlier tick
among them.
"""

from __future__ import annotations

import heapq
import socket
import threading
import time

import grpc

PORTS = {"grpc_port": socket.SOCK_STREAM}
METHOD = "/forwardrpc.Forward/SendMetrics"
MAX_MESSAGE = 256 * 1024 * 1024
REPLY_TIMEOUT_S = 120.0


def _as_is(data: bytes) -> bytes:
    return data


class Feed:
    def __init__(self, ports: dict, traffic: dict):
        self.guard_s = float(traffic["guard_s"])
        target = f"127.0.0.1:{ports['grpc_port']}"
        # a local subchannel pool: one connection a forwarder, not one
        # shared by all channels of the process
        self.channels = [grpc.insecure_channel(target, options=[
            ("grpc.use_local_subchannel_pool", 1),
            ("grpc.max_send_message_length", MAX_MESSAGE)])
            for _ in range(int(traffic["forwarders"]))]
        self.calls = [c.unary_unary(METHOD, request_serializer=_as_is,
                                    response_deserializer=_as_is)
                      for c in self.channels]
        self.messages = 0
        self.entries = 0
        self.refused: list = []
        self.slowest_reply_s = 0.0
        self._cv = threading.Condition()
        self._heap: list = []     # (due, ordinal, forwarder, payload, n, waited)
        self._log: list = []      # fired since the last ``send`` returned
        self._waited_for = 0      # unanswered messages a ``send`` waits for
        self._unanswered = 0
        self._closed = False
        self._thread = threading.Thread(target=self._fire, daemon=True,
                                        name="forwarders")
        self._thread.start()

    def send(self, units: list, start: float, span_s: float) -> list:
        with self._cv:
            for payload, n, forwarder, share, after in units:
                on_time = after is None
                due = (start + span_s * share if on_time
                       else start + span_s + self.guard_s + after)
                heapq.heappush(self._heap, (
                    due, self.messages, forwarder, payload, n, on_time))
                self._waited_for += on_time
                self.messages += 1
                self.entries += n
            self._cv.notify_all()
            self._cv.wait_for(lambda: not self._waited_for,
                              timeout=span_s + REPLY_TIMEOUT_S)
            log, self._log = sorted(self._log), []
        return log

    def _fire(self) -> None:
        while True:
            with self._cv:
                while not self._closed and (
                        not self._heap or self._heap[0][0] > time.time()):
                    self._cv.wait(self._heap[0][0] - time.time()
                                  if self._heap else None)
                if self._closed:
                    return
                due, _i, forwarder, payload, n, waited = heapq.heappop(
                    self._heap)
                self._unanswered += 1
                sent = time.time()
                self._log.append((due, sent, n))
            reply = self.calls[forwarder].future(
                payload, timeout=REPLY_TIMEOUT_S, wait_for_ready=True)
            reply.add_done_callback(
                lambda r, sent=sent, waited=waited: self._answered(
                    r, sent, waited))

    def _answered(self, reply, sent: float, waited: bool) -> None:
        error = reply.exception()
        with self._cv:
            if error is not None:
                self.refused.append(repr(error))
            self.slowest_reply_s = max(self.slowest_reply_s,
                                       time.time() - sent)
            self._unanswered -= 1
            self._waited_for -= waited
            self._cv.notify_all()

    def checks(self, rep, v: dict, patience_s: float) -> None:
        """What this path guarantees: every entry of every message was
        received and merged, no message was refused, and none waited for
        its reply longer than ``patience_s``, an interval over the
        slowest flush."""
        got = v.get("grpc_import", {})
        rep.check("forwards_received", got.get("received") == self.entries
                  and got.get("errors") == 0, messages=self.messages,
                  entries_sent=self.entries, grpc_import=got)
        queue = v.get("http_import", {})
        rep.check("forwards_answered", not self.refused
                  and not self._unanswered and not self._heap
                  and self.slowest_reply_s < patience_s
                  and not queue.get("shed_batches"),
                  refused=self.refused[:4], unanswered=self._unanswered,
                  never_fired=len(self._heap),
                  slowest_reply_s=self.slowest_reply_s, http_import=queue)

    def close(self):
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        self._thread.join()
        for c in self.channels:
            c.close()
