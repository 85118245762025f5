"""The end the harness holds whatever feeds the server
(``benchmark/feeds/``): the loopback receiver that stands where
Datadog's API would. It stamps ``time.time()``, the clock the flush
timeline's ``wall_start`` and the feeds' due times are on."""

from __future__ import annotations

import http.server
import socket
import threading
import time


def free_port(kind: int) -> int:
    with socket.socket(socket.AF_INET, kind) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Receiver:
    """Reads each body whole, stamps the clock at its last byte, answers
    202, and parses nothing while the child lives."""

    def __init__(self):
        self.bodies: list = []   # (stamp, path, content-encoding, bytes)
        lock = threading.Lock()
        receiver = self

        class Handler(http.server.BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                body = self.rfile.read(n)
                stamp = time.time()
                self.send_response(202)
                self.send_header("Content-Length", "0")
                self.end_headers()
                encoding = self.headers.get("Content-Encoding", "")
                body, encoding = receiver.as_stored(body, encoding)
                with lock:
                    receiver.bodies.append(
                        (stamp, self.path, encoding, body))

            do_PUT = do_POST

            def log_message(self, *args):
                pass

        class Server(http.server.ThreadingHTTPServer):
            daemon_threads = True
            request_queue_size = 256

        self.httpd = Server(("127.0.0.1", 0), Handler)
        self.port = self.httpd.server_address[1]
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        kwargs={"poll_interval": 0.05},
                                        daemon=True)

    def as_stored(self, body: bytes, encoding: str) -> tuple:
        """What is kept of a body, untouched here; the fault tests put
        their faults in at this point."""
        return body, encoding

    def start(self):
        self._thread.start()

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()
        self._thread.join()
