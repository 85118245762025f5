"""The main server binary (``/root/reference/cmd/veneur/main.go:22-88``):
``-f config.yaml``, bring up the server, serve until signalled.
"""

from __future__ import annotations

import argparse
import logging
import os
import signal
import sys
import threading
from typing import Optional

from veneur_tpu.cli import upgrade
from veneur_tpu.config import read_config
from veneur_tpu.server import Server

log = logging.getLogger("veneur")

# <checkout>/.jax_cache: a fixed path, because the path is part of the
# cache key's reach — a directory that moves (a temporary name, a pid,
# a time) never hits
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> Optional[str]:
    """Give JAX's persistent compilation cache a home, so a restart
    does not pay every first compile again. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set in code (returns None); otherwise the fixed
    in-checkout path is used and returned."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="veneur")
    ap.add_argument("-f", dest="config", required=True,
                    help="The config file to read for settings.")
    args = ap.parse_args(argv)
    # record the exact launch command line so a SIGUSR2 upgrade
    # re-execs what the operator ran, flags included
    upgrade.record_startup_argv("veneur_tpu.cli.server", argv)

    try:
        config = read_config(args.config)
    except Exception as e:
        log.error("Error reading config file: %s", e)
        return 1

    logging.basicConfig(
        level=logging.DEBUG if config.debug else logging.INFO,
        format="%(asctime)s %(levelname)s %(name)s %(message)s")

    place_compile_cache()
    server = Server(config)

    done = threading.Event()

    def handle_signal(signum, frame):
        log.info("Received signal %d, shutting down", signum)
        # marks the stop operator-requested before setting done, so a
        # racing SIGUSR2 handoff cannot leave a replacement serving
        upgrade.request_shutdown(done)

    def handle_hup(signum, frame):
        # graceful in-process reload (reference HUP path,
        # server.go:1048-1076): re-read the file, hot-swap what can be
        # swapped, keep sockets and store state. Runs on a thread so the
        # signal handler never blocks in sink construction.
        def do_reload():
            try:
                new_cfg = read_config(args.config)
            except Exception as e:
                log.error("SIGHUP reload: config re-read failed, keeping "
                          "the running config: %s", e)
                return
            try:
                server.reload(new_cfg)
            except Exception:
                log.exception("SIGHUP reload failed; continuing with the "
                              "previous configuration")

        log.info("Received SIGHUP, reloading configuration from %s",
                 args.config)
        threading.Thread(target=do_reload, name="config-reload",
                         daemon=True).start()

    # zero-downtime binary upgrade (the reference's einhorn/SIGUSR2
    # handoff, server.go:1048-1076, redesigned over SO_REUSEPORT — see
    # cli/upgrade.py): spawn a replacement, drain only once it serves
    handle_usr2 = upgrade.make_sigusr2_handler(
        args.config, "veneur_tpu.cli.server", done, log)

    # register handlers BEFORE the (slow: jax init + first compiles)
    # server start, so a signal during startup hits the handler rather
    # than the default action killing the half-started process
    signal.signal(signal.SIGTERM, handle_signal)
    signal.signal(signal.SIGINT, handle_signal)
    if hasattr(signal, "SIGHUP"):
        signal.signal(signal.SIGHUP, handle_hup)
    if hasattr(signal, "SIGUSR2"):
        signal.signal(signal.SIGUSR2, handle_usr2)

    server.start()
    log.info("Starting server on %s (statsd) / %s (ssf)",
             server.statsd_addrs, server.ssf_addrs)
    # if we are the replacement generation of an upgrade, release the
    # old generation to drain now that our sockets are serving
    upgrade.notify_ready()

    # HTTPServe/gRPCServe when configured, else block forever
    # (cmd/veneur/main.go:66-88)
    done.wait()
    try:
        server.shutdown()
    finally:
        # if shutdown raced an upgrade, the replacement's handoff never
        # completed and it must not outlive this generation — even when
        # the drain itself raised
        upgrade.reap_unfinished_replacement(log)
    return 0


if __name__ == "__main__":
    sys.exit(main())
