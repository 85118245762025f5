"""Per-kernel compute circuit breakers: the flush-kernel fallback ladder.

The egress breakers (breaker.py) protect the network edge; this wraps the
OTHER failure-prone edge, the batched XLA/Pallas device programs. A
runtime failure of the fused t-digest merge kernel (TPU preemption, a
Mosaic compile error after a config change, a driver wedge) must degrade
the flush, not lose the interval:

    rung 1  Pallas-fused program       (breaker closed, or half-open probe)
    rung 2  interpret/jnp program      (same math, XLA-only; ``use_pallas``
                                        statics retrace without the kernel)
    rung 3  re-merge the generation    (MetricStore re-imports the retired
            into the live store        group's snapshot — the interval
                                        emits LATE next flush, never lost;
                                        PR 2's checkpoint then persists it
                                        on its normal cadence)

``failure_threshold`` consecutive rung-1 failures open the kernel's
breaker: subsequent flushes (and the staging drains, which share the
kernel) go straight to the jnp path without paying a doomed dispatch.
After ``reset_timeout`` one flush probes the kernel again; success closes
the breaker. State rides ``veneur.breaker.state`` tagged with the kernel
name, next to the egress destinations.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, List, Optional, Tuple

from veneur_tpu.resilience.breaker import CLOSED, BreakerRegistry

log = logging.getLogger("veneur.resilience.compute")

# today's only governed kernel: the fused t-digest merge/quantile
# (ops/tdigest_pallas.py) every digest drain and flush dispatches
KERNEL_TDIGEST = "compute.tdigest_merge"

DEFAULT_FAILURE_THRESHOLD = 2
DEFAULT_RESET_TIMEOUT = 60.0


class ComputeBreaker:
    """Thread-safe per-kernel breaker bundle + degradation tallies."""

    def __init__(self, failure_threshold: int = DEFAULT_FAILURE_THRESHOLD,
                 reset_timeout: float = DEFAULT_RESET_TIMEOUT,
                 clock: Callable[[], float] = time.monotonic):
        self._registry = BreakerRegistry(
            failure_threshold=max(1, failure_threshold),
            reset_timeout=reset_timeout, half_open_max=1, clock=clock)
        self._lock = threading.Lock()
        # deterministic fault hook: when set, ``preflight`` consults it
        # before every rung-1 dispatch (resilience/faults.py semantics)
        self.injector = None
        self.fallback_total = 0   # group flushes completed on the jnp rung
        self.requeued_total = 0   # rung 3: generations re-merged, late
        self.lost_total = 0       # every rung failed; checkpoint bounds it
        # the program the last digest flush really ran: "pallas" only
        # when the fused kernel was admitted into it (core/store.py
        # _note_rung), else "xla"; None before the first flush
        self.last_rung: Optional[str] = None

    def probe(self, kernel: str = KERNEL_TDIGEST) -> bool:
        """May this flush attempt the Pallas rung right now? Consumes the
        half-open probe budget, so only the flush path calls it."""
        return self._registry.get(kernel).allow()

    def degraded(self, kernel: str = KERNEL_TDIGEST) -> bool:
        """Cheap read for non-probing callers (the staging drains): stay
        on the jnp path while the kernel's breaker is not closed."""
        return self._registry.get(kernel).state != CLOSED

    def preflight(self, kernel: str = KERNEL_TDIGEST) -> None:
        """Raise the scheduled injected fault, if an injector is armed —
        BEFORE dispatch, so donated device buffers survive for rung 2.
        Machine-checked: the donation-safety pass (lint/deviceflow.py
        PREFLIGHT_CONTRACT) flags any registered compute ladder that
        dispatches before calling this."""
        inj = self.injector
        if inj is not None:
            inj.maybe_fail(kernel)

    def record_success(self, kernel: str = KERNEL_TDIGEST) -> None:
        self._registry.get(kernel).record_success()

    def record_failure(self, kernel: str = KERNEL_TDIGEST) -> None:
        self._registry.get(kernel).record_failure()

    def count_fallback(self, n: int = 1) -> None:
        with self._lock:
            self.fallback_total += n

    def count_requeued(self, n: int = 1) -> None:
        with self._lock:
            self.requeued_total += n

    def count_lost(self, n: int = 1) -> None:
        with self._lock:
            self.lost_total += n

    def states(self) -> List[Tuple[str, float]]:
        """(kernel, state gauge) pairs for telemetry; empty until a
        kernel has been consulted once."""
        return self._registry.states()

    def snapshot(self) -> dict:
        return {"kernels": {name: gauge for name, gauge in self.states()},
                "fallback_total": self.fallback_total,
                "requeued_total": self.requeued_total,
                "lost_total": self.lost_total,
                "last_rung": self.last_rung}


def from_config(cfg, clock: Callable[[], float] = time.monotonic
                ) -> Optional["ComputeBreaker"]:
    """Build the configured compute breaker (always on; the knobs only
    tune it — a flush kernel without a fallback ladder is the round-4
    audit's definition of failing open)."""
    return ComputeBreaker(
        failure_threshold=int(getattr(
            cfg, "compute_breaker_failure_threshold", 0)
            or DEFAULT_FAILURE_THRESHOLD),
        reset_timeout=float(getattr(
            cfg, "compute_breaker_reset_timeout_seconds", 0.0)
            or DEFAULT_RESET_TIMEOUT),
        clock=clock)
