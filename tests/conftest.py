"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip hardware is not available in CI; sharding tests run over
``xla_force_host_platform_device_count=8`` as recommended by the JAX docs.

``JAX_PLATFORMS`` may already be set (to something else) when pytest
starts, and jax may already be imported by then, so the ``os.environ``
edit alone can come too late — ``jax.config.update`` also works as long
as no backend has been initialized yet.

``VENEUR_TPU_TESTS=1`` inverts the gate: the CPU forcing is skipped so
jax picks the real accelerator, and ONLY ``@pytest.mark.tpu`` tests run
(the kernels' hardware smoke subset, ``tests/test_tpu_smoke.py``; the
served path's proof on the chip is a cell of ``benchmark/run.py``).

``VENEUR_MULTIDEVICE_TESTS=1`` opts into the ``@pytest.mark.multidevice``
lane: fleet-scale tests that NEED the 8-device virtual mesh and more
wall-clock than the tier-1 budget allows (multi-interval mesh soaks,
cross-shard oracles). The light mesh/parallel unit tests stay in tier-1
unmarked — the virtual mesh itself is always forced — so tier-1 time
stays flat while the heavy fleet lane has a runnable, opt-in home:

    VENEUR_MULTIDEVICE_TESTS=1 python -m pytest tests/ -m multidevice
"""

import os

import pytest

RUN_TPU = os.environ.get("VENEUR_TPU_TESTS") == "1"
RUN_MULTIDEVICE = os.environ.get("VENEUR_MULTIDEVICE_TESTS") == "1"

if not RUN_TPU:
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax  # noqa: E402

    jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "tpu: hardware smoke subset; runs only under "
                   "VENEUR_TPU_TESTS=1 (real accelerator)")
    config.addinivalue_line(
        "markers", "slow: sleep-heavy / soak tests excluded from the "
                   "tier-1 gate (-m 'not slow')")
    config.addinivalue_line(
        "markers", "multidevice: fleet-scale virtual-mesh lane; opt in "
                   "with VENEUR_MULTIDEVICE_TESTS=1 (keeps tier-1 time "
                   "flat)")


class FakeClock:
    """A manually-advanced monotonic clock for resilience tests: inject
    ``clock`` into Deadline/CircuitBreaker and ``sleep`` into
    call_with_retry so backoff/expiry tests run in milliseconds."""

    def __init__(self, start: float = 1000.0):
        self.now = start
        self.sleeps = []  # every sleep() duration, for backoff asserts

    def __call__(self) -> float:
        return self.now

    def advance(self, seconds: float) -> None:
        self.now += seconds

    def sleep(self, seconds: float) -> None:
        self.sleeps.append(seconds)
        self.now += seconds


@pytest.fixture
def fake_clock():
    return FakeClock()


@pytest.fixture
def tsan_lite():
    """TSan-lite (veneur_tpu/lint/tsan.py): wrap a MetricStore's
    ``@requires_lock`` group mutators and record lock state at each
    call. v2 also arms the Eraser-style lockset detector
    (veneur_tpu/lint/lockset.py) over the store and groups, so
    unannotated-field races surface in ``rec.races`` with both
    stacks. Usage::

        rec = tsan_lite(store)      # arms immediately
        ... drive threads ...
        rec.assert_clean()          # v1 violations AND lockset races

    Everything armed in the test is disarmed at teardown."""
    from veneur_tpu.lint.tsan import LockStateRecorder

    recorders = []

    def arm(store):
        rec = LockStateRecorder(store)
        rec.arm()
        recorders.append(rec)
        return rec

    yield arm
    for rec in recorders:
        rec.disarm()


@pytest.fixture
def ledger_audit():
    """LedgerAudit (veneur_tpu/lint/ledger_audit.py): the drop-flow
    pass's runtime twin. Arm an audit over an IngestFleet, a
    SoakLedger, or a custom term set; every armed audit's violations
    are asserted at teardown (like ``tsan_lite``), so a test that
    forgets its own ``assert_clean()`` still fails on an uncredited
    drop. Usage::

        audit = ledger_audit(fleet=fleet)        # standard lane terms
        audit = ledger_audit(soak_ledger=ledger) # soak identity
        audit = ledger_audit()                   # .register() your own
        ... drive traffic ...
        audit.snapshot(settled=True)             # drained boundary
    """
    from veneur_tpu.lint import ledger_audit as la

    audits = []

    def arm(fleet=None, soak_ledger=None, name="ledger"):
        if fleet is not None:
            audit = la.for_fleet(fleet)
        elif soak_ledger is not None:
            audit = la.for_soak_ledger(soak_ledger)
        else:
            audit = la.LedgerAudit(name)
        audits.append(audit)
        return audit

    yield arm
    for audit in audits:
        audit.assert_clean()


@pytest.fixture
def buffer_census():
    """BufferCensus (veneur_tpu/lint/buffer_census.py): the
    donation-safety pass's runtime twin. Arm a census over the
    process's live ``jax.Array`` population; every armed census is
    settled and asserted at teardown (like ``ledger_audit``), so a
    test that retains a donated or retired device plane fails even
    without its own ``assert_clean()``. Usage::

        census = buffer_census()                  # arms the baseline
        ... drive ingest/flush traffic ...
        census.sample(programs=("flush",))        # optional attribution
        census.settle()                           # early settled check
    """
    from veneur_tpu.lint.buffer_census import BufferCensus

    censuses = []

    def arm(name="test-device-buffers", tolerance_bytes=1 << 20):
        census = BufferCensus(name=name, tolerance_bytes=tolerance_bytes)
        census.arm()
        censuses.append(census)
        return census

    yield arm
    for census in censuses:
        if not any(s.settled for s in census.samples):
            census.settle(label="teardown")
        census.assert_clean()


def pytest_collection_modifyitems(config, items):
    if RUN_TPU:
        skip = pytest.mark.skip(
            reason="VENEUR_TPU_TESTS=1 runs only the tpu-marked subset")
        for item in items:
            if "tpu" not in item.keywords:
                item.add_marker(skip)
    else:
        skip = pytest.mark.skip(
            reason="hardware smoke test; run with VENEUR_TPU_TESTS=1 "
                   "on a real accelerator")
        for item in items:
            if "tpu" in item.keywords:
                item.add_marker(skip)
        if not RUN_MULTIDEVICE:
            skip_md = pytest.mark.skip(
                reason="fleet-scale multi-device lane; run with "
                       "VENEUR_MULTIDEVICE_TESTS=1 (tier-1 time stays "
                       "flat without it)")
            for item in items:
                if "multidevice" in item.keywords:
                    item.add_marker(skip_md)
