import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import pytest  # noqa: E402

FOUR_DEVICES = "--xla_force_host_platform_device_count=4"


@pytest.fixture
def four_virtual_devices(monkeypatch):
    """The environment of a child that rehearses a four-chip cell on the
    CPU: four virtual devices. The cores are left as they are: XLA's CPU
    client has one thread a core, a collective holds one a device until
    all have arrived, and held to four cores the import's program and
    the flush's starved each other into the rendezvous' 40 s abort."""
    monkeypatch.setenv("XLA_FLAGS", FOUR_DEVICES)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
