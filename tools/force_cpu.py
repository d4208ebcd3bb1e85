"""Pin a process to JAX's CPU backend (shared by tests/conftest.py and the
CPU-side tools): ``JAX_PLATFORMS=cpu`` plus, optionally, a virtual device
count via ``--xla_force_host_platform_device_count``.  Nothing else.
"""

from __future__ import annotations

import os


def force_cpu_backend(virtual_devices: int | None = None) -> None:
    """Pin this process to the CPU backend.

    ``virtual_devices`` adds ``--xla_force_host_platform_device_count`` when
    the flag is not already present (the virtual mesh the test tiers use).
    Must run before JAX initializes a backend; importing jax alone does not.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    if virtual_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={virtual_devices}")
    import jax

    jax.config.update("jax_platforms", "cpu")
