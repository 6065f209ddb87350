"""Shared environment pinning for the CPU-mesh evidence scripts.

One place for the virtual-8-device CPU setup: ``jax.config.update`` after
import wins over whatever platform the environment names, and keeps the run
off the chip. XLA_FLAGS is read at backend init, so setting it before the
first device use suffices.
"""

import os


def force_cpu_mesh(n: int = 8) -> None:
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
