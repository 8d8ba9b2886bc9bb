"""Virtual CPU devices for tests and dry runs.

The distributed-without-a-cluster pattern: a process that wants an
n-device mesh without n chips asks the CPU backend for n virtual
devices. One implementation for conftest, the distributed test workers,
and the driver entry points.
"""

from __future__ import annotations


def set_cpu_device_count(n: int, platform: str = "cpu") -> None:
    """Give this process ``n`` virtual CPU devices. Must run before the
    first backend use — jax raises RuntimeError afterwards."""
    import jax
    if platform:
        jax.config.update("jax_platforms", platform)
    jax.config.update("jax_num_cpu_devices", n)
