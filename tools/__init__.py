"""Shared helpers for the repo's measurement/benchmark tools."""

import os


def force_cpu_mesh(n_devices=8):
    """Pin the host (CPU) platform with ``n_devices`` virtual XLA devices.

    Must run before jax initializes its backends (same settings as
    tests/conftest.py).
    """
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_devices}")
    os.environ["DST_ACCELERATOR"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
