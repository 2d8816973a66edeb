"""Accelerator selection (equivalent of reference ``accelerator/real_accelerator.py:52``).

Selection order: explicit ``set_accelerator`` > ``DST_ACCELERATOR`` env >
auto-detect from ``jax.default_backend()``.
"""

import os

import jax

_accelerator = None


def get_accelerator():
    global _accelerator
    if _accelerator is not None:
        return _accelerator

    from .tpu_accelerator import CpuAccelerator, TpuAccelerator

    name = os.environ.get("DST_ACCELERATOR")
    if name is None:
        # ask JAX once; a backend that fails to initialize is an error to
        # see, not a reason to train on the host
        name = "cpu" if jax.default_backend() == "cpu" else "tpu"

    if name == "cpu":
        _accelerator = CpuAccelerator()
    elif name == "tpu":
        _accelerator = TpuAccelerator()
    else:
        raise ValueError(f"Unknown accelerator name: {name!r} (expected 'tpu' or 'cpu')")
    return _accelerator


def set_accelerator(accel):
    global _accelerator
    _accelerator = accel


def is_current_accelerator_supported():
    return get_accelerator().is_available()
