"""Seconds of set-up the program cannot be blamed for: the measuring
process's start to the window's opening LESS ``setup.import_s``,
``setup.initialize_s`` and ``setup.first_steps_s``.  What is left is Python's
and jax's start, ``jax.devices()``, the runner's imports, seeded weights and
batches, and its host copy of the first step's state for the check:
``benchmarks/layer_metrics/_setup_timeline.py``."""

from benchmarks.layer_metrics import _setup_timeline


def compute(record, trace):
    return _setup_timeline.outside_program_s(record)
