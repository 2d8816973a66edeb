"""Seconds of set-up inside ``dst:setup/initialize``, ``dst.initialize``
whole (``runtime/initialize.py``: the configuration, the mesh, the model's
abstract init, the sharding plan, the masters, the moments and their
placement; the engine's stretches under it are PERF.md's, not metrics):
``benchmarks/layer_metrics/_setup_timeline.py``."""

from benchmarks.layer_metrics import _setup_timeline


def compute(record, trace):
    return _setup_timeline.span_s(record, _setup_timeline.INITIALIZE)
