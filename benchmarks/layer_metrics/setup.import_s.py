"""Seconds of set-up inside ``dst:setup/import``, the whole of ``import
deeperspeed_tpu`` (``deeperspeed_tpu/__init__.py`` takes the clock on its
first line and keeps the interval on its last: the engine, the pipeline
module, the topology and every model module a runner imports after it is not
in it), in the measuring process from its start to the window's opening:
``benchmarks/layer_metrics/_setup_timeline.py``."""

from benchmarks.layer_metrics import _setup_timeline


def compute(record, trace):
    return _setup_timeline.span_s(record, _setup_timeline.IMPORT)
