"""Wall seconds of the train steps run before the window opened, from the
program's step timeline: the first ``train_batch``, whose ``train/dispatch``
traces, lowers and loads (or compiles) the step program, and the warm-up
step: ``benchmarks/layer_metrics/_setup_timeline.py``."""

from benchmarks.layer_metrics import _setup_timeline


def compute(record, trace):
    return _setup_timeline.first_steps_s(record)
