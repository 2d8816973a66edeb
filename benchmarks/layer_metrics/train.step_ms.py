"""Median time of one training step: the gaps between the instants at which
consecutive steps' losses became ready (``block_until_ready``), one step in
flight.  Harness clock; steadier than the window's rate, which it explains."""

from benchmarks import core


def compute(record, trace):
    ready = record.get("step_ready_at")
    if not ready or len(ready) < 3:
        return None
    gaps = [b - a for a, b in zip(ready[:-1], ready[1:])]
    return 1e3 * core.median(gaps)
