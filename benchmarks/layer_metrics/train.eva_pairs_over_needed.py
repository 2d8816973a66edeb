"""The (row, key) pairs the program's EVA attention calls compute in a step
over the pairs the equations need (``eva_pairs_visited`` over
``eva_pairs_needed``: the program's own counters, ``telemetry.step_counters()``,
the mean over the window's steps, kept in the run's record by the runner).
1 is a walk that computes nothing outside the mask; the kernel pair reads
1.17 at 16k bytes (the halves of the tiles the diagonal crosses; the
summaries are visited by whole windows and none is masked)."""


def compute(record, trace):
    if trace is None or "losses" not in record:
        return None
    counters = record.get("step_counters") or {}
    needed = counters.get("eva_pairs_needed")
    if not needed or "eva_pairs_visited" not in counters:
        return None
    return counters["eva_pairs_visited"] / needed
