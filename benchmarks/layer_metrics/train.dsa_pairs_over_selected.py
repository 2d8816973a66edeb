"""The (row, key) pairs a pass of the program's sparse-attention kernels
computes for a head over the pairs its rows chose (``dsa_pairs_visited`` over
``dsa_pairs_selected``: the program's own counters,
``telemetry.step_counters()``, the mean over the window's steps, kept in the
run's record by the runner).  The kernels walk the causal tiles under the
packed selection and skip a tile only where no row chose anything, so while
nothing is skipped this reads the causal tiles over the chosen pairs: 4.4 at
16k rows of which each keeps 2048 (528 tiles of 512 x 512 over 31.5M pairs;
4.3 by the triangle itself).  1 is a walk that computes nothing unchosen."""


def compute(record, trace):
    if trace is None or "losses" not in record:
        return None
    counters = record.get("step_counters") or {}
    selected = counters.get("dsa_pairs_selected")
    if not selected or "dsa_pairs_visited" not in counters:
        return None
    return counters["dsa_pairs_visited"] / selected
