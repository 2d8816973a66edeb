"""The fullest held expert over the mean held expert, the largest over the
expert layers, the mean over the window's steps: the program's own counter
(``telemetry.step_counters()``, every step's kept in the run's record by the
runner).
1 is even routing; the first expert layer reads raw embeddings of Zipf ids,
so a hot id sends one expert hundreds of slots."""


def compute(record, trace):
    if trace is None or "losses" not in record:
        return None
    counters = record.get("step_counters")
    return counters.get("moe_load_max_over_mean") if counters else None
