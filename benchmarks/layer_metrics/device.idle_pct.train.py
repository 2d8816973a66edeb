"""Share of the traced slice of a training window in which no operation ran
on the device: 1 - union of device-op intervals / traced window."""


def compute(record, trace):
    if trace is None or "losses" not in record:
        return None
    return trace.idle_pct
