"""Share of the device's busy time in a traced slice of training that lies
under none of the program's scopes (``program_trace.SCOPES``): operations of
other programs between the steps, and what the compiler made without
metadata and no operand gives a scope to."""

from benchmarks import program_trace


def compute(record, trace):
    found = program_trace.for_reader(record, trace)
    return found and found.unattributed_pct()
