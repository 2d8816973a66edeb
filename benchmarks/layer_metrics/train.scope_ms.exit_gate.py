"""Device time of one training step under the scope ``exit_gate``, a part of
``train.scope_ms.head_ce``: a looped model's gate after every pass, the exit
distribution, its entropy and the weighted sum of the exits' cross
entropies, forward and backward.  ``program_trace.SCOPES`` does not know the
scope, so this reader walks the published scopes itself, the way
``ProgramTrace`` does for its own."""

from benchmarks import program_trace

SCOPE = "exit_gate"


def under_scope(op_name):
    """Whether an instruction's ``op_name`` path has the scope on it, whole
    or inside the wrappers differentiation puts around a component."""
    for part in op_name.split("/"):
        while (inner := program_trace._WRAPPED.match(part)) is not None:
            part = inner.group(1)
        if part == SCOPE:
            return True
    return False


def compute(record, trace):
    found = program_trace.for_reader(record, trace)
    if not found or not found.steps or not found.by_scope:
        return None
    ns = sum(self_ns for op, self_ns in program_trace.self_times(found.ops)
             if under_scope(found.scopes.get(op[3], {}).get(op[0], "")))
    return ns / found.steps / 1e6
