"""What the readers of a hybrid (state-space + mixture-of-experts) cell
share.  ``program_trace.SCOPES`` knows neither ``ssm`` nor the scopes inside
an expert layer and cannot be told here, so these readers walk the published
scopes themselves, the way ``train.scope_ms.exit_gate`` does.
"""

from benchmarks import program_trace

#: the scopes this cell's model adds to the program's
HYBRID_SCOPES = frozenset(("ssm", "ssm_scan", "moe_route", "moe_experts",
                           "moe_shared"))


def scopes_on(op_name):
    """Every component of an instruction's ``op_name`` path, whole or inside
    the wrappers differentiation puts around one -> set of names."""
    found = set()
    for part in op_name.split("/"):
        while (inner := program_trace._WRAPPED.match(part)) is not None:
            part = inner.group(1)
        found.add(part)
    return found


def op_name(found, op):
    """The ``op_name`` the program published for a traced operation ``op``
    ([instruction, start, dur, program]); "" where it published none."""
    return found.scopes.get(op[3], {}).get(op[0], "")


def under(found, op, scope):
    """Whether a traced operation ran under ``scope``."""
    return scope in scopes_on(op_name(found, op))


def scope_ms_per_step(record, trace, scope):
    """Device ms a step under ``scope``; None outside a traced training run
    of a program that publishes its scopes."""
    found = program_trace.for_reader(record, trace)
    if not found or not found.steps or not found.by_scope:
        return None
    ns = sum(self_ns for op, self_ns in program_trace.self_times(found.ops)
             if under(found, op, scope))
    return ns / found.steps / 1e6


def unattributed_pct(record, trace):
    """Share of the device's busy time under none of the program's scopes,
    the hybrid model's among them."""
    found = program_trace.for_reader(record, trace)
    if not found or not found.busy_ns or not found.by_scope:
        return None
    known = program_trace.SCOPES | HYBRID_SCOPES
    lost = sum(self_ns for op, self_ns in program_trace.self_times(found.ops)
               if not known & scopes_on(op_name(found, op)))
    return 100.0 * lost / found.busy_ns
