"""Share of the device's busy time in a traced slice of training that lies
under none of the program's scopes, a hybrid model's (``ssm``, ``moe_*``)
and the compiler's grouped-matmul kernels counted as attributed:
``train.scope_unattributed_pct`` for a cell whose model has scopes that
``program_trace.SCOPES`` does not know."""

from benchmarks import hybrid_trace


def compute(record, trace):
    return hybrid_trace.unattributed_pct(record, trace)
