"""Share of its roofline the selection's kernel reached in a training step
(``dsa_select``: the indexer's scores of a block of rows into VMEM and each
row's ``topk`` best of them by bisection): the least time the chip could
take for the SCORES alone over the summed device time of the events of that
name.  Operations and bytes from shapes by ``kernel_costs/dsa_select`` --
the causal pairs' products; the counting passes of the selection are not
matmul work -- a call a layer, every call from the program's compiled step
(``telemetry.kernel_passes()``: a recomputed layer keeps the selection and
calls nothing).  A program that has no such kernel has no such events and
no number."""

from benchmarks import core

_shared = core.layer_metric_reader("_dsa_roofline")


def step_work(passes, at):
    call = core.load_kernel_cost("dsa_select").forward(
        at["batch"], at["indexer_heads"], at["seq"], at["indexer_dim"])
    return {k: sum(passes.values()) * v for k, v in call.items()}


def compute(record, trace):
    return _shared.share(record, trace, "dsa_select", step_work)
