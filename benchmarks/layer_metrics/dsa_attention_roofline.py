"""Share of its roofline the sparse-attention kernels reached in a training
step: the least time the chip could take for the kernel calls in the traced
slice over the summed device time of the events named ``dsa_attention`` (the
scope ``ops/attention/dsa.py`` runs its forward, dq and dk/dv kernels
under).  Operations and bytes from shapes by ``kernel_costs/dsa_attention``
-- the CHOSEN pairs only, ``sum_t min(t + 1, topk)`` a sequence, so the
share says what skipping unchosen pairs could still buy (a walk of the
whole triangle at full speed reads 23 % at 16k) and cannot read over 100 %.
How many of the events are forward, recomputed and backward calls is what
the program's compiled step says (``telemetry.kernel_passes()``), not a
fixed ratio; a backward pass is two kernel calls.  A program that has no
such kernel has no such events and no number."""

from benchmarks import core

_shared = core.layer_metric_reader("_dsa_roofline")


def step_work(passes, at):
    cost = core.load_kernel_cost("dsa_attention")
    args = (at["batch"], at["heads"], at["kv_heads"], at["seq"],
            at["head_dim"], at["topk"])
    f, b = cost.forward(*args), cost.backward(*args)
    n_fwd = passes["forward"] + passes["recomputed"]
    n_bwd = passes["backward"] / 2          # dq and dk/dv: one pass, two calls
    return {"flops": n_fwd * f["flops"] + n_bwd * b["flops"],
            "bytes": n_fwd * f["bytes"] + n_bwd * b["bytes"]}


def compute(record, trace):
    return _shared.share(record, trace, "dsa_attention", step_work)
