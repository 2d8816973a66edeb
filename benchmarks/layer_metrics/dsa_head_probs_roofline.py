"""Share of its roofline the kernel of the indexer's loss reached in a
training step (``dsa_head_probs``: the main attention's head-averaged
probabilities over each row's chosen keys, a chunk of rows a call): the
least time the chip could take over the summed device time of the events of
that name.  Operations and bytes from shapes by
``kernel_costs/dsa_head_probs`` -- the CHOSEN pairs only -- a layer's calls
together, the layers from the configuration and every call from the
program's compiled step (``telemetry.kernel_passes()``: a call in the scan
over the chunks of rows counts as often as the scan runs).  A program that
has no such kernel has no such events and no number."""

from benchmarks import core

_shared = core.layer_metric_reader("_dsa_roofline")


def step_work(passes, at):
    layer = core.load_kernel_cost("dsa_head_probs").layer(
        at["batch"], at["heads"], at["kv_heads"], at["seq"], at["head_dim"],
        at["topk"])
    return {k: at["layers"] * v for k, v in layer.items()}


def compute(record, trace):
    return _shared.share(record, trace, "dsa_head_probs", step_work)
