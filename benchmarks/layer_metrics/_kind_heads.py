"""What the two roofline readers of a model whose query heads go by the
layer's kind share (Laguna: 48 on a full layer, 72 on a sliding one, of which
a chip holds a share).  The accepted readers
(``flash_attention_window_roofline``, ``flash_attention_full_roofline``)
take ``num_attention_heads`` for every layer, which is wrong twice there: the
count is the kind's, and it is the share held.  Everything else is theirs:
the kernel's events by scope, the passes the program's compiled step counted,
``kernel_costs/`` as they are.
"""

from benchmarks import core
from benchmarks.reference import laguna_ref as ref


def heads_held(cfg, kind):
    """Query heads a layer of ``kind`` holds here -- and as many KV heads,
    because the program hands the kernel GQA's copy of k and v -- or None
    for a model whose heads do not go by kind, or that has no such layer."""
    if "num_attention_heads_per_layer" not in cfg:
        return None
    if kind not in [a for a, _ in ref.layer_kinds(cfg)]:
        return None
    return ref.share(cfg)["heads"][kind]


def roofline_pct(record, trace, scope, passes, step_work):
    """Share of its roofline the kernel reached in the events named
    ``scope`` of the traced slice; ``step_work`` gives one step's operations
    and bytes for ``passes``."""
    events = trace.scope_events(scope)
    if not events or not passes or not sum(passes.values()):
        return None
    steps = len(events) / sum(passes.values())
    work = step_work(passes)
    peaks = core.device_peaks(record["device_kind"])
    pct, _bound = core.roofline_pct(
        steps * work["flops"], steps * work["bytes"],
        sum(d for _, d in events) / 1e9,
        peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
    return pct
