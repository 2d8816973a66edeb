"""Share of its roofline the flash-attention kernel reached in the FULL
(causal, unwindowed) calls of a training step of a model that has windowed
layers too: the least time the chip could take for those calls in the traced
slice over the summed device time of the events named ``flash_attention``
(a windowed call's kernels run under ``flash_attention_window`` and are read
by ``flash_attention_window_roofline``).  Operations and bytes from shapes by
``kernel_costs/flash_attention`` (the accepted functions, reused) at the
heads the kernel is called with (``num_attention_heads`` query heads and as
many KV heads: the program hands the kernel GQA's copy of k and v), the
configuration's ``head_dim``, the cell's batch and sequence length; the
events classed forward / recomputed / backward by the program's compiled
step (``telemetry.kernel_passes()``), as ``flash_attention_roofline_held``
classes them.  A model without windowed layers is the accepted readers'
and gets no number here."""

from benchmarks import core

held = core.layer_metric_reader("flash_attention_roofline_held")


def compute(record, trace):
    cfg = record.get("model_config", {})
    if trace is None or not cfg.get("sliding_window"):
        return None
    events = trace.scope_events("flash_attention")
    passes = held.kernel_passes()
    if not events or not passes or not sum(passes.values()):
        return None
    steps = len(events) / sum(passes.values())
    work = held.step_work(passes, record["micro_batch"],
                          int(cfg["num_attention_heads"]), record["seq_len"],
                          int(cfg["head_dim"]))
    peaks = core.device_peaks(record["device_kind"])
    pct, _bound = core.roofline_pct(
        steps * work["flops"], steps * work["bytes"],
        sum(d for _, d in events) / 1e9,
        peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
    return pct
