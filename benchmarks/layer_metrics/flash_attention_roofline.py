"""Share of its roofline the flash-attention kernel reached in a training
step: the least time the chip could take for the kernel calls in the traced
slice (operations and bytes from shapes, ``kernel_costs/flash_attention``)
over the summed device time of the events named ``flash_attention``.  Per
layer and step the kernel runs a forward, a second forward when the layer is
recomputed, and one fused backward; the events are split in that ratio."""

from benchmarks import core


def compute(record, trace):
    if trace is None or "remat" not in record:
        return None
    events = trace.scope_events("flash_attention")
    if not events:
        return None
    cfg = record["model_config"]
    heads = cfg["num_attention_heads"]
    cost = core.load_kernel_cost("flash_attention")
    args = (record["micro_batch"], heads, record["seq_len"],
            cfg["hidden_size"] // heads)
    calls = 3 if record["remat"] else 2       # kernel calls per layer and step
    work = cost.train_step(len(events) / calls, *args, record["remat"])
    peaks = core.device_peaks(record["device_kind"])
    pct, _bound = core.roofline_pct(
        work["flops"], work["bytes"], sum(d for _, d in events) / 1e9,
        peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
    return pct
