"""What the roofline readers of the sparse-attention kernels share: the
program's count of a kernel's calls by pass, the cell's shapes, and the
share itself from a step's least work and the events' device time."""

from benchmarks import core


def kernel_passes(kernel):
    """The program's count of its step's calls of ``kernel`` by pass, or
    None."""
    try:
        from deeperspeed_tpu import telemetry
    except ImportError:
        return None
    published = getattr(telemetry, "kernel_passes", None)
    return published().get(kernel) if published is not None else None


def shapes(record):
    """The cell's shapes as the kernels see them, or None of another
    model's cell."""
    cfg = record.get("model_config", {})
    if "sa_config" not in cfg:
        return None
    sa = cfg["sa_config"]
    return dict(batch=record["micro_batch"], seq=record["seq_len"],
                heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"], topk=sa["topk"],
                indexer_heads=sa["indexer_num_heads"],
                indexer_dim=sa["indexer_head_dim"],
                layers=int(cfg.get("layers_held", cfg["num_hidden_layers"])))


def share(record, trace, kernel, step_work):
    """``step_work(passes, shapes) -> {"flops", "bytes"}`` of one step's
    calls -> the percent of the roofline the traced events reached."""
    if trace is None:
        return None
    at = shapes(record)
    events = trace.scope_events(kernel)
    passes = kernel_passes(kernel)
    if at is None or not events or not passes or not sum(passes.values()):
        return None
    steps = len(events) / sum(passes.values())
    work = step_work(passes, at)
    peaks = core.device_peaks(record["device_kind"])
    pct, _bound = core.roofline_pct(
        steps * work["flops"], steps * work["bytes"],
        sum(d for _, d in events) / 1e9,
        peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
    return pct
