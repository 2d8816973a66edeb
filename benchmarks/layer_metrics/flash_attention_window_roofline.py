"""Share of its roofline the flash-attention kernel reached in its WINDOWED
calls of a training step: the least time the chip could take for those calls
in the traced slice over the summed device time of the events named
``flash_attention_window`` (the scope the program runs a windowed call's
kernels under; full calls are ``flash_attention`` events and are not read
here).  Operations and bytes from shapes by
``kernel_costs/flash_attention_window`` -- the band's pairs only -- at the
heads the kernel is called with (``num_attention_heads`` query heads and as
many KV heads: the program hands the kernel GQA's copy of k and v), the
configuration's ``head_dim`` and ``sliding_window``, the cell's batch and
sequence length.  How many of the events are forward, recomputed and
backward calls is what the program's compiled step says
(``telemetry.kernel_passes()``), not a fixed ratio; a backward pass that
takes two kernels is still one backward pass's work.  A program whose
kernel knows no window has no such events and no number."""

from benchmarks import core


def kernel_passes():
    """The program's count of its step's kernel calls by pass, or None."""
    try:
        from deeperspeed_tpu import telemetry
    except ImportError:
        return None
    published = getattr(telemetry, "kernel_passes", None)
    return (published().get("flash_attention_window")
            if published is not None else None)


def step_work(passes, batch, heads, seq, head_dim, window):
    """Operations and bytes of one step's windowed kernel calls: every
    forward call (first or recomputed) a forward's, each layer (a first
    forward call) one backward's, however many kernels that backward
    takes."""
    cost = core.load_kernel_cost("flash_attention_window")
    f = cost.forward(batch, heads, seq, head_dim, window)
    b = cost.backward(batch, heads, seq, head_dim, window)
    n_fwd = passes["forward"] + passes["recomputed"]
    n_bwd = passes["forward"] if passes["backward"] else 0
    return {"flops": n_fwd * f["flops"] + n_bwd * b["flops"],
            "bytes": n_fwd * f["bytes"] + n_bwd * b["bytes"]}


def compute(record, trace):
    cfg = record.get("model_config", {})
    if trace is None or not cfg.get("sliding_window"):
        return None
    events = trace.scope_events("flash_attention_window")
    passes = kernel_passes()
    if not events or not passes or not sum(passes.values()):
        return None
    steps = len(events) / sum(passes.values())
    work = step_work(passes, record["micro_batch"],
                     int(cfg["num_attention_heads"]), record["seq_len"],
                     int(cfg["head_dim"]), int(cfg["sliding_window"]))
    peaks = core.device_peaks(record["device_kind"])
    pct, _bound = core.roofline_pct(
        steps * work["flops"], steps * work["bytes"],
        sum(d for _, d in events) / 1e9,
        peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
    return pct
