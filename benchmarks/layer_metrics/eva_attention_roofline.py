"""Share of its roofline the EVA attention kernel pair reached in a training
step: the least time the chip could take for the kernel calls in the traced
slice over the summed device time of the events named ``eva_attention`` (the
scope ``ops/attention/pallas_eva.py`` runs its forward and backward kernels
under).  Operations and bytes from shapes by ``kernel_costs/eva_attention``
-- the pairs inside the mask only: a row's window up to itself and the
summaries of every earlier window -- at the heads the kernel is called with
(``attention_heads_held``), the head size ``hidden_size /
num_attention_heads``, the configuration's ``window_size`` and
``chunk_size``, the cell's batch and sequence length.  How many of the events
are forward, recomputed and backward calls is what the program's compiled
step says (``telemetry.kernel_passes()``), not a fixed ratio.  A program
that has no such kernel has no such events and no number."""

from benchmarks import core


def kernel_passes():
    """The program's count of its step's kernel calls by pass, or None."""
    try:
        from deeperspeed_tpu import telemetry
    except ImportError:
        return None
    published = getattr(telemetry, "kernel_passes", None)
    return (published().get("eva_attention")
            if published is not None else None)


def step_work(passes, batch, heads, seq, head_dim, window, chunk):
    """Operations and bytes of one step's kernel calls: every forward call
    (first or recomputed) a forward's, every backward call a backward's."""
    cost = core.load_kernel_cost("eva_attention")
    f = cost.forward(batch, heads, seq, head_dim, window, chunk)
    b = cost.backward(batch, heads, seq, head_dim, window, chunk)
    n_fwd = passes["forward"] + passes["recomputed"]
    return {"flops": n_fwd * f["flops"] + passes["backward"] * b["flops"],
            "bytes": n_fwd * f["bytes"] + passes["backward"] * b["bytes"]}


def compute(record, trace):
    cfg = record.get("model_config", {})
    if trace is None or cfg.get("attention_class") != "eva":
        return None
    events = trace.scope_events("eva_attention")
    passes = kernel_passes()
    if not events or not passes or not sum(passes.values()):
        return None
    steps = len(events) / sum(passes.values())
    heads = int(cfg.get("attention_heads_held", cfg["num_attention_heads"]))
    work = step_work(passes, record["micro_batch"], heads, record["seq_len"],
                     cfg["hidden_size"] // cfg["num_attention_heads"],
                     int(cfg["window_size"]), int(cfg["chunk_size"]))
    peaks = core.device_peaks(record["device_kind"])
    pct, _bound = core.roofline_pct(
        steps * work["flops"], steps * work["bytes"],
        sum(d for _, d in events) / 1e9,
        peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
    return pct
