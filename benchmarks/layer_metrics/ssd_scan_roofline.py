"""Share of its roofline the SSD-scan kernel pair reached in a training step
of a model that holds a share of its Mamba heads: the least time the chip
could take for the kernel calls in the traced slice over the summed device
time of the events named ``ssd_scan`` (the scope right above the program's
``pallas_call``s, inside ``ssm_scan``).  Operations and bytes from shapes by
``kernel_costs/ssd_scan`` at the heads and groups the kernel is really called
with here (``mamba_heads_held``, ``mamba_groups_held``), the configuration's
``mamba_head_dim``, ``ssm_state_size`` and ``chunk_size``, the cell's batch
and sequence length.  How many of the events are forward, recomputed and
backward calls is what the program's compiled step says
(``telemetry.kernel_passes()``), not a fixed ratio.  A program with no such
kernel (the plain ``jnp`` scan) has no such events and no number."""

from benchmarks import core


def kernel_passes():
    """The program's count of its step's kernel calls by pass, or None."""
    try:
        from deeperspeed_tpu import telemetry
    except ImportError:
        return None
    published = getattr(telemetry, "kernel_passes", None)
    return published().get("ssd_scan") if published is not None else None


def step_work(passes, batch, seq, heads, groups, head_dim, state, chunk):
    """Operations and bytes of one step's kernel calls: every forward call
    (first or recomputed) a forward's, every backward call a backward's."""
    cost = core.load_kernel_cost("ssd_scan")
    shapes = (batch, seq, heads, groups, head_dim, state, chunk)
    f, b = cost.forward(*shapes), cost.backward(*shapes)
    n_fwd = passes["forward"] + passes["recomputed"]
    return {"flops": n_fwd * f["flops"] + passes["backward"] * b["flops"],
            "bytes": n_fwd * f["bytes"] + passes["backward"] * b["bytes"]}


def compute(record, trace):
    cfg = record.get("model_config", {})
    if trace is None or "mamba_heads_held" not in cfg:
        return None
    events = trace.scope_events("ssd_scan")
    passes = kernel_passes()
    if not events or not passes or not sum(passes.values()):
        return None
    steps = len(events) / sum(passes.values())
    work = step_work(passes, record["micro_batch"], record["seq_len"],
                     int(cfg["mamba_heads_held"]),
                     int(cfg["mamba_groups_held"]),
                     int(cfg["mamba_head_dim"]), int(cfg["ssm_state_size"]),
                     int(cfg["chunk_size"]))
    peaks = core.device_peaks(record["device_kind"])
    pct, _bound = core.roofline_pct(
        steps * work["flops"], steps * work["bytes"],
        sum(d for _, d in events) / 1e9,
        peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
    return pct
