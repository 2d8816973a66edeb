"""Share of its roofline the grouped-matmul kernels reached in a training
step of a model whose expert layers walk their sorted slots through them
(``ops/pallas_gmm.py``, the events named ``grouped_matmul``): the least time
the chip could take for the slots each traced step was really sent over the
summed device time of those events in the same steps.  The routed load is a
step's own and drifts within a window, so the slots are not the window's
mean: each ``dst:train/step`` annotation of the slice carries its
``step_num``, and the program's step timeline keeps that very step's
counters (``telemetry.step_timeline(read=True, steps=...)``:
``moe_slots_held``, a layer's mean, times ``moe_layer_applications``).
Operations and bytes by ``kernel_costs/grouped_matmul`` at the
configuration's ``hidden_size`` and ``moe_intermediate_size``, gate and up
side by side, the experts held.  It counts chosen slots, not the rows the
kernels multiplied, so padding is a loss.  No number unless every step of the
slice has its record, none dropped a slot, and the compiled step holds the
kernel calls the count stands on (``telemetry.kernel_passes()``: two forward
and six backward a layer, the recomputed layer's among the backward's)."""

from benchmarks import core
from benchmarks.layer_metrics import _step_timeline

KERNEL = "grouped_matmul"


def kernel_passes():
    """The program's count of its step's kernel calls by pass, or None."""
    try:
        from deeperspeed_tpu import telemetry
    except ImportError:
        return None
    published = getattr(telemetry, "kernel_passes", None)
    return published().get(KERNEL) if published is not None else None


def slice_work(cfg, records, passes):
    """Operations and bytes of the slice's steps, each at its own slots, or
    None where a step's counters do not bear the count."""
    cost = core.load_kernel_cost(KERNEL)
    flops = moved = 0.0
    for r in records:
        told = r["counters"]
        layers = told.get("moe_layer_applications")
        if not layers or told.get("moe_slots_dropped") != 0:
            return None
        if passes != {"forward": 2 * layers, "recomputed": 0,
                      "backward": 6 * layers}:
            return None
        layer = cost.train_step(
            told["moe_slots_held"], int(cfg["routed_experts_held"]),
            int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"]))
        flops += layers * layer["flops"]
        moved += layers * layer["bytes"]
    return {"flops": flops, "bytes": moved}


def compute(record, trace):
    cfg = record.get("model_config", {})
    if trace is None or "moe_intermediate_size" not in cfg:
        return None
    passes = kernel_passes()
    if not passes or _step_timeline.program_timeline(steps=()) is None:
        return None
    rows = _step_timeline.slice_rows()
    if rows is None:
        return None
    took = _step_timeline.kernel_ns_by_step(rows, KERNEL)
    if not took or not all(took.values()):
        return None
    records = _step_timeline.program_timeline(read=True, steps=list(took))
    if records is None or sorted(r["step"] for r in records) != sorted(took):
        return None
    work = slice_work(cfg, records, passes)
    if work is None:
        return None
    peaks = core.device_peaks(record["device_kind"])
    pct, _bound = core.roofline_pct(
        work["flops"], work["bytes"], sum(took.values()) / 1e9,
        peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
    return pct
