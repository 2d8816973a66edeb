"""Model FLOP/s utilisation of the whole training step of a model whose
attention runs in a compressed latent with convolutional mixing over a top-1
mixture behind an MLP router, at the median step time, at the shares this
chip holds.  FLOPs a token by ``reference/zaya_ref.flops_per_token``: 6 x the
matmul weights a token passes (the latent's five projections, the per-head
convolution's matrices, the router's four, a routed expert per slot -- the
slots from the program's own counter ``moe_slots_held``, the mean over the
window's steps, kept in the run's record by the runner -- and the tied table
once, as the head), plus the attention's scores and values in the latent over
the causal half; times tokens per step over the median step, over chips x
the published bf16 peak.  Recomputed operations do not count.  The layers are
checked against what the program counted on the device, and a dropped slot
refuses the number: where the program has no such counters, or they say
otherwise, there is no number."""

from benchmarks import core
from benchmarks.reference import zaya_ref as ref


def flops_per_token(cfg, seq_len, tokens_per_step, counters):
    """-> FLOPs a token, or None where the counters disagree with the
    configuration or a slot was dropped."""
    depth = ref.layers_held(cfg)
    if (counters.get("cca_layer_applications") != depth
            or counters.get("moe_layer_applications") != depth
            or counters.get("moe_slots_dropped") != 0):
        return None
    return ref.flops_per_token(
        cfg, seq_len, counters["moe_slots_held"] / tokens_per_step)


def compute(record, trace):
    ready = record.get("step_ready_at")
    cfg = record.get("model_config", {})
    if not ready or len(ready) < 3 or "cca_time0" not in cfg:
        return None
    counters = record.get("step_counters")
    if not counters:
        return None
    tokens_per_step = record["tokens"] / record["attempted"]
    per_token = flops_per_token(cfg, record["seq_len"], tokens_per_step,
                                counters)
    if per_token is None:
        return None
    peak = core.device_peaks(record["device_kind"])["bf16_flops_per_s"]
    step_s = core.median([b - a for a, b in zip(ready[:-1], ready[1:])])
    return core.mfu_pct(per_token, tokens_per_step / step_s, record["chips"],
                        peak)
