"""Model FLOP/s utilisation of the whole training step of a model with latent
attention (MLA) over a sigmoid-scored mixture with shared experts and a
leading dense layer, at the median step time, at the shares this chip holds.
FLOPs a token by ``reference/moonlight_ref.flops_per_token``: 6 x the matmul
weights a token passes (the latent's six projections, the router, the shared
experts, the dense layer's MLP, a routed expert per slot -- the slots from
the program's own counter ``moe_slots_held``, the mean over the window's
steps, kept in the run's record by the runner -- and the head), plus the
attention's score (192 wide) and values (128 wide) over the causal half;
times tokens per step over the median step, over chips x the published bf16
peak.  Recomputed operations do not count.  The layers are checked against
what the program counted on the device, and a dropped slot refuses the
number: where the program has no such counters, or they say otherwise, there
is no number.  (The sixth near copy of this reader: PERF.md section 7 asks a
``benchmark`` issue to fold them.)"""

from benchmarks import core
from benchmarks.reference import moonlight_ref as ref


def flops_per_token(cfg, seq_len, tokens_per_step, counters):
    """-> FLOPs a token, or None where the counters disagree with the
    configuration or a slot was dropped."""
    kinds = ref.layer_kinds(cfg)
    if (counters.get("mla_layer_applications") != len(kinds)
            or counters.get("moe_layer_applications") != kinds.count(
                ref.SPARSE)
            or counters.get("moe_slots_dropped") != 0):
        return None
    return ref.flops_per_token(
        cfg, seq_len, counters["moe_slots_held"] / tokens_per_step)


def compute(record, trace):
    ready = record.get("step_ready_at")
    cfg = record.get("model_config", {})
    if not ready or len(ready) < 3 or "kv_lora_rank" not in cfg:
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
