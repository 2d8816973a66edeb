"""Model FLOP/s utilisation of a hybrid model's training step at the median
step time, at the shares this chip holds.  FLOPs a token by
``reference/nemotron_h_ref.flops_per_token``: 6 x the matmul weights a token
passes, by layer kind, a routed expert counted per slot -- the slots from
the program's own counter ``moe_slots_held``, the mean over the window's
steps, kept in the run's record by the runner -- plus the scan's and
attention's terms; times
tokens per step over the median step, over chips x the published bf16 peak.
Recomputed operations do not count.
The layers of each kind are checked against what the program counted on the
device, and a dropped slot refuses the number: where the program has no such
counters, or they say otherwise, there is no number."""

from benchmarks import core
from benchmarks.reference import nemotron_h_ref as ref

COUNTED = (("ssm_layer_applications", "M"), ("moe_layer_applications", "E"),
           ("attention_layer_applications", "*"))


def flops_per_token(cfg, seq_len, tokens_per_step, counters):
    """-> FLOPs a token, or None where the counters disagree with the
    configuration's pattern or a slot was dropped."""
    layers = ref.pattern(cfg)
    if any(counters.get(name) != layers.count(kind)
           for name, kind in COUNTED):
        return None
    if counters.get("moe_slots_dropped") != 0:
        return None
    return ref.flops_per_token(
        cfg, seq_len, counters["moe_slots_held"] / tokens_per_step)


def compute(record, trace):
    ready = record.get("step_ready_at")
    cfg = record.get("model_config", {})
    if not ready or len(ready) < 3 or "hybrid_override_pattern" not in cfg:
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
