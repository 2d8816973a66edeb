"""Model FLOP/s utilisation of the training step of a model with gated
window and full attention layers whose heads go by kind, a dense MLP among
sparse ones and a shared expert beside the routed ones, at the median step
time, at the shares this chip holds.  FLOPs a token by
``reference/laguna_ref.flops_per_token``: 6 x the matmul weights a token
passes (the gate, the dense MLP and the shared expert counted, a routed
expert per slot -- the slots from the program's own counter
``moe_slots_held``, the mean over the window's steps, kept in the run's
record by the runner) plus attention's scores and values by kind at the heads
held: ``12 heads D S`` a full layer and that times the band's share of the
triangle a windowed one; times tokens per step over the median step, over
chips x the published bf16 peak.  Recomputed operations do not count.
The layers of each kind are checked against what the program counted on the
device, and a dropped slot refuses the number: where the program has no such
counters, or they say otherwise, there is no number."""

from benchmarks import core
from benchmarks.reference import laguna_ref as ref

#: step counter -> (which of a layer's two kinds it counts, the kind)
COUNTED = {"window_layer_applications": (0, ref.SLIDING),
           "full_layer_applications": (0, ref.FULL),
           "dense_mlp_layer_applications": (1, ref.DENSE),
           "moe_layer_applications": (1, ref.SPARSE),
           "shared_expert_layer_applications": (1, ref.SPARSE)}


def layers_counted(cfg, counters):
    """Whether a set of step counters counted the held layers by kind."""
    kinds = ref.layer_kinds(cfg)
    return all(counters.get(name) == sum(1 for k in kinds if k[at] == value)
               for name, (at, value) in COUNTED.items())


def flops_per_token(cfg, seq_len, tokens_per_step, counters):
    """-> FLOPs a token, or None where the counters disagree with the
    configuration's layers or a slot was dropped."""
    if not layers_counted(cfg, counters):
        return None
    if counters.get("moe_slots_dropped") != 0:
        return None
    return ref.flops_per_token(
        cfg, seq_len, counters["moe_slots_held"] / tokens_per_step)


def compute(record, trace):
    ready = record.get("step_ready_at")
    cfg = record.get("model_config", {})
    if (not ready or len(ready) < 3
            or "num_attention_heads_per_layer" not in cfg):
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
