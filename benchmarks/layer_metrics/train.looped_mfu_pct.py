"""Model FLOP/s utilisation of a looped model's training step at the median
step time.  With weight reuse parameters and work part ways, so the count is
per use and not ``6 N``: ``6 (T L P_layer + T P_head + (T - 1) H) + 12 T L H
S`` FLOPs a token (``reference/ouro_ref.flops_per_token``, from the
configuration's shapes: T passes of L blocks, T heads, the gate), times
tokens per step over the median step, over chips x the published bf16 peak.
Recomputed operations do not count.  T x L and T are checked against what
the program counted on the device in its last step
(``telemetry.step_counters()``: layer and head applications): where the
program has no such counters, or they say otherwise, there is no number."""

from benchmarks import core
from benchmarks.reference import ouro_ref as ref


def program_counters():
    """The program's own counters of its last train step, or None."""
    try:
        from deeperspeed_tpu import telemetry
    except ImportError:
        return None
    published = getattr(telemetry, "step_counters", None)
    return published().get("train_step") if published is not None else None


def compute(record, trace):
    ready = record.get("step_ready_at")
    cfg = record.get("model_config", {})
    if not ready or len(ready) < 3 or "total_ut_steps" not in cfg:
        return None
    counters = program_counters()
    if not counters:
        return None
    passes, layers = ref.passes(cfg), ref.depth(cfg)
    if (counters.get("layer_applications") != passes * layers
            or counters.get("head_applications") != passes):
        return None
    peak = core.device_peaks(record["device_kind"])["bf16_flops_per_s"]
    step_s = core.median([b - a for a, b in zip(ready[:-1], ready[1:])])
    tokens_per_s = record["tokens"] / record["attempted"] / step_s
    return core.mfu_pct(ref.flops_per_token(cfg, record["seq_len"]),
                        tokens_per_s, record["chips"], peak)
