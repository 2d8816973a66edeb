"""Model FLOP/s utilisation at the median step time: (6 N_no_embed +
12 L H S) x tokens per step / step seconds, over chips x the published bf16
peak.  Recomputed operations do not count; N comes from the configuration's
shapes, not from the program.  Taken at the median step and not over the
window, because in a traced run stopping the profiler stalls the window."""

from benchmarks import core
from benchmarks.reference import gpt_neox_ref as ref


def compute(record, trace):
    ready = record.get("step_ready_at")
    if not ready or len(ready) < 3 or "model_config" not in record:
        return None
    cfg = record["model_config"]
    per_token = core.model_flops_per_token(
        ref.num_params(cfg, with_input_embedding=False),
        cfg["num_hidden_layers"], cfg["hidden_size"], record["seq_len"])
    peak = core.device_peaks(record["device_kind"])["bf16_flops_per_s"]
    step_s = core.median([b - a for a, b in zip(ready[:-1], ready[1:])])
    tokens_per_s = record["tokens"] / record["attempted"] / step_s
    return core.mfu_pct(per_token, tokens_per_s, record["chips"], peak)
