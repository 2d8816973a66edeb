"""Model FLOP/s utilisation of the whole training step of a byte-level model
with EVA attention, at the median step time, at the share of the heads this
chip holds.  FLOPs a token by ``reference/evabyte_ref.flops_per_token``: 6 x
the matmul weights a token passes (four layers' projections and SwiGLU, the
head's eight slices; the input embedding is a gather) plus EVA's scores and
values at ``12 D`` a (row, key) pair the equations need -- a row's window up
to itself and the summaries of every earlier window, not the square -- plus
the summaries' pooling; times tokens per step over the median step, over
chips x the published bf16 peak.  Recomputed operations do not count.  The
layers are checked against what the program counted on the device
(``layer_applications``, kept in the run's record by the runner): where the
program has no such counter, or it says otherwise, there is no number."""

from benchmarks import core
from benchmarks.reference import evabyte_ref as ref


def compute(record, trace):
    ready = record.get("step_ready_at")
    cfg = record.get("model_config", {})
    if not ready or len(ready) < 3 or cfg.get("attention_class") != "eva":
        return None
    counters = record.get("step_counters")
    if not counters or counters.get("layer_applications") != ref.depth(cfg):
        return None
    peak = core.device_peaks(record["device_kind"])["bf16_flops_per_s"]
    step_s = core.median([b - a for a, b in zip(ready[:-1], ready[1:])])
    tokens_per_s = record["tokens"] / record["attempted"] / step_s
    return core.mfu_pct(ref.flops_per_token(cfg, record["seq_len"]),
                        tokens_per_s, record["chips"], peak)
