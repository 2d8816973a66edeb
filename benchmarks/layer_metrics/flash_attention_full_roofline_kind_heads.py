"""Share of its roofline the flash-attention kernel reached in the FULL
(causal, unwindowed) calls of a training step of a model whose query heads
go by the layer's kind and of which a chip holds a share:
``flash_attention_full_roofline`` (the events named ``flash_attention``, the
passes by the program's compiled step, ``kernel_costs/flash_attention``) at
the heads the kernel is really called with here,
``full_attention_heads_held`` of the full layers' count in
``num_attention_heads_per_layer`` (and as many KV heads: GQA's copy).  A
model with one head count is the accepted readers' and gets no number
here."""

from benchmarks import core
from benchmarks.layer_metrics import _kind_heads

held = core.layer_metric_reader("flash_attention_roofline_held")


def compute(record, trace):
    cfg = record.get("model_config", {})
    if trace is None or not cfg.get("sliding_window"):
        return None
    heads = _kind_heads.heads_held(cfg, "full_attention")
    if heads is None:
        return None
    return _kind_heads.roofline_pct(
        record, trace, "flash_attention", held.kernel_passes(),
        lambda passes: held.step_work(
            passes, record["micro_batch"], heads, record["seq_len"],
            int(cfg["head_dim"])))
