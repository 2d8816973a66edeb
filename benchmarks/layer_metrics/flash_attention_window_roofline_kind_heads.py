"""Share of its roofline the flash-attention kernel reached in its WINDOWED
calls of a training step of a model whose query heads go by the layer's kind
and of which a chip holds a share: ``flash_attention_window_roofline`` (its
events, its passes by the program's compiled step, the band's pairs by
``kernel_costs/flash_attention_window``) at the heads the kernel is really
called with here, ``sliding_attention_heads_held`` of the sliding layers'
count in ``num_attention_heads_per_layer`` (and as many KV heads: GQA's
copy).  A model with one head count is the accepted reader's and gets no
number here."""

from benchmarks import core
from benchmarks.layer_metrics import _kind_heads

window = core.layer_metric_reader("flash_attention_window_roofline")


def compute(record, trace):
    cfg = record.get("model_config", {})
    if trace is None or not cfg.get("sliding_window"):
        return None
    heads = _kind_heads.heads_held(cfg, "sliding_attention")
    if heads is None:
        return None
    return _kind_heads.roofline_pct(
        record, trace, "flash_attention_window", window.kernel_passes(),
        lambda passes: window.step_work(
            passes, record["micro_batch"], heads, record["seq_len"],
            int(cfg["head_dim"]), int(cfg["sliding_window"])))
