"""Share of its roofline the flash-attention kernel reached in a training
step of a model whose attention runs in a compressed latent (CCA): the least
time the chip could take for the kernel calls in the traced slice over the
summed device time of the events named ``flash_attention``.  Operations and
bytes from shapes by ``kernel_costs/flash_attention`` (the accepted
functions, reused through ``flash_attention_roofline_held``'s ``step_work``)
at the heads the kernel is called with here -- ``num_attention_heads`` query
heads of the configuration's ``head_dim`` (the latent's, 8 x 128 = half the
stream's width; the accepted function counts as many KV heads, and the 2 the
kernel reads by group are fewer bytes: the call is bound by its operations
at this length either way) -- the cell's batch and sequence length; the
events classed forward / recomputed / backward by the program's compiled step
(``telemetry.kernel_passes()``).  ``flash_attention_roofline`` takes the head
for ``hidden_size / num_attention_heads``, which is not this model's, and
the ``_held`` and ``_full`` readers ask for keys this configuration has not:
a model without ``cca_time0`` gets no number here."""

from benchmarks import core
from benchmarks.layer_metrics import _kind_heads

held = core.layer_metric_reader("flash_attention_roofline_held")


def compute(record, trace):
    cfg = record.get("model_config", {})
    if trace is None or "cca_time0" not in cfg:
        return None
    return _kind_heads.roofline_pct(
        record, trace, "flash_attention", held.kernel_passes(),
        lambda passes: held.step_work(
            passes, record["micro_batch"], int(cfg["num_attention_heads"]),
            record["seq_len"], int(cfg["head_dim"])))
