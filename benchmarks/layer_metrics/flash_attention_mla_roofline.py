"""Share of its roofline the latent-attention flash kernel reached in a
training step: the least time the chip could take for the kernel calls in the
traced slice over the summed device time of the events named
``flash_attention_mla``.  Operations and bytes from shapes by
``kernel_costs/flash_attention_mla`` (a score over ``qk_nope_head_dim +
qk_rope_head_dim``, a value of ``v_head_dim``, the rotary key and its
gradient at ONE head: the same work whatever implements it) at the
configuration's ``num_attention_heads``, the cell's batch and sequence
length; the events classed forward / recomputed / backward by the program's
compiled step (``telemetry.kernel_passes()``), not a fixed ratio.  A
configuration without ``kv_lora_rank``, or a program that has no such kernel
(no events of the name, no passes), gets no number."""

from benchmarks import core
from benchmarks.layer_metrics import _kind_heads

KERNEL = "flash_attention_mla"


def kernel_passes():
    """The program's count of its step's kernel calls by pass, or None."""
    try:
        from deeperspeed_tpu import telemetry
    except ImportError:
        return None
    published = getattr(telemetry, "kernel_passes", None)
    return published().get(KERNEL) if published is not None else None


def compute(record, trace):
    cfg = record.get("model_config", {})
    if trace is None or "kv_lora_rank" not in cfg:
        return None
    cost = core.load_kernel_cost(KERNEL)
    return _kind_heads.roofline_pct(
        record, trace, KERNEL, kernel_passes(),
        lambda passes: cost.step_work(
            passes, B=record["micro_batch"], S=record["seq_len"],
            N=int(cfg["num_attention_heads"]),
            d_nope=int(cfg["qk_nope_head_dim"]),
            d_rope=int(cfg["qk_rope_head_dim"]),
            d_v=int(cfg["v_head_dim"])))
