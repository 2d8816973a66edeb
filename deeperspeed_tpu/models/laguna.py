"""Laguna: Mellum's sibling (poolside, ``model_type`` ``laguna``; the preset
is Laguna-S-2.1) -- the same ``config.json`` schema (``layer_types``,
``mlp_layer_types``, ``rope_parameters`` by kind) and the same pre-norm
block, ``h = x + Attn(RMSNorm(x))``, ``y = h + MLP(RMSNorm(h))``, with five
mechanisms Mellum has not:

* the number of query heads goes by the layer's kind
  (``num_attention_heads_per_layer``: 48 on a full layer, 72 on a sliding
  one) over the same 8 KV heads;
* every head's attention output is gated before the output projection,
  ``g = sigmoid(u W_g)``, ``W_g`` [H, heads] (``gating`` ``per-head``);
* rotary turns the first ``partial_rotary_factor`` of a head, by kind: half
  of a full layer's head (under YaRN, its frequencies those of the 64 dims
  that turn), the whole of a sliding layer's;
* the MLP goes by ``mlp_layer_types``: ``dense`` (layer 0) is one gated MLP
  of ``intermediate_size``, ``sparse`` the softmax-routed mixture;
* a sparse layer adds one shared expert, unweighted, beside the routed sum,
  and the routed weights are scaled by ``moe_routed_scaling_factor``.

The attention sublayer (into the flash kernel, window and full), ``Rope``,
the routed walk (``MellumMoE`` over ``moe/dropless.py``) and what ``Mellum``
states of its stack (``models/decoder.py``) are Mellum's own code, imported.
The equations, and what the published ``config.json`` leaves to assumption, are
in ``benchmarks/reference/laguna_ref.py``.

A chip's share is told: ``layers_held`` layers from ``first_layer_held``,
``routed_experts_held`` experts from ``first_expert_held``,
``vocab_rows_held`` rows of both tables, and of the heads
``key_value_heads_held`` KV heads from ``first_key_value_head_held`` with
the query heads that read them (``full_attention_heads_held`` |
``sliding_attention_heads_held``: a query head goes with its KV head).
Heads and experts are independent, so the shares' partial outputs add up to
the whole layer's; router, norms, shared expert and the dense MLP are every
chip's alike and count once (``tests/unit/models/test_laguna.py``).

Scopes: ``attention`` with ``attention_window`` | ``attention_full`` inside
by kind and ``attention_gate`` inside those; ``mlp`` with ``mlp_dense`` or
``moe_route``, ``moe_experts``, ``moe_shared`` inside; ``embed``,
``head_ce``.
"""

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.attention.pallas_flash import band_pairs
from ..ops.transformer.normalize import rms_norm
from ..parallel.topology import BATCH_AXES
from .decoder import GatedMLP
from .gpt_neox import maybe_constrain
from .mellum import (FULL, SCOPE_OF, SLIDING, Mellum, MellumAttention,
                     MellumMoE, Rope)

DENSE, SPARSE = "dense", "sparse"
#: a full layer and three windowed ones, twelve times; layer 0's MLP dense
LAGUNA_S_LAYER_TYPES = (FULL, SLIDING, SLIDING, SLIDING) * 12
LAGUNA_S_MLP_TYPES = (DENSE,) + (SPARSE,) * 47


@dataclasses.dataclass(unsafe_hash=True)
class LagunaConfig:
    """Published keys under their published names (the per-layer head list
    as its two counts by kind); the ``*_held`` keys give a chip's share (the
    whole model where they are None)."""
    vocab_size: int = 100352
    hidden_size: int = 3072
    layer_types: Tuple[str, ...] = LAGUNA_S_LAYER_TYPES
    mlp_layer_types: Tuple[str, ...] = LAGUNA_S_MLP_TYPES
    rms_norm_eps: float = 1e-6
    full_attention_heads: int = 48
    sliding_attention_heads: int = 72
    num_kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    rope_sliding: Rope = Rope(theta=10000.0)
    rope_full: Rope = Rope(factor=128.0,
                           attention_factor=1.4852030263919618)
    partial_rotary_sliding: float = 1.0
    partial_rotary_full: float = 0.5
    intermediate_size: int = 12288
    num_experts: int = 256                # the router's width: never a share
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    norm_topk_prob: bool = True
    moe_routed_scaling_factor: float = 2.5
    # a chip's share
    layers_held: Optional[int] = None
    first_layer_held: int = 0
    routed_experts_held: Optional[int] = None
    first_expert_held: int = 0
    vocab_rows_held: Optional[int] = None
    full_attention_heads_held: Optional[int] = None
    sliding_attention_heads_held: Optional[int] = None
    key_value_heads_held: Optional[int] = None
    first_key_value_head_held: int = 0
    # the run
    max_seq_len: int = 8192
    ce_chunk_tokens: int = 2048
    dtype: Any = jnp.float32
    remat: bool = False

    def __post_init__(self):
        kv = self.kv_heads
        if self.first_key_value_head_held + kv > self.num_kv_heads:
            raise ValueError("the KV heads held lie outside the model's")
        for kind in set(self.layer_types):
            # a query head goes with the KV head it reads
            if self.heads(kind) * self.num_kv_heads != self.whole_heads(
                    kind) * kv:
                raise ValueError(
                    f"{kind}: {self.heads(kind)} query heads are not those "
                    f"of {kv} of {self.num_kv_heads} KV heads")

    @property
    def kinds(self):
        """(attention kind, MLP kind) of the layers held, in order."""
        held = (len(self.layer_types) if self.layers_held is None
                else self.layers_held)
        span = slice(self.first_layer_held, self.first_layer_held + held)
        return tuple(zip(self.layer_types[span], self.mlp_layer_types[span]))

    @property
    def experts(self):
        return (self.num_experts if self.routed_experts_held is None
                else self.routed_experts_held)

    @property
    def vocab_rows(self):
        return (self.vocab_size if self.vocab_rows_held is None
                else self.vocab_rows_held)

    @property
    def kv_heads(self):
        return (self.num_kv_heads if self.key_value_heads_held is None
                else self.key_value_heads_held)

    def whole_heads(self, kind):
        return (self.full_attention_heads if kind == FULL
                else self.sliding_attention_heads)

    def heads(self, kind):
        """Query heads of a layer of ``kind`` held here."""
        held = (self.full_attention_heads_held if kind == FULL
                else self.sliding_attention_heads_held)
        return self.whole_heads(kind) if held is None else held

    def rotary_dim(self, kind):
        share = (self.partial_rotary_full if kind == FULL
                 else self.partial_rotary_sliding)
        return int(self.head_dim * share)

    @staticmethod
    def laguna_s_2_1(**held):
        """Laguna-S-2.1 as published: 48 layers (12 full with 48 query
        heads, 36 windowed with 72), layer 0 dense, 256 experts of 1,024
        top-10 and a shared one; keyword arguments give a chip's share."""
        return LagunaConfig(**held)

    @staticmethod
    def tiny(**kw):
        small = dict(
            vocab_size=256, hidden_size=64,
            layer_types=(FULL, SLIDING, SLIDING),
            mlp_layer_types=(DENSE, SPARSE, SPARSE),
            full_attention_heads=4, sliding_attention_heads=6, num_kv_heads=2,
            head_dim=16, sliding_window=8,
            rope_full=Rope(factor=4.0, original_max_position=32,
                           attention_factor=1.1),
            intermediate_size=96, num_experts=16, num_experts_per_tok=3,
            moe_intermediate_size=48, shared_expert_intermediate_size=48,
            routed_experts_held=4, first_expert_held=4, max_seq_len=64,
            ce_chunk_tokens=48)
        return LagunaConfig(**dict(small, **kw))


class LagunaBlock(nn.Module):
    """One layer of kind (attention kind, MLP kind) -> (y, what the routed
    walk counted and chose; nothing of a dense layer)."""

    #: what a layer of ``LagunaConfig.kinds`` may be
    KINDS = frozenset((a, m) for a in SCOPE_OF for m in (DENSE, SPARSE))

    config: LagunaConfig
    kind: Tuple[str, str] = (FULL, SPARSE)

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        attention, mlp = self.kind
        x = maybe_constrain(x, (BATCH_AXES, "sp", None))
        with jax.named_scope("attention"), jax.named_scope(
                SCOPE_OF[attention]):
            scale = self.param("input_norm_scale", nn.initializers.ones,
                               (cfg.hidden_size,), jnp.float32)
            u = rms_norm(x, scale, eps=cfg.rms_norm_eps)
            x = x + MellumAttention(
                cfg, attention, heads=cfg.heads(attention),
                kv_heads=cfg.kv_heads, rotary_dim=cfg.rotary_dim(attention),
                gated=True, name="attn")(u)
        said = {}
        with jax.named_scope("mlp"):
            scale = self.param("post_norm_scale", nn.initializers.ones,
                               (cfg.hidden_size,), jnp.float32)
            u = rms_norm(x, scale, eps=cfg.rms_norm_eps)
            if mlp == DENSE:
                with jax.named_scope("mlp_dense"):
                    y = GatedMLP(cfg, cfg.intermediate_size, name="mlp")(u)
            else:
                y, counters, chosen = MellumMoE(
                    cfg, scale=cfg.moe_routed_scaling_factor, name="moe")(u)
                said = {"counters": counters, "chosen": chosen}
                with jax.named_scope("moe_shared"):
                    y = y + GatedMLP(cfg, cfg.shared_expert_intermediate_size,
                                     name="shared_expert")(u)
            x = x + y.astype(x.dtype)
        return maybe_constrain(x, (BATCH_AXES, "sp", None)), said


class Laguna(Mellum):
    """Causal LM: tokens [B, S] -> (the closing norm's output [B, S, H],
    each sparse layer's counters and chosen-here mask)."""

    block_cls = LagunaBlock

    config: LagunaConfig

    def counters(self, batch, seq):
        kinds = self.config.kinds

        def count(at, value):
            return jnp.int32(sum(1 for kind in kinds if kind[at] == value))

        return {"window_layer_applications": count(0, SLIDING),
                "full_layer_applications": count(0, FULL),
                "dense_mlp_layer_applications": count(1, DENSE),
                "moe_layer_applications": count(1, SPARSE),
                "shared_expert_layer_applications": count(1, SPARSE)}

    def param_partition_rules(self):
        """Megatron-style tp placement: attention by heads (the gate's
        columns with them), the dense MLP and the shared expert by their
        width, the tables by rows; the routed experts are a range the layer
        is told."""
        return [
            (r"embed_tokens/embedding", P("tp", None)),
            (r"(q_proj|k_proj|v_proj|g_proj|gate_proj|up_proj)/kernel",
             P(None, "tp")),
            (r"(o_proj|down_proj)/kernel", P("tp", None)),
            (r"lm_head_kernel", P(None, "tp")),
        ]

    # ---------------------------------------------------------------- counts
    def attention_params(self, kind):
        """Matmul weights of a layer's attention at the heads held: q and o,
        k and v, the gate's column a head."""
        cfg = self.config
        h, d = cfg.hidden_size, cfg.head_dim
        return h * (2 * (cfg.heads(kind) + cfg.kv_heads) * d
                    + cfg.heads(kind))

    def gated_mlp_params(self, width):
        return 3 * self.config.hidden_size * width

    def layer_matmul_params(self, kind):
        """Matmul weights a token passes in one layer outside its routed
        experts."""
        cfg = self.config
        attention, mlp = kind
        if mlp == DENSE:
            return (self.attention_params(attention)
                    + self.gated_mlp_params(cfg.intermediate_size))
        return (self.attention_params(attention)
                + cfg.hidden_size * cfg.num_experts
                + self.gated_mlp_params(cfg.shared_expert_intermediate_size))

    def routed_expert_params(self):
        return self.gated_mlp_params(self.config.moe_intermediate_size)

    def num_params(self):
        cfg = self.config
        h = cfg.hidden_size
        routed = cfg.experts * self.routed_expert_params()
        return (2 * cfg.vocab_rows * h + h + sum(
            self.layer_matmul_params(kind) + 2 * h
            + (routed if kind[1] == SPARSE else 0) for kind in cfg.kinds))

    def flops_per_token(self, slots_per_token=None):
        """Forward + backward FLOPs a trained token needs at the shares
        held: 6 x the matmul weights it passes (a routed expert counted per
        slot, ``slots_per_token`` a sparse layer: what the step's counter
        says, or what even routing would send here; the gate, the shared
        expert and the dense MLP counted), plus attention's scores and
        values by kind: ``12 heads D S`` a full layer and the band's share
        of the triangle of that a windowed one, at the heads held.
        Recomputed operations do not count."""
        cfg = self.config
        if slots_per_token is None:
            slots_per_token = (cfg.num_experts_per_tok * cfg.experts
                               / cfg.num_experts)
        sparse = sum(1 for kind in cfg.kinds if kind[1] == SPARSE)
        matmul = (sum(self.layer_matmul_params(kind) for kind in cfg.kinds)
                  + sparse * slots_per_token * self.routed_expert_params()
                  + cfg.hidden_size * cfg.vocab_rows)
        s = cfg.max_seq_len
        band = band_pairs(s, cfg.sliding_window) / band_pairs(s, None)
        scores = sum(cfg.heads(a) * (1.0 if a == FULL else band)
                     for a, _ in cfg.kinds)
        return 6 * matmul + 12 * cfg.head_dim * s * scores
