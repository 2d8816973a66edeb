"""Mellum 2: a decoder whose attention layers are of two kinds and whose
every MLP is a mixture of gated experts (JetBrains, ``model_type``
``mellum``; the preset is Mellum2-12B-A2.5B-Instruct).

A layer is pre-norm, ``h = x + Attn(RMSNorm(x))``, ``y = h +
MoE(RMSNorm(h))``:

* attention is grouped-query (a KV head serves ``heads / kv`` query heads),
  rotary on the whole head, and by ``layer_types`` either
  ``full_attention`` (the whole causal past; its rotary frequencies are
  YaRN's) or ``sliding_attention`` (row i sees ``i - sliding_window < j <=
  i``; plain rotary).  Both go through the flash kernel, the window as the
  kernel's own (``ops/attention/pallas_flash.py``): nothing is masked
  outside it.
* the MLP scores a token over ALL ``num_experts`` by a float32 softmax,
  keeps the ``num_experts_per_tok`` largest, renormalises them, and sums the
  chosen gated experts (``silu(gate) * up``, then ``down``).  There is no
  shared expert: the layer's whole output is what the routed walk gives
  (``moe/dropless.py``: ``softmax_topk``, ``gated_silu`` on a fused gate |
  up matrix).  The layer is told which experts it holds and computes their
  part, dropless.

The equations, and what the published ``config.json`` leaves to assumption,
are in ``benchmarks/reference/mellum_ref.py``.  The model card's
multi-token-prediction head has no key in the config and is not here.

A chip's share.  The configuration says what THIS chip holds: ``layers_held``
layers of ``layer_types`` from ``first_layer_held``, ``routed_experts_held``
experts from ``first_expert_held``, ``vocab_rows_held`` rows of both
tables.  Experts are independent, so the shares' partial outputs add up to
the whole layer's (router, norms and attention are every chip's alike and
count once): ``tests/unit/models/test_mellum.py``.

The stack, the routed layers' report, the head + loss and the rest of the
engine protocol are ``models/decoder.py``'s.  Scopes: ``attention`` (a
layer's attention sublayer with its norm) with ``attention_window`` or
``attention_full`` inside by kind, ``mlp`` with ``moe_route`` and
``moe_experts`` inside, ``embed``, ``head_ce``.
"""

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..moe import dropless
from ..ops.attention.core import dot_product_attention
from ..ops.attention.pallas_flash import band_pairs
from ..ops.transformer.normalize import rms_norm
from ..ops.transformer.rope import (apply_rotary_pos_emb, rotary_tables,
                                    yarn_inv_freq)
from ..parallel.topology import BATCH_AXES
from .decoder import Decoder, Stack, _dense
from .gpt_neox import maybe_constrain

SLIDING, FULL = "sliding_attention", "full_attention"
#: a period of three windowed layers and a full one, seven times
MELLUM2_LAYER_TYPES = (SLIDING, SLIDING, SLIDING, FULL) * 7
SCOPE_OF = {SLIDING: "attention_window", FULL: "attention_full"}


@dataclasses.dataclass(frozen=True)
class Rope:
    """One layer kind's ``rope_parameters``: plain where ``factor`` is None,
    else YaRN."""
    theta: float = 500000.0
    factor: Optional[float] = None
    original_max_position: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None

    def tables(self, positions, dim, dtype):
        if self.factor is None:
            return rotary_tables(positions, dim, self.theta, dtype)
        return rotary_tables(
            positions, dim, self.theta, dtype,
            inv_freq=yarn_inv_freq(dim, self.theta, self.factor,
                                   self.original_max_position,
                                   self.beta_fast, self.beta_slow),
            scale=self.attention_factor)


@dataclasses.dataclass(unsafe_hash=True)
class MellumConfig:
    """Published keys under their published names; the ``*_held`` keys give
    a chip's share (the whole model where they are None)."""
    vocab_size: int = 98304
    hidden_size: int = 2304
    layer_types: Tuple[str, ...] = MELLUM2_LAYER_TYPES
    rms_norm_eps: float = 1e-6
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 1024
    rope_sliding: Rope = Rope()
    rope_full: Rope = Rope(factor=16.0,
                           attention_factor=1.2772588722239782)
    num_experts: int = 64                 # the router's width: never a share
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 896
    norm_topk_prob: bool = True
    # a chip's share
    layers_held: Optional[int] = None
    first_layer_held: int = 0
    routed_experts_held: Optional[int] = None
    first_expert_held: int = 0
    vocab_rows_held: Optional[int] = None
    # the run
    max_seq_len: int = 8192
    ce_chunk_tokens: int = 2048
    dtype: Any = jnp.float32
    remat: bool = False

    @property
    def kinds(self):
        """The kinds of the layers held, in order."""
        held = (len(self.layer_types) if self.layers_held is None
                else self.layers_held)
        return tuple(self.layer_types[
            self.first_layer_held:self.first_layer_held + held])

    @property
    def experts(self):
        return (self.num_experts if self.routed_experts_held is None
                else self.routed_experts_held)

    @property
    def vocab_rows(self):
        return (self.vocab_size if self.vocab_rows_held is None
                else self.vocab_rows_held)

    @staticmethod
    def mellum2_12b(**kw):
        """Mellum2-12B-A2.5B-Instruct as published: 28 layers (21 windowed,
        7 full), 64 experts of 896 top-8; keyword arguments give a chip's
        share."""
        return MellumConfig(**kw)

    @staticmethod
    def tiny(**kw):
        small = dict(
            vocab_size=256, hidden_size=64,
            layer_types=(SLIDING, SLIDING, FULL), num_heads=4, num_kv_heads=2,
            head_dim=16, sliding_window=8,
            rope_full=Rope(factor=4.0, original_max_position=32,
                           attention_factor=1.1),
            num_experts=16, num_experts_per_tok=3, moe_intermediate_size=48,
            routed_experts_held=4, first_expert_held=4, max_seq_len=64,
            ce_chunk_tokens=48)
        return MellumConfig(**dict(small, **kw))


class MellumAttention(nn.Module):
    """Grouped-query causal attention of one layer kind: the window, and the
    kind's rotary tables, are the kind's.  What a sibling model's layers
    have beside (``models/laguna.py``) is told per layer and absent here:
    ``heads`` / ``kv_heads`` where they go by layer and not by the
    configuration's one count, ``rotary_dim`` where only the first dims of a
    head turn, and ``gated``, a sigmoid gate a head (``sigmoid(u W_g)``, ``W_g``
    [H, heads]) on the attention output before the output projection."""

    config: Any
    kind: str = FULL
    heads: Optional[int] = None
    kv_heads: Optional[int] = None
    rotary_dim: Optional[int] = None
    gated: bool = False

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        B, S, _ = u.shape
        nq, kv, d = (self.heads or cfg.num_heads,
                     self.kv_heads or cfg.num_kv_heads, cfg.head_dim)
        q = _dense(nq * d, cfg, "q_proj")(u).reshape(B, S, nq, d)
        k = _dense(kv * d, cfg, "k_proj")(u).reshape(B, S, kv, d)
        v = _dense(kv * d, cfg, "v_proj")(u).reshape(B, S, kv, d)
        rope = cfg.rope_full if self.kind == FULL else cfg.rope_sliding
        cos, sin = rope.tables(jnp.arange(S)[None], self.rotary_dim or d,
                               cfg.dtype)
        q, k = apply_rotary_pos_emb(q, k, cos, sin)
        # k and v go at their KV heads: the kernel addresses them by the
        # query head's group and nothing copies them (``pallas_flash.mha``)
        out = dot_product_attention(
            q, k, v, causal=True,
            window=cfg.sliding_window if self.kind == SLIDING else None)
        if self.gated:
            with jax.named_scope("attention_gate"):
                gate = jax.nn.sigmoid(
                    _dense(nq, cfg, "g_proj")(u).astype(jnp.float32))
                out = out * gate[..., None].astype(out.dtype)
        with jax.named_scope("attention_layout"):
            out = out.reshape(B, S, nq * d)
        return _dense(cfg.hidden_size, cfg, "o_proj")(out)


class MellumMoE(nn.Module):
    """The routed experts held here: u [B, S, H] -> (this share's output
    [B, S, H], the walk's counters, which held experts each token chose
    [B, S, held])."""

    config: Any
    scale: float = 1.0      # of the routed weights (a sibling model's)

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        B, S, H = u.shape
        held, f = cfg.experts, cfg.moe_intermediate_size
        router = self.param("router_kernel", nn.initializers.normal(0.02),
                            (H, cfg.num_experts), jnp.float32)
        gate_up = self.param("experts_gate_up_proj",
                             nn.initializers.normal(0.02), (held, H, 2 * f),
                             jnp.float32)
        down = self.param("experts_down_proj", nn.initializers.normal(0.02),
                          (held, f, H), jnp.float32)
        tokens = u.reshape(B * S, H)
        with jax.named_scope("moe_route"):
            # scores over all the experts, float32 on every pass of the MXU
            logits = jnp.dot(tokens.astype(jnp.float32),
                             router.astype(jnp.float32), precision="highest")
        out, counters, is_chosen = dropless.dropless_moe(
            tokens, logits, gate_up, down, k=cfg.num_experts_per_tok,
            first_expert=cfg.first_expert_held, experts_held=held,
            normalize=cfg.norm_topk_prob, scale=self.scale,
            scoring=dropless.softmax_topk, activation=dropless.gated_silu)
        return (out.reshape(B, S, H), counters,
                is_chosen.reshape(B, S, held))


class MellumBlock(nn.Module):
    """``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))`` -> (y, what
    the routed walk counted and chose)."""

    #: what a layer of ``MellumConfig.kinds`` may be
    KINDS = frozenset(SCOPE_OF)

    config: MellumConfig
    kind: str = FULL

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        x = maybe_constrain(x, (BATCH_AXES, "sp", None))
        with jax.named_scope("attention"), jax.named_scope(
                SCOPE_OF[self.kind]):
            scale = self.param("input_norm_scale", nn.initializers.ones,
                               (cfg.hidden_size,), jnp.float32)
            u = rms_norm(x, scale, eps=cfg.rms_norm_eps)
            x = x + MellumAttention(cfg, self.kind, name="attn")(u)
        with jax.named_scope("mlp"):
            scale = self.param("post_norm_scale", nn.initializers.ones,
                               (cfg.hidden_size,), jnp.float32)
            u = rms_norm(x, scale, eps=cfg.rms_norm_eps)
            y, counters, chosen = MellumMoE(cfg, name="moe")(u)
            x = x + y.astype(x.dtype)
        return (maybe_constrain(x, (BATCH_AXES, "sp", None)),
                {"counters": counters, "chosen": chosen})


class Mellum(Decoder):
    """Causal LM: tokens [B, S] -> (the closing norm's output [B, S, H],
    each layer's counters and chosen-here mask)."""

    #: a sibling model names its own
    block_cls = MellumBlock
    #: and the grouped walk's plan: its sorts are made once a step
    saved_by_remat = Decoder.saved_by_remat + (dropless.PLAN_SAVED_BY_REMAT,)

    config: MellumConfig

    def stack(self):
        cfg = self.config
        return Stack(kinds=cfg.kinds, rows=cfg.vocab_rows,
                     columns=cfg.vocab_rows, norm_eps=cfg.rms_norm_eps,
                     table_dtype=cfg.dtype)

    def counters(self, batch, seq):
        kinds = self.config.kinds
        return {"window_layer_applications": jnp.int32(kinds.count(SLIDING)),
                "full_layer_applications": jnp.int32(kinds.count(FULL)),
                "moe_layer_applications": jnp.int32(len(kinds))}

    def no_cast_paths(self):
        """Float32 under mixed precision: the embedding table (its gradient
        is a scatter-add) and the router (top-k flips on rounding)."""
        return [r"embed_tokens/embedding", r"router_kernel"]

    def param_partition_rules(self):
        """Megatron-style tp placement of the attention matrices and the
        tables; the routed experts are a range the layer is told, divided
        by giving a chip its share in the configuration."""
        return [
            (r"embed_tokens/embedding", P("tp", None)),
            (r"(q_proj|k_proj|v_proj)/kernel", P(None, "tp")),
            (r"o_proj/kernel", P("tp", None)),
            (r"lm_head_kernel", P(None, "tp")),
        ]

    # ---------------------------------------------------------------- counts
    def layer_matmul_params(self):
        """Matmul weights a token passes in one layer outside its routed
        experts: the four attention projections and the router."""
        cfg = self.config
        h = cfg.hidden_size
        return (2 * h * (cfg.num_heads + cfg.num_kv_heads) * cfg.head_dim
                + h * cfg.num_experts)

    def routed_expert_params(self):
        cfg = self.config
        return 3 * cfg.hidden_size * cfg.moe_intermediate_size

    def num_params(self):
        cfg = self.config
        h = cfg.hidden_size
        return (2 * cfg.vocab_rows * h + h + len(cfg.kinds) * (
            self.layer_matmul_params() + 2 * h
            + cfg.experts * self.routed_expert_params()))

    def flops_per_token(self, slots_per_token=None):
        """Forward + backward FLOPs a trained token needs: 6 x the matmul
        weights it passes, a routed expert counted per slot
        (``slots_per_token`` a layer: what the step's counter says, or what
        even routing would send here), plus attention's scores and values,
        ``12 heads D S`` a full layer and the band's share of the triangle
        of that a windowed one.  Recomputed operations do not count."""
        cfg = self.config
        if slots_per_token is None:
            slots_per_token = (cfg.num_experts_per_tok * cfg.experts
                               / cfg.num_experts)
        matmul = (len(cfg.kinds) * (
            self.layer_matmul_params()
            + slots_per_token * self.routed_expert_params())
            + cfg.hidden_size * cfg.vocab_rows)
        s = cfg.max_seq_len
        band = band_pairs(s, cfg.sliding_window) / band_pairs(s, None)
        return (6 * matmul + 12 * cfg.num_heads * cfg.head_dim * s * (
            cfg.kinds.count(FULL) + band * cfg.kinds.count(SLIDING)))
