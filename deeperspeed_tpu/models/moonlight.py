"""Moonlight-16B-A3B (Moonshot AI, ``model_type`` ``deepseek_v3``): latent
attention (MLA, DeepSeek-V2 section 2.1, arXiv:2405.04434) over DeepSeek-V3's
mixture (its section 2.1.2, arXiv:2412.19437) -- the training half.

A layer is pre-norm, ``h = x + MLA(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``:

* MLA.  ``q = u W_q`` is 16 heads of ``qk_nope_head_dim`` +
  ``qk_rope_head_dim`` (``q_lora_rank`` null: q has no latent; here two
  leaves, ``q_nope_proj`` and ``q_rope_proj``, the columns of the
  published ``q_proj`` by part).  ``[c |
  k_r] = u W_kva`` is ``kv_lora_rank`` + ``qk_rope_head_dim`` wide; ``c`` takes
  an RMSNorm of its own and feeds the up-projections ``k_nope = c W_kb``,
  ``v = c W_vb`` (the published ``kv_b_proj``'s columns by part: DeepSeek-V2's
  ``W^UK`` and ``W^UV``).  Rotary turns ``q``'s rotary part in every head
  and the ONE ``k_r``, which all the heads share; ``k_nope`` and ``v`` carry
  no position.  The score is ``(q_nope . k_nope + q_rope . k_r) / sqrt(d_nope +
  d_rope)``; the value and output are ``v_head_dim`` wide.  The projections'
  outputs are the flash kernel's operands as they stand
  (``ops/attention/core.py::latent_attention`` -> ``pallas_flash_mla.mla``):
  the rotary key is copied to no head and no value is padded to the score's
  width.
* FFN.  The first ``first_k_dense_replace`` layers: one gated SiLU MLP of
  ``intermediate_size``.  The others: ``s = sigmoid(u W_r)`` in float32 over
  ALL ``n_routed_experts``; the ``num_experts_per_tok`` largest of ``s + b``
  (``b`` a selection bias that takes no gradient and that nothing here
  updates; ``n_group`` 1: no group limit); weights ``s / sum(s chosen) x
  routed_scaling_factor``; gated SiLU experts of ``moe_intermediate_size``
  (``moe/dropless.py``: ``sigmoid_topk``, ``gated_silu``, the layer told
  which experts it holds); plus the ``n_shared_experts`` shared experts, ONE
  gated MLP ``n_shared_experts x moe_intermediate_size`` wide on every token.
  ``seq_aux``: the sequence-wise balance term (``dropless.sequence_balance``
  x ``aux_loss_alpha``), summed over the sparse layers and added to the loss.

The equations, and what the published ``config.json`` leaves to assumption,
are in ``benchmarks/reference/moonlight_ref.py``.

A chip's share is told as Mellum's is: ``layers_held`` layers from
``first_layer_held``, ``routed_experts_held`` experts from
``first_expert_held``, ``vocab_rows_held`` rows of both tables; attention with
every head, the router, the shared experts, the norms and the dense MLP are
whole on every chip and count once (``tests/unit/models/
test_moonlight_mechanisms.py``).

The stack, the routed layers' report and the head's call are
``models/decoder.py``'s.  Scopes: ``attention`` with ``mla_latent`` inside
(the down-projection, its norm, the up-projections, the split and the
rotary) and the kernel's own ``flash_attention_mla``; ``mlp`` with
``mlp_dense`` or ``moe_route``, ``moe_experts``, ``moe_shared`` inside;
``embed``, ``head_ce``.
"""

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..moe import dropless
from ..ops.attention.core import latent_attention
from ..ops.transformer.normalize import rms_norm
from ..ops.transformer.rope import apply_rotary_pos_emb, rotary_tables
from ..parallel.topology import BATCH_AXES
from .decoder import Decoder, GatedMLP, Stack, _dense
from .gpt_neox import maybe_constrain

DENSE, SPARSE = "dense", "sparse"


@dataclasses.dataclass(unsafe_hash=True)
class MoonlightConfig:
    """Published keys under their published names; the ``*_held`` keys give
    a chip's share (the whole model where they are None)."""
    vocab_size: int = 163840
    hidden_size: int = 2048
    num_hidden_layers: int = 27
    first_k_dense_replace: int = 1
    rms_norm_eps: float = 1e-5
    num_attention_heads: int = 16
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 50000.0
    intermediate_size: int = 11264
    n_routed_experts: int = 64            # the router's width: never a share
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    moe_intermediate_size: int = 1408
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.446
    aux_loss_alpha: float = 1e-4          # of the sequence-wise balance term
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
        held = (self.num_hidden_layers if self.layers_held is None
                else self.layers_held)
        if self.first_layer_held + held > self.num_hidden_layers:
            raise ValueError("the layers held lie outside the model's")
        return tuple(DENSE if i < self.first_k_dense_replace else SPARSE
                     for i in range(self.first_layer_held,
                                    self.first_layer_held + held))

    @property
    def experts(self):
        return (self.n_routed_experts if self.routed_experts_held is None
                else self.routed_experts_held)

    @property
    def vocab_rows(self):
        return (self.vocab_size if self.vocab_rows_held is None
                else self.vocab_rows_held)

    @property
    def shared_width(self):
        """The shared experts as the one gated MLP they are."""
        return self.n_shared_experts * self.moe_intermediate_size

    @staticmethod
    def moonlight_16b_a3b(**held):
        """Moonlight-16B-A3B as published: 27 layers (the first dense), 16
        heads of 128 + 64 | 128 over a latent of 512, 64 experts of 1,408
        top-6 and two shared; keyword arguments give a chip's share."""
        return MoonlightConfig(**held)

    @staticmethod
    def tiny(**kw):
        small = dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=3,
            num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
            n_routed_experts=16, num_experts_per_tok=3,
            moe_intermediate_size=48, routed_experts_held=4,
            first_expert_held=4, max_seq_len=64, ce_chunk_tokens=48)
        return MoonlightConfig(**dict(small, **kw))


class MLAttention(nn.Module):
    """The latent-attention sublayer: u [B, S, H] -> [B, S, H]."""

    config: Any

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        B, S, _ = u.shape
        n, rank = cfg.num_attention_heads, cfg.kv_lora_rank
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        q_nope = _dense(n * dn, cfg, "q_nope_proj")(u).reshape(B, S, n, dn)
        q_rope = _dense(n * dr, cfg, "q_rope_proj")(u).reshape(B, S, n, dr)
        with jax.named_scope("mla_latent"):
            down = _dense(rank + dr, cfg, "kv_a_proj")(u)
            scale = self.param("kv_a_norm_scale", nn.initializers.ones,
                               (rank,), jnp.float32)
            c = rms_norm(down[..., :rank], scale, eps=cfg.rms_norm_eps)
            k_nope = _dense(n * dn, cfg, "k_b_proj")(c).reshape(B, S, n, dn)
            v = _dense(n * dv, cfg, "v_b_proj")(c).reshape(B, S, n, dv)
            cos, sin = rotary_tables(jnp.arange(S)[None], dr, cfg.rope_theta,
                                     cfg.dtype)
            # the ONE rotary key turns as a head of its own
            q_rope, k_rope = apply_rotary_pos_emb(
                q_rope, down[..., None, rank:], cos, sin)
        out = latent_attention(q_nope, q_rope, k_nope, k_rope[:, :, 0], v)
        with jax.named_scope("attention_layout"):
            out = out.reshape(B, S, n * dv)
        return _dense(cfg.hidden_size, cfg, "o_proj")(out)


class MoonlightMoE(nn.Module):
    """The routed experts held here: u [B, S, H] -> (this share's routed
    output [B, S, H], the walk's counters, which held experts each token
    chose [B, S, held], the layer's balance term)."""

    config: Any

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        B, S, H = u.shape
        held, f = cfg.experts, cfg.moe_intermediate_size
        normal = nn.initializers.normal(0.02)
        router = self.param("router_kernel", normal,
                            (H, cfg.n_routed_experts), jnp.float32)
        # chooses and takes no gradient; nothing here updates it
        bias = jax.lax.stop_gradient(self.param(
            "selection_bias", nn.initializers.zeros,
            (cfg.n_routed_experts,), jnp.float32))
        gate_up = self.param("experts_gate_up_proj", normal, (held, H, 2 * f),
                             jnp.float32)
        down = self.param("experts_down_proj", normal, (held, f, H),
                          jnp.float32)
        tokens = u.reshape(B * S, H)
        with jax.named_scope("moe_route"):
            # scores over all the experts, float32 on every pass of the MXU
            logits = jnp.dot(tokens.astype(jnp.float32),
                             router.astype(jnp.float32), precision="highest")
        told = {}

        def scoring(logits, k, selection_bias, normalize, scale):
            """``sigmoid_topk``, and the balance term of what it chose."""
            chosen, weights = dropless.sigmoid_topk(
                logits, k, selection_bias, normalize, scale)
            told["balance"] = cfg.aux_loss_alpha * dropless.sequence_balance(
                logits, chosen, seqs=B)
            return chosen, weights

        out, counters, is_chosen = dropless.dropless_moe(
            tokens, logits, gate_up, down, k=cfg.num_experts_per_tok,
            first_expert=cfg.first_expert_held, experts_held=held,
            selection_bias=bias, normalize=cfg.norm_topk_prob,
            scale=cfg.routed_scaling_factor, scoring=scoring,
            activation=dropless.gated_silu)
        return (out.reshape(B, S, H), counters,
                is_chosen.reshape(B, S, held), told["balance"])


class MoonlightBlock(nn.Module):
    """``h = x + MLA(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))`` -> (y, what
    the routed walk counted and chose and the layer's balance term; nothing
    of a dense layer)."""

    #: what a layer of ``MoonlightConfig.kinds`` may be
    KINDS = frozenset((DENSE, SPARSE))

    config: MoonlightConfig
    kind: str = SPARSE

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        x = maybe_constrain(x, (BATCH_AXES, "sp", None))
        with jax.named_scope("attention"):
            scale = self.param("input_norm_scale", nn.initializers.ones,
                               (cfg.hidden_size,), jnp.float32)
            u = rms_norm(x, scale, eps=cfg.rms_norm_eps)
            x = x + MLAttention(cfg, name="attn")(u)
        said = {}
        with jax.named_scope("mlp"):
            scale = self.param("post_norm_scale", nn.initializers.ones,
                               (cfg.hidden_size,), jnp.float32)
            u = rms_norm(x, scale, eps=cfg.rms_norm_eps)
            if self.kind == DENSE:
                with jax.named_scope("mlp_dense"):
                    y = GatedMLP(cfg, cfg.intermediate_size, name="mlp")(u)
            else:
                y, counters, chosen, balance = MoonlightMoE(
                    cfg, name="moe")(u)
                said = {"counters": counters, "chosen": chosen,
                        "loss": balance}
                with jax.named_scope("moe_shared"):
                    y = y + GatedMLP(cfg, cfg.shared_width,
                                     name="shared_experts")(u)
            x = x + y.astype(x.dtype)
        return maybe_constrain(x, (BATCH_AXES, "sp", None)), said


class Moonlight(Decoder):
    """Causal LM: tokens [B, S] -> (the closing norm's output [B, S, H],
    each sparse layer's counters, chosen-here mask and balance term)."""

    block_cls = MoonlightBlock
    #: a recomputed layer keeps the flash kernel's residuals and the grouped
    #: walk's plan: its sorts are made once a step
    saved_by_remat = Decoder.saved_by_remat + (dropless.PLAN_SAVED_BY_REMAT,)

    config: MoonlightConfig

    def stack(self):
        cfg = self.config
        return Stack(kinds=cfg.kinds, rows=cfg.vocab_rows,
                     columns=cfg.vocab_rows, norm_eps=cfg.rms_norm_eps,
                     table_dtype=cfg.dtype)

    def counters(self, batch, seq):
        kinds = self.config.kinds
        sparse = jnp.int32(kinds.count(SPARSE))
        return {"mla_layer_applications": jnp.int32(len(kinds)),
                "dense_mlp_layer_applications": jnp.int32(kinds.count(DENSE)),
                "moe_layer_applications": sparse,
                "shared_expert_layer_applications": sparse}

    @nn.nowrap
    def _report(self, told, shape):
        """The stack's report and the balance term's value, summed over the
        sparse layers: what the loss holds beside the cross entropy."""
        return {**super()._report(told, shape), "moe_balance_loss":
                sum(said["loss"] for said in told)}

    def no_cast_paths(self):
        """Float32 under mixed precision: the embedding table (its gradient
        is a scatter-add), the router and its selection bias (top-k flips
        on rounding) and the norms' scales."""
        return [r"embed_tokens/embedding", r"router_kernel",
                r"selection_bias", r"norm_scale"]

    def param_partition_rules(self):
        """Megatron-style tp placement: attention by heads (the shared
        down-projection and its norm whole on every device), the dense MLP
        and the shared experts by their width, the tables by rows; the
        routed experts are a range the layer is told."""
        return [
            (r"embed_tokens/embedding", P("tp", None)),
            (r"(q_nope_proj|q_rope_proj|k_b_proj|v_b_proj|gate_proj|up_proj)"
             r"/kernel", P(None, "tp")),
            (r"(o_proj|down_proj)/kernel", P("tp", None)),
            (r"lm_head_kernel", P(None, "tp")),
        ]

    # ---------------------------------------------------------------- counts
    def attention_params(self):
        """Matmul weights of a layer's attention: q, the down-projection,
        the two up-projections, o."""
        cfg = self.config
        h, n, rank = (cfg.hidden_size, cfg.num_attention_heads,
                      cfg.kv_lora_rank)
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        return (h * n * (dn + dr) + h * (rank + dr) + rank * n * (dn + dv)
                + n * dv * h)

    def gated_mlp_params(self, width):
        return 3 * self.config.hidden_size * width

    def layer_matmul_params(self, kind):
        """Matmul weights a token passes in one layer outside its routed
        experts."""
        cfg = self.config
        if kind == DENSE:
            return (self.attention_params()
                    + self.gated_mlp_params(cfg.intermediate_size))
        return (self.attention_params()
                + cfg.hidden_size * cfg.n_routed_experts
                + self.gated_mlp_params(cfg.shared_width))

    def routed_expert_params(self):
        return self.gated_mlp_params(self.config.moe_intermediate_size)

    def num_params(self):
        cfg = self.config
        h = cfg.hidden_size
        sparse = cfg.kinds.count(SPARSE)
        # a layer's two norms and the latent's; a sparse layer's bias
        return (2 * cfg.vocab_rows * h + h
                + sum(self.layer_matmul_params(kind) for kind in cfg.kinds)
                + len(cfg.kinds) * (2 * h + cfg.kv_lora_rank)
                + sparse * (cfg.n_routed_experts
                            + cfg.experts * self.routed_expert_params()))

    def flops_per_token(self, slots_per_token=None):
        """Forward + backward FLOPs a trained token needs at the shares
        held: 6 x the matmul weights it passes (a routed expert counted per
        slot, ``slots_per_token`` a sparse layer: what the step's counter
        says, or what even routing would send here; the shared experts and
        the dense MLP counted; the head), plus the attention's two score
        products and its values over the causal half, ``3 heads (d_nope +
        d_rope + d_v) S`` a layer.  Recomputed operations do not count."""
        cfg = self.config
        if slots_per_token is None:
            slots_per_token = (cfg.num_experts_per_tok * cfg.experts
                               / cfg.n_routed_experts)
        matmul = (sum(self.layer_matmul_params(kind) for kind in cfg.kinds)
                  + cfg.kinds.count(SPARSE) * slots_per_token
                  * self.routed_expert_params()
                  + cfg.hidden_size * cfg.vocab_rows)
        return 6 * matmul + len(cfg.kinds) * 3 * cfg.num_attention_heads * (
            cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
            + cfg.v_head_dim) * cfg.max_seq_len
