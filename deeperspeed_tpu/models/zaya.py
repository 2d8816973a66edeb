"""ZAYA1's language model (Zyphra, ``model_type`` ``zaya``; the preset is
ZAYA1-8B): 40 layers of one kind on a SCALED RESIDUAL stream, each an
attention sublayer in a compressed latent with convolutional mixing (CCA,
arXiv:2510.04476) and then a top-1-of-16 mixture whose router is an MLP that
carries its state from layer to layer (the ZAYA1 report, arXiv:2511.17127),
with a tied table.

* The stream.  A sublayer reads ``u = RMSNorm(r)`` and gives ``f``; then ``r
  <- a_r * (r + b_r) + a_o * (f + b_o)``, four learned vectors a sublayer,
  float32 (``fold``).
* CCA.  q, k and v are projected into a latent NARROWER than the stream (8
  query heads and 2 KV heads of 128: 1024, 256 and 256 of 2048); the later
  half of the KV heads' values are the PREVIOUS token's; the packed ``[q |
  k]`` goes through a causal depthwise convolution and then a causal
  convolution whose channels mix inside each head (``ops/ssm.py``); the
  mean of a query head and its KV head from BEFORE the convolutions is
  added back; every head is divided by its RMS, k times a learned
  temperature a KV head, rotary on half a head (``ops/attention/cca.py``'s
  ``cca_mix``: everything between the projections and the flash kernel,
  one kernel pair on a TPU where the shapes are whole tiles; the flash
  kernel is called with grouped-query heads as it is for every other
  model).
* The router.  ``rho = u W_D + b_D`` in 256; ``rho += gamma * rho_prev``,
  the previous layer's state (the decoder stack hands it on beside the
  stream); an RMSNorm and a three-matrix GELU MLP give the 16 logits; the
  expert is ``argmax(softmax + beta)``, ``beta`` a balancing bias that takes
  no gradient; the output is the chosen expert's times its softmax
  probability, NOT renormalised.  All of it float32 at ``highest``, as
  Mellum's router is.  The experts are ``moe/dropless.py``'s walk at k = 1.

The equations, and what the published ``config.json`` leaves to assumption,
are in ``benchmarks/reference/zaya_ref.py``.  The rule that updates ``beta``
and the family's mixture-of-depths skip choice are not here.

A chip's share is told as Mellum's is: ``layers_held`` layers from
``first_layer_held``, ``routed_experts_held`` experts from
``first_expert_held`` (a token whose one expert is elsewhere gets nothing
from this layer here), ``vocab_rows_held`` rows of the ONE table; attention,
router and norms are whole on every chip and count once
(``tests/unit/models/test_zaya.py``).  The first layer HELD is handed no
router state and has no ``gamma``: what an earlier pipeline stage would send
is that stage's to send.

The stack, the routed layers' report and the head's call are
``models/decoder.py``'s.  Scopes: ``attention`` with ``cca_mix`` inside;
``mlp`` with ``moe_router_mlp`` (what precedes ``moe_route``), ``moe_route``
and ``moe_experts`` inside; ``embed``, ``head_ce``.
"""

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..moe import dropless
from ..ops.attention.cca import cca_mix
from ..ops.attention.core import dot_product_attention
from ..ops.transformer.normalize import rms_norm
from ..parallel.topology import BATCH_AXES
from .decoder import Decoder, Stack, _dense
from .gpt_neox import maybe_constrain

HYBRID = "hybrid"
#: The name a ``jax.checkpoint`` policy keeps a routed layer's output by, in
#: the stream's type (134 MB of the cell's layer): the scaled residual's
#: backward pass reads it (``d a_o = sum d_r (f + b_o)``), where a plain ``x
#: + f`` reads nothing, and without it a recomputed layer walks its experts
#: forward a second time.
MOE_OUT_SAVED_BY_REMAT = "zaya_moe_out"


@dataclasses.dataclass(unsafe_hash=True)
class ZayaConfig:
    """Published keys under their published names; the ``*_held`` keys give
    a chip's share (the whole model where they are None)."""
    vocab_size: int = 262272
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    rms_norm_eps: float = 1e-5
    num_heads: int = 8
    num_kv_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2          # the depthwise convolution's width
    cca_time1: int = 2          # the per-head convolution's
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5e6
    num_experts: int = 16                 # the router's width: never a share
    num_experts_per_tok: int = 1
    moe_intermediate_size: int = 2048
    router_hidden_size: int = 256
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
        """The kinds of the layers held, in order: all alike."""
        held = (self.num_hidden_layers if self.layers_held is None
                else self.layers_held)
        if self.first_layer_held + held > self.num_hidden_layers:
            raise ValueError("the layers held lie outside the model's")
        return (HYBRID,) * held

    @property
    def experts(self):
        return (self.num_experts if self.routed_experts_held is None
                else self.routed_experts_held)

    @property
    def vocab_rows(self):
        return (self.vocab_size if self.vocab_rows_held is None
                else self.vocab_rows_held)

    @property
    def mixed_channels(self):
        """The packed ``[q | k]`` latent's width."""
        return (self.num_heads + self.num_kv_heads) * self.head_dim

    @staticmethod
    def zaya1_8b(**held):
        """ZAYA1-8B as published; keyword arguments give a chip's share."""
        return ZayaConfig(**held)

    @staticmethod
    def tiny(**kw):
        small = dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=3, num_heads=4,
            num_kv_heads=2, head_dim=16, num_experts=16,
            moe_intermediate_size=48, router_hidden_size=32,
            routed_experts_held=8, first_expert_held=8, max_seq_len=96,
            ce_chunk_tokens=48)
        return ZayaConfig(**dict(small, **kw))


def fold(r, f, scale, bias):
    """The scaled residual: ``a_r * (r + b_r) + a_o * (f + b_o)`` in
    float32, in the stream's type; ``scale`` [2, H] = [a_r, a_o], ``bias``
    [2, H] = [b_r, b_o]."""
    scale, bias = scale.astype(jnp.float32), bias.astype(jnp.float32)
    return (scale[0] * (r.astype(jnp.float32) + bias[0])
            + scale[1] * (f.astype(jnp.float32) + bias[1])).astype(r.dtype)


class ZayaAttention(nn.Module):
    """The CCA sublayer: u [B, S, H] -> [B, S, H], attention in the latent
    ``n_q x d`` wide."""

    config: Any

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        B, S, _ = u.shape
        nq, kv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        c, f32 = cfg.mixed_channels, jnp.float32
        normal = nn.initializers.normal
        qt = _dense(nq * d, cfg, "q_proj")(u)
        kt = _dense(kv * d, cfg, "k_proj")(u)
        v = _dense(kv * d, cfg, "v_proj")(u)     # [own | the previous token's]
        taps = self.param("conv_taps", normal(cfg.cca_time0 ** -0.5),
                          (cfg.cca_time0, c), f32)
        taps_bias = self.param("conv_bias", normal(0.02), (c,), f32)
        head_kernel = self.param(
            "head_conv_kernel", normal((cfg.cca_time1 * d) ** -0.5),
            (cfg.cca_time1, nq + kv, d, d), f32)
        head_bias = self.param("head_conv_bias", normal(0.02), (c,), f32)
        temperature = self.param("k_temperature", nn.initializers.ones,
                                 (kv,), f32)
        with jax.named_scope("cca_mix"):
            q, k, v = cca_mix(
                qt, kt, v, taps, taps_bias, head_kernel, head_bias,
                temperature, heads=nq, kv_heads=kv,
                rotary_dim=int(d * cfg.partial_rotary_factor),
                rope_theta=cfg.rope_theta, eps=cfg.rms_norm_eps)
        # k and v go at their KV heads: the kernel addresses them by the
        # query head's group and nothing copies them (``pallas_flash.mha``)
        out = dot_product_attention(
            q.reshape(B, S, nq, d), k.reshape(B, S, kv, d),
            v.reshape(B, S, kv, d), causal=True)
        with jax.named_scope("attention_layout"):
            out = out.reshape(B, S, nq * d)
        return _dense(cfg.hidden_size, cfg, "o_proj")(out)


def _normal_about(mean, std):
    def init(key, shape, dtype=jnp.float32):
        return mean + std * jax.random.normal(key, shape, dtype)
    return init


def _highest(x, w):
    return jnp.dot(x, w.astype(jnp.float32), precision="highest")


class ZayaMoE(nn.Module):
    """The routed experts held here behind the MLP router: u [B, S, H] and
    the previous layer's router state [B, S, R] (None: the first layer held)
    -> (this share's output [B, S, H], the walk's counters, which held
    expert each token chose [B, S, held], this layer's router state)."""

    config: Any

    @nn.compact
    def __call__(self, u, rho_prev=None):
        cfg = self.config
        B, S, H = u.shape
        held, f = cfg.experts, cfg.moe_intermediate_size
        r = cfg.router_hidden_size
        f32, normal = jnp.float32, nn.initializers.normal(0.02)
        ones = nn.initializers.ones

        def leaf(name, shape, init=normal):
            return self.param(name, init, shape, f32)

        tokens = u.reshape(B * S, H)
        with jax.named_scope("moe_router_mlp"):
            # float32 on every pass of the MXU: a top-1 flips on rounding
            rho = _highest(tokens.astype(f32), leaf(
                "router_down_kernel", (H, r))) + leaf("router_down_bias", (r,))
            if rho_prev is not None:
                gamma = leaf("router_gamma", (r,), _normal_about(0.5, 0.1))
                rho = rho + gamma * rho_prev.reshape(B * S, r)
            hidden = rms_norm(rho, leaf("router_norm_scale", (r,), ones),
                              eps=cfg.rms_norm_eps)
            for name in ("router_mlp_1", "router_mlp_2"):
                hidden = jax.nn.gelu(_highest(hidden, leaf(name, (r, r))),
                                     approximate=False)
            logits = _highest(hidden, leaf("router_mlp_3",
                                           (r, cfg.num_experts)))
        # the balancing bias chooses and takes no gradient; nothing here
        # updates it (the module docstring)
        beta = jax.lax.stop_gradient(leaf(
            "selection_bias", (cfg.num_experts,), nn.initializers.zeros))
        gate_up = leaf("experts_gate_up_proj", (held, H, 2 * f))
        down = leaf("experts_down_proj", (held, f, H))
        out, counters, is_chosen = dropless.dropless_moe(
            tokens, logits, gate_up, down, k=cfg.num_experts_per_tok,
            first_expert=cfg.first_expert_held, experts_held=held,
            selection_bias=beta, normalize=False,
            scoring=dropless.softmax_topk, activation=dropless.gated_silu)
        out = checkpoint_name(out.astype(u.dtype), MOE_OUT_SAVED_BY_REMAT)
        return (out.reshape(B, S, H), counters,
                is_chosen.reshape(B, S, held), rho.reshape(B, S, r))


class ZayaBlock(nn.Module):
    """A CCA sublayer and a routed one on the scaled residual stream: the
    stream and the previous layer's router state -> (the stream, what the
    routed walk counted and chose and the router's state, that state again:
    what the stack hands the next layer)."""

    KINDS = frozenset((HYBRID,))

    config: ZayaConfig
    kind: str = HYBRID

    @nn.compact
    def __call__(self, x, rho_prev=None):
        cfg = self.config
        h, f32 = cfg.hidden_size, jnp.float32
        ones, about_one = nn.initializers.ones, _normal_about(1.0, 0.1)
        small = nn.initializers.normal(0.02)
        x = maybe_constrain(x, (BATCH_AXES, "sp", None))
        with jax.named_scope("attention"):
            u = rms_norm(x, self.param("input_norm_scale", ones, (h,), f32),
                         eps=cfg.rms_norm_eps)
            y = ZayaAttention(cfg, name="attn")(u)
            x = fold(x, y,
                     self.param("attn_res_scale", about_one, (2, h), f32),
                     self.param("attn_res_bias", small, (2, h), f32))
        with jax.named_scope("mlp"):
            u = rms_norm(x, self.param("post_norm_scale", ones, (h,), f32),
                         eps=cfg.rms_norm_eps)
            y, counters, chosen, rho = ZayaMoE(cfg, name="moe")(u, rho_prev)
            x = fold(x, y,
                     self.param("mlp_res_scale", about_one, (2, h), f32),
                     self.param("mlp_res_bias", small, (2, h), f32))
        return (maybe_constrain(x, (BATCH_AXES, "sp", None)),
                {"counters": counters, "chosen": chosen, "router_state": rho},
                rho)


class Zaya(Decoder):
    """Causal LM: tokens [B, S] -> (the closing norm's output [B, S, H],
    what each layer said: the routed walk's counters, its chosen-here mask,
    its router's state).  The head is the table's transpose."""

    block_cls = ZayaBlock
    #: a recomputed layer keeps the flash kernel's residuals, the grouped
    #: walk's plan and the walk's output: each is made once a step
    saved_by_remat = Decoder.saved_by_remat + (
        dropless.PLAN_SAVED_BY_REMAT, MOE_OUT_SAVED_BY_REMAT)

    config: ZayaConfig

    def stack(self):
        cfg = self.config
        return Stack(kinds=cfg.kinds, rows=cfg.vocab_rows,
                     columns=cfg.vocab_rows, norm_eps=cfg.rms_norm_eps,
                     table_dtype=cfg.dtype, tied_head=True)

    def counters(self, batch, seq):
        layers = len(self.config.kinds)
        return {"cca_layer_applications": jnp.int32(layers),
                "moe_layer_applications": jnp.int32(layers)}

    @nn.nowrap
    def _report(self, told, shape):
        """The stack's report and the tokens whose ONE expert is not held
        here, the mean a layer: with even routing over two shares, half."""
        said = super()._report(told, shape)
        return {**said, "moe_tokens_unrouted_here":
                shape[0] * shape[1] - said["moe_slots_held"]}

    def router_states(self, params, input_ids):
        """Every layer's router state [layers, B, S, R], for a check."""
        _, told = self.apply({"params": params}, input_ids)
        return jnp.stack([t["router_state"] for t in told])

    def no_cast_paths(self):
        """Float32 under mixed precision: the table (its gradient is a
        scatter-add and the head's sum), every leaf of the router and its
        balancing bias (a top-1 flips on rounding), k's temperature and the
        residual scales."""
        return [r"embed_tokens/embedding", r"router_", r"selection_bias",
                r"k_temperature", r"_res_(scale|bias)"]

    def param_partition_rules(self):
        """Megatron-style tp placement of the latent's projections and the
        table; the routed experts are a range the layer is told."""
        return [
            (r"embed_tokens/embedding", P("tp", None)),
            (r"(q_proj|k_proj|v_proj)/kernel", P(None, "tp")),
            (r"o_proj/kernel", P("tp", None)),
        ]

    # ---------------------------------------------------------------- counts
    def layer_matmul_params(self):
        """Matmul weights a token passes in one layer outside its routed
        expert: the latent's five projections, the per-head convolution's
        matrices, the router's four."""
        cfg = self.config
        h, r, d = cfg.hidden_size, cfg.router_hidden_size, cfg.head_dim
        heads = cfg.num_heads + cfg.num_kv_heads
        return (2 * h * heads * d + cfg.cca_time1 * heads * d * d
                + h * r + 2 * r * r + r * cfg.num_experts)

    def routed_expert_params(self):
        cfg = self.config
        return 3 * cfg.hidden_size * cfg.moe_intermediate_size

    def num_params(self):
        """The tied table once; the first layer held has no ``gamma``."""
        cfg = self.config
        h, r, c = cfg.hidden_size, cfg.router_hidden_size, cfg.mixed_channels
        small = ((cfg.cca_time0 + 2) * c + cfg.num_kv_heads  # taps, biases,
                 + 10 * h          # tau; two norms, the residual's eight
                 + 3 * r + cfg.num_experts)        # b_D, gamma, norm; beta
        return (cfg.vocab_rows * h + h - r + len(cfg.kinds) * (
            self.layer_matmul_params() + small
            + cfg.experts * self.routed_expert_params()))

    def flops_per_token(self, slots_per_token=None):
        """Forward + backward FLOPs a trained token needs at the shares
        held: 6 x the matmul weights it passes (a routed expert counted per
        slot: what the step's counter says, or what even routing would send
        here; the table once, as the head), plus attention's scores and
        values in the latent over the causal half, ``6 n_q d S`` a layer.
        Recomputed operations do not count."""
        cfg = self.config
        if slots_per_token is None:
            slots_per_token = (cfg.num_experts_per_tok * cfg.experts
                               / cfg.num_experts)
        matmul = (len(cfg.kinds) * (
            self.layer_matmul_params()
            + slots_per_token * self.routed_expert_params())
            + cfg.hidden_size * cfg.vocab_rows)
        return 6 * matmul + len(cfg.kinds) * (
            6 * cfg.num_heads * cfg.head_dim * cfg.max_seq_len)
