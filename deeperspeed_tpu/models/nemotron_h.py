"""Nemotron-H: a hybrid causal LM whose depth is a PATTERN of three kinds of
layer (NVIDIA, ``model_type`` ``nemotron_h``; the preset is
NVIDIA-Nemotron-3-Super-120B-A12B).

Every layer is pre-norm with ONE mixer and no second sublayer, ``x <- x +
Mixer(RMSNorm(x))``, the mixer chosen by the layer's letter in
``pattern``:

* ``M`` -- Mamba-2: one in-projection to ``[z | x B C | dt]``, a causal
  depthwise convolution (+ bias, SiLU) over ``x B C``, the selective scan in
  its chunked SSD form (``ops/ssm.py``), a gated group-wise RMSNorm, the
  out-projection.
* ``E`` -- a latent mixture of experts: sigmoid scores over ALL
  ``n_routed_experts`` in float32, each token's top ``num_experts_per_tok``,
  normalised and scaled weights; the routed experts work in a latent space
  ``moe_latent_size`` wide (down-projection, squared-ReLU experts without a
  gate, up-projection) and a shared expert reads the layer's input beside
  them.  The layer is told which experts it holds (``first_expert``,
  ``experts_held``) and computes their part, dropless
  (``moe/dropless.py``).
* ``*`` -- grouped-query softmax attention, causal, no bias and no rotary
  (positions come from the Mamba layers).

The equations, and what the published ``config.json`` leaves to assumption,
are in ``benchmarks/reference/nemotron_h_ref.py``.  Multi-token prediction
(``num_nextn_predict_layers``) is not here: ROADMAP.md.

A chip's share.  The configuration counts what THIS chip holds: Mamba heads
with the B/C groups that serve them, query heads with their KV heads,
routed experts as a range, rows of the vocabulary.  Heads, groups and experts
are independent, so the shares' partial mixer outputs add up to the whole
layer's (the router, the latent projections and the shared expert are every
chip's alike and count once): ``tests/unit/models/test_nemotron_h.py``.

The stack, the routed layers' report, the head + loss and the rest of the
engine protocol are ``models/decoder.py``'s.  With layers of several kinds,
parameters, FLOPs and the named scopes all go by layer kind: ``ssm`` (with
``ssm_scan`` inside), ``mlp`` (with ``moe_route``, ``moe_experts``,
``moe_shared`` inside) and ``attention``.
"""

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..moe import dropless
from ..ops.attention.core import dot_product_attention
from ..ops.ssm import causal_depthwise_conv1d, gated_group_rms_norm, ssd_scan
from ..ops.transformer.normalize import rms_norm
from ..parallel.topology import BATCH_AXES
from .decoder import Decoder, Stack, _dense
from .gpt_neox import maybe_constrain

SUPER_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                 "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
KINDS = {"M": "ssm", "E": "mlp", "*": "attention"}


@dataclasses.dataclass(unsafe_hash=True)
class NemotronHConfig:
    """Published keys under their published names; every count is of what
    this chip holds (the whole model unless a share is given)."""
    vocab_size: int = 131072
    hidden_size: int = 4096
    pattern: str = SUPER_PATTERN          # one letter a layer: M, E or *
    norm_eps: float = 1e-5
    # M
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8                     # B/C groups; a group serves heads/groups heads
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # *
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    # E
    n_routed_experts: int = 512           # the router's width: never a share
    num_experts_per_tok: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    routed_scaling_factor: float = 5.0
    norm_topk_prob: bool = True
    first_expert: int = 0                 # the experts held here:
    experts_held: int = 512               # [first_expert, first_expert + held)
    # the run
    max_seq_len: int = 8192
    ce_chunk_tokens: int = 2048
    dtype: Any = jnp.float32
    remat: bool = False

    @property
    def mamba_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_channels(self):
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def in_proj_width(self):
        return self.mamba_inner + self.conv_channels + self.mamba_num_heads

    def layers(self, kind):
        return self.pattern.count(kind)

    @staticmethod
    def nemotron_3_super(**kw):
        """NVIDIA-Nemotron-3-Super-120B-A12B as published: 88 layers (40 M,
        40 E, 8 *); keyword arguments give a chip's share."""
        return NemotronHConfig(**kw)

    @staticmethod
    def tiny(**kw):
        small = dict(
            vocab_size=256, hidden_size=64, pattern="EM*M", mamba_num_heads=4,
            mamba_head_dim=8, n_groups=2, ssm_state_size=16, chunk_size=16,
            num_heads=4, num_kv_heads=2, head_dim=16, n_routed_experts=16,
            num_experts_per_tok=3, moe_intermediate_size=48,
            moe_latent_size=32, moe_shared_expert_intermediate_size=96,
            first_expert=4, experts_held=4,
            max_seq_len=64, ce_chunk_tokens=48)
        return NemotronHConfig(**dict(small, **kw))


def _uniform(bound):
    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(cfg):
    def init(key, shape, dtype=jnp.float32):
        lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, dtype, lo, hi)),
                         cfg.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))         # softplus's inverse
    return init


class MambaMixer(nn.Module):
    """Mamba-2 over the heads (and their groups) held here: u [B, S, H] ->
    this share's part of the mixer's output [B, S, H]."""

    config: NemotronHConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        B, S, _ = u.shape
        heads, p = cfg.mamba_num_heads, cfg.mamba_head_dim
        g, n, inner = cfg.n_groups, cfg.ssm_state_size, cfg.mamba_inner
        f32 = jnp.float32
        conv_w = self.param("conv1d_kernel", _uniform(cfg.conv_kernel ** -0.5),
                            (cfg.conv_kernel, cfg.conv_channels), f32)
        conv_b = self.param("conv1d_bias", _uniform(cfg.conv_kernel ** -0.5),
                            (cfg.conv_channels,), f32)
        a_log = self.param("A_log", _a_log_init, (heads,), f32)
        d_skip = self.param("D", nn.initializers.ones, (heads,), f32)
        dt_bias = self.param("dt_bias", _dt_bias_init(cfg), (heads,), f32)
        scale = self.param("norm_scale", nn.initializers.ones, (inner,), f32)

        zxbcdt = _dense(cfg.in_proj_width, cfg, "in_proj")(u)
        z, xbc, dt = jnp.split(zxbcdt, [inner, inner + cfg.conv_channels],
                               axis=-1)
        xbc = jax.nn.silu(causal_depthwise_conv1d(xbc, conv_w, conv_b))
        x, b, c = jnp.split(xbc, [inner, inner + g * n], axis=-1)
        # step sizes and decay rates in float32
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
        y = ssd_scan(x.reshape(B, S, heads, p), dt,
                     -jnp.exp(a_log.astype(f32)), b.reshape(B, S, g, n),
                     c.reshape(B, S, g, n), d_skip, cfg.chunk_size)
        y = gated_group_rms_norm(y.reshape(B, S, inner), z, scale, g,
                                 cfg.norm_eps)
        return _dense(cfg.hidden_size, cfg, "out_proj")(y)


class AttentionMixer(nn.Module):
    """Grouped-query causal attention over the query heads (and their KV
    heads) held here; no bias, no rotary."""

    config: NemotronHConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        B, S, _ = u.shape
        nq, kv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = _dense(nq * d, cfg, "q_proj")(u).reshape(B, S, nq, d)
        k = _dense(kv * d, cfg, "k_proj")(u).reshape(B, S, kv, d)
        v = _dense(kv * d, cfg, "v_proj")(u).reshape(B, S, kv, d)
        # k and v go at their KV heads: the kernel addresses them by the
        # query head's group and nothing copies them (``pallas_flash.mha``)
        out = dot_product_attention(q, k, v, causal=True)
        with jax.named_scope("attention_layout"):
            out = out.reshape(B, S, nq * d)
        return _dense(cfg.hidden_size, cfg, "o_proj")(out)


class LatentMoEMixer(nn.Module):
    """The routed experts held here, in their latent space, and the shared
    expert: u [B, S, H] -> (this share's output [B, S, H], the routed walk's
    counters, which held experts each token chose [B, S, held])."""

    config: NemotronHConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        B, S, H = u.shape
        held, lat, f = (cfg.experts_held, cfg.moe_latent_size,
                        cfg.moe_intermediate_size)
        router = self.param("router_kernel", nn.initializers.normal(0.02),
                            (H, cfg.n_routed_experts), jnp.float32)
        w_in = self.param("experts_up_proj", nn.initializers.normal(0.02),
                          (held, lat, f), jnp.float32)
        w_out = self.param("experts_down_proj", nn.initializers.normal(0.02),
                           (held, f, lat), jnp.float32)
        tokens = u.reshape(B * S, H)
        with jax.named_scope("moe_route"):
            # scores over all the experts, float32 on every pass of the MXU
            logits = jnp.dot(tokens.astype(jnp.float32),
                             router.astype(jnp.float32), precision="highest")
        latent = _dense(lat, cfg, "latent_down")(tokens)
        routed, counters, is_chosen = dropless.dropless_moe(
            latent, logits, w_in, w_out, k=cfg.num_experts_per_tok,
            first_expert=cfg.first_expert, experts_held=held,
            normalize=cfg.norm_topk_prob, scale=cfg.routed_scaling_factor)
        out = _dense(H, cfg, "latent_up")(routed.astype(cfg.dtype))
        with jax.named_scope("moe_shared"):
            out = out + _dense(H, cfg, "shared_down")(dropless.relu2(_dense(
                cfg.moe_shared_expert_intermediate_size, cfg, "shared_up")(
                    tokens)))
        return (out.reshape(B, S, H), counters,
                is_chosen.reshape(B, S, held))


class NemotronHBlock(nn.Module):
    """``x + Mixer(RMSNorm(x))`` for one letter of the pattern -> (x, what
    an E layer's routed walk counted, else nothing)."""

    #: the letters of ``NemotronHConfig.pattern``
    KINDS = frozenset(KINDS)

    config: NemotronHConfig
    kind: str = "M"

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        x = maybe_constrain(x, (BATCH_AXES, "sp", None))
        told = {}
        with jax.named_scope(KINDS[self.kind]):
            scale = self.param("norm_scale", nn.initializers.ones,
                               (cfg.hidden_size,), jnp.float32)
            # in the stream's own type: the first layer reads the float32
            # embedding (``NemotronH.stack``), every later one the compute
            # type
            u = rms_norm(x, scale, eps=cfg.norm_eps)
            if self.kind == "M":
                y = MambaMixer(cfg, name="mixer")(u)
            elif self.kind == "*":
                y = AttentionMixer(cfg, name="mixer")(u)
            else:
                y, counters, chosen = LatentMoEMixer(cfg, name="mixer")(u)
                told = {"counters": counters, "chosen": chosen}
            x = (x + y.astype(x.dtype)).astype(cfg.dtype)
        return maybe_constrain(x, (BATCH_AXES, "sp", None)), told


class NemotronH(Decoder):
    """Hybrid causal LM: tokens [B, S] -> (the closing norm's output
    [B, S, H], each E layer's counters and chosen-here mask)."""

    block_cls = NemotronHBlock

    config: NemotronHConfig

    def stack(self):
        cfg = self.config
        # the table's rows reach the first layer in float32 (``Stack``'s
        # default) and the stream takes the compute type with that layer's
        # output.  Where the first layer is an expert layer it routes on
        # raw embeddings: every token of one id has the same scores, so a
        # rounding that swaps an id's 22nd and 23rd expert moves ALL its
        # tokens at once (a hot id is a tenth of a batch); float32 scores of
        # a float32 input keep the choice the float32 arithmetic's (PERF.md,
        # PR 34)
        return Stack(kinds=tuple(cfg.pattern), rows=cfg.vocab_size,
                     columns=cfg.vocab_size, norm_eps=cfg.norm_eps)

    def counters(self, batch, seq):
        cfg = self.config
        return {"ssm_layer_applications": jnp.int32(cfg.layers("M")),
                "attention_layer_applications": jnp.int32(cfg.layers("*")),
                "moe_layer_applications": jnp.int32(cfg.layers("E"))}

    def none_chosen(self, shape):
        return jnp.zeros((0,) + shape + (self.config.experts_held,), bool)

    def no_cast_paths(self):
        """Float32 under mixed precision: the embedding table (its gradient
        is a scatter-add), the router (top-k of 512 flips on rounding) and
        the scan's per-head decay parameters."""
        return [r"embed_tokens/embedding", r"router_kernel", r"A_log",
                r"dt_bias", r"mixer/D$"]

    def param_partition_rules(self):
        """Megatron-style tp placement of the attention and shared-expert
        matrices and of the tables.  The Mamba in-projection is one matrix
        of three sections and the routed experts are a range the layer is
        told: both are divided by giving a chip its share in the
        configuration, not by a rule here."""
        return [
            (r"embed_tokens/embedding", P("tp", None)),
            (r"(q_proj|k_proj|v_proj|shared_up)/kernel", P(None, "tp")),
            (r"(o_proj|shared_down)/kernel", P("tp", None)),
            (r"lm_head_kernel", P(None, "tp")),
        ]

    # ------------------------------------------------- counts, by layer kind
    def layer_matmul_params(self, kind):
        """Matmul weights a token passes in one layer of ``kind``; for E
        without its routed experts (``routed_expert_params`` each)."""
        cfg = self.config
        h = cfg.hidden_size
        if kind == "M":
            return h * cfg.in_proj_width + cfg.mamba_inner * h
        if kind == "*":
            return 2 * h * (cfg.num_heads + cfg.num_kv_heads) * cfg.head_dim
        return (h * cfg.n_routed_experts + 2 * h * cfg.moe_latent_size
                + 2 * h * cfg.moe_shared_expert_intermediate_size)

    def routed_expert_params(self):
        cfg = self.config
        return 2 * cfg.moe_latent_size * cfg.moe_intermediate_size

    def num_params(self):
        cfg = self.config
        h = cfg.hidden_size
        other = {"M": (cfg.conv_kernel + 1) * cfg.conv_channels
                 + 3 * cfg.mamba_num_heads + cfg.mamba_inner,
                 "E": cfg.experts_held * self.routed_expert_params(),
                 "*": 0}
        return (2 * cfg.vocab_size * h + h + sum(
            cfg.layers(k) * (self.layer_matmul_params(k) + other[k] + h)
            for k in KINDS))

    def scan_flops_per_token(self):
        """Forward FLOPs a token needs in one M layer's convolution and
        chunked scan: per head the chunk's masked product (2 Q P), the state
        it pushes and the state it reads (2 P N each); per group the scores
        (2 Q N); the convolution's K multiply-adds a channel."""
        cfg = self.config
        q, p, n = cfg.chunk_size, cfg.mamba_head_dim, cfg.ssm_state_size
        return (cfg.mamba_num_heads * (2 * q * p + 4 * p * n)
                + cfg.n_groups * 2 * q * n
                + 2 * cfg.conv_kernel * cfg.conv_channels)

    def flops_per_token(self, slots_per_token=None):
        """Forward + backward FLOPs a trained token needs: 6 x the matmul
        weights it passes, by layer kind, with a routed expert counted per
        slot (``slots_per_token`` a layer: what the step's counter says, or
        what even routing would send here), plus the scan's and attention's
        own terms.  Recomputed operations do not count."""
        cfg = self.config
        if slots_per_token is None:
            slots_per_token = (cfg.num_experts_per_tok * cfg.experts_held
                               / cfg.n_routed_experts)
        matmul = (sum(cfg.layers(k) * self.layer_matmul_params(k)
                      for k in KINDS)
                  + cfg.layers("E") * slots_per_token
                  * self.routed_expert_params()
                  + cfg.hidden_size * cfg.vocab_size)
        return (6 * matmul + 3 * cfg.layers("M") * self.scan_flops_per_token()
                + 12 * cfg.layers("*") * cfg.num_heads * cfg.head_dim
                * cfg.max_seq_len)
