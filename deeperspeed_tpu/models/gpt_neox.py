"""GPT-NeoX / Pythia model family, TPU-native.

The reference framework wraps externally-defined GPT-NeoX models
(Megatron-style, see SURVEY.md §2.5); here the architecture is in-tree so
milestone configs (Pythia-160M ... NeoX-20B, ``BASELINE.json``) run
self-contained.  Faithful to the NeoX computation: rotary embeddings with
``rotary_pct``, parallel attention+MLP residual, untied output embedding,
LayerNorm (not RMS).

Tensor parallelism is expressed as param partition rules over the ``tp``
mesh axis (Megatron column/row pattern); sequence activations carry ``sp``
sharding constraints.  XLA/GSPMD inserts the collectives.
"""

import dataclasses
import re
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.attention import dot_product_attention
from ..ops.attention.pallas_flash import SAVED_BY_REMAT
from ..ops.transformer.cross_entropy import mean_linear_cross_entropy
from ..parallel.topology import BATCH_AXES



def maybe_constrain(x, spec):
    """Sharding constraint against the global mesh (see ``topology.constrain``)."""
    from ..parallel.topology import constrain

    return constrain(x, spec)


@dataclasses.dataclass(frozen=True)
class GPTNeoXConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 2048
    rotary_pct: float = 0.25
    rotary_emb_base: int = 10000
    use_parallel_residual: bool = True
    layernorm_eps: float = 1e-5
    hidden_dropout: float = 0.0
    attention_dropout: float = 0.0
    dtype: Any = jnp.float32
    remat: bool = False
    # chunked fused-linear cross entropy: compute the head GEMM + CE over
    # token chunks of this many tokens inside a scan (0 = monolithic).
    # The full [B, S, vocab] logits never exist in HBM -- at bench shapes
    # that tensor plus its fp32 cast round-trip dominate the HBM-bound
    # epilogue; backward recomputes each chunk's logits (jax.checkpoint),
    # trading ~1 extra head-GEMM pass for the logits traffic.
    ce_chunk_tokens: int = 0
    # fused Pallas layernorm kernels (auto-dispatch; False forces plain XLA)
    fused_norms: bool = True
    # sequence/context parallelism over the sp mesh axis:
    #   None      attention on seq-sharded activations (XLA gathers K/V)
    #   "ulysses" all-to-all head-scatter/seq-gather (ref sequence/layer.py)
    #   "ring"    blockwise ring attention (K/V ppermute ring over ICI)
    seq_parallel_mode: Optional[str] = None
    # μP width multiplier relative to a base width (for mu-optimizers)
    mup_base_width: Optional[int] = None
    # paged KV cache geometry (inference v2 ragged serving; 0 = unpaged)
    paged_num_blocks: int = 0
    paged_block_size: int = 64
    # "" = pool in compute dtype; "int8" / "fp8" (e4m3) = block-scaled pool
    # with per-(slot, head) fp32 scales (quantize-on-write, fused
    # dequant-attend)
    paged_kv_dtype: str = ""
    # MoE (0/1 experts = dense). MoE replaces the MLP on every
    # ``moe_expert_interval``-th block (layers 1, 3, ... for interval 2).
    moe_num_experts: int = 0
    moe_expert_interval: int = 2
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.0
    moe_eval_capacity_factor: float = 1.0
    moe_min_capacity: int = 4
    moe_use_residual: bool = False
    moe_noisy_gate_policy: Optional[str] = None
    moe_drop_tokens: bool = True
    moe_use_rts: bool = True
    moe_aux_loss_coef: float = 0.01
    # 1-byte tokens + per-block scales on the dispatch all-to-all wire
    # (set from the runtime ``comm.quantized.moe_alltoall`` config key;
    # dtype: int8 or fp8 -> e4m3)
    moe_quantized_alltoall: bool = False
    moe_quantized_group_size: int = 128
    moe_quantized_alltoall_dtype: str = "int8"

    @property
    def has_moe(self):
        return self.moe_num_experts > 1

    def moe_layer_indices(self):
        return [i for i in range(self.num_layers)
                if self.has_moe and (i + 1) % self.moe_expert_interval == 0]

    def __post_init__(self):
        if self.seq_parallel_mode not in (None, "none", "ulysses", "ring"):
            raise ValueError(
                f"unknown seq_parallel_mode {self.seq_parallel_mode!r}; "
                f"expected None, 'ulysses' or 'ring'")
        assert self.hidden_size % self.num_heads == 0

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads

    @property
    def intermediate_size(self):
        return 4 * self.hidden_size

    # ---- canonical family presets (EleutherAI Pythia / NeoX sizes)
    @staticmethod
    def pythia_160m(**kw):
        return GPTNeoXConfig(hidden_size=768, num_layers=12, num_heads=12, **kw)

    @staticmethod
    def pythia_410m(**kw):
        return GPTNeoXConfig(hidden_size=1024, num_layers=24, num_heads=16, **kw)

    @staticmethod
    def pythia_1_4b(**kw):
        return GPTNeoXConfig(hidden_size=2048, num_layers=24, num_heads=16, **kw)

    @staticmethod
    def pythia_6_9b(**kw):
        return GPTNeoXConfig(hidden_size=4096, num_layers=32, num_heads=32, **kw)

    @staticmethod
    def neox_20b(**kw):
        return GPTNeoXConfig(hidden_size=6144, num_layers=44, num_heads=64,
                             vocab_size=50432, **kw)

    @staticmethod
    def tiny(**kw):
        kw.setdefault("vocab_size", 256)
        kw.setdefault("max_seq_len", 64)
        return GPTNeoXConfig(hidden_size=64, num_layers=2, num_heads=4, **kw)


# rotary math is the canonical op implementation (ops/transformer/rope.py)
from ..ops.transformer.rope import apply_rotary_pos_emb, rotary_tables  # noqa: E402


class ModelLayerNorm(nn.Module):
    """LayerNorm with the same param names as ``nn.LayerNorm`` (checkpoint
    compatible) dispatching to the fused Pallas kernel on TPU.  ``fused=False``
    forces the plain XLA path (same math, fp32 statistics either way)."""

    epsilon: float = 1e-5
    dtype: Any = jnp.float32
    fused: bool = True

    @nn.compact
    def __call__(self, x):
        from ..ops.transformer.normalize import layer_norm

        h = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (h,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (h,), jnp.float32)
        return layer_norm(x.astype(self.dtype), scale, bias, eps=self.epsilon,
                          use_pallas=None if self.fused else False)


class GPTNeoXAttention(nn.Module):
    config: GPTNeoXConfig
    decode: bool = False  # autoregressive KV-cache mode (inference engine)
    paged: bool = False   # blocked/paged KV pool mode (inference v2 ragged)

    @nn.compact
    def __call__(self, x, positions, deterministic=True, attention_mask=None,
                 paged_state=None):
        cfg = self.config
        B, S, H = x.shape
        qkv = nn.Dense(3 * H, dtype=cfg.dtype, name="query_key_value")(x)
        with jax.named_scope("attention_layout"):
            qkv = qkv.reshape(B, S, cfg.num_heads, 3 * cfg.head_dim)
            q, k, v = jnp.split(qkv, 3, axis=-1)

        rot_dim = int(cfg.head_dim * cfg.rotary_pct)
        if rot_dim > 0:
            cos, sin = rotary_tables(positions, rot_dim, cfg.rotary_emb_base, cfg.dtype)
            q, k = apply_rotary_pos_emb(q, k, cos, sin)

        if self.paged:
            out = self._paged_attention(q, k, v, positions, paged_state)
            if out is not None:
                out = out.reshape(B, S, H)
                return nn.Dense(H, dtype=cfg.dtype, name="dense")(out)
            # cache-init trace: fall through to plain causal attention

        if self.decode:
            # Flax-style autoregressive cache: fixed [B, max_len, N, D] K/V
            # buffers + a scalar write index.  Replaces the reference's
            # inference KV-cache workspace (``csrc/transformer/inference``,
            # allocated in ``pt_binding.cpp``) with functional cache state
            # threaded through jit.  Works for both prefill (S>1 at idx 0)
            # and single-token decode (S=1).
            is_init = self.has_variable("cache", "cached_key")
            max_len = cfg.max_seq_len
            cached_key = self.variable(
                "cache", "cached_key", jnp.zeros,
                (B, max_len, cfg.num_heads, cfg.head_dim), k.dtype)
            cached_value = self.variable(
                "cache", "cached_value", jnp.zeros,
                (B, max_len, cfg.num_heads, cfg.head_dim), v.dtype)
            cache_index = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((), jnp.int32))
            if is_init:
                idx = cache_index.value
                k = jax.lax.dynamic_update_slice(cached_key.value, k, (0, idx, 0, 0))
                v = jax.lax.dynamic_update_slice(cached_value.value, v, (0, idx, 0, 0))
                cached_key.value = k
                cached_value.value = v
                cache_index.value = idx + S
                # buffer-index causal mask; attention_mask is the key-validity
                # mask over the full cache buffer [B, max_len]
                q_pos = idx + jnp.arange(S)
                mask = jnp.arange(max_len)[None, :] <= q_pos[:, None]  # [S, max_len]
                mask = mask[None, None]
                if attention_mask is not None:
                    mask = mask & attention_mask[:, None, None, :].astype(bool)
                out = dot_product_attention(q, k, v, mask=mask, causal=False)
                out = out.reshape(B, S, H)
                return nn.Dense(H, dtype=cfg.dtype, name="dense")(out)
            # cache init trace: fall through to plain causal attention

        mask = None
        if attention_mask is not None:
            # key-padding mask [B, S_k] composed with the causal mask
            mask = attention_mask[:, None, None, :].astype(bool)

        dropout_rng = None
        if cfg.attention_dropout > 0.0 and not deterministic:
            dropout_rng = self.make_rng("dropout")
        if cfg.seq_parallel_mode == "ring" and dropout_rng is not None:
            raise NotImplementedError(
                "ring attention does not support attention_dropout; use "
                "seq_parallel_mode='ulysses' or hidden_dropout instead")
        if attention_mask is not None and cfg.seq_parallel_mode in ("ulysses", "ring"):
            raise NotImplementedError(
                "attention_mask (padded batches) is not supported with "
                f"seq_parallel_mode={cfg.seq_parallel_mode!r}; pad-free packed "
                "sequences are the supported long-context input format")
        if cfg.seq_parallel_mode == "ulysses":
            from ..sequence.layer import ulysses_attention

            out = ulysses_attention(
                dot_product_attention, q, k, v, causal=True,
                dropout_rng=dropout_rng,
                dropout_rate=0.0 if deterministic else cfg.attention_dropout,
            )
        elif cfg.seq_parallel_mode == "ring":
            from ..sequence.ring import ring_attention_sharded

            out = ring_attention_sharded(q, k, v, causal=True)
        else:
            out = dot_product_attention(
                q, k, v, mask=mask, causal=True, dropout_rng=dropout_rng,
                dropout_rate=0.0 if deterministic else cfg.attention_dropout,
            )
        with jax.named_scope("attention_layout"):
            out = out.reshape(B, S, H)
        return nn.Dense(H, dtype=cfg.dtype, name="dense")(out)

    def _paged_attention(self, q, k, v, positions, paged_state):
        """Blocked KV-pool attention (inference v2 FastGen analog).

        TPU-native equivalent of the reference's blocked flash attention over
        a paged KV cache (``inference/v2/kernels/ragged_ops``,
        ``v2/ragged/kv_cache.py:40``): each layer owns a
        ``[num_blocks, block_size, N, D]`` K/V pool; ``paged_state`` carries

        * ``block_tables`` [B, max_blocks]  per-sequence block ids
        * ``write_mask``   [B, S]  which incoming tokens are real (scatter
          of pad/inactive tokens is dropped)

        ``positions`` are absolute token positions: they address the pool
        slot (block_tables[pos // bs] * bs + pos % bs) AND drive rotary.
        Writes happen before reads, so a token attends to itself; stale data
        in reallocated blocks is excluded by the pos-based causal mask.
        Returns None during the cache-init trace.

        Long-context two-pass protocol (``inference/v2/longctx.py``) rides
        on three optional keys:

        * ``attn_override`` [B, S, N, D]: the host already combined this
          layer's attention over resident + streamed KV partials -- inject
          it and run the rest of the block unchanged (checked FIRST, so the
          override pass touches no cache state; KV was committed by the
          capture pass).
        * ``write_flat``    [B, S] int32: precomputed pool-row indices for
          the KV scatter, replacing the table lookup -- a partial resident
          table cannot be indexed by ``pos // bs``.
        * ``attn_partial``  (static bool): capture pass -- commit KV to the
          pool, sow the post-rope queries as ``intermediates/attn_q`` and
          return zeros; the caller computes attention itself
          (``ops/attention/paged.py`` partial ops) and re-enters with
          ``attn_override``.
        """
        cfg = self.config
        assert cfg.paged_num_blocks > 0, "set config.paged_num_blocks for paged mode"
        override = None if paged_state is None else paged_state.get("attn_override")
        if override is not None:
            return override.astype(q.dtype)
        B, S = q.shape[:2]
        bs = cfg.paged_block_size
        quant_kv = bool(cfg.paged_kv_dtype)
        shape = (cfg.paged_num_blocks, bs, cfg.num_heads, cfg.head_dim)
        if quant_kv:
            from ..quantization import wire_dtype

            pool_dtype = wire_dtype(cfg.paged_kv_dtype)
        else:
            pool_dtype = k.dtype
        is_init = self.has_variable("cache", "paged_key")
        pk = self.variable("cache", "paged_key", jnp.zeros, shape, pool_dtype)
        pv = self.variable("cache", "paged_value", jnp.zeros, shape, pool_dtype)
        if quant_kv:
            # per-(slot, head) fp32 scales, blockwise alongside the pool
            psk = self.variable("cache", "paged_key_scale", jnp.zeros,
                                shape[:3], jnp.float32)
            psv = self.variable("cache", "paged_value_scale", jnp.zeros,
                                shape[:3], jnp.float32)
        if not is_init:
            return None
        block_tables = paged_state.get("block_tables")  # [B, max_blocks] int32
        write_mask = paged_state["write_mask"]      # [B, S] bool

        write_flat = paged_state.get("write_flat")
        if write_flat is not None:
            flat = jnp.asarray(write_flat, jnp.int32)
        else:
            slot = jnp.take_along_axis(block_tables, positions // bs, axis=1)
            flat = slot * bs + positions % bs       # [B, S] into pool rows
        # dropped writes need a *positive* OOB sentinel: jax wraps negative
        # indices (idx+size) before mode="drop" ever sees them
        oob = cfg.paged_num_blocks * bs
        flat = jnp.where(write_mask, flat, oob)
        N, D = cfg.num_heads, cfg.head_dim
        # the pool update: ``kv_scatter`` in a device trace
        with jax.named_scope("kv_scatter"):
            if quant_kv:
                # quantize-on-write: the pool never holds fp values
                from ..ops.quantizer import quantize_kv

                k, k_scale = quantize_kv(k, cfg.paged_kv_dtype)
                v, v_scale = quantize_kv(v, cfg.paged_kv_dtype)
                pool_sk = psk.value.reshape(-1, N).at[flat.reshape(-1)].set(
                    k_scale.reshape(-1, N), mode="drop")
                pool_sv = psv.value.reshape(-1, N).at[flat.reshape(-1)].set(
                    v_scale.reshape(-1, N), mode="drop")
                psk.value = pool_sk.reshape(shape[:3])
                psv.value = pool_sv.reshape(shape[:3])
            pool_k = pk.value.reshape(-1, N, D).at[flat.reshape(-1)].set(
                k.reshape(-1, N, D), mode="drop")
            pool_v = pv.value.reshape(-1, N, D).at[flat.reshape(-1)].set(
                v.reshape(-1, N, D), mode="drop")
            pk.value = pool_k.reshape(shape)
            pv.value = pool_v.reshape(shape)

        if paged_state.get("attn_partial", False):
            # capture pass: KV is committed above; attention itself runs as
            # host-combined partials over resident + streamed segments
            self.sow("intermediates", "attn_q", q)
            return jnp.zeros_like(q)

        if S == 1:
            # decode: Pallas paged kernel touches only the live blocks
            # (reference blocked flash decode, ``inference/v2/kernels/
            # ragged_ops``); the dense gather below would materialize
            # [B, max_blocks*bs, N, D] every layer.  Quantized pools
            # (int8 / fp8) dequantize INSIDE the kernel's block walk
            # (scales ride as extra VMEM operands) -- no fp cache copy
            # ever exists
            from ..ops.attention.paged import paged_decode_attention

            out = paged_decode_attention(
                q[:, 0], pk.value, pv.value, block_tables,
                positions[:, 0] + 1,
                k_scale=psk.value if quant_kv else None,
                v_scale=psv.value if quant_kv else None)
            return out[:, None].astype(q.dtype)
        if S <= 8:
            # speculative decode / short chunk: k+1 query tokens still walk
            # only the live blocks (one walk verifies all k drafts); per-
            # query causality comes from absolute positions, so garbage in
            # never-committed draft-tail slots is masked out next round
            from ..ops.attention.paged import paged_spec_decode_attention

            out = paged_spec_decode_attention(
                q, pk.value, pv.value, block_tables, positions,
                k_scale=psk.value if quant_kv else None,
                v_scale=psv.value if quant_kv else None)
            return out.astype(q.dtype)
        # prefill: attention over the gathered blocks
        with jax.named_scope("prefill_gather"):
            # -> [B, max_blocks*bs, N, D]
            K = pool_k.reshape(shape)[block_tables].reshape(B, -1, N, D)
            V = pool_v.reshape(shape)[block_tables].reshape(B, -1, N, D)
            if quant_kv:
                from ..ops.quantizer import dequantize_kv

                K = dequantize_kv(K, pool_sk.reshape(shape[:3])[
                    block_tables].reshape(B, -1, N), q.dtype)
                V = dequantize_kv(V, pool_sv.reshape(shape[:3])[
                    block_tables].reshape(B, -1, N), q.dtype)
            kv_pos = jnp.arange(K.shape[1])
            mask = kv_pos[None, None, None, :] <= positions[:, None, :, None]
            return dot_product_attention(q, K, V, mask=mask, causal=False)


class GPTNeoXMLP(nn.Module):
    config: GPTNeoXConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        h = nn.Dense(cfg.intermediate_size, dtype=cfg.dtype, name="dense_h_to_4h")(x)
        h = nn.gelu(h, approximate=True)
        return nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="dense_4h_to_h")(h)


class GPTNeoXBlock(nn.Module):
    config: GPTNeoXConfig
    use_moe: bool = False
    decode: bool = False
    paged: bool = False

    def _mlp(self, h, deterministic):
        cfg = self.config
        if not self.use_moe:
            return GPTNeoXMLP(cfg, name="mlp")(h)
        from ..moe.layer import MoE

        out, l_aux, _ = MoE(
            hidden_size=cfg.hidden_size, num_experts=cfg.moe_num_experts,
            ffn_dim=cfg.intermediate_size, k=cfg.moe_top_k,
            capacity_factor=cfg.moe_capacity_factor,
            eval_capacity_factor=cfg.moe_eval_capacity_factor,
            min_capacity=cfg.moe_min_capacity,
            use_residual=cfg.moe_use_residual,
            noisy_gate_policy=cfg.moe_noisy_gate_policy,
            drop_tokens=cfg.moe_drop_tokens, use_rts=cfg.moe_use_rts,
            quantized_alltoall=cfg.moe_quantized_alltoall,
            quantized_group_size=cfg.moe_quantized_group_size,
            quantized_alltoall_dtype=cfg.moe_quantized_alltoall_dtype,
            dtype=cfg.dtype, name="moe",
        )(h, train=not deterministic)
        self.sow("losses", "moe_aux", l_aux.astype(jnp.float32))
        return out

    @nn.compact
    def __call__(self, x, positions, deterministic=True, attention_mask=None,
                 paged_state=None):
        cfg = self.config
        x = maybe_constrain(x, (BATCH_AXES, "sp", None))
        # the scopes ``attention`` and ``mlp`` (each sublayer with its norm)
        # are what a device trace is read by: PERF.md section 3
        with jax.named_scope("attention"):
            attn_out = GPTNeoXAttention(
                cfg, decode=self.decode, paged=self.paged, name="attention")(
                ModelLayerNorm(epsilon=cfg.layernorm_eps, dtype=cfg.dtype,
                               fused=cfg.fused_norms, name="input_layernorm")(x),
                positions, deterministic=deterministic,
                attention_mask=attention_mask, paged_state=paged_state)
        if not cfg.use_parallel_residual:
            x = x + attn_out
        with jax.named_scope("mlp"):
            mlp_out = self._mlp(
                ModelLayerNorm(epsilon=cfg.layernorm_eps, dtype=cfg.dtype,
                               fused=cfg.fused_norms, name="post_attention_layernorm")(x), deterministic)
        if cfg.use_parallel_residual:
            x = x + attn_out + mlp_out
        else:
            x = x + mlp_out
        if cfg.hidden_dropout > 0.0 and not deterministic:
            x = nn.Dropout(cfg.hidden_dropout)(x, deterministic=False)
        return maybe_constrain(x, (BATCH_AXES, "sp", None))


class GPTNeoX(nn.Module):
    """Causal LM: tokens [B, S] -> logits [B, S, V]."""

    config: GPTNeoXConfig
    decode: bool = False
    paged: bool = False

    @nn.compact
    def __call__(self, input_ids, deterministic=True, positions=None,
                 attention_mask=None, paged_state=None, pld_theta=None,
                 random_ltd_tokens=None, logits_positions=None,
                 return_hidden=False):
        cfg = self.config
        B, S = input_ids.shape
        L = cfg.num_layers
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        # f32 lookup + downcast: embedding grads accumulate via scatter-add,
        # which wants f32 (and bf16 scatter aborts XLA:CPU under shard_map)
        with jax.named_scope("embed"):
            x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=jnp.float32,
                         name="embed_in")(input_ids).astype(cfg.dtype)
        block = GPTNeoXBlock
        if cfg.remat:
            # a recomputed block keeps the flash kernel's own two residuals
            # (its output and a float a row of lse: one more [B, S, H] a
            # layer), so the backward pass does not run the forward kernel
            # again; everything else of the block is recomputed as before
            block = nn.remat(
                GPTNeoXBlock, static_argnums=(3,),
                policy=jax.checkpoint_policies.save_only_these_names(
                    *SAVED_BY_REMAT))
        moe_layers = set(cfg.moe_layer_indices())
        for i in range(L):
            blk = block(cfg, use_moe=i in moe_layers, decode=self.decode,
                        paged=self.paged, name=f"layers_{i}")
            # random-LTD (reference data_routing/basic_layer.py + csrc/
            # random_ltd): middle layers process a random token subset
            use_ltd = (random_ltd_tokens is not None and not deterministic
                       and 0 < random_ltd_tokens < S and 0 < i < L - 1)
            if use_ltd:
                from ..runtime.data_pipeline.data_routing.basic_layer import (
                    random_ltd_gather, random_ltd_scatter)

                sub, idx = random_ltd_gather(
                    x, random_ltd_tokens,
                    jax.random.fold_in(self.make_rng("ltd"), i))
                sub_pos = jnp.take_along_axis(positions, idx, axis=1)
                y_sub = blk(sub, sub_pos, deterministic, None, paged_state)
                y = random_ltd_scatter(x, y_sub, idx)
            else:
                y = blk(x, positions, deterministic, attention_mask, paged_state)
            # progressive layer drop (reference progressive_layer_drop.py:40):
            # block i survives with prob 1 - (i+1)/L * (1 - theta_t)
            if pld_theta is not None and not deterministic and i > 0:
                keep_p = 1.0 - ((i + 1) / L) * (1.0 - pld_theta)
                keep = jax.random.bernoulli(
                    jax.random.fold_in(self.make_rng("pld"), i), keep_p)
                y = jnp.where(keep, y, x)
            x = y
        with jax.named_scope("head_ce"):    # the head, from its norm on
            x = ModelLayerNorm(epsilon=cfg.layernorm_eps, dtype=cfg.dtype,
                               fused=cfg.fused_norms,
                               name="final_layer_norm")(x)
        if return_hidden:
            # chunked-CE path: the caller owns the head projection
            return x
        if logits_positions is not None:
            # ragged logits-gather (reference inference/v2 ragged_ops
            # logits_gather kernel): project ONLY each row's requested
            # positions -- [B, R, V] instead of a [B, S, V] buffer the
            # caller would discard most of.  [B] gathers one position per
            # row (decode); [B, R] gathers the R trailing positions a
            # speculative round verifies in one dispatch.
            lp = jnp.asarray(logits_positions, jnp.int32)
            if lp.ndim == 1:
                lp = lp[:, None]
            x = jnp.take_along_axis(x, lp[..., None], axis=1)
        with jax.named_scope("head_ce"):
            logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                              name="embed_out")(x)
        return logits

    # ------------------------------------------------------------ engine API
    def example_batch(self, batch_size=2, seq_len=None, seed=0):
        seq = seq_len or min(self.config.max_seq_len, 128)
        key = jax.random.PRNGKey(seed)
        toks = jax.random.randint(key, (batch_size, seq + 1), 0, self.config.vocab_size)
        return {"input_ids": toks[:, :-1], "labels": toks[:, 1:]}

    def loss_fn(self):
        cfg = self.config

        def _apply_setup(batch, rng, deterministic, random_ltd_tokens):
            """Shared preamble of both loss closures: one definition of
            the rng streams + engine-injected kwargs, so the chunked and
            monolithic paths cannot drift."""
            # train passes an rng -> stochastic (dropout on); eval passes
            # rng=None -> deterministic. Explicit flag overrides.
            if deterministic is None:
                deterministic = rng is None
            rngs = None
            if rng is not None:
                rngs = {"dropout": rng, "gate": jax.random.fold_in(rng, 17),
                        "pld": jax.random.fold_in(rng, 23),
                        "ltd": jax.random.fold_in(rng, 29)}
            # data-efficiency extras injected by the engine
            kwargs = {"pld_theta": batch.get("pld_theta"),
                      "random_ltd_tokens": random_ltd_tokens}
            return deterministic, rngs, kwargs

        def loss(params, batch, rng=None, model=self, deterministic=None,
                 random_ltd_tokens=None):
            deterministic, rngs, kwargs = _apply_setup(
                batch, rng, deterministic, random_ltd_tokens)
            aux = 0.0
            if cfg.has_moe:
                logits, mutated = model.apply(
                    {"params": params}, batch["input_ids"],
                    deterministic=deterministic, rngs=rngs, mutable=["losses"],
                    **kwargs)
                moe_losses = jax.tree_util.tree_leaves(mutated.get("losses", {}))
                if moe_losses:
                    aux = cfg.moe_aux_loss_coef * sum(moe_losses) / len(moe_losses)
            else:
                logits = model.apply({"params": params}, batch["input_ids"],
                                     deterministic=deterministic, rngs=rngs,
                                     **kwargs)
            labels = batch["labels"]
            with jax.named_scope("head_ce"):   # the head GEMM is in it too
                logits = logits.astype(jnp.float32)
                # ce = logsumexp - gold logit: identical math to
                # log_softmax + gather, but never materializes the [B, S, V]
                # fp32 log-prob tensor (a ~3 GB HBM round-trip per microbatch
                # at bench shapes)
                lse = jax.nn.logsumexp(logits, axis=-1)
                gold = jnp.take_along_axis(logits, labels[..., None],
                                           axis=-1)[..., 0]
                token_ll = gold - lse
                mask = batch.get("loss_mask", jnp.ones_like(token_ll))
                ce = -jnp.sum(token_ll * mask) / jnp.maximum(jnp.sum(mask),
                                                              1.0)
            return ce + aux

        def loss_chunked(params, batch, rng=None, model=self,
                         deterministic=None, random_ltd_tokens=None):
            """Chunked fused-linear CE (``ce_chunk_tokens`` > 0): the step
            is HBM-bound at bench shapes (XLA cost analysis: 75 GB
            accessed vs 12 TFLOPs -- PROFILE.md round 5), and the single
            largest tensor is the [B, S, V] logits + fp32 cast.  Head + CE
            run chunk by chunk (``ops/transformer/cross_entropy.py``), the
            tokens weighted ``-mask / count`` so that the walk makes the
            head's gradient too."""
            deterministic, rngs, kwargs = _apply_setup(
                batch, rng, deterministic, random_ltd_tokens)
            hidden = model.apply({"params": params}, batch["input_ids"],
                                 deterministic=deterministic, rngs=rngs,
                                 return_hidden=True, **kwargs)
            with jax.named_scope("head_ce"):
                return mean_linear_cross_entropy(
                    hidden, params["embed_out"]["kernel"], batch["labels"],
                    batch.get("loss_mask"), cfg.ce_chunk_tokens)

        if cfg.ce_chunk_tokens > 0:
            if cfg.has_moe:
                # silently falling back would fake the feature (the same
                # guard class as the engine's NotImplementedErrors): MoE
                # needs the mutable-losses apply, which the hidden-states
                # path doesn't thread yet
                raise NotImplementedError(
                    "ce_chunk_tokens with MoE is not supported yet: the "
                    "chunked path bypasses the aux-loss collection")
            return loss_chunked
        return loss

    def param_partition_rules(self):
        """Megatron-pattern TP rules: regex over flat param path -> PartitionSpec."""
        return [
            (r"embed_in/embedding", P("tp", None)),
            (r"query_key_value/kernel", P(None, "tp")),
            (r"query_key_value/bias", P("tp")),
            (r"attention/dense/kernel", P("tp", None)),
            # expert weights: leading E dim on ep, Megatron col/row on tp
            (r"experts/dense_h_to_4h/kernel", P("ep", None, "tp")),
            (r"experts/dense_h_to_4h/bias", P("ep", "tp")),
            (r"experts/dense_4h_to_h/kernel", P("ep", "tp", None)),
            (r"experts/dense_4h_to_h/bias", P("ep", None)),
            (r"dense_h_to_4h/kernel", P(None, "tp")),
            (r"dense_h_to_4h/bias", P("tp")),
            (r"dense_4h_to_h/kernel", P("tp", None)),
            (r"embed_out/kernel", P(None, "tp")),
        ]

    def mup_multipliers(self, params):
        """1/width_mult on hidden-to-hidden matrices (μP), 1.0 elsewhere."""
        cfg = self.config
        if cfg.mup_base_width is None:
            return None
        width_mult = cfg.hidden_size / cfg.mup_base_width

        def mult(path, leaf):
            name = "/".join(str(getattr(k, "key", getattr(k, "name", k))) for k in path)
            if "embed_in" in name or "embed_out" in name or leaf.ndim < 2:
                return 1.0
            return 1.0 / width_mult

        return jax.tree_util.tree_map_with_path(mult, params)

    def flops_per_token(self):
        """Analytic fwd+bwd FLOPs per token (6N_active + attention term).

        ``N_active`` excludes the input-embedding table: the lookup is a
        gather (0 FLOPs), so counting its params would inflate MFU.  The
        output head IS a matmul and stays counted.  Agrees with the flops
        profiler's per-module walk (``tests/unit/profiling``).
        """
        cfg = self.config
        n_params = self.num_params() - cfg.vocab_size * cfg.hidden_size
        if cfg.has_moe:
            # only top-k experts run per token
            f = cfg.intermediate_size
            mlp = 2 * cfg.hidden_size * f + f + cfg.hidden_size
            inactive = (cfg.moe_num_experts - cfg.moe_top_k) * mlp
            n_params -= len(cfg.moe_layer_indices()) * inactive
        attn = 12 * cfg.num_layers * cfg.hidden_size * cfg.max_seq_len
        return 6 * n_params + attn

    def num_params(self):
        cfg = self.config
        h, L, v = cfg.hidden_size, cfg.num_layers, cfg.vocab_size
        f = cfg.intermediate_size
        mlp = 2 * h * f + f + h
        attn = 3 * h * h + 3 * h + h * h + h  # qkv + out proj
        lns = 4 * h
        dense_layer = attn + mlp + lns
        n_moe = len(cfg.moe_layer_indices())
        total = v * h + (L - n_moe) * dense_layer + 2 * h + v * h
        if n_moe:
            E = cfg.moe_num_experts
            moe_mlp = E * mlp + h * E  # experts + gate wg
            if cfg.moe_use_residual:
                moe_mlp += mlp + 2 * h + 2  # dense branch + coefficient
            total += n_moe * (attn + moe_mlp + lns)
        return total


def make_param_specs(params, rules, default=P()):
    """Apply (regex, spec) rules to a param pytree -> spec pytree."""

    def spec_for(path, leaf):
        name = "/".join(str(getattr(k, "key", getattr(k, "name", k))) for k in path)
        for pattern, spec in rules:
            if re.search(pattern, name):
                return spec
        return default

    return jax.tree_util.tree_map_with_path(spec_for, params)
