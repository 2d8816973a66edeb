"""Llama-family causal LMs: Llama-2, Mistral (GQA + sliding window), OPT.

Breadth counterpart of the reference's inference-v2 model zoo
(``inference/v2/model_implementations/{llama_v2,mistral,opt}``): the same
engine protocol as :class:`models.GPTNeoX` -- ``loss_fn`` / ``example_batch``
/ ``param_partition_rules`` for training, ``clone(decode=True)`` for the v1
engine's cached generation, ``clone(paged=True)`` + ``paged_state`` for the
v2 ragged engine -- so every engine in the framework serves these
architectures unchanged.

Architecture deltas vs GPT-NeoX:

* RMSNorm (no bias), pre-norm, sequential residual
* separate q/k/v projections with grouped-query attention
  (``num_kv_heads`` < ``num_heads``), full-dim rotary (Llama/Mistral)
* SwiGLU MLP (gate/up/down, no bias)
* Mistral: sliding-window attention, enforced on the dense, cached, and
  paged paths alike
* OPT: learned positions, standard GELU MLP, LayerNorm -- expressed as
  config flags on the same module tree
"""

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.attention.core import dot_product_attention
from ..ops.attention.pallas_flash import SAVED_BY_REMAT
from ..ops.transformer.rope import apply_rotary_pos_emb, rotary_tables
from ..parallel.topology import BATCH_AXES
from .gpt_neox import ModelLayerNorm, maybe_constrain



@dataclasses.dataclass(unsafe_hash=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32            # < num_heads -> GQA (Mistral: 8)
    intermediate_size: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    sliding_window: Optional[int] = None   # Mistral: 4096
    # OPT-style switches
    use_rope: bool = True
    learned_positions: bool = False
    mlp: str = "swiglu"               # "swiglu" | "gelu" | "relu"
    norm: str = "rmsnorm"             # "rmsnorm" | "layernorm"
    tie_embeddings: bool = False
    dtype: Any = jnp.float32
    remat: bool = False
    paged_num_blocks: int = 0
    paged_block_size: int = 64
    # "" = pool in compute dtype; "int8" / "fp8" (e4m3) = block-scaled pool
    # with per-(slot, head) fp32 scales (quantize-on-write, fused
    # dequant-attend)
    paged_kv_dtype: str = ""

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads

    # ---- presets
    @staticmethod
    def llama2_7b(**kw):
        return LlamaConfig(**kw)

    @staticmethod
    def mistral_7b(**kw):
        kw.setdefault("num_kv_heads", 8)
        kw.setdefault("intermediate_size", 14336)
        kw.setdefault("sliding_window", 4096)
        kw.setdefault("max_seq_len", 8192)
        kw.setdefault("vocab_size", 32000)
        return LlamaConfig(**kw)

    @staticmethod
    def opt_125m(**kw):
        kw.setdefault("vocab_size", 50272)
        kw.setdefault("hidden_size", 768)
        kw.setdefault("num_layers", 12)
        kw.setdefault("num_heads", 12)
        kw.setdefault("num_kv_heads", 12)
        kw.setdefault("intermediate_size", 3072)
        kw.setdefault("max_seq_len", 2048)
        kw.setdefault("use_rope", False)
        kw.setdefault("learned_positions", True)
        kw.setdefault("mlp", "relu")
        kw.setdefault("norm", "layernorm")
        kw.setdefault("tie_embeddings", True)
        return LlamaConfig(**kw)

    @staticmethod
    def tiny(**kw):
        kw.setdefault("vocab_size", 256)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_heads", 4)
        kw.setdefault("num_kv_heads", 2)
        kw.setdefault("intermediate_size", 128)
        kw.setdefault("max_seq_len", 64)
        return LlamaConfig(**kw)

    @staticmethod
    def tiny_mistral(**kw):
        kw.setdefault("sliding_window", 16)
        return LlamaConfig.tiny(**kw)

    @staticmethod
    def tiny_opt(**kw):
        kw.setdefault("use_rope", False)
        kw.setdefault("learned_positions", True)
        kw.setdefault("mlp", "relu")
        kw.setdefault("norm", "layernorm")
        kw.setdefault("tie_embeddings", True)
        return LlamaConfig.tiny(**kw)


class _Norm(nn.Module):
    config: LlamaConfig
    name_: str = ""

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        if cfg.norm == "layernorm":
            return ModelLayerNorm(epsilon=cfg.rms_eps, dtype=cfg.dtype,
                                  fused=True)(x)
        from ..ops.transformer.normalize import rms_norm

        h = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (h,), jnp.float32)
        return rms_norm(x.astype(cfg.dtype), scale, eps=cfg.rms_eps)


class LlamaAttention(nn.Module):
    config: LlamaConfig
    decode: bool = False
    paged: bool = False

    def _repeat_kv(self, t):
        """[B, S, KV, D] -> [B, S, N, D] (GQA share)."""
        cfg = self.config
        rep = cfg.num_heads // cfg.num_kv_heads
        if rep == 1:
            return t
        return jnp.repeat(t, rep, axis=2)

    @nn.compact
    def __call__(self, x, positions, deterministic=True, attention_mask=None,
                 paged_state=None):
        cfg = self.config
        B, S, H = x.shape
        n, kv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = nn.Dense(n * d, use_bias=False, dtype=cfg.dtype,
                     name="q_proj")(x).reshape(B, S, n, d)
        k = nn.Dense(kv * d, use_bias=False, dtype=cfg.dtype,
                     name="k_proj")(x).reshape(B, S, kv, d)
        v = nn.Dense(kv * d, use_bias=False, dtype=cfg.dtype,
                     name="v_proj")(x).reshape(B, S, kv, d)
        if cfg.use_rope:
            cos, sin = rotary_tables(positions, d, cfg.rope_theta, cfg.dtype)
            q, k = apply_rotary_pos_emb(q, k, cos, sin)

        # caches hold num_kv_heads tensors -- the KV-memory saving is GQA's
        # whole point; heads are repeated only at attention time
        if self.paged:
            out = self._paged(q, k, v, positions, paged_state)
            if out is not None:
                return nn.Dense(H, use_bias=False, dtype=cfg.dtype,
                                name="o_proj")(out.reshape(B, S, H))
        if self.decode:
            out = self._cached(q, k, v, attention_mask)
            if out is not None:
                return nn.Dense(H, use_bias=False, dtype=cfg.dtype,
                                name="o_proj")(out.reshape(B, S, H))

        # k and v go at their KV heads, which the kernel addresses by the
        # query head's group, and the window goes to it too: it skips what
        # lies left of the band (``pallas_flash.mha``); only a padding mask
        # takes the plain path
        mask = None
        if attention_mask is not None:
            mask = attention_mask[:, None, None, :].astype(bool)
        out = dot_product_attention(q, k, v, mask=mask, causal=True,
                                    window=cfg.sliding_window)
        with jax.named_scope("attention_layout"):
            out = out.reshape(B, S, H)
        return nn.Dense(H, use_bias=False, dtype=cfg.dtype,
                        name="o_proj")(out)

    def _cached(self, q, k, v, attention_mask):
        """v1 engine autoregressive cache (same scheme as GPT-NeoX)."""
        cfg = self.config
        B, S = q.shape[:2]
        max_len = cfg.max_seq_len
        is_init = self.has_variable("cache", "cached_key")
        ck = self.variable("cache", "cached_key", jnp.zeros,
                           (B, max_len, cfg.num_kv_heads, cfg.head_dim),
                           k.dtype)
        cv = self.variable("cache", "cached_value", jnp.zeros,
                           (B, max_len, cfg.num_kv_heads, cfg.head_dim),
                           v.dtype)
        idx_var = self.variable("cache", "cache_index",
                                lambda: jnp.zeros((), jnp.int32))
        if not is_init:
            return None
        idx = idx_var.value
        kf = jax.lax.dynamic_update_slice(ck.value, k, (0, idx, 0, 0))
        vf = jax.lax.dynamic_update_slice(cv.value, v, (0, idx, 0, 0))
        ck.value, cv.value = kf, vf
        idx_var.value = idx + S
        q_pos = idx + jnp.arange(S)
        mask = jnp.arange(max_len)[None, :] <= q_pos[:, None]
        if cfg.sliding_window is not None:
            mask = mask & (jnp.arange(max_len)[None, :]
                           > q_pos[:, None] - cfg.sliding_window)
        mask = mask[None, None]
        if attention_mask is not None:
            mask = mask & attention_mask[:, None, None, :].astype(bool)
        return dot_product_attention(q, self._repeat_kv(kf),
                                     self._repeat_kv(vf), mask=mask,
                                     causal=False)

    def _paged(self, q, k, v, positions, paged_state):
        """v2 ragged engine blocked KV pool (same protocol as GPT-NeoX,
        including the long-context ``attn_override`` / ``write_flat`` /
        ``attn_partial`` two-pass keys -- see
        ``gpt_neox.GPTNeoXAttention._paged_attention``; decode runs the
        Pallas paged kernel over live blocks)."""
        cfg = self.config
        assert cfg.paged_num_blocks > 0
        override = None if paged_state is None else paged_state.get("attn_override")
        if override is not None:
            return override.astype(q.dtype)
        B, S = q.shape[:2]
        bs = cfg.paged_block_size
        KV, D = cfg.num_kv_heads, cfg.head_dim
        quant_kv = bool(cfg.paged_kv_dtype)
        shape = (cfg.paged_num_blocks, bs, KV, D)
        if quant_kv:
            from ..quantization import wire_dtype

            pool_dtype = wire_dtype(cfg.paged_kv_dtype)
        else:
            pool_dtype = k.dtype
        is_init = self.has_variable("cache", "paged_key")
        pk = self.variable("cache", "paged_key", jnp.zeros, shape, pool_dtype)
        pv = self.variable("cache", "paged_value", jnp.zeros, shape, pool_dtype)
        if quant_kv:
            psk = self.variable("cache", "paged_key_scale", jnp.zeros,
                                shape[:3], jnp.float32)
            psv = self.variable("cache", "paged_value_scale", jnp.zeros,
                                shape[:3], jnp.float32)
        if not is_init:
            return None
        block_tables = paged_state.get("block_tables")
        write_mask = paged_state["write_mask"]
        write_flat = paged_state.get("write_flat")
        if write_flat is not None:
            flat = jnp.asarray(write_flat, jnp.int32)
        else:
            slot = jnp.take_along_axis(block_tables, positions // bs, axis=1)
            flat = slot * bs + positions % bs
        oob = cfg.paged_num_blocks * bs
        flat = jnp.where(write_mask, flat, oob)
        if quant_kv:
            from ..ops.quantizer import quantize_kv

            k, k_scale = quantize_kv(k, cfg.paged_kv_dtype)
            v, v_scale = quantize_kv(v, cfg.paged_kv_dtype)
            pool_sk = psk.value.reshape(-1, KV).at[flat.reshape(-1)].set(
                k_scale.reshape(-1, KV), mode="drop")
            pool_sv = psv.value.reshape(-1, KV).at[flat.reshape(-1)].set(
                v_scale.reshape(-1, KV), mode="drop")
            psk.value = pool_sk.reshape(shape[:3])
            psv.value = pool_sv.reshape(shape[:3])
        pool_k = pk.value.reshape(-1, KV, D).at[flat.reshape(-1)].set(
            k.reshape(-1, KV, D), mode="drop")
        pool_v = pv.value.reshape(-1, KV, D).at[flat.reshape(-1)].set(
            v.reshape(-1, KV, D), mode="drop")
        pk.value = pool_k.reshape(shape)
        pv.value = pool_v.reshape(shape)
        if paged_state.get("attn_partial", False):
            # capture pass (long-context two-pass protocol): KV committed,
            # queries sown, attention supplied later via attn_override
            self.sow("intermediates", "attn_q", q)
            return jnp.zeros_like(q)
        rep = cfg.num_heads // KV
        if S == 1 and cfg.sliding_window is None:
            from ..ops.attention.paged import paged_decode_attention

            # GQA: fold the per-kv-head query groups into the batch dim so
            # the kernel's head axis matches the kv-head pools (the pools
            # stay 1/rep the size; each block is read once per group)
            q0 = q[:, 0].reshape(B, KV, rep, D)
            q0 = q0.transpose(0, 2, 1, 3).reshape(B * rep, KV, D)
            out = paged_decode_attention(
                q0, pk.value, pv.value,
                jnp.repeat(block_tables, rep, axis=0),
                jnp.repeat(positions[:, 0] + 1, rep, axis=0),
                k_scale=psk.value if quant_kv else None,
                v_scale=psv.value if quant_kv else None)
            out = out.reshape(B, rep, KV, D).transpose(0, 2, 1, 3)
            return out.reshape(B, 1, cfg.num_heads, D).astype(q.dtype)
        if S <= 8 and cfg.sliding_window is None:
            # speculative decode / short chunk: one block-walk per row
            # verifies all S = k+1 query tokens (see gpt_neox counterpart);
            # GQA folds query groups into the batch dim as above
            from ..ops.attention.paged import paged_spec_decode_attention

            qs = q.reshape(B, S, KV, rep, D)
            qs = qs.transpose(0, 3, 1, 2, 4).reshape(B * rep, S, KV, D)
            out = paged_spec_decode_attention(
                qs, pk.value, pv.value,
                jnp.repeat(block_tables, rep, axis=0),
                jnp.repeat(positions, rep, axis=0),
                k_scale=psk.value if quant_kv else None,
                v_scale=psv.value if quant_kv else None)
            out = out.reshape(B, rep, S, KV, D).transpose(0, 2, 3, 1, 4)
            return out.reshape(B, S, cfg.num_heads, D).astype(q.dtype)
        K = pool_k.reshape(shape)[block_tables].reshape(B, -1, KV, D)
        V = pool_v.reshape(shape)[block_tables].reshape(B, -1, KV, D)
        if quant_kv:
            from ..ops.quantizer import dequantize_kv

            K = dequantize_kv(K, pool_sk.reshape(shape[:3])[
                block_tables].reshape(B, -1, KV), q.dtype)
            V = dequantize_kv(V, pool_sv.reshape(shape[:3])[
                block_tables].reshape(B, -1, KV), q.dtype)
        K = self._repeat_kv(K)
        V = self._repeat_kv(V)
        kv_pos = jnp.arange(K.shape[1])
        mask = kv_pos[None, None, None, :] <= positions[:, None, :, None]
        if cfg.sliding_window is not None:
            # enforce the window here too -- prefill AND (windowed) decode
            # take this dense path, so v2 serving matches the dense model
            mask = mask & (kv_pos[None, None, None, :]
                           > positions[:, None, :, None] - cfg.sliding_window)
        return dot_product_attention(q, K, V, mask=mask, causal=False)


class LlamaMLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        f = cfg.intermediate_size
        if cfg.mlp == "swiglu":
            gate = nn.Dense(f, use_bias=False, dtype=cfg.dtype,
                            name="gate_proj")(x)
            up = nn.Dense(f, use_bias=False, dtype=cfg.dtype,
                          name="up_proj")(x)
            h = nn.silu(gate) * up
        else:
            h = nn.Dense(f, dtype=cfg.dtype, name="up_proj")(x)
            h = nn.relu(h) if cfg.mlp == "relu" else nn.gelu(h)
        return nn.Dense(cfg.hidden_size, use_bias=cfg.mlp != "swiglu",
                        dtype=cfg.dtype, name="down_proj")(h)


class LlamaBlock(nn.Module):
    config: LlamaConfig
    decode: bool = False
    paged: bool = False

    @nn.compact
    def __call__(self, x, positions, deterministic=True, attention_mask=None,
                 paged_state=None):
        cfg = self.config
        x = maybe_constrain(x, (BATCH_AXES, "sp", None))
        # each sublayer with its norm under the scope a device trace is read
        # by (PERF.md section 3), as in ``GPTNeoXBlock``
        with jax.named_scope("attention"):
            h = _Norm(cfg, name="input_norm")(x)
            x = x + LlamaAttention(cfg, decode=self.decode, paged=self.paged,
                                   name="attention")(
                h, positions, deterministic=deterministic,
                attention_mask=attention_mask, paged_state=paged_state)
        with jax.named_scope("mlp"):
            h = _Norm(cfg, name="post_attention_norm")(x)
            x = x + LlamaMLP(cfg, name="mlp")(h)
        return maybe_constrain(x, (BATCH_AXES, "sp", None))


class Llama(nn.Module):
    """Causal LM: tokens [B, S] -> logits [B, S, V]."""

    config: LlamaConfig
    decode: bool = False
    paged: bool = False

    @nn.compact
    def __call__(self, input_ids, deterministic=True, positions=None,
                 attention_mask=None, paged_state=None, logits_positions=None,
                 **_):
        cfg = self.config
        B, S = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=jnp.float32,
                         name="embed_tokens")
        with jax.named_scope("embed"):
            x = embed(input_ids).astype(cfg.dtype)
            if cfg.learned_positions:
                x = x + nn.Embed(
                    cfg.max_seq_len, cfg.hidden_size, dtype=jnp.float32,
                    name="embed_positions")(positions).astype(cfg.dtype)
        block = LlamaBlock
        if cfg.remat:
            # as GPT-NeoX: a dense model uses a layer once a step, so keeping
            # the flash kernel's output and lse costs one more [B, S, H] a
            # layer and saves the forward kernel's second run
            block = nn.remat(
                LlamaBlock, static_argnums=(3,),
                policy=jax.checkpoint_policies.save_only_these_names(
                    *SAVED_BY_REMAT))
        for i in range(cfg.num_layers):
            x = block(cfg, decode=self.decode, paged=self.paged,
                      name=f"layers_{i}")(
                x, positions, deterministic, attention_mask, paged_state)
        with jax.named_scope("head_ce"):    # the head, from its norm on
            x = _Norm(cfg, name="final_norm")(x)
        if logits_positions is not None:
            # ragged logits-gather ([B] or [B, R]): see GPTNeoX.__call__
            lp = jnp.asarray(logits_positions, jnp.int32)
            if lp.ndim == 1:
                lp = lp[:, None]
            x = jnp.take_along_axis(x, lp[..., None], axis=1)
        with jax.named_scope("head_ce"):
            if cfg.tie_embeddings:
                logits = embed.attend(x.astype(jnp.float32))
            else:
                logits = nn.Dense(cfg.vocab_size, use_bias=False,
                                  dtype=cfg.dtype, name="lm_head")(x)
        return logits

    # ---------------------------------------------------- engine API
    # (flax's built-in Module.clone handles decode=/paged=/config= updates)
    def example_batch(self, batch_size=2, seq_len=None, seed=0):
        seq = seq_len or min(self.config.max_seq_len, 128)
        key = jax.random.PRNGKey(seed)
        toks = jax.random.randint(key, (batch_size, seq + 1), 0,
                                  self.config.vocab_size)
        return {"input_ids": toks[:, :-1], "labels": toks[:, 1:]}

    def loss_fn(self):
        model = self

        def loss(params, batch, rng=None, **_):
            logits = model.apply({"params": params}, batch["input_ids"],
                                 deterministic=rng is None)
            labels = batch["labels"]
            with jax.named_scope("head_ce"):   # the head GEMM is in it too
                logits = logits.astype(jnp.float32)
                lse = jax.nn.logsumexp(logits, axis=-1)
                ll = jnp.take_along_axis(logits, labels[..., None],
                                         axis=-1)[..., 0] - lse
                mask = batch.get("loss_mask", jnp.ones_like(ll))
                return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)

        return loss

    def param_partition_rules(self):
        """Megatron-style tp placement (same role as GPT-NeoX's rules)."""
        return [
            (r"embed_tokens/embedding", P("tp", None)),
            (r"embed_positions/embedding", P(None, None)),
            (r"(q_proj|k_proj|v_proj|gate_proj|up_proj)/kernel", P(None, "tp")),
            (r"(q_proj|k_proj|v_proj|gate_proj|up_proj)/bias", P("tp")),
            (r"(o_proj|down_proj)/kernel", P("tp", None)),
            (r"lm_head/kernel", P(None, "tp")),
        ]

    def num_params(self):
        cfg = self.config
        h, f, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
        d = cfg.head_dim
        attn = h * cfg.num_heads * d + 2 * h * cfg.num_kv_heads * d + \
            cfg.num_heads * d * h
        if cfg.mlp == "swiglu":
            mlp = 3 * h * f
        else:
            mlp = 2 * h * f + f + h
        norms = (2 if cfg.norm == "rmsnorm" else 4) * h
        total = v * h + cfg.num_layers * (attn + mlp + norms) + \
            (h if cfg.norm == "rmsnorm" else 2 * h)
        if not cfg.tie_embeddings:
            total += v * h
        if cfg.learned_positions:
            total += cfg.max_seq_len * h
        return total

    def flops_per_token(self):
        cfg = self.config
        n = self.num_params() - cfg.vocab_size * cfg.hidden_size
        if cfg.learned_positions:
            n -= cfg.max_seq_len * cfg.hidden_size
        attn = 12 * cfg.num_layers * cfg.hidden_size * cfg.max_seq_len
        return 6 * n + attn


def Mistral(config=None, **kw):
    """Mistral = Llama arch + GQA + sliding window (preset helper)."""
    return Llama(config or LlamaConfig.mistral_7b(), **kw)


def OPT(config=None, **kw):
    """OPT = learned positions + ReLU MLP + LayerNorm + tied embeddings."""
    return Llama(config or LlamaConfig.opt_125m(), **kw)
