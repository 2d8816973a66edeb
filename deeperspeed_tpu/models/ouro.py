"""Ouro: a looped language model (ByteDance, "Scaling Latent Reasoning via
Looped Language Models", 2025-10).

One stack of ``num_layers`` blocks is run ``total_ut_steps`` = T times on the
SAME weights; after every pass the closing norm's output is an exit (one
untied head serves them all) and the next pass's input; a learned gate turns
the exits into a per-token distribution over "stop after pass t", and the
training loss is the exits' cross entropies weighted by it, less ``beta``
times its entropy.  The equations, with what the published ``config.json``
leaves to assumption, are in ``benchmarks/reference/ouro_ref.py``.

The block is built from ``models/llama.py``'s parts (RMSNorm, multi-head
attention with rotary over the whole head, SwiGLU) in a sandwich: a norm
before and after each sublayer.  The same engine protocol as the other
models (``loss_fn`` / ``example_batch`` / ``param_partition_rules`` /
``num_params`` / ``flops_per_token``) on the training path.  Serving a looped
model (a KV cache per pass and layer, an exit decided per token) is not
here: ROADMAP.md.

What weight sharing changes for the rest of the system: parameters and work
part ways by a factor of T (``num_params`` counts a weight once,
``flops_per_token`` as often as a token passes it), a weight's gradient is
the sum over its T uses, and a step holds T x L remat checkpoints for L
layers' weights.
"""

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.transformer.cross_entropy import (chunked_linear_cross_entropy,
                                             weighted_linear_cross_entropy)
from ..parallel.topology import BATCH_AXES
from .gpt_neox import maybe_constrain
from .llama import LlamaAttention, LlamaConfig, LlamaMLP, _Norm


@dataclasses.dataclass(unsafe_hash=True)
class OuroConfig:
    vocab_size: int = 49152
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 16
    num_kv_heads: int = 16
    intermediate_size: int = 5632
    max_seq_len: int = 4096
    rope_theta: float = 1000000.0
    rms_eps: float = 1e-6
    total_ut_steps: int = 4           # T: passes of the stack, and exits
    exit_entropy_beta: float = 0.1    # weight of the exit distribution's entropy
    # tokens per chunk of the head GEMM + cross entropy: four exits' float32
    # logits at once would be 4 x tokens x vocab x 4 bytes
    ce_chunk_tokens: int = 2048
    dtype: Any = jnp.float32
    remat: bool = False

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads

    def block_config(self):
        """The settings ``models/llama.py``'s parts read."""
        return LlamaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            num_layers=self.num_layers, num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads,
            intermediate_size=self.intermediate_size,
            max_seq_len=self.max_seq_len, rope_theta=self.rope_theta,
            rms_eps=self.rms_eps, dtype=self.dtype)

    @staticmethod
    def ouro_2_6b(**kw):
        """ByteDance/Ouro-2.6B as published: 48 layers, four passes."""
        return OuroConfig(**kw)

    @staticmethod
    def tiny(**kw):
        kw.setdefault("vocab_size", 256)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_heads", 4)
        kw.setdefault("num_kv_heads", 4)
        kw.setdefault("intermediate_size", 128)
        kw.setdefault("max_seq_len", 64)
        kw.setdefault("ce_chunk_tokens", 48)
        return OuroConfig(**kw)


class OuroBlock(nn.Module):
    """``x + RMS2(Attn(RMS1(x)))``, then ``x + RMS4(MLP(RMS3(x)))``."""

    config: OuroConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config.block_config()
        x = maybe_constrain(x, (BATCH_AXES, "sp", None))
        with jax.named_scope("attention"):
            a = LlamaAttention(cfg, name="attention")(
                _Norm(cfg, name="input_layernorm")(x), positions)
            x = x + _Norm(cfg, name="input_layernorm_2")(a)
        with jax.named_scope("mlp"):
            m = LlamaMLP(cfg, name="mlp")(
                _Norm(cfg, name="post_attention_layernorm")(x))
            x = x + _Norm(cfg, name="post_attention_layernorm_2")(m)
        return maybe_constrain(x, (BATCH_AXES, "sp", None))


def exit_distribution(gate, hs):
    """``p^t`` [T, ...] from the exits' hidden states ``hs`` [T, ..., H]:
    ``lambda^t = sigmoid(w_g . h^t + b_g)`` after every pass but the last,
    which takes what is left, so the shares of a token add up to one.  In
    float32 on the vector unit: a float32 matmul would run in bfloat16."""
    w = gate["kernel"].astype(jnp.float32)[:, 0]
    stay, shares = 1.0, []
    for h in hs[:-1]:
        lam = jax.nn.sigmoid(jnp.sum(h.astype(jnp.float32) * w, axis=-1)
                             + gate["bias"].astype(jnp.float32)[0])
        shares.append(lam * stay)
        stay = stay * (1.0 - lam)
    shares.append(stay * jnp.ones(hs.shape[1:-1], jnp.float32))
    return jnp.stack(shares)


def exit_entropy(p):
    """``H(p) = -sum_t p^t log p^t`` over the leading axis; ``0 log 0 = 0``."""
    safe = jnp.where(p > 0, p, 1.0)
    return -jnp.sum(p * jnp.log(safe), axis=0)


class ExitGate(nn.Module):
    """The gate's vector and bias (float32) -> the exit distribution."""

    @nn.compact
    def __call__(self, hs):
        kernel = self.param("kernel", nn.initializers.normal(0.02),
                            (hs.shape[-1], 1), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (1,), jnp.float32)
        with jax.named_scope("exit_gate"):
            return exit_distribution({"kernel": kernel, "bias": bias}, hs)


class Ouro(nn.Module):
    """Looped causal LM: tokens [B, S] -> every exit's logits [T, B, S, V]
    and the exit distribution [T, B, S]."""

    config: OuroConfig

    def setup(self):
        cfg = self.config
        self.embed_tokens = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                                     dtype=jnp.float32)
        # no policy, unlike the dense models (gpt_neox.py, llama.py): T
        # passes over L layers would keep T * L kernel outputs (32 x 67.1 MB
        # = 2.15 GB at [4, 4096, 2048] on the 14.38 GB the 2.6B cell holds,
        # 16.5 GB of a 16.9 GB chip), and the scan over passes cannot save
        # some passes and not others: the forward kernel runs again instead
        block = nn.remat(OuroBlock) if cfg.remat else OuroBlock
        self.layers = [block(cfg) for _ in range(cfg.num_layers)]
        self.final_norm = _Norm(cfg.block_config())
        self.lm_head = nn.Dense(cfg.vocab_size, use_bias=False,
                                dtype=cfg.dtype)
        self.exit_gate = ExitGate()

    def embed(self, input_ids):
        with jax.named_scope("embed"):
            return self.embed_tokens(input_ids).astype(self.config.dtype)

    def one_pass(self, h, positions, applied):
        """The whole stack and the closing norm once; ``applied`` counts
        block applications on the device."""
        for layer in self.layers:
            h = layer(h, positions)
            applied = applied + 1
        with jax.named_scope("head_ce"):    # the head, from its norm on
            return self.final_norm(h), applied

    def __call__(self, input_ids, **_):
        cfg = self.config
        B, S = input_ids.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        h, exits = self.embed(input_ids), []
        for _ in range(cfg.total_ut_steps):
            h, _n = self.one_pass(h, positions, 0)
            exits.append(h)
        hs = jnp.stack(exits)
        with jax.named_scope("head_ce"):
            return self.lm_head(hs), self.exit_gate(hs)

    # ------------------------------------------------------------ engine API
    def example_batch(self, batch_size=2, seq_len=None, seed=0):
        seq = seq_len or min(self.config.max_seq_len, 128)
        toks = jax.random.randint(jax.random.PRNGKey(seed),
                                  (batch_size, seq + 1), 0,
                                  self.config.vocab_size)
        return {"input_ids": toks[:, :-1], "labels": toks[:, 1:]}

    def _passes(self, params, input_ids):
        """The stack traced ONCE and run T times on shared parameters (a
        scan over passes) -> (every pass's closing norm's output
        [T, B, S, H], the layer applications the device counted)."""
        variables = {"params": params}
        B, S = input_ids.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))

        def one_pass(carry, _):
            h, applied = self.apply(variables, carry[0], positions, carry[1],
                                    method="one_pass")
            return (h, applied), h

        h0 = self.apply(variables, input_ids, method="embed")
        (_, layers), hs = jax.lax.scan(one_pass, (h0, jnp.int32(0)), None,
                                       length=self.config.total_ut_steps)
        return hs, layers

    def exits(self, params, input_ids, labels):
        """The training path's forward, for a check that wants every token's
        value at every exit: the passes, then every exit through one chunked
        head + cross entropy.  -> (log-probability of ``labels`` at every
        exit [T, B, S] float32, the exit distribution [T, B, S] float32,
        counters of what ran on the device)."""
        cfg = self.config
        B, S = input_ids.shape
        T = cfg.total_ut_steps
        hs, layers = self._passes(params, input_ids)
        flat_labels = labels.reshape(-1)

        def one_exit(heads, h):
            return heads + 1, chunked_linear_cross_entropy(
                h, params["lm_head"]["kernel"], flat_labels,
                cfg.ce_chunk_tokens)

        with jax.named_scope("head_ce"):
            heads, token_ll = jax.lax.scan(one_exit, jnp.int32(0),
                                           hs.reshape(T, B * S, -1))
            with jax.named_scope("exit_gate"):
                p = exit_distribution(params["exit_gate"], hs)
        return token_ll.reshape(T, B, S), p, {
            "layer_applications": layers, "head_applications": heads}

    def loss_fn(self):
        """``mean_i [ sum_t p^t_i CE^t_i - beta H(p_i) ]`` -> (loss, what the
        step reports of itself: the counters, the batch mean of each exit's
        share and of the entropy).  The exits' weights ``-p * mask / count``
        are made first, so that all T x B x S exit-tokens go through ONE walk
        of the head that makes its gradient as it goes (a walk an exit under
        a scan would stack T float32 head gradients)."""
        cfg = self.config
        beta = cfg.exit_entropy_beta

        def loss(params, batch, rng=None, **_):
            labels = batch["labels"]
            T, tokens = cfg.total_ut_steps, labels.size
            hs, layers = self._passes(params, batch["input_ids"])
            with jax.named_scope("head_ce"):
                with jax.named_scope("exit_gate"):
                    p = exit_distribution(params["exit_gate"], hs)
                    entropy = exit_entropy(p)
                    mask = batch.get("loss_mask", jnp.ones_like(entropy))
                    count = jnp.maximum(jnp.sum(mask), 1.0)
                    weights = -p * mask / count
                    mean_entropy = jnp.sum(entropy * mask) / count
                    share = jnp.sum(p * mask, axis=(1, 2)) / count
                chunk = min(cfg.ce_chunk_tokens, tokens)
                ce, chunks = weighted_linear_cross_entropy(
                    hs.reshape(T * tokens, -1), params["lm_head"]["kernel"],
                    jnp.tile(labels.reshape(-1), T), weights.reshape(-1),
                    chunk)
                stats = jax.lax.stop_gradient({
                    "layer_applications": layers,
                    # the rows the walk covered over the rows an exit has
                    "head_applications": chunks * chunk // tokens,
                    "exit_share": share, "exit_entropy": mean_entropy})
                return ce - beta * mean_entropy, stats

        return loss

    def no_cast_paths(self):
        """The embedding table (its gradient is a scatter-add) and the gate
        stay float32 under mixed precision."""
        return [r"embed_tokens/embedding", r"exit_gate/"]

    def param_partition_rules(self):
        """Megatron-style tp placement, as ``Llama``'s; the gate replicated."""
        return [
            (r"embed_tokens/embedding", P("tp", None)),
            (r"(q_proj|k_proj|v_proj|gate_proj|up_proj)/kernel", P(None, "tp")),
            (r"(o_proj|down_proj)/kernel", P("tp", None)),
            (r"lm_head/kernel", P(None, "tp")),
        ]

    def num_params(self):
        """Every weight once, however often a step uses it."""
        cfg = self.config
        h, f, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
        return (2 * v * h + cfg.num_layers * (self._layer_matmul_params()
                                              + 4 * h) + h + h + 1)

    def _layer_matmul_params(self):
        cfg = self.config
        h, d = cfg.hidden_size, cfg.head_dim
        return (2 * h * cfg.num_heads * d + 2 * h * cfg.num_kv_heads * d
                + 3 * h * cfg.intermediate_size)

    def flops_per_token(self):
        """Forward + backward FLOPs a trained token needs: 6 x every matmul
        weight as often as a token passes it (T passes of the stack, T
        heads, T - 1 gates) plus the attention term per block application.
        With weight reuse this is NOT 6 x ``num_params``."""
        cfg = self.config
        T, h = cfg.total_ut_steps, cfg.hidden_size
        matmul = (T * cfg.num_layers * self._layer_matmul_params()
                  + T * h * cfg.vocab_size + (T - 1) * h)
        return 6 * matmul + 12 * T * cfg.num_layers * h * cfg.max_seq_len
