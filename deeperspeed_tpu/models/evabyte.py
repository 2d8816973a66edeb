"""EvaByte: a byte-level decoder with EVA attention (``model_type``
``evabyte``; the preset is EvaByte 6.5B, ``EvaByte/EvaByte``).

A layer is pre-norm on a FLOAT32 residual stream (``fp32_skip_add``):
``h = x + Attn(N1(x))``, ``y = h + MLP(N2(h))``, each sum in float32, each
branch in the compute dtype; ``N(x) = x / rms(x) * (1 + g)``
(``norm_add_unit_offset``), the MLP SwiGLU.

* attention is multi-head with rotary on the whole head, and EVA
  (``ops/attention/eva.py``): a row attends, in one softmax with float32
  statistics, over the exact keys of its own ``window_size`` window up to
  itself and over softmax-pooled summaries (learned directions
  ``adaptive_mu_k``, ``adaptive_phi`` a head) of the ``chunk_size`` chunks of
  every earlier window.  On a TPU that is the kernel pair
  ``ops/attention/pallas_eva.py``; no score matrix of a sequence's length
  exists.
* the head is ONE matrix of ``num_pred_heads`` slices of the vocabulary's 320
  columns: slice ``i`` of position ``t`` predicts byte ``t + 1 + i``, the
  logits float32 (``fp32_logits``: the product accumulates and stays in
  float32, its operands in the compute dtype).  The training loss is the
  mean cross entropy over all (position, slice) pairs whose target lies
  inside the sequence, through one chunked walk of the head
  (``ops/transformer/cross_entropy.py::multi_label_linear_cross_entropy``).

The equations, and what the published ``config.json`` leaves to assumption,
are in ``benchmarks/reference/evabyte_ref.py``.

A chip's share.  ``layers_held`` layers from ``first_layer_held`` (a
pipeline stage), and of every layer's heads ``attention_heads_held`` from
``first_head_held``: the held heads' columns of ``W_q, W_k, W_v``, rows of
``W_o`` and entries of the directions; norms, MLP and both tables whole.
Heads are independent up to ``W_o``, so the shares' outputs of ``W_o`` add
up to the whole layer's (``tests/unit/models/test_evabyte.py``); what the
absent heads would add is left out and nothing stands in for them.

The stack, the skeleton of ``loss_fn`` and the rest of the engine protocol
are ``models/decoder.py``'s; the eight-slice head (``head_loss``,
``logprobs``) is this file's.  Scopes: ``attention`` (the sublayer with its
norm) with ``eva_pool`` (the summaries; on a TPU a
kernel pair under that name) and ``eva_attend`` (from q, k, v and the
summaries to the mixed output; the kernel pair ``eva_attention``) inside, ``mlp``,
``embed``, ``head_ce``.  A step's counters: ``layer_applications``,
``eva_pairs_visited`` (the (row, key) pairs the attention calls compute, all
heads and layers) beside ``eva_pairs_needed`` (what the equations need), and
``head_chunks``.
"""

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.attention import eva
from ..ops.transformer.cross_entropy import (multi_label_linear_cross_entropy,
                                             multi_label_logprobs)
from ..ops.transformer.normalize import rms_norm
from ..ops.transformer.rope import apply_rotary_pos_emb, rotary_tables
from ..parallel.topology import BATCH_AXES
from .decoder import Decoder, Stack, _dense
from .gpt_neox import maybe_constrain


EVA = "eva"


@dataclasses.dataclass(unsafe_hash=True)
class EvaByteConfig:
    """Published keys under their published names; the ``*_held`` keys give
    a chip's share (the whole model where they are None)."""
    vocab_size: int = 320
    hidden_size: int = 4096
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    intermediate_size: int = 11008
    window_size: int = 2048
    chunk_size: int = 16
    num_pred_heads: int = 8
    rope_theta: float = 100000.0
    rms_norm_eps: float = 1e-5
    init_std: float = 0.01275
    # a chip's share
    layers_held: Optional[int] = None
    first_layer_held: int = 0
    attention_heads_held: Optional[int] = None
    first_head_held: int = 0
    # the run
    max_seq_len: int = 32768
    ce_chunk_tokens: int = 2048
    dtype: Any = jnp.float32
    remat: bool = False

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def layers(self):
        return (self.num_hidden_layers if self.layers_held is None
                else self.layers_held)

    @property
    def heads(self):
        """Heads of a layer that are held here."""
        return (self.num_attention_heads if self.attention_heads_held is None
                else self.attention_heads_held)

    @staticmethod
    def evabyte_6_5b(**kw):
        """EvaByte 6.5B as published; keyword arguments give a chip's share."""
        return EvaByteConfig(**kw)

    @staticmethod
    def tiny(**kw):
        small = dict(vocab_size=40, hidden_size=64, num_hidden_layers=2,
                     num_attention_heads=4, intermediate_size=96,
                     window_size=64, chunk_size=8, num_pred_heads=8,
                     max_seq_len=192, ce_chunk_tokens=80)
        return EvaByteConfig(**dict(small, **kw))


def _directions(key, shape, dtype=jnp.float32):
    """normal(0, 1) clipped to [-1, 1], times ``D^-1/2``."""
    return (jnp.clip(jax.random.normal(key, shape, dtype), -1.0, 1.0)
            * shape[-1] ** -0.5)


def unit_offset_norm(x, weight, eps, dtype):
    """``x / rms(x) * (1 + weight)`` of the stream, in the branch's dtype."""
    return rms_norm(x.astype(dtype), 1.0 + weight.astype(jnp.float32),
                    eps=eps)


class EvaAttention(nn.Module):
    """The held heads of an EVA attention sublayer: u [B, S, H] in the
    compute dtype -> their part of ``W_o``'s output [B, S, H]."""

    config: EvaByteConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        B, S, _ = u.shape
        n, d = cfg.heads, cfg.head_dim
        q, k, v = (_dense(n * d, cfg, name, cfg.init_std)(u).reshape(
            B, S, n, d) for name in ("q_proj", "k_proj", "v_proj"))
        mu = self.param("adaptive_mu_k", _directions, (n, d), jnp.float32)
        phi = self.param("adaptive_phi", _directions, (n, d), jnp.float32)
        cos, sin = rotary_tables(jnp.arange(S)[None], d, cfg.rope_theta,
                                 cfg.dtype)
        q, k = apply_rotary_pos_emb(q, k, cos, sin)
        with jax.named_scope("eva_pool"):
            kb, vb = eva.chunk_summaries(k, v, mu, phi, cfg.chunk_size)
        with jax.named_scope("eva_attend"):
            out = eva.eva_attention(q, k, v, kb, vb, cfg.window_size,
                                    cfg.chunk_size)
        with jax.named_scope("attention_layout"):
            out = out.reshape(B, S, n * d)
        return _dense(cfg.hidden_size, cfg, "o_proj", cfg.init_std)(out)


class EvaMLP(nn.Module):
    config: EvaByteConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        f, std = cfg.intermediate_size, cfg.init_std
        gate = _dense(f, cfg, "gate_proj", std)(u)
        up = _dense(f, cfg, "up_proj", std)(u)
        return _dense(cfg.hidden_size, cfg, "down_proj", std)(
            nn.silu(gate) * up)


class EvaByteBlock(nn.Module):
    """``h = x + Attn(N1(x))``, ``y = h + MLP(N2(h))`` on a float32 stream
    -> (y, nothing: no layer routes)."""

    #: every layer is of the one kind
    KINDS = frozenset((EVA,))

    config: EvaByteConfig
    kind: str = EVA

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        x = maybe_constrain(x, (BATCH_AXES, "sp", None))
        with jax.named_scope("attention"):
            g = self.param("input_norm_weight", nn.initializers.zeros,
                           (cfg.hidden_size,), jnp.float32)
            u = unit_offset_norm(x, g, cfg.rms_norm_eps, cfg.dtype)
            x = x + EvaAttention(cfg, name="attn")(u).astype(jnp.float32)
        with jax.named_scope("mlp"):
            g = self.param("post_norm_weight", nn.initializers.zeros,
                           (cfg.hidden_size,), jnp.float32)
            u = unit_offset_norm(x, g, cfg.rms_norm_eps, cfg.dtype)
            x = x + EvaMLP(cfg, name="mlp")(u).astype(jnp.float32)
        return maybe_constrain(x, (BATCH_AXES, "sp", None)), {}


def byte_targets(labels, k):
    """``labels`` [B, S] (byte ``t + 1`` at ``t``) -> (targets [B, S, K]:
    byte ``t + 1 + i`` in slice ``i``, 0 past the end; which exist
    [B, S, K] bool)."""
    S = labels.shape[1]
    ahead = jnp.arange(S)[:, None] + jnp.arange(k)[None, :]
    inside = ahead < S
    picked = labels[:, jnp.minimum(ahead, S - 1)]
    return (jnp.where(inside, picked, 0),
            jnp.broadcast_to(inside, picked.shape))


class EvaByte(Decoder):
    """Causal byte LM: ids [B, S] -> (the closing norm's output [B, S, H] in
    the compute dtype, nothing: the head is applied by the chunked cross
    entropy and no layer routes)."""

    block_cls = EvaByteBlock
    final_norm = ("final_norm_weight", nn.initializers.zeros,
                  unit_offset_norm)
    example_len = property(lambda self: 2 * self.config.window_size)

    config: EvaByteConfig

    def stack(self):
        cfg = self.config
        # the stream starts, and stays, float32 (``Stack``'s default): a
        # recomputed layer's float32 input is the checkpoint
        return Stack(kinds=(EVA,) * cfg.layers, rows=cfg.vocab_size,
                     columns=cfg.num_pred_heads * cfg.vocab_size,
                     norm_eps=cfg.rms_norm_eps, init_std=cfg.init_std)

    def counters(self, batch, seq):
        """What a step's attention calls compute and what they need, (row,
        key) pairs over all held heads and layers: from the shapes and the
        form the calls take, counted when the program is traced."""
        cfg = self.config
        calls = batch * cfg.heads * cfg.layers
        return {
            "layer_applications": jnp.int32(cfg.layers),
            "eva_pairs_visited": jnp.float32(calls * eva.pairs_visited(
                seq, cfg.window_size, cfg.chunk_size, cfg.head_dim)),
            "eva_pairs_needed": jnp.float32(calls * eva.pairs_needed(
                seq, cfg.window_size, cfg.chunk_size))}

    def logprobs(self, params, input_ids, labels):
        """The training path's forward, for a check that wants every
        target's value -> (log-probability of byte ``t + 1 + i`` in slice
        ``i`` [B, S, K] float32, 0 where the target lies past the end;
        which exist [B, S, K])."""
        cfg = self.config
        hidden, _ = self.apply({"params": params}, input_ids)
        want, inside = byte_targets(labels, cfg.num_pred_heads)
        with jax.named_scope("head_ce"):
            ll = multi_label_logprobs(
                hidden.reshape(-1, cfg.hidden_size), params["lm_head_kernel"],
                want.reshape(-1, cfg.num_pred_heads), cfg.ce_chunk_tokens)
        return jnp.where(inside, ll.reshape(want.shape), 0.0), inside

    @nn.nowrap
    def head_loss(self, hidden, kernel, batch):
        """Mean cross entropy over every (position, slice) pair whose target
        lies inside the sequence (and under ``loss_mask`` [B, S], a mask on
        the TARGET's position) -> (loss, the chunks the head's walk took)."""
        cfg = self.config
        want, inside = byte_targets(batch["labels"], cfg.num_pred_heads)
        mask = inside.astype(jnp.float32)
        if batch.get("loss_mask") is not None:
            mask = mask * byte_targets(
                batch["loss_mask"].astype(jnp.float32),
                cfg.num_pred_heads)[0]
        weights = -mask / jnp.maximum(jnp.sum(mask), 1.0)
        ce, chunks = multi_label_linear_cross_entropy(
            hidden.reshape(-1, cfg.hidden_size), kernel,
            want.reshape(-1, cfg.num_pred_heads),
            weights.reshape(-1, cfg.num_pred_heads), cfg.ce_chunk_tokens)
        return ce, {"head_chunks": chunks}

    def no_cast_paths(self):
        """Float32 under mixed precision: the embedding table (it feeds the
        float32 stream, and its gradient is a scatter-add), the pooling
        directions (their logits are float32) and the norms' weights (``1 +
        g`` in bfloat16 has eight bits for ``g``)."""
        return [r"embed_tokens/embedding", r"adaptive_", r"norm_weight"]

    def param_partition_rules(self):
        """Megatron-style tp placement: heads by column (the directions with
        them), ``W_o`` by row, the MLP as Llama's, the head by column."""
        return [
            (r"(q_proj|k_proj|v_proj|gate_proj|up_proj)/kernel", P(None, "tp")),
            (r"(o_proj|down_proj)/kernel", P("tp", None)),
            (r"adaptive_(mu_k|phi)", P("tp", None)),
            (r"lm_head_kernel", P(None, "tp")),
        ]

    # ---------------------------------------------------------------- counts
    def layer_matmul_params(self):
        cfg = self.config
        h = cfg.hidden_size
        return (4 * h * cfg.heads * cfg.head_dim
                + 3 * h * cfg.intermediate_size)

    def num_params(self):
        cfg = self.config
        h = cfg.hidden_size
        return (cfg.vocab_size * h + h * cfg.num_pred_heads * cfg.vocab_size
                + h + cfg.layers * (self.layer_matmul_params() + 2 * h
                                    + 2 * cfg.heads * cfg.head_dim))

    def flops_by_kind(self, seq_len=None):
        """Forward + backward FLOPs a trained token needs, by kind: ``6 x``
        the matmul weights it passes (trunk, head), EVA's scores and values
        at ``12 D`` a (row, key) pair the equations need, and the summaries
        (``24 D`` a key a head).  Recomputed operations do not count."""
        cfg = self.config
        s = seq_len or cfg.max_seq_len
        heads = cfg.layers * cfg.heads * cfg.head_dim
        return {
            "trunk": 6 * cfg.layers * self.layer_matmul_params(),
            "head": 6 * cfg.hidden_size * cfg.num_pred_heads * cfg.vocab_size,
            "eva_attend": 12 * heads * eva.pairs_needed(
                s, cfg.window_size, cfg.chunk_size) / s,
            "eva_pool": 24 * heads}

    def flops_per_token(self, seq_len=None):
        return sum(self.flops_by_kind(seq_len).values())
