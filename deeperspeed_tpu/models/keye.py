"""Keye-VL 2.0's language model (Kwai-Keye, ``model_type`` ``KeyeVL2``; the
preset is Keye-VL-2.0-30B-A3B): Qwen3-MoE's block, ``h = x + Attn(RMSNorm(x))``,
``y = h + MoE(RMSNorm(h))``, whose attention is LEARNED SPARSE (``sa_config``:
the lightning indexer of the DeepSeek-V3.2-Exp report on grouped-query heads):

* q (32 heads of 128) and k, v (4 KV heads) with an RMSNorm a head on q and
  k (one learned scale of ``head_dim`` each) and multi-axis rotary on the
  whole head (``mrope_section``: the frequency pairs are dealt to a temporal,
  a height and a width position; a text token has the same position on all
  three, which is plain rotary);
* the indexer, on the sublayer's normed input HELD CONSTANT: 16 heads of 64
  over one shared key head (LayerNorm on the key, rotary on the whole 64),
  a per-row weight a head, ``I[t, s] = sum_j w[t, j] relu(q^I[t, j] .
  k^I[s])``;
* each row keeps its ``topk`` (2048) best-scored earlier keys, exactly, and
  the attention is one softmax over those (``ops/attention/dsa.py``; on a
  TPU the selection is a packed mask inside a causal walk,
  ``pallas_dsa.py``);
* the indexer learns from a loss of its own: the KL from the main
  attention's head-averaged probabilities over the chosen keys to the
  indexer's softmax over them, a mean over rows, summed over layers and added
  to the LM loss.  Because the indexer's input and the main attention's
  probabilities are held constant, the indexer's four leaves (``wq_index``,
  ``wk_index``, ``w_index``, the key's LayerNorm) receive gradient from that
  loss alone and everything else from the LM loss alone.

The MLP is Mellum's routed layer as it is (``MellumMoE``: float32 softmax
over all 128, top-8 renormalised, gated experts of 768, no shared expert).
The equations, and what the published ``config.json`` leaves to assumption,
are in ``benchmarks/reference/keye_ref.py``.  The vision tower and its
projector have no key in the config and are not here.

A chip's share is told as Mellum's is: ``layers_held`` layers from
``first_layer_held``, ``routed_experts_held`` experts from
``first_expert_held``, ``vocab_rows_held`` rows of both tables; attention,
indexer, router and norms are whole on every chip and count once
(``tests/unit/models/test_keye.py``).

The stack, the routed layers' report and the head's call are
``models/decoder.py``'s, and what ``Mellum`` states of its stack (the tables,
the float32 leaves, the tp placement, an expert's size) is Mellum's own code,
inherited; a layer also says its indexer's loss and what its
selection counted, and ``loss_fn`` adds the sum of the former to the LM
loss.  Scopes: ``attention`` with ``dsa_index`` (the indexer's projections,
its key's norm, its rotary), ``dsa_select`` (the scores and each row's best
of them: one kernel), ``dsa_attend`` and ``dsa_indexer_loss`` inside;
``mlp`` with ``moe_route`` and ``moe_experts``; ``embed``, ``head_ce``.
"""

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from ..moe import dropless
from ..ops.attention import dsa
from ..ops.transformer.normalize import rms_norm
from ..ops.transformer.rope import (apply_rotary_pos_emb, mrope_tables,
                                    rotary_tables)
from ..parallel.topology import BATCH_AXES
from .decoder import _dense
from .gpt_neox import maybe_constrain
from .mellum import Mellum, MellumMoE

DSA = "dsa_attention"


@dataclasses.dataclass(unsafe_hash=True)
class KeyeConfig:
    """Published keys under their published names (``sa_config``'s flat);
    the ``*_held`` keys give a chip's share (the whole model where they are
    None)."""
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    rms_norm_eps: float = 1e-6
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e7
    mrope_section: Tuple[int, ...] = (16, 24, 24)
    # sa_config
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    topk: int = 2048            # (``q_chunk_size`` and ``kv_chunk_size`` are
    # tile hints of the indexer's computation and change no equation)
    # the mixture
    num_experts: int = 128                # the router's width: never a share
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    # a chip's share
    layers_held: Optional[int] = None
    first_layer_held: int = 0
    routed_experts_held: Optional[int] = None
    first_expert_held: int = 0
    vocab_rows_held: Optional[int] = None
    # the run
    max_seq_len: int = 16384
    ce_chunk_tokens: int = 2048
    dtype: Any = jnp.float32
    remat: bool = False
    #: None: the kernels on a TPU; tests force them on (interpret mode) or off
    use_pallas: Optional[bool] = None

    @property
    def kinds(self):
        """The kinds of the layers held, in order: all alike."""
        held = (self.num_hidden_layers if self.layers_held is None
                else self.layers_held)
        if self.first_layer_held + held > self.num_hidden_layers:
            raise ValueError("the layers held lie outside the model's")
        return (DSA,) * held

    @property
    def experts(self):
        return (self.num_experts if self.routed_experts_held is None
                else self.routed_experts_held)

    @property
    def vocab_rows(self):
        return (self.vocab_size if self.vocab_rows_held is None
                else self.vocab_rows_held)

    @staticmethod
    def keye_vl2_30b(**held):
        """Keye-VL-2.0-30B-A3B's language model as published; keyword
        arguments give a chip's share."""
        return KeyeConfig(**held)

    @staticmethod
    def tiny(**kw):
        small = dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=2, num_heads=4,
            num_kv_heads=2, head_dim=16, mrope_section=(2, 3, 3),
            indexer_num_heads=3, indexer_head_dim=8, topk=16,
            num_experts=16, num_experts_per_tok=3, moe_intermediate_size=48,
            routed_experts_held=4, first_expert_held=4, max_seq_len=96,
            ce_chunk_tokens=48)
        return KeyeConfig(**dict(small, **kw))


def text_positions(batch, seq):
    """The positions ``[3, B, S]`` of a batch of text: one ``arange`` on the
    temporal, the height and the width axis alike."""
    return jnp.broadcast_to(jnp.arange(seq), (3, batch, seq))


class KeyeIndexer(nn.Module):
    """The lightning indexer's three projections of a sublayer's input (held
    constant by the caller): -> (rotated ``q^I [B, S, H_I, D_I]``, rotated
    ``k^I [B, S, D_I]``, the head weights ``w [B, S, H_I]`` float32 with
    ``H_I^-1/2 D_I^-1/2`` in them)."""

    config: Any

    @nn.compact
    def __call__(self, u, positions):
        cfg = self.config
        B, S, _ = u.shape
        heads, d = cfg.indexer_num_heads, cfg.indexer_head_dim
        q = _dense(heads * d, cfg, "wq_index")(u).reshape(B, S, heads, d)
        k = _dense(d, cfg, "wk_index")(u)
        k = nn.LayerNorm(epsilon=1e-6, dtype=cfg.dtype, name="k_norm")(k)
        # rotary on the whole head, by the temporal position (the sections
        # deal out 64 pairs, the indexer's head has 32: for text all three
        # axes coincide)
        cos, sin = rotary_tables(positions[0], d, cfg.rope_theta, cfg.dtype)
        q, k = apply_rotary_pos_emb(q, k[:, :, None], cos, sin)
        w = _dense(heads, cfg, "w_index")(u).astype(jnp.float32)
        return q, k[:, :, 0], w * (float(heads) ** -0.5 * float(d) ** -0.5)


class KeyeAttention(nn.Module):
    """Grouped-query attention over each row's chosen keys -> (the output
    projection's [B, S, H], what the layer says of it: its indexer's loss,
    the packed selection, what the selection counted)."""

    config: Any

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        B, S, _ = u.shape
        nq, kv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = _dense(nq * d, cfg, "q_proj")(u).reshape(B, S, nq, d)
        k = _dense(kv * d, cfg, "k_proj")(u).reshape(B, S, kv, d)
        v = _dense(kv * d, cfg, "v_proj")(u).reshape(B, S, kv, d)
        ones = nn.initializers.ones
        q = rms_norm(q, self.param("q_norm_scale", ones, (d,), jnp.float32),
                     eps=cfg.rms_norm_eps)
        k = rms_norm(k, self.param("k_norm_scale", ones, (d,), jnp.float32),
                     eps=cfg.rms_norm_eps)
        positions = text_positions(B, S)
        cos, sin = mrope_tables(positions, cfg.mrope_section, d,
                                cfg.rope_theta, cfg.dtype)
        q, k = apply_rotary_pos_emb(q, k, cos, sin)
        with jax.named_scope("dsa_index"):
            qi, ki, w = KeyeIndexer(cfg, name="indexer")(
                jax.lax.stop_gradient(u), positions)
        sel = dsa.dsa_select(qi, ki, w, cfg.topk, use_pallas=cfg.use_pallas)
        out, lse = dsa.dsa_attention(q, k, v, sel, use_pallas=cfg.use_pallas)
        kl = dsa.dsa_indexer_loss(qi, ki, w, q, k, lse, sel,
                                  use_pallas=cfg.use_pallas)
        with jax.named_scope("attention_layout"):
            out = out.reshape(B, S, nq * d)
        said = {"indexer_kl": kl, "selection": sel.words,
                "dsa": {"pairs_selected": sel.pairs_selected(),
                        "pairs_visited": sel.pairs_visited(),
                        "tiles_skipped": sel.tiles_skipped()}}
        return _dense(cfg.hidden_size, cfg, "o_proj")(out), said


class KeyeBlock(nn.Module):
    """``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))`` -> (y, what
    the routed walk counted and chose, the indexer's loss and what the
    selection counted)."""

    KINDS = frozenset((DSA,))

    config: KeyeConfig
    kind: str = DSA

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        x = maybe_constrain(x, (BATCH_AXES, "sp", None))
        with jax.named_scope("attention"):
            scale = self.param("input_norm_scale", nn.initializers.ones,
                               (cfg.hidden_size,), jnp.float32)
            u = rms_norm(x, scale, eps=cfg.rms_norm_eps)
            y, said = KeyeAttention(cfg, name="attn")(u)
            x = x + y
        with jax.named_scope("mlp"):
            scale = self.param("post_norm_scale", nn.initializers.ones,
                               (cfg.hidden_size,), jnp.float32)
            u = rms_norm(x, scale, eps=cfg.rms_norm_eps)
            y, counters, chosen = MellumMoE(cfg, name="moe")(u)
            x = x + y.astype(x.dtype)
        return (maybe_constrain(x, (BATCH_AXES, "sp", None)),
                {"counters": counters, "chosen": chosen, **said})


class Keye(Mellum):
    """Causal LM: tokens [B, S] -> (the closing norm's output [B, S, H],
    what each layer said: the routed walk's counters and chosen-here mask,
    its indexer's loss, its selection's counts).  The indexer (one key
    head) is whole on every chip: ``Mellum``'s placement rules leave it
    so."""

    block_cls = KeyeBlock
    #: a recomputed layer keeps the attention kernel's output and
    #: log-sum-exp, the packed selection, the indexer's gradients (made with
    #: its loss) and the grouped walk's plan: each is made once a step
    saved_by_remat = dsa.SAVED_BY_REMAT + (dropless.PLAN_SAVED_BY_REMAT,)

    config: KeyeConfig

    def counters(self, batch, seq):
        layers = len(self.config.kinds)
        return {"dsa_layer_applications": jnp.int32(layers),
                "moe_layer_applications": jnp.int32(layers)}

    @nn.nowrap
    def _report(self, told, shape):
        """Mellum's report and, of the layers' selections: the pairs chosen,
        the pairs a pass of the attention kernels computes for a head (a
        skipped tile's not counted), the tiles skipped inside the triangle,
        each summed over the layers; and the sum of the indexers' losses."""
        dsa_told = [t["dsa"] for t in told]
        return {**super()._report(told, shape),
                **{f"dsa_{name}": sum(t[name] for t in dsa_told)
                   for name in dsa_told[0]},
                "dsa_indexer_kl": sum(t["indexer_kl"] for t in told)}

    def selections(self, params, input_ids):
        """The packed selection ``[B, Sp, W]`` of every layer held
        (``ops/attention/dsa.Selection.words``), for a check."""
        _, told = self.apply({"params": params}, input_ids)
        return tuple(t["selection"] for t in told)

    def loss_fn(self):
        """``L_LM + sum over layers of the indexers' losses`` -> (loss, the
        step's counters, both parts among them: ``lm_loss``,
        ``dsa_indexer_kl``)."""

        def loss(params, batch, rng=None, **_):
            ids = batch["input_ids"]
            hidden, told = self.apply({"params": params}, ids)
            counters = self._report(told, ids.shape)
            with jax.named_scope("head_ce"):
                ce, more = self.head_loss(hidden, params["lm_head_kernel"],
                                          batch)
            return ce + counters["dsa_indexer_kl"], jax.lax.stop_gradient(
                {**counters, **more, "lm_loss": ce})

        return loss

    # ---------------------------------------------------------------- counts
    def indexer_params(self):
        """The indexer's matmul weights: its queries, its key, its head
        weights."""
        cfg = self.config
        return cfg.hidden_size * (
            cfg.indexer_num_heads * cfg.indexer_head_dim
            + cfg.indexer_head_dim + cfg.indexer_num_heads)

    def layer_matmul_params(self):
        """Matmul weights a token passes in one layer outside its routed
        experts: Mellum's (the four attention projections, the router) and
        the indexer's three."""
        return super().layer_matmul_params() + self.indexer_params()

    def num_params(self):
        cfg = self.config
        h = cfg.hidden_size
        return (2 * cfg.vocab_rows * h + h + len(cfg.kinds) * (
            self.layer_matmul_params() + 2 * h + 2 * cfg.head_dim
            + 2 * cfg.indexer_head_dim
            + cfg.experts * self.routed_expert_params()))

    def pairs(self, seq=None):
        """(chosen, causal) (row, key) pairs of one sequence of one layer."""
        s = seq or self.config.max_seq_len
        k = min(self.config.topk, s)
        return k * (k + 1) // 2 + (s - k) * k, s * (s + 1) // 2

    def flops_per_token(self, slots_per_token=None):
        """Forward + backward FLOPs a trained token needs at the shares
        held: 6 x the matmul weights it passes (a routed expert counted per
        slot), over the CHOSEN pairs the main attention's scores and values
        (``12 heads D`` a pair) and the loss's second ``q . k`` (``2 heads
        D``, forward only), and over the CAUSAL pairs the indexer's scores
        (``6 H_I D_I`` a pair: one product forward, two backward).  What the
        walk computes beside (the unchosen pairs of a visited tile, the
        scores made a second time for the loss) and recomputed operations do
        not count."""
        cfg = self.config
        if slots_per_token is None:
            slots_per_token = (cfg.num_experts_per_tok * cfg.experts
                               / cfg.num_experts)
        matmul = (len(cfg.kinds) * (
            self.layer_matmul_params()
            + slots_per_token * self.routed_expert_params())
            + cfg.hidden_size * cfg.vocab_rows)
        chosen, causal = self.pairs()
        pairs = (14 * cfg.num_heads * cfg.head_dim * chosen
                 + 6 * cfg.indexer_num_heads * cfg.indexer_head_dim * causal)
        return 6 * matmul + len(cfg.kinds) * pairs / cfg.max_seq_len
