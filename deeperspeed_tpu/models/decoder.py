"""One decoder stack for the models whose training path is all they have
(``nemotron_h.py``, ``mellum.py`` with ``laguna.py`` and ``keye.py``,
``evabyte.py``, ``zaya.py``).  Four
decisions are made here and nowhere else: how a stack of layers is run and
recomputed, what a layer reports and how a step's counters are made of it,
how the head and the loss are called, and what a model offers the engine by
default (``loss_fn``, ``example_batch``).

A new architecture is a file with its configuration (a hashable dataclass
with ``hidden_size``, ``max_seq_len``, ``ce_chunk_tokens``, ``dtype``,
``remat``), its mixers, and

* a block, ``Block(config, kind, name=...)(x) -> (x, said)``, with ``KINDS``
  on its class: what a layer may be.  ``said`` is ``{}`` of a layer that
  routes nothing, else ``{"counters": what the routed walk counted
  (``dropless.dropless_moe``), "chosen": which held experts each token chose
  [B, S, held]}``, and where the layer has a term of its own in the loss (a
  mixture's balance term) ``"loss"``: a scalar ``loss_fn`` adds to the
  head's.  A block whose layers hand a value on beside the stream
  (a router's state) takes and returns it too, ``Block(...)(x, carried) ->
  (x, said, carried)``: the first layer held is called with ``x`` alone;
* a subclass of ``Decoder`` that states seven values: ``block_cls``; in
  ``stack()`` the ``kinds`` of its layers in order, the table's ``rows``,
  ``table_dtype`` and ``init_std`` and the head's ``columns``, or
  ``tied_head``: the head is the table's transpose, one leaf, whose gradient
  is the sum of its two uses;
  ``saved_by_remat``; ``final_norm`` (with ``norm_eps`` in ``stack()``);
* its counts: ``counters(batch, seq)``, its ``*_layer_applications`` of a
  step on that many tokens (the benchmark's exact checks go by these names),
  ``num_params()`` and ``flops_per_token()``;
* the lists that ARE the model: ``no_cast_paths()`` and
  ``param_partition_rules()``.

A head that is not one next-token softmax overrides ``head_loss`` and
``logprobs`` (``evabyte.py``).  The step is traced from no more Python
frames than the models' own loops were (ROADMAP S7): the blocks are called
from ``__call__``'s own frame, ``loss`` calls ``apply`` itself, and what a
model states is read before the loop.
"""

from typing import Any, NamedTuple, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..moe import dropless
from ..ops.attention.pallas_flash import SAVED_BY_REMAT
from ..ops.transformer.cross_entropy import (chunked_linear_cross_entropy,
                                             mean_linear_cross_entropy)
from ..ops.transformer.normalize import rms_norm


class Stack(NamedTuple):
    """What a model's configuration makes of its stack."""
    kinds: Tuple[Any, ...]      # of the layers held, in order
    rows: int                   # of the table ``embed_tokens``
    columns: int                # of ``lm_head_kernel`` [H, columns] float32
    norm_eps: float             # the closing norm's
    table_dtype: Any = jnp.float32
    init_std: float = 0.02      # of both tables
    tied_head: bool = False     # the head is ``embed_tokens``'s transpose


def _dense(width, cfg, name, std=0.02):
    return nn.Dense(width, use_bias=False, dtype=cfg.dtype, name=name,
                    kernel_init=nn.initializers.normal(std))


class GatedMLP(nn.Module):
    """``W_down (silu(W_gate u) * W_up u)`` at ``width``: a dense layer's
    MLP and a sparse layer's shared expert (``laguna.py``,
    ``moonlight.py``)."""

    config: Any
    width: int

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        hidden = (jax.nn.silu(_dense(self.width, cfg, "gate_proj")(u))
                  * _dense(self.width, cfg, "up_proj")(u))
        return _dense(cfg.hidden_size, cfg, "down_proj")(hidden)


def cast_rms_norm(x, scale, eps, dtype):
    """``rms_norm`` of the stream in the compute type."""
    return rms_norm(x.astype(dtype), scale, eps=eps)


class Decoder(nn.Module):
    """Causal LM: tokens [B, S] -> (the closing norm's output [B, S, H],
    what each layer that routed said).  ``lm_head_kernel`` is declared here
    (unless the stack ties the head to the table) and applied by the chunked
    cross entropy."""

    #: the class of a layer, made with (configuration, a kind of ``KINDS``)
    block_cls = None
    #: a recomputed layer keeps the attention kernel's own two residuals
    #: (its output and one float a row; windowed and EVA calls name theirs
    #: alike), as the dense models' blocks do
    saved_by_remat = SAVED_BY_REMAT
    #: the closing norm: its weight's name and init, and ``(x, weight, eps,
    #: compute dtype) -> x``
    final_norm = ("final_norm_scale", nn.initializers.ones, cast_rms_norm)
    #: ``example_batch``'s default length
    example_len = 128

    def none_chosen(self, shape):
        """``chosen`` where no layer of the stack routed."""
        raise ValueError("no layer of the stack routed: nothing to stack")

    @nn.compact
    def __call__(self, input_ids, **_):
        cfg, block, stack = self.config, self.block_cls, self.stack()
        weight, weight_init, norm = self.final_norm
        if set(stack.kinds) - block.KINDS:
            raise ValueError(
                f"kinds {stack.kinds!r}: a layer of {block.__name__} is one "
                f"of {sorted(block.KINDS)}")
        init = nn.initializers.normal(stack.init_std)
        with jax.named_scope("embed"):
            x = nn.Embed(stack.rows, cfg.hidden_size, dtype=stack.table_dtype,
                         embedding_init=init, name="embed_tokens")(input_ids)
        if cfg.remat:
            block = nn.remat(
                block, policy=jax.checkpoint_policies.save_only_these_names(
                    *self.saved_by_remat))
        told, carried = [], ()  # what a layer hands the next beside ``x``
        for i, kind in enumerate(stack.kinds):
            x, said, *carried = block(cfg, kind, name=f"layers_{i}")(
                x, *carried)
            if said:
                told.append(said)
        with jax.named_scope("head_ce"):    # the head, from its norm on
            x = norm(x, self.param(weight, weight_init, (cfg.hidden_size,),
                                   jnp.float32), stack.norm_eps, cfg.dtype)
            if not stack.tied_head:
                self.param("lm_head_kernel", init,
                           (cfg.hidden_size, stack.columns), jnp.float32)
        return x, told

    # ------------------------------------------------------------ engine API
    def example_batch(self, batch_size=2, seq_len=None, seed=0):
        seq = seq_len or min(self.config.max_seq_len, self.example_len)
        toks = jax.random.randint(jax.random.PRNGKey(seed),
                                  (batch_size, seq + 1), 0, self.stack().rows)
        return {"input_ids": toks[:, :-1], "labels": toks[:, 1:]}

    @nn.nowrap
    def _report(self, told, shape):
        """A step's counters of what ran on the device: the model's own and,
        of the layers that routed, the experts' load."""
        load = (dropless.load_counters([t["counters"] for t in told])
                if told else {})
        return {**self.counters(*shape), **load}

    @nn.nowrap
    def head_kernel(self, params):
        """The head's matrix [H, columns]: a leaf of its own, or the table's
        transpose where the stack ties them."""
        if self.stack().tied_head:
            return params["embed_tokens"]["embedding"].T
        return params["lm_head_kernel"]

    @nn.nowrap
    def head_loss(self, hidden, kernel, batch):
        """Mean next-token cross entropy -> (loss, the head's own
        counters)."""
        return mean_linear_cross_entropy(
            hidden, kernel, batch["labels"], batch.get("loss_mask"),
            self.config.ce_chunk_tokens), {}

    def logprobs(self, params, input_ids, labels):
        """The training path's forward, for a check that wants every token's
        value -> (log-probability of ``labels`` [B, S] float32, which held
        experts each token chose in each layer that routed [layers, B, S,
        held], the step's counters)."""
        cfg = self.config
        hidden, told = self.apply({"params": params}, input_ids)
        counters = self._report(told, input_ids.shape)
        chosen = (jnp.stack([t["chosen"] for t in told]) if told
                  else self.none_chosen(input_ids.shape))
        with jax.named_scope("head_ce"):
            token_ll = chunked_linear_cross_entropy(
                hidden.reshape(-1, cfg.hidden_size), self.head_kernel(params),
                labels.reshape(-1), cfg.ce_chunk_tokens)
        return token_ll.reshape(labels.shape), chosen, counters

    def loss_fn(self):
        """``head_loss`` of the stack -> (loss, the step's counters: layer
        applications by kind, the expert layers' load, the head's own)."""

        def loss(params, batch, rng=None, **_):
            ids = batch["input_ids"]
            hidden, told = self.apply({"params": params}, ids)
            counters = self._report(told, ids.shape)
            with jax.named_scope("head_ce"):
                ce, more = self.head_loss(hidden, self.head_kernel(params),
                                          batch)
            for said in told:       # a layer's own term, where it has one
                if "loss" in said:
                    ce = ce + said["loss"]
            return ce, jax.lax.stop_gradient({**counters, **more})

        return loss
