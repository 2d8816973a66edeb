"""``models/decoder.py``: a fifth model written here from the stack and a
twenty-line block (one kind of layer that routes and says so, one that says
nothing) gets the layer loop, the remat wrap, the routed layers' report, the
head + loss and the engine protocol; and the four real models keep the
parameter names the runners, ``no_cast_paths`` and the limits files address
leaves by."""

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeperspeed_tpu import telemetry
from deeperspeed_tpu.models.decoder import Decoder, Stack, _dense
from deeperspeed_tpu.models.evabyte import EvaByte, EvaByteConfig
from deeperspeed_tpu.models.laguna import Laguna, LagunaConfig
from deeperspeed_tpu.models.mellum import Mellum, MellumConfig
from deeperspeed_tpu.models.nemotron_h import NemotronH, NemotronHConfig
from deeperspeed_tpu.moe import dropless
from deeperspeed_tpu.ops.transformer.normalize import rms_norm

MLP, ROUTED = "mlp", "routed"
EXPERTS, TOP = 4, 2


@dataclasses.dataclass(unsafe_hash=True)
class ToyConfig:
    kinds: Tuple[str, ...] = (MLP, ROUTED, MLP, ROUTED)
    vocab_size: int = 64
    hidden_size: int = 32
    max_seq_len: int = 48
    ce_chunk_tokens: int = 40
    dtype: Any = jnp.float32
    remat: bool = False


class ToyBlock(nn.Module):
    KINDS = frozenset((MLP, ROUTED))

    config: ToyConfig
    kind: str = MLP

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, S, H = x.shape
        scale = self.param("norm_scale", nn.initializers.ones, (H,),
                           jnp.float32)
        u = rms_norm(x, scale, eps=1e-6)
        if self.kind == MLP:
            y = _dense(H, cfg, "down")(nn.silu(_dense(2 * H, cfg, "up")(u)))
            return x + y, {}
        normal = nn.initializers.normal(0.02)
        router = self.param("router_kernel", normal, (H, EXPERTS))
        w_in = self.param("experts_up_proj", normal, (EXPERTS, H, 2 * H))
        w_out = self.param("experts_down_proj", normal, (EXPERTS, 2 * H, H))
        tokens = u.reshape(B * S, H)
        y, counters, chosen = dropless.dropless_moe(
            tokens, tokens.astype(jnp.float32) @ router, w_in, w_out, k=TOP,
            first_expert=0, experts_held=EXPERTS)
        return (x + y.reshape(B, S, H).astype(x.dtype),
                {"counters": counters, "chosen": chosen.reshape(B, S, -1)})


class Toy(Decoder):
    block_cls = ToyBlock

    config: ToyConfig

    def stack(self):
        cfg = self.config
        return Stack(kinds=cfg.kinds, rows=cfg.vocab_size,
                     columns=cfg.vocab_size, norm_eps=1e-6,
                     table_dtype=cfg.dtype)

    def counters(self, batch, seq):
        return {"routed_layer_applications": jnp.int32(
            self.config.kinds.count(ROUTED)), "tokens": jnp.int32(batch * seq)}

    def no_cast_paths(self):
        return [r"embed_tokens/embedding", r"router_kernel"]


def _params(model, batch):
    return model.init(jax.random.PRNGKey(3), batch["input_ids"])["params"]


def test_a_model_made_of_the_stack_trains_through_the_engine():
    import deeperspeed_tpu as dst
    from deeperspeed_tpu.parallel.topology import MeshTopology

    model = Toy(ToyConfig(remat=True, dtype=jnp.bfloat16))
    engine, _, _, _ = dst.initialize(
        model=model, mesh=MeshTopology(devices=jax.devices()[:1]),
        config={"train_batch_size": 2, "train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                "bf16": {"enabled": True}, "zero_optimization": {"stage": 0},
                "steps_per_print": 10 ** 9})
    batch = model.example_batch(2)
    assert batch["input_ids"].shape == (2, 48)      # min(max_seq_len, 128)
    first, second = (float(engine.train_batch(batch=batch)) for _ in "ab")
    assert second < first
    told = telemetry.step_counters()["train_step"]
    assert told["routed_layer_applications"] == 2 and told["tokens"] == 96
    # the load of the layers that routed, by ``dropless.load_counters``
    assert told["moe_slots_held"] == 96 * TOP
    assert told["moe_slots_dropped"] == 0
    assert told["moe_load_max_over_mean"] >= 1.0


def test_remat_changes_neither_the_loss_nor_the_gradients():
    plain, recomputed = Toy(ToyConfig()), Toy(ToyConfig(remat=True))
    batch = plain.example_batch(2, 40)
    params = _params(plain, batch)
    want, got = (jax.value_and_grad(m.loss_fn(), has_aux=True)(params, batch)
                 for m in (plain, recomputed))
    assert float(got[0][0]) == pytest.approx(float(want[0][0]), rel=1e-6)
    assert got[0][1].keys() == want[0][1].keys()
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got[1]),
                            jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-7,
                                   err_msg=jax.tree_util.keystr(path))
    # the recomputed stack is the block under ``jax.checkpoint``, once a layer

    def checkpoints(model):
        return str(jax.make_jaxpr(lambda p: model.apply(
            {"params": p}, batch["input_ids"])[0])(params)).count(
                "remat2[")

    assert (checkpoints(recomputed), checkpoints(plain)) == (4, 0)


def test_the_masked_mean_of_the_logprobs_is_the_loss():
    model = Toy(ToyConfig())
    batch = model.example_batch(2, 40, seed=5)
    params = _params(model, batch)
    mask = jax.random.bernoulli(jax.random.PRNGKey(7), 0.6, (2, 40))
    lp, chosen, told = model.logprobs(params, batch["input_ids"],
                                      batch["labels"])
    loss, told_loss = model.loss_fn()(params, dict(batch, loss_mask=mask))
    assert float(loss) == pytest.approx(
        float(-jnp.sum(jnp.where(mask, lp, 0.0)) / mask.sum()), rel=1e-5)
    # one row of ``chosen`` a layer that routed, and the counters of both
    assert chosen.shape == (2, 2, 40, EXPERTS) and chosen.dtype == bool
    assert int(chosen.sum()) == 2 * 80 * TOP
    assert told.keys() == told_loss.keys()
    assert float(told["moe_slots_held"]) == chosen.sum() / 2


def test_a_kind_the_block_does_not_name_is_refused():
    model = Toy(ToyConfig(kinds=(MLP, "conv")))
    with pytest.raises(ValueError, match="conv.*ToyBlock.*mlp.*routed"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def test_a_stack_none_of_whose_layers_routed():
    """The counters are the model's own, and ``chosen`` is what the model
    says it is then: nothing to stack, unless it says otherwise."""
    model = Toy(ToyConfig(kinds=(MLP, MLP)))
    batch = model.example_batch(1, 16)
    params = _params(model, batch)
    loss, told = model.loss_fn()(params, batch)
    assert np.isfinite(float(loss))
    assert set(told) == {"routed_layer_applications", "tokens"}
    with pytest.raises(ValueError, match="no layer of the stack routed"):
        model.logprobs(params, batch["input_ids"], batch["labels"])


@pytest.mark.parametrize("model,layers,norm", [
    (NemotronH(NemotronHConfig.tiny()), 4, "final_norm_scale"),
    (Mellum(MellumConfig.tiny()), 3, "final_norm_scale"),
    (Laguna(LagunaConfig.tiny()), 3, "final_norm_scale"),
    (EvaByte(EvaByteConfig.tiny()), 2, "final_norm_weight"),
], ids=lambda v: type(v).__name__ if isinstance(v, nn.Module) else None)
def test_the_parameter_tree_keeps_its_names(model, layers, norm):
    cfg = model.config
    ids = model.example_batch(1, 64)["input_ids"]
    tree = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids))
    params = tree["params"]
    assert set(tree) == {"params"}
    assert set(params) == {"embed_tokens", norm, "lm_head_kernel"} | {
        f"layers_{i}" for i in range(layers)}
    rows, columns = model.stack().rows, model.stack().columns
    assert params["embed_tokens"]["embedding"].shape == (rows,
                                                         cfg.hidden_size)
    assert params["lm_head_kernel"].shape == (cfg.hidden_size, columns)
    assert params["lm_head_kernel"].dtype == params[norm].dtype == jnp.float32
    assert params[norm].shape == (cfg.hidden_size,)


# ------------------------------ a value carried from layer to layer, a tied head
class CarryingBlock(nn.Module):
    """A layer that hands the next a running mean of what it read, beside
    the stream: the first layer held is called with the stream alone."""
    KINDS = frozenset((MLP,))

    config: ToyConfig
    kind: str = MLP

    @nn.compact
    def __call__(self, x, carried=None):
        cfg = self.config
        H = x.shape[-1]
        scale = self.param("norm_scale", nn.initializers.ones, (H,),
                           jnp.float32)
        u = rms_norm(x, scale, eps=1e-6)
        state = _dense(8, cfg, "state")(u)
        if carried is not None:
            mix = self.param("mix", nn.initializers.constant(0.5), (8,))
            state = state + mix * carried
        y = _dense(H, cfg, "down")(nn.silu(_dense(2 * H, cfg, "up")(u))
                                   ) + _dense(H, cfg, "read")(state)
        return x + y, {}, state


class Carrying(Toy):
    block_cls = CarryingBlock
    tied = False

    def stack(self):
        return super().stack()._replace(tied_head=self.tied)

    def num_params(self):
        shapes = jax.eval_shape(lambda: self.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
        return sum(x.size for x in jax.tree_util.tree_leaves(shapes))


class Tied(Carrying):
    tied = True


def test_a_value_carried_from_layer_to_layer_crosses_the_remat_wrap():
    """The same loss and gradients with and without remat, the mix of the
    first layer absent (it is handed nothing) and every later layer's
    gradient alive: the carried value is differentiated through."""
    kinds = (MLP,) * 3
    plain, recomputed = (Carrying(ToyConfig(kinds=kinds, remat=r))
                         for r in (False, True))
    batch = plain.example_batch(2, 40)
    params = _params(plain, batch)
    assert "mix" not in params["layers_0"]
    assert "mix" in params["layers_1"] and "mix" in params["layers_2"]
    want, got = (jax.value_and_grad(m.loss_fn(), has_aux=True)(params, batch)
                 for m in (plain, recomputed))
    assert float(got[0][0]) == pytest.approx(float(want[0][0]), rel=1e-6)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got[1]),
                            jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-7,
                                   err_msg=jax.tree_util.keystr(path))
    assert np.asarray(got[1]["layers_2"]["mix"]).any()
    # layer 0's state reaches the loss through layers 1 AND 2 alone once
    # its own read-out is cut: the chain of carried values
    cut = jax.tree_util.tree_map(lambda x: x, params)
    cut["layers_0"]["read"]["kernel"] = jnp.zeros_like(
        cut["layers_0"]["read"]["kernel"])
    through = jax.grad(lambda p: plain.loss_fn()(p, batch)[0])(cut)
    assert np.asarray(through["layers_0"]["state"]["kernel"]).any()


def test_a_stack_that_ties_its_head_has_one_table():
    """No ``lm_head_kernel``; the table's gradient is the sum of its use as
    the embedding and its use as the head; the table counts once."""
    kinds = (MLP,) * 2
    tied, untied = (cls(ToyConfig(kinds=kinds)) for cls in (Tied, Carrying))
    batch = tied.example_batch(2, 40)
    params = _params(tied, batch)
    assert "lm_head_kernel" not in params
    assert "lm_head_kernel" in _params(untied, batch)
    assert untied.num_params() - tied.num_params() == 64 * 32
    table = params["embed_tokens"]["embedding"]
    np.testing.assert_array_equal(tied.head_kernel(params), table.T)

    def loss(embedding, head):
        hidden, _ = tied.apply({"params": dict(
            params, embed_tokens={"embedding": embedding})}, batch["input_ids"])
        return tied.head_loss(hidden, head.T, batch)[0]

    as_embedding, as_head = jax.grad(loss, argnums=(0, 1))(table, table)
    whole = jax.grad(lambda p: tied.loss_fn()(p, batch)[0])(params)[
        "embed_tokens"]["embedding"]
    assert np.asarray(as_embedding).any() and np.asarray(as_head).any()
    np.testing.assert_allclose(as_embedding + as_head, whole, rtol=1e-5,
                               atol=1e-8)
    # the per-token log-probabilities go through the same table
    lp, _, _ = _no_routing(tied).logprobs(params, batch["input_ids"],
                                          batch["labels"])
    assert float(-lp.mean()) == pytest.approx(
        float(tied.loss_fn()(params, batch)[0]), rel=1e-5)


def _no_routing(model):
    """The model, saying what ``chosen`` is where no layer routed."""
    class Quiet(type(model)):
        def none_chosen(self, shape):
            return jnp.zeros((0,) + shape + (0,), bool)
    return Quiet(model.config)


def test_a_stack_that_does_neither_builds_the_tree_it_built_before():
    """Same leaves, same shapes, ``lm_head_kernel`` among them: the toy's
    tree by hand, as it was before a stack could carry or tie."""
    model = Toy(ToyConfig(kinds=(MLP, ROUTED)))
    assert model.stack().tied_head is False
    tree = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    shapes = {jax.tree_util.keystr(p): v.shape
              for p, v in jax.tree_util.tree_leaves_with_path(tree)}
    assert shapes == {
        "['embed_tokens']['embedding']": (64, 32),
        "['final_norm_scale']": (32,),
        "['lm_head_kernel']": (32, 64),
        "['layers_0']['norm_scale']": (32,),
        "['layers_0']['up']['kernel']": (32, 64),
        "['layers_0']['down']['kernel']": (64, 32),
        "['layers_1']['norm_scale']": (32,),
        "['layers_1']['router_kernel']": (32, 4),
        "['layers_1']['experts_up_proj']": (4, 32, 64),
        "['layers_1']['experts_down_proj']": (4, 64, 32)}
