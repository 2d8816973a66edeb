"""The head + loss of the four models that run the chunked head
(``ops/transformer/cross_entropy.py``), at tiny sizes on the CPU: each
``loss_fn`` (the weighted sum that makes its gradient in the forward walk)
against the same loss composed by hand from the per-token form, which
autodiff differentiates through the recomputed logits; and the gradient
program itself, which holds three GEMMs against the vocabulary a chunk, not
four."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import core
from benchmarks.reference import mellum_ref, nemotron_h_ref, ouro_ref
from deeperspeed_tpu import telemetry
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
from deeperspeed_tpu.models.ouro import Ouro, OuroConfig, exit_entropy
from deeperspeed_tpu.ops.transformer.cross_entropy import (
    chunked_linear_cross_entropy)

#: a vocabulary no other dimension of the tiny presets equals (GPT-NeoX's
#: MLP is 4 x 64 = 256 wide, the presets' own vocabulary)
VOCAB = 200
TRAFFIC = {"seq_len": 40, "micro_batch": 2, "dtype": "float32",
           "ce_chunk_tokens": 48}
OURO = {"hidden_size": 64, "vocab_size": VOCAB, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
        "num_hidden_layers": 2, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
        "total_ut_steps": 4, "exit_entropy_beta": 0.1}


def _masked_mean(values, mask):
    return jnp.sum(values * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _ouro():
    model = Ouro(OuroConfig.tiny(vocab_size=VOCAB))

    def by_hand(params, batch):
        token_ll, p, counters = model.exits(params, batch["input_ids"],
                                            batch["labels"])
        entropy = exit_entropy(p)
        per_token = jnp.sum(p * -token_ll, axis=0) - 0.1 * entropy
        mask = batch.get("loss_mask", jnp.ones_like(entropy))
        return _masked_mean(per_token, mask), jax.lax.stop_gradient(dict(
            counters, exit_entropy=_masked_mean(entropy, mask),
            exit_share=jnp.stack([_masked_mean(share, mask) for share in p])))

    return model.loss_fn(), ouro_ref.init_params(OURO, 3), by_hand


def _experts(runner, ref, preset):
    cfg = dict(core.load_json(f"{core.BENCH_DIR}/configs/{preset}.json"),
               vocab_size=VOCAB)
    model = core.load_runner(runner).program_model(cfg, TRAFFIC)

    def by_hand(params, batch):
        token_ll, _, counters = model.logprobs(params, batch["input_ids"],
                                               batch["labels"])
        mask = batch.get("loss_mask", jnp.ones_like(token_ll))
        return -_masked_mean(token_ll, mask), jax.lax.stop_gradient(counters)

    return model.loss_fn(), ref.init_params(cfg, 3), by_hand


def _gpt_neox():
    model = GPTNeoX(GPTNeoXConfig.tiny(vocab_size=VOCAB, ce_chunk_tokens=48))
    params = model.init(jax.random.PRNGKey(3),
                        jnp.zeros((2, 40), jnp.int32))["params"]

    def by_hand(params, batch):
        hidden = model.apply({"params": params}, batch["input_ids"],
                             return_hidden=True)
        token_ll = chunked_linear_cross_entropy(
            hidden.reshape(-1, hidden.shape[-1]),
            params["embed_out"]["kernel"], batch["labels"].reshape(-1), 48)
        mask = batch.get("loss_mask", jnp.ones(batch["labels"].shape))
        return -_masked_mean(token_ll, mask.reshape(-1)), {}

    loss = model.loss_fn()
    return (lambda params, batch: (loss(params, batch), {})), params, by_hand


MODELS = {
    "ouro": _ouro,
    "nemotron_h": lambda: _experts("train_hybrid", nemotron_h_ref,
                                   "tiny-nemotron-rehearsal"),
    "mellum": lambda: _experts("train_swa_moe", mellum_ref,
                               "tiny-mellum-rehearsal"),
    "gpt_neox": _gpt_neox,
}


def _batch(masked):
    toks = np.random.default_rng(5).integers(0, VOCAB, size=(2, 41),
                                             dtype=np.int32)
    batch = {"input_ids": jnp.asarray(toks[:, :-1]),
             "labels": jnp.asarray(toks[:, 1:])}
    if masked:
        batch["loss_mask"] = (jnp.arange(80).reshape(2, 40) % 7 != 0).astype(
            jnp.float32)
    return batch


@pytest.mark.parametrize("masked", [False, True], ids=["unmasked", "masked"])
@pytest.mark.parametrize("name", list(MODELS))
def test_loss_counters_and_gradients_are_the_per_token_forms(name, masked):
    """80 tokens (four exits of them for the looped model) in chunks of 48:
    a tail chunk, and chunks that straddle two exits."""
    loss_fn, params, by_hand = MODELS[name]()
    batch = _batch(masked)
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        loss_fn, has_aux=True))(params, batch)
    (want, want_aux), want_grads = jax.jit(jax.value_and_grad(
        by_hand, has_aux=True))(params, batch)
    assert abs(float(loss) - float(want)) <= 1e-5 * abs(float(want))
    assert aux.keys() == want_aux.keys()
    for key, value in want_aux.items():
        np.testing.assert_allclose(np.asarray(aux[key]), np.asarray(value),
                                   rtol=1e-5, atol=1e-7, err_msg=key)
    if name == "ouro":
        assert int(aux["head_applications"]) == 4
        assert aux["head_applications"].dtype == jnp.int32
    want_grads = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    for path, got in jax.tree_util.tree_leaves_with_path(grads):
        wanted = np.asarray(want_grads[path])
        assert np.linalg.norm(np.asarray(got) - wanted) <= (
            1e-5 * np.linalg.norm(wanted) + 1e-9), jax.tree_util.keystr(path)


def _dot_generals(jaxpr, found):
    """Every ``dot_general`` of a jaxpr and of the jaxprs its equations hold
    (scan and remat bodies, custom rules), with its operands' shapes."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append([v.aval.shape for v in eqn.invars])
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _dot_generals(sub, found)
    return found


@pytest.mark.parametrize("name", list(MODELS))
def test_the_gradient_program_holds_three_gemms_against_the_vocabulary(name):
    """Logits, ``d_x`` and ``d_w``, each once in the head's one scan: the
    per-token form's gradient holds a fourth, the recomputed logits."""
    loss_fn, params, by_hand = MODELS[name]()
    batch = _batch(False)

    def against_the_vocabulary(loss):
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda p: loss(p, batch)[0]))(params)
        return [shapes for shapes in _dot_generals(jaxpr.jaxpr, [])
                if any(VOCAB in shape for shape in shapes)]

    before = telemetry.kernel_paths().get("head_ce", {})
    assert len(against_the_vocabulary(loss_fn)) == 3
    after = telemetry.kernel_paths()["head_ce"]
    assert after["fused"] == before.get("fused", 0) + 1
    assert after.get("per_token", 0) == before.get("per_token", 0)
    assert len(against_the_vocabulary(by_hand)) == 4
    assert telemetry.kernel_paths()["head_ce"]["per_token"] == before.get(
        "per_token", 0) + 1
