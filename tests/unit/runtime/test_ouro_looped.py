"""The looped model (``models/ouro.py``) on the CPU at small sizes: against
the plain reference in float32, what weight sharing means for the count of
parameters and for the gradient, the exit distribution and the loss's parts,
the lifted chunked cross entropy (``GPTNeoX``'s losses what they were), the
scopes and counters the step program publishes, and the scopes added to
``models/llama.py``, which change no program.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deeperspeed_tpu as dst
from benchmarks import core, program_trace
from benchmarks.reference import ouro_ref as ref
from deeperspeed_tpu import telemetry
from deeperspeed_tpu.models import llama as llama_mod
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
from deeperspeed_tpu.models.llama import Llama, LlamaConfig
from deeperspeed_tpu.models.ouro import (Ouro, OuroBlock, OuroConfig,
                                         exit_distribution, exit_entropy)
from deeperspeed_tpu.ops.transformer.cross_entropy import (
    chunked_linear_cross_entropy, weighted_linear_cross_entropy)
from deeperspeed_tpu.parallel.topology import MeshTopology

#: the tiny preset as a configuration file would state it
TINY = {"hidden_size": 64, "vocab_size": 256, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
        "num_hidden_layers": 2, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
        "total_ut_steps": 4, "exit_entropy_beta": 0.1}


def tiny_model(**kw):
    return Ouro(OuroConfig.tiny(**kw))


def batch_of(seed, b=2, s=24):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, TINY["vocab_size"], size=(b, s + 1), dtype=np.int32)
    return {"input_ids": jnp.asarray(toks[:, :-1]),
            "labels": jnp.asarray(toks[:, 1:])}


def flat(tree):
    return {jax.tree_util.keystr(k): np.asarray(v)
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


def shapes(tree):
    return {jax.tree_util.keystr(k): tuple(v.shape)
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


# ------------------------------------------------ against the plain reference
def test_param_tree_and_counts_are_the_references():
    model = tiny_model()
    theirs = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32)))["params"]
    ours = ref.init_params(TINY, 3)
    assert shapes(ours) == shapes(theirs)
    assert model.num_params() == ref.num_params(TINY) == sum(
        v.size for v in flat(ours).values())
    assert model.flops_per_token() == ref.flops_per_token(
        TINY, model.config.max_seq_len)


@pytest.mark.parametrize("seed,remat", [(0, False), (5, True)])
def test_exits_loss_and_gradient_match_the_reference_float32(seed, remat):
    """Every exit's logits, the exit distribution, the loss and the gradient
    of every leaf.  Tolerances: both sides compute in float32 and differ in
    the order of their sums (a fused norm, a chunked head, a scan's carried
    gradient): 2e-5 absolute on logits of order 1, 1e-6 on shares in [0, 1],
    1e-4 of a leaf's gradient norm."""
    model, batch = tiny_model(remat=remat), batch_of(seed)
    params = ref.init_params(TINY, seed)
    logits, p = model.apply({"params": params}, batch["input_ids"])
    want_loss, want_grads, want_lp, want_p = ref.loss_and_grads(
        params, TINY, batch["input_ids"], batch["labels"])
    for b in range(2):
        np.testing.assert_allclose(
            np.asarray(logits[:, b]),
            np.asarray(ref.exit_logits(params, TINY, batch["input_ids"][b])),
            atol=2e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(p[:, 0]), np.asarray(want_p),
                               atol=1e-6)
    token_ll, p_train, _ = model.exits(params, batch["input_ids"],
                                       batch["labels"])
    np.testing.assert_allclose(np.asarray(token_ll[:, 0]),
                               np.asarray(want_lp), atol=2e-5)
    np.testing.assert_allclose(np.asarray(p_train[:, 0]), np.asarray(want_p),
                               atol=1e-6)
    (loss, _), grads = jax.value_and_grad(model.loss_fn(), has_aux=True)(
        params, batch)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    ours, theirs = flat(grads), flat(want_grads)
    assert ours.keys() == theirs.keys()
    for name, want in theirs.items():
        assert np.linalg.norm(ours[name] - want) <= 1e-4 * np.linalg.norm(
            want) + 1e-9, name


# ------------------------------------------------------------ shared weights
def test_parameter_count_does_not_depend_on_the_passes():
    counts = set()
    for passes in (1, 2, 4):
        model = tiny_model(total_ut_steps=passes)
        made = jax.eval_shape(lambda m=model: m.init(
            jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32)))["params"]
        counts.add((sum(int(np.prod(v)) for v in shapes(made).values()),
                    model.num_params()))
    assert len(counts) == 1 and len(set(counts.pop())) == 1
    # ... and the work does: T passes, T heads, T - 1 gates
    one, four = (tiny_model(total_ut_steps=t).flops_per_token()
                 for t in (1, 4))
    assert four == 4 * one + 6 * 3 * 64


def test_gradient_is_the_sum_over_four_unshared_copies():
    """An unrolled plain model with a copy of the stack for every pass: the
    shared model's gradient of a layer's weight is the sum of the four
    copies' gradients (and the loss is the same)."""
    model, batch = tiny_model(), batch_of(7)
    cfg = model.config
    params = ref.init_params(TINY, 7)
    block = OuroBlock(cfg)
    layers = [f"layers_{i}" for i in range(cfg.num_layers)]
    copies = {f"pass_{t}": {k: params[k] for k in layers}
              for t in range(cfg.total_ut_steps)}
    rest = {k: v for k, v in params.items() if k not in layers}

    def unshared_loss(copies, rest):
        ids, labels = batch["input_ids"], batch["labels"]
        positions = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
        h = rest["embed_tokens"]["embedding"][ids]
        hs = []
        for t in range(cfg.total_ut_steps):
            for k in layers:
                h = block.apply({"params": copies[f"pass_{t}"][k]}, h,
                                positions)
            h = ref._rms_norm(h, rest["final_norm"], cfg.rms_eps)
            hs.append(h)
        hs = jnp.stack(hs)
        logits = hs @ rest["lm_head"]["kernel"]
        ll = (jnp.take_along_axis(logits, labels[None, ..., None], -1)[..., 0]
              - jax.nn.logsumexp(logits, -1))
        p = exit_distribution(rest["exit_gate"], hs)
        return jnp.mean(jnp.sum(p * -ll, 0)
                        - cfg.exit_entropy_beta * exit_entropy(p))

    want_loss, (g_copies, g_rest) = jax.value_and_grad(
        unshared_loss, argnums=(0, 1))(copies, rest)
    (loss, _), grads = jax.value_and_grad(model.loss_fn(), has_aux=True)(
        params, batch)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *g_copies.values())
    for name, want in {**flat(summed), **flat(g_rest)}.items():
        got = flat(grads)[name]
        assert np.linalg.norm(got - want) <= 1e-4 * np.linalg.norm(want), name
    # no copy's share is negligible: the sum is not one pass's gradient
    last = flat(g_copies[f"pass_{cfg.total_ut_steps - 1}"])
    name = "['layers_0']['mlp']['down_proj']['kernel']"
    assert np.linalg.norm(flat(grads)[name] - last[name]) > 0.1 * \
        np.linalg.norm(last[name])


def test_one_pass_is_the_plain_stack_with_ordinary_cross_entropy():
    """T = 1: p^1 = 1, H = 0, and the loss is the mean cross entropy of the
    stack's logits."""
    model, batch = tiny_model(total_ut_steps=1), batch_of(11)
    params = ref.init_params(dict(TINY, total_ut_steps=1), 11)
    logits, p = model.apply({"params": params}, batch["input_ids"])
    assert logits.shape[0] == 1 and np.all(np.asarray(p) == 1.0)
    assert np.all(np.asarray(exit_entropy(p)) == 0.0)
    ll = (jnp.take_along_axis(logits[0], batch["labels"][..., None], -1)[..., 0]
          - jax.nn.logsumexp(logits[0], -1))
    loss, stats = model.loss_fn()(params, batch)
    assert abs(float(loss) + float(jnp.mean(ll))) < 1e-5
    assert float(stats["exit_entropy"]) == 0.0
    assert int(stats["layer_applications"]) == 2
    assert int(stats["head_applications"]) == 1


# ------------------------------------------- the exit distribution, the loss
def test_shares_add_up_to_one_and_the_entropy_term_is_subtracted():
    model, batch = tiny_model(), batch_of(13)
    params = ref.init_params(TINY, 13)
    # a gate with an opinion: shares far from 1/2, 1/4, 1/8, 1/8
    params["exit_gate"]["kernel"] = 0.5 * jnp.sign(
        params["exit_gate"]["kernel"])
    token_ll, p, _ = model.exits(params, batch["input_ids"], batch["labels"])
    p = np.asarray(p)
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    assert p.min() >= 0 and np.ptp(p[0]) > 0.5
    entropy = np.asarray(exit_entropy(jnp.asarray(p)))
    assert entropy.min() >= 0 and entropy.max() <= np.log(4) + 1e-6
    expected = np.mean(np.sum(p * -np.asarray(token_ll), 0))
    losses = {beta: float(tiny_model(exit_entropy_beta=beta).loss_fn()(
        params, batch)[0]) for beta in (0.0, 0.1, 1.0)}
    assert abs(losses[0.0] - expected) < 1e-5
    # a larger beta rewards a spread-out distribution: the loss falls by
    # beta times the mean entropy
    assert abs(losses[0.0] - losses[1.0] - entropy.mean()) < 1e-5
    assert losses[1.0] < losses[0.1] < losses[0.0]
    _, stats = model.loss_fn()(params, batch)
    np.testing.assert_allclose(np.asarray(stats["exit_share"]),
                               p.mean((1, 2)), atol=1e-6)
    assert abs(float(stats["exit_entropy"]) - entropy.mean()) < 1e-6
    # a loss mask leaves the masked tokens out of loss and statistics alike
    mask = jnp.zeros_like(batch["labels"]).at[:, :5].set(1).astype(jnp.float32)
    masked, mstats = model.loss_fn()(params, dict(batch, loss_mask=mask))
    want = np.mean((np.sum(p * -np.asarray(token_ll), 0)
                    - 0.1 * entropy)[:, :5])
    assert abs(float(masked) - want) < 1e-5
    np.testing.assert_allclose(np.asarray(mstats["exit_share"]),
                               p[:, :, :5].mean((1, 2)), atol=1e-6)


def test_a_zero_gate_splits_a_half_a_quarter_an_eighth_and_the_rest():
    hs = jnp.ones((4, 3, 8))
    p = exit_distribution({"kernel": jnp.zeros((8, 1)), "bias": jnp.zeros(1)},
                          hs)
    np.testing.assert_array_equal(
        np.asarray(p[:, 0]), np.array([0.5, 0.25, 0.125, 0.125], np.float32))
    assert float(exit_entropy(jnp.array([[1.0], [0.0]]))[0]) == 0.0


# ------------------------------------------------ the lifted cross entropy
@pytest.mark.parametrize("tokens,chunk", [(40, 16), (48, 48), (30, 64)])
def test_chunked_cross_entropy_gives_the_unchunked_per_token_values(tokens,
                                                                    chunk):
    rng = np.random.default_rng(tokens)
    x = jnp.asarray(rng.normal(size=(tokens, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 100)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 100, size=tokens), jnp.int32)

    def plain(x, w):
        logits = x @ w
        return (jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
                - jax.nn.logsumexp(logits, -1))

    got = chunked_linear_cross_entropy(x, w, labels, chunk)
    assert got.shape == (tokens,) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(plain(x, w)),
                               atol=2e-6)
    weights = jnp.asarray(rng.normal(size=tokens), jnp.float32)
    g_got = jax.grad(lambda x, w: jnp.sum(chunked_linear_cross_entropy(
        x, w, labels, chunk) * weights), argnums=(0, 1))(x, w)
    g_want = jax.grad(lambda x, w: jnp.sum(plain(x, w) * weights),
                      argnums=(0, 1))(x, w)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    # a bfloat16 head handed over as float32: the same values, and the
    # gradient comes back in float32 (the sum over the chunks is kept there)
    xb, wb = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    same = chunked_linear_cross_entropy(xb, wb.astype(jnp.float32), labels,
                                        chunk)
    np.testing.assert_array_equal(
        np.asarray(same),
        np.asarray(chunked_linear_cross_entropy(xb, wb, labels, chunk)))
    assert jax.grad(lambda w: jnp.sum(chunked_linear_cross_entropy(
        xb, w, labels, chunk)))(wb.astype(jnp.float32)).dtype == jnp.float32


# ------------------------------- the weighted sum that makes its gradient
def _plain_weighted_sum(x, w, labels, weights):
    """The unchunked float32 expression, for autodiff to differentiate."""
    logits = x.astype(jnp.float32) @ w.astype(jnp.float32)
    return jnp.sum(weights * (
        jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
        - jax.nn.logsumexp(logits, -1)))


def _head_operands(tokens, dtype, hidden=32, vocab=100):
    rng = np.random.default_rng(tokens)
    return (jnp.asarray(rng.normal(size=(tokens, hidden)), dtype),
            jnp.asarray(rng.normal(size=(hidden, vocab)) / 4, dtype),
            jnp.asarray(rng.integers(0, vocab, size=tokens), jnp.int32),
            jnp.asarray(rng.normal(size=tokens), jnp.float32))


#: float32 differs from autodiff in the order of its sums; bfloat16 rounds
#: the logits and their cotangent to 8 bits where the plain expression, in
#: float32 on the same bfloat16 inputs, rounds nothing
TOLERANCE = {"float32": 1e-5, "bfloat16": 1e-2}


@pytest.mark.parametrize("dtype", list(TOLERANCE))
@pytest.mark.parametrize("tokens,chunk", [(40, 16), (48, 48), (30, 64)],
                         ids=["tail_chunk", "one_chunk", "chunk_over_tokens"])
def test_weighted_sum_and_its_gradients_are_autodiffs_of_the_plain_one(
        tokens, chunk, dtype):
    x, w, labels, weights = _head_operands(tokens, getattr(jnp, dtype))
    tol = TOLERANCE[dtype]

    def fused(x, w, weights):
        total, chunks = weighted_linear_cross_entropy(x, w, labels, weights,
                                                      chunk)
        return 3.0 * total, chunks      # a cotangent that is not 1

    # no gradient asked: the primal walk, which counts its chunks too
    total, chunks = fused(x, w, weights)
    assert total.dtype == jnp.float32 and int(chunks) == -(-tokens // chunk)
    (got, chunks), grads = jax.jit(jax.value_and_grad(
        fused, argnums=(0, 1, 2), has_aux=True))(x, w, weights)
    assert int(chunks) == -(-tokens // chunk)
    want, want_grads = jax.value_and_grad(
        lambda *a: 3.0 * _plain_weighted_sum(a[0], a[1], labels, a[2]),
        argnums=(0, 1, 2))(x, w, weights)
    assert abs(float(got) - float(total)) <= 1e-6 * abs(float(total))
    assert abs(float(got) - float(want)) <= tol * abs(float(want))
    for a, b, like in zip(grads, want_grads, (x, w, weights)):
        assert a.dtype == like.dtype and a.shape == like.shape
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.linalg.norm(a - b) <= tol * np.linalg.norm(b)


def test_weighted_sum_ignores_a_token_of_weight_zero_and_pads_with_them():
    x, w, labels, weights = _head_operands(30, jnp.float32)
    weights = weights.at[7].set(0.0)
    grad = jax.grad(lambda x: weighted_linear_cross_entropy(
        x, w, labels, weights, 16)[0])(x)
    assert np.all(np.asarray(grad[7]) == 0.0) and np.all(
        np.abs(np.asarray(grad)).sum(-1)[np.arange(30) != 7] > 0)
    x7 = x.at[7].set(100.0)
    assert float(weighted_linear_cross_entropy(x7, w, labels, weights, 16)[0]
                 ) == float(weighted_linear_cross_entropy(
                     x, w, labels, weights, 16)[0])


def test_weighted_sum_with_the_vocabulary_sharded_over_tp():
    """The head as the models place it, ``P(None, "tp")``: plain ``jnp``
    inside the scan, so the partitioner divides the walk; the same numbers
    as on one device."""
    from jax.sharding import Mesh, NamedSharding

    x, w, labels, weights = _head_operands(40, jnp.float32, vocab=128)

    def fused(x, w, weights):
        return weighted_linear_cross_entropy(x, w, labels, weights, 16)[0]

    want, want_grads = jax.value_and_grad(fused, argnums=(0, 1, 2))(
        x, w, weights)
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    w_tp = jax.device_put(w, NamedSharding(mesh, P(None, "tp")))
    got, grads = jax.jit(jax.value_and_grad(fused, argnums=(0, 1, 2)))(
        x, w_tp, weights)
    assert grads[1].sharding.spec == P(None, "tp")
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    for a, b in zip(grads, want_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def _loss_chunked_as_it_was(model, cfg):
    """``GPTNeoX.loss_fn``'s chunked closure as PR 28 left it, kept here
    word for word as the yardstick of the lift."""
    def loss_chunked(params, batch):
        hidden = model.apply({"params": params}, batch["input_ids"],
                             deterministic=True, rngs=None,
                             return_hidden=True, pld_theta=None,
                             random_ltd_tokens=None)
        w = params["embed_out"]["kernel"]          # [H, V]
        B, S, H = hidden.shape
        labels = batch["labels"].reshape(-1)
        mask = batch.get("loss_mask")
        mask = (jnp.ones((B * S,), jnp.float32) if mask is None
                else mask.reshape(-1).astype(jnp.float32))
        T = B * S
        C = min(cfg.ce_chunk_tokens, T)
        n_chunks = -(-T // C)
        pad = n_chunks * C - T
        x = hidden.reshape(T, H)
        if pad:
            x = jnp.pad(x, ((0, pad), (0, 0)))
            labels = jnp.pad(labels, (0, pad))
            mask = jnp.pad(mask, (0, pad))
        x = x.reshape(n_chunks, C, H)
        labels = labels.reshape(n_chunks, C)
        mask = mask.reshape(n_chunks, C)

        def chunk(carry, op):
            num, den = carry
            xc, lc, mc = op
            logits = (xc @ w.astype(xc.dtype)).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, lc[:, None],
                                       axis=-1)[:, 0]
            num = num + jnp.sum((gold - lse) * mc)
            den = den + jnp.sum(mc)
            return (num, den), None

        with jax.named_scope("head_ce"):
            (num, den), _ = jax.lax.scan(
                jax.checkpoint(chunk),
                (jnp.float32(0.0), jnp.float32(0.0)), (x, labels, mask))
            return -num / jnp.maximum(den, 1.0)

    return loss_chunked


#: ``GPTNeoX.loss_fn()`` of the tiny preset (seed-1 weights, the seed-3
#: example batch of 3 x 20 tokens), read from PR 28's tree before the lift:
#: {(dtype, ce_chunk_tokens, with a loss mask): loss}
LOSSES_AS_THEY_WERE = {
    ("float32", 0, False): 5.95705509185791,
    ("float32", 0, True): 6.010775566101074,
    ("float32", 24, False): 5.95705509185791,
    ("float32", 24, True): 6.010775566101074,
    ("float32", 64, False): 5.957055568695068,
    ("float32", 64, True): 6.010775566101074,
    ("bfloat16", 0, False): 5.9565815925598145,
    ("bfloat16", 0, True): 6.009844779968262,
    ("bfloat16", 24, False): 5.956715106964111,
    ("bfloat16", 24, True): 6.009972095489502,
    ("bfloat16", 64, False): 5.956715106964111,
    ("bfloat16", 64, True): 6.009971618652344,
}


@pytest.mark.parametrize("dtype,chunk,masked", sorted(LOSSES_AS_THEY_WERE))
def test_gpt_neox_losses_are_what_they_were(dtype, chunk, masked):
    """The monolithic loss bit for bit.  The chunked one weights the tokens
    ``-mask / count`` before the walk (PR 44) where PR 28 divided the sum
    after it, and adds the head's gradient up in float32 where PR 28 rounded
    a chunk's to the head's dtype first: the same loss to a float32 rounding,
    the same gradient to the dtype's."""
    cfg = GPTNeoXConfig.tiny(ce_chunk_tokens=chunk, dtype=getattr(jnp, dtype))
    model = GPTNeoX(cfg)
    batch = model.example_batch(batch_size=3, seq_len=20, seed=3)
    if masked:
        batch["loss_mask"] = (jnp.arange(60).reshape(3, 20) % 7 != 0).astype(
            jnp.float32)
    params = model.init(jax.random.PRNGKey(1), batch["input_ids"])["params"]
    loss, grads = jax.jit(jax.value_and_grad(model.loss_fn()))(params, batch)
    want = LOSSES_AS_THEY_WERE[dtype, chunk, masked]
    if not chunk:
        assert float(loss) == want
        return
    assert abs(float(loss) - want) <= 2e-7 * want
    was, was_grads = jax.jit(jax.value_and_grad(
        _loss_chunked_as_it_was(model, cfg)))(params, batch)
    assert float(was) == want
    tol = 1e-5 if dtype == "float32" else 1e-2
    for (name, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(was_grads)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.linalg.norm(a - b) <= tol * np.linalg.norm(b), name


# -------------------------------------------- through the engine: tracing
def one_device():
    return MeshTopology(devices=jax.devices()[:1])


def looped_engine(replicas=1, config=None, **model_kw):
    model = tiny_model(dtype=jnp.bfloat16, remat=True, **model_kw)
    engine, _, _, _ = dst.initialize(
        model=model, mesh=MeshTopology(devices=jax.devices()[:replicas]),
        config={
            "train_batch_size": 4,
            "train_micro_batch_size_per_gpu": 2 // replicas,
            "gradient_accumulation_steps": 2,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "bf16": {"enabled": True}, "zero_optimization": {"stage": 0},
            "gradient_clipping": 1.0, "steps_per_print": 10 ** 9,
            **(config or {})})
    return engine, model.example_batch(batch_size=4, seq_len=32)


#: every reduction of ``runtime/grad_reduce.py`` runs the one micro body, so
#: each hands on what the model says of its step: (replicas, config on top)
REDUCTIONS = {
    "per_microbatch": (1, {}),
    "deferred": (2, {"comm": {"overlap": {"enabled": True}}}),
    "onebit": (2, {"optimizer": {"type": "OneBitAdam", "params": {
        "lr": 1e-3, "freeze_step": 3}}}),
    "qgz": (2, {"comm": {"quantized": {"enabled": True}}}),
}


@pytest.mark.parametrize("reduction", list(REDUCTIONS))
def test_engine_trains_the_looped_model_and_publishes_its_counters(
        reset_mesh, reduction):
    from deeperspeed_tpu.telemetry import trace

    trace._STEP_TIMELINE.clear()
    engine, batch = looped_engine(*REDUCTIONS[reduction])
    assert engine._reduction.name == reduction
    losses = [float(engine.train_batch(batch=batch)) for _ in range(6)]
    assert losses[-1] < losses[0] - 0.5
    counters = telemetry.step_counters()["train_step"]
    # 2 layers x T = 4 passes, T heads, a microbatch (the mean over two)
    assert counters["layer_applications"] == 8
    assert counters["head_applications"] == 4
    assert len(counters["exit_share"]) == 4
    assert abs(sum(counters["exit_share"]) - 1.0) < 1e-5
    assert 0 < counters["exit_entropy"] <= np.log(4) + 1e-6
    # the gate and the embedding table stay float32 under bf16
    master = engine.state["master_params"]
    assert master["exit_gate"]["kernel"].shape == (64, 1)
    mask = engine._no_cast
    assert mask["exit_gate"]["kernel"] and mask["embed_tokens"]["embedding"]
    assert not mask["lm_head"]["kernel"]


def test_a_model_without_counters_publishes_none():
    from deeperspeed_tpu.telemetry import trace

    trace._STEP_TIMELINE.clear()
    model = GPTNeoX(GPTNeoXConfig.tiny())
    engine, _, _, _ = dst.initialize(model=model, mesh=one_device(), config={
        "train_batch_size": 4, "optimizer": {"type": "Adam", "params": {
            "lr": 1e-3}}, "steps_per_print": 10 ** 9})
    engine.train_batch(batch=model.example_batch(batch_size=4, seq_len=16))
    assert telemetry.step_counters() == {}
    assert "model" not in engine._last_metrics


def test_looped_step_program_publishes_its_scopes(tmp_path):
    engine, batch = looped_engine()
    engine.train_batch(batch=batch)
    telemetry.step_scopes().clear()
    jax.profiler.start_trace(str(tmp_path))
    engine.train_batch(batch=batch)
    jax.profiler.stop_trace()
    engine.train_batch(batch=batch)      # the step after a session publishes
    names = set(telemetry.step_scopes()["jit_train_step"].values())
    telemetry.step_scopes().clear()
    # (``attention_layout`` holds only free reshapes on the CPU: the copies
    # it names are around the TPU's flash kernel)
    for scope in ("attention", "mlp", "head_ce", "exit_gate", "embed",
                  "optimizer"):
        assert any(f"/{scope}/" in n or f"({scope})" in n for n in names), scope
    # the gate's work is inside the head's scope, as the readers see both
    gate_reader = core.layer_metric_reader("train.scope_ms.exit_gate")
    gate = [n for n in names if gate_reader.under_scope(n)]
    assert gate and all("head_ce" in program_trace.scopes_of(n) for n in gate)
    # the stack is traced once and run T times: one while loop over the
    # passes, whose body holds the layers' scopes
    assert any("/while/body/" in n and "/attention/" in n for n in names)


# ------------------------------------------------ models/llama.py's scopes
@pytest.mark.parametrize("preset", ["tiny", "tiny_mistral", "tiny_opt"])
def test_llama_scopes_change_no_program(preset, monkeypatch):
    """The lowered program with the named scopes and with every named scope
    turned into nothing: the same operations, letter for letter."""
    model = Llama(getattr(LlamaConfig, preset)())
    batch = model.example_batch(batch_size=2, seq_len=16)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), batch["input_ids"]))["params"]

    def lowered():
        return jax.jit(jax.value_and_grad(model.loss_fn())).lower(
            params, batch).as_text()

    with_scopes = lowered()
    jax.clear_caches()
    monkeypatch.setattr(llama_mod.jax, "named_scope",
                        lambda _name: contextlib.nullcontext())
    without = lowered()
    monkeypatch.undo()
    jax.clear_caches()
    assert with_scopes == without and "stablehlo.dot_general" in with_scopes


def test_llama_path_now_carries_the_readers_scopes():
    model = Llama(LlamaConfig.tiny())
    batch = model.example_batch(batch_size=2, seq_len=16)
    params = model.init(jax.random.PRNGKey(0), batch["input_ids"])["params"]
    text = jax.jit(jax.value_and_grad(model.loss_fn())).lower(
        params, batch).compile().as_text()
    for scope in ("embed", "attention", "attention_layout", "mlp", "head_ce"):
        assert f"/{scope}/" in text, scope


def test_llama_counts_stay_right_for_the_shared_parts():
    for preset in ("tiny", "tiny_opt"):
        model = Llama(getattr(LlamaConfig, preset)())
        params = model.init(jax.random.PRNGKey(0),
                            jnp.ones((1, 8), jnp.int32))["params"]
        assert model.num_params() == sum(
            x.size for x in jax.tree_util.tree_leaves(params))
    cfg = LlamaConfig.tiny()
    n = Llama(cfg).num_params() - cfg.vocab_size * cfg.hidden_size
    assert Llama(cfg).flops_per_token() == 6 * n + 12 * cfg.num_layers * \
        cfg.hidden_size * cfg.max_seq_len
    # the looped model's block is those parts plus two more norms
    looped = tiny_model(num_kv_heads=2)
    block = jax.eval_shape(lambda: OuroBlock(looped.config).init(
        jax.random.PRNGKey(0), jnp.ones((1, 8, 64)),
        jnp.zeros((1, 8), jnp.int32)))["params"]
    llama_layer = (Llama(cfg).num_params() - 2 * cfg.vocab_size
                   * cfg.hidden_size - cfg.hidden_size) // cfg.num_layers
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
        block)) == llama_layer + 2 * cfg.hidden_size
