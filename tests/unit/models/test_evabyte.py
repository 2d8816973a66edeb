"""EvaByte (``models/evabyte.py``) against the plain reference
(``benchmarks/reference/evabyte_ref.py``) at a tiny size on the CPU, three
windows of 64 bytes in chunks of 8: the logits of all eight slices, the
loss, the gradient of every parameter and one Adam update through the
engine; the share test (the outputs of ``W_o`` over the two halves of the
heads add up to the uncut reference's attention output, and the block's
output follows with norms and MLP counted once); the eight targets and the
mask at the end; the float32 islands under bfloat16; the cut's arithmetic;
every attention call of the model takes the kernel (counted in a fresh
trace); and the model through ``dst.initialize`` / ``engine.train_batch``
under a warm-up."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import core
from benchmarks.reference import evabyte_ref as ref
from deeperspeed_tpu import telemetry
from deeperspeed_tpu.models.evabyte import (EvaAttention, EvaByte,
                                            EvaByteBlock, EvaByteConfig,
                                            byte_targets)

runner = core.load_runner("train_evabyte")
TINY = core.load_json(core.BENCH_DIR + "/configs/tiny-evabyte-rehearsal.json")
CELL = core.load_json(core.BENCH_DIR + "/configs/evabyte-6.5b.json")
TRAFFIC = {"seq_len": 192, "micro_batch": 2, "dtype": "float32",
           "ce_chunk_tokens": 80}
S = 192


def _ids(seed, b=2, s=S):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, TINY["vocab_size"], size=(b, s + 1), dtype=np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


def _params(seed, cfg=TINY):
    """Seeded weights with the norms' weights moved off zero, so that the
    unit offset is part of what is compared."""
    params = ref.init_params(cfg, seed)

    def moved(path, x):
        if not str(path[-1].key).endswith("norm_weight"):
            return x
        return x + 0.2 * jnp.sin(jnp.arange(x.size, dtype=jnp.float32))

    return jax.tree_util.tree_map_with_path(moved, params)


def _rel(got, want):
    got, want = (jax.tree_util.tree_leaves(t) for t in (got, want))
    num = sum(float(jnp.sum(jnp.square(a - b))) for a, b in zip(got, want))
    return (num / sum(float(jnp.sum(jnp.square(b))) for b in want)) ** 0.5


# -------------------------------------------------- against the reference
def test_the_tree_and_the_count_are_the_references():
    model = runner.program_model(TINY, TRAFFIC)
    ids, _ = _ids(1)
    made = model.init(jax.random.PRNGKey(0), ids)["params"]
    want = ref.init_params(TINY, 1)
    assert jax.tree_util.tree_structure(made) == jax.tree_util.tree_structure(
        want)
    assert jax.tree_util.tree_map(jnp.shape, made) == jax.tree_util.tree_map(
        jnp.shape, want)
    assert model.num_params() == ref.num_params(TINY) == sum(
        x.size for x in jax.tree_util.tree_leaves(made))
    # the directions start clipped and scaled, the norms' weights at zero
    mu = made["layers_0"]["attn"]["adaptive_mu_k"]
    assert float(jnp.abs(mu).max()) <= 16 ** -0.5 + 1e-6
    assert float(jnp.abs(made["final_norm_weight"]).max()) == 0.0
    assert model.flops_per_token(S) == pytest.approx(
        ref.flops_per_token(TINY, S))


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_logits_of_all_eight_slices_loss_and_gradients(seed):
    """Tolerances: float32 on both sides, so what differs is the order of
    sums (a chunked head, a fused norm's statistics): 2e-5 of a logit,
    1e-5 of the gradient's norm."""
    model = runner.program_model(TINY, TRAFFIC)
    params, (ids, labels) = _params(seed), _ids(seed)
    hidden = model.apply({"params": params}, ids)[0]
    got = (hidden @ params["lm_head_kernel"]).reshape(2, S, 8, -1)
    for b in range(2):
        want = ref.logits(params, TINY, ids[b])
        assert want.shape == (S, 8, TINY["vocab_size"])
        np.testing.assert_allclose(got[b], want, rtol=2e-5, atol=2e-5)
    (loss, told), grads = jax.value_and_grad(
        model.loss_fn(), has_aux=True)(params, {"input_ids": ids,
                                                "labels": labels})
    want_loss, want_grads, want_lp = ref.loss_and_grads(params, TINY, ids,
                                                        labels)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    assert float(want_loss) == pytest.approx(float(ref.loss(
        params, TINY, ids, labels)[0]), rel=1e-6)
    assert _rel(grads, want_grads) < 1e-5
    # every class of parameter has a gradient of its own to compare
    for path, g in jax.tree_util.tree_leaves_with_path(want_grads):
        assert float(jnp.abs(g).max()) > 0, path
    lp, inside = model.logprobs(params, ids, labels)
    np.testing.assert_allclose(lp[0], want_lp, rtol=2e-5, atol=2e-5)
    assert int(inside.sum()) == 2 * (8 * S - 28)
    assert told["layer_applications"] == 2
    assert told["eva_pairs_needed"] == 2 * 2 * 2 * ref.pairs_needed(TINY, S)
    assert told["eva_pairs_visited"] >= told["eva_pairs_needed"]
    assert told["head_chunks"] == -(-2 * S // 80)


def test_one_adam_update_through_the_engine():
    """The engine's first step from the seeded weights against the
    reference's clipped gradient and Adam step at the warm-up's first rate:
    the runner's own comparison, at float32 (1e-4: Adam divides by the
    gradient's magnitude, so a rounding of a small gradient shows)."""
    import types

    traffic = dict(TRAFFIC, clip=1.0, zero_stage=0, token_dist={
        "kind": "zipf", "exponent": 1.1}, optimizer={
            "type": "Adam", "lr": 1e-4, "betas": [0.9, 0.999], "eps": 1e-8},
        scheduler={"type": "WarmupLR", "params": {
            "warmup_min_lr": 1e-6, "warmup_max_lr": 1e-4,
            "warmup_num_steps": 2000, "warmup_type": "linear"}})
    ctx = types.SimpleNamespace(config=TINY, traffic=traffic,
                                cell={"chips": 1})
    engine, batches, first_loss, left = runner.start_engine(ctx, 17)
    got = runner.against_reference(ctx, 17, first_loss, left,
                                   controls=True)
    sound = got["program"]
    assert sound["grad_rel_err"] < 1e-5
    assert sound["adam_update_rel_err"] < 1e-4
    assert sound["first_loss_abs_diff"] < 1e-5
    assert sound["logprob_rms"] < 1e-5 and sound["targets"] == 8 * S - 28
    # a state left unchanged reads 1, bfloat16 masters lose a 1e-6 step
    assert got[runner.UNCHANGED]["adam_update_rel_err"] == pytest.approx(1.0)
    assert got["control_bf16_masters"]["adam_update_rel_err"] > 0.3
    # every control is another computation
    assert got["control_fp8"]["grad_rel_err"] > 0.05
    assert got[runner.ISLANDS]["logprob_rms"] > 100 * sound["logprob_rms"]
    assert got[runner.NO_SUMMARIES]["grad_rel_err"] > 0.01
    assert left["counters"]["layer_applications"] == 2


def test_a_mechanism_left_out_is_another_model():
    params, (ids, labels) = _params(5), _ids(5)
    base = ref.token_logprobs(params, TINY, ids[0], labels[0])[0]
    for mechanism in ref.MECHANISMS:
        other = ref.token_logprobs(params, TINY, ids[0], labels[0],
                                   without=(mechanism,))[0]
        # the first window sees no summary either way
        np.testing.assert_allclose(other[:56], base[:56], atol=1e-6)
        assert float(jnp.abs(other[64:] - base[64:]).max()) > 1e-4, mechanism
    with pytest.raises(ValueError):
        ref.token_logprobs(params, TINY, ids[0], labels[0],
                           without=("rotary",))


# ------------------------------------------------------------- the share
def test_the_shares_of_the_heads_add_up_to_the_whole_layer():
    """Two chips share a layer, the heads two ways: the outputs of ``W_o``
    over the two halves add up to the uncut reference's attention output,
    and the block's output follows with the norms and the MLP counted
    once."""
    whole_cfg = {k: v for k, v in TINY.items()
                 if k not in ("attention_heads_held", "first_head_held")}
    params = _params(9, whole_cfg)
    x = jax.random.normal(jax.random.PRNGKey(9), (S, 64), jnp.float32)
    layer = params["layers_1"]
    u = ref._rms_norm(x, layer["input_norm_weight"], 1e-5)
    want = ref.attention_output(params, whole_cfg, 1, u)
    parts = []
    for first in (0, 2):
        cfg = dict(whole_cfg, attention_heads_held=2, first_head_held=first)
        share = ref.take_heads(params, whole_cfg, first, 2)
        assert share["layers_1"]["attn"]["q_proj"]["kernel"].shape == (64, 32)
        assert share["layers_1"]["attn"]["adaptive_phi"].shape == (2, 16)
        # the reference given the share ...
        ref_part = ref.attention_output(share, cfg, 1, u)
        # ... and the program's sublayer on the share's weights
        mc = runner.program_model(cfg, TRAFFIC).config
        assert mc.heads == 2 and mc.first_head_held == first
        got = EvaAttention(mc).apply(
            {"params": share["layers_1"]["attn"]}, u[None])[0]
        np.testing.assert_allclose(got, ref_part, rtol=2e-5, atol=2e-5)
        parts.append(got)
    np.testing.assert_allclose(parts[0] + parts[1], want, rtol=2e-5,
                               atol=2e-5)
    assert float(jnp.abs(parts[0] - parts[1]).max()) > 1e-3
    # the block: norms and MLP are every chip's alike and count once
    h = x + want
    m = ref._mlp(ref._rms_norm(h, layer["post_norm_weight"], 1e-5),
                 layer["mlp"], "float32")
    with jax.default_matmul_precision("highest"):
        want_y = ref._layer(x, layer, whole_cfg, jnp.arange(S), "float32",
                            "float32", ())
    np.testing.assert_allclose(h + m, want_y, rtol=1e-5, atol=1e-5)
    # a share's block is the stream, the MLP of ITS h and its heads' part:
    # the program's block on the share's weights is the reference's on them
    cfg = dict(whole_cfg, attention_heads_held=2, first_head_held=2)
    share = ref.take_heads(params, whole_cfg, 2, 2)["layers_1"]
    got_y = EvaByteBlock(runner.program_model(cfg, TRAFFIC).config).apply(
        {"params": share}, x[None])[0][0]
    with jax.default_matmul_precision("highest"):
        ref_y = ref._layer(x, share, cfg, jnp.arange(S), "float32", "float32",
                           ())
    np.testing.assert_allclose(got_y, ref_y, rtol=2e-5, atol=2e-5)


def test_the_cut_is_one_stage_and_half_the_heads():
    model = runner.program_model(CELL, {"seq_len": 16384,
                                        "ce_chunk_tokens": 2048})
    cfg = model.config
    assert (cfg.layers, cfg.first_layer_held, cfg.heads,
            cfg.first_head_held) == (4, 12, 16, 0)
    assert (cfg.hidden_size, cfg.head_dim, cfg.intermediate_size,
            cfg.vocab_size, cfg.num_pred_heads, cfg.window_size,
            cfg.chunk_size) == (4096, 128, 11008, 320, 8, 2048, 16)
    layer = 33_558_528 + 135_266_304 + 8_192
    assert layer == 168_833_024
    assert model.num_params() == ref.num_params(CELL) == (
        4 * layer + 1_310_720 + 10_485_760 + 4_096) == 687_132_672
    # a four-chip host's share of the heads
    assert ref.num_params(dict(CELL, attention_heads_held=8)) == 620_015_616
    kinds = model.flops_by_kind(16384)
    total = sum(kinds.values())
    assert total == pytest.approx(ref.flops_per_token(CELL, 16384))
    # EVA is about 3 % of the step's FLOPs here, about 5 % with whole heads
    eva_share = (kinds["eva_attend"] + kinds["eva_pool"]) / total
    assert 0.025 < eva_share < 0.04
    whole = runner.program_model(dict(CELL, attention_heads_held=32), {
        "seq_len": 16384, "ce_chunk_tokens": 2048}).flops_by_kind(16384)
    assert 0.045 < (whole["eva_attend"] + whole["eva_pool"]) / sum(
        whole.values()) < 0.065
    assert 0.01 < kinds["head"] / total < 0.02


# ------------------------------------------- the eight targets and the mask
def test_the_eight_targets_and_the_mask_at_the_end():
    labels = jnp.arange(1, 21)[None]            # byte t + 1 at t, S = 20
    want, inside = byte_targets(labels, 8)
    assert want.shape == inside.shape == (1, 20, 8)
    np.testing.assert_array_equal(want[0, 0], np.arange(1, 9))
    np.testing.assert_array_equal(want[0, 12], np.arange(13, 21))
    # position 13 has seven targets, the last position one
    np.testing.assert_array_equal(inside[0, 13], [1] * 7 + [0])
    np.testing.assert_array_equal(inside[0, 19], [1] + [0] * 7)
    np.testing.assert_array_equal(want[0, 19], [20] + [0] * 7)
    assert int(inside.sum()) == 8 * 20 - 28
    ref_want, ref_inside = ref.targets(labels[0], 8)
    np.testing.assert_array_equal(want[0], ref_want)
    np.testing.assert_array_equal(inside[0], ref_inside)
    # a loss mask is a mask on the TARGET's position
    model = runner.program_model(TINY, TRAFFIC)
    params, (ids, labels) = _params(7), _ids(7)
    batch = {"input_ids": ids, "labels": labels}
    mask = jnp.ones((2, S)).at[:, 100:].set(0.0)
    masked = float(model.loss_fn()(params, dict(batch, loss_mask=mask))[0])
    lp, inside = model.logprobs(params, ids, labels)
    ahead = jnp.arange(S)[:, None] + jnp.arange(8)[None, :]
    kept = inside & (ahead < 100)[None]
    assert masked == pytest.approx(float(-jnp.sum(jnp.where(kept, lp, 0.0))
                                         / kept.sum()), rel=1e-5)


# --------------------------------------------------- the float32 islands
def test_the_stream_the_statistics_and_the_logits_stay_float32():
    """Under bfloat16 the block's input and output are float32 and its
    projections read bfloat16; the embedding, the directions and the norms'
    weights are not cast."""
    model = runner.program_model(TINY, dict(TRAFFIC, dtype="bfloat16"))
    cfg = model.config
    params = _params(2)
    x = jnp.ones((1, S, 64), jnp.float32)
    y = EvaByteBlock(cfg).apply({"params": params["layers_0"]}, x)[0]
    assert y.dtype == jnp.float32
    jaxpr = str(jax.make_jaxpr(lambda p, x: EvaByteBlock(cfg).apply(
        {"params": p}, x)[0])(params["layers_0"], x))
    assert "bf16[1,192,64]" in jaxpr and "f32[1,192,64]" in jaxpr
    hidden = model.apply({"params": params}, jnp.zeros((1, S), jnp.int32))[0]
    assert hidden.dtype == jnp.bfloat16
    import re
    keep = [re.compile(p) for p in model.no_cast_paths()]
    names = ["/".join(str(k.key) for k in path) for path, _ in
             jax.tree_util.tree_leaves_with_path(params)]
    kept = {n for n in names if any(p.search(n) for p in keep)}
    assert "embed_tokens/embedding" in kept
    assert "layers_0/attn/adaptive_mu_k" in kept
    assert "layers_1/post_norm_weight" in kept and "final_norm_weight" in kept
    assert not any("kernel" in n for n in kept)
    rules = dict(model.param_partition_rules())
    assert any("adaptive_" in pattern for pattern in rules)


# ----------------------------------------------------------------- the kernel
def test_every_attention_call_of_the_model_takes_the_kernel(monkeypatch):
    """With the accelerator's kernels on and shapes the kernels tile, both
    layers count an ``eva_attention`` call and an ``eva_pool`` call
    (interpret mode here) in a FRESH trace, and the result is the plain
    path's."""
    from deeperspeed_tpu.accelerator import get_accelerator
    from deeperspeed_tpu.ops.attention import pallas_eva, pallas_eva_pool

    cfg = dict(TINY, hidden_size=64, num_attention_heads=4)
    params, (ids, labels) = _params(13, cfg), _ids(13, b=1)
    model = runner.program_model(cfg, TRAFFIC)
    plain = model.logprobs(params, ids, labels)[0]
    monkeypatch.setattr(type(get_accelerator()), "use_pallas_kernels",
                        lambda self: True)
    # this tiny head is no lane tile: say the shapes compile, as the cell's do
    monkeypatch.setattr(pallas_eva, "compiles_for_tpu", lambda *a: True)
    monkeypatch.setattr(pallas_eva_pool, "compiles_for_tpu", lambda *a: True)
    # ``count_kernel_path`` counts when a call is TRACED
    jax.clear_caches()
    before = {name: dict(telemetry.kernel_paths().get(name, {}))
              for name in ("eva_attention", "eva_pool")}
    got = model.logprobs(params, ids, labels)[0]
    after = telemetry.kernel_paths()
    for name, was in before.items():
        assert after[name]["in_place_1"] == was.get("in_place_1", 0) + 2
        assert after[name].get("plain", 0) == was.get("plain", 0)
    np.testing.assert_allclose(got, plain, rtol=2e-5, atol=2e-5)
    told = model.counters(1, S)
    assert told["eva_pairs_visited"] == 2 * 2 * (
        3 * 64 * 64 + 64 * 8 * 3)      # one row group a window: its square
    monkeypatch.setattr(type(get_accelerator()), "use_pallas_kernels",
                        lambda self: False)
    jax.clear_caches()


@pytest.mark.parametrize("body,bodies,calls_a_layer", [
    ("_fwd_call", 2, 2), ("_bwd_call", 1, 1)])
def test_the_pooling_kernels_are_lowered_once_for_all_the_layers(
        monkeypatch, body, bodies, calls_a_layer):
    """What a cell's set-up pays for the pooling kernels must not grow with
    the layers (PERF.md section 6, PR 51: a kernel's body is straight-line
    code over its block, and traced and lowered at every call site it took a
    warm ``setup_s`` from 31 s to 70): the jitted forward and backward calls
    are bodies of the recomputed model's lowered gradient program whose
    number does not depend on its layers: the forward pass's and the
    recomputed pass's, each called once a layer, and the backward's one."""
    import re

    from deeperspeed_tpu.accelerator import get_accelerator
    from deeperspeed_tpu.ops.attention import pallas_eva, pallas_eva_pool

    monkeypatch.setattr(type(get_accelerator()), "use_pallas_kernels",
                        lambda self: True)
    monkeypatch.setattr(pallas_eva, "compiles_for_tpu", lambda *a: True)
    monkeypatch.setattr(pallas_eva_pool, "compiles_for_tpu", lambda *a: True)
    jax.clear_caches()

    def counted(layers):
        model = EvaByte(EvaByteConfig.tiny(num_hidden_layers=layers,
                                           remat=True, dtype=jnp.bfloat16))
        loss, batch = model.loss_fn(), model.example_batch(1, S)
        params = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), batch["input_ids"]))
        text = jax.jit(jax.grad(lambda p, b: loss(p["params"], b)[0])).lower(
            params, batch).as_text()
        return (len(re.findall(rf"func\.func private @{body}(_\d+)?\(", text)),
                len(re.findall(rf"call @{body}(_\d+)?\(", text)))

    assert counted(2) == (bodies, 2 * calls_a_layer)
    assert counted(4) == (bodies, 4 * calls_a_layer)
    monkeypatch.setattr(type(get_accelerator()), "use_pallas_kernels",
                        lambda self: False)
    jax.clear_caches()


# ------------------------------------------------------------ the engine
def test_trains_through_the_engine_under_a_warm_up():
    import deeperspeed_tpu as dst
    from deeperspeed_tpu.parallel.topology import MeshTopology

    model = EvaByte(EvaByteConfig.tiny(remat=True, dtype=jnp.bfloat16,
                                       attention_heads_held=2))
    engine, _, _, _ = dst.initialize(
        model=model, mesh=MeshTopology(devices=jax.devices()[:1]),
        config={"train_batch_size": 2, "train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "scheduler": {"type": "WarmupLR", "params": {
                    "warmup_min_lr": 1e-4, "warmup_max_lr": 1e-3,
                    "warmup_num_steps": 4, "warmup_type": "linear"}},
                "bf16": {"enabled": True}, "gradient_clipping": 1.0,
                "zero_optimization": {"stage": 0},
                "steps_per_print": 10 ** 9})
    batch = model.example_batch(2, 128)
    losses = [float(engine.train_batch(batch=batch)) for _ in range(5)]
    assert losses[-1] < losses[0]
    told = telemetry.step_counters()["train_step"]
    assert told["layer_applications"] == 2
    assert told["eva_pairs_visited"] >= told["eva_pairs_needed"] > 0
    mask = engine._no_cast_mask(engine.state["master_params"])
    kept = {jax.tree_util.keystr(p) for p, m in
            jax.tree_util.tree_leaves_with_path(mask) if m}
    assert any("embed_tokens" in k for k in kept)
    assert any("adaptive_phi" in k for k in kept)
    assert not any("q_proj" in k or "lm_head" in k for k in kept)
