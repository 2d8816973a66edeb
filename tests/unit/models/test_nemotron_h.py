"""The hybrid model (``models/nemotron_h.py``) against the plain reference
(``benchmarks/reference/nemotron_h_ref.py``) at a tiny size on the CPU:
log-probabilities and gradients for each kind of layer alone and for a whole
period; the shares of a layer add up to the uncut reference's layer (heads of
the Mamba and of the attention mixer, experts of the expert layer); and the
model through ``dst.initialize`` / ``engine.train_batch``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import core
from benchmarks.reference import nemotron_h_ref as ref
from deeperspeed_tpu.models.nemotron_h import (AttentionMixer, LatentMoEMixer,
                                               MambaMixer, NemotronH,
                                               NemotronHConfig)

runner = core.load_runner("train_hybrid")
TINY = core.load_json(core.BENCH_DIR + "/configs/tiny-nemotron-rehearsal.json")
TRAFFIC = {"seq_len": 40, "micro_batch": 2, "dtype": "float32",
           "ce_chunk_tokens": 48}


def _cfg(pattern, **more):
    return dict(TINY, hybrid_override_pattern=pattern, **more)


def _ids(seed, cfg, b=2, s=40):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg["vocab_size"], size=(b, s + 1), dtype=np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


# --------------------------------------------------- model against reference
@pytest.mark.parametrize("pattern", ["M", "E", "*", "EMEMEMEMEM*"])
def test_logprobs_and_routing_are_the_references(pattern):
    cfg = _cfg(pattern)
    params = ref.init_params(cfg, 11)
    ids, labels = _ids(11, cfg)
    model = runner.program_model(cfg, TRAFFIC)
    got_lp, got_chosen, counters = jax.jit(model.logprobs)(params, ids, labels)
    for b in range(2):
        want_lp, want_chosen = ref.token_logprobs(params, cfg, ids[b],
                                                  labels[b])
        np.testing.assert_allclose(got_lp[b], want_lp, rtol=2e-4, atol=2e-4)
        np.testing.assert_array_equal(np.asarray(got_chosen)[:, b],
                                      np.asarray(want_chosen))
    assert int(counters["ssm_layer_applications"]) == pattern.count("M")
    assert int(counters["moe_layer_applications"]) == pattern.count("E")
    assert int(counters["attention_layer_applications"]) == pattern.count("*")
    if "E" in pattern:
        assert float(counters["moe_slots_dropped"]) == 0.0
        assert float(counters["moe_slots_held"]) == pytest.approx(
            np.asarray(got_chosen).sum() / pattern.count("E"))


@pytest.mark.parametrize("pattern", ["M", "E", "*", "EMEMEMEMEM*", "EM*M",
                                     "ME"])
def test_loss_and_gradients_are_the_references(pattern):
    cfg = _cfg(pattern)
    params = ref.init_params(cfg, 13)
    ids, labels = _ids(13, cfg)
    model = runner.program_model(cfg, dict(TRAFFIC, remat=True))
    (loss, _), grads = jax.jit(jax.value_and_grad(
        model.loss_fn(), has_aux=True))(
            params, {"input_ids": ids, "labels": labels})
    want_loss, want, _, _ = ref.loss_and_grads(params, cfg, ids, labels)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    flat = dict(jax.tree_util.tree_leaves_with_path(want))
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        w = flat[path]
        np.testing.assert_allclose(
            g, w, rtol=2e-3, atol=2e-5 + 2e-3 * float(jnp.abs(w).max()),
            err_msg=jax.tree_util.keystr(path))


def test_the_program_counts_what_the_reference_counts():
    cfg = _cfg("EMEMEMEMEM*")
    model = runner.program_model(cfg, TRAFFIC)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert model.num_params() == ref.num_params(cfg) == n
    assert {jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_leaves_with_path(shapes)} == {
        "".join(f"['{k}']" for k in path) for path in ref.param_shapes(cfg)}
    for slots in (None, 0.9):
        want = ref.flops_per_token(
            cfg, 40, slots if slots is not None else 3 * 4 / 16)
        assert model.flops_per_token(slots) == pytest.approx(want)


# ---------------------------------------------------------- shares add up
def _tokens(seed, cfg, s=24):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (s, cfg["hidden_size"]))


def _columns(lo, n):
    return np.arange(lo, lo + n)


@pytest.mark.parametrize("shares", [2, 4])
def test_mamba_head_shares_add_up_to_the_uncut_layer(shares):
    """Each share holds ``heads / shares`` heads with the groups that serve
    them; the out-projection's partial outputs sum to the whole mixer's."""
    cfg = _cfg("M", mamba_num_heads=8, n_groups=4)
    whole_sh = ref.share(cfg)
    p = ref.init_params(cfg, 17)["layers_0"]["mixer"]
    u = _tokens(17, cfg)
    want = ref.mamba_mixer(u, p, cfg, whole_sh)
    hd, n = cfg["mamba_head_dim"], cfg["ssm_state_size"]
    inner, conv, _ = ref.mamba_widths(cfg, whole_sh)
    heads, groups = 8 // shares, 4 // shares
    total = 0.0
    for j in range(shares):
        x_cols = _columns(j * heads * hd, heads * hd)
        b_cols = inner + _columns(j * groups * n, groups * n)
        c_cols = inner + 4 * n + _columns(j * groups * n, groups * n)
        conv_cols = np.concatenate([x_cols, b_cols, c_cols])
        in_cols = np.concatenate([x_cols, inner + conv_cols,
                                  inner + conv + _columns(j * heads, heads)])
        head_rows = _columns(j * heads, heads)
        mine = {"in_proj": {"kernel": p["in_proj"]["kernel"][:, in_cols]},
                "conv1d_kernel": p["conv1d_kernel"][:, conv_cols],
                "conv1d_bias": p["conv1d_bias"][conv_cols],
                "A_log": p["A_log"][head_rows], "D": p["D"][head_rows],
                "dt_bias": p["dt_bias"][head_rows],
                "norm_scale": p["norm_scale"][x_cols],
                "out_proj": {"kernel": p["out_proj"]["kernel"][x_cols]}}
        held = runner.program_model(
            dict(cfg, mamba_heads_held=heads, mamba_groups_held=groups),
            TRAFFIC).config
        total = total + MambaMixer(held).apply({"params": mine}, u[None])[0]
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=2e-5)


def test_attention_head_shares_add_up_to_the_uncut_layer():
    cfg = _cfg("*", num_attention_heads=8, num_key_value_heads=4)
    p = ref.init_params(cfg, 19)["layers_0"]["mixer"]
    u = _tokens(19, cfg)
    want = ref.attention_mixer(u, p, cfg, ref.share(cfg))
    d, total = cfg["head_dim"], 0.0
    for j in range(4):              # two query heads on one KV head a share
        q_cols, kv_cols = _columns(2 * j * d, 2 * d), _columns(j * d, d)
        mine = {"q_proj": {"kernel": p["q_proj"]["kernel"][:, q_cols]},
                "k_proj": {"kernel": p["k_proj"]["kernel"][:, kv_cols]},
                "v_proj": {"kernel": p["v_proj"]["kernel"][:, kv_cols]},
                "o_proj": {"kernel": p["o_proj"]["kernel"][q_cols]}}
        held = runner.program_model(
            dict(cfg, attention_heads_held=2, key_value_heads_held=1),
            TRAFFIC).config
        total = total + AttentionMixer(held).apply({"params": mine},
                                                   u[None])[0]
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=2e-5)


def test_the_64_expert_shares_add_up_to_the_uncut_layer():
    """Every share routes over all 128 experts and computes its two; the
    routed parts of all 64 shares, with what every chip computes alike (the
    shared expert) counted once, are the uncut reference's layer."""
    cfg = _cfg("E", n_routed_experts=128, num_experts_per_tok=6,
               routed_experts_held=128, first_expert_held=0)
    p = ref.init_params(cfg, 23)["layers_0"]["mixer"]
    u = _tokens(23, cfg, s=32)
    want, _ = ref.moe_mixer(u, p, cfg, ref.share(cfg))
    shared = ref._dense(ref._relu2(ref._dense(u, p["shared_up"], "float32")),
                        p["shared_down"], "float32")
    routed, slots = 0.0, 0
    for j in range(64):
        held = runner.program_model(
            dict(cfg, routed_experts_held=2, first_expert_held=2 * j),
            TRAFFIC).config
        mine = dict(p, experts_up_proj=p["experts_up_proj"][2 * j:2 * j + 2],
                    experts_down_proj=p["experts_down_proj"][2 * j:2 * j + 2])
        out, counters, chosen = LatentMoEMixer(held).apply(
            {"params": mine}, u[None])
        assert int(counters["slots"]) == int(counters["done"]) == int(
            chosen.sum())
        routed = routed + (out[0] - shared)
        slots += int(counters["slots"])
    assert slots == 32 * 6
    np.testing.assert_allclose(routed + shared, want, rtol=1e-4, atol=2e-5)
    # and the reference's own shares add up the same way
    parts = sum(ref.moe_mixer(u, mine_p, cfg, dict(
        ref.share(cfg), experts=64, first_expert=lo), with_shared=False)[0]
        for lo, mine_p in ((lo, dict(
            p, experts_up_proj=p["experts_up_proj"][lo:lo + 64],
            experts_down_proj=p["experts_down_proj"][lo:lo + 64]))
            for lo in (0, 64)))
    np.testing.assert_allclose(parts + shared, want, rtol=1e-4, atol=2e-5)


def test_dropless_when_every_token_is_the_same_id():
    """A batch of one id: the first layer (E) reads one embedding, every
    token picks the same experts, and a share that holds them computes
    tokens x held-and-chosen slots: none dropped."""
    cfg = _cfg("EM")
    params = ref.init_params(cfg, 29)
    model = runner.program_model(cfg, TRAFFIC)
    u = ref._rms_norm(params["embed_tokens"]["embedding"],
                      params["layers_0"]["norm_scale"], 1e-5)
    scores = u @ params["layers_0"]["mixer"]["router_kernel"]
    first, held = cfg["first_expert_held"], cfg["routed_experts_held"]
    hits = [(int(i), sum(first <= int(e) < first + held
                         for e in np.argsort(-np.asarray(row))[:3]))
            for i, row in enumerate(scores)]
    token, n_held = max(hits, key=lambda h: h[1])
    assert n_held >= 1
    ids = jnp.full((2, 40), token, jnp.int32)
    _, chosen, counters = jax.jit(model.logprobs)(params, ids, ids)
    assert float(counters["moe_slots_held"]) == 2 * 40 * n_held
    assert float(counters["moe_slots_dropped"]) == 0.0
    assert float(counters["moe_load_max_over_mean"]) == pytest.approx(
        held / n_held)
    assert int(np.asarray(chosen).sum()) == 2 * 40 * n_held


# ------------------------------------------------------------ the engine
def test_trains_through_the_engine_with_float32_islands():
    import deeperspeed_tpu as dst
    from deeperspeed_tpu import telemetry
    from deeperspeed_tpu.parallel.topology import MeshTopology

    model = NemotronH(NemotronHConfig.tiny(remat=True, dtype=jnp.bfloat16))
    engine, _, _, _ = dst.initialize(
        model=model, mesh=MeshTopology(devices=jax.devices()[:1]),
        config={"train_batch_size": 2, "train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True}, "gradient_clipping": 1.0,
                "zero_optimization": {"stage": 0},
                "steps_per_print": 10 ** 9})
    batch = model.example_batch(2, 40)
    losses = [float(engine.train_batch(batch=batch)) for _ in range(4)]
    assert losses[-1] < losses[0]
    told = telemetry.step_counters()["train_step"]
    assert told["ssm_layer_applications"] == 2
    assert told["moe_layer_applications"] == 1
    assert told["attention_layer_applications"] == 1
    assert told["moe_slots_dropped"] == 0
    # what must stay float32 under mixed precision does
    mask = engine._no_cast_mask(engine.state["master_params"])
    kept = {jax.tree_util.keystr(p) for p, m in
            jax.tree_util.tree_leaves_with_path(mask) if m}
    assert any("router_kernel" in k for k in kept)
    assert any("A_log" in k for k in kept) and any("dt_bias" in k
                                                   for k in kept)
    assert any(k.endswith("['D']") for k in kept)
    assert not any("in_proj" in k or "experts" in k for k in kept)


def test_presets():
    whole = NemotronHConfig.nemotron_3_super()
    assert (whole.layers("M"), whole.layers("E"), whole.layers("*")) == (
        40, 40, 8)
    assert whole.in_proj_width == 2 * 8192 + 2 * 8 * 128 + 128 == 18560
    held = NemotronHConfig.nemotron_3_super(
        pattern="EMEMEMEMEM*", mamba_num_heads=32, n_groups=2, num_heads=8,
        num_kv_heads=1, experts_held=8, vocab_size=16384)
    assert held.in_proj_width == 4640
    assert NemotronH(held).num_params() == 773_579_744
    with pytest.raises(ValueError):
        NemotronH(NemotronHConfig.tiny(pattern="MX")).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def test_k_and_v_reach_the_kernel_at_their_kv_heads(kv_heads_go_to_the_kernel):
    """The attention layer at heads of 128 (a head a lane block): its call
    hands k and v to the kernel at the 2 KV heads of its 4 query heads,
    copies nothing, and the loss and gradients are the plain path's."""
    cfg = _cfg("*E", num_attention_heads=4, num_key_value_heads=2,
               head_dim=128)
    ids, labels = _ids(43, cfg, b=1, s=256)
    model = runner.program_model(cfg, dict(TRAFFIC, seq_len=256))
    counted = kv_heads_go_to_the_kernel(
        model.loss_fn(), ref.init_params(cfg, 43),
        {"input_ids": ids, "labels": labels}, [(4, 2, 128)])
    assert {kernel: set(paths) for kernel, paths in counted.items()
            if paths} == {"flash_attention_kv_heads": {"grouped_2"}}
