"""Laguna (``models/laguna.py``) against the plain reference
(``benchmarks/reference/laguna_ref.py``) at a tiny size on the CPU:
log-probabilities, routed sets and gradients of every parameter class for
each kind of layer alone and for the dense layer with a period; each of the
five mechanisms left out is another model; the expert shares and the head
shares of a layer add up to the uncut reference's layer (router, norms,
shared expert and dense MLP counted once); rotary turns the first half of a
full layer's head and the whole of a sliding layer's; the cut's arithmetic;
every attention call of the model goes to the kernel; a wide layer's routed
walk takes the grouped matmul; and the model through ``dst.initialize`` /
``engine.train_batch`` under a warm-up."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import core
from benchmarks.reference import laguna_ref as ref
from deeperspeed_tpu import telemetry
from deeperspeed_tpu.models.laguna import (DENSE, SPARSE, GatedMLP, Laguna,
                                           LagunaConfig)
from deeperspeed_tpu.models.mellum import (FULL, SLIDING, MellumAttention,
                                           MellumMoE)
from deeperspeed_tpu.ops.transformer.rope import apply_rotary_pos_emb

runner = core.load_runner("train_laguna")
TINY = core.load_json(core.BENCH_DIR + "/configs/tiny-laguna-rehearsal.json")
CELL = core.load_json(core.BENCH_DIR + "/configs/laguna-s-2.1.json")
TRAFFIC = {"seq_len": 40, "micro_batch": 2, "dtype": "float32",
           "ce_chunk_tokens": 48}
HELD = ("routed_experts_held", "first_expert_held",
        "full_attention_heads_held", "sliding_attention_heads_held",
        "key_value_heads_held", "first_key_value_head_held")
STACKS = {"full-dense": [(FULL, DENSE)], "sliding-sparse": [(SLIDING, SPARSE)],
          "full-sparse": [(FULL, SPARSE)],
          "stage": [(FULL, DENSE), (SLIDING, SPARSE), (SLIDING, SPARSE),
                    (FULL, SPARSE)]}


def _cfg(kinds, **more):
    heads = {FULL: 4, SLIDING: 6}
    return dict(TINY, layer_types=[a for a, _ in kinds],
                mlp_layer_types=[m for _, m in kinds],
                num_attention_heads_per_layer=[heads[a] for a, _ in kinds],
                num_hidden_layers=len(kinds), **more)


def _uncut(kinds):
    return {k: v for k, v in _cfg(kinds).items() if k not in HELD}


def _ids(seed, cfg, b=2, s=40):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg["vocab_size"], size=(b, s + 1), dtype=np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


def _count(kinds, at, value):
    return sum(1 for kind in kinds if kind[at] == value)


# --------------------------------------------------- model against reference
@pytest.mark.parametrize("kinds", list(STACKS.values()), ids=list(STACKS))
def test_logprobs_and_routing_are_the_references(kinds):
    cfg = _cfg(kinds)
    params = ref.init_params(cfg, 11)
    ids, labels = _ids(11, cfg)
    model = runner.program_model(cfg, TRAFFIC)
    if not _count(kinds, 1, SPARSE):
        with pytest.raises(ValueError):      # nothing routed, nothing to stack
            jax.jit(model.logprobs)(params, ids, labels)
        return
    got_lp, got_chosen, counters = jax.jit(model.logprobs)(params, ids, labels)
    for b in range(2):
        want_lp, want_chosen = ref.token_logprobs(params, cfg, ids[b],
                                                  labels[b])
        np.testing.assert_allclose(got_lp[b], want_lp, rtol=2e-4, atol=2e-4)
        np.testing.assert_array_equal(np.asarray(got_chosen)[:, b],
                                      np.asarray(want_chosen))
    for name, (at, value) in runner.COUNTED.items():
        assert int(counters[name]) == _count(kinds, at, value), name
    assert float(counters["moe_slots_dropped"]) == 0.0
    assert float(counters["moe_slots_held"]) == pytest.approx(
        np.asarray(got_chosen).sum() / _count(kinds, 1, SPARSE))


@pytest.mark.parametrize("kinds", [STACKS["sliding-sparse"], STACKS["stage"]],
                         ids=["sliding-sparse", "stage"])
def test_loss_and_gradients_are_the_references(kinds):
    """Every class of parameter: the gate's ``g_proj``, the shared expert,
    the dense layer's MLP, router and routed experts, norms and tables."""
    cfg = _cfg(kinds)
    params = ref.init_params(cfg, 13)
    ids, labels = _ids(13, cfg)
    model = runner.program_model(cfg, TRAFFIC)
    (loss, _), grads = jax.jit(jax.value_and_grad(
        model.loss_fn(), has_aux=True))(params, {"input_ids": ids,
                                                 "labels": labels})
    want_loss, want, _, _ = ref.loss_and_grads(params, cfg, ids, labels)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    got_flat = dict(jax.tree_util.tree_leaves_with_path(grads))
    seen = set()
    for path, w in jax.tree_util.tree_leaves_with_path(want):
        scale = max(float(jnp.max(jnp.abs(w))), 1e-8)
        assert scale > 1e-7, jax.tree_util.keystr(path)   # it has a gradient
        np.testing.assert_allclose(np.asarray(got_flat[path]) / scale,
                                   np.asarray(w) / scale, rtol=0, atol=3e-4,
                                   err_msg=jax.tree_util.keystr(path))
        seen.add(jax.tree_util.keystr(path[1:]))
    assert {"['attn']['g_proj']['kernel']",
            "['shared_expert']['gate_proj']['kernel']",
            "['moe']['router_kernel']",
            "['moe']['experts_gate_up_proj']"} <= seen
    if (FULL, DENSE) in kinds:
        assert "['mlp']['down_proj']['kernel']" in seen


@pytest.mark.parametrize("mechanism", ref.MECHANISMS)
def test_a_model_without_a_mechanism_is_another_model(mechanism):
    """What each of the runner's mechanism controls computes differs from
    the program by far more than the program differs from the reference."""
    cfg = _cfg(STACKS["stage"])
    params = ref.init_params(cfg, 17)
    ids, labels = _ids(17, cfg, b=1, s=64)
    model = runner.program_model(cfg, dict(TRAFFIC, seq_len=64))
    got = jax.jit(model.logprobs)(params, ids, labels)[0][0]
    whole, _ = ref.token_logprobs(params, cfg, ids[0], labels[0])
    without, _ = ref.token_logprobs(params, cfg, ids[0], labels[0],
                                    without=(mechanism,))
    sound = runner.train.compare_logprobs(got, whole)
    assert sound < 2e-6
    assert runner.train.compare_logprobs(got, without) > 100 * sound
    assert float(jnp.max(jnp.abs(got - without))) > 2e-3
    with pytest.raises(ValueError):
        ref.token_logprobs(params, cfg, ids[0], labels[0], without=("norm",))


def test_the_window_hides_nothing_before_it_is_reached():
    cfg = _cfg(STACKS["stage"])
    params = ref.init_params(cfg, 17)
    ids, labels = _ids(17, cfg)
    windowed, _ = ref.token_logprobs(params, cfg, ids[0], labels[0])
    full, _ = ref.token_logprobs(params, cfg, ids[0], labels[0],
                                 without=("window",))
    w = cfg["sliding_window"]
    np.testing.assert_allclose(windowed[:w], full[:w], rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(windowed[w:] - full[w:]))) > 1e-3


def test_the_references_blocks_of_query_rows_change_nothing(monkeypatch):
    """At the timed size the reference's attention goes 512 query rows at a
    time; here five blocks of eight, gated, against one block."""
    cfg = _cfg(STACKS["stage"])
    params = ref.init_params(cfg, 37)
    ids, labels = _ids(37, cfg)
    whole, _ = ref.token_logprobs(params, cfg, ids[0], labels[0])
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    blocks, _ = ref.token_logprobs(params, cfg, ids[0], labels[0])
    np.testing.assert_allclose(blocks, whole, rtol=1e-5, atol=1e-5)


def test_the_reference_by_blocks_is_jax_grad_of_its_own_logprobs():
    cfg = _cfg([(FULL, DENSE), (SLIDING, SPARSE)])
    params = ref.init_params(cfg, 19)
    ids, labels = _ids(19, cfg)

    def mean_loss(p):
        with jax.default_matmul_precision("highest"):
            return -jnp.mean(jnp.stack([ref.token_logprobs(
                p, cfg, ids[b], labels[b])[0] for b in range(2)]))

    want_loss, want = jax.value_and_grad(mean_loss)(params)
    loss, grads, first, picked = ref.loss_and_grads(params, cfg, ids, labels)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    # the routed sets of the sparse layers alone
    assert picked.shape == (2, 1, 40, cfg["routed_experts_held"])
    np.testing.assert_allclose(
        first, ref.token_logprobs(params, cfg, ids[0], labels[0])[0],
        rtol=1e-6, atol=1e-6)
    for (path, g), (_, w) in zip(jax.tree_util.tree_leaves_with_path(grads),
                                 jax.tree_util.tree_leaves_with_path(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-7,
                                   err_msg=jax.tree_util.keystr(path))


# ------------------------------------------------------------------- shares
def _head_share(p, j, group, d):
    """KV head ``j`` with the ``group`` query heads that read it, cut out of
    an uncut layer's attention weights."""
    q = slice(j * group * d, (j + 1) * group * d)
    kv = slice(j * d, (j + 1) * d)
    return {"q_proj": {"kernel": p["q_proj"]["kernel"][:, q]},
            "k_proj": {"kernel": p["k_proj"]["kernel"][:, kv]},
            "v_proj": {"kernel": p["v_proj"]["kernel"][:, kv]},
            "g_proj": {"kernel": p["g_proj"]["kernel"][
                :, j * group:(j + 1) * group]},
            "o_proj": {"kernel": p["o_proj"]["kernel"][q]}}


@pytest.mark.parametrize("kind", [(SLIDING, SPARSE), (FULL, SPARSE),
                                  (FULL, DENSE)],
                         ids=["sliding-sparse", "full-sparse", "full-dense"])
def test_the_expert_and_head_shares_add_up_to_the_uncut_layer(kind):
    """Two head shares (a KV head each with the query heads that read it)
    and four expert shares (four experts each), as the program computes
    them, are the uncut reference's layer: a share's attention output holds
    its own heads' terms and its routed sum its own experts', and what every
    chip computes alike -- the norms, the router, the shared expert, the
    dense MLP -- is counted once."""
    whole = _uncut([kind])
    sh = ref.share(whole)
    assert (sh["experts"], sh["kv_heads"], sh["heads"][kind[0]]) == (
        16, 2, {FULL: 4, SLIDING: 6}[kind[0]])
    p = ref.init_params(whole, 23)["layers_0"]
    rng = np.random.default_rng(23)
    x = jnp.asarray(rng.standard_normal((32, whole["hidden_size"])),
                    jnp.float32)
    want, want_picked = ref._layer(x, p, kind, whole, sh, "float32")
    eps, d = whole["rms_norm_eps"], whole["head_dim"]
    group = sh["heads"][kind[0]] // sh["kv_heads"]

    def share_cfg(**held):
        return runner.program_model(dict(whole, **held), TRAFFIC).config

    # attention: every pair's two chips hold a KV head each
    u = ref._rms_norm(x, p["input_norm_scale"], eps)
    attended = 0.0
    for j in range(2):
        cfg = share_cfg(key_value_heads_held=1, first_key_value_head_held=j,
                        full_attention_heads_held=2,
                        sliding_attention_heads_held=3)
        attended = attended + MellumAttention(
            cfg, kind[0], heads=cfg.heads(kind[0]), kv_heads=cfg.kv_heads,
            rotary_dim=cfg.rotary_dim(kind[0]), gated=True).apply(
                {"params": _head_share(p["attn"], j, group, d)}, u[None])[0]
    np.testing.assert_allclose(
        attended, ref.attention(u, p["attn"], whole, sh, kind[0]),
        rtol=1e-4, atol=2e-5)
    h = x + attended
    m = ref._rms_norm(h, p["post_norm_scale"], eps)
    if kind[1] == DENSE:
        out = GatedMLP(share_cfg(), whole["intermediate_size"]).apply(
            {"params": p["mlp"]}, m)
        assert want_picked.shape == (32, 0)
    else:
        out, slots = 0.0, 0
        for e in range(4):
            mine = {"router_kernel": p["moe"]["router_kernel"],
                    "experts_gate_up_proj": p["moe"]["experts_gate_up_proj"][
                        4 * e:4 * e + 4],
                    "experts_down_proj": p["moe"]["experts_down_proj"][
                        4 * e:4 * e + 4]}
            part, counters, chosen = MellumMoE(
                share_cfg(routed_experts_held=4, first_expert_held=4 * e),
                scale=whole["moe_routed_scaling_factor"]).apply(
                    {"params": mine}, m[None])
            np.testing.assert_array_equal(
                np.asarray(chosen[0]),
                np.asarray(want_picked)[:, 4 * e:4 * e + 4])
            assert int(counters["slots"]) == int(counters["done"])
            out, slots = out + part[0], slots + int(counters["slots"])
        assert slots == 32 * whole["num_experts_per_tok"]
        # the shared expert, every chip's alike, once
        out = out + GatedMLP(
            share_cfg(), whole["shared_expert_intermediate_size"]).apply(
                {"params": p["shared_expert"]}, m)
    np.testing.assert_allclose(h + out, want, rtol=1e-4, atol=3e-5)


def test_a_query_head_goes_with_its_kv_head():
    with pytest.raises(ValueError):
        LagunaConfig.tiny(key_value_heads_held=1, full_attention_heads_held=2,
                          sliding_attention_heads_held=2)
    with pytest.raises(ValueError):
        LagunaConfig.tiny(key_value_heads_held=2, first_key_value_head_held=1)
    with pytest.raises(ValueError):
        ref.share(dict(TINY, sliding_attention_heads_held=2))
    with pytest.raises(ValueError):
        ref.whole_heads(dict(TINY, num_attention_heads_per_layer=[4, 6, 4]))
    cfg = LagunaConfig.tiny(key_value_heads_held=1,
                            full_attention_heads_held=2,
                            sliding_attention_heads_held=3)
    assert (cfg.heads(FULL), cfg.heads(SLIDING), cfg.kv_heads) == (2, 3, 1)
    assert LagunaConfig.tiny().heads(SLIDING) == 6


def test_the_cut_and_its_arithmetic():
    model = runner.program_model(CELL, dict(TRAFFIC, seq_len=8192,
                                            ce_chunk_tokens=2048))
    cfg = model.config
    assert cfg.kinds == ((FULL, DENSE), (SLIDING, SPARSE), (SLIDING, SPARSE),
                         (SLIDING, SPARSE), (FULL, SPARSE))
    assert (cfg.experts, cfg.vocab_rows, cfg.first_layer_held) == (
        8, 12544, 0)
    assert (cfg.heads(FULL), cfg.heads(SLIDING), cfg.kv_heads) == (24, 36, 4)
    assert (cfg.rotary_dim(FULL), cfg.rotary_dim(SLIDING)) == (64, 128)
    assert model.attention_params(FULL) == 22_093_824
    assert model.attention_params(SLIDING) == 31_567_872
    assert model.layer_matmul_params((FULL, DENSE)) + 6_144 == 135_346_176
    assert (model.layer_matmul_params((SLIDING, SPARSE)) + 6_144
            + 8 * model.routed_expert_params()) == 117_295_104
    assert (model.layer_matmul_params((FULL, SPARSE)) + 6_144
            + 8 * model.routed_expert_params()) == 107_821_056
    assert model.num_params() == ref.num_params(CELL) == 672_125_952
    assert "672,125,952" in CELL["sizing"]["held_params"]
    whole = Laguna(LagunaConfig.laguna_s_2_1())
    assert whole.config.kinds.count((SLIDING, SPARSE)) == 36
    assert whole.config.kinds.count((FULL, SPARSE)) == 11
    assert whole.config.kinds[0] == (FULL, DENSE)
    assert 117.5e9 < whole.num_params() < 117.7e9
    assert whole.num_params() == ref.num_params(
        {k: v for k, v in CELL.items() if not k.endswith("_held")})
    for slots in (0.3125, 1.0):
        assert model.flops_per_token(slots) == pytest.approx(
            ref.flops_per_token(CELL, 8192, slots))
    assert model.flops_per_token() == model.flops_per_token(10 * 8 / 256)
    tiny = runner.program_model(TINY, TRAFFIC)
    assert tiny.num_params() == ref.num_params(TINY)
    assert tiny.flops_per_token(0.7) == pytest.approx(
        ref.flops_per_token(TINY, 40, 0.7))


# -------------------------------------------------------------------- rotary
def test_rotary_turns_half_a_full_layers_head_and_all_of_a_sliding_layers():
    cfg = runner.program_model(CELL, dict(TRAFFIC, seq_len=8192,
                                          ce_chunk_tokens=2048)).config
    rng = np.random.default_rng(29)
    q = jnp.asarray(rng.standard_normal((1, 16, 3, 128)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 16, 2, 128)), jnp.float32)
    positions = jnp.arange(16)
    for kind, rope in ((FULL, cfg.rope_full), (SLIDING, cfg.rope_sliding)):
        turned = cfg.rotary_dim(kind)
        cos, sin = rope.tables(positions[None], turned, jnp.float32)
        got_q, got_k = apply_rotary_pos_emb(q, k, cos, sin)
        # from the second position on every turned dim moved, no other did
        for got, was in ((got_q, q), (got_k, k)):
            np.testing.assert_array_equal(got[..., turned:], was[..., turned:])
            assert float(jnp.min(jnp.abs(got[:, 1:, :, :turned]
                                         - was[:, 1:, :, :turned]))) > 0
        # and they are the reference's
        want_cos, want_sin = ref.rotary(CELL, kind, positions)
        assert want_cos.shape == (16, turned)
        np.testing.assert_allclose(cos[0, :, 0], want_cos, rtol=1e-6,
                                   atol=1e-6)
        np.testing.assert_allclose(
            got_q[0], ref.rotate_first(q[0], want_cos, want_sin), rtol=1e-5,
            atol=1e-5)
    # the full layers' factor is on the turned dims alone
    cos, _ = ref.rotary(CELL, FULL, positions)
    assert float(cos[0, 0]) == pytest.approx(
        CELL["rope_parameters"][FULL]["attention_factor"])
    assert ref.rotary(CELL, FULL, positions, whole_head=True)[0].shape == (
        16, 128)


# ----------------------------------------------------------------- the kernel
def test_every_attention_call_of_the_model_takes_the_kernel(monkeypatch):
    """With the accelerator's kernels on, the stage's windowed layers count
    ``flash_attention_window`` calls and its full layers ``flash_attention``
    calls at the kinds' own head counts (interpret mode here), and the
    result is the plain path's."""
    from deeperspeed_tpu.accelerator import get_accelerator

    cfg = _cfg(STACKS["stage"], head_dim=64, hidden_size=128,
               full_attention_heads_held=2, sliding_attention_heads_held=3)
    params = ref.init_params(cfg, 31)
    ids, labels = _ids(31, cfg, b=1, s=256)
    model = runner.program_model(cfg, dict(TRAFFIC, seq_len=256))
    plain = model.logprobs(params, ids, labels)[0]
    monkeypatch.setattr(type(get_accelerator()), "use_pallas_kernels",
                        lambda self: True)
    # ``count_kernel_path`` counts when a call is TRACED: whatever this
    # process traced before (another model's test, the same shapes) must
    # not answer for the counted call from jit's cache
    jax.clear_caches()
    before = telemetry.kernel_paths()
    got = model.logprobs(params, ids, labels)[0]
    after = telemetry.kernel_paths()

    def calls(paths, kernel):
        return sum(paths.get(kernel, {}).values())

    assert calls(after, "flash_attention_window") > calls(
        before, "flash_attention_window")
    assert calls(after, "flash_attention") > calls(before, "flash_attention")
    np.testing.assert_allclose(got, plain, rtol=2e-4, atol=2e-4)


def test_k_and_v_reach_the_kernels_at_their_kv_heads(kv_heads_go_to_the_kernel):
    """Heads of 128 (a head a lane block, as in the cell), the 3 query
    heads held of a windowed layer and the 2 of a full one over the one KV
    head held: a train step hands k and v to the kernels at the KV head,
    groups of 3 and of 2 by the layer's kind, copies nothing and sums
    nothing after the kernel, and the loss and gradients (the gate's, too)
    are the plain path's."""
    cfg = _cfg(STACKS["stage"], head_dim=128, hidden_size=128)
    ids, labels = _ids(41, cfg, b=1, s=256)
    model = runner.program_model(cfg, dict(TRAFFIC, seq_len=256))
    held = model.config
    groups = [(held.heads(SLIDING), held.kv_heads, 128),
              (held.heads(FULL), held.kv_heads, 128)]
    assert all(heads > kv for heads, kv, _ in groups)
    counted = kv_heads_go_to_the_kernel(
        model.loss_fn(), ref.init_params(cfg, 41),
        {"input_ids": ids, "labels": labels}, groups)
    assert {kernel: set(paths) for kernel, paths in counted.items()
            if paths} == {
        "flash_attention_window_kv_heads": {
            f"grouped_{groups[0][0] // groups[0][1]}"},
        "flash_attention_kv_heads": {
            f"grouped_{groups[1][0] // groups[1][1]}"}}


def test_a_wide_layers_walk_takes_the_grouped_matmul(monkeypatch):
    """The routed walk's form is chosen by the shapes: this tiny model's
    expert layers (top-3 of 64 chooses 4.7 % of the pairs, widths the
    kernels tile) walk by slots until the bytes that walk would pass over
    reach ``GROUPED_FROM_TABLE_BYTES``, as the cell's ``[16384, 3072]`` layers'
    do; from there a step counts ``grouped_matmul: pallas`` a sparse layer
    (the kernels in interpret mode here), the rows the kernels multiplied
    beside the slots held, no slot dropped, and the same loss."""
    from deeperspeed_tpu.moe import dropless

    model = Laguna(LagunaConfig.tiny(
        hidden_size=128, moe_intermediate_size=128, num_experts=64,
        routed_experts_held=8, first_expert_held=4))
    batch = model.example_batch(2, 40)
    params = model.init(jax.random.PRNGKey(3), batch["input_ids"])["params"]
    # nobody routes here by chance at this size: lean towards the held
    lean = jnp.zeros(64).at[4:12].set(0.5)
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf + lean if "router_kernel" in
        jax.tree_util.keystr(path) else leaf, params)

    def step():
        before = collections.Counter(
            telemetry.kernel_paths().get("grouped_matmul"))
        loss, told = model.loss_fn()(params, batch)
        return float(loss), told, dict(collections.Counter(
            telemetry.kernel_paths()["grouped_matmul"]) - before)

    by_slots, told_slots, paths = step()
    assert paths == {"slots": 2} and "moe_rows_computed" not in told_slots
    monkeypatch.setattr(
        dropless, "GROUPED_FROM_TABLE_BYTES", dropless.slots_walk_bytes(
            2 * 40, 3, 64, 128, 8))
    grouped, told, paths = step()
    assert paths == {"pallas": 2}
    assert told["moe_rows_computed"] >= told["moe_slots_held"] > 0
    assert told["moe_slots_held"] == told_slots["moe_slots_held"]
    assert told["moe_slots_dropped"] == 0
    assert grouped == pytest.approx(by_slots, rel=1e-5)


# ------------------------------------------------------------ the engine
def test_trains_through_the_engine_under_a_warm_up():
    import deeperspeed_tpu as dst
    from deeperspeed_tpu.parallel.topology import MeshTopology

    model = Laguna(LagunaConfig.tiny(remat=True, dtype=jnp.bfloat16))
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
    batch = model.example_batch(2, 40)
    losses = [float(engine.train_batch(batch=batch)) for _ in range(5)]
    assert losses[-1] < losses[0]
    told = telemetry.step_counters()["train_step"]
    assert told["window_layer_applications"] == 2
    assert told["full_layer_applications"] == 1
    assert told["dense_mlp_layer_applications"] == 1
    assert told["moe_layer_applications"] == 2
    assert told["shared_expert_layer_applications"] == 2
    assert told["moe_slots_dropped"] == 0 and told["moe_slots_held"] > 0
    mask = engine._no_cast_mask(engine.state["master_params"])
    kept = {jax.tree_util.keystr(p) for p, m in
            jax.tree_util.tree_leaves_with_path(mask) if m}
    assert any("router_kernel" in k for k in kept)
    assert any("embed_tokens" in k for k in kept)
    assert not any("experts" in k or "q_proj" in k or "g_proj" in k
                   or "shared_expert" in k for k in kept)
    rules = dict(model.param_partition_rules())
    assert any("g_proj" in pattern for pattern in rules)
