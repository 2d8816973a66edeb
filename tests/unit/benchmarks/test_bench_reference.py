"""The plain reference against the program at the tiny preset (float32), and
the output check's control: a lower precision must come out as not correct."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import core
from benchmarks.reference import gpt_neox_ref as ref
from benchmarks.runners import train as train_runner

TINY = core.load_json(core.BENCH_DIR + "/configs/tiny-rehearsal.json")
TRAFFIC = {"seq_len": 32, "micro_batch": 2, "dtype": "float32"}


def _ids(seed, b=2, s=32):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, TINY["vocab_size"], size=(b, s + 1), dtype=np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


@pytest.fixture(scope="module")
def model():
    return train_runner.program_model(TINY, TRAFFIC)


def test_param_tree_is_the_programs(model):
    ours = ref.init_params(TINY, 3)
    theirs = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32)))["params"]
    a = {jax.tree_util.keystr(k): v.shape
         for k, v in jax.tree_util.tree_leaves_with_path(ours)}
    b = {jax.tree_util.keystr(k): v.shape
         for k, v in jax.tree_util.tree_leaves_with_path(theirs)}
    assert a == b
    assert ref.num_params(TINY) == model.num_params()


@pytest.mark.parametrize("seed", [1, 2**31 + 11])
def test_same_seed_same_weights(seed):
    a, b = ref.init_params(TINY, seed), ref.init_params(TINY, seed)
    other = ref.init_params(TINY, seed + 1)
    ka, kb, ko = (x["embed_out"]["kernel"] for x in (a, b, other))
    assert np.array_equal(ka, kb) and not np.array_equal(ka, ko)
    assert abs(float(jnp.std(ka)) - 0.02) < 0.002
    assert float(a["final_layer_norm"]["scale"][0]) == 1.0


@pytest.mark.parametrize("seed", [0, 5])
def test_logits_match_program_float32(model, seed):
    params = ref.init_params(TINY, seed)
    ids, _ = _ids(seed)
    theirs = np.asarray(model.apply({"params": params}, ids))
    for b in range(ids.shape[0]):
        ours = np.asarray(ref.logits(params, TINY, ids[b]))
        np.testing.assert_allclose(ours, theirs[b], atol=2e-5, rtol=0)


def test_loss_matches_program_float32(model):
    params = ref.init_params(TINY, 9)
    ids, labels = _ids(9)
    ours, rows = ref.loss(params, TINY, ids, labels)
    theirs = model.loss_fn()(params, {"input_ids": ids, "labels": labels})
    assert abs(float(ours) - float(theirs)) < 1e-5
    assert len(rows) == 2 and rows[0].shape == (32,)


def test_unknown_precision_is_an_error():
    params = ref.init_params(TINY, 4)
    with pytest.raises(ValueError):
        ref.logits(params, TINY, _ids(4)[0][0], precision="int3")


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_control_fails_the_train_comparison(model, seed):
    """bf16 (the stated precision) against the float32 reference reads a
    number; fp8 (the next step down) reads at least three times that.  The
    limits on the chip were set the same way at the cells' sizes."""
    params = ref.init_params(TINY, seed)
    ids, labels = _ids(seed)
    exact = np.asarray(ref.token_logprobs(params, TINY, ids[0], labels[0]))
    bf16 = train_runner.compare_logprobs(np.asarray(ref.token_logprobs(
        params, TINY, ids[0], labels[0], "bfloat16")), exact)
    fp8 = train_runner.compare_logprobs(np.asarray(ref.token_logprobs(
        params, TINY, ids[0], labels[0], "fp8")), exact)
    assert 0 < bf16 < 0.004
    assert fp8 > 3 * bf16
    limit = 2.0 * bf16
    assert core.check("x", bf16, limit)["ok"]
    assert not core.check("x", fp8, limit)["ok"]


# ------------------------------------------------ the step: gradient, Adam
def _flat(tree):
    return np.concatenate([np.asarray(x).ravel()
                           for x in jax.tree_util.tree_leaves(tree)])


@pytest.mark.parametrize("seed", [31, 32])
def test_gradient_matches_program_float32(model, seed):
    params = ref.init_params(TINY, seed)
    ids, labels = _ids(seed)
    loss, grads, first = ref.loss_and_grads(params, TINY, ids, labels)
    theirs = jax.grad(lambda p: model.loss_fn()(
        p, {"input_ids": ids, "labels": labels}))(params)
    ours, theirs = _flat(grads), _flat(theirs)
    assert np.linalg.norm(ours - theirs) < 1e-4 * np.linalg.norm(theirs)
    want_loss, rows = ref.loss(params, TINY, ids, labels)
    assert abs(float(loss) - float(want_loss)) < 1e-6
    np.testing.assert_allclose(np.asarray(first), np.asarray(rows[0]),
                               atol=1e-6)


def test_adam_first_step_and_clip_hand_worked():
    # m_hat = g, v_hat = g^2: the step is lr * g / (|g| + eps)
    new = ref.adam_first_step({"w": jnp.array([1.0, 1.0, 1.0])},
                              {"w": jnp.array([0.5, -2.0, 0.0])}, lr=0.1,
                              eps=0.5)
    np.testing.assert_allclose(np.asarray(new["w"]),
                               [1 - 0.1 * 0.5, 1 + 0.1 * 2 / 2.5, 1.0],
                               rtol=1e-6)
    assert float(ref.global_norm({"a": jnp.array([3.0]),
                                  "b": jnp.array([[4.0]])})) == 5.0
    assert float(ref.clip_scale(5.0, 1.0)) == pytest.approx(0.2)
    assert float(ref.clip_scale(0.5, 1.0)) == 1.0


def _first_step_numbers(seed, precision="float32", master_dtype="float32"):
    """A control in the program's place, against the float32 reference."""
    traffic = dict(TRAFFIC, clip=1.0, optimizer={
        "lr": 1e-4, "betas": [0.9, 0.999], "eps": 1e-8})
    params = ref.init_params(TINY, seed)
    ids, labels = _ids(seed)
    _, grads, _ = ref.loss_and_grads(params, TINY, ids, labels)
    want = train_runner.plain_first_step(TINY, traffic, params, grads)
    _, low, _ = ref.loss_and_grads(params, TINY, ids, labels, precision)
    got = train_runner.plain_first_step(TINY, traffic, params, low,
                                        master_dtype)
    init = train_runner.sample_leaves(params, train_runner.sampled_tops(TINY))
    return train_runner.compare_first_step(got, want, init)


@pytest.mark.parametrize("seed", [41, 42, 43])
def test_control_fails_the_gradient_comparison(seed):
    """The backward pass in bf16 reads a number; in fp8, the control, at
    least three times that, and the Adam number is untouched by either."""
    same = _first_step_numbers(seed)
    assert same["grad_rel_err"] == 0 and same["adam_update_rel_err"] == 0
    bf16 = _first_step_numbers(seed, "bfloat16")
    fp8 = _first_step_numbers(seed, "fp8")
    assert 0 < bf16["grad_rel_err"] < 0.02
    assert fp8["grad_rel_err"] > 3 * bf16["grad_rel_err"]
    limits = core.load_limits("any", rehearse=True)
    assert fp8["grad_rel_err"] > limits["grad_rel_err"]["limit"]
    assert bf16["adam_update_rel_err"] < limits["adam_update_rel_err"]["limit"]


@pytest.mark.parametrize("seed", [51, 52, 53])
def test_control_fails_the_adam_comparison(seed):
    """Masters kept in bfloat16 cannot hold a step of 1e-4 on weights of
    0.02: the update comes out as not correct, the gradient as correct."""
    got = _first_step_numbers(seed, master_dtype="bfloat16")
    limits = core.load_limits("any", rehearse=True)
    assert got["grad_rel_err"] == 0
    assert got["adam_update_rel_err"] > 100 * limits[
        "adam_update_rel_err"]["limit"]


def test_compare_first_step_hand_worked():
    init = {("w",): np.array([1.0, 1.0, 1.0, 1.0], np.float32)}
    want = {"moment": {("w",): np.array([0.3, -0.4, 0.0, 0.0], np.float32)},
            "master": {("w",): np.array([0.9, 1.1, 1.0, 1.0], np.float32)},
            "grad_norm": 0.5}
    got = {"moment": {("w",): np.array([0.3, -0.4, 0.0, 0.5], np.float32)},
           "master": {("w",): np.array([0.9, 1.0, 7.0, 7.0], np.float32)},
           "grad_norm": 0.6}
    out = train_runner.compare_first_step(got, want, init)
    assert out["grad_rel_err"] == pytest.approx(1.0)        # 0.5 / 0.5
    # leaf RMS 0.25: the two elements with a gradient count, the rest not
    assert out["adam_update_rel_err"] == pytest.approx(
        (0.1 ** 2 / (2 * 0.1 ** 2)) ** 0.5, rel=1e-5)
    assert (out["grad_norm"], out["grad_norm_reference"]) == (0.6, 0.5)


def _reading(grad, adam, fp8=None, low=None):
    r = {"program": {"grad_rel_err": grad, "adam_update_rel_err": adam,
                     "logprob_rms": 0.005, "first_loss_abs_diff": 0.0001}}
    if fp8 is not None:
        r["control_fp8"] = {"grad_rel_err": fp8}
        r["control_bf16_masters"] = {"adam_update_rel_err": low}
    return r


def test_limits_are_the_geometric_mean_and_need_three_times_clearance():
    sound = [_reading(0.01, 1e-5, 0.09, 0.4), _reading(0.008, 1e-5, 0.16, 0.4),
             _reading(0.009, 4e-5, 0.1, 0.9), _reading(0.004, 1e-5)]
    got = train_runner.limits_from(sound)
    assert got["grad_rel_err"]["limit"] == pytest.approx(0.03)
    assert got["grad_rel_err"]["sound_seeds"] == 4
    assert got["grad_rel_err"]["control_seeds"] == 3
    assert got["adam_update_rel_err"]["limit"] == pytest.approx(0.004)
    with pytest.raises(SystemExit):                       # 0.025 < 3 x 0.01
        train_runner.limits_from(sound + [_reading(0.01, 1e-5, 0.025, 0.4)])
    with pytest.raises(SystemExit):                       # two control seeds
        train_runner.limits_from(sound[1:])
    bad = _reading(0.01, 1e-5)
    bad["program"]["logprob_rms"] = 0.03
    with pytest.raises(SystemExit):                       # a carried limit
        train_runner.limits_from(sound + [bad])


def test_sampled_leaves_cover_both_tables_and_three_layers():
    tops = train_runner.sampled_tops({"num_hidden_layers": 24})
    assert tops == {"embed_in", "embed_out", "final_layer_norm", "layers_0",
                    "layers_11", "layers_23"}
    got = train_runner.sample_leaves(ref.init_params(TINY, 1),
                                     train_runner.sampled_tops(TINY))
    assert ("embed_out", "kernel") in got
    assert ("layers_0", "attention", "query_key_value", "kernel") in got
    assert all(isinstance(v, np.ndarray) for v in got.values())


def test_remat_changes_no_number():
    params = ref.init_params(TINY, 6)
    ids, labels = _ids(6)
    a = ref.token_logprobs(params, TINY, ids[0], labels[0])
    b = ref.token_logprobs(params, TINY, ids[0], labels[0], remat=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
