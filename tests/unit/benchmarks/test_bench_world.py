"""A traffic file's ``world`` on the CPU, at the rehearsal size: without one
every batch is the draw it was before the key existed (a frozen copy below);
under one the run's seed only renames the ids and reorders the rows, the
tables move with the ids, and the program's first step does the world's work
under any run seed (loss, routed slots, gradients), passes the rehearsal's
limits against ``mellum_ref`` while the controls still fail them,
``calibrate`` still reads a world of its own for every seed, and a whole
run of the rehearsal comes out correct on the world and not correct with the
engine's step broken underneath (a state left unchanged, half of the batch
left out)."""

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import core, run, traffic_gen
from benchmarks.reference import mellum_ref as ref

runner = core.load_runner("train_swa_moe")
NAME = "train-mellum2-ep4-8k"
OTHERS = ["pretrain-2048-remat", "pretrain-1024", "pretrain-4096-loop4-remat",
          "pretrain-8192-hybrid-remat"]
#: two run seeds of one world: a small one, and one past 32 signed bits
RUN_SEEDS = [5, 2**31 + 77]


def _traffic(name):
    return core.load_json(f"{core.BENCH_DIR}/traffic/{name}.json")


def _frozen_batch(traffic, vocab, seed, step):
    """``TokenBatches.batch`` as it was before a traffic file could name a
    world (PR 44's tree), kept here letter for letter."""
    weights = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** float(
        traffic["token_dist"]["exponent"])
    cdf = np.cumsum(weights)
    cdf = cdf / cdf[-1]
    shape = (int(traffic["micro_batch"]) * int(traffic.get("grad_accum", 1)),
             int(traffic["seq_len"]) + 1)
    rng = np.random.default_rng([int(seed), int(step)])
    ids = np.searchsorted(cdf, rng.random(shape)).astype(np.int32)
    ids = np.minimum(ids, vocab - 1)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


# --------------------------------------------------------- without a world
@pytest.mark.parametrize("seed", [0, 2_500_000_001, 2**31 + 5])
@pytest.mark.parametrize("name", ["pretrain-2048-remat",
                                  "pretrain-8192-hybrid-remat"])
def test_without_a_world_a_batch_is_the_frozen_draw(name, seed):
    traffic = _traffic(name)
    assert "world" not in traffic
    batches = traffic_gen.TokenBatches(traffic, 16384, seed)
    assert batches.world_seed == seed and batches.order is None
    for step in (0, 3):
        got, want = batches.batch(step), _frozen_batch(traffic, 16384, seed,
                                                       step)
        for key in ("input_ids", "labels"):
            assert got[key].dtype == want[key].dtype
            assert got[key].tobytes() == want[key].tobytes()


def test_only_the_mixture_cell_of_the_window_model_names_a_world():
    assert not [n for n in OTHERS if "world" in _traffic(n)]
    world = core.find_cell(core.load_manifest(), NAME)[2]["world"]
    assert set(world) == {"seed", "why"} and world["seed"] == int(
        world["seed"]) >= 0
    # the rehearsal runs under it too: its block overrides sizes only
    assert run.rehearsal_overrides(core.find_cell(
        core.load_manifest(), NAME)[2])[1]["world"] == world


# ------------------------------------------------------------ under a world
def test_a_run_seed_renames_the_worlds_ids_and_reorders_its_rows():
    traffic = dict(_traffic("pretrain-2048-remat"), world={"seed": 3})
    vocab = 50304
    a, b = (traffic_gen.TokenBatches(traffic, vocab, s) for s in RUN_SEEDS)
    for t in (a, b):
        assert t.world_seed == 3
        assert np.array_equal(np.sort(t.order), np.arange(vocab))
        assert np.array_equal(t.order[t.inverse], np.arange(vocab))
    assert not np.array_equal(a.order, b.order)
    for step in (0, 4):
        world = _frozen_batch(traffic, vocab, 3, step)
        got_a, got_b = a.batch(step), b.batch(step)
        assert got_a["input_ids"].dtype == np.int32
        assert not np.array_equal(got_a["input_ids"], got_b["input_ids"])
        for t, got in ((a, got_a), (b, got_b)):
            assert np.array_equal(got["input_ids"][:, 1:],
                                  got["labels"][:, :-1])
            assert np.array_equal(got["input_ids"], t.batch(step)["input_ids"])
            # the world's rows, every one once, under the run's names
            back = t.inverse[got["input_ids"]]
            assert sorted(map(bytes, back)) == sorted(
                map(bytes, world["input_ids"]))
            assert sorted(map(bytes, t.inverse[got["labels"]])) == sorted(
                map(bytes, world["labels"]))
    # the rows' order is a step's own: which of the world's rows comes first
    first_rows = set()
    for step in range(12):
        world = _frozen_batch(traffic, vocab, 3, step)["input_ids"]
        first = a.inverse[a.batch(step)["input_ids"][0]]
        first_rows.add(next(i for i, row in enumerate(world)
                            if np.array_equal(row, first)))
    assert len(first_rows) > 1


def test_move_tables_moves_rows_and_columns_and_brings_them_back():
    rng = np.random.default_rng(0)
    tree = {"embed": {"embedding": rng.normal(size=(7, 3)).astype(np.float32)},
            "head": rng.normal(size=(3, 7)).astype(np.float32),
            "norm": np.ones(3, np.float32)}
    order = rng.permutation(7).astype(np.int32)
    inverse = np.argsort(order).astype(np.int32)
    rows, columns = [("embed", "embedding")], [("head",)]
    moved = traffic_gen.move_tables(tree, inverse, rows, columns)
    # the world's id i is the run's order[i]: its row and column went there
    assert np.array_equal(np.asarray(moved["embed"]["embedding"])[order],
                          tree["embed"]["embedding"])
    assert np.array_equal(np.asarray(moved["head"])[:, order], tree["head"])
    assert moved["norm"] is tree["norm"]
    back = traffic_gen.move_tables(moved, order, rows, columns)
    assert np.array_equal(back["embed"]["embedding"],
                          tree["embed"]["embedding"])
    assert np.array_equal(back["head"], tree["head"])
    with pytest.raises(KeyError, match="lm_head"):
        traffic_gen.move_tables(tree, inverse, rows, [("lm_head",)])


# ----------------------------------------- the program's first step, twice
def _context(seed=RUN_SEEDS[0]):
    cell, _, traffic = core.find_cell(core.load_manifest(), NAME)
    config, traffic = run.rehearsal_overrides(traffic)
    args = argparse.Namespace(seed=seed, seconds=0.0, trace=0, rehearse=True)
    return run.Context(args, cell, config, traffic)


@pytest.fixture(scope="module")
def one_world():
    """The program's first step and the reference's under the cell's world,
    for two run seeds -> {seed: what each left}."""
    ctx, out = _context(), {}
    for seed in RUN_SEEDS:
        engine, batches, first_loss, left = runner.start_engine(ctx, seed)
        del engine
        first = batches.batch(0)
        params = runner.seeded_params(ctx.config, batches)
        loss, grads, _, chosen = ref.loss_and_grads(
            params, ctx.config, jnp.asarray(first["input_ids"]),
            jnp.asarray(first["labels"]))
        out[seed] = {
            "batches": batches, "first": first, "first_loss": first_loss,
            "left": left, "reference_loss": float(loss),
            # [layers, held]: the slots every held expert got in every layer
            "slots": np.asarray(chosen).sum(axis=(0, 2)),
            "reference_grads": traffic_gen.move_tables(
                grads, batches.order, runner.TABLE_ROWS,
                runner.TABLE_COLUMNS),
            "against": runner.against_reference(ctx, seed, first_loss, left,
                                                controls=True)}
    return out


def _moved_back(moment, batches):
    """The program's sampled gradients with both tables under the world's
    names again."""
    out = dict(moment)
    out[runner.TABLE_ROWS[0]] = moment[runner.TABLE_ROWS[0]][batches.order]
    out[runner.TABLE_COLUMNS[0]] = moment[runner.TABLE_COLUMNS[0]][
        :, batches.order]
    return out


def test_two_run_seeds_do_the_same_first_step(one_world):
    a, b = (one_world[s] for s in RUN_SEEDS)
    assert a["batches"].world_seed == b["batches"].world_seed
    assert not np.array_equal(a["first"]["input_ids"], b["first"]["input_ids"])
    # float32 at the rehearsal size: the sums run in another order, no more
    assert a["first_loss"] == pytest.approx(b["first_loss"], abs=1e-6)
    assert a["reference_loss"] == pytest.approx(b["reference_loss"], abs=1e-6)
    assert a["left"]["counters"] == b["left"]["counters"]
    assert a["left"]["counters"]["moe_slots_held"] > 0
    assert a["slots"].shape == (3, 4) and np.array_equal(a["slots"],
                                                         b["slots"])
    assert a["slots"].sum() / 3 == pytest.approx(
        a["left"]["counters"]["moe_slots_held"])
    got_a = _moved_back(a["left"]["moment"], a["batches"])
    got_b = _moved_back(b["left"]["moment"], b["batches"])
    assert set(got_a) == set(got_b) and runner.TABLE_ROWS[0] in got_a
    for path, g in got_a.items():
        assert np.linalg.norm(g - got_b[path]) <= 1e-5 * np.linalg.norm(g), path
    # under its own names a table's gradient is another array altogether
    rows = runner.TABLE_ROWS[0]
    assert np.linalg.norm(a["left"]["moment"][rows] - b["left"]["moment"][
        rows]) > 0.1 * np.linalg.norm(a["left"]["moment"][rows])
    # and so with the reference's whole gradient, through ``move_tables``
    for x, y in zip(jax.tree_util.tree_leaves(a["reference_grads"]),
                    jax.tree_util.tree_leaves(b["reference_grads"])):
        assert float(jnp.linalg.norm(x - y)) <= 1e-5 * float(
            jnp.linalg.norm(x))


@pytest.mark.parametrize("seed", RUN_SEEDS)
def test_the_world_passes_the_rehearsals_limits_and_the_controls_fail(
        one_world, seed):
    limits = core.load_json(runner.REHEARSAL_LIMITS)
    got = one_world[seed]["against"]
    assert runner.refused(got["program"], limits) == []
    assert 0 < got["program"]["slots_held"] == pytest.approx(
        got["program"]["slots_held_reference"])
    assert "grad_rel_err" in runner.refused(got["control_fp8"], limits)
    assert len(runner.refused(got["control_every_layer_full"], limits)) >= 3
    for control in ("control_bf16_masters", "control_state_unchanged"):
        assert runner.refused(got[control], limits) == [
            "adam_update_rel_err"]


def test_calibrate_reads_a_world_of_its_own_for_every_seed():
    ctx = _context()
    assert "world" in ctx.traffic
    seeds = [2_500_000_001, 2_500_007_920]
    readings = runner.calibrate(ctx, seeds, control_seeds=0)
    assert [r["seed"] for r in readings] == seeds
    loads = [r["program"]["slots_held"] for r in readings]
    assert loads[0] != loads[1]
    for seed, r in zip(seeds, readings):
        assert traffic_gen.own_world(ctx.traffic, seed)["world"]["seed"] == seed
        assert ctx.traffic["world"]["seed"] != seed
        # the load of the seed's own weights on the seed's own ids, as the
        # cell read it before it had a world
        first = _frozen_batch(ctx.traffic, runner.vocab(ctx.config), seed, 0)
        chosen = ref.loss_and_grads(
            ref.init_params(ctx.config, seed), ctx.config,
            jnp.asarray(first["input_ids"]), jnp.asarray(first["labels"]))[3]
        assert r["program"]["slots_held_reference"] == pytest.approx(
            float(np.asarray(chosen).sum()) / 3)
        assert runner.refused(r["program"], core.load_json(
            runner.REHEARSAL_LIMITS)) == []
    # a traffic without a world is handed back as it is
    plain = _traffic("pretrain-8192-hybrid-remat")
    assert traffic_gen.own_world(plain, 7) is plain


# -------------------------------- a whole run with the timed path broken
def _a_state_left_unchanged(step):
    """The step runs and the state it was given is put back."""
    def train_batch(self, data_iter=None, batch=None):
        kept = jax.tree_util.tree_map(jnp.copy, self.state)
        loss = step(self, data_iter=data_iter, batch=batch)
        self.state = kept
        return loss
    return train_batch


def _half_of_the_batch_left_out(step):
    """The step sees the batch's first half twice: its mean is the mean over
    that half alone."""
    def train_batch(self, data_iter=None, batch=None):
        half = {k: np.concatenate([v[:len(v) // 2]] * 2) for k, v in
                batch.items()}
        return step(self, data_iter=data_iter, batch=half)
    return train_batch


@pytest.mark.parametrize("fault,broken", [
    (None, set()),
    (_a_state_left_unchanged, {"grad_rel_err_vs_reference",
                               "adam_update_rel_err_vs_reference"}),
    (_half_of_the_batch_left_out, {"grad_rel_err_vs_reference"})],
    ids=["sound", "state_left_unchanged", "half_of_the_batch_left_out"])
def test_a_run_of_the_world_is_correct_unless_the_step_is_broken(
        monkeypatch, capsys, fault, broken):
    """The rest of a run as the harness drives it (no look for a chip: the
    rehearsal; one process), on the world, with the engine's step broken
    underneath: ``correct`` comes out false, by the numbers named."""
    from deeperspeed_tpu.runtime.engine import DeeperSpeedEngine

    if fault is not None:
        monkeypatch.setattr(DeeperSpeedEngine, "train_batch",
                            fault(DeeperSpeedEngine.train_batch))
    monkeypatch.setattr(run, "T_PROCESS_START", run.T_PROCESS_START)
    assert run.main(["--workload", NAME, "--seed", str(RUN_SEEDS[1]),
                     "--seconds", "1", "--trace", "0", "--rehearse",
                     "--stage", "measure"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    failed = {c["check"] for c in lines if "check" in c and not c["ok"]}
    assert lines[-1]["correct"] is (fault is None)
    assert failed >= broken and (broken or not failed), failed
