"""Runner for training a decoder with window and full attention layers and
a softmax-routed mixture of gated experts (Mellum 2: one chip's share of the
experts and of the vocabulary, one period of its layers): ``dst.initialize``
-> ``engine.train_batch`` on a fresh seeded batch every step, under the
learning-rate schedule the traffic file gives, exactly as the other train
cells run.  Where the traffic file names a ``world`` (``traffic_gen``), the
weights and the ids of every run are that world's and the run's seed only
renames them: the step's time follows what the weights and ids route to the
experts held, and a cell has to tell one run from another by something else
than that draw.  ``calibrate`` reads a world of its own for every seed.

What is model-free comes from ``runners/train.py`` (the engine's JSON
config, the mesh, the comparison of a first step's gradient and update) and
from ``runners/train_hybrid.py`` (the timed window that keeps every step's
counters, the cast to the compute types, the comparison of routed sets); the
model, the leaves that are sampled, the schedule's first rate, the plain
reference (``reference/mellum_ref.py``), its three controls and the check
are this file's.  ``check`` compares, at the timed sizes, what the timed
engine's first step left (clipped gradient, change of the float32 masters,
the step's own count of routed slots) and the program's forward on the
seeded weights (per-token log-probabilities, which held experts every token
chose) with the reference.  The controls the limits must refuse: the
reference in fp8, its Adam step with bfloat16 masters, a state left
unchanged, and the reference with EVERY LAYER FULL (what a program that
ignored the window computes).

The CPU rehearsal's limits are in ``limits/rehearsal-mellum.json`` (never
``calibrate.py --rehearse --write`` for this cell: that writes
``limits/rehearsal.json``, the Pythia rehearsal's); it is rewritten by
``python3 benchmarks/runners/train_swa_moe.py``.
"""

import copy
import os
import sys

import numpy as np

if __name__ == "__main__":      # the rehearsal's limits, see the foot
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmarks import core, traffic_gen
from benchmarks.reference import mellum_ref as ref
# a program that has no such model fails here, as the runner is loaded:
# before anything is put on the device
from deeperspeed_tpu.models.mellum import Mellum, MellumConfig, Rope

train = core.load_runner("train")
hybrid = core.load_runner("train_hybrid")

#: Limits of the output comparison kept here; those of the gradient and of
#: the update are per cell in ``limits/<cell>.json`` (``limits_from``).  All
#: were set from readings on the chip at the cell's size by one rule: the
#: geometric mean of the largest a sound run gave and the smallest the fp8
#: control gave.  The readings quoted are those of ``calibrate.py --seeds 8
#: --control-seeds 4`` on the final code and of the runs of the cell beside it
#: (my chip runs, PR 38: four control seeds; PERF.md section 2).
#: RMS over the first sequence's tokens of (program log-prob - reference
#: log-prob) of the label: sound runs read 0.0064-0.0085, the fp8 control
#: 0.0829-0.0946, the reference with every layer full 0.158-0.176.
LOGPROB_RMS_LIMIT = 0.026
#: Share of the (token, layer) pairs of the first sequence whose set of
#: chosen held experts differs from the reference's: the 8th and 9th of 64
#: softmax scores swap on a bfloat16 rounding of the router's input, so the
#: share is counted beside a limit and not hidden.  Sound runs read
#: 0.012-0.031, the fp8 control 0.115-0.431, every layer full 0.248-0.563.
ROUTED_SET_MISMATCH_LIMIT = 0.059
#: |slots the program's first step counted - slots the reference counts on
#: the same batch| / the reference's, the mean a layer: the count the FLOPs
#: of ``train.swa_moe_mfu_pct`` stand on.  Sound runs read 0.0005-0.0042 (the
#: largest on the seed whose held experts get fewest slots, 36,842 a layer),
#: the fp8 control 0.0436-0.0972, every layer full 0.0118-0.0732 (it fails
#: by the three limits above).
SLOTS_HELD_REL_LIMIT = 0.013
#: |engine's first-step loss - reference loss on the same batch| is read and
#: printed (``reference``, ``calibrate``) and has NO limit in this cell: the
#: precision hardly moves it (sound runs 0.0001-0.0020, the fp8 control
#: 0.0037-0.0269, every layer full 0.0020-0.0208: nothing separates), and the
#: accepted cells' 0.003 would leave the largest sound reading 1.5 times of
#: room, not three (PERF.md sections 2 and 7).
REHEARSAL_LIMITS = os.path.join(core.BENCH_DIR, "limits",
                                "rehearsal-mellum.json")
#: the number -> the control its limit stands against.  The update's is the
#: state LEFT UNCHANGED, which reads 1 whatever the rate: the masters'
#: precision hardly moves this cell's number the right way round (a bfloat16
#: master's rounding, 3.3e-5, over the first rate, 1e-6, reads 33, and a
#: limit half way to that would pass a step that never happened), so the
#: limit stands between the sound runs and 1, and ``limits_from`` refuses it
#: unless every bfloat16-masters reading breaks it as well.
UNCHANGED = "control_state_unchanged"
CONTROL_OF = dict(train.CONTROL_OF, adam_update_rel_err=UNCHANGED)
#: the numbers with a limit in this file -> that limit
KEPT = {"logprob_rms": LOGPROB_RMS_LIMIT,
        "routed_set_mismatch_share": ROUTED_SET_MISMATCH_LIMIT,
        "slots_held_rel_diff": SLOTS_HELD_REL_LIMIT}
#: layer kind -> the step counter that counts its layers
COUNTED = (("window_layer_applications", "sliding_attention"),
           ("full_layer_applications", "full_attention"))


def _rope(published):
    if published["rope_type"] == "default":
        return Rope(theta=float(published["rope_theta"]))
    return Rope(theta=float(published["rope_theta"]),
                factor=float(published["factor"]),
                original_max_position=int(
                    published["original_max_position_embeddings"]),
                beta_fast=float(published["beta_fast"]),
                beta_slow=float(published["beta_slow"]),
                attention_factor=float(published["attention_factor"]))


def program_model(config, traffic):
    """The program's model object for a configuration file."""
    import jax.numpy as jnp

    ref.layer_kinds(config)              # refuses what neither side runs
    rope = config["rope_parameters"]
    return Mellum(MellumConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        layer_types=tuple(config["layer_types"]),
        rms_norm_eps=config["rms_norm_eps"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], sliding_window=config["sliding_window"],
        rope_sliding=_rope(rope["sliding_attention"]),
        rope_full=_rope(rope["full_attention"]),
        num_experts=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        layers_held=config.get("layers_held"),
        first_layer_held=int(config.get("first_layer_held", 0)),
        routed_experts_held=config.get("routed_experts_held"),
        first_expert_held=int(config.get("first_expert_held", 0)),
        vocab_rows_held=config.get("vocab_rows_held"),
        max_seq_len=int(traffic["seq_len"]),
        ce_chunk_tokens=int(traffic["ce_chunk_tokens"]),
        dtype=getattr(jnp, traffic.get("dtype", "bfloat16")),
        remat=bool(traffic.get("remat", False))))


def sampled_tops(cfg):
    """Top-level names of the leaves whose first-step gradient and update
    are compared: both tables, the final norm, and every parameter of one
    layer of each kind (the first of each)."""
    kinds = ref.layer_kinds(cfg)
    return {"embed_tokens", "lm_head_kernel", "final_norm_scale"} | {
        f"layers_{kinds.index(kind)}" for kind in set(kinds)}


def vocab(cfg):
    return ref.share(cfg)["vocab"]


def engine_config(traffic, seed):
    """``runners/train.py``'s, and the schedule the traffic file gives."""
    config = train.engine_config(traffic, seed)
    if "scheduler" in traffic:
        config["scheduler"] = traffic["scheduler"]
    return config


def first_rate(traffic):
    """The learning rate of the FIRST step: the optimizer's own, or where
    the traffic gives a warm-up its lowest rate (the engine reads the
    schedule at ``state["step"]``, 0 in the first step)."""
    if "scheduler" not in traffic:
        return float(traffic["optimizer"]["lr"])
    if traffic["scheduler"]["type"] != "WarmupLR":
        raise ValueError("the reference's first step knows WarmupLR only")
    return float(traffic["scheduler"]["params"]["warmup_min_lr"])


# ---------------------------------------------- what the first step left
def engine_first_step(engine, cfg):
    """After the engine's first step from the seeded weights: Adam's first
    moment and the float32 masters of the sampled leaves, on the host, and
    the counters the step's model reported."""
    import jax

    from deeperspeed_tpu import telemetry

    tops = sampled_tops(cfg)
    adam = [s for s in jax.tree_util.tree_leaves(
        engine.state["opt_state"], is_leaf=lambda s: hasattr(s, "mu"))
        if hasattr(s, "mu")]
    return {"moment": train.sample_leaves(adam[0].mu, tops),
            "master": train.sample_leaves(engine.state["master_params"], tops),
            "grad_norm": engine.get_global_grad_norm(),
            "counters": telemetry.step_counters().get("train_step", {})}


def plain_first_step(cfg, traffic, params, grads, master_dtype="float32"):
    """The same from the plain reference: ``grads`` (the reference's, or a
    control's) clipped by global norm, one Adam step on ``params`` at the
    schedule's first rate; ``master_dtype`` "bfloat16" is the optimizer's
    control."""
    import jax
    import jax.numpy as jnp

    opt, tops = traffic["optimizer"], sorted(sampled_tops(cfg))
    b1, b2 = opt["betas"]
    norm = jax.jit(ref.global_norm)(grads)

    def step(p, g, norm):
        scale = ref.clip_scale(norm, float(traffic["clip"]))
        g = jax.tree_util.tree_map(lambda x: x * scale, g)
        return g, ref.adam_first_step(p, g, first_rate(traffic), b1, b2,
                                      float(opt["eps"]))

    clipped, new = jax.jit(step)({k: params[k] for k in tops},
                                {k: grads[k] for k in tops}, norm)
    master = train.sample_leaves(new, tops, getattr(jnp, master_dtype))
    return {"moment": {k: (1 - b1) * g for k, g in
                       train.sample_leaves(clipped, tops).items()},
            "master": {k: v.astype(np.float32) for k, v in master.items()},
            "grad_norm": float(norm)}


#: the tables that move with the ids under a world (``move_tables``)
TABLE_ROWS, TABLE_COLUMNS = [("embed_tokens", "embedding")], [("lm_head_kernel",)]


def seeded_params(cfg, batches):
    """The float32 weights a run starts from, for the program and for the
    reference alike: the seed's, or under a world the world's with both
    tables moved to the run's names for the ids."""
    params = ref.init_params(cfg, batches.world_seed)
    if batches.order is None:
        return params
    return traffic_gen.move_tables(params, batches.inverse, TABLE_ROWS,
                                   TABLE_COLUMNS)


def start_engine(ctx, seed):
    """Seeded weights -> the engine, after its first step on the seed's
    first batch.  -> (engine, batches, first loss, what the step left)."""
    import deeperspeed_tpu as dst

    cfg, traffic = ctx.config, ctx.traffic
    batches = traffic_gen.TokenBatches(traffic, vocab(cfg), seed)
    params = seeded_params(cfg, batches)
    engine, _, _, _ = dst.initialize(
        model=program_model(cfg, traffic), model_parameters=params,
        mesh=train.cell_mesh(ctx), config=engine_config(traffic, seed))
    del params
    first_loss = float(engine.train_batch(batch=batches.batch(0)))
    return engine, batches, first_loss, engine_first_step(engine, cfg)


def setup(ctx):
    # warm the one step program: step 0 gives the first loss and what the
    # check compares, step 1 shows that nothing more compiles (the schedule
    # is read from the step counter inside the program)
    engine, batches, first_loss, left = start_engine(ctx, ctx.seed)
    c0 = ctx.compiles.count
    warm_loss = float(engine.train_batch(batch=batches.batch(1)))
    ctx.log("warmup", first_loss=first_loss, second_loss=warm_loss,
            grad_norm=left["grad_norm"], counters=left["counters"],
            compiles_in_second_step=ctx.compiles.count - c0)
    rows, seq = batches.shape[0], batches.shape[1] - 1
    return {"engine": engine, "batches": batches, "next_step": 2,
            "first_loss": first_loss, "first_step": left,
            "tokens_per_step": rows * seq}


#: the timed window, with every step's counters kept and the routed load by
#: step in the progress line ``window_counters``: the hybrid runner's
window = hybrid.window


def against_reference(ctx, seed, first_loss, left, controls=False):
    """The program's first step against the plain reference, on a device the
    program has left.  ``controls`` adds what the controls read in the
    program's place: the reference in fp8 (forward and backward), its Adam
    step with the masters kept in bfloat16, and the reference with every
    layer full.  -> dict of numbers."""
    import jax
    import jax.numpy as jnp

    cfg, traffic = ctx.config, ctx.traffic
    batches = traffic_gen.TokenBatches(traffic, vocab(cfg), seed)
    first = batches.batch(0)
    ids, labels = jnp.asarray(first["input_ids"]), jnp.asarray(first["labels"])
    params = seeded_params(cfg, batches)
    model = program_model(cfg, traffic)
    prog_lp, prog_chosen, _ = jax.jit(model.logprobs)(
        hybrid.cast_for_compute(model, params, traffic), ids[:1], labels[:1])
    prog_lp, prog_chosen = np.asarray(prog_lp)[0], np.asarray(prog_chosen)[:, 0]
    ref_loss, grads, ref_lp, ref_chosen = ref.loss_and_grads(params, cfg, ids,
                                                             labels)
    ref_lp, ref_chosen = np.asarray(ref_lp), np.asarray(ref_chosen)
    # the mean number of slots a layer held over the whole batch
    ref_slots = float(ref_chosen.sum()) / ref_chosen.shape[1]
    init = train.sample_leaves(params, sampled_tops(cfg))
    want = plain_first_step(cfg, traffic, params, grads)
    counters = left["counters"]
    out = {"program": dict(
        train.compare_first_step(left, want, init),
        logprob_rms=train.compare_logprobs(prog_lp, ref_lp),
        routed_set_mismatch_share=hybrid.compare_routing(prog_chosen,
                                                         ref_chosen[0]),
        slots_held_rel_diff=abs(counters.get("moe_slots_held", 0.0)
                                - ref_slots) / max(ref_slots, 1.0),
        slots_held=counters.get("moe_slots_held"),
        slots_held_reference=ref_slots,
        first_loss_abs_diff=abs(first_loss - float(ref_loss)))}
    if not controls:
        return out
    low = plain_first_step(cfg, traffic, params, grads, "bfloat16")
    out["control_bf16_masters"] = {
        "adam_update_rel_err": train.compare_first_step(
            low, want, init)["adam_update_rel_err"]}
    out[UNCHANGED] = {
        "adam_update_rel_err": train.compare_first_step(
            dict(want, master=init), want, init)["adam_update_rel_err"]}
    del grads, low
    for name, changed in (("control_fp8", dict(precision="fp8")),
                          ("control_every_layer_full",
                           dict(every_layer_full=True))):
        ctl_loss, grads, ctl_lp, ctl_chosen = ref.loss_and_grads(
            params, cfg, ids, labels, **changed)
        low = plain_first_step(cfg, traffic, params, grads)
        ctl_chosen = np.asarray(ctl_chosen)
        out[name] = dict(
            grad_rel_err=train.compare_first_step(low, want, init)[
                "grad_rel_err"],
            logprob_rms=train.compare_logprobs(np.asarray(ctl_lp), ref_lp),
            routed_set_mismatch_share=hybrid.compare_routing(ctl_chosen[0],
                                                             ref_chosen[0]),
            slots_held_rel_diff=abs(float(ctl_chosen.sum())
                                    - float(ref_chosen.sum()))
            / max(float(ref_chosen.sum()), 1.0),
            first_loss_abs_diff=abs(float(ctl_loss) - float(ref_loss)))
        del grads, low
    return out


def calibrate(ctx, seeds, control_seeds=3):
    """Readings for the limits, many seeds in one process: the program's
    first step, and on the first ``control_seeds`` seeds the controls,
    against the plain reference; under a world every seed is its own, so
    the limits stand on as many sets of weights as there are seeds.  One
    JSON line per seed -> the readings."""
    readings = []
    for n, seed in enumerate(seeds):
        # every seed a world of its own, under its own renaming
        own = copy.copy(ctx)
        own.traffic = traffic_gen.own_world(ctx.traffic, seed)
        engine, _, first_loss, left = start_engine(own, seed)
        del engine
        live = train.free_device()
        readings.append(dict(seed=seed, **against_reference(
            own, seed, first_loss, left, controls=n < control_seeds)))
        ctx.log("calibrate", live_bytes_after_engine=live, **readings[-1])
    return readings


def refused(numbers, limits):
    """The names of the limits a set of numbers (a control's) breaks."""
    held = dict(KEPT, **{k: v["limit"] for k, v in limits.items()
                         if k in CONTROL_OF})
    return sorted(k for k, v in numbers.items() if k in held and v > held[k])


def limits_from(readings):
    """A cell's limits from its readings, by ``runners/train.py``'s rule: the
    geometric mean of the largest the sound runs gave and the smallest the
    control gave, refused where the control reads under three times the sound
    runs (the update's control is the state left unchanged, ``CONTROL_OF``).
    The limits kept in this file must hold in every reading too, and stand
    as clear of the fp8 control; and every reading of the bfloat16-masters
    control and of the third control, the reference with every layer full,
    must break a limit."""
    out = {}
    for number, control in CONTROL_OF.items():
        sound = [r["program"][number] for r in readings]
        low = [r[control][number] for r in readings if control in r]
        if len(low) < 3 or min(low) < 3 * max(sound):
            raise SystemExit(f"{number}: control {low} does not stand three "
                             f"times clear of the sound runs {sound}")
        out[number] = {"limit": (max(sound) * min(low)) ** 0.5,
                       "sound_largest": max(sound), "sound_seeds": len(sound),
                       "control": control, "control_smallest": min(low),
                       "control_seeds": len(low)}
    import jax

    # the kept limits were set on the chip at the cell's size: there they
    # stand between the readings; a CPU rehearsal only has to stay under them
    on_chip = jax.default_backend() == "tpu"
    for number, limit in KEPT.items():
        sound = max(r["program"][number] for r in readings)
        low = min(r["control_fp8"][number] for r in readings
                  if "control_fp8" in r)
        if sound >= limit or (on_chip and (limit >= low or low < 3 * sound)):
            raise SystemExit(f"{number}: the kept limit {limit} does not "
                             f"stand between {sound} and {low}")
    for r in readings:
        if "control_bf16_masters" in r and not refused(
                r["control_bf16_masters"], out):
            raise SystemExit("bfloat16 masters would pass: "
                             f"{r['control_bf16_masters']}")
        if "control_every_layer_full" in r and not refused(
                r["control_every_layer_full"], out):
            raise SystemExit("a program that ignored the window would pass: "
                             f"{r['control_every_layer_full']}")
    return out


def layers_counted(cfg, *counters):
    """Whether every set of step counters counted the held layers by kind."""
    kinds = ref.layer_kinds(cfg)
    return all(c.get(name) == kinds.count(kind) for c in counters
               for name, kind in COUNTED) and all(
                   c.get("moe_layer_applications") == len(kinds)
                   for c in counters)


def check(ctx, state, record):
    losses = record["losses"]
    k = max(1, min(3, len(losses) // 2))
    head, tail = core.median(losses[:k]), core.median(losses[-k:])
    # what the window's steps counted of themselves (``window``)
    in_window = record["step_counters"]
    first = state["first_step"]["counters"]
    # the engine gives way to the reference's float32 weights and gradient
    del state["engine"]
    ctx.log("freed", live_bytes_after_engine=train.free_device(),
            step_counters=in_window)
    got = against_reference(ctx, ctx.seed, state["first_loss"],
                            state["first_step"])["program"]
    ctx.log("reference", **got)
    limits = (core.load_json(REHEARSAL_LIMITS) if ctx.rehearse
              else core.load_limits(ctx.cell["name"]))
    counted = layers_counted(ctx.config, in_window, first)
    dropped = max(c.get("moe_slots_dropped", -1.0) for c in (in_window, first))
    return [
        core.check("grad_rel_err_vs_reference", got["grad_rel_err"],
                   limits["grad_rel_err"]["limit"]),
        core.check("adam_update_rel_err_vs_reference",
                   got["adam_update_rel_err"],
                   limits["adam_update_rel_err"]["limit"]),
        core.check("logprob_rms_vs_reference", got["logprob_rms"],
                   LOGPROB_RMS_LIMIT),
        core.check("routed_set_mismatch_share_vs_reference",
                   got["routed_set_mismatch_share"],
                   ROUTED_SET_MISMATCH_LIMIT),
        core.check("slots_held_rel_diff_vs_reference",
                   got["slots_held_rel_diff"], SLOTS_HELD_REL_LIMIT),
        core.check("moe_slots_dropped", dropped, 0.0, ok=dropped == 0.0),
        core.check("layers_of_every_kind_counted", int(counted), 1,
                   ok=counted, better="higher"),
        core.check("nonfinite_losses", record["failed"], 0),
        core.check("loss_fall_over_window", head - tail, 0.0,
                   ok=len(losses) < 2 or tail < head, better="higher"),
    ]


if __name__ == "__main__":
    # the CPU rehearsal's limits: ``calibrate.py --rehearse --write`` at the
    # tiny preset, with its output sent to this cell's own file
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from benchmarks import calibrate as calibrate_cli

    core.limits_path = lambda *_a, **_k: REHEARSAL_LIMITS
    sys.exit(calibrate_cli.main([
        "--workload", "train-mellum2-ep4-8k", "--seeds", "8",
        "--control-seeds", "4", "--rehearse", "--write"]))
