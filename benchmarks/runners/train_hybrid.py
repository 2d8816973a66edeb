"""Runner for training a hybrid state-space / mixture-of-experts language
model (Nemotron-H: a pattern of Mamba-2, latent expert and attention layers,
one chip's share of each): ``dst.initialize`` -> ``engine.train_batch`` on a
fresh seeded batch every step, exactly as ``runners/train.py`` runs the
Pythia cells.

What is model-free comes from that runner (the engine's JSON config, the
mesh, the timed window, the comparison of a first step's gradient and
update); the model, the leaves that are sampled, the plain reference
(``reference/nemotron_h_ref.py``) and the check are this file's.  ``check``
compares, at the timed sizes, what the timed engine's first step left
(clipped gradient, change of the float32 masters, the step's own count of
routed slots) and the program's forward on the seeded weights (per-token
log-probabilities, which held experts every token chose) with the reference.

The CPU rehearsal's limits are in ``limits/rehearsal-nemotron.json`` (never
``calibrate.py --rehearse --write`` for this cell: that writes
``limits/rehearsal.json``, the Pythia rehearsal's); it is rewritten by
``python3 benchmarks/runners/train_hybrid.py``.
"""

import os
import sys

import numpy as np

if __name__ == "__main__":      # the rehearsal's limits, see the foot
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmarks import core, traffic_gen
from benchmarks.reference import nemotron_h_ref as ref
# a program that has no hybrid model fails here, as the runner is loaded:
# before anything is put on the device
from deeperspeed_tpu.models.nemotron_h import NemotronH, NemotronHConfig

train = core.load_runner("train")

#: Limits of the output comparison kept here; those of the gradient and of
#: the update are per cell in ``limits/<cell>.json`` (``limits_from``).  All
#: were set from readings on the chip at the cell's size by one rule: the
#: geometric mean of the largest a sound run gave and the smallest the fp8
#: control gave.  The readings quoted are those of one calibration of the
#: final code (my chip runs, PR 34: ``calibrate.py --seeds 8 --control-seeds
#: 4`` and the seven runs of the cell in the same call; PERF.md section 2).
#: RMS over the first sequence's tokens of (program log-prob - reference
#: log-prob) of the label: sound runs read 0.0130-0.0138, the control
#: 0.1476-0.1510.
LOGPROB_RMS_LIMIT = 0.047
#: Share of the (token, expert layer) pairs of the first sequence whose set
#: of chosen held experts differs from the reference's.  Top-22 of 512 sigmoid
#: scores flips on rounding, so the share is counted beside a limit and not
#: hidden: sound runs read 0.0028-0.0066 (the reference's own arithmetic with
#: bfloat16 matmul inputs reads 0.0036-0.0059), the control 0.0435-0.0669.
ROUTED_SET_MISMATCH_LIMIT = 0.018
#: |slots the program's first step counted - slots the reference counts on
#: the same batch| / the reference's, the mean a layer: the count the FLOPs of
#: ``train.hybrid_mfu_pct`` stand on.  Sound runs read 0.0000-0.0025 (15
#: seeds; 0.0028 the largest of some 20 more in earlier calls of the same
#: forward pass), the control 0.0076-0.0234 (0.0064 the smallest of earlier
#: calls): a few flips of 25,000 slots either way, so the control stands
#: only twice clear here; it fails by the three limits above it.
SLOTS_HELD_REL_LIMIT = 0.0042
#: |engine's first-step loss - reference loss on the same batch|: a mean over
#: the step's 16k tokens that the precision hardly moves (sound runs read at
#: most 0.0008, the control 0.0005-0.0047: it does not separate); the limit of
#: the harness's other train cells, three times the sound reading: it guards
#: the loss path.
FIRST_LOSS_LIMIT = 0.003
REHEARSAL_LIMITS = os.path.join(core.BENCH_DIR, "limits",
                                "rehearsal-nemotron.json")
CONTROL_OF = train.CONTROL_OF
#: the numbers with a limit in this file -> that limit
KEPT = {"logprob_rms": LOGPROB_RMS_LIMIT,
        "routed_set_mismatch_share": ROUTED_SET_MISMATCH_LIMIT,
        "slots_held_rel_diff": SLOTS_HELD_REL_LIMIT}


def program_model(config, traffic):
    """The program's model object for a configuration file."""
    import jax.numpy as jnp

    sh = ref.share(config)
    return NemotronH(NemotronHConfig(
        vocab_size=sh["vocab"], hidden_size=config["hidden_size"],
        pattern=ref.pattern(config), norm_eps=config["layer_norm_epsilon"],
        mamba_num_heads=sh["mamba_heads"],
        mamba_head_dim=config["mamba_head_dim"], n_groups=sh["mamba_groups"],
        ssm_state_size=config["ssm_state_size"],
        conv_kernel=config["conv_kernel"], chunk_size=config["chunk_size"],
        time_step_min=config["time_step_min"],
        time_step_max=config["time_step_max"],
        time_step_floor=config["time_step_floor"],
        num_heads=sh["q_heads"], num_kv_heads=sh["kv_heads"],
        head_dim=config["head_dim"],
        n_routed_experts=config["n_routed_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        moe_latent_size=config["moe_latent_size"],
        moe_shared_expert_intermediate_size=config[
            "moe_shared_expert_intermediate_size"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        first_expert=sh["first_expert"], experts_held=sh["experts"],
        max_seq_len=int(traffic["seq_len"]),
        ce_chunk_tokens=int(traffic["ce_chunk_tokens"]),
        dtype=getattr(jnp, traffic.get("dtype", "bfloat16")),
        remat=bool(traffic.get("remat", False))))


def sampled_tops(cfg):
    """Top-level names of the leaves whose first-step gradient and update
    are compared: both tables, the final norm, and every parameter of one
    layer of each kind (the first of each in the pattern)."""
    layers = ref.pattern(cfg)
    return {"embed_tokens", "lm_head_kernel", "final_norm_scale"} | {
        f"layers_{layers.index(kind)}" for kind in set(layers)}


def cast_for_compute(model, params, traffic):
    """Seeded masters in the types the engine computes in: everything in the
    compute type but what the model keeps in float32."""
    import re

    import jax
    import jax.numpy as jnp

    dtype = getattr(jnp, traffic.get("dtype", "bfloat16"))
    keep = [re.compile(p) for p in model.no_cast_paths()]

    def cast(path, leaf):
        name = "/".join(str(k.key) for k in path)
        return leaf if any(p.search(name) for p in keep) else leaf.astype(
            dtype)

    return jax.jit(lambda p: jax.tree_util.tree_map_with_path(cast, p))(params)


def vocab(cfg):
    return ref.share(cfg)["vocab"]


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
    control's) clipped by global norm, one Adam step on ``params``;
    ``master_dtype`` "bfloat16" is the optimizer's control."""
    import jax
    import jax.numpy as jnp

    opt, tops = traffic["optimizer"], sorted(sampled_tops(cfg))
    b1, b2 = opt["betas"]
    norm = jax.jit(ref.global_norm)(grads)

    def step(p, g, norm):
        scale = ref.clip_scale(norm, float(traffic["clip"]))
        g = jax.tree_util.tree_map(lambda x: x * scale, g)
        return g, ref.adam_first_step(p, g, float(opt["lr"]), b1, b2,
                                      float(opt["eps"]))

    clipped, new = jax.jit(step)({k: params[k] for k in tops},
                                {k: grads[k] for k in tops}, norm)
    master = train.sample_leaves(new, tops, getattr(jnp, master_dtype))
    return {"moment": {k: (1 - b1) * g for k, g in
                       train.sample_leaves(clipped, tops).items()},
            "master": {k: v.astype(np.float32) for k, v in master.items()},
            "grad_norm": float(norm)}


def start_engine(ctx, seed):
    """Seeded weights -> the engine, after its first step on the seed's
    first batch.  -> (engine, batches, first loss, what the step left)."""
    import deeperspeed_tpu as dst

    cfg, traffic = ctx.config, ctx.traffic
    batches = traffic_gen.TokenBatches(traffic, vocab(cfg), seed)
    params = ref.init_params(cfg, seed)
    engine, _, _, _ = dst.initialize(
        model=program_model(cfg, traffic), model_parameters=params,
        mesh=train.cell_mesh(ctx), config=train.engine_config(traffic, seed))
    del params
    first_loss = float(engine.train_batch(batch=batches.batch(0)))
    return engine, batches, first_loss, engine_first_step(engine, cfg)


def setup(ctx):
    # warm the one step program: step 0 gives the first loss and what the
    # check compares, step 1 shows that nothing more compiles
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


class CountedSteps:
    """An engine's ``train_batch`` that keeps what every step's model
    counted, as the device arrays they are: nothing is read, so a step still
    runs ahead of the host as it does without."""

    def __init__(self, engine):
        self.engine, self.counters = engine, []

    def train_batch(self, batch):
        from deeperspeed_tpu import telemetry

        loss = self.engine.train_batch(batch=batch)
        self.counters.append(
            telemetry.step_counters(read=False).get("train_step", {}))
        return loss


def window(ctx, state):
    """``runners/train.py``'s timed window, and in its record what the
    window's steps counted: the mean of every counter over the steps (the
    routed load is a step's own), the most a step dropped, and the routed
    slots of the first and the last step, which say whether the load held."""
    counted = CountedSteps(state["engine"])
    record = train.window(ctx, dict(state, engine=counted))
    steps = [{k: float(np.asarray(v)) for k, v in c.items()}
             for c in counted.counters]
    mean = {k: float(np.mean([c[k] for c in steps])) for k in steps[0]}
    if "moe_slots_dropped" in mean:
        mean["moe_slots_dropped"] = max(c["moe_slots_dropped"] for c in steps)
    record["step_counters"] = mean
    slots = [c.get("moe_slots_held", 0.0) for c in steps]
    # beside each step's load the time it took, where there is a clock: a
    # step's time follows the slots routed here.  ``train.window`` waits for
    # a loss one step late and ``train_batch`` fences, so its instants are
    # the ends of the second step on (the last one twice)
    took = {} if ctx.rehearse else {"step_ms_from_third_step": [
        round(1e3 * ms, 1) for ms in np.diff(record["step_ready_at"][:-1])]}
    ctx.log("window_counters", moe_slots_held_first=slots[0],
            moe_slots_held_last=slots[-1], moe_slots_held_min=min(slots),
            moe_slots_held_max=max(slots),
            moe_slots_held_by_step=[round(s) for s in slots], **took, **mean)
    return record


def compare_routing(got, want):
    """Which held experts every token chose, [layers, S, held] bool, of the
    program (or a control) against the reference's -> the share of (layer,
    token) pairs whose sets differ."""
    got, want = np.asarray(got, bool), np.asarray(want, bool)
    return float(np.mean(np.any(got != want, axis=-1))) if want.size else 0.0


def against_reference(ctx, seed, first_loss, left, controls=False):
    """The program's first step against the plain reference, on a device the
    program has left.  ``controls`` adds what the controls read in the
    program's place: the reference in fp8 (forward and backward), and its
    Adam step with the masters kept in bfloat16.  -> dict of numbers."""
    import jax
    import jax.numpy as jnp

    cfg, traffic = ctx.config, ctx.traffic
    first = traffic_gen.TokenBatches(traffic, vocab(cfg), seed).batch(0)
    ids, labels = jnp.asarray(first["input_ids"]), jnp.asarray(first["labels"])
    params = ref.init_params(cfg, seed)
    model = program_model(cfg, traffic)
    prog_lp, prog_chosen, _ = jax.jit(model.logprobs)(
        cast_for_compute(model, params, traffic), ids[:1], labels[:1])
    prog_lp, prog_chosen = np.asarray(prog_lp)[0], np.asarray(prog_chosen)[:, 0]
    ref_loss, grads, ref_lp, ref_chosen = ref.loss_and_grads(params, cfg, ids,
                                                             labels)
    ref_lp, ref_chosen = np.asarray(ref_lp), np.asarray(ref_chosen)
    # the mean number of slots a layer held over the whole batch
    ref_slots = float(ref_chosen.sum()) / max(ref_chosen.shape[1], 1)
    init = train.sample_leaves(params, sampled_tops(cfg))
    want = plain_first_step(cfg, traffic, params, grads)
    counters = left["counters"]
    out = {"program": dict(
        train.compare_first_step(left, want, init),
        logprob_rms=train.compare_logprobs(prog_lp, ref_lp),
        routed_set_mismatch_share=compare_routing(prog_chosen, ref_chosen[0]),
        slots_held_rel_diff=abs(counters.get("moe_slots_held", 0.0)
                                - ref_slots) / max(ref_slots, 1.0),
        slots_held=counters.get("moe_slots_held"),
        slots_held_reference=ref_slots,
        first_loss_abs_diff=abs(first_loss - float(ref_loss)))}
    if controls:
        low = plain_first_step(cfg, traffic, params, grads, "bfloat16")
        out["control_bf16_masters"] = {
            "adam_update_rel_err": train.compare_first_step(
                low, want, init)["adam_update_rel_err"]}
        del grads, low
        ctl_loss, grads, ctl_lp, ctl_chosen = ref.loss_and_grads(
            params, cfg, ids, labels, "fp8")
        low = plain_first_step(cfg, traffic, params, grads)
        ctl_chosen = np.asarray(ctl_chosen)
        out["control_fp8"] = dict(
            grad_rel_err=train.compare_first_step(low, want, init)[
                "grad_rel_err"],
            logprob_rms=train.compare_logprobs(np.asarray(ctl_lp), ref_lp),
            routed_set_mismatch_share=compare_routing(ctl_chosen[0],
                                                      ref_chosen[0]),
            slots_held_rel_diff=abs(float(ctl_chosen.sum())
                                    - float(ref_chosen.sum()))
            / max(float(ref_chosen.sum()), 1.0),
            first_loss_abs_diff=abs(float(ctl_loss) - float(ref_loss)))
    return out


def calibrate(ctx, seeds, control_seeds=3):
    """Readings for the limits, many seeds in one process: the program's
    first step, and on the first ``control_seeds`` seeds the controls,
    against the plain reference.  One JSON line per seed -> the readings."""
    readings = []
    for n, seed in enumerate(seeds):
        engine, _, first_loss, left = start_engine(ctx, seed)
        del engine
        live = train.free_device()
        readings.append(dict(seed=seed, **against_reference(
            ctx, seed, first_loss, left, controls=n < control_seeds)))
        ctx.log("calibrate", live_bytes_after_engine=live, **readings[-1])
    return readings


def limits_from(readings):
    """A cell's limits from its readings, by ``runners/train.py``'s rule: the
    geometric mean of the largest the sound runs gave and the smallest the
    control gave, refused where the control reads under three times the sound
    runs.  The limits kept in this file must hold in every reading too, and
    stand as clear of the fp8 control."""
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
    if max(r["program"]["first_loss_abs_diff"] for r in readings) \
            > FIRST_LOSS_LIMIT:
        raise SystemExit("the first-loss limit does not hold in a reading")
    return out


def check(ctx, state, record):
    losses = record["losses"]
    k = max(1, min(3, len(losses) // 2))
    head, tail = core.median(losses[:k]), core.median(losses[-k:])
    # what the window's steps counted of themselves (``window``)
    in_window = record["step_counters"]
    kinds = ref.pattern(ctx.config)
    # the engine gives way to the reference's float32 weights and gradient
    del state["engine"]
    ctx.log("freed", live_bytes_after_engine=train.free_device(),
            step_counters=in_window)
    got = against_reference(ctx, ctx.seed, state["first_loss"],
                            state["first_step"])["program"]
    ctx.log("reference", **got)
    limits = (core.load_json(REHEARSAL_LIMITS) if ctx.rehearse
              else core.load_limits(ctx.cell["name"]))
    counted = all(c.get(name) == kinds.count(kind) for c in (
        in_window, state["first_step"]["counters"]) for name, kind in (
            ("ssm_layer_applications", "M"), ("moe_layer_applications", "E"),
            ("attention_layer_applications", "*")))
    dropped = max(c.get("moe_slots_dropped", -1.0) for c in (
        in_window, state["first_step"]["counters"]))
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
        core.check("first_loss_abs_diff_vs_reference",
                   got["first_loss_abs_diff"], FIRST_LOSS_LIMIT),
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
        "--workload", "train-nemotron3-super-ep64-8k", "--seeds", "8",
        "--control-seeds", "4", "--rehearse", "--write"]))
