"""Runner for training a byte-level decoder with EVA attention, a float32
residual stream and a head of several slices (EvaByte: one chip's share of
the heads, one pipeline stage of the layers): ``dst.initialize`` ->
``engine.train_batch`` on a fresh seeded batch every step under the traffic
file's schedule, as ``runners/train_laguna.py`` runs its cell, without a
``world`` (a dense model's work does not depend on the seed).

``runners/train_swa_moe.py``'s steps are model-free but for the names they
read from their own module (the reference, the model, the leaves that are
sampled, the comparison).  ``core.load_runner`` executes a runner's file
anew for every caller, so ``swa`` below is this file's own copy, and those
names are given it here, as ``runners/train_laguna.py`` does: its
``start_engine``, ``setup``, ``calibrate``, ``engine_first_step``,
``plain_first_step`` (the schedule's first rate) then run this model.  The
comparison of a first step is ``runners/train.py``'s, the steps that keep
their counters and the cast to the compute types
``runners/train_hybrid.py``'s; the model, the plain reference
(``reference/evabyte_ref.py``), its controls, the window's record and the
check are this file's.  ``check`` compares, at the timed sizes, what the
timed engine's first step left (clipped gradient, change of the float32
masters) and the program's forward on the seeded weights (the
log-probability of EVERY target: eight slices a position) with the
reference.

The controls the limits are read against: the reference in fp8 (every
matmul's inputs and incoming gradients), a state left unchanged (the
update's: under the warm-up the first step moves a weight by 1e-6), its Adam
step with bfloat16 masters, the reference WITHOUT its float32 islands
(bfloat16 matmuls and the residual sums, softmax statistics and logits
rounded to bfloat16: ``control_bf16_islands``), and the reference with the
summaries left out (windowed attention alone).

The CPU rehearsal's limits are in ``limits/rehearsal-evabyte.json`` (never
``calibrate.py --rehearse --write`` for this cell: that writes
``limits/rehearsal.json``, the Pythia rehearsal's); it is rewritten by
``python3 benchmarks/runners/train_evabyte.py``.
"""

import os
import sys

import numpy as np

if __name__ == "__main__":      # the rehearsal's limits, see the foot
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmarks import core, traffic_gen
from benchmarks.reference import evabyte_ref as ref
# a program that has no such model fails here, as the runner is loaded:
# before anything is put on the device
from deeperspeed_tpu.models.evabyte import EvaByte, EvaByteConfig

train = core.load_runner("train")
hybrid = core.load_runner("train_hybrid")
swa = core.load_runner("train_swa_moe")

#: RMS over the first sequence's (position, slice) pairs whose target exists
#: of (program log-prob - reference log-prob).  Kept here; set on the chip at
#: the cell's size by the rule of the limits file, the geometric mean of the
#: largest a sound run gave and the smallest the fp8 control gave (my chip
#: runs, PR 49, ``calibrate.py --seeds 8 --control-seeds 4``, one call):
#: sound runs read 0.00908-0.01028 (8 seeds; two runs of the cell 0.00925,
#: 0.00978), the fp8 control 0.1117-0.1227 (10.9 times clear), the summaries
#: left out 0.324-0.377.  The reference WITHOUT its float32 islands reads
#: 0.00904-0.01003: what the sound runs read.  Four layers deep the error
#: of bfloat16 operands is all there is to see, and no limit on an output can
#: tell a float32 stream, float32 statistics or float32 logits from
#: bfloat16 ones (PERF.md section 7).
LOGPROB_RMS_LIMIT = 0.034
#: |engine's first-step loss - reference loss on the same batch|: a mean over
#: 131,044 targets that the precision hardly moves; it guards the loss path
#: (the eight slices' targets, the mask at the end, the reduction).  Sound
#: runs read 0.00005-0.00117 (8 seeds; the first 0.00117, the others under
#: 0.00052), so the 0.003 of five accepted cells leaves the first reading
#: 2.6 times of room and not three: the looped cell's 0.005 does (4.3 times).
#: The fp8 control reads 0.0031-0.0094, the islands lost 0.00003-0.0017.
FIRST_LOSS_LIMIT = 0.005
REHEARSAL_LIMITS = os.path.join(core.BENCH_DIR, "limits",
                                "rehearsal-evabyte.json")
ISLANDS = "control_bf16_islands"
NO_SUMMARIES = "control_summaries_left_out"
#: the update's control is a state left unchanged, as the Mellum cell's:
#: number -> the control that bounds its limit from above
UNCHANGED, CONTROL_OF = swa.UNCHANGED, swa.CONTROL_OF
KEPT = {"logprob_rms": LOGPROB_RMS_LIMIT}


def program_model(config, traffic):
    """The program's model object for a configuration file."""
    import jax.numpy as jnp

    if config.get("attention_class") != "eva":
        raise ValueError("the program's EvaByte has EVA attention only")
    return EvaByte(EvaByteConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        intermediate_size=config["intermediate_size"],
        window_size=config["window_size"], chunk_size=config["chunk_size"],
        num_pred_heads=config["num_pred_heads"],
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"],
        init_std=float(config.get("init_std", 0.01275)),
        layers_held=config.get("layers_held"),
        first_layer_held=int(config.get("first_layer_held", 0)),
        attention_heads_held=config.get("attention_heads_held"),
        first_head_held=int(config.get("first_head_held", 0)),
        max_seq_len=int(traffic["seq_len"]),
        ce_chunk_tokens=int(traffic["ce_chunk_tokens"]),
        dtype=getattr(jnp, traffic.get("dtype", "bfloat16")),
        remat=bool(traffic.get("remat", False))))


def sampled_tops(cfg):
    """Top-level names of the leaves whose first-step gradient and update
    are compared: both tables, the closing norm, and every parameter of the
    first, middle and last layer held."""
    last = ref.depth(cfg) - 1
    return {"embed_tokens", "lm_head_kernel", "final_norm_weight"} | {
        f"layers_{i}" for i in (0, last // 2, last)}


def vocab(cfg):
    return cfg["vocab_size"]


def window(ctx, state):
    """``runners/train.py``'s timed window, and in its record the mean over
    the window's steps of what every step counted of itself."""
    counted = hybrid.CountedSteps(state["engine"])
    record = train.window(ctx, dict(state, engine=counted))
    steps = [{k: float(np.asarray(v)) for k, v in c.items()}
             for c in counted.counters]
    record["step_counters"] = {
        k: float(np.mean([c[k] for c in steps])) for k in steps[0]}
    ctx.log("window_counters", **record["step_counters"])
    return record


def compare_logprobs(got, want, inside):
    """RMS over the targets that exist of the difference of two [S, K]
    log-probabilities."""
    inside = np.asarray(inside, bool)
    d = (np.asarray(got, np.float64) - np.asarray(want, np.float64))[inside]
    return float(np.sqrt(np.mean(d * d)))


def against_reference(ctx, seed, first_loss, left, controls=False):
    """The program's first step against the plain reference, on a device the
    program has left.  ``controls`` adds what the controls read in the
    program's place.  -> dict of numbers."""
    import jax
    import jax.numpy as jnp

    cfg, traffic = ctx.config, ctx.traffic
    first = traffic_gen.TokenBatches(traffic, cfg["vocab_size"], seed).batch(0)
    ids, labels = jnp.asarray(first["input_ids"]), jnp.asarray(first["labels"])
    params = ref.init_params(cfg, seed)
    model = program_model(cfg, traffic)
    prog_lp, inside = jax.jit(model.logprobs)(
        hybrid.cast_for_compute(model, params, traffic), ids[:1], labels[:1])
    prog_lp, inside = np.asarray(prog_lp)[0], np.asarray(inside)[0]
    ref_loss, grads, ref_lp = ref.loss_and_grads(params, cfg, ids, labels)
    ref_lp = np.asarray(ref_lp)
    init = train.sample_leaves(params, sampled_tops(cfg))
    want = swa.plain_first_step(cfg, traffic, params, grads)
    out = {"program": dict(
        train.compare_first_step(left, want, init),
        logprob_rms=compare_logprobs(prog_lp, ref_lp, inside),
        targets=int(inside.sum()),
        first_loss_abs_diff=abs(first_loss - float(ref_loss)))}
    if not controls:
        return out
    low = swa.plain_first_step(cfg, traffic, params, grads, "bfloat16")
    out["control_bf16_masters"] = {
        "adam_update_rel_err": train.compare_first_step(
            low, want, init)["adam_update_rel_err"]}
    out[UNCHANGED] = {
        "adam_update_rel_err": train.compare_first_step(
            dict(want, master=init), want, init)["adam_update_rel_err"]}
    del grads, low
    for name, changed in (
            ("control_fp8", dict(precision="fp8")),
            (ISLANDS, dict(precision="bfloat16", islands="bfloat16")),
            (NO_SUMMARIES, dict(without=("summaries",)))):
        ctl_loss, grads, ctl_lp = ref.loss_and_grads(params, cfg, ids, labels,
                                                     **changed)
        low = swa.plain_first_step(cfg, traffic, params, grads)
        out[name] = dict(
            grad_rel_err=train.compare_first_step(low, want, init)[
                "grad_rel_err"],
            logprob_rms=compare_logprobs(np.asarray(ctl_lp), ref_lp, inside),
            first_loss_abs_diff=abs(float(ctl_loss) - float(ref_loss)))
        del grads, low
    return out


# this file's own copy of the Mellum cell's runner runs this model (no
# ``world`` here: its weights are the seed's and no table moves)
swa.ref, swa.program_model, swa.sampled_tops = ref, program_model, sampled_tops
swa.vocab, swa.against_reference = vocab, against_reference
engine_config, first_rate = swa.engine_config, swa.first_rate
plain_first_step, start_engine = swa.plain_first_step, swa.start_engine
setup, calibrate = swa.setup, swa.calibrate


def refused(numbers, limits):
    """The names of the limits a set of numbers (a control's) breaks."""
    held = dict(KEPT, **{k: v["limit"] for k, v in limits.items()
                         if k in CONTROL_OF})
    return sorted(k for k, v in numbers.items() if k in held and v > held[k])


def limits_from(readings):
    """A cell's limits from its readings, by ``runners/train.py``'s rule: the
    geometric mean of the largest the sound runs gave and the smallest the
    control gave, refused where the control reads under three times the sound
    runs (the update's control is the state left unchanged).  The limits
    kept in this file must hold in every reading too and stand as clear of
    the fp8 control; every reading of the bfloat16-masters control and of the
    summaries left out must break a limit.  What the reference without its
    float32 islands reads is in the calibration's lines (``ISLANDS``) and
    in PERF.md section 2, and breaks no rule here: the limits file holds the
    two numbers every cell's holds and nothing else."""
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
            > FIRST_LOSS_LIMIT / (3 if on_chip else 1):
        raise SystemExit("the first-loss limit does not leave the sound "
                         "readings three times of room")
    for r in readings:
        for control in ("control_bf16_masters",
                        *((NO_SUMMARIES,) if on_chip else ())):
            if control in r and not refused(r[control], out):
                raise SystemExit(f"{control} would pass: {r[control]}")
    return out


def layers_counted(cfg, *counters):
    """Whether every set of step counters counted the layers held and more
    pairs visited than needed."""
    return all(c.get("layer_applications") == ref.depth(cfg)
               and c.get("eva_pairs_visited", 0) >= c.get(
                   "eva_pairs_needed", 1) > 0 for c in counters)


def pairs_needed(cfg, traffic):
    """(row, key) pairs a step's equations need, every head and layer."""
    rows = int(traffic["micro_batch"]) * int(traffic.get("grad_accum", 1))
    return (rows * ref.heads(cfg) * ref.depth(cfg)
            * ref.pairs_needed(cfg, int(traffic["seq_len"])))


def check(ctx, state, record):
    losses = record["losses"]
    k = max(1, min(3, len(losses) // 2))
    head, tail = core.median(losses[:k]), core.median(losses[-k:])
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
    needed = pairs_needed(ctx.config, ctx.traffic)
    return [
        core.check("grad_rel_err_vs_reference", got["grad_rel_err"],
                   limits["grad_rel_err"]["limit"]),
        core.check("adam_update_rel_err_vs_reference",
                   got["adam_update_rel_err"],
                   limits["adam_update_rel_err"]["limit"]),
        core.check("logprob_rms_vs_reference", got["logprob_rms"],
                   LOGPROB_RMS_LIMIT),
        core.check("first_loss_abs_diff_vs_reference",
                   got["first_loss_abs_diff"], FIRST_LOSS_LIMIT),
        # the program's count of the pairs its equations need is the
        # reference's, to the pair
        core.check("eva_pairs_needed_rel_diff_vs_reference",
                   abs(first.get("eva_pairs_needed", 0.0) - needed) / needed,
                   1e-6),
        core.check("layers_counted", int(counted), 1, ok=counted,
                   better="higher"),
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
        "--workload", "train-evabyte-tp2-16k", "--seeds", "8",
        "--control-seeds", "4", "--rehearse", "--write"]))
