"""Runner for training a decoder of Mellum's schema with gated attention
whose head count goes by the layer's kind, half-rotary full layers, a leading
dense layer and a shared expert beside a scaled softmax-routed mixture
(Laguna-S-2.1: one chip's share of the experts, of the heads and of the
vocabulary; the dense layer and one period of the sparse ones):
``dst.initialize`` -> ``engine.train_batch`` on a fresh seeded batch every
step, under the traffic file's schedule and ``world``, exactly as
``runners/train_swa_moe.py`` runs the Mellum cell.

That runner's steps are model-free but for the names they read from their
own module: the reference, the model, the leaves that are sampled and the
comparison.  ``core.load_runner`` executes a runner's file anew for every
caller, so ``swa`` below is this file's own copy, and those names are given
it here (``swa.ref`` ...): its ``start_engine``, ``setup``, ``calibrate``,
``engine_first_step``, ``plain_first_step`` and ``seeded_params`` then run
this model.  The timed window that keeps every step's counters is
``runners/train_hybrid.py``'s.  The model, the plain reference
(``reference/laguna_ref.py``), its controls and the check are this file's.

The controls the limits must refuse: the reference in fp8, its Adam step
with bfloat16 masters, a state left unchanged, and the reference with one
MECHANISM LEFT OUT at a time (``ref.MECHANISMS``: every layer full, no gate,
no shared expert, rotary on the whole head of the full layers, routed weights
unscaled), each what a program that dropped it would compute; those are
read on the first sequence's forward pass, and the last through the backward
pass as well (``BY_GRADIENT``).

The CPU rehearsal's limits are in ``limits/rehearsal-laguna.json`` (never
``calibrate.py --rehearse --write`` for this cell: that writes
``limits/rehearsal.json``, the Pythia rehearsal's); it is rewritten by
``python3 benchmarks/runners/train_laguna.py``.
"""

import os
import sys

import numpy as np

if __name__ == "__main__":      # the rehearsal's limits, see the foot
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmarks import core, traffic_gen
from benchmarks.reference import laguna_ref as ref
# a program that has no such model fails here, as the runner is loaded:
# before anything is put on the device
from deeperspeed_tpu.models.laguna import Laguna, LagunaConfig

train = core.load_runner("train")
hybrid = core.load_runner("train_hybrid")
swa = core.load_runner("train_swa_moe")

#: Limits of the output comparison kept here; those of the gradient and of
#: the update are per cell in ``limits/<cell>.json`` (``limits_from``).  The
#: first two by one rule from readings on the chip at the cell's size: the
#: geometric mean of the largest a sound run gave and the smallest the fp8
#: control gave.  The readings quoted are those of ``calibrate.py --seeds 8
#: --control-seeds 4`` (my chip runs, PR 47: eight sound seeds, four control
#: seeds; PERF.md section 2).
#: RMS over the first sequence's tokens of (program log-prob - reference
#: log-prob) of the label: sound runs read 0.0203-0.0219, the fp8 control
#: 0.2130-0.2252; with a mechanism left out: every layer full 0.128-0.138,
#: no shared expert 0.557-0.611, no gate 0.784-0.932, rotary on the whole
#: head of the full layers 1.39-1.43, and the routed weights unscaled
#: 0.0505-0.0574, UNDER this limit: that control fails by the gradient.
LOGPROB_RMS_LIMIT = 0.068
#: Share of the (token, sparse layer) pairs of the first sequence whose set
#: of chosen held experts differs from the reference's: the 10th and 11th of
#: 256 softmax scores swap on a bfloat16 rounding of the router's input.
#: Sound runs read 0.0092-0.0124, the fp8 control 0.0887-0.1116.
ROUTED_SET_MISMATCH_LIMIT = 0.033
#: |slots the program's first step counted - slots the reference counts on
#: the same batch| / the reference's, the mean a sparse layer: the count the
#: FLOPs of ``train.gated_swa_moe_mfu_pct`` stand on.  Sound runs read
#: 0.0004-0.0019 (a few flips of ~5,000 slots a layer either way) and the
#: fp8 control 0.0016-0.0422: the precision does NOT separate here, so the
#: limit is three times the largest sound reading and guards the counter (a
#: layer not counted reads 0.25), not the precision.
SLOTS_HELD_REL_LIMIT = 0.006
#: ``grad_rel_err`` over the ROUTED experts' leaves alone (the two matrices of
#: the experts held, in the sampled sparse layers): what the routed weights'
#: scale moves, and the one limit here whose control is not fp8 but the
#: reference with the routed weights UNSCALED.  That control's held experts
#: get 1 / 2.5 of their gradient, which the whole gradient (0.028-0.037
#: against the sound runs' 0.011-0.015) and a token's log-probability see
#: under three times clear: few tokens route here.  Sound runs read
#: 0.0456-0.0794 (an expert's gradient is a few hundred tokens' and a routed
#: set that flips moves a token's whole term), the unscaled control
#: 0.597-0.605 (7.5 times clear), the fp8 control 0.270-0.317.
ROUTED_GRAD_REL_LIMIT = 0.22
#: |engine's first-step loss - reference loss on the same batch|: the
#: accepted cells' limit.  Sound runs read 0.0001-0.0009, which leaves it
#: three times of room; the fp8 control reads 0.0003-0.0098 (it does not
#: separate: a mean over the step's 16k tokens); it guards the loss path.
FIRST_LOSS_LIMIT = 0.003
REHEARSAL_LIMITS = os.path.join(core.BENCH_DIR, "limits",
                                "rehearsal-laguna.json")
UNCHANGED = swa.UNCHANGED
CONTROL_OF = swa.CONTROL_OF
#: the numbers with a limit in this file that stands between the sound runs
#: and a control -> (that limit, that control)
KEPT = {"logprob_rms": (LOGPROB_RMS_LIMIT, "control_fp8"),
        "routed_set_mismatch_share": (ROUTED_SET_MISMATCH_LIMIT,
                                      "control_fp8"),
        "routed_grad_rel_err": (ROUTED_GRAD_REL_LIMIT,
                                "control_routed_weights_unscaled")}
#: those no control bounds, three times a sound reading -> that limit
GUARDS = {"slots_held_rel_diff": SLOTS_HELD_REL_LIMIT,
          "first_loss_abs_diff": FIRST_LOSS_LIMIT}
#: step counter -> (which of a layer's two kinds it counts, the kind): the
#: table of the reader whose FLOPs stand on those counters
mfu = core.layer_metric_reader("train.gated_swa_moe_mfu_pct")
COUNTED = mfu.COUNTED
#: control -> the mechanism the reference leaves out for it
LEFT_OUT = {"control_every_layer_full": "window",
            "control_gate_left_out": "gate",
            "control_shared_expert_left_out": "shared_expert",
            "control_whole_head_rotary": "partial_rotary",
            "control_routed_weights_unscaled": "routed_scale"}
#: the controls read through the backward pass too: a token's log-probability
#: hardly sees the routed weights' scale (a quarter of the (token, layer)
#: pairs route here, by a weight a slot), the held experts' gradients do
BY_GRADIENT = ("control_routed_weights_unscaled",)


def program_model(config, traffic):
    """The program's model object for a configuration file."""
    import jax.numpy as jnp

    whole, rope = ref.whole_heads(config), config["rope_parameters"]
    ref.share(config)                    # refuses what neither side runs
    return Laguna(LagunaConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        layer_types=tuple(config["layer_types"]),
        mlp_layer_types=tuple(config["mlp_layer_types"]),
        rms_norm_eps=config["rms_norm_eps"],
        full_attention_heads=whole.get(ref.FULL, 0),
        sliding_attention_heads=whole.get(ref.SLIDING, 0),
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], sliding_window=config["sliding_window"],
        rope_sliding=swa._rope(rope[ref.SLIDING]),
        rope_full=swa._rope(rope[ref.FULL]),
        partial_rotary_sliding=float(
            rope[ref.SLIDING].get("partial_rotary_factor", 1)),
        partial_rotary_full=float(
            rope[ref.FULL].get("partial_rotary_factor", 1)),
        intermediate_size=config["intermediate_size"],
        num_experts=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        shared_expert_intermediate_size=config[
            "shared_expert_intermediate_size"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        moe_routed_scaling_factor=float(config["moe_routed_scaling_factor"]),
        layers_held=config.get("layers_held"),
        first_layer_held=int(config.get("first_layer_held", 0)),
        routed_experts_held=config.get("routed_experts_held"),
        first_expert_held=int(config.get("first_expert_held", 0)),
        vocab_rows_held=config.get("vocab_rows_held"),
        full_attention_heads_held=config.get("full_attention_heads_held"),
        sliding_attention_heads_held=config.get(
            "sliding_attention_heads_held"),
        key_value_heads_held=config.get("key_value_heads_held"),
        first_key_value_head_held=int(
            config.get("first_key_value_head_held", 0)),
        max_seq_len=int(traffic["seq_len"]),
        ce_chunk_tokens=int(traffic["ce_chunk_tokens"]),
        dtype=getattr(jnp, traffic.get("dtype", "bfloat16")),
        remat=bool(traffic.get("remat", False))))


def sampled_tops(cfg):
    """Top-level names of the leaves whose first-step gradient and update
    are compared: both tables, the final norm, and every parameter of one
    layer of each kind (the first of each: the dense full one, a sparse
    sliding one, a sparse full one)."""
    kinds = ref.layer_kinds(cfg)
    return {"embed_tokens", "lm_head_kernel", "final_norm_scale"} | {
        f"layers_{kinds.index(kind)}" for kind in set(kinds)}


def vocab(cfg):
    return ref.share(cfg)["vocab"]


def against_reference(ctx, seed, first_loss, left, controls=False):
    """The program's first step against the plain reference, on a device the
    program has left.  ``controls`` adds what the controls read in the
    program's place: the reference in fp8 (forward and backward), its Adam
    step with the masters kept in bfloat16, a state left unchanged, and the
    first sequence's forward pass with each mechanism left out.
    -> dict of numbers."""
    import jax
    import jax.numpy as jnp

    cfg, traffic = ctx.config, ctx.traffic
    batches = traffic_gen.TokenBatches(traffic, vocab(cfg), seed)
    first = batches.batch(0)
    ids, labels = jnp.asarray(first["input_ids"]), jnp.asarray(first["labels"])
    params = swa.seeded_params(cfg, batches)
    model = program_model(cfg, traffic)
    prog_lp, prog_chosen, _ = jax.jit(model.logprobs)(
        hybrid.cast_for_compute(model, params, traffic), ids[:1], labels[:1])
    prog_lp, prog_chosen = np.asarray(prog_lp)[0], np.asarray(prog_chosen)[:, 0]
    ref_loss, grads, ref_lp, ref_chosen = ref.loss_and_grads(params, cfg, ids,
                                                             labels)
    ref_lp, ref_chosen = np.asarray(ref_lp), np.asarray(ref_chosen)
    # the mean number of slots a sparse layer held over the whole batch
    ref_slots = float(ref_chosen.sum()) / ref_chosen.shape[1]
    init = train.sample_leaves(params, sampled_tops(cfg))
    want = swa.plain_first_step(cfg, traffic, params, grads)
    counters = left["counters"]

    def routed_grad(step):
        """``grad_rel_err`` of a first step over the routed experts' leaves."""
        routed = {k: v for k, v in want["moment"].items()
                  if k[-1].startswith("experts_")}
        return train.compare_first_step(step, dict(want, moment=routed),
                                        init)["grad_rel_err"]

    def forward_numbers(lp, chosen):
        """A forward pass of the first sequence against the reference's."""
        return dict(
            logprob_rms=train.compare_logprobs(lp, ref_lp),
            routed_set_mismatch_share=hybrid.compare_routing(chosen,
                                                             ref_chosen[0]))

    out = {"program": dict(
        train.compare_first_step(left, want, init),
        routed_grad_rel_err=routed_grad(left),
        **forward_numbers(prog_lp, prog_chosen),
        slots_held_rel_diff=abs(counters.get("moe_slots_held", 0.0)
                                - ref_slots) / max(ref_slots, 1.0),
        slots_held=counters.get("moe_slots_held"),
        slots_held_reference=ref_slots,
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
    ctl_loss, grads, ctl_lp, ctl_chosen = ref.loss_and_grads(
        params, cfg, ids, labels, precision="fp8")
    low = swa.plain_first_step(cfg, traffic, params, grads)
    ctl_chosen = np.asarray(ctl_chosen)
    out["control_fp8"] = dict(
        grad_rel_err=train.compare_first_step(low, want, init)[
            "grad_rel_err"],
        routed_grad_rel_err=routed_grad(low),
        **forward_numbers(np.asarray(ctl_lp), ctl_chosen[0]),
        slots_held_rel_diff=abs(float(ctl_chosen.sum())
                                - float(ref_chosen.sum()))
        / max(float(ref_chosen.sum()), 1.0),
        first_loss_abs_diff=abs(float(ctl_loss) - float(ref_loss)))
    del grads, low
    for name, mechanism in LEFT_OUT.items():
        if name in BY_GRADIENT:
            _, grads, lp, chosen = ref.loss_and_grads(
                params, cfg, ids, labels, without=(mechanism,))
            low = swa.plain_first_step(cfg, traffic, params, grads)
            out[name] = dict(
                grad_rel_err=train.compare_first_step(low, want, init)[
                    "grad_rel_err"],
                routed_grad_rel_err=routed_grad(low),
                **forward_numbers(np.asarray(lp), np.asarray(chosen)[0]))
            del grads, low
            continue
        lp, chosen = jax.jit(
            lambda p, x, y, m=mechanism: ref.token_logprobs(
                p, cfg, x, y, without=(m,)))(params, ids[0], labels[0])
        out[name] = forward_numbers(np.asarray(lp), np.asarray(chosen))
    return out


# this file's own copy of the Mellum cell's runner runs this model
swa.ref, swa.program_model, swa.sampled_tops = ref, program_model, sampled_tops
swa.vocab, swa.against_reference = vocab, against_reference
engine_config, first_rate = swa.engine_config, swa.first_rate
plain_first_step, seeded_params = swa.plain_first_step, swa.seeded_params
start_engine, setup, calibrate = swa.start_engine, swa.setup, swa.calibrate
#: the timed window, with every step's counters kept and the routed load by
#: step in the progress line ``window_counters``: the hybrid runner's
window = hybrid.window


def refused(numbers, limits):
    """The names of the limits a set of numbers (a control's) breaks."""
    held = dict({k: limit for k, (limit, _) in KEPT.items()},
                **{k: v["limit"] for k, v in limits.items()
                   if k in CONTROL_OF})
    return sorted(k for k, v in numbers.items() if k in held and v > held[k])


def limits_from(readings):
    """A cell's limits from its readings, by ``runners/train.py``'s rule: the
    geometric mean of the largest the sound runs gave and the smallest the
    control gave, refused where the control reads under three times the sound
    runs (the update's control is the state left unchanged, as the Mellum
    cell's).  The limits kept in this file must hold in every reading too,
    and stand as clear of their control (``KEPT``) or leave the sound
    readings three times of room (``GUARDS``); and every reading of the
    bfloat16-masters control and of each mechanism left out must break a
    limit."""
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
    for number, (limit, control) in KEPT.items():
        sound = max(r["program"][number] for r in readings)
        low = min(r[control][number] for r in readings if control in r)
        if sound >= limit or (on_chip and (limit >= low or low < 3 * sound)):
            raise SystemExit(f"{number}: the kept limit {limit} does not "
                             f"stand between {sound} and {low}")
    for number, limit in GUARDS.items():
        sound = max(r["program"][number] for r in readings)
        if sound > limit or (on_chip and 3 * sound > limit):
            raise SystemExit(f"{number}: the guard {limit} does not leave "
                             f"{sound} three times of room")
    # a mechanism left out is refused by the kept limits, so where those
    # stand: at the cell's size (the tiny preset's 40 positions hardly turn)
    for r in readings:
        for control in ("control_bf16_masters", *(LEFT_OUT if on_chip else ())):
            if control in r and not refused(r[control], out):
                raise SystemExit(f"{control} would pass: {r[control]}")
    return out


def layers_counted(cfg, *counters):
    """Whether every set of step counters counted the held layers by kind."""
    return all(mfu.layers_counted(cfg, c) for c in counters)


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
        core.check("routed_grad_rel_err_vs_reference",
                   got["routed_grad_rel_err"], ROUTED_GRAD_REL_LIMIT),
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
        "--workload", "train-laguna-s-ep32-8k", "--seeds", "8",
        "--control-seeds", "4", "--rehearse", "--write"]))
