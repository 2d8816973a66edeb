"""Runner for training a decoder whose attention runs in a compressed latent
with convolutional mixing (CCA) over a top-1 mixture whose router is an MLP
that carries its state from layer to layer, on a scaled residual stream with
a tied table (ZAYA1-8B: one chip's share of a two-way expert-parallel layer
and of the vocabulary, a pipeline stage's layers): ``dst.initialize`` ->
``engine.train_batch`` on a fresh seeded batch every step, under the traffic
file's schedule and ``world``, by the route ``runners/train_laguna.py``
takes.

``runners/train_swa_moe.py``'s steps are model-free but for the names they
read from their own module; ``core.load_runner`` executes a runner's file
anew for every caller, so ``swa`` below is this file's own copy, and those
names are given it here: its ``start_engine``, ``setup``, ``calibrate``,
``engine_first_step``, ``plain_first_step`` and ``seeded_params`` then run
this model, whose ONE table moves with the ids under a world (no head of its
own: ``TABLE_COLUMNS`` is empty).  The timed window that keeps every step's
counters is ``runners/train_hybrid.py``'s.  The model, the plain reference
(``reference/zaya_ref.py``), its controls and the check are this file's.

What is compared (``against_reference``), each beside a limit that
``calibrate.py``'s readings on the chip at the cell's size set (the
gradient's and the update's in ``limits/<cell>.json``, the others kept in
this file beside their readings; none is a constant copied from another
cell): the first step's gradient (Adam's first moment) and Adam update over
the tied table, the closing norm and every leaf of the first, a middle and
the last layer held; the first sequence's per-token log-probabilities and
which held expert its tokens chose (a top-1 flips on rounding: the share is
counted); the routed slots the step counted.  The first step's loss is
printed beside the reference's and has no limit (below).

The controls the limits must refuse: the reference in fp8, its Adam step
with bfloat16 masters, a state left unchanged, and the reference with one
MECHANISM LEFT OUT at a time (``ref.MECHANISMS``: no convolutions, no value
shift, no carried router state, the routed weight renormalised, no residual
scaling, the head untied), each what a program that dropped it would
compute, read on the first sequence's forward pass.

The CPU rehearsal's limits are in ``limits/rehearsal-zaya.json``, rewritten
by ``python3 benchmarks/runners/train_cca_moe.py``.
"""

import os
import sys

import numpy as np

if __name__ == "__main__":      # the rehearsal's limits, see the foot
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmarks import core, traffic_gen
from benchmarks.reference import zaya_ref as ref
# a program that has no such model fails here, as the runner is loaded:
# before anything is put on the device
from deeperspeed_tpu.models.zaya import Zaya, ZayaConfig

train = core.load_runner("train")
hybrid = core.load_runner("train_hybrid")
swa = core.load_runner("train_swa_moe")

REHEARSAL_LIMITS = os.path.join(core.BENCH_DIR, "limits",
                                "rehearsal-zaya.json")
UNCHANGED = swa.UNCHANGED
CONTROL_OF = swa.CONTROL_OF
#: control -> the mechanism the reference leaves out for it
LEFT_OUT = {"control_convolutions_left_out": "convolutions",
            "control_value_shift_left_out": "value_shift",
            "control_router_state_left_out": "router_state",
            "control_routed_weight_renormalised": "routed_weight",
            "control_residual_scaling_left_out": "residual_scaling",
            "control_head_untied": "tied_head"}
#: Limits of the output comparison kept here; those of the gradient and of
#: the update are per cell in ``limits/<cell>.json`` (``limits_from``).  Each
#: by one rule from readings on the chip at the cell's size: the geometric
#: mean of the largest a sound run gave and the smallest its control gave.
#: The readings quoted are those of ``calibrate.py --seeds 8 --control-seeds
#: 4`` (my chip runs, PR 56: eight sound seeds, four control seeds; PERF.md
#: section 2).
#: RMS over the first sequence's 8,192 tokens of (program log-prob -
#: reference log-prob) of the label: sound runs read 0.0068-0.0094 (8), the
#: fp8 control 0.0691-0.0908 (7.3 times clear); with a mechanism left out:
#: no carried router state 0.033-0.053 (the nearest: an expert's output is
#: weighted by a probability near 1/16), no value shift 0.133-0.152, no
#: convolutions 0.196-0.234, no residual scaling 0.398-0.479, the routed
#: weight renormalised 0.556-0.867, the head untied 1.07-1.30.
LOGPROB_RMS_LIMIT = 0.0255
#: Share of the (token, layer) pairs of the first sequence whose chosen held
#: expert differs from the reference's: the largest two of 16 softmax
#: probabilities swap on a bfloat16 rounding of the router's input (the
#: router itself is float32 on both sides, which leaves 0.36-1.03 % of the
#: pairs to flip).  Sound runs read 0.0036-0.0103 (8), the fp8 control
#: 0.0564-0.1300 (5.5 times clear), no carried router state 0.189-0.426 (its
#: own control: what decides the expert from the second layer on), the
#: other mechanisms left out 0.21-0.52 (the untied head 0: the stack is the
#: same).
ROUTED_SET_MISMATCH_LIMIT = 0.0241
#: |slots the program's first step counted - slots the reference counts on
#: the same batch| / the reference's, the mean a layer: the count the FLOPs
#: of ``train.cca_moe_mfu_pct`` stand on.  Sound runs read 0.0003-0.0131 (8:
#: the top-1 router collapses, so a layer's count is the flips of ONE
#: boundary, between the hot expert held and the hot one absent) and the fp8
#: control 0.0028-0.0711: the precision does NOT separate, so the limit is
#: three times the largest sound reading and guards the counter (a layer
#: not counted reads 0.2), not the precision.
SLOTS_HELD_REL_LIMIT = 0.04
#: |engine's first-step loss - reference loss on the same batch| is read and
#: printed (``reference``, ``calibrate``) and has NO limit in this cell, as
#: in the Mellum cell: sound runs read 0.00004-0.0020 (8; a mean over the
#: step's 32,768 tokens), the fp8 control 0.0032-0.0079: nothing separates
#: three times clear, and the accepted cells' 0.003 would leave the largest
#: sound reading 1.5 times of room, not three (PERF.md sections 2 and 7).

#: the numbers with a limit in this file that stands between the sound runs
#: and a control -> (that limit, that control)
KEPT = {"logprob_rms": (LOGPROB_RMS_LIMIT, "control_fp8"),
        "routed_set_mismatch_share": (ROUTED_SET_MISMATCH_LIMIT,
                                      "control_fp8")}
#: that no control bounds, three times a sound reading -> that limit
GUARDS = {"slots_held_rel_diff": SLOTS_HELD_REL_LIMIT}


def program_model(config, traffic):
    """The program's model object for a configuration file."""
    import jax.numpy as jnp

    ref.layers_held(config), ref.share(config)  # refuse what neither runs
    rope = config["rope_parameters"][ref.KIND]
    if not config.get("tie_word_embeddings", True):
        raise ValueError("the head is the table's transpose")
    return Zaya(ZayaConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        rms_norm_eps=config["rms_norm_eps"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], cca_time0=config["cca_time0"],
        cca_time1=config["cca_time1"],
        partial_rotary_factor=float(rope["partial_rotary_factor"]),
        rope_theta=float(rope["rope_theta"]),
        num_experts=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        router_hidden_size=config["router_hidden_size"],
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
    are compared: the tied table, the closing norm, and every parameter of
    the first (it alone has no ``gamma``), a middle and the last layer
    held."""
    depth = ref.layers_held(cfg)
    return {"embed_tokens", "final_norm_scale"} | {
        f"layers_{i}" for i in (0, depth // 2, depth - 1)}


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
    # the mean number of slots a layer held over the whole batch
    ref_slots = float(ref_chosen.sum()) / ref_chosen.shape[1]
    init = train.sample_leaves(params, sampled_tops(cfg))
    want = swa.plain_first_step(cfg, traffic, params, grads)
    counters = left["counters"]

    def forward_numbers(lp, chosen):
        """A forward pass of the first sequence against the reference's."""
        return dict(
            logprob_rms=train.compare_logprobs(lp, ref_lp),
            routed_set_mismatch_share=hybrid.compare_routing(chosen,
                                                             ref_chosen[0]))

    out = {"program": dict(
        train.compare_first_step(left, want, init),
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
        **forward_numbers(np.asarray(ctl_lp), ctl_chosen[0]),
        slots_held_rel_diff=abs(float(ctl_chosen.sum())
                                - float(ref_chosen.sum()))
        / max(float(ref_chosen.sum()), 1.0),
        first_loss_abs_diff=abs(float(ctl_loss) - float(ref_loss)))
    del grads, low
    for name, mechanism in LEFT_OUT.items():
        lp, chosen, _ = jax.jit(
            lambda p, x, y, m=mechanism: ref.token_logprobs(
                p, cfg, x, y, without=(m,)))(params, ids[0], labels[0])
        out[name] = forward_numbers(np.asarray(lp), np.asarray(chosen))
    return out


# this file's own copy of the Mellum cell's runner runs this model
swa.ref, swa.program_model, swa.sampled_tops = ref, program_model, sampled_tops
swa.vocab, swa.against_reference = vocab, against_reference
swa.TABLE_COLUMNS = []          # one table: the head is its transpose
engine_config, first_rate = swa.engine_config, swa.first_rate
plain_first_step, seeded_params = swa.plain_first_step, swa.seeded_params
start_engine, setup, calibrate = swa.start_engine, swa.setup, swa.calibrate
#: the timed window, with every step's counters kept and the routed load by
#: step in the progress line ``window_counters``: the hybrid runner's
window = hybrid.window


def held_limits(limits):
    """number -> limit: the cell's file's two and those kept here."""
    return dict({k: limit for k, (limit, _) in KEPT.items()}, **GUARDS,
                **{k: v["limit"] for k, v in limits.items()
                   if k in CONTROL_OF})


def refused(numbers, limits):
    """The names of the limits a set of numbers (a control's) breaks."""
    held = held_limits(limits)
    return sorted(k for k, v in numbers.items() if k in held and v > held[k])


def limits_from(readings):
    """A cell's limits from its readings, by ``runners/train.py``'s rule: the
    geometric mean of the largest the sound runs gave and the smallest the
    control gave, refused where the control reads under three times the sound
    runs (the update's control is the state left unchanged, as the Mellum
    cell's).  The limits kept in this file must hold in every reading too,
    and stand as clear of their control (``KEPT``) or leave the sound
    readings three times of room (``GUARDS``); and every reading of the
    bfloat16-masters control, of the fp8 control and of each mechanism left
    out must break a limit."""
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
    # a control is refused by the kept limits, so where those stand: at the
    # cell's size (at the tiny preset the precision hardly separates)
    for r in readings:
        for control in ("control_bf16_masters", *(
                ("control_fp8", *LEFT_OUT) if on_chip else ())):
            if control in r and not refused(r[control], out):
                raise SystemExit(f"{control} would pass: {r[control]}")
    return out


def layers_counted(cfg, *counters):
    """Whether every set of step counters counted the held layers."""
    depth = ref.layers_held(cfg)
    return all(c.get("cca_layer_applications") == depth
               and c.get("moe_layer_applications") == depth for c in counters)


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
    limits = held_limits(core.load_json(REHEARSAL_LIMITS) if ctx.rehearse
                         else core.load_limits(ctx.cell["name"]))
    counted = layers_counted(ctx.config, in_window, first)
    dropped = max(c.get("moe_slots_dropped", -1.0) for c in (in_window, first))
    return [
        *(core.check(f"{number}_vs_reference", got[number], limits[number])
          for number in (*CONTROL_OF, *KEPT, *GUARDS)),
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
        "--workload", "train-zaya1-8b-ep2-8k", "--seeds", "8",
        "--control-seeds", "4", "--rehearse", "--write"]))
