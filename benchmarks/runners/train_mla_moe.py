"""Runner for training a decoder with latent attention (MLA: a 192-wide
score of which 64 are ONE rotary key shared by the heads, a 128-wide value)
over a sigmoid-scored mixture with shared experts and a leading dense layer
(Moonlight-16B-A3B: one chip's share of an eight-way expert-parallel layer
and of the vocabulary, the first pipeline stage's layers):
``dst.initialize`` -> ``engine.train_batch`` on a fresh seeded batch every
step, under the traffic file's schedule and ``world``, by the route
``runners/train_laguna.py`` and ``train_cca_moe.py`` take.

``runners/train_swa_moe.py``'s steps are model-free but for the names they
read from their own module; ``core.load_runner`` executes a runner's file
anew for every caller, so ``swa`` below is this file's own copy, and those
names are given it here: its ``start_engine``, ``setup``, ``calibrate``,
``engine_first_step``, ``plain_first_step`` and ``seeded_params`` then run
this model (both tables move with the ids under a world, as Mellum's).  The
timed window that keeps every step's counters is ``runners/
train_hybrid.py``'s.  The model, the plain reference
(``reference/moonlight_ref.py``), its controls and the check are this
file's.

What is compared (``against_reference``), each beside a limit that
``calibrate.py``'s readings on the chip at the cell's size set (the
gradient's and the update's in ``limits/<cell>.json``, the others kept in
this file beside their readings; none is a constant copied from another
cell): the first step's gradient (Adam's first moment) and Adam update over
both tables, the closing norm and every leaf of the dense layer, the first
sparse layer and the last layer held (so: the latent projections, ``W_q``'s
rotary columns, the router, the experts held, the shared experts, the
tables); the first sequence's per-token log-probabilities and which held
experts its tokens chose; the routed slots the step counted.  The first
step's loss (cross entropy + the balance terms) is printed beside the
reference's and has no limit (below).

The controls the limits must refuse: the reference in fp8 (every matmul;
the latent's up-projection alone), its Adam step with bfloat16 masters, a
state left unchanged, and the reference with one MECHANISM LEFT OUT at a
time (``ref.MECHANISMS``), each what a program that dropped it would
compute, read on the first sequence's forward pass.  The attention's
products alone in fp8 are read beside them (``READ_BESIDE``).

The CPU rehearsal's limits are in ``limits/rehearsal-moonlight.json``,
rewritten by ``python3 benchmarks/runners/train_mla_moe.py``.
"""

import os
import sys

import numpy as np

if __name__ == "__main__":      # the rehearsal's limits, see the foot
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmarks import core, traffic_gen
from benchmarks.reference import moonlight_ref as ref
# a program that has no such model fails here, as the runner is loaded:
# before anything is put on the device
from deeperspeed_tpu.models.moonlight import Moonlight, MoonlightConfig

train = core.load_runner("train")
hybrid = core.load_runner("train_hybrid")
swa = core.load_runner("train_swa_moe")

REHEARSAL_LIMITS = os.path.join(core.BENCH_DIR, "limits",
                                "rehearsal-moonlight.json")
UNCHANGED = swa.UNCHANGED
CONTROL_OF = swa.CONTROL_OF
#: control -> which of the reference's matmuls ALONE run in fp8 for it, read
#: on the first sequence's forward pass (``control_fp8`` itself is every
#: matmul, forward and backward).  The limits must refuse the first; the
#: second is read and printed beside it and refuses nothing (below)
LOW_PRECISION = {"control_fp8_up_projection": "up_projection"}
READ_BESIDE = {"control_fp8_products": "products"}
#: control -> the mechanism the reference leaves out for it
LEFT_OUT = {"control_latent_norm_left_out": "latent_norm",
            "control_rotary_left_out": "rotary",
            "control_shared_rope_key_left_out": "shared_rope_key",
            "control_shared_experts_left_out": "shared_experts",
            "control_routed_scale_left_out": "routed_scale"}
#: Limits of the output comparison kept here; those of the gradient and of
#: the update are per cell in ``limits/<cell>.json`` (``limits_from``).  Each
#: by one rule from readings on the chip at the cell's size: the geometric
#: mean of the largest a sound run gave and the smallest the fp8 control
#: gave.  The readings quoted are those of ``calibrate.py --seeds 8
#: --control-seeds 4`` (my chip runs, PR 61, call 3: eight sound seeds, four
#: control seeds, every seed a world of its own; PERF.md section 2).
#: RMS over the first sequence's 8,192 tokens of (program log-prob -
#: reference log-prob) of the label: sound runs read 0.0273-0.0343 (8; six
#: layers of bfloat16 with a dense MLP 11,264 wide and a head of 20,480), the
#: fp8 control 0.1713-0.1971 (5.0 times clear), the up-projection ALONE in
#: fp8 0.0933-0.0988; with a mechanism left out: the routed scale 0.102-0.122
#: (the nearest), the latent's norm 0.103-0.143, the shared rotary key
#: 0.341-0.422, rotary 0.489-0.504, the shared experts 0.632-0.704.  The
#: attention's PRODUCTS alone in fp8 read 0.0668-0.0824, under twice the
#: sound runs: a row's softmax averages the rounding of thousands of keys,
#: no limit stands three times clear of both, and the reading refuses
#: nothing (three of its four seeds break a limit below, one breaks none).
LOGPROB_RMS_LIMIT = 0.0766
#: Share of the (token, sparse layer) pairs of the first sequence whose set
#: of chosen held experts differs from the reference's: the 6th and 7th of
#: 64 sigmoid scores swap on a bfloat16 rounding of the router's input (the
#: router itself is float32 on both sides).  Sound runs read 0.0136-0.0238
#: (8), the fp8 control 0.1346-0.1953 (5.6 times clear), the up-projection
#: alone 0.0775-0.0938, the products alone 0.0512-0.0805, a mechanism left
#: out 0.0479-0.4854 (the routed scale alone changes no choice in its own
#: layer: 0.0479-0.0910 is what it moves downstream).
ROUTED_SET_MISMATCH_LIMIT = 0.0566
#: |slots the program's first step counted - slots the reference counts on
#: the same batch| / the reference's, the mean a sparse layer: the count the
#: FLOPs of ``train.mla_moe_mfu_pct`` stand on.  Sound runs read
#: 0.0004-0.0030 (8; 18,221-29,488 slots a layer by the world), the fp8
#: control 0.0157-0.0632 (5.3 times clear).
SLOTS_HELD_REL_LIMIT = 0.00682
#: |engine's first-step loss - reference loss on the same batch| (cross
#: entropy + the balance terms, which read 0.00069-0.00073) is read and
#: printed (``reference``, ``calibrate``) and has NO limit in this cell, as
#: in the Mellum cell: sound runs read 0.0002-0.0017 (8; a mean over the
#: step's 32,768 tokens), the fp8 control 0.0050-0.0300: 2.9 times, not
#: three, and the accepted cells' 0.003 would leave the largest sound
#: reading 1.7 times of room (PERF.md sections 2 and 7).

#: the numbers with a limit in this file that stands between the sound runs
#: and a control -> (that limit, that control)
KEPT = {"logprob_rms": (LOGPROB_RMS_LIMIT, "control_fp8"),
        "routed_set_mismatch_share": (ROUTED_SET_MISMATCH_LIMIT,
                                      "control_fp8"),
        "slots_held_rel_diff": (SLOTS_HELD_REL_LIMIT, "control_fp8")}


def program_model(config, traffic):
    """The program's model object for a configuration file."""
    import jax.numpy as jnp

    ref.layer_kinds(config), ref.share(config)  # refuse what neither runs
    if config.get("tie_word_embeddings", False):
        raise ValueError("the head is a table of its own")
    return Moonlight(MoonlightConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        first_k_dense_replace=config["first_k_dense_replace"],
        rms_norm_eps=config["rms_norm_eps"],
        num_attention_heads=config["num_attention_heads"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        rope_theta=float(config["rope_theta"]),
        intermediate_size=config["intermediate_size"],
        n_routed_experts=config["n_routed_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        n_shared_experts=config["n_shared_experts"],
        moe_intermediate_size=config["moe_intermediate_size"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        aux_loss_alpha=float(config.get("aux_loss_alpha", ref.ALPHA)),
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
    are compared: both tables, the closing norm, and every parameter of the
    first layer of each kind and of the last layer held."""
    kinds = ref.layer_kinds(cfg)
    return {"embed_tokens", "lm_head_kernel", "final_norm_scale"} | {
        f"layers_{i}" for i in (*(kinds.index(kind) for kind in set(kinds)),
                                len(kinds) - 1)}


def vocab(cfg):
    return ref.share(cfg)["vocab"]


def against_reference(ctx, seed, first_loss, left, controls=False):
    """The program's first step against the plain reference, on a device the
    program has left.  ``controls`` adds what the controls read in the
    program's place (module docstring).  -> dict of numbers."""
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
    prog_lp = np.asarray(prog_lp)[0]
    prog_chosen = np.asarray(prog_chosen)[:, 0]
    ref_loss, grads, ref_lp, ref_chosen = ref.loss_and_grads(params, cfg, ids,
                                                             labels)
    ref_lp, ref_chosen = np.asarray(ref_lp), np.asarray(ref_chosen)
    # the mean number of slots a sparse layer held over the whole batch
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
        balance_loss=counters.get("moe_balance_loss"),
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
    forward = [(name, dict(precision="fp8", low=which))
               for name, which in {**LOW_PRECISION, **READ_BESIDE}.items()] + [
                   (name, dict(without=(mechanism,)))
                   for name, mechanism in LEFT_OUT.items()]
    for name, changed in forward:
        lp, chosen = jax.jit(
            lambda p, x, y, changed=changed: ref.token_logprobs(
                p, cfg, x, y, **changed))(params, ids[0], labels[0])
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


def held_limits(limits):
    """number -> limit: the cell's file's two and those kept here."""
    return dict({k: limit for k, (limit, _) in KEPT.items()},
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
    and stand as clear of their control (``KEPT``); and every reading of
    every control must break a limit."""
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
    # a control is refused by the kept limits, so where those stand: at the
    # cell's size (at the tiny preset the precision hardly separates)
    for r in readings:
        for control in ("control_bf16_masters", *(
                ("control_fp8", *LOW_PRECISION, *LEFT_OUT)
                if on_chip else ())):
            if control in r and not refused(r[control], out):
                raise SystemExit(f"{control} would pass: {r[control]}")
    return out


def layers_counted(cfg, *counters):
    """Whether every set of step counters counted the held layers by kind."""
    kinds = ref.layer_kinds(cfg)
    want = {"mla_layer_applications": len(kinds),
            "dense_mlp_layer_applications": kinds.count(ref.DENSE),
            "moe_layer_applications": kinds.count(ref.SPARSE),
            "shared_expert_layer_applications": kinds.count(ref.SPARSE)}
    return all(c.get(name) == n for c in counters for name, n in want.items())


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
          for number in (*CONTROL_OF, *KEPT)),
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
        "--workload", "train-moonlight-16b-ep8-8k", "--seeds", "8",
        "--control-seeds", "4", "--rehearse", "--write"]))
