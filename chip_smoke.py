"""Bring-up proof: the trainer and the server, end to end, on the TPU.

Drives the two things users start -- ``dst.initialize`` ->
``engine.train_batch`` and ``InferenceEngineV2`` + ``DSScheduler.generate``
-- at the full published size of Pythia-410M (24 layers, hidden 1024, 16
heads, vocab 50304, sequence 2048, bf16) with weights made from a seed, and
checks what comes out.  No speed is claimed; it is the quickest proof that
the system still starts on the chip.

    python chip_smoke.py             # one chip: device, train, hybrid, windowed, serve
    python chip_smoke.py --chips 4   # four chips: ZeRO-3 dp=4 vs one device

One process, no subprocess, no network, no git.  Each phase prints one JSON
line; any phase that fails makes the exit code non-zero.  The last line of a
passing run is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
Without a TPU it exits non-zero and prints no such line.
"""

import argparse
import dataclasses
import gc
import json
import math
import re
import sys
import time

import numpy as np

SEED = 20260926
SEQ = 2048
VOCAB = 50304
MICRO_BATCH = 2          # sequences per chip per step (see PERF.md, cells)
TRAIN_STEPS = 6
NEW_TOKENS = 16
TOP_K = 50
#: two logits closer than this are a tie within bf16 noise (the bf16 grid
#: near the largest logits is 2^-6; the paged and the plain forward round
#: differently along 24 layers).  Largest gap seen on the v5e: 0.0016.
LOGIT_MARGIN = 0.05
#: four-chip loss agreement, absolute, per step (bf16 compute; the two runs
#: differ in reduction order: 4 shards vs 4 accumulated microbatches)
LOSS_TOL = 2e-2


def emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def device_facts():
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def peak_bytes(device=None):
    import jax

    stats = (device or jax.devices()[0]).memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def kernel_calls(hlo_text):
    from deeperspeed_tpu.telemetry.hlo_cost import pallas_kernel_calls

    return pallas_kernel_calls(hlo_text)


def make_params(model, seed):
    """Pythia's published init scale from a numpy seed, on the host: normal
    std 0.02 for matrices and embeddings, unit norm scales, zero biases.
    Device-independent, so two engines on different meshes start equal."""
    import jax
    import jax.numpy as jnp

    abstract = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.ones((1, 8), jnp.int32)))["params"]
    rng = np.random.default_rng(seed)

    def leaf(path, s):
        name = path[-1].key
        if name == "scale":
            return np.ones(s.shape, np.float32)
        if name == "bias":
            return np.zeros(s.shape, np.float32)
        return 0.02 * rng.standard_normal(s.shape, dtype=np.float32)

    return jax.tree_util.tree_map_with_path(leaf, abstract)


def train_config(batch, micro, zero_stage):
    return {
        "train_batch_size": batch,
        "train_micro_batch_size_per_gpu": micro,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "zero_optimization": {"stage": zero_stage},
        "steps_per_print": 10 ** 9,
        "seed": SEED,
    }


def run_train(model, params, batch_size, zero_stage, mesh=None):
    """``dst.initialize`` + TRAIN_STEPS x ``train_batch`` on a seeded batch.
    Returns the live engine and the facts of the run."""
    import jax

    import deeperspeed_tpu as dst
    from deeperspeed_tpu.telemetry import compile_stats

    engine, _, _, _ = dst.initialize(
        model=model, model_parameters=params, mesh=mesh,
        config=train_config(batch_size, MICRO_BATCH, zero_stage))
    batch = model.example_batch(batch_size=batch_size, seq_len=SEQ, seed=SEED)
    losses, walls, compiles = [], [], []
    for _ in range(TRAIN_STEPS):
        t0, c0 = time.perf_counter(), compile_stats().programs
        losses.append(float(engine.train_batch(batch=batch)))  # waits
        walls.append(time.perf_counter() - t0)
        compiles.append(compile_stats().programs - c0)
    step_s = float(np.median(walls[1:]))
    # the step's compiled HLO, lowered from the engine's own step function
    # (as telemetry/hlo_cost.py does); same program, so a cache hit
    step_fn = engine._get_train_step(None)
    hlo = step_fn.lower(engine.state, engine._stack_microbatches(batch),
                        jax.random.PRNGKey(0)).compile().as_text()
    facts = {
        "losses": [round(x, 4) for x in losses],
        "compile_s": round(walls[0] - step_s, 2),
        "run_s": round(sum(walls[1:]), 3),
        "step_s": [round(w, 4) for w in walls],
        "step_s_median": round(step_s, 4),
        "compiles_per_step": compiles,
        "gas": engine.gradient_accumulation_steps(),
    }
    return engine, hlo, facts


def check_losses(losses):
    first, last = losses[0], losses[-1]
    problems = []
    if not all(math.isfinite(x) for x in losses):
        problems.append("non-finite loss")
    if abs(first - math.log(VOCAB)) > 0.5:
        problems.append(f"first loss {first:.3f} not within 0.5 of ln(V)")
    if not last < first:
        problems.append(f"loss did not fall: {first:.3f} -> {last:.3f}")
    return problems


# ------------------------------------------------------------------- train
def phase_train(model, params):
    # with remat, as the benchmark's train-410m cell runs the model
    model = type(model)(dataclasses.replace(model.config, remat=True))
    engine, hlo, facts = run_train(model, params, MICRO_BATCH, zero_stage=0)
    from deeperspeed_tpu.telemetry import count_kernel_passes, kernel_paths

    calls = kernel_calls(hlo)
    problems = check_losses(facts["losses"])
    for scope in ("flash_attention", "fused_norm"):
        if not calls.get(scope):
            problems.append(f"no {scope} Pallas kernel in the step's HLO")
    # which form of a kernel the traced programs hold: every flash call of
    # this model should read the projections' layout in place
    paths = kernel_paths()
    if set(paths.get("flash_attention", ())) != {"in_place_2"}:
        problems.append(f"flash attention not in place: {paths}")
    # no profiler session here, so ``telemetry.kernel_passes()`` is empty:
    # its count, from the step's text.  A recomputed block keeps the flash
    # kernel's output and lse and must not run the forward kernel again
    layers = model.config.num_layers
    passes = count_kernel_passes(hlo)
    if passes.get("flash_attention") != dict(forward=layers, recomputed=0,
                                             backward=layers):
        problems.append(f"flash attention's passes under remat: {passes}")
    emit("train", ok=not problems, problems=problems, kernel_paths=paths,
         kernel_passes=passes, remat=True,
         model="pythia_410m", layers=layers,
         hidden=model.config.hidden_size, seq=SEQ, batch=MICRO_BATCH,
         steps=TRAIN_STEPS, zero_stage=0, dtype="bfloat16",
         pallas_calls={k: len(v) for k, v in calls.items()},
         peak_bytes_in_use=peak_bytes(), **facts, **device_facts())
    del engine
    gc.collect()
    return not problems


def last_step_phases():
    """The newest step record's host phases (``telemetry.step_timeline()``)
    beside its counters: ``{span: [wall ms, count]}`` and the step's own wall,
    process-CPU and thread-CPU ms: an operator's one-line look at where a
    live step's host time went, with no profiler."""
    from deeperspeed_tpu import telemetry

    last = telemetry.step_timeline()[-1]
    return {"step": last["step"],
            "wall_ms": round(1e3 * (last["t1"] - last["t0"]), 3),
            "cpu_ms": round(1e3 * (last["cpu1"] - last["cpu0"]), 3),
            "thread_cpu_ms": round(
                1e3 * (last["thread_cpu1"] - last["thread_cpu0"]), 3),
            **{name: [round(1e3 * wall, 3), n]
               for name, (wall, n) in last["phases"].items()}}


def head_ce_problem(before):
    """What is wrong with the head + loss the training step traced since
    ``before`` (``kernel_paths()["head_ce"]`` then): it has to be the fused
    form, whose forward walk makes the gradient, and never the per-token
    one, which recomputes the logits in the backward pass."""
    from deeperspeed_tpu import telemetry

    traced = {form: n - before.get(form, 0) for form, n in
              telemetry.kernel_paths().get("head_ce", {}).items()}
    if not traced.get("fused") or traced.get("per_token"):
        return [f"the step's head + loss is not the fused form: {traced}"]
    return []


def step_kernel_passes(engine, batch):
    """``telemetry.count_kernel_passes`` of the engine's step program: its
    text lowered from the engine's own step function (the same program, so
    a cache hit)."""
    import jax

    from deeperspeed_tpu import telemetry

    return telemetry.count_kernel_passes(engine._get_train_step(None).lower(
        engine.state, engine._stack_microbatches(batch),
        jax.random.PRNGKey(0)).compile().as_text())


# ------------------------------------------------------------------ hybrid
def phase_hybrid():
    """One short step of the hybrid model (``models/nemotron_h.py``) at the
    published widths and a chip's share of every layer: an expert layer, a
    Mamba-2 layer and an attention layer, 2048 tokens.  Fails unless the
    step's head + loss is the fused form (``kernel_paths()["head_ce"]``), no
    routed slot was dropped, every kind of layer counted itself, and the
    Mamba layer's scan is the repo's kernel pair: every traced call on the
    ``pallas`` path, and one forward, one recomputed and one backward kernel
    call in the compiled step (``ops/pallas_ssd.py`` keeps nothing across
    the remat wrap)."""
    import jax.numpy as jnp

    import deeperspeed_tpu as dst
    from deeperspeed_tpu import telemetry
    from deeperspeed_tpu.models.nemotron_h import NemotronH, NemotronHConfig

    model = NemotronH(NemotronHConfig.nemotron_3_super(
        pattern="EM*", mamba_num_heads=32, n_groups=2, num_heads=8,
        num_kv_heads=1, experts_held=8, vocab_size=16384, max_seq_len=SEQ,
        remat=True, dtype=jnp.bfloat16))
    head_ce = dict(telemetry.kernel_paths().get("head_ce", {}))
    engine, _, _, _ = dst.initialize(model=model,
                                     config=train_config(1, 1, 0))
    batch = model.example_batch(batch_size=1, seq_len=SEQ, seed=SEED)
    losses = [float(engine.train_batch(batch=batch)) for _ in range(2)]
    told = telemetry.step_counters().get("train_step", {})
    phases = last_step_phases()
    problems = head_ce_problem(head_ce)
    if not all(math.isfinite(x) for x in losses):
        problems.append("non-finite loss")
    if told.get("moe_slots_dropped") != 0:
        problems.append(f"routed slots dropped: {told}")
    for counter in ("ssm_layer_applications", "moe_layer_applications",
                    "attention_layer_applications"):
        if told.get(counter) != 1:
            problems.append(f"{counter} is not 1: {told}")
    if not told.get("moe_slots_held", 0) > 0:
        problems.append(f"no slot routed to the experts held: {told}")
    paths = telemetry.kernel_paths().get("ssd_scan", {})
    if set(paths) != {"pallas"}:
        problems.append(f"the scan did not take its kernels: {paths}")
    passes = step_kernel_passes(engine, batch).get("ssd_scan")
    if passes != dict(forward=1, recomputed=1, backward=1):
        problems.append(f"the scan's kernel passes under remat: {passes}")
    emit("hybrid", ok=not problems, problems=problems, counters=told,
         step_phases=phases, kernel_paths=paths, kernel_passes=passes,
         model="nemotron_3_super share, pattern EM*", seq=SEQ,
         params=model.num_params(), losses=[round(x, 4) for x in losses],
         peak_bytes_in_use=peak_bytes(), **device_facts())
    del engine
    gc.collect()
    return not problems


# ---------------------------------------------------------------- windowed
def phase_windowed():
    """One short step of Mellum 2 (``models/mellum.py``) at the published
    widths and a chip's share: a period of three sliding-window layers and a
    full one, every MLP 16 of the 64 gated experts, 2048 tokens.  Fails
    unless the step's head + loss is the fused form, every windowed call of
    the model took the kernel (only the
    in-place path under ``flash_attention_window``, and in the compiled step
    three forward and three backward kernel calls of that name beside one
    pair of the full kernel's, nothing recomputed: what the layers' pattern
    says), every expert layer walked the grouped form (``grouped_matmul``:
    eight kernel calls forward, 24 backward, none recomputed, a buffer
    handed over unwritten a layer and pass; its kernels multiplied the slots
    held and no more than a tile an expert and a chunk beside them), every
    kind of layer counted itself, and no routed slot was dropped."""
    import jax.numpy as jnp

    import deeperspeed_tpu as dst
    from deeperspeed_tpu import telemetry
    from deeperspeed_tpu.models.mellum import Mellum, MellumConfig
    from deeperspeed_tpu.ops import pallas_gmm

    walked = dict(telemetry.kernel_paths().get("grouped_matmul", {}))
    head_ce = dict(telemetry.kernel_paths().get("head_ce", {}))
    model = Mellum(MellumConfig.mellum2_12b(
        layers_held=4, first_layer_held=12, routed_experts_held=16,
        vocab_rows_held=24576, max_seq_len=SEQ, remat=True,
        dtype=jnp.bfloat16))
    engine, _, _, _ = dst.initialize(model=model,
                                     config=train_config(1, 1, 0))
    batch = model.example_batch(batch_size=1, seq_len=SEQ, seed=SEED)
    losses = [float(engine.train_batch(batch=batch)) for _ in range(2)]
    told = telemetry.step_counters().get("train_step", {})
    phases = last_step_phases()
    problems = head_ce_problem(head_ce)
    if not all(math.isfinite(x) for x in losses):
        problems.append("non-finite loss")
    if told.get("moe_slots_dropped") != 0:
        problems.append(f"routed slots dropped: {told}")
    for counter, want in (("window_layer_applications", 3),
                          ("full_layer_applications", 1),
                          ("moe_layer_applications", 4)):
        if told.get(counter) != want:
            problems.append(f"{counter} is not {want}: {told}")
    if not told.get("moe_slots_held", 0) > 0:
        problems.append(f"no slot routed to the experts held: {told}")
    held, computed = told.get("moe_slots_held", 0), told.get(
        "moe_rows_computed", 0)
    # 16 experts and at most two chunks of the 2048 x 8 sorted slots
    if not held <= computed <= held + pallas_gmm.TILE_ROWS * (16 + 2):
        problems.append(f"the grouped matmul's rows are not the slots': "
                        f"{told}")
    paths = telemetry.kernel_paths().get("flash_attention_window", {})
    if set(paths) != {"in_place_1"}:
        problems.append(f"a windowed call left the in-place kernel: {paths}")
    walks = {form: n - walked.get(form, 0) for form, n in
             telemetry.kernel_paths().get("grouped_matmul", {}).items()}
    if not walks.get("pallas") or walks.get("slots"):
        problems.append(f"an expert layer left the grouped form: {walks}")
    passes = step_kernel_passes(engine, batch)
    want = {"flash_attention_window": dict(forward=3, recomputed=0,
                                           backward=3),
            "flash_attention": dict(forward=1, recomputed=0, backward=1),
            "grouped_matmul": dict(forward=8, recomputed=0, backward=24),
            "unwritten": dict(forward=4, recomputed=0, backward=4)}
    got = {k: passes.get(k) for k in want}
    if got != want:
        problems.append(f"the kernels' passes under remat: {got}")
    emit("windowed", ok=not problems, problems=problems, counters=told,
         step_phases=phases,
         kernel_paths={"flash_attention_window": paths,
                       "grouped_matmul": walks}, kernel_passes=got,
         model="mellum2_12b share, one period, 16 experts", seq=SEQ,
         params=model.num_params(), losses=[round(x, 4) for x in losses],
         peak_bytes_in_use=peak_bytes(), **device_facts())
    del engine
    gc.collect()
    return not problems


# ------------------------------------------------------------------- serve
def _prompts():
    rng = np.random.default_rng(SEED + 1)
    tok = lambda n: rng.integers(0, VOCAB, size=n, dtype=np.int32)
    shared = tok(192)                       # three full KV blocks of 64
    return {
        "long": tok(900),                   # > one 768-token prefill chunk
        "a": np.concatenate([shared, tok(41)]),
        "b": np.concatenate([shared, tok(77)]),
        "short": tok(37),
        "mid": tok(200),
    }


def _reference_logits(model, params, seqs, prompt_lens):
    """Plain ``model.apply`` (no paging, no scheduler) over each whole
    prompt + generated prefix; logits at the positions that chose the
    generated tokens -> [n, NEW_TOKENS, V] float32."""
    import jax
    import jax.numpy as jnp

    width = max(len(s) for s in seqs)
    width = -(-width // 128) * 128
    ids = np.zeros((len(seqs), width), np.int32)   # causal: right pad is inert
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s
    pos = np.stack([np.arange(p - 1, p - 1 + NEW_TOKENS) for p in prompt_lens])
    fwd = jax.jit(lambda p, x, at: model.apply(
        {"params": p}, x, logits_positions=at).astype(jnp.float32))
    return np.asarray(fwd(params, jnp.asarray(ids), jnp.asarray(pos)))


def _serve_once(model, params, sampling, label):
    """One engine + scheduler: two ``generate`` calls (the second re-asks a
    prompt and adds one sharing its prefix, so the prefix cache must hit).
    Three requests each time, the longest new part between 129 and 256
    tokens, so both calls meet the same step buckets and compile them once."""
    import jax
    import jax.numpy as jnp

    from deeperspeed_tpu.inference.v2 import DSScheduler, InferenceEngineV2

    config = {"dtype": "bfloat16",
              "kv_cache": {"num_blocks": 256, "block_size": 64,
                           "prefix_cache": True},
              "state_manager": {"max_context": SEQ}}
    if sampling:
        config["sampling"] = sampling
    t0 = time.perf_counter()
    engine = InferenceEngineV2(model, config=config, params=params)
    sched = DSScheduler(engine)
    p = _prompts()
    first = [p["long"], p["a"], p["short"]]
    second = [p["b"], p["a"], p["mid"]]
    outs = sched.generate(first, max_new_tokens=NEW_TOKENS)
    t_first = time.perf_counter() - t0
    t1 = time.perf_counter()
    outs += sched.generate(second, max_new_tokens=NEW_TOKENS)
    t_second = time.perf_counter() - t1
    prompts = first + second
    plens = [len(x) for x in prompts]
    problems = []
    for o, pl in zip(outs, plens):
        if len(o) != pl + NEW_TOKENS:
            problems.append(f"request of {pl} tokens returned {len(o) - pl} new")
    ref = _reference_logits(model, engine.params, outs, plens)
    exact = gated = 0
    worst = 0.0
    for r, (o, pl) in enumerate(zip(outs, plens)):
        for i in range(NEW_TOKENS):
            tok, row = int(o[pl + i]), ref[r, i]
            if sampling:
                # inside the reference top-k (a tie at the k-th place counts)
                gap = float(np.partition(row, -TOP_K)[-TOP_K] - row[tok])
            else:
                gap = float(row.max() - row[tok])
            worst = max(worst, gap)
            if (tok == int(row.argmax())) if not sampling else gap <= 0.0:
                exact += 1
            elif gap <= LOGIT_MARGIN:
                gated += 1
            else:
                problems.append(
                    f"request {r} token {i}: {gap:.3f} below the reference")
    total = len(outs) * NEW_TOKENS
    if exact < 0.8 * total:
        problems.append(f"only {exact}/{total} tokens agree outright")
    if not sampling and not np.array_equal(outs[1], outs[4]):
        problems.append("same prompt, different greedy tokens on a cache hit")
    hits = engine.state_manager.prefix_cache.hits
    if hits < 1:
        problems.append("prefix cache never hit")
    # the decode round's compiled HLO: the paged-decode kernel (and, when
    # sampling, the sorted top-k kernel) must be in it -- i.e. neither took
    # its interpret-mode reference branch
    n_pad = max(k[0] for k in engine._step_fns if k[1] == 1)
    zi = jnp.zeros((n_pad,), jnp.int32)
    hlo = engine._get_step_fn(n_pad, 1, 1).lower(
        engine.params, engine.kv_cache, jnp.zeros((n_pad, 1), jnp.int32),
        zi, zi, jnp.zeros((n_pad, engine._max_blocks), jnp.int32), zi,
        jnp.full((n_pad,), config["kv_cache"]["num_blocks"], jnp.int32),
        jnp.zeros((n_pad, 0), jnp.int32), zi, jnp.int32(0),
    ).compile().as_text()
    calls = kernel_calls(hlo)
    wanted = ["paged_decode_attention"] + (["sorted_topk"] if sampling else [])
    for scope in wanted:
        if not calls.get(scope):
            problems.append(f"no {scope} Pallas kernel in the decode HLO")
    emit(label, ok=not problems, problems=problems[:8],
         requests=len(outs), prompt_lens=plens, new_tokens=NEW_TOKENS,
         sampling=sampling or "greedy", tokens_compared=total,
         tokens_equal=exact, tokens_within_margin=gated,
         worst_gap=round(worst, 4), margin=LOGIT_MARGIN,
         prefix_cache_hits=hits, rounds=engine.dispatch_count,
         step_buckets=sorted(engine._step_fns),
         decode_rows=n_pad, pallas_calls={k: len(v) for k, v in calls.items()},
         first_generate_s=round(t_first, 2),      # includes every compile
         second_generate_s=round(t_second, 2),    # mostly compiled already
         peak_bytes_in_use=peak_bytes(), **device_facts())
    served = engine.params
    del engine, sched
    gc.collect()
    return not problems, served


def phase_serve(model, params):
    ok_greedy, served = _serve_once(model, params, None, "serve_greedy")
    # the sampling knobs are static in the compiled step, so the sampler is
    # a second engine over the same (already placed) weights
    ok_sampled, _ = _serve_once(
        model, served, {"temperature": 0.8, "top_k": TOP_K, "top_p": 0.9},
        "serve_sampled")
    return ok_greedy and ok_sampled


# -------------------------------------------------------------- four chips
def phase_four_chips(model, params):
    import jax

    from deeperspeed_tpu.parallel.topology import MeshTopology

    devices = jax.devices()
    batch = MICRO_BATCH * len(devices)
    problems = []

    engine, hlo, dp4 = run_train(model, params, batch, zero_stage=3,
                                 mesh=MeshTopology(devices=devices))
    # (2) every master leaf the plan shards lives on four distinct devices
    sharded_bytes = total_bytes = 0
    for leaf in jax.tree_util.tree_leaves(engine.state["master_params"]):
        total_bytes += leaf.nbytes
        if leaf.sharding.is_fully_replicated:
            continue
        sharded_bytes += leaf.nbytes
        shards = leaf.addressable_shards
        owners = {s.device for s in shards}
        if len(owners) != len(devices) or any(
                s.data.size * len(devices) != leaf.size for s in shards):
            problems.append(f"leaf {leaf.shape} not split over 4 devices")
    if sharded_bytes < 0.9 * total_bytes:
        problems.append("ZeRO-3 sharded under 90% of the master bytes")
    # (3) what each device holds after the run
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    if None in in_use:
        problems.append("a device reports no bytes_in_use")
    elif max(in_use) > 1.25 * min(in_use):
        problems.append(f"bytes_in_use uneven across devices: {in_use}")
    peaks = [peak_bytes(d) for d in devices]
    # (4) each Pallas kernel works on its own quarter of the batch, and no
    # all-gather rebuilds a whole-batch activation to feed it
    calls = kernel_calls(hlo)
    heads, hd = model.config.num_heads, model.config.head_dim
    want = {"flash_attention": (MICRO_BATCH * heads, SEQ, hd),
            "fused_norm": (MICRO_BATCH * SEQ, model.config.hidden_size)}
    whole = {"flash_attention": (batch * heads, SEQ, hd),
             "fused_norm": (batch * SEQ, model.config.hidden_size)}
    for scope, shape in want.items():
        seen = calls.get(scope, [])
        if not seen:
            problems.append(f"no {scope} Pallas kernel in the dp=4 step")
        for operands in seen:
            if shape not in operands or whole[scope] in operands:
                problems.append(f"{scope} operands {operands}: not batch/4")
                break
    gathers = re.findall(
        r'= \w+\[([\d,]*)\][^=]* all-gather(?:-start)?\(.*?op_name="([^"]*)"', hlo)
    # ZeRO-3 gathers parameters; anything with the sequence length in its
    # shape is an activation.  One is by design: the embedding's backward
    # gathers its [B, S, H] cotangent to scatter-add into vocab-sharded rows.
    activation_gathers = [(dims, op) for dims, op in gathers
                          if str(SEQ) in dims.split(",")]
    fed = [g for g in activation_gathers if "/embed_in/" not in g[1]]
    if fed:
        problems.append(f"all-gather of activations: {fed[:4]}")
    del engine
    gc.collect()

    # the same config, seed and steps on a one-device mesh
    engine, _, dp1 = run_train(model, params, batch, zero_stage=3,
                               mesh=MeshTopology(devices=devices[:1]))
    del engine
    gc.collect()
    # (1) per-step loss agreement
    diffs = [abs(a - b) for a, b in zip(dp4["losses"], dp1["losses"])]
    if max(diffs) > LOSS_TOL:
        problems.append(f"dp=4 and one-device losses differ by {max(diffs)}")
    problems += check_losses(dp4["losses"])
    emit("four_chips", ok=not problems, problems=problems[:8],
         model="pythia_410m", zero_stage=3, global_batch=batch, seq=SEQ,
         steps=TRAIN_STEPS, dp4=dp4, one_device=dp1,
         loss_abs_diff=[round(d, 5) for d in diffs], loss_tol=LOSS_TOL,
         master_bytes_sharded_frac=round(sharded_bytes / total_bytes, 4),
         bytes_in_use_per_device=in_use, peak_bytes_per_device=peaks,
         pallas_calls={k: len(v) for k, v in calls.items()},
         kernel_operands={k: v[0] for k, v in calls.items()},
         all_gathers=len(gathers),
         activation_all_gathers=activation_gathers[:8], **device_facts())
    return not problems


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    dev = device_facts()
    if dev["platform"] != "tpu" or dev["count"] != args.chips:
        emit("device", ok=False, wanted=f"{args.chips} x tpu", **dev)
        return 1

    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
    from deeperspeed_tpu.utils.compile_cache import enable_compile_cache

    emit("device", ok=True, compile_cache=enable_compile_cache(),
         jax=jax.__version__, **dev)
    model = GPTNeoX(GPTNeoXConfig.pythia_410m(dtype=jnp.bfloat16,
                                              max_seq_len=SEQ))
    params = make_params(model, SEED)
    if args.chips == 4:
        ok = phase_four_chips(model, params)
    else:
        ok = phase_train(model, params)
        ok = phase_hybrid() and ok
        ok = phase_windowed() and ok
        ok = phase_serve(model, params) and ok
    if not ok:
        emit("result", ok=False)
        return 1
    print(json.dumps({"ok": True, "device": device_facts()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
