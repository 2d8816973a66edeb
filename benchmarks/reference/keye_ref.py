"""Plain reference for Keye-VL-2.0-30B-A3B's language model (Kwai-Keye,
``model_type`` ``KeyeVL2``; the published ``config.json`` is the
configuration file's ``source``): Qwen3-MoE's decoder block whose attention
is LEARNED SPARSE -- a lightning indexer (DeepSeek-V3.2-Exp's) scores every
earlier token, each row keeps its ``topk`` best, one softmax over those --
on grouped-query heads, over a softmax top-8-of-128 mixture.  Forward pass,
per-token log-probabilities, which held experts each token chose, which keys
each row chose, both loss terms and their gradient in straightforward
``jax.numpy``.

The equations, for one sequence ``ids`` [S], ``x`` [S, H].  Lines marked
*assumed* are not settled by the ``config.json``; each is also in the
configuration file's ``assumed``.

* Per layer (all alike: ``decoder_sparse_step`` 1, ``mlp_only_layers`` []):
  ``h = x + Attn(N1(x))``, ``y = h + MoE(N2(h))``, ``N(x) = g * x /
  sqrt(mean(x^2) + eps)``, eps ``rms_norm_eps``, no bias anywhere
  (``attention_bias`` false).  The block's form and the q/k norm are
  Qwen3-MoE's, whose schema the config is (*assumed*).  A closing RMSNorm,
  an untied head (``tie_word_embeddings`` false).
* ``Attn(u)``: ``q = W_q u`` [S, 32, 128], ``k = W_k u``, ``v = W_v u`` [S,
  4, 128]; query head ``h`` reads KV head ``h // 8``.  ``q <- RMSNorm_D(q)``,
  ``k <- RMSNorm_D(k)`` a head, one learned scale of 128 each shared by the
  heads; then rotary on the whole head, the halves convention (*assumed*),
  MULTI-AXIS: frequency pair ``i`` of 64, ``theta_i = rope_theta^(-i/64)``,
  turns by ``theta_i pos_a(i)`` with ``a(i)`` the temporal axis for the
  first ``mrope_section[0]`` = 16 pairs, height for the next 24, width for
  the last 24.  A text token has the same position on all three: plain
  rotary.  Scale ``s = 128^-1/2``.
* Indexer (``sa_config``: ``H_I`` = 16 heads of ``D_I`` = 64, one key head;
  DeepSeek-V3.2-Exp's released indexer, *assumed*, with ``W^I_q`` reading
  the sublayer's normed input since this model has no query latent), on
  ``u~ = stop_gradient(u)``: ``q^I[t, j] = (W^I_q u~_t)_j``, ``k^I_s =
  LayerNorm(W^I_k u~_s)`` (weight and bias, eps 1e-6), rotary on the whole
  64 of both (the same theta base; the TEMPORAL position, *assumed*: the
  sections deal out 64 pairs and the indexer's head has 32), ``w[t, j] =
  (W^I_w u~_t)_j H_I^-1/2 D_I^-1/2``; ``I[t, s] = sum_j w[t, j] relu(q^I[t,
  j] . k^I_s)``, ``s <= t``.  No Hadamard rotation (*assumed*: an orthogonal
  map of both leaves the product unchanged; it serves fp8).  ``q_chunk_size``
  and ``kv_chunk_size`` are tile sizes and change no equation (*assumed*:
  selection is per token, not per block).
* Selection: ``S_t`` = the positions of the ``min(t + 1, topk)`` largest
  ``I[t, s]``, ``s <= t``; of equal scores the lower position first
  (*assumed*).  Exactly that many.  Here by a stable sort of the masked
  scores.
* Attention over the chosen: ``o[t, h] = sum_{s in S_t} softmax_{s in
  S_t}(s q[t, h] . k[s, h // 8]) v[s, h // 8]``, then ``W_o``.  The
  selection is a constant of the backward pass.
* The indexer's loss (the sparse-training stage of the DeepSeek-V3.2-Exp
  report): ``pbar_t[s] = mean_h softmax_{S_t}(s q[t, h] . k_s)[s]`` with no
  gradient through it; ``L^I_layer = mean_t sum_{s in S_t} pbar_t[s] (log
  pbar_t[s] - log softmax_{S_t}(I[t, .])[s])``; the step's loss is ``L_LM +
  sum_layers L^I_layer`` (*assumed*: a mean over rows, coefficient 1).
  Because of the two ``stop_gradient``s the indexer's leaves receive
  gradient from ``L^I`` alone and everything else from ``L_LM`` alone.
* ``MoE(m)``: ``p = softmax(m W_r)`` over all ``num_experts``, the
  ``num_experts_per_tok`` largest, renormalised over the chosen
  (``norm_topk_prob``), ``sum_e p_e W_down,e (silu(W_gate,e m) * W_up,e
  m)``; no shared expert, no auxiliary loss (the config has no coefficient).
* Weights (*assumed*): normal(0, ``initializer_range`` or 0.02) matrices,
  tables and router; unit norm scales; LayerNorm weight 1 and bias 0.
  ``intermediate_size``, ``max_window_layers``, ``use_sliding_window`` false
  and ``sliding_window`` null are kept as published and reach nothing.
* LEFT OUT: the vision tower and its projector (no key in the config; text
  tokens only, for which the three rotary axes coincide), the indexer's
  dense warm-up stage (needs a trained model), any multi-token-prediction or
  balancing term the model card may name.

A chip's share (``share``): ``layers_held`` layers from ``first_layer_held``
(all layers are alike), ``routed_experts_held`` experts from
``first_expert_held`` (a chip adds only its own experts' terms),
``vocab_rows_held`` rows of both tables.  Attention, indexer, router and
norms are whole on every chip.

No kernels, no cache; float32 with
``jax.default_matmul_precision("highest")`` on every matmul unless a lower
``precision`` is asked for: those exist only as *controls* of the
benchmark's output check (``"fp8"``, ``"bfloat16"``), as do the mechanisms
left out one at a time (``without``, any of ``MECHANISMS``).  Scores exist a
block of query rows at a time (``jax.checkpoint`` a block) and the chain
rule goes a layer at a time, so that the cell's size fits the chip beside
float32 weights and gradients.  Imports nothing from the program under
test; weights come from :func:`init_params`, i.e. from the seed.
"""

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference.gpt_neox_ref import (  # noqa: F401
    PRECISIONS, _einsum, _layer_norm, _nest, adam_first_step, clip_scale,
    global_norm)
from benchmarks.reference.mellum_ref import (  # noqa: F401
    QUERY_BLOCK, _at_highest, _dense, _rms_norm, _rotate, moe, share)

#: what ``without`` may name: the mechanism a control leaves out
MECHANISMS = ("selection", "qk_norm", "indexer_relu", "topk_halved",
              "indexer_loss", "indexer_detach")
K_NORM_EPS = 1e-6
#: the indexer's leaves: they alone see the indexer's loss
INDEXER = "indexer"


def _check_without(without):
    if set(without) - set(MECHANISMS):
        raise ValueError(f"without {without!r}: {MECHANISMS}")
    return tuple(without)


def layers_held(cfg):
    held = int(cfg.get("layers_held", cfg["num_hidden_layers"]))
    if int(cfg.get("first_layer_held", 0)) + held > cfg["num_hidden_layers"]:
        raise ValueError("the layers held lie outside the model's")
    if cfg.get("mlp_only_layers") or cfg.get("decoder_sparse_step", 1) != 1:
        raise ValueError("every layer's MLP is the mixture")
    return held


# ---------------------------------------------------------------- weights
def layer_shapes(cfg, sh):
    """One layer's parameters as ``{path tuple: shape}``."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nq, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, sa = cfg["moe_intermediate_size"], cfg["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return {("input_norm_scale",): (h,),
            ("attn", "q_proj", "kernel"): (h, nq * d),
            ("attn", "k_proj", "kernel"): (h, kv * d),
            ("attn", "v_proj", "kernel"): (h, kv * d),
            ("attn", "q_norm_scale"): (d,),
            ("attn", "k_norm_scale"): (d,),
            ("attn", INDEXER, "wq_index", "kernel"): (h, hi * di),
            ("attn", INDEXER, "wk_index", "kernel"): (h, di),
            ("attn", INDEXER, "k_norm", "scale"): (di,),
            ("attn", INDEXER, "k_norm", "bias"): (di,),
            ("attn", INDEXER, "w_index", "kernel"): (h, hi),
            ("attn", "o_proj", "kernel"): (nq * d, h),
            ("post_norm_scale",): (h,),
            ("moe", "router_kernel"): (h, cfg["num_experts"]),
            # gate | up side by side: one matmul in, one out, an expert
            ("moe", "experts_gate_up_proj"): (sh["experts"], h, 2 * f),
            ("moe", "experts_down_proj"): (sh["experts"], f, h)}


def param_shapes(cfg):
    """The parameter tree as ``{path tuple: shape}`` (the program's flax
    tree has the same names)."""
    h, sh = cfg["hidden_size"], share(cfg)
    shapes = {("embed_tokens", "embedding"): (sh["vocab"], h)}
    for i in range(layers_held(cfg)):
        for path, shape in layer_shapes(cfg, sh).items():
            shapes[(f"layers_{i}",) + path] = shape
    shapes[("final_norm_scale",)] = (h,)
    shapes[("lm_head_kernel",)] = (h, sh["vocab"])
    return shapes


def num_params(cfg):
    return sum(math.prod(s) for s in param_shapes(cfg).values())


def init_params(cfg, seed):
    """Seeded float32 weights, made on the default device in ONE jitted
    call: unit norm scales (the LayerNorm's weight too), its bias zero,
    everything else normal(0, initializer_range)."""
    shapes = param_shapes(cfg)
    std = float(cfg.get("initializer_range", 0.02))

    def leaf(key, i, path, shape):
        if path[-1].endswith("norm_scale") or path[-2:] == ("k_norm", "scale"):
            return jnp.ones(shape, jnp.float32)
        if path[-2:] == ("k_norm", "bias"):
            return jnp.zeros(shape, jnp.float32)
        return std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                       jnp.float32)

    def make(key):
        return _nest({path: leaf(key, i, path, shape)
                      for i, (path, shape) in enumerate(shapes.items())})

    # any whole number up to a little over 2**31 (and beyond): two words
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(make)(key)


# ------------------------------------------------------------------ rotary
def text_positions(seq):
    """[3, S]: a text token's position is the same on all three axes."""
    return jnp.broadcast_to(jnp.arange(seq), (3, seq))


def rotary(theta, positions, dim, sections=None):
    """(cos, sin) [S, dim] float32: frequency pair ``i`` turns by
    ``theta^(-2i/dim)`` times its axis's position (``positions`` [axes, S],
    ``sections`` pairs an axis in order), or with no sections by
    ``positions`` [S] alone."""
    inv_freq = float(theta) ** (-2 * jnp.arange(dim // 2, dtype=jnp.float32)
                                / dim)
    if sections is None:
        at = positions.astype(jnp.float32)[:, None]
    else:
        if sum(sections) != dim // 2:
            raise ValueError(f"mrope_section {sections} is not {dim // 2} "
                             "pairs")
        axis_of = jnp.asarray([a for a, n in enumerate(sections)
                               for _ in range(n)])
        at = positions.astype(jnp.float32).T[:, axis_of]        # [S, pairs]
    angles = at * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


# ----------------------------------------------------------------- sublayers
def topk_of(cfg, without=()):
    k = int(cfg["sa_config"]["topk"])
    return k // 2 if "topk_halved" in without else k


def select(scores, at, k):
    """A block of rows' scores [R, S] at positions ``at`` [R] -> bool [R,
    S]: row ``t`` keeps the ``min(t + 1, k)`` largest of ``s <= t``, of
    equal scores the lower position first (a stable sort)."""
    causal = jnp.arange(scores.shape[1])[None, :] <= at[:, None]
    order = jnp.argsort(jnp.where(causal, -scores, jnp.inf), axis=-1,
                        stable=True)
    rank = jnp.argsort(order, axis=-1)
    return (rank < jnp.minimum(at + 1, k)[:, None]) & causal


def attention(u, p, cfg, precision="float32", without=(), positions=None):
    """Learned sparse grouped-query attention, a block of query rows at a
    time: u [S, H] -> ([S, H], the layer's indexer loss summed over its
    rows, which keys each row chose, packed [S, ceil(S / 8)] uint8)."""
    s = u.shape[0]
    nq, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    sa, eps = cfg["sa_config"], cfg["rms_norm_eps"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    if sa.get("indexer_num_kv_heads", 1) != 1:
        raise ValueError("the indexer has one key head")
    positions = text_positions(s) if positions is None else positions
    theta = cfg["rope_theta"]
    cos, sin = rotary(theta, positions, d,
                      tuple(cfg["rope_scaling"]["mrope_section"]))
    q = _dense(u, p["q_proj"], precision).reshape(s, nq, d)
    k = _dense(u, p["k_proj"], precision).reshape(s, kv, d)
    v = _dense(u, p["v_proj"], precision).reshape(s, kv, d)
    if "qk_norm" not in without:
        q = _rms_norm(q, p["q_norm_scale"], eps)
        k = _rms_norm(k, p["k_norm_scale"], eps)
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    k, v = (jnp.repeat(t, nq // kv, axis=1) for t in (k, v))
    # the indexer, on the sublayer's input held constant
    ui = u if "indexer_detach" in without else jax.lax.stop_gradient(u)
    ix = p[INDEXER]
    cos_i, sin_i = rotary(theta, positions[0], di)
    qi = _rotate(_dense(ui, ix["wq_index"], precision).reshape(s, hi, di),
                 cos_i, sin_i)
    ki = _layer_norm(_dense(ui, ix["wk_index"], precision), ix["k_norm"],
                     K_NORM_EPS)
    ki = _rotate(ki[:, None, :], cos_i, sin_i)[:, 0]
    w = _dense(ui, ix["w_index"], precision) * (hi ** -0.5 * di ** -0.5)
    topk = topk_of(cfg, without)

    @jax.checkpoint
    def rows(block):
        qb, qib, wb, at = block
        dots = _einsum("rjd,sd->jrs", qib, ki, precision)
        acts = dots if "indexer_relu" in without else jnp.maximum(dots, 0.0)
        index = jnp.sum(acts * wb.T[:, :, None], axis=0)         # I [R, S]
        if "selection" in without:
            chosen = jnp.arange(s)[None, :] <= at[:, None]
        else:
            chosen = select(jax.lax.stop_gradient(index), at, topk)
        scores = _einsum("rnd,snd->nrs", qb, k, precision) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(chosen[None], scores, -jnp.inf), -1)
        out = _einsum("nrs,snd->rnd", probs, v, precision)
        pbar = jax.lax.stop_gradient(jnp.mean(probs, axis=0))
        log_pi = jax.nn.log_softmax(jnp.where(chosen, index, -jnp.inf), -1)
        seen = chosen & (pbar > 0.0)
        kl = jnp.sum(jnp.where(
            seen, pbar * (jnp.log(jnp.where(seen, pbar, 1.0))
                          - jnp.where(seen, log_pi, 0.0)), 0.0))
        return out, kl, jnp.packbits(chosen, axis=-1)

    # blocks of query rows, one after another (one compiled copy)
    size = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    n = s // size
    out, kl, chosen = jax.lax.map(rows, (
        q.reshape(n, size, nq, d), qi.reshape(n, size, hi, di),
        w.reshape(n, size, hi), jnp.arange(s).reshape(n, size)))
    kl = jnp.sum(kl) * (0.0 if "indexer_loss" in without else 1.0)
    return (_dense(out.reshape(s, nq * d), p["o_proj"], precision), kl,
            chosen.reshape(s, -1))


# ---------------------------------------------------------------- forward
def _layer(x, p, cfg, sh, precision, without=()):
    """-> ((y [S, H], the indexer's loss summed over the rows), (which held
    experts each token chose [S, held], which keys each row chose, packed))."""
    eps = cfg["rms_norm_eps"]
    a, kl, chosen = attention(_rms_norm(x, p["input_norm_scale"], eps),
                              p["attn"], cfg, precision, without)
    h = x + a
    y, picked = moe(_rms_norm(h, p["post_norm_scale"], eps), p["moe"], cfg,
                    sh, precision)
    return (h + y, kl), (picked, chosen)


def hidden_states(params, cfg, ids, precision="float32", without=()):
    """For ONE sequence ``ids`` [S]: the closing norm's output [S, H], the
    indexers' losses summed over layers (each a mean over the rows), which
    held experts each token chose [layers, S, held], which keys each row
    chose [layers, S, ceil(S / 8)] (``jnp.packbits`` of bool [S, S])."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    without = _check_without(without)
    sh, picked, chosen, kl = share(cfg), [], [], 0.0
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"]["embedding"].astype(jnp.float32)[ids]
        for i in range(layers_held(cfg)):
            (x, part), (mine, keys) = _layer(x, params[f"layers_{i}"], cfg,
                                             sh, precision, without)
            kl = kl + part / ids.shape[0]
            picked.append(mine)
            chosen.append(keys)
        x = _rms_norm(x, params["final_norm_scale"], cfg["rms_norm_eps"])
    return x, kl, jnp.stack(picked), jnp.stack(chosen)


def token_logprobs(params, cfg, ids, labels, precision="float32", without=()):
    """log p(labels[i] | ids[:i+1]) [S] for one sequence, the indexers' loss
    of it, the chosen held experts [layers, S, held], the chosen keys
    (packed)."""
    h, kl, picked, chosen = hidden_states(params, cfg, ids, precision,
                                          without)
    with jax.default_matmul_precision("highest"):
        lg = _einsum("sh,hv->sv", h, params["lm_head_kernel"], precision)
    return (jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
            - jax.nn.logsumexp(lg, axis=-1)), kl, picked, chosen


def loss(params, cfg, ids, labels, precision="float32", without=()):
    """(L_LM, sum over layers of L^I) over a batch [B, S], by ``jax``'s own
    differentiation where a test wants it: small sizes."""
    lm = kl = 0.0
    for b in range(ids.shape[0]):
        lp, part, _, _ = token_logprobs(params, cfg, ids[b], labels[b],
                                        precision, without)
        lm, kl = lm - jnp.mean(lp) / ids.shape[0], kl + part / ids.shape[0]
    return lm, kl


def unpack(chosen, seq):
    """The packed chosen keys [..., S, ceil(S / 8)] -> bool [..., S, S]."""
    return jnp.unpackbits(chosen, axis=-1, count=seq).astype(bool)


# --------------------------------------------------------------- training
def loss_and_grads(params, cfg, ids, labels, precision="float32", without=()):
    """Both loss terms over a batch [B, S] and the gradient of their sum
    with respect to every parameter: one sequence at a time, and the chain
    rule a layer at a time.  The forward pass keeps each layer's input; the
    backward pass goes back through the head and then layer by layer,
    recomputing a layer from its input (``jax.vjp``), a layer's indexer loss
    entering with its own cotangent ``1 / (B S)``.  The same arithmetic as
    ``jax.grad`` of the sum of :func:`loss` (a test holds them equal); the
    layers share one compiled program, and no more than one layer's
    intermediates are live.
    -> ((L_LM, sum over layers of L^I), gradient tree, the first sequence's
    per-token log-probs [S], the held experts every sequence's tokens chose
    [B, layers, S, held], the keys the first sequence's rows chose [layers,
    S, ceil(S / 8)] packed)."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    without = _check_without(without)
    n, seq = ids.shape
    sh, depth, eps = share(cfg), layers_held(cfg), cfg["rms_norm_eps"]
    layer = functools.partial(_layer, cfg=cfg, sh=sh, precision=precision,
                              without=without)

    def through(x, p, dy):
        _, transpose, _ = jax.vjp(layer, x, p, has_aux=True)
        return transpose((dy, jnp.float32(1.0 / (n * seq))))

    def head(h, scale, w, y):
        lg = _einsum("sh,hv->sv", _rms_norm(h, scale, eps), w, precision)
        lp = (jnp.take_along_axis(lg, y[:, None], axis=-1)[:, 0]
              - jax.nn.logsumexp(lg, axis=-1))
        return -jnp.mean(lp) / n, lp

    forward = jax.jit(_at_highest(layer))
    backward = jax.jit(_at_highest(through))
    head_grad = jax.jit(_at_highest(jax.value_and_grad(
        head, argnums=(0, 1, 2), has_aux=True)))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=0)
    table = params["embed_tokens"]["embedding"]
    scatter = jax.jit(lambda x, dx: jnp.zeros_like(table).at[x].add(dx))

    total, lm, kl, first, keys, picked = None, 0.0, 0.0, None, None, []
    for b in range(n):
        inputs, mine, chose = [table[ids[b]]], [], []
        for i in range(depth):
            (x, part), (experts, chosen) = forward(inputs[-1],
                                                   params[f"layers_{i}"])
            inputs.append(x)
            kl = kl + part / (n * seq)
            mine.append(experts)
            if b == 0:
                chose.append(chosen)
        (part, lp), (dx, d_scale, d_head) = head_grad(
            inputs.pop(), params["final_norm_scale"],
            params["lm_head_kernel"], labels[b])
        grads = {"final_norm_scale": d_scale, "lm_head_kernel": d_head}
        for i in reversed(range(depth)):
            dx, grads[f"layers_{i}"] = backward(
                inputs.pop(), params[f"layers_{i}"], dx)
        grads["embed_tokens"] = {"embedding": scatter(ids[b], dx)}
        total = grads if total is None else add(total, grads)
        lm = lm + part
        if b == 0:
            first, keys = lp, jnp.stack(chose)
        picked.append(jnp.stack(mine))
    return (lm, kl), total, first, jnp.stack(picked), keys


# ------------------------------------------------------------------ counts
def indexer_params(cfg):
    sa = cfg["sa_config"]
    return cfg["hidden_size"] * (
        sa["indexer_num_heads"] * sa["indexer_head_dim"]
        + sa["indexer_head_dim"] + sa["indexer_num_heads"])


def layer_matmul_params(cfg):
    """Matmul weights a token passes in one layer outside its routed
    experts: the four attention projections, the indexer's three, the
    router."""
    h = cfg["hidden_size"]
    return (2 * h * (cfg["num_attention_heads"] + cfg["num_key_value_heads"])
            * cfg["head_dim"] + indexer_params(cfg) + h * cfg["num_experts"])


def routed_expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def pairs(cfg, seq_len):
    """(chosen, causal) (row, key) pairs of one sequence of one layer:
    ``sum_t min(t + 1, topk)`` and the triangle."""
    k = min(int(cfg["sa_config"]["topk"]), seq_len)
    return k * (k + 1) // 2 + (seq_len - k) * k, seq_len * (seq_len + 1) // 2


def flops_per_token(cfg, seq_len, slots_per_token):
    """Forward + backward FLOPs one trained token needs at the shares held:
    ``6 x`` every matmul weight a token passes (a routed expert per slot:
    ``slots_per_token`` is the mean number of slots a token sends the
    experts held here in one layer), plus the head, plus, a (row, key) pair:
    over the CHOSEN pairs the main attention's scores and values (``12 heads
    D``) and the loss's second ``q . k`` (``2 heads D``, forward only), and
    over the CAUSAL pairs the indexer's scores (``6 H_I D_I``: one product
    forward, two backward).  What a walk computes beside (the unchosen pairs
    of a visited tile, scores made a second time) and recomputed operations
    do not count."""
    sa, depth = cfg["sa_config"], layers_held(cfg)
    matmul = (depth * (layer_matmul_params(cfg)
                       + slots_per_token * routed_expert_params(cfg))
              + cfg["hidden_size"] * share(cfg)["vocab"])
    chosen, causal = pairs(cfg, seq_len)
    wide = cfg["num_attention_heads"] * cfg["head_dim"]
    thin = sa["indexer_num_heads"] * sa["indexer_head_dim"]
    return 6 * matmul + depth * (14 * wide * chosen
                                 + 6 * thin * causal) / seq_len
