"""Plain reference for Moonlight-16B-A3B (``model_type`` ``deepseek_v3``; the
published ``config.json`` is the configuration file's ``source``): latent
attention (MLA) over DeepSeek-V3's mixture.  Forward pass, per-token
log-probabilities, which held experts each token chose, the loss (with the
sequence-wise balance term) and its gradient in straightforward
``jax.numpy``.

The equations, for one sequence ``ids`` [S] and layer l, ``x`` [S, H]
(DeepSeek-V2 section 2.1, arXiv:2405.04434, for the attention; DeepSeek-V3
section 2.1.2, arXiv:2412.19437, for the mixture).  Lines marked *assumed*
are not settled by the ``config.json``; each is also in the configuration
file's ``assumed``.

* ``h = x + MLA(RMS(x))``, ``y = h + FFN(RMS(h))``, ``RMS(x) = x /
  sqrt(mean(x^2) + eps) * scale``, eps ``rms_norm_eps``; no bias anywhere
  (``attention_bias`` false).  A closing RMSNorm, an untied head
  (``tie_word_embeddings`` false).
* ``MLA`` (``q_lora_rank`` null: q has no latent): ``q = u W_q`` -> [S,
  ``num_attention_heads``, ``qk_nope_head_dim`` + ``qk_rope_head_dim``], a
  head ``[q_nope | q_rope]``.  ``[c | k_r] = u W_kva`` -> ``kv_lora_rank`` +
  ``qk_rope_head_dim``; ``c <- RMS(c)`` with a scale of its own, same eps;
  ``k_nope = c W_kb``, ``v = c W_vb`` -> [S, heads, ``qk_nope_head_dim``] and
  [S, heads, ``v_head_dim``].  Rotary at ``rope_theta`` on every head's
  ``q_rope`` and on the ONE ``k_r`` (the halves convention, ``x cos +
  rotate_half(x) sin``: *assumed*, see the file's ``assumed.rotary``).  THE
  EXPANDED FORM: a head's key is ``[k_nope_h | k_r]``, the rotary key copied
  to every head here; ``s = q . k / sqrt(qk_nope_head_dim +
  qk_rope_head_dim)`` (no ``rope_scaling`` in the row: no length scaling of
  the scale), causal softmax in float32, ``o = P v``, ``out = o W_o``.
  Computed a block of query rows at a time under an explicit mask.  The
  weights are stored by part: ``q_nope_proj`` | ``q_rope_proj`` are the
  published ``q_proj``'s columns of each head's two parts, ``k_b_proj`` |
  ``v_b_proj`` the published ``kv_b_proj``'s (DeepSeek-V2's ``W^UK``,
  ``W^UV``): on seeded weights a renaming of columns.
* ``FFN``, the first ``first_k_dense_replace`` layers: ``W_down (silu(W_gate
  u) * W_up u)`` at ``intermediate_size``.  The others: ``s = sigmoid(u
  W_r)`` in float32 over all ``n_routed_experts``; the
  ``num_experts_per_tok`` largest of ``s + b`` (``b`` the selection bias of
  ``topk_method`` ``noaux_tc``; ``n_group`` = ``topk_group`` = 1: no group
  limit); ``w = s_chosen / sum s_chosen`` (``norm_topk_prob``) ``x
  routed_scaling_factor``; ``sum_e w_e Expert_e(u)``, gated SiLU experts at
  ``moe_intermediate_size``; plus the shared experts, ONE gated MLP
  ``n_shared_experts x moe_intermediate_size`` wide on every token,
  unweighted.  Computed as a loop over the held experts with a dense mask
  over the tokens.
* ``seq_aux`` true: per sequence of T tokens and sparse layer, ``s' = s /
  sum_j s_j``, ``P_i = mean_t s'[t, i]``, ``f_i = n_routed_experts /
  (num_experts_per_tok T) x #{t : i chosen}`` (a count: no gradient),
  ``L_bal = alpha sum_i f_i P_i`` over ALL the experts; summed over the
  sparse layers, averaged over the batch's sequences, added to the LM loss.
  ``alpha`` (``aux_loss_alpha``) is not in the row: *assumed* 1e-4,
  DeepSeek-V3 section 4.2's.
* Weights (*assumed*): normal(0, ``initializer_range`` or 0.02) matrices,
  tables and router; unit norm scales; ``b`` = 0.
* Left out: the rule that updates ``b`` (it is a zero leaf that takes no
  gradient); the exchange between the chips that share a layer; dropout
  (none published).

A chip's share (``share``): ``layers_held`` layers from ``first_layer_held``,
``routed_experts_held`` experts from ``first_expert_held`` (a chip adds only
its own experts' terms), ``vocab_rows_held`` rows of both tables.  Attention
with every head, the router, the shared experts, the norms and the dense MLP
are whole on every chip.  What the absent shares would add is left out, here
and in the program alike.

No kernels, no cache; float32 with
``jax.default_matmul_precision("highest")`` on every matmul of the forward,
recomputed and backward passes, unless a lower ``precision`` is asked for:
those exist only as *controls* of the benchmark's output check (``"fp8"``,
``"bfloat16"``: every matmul's inputs, and in the backward pass the incoming
gradient too, rounded to that type; ``low`` names the matmuls that are
rounded, all of them or the attention's ``products`` or the latent's
``up_projection`` alone), as do the mechanisms left out one at a time
(``without``, any of ``MECHANISMS``).  Imports nothing from the program
under test; weights come from :func:`init_params`, i.e. from the seed.
"""

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference.gpt_neox_ref import (  # noqa: F401
    PRECISIONS, _einsum, _nest, adam_first_step, clip_scale, global_norm)
# the siblings' plain pieces that are this model's too
from benchmarks.reference.laguna_ref import (_gated_mlp_shapes, gated_mlp,
                                             gated_mlp_params)
from benchmarks.reference.mellum_ref import (QUERY_BLOCK, _at_highest, _dense,
                                             _rms_norm, _rotate)

DENSE, SPARSE = "dense", "sparse"
#: what ``without`` may name, the mechanism a control leaves out:
#: ``latent_norm`` (``c`` goes on as projected), ``rotary`` (nothing turns),
#: ``shared_rope_key`` (the score's rotary product dropped: q_nope . k_nope
#: alone), ``shared_experts`` (the routed sum alone), ``routed_scale``
#: (weights not multiplied by ``routed_scaling_factor``)
MECHANISMS = ("latent_norm", "rotary", "shared_rope_key", "shared_experts",
              "routed_scale")
#: what ``low`` may name: which matmuls a lower ``precision`` rounds
LOW = ("all", "products", "up_projection")
ALPHA = 1e-4        # where the configuration gives no ``aux_loss_alpha``


def _check_without(without):
    if set(without) - set(MECHANISMS):
        raise ValueError(f"without {without!r}: {MECHANISMS}")
    return tuple(without)


# ------------------------------------------------------------------ shares
def layer_kinds(cfg):
    """The kinds of the layers that are run, in order."""
    whole = int(cfg["num_hidden_layers"])
    first = int(cfg.get("first_layer_held", 0))
    held = int(cfg.get("layers_held", whole))
    if (cfg.get("q_lora_rank") is not None or cfg.get("rope_scaling")
            or int(cfg.get("n_group", 1)) != 1
            or cfg.get("scoring_func", "sigmoid") != "sigmoid"
            or int(cfg.get("moe_layer_freq", 1)) != 1):
        raise ValueError("q has no latent, rotary is unscaled, one group of "
                         "sigmoid-scored experts, every later layer sparse")
    if first + held > whole:
        raise ValueError("the layers held lie outside the model's")
    return [DENSE if i < int(cfg["first_k_dense_replace"]) else SPARSE
            for i in range(first, first + held)]


def share(cfg):
    """What this chip holds, from the ``*_held`` keys (the whole where a key
    is absent)."""
    return {"first_expert": int(cfg.get("first_expert_held", 0)),
            "experts": int(cfg.get("routed_experts_held",
                                   cfg["n_routed_experts"])),
            "vocab": int(cfg.get("vocab_rows_held", cfg["vocab_size"]))}


def widths(cfg):
    """(heads, the latent's rank, d_nope, d_rope, d_v)."""
    return (int(cfg["num_attention_heads"]), int(cfg["kv_lora_rank"]),
            int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]),
            int(cfg["v_head_dim"]))


def shared_width(cfg):
    return int(cfg["n_shared_experts"]) * int(cfg["moe_intermediate_size"])


# ---------------------------------------------------------------- weights
def layer_shapes(cfg, sh, kind):
    """One layer's parameters as ``{path tuple: shape}``."""
    h = cfg["hidden_size"]
    n, rank, dn, dr, dv = widths(cfg)
    shapes = {("input_norm_scale",): (h,),
              ("attn", "q_nope_proj", "kernel"): (h, n * dn),
              ("attn", "q_rope_proj", "kernel"): (h, n * dr),
              ("attn", "kv_a_proj", "kernel"): (h, rank + dr),
              ("attn", "kv_a_norm_scale"): (rank,),
              ("attn", "k_b_proj", "kernel"): (rank, n * dn),
              ("attn", "v_b_proj", "kernel"): (rank, n * dv),
              ("attn", "o_proj", "kernel"): (n * dv, h),
              ("post_norm_scale",): (h,)}
    if kind == DENSE:
        return {**shapes,
                **_gated_mlp_shapes("mlp", h, cfg["intermediate_size"])}
    f = cfg["moe_intermediate_size"]
    return {**shapes,
            ("moe", "router_kernel"): (h, cfg["n_routed_experts"]),
            ("moe", "selection_bias"): (cfg["n_routed_experts"],),
            # gate | up side by side: one matmul in, one out, an expert
            ("moe", "experts_gate_up_proj"): (sh["experts"], h, 2 * f),
            ("moe", "experts_down_proj"): (sh["experts"], f, h),
            **_gated_mlp_shapes("shared_experts", h, shared_width(cfg))}


def param_shapes(cfg):
    """The parameter tree as ``{path tuple: shape}`` (the program's flax
    tree has the same names)."""
    h, sh = cfg["hidden_size"], share(cfg)
    shapes = {("embed_tokens", "embedding"): (sh["vocab"], h)}
    for i, kind in enumerate(layer_kinds(cfg)):
        for path, shape in layer_shapes(cfg, sh, kind).items():
            shapes[(f"layers_{i}",) + path] = shape
    shapes[("final_norm_scale",)] = (h,)
    shapes[("lm_head_kernel",)] = (h, sh["vocab"])
    return shapes


def num_params(cfg):
    return sum(math.prod(s) for s in param_shapes(cfg).values())


def init_params(cfg, seed):
    """Seeded float32 weights, made on the default device in ONE jitted
    call: unit norm scales, a zero selection bias, everything else
    normal(0, initializer_range)."""
    shapes = param_shapes(cfg)
    std = float(cfg.get("initializer_range", 0.02))

    def draw(path, shape, key):
        if path[-1].endswith("norm_scale"):
            return jnp.ones(shape, jnp.float32)
        if path[-1] == "selection_bias":
            return jnp.zeros(shape, jnp.float32)
        return std * jax.random.normal(key, shape, jnp.float32)

    def make(key):
        return _nest({path: draw(path, shape, jax.random.fold_in(key, i))
                      for i, (path, shape) in enumerate(shapes.items())})

    # any whole number up to a little over 2**31 (and beyond): two words
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(make)(key)


# ----------------------------------------------------------------- sublayers
def rotary(cfg, positions):
    """(cos, sin) [S, qk_rope_head_dim] float32."""
    d = int(cfg["qk_rope_head_dim"])
    inv_freq = float(cfg["rope_theta"]) ** (
        -2 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


def _of(low, which, precision):
    """The precision of the matmuls ``which`` names under ``low``."""
    return precision if low in ("all", which) else "float32"


def latent(u, p, cfg, precision="float32", without=(), low="all"):
    """Everything between the sublayer's input and the attention itself, in
    the EXPANDED form: u [S, H] -> (q [S, n, d_nope + d_rope], k alike, the
    rotary key copied to every head, v [S, n, d_v])."""
    s = u.shape[0]
    n, rank, dn, dr, dv = widths(cfg)
    rest = _of(low, "rest", precision)
    q_nope = _dense(u, p["q_nope_proj"], rest).reshape(s, n, dn)
    q_rope = _dense(u, p["q_rope_proj"], rest).reshape(s, n, dr)
    down = _dense(u, p["kv_a_proj"], rest)
    c, k_r = down[:, :rank], down[:, rank:]
    if "latent_norm" not in without:
        c = _rms_norm(c, p["kv_a_norm_scale"], cfg["rms_norm_eps"])
    up = _of(low, "up_projection", precision)
    k_nope = _dense(c, p["k_b_proj"], up).reshape(s, n, dn)
    v = _dense(c, p["v_b_proj"], up).reshape(s, n, dv)
    if "rotary" not in without:
        cos, sin = rotary(cfg, jnp.arange(s))
        q_rope = _rotate(q_rope, cos, sin)
        k_r = _rotate(k_r[:, None], cos, sin)[:, 0]     # a head of its own
    if "shared_rope_key" in without:
        k_r = jnp.zeros_like(k_r)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r[:, None], (s, n, dr))], axis=-1)
    return jnp.concatenate([q_nope, q_rope], axis=-1), k, v


def attention(u, p, cfg, precision="float32", without=(), low="all"):
    """The MLA sublayer, a block of query rows at a time under its explicit
    causal mask: u [S, H] -> [S, H]."""
    s = u.shape[0]
    n, _, dn, dr, dv = widths(cfg)
    q, k, v = latent(u, p, cfg, precision, without, low)
    positions = jnp.arange(s)
    products = _of(low, "products", precision)

    @jax.checkpoint
    def rows(block):
        qb, at = block
        scores = _einsum("qnd,knd->nqk", qb, k, products) / math.sqrt(dn + dr)
        seen = positions[None, :] <= at[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return _einsum("nqk,knd->qnd", probs, v, products)

    # blocks of query rows, one after another (one compiled copy)
    size = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    out = jax.lax.map(rows, (q.reshape(s // size, size, n, dn + dr),
                             positions.reshape(s // size, size)))
    return _dense(out.reshape(s, n * dv), p["o_proj"],
                  _of(low, "rest", precision))


def route(u, p, cfg, precision="float32", without=()):
    """-> (chosen experts [S, k], their weights [S, k], the scores over ALL
    experts [S, E])."""
    scores = jax.nn.sigmoid(_einsum("si,io->so", u, p["router_kernel"],
                                    precision))
    _, chosen = jax.lax.top_k(
        scores + jax.lax.stop_gradient(p["selection_bias"]),
        int(cfg["num_experts_per_tok"]))
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    if "routed_scale" not in without:
        weights = weights * float(cfg["routed_scaling_factor"])
    return chosen, weights, scores


def balance(scores, chosen, cfg):
    """One sequence's ``alpha sum_i f_i P_i`` (module docstring)."""
    tokens, experts = scores.shape
    share_ = scores / jnp.sum(scores, axis=-1, keepdims=True)
    took = jnp.sum(chosen[:, :, None] == jnp.arange(experts), axis=(0, 1))
    f = jax.lax.stop_gradient(took.astype(jnp.float32)) * (
        experts / (chosen.shape[1] * tokens))
    return float(cfg.get("aux_loss_alpha", ALPHA)) * jnp.sum(
        f * jnp.mean(share_, axis=0))


def moe(u, p, cfg, sh, precision="float32", without=()):
    """The routed sum over a share's experts: u [S, H] -> ([S, H], which
    held experts each token chose [S, held] bool, the balance term)."""
    chosen, weights, scores = route(u, p, cfg, precision, without)
    f = cfg["moe_intermediate_size"]

    def expert(out, held):               # one expert, a dense mask over tokens
        index, w_in, w_out = held
        mine = chosen == index                                    # [S, k]
        w = jnp.sum(jnp.where(mine, weights, 0.0), axis=-1)
        hidden = _einsum("sh,hf->sf", u, w_in, precision)
        hidden = jax.nn.silu(hidden[:, :f]) * hidden[:, f:]
        return out + w[:, None] * _einsum(
            "sf,fh->sh", hidden, w_out, precision), jnp.any(mine, axis=-1)

    # the held experts one after another (one compiled copy)
    out, picked = jax.lax.scan(
        expert, jnp.zeros_like(u),
        (sh["first_expert"] + jnp.arange(sh["experts"]),
         p["experts_gate_up_proj"].astype(jnp.float32),
         p["experts_down_proj"].astype(jnp.float32)))
    return out, picked.T, balance(scores, chosen, cfg)


# ---------------------------------------------------------------- forward
def _layer(x, p, kind, cfg, sh, precision, without=(), low="all"):
    """-> ((y [S, H], the layer's balance term), which held experts each
    token chose [S, held]; 0 and [S, 0] of a dense layer)."""
    eps = cfg["rms_norm_eps"]
    rest = _of(low, "rest", precision)
    h = x + attention(_rms_norm(x, p["input_norm_scale"], eps), p["attn"],
                      cfg, precision, without, low)
    m = _rms_norm(h, p["post_norm_scale"], eps)
    if kind == DENSE:
        return (h + gated_mlp(m, p["mlp"], rest), jnp.float32(0.0)), \
            jnp.zeros((x.shape[0], 0), bool)
    y, picked, term = moe(m, p["moe"], cfg, sh, rest, without)
    if "shared_experts" not in without:
        y = y + gated_mlp(m, p["shared_experts"], rest)
    return (h + y, term), picked


def _sparse(kinds, per_layer):
    """The entries of a per-layer list that belong to sparse layers."""
    return [x for kind, x in zip(kinds, per_layer) if kind == SPARSE]


def hidden_states(params, cfg, ids, precision="float32", without=(),
                  low="all"):
    """The closing norm's output [S, H] for ONE sequence ``ids`` [S], which
    held experts each token chose in each sparse layer [sparse layers, S,
    held], and the sequence's balance term summed over the sparse layers."""
    if precision not in PRECISIONS or low not in LOW:
        raise ValueError(f"precision {precision!r} of {PRECISIONS}, low "
                         f"{low!r} of {LOW}")
    without = _check_without(without)
    sh, kinds, picked, terms = share(cfg), layer_kinds(cfg), [], 0.0
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"]["embedding"].astype(jnp.float32)[ids]
        for i, kind in enumerate(kinds):
            (x, term), mine = _layer(x, params[f"layers_{i}"], kind, cfg, sh,
                                     precision, without, low)
            picked.append(mine)
            terms = terms + term
        x = _rms_norm(x, params["final_norm_scale"], cfg["rms_norm_eps"])
    return x, jnp.stack(_sparse(kinds, picked)), terms


def _logits(params, cfg, ids, precision, without, low):
    """-> ([S, vocabulary rows held], the chosen held experts, the balance
    terms) for one sequence."""
    h, picked, terms = hidden_states(params, cfg, ids, precision, without,
                                     low)
    with jax.default_matmul_precision("highest"):
        return _einsum("sh,hv->sv", h, params["lm_head_kernel"],
                       _of(low, "rest", precision)), picked, terms


def logits(params, cfg, ids, precision="float32", without=(), low="all"):
    """[S, vocabulary rows held] for one sequence."""
    return _logits(params, cfg, ids, precision, without, low)[0]


def _sequence(params, cfg, ids, labels, precision, without, low):
    """-> (log p(labels) [S], the chosen held experts, the balance terms)."""
    lg, picked, terms = _logits(params, cfg, ids, precision, without, low)
    return (jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
            - jax.nn.logsumexp(lg, axis=-1)), picked, terms


def token_logprobs(params, cfg, ids, labels, precision="float32", without=(),
                   low="all"):
    """log p(labels[i] | ids[:i+1]) [S] for one sequence, and the chosen
    held experts [sparse layers, S, held]."""
    return _sequence(params, cfg, ids, labels, precision, without, low)[:2]


def loss(params, cfg, ids, labels, precision="float32", without=(),
         low="all"):
    """The mean loss over a batch [B, S], the balance terms in it, by
    ``jax``'s own differentiation where a test wants it: small sizes."""
    total = 0.0
    for b in range(ids.shape[0]):
        lp, _, terms = _sequence(params, cfg, ids[b], labels[b], precision,
                                 without, low)
        total = total - jnp.mean(lp) + terms
    return total / ids.shape[0]


# --------------------------------------------------------------- training
def loss_and_grads(params, cfg, ids, labels, precision="float32", without=(),
                   low="all"):
    """The loss over a batch [B, S] (cross entropy + the balance terms) and
    its gradient with respect to every parameter: one sequence at a time,
    and the chain rule a layer at a time.  The forward pass keeps each
    layer's input; the backward pass goes back through the head and then
    layer by layer, recomputing a layer from its input (``jax.vjp``), a
    sparse layer's balance term entering with the cotangent 1 / B.  The same
    arithmetic as ``jax.grad`` of :func:`loss` (a test holds them equal);
    layers of one kind share one compiled program, and no more than one
    layer's intermediates are live.
    -> (loss, gradient tree, the first sequence's per-token log-probs [S],
    the held experts every sequence's tokens chose [B, sparse layers, S,
    held])."""
    if precision not in PRECISIONS or low not in LOW:
        raise ValueError(f"precision {precision!r} of {PRECISIONS}, low "
                         f"{low!r} of {LOW}")
    without = _check_without(without)
    n, sh = ids.shape[0], share(cfg)
    layers = layer_kinds(cfg)
    eps = cfg["rms_norm_eps"]
    rest = _of(low, "rest", precision)

    def layer(kind):
        return functools.partial(_layer, kind=kind, cfg=cfg, sh=sh,
                                 precision=precision, without=without,
                                 low=low)

    def back(kind):
        def through(x, p, dy):
            _, transpose, _ = jax.vjp(layer(kind), x, p, has_aux=True)
            return transpose((dy, jnp.float32(1.0 / n)))
        return through

    def head(h, scale, w, y):
        lg = _einsum("sh,hv->sv", _rms_norm(h, scale, eps), w, rest)
        lp = (jnp.take_along_axis(lg, y[:, None], axis=-1)[:, 0]
              - jax.nn.logsumexp(lg, axis=-1))
        return -jnp.mean(lp) / n, lp

    forward = {k: jax.jit(_at_highest(layer(k))) for k in set(layers)}
    backward = {k: jax.jit(_at_highest(back(k))) for k in set(layers)}
    head_grad = jax.jit(_at_highest(jax.value_and_grad(
        head, argnums=(0, 1, 2), has_aux=True)))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=0)
    table = params["embed_tokens"]["embedding"]
    scatter = jax.jit(lambda x, dx: jnp.zeros_like(table).at[x].add(dx))

    total, mean, first, picked = None, 0.0, None, []
    for b in range(n):
        inputs, mine = [table[ids[b]]], []
        for i, kind in enumerate(layers):
            (x, term), chose = forward[kind](inputs[-1],
                                             params[f"layers_{i}"])
            inputs.append(x)
            mine.append(chose)
            mean = mean + term / n
        (part, lp), (dx, d_scale, d_head) = head_grad(
            inputs.pop(), params["final_norm_scale"],
            params["lm_head_kernel"], labels[b])
        grads = {"final_norm_scale": d_scale, "lm_head_kernel": d_head}
        for i in reversed(range(len(layers))):
            dx, grads[f"layers_{i}"] = backward[layers[i]](
                inputs.pop(), params[f"layers_{i}"], dx)
        grads["embed_tokens"] = {"embedding": scatter(ids[b], dx)}
        total = grads if total is None else add(total, grads)
        mean = mean + part
        first = lp if first is None else first
        picked.append(jnp.stack(_sparse(layers, mine)))
    return mean, total, first, jnp.stack(picked)


# ------------------------------------------------------------------ counts
def attention_params(cfg):
    """Matmul weights of a layer's attention: q, the down-projection, the
    two up-projections, o."""
    h = cfg["hidden_size"]
    n, rank, dn, dr, dv = widths(cfg)
    return (h * n * (dn + dr) + h * (rank + dr) + rank * n * (dn + dv)
            + n * dv * h)


def layer_matmul_params(cfg, kind):
    """Matmul weights a token passes in one layer outside its routed
    experts: attention, and the dense MLP or the router and the shared
    experts."""
    if kind == DENSE:
        return (attention_params(cfg)
                + gated_mlp_params(cfg, cfg["intermediate_size"]))
    return (attention_params(cfg)
            + cfg["hidden_size"] * cfg["n_routed_experts"]
            + gated_mlp_params(cfg, shared_width(cfg)))


def routed_expert_params(cfg):
    return gated_mlp_params(cfg, cfg["moe_intermediate_size"])


def even_slots_per_token(cfg):
    """Slots a token sends the experts held here in one sparse layer under
    even routing."""
    return (cfg["num_experts_per_tok"] * share(cfg)["experts"]
            / cfg["n_routed_experts"])


def flops_per_token(cfg, seq_len, slots_per_token=None):
    """Forward + backward FLOPs one trained token needs at the shares held:
    ``6 x`` every matmul weight a token passes (``layer_matmul_params``; a
    routed expert per slot: ``slots_per_token`` is the mean number of slots
    a token sends the experts held here in one sparse layer, under even
    routing where none is given), plus the head, plus the attention's score
    (over ``d_nope + d_rope``) and values (``d_v``) over the CAUSAL half,
    ``3 heads (d_nope + d_rope + d_v) S`` a layer (the kernel's own count,
    ``kernel_costs/flash_attention_mla``).  The norms, the rotary and the
    recomputed operations do not count."""
    kinds = layer_kinds(cfg)
    n, _, dn, dr, dv = widths(cfg)
    if slots_per_token is None:
        slots_per_token = even_slots_per_token(cfg)
    matmul = (sum(layer_matmul_params(cfg, kind) for kind in kinds)
              + kinds.count(SPARSE) * slots_per_token
              * routed_expert_params(cfg)
              + cfg["hidden_size"] * share(cfg)["vocab"])
    return 6 * matmul + len(kinds) * 3 * n * (dn + dr + dv) * seq_len
