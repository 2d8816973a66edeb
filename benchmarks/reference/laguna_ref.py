"""Plain reference for Laguna-S-2.1 (poolside, ``model_type`` ``laguna``; the
published ``config.json`` is the configuration file's ``source``): a decoder
of Mellum's schema -- attention layers of two kinds by ``layer_types``, each
with rotary parameters of its own, MLPs by ``mlp_layer_types`` -- whose
query heads go by the layer's kind, whose every head's output is gated,
whose full layers turn half a head, whose first MLP is dense and whose
sparse ones add a shared expert to a scaled softmax-routed sum.  Forward
pass, per-token log-probabilities, which held experts each token chose, the
loss and its gradient in straightforward ``jax.numpy``.

The equations, for one sequence ``ids`` [S] and layer l of kind k =
``layer_types[l]``, ``x`` [S, H].  Lines marked *assumed* are not settled by
the ``config.json``; each is also in the configuration file's ``assumed``.

* ``h = x + Attn_l(RMS(x))``, ``y = h + MLP_l(RMS(h))`` (a pre-norm block of
  two sublayers, *assumed* as Mellum's file assumes it), ``RMS(x) = x /
  sqrt(mean(x^2) + eps) * scale`` (the scale itself, no unit offset), eps
  ``rms_norm_eps``; no bias anywhere (``attention_bias`` false).  A closing
  RMSNorm, an untied head (``tie_word_embeddings`` false).
* ``Attn``: with ``u = RMS(x)``, q [S, ``H_k``, ``head_dim``] where ``H_k =
  num_attention_heads_per_layer[l]`` (48 full, 72 sliding), k and v [S,
  ``num_key_value_heads``, ``head_dim``]; query head ``n`` reads KV head ``n
  // (H_k / kv)``; scale ``head_dim^-1/2``, softmax in float32.  No q/k norm
  (*assumed*: the config has no key for one).  ``full_attention`` row i sees
  the columns j <= i; ``sliding_attention`` the columns ``i - sliding_window
  < j <= i``.  Computed here a block of query rows at a time under an
  explicit mask.
* Rotary by kind (``rope_parameters[kind]``), the halves convention (``x cos
  + rotate_half(x) sin``, *assumed*) on the FIRST ``partial_rotary_factor x
  head_dim`` dims of a head, the rest passed through (*assumed*: which part
  turns; the transformers library's partial rotary turns the first): all 128
  dims of a sliding layer (``default``: ``inv_freq_i = theta^(-2i/d)``,
  theta 10,000), the first 64 of a full layer under ``yarn`` as the
  ``transformers`` library computes it from the published keys WITH ``d`` =
  64, the dims that turn (``mellum_ref.yarn_inv_freq``: factor 128 over
  8,192, beta 32 / 1, theta 500,000), cos and sin times
  ``attention_factor``.
* The gate (``gating`` ``per-head``; its form *assumed*, the headwise gate
  of arXiv:2505.06708): ``g = sigmoid(u W_g)`` [S, ``H_k``], ``W_g`` [H,
  ``H_k``], one scalar a head and token from the sublayer's own input;
  ``Attn = concat_n(g_n a_n) W_o``.
* ``MLP`` where ``mlp_layer_types[l]`` is ``dense`` (layer 0,
  ``mlp_only_layers``): ``W_down (silu(W_gate m) * W_up m)`` at
  ``intermediate_size``.
* ``MLP`` where it is ``sparse``: ``p = softmax(m W_r)`` in float32 over all
  ``num_experts`` (*assumed*: the config names no scoring function;
  ``moe_router_logit_softcapping`` 0 is none); the ``num_experts_per_tok``
  largest; ``w = moe_routed_scaling_factor p_chosen / sum p_chosen``
  (``norm_topk_prob``; on the output, ``moe_apply_router_weight_on_input``
  false); ``sum_e w_e E_e(m) + E_shared(m)``, every expert ``W_down
  (silu(W_gate .) * W_up .)`` at ``moe_intermediate_size`` |
  ``shared_expert_intermediate_size``, the shared one unweighted and ungated
  (*assumed*: no key for a gate on it).  No router bias, no selection bias,
  no auxiliary loss (no coefficient in the config) (*assumed*).  The routed
  sum is computed here as a loop over the held experts with a dense mask
  over the tokens.
* Weights (*assumed*): normal(0, ``initializer_range`` or 0.02) matrices,
  tables, gate and router; unit norm scales.
* Left out: whatever the model card names and the ``config.json`` does not
  (a multi-token-prediction head among them).

A chip's share (``share``): ``layers_held`` layers from ``first_layer_held``,
``routed_experts_held`` experts from ``first_expert_held`` (a chip adds only
its own experts' terms), ``vocab_rows_held`` rows of both tables,
``key_value_heads_held`` KV heads from ``first_key_value_head_held`` with
the query heads that read them (``full_attention_heads_held`` |
``sliding_attention_heads_held``; an absent head adds nothing).  Router,
norms, the shared expert and the dense MLP are whole on every chip.  What
the absent shares would add is left out, here and in the program alike.

No kernels, no cache; float32 with
``jax.default_matmul_precision("highest")`` on every matmul of the forward,
recomputed and backward passes, unless a lower ``precision`` is asked for:
those exist only as *controls* of the benchmark's output check (``"fp8"``,
``"bfloat16"``), as do the mechanisms left out one at a time (``without``,
any of ``MECHANISMS``): what a program that dropped the window, the gate,
the shared expert, the partial rotary or the routed scale would compute.
Imports nothing from the program under test; weights come from
:func:`init_params`, i.e. from the seed.
"""

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference.gpt_neox_ref import (  # noqa: F401
    PRECISIONS, _einsum, _nest, adam_first_step, clip_scale, global_norm)
# the sibling's plain pieces that are this model's too
from benchmarks.reference.mellum_ref import (  # noqa: F401
    KINDS, QUERY_BLOCK, _at_highest, _dense, _rms_norm, _rotate, band_pairs,
    yarn_inv_freq)

SLIDING, FULL = KINDS
DENSE, SPARSE = "dense", "sparse"
#: what ``without`` may name: the mechanism a control leaves out
MECHANISMS = ("window", "gate", "shared_expert", "partial_rotary",
              "routed_scale")


def _check_without(without):
    if set(without) - set(MECHANISMS):
        raise ValueError(f"without {without!r}: {MECHANISMS}")
    return tuple(without)


# ------------------------------------------------------------------ shares
def layer_kinds(cfg):
    """(attention kind, MLP kind) of the layers that are run, in order."""
    whole = list(zip(cfg["layer_types"], cfg["mlp_layer_types"]))
    first = int(cfg.get("first_layer_held", 0))
    held = int(cfg.get("layers_held", len(whole)))
    if any(a not in KINDS or m not in (DENSE, SPARSE) for a, m in whole):
        raise ValueError("layer_types are sliding_attention / full_attention "
                         "and mlp_layer_types dense / sparse")
    return whole[first:first + held]


def whole_heads(cfg):
    """Query heads by layer kind, from the published per-layer list."""
    by_kind = {}
    for kind, heads in zip(cfg["layer_types"],
                           cfg["num_attention_heads_per_layer"]):
        if by_kind.setdefault(kind, int(heads)) != heads:
            raise ValueError(f"{kind} layers differ in their head counts")
    return by_kind


def share(cfg):
    """What this chip holds, from the ``*_held`` keys (the whole where a key
    is absent).  A query head goes with the KV head it reads."""
    whole, kv = whole_heads(cfg), int(cfg["num_key_value_heads"])
    sh = {"first_expert": int(cfg.get("first_expert_held", 0)),
          "experts": int(cfg.get("routed_experts_held", cfg["num_experts"])),
          "vocab": int(cfg.get("vocab_rows_held", cfg["vocab_size"])),
          "kv_heads": int(cfg.get("key_value_heads_held", kv)),
          "first_kv_head": int(cfg.get("first_key_value_head_held", 0)),
          "heads": {kind: int(cfg.get(f"{kind}_heads_held", n))
                    for kind, n in whole.items()}}
    if any(sh["heads"][kind] * kv != n * sh["kv_heads"]
           for kind, n in whole.items()):
        raise ValueError("the query heads held are not those of the KV heads "
                         f"held: {sh['heads']} of {whole}")
    return sh


# ---------------------------------------------------------------- weights
def _gated_mlp_shapes(name, h, width):
    return {(name, "gate_proj", "kernel"): (h, width),
            (name, "up_proj", "kernel"): (h, width),
            (name, "down_proj", "kernel"): (width, h)}


def layer_shapes(cfg, sh, kind):
    """One layer's parameters as ``{path tuple: shape}``."""
    attention, mlp = kind
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nq, kv = sh["heads"][attention], sh["kv_heads"]
    shapes = {("input_norm_scale",): (h,),
              ("attn", "q_proj", "kernel"): (h, nq * d),
              ("attn", "k_proj", "kernel"): (h, kv * d),
              ("attn", "v_proj", "kernel"): (h, kv * d),
              ("attn", "g_proj", "kernel"): (h, nq),
              ("attn", "o_proj", "kernel"): (nq * d, h),
              ("post_norm_scale",): (h,)}
    if mlp == DENSE:
        return {**shapes,
                **_gated_mlp_shapes("mlp", h, cfg["intermediate_size"])}
    f = cfg["moe_intermediate_size"]
    return {**shapes,
            ("moe", "router_kernel"): (h, cfg["num_experts"]),
            # gate | up side by side: one matmul in, one out, an expert
            ("moe", "experts_gate_up_proj"): (sh["experts"], h, 2 * f),
            ("moe", "experts_down_proj"): (sh["experts"], f, h),
            **_gated_mlp_shapes("shared_expert", h,
                                cfg["shared_expert_intermediate_size"])}


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
    call: unit norm scales, everything else normal(0, initializer_range)."""
    shapes = param_shapes(cfg)
    std = float(cfg.get("initializer_range", 0.02))

    def make(key):
        return _nest({
            path: jnp.ones(shape, jnp.float32) if path[-1].endswith(
                "norm_scale") else std * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
            for i, (path, shape) in enumerate(shapes.items())})

    # any whole number up to a little over 2**31 (and beyond): two words
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(make)(key)


# ------------------------------------------------------------------ rotary
def rotary(cfg, kind, positions, whole_head=False):
    """(cos, sin) [S, rotary dims] float32 of a layer kind's rotary
    embedding: over ``partial_rotary_factor`` of ``head_dim``, or
    (``whole_head``, a control's) over all of it."""
    rope = cfg["rope_parameters"][kind]
    d = cfg["head_dim"] if whole_head else int(
        cfg["head_dim"] * float(rope.get("partial_rotary_factor", 1)))
    theta = float(rope["rope_theta"])
    if rope["rope_type"] == "yarn":
        inv_freq = yarn_inv_freq(
            d, theta, float(rope["factor"]),
            int(rope["original_max_position_embeddings"]),
            float(rope.get("beta_fast", 32)), float(rope.get("beta_slow", 1)))
        scale = float(rope.get("attention_factor", 1.0))
    elif rope["rope_type"] == "default":
        inv_freq = theta ** (-2 * jnp.arange(d // 2, dtype=jnp.float32) / d)
        scale = 1.0
    else:
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def rotate_first(x, cos, sin):
    """x [S, heads, d]: the first ``cos.shape[-1]`` dims of every head
    turned (the halves convention among themselves), the rest as they are."""
    turned = cos.shape[-1]
    return jnp.concatenate([_rotate(x[..., :turned], cos, sin),
                            x[..., turned:]], axis=-1)


# ----------------------------------------------------------------- sublayers
def attention(u, p, cfg, sh, kind, precision="float32", without=()):
    """Grouped-query gated attention of one layer kind at the heads held, a
    block of query rows at a time under its explicit mask: u [S, H] -> [S,
    H]."""
    s = u.shape[0]
    nq, kv, d = sh["heads"][kind], sh["kv_heads"], cfg["head_dim"]
    positions = jnp.arange(s)
    cos, sin = rotary(cfg, kind, positions, "partial_rotary" in without)
    q = rotate_first(_dense(u, p["q_proj"], precision).reshape(s, nq, d),
                     cos, sin)
    k = rotate_first(_dense(u, p["k_proj"], precision).reshape(s, kv, d),
                     cos, sin)
    v = _dense(u, p["v_proj"], precision).reshape(s, kv, d)
    k, v = (jnp.repeat(t, nq // kv, axis=1) for t in (k, v))
    window = (int(cfg["sliding_window"])
              if kind == SLIDING and "window" not in without else s)

    @jax.checkpoint
    def rows(block):
        qb, at = block
        scores = _einsum("qnd,knd->nqk", qb, k, precision) / math.sqrt(d)
        seen = ((positions[None, :] <= at[:, None])
                & (positions[None, :] > at[:, None] - window))
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return _einsum("nqk,knd->qnd", probs, v, precision)

    # blocks of query rows, one after another (one compiled copy)
    size = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    out = jax.lax.map(rows, (q.reshape(s // size, size, nq, d),
                             positions.reshape(s // size, size)))
    out = out.reshape(s, nq, d)
    if "gate" not in without:
        out = out * jax.nn.sigmoid(_dense(u, p["g_proj"], precision))[
            ..., None]
    return _dense(out.reshape(s, nq * d), p["o_proj"], precision)


def gated_mlp(u, p, precision="float32"):
    """``W_down (silu(W_gate u) * W_up u)``: u [S, H] -> [S, H]."""
    hidden = (jax.nn.silu(_dense(u, p["gate_proj"], precision))
              * _dense(u, p["up_proj"], precision))
    return _dense(hidden, p["down_proj"], precision)


def route(u, p, cfg, precision="float32", without=()):
    """-> (chosen experts [S, k], their weights [S, k]) over ALL experts."""
    probs = jax.nn.softmax(_einsum("si,io->so", u, p["router_kernel"],
                                   precision), axis=-1)
    weights, chosen = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    if "routed_scale" not in without:
        weights = weights * float(cfg["moe_routed_scaling_factor"])
    return chosen, weights


def moe(u, p, cfg, sh, precision="float32", without=()):
    """The routed sum over a share's experts: u [S, H] -> ([S, H], which
    held experts each token chose [S, held] bool)."""
    chosen, weights = route(u, p, cfg, precision, without)
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
    return out, picked.T


# ---------------------------------------------------------------- forward
def _layer(x, p, kind, cfg, sh, precision, without=()):
    """-> (y [S, H], which held experts each token chose [S, held]; [S, 0]
    of a dense layer)."""
    eps = cfg["rms_norm_eps"]
    h = x + attention(_rms_norm(x, p["input_norm_scale"], eps), p["attn"],
                      cfg, sh, kind[0], precision, without)
    m = _rms_norm(h, p["post_norm_scale"], eps)
    if kind[1] == DENSE:
        return h + gated_mlp(m, p["mlp"], precision), jnp.zeros(
            (x.shape[0], 0), bool)
    y, picked = moe(m, p["moe"], cfg, sh, precision, without)
    if "shared_expert" not in without:
        y = y + gated_mlp(m, p["shared_expert"], precision)
    return h + y, picked


def _sparse(kinds, per_layer):
    """The entries of a per-layer list that belong to sparse layers."""
    return [x for kind, x in zip(kinds, per_layer) if kind[1] == SPARSE]


def hidden_states(params, cfg, ids, precision="float32", without=()):
    """The closing norm's output [S, H] for ONE sequence ``ids`` [S], and
    which held experts each token chose in each sparse layer [sparse layers,
    S, held]."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    without = _check_without(without)
    sh, kinds, picked = share(cfg), layer_kinds(cfg), []
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"]["embedding"].astype(jnp.float32)[ids]
        for i, kind in enumerate(kinds):
            x, mine = _layer(x, params[f"layers_{i}"], kind, cfg, sh,
                             precision, without)
            picked.append(mine)
        x = _rms_norm(x, params["final_norm_scale"], cfg["rms_norm_eps"])
    return x, jnp.stack(_sparse(kinds, picked))


def token_logprobs(params, cfg, ids, labels, precision="float32", without=()):
    """log p(labels[i] | ids[:i+1]) [S] for one sequence, and the chosen
    held experts [sparse layers, S, held]."""
    h, picked = hidden_states(params, cfg, ids, precision, without)
    with jax.default_matmul_precision("highest"):
        lg = _einsum("sh,hv->sv", h, params["lm_head_kernel"], precision)
    return (jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
            - jax.nn.logsumexp(lg, axis=-1)), picked


# --------------------------------------------------------------- training
def loss_and_grads(params, cfg, ids, labels, precision="float32", without=()):
    """The loss over a batch [B, S] and its gradient with respect to every
    parameter: one sequence at a time, and the chain rule a layer at a time.
    The forward pass keeps each layer's input; the backward pass goes back
    through the head and then layer by layer, recomputing a layer from its
    input (``jax.vjp``).  The same arithmetic as ``jax.grad`` of the mean of
    :func:`token_logprobs` (a test holds them equal); layers of one kind
    share one compiled program, and no more than one layer's intermediates
    are live.
    -> (loss, gradient tree, the first sequence's per-token log-probs [S],
    the held experts every sequence's tokens chose [B, sparse layers, S,
    held])."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    without = _check_without(without)
    n, sh = ids.shape[0], share(cfg)
    layers = layer_kinds(cfg)
    eps = cfg["rms_norm_eps"]

    def layer(kind):
        return functools.partial(_layer, kind=kind, cfg=cfg, sh=sh,
                                 precision=precision, without=without)

    def back(kind):
        def through(x, p, dy):
            _, transpose, _ = jax.vjp(layer(kind), x, p, has_aux=True)
            return transpose(dy)
        return through

    def head(h, scale, w, y):
        lg = _einsum("sh,hv->sv", _rms_norm(h, scale, eps), w, precision)
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
            x, chose = forward[kind](inputs[-1], params[f"layers_{i}"])
            inputs.append(x)
            mine.append(chose)
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
def attention_params(cfg, sh, kind):
    """Matmul weights of a layer's attention at the heads held: q and o, k
    and v, the gate's column a head."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nq = sh["heads"][kind]
    return h * (2 * (nq + sh["kv_heads"]) * d + nq)


def gated_mlp_params(cfg, width):
    return 3 * cfg["hidden_size"] * width


def layer_matmul_params(cfg, sh, kind):
    """Matmul weights a token passes in one layer outside its routed
    experts: attention with its gate, and the dense MLP or the router and
    the shared expert."""
    if kind[1] == DENSE:
        return (attention_params(cfg, sh, kind[0])
                + gated_mlp_params(cfg, cfg["intermediate_size"]))
    return (attention_params(cfg, sh, kind[0])
            + cfg["hidden_size"] * cfg["num_experts"]
            + gated_mlp_params(cfg, cfg["shared_expert_intermediate_size"]))


def routed_expert_params(cfg):
    return gated_mlp_params(cfg, cfg["moe_intermediate_size"])


def flops_per_token(cfg, seq_len, slots_per_token):
    """Forward + backward FLOPs one trained token needs at the shares held:
    ``6 x`` every matmul weight a token passes (a routed expert per slot:
    ``slots_per_token`` is the mean number of slots a token sends the
    experts held here in one sparse layer; the gate, the shared expert and
    the dense MLP counted), plus the head, plus the attention scores and
    values by kind at the heads held: ``12 heads D S`` a full layer (the
    customary full-square count of ``core.model_flops_per_token``) and that
    times the band's share of the triangle a windowed layer.  Recomputed
    operations do not count."""
    kinds, sh = layer_kinds(cfg), share(cfg)
    matmul = (sum(layer_matmul_params(cfg, sh, kind) for kind in kinds)
              + len(_sparse(kinds, kinds)) * slots_per_token
              * routed_expert_params(cfg)
              + cfg["hidden_size"] * sh["vocab"])
    band = (band_pairs(seq_len, cfg["sliding_window"])
            / band_pairs(seq_len))
    scores = sum(sh["heads"][a] * (1.0 if a == FULL else band)
                 for a, _ in kinds)
    return 6 * matmul + 12 * cfg["head_dim"] * seq_len * scores
