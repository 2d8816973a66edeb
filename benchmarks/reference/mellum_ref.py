"""Plain reference for Mellum2-12B-A2.5B-Instruct (``model_type`` ``mellum``;
the published ``config.json`` is the configuration file's ``source``): a
decoder whose attention layers are of two kinds by ``layer_types`` (a causal
window or the whole causal past, each with rotary parameters of its own) and
whose every MLP is a softmax-routed mixture of gated experts with no shared
expert.  Forward pass, per-token log-probabilities, which held experts each
token chose, the loss and its gradient in straightforward ``jax.numpy``.

The equations, for one sequence ``ids`` [S] and layer l, ``x`` [S, H].  Lines
marked *assumed* are not settled by the ``config.json``; each is also in the
configuration file's ``assumed``.

* ``h = x + Attn_l(RMS(x))``, ``y = h + MoE(RMS(h))`` (a pre-norm block,
  *assumed*), ``RMS(x) = x / sqrt(mean(x^2) + eps) * scale``, eps
  ``rms_norm_eps``; no bias anywhere (``attention_bias`` false).  A closing
  RMSNorm, an untied head (``tie_word_embeddings`` false).
* ``Attn``: q [S, ``num_attention_heads``, ``head_dim``], k and v [S,
  ``num_key_value_heads``, ``head_dim``] (a KV head serves ``heads / kv``
  consecutive query heads), rotary on all of ``head_dim`` (the halves
  convention: ``x cos + rotate_half(x) sin``), scale ``head_dim^-1/2``,
  softmax in float32, output projection.  No q/k norm (*assumed*).
  ``layer_types[l]``: ``full_attention`` row i sees the columns j <= i;
  ``sliding_attention`` the columns ``i - sliding_window < j <= i``.
  Computed here a block of query rows at a time under an explicit mask.
* Rotary by kind (``rope_parameters[kind]``).  ``default``: ``inv_freq_i =
  theta^(-2i/d)``.  ``yarn``, as the ``transformers`` library computes it
  from the published keys (``yarn_inv_freq``): with ``f_i = theta^(2i/d)``,
  ``c(r) = d ln(original_max_position_embeddings / (2 pi r)) / (2 ln
  theta)``, ``low = floor(c(beta_fast))``, ``high = ceil(c(beta_slow))``
  clamped to [0, d - 1], ``ramp_i = clip((i - low) / (high - low), 0, 1)``:
  ``inv_freq_i = ramp_i / (factor f_i) + (1 - ramp_i) / f_i``; cos and sin
  are multiplied by ``attention_factor``.
* ``MoE``: ``p = softmax(u W_r)`` in float32 over all ``num_experts``; the
  ``num_experts_per_tok`` largest; ``w = p_chosen / sum p_chosen``
  (``norm_topk_prob``); ``sum_e w_e W_down,e (silu(W_gate,e u) * W_up,e u)``
  at width ``moe_intermediate_size`` (``hidden_act`` silu).  No router bias,
  no selection bias, no auxiliary balancing loss (the config states no
  coefficient) (*assumed*).  A layer's whole output is the routed sum: there
  is no shared expert.  Computed here as a loop over the held experts with a
  dense mask over the tokens.  ``mlp_layer_types`` other than ``sparse`` are
  refused (the published list has none; ``intermediate_size`` is kept,
  unused).
* Weights (*assumed*): normal(0, ``initializer_range`` or 0.02) matrices,
  tables and router; unit norm scales.
* Left out: the multi-token-prediction head of the model card; the
  ``config.json`` has no key for it.

A chip's share (``share``): ``layers_held`` layers from ``first_layer_held``,
``routed_experts_held`` experts from ``first_expert_held`` (a chip adds only
its own experts' terms), ``vocab_rows_held`` rows of both tables.  Router,
norms and attention are whole on every chip.  What the absent shares would
add is left out, here and in the program alike.

No kernels, no cache; float32 with
``jax.default_matmul_precision("highest")`` on every matmul of the forward,
recomputed and backward passes, unless a lower ``precision`` is asked for:
those exist only as *controls* of the benchmark's output check (``"fp8"``,
``"bfloat16"``: every matmul's inputs, and in the backward pass the incoming
gradient too, rounded to that type), as does ``every_layer_full`` (every
layer's own rotary, the sliding layers' window ignored: what a program that
dropped the window would compute).  Imports nothing from the program under test; weights come from
:func:`init_params`, i.e. from the seed.
"""

import functools
import math

import jax
import jax.numpy as jnp

# what every reference shares: the controls' rounded matmul, and training
# made plain (global norm, clip, Adam's first step)
from benchmarks.reference.gpt_neox_ref import (  # noqa: F401
    PRECISIONS, _einsum, _nest, adam_first_step, clip_scale, global_norm)

QUERY_BLOCK = 512       # query rows of attention computed at a time
KINDS = ("sliding_attention", "full_attention")


# ------------------------------------------------------------------ shares
def layer_kinds(cfg):
    """The kinds of the attention layers that are run, in order."""
    whole = list(cfg["layer_types"])
    first = int(cfg.get("first_layer_held", 0))
    held = int(cfg.get("layers_held", len(whole)))
    mlps = list(cfg.get("mlp_layer_types", ["sparse"] * len(whole)))
    if set(whole) - set(KINDS) or set(mlps[first:first + held]) - {"sparse"}:
        raise ValueError("layer_types are sliding_attention / full_attention "
                         "and every held MLP is sparse")
    return whole[first:first + held]


def share(cfg):
    """What this chip holds, from the ``*_held`` keys (the whole where a key
    is absent)."""
    return {"first_expert": int(cfg.get("first_expert_held", 0)),
            "experts": int(cfg.get("routed_experts_held", cfg["num_experts"])),
            "vocab": int(cfg.get("vocab_rows_held", cfg["vocab_size"]))}


# ---------------------------------------------------------------- weights
def layer_shapes(cfg, sh):
    """One layer's parameters as ``{path tuple: shape}``."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nq, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f = cfg["moe_intermediate_size"]
    return {("input_norm_scale",): (h,),
            ("attn", "q_proj", "kernel"): (h, nq * d),
            ("attn", "k_proj", "kernel"): (h, kv * d),
            ("attn", "v_proj", "kernel"): (h, kv * d),
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
    for i in range(len(layer_kinds(cfg))):
        for path, shape in layer_shapes(cfg, sh).items():
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
def yarn_inv_freq(dim, theta, factor, original_max_position_embeddings,
                  beta_fast=32, beta_slow=1):
    """YaRN's inverse frequencies [dim / 2], as the ``transformers`` library
    computes them (module docstring)."""
    def correction(rotations):
        return (dim * math.log(original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    freq = theta ** (2 * i / dim)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return ramp / (factor * freq) + (1.0 - ramp) / freq


def rotary(cfg, kind, positions):
    """(cos, sin) [S, head_dim] float32 of a layer kind's rotary embedding."""
    rope, d = cfg["rope_parameters"][kind], cfg["head_dim"]
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


def _rotate(x, cos, sin):
    """x [S, heads, d] by the halves convention."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos[:, None, :] + jnp.concatenate([-x2, x1], -1) * sin[:, None, :]


# ----------------------------------------------------------------- sublayers
def _dense(x, p, precision):
    return _einsum("si,io->so", x, p["kernel"].astype(jnp.float32), precision)


def _rms_norm(x, scale, eps):
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def attention(u, p, cfg, kind, precision="float32", windowed=True):
    """Grouped-query attention of one layer kind, a block of query rows at a
    time under its explicit mask: u [S, H] -> [S, H].  ``windowed`` False is
    the control's: the kind's own rotary, its window ignored."""
    s = u.shape[0]
    nq, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    positions = jnp.arange(s)
    cos, sin = rotary(cfg, kind, positions)
    q = _rotate(_dense(u, p["q_proj"], precision).reshape(s, nq, d), cos, sin)
    k = _rotate(_dense(u, p["k_proj"], precision).reshape(s, kv, d), cos, sin)
    v = _dense(u, p["v_proj"], precision).reshape(s, kv, d)
    k, v = (jnp.repeat(t, nq // kv, axis=1) for t in (k, v))
    window = (int(cfg["sliding_window"])
              if kind == "sliding_attention" and windowed else s)

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
    return _dense(out.reshape(s, nq * d), p["o_proj"], precision)


def route(u, p, cfg, precision="float32"):
    """-> (chosen experts [S, k], their weights [S, k]) over ALL experts."""
    probs = jax.nn.softmax(_einsum("si,io->so", u, p["router_kernel"],
                                   precision), axis=-1)
    weights, chosen = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    return chosen, weights


def moe(u, p, cfg, sh, precision="float32"):
    """The expert layer over a share's experts: u [S, H] -> ([S, H], which
    held experts each token chose [S, held] bool)."""
    chosen, weights = route(u, p, cfg, precision)
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
def _layer(x, p, kind, cfg, sh, precision, windowed=True):
    eps = cfg["rms_norm_eps"]
    h = x + attention(_rms_norm(x, p["input_norm_scale"], eps), p["attn"],
                      cfg, kind, precision, windowed)
    y, picked = moe(_rms_norm(h, p["post_norm_scale"], eps), p["moe"], cfg,
                    sh, precision)
    return h + y, picked


def hidden_states(params, cfg, ids, precision="float32",
                  every_layer_full=False):
    """The closing norm's output [S, H] for ONE sequence ``ids`` [S], and
    which held experts each token chose in each layer [layers, S, held]."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    sh, picked = share(cfg), []
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"]["embedding"].astype(jnp.float32)[ids]
        for i, kind in enumerate(layer_kinds(cfg)):
            x, mine = _layer(x, params[f"layers_{i}"], kind, cfg, sh,
                             precision, not every_layer_full)
            picked.append(mine)
        x = _rms_norm(x, params["final_norm_scale"], cfg["rms_norm_eps"])
    return x, jnp.stack(picked)


def token_logprobs(params, cfg, ids, labels, precision="float32",
                   every_layer_full=False):
    """log p(labels[i] | ids[:i+1]) [S] for one sequence, and the chosen
    held experts [layers, S, held]."""
    h, picked = hidden_states(params, cfg, ids, precision, every_layer_full)
    with jax.default_matmul_precision("highest"):
        lg = _einsum("sh,hv->sv", h, params["lm_head_kernel"], precision)
    return (jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
            - jax.nn.logsumexp(lg, axis=-1)), picked


# --------------------------------------------------------------- training
def _at_highest(fn):
    """``fn`` traced with every float32 matmul at ``highest``."""
    @functools.wraps(fn)
    def run(*args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)
    return run


def loss_and_grads(params, cfg, ids, labels, precision="float32",
                   every_layer_full=False):
    """The loss over a batch [B, S] and its gradient with respect to every
    parameter: one sequence at a time, and the chain rule a layer at a time.
    The forward pass keeps each layer's input; the backward pass goes back
    through the head and then layer by layer, recomputing a layer from its
    input (``jax.vjp``).  The same arithmetic as ``jax.grad`` of the mean of
    :func:`token_logprobs` (a test holds them equal); layers of one kind
    share one compiled program, and no more than one layer's intermediates
    are live.
    -> (loss, gradient tree, the first sequence's per-token log-probs [S],
    the held experts every sequence's tokens chose [B, layers, S, held])."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    n, sh = ids.shape[0], share(cfg)
    layers = layer_kinds(cfg)
    eps = cfg["rms_norm_eps"]

    def layer(kind):
        return functools.partial(_layer, kind=kind, cfg=cfg, sh=sh,
                                 precision=precision,
                                 windowed=not every_layer_full)

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
        picked.append(jnp.stack(mine))
    return mean, total, first, jnp.stack(picked)


# ------------------------------------------------------------------ counts
def layer_matmul_params(cfg):
    """Matmul weights a token passes in one layer outside its routed
    experts: the four attention projections and the router."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    return (2 * h * (cfg["num_attention_heads"]
                     + cfg["num_key_value_heads"]) * d
            + h * cfg["num_experts"])


def routed_expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def band_pairs(seq_len, window=None):
    """(row, column) pairs that see each other under a causal window: the
    triangle's where there is none or it reaches the whole length."""
    w = min(int(window), seq_len) if window else seq_len
    return w * seq_len - w * (w - 1) // 2


def flops_per_token(cfg, seq_len, slots_per_token):
    """Forward + backward FLOPs one trained token needs at the shares held:
    ``6 x`` every matmul weight a token passes (a routed expert per slot:
    ``slots_per_token`` is the mean number of slots a token sends the
    experts held here in one layer), plus the head, plus the attention
    scores and values: ``12 heads D S`` a full layer (the customary
    full-square count of ``core.model_flops_per_token``) and that times the
    band's share of the triangle a windowed layer.  Recomputed operations do
    not count."""
    kinds, sh = layer_kinds(cfg), share(cfg)
    matmul = (len(kinds) * (layer_matmul_params(cfg)
                            + slots_per_token * routed_expert_params(cfg))
              + cfg["hidden_size"] * sh["vocab"])
    square = 12 * cfg["num_attention_heads"] * cfg["head_dim"] * seq_len
    band = (band_pairs(seq_len, cfg["sliding_window"])
            / band_pairs(seq_len))
    return (6 * matmul + square * (kinds.count("full_attention")
                                   + band * kinds.count("sliding_attention")))
