"""Plain reference for ZAYA1-8B's language model (Zyphra, ``model_type``
``zaya``; the published ``config.json`` is the configuration file's
``source``; the family is described in the Compressed Convolutional Attention
report, arXiv:2510.04476, and the ZAYA1 report, arXiv:2511.17127): a decoder
of 40 layers of one kind, each an attention sublayer in a compressed latent
with convolutional mixing (CCA) and then a top-1-of-16 mixture whose router
is an MLP that carries its state from layer to layer, on a scaled residual
stream, with a tied table.  Forward pass, per-token log-probabilities, which
held expert each token chose, the router's carried state, the loss and its
gradient in straightforward ``jax.numpy``.

Sizes from the ``config.json``: H = ``hidden_size`` 2048, n_q =
``num_attention_heads`` 8, n_kv = ``num_key_value_heads`` 2, d = ``head_dim``
128 (the latent's widths L_q = n_q d = 1024 and L_k = n_kv d = 256, g = n_q
/ n_kv = 4), ``cca_time0`` = ``cca_time1`` = 2 (the widths of the two
convolutions), ``partial_rotary_factor`` 0.5, ``rope_theta`` 5,000,000
(``rope_parameters.hybrid``; ``hybrid_sliding`` reaches nothing,
``sliding_window`` null), 16 experts, 1 a token, expert width 2048,
``router_hidden_size`` R = 256, ``rms_norm_eps`` 1e-5, silu, no bias in
attention or head, a tied table.  Every line the ``config.json`` does not
fix is in the configuration file's ``assumed``, with the report it is taken
from.  For one sequence ``ids`` [S]:

* **Scaled residual** (ZAYA1 report).  The stream ``r`` starts as the
  embedding.  A sublayer reads ``u = RMSNorm(r)`` (a learned scale) and gives
  ``f``; entering the next sublayer (after the last one: the closing
  RMSNorm) ``r <- a_r * (r + b_r) + a_o * (f + b_o)``, four learned vectors
  of H.  The four that fold sublayer j's output are kept with sublayer j
  (``attn_res_scale`` = [a_r, a_o] and ``attn_res_bias`` = [b_r, b_o] of a
  layer, ``mlp_res_*`` alike): the same sequence of folds, and a layer is
  then a function of the stream and the router's state alone.
* **CCA** (arXiv:2510.04476).  ``qt = u W_Q`` [S, L_q], ``kt = u W_K`` [S,
  L_k].  Value shift: ``v[t] = [u[t] W_V1 | u[t-1] W_V2]``, ``u[-1] = 0``:
  the first half of the KV heads' values are the token's own, the second
  half's the previous token's (``v_proj`` holds ``[W_V1 | W_V2]``).  Mixing
  on ``z = [qt | kt]`` [S, L_q + L_k]: a causal depthwise convolution of
  width ``cca_time0``, ``z1[t, c] = a0[c] z[t-1, c] + a1[c] z[t, c] + b[c]``
  (``conv_taps`` [2, C] = [a0, a1], ``conv_bias``); then a causal
  convolution of width ``cca_time1`` whose channels mix inside each head
  (n_q + n_kv groups of d channels), ``z2[t, h] = z1[t-1, h] A0_h + z1[t, h]
  A1_h + c_h`` (``head_conv_kernel`` [2, heads, d, d] = [A0, A1],
  ``head_conv_bias``); zeros before the sequence.  The q-k mean, from the
  values BEFORE the convolutions: ``m_q[t, j] = (qt[t, j] + kt[t, j // g]) /
  2`` for query head j, ``m_k[t, i]`` = the mean of ``m_q[t, j]`` over the g
  query heads of KV head i; ``q = z2_q + m_q``, ``k = z2_k + m_k``.  Each
  head of q and k is divided by its RMS over d (``sqrt(mean(x^2) + eps)``,
  no learned scale); k then times a learned temperature a KV head
  (``k_temperature``).  Rotary (the halves convention) on the first
  ``partial_rotary_factor x d`` dims of a head, after the norm.  ``o =
  softmax(q k^T / sqrt(d), causal) v``, a KV head serving g query heads; the
  sublayer's output ``o W_O``, [S, L_q] -> [S, H].  Computed here a block of
  query rows at a time under an explicit mask.
* **Router** (ZAYA1 report).  ``rho = u W_D + b_D`` [S, R]; depth
  averaging: ``rho <- rho + gamma * rho_prev``, ``gamma`` a learned vector
  of R, ``rho_prev`` the previous layer's ``rho`` after its own averaging
  (none in the first layer held, which has no ``gamma``); ``e = W_3
  gelu(W_2 gelu(W_1 RMSNorm(rho)))`` [S, 16] (a learned scale in the norm,
  the exact ``erf`` GELU, no bias on the three matrices); ``p = softmax(e)``
  over all 16; the expert chosen is ``argmax(p + beta)``, ``beta`` a
  balancing bias that takes no gradient (``selection_bias``, zero); the
  layer's output is ``p[chosen] * Expert_chosen(u)``, NOT renormalised (at k
  = 1 a renormalised weight is 1 and the router would get no gradient),
  ``Expert(u) = (silu(u W_g) * (u W_u)) W_d``.  No shared expert, no
  auxiliary loss.  The routed sum is computed here as a loop over the held
  experts with a dense mask over the tokens.
* A closing RMSNorm; the head is the table's transpose
  (``tie_word_embeddings``), so the table's gradient is the sum of the
  embedding's scatter and the head's.
* Weights (*assumed*): normal(0, 0.02) matrices, table and biases; unit norm
  scales and temperature; the convolutions at fan-in scale (``conv_taps``
  normal(0, K^-1/2), ``head_conv_kernel`` normal(0, (K d)^-1/2)), so that
  ``z2`` is of ``m_q``'s size; the residual scales normal(1, 0.1); ``gamma``
  normal(0.5, 0.1); ``beta`` zero.
* Left out: the rule that updates ``beta`` in training, a mixture-of-depths
  skip choice (the family's, no key of it in this config), the 74B
  sibling's sliding layers.

A chip's share (``share``): ``layers_held`` layers from ``first_layer_held``,
``routed_experts_held`` experts from ``first_expert_held`` (a chip adds only
its own experts' terms: a token whose expert is elsewhere gets nothing
here), ``vocab_rows_held`` rows of the table.  Attention, router and norms
are whole on every chip.  The first layer HELD has no carried state and no
``gamma``: at ``first_layer_held`` 0 that is the model's own first layer.

No kernels, no cache; float32 with
``jax.default_matmul_precision("highest")`` on every matmul of the forward,
recomputed and backward passes, unless a lower ``precision`` is asked for:
those exist only as *controls* of the benchmark's output check (``"fp8"``,
``"bfloat16"``), as do the mechanisms left out one at a time (``without``,
any of ``MECHANISMS``).  Imports nothing from the program under test;
weights come from :func:`init_params`, i.e. from the seed.
"""

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference.gpt_neox_ref import (  # noqa: F401
    PRECISIONS, _einsum, _nest, adam_first_step, clip_scale, global_norm)
# the siblings' plain pieces that are this model's too
from benchmarks.reference.laguna_ref import rotate_first  # noqa: F401
from benchmarks.reference.mellum_ref import (  # noqa: F401
    QUERY_BLOCK, _at_highest, _dense, _rms_norm)

KIND = "hybrid"
#: what ``without`` may name, the mechanism a control leaves out:
#: ``convolutions`` (``q = qt + m_q``), ``value_shift`` (both halves the
#: token's own), ``router_state`` (``gamma`` = 0), ``routed_weight`` (the
#: chosen weight renormalised: 1), ``residual_scaling`` (``r <- r + f``),
#: ``tied_head`` (a head of its own, from another seed)
MECHANISMS = ("convolutions", "value_shift", "router_state", "routed_weight",
              "residual_scaling", "tied_head")
#: the seed of the untied control's head
_OTHER_HEAD_SEED = 56


def _check_without(without):
    if set(without) - set(MECHANISMS):
        raise ValueError(f"without {without!r}: {MECHANISMS}")
    return tuple(without)


# ------------------------------------------------------------------ shares
def layers_held(cfg):
    """How many layers are run; every layer is of the one kind."""
    whole = int(cfg["num_hidden_layers"])
    if "layer_types" in cfg and (len(cfg["layer_types"]) != whole or set(
            cfg["layer_types"]) != {KIND}):
        raise ValueError(f"layer_types are {whole} x {KIND!r}")
    if cfg.get("sliding_window") or int(cfg["num_experts_per_tok"]) != 1:
        raise ValueError("no sliding window, one expert a token")
    held = int(cfg.get("layers_held", whole))
    if int(cfg.get("first_layer_held", 0)) + held > whole:
        raise ValueError("the layers held lie outside the model's")
    return held


def share(cfg):
    """What this chip holds, from the ``*_held`` keys (the whole where a key
    is absent)."""
    return {"first_expert": int(cfg.get("first_expert_held", 0)),
            "experts": int(cfg.get("routed_experts_held", cfg["num_experts"])),
            "vocab": int(cfg.get("vocab_rows_held", cfg["vocab_size"]))}


def widths(cfg):
    """(query heads, KV heads, head width, the mixed latent's channels)."""
    nq, kv, d = (int(cfg["num_attention_heads"]),
                 int(cfg["num_key_value_heads"]), int(cfg["head_dim"]))
    return nq, kv, d, (nq + kv) * d


# ---------------------------------------------------------------- weights
def layer_shapes(cfg, sh, first):
    """One layer's parameters as ``{path tuple: shape}``; the ``first``
    layer held has no ``gamma``."""
    h, r = cfg["hidden_size"], cfg["router_hidden_size"]
    nq, kv, d, c = widths(cfg)
    f = cfg["moe_intermediate_size"]
    shapes = {("input_norm_scale",): (h,),
              ("attn", "q_proj", "kernel"): (h, nq * d),
              ("attn", "k_proj", "kernel"): (h, kv * d),
              ("attn", "v_proj", "kernel"): (h, kv * d),
              ("attn", "conv_taps"): (cfg["cca_time0"], c),
              ("attn", "conv_bias"): (c,),
              ("attn", "head_conv_kernel"): (cfg["cca_time1"], nq + kv, d, d),
              ("attn", "head_conv_bias"): (c,),
              ("attn", "k_temperature"): (kv,),
              ("attn", "o_proj", "kernel"): (nq * d, h),
              ("attn_res_scale",): (2, h),
              ("attn_res_bias",): (2, h),
              ("post_norm_scale",): (h,),
              ("moe", "router_down_kernel"): (h, r),
              ("moe", "router_down_bias"): (r,),
              ("moe", "router_gamma"): (r,),
              ("moe", "router_norm_scale"): (r,),
              ("moe", "router_mlp_1"): (r, r),
              ("moe", "router_mlp_2"): (r, r),
              ("moe", "router_mlp_3"): (r, cfg["num_experts"]),
              ("moe", "selection_bias"): (cfg["num_experts"],),
              # gate | up side by side: one matmul in, one out, an expert
              ("moe", "experts_gate_up_proj"): (sh["experts"], h, 2 * f),
              ("moe", "experts_down_proj"): (sh["experts"], f, h),
              ("mlp_res_scale",): (2, h),
              ("mlp_res_bias",): (2, h)}
    if first:
        del shapes[("moe", "router_gamma")]
    return shapes


def param_shapes(cfg):
    """The parameter tree as ``{path tuple: shape}`` (the program's flax
    tree has the same names): ONE table, no head of its own."""
    h, sh = cfg["hidden_size"], share(cfg)
    shapes = {("embed_tokens", "embedding"): (sh["vocab"], h)}
    for i in range(layers_held(cfg)):
        for path, shape in layer_shapes(cfg, sh, i == 0).items():
            shapes[(f"layers_{i}",) + path] = shape
    shapes[("final_norm_scale",)] = (h,)
    return shapes


def num_params(cfg):
    return sum(math.prod(s) for s in param_shapes(cfg).values())


def _draw(path, shape, key, std):
    """A leaf's seeded values by its name (module docstring)."""
    name, normal = path[-1], functools.partial(jax.random.normal, key, shape)
    if name.endswith("norm_scale") or name == "k_temperature":
        return jnp.ones(shape, jnp.float32)
    if name == "selection_bias":
        return jnp.zeros(shape, jnp.float32)
    if name.endswith("res_scale"):
        return 1.0 + 0.1 * normal()
    if name == "router_gamma":
        return 0.5 + 0.1 * normal()
    if name == "conv_taps":
        return normal() * shape[0] ** -0.5
    if name == "head_conv_kernel":
        return normal() * (shape[0] * shape[2]) ** -0.5
    return std * normal()


def init_params(cfg, seed):
    """Seeded float32 weights, made on the default device in ONE jitted
    call."""
    shapes = param_shapes(cfg)
    std = float(cfg.get("initializer_range", 0.02))

    def make(key):
        return _nest({path: _draw(path, shape, jax.random.fold_in(key, i),
                                  std).astype(jnp.float32)
                      for i, (path, shape) in enumerate(shapes.items())})

    # any whole number up to a little over 2**31 (and beyond): two words
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(make)(key)


def other_head(cfg):
    """The untied control's head, a table of its own [V, H]."""
    shape = (share(cfg)["vocab"], cfg["hidden_size"])
    return float(cfg.get("initializer_range", 0.02)) * jax.random.normal(
        jax.random.PRNGKey(_OTHER_HEAD_SEED), shape, jnp.float32)


# ----------------------------------------------------------------- sublayers
def shifted(x, steps=1):
    """``x`` [S, ...] a step later: ``y[t] = x[t - steps]``, zeros before
    the sequence."""
    return jnp.pad(x, ((steps, 0),) + ((0, 0),) * (x.ndim - 1))[:x.shape[0]]


def depthwise_conv(z, taps, bias):
    """``y[t, c] = sum_k taps[k, c] z[t - (K - 1) + k, c] + bias[c]``: z
    [S, C], taps [K, C]; shifted sums."""
    width = taps.shape[0]
    return sum(shifted(z, width - 1 - k) * taps[k]
               for k in range(width)) + bias


def headwise_conv(z, kernel, bias, precision="float32"):
    """``y[t, h] = sum_k z[t - (K - 1) + k, h] kernel[k, h] + bias[h]``: z
    [S, heads * d], kernel [K, heads, d, d]; the channels mix inside a head."""
    width, heads, d, _ = kernel.shape
    s = z.shape[0]
    return sum(_einsum("shd,hde->she",
                       shifted(z, width - 1 - k).reshape(s, heads, d),
                       kernel[k], precision)
               for k in range(width)).reshape(s, heads * d) + bias


def unit_rms(x, eps):
    """Every head of ``x`` [S, heads, d] over its RMS."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def rotary(cfg, positions):
    """(cos, sin) [S, partial_rotary_factor x head_dim] float32."""
    rope = cfg["rope_parameters"][KIND]
    if rope["rope_type"] != "default":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    d = int(cfg["head_dim"] * float(rope["partial_rotary_factor"]))
    inv_freq = float(rope["rope_theta"]) ** (
        -2 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


def cca_mix(u, p, cfg, precision="float32", without=()):
    """Everything between the sublayer's input and the attention itself:
    u [S, H] -> (q [S, n_q, d], k [S, n_kv, d], v [S, n_kv, d])."""
    s = u.shape[0]
    nq, kv, d, _ = widths(cfg)
    g, eps = nq // kv, cfg["rms_norm_eps"]
    qt = _dense(u, p["q_proj"], precision)
    kt = _dense(u, p["k_proj"], precision)
    own, previous = jnp.split(_dense(u, p["v_proj"], precision), 2, axis=-1)
    if "value_shift" not in without:
        previous = shifted(previous)
    v = jnp.concatenate([own, previous], axis=-1).reshape(s, kv, d)
    z = jnp.concatenate([qt, kt], axis=-1)
    if "convolutions" not in without:
        z = depthwise_conv(z, p["conv_taps"], p["conv_bias"])
        z = headwise_conv(z, p["head_conv_kernel"], p["head_conv_bias"],
                          precision)
    z = z.reshape(s, nq + kv, d)
    qt, kt = qt.reshape(s, nq, d), kt.reshape(s, kv, d)
    m_q = (qt + jnp.repeat(kt, g, axis=1)) / 2
    m_k = jnp.mean(m_q.reshape(s, kv, g, d), axis=2)
    cos, sin = rotary(cfg, jnp.arange(s))
    q = rotate_first(unit_rms(z[:, :nq] + m_q, eps), cos, sin)
    k = rotate_first(unit_rms(z[:, nq:] + m_k, eps)
                     * p["k_temperature"][None, :, None], cos, sin)
    return q, k, v


def attention(u, p, cfg, precision="float32", without=()):
    """The CCA sublayer, a block of query rows at a time under its explicit
    causal mask: u [S, H] -> [S, H]."""
    s = u.shape[0]
    nq, kv, d, _ = widths(cfg)
    q, k, v = cca_mix(u, p, cfg, precision, without)
    k, v = (jnp.repeat(t, nq // kv, axis=1) for t in (k, v))
    positions = jnp.arange(s)

    @jax.checkpoint
    def rows(block):
        qb, at = block
        scores = _einsum("qnd,knd->nqk", qb, k, precision) / math.sqrt(d)
        seen = positions[None, :] <= at[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return _einsum("nqk,knd->qnd", probs, v, precision)

    # blocks of query rows, one after another (one compiled copy)
    size = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    out = jax.lax.map(rows, (q.reshape(s // size, size, nq, d),
                             positions.reshape(s // size, size)))
    return _dense(out.reshape(s, nq * d), p["o_proj"], precision)


def router(u, rho_prev, p, cfg, precision="float32", without=()):
    """-> (probabilities over ALL experts [S, E], the state carried to the
    next layer [S, R])."""
    rho = _einsum("si,io->so", u, p["router_down_kernel"],
                  precision) + p["router_down_bias"]
    if rho_prev is not None and "router_state" not in without:
        rho = rho + p["router_gamma"] * rho_prev
    hidden = _rms_norm(rho, p["router_norm_scale"], cfg["rms_norm_eps"])
    for name in ("router_mlp_1", "router_mlp_2"):
        hidden = jax.nn.gelu(_einsum("si,io->so", hidden, p[name], precision),
                             approximate=False)
    return jax.nn.softmax(_einsum("si,io->so", hidden, p["router_mlp_3"],
                                  precision), axis=-1), rho


def moe(u, rho_prev, p, cfg, sh, precision="float32", without=()):
    """The routed layer over a share's experts: u [S, H] -> ([S, H], which
    held expert each token chose [S, held] bool, the router's state)."""
    probs, rho = router(u, rho_prev, p, cfg, precision, without)
    chosen = jnp.argmax(probs + jax.lax.stop_gradient(p["selection_bias"]),
                        axis=-1)
    weight = jnp.take_along_axis(probs, chosen[:, None], axis=-1)[:, 0]
    if "routed_weight" in without:
        weight = jnp.ones_like(weight)
    f = cfg["moe_intermediate_size"]

    def expert(out, held):               # one expert, a dense mask over tokens
        index, w_in, w_out = held
        mine = chosen == index
        hidden = _einsum("sh,hf->sf", u, w_in, precision)
        hidden = jax.nn.silu(hidden[:, :f]) * hidden[:, f:]
        return out + jnp.where(mine, weight, 0.0)[:, None] * _einsum(
            "sf,fh->sh", hidden, w_out, precision), mine

    # the held experts one after another (one compiled copy)
    out, picked = jax.lax.scan(
        expert, jnp.zeros_like(u),
        (sh["first_expert"] + jnp.arange(sh["experts"]),
         p["experts_gate_up_proj"].astype(jnp.float32),
         p["experts_down_proj"].astype(jnp.float32)))
    return out, picked.T, rho


def fold(r, f, scale, bias, without=()):
    """``a_r * (r + b_r) + a_o * (f + b_o)``: scale [2, H] = [a_r, a_o],
    bias [2, H] = [b_r, b_o]."""
    if "residual_scaling" in without:
        return r + f
    return scale[0] * (r + bias[0]) + scale[1] * (f + bias[1])


# ---------------------------------------------------------------- forward
def _layer(x, rho_prev, p, cfg, sh, precision="float32", without=()):
    """-> ((the stream [S, H], the router's state [S, R]), which held expert
    each token chose [S, held])."""
    eps = cfg["rms_norm_eps"]
    f = attention(_rms_norm(x, p["input_norm_scale"], eps), p["attn"], cfg,
                  precision, without)
    x = fold(x, f, p["attn_res_scale"], p["attn_res_bias"], without)
    f, picked, rho = moe(_rms_norm(x, p["post_norm_scale"], eps), rho_prev,
                         p["moe"], cfg, sh, precision, without)
    return (fold(x, f, p["mlp_res_scale"], p["mlp_res_bias"], without),
            rho), picked


def hidden_states(params, cfg, ids, precision="float32", without=()):
    """The closing norm's output [S, H] for ONE sequence ``ids`` [S], which
    held expert each token chose in each layer [layers, S, held] and each
    layer's router state [layers, S, R]."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    without = _check_without(without)
    sh, picked, states, rho = share(cfg), [], [], None
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"]["embedding"].astype(jnp.float32)[ids]
        for i in range(layers_held(cfg)):
            (x, rho), mine = _layer(x, rho, params[f"layers_{i}"], cfg, sh,
                                    precision, without)
            picked.append(mine)
            states.append(rho)
        x = _rms_norm(x, params["final_norm_scale"], cfg["rms_norm_eps"])
    return x, jnp.stack(picked), jnp.stack(states)


def _head_table(params, cfg, without):
    return (other_head(cfg) if "tied_head" in without
            else params["embed_tokens"]["embedding"])


def token_logprobs(params, cfg, ids, labels, precision="float32", without=()):
    """log p(labels[i] | ids[:i+1]) [S] for one sequence, the chosen held
    experts [layers, S, held] and the router's states [layers, S, R]."""
    h, picked, states = hidden_states(params, cfg, ids, precision, without)
    with jax.default_matmul_precision("highest"):
        lg = _einsum("sh,vh->sv", h, _head_table(params, cfg, without),
                     precision)
    return (jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
            - jax.nn.logsumexp(lg, axis=-1)), picked, states


def loss(params, cfg, ids, labels, precision="float32", without=()):
    """The mean loss over a batch [B, S], by ``jax``'s own differentiation
    where a test wants it: small sizes."""
    return -sum(jnp.mean(token_logprobs(params, cfg, ids[b], labels[b],
                                        precision, without)[0])
                for b in range(ids.shape[0])) / ids.shape[0]


# --------------------------------------------------------------- training
def loss_and_grads(params, cfg, ids, labels, precision="float32", without=()):
    """The loss over a batch [B, S] and its gradient with respect to every
    parameter: one sequence at a time, and the chain rule a layer at a time.
    The forward pass keeps each layer's inputs (the stream and the router's
    state it was handed); the backward pass goes back through the head and
    then layer by layer, recomputing a layer from its inputs (``jax.vjp``),
    the cotangent of the state it handed on coming back with the stream's.
    The table's gradient is the sum of its two uses: the head's and the
    embedding's scatter.  The same arithmetic as ``jax.grad`` of
    :func:`loss` (a test holds them equal); the layers share two compiled
    programs (the first held has no carried state), and no more than one
    layer's intermediates are live.
    -> (loss, gradient tree, the first sequence's per-token log-probs [S],
    the held experts every sequence's tokens chose [B, layers, S, held])."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    without = _check_without(without)
    n, sh, depth = ids.shape[0], share(cfg), layers_held(cfg)
    eps = cfg["rms_norm_eps"]
    layer = functools.partial(_layer, cfg=cfg, sh=sh, precision=precision,
                              without=without)

    def back(x, rho_prev, p, d_out):
        _, transpose, _ = jax.vjp(layer, x, rho_prev, p, has_aux=True)
        return transpose(d_out)

    def first(x, p):
        return layer(x, None, p)

    def back_first(x, p, d_out):
        _, transpose, _ = jax.vjp(first, x, p, has_aux=True)
        return transpose(d_out)

    def head(h, scale, table, y):
        lg = _einsum("sh,vh->sv", _rms_norm(h, scale, eps), table, precision)
        lp = (jnp.take_along_axis(lg, y[:, None], axis=-1)[:, 0]
              - jax.nn.logsumexp(lg, axis=-1))
        return -jnp.mean(lp) / n, lp

    forward = [jax.jit(_at_highest(first)), jax.jit(_at_highest(layer))]
    backward = [jax.jit(_at_highest(back_first)), jax.jit(_at_highest(back))]
    head_grad = jax.jit(_at_highest(jax.value_and_grad(
        head, argnums=(0, 1, 2), has_aux=True)))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=0)
    table = params["embed_tokens"]["embedding"]
    head_table = _head_table(params, cfg, without)
    scatter = jax.jit(lambda x, dx, more: more.at[x].add(dx))

    total, mean, first_lp, picked = None, 0.0, None, []
    for b in range(n):
        inputs, mine = [(table[ids[b]],)], []
        for i in range(depth):
            out, chose = forward[min(i, 1)](*inputs[-1],
                                            params[f"layers_{i}"])
            inputs.append(out)
            mine.append(chose)
        x, rho = inputs.pop()
        (part, lp), (dx, d_scale, d_table) = head_grad(
            x, params["final_norm_scale"], head_table, labels[b])
        grads = {"final_norm_scale": d_scale}
        d_out = (dx, jnp.zeros_like(rho))
        for i in reversed(range(depth)):
            *d_out, grads[f"layers_{i}"] = backward[min(i, 1)](
                *inputs.pop(), params[f"layers_{i}"], tuple(d_out))
        if "tied_head" in without:      # the head's own gradient is no leaf's
            d_table = jnp.zeros_like(table)
        grads["embed_tokens"] = {"embedding": scatter(ids[b], d_out[0],
                                                      d_table)}
        total = grads if total is None else add(total, grads)
        mean = mean + part
        first_lp = lp if first_lp is None else first_lp
        picked.append(jnp.stack(mine))
    return mean, total, first_lp, jnp.stack(picked)


# ------------------------------------------------------------------ counts
def layer_matmul_params(cfg):
    """Matmul weights a token passes in one layer outside its routed expert:
    the five projections of the latent, the per-head convolution's matrices
    and the router's four."""
    h, r = cfg["hidden_size"], cfg["router_hidden_size"]
    nq, kv, d, _ = widths(cfg)
    return (h * (2 * nq + 2 * kv) * d
            + cfg["cca_time1"] * (nq + kv) * d * d
            + h * r + 2 * r * r + r * cfg["num_experts"])


def routed_expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def flops_per_token(cfg, seq_len, slots_per_token):
    """Forward + backward FLOPs one trained token needs at the shares held:
    ``6 x`` every matmul weight a token passes (``layer_matmul_params``; a
    routed expert per slot: ``slots_per_token`` is the mean number of slots
    a token sends the experts held here in one layer, at most 1), plus the
    head (the table once), plus the attention scores and values in the
    latent over the CAUSAL half, ``6 n_q d S`` a layer (the kernel's own
    count, ``kernel_costs/flash_attention``; the older cells' customary
    ``12 L H S`` counts the whole square).  The depthwise taps, the norms
    and the recomputed operations do not count."""
    nq, _, d, _ = widths(cfg)
    matmul = (layers_held(cfg) * (layer_matmul_params(cfg) + slots_per_token
                                  * routed_expert_params(cfg))
              + cfg["hidden_size"] * share(cfg)["vocab"])
    return 6 * matmul + layers_held(cfg) * 6 * nq * d * seq_len
