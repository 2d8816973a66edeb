"""Plain reference for the trunk of NVIDIA-Nemotron-3-Super-120B-A12B
(``model_type`` ``nemotron_h``; the published ``config.json`` is the
configuration file's ``source``): a hybrid language model whose depth is a
pattern of Mamba-2 layers, latent mixture-of-experts layers and grouped-query
attention layers.  Forward pass, per-token log-probabilities, which experts
each token chose, the loss and its gradient in straightforward ``jax.numpy``.

The equations, for one sequence ``ids`` [S].  Lines marked *assumed* are not
settled by the ``config.json``; each is also in the configuration file's
``assumed``.

* Every layer is pre-norm with one mixer and no second sublayer:
  ``x <- x + Mixer_l(RMS_l(x))``, ``RMS(x) = x / sqrt(mean(x^2) + eps) *
  scale``, eps ``layer_norm_epsilon``; the mixer by the layer's letter in
  ``hybrid_override_pattern``.  A final RMSNorm, an untied head.  No bias
  anywhere but the convolution's.
* ``M``, Mamba-2: ``[z | xBC | dt] = W_in u`` (widths ``d_inner``, ``d_inner
  + 2 G N``, ``heads``; ``d_inner = heads * mamba_head_dim``); ``xBC <-
  silu(conv(xBC) + b)``, a causal depthwise convolution of width
  ``conv_kernel``; split ``x`` (heads x P), ``B``, ``C`` (G groups x N, a
  group serves ``heads / G`` consecutive heads); ``dt = softplus(dt +
  dt_bias)`` (not clamped, *assumed*); ``A = -exp(A_log)`` a head; the state
  ``H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t`` ([P, N] a head), ``y_t =
  H_t C_t + D x_t``; ``y <- GroupRMS(y * silu(z))`` over G groups of
  ``d_inner / G`` channels (gate before norm, *assumed*); output ``W_out y``.
  Computed here as the recurrence, one step at a time (``lax.scan``).
* ``E``, latent mixture of experts: ``s = sigmoid(W_r u)`` over all
  ``n_routed_experts``; chosen = top ``num_experts_per_tok`` of ``s + b_sel``
  (``n_group = topk_group = 1``: no group limit; ``b_sel`` is a buffer held
  at zero, *assumed*: its balancing update is no gradient); ``w_e =
  routed_scaling_factor * s_e / sum_chosen s`` (``norm_topk_prob``); ``v =
  W_down u`` (hidden -> ``moe_latent_size``); routed ``= sum_{e chosen}
  w_e W2_e relu(W1_e v)^2`` (``relu2``, no gate); output ``W_up routed +
  V2 relu(V1 u)^2``, the shared expert on the layer's input.  The latent
  projections are plain matrices without norm or bias (*assumed*).  Computed
  here as a loop over the experts with a dense mask.
* ``*``, attention: ``num_attention_heads`` query heads on
  ``num_key_value_heads`` KV heads of ``head_dim``, causal softmax at scale
  ``1/sqrt(head_dim)``, no bias, NO rotary embedding (*assumed*: the
  family's published modelling code takes positions from the Mamba layers;
  ``rope_theta`` is kept in the file, unused).  Computed here a block of
  query rows at a time (rows are independent).
* Weights (*assumed*): normal(0, ``initializer_range`` or 0.02) matrices,
  tables and router; unit norm scales and ``D``; ``A_log = log(uniform[1,
  16])``; ``dt_bias`` = softplus's inverse of a log-uniform step in
  [``time_step_min``, ``time_step_max``] floored at ``time_step_floor``; the
  convolution's filter and bias uniform(+-1/sqrt(``conv_kernel``)) (the
  default of the ``Conv1d`` the published code builds).
* Left out: the multi-token-prediction module and its loss
  (``num_nextn_predict_layers``; the config gives its layer pattern and not
  how the hidden state and the next embedding are joined).

A chip's share (``share``): the configuration file's ``*_held`` keys say what
this chip holds of each layer -- ``mamba_heads_held`` with
``mamba_groups_held``, ``attention_heads_held`` with
``key_value_heads_held``, ``routed_experts_held`` from
``first_expert_held``, ``vocab_rows_held``, ``layers_held`` from
``first_layer_held`` -- and the reference is given the same.  Heads, groups
and experts are independent, so a share computes its part of a mixer's
output; the router, the latent projections and the shared expert are every
chip's alike.  What the absent shares would add is left out, here and in the
program alike.

No kernels, no cache; float32 with
``jax.default_matmul_precision("highest")`` on every matmul of the forward,
recomputed and backward passes, unless a lower ``precision`` is asked for:
those exist only as *controls* of the benchmark's output check (``"fp8"``,
``"bfloat16"``: every matmul's inputs, and in the backward pass the incoming
gradient too, rounded to that type).  Imports nothing from the program under
test; weights come from :func:`init_params`, i.e. from the seed.
"""

import functools
import math

import jax
import jax.numpy as jnp

# what every reference shares: the controls' rounded matmul, and training
# made plain (global norm, clip, Adam's first step)
from benchmarks.reference.gpt_neox_ref import (  # noqa: F401
    PRECISIONS, _einsum, _nest, adam_first_step, clip_scale, global_norm)

QUERY_BLOCK = 1024      # query rows of attention computed at a time
SCAN_BLOCK = 128        # steps of the recurrence recomputed at a time


# ------------------------------------------------------------------ shares
def pattern(cfg):
    """The letters of the layers that are run."""
    whole = cfg["hybrid_override_pattern"]
    first = int(cfg.get("first_layer_held", 0))
    return whole[first:first + int(cfg.get("layers_held", len(whole)))]


def share(cfg):
    """What this chip holds of each layer, from the ``*_held`` keys (the
    whole where a key is absent)."""
    return {
        "mamba_heads": int(cfg.get("mamba_heads_held",
                                   cfg["mamba_num_heads"])),
        "mamba_groups": int(cfg.get("mamba_groups_held", cfg["n_groups"])),
        "q_heads": int(cfg.get("attention_heads_held",
                               cfg["num_attention_heads"])),
        "kv_heads": int(cfg.get("key_value_heads_held",
                                cfg["num_key_value_heads"])),
        "first_expert": int(cfg.get("first_expert_held", 0)),
        "experts": int(cfg.get("routed_experts_held",
                               cfg["n_routed_experts"])),
        "vocab": int(cfg.get("vocab_rows_held", cfg["vocab_size"])),
    }


def mamba_widths(cfg, sh):
    """(d_inner, convolution channels, in-projection width) of a share."""
    inner = sh["mamba_heads"] * cfg["mamba_head_dim"]
    conv = inner + 2 * sh["mamba_groups"] * cfg["ssm_state_size"]
    return inner, conv, inner + conv + sh["mamba_heads"]


# ---------------------------------------------------------------- weights
def mixer_shapes(cfg, kind, sh):
    """One mixer's parameters as ``{path tuple: shape}``."""
    h = cfg["hidden_size"]
    if kind == "M":
        inner, conv, width = mamba_widths(cfg, sh)
        heads = sh["mamba_heads"]
        return {("in_proj", "kernel"): (h, width),
                ("conv1d_kernel",): (cfg["conv_kernel"], conv),
                ("conv1d_bias",): (conv,),
                ("A_log",): (heads,), ("D",): (heads,),
                ("dt_bias",): (heads,), ("norm_scale",): (inner,),
                ("out_proj", "kernel"): (inner, h)}
    if kind == "*":
        d = cfg["head_dim"]
        return {("q_proj", "kernel"): (h, sh["q_heads"] * d),
                ("k_proj", "kernel"): (h, sh["kv_heads"] * d),
                ("v_proj", "kernel"): (h, sh["kv_heads"] * d),
                ("o_proj", "kernel"): (sh["q_heads"] * d, h)}
    lat, f = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    fs = cfg["moe_shared_expert_intermediate_size"]
    return {("router_kernel",): (h, cfg["n_routed_experts"]),
            ("latent_down", "kernel"): (h, lat),
            ("latent_up", "kernel"): (lat, h),
            ("experts_up_proj",): (sh["experts"], lat, f),
            ("experts_down_proj",): (sh["experts"], f, lat),
            ("shared_up", "kernel"): (h, fs),
            ("shared_down", "kernel"): (fs, h)}


def param_shapes(cfg):
    """The parameter tree as ``{path tuple: shape}`` (the program's flax
    tree has the same names)."""
    h, sh = cfg["hidden_size"], share(cfg)
    shapes = {("embed_tokens", "embedding"): (sh["vocab"], h)}
    for i, kind in enumerate(pattern(cfg)):
        shapes[(f"layers_{i}", "norm_scale")] = (h,)
        for path, shape in mixer_shapes(cfg, kind, sh).items():
            shapes[(f"layers_{i}", "mixer") + path] = shape
    shapes[("final_norm_scale",)] = (h,)
    shapes[("lm_head_kernel",)] = (h, sh["vocab"])
    return shapes


def num_params(cfg):
    return sum(math.prod(s) for s in param_shapes(cfg).values())


def init_leaf(cfg, name, key, shape):
    """One seeded float32 leaf by the rule of its name (module docstring)."""
    if name in ("norm_scale", "final_norm_scale", "D"):
        return jnp.ones(shape, jnp.float32)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        lo, hi = (math.log(cfg["time_step_min"]),
                  math.log(cfg["time_step_max"]))
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                                    lo, hi)),
                         cfg["time_step_floor"])
        return dt + jnp.log(-jnp.expm1(-dt))
    if name in ("conv1d_kernel", "conv1d_bias"):
        bound = cfg["conv_kernel"] ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    return float(cfg.get("initializer_range", 0.02)) * jax.random.normal(
        key, shape, jnp.float32)


def init_params(cfg, seed):
    """Seeded float32 weights, made on the default device in ONE jitted
    call."""
    shapes = param_shapes(cfg)

    def make(key):
        return _nest({
            path: init_leaf(cfg, path[-1] if path[-1] != "kernel"
                            else path[-2], jax.random.fold_in(key, i), shape)
            for i, (path, shape) in enumerate(shapes.items())})

    # any whole number up to a little over 2**31 (and beyond): two words
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(make)(key)


# ----------------------------------------------------------------- mixers
def _dense(x, p, precision):
    return _einsum("si,io->so", x, p["kernel"].astype(jnp.float32), precision)


def _rms_norm(x, scale, eps):
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _causal_conv(x, kernel, bias):
    """``y[t] = sum_k kernel[k] x[t - (K-1) + k] + bias``, zeros before the
    start; x [S, C], kernel [K, C]."""
    width, s = kernel.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), x.dtype), x])
    return sum(kernel[k] * padded[k:k + s] for k in range(width)) + bias


def recurrence(x, dt, a, b, c, precision="float32"):
    """``H_t = exp(dt_t a) H_{t-1} + dt_t x_t (x) B_t``, ``y_t = H_t C_t``,
    one step at a time.  x [S, heads, P], dt [S, heads], a [heads], b and c
    [S, heads, N] -> y [S, heads, P].  The steps run in blocks that the
    backward pass recomputes (the same arithmetic; without it a state of
    heads x P x N floats would stay live for every step)."""
    s, heads, p = x.shape
    n = b.shape[-1]
    block = math.gcd(s, SCAN_BLOCK)

    def step(state, op):
        xt, dtt, bt, ct = op
        state = (state * jnp.exp(dtt * a)[:, None, None]
                 + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :])
        return state, _einsum("hpn,hn->hp", state, ct, precision)

    @jax.checkpoint
    def steps(state, ops):
        return jax.lax.scan(step, state, ops)

    ops = tuple(t.reshape((s // block, block) + t.shape[1:])
                for t in (x, dt, b, c))
    _, y = jax.lax.scan(steps, jnp.zeros((heads, p, n), jnp.float32), ops)
    return y.reshape(s, heads, p)


def mamba_mixer(u, p, cfg, sh, precision="float32"):
    """The Mamba-2 mixer over a share's heads and groups: u [S, H] ->
    [S, H]."""
    s = u.shape[0]
    heads, groups = sh["mamba_heads"], sh["mamba_groups"]
    hd, n = cfg["mamba_head_dim"], cfg["ssm_state_size"]
    inner, conv, _ = mamba_widths(cfg, sh)
    zxbcdt = _dense(u, p["in_proj"], precision)
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:inner + conv],
                  zxbcdt[:, inner + conv:])
    xbc = jax.nn.silu(_causal_conv(xbc, p["conv1d_kernel"], p["conv1d_bias"]))
    x = xbc[:, :inner].reshape(s, heads, hd)
    b = xbc[:, inner:inner + groups * n].reshape(s, groups, n)
    c = xbc[:, inner + groups * n:].reshape(s, groups, n)
    # a group serves heads / groups consecutive heads
    b, c = (jnp.repeat(t, heads // groups, axis=1) for t in (b, c))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(x, dt, -jnp.exp(p["A_log"]), b, c, precision)
    y = (y + p["D"][:, None] * x).reshape(s, inner)
    gated = (y * jax.nn.silu(z)).reshape(s, groups, inner // groups)
    gated = gated * jax.lax.rsqrt(
        jnp.mean(jnp.square(gated), -1, keepdims=True)
        + cfg["layer_norm_epsilon"])
    return _dense(gated.reshape(s, inner) * p["norm_scale"], p["out_proj"],
                  precision)


def attention_mixer(u, p, cfg, sh, precision="float32"):
    """Grouped-query causal attention over a share's heads, a block of query
    rows at a time: u [S, H] -> [S, H]."""
    s = u.shape[0]
    nq, kv, d = sh["q_heads"], sh["kv_heads"], cfg["head_dim"]
    q = _dense(u, p["q_proj"], precision).reshape(s, nq, d)
    k = _dense(u, p["k_proj"], precision).reshape(s, kv, d)
    v = _dense(u, p["v_proj"], precision).reshape(s, kv, d)
    k, v = (jnp.repeat(t, nq // kv, axis=1) for t in (k, v))
    positions = jnp.arange(s)

    @jax.checkpoint
    def rows(block):
        qb, at = block
        scores = _einsum("qnd,knd->nqk", qb, k, precision) / math.sqrt(d)
        causal = at[:, None] >= positions[None, :]
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        return _einsum("nqk,knd->qnd", probs, v, precision)

    # blocks of query rows, one after another (one compiled copy)
    size = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    out = jax.lax.map(rows, (q.reshape(s // size, size, nq, d),
                             positions.reshape(s // size, size)))
    return _dense(out.reshape(s, nq * d), p["o_proj"], precision)


def route(u, p, cfg, precision="float32"):
    """-> (chosen experts [S, k], their weights [S, k]) over ALL experts."""
    scores = jax.nn.sigmoid(_einsum("si,io->so", u, p["router_kernel"],
                                    precision))
    selection_bias = 0.0                 # a buffer held at zero (assumed)
    _, chosen = jax.lax.top_k(scores + selection_bias,
                              cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    return chosen, weights * cfg["routed_scaling_factor"]


def moe_mixer(u, p, cfg, sh, precision="float32", with_shared=True):
    """The latent expert layer over a share's experts: u [S, H] -> ([S, H],
    which held experts each token chose [S, held] bool).  ``with_shared``
    False leaves out what every chip computes alike (the shared expert), so
    that the shares of a whole layer can be added up."""
    chosen, weights = route(u, p, cfg, precision)
    latent = _dense(u, p["latent_down"], precision)

    def expert(routed, held):            # one expert, a dense mask over tokens
        index, w_in, w_out = held
        mine = chosen == index                                    # [S, k]
        w = jnp.sum(jnp.where(mine, weights, 0.0), axis=-1)
        hidden = _relu2(_einsum("sl,lf->sf", latent, w_in, precision))
        return routed + w[:, None] * _einsum(
            "sf,fl->sl", hidden, w_out, precision), jnp.any(mine, axis=-1)

    # the held experts one after another (one compiled copy)
    routed, picked = jax.lax.scan(
        expert, jnp.zeros_like(latent),
        (sh["first_expert"] + jnp.arange(sh["experts"]),
         p["experts_up_proj"].astype(jnp.float32),
         p["experts_down_proj"].astype(jnp.float32)))
    out = _dense(routed, p["latent_up"], precision)
    if with_shared:
        out = out + _dense(_relu2(_dense(u, p["shared_up"], precision)),
                           p["shared_down"], precision)
    return out, picked.T


# ---------------------------------------------------------------- forward
def _layer(x, p, kind, cfg, sh, precision):
    u = _rms_norm(x, p["norm_scale"], cfg["layer_norm_epsilon"])
    if kind == "M":
        return x + mamba_mixer(u, p["mixer"], cfg, sh, precision), None
    if kind == "*":
        return x + attention_mixer(u, p["mixer"], cfg, sh, precision), None
    y, picked = moe_mixer(u, p["mixer"], cfg, sh, precision)
    return x + y, picked


def hidden_states(params, cfg, ids, precision="float32"):
    """The closing norm's output [S, H] for ONE sequence ``ids`` [S], and
    which held experts each token chose in each E layer [layers, S, held]."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    sh = share(cfg)
    picked = []
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"]["embedding"].astype(jnp.float32)[ids]
        for i, kind in enumerate(pattern(cfg)):
            x, mine = _layer(x, params[f"layers_{i}"], kind, cfg, sh,
                             precision)
            if mine is not None:
                picked.append(mine)
        x = _rms_norm(x, params["final_norm_scale"],
                      cfg["layer_norm_epsilon"])
    return x, (jnp.stack(picked) if picked
               else jnp.zeros((0, ids.shape[0], sh["experts"]), bool))


def token_logprobs(params, cfg, ids, labels, precision="float32"):
    """log p(labels[i] | ids[:i+1]) [S] for one sequence, and the chosen
    held experts [layers, S, held]."""
    h, picked = hidden_states(params, cfg, ids, precision)
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


def loss_and_grads(params, cfg, ids, labels, precision="float32"):
    """The loss over a batch [B, S] and its gradient with respect to every
    parameter: one sequence at a time, and the chain rule a block at a time.
    The forward pass keeps each layer's input; the backward pass goes back
    through the head and then layer by layer, recomputing a layer from its
    input (``jax.vjp``).  The same arithmetic as ``jax.grad`` of the mean of
    :func:`token_logprobs` (a test holds them equal); layers of one kind share one
    compiled program, and no more than one layer's intermediates are live.
    -> (loss, gradient tree, the first sequence's per-token log-probs [S],
    the held experts every sequence's tokens chose [B, layers, S, held])."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    n, sh, layers = ids.shape[0], share(cfg), pattern(cfg)
    eps = cfg["layer_norm_epsilon"]

    def layer(kind):
        return functools.partial(_layer, kind=kind, cfg=cfg, sh=sh,
                                 precision=precision)

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
            if chose is not None:
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
        picked.append(jnp.stack(mine) if mine else jnp.zeros(
            (0, ids.shape[1], sh["experts"]), bool))
    return mean, total, first, jnp.stack(picked)


# ------------------------------------------------------------------ counts
def layer_matmul_params(cfg, kind, sh=None):
    """Matmul weights a token passes in one layer of ``kind`` at the shares
    held; for E without its routed experts."""
    sh = sh or share(cfg)
    h = cfg["hidden_size"]
    if kind == "M":
        inner, _, width = mamba_widths(cfg, sh)
        return h * width + inner * h
    if kind == "*":
        return 2 * h * (sh["q_heads"] + sh["kv_heads"]) * cfg["head_dim"]
    return (h * cfg["n_routed_experts"] + 2 * h * cfg["moe_latent_size"]
            + 2 * h * cfg["moe_shared_expert_intermediate_size"])


def routed_expert_params(cfg):
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def scan_flops_per_token(cfg, sh=None):
    """Forward FLOPs a token needs in one M layer's convolution and scan in
    its chunked form at ``chunk_size`` Q: per head the chunk's masked
    product (2 Q P), the state it pushes and the state it reads (2 P N
    each); per group the scores (2 Q N); the convolution's K multiply-adds
    a channel."""
    sh = sh or share(cfg)
    q, p, n = cfg["chunk_size"], cfg["mamba_head_dim"], cfg["ssm_state_size"]
    _, conv, _ = mamba_widths(cfg, sh)
    return (sh["mamba_heads"] * (2 * q * p + 4 * p * n)
            + sh["mamba_groups"] * 2 * q * n + 2 * cfg["conv_kernel"] * conv)


def flops_per_token(cfg, seq_len, slots_per_token):
    """Forward + backward FLOPs one trained token needs at the shares held:
    ``6 x`` every matmul weight a token passes, by layer kind (a routed
    expert per slot: ``slots_per_token`` is the mean number of slots a token
    sends the experts held here in one E layer), plus the head, plus three
    times the scan's forward count a Mamba layer and the attention scores
    and values ``12 heads D S`` an attention layer (the customary
    full-square count).  Recomputed operations do not count."""
    sh, layers = share(cfg), pattern(cfg)
    matmul = (sum(layer_matmul_params(cfg, kind, sh) for kind in layers)
              + layers.count("E") * slots_per_token
              * routed_expert_params(cfg)
              + cfg["hidden_size"] * sh["vocab"])
    return (6 * matmul
            + 3 * layers.count("M") * scan_flops_per_token(cfg, sh)
            + 12 * layers.count("*") * sh["q_heads"] * cfg["head_dim"]
            * seq_len)
