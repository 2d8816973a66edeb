"""Plain reference for Ouro (ByteDance, "Scaling Latent Reasoning via Looped
Language Models", 2025-10; ``ByteDance/Ouro-2.6B`` ``config.json``): a looped
language model.  One stack of L blocks is run ``total_ut_steps`` = T times
on the SAME weights, a loss is read after every pass, and a learned gate says
after which pass to stop.  Forward pass, per-exit per-token
log-probabilities, the exit distribution, the loss and its gradient in
straightforward ``jax.numpy``.

The equations, for one sequence ``ids`` [S] with labels ``y`` [S].  Lines
marked *assumed* are not stated by the ``config.json``; each is also in the
configuration file's ``assumed``.

* Block, sandwich-normed (*assumed*, from the paper's description; the four
  norms carry the names of the published modelling code):
  ``a = Attn(RMS1(x))``, ``x <- x + RMS2(a)``, ``u = RMS3(x)``,
  ``m = W_down(silu(W_gate u) * (W_up u))``, ``x <- x + RMS4(m)``.
  ``RMS(x) = x / sqrt(mean(x^2) + eps) * scale``.  ``Attn``: q, k, v
  projections without bias, ``num_key_value_heads`` = heads (plain
  multi-head), rotary embedding over the whole head (theta ``rope_theta``,
  half-split convention: dimension i is paired with i + D/2), causal softmax
  attention at scale ``1/sqrt(D)``, output projection without bias.
* Loop: ``h^0 = E[ids]``; for ``t = 1..T``: ``h^t = RMS_f(Stack(h^{t-1}))``
  with the same L blocks, weights and positions every pass.  The final norm
  closes every pass; its output is both the exit's hidden state and the next
  pass's input (*assumed*, as the published modelling code does).  Exit
  logits ``z^t = h^t W_out``: one untied head for all passes.
* Gate: ``lambda^t_i = sigmoid(w_g . h^t_i + b_g)`` per token for ``t < T``,
  ``lambda^T_i = 1``; ``p^t_i = lambda^t_i prod_{j<t} (1 - lambda^j_i)``, so
  that ``sum_t p^t_i = 1``.
* Loss (the paper's stage-I objective; ``beta`` = 0.1 *assumed*):
  ``mean_i [ sum_t p^t_i CE(z^t_i, y_i) - beta H(p_i) ]``,
  ``H(p) = -sum_t p^t log p^t``.  Pre-training takes no early exit;
  ``early_exit_threshold`` is a serving key and unused here.
* Weights (*assumed*): seeded normal(0, 0.02) matrices, embeddings and gate
  vector, unit norm scales, zero gate bias.  The gate is seeded like every
  matrix and not zero: from a zero gate every ``lambda`` is exactly 1/2 in
  any precision, and a comparison of the exit distribution would compare
  nothing.

The depth that is run is ``layers_held`` where the configuration file has
that key (the chip's share of a pipeline over ``num_hidden_layers``), else
``num_hidden_layers``.

No kernels, no cache, no chunking of the mathematics; float32 with
``jax.default_matmul_precision("highest")`` on every matmul of the forward,
recomputed and backward passes, unless a lower ``precision`` is asked for:
those exist only as *controls* of the benchmark's output check (``"fp8"``,
``"bfloat16"``: every matmul's inputs, and in the backward pass the incoming
gradient too, rounded to that type; the rounded matmul, the clip and Adam's
first step are ``gpt_neox_ref``'s).  Imports nothing from the program under
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

NORMS = ("input_layernorm", "input_layernorm_2", "post_attention_layernorm",
         "post_attention_layernorm_2")


def depth(cfg):
    """Layers in the stack that is run."""
    return int(cfg.get("layers_held", cfg["num_hidden_layers"]))


def passes(cfg):
    return int(cfg["total_ut_steps"])


def beta(cfg):
    return float(cfg.get("exit_entropy_beta", 0.1))


# ---------------------------------------------------------------- weights
def param_shapes(cfg):
    """The parameter tree as ``{path tuple: shape}``; names follow the
    published checkpoint's modules (and so the program's flax tree)."""
    h, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    n, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    shapes = {("embed_tokens", "embedding"): (v, h)}
    for i in range(depth(cfg)):
        p = f"layers_{i}"
        for norm in NORMS:
            shapes[(p, norm, "scale")] = (h,)
        shapes[(p, "attention", "q_proj", "kernel")] = (h, n * d)
        shapes[(p, "attention", "k_proj", "kernel")] = (h, kv * d)
        shapes[(p, "attention", "v_proj", "kernel")] = (h, kv * d)
        shapes[(p, "attention", "o_proj", "kernel")] = (n * d, h)
        shapes[(p, "mlp", "gate_proj", "kernel")] = (h, f)
        shapes[(p, "mlp", "up_proj", "kernel")] = (h, f)
        shapes[(p, "mlp", "down_proj", "kernel")] = (f, h)
    shapes[("final_norm", "scale")] = (h,)
    shapes[("lm_head", "kernel")] = (h, v)
    shapes[("exit_gate", "kernel")] = (h, 1)
    shapes[("exit_gate", "bias")] = (1,)
    return shapes


def num_params(cfg, with_input_embedding=True):
    """Every weight once, however often a step uses it."""
    n = sum(math.prod(s) for s in param_shapes(cfg).values())
    if not with_input_embedding:
        n -= cfg["vocab_size"] * cfg["hidden_size"]
    return n


def layer_params(cfg):
    """Matmul weights of one block (its norm scales multiply no matrix)."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    n, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    return 2 * h * n * d + 2 * h * kv * d + 3 * h * f


def flops_per_token(cfg, seq_len):
    """Forward + backward FLOPs one trained token needs: ``6 x`` every
    matmul weight a token passes, each as often as it passes it (T passes of
    L blocks, T applications of the head, T - 1 of the gate), plus the
    attention scores and values ``12 H S`` per block application (the
    customary full-square count).  Recomputed operations do not count."""
    t, layers = passes(cfg), depth(cfg)
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    matmul = t * layers * layer_params(cfg) + t * h * v + (t - 1) * h
    return 6 * matmul + 12 * t * layers * h * seq_len


def init_params(cfg, seed):
    """Seeded float32 weights, made on the default device in ONE jitted
    call: normal(0, initializer_range or 0.02) matrices, embeddings and gate
    vector, unit norm scales, a zero gate bias."""
    shapes = param_shapes(cfg)
    std = float(cfg.get("initializer_range", 0.02))

    def make(key):
        flat = {}
        for i, (path, shape) in enumerate(shapes.items()):
            if path[-1] == "scale":
                flat[path] = jnp.ones(shape, jnp.float32)
            elif path[-1] == "bias":
                flat[path] = jnp.zeros(shape, jnp.float32)
            else:
                flat[path] = std * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
        return _nest(flat)

    # any whole number up to a little over 2**31 (and beyond): two words
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(make)(key)


# ---------------------------------------------------------------- forward
def _dense(x, p, precision):
    return _einsum("si,io->so", x, p["kernel"].astype(jnp.float32), precision)


def _rms_norm(x, p, eps):
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
            * p["scale"].astype(jnp.float32))


def _rotary(x, positions, base):
    """Rotary embedding over the whole head, half-split convention.
    x: [S, N, D]; positions: [S]."""
    d = x.shape[-1]
    inv_freq = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None]   # [S, D/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(x, p, cfg, positions, precision):
    s = x.shape[0]
    n, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    q = _dense(x, p["q_proj"], precision).reshape(s, n, d)
    k = _dense(x, p["k_proj"], precision).reshape(s, kv, d)
    v = _dense(x, p["v_proj"], precision).reshape(s, kv, d)
    q = _rotary(q, positions, cfg["rope_theta"])
    k = _rotary(k, positions, cfg["rope_theta"])
    if kv != n:       # grouped queries: each k/v head serves n / kv of them
        k, v = (jnp.repeat(t, n // kv, axis=1) for t in (k, v))
    scores = _einsum("qnd,knd->nqk", q, k, precision) / math.sqrt(d)
    causal = positions[:, None] >= positions[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    out = _einsum("nqk,knd->qnd", probs, v, precision).reshape(s, n * d)
    return _dense(out, p["o_proj"], precision)


def _layer(x, p, cfg, positions, precision):
    eps = cfg["rms_norm_eps"]
    a = _attention(_rms_norm(x, p["input_layernorm"], eps), p["attention"],
                   cfg, positions, precision)
    x = x + _rms_norm(a, p["input_layernorm_2"], eps)
    u = _rms_norm(x, p["post_attention_layernorm"], eps)
    m = _dense(jax.nn.silu(_dense(u, p["mlp"]["gate_proj"], precision))
               * _dense(u, p["mlp"]["up_proj"], precision),
               p["mlp"]["down_proj"], precision)
    return x + _rms_norm(m, p["post_attention_layernorm_2"], eps)


def exit_states(params, cfg, ids, precision="float32", remat=False):
    """``h^1 .. h^T`` [T, S, H] (float32) for ONE sequence ``ids`` [S]:
    the closing norm's output after each pass.  ``remat`` recomputes each
    layer application in the backward pass (the same arithmetic; without it
    the float32 scores of all T x L applications stay live)."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    positions = jnp.arange(ids.shape[0])
    layer = functools.partial(_layer, cfg=cfg, positions=positions,
                              precision=precision)
    if remat:
        layer = jax.checkpoint(layer)
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"]["embedding"].astype(jnp.float32)[ids]
        exits = []
        for _ in range(passes(cfg)):
            for i in range(depth(cfg)):
                x = layer(x, params[f"layers_{i}"])
            x = _rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
            exits.append(x)
        return jnp.stack(exits)


def exit_logits(params, cfg, ids, precision="float32"):
    """Every exit's logits [T, S, V] for one sequence."""
    hs = exit_states(params, cfg, ids, precision)
    with jax.default_matmul_precision("highest"):
        return jnp.stack([_dense(h, params["lm_head"], precision)
                          for h in hs])


def exit_distribution(params, hs):
    """``p^t_i`` [T, S] from the exits' hidden states [T, S, H]: the gate
    after every pass but the last, which takes what is left."""
    g = params["exit_gate"]
    with jax.default_matmul_precision("highest"):
        lam = jax.nn.sigmoid(
            jnp.einsum("tsh,ho->ts", hs[:-1], g["kernel"].astype(jnp.float32))
            + g["bias"].astype(jnp.float32))
    lam = jnp.concatenate([lam, jnp.ones_like(hs[:1, :, 0])])
    stay = jnp.cumprod(1.0 - lam, axis=0)            # prod_{j<=t} (1 - lam^j)
    return lam * jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])


def entropy(p):
    """``H(p_i)`` [S] of an exit distribution [T, S]; ``0 log 0 = 0``."""
    return -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)),
                              0.0), axis=0)


def exits(params, cfg, ids, labels, precision="float32", remat=False):
    """For one sequence: log p(labels[i] | ids[:i+1]) at every exit [T, S],
    and the exit distribution [T, S]."""
    hs = exit_states(params, cfg, ids, precision, remat)

    def logprob(h):
        with jax.default_matmul_precision("highest"):
            lg = _dense(h, params["lm_head"], precision)
        return (jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
                - jax.nn.logsumexp(lg, axis=-1))

    if remat:        # one exit's [S, V] logits live at a time
        logprob = jax.checkpoint(logprob)
    return jnp.stack([logprob(h) for h in hs]), exit_distribution(params, hs)


def token_loss(lp, p, beta_):
    """Per-token loss [S]: the exits' cross entropies weighted by the exit
    distribution, less ``beta`` times its entropy."""
    return jnp.sum(p * -lp, axis=0) - beta_ * entropy(p)


def loss(params, cfg, ids, labels, precision="float32"):
    """The loss over a batch ``ids``/``labels`` [B, S], one sequence at a
    time -> (loss, [(log-probs [T, S], p [T, S]) per sequence])."""
    fn = jax.jit(lambda p, x, y: exits(p, cfg, x, y, precision))
    rows = [fn(params, ids[b], labels[b]) for b in range(ids.shape[0])]
    per_token = jnp.stack([token_loss(lp, p, beta(cfg)) for lp, p in rows])
    return jnp.mean(per_token), rows


# --------------------------------------------------------------- training
def loss_and_grads(params, cfg, ids, labels, precision="float32"):
    """The loss over a batch [B, S] and its gradient with respect to every
    parameter (each weight's gradient is the sum over its T uses), one
    sequence at a time with every layer application and every exit's logits
    recomputed in the backward pass, summed.  -> (loss, gradient tree, the
    first sequence's per-exit log-probs [T, S], its exit distribution)."""
    n = ids.shape[0]

    def one(p, x, y):
        lp, dist = exits(p, cfg, x, y, precision, remat=True)
        return jnp.mean(token_loss(lp, dist, beta(cfg))) / n, (lp, dist)

    @functools.partial(jax.jit, donate_argnums=1)
    def add(p, total, x, y):
        (part, aux), g = jax.value_and_grad(one, has_aux=True)(p, x, y)
        return jax.tree_util.tree_map(jnp.add, total, g), part, aux

    total = jax.tree_util.tree_map(jnp.zeros_like, params)
    mean, first = 0.0, None
    for b in range(n):
        total, part, aux = add(params, total, ids[b], labels[b])
        mean = mean + part
        first = aux if first is None else first
    return mean, total, first[0], first[1]
