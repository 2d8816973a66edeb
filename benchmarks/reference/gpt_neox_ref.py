"""Plain reference for GPT-NeoX / Pythia: forward pass, per-token
log-probabilities and loss in straightforward ``jax.numpy``.

Written from the GPT-NeoX description (Black et al. 2022, and the
EleutherAI/pythia ``config.json`` files): token embedding; per layer a
LayerNorm, a fused QKV projection laid out per head as ``[q | k | v]``,
rotary embedding on the first ``rotary_pct`` of each head (half-rotation
form), causal softmax attention, output projection; a second LayerNorm
feeding a 4x GELU MLP; with ``use_parallel_residual`` both branches read
the layer's input and are added to it together.  A final LayerNorm and an
untied output projection give the logits.

Departure from the Hugging Face port, noted: the GELU is the tanh
approximation, as GPT-NeoX's fused ``bias_gelu`` computes it in training
(the HF ``config.json`` says ``"gelu"``, which HF evaluates with erf).

Training is the same made plain: the gradient of the mean next-token cross
entropy by ``jax.grad`` (:func:`loss_and_grads`), clipped by its global norm,
and one step of Adam as Kingma & Ba wrote it (:func:`adam_first_step`).

No kernels, no cache, no batching tricks; float32 with
``jax.default_matmul_precision("highest")`` unless a lower ``precision`` is
asked for -- those exist only as *controls* for the benchmark's output
check: ``"bfloat16"`` rounds every matmul's inputs to bf16, ``"fp8"`` to
float8_e4m3 after a per-tensor scale, in the forward pass and (the
gradients flowing back too) in the backward pass.  Imports nothing from the
program under test; weights come from :func:`init_params`, i.e. from the
seed.
"""

import functools
import math

import jax
import jax.numpy as jnp

PRECISIONS = ("float32", "bfloat16", "fp8")


# ---------------------------------------------------------------- weights
def param_shapes(cfg):
    """The parameter tree as ``{path tuple: shape}``.  Names follow the
    GPT-NeoX checkpoint layout (and so the program's flax tree)."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    f = cfg["intermediate_size"]
    shapes = {("embed_in", "embedding"): (v, h)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers_{i}"
        for ln in ("input_layernorm", "post_attention_layernorm"):
            shapes[(p, ln, "scale")] = (h,)
            shapes[(p, ln, "bias")] = (h,)
        shapes[(p, "attention", "query_key_value", "kernel")] = (h, 3 * h)
        shapes[(p, "attention", "query_key_value", "bias")] = (3 * h,)
        shapes[(p, "attention", "dense", "kernel")] = (h, h)
        shapes[(p, "attention", "dense", "bias")] = (h,)
        shapes[(p, "mlp", "dense_h_to_4h", "kernel")] = (h, f)
        shapes[(p, "mlp", "dense_h_to_4h", "bias")] = (f,)
        shapes[(p, "mlp", "dense_4h_to_h", "kernel")] = (f, h)
        shapes[(p, "mlp", "dense_4h_to_h", "bias")] = (h,)
    shapes[("final_layer_norm", "scale")] = (h,)
    shapes[("final_layer_norm", "bias")] = (h,)
    shapes[("embed_out", "kernel")] = (h, v)
    return shapes


def num_params(cfg, with_input_embedding=True):
    n = sum(math.prod(s) for s in param_shapes(cfg).values())
    if not with_input_embedding:
        n -= cfg["vocab_size"] * cfg["hidden_size"]
    return n


def _nest(flat):
    tree = {}
    for path, leaf in flat.items():
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = leaf
    return tree


def init_params(cfg, seed):
    """Seeded float32 weights, made on the default device in ONE jitted
    call: normal(0, initializer_range) matrices and embeddings, unit
    LayerNorm scales, zero biases -- Pythia's published init scale."""
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
def _quantize(x, precision):
    """Round a matmul input as the lower-precision controls would."""
    if precision == "float32":
        return x
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    # fp8 e4m3 (max 448) with one scale per tensor
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _einsum(spec, a, b, precision):
    """``jnp.einsum`` in float32, or as a control: both inputs rounded to the
    lower precision, and in the backward pass the incoming gradient too."""
    if precision == "float32":
        return jnp.einsum(spec, a, b)
    return _rounded_einsum(spec, precision, a, b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _rounded_einsum(spec, precision, a, b):
    return jnp.einsum(spec, _quantize(a, precision), _quantize(b, precision))


def _rounded_einsum_fwd(spec, precision, a, b):
    return _rounded_einsum(spec, precision, a, b), (a, b)


def _rounded_einsum_bwd(spec, precision, inputs, dy):
    qa, qb = (_quantize(x, precision) for x in inputs)
    with jax.default_matmul_precision("highest"):
        _, vjp = jax.vjp(functools.partial(jnp.einsum, spec), qa, qb)
        return vjp(_quantize(dy, precision))


_rounded_einsum.defvjp(_rounded_einsum_fwd, _rounded_einsum_bwd)


def _dense(x, p, precision):
    y = _einsum("si,io->so", x, p["kernel"].astype(jnp.float32), precision)
    if "bias" in p:
        y = y + p["bias"].astype(jnp.float32)
    return y


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps)
            * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32))


def _rotary(x, positions, rot_dim, base):
    """Half-rotation rotary embedding on the first ``rot_dim`` dims of each
    head.  x: [S, N, D]; positions: [S]."""
    inv_freq = 1.0 / (base ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32)
                               / rot_dim))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None]  # [S, r/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rot, rest = x[..., :rot_dim], x[..., rot_dim:]
    x1, x2 = rot[..., :rot_dim // 2], rot[..., rot_dim // 2:]
    rotated = jnp.concatenate([-x2, x1], -1)
    return jnp.concatenate([rot * cos + rotated * sin, rest], -1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _attention(x, p, cfg, positions, precision):
    s, h = x.shape
    n = cfg["num_attention_heads"]
    d = h // n
    qkv = _dense(x, p["query_key_value"], precision).reshape(s, n, 3 * d)
    q, k, v = qkv[..., :d], qkv[..., d:2 * d], qkv[..., 2 * d:]
    rot_dim = int(d * cfg["rotary_pct"])
    if rot_dim:
        q = _rotary(q, positions, rot_dim, cfg["rotary_emb_base"])
        k = _rotary(k, positions, rot_dim, cfg["rotary_emb_base"])
    scores = _einsum("qnd,knd->nqk", q, k, precision) / math.sqrt(d)
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _einsum("nqk,knd->qnd", probs, v, precision).reshape(s, h)
    return _dense(out, p["dense"], precision)


def _layer(x, p, cfg, positions, precision):
    eps = cfg["layer_norm_eps"]
    attn = _attention(_layer_norm(x, p["input_layernorm"], eps),
                      p["attention"], cfg, positions, precision)
    if not cfg["use_parallel_residual"]:
        x = x + attn
    mlp_in = _layer_norm(x, p["post_attention_layernorm"], eps)
    mlp = _dense(_gelu_tanh(_dense(mlp_in, p["mlp"]["dense_h_to_4h"],
                                   precision)),
                 p["mlp"]["dense_4h_to_h"], precision)
    return x + attn + mlp if cfg["use_parallel_residual"] else x + mlp


def hidden_states(params, cfg, ids, precision="float32", remat=False):
    """Final-LayerNorm output [S, H] (float32) for ONE sequence ``ids`` [S].
    ``remat`` recomputes each layer in the backward pass (the same
    arithmetic; without it the float32 scores of every layer stay live)."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    positions = jnp.arange(ids.shape[0])
    layer = functools.partial(_layer, cfg=cfg, positions=positions,
                              precision=precision)
    if remat:
        layer = jax.checkpoint(layer)
    with jax.default_matmul_precision("highest"):
        x = params["embed_in"]["embedding"].astype(jnp.float32)[ids]
        for i in range(cfg["num_hidden_layers"]):
            x = layer(x, params[f"layers_{i}"])
        return _layer_norm(x, params["final_layer_norm"],
                           cfg["layer_norm_eps"])


def logits(params, cfg, ids, precision="float32", remat=False):
    """Logits [S, V] for one sequence."""
    x = hidden_states(params, cfg, ids, precision, remat)
    with jax.default_matmul_precision("highest"):
        return _dense(x, params["embed_out"], precision)


def token_logprobs(params, cfg, ids, labels, precision="float32",
                   remat=False):
    """log p(labels[t] | ids[:t+1]) for one sequence -> [S] float32."""
    lg = logits(params, cfg, ids, precision, remat)
    return (jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
            - jax.nn.logsumexp(lg, axis=-1))


def loss(params, cfg, ids, labels, precision="float32"):
    """Mean next-token cross entropy over a batch ``ids``/``labels`` [B, S],
    one sequence at a time (the reference holds one [S, V] logits buffer)."""
    fn = jax.jit(lambda p, x, y: token_logprobs(p, cfg, x, y, precision))
    rows = [fn(params, ids[b], labels[b]) for b in range(ids.shape[0])]
    return -jnp.mean(jnp.stack(rows)), rows


# --------------------------------------------------------------- training
def loss_and_grads(params, cfg, ids, labels, precision="float32"):
    """Mean next-token cross entropy over a batch [B, S] and its gradient
    with respect to every parameter, one sequence at a time, summed.
    -> (loss, gradient tree, the first sequence's per-token log-probs)."""
    n = ids.shape[0]

    def one(p, x, y):
        lp = token_logprobs(p, cfg, x, y, precision, remat=True)
        return -jnp.mean(lp) / n, lp

    @functools.partial(jax.jit, donate_argnums=1)
    def add(p, total, x, y):
        (part, lp), g = jax.value_and_grad(one, has_aux=True)(p, x, y)
        return jax.tree_util.tree_map(jnp.add, total, g), part, lp

    total = jax.tree_util.tree_map(jnp.zeros_like, params)
    mean, first = 0.0, None
    for b in range(n):
        total, part, lp = add(params, total, ids[b], labels[b])
        mean = mean + part
        first = lp if first is None else first
    return mean, total, first


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree_util.tree_leaves(tree)))


def clip_scale(norm, max_norm):
    """What a gradient of global norm ``norm`` is multiplied by so that its
    norm is at most ``max_norm``."""
    return jnp.minimum(1.0, max_norm / norm)


def adam_first_step(params, grads, lr, b1=0.9, b2=0.999, eps=1e-8):
    """Parameters after the first step of Adam (Kingma & Ba 2015, algorithm
    1, with bias correction) from zero moments, in float32."""
    def step(p, g):
        m, v = (1 - b1) * g, (1 - b2) * g * g
        m_hat, v_hat = m / (1 - b1), v / (1 - b2)
        return p - lr * m_hat / (jnp.sqrt(v_hat) + eps)

    return jax.tree_util.tree_map(step, params, grads)
