"""Plain BERT masked-LM pre-training in float32 ``jax.numpy``: the
yardstick the ``bert_base`` cells are compared with.

Devlin et al. 2018 (arXiv:1810.04805) as google-research/bert's
``modeling.py`` and ``run_pretraining.py`` compute it: word + position +
segment embeddings, layer norm, dropout; per layer self-attention
(dropout(softmax(QK^T / sqrt(d_head))) V over all heads), output projection,
dropout, residual, layer norm, a GELU (tanh form) feed-forward, dropout,
residual, layer norm; the masked positions gathered before the head
(dense, GELU, layer norm, decode with the tied word embeddings plus a
bias); the loss is the mean cross-entropy over the masked positions.
Optimizer: Adam with float32 moments.

It imports nothing of the program and takes nothing the program made.
Dropout masks are ``jax.random.bernoulli`` draws on the key chain the
configuration states (``assumed.dropout_stream``); jax's generator is
not the program's.  Every matrix product runs at ``Precision.HIGHEST``;
each layer is rematerialised so that batch 32 at 512 tokens fits one
chip.  ``precision="fp8"`` is the control: where the configuration holds
weights-at-use and activations in bfloat16, the control holds them in
float8's precision (e4m3's three mantissa bits): every matmul's operands and every
layer's output rounded in the forward pass, gradients straight through.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST


# ---- parameters -------------------------------------------------------------
def param_shapes(model: dict) -> dict:
    h, i = model["hidden_size"], model["intermediate_size"]
    shapes = {
        "emb.word": (model["vocab_size"], h),
        "emb.pos": (model["max_position_embeddings"], h),
        "emb.type": (model["type_vocab_size"], h),
        "emb.ln.gamma": (h,), "emb.ln.beta": (h,),
    }
    for n in range(model["num_hidden_layers"]):
        for name, (a, b) in (("q", (h, h)), ("k", (h, h)), ("v", (h, h)),
                             ("o", (h, h)), ("ffn1", (h, i)),
                             ("ffn2", (i, h))):
            shapes[f"l{n}.{name}.w"] = (a, b)
            shapes[f"l{n}.{name}.b"] = (b,)
        for ln in ("ln1", "ln2"):
            shapes[f"l{n}.{ln}.gamma"] = (h,)
            shapes[f"l{n}.{ln}.beta"] = (h,)
    shapes.update({"mlm.t.w": (h, h), "mlm.t.b": (h,),
                   "mlm.ln.gamma": (h,), "mlm.ln.beta": (h,),
                   "mlm.bias": (model["vocab_size"],)})
    return shapes


def init_weights(config: dict, seed: int) -> dict:
    """Every parameter from ``seed`` in one jitted call, on the device,
    in float32: kernels and embeddings truncated-normal within two
    sigmas times ``initializer_range``, biases 0, layer norms (1, 0)."""
    shapes = param_shapes(config["model"])
    std = config["model"]["initializer_range"]

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            if name.endswith(".gamma"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif len(shape) == 1:
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                out[name] = std * jax.random.truncated_normal(
                    jax.random.fold_in(key, i), -2.0, 2.0, shape, jnp.float32)
        return out

    return make(jax.random.key(seed % (2 ** 31)))


# ---- the control's rounding -------------------------------------------------
@jax.custom_jvp
def _fp8(x):
    """Round to float8 e4m3's three mantissa bits, by integer arithmetic on
    the float32's own bits (half away from zero).  The exponent keeps
    float32's range: a real float8 with one scale a tensor flushed quiet
    channels to a constant, and the normalisations after them then blew the
    gradient up to inf; the chip's own float8 convert gave NaN at the cells'
    sizes (my chip runs, PR 26).  So this control is kinder than float8."""
    bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    bits = (bits + jnp.uint32(1 << 19)) & jnp.uint32(0xFFF00000)
    return lax.bitcast_convert_type(bits, jnp.float32).astype(x.dtype)


@_fp8.defjvp
def _fp8_jvp(primals, tangents):                 # straight through
    return _fp8(primals[0]), tangents[0]


_ROUND = {"f32": lambda x: x, "fp8": _fp8}


# ---- forward ----------------------------------------------------------------
def _dense(p, name, x, q):
    return q(jnp.einsum("...i,io->...o", q(x), q(p[f"{name}.w"]),
                        precision=_HI) + p[f"{name}.b"])


def _layer_norm(p, name, x, eps, q):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return q((x - mean) * lax.rsqrt(var + eps) * p[f"{name}.gamma"]
             + p[f"{name}.beta"])


def _dropout(x, rate, key):
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _layer(p, x, key_mask, key, *, n, model, q):
    heads, eps = model["num_attention_heads"], model["layer_norm_eps"]
    rate = model["hidden_dropout_prob"]
    b, t, h = x.shape
    split = lambda a: a.reshape(b, t, heads, h // heads).transpose(0, 2, 1, 3)
    qh, kh, vh = (split(_dense(p, f"l{n}.{name}", x, q))
                  for name in ("q", "k", "v"))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q(qh), q(kh),
                        precision=_HI) / math.sqrt(h // heads)
    scores = jnp.where(key_mask[:, None, None, :] > 0, scores, -1e9)
    probs = q(jax.nn.softmax(q(scores), axis=-1))
    if model["attention_probs_dropout_prob"]:
        probs = _dropout(probs, model["attention_probs_dropout_prob"],
                         jax.random.fold_in(key, 3))
    ctx = q(jnp.einsum("bhqk,bhkd->bhqd", probs, q(vh), precision=_HI))
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, t, h)
    attn = _dropout(_dense(p, f"l{n}.o", ctx, q), rate, key)
    x = _layer_norm(p, f"l{n}.ln1", x + attn, eps, q)
    ffn = _dense(p, f"l{n}.ffn2", q(_gelu(_dense(p, f"l{n}.ffn1", x, q))), q)
    ffn = _dropout(ffn, rate, jax.random.fold_in(key, 7))
    return _layer_norm(p, f"l{n}.ln2", x + ffn, eps, q)


def loss_fn(params, batch, row_weights, key, *, model, decoded, precision):
    """Masked-LM loss of one batch.  ``row_weights`` is all ones in a
    sound run; a planted fault zeroes half of it."""
    q, eps = _ROUND[precision], model["layer_norm_eps"]
    ids = batch["input_ids"]
    t = ids.shape[1]
    x = params["emb.word"][ids] + params["emb.pos"][None, :t] \
        + params["emb.type"][0]
    key = jax.random.fold_in(key, 0)
    x = _dropout(_layer_norm(params, "emb.ln", x, eps, q),
                 model["hidden_dropout_prob"], key)
    for n in range(model["num_hidden_layers"]):
        layer = functools.partial(_layer, n=n, model=model, q=q)
        x = jax.checkpoint(layer)(
            {k: v for k, v in params.items() if k.startswith(f"l{n}.")},
            x, batch["attention_mask"], jax.random.fold_in(key, n + 1))
    weights = batch["label_weights"] * row_weights[:, None]
    # the masked positions, at most ``decoded`` a row, lower ones first
    _, where = lax.top_k(batch["label_weights"], decoded)
    x = jnp.take_along_axis(x, where[..., None], axis=1)
    labels = jnp.take_along_axis(batch["labels"], where, axis=1)
    weights = jnp.take_along_axis(weights, where, axis=1)
    x = _layer_norm(params, "mlm.ln",
                    q(_gelu(_dense(params, "mlm.t", x, q))), eps, q)
    logits = q(jnp.einsum("bth,vh->btv", x, q(params["emb.word"]),
                          precision=_HI) + params["mlm.bias"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.sum(picked * weights) / jnp.maximum(jnp.sum(weights), 1.0)


def _norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


def first_steps(config: dict, mix: dict, weights: dict, batches: list, *,
                seed: int, precision: str = "f32", row_weights=None) -> dict:
    """Follow the first ``len(batches)`` training steps from ``weights``.

    ``seed`` starts the dropout key chain; the mix gives
    ``max_predictions``.  Returns each step's loss, the norm of every
    leaf's first gradient, and of every leaf's change over the steps."""
    opt = config["optimizer"]
    lr, b1, b2, eps = (opt["learning_rate"], opt["beta1"], opt["beta2"],
                       opt["epsilon"])
    grad = jax.value_and_grad(functools.partial(
        loss_fn, model=config["model"], decoded=mix["max_predictions"],
        precision=precision))

    @jax.jit
    def step(params, mu, nu, count, batch, rows, key):
        loss, g = grad(params, batch, rows, key)
        count = count + 1
        mu = jax.tree_util.tree_map(lambda m, d: b1 * m + (1 - b1) * d, mu, g)
        nu = jax.tree_util.tree_map(
            lambda v, d: b2 * v + (1 - b2) * d * d, nu, g)
        c1, c2 = 1 - b1 ** count, 1 - b2 ** count
        params = jax.tree_util.tree_map(
            lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
            params, mu, nu)
        return params, mu, nu, count, loss, _norms(g)

    params = weights
    mu = jax.tree_util.tree_map(jnp.zeros_like, weights)
    nu = jax.tree_util.tree_map(jnp.zeros_like, weights)
    count = jnp.zeros((), jnp.float32)
    key = jax.random.key((seed + 31) % (2 ** 31), impl="rbg")
    losses, grad_norms = [], None
    for batch in batches:
        n_rows = batch["input_ids"].shape[0]
        rows = (jnp.ones((n_rows,), jnp.float32) if row_weights is None
                else jnp.asarray(row_weights))
        key, sub = jax.random.split(key)
        params, mu, nu, count, loss, norms = step(
            params, mu, nu, count,
            {k: jnp.asarray(v) for k, v in batch.items()}, rows, sub)
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = jax.device_get(norms)
    delta = jax.device_get(jax.jit(_norms)(jax.tree_util.tree_map(
        lambda a, b: a - b, params, weights)))
    return {"losses": losses,
            "grad_norms": {k: float(v) for k, v in grad_norms.items()},
            "delta_norms": {k: float(v) for k, v in delta.items()}}
