"""Plain ResNet-50 v1 training in float32 ``jax.numpy``: the yardstick
the ``resnet50`` cells are compared with.

He et al. 2015 (arXiv:1512.03385), Table 1, 50-layer column: a 7x7/2
stem, 3x3/2 max pool, [3, 4, 6, 3] bottlenecks (1x1 reduce, 3x3, 1x1
expand, projection shortcut in each stage's first block, the stride on
the first 1x1 as in v1), global average pool, a dense softmax head.
Batch normalisation after every convolution, in training mode (batch
statistics, biased variance).  Loss: mean cross-entropy plus
``0.5 * l2 * |w|^2`` over every parameter but the head's bias.
Optimizer: SGD with Nesterov momentum.

It imports nothing of the program and takes nothing the program made.
Every matrix product runs at ``Precision.HIGHEST``; each bottleneck is
rematerialised so that batch 128 at 224x224 fits one chip.  ``precision
="fp8"`` is the control, the step a later PR would be tempted to take
below the configuration's bfloat16: where the configuration holds
weights-at-use and activations in bfloat16, the control holds them in
float8's precision (e4m3's three mantissa bits): every convolution's operands and every
layer's output rounded in the forward pass, gradients straight through.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST
_DIMS = ("NHWC", "HWIO", "NHWC")


# ---- parameters -------------------------------------------------------------
def _blocks(model: dict):
    """(name, c_in, (f1, f2, f3), stride, project) of every bottleneck."""
    c_in = model["stem_width"]
    for stage, (n, widths) in enumerate(zip(model["blocks"],
                                            model["widths"])):
        for b in range(n):
            stride = 2 if (b == 0 and stage > 0) else 1
            yield f"res{stage + 2}_{b}", c_in, tuple(widths), stride, b == 0
            c_in = widths[2]


def param_shapes(model: dict) -> dict:
    shapes = {}

    def conv_bn(name, kh, c_in, c_out):
        shapes[f"{name}.w"] = (kh, kh, c_in, c_out)
        shapes[f"{name}.gamma"] = (c_out,)
        shapes[f"{name}.beta"] = (c_out,)

    conv_bn("stem", 7, model["channels"], model["stem_width"])
    c_last = model["stem_width"]
    for name, c_in, (f1, f2, f3), _, project in _blocks(model):
        conv_bn(f"{name}.a", 1, c_in, f1)
        conv_bn(f"{name}.b", 3, f1, f2)
        conv_bn(f"{name}.c", 1, f2, f3)
        if project:
            conv_bn(f"{name}.proj", 1, c_in, f3)
        c_last = f3
    shapes["fc.w"] = (c_last, model["classes"])
    shapes["fc.b"] = (model["classes"],)
    return shapes


def init_weights(config: dict, seed: int) -> dict:
    """Every parameter from ``seed`` in one jitted call, on the device,
    in float32.  He-normal convolutions, gamma 1 but ``last_gamma`` on
    each block's last normalisation, beta 0, the head N(0, ``head_std``)
    with a zero bias (after Goyal et al. 2017, section 5.1)."""
    shapes = param_shapes(config["model"])
    head_std = config["init"]["head_std"]
    last_gamma = config["init"]["last_gamma"]

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            if name.endswith(".gamma"):
                out[name] = jnp.full(
                    shape, last_gamma if name.endswith(".c.gamma") else 1.0,
                    jnp.float32)
            elif name.endswith(".beta") or name == "fc.b":
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                std = (head_std if name == "fc.w"
                       else math.sqrt(2.0 / (shape[0] * shape[1] * shape[2])))
                out[name] = std * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
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


@jax.custom_vjp
def _bf16(x):
    """The configuration's own precision, for the witness of
    ``tests/witness.py``: bfloat16 where the configuration holds it, both
    ways: the forward value and the cotangent that comes back."""
    return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


_bf16.defvjp(lambda x: (_bf16(x), None), lambda _, g: (_bf16(g),))

_ROUND = {"f32": lambda x: x, "fp8": _fp8, "bf16": _bf16}


# ---- forward ----------------------------------------------------------------
def _conv(x, w, stride, padding, q):
    return q(lax.conv_general_dilated(q(x), q(w), (stride, stride), padding,
                                      dimension_numbers=_DIMS,
                                      precision=_HI))


def _bn(y, gamma, beta, eps):
    mean = jnp.mean(y, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(y - mean), axis=(0, 1, 2))
    return (y - mean) * lax.rsqrt(var + eps) * gamma + beta


def _conv_bn(p, name, x, stride, padding, q, eps):
    y = _conv(x, p[f"{name}.w"], stride, padding, q)
    return q(_bn(y, p[f"{name}.gamma"], p[f"{name}.beta"], eps))


def _bottleneck(p, x, *, name, stride, project, q, eps):
    y = jax.nn.relu(_conv_bn(p, f"{name}.a", x, stride, "VALID", q, eps))
    y = jax.nn.relu(_conv_bn(p, f"{name}.b", y, 1, "SAME", q, eps))
    y = _conv_bn(p, f"{name}.c", y, 1, "VALID", q, eps)
    if project:
        x = _conv_bn(p, f"{name}.proj", x, stride, "VALID", q, eps)
    return q(jax.nn.relu(y + x))


def _stem(p, x, *, q, eps):
    y = _conv_bn(p, "stem", x, 2, [(3, 3), (3, 3)], q, eps)
    return lax.reduce_window(jax.nn.relu(y), -jnp.inf, lax.max,
                             (1, 3, 3, 1), (1, 2, 2, 1), "SAME")


def loss_fn(params, images, labels, row_weights, *, model, l2, precision):
    """Weighted-mean cross-entropy over the rows plus the l2 term.
    ``row_weights`` is all ones in a sound run; a planted fault zeroes
    half of it."""
    q, eps = _ROUND[precision], model["bn_eps"]
    x = jax.checkpoint(functools.partial(_stem, q=q, eps=eps))(params, images)
    for name, _, _, stride, project in _blocks(model):
        block = functools.partial(_bottleneck, name=name, stride=stride,
                                  project=project, q=q, eps=eps)
        x = jax.checkpoint(block)(
            {k: v for k, v in params.items() if k.startswith(name + ".")}, x)
    pooled = q(jnp.mean(x, axis=(1, 2)))
    logits = q(jnp.dot(pooled, q(params["fc.w"]), precision=_HI)
               + params["fc.b"])
    per_row = -jnp.sum(labels * jax.nn.log_softmax(logits, axis=-1), axis=-1)
    data = jnp.sum(per_row * row_weights) / jnp.maximum(
        jnp.sum(row_weights), 1.0)
    penalty = sum(jnp.sum(jnp.square(v)) for k, v in params.items()
                  if k != "fc.b")
    return data + 0.5 * l2 * penalty


def _norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


def first_steps(config: dict, mix: dict, weights: dict, batches: list, *,
                seed: int, precision: str = "f32", row_weights=None) -> dict:
    """Follow the first ``len(batches)`` training steps from ``weights``
    (``mix`` and ``seed`` are not needed here: no size of the model comes
    from the traffic, and nothing in the step is random).

    Returns what the comparison reads: each step's loss, the norm of
    every leaf's first gradient (as the optimizer gets it, the l2 term
    included), and the norm of every leaf's change over the steps."""
    opt = config["optimizer"]
    lr, mu = opt["learning_rate"], opt["momentum"]
    grad = jax.value_and_grad(functools.partial(
        loss_fn, model=config["model"], l2=opt["l2"], precision=precision))

    @jax.jit
    def step(params, trace, images, labels, rows):
        loss, g = grad(params, images, labels, rows)
        trace = jax.tree_util.tree_map(lambda t, d: d + mu * t, trace, g)
        params = jax.tree_util.tree_map(
            lambda p, d, t: p - lr * (d + mu * t), params, g, trace)
        return params, trace, loss, _norms(g)

    params = weights
    trace = jax.tree_util.tree_map(jnp.zeros_like, weights)
    losses, grad_norms = [], None
    for batch in batches:
        rows = (jnp.ones((batch["features"].shape[0],), jnp.float32)
                if row_weights is None else jnp.asarray(row_weights))
        params, trace, loss, norms = step(
            params, trace, jnp.asarray(batch["features"]),
            jnp.asarray(batch["labels"]), rows)
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = jax.device_get(norms)
    delta = jax.device_get(jax.jit(_norms)(jax.tree_util.tree_map(
        lambda a, b: a - b, params, weights)))
    return {"losses": losses,
            "grad_norms": {k: float(v) for k, v in grad_norms.items()},
            "delta_norms": {k: float(v) for k, v in delta.items()}}
