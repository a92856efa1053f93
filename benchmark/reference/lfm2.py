"""Plain LFM2-8B-A1B causal-LM pre-training in float32 ``jax.numpy``: the
yardstick the ``lfm2_8b_a1b`` cells are compared with.

The model (config.json, ``model_type`` ``lfm2_moe``).  Pre-norm residual
blocks ``l<i>`` for the published layers ``first_layer`` ..
``first_layer + num_hidden_layers - 1``, counted from 0 as
``layer_types`` counts them; every norm an RMS norm with a learned scale
and eps ``norm_eps``; no bias anywhere.  ``a`` is a block's normed input:

* gated short convolution (``conv``): ``[B~ ; C~ ; x~] = a W_in``, ``u =
  B~ * x~``, ``z[t] = sum_j w[:, j] u[t - 2 + j]`` over the 3 taps with
  zeros before 0 and no activation, ``y = C~ * z``, ``W_out``;
* grouped-query attention (``full_attention``): ``q = a W_q`` (32 heads
  of 64), ``k = a W_k``, ``v = a W_v`` (8 heads of 64); an RMS norm of
  every query and key head (scales ``q_norm``, ``k_norm``); rotary
  positions on the pairs ``(i, i + 32)``, angle ``t theta^(-2i/64)``;
  causal ``softmax(q k^T / 8) v`` with query head ``h`` reading K/V head
  ``h // 4``, the K/V heads repeated per group by indexing; ``W_o``;
* the first ``num_dense_layers`` blocks a SwiGLU of
  ``intermediate_size``; every later block routed experts: float32 logits
  ``f W_router``, ``s = sigmoid``, the ``num_experts_per_tok`` experts
  with the largest ``s + b``, gates ``routed_scaling_factor * s_e / (sum
  of the chosen s + norm_topk_eps)``; no shared expert;
* final norm, the head tied to the embedding (``logits = h E^T`` with
  ``E`` the one ``embed.W``), mean next-token cross-entropy over positions
  ``0 .. S-2``.

Leaves are named as the program's graph names them, ``<vertex>.<param>``
(``l2_attn.W_q``, ``l3_ffn.W_router``, ``embed.W``).  Departures from the
published description (each an ``assumed`` line of the configuration):
the share (experts ``first_expert .. first_expert + experts_held`` of the
32 live here, routing is over all 32 and what the absent experts would
have added is left out; experts are a dense masked sum); the vocabulary
is a slice; ``b`` is zero and fixed; Adam with float32 moments and no
decay.

It imports nothing of the program and nothing of another reference.
Every matrix product runs at ``Precision.HIGHEST``; each block of the
model is rematerialised and attention is a masked softmax in query
blocks, so that no ``[H, S, S]`` array exists.  ``precision="fp8"`` is
the control: matmul operands, the gates' products and every layer's
output rounded to float8 e4m3's three mantissa bits in the forward pass,
gradients straight through; the router's logits stay float32, as the
program's do.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST
QUERY_BLOCK = 256
EXPERT_GROUP = 4


# ---- parameters -------------------------------------------------------------
def block_names(c: dict) -> list:
    """(vertex prefix, mixer kind, is a routed block) of the layers held,
    in order."""
    first = c.get("first_layer", 0)
    kinds = c["layer_types"][first:first + c["num_hidden_layers"]]
    out = []
    for i, kind in enumerate(kinds):
        if kind not in ("conv", "full_attention"):
            raise ValueError(f"layer_types names a layer {kind!r}")
        out.append((f"l{first + i}", kind, i >= c["num_dense_layers"]))
    return out


def _block_shapes(pre: str, c: dict, kind: str, moe: bool) -> dict:
    h = c["hidden_size"]
    shapes = {f"{pre}_attn_norm.gamma": (h,)}
    if kind == "conv":
        shapes.update({f"{pre}_attn.W_in": (h, 3 * h),
                       f"{pre}_attn.conv_w": (h, c["conv_L_cache"]),
                       f"{pre}_attn.W_out": (h, h)})
    else:
        heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
        d = h // heads
        shapes.update({f"{pre}_attn.W_q": (h, heads * d),
                       f"{pre}_attn.W_k": (h, kv * d),
                       f"{pre}_attn.W_v": (h, kv * d),
                       f"{pre}_attn.W_o": (heads * d, h),
                       f"{pre}_attn.q_norm": (d,),
                       f"{pre}_attn.k_norm": (d,)})
    shapes[f"{pre}_ffn_norm.gamma"] = (h,)
    if moe:
        e, w = c["experts_held"], c["moe_intermediate_size"]
        shapes.update({f"{pre}_ffn.W_router": (h, c["num_experts"]),
                       f"{pre}_ffn.W_gate": (e, h, w),
                       f"{pre}_ffn.W_up": (e, h, w),
                       f"{pre}_ffn.W_down": (e, w, h)})
    else:
        i = c["intermediate_size"]
        shapes.update({f"{pre}_ffn.W_gate": (h, i), f"{pre}_ffn.W_up": (h, i),
                       f"{pre}_ffn.W_down": (i, h)})
    return shapes


def param_shapes(c: dict) -> dict:
    shapes = {"embed.W": (c["vocab_size"], c["hidden_size"])}
    for pre, kind, moe in block_names(c):
        shapes.update(_block_shapes(pre, c, kind, moe))
    shapes["final_norm.gamma"] = (c["hidden_size"],)
    return shapes


def init_weights(config: dict, seed: int) -> dict:
    """Every parameter from ``seed`` in one jitted call, on the device, in
    float32: matrices, the embedding and the convolution taps normal with
    ``init.std``; norm scales 1."""
    shapes = param_shapes(config)
    std = config["init"]["std"]

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            leaf = name.rsplit(".", 1)[1]
            if leaf in ("gamma", "q_norm", "k_norm"):
                out[name] = jnp.ones(shape, jnp.float32)
            else:
                out[name] = std * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
        return out

    return make(jax.random.key(seed % (2 ** 31)))


# ---- the control's rounding -------------------------------------------------
@jax.custom_jvp
def _fp8(x):
    """Round to float8 e4m3's three mantissa bits by integer arithmetic on
    the float32's own bits (half away from zero); the exponent keeps
    float32's range (``reference/bert_base.py`` says why)."""
    bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    bits = (bits + jnp.uint32(1 << 19)) & jnp.uint32(0xFFF00000)
    return lax.bitcast_convert_type(bits, jnp.float32).astype(x.dtype)


@_fp8.defjvp
def _fp8_jvp(primals, tangents):                 # straight through
    return _fp8(primals[0]), tangents[0]


def _bf16(x):
    """bfloat16's rounding: astype is its own straight-through."""
    return x.astype(jnp.bfloat16).astype(x.dtype)


_ROUND = {"f32": lambda x: x, "bf16": _bf16, "fp8": _fp8}


# ---- forward ----------------------------------------------------------------
def _mm(x, w, q):
    return q(jnp.einsum("...i,io->...o", q(x), q(w), precision=_HI))


def _rms_norm(x, g, eps, q):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return q(x * lax.rsqrt(var + eps) * g)


def conv_taps(x, w):
    """``sum_j w[c, j] x[t - (K - 1) + j, c]``, zeros before 0: the
    explicit sum over the taps.  ``x`` ``[B, T, P]``, ``w`` ``[P, K]``."""
    taps, t = w.shape[-1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    total = jnp.zeros_like(x)
    for j in range(taps):
        total = total + w[:, j] * padded[:, j:j + t]
    return total


def _conv(p, pre, a, c, q):
    gate_b, gate_c, x = jnp.split(_mm(a, p[f"{pre}_attn.W_in"], q), 3,
                                  axis=-1)
    z = q(conv_taps(q(gate_b * x), p[f"{pre}_attn.conv_w"]))
    return _mm(q(gate_c * z), p[f"{pre}_attn.W_out"], q)


def rotate_half(x, theta):
    """Rotary positions on the pairs ``(i, i + d/2)`` of the last axis of
    ``[B, T, H, d]``: ``angle = t theta^(-2i/d)``."""
    t, d = x.shape[1], x.shape[-1]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * theta ** (
        -jnp.arange(0, d, 2, dtype=jnp.float32) / d)[None, :]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    first, second = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], axis=-1)


def _gqa(p, pre, a, c, q):
    b, t, h = a.shape
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    d, eps = h // heads, c["norm_eps"]

    def w(name):
        return p[f"{pre}_attn.{name}"]

    q_ = _rms_norm(_mm(a, w("W_q"), q).reshape(b, t, heads, d), w("q_norm"),
                   eps, q)
    k = _rms_norm(_mm(a, w("W_k"), q).reshape(b, t, kv, d), w("k_norm"),
                  eps, q)
    v = _mm(a, w("W_v"), q).reshape(b, t, kv, d)
    theta = float(c["rope_theta"])
    q_, k = q(rotate_half(q_, theta)), q(rotate_half(k, theta))
    # query head h reads K/V head h // (heads / kv): repeated by indexing
    group = jnp.arange(heads) // (heads // kv)
    k, v = k[:, :, group], v[:, :, group]
    block = math.gcd(QUERY_BLOCK, t)
    k_pos = jnp.arange(t)

    @jax.checkpoint
    def rows(blk):
        q_blk, start = blk
        s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k,
                       precision=_HI) / math.sqrt(d)
        q_pos = start + jnp.arange(q_blk.shape[1])
        s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, -1e30)
        weights = q(jax.nn.softmax(q(s), axis=-1))
        return q(jnp.einsum("bhqk,bkhd->bqhd", weights, v, precision=_HI))

    blocks = q_.reshape(b, t // block, block, heads, d)
    out = lax.map(rows, (jnp.moveaxis(blocks, 1, 0),
                         jnp.arange(0, t, block)))
    ctx = jnp.moveaxis(out, 0, 1).reshape(b, t, heads * d)
    return _mm(ctx, w("W_o"), q)


def _swiglu(x, gate, up, down, q):
    return _mm(q(jax.nn.silu(_mm(x, gate, q)) * _mm(x, up, q)), down, q)


def _choose(p, pre, f, c):
    """(experts chosen ``[..., k]``, their gates) over all the routed
    experts: float32 logits, sigmoid scores, the top k of score + b."""
    logits = jnp.einsum("...i,io->...o", f.astype(jnp.float32),
                        p[f"{pre}_ffn.W_router"], precision=_HI)
    s = jax.nn.sigmoid(logits)
    bias = jnp.zeros((c["num_experts"],), jnp.float32)        # b, fixed at 0
    _, chosen = lax.top_k(s + bias, c["num_experts_per_tok"])
    gates = jnp.take_along_axis(s, chosen, axis=-1)
    if c["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True)
                         + c["norm_topk_eps"])
    return chosen, gates * c["routed_scaling_factor"]


def _routed(p, pre, f, c, q):
    """Gates over all the routed experts, the dense masked sum over the
    held ones."""
    first, held = c["first_expert"], c["experts_held"]
    chosen, gates = _choose(p, pre, f, c)
    # [held, ..., 1]: each held expert's gate for each token, or zero
    mine = jnp.stack([jnp.sum(jnp.where(chosen == first + e, gates, 0.0),
                              axis=-1) for e in range(held)])[..., None]

    @jax.checkpoint
    def group(f, gate_w, up_w, down_w, gate_e):
        """Every expert of a few over every token; the gate goes in before
        the down projection."""
        g = q(jnp.einsum("...i,eio->e...o", q(f), q(gate_w), precision=_HI))
        u = q(jnp.einsum("...i,eio->e...o", q(f), q(up_w), precision=_HI))
        hidden = q(jax.nn.silu(g) * u) * gate_e
        return jnp.einsum("e...o,eoi->...i", hidden, q(down_w),
                          precision=_HI)

    out = jnp.zeros_like(f)
    for e in range(0, held, EXPERT_GROUP):
        part = slice(e, e + EXPERT_GROUP)
        out = out + group(f, p[f"{pre}_ffn.W_gate"][part],
                          p[f"{pre}_ffn.W_up"][part],
                          p[f"{pre}_ffn.W_down"][part], mine[part])
    return q(out)


def _block(p, x, *, pre, kind, moe, c, q):
    eps = c["norm_eps"]
    mix = _conv if kind == "conv" else _gqa
    x = x + mix(p, pre, _rms_norm(x, p[f"{pre}_attn_norm.gamma"], eps, q),
                c, q)
    f = _rms_norm(x, p[f"{pre}_ffn_norm.gamma"], eps, q)
    if moe:
        return x + _routed(p, pre, f, c, q)
    return x + _swiglu(f, p[f"{pre}_ffn.W_gate"], p[f"{pre}_ffn.W_up"],
                       p[f"{pre}_ffn.W_down"], q)


def hidden_states(params, tokens, *, config, precision="f32"):
    """The last block's output ``[B, S, hidden]``, every block
    rematerialised."""
    q = _ROUND[precision]
    x = params["embed.W"][tokens]
    for pre, kind, moe in block_names(config):
        mine = {k: v for k, v in params.items() if k.startswith(pre + "_")}
        x = jax.checkpoint(functools.partial(
            _block, pre=pre, kind=kind, moe=moe, c=config, q=q))(mine, x)
    return x


def _head(h, g, emb, eps, q):
    """``RMSNorm(h) E^T``: the head is the embedding's own matrix."""
    return q(jnp.einsum("...i,vi->...v", q(_rms_norm(h, g, eps, q)), q(emb),
                        precision=_HI))


def logits(params, tokens, *, config, precision="f32"):
    """``[B, S, vocab]``: what the tier-1 test compares; the loss never
    holds them all."""
    q = _ROUND[precision]
    x = hidden_states(params, tokens, config=config, precision=precision)
    return _head(x, params["final_norm.gamma"], params["embed.W"],
                 config["norm_eps"], q)


def _next_token_loss(h, g, emb, targets, weights, eps, q):
    """Sum over the weighted positions of the cross-entropy of
    ``RMSNorm(h) E^T`` against ``targets``, and the weights' sum."""
    logp = jax.nn.log_softmax(_head(h, g, emb, eps, q), axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.sum(picked * weights), jnp.sum(weights)


def loss_fn(params, tokens, row_weights, position_weights, *, config,
            precision):
    """Mean next-token loss of one ``[B, S]`` batch of ids.
    ``row_weights`` ``[B]`` and ``position_weights`` ``[S]`` are all ones
    in a sound run; a planted fault zeroes part of one: rows, or the
    positions whose targets are left out of the loss."""
    q = _ROUND[precision]
    t = tokens.shape[1]
    x = hidden_states(params, tokens, config=config, precision=precision)
    head = jax.checkpoint(functools.partial(
        _next_token_loss, eps=config["norm_eps"], q=q))
    # position i predicts t_{i+1}: the last position has no target
    nxt = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    weights = (row_weights[:, None] * position_weights[None, :]
               ).at[:, t - 1:].set(0.0)
    total, count = head(x, params["final_norm.gamma"], params["embed.W"],
                        nxt, weights)
    return total / jnp.maximum(count, 1.0)


def _norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


def make_steps(config: dict, precision: str = "f32") -> tuple:
    """(gradients, update): ``gradients(params, tokens, rows, positions)``
    gives the loss, the gradient and its norm by leaf; ``update(params, mu,
    nu, count, g)`` is Adam's step and gives (params, mu, nu, count)."""
    opt = config["optimizer"]
    lr, b1, b2, eps = (opt["learning_rate"], opt["beta1"], opt["beta2"],
                       opt["epsilon"])
    grad = jax.value_and_grad(functools.partial(
        loss_fn, config=config, precision=precision))

    @jax.jit
    def gradients(params, tokens, rows, positions):
        loss, g = grad(params, tokens, rows, positions)
        return loss, g, _norms(g)

    def update(params, mu, nu, count, g):
        count = count + 1
        mu = jax.tree_util.tree_map(lambda m, d: b1 * m + (1 - b1) * d, mu, g)
        nu = jax.tree_util.tree_map(
            lambda v, d: b2 * v + (1 - b2) * d * d, nu, g)
        c1, c2 = 1 - b1 ** count, 1 - b2 ** count
        params = jax.tree_util.tree_map(
            lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
            params, mu, nu)
        return params, mu, nu, count

    return gradients, update


def first_steps(config: dict, mix: dict, weights: dict, batches: list, *,
                seed: int, precision: str = "f32", row_weights=None,
                position_weights=None) -> dict:
    """Follow the first ``len(batches)`` training steps from ``weights``:
    each step's loss, the norm of every leaf's first gradient, and of
    every leaf's change over the steps.  ``seed`` is unused: the model
    draws nothing.  ``row_weights`` ``[B]`` and ``position_weights``
    ``[S]`` plant a fault (rows, or positions' targets, left out of the
    loss's mean); both are arguments of the one compiled gradient.

    Memory, at the cell's size (2.03 GB a tree, 16 GB a chip): the caller
    keeps ``weights``, so beside them live the parameters, the gradient
    and the float32 activations of one rematerialised block.  Adam's two
    moments wait on the host while the gradient is computed, and the
    update donates what it is handed; the first step reads the caller's
    weights and makes its moments from nought."""
    gradients, update = make_steps(config, precision)
    first = jax.jit(lambda params, g: update(
        params, jax.tree_util.tree_map(jnp.zeros_like, g),
        jax.tree_util.tree_map(jnp.zeros_like, g),
        jnp.zeros((), jnp.float32), g), donate_argnums=1)
    later = jax.jit(update, donate_argnums=(0, 1, 2))
    params, moments, count = weights, None, None
    losses, grad_norms = [], None
    for i, batch in enumerate(batches):
        tokens = jnp.asarray(batch["tokens"])
        rows = (jnp.ones((tokens.shape[0],), jnp.float32)
                if row_weights is None else jnp.asarray(row_weights))
        positions = (jnp.ones((tokens.shape[1],), jnp.float32)
                     if position_weights is None
                     else jnp.asarray(position_weights, jnp.float32))
        loss, g, norms = gradients(params, tokens, rows, positions)
        losses.append(float(loss))
        if moments is None:
            grad_norms = jax.device_get(norms)
            params, mu, nu, count = first(params, g)
        else:
            mu, nu = jax.device_put(moments)
            params, mu, nu, count = later(params, mu, nu, count, g)
        del g
        if i + 1 < len(batches):
            moments = jax.device_get((mu, nu))
        for leaf in jax.tree_util.tree_leaves((mu, nu)):
            leaf.delete()
    delta = jax.device_get(jax.jit(_norms)(
        jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.subtract, a, b),
                donate_argnums=0)(params, weights)))
    return {"losses": losses,
            "grad_norms": {k: float(v) for k, v in grad_norms.items()},
            "delta_norms": {k: float(v) for k, v in delta.items()}}
