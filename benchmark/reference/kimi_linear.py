"""Plain Kimi-Linear causal-LM pre-training in float32 ``jax.numpy``: the
yardstick the ``kimi_linear_48b_a3b`` cells are compared with.

The model (config.json, ``model_type`` ``kimi_linear``; the new layer is
Kimi Delta Attention, KDA, of arXiv:2510.26692).  Pre-norm residual
blocks ``l1`` .. ``l<num_hidden_layers>``, counted from 1 as
``linear_attn_config`` counts them; every norm an RMS norm with a learned
scale; no bias and no position signal anywhere:

* KDA (``kda_layers``), ``a`` the normed input, ``H`` heads of ``d``:
  ``q, k, v = conv(a W_q), conv(a W_k), conv(a W_v)`` with ``conv`` a
  depthwise causal convolution of ``short_conv_kernel_size`` taps and a
  SiLU (``y[t] = silu(sum_j w[:, j] y~[t - 3 + j])``, zeros before 0);
  per head ``q = q / ||q|| / sqrt(d)``, ``k = k / ||k||``; the log decay
  ``g_t = -exp(A_log) softplus(a W_fa W_fb + dt_bias)`` a key channel;
  ``beta_t = sigmoid(a W_beta)`` a head; the state ``S`` ``[d, d]`` a
  head from zero, **token by token**: ``S' = Diag(exp(g_t)) S``, ``S = S'
  + beta_t k_t (v_t - S'^T k_t)^T``, ``o_t = S^T q_t``; then
  ``RMSNorm_head(o_t) * sigmoid(a W_ga W_gb)`` and ``W_o``;
* latent attention (``full_attn_layers``): ``q = a W_q`` per head ``[128
  | 64]``; ``[c_kv | k_r] = a W_kva``; ``[k_nope | v] = RMSNorm(c_kv)
  W_kvb``; ``k = [k_nope | k_r]`` with ``k_r`` one head shared by all and
  **not rotated** (``mla_use_nope``); causal ``softmax(q k^T /
  sqrt(192)) v``; ``W_o``;
* layer 1 a SwiGLU of ``intermediate_size``; every later layer routed
  experts: float32 logits ``f W_r``, ``s = sigmoid``, the
  ``num_experts_per_token`` experts with the largest ``s + b``, gates
  ``routed_scaling_factor * s_e / sum of the chosen s``, plus the shared
  expert;
* final norm, untied head, mean next-token cross-entropy over positions
  ``0 .. S-2``.

Departures from the published description (each an ``assumed`` line of
the configuration): the share (experts ``first_expert .. first_expert +
experts_held`` of the 256 live here, routing is over all 256 and what the
absent experts would have added is left out; experts are a dense masked
sum); the vocabulary is a slice; ``b`` is zero and fixed; Adam with
float32 moments and no decay.

It imports nothing of the program and nothing of another reference.  The
recurrence is the published equation itself, not the chunked form the
program runs: that is what the comparison is worth.  Its 8,192 steps run
as a scan over blocks of tokens with each block rematerialised (a state a
block is kept, not a state a token), elementwise in float32: no matrix
unit's precision enters it.  Every matrix product runs at
``Precision.HIGHEST``; each block of the model is rematerialised and
attention is a masked softmax in query blocks.  ``precision="fp8"`` is
the control: matmul operands, every layer's output and the state where a
token reads it rounded to float8 e4m3's three mantissa bits in the
forward pass, gradients straight through; the decay, ``beta``, the
carried state and the router's logits stay float32, as the program's do.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST
QUERY_BLOCK = 512
EXPERT_GROUP = 4
TOKEN_BLOCK = 64          # tokens of the recurrence under one checkpoint


# ---- parameters -------------------------------------------------------------
def block_names(c: dict) -> list:
    """(prefix, attention kind, is a routed block), layers 1 .. N in order."""
    linear, out = c["linear_attn_config"], []
    for n in range(1, c["num_hidden_layers"] + 1):
        if n in linear["kda_layers"]:
            kind = "kda"
        elif n in linear["full_attn_layers"]:
            kind = "mla"
        else:
            raise ValueError(f"linear_attn_config names no layer {n}")
        out.append((f"l{n}", kind, n > c["first_k_dense_replace"]))
    return out


def _block_shapes(pre: str, c: dict, kind: str, moe: bool) -> dict:
    h = c["hidden_size"]
    shapes = {f"{pre}.attn_norm.gamma": (h,)}
    if kind == "kda":
        linear = c["linear_attn_config"]
        heads, d = linear["num_heads"], linear["head_dim"]
        p, taps, rank = heads * d, linear["short_conv_kernel_size"], d
        shapes.update({
            f"{pre}.attn.W_q": (h, p), f"{pre}.attn.W_k": (h, p),
            f"{pre}.attn.W_v": (h, p), f"{pre}.attn.conv_q": (p, taps),
            f"{pre}.attn.conv_k": (p, taps), f"{pre}.attn.conv_v": (p, taps),
            f"{pre}.attn.W_fa": (h, rank), f"{pre}.attn.W_fb": (rank, p),
            f"{pre}.attn.A_log": (heads,), f"{pre}.attn.dt_bias": (p,),
            f"{pre}.attn.W_beta": (h, heads),
            f"{pre}.attn.W_ga": (h, rank), f"{pre}.attn.W_gb": (rank, p),
            f"{pre}.attn.o_norm": (d,), f"{pre}.attn.W_o": (p, h)})
    else:
        heads = c["num_attention_heads"]
        qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
        shapes.update({
            f"{pre}.attn.W_q": (h, heads * qk),
            f"{pre}.attn.W_kva": (h, c["kv_lora_rank"]
                                  + c["qk_rope_head_dim"]),
            f"{pre}.attn.kv_norm": (c["kv_lora_rank"],),
            f"{pre}.attn.W_kvb": (c["kv_lora_rank"],
                                  heads * (c["qk_nope_head_dim"]
                                           + c["v_head_dim"])),
            f"{pre}.attn.W_o": (heads * c["v_head_dim"], h)})
    shapes[f"{pre}.ffn_norm.gamma"] = (h,)
    if moe:
        e, w = c["experts_held"], c["moe_intermediate_size"]
        ws = w * c["num_shared_experts"]
        shapes.update({
            f"{pre}.ffn.W_router": (h, c["num_experts"]),
            f"{pre}.ffn.W_gate": (e, h, w), f"{pre}.ffn.W_up": (e, h, w),
            f"{pre}.ffn.W_down": (e, w, h),
            f"{pre}.ffn.shared_W_gate": (h, ws),
            f"{pre}.ffn.shared_W_up": (h, ws),
            f"{pre}.ffn.shared_W_down": (ws, h)})
    else:
        i = c["intermediate_size"]
        shapes.update({f"{pre}.ffn.W_gate": (h, i), f"{pre}.ffn.W_up": (h, i),
                       f"{pre}.ffn.W_down": (i, h)})
    return shapes


def param_shapes(c: dict) -> dict:
    h, v = c["hidden_size"], c["vocab_size"]
    shapes = {"embed.W": (v, h)}
    for pre, kind, moe in block_names(c):
        shapes.update(_block_shapes(pre, c, kind, moe))
    shapes.update({"final_norm.gamma": (h,), "lm_head.W": (h, v)})
    return shapes


def init_weights(config: dict, seed: int) -> dict:
    """Every parameter from ``seed`` in one jitted call, on the device, in
    float32: matrices, the embedding and the convolution taps normal with
    ``init.std``; norm scales 1; ``A_log = log(u)``, ``u`` uniform in [1,
    16]; ``dt_bias = softplus^-1(dt)``, ``log dt`` uniform in [log 0.001,
    log 0.1]."""
    shapes = param_shapes(config)
    std = config["init"]["std"]

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            k, leaf = jax.random.fold_in(key, i), name.rsplit(".", 1)[1]
            if leaf in ("gamma", "kv_norm", "o_norm"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif leaf == "A_log":
                out[name] = jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, 1.0, 16.0))
            elif leaf == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, math.log(0.001), math.log(0.1)))
                out[name] = dt + jnp.log(-jnp.expm1(-dt))
            else:
                out[name] = std * jax.random.normal(k, shape, jnp.float32)
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


def short_conv(x, w, q):
    """``silu(sum_j w[c, j] x[t - (K - 1) + j, c])``, zeros before 0: the
    explicit sum over the taps.  ``x`` ``[B, T, P]``, ``w`` ``[P, K]``."""
    taps, t = w.shape[-1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    total = jnp.zeros_like(x)
    for j in range(taps):
        total = total + w[:, j] * padded[:, j:j + t]
    return q(jax.nn.silu(total))


def delta_rule(q_, k, v, g, beta, rnd=lambda x: x):
    """The gated delta rule, one token at a time.  ``q_``, ``k``, ``g``
    ``[B, T, H, dk]``, ``v`` ``[B, T, H, dv]``, ``beta`` ``[B, T, H]`` ->
    (``o`` ``[B, T, H, dv]``, the last state ``[B, H, dk, dv]``).
    Elementwise products and sums in float32: ``S'^T k`` is ``sum_d S'[d,
    :] k[d]``.  ``rnd`` rounds the state where a token reads it (the
    control); the carried state is never rounded."""
    b, t, h, dk = k.shape
    dv = v.shape[-1]

    def token(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = jnp.exp(g_t)[..., None] * state                   # S'
        seen = jnp.sum(rnd(state) * k_t[..., None], axis=-2)      # S'^T k
        state = state + (beta_t[..., None] * k_t)[..., None] \
            * (v_t - seen)[..., None, :]
        return state, jnp.sum(rnd(state) * q_t[..., None], axis=-2)

    @jax.checkpoint
    def tokens(state, xs):
        return lax.scan(token, state, xs)

    block = math.gcd(TOKEN_BLOCK, t)
    xs = tuple(jnp.moveaxis(x, 1, 0).reshape((t // block, block)
                                             + x.shape[:1] + x.shape[2:])
               for x in (q_, k, v, g, beta))
    last, o = lax.scan(tokens, jnp.zeros((b, h, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(o.reshape((t,) + o.shape[2:]), 0, 1), last


def _kda(p, pre, a, c, q):
    b, t, _ = a.shape
    linear = c["linear_attn_config"]
    heads, d = linear["num_heads"], linear["head_dim"]

    def w(name):
        return p[f"{pre}.attn.{name}"]

    def split(y):
        return y.reshape(b, t, heads, d)

    def unit(y):
        return y * lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6)

    q_, k, v = (split(short_conv(_mm(a, w(f"W_{n}"), q), w(f"conv_{n}"), q))
                for n in "qkv")
    q_, k = q(unit(q_) * d ** -0.5), q(unit(k))
    g = -jnp.exp(w("A_log"))[:, None] * split(jax.nn.softplus(
        _mm(_mm(a, w("W_fa"), q), w("W_fb"), q) + w("dt_bias")))
    beta = jax.nn.sigmoid(_mm(a, w("W_beta"), q))
    o, _ = delta_rule(q_, k, v, g, beta, q)
    gate = jax.nn.sigmoid(split(_mm(_mm(a, w("W_ga"), q), w("W_gb"), q)))
    o = q(_rms_norm(q(o), w("o_norm"), c["rms_norm_eps"], q) * gate)
    return _mm(o.reshape(b, t, heads * d), w("W_o"), q)


def _mla(p, pre, a, c, q):
    b, t, _ = a.shape
    heads, nope = c["num_attention_heads"], c["qk_nope_head_dim"]
    rope, dv, rank = c["qk_rope_head_dim"], c["v_head_dim"], c["kv_lora_rank"]
    if c.get("q_lora_rank") or not c["mla_use_nope"]:
        raise ValueError("the reference knows kimi_linear's latent "
                         "attention: q_lora_rank null, mla_use_nope true")
    q_full = _mm(a, p[f"{pre}.attn.W_q"], q).reshape(b, t, heads, nope + rope)
    kv = _mm(a, p[f"{pre}.attn.W_kva"], q)
    c_kv = _rms_norm(kv[..., :rank], p[f"{pre}.attn.kv_norm"],
                     c["rms_norm_eps"], q)
    kvh = _mm(c_kv, p[f"{pre}.attn.W_kvb"], q).reshape(b, t, heads, nope + dv)
    k_full = jnp.concatenate(
        [kvh[..., :nope],
         jnp.broadcast_to(kv[..., None, rank:], (b, t, heads, rope))],
        axis=-1)                                   # k_r shared, not rotated
    v = kvh[..., nope:]
    scale = 1.0 / math.sqrt(nope + rope)
    block = math.gcd(QUERY_BLOCK, t)
    k_pos = jnp.arange(t)

    @jax.checkpoint
    def rows(blk):
        q_blk, start = blk
        s = jnp.einsum("bqhd,bkhd->bhqk", q(q_blk), q(k_full),
                       precision=_HI) * scale
        q_pos = start + jnp.arange(q_blk.shape[1])
        s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, -1e30)
        w = q(jax.nn.softmax(q(s), axis=-1))
        return q(jnp.einsum("bhqk,bkhd->bqhd", w, q(v), precision=_HI))

    blocks = q_full.reshape(b, t // block, block, heads, nope + rope)
    out = lax.map(rows, (jnp.moveaxis(blocks, 1, 0),
                         jnp.arange(0, t, block)))
    ctx = jnp.moveaxis(out, 0, 1).reshape(b, t, heads * dv)
    return _mm(ctx, p[f"{pre}.attn.W_o"], q)


def _swiglu(x, gate, up, down, q):
    return _mm(q(jax.nn.silu(_mm(x, gate, q)) * _mm(x, up, q)), down, q)


def _choose(p, pre, f, c):
    """(experts chosen ``[..., k]``, their gates) over all the routed
    experts: float32 logits, sigmoid scores, the top k of score + b."""
    logits = jnp.einsum("...i,io->...o", f.astype(jnp.float32),
                        p[f"{pre}.ffn.W_router"], precision=_HI)
    s = jax.nn.sigmoid(logits)
    bias = jnp.zeros((c["num_experts"],), jnp.float32)        # b, fixed at 0
    _, chosen = lax.top_k(s + bias, c["num_experts_per_token"])
    gates = jnp.take_along_axis(s, chosen, axis=-1)
    if c["moe_renormalize"]:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return chosen, gates * c["routed_scaling_factor"]


def _routed(p, pre, f, c, q):
    """Gates over all the routed experts, the dense masked sum over the
    held ones, plus the shared expert."""
    first, held = c["first_expert"], c["experts_held"]
    chosen, gates = _choose(p, pre, f, c)
    out = _swiglu(f, p[f"{pre}.ffn.shared_W_gate"],
                  p[f"{pre}.ffn.shared_W_up"], p[f"{pre}.ffn.shared_W_down"],
                  q)
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

    for e in range(0, held, EXPERT_GROUP):
        part = slice(e, e + EXPERT_GROUP)
        out = out + group(f, p[f"{pre}.ffn.W_gate"][part],
                          p[f"{pre}.ffn.W_up"][part],
                          p[f"{pre}.ffn.W_down"][part], mine[part])
    return q(out)


def _block(p, x, *, pre, kind, moe, c, q):
    eps = c["rms_norm_eps"]
    attend = _kda if kind == "kda" else _mla
    x = x + attend(p, pre, _rms_norm(x, p[f"{pre}.attn_norm.gamma"], eps, q),
                   c, q)
    f = _rms_norm(x, p[f"{pre}.ffn_norm.gamma"], eps, q)
    if moe:
        return x + _routed(p, pre, f, c, q)
    return x + _swiglu(f, p[f"{pre}.ffn.W_gate"], p[f"{pre}.ffn.W_up"],
                       p[f"{pre}.ffn.W_down"], q)


def hidden_states(params, tokens, *, config, precision="f32"):
    """The last block's output ``[B, S, hidden]``, every block
    rematerialised."""
    q = _ROUND[precision]
    x = params["embed.W"][tokens]
    for pre, kind, moe in block_names(config):
        mine = {k: v for k, v in params.items() if k.startswith(pre + ".")}
        x = jax.checkpoint(functools.partial(
            _block, pre=pre, kind=kind, moe=moe, c=config, q=q))(mine, x)
    return x


def logits(params, tokens, *, config, precision="f32"):
    """``[B, S, vocab]``: what the tier-1 test and the look at the logits
    by position compare; the loss never holds them all."""
    q = _ROUND[precision]
    x = hidden_states(params, tokens, config=config, precision=precision)
    return _mm(_rms_norm(x, params["final_norm.gamma"],
                         config["rms_norm_eps"], q), params["lm_head.W"], q)


def _next_token_loss(h, g, head, targets, weights, eps, q):
    """Sum over the weighted positions of the cross-entropy of
    ``RMSNorm(h) head`` against ``targets``, and the weights' sum."""
    logp = jax.nn.log_softmax(_mm(_rms_norm(h, g, eps, q), head, q), axis=-1)
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
        _next_token_loss, eps=config["rms_norm_eps"], q=q))
    # position i predicts t_{i+1}: the last position has no target
    nxt = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    weights = (row_weights[:, None] * position_weights[None, :]
               ).at[:, t - 1:].set(0.0)
    total, count = head(x, params["final_norm.gamma"], params["lm_head.W"],
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

    Memory, at the cell's size (2.4 GB a tree, 16 GB a chip): the caller
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
