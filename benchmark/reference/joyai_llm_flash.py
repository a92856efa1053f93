"""Plain JoyAI-LLM-Flash causal-LM pre-training in float32 ``jax.numpy``:
the yardstick the ``joyai_llm_flash`` cells are compared with.

The model (config.json, ``model_type`` ``joyai_llm_flash``; the layer
equations are DeepSeek-V3's, which the keys name).  Pre-norm residual
blocks, every norm an RMS norm with a learned scale:

* latent attention (MLA): ``c_q = RMSNorm(a W_qa)``, ``q = c_q W_qb`` split
  per head into ``[q_nope | q_rope]``; ``[c_kv | k_r] = a W_kva``,
  ``c_kv = RMSNorm(c_kv)``, ``[k_nope | v] = c_kv W_kvb`` per head;
  ``q_rope`` and the one shared ``k_r`` head rotated by position on
  interleaved pairs (2i, 2i+1) with ``rope_theta``; causal
  ``softmax(q k^T / sqrt(qk_head_dim)) v``; ``W_o``.  No biases;
* layer 0 a SwiGLU of ``intermediate_size``; every later layer routed
  experts: float32 logits ``f W_r``, ``s = sigmoid``, the
  ``num_experts_per_tok`` experts with the largest ``s + b`` (``b`` the
  ``noaux_tc`` bias, a buffer), gates ``routed_scaling_factor * s_e /
  sum of the chosen s``, plus the shared expert;
* final norm, untied head, mean next-token cross-entropy;
* the multi-token-prediction module (``num_nextn_predict_layers`` 1):
  ``W_eh [RMSNorm_h(h) ; RMSNorm_e(Emb(t_{i+1}))]``, one more routed
  block, its own final norm, the shared head, cross-entropy against
  ``t_{i+2}``; total loss = main + ``mtp_lambda`` x MTP.

Departures from the published description (each an ``assumed`` line of
the configuration):

* the share: the experts ``first_expert .. first_expert + experts_held``
  of the 256 live here; routing is over all 256 and what the absent
  experts would have added is left out (``model-configs`` guide, s. 4).
  Experts are a dense masked sum: every held expert over every token,
  times its gate or zero;
* the vocabulary is a slice: ids, logits and both losses over it;
* ``b`` is zero and fixed (its update speed is not in the config), so
  this is not aux-loss-free balancing, only its selection rule;
* the MTP stream runs over all S positions with ``Emb(t_{i+1})`` taken as
  zero at the last one; positions S-2 and S-1 have no ``t_{i+2}`` and
  are left out of its loss, and causal attention keeps them from the
  others;
* Adam with float32 moments and no decay.

It imports nothing of the program.  Every matrix product runs at
``Precision.HIGHEST``; each block and each head is rematerialised and the
attention is a masked softmax computed in query blocks, so that 8,192
tokens fit one chip.  ``precision="fp8"`` is the control: matmul operands
and every layer's output rounded to float8 e4m3's three mantissa bits in
the forward pass, gradients straight through; the router's logits stay
float32 on rounded activations, as the program's do on bfloat16 ones.
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


# ---- parameters -------------------------------------------------------------
def _block_shapes(prefix: str, c: dict, moe: bool) -> dict:
    h, heads = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    shapes = {
        f"{prefix}.attn_norm.g": (h,),
        f"{prefix}.q_a.w": (h, c["q_lora_rank"]),
        f"{prefix}.q_a_norm.g": (c["q_lora_rank"],),
        f"{prefix}.q_b.w": (c["q_lora_rank"], heads * qk),
        f"{prefix}.kv_a.w": (h, c["kv_lora_rank"] + c["qk_rope_head_dim"]),
        f"{prefix}.kv_a_norm.g": (c["kv_lora_rank"],),
        f"{prefix}.kv_b.w": (c["kv_lora_rank"],
                             heads * (c["qk_nope_head_dim"]
                                      + c["v_head_dim"])),
        f"{prefix}.o.w": (heads * c["v_head_dim"], h),
        f"{prefix}.ffn_norm.g": (h,),
    }
    if moe:
        e, w = c["experts_held"], c["moe_intermediate_size"]
        ws = w * c["n_shared_experts"]
        shapes.update({
            f"{prefix}.router.w": (h, c["n_routed_experts"]),
            f"{prefix}.experts.gate": (e, h, w),
            f"{prefix}.experts.up": (e, h, w),
            f"{prefix}.experts.down": (e, w, h),
            f"{prefix}.shared.gate.w": (h, ws),
            f"{prefix}.shared.up.w": (h, ws),
            f"{prefix}.shared.down.w": (ws, h),
        })
    else:
        i = c["intermediate_size"]
        shapes.update({f"{prefix}.gate.w": (h, i), f"{prefix}.up.w": (h, i),
                       f"{prefix}.down.w": (i, h)})
    return shapes


def block_names(c: dict) -> list:
    """(prefix, is a routed block) of the main stack, in order."""
    return [(f"l{n}", n >= c["first_k_dense_replace"])
            for n in range(c["num_hidden_layers"])]


def param_shapes(c: dict) -> dict:
    h, v = c["hidden_size"], c["vocab_size"]
    shapes = {"emb.w": (v, h)}
    for prefix, moe in block_names(c):
        shapes.update(_block_shapes(prefix, c, moe))
    shapes.update({"final_norm.g": (h,), "head.w": (h, v)})
    if c["num_nextn_predict_layers"]:
        shapes.update({"mtp.h_norm.g": (h,), "mtp.e_norm.g": (h,),
                       "mtp.proj.w": (2 * h, h)})
        shapes.update(_block_shapes("mtp", c, True))
        shapes["mtp.final_norm.g"] = (h,)
    return shapes


def init_weights(config: dict, seed: int) -> dict:
    """Every parameter from ``seed`` in one jitted call, on the device, in
    float32: matrices and the embedding normal with ``init.std``, norm
    scales 1."""
    shapes = param_shapes(config)
    std = config["init"]["std"]

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            if name.endswith(".g"):
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
    """bfloat16's rounding, for ``tests/routing_flips.py``: astype is its
    own straight-through."""
    return x.astype(jnp.bfloat16).astype(x.dtype)


_ROUND = {"f32": lambda x: x, "bf16": _bf16, "fp8": _fp8}


# ---- forward ----------------------------------------------------------------
def _mm(x, w, q):
    return q(jnp.einsum("...i,io->...o", q(x), q(w), precision=_HI))


def _rms_norm(x, g, eps, q):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return q(x * lax.rsqrt(var + eps) * g)


def _rope(x, theta):
    """Rotate the pairs (2i, 2i+1) of the last axis by position (axis 1):
    angle = position * theta**(-2i / d)."""
    t, d = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    angle = angle.reshape((1, t) + (1,) * (x.ndim - 3) + (d // 2,))
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def _attention(p, pre, a, c, q):
    b, t, _ = a.shape
    heads, nope = c["num_attention_heads"], c["qk_nope_head_dim"]
    rope, dv = c["qk_rope_head_dim"], c["v_head_dim"]
    eps, theta = c["rms_norm_eps"], float(c["rope_theta"])
    c_q = _rms_norm(_mm(a, p[f"{pre}.q_a.w"], q), p[f"{pre}.q_a_norm.g"],
                    eps, q)
    qh = _mm(c_q, p[f"{pre}.q_b.w"], q).reshape(b, t, heads, nope + rope)
    kv = _mm(a, p[f"{pre}.kv_a.w"], q)
    c_kv = _rms_norm(kv[..., :c["kv_lora_rank"]], p[f"{pre}.kv_a_norm.g"],
                     eps, q)
    k_r = _rope(kv[..., c["kv_lora_rank"]:], theta)           # [B,T,rope]
    kvh = _mm(c_kv, p[f"{pre}.kv_b.w"], q).reshape(b, t, heads, nope + dv)
    q_full = jnp.concatenate(
        [qh[..., :nope], _rope(qh[..., nope:], theta)], axis=-1)
    k_full = jnp.concatenate(
        [kvh[..., :nope],
         jnp.broadcast_to(k_r[:, :, None, :], (b, t, heads, rope))], axis=-1)
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

    # one compiled body for all the query blocks
    blocks = q_full.reshape(b, t // block, block, heads, nope + rope)
    out = lax.map(rows, (jnp.moveaxis(blocks, 1, 0),
                         jnp.arange(0, t, block)))
    out = [jnp.moveaxis(out, 0, 1)]
    ctx = jnp.concatenate(out, axis=1).reshape(b, t, heads * dv)
    return _mm(ctx, p[f"{pre}.o.w"], q)


def _swiglu(x, gate, up, down, q):
    return _mm(q(jax.nn.silu(_mm(x, gate, q)) * _mm(x, up, q)), down, q)


def _choose(p, pre, f, c):
    """(experts chosen ``[..., k]``, their gates) over all the routed
    experts: float32 logits, sigmoid scores, the top k of score + b."""
    logits = jnp.einsum("...i,io->...o", f.astype(jnp.float32),
                        p[f"{pre}.router.w"], precision=_HI)
    s = jax.nn.sigmoid(logits)
    bias = jnp.zeros((c["n_routed_experts"],), jnp.float32)   # b, fixed at 0
    _, chosen = lax.top_k(s + bias, c["num_experts_per_tok"])
    gates = jnp.take_along_axis(s, chosen, axis=-1)
    if c["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return chosen, gates * c["routed_scaling_factor"]


def _routed(p, pre, f, c, q):
    """Gates over all the routed experts, the dense masked sum over the
    held ones, plus the shared expert."""
    first, held = c["first_expert"], c["experts_held"]
    chosen, gates = _choose(p, pre, f, c)
    out = _swiglu(f, p[f"{pre}.shared.gate.w"], p[f"{pre}.shared.up.w"],
                  p[f"{pre}.shared.down.w"], q)
    # [held, ..., 1]: each held expert's gate for each token, or zero
    mine = jnp.stack([jnp.sum(jnp.where(chosen == first + e, gates, 0.0),
                              axis=-1) for e in range(held)])[..., None]

    @jax.checkpoint
    def group(f, gate_w, up_w, down_w, gate_e):
        """The dense masked sum over a few experts: every expert over
        every token; the gate goes in before the down projection."""
        g = q(jnp.einsum("...i,eio->e...o", q(f), q(gate_w), precision=_HI))
        u = q(jnp.einsum("...i,eio->e...o", q(f), q(up_w), precision=_HI))
        hidden = q(jax.nn.silu(g) * u) * gate_e
        return jnp.einsum("e...o,eoi->...i", hidden, q(down_w),
                          precision=_HI)

    for e in range(0, held, EXPERT_GROUP):
        part = slice(e, e + EXPERT_GROUP)
        out = out + group(f, p[f"{pre}.experts.gate"][part],
                          p[f"{pre}.experts.up"][part],
                          p[f"{pre}.experts.down"][part], mine[part])
    return q(out)


def _block(p, x, *, pre, moe, c, q):
    eps = c["rms_norm_eps"]
    x = x + _attention(p, pre, _rms_norm(x, p[f"{pre}.attn_norm.g"], eps, q),
                       c, q)
    f = _rms_norm(x, p[f"{pre}.ffn_norm.g"], eps, q)
    if moe:
        return x + _routed(p, pre, f, c, q)
    return x + _swiglu(f, p[f"{pre}.gate.w"], p[f"{pre}.up.w"],
                       p[f"{pre}.down.w"], q)


def _run_block(params, x, pre, moe, c, q):
    mine = {k: v for k, v in params.items() if k.startswith(pre + ".")}
    return jax.checkpoint(functools.partial(
        _block, pre=pre, moe=moe, c=c, q=q))(mine, x)


def _next_token_loss(h, g, head, targets, weights, eps, q):
    """Sum over the weighted positions of the cross-entropy of
    ``RMSNorm(h) head`` against ``targets``, and the weights' sum."""
    logits = _mm(_rms_norm(h, g, eps, q), head, q)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.sum(picked * weights), jnp.sum(weights)


def loss_fn(params, tokens, row_weights, position_weights, *, config,
            precision):
    """Main + ``mtp_lambda`` x MTP loss of one ``[B, S]`` batch of ids.
    ``row_weights`` ``[B]`` and ``position_weights`` ``[S]`` are all ones
    in a sound run; a planted fault zeroes part of one: rows, or the
    positions whose targets are left out of both losses."""
    c, q = config, _ROUND[precision]
    eps, lam = c["rms_norm_eps"], c["mtp_lambda"]
    b, t = tokens.shape
    emb = params["emb.w"][tokens]
    x = emb
    for pre, moe in block_names(c):
        x = _run_block(params, x, pre, moe, c, q)
    head = jax.checkpoint(functools.partial(_next_token_loss, eps=eps, q=q))
    ones = row_weights[:, None] * position_weights[None, :]
    # position i predicts t_{i+1}: the last position has no target
    nxt = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    w1 = ones.at[:, t - 1:].set(0.0)
    total, count = head(x, params["final_norm.g"], params["head.w"], nxt, w1)
    loss = total / jnp.maximum(count, 1.0)
    if c["num_nextn_predict_layers"]:
        emb_next = jnp.concatenate(
            [emb[:, 1:], jnp.zeros_like(emb[:, :1])], axis=1)
        both = jnp.concatenate(
            [_rms_norm(x, params["mtp.h_norm.g"], eps, q),
             _rms_norm(emb_next, params["mtp.e_norm.g"], eps, q)], axis=-1)
        y = _run_block(params, _mm(both, params["mtp.proj.w"], q), "mtp",
                       True, c, q)
        # position i predicts t_{i+2}: the last two have no target
        nxt2 = jnp.concatenate([tokens[:, 2:], tokens[:, :2]], axis=1)
        w2 = ones.at[:, t - 2:].set(0.0)
        total2, count2 = head(y, params["mtp.final_norm.g"],
                              params["head.w"], nxt2, w2)
        loss = loss + lam * total2 / jnp.maximum(count2, 1.0)
    return loss


def routing(params, tokens, *, config, precision="f32"):
    """The experts each token of each routed block of the main stack
    chose, ``{prefix: [B, S, k]}``: what ``tests/routing_flips.py`` counts
    the tokens routed otherwise from.  Forward only, so nothing is
    rematerialised."""
    c, q = config, _ROUND[precision]
    eps = c["rms_norm_eps"]
    out, x = {}, params["emb.w"][tokens]
    for pre, moe in block_names(c):
        x = x + _attention(params, pre, _rms_norm(
            x, params[f"{pre}.attn_norm.g"], eps, q), c, q)
        f = _rms_norm(x, params[f"{pre}.ffn_norm.g"], eps, q)
        if moe:
            out[pre] = _choose(params, pre, f, c)[0]
            x = x + _routed(params, pre, f, c, q)
        else:
            x = x + _swiglu(f, params[f"{pre}.gate.w"],
                            params[f"{pre}.up.w"], params[f"{pre}.down.w"], q)
    return out


def _norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


def make_steps(config: dict, precision: str = "f32") -> tuple:
    """(gradients, update): ``gradients(params, tokens, rows, positions)``
    gives the loss, the gradient and its norm by leaf; ``update(params, mu, nu,
    count, g)`` is Adam's step and gives (params, mu, nu, count)."""
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

    Memory, at the cell's size (2.7 GB a tree, 16 GB a chip): the caller
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
