"""The layers of a current decoder-only language model (ROADMAP R1-R3).

No reference parity: deeplearning4j stops at 2018's encoder.  These are
the blocks of DeepSeek-V3's family as its published configurations name
them: RMS norm, the gated (SwiGLU) feed-forward, latent attention (MLA)
with rotary positions inside it or none at all, routed experts beside a
shared expert with a layer that is told which experts it holds, and the
next-token output layer that owns the head once for the main and the
multi-token-prediction stream, or reads the embedding's matrix as its
own (a tied head); Kimi Linear's delta attention (KDA,
arXiv:2510.26692): a gated delta rule whose state is carried along the
sequence, run as a chunked scan; and LFM2's two token mixers: the
double-gated short convolution and grouped-query attention with a norm on
each query and key head and rotary positions on half-split pairs.
``models.zoo.joyai_llm_flash``, ``models.zoo.kimi_linear`` and
``models.zoo.lfm2_moe`` wire them into a ``ComputationGraph``.

Precision follows the dtype policy as ``DenseLayer`` does (float32
parameters cast to the compute dtype at use, outputs in the output
dtype); norm reductions, rotary angles, the router's logits and gates,
and the loss are float32 at least, whatever the policy (float64 under the
gradient checks' float64 policy); so are delta attention's decay, its
cumulative sums and exponentials, ``beta``, the inversion inside a chunk
and the state.
"""

from __future__ import annotations

import dataclasses
import functools
import jax
import jax.numpy as jnp

from deeplearning4j_tpu.config import dtype_policy
from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer
from deeplearning4j_tpu.ops.attention import multi_head_attention


def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _wide(dtype):
    """float32, or ``dtype`` where it is wider."""
    return jnp.promote_types(dtype, jnp.float32)


def _linear(x, w):
    """``x @ w`` in the compute dtype, handed on in the output dtype."""
    policy = dtype_policy()
    return jnp.dot(x.astype(policy.compute_dtype),
                   w.astype(policy.compute_dtype)).astype(policy.output_dtype)


def rms_norm(x, gamma, eps: float):
    """``x / rms(x) * gamma`` over the last axis, reduced in float32."""
    x32 = x.astype(_wide(x.dtype))
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale * gamma.astype(x32.dtype)).astype(
        dtype_policy().output_dtype)


def rotate_interleaved(x, theta: float):
    """Rotary positions on the pairs (2i, 2i+1) of the last axis; axis 1
    is the position.  angle = position * theta**(-2i / d), in float32."""
    t, d, wide = x.shape[1], x.shape[-1], _wide(x.dtype)
    freq = theta ** (-jnp.arange(0, d, 2, dtype=wide) / d)
    angle = jnp.arange(t, dtype=wide)[:, None] * freq[None, :]
    angle = angle.reshape((1, t) + (1,) * (x.ndim - 3) + (d // 2,))
    pairs = x.astype(wide).reshape(x.shape[:-1] + (d // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def rotate_half(x, theta: float):
    """Rotary positions on the pairs (i, i + d/2) of the last axis (the
    ``rotate_half`` form); axis 1 is the position.  angle = position *
    theta**(-2i / d), in float32."""
    t, d, wide = x.shape[1], x.shape[-1], _wide(x.dtype)
    freq = theta ** (-jnp.arange(0, d, 2, dtype=wide) / d)
    angle = jnp.arange(t, dtype=wide)[:, None] * freq[None, :]
    angle = angle.reshape((1, t) + (1,) * (x.ndim - 3) + (d // 2,))
    first, second = jnp.split(x.astype(wide), 2, axis=-1)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin],
                           axis=-1).astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    """``(silu(x W_gate) * x W_up) W_down``."""
    return _linear(jax.nn.silu(_linear(x, w_gate)) * _linear(x, w_up), w_down)


def _width(input_type: InputType) -> int:
    return input_type.size if input_type.kind == "rnn" \
        else input_type.flat_size()


@register_layer("rms_norm")
@dataclasses.dataclass
class RMSNorm(Layer):
    """Root-mean-square norm over the last axis with a learned scale."""

    eps: float = 1e-6

    def init_params(self, key, input_type):
        return {"gamma": jnp.ones((_width(input_type),), self._param_dtype())}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return rms_norm(x, params["gamma"], self.eps), state


@register_layer("gated_feed_forward")
@dataclasses.dataclass
class GatedFeedForward(Layer):
    """SwiGLU feed-forward of width ``hidden``; output width = input's."""

    hidden: int = 0
    init_std: float = 0.02

    def init_params(self, key, input_type):
        d, dt = _width(input_type), self._param_dtype()
        kg, ku, kd = jax.random.split(key, 3)
        return {"W_gate": _normal(kg, (d, self.hidden), self.init_std, dt),
                "W_up": _normal(ku, (d, self.hidden), self.init_std, dt),
                "W_down": _normal(kd, (self.hidden, d), self.init_std, dt)}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return swiglu(x, params["W_gate"], params["W_up"],
                      params["W_down"]), state


@register_layer("latent_attention")
@dataclasses.dataclass
class LatentAttention(Layer):
    """Multi-head latent attention (MLA), causal, rotary inside it.

    ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` per head ``[q_nope |
    q_rope]``; ``[c_kv | k_r] = x W_kva``; ``c_kv = RMSNorm(c_kv)``;
    ``[k_nope | v] = c_kv W_kvb`` per head; ``q_rope`` and ``k_r`` (one
    head, shared by all) rotated on interleaved pairs; ``softmax(q k^T /
    sqrt(nope + rope)) v``; ``W_o``.  Keys and queries are ``nope + rope``
    wide and values ``v_head_dim``: ``ops.attention`` (the flash kernel
    from 1,024 tokens on, the einsum chain below) takes the two sizes
    apart.

    ``q_lora_rank`` 0 (a published ``null``) is one ``W_q`` and no query
    norm; ``rotary`` False (``mla_use_nope``) rotates nothing: the layer
    then knows no position, and layers beside it carry the order."""

    INPUT_KIND = "rnn"
    ATTENTION_KIND = "mla"            # ``ComputationGraph.trace_attrs``

    n_heads: int = 1
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_theta: float = 10000.0
    rotary: bool = True
    eps: float = 1e-6
    init_std: float = 0.02

    def init_params(self, key, input_type):
        d, dt, h = input_type.size, self._param_dtype(), self.n_heads
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        queries = ({"W_qa": (d, self.q_lora_rank),
                    "W_qb": (self.q_lora_rank, h * qk)}
                   if self.q_lora_rank else {"W_q": (d, h * qk)})
        shapes = {
            **queries,
            "W_kva": (d, self.kv_lora_rank + self.qk_rope_head_dim),
            "W_kvb": (self.kv_lora_rank,
                      h * (self.qk_nope_head_dim + self.v_head_dim)),
            "W_o": (h * self.v_head_dim, d),
        }
        keys = jax.random.split(key, len(shapes))
        params = {name: _normal(k, shape, self.init_std, dt)
                  for k, (name, shape) in zip(keys, shapes.items())}
        if self.q_lora_rank:
            params["q_norm"] = jnp.ones((self.q_lora_rank,), dt)
        params["kv_norm"] = jnp.ones((self.kv_lora_rank,), dt)
        return params

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        b, t, _ = x.shape
        h, nope, rope = (self.n_heads, self.qk_nope_head_dim,
                         self.qk_rope_head_dim)
        with jax.named_scope("mla"):
            if self.q_lora_rank:
                c_q = rms_norm(_linear(x, params["W_qa"]), params["q_norm"],
                               self.eps)
                q = _linear(c_q, params["W_qb"])
            else:
                q = _linear(x, params["W_q"])
            q = q.reshape(b, t, h, nope + rope)
            kv = _linear(x, params["W_kva"])
            c_kv = rms_norm(kv[..., :self.kv_lora_rank], params["kv_norm"],
                            self.eps)
            k_r = kv[..., self.kv_lora_rank:]
            if self.rotary:
                k_r = rotate_interleaved(k_r, self.rope_theta)
            kvh = _linear(c_kv, params["W_kvb"]).reshape(
                b, t, h, nope + self.v_head_dim)
            if self.rotary:
                q = jnp.concatenate(
                    [q[..., :nope],
                     rotate_interleaved(q[..., nope:], self.rope_theta)],
                    axis=-1)
            k = jnp.concatenate(
                [kvh[..., :nope],
                 jnp.broadcast_to(k_r[:, :, None, :], (b, t, h, rope))],
                axis=-1)
            ctx = multi_head_attention(
                q.reshape(b, t, -1), k.reshape(b, t, -1),
                kvh[..., nope:].reshape(b, t, -1), n_heads=h, causal=True)
            return _linear(ctx, params["W_o"]), state


def causal_taps(x, w):
    """Causal depthwise convolution along axis 1: ``y[t, c] = sum_j w[c,
    j] * x[t - (K - 1) + j, c]`` with zeros before ``t = 0``, so position
    ``t`` reads ``t - K + 1 .. t`` and rows never mix.  ``x`` ``[B, T,
    P]``, ``w`` ``[P, K]``; summed in float32 and handed on so."""
    taps, t, wide = w.shape[-1], x.shape[1], _wide(x.dtype)
    padded = jnp.pad(x.astype(wide), ((0, 0), (taps - 1, 0), (0, 0)))
    w = w.astype(wide)
    return sum(padded[:, j:j + t] * w[:, j] for j in range(taps))


def short_conv(x, w):
    """:func:`causal_taps`, then SiLU (KDA's short convolution)."""
    return jax.nn.silu(causal_taps(x, w))


@register_layer("gated_short_conv")
@dataclasses.dataclass
class GatedShortConv(Layer):
    """LFM2's double-gated short convolution, a token mixer: ``[B~ ; C~ ;
    x~] = x W_in`` (three ``d``-wide parts), ``y = C~ * causal_taps(B~ *
    x~)`` over ``taps`` positions with no activation and no bias, then
    ``W_out``.  The gates' products and the tap sum in float32."""

    INPUT_KIND = "rnn"
    ATTENTION_KIND = "conv"           # ``ComputationGraph.trace_attrs``

    taps: int = 3
    init_std: float = 0.02

    def init_params(self, key, input_type):
        d, dt = input_type.size, self._param_dtype()
        shapes = {"W_in": (d, 3 * d), "conv_w": (d, self.taps),
                  "W_out": (d, d)}
        keys = jax.random.split(key, len(shapes))
        return {name: _normal(k, shape, self.init_std, dt)
                for k, (name, shape) in zip(keys, shapes.items())}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        wide = _wide(x.dtype)
        with jax.named_scope("conv"):
            with jax.named_scope("conv.proj"):
                gate_b, gate_c, u = jnp.split(_linear(x, params["W_in"]), 3,
                                              axis=-1)
            with jax.named_scope("conv.taps"):
                z = causal_taps(gate_b.astype(wide) * u.astype(wide),
                                params["conv_w"])
            with jax.named_scope("conv.out"):
                return _linear(gate_c.astype(wide) * z, params["W_out"]), \
                    state


@register_layer("grouped_query_attention")
@dataclasses.dataclass
class GroupedQueryAttention(Layer):
    """Causal grouped-query attention as LFM2 has it: ``n_heads`` query
    heads and ``n_kv_heads`` K/V heads of ``head_dim``; an RMS norm on
    every query and key head (one ``head_dim``-wide scale each, shared by
    the heads); rotary positions on the half-split pairs (``rotate_half``)
    of both; ``softmax(q k^T / sqrt(head_dim)) v`` with query head ``h``
    reading K/V head ``h // (n_heads / n_kv_heads)``; ``W_o``.  No bias.
    ``ops.attention`` takes the flash kernels from 1,024 tokens on, the
    einsum chain below, with the same grouping and no K/V head copied."""

    INPUT_KIND = "rnn"
    ATTENTION_KIND = "gqa"            # ``ComputationGraph.trace_attrs``

    n_heads: int = 1
    n_kv_heads: int = 1
    head_dim: int = 0
    rope_theta: float = 10000.0
    eps: float = 1e-5
    init_std: float = 0.02

    def init_params(self, key, input_type):
        d, dt = input_type.size, self._param_dtype()
        q, kv = self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
        shapes = {"W_q": (d, q), "W_k": (d, kv), "W_v": (d, kv),
                  "W_o": (q, d)}
        keys = jax.random.split(key, len(shapes))
        params = {name: _normal(k, shape, self.init_std, dt)
                  for k, (name, shape) in zip(keys, shapes.items())}
        params["q_norm"] = jnp.ones((self.head_dim,), dt)
        params["k_norm"] = jnp.ones((self.head_dim,), dt)
        return params

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        b, t, _ = x.shape
        h, kv, dh = self.n_heads, self.n_kv_heads, self.head_dim
        with jax.named_scope("gqa"):
            q = _linear(x, params["W_q"]).reshape(b, t, h, dh)
            k = _linear(x, params["W_k"]).reshape(b, t, kv, dh)
            v = _linear(x, params["W_v"])
            with jax.named_scope("gqa.qk_norm"):
                q = rms_norm(q, params["q_norm"], self.eps)
                k = rms_norm(k, params["k_norm"], self.eps)
            with jax.named_scope("gqa.rope"):
                q = rotate_half(q, self.rope_theta)
                k = rotate_half(k, self.rope_theta)
            with jax.named_scope("gqa.core"):
                ctx = multi_head_attention(
                    q.reshape(b, t, h * dh), k.reshape(b, t, kv * dh), v,
                    n_heads=h, n_kv_heads=kv, causal=True)
            return _linear(ctx, params["W_o"]), state


def _compute_dot(spec: str, a, b):
    """An einsum with its operands in the compute dtype, summed in float32."""
    cd = dtype_policy().compute_dtype
    return jnp.einsum(spec, a.astype(cd), b.astype(cd),
                      preferred_element_type=_wide(cd))


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower-triangular ``a`` ``[..., C, C]``,
    ``C`` a power of two, by doubling: the inverse of ``[[P, 0], [R, S]]``
    is ``[[P^-1, 0], [-S^-1 R P^-1, S^-1]]``, from 1x1 blocks up.  Forward
    substitution in block form: no power of ``a`` is ever taken, so equal
    keys without decay (``a`` all ones, whose 32nd power holds 1e17)
    invert as exactly as anything else.  In ``a``'s dtype, products at
    ``Precision.HIGHEST``."""
    c, lead = a.shape[-1], a.shape[:-2]
    hi = jax.lax.Precision.HIGHEST
    inv, s = jnp.ones(lead + (c, 1, 1), a.dtype), 1
    while s < c:
        n = c // (2 * s)
        blocks = a.reshape(lead + (n, 2, s, n, 2, s))
        # block (2p + 1, 2p) of every pair p: the diagonal over the two n
        r = jnp.moveaxis(jnp.diagonal(blocks[..., :, 1, :, :, 0, :],
                                      axis1=-4, axis2=-2), -1, -3)
        pair = inv.reshape(lead + (n, 2, s, s))
        upper, lower = pair[..., 0, :, :], pair[..., 1, :, :]
        corner = -jnp.einsum("...ij,...jk,...kl->...il", lower, r, upper,
                             precision=hi)
        inv = jnp.concatenate(
            [jnp.concatenate([upper, jnp.zeros_like(upper)], axis=-1),
             jnp.concatenate([corner, lower], axis=-1)], axis=-2)
        s *= 2
    return inv[..., 0, :, :]


# A chunk's rows are split four ways, and each part four ways again,
# down to PAIRWISE rows (chunked_delta_rule, _decayed_scores)
SPLIT, PAIRWISE = 4, 4


@jax.checkpoint
def _pairwise_scores(rows, k, g_sum):
    """:func:`_decayed_scores` for a few rows, the decays taken pairwise in
    float32: column ``j`` is ``sum_d rows[i, d] k[j, d] exp(G[i, d] - G[j,
    d])`` for ``i >= j``, one pass over ``[..., P, d]`` a column, so that
    no ``[P, P, d]`` array is ever formed; rematerialised, so that the
    backward pass keeps no exponential either."""
    size = k.shape[-2]
    later = jnp.arange(size)[:, None] >= jnp.arange(size)[None, :]
    return jnp.stack(
        [jnp.sum(rows * k[..., j:j + 1, :] * jnp.exp(jnp.where(
            later[:, j:j + 1], g_sum - g_sum[..., j:j + 1, :], -jnp.inf)),
            axis=-1) for j in range(size)], axis=-1)


def _decayed_scores(rows, k, g_sum):
    """``sum_d rows[i, d] k[j, d] exp(G[i, d] - G[j, d])`` for ``j <= i``,
    zero above the diagonal.  ``rows`` ``[n, ..., P, d]`` (``n`` sets of
    rows scored against the same keys), ``k`` and ``g_sum`` (``G``, the
    decay's running sum, falling along ``P``) ``[..., P, d]`` -> ``[n,
    ..., P, P]`` float32.

    ``exp(G_i - G_j)`` is never split into ``exp(G_i) exp(-G_j)`` over
    all the rows: the second factor overflows float32 at decays a layer
    starts with.  The rows are split into ``SPLIT`` parts.  A later part
    against the rows before it is split about the later part's first row
    ``a``: ``exp(G_i - G_a)`` and ``exp(G_a - G_j)``, both exponents <= 0,
    each a matrix product's operand.  A part against itself is this
    function again, down to ``PAIRWISE`` rows, where the difference is
    taken as it stands."""
    size, d = k.shape[-2:]
    if size <= PAIRWISE:
        return _pairwise_scores(rows, k, g_sum)
    sub = size // SPLIT
    tiled = k.shape[:-2] + (SPLIT, sub, d)
    rows_t = rows.reshape(rows.shape[:1] + tiled)
    g_t = g_sum.reshape(tiled)
    within = _decayed_scores(rows_t, k.reshape(tiled), g_t)
    left = rows_t * jnp.exp(g_t - g_t[..., :1, :])
    out = []
    for a in range(SPLIT):
        before, parts = a * sub, [within[..., a, :, :]]
        if before:
            right = k[..., :before, :] * jnp.exp(
                g_t[..., a, :1, :] - g_sum[..., :before, :])
            parts.insert(0, _compute_dot("n...id,...jd->n...ij",
                                         left[..., a, :, :], right))
        if size - before - sub:
            parts.append(jnp.zeros(within.shape[:-3]
                                   + (sub, size - before - sub),
                                   within.dtype))
        out.append(jnp.concatenate(parts, axis=-1))
    return jnp.concatenate(out, axis=-2)


HEAD_GROUP = 8      # heads whose chunk phase is live at once


def _group_size(heads: int, head_group: int) -> int:
    """``head_group``, or all ``heads`` where they do not divide into such
    groups."""
    return heads if heads % head_group else head_group


def _map_head_groups(fn, arrays, head_axes, head_group: int):
    """``fn`` over groups of ``head_group`` heads, one group after the
    other and each rematerialised: ``arrays[i]`` has its heads on axis
    ``head_axes[i]``, and ``fn`` gets every array with that axis cut to
    one group.  Results come stacked, the groups first.  Of the two dozen
    ``[T, h d]`` float32 arrays a group's phase makes only one group's
    are ever live (8,192 tokens of 32 heads of 128 are 134 MB an array).
    Heads that do not divide into such groups are one group."""
    head_group = _group_size(arrays[0].shape[head_axes[0]], head_group)

    def grouped(x, axis):
        x = x.reshape(x.shape[:axis] + (x.shape[axis] // head_group,
                                        head_group) + x.shape[axis + 1:])
        return jnp.moveaxis(x, axis, 0)

    return jax.lax.map(jax.checkpoint(lambda xs: fn(*xs)), tuple(
        grouped(x, axis) for x, axis in zip(arrays, head_axes)))


def _chunk_phase(q, k, v, g, beta, *, chunk: int):
    """What :func:`chunked_delta_rule` computes of every chunk without the
    state, for a group of heads: ``[B, T, h, ...]`` -> ``W``, ``U~``,
    ``K exp(G_C - G)``, ``exp(G_C)`` (the scan's inputs) and ``Q exp(G)``,
    ``B`` (the read's), each ``[B, h, n, chunk, ...]``.  What only ever
    is a matrix product's operand leaves in the compute dtype.

    In ``jax.numpy``: what :func:`chunked_delta_rule` runs, and what
    ``DeltaAttention`` runs both ways where ``ops.pallas.kda_chunk`` does
    not serve its head size and chunk.  Where it does, the forward and the
    backward are that module's two kernels, the same equations at the same
    precision, with ``G`` a product with the lower triangle of ones
    (Mosaic lowers no ``cumsum``)."""
    if chunk & (chunk - 1) or chunk < PAIRWISE:
        raise ValueError(f"chunk has to be a power of two of at least "
                         f"{PAIRWISE}, got {chunk}")
    b, t = k.shape[:2]
    n, wide, cd = -(-t // chunk), g.dtype, dtype_policy().compute_dtype

    def chunks(x):
        """[B, T, h, ...] -> [B, h, n, chunk, ...], zero-padded, wide."""
        x = jnp.pad(x.astype(wide), ((0, 0), (0, n * chunk - t))
                    + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x.reshape((b, n, chunk) + x.shape[2:]), 3, 1)

    with jax.named_scope("kda.chunk"):
        q, k, v, g = chunks(q), chunks(k), chunks(v), chunks(g)
        beta = chunks(beta)[..., None]                   # [B, h, n, C, 1]
        g_sum = jnp.cumsum(g, axis=-2)
        g_end = g_sum[..., -1:, :]
        b_qk, a_kk = _decayed_scores(jnp.stack([q, k]), k, g_sum)
        strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
        solve = _unit_lower_inverse(jnp.where(strict, beta * a_kk, 0.0))
        u_free = _compute_dot("...ij,...jd->...id", solve, beta * v)
        w = _compute_dot("...ij,...jd->...id", solve,
                         beta * k * jnp.exp(g_sum))
        k_left = k * jnp.exp(g_end - g_sum)     # what is left at the end
        decay = jnp.exp(g_end[..., 0, :])[..., None]     # [B, h, n, dk, 1]
        return (w.astype(cd), u_free, k_left.astype(cd), decay,
                (q * jnp.exp(g_sum)).astype(cd), b_qk.astype(cd))


def _scan_and_read(w, u_free, k_left, decay, q_decayed, b_qk, *, t: int):
    """The state through the chunks, then every chunk's outputs, from
    :func:`_chunk_phase`'s results stacked ``[groups, B, h, n, chunk,
    ...]`` -> ``o`` ``[B, t, H, dv]`` and the last state ``[B, H, dk,
    dv]``."""
    groups, b, hg, n, chunk, dk = w.shape
    dv, wide, cd = u_free.shape[-1], u_free.dtype, w.dtype
    with jax.named_scope("kda.scan"):
        def step(state, chunk_in):
            w_c, u_c, k_c, decay_c = chunk_in
            entry = state.astype(cd)        # the state as products read it
            u = u_c - _compute_dot("...id,...de->...ie", w_c, entry)
            return (decay_c * state
                    + _compute_dot("...id,...ie->...de", k_c, u)), (entry, u)

        last, (entry, u) = jax.lax.scan(
            step, jnp.zeros((groups, b, hg, dk, dv), wide),
            tuple(jnp.moveaxis(x, 3, 0) for x in (w, u_free, k_left, decay)))

    with jax.named_scope("kda.read"):
        entry, u = jnp.moveaxis(entry, 0, 3), jnp.moveaxis(u, 0, 3)
        o = _compute_dot("...id,...de->...ie", q_decayed, entry) \
            + _compute_dot("...ij,...je->...ie", b_qk, u)
        # [groups, B, h, n, C, dv] -> [B, n, C, groups, h, dv]
        o = jnp.transpose(o, (1, 3, 4, 0, 2, 5)).reshape(
            b, n * chunk, groups * hg, dv)[:, :t]
    return o, jnp.moveaxis(last, 0, 1).reshape(b, groups * hg, dk, dv)


def chunked_delta_rule(q, k, v, g, beta, *, chunk: int = 64,
                       head_group: int = HEAD_GROUP):
    """The gated delta rule with a decay a key channel (Kimi Delta
    Attention, arXiv:2510.26692), in chunks.  A head's state ``S``
    ``[d_k, d_v]`` starts at zero and follows ``S'_t = Diag(exp(g_t))
    S_{t-1}``, ``S_t = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T``, ``o_t =
    S_t^T q_t``.

    ``q``, ``k``, ``g`` ``[B, T, H, d_k]`` (``g`` the log decay, <= 0,
    float32 or wider), ``v`` ``[B, T, H, d_v]``, ``beta`` ``[B, T, H]``
    -> ``o`` ``[B, T, H, d_v]`` and the last state ``[B, H, d_k, d_v]``,
    in ``g``'s dtype.

    With ``G`` the running sum of ``g`` inside a chunk, ``A_ij = beta_i
    sum_d k_id k_jd exp(G_id - G_jd)`` (``j < i``) and ``B_ij`` the same
    with ``q_i`` and no ``beta`` (``j <= i``): ``T = (I + A)^-1``, ``U~ =
    T (beta V)``, ``W = T (beta K exp(G))``, for all chunks at once
    (:func:`_chunk_phase`, ``head_group`` heads at a time); then chunk by
    chunk, ``S`` the state on entry, ``U = U~ - W S`` and ``S <-
    Diag(exp(G_C)) S + (K exp(G_C - G))^T U`` (the only sequential part:
    ``T / chunk`` steps of two products); and again for all chunks at
    once ``O = (Q exp(G)) S + B U`` (:func:`_scan_and_read`).  Every
    exponent is <= 0 as written.  Matrix products take their operands in
    the compute dtype and sum in float32; the inversion, the decays and
    the state stay float32.  A length that is no multiple of ``chunk`` is
    padded with tokens that change nothing (``k = 0``, ``g = 0``).

    All of it in ``jax.numpy``, at any shape and both ways: the plain form
    of what ``DeltaAttention`` runs, whose chunk phase goes forward
    through the ``tpudl_kda_chunk`` kernel and backward through
    ``tpudl_kda_chunk_bwd`` where they can."""
    return _scan_and_read(*_map_head_groups(
        functools.partial(_chunk_phase, chunk=chunk), (q, k, v, g, beta),
        (2,) * 5, head_group), t=k.shape[1])


def _kda_inputs(q, k, v, f, beta, conv_q, conv_k, conv_v, a_log, dt_bias):
    """A group of ``h`` heads from the projections' outputs (``q``, ``k``,
    ``v``, ``f`` ``[B, T, h, d]``, ``beta`` ``[B, T, h]``; taps ``[h, d,
    K]``, ``a_log`` ``[h]``, ``dt_bias`` ``[h, d]``) to the chunk phase's
    inputs: short convolutions, norms and gates, in float32."""
    b, t, h, dh = q.shape
    wide = _wide(q.dtype)

    def unit(y):                      # a head's L2 norm, eps under the root
        return y * jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True)
                                 + 1e-6)

    def conv(y, taps):
        return short_conv(y.reshape(b, t, h * dh),
                          taps.reshape(h * dh, -1)).reshape(y.shape)

    with jax.named_scope("kda.conv"):
        q = unit(conv(q, conv_q)) * dh ** -0.5
        k, v = unit(conv(k, conv_k)), conv(v, conv_v)
    with jax.named_scope("kda.gate"):
        g = -jnp.exp(a_log.astype(wide))[:, None] * jax.nn.softplus(
            f.astype(wide) + dt_bias.astype(wide))
        beta = jax.nn.sigmoid(beta.astype(wide))
    return q, k, v, g, beta


# where each argument of _kda_inputs has its heads
_KDA_HEAD_AXES = (2,) * 5 + (0,) * 5


def _kda_grouped(xs, chunk: int):
    """The chunk phase from the projections' outputs in ``jax.numpy``, by
    groups of ``HEAD_GROUP`` heads, each rematerialised."""
    return _map_head_groups(
        lambda *group: _chunk_phase(*_kda_inputs(*group), chunk=chunk),
        xs, _KDA_HEAD_AXES, HEAD_GROUP)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _kda_chunks(chunk, compute_dtype, interpret, *xs):
    return _kda_chunks_fwd(chunk, compute_dtype, interpret, *xs)[0]


def _kda_chunks_fwd(chunk, compute_dtype, interpret, *xs):
    from deeplearning4j_tpu.ops.pallas.kda_chunk import kda_chunk
    q, k, v, g, beta = _kda_inputs(*xs)
    with jax.named_scope("kda.chunk"):
        chunked = kda_chunk(q, k, v, g, beta, chunk=chunk,
                            head_group=_group_size(q.shape[2], HEAD_GROUP),
                            compute_dtype=compute_dtype, interpret=interpret)
    return chunked, xs


def _kda_chunks_bwd(chunk, compute_dtype, interpret, xs, cts):
    return _kda_kernel_vjp(xs, cts, chunk=chunk, compute_dtype=compute_dtype,
                           interpret=interpret)


_kda_chunks.defvjp(_kda_chunks_fwd, _kda_chunks_bwd)


@functools.partial(jax.jit, static_argnames=("chunk", "compute_dtype",
                                             "interpret"))
def _kda_kernel_vjp(xs, cts, *, chunk: int, compute_dtype, interpret: bool):
    """The cotangents of the projections' outputs from those of the chunk
    phase's six results: the ``tpudl_kda_chunk_bwd`` kernel for all heads
    at once, then back through the convolutions, norms and gates
    (``jax.vjp`` of :func:`_kda_inputs`, recomputed from ``xs``).  One jit
    for every layer of a shape: the step traces and lowers it once.

    Memory: the kernel's float32 inputs (four ``[T, H d]`` arrays, 537 MB
    at the Kimi cell's shapes) are made only once the cotangents are
    there (the barrier: else the compiler computes them from ``xs`` early,
    beside the scan's backward, and the step's temporaries grow by half a
    gigabyte), and the convolutions' and gates' own residuals are
    recomputed in their backward rather than kept beside the kernel."""
    from deeplearning4j_tpu.ops.pallas.kda_chunk import kda_chunk_bwd
    xs, cts = jax.lax.optimization_barrier((xs, cts))
    inputs, pull = jax.vjp(jax.checkpoint(_kda_inputs), *xs)
    with jax.named_scope("kda.chunk"):
        grads = kda_chunk_bwd(
            *inputs, cts, chunk=chunk,
            head_group=_group_size(inputs[0].shape[2], HEAD_GROUP),
            compute_dtype=compute_dtype, interpret=interpret)
    return pull(grads)


@functools.partial(jax.jit, static_argnames=("chunk", "compute_dtype",
                                             "interpret"))
def _kda_kernel_chunks(xs, *, chunk: int, compute_dtype, interpret: bool):
    """:func:`_kda_grouped`'s results through the ``tpudl_kda_chunk``
    kernel for all heads at once (short convolutions, norms and gates in
    ``jax.numpy`` before it), backward by :func:`_kda_kernel_vjp` from
    the same inputs, which are all the backward pass keeps.  One jit for
    every layer of a shape: the step traces and lowers it once."""
    return _kda_chunks(chunk, compute_dtype, interpret, *xs)


@register_layer("delta_attention")
@dataclasses.dataclass
class DeltaAttention(Layer):
    """Kimi Delta Attention (KDA): linear attention whose per-head state
    ``[head_dim, head_dim]`` follows a gated delta rule along the
    sequence, causal by construction and with no other position signal.

    ``q, k, v = short_conv(x W_q), short_conv(x W_k), short_conv(x W_v)``
    (depthwise, causal, ``conv_taps`` taps, SiLU); per head ``q = q /
    ||q|| / sqrt(head_dim)``, ``k = k / ||k||``; the log decay a key
    channel ``g = -exp(A_log) softplus(x W_fa W_fb + dt_bias)``; ``beta =
    sigmoid(x W_beta)`` a head; :func:`chunked_delta_rule`; then a per-head
    RMS norm (one ``head_dim``-wide scale for all heads) gated by
    ``sigmoid(x W_ga W_gb)``, and ``W_o``.  Both low-rank gates have rank
    ``head_dim``.

    Where the chunk phase runs: where ``head_dim`` fills the lanes and
    ``chunk`` the float32 sublanes (``ops.pallas.kda_chunk.takes``; the
    Kimi cell's 128 and 64), its forward, and the rematerialised run's
    recomputed forward, is the ``tpudl_kda_chunk`` kernel for all heads
    at once after the convolutions and gates in ``jax.numpy``; its
    backward pass is the ``tpudl_kda_chunk_bwd`` kernel for all heads at
    once, from the projections' outputs it keeps, then the convolutions'
    and gates' own backward in ``jax.numpy`` (rematerialised: their
    residuals would not fit beside the kernel's operands).  At any other
    shape both ways are the ``jax.numpy`` path, by groups of
    ``HEAD_GROUP`` heads each rematerialised.  The scan and the read are
    ``jax.numpy`` at every shape."""

    INPUT_KIND = "rnn"
    ATTENTION_KIND = "kda"            # ``ComputationGraph.trace_attrs``

    n_heads: int = 1
    head_dim: int = 0
    conv_taps: int = 4
    chunk: int = 64
    eps: float = 1e-5
    init_std: float = 0.02

    def init_params(self, key, input_type):
        d, dt, h = input_type.size, self._param_dtype(), self.n_heads
        p, rank = h * self.head_dim, self.head_dim
        shapes = {"W_q": (d, p), "W_k": (d, p), "W_v": (d, p),
                  "conv_q": (p, self.conv_taps), "conv_k": (p, self.conv_taps),
                  "conv_v": (p, self.conv_taps),
                  "W_fa": (d, rank), "W_fb": (rank, p), "W_beta": (d, h),
                  "W_ga": (d, rank), "W_gb": (rank, p), "W_o": (p, d)}
        keys = jax.random.split(key, len(shapes) + 2)
        params = {name: _normal(k, shape, self.init_std, dt)
                  for k, (name, shape) in zip(keys, shapes.items())}
        # a step's log decay starts in about [-1.6, -0.001]
        params["A_log"] = jnp.log(jax.random.uniform(
            keys[-2], (h,), jnp.float32, 1.0, 16.0)).astype(dt)
        step = jnp.exp(jax.random.uniform(
            keys[-1], (p,), jnp.float32, jnp.log(0.001), jnp.log(0.1)))
        params["dt_bias"] = (step + jnp.log(-jnp.expm1(-step))).astype(dt)
        params["o_norm"] = jnp.ones((self.head_dim,), dt)
        return params

    @property
    def kernel(self) -> str | None:
        """The kernel the chunk phase runs forward as, where the shapes
        allow one (``ComputationGraph.trace_attrs``).  Imported here: a
        model without delta attention never loads Pallas."""
        from deeplearning4j_tpu.ops.pallas import kda_chunk
        return kda_chunk.KERNEL_NAME if kda_chunk.takes(
            self.head_dim, self.chunk) else None

    @property
    def bwd_kernel(self) -> str | None:
        """The kernel the chunk phase's backward pass runs as, wherever
        its forward runs as :attr:`kernel`."""
        from deeplearning4j_tpu.ops.pallas import kda_chunk
        return kda_chunk.BWD_KERNEL_NAME if self.kernel else None

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        b, t, _ = x.shape
        h, dh, wide = self.n_heads, self.head_dim, _wide(x.dtype)

        def heads(y):
            return y.reshape(b, t, h, dh)

        with jax.named_scope("kda"):
            with jax.named_scope("kda.proj"):
                q, k, v = (heads(_linear(x, params[name]))
                           for name in ("W_q", "W_k", "W_v"))
                f = heads(_linear(_linear(x, params["W_fa"]), params["W_fb"]))
                beta = _linear(x, params["W_beta"])
                gate = _linear(_linear(x, params["W_ga"]), params["W_gb"])
            xs = (q, k, v, f, beta,
                  *(params[name].reshape(h, dh, self.conv_taps)
                    for name in ("conv_q", "conv_k", "conv_v")),
                  params["A_log"], params["dt_bias"].reshape(h, dh))
            if self.kernel:
                chunked = _kda_kernel_chunks(
                    xs, chunk=self.chunk,
                    compute_dtype=jnp.dtype(dtype_policy().compute_dtype),
                    interpret=jax.default_backend() != "tpu")
            else:
                chunked = _kda_grouped(xs, self.chunk)
            o, _ = _scan_and_read(*chunked, t=t)
            with jax.named_scope("kda.out"):
                o = rms_norm(o, params["o_norm"], self.eps).astype(wide) \
                    * jax.nn.sigmoid(heads(gate).astype(wide))
                return _linear(o.reshape(b, t, h * dh), params["W_o"]), state


def route(logits, bias, *, top_k: int, scale: float, normalize: bool = True,
          eps: float = 1e-20):
    """Sigmoid scores, the ``top_k`` experts with the largest ``score +
    bias`` (the ``noaux_tc`` selection bias takes part in the choice and
    not in the gate), gates ``scale * s_e / (sum of the chosen s +
    eps)``.  ``logits`` float32 ``[N, E]`` -> (experts ``[N, k]`` int32,
    gates ``[N, k]`` float32)."""
    scores = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if normalize:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + eps)
    return chosen, picked * scale


def dropless_experts(x, chosen, gates, w_gate, w_up, w_down, *,
                     first_expert: int):
    """What the held experts add: ``sum_e gate_e * Expert_e(x)`` over the
    experts ``first_expert .. first_expert + E`` whose weights are given
    (``[E, d, w]``, ``[E, d, w]``, ``[E, w, d]``), for ``x`` ``[N, d]``
    routed as ``chosen``/``gates`` ``[N, k]`` over ALL the experts.

    Dropless: the (token, expert) pairs whose expert is held are sorted
    by expert and run through grouped matrix products
    (``jax.lax.ragged_dot``); there is no capacity, so the buffer is as
    long as the most pairs that can be held, ``N * min(k, E)``, and rows
    past the pairs there are count as nothing.  Returns the sum in float32
    ``[N, d]`` and the held experts' pair counts ``[E]``."""
    n, k = chosen.shape
    held = w_gate.shape[0]
    local = chosen.reshape(-1) - first_expert
    key = jnp.where((local >= 0) & (local < held), local, held)
    rows = n * min(k, held)
    order = jnp.argsort(key, stable=True)[:rows]
    sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    token = order // k
    valid = key[order] < held
    weight = gates.reshape(-1)[order]
    policy = dtype_policy()
    # rows past the pairs are never written by the grouped product: on the
    # chip they hold whatever the buffer held (NaN, on the first run).
    # Zero rows in, and a ``where`` on the way out BEFORE the gate's
    # product (0 x NaN is NaN in the gate's gradient), keep that out of
    # both passes
    xs = jnp.where(valid[:, None], x.astype(policy.compute_dtype)[token], 0)

    def grouped(a, w):
        return jax.lax.ragged_dot(a, w.astype(policy.compute_dtype), sizes)

    y = grouped(jax.nn.silu(grouped(xs, w_gate)) * grouped(xs, w_up), w_down)
    y = jnp.where(valid[:, None], y.astype(weight.dtype), 0.0) \
        * weight[:, None]
    return jnp.zeros((n, x.shape[-1]), y.dtype).at[token].add(y), sizes


@register_layer("routed_experts")
@dataclasses.dataclass
class RoutedExperts(Layer):
    """Sigmoid-routed SwiGLU experts beside a shared expert, dropless.

    The layer is told its share of an expert-parallel deployment: the
    router is ``n_routed_experts`` wide (the published width, always),
    and ``experts_held`` of them, from ``first_expert`` on, live here
    (0 = all).  It routes over all of them and computes what its own
    experts add; what absent experts would have added is left out, and
    nothing stands in for their chips or the exchange.  With all held it
    is the whole layer.  The shared expert is on every chip.

    State: ``bias``, the ``noaux_tc`` selection bias (a buffer, never a
    gradient; nothing here updates it), and the last step's routing
    load as float32 scalars: ``moe_pairs`` (pairs computed here),
    ``moe_pairs_max_expert`` (the busiest held expert's) and
    ``moe_tokens``.  ``Trainer`` folds them into the registry on a step
    whose loss a listener has already read."""

    n_routed_experts: int = 0
    experts_held: int = 0
    first_expert: int = 0
    top_k: int = 1
    hidden: int = 0                   # each routed expert's width
    shared_hidden: int = 0            # the shared expert's; 0 = none
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    norm_eps: float = 1e-20           # added to the chosen scores' sum
    init_std: float = 0.02

    # state key -> registry counter (Trainer._fold_step_counters)
    STEP_COUNTERS = {"moe_pairs": "tpudl_moe_pairs_total",
                     "moe_pairs_max_expert": "tpudl_moe_pairs_max_expert_total",
                     "moe_tokens": "tpudl_moe_tokens_total"}

    def _held(self) -> int:
        return self.experts_held or self.n_routed_experts

    def init_params(self, key, input_type):
        d, dt, e = _width(input_type), self._param_dtype(), self._held()
        shapes = {"W_router": (d, self.n_routed_experts),
                  "W_gate": (e, d, self.hidden), "W_up": (e, d, self.hidden),
                  "W_down": (e, self.hidden, d)}
        if self.shared_hidden:
            shapes.update({"shared_W_gate": (d, self.shared_hidden),
                           "shared_W_up": (d, self.shared_hidden),
                           "shared_W_down": (self.shared_hidden, d)})
        keys = jax.random.split(key, len(shapes))
        return {name: _normal(k, shape, self.init_std, dt)
                for k, (name, shape) in zip(keys, shapes.items())}

    def init_state(self, input_type):
        state = {name: jnp.zeros((), jnp.float32) for name in self.STEP_COUNTERS}
        state["bias"] = jnp.zeros((self.n_routed_experts,), jnp.float32)
        return state

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        flat = x.reshape(-1, x.shape[-1])
        with jax.named_scope("moe.route"):
            wide = _wide(params["W_router"].dtype)
            logits = jnp.dot(flat.astype(wide),
                             params["W_router"].astype(wide),
                             precision=jax.lax.Precision.HIGHEST)
            chosen, gates = route(
                logits, jax.lax.stop_gradient(state["bias"]),
                top_k=self.top_k, scale=self.routed_scaling_factor,
                normalize=self.norm_topk_prob, eps=self.norm_eps)
        with jax.named_scope("moe.experts"):
            y, sizes = dropless_experts(
                flat, chosen, gates, params["W_gate"], params["W_up"],
                params["W_down"], first_expert=self.first_expert)
        if self.shared_hidden:
            with jax.named_scope("moe.shared"):
                y = y + swiglu(flat, params["shared_W_gate"],
                               params["shared_W_up"],
                               params["shared_W_down"]).astype(y.dtype)
        new_state = {
            "bias": state["bias"],
            "moe_pairs": jnp.sum(sizes).astype(jnp.float32),
            "moe_pairs_max_expert": jnp.max(sizes).astype(jnp.float32),
            "moe_tokens": jnp.float32(flat.shape[0]),
        }
        return y.astype(dtype_policy().output_dtype).reshape(x.shape), \
            new_state


@register_layer("causal_lm_output")
@dataclasses.dataclass
class CausalLMOutput(Layer):
    """Next-token loss over a head that is owned once and read by
    ``n_streams`` streams stacked along the batch axis (a ``StackVertex``
    in front): stream 0 is the model's own, predicting ``t_{i+1}`` at
    position ``i``; stream ``j`` is the ``j``-th multi-token-prediction
    module's, predicting ``t_{i+1+j}``, weighted ``mtp_weight``.  The
    labels are the ``[B, S]`` int32 ids themselves: the shift is taken
    here, on the device, and the last ``1 + j`` positions of stream ``j``
    have no target and are left out of its mean.  Each stream's logits
    are rematerialised in the backward pass, so two ``[B*S, V]`` float32
    sets and their gradients are never live together.  ``apply`` gives
    stream 0's logits.

    The head is untied (``W`` ``[d, n_out]``, this layer's own), or with
    ``tie_to`` the ``W`` ``[n_out, d]`` of that vertex, an embedding:
    ``logits = x E^T``.  A tied head owns no parameter; the graph hands it
    the one leaf, whose gradient is then the sum of both uses."""

    INPUT_KIND = "rnn"

    n_out: int = 0                    # the vocabulary held here
    n_streams: int = 1
    mtp_weight: float = 0.0
    init_std: float = 0.02
    tie_to: str = ""                  # ComputationGraph's parameter sharing

    def get_output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def init_params(self, key, input_type):
        if self.tie_to:
            return {}
        return {"W": _normal(key, (input_type.size, self.n_out),
                             self.init_std, self._param_dtype())}

    def _logits(self, w, x):
        cd = dtype_policy().compute_dtype
        if self.tie_to:
            return jnp.einsum("...d,vd->...v", x.astype(cd), w.astype(cd),
                              preferred_element_type=_wide(cd))
        return jnp.dot(x.astype(cd), w.astype(cd),
                       preferred_element_type=_wide(cd))

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope("lm_head"):
            own = x[:x.shape[0] // self.n_streams]
            return self._logits(params["W"], own), state

    def compute_score_array(self, params, state, x, labels, *, train=False,
                            rng=None, mask=None):
        """``[B]``: each row's mean loss of stream 0 plus ``mtp_weight``
        times that of every further stream."""
        labels = labels.astype(jnp.int32)
        b, t = labels.shape

        @functools.partial(jax.checkpoint, static_argnums=(2,))
        def stream_loss(w, h, shift):
            logp = jax.nn.log_softmax(self._logits(w, h), axis=-1)
            target = jnp.roll(labels, -shift, axis=1)
            picked = jnp.take_along_axis(logp, target[..., None],
                                         axis=-1)[..., 0]
            has_target = jnp.arange(t) < t - shift
            return -jnp.sum(jnp.where(has_target[None, :], picked, 0.0),
                            axis=1) / max(t - shift, 1)

        with jax.named_scope("lm_head"):
            score = 0.0
            for j in range(self.n_streams):
                loss = stream_loss(params["W"], x[j * b:(j + 1) * b], 1 + j)
                score = score + (loss if j == 0 else self.mtp_weight * loss)
            return score

    def labels_required(self) -> bool:
        return True
