"""The layers of a current decoder-only language model (ROADMAP R1-R3).

No reference parity: deeplearning4j stops at 2018's encoder.  These are
the blocks of DeepSeek-V3's family as its published configurations name
them: RMS norm, the gated (SwiGLU) feed-forward, latent attention (MLA)
with rotary positions inside it, routed experts beside a shared expert
with a layer that is told which experts it holds, and the next-token
output layer that owns the head once for the main and the
multi-token-prediction stream.  ``models.zoo.joyai_llm_flash`` wires them
into a ``ComputationGraph``.

Precision follows the dtype policy as ``DenseLayer`` does (float32
parameters cast to the compute dtype at use, outputs in the output
dtype); norm reductions, rotary angles, the router's logits and gates,
and the loss are float32 at least, whatever the policy (float64 under the
gradient checks' float64 policy).
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
    apart."""

    INPUT_KIND = "rnn"

    n_heads: int = 1
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_theta: float = 10000.0
    eps: float = 1e-6
    init_std: float = 0.02

    def init_params(self, key, input_type):
        d, dt, h = input_type.size, self._param_dtype(), self.n_heads
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        shapes = {
            "W_qa": (d, self.q_lora_rank),
            "W_qb": (self.q_lora_rank, h * qk),
            "W_kva": (d, self.kv_lora_rank + self.qk_rope_head_dim),
            "W_kvb": (self.kv_lora_rank,
                      h * (self.qk_nope_head_dim + self.v_head_dim)),
            "W_o": (h * self.v_head_dim, d),
        }
        keys = jax.random.split(key, len(shapes))
        params = {name: _normal(k, shape, self.init_std, dt)
                  for k, (name, shape) in zip(keys, shapes.items())}
        params["q_norm"] = jnp.ones((self.q_lora_rank,), dt)
        params["kv_norm"] = jnp.ones((self.kv_lora_rank,), dt)
        return params

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        b, t, _ = x.shape
        h, nope, rope = (self.n_heads, self.qk_nope_head_dim,
                         self.qk_rope_head_dim)
        with jax.named_scope("mla"):
            c_q = rms_norm(_linear(x, params["W_qa"]), params["q_norm"],
                           self.eps)
            q = _linear(c_q, params["W_qb"]).reshape(b, t, h, nope + rope)
            kv = _linear(x, params["W_kva"])
            c_kv = rms_norm(kv[..., :self.kv_lora_rank], params["kv_norm"],
                            self.eps)
            k_r = rotate_interleaved(kv[..., self.kv_lora_rank:],
                                     self.rope_theta)
            kvh = _linear(c_kv, params["W_kvb"]).reshape(
                b, t, h, nope + self.v_head_dim)
            q = jnp.concatenate(
                [q[..., :nope],
                 rotate_interleaved(q[..., nope:], self.rope_theta)], axis=-1)
            k = jnp.concatenate(
                [kvh[..., :nope],
                 jnp.broadcast_to(k_r[:, :, None, :], (b, t, h, rope))],
                axis=-1)
            ctx = multi_head_attention(
                q.reshape(b, t, -1), k.reshape(b, t, -1),
                kvh[..., nope:].reshape(b, t, -1), n_heads=h, causal=True)
            return _linear(ctx, params["W_o"]), state


def route(logits, bias, *, top_k: int, scale: float, normalize: bool = True):
    """Sigmoid scores, the ``top_k`` experts with the largest ``score +
    bias`` (the ``noaux_tc`` selection bias takes part in the choice and
    not in the gate), gates ``scale * s_e / sum of the chosen s``.
    ``logits`` float32 ``[N, E]`` -> (experts ``[N, k]`` int32, gates
    ``[N, k]`` float32)."""
    scores = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if normalize:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
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
                normalize=self.norm_topk_prob)
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
    """Next-token loss over an untied head that is owned once and read by
    ``n_streams`` streams stacked along the batch axis (a ``StackVertex``
    in front): stream 0 is the model's own, predicting ``t_{i+1}`` at
    position ``i``; stream ``j`` is the ``j``-th multi-token-prediction
    module's, predicting ``t_{i+1+j}``, weighted ``mtp_weight``.  The
    labels are the ``[B, S]`` int32 ids themselves: the shift is taken
    here, on the device, and the last ``1 + j`` positions of stream ``j``
    have no target and are left out of its mean.  Each stream's logits
    are rematerialised in the backward pass, so two ``[B*S, V]`` float32
    sets and their gradients are never live together.  ``apply`` gives
    stream 0's logits."""

    INPUT_KIND = "rnn"

    n_out: int = 0                    # the vocabulary held here
    n_streams: int = 1
    mtp_weight: float = 0.0
    init_std: float = 0.02

    def get_output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def init_params(self, key, input_type):
        return {"W": _normal(key, (input_type.size, self.n_out),
                             self.init_std, self._param_dtype())}

    def _logits(self, w, x):
        cd = dtype_policy().compute_dtype
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
