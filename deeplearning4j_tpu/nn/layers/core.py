"""Core feed-forward layers.

Parity targets (deeplearning4j-nn):
- ``conf/layers/DenseLayer.java`` + ``layers/feedforward/dense/DenseLayer.java``
- ``conf/layers/OutputLayer.java`` + ``layers/OutputLayer.java``
- ``conf/layers/LossLayer.java``, ``ActivationLayer.java``, ``DropoutLayer.java``
- ``conf/layers/EmbeddingLayer.java``, ``EmbeddingSequenceLayer.java``
- ``conf/layers/BatchNormalization.java`` + ``layers/normalization/BatchNormalization.java``

The matmul is ``x @ W + b`` on the MXU via ``jnp.dot`` in the compute dtype
(bf16 under the bf16 policy); params stay float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.config import dtype_policy
from deeplearning4j_tpu.nn import activations, losses
from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer


@register_layer("dense")
@dataclasses.dataclass
class DenseLayer(Layer):
    """Fully connected: y = act(x @ W + b).  W: [nIn, nOut]."""

    n_out: int = 0
    has_bias: bool = True

    def get_output_type(self, input_type: InputType) -> InputType:
        if input_type.kind == "rnn":
            # DL4J auto-inserts RnnToFeedForward/FeedForwardToRnn
            # preprocessor pairs around a DenseLayer fed by an RNN layer —
            # net effect: time-distributed dense, [B,T,nIn] → [B,T,nOut].
            return InputType.recurrent(self.n_out, input_type.timesteps)
        return InputType.feed_forward(self.n_out)

    def init_params(self, key, input_type):
        n_in = input_type.size if input_type.kind == "rnn" else input_type.flat_size()
        params = {"W": self._init_weight(key, (n_in, self.n_out), n_in, self.n_out)}
        if self.has_bias:
            params["b"] = self._init_bias((self.n_out,))
        return params

    def pre_output(self, params, state, x, *, train=False, rng=None):
        policy = dtype_policy()
        x = self._maybe_dropout(x, train, rng)
        quantized = "W_q" in params   # nn.quantize: per-channel int8 weights
        n_in = (params["W_q"] if quantized else params["W"]).shape[0]
        if x.ndim > 2 and x.shape[-1] == n_in:
            pass  # [B,T,C] time-distributed path: contract the last axis
        elif x.ndim > 2:
            x = x.reshape(x.shape[0], -1)  # CNN→FF flatten
        if quantized:
            # int8 weights stream 1 byte/param from HBM; the dequant is
            # fused into the matmul (Pallas kernel on TPU, jnp oracle
            # elsewhere) — activations stay in the compute dtype
            from deeplearning4j_tpu.ops.pallas.quant_matmul import int8_matmul
            xc = x.astype(policy.compute_dtype)
            lead = xc.shape[:-1]
            y = int8_matmul(xc.reshape(-1, xc.shape[-1]),
                            params["W_q"], params["W_scale"])
            y = y.reshape(lead + (y.shape[-1],))
        else:
            y = jnp.dot(x.astype(policy.compute_dtype),
                        params["W"].astype(policy.compute_dtype))
        if self.has_bias:
            y = y + params["b"].astype(y.dtype)
        return y.astype(policy.output_dtype)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        z = self.pre_output(params, state, x, train=train, rng=rng)
        return activations.get(self.activation or "identity")(z), state


@register_layer("output")
@dataclasses.dataclass
class OutputLayer(DenseLayer):
    """Dense + loss head (``conf/layers/OutputLayer.java``).  ``apply``
    returns the activated output; ``compute_score_array`` pairs the
    pre-activation with the loss (stable fused softmax/sigmoid paths)."""

    loss: Any = "mcxent"

    def get_output_type(self, input_type: InputType) -> InputType:
        if input_type.kind == "rnn":
            raise ValueError(
                "OutputLayer cannot follow a recurrent layer — use "
                "RnnOutputLayer for per-timestep output, or wrap the RNN in "
                "LastTimeStep/GlobalPoolingLayer (DL4J config-validation parity)")
        return InputType.feed_forward(self.n_out)

    def compute_score_array(self, params, state, x, labels, *, train=False,
                            rng=None, mask=None):
        z = self.pre_output(params, state, x, train=train, rng=rng)
        # loss math (softmax/log/…) in at-least-f32 — bf16 output policies
        # keep the big tensors cheap but the scalar-score path exact
        z = z.astype(jnp.promote_types(z.dtype, jnp.float32))
        loss_fn = losses.get(self.loss)
        score = loss_fn(labels, z, self.activation or "identity", mask)
        return score

    def labels_required(self) -> bool:
        return True


@register_layer("loss")
@dataclasses.dataclass
class LossLayer(Layer):
    """Loss without params (``conf/layers/LossLayer.java``): applies
    activation + loss to its input directly."""

    loss: Any = "mcxent"

    def has_params(self) -> bool:
        return False

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return activations.get(self.activation or "identity")(x), state

    def compute_score_array(self, params, state, x, labels, *, train=False,
                            rng=None, mask=None):
        x = x.astype(jnp.promote_types(x.dtype, jnp.float32))
        loss_fn = losses.get(self.loss)
        return loss_fn(labels, x, self.activation or "identity", mask)

    def labels_required(self) -> bool:
        return True


@register_layer("activation")
@dataclasses.dataclass
class ActivationLayer(Layer):
    """Standalone activation (``conf/layers/ActivationLayer.java``)."""

    def has_params(self) -> bool:
        return False

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return activations.get(self.activation or "identity")(x), state


@register_layer("dropout")
@dataclasses.dataclass
class DropoutLayer(Layer):
    """Standalone dropout (``conf/layers/DropoutLayer.java``); ``dropout``
    field is the retain probability per DL4J convention."""

    def has_params(self) -> bool:
        return False

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return self._maybe_dropout(x, train, rng), state


@register_layer("embedding")
@dataclasses.dataclass
class EmbeddingLayer(Layer):
    """Index → vector lookup (``conf/layers/EmbeddingLayer.java``): input is
    one int index per example; equivalent to a Dense over one-hot but
    executed as a gather (libnd4j ``gather`` declarable op → jnp.take)."""

    n_in: int = 0   # vocab size
    n_out: int = 0
    has_bias: bool = True

    def get_output_type(self, input_type: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)

    def init_params(self, key, input_type):
        n_in = self.n_in or input_type.flat_size()
        params = {"W": self._init_weight(key, (n_in, self.n_out), n_in, self.n_out)}
        if self.has_bias:
            params["b"] = self._init_bias((self.n_out,))
        return params

    def _lookup(self, params, idx):
        """Gather rows; a quantized table gathers int8 rows (1 byte per
        element off HBM) and applies the per-channel scale after.  The
        result lands in the policy COMPUTE dtype — an f32 result under a
        bf16 policy would widen every [B,T,D] activation downstream,
        exactly the upcast the quantized path exists to avoid."""
        if "W_q" in params:
            y = (jnp.take(params["W_q"], idx, axis=0).astype(jnp.float32)
                 * params["W_scale"])
            return y.astype(dtype_policy().compute_dtype)
        return jnp.take(params["W"], idx, axis=0)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        idx = x.astype(jnp.int32)
        if idx.ndim == 2 and idx.shape[-1] == 1:
            idx = idx[..., 0]
        y = self._lookup(params, idx)
        if self.has_bias:
            y = y + params["b"]
        return activations.get(self.activation or "identity")(y), state


@register_layer("embedding_sequence")
@dataclasses.dataclass
class EmbeddingSequenceLayer(EmbeddingLayer):
    """Sequence of indices → [B, T, nOut] (``EmbeddingSequenceLayer.java``).
    Output is time-major-free NTC (batch, time, channels)."""

    def get_output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        idx = x.astype(jnp.int32)
        if idx.ndim == 3 and idx.shape[-1] == 1:
            idx = idx[..., 0]
        y = self._lookup(params, idx)  # [B, T, nOut]
        if self.has_bias:
            y = y + params["b"]
        return activations.get(self.activation or "identity")(y), state


@register_layer("batch_norm")
@dataclasses.dataclass
class BatchNormalization(Layer):
    """Batch normalization over the channel (last) axis
    (``conf/layers/BatchNormalization.java``; libnd4j ``batchnorm`` op and
    its cuDNN platform engine — here a fused XLA pattern).

    ``decay`` is the running-average decay (DL4J default 0.9):
    running = decay * running + (1-decay) * batch_stat.

    Training statistics are taken in ONE pass over the activation, in
    float32 or wider: ``mean = sum(x)/n`` and
    ``var = max(sum(x*x)/n - mean*mean, 0)`` (biased), as flax's
    ``BatchNorm`` takes its own.  Both sums
    depend on ``x`` alone, so the compiler reads ``x`` once for the two
    (``jnp.mean`` then ``jnp.var`` reads it twice, and a third time in the
    backward); autodiff differentiates the expression as written.

    The price is cancellation, which two passes did not have: float32
    loses about ``(mean / std)**2 * 1e-7`` of the variance, and more the
    more elements a channel sums.  That is rounding where |mean| is of
    the order of std: images, and everything behind an earlier
    normalisation (a convolution or dense layer in front hands on its
    input's ratio).  It is not for raw features with ``|mean| >> std``,
    on every step: at ``mean = 1e2 * std`` the variance reads up to 4%
    off; at ``1e3`` 10-40% off, or 0 (the clamp) where thousands of
    elements are summed; at ``1e4`` nothing but rounding, 0 or many times
    too large, so the output is finite but scaled by up to ``rsqrt(eps)``.
    The mean stays right.  Standardise such inputs.
    """

    decay: float = 0.9
    eps: float = 1e-5
    lock_gamma_beta: bool = False
    use_gamma_beta: bool = True

    def get_output_type(self, input_type: InputType) -> InputType:
        return input_type

    def _num_features(self, input_type: InputType) -> int:
        if input_type.kind == "cnn":
            return input_type.channels
        if input_type.kind == "cnn3d":
            return input_type.channels
        return input_type.flat_size() if input_type.kind != "rnn" else input_type.size

    def init_params(self, key, input_type):
        n = self._num_features(input_type)
        if not self.use_gamma_beta or self.lock_gamma_beta:
            return {}
        dt = self._param_dtype()
        return {"gamma": jnp.ones((n,), dt), "beta": jnp.zeros((n,), dt)}

    def init_state(self, input_type):
        n = self._num_features(input_type)
        dt = self._param_dtype()
        return {"mean": jnp.zeros((n,), dt), "var": jnp.ones((n,), dt)}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        axes = tuple(range(x.ndim - 1))  # all but channel axis (NHWC/NC/NTC)
        if train:
            # stats in ≥f32 regardless of activation dtype (bf16
            # accumulation would drift).  Both sums hang on x alone, so XLA
            # takes them in ONE multi-output reduction with the cast fused
            # into the read.  Not jnp.mean + jnp.var: var's own reduction
            # waits on its inner mean, a second pass over x and a third,
            # zero-valued one in the backward (class docstring).
            x32 = x.astype(jnp.promote_types(x.dtype, jnp.float32))
            n = math.prod(x.shape[:-1])
            mean = jnp.sum(x32, axis=axes) / n
            var = jnp.maximum(
                jnp.sum(x32 * x32, axis=axes) / n - mean * mean, 0.0)
            new_state = {
                "mean": self.decay * state["mean"] + (1.0 - self.decay) * mean,
                "var": self.decay * state["var"] + (1.0 - self.decay) * var,
            }
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
        # fold (mean, var, gamma, beta) into a per-channel scale/shift in
        # f32, then apply in x's own dtype — under a bf16 policy the big
        # [N,H,W,C] arithmetic stays bf16 (f32 gamma would otherwise
        # promote the whole tensor and double HBM traffic)
        scale = jax.lax.rsqrt(var + self.eps)
        shift = -mean * scale
        if params:
            scale = scale * params["gamma"]
            shift = shift * params["gamma"] + params["beta"]
        y = x * scale.astype(x.dtype) + shift.astype(x.dtype)
        y = activations.get(self.activation or "identity")(y)
        return y, new_state
