"""Layer-catalog tail: geometry 1D/3D ops, noise/dropout family, locally
connected, capsules, VAE, detection/center-loss heads, recurrent attention.

Parity targets (deeplearning4j-nn ``conf/layers/**``):
``ZeroPadding1DLayer/ZeroPadding3DLayer``, ``Cropping1D/Cropping3D``,
``Upsampling1D/Upsampling3D``, ``SpaceToBatchLayer``,
``dropout/GaussianDropout|GaussianNoise|AlphaDropout|SpatialDropout``
(as standalone layers), ``LocallyConnected1D/2D``,
``ElementWiseMultiplicationLayer``, ``misc/RepeatVector``,
``recurrent/MaskZeroLayer``, ``CenterLossOutputLayer``,
``objdetect/Yolo2OutputLayer``, ``variational/VariationalAutoencoder``,
``CapsuleLayer/PrimaryCapsules/CapsuleStrengthLayer``,
``RecurrentAttentionLayer``, ``GravesBidirectionalLSTM``.

All forward passes are pure jnp/lax traced into the network's single XLA
program; no per-op dispatch.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.config import dtype_policy
from deeplearning4j_tpu.nn import activations
from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer, layer_from_dict
from deeplearning4j_tpu.nn.layers.conv import _pair
from deeplearning4j_tpu.nn.layers.core import OutputLayer
from deeplearning4j_tpu.nn.layers.recurrent import Bidirectional, GravesLSTM
from deeplearning4j_tpu.ops.attention import dropout


def _two(v):
    """(before, after) from int or 2-seq."""
    return (v, v) if isinstance(v, int) else (v[0], v[1])


# ======================================================= geometry — 1D (NTC)
@register_layer("zero_padding1d")
@dataclasses.dataclass
class ZeroPadding1DLayer(Layer):
    """(``ZeroPadding1DLayer.java``) pad the time axis of [B,T,C]."""

    INPUT_KIND = "rnn"

    padding: Any = 1

    def has_params(self) -> bool:
        return False

    def get_output_type(self, input_type):
        lo, hi = _two(self.padding)
        t = None if input_type.timesteps is None else input_type.timesteps + lo + hi
        return InputType.recurrent(input_type.size, t)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        lo, hi = _two(self.padding)
        return jnp.pad(x, ((0, 0), (lo, hi), (0, 0))), state


    def transform_mask(self, mask):
        if mask is None:
            return None
        lo, hi = _two(self.padding)
        return jnp.pad(mask, ((0, 0), (lo, hi)), constant_values=1.0)

@register_layer("cropping1d")
@dataclasses.dataclass
class Cropping1DLayer(Layer):
    """(``Cropping1D.java``) crop the time axis of [B,T,C]."""

    INPUT_KIND = "rnn"

    cropping: Any = 0

    def has_params(self) -> bool:
        return False

    def get_output_type(self, input_type):
        lo, hi = _two(self.cropping)
        t = None if input_type.timesteps is None else input_type.timesteps - lo - hi
        return InputType.recurrent(input_type.size, t)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        lo, hi = _two(self.cropping)
        t = x.shape[1]
        return x[:, lo:t - hi if hi else t, :], state


    def transform_mask(self, mask):
        if mask is None:
            return None
        lo, hi = _two(self.cropping)
        t = mask.shape[1]
        return mask[:, lo:t - hi if hi else t]

@register_layer("upsampling1d")
@dataclasses.dataclass
class Upsampling1DLayer(Layer):
    """(``Upsampling1D.java``) repeat timesteps of [B,T,C]."""

    INPUT_KIND = "rnn"

    size: int = 2

    def has_params(self) -> bool:
        return False

    def get_output_type(self, input_type):
        t = None if input_type.timesteps is None else input_type.timesteps * self.size
        return InputType.recurrent(input_type.size, t)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return jnp.repeat(x, self.size, axis=1), state

    def transform_mask(self, mask):
        return None if mask is None else jnp.repeat(mask, self.size, axis=1)


# ==================================================== geometry — 3D (NDHWC)
@register_layer("zero_padding3d")
@dataclasses.dataclass
class ZeroPadding3DLayer(Layer):
    """(``ZeroPadding3DLayer.java``) pad D/H/W of [B,D,H,W,C].
    padding: int, (d,h,w) symmetric, or ((d0,d1),(h0,h1),(w0,w1))."""

    INPUT_KIND = "cnn3d"

    padding: Any = 1

    def has_params(self) -> bool:
        return False

    def _pads(self):
        p = self.padding
        if isinstance(p, int):
            return ((p, p), (p, p), (p, p))
        return tuple(_two(v) for v in p)

    def get_output_type(self, input_type):
        (d0, d1), (h0, h1), (w0, w1) = self._pads()
        return InputType.convolutional3d(
            input_type.depth + d0 + d1, input_type.height + h0 + h1,
            input_type.width + w0 + w1, input_type.channels)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        pd, ph, pw = self._pads()
        return jnp.pad(x, ((0, 0), pd, ph, pw, (0, 0))), state


@register_layer("cropping3d")
@dataclasses.dataclass
class Cropping3DLayer(Layer):
    """(``Cropping3D.java``) crop D/H/W of [B,D,H,W,C]."""

    INPUT_KIND = "cnn3d"

    cropping: Any = 0

    def has_params(self) -> bool:
        return False

    def _crops(self):
        c = self.cropping
        if isinstance(c, int):
            return ((c, c), (c, c), (c, c))
        return tuple(_two(v) for v in c)

    def get_output_type(self, input_type):
        (d0, d1), (h0, h1), (w0, w1) = self._crops()
        return InputType.convolutional3d(
            input_type.depth - d0 - d1, input_type.height - h0 - h1,
            input_type.width - w0 - w1, input_type.channels)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        (d0, d1), (h0, h1), (w0, w1) = self._crops()
        d, h, w = x.shape[1], x.shape[2], x.shape[3]
        return x[:, d0:d - d1 if d1 else d, h0:h - h1 if h1 else h,
                 w0:w - w1 if w1 else w, :], state


@register_layer("upsampling3d")
@dataclasses.dataclass
class Upsampling3DLayer(Layer):
    """(``Upsampling3D.java``) nearest-neighbor repeat of [B,D,H,W,C]."""

    INPUT_KIND = "cnn3d"

    size: Any = 2

    def has_params(self) -> bool:
        return False

    def _sizes(self):
        s = self.size
        return (s, s, s) if isinstance(s, int) else tuple(s)

    def get_output_type(self, input_type):
        sd, sh, sw = self._sizes()
        return InputType.convolutional3d(
            input_type.depth * sd, input_type.height * sh,
            input_type.width * sw, input_type.channels)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        sd, sh, sw = self._sizes()
        y = jnp.repeat(x, sd, axis=1)
        y = jnp.repeat(y, sh, axis=2)
        y = jnp.repeat(y, sw, axis=3)
        return y, state


@register_layer("space_to_batch")
@dataclasses.dataclass
class SpaceToBatchLayer(Layer):
    """(``SpaceToBatchLayer.java``; libnd4j ``space_to_batch``): move h/w
    blocks into the batch dim.  [B,H,W,C] → [B*bh*bw, H/bh, W/bw, C]."""

    INPUT_KIND = "cnn"

    blocks: Any = 2
    padding: Any = 0    # (h, w) symmetric pads applied before blocking

    def has_params(self) -> bool:
        return False

    def get_output_type(self, input_type):
        bh, bw = _pair(self.blocks)
        ph, pw = _pair(self.padding)
        h, w = input_type.height + 2 * ph, input_type.width + 2 * pw
        if h % bh or w % bw:
            raise ValueError(
                f"space_to_batch: padded spatial dims ({h}x{w}) must be "
                f"divisible by blocks ({bh}x{bw})")
        return InputType.convolutional(h // bh, w // bw, input_type.channels)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        bh, bw = _pair(self.blocks)
        ph, pw = _pair(self.padding)
        if ph or pw:
            x = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
        n, h, w, c = x.shape
        y = x.reshape(n, h // bh, bh, w // bw, bw, c)
        # → [bh, bw, N, H/bh, W/bw, C] → [bh*bw*N, H/bh, W/bw, C]
        y = y.transpose(2, 4, 0, 1, 3, 5).reshape(n * bh * bw, h // bh, w // bw, c)
        return y, state

    def transform_mask(self, mask):
        return None   # batch dim changes — spatial masks don't survive


# ========================================================= noise / dropout
@register_layer("gaussian_dropout")
@dataclasses.dataclass
class GaussianDropoutLayer(Layer):
    """Multiplicative gaussian noise (``conf/dropout/GaussianDropout.java``):
    x * N(1, rate/(1-rate)); identity at inference."""

    rate: float = 0.1

    def has_params(self) -> bool:
        return False

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        if not train or rng is None or self.rate <= 0.0:
            return x, state
        std = (self.rate / (1.0 - self.rate)) ** 0.5
        noise = 1.0 + std * jax.random.normal(rng, x.shape, x.dtype)
        return x * noise, state


@register_layer("gaussian_noise")
@dataclasses.dataclass
class GaussianNoiseLayer(Layer):
    """Additive gaussian noise (``conf/dropout/GaussianNoise.java``)."""

    stddev: float = 0.1

    def has_params(self) -> bool:
        return False

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        if not train or rng is None or self.stddev <= 0.0:
            return x, state
        return x + self.stddev * jax.random.normal(rng, x.shape, x.dtype), state


@register_layer("alpha_dropout")
@dataclasses.dataclass
class AlphaDropoutLayer(Layer):
    """Self-normalizing (SELU) dropout (``conf/dropout/AlphaDropout.java``):
    keeps zero mean/unit variance by replacing dropped units with
    alpha' = -lambda*alpha and applying an affine correction."""

    p: float = 0.95      # retain probability (DL4J convention)

    ALPHA = 1.6732632423543772
    LAMBDA = 1.0507009873554805

    def has_params(self) -> bool:
        return False

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        if not train or rng is None or self.p >= 1.0:
            return x, state
        p = self.p
        alpha_p = -self.LAMBDA * self.ALPHA
        a = (p + alpha_p * alpha_p * p * (1 - p)) ** -0.5
        b = -a * (1 - p) * alpha_p
        keep = jax.random.bernoulli(rng, p, x.shape)
        y = a * jnp.where(keep, x, jnp.asarray(alpha_p, x.dtype)) + b
        return y.astype(x.dtype), state


@register_layer("spatial_dropout")
@dataclasses.dataclass
class SpatialDropoutLayer(Layer):
    """Whole-feature-map dropout (``conf/dropout/SpatialDropout.java``):
    drops entire channels of CNN/CNN3D/RNN activations with inverted
    scaling; p is the retain probability."""

    p: float = 0.9

    def has_params(self) -> bool:
        return False

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        if not train or rng is None or self.p >= 1.0:
            return x, state
        shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (x.shape[-1],)
        return dropout(x, self.p, rng, shape), state


# ======================================================== locally connected
@register_layer("locally_connected2d")
@dataclasses.dataclass
class LocallyConnected2D(Layer):
    """Conv2D with UNSHARED weights per output position
    (``conf/layers/LocallyConnected2D.java``).  W: [outH, outW, kh*kw*cin,
    nOut]; one einsum on the MXU, no im2col materialization beyond the
    patch gather XLA fuses."""

    INPUT_KIND = "cnn"

    n_out: int = 0
    kernel: Any = 3
    stride: Any = 1
    padding: Any = 0
    has_bias: bool = True
    # Keras LocallyConnected2D learns one bias PER OUTPUT POSITION
    # ([oh, ow, nOut]); DL4J shares it ([nOut]).  Import sets this flag.
    per_position_bias: bool = False

    def _geom(self, input_type):
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        oh = (input_type.height + 2 * ph - kh) // sh + 1
        ow = (input_type.width + 2 * pw - kw) // sw + 1
        return kh, kw, sh, sw, ph, pw, oh, ow

    def get_output_type(self, input_type):
        *_, oh, ow = self._geom(input_type)
        return InputType.convolutional(oh, ow, self.n_out)

    def init_params(self, key, input_type):
        kh, kw, _, _, _, _, oh, ow = self._geom(input_type)
        cin = input_type.channels
        fan_in = kh * kw * cin
        params = {"W": self._init_weight(key, (oh, ow, fan_in, self.n_out),
                                         fan_in, self.n_out)}
        if self.has_bias:
            shape = ((oh, ow, self.n_out) if self.per_position_bias
                     else (self.n_out,))
            params["b"] = self._init_bias(shape)
        return params

    def _patches(self, x, kh, kw, sh, sw, oh, ow):
        # unrolled at trace time: kh*kw strided slices, fused by XLA
        cols = []
        for ki in range(kh):
            for kj in range(kw):
                cols.append(jax.lax.slice(
                    x, (0, ki, kj, 0),
                    (x.shape[0], ki + (oh - 1) * sh + 1, kj + (ow - 1) * sw + 1,
                     x.shape[3]),
                    (1, sh, sw, 1)))
        return jnp.concatenate(cols, axis=-1)   # [B, oh, ow, kh*kw*C]

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        kh, kw, sh, sw, ph, pw, oh, ow = self._geom(
            InputType.convolutional(x.shape[1], x.shape[2], x.shape[3]))
        if ph or pw:
            x = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
        x = self._maybe_dropout(x, train, rng)
        policy = dtype_policy()
        patches = self._patches(x, kh, kw, sh, sw, oh, ow)
        y = jnp.einsum("bhwk,hwko->bhwo",
                       patches.astype(policy.compute_dtype),
                       params["W"].astype(policy.compute_dtype))
        if self.has_bias:
            y = y + params["b"].astype(y.dtype)
        y = y.astype(policy.output_dtype)
        return activations.get(self.activation or "identity")(y), state


@register_layer("locally_connected1d")
@dataclasses.dataclass
class LocallyConnected1D(Layer):
    """1D unshared convolution over [B,T,C]
    (``conf/layers/LocallyConnected1D.java``)."""

    INPUT_KIND = "rnn"

    n_out: int = 0
    kernel: int = 3
    stride: int = 1
    padding: int = 0
    has_bias: bool = True
    per_position_bias: bool = False   # Keras parity: bias [ot, nOut]

    def transform_mask(self, mask):
        return None   # time length changes without a step correspondence

    def _geom(self, t):
        ot = (t + 2 * self.padding - self.kernel) // self.stride + 1
        return ot

    def get_output_type(self, input_type):
        t = input_type.timesteps
        return InputType.recurrent(self.n_out,
                                   None if t is None else self._geom(t))

    def init_params(self, key, input_type):
        if input_type.timesteps is None:
            raise ValueError("LocallyConnected1D needs a fixed sequence "
                             "length (set timesteps on the recurrent InputType)")
        ot = self._geom(input_type.timesteps)
        cin = input_type.size
        fan_in = self.kernel * cin
        params = {"W": self._init_weight(key, (ot, fan_in, self.n_out),
                                         fan_in, self.n_out)}
        if self.has_bias:
            shape = ((ot, self.n_out) if self.per_position_bias
                     else (self.n_out,))
            params["b"] = self._init_bias(shape)
        return params

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        if self.padding:
            x = jnp.pad(x, ((0, 0), (self.padding, self.padding), (0, 0)))
        x = self._maybe_dropout(x, train, rng)
        ot = params["W"].shape[0]
        policy = dtype_policy()
        cols = [jax.lax.slice(x, (0, k, 0),
                              (x.shape[0], k + (ot - 1) * self.stride + 1, x.shape[2]),
                              (1, self.stride, 1))
                for k in range(self.kernel)]
        patches = jnp.concatenate(cols, axis=-1)       # [B, ot, k*C]
        y = jnp.einsum("btk,tko->bto",
                       patches.astype(policy.compute_dtype),
                       params["W"].astype(policy.compute_dtype))
        if self.has_bias:
            y = y + params["b"].astype(y.dtype)
        y = y.astype(policy.output_dtype)
        return activations.get(self.activation or "identity")(y), state


# ===================================================== small utility layers
@register_layer("element_wise_mult")
@dataclasses.dataclass
class ElementWiseMultiplicationLayer(Layer):
    """y = act(x ⊙ w + b) (``ElementWiseMultiplicationLayer.java``)."""

    INPUT_KIND = "ff"

    n_out: int = 0   # must equal nIn (DL4J validates)

    def get_output_type(self, input_type):
        return InputType.feed_forward(self.n_out or input_type.flat_size())

    def init_params(self, key, input_type):
        n = input_type.flat_size()
        if self.n_out and self.n_out != n:
            raise ValueError(f"ElementWiseMultiplication nIn ({n}) must equal "
                             f"nOut ({self.n_out})")
        return {"w": jnp.ones((n,), self._param_dtype()),
                "b": self._init_bias((n,))}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self._maybe_dropout(x, train, rng)
        y = x * params["w"] + params["b"]
        return activations.get(self.activation or "identity")(y), state


@register_layer("repeat_vector")
@dataclasses.dataclass
class RepeatVector(Layer):
    """[B,C] → [B,n,C] (``misc/RepeatVector.java``)."""

    INPUT_KIND = "ff"

    n: int = 1

    def has_params(self) -> bool:
        return False

    def get_output_type(self, input_type):
        return InputType.recurrent(input_type.flat_size(), self.n)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return jnp.repeat(x[:, None, :], self.n, axis=1), state


    def transform_mask(self, mask):
        return None   # fresh time axis — no per-timestep mask to inherit

@register_layer("mask_zero")
@dataclasses.dataclass
class MaskZeroLayer(Layer):
    """Wraps a recurrent layer, deriving a timestep mask from input rows
    equal to ``mask_value`` (``recurrent/MaskZeroLayer.java``)."""

    INPUT_KIND = "rnn"

    underlying: Any = None
    mask_value: float = 0.0

    def __post_init__(self):
        if isinstance(self.underlying, dict):
            self.underlying = layer_from_dict(self.underlying)

    def inherit_defaults(self, defaults):
        super().inherit_defaults(defaults)
        if self.underlying is not None:
            self.underlying.inherit_defaults(defaults)

    def to_dict(self):
        out = super().to_dict()
        out["underlying"] = self.underlying.to_dict()
        return out

    def get_output_type(self, input_type):
        return self.underlying.get_output_type(input_type)

    def init_params(self, key, input_type):
        return self.underlying.init_params(key, input_type)

    def init_state(self, input_type):
        return self.underlying.init_state(input_type)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        derived = jnp.any(x != self.mask_value, axis=-1).astype(x.dtype)  # [B,T]
        mask = derived if mask is None else mask * derived
        return self.underlying.apply(params, state, x, train=train, rng=rng,
                                     mask=mask)


@register_layer("graves_bidirectional_lstm")
@dataclasses.dataclass
class GravesBidirectionalLSTM(Bidirectional):
    """Fused bidirectional Graves LSTM (``GravesBidirectionalLSTM.java``):
    separate fwd/bwd GravesLSTM params, outputs ADDED (output width =
    nOut, unlike the CONCAT default of the Bidirectional wrapper)."""

    n_out: int = 0

    def __post_init__(self):
        if self.fwd is None and self.n_out:
            self.fwd = GravesLSTM(n_out=self.n_out, activation=self.activation)
        super().__post_init__()
        self.mode = "add"


# ============================================================ output heads
@register_layer("center_loss_output")
@dataclasses.dataclass
class CenterLossOutputLayer(OutputLayer):
    """Softmax CE + center loss (``CenterLossOutputLayer.java``):
    L = CE + (lambda/2)·||f − c_y||² with per-class centers over the layer
    INPUT features.  Design note vs DL4J: centers live in params and learn
    through the autodiff gradient −lambda(f−c_y) under the net's updater,
    replacing DL4J's manual ``alpha`` moving-average update — same fixed
    point, one optimizer."""

    alpha: float = 0.05          # kept for config parity / import mapping
    lambda_: float = 2e-4

    def init_params(self, key, input_type):
        params = super().init_params(key, input_type)
        # ff input only (OutputLayer.get_output_type rejects rnn at build)
        params["centers"] = jnp.zeros((self.n_out, input_type.flat_size()),
                                      self._param_dtype())
        return params

    def compute_score_array(self, params, state, x, labels, *, train=False,
                            rng=None, mask=None):
        base = super().compute_score_array(params, state, x, labels,
                                           train=train, rng=rng, mask=mask)
        acc = jnp.promote_types(x.dtype, jnp.float32)
        feats = x.reshape(x.shape[0], -1).astype(acc)
        centers_y = jnp.einsum("bc,cf->bf", labels.astype(acc),
                               params["centers"].astype(acc))
        center_term = 0.5 * self.lambda_ * jnp.sum(
            (feats - centers_y) ** 2, axis=-1)
        return base + center_term


@register_layer("yolo2_output")
@dataclasses.dataclass
class Yolo2OutputLayer(Layer):
    """YOLOv2 detection loss (``objdetect/Yolo2OutputLayer.java``).

    Input/labels: [B, H, W, A*(5+C)] grids, A = len(anchors); per anchor
    (tx, ty, tw, th, conf, class...).  Label conf ∈ {0,1} marks the
    responsible anchor; coordinate + class terms apply only there, the
    no-object confidence term elsewhere (``lambda_coord``/``lambda_noobj``
    weighting per the paper and the reference layer).  Loss spaces follow
    Darknet: xy compared as sigmoid(tx,ty) vs cell-relative [0,1] targets,
    wh compared RAW in t-space (label tw,th are log-space offsets vs the
    anchor priors), conf as sigmoid vs {0,1}, classes as softmax CE.
    ``apply()`` (inference) returns the fully activated grid including
    exp(tw,th)·anchors (``YoloUtils.activate``).  Label layout note: the
    reference consumes NCHW bbox-corner labels; this TPU-native head uses
    the per-anchor grid encoding above (loss semantics are the same).
    """

    INPUT_KIND = "cnn"

    anchors: Any = ((1.0, 1.0),)
    num_classes: int = 0
    lambda_coord: float = 5.0
    lambda_noobj: float = 0.5

    def has_params(self) -> bool:
        return False

    def labels_required(self) -> bool:
        return True

    def get_output_type(self, input_type):
        return input_type

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        """Activated predictions (``YoloUtils.activate`` parity):
        sigmoid(tx,ty,conf), exp(tw,th)·anchor priors, softmax(classes) —
        the decodable form downstream NMS expects."""
        a = len(self.anchors)
        c = self.num_classes
        b, h, w, _ = x.shape
        acc = jnp.promote_types(x.dtype, jnp.float32)
        g = x.astype(acc).reshape(b, h, w, a, 5 + c)
        anchors = jnp.asarray(self.anchors, acc)          # [A, 2]
        xy = jax.nn.sigmoid(g[..., 0:2])
        wh = jnp.exp(g[..., 2:4]) * anchors[None, None, None, :, :]
        conf = jax.nn.sigmoid(g[..., 4:5])
        parts = [xy, wh, conf]
        if c > 0:
            parts.append(jax.nn.softmax(g[..., 5:], axis=-1))
        y = jnp.concatenate(parts, axis=-1).reshape(b, h, w, a * (5 + c))
        return y.astype(x.dtype), state

    def compute_score_array(self, params, state, x, labels, *, train=False,
                            rng=None, mask=None):
        a = len(self.anchors)
        c = self.num_classes
        b, h, w, _ = x.shape
        acc = jnp.promote_types(x.dtype, jnp.float32)   # loss math ≥ f32
        x = x.astype(acc).reshape(b, h, w, a, 5 + c)
        y = labels.astype(acc).reshape(b, h, w, a, 5 + c)
        pred_xy = jax.nn.sigmoid(x[..., 0:2])
        pred_wh = x[..., 2:4]
        pred_conf = jax.nn.sigmoid(x[..., 4])
        obj = y[..., 4]                                   # [B,H,W,A]
        coord = jnp.sum((pred_xy - y[..., 0:2]) ** 2, axis=-1) + \
            jnp.sum((pred_wh - y[..., 2:4]) ** 2, axis=-1)
        coord_loss = self.lambda_coord * jnp.sum(obj * coord, axis=(1, 2, 3))
        conf_loss = jnp.sum(obj * (pred_conf - 1.0) ** 2, axis=(1, 2, 3)) + \
            self.lambda_noobj * jnp.sum((1 - obj) * pred_conf ** 2, axis=(1, 2, 3))
        if c > 0:
            logp = jax.nn.log_softmax(x[..., 5:], axis=-1)
            class_loss = -jnp.sum(obj * jnp.sum(y[..., 5:] * logp, axis=-1),
                                  axis=(1, 2, 3))
        else:
            class_loss = 0.0
        return coord_loss + conf_loss + class_loss


# ======================================================================= VAE
@register_layer("vae")
@dataclasses.dataclass
class VariationalAutoencoder(Layer):
    """VAE as a (pre)trainable layer
    (``conf/layers/variational/VariationalAutoencoder.java``).

    ``apply`` outputs the mean of q(z|x) (DL4J: activations = latent
    mean); ``compute_score_array`` is the negative ELBO (reconstruction
    NLL + KL(q(z|x)‖N(0,I))), with the input as its own target — pass the
    features as labels (or a LossLayer-style identity labels mapping).
    reconstruction ∈ gaussian (2·nIn outputs: mean, logvar) | bernoulli.
    """

    INPUT_KIND = "ff"

    n_out: int = 0                       # latent size
    encoder_layer_sizes: Any = (256,)
    decoder_layer_sizes: Any = (256,)
    reconstruction: str = "gaussian"
    num_samples: int = 1

    def get_output_type(self, input_type):
        return InputType.feed_forward(self.n_out)

    def _mlp_params(self, key, sizes, n_in):
        params = []
        for i, n in enumerate(sizes):
            key, sub = jax.random.split(key)
            params.append({"W": self._init_weight(sub, (n_in, n), n_in, n),
                           "b": self._init_bias((n,))})
            n_in = n
        return params, n_in, key

    def init_params(self, key, input_type):
        n_in = input_type.flat_size()
        enc, width, key = self._mlp_params(key, tuple(self.encoder_layer_sizes), n_in)
        k1, k2, k3 = jax.random.split(key, 3)
        mu = {"W": self._init_weight(k1, (width, self.n_out), width, self.n_out),
              "b": self._init_bias((self.n_out,))}
        logvar = {"W": self._init_weight(k2, (width, self.n_out), width, self.n_out),
                  "b": self._init_bias((self.n_out,))}
        dec, dwidth, k3 = self._mlp_params(k3, tuple(self.decoder_layer_sizes),
                                           self.n_out)
        out_n = 2 * n_in if self.reconstruction == "gaussian" else n_in
        k4, _ = jax.random.split(k3)
        recon = {"W": self._init_weight(k4, (dwidth, out_n), dwidth, out_n),
                 "b": self._init_bias((out_n,))}
        return {"encoder": enc, "mu": mu, "logvar": logvar,
                "decoder": dec, "recon": recon}

    def _mlp(self, layers, x):
        act = activations.get(self.activation or "relu")
        for p in layers:
            x = act(x @ p["W"] + p["b"])
        return x

    def _encode(self, params, x):
        h = self._mlp(params["encoder"],
                      x.reshape(x.shape[0], -1).astype(
                          jnp.promote_types(x.dtype, jnp.float32)))
        mu = h @ params["mu"]["W"] + params["mu"]["b"]
        logvar = h @ params["logvar"]["W"] + params["logvar"]["b"]
        return mu, logvar

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        mu, _ = self._encode(params, x)
        return mu, state

    def decode(self, params, z):
        h = self._mlp(params["decoder"], z)
        return h @ params["recon"]["W"] + params["recon"]["b"]

    def compute_score_array(self, params, state, x, labels, *, train=False,
                            rng=None, mask=None):
        target = (labels if labels is not None else x)
        target = target.reshape(target.shape[0], -1).astype(
            jnp.promote_types(target.dtype, jnp.float32))
        mu, logvar = self._encode(params, x)
        kl = 0.5 * jnp.sum(jnp.exp(logvar) + mu ** 2 - 1.0 - logvar, axis=-1)
        recon_nll = 0.0
        n = max(self.num_samples, 1)
        for s in range(n):
            if train and rng is not None:
                eps = jax.random.normal(jax.random.fold_in(rng, s), mu.shape)
                z = mu + jnp.exp(0.5 * logvar) * eps
            else:
                z = mu
            out = self.decode(params, z)
            if self.reconstruction == "bernoulli":
                logp = target * jax.nn.log_sigmoid(out) + \
                    (1 - target) * jax.nn.log_sigmoid(-out)
                recon_nll += -jnp.sum(logp, axis=-1)
            else:
                mean, logv = jnp.split(out, 2, axis=-1)
                logv = jnp.clip(logv, -10.0, 10.0)
                recon_nll += 0.5 * jnp.sum(
                    logv + (target - mean) ** 2 / jnp.exp(logv)
                    + jnp.log(2 * jnp.pi), axis=-1)
        return recon_nll / n + kl

    def labels_required(self) -> bool:
        return False


# ================================================================== capsules
def _squash(v, axis=-1, eps=1e-7):
    n2 = jnp.sum(v * v, axis=axis, keepdims=True)
    return (n2 / (1.0 + n2)) * v / jnp.sqrt(n2 + eps)


@register_layer("primary_capsules")
@dataclasses.dataclass
class PrimaryCapsules(Layer):
    """Conv → capsule reshape + squash (``CapsNet PrimaryCapsules.java``).
    Output: [B, numCaps, capDim] (recurrent-kind shape chain)."""

    INPUT_KIND = "cnn"

    capsules: int = 8            # capsule channel groups
    capsule_dimensions: int = 8
    kernel: Any = 9
    stride: Any = 2

    def _geom(self, input_type):
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        oh = (input_type.height - kh) // sh + 1
        ow = (input_type.width - kw) // sw + 1
        return kh, kw, sh, sw, oh, ow

    def get_output_type(self, input_type):
        *_, oh, ow = self._geom(input_type)
        return InputType.recurrent(self.capsule_dimensions,
                                   oh * ow * self.capsules)

    def init_params(self, key, input_type):
        kh, kw, *_ = self._geom(input_type)
        cin = input_type.channels
        cout = self.capsules * self.capsule_dimensions
        fan_in = kh * kw * cin
        return {"W": self._init_weight(key, (kh, kw, cin, cout), fan_in, cout),
                "b": self._init_bias((cout,))}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        _, _, sh, sw, oh, ow = self._geom(
            InputType.convolutional(x.shape[1], x.shape[2], x.shape[3]))
        policy = dtype_policy()
        y = jax.lax.conv_general_dilated(
            x.astype(policy.compute_dtype),
            params["W"].astype(policy.compute_dtype),
            window_strides=(sh, sw), padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        y = (y + params["b"].astype(y.dtype)).astype(
            jnp.promote_types(x.dtype, jnp.float32))
        caps = y.reshape(x.shape[0], oh * ow * self.capsules,
                         self.capsule_dimensions)
        return _squash(caps), state


@register_layer("capsules")
@dataclasses.dataclass
class CapsuleLayer(Layer):
    """Dynamic-routing capsule layer (``CapsuleLayer.java``).
    [B, inCaps, inDim] → [B, capsules, capsule_dimensions]."""

    INPUT_KIND = "rnn"

    capsules: int = 10
    capsule_dimensions: int = 16
    routings: int = 3

    def get_output_type(self, input_type):
        return InputType.recurrent(self.capsule_dimensions, self.capsules)

    def init_params(self, key, input_type):
        in_caps, in_dim = input_type.timesteps, input_type.size
        if in_caps is None:
            raise ValueError("CapsuleLayer needs a known input capsule count")
        fan_in = in_dim
        return {"W": self._init_weight(
            key, (in_caps, self.capsules, self.capsule_dimensions, in_dim),
            fan_in, self.capsule_dimensions)}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        policy = dtype_policy()
        # u_hat[b,i,j,d] = W[i,j,d,:] · x[b,i,:]   (one MXU einsum)
        acc = jnp.promote_types(x.dtype, jnp.float32)  # routing math ≥ f32
        u_hat = jnp.einsum("ijdk,bik->bijd",
                           params["W"].astype(policy.compute_dtype),
                           x.astype(policy.compute_dtype)).astype(acc)
        b, i, j, d = u_hat.shape
        logits = jnp.zeros((b, i, j), acc)
        # routing iterations: fixed small count → unrolled, XLA-friendly;
        # gradients flow through the full routing (differentiable agreement)
        v = None
        for r in range(self.routings):
            c = jax.nn.softmax(logits, axis=2)           # over out capsules
            s = jnp.einsum("bij,bijd->bjd", c, u_hat)
            v = _squash(s)
            if r < self.routings - 1:
                logits = logits + jnp.einsum("bijd,bjd->bij", u_hat, v)
        return v, state


@register_layer("capsule_strength")
@dataclasses.dataclass
class CapsuleStrengthLayer(Layer):
    """‖capsule‖ per output capsule (``CapsuleStrengthLayer.java``):
    [B, caps, dim] → [B, caps]."""

    INPUT_KIND = "rnn"

    def has_params(self) -> bool:
        return False

    def get_output_type(self, input_type):
        return InputType.feed_forward(input_type.timesteps)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return jnp.sqrt(jnp.sum(x * x, axis=-1) + 1e-12), state


# ===================================================== recurrent attention
@register_layer("recurrent_attention")
@dataclasses.dataclass
class RecurrentAttentionLayer(Layer):
    """Recurrent attention (``RecurrentAttentionLayer.java``): an RNN whose
    step input is augmented with attention over the WHOLE input sequence,
    queried by the previous hidden state.  lax.scan over time; keys/values
    are precomputed once (two MXU einsums), the scan body is small."""

    INPUT_KIND = "rnn"

    n_out: int = 0
    has_bias: bool = True

    def get_output_type(self, input_type):
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def init_params(self, key, input_type):
        n_in, n = input_type.size, self.n_out
        ks = jax.random.split(key, 5)
        params = {
            "Wx": self._init_weight(ks[0], (n_in, n), n_in, n),
            "Wr": self._init_weight(ks[1], (n, n), n, n),
            "Wq": self._init_weight(ks[2], (n, n), n, n),
            "Wk": self._init_weight(ks[3], (n_in, n), n_in, n),
            "Wv": self._init_weight(ks[4], (n_in, n), n_in, n),
        }
        if self.has_bias:
            params["b"] = self._init_bias((n,))
        return params

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        policy = dtype_policy()
        act = activations.get(self.activation or "tanh")
        cd = policy.compute_dtype
        acc = jnp.promote_types(x.dtype, jnp.float32)   # softmax/state ≥ f32
        x = self._maybe_dropout(x, train, rng)
        xc = x.astype(cd)
        keys = jnp.einsum("btc,cn->btn", xc, params["Wk"].astype(cd))
        vals = jnp.einsum("btc,cn->btn", xc, params["Wv"].astype(cd))
        xin = jnp.einsum("btc,cn->btn", xc, params["Wx"].astype(cd))
        scale = 1.0 / jnp.sqrt(jnp.asarray(self.n_out, acc))
        neg = jnp.asarray(-1e9, acc)
        kv_mask = None if mask is None else mask.astype(acc)

        def step(h, t_in):
            x_t = t_in
            q = (h.astype(cd) @ params["Wq"].astype(cd)).astype(acc)
            scores = jnp.einsum("bn,btn->bt", q, keys.astype(acc)) * scale
            if kv_mask is not None:
                scores = jnp.where(kv_mask > 0, scores, neg)
            attn = jax.nn.softmax(scores, axis=-1)
            ctx = jnp.einsum("bt,btn->bn", attn, vals.astype(acc))
            z = x_t.astype(acc) + \
                (h.astype(cd) @ params["Wr"].astype(cd)).astype(acc) + ctx
            if self.has_bias:
                z = z + params["b"].astype(acc)
            h_new = act(z)
            return h_new.astype(x.dtype), h_new.astype(x.dtype)

        h0 = jnp.zeros((x.shape[0], self.n_out), x.dtype)
        _, ys = jax.lax.scan(step, h0, jnp.swapaxes(xin, 0, 1))
        y = jnp.swapaxes(ys, 0, 1)
        if mask is not None:
            y = y * mask[..., None].astype(y.dtype)
        return y, state


@register_layer("mixture_of_experts")
@dataclasses.dataclass
class MixtureOfExperts(Layer):
    """Sparsely-gated mixture-of-experts FFN (beyond-reference capability:
    the reference is pre-MoE — SURVEY.md §2.7).  Output dim equals input
    dim (residual-style FFN block); single-device forward here, with the
    expert-parallel all_to_all execution provided by
    :func:`deeplearning4j_tpu.parallel.expert_parallel.moe_ffn` over the
    ``expert`` mesh axis."""

    n_experts: int = 4
    hidden: int = 0          # expert FFN hidden width (default 4x input)
    top_k: int = 2
    capacity_factor: float = 2.0

    def get_output_type(self, input_type: InputType) -> InputType:
        if input_type.kind not in ("ff", "rnn"):
            raise ValueError(
                f"MixtureOfExperts expects feed-forward or recurrent input "
                f"(tokens over the last axis), got {input_type.kind} — add "
                f"a GlobalPoolingLayer or DenseLayer first")
        return input_type

    def init_params(self, key, input_type):
        from deeplearning4j_tpu.parallel.unified import init_moe_params
        d = input_type.size if input_type.kind == "rnn" else input_type.flat_size()
        hidden = self.hidden or 4 * d
        return init_moe_params(key, d, hidden, self.n_experts,
                               dtype=self._param_dtype())

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.parallel.unified import moe_ffn_dense
        x = self._maybe_dropout(x, train, rng)
        act = activations.get(self.activation or "gelu")
        shape = x.shape
        flat = x.reshape(-1, shape[-1])
        # high-capacity during gradcheck-sized batches is fine; capacity
        # stays static per shape under jit
        y = moe_ffn_dense(params, flat, top_k=min(self.top_k, self.n_experts),
                          capacity_factor=self.capacity_factor,
                          activation=act)
        y = y.reshape(shape)
        if mask is not None and y.ndim == 3:
            y = y * mask[..., None].astype(y.dtype)
        return y, state


# ============================================== keras-import tail (round 5)
@register_layer("permute")
@dataclasses.dataclass
class PermuteLayer(Layer):
    """Permute the non-batch axes (Keras ``Permute`` parity; DL4J
    ``KerasPermute`` → preprocessor).  ``dims`` are 1-indexed positions
    of the INPUT axes (batch excluded), Keras convention."""

    dims: Any = (1,)

    def get_output_type(self, input_type: InputType) -> InputType:
        if input_type.kind == "rnn" and input_type.timesteps is None:
            raise ValueError(
                "Permute over a dynamic-length recurrent input needs a "
                "fixed timesteps on the recurrent InputType (the time "
                "axis becomes the feature axis)")
        shape = input_type.batch_shape()[1:]
        if len(self.dims) != len(shape):
            raise ValueError(f"Permute dims {self.dims} rank != input "
                             f"rank {len(shape)}")
        new = tuple(shape[d - 1] for d in self.dims)
        if input_type.kind == "rnn":
            return InputType.recurrent(new[1], new[0])
        if input_type.kind == "cnn":
            return InputType.convolutional(new[0], new[1], new[2])
        if input_type.kind == "ff":
            return input_type
        raise ValueError(f"Permute over {input_type.kind} input")

    def transform_mask(self, mask):
        return None   # the time axis moves; no step correspondence

    def init_params(self, key, input_type):
        return {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return jnp.transpose(x, (0,) + tuple(self.dims)), state


@register_layer("separable_conv1d")
@dataclasses.dataclass
class SeparableConvolution1D(Layer):
    """Depthwise-separable 1-D conv over [B, T, C] (Keras
    ``SeparableConv1D`` parity; libnd4j sconv via the grouped-conv
    lowering).  depthW [k, 1, C*mult] (group-major channel flatten,
    matching the 2-D separable layout), pointW [1, C*mult, nOut]."""

    INPUT_KIND = "rnn"

    n_out: int = 0
    kernel_size: int = 3
    stride: int = 1
    depth_multiplier: int = 1
    convolution_mode: str = "truncate"
    has_bias: bool = True

    def transform_mask(self, mask):
        if self.stride == 1 and self.convolution_mode == "same":
            return mask
        return None

    def _out_len(self, t):
        if t is None:
            return None
        if self.convolution_mode == "same":
            return -(-t // self.stride)
        return (t - self.kernel_size) // self.stride + 1

    def get_output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out,
                                   self._out_len(input_type.timesteps))

    def init_params(self, key, input_type):
        cin = input_type.size
        mid = cin * self.depth_multiplier
        k1, k2 = jax.random.split(key)
        params = {
            "depthW": self._init_weight(
                k1, (self.kernel_size, 1, mid), self.kernel_size,
                self.kernel_size * self.depth_multiplier),
            "pointW": self._init_weight(k2, (1, mid, self.n_out),
                                        mid, self.n_out),
        }
        if self.has_bias:
            params["b"] = self._init_bias((self.n_out,))
        return params

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self._maybe_dropout(x, train, rng)
        policy = dtype_policy()
        pad = "SAME" if self.convolution_mode == "same" else "VALID"
        dn = jax.lax.conv_dimension_numbers(x.shape, params["depthW"].shape,
                                            ("NWC", "WIO", "NWC"))
        y = jax.lax.conv_general_dilated(
            x.astype(policy.compute_dtype),
            params["depthW"].astype(policy.compute_dtype),
            (self.stride,), pad, dimension_numbers=dn,
            feature_group_count=x.shape[-1])
        y = jax.lax.conv_general_dilated(
            y, params["pointW"].astype(policy.compute_dtype),
            (1,), "VALID", dimension_numbers=dn)
        if self.has_bias:
            y = y + params["b"].astype(y.dtype)
        y = y.astype(policy.output_dtype)
        return activations.get(self.activation or "identity")(y), state


@register_layer("conv_lstm2d")
@dataclasses.dataclass
class ConvLSTM2D(Layer):
    """Convolutional LSTM over [B, T, H, W, C] (Keras ``ConvLSTM2D``
    parity — xingjian et al.'s ConvLSTM).  Gate order follows Keras's
    i,f,c,o so imported kernels map without permutation: W [kh,kw,Cin,4F]
    convolves the input (``convolution_mode`` + stride), U [kh,kw,F,4F]
    convolves the hidden state (always SAME, spatial dims preserved).
    One ``lax.scan`` over time; the 4-gate convs batch into single MXU
    convolutions per step."""

    INPUT_KIND = "cnn3d"

    n_out: int = 0
    kernel_size: Any = (3, 3)
    stride: Any = (1, 1)
    convolution_mode: str = "truncate"
    return_sequences: bool = False
    gate_activation: str = "sigmoid"
    has_bias: bool = True

    def _spatial_out(self, h, w):
        kh, kw = self.kernel_size
        sh, sw = self.stride
        if self.convolution_mode == "same":
            return -(-h // sh), -(-w // sw)
        return (h - kh) // sh + 1, (w - kw) // sw + 1

    def get_output_type(self, input_type: InputType) -> InputType:
        oh, ow = self._spatial_out(input_type.height, input_type.width)
        if self.return_sequences:
            return InputType.convolutional3d(input_type.depth, oh, ow,
                                             self.n_out)
        return InputType.convolutional(oh, ow, self.n_out)

    def init_params(self, key, input_type):
        kh, kw = self.kernel_size
        cin = input_type.channels
        k1, k2 = jax.random.split(key)
        params = {
            "W": self._init_weight(k1, (kh, kw, cin, 4 * self.n_out),
                                   kh * kw * cin, kh * kw * self.n_out),
            "U": self._init_weight(k2, (kh, kw, self.n_out, 4 * self.n_out),
                                   kh * kw * self.n_out,
                                   kh * kw * self.n_out),
        }
        if self.has_bias:
            params["b"] = self._init_bias((4 * self.n_out,))
        return params

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self._maybe_dropout(x, train, rng)
        policy = dtype_policy()
        cd = policy.compute_dtype
        B, T = x.shape[0], x.shape[1]
        F = self.n_out
        pad = "SAME" if self.convolution_mode == "same" else "VALID"
        gate = activations.get(self.gate_activation)
        act = activations.get(self.activation or "tanh")
        W = params["W"].astype(cd)
        U = params["U"].astype(cd)
        dn = ("NHWC", "HWIO", "NHWC")

        def in_conv(xt):
            d = jax.lax.conv_dimension_numbers(xt.shape, W.shape, dn)
            return jax.lax.conv_general_dilated(
                xt.astype(cd), W, tuple(self.stride), pad,
                dimension_numbers=d)

        # all timesteps' input convolutions in one batched conv
        zx = in_conv(x.reshape((B * T,) + x.shape[2:]))
        zx = zx.reshape((B, T) + zx.shape[1:])
        if self.has_bias:
            zx = zx + params["b"].astype(cd)
        oh, ow = zx.shape[2], zx.shape[3]
        h0 = jnp.zeros((B, oh, ow, F), cd)
        c0 = jnp.zeros((B, oh, ow, F), cd)

        def step(carry, zt):
            h, c = carry
            d = jax.lax.conv_dimension_numbers(h.shape, U.shape, dn)
            z = zt + jax.lax.conv_general_dilated(
                h, U, (1, 1), "SAME", dimension_numbers=d)
            i = gate(z[..., :F])
            f = gate(z[..., F:2 * F])
            cc = z[..., 2 * F:3 * F]
            o = gate(z[..., 3 * F:])
            c = f * c + i * act(cc)
            h = o * act(c)
            return (h, c), h

        (hT, _), ys = jax.lax.scan(step, (h0, c0),
                                   jnp.moveaxis(zx, 1, 0))
        if self.return_sequences:
            y = jnp.moveaxis(ys, 0, 1)          # [B, T, oh, ow, F]
        else:
            y = hT
        return y.astype(policy.output_dtype), state
