"""Namespaced op façades — parity with ND4J's generated namespaces
(``Nd4j.math()`` etc., nd4j-api ``org/nd4j/linalg/factory/ops/NDMath.java``,
``NDNN.java``, ``NDCNN.java``, ``NDRNN.java``, ``NDLoss.java``,
``NDLinalg.java``, ``NDRandom.java``, ``NDImage.java``, ``NDBitwise.java``;
single-sourced in the reference from contrib/codegen-tools op DSL).

Each namespace is a plain module-level object of pure functions over
jax.Array.  Everything here is jit-safe and fuses under XLA; there is no
per-op dispatch layer to port — that's the point of the rewrite.
"""

from __future__ import annotations

import math as _pymath
from types import SimpleNamespace
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.ops import attention as _attention


# ---------------------------------------------------------------- math
def _norm1(x, axis=None): return jnp.sum(jnp.abs(x), axis=axis)
def _norm2(x, axis=None): return jnp.sqrt(jnp.sum(x * x, axis=axis))
def _normmax(x, axis=None): return jnp.max(jnp.abs(x), axis=axis)


def _standardize(x, axis=-1, eps=0.0):
    mean = jnp.mean(x, axis=axis, keepdims=True)
    std = jnp.std(x, axis=axis, keepdims=True)
    return (x - mean) / jnp.where(std > eps, std, 1.0)


def _clip_by_global_norm(xs, n):
    gn = jnp.sqrt(sum(jnp.sum(v * v) for v in xs))   # one pass over the tree
    scale = jnp.minimum(1.0, n / jnp.maximum(gn, 1e-12))
    return [x * scale for x in xs]


def _bincount(x, length, weights=None):
    """Out-of-range ids (negative or >= length) are DROPPED — jax's
    negative-index wrap would silently count padding/ignore labels."""
    idx = jnp.ravel(x).astype(jnp.int32)
    valid = (idx >= 0) & (idx < length)
    dtype = jnp.int32 if weights is None else jnp.asarray(weights).dtype
    w = (jnp.ones(idx.shape, dtype) if weights is None
         else jnp.ravel(jnp.asarray(weights)))
    return jnp.zeros((length,), dtype).at[jnp.where(valid, idx, 0)].add(
        jnp.where(valid, w, 0).astype(dtype))


math = SimpleNamespace(
    abs=jnp.abs, ceil=jnp.ceil, floor=jnp.floor, round=jnp.round,
    exp=jnp.exp, expm1=jnp.expm1, log=jnp.log, log1p=jnp.log1p,
    log2=jnp.log2, log10=jnp.log10,
    sqrt=jnp.sqrt, rsqrt=lax.rsqrt, square=jnp.square, pow=jnp.power,
    cube=lambda x: x ** 3, reciprocal=jnp.reciprocal, neg=jnp.negative,
    sign=jnp.sign, sin=jnp.sin, cos=jnp.cos, tan=jnp.tan,
    asin=jnp.arcsin, acos=jnp.arccos, atan=jnp.arctan, atan2=jnp.arctan2,
    sinh=jnp.sinh, cosh=jnp.cosh, tanh=jnp.tanh,
    asinh=jnp.arcsinh, acosh=jnp.arccosh, atanh=jnp.arctanh,
    erf=lax.erf, erfc=lax.erfc,
    clip_by_value=jnp.clip,
    clip_by_norm=lambda x, n: x * jnp.minimum(1.0, n / jnp.maximum(_norm2(x), 1e-12)),
    cumsum=jnp.cumsum, cumprod=jnp.cumprod,
    add=jnp.add, sub=jnp.subtract, mul=jnp.multiply, div=jnp.divide,
    floormod=jnp.mod, floordiv=jnp.floor_divide,
    maximum=jnp.maximum, minimum=jnp.minimum,
    mean=jnp.mean, sum=jnp.sum, prod=jnp.prod, max=jnp.max, min=jnp.min,
    std=jnp.std, var=jnp.var,
    norm1=_norm1, norm2=_norm2, normmax=_normmax,
    argmax=jnp.argmax, argmin=jnp.argmin,
    iamax=lambda x: jnp.argmax(jnp.abs(x)), iamin=lambda x: jnp.argmin(jnp.abs(x)),
    count_nonzero=jnp.count_nonzero,
    count_zero=lambda x, axis=None: jnp.sum(x == 0, axis=axis),
    entropy=lambda x, axis=None: -jnp.sum(x * jnp.log(jnp.clip(x, 1e-12)), axis=axis),
    log_entropy=lambda x, axis=None: jnp.log(
        -jnp.sum(x * jnp.log(jnp.clip(x, 1e-12)), axis=axis)),
    shannon_entropy=lambda x, axis=None: -jnp.sum(
        x * jnp.log2(jnp.clip(x, 1e-12)), axis=axis),
    amean=lambda x, axis=None: jnp.mean(jnp.abs(x), axis=axis),
    amax=lambda x, axis=None: jnp.max(jnp.abs(x), axis=axis),
    amin=lambda x, axis=None: jnp.min(jnp.abs(x), axis=axis),
    asum=lambda x, axis=None: jnp.sum(jnp.abs(x), axis=axis),
    standardize=_standardize,
    is_nan=jnp.isnan, is_inf=jnp.isinf, is_finite=jnp.isfinite,
    cosine_similarity=lambda a, b, axis=-1: jnp.sum(a * b, axis=axis)
    / jnp.clip(_norm2(a, axis) * _norm2(b, axis), 1e-12),
    cosine_distance=lambda a, b, axis=-1: 1.0 - jnp.sum(a * b, axis=axis)
    / jnp.clip(_norm2(a, axis) * _norm2(b, axis), 1e-12),
    euclidean_distance=lambda a, b, axis=-1: _norm2(a - b, axis),
    manhattan_distance=lambda a, b, axis=-1: _norm1(a - b, axis),
    hamming_distance=lambda a, b, axis=-1: jnp.sum(a != b, axis=axis),
    jaccard_distance=lambda a, b, axis=-1: 1.0
    - jnp.sum(jnp.minimum(a, b), axis=axis) / jnp.clip(jnp.sum(jnp.maximum(a, b), axis=axis), 1e-12),
    # libnd4j reversed/compound pairwise ops
    rsub=lambda x, y: y - x,
    rdiv=lambda x, y: y / x,
    squared_difference=lambda x, y: (x - y) ** 2,
    axpy=lambda a, x, y: a * x + y,
    all=jnp.all, any=jnp.any,
    # libnd4j IsMax marks exactly ONE position (first argmax), not ties
    is_max=lambda x: jnp.zeros(jnp.shape(x), bool).ravel()
    .at[jnp.argmax(x)].set(True).reshape(jnp.shape(x)),
    # comparisons / predicates (libnd4j pairwise bool ops)
    eq=jnp.equal, neq=jnp.not_equal,
    gt=jnp.greater, gte=jnp.greater_equal,
    lt=jnp.less, lte=jnp.less_equal,
    logical_and=jnp.logical_and, logical_or=jnp.logical_or,
    logical_xor=jnp.logical_xor, logical_not=jnp.logical_not,
    is_close=jnp.isclose,
    where=jnp.where,
    # rounding / cleanup
    trunc=jnp.trunc, rint=jnp.rint, nan_to_num=jnp.nan_to_num,
    # special functions (libnd4j transforms — XLA intrinsics)
    lgamma=lax.lgamma, digamma=lax.digamma,
    igamma=lax.igamma, igammac=lax.igammac,
    betainc=lax.betainc,
    zeta=jax.scipy.special.zeta,
    polygamma=lax.polygamma,
    log_sum_exp=jax.scipy.special.logsumexp,
    logaddexp=jnp.logaddexp,
    sort=jnp.sort, argsort=jnp.argsort,
    reverse=lambda x, axis=0: jnp.flip(x, axis=axis),
    # merge family (libnd4j mergemax/mergeavg/mergeadd — variadic)
    merge_max=lambda xs: jnp.max(jnp.stack(xs), axis=0),
    merge_avg=lambda xs: jnp.mean(jnp.stack(xs), axis=0),
    merge_add=lambda xs: jnp.sum(jnp.stack(xs), axis=0),
    # clip family beyond value/norm
    # average norm = ||x||2 / N (TF clip_by_average_norm / libnd4j
    # clipbyavgnorm), not RMS
    clip_by_avg_norm=lambda x, n: x * jnp.minimum(
        1.0, n / jnp.maximum(_norm2(x) / float(jnp.size(x)), 1e-12)),
    clip_by_global_norm=_clip_by_global_norm,
    percentile=lambda x, q, axis=None: jnp.percentile(x, q, axis=axis),
    nth_element=lambda x, n, reverse=False: (
        jnp.sort(x, axis=-1)[..., -(n + 1)] if reverse
        else jnp.sort(x, axis=-1)[..., n]),
    bincount=_bincount,
    histogram_fixed_width=lambda x, lo, hi, nbins: jnp.zeros(
        (nbins,), jnp.int32).at[jnp.clip(
            ((x - lo) / jnp.maximum(hi - lo, 1e-12) * nbins).astype(jnp.int32),
            0, nbins - 1)].add(1),
)


# ---------------------------------------------------------------- nn
nn = SimpleNamespace(
    relu=jax.nn.relu, relu6=jax.nn.relu6, elu=jax.nn.elu, selu=jax.nn.selu,
    gelu=jax.nn.gelu, silu=jax.nn.silu, swish=jax.nn.silu,
    sigmoid=jax.nn.sigmoid, hard_sigmoid=jax.nn.hard_sigmoid,
    tanh=jnp.tanh, hard_tanh=jax.nn.hard_tanh,
    softmax=jax.nn.softmax, log_softmax=jax.nn.log_softmax,
    softplus=jax.nn.softplus, softsign=jax.nn.soft_sign,
    leaky_relu=jax.nn.leaky_relu,
    log_sigmoid=jax.nn.log_sigmoid,
    one_hot=jax.nn.one_hot,
    linear=lambda x, w, b=None: jnp.dot(x, w) + (b if b is not None else 0.0),
    dropout=lambda key, x, keep_prob: _attention.dropout(x, keep_prob, key),
    layer_norm=lambda x, gamma, beta=None, eps=1e-5: (
        (x - jnp.mean(x, -1, keepdims=True))
        * lax.rsqrt(jnp.var(x, -1, keepdims=True) + eps) * gamma
        + (beta if beta is not None else 0.0)),
    batch_norm=lambda x, mean, var, gamma=None, beta=None, eps=1e-5: (
        (x - mean) * lax.rsqrt(var + eps)
        * (gamma if gamma is not None else 1.0)
        + (beta if beta is not None else 0.0)),
    pad=jnp.pad,
    # DL4J IActivation family beyond jax.nn (linalg/activations/impl/)
    prelu=lambda x, alpha: jnp.where(x >= 0, x, alpha * x),
    mish=jax.nn.mish,
    hard_swish=jax.nn.hard_swish,
    rational_tanh=lambda x: 1.7159 * jnp.tanh(2.0 * x / 3.0),
    rectified_tanh=lambda x: jnp.maximum(jnp.tanh(x), 0.0),
    hard_shrink=lambda x, lam=0.5: jnp.where(jnp.abs(x) > lam, x, 0.0),
    soft_shrink=lambda x, lam=0.5: jnp.sign(x) * jnp.maximum(jnp.abs(x) - lam, 0.0),
    thresholded_relu=lambda x, theta=1.0: jnp.where(x > theta, x, 0.0),
    crelu=lambda x: jnp.concatenate([jax.nn.relu(x), jax.nn.relu(-x)], axis=-1),
    glu=jax.nn.glu,
    moments=lambda x, axis=None: (jnp.mean(x, axis=axis), jnp.var(x, axis=axis)),
    l2_normalize=lambda x, axis=-1, eps=1e-12: x * lax.rsqrt(
        jnp.maximum(jnp.sum(x * x, axis=axis, keepdims=True), eps)),
    embedding_lookup=lambda table, ids: jnp.take(table, ids.astype(jnp.int32), axis=0),
    # libnd4j fused-affine declarables
    bias_add=lambda x, b: x + b,
    xw_plus_b=lambda x, w, b: jnp.dot(x, w) + b,
    relu_layer=lambda x, w, b: jax.nn.relu(jnp.dot(x, w) + b),
    # libnd4j dot_product_attention / multi_head_dot_product_attention
    dot_product_attention=_attention.dot_product_attention,
    multi_head_dot_product_attention=_attention.multi_head_attention,
)


# ---------------------------------------------------------------- cnn
def _conv2d(x, w, stride=(1, 1), padding="SAME", dilation=(1, 1), groups=1,
            precision=None):
    """``precision``: None = backend default (bf16 passes on the TPU MXU —
    the fast path); "highest" = full f32 accumulation (golden tests)."""
    return lax.conv_general_dilated(
        x, w, stride, padding, rhs_dilation=dilation,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=groups,
        precision=precision)


def _max_pool2d(x, k=(2, 2), s=None, padding="VALID"):
    s = s or k
    return lax.reduce_window(x, -jnp.inf, lax.max, (1,) + tuple(k) + (1,),
                             (1,) + tuple(s) + (1,), padding)


def _avg_pool2d(x, k=(2, 2), s=None, padding="VALID"):
    s = s or k
    y = lax.reduce_window(x, 0.0, lax.add, (1,) + tuple(k) + (1,),
                          (1,) + tuple(s) + (1,), padding)
    return y / _pymath.prod(k)


def _im2col(x, kh, kw, sh=1, sw=1, ph=0, pw=0):
    """libnd4j ``im2col`` parity (the reference's conv lowering; exposed for
    parity tests — XLA convs don't need it)."""
    x = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    n, h, w, c = x.shape
    oh = (h - kh) // sh + 1
    ow = (w - kw) // sw + 1
    idx_h = jnp.arange(oh)[:, None] * sh + jnp.arange(kh)[None, :]
    idx_w = jnp.arange(ow)[:, None] * sw + jnp.arange(kw)[None, :]
    # advanced indexing broadcasts to (n, oh, kh, ow, kw, c); bring the
    # patch axes together before flattening to (kh, kw, c)-major columns
    cols = x[:, idx_h[:, :, None, None], idx_w[None, None], :]
    cols = cols.transpose(0, 1, 3, 2, 4, 5)
    return cols.reshape(n, oh, ow, kh * kw * c)


def _conv1d(x, w, stride=1, padding="SAME", dilation=1, groups=1,
            precision=None):
    """[B,T,C] @ [K,C,Cout] (NWC/WIO) — libnd4j ``conv1d``."""
    return lax.conv_general_dilated(
        x, w, (stride,), padding, rhs_dilation=(dilation,),
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=groups,
        precision=precision)


def _conv3d(x, w, stride=(1, 1, 1), padding="SAME", dilation=(1, 1, 1),
            groups=1, precision=None):
    """[B,D,H,W,C] @ [Kd,Kh,Kw,C,Cout] — libnd4j ``conv3dnew``."""
    return lax.conv_general_dilated(
        x, w, stride, padding, rhs_dilation=dilation,
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"),
        feature_group_count=groups, precision=precision)


def _depthwise_conv2d(x, w, stride=(1, 1), padding="SAME", dilation=(1, 1),
                      precision=None):
    """w [Kh,Kw,C,mult] — libnd4j ``depthwise_conv2d``."""
    c = x.shape[-1]
    w = w.reshape(w.shape[0], w.shape[1], 1, -1)
    return lax.conv_general_dilated(
        x, w, stride, padding, rhs_dilation=dilation,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=c,
        precision=precision)


def _separable_conv2d(x, depth_w, point_w, stride=(1, 1), padding="SAME",
                      dilation=(1, 1), precision=None):
    """Depthwise then 1x1 pointwise — libnd4j ``sconv2d``."""
    y = _depthwise_conv2d(x, depth_w, stride, padding, dilation,
                          precision=precision)
    return _conv2d(y, point_w, (1, 1), "SAME", precision=precision)


def _deconv2d(x, w, stride=(2, 2), padding="SAME", precision=None):
    """Transposed conv (libnd4j ``deconv2d``); w [Kh,Kw,Cin,Cout]."""
    return lax.conv_transpose(
        x, w, stride, padding, dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=precision)


def _deconv3d(x, w, stride=(2, 2, 2), padding="SAME", precision=None):
    return lax.conv_transpose(
        x, w, stride, padding,
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"), precision=precision)


def _pool_nd(x, k, s, padding, op, init):
    window = (1,) + tuple(k) + (1,)
    strides = (1,) + tuple(s) + (1,)
    return lax.reduce_window(x, init, op, window, strides, padding)


def _max_pool1d(x, k=2, s=None, padding="VALID"):
    return _pool_nd(x, (k,), (s or k,), padding, lax.max, -jnp.inf)


def _avg_pool1d(x, k=2, s=None, padding="VALID"):
    return _pool_nd(x, (k,), (s or k,), padding, lax.add, 0.0) / k


def _max_pool3d(x, k=(2, 2, 2), s=None, padding="VALID"):
    return _pool_nd(x, k, s or k, padding, lax.max, -jnp.inf)


def _avg_pool3d(x, k=(2, 2, 2), s=None, padding="VALID"):
    return _pool_nd(x, k, s or k, padding, lax.add, 0.0) / _pymath.prod(k)


def _pnorm_pool2d(x, p=2.0, k=(2, 2), s=None, padding="VALID"):
    """DL4J PNORM pooling, per-window EXACT at any p: windows are
    extracted as patches so each normalizes by its OWN max —
    m_w * (Σ (|x|/m_w)^p)^(1/p) keeps every intermediate in [0, 1]
    with no cross-window coupling (a global-max prescale would flush
    windows far below the global max to zero at large p).

    SubsamplingLayer's pnorm path keeps the reference's direct
    ``Σ|x|^p`` reduce_window (bit-parity with DL4J, which computes the
    same way and has the same f32 range limits; fine at practical
    p ≲ 16) — use this op when p is large."""
    s = s or k
    kh, kw = k
    if padding == "SAME":
        h, w = x.shape[1], x.shape[2]
        oh, ow = -(-h // s[0]), -(-w // s[1])
        pad_h = max((oh - 1) * s[0] + kh - h, 0)
        pad_w = max((ow - 1) * s[1] + kw - w, 0)
        x = jnp.pad(x, ((0, 0), (pad_h // 2, pad_h - pad_h // 2),
                        (pad_w // 2, pad_w - pad_w // 2), (0, 0)))
    cols = _im2col(x, kh, kw, s[0], s[1])          # [N,oh,ow,kh*kw*C]
    n, oh, ow, _ = cols.shape
    patches = jnp.abs(cols.reshape(n, oh, ow, kh * kw, x.shape[-1]))
    m = jnp.maximum(jnp.max(patches, axis=3), 1e-30)
    scaled = jnp.sum((patches / m[:, :, :, None, :]) ** p, axis=3)
    return m * scaled ** (1.0 / p)


def _col2im(cols, h, w, kh, kw, sh=1, sw=1, ph=0, pw=0):
    """Inverse of :func:`_im2col`: scatter-add patches back to the
    [N, H, W, C] image (libnd4j ``col2im`` — the conv backward lowering)."""
    n, oh, ow, _ = cols.shape
    c = cols.shape[3] // (kh * kw)
    cols = cols.reshape(n, oh, ow, kh, kw, c)
    img = jnp.zeros((n, h + 2 * ph, w + 2 * pw, c), cols.dtype)
    idx_h = (jnp.arange(oh)[:, None] * sh + jnp.arange(kh)[None, :])  # [oh,kh]
    idx_w = (jnp.arange(ow)[:, None] * sw + jnp.arange(kw)[None, :])  # [ow,kw]
    hh = jnp.broadcast_to(idx_h[:, None, :, None], (oh, ow, kh, kw)).ravel()
    ww = jnp.broadcast_to(idx_w[None, :, None, :], (oh, ow, kh, kw)).ravel()
    vals = cols.reshape(n, -1, c)
    img = img.at[:, hh, ww, :].add(vals)
    return img[:, ph:ph + h, pw:pw + w, :]


def _local_response_normalization(x, depth_radius=5, bias=1.0, alpha=1.0,
                                  beta=0.5):
    """TF-style LRN over the channel axis (libnd4j ``lrn``)."""
    sq = x * x
    c = x.shape[-1]
    pad = jnp.pad(sq, [(0, 0)] * (x.ndim - 1) + [(depth_radius, depth_radius)])
    window = sum(pad[..., i:i + c] for i in range(2 * depth_radius + 1))
    return x / jnp.power(bias + alpha * window, beta)


def _batch_to_space(x, block, crops=((0, 0), (0, 0))):
    n, h, w, c = x.shape
    out = x.reshape(block, block, n // block ** 2, h, w, c)
    out = out.transpose(2, 3, 0, 4, 1, 5).reshape(
        n // block ** 2, h * block, w * block, c)
    (ct, cb), (cl, cr) = crops
    return out[:, ct:h * block - cb, cl:w * block - cr, :]


def _space_to_batch(x, block, pads=((0, 0), (0, 0))):
    x = jnp.pad(x, ((0, 0), tuple(pads[0]), tuple(pads[1]), (0, 0)))
    n, h, w, c = x.shape
    out = x.reshape(n, h // block, block, w // block, block, c)
    return out.transpose(2, 4, 0, 1, 3, 5).reshape(
        n * block ** 2, h // block, w // block, c)


cnn = SimpleNamespace(
    conv1d=_conv1d,
    conv2d=_conv2d,
    conv3d=_conv3d,
    depthwise_conv2d=_depthwise_conv2d,
    separable_conv2d=_separable_conv2d,
    deconv2d=_deconv2d,
    deconv3d=_deconv3d,
    max_pooling1d=_max_pool1d,
    avg_pooling1d=_avg_pool1d,
    max_pooling2d=_max_pool2d,
    avg_pooling2d=_avg_pool2d,
    max_pooling3d=_max_pool3d,
    avg_pooling3d=_avg_pool3d,
    pnorm_pooling2d=_pnorm_pool2d,
    global_max_pooling=lambda x: jnp.max(x, axis=tuple(range(1, x.ndim - 1))),
    global_avg_pooling=lambda x: jnp.mean(x, axis=tuple(range(1, x.ndim - 1))),
    im2col=_im2col,
    col2im=_col2im,
    local_response_normalization=_local_response_normalization,
    batch_to_space=_batch_to_space,
    space_to_batch=_space_to_batch,
    space_to_depth=lambda x, s: x.reshape(x.shape[0], x.shape[1] // s, s,
                                          x.shape[2] // s, s, x.shape[3])
    .transpose(0, 1, 3, 2, 4, 5).reshape(x.shape[0], x.shape[1] // s, x.shape[2] // s, -1),
    depth_to_space=lambda x, s: x.reshape(x.shape[0], x.shape[1], x.shape[2], s, s, -1)
    .transpose(0, 1, 3, 2, 4, 5).reshape(x.shape[0], x.shape[1] * s, x.shape[2] * s, -1),
    upsampling1d=lambda x, s: jnp.repeat(x, s, axis=1),
    upsampling2d=lambda x, s: jnp.repeat(jnp.repeat(x, s, axis=1), s, axis=2),
    upsampling3d=lambda x, s: jnp.repeat(jnp.repeat(jnp.repeat(
        x, s, axis=1), s, axis=2), s, axis=3),
)

# ---------------------------------------------------------------- rnn / loss
from deeplearning4j_tpu.nn import losses as _losses  # noqa: E402

loss = SimpleNamespace(
    **{name: _losses.get(name) for name in
       ("mcxent", "mse", "mae", "l1", "l2", "binary_xent", "hinge",
        "squared_hinge", "poisson", "kl_divergence", "cosine_proximity",
        "mape", "msle", "sparse_mcxent", "wasserstein", "fmeasure",
        "huber", "log_poisson", "weighted_cross_entropy_with_logits",
        "mean_pairwise_squared_error")},
    mean_score=_losses.mean_score,
)

rnn = SimpleNamespace()  # populated below to avoid circular imports at module load


def _lstm_layer(x, w, u, b, h0=None, c0=None):
    """Functional LSTM over [B,T,C] with IFOG-packed weights — libnd4j
    ``lstmLayer`` parity."""
    from deeplearning4j_tpu.nn.layers.recurrent import LSTM as _LSTM
    hsz = u.shape[0]
    layer = _LSTM(n_out=hsz)
    params = {"W": w, "U": u, "b": b}
    carry = (h0 if h0 is not None else jnp.zeros((x.shape[0], hsz), x.dtype),
             c0 if c0 is not None else jnp.zeros((x.shape[0], hsz), x.dtype))
    y, carry = layer._scan(params, x, None, carry)
    return y, carry


def _gru_cell(x_t, h_prev, w, u, b):
    from deeplearning4j_tpu.nn.layers.recurrent import GRU as _GRU
    layer = _GRU(n_out=u.shape[0])
    new_h, _ = layer.step({"W": w, "U": u, "b": b}, h_prev, x_t)
    return new_h


rnn.lstm_layer = _lstm_layer
rnn.gru_cell = _gru_cell

from deeplearning4j_tpu.ops import extra as _extra  # noqa: E402

rnn.lstm_cell = _extra.lstm_cell
rnn.lstm_block = _extra.lstm_block
rnn.gru = _extra.gru
rnn.sru = _extra.sru
rnn.sru_cell = _extra.sru_cell
rnn.simple_rnn = _extra.simple_rnn


# ---------------------------------------------------------------- linalg
linalg = SimpleNamespace(
    mmul=jnp.matmul, matmul=jnp.matmul,
    gemm=lambda a, b, alpha=1.0, beta=0.0, c=None, transpose_a=False, transpose_b=False:
        alpha * jnp.matmul(a.T if transpose_a else a, b.T if transpose_b else b)
        + (beta * c if c is not None else 0.0),
    tensormmul=jnp.tensordot,
    dot=jnp.dot, vdot=jnp.vdot, outer=jnp.outer, einsum=jnp.einsum,
    cholesky=jnp.linalg.cholesky, svd=jnp.linalg.svd, qr=jnp.linalg.qr,
    inv=jnp.linalg.inv, pinv=jnp.linalg.pinv, det=jnp.linalg.det,
    slogdet=jnp.linalg.slogdet, eig=jnp.linalg.eig, eigh=jnp.linalg.eigh,
    solve=jnp.linalg.solve, lstsq=jnp.linalg.lstsq,
    matrix_rank=jnp.linalg.matrix_rank, norm=jnp.linalg.norm,
    trace=jnp.trace, diag=jnp.diag, diag_part=jnp.diagonal,
    matrix_band_part=lambda x, lower, upper: jnp.where(
        (jnp.arange(x.shape[-2])[:, None] - jnp.arange(x.shape[-1])[None, :] <= (lower if lower >= 0 else x.shape[-2]))
        & (jnp.arange(x.shape[-1])[None, :] - jnp.arange(x.shape[-2])[:, None] <= (upper if upper >= 0 else x.shape[-1])),
        x, 0),
    tri=jnp.tri, tril=jnp.tril, triu=jnp.triu,
    cross=jnp.cross, kron=jnp.kron,
    matrix_power=jnp.linalg.matrix_power,
    matrix_diag=lambda v: jnp.zeros(v.shape + (v.shape[-1],), v.dtype)
    .at[..., jnp.arange(v.shape[-1]), jnp.arange(v.shape[-1])].set(v),
    matrix_set_diag=lambda x, v: x.at[..., jnp.arange(min(x.shape[-2:])),
                                      jnp.arange(min(x.shape[-2:]))].set(v),
    lu=jax.scipy.linalg.lu,
)


# ---------------------------------------------------------------- random
random = SimpleNamespace(
    normal=jax.random.normal, uniform=jax.random.uniform,
    bernoulli=jax.random.bernoulli,
    truncated_normal=jax.random.truncated_normal,
    gamma=jax.random.gamma, beta=jax.random.beta,
    exponential=jax.random.exponential, poisson=jax.random.poisson,
    binomial=jax.random.binomial, categorical=jax.random.categorical,
    gumbel=jax.random.gumbel, laplace=jax.random.laplace,
    log_normal=lambda key, shape=(), mean=0.0, std=1.0:
        jnp.exp(mean + std * jax.random.normal(key, shape)),
    shuffle=jax.random.permutation, choice=jax.random.choice,
    split=jax.random.split, key=jax.random.key, fold_in=jax.random.fold_in,
)


# ---------------------------------------------------------------- image
def _resize_bilinear(img, out_h, out_w):
    shape = img.shape[:-3] + (out_h, out_w, img.shape[-1])
    return jax.image.resize(img, shape, method="bilinear")


def _resize_nearest(img, out_h, out_w):
    shape = img.shape[:-3] + (out_h, out_w, img.shape[-1])
    return jax.image.resize(img, shape, method="nearest")


from deeplearning4j_tpu.ops import extra as _extra_img  # noqa: E402

image = SimpleNamespace(
    resize_bilinear=_resize_bilinear,
    resize_nearest=_resize_nearest,
    resize_bicubic=_extra_img.resize_bicubic,
    resize_area=_extra_img.resize_area,
    flip_left_right=lambda x: jnp.flip(x, axis=-2),
    flip_up_down=lambda x: jnp.flip(x, axis=-3),
    rot90=lambda x, k=1: jnp.rot90(x, k, axes=(-3, -2)),
    adjust_brightness=lambda x, delta: x + delta,
    adjust_contrast=lambda x, factor: (x - jnp.mean(x, axis=(-3, -2), keepdims=True)) * factor
    + jnp.mean(x, axis=(-3, -2), keepdims=True),
    adjust_hue=_extra_img.adjust_hue,
    adjust_saturation=_extra_img.adjust_saturation,
    crop=lambda x, top, left, h, w: x[..., top:top + h, left:left + w, :],
    rgb_to_hsv=_extra_img.rgb_to_hsv,
    hsv_to_rgb=_extra_img.hsv_to_rgb,
    rgb_to_yuv=_extra_img.rgb_to_yuv,
    yuv_to_rgb=_extra_img.yuv_to_rgb,
    rgb_to_grayscale=lambda x: jnp.sum(
        x * jnp.array([0.2989, 0.5870, 0.1140]), axis=-1, keepdims=True),
    extract_image_patches=_extra_img.extract_image_patches,
    iou=_extra_img.iou,
    non_max_suppression=_extra_img.non_max_suppression,
    crop_and_resize=_extra_img.crop_and_resize,
)


# ---------------------------------------------------------------- bitwise
bitwise = SimpleNamespace(
    and_=jnp.bitwise_and, or_=jnp.bitwise_or, xor=jnp.bitwise_xor,
    invert=jnp.bitwise_not,
    left_shift=jnp.left_shift, right_shift=jnp.right_shift,
    bits_hamming_distance=lambda a, b: jnp.sum(
        jnp.unpackbits(jnp.bitwise_xor(a, b).view(jnp.uint8))),
)


# ---------------------------------------------------------------- scatter
# scatter/gather/segment families (libnd4j parity_ops — SURVEY §2.1);
# implementations in ops/scatter.py
from deeplearning4j_tpu.ops import scatter as _scatter_mod  # noqa: E402

scatter = SimpleNamespace(
    gather=_scatter_mod.gather,
    gather_nd=_scatter_mod.gather_nd,
    scatter_update=_scatter_mod.scatter_update,
    scatter_add=_scatter_mod.scatter_add,
    scatter_sub=_scatter_mod.scatter_sub,
    scatter_mul=_scatter_mod.scatter_mul,
    scatter_div=_scatter_mod.scatter_div,
    scatter_max=_scatter_mod.scatter_max,
    scatter_min=_scatter_mod.scatter_min,
    scatter_nd=_scatter_mod.scatter_nd,
    scatter_nd_add=_scatter_mod.scatter_nd_add,
    scatter_nd_update=_scatter_mod.scatter_nd_update,
    segment_sum=_scatter_mod.segment_sum,
    segment_mean=_scatter_mod.segment_mean,
    segment_prod=_scatter_mod.segment_prod,
    segment_max=_scatter_mod.segment_max,
    segment_min=_scatter_mod.segment_min,
    unsorted_segment_sum=_scatter_mod.unsorted_segment_sum,
    unsorted_segment_mean=_scatter_mod.unsorted_segment_mean,
    unsorted_segment_prod=_scatter_mod.unsorted_segment_prod,
    unsorted_segment_max=_scatter_mod.unsorted_segment_max,
    unsorted_segment_min=_scatter_mod.unsorted_segment_min,
    unsorted_segment_sqrt_n=_scatter_mod.unsorted_segment_sqrt_n,
)

# ctc_loss joins the loss namespace (libnd4j ctcLoss.cpp parity)
from deeplearning4j_tpu.ops.ctc import ctc_loss as _ctc_loss  # noqa: E402
loss.ctc_loss = _ctc_loss


# ---------------------------------------------------------------- base
# ND4J NDBase parity (org/nd4j/linalg/factory/ops/NDBase.java): shape,
# sequence, indexing and host-side set utilities.  Data-dependent-size
# ops (unique, boolean_mask, dynamic_partition) are eager-only, like the
# reference's host-side implementations.
base = SimpleNamespace(
    concat=jnp.concatenate,
    stack=jnp.stack,
    unstack=lambda x, axis=0: [jnp.squeeze(s, axis) for s in
                               jnp.split(x, x.shape[axis], axis)],
    split=jnp.split,
    tile=jnp.tile,
    repeat=jnp.repeat,
    squeeze=jnp.squeeze,
    expand_dims=jnp.expand_dims,
    transpose=jnp.transpose,
    permute=lambda x, *axes: jnp.transpose(x, axes if axes else None),
    reshape=jnp.reshape,
    slice=lax.slice,
    strided_slice=lambda x, begin, end, strides: x[tuple(
        slice(b, e, s) for b, e, s in zip(begin, end, strides))],
    gather=lambda x, indices, axis=0: jnp.take(x, indices, axis=axis),
    reverse=lambda x, axis=0: jnp.flip(x, axis=axis),
    reverse_sequence=_extra.reverse_sequence,
    sequence_mask=_extra.sequence_mask,
    dynamic_partition=_extra.dynamic_partition,
    dynamic_stitch=_extra.dynamic_stitch,
    confusion_matrix=_extra.confusion_matrix,
    eye=jnp.eye,
    linspace=jnp.linspace,
    arange=jnp.arange,
    meshgrid=jnp.meshgrid,
    zeros_like=jnp.zeros_like,
    ones_like=jnp.ones_like,
    full_like=jnp.full_like,
    fill=jnp.full,
    cast=lambda x, dtype: jnp.asarray(x).astype(dtype),
    shape_of=lambda x: jnp.asarray(jnp.asarray(x).shape),
    size_of=lambda x: jnp.asarray(jnp.asarray(x).size),
    rank=lambda x: jnp.asarray(jnp.asarray(x).ndim),
    broadcast_to=jnp.broadcast_to,
    roll=jnp.roll,
    split_v=lambda x, sizes, axis=0: jnp.split(
        x, [sum(sizes[:i + 1]) for i in range(len(sizes) - 1)], axis=axis),
    top_k=_extra.top_k,
    in_top_k=_extra.in_top_k,
    unique=_extra.unique,
    unique_with_counts=_extra.unique_with_counts,
    boolean_mask=_extra.boolean_mask,
    match_condition_count=_extra.match_condition_count,
)


# ===================================================== round-5 catalog tail
# (VERDICT r4 missing #3 / next #8: the highest-value remaining
# declarables — importer-facing first.  The documented-exclusion list for
# everything still out is docs/OPS_EXCLUSIONS.md.)

# ---- matrix functions (libnd4j sqrtm / matrix exotica family)
linalg.sqrtm = jax.scipy.linalg.sqrtm
linalg.expm = jax.scipy.linalg.expm
linalg.solve_triangular = jax.scipy.linalg.solve_triangular
linalg.lu_factor = jax.scipy.linalg.lu_factor
linalg.lu_solve = jax.scipy.linalg.lu_solve
linalg.cho_factor = jax.scipy.linalg.cho_factor
linalg.cho_solve = jax.scipy.linalg.cho_solve
linalg.eigvals = jnp.linalg.eigvals
linalg.eigvalsh = jnp.linalg.eigvalsh
linalg.tensorsolve = jnp.linalg.tensorsolve
linalg.tensorinv = jnp.linalg.tensorinv
linalg.polar = jax.scipy.linalg.polar
linalg.block_diag = jax.scipy.linalg.block_diag
linalg.toeplitz = jax.scipy.linalg.toeplitz

# ---- remaining random distributions (libnd4j random op family)
random.randint = jax.random.randint
random.cauchy = jax.random.cauchy
random.weibull = jax.random.weibull_min
random.dirichlet = jax.random.dirichlet
random.student_t = jax.random.t
random.rademacher = jax.random.rademacher
random.multinomial = _extra.random_multinomial

# ---- image: the resize-method tail + crop/pad utilities
image.image_resize = _extra.image_resize
image.resize_lanczos3 = lambda img, h, w: _extra.image_resize(
    img, h, w, method="lanczos3")
image.resize_lanczos5 = lambda img, h, w: _extra.image_resize(
    img, h, w, method="lanczos5")
image.central_crop = _extra.central_crop
image.pad_to_bounding_box = _extra.pad_to_bounding_box

# ---- cnn: pooling/morphology tail (TF/ONNX importer-facing)
cnn.max_pool_with_argmax = _extra.max_pool_with_argmax
cnn.dilation2d = _extra.dilation2d

# ---- base/bitwise tail
base.one_hot = lambda x, depth, on_value=1.0, off_value=0.0, axis=-1, \
    dtype=None: (jax.nn.one_hot(x, depth, dtype=jnp.float32, axis=axis)
                 * (on_value - off_value)
                 + off_value).astype(dtype or jnp.float32)
base.searchsorted = jnp.searchsorted
base.diff = jnp.diff
bitwise.cyclic_shift_left = _extra.cyclic_shift_left
bitwise.cyclic_shift_right = _extra.cyclic_shift_right

# ---- ctc decoders join the loss namespace next to ctc_loss
from deeplearning4j_tpu.ops.ctc import (  # noqa: E402
    ctc_beam_decode as _ctc_beam_decode,
    ctc_greedy_decode as _ctc_greedy_decode)
loss.ctc_greedy_decode = _ctc_greedy_decode
loss.ctc_beam_decode = _ctc_beam_decode
