"""Fused 3×3-conv + BatchNorm Pallas kernel — an experiment on the last
ResNet-50 HBM lever (~310 MB/image of BN normalize/stats traffic around
the 3×3 bottleneck convs).

Forward: NHWC stride-1 SAME 3×3 conv expressed as 9 shifted
[H·W, C] @ [C, Cout] MXU matmuls with the ENTIRE image plane resident
in VMEM (ResNet-50's 3×3 shapes are ≤ 56×3584 bf16 = 401 KB — no halo
exchange needed; grid is the batch), a BN-fold prologue
``xh = relu(x·a + b)`` applied in VMEM, and the BN-statistics epilogue
(per-channel Σy, Σy²) accumulated in VMEM scratch.  Requirements:
W·C a lane multiple (ResNet-50's 3×3 shapes are all W·C = 3584) and the
[H, W·C] plane fitting VMEM.

Backward: jax.vjp of the jnp reference (XLA conv) — the fusion claim
under test is the FORWARD's elimination of the normalize + stats
passes; the backward is shared between both paths being compared.

Verdict: measured slower than the XLA path before PR 1, on another chip
and stack; not re-measured since (PERF.md).  No layer uses it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _prec(dtype):
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _fwd_kernel(x_ref, w_ref, a_ref, b_ref, y_ref, s1_ref, s2_ref,
                s1_scr, s2_scr, *, has_prologue: bool, relu_in: bool,
                H: int, W: int, C: int, Cout: int, n_imgs: int):
    ni = pl.program_id(0)

    @pl.when(ni == 0)
    def _init():
        s1_scr[...] = jnp.zeros_like(s1_scr)
        s2_scr[...] = jnp.zeros_like(s2_scr)

    X = x_ref[0]                                    # [H, W*C]
    if has_prologue:
        Xf = X.astype(jnp.float32) * a_ref[0:1, :] + b_ref[0:1, :]
        if relu_in:
            Xf = jnp.maximum(Xf, 0.0)
        X = Xf.astype(X.dtype)
    X3 = X.reshape(H, W, C)

    acc = jnp.zeros((H * W, Cout), jnp.float32)
    for di in range(3):
        if di == 0:       # tap above: shift rows down, zero row 0
            rows = jnp.pad(X3[:-1], ((1, 0), (0, 0), (0, 0)))
        elif di == 2:     # tap below
            rows = jnp.pad(X3[1:], ((0, 1), (0, 0), (0, 0)))
        else:
            rows = X3
        for dj in range(3):
            if dj == 0:   # left neighbor: shift right, zero col 0
                sh = jnp.pad(rows[:, :-1], ((0, 0), (1, 0), (0, 0)))
            elif dj == 2:
                sh = jnp.pad(rows[:, 1:], ((0, 0), (0, 1), (0, 0)))
            else:
                sh = rows
            acc += jax.lax.dot_general(
                sh.reshape(H * W, C), w_ref[3 * di + dj],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=_prec(X.dtype))
    y_ref[0] = acc.reshape(H, W * Cout).astype(y_ref.dtype)
    s1_scr[0:1, :] += jnp.sum(acc, axis=0, keepdims=True)
    s2_scr[0:1, :] += jnp.sum(acc * acc, axis=0, keepdims=True)

    @pl.when(ni == n_imgs - 1)
    def _flush():
        s1_ref[...] = s1_scr[...]
        s2_ref[...] = s2_scr[...]


def _fwd_kernel_tiled(x_ref, xp_ref, xn_ref, w_ref, a_ref, b_ref,
                      y_ref, s1_ref, s2_ref, s1_scr, s2_scr,
                      *, has_prologue: bool, relu_in: bool, bh: int, W: int,
                      C: int, Cout: int, n_h: int):
    """Row-tiled variant for planes too large for VMEM: 8-row blocks
    with halo rows taken from the NEIGHBOR blocks (streamed as full
    sublane-legal blocks; only one row of each is used)."""
    ni = pl.program_id(0)
    hi = pl.program_id(1)

    @pl.when((ni == 0) & (hi == 0))
    def _init():
        s1_scr[...] = jnp.zeros_like(s1_scr)
        s2_scr[...] = jnp.zeros_like(s2_scr)

    xm = x_ref[0]                                     # [bh, W*C]
    xt = jnp.where(hi > 0, xp_ref[0][bh - 1:bh], 0.0).astype(xm.dtype)
    xb = jnp.where(hi < n_h - 1, xn_ref[0][0:1], 0.0).astype(xm.dtype)
    X = jnp.concatenate([xt, xm, xb], axis=0)         # [bh+2, W*C]
    if has_prologue:
        Xf = X.astype(jnp.float32) * a_ref[0:1, :] + b_ref[0:1, :]
        if relu_in:
            Xf = jnp.maximum(Xf, 0.0)
        live = jnp.concatenate(
            [jnp.where(hi > 0, 1.0, 0.0)[None, None],
             jnp.ones((bh, 1), jnp.float32),
             jnp.where(hi < n_h - 1, 1.0, 0.0)[None, None]], axis=0)
        X = (Xf * live).astype(X.dtype)
    X3 = X.reshape(bh + 2, W, C)

    acc = jnp.zeros((bh * W, Cout), jnp.float32)
    for di in range(3):
        rows = X3[di:di + bh]
        for dj in range(3):
            if dj == 0:
                sh = jnp.pad(rows[:, :-1], ((0, 0), (1, 0), (0, 0)))
            elif dj == 2:
                sh = jnp.pad(rows[:, 1:], ((0, 0), (0, 1), (0, 0)))
            else:
                sh = rows
            acc += jax.lax.dot_general(
                sh.reshape(bh * W, C), w_ref[3 * di + dj],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=_prec(X.dtype))
    y_ref[0] = acc.reshape(bh, W * Cout).astype(y_ref.dtype)
    s1_scr[0:1, :] += jnp.sum(acc, axis=0, keepdims=True)
    s2_scr[0:1, :] += jnp.sum(acc * acc, axis=0, keepdims=True)

    @pl.when((ni == pl.num_programs(0) - 1) & (hi == n_h - 1))
    def _flush():
        s1_ref[...] = s1_scr[...]
        s2_ref[...] = s2_scr[...]


def _reference(x, w, a, b, *, has_prologue, relu_in):
    """jnp twin (also the vjp source): stride-1 SAME NHWC 3×3 conv over
    the BN-folded input, returning (y, Σy, Σy²)."""
    xh = x
    if has_prologue:
        xh = x.astype(jnp.float32) * a + b
        if relu_in:
            xh = jnp.maximum(xh, 0.0)
        xh = xh.astype(x.dtype)
    dn = jax.lax.conv_dimension_numbers(x.shape, w.shape,
                                        ("NHWC", "HWIO", "NHWC"))
    # native dtype (XLA:TPU accumulates bf16 convs in f32 internally);
    # preferred_element_type=f32 here would break the conv transpose
    # rule's dtype agreement under vjp
    y = jax.lax.conv_general_dilated(
        xh, w.astype(x.dtype), (1, 1), "SAME", dimension_numbers=dn)
    yf = y.astype(jnp.float32)
    s1 = jnp.sum(yf, axis=(0, 1, 2))
    s2 = jnp.sum(yf * yf, axis=(0, 1, 2))
    return y, s1, s2


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _conv3_core(x, w, a, b, has_prologue, relu_in, interpret):
    return _fwd_impl(x, w, a, b, has_prologue=has_prologue,
                     relu_in=relu_in, interpret=interpret)


def _fwd_impl(x, w, a, b, *, has_prologue, relu_in, interpret):
    N, H, W, C = x.shape
    Cout = w.shape[-1]
    xf = x.reshape(N, H, W * C)
    wf = w.reshape(9, C, Cout)
    # per-(W·C) broadcast of the per-C fold vectors, sublane-tiled
    av = jnp.broadcast_to(jnp.tile(a.astype(jnp.float32), W)[None, :],
                          (8, W * C))
    bv = jnp.broadcast_to(jnp.tile(b.astype(jnp.float32), W)[None, :],
                          (8, W * C))

    plane_bytes = H * W * C * jnp.dtype(x.dtype).itemsize
    if plane_bytes > 2 ** 20 and H % 8 == 0 and not interpret:
        # large plane (ResNet's 56×56×64): 8-row tiles + neighbor-block
        # halos (one extra streamed block per side; only 1 row used)
        bh = 8
        n_h = H // bh
        y, s1, s2 = pl.pallas_call(
            functools.partial(_fwd_kernel_tiled, has_prologue=has_prologue,
                              relu_in=relu_in, bh=bh, W=W, C=C, Cout=Cout,
                              n_h=n_h),
            grid=(N, n_h),
            in_specs=[
                pl.BlockSpec((1, bh, W * C), lambda n, hi: (n, hi, 0)),
                pl.BlockSpec((1, bh, W * C),
                             lambda n, hi: (n, jnp.maximum(hi - 1, 0), 0)),
                pl.BlockSpec((1, bh, W * C),
                             lambda n, hi: (n, jnp.minimum(hi + 1,
                                                           n_h - 1), 0)),
                pl.BlockSpec((9, C, Cout), lambda n, hi: (0, 0, 0)),
                pl.BlockSpec((8, W * C), lambda n, hi: (0, 0)),
                pl.BlockSpec((8, W * C), lambda n, hi: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, bh, W * Cout), lambda n, hi: (n, hi, 0)),
                pl.BlockSpec((8, Cout), lambda n, hi: (0, 0)),
                pl.BlockSpec((8, Cout), lambda n, hi: (0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((N, H, W * Cout), x.dtype),
                jax.ShapeDtypeStruct((8, Cout), jnp.float32),
                jax.ShapeDtypeStruct((8, Cout), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((8, Cout), jnp.float32),
                            pltpu.VMEM((8, Cout), jnp.float32)],
            name="tpudl_conv3x3_bn_fwd_tiled",
            interpret=interpret,
        )(xf, xf, xf, wf, av, bv)
        return y.reshape(N, H, W, Cout), s1[0], s2[0]

    y, s1, s2 = pl.pallas_call(
        functools.partial(_fwd_kernel, has_prologue=has_prologue,
                          relu_in=relu_in, H=H, W=W, C=C, Cout=Cout,
                          n_imgs=N),
        grid=(N,),
        in_specs=[
            pl.BlockSpec((1, H, W * C), lambda n: (n, 0, 0)),
            pl.BlockSpec((9, C, Cout), lambda n: (0, 0, 0)),
            pl.BlockSpec((8, W * C), lambda n: (0, 0)),
            pl.BlockSpec((8, W * C), lambda n: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, H, W * Cout), lambda n: (n, 0, 0)),
            pl.BlockSpec((8, Cout), lambda n: (0, 0)),
            pl.BlockSpec((8, Cout), lambda n: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, H, W * Cout), x.dtype),
            jax.ShapeDtypeStruct((8, Cout), jnp.float32),
            jax.ShapeDtypeStruct((8, Cout), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((8, Cout), jnp.float32),
                        pltpu.VMEM((8, Cout), jnp.float32)],
        name="tpudl_conv3x3_bn_fwd",
        interpret=interpret,
    )(xf, wf, av, bv)
    return y.reshape(N, H, W, Cout), s1[0], s2[0]


def _conv3_fwd(x, w, a, b, has_prologue, relu_in, interpret):
    out = _fwd_impl(x, w, a, b, has_prologue=has_prologue, relu_in=relu_in,
                    interpret=interpret)
    return out, (x, w, a, b)


def _conv3_bwd(has_prologue, relu_in, interpret, res, cts):
    x, w, a, b = res
    _, vjp = jax.vjp(
        lambda x, w, a, b: _reference(x, w, a, b,
                                      has_prologue=has_prologue,
                                      relu_in=relu_in), x, w, a, b)
    return vjp(cts)


_conv3_core.defvjp(_conv3_fwd, _conv3_bwd)


def conv3x3_bn_act(x, w, a=None, b=None, *, relu_in: bool = True,
                   interpret: bool | None = None):
    """Fused ``y = conv3x3_SAME(act(x·a + b))`` + BN-stats epilogue.

    x [N,H,W,C] NHWC, w [3,3,C,Cout], a/b optional per-C f32 BN fold.
    Returns (y, s1 [Cout] = Σy, s2 [Cout] = Σy²).  Stride-1 SAME only;
    W·C must be a lane multiple and the [H, W·C] plane must fit VMEM.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    N, H, W, C = x.shape
    if (W * C) % 128 and not interpret:
        raise ValueError(f"W*C = {W * C} must be a lane multiple")
    if C < 128 and not interpret:
        # Mosaic rejects the [rows, W·C] → [rows·W, C] shape cast below
        # 128 lanes; padding C to 128 would double the bytes the fusion
        # exists to save
        raise NotImplementedError(
            f"conv3x3_bn_act requires C >= 128 on TPU (got {C}); "
            f"use the XLA path")
    if H * W * C * jnp.dtype(x.dtype).itemsize > 2 ** 20 and H % 8:
        raise ValueError("large image plane needs H divisible by 8 "
                         "(row-tiled path)")
    has_prologue = a is not None
    if a is None:
        a = jnp.ones((C,), jnp.float32)
    if b is None:
        b = jnp.zeros((C,), jnp.float32)
    return _conv3_core(x, w, a, b, has_prologue, relu_in, interpret)
