"""Fused 1x1-conv + BatchNorm Pallas kernel (the round-3 perf lever).

Parity target: the reference's platform engines — libnd4j
``ops/declarable/platform/cudnn/`` fused conv+BN paths (SURVEY §2.1).
On TPU the equivalent is owning the conv's VMEM tile so the BN work
rides the matmul instead of streaming activations through HBM again:

  * prologue: the PREVIOUS conv's BN fold ``xhat = act(x*a + b)`` is
    applied to the input tile in VMEM (a, b are per-channel f32 fold of
    (mean, var, gamma, beta)) — eliminates the separate normalize
    read+write pass between two convs;
  * epilogue: per-channel ``sum`` and ``sum of squares`` of the conv
    output accumulate in VMEM while the output tile is still resident —
    eliminates the separate BN-statistics read pass.

A 1x1 convolution over NHWC is exactly ``[N*H*W, Cin] @ [Cin, Cout]``,
so the kernel is a 1-D-grid matmul (M blocked, K/N whole — ResNet-50's
largest (K, N) is (2048, 512), a 2 MB bf16 weight tile that stays
resident in VMEM).  The backward is a custom_vjp with two more matmul
kernels: dX (epilogue: da, db reductions) and dW (VMEM-accumulated);
the cotangents of the emitted statistics (ds1, ds2) fold into
``dy_total = dy + ds1 + 2*y*ds2`` inside the kernels, so the entire
BN-training backward costs no extra HBM passes over activations.
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


def _live_rows(mi, block_m, m_total):
    """[block_m, 1] bool — masks the M-padding tail of the last tile."""
    row = mi * block_m + jax.lax.broadcasted_iota(jnp.int32, (block_m, 1), 0)
    return row < m_total


def _apply_prologue(x, a_ref, b_ref, *, has_prologue, relu_in):
    if not has_prologue:
        return x
    xh = x.astype(jnp.float32) * a_ref[0:1, :] + b_ref[0:1, :]
    if relu_in:
        xh = jnp.maximum(xh, 0.0)
    return xh.astype(x.dtype)


def _fwd_kernel(x_ref, w_ref, a_ref, b_ref, y_ref, s1_ref, s2_ref,
                s1_scr, s2_scr, *, has_prologue: bool, relu_in: bool,
                n_m: int, block_m: int, m_total: int):
    mi = pl.program_id(0)

    @pl.when(mi == 0)
    def _init():
        s1_scr[...] = jnp.zeros_like(s1_scr)
        s2_scr[...] = jnp.zeros_like(s2_scr)

    xh = _apply_prologue(x_ref[...], a_ref, b_ref,
                         has_prologue=has_prologue, relu_in=relu_in)
    y = jax.lax.dot_general(xh, w_ref[...], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=_prec(xh.dtype))
    y_ref[...] = y.astype(y_ref.dtype)
    ys = jnp.where(_live_rows(mi, block_m, m_total), y, 0.0)
    s1_scr[0:1, :] += jnp.sum(ys, axis=0, keepdims=True)
    s2_scr[0:1, :] += jnp.sum(ys * ys, axis=0, keepdims=True)

    @pl.when(mi == n_m - 1)
    def _flush():
        s1_ref[...] = s1_scr[...]
        s2_ref[...] = s2_scr[...]


def _dy_total(y_ref, dy_ref, ds1_ref, ds2_ref, live):
    """dy + ds1 + 2·y·ds2, with M-padding rows zeroed (they'd otherwise
    inject ds1 into the dW/da/db reductions)."""
    dy = (dy_ref[...].astype(jnp.float32) + ds1_ref[0:1, :]
          + 2.0 * y_ref[...].astype(jnp.float32) * ds2_ref[0:1, :])
    return jnp.where(live, dy, 0.0).astype(dy_ref.dtype)


def _bwd_kernel(x_ref, w_ref, a_ref, b_ref, y_ref, dy_ref, ds1_ref, ds2_ref,
                dx_ref, dw_ref, da_ref, db_ref, dw_scr, da_scr, db_scr,
                *, has_prologue: bool, relu_in: bool, n_m: int,
                block_m: int, m_total: int):
    """One merged backward pass: dX out, dW/da/db accumulated in VMEM —
    x/y/dy stream through HBM exactly once (the separate-kernels layout
    read them twice and measured ~0.6x of the XLA chain)."""
    mi = pl.program_id(0)

    @pl.when(mi == 0)
    def _init():
        dw_scr[...] = jnp.zeros_like(dw_scr)
        da_scr[...] = jnp.zeros_like(da_scr)
        db_scr[...] = jnp.zeros_like(db_scr)

    live = _live_rows(mi, block_m, m_total)
    dy = _dy_total(y_ref, dy_ref, ds1_ref, ds2_ref, live)
    # dxhat = dy_total @ W^T  (contract the Cout axis)
    dxhat = jax.lax.dot_general(dy, w_ref[...], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=_prec(dy.dtype))
    if has_prologue:
        x = x_ref[...].astype(jnp.float32)
        pre = x * a_ref[0:1, :] + b_ref[0:1, :]
        xh = (jnp.maximum(pre, 0.0) if relu_in else pre).astype(x_ref.dtype)
        dpre = jnp.where(pre > 0.0, dxhat, 0.0) if relu_in else dxhat
        dx_ref[...] = (dpre * a_ref[0:1, :]).astype(dx_ref.dtype)
        dpre = jnp.where(live, dpre, 0.0)
        da_scr[0:1, :] += jnp.sum(dpre * x, axis=0, keepdims=True)
        db_scr[0:1, :] += jnp.sum(dpre, axis=0, keepdims=True)
    else:
        xh = x_ref[...]
        dx_ref[...] = dxhat.astype(dx_ref.dtype)
    # dW += xhat^T @ dy_total  (contract the M axis)
    dw_scr[...] += jax.lax.dot_general(xh, dy, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32,
                                       precision=_prec(xh.dtype))

    @pl.when(mi == n_m - 1)
    def _flush():
        dw_ref[...] = dw_scr[...]
        da_ref[...] = da_scr[...]
        db_ref[...] = db_scr[...]


def _pad_m(x, block_m):
    pad = (-x.shape[0]) % block_m
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x


_MIB = 1024 * 1024
_VMEM_SCOPED = 16 * _MIB    # Mosaic's limit for a kernel that states none
_VMEM_BUDGET = 10 * _MIB    # the slice of it the M-tiles are sized into
_VMEM_CEILING = 64 * _MIB   # the most a kernel here states for itself:
#                             half of a v5e core's 128 MiB, the rest stays
#                             with the XLA program around the kernel


def _resident_bytes(k, n, itemsize, *, bwd):
    """VMEM no M-block choice changes.  Pallas double-buffers every
    in/out block, so the backward holds W and its f32 dW OUTPUT block
    twice, plus the f32 dW scratch; the forward holds W."""
    if bwd:
        return 2 * (k * n * itemsize + 4 * k * n) + 4 * k * n
    return k * n * itemsize


def _tile_bytes(bm, k, n, itemsize, *, bwd):
    """Double-buffered M-tiles: x, dx, y, dy (backward) or x, y."""
    return 2 * bm * ((2 * k + 2 * n) if bwd else (k + n)) * itemsize


def _pick_block(m, k, n, itemsize, *, bwd):
    """Largest power-of-two M-block whose tiles fit beside the resident
    blocks in ``_VMEM_BUDGET``; the smallest candidate where none does
    (the backward then states its real need, ``_bwd_vmem_limit``)."""
    fixed = _resident_bytes(k, n, itemsize, bwd=bwd)
    if not bwd and fixed > 14 * _MIB:
        # W alone exceeds the scoped VMEM — no block size can help
        raise ValueError(
            f"matmul_bn_act: weight [{k}, {n}] cannot fit the ~16 MB "
            f"scoped TPU VMEM; channel dims too large for the fused "
            f"kernel — use the unfused conv+BN path")
    for bm in (4096, 2048, 1024, 512, 256, 128):
        if _tile_bytes(bm, k, n, itemsize, bwd=bwd) + fixed <= _VMEM_BUDGET:
            break
    return max(8, min(bm, -(-m // 8) * 8))


def _bwd_vmem_limit(bm, k, n, itemsize):
    """Scoped-VMEM limit the backward states for itself.  Inside a whole
    train step XLA may keep a neighbouring buffer in VMEM too, so the
    default limit that admits the kernel alone refuses it there (the
    ResNet-50 stage-5 K=1024, N=2048 conv); the kernel states what it
    holds plus a quarter for the [8, ·] rows and Mosaic's own f32
    temporaries."""
    need = (_tile_bytes(bm, k, n, itemsize, bwd=True)
            + _resident_bytes(k, n, itemsize, bwd=True))
    need += need // 4
    if need > _VMEM_CEILING:
        raise ValueError(
            f"matmul_bn_act: the backward of weight [{k}, {n}] holds W, "
            f"the f32 dW block and scratch and its tiles in VMEM, "
            f"{need // _MIB} MiB of the {_VMEM_CEILING // _MIB} MiB a "
            f"kernel may claim; channel dims too large for the fused "
            f"kernel — use the unfused conv+BN path")
    return max(need, _VMEM_SCOPED)


def _row(v, n):
    """Per-channel vector → [8, n] f32 (sublane-tiled; kernels read row 0)."""
    if v is None:
        v = jnp.zeros((n,), jnp.float32)
    return jnp.broadcast_to(v.astype(jnp.float32)[None, :], (8, n))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _matmul_bn_core(x, w, a, b, has_prologue, relu_in, block_m, interpret):
    return _fwd_impl(x, w, a, b, has_prologue=has_prologue,
                     relu_in=relu_in, block_m=block_m, interpret=interpret)


def _fwd_impl(x, w, a, b, *, has_prologue, relu_in, block_m, interpret):
    m, k = x.shape
    n = w.shape[1]
    if block_m == 0:
        block_m = _pick_block(m, k, n, jnp.dtype(x.dtype).itemsize,
                              bwd=False)
    xf = _pad_m(x, block_m)
    n_m = xf.shape[0] // block_m
    av, bv = _row(a, k), _row(b, k)
    y, s1, s2 = pl.pallas_call(
        functools.partial(_fwd_kernel, has_prologue=has_prologue,
                          relu_in=relu_in, n_m=n_m, block_m=block_m,
                          m_total=m),
        grid=(n_m,),
        in_specs=[
            pl.BlockSpec((block_m, k), lambda i: (i, 0)),
            pl.BlockSpec((k, n), lambda i: (0, 0)),
            pl.BlockSpec((8, k), lambda i: (0, 0)),
            pl.BlockSpec((8, k), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, n), lambda i: (i, 0)),
            pl.BlockSpec((8, n), lambda i: (0, 0)),
            pl.BlockSpec((8, n), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((xf.shape[0], n), x.dtype),
            jax.ShapeDtypeStruct((8, n), jnp.float32),
            jax.ShapeDtypeStruct((8, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((8, n), jnp.float32),
                        pltpu.VMEM((8, n), jnp.float32)],
        name="tpudl_matmul_bn_fwd",
        interpret=interpret,
    )(xf, w, av, bv)
    return y[:m], s1[0], s2[0]


def _matmul_bn_fwd(x, w, a, b, has_prologue, relu_in, block_m, interpret):
    y, s1, s2 = _fwd_impl(x, w, a, b, has_prologue=has_prologue,
                          relu_in=relu_in, block_m=block_m,
                          interpret=interpret)
    return (y, s1, s2), (x, w, a, b, y)


def _matmul_bn_bwd(has_prologue, relu_in, block_m, interpret, res, cts):
    x, w, a, b, y = res
    dy, ds1, ds2 = cts
    m, k = x.shape
    n = w.shape[1]
    if block_m == 0:
        block_m = _pick_block(m, k, n, jnp.dtype(x.dtype).itemsize,
                              bwd=True)
    xf = _pad_m(x, block_m)
    yf = _pad_m(y, block_m)
    dyf = _pad_m(dy, block_m)
    n_m = xf.shape[0] // block_m
    av, bv = _row(a, k), _row(b, k)
    ds1v, ds2v = _row(ds1, n), _row(ds2, n)

    dx, dw, da, db = pl.pallas_call(
        functools.partial(_bwd_kernel, has_prologue=has_prologue,
                          relu_in=relu_in, n_m=n_m, block_m=block_m,
                          m_total=m),
        grid=(n_m,),
        in_specs=[
            pl.BlockSpec((block_m, k), lambda i: (i, 0)),
            pl.BlockSpec((k, n), lambda i: (0, 0)),
            pl.BlockSpec((8, k), lambda i: (0, 0)),
            pl.BlockSpec((8, k), lambda i: (0, 0)),
            pl.BlockSpec((block_m, n), lambda i: (i, 0)),
            pl.BlockSpec((block_m, n), lambda i: (i, 0)),
            pl.BlockSpec((8, n), lambda i: (0, 0)),
            pl.BlockSpec((8, n), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, k), lambda i: (i, 0)),
            pl.BlockSpec((k, n), lambda i: (0, 0)),
            pl.BlockSpec((8, k), lambda i: (0, 0)),
            pl.BlockSpec((8, k), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((xf.shape[0], k), x.dtype),
            jax.ShapeDtypeStruct((k, n), jnp.float32),
            jax.ShapeDtypeStruct((8, k), jnp.float32),
            jax.ShapeDtypeStruct((8, k), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((k, n), jnp.float32),
                        pltpu.VMEM((8, k), jnp.float32),
                        pltpu.VMEM((8, k), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_bwd_vmem_limit(
                block_m, k, n, jnp.dtype(x.dtype).itemsize)),
        name="tpudl_matmul_bn_bwd",
        interpret=interpret,
    )(xf, w, av, bv, yf, dyf, ds1v, ds2v)

    dx = dx[:m]
    if has_prologue:
        return (dx, dw.astype(w.dtype), da[0], db[0])
    return (dx, dw.astype(w.dtype), jnp.zeros_like(a), jnp.zeros_like(b))


_matmul_bn_core.defvjp(_matmul_bn_fwd, _matmul_bn_bwd)


def matmul_bn_act(x, w, a=None, b=None, *, relu_in: bool = True,
                  block_m: int = 0, interpret: bool | None = None):
    """Fused ``y = act(x*a + b) @ w`` with BN-statistics epilogue.

    x [M, K] (the previous conv's RAW output, channels last), w [K, N],
    a/b optional per-K f32 fold of the previous BN (None = no prologue).
    Returns (y [M, N] in x.dtype, s1 [N] f32 = per-channel sum of y,
    s2 [N] f32 = per-channel sum of y²).  Fully differentiable, incl.
    through s1/s2 (the BN-training stats chain).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    has_prologue = a is not None
    if jnp.dtype(x.dtype) == jnp.float64:
        # exact reference path: the Pallas kernel accumulates stats in
        # f32, too noisy for f64 gradchecks; autodiff handles the vjp
        xh = x
        if has_prologue:
            xh = x * a.astype(x.dtype) + b.astype(x.dtype)
            if relu_in:
                xh = jnp.maximum(xh, 0.0)
        y = jax.lax.dot_general(xh, w.astype(x.dtype),
                                (((1,), (0,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST)
        return y, jnp.sum(y, axis=0), jnp.sum(y * y, axis=0)
    if a is None:
        a = jnp.ones((x.shape[1],), jnp.float32)
    if b is None:
        b = jnp.zeros((x.shape[1],), jnp.float32)
    # block_m == 0: fwd and bwd each auto-pick the largest VMEM-fitting
    # M-block (they differ — the bwd carries a dW scratch + two extra tiles)
    if block_m:
        block_m = max(8, min(block_m, -(-x.shape[0] // 8) * 8))
    return _matmul_bn_core(x, w, a, b, has_prologue, relu_in,
                           block_m, interpret)
