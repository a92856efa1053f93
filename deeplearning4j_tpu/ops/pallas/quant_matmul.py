"""Fused int8-weight dequant-matmul Pallas kernel — the quantized-serve
hot path.

Post-training quantization (``deeplearning4j_tpu.nn.quantize``) stores
dense/embedding/conv weights as per-output-channel int8 plus an f32
scale vector; activations stay bf16 (or the policy compute dtype).  The
serving matmul then streams **one byte per weight** from HBM instead of
two (bf16) or four (f32) — on an HBM-bound serving forward that halves
the dominant traffic term, which is the whole arithmetic-intensity
argument of ROADMAP item 1 ("Tensor Processing Primitives", PAPERS.md:
a small set of fused low-precision primitives the layer zoo lowers
onto).

The kernel keeps the int8 weight tile resident in VMEM, widens it to
the activation dtype *in VMEM* (no dequantized copy ever exists in
HBM), runs the MXU matmul with f32 accumulation, and applies the
per-channel scale in the epilogue while the output tile is still
resident:

    y[m, n] = (x[m, :] @ int8_w[:, n]) * scale[n]

Grid: 1-D over M blocks; K and N ride whole (serving layer widths fit
VMEM comfortably — a 2048x2048 int8 weight is 4 MB).  Compiled on TPU,
interpreter mode on CPU; :func:`int8_matmul_reference` is the pure-jnp
oracle the parity tests hold the kernel to (1e-2 relative band — int8
quantization noise dwarfs any kernel-vs-XLA rounding).

Inference-only by design: the quantized path serves frozen weights, so
there is no backward kernel (training stays on the full-precision
path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM_BUDGET = 10 * 1024 * 1024   # conservative slice of ~16 MB VMEM


def _kernel(x_ref, w_ref, s_ref, o_ref):
    x = x_ref[...]
    # int8 → activation dtype inside VMEM; the dequantized weights never
    # round-trip through HBM
    w = w_ref[...].astype(x.dtype)
    y = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=(jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
                   else jax.lax.Precision.DEFAULT))
    o_ref[...] = (y * s_ref[0:1, :]).astype(o_ref.dtype)


def _pad_m(x, block_m):
    pad = (-x.shape[0]) % block_m
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x


def _pick_block(m, k, n, itemsize):
    """Largest power-of-two M block whose double-buffered tiles fit VMEM
    next to the resident int8 weight + f32 scale row."""
    fixed = k * n + 4 * 8 * n                 # int8 W + replicated scale
    for bm in (4096, 2048, 1024, 512, 256, 128):
        tiles = 2 * bm * (k * itemsize + 4 * n)   # x tiles + f32 y tiles
        if tiles + fixed <= _VMEM_BUDGET:
            return max(8, min(bm, -(-m // 8) * 8))
    if fixed + 2 * 128 * (k * itemsize + 4 * n) > 14 * 1024 * 1024:
        # even the smallest block cannot coexist with the resident
        # weight — fail loudly at build time, not as a Mosaic OOM at
        # serve time
        raise ValueError(
            f"int8_matmul: weight [{k}, {n}] (+ tiles) cannot fit the "
            f"~16 MB TPU VMEM even at int8 with the smallest M block — "
            f"channel dims too large for the fused kernel")
    # between the conservative budget and the hard ceiling: fall through
    # with the smallest candidate (the estimate is conservative; Mosaic
    # reports its own OOM if it truly doesn't fit)
    return max(8, min(128, -(-m // 8) * 8))


def _scale_row(scale, n):
    """Per-channel f32 scale → sublane-replicated [8, n] (TPU tiling
    wants ≥2-D operands; kernels read row 0)."""
    return jnp.broadcast_to(scale.astype(jnp.float32)[None, :], (8, n))


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def int8_matmul_pallas(x, w_q, scale, *, block_m: int = 0,
                       interpret: bool | None = None):
    """``(x @ w_q) * scale`` with the dequant fused into the matmul.

    x [M, K] bf16/f32, w_q [K, N] int8, scale [N] f32 (per output
    channel).  Returns [M, N] in x.dtype.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m, k = x.shape
    n = w_q.shape[1]
    if block_m == 0:
        block_m = _pick_block(m, k, n, jnp.dtype(x.dtype).itemsize)
    else:
        block_m = max(8, min(block_m, -(-m // 8) * 8))
    xf = _pad_m(x, block_m)
    n_m = xf.shape[0] // block_m
    y = pl.pallas_call(
        _kernel,
        grid=(n_m,),
        in_specs=[
            pl.BlockSpec((block_m, k), lambda i: (i, 0)),
            pl.BlockSpec((k, n), lambda i: (0, 0)),
            pl.BlockSpec((8, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((xf.shape[0], n), x.dtype),
        name="tpudl_quant_matmul",
        interpret=interpret,
    )(xf, w_q, _scale_row(scale, n))
    return y[:m]


def int8_matmul_reference(x, w_q, scale):
    """Pure-jnp oracle: widen, matmul in f32, scale — the numeric
    contract the Pallas kernel is held to (and the CPU serving path,
    where an interpreted grid loop would only add overhead)."""
    y = jax.lax.dot_general(
        x.astype(jnp.float32), w_q.astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return (y * scale.astype(jnp.float32)[None, :]).astype(x.dtype)


def int8_matmul(x, w_q, scale):
    """Backend dispatch for the serving layers: the compiled Pallas
    kernel on TPU, the jnp oracle elsewhere (numerically identical up to
    f32 rounding; on CPU the XLA dot is the fast path and the
    interpreter-mode kernel exists for parity tests, not serving)."""
    if jax.default_backend() == "tpu":
        return int8_matmul_pallas(x, w_q, scale)
    return int8_matmul_reference(x, w_q, scale)
