"""Kimi Delta Attention's chunk phase as one Pallas kernel,
``tpudl_kda_chunk``.

What ``nn.layers.decoder._chunk_phase`` computes of every chunk without
the state (the equations are ``chunked_delta_rule``'s), for one head's
chunk a program: from ``q``, ``k``, ``v``, ``g`` (the log decay) and
``beta`` to ``W``, ``U~``, ``K exp(G_C - G)``, ``exp(G_C)``, ``Q exp(G)``
and ``B``.  The ``[C, d]`` tiles, the running sum ``G``, the decayed
scores and the inversion stay in VMEM: a chunk's float32 intermediates
never pass through HBM.  The inputs are read in the layout the layer
projects into (``[B, T, H d]``: a head's chunk is the block ``(C, d)`` at
``(b, chunk, head)``); the outputs are written in the layout
``_scan_and_read`` reads, ``[groups, B, heads of a group, n, C, ...]``.

Precision is ``_chunk_phase``'s: ``G``, every exponential, ``beta``, the
inversion and ``U~`` are float32; a matrix product takes its operands in
the compute dtype and sums in float32 (``_compute_dot``); the inversion's
products run at ``Precision.HIGHEST``.  Every exponent is <= 0 as
written.  Three things are formed otherwise than in ``jax.numpy``:

* ``G`` is a product with the lower triangle of ones at ``HIGHEST``:
  Mosaic has no ``cumsum``.
* The decayed scores are taken pairwise inside row blocks of ``ROWS``
  (the float32 sublane tile), and between blocks factorised about the
  later block's first row ``a`` as ``exp(G_i - G_a) exp(G_a - G_j)``.
* ``(I + A)^-1`` is the same doubling as ``_unit_lower_inverse``, on the
  whole ``[C, C]`` tile: at block size ``s`` the inverse ``X`` is
  block-diagonal, and ``X - X R X``, ``R`` the lower-left ``s``-blocks of
  ``A`` inside each ``2s``-block, is the inverse at ``2s``.

``kda_chunk`` binds a primitive of its own whose lowering calls one
jitted ``pallas_call``: however many times a step runs the chunk phase
(four layers, each forward and rematerialised), its module holds one
kernel body, lowered once, and a call site for each run.  Interpret mode
off the chip, as ``flash_attention``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.extend.core import Primitive
from jax.interpreters import mlir

KERNEL_NAME = "tpudl_kda_chunk"
ROWS = 8            # a row block of the decayed scores: the f32 sublane tile
LANES = 128         # a head's width has to fill the lanes


def _kernel(q_ref, k_ref, v_ref, g_ref, beta_ref,
            w_ref, u_ref, k_left_ref, decay_ref, q_decayed_ref, b_qk_ref,
            *, compute_dtype):
    f32, hi = jnp.float32, jax.lax.Precision.HIGHEST
    q, k, v, g = (ref[...].astype(f32) for ref in (q_ref, k_ref, v_ref, g_ref))
    c, d = k.shape
    blocks = c // ROWS
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)

    def dot(a, b, contract=((1,), (0,))):
        """Operands in the compute dtype, summed in float32."""
        return jax.lax.dot_general(
            a.astype(compute_dtype), b.astype(compute_dtype),
            (contract, ((), ())), preferred_element_type=f32)

    def exact(a, b):
        return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                   preferred_element_type=f32, precision=hi)

    g_sum = exact((row >= col).astype(f32), g)            # G, inclusive
    end = g_sum[c - 1:c]                                  # G_C, [1, d]

    # pairwise inside a row block: column a + j of row a + r holds
    # sum_d x k_(a+j) exp(G_(a+r) - G_(a+j)) for r >= j
    shape3 = (blocks, ROWS, d)
    g3, k3 = g_sum.reshape(shape3), k.reshape(shape3)
    rows3 = (q.reshape(shape3), k3)
    place = jax.lax.broadcasted_iota(jnp.int32, shape3, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (blocks, ROWS, c), 2)
    first = ROWS * jax.lax.broadcasted_iota(jnp.int32, (blocks, ROWS, c), 0)
    within = [jnp.zeros((blocks, ROWS, c), f32) for _ in rows3]
    for j in range(ROWS):
        decayed = k3[:, j:j + 1, :] * jnp.exp(jnp.where(
            place >= j, g3 - g3[:, j:j + 1, :], -jnp.inf))
        for s, x in enumerate(rows3):
            within[s] = jnp.where(lane == first + j, jnp.sum(
                x * decayed, axis=-1, keepdims=True), within[s])
    b_qk, a_kk = (s.reshape(c, c) for s in within)

    # against the blocks before: exp(G_i - G_a) exp(G_a - G_j), a the
    # first row of i's block, both factors a product's operands
    anchor = jnp.broadcast_to(g3[:, :1, :], shape3).reshape(c, d)
    left = jnp.exp(g_sum - anchor)
    left_q, left_k = q * left, k * left
    token = jax.lax.broadcasted_iota(jnp.int32, (c, d), 0)
    for p in range(1, blocks):
        a = p * ROWS
        right = k * jnp.exp(jnp.where(token < a, g_sum[a:a + 1] - g_sum,
                                      -jnp.inf))
        here = (row >= a) & (row < a + ROWS)
        b_qk = jnp.where(here, b_qk + dot(left_q, right, ((1,), (1,))), b_qk)
        a_kk = jnp.where(here, a_kk + dot(left_k, right, ((1,), (1,))), a_kk)

    # T = (I + A)^-1, A_ij = beta_i (a_kk)_ij below the diagonal
    eye = row == col
    beta = jnp.sum(jnp.where(eye, beta_ref[...].astype(f32), 0.0), axis=1,
                   keepdims=True)                         # [c, 1]
    a_low = jnp.where(row > col, beta * a_kk, 0.0)
    solve, s = eye.astype(f32), 1
    while s < c:
        shift = s.bit_length()                            # log2(2 s)
        corner = ((row >> shift) == (col >> shift)) & ((row & s) != 0) \
            & ((col & s) == 0)
        solve = solve - exact(exact(solve, jnp.where(corner, a_low, 0.0)),
                              solve)
        s *= 2

    u_ref[...] = dot(solve, beta * v).astype(u_ref.dtype)
    w_ref[...] = dot(solve, beta * k * jnp.exp(g_sum)).astype(w_ref.dtype)
    k_left_ref[...] = (k * jnp.exp(end - g_sum)).astype(k_left_ref.dtype)
    decay_ref[...] = jnp.exp(end).astype(decay_ref.dtype)
    q_decayed_ref[...] = (q * jnp.exp(g_sum)).astype(q_decayed_ref.dtype)
    b_qk_ref[...] = b_qk.astype(b_qk_ref.dtype)


def _out_shapes(q_shape, v_shape, *, chunk, head_dim, head_group,
                compute_dtype):
    """``W``, ``U~``, ``K exp(G_C - G)``, ``exp(G_C)`` (a row, ``[..., 1,
    d]``), ``Q exp(G)``, ``B``: ``[groups, B, head_group, n, ...]``."""
    b, t, width = q_shape
    heads, dv = width // head_dim, v_shape[-1] // (width // head_dim)
    lead = (heads // head_group, b, head_group, t // chunk)
    cd, f32 = jnp.dtype(compute_dtype), jnp.dtype(jnp.float32)
    return (jax.ShapeDtypeStruct(lead + (chunk, head_dim), cd),
            jax.ShapeDtypeStruct(lead + (chunk, dv), f32),
            jax.ShapeDtypeStruct(lead + (chunk, head_dim), cd),
            jax.ShapeDtypeStruct(lead + (1, head_dim), f32),
            jax.ShapeDtypeStruct(lead + (chunk, head_dim), cd),
            jax.ShapeDtypeStruct(lead + (chunk, chunk), cd))


@functools.partial(jax.jit, static_argnames=(
    "chunk", "head_dim", "head_group", "compute_dtype", "interpret"))
def _pallas(q, k, v, g, beta, *, chunk, head_dim, head_group, compute_dtype,
            interpret):
    b, t, width = q.shape
    heads, dv = width // head_dim, v.shape[-1] // (width // head_dim)
    shapes = _out_shapes(q.shape, v.shape, chunk=chunk, head_dim=head_dim,
                         head_group=head_group, compute_dtype=compute_dtype)

    def tile(d):
        return pl.BlockSpec((None, chunk, d), lambda i, h, c: (i, c, h))

    def out(shape):
        return pl.BlockSpec(
            (None, None, None, None) + shape.shape[-2:],
            lambda i, h, c: (h // head_group, i, h % head_group, c, 0, 0))

    return pl.pallas_call(
        functools.partial(_kernel, compute_dtype=jnp.dtype(compute_dtype)),
        grid=(b, heads, t // chunk),
        in_specs=[tile(head_dim), tile(head_dim), tile(dv), tile(head_dim),
                  pl.BlockSpec((None, None, None, 1, chunk),
                               lambda i, h, c: (i, h, c, 0, 0))],
        out_specs=[out(shape) for shape in shapes],
        out_shape=list(shapes),
        name=KERNEL_NAME,
        interpret=interpret,
    )(q, k, v, g, beta)


kda_chunk_p = Primitive(KERNEL_NAME)
kda_chunk_p.multiple_results = True
kda_chunk_p.def_impl(_pallas)
kda_chunk_p.def_abstract_eval(
    lambda q, k, v, g, beta, **params: [
        jax.core.ShapedArray(s.shape, s.dtype)
        for s in _out_shapes(q.shape, v.shape, chunk=params["chunk"],
                             head_dim=params["head_dim"],
                             head_group=params["head_group"],
                             compute_dtype=params["compute_dtype"])])
# every call site lowers through the one jitted ``_pallas``: its jaxpr is
# traced once a shape, and a module lowers a jaxpr once
mlir.register_lowering(kda_chunk_p,
                       mlir.lower_fun(_pallas, multiple_results=True))


def takes(head_dim: int, chunk: int) -> bool:
    """Whether the kernel serves a head size and a chunk: a head fills the
    lanes, a chunk the float32 sublanes."""
    return head_dim % LANES == 0 and chunk % ROWS == 0


def kda_chunk(q, k, v, g, beta, *, chunk: int, head_group: int,
              compute_dtype, interpret: bool | None = None):
    """``q``, ``k``, ``g`` ``[B, T, H, d_k]`` and ``v`` ``[B, T, H, d_v]``
    float32, ``beta`` ``[B, T, H]`` -> ``_chunk_phase``'s six results for
    all heads, ``[H / head_group, B, head_group, n, chunk, ...]``.  A
    length that is no multiple of ``chunk`` is padded with tokens that
    change nothing (``k = 0``, ``g = 0``)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, t, heads, head_dim = k.shape
    n = -(-t // chunk)

    def padded(x):
        return jnp.pad(x, ((0, 0), (0, n * chunk - t)) + ((0, 0),) * (
            x.ndim - 2)) if n * chunk != t else x

    q, k, v, g = (padded(x).reshape(b, n * chunk, -1) for x in (q, k, v, g))
    beta = jnp.swapaxes(padded(beta), 1, 2).reshape(b, heads, n, 1, chunk)
    *out, decay, q_decayed, b_qk = kda_chunk_p.bind(
        q, k, v, g, beta, chunk=chunk, head_dim=head_dim,
        head_group=head_group, compute_dtype=jnp.dtype(compute_dtype),
        interpret=interpret)
    return (*out, jnp.swapaxes(decay, -1, -2), q_decayed, b_qk)
