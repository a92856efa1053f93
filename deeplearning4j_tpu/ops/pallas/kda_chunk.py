"""Kimi Delta Attention's chunk phase as two Pallas kernels,
``tpudl_kda_chunk`` and its backward pass, ``tpudl_kda_chunk_bwd``.

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

The backward kernel reads the same inputs and the six results'
cotangents in the results' layout, recomputes ``G``, ``A`` and ``T =
(I + A)^-1`` as the forward does, and writes the inputs' cotangents in
the inputs' layout: ``dA = -T^T dT T^T`` at ``HIGHEST``; the scores'
cotangents pairwise inside row blocks and factorised between them as the
scores are; ``dg`` a product with the upper triangle of ones at
``HIGHEST``, the transpose of the running sum.

Precision is ``_chunk_phase``'s: ``G``, every exponential, ``beta``, the
inversion and ``U~`` are float32; a matrix product takes its operands in
the compute dtype and sums in float32 (``_compute_dot``); the inversion's
products, and those of its backward, run at ``Precision.HIGHEST``.  Every
exponent is <= 0 as written.  Three things are formed otherwise than in
``jax.numpy``:

* ``G`` is a product with the lower triangle of ones at ``HIGHEST``:
  Mosaic has no ``cumsum``.
* The decayed scores are taken pairwise inside row blocks of ``ROWS``
  (the float32 sublane tile), and between blocks factorised about the
  later block's first row ``a`` as ``exp(G_i - G_a) exp(G_a - G_j)``.
* ``(I + A)^-1`` is the same doubling as ``_unit_lower_inverse``, on the
  whole ``[C, C]`` tile: at block size ``s`` the inverse ``X`` is
  block-diagonal, and ``X - X R X``, ``R`` the lower-left ``s``-blocks of
  ``A`` inside each ``2s``-block, is the inverse at ``2s``.

``kda_chunk`` and ``kda_chunk_bwd`` each bind a primitive of their own
whose lowering calls one jitted ``pallas_call``: however many times a
step runs the chunk phase (four layers, each forward, rematerialised and
backward), its module holds one body of each kernel, lowered once.
Interpret mode off the chip, as ``flash_attention``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.extend.core import Primitive
from jax.interpreters import mlir

KERNEL_NAME = "tpudl_kda_chunk"
BWD_KERNEL_NAME = "tpudl_kda_chunk_bwd"
ROWS = 8            # a row block of the decayed scores: the f32 sublane tile
LANES = 128         # a head's width has to fill the lanes


def _products(compute_dtype):
    """``dot``, operands in the compute dtype summed in float32, and
    ``exact``, float32 at ``Precision.HIGHEST``.  ``contract`` names the
    axes summed over: ``((1,), (0,))`` is ``a b``, ``((1,), (1,))`` ``a
    b^T``, ``((0,), (0,))`` ``a^T b``."""
    f32, hi = jnp.float32, jax.lax.Precision.HIGHEST

    def dot(a, b, contract=((1,), (0,))):
        return jax.lax.dot_general(
            a.astype(compute_dtype), b.astype(compute_dtype),
            (contract, ((), ())), preferred_element_type=f32)

    def exact(a, b, contract=((1,), (0,))):
        return jax.lax.dot_general(a, b, (contract, ((), ())),
                                   preferred_element_type=f32, precision=hi)

    return dot, exact


def _parts(x, compute_dtype):
    """``x`` float32 as operands of a product in the compute dtype: where
    that is narrower, its rounding and the rounding of what is left, whose
    sum holds ``x`` to about twice the compute dtype's bits; else ``x``."""
    if jnp.dtype(compute_dtype).itemsize >= 4:
        return (x,)
    high = x.astype(compute_dtype)
    return high, (x - high.astype(jnp.float32)).astype(compute_dtype)


def _fine(dot, compute_dtype):
    """A product of two float32 operands as the sum of ``dot``'s of their
    :func:`_parts`, all but the two remainders': about twice the compute
    dtype's bits, at three passes.  What the backward kernel takes for
    every product with a cotangent, so that it stays at least as close to
    the float32 result as the ``jax.numpy`` backward, whose products take
    their cotangent in float32: with each operand rounded once, ``dg``
    read up to twice that path's distance (in the scores' backward ``x dx``
    and ``k dk`` cancel in ``G``'s cotangent)."""
    def fine(a, b, contract=((1,), (0,))):
        return sum(dot(x, y, contract)
                   for i, x in enumerate(_parts(a, compute_dtype))
                   for j, y in enumerate(_parts(b, compute_dtype))
                   if not (i and j))
    return fine


def _square(c):
    """(row, column) indices of a ``[c, c]`` tile."""
    return (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0),
            jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))


def _beta_column(beta_ref, eye):
    """``beta``, read as a row, as a ``[c, 1]`` column."""
    return jnp.sum(jnp.where(eye, beta_ref[...].astype(jnp.float32), 0.0),
                   axis=1, keepdims=True)


def _block_indices(c, d):
    """A ``[c, d]`` tile as ``ROWS``-row blocks: the row inside its block
    (``[blocks, ROWS, d]``), and of ``[blocks, ROWS, c]`` the column and
    its block's first column."""
    blocks, iota = c // ROWS, jax.lax.broadcasted_iota
    return (iota(jnp.int32, (blocks, ROWS, d), 1),
            iota(jnp.int32, (blocks, ROWS, c), 2),
            ROWS * iota(jnp.int32, (blocks, ROWS, c), 0))


def _scores(rows, k, g_sum, dot):
    """``sum_d x_i k_j exp(G_i - G_j)`` for ``j <= i`` of each ``x`` in
    ``rows``, zero above the diagonal: ``[c, c]`` each."""
    c, d = k.shape
    blocks = c // ROWS
    row, _ = _square(c)

    # pairwise inside a row block: column a + j of row a + r holds
    # sum_d x k_(a+j) exp(G_(a+r) - G_(a+j)) for r >= j
    shape3 = (blocks, ROWS, d)
    g3, k3 = g_sum.reshape(shape3), k.reshape(shape3)
    rows3 = tuple(x.reshape(shape3) for x in rows)
    place, lane, first = _block_indices(c, d)
    within = [jnp.zeros((blocks, ROWS, c), jnp.float32) for _ in rows3]
    for j in range(ROWS):
        decayed = k3[:, j:j + 1, :] * jnp.exp(jnp.where(
            place >= j, g3 - g3[:, j:j + 1, :], -jnp.inf))
        for s, x in enumerate(rows3):
            within[s] = jnp.where(lane == first + j, jnp.sum(
                x * decayed, axis=-1, keepdims=True), within[s])
    scores = [s.reshape(c, c) for s in within]

    # against the blocks before: exp(G_i - G_a) exp(G_a - G_j), a the
    # first row of i's block, both factors a product's operands
    anchor = jnp.broadcast_to(g3[:, :1, :], shape3).reshape(c, d)
    left = jnp.exp(g_sum - anchor)
    lefts = [x * left for x in rows]
    token = jax.lax.broadcasted_iota(jnp.int32, (c, d), 0)
    for p in range(1, blocks):
        a = p * ROWS
        right = k * jnp.exp(jnp.where(token < a, g_sum[a:a + 1] - g_sum,
                                      -jnp.inf))
        here = (row >= a) & (row < a + ROWS)
        for s, x in enumerate(lefts):
            scores[s] = jnp.where(here, scores[s] + dot(
                x, right, ((1,), (1,))), scores[s])
    return scores


def _scores_bwd(rows, d_scores, k, g_sum, fine):
    """The cotangents of :func:`_scores`' inputs from the scores' (zero
    wherever a score is): of each ``x`` in ``rows``, ``dx_i = sum_j dS_ij
    k_j exp(G_i - G_j)``, and of ``k`` as the key, ``dk_j = sum_i dS_ij
    x_i exp(G_i - G_j)`` summed over ``rows``.  ``G``'s is ``x dx - k dk``.
    Taken as the forward takes the scores: pairwise inside a row block,
    factorised about the later block's first row between blocks, those
    products taken ``fine``."""
    c, d = k.shape
    blocks, f32 = c // ROWS, jnp.float32
    row, col = _square(c)

    shape3 = (blocks, ROWS, d)
    g3, k3 = g_sum.reshape(shape3), k.reshape(shape3)
    rows3 = tuple(x.reshape(shape3) for x in rows)
    d3 = tuple(s.reshape(blocks, ROWS, c) for s in d_scores)
    place, lane, first = _block_indices(c, d)
    d_rows3 = [jnp.zeros(shape3, f32) for _ in rows3]
    d_key3 = jnp.zeros(shape3, f32)
    for j in range(ROWS):
        decay = jnp.exp(jnp.where(place >= j, g3 - g3[:, j:j + 1, :],
                                  -jnp.inf))
        to_key = jnp.zeros(shape3, f32)
        for s, x in enumerate(rows3):
            weight = decay * jnp.sum(jnp.where(lane == first + j, d3[s], 0.0),
                                     axis=-1, keepdims=True)
            d_rows3[s] = d_rows3[s] + weight * k3[:, j:j + 1, :]
            to_key = to_key + weight * x
        d_key3 = jnp.where(place == j, jnp.sum(to_key, axis=1,
                                               keepdims=True), d_key3)
    d_key = d_key3.reshape(c, d)

    anchor = jnp.broadcast_to(g3[:, :1, :], shape3).reshape(c, d)
    left = jnp.exp(g_sum - anchor)
    lefts = [x * left for x in rows]
    token = jax.lax.broadcasted_iota(jnp.int32, (c, d), 0)
    between = [jnp.zeros((c, d), f32) for _ in rows]
    for p in range(1, blocks):
        a = p * ROWS
        right = jnp.exp(jnp.where(token < a, g_sum[a:a + 1] - g_sum,
                                  -jnp.inf))
        here = (row >= a) & (row < a + ROWS) & (col < a)
        for s, x in enumerate(lefts):
            d_here = jnp.where(here, d_scores[s], 0.0)
            between[s] = between[s] + fine(d_here, k * right)
            d_key = d_key + right * fine(d_here, x, ((0,), (0,)))
    return ([dx.reshape(c, d) + left * b for dx, b in zip(d_rows3, between)],
            d_key)


def _inverse(a_low, exact):
    """``(I + A)^-1`` of the strictly lower ``a_low`` ``[c, c]``: at block
    size ``s`` the inverse ``X`` is block-diagonal, and ``X - X R X``,
    ``R`` the lower-left ``s``-blocks of ``A`` inside each ``2s``-block,
    is the inverse at ``2s``."""
    c = a_low.shape[0]
    row, col = _square(c)
    solve, s = (row == col).astype(jnp.float32), 1
    while s < c:
        shift = s.bit_length()                            # log2(2 s)
        corner = ((row >> shift) == (col >> shift)) & ((row & s) != 0) \
            & ((col & s) == 0)
        solve = solve - exact(exact(solve, jnp.where(corner, a_low, 0.0)),
                              solve)
        s *= 2
    return solve


def _kernel(q_ref, k_ref, v_ref, g_ref, beta_ref,
            w_ref, u_ref, k_left_ref, decay_ref, q_decayed_ref, b_qk_ref,
            *, compute_dtype):
    f32 = jnp.float32
    dot, exact = _products(compute_dtype)
    q, k, v, g = (ref[...].astype(f32) for ref in (q_ref, k_ref, v_ref, g_ref))
    c = k.shape[0]
    row, col = _square(c)

    g_sum = exact((row >= col).astype(f32), g)            # G, inclusive
    end = g_sum[c - 1:c]                                  # G_C, [1, d]
    b_qk, a_kk = _scores((q, k), k, g_sum, dot)

    # T = (I + A)^-1, A_ij = beta_i (a_kk)_ij below the diagonal
    beta = _beta_column(beta_ref, row == col)             # [c, 1]
    solve = _inverse(jnp.where(row > col, beta * a_kk, 0.0), exact)

    u_ref[...] = dot(solve, beta * v).astype(u_ref.dtype)
    w_ref[...] = dot(solve, beta * k * jnp.exp(g_sum)).astype(w_ref.dtype)
    k_left_ref[...] = (k * jnp.exp(end - g_sum)).astype(k_left_ref.dtype)
    decay_ref[...] = jnp.exp(end).astype(decay_ref.dtype)
    q_decayed_ref[...] = (q * jnp.exp(g_sum)).astype(q_decayed_ref.dtype)
    b_qk_ref[...] = b_qk.astype(b_qk_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref,
                d_w_ref, d_u_ref, d_k_left_ref, d_decay_ref, d_q_decayed_ref,
                d_b_qk_ref, dq_ref, dk_ref, dv_ref, dg_ref, d_beta_ref,
                *, compute_dtype):
    """:func:`_kernel`'s inputs again, ``G``, ``A`` and ``T`` recomputed
    as it computes them, and the cotangents of its six results -> those
    of its five inputs."""
    f32 = jnp.float32
    dot, exact = _products(compute_dtype)
    q, k, v, g = (ref[...].astype(f32) for ref in (q_ref, k_ref, v_ref, g_ref))
    d_w, d_u, d_k_left, d_decay, d_q_decayed, d_b_qk = (
        ref[...].astype(f32) for ref in (d_w_ref, d_u_ref, d_k_left_ref,
                                         d_decay_ref, d_q_decayed_ref,
                                         d_b_qk_ref))
    c = k.shape[0]
    row, col = _square(c)
    eye = row == col

    g_sum = exact((row >= col).astype(f32), g)
    end = g_sum[c - 1:c]
    (a_kk,) = _scores((k,), k, g_sum, dot)
    beta = _beta_column(beta_ref, eye)
    solve = _inverse(jnp.where(row > col, beta * a_kk, 0.0), exact)
    grow = jnp.exp(g_sum)
    beta_v, beta_k = beta * v, beta * k * grow

    # U~ = T (beta V), W = T (beta K exp(G)); then T = (I + A)^-1:
    # dA = -T^T dT T^T, below the diagonal
    fine = _fine(dot, compute_dtype)
    d_solve = fine(d_u, beta_v, ((1,), (1,))) + fine(d_w, beta_k,
                                                      ((1,), (1,)))
    d_beta_v = fine(solve, d_u, ((0,), (0,)))
    d_beta_k = fine(solve, d_w, ((0,), (0,)))
    d_a = jnp.where(row > col, -exact(
        solve, exact(d_solve, solve, ((1,), (1,))), ((0,), (0,))), 0.0)
    d_beta = (jnp.sum(d_a * a_kk, axis=1, keepdims=True)
              + jnp.sum(v * d_beta_v, axis=1, keepdims=True)
              + jnp.sum(k * grow * d_beta_k, axis=1, keepdims=True))
    dv = beta * d_beta_v
    dk = beta * grow * d_beta_k
    d_g = beta_k * d_beta_k

    # K exp(G_C - G), exp(G_C) and Q exp(G)
    shrink = jnp.exp(end - g_sum)
    kept = k * shrink * d_k_left
    dk = dk + shrink * d_k_left
    d_g = d_g - kept
    d_end = jnp.sum(kept, axis=0, keepdims=True) + jnp.exp(end) * d_decay
    dq = grow * d_q_decayed
    d_g = d_g + q * dq

    # the decayed scores: B with q as its rows, A's scores with k
    (d_rows_q, d_rows_k), d_key = _scores_bwd(
        (q, k), (jnp.where(row >= col, d_b_qk, 0.0), beta * d_a), k, g_sum,
        fine)
    dq = dq + d_rows_q
    dk = dk + d_rows_k + d_key
    d_g = d_g + q * d_rows_q + k * (d_rows_k - d_key)

    # G = (lower ones) g: dg = (upper ones) dG, and G_C holds every g
    dq_ref[...] = dq
    dk_ref[...] = dk
    dv_ref[...] = dv
    dg_ref[...] = exact((row <= col).astype(f32), d_g) + d_end
    d_beta_ref[...] = jnp.sum(jnp.where(eye, d_beta, 0.0), axis=0,
                              keepdims=True)


def _tile_spec(chunk, d):
    """A head's chunk of a ``[B, T, H d]`` array."""
    return pl.BlockSpec((None, chunk, d), lambda i, h, c: (i, c, h))


def _beta_spec(chunk):
    """A head's chunk of ``beta`` laid out ``[B, H, n, 1, chunk]``."""
    return pl.BlockSpec((None, None, None, 1, chunk),
                        lambda i, h, c: (i, h, c, 0, 0))


def _grouped_spec(shape, head_group):
    """A head's chunk of a ``[groups, B, head_group, n, ...]`` array."""
    return pl.BlockSpec(
        (None, None, None, None) + tuple(shape[-2:]),
        lambda i, h, c: (h // head_group, i, h % head_group, c, 0, 0))


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
    return pl.pallas_call(
        functools.partial(_kernel, compute_dtype=jnp.dtype(compute_dtype)),
        grid=(b, heads, t // chunk),
        in_specs=[_tile_spec(chunk, head_dim), _tile_spec(chunk, head_dim),
                  _tile_spec(chunk, dv), _tile_spec(chunk, head_dim),
                  _beta_spec(chunk)],
        out_specs=[_grouped_spec(s.shape, head_group) for s in shapes],
        out_shape=list(shapes),
        name=KERNEL_NAME,
        interpret=interpret,
    )(q, k, v, g, beta)


def _bwd_shapes(q, v, beta):
    """The cotangents of ``q``, ``k``, ``v``, ``g`` and ``beta``, each in
    its input's layout and dtype."""
    return [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, q, v, q, beta)]


@functools.partial(jax.jit, static_argnames=(
    "chunk", "head_dim", "head_group", "compute_dtype", "interpret"))
def _pallas_bwd(q, k, v, g, beta, *cts, chunk, head_dim, head_group,
                compute_dtype, interpret):
    b, t, width = q.shape
    heads, dv = width // head_dim, v.shape[-1] // (width // head_dim)
    inputs = [_tile_spec(chunk, head_dim), _tile_spec(chunk, head_dim),
              _tile_spec(chunk, dv), _tile_spec(chunk, head_dim),
              _beta_spec(chunk)]
    return pl.pallas_call(
        functools.partial(_bwd_kernel,
                          compute_dtype=jnp.dtype(compute_dtype)),
        grid=(b, heads, t // chunk),
        in_specs=inputs + [_grouped_spec(x.shape, head_group) for x in cts],
        out_specs=inputs,
        out_shape=_bwd_shapes(q, v, beta),
        # a program writes the blocks it read: each cotangent takes its
        # input's buffer where nothing reads that input after the kernel
        input_output_aliases={i: i for i in range(5)},
        name=BWD_KERNEL_NAME,
        interpret=interpret,
    )(q, k, v, g, beta, *cts)


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

kda_chunk_bwd_p = Primitive(BWD_KERNEL_NAME)
kda_chunk_bwd_p.multiple_results = True
kda_chunk_bwd_p.def_impl(_pallas_bwd)
kda_chunk_bwd_p.def_abstract_eval(
    lambda q, k, v, g, beta, *cts, **params: [
        jax.core.ShapedArray(s.shape, s.dtype)
        for s in _bwd_shapes(q, v, beta)])
mlir.register_lowering(kda_chunk_bwd_p,
                       mlir.lower_fun(_pallas_bwd, multiple_results=True))


def takes(head_dim: int, chunk: int) -> bool:
    """Whether the kernels serve a head size and a chunk: a head fills the
    lanes, a chunk the float32 sublanes."""
    return head_dim % LANES == 0 and chunk % ROWS == 0


def _layout(q, k, v, g, beta, chunk):
    """``[B, T, H, d]`` -> ``[B, n chunk, H d]``, and ``beta`` ``[B, T, H]``
    -> ``[B, H, n, 1, chunk]``: zero-padded to whole chunks, as the
    kernels read them."""
    b, t, heads, _ = k.shape
    n = -(-t // chunk)

    def padded(x):
        return jnp.pad(x, ((0, 0), (0, n * chunk - t)) + ((0, 0),) * (
            x.ndim - 2)) if n * chunk != t else x

    return (*(padded(x).reshape(b, n * chunk, -1) for x in (q, k, v, g)),
            jnp.swapaxes(padded(beta), 1, 2).reshape(b, heads, n, 1, chunk))


def _interpret(interpret):
    return jax.default_backend() != "tpu" if interpret is None else interpret


def kda_chunk(q, k, v, g, beta, *, chunk: int, head_group: int,
              compute_dtype, interpret: bool | None = None):
    """``q``, ``k``, ``g`` ``[B, T, H, d_k]`` and ``v`` ``[B, T, H, d_v]``
    float32, ``beta`` ``[B, T, H]`` -> ``_chunk_phase``'s six results for
    all heads, ``[H / head_group, B, head_group, n, chunk, ...]``.  A
    length that is no multiple of ``chunk`` is padded with tokens that
    change nothing (``k = 0``, ``g = 0``)."""
    *out, decay, q_decayed, b_qk = kda_chunk_p.bind(
        *_layout(q, k, v, g, beta, chunk), chunk=chunk,
        head_dim=k.shape[-1], head_group=head_group,
        compute_dtype=jnp.dtype(compute_dtype),
        interpret=_interpret(interpret))
    return (*out, jnp.swapaxes(decay, -1, -2), q_decayed, b_qk)


def kda_chunk_bwd(q, k, v, g, beta, cts, *, chunk: int, head_group: int,
                  compute_dtype, interpret: bool | None = None):
    """The backward pass of :func:`kda_chunk`: its inputs, and ``cts`` the
    cotangents of its six results as it returns them -> the cotangents of
    ``q``, ``k``, ``v``, ``g`` (``[B, T, H, d]``) and ``beta`` (``[B, T,
    H]``), float32."""
    b, t, heads, _ = k.shape
    d_w, d_u, d_k_left, d_decay, d_q_decayed, d_b_qk = cts
    *grads, d_beta = kda_chunk_bwd_p.bind(
        *_layout(q, k, v, g, beta, chunk), d_w, d_u, d_k_left,
        jnp.swapaxes(d_decay, -1, -2), d_q_decayed, d_b_qk, chunk=chunk,
        head_dim=k.shape[-1], head_group=head_group,
        compute_dtype=jnp.dtype(compute_dtype),
        interpret=_interpret(interpret))
    return (*(x.reshape(b, -1, heads, x.shape[-1] // heads)[:, :t]
              for x in grads),
            jnp.swapaxes(d_beta.reshape(b, heads, -1), 1, 2)[:, :t])
