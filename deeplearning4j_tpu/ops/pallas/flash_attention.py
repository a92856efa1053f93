"""Flash (blockwise) attention Pallas kernel.

Replaces the jnp ``_block_attention`` inner step of ring attention
(SURVEY §5.7: the reference's ``dot_product_attention`` materializes the
full score matrix; the round-1 ring path still materialized per-BLOCK
score matrices in HBM).  This kernel tiles Q into [block_q, D] and
iterates K/V tiles of [block_k, D] entirely in VMEM with the classic
online-softmax recurrence — the [Tq, Tk] matrix never exists outside a
VMEM tile, scores accumulate in f32 on the MXU.

Contract matches the jnp oracle: returns UNNORMALIZED (o, m, l) — the
per-row running max and sum-exp — so ring attention can merge partial
results across ring steps exactly.  ``q_offset``/``k_offset`` give the
global positions of the local blocks for causal masking inside a sharded
ring (traced scalars are fine: they enter through SMEM).

Grid: (B*H, Tq/block_q, Tk/block_k), K-axis innermost (sequential on
TPU) with VMEM scratch carrying (acc, m, l) across K tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# the two residuals of ``flash_attention`` that only the forward kernel can
# make, by the names ``_mha_fwd`` gives them: what a ``jax.checkpoint`` around
# a call keeps (``nn.graph``'s rematerialised runs), so that the backward pass
# does not run the kernel a second time.  Outside a checkpoint a name is the
# identity.
REMAT_KEEPS = ("tpudl_flash_out", "tpudl_flash_lse")


def _kernel(qoff_ref, koff_ref, klen_ref, q_ref, k_ref, v_ref, kmask_ref,
            o_ref, m_ref, l_ref, acc_scr, m_scr, l_scr,
            *, scale: float, causal: bool, has_mask: bool, block_q: int,
            block_k: int, n_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    def _compute():
        q = q_ref[0]                                  # [block_q, D]
        k = k_ref[0]                                  # [block_k, D]
        v = v_ref[0]
        # f32 inputs: force exact (multi-pass) MXU f32 — the default would
        # round through bf16 and diverge from the jnp oracle; bf16 inputs
        # use the native single-pass MXU path with f32 accumulation
        f32_in = q.dtype == jnp.float32
        prec = jax.lax.Precision.HIGHEST if f32_in else jax.lax.Precision.DEFAULT
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=prec) * scale

        q_pos = qoff_ref[0] + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos_local = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        k_pos = koff_ref[0] + k_pos_local
        mask = k_pos_local < klen_ref[0]              # mask padded keys
        if has_mask:
            # per-(batch,head) key padding mask, sublane-replicated
            mask = mask & jnp.broadcast_to(kmask_ref[0][0:1, :] > 0,
                                           (block_q, block_k))
        if causal:
            mask = mask & (q_pos >= k_pos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                           # [block_q, 128]
        m_blk = jnp.max(s, axis=1, keepdims=True)     # [block_q, 1]
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_blk, m_prev.shape))
        # rows with nothing visible stay at NEG_INF; exp(NEG_INF-NEG_INF)
        # must not produce 1s
        alive = m_new[:, :1] > NEG_INF / 2
        p = jnp.exp(s - m_new[:, :1])
        p = jnp.where(mask & jnp.broadcast_to(alive, mask.shape), p, 0.0)
        correction = jnp.where(alive,
                               jnp.exp(m_prev[:, :1] - m_new[:, :1]), 0.0)

        # p @ v in the inputs' dtype (bf16 stays on the fast MXU path)
        pv = jax.lax.dot_general(
            p if f32_in else p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST if f32_in else jax.lax.Precision.DEFAULT)
        acc_scr[...] = acc_scr[...] * correction + pv
        l_scr[...] = l_scr[...] * correction + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), l_scr.shape)
        m_scr[...] = m_new

    if causal:
        # skip k-blocks strictly in this q-block's future — they never
        # contribute (halves the causal FLOPs)
        last_q_pos = qoff_ref[0] + (qi + 1) * block_q - 1
        first_k_pos = koff_ref[0] + ki * block_k
        pl.when(last_q_pos >= first_k_pos)(_compute)
    else:
        _compute()

    @pl.when(ki == n_k - 1)
    def _flush():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)
        # m/l emitted lane-replicated [block_q, 128] (TPU tiling needs the
        # last dim = 128); callers read lane 0
        m_ref[0] = m_scr[...].astype(m_ref.dtype)
        l_ref[0] = l_scr[...].astype(l_ref.dtype)


def _sds(q, k, shape, dtype=jnp.float32):
    """Output ShapeDtypeStruct carrying the inputs' varying-manual-axes —
    required when the kernel runs inside shard_map (ring attention).
    Older jax has neither ``jax.typeof`` nor vma tracking — there the
    plain struct is correct."""
    typeof = getattr(jax, "typeof", None)
    if typeof is None:
        return jax.ShapeDtypeStruct(shape, dtype)
    vma = frozenset()
    for a in (q, k):
        vma = vma | (getattr(typeof(a), "vma", None) or frozenset())
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _wide_heads(d: int, d_v: int):
    """Compiler parameters for heads wider than 128 (latent attention's
    192-wide keys): at the tuned 1024x1024 tile the backward's score-sized
    temporaries plus the wider operands ask for 19.1 MB of VMEM, over the
    compiler's 16 MiB default scope (a described-chip compile, PR 38), so
    such a call is given 32 MiB of a v5e core's 128.  Narrower heads keep
    the default, and their compiled kernels are what they were."""
    if max(d, d_v) <= 128:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=32 * 1024 * 1024)


def _group(h: int, h_kv: int) -> int:
    """Query heads a K/V head serves (grouped-query attention; 1 for
    multi-head attention)."""
    if h_kv < 1 or h % h_kv:
        raise ValueError(f"{h} query heads do not divide into groups over "
                         f"{h_kv} K/V heads")
    return h // h_kv


def _pad_to(x, axis, multiple):
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _block_sizes(tq, tk, block_q, block_k, dtype, interpret):
    """Clamp/round block sizes.  Sublane rounding always applies; on a
    real TPU the key-block additionally rounds to a lane tile (128) —
    or the whole (padded) row for short keys — because the mask input's
    lane-dim block must be 128-divisible or cover the array
    (Mosaic tiling rule; the interpreter has no such restriction)."""
    sublane = 16 if dtype == jnp.bfloat16 else 8
    block_q = -(-min(block_q, max(tq, sublane)) // sublane) * sublane
    block_k = -(-min(block_k, max(tk, sublane)) // sublane) * sublane
    if not interpret:
        if tk < 128:
            block_k = -(-max(tk, sublane) // sublane) * sublane  # one block
        else:
            block_k = -(-block_k // 128) * 128
    return block_q, block_k


def _key_mask_array(key_mask, b, h, tk, tk_p, block_k):
    """[B, Tk] padding mask → sublane-replicated f32 [B*H, 8, Tk_p] the
    kernels can tile as (1, 8, block_k) and read one sublane of.  With no
    mask, a single dummy block (pinned by a constant index map) keeps the
    pallas_call arity fixed without materializing [B*H, 8, Tk_p] ones —
    the kernels skip the AND entirely (static ``has_mask=False``)."""
    if key_mask is None:
        return jnp.zeros((1, 8, block_k), jnp.float32)
    km = jnp.broadcast_to(key_mask.astype(jnp.float32)[:, None, :],
                          (b, h, tk)).reshape(b * h, tk)
    km = _pad_to(km, 1, block_k)
    return jnp.broadcast_to(km[:, None, :], (b * h, 8, km.shape[1]))


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret"))
def flash_attention_block(q, k, v, *, scale: float, causal: bool = False,
                          key_mask=None, q_offset=0, k_offset=0,
                          block_q: int = 128, block_k: int = 128,
                          interpret: bool | None = None):
    """One (q-block, kv-block) flash pass.

    q [B,H,Tq,D], k [B,Hkv,Tk,D], v [B,Hkv,Tk,Dv] → (o [B,H,Tq,Dv]
    unnormalized, m [B,H,Tq] row max, l [B,H,Tq] row sum-exp) — drop-in
    for the jnp ``_block_attention`` oracle.  ``Dv`` may differ from
    ``D`` (latent attention: 192-wide keys, 128-wide values).  ``Hkv``
    may be a divisor of ``H`` (grouped-query attention): query head ``h``
    reads K/V head ``h // (H / Hkv)`` through the K/V BlockSpecs' index
    map, and no K/V head is ever copied.  ``q_offset``/``k_offset``: global
    positions of row/col 0 (ints or traced scalars).  ``key_mask``:
    optional [B, Tk] padding mask (1 = attend), broadcast over heads.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, tq, d = q.shape
    h_kv, tk, dv = k.shape[1], k.shape[2], v.shape[3]
    group = _group(h, h_kv)
    block_q, block_k = _block_sizes(tq, tk, block_q, block_k, q.dtype,
                                    interpret)

    qf = _pad_to(q.reshape(b * h, tq, d), 1, block_q)
    kf = _pad_to(k.reshape(b * h_kv, tk, d), 1, block_k)
    vf = _pad_to(v.reshape(b * h_kv, tk, dv), 1, block_k)
    tq_p, tk_p = qf.shape[1], kf.shape[1]
    n_q, n_k = tq_p // block_q, tk_p // block_k
    has_mask = key_mask is not None
    kmaskf = _key_mask_array(key_mask, b, h, tk, tk_p, block_k)
    km_map = (lambda bh, qi, ki: (bh, 0, ki)) if has_mask \
        else (lambda bh, qi, ki: (0, 0, 0))
    # the [B*Hkv, ...] K/V row a [B*H, ...] query row reads: itself for
    # multi-head attention, whose index maps stay what they were
    kv_row = (lambda bh: bh) if group == 1 else (lambda bh: bh // group)

    qoff = jnp.asarray(q_offset, jnp.int32).reshape(1)
    koff = jnp.asarray(k_offset, jnp.int32).reshape(1)
    klen = jnp.asarray(tk, jnp.int32).reshape(1)

    kernel = functools.partial(_kernel, scale=float(scale), causal=causal,
                               has_mask=has_mask, block_q=block_q,
                               block_k=block_k, n_k=n_k)
    o, m, l = pl.pallas_call(
        kernel,
        grid=(b * h, n_q, n_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, ki: (kv_row(bh), ki, 0)),
            pl.BlockSpec((1, block_k, dv),
                         lambda bh, qi, ki: (kv_row(bh), ki, 0)),
            pl.BlockSpec((1, 8, block_k), km_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 128), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 128), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[_sds(qf, kf, (b * h, tq_p, dv)),
                   _sds(qf, kf, (b * h, tq_p, 128)),
                   _sds(qf, kf, (b * h, tq_p, 128))],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        name="tpudl_flash_fwd",
        compiler_params=_wide_heads(d, dv),
        interpret=interpret,
    )(qoff, koff, klen, qf, kf, vf, kmaskf)
    o = o[:, :tq].reshape(b, h, tq, dv)
    m = m[:, :tq, 0].reshape(b, h, tq)
    l = l[:, :tq, 0].reshape(b, h, tq)
    return o, m, l


# ---------------------------------------------------------------------------
# Backward pass (round 3): standard flash backward — recompute per-block
# scores from the saved logsumexp, no [Tq, Tk] materialization.  Two
# kernels because the two reductions run over different grid axes:
#   dQ  = Σ_k  dS·K        → K-axis innermost, dq accumulates in VMEM
#   dK/dV = Σ_q dSᵀ·Q, PᵀdO → Q-axis innermost, dk/dv accumulate in VMEM
# where P = exp(S − lse), dP = dO·Vᵀ, dS = P ⊙ (dP − Δ), Δ = rowsum(dO⊙O).
# Parity: libnd4j multi_head_dot_product_attention_bp (SURVEY §2.1/§2.4).
# ---------------------------------------------------------------------------


def _bwd_p(q, k, do, v, lse, mask, *, scale, f32_in):
    """Shared tile math: returns (p, ds) [block_q, block_k] f32."""
    prec = jax.lax.Precision.HIGHEST if f32_in else jax.lax.Precision.DEFAULT
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=prec) * scale
    alive = lse > NEG_INF / 2                      # [block_q, 1]
    p = jnp.exp(s - lse)
    p = jnp.where(mask & jnp.broadcast_to(alive, mask.shape), p, 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32,
                             precision=prec)
    return p, dp


def _bwd_dq_kernel(qoff_ref, koff_ref, klen_ref, q_ref, k_ref, v_ref,
                   kmask_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
                   *, scale: float, causal: bool, has_mask: bool,
                   block_q: int, block_k: int, n_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]            # native dtype: bf16 stays on the fast MXU
        lse = lse_ref[0][:, :1]                    # [block_q, 1]
        delta = delta_ref[0][:, :1]
        f32_in = q.dtype == jnp.float32
        prec = jax.lax.Precision.HIGHEST if f32_in else jax.lax.Precision.DEFAULT

        k_pos_local = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos_local < klen_ref[0]
        if has_mask:
            mask = mask & jnp.broadcast_to(kmask_ref[0][0:1, :] > 0,
                                           (block_q, block_k))
        if causal:
            q_pos = qoff_ref[0] + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = mask & (q_pos >= koff_ref[0] + k_pos_local)

        p, dp = _bwd_p(q, k, do, v, lse, mask,
                       scale=scale, f32_in=f32_in)
        ds = p * (dp - delta) * scale              # [block_q, block_k] f32
        dq_scr[...] += jax.lax.dot_general(
            ds if f32_in else ds.astype(k.dtype), k,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)

    if causal:
        last_q_pos = qoff_ref[0] + (qi + 1) * block_q - 1
        first_k_pos = koff_ref[0] + ki * block_k
        pl.when(last_q_pos >= first_k_pos)(_compute)
    else:
        _compute()

    @pl.when(ki == n_k - 1)
    def _flush():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(qoff_ref, koff_ref, klen_ref, q_ref, k_ref, v_ref,
                    kmask_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                    dk_scr, dv_scr,
                    *, scale: float, causal: bool, has_mask: bool,
                    block_q: int, block_k: int, n_q: int):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]            # native dtype: bf16 stays on the fast MXU
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        f32_in = q.dtype == jnp.float32
        prec = jax.lax.Precision.HIGHEST if f32_in else jax.lax.Precision.DEFAULT

        k_pos_local = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos_local < klen_ref[0]
        if has_mask:
            mask = mask & jnp.broadcast_to(kmask_ref[0][0:1, :] > 0,
                                           (block_q, block_k))
        if causal:
            q_pos = qoff_ref[0] + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = mask & (q_pos >= koff_ref[0] + k_pos_local)

        p, dp = _bwd_p(q, k, do, v, lse, mask,
                       scale=scale, f32_in=f32_in)
        ds = p * (dp - delta) * scale
        # contractions over the q axis (dim 0 of both operands) — no
        # explicit transpose needed on the MXU
        pv = p if f32_in else p.astype(do.dtype)
        dsv = ds if f32_in else ds.astype(q.dtype)
        dv_scr[...] += jax.lax.dot_general(
            pv, do.astype(pv.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
        dk_scr[...] += jax.lax.dot_general(
            dsv, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)

    if causal:
        # q-blocks entirely before this k-block never attend to it
        last_q_pos = qoff_ref[0] + (qi + 1) * block_q - 1
        first_k_pos = koff_ref[0] + ki * block_k
        pl.when(last_q_pos >= first_k_pos)(_compute)
    else:
        _compute()

    @pl.when(qi == n_q - 1)
    def _flush():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_merged_kernel(qoff_ref, koff_ref, klen_ref, q_ref, k_ref, v_ref,
                       kmask_ref, do_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, dqp_ref, dk_scr, dv_scr,
                       *, scale: float, causal: bool, has_mask: bool,
                       block_q: int, block_k: int, n_q: int, group: int = 1):
    """Merged backward (round 5): ONE pass computes dK, dV and a per-
    k-block PARTIAL dQ — the per-tile score/dP recompute happens once
    instead of once per kernel (5 matmuls/tile, not 7), and Q/K/V/dO
    stream from HBM once.  dQ = Σ over k-blocks of the partials (a cheap
    jnp reduction outside); each (k-block, q-block) grid step writes a
    DISTINCT dq-partial block, so no cross-step output revisiting.

    Grouped-query attention (``group`` query heads a K/V head): the grid
    is (K/V row, k-block, query head of the group, q-block), the last two
    sequential, so that dK and dV sum over the group's heads in VMEM and
    leave once a K/V head."""
    ki = pl.program_id(1)
    qi = pl.program_id(2 if group == 1 else 3)

    def first_of_row():
        if group == 1:
            return qi == 0
        return (qi == 0) & (pl.program_id(2) == 0)

    def last_of_row():
        if group == 1:
            return qi == n_q - 1
        return (qi == n_q - 1) & (pl.program_id(2) == group - 1)

    @pl.when(first_of_row())
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        f32_in = q.dtype == jnp.float32
        prec = jax.lax.Precision.HIGHEST if f32_in else jax.lax.Precision.DEFAULT

        k_pos_local = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos_local < klen_ref[0]
        if has_mask:
            mask = mask & jnp.broadcast_to(kmask_ref[0][0:1, :] > 0,
                                           (block_q, block_k))
        if causal:
            q_pos = qoff_ref[0] + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = mask & (q_pos >= koff_ref[0] + k_pos_local)

        p, dp = _bwd_p(q, k, do, v, lse, mask,
                       scale=scale, f32_in=f32_in)
        ds = p * (dp - delta) * scale
        pv = p if f32_in else p.astype(do.dtype)
        dsv = ds if f32_in else ds.astype(q.dtype)
        dv_scr[...] += jax.lax.dot_general(
            pv, do.astype(pv.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
        dk_scr[...] += jax.lax.dot_general(
            dsv, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
        dqp_ref[0, 0] = jax.lax.dot_general(
            dsv, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec).astype(dqp_ref.dtype)

    if causal:
        last_q_pos = qoff_ref[0] + (qi + 1) * block_q - 1
        first_k_pos = koff_ref[0] + ki * block_k

        @pl.when(last_q_pos < first_k_pos)
        def _skip():
            # the partial-dq output block must still be defined
            dqp_ref[0, 0] = jnp.zeros_like(dqp_ref[0, 0])
        pl.when(last_q_pos >= first_k_pos)(_compute)
    else:
        _compute()

    @pl.when(last_of_row())
    def _flush():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _pad_rows(x, axis, multiple, value=0.0):
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.jit, static_argnames=("scale", "causal", "block_q",
                                             "block_k", "interpret",
                                             "merged"))
def flash_attention_block_bwd(q, k, v, out, lse, dout, *, scale: float,
                              causal: bool = False, key_mask=None,
                              q_offset=0, k_offset=0,
                              block_q: int = 128, block_k: int = 128,
                              interpret: bool | None = None,
                              merged: bool = True):
    """Backward of normalized blockwise attention.

    q [B,H,Tq,D], k [B,Hkv,Tk,D], v [B,Hkv,Tk,Dv], out/dout [B,H,Tq,Dv]
    (normalized output and its cotangent), lse [B,H,Tq] = m + log(l) from
    the forward pass.  Returns (dq, dk, dv) in f32, heads layout, each in
    its operand's shape: with ``Hkv`` a divisor of ``H`` (grouped-query
    attention) dK and dV are summed over each K/V head's query heads
    inside the kernel.  ``q_offset``/``k_offset``
    give global positions for causal masking inside a sharded ring.

    ``merged=True`` (default, round 5): one kernel pass produces dK, dV
    and per-k-block dQ partials (summed outside) — 5 matmuls per tile
    and one HBM stream of the operands, vs 7 matmuls over two kernels
    (measured −22% bwd wall time at seq 4096 on v5e).  ``merged=False``
    keeps the two-kernel form (the r3 oracle), for ``Dv == D`` and
    ``Hkv == H`` only.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, tq, d = q.shape
    h_kv, tk, d_v = k.shape[1], k.shape[2], v.shape[3]
    group = _group(h, h_kv)
    block_q, block_k = _block_sizes(tq, tk, block_q, block_k, q.dtype,
                                    interpret)

    # Δ_i = Σ_d dO⊙O — one cheap fused jnp pass; lse/Δ enter the kernels
    # lane-replicated (TPU tiling wants last dim 128), like fwd's m/l
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                     # [B,H,Tq]

    qf = _pad_to(q.reshape(b * h, tq, d), 1, block_q)
    kf = _pad_to(k.reshape(b * h_kv, tk, d), 1, block_k)
    vf = _pad_to(v.reshape(b * h_kv, tk, d_v), 1, block_k)
    dof = _pad_to(dout.reshape(b * h, tq, d_v), 1, block_q)
    # padded q rows carry lse = -inf → p = 0 in both kernels (no NaNs,
    # no contribution to dk/dv); padded k cols are masked via klen
    lsef = _pad_rows(lse.astype(jnp.float32).reshape(b * h, tq),
                     1, block_q, NEG_INF)
    deltaf = _pad_rows(delta.reshape(b * h, tq), 1, block_q, 0.0)
    lsef = jnp.broadcast_to(lsef[..., None], lsef.shape + (128,))
    deltaf = jnp.broadcast_to(deltaf[..., None], deltaf.shape + (128,))

    tq_p, tk_p = qf.shape[1], kf.shape[1]
    n_q, n_k = tq_p // block_q, tk_p // block_k
    has_mask = key_mask is not None
    kmaskf = _key_mask_array(key_mask, b, h, tk, tk_p, block_k)

    qoff = jnp.asarray(q_offset, jnp.int32).reshape(1)
    koff = jnp.asarray(k_offset, jnp.int32).reshape(1)
    klen = jnp.asarray(tk, jnp.int32).reshape(1)

    if merged:
        # grid (K/V row r, k-block j, [query head g of the group,] q-block
        # i): the query row a step reads is r itself, or r * group + g
        def q_at(r, j, *gi):
            return (r, gi[-1]) if group == 1 else (r * group + gi[0], gi[-1])

        q_spec2 = pl.BlockSpec((1, block_q, d), lambda *ids: (*q_at(*ids), 0))
        do_spec2 = pl.BlockSpec((1, block_q, d_v),
                                lambda *ids: (*q_at(*ids), 0))
        stat_spec2 = pl.BlockSpec((1, block_q, 128),
                                  lambda *ids: (*q_at(*ids), 0))
        k_spec2 = pl.BlockSpec((1, block_k, d), lambda r, j, *_: (r, j, 0))
        v_spec2 = pl.BlockSpec((1, block_k, d_v),
                               lambda r, j, *_: (r, j, 0))
        km_spec2 = pl.BlockSpec((1, 8, block_k),
                                (lambda *ids: (q_at(*ids)[0], 0, ids[1]))
                                if has_mask
                                else (lambda *ids: (0, 0, 0)))
        dqp_spec = pl.BlockSpec((1, 1, block_q, d),
                                lambda *ids: (ids[1], *q_at(*ids), 0))
        # partials in the input dtype: callers cast dq to q.dtype anyway
        # (custom_vjp), so bf16 partials only halve the HBM round-trip;
        # the f32 path keeps f32 partials for oracle parity
        dqp_dtype = q.dtype if q.dtype == jnp.bfloat16 else jnp.float32
        dk, dv, dqp = pl.pallas_call(
            functools.partial(_bwd_merged_kernel, scale=float(scale),
                              causal=causal, has_mask=has_mask,
                              block_q=block_q, block_k=block_k, n_q=n_q,
                              group=group),
            grid=((b * h, n_k, n_q) if group == 1
                  else (b * h_kv, n_k, group, n_q)),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * 3
            + [q_spec2, k_spec2, v_spec2, km_spec2, do_spec2,
               stat_spec2, stat_spec2],
            out_specs=[k_spec2, v_spec2, dqp_spec],
            out_shape=[_sds(qf, kf, (b * h_kv, tk_p, d)),
                       _sds(qf, kf, (b * h_kv, tk_p, d_v)),
                       _sds(qf, kf, (n_k, b * h, tq_p, d), dqp_dtype)],
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, d_v), jnp.float32)],
            name="tpudl_flash_bwd_merged",
            compiler_params=_wide_heads(d, d_v),
            interpret=interpret,
        )(qoff, koff, klen, qf, kf, vf, kmaskf, dof, lsef, deltaf)
        dq = jnp.sum(dqp.astype(jnp.float32), axis=0)
        dq = dq[:, :tq].reshape(b, h, tq, d)
        dk = dk[:, :tk].reshape(b, h_kv, tk, d)
        dv = dv[:, :tk].reshape(b, h_kv, tk, d_v)
        return dq, dk, dv

    if d_v != d or group != 1:
        raise ValueError(
            "flash_attention_block_bwd(merged=False) takes q, k and v of one "
            f"head size and as many heads, got {d} and {d_v}, {h} and "
            f"{h_kv}: only the merged kernel takes a value head size of its "
            "own and grouped K/V heads")
    smem = [pl.BlockSpec(memory_space=pltpu.SMEM)] * 3
    q_spec = pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0))
    stat_spec = pl.BlockSpec((1, block_q, 128), lambda bh, i, j: (bh, i, 0))
    k_spec = pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0))
    km_spec = pl.BlockSpec((1, 8, block_k),
                           (lambda bh, i, j: (bh, 0, j)) if has_mask
                           else (lambda bh, i, j: (0, 0, 0)))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=float(scale), causal=causal,
                          has_mask=has_mask,
                          block_q=block_q, block_k=block_k, n_k=n_k),
        grid=(b * h, n_q, n_k),
        in_specs=smem + [q_spec, k_spec, k_spec, km_spec, q_spec, stat_spec,
                         stat_spec],
        out_specs=q_spec,
        out_shape=_sds(qf, kf, (b * h, tq_p, d)),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        name="tpudl_flash_bwd_dq",
        interpret=interpret,
    )(qoff, koff, klen, qf, kf, vf, kmaskf, dof, lsef, deltaf)

    # dk/dv: swap the roles — k-blocks outer, q-axis innermost/sequential
    q_spec2 = pl.BlockSpec((1, block_q, d), lambda bh, j, i: (bh, i, 0))
    stat_spec2 = pl.BlockSpec((1, block_q, 128), lambda bh, j, i: (bh, i, 0))
    k_spec2 = pl.BlockSpec((1, block_k, d), lambda bh, j, i: (bh, j, 0))
    km_spec2 = pl.BlockSpec((1, 8, block_k),
                            (lambda bh, j, i: (bh, 0, j)) if has_mask
                            else (lambda bh, j, i: (0, 0, 0)))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=float(scale), causal=causal,
                          has_mask=has_mask,
                          block_q=block_q, block_k=block_k, n_q=n_q),
        grid=(b * h, n_k, n_q),
        in_specs=smem + [q_spec2, k_spec2, k_spec2, km_spec2, q_spec2,
                         stat_spec2, stat_spec2],
        out_specs=[k_spec2, k_spec2],
        out_shape=[_sds(qf, kf, (b * h, tk_p, d)),
                   _sds(qf, kf, (b * h, tk_p, d))],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        name="tpudl_flash_bwd_dkv",
        interpret=interpret,
    )(qoff, koff, klen, qf, kf, vf, kmaskf, dof, lsef, deltaf)

    dq = dq[:, :tq].reshape(b, h, tq, d)
    dk = dk[:, :tk].reshape(b, h, tk, d)
    dv = dv[:, :tk].reshape(b, h, tk, d)
    return dq, dk, dv


def flash_lse(m, l):
    """Logsumexp from the forward's (m, l) stats; -inf for dead rows."""
    return jnp.where(l > 0,
                     m + jnp.log(jnp.maximum(l, 1e-37)),
                     jnp.float32(NEG_INF))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _mha_core(qh, kh, vh, key_mask, scale, causal, block_q, block_k,
              interpret):
    o, m, l = flash_attention_block(qh, kh, vh, scale=scale, causal=causal,
                                    key_mask=key_mask, block_q=block_q,
                                    block_k=block_k, interpret=interpret)
    return (o / jnp.maximum(l[..., None], 1e-20)).astype(qh.dtype)


def _mha_fwd(qh, kh, vh, key_mask, scale, causal, block_q, block_k,
             interpret):
    o, m, l = flash_attention_block(qh, kh, vh, scale=scale, causal=causal,
                                    key_mask=key_mask, block_q=block_q,
                                    block_k=block_k, interpret=interpret)
    out = checkpoint_name(
        (o / jnp.maximum(l[..., None], 1e-20)).astype(qh.dtype),
        REMAT_KEEPS[0])
    lse = checkpoint_name(flash_lse(m, l), REMAT_KEEPS[1])
    return out, (qh, kh, vh, key_mask, out, lse)


def _mha_bwd(scale, causal, block_q, block_k, interpret, res, dout):
    qh, kh, vh, key_mask, out, lse = res
    dq, dk, dv = flash_attention_block_bwd(
        qh, kh, vh, out, lse, dout, scale=scale, causal=causal,
        key_mask=key_mask, block_q=block_q, block_k=block_k,
        interpret=interpret)
    dmask = None if key_mask is None else jnp.zeros_like(key_mask)
    return (dq.astype(qh.dtype), dk.astype(kh.dtype), dv.astype(vh.dtype),
            dmask)


_mha_core.defvjp(_mha_fwd, _mha_bwd)


def flash_attention(q, k, v, *, n_heads: int, n_kv_heads: int | None = None,
                    causal: bool = False, key_mask=None, block_q: int = 1024,
                    block_k: int = 1024, interpret: bool | None = None):
    """Full single-device flash attention: [B, T, H*D] → [B, T, H*D]
    (``v`` [B, T, H*Dv] with a head size of its own gives [B, T, H*Dv]).
    ``n_kv_heads`` (default ``n_heads``) K/V heads, ``k`` [B, T, Hkv*D]
    and ``v`` [B, T, Hkv*Dv], is grouped-query attention: query head ``h``
    reads K/V head ``h // (n_heads / n_kv_heads)``, in both kernels.
    Normalized output (softmax(QKᵀ/√d)·V) with no [T,T] materialization —
    the libnd4j ``multi_head_dot_product_attention`` replacement for long
    sequences on one chip.  Differentiable: ``jax.grad`` routes through
    the Pallas backward kernels (``flash_attention_block_bwd``), which
    read q, k, v, the output and the rows' logsumexp.  Under a
    ``jax.checkpoint`` whose policy saves ``REMAT_KEEPS`` (a
    ``GraphBuilder.remat`` run) the last two, ``[B,H,T,Dv]`` in q's dtype
    and ``[B,H,T]`` float32, are kept through the forward pass and the
    kernel runs once; under a bare one it runs again in the backward pass.
    ``key_mask``: optional [B, Tk] padding mask (1 = attend).  Cross
    attention (Tk != Tq) is supported."""
    b, t, dm = q.shape
    tk = k.shape[1]
    n_kv = n_kv_heads or n_heads
    dh = dm // n_heads
    dv = v.shape[2] // n_kv
    qh = q.reshape(b, t, n_heads, dh).transpose(0, 2, 1, 3)
    kh = k.reshape(b, tk, n_kv, dh).transpose(0, 2, 1, 3)
    vh = v.reshape(b, tk, n_kv, dv).transpose(0, 2, 1, 3)
    if key_mask is not None:
        key_mask = jnp.asarray(key_mask, jnp.float32)
    out = _mha_core(qh, kh, vh, key_mask, 1.0 / (dh ** 0.5), causal,
                    block_q, block_k, interpret)
    return out.transpose(0, 2, 1, 3).reshape(b, t, n_heads * dv).astype(
        q.dtype)
