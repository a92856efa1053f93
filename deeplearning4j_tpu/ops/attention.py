"""Attention ops.

Parity with libnd4j ``dot_product_attention`` /
``multi_head_dot_product_attention`` (declarable ops under
``include/ops/declarable/generic/nn/attention/``) — the reference
materializes the [T,T] score matrix; here the standard path is one fused
einsum chain, from ``FLASH_AUTO_SEQ_LEN`` on the blockwise Pallas kernel; ring
attention over a mesh ``seq`` axis is ``parallel/unified.py``'s.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

NEG_INF = -1e9

# the promoted default (ROADMAP item 1): sequences at/above this length
# route through the Pallas flash kernel automatically — the crossover
# measured on a v5e before PR 1 was ~1k (1.29x over einsum at seq 4096;
# not re-measured since, see PERF.md), below it the einsum chain wins on
# launch overhead
FLASH_AUTO_SEQ_LEN = 1024

# the layout the TPU's hardware generator writes a [B,H,Tq,Tk] draw in:
# row-major, the key axis minor-most (and every backend's default)
GENERATOR_LAYOUT = Layout(major_to_minor=(0, 1, 2, 3))


def _auto_flash(q, k) -> bool:
    """Default flash routing for ``use_flash=None``: long sequences in a
    kernel-supported dtype.  Explicit True/False always wins."""
    return (max(q.shape[1], k.shape[1]) >= FLASH_AUTO_SEQ_LEN
            and q.dtype in (jnp.float32, jnp.bfloat16))


def dropout(x: jnp.ndarray, keep_prob: float, key: jax.Array,
            shape: Optional[tuple] = None,
            mask_layout: Optional[Layout] = None) -> jnp.ndarray:
    """Inverted dropout in ``x``'s dtype: keep with probability ``keep_prob``,
    scale what is kept by its inverse.  ``shape`` is the mask's, where it
    broadcasts against ``x``; ``mask_layout`` pins the layout the mask is
    held in (the draw is the same).  Guards, and what the number means
    (DL4J's retain probability, BERT's drop rate), are the callers'."""
    keep = jax.random.bernoulli(key, keep_prob, shape or x.shape)
    if mask_layout is not None:
        keep = with_layout_constraint(keep, mask_layout)
    return jnp.where(keep, x / keep_prob, 0.0).astype(x.dtype)


def dot_product_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          mask: Optional[jnp.ndarray] = None,
                          scaled: bool = True) -> jnp.ndarray:
    """Single-head attention.  q [B,Tq,D], k/v [B,Tk,D], mask [B,Tk] or
    [B,Tq,Tk] (1 = attend)."""
    scale = 1.0 / math.sqrt(q.shape[-1]) if scaled else 1.0
    scores = jnp.einsum("bqd,bkd->bqk", q, k) * scale
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[:, None, :]
        scores = jnp.where(mask > 0, scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", weights, v)


def multi_head_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         n_heads: int,
                         mask: Optional[jnp.ndarray] = None,
                         kv_mask: Optional[jnp.ndarray] = None,
                         causal: bool = False,
                         use_flash: Optional[bool] = None,
                         flash_block: int = 0, dropout_rate: float = 0.0,
                         dropout_rng: Optional[jax.Array] = None,
                         n_kv_heads: Optional[int] = None
                         ) -> jnp.ndarray:
    """Multi-head attention on pre-projected q/k/v of shape [B,T,H*Dh].
    ``v`` may have a head size of its own ([B,T,H*Dv], latent attention's
    192-wide keys beside 128-wide values): the output is then [B,T,H*Dv].
    ``n_kv_heads`` (default ``n_heads``, a divisor of it) is grouped-query
    attention: ``k`` [B,T,Hkv*Dh] and ``v`` [B,T,Hkv*Dv], and query head
    ``h`` reads K/V head ``h // (n_heads / n_kv_heads)`` on both routes,
    with no K/V head repeated in memory.

    ``mask``: [B,T] padding mask applied to keys (and zeroing masked query
    outputs, matching DL4J's masked-attention semantics); ``kv_mask`` masks
    keys only (cross-attention).  ``causal`` adds the autoregressive mask.
    ``use_flash`` routes through the Pallas blockwise kernel (no [T,T]
    materialization, differentiable) — ``None`` (the default) auto-enables
    it for seq_len >= ``FLASH_AUTO_SEQ_LEN`` (1024), where the kernel is
    the measured winner; an explicit ``False`` always keeps the einsum
    chain.
    ``dropout_rate`` > 0 with a ``dropout_rng`` drops attention
    probabilities: one mask over [B,H,Tq,Tk] on ``softmax(scores)``.  The
    flash kernel cannot apply it, so ``use_flash=None`` then keeps the
    einsum chain at any length (at long sequences the [B,H,T,T]
    probabilities exist in memory) and ``use_flash=True`` raises.  With no
    key or rate 0 both routes compute what they do without the arguments.
    The mask is pinned to ``GENERATOR_LAYOUT`` so that the probabilities'
    chain is kept where the TPU's generator writes its bits and no
    transposition of ``uint32`` bits exists in the compiled step (PERF.md,
    PR 34); the draw itself, ``bernoulli(dropout_rng, 1 - rate,
    [B,H,Tq,Tk])``, is what the benchmark's reference draws and must not
    change.
    """
    b, tq, d = q.shape
    if n_kv_heads is not None and (n_kv_heads < 1 or n_heads % n_kv_heads):
        raise ValueError(f"{n_heads} query heads do not divide into groups "
                         f"over {n_kv_heads} K/V heads")
    drop = dropout_rate > 0.0 and dropout_rng is not None
    if drop and use_flash:
        raise ValueError(
            "use_flash=True cannot apply dropout_rate with dropout_rng: the "
            "flash kernel never holds the attention probabilities")
    if use_flash is None:
        use_flash = not drop and _auto_flash(q, k)
    key_mask = mask if mask is not None else kv_mask
    if use_flash:
        from deeplearning4j_tpu.ops.pallas import flash_attention
        # flash_block=0: tuned defaults (1024×1024 — the optimum measured
        # on a v5e before PR 1 at both narrow and BERT-base widths; the
        # earlier 512×1024 default was 1.35-1.5× slower)
        out = flash_attention(q, k, v, n_heads=n_heads,
                              n_kv_heads=n_kv_heads, causal=causal,
                              key_mask=key_mask,
                              block_q=flash_block or 1024,
                              block_k=flash_block or 1024)
        if mask is not None and tq == k.shape[1]:
            out = out * mask[:, :, None].astype(out.dtype)
        return out
    tk = k.shape[1]
    n_kv = n_kv_heads or n_heads
    group = n_heads // n_kv
    dh = d // n_heads
    qh = q.reshape(b, tq, n_heads, dh).transpose(0, 2, 1, 3)  # [B,H,Tq,Dh]
    kh = k.reshape(b, tk, n_kv, dh).transpose(0, 2, 1, 3)
    dv = v.shape[-1] // n_kv
    vh = v.reshape(b, tk, n_kv, dv).transpose(0, 2, 1, 3)
    if group == 1:
        scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) / math.sqrt(dh)
    else:                   # a group's query heads against their K/V head
        scores = jnp.einsum("bhgqd,bhkd->bhgqk",
                            qh.reshape(b, n_kv, group, tq, dh), kh
                            ).reshape(b, n_heads, tq, tk) / math.sqrt(dh)
    if key_mask is not None:
        scores = jnp.where(key_mask[:, None, None, :] > 0, scores, NEG_INF)
    if causal:
        cm = jnp.tril(jnp.ones((tq, tk), dtype=bool))
        scores = jnp.where(cm[None, None], scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    if drop:
        weights = dropout(weights, 1.0 - dropout_rate, dropout_rng,
                          mask_layout=GENERATOR_LAYOUT)
    if group == 1:
        out = jnp.einsum("bhqk,bhkd->bhqd", weights, vh)
    else:
        out = jnp.einsum("bhgqk,bhkd->bhgqd",
                         weights.reshape(b, n_kv, group, tq, tk), vh
                         ).reshape(b, n_heads, tq, dv)
    out = out.transpose(0, 2, 1, 3).reshape(b, tq, n_heads * dv)
    if mask is not None and tq == tk:
        out = out * mask[:, :, None]
    return out
