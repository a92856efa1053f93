"""Operations a Kimi-Linear causal-LM training step needs per token,
counted from shapes (configurations whose ``flops`` is
``kimi_linear_causal_lm``), and the flash-attention kernels' own
operations and bytes.

The benchmark's own arithmetic, as ``resnet.py`` says.  Only matrix
products are counted (norms, the short convolutions, the decays'
exponentials, the inversion inside a chunk, softmax, SiLU, routing's sort
and the optimizer are bandwidth or vector work), a multiply-add as two
operations, the backward pass as twice the forward, recomputation not at
all.  Latent attention is causal: a token attends to half the sequence on
average.  A routed expert is counted for the pairs expected here:
``num_experts_per_token * experts_held / num_experts`` experts a token.

Kimi Delta Attention's core is counted in its chunked form at a chunk of
``KDA_CHUNK`` = 64 tokens, whatever chunk the program runs (a longer
chunk does more work inside a chunk and less between chunks: the count
is the yardstick, not the program's): per chunk and head the four
``[C, C]`` products ``A = K K^T``, ``B = Q K^T``, ``W = T (beta K e^G)``
and ``U~ = T (beta V)`` (whole squares: the masked half is computed too),
the read ``B U``, and the three products with the ``[d_k, d_v]`` state,
``W S``, ``Q S`` and ``K^T U``.

Figures this file gives (checked by ``tests/test_cell_pr40.py``), at the
cell's 8,192 tokens, layers 1-5 (KDA with the dense feed-forward, KDA,
KDA, latent, KDA, the last four routed), 8 of 256 experts held and 20,480
ids: a KDA layer's projections 78.9 MFLOP a token forward and its core
5.8; latent attention's projections 58.2 and its core 83.9; the dense
block 212.1, a KDA routed block 103.6, the latent routed block 161.0, the
head 94.4; 778 MFLOP a token forward, 19.1 TFLOP a step; the flash
kernels 2.06 TFLOP of it.
"""

from __future__ import annotations

KDA_CHUNK = 64


def _attention_core(c: dict, seq: int) -> tuple:
    """(QK^T, AV) operations a token a latent block forward, causal."""
    heads = c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (2.0 * heads * qk * seq / 2.0,
            2.0 * heads * c["v_head_dim"] * seq / 2.0)


def _kinds(c: dict) -> list:
    """The attention kind of layers 1 .. ``num_hidden_layers``."""
    linear = c["linear_attn_config"]
    return ["kda" if n in linear["kda_layers"] else "mla"
            for n in range(1, c["num_hidden_layers"] + 1)]


def forward_per_token(c: dict, seq: int) -> dict:
    """Forward operations a token, by part."""
    h, linear = c["hidden_size"], c["linear_attn_config"]
    heads, d = linear["num_heads"], linear["head_dim"]
    p, rank = heads * d, d
    kda_projections = 2.0 * (3 * h * p + p * h + 2 * (h * rank + rank * p)
                             + h * heads)
    kda_core = heads * (5 * 2.0 * KDA_CHUNK * d + 3 * 2.0 * d * d)
    mla_heads = c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    mla_projections = 2.0 * (
        h * mla_heads * qk + h * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
        + c["kv_lora_rank"] * mla_heads * (c["qk_nope_head_dim"]
                                           + c["v_head_dim"])
        + mla_heads * c["v_head_dim"] * h)
    mla_core = sum(_attention_core(c, seq))
    expert = 2.0 * 3 * h * c["moe_intermediate_size"]
    held = c.get("experts_held") or c["num_experts"]
    routed = (2.0 * h * c["num_experts"] + expert * c["num_shared_experts"]
              + expert * c["num_experts_per_token"] * held
              / c["num_experts"])
    return {"kda_projections": kda_projections, "kda_core": kda_core,
            "mla_projections": mla_projections, "mla_core": mla_core,
            "kda": kda_projections + kda_core,
            "mla": mla_projections + mla_core,
            "routed_ffn": routed,
            "dense_ffn": 2.0 * 3 * h * c["intermediate_size"],
            "head": 2.0 * h * c["vocab_size"]}


def per_step(c: dict, batch: int, seq: int) -> float:
    parts = forward_per_token(c, seq)
    forward = parts["head"]
    for n, kind in enumerate(_kinds(c), 1):
        forward += parts[kind] + parts[
            "routed_ffn" if n > c["first_k_dense_replace"] else "dense_ffn"]
    return 3.0 * forward * batch * seq


def per_unit(config: dict, mix: dict) -> float:
    """Operations per token trained."""
    return per_step(config, mix["batch"], mix["seq"]) / (
        mix["batch"] * mix["seq"])


def flash_kernels(config: dict, mix: dict) -> dict:
    """{kernel name: (calls a step, needed operations a step, bytes a
    step)} of the flash-attention kernels in one training step: the
    latent blocks' alone (KDA calls no kernel of this repo's).  Forward:
    QK^T and AV.  Backward: dV = P^T dO, dP = dO V^T, dQ = dS K, dK =
    dS^T Q (the scores' recomputation is not needed work).  Each kernel
    runs once a latent block: a rematerialised run keeps the forward's
    output and row statistics (PR 39).  Bytes: each operand read and each
    result written once, in the compute dtype."""
    c, seq, batch = config, mix["seq"], mix["batch"]
    blocks = _kinds(c).count("mla")
    qk_ops, av_ops = _attention_core(c, seq)
    tokens = batch * seq
    heads = c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    size = 2 if config["precision"]["compute"] == "bfloat16" else 4
    q_bytes = tokens * heads * qk * size
    v_bytes = tokens * heads * c["v_head_dim"] * size
    return {
        "tpudl_flash_fwd": (blocks, blocks * tokens * (qk_ops + av_ops),
                            blocks * (2 * q_bytes + 2 * v_bytes)),
        "tpudl_flash_bwd_merged": (
            blocks, blocks * tokens * 2.0 * (qk_ops + av_ops),
            blocks * (4 * q_bytes + 4 * v_bytes)),
    }
