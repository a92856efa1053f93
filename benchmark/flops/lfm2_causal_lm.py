"""Operations an LFM2-MoE causal-LM training step needs per token, counted
from shapes (configurations whose ``flops`` is ``lfm2_causal_lm``), and
the flash-attention kernels' own operations and bytes.

The benchmark's own arithmetic, as ``resnet.py`` says.  Only matrix
products are counted (norms, the convolutions' gates and taps, rotary
positions, softmax, SiLU, routing's sort and the optimizer are bandwidth
or vector work), a multiply-add as two operations, the backward pass as
twice the forward, recomputation not at all.  Attention is causal: a
token attends to half the sequence on average; every one of the
``num_attention_heads`` query heads does its own products, whatever K/V
head it reads.  A routed expert is counted for the pairs expected here:
``num_experts_per_tok * experts_held / num_experts`` experts a token.
The head is tied to the embedding, and its product is counted once.

Figures this file gives (checked by ``tests/test_cell_pr45.py``), at the
cell's 2 x 8,192 tokens, layers 1-5 (conv with the dense feed-forward,
attention, conv, conv, conv, the last four routed), 8 of 32 experts held
and 16,384 ids: a convolution mixer's projections 33.6 MFLOP a token
forward; attention's projections 21.0 and its core 33.6; the dense block
121.6, the attention block 76.7, a convolution routed block 55.7, the
head 67.1; 432.5 MFLOP a token forward, 21.3 TFLOP a step; the flash
kernels 1.65 TFLOP of it.
"""

from __future__ import annotations


def _kinds(c: dict) -> list:
    """The mixer kind of the layers held."""
    first = c.get("first_layer", 0)
    return c["layer_types"][first:first + c["num_hidden_layers"]]


def _attention_core(c: dict, seq: int) -> tuple:
    """(QK^T, AV) operations a token an attention block forward, causal."""
    heads = c["num_attention_heads"]
    d = c["hidden_size"] // heads
    return 2.0 * heads * d * seq / 2.0, 2.0 * heads * d * seq / 2.0


def forward_per_token(c: dict, seq: int) -> dict:
    """Forward operations a token, by part."""
    h, heads = c["hidden_size"], c["num_attention_heads"]
    kv = c["num_key_value_heads"] * (h // heads)
    expert = 2.0 * 3 * h * c["moe_intermediate_size"]
    held = c.get("experts_held") or c["num_experts"]
    parts = {"conv": 2.0 * (h * 3 * h + h * h),
             "attention_projections": 2.0 * (2 * h * h + 2 * h * kv),
             "attention_core": sum(_attention_core(c, seq)),
             "routed_ffn": (2.0 * h * c["num_experts"]
                            + expert * c["num_experts_per_tok"] * held
                            / c["num_experts"]),
             "dense_ffn": 2.0 * 3 * h * c["intermediate_size"],
             "head": 2.0 * h * c["vocab_size"]}
    parts["full_attention"] = (parts["attention_projections"]
                               + parts["attention_core"])
    return parts


def per_step(c: dict, batch: int, seq: int) -> float:
    parts = forward_per_token(c, seq)
    forward = parts["head"]
    for n, kind in enumerate(_kinds(c)):
        forward += parts[kind] + parts[
            "routed_ffn" if n >= c["num_dense_layers"] else "dense_ffn"]
    return 3.0 * forward * batch * seq


def per_unit(config: dict, mix: dict) -> float:
    """Operations per token trained."""
    return per_step(config, mix["batch"], mix["seq"]) / (
        mix["batch"] * mix["seq"])


def flash_kernels(config: dict, mix: dict) -> dict:
    """{kernel name: (calls a step, needed operations a step, bytes a
    step)} of the flash-attention kernels in one training step: the
    attention blocks' alone.  Forward: QK^T and AV of every query head.
    Backward: dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q (the
    scores' recomputation is not needed work).  Each kernel runs once an
    attention block: a rematerialised run keeps the forward's output and
    row statistics.  Bytes: each operand read and each result
    written once, in the compute dtype; K, V, dK and dV are of the
    ``num_key_value_heads`` heads alone."""
    c, seq, batch = config, mix["seq"], mix["batch"]
    blocks = _kinds(c).count("full_attention")
    qk_ops, av_ops = _attention_core(c, seq)
    tokens = batch * seq
    d = c["hidden_size"] // c["num_attention_heads"]
    size = 2 if config["precision"]["compute"] == "bfloat16" else 4
    q_bytes = tokens * c["num_attention_heads"] * d * size
    kv_bytes = tokens * c["num_key_value_heads"] * d * size
    return {
        "tpudl_flash_fwd": (blocks, blocks * tokens * (qk_ops + av_ops),
                            blocks * (2 * q_bytes + 2 * kv_bytes)),
        "tpudl_flash_bwd_merged": (
            blocks, blocks * tokens * 2.0 * (qk_ops + av_ops),
            blocks * (4 * q_bytes + 4 * kv_bytes)),
    }
