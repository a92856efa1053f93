"""Operations a JoyAI-LLM-Flash causal-LM training step needs per token,
counted from shapes (configurations whose ``flops`` is ``joyai_causal_lm``),
and the flash-attention kernels' own operations and bytes.

The benchmark's own arithmetic, as ``resnet.py`` says.  Only matrix
products are counted (norms, rotary positions, softmax, SiLU, routing's
sort and the optimizer are bandwidth work), a multiply-add as two
operations, the backward pass as twice the forward, recomputation not at
all.  Attention is causal: a token attends to half the sequence on
average.  A routed expert is counted for the pairs expected here:
``num_experts_per_tok * experts_held / n_routed_experts`` experts a token.

Figures this file gives (checked by ``tests/test_flops_joyai.py``), at
the cell's 8,192 tokens, 1 dense + 4 routed blocks + the MTP block, 16
of 256 experts held and 16,160 ids: the attention core 83.9 MFLOP a token
a block forward, a routed block 151.8, the dense block 224.7, the heads
and the MTP projection 149.2; 1.133 GFLOP a token forward, 27.84 TFLOP a
step; the flash kernels 12.37 TFLOP of it.
"""

from __future__ import annotations


def _attention_core(c: dict, seq: int) -> tuple:
    """(QK^T, AV) operations a token a block forward, causal."""
    heads = c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (2.0 * heads * qk * seq / 2.0,
            2.0 * heads * c["v_head_dim"] * seq / 2.0)


def forward_per_token(c: dict, seq: int) -> dict:
    """Forward operations a token, by part."""
    h, heads = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    projections = 2.0 * (
        h * c["q_lora_rank"] + c["q_lora_rank"] * heads * qk
        + h * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
        + c["kv_lora_rank"] * heads * (c["qk_nope_head_dim"]
                                       + c["v_head_dim"])
        + heads * c["v_head_dim"] * h)
    core = sum(_attention_core(c, seq))
    expert = 2.0 * 3 * h * c["moe_intermediate_size"]
    held = c.get("experts_held") or c["n_routed_experts"]
    routed = (projections + core + 2.0 * h * c["n_routed_experts"]
              + expert * c["n_shared_experts"]
              + expert * c["num_experts_per_tok"] * held
              / c["n_routed_experts"])
    dense = projections + core + 2.0 * 3 * h * c["intermediate_size"]
    mtp = c["num_nextn_predict_layers"]
    return {"attention_core": core, "routed_block": routed,
            "dense_block": dense,
            "heads": (1 + mtp) * 2.0 * h * c["vocab_size"]
            + mtp * 2.0 * 2 * h * h}


def per_step(c: dict, batch: int, seq: int) -> float:
    parts = forward_per_token(c, seq)
    n_dense = c["first_k_dense_replace"]
    n_routed = (c["num_hidden_layers"] - n_dense
                + c["num_nextn_predict_layers"])
    forward = (n_dense * parts["dense_block"]
               + n_routed * parts["routed_block"] + parts["heads"])
    return 3.0 * forward * batch * seq


def per_unit(config: dict, mix: dict) -> float:
    """Operations per token trained."""
    return per_step(config, mix["batch"], mix["seq"]) / (
        mix["batch"] * mix["seq"])


def flash_kernels(config: dict, mix: dict) -> dict:
    """{kernel name: (calls a step, needed operations a step, bytes a
    step)} of the flash-attention kernels in one training step.  Forward:
    QK^T and AV.  Backward: dV = P^T dO, dP = dO V^T, dQ = dS K, dK =
    dS^T Q (the scores' recomputation is not needed work).  The forward
    kernel runs twice a block (once more in the rematerialised backward),
    and its needed work is one run's.  Bytes: each operand read and each
    result written once, in the compute dtype."""
    c, seq, batch = config, mix["seq"], mix["batch"]
    blocks = c["num_hidden_layers"] + c["num_nextn_predict_layers"]
    qk_ops, av_ops = _attention_core(c, seq)
    tokens = batch * seq
    heads = c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    size = 2 if config["precision"]["compute"] == "bfloat16" else 4
    q_bytes = tokens * heads * qk * size
    v_bytes = tokens * heads * c["v_head_dim"] * size
    return {
        "tpudl_flash_fwd": (2 * blocks, blocks * tokens * (qk_ops + av_ops),
                            blocks * (2 * q_bytes + 2 * v_bytes)),
        "tpudl_flash_bwd_merged": (
            blocks, blocks * tokens * 2.0 * (qk_ops + av_ops),
            blocks * (4 * q_bytes + 4 * v_bytes)),
    }
