"""Operations a BERT masked-LM training step needs per token, counted
from shapes (configurations whose ``flops`` is ``bert_mlm``).

The benchmark's own arithmetic, as ``resnet.py`` says.  Only matrix
multiplications are counted: layer norms, softmax, GELU, dropout and the
optimizer are bandwidth work and add under 1%.

Figures this file gives (checked by ``tests/test_flops.py``): BERT-base
at 512 tokens, 76 decoded positions, batch 32: 9.628 TFLOP a step, 587.6
MFLOP per token.
"""

from __future__ import annotations


def per_step(model: dict, batch: int, seq: int, decoded: int) -> float:
    """``decoded``: positions per sequence that reach the vocabulary
    matmul (google-research/bert's max_predictions_per_seq)."""
    h, i = model["hidden_size"], model["intermediate_size"]
    layers, vocab = model["num_hidden_layers"], model["vocab_size"]
    tokens = batch * seq
    per_token_layer = 4 * h * h + 2 * h * i            # QKV, output, FFN
    encoder = 6.0 * layers * per_token_layer * tokens
    # QK^T and AV: seq x hidden multiply-adds each, per token and layer
    attention = 3.0 * 2.0 * 2.0 * layers * tokens * seq * h
    head = 6.0 * (h * h + h * vocab) * batch * decoded
    return encoder + attention + head


def per_unit(config: dict, mix: dict) -> float:
    """Operations per token trained."""
    step = per_step(config["model"], mix["batch"], mix["seq"],
                    mix["max_predictions"])
    return step / (mix["batch"] * mix["seq"])
