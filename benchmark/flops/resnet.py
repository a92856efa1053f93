"""Operations a ResNet training step needs per image, counted from shapes
(configurations whose ``flops`` is ``resnet``).

The benchmark's own arithmetic: nothing here reads XLA's cost analysis
(which cannot see inside a Mosaic custom call) or any program counter.
A multiply-add is two operations.  Only convolutions and the dense head
are counted: normalisation, activations and the optimizer are bandwidth
work and add under 1%.  Recomputed work never counts.

Figures this file gives (checked by ``tests/test_flops.py``): ResNet-50
v1 at 224x224, 1000 classes: 3.858 G multiply-adds forward (He et al.
2015, Table 1: "3.8 x 10^9"), so 7.72 GFLOP forward and 22.91 GFLOP per
image trained: three times the forward (the forward, the gradient by the
inputs, the gradient by the weights) less the first convolution's input
gradient, which nothing needs.
"""

from __future__ import annotations


def _conv_out(size: int, kernel: int, stride: int, pad: int) -> int:
    return (size + 2 * pad - kernel) // stride + 1


def forward_macs(model: dict) -> dict:
    """Multiply-adds of one image's forward pass, by part."""
    size = _conv_out(model["image"], 7, 2, 3)
    stem = size * size * 7 * 7 * model["channels"] * model["stem_width"]
    size = -(-size // 2)                       # 3x3 max pool, stride 2, SAME
    c_in = model["stem_width"]
    blocks = 0
    for stage, (n_blocks, (f1, f2, f3)) in enumerate(
            zip(model["blocks"], model["widths"])):
        for b in range(n_blocks):
            if b == 0 and stage > 0:           # v1: the first 1x1 strides
                size = -(-size // 2)
            px = size * size
            blocks += px * (c_in * f1 + 9 * f1 * f2 + f2 * f3)
            if b == 0:
                blocks += px * c_in * f3       # projection shortcut
            c_in = f3
    head = c_in * model["classes"]
    return {"stem": stem, "blocks": blocks, "head": head}


def per_unit(config: dict, mix: dict) -> float:
    """Operations per image trained."""
    macs = forward_macs(config["model"])
    forward = 2.0 * sum(macs.values())
    return 3.0 * forward - 2.0 * macs["stem"]
