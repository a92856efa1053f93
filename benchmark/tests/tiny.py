"""Tiny configurations and mixes for the CPU rehearsals: the same keys as
the real files, sizes a CPU holds.  Widths here are toys; nothing under
``tests/`` is ever timed."""

from __future__ import annotations

import copy
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for _path in (BENCH, ROOT):
    if _path not in sys.path:
        sys.path.insert(0, _path)


def _load(kind: str, name: str) -> dict:
    with open(os.path.join(BENCH, kind, f"{name}.json")) as f:
        return json.load(f)


def resnet50_unfused() -> tuple:
    config, mix = _load("configs", "resnet50_unfused"), _load("traffic",
                                                              "train_b128")
    config = copy.deepcopy(config)
    config["model"].update(image=32, classes=10)
    # at 32 pixels the last stage normalises over 8 values a channel and the
    # published rate makes the toy's loss explode (6.5, 40, 67): calm it
    config["optimizer"]["learning_rate"] = 0.002
    mix.update(batch=8, loss_every=2)
    return config, mix


def bert_base() -> tuple:
    """The twin keeps the file's published dropout rates, 0.1 for both:
    the program applies both since PR 32, and ``test_control.py`` counts
    the masks its step draws."""
    config, mix = _load("configs", "bert_base"), _load("traffic",
                                                       "mlm_s512_b32")
    config = copy.deepcopy(config)
    config["model"].update(vocab_size=1200, hidden_size=64,
                           num_hidden_layers=2, num_attention_heads=4,
                           intermediate_size=128,
                           max_position_embeddings=32)
    mix.update(batch=4, seq=32, max_predictions=5, units_per_row=32,
               loss_every=2)
    return config, mix


CELLS = {
    "resnet50_unfused.train_b128": resnet50_unfused,
    "bert_base.mlm_s512_b32": bert_base,
}

# The tiny cells' own limits, set as the real ones are: above what sound
# tiny runs read on the CPU (seeds 11-13), under what the float8 control
# and the planted faults read there.  They say nothing about the chip.
LIMITS = {
    "resnet50_unfused.train_b128": {"loss1_gap": 0.01, "loss2_gap": 0.01,
                                    "loss3_gap": 0.02, "grad_gap": 0.3,
                                    "grad_gap_median": 0.009,
                                    "delta_gap": 0.25,
                                    "delta_gap_median": 0.0075},
    "bert_base.mlm_s512_b32": {"loss1_gap": 1.5e-4, "loss2_gap": 1e-3,
                               "loss3_gap": 1e-3, "grad_gap": 0.02,
                               "grad_gap_median": 0.0025, "delta_gap": 0.08,
                               "delta_gap_median": 0.001},
}
