"""``flops/<kind>.py`` against the figures they state, and ``peaks.json``."""

import json
import os

import pytest

import harness
import readers

resnet = harness.load_module("flops", "resnet")
bert_mlm = harness.load_module("flops", "bert_mlm")

HERE = os.path.dirname(os.path.abspath(__file__))


def _config(name):
    with open(os.path.join(HERE, "..", "configs", f"{name}.json")) as f:
        return json.load(f)


def _mix(name):
    with open(os.path.join(HERE, "..", "traffic", f"{name}.json")) as f:
        return json.load(f)


def test_resnet50_matches_the_paper():
    config = _config("resnet50_unfused")
    macs = resnet.forward_macs(config["model"])
    total = sum(macs.values())
    assert total == 3_857_973_248
    # He et al. 2015, Table 1: 3.8e9 multiply-adds for the 50-layer net
    assert abs(total - 3.8e9) / 3.8e9 < 0.02
    per_image = resnet.per_unit(config, _mix("train_b128"))
    assert per_image == 3 * 2 * total - 2 * macs["stem"]
    assert round(per_image / 1e9, 2) == 22.91


def test_resnet50_first_block_by_hand():
    # res2_0 at 56x56: 64->64 1x1, 64->64 3x3, 64->256 1x1, 64->256 shortcut
    model = dict(_config("resnet50_unfused")["model"], blocks=[1],
                 widths=[[64, 64, 256]])
    px = 56 * 56
    assert resnet.forward_macs(model)["blocks"] == px * (
        64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)


def test_bert_base_step():
    config, mix = _config("bert_base"), _mix("mlm_s512_b32")
    step = bert_mlm.per_step(config["model"], 32, 512, 76)
    # 6 x 84.9 M encoder matmul parameters x 16,384 tokens
    #   + 12 x 12 layers x 32 x 512^2 x 768 for attention
    #   + 6 x 24.03 M head parameters x 2,432 decoded positions
    by_hand = (6 * 84_934_656 * 16_384 + 12 * 12 * 32 * 512 ** 2 * 768
               + 6 * (768 * 768 + 768 * 30522) * 2432)
    assert step == by_hand
    assert round(step / 1e12, 3) == 9.628
    per_token = bert_mlm.per_unit(config, mix)
    assert per_token == step / (32 * 512)
    assert round(per_token / 1e6, 1) == 587.6


def test_peaks_know_the_v5e_and_nothing_else():
    v5e = readers.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    for unknown in ("cpu", "TPU v4", "_source"):
        with pytest.raises(KeyError):
            readers.peaks(unknown)
