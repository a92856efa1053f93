"""The tiny twin of the cell PR 40 added, ``kimi_linear_48b_a3b.clm_s8192_b1``,
rehearsed on the CPU through the real harness code (``harness.run_cell``),
the comparison held to what it has to catch on it (the precision below, a
step that changes nothing, part of the targets left out), and the figures
``flops/kimi_linear_causal_lm.py`` states.  A cell brings its own file, as
the README says; the faults and the helpers are ``test_cells_pr38.py``'s."""

import copy
import json
import math
import os
import time

import pytest

import harness
import readings
import tiny
from test_cells_pr38 import (_bfloat16_reference,  # noqa: F401
                             _half_positions_program,
                             _half_positions_reference, cpu_peaks)
from test_faults import _frozen_trainer_step, fresh_steps  # noqa: F401

kimi_flops = harness.load_module("flops", "kimi_linear_causal_lm")

KIMI = "kimi_linear_48b_a3b.clm_s8192_b1"
# The twin's own limits, set as ``test_cells_pr38.LIMITS`` are: above what
# the sound twin reads on the CPU (seeds 11-13), under what the control
# and the planted faults read there.  They say nothing about the chip.
# The twin states float32, so both sides round alike (the program reads at
# most 1.7e-7 on a loss, 9.7e-7 on ``grad_gap``, 6.1e-6 on ``delta_gap``
# and 7.3e-8 on the medians) and its nearest precision below is bfloat16,
# which reads at least 5.3e-6, 4.4e-6, 3.6e-3, 2.1e-4, 3.3e-3 and 4.8e-4
# in the order below; float8 and the faults read ten times that and more.
LIMITS = {"loss1_gap": 2e-6, "loss2_gap": 2e-6, "grad_gap": 5e-5,
          "grad_gap_median": 5e-6, "delta_gap": 5e-5,
          "delta_gap_median": 5e-6}


def kimi_linear() -> tuple:
    """The cell at toy widths (hidden 64; KDA 4 heads of 16 behind 4 taps;
    latent attention 4 heads at 16+8 / 16 over a rank of 16; 16 experts
    top-4 of width 32, 4 of them held from the 8th on; 256 ids; layers 1-5
    as in the cell; 128 tokens in chunks of 16), float32 so that the CPU's
    comparison is tight."""
    config = copy.deepcopy(tiny._load("configs", "kimi_linear_48b_a3b"))
    mix = tiny._load("traffic", "clm_s8192_b1")
    config.update(hidden_size=64, num_attention_heads=4, kv_lora_rank=16,
                  qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                  intermediate_size=128, moe_intermediate_size=32,
                  num_experts=16, experts_held=4, first_expert=8,
                  num_experts_per_token=4, vocab_size=256, kda_chunk=16)
    config["linear_attn_config"].update(num_heads=4, head_dim=16)
    config["model"]["vocab_size"] = 256
    config["precision"] = {"params": "float32", "compute": "float32",
                           "activations": "float32"}
    mix.update(batch=2, seq=128, units_per_row=128, loss_every=2)
    return config, mix


def _benchmark():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def run_tiny(tmp_path, *, trace=False):
    import jax
    bench = _benchmark()
    cell = {c["name"]: c for c in bench["workloads"]}[KIMI]
    config, mix = kimi_linear()
    return harness.run_cell(
        cell, 2 ** 31 + 4321, 2.0, trace, config=config, mix=mix,
        limits=LIMITS, metrics=harness.cell_metrics(bench, KIMI, trace),
        devices=jax.devices()[:1], started=time.perf_counter(),
        out_dir=str(tmp_path / "bench_out"), device_prefix="/host:CPU")


def test_end_to_end_line(tmp_path, fresh_steps):
    result = run_tiny(tmp_path)
    assert result["correct"] is True and result["failed"] == 0, \
        result["compared"]
    assert result["attempted"] == result["window"]["steps"] > 0
    assert result["window"]["recompiles"] == 0
    assert result["window"]["losses_read"] == result["window"]["steps"] // 2
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert set(result["compared"]) == set(LIMITS)
    json.loads(json.dumps(result))


def test_traced_line(tmp_path, cpu_peaks, fresh_steps):
    result = run_tiny(tmp_path, trace=True)
    listed = {m["name"] for m in _benchmark()["per_layer"]
              if KIMI in m.get("workloads", [KIMI])}
    # the flash kernels' roofline share does not list this cell: on the chip
    # all ten of its heads are the head groups' loops, and the reader found
    # nothing to read (PERF.md section 5)
    assert "flash_attention_roofline_pct.tokens" not in listed
    assert set(result["metrics"]) == listed
    load = result["metrics"]["moe_load_max_over_mean.tokens"]["value"]
    assert 1.0 <= load <= 4.0              # 4 held experts: at most all
    assert 0 < result["metrics"]["step_mfu_pct.tokens"]["value"] < 100
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]


# ---- what the comparison has to catch on the twin -----------------------------
def test_control_fails_and_program_passes():
    """The float8 control and half a batch (the twin has two rows) in the
    reference put in the program's place."""
    config, mix = kimi_linear()
    for seed in (11, 12, 13):
        row = readings.one_seed(config, mix, seed, control=True,
                                limits=LIMITS)
        assert row["verdict"]["program"]["correct"], (seed, row["program"])
        for who in ("control_fp8", "fault_half_batch"):
            assert not row["verdict"][who]["correct"], (seed, row[who])
            assert row["verdict"][who]["over"], (seed, row[who])


@pytest.mark.parametrize("plant", [_bfloat16_reference,
                                   _half_positions_reference])
def test_planted_in_the_reference_reads_not_correct(plant):
    config, mix = kimi_linear()
    for seed in (11, 12, 13):
        verdict = readings.verdict(plant(config, mix, seed), LIMITS)
        assert not verdict["correct"] and verdict["over"], (seed, verdict)
        if plant is _half_positions_reference:
            assert verdict["over"]["grad_gap"][0] > 0.1
            assert verdict["over"]["grad_gap_median"][0] > 0.1


@pytest.mark.parametrize("fault", [_frozen_trainer_step,
                                   _half_positions_program],
                         ids=["state_unchanged", "half_positions"])
def test_planted_in_the_program_reads_not_correct(fault, tmp_path,
                                                  monkeypatch, fresh_steps):
    fault(monkeypatch)
    result = run_tiny(tmp_path)
    assert result["correct"] is False, result["compared"]
    over = {n for n, c in result["compared"].items()
            if c["value"] > c["limit"]}
    assert over, result["compared"]
    if fault is _frozen_trainer_step:
        assert result["compared"]["delta_gap"]["value"] == pytest.approx(1.0)
    else:
        assert {"grad_gap", "grad_gap_median"} <= over


def test_the_parent_program_gives_the_readers_nothing():
    """On a program without the counters and a summary without the
    kernels the two readers that read this PR's program return ``None``
    and do not raise: the parent lacks the configuration, and a traced run
    of an older cell lacks nothing."""
    config, mix = kimi_linear()
    obs = {"mix": mix, "config": config, "chips": 1,
           "device_kind": "TPU v5 lite",
           "window": {"seconds": 20.0, "steps": 40},
           "counters": {"before": {}, "after": {}},
           "trace": {"window_s": 4.0, "device_ops": [["while.3", 1.0]]}}
    for name in ("moe_load_max_over_mean.tokens",
                 "flash_attention_roofline_pct.tokens"):
        assert harness.load_module("metrics", name).read(obs) is None


# ---- the figures the flops file states -----------------------------------------
def test_kimi_figures():
    config, mix = tiny._load("configs", "kimi_linear_48b_a3b"), tiny._load(
        "traffic", "clm_s8192_b1")
    parts = kimi_flops.forward_per_token(config, mix["seq"])
    # 39.46 M matrix weights a KDA layer (the taps, A_log, dt_bias and the
    # output norm's scale are no matrix products')
    kda_weights = (4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096)
                   + 2304 * 32)
    assert parts["kda_projections"] == 2 * kda_weights
    assert round(parts["kda_projections"] / 1e6, 1) == 78.9
    # per head and token at a chunk of 64: A, B, W, U~ and B U at 2 x 64 x
    # 128, W S, Q S and K^T U at 2 x 128 x 128
    assert kimi_flops.KDA_CHUNK == config["kda_chunk"] == 64
    assert parts["kda_core"] == 32 * (5 * 2 * 64 * 128 + 3 * 2 * 128 * 128)
    assert round(parts["kda_core"] / 1e6, 1) == 5.8
    assert parts["mla_projections"] == 2 * 29_114_368      # 29.11 M weights
    assert round(parts["mla_projections"] / 1e6, 1) == 58.2
    assert parts["mla_core"] == 2 * 32 * (192 + 128) * 4096
    assert round(parts["mla_core"] / 1e6, 1) == 83.9
    expert = 2 * 3 * 2304 * 1024
    assert parts["routed_ffn"] == 2 * 2304 * 256 + expert \
        + expert * 8 * 8 / 256
    dense = parts["kda"] + parts["dense_ffn"]
    kda_routed = parts["kda"] + parts["routed_ffn"]
    mla_routed = parts["mla"] + parts["routed_ffn"]
    assert [round(x / 1e6, 1) for x in (dense, kda_routed, mla_routed,
                                        parts["head"])] == [212.1, 103.6,
                                                            161.0, 94.4]
    forward = dense + 3 * kda_routed + mla_routed + parts["head"]
    assert round(forward / 1e6) == 778
    step = kimi_flops.per_step(config, 1, 8192)
    assert step == pytest.approx(3 * forward * 8192, rel=1e-12)
    assert round(step / 1e12, 1) == 19.1
    assert kimi_flops.per_unit(config, mix) == step / 8192
    kernels = kimi_flops.flash_kernels(config, mix)
    assert kernels["tpudl_flash_fwd"][0] == 1
    assert kernels["tpudl_flash_bwd_merged"][0] == 1
    total = sum(k[1] for k in kernels.values())
    assert total == 8192 * 3 * parts["mla_core"]
    assert round(total / 1e12, 2) == 2.06
    # compute-bound: the bytes' time is an eighth of the operations'
    assert sum(k[2] for k in kernels.values()) / 819e9 < 0.2 * total / 197e12


def test_the_file_holds_the_catalog_row_and_the_cut():
    config = tiny._load("configs", "kimi_linear_48b_a3b")
    published = {"hidden_size": 2304, "q_lora_rank": None,
                 "kv_lora_rank": 512, "num_attention_heads": 32,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "v_head_dim": 128, "intermediate_size": 9216,
                 "moe_intermediate_size": 1024, "num_experts": 256,
                 "num_experts_per_token": 8, "routed_scaling_factor": 2.446,
                 "first_k_dense_replace": 1, "num_shared_experts": 1,
                 "num_nextn_predict_layers": 0, "mla_use_nope": True,
                 "rms_norm_eps": 1e-05, "moe_renormalize": True,
                 "moe_router_activation_func": "sigmoid"}
    assert {k: config[k] for k in published} == published
    linear = config["linear_attn_config"]     # the published group, whole
    assert (linear["num_heads"], linear["head_dim"],
            linear["short_conv_kernel_size"]) == (32, 128, 4)
    assert len(linear["kda_layers"]) == 20
    assert linear["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert config["reduced"] == ["num_hidden_layers", "experts_held",
                                 "vocab_size"]
    held = config["model"]["held"]
    assert (config["num_hidden_layers"], config["experts_held"],
            config["first_expert"], config["vocab_size"]) == (
        held["num_hidden_layers"], held["experts_held"],
        held["first_expert"], held["vocab_size"]) == (5, 8, 0, 20480)
    assert config["model"]["vocab_size"] == config["vocab_size"]
    assert config["model"]["published"]["vocab_size"] == 8 * 20480
    bench = {c["name"]: c for c in _benchmark()["configs"]}
    assert bench["kimi_linear_48b_a3b"]["reduced"] == config["reduced"]
    reference = harness.load_module("reference", "kimi_linear")
    assert [kind for _, kind, _ in reference.block_names(config)] == [
        "kda", "kda", "kda", "mla", "kda"]
    assert (held["kda_layers"], held["full_attn_layers"]) == ([1, 2, 3, 5],
                                                              [4])
    by_kind = {}
    for name, shape in reference.param_shapes(config).items():
        block, _, leaf = name.partition(".")
        kind = ("attn" if leaf.startswith("attn.") else
                "ffn" if leaf.startswith("ffn.") else "rest")
        by_kind[block, kind] = by_kind.get((block, kind), 0) + math.prod(
            shape)
    assert round(by_kind["l1", "attn"] / 1e6, 2) == 39.51      # KDA
    assert round(by_kind["l4", "attn"] / 1e6, 2) == 29.11      # latent
    assert round(by_kind["l1", "ffn"] / 1e6, 2) == 63.70       # dense
    n = sum(by_kind.values())
    assert n == 602_433_408 and round(n * 16 / 1e9, 2) == 9.64
