"""The tiny twins of the two cells PR 38 added, rehearsed on the CPU
through the real harness code (``harness.run_cell``), the comparison
held to what it has to catch on them (the control in the precision
below, a step that changes nothing, part of the targets left out), and
the figures ``flops/joyai_causal_lm.py`` states.  ``tiny.py`` holds the
older cells' twins; a later cell brings its own file, as the README
says."""

import copy
import json
import os
import time

import pytest

import harness
import position_fault
import readers
import readings
import tiny
from test_faults import _frozen_trainer_step, fresh_steps  # noqa: F401

joyai_flops = harness.load_module("flops", "joyai_causal_lm")

# The twins' own limits, set as the real ones are and as ``tiny.LIMITS``:
# above what the sound twins read on the CPU (seeds 11-13), under what the
# control and the planted faults read there.  They say nothing about the
# chip.  The BERT twin runs bfloat16 against float32, its control is
# float8, and ``delta_gap_median`` carries it (program at most 3.7e-4,
# control at least 2.6e-3; the worst leaf's ``delta_gap`` swings up to
# 0.023 with the seed and tells no control); half a batch reads
# ``grad_gap`` 0.37 and ``delta_gap`` 0.077 and up.  The JoyAI twin
# states float32, so both sides round alike (the program reads at most
# 2.0e-7 on a loss and 1.0e-6 elsewhere) and its nearest precision below
# is bfloat16, which reads at least 5.1e-6, 6.5e-6, 3.7e-3, 6.1e-4, 2.3e-3
# and 2.4e-4 in the order below; float8 and the faults read ten times
# that and more.
LIMITS = {
    "bert_base.mlm_s128_b128": {"loss1_gap": 1e-4, "grad_gap": 0.025,
                                "grad_gap_median": 0.004, "delta_gap": 0.06,
                                "delta_gap_median": 1e-3},
    "joyai_llm_flash.clm_s8192_b1": {"loss1_gap": 2e-6, "loss2_gap": 2e-6,
                                     "grad_gap": 5e-5,
                                     "grad_gap_median": 5e-6,
                                     "delta_gap": 5e-5,
                                     "delta_gap_median": 5e-6},
}
JOYAI = "joyai_llm_flash.clm_s8192_b1"


def bert_base_s128() -> tuple:
    """``bert_base.mlm_s128_b128`` with ``tiny.bert_base``'s toy widths:
    shorter rows and more of them than the s512 twin."""
    config, _ = tiny.bert_base()
    mix = tiny._load("traffic", "mlm_s128_b128")
    mix.update(batch=8, seq=16, max_predictions=3, units_per_row=16,
               loss_every=2)
    return config, mix


def joyai_llm_flash() -> tuple:
    """``joyai_llm_flash.clm_s8192_b1`` at toy widths (hidden 64, 4 heads
    at 16+8 / 16, lora ranks 32/16, 16 experts top-4 of width 32, 4 of
    them held from the 8th on, 256 ids, 1 dense + 2 routed blocks + MTP,
    64 tokens), float32 so that the CPU's comparison is tight."""
    config = copy.deepcopy(tiny._load("configs", "joyai_llm_flash"))
    mix = tiny._load("traffic", "clm_s8192_b1")
    config.update(hidden_size=64, num_attention_heads=4, q_lora_rank=32,
                  kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
                  v_head_dim=16, intermediate_size=128,
                  moe_intermediate_size=32, n_routed_experts=16,
                  experts_held=4, first_expert=8, num_experts_per_tok=4,
                  num_hidden_layers=3, vocab_size=256)
    config["model"]["vocab_size"] = 256
    config["precision"] = {"params": "float32", "compute": "float32",
                           "activations": "float32"}
    mix.update(batch=2, seq=64, units_per_row=64, loss_every=2)
    return config, mix


CELLS = {"bert_base.mlm_s128_b128": bert_base_s128,
         "joyai_llm_flash.clm_s8192_b1": joyai_llm_flash}


def _benchmark():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def run_tiny(name, tmp_path, *, trace=False):
    import jax
    bench = _benchmark()
    cell = {c["name"]: c for c in bench["workloads"]}[name]
    config, mix = CELLS[name]()
    return harness.run_cell(
        cell, 2 ** 31 + 4321, 2.0, trace, config=config, mix=mix,
        limits=LIMITS[name], metrics=harness.cell_metrics(bench, name, trace),
        devices=jax.devices()[:1], started=time.perf_counter(),
        out_dir=str(tmp_path / "bench_out"), device_prefix="/host:CPU")


@pytest.fixture
def cpu_peaks(monkeypatch):
    v5e = readers.peaks("TPU v5 lite")
    monkeypatch.setattr(readers, "peaks", lambda kind: v5e)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_end_to_end_line(name, tmp_path, fresh_steps):
    result = run_tiny(name, tmp_path)
    assert result["correct"] is True and result["failed"] == 0, \
        result["compared"]
    assert result["attempted"] == result["window"]["steps"] > 0
    assert result["window"]["recompiles"] == 0
    assert result["window"]["losses_read"] == result["window"]["steps"] // 2
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert set(result["compared"]) == set(LIMITS[name])
    json.loads(json.dumps(result))


# ---- what the comparison has to catch on the twins ---------------------------
@pytest.mark.parametrize("name", sorted(CELLS))
def test_control_fails_and_program_passes(name):
    """``test_control.py``'s case for these twins: the float8 control and
    half a batch (the JoyAI twin has two rows) in the reference put in
    the program's place."""
    config, mix = CELLS[name]()
    for seed in (11, 12, 13):
        row = readings.one_seed(config, mix, seed, control=True,
                                limits=LIMITS[name])
        assert row["verdict"]["program"]["correct"], (seed, row["program"])
        for who in ("control_fp8", "fault_half_batch"):
            assert not row["verdict"][who]["correct"], (seed, row[who])
            assert row["verdict"][who]["over"], (seed, row[who])


def _bfloat16_reference(config, mix, seed):
    """The twin states float32, so the nearest precision below it is
    bfloat16: the reference in bfloat16's rounding in the program's
    place."""
    import compare
    import traffic
    weight_seed, data_seed, model_seed = harness._seeds(seed, 3)
    reference = harness.load_module("reference", config["reference"])
    weights = reference.init_weights(config, weight_seed)
    arrays = traffic.make_batches(
        mix, config["model"], data_seed)[:int(mix["first_steps"])]
    wanted, got = (reference.first_steps(config, mix, weights, arrays,
                                         seed=model_seed, precision=p)
                   for p in ("f32", "bf16"))
    return compare.gaps(got, wanted)[0]


def _half_positions_reference(config, mix, seed):
    """``position_fault.py``'s reading: the reference scoring the first
    half of the positions only."""
    return position_fault.one_seed(config, mix, seed,
                                   limits={})["fault_half_positions"]


@pytest.mark.parametrize("plant", [_bfloat16_reference,
                                   _half_positions_reference])
def test_planted_in_the_reference_reads_not_correct(plant):
    config, mix = CELLS[JOYAI]()
    for seed in (11, 12, 13):
        verdict = readings.verdict(plant(config, mix, seed), LIMITS[JOYAI])
        assert not verdict["correct"] and verdict["over"], (seed, verdict)
        if plant is _half_positions_reference:
            # the gradients carry it, a hundred times over; a loss alone
            # need not (PERF.md section 6, PR 38)
            assert verdict["over"]["grad_gap"][0] > 0.1
            assert verdict["over"]["grad_gap_median"][0] > 0.1


def _half_positions_program(monkeypatch):
    """The output layer scores the first half of the sequence only, in
    both streams, and takes the mean over what it scored."""
    from deeplearning4j_tpu.nn.layers import decoder
    sound = decoder.CausalLMOutput.compute_score_array

    def broken(self, params, state, x, labels, **how):
        half = labels.shape[1] // 2
        return sound(self, params, state, x[:, :half], labels[:, :half],
                     **how)
    monkeypatch.setattr(decoder.CausalLMOutput, "compute_score_array",
                        broken)


@pytest.mark.parametrize("fault", [_frozen_trainer_step,
                                   _half_positions_program],
                         ids=["state_unchanged", "half_positions"])
def test_planted_in_the_program_reads_not_correct(fault, tmp_path,
                                                  monkeypatch, fresh_steps):
    """``test_faults.py``'s cases for the JoyAI twin, through the whole
    run; at batch 1 the partial fault is by position."""
    fault(monkeypatch)
    result = run_tiny(JOYAI, tmp_path)
    assert result["correct"] is False, result["compared"]
    over = {n for n, c in result["compared"].items()
            if c["value"] > c["limit"]}
    assert over, result["compared"]
    if fault is _frozen_trainer_step:
        # nothing moved: the change reads exactly 1 by the measure
        assert result["compared"]["delta_gap"]["value"] == pytest.approx(1.0)
    else:
        assert {"grad_gap", "grad_gap_median"} <= over


@pytest.mark.parametrize("name", sorted(CELLS))
def test_traced_line(name, tmp_path, cpu_peaks, fresh_steps):
    result = run_tiny(name, tmp_path, trace=True)
    bench = _benchmark()
    listed = {m["name"] for m in bench["per_layer"]
              if name in m.get("workloads", [name])}
    # the CPU's trace holds no Pallas call to read a roofline share from
    assert set(result["metrics"]) == listed - {
        "flash_attention_roofline_pct.tokens"}
    if name.startswith("joyai"):
        load = result["metrics"]["moe_load_max_over_mean.tokens"]["value"]
        assert 1.0 <= load <= 4.0          # 4 held experts: at most all
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]


def test_the_parent_program_gives_the_new_readers_nothing():
    """On a program without the counters and a summary without the
    kernels both readers return ``None`` and do not raise."""
    config, mix = joyai_llm_flash()
    obs = {"mix": mix, "config": config, "chips": 1,
           "device_kind": "TPU v5 lite",
           "window": {"seconds": 20.0, "steps": 40},
           "counters": {"before": {}, "after": {}},
           "trace": {"window_s": 4.0, "device_ops": [["fusion.1", 1.0]]}}
    for name in ("moe_load_max_over_mean.tokens",
                 "flash_attention_roofline_pct.tokens"):
        assert harness.load_module("metrics", name).read(obs) is None
        assert harness.load_module("metrics", name).read(
            dict(obs, trace=None)) is None


def test_flash_roofline_reader_by_hand():
    """A trace of 6.5 steps: a call site ran 7 times or 6.  All six sites
    of the backward kernel are listed (mean 6.5 runs of 40 ms); of the
    forward kernel's twelve the two listed are among the six that ran 7
    times (10 ms a call)."""
    config, mix = tiny._load("configs", "joyai_llm_flash"), tiny._load(
        "traffic", "clm_s8192_b1")
    fwd_ops, bwd_ops = 6 * 8192 * 83_886_080.0, 6 * 8192 * 167_772_160.0
    obs = {"mix": mix, "config": config, "chips": 1,
           "device_kind": "TPU v5 lite",
           "window": {"seconds": 20.0, "steps": 40},
           "trace": {"window_s": 3.25, "device_ops": [
               *([f"tpudl_flash_bwd_merged.{n}", 0.28] for n in (12, 13, 14)),
               *([f"tpudl_flash_bwd_merged.{n}", 0.24] for n in (15, 16, 17)),
               ["fusion.9 kLoop", 0.2], ["tpudl_flash_fwd.3", 0.07],
               ["tpudl_flash_fwd.8", 0.07]]}}
    spent = 12 * 0.010 + 6 * 0.040
    want = 100.0 * (fwd_ops + bwd_ops) / 197e12 / spent
    reader = harness.load_module("metrics",
                                 "flash_attention_roofline_pct.tokens")
    assert reader.read(obs) == pytest.approx(want, rel=1e-9)
    assert 0 < want < 100
    # a whole number of steps: every site ran as often
    assert reader._runs(2, 12, 8.0) == reader._runs(12, 12, 8.0) == 8.0
    # more sites listed than ran once more: the mean lies between
    assert reader._runs(9, 12, 6.5) == pytest.approx(6 + 6 / 9)


def test_joyai_figures():
    config, mix = tiny._load("configs", "joyai_llm_flash"), tiny._load(
        "traffic", "clm_s8192_b1")
    parts = joyai_flops.forward_per_token(config, mix["seq"])
    # QK^T over 192 and AV over 128, 32 heads, half of 8,192 keys
    assert parts["attention_core"] == 2 * 32 * (192 + 128) * 4096
    assert round(parts["attention_core"] / 1e6, 1) == 83.9
    attention = 2 * 26_345_472 + parts["attention_core"]   # 26.35 M weights
    expert = 2 * 3 * 2048 * 768
    assert parts["routed_block"] == attention + 2 * 2048 * 256 + expert \
        + expert * 8 * 16 / 256
    assert round(parts["routed_block"] / 1e6, 1) == 151.8
    assert parts["dense_block"] == attention + 2 * 3 * 2048 * 7168
    assert round(parts["dense_block"] / 1e6, 1) == 224.7
    assert round(parts["heads"] / 1e6, 1) == 149.2
    forward = parts["dense_block"] + 5 * parts["routed_block"] \
        + parts["heads"]
    assert round(forward / 1e9, 3) == 1.133
    step = joyai_flops.per_step(config, 1, 8192)
    assert step == 3 * forward * 8192
    assert round(step / 1e12, 2) == 27.84
    assert joyai_flops.per_unit(config, mix) == step / 8192
    kernels = joyai_flops.flash_kernels(config, mix)
    assert kernels["tpudl_flash_fwd"][0] == 12
    assert kernels["tpudl_flash_bwd_merged"][0] == 6
    total = sum(k[1] for k in kernels.values())
    assert total == 6 * 8192 * 3 * parts["attention_core"]
    assert round(total / 1e12, 2) == 12.37
    # compute-bound: the bytes' time is an eighth of the operations'
    assert sum(k[2] for k in kernels.values()) / 819e9 < 0.2 * total / 197e12


def test_the_file_holds_the_catalog_row_and_the_cut():
    config = tiny._load("configs", "joyai_llm_flash")
    published = {"hidden_size": 2048, "q_lora_rank": 1536,
                 "kv_lora_rank": 512, "num_attention_heads": 32,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "v_head_dim": 128, "intermediate_size": 7168,
                 "moe_intermediate_size": 768, "n_routed_experts": 256,
                 "num_experts_per_tok": 8, "routed_scaling_factor": 2.5,
                 "rope_theta": 32000000, "first_k_dense_replace": 1,
                 "n_shared_experts": 1, "num_nextn_predict_layers": 1}
    assert {k: config[k] for k in published} == published
    assert config["reduced"] == ["num_hidden_layers", "experts_held",
                                 "vocab_size"]
    held = config["model"]["held"]
    assert (config["num_hidden_layers"], config["experts_held"],
            config["first_expert"], config["vocab_size"]) == (
        held["num_hidden_layers"], held["experts_held"],
        held["first_expert"], held["vocab_size"]) == (5, 16, 0, 16160)
    assert config["model"]["vocab_size"] == config["vocab_size"]
    assert config["model"]["published"]["vocab_size"] == 8 * 16160
    bench = {c["name"]: c for c in _benchmark()["configs"]}
    assert bench["joyai_llm_flash"]["reduced"] == config["reduced"]
    reference = harness.load_module("reference", "joyai_llm_flash")
    shapes = reference.param_shapes(config)
    n = sum(int(__import__("math").prod(s)) for s in shapes.values())
    assert round(n / 1e6, 1) == 680.4
