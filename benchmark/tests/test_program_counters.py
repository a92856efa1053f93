"""The three readers of the program's own histograms (``feed_busy_ms``,
``dispatch_ms``, ``host_self_ms``) rehearsed on the CPU through
``harness.run_cell`` at ``tiny.py``'s sizes.  Nothing here is a speed."""

import math

import harness
import program_counters
from test_harness_cpu import _benchmark, cpu_peaks, run_tiny  # noqa: F401

NEW = ("feed_busy_ms.images", "dispatch_ms.images", "host_self_ms.images")


def test_benchmark_json_names_the_three_for_the_image_cell():
    entries = {m["name"]: m for m in _benchmark()["per_layer"]}
    layers = {"feed_busy_ms.images": "device feed",
              "dispatch_ms.images": "jit step",
              "host_self_ms.images": "entry points"}
    for name in NEW:
        assert entries[name] == {
            "name": name, "unit": "ms/step", "better": "lower",
            "source": "program_counter", "layer": layers[name],
            "moves": "train_images_per_s",
            "workloads": ["resnet50_unfused.train_b128"]}


def test_image_cell_reports_the_three(tmp_path, cpu_peaks):
    result = run_tiny("resnet50_unfused.train_b128", trace=True,
                      tmp_path=tmp_path)
    got = result["metrics"]
    for name in NEW:
        assert got[name]["unit"] == "ms/step"
        assert math.isfinite(got[name]["value"]) and got[name]["value"] >= 0
    # the producer stages a batch and the loop enqueues a step: both take
    # some time on any machine
    assert got["feed_busy_ms.images"]["value"] > 0
    assert got["dispatch_ms.images"]["value"] > 0
    assert result["correct"] is True and result["window"]["recompiles"] == 0


def test_tokens_mix_reports_none_of_them(tmp_path, cpu_peaks):
    import jax
    import time
    import tiny
    name = "bert_base.mlm_s512_b32"
    config, mix = tiny.CELLS[name]()
    asked = {**harness.cell_metrics(_benchmark(), name, True),
             "feed_wait_ms.tokens": "ms/step",
             **{metric: "ms/step" for metric in NEW}}
    result = harness.run_cell(
        {"name": name, "chips": 1}, 2**31 + 7, 2.0, True, config=config,
        mix=mix, limits={"loss1_gap": 10.0}, metrics=asked,
        devices=jax.devices()[:1], started=time.perf_counter(),
        out_dir=str(tmp_path) + "/bench_out", device_prefix="/host:CPU")
    assert "feed_wait_ms.tokens" in result["metrics"]    # the run had steps
    assert not set(NEW) & set(result["metrics"])


def _obs(before, after, steps=4, unit="images"):
    return {"mix": {"unit": unit}, "window": {"steps": steps},
            "counters": {"before": before, "after": after}}


def test_per_step_ms_is_the_growth_of_the_sums_over_the_steps():
    before = {"a": (1.0, 10), "b": (0.5, 10)}
    after = {"a": (1.4, 14), "b": (0.6, 14), "c": (0.2, 4)}
    obs = _obs(before, after)
    assert math.isclose(program_counters.per_step_ms(obs, "images", ("a",)),
                        1e3 * 0.4 / 4)
    # a series that did not exist before the window grew from nothing
    assert math.isclose(
        program_counters.per_step_ms(obs, "images", ("a", "c")),
        1e3 * (0.4 + 0.2) / 4)
    got = program_counters.per_step_ms(obs, "images", ("a",), ("b", "c"))
    assert math.isclose(got, 1e3 * (0.4 - 0.1 - 0.2) / 4)


def test_a_program_without_the_series_reads_none():
    # the parent commit keeps none of them: no value, no error
    obs = _obs({}, {"tpudl_data_etl_wait_seconds": (0.1, 4)})
    for name in NEW:
        assert harness.load_module("metrics", name).read(obs) is None
    assert program_counters.per_step_ms(
        _obs({}, {"a": (1.0, 1)}, steps=0), "images", ("a",)) is None
    assert program_counters.per_step_ms(
        _obs({}, {"a": (1.0, 1)}, unit="tokens"), "images", ("a",)) is None
