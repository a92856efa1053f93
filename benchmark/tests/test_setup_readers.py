"""The five readers of set-up's split (layer 'compile reuse'): on a line of
a program that keeps no such series they read ``None``; on one that does,
the series' sum at the window's start; and the tiny twins run through
``harness.run_cell`` with tracing report all five from the program's own
histograms."""

import harness
from test_harness_cpu import cpu_peaks, run_tiny  # noqa: F401  (a fixture)

from deeplearning4j_tpu.obs.registry import MetricsRegistry, set_registry

READERS = {"setup_trace_s": "tpudl_compile_trace_seconds",
           "setup_lower_s": "tpudl_compile_lower_seconds",
           "setup_xla_s": "tpudl_compile_xla_seconds",
           "setup_cache_load_s": "tpudl_compile_cache_load_seconds",
           "setup_analysis_s": "tpudl_perf_analysis_seconds"}
COMPILES = ("setup_trace_s", "setup_lower_s", "setup_xla_s",
            "setup_cache_load_s")


def _obs(before: dict, after: dict) -> dict:
    return {"mix": {"unit": "tokens"}, "window": {"steps": 3, "rate": 1.0},
            "setup": {"seconds": 30.0, "cache_misses": 0, "cache_hits": 2},
            "counters": {"before": before, "after": after}, "trace": None}


def test_a_program_without_the_series_reads_none():
    obs = _obs({"tpudl_train_steps_total": 3.0},
               {"tpudl_train_steps_total": 9.0})
    for name in READERS:
        assert harness.load_module("metrics", name).read(obs) is None, name


def test_each_reads_its_sum_at_the_windows_start():
    before = {series: (1.5 + i, 2) for i, series in
              enumerate(READERS.values())}
    # what the window adds is not set-up's
    after = {series: (100.0 + s, c + 5) for series, (s, c) in before.items()}
    obs = _obs(before, after)
    for i, name in enumerate(READERS):
        assert harness.load_module("metrics", name).read(obs) == 1.5 + i


def test_both_tiny_twins_report_all_five(tmp_path, cpu_peaks):
    entries = {m["name"]: m for m in harness.load_json(
        "..", "BENCHMARK.json")["per_layer"]}
    for name in READERS:
        assert entries[name] == {
            "name": name, "unit": "s", "better": "lower",
            "source": "program_counter", "layer": "compile reuse",
            "moves": "setup_s"}
    for cell in ("resnet50_unfused.train_b128", "bert_base.mlm_s512_b32"):
        # a registry of the run's own, as a process of its own would have
        prev = set_registry(MetricsRegistry())
        try:
            result = run_tiny(cell, trace=True, tmp_path=tmp_path)
        finally:
            set_registry(prev)
        got = {name: result["metrics"][name]["value"] for name in READERS}
        assert all(result["metrics"][name]["unit"] == "s" for name in got)
        assert got["setup_trace_s"] > 0 and got["setup_lower_s"] > 0
        assert got["setup_xla_s"] > 0
        # the compiles happen inside set-up, after the imports
        window = result["window"]
        assert sum(got[name] for name in COMPILES) <= (
            window["setup_s"] - window["setup_phases"]["imports"])
        # only Trainer's loop schedules a cost-model analysis
        if cell.startswith("resnet50"):
            assert got["setup_analysis_s"] > 0
        else:
            assert got["setup_analysis_s"] == 0
