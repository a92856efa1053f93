"""The ``.tokens`` readers: the tiny BERT twin is run through
``harness.run_cell`` with the cell's rate and its six ``.tokens`` metrics
by name, and each reader finds its series in ``BertForMaskedLM.fit``'s
loop."""

import time

import harness
import tiny
from test_harness_cpu import LOOSE, cpu_peaks  # noqa: F401  (a fixture)

CELL = "bert_base.mlm_s512_b32"
END_TO_END = {"train_tokens_per_s": "tokens/s", "setup_s": "s"}
PER_LAYER = {"device_idle_pct.tokens": "%", "step_mfu_pct.tokens": "%",
             "feed_wait_ms.tokens": "ms/step", "feed_busy_ms.tokens": "ms/step",
             "dispatch_ms.tokens": "ms/step", "host_self_ms.tokens": "ms/step"}


def _run(metrics, trace, tmp_path):
    import jax
    config, mix = tiny.bert_base()
    return harness.run_cell(
        {"name": CELL, "chips": 1}, 2**31 + 77, 2.0, trace, config=config,
        mix=mix, limits=LOOSE, metrics=metrics,
        devices=jax.devices()[:1], started=time.perf_counter(),
        out_dir=str(tmp_path) + "/bench_out", device_prefix="/host:CPU")


def test_rate_in_tokens(tmp_path):
    result = _run(END_TO_END, False, tmp_path)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == set(END_TO_END)
    window = result["window"]
    tokens = window["steps"] * 4 * 32           # the twin's batch x seq
    assert result["metrics"]["train_tokens_per_s"]["value"] == \
        tokens / window["seconds"]


def test_every_tokens_reader_finds_its_series(tmp_path, cpu_peaks):
    result = _run(PER_LAYER, True, tmp_path)
    assert set(result["metrics"]) == set(PER_LAYER)
    got = {name: m["value"] for name, m in result["metrics"].items()}
    assert all(v > 0 for v in got.values()), got
    assert got["step_mfu_pct.tokens"] < 100
    assert got["device_idle_pct.tokens"] < 100
    # the loop's wait for the feeder is inside the step's period, the
    # producer's busy time is not: only the first is bounded by it
    step_ms = 1e3 * result["window"]["seconds"] / result["window"]["steps"]
    for name in ("feed_wait_ms.tokens", "dispatch_ms.tokens",
                 "host_self_ms.tokens"):
        assert got[name] < step_ms, (name, got[name], step_ms)


def test_an_images_cell_reads_none_of_them():
    obs = {"mix": {"unit": "images"}, "window": {"steps": 3, "rate": 1.0},
           "counters": {"before": {}, "after": {}}, "trace": None}
    for name in list(PER_LAYER) + ["train_tokens_per_s"]:
        assert harness.load_module("metrics", name).read(obs) is None, name
