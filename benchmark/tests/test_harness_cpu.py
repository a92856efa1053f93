"""The whole command rehearsed on the CPU at a tiny size through the real
harness code: ``harness.run_cell`` is handed a tiny configuration and mix
(``tiny.py``); ``run.py`` gains no option for it."""

import json
import os
import time

import pytest

import harness
import readers
import tiny

LOOSE = {name: 10.0 for name in ("loss1_gap", "loss2_gap", "loss3_gap",
                                 "grad_gap", "delta_gap")}


def _benchmark():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def run_tiny(name, *, trace=False, limits=LOOSE, seed=2**31 + 12345,
             tmp_path=None):
    import jax
    bench = _benchmark()
    cell = {c["name"]: c for c in bench["workloads"]}[name]
    config, mix = tiny.CELLS[name]()
    return harness.run_cell(
        cell, seed, 2.0, trace, config=config, mix=mix, limits=limits,
        metrics=harness.cell_metrics(bench, name, trace),
        devices=jax.devices()[:1], started=time.perf_counter(),
        out_dir=str(tmp_path or "/tmp") + "/bench_out",
        device_prefix="/host:CPU")


@pytest.fixture
def cpu_peaks(monkeypatch):
    """The CPU has no row in peaks.json, and must not get one: the test
    lends it the v5e's so that the MFU reader has something to divide by."""
    v5e = readers.peaks("TPU v5 lite")
    monkeypatch.setattr(readers, "peaks", lambda kind: v5e)


@pytest.mark.parametrize("name", sorted(tiny.CELLS))
def test_end_to_end_line(name, tmp_path):
    result = run_tiny(name, tmp_path=tmp_path)
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == result["window"]["steps"] > 0
    assert result["window"]["recompiles"] == 0
    # the window's listener reads the loss at the mix's cadence, in both
    # entries, whatever the program's loop does with it
    loss_every = tiny.CELLS[name]()[1]["loss_every"]
    assert loss_every == 2          # a cadence of 1 would show nothing
    assert result["window"]["losses_read"] == \
        result["window"]["steps"] // loss_every
    assert type(result["window"]["last_loss"]) is float
    bench = _benchmark()
    want = {m["name"] for m in bench["end_to_end"]
            if name in m.get("workloads", [name])}
    assert set(result["metrics"]) == want and "setup_s" in want
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"]["memory_peak_bytes"] > 0
    assert set(result["compared"]) == set(LOOSE)
    json.loads(json.dumps(result))          # the line is plain JSON


@pytest.mark.parametrize("name", sorted(tiny.CELLS))
def test_traced_line(name, tmp_path, cpu_peaks):
    result = run_tiny(name, trace=True, tmp_path=tmp_path)
    bench = _benchmark()
    want = {m["name"] for m in bench["per_layer"]
            if name in m.get("workloads", [name])}
    assert set(result["metrics"]) == want
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
    assert 0 < len(result["breakdown"]["device_ops"]) <= 10
    assert len(result["breakdown"]["idle_gaps"]) <= 10
    assert not os.path.exists(str(tmp_path) + "/bench_out")   # trace removed


def test_every_cell_has_a_tiny_twin_here_or_in_a_later_file():
    # a later PR's cell brings its tiny twin in a test file of its own
    assert "resnet50_unfused.train_b128" in {
        c["name"] for c in _benchmark()["workloads"]} & set(tiny.CELLS)


def test_run_py_refuses_a_cpu(capsys):
    import run
    with pytest.raises(SystemExit) as stop:
        run.main(["--workload", "resnet50_unfused.train_b128", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert "needs a TPU" in str(stop.value.code)
    assert capsys.readouterr().out == ""     # no result line


def test_run_py_refuses_a_bare_checkout(monkeypatch, tmp_path):
    import run
    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    with pytest.raises(SystemExit) as stop:
        run.main(["--workload", "resnet50_unfused.train_b128", "--seed", "1",
                  "--seconds", "1"])
    assert "nothing to measure" in str(stop.value.code)
