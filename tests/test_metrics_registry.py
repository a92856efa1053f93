"""Metrics registry tests: metric semantics, the tpudl_<area>_<name>
convention, Prometheus text rendering, the /metrics endpoint, and the
``obs.selfcheck`` metric lint (plus its deprecated ``obs.check``
shim entry point)."""

import json
import math
import subprocess
import sys
import urllib.request

import pytest

from deeplearning4j_tpu.obs import registry as reg_mod
from deeplearning4j_tpu.obs.registry import (
    METRIC_NAME_RE, Counter, Gauge, Histogram, MetricsRegistry,
    install_standard_metrics)


@pytest.fixture
def registry():
    return MetricsRegistry()


def test_counter_gauge_semantics(registry):
    c = registry.counter("tpudl_test_things_total", "help text")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = registry.gauge("tpudl_test_level")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value == 3


def test_histogram_buckets_cumulative(registry):
    h = registry.histogram("tpudl_test_latency_seconds",
                           buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5, 5.0):
        h.observe(v)
    counts = h.bucket_counts()
    assert counts[0.01] == 1
    assert counts[0.1] == 3          # cumulative
    assert counts[1.0] == 4
    assert counts[math.inf] == 5
    assert h.count == 5
    assert abs(h.sum - 5.605) < 1e-9


def test_name_convention_enforced(registry):
    for bad in ("train_steps_total", "tpudl_steps", "tpudl_Train_x",
                "tpudl_train_", "notaprefix_train_steps_total"):
        with pytest.raises(ValueError):
            registry.counter(bad)
    assert METRIC_NAME_RE.match("tpudl_train_steps_total")


def test_reregistration_idempotent_but_type_safe(registry):
    a = registry.counter("tpudl_test_things_total")
    b = registry.counter("tpudl_test_things_total")
    assert a is b
    with pytest.raises(ValueError):
        registry.gauge("tpudl_test_things_total")


def test_prometheus_text_format(registry):
    c = registry.counter("tpudl_test_things_total", "things\nprocessed")
    c.inc(7)
    h = registry.histogram("tpudl_test_latency_seconds", "latency",
                           buckets=(0.5,))
    h.observe(0.25)
    text = registry.render_prometheus()
    lines = text.splitlines()
    assert "# HELP tpudl_test_latency_seconds latency" in lines
    assert "# TYPE tpudl_test_latency_seconds histogram" in lines
    assert "# TYPE tpudl_test_things_total counter" in lines
    # newlines in help are escaped per the exposition format
    assert "# HELP tpudl_test_things_total things\\nprocessed" in lines
    assert "tpudl_test_things_total 7" in lines
    assert 'tpudl_test_latency_seconds_bucket{le="0.5"} 1' in lines
    assert 'tpudl_test_latency_seconds_bucket{le="+Inf"} 1' in lines
    assert "tpudl_test_latency_seconds_sum 0.25" in lines
    assert "tpudl_test_latency_seconds_count 1" in lines
    assert text.endswith("\n")


def test_labeled_counter_and_gauge_semantics(registry):
    c = registry.labeled_counter("tpudl_test_requests_total", "requests",
                                 ("status",))
    c.inc(status="ok")
    c.inc(2, status="ok")
    c.inc(status="shed")
    assert c.labeled_value(status="ok") == 3
    assert c.labeled_value(status="shed") == 1
    assert c.value == 4                      # total across labels
    with pytest.raises(ValueError):
        c.inc(-1, status="ok")
    with pytest.raises(ValueError):
        c.inc(status="ok", bogus="x")        # undeclared label name
    with pytest.raises(ValueError):
        c.inc()                              # missing declared label
    g = registry.labeled_gauge("tpudl_test_version", "per-model version",
                               ("model",))
    g.set(3, model="a")
    g.set(7, model="b")
    assert g.labeled_value(model="a") == 3
    assert g.labeled_value(model="b") == 7
    # idempotent re-registration; label mismatch is a hard error
    assert registry.labeled_counter("tpudl_test_requests_total") is c
    with pytest.raises(ValueError):
        registry.labeled_counter("tpudl_test_requests_total",
                                 label_names=("other",))


def test_labeled_metrics_prometheus_render(registry):
    c = registry.labeled_counter("tpudl_test_requests_total", "reqs",
                                 ("status",))
    c.inc(5, status="ok")
    c.inc(status='we"ird\nvalue')
    text = registry.render_prometheus()
    lines = text.splitlines()
    assert "# TYPE tpudl_test_requests_total counter" in lines
    assert 'tpudl_test_requests_total{status="ok"} 5' in lines
    # label values escaped per the exposition format
    assert 'tpudl_test_requests_total{status="we\\"ird\\nvalue"} 1' in lines


def test_labeled_histogram_semantics(registry):
    h = registry.labeled_histogram("tpudl_test_prog_seconds", "per-program",
                                   buckets=(0.01, 0.1, 1.0),
                                   label_names=("program",))
    for v in (0.005, 0.05, 0.5):
        h.observe(v, program="train")
    h.observe(5.0, program="serve")
    assert h.labeled_count(program="train") == 3
    assert h.labeled_count(program="serve") == 1
    assert h.count == 4                       # aggregate across children
    assert abs(h.sum - 5.555) < 1e-9
    counts = h.bucket_counts(program="train")
    assert counts[0.01] == 1
    assert counts[0.1] == 2                   # cumulative
    assert counts[math.inf] == 3
    assert h.bucket_counts(program="serve")[1.0] == 0
    with pytest.raises(ValueError):
        h.observe(1.0)                        # missing declared label
    with pytest.raises(ValueError):
        h.observe(1.0, program="x", extra="y")
    # idempotent re-registration; bucket/label mismatches are hard errors
    assert registry.labeled_histogram("tpudl_test_prog_seconds",
                                      buckets=(0.01, 0.1, 1.0)) is h
    with pytest.raises(ValueError):
        registry.labeled_histogram("tpudl_test_prog_seconds",
                                   buckets=(0.5,))


def test_labeled_histogram_prometheus_render(registry):
    h = registry.labeled_histogram("tpudl_test_prog_seconds", "per-program",
                                   buckets=(0.5,), label_names=("program",))
    h.observe(0.25, program="train")
    h.observe(2.0, program="train")
    text = registry.render_prometheus()
    lines = text.splitlines()
    assert "# TYPE tpudl_test_prog_seconds histogram" in lines
    assert ('tpudl_test_prog_seconds_bucket{program="train",le="0.5"} 1'
            in lines)
    assert ('tpudl_test_prog_seconds_bucket{program="train",le="+Inf"} 2'
            in lines)
    assert 'tpudl_test_prog_seconds_sum{program="train"} 2.25' in lines
    assert 'tpudl_test_prog_seconds_count{program="train"} 2' in lines


def test_perf_family_installed_and_exposed(registry):
    """The tpudl_perf_* roofline family is part of the standard catalog
    and renders as one valid exposition (gauges + the labeled series)."""
    installed = install_standard_metrics(registry)
    for name in ("tpudl_perf_mfu", "tpudl_perf_hbm_util",
                 "tpudl_perf_arith_intensity",
                 "tpudl_perf_roofline_fraction", "tpudl_perf_peak_flops",
                 "tpudl_perf_peak_hbm_bytes", "tpudl_perf_program_flops",
                 "tpudl_perf_program_bytes", "tpudl_perf_step_seconds"):
        assert name in installed, name
    registry.gauge("tpudl_perf_mfu").set(0.42)
    registry.labeled_gauge("tpudl_perf_program_flops",
                           label_names=("program",)).set(
        1e9, program="train:Net")
    registry.labeled_histogram("tpudl_perf_step_seconds").observe(
        0.01, program="train:Net")
    text = registry.render_prometheus()
    assert "tpudl_perf_mfu 0.42" in text
    assert 'tpudl_perf_program_flops{program="train:Net"} 1000000000' in text
    assert ('tpudl_perf_step_seconds_count{program="train:Net"} 1'
            in text)


def test_every_standard_metric_has_a_docs_row():
    """Anti-drift (the obs.check pattern, both directions): the metric
    catalog table in docs/observability.md and install_standard_metrics
    agree exactly — a new metric without a docs row (or a stale docs
    row) fails here, not in a dashboard.  One source of truth:
    selfcheck's own parity check."""
    from deeplearning4j_tpu.obs.selfcheck import check_metric_doc_parity
    problems: list = []
    check_metric_doc_parity(problems)
    assert problems == []


def test_standard_metrics_install_and_lint(registry):
    from deeplearning4j_tpu.obs.selfcheck import metric_lint
    installed = install_standard_metrics(registry)
    assert "tpudl_train_steps_total" in installed
    assert "tpudl_train_dispatch_seconds" in installed
    assert metric_lint(registry) == []
    # a rogue counter without _total is flagged
    registry._metrics["tpudl_test_rogue"] = Counter("tpudl_test_rogue")
    assert any("_total" in p for p in metric_lint(registry))


def test_deprecated_check_entry_point_runs_clean():
    """Existing CI invocations of the folded-away ``obs.check`` module
    keep working (the one-line shim over selfcheck's metric lint)."""
    proc = subprocess.run(
        [sys.executable, "-m", "deeplearning4j_tpu.obs.check"],
        capture_output=True, text=True, timeout=120,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "OK" in proc.stdout


def test_metrics_endpoint_after_training(tmp_path):
    """Acceptance: GET /metrics returns Prometheus text including
    tpudl_train_steps_total and the step-latency histogram after a fit."""
    from deeplearning4j_tpu.data import datasets
    from deeplearning4j_tpu.nn import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.obs import UIServer, get_registry
    from deeplearning4j_tpu.train import Adam

    conf = (NeuralNetConfiguration.builder().seed(3).updater(Adam(1e-3))
            .list()
            .layer(DenseLayer(n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=10, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(784)).build())
    net = MultiLayerNetwork(conf).init()
    it = datasets.mnist(batch_size=64, train=True, n_synthetic=128)
    before = get_registry().counter("tpudl_train_steps_total").value
    net.fit(it, epochs=1)
    assert get_registry().counter("tpudl_train_steps_total").value \
        == before + 2

    server = UIServer(port=0)
    try:
        with urllib.request.urlopen(server.url + "metrics", timeout=5) as r:
            assert r.status == 200
            assert "text/plain" in r.headers["Content-Type"]
            body = r.read().decode()
    finally:
        server.stop()
    assert "tpudl_train_steps_total" in body
    assert 'tpudl_train_dispatch_seconds_bucket{le="+Inf"}' in body
    assert "tpudl_train_dispatch_seconds_count" in body


def test_metrics_writer_feeds_registry(tmp_path):
    from deeplearning4j_tpu.obs import MetricsWriter, get_registry
    before = get_registry().counter("tpudl_obs_records_total").value
    with MetricsWriter(str(tmp_path / "m.jsonl")) as w:
        w.write({"event": "x"})
        w.write({"event": "y"})
    assert get_registry().counter("tpudl_obs_records_total").value \
        == before + 2
