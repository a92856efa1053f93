"""tpudl.obs.report (ISSUE 16): the one-page fleet-health report."""

import json

import pytest

from deeplearning4j_tpu.obs import report, slo
from deeplearning4j_tpu.obs.registry import (MetricsRegistry, get_registry,
                                             install_standard_metrics,
                                             set_registry)


@pytest.fixture
def metrics():
    prev = set_registry(MetricsRegistry())
    try:
        yield get_registry()
    finally:
        set_registry(prev)


def test_report_honesty_counters_render_as_zeros(metrics):
    install_standard_metrics(metrics)
    built = report.build_report(registry=metrics)
    # honesty counters render as explicit zeros, not absences
    counters = built["health"]["counters"]
    assert counters["tpudl_slo_breaches_total"]["value"] == 0
    assert counters["tpudl_online_rollbacks_total"]["value"] == 0

    text = report.render_markdown(built)
    assert "# Fleet health" in text
    assert "SLO breaches (`tpudl_slo_breaches_total`): 0" in text


def test_report_slo_rows_from_a_live_monitor(metrics):
    requests = metrics.labeled_counter("tpudl_serve_requests_total")
    clock_t = [0.0]
    mon = slo.SLOMonitor(
        [slo.AvailabilitySLO(target=0.99)],
        registry=metrics,
        windows=(slo.BurnWindow("fast", 60.0, 300.0, 10.0),),
        clock=lambda: clock_t[0])
    for _ in range(2):
        requests.inc(9, status="error")
        requests.inc(1, status="ok")
        mon.evaluate_once()
        clock_t[0] += 10.0
    built = report.build_report(monitor=mon, registry=metrics)
    (row,) = built["slos"]
    assert row["slo"] == "availability" and row["healthy"] is False
    assert row["burn_rate"] > 10.0
    text = report.render_markdown(built)
    assert "| availability | BREACHED |" in text


def test_report_slo_rows_read_back_from_published_metrics(metrics):
    # the CLI path: no live monitor, just the exported tpudl_slo_* family
    metrics.labeled_gauge("tpudl_slo_healthy",
                          label_names=("slo",)).set(0.0, slo="latency")
    metrics.labeled_gauge("tpudl_slo_burn_rate",
                          label_names=("slo",)).set(22.5, slo="latency")
    metrics.labeled_gauge("tpudl_slo_budget_remaining",
                          label_names=("slo",)).set(0.1, slo="latency")
    built = report.build_report(registry=metrics)
    (row,) = built["slos"]
    assert row["slo"] == "latency"
    assert row["healthy"] is False
    assert row["burn_rate"] == pytest.approx(22.5)


def test_report_cli_json_is_machine_readable(capsys):
    assert report.main(["--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert {"slos", "health"} <= set(payload)
