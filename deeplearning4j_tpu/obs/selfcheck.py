"""Observability self-check — ``python -m deeplearning4j_tpu.obs.selfcheck``.

One CI entry point that proves the observability layer is internally
consistent on a bare CPU box:

1. **registry lint** — every registered metric (standard catalog
   installed) passes the TPU305 naming rules;
2. **metric-doc parity** — every standard metric has a row in
   ``docs/observability.md``'s catalog table and every ``tpudl_``-named
   row in that table names a registered metric (anti-drift both ways,
   the ``obs.check`` / rule-table pattern);
3. **cost-model smoke** — a tiny jitted matmul is analyzed through
   ``lowered.compile().cost_analysis()``: FLOPs/bytes are positive and
   the MFU/HBM/arith-intensity stamp computes on the CPU fallback peaks;
4. **flight-recorder smoke** — events + a dump round-trip: the dump
   carries thread stacks, ring events and a metrics snapshot;
5. **federation smoke** — a loopback ``RemoteStatsRouter`` →
   ``UIServer`` ingest round-trip: pushed step records appear in the
   ``/cluster.json`` summary and as ``worker``-labeled series on
   ``/metrics`` (the tpudl_cluster_* families stay wired end-to-end).

This module also absorbs the deprecated ``obs.check`` entry point: the
metric-name lint lives here as :func:`metric_lint` /
:func:`metric_lint_main` (``obs/check.py`` is a one-line shim with a
DeprecationWarning).

Exit 0 = all pass; 1 = failures (printed).  Wired into tier-1 via
``tests/test_obs_selfcheck.py``.
"""

from __future__ import annotations

import os
import re
import sys
import tempfile


# ---------------------------------------------- the former obs.check lint
def metric_lint(registry=None) -> list[str]:
    """Human-readable metric-name violations (empty = clean) — delegates
    to the TPU305 rule in ``tpudl.analyze`` (the single source of the
    naming convention)."""
    from deeplearning4j_tpu.analyze.lint import check_metric_names
    report = check_metric_names(registry)
    return [f"{d.path}: {d.message}" for d in report.sorted()]


def metric_lint_main(argv=None) -> int:
    """The old ``python -m deeplearning4j_tpu.obs.check`` behavior."""
    from deeplearning4j_tpu.obs.registry import get_registry
    problems = metric_lint()
    if problems:
        print(f"obs metric lint: {len(problems)} metric-name "
              f"violation(s) [TPU305]:")
        for p in problems:
            print(f"  - {p}")
        return 1
    print(f"obs metric lint: {len(get_registry().names())} registered "
          f"metric names OK (tpudl_<area>_<name>)")
    return 0


def _doc_metric_names(doc_text: str) -> set:
    """Metric names out of the docs/observability.md catalog table rows
    (``| `tpudl_x_y{label}` | type | ...``) — label suffixes stripped."""
    names = set()
    for m in re.finditer(r"^\|\s*`(tpudl_[a-z0-9_]+)(\{[^`]*\})?`\s*\|",
                         doc_text, re.MULTILINE):
        names.add(m.group(1))
    return names


def check_registry_lint(problems: list) -> None:
    from deeplearning4j_tpu.analyze.lint import check_metric_names
    report = check_metric_names()
    for d in report.sorted():
        problems.append(f"registry lint: {d.render()}")


def check_metric_doc_parity(problems: list) -> None:
    from deeplearning4j_tpu.obs.registry import (MetricsRegistry,
                                                 install_standard_metrics)
    import deeplearning4j_tpu
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(
        deeplearning4j_tpu.__file__)))
    doc_path = os.path.join(repo_root, "docs", "observability.md")
    try:
        with open(doc_path) as f:
            doc = f.read()
    except OSError as e:
        problems.append(f"metric-doc parity: cannot read {doc_path}: {e}")
        return
    documented = _doc_metric_names(doc)
    standard = set(install_standard_metrics(MetricsRegistry()))
    for name in sorted(standard - documented):
        problems.append(f"metric-doc parity: {name} is registered but has "
                        f"no row in docs/observability.md")
    for name in sorted(documented - standard):
        problems.append(f"metric-doc parity: docs/observability.md "
                        f"documents {name} but install_standard_metrics "
                        f"does not register it")


def check_costmodel_smoke(problems: list) -> None:
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.obs import costmodel

    @jax.jit
    def _mm(a, b):
        return jnp.dot(a, b)

    a = jnp.ones((128, 256), jnp.float32)
    b = jnp.ones((256, 128), jnp.float32)
    _mm(a, b).block_until_ready()
    cost = costmodel.analyze_jitted(_mm, costmodel.abstractify((a, b)),
                                    kind="selfcheck:matmul")
    if cost is None:
        problems.append("costmodel: cost_analysis unavailable for a jitted "
                        "matmul on this backend")
        return
    if cost.flops <= 0 or cost.bytes_accessed <= 0:
        problems.append(f"costmodel: non-positive cost facts "
                        f"(flops={cost.flops}, bytes={cost.bytes_accessed})")
    costmodel.observe_step(_mm, 0.01)
    stamp = costmodel.bench_detail(kind="selfcheck:matmul")
    if not stamp or stamp["mfu"] <= 0 or stamp["arith_intensity"] <= 0:
        problems.append(f"costmodel: bench stamp incomplete: {stamp}")
    elif stamp["source"] != "xla_cost_analysis":
        problems.append("costmodel: stamp not sourced from cost_analysis")


def check_flight_recorder_smoke(problems: list) -> None:
    from deeplearning4j_tpu.obs import flight_recorder
    rec = flight_recorder.FlightRecorder(capacity=16)
    rec.record("selfcheck", n=1)
    rec.progress("selfcheck.site")
    with tempfile.TemporaryDirectory() as td:
        path = rec.dump(os.path.join(td, "flight.jsonl"),
                        reason="selfcheck")
        lines = flight_recorder.read_dump(path)
    kinds = {line.get("type") for line in lines}
    for wanted in ("header", "thread", "event", "metrics", "liveness"):
        if wanted not in kinds:
            problems.append(f"flight recorder: dump missing a "
                            f"{wanted!r} line (got {sorted(kinds)})")
    if not any(line.get("kind") == "selfcheck" for line in lines):
        problems.append("flight recorder: ring event missing from dump")


def check_federation_smoke(problems: list) -> None:
    """Loopback router → UIServer ingest round-trip: the whole
    federation path (buffered push, HTTP ingest, ClusterStore summary,
    worker-labeled /metrics series) on 127.0.0.1."""
    import json
    import time
    import urllib.request

    from deeplearning4j_tpu.obs.remote import RemoteStatsRouter
    from deeplearning4j_tpu.obs.ui_server import UIServer

    server = UIServer(port=0)
    router = RemoteStatsRouter(server.url, worker="selfcheck",
                               flush_interval_s=0.05)
    try:
        for i in range(3):
            router.put_event("step", iteration=i, step_seconds=0.01,
                             score=1.0)
        summary = {}
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            with urllib.request.urlopen(server.url + "cluster.json",
                                        timeout=2) as resp:
                summary = json.loads(resp.read())
            if summary.get("workers", {}).get("selfcheck",
                                              {}).get("steps") == 3:
                break
            time.sleep(0.05)
        worker = summary.get("workers", {}).get("selfcheck")
        if not worker or worker.get("steps") != 3:
            problems.append(f"federation: /cluster.json never showed the "
                            f"3 pushed steps (got {summary})")
            return
        if worker.get("median_step_ms") is None:
            problems.append("federation: worker summary has no "
                            "median_step_ms")
        with urllib.request.urlopen(server.url + "metrics",
                                    timeout=2) as resp:
            body = resp.read().decode()
        if 'tpudl_cluster_worker_iteration{worker="selfcheck"}' not in body:
            problems.append("federation: /metrics exposition lacks the "
                            "worker-labeled tpudl_cluster_worker_iteration "
                            "series")
        if router.dropped:
            problems.append(f"federation: loopback push dropped "
                            f"{router.dropped} records")
    except Exception as e:
        problems.append(f"federation: loopback round-trip failed: {e!r}")
    finally:
        router.close(timeout=2.0)
        server.stop()


def main(argv=None) -> int:
    problems: list[str] = []
    check_registry_lint(problems)
    check_metric_doc_parity(problems)
    check_costmodel_smoke(problems)
    check_flight_recorder_smoke(problems)
    check_federation_smoke(problems)
    if problems:
        print(f"obs.selfcheck: {len(problems)} problem(s):")
        for p in problems:
            print(f"  - {p}")
        return 1
    from deeplearning4j_tpu.obs.registry import get_registry
    n = len(get_registry().names())
    print(f"obs.selfcheck OK: registry lint clean ({n} metrics), "
          f"metric-doc parity holds, cost_analysis smoke passed, "
          f"flight-recorder dump round-trips, router→UIServer "
          f"federation round-trips on loopback")
    return 0


if __name__ == "__main__":
    sys.exit(main())
