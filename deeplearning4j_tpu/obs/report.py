"""Fleet health in one command — ``python -m deeplearning4j_tpu.obs.report``.

Renders everything the verdict layer knows as a single page: SLO
status with budget remaining (from a live :class:`SLOMonitor` in
library use, or the published ``tpudl_slo_*`` series when reading a
registry), open health anomalies, and the honesty counters (artifact
rejects, recompiles, rollbacks) — as markdown for humans (default) and
JSON for machines (``--json``).  The perf trajectory is the driver's
``PERF_LEDGER.jsonl``, not a page of this report.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Optional

from .registry import (MetricsRegistry, get_registry,
                       install_standard_metrics)

# the registry honesty counters worth a row on the front page
_COUNTERS = (
    ("tpudl_compile_artifact_rejects_total", "artifact rejects"),
    ("tpudl_train_recompiles_total", "train recompiles"),
    ("tpudl_serve_recompiles_total", "serve recompiles"),
    ("tpudl_online_rollbacks_total", "online rollbacks"),
    ("tpudl_slo_breaches_total", "SLO breaches"),
)


def _slo_section(monitor=None,
                 registry: Optional[MetricsRegistry] = None) -> list[dict]:
    """Per-objective rows.  A live monitor is authoritative; otherwise
    the published ``tpudl_slo_*`` series are read back (the CLI path —
    whatever process evaluated last has already exported its verdicts)."""
    if monitor is not None:
        return [{
            "slo": st.slo, "target": st.target, "healthy": st.healthy,
            "burn_rate": round(st.burn_rate, 3),
            "budget_remaining": round(st.budget_remaining, 4),
            "bad": st.bad, "total": st.total,
            "description": st.description,
        } for st in monitor.status().values()]
    reg = registry or get_registry()
    healthy = reg.get("tpudl_slo_healthy")
    if healthy is None or not hasattr(healthy, "child_values"):
        return []
    burn = reg.get("tpudl_slo_burn_rate")
    budget = reg.get("tpudl_slo_budget_remaining")
    rows = []
    for key, val in sorted(healthy.child_values().items()):
        name = key[0]
        rows.append({
            "slo": name, "target": None, "healthy": bool(val),
            "burn_rate": round(burn.labeled_value(slo=name), 3)
            if burn is not None else None,
            "budget_remaining": round(
                budget.labeled_value(slo=name), 4)
            if budget is not None else None,
            "bad": None, "total": None, "description": "",
        })
    return rows


def _health_section(registry: Optional[MetricsRegistry] = None) -> dict:
    reg = registry or get_registry()
    anomalies = reg.get("tpudl_health_anomalies_total")
    by_kind = {}
    if anomalies is not None and hasattr(anomalies, "child_values"):
        by_kind = {k[0]: v for k, v in anomalies.child_values().items()
                   if v > 0}
    counters = {}
    for name, label in _COUNTERS:
        m = reg.get(name)
        if m is not None:
            counters[name] = {"label": label, "value": m.value}
    return {"anomalies_by_kind": by_kind, "counters": counters}


def build_report(monitor=None,
                 registry: Optional[MetricsRegistry] = None) -> dict:
    """The whole machine-readable report; every renderer reads this."""
    return {
        "slos": _slo_section(monitor, registry),
        "health": _health_section(registry),
    }


def render_markdown(report: dict) -> str:
    out = ["# Fleet health", ""]

    out.append("## SLOs")
    if report["slos"]:
        out.append("| objective | healthy | burn rate | budget left |")
        out.append("|---|---|---|---|")
        for row in report["slos"]:
            budget = row["budget_remaining"]
            out.append(
                f"| {row['slo']} "
                f"| {'yes' if row['healthy'] else 'BREACHED'} "
                f"| {row['burn_rate'] if row['burn_rate'] is not None else '—'} "
                f"| {'—' if budget is None else format(budget, '.0%')} |")
    else:
        out.append("no SLO evaluations in this registry (start an "
                   "SLOMonitor, or read a serving process's registry)")
    out.append("")

    health = report["health"]
    out.append("## Health & honesty counters")
    if health["anomalies_by_kind"]:
        for kind, count in sorted(health["anomalies_by_kind"].items()):
            out.append(f"- open health anomalies `{kind}`: {count:g}")
    else:
        out.append("- health anomalies: none recorded")
    for name, row in sorted(health["counters"].items()):
        out.append(f"- {row['label']} (`{name}`): {row['value']:g}")
    return "\n".join(out) + "\n"


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m deeplearning4j_tpu.obs.report",
        description="fleet health: SLO status, health + honesty "
                    "counters")
    p.add_argument("--json", action="store_true",
                   help="emit the machine-readable report")
    args = p.parse_args(argv)
    # a fresh CLI process has an empty registry: install the standard
    # family so the counter rows render (as zeros) instead of vanishing
    install_standard_metrics()
    report = build_report()
    if args.json:
        print(json.dumps(report, indent=1, default=str))
    else:
        print(render_markdown(report), end="")
    return 0


if __name__ == "__main__":
    sys.exit(main())
