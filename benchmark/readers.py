"""What the per-metric readers under ``metrics/`` share.  Each takes the
run's observations (``harness.run_cell`` builds them) and the unit of the
cell's rate it is for; a reader that finds nothing to read returns
``None`` and the harness leaves the metric out of the line."""

from __future__ import annotations

import json
import os

import harness

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind ``peaks.json`` lacks is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(
            f"benchmark/peaks.json has no peaks for device_kind "
            f"{device_kind!r}; add a row with its source")
    return table[device_kind]


def rate(obs: dict, unit: str):
    """All the work of the window over all its time."""
    if obs["mix"]["unit"] != unit:
        return None
    return obs["window"]["rate"]


def device_idle_pct(obs: dict, unit: str):
    """Idle share of the chip that idles most, from the profiler trace."""
    if obs["mix"]["unit"] != unit or obs["trace"] is None:
        return None
    return obs["trace"]["idle_pct_worst"]


def step_mfu_pct(obs: dict, unit: str):
    """The whole step's share of the chips' peak: operations the forward
    and backward passes need per unit (``flops/<kind>.py`` by the name the
    configuration gives, from shapes) times the run's units per second,
    over chips times the bf16 peak (peaks.json)."""
    if obs["mix"]["unit"] != unit:
        return None
    per_unit = harness.load_module("flops", obs["config"]["flops"]).per_unit(
        obs["config"], obs["mix"])
    peak = peaks(obs["device_kind"])["bf16_flops_per_s"]
    return 100.0 * per_unit * obs["window"]["rate"] / (obs["chips"] * peak)


def feed_wait_ms(obs: dict, unit: str):
    """Time the step loop waited for the feeder, per step: the growth of
    ``tpudl_data_etl_wait_seconds``' sum over the window."""
    name = "tpudl_data_etl_wait_seconds"
    before = obs["counters"]["before"].get(name)
    after = obs["counters"]["after"].get(name)
    if obs["mix"]["unit"] != unit or after is None or not obs["window"]["steps"]:
        return None
    grown = after[0] - (before[0] if before else 0.0)
    return 1e3 * grown / obs["window"]["steps"]
