"""What the readers of the program's own step and feeder histograms share
(``metrics/feed_busy_ms.*``, ``dispatch_ms.*``, ``host_self_ms.*``).  Each
reads the growth of histogram sums over the window from the registry
snapshots ``harness.run_cell`` takes, per step of the window.  A program
that keeps no such series (a parent commit from before it did) gives
``None``, and the harness leaves the metric out of the line."""

from __future__ import annotations


def grown(obs: dict, name: str):
    """Growth of the histogram's sum over the window, in seconds."""
    after = obs["counters"]["after"].get(name)
    if after is None:
        return None
    before = obs["counters"]["before"].get(name)
    return after[0] - (before[0] if before else 0.0)


def per_step_ms(obs: dict, unit: str, plus: tuple, minus: tuple = ()):
    """(sum of ``plus`` - sum of ``minus``) over the window's steps, in
    milliseconds; ``None`` for a mix of another unit, a window without
    steps, or where any of the series is absent."""
    if obs["mix"]["unit"] != unit or not obs["window"]["steps"]:
        return None
    added = [grown(obs, name) for name in plus]
    taken = [grown(obs, name) for name in minus]
    if None in added or None in taken:
        return None
    return 1e3 * (sum(added) - sum(taken)) / obs["window"]["steps"]
