"""Layer 'kernels': the flash-attention kernels' share of their roofline.

Needed work a step (``flops/<kind>.py``'s ``flash_kernels``: forward 2 and
backward 4 matrix products over the causal half, recomputation not
counted) over the kernels' device time a step and the chip's bf16 peak,
or their bytes over HBM's pace, whichever takes longer.

The device time comes from the trace summary's ``device_ops``, which
names an operation by its HLO instruction (``tpudl_flash_fwd.7``): every
call site of a kernel is an instruction of its own, and the list holds
only the ten operations that took most time.  Every call of one kernel
in a step has the same shapes and the same work, so the sites that are
listed stand for those that are not: their mean time, over the times
such a site ran in the trace, is the kernel's time a call.  The trace
holds a whole number of steps and a part (6.5 today), so a site ran 6
or 7 times, and the list, sorted by time, holds the 7-time sites first:
``_runs`` counts the listed sites' mean runs from that, where dividing
by 6.5 would read the time up to 8% high and the share low.  Events cut
at the trace's edges are not corrected for.

``None`` means "no call site of some kernel made the list's ten", and
so does not say the kernel is gone: the forward kernel's sites are the
list's last entries today, and a faster forward kernel or a slower
fusion pushes them off it.  Until ``trace.reduce`` sums ``device_ops``
by the kernel's ``name=`` (PERF.md section 7, ROADMAP T1), read the
line's ``breakdown.device_ops`` beside a ``None``.  A program or a
configuration without such kernels gives ``None`` too.
"""

import math

import harness
import readers


def _runs(listed: int, calls: int, steps: float) -> float:
    """Mean number of times the ``listed`` longest-running of a kernel's
    ``calls`` call sites ran in a trace of ``steps`` steps: every site
    ran ``floor(steps)`` times and the part of a step at the end ran
    that share of the sites once more; those lead the list."""
    whole = math.floor(steps)
    once_more = (steps - whole) * calls
    return whole + min(once_more, listed) / listed


def read(obs):
    if obs["mix"]["unit"] != "tokens" or obs["trace"] is None \
            or not obs["window"]["steps"]:
        return None
    counted = getattr(harness.load_module("flops", obs["config"]["flops"]),
                      "flash_kernels", None)
    if counted is None:
        return None
    peaks = readers.peaks(obs["device_kind"])
    # the window's time a step says how many steps the trace held
    step_s = obs["window"]["seconds"] / obs["window"]["steps"]
    traced_s = obs["trace"]["window_s"]
    least = spent = 0.0
    for kernel, (calls, operations, bytes_) in counted(
            obs["config"], obs["mix"]).items():
        # "tpudl_flash_fwd.7" or "tpudl_flash_fwd", a kind after a space
        sites = [t for name, t in obs["trace"]["device_ops"]
                 if name.split(" ")[0].partition(".")[0] == kernel]
        if not sites or traced_s <= 0:
            return None
        runs = _runs(len(sites), calls, traced_s / step_s)
        if runs <= 0:
            return None
        spent += calls * (sum(sites) / len(sites)) / runs
        least += max(operations / peaks["bf16_flops_per_s"],
                     bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (obs["chips"] * spent) if spent > 0 else None
