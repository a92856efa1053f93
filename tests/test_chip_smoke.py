"""``chip_smoke.py`` and ``bench.py`` without a chip: they must refuse, and
the smoke's phases must pass at tiny sizes.

The phases assert absolute registry counts (one recompile, twelve ok
requests) and the warm restart is by definition a fresh process, so the
rehearsal runs them as the script does: one child process after the other,
sharing a work directory and a compile cache.
"""

import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_RUN_PHASES = """
import sys
import chip_smoke
tiny = chip_smoke.Sizes(
    image=int(sys.argv[3]), classes=10, batch=int(sys.argv[4]),
    full_batches=2, tail=3, epochs=2, buckets=(1, 2, 4),
    requests=(1, 2, 4, 3, 1, 4, 2, 3, 1, 2, 4, 1),
    flash=(1, 256, 64, 2), int8=(8, 128, 128), dp_steps=2)
chip_smoke.run_phases(sys.argv[1], tiny, sys.argv[2])
"""


def _run(argv, **env):
    return subprocess.run(
        [sys.executable] + argv, cwd=REPO_ROOT, capture_output=True,
        text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


def test_chip_smoke_refuses_a_cpu():
    proc = _run(["chip_smoke.py"])
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_bench_refuses_a_cpu():
    proc = _run(["bench.py"])
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""


def test_chip_smoke_phases_at_tiny_sizes(tmp_path):
    workdir = tmp_path / "work"
    workdir.mkdir()
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
           "PYTHONPATH": REPO_ROOT}
    # image and batch: the smallest at which bf16 BatchNorm statistics are
    # stable enough for dp4 to track one device, and smaller for the
    # single-chip children, whose checks do not compare two runs.  That
    # was 64x64 while the statistics took two passes (first losses 0.06-
    # 0.23% apart over three seeds).  Taken in one pass (PR 28) they feel
    # the reduction's order (mean/std)**2 times as much, and this net at
    # gamma 1 amplifies it: 0.08-1.3% at 64x64 and 96x96, 0.11-0.55% over
    # six seeds at 128x128 (the same 36 s), against the smoke's band of 1%
    for phase, image, batch in (("a", 32, 8), ("b", 32, 8),
                                ("dp4", 128, 16)):
        proc = _run(["-c", _RUN_PHASES, phase, str(workdir), str(image),
                     str(batch)], **env)
        assert proc.returncode == 0, (phase, proc.stderr[-3000:])
        said = [line.split()[1] for line in proc.stdout.splitlines()
                if line.startswith("[chip_smoke] ")]
        assert said == {
            "a": ["child_a", "train", "serve", "kernels", "bake",
                  "child_a_done"],
            "b": ["child_b", "warm_restart", "warm_train_step",
                  "child_b_done"],
            "dp4": ["child_dp4", "dp4", "child_dp4_done"]}[phase], proc.stdout
        if phase in ("a", "b"):
            # the train step child A's ``net.fit`` compiled is found again
            # by child B's ``fit_batch``: another call stack, the same key
            steps = [f for f in os.listdir(tmp_path / "cache")
                     if f.startswith("jit_tpudl_train_step-")]
            assert len(steps) == 1, steps
