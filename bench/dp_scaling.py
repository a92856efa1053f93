#!/usr/bin/env python
"""DP scaling measurement on the 8-device virtual CPU mesh.

Weak scaling: fixed per-device batch, dp = 1/2/4/8 over the virtual
mesh, real ``ParallelWrapper`` trainer (psum gradient allreduce inside
the donated jit step).  CPU collectives model the dp *overhead
structure* (program + collective per step, same XLA SPMD partitioner
the TPU path uses), not ICI bandwidth.

Prints ONE json line that says ``platform: cpu``; run standalone.
"""

import json
import os
import sys
import time

# must precede jax import
os.environ["JAX_PLATFORMS"] = "cpu"
# force EXACTLY 8 virtual devices (a pre-existing count in XLA_FLAGS
# would silently shrink the dp sweep)
_flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
          if "xla_force_host_platform_device_count" not in f]
os.environ["XLA_FLAGS"] = " ".join(
    _flags + ["--xla_force_host_platform_device_count=8"])

import jax  # noqa: E402
import numpy as np  # noqa: E402


def measure(per_device_batch: int = 32, steps: int = 8,
            warmup: int = 2) -> dict:
    import jax.numpy as jnp
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.models import lenet
    from deeplearning4j_tpu.parallel.data_parallel import ParallelWrapper
    from deeplearning4j_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(0)
    rows = []
    for dp in (1, 2, 4, 8):
        net = lenet(height=32, width=32, channels=3)
        mesh = make_mesh(data=dp, devices=jax.devices()[:dp])
        trainer = ParallelWrapper(net, mesh=mesh)
        batch = per_device_batch * dp
        ds = DataSet(
            jnp.asarray(rng.normal(size=(batch, 32, 32, 3))
                        .astype(np.float32)),
            jnp.asarray(np.eye(10, dtype=np.float32)[
                rng.integers(0, 10, batch)]))
        key = jax.random.key(0)
        for _ in range(warmup):
            loss = trainer.fit_batch(ds, key)
        float(loss)
        # best-of-3: host-load noise on the shared virtual devices was
        # ±2x run to run; min is the stable
        # estimator of the program's actual cost
        dt = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(steps):
                loss = trainer.fit_batch(ds, key)
            float(loss)
            dt = min(dt, (time.perf_counter() - t0) / steps)
        rows.append({"dp": dp, "global_batch": batch,
                     "step_ms": round(dt * 1000, 2),
                     "img_per_sec": round(batch / dt, 1)})
    t1 = rows[0]["step_ms"]
    for r in rows:
        # virtual CPU devices SHARE the host cores, so total work scales
        # with dp and step time grows ~linearly; the measurable quantity
        # is the SPMD overhead factor — partitioned program + psum
        # allreduce vs dp x the single-device work.  1.0 = the
        # partitioner/collective added nothing; >1 = overhead.
        r["spmd_overhead_factor"] = round(r["step_ms"] / (t1 * r["dp"]), 3)
    return {"metric": "dp_weak_scaling_cpu_mesh",
            "per_device_batch": per_device_batch,
            "model": "lenet_cifar10_shape", "rows": rows,
            "platform": jax.devices()[0].platform,
            "note": ("virtual devices share host cores: spmd_overhead_"
                     "factor isolates partitioner+collective cost, not "
                     "ICI bandwidth")}


if __name__ == "__main__":
    from deeplearning4j_tpu.config import place_compile_cache
    place_compile_cache()
    print(json.dumps(measure()))
    sys.exit(0)
