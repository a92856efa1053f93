#!/usr/bin/env python
"""CPU micro-bench: DeviceFeeder on vs off over an ETL-heavy ragged epoch.

Measures the device-feed pipeline's two effects without a TPU:

* **overlap** — per-batch host ETL (normalize + noise passes) runs on
  the feeder's background stage under device execution instead of
  serializing with it → steps/sec.  The loop carries a per-step score
  listener (the common ScoreIterationListener configuration), which
  syncs each step's loss — exactly the regime where inline ETL
  serializes host against device and the feeder's background stage
  wins it back;
* **recompile guard** — the 1031-example / batch-64 epoch has a ragged
  tail; with the feeder's shape bucketing the train step compiles ONCE
  (jit cache size 1), without it the tail shape compiles a second
  program.

Run standalone (``python bench/feed_overlap.py``); pinned to
``JAX_PLATFORMS=cpu`` unless the variable is set.  Prints ONE json line
that names the platform it ran on.
"""

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

N_EXAMPLES = 1031     # deliberately non-divisible: full batches + ragged tail
N_FEATURES = 512
BATCH = 64
EPOCHS = 3
ETL_NOISE_PASSES = 6  # host work per batch the feeder can hide


def _etl_iterator(x, y):
    """Generator iterator with deliberate per-batch host ETL (the work
    the feeder's background stage overlaps with the device step)."""
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterators import GeneratorDataSetIterator

    def factory():
        n = x.shape[0]
        for lo in range(0, n, BATCH):
            xb = x[lo:lo + BATCH]
            xb = (xb - xb.mean(axis=0)) / (xb.std(axis=0) + 1e-6)
            rng = np.random.default_rng(lo)
            for _ in range(ETL_NOISE_PASSES):
                xb = xb + rng.normal(scale=1e-3, size=xb.shape)
            yield DataSet(xb.astype(np.float32), y[lo:lo + BATCH])

    return GeneratorDataSetIterator(factory)


class _ScoreSync:
    """Per-step host read of the loss (ScoreIterationListener regime) —
    the sync that makes inline ETL serialize against the device."""

    def iteration_done(self, model, iteration, epoch, score):
        self.last = float(score)


def run_mode(device_feed: bool) -> dict:
    from deeplearning4j_tpu.config import set_config
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.input_type import InputType
    from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.train.step_cache import jit_cache_entries
    from deeplearning4j_tpu.train.trainer import Trainer
    from deeplearning4j_tpu.train.updaters import Sgd

    set_config(device_feed=device_feed, shape_bucketing=device_feed)
    rng = np.random.default_rng(7)
    x = rng.normal(size=(N_EXAMPLES, N_FEATURES)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, N_EXAMPLES)]
    # distinct seed per mode → distinct step-cache key, so the OFF run's
    # compiled programs cannot leak into the ON run's recompile count
    conf = (NeuralNetConfiguration.builder()
            .seed(1000 + int(device_feed)).updater(Sgd(0.05)).list()
            .layer(DenseLayer(n_out=1024, activation="relu"))
            .layer(DenseLayer(n_out=1024, activation="relu"))
            .layer(OutputLayer(n_out=10, activation="softmax"))
            .set_input_type(InputType.feed_forward(N_FEATURES)).build())
    net = MultiLayerNetwork(conf).init()
    trainer = Trainer(net, listeners=[_ScoreSync()])
    iterator = _etl_iterator(x, y)

    trainer.fit(iterator, epochs=1)       # compile + warm both shapes
    float(net._score)                     # sync fence
    # the warm epoch queued this step's background cost analysis (a
    # duplicate XLA compile) — and the OFF run's may still be in flight
    # when the ON run measures; drain so it never contends with the
    # region that decides the off-vs-on speedup
    from deeplearning4j_tpu.obs import costmodel
    costmodel.drain()
    t0 = time.perf_counter()
    trainer.fit(iterator, epochs=EPOCHS)
    float(net._score)                     # sync fence inside the region
    dt = time.perf_counter() - t0
    n_steps = -(-N_EXAMPLES // BATCH) * EPOCHS
    return {
        "steps_per_sec": round(n_steps / dt, 2),
        "recompiles": jit_cache_entries(trainer._step),
    }


def main() -> int:
    import jax

    from deeplearning4j_tpu.config import place_compile_cache
    place_compile_cache()
    off = run_mode(False)
    on = run_mode(True)
    # roofline stamp: the trainers above ran under the cost model, so
    # the record carries MFU / HBM utilization / arithmetic intensity
    # from the compiled step's own cost_analysis
    from deeplearning4j_tpu.obs import costmodel
    costmodel.drain()   # flush any still-queued background analysis
    perf = costmodel.bench_detail() or {}
    result = {
        "metric": "feed_overlap",
        "platform": jax.devices()[0].platform,
        "batch": BATCH, "examples": N_EXAMPLES, "epochs": EPOCHS,
        "prefetch_off_steps_per_sec": off["steps_per_sec"],
        "prefetch_on_steps_per_sec": on["steps_per_sec"],
        "speedup": round(on["steps_per_sec"] / max(off["steps_per_sec"],
                                                   1e-9), 3),
        "recompiles": {"off": off["recompiles"], "on": on["recompiles"]},
        "mfu": perf.get("mfu"),
        "hbm_util": perf.get("hbm_util"),
        "arith_intensity": perf.get("arith_intensity"),
        "perf": perf,
        "note": ("per-step score sync (ScoreIterationListener regime); "
                 "etl waits land in tpudl_data_etl_wait_seconds"),
    }
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
