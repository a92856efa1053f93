#!/usr/bin/env python
"""CPU micro-bench: the closed continual-learning loop, timed end to end.

Measures the ``tpudl.online`` subsystem's three operational numbers
without a TPU (docs/online.md):

* **feedback→deploy latency** — wall time from the first feedback
  record landing in the spool to a gated hot-swap completing: spool
  drain + round trigger + fine-tune from the latest verified checkpoint
  + gate eval + registry verified hot-swap.  This is the loop's
  "fine-tune→serve turnaround" headline (the Gemma-on-TPU serving
  comparison's axis, PAPERS.md).
* **gate eval seconds** — verify + score candidate and incumbent on the
  held-out slice + decide (the pure gate overhead a deploy pays).
* **rollback MTTR** — regression detection to the rolled-back previous
  version serving again, measured by injecting a post-deploy serve
  error burst under a live :class:`DeployWatch`.

Run standalone (``python bench/online.py``); pinned to
``JAX_PLATFORMS=cpu`` unless the variable is set.  Prints ONE json line
that names the platform it ran on.
"""

import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

N_IN, N_OUT = 16, 4
FEEDBACK_RECORDS = 96
BATCH = 16


def _teacher(rng):
    return rng.normal(size=(N_IN, N_OUT)).astype(np.float32)


def _make_xy(w, n, seed):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, N_IN)).astype(np.float32)
    y = np.eye(N_OUT, dtype=np.float32)[np.argmax(x @ w, -1)]
    return x, y


def _build_net(seed):
    from deeplearning4j_tpu.nn import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.train import Adam
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-2))
            .list()
            .layer(DenseLayer(n_out=32, activation="relu"))
            .layer(OutputLayer(n_out=N_OUT, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(N_IN)).build())
    return MultiLayerNetwork(conf).init()


def main() -> dict:
    import jax

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterators import ListDataSetIterator
    from deeplearning4j_tpu.obs.registry import get_registry
    from deeplearning4j_tpu.online import (DeployWatch, EvalGate,
                                           OnlineConfig, OnlineTrainer)
    from deeplearning4j_tpu.serve import FeedbackLog, ModelRegistry

    rng = np.random.default_rng(0)
    w = _teacher(rng)
    workdir = tempfile.mkdtemp(prefix="tpudl_bench_online_")

    # a briefly-trained base model, deployed as the incumbent
    net = _build_net(1)
    x0, y0 = _make_xy(w, 64, 1)
    net.fit(ListDataSetIterator(
        [DataSet(x0[i:i + BATCH], y0[i:i + BATCH])
         for i in range(0, 64, BATCH)]), epochs=1)
    base = os.path.join(workdir, "base.zip")
    net.save(base)
    registry = ModelRegistry(max_batch=8, max_latency_ms=2.0)
    registry.deploy("bench", base)

    hx, hy = _make_xy(w, 128, 3)
    gate = EvalGate(ListDataSetIterator([DataSet(hx, hy)]),
                    metric="accuracy", min_delta=1.0)   # non-regression only
    spool = os.path.join(workdir, "spool")
    log = FeedbackLog(spool)
    trainer = OnlineTrainer(
        registry, "bench", spool, os.path.join(workdir, "online"), gate,
        base, config=OnlineConfig(min_records=FEEDBACK_RECORDS,
                                  batch_size=BATCH,
                                  max_records_per_round=FEEDBACK_RECORDS))

    # ---- feedback → deploy: first record spooled to hot-swap complete
    xf, yf = _make_xy(w, FEEDBACK_RECORDS, 2)
    t0 = time.perf_counter()
    log.extend(xf, yf)
    log.flush()
    decision = trainer.run_once(force=True)
    feedback_to_deploy_s = time.perf_counter() - t0
    deployed = decision["status"] == "deployed"
    gate_eval_s = decision.get("gate", {}).get("gate_seconds", 0.0)

    # ---- rollback MTTR: a live watch over an injected serve error burst
    import threading
    reg = get_registry()
    requests = reg.labeled_counter("tpudl_serve_requests_total")
    watch = DeployWatch(registry, "bench", window_s=10.0, poll_s=0.02,
                        error_rate_max=0.25, min_requests=4)

    def _burst():
        # the burst lands AFTER the watch's baseline snapshot — the
        # delta is what detection reads
        time.sleep(0.05)
        requests.inc(16, status="error")
        requests.inc(4, status="ok")

    t1 = time.perf_counter()
    threading.Thread(target=_burst, daemon=True).start()
    verdict = watch.run()
    rollback_wall_s = time.perf_counter() - t1

    registry.close()
    log.close()
    spool_records = reg.counter("tpudl_online_spool_records_total").value
    return {
        "metric": "online_feedback_to_deploy_seconds",
        "value": round(feedback_to_deploy_s, 3),
        "unit": "seconds",
        "deployed": deployed,
        "gate_eval_s": round(gate_eval_s, 3),
        "fine_tune_s": round(decision.get("fine_tune_s", 0.0), 3),
        "rollback_mttr_s": round(verdict.get("mttr_s", 0.0), 4),
        "rollback_detect_to_restore_s": round(rollback_wall_s, 3),
        "rolled_back": bool(verdict.get("rolled_back")),
        "platform": jax.devices()[0].platform,
        "records": int(FEEDBACK_RECORDS),
        "spool_records_total": int(spool_records),
        "gate_decision": decision.get("gate", {}).get("reason"),
        "note": ("CPU form of the closed loop: spool→round→fine-tune→"
                 "gate→verified hot-swap, then an injected error burst "
                 "under DeployWatch; real-HW numbers scale with model "
                 "size, not loop overhead"),
    }


if __name__ == "__main__":
    from deeplearning4j_tpu.config import place_compile_cache
    place_compile_cache()
    print(json.dumps(main()))
    sys.exit(0)
