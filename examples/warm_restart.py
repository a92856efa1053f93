"""Warm restarts: deploy → kill the server → restart → serve in
milliseconds from the compiled-artifact store.

Demonstrates ISSUE 12 (docs/fault_tolerance.md "Warm restarts",
docs/serving.md "Warm restarts"):

1. train a model, save it through the durable serializer, and **bake**
   its compiled serve program into the zip
   (``artifact_store.ensure_zip_artifacts`` — what
   ``ModelRegistry.deploy(bake_artifacts=True)`` and the online gate's
   pre-flip hook do);
2. "run a server and kill it": a subprocess deploys the zip and answers
   one request — first COLD (a copy of the zip with the artifacts
   stripped: the first request pays live XLA compilation), then WARM
   (the baked zip: the restarted process deserializes the executable
   and serves with **zero JIT on the request path**);
3. print the restart → first-response latency before/after.

A restart must be a real process event — an in-process "restart" would
be answered from warm jit caches and lie — so each measurement runs in
a fresh interpreter.

Run: ``python -m examples.warm_restart``
"""

import json
import os
import subprocess
import sys
import tempfile
import zipfile

import numpy as np

N_IN, N_CLASSES = 24, 4
BUCKET = 8

# one restarted server: deploy the zip, answer one request, report
# timings and the zero-JIT evidence
_SERVE_ONCE = r"""
import json, os, sys, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["DL4J_TPU_COSTMODEL"] = "0"
import numpy as np
from deeplearning4j_tpu.obs.registry import get_registry
from deeplearning4j_tpu.serve import ModelRegistry
zip_path, n_in, bucket = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
x = np.zeros((bucket, n_in), np.float32)
t0 = time.perf_counter()
registry = ModelRegistry(max_batch=bucket, buckets=(bucket,))
entry = registry.deploy("m", zip_path)
ready_s = time.perf_counter() - t0
out = np.asarray(registry.predict("m", x, timeout_s=300))
total_s = time.perf_counter() - t0
print(json.dumps({"ready_s": round(ready_s, 4),
                  "first_response_s": round(total_s - ready_s, 4),
                  "total_s": round(total_s, 4),
                  "compiled_programs": entry.engine.compiled_programs,
                  "warm_programs": entry.engine.warm_programs,
                  "artifact_rejects": get_registry().counter(
                      "tpudl_compile_artifact_rejects_total").value,
                  "classes": int(out.shape[-1])}))
registry.close()
"""


def _trained_net(seed=7):
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterators import ListDataSetIterator
    from deeplearning4j_tpu.nn import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.train import Adam
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-2))
            .list()
            .layer(DenseLayer(n_out=48, activation="relu"))
            .layer(DenseLayer(n_out=48, activation="relu"))
            .layer(OutputLayer(n_out=N_CLASSES, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(N_IN)).build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(128, N_IN)).astype(np.float32)
    y = np.eye(N_CLASSES, dtype=np.float32)[rng.integers(0, N_CLASSES, 128)]
    batches = [DataSet(x[i:i + 16], y[i:i + 16]) for i in range(0, 128, 16)]
    net.fit(ListDataSetIterator(batches), epochs=1)
    return net


def _strip_artifacts(src, dst):
    """A copy of the zip WITHOUT its artifact store (the pre-ISSUE-12
    deployable) — written through the durable writer so the manifest
    stays consistent."""
    from deeplearning4j_tpu.resilience.checkpoint import (
        MANIFEST_NAME, write_checkpoint_zip)
    entries = {}
    with zipfile.ZipFile(src) as zf:
        for name in zf.namelist():
            if name != MANIFEST_NAME and not name.startswith("artifacts/"):
                entries[name] = zf.read(name)
    write_checkpoint_zip(dst, entries)


def _serve_once(zip_path):
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "DL4J_TPU_COSTMODEL": "0",
           "PYTHONPATH": repo_root + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    proc = subprocess.run(
        [sys.executable, "-c", _SERVE_ONCE, zip_path, str(N_IN),
         str(BUCKET)],
        capture_output=True, text=True, timeout=600, env=env)
    if proc.returncode != 0:
        raise RuntimeError(f"server process failed rc={proc.returncode}:\n"
                           f"{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main(workdir=None, verbose=True):
    from deeplearning4j_tpu.train import artifact_store

    def say(*args):
        if verbose:
            print(*args)

    workdir = workdir or tempfile.mkdtemp(prefix="tpudl_warm_restart_")
    warm_zip = os.path.join(workdir, "model.zip")
    cold_zip = os.path.join(workdir, "model_noartifacts.zip")

    say("== train + deploy-time bake")
    net = _trained_net()
    net.save(warm_zip)
    baked = artifact_store.ensure_zip_artifacts(warm_zip, net=net,
                                                buckets=(BUCKET,))
    say(f"   baked {baked} serve program(s) into "
        f"{os.path.basename(warm_zip)}")
    _strip_artifacts(warm_zip, cold_zip)

    say("== kill the server, restart COLD (no artifact store)")
    cold = _serve_once(cold_zip)
    say(f"   restart -> first response: {cold['total_s'] * 1e3:.0f} ms "
        f"(first request waited {cold['first_response_s'] * 1e3:.0f} ms "
        f"on live XLA compile; {cold['compiled_programs']} program "
        f"traced)")

    say("== kill the server, restart WARM (artifact store in the zip)")
    warm = _serve_once(warm_zip)
    say(f"   restart -> first response: {warm['total_s'] * 1e3:.0f} ms "
        f"(first request waited {warm['first_response_s'] * 1e3:.0f} ms; "
        f"{warm['compiled_programs']} programs traced, "
        f"{warm['warm_programs']} served from the store)")

    result = {
        "cold": cold, "warm": warm,
        "restart_speedup": round(cold["total_s"]
                                 / max(warm["total_s"], 1e-9), 2),
        "first_response_speedup": round(
            cold["first_response_s"]
            / max(warm["first_response_s"], 1e-9), 2),
        "zero_jit_after_warm": warm["compiled_programs"] == 0
        and warm["warm_programs"] >= 1,
    }
    say(f"== warm restart {result['restart_speedup']}x faster end to end, "
        f"first response {result['first_response_speedup']}x faster, "
        f"zero JIT on the request path: {result['zero_jit_after_warm']}")
    return result


if __name__ == "__main__":
    main()
