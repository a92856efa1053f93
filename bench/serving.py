#!/usr/bin/env python
"""CPU micro-bench: batch-1 sequential serving vs dynamic micro-batching.

Measures the serve subsystem's two effects without a TPU:

* **throughput/latency** — 16 closed-loop clients each issue ragged
  requests (1–4 rows).  Sequential mode answers each request with its
  own ``net.output`` call (one dispatch per request); dynamic mode
  routes the same traffic through ``serve.InferenceEngine``, which
  coalesces concurrent requests into deadline-bounded micro-batches —
  fewer, larger dispatches → higher requests/sec and a far tighter p99.
* **recompile guard** — the ragged sizes compile one XLA program per
  DISTINCT request shape on the sequential path, *during* serving (the
  p99 cliffs); the engine's bucket set is finite and precompiled up
  front, so ragged traffic never compiles on the serving path.
* **load_sweep (ISSUE 13)** — closed-loop offered load rising ~10x
  against one router-managed model while the queue-depth autoscaler
  grows replicas 1→4, with one fan-out hot-swap and one all-replica
  rollback landing under load: p99 held within 2x of the 1x baseline,
  zero dropped or garbled responses (own subprocess, like cold_start).

Run standalone (``python bench/serving.py``); pinned to
``JAX_PLATFORMS=cpu`` unless the variable is set.  Prints ONE json line
that names the platform it ran on.
"""

import json
import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

N_CLIENTS = 16
REQS_PER_CLIENT = 15
N_FEATURES = 512
HIDDEN = 512
CLASSES = 16
MAX_ROWS = 4          # ragged request sizes 1..MAX_ROWS


def _build_net(hidden=HIDDEN, depth=1, n_features=N_FEATURES, seed=7):
    from deeplearning4j_tpu.nn import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.train import Sgd
    builder = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(0.1))
               .list())
    for _ in range(depth):
        builder = builder.layer(DenseLayer(n_out=hidden, activation="relu"))
    conf = (builder
            .layer(OutputLayer(n_out=CLASSES, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(n_features)).build())
    return MultiLayerNetwork(conf).init()


def _requests(seed=0):
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, MAX_ROWS + 1, N_CLIENTS * REQS_PER_CLIENT)
    return [rng.normal(size=(int(n), N_FEATURES)).astype(np.float32)
            for n in sizes]


def _percentiles(latencies):
    ordered = sorted(latencies)

    def pick(q):
        return ordered[min(len(ordered) - 1, int(q * len(ordered)))]

    return {"p50_ms": round(1e3 * pick(0.50), 3),
            "p99_ms": round(1e3 * pick(0.99), 3)}


def _run_clients(answer, reqs):
    """Closed-loop load: N_CLIENTS threads, each waits for its previous
    answer before sending the next request."""
    latencies = []
    lock = threading.Lock()
    chunks = [reqs[i::N_CLIENTS] for i in range(N_CLIENTS)]

    def client(mine):
        for x in mine:
            t1 = time.perf_counter()
            answer(x)
            dt = time.perf_counter() - t1
            with lock:
                latencies.append(dt)

    threads = [threading.Thread(target=client, args=(c,)) for c in chunks]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    return latencies, wall


def bench_sequential(net, reqs):
    from deeplearning4j_tpu.train import step_cache
    # warm the smallest shape only — recompiles for the OTHER ragged
    # shapes land in the measured pass (that is the story)
    np.asarray(net.output(reqs[0]))
    from deeplearning4j_tpu.obs import costmodel
    costmodel.drain()   # warm shape's background analysis out of the region
    lat, wall = _run_clients(lambda x: np.asarray(net.output(x)), reqs)
    return {"requests_per_s": round(len(reqs) / wall, 1),
            **_percentiles(lat),
            "compiled_programs": step_cache.jit_cache_entries(
                net._output_fn)}


def bench_dynamic(net, reqs, name="bench"):
    from deeplearning4j_tpu.serve import InferenceEngine
    engine = InferenceEngine(net, name=name, max_batch=32,
                             max_latency_ms=1.0, buckets=(8, 16, 32),
                             queue_limit=4 * N_CLIENTS)
    try:
        # the production state: the WHOLE bucket set is precompilable up
        # front (that is the point of bounded buckets) — ragged traffic
        # then never compiles.  The sequential path has no equivalent:
        # every distinct request shape is a cold compile.
        rng = np.random.default_rng(1)
        width = reqs[0].shape[1]
        for bucket in engine.buckets:
            engine.predict(rng.normal(size=(bucket, width))
                           .astype(np.float32), timeout_s=120)
        from deeplearning4j_tpu.obs import costmodel
        costmodel.drain()   # bucket analyses (and sequential's leftovers)
        lat, wall = _run_clients(
            lambda x: engine.predict(x, timeout_s=120), reqs)
        return {"requests_per_s": round(len(reqs) / wall, 1),
                **_percentiles(lat),
                "compiled_programs": engine.compiled_programs,
                "buckets_touched": list(engine.buckets)}
    finally:
        engine.shutdown()


def bench_quantized():
    """ISSUE 11: the quantized-serve row — ONE ragged closed-loop
    traffic mix (its own, weight-bound: chunkier/wider than the
    headline rows') through a bf16 engine and an int8-quantized engine
    of the same architecture (int8 weights via ``nn.quantize``,
    activations bf16, dequant fused into the matmul).  On TPU the int8
    win is HBM bytes (weights stream 1 byte/param); on this CPU rig the
    same program graph wins because XLA's bf16 dot is slower than the
    int8-widening dot — either way the row is req/s + p99, int8 vs
    bf16, plus the cost-model stamps showing the int8 program's higher
    arithmetic intensity (cost_analysis counts the int8 param bytes)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.config import DTypePolicy, set_dtype_policy
    from deeplearning4j_tpu.nn import quantize
    from deeplearning4j_tpu.obs import costmodel

    # serving-deployment policy: weights SHIP as bf16 (param_dtype
    # bf16 — no per-call f32→bf16 weight convert; inference holds no
    # optimizer state, so the train-side reason for f32 params is moot)
    set_dtype_policy(DTypePolicy(param_dtype=jnp.bfloat16,
                                 compute_dtype=jnp.bfloat16,
                                 output_dtype=jnp.bfloat16))
    try:
        # weight-bound config (wider + deeper than the headline row, and
        # chunkier requests): serving cost is dominated by running the
        # weight matrices, which is the regime the int8 path exists for —
        # with a ~1 ms forward the batcher's deadline flush would drown
        # the per-dispatch difference in scheduler noise
        width = 1024
        net = _build_net(hidden=width, depth=6, n_features=width)
        rng = np.random.default_rng(5)
        sizes = rng.integers(4, 17, N_CLIENTS * 20)
        reqs = [rng.normal(size=(int(n), width)).astype(np.float32)
                for n in sizes]
        calib = [reqs[0], reqs[1]]
        qnet = quantize.quantize_net(net, calibration=calib)
        report = qnet.quantization_
        bf16 = bench_dynamic(net, reqs, name="bench_bf16")
        int8 = bench_dynamic(qnet, reqs, name="bench_int8")
        # stamp pass: the engines' background analyses race the traffic
        # (a duplicate XLA compile competing with 16 client threads may
        # land only after the run ends, and an un-redispatched bucket
        # never observes) — so stamp each variant's program
        # synchronously through the step-cached forward, one fixed
        # bucket, analysis + one fenced measured call
        costmodel.drain()
        import time as _time

        import jax.numpy as jnp
        from deeplearning4j_tpu.serve import InferenceEngine
        kind = "serve_forward:MultiLayerNetwork"
        bucket = 32
        xpad = np.zeros((bucket, width), np.float32)
        for model, suffix in ((net, ""), (qnet, ":int8")):
            eng = InferenceEngine(model, name="stamp", max_batch=bucket,
                                  buckets=(bucket,), max_latency_ms=0.5)
            try:
                eng.predict(xpad, timeout_s=120)       # warm the trace
                fwd = eng._fwd
                args = (model.params_, model.state_, jnp.asarray(xpad),
                        None)
                sigk = ("stamp", suffix)
                if costmodel.should_analyze(fwd, sig=sigk):
                    costmodel.analyze_jitted(
                        fwd, costmodel.abstractify(args),
                        kind=kind + suffix, sig=sigk)
                t0 = _time.perf_counter()
                np.asarray(fwd(*args))                 # fenced measure
                costmodel.observe_step(fwd, _time.perf_counter() - t0,
                                       sig=sigk)
            finally:
                eng.shutdown()
        perf_bf16 = costmodel.bench_detail(kind=kind) or {}
        perf_int8 = costmodel.bench_detail(kind=kind + ":int8") or {}
        ai_bf16 = perf_bf16.get("arith_intensity")
        ai_int8 = perf_int8.get("arith_intensity")
        speedup = round(int8["requests_per_s"]
                        / max(bf16["requests_per_s"], 1e-9), 2)
        return {
            "bf16": bf16,
            "int8": int8,
            "speedup": speedup,
            "p99_ratio": round(int8["p99_ms"] / max(bf16["p99_ms"], 1e-9),
                               2),
            "wins": bool(speedup >= 1.3
                         or int8["p99_ms"] < bf16["p99_ms"]),
            "arith_intensity_bf16": ai_bf16,
            "arith_intensity_int8": ai_int8,
            "intensity_gain": (round(ai_int8 / ai_bf16, 2)
                               if ai_bf16 and ai_int8 else None),
            "quantization": report.to_dict(),
            "note": ("same traffic, same architecture; int8 weights + "
                     "bf16 activations vs bf16 end-to-end — the int8 "
                     "program streams 1 byte/weight (see "
                     "arith_intensity_int8 vs _bf16 from "
                     "xla_cost_analysis)"),
        }
    finally:
        set_dtype_policy(DTypePolicy.f32())


# ------------------------------------------------------------ load sweep
SWEEP_WIDTH = 1024       # weight-heavy forward (~10ms/dispatch on CPU):
SWEEP_DEPTH = 6          # one replica saturates, so scaling is visible
SWEEP_POOL = 32          # oracle input rows (requests slice into these)
SWEEP_MAX_ROWS = 4


def _sweep_stage(registry, router, x_pool, clients, reqs_per_client,
                 mid_stage=None):
    """One closed-loop load stage: ``clients`` threads, each waiting
    for its previous answer before the next request (offered load
    scales with the client count).  Every response is checked later
    against the per-version oracles; sheds are counted by lane.
    ``mid_stage`` (the fan-out swap / rollback hook) fires once while
    the clients are in full flight."""
    from deeplearning4j_tpu.serve import Overloaded
    results, latencies, errors = [], [], []
    sheds = {"interactive": 0, "batch": 0}
    lock = threading.Lock()

    def client(cid):
        rng = np.random.default_rng(1000 + cid)
        lane = "batch" if cid % 4 == 3 else "interactive"
        tenant = "paid" if cid % 2 else "free"
        for req_idx in range(reqs_per_client):
            i = int(rng.integers(0, SWEEP_POOL - SWEEP_MAX_ROWS))
            n = int(rng.integers(1, SWEEP_MAX_ROWS + 1))
            t1 = time.perf_counter()
            try:
                out = registry.predict("m", x_pool[i:i + n], timeout_s=60,
                                       tenant=tenant, lane=lane)
            except Overloaded:
                with lock:
                    sheds[lane] += 1
                continue
            except BaseException as e:   # a DROPPED request — must be 0
                with lock:
                    errors.append(repr(e)[:200])
                continue
            dt = time.perf_counter() - t1
            with lock:
                # latency measures STEADY-STATE closed-loop serving:
                # every client's first round lands on a synchronized
                # burst into an empty queue (an artifact of the stage
                # harness, not of offered load) — answered/garble checks
                # still cover it
                if req_idx > 0:
                    latencies.append(dt)
                results.append((i, n, np.asarray(out)))

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    event = None
    if mid_stage is not None:
        time.sleep(0.15)         # clients are in full flight
        t1 = time.perf_counter()
        event = mid_stage()
        event["duration_ms"] = round(1e3 * (time.perf_counter() - t1), 1)
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    record = {
        "clients": clients,
        "offered": clients * reqs_per_client,
        "answered": len(results),
        "requests_per_s": round(len(results) / max(wall, 1e-9), 1),
        **(_percentiles(latencies) if latencies
           else {"p50_ms": None, "p99_ms": None}),
        "shed_by_lane": dict(sheds),
        "errors": errors,
        "replicas": router.replicas,
    }
    if event is not None:
        record["event"] = event
    return record, results


def bench_load_sweep():
    """ISSUE 13: traffic-scale serving.  Closed-loop offered load rises
    ~10x (2 → 20 clients) against ONE router-managed model while the
    queue-depth autoscaler grows the replica set 1 → 4; mid-sweep the
    deploy plane runs one verified fan-out hot-swap (v1 → v2) and one
    all-replica rollback UNDER load.  Reports req/s, p50/p99, sheds by
    priority lane, and the replica count per stage.  Acceptance: p99 at
    10x offered load held within 2x of the single-replica 1x baseline,
    zero dropped and zero garbled responses through both swap events —
    every answered row must equal one version's oracle output."""
    import tempfile

    from deeplearning4j_tpu.obs import costmodel
    from deeplearning4j_tpu.serve import (AdmissionControl, Autoscaler,
                                          AutoscaleConfig, Lane,
                                          ModelRegistry, ReplicaRouter)
    net1 = _build_net(hidden=SWEEP_WIDTH, depth=SWEEP_DEPTH,
                      n_features=SWEEP_WIDTH, seed=11)
    rng = np.random.default_rng(9)
    # v2 = SAME architecture (same config sha → the fan-out swap shares
    # the step-cached compiled forward: zero recompiles under load),
    # different weights — one fit epoch moves every layer
    net2 = _build_net(hidden=SWEEP_WIDTH, depth=SWEEP_DEPTH,
                      n_features=SWEEP_WIDTH, seed=11)
    from deeplearning4j_tpu.data.iterators import ArrayDataSetIterator
    xs = rng.normal(size=(64, SWEEP_WIDTH)).astype(np.float32)
    ys = np.eye(CLASSES, dtype=np.float32)[
        rng.integers(0, CLASSES, 64)]
    net2.fit(ArrayDataSetIterator(xs, ys, 32), epochs=1)
    x_pool = rng.normal(size=(SWEEP_POOL, SWEEP_WIDTH)).astype(np.float32)
    oracle = {1: np.asarray(net1.output(x_pool)),
              2: np.asarray(net2.output(x_pool))}
    workdir = tempfile.mkdtemp(prefix="tpudl_loadsweep_")
    p1 = os.path.join(workdir, "v1.zip")
    p2 = os.path.join(workdir, "v2.zip")
    net1.save(p1)
    net2.save(p2)

    # engine knobs: the stack defaults (docs/serving.md) — at 1x load
    # latency pays the 5ms batching deadline, under load batches
    # size-flush and the deadline never binds
    registry = ModelRegistry(max_batch=16, queue_limit=24)
    registry.deploy("m", p1)
    router = ReplicaRouter(
        registry, "m", replicas=1, min_replicas=1, max_replicas=4,
        admission=AdmissionControl(
            lanes=[Lane("interactive", 0, shed_at=1.0),
                   Lane("batch", 1, shed_at=0.15)],
            default_lane="interactive"))
    autoscaler = None
    try:
        # warm every bucket once — all replicas share the step-cached
        # forward, so this covers the whole (current and future) fleet
        for bucket in (1, 2, 4, 8, 16):
            router.predict(x_pool[:bucket], timeout_s=120)
        costmodel.drain()
        # replica add/retire cost: the scale-up-in-milliseconds claim,
        # measured (shared compiled forward — a thread and a queue)
        t0 = time.perf_counter()
        router.add_replica()
        add_ms = round(1e3 * (time.perf_counter() - t0), 2)
        router.retire_replica()

        # baseline: 1x offered load, single replica, autoscaler off
        # (enough rounds that its p99 is a percentile, not one outlier)
        baseline, results = _sweep_stage(registry, router, x_pool,
                                         clients=2, reqs_per_client=80)
        all_results = list(results)

        autoscaler = Autoscaler(router, AutoscaleConfig(
            scale_up_at=0.05, scale_down_at=0.01, poll_s=0.01,
            up_cooldown_s=0.01, down_cooldown_s=60.0))
        stages = [baseline]
        # the deploy-plane events land in the RAMP stages (under live
        # load, while the autoscaler is growing the fleet); the 10x
        # stage then measures pure scaled-out serving
        for clients, rpc, mid in (
                (6, 25, lambda: {"fan_out_swap":
                                 router.deploy(p2).version}),
                (12, 20, lambda: {"rollback":
                                  registry.rollback("m").version}),
                (20, 20, None)):
            record, results = _sweep_stage(registry, router, x_pool,
                                           clients, rpc, mid_stage=mid)
            stages.append(record)
            all_results.extend(results)
    finally:
        if autoscaler is not None:
            autoscaler.close()
        registry.close()

    garbled = 0
    for i, n, rows in all_results:
        if not any(np.allclose(rows, oracle[v][i:i + n],
                               rtol=1e-4, atol=1e-4) for v in oracle):
            garbled += 1
    dropped = sum(len(s["errors"]) for s in stages)
    shed_by_lane = {
        lane: sum(s["shed_by_lane"].get(lane, 0) for s in stages)
        for lane in ("interactive", "batch")}
    p99_ratio = (round(stages[-1]["p99_ms"] / baseline["p99_ms"], 2)
                 if stages[-1]["p99_ms"] and baseline["p99_ms"] else None)
    held = bool(p99_ratio is not None and p99_ratio <= 2.0)
    return {
        "metric": "load_sweep_p99_ratio_at_10x_load",
        "value": p99_ratio,
        "offered_load_x": round(stages[-1]["clients"]
                                / baseline["clients"], 1),
        "stages": stages,
        "replicas_per_stage": [s["replicas"] for s in stages],
        "replica_add_ms": add_ms,
        "shed_by_lane": shed_by_lane,
        "p99_held_2x": held,
        "dropped": dropped,
        "garbled": garbled,
        "zero_dropped_or_garbled": bool(dropped == 0 and garbled == 0),
        "wins": bool(held and dropped == 0 and garbled == 0
                     and max(s["replicas"] for s in stages) >= 3),
        "note": ("closed-loop clients against one router-managed model; "
                 "offered load ~10x while the queue-depth autoscaler "
                 "grows replicas (scale-up = a thread + a queue: the "
                 "compiled forward is shared process-wide); one fan-out "
                 "hot-swap and one all-replica rollback land mid-sweep "
                 "under load — every response row must equal one "
                 "version's oracle output"),
    }


_SWEEP_CHILD_FLAG = "--load-sweep-child"


def _spawn_load_sweep():
    """Run the load sweep in a FRESH subprocess: the headline rows
    leave behind compiled programs, drained engines and background
    analysis threads whose scheduler noise lands squarely in a p99
    measurement — the sweep gets the same process isolation the
    cold-start record uses."""
    import subprocess
    here = os.path.abspath(__file__)
    repo_root = os.path.dirname(os.path.dirname(here))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": repo_root + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    proc = subprocess.run(
        [sys.executable, here, _SWEEP_CHILD_FLAG],
        capture_output=True, text=True, timeout=600, env=env)
    if proc.returncode != 0:
        raise RuntimeError(f"load-sweep child failed rc={proc.returncode}: "
                           f"{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


COLD_BUCKET = 16
COLD_WIDTH = 128
COLD_DEPTH = 10        # stacked LSTMs: XLA's slowest-compiling shape
COLD_TIMESTEPS = 32    # per parameter byte — compile dominates restore,
                       # which is the regime every real TPU model is in

_COLD_CHILD_FLAG = "--cold-child"


def _cold_net():
    """The cold-start model: a deep LSTM stack.  Recurrent scans are
    the worst-case XLA compile per weight byte on CPU, which makes the
    restart cost structure match real TPU serving (compile >> weight
    load) at bench-friendly sizes."""
    from deeplearning4j_tpu.nn import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import LSTM, RnnOutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.train import Sgd
    builder = (NeuralNetConfiguration.builder().seed(7).updater(Sgd(0.1))
               .list())
    for _ in range(COLD_DEPTH):
        builder = builder.layer(LSTM(n_out=COLD_WIDTH, activation="tanh"))
    conf = (builder
            .layer(RnnOutputLayer(n_out=CLASSES, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(InputType.recurrent(COLD_WIDTH,
                                                COLD_TIMESTEPS)).build())
    return MultiLayerNetwork(conf).init()


def _cold_child(zip_path):
    """One 'restarted server': deploy the zip and answer ONE request,
    timing restore→ready and ready→first-response.  Runs in its own
    process (a restart is a process event; in-process simulation would
    hit warm jit caches and lie).  Prints one json line."""
    import numpy as np

    from deeplearning4j_tpu.obs.registry import get_registry
    from deeplearning4j_tpu.serve.registry import ModelRegistry
    x = np.zeros((COLD_BUCKET, COLD_TIMESTEPS, COLD_WIDTH), np.float32)
    t0 = time.perf_counter()
    registry = ModelRegistry(max_batch=COLD_BUCKET, buckets=(COLD_BUCKET,))
    entry = registry.deploy("m", zip_path)
    deploy_s = time.perf_counter() - t0
    out = np.asarray(registry.predict("m", x, timeout_s=300))
    total_s = time.perf_counter() - t0
    assert out.shape[0] == COLD_BUCKET
    reg = get_registry()
    print(json.dumps({
        "deploy_s": round(deploy_s, 4),
        "first_response_s": round(total_s - deploy_s, 4),
        "total_s": round(total_s, 4),
        "compiled_programs": entry.engine.compiled_programs,
        "warm_programs": entry.engine.warm_programs,
        "artifacts_loaded": reg.counter(
            "tpudl_compile_artifacts_loaded_total").value,
        "artifact_rejects": reg.counter(
            "tpudl_compile_artifact_rejects_total").value,
    }))
    registry.close()
    return 0


def _spawn_cold_child(zip_path):
    import subprocess
    here = os.path.abspath(__file__)
    repo_root = os.path.dirname(os.path.dirname(here))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           # clean measurement: no background duplicate-compile racing
           # the timed window in either child
           "DL4J_TPU_COSTMODEL": "0",
           # prepend, never overwrite — the parent's PYTHONPATH may
           # carry required shims (multichip.py convention)
           "PYTHONPATH": repo_root + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    proc = subprocess.run(
        [sys.executable, here, _COLD_CHILD_FLAG, zip_path],
        capture_output=True, text=True, timeout=600, env=env)
    if proc.returncode != 0:
        raise RuntimeError(f"cold-start child failed rc={proc.returncode}: "
                           f"{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def bench_cold_start():
    """ISSUE 12: restart → first served response, before/after the
    compiled-artifact store (train/artifact_store).  The same model zip
    is deployed by two fresh subprocesses: COLD (no artifacts — the
    first request pays live XLA compilation) and WARM (the zip carries
    AOT-serialized executables baked at 'deploy time' by the parent —
    the restarted server deserializes and answers with zero JIT on the
    request path).  The children are pinned to the CPU."""
    import tempfile

    from deeplearning4j_tpu.train import artifact_store
    net = _cold_net()
    workdir = tempfile.mkdtemp(prefix="tpudl_coldstart_")
    zip_path = os.path.join(workdir, "model.zip")
    net.save(zip_path)
    cold = _spawn_cold_child(zip_path)
    t0 = time.perf_counter()
    baked = artifact_store.ensure_zip_artifacts(net=net, path=zip_path,
                                                buckets=(COLD_BUCKET,))
    bake_s = time.perf_counter() - t0
    warm = _spawn_cold_child(zip_path)
    speedup = round(cold["total_s"] / max(warm["total_s"], 1e-9), 2)
    first_response_speedup = round(
        cold["first_response_s"] / max(warm["first_response_s"], 1e-9), 2)
    return {
        "metric": "cold_start_restart_to_first_response_s",
        "value": warm["total_s"],
        "cold": cold,
        "warm": warm,
        # restart → first served response end to end (verified restore
        # is common to both sides; the store removes the compile term)
        "speedup": speedup,
        # the request-path story: what the first caller actually waits
        # after the server reports ready — live XLA compile vs a warm
        # dispatch of the deserialized executable
        "first_response_speedup": first_response_speedup,
        "programs_baked": baked,
        "bake_s": round(bake_s, 3),
        "zero_jit_after_warm": bool(warm["compiled_programs"] == 0
                                    and warm["warm_programs"] >= 1),
        "wins": bool(first_response_speedup >= 5.0 and speedup > 1.0),
        "note": ("restart → first served response, measured inside two "
                 "fresh subprocesses deploying the SAME zip; warm path "
                 "deserializes AOT-compiled executables from the "
                 "checkpoint's artifact store instead of compiling on "
                 "first traffic"),
    }


def main():
    import jax
    net = _build_net()
    reqs = _requests()
    sequential = bench_sequential(net, reqs)
    dynamic = bench_dynamic(_build_net(), reqs)
    try:    # int8 vs bf16 through the same engine machinery
        quantized = bench_quantized()
    except Exception as e:   # the headline rows survive a quantize break
        quantized = {"error": f"{type(e).__name__}: {e}"[:200]}
    try:    # restart → first response, cold vs artifact-warmed (ISSUE 12)
        cold_start = bench_cold_start()
    except Exception as e:   # headline rows survive a cold-start break
        cold_start = {"error": f"{type(e).__name__}: {e}"[:200]}
    try:    # 10x load vs replica autoscaling + fan-out swaps (ISSUE 13)
        load_sweep = _spawn_load_sweep()
    except Exception as e:   # headline rows survive a sweep break
        load_sweep = {"error": f"{type(e).__name__}: {e}"[:200]}
    # roofline stamp: the engine's dispatch loop analyzed its compiled
    # forward through cost_analysis and observed per-batch device time,
    # so the serving record self-reports MFU/HBM/intensity
    from deeplearning4j_tpu.obs import costmodel
    costmodel.drain()   # flush any still-queued background analysis
    perf = costmodel.bench_detail() or {}
    out = {
        "metric": "serving_requests_per_s",
        "platform": jax.devices()[0].platform,
        "value": dynamic["requests_per_s"],
        "clients": N_CLIENTS,
        "requests": len(reqs),
        "ragged_rows": [1, MAX_ROWS],
        "sequential": sequential,
        "dynamic": dynamic,
        "quantized": quantized,
        "cold_start": cold_start,
        "load_sweep": load_sweep,
        "mfu": perf.get("mfu"),
        "hbm_util": perf.get("hbm_util"),
        "arith_intensity": perf.get("arith_intensity"),
        "perf": perf,
        "throughput_ratio": round(
            dynamic["requests_per_s"]
            / max(sequential["requests_per_s"], 1e-9), 2),
        "note": ("closed-loop clients on CPU; sequential pays one "
                 "dispatch (and one compile per distinct ragged shape), "
                 "dynamic micro-batching coalesces concurrent requests "
                 "into bucket-padded batches"),
    }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    from deeplearning4j_tpu.config import place_compile_cache
    place_compile_cache()
    if len(sys.argv) > 1 and sys.argv[1] == _COLD_CHILD_FLAG:
        sys.exit(_cold_child(sys.argv[2]))
    if len(sys.argv) > 1 and sys.argv[1] == _SWEEP_CHILD_FLAG:
        print(json.dumps(bench_load_sweep()))
        sys.exit(0)
    sys.exit(main())
