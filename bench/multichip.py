"""Multichip scaling bench — federated telemetry measures the gang.

ROADMAP item 2's explicit deliverable: a multichip bench record that
COMPLETES under timeout and reports per-chip scaling efficiency.  Five
MULTICHIP rounds of the real-pod form died rc=124; this row is the
CPU-runnable form (the same `spawn_local_cluster` gang the tests use —
real multi-process jax.distributed over loopback, CPU-only by
construction), and its numbers come from the telemetry federation
rather than per-process stopwatches:

- a coordinator ``UIServer`` runs in THIS process; every gang member's
  ``RemoteStatsRouter`` (injected via ``spawn_local_cluster``'s
  ``remote_ui``) stamps its steps onto it;
- per-worker throughput = 1 / median federated step time;
- ``per_chip_scaling_efficiency`` = (aggregate N-worker throughput / N)
  / single-worker throughput measured the same way;
- ``straggler_skew`` = max worker median step time / cluster median of
  medians (1.0 = perfectly even gang).

Since the self-healing-gangs PR the record also carries a **recovery**
section: a 2-worker gang runs under the
:class:`~deeplearning4j_tpu.resilience.supervisor.ClusterSupervisor`
with a fault-injected SIGKILL of one worker mid-fit; the supervisor
tears down, respawns from the latest verified checkpoint, and the
record reports the measured ``mttr_s`` (failure detection → first
post-restart federated step), ``steps_replayed`` and
``recovered: true`` — recovery time as a first-class efficiency number.

Since the elastic-device-pool PR the record also carries an **elastic**
section (own subprocess, like the mesh sweep): a grow scenario — one
continuous fit grows dp2→dp4 at an epoch boundary and its post-boundary
losses are diffed against a fixed-dp4 run (the checkpoint-consistency
number) — and a borrow/return scenario — a
:class:`~deeplearning4j_tpu.resilience.arbiter.DevicePoolArbiter` moves
2 chips from a live dp4 trainer to a live serve router and back under
threaded client load, reporting whether serve p99 held, the measured
gang grow-back MTTR, and that zero responses were dropped or garbled.

Prints ONE json line that says ``platform: cpu``.  Env knobs: ``DL4J_TPU_MULTICHIP_WORKERS`` (4),
``DL4J_TPU_MULTICHIP_STEPS`` (16), ``DL4J_TPU_MULTICHIP_PORT`` (24211),
``DL4J_TPU_MULTICHIP_RECOVERY_STEPS`` (8).
"""

import functools
import json
import os
import sys

# the gang children unpickle the worker fn by module path: make this
# file importable as `multichip` in the children too (the established
# tests/cluster_workers.py pattern)
_HERE = os.path.dirname(os.path.abspath(__file__))
if _HERE not in sys.path:
    sys.path.insert(0, _HERE)
_REPO = os.path.dirname(_HERE)
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def train_worker(pid, n, steps=16):
    """One gang member: train a small MLP for ``steps`` steps; every
    step stamps onto the coordinator via the env-injected router (the
    launcher bootstraps it — no telemetry code here)."""
    import numpy as np
    import jax
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.nn import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.train import Sgd
    from deeplearning4j_tpu.train.trainer import Trainer

    conf = (NeuralNetConfiguration.builder().seed(7 + pid)
            .updater(Sgd(0.05)).list()
            .layer(DenseLayer(n_out=64, activation="tanh"))
            .layer(OutputLayer(n_out=5, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(16)).build())
    net = MultiLayerNetwork(conf).init()
    trainer = Trainer(net)
    rng = np.random.default_rng(pid)
    x = rng.normal(size=(64, 16)).astype(np.float32)
    y = np.eye(5, dtype=np.float32)[rng.integers(0, 5, 64)]
    batch = DataSet(x, y)
    key = jax.random.key(pid)
    for _ in range(steps):
        key, sub = jax.random.split(key)
        trainer.step_batch(batch, sub)
    return {"pid": pid, "steps": steps}


def recovery_worker(pid, n, steps=8, workdir=None, kill_at=None):
    """Supervised gang member for the recovery record: fit over a
    ResumableIterator with per-iteration-pair checkpoints; in generation
    0 the LAST worker SIGKILLs itself mid-fit (faults ``kill`` action —
    real, uncatchable process death).  Respawned generations resume from
    their own verified checkpoints via the supervisor-injected
    ``DL4J_TPU_RESUME_FROM``."""
    import os
    import numpy as np
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterators import (ListDataSetIterator,
                                                   ResumableIterator)
    from deeplearning4j_tpu.io.checkpoint import CheckpointListener
    from deeplearning4j_tpu.nn import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.resilience import faults, supervisor
    from deeplearning4j_tpu.train import Sgd
    from deeplearning4j_tpu.train.trainer import Trainer

    generation = int(os.environ.get(supervisor.GENERATION_ENV, "0"))
    if kill_at is None:
        kill_at = max(2, steps - 2)
    if generation == 0 and pid == n - 1:
        # the chaos: REAL SIGKILL before step kill_at commits — only in
        # the first generation (the supervisor also strips the env fault
        # plan on respawn; this programmatic plan is gated here)
        faults.install_fault_plan(
            faults.FaultPlan.parse(f"trainer.step@{kill_at}:kill"))

    conf = (NeuralNetConfiguration.builder().seed(19 + pid)
            .updater(Sgd(0.05)).list()
            .layer(DenseLayer(n_out=32, activation="tanh"))
            .layer(OutputLayer(n_out=5, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(16)).build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(37 + pid)
    x = rng.normal(size=(steps * 16, 16)).astype(np.float32)
    y = np.eye(5, dtype=np.float32)[rng.integers(0, 5, steps * 16)]
    batches = [DataSet(x[i:i + 16], y[i:i + 16])
               for i in range(0, steps * 16, 16)]
    iterator = ResumableIterator(ListDataSetIterator(batches))
    ckpt_dir = os.path.join(workdir, f"w{pid}")
    ckpt = CheckpointListener(ckpt_dir, save_every_n_iterations=2,
                              keep_last=3, iterator=iterator)
    resume = os.environ.get(supervisor.RESUME_ENV)
    trainer = Trainer(net, listeners=[ckpt])
    trainer.fit(iterator, epochs=1,
                resume_from=(ckpt_dir if resume else None))
    return {"pid": pid, "generation": generation,
            "iteration": net.iteration}


def _run_recovery(server, steps, port, workdir):
    """The recovery row: a supervised 2-worker gang with an injected
    SIGKILL; returns measured MTTR + steps replayed."""
    from deeplearning4j_tpu.obs.remote import ClusterStore
    from deeplearning4j_tpu.resilience.supervisor import ClusterSupervisor
    server.cluster = ClusterStore()
    import multichip as _self
    fn = functools.partial(_self.recovery_worker, steps=steps,
                           workdir=workdir)
    sup = ClusterSupervisor(
        fn, n_processes=2, checkpoint_dir=workdir, max_restarts=2,
        port=port, timeout=300.0, remote_ui=server.url,
        cluster_store=server.cluster,
        extra_env={"PYTHONPATH": _HERE + os.pathsep
                   + os.environ.get("PYTHONPATH", "")})
    run = sup.run()
    incident = run.incidents[0] if run.incidents else None
    return {
        "recovered": bool(run.incidents) and len(run.results) == 2,
        "restarts": len(run.incidents),
        "generations": run.generations,
        "mttr_s": (None if incident is None or incident.mttr_s is None
                   else round(incident.mttr_s, 3)),
        "steps_replayed": (None if incident is None
                           else incident.steps_replayed),
        "reason": None if incident is None else incident.reason,
        "note": ("2-worker supervised gang; one worker SIGKILLed "
                 "mid-fit by the fault harness, gang respawned from "
                 "the latest verified checkpoint; mttr_s = detection "
                 "to first post-restart federated step"),
    }


def mesh_sweep_main():
    """ISSUE-14 deliverable: the SAME model stepped under several
    composable layouts on one host's 8-device virtual CPU mesh —
    measured steps/s per layout, the analytic per-step collective-bytes
    estimate from ``MeshLayout.collective_bytes_per_step``, and
    per-layout arithmetic intensity pulled from the compiled program's
    XLA cost_analysis (the PR-6 cost model; collectives show up as
    bytes, so layout choices move the measured intensity).  Runs
    in-process — ``main()`` launches it as a subprocess with the forced
    device count so the gang runs above keep their 1-device children.
    Prints ONE json line."""
    import time

    import numpy as np
    import jax

    from deeplearning4j_tpu.config import set_config
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.nn import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.obs import costmodel
    from deeplearning4j_tpu.train import Sgd
    from deeplearning4j_tpu.train.trainer import Trainer

    layouts = [s for s in os.environ.get(
        "DL4J_TPU_MESH_SWEEP_LAYOUTS",
        "dp4,tp4,dp2xtp2,dp2xpp2").split(",") if s]
    steps = int(os.environ.get("DL4J_TPU_MESH_SWEEP_STEPS", "10"))
    width, hidden, classes, batch = 64, 256, 8, 64
    set_config(device_feed=False)   # direct fit_batch loop, no feeder thread

    def build_net():
        conf = (NeuralNetConfiguration.builder().seed(31)
                .updater(Sgd(0.05)).list()
                .layer(DenseLayer(n_out=hidden, activation="relu"))
                .layer(DenseLayer(n_out=hidden, activation="tanh"))
                .layer(OutputLayer(n_out=classes, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(width)).build())
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(5)
    x = rng.normal(size=(batch, width)).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[rng.integers(0, classes, batch)]
    batch_ds = DataSet(x, y)

    def run(layout):
        net = build_net()
        mb = 2 if layout and "pp" in layout else 1
        trainer = Trainer(net, layout=layout, n_microbatches=mb)
        key = jax.random.key(11)
        for _ in range(2):      # compile + settle
            key, sub = jax.random.split(key)
            jax.block_until_ready(trainer.fit_batch(batch_ds, sub))
        t0 = time.perf_counter()
        for _ in range(steps):
            key, sub = jax.random.split(key)
            loss = trainer.fit_batch(batch_ds, sub)
        jax.block_until_ready(loss)
        dt = (time.perf_counter() - t0) / steps
        row = {"steps_per_s": round(1.0 / dt, 3),
               "step_ms": round(dt * 1e3, 3)}
        stamp = None
        if trainer._bake_args is not None:
            stamp = costmodel.measure(trainer._step, trainer._bake_args,
                                      dt, kind=f"train:{layout or 'single'}")
        if stamp:
            row.update({k: stamp[k] for k in
                        ("arith_intensity", "flops_per_step",
                         "bytes_per_step", "roofline_bound")
                        if k in stamp})
        if trainer._layout is not None:
            param_bytes = sum(
                int(l.size) * l.dtype.itemsize
                for l in jax.tree_util.tree_leaves(net.params_)
                if hasattr(l, "size"))
            act_bytes = batch * hidden * 4
            row["collective_bytes_per_step"] = \
                trainer._layout.collective_bytes_per_step(param_bytes,
                                                          act_bytes)
            row["collective_bytes_source"] = "analytic_estimate"
            row["layout"] = trainer._layout.describe()
        return row

    baseline = run(None)
    rows = {}
    for layout in layouts:
        try:
            rows[layout] = run(layout)
        except Exception as e:   # a layout that cannot build on this host
            rows[layout] = {"error": f"{type(e).__name__}: {str(e)[:160]}"}
    print(json.dumps({
        "metric": "mesh_layout_sweep",
        "platform": jax.devices()[0].platform,
        "value": max((r.get("steps_per_s") or 0.0) for r in rows.values()),
        "unit": "steps_per_s",
        "model": f"mlp_{width}x{hidden}x{hidden}x{classes}",
        "batch": batch,
        "steps_timed": steps,
        "single_device": baseline,
        "layouts": rows,
        "note": ("same model, same batches, one unified mesh — layouts "
                 "selected via Trainer(layout=...); steps/s measured "
                 "after compile, arith intensity from XLA cost_analysis "
                 "of each layout's compiled step, collective bytes from "
                 "the MeshLayout analytic model (virtual CPU devices: "
                 "relative layout cost, not TPU wall time)"),
    }))
    return 0


def elastic_main():
    """The elastic-device-pool record (ISSUE 19).  Two scenarios on the
    forced 8-device virtual CPU mesh, in-process:

    - **grow**: the SAME model/data/seed run twice — fixed dp4, and
      dp2 growing to dp4 at an epoch boundary inside one continuous fit
      (dropout active, width-invariant partitionable RNG).  Reports the
      max post-boundary per-step loss delta: the checkpoint-consistent
      reshard makes it ~0.
    - **arbiter**: a DevicePoolArbiter borrows 2 chips from a live dp4
      trainer for a live serve router under threaded client load, then
      returns them; reports serve p99 steady vs during the flips, the
      gang grow-back MTTR, and zero dropped/garbled responses.

    Prints ONE json line."""
    import tempfile
    import threading
    import time

    import jax
    import numpy as np

    from deeplearning4j_tpu.data.iterators import ArrayDataSetIterator
    from deeplearning4j_tpu.nn import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.resilience.arbiter import (DevicePoolArbiter,
                                                       TrainerGang)
    from deeplearning4j_tpu.serve import ModelRegistry, ReplicaRouter
    from deeplearning4j_tpu.train import Sgd
    from deeplearning4j_tpu.train.trainer import Trainer

    def mlp(seed=11, dropout=0.8):
        conf = (NeuralNetConfiguration.builder().seed(seed)
                .updater(Sgd(0.1)).weight_init("xavier").list()
                .layer(DenseLayer(n_out=16, activation="relu",
                                  dropout=dropout))
                .layer(DenseLayer(n_out=16, activation="tanh",
                                  dropout=dropout))
                .layer(OutputLayer(n_out=4, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(8)).build())
        return MultiLayerNetwork(conf)

    rng = np.random.default_rng(0)
    x = rng.normal(size=(32, 8)).astype(np.float32)
    w = rng.normal(size=(8, 4)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[np.argmax(x @ w, -1)]
    epochs, boundary = 4, 2

    def run(start, resize_to=None):
        net = mlp()
        trainer = Trainer(net, layout=start)
        losses = []

        class Rec:
            def iteration_done(self, net, it, ep, loss):
                losses.append(float(loss))

            def on_epoch_end(self, net, epoch, info):
                if resize_to is not None and epoch + 1 == boundary:
                    trainer.request_resize(resize_to)

        trainer.bus.listeners.append(Rec())
        trainer.fit(ArrayDataSetIterator(x, y, 16, shuffle=False),
                    epochs=epochs)
        return losses, trainer

    fixed_losses, _ = run("dp4")
    elastic_losses, trainer = run("dp2", resize_to=4)
    cut = boundary * (len(fixed_losses) // epochs)
    delta = max(abs(a - b) for a, b in
                zip(elastic_losses[cut:], fixed_losses[cut:]))
    grow = {
        "from_width": 2, "to_width": 4, "resize_epoch": boundary,
        "post_boundary_max_loss_delta": float(f"{delta:.3e}"),
        "matches_fixed_width": bool(delta <= 1e-6),
        "final_layout": trainer._layout.describe(),
        "note": ("one continuous fit grows dp2->dp4 at the epoch "
                 "boundary; post-boundary per-step losses diffed "
                 "against a fixed-dp4 run (dropout active)"),
    }

    # ----- borrow/return under live serve load
    workdir = tempfile.mkdtemp(prefix="dl4j_tpu_elastic_")
    snet = mlp(seed=23, dropout=None).init()
    path = os.path.join(workdir, "serve.zip")
    snet.save(path)
    models = ModelRegistry(max_batch=8, max_latency_ms=2, queue_limit=64)
    models.deploy("m", path)
    router = ReplicaRouter(models, "m", replicas=2, max_replicas=4)
    trainer = Trainer(mlp(), layout="dp4")
    it = ArrayDataSetIterator(x, y, 16, shuffle=False)
    trainer.fit(it, epochs=1)
    arb = DevicePoolArbiter(router, TrainerGang(trainer), min_train=2,
                            chips_per_flip=2, cooldown_s=0.0, serve_chips=2)
    xs = x[:8]
    expected = np.asarray(snet.output(xs))
    stop, errors, lat = threading.Event(), [], []

    def client():
        while not stop.is_set():
            t = time.perf_counter()
            try:
                out, _ = models.predict_versioned("m", xs, timeout_s=30)
            except Exception as e:
                errors.append(repr(e)[:200])
                return
            lat.append(time.perf_counter() - t)
            if not np.allclose(out, expected, rtol=1e-5, atol=1e-6):
                errors.append("garbled response")
                return

    def p99(samples):
        s = sorted(samples) or [0.0]
        return s[int(0.99 * (len(s) - 1))]

    for _ in range(3):                   # compile + settle the engine
        models.predict_versioned("m", xs, timeout_s=30)
    threads = [threading.Thread(target=client) for _ in range(3)]
    for th in threads:
        th.start()
    deadline = time.monotonic() + 5.0    # steady-state sample
    while len(lat) < 30 and time.monotonic() < deadline:
        time.sleep(0.02)
    n0, p99_steady = len(lat), p99(lat)
    borrowed = arb.borrow()
    trainer.fit(it, epochs=1)            # shrink lands at the boundary
    width_during = trainer._layout.spec.total()
    t_return = time.perf_counter()
    returned = arb.return_chips()
    trainer.fit(it, epochs=1)            # ... grow-back too
    mttr_s = time.perf_counter() - t_return
    stop.set()
    for th in threads:
        th.join(timeout=30)
    p99_flips = p99(lat[n0:])
    arbiter = {
        "borrowed": bool(borrowed), "returned": bool(returned),
        "width_during_borrow": width_during,
        "width_restored": trainer._layout.spec.total() == 4,
        "pool": arb.snapshot(),
        "served": len(lat),
        "zero_dropped_or_garbled": not errors,
        "errors": errors[:3],
        "serve_p99_ms_steady": round(p99_steady * 1e3, 3),
        "serve_p99_ms_during_flips": round(p99_flips * 1e3, 3),
        "p99_held": bool(not errors
                         and p99_flips <= max(p99_steady * 5, 0.25)),
        "grow_back_mttr_s": round(mttr_s, 3),
        "note": ("2 chips borrowed from a live dp4 trainer for the "
                 "serve router and returned under 3 threaded clients; "
                 "mttr_s = return_chips() to the gang trained back at "
                 "dp4 (includes the boundary epoch + reshard)"),
    }
    ok = (grow["matches_fixed_width"] and arbiter["width_restored"]
          and arbiter["zero_dropped_or_garbled"])
    print(json.dumps({
        "metric": "elastic_pool", "value": 1.0 if ok else 0.0,
        "platform": jax.devices()[0].platform,
        "unit": "ok", "grow": grow, "arbiter": arbiter,
    }))
    return 0


def _run_elastic(timeout_s=420.0):
    """Run the elastic record in a subprocess with the forced 8-device
    virtual CPU topology and the width-invariant partitionable RNG (the
    1e-6 grow contract depends on it)."""
    import subprocess
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags = (flags + " --xla_force_host_platform_device_count=8").strip()
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=flags,
               JAX_THREEFRY_PARTITIONABLE="1")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--elastic"],
        capture_output=True, text=True, timeout=timeout_s, env=env)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    if lines:
        return json.loads(lines[-1])
    return {"error": (proc.stderr or "no output")[-300:]}


def _run_mesh_sweep(timeout_s=420.0):
    """Run the sweep in a subprocess with the forced 8-device virtual
    CPU topology (the parent keeps its own device view for the gangs)."""
    import subprocess
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags = (flags + " --xla_force_host_platform_device_count=8").strip()
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=flags)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--mesh-sweep"],
        capture_output=True, text=True, timeout=timeout_s, env=env)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    if lines:
        return json.loads(lines[-1])
    return {"error": (proc.stderr or "no output")[-300:]}


def _fetch_json(url):
    import urllib.request
    with urllib.request.urlopen(url, timeout=5) as resp:
        return json.loads(resp.read())


def _run_gang(server, n_workers, steps, port):
    """One federated gang run; returns the coordinator's summary of it.
    A fresh ClusterStore per run keeps the baseline's telemetry out of
    the N-worker medians."""
    from deeplearning4j_tpu.obs.remote import ClusterStore
    from deeplearning4j_tpu.parallel.launcher import spawn_local_cluster
    server.cluster = ClusterStore()
    # reference the worker through the IMPORTED module, not __main__:
    # the gang children unpickle `multichip.train_worker` via the
    # PYTHONPATH handed to them below
    import multichip as _self
    fn = functools.partial(_self.train_worker, steps=steps)
    spawn_local_cluster(fn, n_processes=n_workers, port=port,
                        timeout=420.0, remote_ui=server.url,
                        extra_env={"PYTHONPATH": _HERE + os.pathsep
                                   + os.environ.get("PYTHONPATH", "")})
    return _fetch_json(server.url + "cluster.json")


def _throughputs(summary):
    """worker → steps/s from the federated median step time (None when a
    worker never reported a measurable median)."""
    out = {}
    for name, w in summary.get("workers", {}).items():
        med = w.get("median_step_ms")
        out[name] = (1e3 / med) if med else None
    return out


def main():
    import tempfile
    n_workers = int(os.environ.get("DL4J_TPU_MULTICHIP_WORKERS", "4"))
    steps = int(os.environ.get("DL4J_TPU_MULTICHIP_STEPS", "16"))
    port = int(os.environ.get("DL4J_TPU_MULTICHIP_PORT", "24211"))
    recovery_steps = int(os.environ.get("DL4J_TPU_MULTICHIP_RECOVERY_STEPS",
                                        "8"))
    from deeplearning4j_tpu.obs.ui_server import UIServer
    server = UIServer(port=0)
    try:
        # single-worker baseline under the IDENTICAL harness (same spawn,
        # same distributed runtime, same telemetry path)
        base_summary = _run_gang(server, 1, steps, port)
        base_tp = [t for t in _throughputs(base_summary).values() if t]
        if not base_tp:
            raise RuntimeError(f"baseline run produced no federated step "
                               f"timings: {base_summary}")
        baseline = base_tp[0]

        gang_summary = _run_gang(server, n_workers, steps, port + 173)
        tps = _throughputs(gang_summary)
        measured = [t for t in tps.values() if t]
        if len(measured) < n_workers:
            raise RuntimeError(f"only {len(measured)}/{n_workers} workers "
                               f"reported step timings: {gang_summary}")
        aggregate = sum(measured)
        efficiency = (aggregate / n_workers) / baseline
        skew = gang_summary.get("straggler_skew") or 1.0

        # the self-healing row: kill-and-heal under the supervisor,
        # measured from the same federated telemetry
        recovery = _run_recovery(server, recovery_steps, port + 391,
                                 tempfile.mkdtemp(prefix="dl4j_tpu_rec_"))
        # the unified-mesh layout sweep (own subprocess: needs the
        # forced 8-device topology the gang children must not inherit)
        try:
            mesh_sweep = _run_mesh_sweep()
        except Exception as e:
            mesh_sweep = {"error": str(e)[:200]}
        # the elastic-pool row (own subprocess: needs the forced
        # 8-device topology AND the partitionable RNG)
        try:
            elastic = _run_elastic()
        except Exception as e:
            elastic = {"error": str(e)[:200]}
        print(json.dumps({
            "metric": "multichip_scaling_efficiency",
            "platform": "cpu",      # spawn_local_cluster pins its gangs
            "value": round(efficiency, 4),
            "unit": "fraction",
            "n_workers": n_workers,
            "steps_per_worker": steps,
            "per_chip_scaling_efficiency": round(efficiency, 4),
            "straggler_skew": round(skew, 4),
            "recovery": recovery,
            "mesh_sweep": mesh_sweep,
            "elastic": elastic,
            "detail": {
                "baseline_steps_per_s": round(baseline, 3),
                "aggregate_steps_per_s": round(aggregate, 3),
                "workers": gang_summary.get("workers", {}),
                "source": "federated_telemetry",
                "note": ("CPU loopback gang (all workers share the host's "
                         "cores, so efficiency < 1 is expected and real); "
                         "throughput = 1/median federated step time per "
                         "worker, scraped from the coordinator's "
                         "/cluster.json"),
            },
        }))
        return 0
    finally:
        server.stop()


if __name__ == "__main__":
    from deeplearning4j_tpu.config import place_compile_cache
    place_compile_cache()
    if "--mesh-sweep" in sys.argv:
        sys.exit(mesh_sweep_main())
    if "--elastic" in sys.argv:
        sys.exit(elastic_main())
    sys.exit(main())
