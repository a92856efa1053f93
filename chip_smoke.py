"""The quickest proof that the system still starts on the chip.

``python chip_smoke.py`` drives the main path once on ONE TPU chip, through
the entry points a user calls, at ResNet-50's full width (224x224, 1000
classes, batch 128, bf16 policy; weights random from a seed):

    child A   train   net.fit over a seeded iterator (3 x 128 + a ragged tail)
              serve   net.save -> ModelRegistry.deploy -> 12 HTTP predicts
              kernels flash attention + int8 matmul, compiled, vs jnp
              bake    the serve programs AOT-serialized into the zip
    child B   warm    a FRESH process deploys that zip and answers the same
                      requests with zero JIT, then takes one train step

``python chip_smoke.py --chips 4`` runs ONLY the cross-chip phase and what
it is compared with: three ``fit_batch`` steps of the same ResNet-50 on one
device and three under ``Trainer(net, layout="dp4")``.

A chip belongs to one process at a time, and a warm restart is by definition
a fresh process, so the parent NEVER imports jax: it starts one child after
the other has exited, each ``python chip_smoke.py --phase ...`` of this file.
A child's first act is to assert that jax found a TPU; a child that exits
non-zero ends the run with that code.  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

There is no option that lets this pass on a CPU.  The CPU rehearsal is
``tests/test_chip_smoke.py``, which calls the phase functions below at tiny
sizes.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
MODEL = "resnet50"
SEED = 22


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the phases run at.  The defaults are the smoke; only the CPU
    test passes others."""

    image: int = 224
    classes: int = 1000
    batch: int = 128
    full_batches: int = 3
    tail: int = 50                       # the ragged last batch of an epoch
    epochs: int = 2
    buckets: tuple = (1, 4, 16)
    # rows of each predict request: every bucket is touched, 12 requests
    requests: tuple = (1, 2, 4, 3, 16, 1, 8, 5, 4, 12, 1, 16)
    flash: tuple = (2, 4096, 768, 12)    # batch, tokens, model width, heads
    int8: tuple = (64, 2048, 2048)       # M, K, N
    dp_steps: int = 3


# ---- stated bands ---------------------------------------------------------
# Served rows vs ``net.output`` on the same rows: the same bf16 forward, but
# compiled at another batch size (a bucket, not 16), so convolutions tile and
# round differently.  Softmax outputs, compared against the largest
# probability of the reference.
SERVE_BAND = 0.05
# flash attention in bf16 against the einsum chain: the band of
# tests/test_pallas.py::test_grads_bf16 (rtol = atol = 0.1).
FLASH_BAND = 0.1
# int8 matmul against its jnp oracle: tests/test_quantize.py (1e-2 of the
# output scale).
INT8_BAND = 1e-2
# per-step loss, one device vs dp4, bf16 compute.  The first step starts
# from identical parameters, so only the cross-device reduction order
# differs; every later step starts from parameters that already differ, and
# momentum at lr 0.1 amplifies that.
DP_FIRST_LOSS_BAND = 0.01
DP_LOSS_BAND = 0.05


def say(phase: str, **facts) -> None:
    print(f"[chip_smoke] {phase} " + json.dumps(facts, sort_keys=True),
          flush=True)


# ---- shared builders --------------------------------------------------------
def _resnet50(sz: Sizes):
    from deeplearning4j_tpu.config import DTypePolicy, set_dtype_policy
    from deeplearning4j_tpu.models import resnet50
    from deeplearning4j_tpu.train import Nesterovs
    set_dtype_policy(DTypePolicy.bf16())
    return resnet50(height=sz.image, width=sz.image, num_classes=sz.classes,
                    updater=Nesterovs(0.1, 0.9))


def _batches(sz: Sizes, n_full: int, tail: int = 0):
    """Seeded synthetic batches, made in bulk."""
    import numpy as np

    from deeplearning4j_tpu.data.dataset import DataSet
    rng = np.random.default_rng(SEED)
    sizes = [sz.batch] * n_full + ([tail] if tail else [])
    n = sum(sizes)
    x = rng.random((n, sz.image, sz.image, 3), dtype=np.float32)
    y = np.eye(sz.classes, dtype=np.float32)[rng.integers(0, sz.classes, n)]
    out, at = [], 0
    for s in sizes:
        out.append(DataSet(x[at:at + s], y[at:at + s]))
        at += s
    return out


def _request_rows(sz: Sizes):
    """The pool of images the predict requests slice, and each slice."""
    import numpy as np
    pool = np.random.default_rng(SEED + 1).random(
        (max(sz.buckets), sz.image, sz.image, 3), dtype=np.float32)
    # three decimals keep a 16-image JSON body to a few MB; the reference
    # sees the same rounded rows
    pool = np.round(pool, 3)
    slices = [(i % (len(pool) - n + 1), n) for i, n in enumerate(sz.requests)]
    return pool, slices


def _counter(name: str) -> float:
    from deeplearning4j_tpu.obs.registry import get_registry
    return get_registry().counter(name).value


def _compile_s() -> float:
    """Seconds this process has spent compiling: tracing, lowering, XLA
    and persistent-cache loads (the program's set-up histograms)."""
    from deeplearning4j_tpu.obs.registry import setup_metrics
    m = setup_metrics()
    return m.trace.sum + m.lower.sum + m.xla.sum + m.cache_load.sum


def _step_text(trainer, batch, *, compiled: bool) -> str:
    """Text of the trainer's own train step for ``batch``: the lowered
    module, or the compiled program (a persistent-cache hit after the
    step has run)."""
    import jax

    from deeplearning4j_tpu.obs import costmodel
    trainer._ensure_ready()
    net = trainer.net
    placed = trainer._place_batch(batch)
    args = costmodel.abstractify(
        (net.params_, net.state_, net.opt_state, placed.features,
         placed.labels, None, placed.labels_mask, jax.random.key(0)))
    lowered = trainer._step.lower(*args)
    return lowered.compile().as_text() if compiled else lowered.as_text()


# ---- phases -----------------------------------------------------------------
class _Losses:
    """Listener: every step's loss, synced, and when it landed."""

    def __init__(self):
        self.losses, self.at = [], []

    def iteration_done(self, net, iteration, epoch, loss):
        self.losses.append(float(loss))
        self.at.append(time.perf_counter())


def phase_train(sz: Sizes):
    """``net.fit`` over a ragged epoch: feeder, bucketing, donating step."""
    import jax
    import numpy as np

    from deeplearning4j_tpu.data.iterators import ListDataSetIterator
    from deeplearning4j_tpu.train.trainer import Trainer
    net = _resnet50(sz)
    net.init()
    before = np.asarray(net.params())
    batches = _batches(sz, sz.full_batches, sz.tail)
    seen = _Losses()
    net.fit(ListDataSetIterator(batches), epochs=sz.epochs, listeners=[seen])
    after = np.asarray(net.params())

    n_steps = sz.epochs * len(batches)
    n_examples = sz.epochs * (sz.full_batches * sz.batch + sz.tail)
    assert len(seen.losses) == n_steps, seen.losses
    assert np.all(np.isfinite(seen.losses)), seen.losses
    assert np.all(np.isfinite(after)) and np.any(after != before), \
        "the parameters did not move"
    assert _counter("tpudl_train_recompiles_total") == 1, \
        _counter("tpudl_train_recompiles_total")
    assert _counter("tpudl_train_examples_total") == n_examples, \
        _counter("tpudl_train_examples_total")
    # the step the benchmark measures: convolutions and BN as XLA fuses
    # them, no Mosaic call
    kernel_in_step = "tpu_custom_call" in _step_text(Trainer(net), batches[0],
                                                     compiled=False)
    assert not kernel_in_step, "tpu_custom_call in the lowered ResNet step"
    # host clock between synced losses; the first interval still drains
    # what queued up behind the compile
    steady = np.diff(seen.at)[1:]
    say("train", losses=seen.losses, steps=n_steps,
        examples=n_examples, recompiles=1,
        compile_s=_compile_s(),
        step_s_median=float(np.median(steady)),
        tpu_custom_call_in_step=kernel_in_step,
        peak_bytes_in_use=(jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use"))
    return net


def _post_predict(port: int, rows):
    import http.client

    import numpy as np
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)
    try:
        conn.request("POST", f"/v1/models/{MODEL}:predict",
                     body=json.dumps({"instances": rows.tolist()}))
        response = conn.getresponse()
        body = json.loads(response.read().decode())
    finally:
        conn.close()
    assert response.status == 200, (response.status, str(body)[:300])
    return np.asarray(body["predictions"], np.float32)


def _get_metrics(port: int) -> str:
    import http.client
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request("GET", "/metrics")
        return conn.getresponse().read().decode()
    finally:
        conn.close()


def _serve_requests(sz: Sizes, zip_path: str):
    """Deploy ``zip_path`` behind the HTTP server and answer the twelve
    requests.  Returns (answers, engine facts, /metrics text)."""
    from deeplearning4j_tpu.serve import ModelRegistry, ModelServer
    pool, slices = _request_rows(sz)
    registry = ModelRegistry(max_batch=max(sz.buckets), buckets=sz.buckets)
    server = None
    try:
        entry = registry.deploy(MODEL, zip_path)
        # the first request of a bucket compiles its forward (cold) or
        # dispatches the deserialized one (warm), well inside this limit
        server = ModelServer(registry, request_timeout_s=600.0)
        t0 = time.perf_counter()
        answers = [_post_predict(server.port, pool[a:a + n])
                   for a, n in slices]
        seconds = time.perf_counter() - t0
        metrics = _get_metrics(server.port)
        facts = {"compiled_programs": entry.engine.compiled_programs,
                 "warm_programs": entry.engine.warm_programs,
                 "requests": len(answers), "seconds": seconds}
    finally:
        if server is not None:
            server.stop()
        registry.close()
    return answers, facts, metrics


def _assert_served_ok(metrics: str, n: int) -> None:
    """``/metrics`` counted ``n`` requests as ok and none as anything else."""
    series = 'tpudl_serve_requests_total{status="'
    by_status = {line[len(series):].split('"')[0]: float(line.split()[-1])
                 for line in metrics.splitlines() if line.startswith(series)}
    not_ok = {k: v for k, v in by_status.items() if k != "ok" and v}
    assert by_status.get("ok") == n and not not_ok, by_status


def _within_band(got, want, band: float) -> float:
    """Max abs difference, asserted under ``band`` of the reference's
    largest magnitude."""
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (got.shape, want.shape)
    assert np.all(np.isfinite(got))
    err = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    assert err <= band * scale, f"max |diff| {err} > {band} x {scale}"
    return err


def phase_serve(sz: Sizes, net, workdir: str) -> str:
    """Save, deploy, twelve HTTP predicts, answers == ``net.output``."""
    import numpy as np
    zip_path = os.path.join(workdir, f"{MODEL}.zip")
    net.save(zip_path)
    pool, slices = _request_rows(sz)
    want = np.asarray(net.output(pool), np.float32)
    answers, facts, metrics = _serve_requests(sz, zip_path)
    errs = [_within_band(got, want[a:a + n], SERVE_BAND)
            for got, (a, n) in zip(answers, slices)]
    _assert_served_ok(metrics, len(slices))
    touched = {min(b for b in sz.buckets if b >= n) for _, n in slices}
    assert facts["compiled_programs"] == len(touched), facts
    np.savez(os.path.join(workdir, "answers.npz"), *answers)
    say("serve", max_abs_diff=max(errs), band=SERVE_BAND,
        reference_max=float(want.max()), buckets_touched=sorted(touched),
        zip_mb=round(os.path.getsize(zip_path) / 2**20, 1), **facts)
    return zip_path


def phase_kernels(sz: Sizes) -> None:
    """The other two Pallas kernels, as the backend runs them, against
    their jnp references."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.nn.quantize import quantize_weight
    from deeplearning4j_tpu.ops.attention import multi_head_attention
    from deeplearning4j_tpu.ops.pallas import flash_attention
    from deeplearning4j_tpu.ops.pallas.quant_matmul import (
        int8_matmul_pallas, int8_matmul_reference)
    rng = np.random.default_rng(SEED + 2)
    b, t, dm, heads = sz.flash
    q, k, v = (jnp.asarray(rng.normal(size=(b, t, dm)).astype(np.float32),
                           jnp.bfloat16) for _ in range(3))

    # parallel.reference_attention would itself route to the kernel at
    # this length: the reference is the einsum chain, use_flash=False
    def loss(attend):
        return lambda *a: jnp.sum(attend(*a).astype(jnp.float32) ** 2)

    flash = lambda *a: flash_attention(*a, n_heads=heads)
    chain = lambda *a: multi_head_attention(*a, n_heads=heads,
                                            use_flash=False)
    fwd_err = _within_band(jax.jit(flash)(q, k, v), jax.jit(chain)(q, k, v),
                           FLASH_BAND)
    g_flash = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    g_chain = jax.jit(jax.grad(loss(chain), argnums=(0, 1, 2)))(q, k, v)
    grad_err = max(_within_band(gf, gc, FLASH_BAND)
                   for gf, gc in zip(g_flash, g_chain))

    m, kk, n = sz.int8
    x = jnp.asarray(rng.normal(size=(m, kk)).astype(np.float32),
                    jnp.bfloat16)
    w_q, scale = quantize_weight(
        jnp.asarray(rng.normal(size=(kk, n)).astype(np.float32)))
    int8_err = _within_band(int8_matmul_pallas(x, w_q, scale),
                            int8_matmul_reference(x, w_q, scale), INT8_BAND)
    say("kernels", flash_shape=list(sz.flash), flash_fwd_max_abs_diff=fwd_err,
        flash_grad_max_abs_diff=grad_err, flash_band=FLASH_BAND,
        int8_shape=list(sz.int8), int8_max_abs_diff=int8_err,
        int8_band=INT8_BAND)


def phase_bake(sz: Sizes, zip_path: str) -> None:
    """AOT-serialize the serve programs into the zip (child A, last)."""
    from deeplearning4j_tpu.resilience.checkpoint import verify_checkpoint
    from deeplearning4j_tpu.train import artifact_store
    baked = artifact_store.ensure_zip_artifacts(zip_path, buckets=sz.buckets)
    assert baked == len(sz.buckets), baked
    findings = verify_checkpoint(zip_path)
    assert not findings, findings
    say("bake", baked=baked,
        zip_mb=round(os.path.getsize(zip_path) / 2**20, 1))


def phase_warm_restart(sz: Sizes, workdir: str, cache_dir: str) -> None:
    """A fresh process: the baked zip serves the same answers with zero
    JIT, then one train step finds child A's compile in the cache."""
    import jax
    import numpy as np

    from deeplearning4j_tpu.config import DTypePolicy, set_dtype_policy
    from deeplearning4j_tpu.data.device_pipeline import pad_to_bucket
    from deeplearning4j_tpu.train.trainer import Trainer
    with open(os.path.join(workdir, "a.json")) as f:
        child_a = json.load(f)
    assert cache_dir == child_a["cache_dir"], (cache_dir, child_a)
    assert os.listdir(cache_dir), f"compile cache {cache_dir} is empty"
    set_dtype_policy(DTypePolicy.bf16())       # part of the artifact key
    zip_path = os.path.join(workdir, f"{MODEL}.zip")
    answers, facts, metrics = _serve_requests(sz, zip_path)
    with np.load(os.path.join(workdir, "answers.npz")) as saved:
        errs = [_within_band(got, saved[f"arr_{i}"], SERVE_BAND)
                for i, got in enumerate(answers)]
    _assert_served_ok(metrics, len(answers))
    assert facts["compiled_programs"] == 0, facts
    assert facts["warm_programs"] == len(sz.buckets), facts
    assert _counter("tpudl_serve_recompiles_total") == 0
    assert _counter("tpudl_compile_artifact_rejects_total") == 0
    say("warm_restart", max_abs_diff_vs_child_a=max(errs), serve_recompiles=0,
        artifact_rejects=0, **facts)

    # the same step child A's fit compiled: same net, same bucketed batch
    net = _resnet50(sz)
    batch, _ = pad_to_bucket(_batches(sz, 1)[0], sz.batch)
    trainer = Trainer(net)
    entries_before = len(os.listdir(cache_dir))
    t0 = time.perf_counter()
    loss = float(trainer.fit_batch(batch, jax.random.key(0)))
    first_step_s = time.perf_counter() - t0
    assert np.isfinite(loss), loss
    # nothing added = the step came out of the cache child A filled
    say("warm_train_step", first_step_s=first_step_s,
        child_a_compile_s=child_a["compile_s"], loss=loss,
        cache_dir=cache_dir, cache_entries=entries_before,
        cache_entries_added=len(os.listdir(cache_dir)) - entries_before)


def phase_dp4(sz: Sizes) -> None:
    """Three steps on one device, the same three under ``layout="dp4"``."""
    import jax
    import numpy as np

    from deeplearning4j_tpu.obs.registry import get_registry
    from deeplearning4j_tpu.train.trainer import Trainer
    batches = _batches(sz, sz.dp_steps)
    keys = jax.random.split(jax.random.key(SEED), sz.dp_steps)

    def run(layout):
        trainer = Trainer(_resnet50(sz), layout=layout)
        t0 = time.perf_counter()
        losses = [float(trainer.fit_batch(b, k))
                  for b, k in zip(batches, keys)]
        return trainer, losses, time.perf_counter() - t0

    one, one_s = run(None)[1:]          # its trainer and net are dropped
    trainer, dp4, dp4_s = run("dp4")
    assert np.all(np.isfinite(one)) and np.all(np.isfinite(dp4)), (one, dp4)
    for i, (a, b) in enumerate(zip(one, dp4)):
        band = DP_LOSS_BAND if i else DP_FIRST_LOSS_BAND
        assert abs(a - b) <= band * abs(a), (i, band, one, dp4)
    assert get_registry().gauge("tpudl_mesh_devices").value == 4
    shards = trainer._place_batch(batches[0]).features.addressable_shards
    batch_devices = {s.device.id for s in shards}
    assert len(batch_devices) == 4, batch_devices
    assert all(s.data.shape[0] == sz.batch // 4 for s in shards)
    for leaf in jax.tree_util.tree_leaves(trainer.net.params_):
        assert len(leaf.sharding.device_set) == 4 \
            and leaf.is_fully_addressable, leaf.sharding
    text = _step_text(trainer, batches[0], compiled=True)
    assert "all-reduce" in text, "no all-reduce in the compiled dp4 step"
    say("dp4", losses_one_device=one, losses_dp4=dp4,
        band_first_step=DP_FIRST_LOSS_BAND, band_later_steps=DP_LOSS_BAND,
        mesh_devices=4, batch_devices=sorted(batch_devices),
        all_reduces=text.count("all-reduce("),
        seconds_one_device=one_s, seconds_dp4=dp4_s, global_batch=sz.batch)


# ---- children ---------------------------------------------------------------
def run_phases(phase: str, sz: Sizes, workdir: str) -> None:
    """One child's phases, in order, on whatever jax found.  What a later
    child or the parent needs is left in ``<workdir>/<phase>.json``."""
    import jax

    from deeplearning4j_tpu import config
    from deeplearning4j_tpu.native import fast_io
    from deeplearning4j_tpu.obs import costmodel
    devices = jax.devices()
    cache_dir = config.place_compile_cache()
    cache_events = {"cache_hits": 0, "cache_misses": 0}

    def count(event, **_):
        name = event.rsplit("/", 1)[-1]
        if name in cache_events and "/compilation_cache/" in event:
            cache_events[name] += 1
    jax.monitoring.register_event_listener(count)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(f"child_{phase}", device=device, jax=jax.__version__,
        cache_dir=cache_dir, native_fast_io=fast_io.available())
    facts = {"device": device, "cache_dir": cache_dir}
    if phase == "a":
        net = phase_train(sz)
        facts["compile_s"] = _compile_s()
        zip_path = phase_serve(sz, net, workdir)
        phase_kernels(sz)
        phase_bake(sz, zip_path)
    elif phase == "b":
        phase_warm_restart(sz, workdir, cache_dir)
    else:
        phase_dp4(sz)
    # the cost model's background AOT compile racing interpreter shutdown
    # aborts a short script
    assert costmodel.drain(timeout_s=300), "cost-model analyses still queued"
    say(f"child_{phase}_done", **cache_events)
    with open(os.path.join(workdir, f"{phase}.json"), "w") as f:
        json.dump(facts, f)


def _child(phase: str, workdir: str) -> None:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, jax found platform "
                 f"{devices[0].platform!r} ({devices[0].device_kind})")
    want = 4 if phase == "dp4" else 1
    if len(devices) != want:
        sys.exit(f"chip_smoke: phase {phase} needs {want} chip(s), jax "
                 f"found {len(devices)}")
    run_phases(phase, Sizes(), workdir)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the dp4 phase and its one-device twin")
    ap.add_argument("--phase", choices=("a", "b", "dp4"),
                    help=argparse.SUPPRESS)     # a child of this script
    ap.add_argument("--workdir", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if not __debug__:
        sys.exit("chip_smoke: its checks are assert statements; run it "
                 "without -O")
    if args.phase:
        _child(args.phase, args.workdir)
        return 0
    # the parent: no jax here, the chip is the children's
    if not os.path.isdir(os.path.join(HERE, "deeplearning4j_tpu")):
        sys.exit(f"chip_smoke: no deeplearning4j_tpu package beside {HERE}")
    phases = ("dp4",) if args.chips == 4 else ("a", "b")
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        for phase in phases:
            rc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--phase", phase,
                 "--workdir", workdir]).returncode
            if rc != 0:
                print(f"chip_smoke: child {phase} exited {rc}",
                      file=sys.stderr)
                return rc
        with open(os.path.join(workdir, f"{phases[-1]}.json")) as f:
            device = json.load(f)["device"]
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
