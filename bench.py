#!/usr/bin/env python
"""Benchmark harness — prints ONE json line with the headline metric.

Headline (BASELINE.json): ResNet-50 ImageNet-shape training throughput in
images/sec/chip on the real TPU.  The reference publishes no number
(BASELINE.md), so ``vs_baseline`` is computed against the public
MLPerf-era proxy for the A100 comparison point named by the north star
(~2750 img/s bf16 on one A100 — marked as a proxy, not a reference-repo
measurement).

Needs a TPU and exits non-zero without one; a row that raises fails the
run.  bfloat16 compute policy, synthetic data (no network), steady-state
steps timed after compile+warmup.  The CPU-pinned scripts under ``bench/``
run on their own and say ``platform: cpu`` in what they print.
"""

import json
import os
import sys
import time

import numpy as np


A100_PROXY_IMG_PER_SEC = 2750.0  # public MLPerf-era proxy, see BASELINE.md

# v5e public peak numbers for utilization lines
V5E_PEAK_BF16_TFLOPS = 197.0
V5E_HBM_GBPS = 819.0

def _timed_region(run, sync, steps, repeats=3):
    """Best-of-``repeats`` steady-state seconds/step.

    ``run()`` dispatches one step and returns a handle; ``sync`` forces a
    device→host transfer of that handle, which fences the device work
    the dispatches queued."""
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        handle = None
        for _ in range(steps):
            handle = run()
        sync(handle)
        best = min(best, (time.perf_counter() - t0) / steps)
    return best


# ResNet-50 224x224 training FLOPs/image, from XLA cost_analysis of the
# full donated train step at batch 256 (5.72 TFLOP / 256 images; includes
# fwd+bwd+Nesterov update)
RESNET50_TRAIN_GFLOP_PER_IMG = 22.34
# ... and HBM bytes/image from the same analysis (344 MB/image)
RESNET50_TRAIN_MB_PER_IMG = 344.0


def _phase_spans(trainer, batch_ds, key, steps, warmup):
    """Run warmup + one short attribution pass under a pinned tracer,
    emitting the span taxonomy from docs/observability.md (``bench`` →
    ``compile`` / ``steps``/``host_dispatch``).  Returns (tracer, phase
    dict) — the dict is DERIVED from the spans, so the jsonl/Chrome
    exports and the printed breakdown come from one measurement.  This
    pass doubles as the headline run's warmup (compile + steady steps);
    the headline number itself still comes from ``_timed_region``'s
    best-of-repeats discipline, so the attribution pass is capped at a
    few steps to keep its extra device time negligible."""
    from deeplearning4j_tpu.obs import tracing

    steps = min(steps, 4)
    tracer = tracing.Tracer(enabled=True)
    with tracing.use_tracer(tracer):
        with tracing.span("bench", steps=steps):
            with tracing.span("compile"):
                # first call traces+compiles the whole donated train step
                tracing.device_sync(trainer.fit_batch(batch_ds, key))
            for _ in range(max(warmup - 1, 0)):
                float(trainer.fit_batch(batch_ds, key))
            with tracing.span("steps", n=steps) as sp:
                handle = None
                with tracing.span("host_dispatch"):
                    for _ in range(steps):
                        handle = trainer.fit_batch(batch_ds, key)
                tracing.device_sync(handle)   # device wait lands on sp

    compile_s = sum(s.duration_s for s in tracer.find("compile"))
    host_s = sum(s.duration_s for s in tracer.find("host_dispatch"))
    measured = tracer.find("steps")
    wall_s = sum(s.duration_s for s in measured)
    sync_s = sum(s.device_sync_s for s in measured)
    phases = {
        "compile_s": round(compile_s, 3),
        "host_dispatch_ms_per_step": round(1e3 * host_s / steps, 3),
        "device_wait_ms_per_step": round(1e3 * sync_s / steps, 3),
        "wall_ms_per_step": round(1e3 * wall_s / steps, 3),
        "note": ("host = python+dispatch; device wait = post-dispatch "
                 "sync; execute/step ~= wall - host (async dispatch "
                 "keeps the device busy across steps)"),
    }
    return tracer, phases


def bench_resnet50(batch: int = 256, image: int = 224, steps: int = 12,
                   warmup: int = 2) -> dict:
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.config import DTypePolicy, set_dtype_policy
    from deeplearning4j_tpu.models import resnet50
    from deeplearning4j_tpu.obs.registry import get_registry
    from deeplearning4j_tpu.train.trainer import Trainer
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.train import Nesterovs

    set_dtype_policy(DTypePolicy.bf16())
    net = resnet50(height=image, width=image, num_classes=1000,
                   updater=Nesterovs(0.1, 0.9))
    net.init()
    trainer = Trainer(net)

    rng = np.random.default_rng(0)
    x = rng.uniform(0, 1, size=(batch, image, image, 3)).astype(np.float32)
    y = np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, batch)]
    batch_ds = DataSet(jnp.asarray(x), jnp.asarray(y))
    key = jax.random.key(0)

    # warmup (compile) + phase attribution ride the same tracer
    tracer, phases = _phase_spans(trainer, batch_ds, key, steps, warmup)
    # the warmup queued the step's background cost analysis — a REAL
    # duplicate XLA compile that would contend with the very steps it
    # grades; let it land before entering the measured region (generous
    # timeout: a ResNet-50 TPU compile outlives drain's 60s default)
    from deeplearning4j_tpu.obs import costmodel
    costmodel.drain(timeout_s=300.0)
    step_s = _timed_region(lambda: trainer.fit_batch(batch_ds, key),
                           float, steps)
    get_registry().histogram("tpudl_bench_step_seconds").observe(step_s)
    trace_path = os.environ.get("DL4J_TPU_BENCH_TRACE")
    if trace_path:
        tracer.export_chrome_trace(trace_path)
        phases["chrome_trace"] = trace_path
    dt = step_s * steps
    img_per_sec = batch * steps / dt
    n_chips = max(len(jax.devices()), 1)
    per_chip = img_per_sec / n_chips
    # utilization lines from the MEASURED program: the trainer's cost
    # model pulled FLOPs/bytes from the compiled step's cost_analysis;
    # feed it the bench's own best-of step time so mfu/hbm_util come
    # from the compiler's accounting, not hand-derived constants
    costmodel.observe_step(trainer._last_step_fn, step_s,
                           sig=getattr(trainer, "_last_step_sig", None))
    perf = costmodel.bench_detail() or {}
    # hand-derived fallback lines kept for cross-checking the model
    mfu_proxy = (per_chip * RESNET50_TRAIN_GFLOP_PER_IMG / 1e3
                 / V5E_PEAK_BF16_TFLOPS)
    hbm = per_chip * RESNET50_TRAIN_MB_PER_IMG / 1e3
    return {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(per_chip / A100_PROXY_IMG_PER_SEC, 4),
        "detail": {
            "batch": batch, "image": image, "steps": steps,
            "step_time_ms": round(1000 * dt / steps, 2),
            "phases": phases,
            "mfu": perf.get("mfu", round(mfu_proxy, 3)),
            "hbm_util": perf.get("hbm_util"),
            "arith_intensity": perf.get("arith_intensity"),
            "perf": perf,
            "mfu_hand_proxy": round(mfu_proxy, 3),
            "hbm_gbps_sustained": round(hbm, 1),
            "hbm_roof_fraction": round(hbm / V5E_HBM_GBPS, 3),
            "baseline_note": "A100 bf16 public proxy (~2750 img/s); reference repo publishes no number",
        },
    }


def bench_bert_mlm(batch: int = 32, seq_len: int = 128, steps: int = 30,
                   warmup: int = 3, repeats: int = 3) -> dict:
    """BERT-base MLM fine-tune step time — the second headline metric
    (BASELINE.json config #4: SameDiff TF-import BERT-base MLM).

    Timing discipline: see ``_timed_region``."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.config import DTypePolicy, set_dtype_policy
    from deeplearning4j_tpu.models.bert import BertConfig, BertForMaskedLM
    from deeplearning4j_tpu.train import Adam

    set_dtype_policy(DTypePolicy.bf16())
    # max_predictions: decode the vocab only at gathered masked positions
    # (TF BERT max_predictions_per_seq; 32 of 128 = 25%, safely above the
    # 15% masking rate) — FLOP accounting below credits the decode for
    # the gathered positions only
    config = dataclasses.replace(BertConfig.base(), max_predictions=32)
    model = BertForMaskedLM(config, seed=0)
    # bf16 first moment: −1.3 ms/step of mu HBM traffic; loss trajectory
    # agrees with f32-state Adam to ≤0.02 abs (≈0.3% rel) over 30 steps
    # (measured r5)
    tx = Adam(2e-5, mu_dtype="bf16").to_optax()
    opt_state = tx.init(model.params)
    step = model.make_train_step(tx)

    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, config.vocab_size, (batch, seq_len)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, config.vocab_size, (batch, seq_len)), jnp.int32)
    weights = jnp.asarray((rng.random((batch, seq_len)) < 0.15), jnp.float32)
    attn = jnp.ones((batch, seq_len), jnp.float32)
    # rbg = the TPU-accelerated generator the model uses for dropout
    key = jax.random.key(0, impl="rbg")

    params, opt = model.params, opt_state
    n_params = model.num_params()
    for _ in range(warmup):
        params, opt, loss = step(params, opt, ids, labels, weights, attn, key)
    jax.device_get(loss)
    state = [params, opt]

    def run():
        state[0], state[1], loss = step(state[0], state[1], ids, labels,
                                        weights, attn, key)
        return loss

    step_s = _timed_region(run, jax.device_get, steps, repeats)
    # measured roofline stamp: FLOPs/bytes from the compiled step's own
    # cost_analysis (the analytic 6PT estimate below stays as the
    # cross-check the estimate-vs-compiler gap is judged by)
    from deeplearning4j_tpu.obs import costmodel
    perf = costmodel.measure(
        step, costmodel.abstractify((state[0], state[1], ids, labels,
                                     weights, attn, key)),
        step_s, kind="bench:bert_mlm") or {}
    # transformer train FLOPs ≈ 6·P·tokens + attention 12·L·T²·H·Dh·3
    # (fwd+bwd); the 6PT term dominates at seq 128.  The word-embedding
    # table's matmul is the MLM decode — credited only for the positions
    # it actually decodes (max_predictions gather), not the full width.
    tokens = batch * seq_len
    emb_params = config.vocab_size * config.hidden_size
    decode_tokens = (batch * config.max_predictions
                     if config.max_predictions else tokens)
    attn_flops = (12 * config.num_layers * batch * seq_len ** 2
                  * config.hidden_size)
    flops = (6.0 * (n_params - emb_params) * tokens
             + 6.0 * emb_params * decode_tokens + attn_flops)
    return {"step_time_ms": round(1000 * step_s, 2),
            "batch": batch, "seq_len": seq_len,
            "max_predictions": config.max_predictions,
            "tflops_per_step": round(flops / 1e12, 2),
            "mfu": perf.get("mfu", round(
                flops / step_s / 1e12 / V5E_PEAK_BF16_TFLOPS, 3)),
            "hbm_util": perf.get("hbm_util"),
            "arith_intensity": perf.get("arith_intensity"),
            "mfu_analytic": round(
                flops / step_s / 1e12 / V5E_PEAK_BF16_TFLOPS, 3)}


def bench_bert_long_seq(seq_len: int = 4096, batch: int = 2,
                        steps: int = 5, warmup: int = 2) -> dict:
    """Long-sequence BERT MLM train step: Pallas flash kernel (fwd+bwd)
    vs the materializing einsum path (SURVEY §5.7 long-seq training)."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.config import DTypePolicy, set_dtype_policy
    from deeplearning4j_tpu.models import bert as bert_mod
    from deeplearning4j_tpu.train import Adam

    set_dtype_policy(DTypePolicy.bf16())
    # the flash kernel cannot drop attention probabilities (use_flash=True
    # with a rate raises), so both sides of this comparison run without
    base = bert_mod.BertConfig(vocab_size=30522, hidden_size=768,
                               num_layers=4, num_heads=12,
                               intermediate_size=3072, max_position=seq_len,
                               attention_dropout=0.0)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, base.vocab_size, (batch, seq_len)),
                      jnp.int32)
    labels = jnp.asarray(rng.integers(0, base.vocab_size, (batch, seq_len)),
                         jnp.int32)
    weights = jnp.asarray((rng.random((batch, seq_len)) < 0.15), jnp.float32)
    attn = jnp.ones((batch, seq_len), jnp.float32)
    key = jax.random.key(0, impl="rbg")

    out = {"seq_len": seq_len, "batch": batch, "num_layers": base.num_layers}
    n_params = None
    for name, cfg in (("einsum", base),
                      ("flash", dataclasses.replace(base, use_flash=True))):
        model = bert_mod.BertForMaskedLM(cfg, seed=0)
        n_params = model.num_params()
        tx = Adam(2e-5).to_optax()
        opt = tx.init(model.params)
        step = model.make_train_step(tx)
        params = model.params
        for _ in range(warmup):
            params, opt, loss = step(params, opt, ids, labels, weights,
                                     attn, key)
        jax.device_get(loss)
        state = [params, opt]

        def run():
            state[0], state[1], loss = step(state[0], state[1], ids, labels,
                                            weights, attn, key)
            return loss

        out[f"{name}_step_ms"] = round(
            _timed_region(run, jax.device_get, steps) * 1000, 2)
    out["flash_speedup"] = round(out["einsum_step_ms"]
                                 / out["flash_step_ms"], 2)
    flops = (6.0 * n_params * batch * seq_len
             + 12 * base.num_layers * batch * seq_len ** 2
             * base.hidden_size)
    out["tflops_per_step"] = round(flops / 1e12, 2)
    out["flash_mfu"] = round(
        flops / (out["flash_step_ms"] / 1e3) / 1e12 / V5E_PEAK_BF16_TFLOPS, 3)
    return out


def bench_dcn_multislice(steps: int = 6, batch: int = 32) -> dict:
    """Production multi-slice DCN training at ResNet-50 gradient scale:
    wire-bytes ratio, D2H reduction, and per-step exchange overhead,
    sync vs overlapped.

    Both slices run on the ONE real chip (their compute serializes), so
    per-step DCN overhead = multislice_step − 2 × plain_step; the codec
    path (device encode → compact message → ring exchange → device
    decode+apply) is exactly the multi-process production path."""
    import time as _time

    import jax
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.models import resnet50
    from deeplearning4j_tpu.parallel.compression import (
        AdaptiveThresholdAlgorithm)
    from deeplearning4j_tpu.parallel.dcn_trainer import MultiSliceTrainer
    from deeplearning4j_tpu.train import Sgd, Trainer

    rng = np.random.default_rng(11)
    x = rng.uniform(0, 1, (batch, 32, 32, 3)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch)]
    data = DataSet(x, y)
    half = DataSet(x[:batch // 2], y[:batch // 2])

    def wall(fn, n):
        fn()                              # warm
        t0 = _time.monotonic()
        for _ in range(n):
            fn()
        return (_time.monotonic() - t0) / n

    # plain single-slice baseline at the same per-slice batch
    net0 = resnet50(height=32, width=32, num_classes=10,
                    updater=Sgd(0.01))
    net0.init()
    tr0 = Trainer(net0)
    key = jax.random.key(2)
    from deeplearning4j_tpu.obs import costmodel
    tr0.fit_batch(half, key)            # compile + queue cost analysis
    costmodel.drain(timeout_s=300.0)    # keep its duplicate compile out
    plain_s = wall(lambda: tr0.fit_batch(half, key), steps)

    out = {"grad_mb": None, "plain_step_ms": round(plain_s * 1e3, 2)}
    for overlap in (False, True):
        net = resnet50(height=32, width=32, num_classes=10,
                       updater=Sgd(0.01))
        net.init()
        # steady-state message capacity (the production default, 4× the
        # adaptive sparsity target ≈ 94k entries / 0.75 MB wire): the
        # dense warm-up transient is top-|v|-truncated by design, and τ
        # burns in over the warm-up steps below.
        trainer = MultiSliceTrainer(
            net, n_slices=2, data_per_slice=1,
            devices=[jax.devices()[0]] * 2,
            device_encode=True, overlap=overlap,
            algorithm=AdaptiveThresholdAlgorithm(initial_threshold=1.0))
        try:
            for _ in range(6):      # τ burn-in toward the target sparsity
                trainer.fit_batch(data, key)
            costmodel.drain(timeout_s=300.0)   # codec analyses out of the region
            s = wall(lambda: trainer.fit_batch(data, key), steps)
            ws = trainer.last_wire_stats[0]
            out["grad_mb"] = round(ws["dense_bytes"] / 2 ** 20, 1)
            label = "overlap" if overlap else "sync"
            out[f"{label}_step_ms"] = round(s * 1e3, 2)
            out[f"{label}_overhead_ms"] = round((s - 2 * plain_s) * 1e3, 2)
            if not overlap:
                out["wire_bytes"] = ws["wire_bytes"]
                out["d2h_bytes"] = ws["d2h_bytes"]
                out["dense_bytes"] = ws["dense_bytes"]
                out["wire_ratio"] = round(
                    ws["dense_bytes"] / max(ws["wire_bytes"], 1), 1)
                out["d2h_reduction"] = round(
                    ws["dense_bytes"] / max(ws["d2h_bytes"], 1), 1)
        finally:
            trainer.close()
    out["note"] = ("2 slices share the one chip (compute serializes); "
                   "overhead = step - 2*plain_step (4 sub-MB "
                   "device<->host transfers/step); the multi-process "
                   "form runs in tests/test_multiprocess.py over real TCP")
    return out


def _bench_net_step(net, features, labels, steps=20, warmup=3, repeats=3):
    """Steady-state fit_batch time for a workload net (``_timed_region``
    discipline)."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.train.trainer import Trainer
    trainer = Trainer(net)
    batch = DataSet(jnp.asarray(features), jnp.asarray(labels))
    key = jax.random.key(0)
    for _ in range(warmup):
        loss = trainer.fit_batch(batch, key)
    float(loss)
    from deeplearning4j_tpu.obs import costmodel
    costmodel.drain()   # background cost analysis out of the timed region
    return round(_timed_region(lambda: trainer.fit_batch(batch, key),
                               float, steps, repeats) * 1000, 2)


def bench_workload_steps() -> dict:
    """BASELINE rows 'MLPMnist / LeNet CIFAR-10 / LSTM UCI-HAR step time'
    (SURVEY §7.2 M1/M3/M4 measurements)."""
    from deeplearning4j_tpu.models import mlp_mnist, lenet, lstm_classifier
    rng = np.random.default_rng(0)
    out = {}
    net = mlp_mnist()
    out["mlp_mnist_step_ms"] = _bench_net_step(
        net, rng.normal(size=(128, 784)).astype(np.float32),
        np.eye(10, dtype=np.float32)[rng.integers(0, 10, 128)])
    net = lenet(height=32, width=32, channels=3)       # CIFAR-10 shape
    out["lenet_cifar10_step_ms"] = _bench_net_step(
        net, rng.normal(size=(128, 32, 32, 3)).astype(np.float32),
        np.eye(10, dtype=np.float32)[rng.integers(0, 10, 128)])
    net = lstm_classifier(timesteps=128)               # UCI-HAR shape
    out["lstm_har_step_ms"] = _bench_net_step(
        net, rng.normal(size=(64, 128, 9)).astype(np.float32),
        np.eye(6, dtype=np.float32)[rng.integers(0, 6, 64)])
    return out


def main():
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        # a timing from any other backend is not a number of this bench
        print(f"bench: needs a TPU, jax found platform "
              f"{devices[0].platform!r} ({devices[0].device_kind})",
              file=sys.stderr)
        return 1
    from deeplearning4j_tpu.config import place_compile_cache
    from deeplearning4j_tpu.obs import costmodel
    place_compile_cache()
    # HBM-bound workload: a large batch amortizes weight traffic
    result = bench_resnet50(batch=256)
    detail = result["detail"]
    detail["device"] = {"platform": devices[0].platform,
                        "kind": devices[0].device_kind,
                        "count": len(devices)}
    # second headline metric: BERT-base MLM step time
    detail["bert_base_mlm"] = bench_bert_mlm()
    # BASELINE M1/M3/M4 workload step times
    detail["workloads"] = bench_workload_steps()
    # long-seq BERT: flash (Pallas fwd+bwd) vs einsum
    detail["bert_long_seq"] = bench_bert_long_seq()
    # multi-slice DCN: wire/overhead row (workload #5)
    detail["dcn_multislice"] = bench_dcn_multislice()
    # per-compiled-program cost breakdown (top-K by FLOPs)
    detail["perf_top_programs"] = costmodel.top_programs(5)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
