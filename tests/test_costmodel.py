"""Roofline cost model: cost_analysis extraction, peak table, per-step
MFU/HBM gauges through a real Trainer fit, and the bench stamp."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.obs import costmodel
from deeplearning4j_tpu.obs.registry import (MetricsRegistry, get_registry,
                                             set_registry)


@pytest.fixture(autouse=True)
def _fresh_state():
    prev = set_registry(MetricsRegistry())
    costmodel.clear()
    yield
    costmodel.clear()
    set_registry(prev)


def _small_net(seed=3):
    from deeplearning4j_tpu.nn import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.train import Sgd
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(0.1))
            .list()
            .layer(DenseLayer(n_out=32, activation="relu"))
            .layer(OutputLayer(n_out=10, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(64)).build())
    return MultiLayerNetwork(conf).init()


class TestBackendPeaks:
    def test_cpu_fallback_is_estimated_and_positive(self):
        peaks = costmodel.backend_peaks()
        assert peaks.peak_flops > 0
        assert peaks.peak_bytes_per_s > 0
        assert peaks.estimated            # CPU has no real peak table row
        assert peaks.ridge_intensity > 0
        # the assumed peaks are visible on the scrape surface
        assert get_registry().gauge("tpudl_perf_peak_flops").value \
            == peaks.peak_flops

    def test_env_overrides_win(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_PEAK_TFLOPS", "130")
        monkeypatch.setenv("DL4J_TPU_PEAK_HBM_GBPS", "819")
        peaks = costmodel.backend_peaks()
        assert peaks.peak_flops == 130e12
        assert peaks.peak_bytes_per_s == 819e9
        assert not peaks.estimated        # measured ceiling supplied

    def test_single_env_override_keeps_estimated(self, monkeypatch):
        """One override must not launder the OTHER, still-synthetic
        peak into a 'measured' stamp."""
        monkeypatch.setenv("DL4J_TPU_PEAK_TFLOPS", "1.5")
        monkeypatch.delenv("DL4J_TPU_PEAK_HBM_GBPS", raising=False)
        peaks = costmodel.backend_peaks()
        assert peaks.peak_flops == 1.5e12
        assert peaks.estimated            # bandwidth is still synthetic


    def test_unknown_tpu_kind_raises(self, monkeypatch):
        """A chip the table does not know gets no other chip's peaks."""
        import types
        monkeypatch.delenv("DL4J_TPU_PEAK_TFLOPS", raising=False)
        monkeypatch.delenv("DL4J_TPU_PEAK_HBM_GBPS", raising=False)
        unknown = types.SimpleNamespace(platform="tpu",
                                        device_kind="TPU v99 mega")
        with pytest.raises(ValueError, match="v99 mega"):
            costmodel.backend_peaks(unknown)
        v5e = types.SimpleNamespace(platform="tpu",
                                    device_kind="TPU v5 lite")
        peaks = costmodel.backend_peaks(v5e)
        assert (peaks.peak_flops, peaks.peak_bytes_per_s) == (197e12, 819e9)
        assert not peaks.estimated


class TestAnalyze:
    def test_jitted_matmul_costs_and_roofline(self):
        @jax.jit
        def mm(a, b):
            return jnp.dot(a, b)

        a = jnp.ones((64, 128), jnp.float32)
        b = jnp.ones((128, 32), jnp.float32)
        mm(a, b).block_until_ready()
        cost = costmodel.analyze_jitted(mm, costmodel.abstractify((a, b)),
                                        kind="test:mm")
        assert cost is not None
        # dot(64x128, 128x32) = 2*64*128*32 FLOPs
        assert cost.flops == pytest.approx(2 * 64 * 128 * 32)
        assert cost.bytes_accessed >= (64 * 128 + 128 * 32 + 64 * 32) * 4
        assert cost.arith_intensity > 0
        assert cost.bound in ("compute", "memory")
        assert cost.roofline_flops <= cost.peaks.peak_flops
        # idempotent: second sight is a cache hit, not a re-analysis
        assert not costmodel.should_analyze(mm)
        assert costmodel.costs_for(mm) is cost

    def test_abstractify_passes_none_and_keys(self):
        key = jax.random.key(0)
        out = costmodel.abstractify((jnp.ones((2, 3)), None, key))
        assert out[0].shape == (2, 3)
        assert out[1] is None
        assert out[2].shape == key.shape

    def test_analysis_failure_is_silent_and_cached(self):
        def not_jitted(x):
            return x

        assert costmodel.analyze_jitted(not_jitted, ((),), kind="x") is None
        assert not costmodel.should_analyze(not_jitted)   # failure cached

    def test_recycled_id_does_not_inherit_cost_entry(self):
        """CPython recycles ids once an object dies: an id-keyed entry
        whose weakref resolves to a DIFFERENT object must read as absent
        (and be evicted), never as the dead program's cost."""
        @jax.jit
        def f(x):
            return x + 1

        x = jnp.ones(3)
        f(x).block_until_ready()
        cost = costmodel.analyze_jitted(f, costmodel.abstractify((x,)),
                                        kind="test:f")
        assert cost is not None

        def imposter(x):
            return x

        with costmodel._LOCK:
            costmodel._COSTS[(id(imposter), None)] = \
                (costmodel._mkref(f), cost)
            costmodel._KINDS[id(imposter)] = (costmodel._mkref(f), "test:f")
            costmodel._FAILED[(id(imposter), None)] = \
                (costmodel._mkref(f), True)
        assert costmodel.costs_for(imposter) is None
        assert costmodel.program_kind(imposter) is None
        assert costmodel.should_analyze(imposter)   # FAILED entry stale too
        # the live fn's entries are untouched
        assert costmodel.costs_for(f) is cost

    def test_top_programs_purges_dead_entries(self):
        """A retired program (weakref dead) must be purged by
        top_programs, which still returns the live breakdown — the
        bench/dump cost breakdown must not vanish the moment any
        analyzed fn is garbage-collected."""
        import gc
        import weakref

        @jax.jit
        def live(x):
            return x * 3.0

        x = jnp.ones((4, 4))
        live(x).block_until_ready()
        cost = costmodel.analyze_jitted(live, costmodel.abstractify((x,)),
                                        kind="test:live")
        assert cost is not None

        class _Retired:
            pass

        obj = _Retired()
        dead_ref = weakref.ref(obj)
        del obj
        gc.collect()
        assert dead_ref() is None
        with costmodel._LOCK:
            costmodel._COSTS[(999999999, None)] = (dead_ref, cost)
        top = costmodel.top_programs(5)
        assert any(t["kind"] == "test:live" for t in top)
        with costmodel._LOCK:
            assert (999999999, None) not in costmodel._COSTS

    def test_per_signature_cost_entries(self):
        """One jit fn holds one compiled program PER call signature
        (serving buckets): bucket-16's wall time must be attributed
        bucket-16's FLOPs, never the first-analyzed bucket's."""
        @jax.jit
        def mm(a, b):
            return jnp.dot(a, b)

        b = jnp.ones((64, 32), jnp.float32)
        a8 = jnp.ones((8, 64), jnp.float32)
        a16 = jnp.ones((16, 64), jnp.float32)
        mm(a8, b).block_until_ready()
        mm(a16, b).block_until_ready()
        c8 = costmodel.analyze_jitted(mm, costmodel.abstractify((a8, b)),
                                      kind="test:mm", sig=8)
        assert c8 is not None
        assert costmodel.should_analyze(mm, sig=16)   # distinct program
        c16 = costmodel.analyze_jitted(mm, costmodel.abstractify((a16, b)),
                                       kind="test:mm", sig=16)
        assert c16.flops == pytest.approx(2 * c8.flops)
        assert costmodel.costs_for(mm, sig=8) is c8
        assert costmodel.costs_for(mm, sig=16) is c16
        costmodel.observe_step(mm, 0.01, sig=16)
        assert costmodel.last_observation()["cost"] is c16

    def test_schedule_analysis_runs_in_background(self):
        @jax.jit
        def f(x):
            return x * 2.0

        x = jnp.ones((16, 16))
        f(x).block_until_ready()
        costmodel.schedule_analysis(f, costmodel.abstractify((x,)),
                                    kind="test:bg")
        assert costmodel.drain(30.0)
        assert costmodel.costs_for(f) is not None
        assert not costmodel.should_analyze(f)
        # idempotent while analyzed
        costmodel.schedule_analysis(f, costmodel.abstractify((x,)),
                                    kind="test:bg")
        assert costmodel.drain(30.0)

    def test_disabled_by_config(self):
        from deeplearning4j_tpu.config import set_config
        set_config(costmodel=False)
        try:
            @jax.jit
            def f(x):
                return x * 2

            assert not costmodel.should_analyze(f)
            assert costmodel.analyze_jitted(
                f, costmodel.abstractify((jnp.ones(4),))) is None
        finally:
            set_config(costmodel=True)


class TestTrainerIntegration:
    def test_fit_publishes_mfu_and_program_series(self):
        from deeplearning4j_tpu.data.dataset import DataSet
        from deeplearning4j_tpu.train import Trainer
        net = _small_net()
        trainer = Trainer(net)
        rng = np.random.default_rng(0)
        ds = DataSet(rng.normal(size=(16, 64)).astype(np.float32),
                     np.eye(10, dtype=np.float32)[rng.integers(0, 10, 16)])
        key = jax.random.key(0)
        trainer.step_batch(ds, key)        # compile + schedule analysis
        assert costmodel.drain(60.0)       # background analysis lands
        for _ in range(2):
            trainer.step_batch(ds, key)    # steady-state: observed
        reg = get_registry()
        assert reg.gauge("tpudl_perf_mfu").value > 0
        assert reg.gauge("tpudl_perf_hbm_util").value > 0
        assert reg.gauge("tpudl_perf_arith_intensity").value > 0
        assert 0 < reg.gauge("tpudl_perf_roofline_fraction").value <= 1.0
        # the program series carries the step-cache kind tag
        flops = reg.labeled_gauge("tpudl_perf_program_flops",
                                  label_names=("program",))
        assert flops.labeled_value(program="train:MultiLayerNetwork") > 0
        hist = reg.labeled_histogram("tpudl_perf_step_seconds")
        # the 2 post-analysis steps observed (compile step excluded)
        assert hist.labeled_count(program="train:MultiLayerNetwork") == 2
        top = costmodel.top_programs(5)
        assert top and top[0]["kind"] == "train:MultiLayerNetwork"
        assert top[0]["flops"] > 0

    def test_bench_detail_stamp_shape(self):
        from deeplearning4j_tpu.data.dataset import DataSet
        from deeplearning4j_tpu.train import Trainer
        net = _small_net(seed=5)
        trainer = Trainer(net)
        rng = np.random.default_rng(1)
        ds = DataSet(rng.normal(size=(8, 64)).astype(np.float32),
                     np.eye(10, dtype=np.float32)[rng.integers(0, 10, 8)])
        key = jax.random.key(1)
        trainer.step_batch(ds, key)        # compile + schedule analysis
        assert costmodel.drain(60.0)
        trainer.step_batch(ds, key)        # observed against the cost
        stamp = costmodel.bench_detail()
        assert stamp is not None
        for field in ("mfu", "hbm_util", "arith_intensity",
                      "flops_per_step", "bytes_per_step", "program",
                      "backend", "roofline_bound"):
            assert stamp.get(field) is not None, field
        assert stamp["source"] == "xla_cost_analysis"
        assert stamp["mfu"] > 0


class TestServeIntegration:
    def test_engine_dispatch_observes_forward_cost(self):
        from deeplearning4j_tpu.serve import InferenceEngine
        net = _small_net(seed=7)
        engine = InferenceEngine(net, name="cm", max_batch=8,
                                 max_latency_ms=1.0, buckets=(8,))
        try:
            x = np.random.default_rng(0).normal(size=(4, 64)) \
                .astype(np.float32)
            engine.predict(x, timeout_s=60)   # compile + schedule analysis
            assert costmodel.drain(60.0)
            engine.predict(x, timeout_s=60)   # steady-state: observed
        finally:
            engine.shutdown()
        reg = get_registry()
        flops = reg.labeled_gauge("tpudl_perf_program_flops",
                                  label_names=("program",))
        assert flops.labeled_value(
            program="serve_forward:MultiLayerNetwork") > 0
        assert reg.gauge("tpudl_perf_mfu").value > 0


class TestFusedCheckFinite:
    """The NAN/INF panic scan batches every leaf into ONE fused device
    reduction (one host sync), and only walks per-leaf after a hit."""

    @pytest.fixture(autouse=True)
    def _panic(self):
        from deeplearning4j_tpu.config import set_config
        set_config(nan_panic=True, inf_panic=True)
        yield
        set_config(nan_panic=False, inf_panic=False)

    def test_clean_tree_passes(self):
        from deeplearning4j_tpu.obs.profiler import check_finite
        tree = {"a": jnp.ones((4, 4)), "b": [jnp.zeros(3),
                                             jnp.asarray([1, 2])]}
        check_finite(tree, "params")        # int leaves skipped, no raise

    def test_nan_is_found_and_anchored(self):
        from deeplearning4j_tpu.obs.profiler import (NonFiniteError,
                                                     check_finite)
        tree = {"ok": jnp.ones(3),
                "bad": jnp.asarray([1.0, float("nan"), 2.0])}
        with pytest.raises(NonFiniteError, match="NaN.*bad"):
            check_finite(tree, "params")

    def test_inf_is_found(self):
        from deeplearning4j_tpu.obs.profiler import (NonFiniteError,
                                                     check_finite)
        with pytest.raises(NonFiniteError, match="Inf"):
            check_finite([jnp.asarray([float("inf")])], "grads")

    def test_one_fused_program_per_structure(self):
        """Re-checking the same tree structure reuses ONE compiled
        reduction — not a jnp.any dispatch per leaf per call."""
        from deeplearning4j_tpu.obs.profiler import _finite_flags, check_finite
        from deeplearning4j_tpu.train.step_cache import jit_cache_entries
        tree = [jnp.ones((8, 8)) * i for i in range(6)]
        check_finite(tree, "params")
        before = jit_cache_entries(_finite_flags)
        for _ in range(5):
            check_finite(tree, "params")
        assert jit_cache_entries(_finite_flags) == before
