"""Set-up seen from inside the program: jax's compile events become the
``tpudl_compile_*`` histograms (always) and ``compile.*`` spans (tracing
on); the cost model's analysis is ``tpudl_perf_analysis_seconds`` and
``costmodel.analyze`` alone (all CPU)."""

import os
import time
import uuid

import pytest

from deeplearning4j_tpu.obs import costmodel, tracing
from deeplearning4j_tpu.obs.registry import (MetricsRegistry, set_registry,
                                             setup_metrics)

COMPILES = ("trace", "lower", "xla", "cache_load")


@pytest.fixture
def metrics():
    """A registry of this test's own, so that the counts are this test's."""
    prev = set_registry(MetricsRegistry())
    try:
        yield setup_metrics()
    finally:
        set_registry(prev)


def _counts(m) -> dict:
    return {name: getattr(m, name).count for name in COMPILES + ("analysis",)}


def _sums(m) -> dict:
    return {name: getattr(m, name).sum for name in COMPILES}


def _fresh_step():
    """A ``tpudl_*`` jit that calls two inner jits, around a constant no
    other test compiles: a program neither cache has seen."""
    import jax
    import jax.numpy as jnp
    salt = float(uuid.uuid4().int % 10**6) / 10**6

    @jax.jit
    def inner_a(x):
        return jnp.sin(x) * salt

    @jax.jit
    def inner_b(x):
        return jnp.cos(x) + salt

    def tpudl_probe_step(x):
        return inner_a(x) + inner_b(x)

    return jax.jit(tpudl_probe_step)


def _argument():
    import jax
    import jax.numpy as jnp
    return jax.block_until_ready(jnp.arange(8, dtype=jnp.float32))


def test_a_fresh_jit_is_one_trace_one_lowering_one_xla_compile(metrics):
    step, x = _fresh_step(), _argument()
    before, sums = _counts(metrics), _sums(metrics)
    t0 = time.perf_counter()
    step(x).block_until_ready()
    wall = time.perf_counter() - t0
    after = _counts(metrics)
    grown = {k: after[k] - before[k] for k in after}
    # the inner jits are traced inside the outer trace: one observation
    assert grown == {"trace": 1, "lower": 1, "xla": 1, "cache_load": 0,
                     "analysis": 0}
    took = {k: v - sums[k] for k, v in _sums(metrics).items()}
    assert 0 < took["trace"] <= wall
    assert sum(took.values()) <= wall
    # a second call compiles nothing
    step(x).block_until_ready()
    assert _counts(metrics) == after


def test_threads_compiling_at_once_each_count_once(metrics):
    """The nesting rule is kept per thread: one thread's open trace must
    not swallow another's."""
    import sys
    import threading
    steps = [_fresh_step() for _ in range(12)]
    x = _argument()
    before = _counts(metrics)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=lambda f=f: f(x).block_until_ready())
                   for f in steps]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    after = _counts(metrics)
    assert [after[k] - before[k] for k in ("trace", "lower", "xla")] == \
        [12, 12, 12]


def test_a_cached_executable_is_a_cache_load(metrics, tmp_path):
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_enable_compilation_cache")
    was = {name: getattr(jax.config, name) for name in names}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
    try:
        step, x = _fresh_step(), _argument()
        step(x).block_until_ready()
        assert metrics.cache_load.count == 0        # written, not read
        misses = metrics.xla.count
        jax.clear_caches()
        step(x).block_until_ready()
    finally:
        for name, value in was.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
    assert metrics.cache_load.count >= 1
    assert metrics.cache_load.sum > 0
    # a hit is still one backend event: its own time is the key and lookup
    assert metrics.xla.count == misses + 1


def test_the_cost_models_analysis_is_its_own_histogram(metrics):
    import jax
    step, x = _fresh_step(), _argument()
    step(x).block_until_ready()
    before = _counts(metrics)
    costmodel.schedule_analysis(step, (jax.ShapeDtypeStruct(x.shape,
                                                            x.dtype),),
                                kind="probe")
    assert costmodel.drain(timeout_s=60)
    after = _counts(metrics)
    assert {k: after[k] - before[k] for k in after} == {
        "trace": 0, "lower": 0, "xla": 0, "cache_load": 0, "analysis": 1}
    assert metrics.analysis.sum > 0
    assert costmodel.costs_for(step) is not None


def test_tracing_on_keeps_compile_spans_under_the_current_span(metrics):
    step, x = _fresh_step(), _argument()
    traced_before = metrics.trace.sum
    t = tracing.Tracer(enabled=True)
    with tracing.use_tracer(t):
        t0 = time.time_ns()
        with tracing.span("step") as outer:
            step(x).block_until_ready()
        t1 = time.time_ns()
    spans = {s.name: s for s in t.spans if s.name.startswith("compile.")}
    assert set(spans) == {"compile.trace", "compile.lower", "compile.xla"}
    for s in spans.values():
        assert s.parent_id == outer.span_id
        assert s.trace_id == outer.trace_id
        assert "tpudl_probe_step" in s.attributes["program"]
        assert t0 <= s.start_ns < s.end_ns <= t1
        assert outer.start_ns <= s.start_ns and s.end_ns <= outer.end_ns
    assert spans["compile.lower"].attributes["program"] == \
        "jit(tpudl_probe_step)"
    # in order, and each span's length is its histogram's observation
    assert spans["compile.trace"].end_ns <= spans["compile.lower"].start_ns
    assert spans["compile.lower"].end_ns <= spans["compile.xla"].start_ns
    assert spans["compile.trace"].duration_s == pytest.approx(
        metrics.trace.sum - traced_before, abs=1e-6)


def test_tracing_off_keeps_no_span_and_the_histograms_grow(metrics):
    step, x = _fresh_step(), _argument()
    before = _counts(metrics)
    t = tracing.Tracer(enabled=False)
    with tracing.use_tracer(t):
        step(x).block_until_ready()
    assert t.spans == []
    after = _counts(metrics)
    assert [after[k] - before[k] for k in ("trace", "lower", "xla")] == \
        [1, 1, 1]


def test_the_analysis_span_holds_its_compiles_under_the_scheduling_span(
        metrics):
    import jax
    step, x = _fresh_step(), _argument()
    step(x).block_until_ready()
    t = tracing.Tracer(enabled=True)
    with tracing.use_tracer(t):
        with tracing.span("step") as outer:
            costmodel.schedule_analysis(
                step, (jax.ShapeDtypeStruct(x.shape, x.dtype),),
                kind="probe")
        assert costmodel.drain(timeout_s=60)
    (analysis,) = t.find("costmodel.analyze")
    assert analysis.parent_id == outer.span_id
    assert analysis.thread == "tpudl-costmodel-analyzer"
    assert analysis.attributes == {"program": "probe"}
    for s in t.spans:
        if s.name.startswith("compile."):
            assert s.parent_id == analysis.span_id
    assert metrics.analysis.sum == pytest.approx(analysis.duration_s,
                                                 abs=0.05)


def test_a_tiny_fit_compiles_nothing_after_its_first_step(metrics):
    """Cost: the listener runs only when jax compiles, and a steady step
    compiles nothing on any thread that counts."""
    from deeplearning4j_tpu.data import datasets
    from deeplearning4j_tpu.nn import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.obs import TrainingListener
    from deeplearning4j_tpu.train import Adam

    class Counts(TrainingListener):
        def __init__(self):
            self.seen = []

        def iteration_done(self, model, iteration, epoch, score):
            self.seen.append(sum(getattr(metrics, n).count
                                 for n in COMPILES))

    conf = (NeuralNetConfiguration.builder().seed(5).updater(Adam(1e-3))
            .list()
            .layer(DenseLayer(n_out=6, activation="relu"))
            .layer(OutputLayer(n_out=10, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(784)).build())
    counts = Counts()
    MultiLayerNetwork(conf).init().fit(datasets.mnist(batch_size=64, train=True, n_synthetic=256),
               epochs=2, listeners=[counts])
    assert len(counts.seen) == 8
    assert len(set(counts.seen[1:])) == 1, counts.seen


SMALL_TRACE = os.path.join(os.path.dirname(__file__), os.pardir, "benchmark",
                           "tests", "small_trace.xplane.pb")
SMALL_TRACE_START_NS = 1790762435962424595      # its profile_start_time


@pytest.mark.parametrize("parent", ["step", "step.dispatch"])
def test_timeline_names_a_gap_a_compile_covers(parent):
    """small_trace.xplane.pb's longest device gap, 41.2 ms at 68.0 ms,
    under a span whose ``compile.trace`` child covers most of it: the gap
    is named by the child under its parent, with no change to
    ``timeline``.  A compile inside the loop's jitted call sits under
    ``step.dispatch``."""
    from deeplearning4j_tpu.obs.profiler import timeline

    def span(name, span_id, parent_id, a_ms, b_ms):
        return {"name": name, "span_id": span_id, "parent_id": parent_id,
                "tid": 1, "thread": "MainThread",
                "start_ns": SMALL_TRACE_START_NS + int(a_ms * 1e6),
                "end_ns": SMALL_TRACE_START_NS + int(b_ms * 1e6),
                "attributes": {"program": "jit(tpudl_train_step)"}}
    spans = [span(parent, "s1", None, 67.0, 109.5),
             span("compile.trace", "c1", "s1", 68.5, 100.5)]
    gaps = timeline(SMALL_TRACE, spans)["gaps"]
    assert [s[:2] for s in gaps[0]["spans"]] == [
        [f"{parent}>compile.trace", "MainThread"], [parent, "MainThread"]]
    assert gaps[0]["spans"][0][2] == pytest.approx(0.777, abs=2e-3)
