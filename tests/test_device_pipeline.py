"""Device-feed pipeline: async prefetch, shape-bucketing recompile
guard, tBPTT tail padding, the process-level step cache, and the TPU307
lint rule (ISSUE 3 acceptance: one-compile epochs proven via jit cache
stats, bucketed loss == unpadded loss to 1e-6)."""

import threading
import time

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.config import set_config
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.device_pipeline import (
    DeviceFeeder, FedBatch, choose_bucket, ensure_feature_mask,
    pad_segment, pad_to_bucket, synth_example_mask)
from deeplearning4j_tpu.data.iterators import (
    ArrayDataSetIterator, AsyncDataSetIterator, ListDataSetIterator)
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.layers.recurrent import LSTM, RnnOutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.obs.registry import (
    MetricsRegistry, get_registry, set_registry)
from deeplearning4j_tpu.train import step_cache
from deeplearning4j_tpu.train.trainer import (
    Trainer, _tbptt_segments, make_loss_fn)
from deeplearning4j_tpu.train.updaters import Sgd


@pytest.fixture
def registry():
    """Isolated process-wide registry (restored afterwards) so counter
    assertions aren't polluted by other tests."""
    prev = set_registry(MetricsRegistry())
    try:
        yield get_registry()
    finally:
        set_registry(prev)


@pytest.fixture(autouse=True)
def _default_pipeline_config():
    """Pin the pipeline knobs to defaults for every test here (some
    tests flip them) and leave the step cache clean."""
    set_config(device_feed=True, shape_bucketing=True, prefetch_size=2)
    yield
    set_config(device_feed=True, shape_bucketing=True, prefetch_size=2)


def _mlp_conf(seed, n_in=6, n_hidden=16, n_out=3, lr=0.05):
    return (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(lr))
            .list()
            .layer(DenseLayer(n_out=n_hidden, activation="tanh"))
            .layer(OutputLayer(n_out=n_out, activation="softmax"))
            .set_input_type(InputType.feed_forward(n_in)).build())


def _mlp_data(n, n_in=6, n_out=3, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, n_in)).astype(np.float32)
    y = np.eye(n_out, dtype=np.float32)[rng.integers(0, n_out, n)]
    return x, y


# ------------------------------------------------------- recompile guard
def test_ragged_epoch_compiles_train_step_once(registry):
    """103 examples at batch 32 → tail of 7, padded to the 32 bucket:
    the donating train step traces exactly ONE program."""
    x, y = _mlp_data(103)
    net = MultiLayerNetwork(_mlp_conf(seed=11)).init()
    trainer = Trainer(net)
    trainer.fit(ArrayDataSetIterator(x, y, batch_size=32), epochs=2)
    assert trainer._step._cache_size() == 1
    assert registry.counter("tpudl_train_recompiles_total").value == 1
    # real example count, not the padded shape
    assert registry.counter("tpudl_train_examples_total").value == 206
    # 4 steps/epoch (incl. the padded tail), 2 epochs
    assert registry.counter("tpudl_train_steps_total").value == 8


def test_ragged_epoch_recompiles_without_bucketing(registry):
    """Control: with the guard off, the 7-row tail compiles a second
    program — the cliff the bucket removes."""
    set_config(shape_bucketing=False)
    x, y = _mlp_data(103)
    net = MultiLayerNetwork(_mlp_conf(seed=12)).init()
    trainer = Trainer(net)
    trainer.fit(ArrayDataSetIterator(x, y, batch_size=32), epochs=1)
    assert trainer._step._cache_size() == 2
    assert registry.counter("tpudl_train_recompiles_total").value == 2


def test_bucketed_loss_matches_unpadded():
    x, y = _mlp_data(7, seed=3)
    net = MultiLayerNetwork(_mlp_conf(seed=13, lr=0.0)).init()
    trainer = Trainer(net)
    plain = float(trainer.eval_loss(DataSet(x, y)))
    padded, real = pad_to_bucket(DataSet(x, y), 32)
    assert real == 7
    assert padded.features.shape[0] == 32
    assert float(np.sum(np.asarray(padded.labels_mask))) == 7.0
    assert abs(float(trainer.eval_loss(padded)) - plain) <= 1e-6


def test_padded_rows_contribute_zero_gradient():
    """Grad of the padded+masked batch == grad of the unpadded batch."""
    x, y = _mlp_data(7, seed=4)
    net = MultiLayerNetwork(_mlp_conf(seed=14)).init()
    loss_fn = make_loss_fn(net)

    def grads_for(batch):
        (_, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            net.params_, net.state_, batch.features, batch.labels,
            batch.features_mask, batch.labels_mask, None)
        return grads

    g_plain = grads_for(DataSet(x, y))
    padded, _ = pad_to_bucket(DataSet(x, y), 32)
    g_padded = grads_for(padded)
    flat_a = jax.flatten_util.ravel_pytree(g_plain)[0]
    flat_b = jax.flatten_util.ravel_pytree(g_padded)[0]
    np.testing.assert_allclose(np.asarray(flat_a), np.asarray(flat_b),
                               atol=1e-6)


def test_bucketed_training_matches_mean_semantics():
    """End-to-end: fitting the ragged epoch with bucketing produces the
    same parameters as fitting with the guard off (masked mean divides
    by the real count — DL4J mini_batch=True semantics)."""
    x, y = _mlp_data(39, seed=5)

    def fit(bucketing, seed):
        set_config(shape_bucketing=bucketing, device_feed=bucketing)
        net = MultiLayerNetwork(_mlp_conf(seed=seed)).init()
        Trainer(net).fit(ArrayDataSetIterator(x, y, batch_size=16),
                         epochs=2)
        return jax.flatten_util.ravel_pytree(net.params_)[0]

    # identical seed → identical init; only the pipeline differs
    p_on = fit(True, seed=15)
    p_off = fit(False, seed=15)
    np.testing.assert_allclose(np.asarray(p_on), np.asarray(p_off),
                               atol=1e-5)


# ------------------------------------------------------------ tBPTT tail
def _rnn_conf(seed, n_in=5, n_out=4, fwd=4):
    return (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(0.01))
            .list()
            .layer(LSTM(n_out=12))
            .layer(RnnOutputLayer(n_out=n_out, activation="softmax"))
            .set_input_type(InputType.recurrent(n_in))
            .backprop_type("tbptt", fwd_length=fwd, back_length=fwd)
            .build())


def test_tbptt_nondivisible_compiles_once():
    """T=10 at tbptt_fwd_length=4 → segments 4,4,2; the tail pads to 4
    with a masked tail and the tBPTT step traces exactly ONE program."""
    rng = np.random.default_rng(1)
    xs = rng.normal(size=(8, 10, 5)).astype(np.float32)
    ys = np.eye(4, dtype=np.float32)[rng.integers(0, 4, (8, 10))]
    net = MultiLayerNetwork(_rnn_conf(seed=16)).init()
    trainer = Trainer(net)
    trainer.fit(ListDataSetIterator([DataSet(xs, ys)]), epochs=2)
    assert trainer._tbptt_step._cache_size() == 1


def test_tbptt_padded_tail_loss_matches_unpadded():
    rng = np.random.default_rng(2)
    xs = rng.normal(size=(8, 10, 5)).astype(np.float32)
    ys = np.eye(4, dtype=np.float32)[rng.integers(0, 4, (8, 10))]
    net = MultiLayerNetwork(_rnn_conf(seed=17)).init()
    loss_fn = make_loss_fn(net, with_carries=True)
    from deeplearning4j_tpu.nn.layers.recurrent import BaseRecurrentLayer
    carries = [l.init_carry(8, np.float32)
               if isinstance(l, BaseRecurrentLayer) else None
               for l in net.layers]
    batch = DataSet(xs, ys)
    padded = list(_tbptt_segments(ensure_feature_mask(batch), 4))
    raw = list(_tbptt_segments(batch, 4, pad_tail=False))
    assert padded[-1].features.shape[1] == 4       # tail 2 → 4
    assert raw[-1].features.shape[1] == 2
    for seg_p, seg_r in zip(padded, raw):
        loss_p, (_, carries_p) = loss_fn(
            net.params_, net.state_, carries, seg_p.features, seg_p.labels,
            seg_p.features_mask, seg_p.labels_mask, None)
        loss_r, (_, carries_r) = loss_fn(
            net.params_, net.state_, carries, seg_r.features, seg_r.labels,
            seg_r.features_mask, seg_r.labels_mask, None)
        assert abs(float(loss_p) - float(loss_r)) <= 1e-6
        # masked steps are carry-through: padded-tail carries == unpadded
        for cp, cr in zip(carries_p, carries_r):
            if cp is None:
                continue
            for a, b in zip(jax.tree_util.tree_leaves(cp),
                            jax.tree_util.tree_leaves(cr)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-6)
        carries = carries_r


# ------------------------------------------------------------- the feeder
def test_feeder_yields_all_batches_in_order(registry):
    x, y = _mlp_data(103, seed=6)
    feeder = DeviceFeeder(depth=2)
    fed = list(feeder.feed(ArrayDataSetIterator(x, y, batch_size=32)))
    assert [f.n_examples for f in fed] == [32, 32, 32, 7]
    assert all(isinstance(f, FedBatch) for f in fed)
    assert [f.batch.features.shape[0] for f in fed] == [32, 32, 32, 32]
    assert fed[-1].padded == 25
    # sticky bucket: first batch defined the one static shape
    assert feeder.buckets == (32,)
    # metrics flowed
    assert registry.histogram("tpudl_data_etl_wait_seconds").count == 4
    # real rows ride through unchanged
    np.testing.assert_allclose(
        np.asarray(fed[-1].batch.features)[:7], x[96:])


def test_feeder_abandonment_stops_producer():
    x, y = _mlp_data(400, seed=7)
    feeder = DeviceFeeder(depth=2)
    before = threading.active_count()
    for i, _ in enumerate(feeder.feed(ArrayDataSetIterator(x, y, 10))):
        if i == 2:
            break
    deadline = time.time() + 5.0
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.01)
    assert threading.active_count() <= before


def test_feeder_propagates_producer_errors():
    def gen():
        yield DataSet(*_mlp_data(4, seed=8))
        raise RuntimeError("ETL exploded")

    feeder = DeviceFeeder(bucketing=False)
    with pytest.raises(RuntimeError, match="ETL exploded"):
        list(feeder.feed(gen()))


def test_feeder_producer_failure_hygiene():
    """Producer-thread death mid-epoch: the consumer re-raises the
    ORIGINAL exception object (traceback intact, pointing into the ETL
    generator), the queue drains, and the daemon thread exits — no
    leaked threads across tests."""
    import traceback

    def gen():
        for i in range(4):
            yield DataSet(*_mlp_data(4, seed=8))
        raise RuntimeError("ETL exploded at batch 4")

    feeder = DeviceFeeder(bucketing=False, depth=2)
    before = threading.active_count()
    with pytest.raises(RuntimeError, match="ETL exploded") as exc_info:
        for _ in feeder.feed(gen()):
            pass
    frames = traceback.extract_tb(exc_info.value.__traceback__)
    assert any(f.name == "gen" for f in frames), (
        "original producer traceback was lost in the thread handoff")
    deadline = time.time() + 5.0
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.01)
    assert threading.active_count() <= before, "feeder thread leaked"


def test_feeder_spans_cover_their_work_on_the_thread_that_does_it(registry):
    """``feed.source`` and ``feed.stage`` on the producer's thread, each
    as long as the work it names (a slow iterator, a slow placement, a
    retried stage), ``feed.wait`` on the consumer's, and a histogram at
    each of the three boundaries."""
    from deeplearning4j_tpu.obs import tracing
    from deeplearning4j_tpu.resilience import faults

    def slow_source():
        for i in range(3):
            time.sleep(0.02)
            yield DataSet(*_mlp_data(4, seed=i))

    def slow_place(batch):
        time.sleep(0.03)
        return batch

    t = tracing.Tracer(enabled=True)
    with tracing.use_tracer(t), tracing.span("epoch") as epoch, \
            faults.inject("feeder.stage@1:error"):    # one retried attempt
        fed = list(DeviceFeeder(slow_place, bucketing=False, depth=1)
                   .feed(slow_source()))
    assert len(fed) == 3
    me = threading.current_thread().name
    sources = [s for s in t.find("feed.source")
               if not s.attributes.get("exhausted")]
    stages, waits = t.find("feed.stage"), t.find("feed.wait")
    assert len(sources) == len(stages) == 3
    assert len(waits) == 4                 # the last one finds the end
    for s in sources + stages:
        assert s.thread == "tpudl-device-feeder" and s.thread != me
        assert s.parent_id == epoch.span_id and s.trace_id == epoch.trace_id
    assert all(s.duration_s >= 0.02 for s in sources)
    assert all(s.duration_s >= 0.03 for s in stages)
    assert [s.attributes["n_examples"] for s in stages] == [4, 4, 4]
    # the retry (fault, back-off, second placement) is inside its stage
    retried = max(stages, key=lambda s: s.duration_s)
    attempts = [s for s in t.find("retry_attempt")
                if s.parent_id == retried.span_id]
    assert len(attempts) == 2 and retried.duration_s >= 0.05
    for s in waits:
        assert s.thread == me and s.parent_id == epoch.span_id
        assert s.end_ns > s.start_ns
    # the consumer waited for the first batch: from inside its draw (the
    # producer's thread gets a few ms of head start under a busy
    # interpreter, so not from the draw's first instant) to the end of
    # its staging, so the stage's 30 ms at least
    first_source = min(sources, key=lambda s: s.start_ns)
    first_stage = min(stages, key=lambda s: s.start_ns)
    assert waits[0].start_ns < first_source.end_ns
    assert waits[0].end_ns >= first_stage.end_ns
    assert waits[0].duration_s >= 0.03
    assert waits[0].attributes["wait_ms"] == pytest.approx(
        waits[0].duration_s * 1e3, abs=1.0)
    for name, least in (("tpudl_data_source_seconds", 0.06),
                        ("tpudl_data_stage_seconds", 0.09),
                        ("tpudl_data_etl_wait_seconds", 0.05)):
        h = registry.histogram(name)
        assert h.count == 3 and h.sum >= least, name


def test_bucket_helpers():
    assert choose_bucket(7, (32, 64)) == 32
    assert choose_bucket(33, (32, 64)) == 64
    assert choose_bucket(100, (32, 64)) == 100
    m = synth_example_mask(np.zeros((7, 3)), real=5, total=7)
    assert m.shape == (7,) and m.sum() == 5
    m3 = synth_example_mask(np.zeros((4, 9, 3)), real=2, total=4)
    assert m3.shape == (4, 9) and m3.sum() == 18
    seg = pad_segment(DataSet(np.ones((2, 3, 5), np.float32),
                              features_mask=np.ones((2, 3), np.float32)), 8)
    assert seg.features.shape == (2, 8, 5)
    assert seg.features_mask.shape == (2, 8)
    assert float(seg.features_mask[:, 3:].sum()) == 0.0


# --------------------------------------------------- async iterator rework
def test_async_iterator_resets_etl_wait_per_epoch(registry):
    x, y = _mlp_data(50, seed=9)
    it = AsyncDataSetIterator(ArrayDataSetIterator(x, y, 10), queue_size=2)
    for _ in it:
        time.sleep(0.002)   # make the producer's head start measurable
    first_epoch = it.etl_wait_s
    assert len(list(it)) == 5     # second epoch works after reset
    assert it.etl_wait_s >= 0.0
    assert first_epoch >= 0.0
    # per-epoch reset: the attribute is NOT cumulative across epochs
    assert registry.histogram("tpudl_data_etl_wait_seconds").count == 10


def test_async_iterator_no_thread_leak_on_break():
    x, y = _mlp_data(1000, seed=10)
    it = AsyncDataSetIterator(ArrayDataSetIterator(x, y, 10), queue_size=2)
    before = threading.active_count()
    for i, _ in enumerate(it):
        if i == 3:
            break
    deadline = time.time() + 5.0
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.01)
    assert threading.active_count() <= before


# ------------------------------------------------------------- step cache
def test_step_cache_shared_across_trainers(registry):
    conf = _mlp_conf(seed=18)
    t1 = Trainer(MultiLayerNetwork(conf).init())
    t1._ensure_ready()
    t2 = Trainer(MultiLayerNetwork(conf).init())
    t2._ensure_ready()
    assert t1._step is t2._step
    assert registry.counter("tpudl_train_step_cache_hits_total").value >= 1
    # fitting BOTH trainers still traces one program (same step object)
    x, y = _mlp_data(32, seed=11)
    key = jax.random.key(0)
    float(t1.fit_batch(DataSet(x, y), key))
    float(t2.fit_batch(DataSet(x, y), key))
    assert t1._step._cache_size() == 1


def test_step_cache_key_names_the_policy_dtypes():
    """A policy of dtype objects (what a checkpoint's restore sets) keys
    the step as the default policy of scalar types does."""
    from deeplearning4j_tpu.config import (DTypePolicy, dtype_policy,
                                           set_dtype_policy)
    net = MultiLayerNetwork(_mlp_conf(seed=19)).init()
    was = dtype_policy()
    try:
        set_dtype_policy(DTypePolicy.f32())
        default = step_cache.net_signature(net)
        set_dtype_policy(DTypePolicy(*(np.dtype("float32"),) * 3))
        restored = step_cache.net_signature(net)
        set_dtype_policy(DTypePolicy.bf16())
        mixed = step_cache.net_signature(net)
    finally:
        set_dtype_policy(was)
    assert default == restored
    assert default[2:] == ("float32",) * 3
    assert mixed != default


def test_step_cache_distinct_configs_do_not_collide():
    t1 = Trainer(MultiLayerNetwork(_mlp_conf(seed=19)).init())
    t2 = Trainer(MultiLayerNetwork(_mlp_conf(seed=19, n_hidden=32)).init())
    t1._ensure_ready()
    t2._ensure_ready()
    assert t1._step is not t2._step


def test_step_cache_opts_out_for_per_layer_updaters():
    from deeplearning4j_tpu.train.updaters import Adam
    conf = (NeuralNetConfiguration.builder().seed(20).updater(Sgd(0.01))
            .list()
            .layer(DenseLayer(n_out=8, activation="relu",
                              updater=Adam(0.05)))
            .layer(OutputLayer(n_out=3, activation="softmax"))
            .set_input_type(InputType.feed_forward(6)).build())
    trainer = Trainer(MultiLayerNetwork(conf).init())
    assert trainer._cache_sig is None
    assert trainer._step_key("train") is None


def test_eval_loss_reuses_cached_step():
    conf = _mlp_conf(seed=21)
    x, y = _mlp_data(16, seed=12)
    t1 = Trainer(MultiLayerNetwork(conf).init())
    float(t1.eval_loss(DataSet(x, y)))
    t2 = Trainer(MultiLayerNetwork(conf).init())
    float(t2.eval_loss(DataSet(x, y)))
    assert t1._eval_loss_fn is t2._eval_loss_fn
    assert t1._eval_loss_fn._cache_size() == 1


# ------------------------------------------------------------ TPU307 lint
def test_tpu307_flags_inline_transfer_in_training_loop(tmp_path):
    from deeplearning4j_tpu.analyze.lint import lint_paths
    bad = tmp_path / "bad_loop.py"
    bad.write_text(
        "import jax\n"
        "import jax.numpy as jnp\n"
        "def train(step, iterator, params):\n"
        "    for batch in iterator:\n"
        "        params = step(params, jnp.asarray(batch.features),\n"
        "                      jax.device_put(batch.labels))\n"
        "    return params\n")
    report = lint_paths([str(bad)])
    hits = report.by_rule("TPU307")
    assert len(hits) == 2
    assert all("bypasses the device feeder" in d.message for d in hits)
    assert report.exit_code() == 1


def test_tpu307_clean_cases(tmp_path):
    from deeplearning4j_tpu.analyze.lint import lint_paths
    ok = tmp_path / "ok_loop.py"
    ok.write_text(
        "import jax.numpy as jnp\n"
        "from deeplearning4j_tpu.data.device_pipeline import DeviceFeeder\n"
        "def train(step, iterator, params):\n"
        "    feeder = DeviceFeeder(lambda b: jnp.asarray(b))\n"
        "    for fed in feeder.feed(iterator):\n"
        "        params = step(params, fed.batch)\n"
        "    return params\n"
        "def setup(arrays):\n"
        "    out = []\n"
        "    for a in arrays:           # no step call in this loop\n"
        "        out.append(jnp.asarray(a))\n"
        "    return out\n")
    report = lint_paths([str(ok)])
    assert report.by_rule("TPU307") == []


# ------------------------------------------------------- persistent cache
@pytest.fixture
def cache_dir_restored():
    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_traceback_in_locations_limit)
    yield
    jax.config.update("jax_compilation_cache_dir", prev[0])
    jax.config.update("jax_traceback_in_locations_limit", prev[1])


def test_compile_cache_placed_from_outside_is_untouched(
        tmp_path, monkeypatch, cache_dir_restored):
    from deeplearning4j_tpu import config as config_mod
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append(name))
    assert config_mod.place_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in calls


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                cache_dir_restored):
    import os

    from deeplearning4j_tpu import config as config_mod
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    assert config_mod.place_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert config_mod.place_compile_cache() == want     # not pid/time made
    # cache keys of programs that hold a Pallas kernel must not depend on
    # the Python call stack that traced them: one frame, the innermost
    assert jax.config.jax_traceback_in_locations_limit == 1
    # ... and a named scope must still reach the operation's name in the
    # compiled program, where the device trace reads it
    import jax.numpy as jnp

    def layer(x):
        with jax.named_scope("res2_0_a_conv"):
            return jnp.tanh(x)

    def caller(x):
        return layer(x)
    assert jax.config.jax_include_full_tracebacks_in_locations
    lowered = jax.jit(caller).lower(jnp.ones((4,)))
    text = lowered.as_text(debug_info=True)
    assert 'loc("jit(caller)/res2_0_a_conv/tanh"' in text
    assert '"caller"' not in text.split("#loc", 1)[1]   # no outer frame
    assert 'op_name="jit(caller)/res2_0_a_conv/tanh"' in \
        lowered.compile().as_text()
