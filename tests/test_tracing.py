"""Span tracing tests: nesting, exports, cross-process context
propagation, and the trainer's fit/epoch/step emission (all CPU)."""

import json
import os

import numpy as np
import pytest

from deeplearning4j_tpu.config import set_config
from deeplearning4j_tpu.obs import tracing


@pytest.fixture
def tracer():
    t = tracing.Tracer(enabled=True)
    with tracing.use_tracer(t):
        yield t


def test_span_nesting_and_attributes(tracer):
    with tracing.span("fit", model="test"):
        with tracing.span("epoch", epoch=0):
            with tracing.span("step", iteration=3) as s:
                s.set_attribute("score", 1.25)
    spans = {s.name: s for s in tracer.spans}
    assert set(spans) == {"fit", "epoch", "step"}
    assert spans["step"].parent_id == spans["epoch"].span_id
    assert spans["epoch"].parent_id == spans["fit"].span_id
    assert spans["fit"].parent_id is None
    # one trace, durations contain each other
    assert len({s.trace_id for s in tracer.spans}) == 1
    assert spans["fit"].duration_s >= spans["epoch"].duration_s \
        >= spans["step"].duration_s >= 0
    assert spans["step"].attributes == {"iteration": 3, "score": 1.25}


def test_disabled_tracing_is_noop():
    t = tracing.Tracer(enabled=False)
    with tracing.use_tracer(t):
        with tracing.span("fit") as s:
            assert s is tracing.NULL_SPAN
            s.set_attribute("x", 1)          # no-op surface
            assert tracing.current_span() is None
    assert t.spans == []


def test_sibling_spans_share_parent(tracer):
    with tracing.span("step"):
        with tracing.span("encode"):
            pass
        with tracing.span("exchange"):
            pass
    step = tracer.find("step")[0]
    assert tracer.find("encode")[0].parent_id == step.span_id
    assert tracer.find("exchange")[0].parent_id == step.span_id


def test_explicit_parent_for_thread_hops(tracer):
    # a worker thread has no ambient context — the parent rides explicitly
    with tracing.span("step") as sp:
        ctx = sp.context()
    with tracing.span("slice", parent=ctx) as child:
        pass
    assert child.parent_id == ctx.span_id
    assert child.trace_id == ctx.trace_id


def test_chrome_trace_export_is_valid(tracer, tmp_path):
    with tracing.span("fit"):
        with tracing.span("step", iteration=0):
            pass
    path = tracer.export_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    assert len(events) == 2
    for ev in events:
        assert ev["ph"] == "X" and ev["cat"] == "tpudl"
        assert isinstance(ev["ts"], float) and isinstance(ev["dur"], float)
        assert ev["dur"] >= 0
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        assert "span_id" in ev["args"]
    by_name = {ev["name"]: ev for ev in events}
    # child event temporally contained in the parent event
    fit, step = by_name["fit"], by_name["step"]
    assert fit["ts"] <= step["ts"]
    assert fit["ts"] + fit["dur"] >= step["ts"] + step["dur"] - 1e-3
    assert step["args"]["parent_id"] == fit["args"]["span_id"]


def test_jsonl_export(tracer, tmp_path):
    with tracing.span("fit", k="v"):
        pass
    path = tracer.export_jsonl(str(tmp_path / "spans.jsonl"))
    lines = [json.loads(l) for l in open(path)]
    assert len(lines) == 1
    rec = lines[0]
    assert rec["name"] == "fit" and rec["attributes"] == {"k": "v"}
    assert rec["duration_s"] >= 0 and rec["parent_id"] is None


def test_jsonl_export_is_incremental(tracer, tmp_path):
    """Periodic flushing must not duplicate spans (per-path high-water)."""
    path = str(tmp_path / "spans.jsonl")
    with tracing.span("a"):
        pass
    tracer.export_jsonl(path)
    tracer.export_jsonl(path)                 # nothing new → no dupes
    with tracing.span("b"):
        pass
    tracer.export_jsonl(path)
    names = [json.loads(l)["name"] for l in open(path)]
    assert names == ["a", "b"]


def test_context_inject_extract_roundtrip(tracer):
    assert tracing.inject() is None          # no active span
    with tracing.span("parent") as p:
        raw = tracing.inject()
    ctx = tracing.extract(raw)
    assert ctx.trace_id == p.trace_id and ctx.span_id == p.span_id
    assert tracing.extract(None) is None
    assert tracing.extract("not json{") is None


def test_cross_process_context_via_env(tracer, monkeypatch):
    """The launcher hands DL4J_TPU_TRACE_CONTEXT to workers; a fresh
    Tracer in the child process parents its root spans under the
    launcher's span — simulated here by re-reading the env."""
    with tracing.span("launcher") as p:
        env = tracing.propagation_env()
    assert env["DL4J_TPU_TRACING"] == "1"
    monkeypatch.setenv(tracing.TRACE_CONTEXT_ENV,
                       env[tracing.TRACE_CONTEXT_ENV])
    child = tracing.Tracer(enabled=True)     # what the worker builds
    with tracing.use_tracer(child):
        with tracing.span("worker_root") as w:
            pass
    assert w.trace_id == p.trace_id
    assert w.parent_id == p.span_id
    # malformed env never breaks a worker
    monkeypatch.setenv(tracing.TRACE_CONTEXT_ENV, "}{garbage")
    assert tracing.Tracer(enabled=True)._remote_parent is None


def test_device_sync_attribution(tracer):
    import jax.numpy as jnp
    with tracing.span("step") as s:
        out = tracing.device_sync(jnp.ones((8,)) * 2)
    assert float(out[0]) == 2.0
    assert s.device_sync_s >= 0


def _mlp():
    from deeplearning4j_tpu.nn import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.train import Adam

    conf = (NeuralNetConfiguration.builder().seed(7).updater(Adam(1e-3))
            .list()
            .layer(DenseLayer(n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=10, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(784)).build())
    return MultiLayerNetwork(conf).init()


def _mnist():
    from deeplearning4j_tpu.data import datasets
    return datasets.mnist(batch_size=64, train=True, n_synthetic=192)


def _bert_batches(n=3, batch=2, seq=8, vocab=50):
    rng = np.random.default_rng(0)
    return [{"input_ids": rng.integers(0, vocab, (batch, seq), np.int32),
             "labels": rng.integers(0, vocab, (batch, seq), np.int32),
             "label_weights": (rng.random((batch, seq)) < 0.3).astype(
                 np.float32)} for _ in range(n)]


def _tiny_bert():
    from deeplearning4j_tpu.models.bert import BertConfig, BertForMaskedLM
    return BertForMaskedLM(BertConfig(
        vocab_size=50, hidden_size=16, num_layers=2, num_heads=2,
        intermediate_size=32, max_position=8), seed=3)


N_STEPS = 6          # 192/64 MNIST batches, or 3 BERT batches, x 2 epochs


def _fit(which, listeners=None):
    if which == "trainer":
        _mlp().fit(_mnist(), epochs=2, listeners=listeners)
    else:
        _tiny_bert().fit(_bert_batches(), epochs=2, listeners=listeners)


def test_multilayer_fit_emits_step_spans():
    """Smoke: MultiLayerNetwork.fit under tracing produces nested
    fit → epoch → step spans with model attrs (acceptance criterion)."""
    net = _mlp()
    t = tracing.Tracer(enabled=True)
    with tracing.use_tracer(t):
        net.fit(_mnist(), epochs=2)

    fits = t.find("fit")
    epochs = t.find("epoch")
    steps = t.find("step")
    assert len(fits) == 1 and len(epochs) == 2
    assert len(steps) == 6                    # 192/64 batches × 2 epochs
    assert all(e.parent_id == fits[0].span_id for e in epochs)
    epoch_ids = {e.span_id for e in epochs}
    assert all(s.parent_id in epoch_ids for s in steps)
    assert fits[0].attributes["model"] == "MultiLayerNetwork"
    assert fits[0].attributes["params"] == net.num_params()
    assert steps[0].attributes.get("compile") is True
    assert not any(s.attributes.get("compile") for s in steps[1:])
    # a span never reads the loss: nothing on it needs the device
    assert all(set(s.attributes) <= {"iteration", "epoch", "compile"}
               for s in steps)
    assert all(s.device_sync_s == 0 for s in steps)


@pytest.mark.parametrize("which", ["trainer", "bert"])
def test_fit_emits_the_whole_span_tree(which):
    """Every span of the table: opened before and closed after the work,
    under the right parent, on the thread that does it."""
    import threading
    t, n_steps = tracing.Tracer(enabled=True), N_STEPS
    with tracing.use_tracer(t):
        _fit(which)
    loop = threading.current_thread().name
    by_id = {s.span_id: s for s in t.spans}
    assert all(s.end_ns > s.start_ns for s in t.spans)
    steps = t.find("step")
    assert len(steps) == n_steps
    for name in ("step.dispatch", "step.read"):
        children = t.find(name)
        assert len(children) == n_steps
        for c in children:
            parent = by_id[c.parent_id]
            assert parent.name == "step" and c.thread == loop
            assert parent.start_ns <= c.start_ns < c.end_ns <= parent.end_ns
    for s in steps:
        kids = sorted((c for c in t.spans if c.parent_id == s.span_id),
                      key=lambda c: c.start_ns)
        assert [c.name for c in kids] == ["step.dispatch", "step.read"]
        assert kids[0].end_ns <= kids[1].start_ns
        assert by_id[s.parent_id].name == "epoch" and s.thread == loop
    # the feeder: wait on the loop, source and stage on the producer,
    # all three in the epoch's trace
    waits = [s for s in t.find("feed.wait") if "n_examples" in s.attributes]
    assert len(waits) == n_steps
    assert all(s.thread == loop and by_id[s.parent_id].name == "epoch"
               and "wait_ms" in s.attributes for s in waits)
    stages = t.find("feed.stage")
    sources = [s for s in t.find("feed.source")
               if not s.attributes.get("exhausted")]
    assert len(stages) == len(sources) == n_steps
    for s in stages + sources:
        assert s.thread == "tpudl-device-feeder" != loop
        assert by_id[s.parent_id].name == "epoch"
        assert s.trace_id == steps[0].trace_id
    assert not t.find("feed")                 # the zero-length span went


@pytest.mark.parametrize("which", ["trainer", "bert"])
def test_tracing_never_syncs_and_changes_no_loss(which, monkeypatch):
    """Turning the tracer on must not change the program it traces: no
    device_sync, no block_until_ready on the step path, the same losses."""
    import jax
    from deeplearning4j_tpu.obs import CollectScoresListener
    calls = {"device_sync": 0, "block_until_ready": 0}
    real_sync, real_block = tracing.device_sync, jax.block_until_ready

    def counted(name, fn):
        def wrapper(*a, **k):
            calls[name] += 1
            return fn(*a, **k)
        return wrapper
    losses = {}
    for traced in (False, True):
        seen = CollectScoresListener()
        t = tracing.Tracer(enabled=traced)
        with tracing.use_tracer(t):
            monkeypatch.setattr(tracing, "device_sync",
                                counted("device_sync", real_sync))
            monkeypatch.setattr(jax, "block_until_ready",
                                counted("block_until_ready", real_block))
            _fit(which, listeners=[seen])
            monkeypatch.undo()
        losses[traced] = seen.scores
        assert bool(t.spans) == traced
    assert calls == {"device_sync": 0, "block_until_ready": 0}
    assert len(losses[True]) == N_STEPS and losses[True] == losses[False]


# ---- who reads the loss in BertForMaskedLM.fit (Trainer's form: the loop
# hands out the device scalar and converts nothing but fit's return value)

class _CountedLoss:
    """Stands in for the step's loss: counts the conversions to float."""

    def __init__(self, loss, conversions: list):
        self.loss, self.conversions = loss, conversions

    def __float__(self):
        self.conversions.append(1)
        return float(self.loss)


class _EveryKth:
    """A listener that converts every ``k``-th score, as
    ``ScoreIterationListener(k)`` does for a user who logs it."""

    def __init__(self, k: int):
        self.k, self.steps, self.scores = k, 0, []

    def iteration_done(self, model, iteration, epoch, score):
        self.steps += 1
        if self.steps % self.k == 0:
            self.scores.append(float(score))


def _bert_with_counted_loss(conversions: list):
    """A tiny BERT whose jitted step is wrapped: same step, but the loss it
    hands the loop counts every ``float()`` taken of it (the transfer guard
    does not fire on the CPU backend, so conversions are counted)."""
    from deeplearning4j_tpu.train import Adam
    model, updater = _tiny_bert(), Adam(1e-3)
    step = model.make_train_step(updater.to_optax())

    def counted_step(*args):
        params, opt_state, loss = step(*args)
        return params, opt_state, _CountedLoss(loss, conversions)
    model._step = counted_step
    return model, updater


@pytest.mark.parametrize("every", [None, 1, 2, 3, 4, 7])
def test_bert_loop_converts_the_loss_as_often_as_its_listeners(every):
    """``fit`` over N batches with a listener converting every k-th score:
    N // k conversions in the loop and one at ``fit``'s return; with no
    listener exactly that one."""
    n = 6
    conversions = []
    model, updater = _bert_with_counted_loss(conversions)
    listeners = [] if every is None else [_EveryKth(every)]
    last = model.fit(_bert_batches(n), updater=updater, listeners=listeners)
    in_loop = 0 if every is None else n // every
    assert len(conversions) == in_loop + 1
    assert isinstance(last, float) and np.isfinite(last)
    for seen in listeners:
        assert seen.steps == n and len(seen.scores) == in_loop
        if n % every == 0:
            assert seen.scores[-1] == last
    assert model.iteration == n


def test_bert_listener_gets_the_device_scalar_after_the_rebind():
    """Every step hands the listeners a ``jax.Array``, never a python
    float, and only after ``model.params`` / ``model.opt_state`` were
    rebound to the step's outputs (the step donates both): readable at
    step one, and changed by it."""
    import jax
    seen = []
    model = _tiny_bert()
    before = np.asarray(
        model.params["embeddings"]["word_embeddings"]).copy()

    class Reads:
        def iteration_done(self, model, iteration, epoch, score):
            leaves = jax.tree_util.tree_leaves(
                (model.params, model.opt_state))
            seen.append({
                "score": score,
                "deleted": [leaf.is_deleted() for leaf in leaves
                            if isinstance(leaf, jax.Array)],
                "word": np.asarray(
                    model.params["embeddings"]["word_embeddings"]).copy(),
                "iteration": (iteration, model.iteration)})

    last = model.fit(_bert_batches(3), listeners=[Reads()])
    assert len(seen) == 3
    for i, got in enumerate(seen):
        assert isinstance(got["score"], jax.Array)
        assert got["score"].shape == () and got["deleted"]
        assert not any(got["deleted"])
        assert got["iteration"] == (i, i)
    # step one's parameters at step one's callback, not the initial ones
    assert not np.array_equal(seen[0]["word"], before)
    assert not np.array_equal(seen[1]["word"], seen[0]["word"])
    assert last == float(seen[-1]["score"])


def test_bert_fit_returns_a_python_float_and_nan_over_no_batches():
    import math
    from deeplearning4j_tpu.obs import CollectScoresListener
    model, seen = _tiny_bert(), CollectScoresListener()
    last = model.fit(_bert_batches(2), epochs=2, listeners=[seen])
    assert type(last) is float and last == seen.scores[-1]
    assert len(seen.scores) == 4 and seen.iterations == [0, 1, 2, 3]
    empty = model.fit([], listeners=[seen])
    assert type(empty) is float and math.isnan(empty)
    assert len(seen.scores) == 4 and model.iteration == 4


_LOOP_HISTOGRAMS = ["tpudl_train_iteration_seconds",
                    "tpudl_train_dispatch_seconds",
                    "tpudl_train_read_seconds"]
_FEED_HISTOGRAMS = ["tpudl_data_source_seconds", "tpudl_data_stage_seconds",
                    "tpudl_data_etl_wait_seconds"]


@pytest.mark.parametrize("which", ["trainer", "bert"])
def test_histograms_grow_by_the_steps_with_tracing_off(which):
    """The counters sit at the spans' boundaries and are always on; the
    loop's three leave the compile step out, together, so that their sums
    subtract."""
    from deeplearning4j_tpu.obs.registry import (MetricsRegistry,
                                                 get_registry, set_registry)
    prev = set_registry(MetricsRegistry())
    try:
        _fit(which)
        reg = get_registry()
        assert reg.counter("tpudl_train_steps_total").value == N_STEPS
        # 0 where an earlier test left the MLP's step in the step cache
        compiles = int(reg.counter("tpudl_train_recompiles_total").value)
        assert compiles in (0, 1)
        assert reg.counter("tpudl_train_examples_total").value == (
            384 if which == "trainer" else 12)
        for name in _LOOP_HISTOGRAMS:
            assert reg.histogram(name).count == N_STEPS - compiles, name
        for name in _FEED_HISTOGRAMS:
            assert reg.histogram(name).count == N_STEPS, name
        own = (reg.histogram("tpudl_train_iteration_seconds").sum
               - reg.histogram("tpudl_train_dispatch_seconds").sum
               - reg.histogram("tpudl_train_read_seconds").sum)
        assert own > 0
        assert "tpudl_train_step_seconds" not in reg.names()
    finally:
        set_registry(prev)


def test_span_clock_is_unix_nanoseconds(tracer):
    import threading
    import time
    before = time.time_ns()
    with tracing.span("a") as s:
        time.sleep(0.002)
    after = time.time_ns()
    assert before <= s.start_ns < s.end_ns <= after + 1_000_000
    assert s.end_ns - s.start_ns >= 2_000_000
    assert s.start_s == s.start_ns / 1e9 and s.end_s == s.end_ns / 1e9
    assert s.thread == threading.current_thread().name
    d = s.to_dict()
    assert (d["start_ns"], d["end_ns"], d["thread"]) == (
        s.start_ns, s.end_ns, s.thread)


def test_self_intervals_take_same_thread_children_out():
    def span(name, span_id, parent, a, b, tid=1, thread="loop"):
        return {"name": name, "span_id": span_id, "parent_id": parent,
                "start_ns": a, "end_ns": b, "tid": tid, "thread": thread}
    got = sorted(tracing.self_intervals([
        span("step", "s", None, 0, 100),
        span("step.dispatch", "d", "s", 10, 30),
        span("step.read", "r", "s", 60, 90),
        span("feed.stage", "f", "s", 20, 80, tid=2, thread="feeder"),
        {**span("open", "o", "s", 95, None)}]))
    assert got == [(0, 10, "loop", "step"),
                   (10, 30, "loop", "step>step.dispatch"),
                   (20, 80, "feeder", "feed.stage"), (30, 60, "loop", "step"),
                   (60, 90, "loop", "step>step.read"),
                   (90, 100, "loop", "step")]


# ---- the join with a device trace (obs.profiler.timeline)

SMALL_TRACE = os.path.join(os.path.dirname(__file__), os.pardir, "benchmark",
                           "tests", "small_trace.xplane.pb")
SMALL_TRACE_START_NS = 1790762435962424595      # its profile_start_time


def test_timeline_names_the_gaps_spans_cover_and_no_other():
    """small_trace.xplane.pb (recorded on a v5e in PR 26, read only): six
    90 us programs with device gaps of 41.2, 21.9 and 21.0 ms at 68.0, 46.0
    and 110.0 ms.  Synthetic spans over the first two name those two; the
    third stays unnamed."""
    from deeplearning4j_tpu.obs.profiler import timeline

    def span(name, span_id, parent, a_ms, b_ms, tid=1, thread="MainThread"):
        return {"name": name, "span_id": span_id, "parent_id": parent,
                "tid": tid, "thread": thread,
                "start_ns": SMALL_TRACE_START_NS + int(a_ms * 1e6),
                "end_ns": SMALL_TRACE_START_NS + int(b_ms * 1e6)}
    spans = [span("step", "s1", None, 68.0, 109.5),
             span("step.read", "r1", "s1", 68.5, 100.5),
             span("feed.wait", "w1", None, 45.5, 59.0),
             span("feed.stage", "g1", None, 40.0, 58.0, tid=2,
                  thread="tpudl-device-feeder")]
    got = timeline(SMALL_TRACE, spans)
    assert got["profile_start_time_ns"] == SMALL_TRACE_START_NS
    gaps = got["gaps"]
    assert [round(g["ms"], 1) for g in gaps[:3]] == [41.2, 21.9, 21.0]
    # the longest: step.read's own time covers 32 of its 41.2 ms, step's
    # own time (read taken out) the rest
    assert [s[:2] for s in gaps[0]["spans"]] == [
        ["step>step.read", "MainThread"], ["step", "MainThread"]]
    assert gaps[0]["spans"][0][2] == pytest.approx(0.777, abs=2e-3)
    assert sum(s[2] for s in gaps[0]["spans"]) == pytest.approx(1.0, abs=2e-3)
    # the second: the loop waits while the producer stages
    assert [s[:2] for s in gaps[1]["spans"]] == [
        ["feed.wait", "MainThread"], ["feed.stage", "tpudl-device-feeder"]]
    assert gaps[2]["spans"] == []
    covered = 41.17 + (59.0 - 46.006)
    assert got["idle_ms_in_gaps_over_1ms"] == pytest.approx(84.09, abs=0.01)
    assert got["named_idle_share"] == pytest.approx(covered / 84.09, abs=2e-3)
    # device time per executed program, from the XLA Modules line
    assert got["steps"] == {"jit__lambda": {
        "count": 6, "mean_ms": pytest.approx(0.090218, rel=1e-4),
        "max_ms": pytest.approx(0.09022, rel=1e-4)}}
    # the recorded lambda had no named scope: its op name is read from the
    # event metadata all the same
    assert got["scopes"][0][0] == "(unscoped)" and got["scoped_share"] == 0.0
    assert timeline(SMALL_TRACE, [])["named_idle_share"] == 0.0


def test_scope_of_an_op_name():
    from deeplearning4j_tpu.obs.profiler import _op_names, _scope
    assert _scope("jit(tpudl_train_step)/jvp(res2_0_a_conv)/"
                  "conv_general_dilated") == "res2_0_a_conv"
    assert _scope("jit(tpudl_train_step)/jit(main)/transpose(jvp("
                  "res2_0_a_conv))/conv_general_dilated:") == "res2_0_a_conv bwd"
    assert _scope("jit(tpudl_bert_mlm_step)/jvp(encoder_3)/attention/"
                  "softmax/exp") == "encoder_3/attention"
    assert _scope("jit(tpudl_train_step)/optimizer/add") == "optimizer"
    assert _scope("jit(<lambda>)/dot_general:") == "(unscoped)"
    assert _scope("") == "(unscoped)"
    names = _op_names(SMALL_TRACE, "/device:TPU:0")
    assert set(names.values()) == {"jit(<lambda>)/dot_general:"}
    assert _op_names(SMALL_TRACE, "/device:TPU:7") == {}


def test_profiling_with_tracing_writes_the_timeline(tmp_path):
    """The operator's recipe (DL4J_TPU_PROFILING=1 DL4J_TPU_TRACING=1) on a
    CPU: no device plane, so no gaps, but spans.jsonl and timeline.json
    are written beside the trace and fit returns."""
    from deeplearning4j_tpu.config import get_config
    prev = get_config()
    t = tracing.Tracer()
    try:
        set_config(profiling=True, tracing=True, trace_dir=str(tmp_path))
        with tracing.use_tracer(t):
            _mlp().fit(_mnist(), epochs=1)
    finally:
        set_config(profiling=prev.profiling, tracing=prev.tracing,
                   trace_dir=prev.trace_dir)
    names = [json.loads(line)["name"] for line in open(tmp_path / "spans.jsonl")]
    assert names.count("step") == 3 and names[-1] == "fit"
    with open(tmp_path / "timeline.json") as f:
        got = json.load(f)
    assert got["gaps"] == [] and got["named_idle_share"] is None
    assert got["scopes"] == [] and got["scoped_share"] is None
    # a trace jax wrote here (it names its host beside the planes) reads
    # without a device's plane too
    import glob
    from deeplearning4j_tpu.obs.profiler import _op_names
    (xplane,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
    assert _op_names(xplane, "/device:TPU:0") == {}
    assert got["profile_start_time_ns"] <= t.find("fit")[0].start_ns + 10**9


# ---- names on the device's operations

def _lowered_text(lowered) -> str:
    return lowered.as_text(debug_info=True)


def test_resnet_step_carries_every_vertex_scope():
    """The lowered tiny ResNet-50 step names itself and puts every vertex's
    name, ``loss`` and ``optimizer`` on its operations."""
    import jax
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.device_pipeline import pad_to_bucket
    from deeplearning4j_tpu.models import resnet50
    from deeplearning4j_tpu.obs import costmodel
    from deeplearning4j_tpu.train.trainer import Trainer
    net = resnet50(height=32, width=32, channels=3, num_classes=10,
                   fused=False).init()
    trainer = Trainer(net)
    trainer._ensure_ready()
    batch = DataSet(np.zeros((4, 32, 32, 3), np.float32),
                    np.eye(10, dtype=np.float32)[:4])
    placed = trainer._place_batch(pad_to_bucket(batch, 4)[0])
    text = _lowered_text(trainer._step.lower(*costmodel.abstractify(
        (net.params_, net.state_, net.opt_state, placed.features,
         placed.labels, None, placed.labels_mask, jax.random.key(0)))))
    assert "jit(tpudl_train_step)/" in text and "jit(step)" not in text
    vertices = [spec.name for spec in net._topo]
    assert len(vertices) > 100
    missing = [v for v in vertices
               if f"jvp({v})/" not in text and f"/{v}/" not in text]
    assert missing == []
    assert "transpose(jvp(res2_0_a_conv))/" in text      # the backward pass
    assert "/loss/" in text or "(loss)" in text
    assert "/optimizer/" in text


def test_bert_step_carries_encoder_scopes():
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.train import updaters
    model = _tiny_bert()
    tx = updaters.Adam(1e-3).to_optax()
    step = model.make_train_step(tx)
    b = _bert_batches(1)[0]
    text = _lowered_text(step.lower(
        model.params, tx.init(model.params), jnp.asarray(b["input_ids"]),
        jnp.asarray(b["labels"]), jnp.asarray(b["label_weights"]), None,
        jax.random.key(0, impl="rbg")))
    assert "jit(tpudl_bert_mlm_step)/" in text
    for i in range(model.config.num_layers):
        for part in ("attention", "ffn"):
            assert f"jvp(encoder_{i})/{part}/" in text, (i, part)
            assert f"transpose(jvp(encoder_{i}))/{part}/" in text, (i, part)
    for scope in ("embeddings", "mlm_head", "loss"):
        assert f"jvp({scope})/" in text, scope
    assert "/optimizer/" in text
