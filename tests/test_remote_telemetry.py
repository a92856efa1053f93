"""Telemetry federation: RemoteStatsRouter buffering/backpressure, the
UIServer ingest + /cluster surface, ClusterStore straggler detection,
and the ISSUE-7 acceptance rig — a spawn_local_cluster gang whose every
worker reports in, with a fault-injected straggler flagged on the
coordinator from federated step times alone."""

import functools
import json
import os
import socket
import sys
import time
import urllib.request

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
import cluster_workers  # noqa: E402

from deeplearning4j_tpu.obs.registry import (MetricsRegistry, get_registry,
                                             set_registry)  # noqa: E402
from deeplearning4j_tpu.obs.remote import (ClusterStore,  # noqa: E402
                                           RemoteStatsRouter)
from deeplearning4j_tpu.obs.ui_server import UIServer  # noqa: E402

_ENV = {"PYTHONPATH": os.path.dirname(__file__) + os.pathsep +
        os.environ.get("PYTHONPATH", "")}


def _get(url):
    with urllib.request.urlopen(url, timeout=5) as resp:
        return resp.read().decode()


@pytest.fixture
def registry():
    prev = set_registry(MetricsRegistry())
    yield get_registry()
    set_registry(prev)


# ===================================================== router semantics
class TestRouter:
    def test_loopback_round_trip(self, registry):
        """Records pushed through the router land in the coordinator's
        ClusterStore and on /metrics with a worker label."""
        server = UIServer(port=0)
        router = RemoteStatsRouter(server.url, worker="rt",
                                   flush_interval_s=0.02)
        try:
            for i in range(4):
                router.put_event("step", iteration=i, step_seconds=0.01,
                                 score=0.5)
            router.put({"type": "stats", "iteration": 3,
                        "params": {"0": {"norm": 1.0}}})
            deadline = time.monotonic() + 10
            summary = {}
            while time.monotonic() < deadline:
                summary = json.loads(_get(server.url + "cluster.json"))
                if summary["workers"].get("rt", {}).get("steps") == 4:
                    break
                time.sleep(0.02)
            worker = summary["workers"]["rt"]
            assert worker["steps"] == 4
            assert worker["iteration"] == 3
            assert worker["median_step_ms"] == pytest.approx(10.0)
            assert worker["liveness_age_s"] < 10
            # the full stats record rides along (dashboard replay)
            assert server.cluster.records_for("rt")
            body = _get(server.url + "metrics")
            assert 'tpudl_cluster_worker_iteration{worker="rt"} 3' in body
            assert 'tpudl_cluster_step_seconds_count{worker="rt"} 4' in body
            assert router.dropped == 0
        finally:
            router.close(timeout=2)
            server.stop()

    def test_put_is_nonblocking_and_buffer_bounded(self, registry):
        """With NO coordinator at all, producers never block and the
        buffer stays bounded (drop-oldest, counted)."""
        # a port nothing listens on: connect fails fast
        router = RemoteStatsRouter("http://127.0.0.1:9", worker="nb",
                                   flush_interval_s=10.0, max_buffer=16,
                                   timeout_s=0.2)
        try:
            t0 = time.perf_counter()
            for i in range(5000):
                router.put_event("step", iteration=i)
            elapsed = time.perf_counter() - t0
            assert elapsed < 2.0          # ~µs/append, never a network wait
            assert len(router._buf) <= 16
            # everything beyond the bounded buffer + one in-flight batch
            # is dropped AND counted
            assert router.dropped >= 5000 - 16 - 64
        finally:
            router.close(timeout=5)

    def test_stalled_coordinator_never_blocks_fit(self, registry):
        """THE off-step-path contract: a stalled (non-accepting)
        coordinator leaves fit() step timings unaffected; the worker
        exits cleanly with a bounded drop counter, never an exception."""
        import jax
        from deeplearning4j_tpu.data.dataset import DataSet
        from deeplearning4j_tpu.obs import remote
        from deeplearning4j_tpu.train.trainer import Trainer

        # a listener that never accepts: connections sit in the backlog
        # (or hang in SYN) — the worst case for a synchronous pusher
        blocked = socket.create_server(("127.0.0.1", 0), backlog=1)
        port = blocked.getsockname()[1]
        router = remote.install(f"http://127.0.0.1:{port}",
                                worker="stalled", flush_interval_s=0.02,
                                max_buffer=8, timeout_s=0.3)
        try:
            net = cluster_workers._small_net(seed=5)
            trainer = Trainer(net)
            x, y = cluster_workers.global_batch(n=16, seed=0)
            batch = DataSet(x, y)
            key = jax.random.key(0)
            trainer.step_batch(batch, key)    # compile outside the clock
            t0 = time.perf_counter()
            for _ in range(20):
                key, sub = jax.random.split(key)
                trainer.step_batch(batch, sub)
            wall = time.perf_counter() - t0
            # 20 CPU steps are milliseconds; a step path that waited on
            # the stalled socket even once would eat a 0.3s timeout
            assert wall < 3.0, f"steps took {wall:.2f}s with a stalled " \
                               f"coordinator — pushes are ON the step path"
            router.close(timeout=5.0)         # clean exit, no exception
            assert not router._thread.is_alive()
            assert router.dropped > 0         # bounded loss, counted
            assert router.dropped <= 20 + 8 + router.push_failures * 64
        finally:
            remote.close_router()
            blocked.close()

    def test_stats_listener_federates_through_router(self, registry):
        """StatsListener(storage=router): the full stats records (incl.
        the init topology) arrive on the coordinator."""
        import jax
        from deeplearning4j_tpu.data.dataset import DataSet
        from deeplearning4j_tpu.obs.stats import StatsListener
        from deeplearning4j_tpu.train.trainer import Trainer

        server = UIServer(port=0)
        router = RemoteStatsRouter(server.url, worker="sl",
                                   flush_interval_s=0.02)
        try:
            net = cluster_workers._small_net(seed=6)
            trainer = Trainer(net, listeners=[StatsListener(router,
                                                            frequency=1)])
            x, y = cluster_workers.global_batch(n=8, seed=1)
            key = jax.random.key(0)
            for _ in range(3):
                key, sub = jax.random.split(key)
                trainer.step_batch(DataSet(x, y), sub)
            deadline = time.monotonic() + 10
            records = []
            while time.monotonic() < deadline:
                records = server.cluster.records_for("sl")
                if sum(1 for r in records if r.get("type") == "stats") >= 3:
                    break
                time.sleep(0.02)
            kinds = [r.get("type") for r in records]
            assert kinds.count("stats") >= 3
            assert "init" in kinds            # topology record federated
            stats = next(r for r in records if r.get("type") == "stats")
            assert "params" in stats and "gradients" in stats
        finally:
            router.close(timeout=2)
            server.stop()


# ================================================ coordinator-side logic
class TestClusterStore:
    def _feed(self, store, worker, step_s, n=6):
        store.ingest(worker, [{"type": "step", "iteration": i,
                               "step_seconds": step_s, "score": 1.0}
                              for i in range(n)])

    def test_straggler_flagged_and_counted(self, registry):
        from deeplearning4j_tpu.obs.registry import install_standard_metrics
        install_standard_metrics()
        store = ClusterStore(straggler_factor=2.0)
        self._feed(store, "w0", 0.01)
        self._feed(store, "w1", 0.011)
        self._feed(store, "w2", 0.009)
        self._feed(store, "w3", 0.05)     # 5x the median
        summary = store.summary()
        assert summary["workers"]["w3"]["straggler"] is True
        assert all(not summary["workers"][w]["straggler"]
                   for w in ("w0", "w1", "w2"))
        assert summary["straggler_skew"] > 2.0
        anomalies = get_registry().labeled_counter(
            "tpudl_health_anomalies_total", label_names=("kind",))
        assert anomalies.labeled_value(kind="straggler") == 1.0
        # an even gang never flags
        even = ClusterStore(straggler_factor=2.0)
        for w in ("a", "b", "c"):
            self._feed(even, w, 0.01)
        assert even.straggler_skew() == pytest.approx(1.0)
        assert not any(w["straggler"]
                       for w in even.summary()["workers"].values())

    def test_steps_per_s_uses_producer_clock(self, registry):
        """A router flush delivers many step records in ONE ingest call;
        the rate must come from the records' own ``time`` stamps, not
        the (near-zero) coordinator receipt span."""
        store = ClusterStore()
        t0 = time.time()
        store.ingest("w", [{"type": "step", "iteration": i,
                            "step_seconds": 0.1, "time": t0 + i * 0.1}
                           for i in range(11)])     # 10 Hz worker
        rate = store.summary()["workers"]["w"]["steps_per_s"]
        assert rate == pytest.approx(10.0, rel=0.01)
        # records without a producer clock fall back to 1/median, never
        # to the inflated receipt-span rate
        bare = ClusterStore()
        bare.ingest("w", [{"type": "step", "iteration": i,
                           "step_seconds": 0.05} for i in range(6)])
        assert bare.summary()["workers"]["w"]["steps_per_s"] \
            == pytest.approx(20.0, rel=0.01)

    def test_ingest_rejects_garbage_payloads(self, registry):
        server = UIServer(port=0)
        try:
            req = urllib.request.Request(
                server.url.rstrip("/") + "/remote/stats",
                data=b"not json",
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(req, timeout=5)
            assert err.value.code == 400
            # a proper payload on a wrong path 404s
            req = urllib.request.Request(
                server.url.rstrip("/") + "/remote/nope", data=b"{}")
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(req, timeout=5)
            assert err.value.code == 404
        finally:
            server.stop()

    def test_malformed_record_fields_never_500(self, registry):
        """Structurally-valid JSON whose record FIELDS are garbage (a
        null iteration) must not crash the handler or poison the worker
        state: the bad record is skipped, its siblings land."""
        store = ClusterStore()
        n = store.ingest("w", [
            {"type": "step", "iteration": None},              # skipped
            {"type": "step", "iteration": 0, "step_seconds": 0.01},
            {"type": "step", "iteration": "nope"},            # skipped
            {"type": "step", "iteration": 1, "step_seconds": 0.01},
        ])
        assert n == 2
        w = store.summary()["workers"]["w"]
        assert w["steps"] == 2 and w["iteration"] == 1
        # over HTTP the same payload answers 200 (never a connection
        # reset from an unhandled handler exception)
        server = UIServer(port=0)
        try:
            req = urllib.request.Request(
                server.url.rstrip("/") + "/remote/stats",
                data=json.dumps({"worker": "w", "records": [
                    {"type": "step", "iteration": None},
                    {"type": "step", "iteration": 3},
                ]}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=5) as resp:
                assert json.loads(resp.read())["ok"] == 1
        finally:
            server.stop()


# ======================================================= the acceptance
class TestClusterFederationE2E:
    def test_four_workers_report_in_and_straggler_is_flagged(self):
        """ISSUE-7 acceptance: 4 workers under spawn_local_cluster →
        the coordinator's /metrics exposes per-worker series with
        ``worker`` labels, /cluster renders per-worker step time +
        liveness, and the delay@-injected worker 0 is flagged as a
        straggler from federated telemetry alone."""
        from deeplearning4j_tpu.parallel.launcher import spawn_local_cluster

        server = UIServer(port=0)
        try:
            fn = functools.partial(cluster_workers.telemetry_train_worker,
                                   steps=8, straggler_pid=0, delay_s=0.25)
            results = spawn_local_cluster(fn, n_processes=4, port=23801,
                                          timeout=240.0, extra_env=_ENV,
                                          remote_ui=server.url)
            assert len(results) == 4
            summary = json.loads(_get(server.url + "cluster.json"))
            workers = summary["workers"]
            assert sorted(workers) == ["w0", "w1", "w2", "w3"]
            for name, w in workers.items():
                assert w["steps"] == 8, (name, w)
                assert w["median_step_ms"] is not None
                assert w["liveness_age_s"] < 120
            # the injected 0.25s delay dwarfs a millisecond CPU step
            assert workers["w0"]["straggler"] is True
            assert not any(workers[w]["straggler"]
                           for w in ("w1", "w2", "w3"))
            assert summary["straggler_skew"] > 2.0
            # federated /metrics: per-worker series under one scrape
            body = _get(server.url + "metrics")
            for w in ("w0", "w1", "w2", "w3"):
                assert f'tpudl_cluster_worker_iteration{{worker="{w}"}} 7' \
                    in body
                assert f'tpudl_cluster_step_seconds_count{{worker="{w}"}}' \
                    in body
            # /cluster renders per-worker step time + liveness + the flag
            html = _get(server.url + "cluster")
            assert "median step ms" in html and "liveness age s" in html
            assert "w3" in html and "straggler" in html
            # the coordinator's health family saw the straggler verdict
            anomalies = get_registry().labeled_counter(
                "tpudl_health_anomalies_total", label_names=("kind",))
            assert anomalies.labeled_value(kind="straggler") >= 1.0
        finally:
            server.stop()


# ============================================== multichip bench record
def test_bench_multichip_record_measures_scaling(tmp_path):
    """The ROADMAP-2 deliverable plus the ISSUE-8 recovery row:
    bench/multichip.py completes on CPU (rc=0), reports measured per_chip_scaling_efficiency +
    straggler_skew from federated telemetry, and the recovery record
    shows a supervised kill-and-heal with measured mttr_s and
    steps_replayed."""
    import subprocess
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "DL4J_TPU_MULTICHIP_WORKERS": "2",
           "DL4J_TPU_MULTICHIP_STEPS": "5",
           "DL4J_TPU_MULTICHIP_RECOVERY_STEPS": "8",
           "DL4J_TPU_MULTICHIP_PORT": "24451"}
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "bench", "multichip.py")],
        capture_output=True, text=True, timeout=420, env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    record = json.loads([ln for ln in proc.stdout.splitlines()
                         if ln.startswith("{")][-1])
    assert record["metric"] == "multichip_scaling_efficiency"
    assert record["n_workers"] == 2
    assert record["per_chip_scaling_efficiency"] > 0
    assert record["straggler_skew"] >= 1.0
    workers = record["detail"]["workers"]
    assert sorted(workers) == ["w0", "w1"]
    assert all(w["median_step_ms"] for w in workers.values())
    assert record["detail"]["source"] == "federated_telemetry"
    # the ISSUE-8 recovery record: injected worker kill under the
    # supervisor, measured MTTR + steps replayed, recovered: true
    recovery = record["recovery"]
    assert recovery["recovered"] is True
    assert recovery["restarts"] == 1
    assert recovery["reason"] == "killed"
    assert recovery["mttr_s"] is not None and recovery["mttr_s"] > 0
    assert recovery["steps_replayed"] is not None
    assert recovery["steps_replayed"] >= 0


# ==================================== restart generations (self-healing)
class TestGenerationAwareStore:
    def test_restart_resets_window_and_drops_stale_records(self, registry):
        """A respawned worker re-registers under generation+1: its dead
        predecessor's step window stops feeding straggler math and
        median_step_ms, and the predecessor's late buffered records are
        dropped (counted), never mixed into the new series."""
        from deeplearning4j_tpu.obs.registry import install_standard_metrics
        install_standard_metrics()
        store = ClusterStore(straggler_factor=2.0)
        # generation 0: w1 is pathologically slow → flagged straggler
        for w, dt in (("w0", 0.01), ("w2", 0.01)):
            store.ingest(w, [{"type": "step", "iteration": i,
                              "step_seconds": dt} for i in range(6)])
        store.ingest("w1", [{"type": "step", "iteration": i,
                             "step_seconds": 0.08} for i in range(6)])
        assert store.summary()["workers"]["w1"]["straggler"] is True
        # the supervisor respawns w1; generation 1 is healthy
        store.ingest("w1", [{"type": "resume", "iteration": 4}],
                     generation=1)
        store.ingest("w1", [{"type": "step", "iteration": i,
                             "step_seconds": 0.01} for i in range(4, 10)],
                     generation=1)
        w1 = store.summary()["workers"]["w1"]
        assert w1["generation"] == 1
        assert w1["restarts"] == 1
        assert w1["resumed_iteration"] == 4
        # the pre-crash 80ms window is GONE: median reflects gen 1 only
        assert w1["median_step_ms"] == pytest.approx(10.0)
        assert w1["straggler"] is False
        assert store.straggler_skew() == pytest.approx(1.0)
        # a dying predecessor's buffered telemetry arrives late: dropped
        n = store.ingest("w1", [{"type": "step", "iteration": 99,
                                 "step_seconds": 0.5}], generation=0)
        assert n == 0
        assert store.summary()["workers"]["w1"]["median_step_ms"] \
            == pytest.approx(10.0)
        assert get_registry().counter(
            "tpudl_cluster_stale_records_total").value == 1
        # restart annotation recorded for the /cluster dashboard
        notes = store.summary()["restarts"]
        assert len(notes) == 1
        assert notes[0]["worker"] == "w1"
        assert notes[0]["from_generation"] == 0
        assert notes[0]["to_generation"] == 1
        assert notes[0]["last_iteration"] == 5
        html = store.render_html(refresh_seconds=0)
        assert "generation" in html and "Restarts" in html

    def test_ingest_generation_rides_http_payload(self, registry):
        """The router stamps its generation on every push; the UIServer
        hands it to the store."""
        server = UIServer(port=0)
        router = RemoteStatsRouter(server.url, worker="gw",
                                   flush_interval_s=0.02, generation=3)
        try:
            router.put_event("step", iteration=0, step_seconds=0.01)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                summary = json.loads(_get(server.url + "cluster.json"))
                if summary["workers"].get("gw", {}).get("steps") == 1:
                    break
                time.sleep(0.02)
            assert summary["workers"]["gw"]["generation"] == 3
            body = _get(server.url + "metrics")
            assert 'tpudl_cluster_worker_generation{worker="gw"} 3' in body
        finally:
            router.close(timeout=2)
            server.stop()

    def test_router_generation_defaults_from_env(self, registry, monkeypatch):
        from deeplearning4j_tpu.obs import remote
        monkeypatch.setenv(remote.GENERATION_ENV, "5")
        router = RemoteStatsRouter("http://127.0.0.1:9", worker="ge",
                                   flush_interval_s=10.0)
        try:
            assert router.generation == 5
        finally:
            router.close(timeout=1)
