"""Multi-host SPMD bootstrap — the Spark-orchestration replacement.

Parity with the reference's cluster story (SURVEY.md §2.7/§3.4: Spark
driver broadcasts the model, launches one long-lived worker per executor,
Aeron mesh forms via driver handshake): on TPU pods the runtime IS the
cluster — one process per host, ``jax.distributed.initialize`` handshakes
with the coordinator, and every jit'd step runs gang-scheduled SPMD.

Also provides the multi-process CPU test rig (DummyTransport parity,
SURVEY.md §4.2): spawn N local processes over loopback with
``spawn_local_cluster`` and run a function under a real multi-process
``jax.distributed`` runtime without any TPU pod.
"""

from __future__ import annotations

import os
import pickle
import signal
import subprocess
import sys
import tempfile
import time
from typing import Callable, Optional


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """``jax.distributed.initialize`` with env-var fallbacks
    (DL4J VoidConfiguration's controller address/ports equivalent).
    No-ops on single-process runs."""
    import jax
    from deeplearning4j_tpu.obs import tracing
    coordinator_address = coordinator_address or os.environ.get("DL4J_TPU_COORDINATOR")
    if num_processes is None:
        num_processes = int(os.environ.get("DL4J_TPU_NUM_PROCESSES", "1"))
    if process_id is None:
        process_id = int(os.environ.get("DL4J_TPU_PROCESS_ID", "0"))
    if num_processes <= 1:
        return
    with tracing.span("distributed_init", processes=num_processes,
                      process_id=process_id):
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id)


_WORKER_TEMPLATE = r"""
import os, pickle, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count={local_devices}")
import jax
jax.config.update("jax_platforms", "cpu")  # local gangs are CPU-only
from deeplearning4j_tpu.obs import flight_recorder as _fr
from deeplearning4j_tpu.obs import remote as _remote
_fr.install_from_env()   # black box: crash handlers + gang-deadline watchdog
_remote.install_from_env()   # telemetry federation: heartbeats + step stamps
jax.distributed.initialize(coordinator_address="127.0.0.1:{port}",
                           num_processes={n}, process_id={pid})
with open({fn_path!r}, "rb") as f:
    fn = pickle.load(f)
try:
    result = fn(jax.process_index(), jax.process_count())
    with open({out_path!r}, "wb") as f:
        pickle.dump(result, f)
finally:
    # ALSO on the failure path: an in-flight background cost analysis
    # (a real XLA compile on a worker thread) racing interpreter +
    # distributed shutdown aborts the process with a C++ terminate —
    # which would replace the Python traceback the launcher's stderr
    # tail surfaces; and a failing worker's buffered telemetry (the
    # steps leading up to the failure) is the telemetry worth flushing
    from deeplearning4j_tpu.obs import costmodel as _cm
    _cm.drain(timeout_s=60.0)
    _remote.close_router()
"""


class ClusterTimeoutError(RuntimeError):
    """The gang never completed within the wall budget.  Deliberately
    NOT retryable: its message embeds every child's stderr tail, which
    routinely contains coordinator-join noise ('connection refused')
    that must not be mistaken for a startup flake — re-running a
    timed-out gang would multiply an already-spent timeout.

    ``flight_dumps`` maps process id → that child's parsed flight-
    recorder dump lines (empty when the child never dumped)."""

    def __init__(self, *args):
        super().__init__(*args)
        self.flight_dumps: dict = {}


class ClusterStallError(RuntimeError):
    """One or more gang members' flight-recorder watchdogs fired (no
    step/exchange progress within the gang deadline): the per-host
    black boxes are attached as ``flight_dumps`` (pid → parsed JSONL
    lines with thread stacks, recent spans/events, metric snapshot).
    NOT retryable — a deterministic stall would just stall again."""

    def __init__(self, *args):
        super().__init__(*args)
        self.flight_dumps: dict = {}


# stderr fingerprints of a flaky STARTUP (stale coordinator port, racing
# binds) — worth retrying on a fresh port; genuine hangs/crashes are not.
# Deliberately NOT "connection refused": when one child dies for a real
# reason, its SIBLINGS routinely print coordinator-join 'connection
# refused' noise, and retrying a deterministic failure just multiplies it.
_STARTUP_FLAKE_MARKERS = ("address already in use", "failed to bind",
                          "errno 98")


def _is_startup_flake(e: BaseException) -> bool:
    from deeplearning4j_tpu.resilience.retry import default_retryable
    if isinstance(e, (ClusterTimeoutError, ClusterStallError)):
        return False
    if default_retryable(e):
        return True
    msg = str(e).lower()
    return isinstance(e, RuntimeError) and any(
        marker in msg for marker in _STARTUP_FLAKE_MARKERS)


def _terminate_then_kill(procs, grace: float = 3.0, first_pid: int = 0,
                         tail_fn=None) -> list[str]:
    """Stop every child (TERM, grace period, then KILL) and return each
    one's captured stderr tail — a timed-out gang must leave no orphans
    and no silent diagnostics.  ``tail_fn(pid) -> str`` supplies the
    tail when the children's output goes to files (GangHandle) instead
    of pipes."""
    for proc in procs:
        if proc.poll() is None:
            proc.terminate()
    deadline = time.monotonic() + grace
    for proc in procs:
        if proc.poll() is None:
            try:
                proc.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                proc.kill()
    tails = []
    for pid, proc in enumerate(procs):
        if tail_fn is not None:
            try:
                proc.wait(timeout=5.0)
            except (subprocess.TimeoutExpired, ValueError, OSError):
                pass
            text = tail_fn(first_pid + pid)
        else:
            try:
                _, stderr = proc.communicate(timeout=5.0)
            except (subprocess.TimeoutExpired, ValueError, OSError):
                stderr = b""
            text = (stderr or b"").decode(errors="replace")
        rc = proc.poll()
        tails.append(f"process {first_pid + pid} rc={rc} stderr tail: "
                     f"{text[-800:]}")
    return tails


def _collect_flight_dumps(workdir: str, n_processes: int) -> dict:
    """pid → parsed flight-recorder dump lines for every child that
    wrote one (missing/empty dumps → absent)."""
    from deeplearning4j_tpu.obs import flight_recorder
    dumps = {}
    for pid in range(n_processes):
        lines = flight_recorder.read_dump(
            os.path.join(workdir, f"flight_{pid}.jsonl"))
        if lines:
            dumps[pid] = lines
    return dumps


def _dump_summary(dumps: dict) -> str:
    """One readable line per dumped child for the raised error message
    (the full parsed dumps ride on the exception's ``flight_dumps``)."""
    if not dumps:
        return "no flight-recorder dumps found"
    lines = []
    for pid, entries in sorted(dumps.items()):
        header = next((e for e in entries if e.get("type") == "header"), {})
        live = next((e for e in entries if e.get("type") == "liveness"), {})
        threads = sum(1 for e in entries if e.get("type") == "thread")
        events = sum(1 for e in entries if e.get("type") == "event")
        lines.append(
            f"process {pid} black box: reason={header.get('reason')} "
            f"last_site={live.get('last_site')} "
            f"stalled_for_s={live.get('stalled_for_s')} "
            f"({threads} thread stacks, {events} ring events)")
    return "\n".join(lines)


class GangHandle:
    """A RUNNING local gang — the restartable handle the
    :class:`~deeplearning4j_tpu.resilience.supervisor.ClusterSupervisor`
    drives.  Construction spawns the child processes and returns
    immediately; callers either block in :meth:`wait` (the
    ``spawn_local_cluster`` path — identical semantics to the historical
    one-shot spawn) or poll :meth:`poll_exits` from a supervision loop,
    then :meth:`shutdown` the survivors and :meth:`collect_flight_dumps`
    when a member dies.

    ``child_env`` is the per-child env hook (``pid -> dict``), applied
    LAST so a supervisor can stamp per-worker identity (worker id,
    gang generation, resume pointer) over both the launcher defaults
    and the shared ``extra_env``."""

    def __init__(self, fn: Callable, n_processes: int, port: int,
                 local_devices: int = 1, timeout: float = 120.0,
                 extra_env: Optional[dict] = None,
                 gang_deadline: Optional[float] = None,
                 gang_fires: int = 1,
                 remote_ui: Optional[str] = None,
                 child_env: Optional[Callable[[int], dict]] = None):
        from deeplearning4j_tpu.obs import flight_recorder, tracing
        from deeplearning4j_tpu.obs import remote as obs_remote
        from deeplearning4j_tpu.resilience import faults
        faults.fire("launcher.spawn")
        self.n_processes = n_processes
        self.timeout = timeout
        self.gang_deadline = gang_deadline
        self.workdir = tempfile.mkdtemp(prefix="dl4j_tpu_cluster_")
        fn_path = os.path.join(self.workdir, "fn.pkl")
        with open(fn_path, "wb") as f:
            pickle.dump(fn, f)
        self.procs: list = []
        self.out_paths: list[str] = []
        trace_env = tracing.propagation_env()
        for pid in range(n_processes):
            out_path = os.path.join(self.workdir, f"out_{pid}.pkl")
            self.out_paths.append(out_path)
            script = _WORKER_TEMPLATE.format(
                n=n_processes, pid=pid, port=port, fn_path=fn_path,
                out_path=out_path, local_devices=local_devices)
            env = dict(os.environ)
            env.pop("XLA_FLAGS", None)  # template sets its own
            env.update(trace_env)
            # every child gets a black box: crash/SIGTERM dumps always,
            # plus a stall watchdog when a gang deadline is set.
            # Tracing is turned on alongside so the dump's ring carries
            # the last N spans, not just raw events.
            env[flight_recorder.DUMP_ENV] = os.path.join(
                self.workdir, f"flight_{pid}.jsonl")
            if gang_deadline is not None:
                env[flight_recorder.WATCHDOG_ENV] = str(float(gang_deadline))
                env[flight_recorder.WATCHDOG_FIRES_ENV] = str(int(gang_fires))
                env.setdefault("DL4J_TPU_TRACING", "1")
            if remote_ui:
                # telemetry federation: every child routes stats/
                # heartbeats to the coordinator UIServer under its own
                # worker label
                env[obs_remote.ENDPOINT_ENV] = remote_ui
                env[obs_remote.WORKER_ENV] = f"w{pid}"
            if extra_env:
                env.update(extra_env)
            if child_env is not None:
                env.update({k: str(v) for k, v in child_env(pid).items()})
            # children write to FILES, not pipes: the supervision loop
            # only polls exit codes, so a pipe nobody drains would wedge
            # a chatty child on a full 64KB buffer — and even the
            # blocking wait() drains sequentially (child N+1 could fill
            # its pipe while child N is being waited on)
            with open(os.path.join(self.workdir, f"stderr_{pid}.log"),
                      "wb") as err_f:
                self.procs.append(subprocess.Popen(
                    [sys.executable, "-c", script], env=env,
                    stdout=err_f, stderr=err_f))
        # ONE wall-clock budget for the whole gang: jax.distributed
        # blocks until every process joins, so child 0 timing out means
        # they all did
        self.started_at = time.monotonic()
        self.deadline = self.started_at + timeout

    # ------------------------------------------------- supervision surface
    def poll_exits(self) -> dict:
        """pid → return code for every child (None = still running).
        Non-blocking; the supervisor's detection loop."""
        return {pid: proc.poll() for pid, proc in enumerate(self.procs)}

    def running(self) -> bool:
        return any(proc.poll() is None for proc in self.procs)

    def stderr_tail(self, pid: int, limit: int = 800) -> str:
        """Last ``limit`` chars of the child's combined stdout/stderr
        file (children write to files so nothing ever blocks on an
        undrained pipe)."""
        try:
            with open(os.path.join(self.workdir, f"stderr_{pid}.log"),
                      "rb") as f:
                f.seek(0, os.SEEK_END)
                size = f.tell()
                f.seek(max(0, size - 4 * limit))
                return f.read().decode(errors="replace")[-limit:]
        except OSError:
            return ""

    def request_dumps(self, grace: float = 3.0) -> None:
        """Ask every still-alive child for its black box (SIGUSR1 → the
        flight recorder dumps and SURVIVES), then wait up to ``grace``
        for the dump files to GROW past their pre-signal size and go
        quiet — a dump written earlier in the generation (a watchdog
        grace fire, a health-monitor action) must not satisfy the wait
        and let teardown kill a child mid-append.  Separate from
        :meth:`shutdown` because jax's TSL preemption notifier owns
        SIGTERM in gang children — a SIGTERM never reaches the Python
        dump handler, so evidence must be collected before the stop
        signal.  Limitation: CPython runs signal handlers between
        bytecodes on the main thread, so a sibling wedged inside a
        native collective cannot answer — that state is the stall
        watchdog's job (it dumps from its own thread and exits 87)."""
        def sizes():
            out = {}
            for pid, p in enumerate(self.procs):
                try:
                    out[pid] = os.path.getsize(
                        os.path.join(self.workdir, f"flight_{pid}.jsonl"))
                except OSError:
                    out[pid] = -1
            return out

        before = sizes()
        alive = []
        for pid, p in enumerate(self.procs):
            if p.poll() is None:
                alive.append(pid)
                try:
                    p.send_signal(signal.SIGUSR1)
                except (ProcessLookupError, OSError):
                    pass
        if not alive:
            return
        deadline = time.monotonic() + grace
        prev = before
        while time.monotonic() < deadline:
            time.sleep(0.1)
            now = sizes()
            grown = all(now[pid] > before[pid] for pid in alive
                        if self.procs[pid].poll() is None)
            settled = all(now[pid] == prev[pid] for pid in alive)
            if grown and settled:
                return          # every reachable child dumped, writes quiet
            prev = now

    def shutdown(self, grace: float = 3.0) -> list[str]:
        """Terminate-then-kill every remaining child; returns each
        child's stderr tail (already-exited children just report)."""
        return _terminate_then_kill(self.procs, grace=grace,
                                    tail_fn=self.stderr_tail)

    def abort_timeout(self, reason: str,
                      extra_lines: Optional[list] = None
                      ) -> "ClusterTimeoutError":
        """Stop the whole gang and build the ``ClusterTimeoutError`` for
        a blown wall budget — one construction shared by the blocking
        :meth:`wait` and the supervisor's watch loop, so the message
        shape and the ``flight_dumps`` attachment can't drift."""
        tails = self.shutdown()
        dumps = self.collect_flight_dumps()
        err = ClusterTimeoutError(
            reason + "\n" + "\n".join((extra_lines or []) + tails)
            + "\n" + _dump_summary(dumps))
        err.flight_dumps = dumps
        return err

    def collect_flight_dumps(self) -> dict:
        return _collect_flight_dumps(self.workdir, self.n_processes)

    def results(self) -> list:
        """Return values of the children that completed (out pickles
        present).  Call after a clean gang exit."""
        results = []
        for path in self.out_paths:
            if os.path.exists(path):
                with open(path, "rb") as f:
                    results.append(pickle.load(f))
        return results

    # ----------------------------------------------- blocking collection
    def wait(self) -> list:
        """Block until the gang finishes; return every child's result or
        raise (``ClusterTimeoutError`` / ``ClusterStallError`` /
        ``RuntimeError``) with flight dumps attached — the historical
        ``spawn_local_cluster`` semantics."""
        from deeplearning4j_tpu.obs import flight_recorder
        procs, workdir = self.procs, self.workdir
        n_processes, timeout = self.n_processes, self.timeout
        gang_deadline = self.gang_deadline
        results = []
        errors = []
        stalled = []
        for pid, proc in enumerate(procs):
            try:
                proc.wait(timeout=max(0.1, self.deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                # a hung gang member past even the watchdog: stop EVERY
                # child (terminate → grace → kill) and surface each
                # one's stderr AND whatever black boxes landed — the
                # raised error must say which process wedged and why,
                # not just "timed out"
                raise self.abort_timeout(
                    f"local cluster timed out after {timeout:.0f}s waiting "
                    f"for process {pid}; all {n_processes} children "
                    f"stopped:", extra_lines=stalled)
            if proc.returncode == flight_recorder.WATCHDOG_EXIT_CODE:
                stalled.append(f"process {pid} stalled (flight-recorder "
                               f"watchdog, gang deadline "
                               f"{gang_deadline}s): "
                               f"{self.stderr_tail(pid, limit=400)}")
                # one stalled member wedges every sibling on its
                # collectives and the gang is going to raise regardless —
                # stop the rest instead of letting them burn the
                # remaining wall clock.  But the siblings are stalled on
                # the SAME exchange: their own watchdogs fire within ~a
                # poll interval of this one, so first give every
                # still-alive sibling one short window to write its black
                # box (killed pre-dump = no thread stacks for that child,
                # and per-child dumps are the point)
                rest = procs[pid + 1:]
                if rest:
                    grace_deadline = time.monotonic() + min(
                        5.0, gang_deadline or 5.0)
                    while time.monotonic() < grace_deadline and any(
                            p.poll() is None and not os.path.exists(
                                os.path.join(workdir, f"flight_{q}.jsonl"))
                            for q, p in enumerate(rest, start=pid + 1)):
                        time.sleep(0.05)
                    time.sleep(0.2)     # let an in-flight dump write finish
                    errors.extend(
                        f"stopped after sibling stall: {tail}"
                        for tail in _terminate_then_kill(
                            rest, first_pid=pid + 1,
                            tail_fn=self.stderr_tail))
                break
            elif proc.returncode != 0:
                errors.append(f"process {pid} rc={proc.returncode}: "
                              f"{self.stderr_tail(pid)}")
            elif os.path.exists(self.out_paths[pid]):
                with open(self.out_paths[pid], "rb") as f:
                    results.append(pickle.load(f))
        if stalled:
            # one stalled member wedges the whole gang (collectives
            # block); siblings usually die of the same watchdog — report
            # them all, with every child's black box attached
            dumps = _collect_flight_dumps(workdir, n_processes)
            err = ClusterStallError(
                "local cluster stalled:\n" + "\n".join(stalled + errors)
                + "\n" + _dump_summary(dumps))
            err.flight_dumps = dumps
            raise err
        if errors:
            dumps = _collect_flight_dumps(workdir, n_processes)
            err = RuntimeError("local cluster failed:\n" + "\n".join(errors))
            err.flight_dumps = dumps
            raise err
        return results


def _spawn_once(fn: Callable, n_processes: int, port: int,
                local_devices: int, timeout: float,
                extra_env: Optional[dict],
                gang_deadline: Optional[float],
                gang_fires: int = 1,
                remote_ui: Optional[str] = None) -> list:
    return GangHandle(fn, n_processes, port, local_devices=local_devices,
                      timeout=timeout, extra_env=extra_env,
                      gang_deadline=gang_deadline, gang_fires=gang_fires,
                      remote_ui=remote_ui).wait()


def spawn_local_cluster(fn: Callable, n_processes: int = 2, port: int = 12655,
                        local_devices: int = 1, timeout: float = 120.0,
                        extra_env: Optional[dict] = None,
                        startup_retries: int = 2,
                        gang_deadline: Optional[float] = None,
                        remote_ui: Optional[str] = None) -> list:
    """Run ``fn(process_index, process_count)`` in N fresh local processes
    under a real jax.distributed runtime (CPU, loopback).  Returns each
    process's pickled return value.  ``fn`` must be picklable (module-level
    function).  This is the test rig for launcher/checkpoint/fault-
    tolerance paths — the DummyTransport translation.

    Resilience: a gang member that never joins gets the WHOLE gang
    terminated (then killed) and the error carries every child's stderr
    tail; startup flakes (stale coordinator port, racing binds) retry up
    to ``startup_retries`` times on a shifted port with backoff
    (``resilience.retry``, site ``launcher.spawn``).

    Flight recorder: every child dumps a black box (thread stacks, the
    last N spans/events, metric snapshot) on crash or SIGTERM.
    ``gang_deadline`` additionally arms a per-child stall watchdog: a
    child whose instrumented sites (``trainer.step``, ``dcn.exchange``,
    ...) make no progress for that long dumps its box and exits, and
    the raised :class:`ClusterStallError` / :class:`ClusterTimeoutError`
    carries every child's parsed dump as ``.flight_dumps`` — the next
    rc=124 is a per-host stall report, not silence.  When not passed,
    the deadline defaults to half the wall budget with one grace fire
    (first dead deadline dumps + re-arms; the second exits 87 still
    inside ``timeout``), so a legitimately slow XLA compile between
    stamps never kills a healthy gang; an explicit ``gang_deadline``
    is one-strike.  The watchdog arms on a child's FIRST progress
    stamp, so workers that never touch an instrumented site are only
    bounded by ``timeout``.  Pass ``gang_deadline=0`` to disable the
    watchdog.

    When tracing is active in the launching process, its span context is
    handed to every worker via ``DL4J_TPU_TRACE_CONTEXT`` — worker spans
    parent under the launcher's current span, so one Chrome trace shows
    the whole cluster.

    Telemetry federation: ``remote_ui`` (a coordinator ``UIServer`` URL,
    default: the launcher's own ``DL4J_TPU_REMOTE_UI``) is injected into
    every child as ``DL4J_TPU_REMOTE_UI`` plus a per-child
    ``DL4J_TPU_WORKER_ID`` (``w<pid>``); the child bootstrap installs a
    :class:`~deeplearning4j_tpu.obs.remote.RemoteStatsRouter`, so every
    gang member's steps, heartbeats and stats land on the coordinator's
    ``/cluster`` dashboard and ``worker``-labeled ``/metrics`` series."""
    from deeplearning4j_tpu.resilience.retry import RetryPolicy, with_retries
    if remote_ui is None:
        remote_ui = os.environ.get("DL4J_TPU_REMOTE_UI") or None
    gang_fires = 1
    if gang_deadline is None:
        # silently-armed default: half the wall budget with ONE grace
        # fire, so a child whose XLA compile legitimately outlives one
        # deadline costs a spurious dump, not the gang — a genuine stall
        # still exits 87 at 2×deadline, inside the wall clock.  Callers
        # who pass an explicit deadline asked for one-strike semantics.
        gang_deadline = max(5.0, (timeout - 15.0) / 2.0)
        gang_fires = 2
    elif gang_deadline <= 0:
        gang_deadline = None
    attempt = {"n": 0}

    def _once():
        i = attempt["n"]
        attempt["n"] += 1
        # a fresh port per retry: the usual flake is the previous gang's
        # coordinator socket lingering in TIME_WAIT
        return _spawn_once(fn, n_processes, port + i * 97, local_devices,
                           timeout, extra_env, gang_deadline, gang_fires,
                           remote_ui=remote_ui)

    policy = RetryPolicy(max_attempts=1 + max(0, startup_retries),
                         base_delay_s=0.2, jitter=0.0,
                         retryable=_is_startup_flake)
    return with_retries(_once, policy=policy, site="launcher.spawn")
