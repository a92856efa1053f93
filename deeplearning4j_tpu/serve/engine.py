"""Dynamic micro-batching inference engine — the serving hot path.

DL4J's ``ParallelInference`` batches whatever happens to be queued when
the worker wakes up; production TPU serving needs the three properties
it lacks (TFX/TensorFlow-Serving design, PAPERS.md):

1. **Deadline-bounded micro-batching** — requests accumulate until
   ``max_batch`` rows are queued (size flush) OR ``max_latency_ms`` has
   passed since the oldest request in the forming batch (deadline
   flush).  Throughput comes from the batch; the tail latency bound
   comes from the deadline.
2. **Compiled-shape reuse** — ragged request sizes pad up to a static
   bucket set (powers of two up to ``max_batch`` by default, sticky-
   extended like the PR-3 device feeder), so mixed-size traffic runs
   through at most one XLA program per bucket instead of one per
   distinct row count.  The jit-wrapped forward itself is shared
   process-wide through :mod:`deeplearning4j_tpu.train.step_cache`
   keyed by (net class, config sha, dtype policy) — hot-swapping a
   same-architecture model reuses the already-compiled program, so a
   swap costs zero recompiles.
3. **Backpressure with explicit load shedding** — the request queue is
   bounded; a submit against a full queue fails *immediately* with
   :class:`Overloaded` (never unbounded growth), and a request can
   carry a deadline after which it is cancelled instead of dispatched.

Padded rows are tracked with a row-validity mask and sliced off before
results are scattered back to callers, so batched outputs equal
per-request outputs (inference mode is row-independent: no dropout,
BatchNorm uses running statistics).

**Continuous batching (sequence workloads).**  The worker keeps ONE
persistent host staging buffer per request signature
(:class:`_BatchStage`) and copies each request's rows into it *at
admission time*, inside the batching window — the staging work overlaps
the deadline wait instead of serializing after the flush decision, and
the buffer, its zero padding, and its mask scratch are REUSED across
flushes instead of re-allocated per dispatch.  For sequence workloads
(BERT MLM, LSTM: ``[n, T, F]`` requests where one flush's padded batch
is megabytes) this removes a per-flush allocate+concatenate+pad of the
whole batch from the hot path.  Reuse is visible in
``tpudl_serve_stage_reuse_total``.

Observability: a ``serve`` span per dispatched batch (queue-wait vs
device-time attribution) and the ``tpudl_serve_*`` metrics —
see docs/serving.md for the full table.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from concurrent.futures import Future
from typing import Optional, Sequence

import numpy as np

from deeplearning4j_tpu.data.device_pipeline import _pad_rows, choose_bucket
from deeplearning4j_tpu.obs import costmodel, flight_recorder, tracing
from deeplearning4j_tpu.obs.registry import get_registry
from deeplearning4j_tpu.resilience import faults
from deeplearning4j_tpu.train import step_cache


class Overloaded(RuntimeError):
    """Request shed at submit time: the engine's bounded queue is full.
    Deliberately immediate — the caller (or its load balancer) should
    retry elsewhere/later rather than pile onto this replica."""


class DeadlineExceeded(RuntimeError):
    """Request expired in the queue before it could be dispatched."""


class EngineClosed(RuntimeError):
    """Submit against an engine that has been shut down (e.g. the old
    version's engine after a registry hot-swap finished draining)."""


@dataclasses.dataclass
class _Request:
    x: np.ndarray
    mask: Optional[np.ndarray]
    future: Future
    t_submit: float                   # perf_counter at submit
    deadline: Optional[float]         # absolute perf_counter deadline
    trace_id: Optional[str] = None    # X-Trace-Id propagated end to end

    @property
    def n(self) -> int:
        return int(self.x.shape[0])


def _default_buckets(max_batch: int) -> tuple[int, ...]:
    """Powers of two up to (and always including) ``max_batch`` — a
    bounded compile budget of ~log2(max_batch) programs."""
    buckets = []
    b = 1
    while b < max_batch:
        buckets.append(b)
        b *= 2
    buckets.append(int(max_batch))
    return tuple(buckets)


class _BatchStage:
    """Reusable host staging state for one request signature — the
    continuous-batching buffer.

    One ``(capacity, *tail)`` features buffer (and a lazily-created mask
    buffer) lives across flushes; admitted requests copy their rows in
    immediately, so by the time the flush decision lands the batch is
    already staged.  ``dirty``/``mask_dirty`` track rows holding stale
    data from earlier flushes so only the necessary tail is re-zeroed —
    padding rows beyond the high-water mark are still zero from the
    original allocation.

    Single-threaded by construction: only the engine's worker thread
    touches a stage, and a dispatch completes (device_sync) before the
    next flush reuses the buffer, so the forward never reads a buffer
    that is being rewritten.
    """

    __slots__ = ("features", "mask", "dirty", "mask_dirty", "has_mask",
                 "uses")

    def __init__(self, capacity: int, tail: tuple, dtype):
        self.features = np.zeros((capacity,) + tail, dtype)
        self.mask: Optional[np.ndarray] = None
        self.dirty = 0          # feature rows stale from earlier flushes
        self.mask_dirty = 0
        self.has_mask = False   # any masked request staged THIS flush
        self.uses = 0           # flushes served from this buffer

    @property
    def capacity(self) -> int:
        return int(self.features.shape[0])

    def begin(self) -> None:
        """Start staging a new forming batch."""
        self.has_mask = False

    def put(self, req: "_Request", offset: int) -> bool:
        """Stage one request's rows at ``offset``; False when the
        request does not fit this buffer's signature (the flush then
        falls back to the concat path)."""
        x = req.x
        if x.shape[1:] != self.features.shape[1:] \
                or x.dtype != self.features.dtype \
                or offset + req.n > self.capacity:
            return False
        if req.mask is not None:
            mask = req.mask
            if self.mask is None:
                self.mask = np.zeros(
                    (self.capacity,) + mask.shape[1:], np.float32)
            elif mask.shape[1:] != self.mask.shape[1:]:
                return False
            if not self.has_mask and offset:
                # earlier maskless rows in this batch get all-ones
                self.mask[:offset] = 1.0
            self.has_mask = True
            self.mask[offset:offset + req.n] = mask
            self.mask_dirty = max(self.mask_dirty, offset + req.n)
        elif self.has_mask:
            self.mask[offset:offset + req.n] = 1.0
            self.mask_dirty = max(self.mask_dirty, offset + req.n)
        self.features[offset:offset + req.n] = x
        # the high-water mark moves at WRITE time: rows staged for a
        # request that later dies (restage compacts past it) or for a
        # flush that falls back to concat must still count as stale, or
        # a later, smaller flush would ship them as "padding"
        self.dirty = max(self.dirty, offset + req.n)
        return True

    def restage(self, live: list) -> None:
        """Compact after some admitted requests died (deadline expiry /
        cancellation) before dispatch: rewrite the surviving rows
        contiguously — still into the persistent buffer, no allocation.
        Rows beyond the survivors keep their dirty accounting (put
        raised the high-water mark when they were first staged), so
        ``view`` re-zeroes them before they could ship as padding."""
        self.begin()
        offset = 0
        for req in live:
            self.put(req, offset)
            offset += req.n

    def view(self, bucket: int, rows: int) -> np.ndarray:
        """The ``[bucket, ...]`` dispatch view; zeroes only the stale
        tail rows left by a previous, larger flush."""
        if self.dirty > rows:
            self.features[rows:self.dirty] = 0
        self.dirty = rows
        return self.features[:bucket]

    def mask_view(self, bucket: int, rows: int) -> Optional[np.ndarray]:
        """The mask dispatch view (padding rows zero, exactly like the
        concat path's ``_pad_rows``); None when no request in this flush
        carried a mask."""
        if not self.has_mask:
            return None
        if self.mask_dirty > rows:
            self.mask[rows:self.mask_dirty] = 0
        self.mask_dirty = rows
        return self.mask[:bucket]


def _pure_forward_net(model) -> bool:
    """True for nets whose forward is a pure function of (params, state,
    x, mask) with one input and one output — the MultiLayerNetwork
    family and a ComputationGraph with a single input and output (the
    conv zoo: ResNet-50 is one).  Those get a process-cached jit
    forward, and with it the recompile guard and the artifact store;
    multi-input/-output graphs and duck-typed models fall back to
    ``model.output``."""
    if not hasattr(model, "_forward") \
            or getattr(model, "params_", None) is None:
        return False
    if hasattr(model, "layer_params"):          # ComputationGraph
        return len(model.conf.inputs) == 1 and len(model.conf.outputs) == 1
    return True


def _build_forward(net):
    """Build the jit forward for a pure-forward net.  Cached process-wide
    via step_cache: reuse across engines (and across hot-swapped nets of
    the same architecture) is sound because params/state are arguments,
    not closure state."""
    import jax

    @jax.jit
    def _fwd(params, state, x, mask):
        y, _, _ = net._forward(params, state, x, train=False, mask=mask)
        return y

    return _fwd


class InferenceEngine:
    """Micro-batching inference front-end for one model instance.

    Thread model: callers submit from any thread; ONE worker thread
    drains the bounded queue, forms batches, and runs the compiled
    forward (on TPU a single jit'd forward saturates the chip — replicas
    across devices come from running one engine per device/process).
    """

    _SHUTDOWN = object()

    def __init__(self, model, name: str = "default", max_batch: int = 32,
                 max_latency_ms: float = 5.0, queue_limit: int = 128,
                 buckets: Optional[Sequence[int]] = None,
                 bucketing: bool = True):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if queue_limit < 1:
            raise ValueError(f"queue_limit must be >= 1, got {queue_limit}")
        self.model = model
        self.name = name
        self.max_batch = int(max_batch)
        self.max_latency_s = float(max_latency_ms) / 1e3
        self.queue_limit = int(queue_limit)
        self.bucketing = bool(bucketing)
        self.buckets: tuple[int, ...] = (
            tuple(sorted(int(b) for b in buckets)) if buckets
            else _default_buckets(self.max_batch))
        self._queue: queue.Queue = queue.Queue(maxsize=self.queue_limit)
        self._closed = threading.Event()
        # continuous-batching state: persistent staging buffers keyed by
        # request signature, worker-thread-only (bounded: odd signatures
        # evict the oldest — steady traffic has one or two)
        self._stages: dict[tuple, _BatchStage] = {}
        self._fwd = None
        # quantized variant (nn.quantize): same class + config as its
        # full-precision sibling, so it SHARES the step-cached forward —
        # the int8 param pytree just holds its own compiled program per
        # bucket under the same jit boundary (zero-recompile swaps both
        # ways once each precision is warm).  Cost-model entries and the
        # tpudl_serve_quantized_* series key off this flag.
        self.precision: str = getattr(model, "quantized_", None) or "fp"
        if _pure_forward_net(model):
            sig = step_cache.net_signature(model)
            key = sig + ("serve_forward",) if sig is not None else None
            self._fwd = step_cache.get_or_build(
                key, lambda: _build_forward(model))
        self._worker = threading.Thread(
            target=self._run, daemon=True, name=f"tpudl-serve-{name}")
        self._worker.start()

    # ------------------------------------------------------------- submit
    def submit(self, x, mask=None, deadline_ms: Optional[float] = None,
               block: bool = False,
               timeout_s: Optional[float] = None,
               trace_id: Optional[str] = None) -> Future:
        """Enqueue one request of ``[n, ...]`` examples; returns a Future
        resolving to the ``[n, ...]`` outputs.

        Queue-full policy: ``block=False`` (serving default) sheds with
        :class:`Overloaded`; ``block=True`` (the historical
        ``ParallelInference`` contract) blocks the submitting thread —
        memory stays bounded either way.  ``deadline_ms`` bounds the
        time the request may wait before dispatch.  ``trace_id`` (the
        HTTP layer's ``X-Trace-Id``) rides through to the ``serve`` span
        and the flight-recorder ring, so one request is findable across
        the front-end, the batcher, and a black-box dump."""
        if self._closed.is_set():
            raise EngineClosed(f"engine {self.name!r} is shut down")
        x = np.asarray(x)
        if x.ndim == 0:
            raise ValueError("request must have a leading example dim")
        req = _Request(
            x, None if mask is None else np.asarray(mask), Future(),
            time.perf_counter(),
            None if deadline_ms is None
            else time.perf_counter() + float(deadline_ms) / 1e3,
            trace_id=trace_id)
        reg = get_registry()
        try:
            if block:
                self._queue.put(req, timeout=timeout_s)
            else:
                self._queue.put_nowait(req)
        except queue.Full:
            reg.counter("tpudl_serve_shed_total").inc()
            reg.labeled_counter("tpudl_serve_requests_total").inc(
                status="shed")
            raise Overloaded(
                f"engine {self.name!r} queue full "
                f"({self.queue_limit} waiting)") from None
        # close the submit/shutdown race: if shutdown won and the worker
        # is already gone, nobody will ever serve this queue — fail the
        # leftovers (ours included) instead of stranding the Future
        if self._closed.is_set() and not self._worker.is_alive():
            self._fail_leftovers()
        if not req.future.done():
            reg.gauge("tpudl_serve_queue_depth").set(self._queue.qsize())
        return req.future

    def predict(self, x, mask=None, deadline_ms: Optional[float] = None,
                timeout_s: Optional[float] = None,
                trace_id: Optional[str] = None) -> np.ndarray:
        """Blocking submit + wait."""
        return self.submit(x, mask=mask, deadline_ms=deadline_ms,
                           trace_id=trace_id).result(timeout=timeout_s)

    # ------------------------------------------------------------- worker
    def _stage_for(self, req: _Request) -> Optional[_BatchStage]:
        """The persistent staging buffer for this request's signature
        (created on first sight); None when the request can't stage
        (oversize single request — it defines its own sticky bucket and
        rides the concat path)."""
        if req.n > self.max_batch:
            return None
        key = (req.x.shape[1:], req.x.dtype.str)
        stage = self._stages.get(key)
        if stage is None:
            if len(self._stages) >= 8:      # bounded scratch memory
                self._stages.pop(next(iter(self._stages)))
            stage = _BatchStage(self.max_batch, req.x.shape[1:],
                                req.x.dtype)
            self._stages[key] = stage
        return stage

    def _run(self) -> None:
        carry = None       # request that would have overflowed max_batch
        while True:
            item = carry if carry is not None else self._queue.get()
            carry = None
            if item is self._SHUTDOWN:
                return
            batch = [item]
            rows = item.n
            # continuous staging: rows copy into the persistent buffer
            # as requests are admitted, overlapping the batching window
            stage = self._stage_for(item)
            if stage is not None:
                stage.begin()
                if not stage.put(item, 0):
                    stage = None
            flush_at = time.perf_counter() + self.max_latency_s
            while rows < self.max_batch:
                remaining = flush_at - time.perf_counter()
                if remaining <= 0:
                    break                      # deadline flush
                try:
                    nxt = self._queue.get(timeout=remaining)
                except queue.Empty:
                    break                      # deadline flush (idle)
                if nxt is self._SHUTDOWN:
                    self._dispatch(batch, stage)
                    return
                if rows + nxt.n > self.max_batch:
                    carry = nxt                # opens the NEXT batch
                    break                      # size flush (full)
                if stage is not None and not stage.put(nxt, rows):
                    stage = None    # mixed signature: concat fallback
                batch.append(nxt)
                rows += nxt.n
            self._dispatch(batch, stage)       # size flush when loop ended

    def _bucket_for(self, n: int) -> int:
        bucket = choose_bucket(n, self.buckets)
        if bucket not in self.buckets:
            # oversize request defines a new sticky bucket (feeder
            # semantics) — later tails pad up to the compiled shape
            self.buckets = tuple(sorted(self.buckets + (bucket,)))
        return bucket

    def _concat_masks(self, live: list) -> Optional[np.ndarray]:
        """Caller-provided masks, concatenated; requests without one get
        all-ones rows shaped like the present masks' trailing dims."""
        if not any(r.mask is not None for r in live):
            return None
        tail = next(r.mask.shape[1:] for r in live if r.mask is not None)
        parts = [r.mask if r.mask is not None
                 else np.ones((r.n,) + tail, np.float32) for r in live]
        return np.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]

    def _forward(self, features, mask):
        if self._fwd is not None:
            return self._fwd(self.model.params_, self.model.state_,
                             features, mask)
        if mask is not None:
            return self.model.output(features, mask=mask)
        return self.model.output(features)

    def _dispatch(self, batch: list,
                  stage: Optional[_BatchStage] = None) -> None:
        """Run one micro-batch end to end; every future in ``batch`` is
        resolved (result, deadline error, cancellation, or the forward's
        exception) — the worker itself never dies.  ``stage`` carries
        the pre-staged continuous-batching buffer when every request in
        ``batch`` copied in at admission; None falls back to the
        concat+pad path."""
        reg = get_registry()
        requests_c = reg.labeled_counter("tpudl_serve_requests_total")
        now = time.perf_counter()
        live = []
        for req in batch:
            if req.deadline is not None and now > req.deadline:
                requests_c.inc(status="expired")
                req.future.set_exception(DeadlineExceeded(
                    f"request expired in queue after "
                    f"{1e3 * (now - req.t_submit):.1f} ms"))
            elif not req.future.set_running_or_notify_cancel():
                requests_c.inc(status="cancelled")
            else:
                live.append(req)
        if not live:
            return
        rows = sum(r.n for r in live)
        queue_wait_s = now - min(r.t_submit for r in live)
        try:
            # chaos hook: an injected dispatch fault takes the real
            # error path below (per-request status="error" + serve_error
            # flight event) — how the SLO breach tests drive the
            # availability budget without a broken model
            faults.fire("serve.dispatch")
            bucket, padded = rows, 0
            if self.bucketing:
                bucket = self._bucket_for(rows)
                padded = bucket - rows
            if stage is not None and bucket > stage.capacity:
                stage = None    # sticky bucket outgrew the buffer
            if stage is not None:
                if len(live) != len(batch):
                    stage.restage(live)   # compact around dead requests
                features = stage.view(bucket, rows)
                mask = stage.mask_view(bucket, rows)
            else:
                features = (np.concatenate([r.x for r in live], axis=0)
                            if len(live) > 1 else live[0].x)
                mask = self._concat_masks(live)
                if padded:
                    features = _pad_rows(features, bucket)
                    if mask is not None:
                        mask = _pad_rows(mask, bucket)
            trace_ids = [r.trace_id for r in live if r.trace_id]
            traces_before = step_cache.jit_cache_entries(self._fwd)
            analyze_args = None
            # per-bucket cost entries: one forward fn holds one compiled
            # program PER bucket, and bucket-B's wall time must be
            # attributed bucket-B's FLOPs, not the first-analyzed one's.
            # A quantized engine shares the forward fn with its
            # full-precision sibling, so the precision joins the
            # signature — int8's (fewer) weight bytes must not launder
            # into the bf16 program's roofline numbers or vice versa.
            cost_sig = (bucket, self.precision) if self.precision != "fp" \
                else bucket
            if self._fwd is not None \
                    and costmodel.should_analyze(self._fwd, sig=cost_sig):
                analyze_args = costmodel.abstractify(
                    (self.model.params_, self.model.state_, features, mask))
            with tracing.span("serve", model=self.name, rows=rows,
                              requests=len(live), bucket=bucket,
                              queue_wait_ms=round(queue_wait_s * 1e3, 3)
                              ) as sp:
                if trace_ids:
                    sp.set_attribute("trace_ids", ",".join(trace_ids))
                t0 = time.perf_counter()
                out = np.asarray(tracing.device_sync(
                    self._forward(features, mask)))
                device_s = time.perf_counter() - t0
                sp.set_attribute("device_ms", round(device_s * 1e3, 3))
                if padded:
                    sp.set_attribute("padded", padded)
        except BaseException as e:
            flight_recorder.record("serve_error", model=self.name,
                                   requests=len(live), error=repr(e)[:200])
            for req in live:
                requests_c.inc(status="error")
                if not req.future.done():
                    req.future.set_exception(e)
            return
        end = time.perf_counter()
        try:
            # telemetry first (a caller returning from result() must see
            # the batch's metrics settled) but GUARDED: the worker's
            # "every Future resolves" contract must survive an
            # observability failure (e.g. the cost-model analyzer thread
            # failing to start under fd/thread pressure)
            retraced = step_cache.jit_cache_entries(self._fwd) \
                - traces_before
            if retraced > 0:
                reg.counter("tpudl_serve_recompiles_total").inc(retraced)
            if stage is not None:
                stage.uses += 1
                if stage.uses > 1:   # served from a REUSED staging buffer
                    reg.counter("tpudl_serve_stage_reuse_total").inc()
            if analyze_args is not None:
                kind = (costmodel.program_kind(self._fwd)
                        or f"serve:{type(self.model).__name__}")
                if self.precision != "fp":
                    kind = f"{kind}:{self.precision}"
                costmodel.schedule_analysis(
                    self._fwd, analyze_args, kind=kind, sig=cost_sig)
            if retraced == 0:
                # steady-state micro-batch: serving self-reports MFU/HBM
                # utilization of its compiled forward too
                costmodel.observe_step(self._fwd, device_s, sig=cost_sig)
            if self.precision != "fp":
                reg.counter("tpudl_serve_quantized_batches_total").inc()
            flight_recorder.progress("serve.dispatch")
            flight_recorder.record(
                "serve", model=self.name, rows=rows, requests=len(live),
                bucket=bucket, device_ms=round(device_s * 1e3, 3),
                queue_wait_ms=round(queue_wait_s * 1e3, 3),
                **({"trace_ids": trace_ids} if trace_ids else {}))
            reg.counter("tpudl_serve_batches_total").inc()
            reg.gauge("tpudl_serve_batch_size").set(bucket)
            latency_h = reg.histogram("tpudl_serve_latency_seconds")
            for req in live:
                requests_c.inc(status="ok")
                latency_h.observe(end - req.t_submit)
        except Exception:
            pass
        offset = 0
        for req in live:
            req.future.set_result(out[offset:offset + req.n])
            offset += req.n

    # ----------------------------------------------------------- lifecycle
    @property
    def queue_depth(self) -> int:
        """Requests currently waiting (the router's least-queue-depth
        dispatch signal — cheap, lock-free, approximate)."""
        return self._queue.qsize()

    @property
    def healthy(self) -> bool:
        """True while the worker thread is alive and the engine accepts
        submits — the router's per-replica health signal."""
        return self._worker.is_alive() and not self._closed.is_set()

    @property
    def compiled_programs(self) -> int:
        """Traced XLA programs behind this engine's forward (0 for
        fallback models) — the ≤1-per-bucket invariant's measurement.
        A forward warmed from the artifact store dispatches preloaded
        executables without tracing, so this stays 0 across a warm
        restart — exactly what the zero-JIT-on-the-request-path tests
        pin."""
        return step_cache.jit_cache_entries(self._fwd)

    @property
    def warm_programs(self) -> int:
        """Distinct call signatures this engine has served from the
        persistent artifact store (train/artifact_store) instead of
        compiling live."""
        served = getattr(self._fwd, "warm_served", None)
        return len(served) if served is not None else 0

    def shutdown(self, drain: bool = True, timeout_s: float = 30.0) -> None:
        """Stop the engine.  ``drain=True`` (default, and what the
        registry's hot-swap uses) serves everything already queued
        before the worker exits; ``drain=False`` fails queued requests
        with :class:`EngineClosed`.  New submits fail immediately either
        way."""
        if self._closed.is_set():
            self._worker.join(timeout=timeout_s)
            return
        self._closed.set()
        if not drain:
            reg = get_registry()
            requests_c = reg.labeled_counter("tpudl_serve_requests_total")
            while True:
                try:
                    req = self._queue.get_nowait()
                except queue.Empty:
                    break
                if req is self._SHUTDOWN:
                    continue
                requests_c.inc(status="error")
                req.future.set_exception(
                    EngineClosed(f"engine {self.name!r} shut down"))
        self._queue.put(self._SHUTDOWN)
        self._worker.join(timeout=timeout_s)
        # a submit that raced the closed flag may have landed BEHIND the
        # sentinel — no future may ever be stranded, so fail leftovers
        # (submit runs the same sweep when it loses the race even later)
        self._fail_leftovers()

    def _fail_leftovers(self) -> None:
        """Fail every request still queued after the worker has exited.
        Safe to run concurrently from shutdown and late submitters —
        ``get_nowait`` hands each request to exactly one sweeper."""
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                return
            if req is self._SHUTDOWN or req.future.done():
                continue
            get_registry().labeled_counter(
                "tpudl_serve_requests_total").inc(status="error")
            req.future.set_exception(
                EngineClosed(f"engine {self.name!r} shut down"))

    def __enter__(self) -> "InferenceEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()
