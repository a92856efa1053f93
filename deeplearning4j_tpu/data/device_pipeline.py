"""Device-feed pipeline — async host→device prefetch + shape bucketing.

Two TPU step-time cliffs live between the iterator and the jit boundary
(Abadi et al. input starvation; Fisher & Saba recompile cliffs, see
PAPERS.md):

1. **Input starvation** — the reference moves every batch host→device
   synchronously inside the step, so the accelerator idles behind ETL.
   :class:`DeviceFeeder` stages the NEXT batch (bucket-pad on host →
   ``Trainer._prepare_batch`` sharding → ``jax.device_put``) on a
   background thread while step N executes — true double buffering
   ahead of the donating train step (batch args are not donated, so an
   in-flight step never races the staging copy).

2. **Recompiles from ragged shapes** — a 103-example epoch at batch 32
   ends in a 7-row tail; a 10-step sequence under ``tbptt_fwd_length=4``
   ends in a 2-step segment.  Each new shape re-traces and re-compiles
   the whole XLA program.  :func:`pad_to_bucket` pads the batch dim up
   to a small static set of bucket shapes and extends/synthesizes
   ``labels_mask`` so padded rows contribute **zero loss and zero
   gradient**; :func:`pad_segment` does the same on the time axis for
   the final tBPTT segment.

Mask-extension rules (loss invariance — see docs/data_pipeline.md):

* an existing mask is extended with zeros for padded rows/steps;
* with no ``labels_mask``, one is synthesized — ones for real examples,
  zeros for padding — shaped like the per-example score array
  (``[B]`` for 2D labels, ``[B, T]`` for 3D sequence labels).  DL4J
  ``mini_batch=True`` mean semantics then divide by the *real* example
  count (``mean_score`` divides by ``sum(mask)``), so the padded loss
  equals the unpadded loss and padded rows get zero gradient;
* for structural stability (one pytree → one compile) the feeder
  attaches the synthesized mask to **every** batch of a bucketed
  stream, not just the ragged tail.

Caveat: batch statistics (BatchNorm) are computed over all rows,
including padding — for BN nets the tail batch's statistics shift
slightly.  Use ``drop_last`` iterators or ``set_config(
shape_bucketing=False)`` where bit-exact BN tail behavior matters.

``MultiDataSet`` (ComputationGraph) batches ride the async stage but are
not bucketed (per-output mask-plural loss semantics don't compose with
synthesis yet); their ragged tails recompile exactly as before.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, Callable, Iterable, Iterator, Optional, Sequence

import numpy as np

from deeplearning4j_tpu.config import get_config
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.obs import tracing
from deeplearning4j_tpu.obs.registry import get_registry
from deeplearning4j_tpu.resilience import faults
from deeplearning4j_tpu.resilience.retry import RetryPolicy, with_retries


# ---------------------------------------------------------------- bucketing
def choose_bucket(n: int, buckets: Sequence[int]) -> int:
    """Smallest bucket ≥ n; n itself when every bucket is too small."""
    for b in sorted(buckets):
        if b >= n:
            return int(b)
    return int(n)


def _pad_rows(a, total: int):
    a = np.asarray(a)
    if a.shape[0] >= total:
        return a
    widths = [(0, total - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
    return np.pad(a, widths)


def synth_example_mask(labels, real: int, total: int) -> np.ndarray:
    """Ones for the ``real`` leading examples, zeros for padding, shaped
    like the per-example score array (``[B]``, or ``[B, T]`` for 3D
    sequence labels)."""
    labels = np.asarray(labels)
    shape = (total, labels.shape[1]) if labels.ndim == 3 else (total,)
    mask = np.zeros(shape, np.float32)
    mask[:real] = 1.0
    return mask


def pad_to_bucket(batch: DataSet, bucket: int,
                  attach_mask: bool = True) -> tuple[DataSet, int]:
    """Pad ``batch`` along the example dim up to ``bucket``; returns
    ``(padded_batch, real_example_count)``.

    Existing masks are zero-extended; with ``attach_mask`` a
    ``labels_mask`` is synthesized when absent (even at zero padding, so
    every batch of a bucketed stream shares one pytree structure — a
    mask appearing only on the tail batch would itself recompile)."""
    if not isinstance(batch, DataSet):
        return batch, batch.num_examples()
    n = batch.num_examples()
    total = max(int(bucket), n)
    needs_mask = attach_mask and batch.labels is not None \
        and batch.labels_mask is None
    if total == n and not needs_mask:
        return batch, n
    labels = None if batch.labels is None else _pad_rows(batch.labels, total)
    if batch.labels_mask is not None:
        lmask = _pad_rows(batch.labels_mask, total)
    elif needs_mask:
        lmask = synth_example_mask(labels, n, total)
    else:
        lmask = None
    return DataSet(
        _pad_rows(batch.features, total), labels,
        None if batch.features_mask is None
        else _pad_rows(batch.features_mask, total),
        lmask), n


# ------------------------------------------------------- tBPTT tail padding
def _pad_time(a, length: int):
    """Pad axis 1 (time) with zeros up to ``length``; numpy in → numpy
    out, device array in → device op (no host round-trip)."""
    t = a.shape[1]
    if t >= length:
        return a
    widths = [(0, 0), (0, length - t)] + [(0, 0)] * (a.ndim - 2)
    if isinstance(a, np.ndarray):
        return np.pad(a, widths)
    import jax.numpy as jnp
    return jnp.pad(a, widths)


def ensure_feature_mask(batch):
    """Attach an all-ones ``[B, T]`` features_mask when absent.  Called
    once per non-divisible tBPTT batch so every segment — including the
    padded tail — shares one pytree structure; recurrent layers treat
    masked steps as carry-through, so an all-ones mask is forward-exact
    and the zero tail leaves carries and loss untouched."""
    if batch.features_mask is not None:
        return batch
    f = batch.features
    if isinstance(f, np.ndarray):
        mask = np.ones(f.shape[:2], np.float32)
    else:
        import jax.numpy as jnp
        mask = jnp.ones(f.shape[:2], jnp.float32)
    return dataclasses.replace(batch, features_mask=mask)


def pad_segment(seg, length: int):
    """Pad a tBPTT segment's time axis to the static segment ``length``
    with a masked tail (zero features, zero mask — zero loss, zero
    gradient, carry-through recurrent state)."""
    fields: dict[str, Any] = {"features": _pad_time(seg.features, length)}
    if seg.labels is not None and getattr(seg.labels, "ndim", 0) == 3:
        fields["labels"] = _pad_time(seg.labels, length)
    if seg.features_mask is not None:
        fields["features_mask"] = _pad_time(seg.features_mask, length)
    if seg.labels_mask is not None and getattr(seg.labels_mask, "ndim", 0) >= 2:
        fields["labels_mask"] = _pad_time(seg.labels_mask, length)
    return dataclasses.replace(seg, **fields)


# ------------------------------------------------------------ device feeder
def _leading_dim(obj) -> int:
    """Best-effort example count of an arbitrary staged batch (DataSet,
    MultiDataSet, dict, or array tuple); 0 when undeterminable."""
    feats = getattr(obj, "features", None)
    if feats is None:
        if isinstance(obj, dict):
            feats = next(iter(obj.values()), None)
        elif isinstance(obj, (list, tuple)):
            feats = obj[0] if obj else None
        else:
            feats = obj
    if isinstance(feats, (list, tuple)):
        feats = feats[0] if feats else None
    shape = getattr(feats, "shape", None)
    return int(shape[0]) if shape else 0


@dataclasses.dataclass
class FedBatch:
    """One staged batch: device-resident arrays + the real (unpadded)
    example count the metrics/listeners must see."""

    batch: Any
    n_examples: int
    padded: int = 0
    bucket: Optional[int] = None


class DeviceFeeder:
    """Overlap host ETL + H2D transfer with device execution.

    A background stage runs ``bucket-pad → place_fn`` per batch
    (``place_fn`` is the trainer's ``_prepare_batch`` + device
    conversion — for ``ParallelWrapper`` that is the sharded
    ``jax.device_put`` against the trainer's mesh) and keeps a bounded
    queue of device-ready :class:`FedBatch`es, so step N+1's transfer
    rides under step N's execution.

    Queue discipline is event-driven: the producer blocks in ``put`` and
    the consumer *drains* the queue on abandonment (no polling
    timeouts on the hot path).  Per batch, each span beside the counter
    taken at the same boundary: on the producer's thread ``feed.source``
    / ``tpudl_data_source_seconds`` (``next()`` on the iterator) and
    ``feed.stage`` / ``tpudl_data_stage_seconds`` (:meth:`stage`, retries
    included); on the consumer's ``feed.wait`` /
    ``tpudl_data_etl_wait_seconds`` (the queue get), and
    ``tpudl_data_prefetch_depth`` (ready batches after each get).
    """

    _DONE = object()

    def __init__(self, place_fn: Optional[Callable[[Any], Any]] = None,
                 depth: Optional[int] = None,
                 bucketing: Optional[bool] = None,
                 buckets: Optional[Sequence[int]] = None,
                 retry_policy: Optional[RetryPolicy] = None):
        cfg = get_config()
        self.place_fn = place_fn if place_fn is not None else (lambda b: b)
        self.depth = max(1, cfg.prefetch_size if depth is None else depth)
        self.bucketing = (cfg.shape_bucketing if bucketing is None
                          else bucketing)
        self.buckets: tuple[int, ...] = tuple(
            sorted(int(b) for b in buckets)) if buckets else ()
        self.etl_wait_s = 0.0   # PerformanceListener parity attribute
        # transient staging failures (a flaky H2D transfer, an injected
        # feeder fault) retry briefly on the producer thread; persistent
        # ones re-raise on the CONSUMER with the original traceback
        self.retry_policy = retry_policy or RetryPolicy(
            max_attempts=2, base_delay_s=0.02, max_delay_s=0.2)

    def _bucket_for(self, n: int) -> int:
        bucket = choose_bucket(n, self.buckets)
        if bucket not in self.buckets:
            # first batch (or an oversize one) defines a new static
            # bucket — typically the full batch size, so every ragged
            # tail thereafter pads up to an already-compiled shape
            self.buckets = tuple(sorted(self.buckets + (bucket,)))
        return bucket

    def stage(self, batch) -> FedBatch:
        """Producer-side work for one batch: host-side bucket padding,
        then device placement via ``place_fn``.  The ``feeder.stage``
        fault site fires per attempt, so injected transient errors
        exercise the producer's retry path."""
        padded, bucket = 0, None
        n = batch.num_examples() if hasattr(batch, "num_examples") else None
        if self.bucketing and isinstance(batch, DataSet):
            bucket = self._bucket_for(n)
            batch, n = pad_to_bucket(batch, bucket)
            padded = max(bucket - n, 0)
        faults.fire("feeder.stage")
        placed = self.place_fn(batch)
        if n is None:
            n = _leading_dim(placed)
        return FedBatch(placed, n, padded, bucket)

    def feed(self, iterator: Iterable) -> Iterator[FedBatch]:
        """Iterate ``iterator`` through the background stage, yielding
        device-ready :class:`FedBatch`es in order."""
        self.etl_wait_s = 0.0   # fresh per epoch
        q: queue.Queue = queue.Queue(maxsize=self.depth)
        stop = threading.Event()
        error: list[BaseException] = []

        reg = get_registry()
        source_hist = reg.histogram("tpudl_data_source_seconds")
        stage_hist = reg.histogram("tpudl_data_stage_seconds")
        wait_hist = reg.histogram("tpudl_data_etl_wait_seconds")
        depth_gauge = reg.gauge("tpudl_data_prefetch_depth")
        # the producer's thread has no ambient span: its spans join the
        # trace of whatever span called feed() (the trainer's epoch)
        parent = tracing.current_context()

        def producer():
            try:
                source = iter(iterator)
                while not stop.is_set():
                    t0 = time.perf_counter()
                    with tracing.span("feed.source", parent=parent) as sp:
                        item = next(source, self._DONE)
                        if item is self._DONE:
                            sp.set_attribute("exhausted", True)
                    if item is self._DONE:
                        return
                    t1 = time.perf_counter()
                    source_hist.observe(t1 - t0)
                    if stop.is_set():
                        return
                    with tracing.span("feed.stage", parent=parent) as sp:
                        staged = with_retries(
                            lambda item=item: self.stage(item),
                            policy=self.retry_policy, site="feeder.stage")
                        sp.set_attribute("n_examples", staged.n_examples)
                    stage_hist.observe(time.perf_counter() - t1)
                    q.put(staged)   # blocking; consumer drains on abandon
            except BaseException as e:   # surfaced on the consumer side
                error.append(e)
            finally:
                if not stop.is_set():
                    q.put(self._DONE)

        thread = threading.Thread(target=producer, daemon=True,
                                  name="tpudl-device-feeder")
        # per-epoch thread owned by feed() itself — no class-level close()
        # exists on purpose: the generator's finally stops and drains it,
        # and a join would block the abandoning consumer on in-flight
        # staging (see _drain's docstring)
        # tpudl: ok(TPU405) — feed()'s own finally stops+drains the producer
        thread.start()
        try:
            while True:
                t0 = time.perf_counter()
                with tracing.span("feed.wait") as sp:
                    item = q.get()
                    wait = time.perf_counter() - t0
                    if item is not self._DONE:
                        sp.set_attribute("wait_ms", round(wait * 1e3, 3))
                        sp.set_attribute("n_examples", item.n_examples)
                        if item.padded:
                            sp.set_attribute("padded", item.padded)
                if item is self._DONE:
                    if error:
                        raise error[0]
                    return
                self.etl_wait_s += wait
                wait_hist.observe(wait)
                # batches still ready AFTER taking this one: 0 here means
                # the consumer is racing the producer (starvation)
                depth_gauge.set(q.qsize())
                yield item
        finally:
            stop.set()
            _drain(q, thread)


def _drain(q: queue.Queue, thread: threading.Thread) -> None:
    """Release a producer blocked in ``put`` after the consumer abandons
    the epoch (break / EarlyTermination / error) — WITHOUT waiting for
    any in-flight staging work.  The stop flag is already set, so the
    producer stages at most one more item; emptying the queue guarantees
    it space for that final put (and for a sentinel it may already be
    blocked on), after which it sees the flag and exits on its own
    daemon thread while the consumer returns immediately."""
    while True:
        try:
            q.get_nowait()
        except queue.Empty:
            break
