"""Training listener bus.

Parity with DL4J's ``TrainingListener`` callbacks
(deeplearning4j-nn ``org/deeplearning4j/optimize/api/TrainingListener.java``
and ``optimize/listeners/``: ScoreIterationListener, PerformanceListener,
TimeIterationListener, EvaluativeListener, CollectScoresIterationListener)
and SameDiff's ``org/nd4j/autodiff/listeners/Listener.java``.

The bus is the cross-cutting seam every aux feature hangs off (UI stats,
checkpoints, profiling) — built first per SURVEY.md §5.1.
"""

from __future__ import annotations

import logging
import time
from typing import Any, Callable, Optional

from deeplearning4j_tpu.obs.registry import get_registry

log = logging.getLogger("deeplearning4j_tpu")


class TrainingListener:
    """Callback interface.  All hooks are optional; ``model`` is the network
    object, ``info`` a plain dict of host-side scalars (already device→host
    synced by the trainer, so listeners never block the step)."""

    def on_epoch_start(self, model: Any, epoch: int) -> None: ...

    def on_epoch_end(self, model: Any, epoch: int, info: dict) -> None: ...

    def on_forward_pass(self, model: Any, activations: Any) -> None: ...

    def on_gradient_calculation(self, model: Any, gradients: Any) -> None: ...

    def iteration_done(self, model: Any, iteration: int, epoch: int, score: float) -> None: ...

    def on_fit_start(self, model: Any) -> None: ...

    def on_fit_end(self, model: Any, info: dict) -> None: ...


class ListenerBus:
    def __init__(self, listeners: Optional[list[TrainingListener]] = None):
        self.listeners: list[TrainingListener] = list(listeners or [])

    def add(self, listener: TrainingListener) -> None:
        self.listeners.append(listener)

    def dispatch(self, hook: str, *args: Any, **kwargs: Any) -> None:
        for listener in self.listeners:
            fn = getattr(listener, hook, None)
            if fn is not None:
                fn(*args, **kwargs)


class ScoreIterationListener(TrainingListener):
    """Logs the score (loss) every N iterations
    (``optimize/listeners/ScoreIterationListener.java``)."""

    def __init__(self, frequency: int = 10):
        self.frequency = max(1, frequency)

    def iteration_done(self, model, iteration, epoch, score):
        if iteration % self.frequency == 0 and log.isEnabledFor(logging.INFO):
            score = float(score)   # the one read of the device's scalar
            log.info("Score at iteration %d (epoch %d) is %.6f", iteration, epoch, score)
            get_registry().gauge("tpudl_train_last_score").set(score)


class CollectScoresListener(TrainingListener):
    """Accumulates (iteration, score) pairs in memory
    (``CollectScoresIterationListener``)."""

    def __init__(self):
        self.iterations: list[int] = []
        self.scores: list[float] = []

    def iteration_done(self, model, iteration, epoch, score):
        self.iterations.append(iteration)
        self.scores.append(float(score))


class PerformanceListener(TrainingListener):
    """Samples/sec and batches/sec every N iterations
    (``optimize/listeners/PerformanceListener.java``); also reports ETL wait
    time when the iterator provides it (AsyncDataSetIterator parity)."""

    def __init__(self, frequency: int = 10, report_batch: bool = True):
        self.frequency = max(1, frequency)
        self.report_batch = report_batch
        self._last_time: float | None = None
        self._last_iter = 0
        self._samples_since = 0

    def record_batch(self, batch_size: int) -> None:
        self._samples_since += batch_size

    def iteration_done(self, model, iteration, epoch, score):
        now = time.perf_counter()
        if self._last_time is None:
            self._last_time = now
            self._last_iter = iteration
            self._samples_since = 0
            return
        if iteration - self._last_iter >= self.frequency:
            dt = now - self._last_time
            iters = iteration - self._last_iter
            msg = f"{iters / dt:.1f} batches/sec"
            if self._samples_since:
                msg += f", {self._samples_since / dt:.1f} samples/sec"
            log.info("Perf at iteration %d: %s", iteration, msg)
            self._last_time = now
            self._last_iter = iteration
            self._samples_since = 0


class TimeIterationListener(TrainingListener):
    """Estimates remaining training time (``TimeIterationListener``)."""

    def __init__(self, total_iterations: int, frequency: int = 50):
        self.total = total_iterations
        self.frequency = max(1, frequency)
        self._start = time.perf_counter()

    def iteration_done(self, model, iteration, epoch, score):
        if iteration and iteration % self.frequency == 0:
            elapsed = time.perf_counter() - self._start
            per_iter = elapsed / max(iteration, 1)
            remaining = per_iter * max(self.total - iteration, 0)
            log.info("Iteration %d/%d, ETA %.1fs", iteration, self.total, remaining)


class EvaluativeListener(TrainingListener):
    """Runs an evaluation every N iterations or at epoch end
    (``optimize/listeners/EvaluativeListener.java``)."""

    def __init__(self, iterator_factory: Callable[[], Any], frequency: int = 0,
                 invocation: str = "epoch_end"):
        # invocation: "epoch_end" or "iteration"
        self.iterator_factory = iterator_factory
        self.frequency = frequency
        self.invocation = invocation
        self.evaluations: list[Any] = []

    def _evaluate(self, model) -> None:
        evaluation = model.evaluate(self.iterator_factory())
        self.evaluations.append(evaluation)
        log.info("EvaluativeListener: accuracy=%.4f", evaluation.accuracy())

    def iteration_done(self, model, iteration, epoch, score):
        if self.invocation == "iteration" and self.frequency and iteration % self.frequency == 0:
            self._evaluate(model)

    def on_epoch_end(self, model, epoch, info):
        if self.invocation == "epoch_end":
            self._evaluate(model)
