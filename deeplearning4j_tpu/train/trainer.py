"""Training loop — the Solver/StochasticGradientDescent replacement.

Parity with DL4J ``org/deeplearning4j/optimize/solvers/
StochasticGradientDescent.java`` + ``MultiLayerNetwork.fitHelper`` (stack
3.1 in SURVEY.md): per-batch {forward, score, backward, updater, listeners}.
On TPU the whole step — forward, loss, backward, gradient normalization,
updater, param update — is ONE jit-compiled XLA program; listeners receive
host-side scalars after the step.

Loss composition (``BaseLayer.calcRegularizationScore`` +
``ILossFunction.computeScore``): mean per-example loss + Σ layer L1/L2
penalties.  Gradients are averaged over the minibatch (``mini_batch=True``
divides by batch size, DL4J semantics).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import time
from typing import Any, Iterable, Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.config import get_config
from deeplearning4j_tpu.data.device_pipeline import (
    DeviceFeeder, FedBatch, ensure_feature_mask, pad_segment)
from deeplearning4j_tpu.nn.losses import mean_score
from deeplearning4j_tpu.obs import costmodel, flight_recorder, tracing
from deeplearning4j_tpu.obs import remote as obs_remote
from deeplearning4j_tpu.obs.listeners import ListenerBus
from deeplearning4j_tpu.obs.profiler import check_finite
from deeplearning4j_tpu.obs.registry import (
    get_registry, record_device_memory, train_loop_metrics)
from deeplearning4j_tpu.resilience import faults
from deeplearning4j_tpu.train import step_cache
from deeplearning4j_tpu.train import updaters as updater_mod


def _as_device(v):
    """Host → device array(s); MultiDataSet features/labels are tuples."""
    if v is None:
        return None
    if isinstance(v, (list, tuple)):
        return tuple(None if a is None else jnp.asarray(a) for a in v)
    return jnp.asarray(v)


def _batch_masks(batch):
    """(features_mask, labels_mask) with MultiDataSet plural-name fallback."""
    fmask = getattr(batch, "features_mask", None)
    if fmask is None:
        fmask = getattr(batch, "features_masks", None)
    lmask = getattr(batch, "labels_mask", None)
    if lmask is None:
        lmask = getattr(batch, "labels_masks", None)
    return fmask, lmask


def make_loss_fn(net, with_carries: bool = False, train: bool = True):
    """Build the pure loss fn.  Default signature: (params, state, features,
    labels, fmask, lmask, rng) → (scalar_loss, new_state).  With
    ``with_carries`` (tBPTT), signature gains a ``carries`` arg after
    ``state`` and the aux becomes ``(new_state, new_carries)``.
    ``train=False`` scores in inference mode (no dropout; frozen BN stats)
    — ``DataSetLossCalculator`` / ``MultiLayerNetwork.score(DataSet)``."""

    def _score(params, state, score_array, features_mask, labels_mask):
        if score_array is None:
            raise ValueError(
                "last layer has no loss — use OutputLayer/LossLayer/"
                "RnnOutputLayer as the final layer for fit()")
        mask = labels_mask
        if mask is None and score_array.ndim == 2 and features_mask is not None:
            mask = features_mask  # per-timestep RNN scores fall back to feature mask
        if net.conf.mini_batch:
            data_loss = mean_score(score_array, mask)
        else:
            # minibatch(false) parity: do NOT divide by batch size
            if mask is not None:
                score_array = score_array * jnp.reshape(mask, score_array.shape)
            data_loss = jnp.sum(score_array)
        reg = jnp.float32(0.0)
        layer_params = (net.layer_params(params) if hasattr(net, "layer_params")
                        else params)
        for layer, p in zip(net.layers, layer_params):
            if p:
                reg = reg + layer.regularization_penalty(p)
        return data_loss + reg

    if with_carries:
        def loss_fn(params, state, carries, features, labels, features_mask,
                    labels_mask, rng):
            out, new_state, score_array, new_carries = net._forward_impl(
                params, state, features, carries, train=train, rng=rng,
                mask=features_mask, labels=labels)
            with jax.named_scope("loss"):
                loss = _score(params, state, score_array, features_mask,
                              labels_mask)
            return loss, (new_state, new_carries)
    else:
        def loss_fn(params, state, features, labels, features_mask,
                    labels_mask, rng):
            out, new_state, score_array = net._forward(
                params, state, features, train=train, rng=rng,
                mask=features_mask, labels=labels)
            with jax.named_scope("loss"):
                loss = _score(params, state, score_array, features_mask,
                              labels_mask)
            return loss, new_state

    return loss_fn


def make_tbptt_step(net, tx, opt_state_shardings=None):
    """jit'd tBPTT segment step: like ``make_train_step`` but threads
    recurrent carries — forward state flows across segments, gradients
    truncate at segment boundaries (``stop_gradient`` inside
    ``_forward_impl``).  DL4J parity:
    ``MultiLayerNetwork.rnnActivateUsingStoredState`` + tBPTT."""
    loss_fn = make_loss_fn(net, with_carries=True)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def tpudl_tbptt_step(params, state, opt_state, carries, features, labels,
                         features_mask, labels_mask, rng):
        (loss, (new_state, new_carries)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, state, carries, features, labels,
                                   features_mask, labels_mask, rng)
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, opt_state, params)
            if opt_state_shardings is not None:   # ZeRO-1 placement pin
                opt_state = jax.lax.with_sharding_constraint(
                    opt_state, opt_state_shardings)
            params = jax.tree_util.tree_map(lambda p, u: p + u, params,
                                            updates)
        return params, new_state, opt_state, new_carries, loss

    return tpudl_tbptt_step


def make_train_step(net, tx, with_stats: bool = False,
                    opt_state_shardings=None):
    """jit'd (params, state, opt_state, batch..., rng) → updated triple + loss.

    ``with_stats=True`` additionally returns per-layer parameter /
    gradient / update statistics (L2 norms, mean/stdev, 20-bin histograms)
    computed ON DEVICE inside the same program — the StatsListener samples
    this step at its frequency, so stats cost nothing on non-sampled
    iterations and never round-trip full tensors to the host.

    ``opt_state_shardings`` (a pytree of NamedSharding matching the
    opt_state) pins the updated optimizer state's placement — the
    ZeRO-1 hook: GSPMD then keeps each updater-state shard resident on
    its owning device instead of re-replicating it every step."""
    loss_fn = make_loss_fn(net)

    def _layer_stats(tree):
        from deeplearning4j_tpu.obs.stats import device_layer_stats
        return device_layer_stats(tree)

    # donate params/state/opt_state buffers: the step's outputs reuse their
    # HBM (essential for large models — no 2x parameter memory)
    # The function's name is the program's on the device trace's XLA Modules
    # line (jit_tpudl_train_step); the scopes (each layer's in the forward
    # loop, "loss", "optimizer") are on its operations.
    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def tpudl_train_step(params, state, opt_state, features, labels,
                         features_mask, labels_mask, rng):
        (loss, new_state), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, state, features, labels, features_mask, labels_mask, rng)
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, opt_state, params)
            if opt_state_shardings is not None:
                opt_state = jax.lax.with_sharding_constraint(
                    opt_state, opt_state_shardings)
            new_params = jax.tree_util.tree_map(lambda p, u: p + u, params,
                                                updates)
        if with_stats:
            with jax.named_scope("stats"):
                stats = {"params": _layer_stats(new_params),
                         "gradients": _layer_stats(grads),
                         "updates": _layer_stats(updates)}
            return new_params, new_state, opt_state, loss, stats
        return new_params, new_state, opt_state, loss

    return tpudl_train_step


def make_eval_step(net):
    """jit'd inference-mode loss: (params, state, features, labels,
    fmask, lmask) → scalar loss (``MultiLayerNetwork.score(DataSet)``)."""
    loss_fn = make_loss_fn(net, train=False)

    @jax.jit
    def _eval(params, state, features, labels, fmask, lmask):
        loss, _ = loss_fn(params, state, features, labels, fmask, lmask,
                          None)
        return loss

    return _eval


class Trainer:
    def __init__(self, net, listeners=None, mesh=None, layout=None,
                 n_microbatches: int = 1):
        """``mesh=`` / ``layout=`` — the ONE flag that picks a parallel
        layout on the unified device mesh (docs/PARALLELISM.md): a
        layout string (``"dp2"``, ``"dp2xtp2"``, ``"dp2xtp2xpp2"``), a
        ``parallel.mesh.MeshSpec``/``MeshLayout``, or a
        ``jax.sharding.Mesh`` built by ``make_mesh``.  data/model axes
        run the donated GSPMD step (batch sharded over ``data``, params
        per the TP rule family over ``model``); a ``pipe`` axis lowers
        onto the 1F1B pipeline (``n_microbatches`` microbatches; 1 keeps
        dropout bit-compatible with the single-device run).  No flag =
        the single-device path, unchanged."""
        self.net = net
        self.bus = listeners if isinstance(listeners, ListenerBus) else ListenerBus(listeners)
        self._layout = None
        self._n_microbatches = int(n_microbatches)
        if mesh is not None or layout is not None:
            # local import: parallel/__init__ imports trainer back
            from deeplearning4j_tpu.parallel import mesh as mesh_mod
            self._layout = mesh_mod.resolve_layout(mesh=mesh, layout=layout)
        self._layout_placed = False
        conf = net.conf
        updater = conf.updater or updater_mod.Sgd(0.1)
        if net.params_ is None:
            net.init()
        self._per_layer_updaters = any(
            getattr(l, "updater", None) is not None for l in net.layers)
        frozen_mask = None
        if any(getattr(l, "frozen", False) for l in net.layers):
            layer_params = (net.layer_params(net.params_) if hasattr(net, "layer_params")
                            else net.params_)
            per_layer = [jax.tree_util.tree_map(lambda _: bool(layer.frozen), p)
                         for layer, p in zip(net.layers, layer_params)]
            if hasattr(net, "layer_params"):
                # rebuild the dict-shaped mask for ComputationGraph
                frozen_mask = {}
                li = 0
                for spec in net._topo:
                    if spec.kind == "layer":
                        frozen_mask[spec.name] = per_layer[li]
                        li += 1
                    else:
                        frozen_mask[spec.name] = {}
            else:
                frozen_mask = per_layer
        if self._per_layer_updaters:
            self.tx = self._build_multi_updater(updater, conf, frozen_mask)
        else:
            self.tx = updater_mod.build_optimizer(
                updater, conf.gradient_normalization,
                conf.gradient_normalization_threshold, frozen_mask)
        self._step = None
        self._tbptt_step = None
        self._stats_step = None
        self._eval_loss_fn = None
        # artifact-store bookkeeping: the first step's abstract call
        # signature (what a bake lowers against) and the one-shot
        # background-bake latch (config.artifact_bake)
        self._bake_args = None
        self._tbptt_bake_args = None
        self._bake_scheduled = False
        self._stats_listeners = [l for l in self.bus.listeners
                                 if getattr(l, "wants_model_stats", False)]
        self._compiled = False   # first step through a jit boundary = compile
        # host seconds of the current iteration inside the jitted call(s)
        # and inside listeners; step_batch zeroes and observes them
        self._dispatch_s = self._read_s = 0.0
        self._step_metrics = None   # (registry, handles): see _metrics()
        # layers whose state holds the last step's counters as device
        # scalars (RoutedExperts' routing load): {vertex: STEP_COUNTERS}
        self._step_counters = {
            spec.name: spec.obj.STEP_COUNTERS
            for spec in getattr(net, "_topo", ())
            if getattr(spec.obj, "STEP_COUNTERS", None)}
        # process-level step-cache identity; None (per-layer updaters,
        # frozen layers, unserializable conf) = build per instance
        self._cache_sig = None
        if not self._per_layer_updaters and frozen_mask is None:
            net_sig = step_cache.net_signature(net)
            tx_sig = step_cache.updater_signature(conf)
            if net_sig is not None and tx_sig is not None:
                self._cache_sig = net_sig + (tx_sig,)

    def _build_multi_updater(self, default_updater, conf, frozen_mask):
        """Per-layer updater overrides (DL4J allows ``layer.updater(...)``):
        optax.multi_transform with one label per distinct updater."""
        import optax
        net = self.net
        transforms = {"_default": updater_mod.build_optimizer(
            default_updater, conf.gradient_normalization,
            conf.gradient_normalization_threshold)}
        layer_labels = []
        for i, layer in enumerate(net.layers):
            if getattr(layer, "updater", None) is not None:
                label = f"layer_{i}"
                transforms[label] = updater_mod.build_optimizer(
                    layer.updater, conf.gradient_normalization,
                    conf.gradient_normalization_threshold)
            else:
                label = "_default"
            layer_labels.append(label)

        def label_tree(params):
            layer_params = (net.layer_params(params) if hasattr(net, "layer_params")
                            else params)
            per_layer = [jax.tree_util.tree_map(lambda _: lbl, p)
                         for lbl, p in zip(layer_labels, layer_params)]
            if hasattr(net, "layer_params"):
                out, li = {}, 0
                for spec in net._topo:
                    if spec.kind == "layer":
                        out[spec.name] = per_layer[li]
                        li += 1
                    else:
                        out[spec.name] = {}
                return out
            return per_layer

        tx = optax.multi_transform(transforms, label_tree)
        if frozen_mask is not None:
            def mask_fn(updates, state, params=None):
                return jax.tree_util.tree_map(
                    lambda u, m: jnp.zeros_like(u) if m else u,
                    updates, frozen_mask), state
            import optax as _optax
            tx = _optax.chain(tx, _optax.GradientTransformation(
                lambda p: _optax.EmptyState(), mask_fn))
        return tx

    # pytree of NamedSharding for the opt_state, set by subclasses BEFORE
    # the first step is built (ParallelWrapper's ZeRO-1 mode)
    _opt_state_shardings = None
    # layout bookkeeping: param placement tree + one-shot opt placement
    _param_shardings = None
    _opt_placed = False
    # elastic: a width requested mid-epoch (request_resize), applied by
    # fit() at the next epoch boundary — the round boundary where the
    # feeder restarts, so no stale-sharded batch crosses the flip
    _pending_resize = None
    # which jit program (and how many calls of it) the last fit_batch/
    # tbptt pass ran — the cost model's per-step MFU denominator pairing
    _last_step_fn = None
    _last_step_calls = 1

    def _layout_sig(self) -> str:
        """Deterministic layout component of the step-cache key — the
        sharded program is a DIFFERENT executable (and a different
        artifact-store entry) than its single-device sibling, and a
        DP=2 child must rebuild the exact key its parent baked under."""
        if self._layout is None:
            return ""
        sig = self._layout.cache_signature()
        if self._layout.pipe > 1:
            sig += f"|mb:{self._n_microbatches}"
        return sig

    def _step_key(self, kind: str) -> Optional[tuple]:
        """Step-cache key for this trainer's config, or None (no cache)."""
        if self._cache_sig is None:
            return None
        return self._cache_sig + (
            step_cache.sharding_signature(self._opt_state_shardings),
            self._layout_sig(), kind)

    def _jit_step_fns(self) -> tuple:
        """Every jit-wrapped step this trainer may call — the recompile
        guard sums their traced-program counts around each step."""
        return (self._step, self._stats_step, self._tbptt_step,
                self._eval_loss_fn)

    def _ensure_ready(self):
        net = self.net
        if net.params_ is None:
            net.init()
        if self._layout is not None and not self._layout_placed:
            self._place_layout()
        if net.opt_state is None:
            net.opt_state = self.tx.init(net.params_)
        if self._layout is not None and not self._opt_placed:
            # place the updater state like the params it mirrors (Adam
            # mu/nu take the param layout; counts replicate) — a
            # deterministic derivation, so two processes produce the
            # SAME sharding signature (the warm-restart key contract).
            # A subclass that preset _opt_state_shardings (ZeRO-1) keeps
            # its own placement.
            if self._opt_state_shardings is not None:
                osh = self._opt_state_shardings
            else:
                osh = self._layout.opt_state_sharding_tree(
                    net.opt_state, net.params_,
                    param_shardings=self._param_shardings)
            net.opt_state = jax.tree_util.tree_map(
                jax.device_put, net.opt_state, osh)
            if self._layout.model > 1 and self._layout.pipe == 1 \
                    and self._opt_state_shardings is None:
                # the with_sharding_constraint pin in the GSPMD step
                # keeps XLA from re-replicating the moments every step
                self._opt_state_shardings = osh
            self._opt_placed = True
        if self._step is None:
            if self._layout is not None and self._layout.pipe > 1:
                from deeplearning4j_tpu.parallel import unified
                layout, mb = self._layout, self._n_microbatches
                self._step = step_cache.get_or_build(
                    self._step_key("train"),
                    lambda: unified.make_pp_train_step(
                        net, self.tx, layout, mb))
            else:
                self._step = step_cache.get_or_build(
                    self._step_key("train"),
                    lambda: make_train_step(
                        net, self.tx,
                        opt_state_shardings=self._opt_state_shardings))

    def _place_layout(self):
        """One-time placement of params/state onto the unified mesh:
        data/model layouts follow the TP rule family (replicated when
        model == 1); pipe layouts place params dim-0-sharded over
        ``model`` (gathered on use inside their stage).  Publishes the
        ``tpudl_mesh_*`` gauges for the active layout."""
        layout, net = self._layout, self.net
        if layout.pipe > 1:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as _P

            # validation happens in make_pp_train_step (the builder is
            # the one external callers can also reach) — not here too:
            # each pass costs a per-layer host sync
            from deeplearning4j_tpu.parallel import unified
            specs = unified.pp_layer_spec_tree(net.params_, layout.model)
            pshard = jax.tree_util.tree_map(
                lambda spec: NamedSharding(layout.mesh, spec), specs,
                is_leaf=lambda v: isinstance(v, _P))
        else:
            net.state_ = layout.replicate(net.state_)
            pshard = layout.param_sharding_tree(net.params_)
        net.params_ = jax.tree_util.tree_map(
            jax.device_put, net.params_, pshard)
        self._param_shardings = pshard
        param_bytes = sum(
            int(l.size) * l.dtype.itemsize
            for l in jax.tree_util.tree_leaves(net.params_)
            if hasattr(l, "size"))
        layout.publish_metrics(param_bytes=param_bytes)
        get_registry().gauge("tpudl_parallel_mesh_devices").set(
            int(layout.data))
        self._layout_placed = True

    # ------------------------------------------------------------- elastic
    def request_resize(self, n_devices: int) -> None:
        """Ask for an elastic resize at the NEXT epoch (round) boundary.

        Validates eagerly — an impossible width (no layout on this
        trainer, or a width the layout's fixed axes don't divide) raises
        here, at the decision site, not an epoch later inside fit().
        The flip itself happens in :meth:`resize_mesh`, which fit()
        calls between epochs so no batch sharded for the old width ever
        meets the new step."""
        from deeplearning4j_tpu.parallel import mesh as mesh_mod
        if self._layout is None:
            raise ValueError(
                "request_resize needs a mesh/layout-configured Trainer "
                "(the single-device path has no width to change)")
        mesh_mod.resize_spec(self._layout.spec, int(n_devices))  # validate
        self._pending_resize = int(n_devices)

    def resize_mesh(self, n_devices: int) -> bool:
        """Reshard this trainer onto the SAME layout at a new device
        width (grow or shrink), checkpoint-consistently: the new
        ``MeshLayout`` is derived first (a non-divisible width raises
        :class:`parallel.mesh.LayoutResizeError` before anything
        mutates), then params/opt-state are device_put onto the new
        layout's structure-matched sharding trees — the PR-14 derivation,
        so post-flip state is bit-identical to a from-scratch build at
        the new width and the 1e-6 loss contract holds across the
        boundary.  Returns False when the width is already current.

        The ``gang.grow`` fault site fires BEFORE any state is touched:
        an injected crash/kill mid-reshard leaves the old layout fully
        consistent (no torn placement), which is what the supervisor
        drill in tests/test_elastic.py pins."""
        from deeplearning4j_tpu.parallel import mesh as mesh_mod
        n_devices = int(n_devices)
        self._pending_resize = None
        if self._layout is None:
            raise ValueError(
                "resize_mesh needs a mesh/layout-configured Trainer")
        old_width = self._layout.spec.total()
        if n_devices == old_width:
            return False
        # derive-then-commit: a typed LayoutResizeError escapes here
        # with the trainer untouched
        new_layout = mesh_mod.resize_layout(self._layout, n_devices)
        grow = n_devices > old_width
        if grow:
            faults.fire("gang.grow")
        t0 = time.perf_counter()
        self._layout = new_layout
        # every derived artifact of the old width is stale: placement,
        # sharding trees, compiled steps and their bake bookkeeping
        self._layout_placed = False
        self._opt_placed = False
        self._param_shardings = None
        self._opt_state_shardings = None
        self._step = None
        self._stats_step = None
        self._tbptt_step = None
        self._eval_loss_fn = None
        self._bake_args = None
        self._tbptt_bake_args = None
        self._bake_scheduled = False
        # eager re-place + step rebuild: the flip's full cost lands here
        # (where flip MTTR is measured), not on the first post-flip step
        self._ensure_ready()
        flip_s = time.perf_counter() - t0
        reg = get_registry()
        reg.counter("tpudl_elastic_grows_total" if grow
                    else "tpudl_elastic_shrinks_total").inc()
        reg.gauge("tpudl_elastic_gang_width").set(n_devices)
        reg.histogram("tpudl_elastic_flip_seconds").observe(flip_s)
        flight_recorder.record(
            "elastic_resize", direction="grow" if grow else "shrink",
            from_width=old_width, to_width=n_devices,
            layout=new_layout.spec.describe(), flip_s=flip_s)
        obs_remote.notify_event(
            "elastic_resize", direction="grow" if grow else "shrink",
            from_width=old_width, to_width=n_devices)
        return True

    def _prepare_batch(self, batch):
        """Hook: with an active layout the batch shards its leading dim
        over ``data`` (replicated across the other axes); subclasses
        (ParallelWrapper's averaging mode) override; identity for the
        single-device trainer."""
        if self._layout is None:
            return batch
        fields = {}
        for name in ("features", "labels", "features_mask", "labels_mask",
                     "features_masks", "labels_masks"):
            v = getattr(batch, name, None)
            if v is not None:
                fields[name] = self._layout.shard_batch(v)
        return dataclasses.replace(batch, **fields) if fields else batch

    def _place_batch(self, batch):
        """Full host→device placement for one batch: the subclass
        sharding hook, then device conversion of every array.  The
        DeviceFeeder runs this on its background stage so the transfer
        of batch N+1 overlaps step N; direct ``fit_batch`` callers hit
        it inline (the old synchronous behavior)."""
        batch = self._prepare_batch(batch)
        fields = {}
        for name in ("features", "labels", "features_mask", "labels_mask",
                     "features_masks", "labels_masks"):
            v = getattr(batch, name, None)
            if v is not None:
                fields[name] = _as_device(v)
        return dataclasses.replace(batch, **fields) if fields else batch

    def eval_loss(self, batch) -> float:
        """Inference-mode loss on one batch, no parameter update
        (``MultiLayerNetwork.score(DataSet)`` parity).  Eval-only: does
        NOT allocate optimizer state or build the donating train step."""
        if self.net.params_ is None:
            self.net.init()
        if isinstance(batch, FedBatch):
            batch = batch.batch
        else:
            batch = self._place_batch(batch)
        if self._eval_loss_fn is None:
            self._eval_loss_fn = step_cache.get_or_build(
                self._step_key("eval"), lambda: make_eval_step(self.net))
        net = self.net
        fmask, lmask = _batch_masks(batch)
        return self._eval_loss_fn(
            net.params_, net.state_, batch.features, batch.labels,
            fmask, lmask)

    def _dispatch(self, step_fn, *args):
        """The jitted step's call and nothing else: the ``step.dispatch``
        span, and the time ``tpudl_train_dispatch_seconds`` takes.  It
        returns when the program is enqueued, not when the device is done."""
        t0 = time.perf_counter()
        with tracing.span("step.dispatch"):
            out = step_fn(*args)
        self._dispatch_s += time.perf_counter() - t0
        return out

    @contextlib.contextmanager
    def _reading(self, **attributes):
        """Around listeners, where the loop may block on a device value:
        the ``step.read`` span, and the time ``tpudl_train_read_seconds``
        takes."""
        t0 = time.perf_counter()
        try:
            with tracing.span("step.read", **attributes):
                yield
        finally:
            self._read_s += time.perf_counter() - t0

    def fit_batch(self, batch, rng, prepared: bool = False) -> float:
        """One optimization step on one batch; returns host-side loss.
        ``prepared=True`` marks a batch the DeviceFeeder already staged
        (sharded + device-resident) — no further host work happens."""
        self._ensure_ready()
        if not prepared:
            batch = self._place_batch(batch)
        net = self.net
        fmask, lmask = _batch_masks(batch)
        if self._layout is not None and self._layout.pipe > 1 \
                and fmask is not None:
            raise ValueError(
                "pipe-axis layouts do not support features_mask "
                "(per-timestep masking) — use a data/model layout; "
                "labels_mask (bucket padding) rides the packed labels")
        sampling = [l for l in self._stats_listeners
                    if l.wants_stats_now(net.iteration)]
        args = (net.params_, net.state_, net.opt_state,
                batch.features, batch.labels, fmask, lmask, rng)
        # roofline cost model: capture the call's abstract signature
        # BEFORE the donating step invalidates the input buffers; the
        # analysis itself (a duplicate XLA compile) runs on the
        # costmodel's background worker, never on the step path.  The
        # batch-shape sig keeps bucketed tails from inheriting the main
        # bucket's FLOPs.
        sig = (costmodel.shape_sig((batch.features, batch.labels,
                                    fmask, lmask))
               if costmodel.enabled() else None)
        if self._bake_args is None and get_config().artifact_store:
            # what a bake will AOT-lower (abstract only — holding real
            # buffers here would block donation).  Captured whenever
            # the store is enabled, not just under artifact_bake, so an
            # explicit bake_artifacts() call after fit always works;
            # one tree_map on the first step, then the None check
            # short-circuits.
            self._bake_args = costmodel.abstractify(args)
        analyze_args = (
            costmodel.abstractify(args)
            if not sampling and costmodel.should_analyze(self._step, sig=sig)
            else None)
        if sampling:
            if self._stats_step is None:
                self._stats_step = step_cache.get_or_build(
                    self._step_key("train_stats"),
                    lambda: make_train_step(
                        net, self.tx, with_stats=True,
                        opt_state_shardings=self._opt_state_shardings))
            params, state, opt_state, loss, stats = self._dispatch(
                self._stats_step, *args)
            # publish the fresh (non-donated) buffers BEFORE listeners run —
            # net.params_ still references donated inputs at this point
            net.params_, net.state_, net.opt_state = params, state, opt_state
            with self._reading(stats=True):
                for listener in sampling:
                    listener.stats_ready(net, net.iteration, net.epoch,
                                         float(loss), stats)
        else:
            params, state, opt_state, loss = self._dispatch(self._step, *args)
        net.params_, net.state_, net.opt_state = params, state, opt_state
        self._last_step_fn = self._stats_step if sampling else self._step
        self._last_step_calls = 1
        self._last_step_sig = sig
        if analyze_args is not None:
            costmodel.schedule_analysis(
                self._step, analyze_args,
                kind=(costmodel.program_kind(self._step)
                      or f"train:{type(net).__name__}"), sig=sig)
        cfg = get_config()
        if cfg.nan_panic or cfg.inf_panic:
            check_finite(params, "params after step")
        # Return the DEVICE scalar — callers/listeners convert when they
        # actually read it, so back-to-back steps pipeline without a
        # host↔device sync per iteration (the reference syncs per op;
        # syncing per *step* would still serialize dispatch on TPU).
        return loss

    def _fit_tbptt(self, batch, rng, prepared: bool = False):
        """Truncated BPTT over one batch of full sequences: forward state
        carries between segments (gradient-truncated); dropout rng is
        folded per segment so masks differ across segments.

        Recompile guard: a non-divisible T gets an all-ones
        features_mask up front (so every segment shares one pytree
        structure) and the short tail segment is padded to the static
        ``tbptt_fwd_length`` with a masked tail — one segment shape,
        one compile, carries and loss untouched (masked steps are
        carry-through in the recurrent scan)."""
        from deeplearning4j_tpu.nn.layers.recurrent import BaseRecurrentLayer
        if self._layout is not None and self._layout.pipe > 1:
            raise NotImplementedError(
                "tBPTT is not supported on pipe-axis layouts (recurrent "
                "carries cannot ride the 1F1B ring); use a data/model "
                "layout")
        self._ensure_ready()
        net = self.net
        if self._tbptt_step is None:
            self._tbptt_step = step_cache.get_or_build(
                self._step_key("tbptt"),
                lambda: make_tbptt_step(
                    net, self.tx,
                    opt_state_shardings=self._opt_state_shardings))
        length = net.conf.tbptt_fwd_length
        if batch.features.shape[1] % length:
            batch = ensure_feature_mask(batch)
        if not prepared:
            batch = self._place_batch(batch)
        b = batch.features.shape[0]
        dtype = batch.features.dtype
        carries = [layer.init_carry(b, dtype)
                   if isinstance(layer, BaseRecurrentLayer) else None
                   for layer in net.layers]
        loss = None
        analyze_args = None
        sig = None
        n_segments = 0
        for seg_idx, seg in enumerate(_tbptt_segments(batch, length)):
            seg_rng = jax.random.fold_in(rng, seg_idx)
            if seg_idx == 0 and self._tbptt_bake_args is None \
                    and get_config().artifact_store:
                self._tbptt_bake_args = costmodel.abstractify(
                    (net.params_, net.state_, net.opt_state, carries,
                     seg.features, seg.labels, seg.features_mask,
                     seg.labels_mask, seg_rng))
            if seg_idx == 0 and costmodel.enabled():
                # one shared segment shape by construction (masked tail
                # padding), so the first segment's sig covers them all
                sig = costmodel.shape_sig(
                    (seg.features, seg.labels, seg.features_mask,
                     seg.labels_mask))
                if costmodel.should_analyze(self._tbptt_step, sig=sig):
                    analyze_args = costmodel.abstractify(
                        (net.params_, net.state_, net.opt_state, carries,
                         seg.features, seg.labels, seg.features_mask,
                         seg.labels_mask, seg_rng))
            params, state, opt_state, carries, loss = self._dispatch(
                self._tbptt_step,
                net.params_, net.state_, net.opt_state, carries,
                seg.features, seg.labels, seg.features_mask,
                seg.labels_mask, seg_rng)
            net.params_, net.state_, net.opt_state = params, state, opt_state
            n_segments += 1
        self._last_step_fn = self._tbptt_step
        self._last_step_calls = n_segments
        self._last_step_sig = sig
        if analyze_args is not None:
            costmodel.schedule_analysis(
                self._tbptt_step, analyze_args,
                kind=(costmodel.program_kind(self._tbptt_step)
                      or f"tbptt:{type(net).__name__}"), sig=sig)
        cfg = get_config()
        if cfg.nan_panic or cfg.inf_panic:
            check_finite(net.params_, "params after tBPTT step")
        return loss

    def _loop_metrics(self):
        """The loop's counter and histogram handles, looked up once per
        registry (tests swap it), not by name every step."""
        reg = get_registry()
        if self._step_metrics is None or self._step_metrics[0] is not reg:
            self._step_metrics = (reg, train_loop_metrics(reg))
        return self._step_metrics[1]

    def _fold_step_counters(self, loss) -> None:
        """Add the step's device-side counters (``_step_counters``) to the
        registry, but only where it costs no wait: on a step whose loss a
        listener has converted, the program that wrote them is done.  A
        step nobody read is skipped, so the totals are of sampled steps."""
        ready = getattr(loss, "is_ready", None)
        if ready is None or not ready():
            return
        state = self.net.state_
        read = jax.device_get({name: {key: state[name][key] for key in keys}
                               for name, keys in self._step_counters.items()})
        totals: dict = {}
        for name, keys in self._step_counters.items():
            for key, metric in keys.items():
                totals[metric] = totals.get(metric, 0.0) + float(
                    read[name][key])
        registry = get_registry()
        for metric, value in totals.items():
            registry.counter(metric).inc(value)

    def step_batch(self, batch, rng):
        """One training iteration with full semantics: tBPTT routing,
        score tracking, listener dispatch, iteration counter.  Used by
        ``fit`` and by external epoch drivers (EarlyStoppingTrainer).

        Observability: a ``step`` span over the whole iteration with two
        children, ``step.dispatch`` (the jitted call, :meth:`_dispatch`)
        and ``step.read`` (the listeners, where the loop may block on a
        device value), and a histogram at each of the three boundaries
        (``tpudl_train_iteration_seconds``, ``_dispatch_``, ``_read_``).
        Nothing here syncs the device, tracing on or off: all of it is
        host time, and the device's time per step comes from a profiler
        trace (``obs.profiler.timeline``)."""
        net = self.net
        metrics = self._loop_metrics()
        self._dispatch_s = self._read_s = 0.0
        # the step clock starts BEFORE the fault site: an injected delay
        # models a slow step, so it must show in the reported step time
        # (the federated straggler check judges exactly that number)
        t0 = time.perf_counter()
        with tracing.span("step", iteration=net.iteration,
                          epoch=net.epoch) as sp:
            # fault-injection site: a "crash" here models preemption
            # BEFORE the step commits — the last durable checkpoint stays
            # authoritative
            faults.fire("trainer.step", index=net.iteration)
            flight_recorder.progress("trainer.step")
            fed = isinstance(batch, FedBatch)
            data = batch.batch if fed else batch
            first = (data.features[0]
                     if isinstance(data.features, (list, tuple))
                     else data.features)
            # listeners and the examples counter must see the REAL example
            # count, not the bucket-padded shape
            n_examples = batch.n_examples if fed else int(first.shape[0])
            if not self._compiled:
                sp.set_attribute("compile", True)
            traces_before = step_cache.jit_cache_entries(
                *self._jit_step_fns())
            if net.conf.backprop_type == "tbptt" \
                    and not isinstance(data.features, (list, tuple)) \
                    and first.ndim == 3:
                loss = self._fit_tbptt(data, rng, prepared=fed)
            else:
                loss = self.fit_batch(data, rng, prepared=fed)
            # fault site to the dispatch's return: what the straggler
            # check, the flight recorder and the cost model are handed
            dt = time.perf_counter() - t0
            self._compiled = True
            # recompile guard measurement: new traced programs across this
            # step (first compile counts too; a shared step-cache hit does
            # not — the program already existed)
            retraced = (step_cache.jit_cache_entries(*self._jit_step_fns())
                        - traces_before)
            if retraced > 0:
                metrics.recompiles.inc(retraced)
            else:
                # steady-state step: self-report MFU / HBM utilization
                # against the program's cost_analysis facts (compile steps
                # would lie — their wall time is dominated by XLA, not
                # execution)
                costmodel.observe_step(self._last_step_fn, dt,
                                       calls=self._last_step_calls,
                                       sig=getattr(self, "_last_step_sig",
                                                   None))
            metrics.steps.inc()
            metrics.examples.inc(n_examples)
            if retraced == 0 and not self._bake_scheduled \
                    and get_config().artifact_bake \
                    and (self._bake_args is not None
                         or self._tbptt_bake_args is not None):
                # compiles have settled: bake this trainer's programs ONCE
                # on the background worker, so every checkpoint written
                # from here on carries warm-restart artifacts
                self._bake_scheduled = True
                from deeplearning4j_tpu.train import artifact_store
                artifact_store.schedule_bake(self.bake_artifacts)
            flight_recorder.record("step", iteration=net.iteration,
                                   epoch=net.epoch,
                                   duration_ms=round(dt * 1e3, 3),
                                   examples=n_examples,
                                   compile=bool(retraced))
            flight_recorder.progress("trainer.step")
            # fault site: a "nan" rule poisons the reported loss (numeric-
            # blowup stand-in) so health-monitor detection runs end-to-end
            if faults.poison("trainer.step", index=net.iteration):
                loss = float("nan")
            # cluster federation: stamp this worker's progress onto the
            # coordinator's dashboard (buffer-append only — the router's
            # background thread does the network I/O; see obs/remote.py)
            obs_remote.notify_step(net.iteration, epoch=net.epoch,
                                   duration_s=dt, score=loss,
                                   examples=n_examples,
                                   compile=bool(retraced))
            net._score = loss
            with self._reading():
                for listener in self.bus.listeners:
                    if hasattr(listener, "record_batch"):
                        listener.record_batch(n_examples)
                self.bus.dispatch("iteration_done", net, net.iteration,
                                  net.epoch, loss)
                if self._step_counters:
                    self._fold_step_counters(loss)
            net.iteration += 1
        if retraced == 0:
            # the three together, so that their sums subtract: iteration
            # less dispatch less read is the loop's own python
            metrics.iteration.observe(time.perf_counter() - t0)
            metrics.dispatch.observe(self._dispatch_s)
            metrics.read.observe(self._read_s)
        return loss

    def bake_artifacts(self) -> int:
        """AOT-compile and serialize this trainer's programs (train or
        tbptt step + eval loss) into an artifact stash on the net, so
        every subsequent checkpoint zip embeds them and a restarted
        process resumes with zero JIT (train/artifact_store).  Needs at
        least one completed step (the abstract call signature is
        captured there); uncacheable configs (per-layer updaters,
        frozen layers) bake nothing, exactly like the step cache.
        Returns the number of programs baked.  Runs on the background
        bake worker when ``config.artifact_bake`` is set; callable
        directly (e.g. right before a deploy-time save)."""
        from deeplearning4j_tpu.train import artifact_store
        if self._cache_sig is None:
            return 0
        jobs = []
        if self._tbptt_bake_args is not None and self._tbptt_step is not None:
            jobs.append((self._tbptt_step, self._tbptt_bake_args,
                         self._step_key("tbptt"), "tbptt"))
        if self._bake_args is not None:
            if self._step is not None:
                jobs.append((self._step, self._bake_args,
                             self._step_key("train"), "train"))
            # eval loss shares the train step's (params, state, batch)
            # signature minus opt_state and rng
            a = self._bake_args
            eval_args = (a[0], a[1], a[3], a[4], a[5], a[6])
            if self._eval_loss_fn is None:
                self._eval_loss_fn = step_cache.get_or_build(
                    self._step_key("eval"),
                    lambda: make_eval_step(self.net))
            jobs.append((self._eval_loss_fn, eval_args,
                         self._step_key("eval"), "eval"))
        entries: dict = {}
        index: list = []
        for fn, abstract_args, key, kind in jobs:
            if key is None or fn is None:
                continue
            inner = getattr(fn, "_fn", fn)   # unwrap WarmedJit
            try:
                e, ix = artifact_store.bake_program(
                    inner, abstract_args, key, kind)
            except Exception:
                # baking is an optimization; a program that refuses AOT
                # serialization must not fail training or checkpoints
                flight_recorder.record("artifact_bake_failed",
                                       program=kind)
                continue
            entries.update(e)
            index.append(ix)
        artifact_store.stash_on_net(self.net, entries, index)
        return len(index)

    def resume_state(self, source, iterator=None) -> dict:
        """Restore full training state from ``source`` (a checkpoint zip
        or a directory of them) into this trainer's net: params, updater
        state, RNG key, completed iteration/epoch counters, dtype policy
        — and fast-forward ``iterator`` past already-consumed batches
        when the checkpoint was taken mid-epoch.  Returns the restored
        training-state dict (see docs/fault_tolerance.md)."""
        from deeplearning4j_tpu.config import set_dtype_policy, DTypePolicy
        from deeplearning4j_tpu.io.checkpoint import CheckpointListener
        from deeplearning4j_tpu.io.model_serializer import (
            read_iterator_state, restore_into)
        path = source
        verified = False
        if os.path.isdir(source):
            # discovery verifies each candidate (newest intact wins) —
            # don't re-hash the multi-GB zip a second time below
            path = CheckpointListener.last_checkpoint_in(source)
            verified = True
            if path is None:
                raise FileNotFoundError(
                    f"no intact checkpoint found under {source}")
        elif not os.path.exists(source):
            raise FileNotFoundError(
                f"resume_from path does not exist: {source}")
        self._ensure_ready()
        state = restore_into(self.net, path, tx=self.tx,
                             verify=not verified)
        # a gang child respawned as part of a GROW resize announces the
        # reshard here — the instrumentation point where an injected
        # kill proves a torn mid-grow death leaves the checkpoint intact
        # and recovers through the normal supervisor respawn path
        from deeplearning4j_tpu.resilience import elastic as _elastic
        if os.environ.get(_elastic.GROWN_ENV):
            faults.fire("gang.grow")
        # warm the compiled-artifact pool — a respawned process
        # (supervisor, online loop) then takes its first step with zero
        # JIT instead of recompiling the world.  Strictly AFTER the
        # verified restore above: a corrupt zip must be refused whole
        # before any of its artifacts can enter the first-wins pool
        # (the warmed wrappers re-check the pool per call, so warming
        # after the step was built loses nothing).
        from deeplearning4j_tpu.train import artifact_store
        if artifact_store.enabled():
            artifact_store.warm_from_zip(path)
        policy = state.get("dtype_policy")
        if policy:
            # the compiled step must see the dtypes the run was using
            set_dtype_policy(DTypePolicy(
                param_dtype=jnp.dtype(policy["param_dtype"]),
                compute_dtype=jnp.dtype(policy["compute_dtype"]),
                output_dtype=jnp.dtype(policy["output_dtype"])))
        skip = int(state.get("epoch_batches", 0) or 0)
        if skip:
            if iterator is None or not hasattr(iterator, "set_state"):
                raise ValueError(
                    f"checkpoint {path} was taken mid-epoch "
                    f"({skip} batches in) — resuming exactly needs a "
                    f"ResumableIterator (data.iterators) to fast-forward")
            # iteratorState.json carries whatever extra fields the
            # iterator saved (shuffle RNG, shard offset, ...); the
            # position itself comes from the TRAINER's counters — the
            # feeder prefetches ahead, so the iterator's own count lies
            it_state = read_iterator_state(path) or {}
            it_state.update({"epoch": self.net.epoch, "batch_index": skip})
            iterator.set_state(it_state)
        state["checkpoint_path"] = path
        # surface the resume point: the supervisor computes steps
        # replayed per incident as (last pre-crash iteration − this),
        # and the coordinator's /cluster dashboard annotates the restart
        resumed_iter = int(state.get("iteration", 0) or 0)
        reg = get_registry()
        reg.counter("tpudl_resilience_resumes_total").inc()
        reg.gauge("tpudl_resilience_resumed_iteration").set(resumed_iter)
        flight_recorder.record("resume", iteration=resumed_iter,
                               epoch=int(state.get("epoch", 0) or 0),
                               checkpoint=os.path.basename(path))
        obs_remote.notify_event("resume", iteration=resumed_iter,
                                epoch=int(state.get("epoch", 0) or 0),
                                checkpoint=os.path.basename(path))
        return state

    def fit(self, iterator, epochs: int = 1, resume_from=None):
        """Train ``epochs`` epochs.  With ``resume_from`` (a checkpoint
        zip or directory), training state is restored first and
        ``epochs`` counts the TOTAL run — completed epochs are skipped
        and a mid-epoch checkpoint fast-forwards the iterator, so an
        interrupted fit resumed here reproduces the uninterrupted run's
        per-step losses exactly (tests/test_resilience.py pins 1e-6)."""
        net = self.net
        epochs_to_run = epochs
        if resume_from is None:
            # the supervisor's respawn contract: a verified checkpoint
            # pointer rides DL4J_TPU_RESUME_FROM into every respawned
            # gang child — consuming it here makes resume automatic for
            # any worker fn that calls fit, instead of each one
            # re-implementing the env read
            from deeplearning4j_tpu.resilience.supervisor import RESUME_ENV
            resume_from = os.environ.get(RESUME_ENV) or None
        if resume_from is not None:
            # resume first: it verifies + restores state, then warms
            # the artifact pool, so the first step below dispatches the
            # checkpoint's deserialized program instead of compiling
            self.resume_state(resume_from, iterator)
            epochs_to_run = max(0, epochs - net.epoch)
        self._ensure_ready()
        # the post-split key stamped by the previous step/restore; a
        # fresh net derives from its seed (bitwise-deterministic runs)
        key = getattr(net, "_rng_key", None)
        if key is None:
            key = jax.random.key(net.conf.seed + 7919)
        attrs = (net.trace_attrs() if hasattr(net, "trace_attrs") else
                 {"model": type(net).__name__})
        cfg = get_config()
        # the device-feed stage: bucket-pad + shard + device_put batch
        # N+1 on a background thread while step N executes; one feeder
        # for the whole fit so the bucket set stays sticky across epochs
        feeder = DeviceFeeder(self._place_batch) if cfg.device_feed else None
        if cfg.profiling:
            from deeplearning4j_tpu.obs.profiler import trace as profiler_trace
            profile_ctx = profiler_trace(cfg.trace_dir)
        else:
            profile_ctx = contextlib.nullcontext()
        with profile_ctx:
            with tracing.span("fit", epochs=epochs, **attrs):
                self.bus.dispatch("on_fit_start", net)
                for _ in range(epochs_to_run):
                    if self._pending_resize is not None:
                        # elastic round boundary: the feeder restarts
                        # below, so nothing sharded for the old width
                        # survives into the resized epoch
                        self.resize_mesh(self._pending_resize)
                    with tracing.span("epoch", epoch=net.epoch):
                        self.bus.dispatch("on_epoch_start", net, net.epoch)
                        epoch_t0 = time.perf_counter()
                        n_batches = 0
                        # resume bookkeeping: what a checkpoint taken NOW
                        # should record (counters are post-step values,
                        # stamped before each step so a mid-step crash
                        # leaves the previous step's stamp in place)
                        net._completed_epochs = net.epoch
                        if hasattr(iterator, "reset"):
                            iterator.reset()
                        source = (feeder.feed(iterator) if feeder is not None
                                  else iterator)
                        for batch in source:
                            key, sub = jax.random.split(key)
                            net._rng_key = key
                            net._completed_iterations = net.iteration + 1
                            net._epoch_batches = n_batches + 1
                            self.step_batch(batch, sub)
                            n_batches += 1
                        # epoch complete: a checkpoint here resumes at
                        # the NEXT epoch's first batch
                        net._completed_epochs = net.epoch + 1
                        net._epoch_batches = 0
                        epoch_s = time.perf_counter() - epoch_t0
                        # the epoch wall time rides the registry (where
                        # SLO/trend evaluation can see it), not only the
                        # listener-bus info dict
                        get_registry().histogram(
                            "tpudl_train_epoch_seconds").observe(epoch_s)
                        # the HBM gauges, once an epoch: memory_stats()
                        # asks the allocator, not the device
                        record_device_memory()
                        info = {"epoch_time_s": epoch_s,
                                "batches": n_batches, "score": net._score}
                        self.bus.dispatch("on_epoch_end", net, net.epoch, info)
                    get_registry().counter("tpudl_train_epochs_total").inc()
                    net.epoch += 1
                self.bus.dispatch("on_fit_end", net, {"epochs": epochs})
        # a COMPLETED fit restores pre-resilience RNG semantics: the next
        # fit() derives from the seed again (repeated-fit reproducibility
        # baselines hold).  A crash skips this line, so mid-run restarts
        # — and every checkpoint written along the way — keep the
        # continuation key that makes resume exact.
        net._rng_key = None
        return net


def _tbptt_segments(batch, length: int, pad_tail: bool = True):
    """Truncated-BPTT segmentation (``MultiLayerConfiguration.tBPTTLength``):
    split [B, T, C] sequences into chunks of ``length`` steps.  Forward
    state is carried across chunks by ``Trainer._fit_tbptt`` (gradients
    truncate at chunk boundaries, DL4J semantics).

    ``pad_tail`` (default): a final chunk shorter than ``length`` is
    zero-padded to the static segment shape with a masked tail — one
    segment shape per config means ONE compiled tBPTT step instead of a
    second trace+compile every epoch (the caller synthesizes a
    features_mask for non-divisible T so segment pytrees stay uniform)."""
    t = batch.features.shape[1]
    for start in range(0, t, length):
        end = min(start + length, t)
        seg = dataclasses.replace(
            batch,
            features=batch.features[:, start:end],
            labels=batch.labels[:, start:end] if batch.labels is not None and batch.labels.ndim == 3 else batch.labels,
            features_mask=None if batch.features_mask is None else batch.features_mask[:, start:end],
            labels_mask=None if batch.labels_mask is None else (
                batch.labels_mask[:, start:end] if batch.labels_mask.ndim >= 2 else batch.labels_mask),
        )
        if pad_tail and end - start < length:
            seg = pad_segment(seg, length)
        yield seg
