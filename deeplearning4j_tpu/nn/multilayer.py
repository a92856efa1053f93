"""MultiLayerNetwork — the linear-stack network.

Parity with DL4J ``org/deeplearning4j/nn/multilayer/MultiLayerNetwork.java``:
init / feed-forward / fit / output / score / evaluate / params /
save-load, plus ``rnnTimeStep`` streaming state.  Differences by design:

- forward/backward are ONE jit-compiled XLA program per (shape, mode) —
  no per-op JNI dispatch (reference stack 3.1 in SURVEY.md collapses into
  a single fused computation).
- parameters are a pytree (list of per-layer dicts) living in device HBM;
  the flat contiguous vector of the reference is available as a *view*
  via ``params()`` (utils.pytree) for serde/codec parity.
- the updater is optax; updater state is a pytree checkpointed alongside
  params (``updaterState.bin`` parity).
"""

from __future__ import annotations

import functools
from typing import Any, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration
from deeplearning4j_tpu.nn import preprocessors
from deeplearning4j_tpu.utils.pytree import flat_param_vector, param_count


class MultiLayerNetwork:
    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layers = conf.layers
        self.params_: Optional[list] = None     # list of per-layer param dicts
        self.state_: Optional[list] = None      # list of per-layer state dicts
        self.opt_state = None
        self.iteration = 0
        self.epoch = 0
        self._score = float("nan")
        self._rnn_carries: Optional[list] = None  # rnnTimeStep streaming state
        self._output_fn = None

    # ------------------------------------------------------------- init
    def init(self, seed: Optional[int] = None) -> "MultiLayerNetwork":
        seed = self.conf.seed if seed is None else seed
        key = jax.random.key(seed)
        types = self.conf.input_types()
        self.params_, self.state_ = [], []
        for layer, itype in zip(self.layers, types):
            key, sub = jax.random.split(key)
            self.params_.append(layer.init_params(sub, itype) if layer.has_params() else {})
            self.state_.append(layer.init_state(itype))
        return self

    def num_params(self) -> int:
        return param_count(self.params_)

    def params(self) -> jnp.ndarray:
        """Flat contiguous parameter vector (``MultiLayerNetwork.params()``)."""
        return flat_param_vector(self.params_)

    def set_params(self, params: list) -> None:
        self.params_ = params

    # ---------------------------------------------------------- forward
    def _forward(self, params, state, x, *, train: bool, rng=None, mask=None,
                 labels=None):
        """Full forward pass.  Returns (output, new_state, score_array|None).

        The per-layer loop is a PYTHON loop over statically-known layers —
        it unrolls at trace time into one fused XLA program.
        """
        out, new_state, score_array, _ = self._forward_impl(
            params, state, x, None, train=train, rng=rng, mask=mask,
            labels=labels)
        return out, new_state, score_array

    def _forward_impl(self, params, state, x, carries, *, train: bool,
                      rng=None, mask=None, labels=None):
        """Forward with optional recurrent-carry threading.  ``carries`` is a
        per-layer list (None entries for non-recurrent layers); when given,
        recurrent layers start from ``stop_gradient(carry)`` — forward state
        flows, gradients truncate at the segment boundary (DL4J tBPTT)."""
        from deeplearning4j_tpu.nn.layers.recurrent import BaseRecurrentLayer
        types = self.conf.input_types()
        new_state = []
        new_carries = [None] * len(self.layers)
        current_mask = mask
        score_array = None
        for i, (layer, itype) in enumerate(zip(self.layers, types)):
            # the layer's name on every device operation it lowers to: what
            # obs.profiler.timeline sums device time by
            with jax.named_scope(layer.name
                                 or f"layer{i}_{type(layer).__name__}"):
                x = preprocessors.adapt_array(x, itype_before(self, i, types), layer)
                layer_rng = jax.random.fold_in(rng, i) if rng is not None else None
                is_last = i == len(self.layers) - 1
                if is_last and labels is not None and hasattr(layer, "compute_score_array"):
                    # same noised weights as apply(): IWeightNoise applies to
                    # the loss path too (DL4J BaseLayer.getParamWithNoise)
                    score_array = layer.compute_score_array(
                        layer.noised_params(params[i], train, layer_rng),
                        state[i], x, labels, train=train, rng=layer_rng,
                        mask=current_mask)
                if carries is not None and isinstance(layer, BaseRecurrentLayer):
                    carry = carries[i]
                    if carry is not None:
                        carry = jax.lax.stop_gradient(carry)
                    y, s, new_carries[i] = layer.apply_with_carry(
                        layer.noised_params(params[i], train, layer_rng),
                        state[i], x, carry, train=train, rng=layer_rng,
                        mask=current_mask)
                else:
                    y, s = layer.apply(
                        layer.noised_params(params[i], train, layer_rng),
                        state[i], x, train=train,
                        rng=layer_rng, mask=current_mask)
            new_state.append(s)
            x = y
            # time-geometry layers reshape the [B,T] mask alongside the data
            # (DL4J Layer.feedForwardMaskArray parity)
            current_mask = layer.transform_mask(current_mask)
        return x, new_state, score_array, new_carries

    def output(self, x, mask=None) -> jnp.ndarray:
        """Inference forward (``MultiLayerNetwork.output``); jit-cached."""
        if self._output_fn is None:
            @jax.jit
            def _out(params, state, x, mask):
                y, _, _ = self._forward(params, state, x, train=False, mask=mask)
                return y
            self._output_fn = _out
        return self._output_fn(self.params_, self.state_, jnp.asarray(x), mask)

    def feed_forward(self, x, train: bool = False):
        """Returns the list of all layer activations (``feedForward``)."""
        types = self.conf.input_types()
        acts = []
        for i, (layer, itype) in enumerate(zip(self.layers, types)):
            x = preprocessors.adapt_array(x, itype_before(self, i, types), layer)
            x, _ = layer.apply(self.params_[i], self.state_[i], x, train=train)
            acts.append(x)
        return acts

    # ---------------------------------------------------------- training
    def score(self) -> float:
        """Loss of the most recent fit minibatch (``score()``); syncs the
        device scalar on read."""
        return float(self._score)

    def fit(self, iterator, epochs: int = 1, listeners=None,
            resume_from=None):
        from deeplearning4j_tpu.train.trainer import Trainer
        Trainer(self, listeners=listeners).fit(iterator, epochs,
                                               resume_from=resume_from)
        return self

    def trace_attrs(self) -> dict:
        """Model identity attached to the trainer's ``fit`` span
        (``obs.tracing``) — what a trace viewer shows for this run."""
        return {"model": "MultiLayerNetwork",
                "layers": len(self.layers),
                "params": self.num_params() if self.params_ is not None else 0}

    def evaluate(self, iterator, top_n: int = 1):
        from deeplearning4j_tpu.evaluation.classification import Evaluation
        evaluation = Evaluation(top_n=top_n)
        for batch in iterator:
            features, labels = batch.features, batch.labels
            out = self.output(features, mask=batch.features_mask)
            evaluation.eval(labels, np.asarray(out), mask=batch.labels_mask)
        return evaluation

    def evaluate_regression(self, iterator):
        from deeplearning4j_tpu.evaluation.regression import RegressionEvaluation
        evaluation = RegressionEvaluation()
        for batch in iterator:
            out = self.output(batch.features, mask=batch.features_mask)
            evaluation.eval(batch.labels, np.asarray(out), mask=batch.labels_mask)
        return evaluation

    def evaluate_roc(self, iterator, threshold_steps: int = 0):
        from deeplearning4j_tpu.evaluation.roc import ROC, ROCMultiClass
        n_out = self.conf.output_type().flat_size()
        roc = ROC(threshold_steps) if n_out <= 2 else ROCMultiClass(threshold_steps)
        for batch in iterator:
            out = self.output(batch.features, mask=batch.features_mask)
            roc.eval(batch.labels, np.asarray(out), mask=batch.labels_mask)
        return roc

    # ---------------------------------------------------------- rnn API
    def rnn_clear_previous_state(self):
        self._rnn_carries = None

    def rnn_time_step(self, x) -> jnp.ndarray:
        """Streaming inference with stored state
        (``MultiLayerNetwork.rnnTimeStep``): feed [B, T, C] (or [B, C] for a
        single step); hidden state carries across calls."""
        from deeplearning4j_tpu.nn.layers.recurrent import BaseRecurrentLayer
        x = jnp.asarray(x)
        single = x.ndim == 2
        if single:
            x = x[:, None, :]
        if self._rnn_carries is None:
            self._rnn_carries = [None] * len(self.layers)
        types = self.conf.input_types()
        for i, layer in enumerate(self.layers):
            x = preprocessors.adapt_array(x, itype_before(self, i, types), layer)
            if isinstance(layer, BaseRecurrentLayer):
                carry = self._rnn_carries[i]
                if carry is None:
                    carry = layer.init_carry(x.shape[0], x.dtype)
                y, carry = layer._scan(self.params_[i], x, None, carry)
                self._rnn_carries[i] = carry
                x = y
            else:
                x, _ = layer.apply(self.params_[i], self.state_[i], x, train=False)
        return x[:, -1, :] if single and x.ndim == 3 else x

    # ---------------------------------------------------------- serde
    def save(self, path: str, save_updater: bool = True,
             iterator_state: Optional[dict] = None, normalizer=None) -> None:
        from deeplearning4j_tpu.io.model_serializer import write_model
        write_model(self, path, save_updater=save_updater,
                    iterator_state=iterator_state, normalizer=normalizer)

    @staticmethod
    def load(path: str, load_updater: bool = True) -> "MultiLayerNetwork":
        from deeplearning4j_tpu.io.model_serializer import restore_multi_layer_network
        return restore_multi_layer_network(path, load_updater=load_updater)

    # ---------------------------------------------------------- misc
    def summary(self) -> str:
        types = self.conf.input_types()
        lines = [f"{'idx':<4}{'type':<24}{'out shape':<20}{'params':<10}"]
        for i, (layer, itype) in enumerate(zip(self.layers, types)):
            out = layer.get_output_type(itype)
            n = param_count(self.params_[i]) if self.params_ else 0
            lines.append(f"{i:<4}{layer.TYPE_NAME:<24}{str(out.batch_shape()):<20}{n:<10}")
        lines.append(f"Total params: {self.num_params() if self.params_ else 0}")
        return "\n".join(lines)

    def clone(self) -> "MultiLayerNetwork":
        # deep-copy device buffers: the jit train step DONATES param buffers,
        # so aliasing them here would leave the clone holding deleted arrays
        # after the source trains another step
        net = MultiLayerNetwork(MultiLayerConfiguration.from_dict(self.conf.to_dict()))
        if self.params_ is not None:
            net.params_ = jax.tree_util.tree_map(
                lambda a: jnp.array(a, copy=True), self.params_)
            net.state_ = jax.tree_util.tree_map(
                lambda a: jnp.array(a, copy=True), self.state_)
        return net


def itype_before(net: MultiLayerNetwork, i: int, types: list) -> Any:
    """InputType of the activation arriving at layer i (pre-adaptation)."""
    if i == 0:
        return net.conf.input_type
    return net.layers[i - 1].get_output_type(types[i - 1])
