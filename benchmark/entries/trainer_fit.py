"""Entry ``trainer_fit``: a zoo network trained through ``net.fit``
(``ComputationGraph.fit`` -> ``Trainer.fit`` -> ``DeviceFeeder`` -> the
donating jitted step).  Used by the ``resnet50_unfused`` configuration.

The adapter between the benchmark's names and the program's: the
reference's flat leaves (``res2_0.a.w``, HWIO) go into the graph's
nested parameter dict (``res2_0_a_conv``/``W``, ``res2_0_a_bn``/``gamma``),
and what the comparison reads comes back under the reference's names.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import probe


def _where(name: str) -> tuple:
    """Reference leaf name -> (vertex, parameter) of the graph."""
    parts = name.split(".")
    if parts[0] == "fc":
        return "out", {"w": "W", "b": "b"}[parts[1]]
    vertex = "_".join(parts[:-1])
    return ((f"{vertex}_conv", "W") if parts[-1] == "w"
            else (f"{vertex}_bn", parts[-1]))


_POLICIES = {("float32", "bfloat16"): "bf16", ("float32", "float32"): "f32"}


class Entry:
    def __init__(self, config: dict, mix: dict):
        self.config, self.mix = config, mix
        self.net = None
        self.weights = None
        self.cadence = probe.Cadence(mix["loss_every"])

    # ---- set-up -------------------------------------------------------------
    def build(self, weights: dict, seed: int) -> None:
        from deeplearning4j_tpu.config import DTypePolicy, set_dtype_policy
        from deeplearning4j_tpu.models import resnet50
        from deeplearning4j_tpu.train import Nesterovs
        precision, opt = self.config["precision"], self.config["optimizer"]
        policy = _POLICIES.get((precision["params"], precision["compute"]))
        if policy is None:
            raise ValueError(f"trainer_fit knows the policies "
                             f"{sorted(_POLICIES)}, the configuration "
                             f"states {precision}")
        set_dtype_policy(getattr(DTypePolicy, policy)())
        model = self.config["model"]
        net = resnet50(height=model["image"], width=model["image"],
                       channels=model["channels"],
                       num_classes=model["classes"], seed=seed,
                       updater=Nesterovs(opt["learning_rate"],
                                         opt["momentum"]))
        l2 = {layer.l2 for layer in net.layers}
        if l2 != {opt["l2"]}:
            raise ValueError(f"the zoo's l2 {l2} is not the "
                             f"configuration's {opt['l2']}")

        def shapes():                      # net.init traced, never run
            net.init()
            return net.params_, net.state_
        param_shapes, state_shapes = jax.eval_shape(shapes)
        self._names = {name: _where(name) for name in weights}
        n_leaves = len(jax.tree_util.tree_leaves(param_shapes))
        if n_leaves != len(weights):
            raise ValueError(f"the graph has {n_leaves} parameter leaves, "
                             f"the reference {len(weights)}")

        @jax.jit
        def place(flat):
            params = jax.tree_util.tree_map(lambda s: None, param_shapes)
            for name, (vertex, leaf) in self._names.items():
                want = param_shapes[vertex][leaf]
                params[vertex][leaf] = flat[name].reshape(
                    want.shape).astype(want.dtype)
            state = {v: {k: (jnp.ones if k.startswith("var") else jnp.zeros)(
                s.shape, s.dtype) for k, s in leaves.items()}
                for v, leaves in state_shapes.items()}
            return params, state

        net.params_, net.state_ = place(weights)
        self.net, self.weights = net, weights
        self.reader = probe.FlatReader(self._flatten, weights)

    def _flatten(self, tree) -> dict:
        return {name: tree[vertex][leaf].reshape(self.weights[name].shape)
                for name, (vertex, leaf) in self._names.items()}

    def to_batch(self, arrays: dict):
        from deeplearning4j_tpu.data.dataset import DataSet
        return DataSet(arrays["features"], arrays["labels"])

    def first_steps(self, batches: list) -> dict:
        """The warm-up IS the first steps: the same ``net.fit`` and feeder
        the window uses, over batches that all differ."""
        seen = probe.FirstSteps(self.reader, len(batches),
                                lambda net: net.params_)
        self.net.fit(iter(batches), listeners=[seen])
        return seen.readings(self.config["optimizer"])

    # ---- the window ---------------------------------------------------------
    def run(self, iterator) -> None:
        self.net.fit(iterator, listeners=[self.cadence])

    def wait(self) -> None:
        jax.block_until_ready(self.net.params_)

    def steps(self) -> int:
        return self.cadence.steps

    def window_losses(self) -> list:
        return list(self.cadence.losses)

    def recompiles(self) -> float:
        from deeplearning4j_tpu.obs.registry import get_registry
        return get_registry().counter("tpudl_train_recompiles_total").value

    # ---- after the window ---------------------------------------------------
    def lowered_step(self, batch):
        """The trainer's own step, lowered for ``batch``: compiling it is
        a cache hit once the step has run."""
        from deeplearning4j_tpu.data.device_pipeline import pad_to_bucket
        from deeplearning4j_tpu.obs import costmodel
        from deeplearning4j_tpu.train.trainer import Trainer
        trainer = Trainer(self.net)
        trainer._ensure_ready()
        # the feeder's bucketing attaches an all-ones labels mask
        placed = trainer._place_batch(
            pad_to_bucket(batch, batch.num_examples())[0])
        args = costmodel.abstractify(
            (self.net.params_, self.net.state_, self.net.opt_state,
             placed.features, placed.labels, None, placed.labels_mask,
             jax.random.key(0)))
        return trainer._step.lower(*args)

    def free(self) -> None:
        net, self.net = self.net, None
        if net is not None:
            for tree in (net.params_, net.state_, net.opt_state):
                for leaf in jax.tree_util.tree_leaves(tree):
                    if hasattr(leaf, "delete"):
                        leaf.delete()
            net.params_ = net.state_ = net.opt_state = None


def make(config: dict, mix: dict) -> Entry:
    return Entry(config, mix)
