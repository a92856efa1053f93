"""Entry ``decoder_lm_fit``: a zoo decoder-only language model whose
builder takes the shared arguments alone, trained through ``net.fit``
(``ComputationGraph.fit`` -> ``Trainer.fit`` -> ``DeviceFeeder`` -> the
donating jitted step), the route ``trainer_fit``, ``causal_lm_fit`` and
``hybrid_lm_fit`` take.  Used by the ``lfm2_8b_a1b`` configuration.

Beside ``hybrid_lm_fit`` only the builder's arguments differ: no chunk of
a scan and no multi-token-prediction weight.  The reference names a leaf
``<vertex>.<parameter>`` (``l2_attn.W_q``, ``embed.W``), which is the
graph's ``l2_attn``/``W_q``: a head tied to the embedding owns no leaf of
its own, and the one ``embed.W`` carries the sum of both uses'
gradients.  The batch is the one ``[B, S]`` int32 array of ids the
traffic draws, as features and as labels.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp

import harness
import probe

_POLICIES = {("float32", "bfloat16"): "bf16", ("float32", "float32"): "f32"}
_hybrid = harness.load_module("entries", "hybrid_lm_fit")


class Entry(_hybrid.Entry):
    """``hybrid_lm_fit``'s entry (``trainer_fit``'s first steps, window,
    re-lowered step and freeing; ``causal_lm_fit``'s batch) around a
    builder with the shared arguments alone."""

    def build(self, weights: dict, seed: int) -> None:
        from deeplearning4j_tpu import models
        from deeplearning4j_tpu.config import DTypePolicy, set_dtype_policy
        from deeplearning4j_tpu.train import Adam
        precision, opt = self.config["precision"], self.config["optimizer"]
        policy = _POLICIES.get((precision["params"], precision["compute"]))
        if policy is None:
            raise ValueError(f"decoder_lm_fit knows the policies "
                             f"{sorted(_POLICIES)}, the configuration "
                             f"states {precision}")
        set_dtype_policy(getattr(DTypePolicy, policy)())
        net = getattr(models, self.config["builder"])(
            self.config, int(self.mix["seq"]), seed=seed,
            updater=Adam(opt["learning_rate"], opt["beta1"], opt["beta2"],
                         opt["epsilon"]),
            init_std=self.config["init"]["std"])

        def shapes():                      # net.init traced, never run
            net.init()
            return net.params_, net.state_
        param_shapes, state_shapes = jax.eval_shape(shapes)
        self._names = {name: _hybrid._where(name) for name in weights}
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
            # the selection bias and the routing counters start at nought
            state = jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), state_shapes)
            return params, state

        net.params_, net.state_ = place(weights)
        self.net, self.weights = net, weights
        self.reader = probe.FlatReader(self._flatten, weights)


def make(config: dict, mix: dict) -> Entry:
    """Refuses at once, before a weight is drawn, a program that lacks the
    configuration's builder: the parent of the PR that brings one."""
    from deeplearning4j_tpu import models
    if not hasattr(models, config["builder"]):
        sys.exit(f"decoder_lm_fit: deeplearning4j_tpu.models has no builder "
                 f"{config['builder']!r}: this program cannot run the "
                 f"configuration")
    return Entry(config, mix)
