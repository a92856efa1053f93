"""Entry ``hybrid_lm_fit``: a zoo decoder-only language model whose blocks
differ by layer, trained through ``net.fit`` (``ComputationGraph.fit`` ->
``Trainer.fit`` -> ``DeviceFeeder`` -> the donating jitted step), the
route ``trainer_fit`` and ``causal_lm_fit`` take.  Used by the
``kimi_linear_48b_a3b`` configuration.

Beside ``causal_lm_fit`` only the name map and the builder's arguments
differ: the reference names a leaf ``<vertex with dots>.<parameter>``
(``l2.attn.W_fa``, ``l4.ffn.shared_W_up``, ``lm_head.W``), which is the
graph's ``l2_attn``/``W_fa``; the builder gets the chunk of its scan and
no multi-token-prediction weight.  The batch is the one ``[B, S]`` int32
array of ids the traffic draws, as features and as labels.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp

import harness
import probe

_POLICIES = {("float32", "bfloat16"): "bf16", ("float32", "float32"): "f32"}


def _where(name: str) -> tuple:
    """Reference leaf name -> (vertex, parameter) of the graph."""
    vertex, _, leaf = name.rpartition(".")
    return vertex.replace(".", "_"), leaf


class Entry(harness.load_module("entries", "causal_lm_fit").Entry):
    """``causal_lm_fit``'s entry (``trainer_fit``'s first steps, window,
    re-lowered step and freeing; its batch) around another builder."""

    def build(self, weights: dict, seed: int) -> None:
        from deeplearning4j_tpu import models
        from deeplearning4j_tpu.config import DTypePolicy, set_dtype_policy
        from deeplearning4j_tpu.train import Adam
        precision, opt = self.config["precision"], self.config["optimizer"]
        policy = _POLICIES.get((precision["params"], precision["compute"]))
        if policy is None:
            raise ValueError(f"hybrid_lm_fit knows the policies "
                             f"{sorted(_POLICIES)}, the configuration "
                             f"states {precision}")
        set_dtype_policy(getattr(DTypePolicy, policy)())
        net = getattr(models, self.config["builder"])(
            self.config, int(self.mix["seq"]), seed=seed,
            updater=Adam(opt["learning_rate"], opt["beta1"], opt["beta2"],
                         opt["epsilon"]),
            init_std=self.config["init"]["std"],
            kda_chunk=self.config["kda_chunk"])

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
        sys.exit(f"hybrid_lm_fit: deeplearning4j_tpu.models has no builder "
                 f"{config['builder']!r}: this program cannot run the "
                 f"configuration")
    return Entry(config, mix)
