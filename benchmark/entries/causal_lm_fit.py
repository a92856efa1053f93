"""Entry ``causal_lm_fit``: a zoo decoder-only language model trained
through ``net.fit`` (``ComputationGraph.fit`` -> ``Trainer.fit`` ->
``DeviceFeeder`` -> the donating jitted step), the route ``trainer_fit``
takes for ResNet-50.  Used by the ``joyai_llm_flash`` configuration.

The adapter between the benchmark's names and the program's: the
reference's flat leaves (``l2.kv_b.w``, ``mtp.experts.gate``) go into the
graph's nested parameter dict (``l2_attn``/``W_kvb``,
``mtp_ffn``/``W_gate``), and what the comparison reads comes back under
the reference's names.  The batch is the one ``[B, S]`` int32 array of
ids the traffic draws, as features and as labels: the shift is the
program's, on the device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import harness
import probe

_ATTN = {"q_a.w": "W_qa", "q_a_norm.g": "q_norm", "q_b.w": "W_qb",
         "kv_a.w": "W_kva", "kv_a_norm.g": "kv_norm", "kv_b.w": "W_kvb",
         "o.w": "W_o"}
_FFN = {"gate.w": "W_gate", "up.w": "W_up", "down.w": "W_down",
        "router.w": "W_router", "experts.gate": "W_gate",
        "experts.up": "W_up", "experts.down": "W_down",
        "shared.gate.w": "shared_W_gate", "shared.up.w": "shared_W_up",
        "shared.down.w": "shared_W_down"}
_TOP = {"emb.w": ("embed", "W"), "head.w": ("lm_head", "W"),
        "final_norm.g": ("final_norm", "gamma"),
        "mtp.h_norm.g": ("mtp_h_norm", "gamma"),
        "mtp.e_norm.g": ("mtp_e_norm", "gamma"),
        "mtp.proj.w": ("mtp_proj", "W"),
        "mtp.final_norm.g": ("mtp_final_norm", "gamma")}


def _where(name: str) -> tuple:
    """Reference leaf name -> (vertex, parameter) of the graph."""
    if name in _TOP:
        return _TOP[name]
    block, _, leaf = name.partition(".")
    if leaf in ("attn_norm.g", "ffn_norm.g"):
        return f"{block}_{leaf[:-2]}", "gamma"
    if leaf in _ATTN:
        return f"{block}_attn", _ATTN[leaf]
    return f"{block}_ffn", _FFN[leaf]


_POLICIES = {("float32", "bfloat16"): "bf16", ("float32", "float32"): "f32"}


class Entry(harness.load_module("entries", "trainer_fit").Entry):
    """``trainer_fit``'s entry (the first steps, the window, the re-lowered
    step, the freeing: all ``Trainer``'s, whatever the graph) around
    another builder and another batch."""

    def build(self, weights: dict, seed: int) -> None:
        from deeplearning4j_tpu import models
        from deeplearning4j_tpu.config import DTypePolicy, set_dtype_policy
        from deeplearning4j_tpu.train import Adam
        precision, opt = self.config["precision"], self.config["optimizer"]
        policy = _POLICIES.get((precision["params"], precision["compute"]))
        if policy is None:
            raise ValueError(f"causal_lm_fit knows the policies "
                             f"{sorted(_POLICIES)}, the configuration "
                             f"states {precision}")
        set_dtype_policy(getattr(DTypePolicy, policy)())
        net = getattr(models, self.config["builder"])(
            self.config, int(self.mix["seq"]), seed=seed,
            updater=Adam(opt["learning_rate"], opt["beta1"], opt["beta2"],
                         opt["epsilon"]),
            mtp_weight=self.config["mtp_lambda"],
            init_std=self.config["init"]["std"])

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

    def to_batch(self, arrays: dict):
        from deeplearning4j_tpu.data.dataset import DataSet
        return DataSet(arrays["tokens"], arrays["tokens"])


def make(config: dict, mix: dict) -> Entry:
    return Entry(config, mix)
