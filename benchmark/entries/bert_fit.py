"""Entry ``bert_fit``: BERT masked-LM pre-training through
``BertForMaskedLM.fit`` (its own jitted step behind ``DeviceFeeder``).
The window's listener reads the loss every ``loss_every``-th step of the
traffic mix, whatever the loop hands it: a python float today, a device
scalar once the loop stops reading the loss itself.  Used by the
``bert_base`` configuration.

ROADMAP R1 will move BERT onto ``Trainer``; when it removes
``BertForMaskedLM.fit``, a ``benchmark`` PR re-points this entry.

The adapter between the benchmark's names and the program's: the
reference's flat leaves (``l3.q.w``) go into the TF-checkpoint-shaped
tree (``encoder/layer_3/attention/query/kernel``), and what the
comparison reads comes back under the reference's names.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import probe

_DENSE = {"q": ("attention", "query"), "k": ("attention", "key"),
          "v": ("attention", "value"), "o": ("attention", "output"),
          "ffn1": ("intermediate",), "ffn2": ("output",)}
_NORM = {"ln1": ("attention", "output_layer_norm"),
         "ln2": ("output_layer_norm",)}
_TOP = {
    "emb.word": ("embeddings", "word_embeddings"),
    "emb.pos": ("embeddings", "position_embeddings"),
    "emb.type": ("embeddings", "token_type_embeddings"),
    "emb.ln.gamma": ("embeddings", "layer_norm", "gamma"),
    "emb.ln.beta": ("embeddings", "layer_norm", "beta"),
    "mlm.t.w": ("mlm", "transform", "kernel"),
    "mlm.t.b": ("mlm", "transform", "bias"),
    "mlm.ln.gamma": ("mlm", "transform_layer_norm", "gamma"),
    "mlm.ln.beta": ("mlm", "transform_layer_norm", "beta"),
    "mlm.bias": ("mlm", "output_bias"),
}


def _where(name: str) -> tuple:
    """Reference leaf name -> path in ``BertForMaskedLM.params``."""
    if name in _TOP:
        return _TOP[name]
    layer, part, leaf = name.split(".")
    path = ("encoder", f"layer_{layer[1:]}")
    if part in _DENSE:
        return path + _DENSE[part] + ({"w": "kernel", "b": "bias"}[leaf],)
    return path + _NORM[part] + (leaf,)


def _get(tree, path):
    for key in path:
        tree = tree[key]
    return tree


class Entry:
    def __init__(self, config: dict, mix: dict):
        self.config, self.mix = config, mix
        self.model = None
        self.weights = None
        self.cadence = probe.Cadence(mix["loss_every"])

    # ---- set-up -------------------------------------------------------------
    def build(self, weights: dict, seed: int) -> None:
        from deeplearning4j_tpu.config import DTypePolicy, set_dtype_policy
        from deeplearning4j_tpu.models.bert import BertConfig, BertForMaskedLM
        precision, m = self.config["precision"], self.config["model"]
        if (precision["params"], precision["compute"]) != ("float32",
                                                           "bfloat16"):
            raise ValueError(f"bert_fit runs the bf16 policy, the "
                             f"configuration states {precision}")
        if m["hidden_act"] != "gelu":
            raise ValueError("models/bert.py computes tanh-GELU; the "
                             "configuration states otherwise")
        set_dtype_policy(DTypePolicy.bf16())
        cfg = BertConfig(
            vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
            num_layers=m["num_hidden_layers"],
            num_heads=m["num_attention_heads"],
            intermediate_size=m["intermediate_size"],
            max_position=m["max_position_embeddings"],
            type_vocab_size=m["type_vocab_size"],
            hidden_dropout=m["hidden_dropout_prob"],
            attention_dropout=m["attention_probs_dropout_prob"],
            layer_norm_eps=m["layer_norm_eps"],
            initializer_range=m["initializer_range"],
            max_predictions=self.mix["max_predictions"])
        made = {}

        def shapes():              # the constructor traced, never run
            made["model"] = BertForMaskedLM(cfg, seed=seed)
            return made["model"].params
        param_shapes = jax.eval_shape(shapes)
        self._names = {name: _where(name) for name in weights}
        leaves = len(jax.tree_util.tree_leaves(param_shapes))
        pooler = len(jax.tree_util.tree_leaves(param_shapes["pooler"]))
        if leaves - pooler != len(weights):
            raise ValueError(f"the model has {leaves - pooler} leaves in "
                             f"its MLM loss, the reference {len(weights)}")

        @jax.jit
        def place(flat):
            # the pooler is outside the MLM loss: zeros, and it stays there
            params = jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), param_shapes)
            for name, path in self._names.items():
                want = _get(param_shapes, path)
                _get(params, path[:-1])[path[-1]] = flat[name].astype(
                    want.dtype)
            return params

        self.model = made["model"]
        self.model.params = place(weights)
        self.weights = weights
        self.reader = probe.FlatReader(self._flatten, weights)

    def _flatten(self, tree) -> dict:
        return {name: _get(tree, path) for name, path in self._names.items()}

    def to_batch(self, arrays: dict):
        return arrays                    # fit takes the dict as it is

    def _fit(self, batches, listener) -> None:
        from deeplearning4j_tpu.train.updaters import Adam
        opt = self.config["optimizer"]
        if opt["kind"] != "adam":
            raise ValueError(f"bert_fit trains with Adam, not {opt['kind']}")
        self.model.fit(batches, updater=Adam(
            opt["learning_rate"], opt["beta1"], opt["beta2"], opt["epsilon"]),
            listeners=[listener])

    def first_steps(self, batches: list) -> dict:
        """The warm-up IS the first steps: the same ``fit`` and feeder the
        window uses, over batches that all differ."""
        seen = probe.FirstSteps(self.reader, len(batches),
                                lambda model: model.params)
        self._fit(iter(batches), seen)
        return seen.readings(self.config["optimizer"])

    # ---- the window ---------------------------------------------------------
    def run(self, iterator) -> None:
        self._fit(iterator, self.cadence)

    def wait(self) -> None:
        jax.block_until_ready(self.model.params)

    def steps(self) -> int:
        return self.cadence.steps

    def window_losses(self) -> list:
        return list(self.cadence.losses)

    def recompiles(self) -> float:
        """Programs the step has traced so far (1 after the warm-up; the
        program keeps no recompile counter for this path)."""
        return float(self.model._step._cache_size())

    # ---- after the window ---------------------------------------------------
    def lowered_step(self, batch):
        """The model's own step, lowered for ``batch``: compiling it is a
        cache hit once the step has run."""
        model = self.model
        return model._step.lower(
            model.params, model.opt_state, jnp.asarray(batch["input_ids"]),
            jnp.asarray(batch["labels"]), jnp.asarray(batch["label_weights"]),
            jnp.asarray(batch["attention_mask"]),
            jax.random.key(0, impl="rbg"))

    def free(self) -> None:
        model, self.model = self.model, None
        if model is not None:
            for tree in (model.params, model.opt_state):
                for leaf in jax.tree_util.tree_leaves(tree):
                    if hasattr(leaf, "delete"):
                        leaf.delete()
            model.params = model.opt_state = None


def make(config: dict, mix: dict) -> Entry:
    return Entry(config, mix)
