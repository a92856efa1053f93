"""BERT — transformer encoder for the MLM fine-tune workload.

The reference runs BERT by importing a TF GraphDef into SameDiff
(``nd4j/samediff-import/`` + ``TFGraphMapper``; BASELINE config #4) and
fine-tuning with ``SameDiff.fit``.  TPU-native design: the encoder is a
pure-jax function over a named parameter pytree whose keys mirror the TF
BERT checkpoint variable names (bert/embeddings/word_embeddings, ...,
bert/encoder/layer_N/attention/self/query/kernel, ...) so the
TF-checkpoint importer (``deeplearning4j_tpu.importers.tf_bert``) is a
pure name-mapping exercise, and tensor-parallel sharding rules
(``deeplearning4j_tpu.parallel``) can be keyed by the same names.

Everything traces into one XLA program: embeddings gather, H-head fused
attention (MXU einsums), GELU FFN, residual+layernorm — no per-op
dispatch.  Weights are float32; matmuls run in the global dtype policy's
compute dtype (bf16 on TPU for speed parity).
"""

from __future__ import annotations

import dataclasses
import json
import time
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.config import dtype_policy
from deeplearning4j_tpu.obs import tracing
from deeplearning4j_tpu.obs.registry import train_loop_metrics
from deeplearning4j_tpu.ops.attention import dropout, multi_head_attention
from deeplearning4j_tpu.train import step_cache


@dataclasses.dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position: int = 512
    type_vocab_size: int = 2
    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    layer_norm_eps: float = 1e-12
    initializer_range: float = 0.02
    # long-sequence path: Pallas flash kernel (fwd + bwd) instead of the
    # materialized [T,T] einsum chain.  None = auto (the promoted
    # default): flash at seq >= 1024 — the crossover measured on a v5e
    # before PR 1 (1.29x at seq 4096); explicit False always wins
    use_flash: Optional[bool] = None
    flash_block: int = 0      # 0 = tuned default (1024×1024 blocks)
    # MLM head scope: decode only `max_predictions` gathered positions
    # per sequence instead of every token (TF BERT's
    # max_predictions_per_seq; google-research/bert run_pretraining
    # gathers masked positions before the vocab matmul).  0 = decode the
    # full width (exact when every position may carry a label).  On TPU
    # the gather removes ~6·E·(T−k)/T of vocab-matmul FLOPs AND the
    # [B,T,V] f32 logits materialization (≈0.5 GB at base/seq128).
    max_predictions: int = 0

    @staticmethod
    def base() -> "BertConfig":
        return BertConfig()

    @staticmethod
    def tiny(vocab_size: int = 1000) -> "BertConfig":
        """Test-sized config (fast on CPU)."""
        return BertConfig(vocab_size=vocab_size, hidden_size=64, num_layers=2,
                          num_heads=4, intermediate_size=128, max_position=128)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @staticmethod
    def from_dict(d: dict) -> "BertConfig":
        known = {f.name for f in dataclasses.fields(BertConfig)}
        return BertConfig(**{k: v for k, v in d.items() if k in known})


def _dense_params(key, n_in, n_out, std):
    kw, _ = jax.random.split(key)
    return {"kernel": std * jax.random.truncated_normal(kw, -2.0, 2.0, (n_in, n_out)),
            "bias": jnp.zeros((n_out,))}


def _ln_params(n):
    return {"gamma": jnp.ones((n,)), "beta": jnp.zeros((n,))}


def init_params(config: BertConfig, key: jax.Array) -> dict:
    """Parameter pytree with TF-BERT-shaped naming."""
    std = config.initializer_range
    h = config.hidden_size
    keys = jax.random.split(key, 4 + config.num_layers)
    params: dict[str, Any] = {
        "embeddings": {
            "word_embeddings": std * jax.random.truncated_normal(
                keys[0], -2.0, 2.0, (config.vocab_size, h)),
            "position_embeddings": std * jax.random.truncated_normal(
                keys[1], -2.0, 2.0, (config.max_position, h)),
            "token_type_embeddings": std * jax.random.truncated_normal(
                keys[2], -2.0, 2.0, (config.type_vocab_size, h)),
            "layer_norm": _ln_params(h),
        },
        "encoder": {},
        "mlm": {
            "transform": _dense_params(keys[3], h, h, std),
            "transform_layer_norm": _ln_params(h),
            "output_bias": jnp.zeros((config.vocab_size,)),
        },
        "pooler": _dense_params(jax.random.fold_in(keys[3], 99), h, h, std),
    }
    for i in range(config.num_layers):
        lk = jax.random.split(keys[4 + i], 6)
        params["encoder"][f"layer_{i}"] = {
            "attention": {
                "query": _dense_params(lk[0], h, h, std),
                "key": _dense_params(lk[1], h, h, std),
                "value": _dense_params(lk[2], h, h, std),
                "output": _dense_params(lk[3], h, h, std),
                "output_layer_norm": _ln_params(h),
            },
            "intermediate": _dense_params(lk[4], h, config.intermediate_size, std),
            "output": _dense_params(lk[5], config.intermediate_size, h, std),
            "output_layer_norm": _ln_params(h),
        }
    return params


def _dense(p, x):
    policy = dtype_policy()
    y = jnp.einsum("...i,io->...o", x.astype(policy.compute_dtype),
                   p["kernel"].astype(policy.compute_dtype))
    return (y + p["bias"].astype(y.dtype)).astype(policy.output_dtype)


def _layer_norm(p, x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["gamma"] + p["beta"]


def _dropout(x, rate, train, rng):
    if not train or rate <= 0.0 or rng is None:
        return x
    return dropout(x, 1.0 - rate, rng)


def encoder_layer(lp: dict, config: BertConfig, x: jnp.ndarray,
                  attention_mask: Optional[jnp.ndarray] = None,
                  *, train: bool = False,
                  rng: Optional[jax.Array] = None) -> jnp.ndarray:
    """One transformer encoder block (bert/encoder/layer_N) — the single
    source for both :func:`encode` and :func:`pipeline_stages`."""
    # the key benchmark/reference/bert_base.py draws the same mask on
    drop_rng = jax.random.fold_in(rng, 3) if train and rng is not None else None
    with jax.named_scope("attention"):
        q = _dense(lp["attention"]["query"], x)
        k = _dense(lp["attention"]["key"], x)
        v = _dense(lp["attention"]["value"], x)
        attn = multi_head_attention(q, k, v, n_heads=config.num_heads,
                                    kv_mask=attention_mask,
                                    use_flash=config.use_flash,
                                    flash_block=config.flash_block,
                                    dropout_rate=config.attention_dropout,
                                    dropout_rng=drop_rng)
        attn = _dense(lp["attention"]["output"], attn)
        attn = _dropout(attn, config.hidden_dropout, train, rng)
        x = _layer_norm(lp["attention"]["output_layer_norm"], x + attn,
                        config.layer_norm_eps)
    with jax.named_scope("ffn"):
        inter = jax.nn.gelu(_dense(lp["intermediate"], x))
        out = _dense(lp["output"], inter)
        out = _dropout(out, config.hidden_dropout, train,
                       jax.random.fold_in(rng, 7) if rng is not None
                       else None)
        return _layer_norm(lp["output_layer_norm"], x + out,
                           config.layer_norm_eps)


def embed(params: dict, config: BertConfig, input_ids: jnp.ndarray,
          token_type_ids: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Embedding sum + layernorm (bert/embeddings)."""
    t = input_ids.shape[1]
    emb = params["embeddings"]
    x = jnp.take(emb["word_embeddings"], input_ids.astype(jnp.int32), axis=0)
    x = x + emb["position_embeddings"][None, :t, :]
    if token_type_ids is None:
        token_type_ids = jnp.zeros_like(input_ids)
    x = x + jnp.take(emb["token_type_embeddings"],
                     token_type_ids.astype(jnp.int32), axis=0)
    return _layer_norm(emb["layer_norm"], x, config.layer_norm_eps)


def encode(params: dict, config: BertConfig, input_ids: jnp.ndarray,
           token_type_ids: Optional[jnp.ndarray] = None,
           attention_mask: Optional[jnp.ndarray] = None,
           *, train: bool = False, rng: Optional[jax.Array] = None) -> jnp.ndarray:
    """input_ids [B,T] int32 → hidden states [B,T,H]."""
    with jax.named_scope("embeddings"):
        x = embed(params, config, input_ids, token_type_ids)
        if rng is not None:
            rng = jax.random.fold_in(rng, 0)
        x = _dropout(x, config.hidden_dropout, train, rng)
    for i in range(config.num_layers):
        layer_rng = jax.random.fold_in(rng, i + 1) if rng is not None else None
        with jax.named_scope(f"encoder_{i}"):
            x = encoder_layer(params["encoder"][f"layer_{i}"], config, x,
                              attention_mask, train=train, rng=layer_rng)
    return x


def pool(params: dict, hidden: jnp.ndarray) -> jnp.ndarray:
    """[CLS] pooler (bert/pooler/dense, tanh)."""
    return jnp.tanh(_dense(params["pooler"], hidden[:, 0]))


def mlm_logits(params: dict, config: BertConfig, hidden: jnp.ndarray) -> jnp.ndarray:
    """Masked-LM head: transform → layernorm → decode with TIED word
    embeddings + output bias (TF BERT cls/predictions)."""
    x = jax.nn.gelu(_dense(params["mlm"]["transform"], hidden))
    x = _layer_norm(params["mlm"]["transform_layer_norm"], x, config.layer_norm_eps)
    policy = dtype_policy()
    logits = jnp.einsum("bth,vh->btv", x.astype(policy.compute_dtype),
                        params["embeddings"]["word_embeddings"].astype(policy.compute_dtype))
    logits = logits + params["mlm"]["output_bias"].astype(logits.dtype)
    # MLM softmax/loss math runs in >=f32 downstream
    return logits.astype(jnp.promote_types(policy.output_dtype, jnp.float32))


def _weighted_mlm_ce(logits, labels, label_weights):
    """Weighted-mean cross-entropy over the masked positions — shared by
    :func:`mlm_loss` and :func:`mlm_loss_from_logits`."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, labels.astype(jnp.int32)[..., None], axis=-1)[..., 0]
    weights = label_weights.astype(logp.dtype)
    return -jnp.sum(picked * weights) / jnp.maximum(jnp.sum(weights), 1.0)


def mlm_loss(params: dict, config: BertConfig, input_ids, labels, label_weights,
             token_type_ids=None, attention_mask=None, *, train=True, rng=None):
    """Masked-LM loss: mean cross-entropy over positions with
    label_weights==1 (the masked positions).

    With ``config.max_predictions = k`` the masked positions are gathered
    BEFORE the vocab decode (top-k by weight, ties → lower position —
    exact whenever ≤ k positions carry weight; beyond-k positions drop,
    which is TF BERT's max_predictions_per_seq behavior)."""
    hidden = encode(params, config, input_ids, token_type_ids, attention_mask,
                    train=train, rng=rng)
    with jax.named_scope("mlm_head"):
        k = config.max_predictions
        if k and k < hidden.shape[1]:
            _, pos = jax.lax.top_k(label_weights, k)           # [B, k]
            hidden = jnp.take_along_axis(hidden, pos[..., None], axis=1)
            labels = jnp.take_along_axis(labels, pos, axis=1)
            label_weights = jnp.take_along_axis(label_weights, pos, axis=1)
        logits = mlm_logits(params, config, hidden)
    with jax.named_scope("loss"):
        return _weighted_mlm_ce(logits, labels, label_weights)


def pipeline_stages(config: BertConfig, params: dict, n_stages: int):
    """Split the BERT MLM model into ``n_stages`` pipeline stages for
    :func:`deeplearning4j_tpu.parallel.pipeline_stages.pipeline_train_step`.

    Stage 0 owns embeddings (+ first encoder layers), middle stages own
    encoder layers, the last stage owns its layers + the MLM head (tied
    decode uses a COPY of the word embeddings in the last stage's params;
    apply :func:`merge_tied_embedding_grads` to each step's grads to keep
    the two copies exactly tied under training).  Returns
    ``(stage_fns, stage_params)``; the pipeline input is
    ``input_ids.astype(float32)`` ([B, T]) and the last stage's output is
    the MLM logits ([B, T, V]).
    """
    L = config.num_layers
    if n_stages < 2 or L % n_stages:
        raise ValueError(f"{L} layers not divisible into {n_stages} stages")
    per = L // n_stages
    eps = config.layer_norm_eps
    stage_params = []
    stage_fns = []
    for s in range(n_stages):
        layers = {f"layer_{i}": params["encoder"][f"layer_{i}"]
                  for i in range(s * per, (s + 1) * per)}
        sp = {"layers": layers}
        if s == 0:
            sp["embeddings"] = params["embeddings"]
        if s == n_stages - 1:
            sp["mlm"] = params["mlm"]
            sp["decode_embeddings"] = params["embeddings"]["word_embeddings"]
        stage_params.append(sp)

        def fn(p, h, s=s):
            if s == 0:
                ids = jax.lax.stop_gradient(h).astype(jnp.int32)
                x = embed(p, config, ids)
            else:
                x = h
            for i in range(s * per, (s + 1) * per):
                with jax.named_scope(f"encoder_{i}"):
                    x = encoder_layer(p["layers"][f"layer_{i}"], config, x)
            if s == n_stages - 1:
                y = jax.nn.gelu(_dense(p["mlm"]["transform"], x))
                y = _layer_norm(p["mlm"]["transform_layer_norm"], y, eps)
                policy = dtype_policy()
                logits = jnp.einsum(
                    "bth,vh->btv", y.astype(policy.compute_dtype),
                    p["decode_embeddings"].astype(policy.compute_dtype))
                logits = logits + p["mlm"]["output_bias"].astype(logits.dtype)
                return logits.astype(jnp.float32)
            return x

        stage_fns.append(fn)
    return stage_fns, stage_params


def merge_tied_embedding_grads(stage_grads):
    """Re-tie the pipelined MLM decode weights to stage 0's embedding
    table.

    :func:`pipeline_stages` gives the LAST stage an independent copy of
    ``word_embeddings`` (``decode_embeddings``) for the tied decode; a
    single pipeline step therefore produces the embedding gradient split
    across two leaves.  This sums the two and writes the total into BOTH
    leaves, so under any per-leaf elementwise updater the two copies —
    identical at init — receive identical updates every step and stay
    exactly tied; multi-step training then matches the dense
    :func:`mlm_loss` model (which owns a single shared table).  Call it
    on the grads returned by ``pipeline_train_step`` before the updater.
    """
    grads = list(stage_grads)
    first = dict(grads[0])
    last = dict(grads[-1])
    emb = dict(first["embeddings"])
    total = emb["word_embeddings"] + last["decode_embeddings"]
    emb["word_embeddings"] = total
    first["embeddings"] = emb
    last["decode_embeddings"] = total
    grads[0] = first
    grads[-1] = last
    return tuple(grads)


def mlm_loss_from_logits(logits, packed_labels):
    """Loss head for the pipelined model: ``packed_labels`` [B, T, 2] =
    (labels, label_weights) stacked on the last axis."""
    return _weighted_mlm_ce(logits, packed_labels[..., 0],
                            packed_labels[..., 1])


class BertForMaskedLM:
    """Workload wrapper: holds params + jit'd train step (SameDiff
    ``TrainingConfig`` + ``fit`` parity for the BERT config)."""

    def __init__(self, config: BertConfig, seed: int = 0):
        self.config = config
        self.seed = seed
        self.params = init_params(config, jax.random.key(seed))
        self.opt_state = None
        self._step = None
        self.iteration = 0

    def num_params(self) -> int:
        from deeplearning4j_tpu.utils.pytree import param_count
        return param_count(self.params)

    def make_train_step(self, tx):
        """Build the jit'd MLM train step.

        DONATION CONTRACT: the returned step donates its ``params`` and
        ``opt_state`` arguments (updated in place in HBM).  After calling
        ``step(params, opt_state, ...)`` the arrays passed in are DELETED —
        callers MUST rebind to the returned ``(params, opt_state, loss)``,
        e.g. ``model.params, model.opt_state, loss = step(model.params, ...)``
        exactly as :meth:`fit` does.  Reading ``model.params`` after a manual
        step without rebinding raises a deleted-buffer error.
        """
        config = self.config

        # the name the device trace's XLA Modules line shows: jit_<name>
        @partial(jax.jit, donate_argnums=(0, 1))
        def tpudl_bert_mlm_step(params, opt_state, input_ids, labels,
                                label_weights, attention_mask, rng):
            def loss_fn(p):
                return mlm_loss(p, config, input_ids, labels, label_weights,
                                attention_mask=attention_mask, train=True, rng=rng)
            loss, grads = jax.value_and_grad(loss_fn)(params)
            with jax.named_scope("optimizer"):
                updates, opt_state2 = tx.update(grads, opt_state, params)
                params2 = jax.tree_util.tree_map(lambda a, u: a + u, params,
                                                 updates)
            return params2, opt_state2, loss

        return tpudl_bert_mlm_step

    def fit(self, batches, updater=None, epochs: int = 1,
            listeners=None) -> float:
        """Train over ``batches`` for ``epochs`` and return the last step's
        loss as a python float (``nan`` over no batches): the loop's ONE
        host read, after the last epoch, and the point where the caller is
        sure the work is done.

        ``iteration_done`` listeners are handed the step's loss as the
        DEVICE scalar the jitted step returned, as ``Trainer.step_batch``
        hands it; the loop never converts it, so back-to-back steps enqueue
        without a wait.  A listener that converts every score (e.g.
        ``CollectScoresListener``) makes the loop wait for every step;
        ``ScoreIterationListener(n)`` reads one in ``n``.
        """
        from deeplearning4j_tpu.train import updaters as updater_mod
        from deeplearning4j_tpu.obs.listeners import ListenerBus
        bus = listeners if isinstance(listeners, ListenerBus) else ListenerBus(listeners)
        tx = (updater or updater_mod.Adam(2e-5)).to_optax()
        if self.opt_state is None:
            self.opt_state = tx.init(self.params)
        if self._step is None:
            self._step = self.make_train_step(tx)
        # rbg: XLA's hardware rng-bit-generator — ~2 ms/step cheaper than
        # threefry for the 37 per-layer dropout masks on v5e (bench r4);
        # dropout needs speed, not counter-stream reproducibility
        key = jax.random.key(self.seed + 31, impl="rbg")
        last = float("nan")

        def _place(batch):
            """Background-stage H2D: batch N+1 transfers while step N
            executes (batches are fixed-shape dicts — no bucketing)."""
            attn = batch.get("attention_mask")
            return (jnp.asarray(batch["input_ids"]),
                    jnp.asarray(batch["labels"]),
                    jnp.asarray(batch["label_weights"]),
                    None if attn is None else jnp.asarray(attn))

        from deeplearning4j_tpu.data.device_pipeline import DeviceFeeder
        feeder = DeviceFeeder(_place, bucketing=False)
        # the spans and counters Trainer.step_batch keeps, at the same
        # boundaries (the handles looked up once, here)
        metrics = train_loop_metrics()
        with tracing.span("fit", model=type(self).__name__, epochs=epochs):
            for epoch in range(epochs):
                if hasattr(batches, "reset"):
                    batches.reset()
                with tracing.span("epoch", epoch=epoch):
                    for fed in feeder.feed(batches):
                        key, sub = jax.random.split(key)
                        last = self._fit_step(fed, sub, bus, metrics)
        return float(last)

    def _fit_step(self, fed, rng, bus, metrics) -> jax.Array:
        """One iteration of :meth:`fit`: ``step`` over all of it,
        ``step.dispatch`` around the jitted call and nothing else,
        ``step.read`` around the listeners (what ``Trainer._reading``
        holds).  Hands the listeners, and returns, the loss as the device
        scalar the step returned: whoever reads it converts it."""
        t0 = time.perf_counter()
        with tracing.span("step", iteration=self.iteration) as sp:
            ids, labels, weights, attn = fed.batch
            traces_before = step_cache.jit_cache_entries(self._step)
            t1 = time.perf_counter()
            with tracing.span("step.dispatch"):
                self.params, self.opt_state, loss = self._step(
                    self.params, self.opt_state, ids, labels, weights,
                    attn, rng)
            t2 = time.perf_counter()
            retraced = (step_cache.jit_cache_entries(self._step)
                        - traces_before)
            if retraced > 0:
                sp.set_attribute("compile", True)
                metrics.recompiles.inc(retraced)
            metrics.steps.inc()
            metrics.examples.inc(fed.n_examples)
            t3 = time.perf_counter()
            with tracing.span("step.read"):
                bus.dispatch("iteration_done", self, self.iteration, 0, loss)
            t4 = time.perf_counter()
            self.iteration += 1
        if retraced == 0:
            metrics.iteration.observe(time.perf_counter() - t0)
            metrics.dispatch.observe(t2 - t1)
            metrics.read.observe(t4 - t3)
        return loss

    def predict_mlm(self, input_ids, attention_mask=None):
        hidden = encode(self.params, self.config, jnp.asarray(input_ids),
                        attention_mask=attention_mask)
        return mlm_logits(self.params, self.config, hidden)

    # ------------------------------------------------------------- serde
    def save(self, path: str) -> None:
        import zipfile
        from deeplearning4j_tpu.io.model_serializer import _tree_to_npz_bytes
        with zipfile.ZipFile(path, "w", zipfile.ZIP_DEFLATED) as zf:
            zf.writestr("bert_config.json", json.dumps(self.config.to_dict()))
            zf.writestr("params.npz", _tree_to_npz_bytes(self.params))

    @staticmethod
    def load(path: str) -> "BertForMaskedLM":
        import zipfile
        from deeplearning4j_tpu.io.model_serializer import (
            _npz_bytes_to_leaves, _rebuild_like)
        with zipfile.ZipFile(path, "r") as zf:
            config = BertConfig.from_dict(json.loads(zf.read("bert_config.json").decode()))
            model = BertForMaskedLM(config)
            model.params = _rebuild_like(model.params,
                                         _npz_bytes_to_leaves(zf.read("params.npz")))
        return model
