"""The zoo's LFM2-MoE graph against an independent reference.

``models.lfm2_moe`` (double-gated short convolutions in three blocks of
four, grouped-query attention in the fourth, routed experts without a
shared one, a head tied to the embedding) at a small size on the CPU
under the float32 policy, against ``benchmark/reference/lfm2.py``: plain
``jax.numpy`` that imports nothing of the program, repeats the K/V heads
per group by indexing and keeps the tie as one array.  Read from
``benchmark/`` by path, as ``test_kimi_linear.py`` reads its own.  Then
grouped-query attention on both routes of ``ops.attention`` against
multi-head attention over K/V heads repeated per group, and the flash
kernels' TPU lowering for as many K/V heads as query heads against the
text they had before they learnt groups.

Tolerances.  Both sides are float32 on the CPU and compute the same
equations in another order (the program's experts are a sort and grouped
products, the reference's a dense masked sum; its attention is one
einsum, the reference's query blocks), so they differ by float32
rounding: read when written 2.4e-7 on the logits, 1.5e-6 on the worst
leaf's gap of norms and 2.6e-8 on a gradient (against the larger of 1 and
its largest entry).  The limits are twenty to forty times that, and a
thousand times under what a tap off by one position, a K/V head read by
the wrong group, a rotation on interleaved pairs or a bfloat16 matmul
reads (1e-2 and up).
"""

import copy
import functools
import hashlib
import importlib.util
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.config import (DTypePolicy, dtype_policy,
                                       set_dtype_policy)
from deeplearning4j_tpu.models import lfm2_moe, resnet50
from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers.decoder import (GatedShortConv,
                                                  causal_taps, rotate_half)
from deeplearning4j_tpu.ops.attention import multi_head_attention
from deeplearning4j_tpu.ops.pallas.flash_attention import flash_attention
from deeplearning4j_tpu.train.trainer import make_loss_fn

BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
SEQ, BATCH, SEED = 64, 2, 11
LOSS_LIMIT, LOGIT_LIMIT, GAP_LIMIT, GRAD_LIMIT = 1e-5, 1e-5, 3e-5, 1e-6
KINDS = ["conv", "gqa", "conv", "conv", "conv"]


def _load(kind: str, name: str):
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}", os.path.join(BENCHMARK, kind,
                                                 f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def small_config(**changes) -> dict:
    """The cell's configuration file at toy widths: hidden 64; attention 4
    query heads and 2 K/V heads of 16; 3 taps; 8 experts top-4 of width
    32, all held; dense 128; 256 ids; the cell's layers 1-3 (convolution
    with the dense feed-forward, attention, convolution: every kind of
    block, and a third of the compile the cell's five would take)."""
    with open(os.path.join(BENCHMARK, "configs", "lfm2_8b_a1b.json")) as f:
        config = json.load(f)
    config.update(hidden_size=64, num_attention_heads=4,
                  num_key_value_heads=2, intermediate_size=128,
                  moe_intermediate_size=32, num_experts=8, experts_held=8,
                  first_expert=0, vocab_size=256, num_hidden_layers=3)
    config["model"] = {"vocab_size": 256}
    config["optimizer"] = dict(config["optimizer"], learning_rate=1e-3)
    config["precision"] = {"params": "float32", "compute": "float32",
                           "activations": "float32"}
    config.update(changes)
    return config


@pytest.fixture(scope="module")
def reference():
    return _load("reference", "lfm2")


@pytest.fixture
def float32_policy():
    was = dtype_policy()
    set_dtype_policy(DTypePolicy.f32())
    yield
    set_dtype_policy(was)


def _entry(config, weights):
    """The benchmark's own adapter, so that the names are mapped once."""
    if BENCHMARK not in sys.path:
        sys.path.insert(0, BENCHMARK)
    entry = _load("entries", "decoder_lm_fit").make(
        config, {"seq": SEQ, "loss_every": 1})
    entry.build(weights, SEED)
    return entry


@pytest.fixture(scope="module")
def twin(reference):
    """(config, the reference's weights, the graph holding them): built
    once, so that its forward compiles once for the cases that share it."""
    was = dtype_policy()
    config = small_config()
    weights = reference.init_weights(config, SEED)
    net = _entry(config, weights).net            # sets the float32 policy
    set_dtype_policy(was)
    return config, weights, net


def _tokens(config, seed=SEED, batch=BATCH, seq=SEQ):
    return np.random.default_rng(seed).integers(
        0, config["vocab_size"], (batch, seq), dtype=np.int32)


def _close(got, want, limit):
    got, want = np.asarray(got), np.asarray(want)
    assert np.all(np.isfinite(got))
    scale = max(1.0, float(np.max(np.abs(want))))
    np.testing.assert_allclose(got, want, atol=limit * scale, rtol=0)


# ---- (a) logits, loss and every leaf's gradient --------------------------------
def test_logits_loss_and_gradients_match_the_reference(reference, twin,
                                                       float32_policy):
    config, weights, net = twin
    assert [kind for _, kind, _ in reference.block_names(config)] == [
        "conv", "full_attention", "conv"]
    tokens = jnp.asarray(_tokens(config))
    ones = (jnp.ones((BATCH,)), jnp.ones((SEQ,)))
    want, want_grad = jax.jit(jax.value_and_grad(functools.partial(
        reference.loss_fn, config=config, precision="f32")))(
        weights, tokens, *ones)
    loss = make_loss_fn(net)
    (got, _), got_grad = jax.jit(jax.value_and_grad(loss, has_aux=True))(
        net.params_, net.state_, tokens, tokens, None, None,
        jax.random.key(0))
    assert abs(float(got) - float(want)) / float(want) < LOSS_LIMIT
    assert set(weights) == {f"{v}.{k}" for v, leaves in net.params_.items()
                            for k in leaves}
    got_grad, want_grad = jax.device_get((got_grad, want_grad))
    for name, g in want_grad.items():
        vertex, leaf = name.rsplit(".", 1)
        assert np.max(np.abs(g)) > 0, name
        _close(got_grad[vertex][leaf], g, GRAD_LIMIT)
    logits = net.output(tokens)
    ref_logits = jax.jit(functools.partial(reference.logits, config=config))(
        weights, tokens)
    assert logits.shape == (BATCH, SEQ, config["vocab_size"])
    assert float(jnp.max(jnp.abs(ref_logits))) > 0.1
    assert float(jnp.max(jnp.abs(logits - ref_logits))) < LOGIT_LIMIT


# ---- (b) three net.fit steps through compare.gaps ----------------------------
def test_three_fit_steps_match_first_steps(reference, float32_policy):
    config = small_config(experts_held=4, first_expert=4)
    weights = reference.init_weights(config, SEED)
    entry = _entry(config, weights)
    import compare                     # benchmark/ is on the path by now
    arrays = [{"tokens": _tokens(config, seed=SEED + i)} for i in range(3)]
    got = entry.first_steps([entry.to_batch(a) for a in arrays])
    want = reference.first_steps(config, {}, weights, arrays, seed=SEED)
    numbers, where = compare.gaps(got, want)
    assert set(got["grad_norms"]) == set(reference.param_shapes(config))
    assert all(value < GAP_LIMIT for value in numbers.values()), (numbers,
                                                                   where)
    # every leaf moved: ids drawn at random teach no loss to fall
    assert min(want["delta_norms"].values()) > 0


# ---- (c) the gated convolution: the three-term sum, causal, rows apart ---------
def test_gated_conv_is_the_gated_tap_sum_causal_and_row_by_row(
        float32_policy):
    layer = GatedShortConv(taps=3)
    params = layer.init_params(jax.random.key(0), InputType.recurrent(8, 12))
    assert {k: v.shape for k, v in params.items()} == {
        "W_in": (8, 24), "conv_w": (8, 3), "W_out": (8, 8)}
    x = jax.random.normal(jax.random.key(1), (2, 12, 8))

    apply = jax.jit(lambda x: layer.apply(params, {}, x)[0])
    got = np.asarray(apply(x))
    x, params = np.asarray(x), jax.device_get(params)
    gate_b, gate_c, u = np.split(x @ params["W_in"], 3, axis=-1)
    bu, w = gate_b * u, params["conv_w"]
    for t in (0, 1, 2, 11):
        z = sum(w[:, j] * (bu[:, t - 2 + j] if t - 2 + j >= 0 else 0.0)
                for j in range(3))
        np.testing.assert_allclose(got[:, t], (gate_c[:, t] * z)
                                   @ params["W_out"], rtol=1e-5, atol=1e-6)
    # a later position moves no earlier one; one row moves no other row
    later = x.copy()
    later[:, 7] += 3.0
    moved = np.asarray(apply(later))
    np.testing.assert_array_equal(moved[:, :7], got[:, :7])
    assert np.min(np.max(np.abs(moved[:, 7:10] - got[:, 7:10]), axis=-1)) > 0
    other = x.copy()
    other[1] += 3.0
    np.testing.assert_array_equal(np.asarray(apply(other))[0], got[0])
    # the tap sum alone: KDA's short convolution is its SiLU
    np.testing.assert_allclose(np.asarray(causal_taps(bu, w))[:, 0],
                               bu[:, 0] * w[:, 2], rtol=1e-6)


def test_no_position_reads_a_later_one(twin, float32_policy):
    """The whole model, convolution and attention blocks alike: another
    last token moves no earlier logit, another first row no second."""
    config, _, net = twin
    tokens = jnp.asarray(_tokens(config))
    other = tokens.at[:, -1].set((tokens[:, -1] + 7) % config["vocab_size"])
    before, after = net.output(tokens), net.output(other)
    np.testing.assert_array_equal(before[:, :-1], after[:, :-1])
    assert float(jnp.max(jnp.abs(before[:, -1] - after[:, -1]))) > 1e-4
    row = tokens.at[0].set((tokens[0] + 3) % config["vocab_size"])
    np.testing.assert_array_equal(net.output(row)[1], before[1])


def test_rotate_half_turns_the_half_split_pairs():
    x = jax.random.normal(jax.random.key(4), (1, 5, 2, 8))
    got, x = np.asarray(rotate_half(x, 1e6)), np.asarray(x)
    for t in (0, 3):
        for i in (0, 3):
            angle = t * 1e6 ** (-2 * i / 8)
            c, s = np.cos(angle), np.sin(angle)
            a, b = x[0, t, :, i], x[0, t, :, i + 4]
            np.testing.assert_allclose(got[0, t, :, i], a * c - b * s,
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(got[0, t, :, i + 4], b * c + a * s,
                                       rtol=1e-5, atol=1e-6)


# ---- (d) the share test at this model's routing numbers ------------------------
def test_the_shares_add_up_to_the_uncut_layer(reference, twin,
                                              float32_policy):
    """Four shares of 2 experts from ``first_expert`` 0, 2, 4, 6 (the
    cell's ep 4) give routed parts that add up to what the uncut
    reference layer gives; there is no shared expert to count once."""
    config, weights, net = twin
    assert config["num_experts_per_tok"] == 4
    f = jax.random.normal(jax.random.key(3), (BATCH, SEQ, 64))
    whole = reference._routed(weights, "l2", f, config, lambda a: a)
    layer = {v.name: v.obj for v in net.conf.vertices}["l2_ffn"]
    assert layer.shared_hidden == 0 and layer.norm_eps == 1e-6
    params = net.params_["l2_ffn"]
    total, pairs = 0.0, 0.0
    for first in (0, 2, 4, 6):
        share = copy.copy(layer)
        share.experts_held, share.first_expert = 2, first
        mine = dict(params, **{name: params[name][first:first + 2]
                               for name in ("W_gate", "W_up", "W_down")})
        out, state = jax.jit(share.apply)(mine, layer.init_state(None), f)
        total = total + out
        pairs += float(state["moe_pairs"])
    assert float(jnp.max(jnp.abs(total - whole))) < LOGIT_LIMIT
    assert pairs == BATCH * SEQ * config["num_experts_per_tok"]


# ---- (e) the tie -----------------------------------------------------------------
def _embed_and_head(tied: bool):
    """ids -> embedding -> RMS norm -> the next-token head, tied or not."""
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.layers import (CausalLMOutput,
                                              EmbeddingSequenceLayer, RMSNorm)
    gb = (NeuralNetConfiguration.builder().seed(SEED).graph()
          .add_inputs("tokens").set_input_types(InputType.recurrent(1, 8)))
    gb.add_layer("embed", EmbeddingSequenceLayer(n_in=32, n_out=16,
                                                 has_bias=False), "tokens")
    gb.add_layer("final_norm", RMSNorm(), "embed")
    gb.add_layer("lm_head", CausalLMOutput(
        n_out=32, tie_to="embed" if tied else ""), "final_norm")
    gb.set_outputs("lm_head")
    return ComputationGraph(gb.build()).init()


def test_the_head_is_the_embedding_and_its_gradient_sums_both_uses(
        twin, float32_policy):
    _, _, net = twin
    assert net.params_["lm_head"] == {}
    assert list(net.params_["embed"]) == ["W"]
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 32, (2, 8)))

    def grad_of(graph, params):
        loss = make_loss_fn(graph)
        return jax.jit(jax.grad(lambda p: loss(
            p, graph.state_, tokens, tokens, None, None,
            jax.random.key(0))[0]))(params)

    tied = _embed_and_head(True)
    assert tied.params_["lm_head"] == {} and tied.num_params() == 32 * 16 + 16
    both = grad_of(tied, tied.params_)["embed"]["W"]
    # the same graph untied onto a copy of the same matrix: the two uses'
    # gradients come apart, and they add up to the tie's
    untied = _embed_and_head(False)
    got = grad_of(untied, dict(tied.params_, lm_head={
        "W": tied.params_["embed"]["W"].T}))
    as_input, as_head = got["embed"]["W"], got["lm_head"]["W"].T
    assert float(jnp.max(jnp.abs(as_input))) > 0
    assert float(jnp.max(jnp.abs(as_head))) > 0
    _close(both, as_input + as_head, 1e-6)


# ---- (f) grouped-query attention on both routes ----------------------------------
def _gqa_inputs(heads=4, kv=2, d=16, t=128):
    ks = jax.random.split(jax.random.key(5), 3)
    return (jax.random.normal(ks[0], (2, t, heads * d)),
            jax.random.normal(ks[1], (2, t, kv * d)),
            jax.random.normal(ks[2], (2, t, kv * d)))


def _repeated(x, kv, group, d):
    """K or V ``[B, T, kv*d]`` with every head repeated ``group`` times
    in place: what multi-head attention reads for grouped-query."""
    b, t = x.shape[:2]
    return jnp.repeat(x.reshape(b, t, kv, 1, d), group, axis=3).reshape(
        b, t, kv * group * d)


@pytest.mark.parametrize("route", ["einsum", "flash"])
def test_grouped_query_attention_is_mha_over_repeated_heads(route,
                                                            float32_policy):
    q, k, v = _gqa_inputs()
    flash = {"use_flash": route == "flash", "flash_block": 64}

    def grouped(q, k, v):
        return multi_head_attention(q, k, v, n_heads=4, n_kv_heads=2,
                                    causal=True, **flash)

    def repeated(q, k, v):
        return multi_head_attention(q, _repeated(k, 2, 2, 16),
                                    _repeated(v, 2, 2, 16), n_heads=4,
                                    causal=True, use_flash=False)

    cotangent = jax.random.normal(jax.random.key(6), q.shape)

    def both_ways(fn):
        out, pull = jax.vjp(fn, q, k, v)
        return (out,) + pull(cotangent)

    got = jax.jit(lambda: both_ways(grouped))()
    want = jax.jit(lambda: both_ways(repeated))()
    assert got[2].shape == k.shape                   # dK of the 2 K/V heads
    for a, b in zip(got, want):
        _close(a, b, 1e-5)


# the sha256 of the flash kernels' TPU lowering below (the traced function's
# name is part of the text), locations off, as it was before the kernels
# learnt K/V groups: multi-head attention must lower to the same text,
# operation for operation
MHA_FLASH_LOWERING = (
    "95317cd2b7fa0ecff46a21b41b4570b9f50229216c060f237f30a6d6e7ed1cda")


@pytest.mark.parametrize("n_kv_heads", [None, 4])
def test_multi_head_flash_lowers_to_the_text_it_had(n_kv_heads):
    """Lowered for a TPU, not compiled: no chip and no TPU library is
    needed for the text."""
    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, n_heads=4, causal=True, interpret=False,
            **({} if n_kv_heads is None else {"n_kv_heads": n_kv_heads})
        ).astype(jnp.float32))

    shape = jax.ShapeDtypeStruct((1, 2048, 4 * 64), jnp.bfloat16)
    was = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
            shape, shape, shape).lower(
            lowering_platforms=("tpu",)).as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", was)
    assert text.count("tpu_custom_call") == 2
    assert hashlib.sha256(text.encode()).hexdigest() == MHA_FLASH_LOWERING


def test_grouped_heads_have_to_divide():
    q, k, v = _gqa_inputs(heads=4, kv=3, d=16)
    with pytest.raises(ValueError, match="do not divide"):
        multi_head_attention(q, k, v, n_heads=4, n_kv_heads=3)


# ---- (g) what the fit span says -------------------------------------------------
def test_the_step_names_the_new_scopes(twin, float32_policy):
    """Every operation of the new mixers carries its scope, both ways:
    what ``obs.profiler.timeline`` sums device time by."""
    from deeplearning4j_tpu.train import Adam
    from deeplearning4j_tpu.train.trainer import make_train_step
    config, _, net = twin
    tx = Adam(1e-3).to_optax()
    ids = jnp.asarray(_tokens(config))
    text = make_train_step(net, tx).lower(
        net.params_, net.state_, tx.init(net.params_), ids, ids, None, None,
        jax.random.key(0)).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    for scope in ("conv/conv.proj", "conv/conv.taps", "conv/conv.out",
                  "gqa/gqa.qk_norm", "gqa/gqa.rope", "gqa/gqa.core"):
        mixer = "l2_attn" if scope.startswith("gqa") else "l3_attn"
        mine = [n for n in names if f"{mixer}/{scope}/" in n
                or f"{mixer})/{scope}/" in n]
        assert any(n.startswith("jit(tpudl_train_step)/jvp(") for n in mine), \
            scope
        assert any("/transpose(" in n for n in mine), scope


def test_trace_attrs_carry_the_mixers_and_the_groups():
    attrs = lfm2_moe(small_config(num_hidden_layers=5), SEQ,
                     seed=SEED).trace_attrs()
    assert attrs["attention_kinds"] == KINDS
    assert attrs["kv_groups"] == 2
    assert attrs["remat_runs"] == 5            # a block is one run
    config = small_config(num_key_value_heads=1)
    assert lfm2_moe(config, SEQ, seed=SEED).trace_attrs()["kv_groups"] == 4
    attrs = resnet50(height=32, width=32, num_classes=10).trace_attrs()
    assert "attention_kinds" not in attrs and "kv_groups" not in attrs
