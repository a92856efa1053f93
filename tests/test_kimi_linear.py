"""The zoo's Kimi-Linear graph against an independent reference.

``models.kimi_linear`` (Kimi Delta Attention as a chunked scan in three
blocks of four, latent attention without positions in the fourth, the
routed experts ``joyai_llm_flash`` has) at a small size on the CPU under
the float32 policy, against ``benchmark/reference/kimi_linear.py``: plain
``jax.numpy`` that imports nothing of the program and runs the delta rule
one token at a time.  Read from ``benchmark/`` by path, as
``test_joyai_llm_flash.py`` reads its own.

Tolerances.  Both sides are float32 on the CPU and compute the same
equations in another order (the reference's state goes token by token,
the program's chunk by chunk through an inversion; its experts are a
dense masked sum, the program's a sort and grouped products), so they
differ by float32 rounding: read when written 1e-7 to 4e-6 on every
number of the whole model, and up to 1.4e-5 of gradients that reach 36 on
the bare scan.  The limits are some twenty times that, and a thousand
times under what a decay off by one token, a missing ``beta``, one
mis-routed expert or a bfloat16 matmul reads (1e-2 and up).
"""

import copy
import dataclasses
import functools
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.config import (DTypePolicy, dtype_policy,
                                       set_dtype_policy)
from deeplearning4j_tpu.models import kimi_linear, resnet50
from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers.decoder import (DeltaAttention,
                                                  LatentAttention,
                                                  _chunk_phase,
                                                  _map_head_groups,
                                                  _scan_and_read,
                                                  chunked_delta_rule,
                                                  short_conv)
from deeplearning4j_tpu.ops.pallas.kda_chunk import (kda_chunk,
                                                     kda_chunk_bwd)
from deeplearning4j_tpu.train.trainer import make_loss_fn

BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
SEQ, BATCH, SEED = 128, 2, 11
LOSS_LIMIT, LOGIT_LIMIT, GAP_LIMIT = 1e-5, 1e-4, 1e-4
KINDS = ["kda", "kda", "kda", "mla", "kda"]


def _load(kind: str, name: str):
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}", os.path.join(BENCHMARK, kind,
                                                 f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def small_config(**changes) -> dict:
    """The cell's configuration file at the sizes ISSUE 40 names for the
    CPU: hidden 64; KDA 4 heads of 16 behind 4 taps; latent attention 4
    heads at 16+8 / 16 over a rank of 16; 16 experts top-4 of width 32
    and a shared one; dense 128; 256 ids; layers 1-5 as in the cell."""
    with open(os.path.join(BENCHMARK, "configs",
                           "kimi_linear_48b_a3b.json")) as f:
        config = json.load(f)
    config.update(hidden_size=64, num_attention_heads=4, kv_lora_rank=16,
                  qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                  intermediate_size=128, moe_intermediate_size=32,
                  num_experts=16, experts_held=16, first_expert=0,
                  num_experts_per_token=4, vocab_size=256)
    config["linear_attn_config"] = dict(config["linear_attn_config"],
                                        num_heads=4, head_dim=16)
    config["model"] = {"vocab_size": 256}
    config["optimizer"] = dict(config["optimizer"], learning_rate=1e-3)
    config["precision"] = {"params": "float32", "compute": "float32",
                           "activations": "float32"}
    config.update(changes)
    return config


@pytest.fixture(scope="module")
def reference():
    return _load("reference", "kimi_linear")


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
    entry = _load("entries", "hybrid_lm_fit").make(
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


# ---- (a) logits and loss ------------------------------------------------------
def test_logits_and_loss_match_the_reference(reference, twin, float32_policy):
    config, weights, net = twin
    assert [kind for _, kind, _ in reference.block_names(config)] == KINDS
    tokens = jnp.asarray(_tokens(config))
    want = jax.jit(functools.partial(
        reference.loss_fn, config=config, precision="f32"))(
        weights, tokens, jnp.ones((BATCH,)), jnp.ones((SEQ,)))
    got, _ = jax.jit(make_loss_fn(net))(
        net.params_, net.state_, tokens, tokens, None, None,
        jax.random.key(0))
    assert abs(float(got) - float(want)) / float(want) < LOSS_LIMIT
    logits = net.output(tokens)
    ref_logits = jax.jit(functools.partial(reference.logits, config=config))(
        weights, tokens)
    assert logits.shape == (BATCH, SEQ, config["vocab_size"])
    assert float(jnp.max(jnp.abs(ref_logits))) > 0.1
    assert float(jnp.max(jnp.abs(logits - ref_logits))) < LOGIT_LIMIT


# ---- (b) three net.fit steps through compare.gaps ----------------------------
def test_three_fit_steps_match_first_steps(reference, float32_policy):
    config = small_config(experts_held=4, first_expert=8)
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
    assert want["losses"][2] < want["losses"][0]        # it trains
    # the routing counters PR 38 added read here as for JoyAI: a step whose
    # loss the listener read folds the pairs of the four routed blocks
    from deeplearning4j_tpu.obs.registry import (MetricsRegistry,
                                                 get_registry, set_registry)

    class Reads:
        def iteration_done(self, model, iteration, epoch, loss):
            float(loss)

    was = set_registry(MetricsRegistry())
    try:
        entry.net.fit(iter([entry.to_batch(a) for a in arrays[:2]]),
                      listeners=[Reads()])
        seen = get_registry().counter("tpudl_moe_tokens_total").value
        pairs = get_registry().counter("tpudl_moe_pairs_total").value
    finally:
        set_registry(was)
    assert seen == 2 * 4 * BATCH * SEQ
    assert 0 < pairs <= seen * config["num_experts_per_token"]


# ---- (c) the chunked scan against the token recurrence -------------------------
def _scan_inputs(t, decay, heads=2, d=16, least=0.05):
    """Unit keys, queries over sqrt(d), a per-step log decay between
    ``decay`` and ``least`` of it (a twentieth)."""
    ks = jax.random.split(jax.random.key(t), 5)
    q, k = (jax.random.normal(kk, (1, t, heads, d)) for kk in ks[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (1, t, heads, d))
    g = decay * jax.random.uniform(ks[3], (1, t, heads, d), minval=least)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, t, heads)))
    return q, k, v, g, beta


@functools.lru_cache(maxsize=None)
def _both_rules(chunk):
    """(chunked, recurrence), each jitted once: outputs, the last state
    and the gradient of a weighted sum of both by every input."""
    def values_and_grads(rule):
        def total(q, k, v, g, beta, weight):
            o, last = rule(q, k, v, g, beta)
            return jnp.sum(o * weight) + jnp.sum(last * last), (o, last)
        return jax.jit(jax.value_and_grad(total, argnums=(0, 1, 2, 3, 4),
                                          has_aux=True))
    reference = _load("reference", "kimi_linear")
    # the two heads as two groups of one at a chunk of 16, as one group of
    # two at 64
    return (values_and_grads(functools.partial(
        chunked_delta_rule, chunk=chunk, head_group=1 if chunk == 16 else 2)),
        values_and_grads(reference.delta_rule))


@functools.lru_cache(maxsize=None)
def _kernel_rule(chunk):
    """(the rule with its chunk phase run by ``tpudl_kda_chunk``, interpret
    mode here: the phase's six results and the rule's outputs and last
    state; ``_chunk_phase``; the recurrence), each jitted once."""
    def rule(q, k, v, g, beta):
        phase = kda_chunk(q, k, v, g, beta, chunk=chunk,
                          head_group=q.shape[2], compute_dtype=jnp.float32)
        return phase, _scan_and_read(*phase, t=k.shape[1])
    return (jax.jit(rule), jax.jit(functools.partial(_chunk_phase,
                                                     chunk=chunk)),
            jax.jit(_load("reference", "kimi_linear").delta_rule))


def _close(got, want):
    """Finite, and equal to 1e-5 of the array's largest entry (of 1 where
    that is smaller)."""
    assert bool(jnp.all(jnp.isfinite(got)))
    scale = max(1.0, float(jnp.max(jnp.abs(want))))
    np.testing.assert_allclose(got, want, atol=1e-5 * scale, rtol=0)


@pytest.mark.parametrize("decay", [-0.001, -1.6, -16.0])
@pytest.mark.parametrize("t", [128, 100], ids=["whole_chunks", "ragged"])
@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("phase", ["jnp", "kernel"])
def test_chunked_scan_equals_the_token_recurrence(float32_policy, phase,
                                                  chunk, t, decay):
    """Outputs, the last state and ``jax.grad`` of every input, at decays
    from next to none to 16 nats a step (64 steps of which no
    factorised ``exp(-G)`` survives in float32): finite, and equal to
    1e-5 of the array's largest entry (of 1 where that is smaller).  A
    length that is no multiple of the chunk is padded with tokens that
    change nothing.

    ``kernel``: the chunk phase as ``tpudl_kda_chunk`` at a head size of
    128, its six results against ``_chunk_phase``'s and the outputs and
    last state through the scan against the recurrence (its gradient,
    ``tpudl_kda_chunk_bwd``: ``test_kernel_backward_equals_the_jnp_...``)."""
    if phase == "kernel":
        x = _scan_inputs(t, decay, d=128)
        rule, reference_phase, recurrence = _kernel_rule(chunk)
        got_phase, got_out = rule(*x)
        for got, want in zip(got_phase, reference_phase(*x)):
            assert got.shape == (1,) + want.shape and got.dtype == want.dtype
            _close(got[0], want)
        want_out = recurrence(*x)
        # unit keys of 128 overlap less than of 16: smaller outputs
        assert float(jnp.max(jnp.abs(want_out[0]))) > 0.01
        for got, want in zip(got_out, want_out):
            _close(got, want)
        return
    x = _scan_inputs(t, decay)
    weight = jax.random.normal(jax.random.key(9), x[2].shape)
    chunked, recurrence = _both_rules(chunk)
    (_, got_out), got_grads = chunked(*x, weight)
    (_, want_out), want_grads = recurrence(*x, weight)
    assert float(jnp.max(jnp.abs(want_out[0]))) > 0.1
    for got, want in zip((*got_out, *got_grads), (*want_out, *want_grads)):
        _close(got, want)


def test_chunk_has_to_be_a_power_of_two():
    with pytest.raises(ValueError, match="power of two"):
        chunked_delta_rule(*_scan_inputs(48, -0.1), chunk=48)


def _equal_keys(t=64, d=128):
    """One key for every token, the query half of it, no decay, ``beta``
    1: ``A`` all ones below the diagonal, whose powers reach 1e17."""
    k = jnp.zeros((1, t, 1, d)).at[..., 0].set(1.0)
    v = jax.random.normal(jax.random.key(0), (1, t, 1, d))
    return 0.5 * k, k, v, jnp.zeros_like(k), jnp.ones((1, t, 1))


@pytest.mark.parametrize("phase", ["jnp", "kernel"])
def test_equal_keys_without_decay_invert_exactly(float32_policy, phase):
    """``A`` all ones below the diagonal, whose powers reach 1e17: the
    inversion by doubling takes none, and the outputs stay the
    recurrence's.  With ``beta`` 1 and no decay every token overwrites
    what the one key holds, so ``o_t = v_t (k . q)``.  ``kernel``: the
    same through ``tpudl_kda_chunk``'s doubling on the whole tile, at a
    head size of 128."""
    x = _equal_keys(d=16 if phase == "jnp" else 128)
    if phase == "jnp":
        o, _ = jax.jit(functools.partial(chunked_delta_rule, chunk=64))(*x)
    else:
        _, (o, _) = _kernel_rule(64)[0](*x)
    np.testing.assert_allclose(o, 0.5 * x[2], atol=1e-6)


# (inputs, chunk, heads a group) for the backward kernel
_BWD_CASES = {
    "random": (lambda: _scan_inputs(128, -0.1, d=128), 64, 2),
    "equal_keys_no_decay": (_equal_keys, 64, 1),
    # -1.44 to -1.6 a step: exp(-G) overflows float32 within a chunk of 64
    "strongest_decay": (lambda: _scan_inputs(128, -1.6, d=128, least=0.9),
                        64, 2),
    "ragged": (lambda: _scan_inputs(100, -0.1, d=128), 16, 2),
    "head_groups": (lambda: _scan_inputs(128, -0.1, heads=4, d=128), 64, 2),
}


def _phase_vjp(x, cts, chunk, head_group, policy):
    """``jax.vjp`` of ``_chunk_phase`` by groups of ``head_group`` heads
    under ``policy``, and of ``tpudl_kda_chunk_bwd`` (interpret mode),
    from the cotangents ``cts`` (cast to each result's dtype)."""
    was = dtype_policy()
    set_dtype_policy(policy)
    try:
        results, pull = jax.vjp(lambda *x: _map_head_groups(
            functools.partial(_chunk_phase, chunk=chunk), x, (2,) * 5,
            head_group), *x)
        cts = tuple(c.astype(r.dtype) for c, r in zip(cts, results))
        return jax.jit(pull)(cts), jax.jit(functools.partial(
            kda_chunk_bwd, chunk=chunk, head_group=head_group,
            compute_dtype=policy.compute_dtype))(*x, cts)
    finally:
        set_dtype_policy(was)


@pytest.mark.parametrize("policy", ["f32", "bf16"])
@pytest.mark.parametrize("case", list(_BWD_CASES))
def test_kernel_backward_equals_the_jnp_phase(case, policy):
    """``tpudl_kda_chunk_bwd`` against ``jax.vjp`` of ``_chunk_phase`` by
    the same groups of heads, from the same random cotangents of the six
    results (drawn in bfloat16, so that both policies read the same
    values): the cotangents of ``q``, ``k``, ``v``, ``g`` and ``beta`` are
    finite and, under the float32 policy, equal to 1e-5 of each array's
    largest entry (read when written: 4e-6 at most).  Under the bfloat16
    policy both round the products' operands: the kernel's distance from
    the float32 ``jax.numpy`` result, as a norm, is at most half that
    path's own under bfloat16 (read: 0.22 at most; with its products'
    operands rounded once, as the forward's, ``g``'s read 1.3-2.0), and
    under 1e-2 of the largest entry."""
    make, chunk, head_group = _BWD_CASES[case]
    x = make()
    shapes = jax.eval_shape(lambda *x: _map_head_groups(
        functools.partial(_chunk_phase, chunk=chunk), x, (2,) * 5,
        head_group), *x)
    cts = tuple(jax.random.normal(jax.random.key(20 + i), r.shape)
                .astype(jnp.bfloat16) for i, r in enumerate(shapes))
    want, got = _phase_vjp(x, cts, chunk, head_group, DTypePolicy.f32())
    if policy == "bf16":
        jnp_bf16, got = _phase_vjp(x, cts, chunk, head_group,
                                   DTypePolicy.bf16())
    for i, name in enumerate(("q", "k", "v", "g", "beta")):
        assert got[i].shape == want[i].shape, name
        assert got[i].dtype == jnp.float32, name
        assert bool(jnp.all(jnp.isfinite(got[i]))), name
        scale = max(1.0, float(jnp.max(jnp.abs(want[i]))))
        gap = float(jnp.max(jnp.abs(got[i] - want[i])))
        if policy == "f32":
            assert gap <= 1e-5 * scale, (name, gap, scale)
        else:
            assert gap < 1e-2 * scale, (name, gap, scale)
            norm, own = (float(jnp.linalg.norm(y[i] - want[i]))
                         for y in (got, jnp_bf16))
            assert norm <= 0.5 * own, (name, norm, own)


def test_layer_with_the_kernel_equals_the_jnp_path(float32_policy,
                                                   monkeypatch):
    """``DeltaAttention`` at a head size of 128 takes both kernels (the
    forward, and the backward from the projections' outputs): its outputs
    and ``jax.grad`` of every parameter, each by name, and of the input
    equal the ``jax.numpy`` path's, a ragged length in chunks of 16, two
    heads, one group."""
    layer = DeltaAttention(n_heads=2, head_dim=128, chunk=16)
    t = 100
    params = layer.init_params(jax.random.key(0), InputType.recurrent(64, t))
    x = jax.random.normal(jax.random.key(1), (BATCH, t, 64))
    weight = jax.random.normal(jax.random.key(2), (BATCH, t, 64))

    def run():
        def total(params, x):
            out = layer.apply(params, {}, x)[0]
            return jnp.sum(out * weight), out
        return jax.jit(jax.value_and_grad(total, argnums=(0, 1),
                                          has_aux=True))(params, x)

    assert layer.kernel == "tpudl_kda_chunk"
    assert layer.bwd_kernel == "tpudl_kda_chunk_bwd"
    (_, got_out), (got_params, got_x) = run()
    monkeypatch.setattr(DeltaAttention, "kernel", property(lambda _: None))
    assert layer.bwd_kernel is None
    (_, want_out), (want_params, want_x) = run()
    assert float(jnp.max(jnp.abs(want_out))) > 1e-3
    _close(got_out, want_out)
    _close(got_x, want_x)
    assert sorted(got_params) == sorted(want_params) == sorted(params)
    for name in params:
        # every parameter's gradient is there to compare
        assert float(jnp.max(jnp.abs(want_params[name]))) > 0, name
        _close(got_params[name], want_params[name])


# ---- (d) the short convolution, and the block's causality ----------------------
def test_short_conv_is_the_four_term_sum_and_causal():
    x = jax.random.normal(jax.random.key(1), (2, 12, 6))
    w = jax.random.normal(jax.random.key(2), (6, 4))
    got = short_conv(x, w)
    for t in (0, 2, 11):
        want = sum(w[:, j] * (x[:, t - 3 + j] if t - 3 + j >= 0 else 0.0)
                   for j in range(4))
        np.testing.assert_allclose(got[:, t], jax.nn.silu(want), rtol=1e-6,
                                   atol=1e-6)
    moved = short_conv(x.at[:, -1].add(5.0), w)
    np.testing.assert_array_equal(moved[:, :-1], got[:, :-1])
    assert bool(jnp.all(moved[:, -1] != got[:, -1]))


def test_no_position_reads_a_later_one(twin, float32_policy):
    """The whole model, KDA and latent blocks alike: another last token
    moves no earlier logit."""
    config, _, net = twin
    tokens = jnp.asarray(_tokens(config))
    other = tokens.at[:, -1].set((tokens[:, -1] + 7) % config["vocab_size"])
    before, after = net.output(tokens), net.output(other)
    np.testing.assert_array_equal(before[:, :-1], after[:, :-1])
    assert float(jnp.max(jnp.abs(before[:, -1] - after[:, -1]))) > 1e-4


# ---- (e) latent attention without a query rank or positions --------------------
def test_latent_attention_without_query_rank_or_rotation(reference, twin,
                                                         float32_policy):
    config, weights, _ = twin
    layer = LatentAttention(n_heads=4, q_lora_rank=0, kv_lora_rank=16,
                            qk_nope_head_dim=16, qk_rope_head_dim=8,
                            v_head_dim=16, rotary=False, eps=1e-5)
    params = layer.init_params(jax.random.key(0),
                               InputType.recurrent(64, SEQ))
    assert sorted(params) == ["W_kva", "W_kvb", "W_o", "W_q", "kv_norm"]
    mine = {name: weights[f"l4.attn.{name}"] for name in params}
    a = jax.random.normal(jax.random.key(3), (BATCH, SEQ, 64))
    def apply(layer, a):
        return jax.jit(lambda a: layer.apply(mine, {}, a)[0])(a)

    got = apply(layer, a)
    want = jax.jit(lambda a: reference._mla(weights, "l4", a, config,
                                            lambda x: x))(a)
    assert float(jnp.max(jnp.abs(want))) > 1e-3
    assert float(jnp.max(jnp.abs(got - want))) < 1e-6
    # no position: the tokens before a position may come in any order
    swapped = a.at[:, 0].set(a[:, 1]).at[:, 1].set(a[:, 0])
    np.testing.assert_allclose(apply(layer, swapped)[:, 2:], got[:, 2:],
                               atol=1e-6)
    rotated = apply(dataclasses.replace(layer, rotary=True), a)
    assert float(jnp.max(jnp.abs(rotated - got))) > 1e-4


# ---- (f) the share test at this model's routing numbers ------------------------
def test_the_shares_add_up_to_the_uncut_layer(reference, twin,
                                              float32_policy):
    """Shares of 4 experts from ``first_expert`` 0, 4, 8, 12 at scaling
    2.446 give routed parts that, with the shared expert counted once,
    add up to what the uncut reference layer gives."""
    config, weights, net = twin
    assert config["routed_scaling_factor"] == 2.446
    pre = "l2"
    f = jax.random.normal(jax.random.key(3), (BATCH, SEQ, 64))
    whole = reference._routed(weights, pre, f, config, lambda a: a)
    shared = reference._swiglu(
        f, weights[f"{pre}.ffn.shared_W_gate"],
        weights[f"{pre}.ffn.shared_W_up"],
        weights[f"{pre}.ffn.shared_W_down"], lambda a: a)
    layer = {v.name: v.obj for v in net.conf.vertices}["l2_ffn"]
    params = net.params_["l2_ffn"]
    total, pairs = shared, 0.0
    for first in (0, 4, 8, 12):
        share = copy.copy(layer)
        share.experts_held, share.first_expert = 4, first
        mine = dict(params, **{name: params[name][first:first + 4]
                               for name in ("W_gate", "W_up", "W_down")})
        out, state = share.apply(mine, layer.init_state(None), f)
        total = total + (out - shared)
        pairs += float(state["moe_pairs"])
    assert float(jnp.max(jnp.abs(total - whole))) < LOGIT_LIMIT
    assert pairs == BATCH * SEQ * config["num_experts_per_token"]


# ---- (g) what the fit span says -------------------------------------------------
def test_trace_attrs_carry_the_attention_kinds_and_the_chunk():
    net = kimi_linear(small_config(), SEQ, seed=SEED, kda_chunk=32)
    attrs = net.trace_attrs()
    assert attrs["attention_kinds"] == KINDS
    assert attrs["kda_chunk"] == 32
    assert attrs["remat_runs"] == 10       # a block is two runs
    assert "kda_kernel" not in attrs       # heads of 16: the jnp path
    assert "kda_bwd_kernel" not in attrs
    config = small_config()
    config["linear_attn_config"] = dict(config["linear_attn_config"],
                                        head_dim=128)
    attrs = kimi_linear(config, SEQ, seed=SEED).trace_attrs()
    assert attrs["kda_kernel"] == "tpudl_kda_chunk"
    assert attrs["kda_bwd_kernel"] == "tpudl_kda_chunk_bwd"
    attrs = resnet50(height=32, width=32, num_classes=10).trace_attrs()
    assert "attention_kinds" not in attrs and "kda_chunk" not in attrs
    assert "kda_kernel" not in attrs and "kda_bwd_kernel" not in attrs


def test_the_layer_starts_inside_the_stated_decays():
    layer = DeltaAttention(n_heads=4, head_dim=16)
    params = layer.init_params(jax.random.key(0),
                               InputType.recurrent(64, SEQ))
    assert params["W_fb"].shape == (16, 64) and params["conv_k"].shape == (64,
                                                                           4)
    step = jax.nn.softplus(params["dt_bias"])
    assert 0.001 <= float(step.min()) and float(step.max()) <= 0.1 + 1e-6
    decay = -jnp.exp(params["A_log"])[:, None] * step.reshape(4, 16)
    assert -1.6 - 1e-5 <= float(decay.min()) and float(decay.max()) <= -0.001
