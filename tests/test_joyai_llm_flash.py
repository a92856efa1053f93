"""The zoo's JoyAI-LLM-Flash graph against an independent reference.

``models.joyai_llm_flash`` (latent attention, dropless sigmoid-routed
experts beside a shared expert, the multi-token-prediction module, two
losses over one head) at a small size on the CPU under the float32
policy, against ``benchmark/reference/joyai_llm_flash.py``: plain
``jax.numpy`` that imports nothing of the program, read from
``benchmark/`` by path as ``test_resnet_reference.py`` reads its own.

Tolerances.  Both sides are float32 on the CPU and compute the same
equations in another order (the reference's experts are a dense masked
sum, the program's a sort and grouped products), so they differ by
float32 rounding: read when written 1e-7 to 5e-6 on every number here.
The limits below are some twenty times that, and a thousand times under
what one mis-routed expert, a shift off by one or a bfloat16 matmul
reads (1e-2 and up).
"""

import copy
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
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.models import joyai_llm_flash
from deeplearning4j_tpu.nn.layers.decoder import (dropless_experts, route,
                                                  swiglu)
from deeplearning4j_tpu.ops.attention import multi_head_attention
from deeplearning4j_tpu.train import Adam
from deeplearning4j_tpu.train.trainer import make_loss_fn

BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
SEQ, BATCH, SEED = 64, 2, 11
LOSS_LIMIT, LOGIT_LIMIT, GAP_LIMIT = 1e-5, 1e-4, 1e-4


def _load(kind: str, name: str):
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}", os.path.join(BENCHMARK, kind,
                                                 f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def small_config(**changes) -> dict:
    """The cell's configuration file at the sizes ISSUE 38 names for the
    CPU: hidden 64, 4 heads at 16+8 / 16, lora ranks 32/16, 16 experts
    top-4 of width 32, vocabulary 256, 1 dense + 2 routed blocks + MTP."""
    with open(os.path.join(BENCHMARK, "configs",
                           "joyai_llm_flash.json")) as f:
        config = json.load(f)
    config.update(hidden_size=64, num_attention_heads=4, q_lora_rank=32,
                  kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
                  v_head_dim=16, intermediate_size=128,
                  moe_intermediate_size=32, n_routed_experts=16,
                  experts_held=16, first_expert=0, num_experts_per_tok=4,
                  num_hidden_layers=3, vocab_size=256)
    config["model"] = {"vocab_size": 256}
    config["optimizer"] = dict(config["optimizer"], learning_rate=1e-3)
    config["precision"] = {"params": "float32", "compute": "float32",
                           "activations": "float32"}
    config.update(changes)
    return config


@pytest.fixture(scope="module")
def reference():
    return _load("reference", "joyai_llm_flash")


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
    entry = _load("entries", "causal_lm_fit").make(
        config, {"seq": SEQ, "loss_every": 1})
    entry.build(weights, SEED)
    return entry


def _tokens(config, seed=SEED, batch=BATCH, seq=SEQ):
    return np.random.default_rng(seed).integers(
        0, config["vocab_size"], (batch, seq), dtype=np.int32)


# ---- (a) logits and both losses ---------------------------------------------
@pytest.mark.parametrize("mtp", [1, 0], ids=["mtp", "no_mtp"])
def test_logits_and_losses_match_the_reference(reference, float32_policy,
                                               mtp):
    config = small_config(num_nextn_predict_layers=mtp)
    weights = reference.init_weights(config, SEED)
    net = _entry(config, weights).net
    tokens = jnp.asarray(_tokens(config))
    want = reference.loss_fn(weights, tokens, jnp.ones((BATCH,)),
                             jnp.ones((SEQ,)), config=config,
                             precision="f32")
    got, _ = make_loss_fn(net)(net.params_, net.state_, tokens, tokens, None,
                               None, jax.random.key(0))
    assert abs(float(got) - float(want)) / float(want) < LOSS_LIMIT
    # the main stream's logits: the reference's head on its own last block
    logits = net.output(tokens)
    x = weights["emb.w"][tokens]
    for pre, moe in reference.block_names(config):
        x = reference._run_block(weights, x, pre, moe, config, lambda a: a)
    ref_logits = reference._rms_norm(
        x, weights["final_norm.g"], config["rms_norm_eps"],
        lambda a: a) @ weights["head.w"]
    assert logits.shape == (BATCH, SEQ, config["vocab_size"])
    assert float(jnp.max(jnp.abs(logits - ref_logits))) < LOGIT_LIMIT
    if mtp:     # the second loss is there and weighs what the file says
        without = reference.loss_fn(
            weights, tokens, jnp.ones((BATCH,)), jnp.ones((SEQ,)),
            precision="f32",
            config=dict(config, num_nextn_predict_layers=0))
        assert 0.2 * float(without) < float(want) - float(without) \
            < 0.4 * float(without)


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


# ---- (c) the share test, (d) dropless under skew ------------------------------
def _expert_layer(key, n=96, d=32, experts=16, width=24, k=4):
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (n, d))
    logits = jax.random.normal(ks[1], (n, experts))
    w = [0.2 * jax.random.normal(kk, shape) for kk, shape in zip(
        ks[2:], [(experts, d, width), (experts, d, width),
                 (experts, width, d)])]
    return x, logits, w, k


def _dense_masked_sum(x, chosen, gates, w, experts):
    """Every expert in ``experts`` over every token, times its gate or 0."""
    out = jnp.zeros_like(x)
    for e in experts:
        gate = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1)
        out = out + gate[:, None] * swiglu(x, w[0][e], w[1][e], w[2][e])
    return out


def test_the_shares_add_up_to_the_uncut_layer(reference, float32_policy):
    """Shares ``first_expert`` = 0, 4, 8, 12 of ``experts_held`` = 4 give
    routed parts that, with the shared expert counted once, add up to
    what the uncut reference layer gives."""
    config = small_config()
    weights = reference.init_weights(config, SEED)
    pre = "l1"
    f = jax.random.normal(jax.random.key(3), (BATCH, SEQ, 64))
    whole = reference._routed(weights, pre, f, config, lambda a: a)
    shared = reference._swiglu(
        f, weights[f"{pre}.shared.gate.w"], weights[f"{pre}.shared.up.w"],
        weights[f"{pre}.shared.down.w"], lambda a: a)
    net = _entry(config, weights).net
    layer, params = net.conf.vertices, net.params_["l1_ffn"]
    layer = {v.name: v.obj for v in layer}["l1_ffn"]
    total, pairs = shared, 0.0
    for first in (0, 4, 8, 12):
        share = copy.copy(layer)
        share.experts_held, share.first_expert = 4, first
        mine = dict(params, **{name: params[name][first:first + 4]
                               for name in ("W_gate", "W_up", "W_down")})
        out, state = share.apply(mine, layer.init_state(None), f)
        total = total + (out - shared)
        pairs += float(state["moe_pairs"])
        # the reference given the same share computes the same part
        cut = dict(config, experts_held=4, first_expert=first)
        cut_w = dict(weights, **{
            f"{pre}.experts.{n}": weights[f"{pre}.experts.{n}"][first:first + 4]
            for n in ("gate", "up", "down")})
        want = reference._routed(cut_w, pre, f, cut, lambda a: a)
        assert float(jnp.max(jnp.abs(out - want))) < LOGIT_LIMIT
    assert float(jnp.max(jnp.abs(total - whole))) < LOGIT_LIMIT
    # every pair was computed by exactly one share: nothing dropped
    assert pairs == BATCH * SEQ * config["num_experts_per_tok"]


@pytest.mark.parametrize("held, first", [(16, 0), (4, 0), (4, 12)])
def test_dropless_under_skew(float32_policy, held, first):
    """A batch in which one expert takes most pairs loses no token and
    matches the dense masked sum."""
    x, logits, w, k = _expert_layer(jax.random.key(5))
    logits = logits.at[:, 1].add(8.0).at[:80, 13].add(6.0)   # the skew
    chosen, gates = route(logits, jnp.zeros((16,)), top_k=k, scale=2.5)
    assert int(jnp.sum(chosen == 1)) == x.shape[0]      # every token
    cut = [m[first:first + held] for m in w]
    got, sizes = dropless_experts(x, chosen, gates, *cut, first_expert=first)
    want = _dense_masked_sum(x, chosen - first, gates, cut, range(held))
    assert float(jnp.max(jnp.abs(got - want))) < LOGIT_LIMIT
    in_share = (chosen >= first) & (chosen < first + held)
    assert int(jnp.sum(sizes)) == int(jnp.sum(in_share))
    assert int(jnp.max(sizes)) >= (x.shape[0] if first <= 1 else 80)
    # and the gradient reaches every pair's gate and token
    grads = jax.grad(lambda g: jnp.sum(dropless_experts(
        x, chosen, g, *cut, first_expert=first)[0]))(gates)
    assert bool(jnp.all((grads != 0) == in_share))


def test_rows_past_the_pairs_may_hold_anything(float32_policy, monkeypatch):
    """The chip's grouped product never writes the buffer's rows past the
    pairs (NaN on the first chip run, PR 38): neither the sum nor any
    gradient may read them."""
    from deeplearning4j_tpu.nn.layers import decoder
    real = jax.lax.ragged_dot

    def unwritten(a, w, sizes):
        past = jnp.arange(a.shape[0])[:, None] >= jnp.sum(sizes)
        return jnp.where(past, jnp.nan, real(a, w, sizes))

    x, logits, w, k = _expert_layer(jax.random.key(6))
    chosen, gates = route(logits, jnp.zeros((16,)), top_k=k, scale=2.5)
    cut = [m[:4] for m in w]

    def total(x, gates, *cut):
        return jnp.sum(dropless_experts(x, chosen, gates, *cut,
                                        first_expert=0)[0] ** 2)

    want = jax.value_and_grad(total, (0, 1, 2, 3, 4))(x, gates, *cut)
    monkeypatch.setattr(decoder.jax.lax, "ragged_dot", unwritten)
    got = jax.value_and_grad(total, (0, 1, 2, 3, 4))(x, gates, *cut)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_route_is_sigmoid_top_k_with_a_selection_bias():
    logits = jnp.array([[2.0, 1.0, 0.0, -1.0]])
    chosen, gates = route(logits, jnp.zeros((4,)), top_k=2, scale=2.5)
    s = jax.nn.sigmoid(logits[0])
    assert sorted(chosen[0].tolist()) == [0, 1]
    np.testing.assert_allclose(sorted(gates[0].tolist()), sorted(
        (2.5 * s[:2] / (s[0] + s[1])).tolist()), rtol=1e-6)
    # the bias moves the choice and not the gate
    chosen, gates = route(logits, jnp.array([0.0, 0.0, 0.0, 5.0]), top_k=2,
                          scale=1.0, normalize=False)
    assert sorted(chosen[0].tolist()) == [0, 3]
    np.testing.assert_allclose(sorted(gates[0].tolist()),
                               sorted([float(s[0]), float(s[3])]), rtol=1e-6)


# ---- (e) the flash kernel with a value head size of its own -------------------
@pytest.mark.parametrize("qk, dv", [(16 + 8, 16), (128 + 64, 128)])
def test_flash_kernel_takes_a_value_head_size_of_its_own(qk, dv):
    """Forward and ``jax.grad`` of the Pallas kernels (interpret mode)
    against the einsum chain, causal.  float32 in and out: the two differ
    by the online softmax's rounding, 1e-6 read, 2e-5 allowed."""
    heads, t = 2, 48
    ks = jax.random.split(jax.random.key(qk), 4)
    q, k = (jax.random.normal(kk, (1, t, heads * qk)) for kk in ks[:2])
    v = jax.random.normal(ks[2], (1, t, heads * dv))
    weight = jax.random.normal(ks[3], (1, t, heads * dv))

    def loss(flash):
        return lambda q, k, v: jnp.sum(weight * multi_head_attention(
            q, k, v, n_heads=heads, causal=True, use_flash=flash,
            flash_block=16))

    chain, chain_grads = jax.value_and_grad(loss(False), (0, 1, 2))(q, k, v)
    flash, flash_grads = jax.value_and_grad(loss(True), (0, 1, 2))(q, k, v)
    assert abs(float(chain) - float(flash)) < 2e-5 * abs(float(chain)) + 2e-5
    for a, b in zip(chain_grads, flash_grads):
        assert a.shape == b.shape
        assert float(jnp.max(jnp.abs(a - b))) < 2e-5
    out = multi_head_attention(q, k, v, n_heads=heads, causal=True,
                               use_flash=True, flash_block=16)
    assert out.shape == (1, t, heads * dv)


def test_the_two_kernel_backward_refuses_a_value_head_size_of_its_own():
    """Only the merged backward, the one ``jax.grad`` takes, was widened;
    the two-kernel form nothing calls says so instead of mis-shaping."""
    from deeplearning4j_tpu.ops.pallas.flash_attention import (
        flash_attention_block_bwd)
    q = k = jnp.zeros((1, 2, 16, 24))
    v = out = jnp.zeros((1, 2, 16, 16))
    with pytest.raises(ValueError, match="merged=False"):
        flash_attention_block_bwd(q, k, v, out, jnp.zeros((1, 2, 16)), out,
                                  scale=1.0, block_q=16, block_k=16,
                                  interpret=True, merged=False)


# ---- (f) the MTP label shift at the sequence's end ----------------------------
def test_mtp_label_shift_at_the_sequences_end(reference, float32_policy):
    """Stream 0 at position i is scored against t_{i+1} and stream 1
    against t_{i+2}; the last one and two positions have no target: the
    loss does not move when they (or what wrapped around) change."""
    config = small_config()
    net = _entry(config, reference.init_weights(config, SEED)).net
    head = {v.name: v.obj for v in net.conf.vertices}["lm_head"]
    params = net.params_["lm_head"]
    h = jax.random.normal(jax.random.key(9), (2 * BATCH, SEQ, 64))
    tokens = jnp.asarray(_tokens(config))
    score = head.compute_score_array(params, {}, h, tokens)
    logp = jax.nn.log_softmax(h @ params["W"], axis=-1)
    main = -jnp.mean(jnp.take_along_axis(
        logp[:BATCH, :-1], tokens[:, 1:, None], axis=-1)[..., 0], axis=1)
    mtp = -jnp.mean(jnp.take_along_axis(
        logp[BATCH:, :-2], tokens[:, 2:, None], axis=-1)[..., 0], axis=1)
    np.testing.assert_allclose(score, main + head.mtp_weight * mtp,
                               rtol=1e-5)
    # the hidden states past the last target, and the first two ids (which
    # a wrapped shift would read as targets), change nothing
    moved = h.at[:BATCH, -1].add(3.0).at[BATCH:, -2:].add(3.0)
    np.testing.assert_allclose(
        head.compute_score_array(params, {}, moved, tokens), score, rtol=1e-6)
    other = tokens.at[:, 0].set((tokens[:, 0] + 7) % config["vocab_size"])
    np.testing.assert_allclose(
        head.compute_score_array(params, {}, h, other), score, rtol=1e-6)
    # the MTP module's own input: the embedded sequence one token ahead
    shift = {v.name: v.obj for v in net.conf.vertices}["mtp_next"]
    emb = jnp.arange(2 * 5 * 3, dtype=jnp.float32).reshape(2, 5, 3)
    ahead = shift.apply([emb])
    np.testing.assert_array_equal(ahead[:, :-1], emb[:, 1:])
    np.testing.assert_array_equal(ahead[:, -1], jnp.zeros((2, 3)))


# ---- the loop: counters, remat, the other graphs' steps -----------------------
def test_fit_folds_the_routing_counters_only_on_read_steps(float32_policy):
    from deeplearning4j_tpu.obs.registry import (MetricsRegistry,
                                                 get_registry, set_registry)
    config = small_config(experts_held=4, first_expert=4)
    net = joyai_llm_flash(config, SEQ, seed=SEED, updater=Adam(1e-3))
    tokens = _tokens(config)
    batches = [DataSet(tokens, tokens)] * 4

    class ReadsEveryOther:
        def iteration_done(self, model, iteration, epoch, loss):
            if iteration % 2 == 1:
                float(loss)

    class ReadsNothing:
        def iteration_done(self, model, iteration, epoch, loss):
            pass

    was = set_registry(MetricsRegistry())
    try:
        net.fit(iter(batches), listeners=[ReadsNothing()])
        jax.block_until_ready(net.params_)
        unread = get_registry().counter("tpudl_moe_tokens_total").value
        net.fit(iter(batches), listeners=[ReadsEveryOther()])
        registry = get_registry()
        tokens_seen = registry.counter("tpudl_moe_tokens_total").value - unread
        pairs = registry.counter("tpudl_moe_pairs_total").value
        busiest = registry.counter("tpudl_moe_pairs_max_expert_total").value
    finally:
        set_registry(was)
    routed_layers = 3                      # l1, l2 and the MTP block
    per_step = routed_layers * BATCH * SEQ
    # a step nobody read may still be folded if the device was done by
    # then; the two read steps always are
    assert tokens_seen >= 2 * per_step and tokens_seen % per_step == 0
    assert unread % per_step == 0
    assert 0 < busiest <= pairs <= (unread + tokens_seen) * 4


FLASH_SEQ = 1024       # where ``_auto_flash`` takes the kernel (interpret mode)
RUNS = 4               # 3 blocks + MTP


def _loss_and_args(seq, batch):
    config = small_config()
    net = joyai_llm_flash(config, seq, seed=SEED)
    net.init()
    tokens = jnp.asarray(_tokens(config, batch=batch, seq=seq))
    return net, make_loss_fn(net), (net.params_, net.state_, tokens, tokens,
                                    None, None, jax.random.key(0))


def _bare_checkpoint(monkeypatch):
    """``jax.checkpoint`` without a policy, as the runs had it until PR 39:
    a run keeps its inputs and nothing else."""
    real = jax.checkpoint
    monkeypatch.setattr(jax, "checkpoint",
                        lambda fn, policy=None, **kw: real(fn, **kw))


def _lowered_grad(loss, args) -> str:
    return jax.jit(jax.value_and_grad(loss, has_aux=True)).lower(
        *args).as_text()


def _program(text: str) -> str:
    """A lowered module as one digest: every function named by its own
    text with its callees named so first, ``main`` last.  Which inner
    jitted functions a module shares (``@tril`` once, or ``@tril`` and
    ``@tril_89`` with one body) follows jax's tracing caches and whether a
    checkpoint has a policy, and is no difference in the program."""
    _, *funcs = re.split(r"\n  func\.func ", text)
    bodies = {re.match(r"(?:public |private )?@(\w+)", f).group(1): f
              for f in funcs}
    digest = {}
    while "main" not in digest:
        for name, body in bodies.items():
            if name not in digest and set(re.findall(
                    r"call @(\w+)", body)) <= digest.keys():
                body = re.sub(r"call @(\w+)",
                              lambda m: "call @" + digest[m.group(1)], body)
                digest[name] = hashlib.sha1(body.replace(
                    f"@{name}", "@", 1).encode()).hexdigest()
    return digest["main"]


@pytest.mark.parametrize("seq, batch", [(SEQ, BATCH), (FLASH_SEQ, 1)],
                         ids=["einsum_chain", "flash_kernel"])
def test_remat_runs_change_no_number(float32_policy, seq, batch):
    net, loss, args = _loss_and_args(seq, batch)
    assert len(net.conf.remat_segments) == RUNS
    (with_remat, _), grads = jax.jit(
        jax.value_and_grad(loss, has_aux=True))(*args)
    segments, net.conf.remat_segments = net.conf.remat_segments, []
    (without, _), plain = jax.jit(
        jax.value_and_grad(loss, has_aux=True))(*args)
    net.conf.remat_segments = segments
    assert float(with_remat) == pytest.approx(float(without), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(plain)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-7)
    # the runs survive the configuration's JSON
    from deeplearning4j_tpu.nn.graph import ComputationGraphConfiguration
    again = ComputationGraphConfiguration.from_json(net.conf.to_json())
    assert again.remat_segments == segments


@pytest.mark.parametrize("bare, forward_calls", [(False, RUNS),
                                                 (True, 2 * RUNS)],
                         ids=["keeps_out_and_lse", "bare_checkpoint"])
def test_a_remat_run_calls_the_flash_forward_once(float32_policy,
                                                  monkeypatch, bare,
                                                  forward_calls):
    """The count that says the policy engaged: the lowered gradient calls
    the forward kernel once a run and the merged backward once a run.
    Under a bare ``jax.checkpoint`` the backward pass rebuilds the kernel's
    output and row statistics by calling it again."""
    if bare:
        _bare_checkpoint(monkeypatch)
    _, loss, args = _loss_and_args(FLASH_SEQ, 1)
    text = _lowered_grad(loss, args)
    calls = re.findall(r"call @flash_attention_block(_bwd)?(?:_\d+)?\(", text)
    assert calls.count("") == forward_calls
    assert calls.count("_bwd") == RUNS


def test_a_run_without_a_flash_call_lowers_as_under_a_bare_checkpoint(
        float32_policy, monkeypatch):
    """Short sequences take the einsum chain: no name is in the run, the
    policy keeps nothing, and the step is the one a bare checkpoint gave."""
    net, loss, args = _loss_and_args(SEQ, BATCH)
    with_policy = _lowered_grad(loss, args)
    _bare_checkpoint(monkeypatch)
    assert "flash_attention_block" not in with_policy
    assert _program(with_policy) == _program(_lowered_grad(loss, args))
    net.conf.remat_segments = []           # and the digest tells programs apart
    assert _program(with_policy) != _program(_lowered_grad(loss, args))


def test_a_run_keeps_the_flash_output_and_row_statistics_only(
        float32_policy, monkeypatch, capsys):
    """What each run saves for its backward pass, beyond its own inputs
    and constants: the kernel's output ``[B,H,T,v_head_dim]`` and its rows'
    logsumexp ``[B,H,T]``.  Traced, not run."""
    from jax.ad_checkpoint import print_saved_residuals
    net, _, (params, state, tokens, *_) = _loss_and_args(FLASH_SEQ, 1)
    real = jax.checkpoint

    def listing(fn, **kw):
        run = real(fn, **kw)

        def call(p, s, acts, masks, rng):
            print_saved_residuals(lambda p, acts: run(p, s, acts, masks,
                                                      rng)[0], p, acts)
            print("run ends")
            return run(p, s, acts, masks, rng)
        return call if fn.__name__ == "segment" else run

    monkeypatch.setattr(jax, "checkpoint", listing)
    jax.eval_shape(lambda p: net._forward(
        p, state, tokens, train=True, rng=jax.random.key(0),
        labels=tokens)[2], params)
    *runs, rest = capsys.readouterr().out.split("run ends\n")
    assert len(runs) == RUNS and not rest
    config = small_config()
    heads, dv = config["num_attention_heads"], config["v_head_dim"]
    for run in runs:
        kept = [line.split()[0] for line in run.splitlines() if not re.search(
            r" from (the argument|a constant|a literal)", line)]
        assert sorted(kept) == sorted([f"f32[1,{heads},{FLASH_SEQ},{dv}]",
                                       f"f32[1,{heads},{FLASH_SEQ}]"]), run


def test_trace_attrs_carry_the_runs_and_what_they_keep():
    """The ``fit`` span's attributes say how many runs the step
    rematerialises and which names their checkpoints keep."""
    from deeplearning4j_tpu.models import resnet50
    from deeplearning4j_tpu.ops.pallas.flash_attention import REMAT_KEEPS
    attrs = joyai_llm_flash(small_config(), SEQ, seed=SEED).trace_attrs()
    assert attrs["remat_runs"] == RUNS
    assert attrs["remat_keeps"] == list(REMAT_KEEPS)
    attrs = resnet50(height=32, width=32, num_classes=10).trace_attrs()
    assert attrs["remat_runs"] == 0 and attrs["remat_keeps"] == []


def test_a_graph_without_runs_or_experts_keeps_its_configuration():
    """What ``step_cache`` keys a ResNet's compiled step by does not grow
    a key, and its trainer folds nothing."""
    from deeplearning4j_tpu.models import resnet50
    from deeplearning4j_tpu.train.trainer import Trainer
    net = resnet50(height=32, width=32, num_classes=10)
    assert "remat_segments" not in net.conf.to_dict()
    assert Trainer(net)._step_counters == {}
