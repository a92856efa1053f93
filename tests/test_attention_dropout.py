"""The attention probabilities' dropout and the one inverted ``dropout``.

``BertConfig.attention_dropout`` takes effect through
``multi_head_attention(dropout_rate=, dropout_rng=)``; every inverted
dropout of the program is ``ops.attention.dropout``.  What must not move
is pinned bit for bit against the formula written out here, never against
a stored array: the former callers' outputs for a fixed key, and
attention without a key, without a rate or outside training.
"""

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import bert as B
from deeplearning4j_tpu.nn.layers.core import DenseLayer
from deeplearning4j_tpu.nn.layers.extra import SpatialDropoutLayer
from deeplearning4j_tpu.nn.weight_noise import DropConnect
from deeplearning4j_tpu.ops import attention as A
from deeplearning4j_tpu.ops import namespaces as ns
from deeplearning4j_tpu.ops import pallas as pallas_mod
from deeplearning4j_tpu.ops.attention import dropout, multi_head_attention
from deeplearning4j_tpu.train import Adam


def _inverted(x, p, key, shape=None):
    """Inverted dropout as each caller wrote it out at the parent."""
    keep = jax.random.bernoulli(key, p, shape or x.shape)
    return jnp.where(keep, x / p, 0.0)


def _plain_attention(q, k, v, heads, *, mask=None, kv_mask=None,
                     causal=False, rate=0.0, key=None):
    """The einsum chain as the parent computed it, and with ``key`` the
    published dropout on the probabilities."""
    b, tq, d = q.shape
    tk, dh = k.shape[1], d // heads
    qh, kh, vh = (a.reshape(b, a.shape[1], heads, dh).transpose(0, 2, 1, 3)
                  for a in (q, k, v))
    scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) / math.sqrt(dh)
    key_mask = mask if mask is not None else kv_mask
    if key_mask is not None:
        scores = jnp.where(key_mask[:, None, None, :] > 0, scores, -1e9)
    if causal:
        scores = jnp.where(jnp.tril(jnp.ones((tq, tk), dtype=bool))[None, None],
                           scores, -1e9)
    weights = jax.nn.softmax(scores, axis=-1)
    if key is not None:
        weights = _inverted(weights, 1.0 - rate, key, (b, heads, tq, tk))
    out = jnp.einsum("bhqk,bhkd->bhqd", weights, vh)
    out = out.transpose(0, 2, 1, 3).reshape(b, tq, d)
    if mask is not None and tq == tk:
        out = out * mask[:, :, None]
    return out


def _qkv(seed, b, t, d, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(b, t, d)), dtype)
                 for _ in range(3))


def _same_bits(a, b):
    assert a.dtype == b.dtype and a.shape == b.shape
    np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                  np.asarray(b.astype(jnp.float32)))


# ------------------------------------------------- (e) the one dropout
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=str)
@pytest.mark.parametrize("shape,mask_shape", [
    ((4, 12), None),                       # Layer._maybe_dropout, nn.dropout
    ((2, 8, 64), None),                    # BERT's hidden states
    ((2, 4, 8, 8), None),                  # attention probabilities
    ((2, 5, 5, 6), (2, 1, 1, 6)),          # SpatialDropout on CNN
    ((2, 7, 6), (2, 1, 6)),                # SpatialDropout on RNN
], ids=["ff", "hidden", "probs", "spatial_cnn", "spatial_rnn"])
def test_dropout_is_the_written_out_expression(shape, mask_shape, dtype):
    x = jnp.asarray(np.random.default_rng(0).normal(size=shape), dtype)
    key = jax.random.key(5)
    got = dropout(x, 0.8, key, mask_shape)
    assert got.dtype == dtype
    _same_bits(got, _inverted(x, 0.8, key, mask_shape))
    dropped = np.asarray(got.astype(jnp.float32)) == 0.0
    assert 0.02 < dropped.mean() < 0.6


def _call_namespace(x, key):
    return ns.nn.dropout(key, x, 0.75), _inverted(x, 0.75, key)


def _call_layer(x, key):
    layer = DenseLayer(n_out=3, dropout=0.6)
    return layer._maybe_dropout(x, True, key), _inverted(x, 0.6, key)


def _call_spatial(x, key):
    x = x.reshape(2, 3, 2, -1)
    got, _ = SpatialDropoutLayer(p=0.7).apply({}, {}, x, train=True, rng=key)
    return got, _inverted(x, 0.7, key, (2, 1, 1, x.shape[-1]))


def _call_bert(x, key):
    return B._dropout(x, 0.1, True, key), _inverted(x, 1.0 - 0.1, key)


def _call_drop_connect(x, key):
    return DropConnect(p=0.5).transform(x, key), _inverted(x, 0.5, key)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=str)
@pytest.mark.parametrize("call", [_call_namespace, _call_layer, _call_spatial,
                                  _call_bert, _call_drop_connect],
                         ids=lambda f: f.__name__[6:])
def test_former_caller_output_unchanged(call, dtype):
    """Same key, same shape, same bits as the copy each caller held."""
    x = jnp.asarray(np.random.default_rng(1).normal(size=(12, 8)), dtype)
    got, parent = call(x, jax.random.key(9))
    _same_bits(got, parent.astype(dtype))


@pytest.mark.parametrize("guard", ["eval", "no_key", "off"])
def test_former_callers_keep_their_guards(guard):
    x = jnp.ones((2, 3, 3, 4))
    key = None if guard == "no_key" else jax.random.key(0)
    train = guard != "eval"
    off = guard == "off"
    assert DenseLayer(n_out=3, dropout=None if off else 0.5)._maybe_dropout(
        x, train, key) is x
    assert SpatialDropoutLayer(p=1.0 if off else 0.5).apply(
        {}, {}, x, train=train, rng=key)[0] is x
    assert B._dropout(x, 0.0 if off else 0.5, train, key) is x


# ------------------------------------- (b) nothing drawn: the parent's bits
def _off(case):
    """Arguments under which nothing may be drawn."""
    return {"no_key": dict(dropout_rate=0.1),
            "rate_0": dict(dropout_rate=0.0, dropout_rng=jax.random.key(3)),
            "neither": {}}[case]


_OFF = ["no_key", "rate_0", "neither"]


@pytest.mark.parametrize("off", _OFF)
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_einsum_route_without_dropout_is_the_parents(off, causal):
    q, k, v = _qkv(2, 2, 12, 16)
    mask = jnp.ones((2, 12)).at[:, -3:].set(0.0)
    got = multi_head_attention(q, k, v, n_heads=4, mask=mask, causal=causal,
                               use_flash=False, **_off(off))
    _same_bits(got, _plain_attention(q, k, v, 4, mask=mask, causal=causal))


@pytest.mark.parametrize("off", _OFF)
def test_flash_route_without_dropout_is_the_parents(off):
    q, k, v = _qkv(3, 1, 16, 16)
    kvm = jnp.ones((1, 16)).at[:, -4:].set(0.0)
    got = multi_head_attention(q, k, v, n_heads=2, kv_mask=kvm,
                               use_flash=True, flash_block=8, **_off(off))
    _same_bits(got, pallas_mod.flash_attention(q, k, v, n_heads=2, causal=False,
                                               key_mask=kvm, block_q=8,
                                               block_k=8))


# ------------------------------ (c) with a rate and a key: the plain formula
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "kv_mask"])
def test_dropout_on_probabilities_matches_plain_formula(causal, masked):
    b, t, heads, d = 2, 10, 4, 16
    q, k, v = _qkv(4, b, t, d)
    kvm = jnp.ones((b, t)).at[:, -2:].set(0.0) if masked else None
    key, rate = jax.random.key(11), 0.25

    def program(q, k, v):
        return multi_head_attention(q, k, v, n_heads=heads, kv_mask=kvm,
                                    causal=causal, dropout_rate=rate,
                                    dropout_rng=key)

    def plain(q, k, v):
        return _plain_attention(q, k, v, heads, kv_mask=kvm, causal=causal,
                                rate=rate, key=key)

    _same_bits(program(q, k, v), plain(q, k, v))
    assert not np.array_equal(
        np.asarray(program(q, k, v)),
        np.asarray(_plain_attention(q, k, v, heads, kv_mask=kvm,
                                    causal=causal)))
    w = jnp.asarray(np.random.default_rng(5).normal(size=(b, t, d)),
                    jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(program(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-6, atol=1e-7)


# --------------------------- (d) the flash kernel never leaves it out silently
@pytest.fixture
def flash_calls(monkeypatch):
    calls = []
    real = pallas_mod.flash_attention

    def spy(*a, **kw):
        calls.append(kw)
        return real(*a, **kw)

    monkeypatch.setattr(pallas_mod, "flash_attention", spy)
    return calls


def test_auto_route_with_dropout_takes_einsum_at_1024(flash_calls):
    t = A.FLASH_AUTO_SEQ_LEN
    q, k, v = _qkv(6, 1, t, 16)
    key = jax.random.key(2)
    got = multi_head_attention(q, k, v, n_heads=2, dropout_rate=0.1,
                               dropout_rng=key)
    assert flash_calls == []
    _same_bits(got, _plain_attention(q, k, v, 2, rate=0.1, key=key))
    # without a key the same call is the kernel's, as before
    multi_head_attention(q, k, v, n_heads=2, dropout_rate=0.1)
    assert len(flash_calls) == 1


@pytest.mark.parametrize("t", [16, 1024])
def test_explicit_flash_with_dropout_raises(t, flash_calls):
    q, k, v = _qkv(7, 1, t, 16)
    with pytest.raises(ValueError, match="use_flash.*dropout_rate.*dropout_rng"):
        multi_head_attention(q, k, v, n_heads=2, use_flash=True,
                             dropout_rate=0.1, dropout_rng=jax.random.key(0))
    assert flash_calls == []


def test_bert_flash_config_raises_in_training_only():
    """``BertConfig(use_flash=True)`` with the published rate: the training
    pass says so, inference and a rate of 0 run the kernel."""
    config = dataclasses.replace(B.BertConfig.tiny(vocab_size=64),
                                 num_layers=1, use_flash=True, flash_block=8)
    params = B.init_params(config, jax.random.key(0))
    ids = jnp.zeros((1, 16), jnp.int32)
    B.encode(params, config, ids, train=False, rng=jax.random.key(1))
    with pytest.raises(ValueError, match="use_flash"):
        B.encode(params, config, ids, train=True, rng=jax.random.key(1))
    B.encode(params, dataclasses.replace(config, attention_dropout=0.0), ids,
             train=True, rng=jax.random.key(1))


# ------------------------------------------------- BERT: (a), (b), (f)
def _tiny(layers=2, **kw):
    config = dataclasses.replace(B.BertConfig.tiny(vocab_size=96),
                                 num_layers=layers, **kw)
    return config, B.init_params(config, jax.random.key(0))


def _ref_layer(lp, config, x, kv_mask, train, key):
    """``encoder_layer`` as the published step computes it, from the
    module's own dense and layer norm and the formulas above."""
    drop = train and key is not None

    def hidden(h, k):
        if drop and config.hidden_dropout > 0.0:
            return _inverted(h, 1.0 - config.hidden_dropout, k)
        return h

    at = lp["attention"]
    q, k, v = (B._dense(at[n], x) for n in ("query", "key", "value"))
    on = drop and config.attention_dropout > 0.0
    attn = _plain_attention(
        q, k, v, config.num_heads, kv_mask=kv_mask,
        rate=config.attention_dropout,
        key=jax.random.fold_in(key, 3) if on else None)
    attn = hidden(B._dense(at["output"], attn), key)
    x = B._layer_norm(at["output_layer_norm"], x + attn,
                      config.layer_norm_eps)
    out = B._dense(lp["output"], jax.nn.gelu(B._dense(lp["intermediate"], x)))
    out = hidden(out, jax.random.fold_in(key, 7) if drop else None)
    return B._layer_norm(lp["output_layer_norm"], x + out,
                         config.layer_norm_eps)


@pytest.mark.parametrize("train,keyed,rate", [
    (False, True, 0.1), (True, False, 0.1), (True, True, 0.0),
    (True, True, 0.1), (True, True, 0.3),
], ids=["eval", "no_key", "rate_0", "published", "rate_0.3"])
def test_encoder_layer_against_written_out_layer(train, keyed, rate):
    """The first three cases are the parent's bits (its layer had no
    attention dropout); the last two are the published layer, on the key
    ``benchmark/reference/bert_base.py`` draws on: ``fold_in(rng, 3)``."""
    config, params = _tiny(layers=1, attention_dropout=rate)
    lp = params["encoder"]["layer_0"]
    x = jnp.asarray(np.random.default_rng(8).normal(size=(2, 12, 64)),
                    jnp.float32)
    kvm = jnp.ones((2, 12)).at[:, -3:].set(0.0)
    key = jax.random.key(4) if keyed else None
    got = B.encoder_layer(lp, config, x, kvm, train=train, rng=key)
    _same_bits(got, _ref_layer(lp, config, x, kvm, train, key))
    if train and keyed and rate:
        without = dataclasses.replace(config, attention_dropout=0.0)
        assert not np.array_equal(
            np.asarray(got),
            np.asarray(B.encoder_layer(lp, without, x, kvm, train=True,
                                       rng=key)))


@pytest.mark.parametrize("layers", [1, 2, 3])
@pytest.mark.parametrize("rate,per_layer", [(0.1, 3), (0.0, 2)],
                         ids=["published", "attention_dropout_0"])
def test_lowered_train_step_mask_count(layers, rate, per_layer):
    """The published step draws a mask after the embeddings and three a
    layer: the attention's probabilities, its output, the feed-forward's."""
    config = dataclasses.replace(B.BertConfig.tiny(vocab_size=96),
                                 num_layers=layers, attention_dropout=rate)
    model = B.BertForMaskedLM(config, seed=0)
    tx = Adam(1e-3).to_optax()
    ids = jax.ShapeDtypeStruct((2, 8), jnp.int32)
    f32 = jax.ShapeDtypeStruct((2, 8), jnp.float32)
    text = model.make_train_step(tx).lower(
        model.params, tx.init(model.params), ids, ids, f32, f32,
        jax.random.key(0)).as_text()
    assert len(re.findall(r"call @_bernoulli", text)) == 1 + per_layer * layers


def _stages_forward(config, params, ids, n_stages):
    fns, sp = B.pipeline_stages(config, params, n_stages)
    h = ids.astype(jnp.float32)
    for fn, p in zip(fns, sp):
        h = fn(p, h)
    return h


@pytest.mark.parametrize("keyed", [False, True], ids=["no_key", "one_key"])
def test_encode_and_pipeline_stages_agree_under_train(keyed):
    """``encode`` and ``pipeline_stages`` are one ``encoder_layer``.  The
    stages hand it no key, so they draw nothing, and agree bit for bit with
    ``encode(train=True)`` without a key; with one key ``encode`` is the
    layer-by-layer composition a stage would compute on that key's chain,
    attention mask included."""
    config, params = _tiny(layers=4)
    ids = jnp.asarray(np.random.default_rng(3).integers(5, 96, (2, 12)),
                      jnp.int32)
    if not keyed:
        logits = B.mlm_logits(params, config,
                              B.encode(params, config, ids, train=True))
        _same_bits(_stages_forward(config, params, ids, 2), logits)
        return
    key = jax.random.key(6)
    got = B.encode(params, config, ids, train=True, rng=key)
    chain = jax.random.fold_in(key, 0)
    x = B._dropout(B.embed(params, config, ids), config.hidden_dropout,
                   True, chain)
    for i in range(config.num_layers):
        x = B.encoder_layer(params["encoder"][f"layer_{i}"], config, x,
                            train=True, rng=jax.random.fold_in(chain, i + 1))
    _same_bits(got, x)
    assert not np.array_equal(
        np.asarray(got), np.asarray(B.encode(
            params, dataclasses.replace(config, attention_dropout=0.0), ids,
            train=True, rng=key)))


# ------------- the mask's layout is pinned; the draw is the parent's, bit for bit
def _under_jit(fn, q, k, v):
    return jax.jit(fn)(q, k, v)


def _under_checkpoint(fn, q, k, v):
    return jax.jit(jax.checkpoint(fn))(q, k, v)


def _under_vmap(fn, q, k, v):
    """Two independent problems side by side; the key is closed over, so
    each draws the un-batched call's mask."""
    two = tuple(jnp.stack([a, a[::-1]]) for a in (q, k, v))
    return tuple(o[0] for o in jax.jit(jax.vmap(fn))(*two))


def _under_data_mesh(fn, q, k, v):
    """The batch sharded over a ``data`` axis of the CPU's devices, as a
    data-parallel step holds it."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    rows = NamedSharding(mesh, P("data"))
    q, k, v = (jax.device_put(a, rows) for a in (q, k, v))
    out = jax.jit(fn, in_shardings=(rows,) * 3)(q, k, v)
    assert all(len(o.sharding.device_set) == 4
               for o in jax.tree_util.tree_leaves(out))
    return out


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=str)
@pytest.mark.parametrize("under", [_under_jit, _under_checkpoint, _under_vmap,
                                   _under_data_mesh],
                         ids=lambda f: f.__name__[7:])
def test_pinned_mask_is_the_parents_draw_bit_for_bit(under, dtype):
    """``multi_head_attention`` keeps the probabilities' chain in the layout
    the generator writes its bits in; that may move no bit of the output or
    of the gradients to q, k, v against the chain written out with
    ``jax.random.bernoulli(key, 0.9, (B,H,Tq,Tk))``."""
    b, t, heads, d = 4, 16, 4, 32
    q, k, v = _qkv(12, b, t, d, dtype)
    key, rate = jax.random.key(21, impl="rbg"), 0.1
    w = jnp.asarray(np.random.default_rng(13).normal(size=(b, t, d)), dtype)

    def both(attend):
        def fn(q, k, v):
            def loss(q, k, v):
                out = attend(q, k, v)
                return jnp.sum((out * w).astype(jnp.float32)), out
            grads, out = jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(
                q, k, v)
            return (out, *grads)
        return fn

    program = both(lambda q, k, v: multi_head_attention(
        q, k, v, n_heads=heads, dropout_rate=rate, dropout_rng=key))
    plain = both(lambda q, k, v: _plain_attention(
        q, k, v, heads, rate=rate, key=key))
    got, want = under(program, q, k, v), under(plain, q, k, v)
    for g, r in zip(got, want):
        _same_bits(g, r)
    dropped = under(both(lambda q, k, v: _plain_attention(q, k, v, heads)),
                    q, k, v)
    assert not np.array_equal(np.asarray(got[0].astype(jnp.float32)),
                              np.asarray(dropped[0].astype(jnp.float32)))
