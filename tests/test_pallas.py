"""Pallas flash-attention kernel tests (VERDICT #7).

Runs in interpreter mode on the CPU test rig; the jnp implementations
(_block_attention / reference_attention) are the numerical oracles.
The described-chip compiles live in tests/test_chip_compile.py, the
compiled run against the einsum chain in chip_smoke.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops.pallas import flash_attention, flash_attention_block
from deeplearning4j_tpu.parallel.unified import (
    _block_attention, reference_attention, ring_attention)
from deeplearning4j_tpu.parallel.mesh import make_mesh


def _qkv(b=2, h=3, t=24, d=16, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(b, h, t, d)).astype(np.float32))
                 for _ in range(3))


class TestFlashBlock:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_jnp_oracle(self, causal):
        q, k, v = _qkv()
        scale = 0.25
        if causal:
            pos = jnp.arange(24)
            mask = pos[:, None] >= pos[None, :]
        else:
            mask = None
        o1, m1, l1 = _block_attention(q, k, v, scale, mask)
        o2, m2, l2 = flash_attention_block(q, k, v, scale=scale,
                                           causal=causal, block_q=8,
                                           block_k=8)
        np.testing.assert_allclose(np.asarray(o2), np.asarray(o1),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(m2), np.asarray(m1),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(l2), np.asarray(l1),
                                   rtol=1e-5, atol=1e-6)

    def test_offsets_and_rectangular_blocks(self):
        """Ring-step shape: Tq != Tk, non-zero global offsets, future block
        fully masked under causal."""
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.normal(size=(1, 2, 20, 16)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(1, 2, 28, 16)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(1, 2, 28, 16)).astype(np.float32))
        qpos, kpos = 40 + jnp.arange(20), 16 + jnp.arange(28)
        mask = qpos[:, None] >= kpos[None, :]
        o1, m1, l1 = _block_attention(q, k, v, 0.25, mask)
        o2, m2, l2 = flash_attention_block(q, k, v, scale=0.25, causal=True,
                                           q_offset=40, k_offset=16,
                                           block_q=8, block_k=8)
        np.testing.assert_allclose(np.asarray(o2), np.asarray(o1),
                                   rtol=1e-5, atol=1e-5)
        # entirely-future kv block: every row must report nothing visible
        o3, m3, l3 = flash_attention_block(q, k, v, scale=0.25, causal=True,
                                           q_offset=0, k_offset=100,
                                           block_q=8, block_k=8)
        assert np.all(np.asarray(l3) == 0.0)
        assert np.all(np.asarray(m3) <= -1e29)

    def test_padding_of_non_multiple_lengths(self):
        q, k, v = _qkv(t=23)           # 23 % 8 != 0 → padded internally
        o1, m1, l1 = _block_attention(q, k, v, 0.3, None)
        o2, m2, l2 = flash_attention_block(q, k, v, scale=0.3, block_q=8,
                                           block_k=8)
        np.testing.assert_allclose(np.asarray(o2), np.asarray(o1),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(l2), np.asarray(l1),
                                   rtol=1e-5, atol=1e-6)


class TestFlashFull:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference_attention(self, causal):
        rng = np.random.default_rng(2)
        b, t, h, d = 2, 40, 4, 16
        q = jnp.asarray(rng.normal(size=(b, t, h * d)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(b, t, h * d)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(b, t, h * d)).astype(np.float32))
        out = flash_attention(q, k, v, n_heads=h, causal=causal,
                              block_q=8, block_k=8)
        ref = reference_attention(q, k, v, n_heads=h, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestFlashBackward:
    """jax.grad through the Pallas kernels vs grad through the jnp
    reference (VERDICT r2 weak #2: the kernel was forward-only)."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_reference(self, causal):
        rng = np.random.default_rng(7)
        b, t, h, d = 2, 40, 4, 16
        q, k, v = (jnp.asarray(rng.normal(size=(b, t, h * d))
                               .astype(np.float32)) for _ in range(3))

        def loss_flash(q, k, v):
            out = flash_attention(q, k, v, n_heads=h, causal=causal,
                                  block_q=8, block_k=8)
            return jnp.sum(jnp.sin(out))          # non-uniform cotangent

        def loss_ref(q, k, v):
            return jnp.sum(jnp.sin(
                reference_attention(q, k, v, n_heads=h, causal=causal)))

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for gf, gr, name in zip(g_flash, g_ref, "qkv"):
            np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                       rtol=2e-4, atol=2e-5, err_msg=name)

    def test_grads_non_multiple_length(self):
        # t not a multiple of the block: padded rows must not pollute grads
        rng = np.random.default_rng(8)
        b, t, h, d = 1, 21, 2, 8
        q, k, v = (jnp.asarray(rng.normal(size=(b, t, h * d))
                               .astype(np.float32)) for _ in range(3))
        f = lambda *a: jnp.sum(flash_attention(*a, n_heads=h, causal=True,
                                               block_q=8, block_k=8) ** 2)
        r = lambda *a: jnp.sum(reference_attention(*a, n_heads=h,
                                                   causal=True) ** 2)
        g_flash = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
        for gf, gr in zip(g_flash, g_ref):
            assert not np.any(np.isnan(np.asarray(gf)))
            np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                       rtol=2e-4, atol=2e-5)

    def test_grads_bf16(self):
        rng = np.random.default_rng(9)
        b, t, h, d = 1, 32, 2, 16
        q, k, v = (jnp.asarray(rng.normal(size=(b, t, h * d))
                               .astype(np.float32)).astype(jnp.bfloat16)
                   for _ in range(3))
        f = lambda *a: jnp.sum(flash_attention(
            *a, n_heads=h, block_q=16, block_k=16).astype(jnp.float32))
        r = lambda *a: jnp.sum(reference_attention(
            *a, n_heads=h).astype(jnp.float32))
        g_flash = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
        for gf, gr in zip(g_flash, g_ref):
            assert gf.dtype == jnp.bfloat16
            np.testing.assert_allclose(np.asarray(gf, dtype=np.float32),
                                       np.asarray(gr, dtype=np.float32),
                                       rtol=0.1, atol=0.1)


class TestFlashMaskAndProduct:
    def test_key_mask_matches_reference(self):
        rng = np.random.default_rng(11)
        b, t, h, d = 2, 24, 2, 8
        q, k, v = (jnp.asarray(rng.normal(size=(b, t, h * d))
                               .astype(np.float32)) for _ in range(3))
        mask = jnp.asarray(rng.integers(0, 2, size=(b, t)), jnp.float32)
        mask = mask.at[:, 0].set(1.0)          # keep at least one key alive
        from deeplearning4j_tpu.ops.attention import multi_head_attention

        def loss(fn):
            return lambda *a: jnp.sum(jnp.sin(fn(*a)))

        flash = loss(lambda *a: multi_head_attention(
            *a, n_heads=h, mask=mask, use_flash=True, flash_block=8))
        ref = loss(lambda *a: multi_head_attention(*a, n_heads=h, mask=mask))
        np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                                   np.asarray(ref(q, k, v)),
                                   rtol=2e-5, atol=2e-5)
        gf = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
        for a, b2 in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b2),
                                       rtol=2e-4, atol=2e-5)

    def test_cross_attention_flash(self):
        """tq != tk with kv_mask (review regression: flash reshaped k/v
        with q's length)."""
        from deeplearning4j_tpu.ops.attention import multi_head_attention
        rng = np.random.default_rng(14)
        q = jnp.asarray(rng.normal(size=(2, 10, 16)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(2, 18, 16)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(2, 18, 16)).astype(np.float32))
        kvm = jnp.ones((2, 18)).at[:, -4:].set(0.0)
        f = lambda *a: jnp.sum(jnp.sin(multi_head_attention(
            *a, n_heads=2, kv_mask=kvm, use_flash=True, flash_block=8)))
        r = lambda *a: jnp.sum(jnp.sin(multi_head_attention(
            *a, n_heads=2, kv_mask=kvm)))
        np.testing.assert_allclose(float(f(q, k, v)), float(r(q, k, v)),
                                   rtol=1e-5)
        gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
        for a, b2 in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b2),
                                       rtol=2e-4, atol=2e-5)

    def test_self_attention_layer_flash_trains(self):
        """use_flash on the layer: same forward and grads as the einsum
        path (VERDICT r2: the kernel must be in the product)."""
        from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer
        from deeplearning4j_tpu.nn.input_type import InputType
        rng = np.random.default_rng(12)
        x = jnp.asarray(rng.normal(size=(2, 16, 32)).astype(np.float32))
        lay = SelfAttentionLayer(n_heads=4, use_flash=True, flash_block=8)
        ref = SelfAttentionLayer(n_heads=4)
        params = lay.init_params(jax.random.key(0),
                                 InputType.recurrent(32, 16))

        def f(layer):
            def loss(p):
                y, _ = layer.apply(p, {}, x)
                return jnp.sum(y ** 2)
            return loss

        np.testing.assert_allclose(np.asarray(f(lay)(params)),
                                   np.asarray(f(ref)(params)), rtol=1e-5)
        gf = jax.grad(f(lay))(params)
        gr = jax.grad(f(ref))(params)
        for name in gf:
            np.testing.assert_allclose(np.asarray(gf[name]),
                                       np.asarray(gr[name]),
                                       rtol=2e-4, atol=2e-5, err_msg=name)

    @pytest.mark.slow
    def test_bert_flash_step_matches(self):
        """One MLM train step with use_flash on == off (tiny config)."""
        import dataclasses as dc
        from deeplearning4j_tpu.models import bert as bert_mod
        cfg = bert_mod.BertConfig.tiny()
        cfg_flash = dc.replace(cfg, use_flash=True, flash_block=8)
        rng = np.random.default_rng(13)
        b, t = 2, 24
        ids = jnp.asarray(rng.integers(0, 1000, size=(b, t)), jnp.int32)
        labels = jnp.asarray(rng.integers(0, 1000, size=(b, t)), jnp.int32)
        weights = jnp.asarray(rng.integers(0, 2, size=(b, t)), jnp.float32)
        amask = jnp.ones((b, t), jnp.float32).at[:, -5:].set(0.0)
        params = bert_mod.init_params(cfg, jax.random.key(1))

        grads = []
        for c in (cfg, cfg_flash):
            def loss(p):
                return bert_mod.mlm_loss(p, c, ids, labels, weights,
                                         attention_mask=amask, train=False)
            l, g = jax.value_and_grad(loss)(params)
            grads.append((l, g))
        np.testing.assert_allclose(np.asarray(grads[0][0]),
                                   np.asarray(grads[1][0]), rtol=1e-5)
        flat0 = jax.tree_util.tree_leaves(grads[0][1])
        flat1 = jax.tree_util.tree_leaves(grads[1][1])
        for a, b2 in zip(flat0, flat1):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b2),
                                       rtol=5e-4, atol=5e-5)


class TestRingWithFlash:
    def test_ring_attention_flash_bf16(self):
        """The advertised long-seq dtype must trace through the scan carry
        (review regression: f32 kernel outputs vs bf16 carry)."""
        mesh = make_mesh(data=2, seq=4)
        rng = np.random.default_rng(5)
        q = jnp.asarray(rng.normal(size=(2, 32, 32)).astype(np.float32)
                        ).astype(jnp.bfloat16)
        with mesh:
            out = ring_attention(q, q, q, mesh, axis="seq", n_heads=4,
                                 causal=True, use_flash=True, flash_block=8,
                                 data_axis="data")
        assert out.dtype == jnp.bfloat16
        ref = reference_attention(q, q, q, n_heads=4, causal=True)
        np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                                   np.asarray(ref, dtype=np.float32),
                                   rtol=0.1, atol=0.05)   # bf16 tolerance

    @pytest.mark.parametrize("causal", [False, True])
    def test_ring_attention_flash_inner_kernel(self, causal):
        """Ring attention with the Pallas inner kernel == jnp ring == full
        reference, on the 8-device mesh."""
        mesh = make_mesh(data=1, seq=8)
        b, t, heads, dh = 2, 32, 4, 8
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.normal(size=(b, t, heads * dh)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(b, t, heads * dh)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(b, t, heads * dh)).astype(np.float32))
        with mesh:
            out = ring_attention(q, k, v, mesh, axis="seq", n_heads=heads,
                                 causal=causal, use_flash=True, flash_block=8)
        ref = reference_attention(q, k, v, n_heads=heads, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)


class TestFlashAutoDefault:
    """ISSUE 11 satellite: the flash kernel is the standard BERT path —
    ``use_flash=None`` auto-enables at seq >= 1024 (explicit False
    still wins), with numeric parity against the einsum path."""

    def test_auto_matches_einsum_at_long_seq(self):
        from deeplearning4j_tpu.ops.attention import multi_head_attention
        rng = np.random.default_rng(21)
        b, t, h, d = 1, 1024, 2, 8
        q, k, v = (jnp.asarray(rng.normal(size=(b, t, h * d))
                               .astype(np.float32)) for _ in range(3))
        auto = multi_head_attention(q, k, v, n_heads=h)          # default
        einsum = multi_head_attention(q, k, v, n_heads=h, use_flash=False)
        np.testing.assert_allclose(np.asarray(auto), np.asarray(einsum),
                                   rtol=2e-5, atol=2e-5)

    def test_auto_routing_thresholds(self, monkeypatch):
        """seq >= 1024 routes to the kernel, shorter stays on einsum,
        and an explicit False beats the auto promotion."""
        from deeplearning4j_tpu.ops import pallas as pallas_mod
        from deeplearning4j_tpu.ops.attention import multi_head_attention
        calls = []
        real = pallas_mod.flash_attention

        def spy(*a, **kw):
            calls.append(kw.get("block_q"))
            return real(*a, **kw)

        monkeypatch.setattr(pallas_mod, "flash_attention", spy)
        rng = np.random.default_rng(22)
        short = jnp.asarray(rng.normal(size=(1, 64, 16)).astype(np.float32))
        long = jnp.asarray(rng.normal(size=(1, 1024, 16)).astype(np.float32))
        multi_head_attention(short, short, short, n_heads=2)
        assert calls == []                       # short seq: einsum path
        multi_head_attention(long, long, long, n_heads=2)
        assert len(calls) == 1                   # long seq: promoted
        multi_head_attention(long, long, long, n_heads=2, use_flash=False)
        assert len(calls) == 1                   # explicit False wins

    def test_bert_config_default_is_auto(self):
        from deeplearning4j_tpu.models.bert import BertConfig
        from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer
        assert BertConfig().use_flash is None
        assert SelfAttentionLayer().use_flash is None
