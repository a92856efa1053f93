"""Fused 1x1-conv+BN Pallas kernel + FusedBottleneck layer tests
(round 3, VERDICT #1: the cuDNN-platform-engine analog).

Interpreter mode on the CPU rig; jnp implementations are the oracles.
The compiled kernels at ResNet-50's shapes: tests/test_chip_compile.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers.fused import FusedBottleneck
from deeplearning4j_tpu.ops.pallas.conv_bn import matmul_bn_act


def _oracle(x, w, a, b, relu_in, prologue):
    xh = x * a + b if prologue else x
    if prologue and relu_in:
        xh = jnp.maximum(xh, 0.0)
    y = xh @ w
    return y, jnp.sum(y, 0), jnp.sum(y * y, 0)


class TestMatmulBnAct:
    @pytest.mark.parametrize("prologue,relu_in",
                             [(True, True), (True, False), (False, False)])
    def test_forward_and_grads_match(self, prologue, relu_in):
        rng = np.random.default_rng(0)
        m, k, n = 300, 32, 48              # m % block_m != 0 → pad path
        x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32) * 0.1)
        a = jnp.asarray(rng.uniform(0.5, 1.5, k).astype(np.float32))
        b = jnp.asarray(rng.normal(size=k).astype(np.float32) * 0.2)
        args = (x, w, a, b) if prologue else (x, w)

        y, s1, s2 = matmul_bn_act(*args, relu_in=relu_in, block_m=64)
        yo, s1o, s2o = _oracle(x, w, a, b, relu_in, prologue)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yo),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s1o),
                                   rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(np.asarray(s2), np.asarray(s2o),
                                   rtol=1e-4, atol=1e-3)

        # grads through y AND the stats outputs (the BN-training chain)
        def loss_k(*args2):
            y, s1, s2 = matmul_bn_act(*args2, relu_in=relu_in, block_m=64)
            return (jnp.sum(jnp.sin(y)) + jnp.sum(s1 * 0.3)
                    + jnp.sum(jnp.sqrt(jnp.abs(s2))))

        def loss_o(*args2):
            if prologue:
                y, s1, s2 = _oracle(*args2, relu_in, True)
            else:
                y, s1, s2 = _oracle(args2[0], args2[1], a, b, relu_in, False)
            return (jnp.sum(jnp.sin(y)) + jnp.sum(s1 * 0.3)
                    + jnp.sum(jnp.sqrt(jnp.abs(s2))))

        gk = jax.grad(loss_k, argnums=tuple(range(len(args))))(*args)
        go = jax.grad(loss_o, argnums=tuple(range(len(args))))(*args)
        for i, (u, v) in enumerate(zip(gk, go)):
            np.testing.assert_allclose(np.asarray(u), np.asarray(v),
                                       rtol=1e-4, atol=1e-4,
                                       err_msg=f"arg{i}")

    def test_auto_block_pick(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(100, 16)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(16, 24)).astype(np.float32))
        y, s1, s2 = matmul_bn_act(x, w)     # block_m=0 → auto
        yo, s1o, s2o = _oracle(x, w, None, None, False, False)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yo),
                                   rtol=1e-5, atol=1e-5)


def _bottleneck_oracle(p, x, stride, project, eps=1e-5):
    def bn(y, g, b):
        axes = tuple(range(y.ndim - 1))
        mean = jnp.mean(y, axis=axes)
        var = jnp.var(y, axis=axes)
        return (y - mean) * jax.lax.rsqrt(var + eps) * g + b

    xs = x[:, ::stride[0], ::stride[1], :] if stride != (1, 1) else x
    n, h, w, c = xs.shape
    y1 = xs.reshape(-1, c) @ p["W_a"]
    z1 = jnp.maximum(bn(y1, p["gamma_a"], p["beta_a"]), 0).reshape(n, h, w, -1)
    y2 = jax.lax.conv_general_dilated(
        z1, p["W_b3"], (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    z2 = jnp.maximum(bn(y2, p["gamma_b3"], p["beta_b3"]), 0)
    y3 = bn(z2.reshape(n * h * w, -1) @ p["W_c"], p["gamma_c"], p["beta_c"])
    if project:
        sc = bn(xs.reshape(-1, c) @ p["W_proj"],
                p["gamma_proj"], p["beta_proj"])
    else:
        sc = xs.reshape(n * h * w, -1)
    return jnp.maximum(y3 + sc, 0).reshape(n, h, w, -1)


class TestFusedBottleneck:
    @pytest.mark.parametrize("project,stride,cin",
                             [(True, (1, 1), 16), (True, (2, 2), 32),
                              (False, (1, 1), 32)])
    def test_matches_unfused_composition(self, project, stride, cin):
        rng = np.random.default_rng(0)
        lay = FusedBottleneck(filters=(8, 8, 32), stride=stride,
                              project=project)
        it = InputType.convolutional(8, 8, cin)
        params = lay.init_params(jax.random.key(0), it)
        state = lay.init_state(it)
        x = jnp.asarray(rng.normal(size=(4, 8, 8, cin)).astype(np.float32))
        out, new_state = lay.apply(params, state, x, train=True)
        ref = _bottleneck_oracle(params, x, stride, project)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        # running stats moved off init
        assert not np.allclose(np.asarray(new_state["mean_a"]), 0.0)

        gk = jax.grad(lambda p: jnp.sum(
            lay.apply(p, state, x, train=True)[0] ** 2))(params)
        go = jax.grad(lambda p: jnp.sum(
            _bottleneck_oracle(p, x, stride, project) ** 2))(params)
        for k in gk:
            np.testing.assert_allclose(np.asarray(gk[k]), np.asarray(go[k]),
                                       rtol=3e-3, atol=3e-3, err_msg=k)

    def test_eval_uses_running_stats(self):
        rng = np.random.default_rng(2)
        lay = FusedBottleneck(filters=(4, 4, 8), project=True)
        it = InputType.convolutional(4, 4, 8)
        params = lay.init_params(jax.random.key(0), it)
        state = lay.init_state(it)
        x = jnp.asarray(rng.normal(size=(2, 4, 4, 8)).astype(np.float32))
        _, trained = lay.apply(params, state, x, train=True)
        out1, s1 = lay.apply(params, trained, x, train=False)
        out2, s2 = lay.apply(params, trained, x, train=False)
        np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
        # eval must not move the running stats
        np.testing.assert_array_equal(np.asarray(s1["mean_a"]),
                                      np.asarray(trained["mean_a"]))

    def test_resnet50_fused_builds_and_runs(self):
        from deeplearning4j_tpu.models import resnet50
        net = resnet50(height=32, width=32, num_classes=10, fused=True)
        net.init()
        x = jnp.zeros((2, 32, 32, 3), jnp.float32)
        out = net.output(x)
        assert np.asarray(out).shape == (2, 10)
        assert np.all(np.isfinite(np.asarray(out)))

    def test_checkpoint_remap_fused_unfused(self):
        """Unfused checkpoint → fused graph (and back) is numerically the
        same network in eval mode."""
        from deeplearning4j_tpu.models import resnet50
        from deeplearning4j_tpu.models.zoo import remap_bottleneck_params
        rng = np.random.default_rng(3)
        net_u = resnet50(height=32, width=32, num_classes=10,
                         fused=False).init()
        net_f = resnet50(height=32, width=32, num_classes=10, fused=True).init()
        x = jnp.asarray(rng.normal(size=(2, 32, 32, 3)).astype(np.float32))
        # train one step worth of stats so running mean/var are non-trivial
        _, net_u.state_, _ = net_u._forward(net_u.params_, net_u.state_, x,
                                            train=True,
                                            rng=jax.random.key(0))

        pf, sf = remap_bottleneck_params(net_u.params_, net_u.state_,
                                         to_fused=True)
        assert set(pf) == set(net_f.params_), "fused key sets must match"
        net_f.params_, net_f.state_ = pf, sf
        out_u = net_u.output(x)
        out_f = net_f.output(x)
        np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_u),
                                   rtol=2e-4, atol=2e-4)

        pu, su = remap_bottleneck_params(pf, sf, to_fused=False)
        assert set(pu) == set(net_u.params_)
        for k in pu:
            jax.tree.map(np.testing.assert_array_equal,
                         pu[k], net_u.params_[k])


class TestFusedConvDefault:
    """ISSUE 11 satellite: FusedBottleneck is the DEFAULT conv-zoo
    lowering behind ``config.fused_conv`` (on by default); an explicit
    ``fused=`` argument always wins.  The numeric pin against the
    unfused path is ``test_checkpoint_remap_fused_unfused`` above —
    here the default graph is proven to be the fused one AND to match
    the unfused oracle on the same weights."""

    def test_default_follows_config_and_explicit_wins(self):
        from deeplearning4j_tpu.config import set_config
        from deeplearning4j_tpu.models import resnet50

        def bottleneck_layers(net):
            return [v.obj for v in net.conf.vertices
                    if isinstance(v.obj, FusedBottleneck)]

        try:
            assert bottleneck_layers(
                resnet50(height=32, width=32, num_classes=4)), \
                "config.fused_conv=True (default) must build FusedBottleneck"
            assert not bottleneck_layers(
                resnet50(height=32, width=32, num_classes=4, fused=False))
            set_config(fused_conv=False)
            assert not bottleneck_layers(
                resnet50(height=32, width=32, num_classes=4))
            assert bottleneck_layers(
                resnet50(height=32, width=32, num_classes=4, fused=True))
        finally:
            set_config(fused_conv=True)

    def test_default_graph_matches_unfused_oracle(self):
        """The shipped default (fused) evaluates to the same function as
        the unfused graph under remapped weights."""
        from deeplearning4j_tpu.models import resnet50
        from deeplearning4j_tpu.models.zoo import remap_bottleneck_params
        rng = np.random.default_rng(7)
        net_d = resnet50(height=32, width=32, num_classes=4).init()
        assert any(isinstance(v.obj, FusedBottleneck)
                   for v in net_d.conf.vertices)
        net_u = resnet50(height=32, width=32, num_classes=4,
                         fused=False).init()
        pu, su = remap_bottleneck_params(net_d.params_, net_d.state_,
                                         to_fused=False)
        net_u.params_, net_u.state_ = pu, su
        x = jnp.asarray(rng.normal(size=(2, 32, 32, 3)).astype(np.float32))
        np.testing.assert_allclose(np.asarray(net_u.output(x)),
                                   np.asarray(net_d.output(x)),
                                   rtol=2e-4, atol=2e-4)
