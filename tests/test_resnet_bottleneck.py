"""The zoo's ResNet bottleneck (``zoo._bottleneck``: ConvolutionLayer +
BatchNormalization per branch, the only lowering since PR 35) held to a
written-out ``jnp`` chain, and the refusals that answer whoever still names
the deleted Pallas conv+BN path.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import resnet50, zoo
from deeplearning4j_tpu.nn import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.graph import ComputationGraph

FILTERS = (8, 8, 32)
DECAY = 0.9                              # BatchNormalization's default


def _block_graph(cin, stride, project, hw=8):
    gb = (NeuralNetConfiguration.builder().seed(0).weight_init("relu").graph()
          .add_inputs("in")
          .set_input_types(InputType.convolutional(hw, hw, cin)))
    out = zoo._bottleneck(gb, "blk", "in", FILTERS, stride, project)
    gb.set_outputs(out)
    return ComputationGraph(gb.build()).init()


def _bottleneck_oracle(p, x, stride, project, eps=1e-5):
    """conv -> BN (batch statistics, biased variance) -> relu, three times,
    plus the shortcut; returns the output and every BN's batch moments."""
    moments = {}

    def conv_bn(name, y, strides, relu):
        y = jax.lax.conv_general_dilated(
            y, p[f"blk_{name}_conv"]["W"], strides, "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST)
        mean, var = jnp.mean(y, axis=(0, 1, 2)), jnp.var(y, axis=(0, 1, 2))
        moments[f"blk_{name}_bn"] = (mean, var)
        bn = p[f"blk_{name}_bn"]
        y = (y - mean) * jax.lax.rsqrt(var + eps) * bn["gamma"] + bn["beta"]
        return jnp.maximum(y, 0) if relu else y

    y = conv_bn("a", x, stride, True)
    y = conv_bn("b", y, (1, 1), True)
    y = conv_bn("c", y, (1, 1), False)
    shortcut = conv_bn("proj", x, stride, False) if project else x
    return jnp.maximum(y + shortcut, 0), moments


def _spread_bn(params, rng):
    """gamma and beta off their initial 1 and 0, so their gradients and the
    shift's path are exercised."""
    params = jax.tree_util.tree_map(lambda leaf: leaf, params)
    for name, leaves in params.items():
        if name.endswith("_bn"):
            n = leaves["gamma"].shape[0]
            leaves["gamma"] = jnp.asarray(rng.uniform(0.5, 1.5, n), jnp.float32)
            leaves["beta"] = jnp.asarray(rng.normal(0, 0.2, n), jnp.float32)
    return params


@pytest.mark.parametrize("project,stride,cin",
                         [(True, (1, 1), 16), (True, (2, 2), 32),
                          (False, (1, 1), 32)])
def test_bottleneck_graph_matches_written_out_chain(project, stride, cin):
    rng = np.random.default_rng(0)
    net = _block_graph(cin, stride, project)
    params = _spread_bn(net.params_, rng)
    x = jnp.asarray(rng.normal(size=(4, 8, 8, cin)).astype(np.float32))

    out, new_state, _ = net._forward(params, net.state_, x, train=True)
    ref, moments = _bottleneck_oracle(params, x, stride, project)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    assert len(moments) == (4 if project else 3)
    for name, (mean, var) in moments.items():
        np.testing.assert_allclose(np.asarray(new_state[name]["mean"]),
                                   (1 - DECAY) * np.asarray(mean),
                                   rtol=1e-4, atol=1e-5, err_msg=name)
        np.testing.assert_allclose(np.asarray(new_state[name]["var"]),
                                   DECAY + (1 - DECAY) * np.asarray(var),
                                   rtol=1e-4, atol=1e-5, err_msg=name)

    got = jax.grad(lambda p: jnp.sum(
        net._forward(p, net.state_, x, train=True)[0] ** 2))(params)
    want = jax.grad(lambda p: jnp.sum(
        _bottleneck_oracle(p, x, stride, project)[0] ** 2))(params)
    n_leaves = 0
    for vertex, leaves in want.items():
        for leaf, g in leaves.items():
            np.testing.assert_allclose(np.asarray(got[vertex][leaf]),
                                       np.asarray(g), rtol=3e-3, atol=3e-3,
                                       err_msg=f"{vertex}/{leaf}")
            n_leaves += 1
    assert n_leaves == (12 if project else 9)


def test_bottleneck_eval_uses_running_stats():
    rng = np.random.default_rng(2)
    net = _block_graph(16, (1, 1), True, hw=4)
    x = jnp.asarray(rng.normal(size=(2, 4, 4, 16)).astype(np.float32))
    train_out, trained, _ = net._forward(net.params_, net.state_, x,
                                         train=True)
    out1, s1, _ = net._forward(net.params_, trained, x, train=False)
    out2, _, _ = net._forward(net.params_, trained, x, train=False)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    # the running statistics, not the batch's, normalise in eval ...
    assert not np.allclose(np.asarray(out1), np.asarray(train_out))
    # ... and eval does not move them
    for name, leaves in trained.items():
        for leaf, value in leaves.items():
            np.testing.assert_array_equal(np.asarray(s1[name][leaf]),
                                          np.asarray(value))


def test_resnet50_default_is_the_conv_bn_graph():
    from deeplearning4j_tpu.config import ENV_KNOBS, Config
    default = resnet50(height=32, width=32, num_classes=4)
    assert default.conf.to_json() == resnet50(
        height=32, width=32, num_classes=4, fused=False).conf.to_json()
    kinds = {type(spec.obj).__name__ for spec in default.conf.vertices}
    assert kinds == {"ZeroPaddingLayer", "ConvolutionLayer",
                     "BatchNormalization", "SubsamplingLayer",
                     "ElementWiseVertex", "ActivationLayer",
                     "GlobalPoolingLayer", "OutputLayer"}, kinds
    assert sum(type(spec.obj).__name__ == "ConvolutionLayer"
               for spec in default.conf.vertices) == 53
    assert "fused_conv" not in {f.name for f in dataclasses.fields(Config)}
    assert "DL4J_TPU_FUSED_CONV" not in ENV_KNOBS


def test_resnet50_fused_true_is_refused():
    # None asked for "what config.fused_conv says", which was the same path
    for fused in (True, None):
        with pytest.raises(ValueError, match=r"deleted in PR 35.*PERF\.md"):
            resnet50(height=32, width=32, num_classes=4, fused=fused)


def test_configuration_naming_fused_bottleneck_is_refused():
    from deeplearning4j_tpu.nn.layers.base import layer_from_dict
    with pytest.raises(KeyError, match="unknown layer type 'fused_bottleneck'"):
        layer_from_dict({"type": "fused_bottleneck", "filters": [8, 8, 32],
                         "stride": [1, 1], "project": True})
