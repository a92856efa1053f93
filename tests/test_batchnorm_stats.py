"""``BatchNormalization``'s training statistics: one pass over the
activation (``sum(x)``, ``sum(x*x)``) has to give what two passes give,
and where float32 cannot (data far from zero) it has to fail as the
layer's docstring says.  The two-pass layer is written here, in numpy for
the statistics and in jax for the gradient, and shares nothing with the
layer under test.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.config import DTypePolicy, set_dtype_policy
from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers import BatchNormalization

KEY = jax.random.key(0)
C = 5
SHAPES = {"NC": (48, C), "NTC": (6, 7, C), "NHWC": (4, 6, 6, C)}


def _layer(**kw):
    layer = BatchNormalization(**kw)
    itype = InputType.feed_forward(C)
    return layer, layer.init_params(KEY, itype), layer.init_state(itype)


def _two_pass(x):
    """numpy's mean, then the mean of squared distances from it, in
    float64 over everything but the channel axis."""
    x = np.asarray(x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x,
                   np.float64)
    axes = tuple(range(x.ndim - 1))
    mean = x.mean(axis=axes)
    return mean, ((x - mean) ** 2).mean(axis=axes)


def _data(shape, dtype, loc=0.7, scale=1.3, seed=0):
    x = np.random.default_rng(seed).normal(loc, scale, size=shape)
    return jnp.asarray(x, dtype)


@contextlib.contextmanager
def _widest(dtype):
    """float64 needs x64 on and a policy that stores the state so, as
    ``test_gradchecks.py`` runs the layer."""
    if dtype != jnp.float64:
        yield
        return
    with jax.enable_x64(True):
        set_dtype_policy(DTypePolicy(param_dtype=dtype, compute_dtype=dtype,
                                     output_dtype=dtype))
        try:
            yield
        finally:
            set_dtype_policy(DTypePolicy.f32())


def _stats_match(shape, dtype, rtol):
    """The state written equals numpy's two-pass statistics; it is kept in
    float32 for a bf16 input and in float64 under a float64 policy."""
    with _widest(dtype):
        # decay 0: the state written IS the batch statistic
        layer, params, state = _layer(decay=0.0)
        x = _data(SHAPES[shape], dtype)
        y, new_state = layer.apply(params, state, x, train=True)
        mean, var = _two_pass(x)
        assert y.dtype == x.dtype
        wide = jnp.float64 if dtype == jnp.float64 else jnp.float32
        assert new_state["mean"].dtype == new_state["var"].dtype == wide
        np.testing.assert_allclose(np.asarray(new_state["mean"]), mean,
                                   rtol=rtol)
        np.testing.assert_allclose(np.asarray(new_state["var"]), var,
                                   rtol=rtol)


def _constant_channel():
    """Two passes read exactly 0 for a constant channel.  One pass reads
    exactly 0 where the sums are exact, and otherwise what rounding leaves
    of sum(x*x)/n - mean*mean, which the clamp keeps from going negative."""
    layer, params, state = _layer(decay=0.0)
    x = np.array(_data(SHAPES["NHWC"], jnp.float32))
    x[..., 2] = 0.1    # not exact in binary
    x[..., 3] = -7.25  # exact, and so are its sums
    x[..., 4] = 0.0    # a dead channel
    y, new_state = layer.apply(params, state, jnp.asarray(x), train=True)
    var = np.asarray(new_state["var"])
    assert var[3] == 0.0 and var[4] == 0.0, var
    assert np.all(var >= 0.0) and var[2] < 0.1 * layer.eps, var
    assert np.all(np.isfinite(np.asarray(y)))
    np.testing.assert_allclose(np.asarray(y)[..., 2:], 0.0, atol=1e-3)


def _far_from_zero(ratio, rtol):
    """Data at mean = ratio * std.  float32 holds sum(x*x)/n - mean*mean
    to about ratio**2 * 1e-7, and worse the more elements are summed: a
    third of a percent at 1e2 over 256 rows, up to 40% at 1e3, and at 1e4
    it is all rounding (clamped at 0 or many times too large; ``rtol`` is
    None).  What every case keeps, on every step: finite output, no
    negative variance, the two-pass mean."""
    std = 2.0
    layer, params, state = _layer(decay=0.0)
    for seed in range(3):
        x = _data((256, C), jnp.float32, loc=ratio * std, scale=std, seed=seed)
        y, state = layer.apply(params, state, x, train=True)
        mean, var = _two_pass(x)
        assert np.all(np.isfinite(np.asarray(y)))
        assert np.all(np.asarray(state["var"]) >= 0.0)
        np.testing.assert_allclose(np.asarray(state["mean"]), mean, rtol=1e-6)
        if rtol is not None:
            np.testing.assert_allclose(np.asarray(state["var"]), var, rtol=rtol)


def _many_elements_far_from_zero():
    """The more elements a channel sums, the sooner rounding eats the
    variance: 8x14x14 at mean = 1e3 * std can read 0 in a channel (the
    clamp), and the output is then (x - mean) * rsqrt(eps): large,
    finite.  The mean stays right."""
    layer, params, state = _layer(decay=0.0)
    x = _data((8, 14, 14, C), jnp.float32, loc=2e3, scale=2.0)
    y, new_state = layer.apply(params, state, x, train=True)
    assert np.all(np.asarray(new_state["var"]) >= 0.0)
    assert np.all(np.isfinite(np.asarray(y)))
    np.testing.assert_allclose(np.asarray(new_state["mean"]),
                               _two_pass(x)[0], rtol=1e-5)


def _grad_matches_two_pass(shape):
    layer, params, state = _layer()
    params = {"gamma": _data((C,), jnp.float32, 1.0, 0.2, seed=1),
              "beta": _data((C,), jnp.float32, 0.0, 0.2, seed=2)}
    x = _data(SHAPES[shape], jnp.float32)
    w = _data(SHAPES[shape], jnp.float32, 0.0, 1.0, seed=4)
    axes = tuple(range(x.ndim - 1))

    def one_pass(p, x):
        y, _ = layer.apply(p, state, x, train=True)
        return jnp.sum(jnp.tanh(y) * w)

    def two_pass(p, x):
        mean = jnp.mean(x, axis=axes)
        var = jnp.mean(jnp.square(x - mean), axis=axes)
        y = (x - mean) * jax.lax.rsqrt(var + layer.eps) * p["gamma"] + p["beta"]
        return jnp.sum(jnp.tanh(y) * w)

    got = jax.grad(one_pass, argnums=(0, 1))(params, x)
    want = jax.grad(two_pass, argnums=(0, 1))(params, x)
    for g, t in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(t)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(t), rtol=1e-5,
                                   atol=1e-5 * scale)


def _eval_keeps_state():
    layer, params, state = _layer()
    x = _data(SHAPES["NHWC"], jnp.float32)
    y, new_state = layer.apply(params, state, x, train=False)
    assert new_state is state
    want = np.asarray(x) / np.sqrt(1.0 + layer.eps)  # the initial state
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-6)


CHECKS = {
    **{f"stats_f32_{s}": (_stats_match, s, jnp.float32, 1e-5) for s in SHAPES},
    # bf16 values are exact in float32, so float32 statistics of them
    # still meet numpy's to 1e-5
    **{f"stats_bf16_{s}": (_stats_match, s, jnp.bfloat16, 1e-5) for s in SHAPES},
    **{f"stats_f64_{s}": (_stats_match, s, jnp.float64, 1e-12)
       for s in SHAPES},
    "constant_channel_var_is_zero": (_constant_channel,),
    # (mean / std, the bound on the variance or None where it reads anything)
    "mean_1e2_std": (_far_from_zero, 1e2, 0.02),
    "mean_1e3_std": (_far_from_zero, 1e3, 0.3),
    "mean_1e4_std": (_far_from_zero, 1e4, None),
    "mean_1e3_std_many_elements": (_many_elements_far_from_zero,),
    **{f"grad_{s}": (_grad_matches_two_pass, s) for s in SHAPES},
    "eval_returns_state_unchanged": (_eval_keeps_state,),
}


@pytest.mark.parametrize("check", list(CHECKS.values()), ids=list(CHECKS))
def test_batchnorm_one_pass_statistics(check):
    fn, *args = check
    fn(*args)
