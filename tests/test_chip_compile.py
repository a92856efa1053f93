"""Compiles for a TPU v5e that is described, not attached.

The chip's compiler is installed beside the CPU backend and refuses what
interpret mode never sees: a kernel that asks for more VMEM than it may
use, a slice off the tiling, a program that does not fit the HBM.  These
tests hand it the kernels at BERT-base / serving widths, the conv+BN graph
at ResNet-50's four stages and ONE whole program, the ResNet-50 train step
the benchmark measures.  Nothing runs: no results, no times.

Only one process may load the TPU library, and it keeps it until it exits.
So the topology is described inside a module-scoped fixture, never at
import, in ``parametrize`` or in ``skipif``, and every such compile lives in
this one file: under xdist exactly one worker is handed it.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deeplearning4j_tpu.ops.pallas.flash_attention import flash_attention
from deeplearning4j_tpu.ops.pallas.quant_matmul import int8_matmul_pallas

V5E_HBM_BYTES = 16e9

@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Sharding onto one described chip, with the persistent compilation
    cache off while this module compiles: an entry written for a described
    chip cannot be read back without one, and the retry warns."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_flash_attention_bert_base_seq4096(one_chip, grad):
    attend = functools.partial(flash_attention, n_heads=12, interpret=False)
    fn = attend
    if grad:
        fn = jax.grad(lambda q, k, v: jnp.sum(
            attend(q, k, v).astype(jnp.float32)), argnums=(0, 1, 2))
    qkv = ((2, 4096, 768), jnp.bfloat16)
    assert _has_kernel(_compile(fn, one_chip, qkv, qkv, qkv))


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_flash_attention_latent_heads_seq8192(one_chip, grad):
    """Latent attention's shapes in ``joyai_llm_flash.clm_s8192_b1``: 32
    heads, keys and queries 192 wide, values 128, 8,192 tokens, causal.
    At the tuned 1024x1024 tile the merged backward asks for 19.1 MB of
    VMEM, over the compiler's 16 MiB default scope: ``_wide_heads`` gives
    such calls 32 MiB (PR 38, the refusal this case guards)."""
    attend = functools.partial(flash_attention, n_heads=32, causal=True,
                               interpret=False)
    fn = attend
    if grad:
        fn = jax.grad(lambda q, k, v: jnp.sum(
            attend(q, k, v).astype(jnp.float32)), argnums=(0, 1, 2))
    qk = ((1, 8192, 32 * 192), jnp.bfloat16)
    compiled = _compile(fn, one_chip, qk, qk, ((1, 8192, 32 * 128),
                                               jnp.bfloat16))
    assert _has_kernel(compiled)


def test_flash_attention_grouped_query_heads_seq8192(one_chip):
    """Grouped-query attention's shapes in ``lfm2_8b_a1b.clm_s8192_b2``: 2
    rows of 8,192 tokens, 32 query heads and 8 K/V heads of 64, causal,
    forward and merged backward in one program: the K/V BlockSpecs'
    index maps read a group's head, the backward's grid runs a group's
    query heads inside each K/V block and sums dK and dV in VMEM, and
    both fit the compiler's default VMEM scope (heads of 64 take no
    ``_wide_heads`` parameters)."""
    attend = functools.partial(flash_attention, n_heads=32, n_kv_heads=8,
                               causal=True, interpret=False)
    fn = jax.value_and_grad(lambda q, k, v: jnp.sum(
        attend(q, k, v).astype(jnp.float32)), argnums=(0, 1, 2))
    compiled = _compile(fn, one_chip, ((2, 8192, 32 * 64), jnp.bfloat16),
                        ((2, 8192, 8 * 64), jnp.bfloat16),
                        ((2, 8192, 8 * 64), jnp.bfloat16))
    calls = [line for line in compiled.as_text().splitlines()
             if "custom-call(" in line]
    for kernel in ("tpudl_flash_fwd", "tpudl_flash_bwd_merged"):
        assert any(re.search(kernel + r"\b", line) for line in calls), kernel
    # dK and dV leave the kernel once a K/V head: [2 * 8, 8192, 64] float32
    assert any("f32[16,8192,64]" in line for line in calls
               if "tpudl_flash_bwd_merged" in line)


def test_lfm2_train_step_lowers_one_flash_pair(one_chip):
    """The program ``lfm2_8b_a1b.clm_s8192_b2`` runs, from the cell's own
    configuration file (507.8 M parameters, 2 x 8,192 tokens, bf16 policy,
    Adam), lowered from ``make_train_step`` and not compiled (the whole
    step compiles in about 30 s, more than this file should spend): its
    one attention block calls the flash forward kernel once and the
    merged backward once a step, the forward's output and row statistics
    kept through the block's rematerialised run."""
    import json
    import os

    from deeplearning4j_tpu.config import (DTypePolicy, dtype_policy,
                                           set_dtype_policy)
    from deeplearning4j_tpu.models import lfm2_moe
    from deeplearning4j_tpu.train import Adam
    from deeplearning4j_tpu.train.trainer import Trainer, make_train_step
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "lfm2_8b_a1b.json")) as f:
        config = json.load(f)
    batch, seq = 2, 8192
    was = dtype_policy()
    set_dtype_policy(DTypePolicy.bf16())
    try:
        net = lfm2_moe(config, seq, updater=Adam(1e-4))

        def shapes():                      # net.init traced, never run
            net.init()
            return net.params_, net.state_
        params, state = jax.eval_shape(shapes)
        net.params_ = params
        tx = Trainer(net).tx

        def on_chip(tree):
            return jax.tree_util.tree_map(
                lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                                  sharding=one_chip), tree)

        ids = on_chip(jax.ShapeDtypeStruct((batch, seq), jnp.int32))
        args = (on_chip(params), on_chip(state),
                on_chip(jax.eval_shape(tx.init, params)), ids, ids, None,
                on_chip(jax.ShapeDtypeStruct((batch,), jnp.float32)),
                on_chip(jax.eval_shape(lambda: jax.random.key(0))))
        real_backend, jax.default_backend = jax.default_backend, lambda: "tpu"
        try:
            text = make_train_step(net, tx).lower(*args).as_text()
        finally:
            jax.default_backend = real_backend
    finally:
        set_dtype_policy(was)
    n_params = sum(leaf.size for leaf in jax.tree_util.tree_leaves(params))
    assert n_params == 507_820_160
    assert net.trace_attrs()["attention_kinds"] == ["conv", "gqa", "conv",
                                                    "conv", "conv"]
    assert net.trace_attrs()["kv_groups"] == 4
    _, *funcs = re.split(r"\n  func\.func ", text)
    names = [re.match(r"(?:public |private )?@(\w+)", f).group(1)
             for f in funcs]

    @functools.lru_cache(maxsize=None)
    def runs(name):
        """How often a step calls the function ``name``, through every
        chain of calls from ``main``."""
        if name == "main":
            return 1
        calls = [(len(re.findall(rf"call @{name}\(", body)), caller)
                 for caller, body in zip(names, funcs)]
        return sum(n * runs(caller) for n, caller in calls if n)

    for kernel in ("tpudl_flash_fwd", "tpudl_flash_bwd_merged"):
        pattern = r"tpu_custom_call.*" + kernel + r"\b"
        assert len(re.findall(pattern, text)) == 1, kernel
        body = [name for name, f in zip(names, funcs)
                if re.search(pattern, f)]
        assert len(body) == 1 and runs(body[0]) == 1, kernel


def test_joyai_train_step_whole_program(one_chip):
    """The program ``joyai_llm_flash.clm_s8192_b1`` runs, from the cell's
    own configuration file: 680.4 M parameters at the published widths,
    8,192 tokens, bf16 policy, Adam, lowered from ``make_train_step``.  It
    has to hold the flash kernels and the grouped products (Mosaic calls)
    and fit the chip beside the harness's own copy of the weights: 8.17 GB
    of parameters and moments and 3.75 GB of temporaries (3.33 GB until
    PR 39: each of the six rematerialised runs now keeps its flash call's
    output and row statistics, 69.7 MB, and the compiled step calls the
    forward kernel 6 times, not 12)."""
    import json
    import os

    from deeplearning4j_tpu.config import (DTypePolicy, dtype_policy,
                                           set_dtype_policy)
    from deeplearning4j_tpu.models import joyai_llm_flash
    from deeplearning4j_tpu.train import Adam
    from deeplearning4j_tpu.train.trainer import Trainer, make_train_step
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "joyai_llm_flash.json")) as f:
        config = json.load(f)
    seq = 8192
    was = dtype_policy()
    set_dtype_policy(DTypePolicy.bf16())
    try:
        net = joyai_llm_flash(config, seq, updater=Adam(1e-4))

        def shapes():                      # net.init traced, never run
            net.init()
            return net.params_, net.state_
        params, state = jax.eval_shape(shapes)
        net.params_ = params               # Trainer only asks whether set
        tx = Trainer(net).tx

        def on_chip(tree):
            return jax.tree_util.tree_map(
                lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                                  sharding=one_chip), tree)

        ids = on_chip(jax.ShapeDtypeStruct((1, seq), jnp.int32))
        args = (on_chip(params), on_chip(state),
                on_chip(jax.eval_shape(tx.init, params)), ids, ids, None,
                on_chip(jax.ShapeDtypeStruct((1,), jnp.float32)),
                on_chip(jax.eval_shape(lambda: jax.random.key(0))))
        real_backend, jax.default_backend = jax.default_backend, lambda: "tpu"
        try:                 # the kernels' compiled branch, not interpret
            compiled = make_train_step(net, tx).lower(*args).compile()
        finally:
            jax.default_backend = real_backend
    finally:
        set_dtype_policy(was)
    n_params = sum(leaf.size for leaf in jax.tree_util.tree_leaves(params))
    assert round(n_params / 1e6, 1) == 680.4
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "custom-call(" in line]
    blocks = config["num_hidden_layers"] + config["num_nextn_predict_layers"]
    for kernel in ("tpudl_flash_fwd", "tpudl_flash_bwd_merged"):
        assert sum(kernel in line for line in calls) == blocks == 6, kernel
    assert "ragged-dot" in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 4.0e9, mem
    resident = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    # the harness keeps its own 2.72 GB copy of the weights beside it
    assert resident + 4 * n_params < V5E_HBM_BYTES, mem


def test_delta_attention_layer_seq8192(one_chip):
    """One Kimi Delta Attention layer at the widths of
    ``kimi_linear_48b_a3b.clm_s8192_b1`` (32 heads of 128 behind 4 taps,
    hidden 2304, 8,192 tokens, bf16 policy), forward and ``jax.grad``: the
    chunk phase's forward is the ``tpudl_kda_chunk`` kernel and its
    backward ``tpudl_kda_chunk_bwd``, both for all 32 heads at once, so
    the only loops left are the scan's, one each way (the jnp backward's
    loop over head groups is gone).  Its temporaries, 2.16 GB when written
    (2.77 with the grouped jnp backward), stay under 2.3 GB: the kernel's
    float32 inputs are made only once the cotangents are there, and the
    convolutions' residuals are recomputed."""
    from deeplearning4j_tpu.config import (DTypePolicy, dtype_policy,
                                           set_dtype_policy)
    from deeplearning4j_tpu.nn.input_type import InputType
    from deeplearning4j_tpu.nn.layers.decoder import DeltaAttention
    layer = DeltaAttention(n_heads=32, head_dim=128, chunk=64, eps=1e-5)
    was = dtype_policy()
    set_dtype_policy(DTypePolicy.bf16())
    try:
        params = jax.eval_shape(lambda: layer.init_params(
            jax.random.key(0), InputType.recurrent(2304, 8192)))

        def loss(params, x):
            return jnp.sum(layer.apply(params, {}, x)[0].astype(jnp.float32))

        args = jax.tree_util.tree_map(
            lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                              sharding=one_chip),
            (params, jax.ShapeDtypeStruct((1, 8192, 2304), jnp.bfloat16)))
        real_backend, jax.default_backend = jax.default_backend, lambda: "tpu"
        try:                 # the kernel's compiled branch, not interpret
            compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
                *args).compile()
        finally:
            jax.default_backend = real_backend
    finally:
        set_dtype_policy(was)
    n_params = sum(leaf.size for leaf in jax.tree_util.tree_leaves(params))
    assert round(n_params / 1e6, 2) == 39.51
    text = compiled.as_text()
    assert len(re.findall(r" while\(", text)) == 2
    calls = [line for line in text.splitlines() if "custom-call(" in line]
    for kernel in ("tpudl_kda_chunk", "tpudl_kda_chunk_bwd"):
        assert any(re.search(kernel + r"\b", line) for line in calls), kernel
    assert compiled.memory_analysis().temp_size_in_bytes < 2.3e9


def test_kimi_train_step_lowers_the_kda_kernel_once(one_chip):
    """The program ``kimi_linear_48b_a3b.clm_s8192_b1`` runs, from the
    cell's own configuration file (602.4 M parameters, 8,192 tokens, bf16
    policy, Adam), lowered from ``make_train_step`` and not compiled: four
    KDA layers, each run forward and again in its block's rematerialised
    run, make 8 calls of the chunk-phase kernel, and the module holds ONE
    kernel body that they all call, lowered once a step (what the set-up
    of every run of the cell pays, even where the compile is cached); their
    4 backward passes run ONE body of ``tpudl_kda_chunk_bwd``.  The only
    loops are the 12 scans (4 layers forward, rematerialised and
    backward): no loop over head groups is left."""
    import json
    import os

    from deeplearning4j_tpu.config import (DTypePolicy, dtype_policy,
                                           set_dtype_policy)
    from deeplearning4j_tpu.models import kimi_linear
    from deeplearning4j_tpu.train import Adam
    from deeplearning4j_tpu.train.trainer import Trainer, make_train_step
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "kimi_linear_48b_a3b.json")) as f:
        config = json.load(f)
    seq = 8192
    was = dtype_policy()
    set_dtype_policy(DTypePolicy.bf16())
    try:
        net = kimi_linear(config, seq, updater=Adam(1e-4),
                          kda_chunk=config["kda_chunk"])

        def shapes():                      # net.init traced, never run
            net.init()
            return net.params_, net.state_
        params, state = jax.eval_shape(shapes)
        net.params_ = params
        tx = Trainer(net).tx

        def on_chip(tree):
            return jax.tree_util.tree_map(
                lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                                  sharding=one_chip), tree)

        ids = on_chip(jax.ShapeDtypeStruct((1, seq), jnp.int32))
        args = (on_chip(params), on_chip(state),
                on_chip(jax.eval_shape(tx.init, params)), ids, ids, None,
                on_chip(jax.ShapeDtypeStruct((1,), jnp.float32)),
                on_chip(jax.eval_shape(lambda: jax.random.key(0))))
        real_backend, jax.default_backend = jax.default_backend, lambda: "tpu"
        try:
            text = make_train_step(net, tx).lower(*args).as_text()
        finally:
            jax.default_backend = real_backend
    finally:
        set_dtype_policy(was)
    n_params = sum(leaf.size for leaf in jax.tree_util.tree_leaves(params))
    assert round(n_params / 1e6, 1) == 602.4
    assert net.trace_attrs()["kda_kernel"] == "tpudl_kda_chunk"
    assert net.trace_attrs()["kda_bwd_kernel"] == "tpudl_kda_chunk_bwd"
    _, *funcs = re.split(r"\n  func\.func ", text)
    names = [re.match(r"(?:public |private )?@(\w+)", f).group(1)
             for f in funcs]

    @functools.lru_cache(maxsize=None)
    def runs(name):
        """How often a step calls the function ``name``, through every
        chain of calls from ``main``."""
        if name == "main":
            return 1
        calls = [(len(re.findall(rf"call @{name}\(", body)), caller)
                 for caller, body in zip(names, funcs)]
        return sum(n * runs(caller) for n, caller in calls if n)

    body = {}
    for kernel, n_runs in (("tpudl_kda_chunk", 8), ("tpudl_kda_chunk_bwd", 4)):
        pattern = r"tpu_custom_call.*" + kernel + r"\b"
        bodies = [name for name, f in zip(names, funcs)
                  if re.search(pattern, f)]
        assert len(bodies) == 1, kernel
        assert len(re.findall(pattern, text)) == 1, kernel
        assert runs(bodies[0]) == n_runs, kernel
        body[kernel] = bodies[0]
    assert len(re.findall(rf"call @{body['tpudl_kda_chunk']}\(", text)) == 8
    assert len(re.findall(r"stablehlo\.while", text)) == 12


def test_int8_matmul(one_chip):
    fn = functools.partial(int8_matmul_pallas, interpret=False)
    compiled = _compile(fn, one_chip, ((64, 2048), jnp.bfloat16),
                        ((2048, 2048), jnp.int8), ((2048,), jnp.float32))
    assert _has_kernel(compiled)


def test_attention_dropout_bits_are_not_transposed(one_chip):
    """The counter that says ``multi_head_attention``'s layout pin engaged:
    the gradient of one BERT-base attention ([32,512,768] bf16, 12 heads)
    with the published dropout and the ``rbg`` key ``BertForMaskedLM.fit``
    draws on.  The hardware generator writes its ``uint32`` bits row-major
    and XLA kept the probabilities query-minor, so the compiled step held a
    ``copy`` of the raw bits, 805 MB through HBM a layer, before the
    one-byte comparison; with the mask pinned there is none."""
    from deeplearning4j_tpu.ops.attention import multi_head_attention

    def loss(q, k, v, key):
        out = multi_head_attention(q, k, v, n_heads=12, dropout_rate=0.1,
                                   dropout_rng=key)
        return jnp.sum(out.astype(jnp.float32))

    qkv = ((32, 512, 768), jnp.bfloat16)
    key = jax.eval_shape(lambda: jax.random.key(0, impl="rbg"))
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, qkv, qkv,
                    qkv, (key.shape, key.dtype)).as_text()
    bits = r"u32\[32,12,512,512\]\{[^}]*\}"
    assert re.search(bits + r" rng-bit-generator\(", text)
    assert not re.findall(r"= " + bits + r" copy\(", text)


def test_resnet50_train_step_whole_program(one_chip):
    """The program the benchmark's ResNet cell and ``chip_smoke.py`` run:
    ``resnet50()`` as it builds with no argument, 224x224, 1000 classes,
    batch 128, bf16 policy, lowered from ``make_train_step``: convolutions
    and BN as XLA fuses them, no Mosaic call, 4.428 GB of temporaries when
    written (PERF.md section 5)."""
    from deeplearning4j_tpu.config import (DTypePolicy, dtype_policy,
                                           set_dtype_policy)
    from deeplearning4j_tpu.models import resnet50
    from deeplearning4j_tpu.train import Nesterovs
    from deeplearning4j_tpu.train.trainer import Trainer, make_train_step
    batch = 128
    was = dtype_policy()
    set_dtype_policy(DTypePolicy.bf16())
    try:
        net = resnet50(height=224, width=224, num_classes=1000,
                       updater=Nesterovs(0.1, 0.9))
        net.init()
        tx = Trainer(net).tx

        def on_chip(tree):
            return jax.tree_util.tree_map(
                lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                                  sharding=one_chip), tree)

        args = (on_chip(net.params_), on_chip(net.state_),
                on_chip(jax.eval_shape(tx.init, net.params_)),
                on_chip(jax.ShapeDtypeStruct((batch, 224, 224, 3),
                                             jnp.float32)),
                on_chip(jax.ShapeDtypeStruct((batch, 1000), jnp.float32)),
                None, None,
                on_chip(jax.eval_shape(lambda: jax.random.key(0))))
        compiled = make_train_step(net, tx).lower(*args).compile()
    finally:
        set_dtype_policy(was)
    assert not _has_kernel(compiled)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 4.6e9, mem
    resident = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert resident < V5E_HBM_BYTES, mem


# two identity bottlenecks of each ResNet-50 stage at batch 128:
# (height = width, channels in = out, bottleneck width)
RESNET50_STAGES = {"res2": (56, 256, 64), "res3": (28, 512, 128),
                   "res4": (14, 1024, 256), "res5": (7, 2048, 512)}
# counted bytes of the one-pass graph over the two-pass graph's, at most.
# Read when written: res2 0.770 fwd (2.06 against 2.68 GB), 0.907 grad (9.05
# against 9.98 GB); res3 0.716, 0.770; res4 0.814, 0.864; res5 0.863, 0.915;
# fusions 25 against 37 fwd, 74-80 against 98 grad.
ONE_PASS_BYTES = {("res2", False): 0.81, ("res2", True): 0.95,
                  ("res3", False): 0.75, ("res3", True): 0.81,
                  ("res4", False): 0.85, ("res4", True): 0.90,
                  ("res5", False): 0.90, ("res5", True): 0.95}


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("stage", sorted(RESNET50_STAGES))
def test_batchnorm_statistics_take_one_pass(one_chip, stage, grad):
    """The counter that says ``BatchNormalization``'s one-pass statistics
    engaged: two bottlenecks of ``ConvolutionLayer`` +
    ``BatchNormalization`` at each stage's shapes (batch 128, bf16 policy;
    the training forward with its new running statistics, or the gradient
    of parameters and input) against the same graph with the two-pass
    statistics written here.  ``jnp.var`` reads the activation a second
    time after ``jnp.mean`` and autodiff adds a zero-valued
    ``sum(x - mean)`` to the backward; the layer's sums hang on ``x``
    alone and share one read."""
    from deeplearning4j_tpu.config import (DTypePolicy, dtype_policy,
                                           set_dtype_policy)
    from deeplearning4j_tpu.nn.input_type import InputType
    from deeplearning4j_tpu.nn.layers import (BatchNormalization,
                                              ConvolutionLayer)
    hw, wide, narrow = RESNET50_STAGES[stage]

    class TwoPass(BatchNormalization):
        def apply(self, params, state, x, *, train=False, rng=None,
                  mask=None):
            axes = tuple(range(x.ndim - 1))
            x32 = x.astype(jnp.float32)
            mean, var = jnp.mean(x32, axis=axes), jnp.var(x32, axis=axes)
            keep = self.decay
            new_state = {"mean": keep * state["mean"] + (1.0 - keep) * mean,
                         "var": keep * state["var"] + (1.0 - keep) * var}
            scale = jax.lax.rsqrt(var + self.eps) * params["gamma"]
            shift = params["beta"] - mean * scale
            y = x * scale.astype(x.dtype) + shift.astype(x.dtype)
            return (jax.nn.relu(y) if self.activation == "relu" else y,
                    new_state)

    def graph(bn_cls):
        layers, itype = [], InputType.convolutional(hw, hw, wide)
        for n_out, kernel, act in 2 * [(narrow, (1, 1), "relu"),
                                       (narrow, (3, 3), "relu"),
                                       (wide, (1, 1), "identity")]:
            for layer in (ConvolutionLayer(n_out=n_out, kernel_size=kernel,
                                           convolution_mode="same",
                                           has_bias=False,
                                           activation="identity"),
                          bn_cls(activation=act)):
                layers.append((layer, itype))
                itype = layer.get_output_type(itype)

        def init():
            keys = jax.random.split(jax.random.key(0), len(layers))
            return ([l.init_params(k, t) for k, (l, t) in zip(keys, layers)],
                    [l.init_state(t) for l, t in layers])

        def loss(params, x, state):
            new_state = []
            for at in range(0, len(layers), 6):
                shortcut = x
                for (layer, _), p, s in zip(layers[at:at + 6],
                                            params[at:at + 6],
                                            state[at:at + 6]):
                    x, s = layer.apply(p, s, x, train=True)
                    new_state.append(s)
                x = jax.nn.relu(x + shortcut)
            return jnp.sum(x.astype(jnp.float32)), new_state

        return init, (jax.grad(loss, argnums=(0, 1), has_aux=True) if grad
                      else loss)

    def compiled(bn_cls):
        init, fn = graph(bn_cls)
        params, state = jax.tree_util.tree_map(
            lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                              sharding=one_chip),
            jax.eval_shape(init))
        x = jax.ShapeDtypeStruct((128, hw, hw, wide), jnp.bfloat16,
                                 sharding=one_chip)
        return jax.jit(fn).lower(params, x, state).compile()

    def read(c):
        return (c.cost_analysis()["bytes accessed"],
                c.as_text().count(" fusion("))

    was = dtype_policy()
    set_dtype_policy(DTypePolicy.bf16())
    try:
        one_bytes, one_fusions = read(compiled(BatchNormalization))
        two_bytes, two_fusions = read(compiled(TwoPass))
    finally:
        set_dtype_policy(was)
    assert one_bytes <= ONE_PASS_BYTES[stage, grad] * two_bytes, \
        (one_bytes, two_bytes)
    assert one_fusions < two_fusions, (one_fusions, two_fusions)
