"""Zoo model builders.

Parity notes per model (reference classes under
``deeplearning4j/deeplearning4j-zoo/src/main/java/org/deeplearning4j/zoo/model/``):

- ``lenet`` → ``LeNet.java`` (conv5x5x20 → pool → conv5x5x50 → pool →
  dense500 → softmax; DL4J's variant of LeCun's LeNet).
- ``alexnet`` → ``AlexNet.java`` (the one-GPU variant w/ LRN).
- ``vgg16`` → ``VGG16.java``.
- ``resnet50`` → ``ResNet50.java`` (ComputationGraph, bottleneck blocks,
  conv/identity shortcuts, BN after each conv — v1 architecture).
- ``simple_cnn`` → ``SimpleCNN.java``.
- ``text_gen_lstm`` → ``TextGenerationLSTM.java`` (char-RNN,
  GravesLSTM stack + RnnOutputLayer MCXENT).
- ``joyai_llm_flash`` has no reference twin: a decoder-only language model
  of DeepSeek-V3's kind (latent attention, sigmoid-routed dropless experts
  beside a shared expert, a multi-token-prediction module) from the keys of
  its published ``config.json``, as a ComputationGraph of ``nn.layers.decoder``.
- ``kimi_linear`` has none either: Kimi-Linear's hybrid of Kimi Delta
  Attention (a gated delta rule run as a chunked scan) in three blocks of
  four and latent attention without positions in the fourth, the same
  routed experts, from its published ``config.json``.
- ``lfm2_moe`` has none either: LFM2-8B-A1B's hybrid of double-gated
  short convolutions and grouped-query attention, the same routed experts
  without a shared one, a head tied to the embedding, from its published
  ``config.json``.
- ``mlp_mnist`` / ``lstm_classifier`` → dl4j-examples workloads named in
  BASELINE.json (MLPMnistTwoLayerExample; UCI HAR sequence classification).

All CNNs are NHWC; ImageNet-sized models default to 224x224x3 inputs.
"""

from __future__ import annotations

from typing import Optional

from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import (
    DenseLayer, OutputLayer, ConvolutionLayer, SubsamplingLayer,
    BatchNormalization, ActivationLayer, DropoutLayer, GlobalPoolingLayer,
    LocalResponseNormalization, LSTM, GravesLSTM, LastTimeStep, RnnOutputLayer,
    ZeroPaddingLayer,
)
from deeplearning4j_tpu.nn.layers import (
    CausalLMOutput, DeltaAttention, EmbeddingSequenceLayer, GatedFeedForward,
    GatedShortConv, GroupedQueryAttention, LatentAttention, RMSNorm,
    RoutedExperts,
)
from deeplearning4j_tpu.nn.weights import distribution
from deeplearning4j_tpu.nn.vertices import (
    ElementWiseVertex, MergeVertex, ShiftTimeVertex, StackVertex,
)
from deeplearning4j_tpu.train import Adam, Nesterovs, Sgd


def mlp_mnist(seed: int = 123, hidden: int = 500, hidden2: int = 100,
              updater=None) -> MultiLayerNetwork:
    """MLPMnistTwoLayerExample parity (dl4j-examples)."""
    return MultiLayerNetwork(
        NeuralNetConfiguration.builder()
        .seed(seed)
        .updater(updater or Nesterovs(0.0015, 0.98))
        .weight_init("xavier")
        .l2(1e-4)
        .list()
        .layer(DenseLayer(n_out=hidden, activation="relu"))
        .layer(DenseLayer(n_out=hidden2, activation="relu"))
        .layer(OutputLayer(n_out=10, activation="softmax", loss="mcxent"))
        .set_input_type(InputType.feed_forward(784))
        .build())


def lenet(seed: int = 123, height: int = 28, width: int = 28, channels: int = 1,
          num_classes: int = 10, updater=None) -> MultiLayerNetwork:
    """LeNet.java parity (zoo)."""
    return MultiLayerNetwork(
        NeuralNetConfiguration.builder()
        .seed(seed)
        .updater(updater or Adam(1e-3))
        .weight_init("xavier")
        .list()
        .layer(ConvolutionLayer(n_out=20, kernel_size=(5, 5), stride=(1, 1),
                                convolution_mode="same", activation="identity"))
        .layer(SubsamplingLayer(pooling_type="max", kernel_size=(2, 2), stride=(2, 2)))
        .layer(ConvolutionLayer(n_out=50, kernel_size=(5, 5), stride=(1, 1),
                                convolution_mode="same", activation="identity"))
        .layer(SubsamplingLayer(pooling_type="max", kernel_size=(2, 2), stride=(2, 2)))
        .layer(DenseLayer(n_out=500, activation="relu"))
        .layer(OutputLayer(n_out=num_classes, activation="softmax", loss="mcxent"))
        .set_input_type(InputType.convolutional(height, width, channels))
        .build())


def simple_cnn(seed: int = 123, height: int = 48, width: int = 48, channels: int = 3,
               num_classes: int = 10) -> MultiLayerNetwork:
    """SimpleCNN.java parity."""
    b = (NeuralNetConfiguration.builder()
         .seed(seed)
         .updater(Adam(1e-3))
         .weight_init("relu")
         .list())
    for n_out in (16, 32, 64):
        b.layer(ConvolutionLayer(n_out=n_out, kernel_size=(3, 3),
                                 convolution_mode="same", activation="relu"))
        b.layer(BatchNormalization())
        b.layer(SubsamplingLayer(pooling_type="max", kernel_size=(2, 2), stride=(2, 2)))
    b.layer(DropoutLayer(dropout=0.5))
    b.layer(DenseLayer(n_out=256, activation="relu"))
    b.layer(OutputLayer(n_out=num_classes, activation="softmax", loss="mcxent"))
    b.set_input_type(InputType.convolutional(height, width, channels))
    return MultiLayerNetwork(b.build())


def alexnet(seed: int = 123, num_classes: int = 1000) -> MultiLayerNetwork:
    """AlexNet.java parity (one-tower variant with LRN)."""
    return MultiLayerNetwork(
        NeuralNetConfiguration.builder()
        .seed(seed)
        .updater(Nesterovs(1e-2, 0.9))
        .weight_init("normal")
        .l2(5e-4)
        .list()
        .layer(ConvolutionLayer(n_out=96, kernel_size=(11, 11), stride=(4, 4),
                                activation="relu"))
        .layer(LocalResponseNormalization())
        .layer(SubsamplingLayer(pooling_type="max", kernel_size=(3, 3), stride=(2, 2)))
        .layer(ConvolutionLayer(n_out=256, kernel_size=(5, 5), convolution_mode="same",
                                activation="relu", bias_init=1.0))
        .layer(LocalResponseNormalization())
        .layer(SubsamplingLayer(pooling_type="max", kernel_size=(3, 3), stride=(2, 2)))
        .layer(ConvolutionLayer(n_out=384, kernel_size=(3, 3), convolution_mode="same",
                                activation="relu"))
        .layer(ConvolutionLayer(n_out=384, kernel_size=(3, 3), convolution_mode="same",
                                activation="relu", bias_init=1.0))
        .layer(ConvolutionLayer(n_out=256, kernel_size=(3, 3), convolution_mode="same",
                                activation="relu", bias_init=1.0))
        .layer(SubsamplingLayer(pooling_type="max", kernel_size=(3, 3), stride=(2, 2)))
        .layer(DenseLayer(n_out=4096, activation="relu", dropout=0.5, bias_init=1.0))
        .layer(DenseLayer(n_out=4096, activation="relu", dropout=0.5, bias_init=1.0))
        .layer(OutputLayer(n_out=num_classes, activation="softmax", loss="mcxent"))
        .set_input_type(InputType.convolutional(224, 224, 3))
        .build())


def vgg16(seed: int = 123, num_classes: int = 1000) -> MultiLayerNetwork:
    """VGG16.java parity."""
    b = (NeuralNetConfiguration.builder()
         .seed(seed)
         .updater(Nesterovs(1e-2, 0.9))
         .weight_init("relu")
         .list())
    for block, (n_out, convs) in enumerate([(64, 2), (128, 2), (256, 3), (512, 3), (512, 3)]):
        for _ in range(convs):
            b.layer(ConvolutionLayer(n_out=n_out, kernel_size=(3, 3),
                                     convolution_mode="same", activation="relu"))
        b.layer(SubsamplingLayer(pooling_type="max", kernel_size=(2, 2), stride=(2, 2)))
    b.layer(DenseLayer(n_out=4096, activation="relu"))
    b.layer(DenseLayer(n_out=4096, activation="relu"))
    b.layer(OutputLayer(n_out=num_classes, activation="softmax", loss="mcxent"))
    b.set_input_type(InputType.convolutional(224, 224, 3))
    return MultiLayerNetwork(b.build())


def vgg19(seed: int = 123, num_classes: int = 1000) -> MultiLayerNetwork:
    """VGG19.java parity: VGG16 with 4-conv blocks at 256/512."""
    b = (NeuralNetConfiguration.builder()
         .seed(seed)
         .updater(Nesterovs(1e-2, 0.9))
         .weight_init("relu")
         .list())
    for n_out, convs in [(64, 2), (128, 2), (256, 4), (512, 4), (512, 4)]:
        for _ in range(convs):
            b.layer(ConvolutionLayer(n_out=n_out, kernel_size=(3, 3),
                                     convolution_mode="same", activation="relu"))
        b.layer(SubsamplingLayer(pooling_type="max", kernel_size=(2, 2), stride=(2, 2)))
    b.layer(DenseLayer(n_out=4096, activation="relu"))
    b.layer(DenseLayer(n_out=4096, activation="relu"))
    b.layer(OutputLayer(n_out=num_classes, activation="softmax", loss="mcxent"))
    b.set_input_type(InputType.convolutional(224, 224, 3))
    return MultiLayerNetwork(b.build())


# ------------------------------------------------------------------ ResNet-50
def _conv_bn(gb, name, n_out, kernel, stride, input_name, activation="identity",
             mode="same"):
    gb.add_layer(f"{name}_conv",
                 ConvolutionLayer(n_out=n_out, kernel_size=kernel, stride=stride,
                                  convolution_mode=mode, has_bias=False,
                                  activation="identity"),
                 input_name)
    gb.add_layer(f"{name}_bn", BatchNormalization(activation=activation),
                 f"{name}_conv")
    return f"{name}_bn"


def _bottleneck(gb, name, in_name, filters, stride, project):
    """ResNet v1 bottleneck: 1x1 reduce → 3x3 → 1x1 expand, +shortcut.
    ``ResNet50.java`` convBlock/identityBlock parity."""
    f1, f2, f3 = filters
    x = _conv_bn(gb, f"{name}_a", f1, (1, 1), stride, in_name, activation="relu")
    x = _conv_bn(gb, f"{name}_b", f2, (3, 3), (1, 1), x, activation="relu")
    x = _conv_bn(gb, f"{name}_c", f3, (1, 1), (1, 1), x, activation="identity")
    if project:
        shortcut = _conv_bn(gb, f"{name}_proj", f3, (1, 1), stride, in_name,
                            activation="identity")
    else:
        shortcut = in_name
    gb.add_vertex(f"{name}_add", ElementWiseVertex(op="add"), x, shortcut)
    gb.add_layer(f"{name}_out", ActivationLayer(activation="relu"), f"{name}_add")
    return f"{name}_out"


def resnet50(seed: int = 123, num_classes: int = 1000, height: int = 224,
             width: int = 224, channels: int = 3, updater=None,
             fused: bool = False) -> ComputationGraph:
    """ResNet50.java parity: [3, 4, 6, 3] bottleneck stages — the BASELINE
    headline model.  NHWC + channels-last BN; stride-2 downsampling in the
    first block of stages 3-5 (v1).

    ``fused`` takes ``False`` only: the benchmark's entry still passes the
    keyword.  ``True``, and ``None`` for "as ``config.fused_conv`` says",
    named the Pallas conv+BN bottleneck, deleted in PR 35 (slower on the
    chip, and its gradients near the stem were wrong: PERF.md section 7)."""
    if fused is not False:
        raise ValueError(
            f"resnet50(fused={fused!r}): the Pallas conv+BN bottleneck was "
            f"deleted in PR 35 (PERF.md section 7); the ConvolutionLayer + "
            f"BatchNormalization graph is the only one")
    gb = (NeuralNetConfiguration.builder()
          .seed(seed)
          .updater(updater or Nesterovs(1e-1, 0.9))
          .weight_init("relu")
          .l2(1e-4)
          .graph()
          .add_inputs("in")
          .set_input_types(InputType.convolutional(height, width, channels)))
    gb.add_layer("stem_pad", ZeroPaddingLayer(padding=(3, 3)), "in")
    x = _conv_bn(gb, "stem", 64, (7, 7), (2, 2), "stem_pad", activation="relu",
                 mode="truncate")
    gb.add_layer("stem_pool",
                 SubsamplingLayer(pooling_type="max", kernel_size=(3, 3),
                                  stride=(2, 2), convolution_mode="same"), x)
    x = "stem_pool"
    stages = [
        ("res2", [64, 64, 256], 3, (1, 1)),
        ("res3", [128, 128, 512], 4, (2, 2)),
        ("res4", [256, 256, 1024], 6, (2, 2)),
        ("res5", [512, 512, 2048], 3, (2, 2)),
    ]
    for stage_name, filters, blocks, first_stride in stages:
        for i in range(blocks):
            stride = first_stride if i == 0 else (1, 1)
            x = _bottleneck(gb, f"{stage_name}_{i}", x, filters,
                            stride, project=i == 0)
    gb.add_layer("avgpool", GlobalPoolingLayer(pooling_type="avg"), x)
    gb.add_layer("out", OutputLayer(n_out=num_classes, activation="softmax",
                                    loss="mcxent"), "avgpool")
    gb.set_outputs("out")
    return ComputationGraph(gb.build())


# ------------------------------------------------------- decoder-only LMs
def _decoder_block(gb, name, x, *, attention, ffn, eps: float,
                   split_run: bool = False):
    """One pre-norm block under ``name``: RMS norm, ``attention`` (the
    layer the model places there), add; RMS norm, ``ffn`` (gated
    feed-forward or routed experts), add.  Rematerialised as one run, or
    with ``split_run`` as two, the attention's half and the
    feed-forward's: the backward pass then holds one half's recomputed
    activations at a time and keeps one more ``[B, T, hidden]`` array a
    block.  Returns the name of its output vertex."""
    gb.add_layer(f"{name}_attn_norm", RMSNorm(eps=eps), x)
    gb.add_layer(f"{name}_attn", attention, f"{name}_attn_norm")
    gb.add_vertex(f"{name}_attn_add", ElementWiseVertex(op="add"), x,
                  f"{name}_attn")
    gb.add_layer(f"{name}_ffn_norm", RMSNorm(eps=eps), f"{name}_attn_add")
    gb.add_layer(f"{name}_ffn", ffn, f"{name}_ffn_norm")
    gb.add_vertex(f"{name}_out", ElementWiseVertex(op="add"),
                  f"{name}_attn_add", f"{name}_ffn")
    if split_run:
        gb.remat(f"{name}_attn_norm", f"{name}_attn_add")
        gb.remat(f"{name}_ffn_norm", f"{name}_out")
    else:
        gb.remat(f"{name}_attn_norm", f"{name}_out")
    return f"{name}_out"


def _lm_graph(c: dict, seq_len: int, seed: int, updater, std: float):
    """The builder of a decoder-only language model's graph with its
    embedding placed: one input of ``[B, seq_len]`` ids, vertex ``embed``."""
    gb = (NeuralNetConfiguration.builder()
          .seed(seed)
          .updater(updater or Adam(1e-4))
          .graph()
          .add_inputs("tokens")
          .set_input_types(InputType.recurrent(1, seq_len)))
    gb.add_layer("embed", EmbeddingSequenceLayer(
        n_in=c["vocab_size"], n_out=c["hidden_size"], has_bias=False,
        weight_init=distribution("normal", std=std)), "tokens")
    return gb


def _latent_attention(c: dict, std: float, rotary: bool = True):
    """Latent attention from the keys DeepSeek-V3's family shares; a
    published ``q_lora_rank`` of null is one query matrix."""
    return LatentAttention(
        n_heads=c["num_attention_heads"], q_lora_rank=c["q_lora_rank"] or 0,
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        rope_theta=float(c["rope_theta"]), rotary=rotary,
        eps=c["rms_norm_eps"], init_std=std)


def _feed_forward(c: dict, std: float, *, routed: bool, experts: int,
                  top_k: int, shared: int, normalize: bool,
                  norm_eps: float = 1e-20):
    """A block's feed-forward: routed experts (the chip's share from the
    keys ``experts_held`` and ``first_expert``) or the dense SwiGLU.  What
    the published families name differently comes as arguments."""
    if not routed:
        return GatedFeedForward(hidden=c["intermediate_size"], init_std=std)
    return RoutedExperts(
        n_routed_experts=experts, experts_held=c.get("experts_held", 0),
        first_expert=c.get("first_expert", 0), top_k=top_k,
        hidden=c["moe_intermediate_size"],
        shared_hidden=c["moe_intermediate_size"] * shared,
        routed_scaling_factor=c["routed_scaling_factor"],
        norm_topk_prob=normalize, norm_eps=norm_eps, init_std=std)


def joyai_llm_flash(config: dict, seq_len: int, seed: int = 123,
                    updater=None, mtp_weight: float = 0.3,
                    init_std: float = 0.02) -> ComputationGraph:
    """JoyAI-LLM-Flash (``model_type`` ``joyai_llm_flash``, 48B-A2.7B) from
    the keys of its published ``config.json``: ``first_k_dense_replace``
    dense blocks, then routed ones (sigmoid scores, ``noaux_tc`` selection
    bias, ``norm_topk_prob``, ``routed_scaling_factor``, a shared expert),
    latent attention in every block, an untied head, and with
    ``num_nextn_predict_layers`` 1 DeepSeek-V3's multi-token-prediction
    module: ``W_eh [RMSNorm(h) ; RMSNorm(Emb(t_{i+1}))]``, one more routed
    block, its own final norm, the shared head, weighted ``mtp_weight``.

    A chip's share of an expert-parallel deployment is two more keys:
    ``experts_held`` of the ``n_routed_experts`` live here, from
    ``first_expert`` on; ``vocab_size`` is the slice held.  Input and
    labels are the same ``[B, seq_len]`` int32 ids: ``net.fit`` over
    ``DataSet(ids, ids)``."""
    c, h, eps = config, config["hidden_size"], config["rms_norm_eps"]
    if c["num_nextn_predict_layers"] not in (0, 1):
        raise ValueError("one multi-token-prediction module at most")
    if c.get("n_group", 1) != 1 or c.get("topk_group", 1) != 1:
        raise ValueError("group-limited routing is not built: n_group and "
                         "topk_group have to be 1")

    def block(name, x, routed):
        return _decoder_block(
            gb, name, x, attention=_latent_attention(c, init_std),
            ffn=_feed_forward(c, init_std, routed=routed,
                              experts=c["n_routed_experts"],
                              top_k=c["num_experts_per_tok"],
                              shared=c["n_shared_experts"],
                              normalize=c["norm_topk_prob"]), eps=eps)

    gb = _lm_graph(c, seq_len, seed, updater, init_std)
    x = "embed"
    for n in range(c["num_hidden_layers"]):
        x = block(f"l{n}", x, n >= c["first_k_dense_replace"])
    gb.add_layer("final_norm", RMSNorm(eps=eps), x)
    streams = ["final_norm"]
    if c["num_nextn_predict_layers"]:
        gb.add_layer("mtp_h_norm", RMSNorm(eps=eps), x)
        gb.add_vertex("mtp_next", ShiftTimeVertex(), "embed")
        gb.add_layer("mtp_e_norm", RMSNorm(eps=eps), "mtp_next")
        gb.add_vertex("mtp_merge", MergeVertex(), "mtp_h_norm", "mtp_e_norm")
        gb.add_layer("mtp_proj", DenseLayer(
            n_out=h, has_bias=False, activation="identity",
            weight_init=distribution("normal", std=init_std)), "mtp_merge")
        y = block("mtp", "mtp_proj", True)
        gb.add_layer("mtp_final_norm", RMSNorm(eps=eps), y)
        streams.append("mtp_final_norm")
    gb.add_vertex("streams", StackVertex(), *streams)
    gb.add_layer("lm_head", CausalLMOutput(
        n_out=c["vocab_size"], n_streams=len(streams), mtp_weight=mtp_weight,
        init_std=init_std), "streams")
    gb.set_outputs("lm_head")
    return ComputationGraph(gb.build())


def kimi_linear(config: dict, seq_len: int, seed: int = 123, updater=None,
                init_std: float = 0.02, kda_chunk: int = 64
                ) -> ComputationGraph:
    """Kimi-Linear (``model_type`` ``kimi_linear``, 48B-A3B) from the keys
    of its published ``config.json``.  ``linear_attn_config`` says, layer
    by layer and counting from 1, which attention a block holds: Kimi
    Delta Attention (``kda_layers``: ``num_heads`` heads of ``head_dim``
    behind a short convolution of ``short_conv_kernel_size`` taps, run in
    chunks of ``kda_chunk``) or latent attention (``full_attn_layers``:
    ``q_lora_rank`` null is one query matrix, ``mla_use_nope`` no rotary
    positions: the KDA layers carry the order).  The first
    ``first_k_dense_replace`` blocks have a dense SwiGLU, the others
    ``num_experts`` sigmoid-routed experts (``num_experts_per_token``,
    ``moe_renormalize``, ``routed_scaling_factor``) beside
    ``num_shared_experts`` shared ones; an untied head, no multi-token
    prediction.  Blocks are ``l1`` .. ``l<num_hidden_layers>``, as the
    config counts them.

    A chip's share of an expert-parallel deployment is two more keys, as
    for :func:`joyai_llm_flash`: ``experts_held`` from ``first_expert``
    on, and ``vocab_size`` the slice held.  ``net.fit`` over
    ``DataSet(ids, ids)``."""
    c, eps = config, config["rms_norm_eps"]
    linear = c["linear_attn_config"]
    if c["moe_router_activation_func"] != "sigmoid":
        raise ValueError("only sigmoid router scores are built, the config "
                         f"states {c['moe_router_activation_func']!r}")
    if c.get("num_expert_group", 1) != 1 or c.get("topk_group", 1) != 1:
        raise ValueError("group-limited routing is not built: "
                         "num_expert_group and topk_group have to be 1")
    if c.get("num_nextn_predict_layers", 0):
        raise ValueError("kimi_linear has no multi-token-prediction module")
    gb = _lm_graph(c, seq_len, seed, updater, init_std)
    x = "embed"
    for n in range(1, c["num_hidden_layers"] + 1):
        if n in linear["kda_layers"]:
            attention = DeltaAttention(
                n_heads=linear["num_heads"], head_dim=linear["head_dim"],
                conv_taps=linear["short_conv_kernel_size"], chunk=kda_chunk,
                eps=eps, init_std=init_std)
        elif n in linear["full_attn_layers"]:
            attention = _latent_attention(c, init_std,
                                          rotary=not c["mla_use_nope"])
        else:
            raise ValueError(f"linear_attn_config names layer {n} in neither "
                             f"kda_layers nor full_attn_layers")
        ffn = _feed_forward(c, init_std,
                            routed=n > c["first_k_dense_replace"],
                            experts=c["num_experts"],
                            top_k=c["num_experts_per_token"],
                            shared=c["num_shared_experts"],
                            normalize=c["moe_renormalize"])
        x = _decoder_block(gb, f"l{n}", x, attention=attention, ffn=ffn,
                           eps=eps, split_run=True)
    gb.add_layer("final_norm", RMSNorm(eps=eps), x)
    gb.add_layer("lm_head", CausalLMOutput(
        n_out=c["vocab_size"], init_std=init_std), "final_norm")
    gb.set_outputs("lm_head")
    return ComputationGraph(gb.build())


def lfm2_moe(config: dict, seq_len: int, seed: int = 123, updater=None,
             init_std: float = 0.02) -> ComputationGraph:
    """LFM2's mixture of experts (``model_type`` ``lfm2_moe``, LFM2-8B-A1B)
    from the keys of its published ``config.json``.  ``layer_types`` says,
    layer by layer and counting from 0, which token mixer a block holds:
    the double-gated short convolution (``conv``: ``conv_L_cache`` taps, no
    bias) or grouped-query attention (``full_attention``:
    ``num_attention_heads`` query heads and ``num_key_value_heads`` K/V
    heads of ``hidden_size / num_attention_heads``, norms on the query and
    key heads, rotary positions at ``rope_theta``).  The first
    ``num_dense_layers`` blocks held have a dense SwiGLU, the others
    ``num_experts`` sigmoid-routed experts (``num_experts_per_tok``,
    ``norm_topk_prob`` with ``norm_topk_eps`` under the sum,
    ``routed_scaling_factor``, a selection bias) and no shared one; every
    norm's eps is ``norm_eps``; the head is tied to the embedding.

    A chip's share of a deployment is three more keys: ``first_layer``,
    the published index of the first layer held (blocks are ``l<index>``,
    ``num_hidden_layers`` of them); ``experts_held`` from ``first_expert``
    on, as for :func:`joyai_llm_flash`; and ``vocab_size`` the slice held.
    ``net.fit`` over ``DataSet(ids, ids)``."""
    c, eps = config, config["norm_eps"]
    if c.get("conv_bias"):
        raise ValueError("lfm2_moe builds the convolution without a bias, "
                         "the config states conv_bias true")
    if c.get("tie_word_embeddings") is False:
        raise ValueError("lfm2_moe ties its head to the embedding, the "
                         "config states tie_word_embeddings false")
    first = c.get("first_layer", 0)
    kinds = c["layer_types"][first:first + c["num_hidden_layers"]]
    if len(kinds) != c["num_hidden_layers"]:
        raise ValueError(f"layer_types holds no layers {first} .. "
                         f"{first + c['num_hidden_layers'] - 1}")
    heads = c["num_attention_heads"]
    gb = _lm_graph(c, seq_len, seed, updater, init_std)
    x = "embed"
    for held, kind in enumerate(kinds):
        if kind == "conv":
            mixer = GatedShortConv(taps=c["conv_L_cache"], init_std=init_std)
        elif kind == "full_attention":
            mixer = GroupedQueryAttention(
                n_heads=heads, n_kv_heads=c["num_key_value_heads"],
                head_dim=c["hidden_size"] // heads,
                rope_theta=float(c["rope_theta"]), eps=eps,
                init_std=init_std)
        else:
            raise ValueError(f"layer_types names a layer {kind!r}: "
                             f"lfm2_moe knows conv and full_attention")
        ffn = _feed_forward(c, init_std, routed=held >= c["num_dense_layers"],
                            experts=c["num_experts"],
                            top_k=c["num_experts_per_tok"], shared=0,
                            normalize=c["norm_topk_prob"],
                            norm_eps=c["norm_topk_eps"])
        x = _decoder_block(gb, f"l{first + held}", x, attention=mixer,
                           ffn=ffn, eps=eps)
    gb.add_layer("final_norm", RMSNorm(eps=eps), x)
    gb.add_layer("lm_head", CausalLMOutput(
        n_out=c["vocab_size"], init_std=init_std, tie_to="embed"),
        "final_norm")
    gb.set_outputs("lm_head")
    return ComputationGraph(gb.build())


# ------------------------------------------------------------------ RNN zoo
def lstm_classifier(seed: int = 123, n_in: int = 9, n_classes: int = 6,
                    timesteps: Optional[int] = 128, hidden: int = 128,
                    graves: bool = True, updater=None) -> MultiLayerNetwork:
    """UCI-HAR / sequence-classification workload (BASELINE config #3):
    GravesLSTM → LastTimeStep → OutputLayer(MCXENT)."""
    cell = GravesLSTM(n_out=hidden) if graves else LSTM(n_out=hidden)
    return MultiLayerNetwork(
        NeuralNetConfiguration.builder()
        .seed(seed)
        .updater(updater or Adam(5e-3))
        .weight_init("xavier")
        .gradient_normalization("clip_element_wise_absolute_value", 0.5)
        .list()
        .layer(LastTimeStep(underlying=cell))
        .layer(OutputLayer(n_out=n_classes, activation="softmax", loss="mcxent"))
        .set_input_type(InputType.recurrent(n_in, timesteps))
        .build())


def text_gen_lstm(seed: int = 123, vocab_size: int = 77, hidden: int = 256,
                  timesteps: Optional[int] = None, layers: int = 2) -> MultiLayerNetwork:
    """TextGenerationLSTM.java / char-RNN parity: stacked GravesLSTM +
    per-timestep softmax with tBPTT."""
    b = (NeuralNetConfiguration.builder()
         .seed(seed)
         .updater(Adam(2e-3))
         .weight_init("xavier")
         .gradient_normalization("clip_element_wise_absolute_value", 1.0)
         .list())
    for _ in range(layers):
        b.layer(GravesLSTM(n_out=hidden, activation="tanh"))
    b.layer(RnnOutputLayer(n_out=vocab_size, activation="softmax", loss="mcxent"))
    b.set_input_type(InputType.recurrent(vocab_size, timesteps))
    b.backprop_type("tbptt", 50, 50)
    return MultiLayerNetwork(b.build())
