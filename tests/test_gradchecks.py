"""Per-layer-type numerical gradient checks — the GradientCheckUtil spine.

Parity with deeplearning4j-nn ``gradientcheck/GradientCheckUtil.java`` and
its suites (GradientCheckTests, CNNGradientCheckTest,
LSTMGradientCheckTests): every registered layer type is exercised inside a
small full network and its end-to-end loss gradient is validated against
central differences in float64 on CPU.  Every registered loss function is
checked the same way through an OutputLayer.

Run in x64: central differences in f32 are too noisy for a 1e-3 rel bound.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.autodiff.gradcheck import check_model_gradients
from deeplearning4j_tpu.config import DTypePolicy, set_dtype_policy
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.nn import NeuralNetConfiguration, InputType
from deeplearning4j_tpu.nn.layers import *  # noqa: F401,F403
from deeplearning4j_tpu.nn.layers.base import layer_registry
from deeplearning4j_tpu.nn import losses as losses_mod
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.train import Sgd


_CASE_COUNTER = iter(range(10 ** 9))


@pytest.fixture(autouse=True)
def _periodic_cache_clear():
    """XLA:CPU segfaults inside backend_compile after ~50 accumulated
    f64 compilations in one process (state-dependent compiler bug:
    reproducible at the 48th test of this module under the 8-device CPU
    mesh, passes in isolation).  Dropping the jit caches every few cases
    keeps the compiler out of the poisoned state."""
    yield
    if next(_CASE_COUNTER) % 8 == 7:
        jax.clear_caches()


@pytest.fixture(autouse=True, scope="module")
def _x64():
    jax.config.update("jax_enable_x64", True)
    set_dtype_policy(DTypePolicy(param_dtype=jnp.float64,
                                 compute_dtype=jnp.float64,
                                 output_dtype=jnp.float64))
    yield
    set_dtype_policy(DTypePolicy.f32())
    jax.config.update("jax_enable_x64", False)


def _r():
    return np.random.default_rng(0)


def _ff_batch(n_in, n_out, b=4):
    r = _r()
    x = r.normal(size=(b, n_in))
    y = np.eye(n_out)[r.integers(0, n_out, b)]
    return DataSet(x, y)


def _rnn_batch(n_in, n_out, t=5, b=3):
    r = _r()
    x = r.normal(size=(b, t, n_in))
    y = np.zeros((b, t, n_out))
    y[np.arange(b)[:, None], np.arange(t)[None, :],
      r.integers(0, n_out, (b, t))] = 1.0
    return DataSet(x, y)


def _cnn_batch(h, w, c, n_out, b=2):
    r = _r()
    x = r.normal(size=(b, h, w, c))
    y = np.eye(n_out)[r.integers(0, n_out, b)]
    return DataSet(x, y)


def _cnn3d_batch(d, h, w, c, n_out, b=2):
    r = _r()
    x = r.normal(size=(b, d, h, w, c))
    y = np.eye(n_out)[r.integers(0, n_out, b)]
    return DataSet(x, y)


FF_OUT = lambda n=3: OutputLayer(n_out=n, activation="softmax", loss="mcxent")
RNN_OUT = lambda n=3: RnnOutputLayer(n_out=n, activation="softmax", loss="mcxent")

# type-name → (layers, input_type, batch builder).  Smooth activations
# (tanh/softplus) keep the central difference well-behaved; max-pool /
# relu kinks are measure-zero under the random inputs.
LAYER_CASES = {
    "dense": ([DenseLayer(n_out=6, activation="tanh"), FF_OUT()],
              InputType.feed_forward(4), lambda: _ff_batch(4, 3)),
    "output": ([FF_OUT()], InputType.feed_forward(4), lambda: _ff_batch(4, 3)),
    "loss": ([DenseLayer(n_out=3, activation="softmax"), LossLayer(loss="mcxent")],
             InputType.feed_forward(4), lambda: _ff_batch(4, 3)),
    "activation": ([DenseLayer(n_out=6, activation="identity"),
                    ActivationLayer(activation="softplus"), FF_OUT()],
                   InputType.feed_forward(4), lambda: _ff_batch(4, 3)),
    "dropout": ([DropoutLayer(dropout=0.5), DenseLayer(n_out=6, activation="tanh"),
                 FF_OUT()],
                InputType.feed_forward(4), lambda: _ff_batch(4, 3)),
    "embedding": ([EmbeddingLayer(n_in=7, n_out=5), DenseLayer(n_out=6, activation="tanh"),
                   FF_OUT()],
                  InputType.feed_forward(1),
                  lambda: DataSet(_r().integers(0, 7, (4, 1)).astype(np.float64),
                                  np.eye(3)[_r().integers(0, 3, 4)])),
    "embedding_sequence": ([EmbeddingSequenceLayer(n_in=7, n_out=5), RNN_OUT()],
                           InputType.recurrent(1, 5),
                           lambda: DataSet(
                               _r().integers(0, 7, (3, 5, 1)).astype(np.float64),
                               np.eye(3)[_r().integers(0, 3, (3, 5))])),
    "batch_norm": ([DenseLayer(n_out=6, activation="tanh"), BatchNormalization(),
                    FF_OUT()],
                   InputType.feed_forward(4), lambda: _ff_batch(4, 3)),
    "layer_norm": ([DenseLayer(n_out=6, activation="tanh"), LayerNormalization(),
                    FF_OUT()],
                   InputType.feed_forward(4), lambda: _ff_batch(4, 3)),
    "prelu": ([DenseLayer(n_out=6, activation="identity"), PReLULayer(), FF_OUT()],
              InputType.feed_forward(4), lambda: _ff_batch(4, 3)),
    "conv2d": ([ConvolutionLayer(n_out=4, kernel_size=(3, 3), activation="tanh"),
                GlobalPoolingLayer(pooling_type="avg"), FF_OUT()],
               InputType.convolutional(8, 8, 2), lambda: _cnn_batch(8, 8, 2, 3)),
    "conv1d": ([Convolution1DLayer(n_out=4, kernel_size=3, activation="tanh"), RNN_OUT()],
               InputType.recurrent(2, 6),
               # truncate mode: t 6→4, labels must match the output length
               lambda: DataSet(_r().normal(size=(3, 6, 2)),
                               _rnn_batch(3, 3, t=4).labels)),
    "conv3d": ([Convolution3DLayer(n_out=3, kernel_size=(2, 2, 2), activation="tanh"),
                GlobalPoolingLayer(pooling_type="avg"), FF_OUT()],
               InputType.convolutional3d(4, 4, 4, 2),
               lambda: _cnn3d_batch(4, 4, 4, 2, 3)),
    "separable_conv2d": ([SeparableConvolution2D(n_out=4, kernel_size=(3, 3),
                                                 activation="tanh"),
                          GlobalPoolingLayer(pooling_type="avg"), FF_OUT()],
                         InputType.convolutional(8, 8, 2),
                         lambda: _cnn_batch(8, 8, 2, 3)),
    "depthwise_conv2d": ([DepthwiseConvolution2D(depth_multiplier=2, kernel_size=(3, 3),
                                                 activation="tanh"),
                          GlobalPoolingLayer(pooling_type="avg"), FF_OUT()],
                         InputType.convolutional(8, 8, 2),
                         lambda: _cnn_batch(8, 8, 2, 3)),
    "deconv2d": ([Deconvolution2D(n_out=4, kernel_size=(3, 3), activation="tanh"),
                  GlobalPoolingLayer(pooling_type="avg"), FF_OUT()],
                 InputType.convolutional(6, 6, 2), lambda: _cnn_batch(6, 6, 2, 3)),
    "subsampling": ([ConvolutionLayer(n_out=4, kernel_size=(3, 3), activation="tanh"),
                     SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)),
                     GlobalPoolingLayer(pooling_type="avg"), FF_OUT()],
                    InputType.convolutional(8, 8, 2), lambda: _cnn_batch(8, 8, 2, 3)),
    "subsampling1d": ([Convolution1DLayer(n_out=4, kernel_size=3, activation="tanh"),
                       Subsampling1DLayer(kernel_size=2, stride=2), RNN_OUT()],
                      InputType.recurrent(2, 8),
                      # conv t 8→6, pool 6→3
                      lambda: DataSet(_r().normal(size=(3, 8, 2)),
                                      _rnn_batch(3, 3, t=3).labels)),
    "subsampling3d": ([Convolution3DLayer(n_out=3, kernel_size=(2, 2, 2), activation="tanh"),
                       Subsampling3DLayer(kernel_size=(2, 2, 2), stride=(2, 2, 2)),
                       GlobalPoolingLayer(pooling_type="avg"), FF_OUT()],
                      InputType.convolutional3d(4, 4, 4, 2),
                      lambda: _cnn3d_batch(4, 4, 4, 2, 3)),
    "upsampling2d": ([ConvolutionLayer(n_out=3, kernel_size=(3, 3), activation="tanh"),
                      UpsamplingLayer(size=2),
                      GlobalPoolingLayer(pooling_type="avg"), FF_OUT()],
                     InputType.convolutional(6, 6, 2), lambda: _cnn_batch(6, 6, 2, 3)),
    "zero_padding": ([ZeroPaddingLayer(padding=(1, 1, 1, 1)),
                      ConvolutionLayer(n_out=3, kernel_size=(3, 3), activation="tanh"),
                      GlobalPoolingLayer(pooling_type="avg"), FF_OUT()],
                     InputType.convolutional(6, 6, 2), lambda: _cnn_batch(6, 6, 2, 3)),
    "cropping2d": ([CroppingLayer(cropping=(1, 1, 1, 1)),
                    ConvolutionLayer(n_out=3, kernel_size=(3, 3), activation="tanh"),
                    GlobalPoolingLayer(pooling_type="avg"), FF_OUT()],
                   InputType.convolutional(8, 8, 2), lambda: _cnn_batch(8, 8, 2, 3)),
    "space_to_depth": ([SpaceToDepthLayer(block_size=2),
                        ConvolutionLayer(n_out=3, kernel_size=(1, 1), activation="tanh"),
                        GlobalPoolingLayer(pooling_type="avg"), FF_OUT()],
                       InputType.convolutional(6, 6, 2), lambda: _cnn_batch(6, 6, 2, 3)),
    "global_pooling": ([ConvolutionLayer(n_out=4, kernel_size=(3, 3), activation="tanh"),
                        GlobalPoolingLayer(pooling_type="pnorm"), FF_OUT()],
                       InputType.convolutional(6, 6, 2), lambda: _cnn_batch(6, 6, 2, 3)),
    "lrn": ([ConvolutionLayer(n_out=4, kernel_size=(3, 3), activation="tanh"),
             LocalResponseNormalization(),
             GlobalPoolingLayer(pooling_type="avg"), FF_OUT()],
            InputType.convolutional(6, 6, 2), lambda: _cnn_batch(6, 6, 2, 3)),
    "lstm": ([LSTM(n_out=5), RNN_OUT()],
             InputType.recurrent(3, 5), lambda: _rnn_batch(3, 3)),
    "graves_lstm": ([GravesLSTM(n_out=5), RNN_OUT()],
                    InputType.recurrent(3, 5), lambda: _rnn_batch(3, 3)),
    "simple_rnn": ([SimpleRnn(n_out=5), RNN_OUT()],
                   InputType.recurrent(3, 5), lambda: _rnn_batch(3, 3)),
    "gru": ([GRU(n_out=5), RNN_OUT()],
            InputType.recurrent(3, 5), lambda: _rnn_batch(3, 3)),
    "bidirectional": ([Bidirectional(fwd=LSTM(n_out=4), mode="concat"), RNN_OUT()],
                      InputType.recurrent(3, 5), lambda: _rnn_batch(3, 3)),
    "last_time_step": ([LastTimeStep(underlying=LSTM(n_out=5)), FF_OUT()],
                       InputType.recurrent(3, 5),
                       lambda: DataSet(_r().normal(size=(3, 5, 3)),
                                       np.eye(3)[_r().integers(0, 3, 3)])),
    "time_distributed": ([LSTM(n_out=5),
                          TimeDistributed(underlying=DenseLayer(n_out=4, activation="tanh")),
                          RNN_OUT()],
                         InputType.recurrent(3, 5), lambda: _rnn_batch(3, 3)),
    "rnn_output": ([SimpleRnn(n_out=5), RNN_OUT()],
                   InputType.recurrent(3, 5), lambda: _rnn_batch(3, 3)),
    "rnn_loss": ([SimpleRnn(n_out=3, activation="identity"),
                  ActivationLayer(activation="softmax"), RnnLossLayer(loss="mcxent")],
                 InputType.recurrent(3, 5), lambda: _rnn_batch(3, 3)),
    "self_attention": ([SelfAttentionLayer(n_heads=2), RNN_OUT()],
                       InputType.recurrent(4, 5), lambda: _rnn_batch(4, 3)),
    "learned_self_attention": ([LearnedSelfAttentionLayer(n_heads=2, n_queries=3),
                                GlobalPoolingLayer(pooling_type="avg"), FF_OUT()],
                               InputType.recurrent(4, 5),
                               lambda: DataSet(_r().normal(size=(3, 5, 4)),
                                               np.eye(3)[_r().integers(0, 3, 3)])),
    # ---- layer-catalog tail (nn/layers/extra.py) -----------------------
    "zero_padding1d": ([ZeroPadding1DLayer(padding=1), RNN_OUT()],
                       InputType.recurrent(3, 5),
                       lambda: DataSet(_r().normal(size=(3, 5, 3)),
                                       _rnn_batch(3, 3, t=7).labels)),
    "cropping1d": ([Cropping1DLayer(cropping=1), RNN_OUT()],
                   InputType.recurrent(3, 5),
                   lambda: DataSet(_r().normal(size=(3, 5, 3)),
                                   _rnn_batch(3, 3, t=3).labels)),
    "upsampling1d": ([Upsampling1DLayer(size=2), RNN_OUT()],
                     InputType.recurrent(3, 4),
                     lambda: DataSet(_r().normal(size=(3, 4, 3)),
                                     _rnn_batch(3, 3, t=8).labels)),
    "zero_padding3d": ([ZeroPadding3DLayer(padding=1),
                        GlobalPoolingLayer(pooling_type="avg"), FF_OUT()],
                       InputType.convolutional3d(3, 3, 3, 2),
                       lambda: _cnn3d_batch(3, 3, 3, 2, 3)),
    "cropping3d": ([Cropping3DLayer(cropping=1),
                    GlobalPoolingLayer(pooling_type="avg"), FF_OUT()],
                   InputType.convolutional3d(4, 4, 4, 2),
                   lambda: _cnn3d_batch(4, 4, 4, 2, 3)),
    "upsampling3d": ([Upsampling3DLayer(size=2),
                      GlobalPoolingLayer(pooling_type="avg"), FF_OUT()],
                     InputType.convolutional3d(2, 2, 2, 2),
                     lambda: _cnn3d_batch(2, 2, 2, 2, 3)),
    "space_to_batch": ([SpaceToBatchLayer(blocks=2),
                        GlobalPoolingLayer(pooling_type="avg"), FF_OUT()],
                       InputType.convolutional(4, 4, 2),
                       # blocks 2x2 quadruple the batch: labels for 4*B rows
                       lambda: DataSet(_r().normal(size=(2, 4, 4, 2)),
                                       np.eye(3)[_r().integers(0, 3, 8)])),
    "gaussian_dropout": ([GaussianDropoutLayer(rate=0.1),
                          DenseLayer(n_out=5, activation="tanh"), FF_OUT()],
                         InputType.feed_forward(4), lambda: _ff_batch(4, 3)),
    "gaussian_noise": ([GaussianNoiseLayer(stddev=0.1),
                        DenseLayer(n_out=5, activation="tanh"), FF_OUT()],
                       InputType.feed_forward(4), lambda: _ff_batch(4, 3)),
    "alpha_dropout": ([AlphaDropoutLayer(p=0.9),
                       DenseLayer(n_out=5, activation="tanh"), FF_OUT()],
                      InputType.feed_forward(4), lambda: _ff_batch(4, 3)),
    "spatial_dropout": ([SpatialDropoutLayer(p=0.9),
                         ConvolutionLayer(n_out=3, kernel_size=(3, 3),
                                          activation="tanh"),
                         GlobalPoolingLayer(pooling_type="avg"), FF_OUT()],
                        InputType.convolutional(6, 6, 2),
                        lambda: _cnn_batch(6, 6, 2, 3)),
    "locally_connected1d": ([LocallyConnected1D(n_out=4, kernel=3,
                                                activation="tanh"), RNN_OUT()],
                            InputType.recurrent(2, 6),
                            lambda: DataSet(_r().normal(size=(3, 6, 2)),
                                            _rnn_batch(3, 3, t=4).labels)),
    "locally_connected2d": ([LocallyConnected2D(n_out=4, kernel=3,
                                                activation="tanh"),
                             GlobalPoolingLayer(pooling_type="avg"), FF_OUT()],
                            InputType.convolutional(6, 6, 2),
                            lambda: _cnn_batch(6, 6, 2, 3)),
    "element_wise_mult": ([DenseLayer(n_out=5, activation="tanh"),
                           ElementWiseMultiplicationLayer(n_out=5,
                                                          activation="tanh"),
                           FF_OUT()],
                          InputType.feed_forward(4), lambda: _ff_batch(4, 3)),
    "repeat_vector": ([DenseLayer(n_out=5, activation="tanh"),
                       RepeatVector(n=4), RNN_OUT()],
                      InputType.feed_forward(4),
                      lambda: DataSet(_r().normal(size=(3, 4)),
                                      _rnn_batch(3, 3, t=4).labels)),
    "mask_zero": ([MaskZeroLayer(underlying=LSTM(n_out=5)), RNN_OUT()],
                  InputType.recurrent(3, 5), lambda: _rnn_batch(3, 3)),
    "permute": ([PermuteLayer(dims=(2, 1)), RNN_OUT()],
                InputType.recurrent(3, 4),
                lambda: DataSet(_r().normal(size=(3, 4, 3)),
                                _rnn_batch(3, 3, t=3).labels)),
    "separable_conv1d": ([SeparableConvolution1D(n_out=4, kernel_size=3,
                                                 activation="tanh"),
                          RNN_OUT()],
                         InputType.recurrent(2, 6),
                         lambda: DataSet(_r().normal(size=(3, 6, 2)),
                                         _rnn_batch(3, 3, t=4).labels)),
    "conv_lstm2d": ([ConvLSTM2D(n_out=3, kernel_size=(2, 2),
                                convolution_mode="same"),
                     GlobalPoolingLayer(pooling_type="avg"), FF_OUT()],
                    InputType.convolutional3d(3, 4, 4, 2),
                    lambda: DataSet(_r().normal(size=(2, 3, 4, 4, 2)),
                                    np.eye(3)[_r().integers(0, 3, 2)])),
    "bidirectional_last": ([BidirectionalLastStep(fwd=LSTM(n_out=4),
                                                  mode="concat"), FF_OUT()],
                           InputType.recurrent(3, 5),
                           lambda: DataSet(_r().normal(size=(3, 5, 3)),
                                           np.eye(3)[_r().integers(0, 3, 3)])),
    "graves_bidirectional_lstm": ([GravesBidirectionalLSTM(n_out=5), RNN_OUT()],
                                  InputType.recurrent(3, 5),
                                  lambda: _rnn_batch(3, 3)),
    "center_loss_output": ([DenseLayer(n_out=6, activation="tanh"),
                            CenterLossOutputLayer(n_out=3, activation="softmax",
                                                  loss="mcxent", lambda_=1e-2)],
                           InputType.feed_forward(4), lambda: _ff_batch(4, 3)),
    "yolo2_output": ([ConvolutionLayer(n_out=14, kernel_size=(1, 1),
                                       activation="identity"),
                      Yolo2OutputLayer(anchors=((1.0, 1.5), (2.0, 1.0)),
                                       num_classes=2)],
                     InputType.convolutional(3, 3, 4),
                     lambda: DataSet(_r().normal(size=(2, 3, 3, 4)),
                                     _yolo_batch(3, 3, 2, 2).labels)),
    "vae": ([VariationalAutoencoder(n_out=3, encoder_layer_sizes=(6,),
                                    decoder_layer_sizes=(6,),
                                    activation="tanh",
                                    reconstruction="gaussian")],
            InputType.feed_forward(4),
            lambda: (lambda x: DataSet(x, x))(_r().normal(size=(3, 4)))),
    "primary_capsules": ([PrimaryCapsules(capsules=2, capsule_dimensions=4,
                                          kernel=3, stride=2),
                          GlobalPoolingLayer(pooling_type="avg"), FF_OUT()],
                         InputType.convolutional(7, 7, 2),
                         lambda: _cnn_batch(7, 7, 2, 3)),
    "capsules": ([CapsuleLayer(capsules=3, capsule_dimensions=4, routings=2),
                  GlobalPoolingLayer(pooling_type="avg"), FF_OUT()],
                 InputType.recurrent(4, 6),
                 lambda: DataSet(_r().normal(size=(3, 6, 4)),
                                 np.eye(3)[_r().integers(0, 3, 3)])),
    "capsule_strength": ([CapsuleStrengthLayer(), FF_OUT()],
                         InputType.recurrent(4, 5),
                         lambda: DataSet(_r().normal(size=(3, 5, 4)),
                                         np.eye(3)[_r().integers(0, 3, 3)])),
    "recurrent_attention": ([RecurrentAttentionLayer(n_out=4, activation="tanh"),
                             RNN_OUT()],
                            InputType.recurrent(3, 5), lambda: _rnn_batch(3, 3)),
    # one branch of the ResNet bottleneck (zoo._conv_bn); relu kinks are
    # measure-zero under random inputs (as for max-pool)
    "conv1x1_nobias_bn_relu": ([ConvolutionLayer(n_out=8, kernel_size=(1, 1),
                                                 has_bias=False,
                                                 activation="identity"),
                                BatchNormalization(activation="relu"),
                                GlobalPoolingLayer(pooling_type="avg"),
                                FF_OUT()],
                               InputType.convolutional(6, 6, 4),
                               lambda: _cnn_batch(6, 6, 4, 3)),
    # generous capacity: no token drops, so routing is locally constant
    # and the loss is differentiable at the sampled inputs
    # ---- decoder-only language model layers (nn/layers/decoder.py) -------
    "rms_norm": ([DenseLayer(n_out=6, activation="tanh"), RMSNorm(),
                  FF_OUT()],
                 InputType.feed_forward(4), lambda: _ff_batch(4, 3)),
    "gated_feed_forward": ([GatedFeedForward(hidden=6, init_std=0.5),
                            FF_OUT()],
                           InputType.feed_forward(4), lambda: _ff_batch(4, 3)),
    "latent_attention": ([LatentAttention(
        n_heads=2, q_lora_rank=4, kv_lora_rank=4, qk_nope_head_dim=2,
        qk_rope_head_dim=2, v_head_dim=3, rope_theta=100.0, init_std=0.5),
        RNN_OUT()],
        InputType.recurrent(4, 5), lambda: _rnn_batch(4, 3)),
    # 5 tokens in chunks of 4: two chunks, the second padded
    "delta_attention": ([DeltaAttention(n_heads=2, head_dim=3, chunk=4,
                                        init_std=0.5), RNN_OUT()],
                        InputType.recurrent(4, 5), lambda: _rnn_batch(4, 3)),
    # LFM2's token mixers: 5 tokens through 3 taps; 2 query heads over one
    # K/V head (the einsum chain at this length)
    "gated_short_conv": ([GatedShortConv(taps=3, init_std=0.5), RNN_OUT()],
                         InputType.recurrent(4, 5), lambda: _rnn_batch(4, 3)),
    "grouped_query_attention": ([GroupedQueryAttention(
        n_heads=2, n_kv_heads=1, head_dim=2, rope_theta=100.0,
        init_std=0.5), RNN_OUT()],
        InputType.recurrent(4, 5), lambda: _rnn_batch(4, 3)),
    "routed_experts": ([RoutedExperts(
        n_routed_experts=4, experts_held=2, first_expert=1, top_k=2, hidden=6,
        shared_hidden=6, routed_scaling_factor=2.5, init_std=0.5), FF_OUT()],
        InputType.feed_forward(4), lambda: _ff_batch(4, 3)),
    "causal_lm_output": ([EmbeddingSequenceLayer(n_in=7, n_out=5),
                          CausalLMOutput(n_out=7, init_std=0.5)],
                         InputType.recurrent(1, 5),
                         lambda: (lambda ids: DataSet(ids[..., None], ids))(
                             _r().integers(0, 7, (3, 5)).astype(np.float64))),
    "mixture_of_experts": ([MixtureOfExperts(n_experts=3, hidden=6, top_k=2,
                                             capacity_factor=3.0,
                                             activation="tanh"),
                            FF_OUT()],
                           InputType.feed_forward(4), lambda: _ff_batch(4, 3)),
}


def _yolo_batch(h, w, a, c, b=2):
    """Grid labels: per anchor (tx,ty,tw,th,obj,classes) with obj∈{0,1}
    and one-hot classes on object cells."""
    r = _r()
    x = r.normal(size=(b, h, w, a * (5 + c)))
    y = np.zeros((b, h, w, a, 5 + c))
    obj = r.integers(0, 2, (b, h, w, a))
    y[..., 0:2] = r.uniform(0.2, 0.8, (b, h, w, a, 2))
    y[..., 2:4] = r.normal(0, 0.3, (b, h, w, a, 2))
    y[..., 4] = obj
    cls = np.eye(c)[r.integers(0, c, (b, h, w, a))]
    y[..., 5:] = cls * obj[..., None]
    return DataSet(x, y.reshape(b, h, w, a * (5 + c)))


def test_all_registered_layer_types_have_gradcheck_cases():
    """Every type in the registry must appear in LAYER_CASES — adding a
    layer without a gradcheck fails the suite (OpValidation's coverage
    discipline applied to layers)."""
    registered = set(layer_registry())
    missing = registered - set(LAYER_CASES)
    assert not missing, f"layer types without gradcheck cases: {sorted(missing)}"


@pytest.mark.parametrize("type_name", sorted(LAYER_CASES))
def test_layer_gradcheck(type_name):
    layers, itype, batch_fn = LAYER_CASES[type_name]
    builder = NeuralNetConfiguration.builder().seed(12345).updater(Sgd(0.1)).list()
    for layer in layers:
        builder = builder.layer(layer)
    conf = builder.set_input_type(itype).build()
    net = MultiLayerNetwork(conf).init()
    report = check_model_gradients(net, batch_fn(), eps=1e-5,
                                   max_rel_error=1e-3,
                                   max_checks_per_leaf=10)
    assert report["checked"] > 0, f"{type_name}: no gradient entries checked"


SMOOTH_LOSS_DATA = {
    # loss name → (activation, labels builder over (b, n))
    "mcxent": ("softmax", lambda b, n: np.eye(n)[_r().integers(0, n, b)]),
    "sparse_mcxent": ("softmax", lambda b, n: _r().integers(0, n, (b,)).astype(np.float64)),
    "binary_xent": ("sigmoid", lambda b, n: _r().integers(0, 2, (b, n)).astype(np.float64)),
    "mse": ("identity", lambda b, n: _r().normal(size=(b, n))),
    "l2": ("identity", lambda b, n: _r().normal(size=(b, n))),
    "mae": ("identity", lambda b, n: _r().normal(size=(b, n))),
    "l1": ("identity", lambda b, n: _r().normal(size=(b, n))),
    "msle": ("sigmoid", lambda b, n: _r().uniform(0.1, 2.0, (b, n))),
    "mape": ("identity", lambda b, n: _r().uniform(0.5, 2.0, (b, n))),
    "poisson": ("softplus", lambda b, n: _r().uniform(0.1, 3.0, (b, n))),
    "kld": ("softmax", lambda b, n: (lambda p: p / p.sum(-1, keepdims=True))(
        _r().uniform(0.1, 1.0, (b, n)))),
    "kl_divergence": ("softmax", lambda b, n: (lambda p: p / p.sum(-1, keepdims=True))(
        _r().uniform(0.1, 1.0, (b, n)))),
    "cosine_proximity": ("identity", lambda b, n: _r().normal(size=(b, n))),
    "hinge": ("identity", lambda b, n: 2.0 * _r().integers(0, 2, (b, n)) - 1.0),
    "squared_hinge": ("identity", lambda b, n: 2.0 * _r().integers(0, 2, (b, n)) - 1.0),
    "wasserstein": ("identity", lambda b, n: 2.0 * _r().integers(0, 2, (b, n)) - 1.0),
    "fmeasure": ("sigmoid", lambda b, n: _r().integers(0, 2, (b, n)).astype(np.float64)),
    # |err| = delta kink is measure-zero under random labels
    "huber": ("identity", lambda b, n: _r().normal(size=(b, n))),
    "log_poisson": ("identity", lambda b, n: _r().uniform(0.1, 3.0, (b, n))),
    # labels fixed during the check: the labels>1 Stirling gate is constant
    "log_poisson_full": ("identity", lambda b, n: _r().uniform(0.1, 3.0, (b, n))),
    "weighted_cross_entropy_with_logits": (
        "identity", lambda b, n: _r().integers(0, 2, (b, n)).astype(np.float64)),
    "mean_pairwise_squared_error": (
        "identity", lambda b, n: _r().normal(size=(b, n))),
}


def test_all_registered_losses_have_gradcheck_cases():
    """Every DISTINCT loss function (names() includes aliases) must have a
    gradcheck case under at least one of its names."""
    covered_fns = {id(losses_mod.get(n)) for n in SMOOTH_LOSS_DATA}
    missing = [n for n in losses_mod.names()
               if id(losses_mod.get(n)) not in covered_fns]
    assert not missing, f"losses without gradcheck cases: {sorted(missing)}"


@pytest.mark.parametrize("loss_name", sorted(SMOOTH_LOSS_DATA))
def test_loss_gradcheck(loss_name):
    act, label_fn = SMOOTH_LOSS_DATA[loss_name]
    n = 4
    conf = (NeuralNetConfiguration.builder().seed(7).updater(Sgd(0.1)).list()
            .layer(DenseLayer(n_out=5, activation="tanh"))
            .layer(OutputLayer(n_out=n, activation=act, loss=loss_name))
            .set_input_type(InputType.feed_forward(3))
            .build())
    net = MultiLayerNetwork(conf).init()
    b = 4
    x = _r().normal(size=(b, 3))
    batch = DataSet(x, label_fn(b, n))
    report = check_model_gradients(net, batch, eps=1e-5, max_rel_error=1e-3,
                                   max_checks_per_leaf=10)
    assert report["checked"] > 0
