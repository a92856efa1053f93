"""Model zoo — parity with DL4J ``deeplearning4j-zoo``
(``org/deeplearning4j/zoo/model/``: LeNet, AlexNet, VGG16, ResNet50,
SimpleCNN, TextGenerationLSTM, ...) plus the dl4j-examples workload models
named by BASELINE.json (MLPMnist, LSTM sequence classification, BERT).

Each zoo entry is a function returning a ready-to-init network built
through the public config API (so zoo models exercise the same code path
users write), except BERT which is a dedicated transformer module
(``deeplearning4j_tpu.models.bert``).
"""

from deeplearning4j_tpu.models.zoo import (
    mlp_mnist,
    lenet,
    simple_cnn,
    alexnet,
    vgg16,
    vgg19,
    resnet50,
    joyai_llm_flash,
    kimi_linear,
    lfm2_moe,
    lstm_classifier,
    text_gen_lstm,
)
from deeplearning4j_tpu.models.zoo_extra import (
    squeezenet,
    darknet19,
    tiny_yolo,
    yolo2,
    unet,
    xception,
    inception_resnet_v1,
    nasnet_mobile,
)
from deeplearning4j_tpu.models import bert

__all__ = [
    "mlp_mnist", "lenet", "simple_cnn", "alexnet", "vgg16", "vgg19",
    "resnet50", "joyai_llm_flash", "kimi_linear", "lfm2_moe",
    "lstm_classifier", "text_gen_lstm", "bert",
    "squeezenet", "darknet19", "tiny_yolo", "yolo2", "unet", "xception",
    "inception_resnet_v1", "nasnet_mobile",
]
