"""Model zoo — standard architectures as ready-to-init configs.

Reference parity: deeplearning4j-zoo (SURVEY.md §2.2 J14:
org/deeplearning4j/zoo/model/{LeNet,AlexNet,VGG16,VGG19,ResNet50,SqueezeNet,
Darknet19,UNet,Xception,SimpleCNN,TextGenerationLSTM}.java, each a ZooModel
with conf() + init()) — path-cite, mount empty this round.

Pretrained-weight download is stubbed: this machine has no egress; use
ModelSerializer restore for locally saved weights instead.
"""

from deeplearning4j_tpu.zoo.bert import Bert  # noqa: F401
from deeplearning4j_tpu.zoo.glm4_moe_lite import Glm4MoeLite  # noqa: F401
from deeplearning4j_tpu.zoo.jamba import Jamba  # noqa: F401
from deeplearning4j_tpu.zoo.kimi_linear import KimiLinear  # noqa: F401
from deeplearning4j_tpu.zoo.olmo_hybrid import OlmoHybrid  # noqa: F401
from deeplearning4j_tpu.zoo.unet import DiffusionUNet  # noqa: F401
from deeplearning4j_tpu.zoo.models import (  # noqa: F401
    AlexNet,
    Darknet19,
    FaceNetNN4Small2,
    LeNet,
    ResNet50,
    SimpleCNN,
    InceptionResNetV1,
    SqueezeNet,
    TextGenerationLSTM,
    TinyYOLO,
    YOLO2,
    UNet,
    VGG16,
    VGG19,
    Xception,
    ZooModel,
)
