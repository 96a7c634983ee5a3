"""Model zoo (reference ``DL/models/``)."""

from bigdl_tpu.models.lenet import lenet5
from bigdl_tpu.models.resnet import resnet_cifar, resnet50
from bigdl_tpu.models.vgg import vgg_for_cifar10, vgg16
from bigdl_tpu.models.inception import inception_v1
from bigdl_tpu.models.rnn import simple_rnn, ptb_model
from bigdl_tpu.models.autoencoder import autoencoder
from bigdl_tpu.models.transformer import (
    transformer_lm, transformer_block, LearnedPositionalEmbedding,
)
from bigdl_tpu.models.recommender import NeuralCF, WideAndDeep
from bigdl_tpu.models.granite_moe_hybrid import (
    GraniteMoeHybrid, GraniteMoeHybridLayer, granite_moe_hybrid,
)
from bigdl_tpu.models.zaya import Zaya, ZayaLayer, zaya
