"""bigdl_tpu.nn — module system, layers, criterions.

TPU-native re-design of ``DL/nn/`` (reference: 413 files, 67,616 LoC).
See ``module.py`` for the functional contract that replaces
``AbstractModule``'s mutable forward/backward.
"""

from bigdl_tpu.nn.module import (
    Module, Container, Sequential, Concat, ConcatTable, ParallelTable,
    Identity, Echo, Lambda, Remat,
)
from bigdl_tpu.nn.initialization import (
    InitializationMethod, Zeros, Ones, ConstInitMethod, Xavier, MsraFiller,
    RandomUniform, RandomNormal, BilinearFiller,
)
from bigdl_tpu.nn.layers import (
    Linear, SpatialConvolution, SpatialFullConvolution, SpatialMaxPooling,
    SpatialAveragePooling, SpatialBatchNormalization, BatchNormalization,
    Dropout, LookupTable, SpatialCrossMapLRN, Normalize, NormalizeScale,
    CMul, CAdd,
    TemporalConvolution,
)
from bigdl_tpu.nn.activations import (
    ReLU, ReLU6, Tanh, Sigmoid, SoftMax, LogSoftMax, SoftPlus, SoftSign,
    ELU, LeakyReLU, HardTanh, HardSigmoid, GELU, SiLU, PReLU, RReLU, SReLU,
    Threshold, HardShrink, SoftShrink, LogSigmoid, SoftMin, TanhShrink,
)
from bigdl_tpu.nn.shape_ops import (
    Reshape, View, Flatten, Squeeze, Unsqueeze, Transpose, Contiguous,
    Narrow, Select, Index, Padding, SpatialZeroPadding, JoinTable,
    SplitTable, CAddTable, CMulTable, CSubTable, CDivTable, CMaxTable,
    CMinTable, FlattenTable, SelectTable, MulConstant, AddConstant, Power,
    Sqrt, Square, Abs, Exp, Log, Clamp, Mean, Sum, Max, Min, Replicate,
    Pack, Scale, Masking,
)
from bigdl_tpu.nn.criterion import (
    Criterion, ClassNLLCriterion, CrossEntropyCriterion, MSECriterion,
    AbsCriterion, BCECriterion, BCEWithLogitsCriterion, SmoothL1Criterion,
    DistKLDivCriterion, KLDCriterion, GaussianCriterion, MarginCriterion,
    MarginRankingCriterion, CosineEmbeddingCriterion,
    HingeEmbeddingCriterion, SoftMarginCriterion, L1Cost,
    DiceCoefficientCriterion, MultiLabelSoftMarginCriterion, MultiCriterion,
    ParallelCriterion, TimeDistributedCriterion, PGCriterion,
    MultiLabelMarginCriterion, SoftmaxWithCriterion,
    CosineDistanceCriterion, CosineProximityCriterion, DotProductCriterion,
    KullbackLeiblerDivergenceCriterion, L1HingeEmbeddingCriterion,
    MeanAbsolutePercentageCriterion, MeanSquaredLogarithmicCriterion,
    MultiMarginCriterion, PoissonCriterion, ClassSimplexCriterion,
    SmoothL1CriterionWithWeights, TimeDistributedMaskCriterion,
    TransformerCriterion, CategoricalCrossEntropy,
)
from bigdl_tpu.nn.graph import Graph, DynamicGraph, Input, Node
from bigdl_tpu.nn.control_flow import Cond, Merge, Switch, While
from bigdl_tpu.nn.recurrent import (
    Cell, RnnCell, LSTM, LSTMPeephole, GRU, ConvLSTMPeephole,
    ConvLSTMPeephole3D, MultiRNNCell,
    Recurrent, BiRecurrent, RecurrentDecoder, TimeDistributed,
)
from bigdl_tpu.nn.detection import (
    Anchor, Nms, nms, PriorBox, Proposal, RoiPooling, DetectionOutputSSD,
    DetectionOutputFrcnn,
    bbox_transform_inv, clip_boxes, box_iou,
)
from bigdl_tpu.nn.tree import TreeLSTM, BinaryTreeLSTM
from bigdl_tpu.nn.quantized import (
    quantize, QuantizedLinear, QuantizedSpatialConvolution,
)
from bigdl_tpu.nn.attention import (
    CompressedConvAttention, GatedAttention, GroupedQueryAttention,
    LayerNorm, MultiHeadAttention, RMSNorm, dot_product_attention, rms_norm,
    rope, zero_centred_rms_norm,
)
from bigdl_tpu.nn.gated_delta import GatedDeltaNet, chunked_gated_delta_rule
from bigdl_tpu.nn.mamba import (
    Mamba2Mixer, causal_depthwise_conv1d, gated_rms_norm, ssd_chunked_scan,
)
from bigdl_tpu.nn.moe import (
    ExpertParallelMoE, GatedMLP, LinearTopKRouter, MLPRouter, expert_rows,
)
from bigdl_tpu.nn.regularizers import (
    L1L2Regularizer, L1Regularizer, L2Regularizer, regularization_loss,
)
from bigdl_tpu.nn.sparse import (
    COOBatch, LookupTableSparse, SparseLinear, SparseJoinTable,
    DenseToSparse, coo_row_reduce, coo_spmm, dense_to_bags,
)
from bigdl_tpu.nn.volumetric import (
    VolumetricConvolution, VolumetricMaxPooling, VolumetricAveragePooling,
    VolumetricFullConvolution,
)
from bigdl_tpu.nn.spatial_extras import (
    SpatialDilatedConvolution, SpatialShareConvolution,
    SpatialSeparableConvolution, SpatialConvolutionMap,
    LocallyConnected1D, LocallyConnected2D, SpatialWithinChannelLRN,
    SpatialSubtractiveNormalization, SpatialDivisiveNormalization,
    SpatialContrastiveNormalization, SpatialDropout1D, SpatialDropout2D,
    SpatialDropout3D, UpSampling1D, UpSampling2D, UpSampling3D,
    ResizeBilinear, Cropping2D, Cropping3D, TemporalMaxPooling,
)
from bigdl_tpu.nn.tensor_extras import (
    MM, MV, DotProduct, CrossProduct, PairwiseDistance, CosineDistance,
    Bilinear, Cosine, Euclidean, Add, Mul, Maxout, Highway, MixtureTable,
    MaskedSelect, Reverse, Tile, Negative, InferReshape, NarrowTable,
    CAveTable, BifurcateSplitTable, Bottle, MapTable, GradientReversal,
    GaussianDropout, GaussianNoise, GaussianSampler, L1Penalty,
    NegativeEntropyPenalty, ActivityRegularization, BinaryThreshold,
)
from bigdl_tpu.nn.kda import KimiDeltaAttention, chunked_kda_rule
from bigdl_tpu.nn.mla import MultiHeadLatentAttention
from bigdl_tpu.nn.moe import GroupLimitedSigmoidRouter
