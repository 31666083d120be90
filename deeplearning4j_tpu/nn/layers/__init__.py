"""Layer zoo.

Design note vs the reference: DL4J splits every layer into a config class
(nn/conf/layers/*.java) and an imperative impl class with hand-written
forward/backward (nn/layers/**). Under JAX, backprop is autodiff, so each
layer here is ONE dataclass carrying its hyperparameters plus pure
``init_params`` / ``apply`` functions. The JSON-polymorphism role of
Jackson subtype registration (ref: nn/conf/NeuralNetConfiguration.java:123)
is played by the ``LAYER_REGISTRY`` type-tag map.
"""

from deeplearning4j_tpu.nn.layers.base import (  # noqa: F401
    BaseLayerConf,
    LAYER_REGISTRY,
    register_layer,
    layer_from_dict,
)
from deeplearning4j_tpu.nn.layers.core import (  # noqa: F401
    DenseLayer,
    OutputLayer,
    LossLayer,
    ActivationLayer,
    DropoutLayer,
    EmbeddingLayer,
    AutoEncoder,
    RBM,
    CenterLossOutputLayer,
)
from deeplearning4j_tpu.nn.layers.convolution import (  # noqa: F401
    ConvolutionLayer,
    Convolution1DLayer,
    SubsamplingLayer,
    Subsampling1DLayer,
    ZeroPaddingLayer,
)
from deeplearning4j_tpu.nn.layers.normalization import (  # noqa: F401
    LayerNormalization,
    RMSNorm,
    BatchNormalization,
    LocalResponseNormalization,
)
from deeplearning4j_tpu.nn.layers.pooling import GlobalPoolingLayer  # noqa: F401
from deeplearning4j_tpu.nn.layers.recurrent import (  # noqa: F401
    GRU,
    LSTM,
    GravesLSTM,
    GravesBidirectionalLSTM,
    LastTimeStepLayer,
    RnnOutputLayer,
    SimpleRnn,
)
from deeplearning4j_tpu.nn.layers.shape import (  # noqa: F401
    PermuteLayer,
    RepeatVectorLayer,
    ReshapeLayer,
    TimeDistributedLayer,
    ZeroPadding1DLayer,
)
from deeplearning4j_tpu.nn.layers.variational import VariationalAutoencoder  # noqa: F401
from deeplearning4j_tpu.nn.layers.attention import (  # noqa: F401
    DifferentialAttentionLayer,
    GroupedQueryAttentionLayer,
    KeyValueProjectionLayer,
    QKNormAttentionLayer,
    SelfAttentionLayer,
    SparseIndexerLayer,
)
from deeplearning4j_tpu.nn.layers.embedding import (  # noqa: F401
    PositionalEmbeddingLayer,
    TiedRnnOutputLayer,
    TokenEmbeddingLayer,
)
from deeplearning4j_tpu.nn.layers.experts import RoutedExpertsLayer  # noqa: F401
from deeplearning4j_tpu.nn.layers.feedforward import GatedFeedForwardLayer  # noqa: F401
from deeplearning4j_tpu.nn.layers.linear_attention import GatedDeltaNetLayer  # noqa: F401
from deeplearning4j_tpu.nn.layers.state_space import (  # noqa: F401
    GatedMemoryUnitLayer,
    SelectiveScanLayer,
)
