"""paddle.nn parity surface (`python/paddle/nn/`)."""
from . import functional  # noqa: F401
from . import initializer  # noqa: F401
from .clip import (  # noqa: F401
    ClipGradByGlobalNorm, ClipGradByNorm, ClipGradByValue, clip_grad_norm_,
    clip_grad_value_,
)
from .layer_base import Layer  # noqa: F401
from .layers_activation import *  # noqa: F401,F403
from .layers_common import *  # noqa: F401,F403
from .layers_conv_pool import *  # noqa: F401,F403
from .layers_loss import *  # noqa: F401,F403
from .layers_norm import *  # noqa: F401,F403
from .layers_rnn import *  # noqa: F401,F403
from . import utils  # noqa: F401
from . import quant  # noqa: F401
from .layers_transformer import *  # noqa: F401,F403
from .layers_sparse_index import *  # noqa: F401,F403
from ..core.tensor import Parameter  # noqa: F401


class ParamAttr:
    """paddle.ParamAttr parity: bundles name/initializer/lr/clip options."""

    def __init__(self, name=None, initializer=None, learning_rate=1.0,
                 regularizer=None, trainable=True, do_model_average=True,
                 need_clip=True):
        self.name = name
        self.initializer = initializer
        self.learning_rate = learning_rate
        self.regularizer = regularizer
        self.trainable = trainable
        self.need_clip = need_clip
