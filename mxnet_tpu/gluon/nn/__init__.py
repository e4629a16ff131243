"""Neural network layers (reference: python/mxnet/gluon/nn/)."""
from .activations import *  # noqa: F401,F403
from .basic_layers import *  # noqa: F401,F403
from .conv_layers import *  # noqa: F401,F403
from .sequence_layers import *  # noqa: F401,F403
from .layout import (  # noqa: F401
    channel_axis, default_layout, is_channel_last, resolve_layout)
