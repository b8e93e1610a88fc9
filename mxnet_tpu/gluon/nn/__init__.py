"""gluon.nn — neural network layers."""
from .basic_layers import *
from .conv_layers import *
from .transformer import *
from .decoder import *
from . import basic_layers
from . import conv_layers
from . import transformer
from . import decoder

__all__ = basic_layers.__all__ + conv_layers.__all__ + transformer.__all__ \
    + decoder.__all__
