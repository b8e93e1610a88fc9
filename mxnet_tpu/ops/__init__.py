"""Operator library. Importing this package registers all operators."""
from . import registry
from . import tensor  # noqa: F401
from . import nn  # noqa: F401
from . import random_ops  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import rnn_ops  # noqa: F401
from . import contrib  # noqa: F401
from . import quantization  # noqa: F401
from . import extra  # noqa: F401
from . import attention  # noqa: F401
from . import lm  # noqa: F401

from .registry import get_op, list_ops  # noqa: F401
