"""mxnet_tpu — a TPU-native deep learning framework with MXNet capabilities.

Brand-new design on JAX/XLA/PJRT (see SURVEY.md at repo root for the blueprint
and reference citations): NDArrays wrap PJRT buffers with async-future
semantics, operators are jax-traceable functions compiled per (op, attrs,
shapes), symbolic graphs lower to single XLA modules, and distributed data
parallelism rides XLA collectives over ICI/DCN behind the kvstore API.

Conventional usage mirrors MXNet:

    import mxnet_tpu as mx
    x = mx.nd.zeros((2, 3), ctx=mx.tpu(0))
    net = mx.sym.FullyConnected(mx.sym.Variable('data'), num_hidden=10)
"""
from __future__ import annotations

import time as _time
_T_IMPORT = _time.perf_counter()    # first: `import.mxnet_tpu` starts here

__version__ = "0.1.0"

from .base import MXNetError, AttrScope, NameManager
from .context import Context, cpu, gpu, tpu, current_context, num_gpus, num_tpus
from . import engine
from . import ops
from . import ndarray
from . import ndarray as nd
from . import random
from . import autograd
from . import symbol
from . import symbol as sym
from .symbol import Symbol
from . import executor
from .executor import Executor
from . import lr_scheduler
from . import optimizer
from .optimizer import Optimizer
from . import initializer
from . import initializer as init
from . import metric
from . import callback
from . import model
from . import io
from . import recordio
from . import kvstore
from . import kvstore as kv
from . import monitor
from . import contrib
from . import profiler
from . import visualization
from . import visualization as viz
from . import config
from . import operator
from . import rtc
from . import amp
config._apply_startup()
from .monitor import Monitor
from . import module
from . import module as mod
from . import parallel
from . import image
from . import gluon
from . import rnn
from . import serving
from . import pipeline
from . import checkpoint
from . import test_utils
from .telemetry import tracing as _tracing
_tracing.record_startup(_T_IMPORT)  # last: `import.mxnet_tpu` ends here
