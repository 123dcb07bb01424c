"""mxnet_tpu — a TPU-native deep-learning framework with the capabilities of
Apache MXNet 1.3 (reference: XiaotaoChen/incubator-mxnet), rebuilt on
JAX/XLA/Pallas.

Usage mirrors the reference: ``import mxnet_tpu as mx`` then ``mx.nd``,
``mx.sym``, ``mx.gluon``, ``mx.mod``, ``mx.autograd``, ``mx.kvstore``...

Architecture (see SURVEY.md for the full mapping):
  * the async dependency engine        → XLA async dispatch (sync at read)
  * NNVM graph + GraphExecutor/CachedOp → jax tracing + whole-graph XLA compile
  * mshadow/CUDA kernels               → jax.numpy/lax + Pallas kernels
  * ps-lite/NCCL kvstore               → device-mesh collectives over ICI/DCN
"""
__version__ = "0.1.0"


from .base import MXNetError
from .context import Context, cpu, gpu, tpu, current_context, num_gpus, num_tpus
from . import ndarray
from . import ndarray as nd
from . import autograd
from . import random
from . import optimizer
from . import metric
from . import initializer
from . import lr_scheduler
from . import callback
from . import io
from . import kvstore as kvs  # module
from .kvstore import create as _kvstore_create
from . import engine
from . import profiler
from . import util
util.compile_cache_dir()
from . import faults
from . import env

init = initializer  # mx.init.Xavier() style access
kvstore = kvs
kv = kvs            # mx.kv.create(...) (reference python/mxnet/__init__.py)

from . import symbol
from . import symbol as sym
from . import operator
operator._install()
from . import module
from . import module as mod
from . import gluon
from . import image
from . import parallel
from . import test_utils
from . import recordio
from . import visualization
from . import visualization as viz
from . import attribute
from .attribute import AttrScope
from . import name
from . import model
from . import monitor
from .monitor import Monitor
from . import contrib
from . import rnn
from . import serving
from .executor import Executor
from . import rtc  # compat shim: runtime kernels are Pallas on TPU

from .util import is_np_array  # noqa: F401
