"""Operator registry + the full op library.

Importing this package registers every op (the analog of static
``NNVM_REGISTER_OP`` blocks running at library load in the reference).
"""
from .registry import Op, register, get_op, list_ops, alias

from . import elemwise        # noqa: F401
from . import reduce_ops      # noqa: F401
from . import tensor_ops      # noqa: F401
from . import nn_ops          # noqa: F401
from . import random_ops      # noqa: F401
from . import optimizer_ops   # noqa: F401
from . import linalg_ops      # noqa: F401
from . import contrib_ops     # noqa: F401
from . import quantization_ops  # noqa: F401
from . import pallas_ops      # noqa: F401
from . import decoder_ops     # noqa: F401
from . import sparse_ops      # noqa: F401
from . import misc_ops       # noqa: F401
