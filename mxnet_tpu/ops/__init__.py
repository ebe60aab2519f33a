"""Operator library: registry + kernel modules.

Importing this package registers the full op surface (reference:
``src/operator/`` registration side effects at library load).
"""
from .registry import OpDef, register, get_op, list_ops, alias

from . import elemwise      # noqa: F401  (registration side effects)
from . import reduce        # noqa: F401
from . import matrix        # noqa: F401
from . import nn            # noqa: F401
from . import random_ops    # noqa: F401
from . import rnn           # noqa: F401
from . import control_flow  # noqa: F401
from . import vision        # noqa: F401
from . import contrib_ops   # noqa: F401
from . import detection     # noqa: F401
from . import quantization  # noqa: F401
from . import pallas_attention  # noqa: F401
from . import moe           # noqa: F401
from . import ssm           # noqa: F401
from . import delta_rule    # noqa: F401

__all__ = ["OpDef", "register", "get_op", "list_ops", "alias"]
