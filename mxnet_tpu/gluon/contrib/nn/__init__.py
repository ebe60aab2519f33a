"""Contrib neural-network layers (reference
``python/mxnet/gluon/contrib/nn/``)."""
from .basic_layers import *  # noqa: F401,F403
from .basic_layers import __all__ as _basic_all
from .transformer import *  # noqa: F401,F403
from .transformer import __all__ as _transformer_all
from .moe import *  # noqa: F401,F403
from .moe import __all__ as _moe_all
from .ssm import *  # noqa: F401,F403
from .ssm import __all__ as _ssm_all

__all__ = list(_basic_all) + list(_transformer_all) + list(_moe_all) \
    + list(_ssm_all)
