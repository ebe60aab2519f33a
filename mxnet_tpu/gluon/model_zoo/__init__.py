"""Gluon model zoo (reference ``python/mxnet/gluon/model_zoo/``).

Provides the same constructor surface (``vision.resnet50_v1()`` etc.) built
on the TPU-native Gluon layers.  Pretrained-weight download is descoped in
this build (zero-egress environment); constructors accept ``pretrained``
for API parity and raise with a clear message when it is requested.
"""
from . import vision  # noqa: F401
from . import bert  # noqa: F401
from .bert import BERTModel, bert_base, bert_small  # noqa: F401
from . import zaya  # noqa: F401
from .zaya import ZAYA1Model, zaya1  # noqa: F401
from . import nemotron_h as _nemotron_h  # noqa: F401
from .nemotron_h import NemotronHModel, nemotron_h  # noqa: F401
from . import sdar as _sdar  # noqa: F401
from .sdar import (SDARModel, sdar, block_diffusion_mask,  # noqa: F401
                   block_diffusion_row)
from . import laguna as _laguna  # noqa: F401
from .laguna import LagunaModel, laguna  # noqa: F401

__all__ = ["vision", "bert", "BERTModel", "bert_base", "bert_small",
           "zaya", "ZAYA1Model", "zaya1", "NemotronHModel", "nemotron_h",
           "SDARModel", "sdar", "block_diffusion_mask",
           "block_diffusion_row", "LagunaModel", "laguna"]
