"""mxnet_tpu: a TPU-native deep-learning framework with MXNet's capabilities.

Built from scratch on JAX/XLA (compute), Pallas (custom TPU kernels) and
``jax.sharding``/pjit (parallelism).  The public surface mirrors Apache
MXNet's (the reference at /root/reference — see SURVEY.md): ``mx.nd``,
``mx.autograd``, ``mx.gluon``, ``mx.sym``/``mx.mod``, ``mx.kv``, ``mx.io``,
``mx.optimizer``, ``mx.metric``, ``mx.init`` — but the architecture is
TPU-first, not a port: no dependency engine (JAX async dispatch + XLA),
no hand-written kernels (XLA fusion + Pallas for hot spots), no ps-lite
(XLA collectives over ICI/DCN).
"""
from __future__ import annotations

import os as _os

if _os.environ.get("MXNET_TPU_COORDINATOR_ADDRESS"):
    # Launched by tools/launch.py: join the coordination service BEFORE any
    # computation initializes the jax backends — by first import is the only
    # reliably-early point, so the library owns this invariant rather than
    # every entry-point script.
    # deliberately NOT caught: with the distributed env set, proceeding
    # single-process after a failed join would silently train on 1/N of
    # the data (the reference's dist kvstore errors hard the same way).
    # One bootstrap implementation: parallel.initialize (idempotent, reads
    # the same env contract incl. MXNET_TPU_INIT_TIMEOUT).
    from .parallel import initialize as _dist_init
    _dist_init()

from .base import MXNetError, __version__
from .context import Context, cpu, gpu, tpu, cpu_pinned, current_context, num_gpus, num_tpus

from . import telemetry
from . import base
from . import context
from . import ndarray
from . import ndarray as nd
from . import autograd
from . import random
from . import initializer
from . import initializer as init
from . import optimizer
from . import optimizer as opt
from . import lr_scheduler
from . import metric
from . import kvstore
from . import kvstore as kv
from . import io
from . import recordio
from . import image
from . import gluon
from . import parallel
from . import operator
from . import profiler
from . import symbol
from . import symbol as sym
from . import executor
from . import model
from . import checkpoint
from . import module
from . import module as mod
from . import callback
from . import contrib
from . import serve
from . import monitor
from . import visualization
from . import visualization as viz
from . import runtime
from . import engine
from . import subgraph
from . import attribute
from . import name
from .attribute import AttrScope

# convenience re-exports matching `import mxnet as mx` usage
from .ndarray import NDArray

__all__ = [
    "MXNetError", "Context", "cpu", "gpu", "tpu", "cpu_pinned",
    "current_context", "num_gpus", "num_tpus", "nd", "ndarray",
    "autograd", "random", "NDArray", "initializer", "init", "gluon",
    "optimizer", "opt", "lr_scheduler", "metric", "kvstore", "kv",
    "io", "recordio", "image", "parallel", "profiler", "symbol", "sym",
    "executor", "model", "module", "mod", "callback", "contrib",
    "monitor", "visualization", "viz", "runtime", "engine", "telemetry",
]
