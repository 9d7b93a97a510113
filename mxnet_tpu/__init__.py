"""mxnet_tpu: a TPU-native deep-learning framework with MXNet's capabilities.

Built new for TPU (JAX/XLA/Pallas/pjit idioms) to the blueprint in SURVEY.md;
reference for API/behavior parity: RustyRaptor/incubator-mxnet (read-only
snapshot).  Import convention mirrors the reference::

    import mxnet_tpu as mx
    x = mx.nd.ones((2, 3), ctx=mx.tpu())
"""
from __future__ import annotations

__version__ = "0.1.0"

# arm the runtime lock-order sanitizer (MXTPU_LOCKDEP) before ANY other
# framework import — the factories must be wrapped before the first
# module-level lock is created, and lockdep itself is stdlib-only
from . import lockdep  # noqa: F401

lockdep.install_from_env()

# arm the runtime lockset race sanitizer (MXTPU_RACECHECK) next — its
# lock-identity tokens must wrap whatever factory is live (stacking on
# lockdep's), and before any tracked class is instantiated
from . import racecheck  # noqa: F401

racecheck.install_from_env()

# arm the runtime resource-leak sanitizer (MXTPU_LEAKCHECK) the same way
# — stdlib-only, and its track/untrack hooks must be live before the
# first allocator/breaker/future exists
from . import leakcheck  # noqa: F401

leakcheck.install_from_env()

# give the persistent XLA compilation cache a directory (runtime.py has the
# rule) before anything can trigger a compile — jax reads the cache dir at
# compile time, so this must precede the first jitted call in the process
from .runtime import init_compile_cache as _init_compile_cache

_init_compile_cache()

from ._dist import init_from_env as _dist_init_from_env

_dist_init_from_env()  # multi-worker bootstrap (mxnet_tpu.tools.launch)

from .base import MXNetError  # noqa: F401
from .attribute import AttrScope  # noqa: F401
from .context import (Context, cpu, gpu, tpu, cpu_pinned, num_gpus,  # noqa: F401
                      num_tpus, current_context)
from . import ops  # noqa: F401  (registers the op corpus)
from . import operator  # noqa: F401  (registers 'Custom' before nd codegen)
from . import ndarray  # noqa: F401
from . import ndarray as nd  # noqa: F401
from . import autograd  # noqa: F401
from . import random  # noqa: F401
from .ndarray import NDArray  # noqa: F401
from . import initializer  # noqa: F401
from . import initializer as init  # noqa: F401
from . import lr_scheduler  # noqa: F401
from . import optimizer  # noqa: F401
from . import optimizer as opt  # noqa: F401
from . import metric  # noqa: F401
from . import kvstore  # noqa: F401
from .kvstore import create as _kv_create  # noqa: F401
from . import kvstore as kv  # noqa: F401
from . import gluon  # noqa: F401
from . import symbol  # noqa: F401
from . import symbol as sym  # noqa: F401
from .executor import Executor  # noqa: F401
from . import io  # noqa: F401
from . import recordio  # noqa: F401
from . import model  # noqa: F401
from . import module  # noqa: F401
from . import module as mod  # noqa: F401
from . import callback  # noqa: F401
from . import predict  # noqa: F401
from . import image  # noqa: F401
from . import profiler  # noqa: F401
from . import telemetry  # noqa: F401
from . import dispatch  # noqa: F401
from . import contrib  # noqa: F401
from . import monitor  # noqa: F401
from .monitor import Monitor  # noqa: F401
from . import visualization  # noqa: F401
from . import visualization as viz  # noqa: F401
from .config import config  # noqa: F401  (mx.config = the knob registry;
#                            the module stays importable as mxnet_tpu.config
#                            via sys.modules and has the same describe())
from . import runtime  # noqa: F401
from . import rtc  # noqa: F401
from . import elastic  # noqa: F401
from . import chaos  # noqa: F401
from . import sentinel  # noqa: F401
from . import serving  # noqa: F401
from . import generation  # noqa: F401
from . import fleet  # noqa: F401
from . import gateway  # noqa: F401
from . import benchmark  # noqa: F401

# everything registered up to here is the shipped op corpus; later
# registrations are user ops (operator.register / rtc.PallasModule)
ops.registry.freeze_builtins()

if config.profiler_autostart:
    profiler.start()

# JSONL exporter / localhost metrics endpoint, when the MXNET_TELEMETRY_*
# knobs ask for them (both default off — docs/OBSERVABILITY.md)
telemetry.init_from_env()
