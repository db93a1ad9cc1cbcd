"""mxnet_tpu: a TPU-native deep-learning framework with MXNet-1.x capabilities.

Usage mirrors the reference (`import mxnet as mx`):

    import mxnet_tpu as mx
    x = mx.nd.ones((2, 3), ctx=mx.tpu())
    with mx.autograd.record():
        y = (x * 2).sum()
    y.backward()

Compute path: JAX/XLA (MXU matmuls, fused elementwise, Pallas custom calls);
runtime semantics (async engine, Context, NDArray mutability, autograd tape,
hybridize-to-compiled-graph) match the reference's programming model.
"""
from __future__ import annotations

__version__ = "0.1.0"

from .base import (MXNetError, apply_platform_env as _ape,
                   maybe_init_distributed as _midi)

# both must run BEFORE anything touches the XLA backend (the only moment
# they work): MXTPU_PLATFORM platform pinning, then the tools/launch.py
# jax.distributed rendezvous
_ape()
_midi()
del _ape, _midi

import os as _os

if _os.environ.get("MXTPU_GANG_DIR"):
    # launched by the elastic gang supervisor: arm the heartbeat channel
    # + the PeerLostError->exit-76 excepthook (import-light; skipped
    # entirely outside a supervised run)
    from .elastic import maybe_install_from_env as _gang

    _gang()
    del _gang
del _os
from .context import (Context, cpu, tpu, gpu, cpu_pinned, num_tpus, num_gpus,
                      current_context)
from . import engine
from . import random
from . import autograd
from . import ndarray
from . import ndarray as nd
from .ndarray import NDArray
from . import initializer
from . import initializer as init
from . import gluon

__all__ = [
    "MXNetError", "Context", "cpu", "tpu", "gpu", "cpu_pinned", "num_tpus",
    "num_gpus", "current_context", "engine", "random", "autograd", "nd",
    "ndarray", "NDArray", "initializer", "init", "gluon", "__version__",
]

import os as _os

if _os.environ.get("MXNET_TPU_CONCUR_TRACE", "").lower() in ("1", "true",
                                                             "on"):
    # arm the lock witness (chaos drills / supervised workers): wraps the
    # package's module-level locks and cross-checks acquisition order at
    # exit — analysis/concur.py pass 4. After the eager imports above so
    # the sweep never imports submodules against a half-initialised
    # package.
    from .analysis import concur as _concur

    _concur.trace_locks(register_atexit=True)
    del _concur
del _os


def __getattr__(name):
    # lazily exposed heavyweight subsystems
    if name in ("optimizer", "lr_scheduler", "metric", "io", "image",
                "symbol", "sym", "module", "mod", "kvstore", "kv",
                "profiler", "recordio", "callback", "monitor", "model",
                "test_utils", "amp", "parallel", "np", "npx", "visualization",
                "contrib", "util", "runtime", "onnx", "operator", "library",
                "log", "name", "attribute", "faults", "checkpoint",
                "analysis", "watchdog", "preempt", "elastic", "compile",
                "serving", "telemetry"):
        import importlib

        try:
            mod = importlib.import_module(
                "." + {"sym": "symbol", "mod": "module", "kv": "kvstore",
                       "np": "numpy", "npx": "numpy_extension"}.get(name, name),
                __name__)
        except ImportError as e:
            raise AttributeError(
                f"module {__name__!r} has no attribute {name!r} ({e})") from None
        globals()[name] = mod
        return mod
    if name == "AttrScope":  # reference exposes it at top level too
        from .attribute import AttrScope

        globals()[name] = AttrScope
        return AttrScope
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
