"""Foundation helpers: dtype registry, error types, name managers.

Role parity: `python/mxnet/base.py` in the reference (ctypes lib loading,
dtype maps, MXNetError). Here the "backend" is JAX/XLA, so this module only
keeps the pure-Python pieces: dtype canonicalisation, error types, and small
utilities shared across the package.
"""
from __future__ import annotations

import threading

import numpy as _np

__all__ = ["MXNetError", "string_types", "numeric_types", "integer_types",
           "did_you_mean"]


class MXNetError(RuntimeError):
    """Error raised by the framework (parity: dmlc error -> MXNetError)."""


def did_you_mean(name, candidates, n=1):
    """A ``" (did you mean ...?)"`` suffix for a near-miss name, or ``""``.

    The one difflib helper shared by every naming-error site — OpSchema
    kwargs, the operator registry, DeviceMesh axis names, and the distcheck
    sharding verifier — so all of them hint the same way."""
    import difflib

    close = difflib.get_close_matches(str(name),
                                      [str(c) for c in candidates], n=n)
    if not close:
        return ""
    if len(close) == 1:
        return f" (did you mean {close[0]!r}?)"
    return f" (did you mean one of {close}?)"


string_types = (str,)
numeric_types = (float, int, _np.generic)
integer_types = (int, _np.integer)

# Canonical dtype universe. bf16 is first-class on TPU (MXU native input type);
# fp64 is supported on CPU meshes for numeric-gradient tests.
_DTYPE_ALIASES = {
    "float32": "float32",
    "float64": "float64",
    "float16": "float16",
    "bfloat16": "bfloat16",
    "uint8": "uint8",
    "int8": "int8",
    "int32": "int32",
    "int64": "int64",
    "bool": "bool",
}


def canonical_dtype(dtype):
    """Normalise a dtype-ish value to a numpy/ml_dtypes dtype object."""
    import jax.numpy as jnp

    if dtype is None:
        return _np.dtype("float32")
    if isinstance(dtype, str):
        if dtype == "bfloat16":
            return jnp.bfloat16
        if dtype not in _DTYPE_ALIASES:
            raise TypeError(f"unsupported dtype {dtype!r}")
        return _np.dtype(dtype)
    if dtype is jnp.bfloat16:
        return jnp.bfloat16
    try:
        d = _np.dtype(dtype)
    except TypeError:
        # jax weak types / ml_dtypes
        return dtype
    return d


def dtype_name(dtype) -> str:
    import jax.numpy as jnp

    if dtype is jnp.bfloat16:
        return "bfloat16"
    return _np.dtype(dtype).name if not hasattr(dtype, "name") else str(getattr(dtype, "name"))


class _NameManager(threading.local):
    """Automatic unique-name generation (parity: mxnet.name.NameManager)."""

    def __init__(self):
        super().__init__()
        self.counters = {}

    def get(self, hint: str) -> str:
        idx = self.counters.get(hint, 0)
        self.counters[hint] = idx + 1
        return f"{hint}{idx}"


name_manager = _NameManager()


def apply_platform_env():
    """Honor MXTPU_PLATFORM=cpu|tpu at import time: the platform pin of
    the C ABI (``include/mxtpu/c_api.h``, ``capi_bridge.py``) and of
    launcher-spawned workers. It only takes effect while no backend is
    initialised."""
    import os

    plat = os.environ.get("MXTPU_PLATFORM")
    if not plat:
        return
    import jax

    try:
        jax.config.update("jax_platforms", plat)
    except Exception:
        pass  # backend already initialised — keep its platform


# the gang generation this process last rendezvoused at (None = never):
# an elastic supervisor restart hands workers a NEW generation + a NEW
# coordinator address, and re-joining requires leaving the old epoch
_dist_generation = None


def maybe_init_distributed(generation=None):
    """Join the multi-host rendezvous when launched by tools/launch.py
    (parity: KVStoreDist workers connecting to the dmlc tracker via
    DMLC_* env). jax.distributed.initialize only works BEFORE the XLA
    backend spins up, so mxnet_tpu/__init__ calls this at import; the
    kvstore path calls it again as a fallback and warns loudly instead of
    silently degrading to a single-worker group.

    Coordinator re-rendezvous (elastic gang restarts): a supervisor spawns
    generation N+1 with a fresh ``MXTPU_GANG_GENERATION`` and a fresh
    coordinator port, with surviving ranks renumbered densely. A process
    already joined at an older generation (possible when a surviving
    worker re-enters in place rather than being re-exec'd) leaves the dead
    epoch via ``jax.distributed.shutdown()`` and joins the new one."""
    import logging
    import os

    coord = os.environ.get("MXTPU_COORDINATOR")
    if not coord:
        return
    num = int(os.environ.get("MXTPU_NUM_WORKERS", "1"))
    if num <= 1:
        return
    if generation is None:
        try:
            generation = int(os.environ.get("MXTPU_GANG_GENERATION", "0"))
        except ValueError:
            generation = 0
    global _dist_generation
    import jax
    from jax._src import distributed as _dist

    log = logging.getLogger("mxnet_tpu")
    if getattr(_dist.global_state, "client", None) is not None:
        if not generation or generation == _dist_generation:
            return  # already joined this incarnation
        # gang restart: the old coordinator epoch is dead — leave it
        # before rendezvousing at the new address
        try:
            jax.distributed.shutdown()
        except Exception as e:
            log.error(
                "gang generation %s -> %s: jax.distributed.shutdown "
                "failed (%s) — this worker cannot re-rendezvous and "
                "stays in its stale group", _dist_generation, generation,
                e)
            return
        log.warning("gang: re-rendezvous at generation %s (coordinator "
                    "%s, %d workers)", generation, coord, num)
    try:
        jax.distributed.initialize(
            coordinator_address=coord, num_processes=num,
            process_id=int(os.environ.get("MXTPU_WORKER_ID", "0")))
        _dist_generation = generation or None
    except RuntimeError as e:
        log.error(
            "MXTPU_COORDINATOR=%s is set but jax.distributed could not "
            "initialize (%s) — this worker will run as an ISOLATED "
            "single-process group and dist_* stores will NOT aggregate. "
            "Import mxnet_tpu before running any computation.", coord, e)
