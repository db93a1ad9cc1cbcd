"""Device / Context model.

Parity target: ``Context`` in the reference (`include/mxnet/base.h:102-188`,
Python mirror `python/mxnet/context.py:28-311`): a (device_type, device_id)
pair used to place NDArrays and route work to per-device execution lanes.

TPU-native redesign: a Context wraps a ``jax.Device``. Device types are
``cpu`` and ``tpu`` (``kCPU=1``/``kTPU=2`` — the reference's ``kGPU`` slot is
taken by the TPU). ``cpu_pinned`` maps to plain host memory (PJRT manages
pinned staging buffers itself), and ``cpu_shared`` (DataLoader IPC) maps to
host shared memory handled at the Python layer.

Placement itself is delegated to XLA: a Context resolves to a concrete
``jax.Device`` (or, for sharded arrays, a `mxnet_tpu.parallel` mesh), and the
runtime uses ``jax.device_put`` / sharding constraints instead of explicit
stream assignment.
"""
from __future__ import annotations

import threading
from typing import Optional

__all__ = [
    "Context",
    "cpu",
    "tpu",
    "gpu",
    "cpu_pinned",
    "num_tpus",
    "num_gpus",
    "current_context",
    "default_context",
]


class Context:
    """A device context (device_type, device_id).

    Acts as a context manager exactly like the reference's
    ``with mx.tpu(0):`` idiom, setting the thread-local default device.
    """

    # parity: include/mxnet/base.h:105-110 (kCPU=1, kGPU=2, kCPUPinned=3, kCPUShared=5)
    devtype2str = {1: "cpu", 2: "tpu", 3: "cpu_pinned", 5: "cpu_shared"}
    devstr2type = {"cpu": 1, "tpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "gpu": 2}

    _tls = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if device_type not in self.devstr2type:
                raise ValueError(f"unknown device type {device_type!r}")
            self.device_typeid = self.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx: Optional[Context] = None

    @property
    def device_type(self) -> str:
        return self.devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    def __enter__(self):
        if not hasattr(Context._tls, "stack"):
            Context._tls.stack = []
        Context._tls.stack.append(self)
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        Context._tls.stack.pop()

    # -- JAX resolution -----------------------------------------------------
    def jax_device(self):
        """Resolve this Context to a concrete jax.Device.

        On a host with no TPU, ``tpu`` resolves to the default backend's
        devices so test suites written against ``mx.tpu()`` run anywhere —
        the same trick the reference uses with ``default_context()``
        (`python/mxnet/test_utils.py:58`). Entry points that must run on
        the chip (``chip_smoke.py``, ``chipbench/run.py``) assert the platform
        themselves. A ``tpu`` id past the last device raises.
        """
        import jax

        # LOCAL devices only: under jax.distributed the global list
        # contains other processes' (non-addressable) devices
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            # the CPU backend always exists, even on accelerator hosts
            devs = jax.local_devices(backend="cpu")
            return devs[self.device_id % len(devs)]
        # tpu: prefer real TPU devices, else whatever the default backend is
        devs = [d for d in jax.local_devices() if d.platform == "tpu"]
        if not devs:
            devs = jax.local_devices()
        if self.device_id >= len(devs):
            raise ValueError(
                f"{self!r} names device {self.device_id} but only "
                f"{len(devs)} {devs[0].platform} device(s) are attached")
        return devs[self.device_id]


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


# Compatibility alias: reference code says mx.gpu(); on this framework the
# accelerator is a TPU.
def gpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def num_tpus() -> int:
    """Count attached accelerator devices: every local device of a
    non-CPU platform (parity: python/mxnet/context.py:246 num_gpus)."""
    import jax

    try:
        n = len([d for d in jax.local_devices()
                 if d.platform not in ("cpu",)])
    except RuntimeError:
        return 0
    if n:
        return n
    # default backend is CPU (e.g. JAX_PLATFORMS="cpu,tpu" priority):
    # an explicit tpu backend may still exist alongside it
    try:
        return len(jax.local_devices(backend="tpu"))
    except RuntimeError:
        return 0


def num_gpus() -> int:  # parity alias (python/mxnet/context.py:246)
    return num_tpus()


def current_context() -> Context:
    """The active default context (thread-local `with ctx:` stack)."""
    stack = getattr(Context._tls, "stack", None)
    if stack:
        return stack[-1]
    return Context._default_ctx


def default_context() -> Context:
    return current_context()


Context._default_ctx = Context("cpu", 0)
