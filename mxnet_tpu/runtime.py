"""Runtime feature detection (parity: `python/mxnet/runtime.py`).

The reference enumerates compile-time features (`libinfo_features`,
`src/libinfo.cc`) — CUDA/CUDNN/MKLDNN/OPENMP/etc. The TPU-native analogue
probes the live JAX/XLA environment: available backends, dtype support,
and parallelism capabilities. `Features()["TPU"].enabled` etc.

Usage (identical to the reference):

    features = mx.runtime.Features()
    features.is_enabled("TPU")
"""
from __future__ import annotations

import collections

__all__ = ["Feature", "feature_list", "Features"]


class Feature:
    """One named capability flag (parity: runtime.py:53)."""

    def __init__(self, name, enabled):
        self.name = name
        self.enabled = bool(enabled)

    def __repr__(self):
        if self.enabled:
            return f"✔ {self.name}"
        return f"✖ {self.name}"


def _probe():
    import jax

    feats = {}
    try:
        platforms = {d.platform.lower() for d in jax.devices()}
    except Exception:
        platforms = set()
    feats["TPU"] = "tpu" in platforms
    feats["CPU"] = True
    feats["CUDA"] = "gpu" in platforms or "cuda" in platforms
    feats["XLA"] = True
    feats["BF16"] = True          # MXU-native input type
    feats["F16C"] = True          # fp16 storage supported by XLA
    feats["INT64_TENSOR_SIZE"] = jax.config.jax_enable_x64
    feats["SPMD"] = True          # jax.sharding GSPMD partitioning
    feats["PALLAS"] = _has_module("jax.experimental.pallas")
    feats["DIST_KVSTORE"] = _has_module("jax.experimental.multihost_utils")
    feats["OPENMP"] = True        # host-side threading via XLA thread pools
    feats["SIGNAL_HANDLER"] = False
    feats["DEBUG"] = False
    feats["PROFILER"] = True
    # reference features with no TPU meaning report disabled for parity
    for off in ("CUDNN", "NCCL", "TENSORRT", "MKLDNN", "OPENCV", "LAPACK",
                "BLAS_MKL", "BLAS_OPEN", "SSE", "CAFFE", "TVM_OP"):
        feats.setdefault(off, False)
    return feats


def _has_module(name):
    import importlib.util

    try:
        return importlib.util.find_spec(name) is not None
    except (ImportError, ModuleNotFoundError, ValueError):
        return False


def feature_list():
    """parity: runtime.py:76."""
    return [Feature(k, v) for k, v in _probe().items()]


class Features(collections.OrderedDict):
    """Map of feature name -> Feature (parity: runtime.py:90)."""

    instance = None

    def __new__(cls):
        if cls.instance is None:
            cls.instance = super().__new__(cls)
            cls.instance.update([(f.name, f) for f in feature_list()])
        return cls.instance

    def __repr__(self):
        return str(list(self.values()))

    def is_enabled(self, feature_name):
        feature_name = feature_name.upper()
        if feature_name not in self:
            raise RuntimeError(f"Feature '{feature_name}' is unknown, "
                               "known features are: "
                               f"{list(self.keys())}")
        return self[feature_name].enabled
