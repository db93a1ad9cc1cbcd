"""Model zoo (parity: python/mxnet/gluon/model_zoo)."""
from . import text
from . import vision
from .vision import get_model

__all__ = ["text", "vision", "get_model"]
