"""Gluon neural-net layers (parity: python/mxnet/gluon/nn/)."""
from ..block import Block, HybridBlock, SymbolBlock
from .basic_layers import *
from .conv_layers import *
from .text_layers import *

from .basic_layers import __all__ as _basic_all
from .conv_layers import __all__ as _conv_all
from .text_layers import __all__ as _text_all

__all__ = ["Block", "HybridBlock", "SymbolBlock"] + list(_basic_all) + list(_conv_all) \
    + list(_text_all)
