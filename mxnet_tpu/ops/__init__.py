"""Operator layer: registry + the op corpus.

Parity: `src/operator/` in the reference (~550 NNVM_REGISTER_OP entries).
Importing this package registers the full op set; consumers look ops up by
name via `ops.get(name)` (nnvm `Op::Get` analogue).
"""
from .registry import Operator, register, get, list_ops, apply_op, infer_output

from . import math  # noqa: F401  (registers elementwise/scalar/broadcast ops)
from . import tensor  # noqa: F401  (reduce/linalg/indexing/shape ops)
from . import nn  # noqa: F401  (FC/conv/pool/norm/softmax/rnn ops)
from . import optimizer_op  # noqa: F401  (fused optimizer updates)
from . import random_ops  # noqa: F401  (samplers)
from . import quantization  # noqa: F401  (int8 quantize/dequantize/conv/fc)
from . import numpy_ops  # noqa: F401  (_npi_* NumPy-frontend ops)
from . import la_op  # noqa: F401  (linalg_* suite)
from . import contrib_ops  # noqa: F401  (fft/detection/roi/stn/misc)
from . import output_ops  # noqa: F401  (regression/SVM loss heads)
from . import pallas_ops  # noqa: F401  (flash attention TPU kernel)
from . import text_ops  # noqa: F401  (RMS norm/rotary/gated SiLU/sparse MoE/LM loss)
from . import custom  # noqa: F401  (Custom op — user-defined Python operators)

__all__ = ["Operator", "register", "get", "list_ops", "apply_op", "infer_output"]
