"""The plain DeepSeek-V3 reference, for the tests.

The benchmark keeps the reference (``bench/ref/deepseek_v3.py``: float32
``jax.numpy`` at the highest matmul precision, nothing of ``repro``), so
that it runs over any checkout with nothing outside its own directory;
this module puts the checkout on the import path and re-exports it.
"""

import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from bench.ref.deepseek_v3 import (CLASSES, decode_step, dot_macs,  # noqa: E402
                                   forward, init_cache, mla_layer, mla_step,
                                   param_shapes)

__all__ = ["CLASSES", "decode_step", "dot_macs", "forward", "init_cache",
           "mla_layer", "mla_step", "param_shapes"]
