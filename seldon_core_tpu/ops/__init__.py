"""TPU kernels (Pallas) for the hot ops.

The reference has no custom kernels anywhere (its FLOPs live behind
TFServing/Triton, SURVEY §2 #35-36); this package is new TPU-native
capability: hand-tiled Pallas kernels for the ops XLA leaves bandwidth
on the table for, with XLA fallbacks everywhere so every call site works
on CPU and in tests.
"""

from .flash_attention import attention, flash_attention  # noqa: F401
from .decode_attention import decode_attention, ragged_decode_attention  # noqa: F401
