"""TPU-native kernels for the hot ops (pallas).

The reference client has no compute kernels of its own — its models run
inside Triton's backends (cuDNN/cuBLAS/TensorRT). This framework serves
models directly, so the hot inner ops live here, written as pallas TPU
kernels with jnp fallbacks for non-TPU backends.
"""

from .flash_attention import flash_attention, flash_attention_reference
from .int8_matmul import fits as int8_matmul_fits
from .int8_matmul import int8_matmul, int8_matmul_reference

__all__ = ["flash_attention", "flash_attention_reference",
           "int8_matmul", "int8_matmul_fits", "int8_matmul_reference"]
