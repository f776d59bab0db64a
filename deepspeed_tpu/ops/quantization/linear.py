"""Int8-at-rest linear layer for serving.

``QuantDense`` is the drop-in serving replacement for ``nn.Dense`` behind
the inference engine's weight-quantization tier (reference
``weight_quantizer.py`` + the fused dequant-GEMM in
``csrc/transformer/inference/csrc/dequantize.cu``): parameters are an
int8 ``kernel`` plus f32 per-output-channel ``scale``, and the forward is
the Pallas :func:`int8_matmul` so weights stream from HBM as int8.

Feature counts are padded up to a lane multiple (128) at parameter-build
time so every kernel call tiles; the pad columns carry zero weights and
the output is sliced back to ``features``.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax.numpy as jnp

from .. import backend
from .int8_matmul import int8_matmul, int8_matmul_reference

LANE = 128


def pad_features(features: int) -> int:
    """Feature count padded to the vector-lane multiple QuantDense stores."""
    return -(-features // LANE) * LANE


class QuantDense(nn.Module):
    """Dense layer with int8 kernel + per-output-channel f32 scale.

    ``kernel_mode``: ``auto`` uses the Pallas kernel on TPU and the jnp
    reference elsewhere; ``on`` forces the kernel (interpret mode
    off-TPU — for tests); ``off`` forces the jnp reference. Compute runs
    in bf16 regardless of ``dtype`` (the quantized tier's compute
    contract); ``dtype`` is the output dtype.
    """

    features: int
    use_bias: bool = True
    dtype: Any = jnp.bfloat16
    kernel_mode: str = "auto"

    @nn.compact
    def __call__(self, x):
        K = x.shape[-1]
        n_pad = pad_features(self.features)
        kernel = self.param("kernel", nn.initializers.zeros, (K, n_pad),
                            jnp.int8)
        scale = self.param("scale", nn.initializers.ones, (1, n_pad),
                           jnp.float32)
        if self.kernel_mode == "off":
            y = int8_matmul_reference(x, kernel, scale, out_dtype=self.dtype)
        else:
            y = int8_matmul(x, kernel, scale, out_dtype=self.dtype,
                            interpret=(True if self.kernel_mode == "on" and
                                       backend.pallas_interpret()
                                       else None))
        if n_pad != self.features:
            y = y[..., :self.features]
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros,
                              (self.features,), self.dtype)
            y = y + bias
        return y
