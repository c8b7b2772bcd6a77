"""repro.quant — serving-side weight quantization (int8 + fp8 stub).

One implementation of symmetric quantization shared with gradient
compression (``core``), plus the weight-tree layer (``weights``) that the
``ModelRuntime.quantized`` / ``--quantize int8`` serving path consumes.
Matmuls over quantized weights dispatch through ``kernels/q_matmul.py``
(Pallas, dequant fused in the MXU epilogue) or the reference einsums.
"""
from .core import (FP8_MAX, INT8_MAX, QuantMeta, QuantTensor, dequantize_fp8,
                   dequantize_int8, is_quant_tensor,
                   quantize_fp8, quantize_int8, quantize_tensor)
from .weights import (DEFAULT_QUANT_TARGETS, QuantConfig, dequantize_params,
                      is_quantized_tree, quantize_params, quantized_abstract,
                      tree_bytes)

__all__ = [
    "FP8_MAX", "INT8_MAX", "QuantMeta", "QuantTensor", "QuantConfig",
    "DEFAULT_QUANT_TARGETS", "dequantize_fp8", "dequantize_int8",
    "dequantize_params", "is_quant_tensor",
    "is_quantized_tree", "quantize_fp8", "quantize_int8", "quantize_params",
    "quantize_tensor", "quantized_abstract", "tree_bytes",
]
