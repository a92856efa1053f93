"""Pallas TPU kernels (SURVEY.md §5.7/§7.7).

The compute path of the framework is XLA; Pallas covers the few ops where
hand-tiling beats the compiler — the blockwise (flash) attention inner
kernel used by ring attention (and, since ISSUE 11, the standard long-seq
attention default), which keeps score tiles in VMEM instead of
materializing per-block [Tq,Tk] matrices in HBM; the int8xbf16 fused
dequant-matmul behind the quantized serve path (``nn.quantize``); and
Kimi Delta Attention's chunk phase (``kda_chunk``, imported by the layer
that runs it).

Kernels run compiled on TPU and in interpreter mode on CPU (tests), with
the pure-jnp implementations kept as numerical oracles.
"""

from deeplearning4j_tpu.ops.pallas.flash_attention import (
    flash_attention_block, flash_attention_block_bwd, flash_attention)
from deeplearning4j_tpu.ops.pallas.quant_matmul import (
    int8_matmul, int8_matmul_pallas, int8_matmul_reference)

__all__ = ["flash_attention_block", "flash_attention_block_bwd",
           "flash_attention", "int8_matmul", "int8_matmul_pallas",
           "int8_matmul_reference"]
