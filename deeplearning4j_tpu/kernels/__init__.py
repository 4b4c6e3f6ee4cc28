"""Pallas TPU kernels — the hand-fused hot ops (≡ the reference's cuDNN
helper layer, rebuilt as TPU VMEM-tiled kernels; interpret-mode on CPU)."""
from deeplearning4j_tpu.kernels.flash_attention import (
    flash_attention, flash_attention_decode, flash_attention_decode_mq,
    flash_attention_decode_mq_paged, flash_attention_decode_paged,
    gather_kv_pages)
from deeplearning4j_tpu.kernels.layernorm import fused_layernorm
from deeplearning4j_tpu.kernels.pointwise_conv import (
    int8_matmul_epilogue, matmul_epilogue)

__all__ = ["flash_attention", "flash_attention_decode",
           "flash_attention_decode_mq",
           "flash_attention_decode_mq_paged", "flash_attention_decode_paged",
           "gather_kv_pages",
           "fused_layernorm", "int8_matmul_epilogue", "matmul_epilogue"]
