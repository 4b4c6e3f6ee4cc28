"""Quantization + memory-traffic diet (ROADMAP item 3).

The ResNet-50 step measured at 93.7% of the HBM-bandwidth roof
(round 4, builder-local) — XLA knobs exhausted; the remaining single-chip
lever is moving fewer bytes. This package is that lever:

- `core` — symmetric int8 primitives: per-channel scales, quantize /
  dequantize, the straight-through-estimator `fake_quant`, the
  int8×int8→int32 contraction and its exact f32 twin, and the fused
  dequant+bias+activation epilogue.
- `policy` — `PrecisionPolicy`: the conf-DSL knob
  (`.precisionPolicy(PrecisionPolicy.int8())`) driving training-time
  QAT fake-quant AND the inference rewrite's eligibility.
- `calibrate` — activation-scale calibration: observed absmax over
  sample batches, or derived from BatchNorm statistics (data-free).
- `infer` — `quantize_network(net)`: the post-training rewrite to an
  inference-only int8 twin (BN folding, fused epilogues, and the
  cache-resident tiled chain executor for pointwise/residual runs),
  served through ExecutableStore / ParallelInference unchanged.
- `kvcache` — int8 KV-cache codec for the generation decode path
  (per-head row scales, dequant inside attention).
- `traffic` — the bytes ledger: activation-traffic / saved-for-backward
  estimates by precision + remat policy, published to
  `dl4j.quant.activation_traffic_bytes`.
"""
from deeplearning4j_tpu.quantize.core import (  # noqa: F401
    INT8_MAX, dequant_epilogue, dequantize, fake_quant, fake_quant_act,
    fake_quant_weight, int8_dot, per_channel_scales, per_tensor_scale,
    quantize, scaled_int8_dot)
from deeplearning4j_tpu.quantize.policy import (  # noqa: F401
    PrecisionPolicy)
from deeplearning4j_tpu.quantize.infer import (  # noqa: F401
    QuantPassthrough, QuantizedConv1x1, QuantizedDense,
    quantize_network)
from deeplearning4j_tpu.quantize.traffic import (  # noqa: F401
    activation_report, publish)

__all__ = [
    "INT8_MAX", "PrecisionPolicy", "QuantPassthrough",
    "QuantizedConv1x1", "QuantizedDense", "activation_report",
    "dequant_epilogue", "dequantize", "fake_quant", "fake_quant_act",
    "fake_quant_weight", "int8_dot", "per_channel_scales",
    "per_tensor_scale", "publish", "quantize", "quantize_network",
    "scaled_int8_dot",
]
