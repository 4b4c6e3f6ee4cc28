"""Quantization primitives: per-channel symmetric scales, int8
quantize/dequantize, straight-through-estimator fake-quant, and the int8
GEMM with a fused dequant epilogue.

Why this exists (ROADMAP item 3, round-4 chip runs): the ResNet-50 step runs at
93.7% of the HBM-bandwidth roof — XLA knobs are exhausted, the remaining
lever is moving FEWER BYTES. The cuDNN paper's precision argument applies
directly: half (or a quarter) of the activation bytes is half (a quarter)
of the traffic on a bandwidth-bound step. Everything here is symmetric
int8 (no zero-points): TPU MXUs take int8×int8→int32 natively, symmetric
scales keep the epilogue a single fused multiply, and the absence of a
zero-point term keeps the GEMM exactly `acc * (sx*sw)` — no cross terms.

Two executable strategies for the SAME arithmetic, chosen per backend:

- ``int8_dot``: the canonical int8×int8→int32 `lax.dot_general` — one
  MXU-native kernel on TPU. (On XLA:CPU this lowers to a scalar loop;
  the inference rewriter in `quantize/infer.py` uses the cache-resident
  tiled strategy there instead — see its module docstring.)
- ``scaled_int8_dot``: int-valued operands contracted in float32 with
  the dequant scales folded into the epilogue. For |q| <= 127 and
  K <= 2^10 every product (< 2^14) and partial sum (< 2^24) is exactly
  representable in float32, so this is BIT-equivalent to int32
  accumulation followed by a float multiply — it exists because XLA:CPU
  has no fast int8 GEMM lowering while its f32 GEMM runs near peak.

Gradients: training never calls the real int8 path. QAT uses
``fake_quant`` — forward quantize→dequantize, backward straight-through
(gradient passes unchanged inside the clip range, zero outside), the
standard STE from Jacob et al. / the cuDNN-paper lineage.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

#: symmetric int8 range: [-127, 127] (−128 unused, keeps |q| symmetric so
#: the MXU's int8×int8 products never overflow int16 pairs)
INT8_MAX = 127.0

__all__ = [
    "INT8_MAX", "per_channel_scales", "per_tensor_scale", "quantize",
    "dequantize", "fake_quant", "int8_dot", "scaled_int8_dot",
    "dequant_epilogue",
]


def per_channel_scales(w, channel_axis=-1):
    """Symmetric per-output-channel scales for a weight tensor: one
    float32 scale per channel, absmax/127, zero-guarded (an all-zero
    channel gets scale 1 so q = 0 round-trips)."""
    axes = tuple(i for i in range(w.ndim) if i != channel_axis % w.ndim)
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axes)
    return jnp.where(amax > 0, amax / INT8_MAX, 1.0)


def per_tensor_scale(x):
    """Symmetric whole-tensor scale (activations): absmax/127."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    return jnp.where(amax > 0, amax / INT8_MAX, 1.0)


def _broadcast_scale(x, scale, channel_axis):
    """Scale shaped to broadcast against x: scalar as-is, a per-channel
    vector reshaped onto `channel_axis`. THE one broadcast rule shared
    by quantize/dequantize/fake_quant (they must never disagree)."""
    s = jnp.asarray(scale, jnp.float32)
    if channel_axis is not None and s.ndim == 1:
        shape = [1] * x.ndim
        shape[channel_axis % x.ndim] = s.shape[0]
        s = s.reshape(shape)
    return s


def quantize(x, scale, channel_axis=None):
    """x/scale, rounded and clipped to [-127, 127], as int8. `scale` is
    a scalar (per-tensor) or a per-channel vector (then `channel_axis`
    names the axis it broadcasts over)."""
    s = _broadcast_scale(x, scale, channel_axis)
    q = jnp.round(x.astype(jnp.float32) / s)
    return jnp.clip(q, -INT8_MAX, INT8_MAX).astype(jnp.int8)


def dequantize(q, scale, channel_axis=None, dtype=jnp.float32):
    s = _broadcast_scale(q, scale, channel_axis)
    return (q.astype(jnp.float32) * s).astype(dtype)


# -- QAT fake-quant (straight-through estimator) ----------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def fake_quant(x, scale, channel_axis=None):
    """quantize→dequantize in the forward pass; straight-through gradient
    in the backward pass (dx = dy inside the representable range
    [-127·s, 127·s], 0 where the forward CLIPPED — the clipped-STE that
    keeps QAT stable, values the int8 lattice cannot express stop pulling
    gradient). `scale` receives no gradient (recomputed from data each
    step by the callers)."""
    y, _ = _fake_quant_fwd(x, scale, channel_axis)
    return y


def _fake_quant_fwd(x, scale, channel_axis):
    s = _broadcast_scale(x, scale, channel_axis)
    xf = x.astype(jnp.float32)
    q = jnp.clip(jnp.round(xf / s), -INT8_MAX, INT8_MAX)
    y = (q * s).astype(x.dtype)
    inside = (jnp.abs(xf) <= INT8_MAX * s)
    return y, inside


def _fake_quant_bwd(channel_axis, inside, dy):
    dx = jnp.where(inside, dy, 0).astype(dy.dtype)
    return dx, None   # scale: no gradient (data-derived)


fake_quant.defvjp(_fake_quant_fwd, _fake_quant_bwd)


def fake_quant_weight(w, channel_axis=-1):
    """QAT weight fake-quant: per-output-channel dynamic scales from the
    CURRENT weights (scales track the weights as they train)."""
    return fake_quant(w, per_channel_scales(w, channel_axis), channel_axis)


def fake_quant_act(x):
    """QAT activation fake-quant: per-tensor dynamic absmax scale."""
    return fake_quant(x, per_tensor_scale(x), None)


# -- the int8 GEMM ----------------------------------------------------------
def int8_dot(xq, wq):
    """int8 (..., K) × int8 (K, N) → int32 (..., N): the canonical
    quantized contraction over the trailing axis. Lowers to one
    MXU-native kernel on TPU; on XLA:CPU the lowering is a scalar
    loop — prefer `scaled_int8_dot` there."""
    return lax.dot_general(xq, wq, (((xq.ndim - 1,), (0,)), ((), ())),
                           preferred_element_type=jnp.int32)


def scaled_int8_dot(xq, wq, out_scale):
    """The same contraction computed exactly in float32: int-valued
    operands (|q| <= 127) contracted with preferred f32 and the dequant
    scale applied after. For K <= 2^10 every partial sum fits in f32's
    24-bit mantissa, so this equals int32 accumulation bit-for-bit —
    it exists for backends (XLA:CPU) whose f32 GEMM is the only fast
    GEMM. `out_scale`: scalar or (N,) per-channel dequant factor."""
    xf = xq.astype(jnp.float32)
    acc = lax.dot_general(xf, wq.astype(jnp.float32),
                          (((xf.ndim - 1,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32)
    return acc * out_scale


def dequant_epilogue(acc, scale, bias=None, residual=None, act=None):
    """The fused dequant+bias+activation epilogue over a raw int32 (or
    exactly-int-valued f32) accumulator: y = act(acc·scale + bias
    [+ residual]). One elementwise pass; XLA fuses it into the
    accumulator's consumer chain so the int32 tensor never round-trips
    HBM on its own."""
    y = acc.astype(jnp.float32) * scale
    if bias is not None:
        y = y + bias
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    if act is not None:
        from deeplearning4j_tpu.nn.activations import get_activation
        y = get_activation(act)(y)
    return y
