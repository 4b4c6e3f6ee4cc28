"""int8 KV-cache codec for the generation decode path.

Steady-state decode traffic is dominated by reading the whole K/V cache
once per token (the single-query attention is a GEMV — pure bandwidth).
Storing the cache int8 with one float32 scale per (head, position) row
cuts that read to ~¼ (bf16 caches: ~½) at a per-row quantization error
attention's softmax largely absorbs — the PR 8 decode tests hold the
int8 token stream to the fp stream within tolerance.

Layout: alongside each `(..., C, H·D)` cache leaf (rows major, hidden
minor — generation/decode.py) rides a `(..., C, H)` float32 scale leaf —
"per-head scales": every head quantizes its D lanes of each cached row
against their own absmax, so one outlier head (or one outlier position)
cannot crush the resolution of the rest.

Dequantization happens INSIDE `flash_attention_decode` (the scales ride
into the attention contraction as epilogue multipliers — for the score
pass the row scale folds onto the logits, for the value pass it folds
onto the softmax weights), so no dequantized fp copy of the cache is
ever materialized in HBM.

The codec composes with the paged KV layout unchanged: an int8 page is
the same `(ps, H·Dh)` block plus its `(ps, H)` scale page, so paging
halves again on top of the int8 ¼ — `cache_page_bytes` is the one
place that arithmetic lives (the bench ledger and the pool-sizing docs
both read it)."""
from __future__ import annotations

import jax.numpy as jnp

from deeplearning4j_tpu.quantize.core import INT8_MAX

__all__ = ["quantize_rows", "dequantize_rows", "cache_page_bytes"]


def cache_page_bytes(layers, heads, page_size, head_dim, kv_dtype="fp",
                     dtype_bytes=4):
    """HBM bytes one physical KV page costs across all layers: K and V
    blocks of `(heads, page_size, head_dim)` per layer — int8 pages pay
    1 byte/element plus the per-(head, row) float32 scale columns, fp
    pages pay `dtype_bytes`. Host-side sizing arithmetic only (pool
    provisioning, the paged bench's bytes-saved ledger); nothing here
    touches a device value."""
    rows = int(heads) * int(page_size)
    if kv_dtype == "int8":
        per = rows * int(head_dim) * 1 + rows * 4   # payload + scales
    else:
        per = rows * int(head_dim) * int(dtype_bytes)
    return 2 * int(layers) * per                    # K and V pools


def quantize_rows(x):
    """Per-row symmetric int8: x (..., D) → (q int8 (..., D), scale f32
    (...,)) with scale = absmax(row)/127 (all-zero rows get scale 1 so
    they round-trip to zeros)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(amax > 0, amax / INT8_MAX, 1.0)
    q = jnp.round(xf / scale[..., None])
    q = jnp.clip(q, -INT8_MAX, INT8_MAX).astype(jnp.int8)
    return q, scale


def dequantize_rows(q, scale, dtype=jnp.float32):
    """Inverse of quantize_rows (materializing — prefer the fused
    in-attention dequant on the hot path)."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)
