"""What the served language models of this package share outside their
layers: the norm, the head, and the run of tokens an expert layer takes at
once. One place, so that no model's file imports another's."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: tokens one call of an expert layer takes: its pairs' tiled rows and
#: results are (tokens x top_k) x hidden, a gigabyte at 16384 tokens
MOE_CHUNK = 2048


def rms_norm(x, weight, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * weight).astype(x.dtype)


def logits(cfg, params, x):
    """The final norm and the head over hidden rows `x` (..., H), float32."""
    with jax.named_scope("logits"):
        u = rms_norm(x, params["norm_f"], cfg.rms_norm_eps)
        return jnp.dot(u, params["head"].astype(u.dtype),
                       preferred_element_type=jnp.float32)
