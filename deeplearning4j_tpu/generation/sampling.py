"""Batched token sampling for the decode loop — pure, jit-friendly.

Every knob is a TRACED per-slot value (method id, temperature, top-k),
not a static python argument: the whole continuous batch samples in one
fused op inside the decode-step executable, and a newly admitted
request can carry different sampling settings than its in-flight
neighbours WITHOUT a recompile — the (bucket, cache-rung) executable
set stays closed over sampling configuration.

RNG is an explicit per-slot key column `(S, 2) uint32`: each sampling
step splits every slot's key and consumes the subkey, so a slot's token
stream is a pure function of its admission key — reproducible per
request, independent of which other requests share the batch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.kernels.selection import kth_largest

__all__ = ["GREEDY", "SAMPLE", "kth_largest", "method_id", "sample_step",
           "split_keys"]

#: per-slot sampling method ids (device i32)
GREEDY = 0
SAMPLE = 1     # temperature (+ optional top-k) categorical

_NEG = -1e30


def method_id(name):
    """'greedy' → GREEDY; 'sample'/'temperature'/'top_k' → SAMPLE."""
    name = str(name).lower()
    if name == "greedy":
        return GREEDY
    if name in ("sample", "temperature", "top_k", "topk"):
        return SAMPLE
    raise ValueError(f"unknown sampling method {name!r}; expected "
                     "'greedy', 'temperature', or 'top_k'")


def split_keys(keys):
    """(S, 2) uint32 → (new_keys, subkeys), both (S, 2). One split per
    decode step keeps every slot's stream independent of its batch
    neighbours."""
    s = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
    return s[:, 0], s[:, 1]


@jax.named_scope("sample")
def sample_step(logits, keys, method, temperature, top_k):
    """One batched sampling step (scope `sample` in a profiler trace).

    - logits: (S, V) float32
    - keys: (S, 2) uint32 per-slot rng keys
    - method: (S,) int32 — GREEDY or SAMPLE per slot
    - temperature: (S,) float32 (<= 0 treated as 1.0)
    - top_k: (S,) int32 — 0 (or >= V) disables the top-k filter

    Returns (tokens (S,) int32, new_keys (S, 2)). Greedy slots ignore
    their key (the split still advances, keeping streams aligned)."""
    v = logits.shape[-1]
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t = jnp.where(temperature > 0, temperature, 1.0)[:, None]
    scaled = logits / t
    # top-k threshold: the kth-largest value per row; ties at the
    # threshold stay in — a superset of k never excludes the true top-k
    k_eff = jnp.clip(top_k, 0, v)
    kth = kth_largest(scaled, jnp.maximum(k_eff, 1))[:, None]
    use_k = ((k_eff > 0) & (k_eff < v))[:, None]
    filtered = jnp.where(use_k & (scaled < kth), _NEG, scaled)
    new_keys, subkeys = split_keys(keys)
    sampled = jax.vmap(jax.random.categorical)(subkeys, filtered)
    tokens = jnp.where(method == GREEDY, greedy_tok,
                       sampled.astype(jnp.int32))
    return tokens, new_keys
