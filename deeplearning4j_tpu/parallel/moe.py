"""Expert parallelism helpers (`ep` mesh axis).

The reference has no MoE; this is a TPU-native addition. Two routers live
here:

- `switch_router`: top-1 switch routing for `models.bert` MoE layers. Their
  expert-major parameter tensors shard their leading dim over ep
  (sharding_rules), each chip holds |E|/|ep| experts, and the experts run as
  a one-hot dispatch einsum that computes every expert for every token.
- `routed_experts`: top-k routing over ALL of a layer's experts for a chip
  that HOLDS a contiguous share of them (`held = (first, count)`): the
  (token, held expert) pairs are sorted by expert and each projection is ONE
  grouped matrix product over the held experts' stacked weights
  (`lax.ragged_dot`; on the TPU, XLA's own grouped-matmul kernel), so an
  expert's weights are read once a call and only for the tokens routed to
  it. What the experts held elsewhere would add is left out: on one chip the
  layer runs without its exchange, and nothing here stands in for it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: what `routed_experts` counts a call, in this order
ROUTED_COUNTS = ("pairs", "expert_reads", "pairs_max")


def switch_router(x, router_w, num_experts):
    """Top-1 switch routing: returns (one_hot dispatch, gate, aux_loss).
    aux_loss is the standard load-balancing loss (mean_prob · mean_dispatch
    · E) keeping experts evenly used."""
    logits = x @ router_w.astype(x.dtype)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top = jnp.argmax(probs, axis=-1)
    onehot = jax.nn.one_hot(top, num_experts, dtype=x.dtype)
    gate = jnp.max(probs, axis=-1).astype(x.dtype)
    # load-balancing aux loss (Switch Transformer eq. 4)
    density = jnp.mean(onehot.astype(jnp.float32), axis=tuple(range(onehot.ndim - 1)))
    mean_prob = jnp.mean(probs, axis=tuple(range(probs.ndim - 1)))
    aux = num_experts * jnp.sum(density * mean_prob)
    return onehot, gate, aux


def routed_experts(x, scores, select_bias, w_in, w_out, held, top_k,
                   scale=1.0, activation=jax.nn.relu):
    """This chip's part of a top-k expert layer.

    - x (T, D): the tokens, in the experts' input width
    - scores (T, E) float32: the router's scores over ALL E experts
    - select_bias (E,): added to the scores for the CHOICE of the top_k only
      (a load-balancing correction); the combine weights are the chosen
      scores themselves, normalised over all top_k chosen — held here or
      not — and times `scale`
    - w_in (n, D, F), w_out (n, F, D): the held experts' weights, expert
      `first + j` at index j; `held = (first, n)`
    - activation: between the two projections (expert e computes
      `activation(x @ w_in[e]) @ w_out[e]`)

    Returns (out (T, D) float32, counts (3,) int32 as `ROUTED_COUNTS`:
    the (token, held expert) pairs computed, the held experts with at
    least one pair, and the fullest expert's pairs).

    The pair buffer has T·top_k rows, what every chosen expert being held
    here would fill; rows past the held pairs belong to no group, and the
    grouped product skips them."""
    first, n = held
    t, k = x.shape[0], int(top_k)
    _, idx = lax.top_k(scores + select_bias, k)               # (T, k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weights = scale * chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
    local = idx - first
    is_held = (local >= 0) & (local < n)
    # pairs sorted by held expert; those of experts held elsewhere last
    key = jnp.where(is_held, local, n).reshape(-1)            # (T·k,)
    order = jnp.argsort(key)
    group_sizes = jnp.bincount(key, length=n + 1)[:n].astype(jnp.int32)
    rows = jnp.take(x, order // k, axis=0)                    # (T·k, D)
    h = lax.ragged_dot(rows, w_in.astype(x.dtype), group_sizes,
                       preferred_element_type=jnp.float32)
    y = lax.ragged_dot(activation(h).astype(x.dtype), w_out.astype(x.dtype),
                       group_sizes, preferred_element_type=jnp.float32)
    # back to (token, choice) order; a pair not held adds nothing
    y = jnp.take(y, jnp.argsort(order), axis=0).reshape(t, k, -1)
    out = jnp.where(is_held[..., None], weights[..., None] * y, 0.0).sum(1)
    counts = jnp.stack([group_sizes.sum(), (group_sizes > 0).sum(),
                        group_sizes.max()]).astype(jnp.int32)
    return out, counts
