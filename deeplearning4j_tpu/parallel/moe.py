"""Expert parallelism helpers (`ep` mesh axis).

The reference has no MoE; this is a TPU-native addition. Two routers live
here:

- `switch_router`: top-1 switch routing for `models.bert` MoE layers. Their
  expert-major parameter tensors shard their leading dim over ep
  (sharding_rules), each chip holds |E|/|ep| experts, and the experts run as
  a one-hot dispatch einsum that computes every expert for every token.
- `routed_experts`: top-k routing over ALL of a layer's experts for a chip
  that HOLDS a contiguous share of them (`held = (first, count)`): every
  (token, held expert) pair is computed, `activation(x @ w_in[e]) @
  w_out[e]` (or, with a gate stack, `(activation(x @ w_gate[e]) * (x @
  w_in[e])) @ w_out[e]`), as ONE grouped product over the held experts'
  stacked weights,
  so an expert's weights are read once a call and only if a token was
  routed to it. On a TPU, at widths that are multiples of 128, that is the
  Pallas kernel of `kernels/grouped_matmul.py` (both projections fused, an
  expert's weight blocks whole in VMEM, row tiles of 16 to 128 rows sized
  from the rows an expert sees on average); elsewhere (the CPU, the tests'
  toy widths) two `lax.ragged_dot`s over the pairs sorted by expert. The
  choice reads the backend and the operands' shapes, nothing else. What
  the experts held elsewhere would add is left out: on one chip the layer
  runs without its exchange, and nothing here stands in for it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.kernels import grouped_matmul

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


def _row_tile(rows_per_expert):
    """The kernel's row tile for experts that see `rows_per_expert` rows a
    call on average: the first of 16, 32, 64, 128 to hold twice the mean
    (the fullest expert sees 2.6 times it at 128 tokens over 512 experts),
    so that most experts are one tile, one pass of their weights through
    the MXU, and the tiles are not mostly padding to gather and write."""
    tile = 16
    while tile < 128 and tile < 2 * rows_per_expert:
        tile *= 2
    return tile


def routed_experts(x, scores, select_bias, w_in, w_out, held, top_k,
                   scale=1.0, activation=jax.nn.relu, impl="auto",
                   interpret=None, w_gate=None):
    """This chip's part of a top-k expert layer.

    - x (T, D): the tokens, in the experts' input width
    - scores (T, E) float32: the router's scores over ALL E experts
    - select_bias (E,) or None: added to the scores for the CHOICE of the
      top_k only (a load-balancing correction); the combine weights are
      the chosen scores themselves, normalised over all top_k chosen —
      held here or not — and times `scale`
    - w_in (n, D, F), w_out (n, F, D): the held experts' weights, expert
      `first + j` at index j; `held = (first, n)`
    - activation: between the two projections (expert e computes
      `activation(x @ w_in[e]) @ w_out[e]`)
    - w_gate (n, D, F) or None: a GATED expert's third stack; expert e
      then computes `(activation(x @ w_gate[e]) * (x @ w_in[e])) @
      w_out[e]` (SwiGLU with `activation = silu`). Without it the
      programs are what they were

    Returns (out (T, D) float32, counts (3,) int32 as `ROUTED_COUNTS`:
    the (token, held expert) pairs computed, the held experts with at
    least one pair, and the fullest expert's pairs).

    There are T·top_k pairs, what every chosen expert being held here
    would fill; those of experts held elsewhere belong to no group, the
    grouped product skips them and what it leaves in their rows is
    selected away.

    impl: 'auto' (the Pallas kernel on a TPU at widths it takes,
    `lax.ragged_dot` elsewhere), 'pallas' (force the kernel; interpreted
    off the TPU unless `interpret` says otherwise) or 'ragged'."""
    first, n = held
    t, k = x.shape[0], int(top_k)
    _, idx = lax.top_k(scores if select_bias is None
                       else scores + select_bias, k)          # (T, k)
    # (a selection over the experts' axis, not `take_along_axis`: a scalar
    # gather of T·k entries costs the TPU 8 ns each)
    chosen = jnp.where(idx[..., None] == jnp.arange(scores.shape[-1]),
                       scores[:, None, :], 0.0).sum(-1)
    weights = scale * chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
    local = idx - first
    is_held = (local >= 0) & (local < n)
    # a pair's group: its held expert, or n for one held elsewhere
    key = jnp.where(is_held, local, n).reshape(-1)            # (T·k,)
    group_sizes = (key[:, None] == jnp.arange(n)[None, :]).sum(
        0, dtype=jnp.int32)
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" \
            and grouped_matmul.supported(*w_in.shape[1:], w_out.shape[2]) \
            else "ragged"
    if impl not in ("pallas", "ragged"):
        raise ValueError(f"unknown routed_experts impl {impl!r}; expected "
                         f"'auto', 'pallas' or 'ragged'")
    if impl == "pallas":
        y = grouped_matmul.grouped_mlp(
            x, w_in, w_out, key, activation,
            rows=jnp.arange(t * k, dtype=jnp.int32) // k,
            row_tile=_row_tile(t * k / scores.shape[-1]),
            interpret=interpret, w_gate=w_gate)
    else:
        # pairs sorted by held expert, those of experts held elsewhere
        # last: rows past the groups, which the grouped product skips
        order = jnp.argsort(key)
        xs = jnp.take(x, order // k, axis=0)
        h = lax.ragged_dot(xs, w_in.astype(x.dtype), group_sizes,
                           preferred_element_type=jnp.float32)
        if w_gate is None:
            h = activation(h)
        else:
            h = activation(lax.ragged_dot(
                xs, w_gate.astype(x.dtype), group_sizes,
                preferred_element_type=jnp.float32)) * h
        y = lax.ragged_dot(h.astype(x.dtype),
                           w_out.astype(x.dtype), group_sizes,
                           preferred_element_type=jnp.float32)
        y = jnp.take(y, jnp.argsort(order), axis=0)
    # a pair not held adds nothing
    y = y.reshape(t, k, -1)
    out = jnp.where(is_held[..., None], weights[..., None] * y, 0.0).sum(1)
    counts = jnp.stack([group_sizes.sum(), (group_sizes > 0).sum(),
                        group_sizes.max()]).astype(jnp.int32)
    return out, counts
