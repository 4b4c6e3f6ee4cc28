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
  from the rows an expert sees on average), which picks each held pair's
  token row itself and adds the pair's weighted result into the token's
  output row itself: no row is moved outside it for a pair, held here or
  not; elsewhere (the CPU, the tests' toy widths) two `lax.ragged_dot`s
  over the pairs sorted by expert, their results gathered back and summed
  with their weights. The choice reads the backend and the operands'
  shapes, nothing else. What the experts held elsewhere would add is left
  out: on one chip the layer runs without its exchange, and nothing here
  stands in for it.
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


def takes_kernel(w_in, w_out):
    """Whether `routed_experts(impl="auto")` over these weight stacks takes
    the Pallas kernel: on a TPU, at widths Mosaic takes."""
    return jax.default_backend() == "tpu" \
        and grouped_matmul.supported(*w_in.shape[1:], w_out.shape[2])


def kernel_runs(t, top_k, tm, x_dtype, w_in, w_out, w_gate=None):
    """(calls, tokens a call) of the kernel over t tokens of `x_dtype`,
    top_k pairs each, at row tile tm: one call where the tokens fit what a
    call may hold beside its weight blocks (`grouped_matmul.rows_a_call`),
    else runs of equal length, a multiple of 16. Each call reads the
    weights of the experts it uses."""
    n, k, f = w_in.shape
    d = w_out.shape[2]
    stacks = 1 if w_gate is None else 2
    per_call = grouped_matmul.rows_a_call(
        t, top_k, k, d, jnp.dtype(x_dtype).itemsize, n, tm,
        grouped_matmul.weight_bytes(stacks * k, f, d,
                                    jnp.dtype(w_in.dtype).itemsize))
    if per_call >= t:
        return 1, t
    runs = -(-t // per_call)
    return runs, (-(-t // runs) + 15) // 16 * 16


def routed_calls(t, top_k, experts, x_dtype, w_in, w_out, w_gate=None):
    """How many times `routed_experts(impl="auto")` over t tokens, top_k of
    `experts` each, reads the held experts' weights: its kernel's calls
    (`kernel_runs`) where it takes the kernel, else once."""
    if not takes_kernel(w_in, w_out):
        return 1
    return kernel_runs(t, top_k, _row_tile(t * top_k / experts), x_dtype,
                       w_in, w_out, w_gate)[0]


def _kernel_runs(x, key, weights, w_in, w_out, n, k, tm, activation,
                 interpret, w_gate, init):
    """The kernel over the tokens x (T, D), their pairs' groups `key` (T·k,)
    and weights (T, k), each token's sum started from its row of `init`
    (T, D) where given: in as many calls as `kernel_runs` says, the last
    run padded with tokens whose pairs have no group, the runs' sums
    started from and written into one buffer where there is a start."""
    t = x.shape[0]
    runs, run = kernel_runs(t, k, tm, x.dtype, w_in, w_out, w_gate)

    def call(x, key, weights, init, at=None):
        return grouped_matmul.grouped_mlp(
            x, w_in, w_out, key, activation, weights=weights,
            rows=jnp.arange(key.shape[0], dtype=jnp.int32) // k,
            row_tile=tm, interpret=interpret, w_gate=w_gate, init=init,
            at=at)

    if runs == 1:
        return call(x, key, weights.reshape(-1), init)
    pad = ((0, runs * run - t), (0, 0))
    x, key, weights = (jnp.pad(x, pad), jnp.pad(
        key.reshape(t, k), pad, constant_values=n), jnp.pad(weights, pad))
    # unrolled: under `lax.map` XLA would place the stacked results in
    # VMEM beside the kernel's own
    starts = range(0, runs * run, run)
    # without a start the results are joined: one pass over them, as
    # zeroing a buffer for them would be
    if init is None:
        return jnp.concatenate([
            call(x[i:i + run], key[i:i + run].reshape(-1),
                 weights[i:i + run].reshape(-1), None)
            for i in starts])[:t]
    # each run starts from its rows of ONE buffer and writes its sums over
    # them: no run's start is sliced out and no results are joined
    out = jnp.pad(init.astype(jnp.float32), pad)
    for i in starts:
        out = call(x[i:i + run], key[i:i + run].reshape(-1),
                   weights[i:i + run].reshape(-1), out, at=i)
    return out[:t]


def routed_experts(x, scores, select_bias, w_in, w_out, held, top_k,
                   scale=1.0, activation=jax.nn.relu, impl="auto",
                   interpret=None, w_gate=None, init=None, norm_eps=1e-20):
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
    - init (T, D) float32 or None: where each token's sum starts (a shared
      expert's output that the layer adds anyway). The kernel starts its
      sum from it and writes the result over it, so a caller computes it
      BEFORE the routed experts and adds nothing after them
    - norm_eps: added to the chosen scores' sum before the weights are
      divided by it (a family's own constant; 1e-20 guards a zero sum)

    Returns (out (T, D) float32, the routed sum after `init`; counts (3,)
    int32 as `ROUTED_COUNTS`: the (token, held expert) pairs computed, the
    held experts with at least one pair, and the fullest expert's pairs).

    There are T·top_k pairs, what every chosen expert being held here
    would fill; those of experts held elsewhere belong to no group and add
    nothing: the kernel neither computes nor adds them, the ragged path
    selects their rows away.

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
    weights = scale * chosen / (chosen.sum(-1, keepdims=True) + norm_eps)
    local = idx - first
    is_held = (local >= 0) & (local < n)
    # a pair's group: its held expert, or n for one held elsewhere
    key = jnp.where(is_held, local, n).reshape(-1)            # (T·k,)
    group_sizes = (key[:, None] == jnp.arange(n)[None, :]).sum(
        0, dtype=jnp.int32)
    if impl == "auto":
        impl = "pallas" if takes_kernel(w_in, w_out) else "ragged"
    if impl not in ("pallas", "ragged"):
        raise ValueError(f"unknown routed_experts impl {impl!r}; expected "
                         f"'auto', 'pallas' or 'ragged'")
    if impl == "pallas":
        out = _kernel_runs(x, key, weights, w_in, w_out, n, k,
                           _row_tile(t * k / scores.shape[-1]), activation,
                           interpret, w_gate, init)
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
        y = jnp.take(y, jnp.argsort(order), axis=0).reshape(t, k, -1)
        # a pair not held adds nothing
        out = jnp.where(is_held[..., None], weights[..., None] * y,
                        0.0).sum(1)
        if init is not None:
            out = init + out
    counts = jnp.stack([group_sizes.sum(), (group_sizes > 0).sum(),
                        group_sizes.max()]).astype(jnp.int32)
    return out, counts
