"""Pallas TPU kernel for a sparse-attention indexer's scores over a whole
sequence (DeepSeek-Sparse-Attention's "lightning indexer"):

    I[t, s] = scale * sum_j w[t, j] * relu(q[j, t] . k[s])    for s <= t

J small heads of a narrow width read ONE shared key head. In XLA the J
per-head products would be written out before the ReLU and the weighted
sum over heads can run: J x Tq x Tk float32, 4.3 GB for 4096 queries of 16
heads against 16384 keys. The kernel keeps a (block_q, block_k) tile of
the sum in VMEM, walks the heads inside a grid step and writes the tile
once; tiles wholly above the diagonal are filled and not computed.

`index_scores_decode` is the same sum for ONE query a slot against the
slot's rung of index keys in a decode cache leaf, read in place. The leaf
is PACKED, `(S, C / 2, 2 D)`: row r holds the keys of positions 2r and
2r + 1 side by side (`pack_rows`). D = 64 is half a lane tile, and the
chip gives a `(S, C, 64)` array a layout of its own choosing: the compiled
decode step copied every such leaf whole on the way in, and again on the
way out (1.1 GB of temporaries a step at 32 slots of 18432 rows). Packed,
the minor dimension is one whole lane tile and the leaf lies as every
other cache leaf does. Tiles past a slot's position are neither fetched
nor computed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _weighted(products, w, scale):
    """`scale * sum_j w[..., j] relu(products[..., j, :])`: (..., J, C)
    products and (..., J) weights -> (..., C) float32."""
    return scale * (w.astype(jnp.float32)[..., None]
                    * jnp.maximum(products, 0.0)).sum(-2)


def index_scores_dense(q, k, w, scale, q_offset=0):
    """The scores as their equation reads, in XLA: q (J, Tq, D), k (Tk, D),
    w (Tq, J) -> (Tq, Tk) float32, -inf above the diagonal (query t sits
    at position `q_offset + t`)."""
    s = jnp.einsum("jqd,kd->qjk", q, k, preferred_element_type=jnp.float32)
    seen = jnp.arange(k.shape[0])[None, :] \
        <= q_offset + jnp.arange(q.shape[1])[:, None]
    return jnp.where(seen, _weighted(s, w, scale), -jnp.inf)


def _kernel(q_ref, k_ref, w_ref, o_ref, *, scale, q_offset):
    i, j = pl.program_id(0), pl.program_id(1)
    bq, bk = o_ref.shape
    first = q_offset + i * bq                  # the tile's first query

    @pl.when(j * bk > first + bq - 1)
    def _above():
        o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)

    @pl.when(j * bk <= first + bq - 1)
    def _compute():
        k = k_ref[...]
        w = w_ref[...].astype(jnp.float32)
        acc = jnp.zeros((bq, bk), jnp.float32)
        for h in range(q_ref.shape[0]):
            s = jax.lax.dot_general(
                q_ref[h], k, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc = acc + w[:, h:h + 1] * jnp.maximum(s, 0.0)
        row = first + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        col = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        o_ref[...] = jnp.where(col <= row, scale * acc, -jnp.inf)


def index_scores(q, k, w, scale, q_offset=0, impl="auto", block_q=256,
                 block_k=512, interpret=None):
    """Index scores of a block of queries against a sequence's index keys.

    - q (J, Tq, D): the indexer's query heads; query t is at position
      `q_offset + t` (static)
    - k (Tk, D): the one index-key head, positions 0..Tk-1
    - w (Tq, J): the heads' weights
    - impl: 'auto' (the kernel on a TPU, XLA elsewhere), 'pallas'
      (interpreted off the TPU unless `interpret` says otherwise), 'dense'

    Returns (Tq, Tk) float32, -inf at s > q_offset + t. Products in the
    operands' dtype with float32 sums; ReLU, weights and the sum over
    heads float32."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "dense"
    if impl == "dense":
        return index_scores_dense(q, k, w, scale, q_offset)
    if impl != "pallas":
        raise ValueError(f"unknown index_scores impl {impl!r}; expected "
                         f"'auto', 'pallas' or 'dense'")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    nh, tq, d = q.shape
    tk = k.shape[0]
    bq, bk = min(block_q, tq), min(block_k, tk)
    pq, pk = -tq % bq, -tk % bk
    if pq or pk:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
        w = jnp.pad(w, ((0, pq), (0, 0)))
        k = jnp.pad(k, ((0, pk), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, q_offset=int(q_offset)),
        grid=((tq + pq) // bq, (tk + pk) // bk),
        in_specs=[pl.BlockSpec((nh, bq, d), lambda i, j: (0, i, 0)),
                  pl.BlockSpec((bk, d), lambda i, j: (j, 0)),
                  pl.BlockSpec((bq, nh), lambda i, j: (i, 0))],
        out_specs=pl.BlockSpec((bq, bk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((tq + pq, tk + pk), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="index_scores",
    )(q, k, w)
    return out[:tq, :tk]


def pack_rows(rows):
    """Index-key rows (..., C, D), C even, as a packed leaf holds them:
    (..., C / 2, 2 D), positions 2r and 2r + 1 side by side in row r."""
    *lead, c, d = rows.shape
    return rows.reshape(*lead, c // 2, 2 * d)


def unpack_rows(packed):
    """`pack_rows` undone: (..., C / 2, 2 D) -> (..., C, D)."""
    *lead, half, d2 = packed.shape
    return packed.reshape(*lead, 2 * half, d2 // 2)


def write_packed_row(leaf, pos, rows):
    """A packed leaf `(S, C / 2, 2 D)` with slot s's key of position pos[s]
    set to rows[s] (S, D): the half of row pos // 2 that position owns; the
    other half stays. A position past the leaf is dropped."""
    slot = jnp.arange(leaf.shape[0])
    d = rows.shape[-1]
    old = leaf[slot, pos // 2]                                  # (S, 2 D)
    own = (jnp.arange(2 * d) >= d)[None, :] == (pos % 2 == 1)[:, None]
    both = jnp.concatenate([rows, rows], axis=-1).astype(leaf.dtype)
    return leaf.at[slot, pos // 2].set(jnp.where(own, both, old))


def _decode_kernel(pos_ref, q_ref, w_ref, k_ref, o_ref, *, scale):
    """Grid (slot, k_tiles): one slot's J index queries against a (block_k,
    2 D) tile of its packed keys. The queries come twice, in the left lanes
    and in the right (2 J rows), so that ONE product gives the even
    positions' scores in rows 0..J-1 and the odd ones' in J..2J-1; they
    leave as the two rows of a (2, block_k) tile."""
    s, j = pl.program_id(0), pl.program_id(1)
    bk = k_ref.shape[1]
    nh = q_ref.shape[1] // 2
    pos = pos_ref[s]

    @pl.when(2 * j * bk > pos)
    def _past():
        o_ref[0] = jnp.full(o_ref.shape[1:], -jnp.inf, o_ref.dtype)

    @pl.when(2 * j * bk <= pos)
    def _compute():
        prod = jax.lax.dot_general(
            q_ref[0], k_ref[0], dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # (2 J, block_k)
        val = scale * w_ref[0].astype(jnp.float32) * jnp.maximum(prod, 0.0)
        val = jnp.concatenate(
            [jnp.sum(val[:nh], axis=0, keepdims=True),
             jnp.sum(val[nh:], axis=0, keepdims=True)], axis=0)
        at = 2 * (j * bk + jax.lax.broadcasted_iota(jnp.int32, (2, bk), 1)) \
            + jax.lax.broadcasted_iota(jnp.int32, (2, bk), 0)
        o_ref[0] = jnp.where(at <= pos, val, -jnp.inf)


def index_scores_decode(q, packed, w, pos, scale, impl="auto",
                        block_k=1024, interpret=None):
    """One token a slot against its rung of index keys.

    - q (S, J, D): each slot's index queries; w (S, J) their weights
    - packed (S, C / 2, 2 D): a decode cache's index-key leaf
      (`pack_rows`), read in place
    - pos (S,) int32: positions 0..pos[s] of slot s are in use

    Returns (S, C) float32 in the positions' order, -inf past a slot's
    position. impl as `index_scores`."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "dense"
    s, half, d2 = packed.shape
    c = 2 * half
    if impl == "dense":
        prod = jnp.einsum("sjd,scd->sjc", q, unpack_rows(packed),
                          preferred_element_type=jnp.float32)
        return jnp.where(jnp.arange(c)[None, :] <= pos[:, None],
                         _weighted(prod, w, scale), -jnp.inf)
    if impl != "pallas":
        raise ValueError(f"unknown index_scores impl {impl!r}; expected "
                         f"'auto', 'pallas' or 'dense'")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    nh = q.shape[1]
    bk = block_k if half % block_k == 0 else half
    zero = jnp.zeros_like(q)
    twice = jnp.concatenate([jnp.concatenate([q, zero], axis=-1),
                             jnp.concatenate([zero, q], axis=-1)], axis=1)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s, half // bk),
            in_specs=[
                pl.BlockSpec((1, 2 * nh, d2), lambda i, j, pos: (i, 0, 0)),
                pl.BlockSpec((1, 2 * nh, 1), lambda i, j, pos: (i, 0, 0)),
                # a tile past the position is not computed: name the last
                # one that is, so that nothing is fetched for it
                pl.BlockSpec((1, bk, d2), lambda i, j, pos: (
                    i, jnp.minimum(j, jnp.minimum(pos[i] // 2, half - 1)
                                   // bk), 0)),
            ],
            out_specs=pl.BlockSpec((1, 2, bk), lambda i, j, pos: (i, 0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((s, 2, half), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="index_scores_decode",
    )(pos.astype(jnp.int32), twice, jnp.concatenate([w, w], axis=1)[..., None],
      packed)
    # (S, 2, C / 2): evens and odds apart -> the positions' order
    return out.transpose(0, 2, 1).reshape(s, c)
