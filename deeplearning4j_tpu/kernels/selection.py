"""Exact selection without a sort: the k-th largest value of a row by
counting passes, the k largest entries as a mask, and a mask's set
positions as a short list of indices.

Shared by the sampler (`generation/sampling.py`: the top-k threshold of a
row of logits) and by learned sparse attention (`models/keye_vl.py`: the
cache rows an indexer keeps for a query). Everything here is dense
arithmetic of fixed shape: compares, sums along a row and small matrix
products; no sort, no scatter and no scalar gather, each of which costs a
TPU microseconds an entry.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["compact_indices", "kth_largest", "top_k_mask"]

#: positions a block of the prefix counts holds: one lane tile
_BLOCK = 128


@jax.named_scope("select")
def kth_largest(x, k):
    """The k-th largest value of each row, exactly, without sorting
    (scope `sample/select` in a profiler trace).

    - x: (S, V) float32
    - k: (S,) int32 in 1..V, a different one in every row

    Returns (S,) float32: what an ascending sort of row s holds at
    index V - k[s]. The row is mapped once to an unsigned image that
    orders as the floats do (-inf lowest); the threshold is then built
    bit by bit from the top: a bit stays set where at least k elements
    lie at or above the candidate. 32 fused compare-and-count passes
    over the row, no sorted copy (the sort was a third of BERT-base's
    decode step on a v5e: `PERF.md`, PR 29)."""
    b = lax.bitcast_convert_type(x, jnp.uint32)
    top = jnp.uint32(1 << 31)
    u = jnp.where(b >= top, ~b, b | top)

    def grow(i, prefix):
        cand = prefix | (top >> i.astype(jnp.uint32))
        cnt = jnp.sum(u >= cand[:, None], axis=-1, dtype=jnp.int32)
        return jnp.where(cnt >= k, cand, prefix)

    prefix = lax.fori_loop(0, 32, grow, jnp.zeros(x.shape[:1], jnp.uint32))
    return lax.bitcast_convert_type(
        jnp.where(prefix >= top, prefix ^ top, ~prefix), jnp.float32)


def _prefix_counts(mask):
    """Running counts of a mask (S, C), C a multiple of `_BLOCK`, in blocks:
    (inside (S, C / 128, 128) float32: the set positions of a block up to
    and with each of its lanes; before (S, C / 128) int32: those of all the
    blocks before it). A block's count is a product with a triangle of
    ones, exact in bfloat16 operands (0 and 1) with float32 sums."""
    s, c = mask.shape
    blocks = mask.reshape(s, c // _BLOCK, _BLOCK)
    upto = jnp.tril(jnp.ones((_BLOCK, _BLOCK), jnp.bfloat16)).T
    inside = jnp.einsum("sbl,lm->sbm", blocks.astype(jnp.bfloat16), upto,
                        preferred_element_type=jnp.float32)
    sizes = inside[..., -1].astype(jnp.int32)
    return inside, jnp.cumsum(sizes, axis=-1) - sizes


def _pad_blocks(a, fill):
    pad = -a.shape[-1] % _BLOCK
    return a if not pad else jnp.pad(a, ((0, 0), (0, pad)),
                                     constant_values=fill)


def top_k_mask(x, k):
    """The k largest entries of each row as a mask, exactly `k[s]` of them
    set in row s; entries that tie at the k-th value go to the lower index
    (the set `lax.top_k` returns).

    - x: (S, C) float32; an entry that must not be chosen holds -inf (and
      k counts only the others)
    - k: (S,) int32 in 1..C

    Returns (S, C) bool. The threshold is `kth_largest`'s; everything
    above it is in, and of the entries equal to it the first few that fill
    the count."""
    c = x.shape[-1]
    kth = kth_largest(x, k)[:, None]
    above, ties = x > kth, x == kth
    inside, before = _prefix_counts(_pad_blocks(ties, False))
    # a tie's rank among the ties of its row, from 0
    rank = (before[..., None] + inside.astype(jnp.int32)).reshape(
        x.shape[0], -1)[:, :c] - 1
    room = k[:, None] - above.sum(-1, dtype=jnp.int32, keepdims=True)
    return above | (ties & (rank < room))


def compact_indices(mask, width):
    """The positions a mask sets, in ascending order, as indices.

    - mask: (S, C) bool
    - width: how many to list a row (static)

    Returns (S, width) int32: entry j of row s is the position of the
    (j + 1)-th set bit of `mask[s]`; past the row's last set bit the
    entries are some position in range that the caller masks away. Two
    levels of counting: which block of 128 holds the j-th set bit (compare
    j with the blocks' running counts), then which lane of that block (the
    block's row of running counts, fetched by a one-hot product, compared
    with what is left of j)."""
    c = mask.shape[-1]
    inside, before = _prefix_counts(_pad_blocks(mask, False))
    nb = before.shape[-1]
    through = before + inside[..., -1].astype(jnp.int32)      # (S, nb)
    j = jnp.arange(width, dtype=jnp.int32)
    block = jnp.minimum(
        (through[:, None, :] <= j[None, :, None]).sum(-1, dtype=jnp.int32),
        nb - 1)                                               # (S, width)
    own = block[..., None] == jnp.arange(nb, dtype=jnp.int32)
    left = j[None, :] - jnp.where(own, before[:, None, :], 0).sum(-1)
    # counts up to 128 are whole numbers in bfloat16
    rows = jnp.einsum("swb,sbl->swl", own.astype(jnp.bfloat16),
                      inside.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)
    lane = (rows <= left[..., None].astype(jnp.float32)).sum(
        -1, dtype=jnp.int32)
    return jnp.minimum(block * _BLOCK + lane, c - 1)
