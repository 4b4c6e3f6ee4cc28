"""Decode attention over a LATENT cache (multi-head latent attention in its
absorbed form): every query head of a slot attends ONE shared row a cached
position, and the value is the first lanes of the key.

A cached position holds `c` (L normed latent values) and `kr` (R rotated key
lanes). With the key up-projection folded into the query (`q_lat[h] =
q_nope[h] W_UK[h]^T`), head h's score against position s is `q_lat[h] . c_s
+ q_rope[h] . kr_s`, and its output in the latent space is `sum_s p[h]_s
c_s`: the row is key AND value, and the kernel reads it once.

The leaf. Rows major, TWO positions a row: `(S, C / 2, 2 L + 2 R)`, row r
holding `[c_2r, c_2r+1, kr_2r, kr_2r+1]`. At L = 512 and R = 64 that is 1152
lanes, nine whole lane tiles, where a `(S, C, 576)` leaf is four and a half
(the chip's tiled layout pads it to 640) and a `(S, C, 64)` leaf of rotary
keys beside a `(S, C, 512)` one is half a tile (`generation/decode.py`, on
Keye's index keys: the chip copied every such leaf whole, in and out). A
softmax does not care in which order its rows come, so nothing is ever
un-packed: the even positions of a tile are scored against lanes 0..L-1
and the odd ones against lanes L..2L-1, both sets enter one running
maximum and sum, and the two weighted sums of value rows add up.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.kernels.flash_attention import (_NEG_INF,
                                                        decode_tile_rows)


def pack_latent(c, kr):
    """Latent rows `c` (..., C, L) and rotary key rows `kr` (..., C, R), C
    even, as the leaf holds them: (..., C / 2, 2 L + 2 R)."""
    *lead, rows, lat = c.shape
    return jnp.concatenate(
        [c.reshape(*lead, rows // 2, 2 * lat),
         kr.reshape(*lead, rows // 2, 2 * kr.shape[-1])], axis=-1)


def unpack_latent(leaf, lat):
    """`pack_latent` undone: (c (..., C, L), kr (..., C, R))."""
    *lead, half, _ = leaf.shape
    return (leaf[..., :2 * lat].reshape(*lead, 2 * half, lat),
            leaf[..., 2 * lat:].reshape(*lead, 2 * half, -1))


def write_latent_row(leaf, pos, c, kr):
    """The leaf with slot s's position pos[s] set to (c[s] (L,), kr[s]
    (R,)): the half of row pos // 2 that the position owns; the other half
    stays. A position past the leaf is dropped."""
    slot = jnp.arange(leaf.shape[0])
    lat, rope = c.shape[-1], kr.shape[-1]
    old = leaf[slot, pos // 2]                        # (S, 2 L + 2 R)
    lane = jnp.arange(2 * lat + 2 * rope)
    odd_half = jnp.where(lane < 2 * lat, lane >= lat, lane >= 2 * lat + rope)
    own = odd_half[None, :] == (pos % 2 == 1)[:, None]
    both = jnp.concatenate([c, c, kr, kr], axis=-1).astype(leaf.dtype)
    return leaf.at[slot, pos // 2].set(jnp.where(own, both, old))


def latent_tile_positions(rung, lat, dtype):
    """Cached positions a grid step of `mla_attention_decode` reads on a
    rung of `rung` positions: `flash_attention.decode_tile_rows`' rule (the
    largest of its tiles that divides the rung and whose K rows hold at
    most 1 MiB) over the latent's L lanes, the part that is key AND value;
    the rotary lanes ride along. 1024 positions, 512 packed rows of 2304
    B, at the published widths: 1.18 MB a grid step where the K and V
    kernel that rule was measured on reads 2 MiB. On a v5e at `(48, 9216,
    1152)` bfloat16 under the cell's positions (9057 rows in use a slot,
    `PERF.md`, PR 37): 1.632, 1.156, 0.913 and 0.924 ms a call at 256, 512,
    1024 and 2048 positions."""
    return decode_tile_rows(rung, lat, dtype)


def _ragged_tile(i, j, lengths, block_k):
    """(slot, tile) of the K, V and mask block that grid step (i, j)
    names: tile j up to the slot's last tile in use, `(max(lengths[i], 1) -
    1) // block_k`; past it, the NEXT slot's first tile. A block index that
    does not change is not copied again, so the tiles wholly past a slot's
    rows are never fetched, and the next slot's first tile arrives under
    this slot's last compute instead of after its skipped steps (0.966 ->
    0.940 ms a call of `flash_fwd` against naming the last tile in use
    again, PR 36; since PR 38 that kernel's grid holds no skipped step at
    all, `flash_attention._tiles_in_use`, a form this kernel could take)."""
    past = j > (jnp.maximum(lengths[i], 1) - 1) // block_k
    last_slot = lengths.shape[0] - 1
    return (jnp.where(past, jnp.minimum(i + 1, last_slot), i),
            jnp.where(past, 0, j))


def _mla_decode_kernel(len_ref, ql_ref, qr_ref, c_ref, o_ref, acc_ref, l_ref,
                       m_ref, *, scale, lat):
    """Grid (slot, tiles), tiles innermost: one slot's H absorbed queries
    against a (block, 2 L + 2 R) tile of its packed rows. `ql_ref` (H, L)
    holds the latent queries; `qr_ref` (2 H, 2 R) the rotary ones twice,
    rows 0..H-1 in the even position's lanes and rows H..2H-1 in the odd
    one's, so that ONE product with the tile's last 2 R lanes scores both.
    Operands stay in the leaf's dtype, sums and the softmax are float32.
    A tile wholly past the slot's length is neither computed nor fetched
    (`_ragged_tile`)."""
    i, j = pl.program_id(0), pl.program_id(1)
    block = c_ref.shape[1]
    h = ql_ref.shape[1]
    length = len_ref[i]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        l_ref[...] = jnp.zeros_like(l_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)

    @pl.when(2 * j * block < length)
    def _tile():
        def scores(q, rows):
            return jax.lax.dot_general(
                q, rows, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

        ql = ql_ref[0]
        even, odd = c_ref[0, :, :lat], c_ref[0, :, lat:2 * lat]
        sr = scores(qr_ref[0], c_ref[0, :, 2 * lat:])       # (2 H, block)
        at = 2 * (j * block
                  + jax.lax.broadcasted_iota(jnp.int32, (h, block), 1))
        se = jnp.where(at < length,
                       (scores(ql, even) + sr[:h]) * scale, _NEG_INF)
        so = jnp.where(at + 1 < length,
                       (scores(ql, odd) + sr[h:]) * scale, _NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.maximum(
            jnp.max(se, axis=-1, keepdims=True),
            jnp.max(so, axis=-1, keepdims=True)))
        alpha = jnp.exp(m_prev - m_new)
        # (position 2 j block is in use, so m_new is a real score and a
        # masked entry's exp underflows to 0)
        pe, po = jnp.exp(se - m_new), jnp.exp(so - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(pe, axis=-1, keepdims=True) \
            + jnp.sum(po, axis=-1, keepdims=True)
        m_ref[...] = m_new

        def weighted(p, rows):
            return jax.lax.dot_general(
                p.astype(rows.dtype), rows,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        acc_ref[...] = acc_ref[...] * alpha + weighted(pe, even) \
            + weighted(po, odd)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype)


def _mla_decode_dense(q_lat, q_rope, leaf, lengths, scale):
    """The same attention as one masked softmax a head, in XLA."""
    c, kr = unpack_latent(leaf, q_lat.shape[-1])
    f32 = jnp.float32
    s = (jnp.einsum("shl,scl->shc", q_lat, c, preferred_element_type=f32)
         + jnp.einsum("shr,scr->shc", q_rope, kr,
                      preferred_element_type=f32)) * scale
    seen = jnp.arange(c.shape[1])[None, :] < lengths[:, None]     # (S, C)
    p = jax.nn.softmax(jnp.where(seen[:, None, :], s, _NEG_INF), axis=-1)
    out = jnp.einsum("shc,scl->shl", p.astype(c.dtype), c,
                     preferred_element_type=f32)
    return jnp.where((lengths > 0)[:, None, None], out, 0).astype(
        q_lat.dtype)


@jax.named_scope("flash_decode")
def mla_attention_decode(q_lat, q_rope, leaf, lengths, scale, impl="auto",
                         block_k=None, interpret=None):
    """One absorbed query a head a slot against the slot's latent rows.

    - q_lat (S, H, L): the queries in the latent space (`q_nope W_UK^T`);
      q_rope (S, H, R): their rotated lanes
    - leaf (S, C / 2, 2 L + 2 R): a decode cache's latent leaf
      (`pack_latent`), read in place, each row once
    - lengths (S,) int32: positions 0..lengths[s] - 1 of slot s are in use;
      whatever lies past them is never looked at. 0 gives zeros
    - scale: of the scores (the expanded head's `1 / sqrt(nope + R)`)
    - impl: 'auto' (the kernel on a TPU, XLA elsewhere), 'pallas'
      (interpreted off the TPU unless `interpret` says otherwise), 'dense'
    - block_k: positions a grid step reads (even; a rung it does not
      divide is one tile); by default `latent_tile_positions`

    Returns (S, H, L) in the queries' dtype: `sum_s p[h]_s c_s`, which the
    value up-projection takes to the head's output. Forward only."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "dense"
    s, h, lat = q_lat.shape
    rope = q_rope.shape[-1]
    half, lanes = leaf.shape[1:]
    if leaf.shape[0] != s or lanes != 2 * (lat + rope) \
            or lengths.shape != (s,):
        raise ValueError(
            f"leaf must be (S, C / 2, 2 L + 2 R) = ({s}, C / 2, "
            f"{2 * (lat + rope)}) and lengths (S,), got {leaf.shape} and "
            f"{lengths.shape}")
    lengths = jnp.minimum(lengths.astype(jnp.int32), 2 * half)
    if impl == "dense":
        return _mla_decode_dense(q_lat, q_rope, leaf, lengths, scale)
    if impl != "pallas":
        raise ValueError(f"unknown decode impl {impl!r}; expected 'auto', "
                         f"'pallas' or 'dense'")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if block_k is None:
        block_k = latent_tile_positions(2 * half, lat, leaf.dtype)
    if block_k % 2 or (2 * half) % block_k:
        block_k = 2 * half
    block = block_k // 2                                   # packed rows
    hp = -(-h // 8) * 8            # float32 sublane tile of the scores
    ql = jnp.pad(q_lat, ((0, 0), (0, hp - h), (0, 0))).astype(leaf.dtype)
    qr = jnp.pad(q_rope, ((0, 0), (0, hp - h), (0, 0))).astype(leaf.dtype)
    zero = jnp.zeros_like(qr)
    twice = jnp.concatenate([jnp.concatenate([qr, zero], axis=-1),
                             jnp.concatenate([zero, qr], axis=-1)], axis=1)

    def row_at(i, j, n):
        return i, 0, 0

    out = pl.pallas_call(
        functools.partial(_mla_decode_kernel, scale=float(scale), lat=lat),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s, half // block),
            in_specs=[
                pl.BlockSpec((1, hp, lat), row_at),
                pl.BlockSpec((1, 2 * hp, 2 * rope), row_at),
                pl.BlockSpec((1, block, lanes), lambda i, j, n: (
                    *_ragged_tile(i, j, n, block_k), 0)),
            ],
            out_specs=pl.BlockSpec((1, hp, lat), row_at),
            scratch_shapes=[
                pltpu.VMEM((hp, lat), jnp.float32),
                pltpu.VMEM((hp, 1), jnp.float32),
                pltpu.VMEM((hp, 1), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((s, hp, lat), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name="mla_decode",
    )(lengths, ql, twice, leaf)
    return out[:, :h]
