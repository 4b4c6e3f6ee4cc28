"""Pallas TPU grouped matmul for an expert layer: `activation(x @ w_in[g])
@ w_out[g]` for the rows of each group `g`, the two products fused so that
the intermediate never leaves VMEM. With a third stack `w_gate` the expert
is GATED, `(activation(x @ w_gate[g]) * (x @ w_in[g])) @ w_out[g]` (SwiGLU
with `activation = silu`): one more weight block a grid step, the same
grid, rows and layout.

What it is for (`parallel/moe.py` `routed_experts`): a chip that holds 128
experts of (1024, 2688) + (2688, 1024) bfloat16 and gives each 5 to 22 rows
a call. The work is reading 11 MB of weights an expert, so the kernel is
built around that stream and not around the rows:

- the weights are read in place, `(n, K, F)` and `(n, F, D)` as the model
  holds them, one `(K, block_f)` and one `(block_f, D)` block a grid step
  through the ordinary double-buffered pipeline; with `block_f = F` (the
  default where it fits) an expert's blocks stay in VMEM while the grid
  walks over as many row tiles as its group needs, and an expert without a
  row is never fetched;
- the rows are laid out for the kernel: every group starts on a row tile
  (`_layout`), so a tile belongs to one group, needs no mask and is read with
  an aligned block. The row tile is small (16 rows is the bfloat16 sublane
  tile) because an expert's group is: a product of 16 rows and one of 128
  cost the MXU about the same, but 128-row tiles of 5 rows each would be
  mostly padding to gather and to write back;
- the grid is `(tiles in use, F / block_f)`, its first extent read from
  the groups' sizes at run time: rows past the last group are never
  visited, and their output is undefined (callers select them away).

bfloat16 (or float32) operands, float32 accumulation, activation and
result: the numerics of two `lax.ragged_dot`s with
`preferred_element_type=float32` and the activation between them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: VMEM the two weight blocks of a grid step may take, both pipeline
#: buffers counted (a v5e core has 128 MiB; Mosaic's default scoped limit
#: of 16 MiB is raised to what the blocks need)
_WEIGHT_VMEM = 48 << 20


def supported(k, f, d):
    """Whether Mosaic takes these widths: each is the lane (minor)
    dimension of some block, so each is a multiple of 128."""
    return k % 128 == 0 and f % 128 == 0 and d % 128 == 0


def _block_f(k, f, d, itemsize):
    """The largest multiple of 128 that divides `f` whose weight blocks
    fit `_WEIGHT_VMEM` double-buffered; `k` counts every stack that reads
    the rows (twice the contraction for a gated expert)."""
    fits = [b for b in range(128, f + 1, 128)
            if f % b == 0 and 2 * b * (k + d) * itemsize <= _WEIGHT_VMEM]
    if not fits:
        raise ValueError(f"no block of the width {f} fits VMEM at "
                         f"contraction {k} and output {d}")
    return fits[-1]


def _layout(groups, rows, n, tm):
    """Where each pair goes when every group starts on a row tile.

    `groups` (m,) int: pair i's group, outside [0, n) for none; `rows` (m,)
    int: the row of x it reads. Returns
    - tile_group (V,) int32: the group of each row tile, V = m // tm + n the
      most tiles any grouping can need (each group's last tile may be
      partly empty);
    - tiles () int32: how many are in use, the groups' in order;
    - tile_rows (V·tm,): the row of x each tiled row reads (padding reads
      row 0: computed, and read by nobody);
    - tiled (m,) int32: the tiled row of each pair (0 for a pair of no
      group).

    No sort: a pair's place is a running count down its group's column of
    an (m, n) table, which the TPU does in microseconds, and the one
    scalar scatter left (m entries at 8 ns each there: my chip runs, PR
    34) stands for `bincount`, `argsort`, its inverse and two indexings
    of an index."""
    m = groups.shape[0]
    member = groups[:, None] == jnp.arange(n, dtype=groups.dtype)[None, :]
    sizes = member.sum(0, dtype=jnp.int32)
    group_tiles = (sizes + tm - 1) // tm
    tile_end = jnp.cumsum(group_tiles)
    first_tile = tile_end - group_tiles
    # a pair's place: its group's first tile, then its rank in the group
    rank = jnp.cumsum(member, axis=0, dtype=jnp.int32) - 1
    tiled = jnp.where(member, first_tile * tm + rank, 0).sum(-1)
    v = jnp.arange(m // tm + n, dtype=jnp.int32)
    tile_group = jnp.minimum(
        (v[:, None] >= tile_end[None, :]).sum(-1, dtype=jnp.int32), n - 1)
    # each tiled row's row of x: written by the pair that sits there (a
    # pair of no group writes past the end, which drops it)
    tile_rows = jnp.zeros((v.shape[0] * tm,), rows.dtype).at[
        jnp.where((groups >= 0) & (groups < n), tiled,
                  v.shape[0] * tm)].set(rows, mode="drop")
    return tile_group, tile_end[-1], tile_rows, tiled


def _kernel(tile_group_ref, x_ref, *refs, activation):
    """One row tile against one `block_f` of its expert: grid (tile, f),
    f innermost; the output tile stays in VMEM over f and sums the
    blocks' contributions in float32. `refs` are the weight blocks (in,
    out, or gate, in, out) and the output."""
    del tile_group_ref                     # read by the index maps
    *gate_ref, w_in_ref, w_out_ref, o_ref = refs
    x = x_ref[...]
    h = jnp.dot(x, w_in_ref[...].astype(x.dtype),
                preferred_element_type=jnp.float32)
    if gate_ref:
        h = activation(jnp.dot(x, gate_ref[0][...].astype(x.dtype),
                               preferred_element_type=jnp.float32)) * h
    else:
        h = activation(h)
    y = jnp.dot(h.astype(x.dtype), w_out_ref[...].astype(x.dtype),
                preferred_element_type=jnp.float32)
    f = pl.program_id(1)

    @pl.when(f == 0)
    def _first():
        o_ref[...] = y

    @pl.when(f > 0)
    def _add():
        o_ref[...] += y


def _tiled_mlp(xt, w_in, w_out, tile_group, tiles, activation, tm, block_f,
               interpret, w_gate=None):
    """The kernel over rows already in tiles: xt (V·tm, K) -> (V·tm, D)
    float32, tiles `tiles` and up left as they were."""
    k, f, d = w_in.shape[1], w_in.shape[2], w_out.shape[2]
    itemsize = jnp.dtype(w_in.dtype).itemsize
    reads = (w_in,) if w_gate is None else (w_gate, w_in)
    if block_f is None:
        block_f = _block_f(k * len(reads), f, d, itemsize)
    read_spec = pl.BlockSpec((None, k, block_f),
                             lambda v, j, grp: (grp[v], 0, j))
    return pl.pallas_call(
        functools.partial(_kernel, activation=activation),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            # no tile in use (no row belongs to a group): one harmless
            # visit of tile 0, whose rows nobody reads
            grid=(jnp.maximum(tiles, 1), f // block_f),
            in_specs=[
                pl.BlockSpec((tm, k), lambda v, j, grp: (v, 0)),
                *[read_spec] * len(reads),
                pl.BlockSpec((None, block_f, d),
                             lambda v, j, grp: (grp[v], j, 0)),
            ],
            out_specs=pl.BlockSpec((tm, d), lambda v, j, grp: (v, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((xt.shape[0], d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=2 * block_f * (k * len(reads) + d) * itemsize
            + (16 << 20)),
        interpret=interpret,
        name="grouped_mlp",
    )(tile_group, xt, *reads, w_out)


def grouped_mlp(x, w_in, w_out, groups, activation=jax.nn.relu, *,
                rows=None, row_tile=16, block_f=None, interpret=None,
                w_gate=None):
    """`activation(x[rows[i]] @ w_in[g]) @ w_out[g]` for every pair i of a
    row and its group `g = groups[i]`; with `w_gate` (n, K, F),
    `(activation(x[rows[i]] @ w_gate[g]) * (x[rows[i]] @ w_in[g])) @
    w_out[g]`.

    - x (R, K); rows (m,) int: the row pair i reads (default: x has m
      rows, one a pair)
    - groups (m,) int: the pairs' groups, in any order; a pair whose group
      lies outside [0, n) is computed by nobody
    - w_in (n, K, F), w_out (n, F, D); K, F, D multiples of 128
      (`supported`)
    - row_tile: rows a grid step multiplies, a multiple of 16; a group
      takes as many tiles as its size needs, each fetching its weights
      unless the tile before it left them in VMEM

    Returns (m, D) float32, pair by pair; the row of a pair of no group is
    undefined. Equal, up to the order of float32 sums, to the pairs sorted
    by group through `ragged_dot(activation(ragged_dot(., w_in, sizes))
    .astype(x.dtype), w_out, sizes)`, both with
    `preferred_element_type=float32`."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if rows is None:
        rows = jnp.arange(x.shape[0], dtype=jnp.int32)
    tm = int(row_tile)
    tile_group, tiles, tile_rows, tiled = _layout(
        groups, rows, w_in.shape[0], tm)
    xt = x.at[tile_rows].get(mode="promise_in_bounds")
    yt = _tiled_mlp(xt, w_in, w_out, tile_group, tiles, activation, tm,
                    block_f, interpret, w_gate)
    return yt.at[tiled].get(mode="promise_in_bounds")
