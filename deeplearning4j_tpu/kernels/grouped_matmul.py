"""Pallas TPU grouped matmul for an expert layer: `activation(x @ w_in[g])
@ w_out[g]` for the rows of each group `g`, the two products fused so that
the intermediate never leaves VMEM. With a third stack `w_gate` the expert
is GATED, `(activation(x @ w_gate[g]) * (x @ w_in[g])) @ w_out[g]` (SwiGLU
with `activation = silu`): one more weight block a grid step, the same
grid, rows and layout.

What it is for (`parallel/moe.py` `routed_experts`): a chip that holds 128
experts of (1024, 2688) + (2688, 1024) bfloat16 and gives each 5 to 22 rows
a call, or 16 experts of a long prefill's 2048 tokens, each of which picks
8 of 128 experts. The work is reading 11 MB of weights an expert, so the
kernel is built around that stream and not around the rows:

- the weights are read in place, `(n, K, F)` and `(n, F, D)` as the model
  holds them, one `(K, block_f)` and one `(block_f, D)` block a grid step
  through the ordinary double-buffered pipeline; with `block_f = F` (the
  default where it fits) an expert's blocks stay in VMEM while the grid
  walks over as many row tiles as its group needs, and an expert without a
  row is never fetched;
- the pairs of a row and a group are laid out for the kernel: every group
  starts on a row tile (`_layout`), so a tile belongs to one group. The row
  tile is small (16 rows is the bfloat16 sublane tile) because an expert's
  group is: a product of 16 rows and one of 128 cost the MXU about the
  same, but the activation and the result are computed for every row of a
  tile, used or not;
- ROWS IN BY DMA: `x` is handed over where it lies in HBM (`pl.ANY`), as
  32-bit words (bfloat16 two rows to a word row: Mosaic loads a single row
  of a 32-bit array in VMEM only, and DMAs no single row out of a tiled
  array at all), and ONE copy brings its R rows into VMEM at the first
  grid step (8 MB for a prefill run of 2048 tokens of 2048 bfloat16). A
  tile then picks the rows of its pairs one by one, a word row each, its
  half widened to float32 by a shift. A tile's rows past its group's last
  pair are neither picked nor used (they hold whatever the buffer held);
- RESULTS OUT, COMBINED: each pair's result row, times its weight, is added
  into its row of an `(R, D)` float32 sum that stays in VMEM over the whole
  grid, started at the first step (zeroed, or copied in from a starting
  value the caller hands over, such as a shared expert's output, which
  the result is then written over) and copied out at the last. Nothing
  the size of the pairs or of the tiled rows exists outside the kernel;
- what a call holds grows with its rows (VMEM: x and the sums) and its
  pairs (SMEM: the layout's tables), so a call takes at most what
  `rows_a_call` allows, and a caller with more rows loops over runs of
  them, each run reading the weights of the experts it uses once; runs
  whose sums have a starting value pass one buffer of all their rows on
  (`at`: a run reads its start from its rows of it and writes its sums
  over them), so no run's start is cut out and no results are joined;
- the grid is `(tiles in use, F / block_f)`, its first extent read from
  the groups' sizes at run time: tiles past the last group are never
  visited.

bfloat16 (or float32) operands, float32 accumulation, activation, combine
and result: the numerics of two `lax.ragged_dot`s with
`preferred_element_type=float32`, the activation between them and a
weighted float32 sum over each row's pairs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: VMEM the two weight blocks of a grid step may take, both pipeline
#: buffers counted: `block_f` is the widest whose blocks fit
_WEIGHT_VMEM = 48 << 20

#: VMEM a call may take for its weight blocks and its rows together (a v5e
#: core has 128 MiB; Mosaic's default scoped limit of 16 MiB is raised to
#: what the call holds, plus 16 MiB): the rows (x as words, their float32
#: sums, a tile's rows and result) take what the call's own weight blocks
#: leave, never less than 40 MiB. 88 MiB holds LFM2's 4096 tokens of 2048
#: beside its gated blocks of 1536 (36 MiB), so a prompt of 16384 goes in
#: 4 runs, each reading the experts once
_CALL_VMEM = 88 << 20

#: SMEM a call's scalar-prefetched tables may take, of a v5e's 1 MiB: a
#: word for each tile's group and count, each tiled row's pair, and each
#: pair's row and weight
_TABLE_SMEM = 768 << 10


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


def weight_bytes(k, f, d, itemsize, block_f=None):
    """VMEM a call's weight blocks take, both pipeline buffers: `k` counts
    every stack that reads the rows, `block_f` (default `_block_f`'s) the
    width of a block."""
    if block_f is None:
        block_f = _block_f(k, f, d, itemsize)
    return 2 * block_f * (k + d) * itemsize


def _layout(groups, n, tm):
    """Where each pair goes when every group starts on a row tile.

    `groups` (m,) int: pair i's group, outside [0, n) for none. Returns
    - tile_group (V,) int32: the group of each row tile, V = m // tm + n the
      most tiles any grouping can need (each group's last tile may be
      partly empty);
    - tile_count (V,) int32: how many of a tile's rows hold a pair, from its
      first row on (tm but in a group's last tile; 0 past the last tile in
      use);
    - tiles () int32: how many tiles are in use, the groups' in order;
    - tile_pair (V·tm,) int32: the pair at each tiled row (0 where none:
      read by nobody).

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
    tile_member = v[:, None] >= tile_end[None, :]
    tile_group = jnp.minimum(tile_member.sum(-1, dtype=jnp.int32), n - 1)
    # a tile's rows up to its group's last pair (a one-hot select, not a
    # gather of V entries)
    group_rows_end = jnp.where(
        tile_group[:, None] == jnp.arange(n, dtype=jnp.int32)[None, :],
        first_tile * tm + sizes, 0).sum(-1)
    tile_count = jnp.where(v < tile_end[-1],
                           jnp.clip(group_rows_end - v * tm, 0, tm), 0)
    # each tiled row's pair: written by the pair that sits there (a pair of
    # no group writes past the end, which drops it)
    tile_pair = jnp.zeros((v.shape[0] * tm,), jnp.int32).at[
        jnp.where((groups >= 0) & (groups < n), tiled,
                  v.shape[0] * tm)].set(jnp.arange(m, dtype=jnp.int32),
                                        mode="drop")
    return tile_group, tile_count, tile_end[-1], tile_pair


def _chunk(r):
    """Rows a step of the loop that zeroes the r rows of the sum (r a
    multiple of 8, the float32 sublane tile): 64 where r allows, else 8."""
    return 64 if r % 64 == 0 else 8


def _sum_rows(r):
    """The rows of the kernel's float32 sum for r rows of x: r up to a
    multiple of 8, so that it is zeroed and copied out in whole tiles"""
    return -(-r // 8) * 8


def _tile_bytes(k, d, tm):
    """VMEM a call holds for one tile: its rows, their shifts and its
    result"""
    return tm * (k + 128 + d) * 4


def _held_bytes(r, m, k, d, itemsize, n, tm):
    """(VMEM, SMEM) bytes a call of r rows of width k and m pairs holds
    beside its weight blocks"""
    v = m // tm + n
    vmem = (r + r % 2) * k * itemsize + _sum_rows(r) * d * 4 \
        + _tile_bytes(k, d, tm)
    return vmem, 4 * (v * (tm + 2) + 1 + 2 * m)


def vmem_limit(weights, vmem):
    """The scoped VMEM a call asks Mosaic for: its weight blocks, what its
    rows hold (`_held_bytes`) and 16 MiB for the kernel's own values"""
    return weights + vmem + (16 << 20)


def _rows_vmem(weights):
    """VMEM a call's rows may take beside weight blocks of `weights`
    bytes"""
    return _CALL_VMEM - weights


def rows_a_call(r, top_k, k, d, itemsize, n, tm, weights):
    """How many of r rows of x, top_k pairs each, one call whose weight
    blocks take `weights` bytes (`weight_bytes`) may take: all r where they
    fit what the blocks leave of `_CALL_VMEM` and their tables
    `_TABLE_SMEM`, else the most that fit, a multiple of 16."""
    vmem, smem = _held_bytes(r, r * top_k, k, d, itemsize, n, tm)
    if vmem <= _rows_vmem(weights) and smem <= _TABLE_SMEM:
        return r
    by_vmem = (_rows_vmem(weights) - _tile_bytes(k, d, tm)) \
        // (k * itemsize + d * 4)
    # SMEM words, m pairs: at most (m / tm + n)(tm + 2) + 1 + 2m
    words = _TABLE_SMEM // 4 - 1 - n * (tm + 2)
    by_smem = words * tm // (3 * tm + 2) // top_k
    return max(min(by_vmem, by_smem) // 16 * 16, 16)


def _words(x):
    """x (R, K) as rows of 32-bit words: float32 as it is; bfloat16 two
    rows to a word row, row 2j in the low half and 2j + 1 in the high (R
    padded to even)."""
    if x.dtype == jnp.float32:
        return x
    if x.dtype != jnp.bfloat16:
        raise ValueError(f"grouped_mlp takes float32 or bfloat16 rows, "
                         f"not {x.dtype}")
    if x.shape[0] % 2:
        x = jnp.pad(x, ((0, 1), (0, 0)))
    bits = lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    r = bits.shape[0]
    return lax.slice_in_dim(bits, 0, r, 2) \
        | (lax.slice_in_dim(bits, 1, r, 2) << 16)


def _kernel(tile_group_ref, tile_count_ref, tiles_ref, tile_pair_ref,
            rows_ref, weights_ref, x_hbm, *refs, activation, tm, nf, dtype,
            seeded, at):
    """One row tile against one `block_f` of its expert: grid (tile, f),
    f innermost. The first step brings x in and starts the sum (zeroed,
    or `seeded`: copied in from the starting value); at f == 0 a tile
    picks its rows; its result sums the blocks' contributions in float32,
    and at the last f each row is added, weighted, into its row of the sum,
    which the last grid step copies out. `refs` are the weight blocks (in,
    out, or gate, in, out), the starting value in HBM where `seeded`, the
    output in HBM and the scratch: x as words (`_words`), the tile's rows as
    words, each row's shift into the high half, its result, the sum.
    `dtype` is x's, into the products. Where `at` is a row, the start and
    the output are a buffer of several calls' rows and this call's are
    those from `at` on."""
    del tile_group_ref                     # read by the index maps
    blocks = list(refs[:-7])
    o_hbm, x_ref, xt_ref, shift_ref, acc_ref, sum_ref, copied = refs[-7:]
    init_hbm = blocks.pop() if seeded else None

    def mine(ref):
        return ref if at is None else ref.at[pl.ds(at, sum_ref.shape[0])]
    *gate_ref, w_in_ref, w_out_ref = blocks
    packed = x_ref.dtype == jnp.uint32
    v, f = pl.program_id(0), pl.program_id(1)

    def each_pair(body):
        """body(i, pair, its row) for each of tile v's rows that holds a
        pair"""
        def step(i, carry):
            pair = tile_pair_ref[v * tm + i]
            body(i, pair, rows_ref[pair])
            return carry
        lax.fori_loop(0, tile_count_ref[v], step, 0)

    @pl.when((v == 0) & (f == 0))
    def _first():
        copy = pltpu.make_async_copy(x_hbm, x_ref, copied)
        copy.start()
        if seeded:
            # on x's semaphore: nothing reads either buffer before both
            # waits have returned
            start = pltpu.make_async_copy(mine(init_hbm), sum_ref, copied)
            start.start()
            start.wait()
        else:
            c = _chunk(sum_ref.shape[0])

            def zero(s, carry):
                sum_ref[pl.ds(pl.multiple_of(s * c, c), c), :] = jnp.zeros(
                    (c, sum_ref.shape[1]), jnp.float32)
                return carry
            lax.fori_loop(0, sum_ref.shape[0] // c, zero, 0)
        copy.wait()

    @pl.when(f == 0)
    def _rows_in():
        def pick(i, pair, row):
            if not packed:
                xt_ref[pl.ds(i, 1), :] = x_ref[pl.ds(row, 1), :]
                return
            xt_ref[pl.ds(i, 1), :] = x_ref[pl.ds(row // 2, 1), :]
            shift_ref[pl.ds(i, 1), :] = jnp.full(
                (1, 128), 16 - 16 * (row % 2), jnp.uint32)
        each_pair(pick)
        if packed:
            # each row's half into the high half, a whole tile at once: a
            # bfloat16's bits are the high half of its float32's
            for j in range(0, xt_ref.shape[1], 128):
                lanes = slice(j, j + 128)
                xt_ref[:, lanes] = (xt_ref[:, lanes] << shift_ref[...]) \
                    & jnp.uint32(0xFFFF0000)

    x = xt_ref[...]
    x = (pltpu.bitcast(x, jnp.float32) if packed else x).astype(dtype)
    h = jnp.dot(x, w_in_ref[...].astype(dtype),
                preferred_element_type=jnp.float32)
    if gate_ref:
        h = activation(jnp.dot(x, gate_ref[0][...].astype(dtype),
                               preferred_element_type=jnp.float32)) * h
    else:
        h = activation(h)
    y = jnp.dot(h.astype(dtype), w_out_ref[...].astype(dtype),
                preferred_element_type=jnp.float32)

    @pl.when(f == 0)
    def _first_block():
        acc_ref[...] = y

    @pl.when(f > 0)
    def _add_block():
        acc_ref[...] += y

    @pl.when(f == nf - 1)
    def _results_out():
        def add(i, pair, row):
            sum_ref[pl.ds(row, 1), :] += \
                weights_ref[pair] * acc_ref[pl.ds(i, 1), :]
        each_pair(add)

        @pl.when(v == jnp.maximum(tiles_ref[0], 1) - 1)
        def _store():
            copy = pltpu.make_async_copy(sum_ref, mine(o_hbm), copied)
            copy.start()
            copy.wait()


def _fused_mlp(x, w_in, w_out, tile_group, tile_count, tiles, tile_pair,
               rows, weights, activation, tm, block_f, interpret,
               w_gate=None, init=None, at=None):
    """The kernel over the layout: x (R, K) in HBM -> (R, D) float32, the
    weighted sum of each row's pairs' results, started from `init` (R, D)
    float32 where it is given (the output is written over it); with `at`,
    `init` is a float32 buffer of several calls' rows, x's from `at` on,
    and the whole buffer is returned."""
    (r, k), f, d = x.shape, w_in.shape[2], w_out.shape[2]
    itemsize = jnp.dtype(w_in.dtype).itemsize
    reads = (w_in,) if w_gate is None else (w_gate, w_in)
    if block_f is None:
        block_f = _block_f(k * len(reads), f, d, itemsize)
    blocks = weight_bytes(k * len(reads), f, d, itemsize, block_f)
    vmem, smem = _held_bytes(r, rows.shape[0], k, d, x.dtype.itemsize,
                             w_in.shape[0], tm)
    if vmem > _rows_vmem(blocks) or smem > _TABLE_SMEM:
        raise ValueError(
            f"{r} rows of width {k} and {rows.shape[0]} pairs hold {vmem} "
            f"bytes of VMEM and {smem} of SMEM, over {_rows_vmem(blocks)} "
            f"and {_TABLE_SMEM}: take at most `rows_a_call` rows a call")
    words = _words(x)
    read_spec = pl.BlockSpec((None, k, block_f),
                             lambda v, j, grp, *_: (grp[v], 0, j))
    scratch = [pltpu.VMEM(words.shape, words.dtype),  # x as words
               pltpu.VMEM((tm, k), words.dtype),      # the tile's rows
               pltpu.VMEM((tm, 128), jnp.uint32),     # their shifts
               pltpu.VMEM((tm, d), jnp.float32),      # the tile's result
               pltpu.VMEM((_sum_rows(r), d), jnp.float32),  # the sums
               pltpu.SemaphoreType.DMA(())]
    seeded = init is not None
    starts = ()
    if at is not None:
        if init.dtype != jnp.float32 or r % 8 \
                or not 0 <= at <= init.shape[0] - r:
            raise ValueError(
                f"rows {at}..{at + r} of a {init.dtype} buffer of "
                f"{init.shape[0]}: `at` takes a float32 buffer that holds "
                f"x's rows, a multiple of 8")
        starts = (init,)
    elif seeded:
        starts = (jnp.pad(init.astype(jnp.float32),
                          ((0, _sum_rows(r) - r), (0, 0))),)
    out = pl.pallas_call(
        functools.partial(_kernel, activation=activation, tm=tm,
                          nf=f // block_f, dtype=x.dtype, seeded=seeded,
                          at=at),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            # no tile in use (no pair belongs to a group): one visit of
            # tile 0, which picks no row and adds nothing
            grid=(jnp.maximum(tiles, 1), f // block_f),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                *[read_spec] * len(reads),
                pl.BlockSpec((None, block_f, d),
                             lambda v, j, grp, *_: (grp[v], j, 0)),
                *[pl.BlockSpec(memory_space=pl.ANY)] * len(starts),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct(
            starts[0].shape if seeded else (_sum_rows(r), d), jnp.float32),
        # the sum is written over its starting value: operands are the six
        # tables, x, the weight stacks, then the start
        input_output_aliases={7 + len(reads) + 1: 0} if seeded else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit(blocks, vmem)),
        interpret=interpret,
        name="grouped_mlp",
    )(tile_group, tile_count, jnp.reshape(tiles, (1,)), tile_pair, rows,
      weights, words, *reads, w_out, *starts)
    return out if at is not None else out[:r]


def grouped_mlp(x, w_in, w_out, groups, activation=jax.nn.relu, *,
                weights, rows=None, row_tile=16, block_f=None,
                interpret=None, w_gate=None, init=None, at=None):
    """`sum_i weights[i] * activation(x[r] @ w_in[g]) @ w_out[g]` for every
    row r of x, over the pairs i of a row `rows[i] = r` and a group `g =
    groups[i]`; with `w_gate` (n, K, F) each pair computes
    `(activation(x[r] @ w_gate[g]) * (x[r] @ w_in[g])) @ w_out[g]`.

    - x (R, K) float32 or bfloat16; rows (m,) int: the row pair i reads
      and adds into (default: x has m rows, one a pair); weights (m,)
      float32: each pair's weight in its row's sum; init (R, D) or None:
      where each row's sum starts (0 without)
    - at: a row of `init`, which is then a float32 buffer of several
      calls' rows (T, D): x's R rows are those from `at` on (R a multiple
      of 8), their sums start from them and are written over them, and the
      whole buffer, the other rows as they were, is returned; a caller with
      more rows than a call holds runs its calls through one buffer so
    - groups (m,) int: the pairs' groups, in any order; a pair whose group
      lies outside [0, n) is computed by nobody and adds nothing
    - w_in (n, K, F), w_out (n, F, D); K, F, D multiples of 128
      (`supported`)
    - row_tile: rows a grid step multiplies, a multiple of 16; a group
      takes as many tiles as its size needs, each fetching its weights
      unless the tile before it left them in VMEM
    - R and m at most what `rows_a_call` allows (else ValueError)

    Returns (R, D) float32; a row no pair of a group reads is its start
    (0 without `init`). Equal, up to the order of float32 sums, to the
    pairs sorted by group through `ragged_dot(activation(ragged_dot(.,
    w_in, sizes)).astype(x.dtype), w_out, sizes)`, both with
    `preferred_element_type=float32`, each result times its weight and
    summed into its row, after its start, in float32."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m = groups.shape[0]
    if rows is None:
        rows = jnp.arange(m, dtype=jnp.int32)
    tm = int(row_tile)
    tile_group, tile_count, tiles, tile_pair = _layout(
        groups, w_in.shape[0], tm)
    return _fused_mlp(x, w_in, w_out, tile_group, tile_count, tiles,
                      tile_pair, rows.astype(jnp.int32),
                      weights.astype(jnp.float32), activation, tm, block_f,
                      interpret, w_gate, init, at)
