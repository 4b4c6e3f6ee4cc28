"""Pallas TPU flash attention — forward AND backward kernels.

The reference accelerates attention-era models by dispatching to
hand-fused cuDNN helpers (deeplearning4j-cuda :: CudnnLSTMHelper etc.);
the TPU-native equivalent of "the hand-tuned fused kernel" is a Pallas
kernel that tiles Q/K/V through VMEM and never materialises the (T, T)
score matrix: online-softmax accumulation per Q tile, MXU matmuls in
bfloat16/f32, O(T) HBM traffic.

Backward (round 2; round 1 used a blockwise jax.vjp recompute) is the
standard flash-attention-2 split: the forward additionally emits the
per-row logsumexp L; backward precomputes D = rowsum(dO ∘ O), then
- a dQ kernel tiled (q_tiles × k_tiles, k innermost) recomputes
  P = exp(S − L) per tile and accumulates dQ = scale · Σ_k dS·K,
- a dK/dV kernel tiled (k_tiles × q_tiles, q innermost) accumulates
  dV = Σ_q Pᵀ·dO and dK = scale · Σ_q dSᵀ·Q,
with dS = P ∘ (dO·Vᵀ − D). No (T, T) tensor ever hits HBM in either
direction. On non-TPU backends the kernels run in interpret mode so
tests exercise the identical code path.

Layout: (B, H, T, D) like parallel/ring_attention.py; the two compose —
ring attention rotates K/V shards across chips, and each local block can
use this kernel for its on-chip work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _flash_fwd_kernel(*refs, block_k, causal, scale, tk_actual, has_mask,
                      native=False, window=None, k_tiles=None, steps=None,
                      heads=None):
    """Grid (BH, q_tiles, k_tiles), k innermost: only one (block_k, d) K/V
    tile is VMEM-resident per step; o/l/m accumulate in VMEM scratch across
    the k dimension and the output tile is written on the last k step.
    The q and k tilings are independent, so Tq ≠ Tk (cross-attention)
    falls out of the same kernel; so is V's width, which only the
    accumulator and the output see.

    `native`: both products take their operands in the operands' own dtype
    (bfloat16 on the MXU in one pass) with float32 sums, the scale applied
    to the float32 scores; without it every operand is float32 first.

    With has_mask, an extra (1, block_k) int32 KEY-validity tile (from the
    per-example (B, Tk) padding mask) masks scores; invalid QUERY rows are
    handled outside the kernel (outputs zeroed, lse forced to +inf so the
    backward recompute sees p == 0).

    `window` (causal only): query t sees keys `t - window < s <= t`, and
    the k axis of the grid runs over the BAND: step j of q tile i is k tile
    `_band_span(i)[0] + j` (`_band_span`), and a step past the band's last
    tile computes nothing and names that tile again, so nothing is
    fetched for it. Tiles wholly below the band have no grid step.

    `steps` (causal, with the prompts' lengths): the grid is (BH, step)
    over the (q tile, k tile) pairs IN USE (`_pairs_in_use`), whose four
    vectors of `steps` entries an example come first as scalar-prefetch
    operands: a step's output q tile, its input q tile, its k tile and
    its kind (`_RUN`, `_FIRST`, `_LAST`, `_ZERO`). A pair runs the same
    arithmetic as the step of the static grid that names it; a q tile
    wholly past its length takes one `_ZERO` step that writes zeros and
    names the blocks already resident, so nothing is fetched for it."""
    if steps is not None:
        qo_ref, _, kt_ref, kind_ref, *refs = refs
        at = pl.program_id(0) // heads * steps + pl.program_id(1)
        kind = kind_ref[at]
    if has_mask:
        q_ref, k_ref, v_ref, km_ref, o_ref, lse_ref, acc_ref, l_ref, m_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, l_ref, m_ref = refs
        km_ref = None
    if steps is None:
        qi = pl.program_id(1)
        kj = pl.program_id(2)
    else:
        qi, kj = qo_ref[at], kt_ref[at]
    block_q = q_ref.shape[1]
    if window is not None and steps is None:
        first, last = _band_span(qi, block_q, block_k, window, k_tiles)
        band_step, kj = kj, first + kj

    if steps is not None:
        first_step = (kind & _FIRST) != 0
    elif window is None:
        first_step = kj == 0
    else:
        first_step = band_step == 0

    @pl.when(first_step)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        l_ref[...] = jnp.zeros_like(l_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)

    def _compute():
        if native:
            k, v = k_ref[0], v_ref[0]
            s = jax.lax.dot_general(
                q_ref[0], k, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
        else:
            q = q_ref[0].astype(jnp.float32) * scale  # (block_q, d)
            k = k_ref[0]                              # (block_k, d)
            v = v_ref[0]
            s = jax.lax.dot_general(
                q, k.astype(jnp.float32),
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)   # (block_q, block_k)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos < tk_actual
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        if has_mask:
            mask &= km_ref[0] > 0            # (1, block_k) broadcasts
        s = jnp.where(mask, s, _NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            *((p.astype(v.dtype), v) if native
              else (p, v.astype(jnp.float32))),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if steps is not None:
        # only pairs in use have a step; a _ZERO step or one past its
        # example's table computes nothing
        pl.when((kind & _RUN) != 0)(_compute)
    elif window is not None:
        # the band's steps past its last tile repeat that tile's block
        pl.when(kj <= last)(_compute)
    elif causal:
        # skip k-tiles entirely above the diagonal: both MXU matmuls would
        # only produce fully-masked (p == 0) contributions
        pl.when(kj * block_k <= qi * block_q + block_q - 1)(_compute)
    else:
        _compute()

    if steps is not None:
        last_step = (kind & _LAST) != 0
    else:
        last_step = ((kj if window is None else band_step)
                     == pl.num_programs(2) - 1)

    @pl.when(last_step)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[...] +
                         jnp.log(jnp.maximum(l_ref[...], 1e-30)))[:, 0]

    if steps is not None:
        @pl.when((kind & _ZERO) != 0)
        def _zero():
            # a q tile wholly past its length: its rows read as zeros
            o_ref[...] = jnp.zeros_like(o_ref)
            lse_ref[...] = jnp.zeros_like(lse_ref)


#: kinds of a step of the grid over the pairs in use (`_pairs_in_use`), as
#: bits: the pair computes, it is its q tile's first, its last; a q tile
#: wholly past its length writes zeros. A step with none does nothing.
_RUN, _FIRST, _LAST, _ZERO = 1, 2, 4, 8


def _row_spans(q_tiles, block_q, block_k, k_tiles, window):
    """(first, last) k tile of each q tile of a causal attention: the
    band's (`_band_span`) or, without a window, 0 up to the diagonal."""
    if window is None:
        return [(0, min((i * block_q + block_q - 1) // block_k, k_tiles - 1))
                for i in range(q_tiles)]
    return [_band_span(i, block_q, block_k, window, k_tiles)
            for i in range(q_tiles)]


def _pairs_in_use(lengths, block_q, spans):
    """The grid over the (q tile, k tile) pairs in use of a causal attention
    whose q tiles need the k tiles `spans` (`_row_spans`), from the
    examples' `lengths` (B,): an example's q tiles that hold a row below its
    length (at least one) in order, each with its k tiles in order, then
    one `_ZERO` step for each q tile past them. -> four int32 vectors of
    `steps` entries an example, examples one after the other: a step's
    output q tile, the q tile it reads, its k tile and its kind; and the
    grid's extent, the most steps an example takes. An example's steps
    past its own count name its last step's blocks and do nothing."""
    q_tiles = len(spans)
    pair_q = [i for i, (f, l) in enumerate(spans) for _ in range(f, l + 1)]
    pair_k = [j for f, l in spans for j in range(f, l + 1)]
    pair_kind = [_RUN | (_FIRST if j == f else 0) | (_LAST if j == l else 0)
                 for f, l in spans for j in range(f, l + 1)]
    starts = [0]
    for f, l in spans:
        starts.append(starts[-1] + l - f + 1)
    steps = starts[-1]
    used = jnp.clip(-(-lengths // block_q), 1, q_tiles).astype(jnp.int32)
    paired = jnp.asarray(starts, jnp.int32)[used][:, None]     # (B, 1)
    last_k = jnp.asarray([l for _, l in spans], jnp.int32)[used - 1]
    step = jnp.arange(steps, dtype=jnp.int32)[None, :]
    in_use = step < paired
    past = used[:, None] + step - paired        # the q tile a _ZERO names
    pick = functools.partial(jnp.where, in_use)
    q_out = pick(jnp.asarray([pair_q], jnp.int32),
                 jnp.minimum(past, q_tiles - 1))
    q_in = pick(q_out, used[:, None] - 1)
    k_tile = pick(jnp.asarray([pair_k], jnp.int32), last_k[:, None])
    kind = pick(jnp.asarray([pair_kind], jnp.int32),
                jnp.where(past < q_tiles, _ZERO, 0))
    extent = jnp.max(paired[:, 0] + q_tiles - used)
    return (*(a.reshape(-1) for a in (q_out, q_in, k_tile, kind)), extent)


def prefill_grid_steps(length, t, block_q, block_k, window=None):
    """Grid steps one head of `flash_attention(..., causal=True, lengths=)`
    takes over a sequence of `t` rows whose first `length` are real, and
    the steps the static grid of the same call without `lengths` holds:
    (run, static), Python ints from the rule `_pairs_in_use` builds the
    grid by. `run` counts the pairs in use and one step for each q tile
    past them; `static` the (q tile, k tile) steps of the whole bucket,
    those above the diagonal or past the band's last tile included."""
    block_q, block_k = _block_sizes(t, t, block_q, block_k)
    q_tiles, k_tiles = -(-t // block_q), -(-t // block_k)
    spans = _row_spans(q_tiles, block_q, block_k, k_tiles, window)
    used = min(max(-(-int(length) // block_q), 1), q_tiles)
    run = sum(l - f + 1 for f, l in spans[:used]) + q_tiles - used
    width = k_tiles if window is None else max(l - f + 1 for f, l in spans)
    return run, q_tiles * width


def _band_span(qi, block_q, block_k, window, k_tiles):
    """The k tiles (first, last) that q tile `qi` of a sliding-window causal
    attention needs: its rows `t` see keys `t - window < s <= t`. Works on
    Python ints and on traced int32 alike."""
    lo = qi * block_q - window + 1
    if isinstance(lo, int):
        first = max(lo, 0) // block_k
        last = min((qi * block_q + block_q - 1) // block_k, k_tiles - 1)
    else:
        first = jnp.maximum(lo, 0) // block_k
        last = jnp.minimum((qi * block_q + block_q - 1) // block_k,
                           k_tiles - 1)
    return first, last


def band_tiles(q_tiles, block_q, block_k, window, k_tiles):
    """The extent of a banded grid's k axis: the most k tiles any q tile's
    band touches."""
    return max(last - first + 1 for first, last in (
        _band_span(i, block_q, block_k, window, k_tiles)
        for i in range(q_tiles)))


def _pad_to(x, axis, mult):
    t = x.shape[axis]
    pad = (-t) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _block_sizes(tq, tk, block_q, block_k):
    return min(block_q, max(tq, 8)), min(block_k, max(tk, 8))


def _prep_mask(mask, block_k):
    """(B, T) truthy mask → int32 (B, 1, T_padded) for (1, 1, block_k)
    tiles (zero padding = invalid keys, matching the padded K/V rows)."""
    return _pad_to(mask.astype(jnp.int32), 1, block_k)[:, None, :]


def _flash_forward(q, k, v, q_mask, kv_mask, causal, block_q, block_k,
                   interpret, native=False, window=None, lengths=None):
    """Returns (out (B,H,Tq,Dv), lse (B*H, Tq_padded)). `kv_mask` is an
    optional (B, Tk) KEY-validity mask; `q_mask` an optional (B, Tq)
    QUERY-validity mask — invalid q rows come back zeroed with
    lse = +1e30 so the backward kernels recompute p == 0 for them.
    Self-attention passes the same (B, T) mask for both. V's width Dv may
    differ from Q's and K's D (the scale is D's). With `lengths` (B,) int32
    (causal, no masks) the grid is (BH, step) over the pairs in use
    (`_pairs_in_use`), its extent read from the lengths when the call runs;
    the q tiles wholly past an example's length come back as zeros."""
    if lengths is not None:
        return _flash_forward_in_use(q, k, v, block_q, block_k, interpret,
                                     native, window, lengths)
    b, h, tq_a, d = q.shape
    tk_a = k.shape[2]
    dv = v.shape[3]
    scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q, block_k = _block_sizes(tq_a, tk_a, block_q, block_k)
    qp = _pad_to(q.reshape(b * h, tq_a, d), 1, block_q)
    kp = _pad_to(k.reshape(b * h, tk_a, d), 1, block_k)
    vp = _pad_to(v.reshape(b * h, tk_a, dv), 1, block_k)
    tq = qp.shape[1]
    grid = (b * h, tq // block_q, kp.shape[1] // block_k)
    kernel = functools.partial(_flash_fwd_kernel, block_k=block_k,
                               causal=causal, scale=scale, tk_actual=tk_a,
                               has_mask=kv_mask is not None, native=native)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0)),
        pl.BlockSpec((1, block_k, dv), lambda bh, i, j: (bh, j, 0)),
    ]
    if window is not None:
        # the k axis over the band's tiles only (`_flash_fwd_kernel`)
        k_tiles = grid[2]
        grid = grid[:2] + (band_tiles(grid[1], block_q, block_k, window,
                                      k_tiles),)
        kernel = functools.partial(kernel, window=window, k_tiles=k_tiles)

        def band(i, j):
            first, last = _band_span(i, block_q, block_k, window, k_tiles)
            return jnp.minimum(first + j, last)

        in_specs[1:] = [
            pl.BlockSpec((1, block_k, w), lambda bh, i, j: (bh, band(i, j),
                                                            0))
            for w in (d, dv)]
    operands = [qp, kp, vp]
    if kv_mask is not None:
        in_specs.append(
            pl.BlockSpec((1, 1, block_k), lambda bh, i, j: (bh // h, 0, j)))
        operands.append(_prep_mask(kv_mask, block_k))
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda bh, i, j: (bh, i, 0)),
            # row vectors ride as (N, 1, T) with (1, 1, block) tiles:
            # a 2-D (1, block) tile violates the Mosaic (8, 128) minimum
            # unless the block covers the full array dim
            pl.BlockSpec((1, 1, block_q), lambda bh, i, j: (bh, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tq, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, tq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(*operands)
    lse = lse[:, 0]
    out = out[:, :tq_a, :].reshape(b, h, tq_a, dv)
    if q_mask is not None or kv_mask is not None:
        qvalid = (jnp.ones((b, tq_a), bool) if q_mask is None
                  else q_mask.astype(bool))             # (B, Tq)
        if kv_mask is not None:
            # an example with NO valid keys has no defined softmax: its
            # query rows come back zeroed, and the lse = +1e30 sentinel
            # makes the backward recompute p == 0 (no dk/dv leak into
            # fully-padded K/V)
            qvalid &= kv_mask.astype(bool).any(axis=1)[:, None]
        out = jnp.where(qvalid[:, None, :, None], out, 0)
        lse_valid = _pad_to(qvalid, 1, block_q)[:, None, :]  # (B, 1, tq)
        lse = jnp.where(
            jnp.broadcast_to(lse_valid, (b, h, tq)).reshape(b * h, tq),
            lse, 1e30)
    return out, lse


def _flash_forward_in_use(q, k, v, block_q, block_k, interpret, native,
                          window, lengths):
    """`_flash_forward`'s causal call over the pairs in use of each
    example's `lengths`: the kernel's blocks and arithmetic, a grid of
    (BH, step) whose step's q tile, k tile and kind are scalar-prefetched
    (`_pairs_in_use`, `_flash_fwd_kernel`)."""
    b, h, t, d = q.shape
    dv = v.shape[3]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q, block_k = _block_sizes(t, t, block_q, block_k)
    qp = _pad_to(q.reshape(b * h, t, d), 1, block_q)
    kp = _pad_to(k.reshape(b * h, t, d), 1, block_k)
    vp = _pad_to(v.reshape(b * h, t, dv), 1, block_k)
    tq, k_tiles = qp.shape[1], kp.shape[1] // block_k
    spans = _row_spans(tq // block_q, block_q, block_k, k_tiles, window)
    *table, extent = _pairs_in_use(lengths.astype(jnp.int32), block_q, spans)
    steps = table[0].shape[0] // b

    def tile(which):
        def index(bh, s, *refs):
            return refs[which][bh // h * steps + s]
        return index

    q_out, q_in, k_tile = tile(0), tile(1), tile(2)
    out, lse = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, block_k=block_k, causal=True,
                          scale=1.0 / (d ** 0.5), tk_actual=t,
                          has_mask=False, native=native, window=window,
                          steps=steps, heads=h),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(table),
            grid=(b * h, extent),
            in_specs=[
                pl.BlockSpec((1, block_q, d),
                             lambda bh, s, *r: (bh, q_in(bh, s, *r), 0)),
                pl.BlockSpec((1, block_k, d),
                             lambda bh, s, *r: (bh, k_tile(bh, s, *r), 0)),
                pl.BlockSpec((1, block_k, dv),
                             lambda bh, s, *r: (bh, k_tile(bh, s, *r), 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, dv),
                             lambda bh, s, *r: (bh, q_out(bh, s, *r), 0)),
                pl.BlockSpec((1, 1, block_q),
                             lambda bh, s, *r: (bh, 0, q_out(bh, s, *r))),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, dv), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tq, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, tq), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(*table, qp, kp, vp)
    return out[:, :t, :].reshape(b, h, t, dv), lse[:, 0]


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------
def _recompute_p(q_ref, k_ref, lse_ref, km_ref, qi, kj, block_q, block_k,
                 causal, scale, tk_actual):
    """exp(S − L) for this (q, k) tile — the fwd tile re-derived in VMEM.
    Invalid q rows carry lse == +1e30 (set by the forward wrapper), so
    exp(finite − 1e30) underflows to exactly 0 without a q-side mask."""
    qs = q_ref[0].astype(jnp.float32) * scale
    s = jax.lax.dot_general(
        qs, k_ref[0].astype(jnp.float32),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)  # (block_q, block_k)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = k_pos < tk_actual
    if causal:
        mask &= q_pos >= k_pos
    if km_ref is not None:
        mask &= km_ref[0] > 0
    s = jnp.where(mask, s, _NEG_INF)
    return jnp.exp(s - lse_ref[0, 0][:, None])


def _flash_bwd_dq_kernel(*refs, block_k, causal, scale, tk_actual, has_mask):
    """Grid (BH, q_tiles, k_tiles), k innermost; dq accumulates in VMEM."""
    if has_mask:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, km_ref,
         dq_ref, dq_acc) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc = refs
        km_ref = None
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    block_q = q_ref.shape[1]

    @pl.when(kj == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _compute():
        p = _recompute_p(q_ref, k_ref, lse_ref, km_ref, qi, kj, block_q,
                         block_k, causal, scale, tk_actual)
        do = do_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(
            do, v_ref[0].astype(jnp.float32),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)     # dO·Vᵀ (bq, bk)
        ds = p * (dp - delta_ref[0, 0][:, None])
        dq_acc[...] += scale * jax.lax.dot_general(
            ds, k_ref[0].astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(kj * block_k <= qi * block_q + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(*refs, block_k, causal, scale, tk_actual,
                          has_mask):
    """Grid (BH, k_tiles, q_tiles), q innermost; dk/dv accumulate in VMEM."""
    if has_mask:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, km_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        km_ref = None
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    block_q = q_ref.shape[1]

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _compute():
        p = _recompute_p(q_ref, k_ref, lse_ref, km_ref, qi, kj, block_q,
                         block_k, causal, scale, tk_actual)
        do = do_ref[0].astype(jnp.float32)
        dv_acc[...] += jax.lax.dot_general(
            p, do, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)     # Pᵀ·dO (bk, d)
        dp = jax.lax.dot_general(
            do, v_ref[0].astype(jnp.float32),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, None])
        dk_acc[...] += scale * jax.lax.dot_general(
            ds, q_ref[0].astype(jnp.float32),
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)     # dSᵀ·Q (bk, d)

    if causal:
        # q-tiles strictly above the diagonal contribute nothing
        pl.when(qi * block_q + block_q - 1 >= kj * block_k)(_compute)
    else:
        _compute()

    @pl.when(qi == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, q_mask, kv_mask, o, lse, g, causal, block_q,
                    block_k, interpret):
    b, h, tq_a, d = q.shape
    tk_a = k.shape[2]
    scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q, block_k = _block_sizes(tq_a, tk_a, block_q, block_k)
    has_mask = kv_mask is not None

    # D = rowsum(dO ∘ O) — one fused elementwise pass, O(T·D) traffic
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    qp = _pad_to(q.reshape(b * h, tq_a, d), 1, block_q)
    dop = _pad_to(g.reshape(b * h, tq_a, d), 1, block_q)
    deltap = _pad_to(delta.reshape(b * h, tq_a), 1, block_q)[:, None, :]
    kp = _pad_to(k.reshape(b * h, tk_a, d), 1, block_k)
    vp = _pad_to(v.reshape(b * h, tk_a, d), 1, block_k)
    tq, tk = qp.shape[1], kp.shape[1]
    # lse comes back from forward already padded to the q tiling
    lsep = (lse if lse.shape[1] == tq
            else _pad_to(lse, 1, block_q))[:, None, :]

    q_spec = pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0))
    k_spec = pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0))
    row_spec = pl.BlockSpec((1, 1, block_q), lambda bh, i, j: (bh, 0, i))

    kmp = _prep_mask(kv_mask, block_k) if has_mask else None
    operands = [qp, kp, vp, dop, lsep, deltap]
    in_specs = [q_spec, k_spec, k_spec, q_spec, row_spec, row_spec]
    if has_mask:
        operands.append(kmp)
        in_specs.append(
            pl.BlockSpec((1, 1, block_k), lambda bh, i, j: (bh // h, 0, j)))

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, block_k=block_k,
                          causal=causal, scale=scale, tk_actual=tk_a,
                          has_mask=has_mask),
        grid=(b * h, tq // block_q, tk // block_k),
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, tq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dq",
    )(*operands)

    # dk/dv: swap the roles — k tiles outer, q tiles innermost
    q_spec2 = pl.BlockSpec((1, block_q, d), lambda bh, j, i: (bh, i, 0))
    k_spec2 = pl.BlockSpec((1, block_k, d), lambda bh, j, i: (bh, j, 0))
    row_spec2 = pl.BlockSpec((1, 1, block_q), lambda bh, j, i: (bh, 0, i))
    operands2 = [qp, kp, vp, dop, lsep, deltap]
    in_specs2 = [q_spec2, k_spec2, k_spec2, q_spec2, row_spec2, row_spec2]
    if has_mask:
        operands2.append(kmp)
        in_specs2.append(
            pl.BlockSpec((1, 1, block_k), lambda bh, j, i: (bh // h, 0, j)))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, block_k=block_k,
                          causal=causal, scale=scale, tk_actual=tk_a,
                          has_mask=has_mask),
        grid=(b * h, tk // block_k, tq // block_q),
        in_specs=in_specs2,
        out_specs=[k_spec2, k_spec2],
        out_shape=[jax.ShapeDtypeStruct((b * h, tk, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, tk, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(*operands2)

    dq = dq[:, :tq_a, :].reshape(b, h, tq_a, d)
    dk = dk[:, :tk_a, :].reshape(b, h, tk_a, d)
    dv = dv[:, :tk_a, :].reshape(b, h, tk_a, d)
    return dq, dk, dv


def _zero_mask_cotangent(mask):
    if mask is None:
        return None
    if jnp.issubdtype(mask.dtype, jnp.inexact):
        # float masks (e.g. 0/1 float32 from DataSet masks) need a real
        # zero cotangent — float0 is only valid for int/bool primals
        return jnp.zeros(mask.shape, mask.dtype)
    import numpy as np
    return np.zeros(mask.shape, dtype=jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_attention_vjp(q, k, v, q_mask, kv_mask, causal, block_q, block_k,
                         interpret):
    out, _ = _flash_forward(q, k, v, q_mask, kv_mask, causal, block_q,
                            block_k, interpret)
    return out


def _flash_fwd_rule(q, k, v, q_mask, kv_mask, causal, block_q, block_k,
                    interpret):
    out, lse = _flash_forward(q, k, v, q_mask, kv_mask, causal, block_q,
                              block_k, interpret)
    return out, (q, k, v, q_mask, kv_mask, out, lse)


def _flash_bwd_rule(causal, block_q, block_k, interpret, res, g):
    q, k, v, q_mask, kv_mask, o, lse = res
    dq, dk, dv = _flash_backward(q, k, v, q_mask, kv_mask, o, lse, g,
                                 causal, block_q, block_k, interpret)
    return (dq, dk, dv, _zero_mask_cotangent(q_mask),
            _zero_mask_cotangent(kv_mask))


_flash_attention_vjp.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _flash_forward_only(q, k, v, q_mask, kv_mask, lengths, causal, block_q,
                        block_k, interpret, native, window):
    return _flash_forward(q, k, v, q_mask, kv_mask, causal, block_q,
                          block_k, interpret, native, window, lengths)[0]


def _forward_only_fwd_rule(q, k, v, q_mask, kv_mask, lengths, causal,
                           block_q, block_k, interpret, native, window):
    raise NotImplementedError(
        f"flash_attention is forward only with native=True, with a sliding "
        f"window, with lengths or with values of another width than the "
        f"keys (native={native}, window={window}, lengths "
        f"{'given' if lengths is not None else 'not given'}, keys of "
        f"{k.shape[3]}, values of {v.shape[3]}): the backward kernels take "
        f"float32 products, equal widths, no band and the whole grid. "
        f"Differentiate through native=False at equal widths without a "
        f"window or lengths, or through a dense attention")


_flash_forward_only.defvjp(_forward_only_fwd_rule, lambda *_: None)


def flash_attention(q, k, v, causal=False, block_q=128, block_k=128,
                    interpret=None, mask=None, kv_mask=None, native=False,
                    window=None, lengths=None):
    """Fused attention: softmax(QKᵀ/√d)·V without materialising (Tq,Tk).

    Pallas on TPU (interpret-mode elsewhere); differentiable — backward is
    the Pallas dQ / dK-dV kernel pair (flash-attention-2 style recompute
    from the saved logsumexp), O(T) HBM in both directions. The q and k
    tilings are independent, so CROSS-attention (Tq ≠ Tk) uses the same
    kernels.

    Masks for padded batches:
    - self-attention: pass `mask` (B, T) — a False position is invalid as
      both key and query; its keys are excluded from every softmax and its
      output rows come back as zeros, matching a masked dense attention
      whose padded rows are zeroed.
    - cross-attention: pass `kv_mask` (B, Tk) for key/value padding and
      optionally `mask` (B, Tq) for query-row padding.
    Gradients flow to q/k/v only at valid positions.

    FORWARD ONLY in two cases, which serving's prefill uses: V narrower or
    wider than Q and K (latent attention's expanded form: keys of 192,
    values of 128; the scale is the keys'), and `native=True`, where both
    products take their operands as they come (bfloat16 through the MXU in
    one pass, float32 sums) instead of as float32. A gradient taken through
    either raises. `native` is a TEMPORARY fork (PERF.md section 7, ROADMAP
    S10): it exists so that the other callers' programs stay as they were
    measured, not because two numerics are wanted.

    `window` (an int, with `causal`): sliding-window attention, query t
    over keys `t - window < s <= t`. The grid's k axis runs over the band's
    tiles only: a K/V tile wholly below the band is neither fetched nor
    computed, so a prompt of T costs about `T x (window + block)` pairs and
    not `T^2 / 2`. Forward only, without masks. Without it the call is the
    one it was.

    `lengths` (an int32 (B,), with `causal`, with or without `window`):
    the rows each example really holds, the rest padding. The grid runs
    over the (q tile, k tile) pairs in use only: a q tile holding a row
    below its length, with its k tiles up to the diagonal or over its
    band. No step exists above the diagonal, below the band or in a q tile
    wholly past the length, so nothing is fetched or computed there; such
    a q tile's rows come back as exact zeros. Every other row is what the
    call without `lengths` gives, to the bit: the same tiles in the same
    order. Forward only, without masks. Without it the call is the one it
    was.
    """
    tq, tk = q.shape[2], k.shape[2]
    if lengths is not None:
        if not causal or tq != tk or mask is not None or kv_mask is not None \
                or jnp.shape(lengths) != (q.shape[0],):
            raise ValueError(f"lengths needs causal=True over Tq == Tk, no "
                             f"masks and one length an example, got lengths "
                             f"of shape {jnp.shape(lengths)} for q "
                             f"{q.shape}")
    if window is not None:
        if not causal or tq != tk or int(window) < 1 or mask is not None \
                or kv_mask is not None:
            raise ValueError(f"window={window!r} needs causal=True over "
                             f"Tq == Tk, a positive width and no masks")
        window = int(window)
    if window is not None or lengths is not None:
        return _flash_forward_only(q, k, v, None, None, lengths, True,
                                   block_q, block_k, interpret, native,
                                   window)
    if causal and tq != tk:
        raise ValueError(
            f"causal flash attention requires Tq == Tk, got {tq} != {tk}")
    if mask is not None and mask.ndim != 2:
        raise ValueError(f"mask must be (batch, seq), got {mask.shape}")
    if kv_mask is not None and kv_mask.ndim != 2:
        raise ValueError(
            f"kv_mask must be (batch, kv_seq), got {kv_mask.shape}")
    if kv_mask is None:
        if mask is not None and tq != tk:
            raise ValueError(
                "a single (B, T) mask implies self-attention (Tq == Tk); "
                f"got Tq={tq}, Tk={tk} — pass kv_mask for cross-attention")
        kv_mask = mask
    if mask is not None and mask.shape[1] != tq:
        raise ValueError(
            f"query mask length {mask.shape[1]} != Tq {tq}")
    if kv_mask is not None and kv_mask.shape[1] != tk:
        raise ValueError(
            f"kv_mask length {kv_mask.shape[1]} != Tk {tk}")
    if native or v.shape[3] != q.shape[3]:
        return _flash_forward_only(q, k, v, mask, kv_mask, None, causal,
                                   block_q, block_k, interpret, native, None)
    return _flash_attention_vjp(q, k, v, mask, kv_mask, causal, block_q,
                                block_k, interpret)


# ---------------------------------------------------------------------------
# prefill under a row-by-row selection (learned sparse attention)
# ---------------------------------------------------------------------------
def _selected_kernel(q_ref, k_ref, v_ref, s_ref, o_ref, acc_ref, l_ref, m_ref,
                     *, scale, group, head_dim, q_offset):
    """Grid (KV head, q_tiles, k_tiles), k innermost: the `group` query
    heads of one KV head, stacked as rows (group·block_q, D), against one
    (block_k, D) tile of that head's keys and values, under ONE (block_q,
    block_k) tile of the selection that every head shares. The online
    softmax of `_flash_fwd_kernel`; a k tile wholly above the diagonal is
    skipped (query row t sits at position `q_offset + t`)."""
    i, j = pl.program_id(1), pl.program_id(2)
    bq, bk = s_ref.shape
    d = head_dim

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        l_ref[...] = jnp.zeros_like(l_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)

    @pl.when(j * bk <= q_offset + (i + 1) * bq - 1)
    def _compute():
        q = jnp.concatenate([q_ref[:, g * d:(g + 1) * d]
                             for g in range(group)], axis=0)
        s = jax.lax.dot_general(
            q, k_ref[...], dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        sel = s_ref[...].astype(jnp.float32)
        keep = jnp.concatenate([sel] * group, axis=0) > 0
        s = jnp.where(keep, s, _NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # (a row with nothing kept in any tile so far has m = -1e30 and
        # exp(0) = 1 for every entry: the select drops them)
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        v = v_ref[...]
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        o = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        for g in range(group):
            o_ref[:, g * d:(g + 1) * d] = \
                o[g * bq:(g + 1) * bq].astype(o_ref.dtype)


def _selected_dense(q, k, v, selected, hkv):
    """The same attention as one masked softmax a head, in XLA."""
    tq, tk = selected.shape
    d = k.shape[1] // hkv
    qh = q.reshape(tq, hkv, -1, d).astype(jnp.float32)
    kh, vh = (a.reshape(tk, hkv, d).astype(jnp.float32) for a in (k, v))
    s = jnp.einsum("qhgd,khd->hgqk", qh, kh) / (d ** 0.5)
    p = jax.nn.softmax(jnp.where(selected > 0, s, -jnp.inf), axis=-1)
    return jnp.einsum("hgqk,khd->qhgd", p, vh).reshape(q.shape).astype(
        q.dtype)


def flash_attention_selected(q, k, v, selected, num_kv_heads, q_offset=0,
                             impl="auto", block_q=128, block_k=512,
                             interpret=None):
    """Grouped-query attention of a block of queries over the key rows a
    selection marks for each of them, row by row (`flash_attention` takes
    one mask a sequence): what a sparse-attention indexer leaves of a
    causal prefill. Forward only.

    - q (Tq, Hq·D): the queries, heads side by side along the lanes; query
      t sits at position `q_offset + t` (static), and `selected` marks no
      key past it
    - k, v (Tk, Hkv·D): the sequence's key and value rows, as a decode
      cache leaf holds them; query head i reads KV head i // (Hq / Hkv)
    - selected (Tq, Tk) int8 (or bool): nonzero where query t attends key
      s; every row marks at least one key
    - impl: 'auto' (the kernel on a TPU, XLA elsewhere), 'pallas'
      (interpreted off the TPU unless `interpret` says otherwise), 'dense'

    Returns (Tq, Hq·D). Products in the operands' dtype with float32 sums
    and a float32 softmax; the weights go to the values' dtype for the
    second product."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "dense"
    if impl == "dense":
        return _selected_dense(q, k, v, selected, num_kv_heads)
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r}; expected 'auto', "
                         f"'pallas' or 'dense'")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    tq, tk = selected.shape
    d = k.shape[1] // num_kv_heads
    group = q.shape[1] // (num_kv_heads * d)
    bq, bk = min(block_q, tq), min(block_k, tk)
    selected = selected.astype(jnp.int8)
    if tq % bq or tk % bk:
        q = _pad_to(q, 0, bq)
        k, v = _pad_to(k, 0, bk), _pad_to(v, 0, bk)
        selected = _pad_to(_pad_to(selected, 0, bq), 1, bk)

    def kv_tile(h, i, j):
        # a tile above the diagonal is not computed: name the last one
        # that was, so that nothing is fetched for it
        return jnp.minimum(j, (q_offset + (i + 1) * bq - 1) // bk), h

    out = pl.pallas_call(
        functools.partial(_selected_kernel, scale=1.0 / (d ** 0.5),
                          group=group, head_dim=d, q_offset=int(q_offset)),
        grid=(num_kv_heads, q.shape[0] // bq, k.shape[0] // bk),
        in_specs=[
            pl.BlockSpec((bq, group * d), lambda h, i, j: (i, h)),
            pl.BlockSpec((bk, d), kv_tile),
            pl.BlockSpec((bk, d), kv_tile),
            pl.BlockSpec((bq, bk),
                         lambda h, i, j: (i, kv_tile(h, i, j)[0])),
        ],
        out_specs=pl.BlockSpec((bq, group * d), lambda h, i, j: (i, h)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group * bq, d), jnp.float32),
            pltpu.VMEM((group * bq, 1), jnp.float32),
            pltpu.VMEM((group * bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name="flash_selected",
    )(q, k, v, selected)
    return out[:tq]


# ---------------------------------------------------------------------------
# decode: one query token (or a draft block) against a cached K/V
#
# Cache operands are ROWS MAJOR, HIDDEN MINOR: (B, C, H·D) — the layout
# the decode cache keeps on the device (generation/decode.py). Its minor
# dimension is the model's hidden width, a whole number of 128-lane
# tiles, so the row write, the kernel and the donated state all take the
# array as it lies: nothing re-lays the cache between them.
# ---------------------------------------------------------------------------
def _split_heads(cache, h):
    """(B, C, H·D) -> (B, C, H, D): the einsum paths' view of a cache."""
    b, c, hd = cache.shape
    return cache.reshape(b, c, h, hd // h)


def _check_cache_operands(q, k_cache, v_cache):
    """q is (B, Hq, Tq, D); the caches must match as (B, C, H·D), H the KV
    heads, with Hq a multiple of H: query head i reads the lanes of KV
    head i // (Hq / H). Returns H."""
    if k_cache.shape != v_cache.shape or k_cache.ndim != 3:
        raise ValueError(
            f"k_cache/v_cache must match as (B, C, H·D): "
            f"{k_cache.shape} vs {v_cache.shape}")
    b, hq, _, d = q.shape
    h, rest = divmod(k_cache.shape[2], d)
    if k_cache.shape[0] != b or rest or h < 1 or hq % h:
        raise ValueError(
            f"k_cache/v_cache must be (B, C, H·D) = ({b}, C, H·{d}) with "
            f"H KV heads dividing the {hq} query heads of q {q.shape}, "
            f"got {k_cache.shape}")
    return h


def _masked_attend(q, k_cache, v_cache, valid, k_scale=None, v_scale=None):
    """The einsum masked softmax every non-kernel decode path runs:
    softmax(q·Kᵀ/√d)·V over the VALID cache rows of each query.

    - q (B, Hq, Tq, D); k_cache / v_cache (B, C, Hkv·D), Hq a multiple of
      Hkv: the query heads of one KV head's group go through as more query
      rows of that head (with Hq = Hkv nothing is moved)
    - valid (B, Tq, C) bool
    - k_scale / v_scale: (B, C, Hkv) float32 row scales of an int8 cache,
      folded INTO the contractions: the key scale multiplies the score
      logits (s·(k_row·ks) = (s·k_row)·ks), the value scale folds onto
      the softmax weights before the value pass — no dequantized copy of
      the cache materializes and the cache reads stay int8.
    Queries with no valid row come back zeroed (the kernel's
    empty-softmax convention)."""
    b, hq, tq, d = q.shape
    h = k_cache.shape[2] // d
    group = hq // h
    if group > 1:
        q = q.reshape(b, h, group * tq, d)
        valid = jnp.tile(valid, (1, group, 1))
    scale = 1.0 / (d ** 0.5)
    s = jnp.einsum("bhqd,bchd->bhqc", q.astype(jnp.float32),
                   _split_heads(k_cache, h).astype(jnp.float32)) * scale
    if k_scale is not None:
        s = s * k_scale.astype(jnp.float32).transpose(0, 2, 1)[:, :, None]
    s = jnp.where(valid[:, None, :, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if v_scale is not None:
        p = p * v_scale.astype(jnp.float32).transpose(0, 2, 1)[:, :, None]
    out = jnp.einsum("bhqc,bchd->bhqd", p,
                     _split_heads(v_cache, h).astype(jnp.float32)
                     ).astype(q.dtype)
    any_valid = valid.any(axis=-1)                    # (B, Tq)
    return jnp.where(any_valid[:, None, :, None], out, 0).reshape(
        b, hq, tq, d)


def _flash_decode_kernel(*refs, scale, head_dim, group, ragged=False):
    """Grid (B, k_tiles), k innermost: one slot's single query row against
    a (block_k, Hkv·D) tile of its cache rows, read IN PLACE — every head
    of the slot in one grid step.

    The query becomes a block-diagonal (Hp, Hkv·D) operand (row i holds
    query head i's D lanes at the lanes of ITS KV head, i // group, zeros
    elsewhere), so `_flash_fwd_kernel`'s own arithmetic — q·Kᵀ, the masked
    online softmax with float32 o/l/m scratch across the k tiles, p·V —
    runs once for all heads: scores (Hp, block_k), accumulator (Hp,
    Hkv·D). Head i's output is the (1, D) block of accumulator row i at
    its KV head's lanes.

    With `group` 1 (as many cache heads as query heads) the query comes in
    as one (1, H·D) row, broadcast over the rows, and the last k step
    folds the diagonal blocks back into one (1, H·D) row. With a larger
    group the query heads come in as the rows of a (Hp, D) block, repeated
    along the lanes once a KV head, and go out the same way.

    `ragged`: the grid is ONE axis over the tiles in use, slot by slot
    (`_tiles_in_use`), whose three vectors come first as scalar-prefetch
    operands: a grid step's slot, its tile of that slot, and a slot's
    tiles in use. A tile wholly past its slot's length has no grid step,
    so it is neither fetched nor computed nor paid a step for. The mask
    hides every row at or past the length already
    (`flash_attention_decode` sees to it), so the grid changes the time
    and nothing else."""
    if ragged:
        slot_ref, tile_ref, used_ref, *refs = refs
        step = pl.program_id(0)
        kj = tile_ref[step]
    else:
        kj = pl.program_id(1)
    q_ref, k_ref, v_ref, km_ref, o_ref, acc_ref, l_ref, m_ref = refs
    hp, hd = acc_ref.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (hp, hd), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (hp, hd), 1)
    if group > 1:
        row = jax.lax.div(row, group)             # the row's KV head
    own = (lane >= row * head_dim) & (lane < (row + 1) * head_dim)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        l_ref[...] = jnp.zeros_like(l_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)

    q = q_ref[0].astype(jnp.float32) * scale
    if group > 1:
        q = jnp.concatenate([q] * (hd // head_dim), axis=1)
    q = jnp.where(own, q, 0.0)
    s = jax.lax.dot_general(
        q, k_ref[0].astype(jnp.float32),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)       # (Hp, block_k)
    s = jnp.where(km_ref[0] > 0, s, _NEG_INF)     # (1, block_k) mask
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v_ref[0].astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)       # (Hp, Hkv·D)
    last = (used_ref[slot_ref[step]] if ragged else pl.num_programs(1)) - 1

    @pl.when(kj == last)
    def _finalize():
        o = jnp.where(own, acc_ref[...] / jnp.maximum(l_ref[...], 1e-30),
                      0.0)
        if group > 1:
            out = o[:, :head_dim]
            for h in range(1, hd // head_dim):
                out = out + o[:, h * head_dim:(h + 1) * head_dim]
            o_ref[0] = out.astype(o_ref.dtype)
        else:
            o_ref[0] = jnp.sum(o, axis=0, keepdims=True).astype(o_ref.dtype)


#: tiles a decode call with `lengths` may read its rung in, largest first,
#: the bytes of K one may hold, and the tiles a rung has at least. A slot
#: reads its rows in use rounded UP to a tile, so a large tile reads rows
#: past the length and a small one pays a grid step's fixed part more
#: often. Measured on a v5e, device ms a call (`PERF.md` PR 38,
#: `scripts/sweep_decode_tiles.py`): 32 slots of a `(18432, 512)` bfloat16
#: leaf, 8.9k rows in use a slot: 0.800, 0.822, 0.862 at 512, 1024, 2048
#: rows (1.597 the whole rung); 1024 stays, the tile `mla_decode` shares
#: through `latent_tile_positions` and measured best for itself. 64 slots
#: of a `(512, 768)` float32 leaf, 121 rows in use a slot (BERT-base's
#: decode): 0.106, 0.139, 0.268 at 128, 256, 512 rows, and in bfloat16
#: 0.076, 0.075, 0.135: on a short rung half a tile of rounding is most of
#: what a slot reads, hence the share of the rung. 64 rows cannot be: the
#: mask's block would be half a lane tile
_RAGGED_TILES = (2048, 1024, 512, 256, 128)
_RAGGED_TILE_BYTES = 1 << 20
_RAGGED_RUNG_TILES = 4


def decode_tile_rows(rung, lanes, dtype):
    """Cache rows a grid step of `flash_attention_decode(..., lengths=)`
    reads, from the call's shapes alone: the largest of `_RAGGED_TILES`
    that divides the rung, whose K tile holds at most `_RAGGED_TILE_BYTES`
    and of which the rung holds `_RAGGED_RUNG_TILES` or more (a slot reads
    its rows in use rounded UP to a tile, so the tile is held to a share of
    what a slot may hold); a rung none of them fits is one whole tile."""
    row = lanes * jnp.dtype(dtype).itemsize
    fits = [t for t in _RAGGED_TILES
            if rung % t == 0 and t * row <= _RAGGED_TILE_BYTES
            and t * _RAGGED_RUNG_TILES <= rung]
    return fits[0] if fits else rung


def _tiles_in_use(lengths, block_k, tiles):
    """The ragged decode grid, from the slots' `lengths` (B,) over a rung of
    `tiles` tiles of `block_k` rows: a slot takes the tiles that hold its
    rows in use, and at least one (its output block is written on its last
    step). -> for every grid step there may be, `B x tiles` of them, its
    slot and its tile of that slot; a slot's tiles in use (B,); and the
    grid's extent, their sum. Steps at or past the extent are never run."""
    used = jnp.clip(-(-lengths // block_k), 1, tiles).astype(jnp.int32)
    ends = jnp.cumsum(used)
    step = jnp.arange(lengths.shape[0] * tiles, dtype=jnp.int32)
    slot = jnp.minimum((step[:, None] >= ends[None, :]).sum(
        axis=1, dtype=jnp.int32), lengths.shape[0] - 1)
    return slot, step - (ends - used)[slot], used, ends[-1]


def _flash_decode(q, k_cache, v_cache, cache_mask, block_k, interpret,
                  lengths=None):
    """q (B, Hq, 1, D) against (B, C, Hkv·D) caches through the Pallas
    kernel; returns (B, Hq, 1, D). The caches go in as they are: no pad,
    no reshape — a rung the k tile does not divide is one whole tile.
    Without `lengths` the grid is (slot, k tile) over the whole rung. With
    `lengths` (B,) int32, past which the mask holds nothing, it is one
    axis over the tiles in use (`_tiles_in_use`), its extent read from the
    lengths when the call runs, and the step's slot and tile go in first,
    as scalar-prefetch operands (`_flash_decode_kernel`)."""
    b, h, _, d = q.shape
    c, hd = k_cache.shape[1], k_cache.shape[2]
    group = h // (hd // d)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if c % block_k:
        block_k = c
    hp = -(-h // 8) * 8            # float32 sublane tile of the scores
    ragged = lengths is not None
    if ragged:
        slot, tile, used, extent = _tiles_in_use(lengths, block_k,
                                                 c // block_k)
        grid, semantics = (extent,), ("arbitrary",)
        prefetch = (slot, tile, used)

        def tile_at(g, slot_ref, tile_ref, _):
            return slot_ref[g], tile_ref[g]
    else:
        grid, semantics = (b, c // block_k), ("parallel", "arbitrary")
        prefetch = ()

        def tile_at(i, j):
            return i, j

    def row_at(*at):
        return tile_at(*at)[0], 0, 0

    def mask_at(*at):
        i, j = tile_at(*at)
        return i, 0, j

    cache_spec = pl.BlockSpec((1, block_k, hd),
                              lambda *at: (*tile_at(*at), 0))
    if group > 1:                  # the query heads as rows, padded to Hp
        q_rows = jnp.pad(q[:, :, 0, :], ((0, 0), (0, hp - h), (0, 0)))
        row_spec = pl.BlockSpec((1, hp, d), row_at)
        out_shape = (b, hp, d)
    else:
        q_rows = q.reshape(b, 1, hd)
        row_spec = pl.BlockSpec((1, 1, hd), row_at)
        out_shape = (b, 1, hd)
    spec = dict(
        grid=grid,
        in_specs=[row_spec, cache_spec, cache_spec,
                  pl.BlockSpec((1, 1, block_k), mask_at)],
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((hp, hd), jnp.float32),
            pltpu.VMEM((hp, 1), jnp.float32),
            pltpu.VMEM((hp, 1), jnp.float32),
        ])
    limits = {}
    if ragged:
        spec = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), **spec))
        # the K and the V tile twice (the pipeline's two buffers) and their
        # float32 images in the body; twice that for the rest and for room
        vmem = 2 * block_k * hd * (4 * k_cache.dtype.itemsize + 8)
        limits = dict(vmem_limit_bytes=min(max(vmem, 32 << 20), 100 << 20))
    out = pl.pallas_call(
        functools.partial(_flash_decode_kernel, scale=1.0 / (d ** 0.5),
                          head_dim=d, group=group, ragged=ragged),
        **spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, **limits),
        interpret=interpret,
        name="flash_fwd",
    )(*prefetch, q_rows, k_cache, v_cache,
      cache_mask.astype(jnp.int32)[:, None, :])
    if group > 1:
        out = out[:, :h]
    # a slot with NO valid cache row has no defined softmax: zeros
    any_valid = cache_mask.astype(bool).any(axis=1)
    return jnp.where(any_valid[:, None, None], out, 0).reshape(b, h, 1, d)


@jax.named_scope("flash_decode")
def flash_attention_decode_mq(q, k_cache, v_cache, q_mask, impl="auto"):
    """Multi-query decode attention: a DRAFT block of queries per
    sequence attends the cached K/V under a per-query validity mask.

    The greedy-drafting verification primitive (generation/): the host
    proposes `d-1` draft tokens, the decode loop runs the q-block
    `[current, draft_0, ..., draft_{d-2}]` through the model in ONE
    dispatch, and each query j may only see cache rows written at or
    before its own position — a causal pattern offset into the cache,
    expressed as the explicit per-query mask `q_mask[b, j, c]` (row c
    valid for query j). Amortizes the per-token dispatch exactly like
    the superstep, but with the verification semantics drafting needs.

    - q: (B, H, Tq, D) — the draft-block queries (Tq = block length)
    - k_cache / v_cache: (B, C, H·D) — rows major, hidden minor
    - q_mask: (B, Tq, C) truthy — valid cache rows PER QUERY (ragged
      slots and the intra-block causal offset in one mask)
    - impl: 'auto'/'dense' run the einsum contraction; 'pallas' is
      rejected — the streaming-softmax kernel has no per-query ragged
      mask slot yet, and the draft block is tiny (d ≤ ~8), so the
      (B, H, d, C) score tensor is far below kernel-worthy size.
    Forward-only. Queries with NO valid cache row return zeros
    (matching `flash_attention_decode`'s empty-softmax convention).
    """
    if q.ndim != 4:
        raise ValueError(f"q must be (B, H, Tq, D), got {q.shape}")
    _check_cache_operands(q, k_cache, v_cache)
    expect = (q.shape[0], q.shape[2], k_cache.shape[1])
    if tuple(q_mask.shape) != expect:
        raise ValueError(
            f"q_mask must be (B, Tq, C) = {expect}, got {q_mask.shape}")
    if impl == "pallas":
        raise ValueError(
            "impl='pallas' has no multi-query ragged-mask variant — "
            "the draft q-block runs the einsum path on every backend")
    if impl not in ("auto", "dense"):
        raise ValueError(
            f"unknown decode impl {impl!r}; expected 'auto', 'pallas' "
            "or 'dense'")
    return _masked_attend(q, k_cache, v_cache, q_mask.astype(bool))


@jax.named_scope("flash_decode")
def flash_attention_decode(q1, k_cache, v_cache, cache_mask, impl="auto",
                           block_k=None, interpret=None, k_scale=None,
                           v_scale=None, lengths=None):
    """Incremental-decode attention: a SINGLE query block per sequence
    attends over that sequence's cached K/V under a cache-validity mask.

    The KV-cache serving hot path (generation/): at decode step t the
    cache holds keys/values for positions 0..t (the current token's K/V
    already written), `cache_mask` marks which cache rows are real
    (ragged per sequence — slots in a continuous batch sit at different
    positions), and the query is the current token only. O(C·D) HBM
    per step instead of the O(T²) full-sequence re-forward.

    - q1: (B, Hq, D) or (B, Hq, 1, D) — current-token query
    - k_cache / v_cache: (B, C, Hkv·D) — rolling caches, rows major and
      the hidden width minor (C = cache rung): one decode-cache leaf as
      a decoder's `init_cache` lays it out, read in place. Hq is a
      multiple of Hkv (grouped-query attention): query head i reads the
      lanes of KV head i // (Hq / Hkv); Hq = Hkv is plain multi-head
    - cache_mask: (B, C) truthy — valid cache rows (ragged lengths)
    - impl: 'auto' (Pallas kernel on TPU, einsum elsewhere), 'pallas'
      (force kernel; interpret-mode off-TPU), or 'dense'
    - block_k: cache rows a kernel grid step reads (a rung it does not
      divide is read as one tile); by default 512, and with `lengths`
      what `decode_tile_rows` gives for the call's shapes: 1024 rows of
      a long bfloat16 rung, a quarter of a short one
    - k_scale / v_scale: (B, C, Hkv) float32 per-head row scales of an
      int8-quantized cache (quantize/kvcache.py). When given, the
      dequant happens INSIDE the attention contractions — the single-
      query decode pass is a bandwidth-bound GEMV, so reading the
      cache at int8 width is the point; a materializing dequant would
      give the traffic straight back. (The quantized path is einsum-
      based on every backend: the scales fold onto logits/softmax
      weights, which the Pallas fp kernel's streaming-softmax layout
      has no slot for yet.)
    - lengths: (B,) int32, optional: rows 0..lengths[b] - 1 of slot b are
      in use. A row at or past its slot's length is masked whatever
      `cache_mask` says, and the kernel's grid holds the tiles in use
      and no other (`_tiles_in_use`): a slot costs its rows in use,
      rounded up to a tile, not its rung — a decoder's slots part-way
      up a rung, or a selection mask over a long one (the rows a
      sparse-attention indexer keeps). `lengths[b] == 0` gives zeros.
      Without it the kernel reads every slot's whole rung, the call it
      was (not given with an int8 cache)
    Forward-only (decode never backprops). Rows whose mask has NO valid
    cache entry return zeros. Returns the same rank as q1.
    """
    squeeze = q1.ndim == 3
    q = q1[:, :, None, :] if squeeze else q1
    if q.ndim != 4 or q.shape[2] != 1:
        raise ValueError(
            f"q1 must be (B, H, D) or (B, H, 1, D), got {q1.shape}")
    hkv = _check_cache_operands(q, k_cache, v_cache)
    if cache_mask.shape != (q.shape[0], k_cache.shape[1]):
        raise ValueError(
            f"cache_mask must be (B, C) = "
            f"{(q.shape[0], k_cache.shape[1])}, got {cache_mask.shape}")
    if impl not in ("auto", "pallas", "dense"):
        raise ValueError(
            f"unknown decode impl {impl!r}; expected 'auto', 'pallas' "
            "or 'dense'")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    if lengths is not None and (lengths.shape != q.shape[:1]
                                or k_scale is not None):
        raise ValueError(
            f"lengths must be (B,) = {q.shape[:1]}, over a cache without "
            f"row scales, got {lengths.shape}")
    if k_scale is not None:
        if impl == "pallas":
            raise ValueError(
                "impl='pallas' has no int8-cache variant (the "
                "streaming-softmax kernel has no slot for per-row "
                "scales yet) — use 'auto' or 'dense' with a "
                "quantized cache")
        expect = (q.shape[0], k_cache.shape[1], hkv)
        if tuple(k_scale.shape) != expect \
                or tuple(v_scale.shape) != expect:
            raise ValueError(
                f"k_scale/v_scale must be (B, C, H) = {expect}, got "
                f"{k_scale.shape} / {v_scale.shape}")
        impl = "dense"
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "dense"
    if lengths is not None:       # one contract for both impls
        cache_mask = cache_mask.astype(bool) & (
            jnp.arange(k_cache.shape[1])[None, :] < lengths[:, None])
    if impl == "dense":
        out = _masked_attend(q, k_cache, v_cache,
                             cache_mask.astype(bool)[:, None, :],
                             k_scale, v_scale)
    else:
        if block_k is None:
            block_k = 512 if lengths is None else decode_tile_rows(
                *k_cache.shape[1:], k_cache.dtype)
        out = _flash_decode(q, k_cache, v_cache, cache_mask, block_k,
                            interpret, lengths)
    return out[:, :, 0, :] if squeeze else out


# ---------------------------------------------------------------------------
# paged decode: attention reading a pooled KV through a per-slot page index
# ---------------------------------------------------------------------------
def gather_kv_pages(pool, page_table):
    """Materialize the per-slot contiguous cache VIEW from a paged pool.

    - pool: (P, ps, W) — one layer's page pool: P physical pages of `ps`
      rows each, rows major like the dense cache (W = H·D for K/V pages,
      W = H for the int8 pool's per-row scale pages); page 0 is the
      null/scratch page by convention
    - page_table: (B, n) int32 — physical page id per (slot, logical
      page); unmapped entries point at page 0 and are hidden by the
      caller's cache mask
    Returns (B, n·ps, W) — bit-identical to the slot-contiguous cache
    layout, so the existing masked-softmax decode arithmetic (and
    therefore token streams) carries over unchanged. Pages and rows are
    both major dimensions, so the view is a gather and a free reshape.
    """
    if pool.ndim != 3:
        raise ValueError(f"pool must be (P, ps, W), got {pool.shape}")
    if page_table.ndim != 2:
        raise ValueError(
            f"page_table must be (B, n_pages), got {page_table.shape}")
    b, n = page_table.shape
    _, ps, w = pool.shape
    return jnp.take(pool, page_table, axis=0).reshape(b, n * ps, w)


def flash_attention_decode_paged(q1, k_pool, v_pool, page_table,
                                 cache_mask, impl="auto", block_k=512,
                                 interpret=None, k_scale_pool=None,
                                 v_scale_pool=None):
    """`flash_attention_decode` generalized to gather-by-page: the query
    attends a (B, C, H·D) view gathered from a device-resident page
    pool through the per-slot page index, C = n_pages·ps.

    Pages let ragged sequences pay for the rows they use instead of a
    worst-case rung (µ-cuDNN's fixed-block thesis applied to cache
    memory), and let identical prompt prefixes share physical pages.
    The gather feeds the UNCHANGED masked-softmax machinery — einsum
    reference, Pallas kernel, and the int8 scale-folding path all see
    the same (B, C, H·D) operands as the slot-contiguous layout, so
    streams stay bit-identical.

    - q1: (B, H, D) or (B, H, 1, D)
    - k_pool / v_pool: (P, ps, H·D) — pooled pages (int8 under
      `kv_dtype="int8"`, halving page bytes)
    - page_table: (B, n_pages) int32 physical page ids
    - cache_mask: (B, n_pages·ps) — valid ROWS of the gathered view
    - k_scale_pool / v_scale_pool: (P, ps, H) float32 scales of an
      int8 pool; folded inside the contractions as in the contiguous
      path
    """
    if k_pool.shape != v_pool.shape or k_pool.ndim != 3:
        raise ValueError(
            f"k_pool/v_pool must match as (P, ps, H·D): "
            f"{k_pool.shape} vs {v_pool.shape}")
    if (k_scale_pool is None) != (v_scale_pool is None):
        raise ValueError(
            "k_scale_pool and v_scale_pool must be given together")
    ks = vs = None
    with jax.named_scope("flash_decode"):    # the gathers are its work
        kc = gather_kv_pages(k_pool, page_table)
        vc = gather_kv_pages(v_pool, page_table)
        if k_scale_pool is not None:
            ks = gather_kv_pages(k_scale_pool, page_table)
            vs = gather_kv_pages(v_scale_pool, page_table)
    return flash_attention_decode(q1, kc, vc, cache_mask, impl=impl,
                                  block_k=block_k, interpret=interpret,
                                  k_scale=ks, v_scale=vs)


def flash_attention_decode_mq_paged(q, k_pool, v_pool, page_table,
                                    q_mask, impl="auto"):
    """`flash_attention_decode_mq` through the page index: the drafting
    verify dispatch reads the SAME paged pool as the superstep scan, so
    every decode mode inherits paging from one gather. Operands as in
    `flash_attention_decode_mq` with (k_pool, v_pool, page_table) in
    place of the contiguous caches."""
    if k_pool.shape != v_pool.shape or k_pool.ndim != 3:
        raise ValueError(
            f"k_pool/v_pool must match as (P, ps, H·D): "
            f"{k_pool.shape} vs {v_pool.shape}")
    with jax.named_scope("flash_decode"):    # the gathers are its work
        kc = gather_kv_pages(k_pool, page_table)
        vc = gather_kv_pages(v_pool, page_table)
    return flash_attention_decode_mq(q, kc, vc, q_mask, impl=impl)
